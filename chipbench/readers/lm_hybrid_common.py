"""What the ``lm_hybrid_*`` readers share beside ``lm_common``: the
token-layers the recurrence computed in one step, from the program's
counter."""

from chipbench.readers import lm_common

COUNTER = "ps_lm_kda_scan_tokens_total"


def scan_token_layers_per_step(ctx: dict):
    """Tokens x KDA layers of one step (forward count): the window's
    growth of ``ps_lm_kda_scan_tokens_total`` over the window's tokens,
    times a step's tokens. None where the program has no such counter
    (a commit before the layer) or counted nothing."""
    if COUNTER not in ctx["after"]:
        return None
    tokens = lm_common.counter_growth(ctx, "ps_lm_tokens_total")
    counted = lm_common.counter_growth(ctx, COUNTER)
    if tokens <= 0 or counted <= 0:
        return None
    lm = ctx["lm"]
    return counted / tokens * lm["seq_len"] * lm["sequences"]

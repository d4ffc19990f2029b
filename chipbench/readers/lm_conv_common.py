"""What the ``lm_conv_*`` readers share beside ``lm_common`` and
``lm_swa_common``: the token-layers of the gated short convolutions one
step ran, from the program's counter."""

from chipbench.readers import lm_common

COUNTER = "ps_lm_conv_token_layers_total"


def conv_token_layers_per_step(ctx: dict):
    """Tokens x convolution layers of one step (forward count): the
    window's growth of ``ps_lm_conv_token_layers_total`` over the
    window's tokens, times a step's tokens. None where the program has
    no such counter (a commit before the layer) or counted nothing."""
    if COUNTER not in ctx["after"]:
        return None
    tokens = lm_common.counter_growth(ctx, "ps_lm_tokens_total")
    counted = lm_common.counter_growth(ctx, COUNTER)
    if tokens <= 0 or counted <= 0:
        return None
    lm = ctx["lm"]
    return counted / tokens * lm["seq_len"] * lm["sequences"]

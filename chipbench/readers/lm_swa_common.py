"""What the ``lm_swa_*`` readers share beside ``lm_common``: the
token-layers of attention one step ran, by kind of layer, from the
program's counter."""

from chipbench.readers import lm_common

COUNTER = "ps_lm_attention_token_layers_total"


def token_layers_per_step(ctx: dict):
    """``{"window": ., "full": .}``: tokens x layers of each kind of one
    step (forward count): the window's growth of each series of
    ``ps_lm_attention_token_layers_total`` over the window's tokens,
    times a step's tokens. None where the program has no such counter
    (a commit before the layers) or counted nothing."""
    if COUNTER not in ctx["after"]:
        return None
    tokens = lm_common.counter_growth(ctx, "ps_lm_tokens_total")
    if tokens <= 0:
        return None

    def by_kind(state):
        return {
            s["labels"].get("kind"): s.get("value", 0.0)
            for s in state.get(COUNTER, {}).get("series", [])
        }

    before, after = by_kind(ctx["before"]), by_kind(ctx["after"])
    lm = ctx["lm"]
    a_step = lm["seq_len"] * lm["sequences"] / tokens
    out = {
        kind: (after.get(kind, 0.0) - before.get(kind, 0.0)) * a_step
        for kind in ("window", "full")
    }
    return out if sum(out.values()) > 0 else None

"""What the ``lm_afmoe_*`` readers share beside ``lm_common`` and
``lm_swa_common``: the gated token-layers one step ran, by kind of
layer, from the program's counter."""

from chipbench.readers import lm_common

COUNTER = "ps_lm_attention_gate_token_layers_total"


def gate_token_layers_per_step(ctx: dict):
    """``{"window": ., "full": .}``: tokens x gated layers of each kind
    of one step (forward count), as ``lm_swa_common`` reads the
    attention's. None where the program has no such counter (a commit
    before the count) or counted nothing."""
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

"""Share of the chip's bf16 peak that a training step's model work is, in
percent, for a model of gated delta-rule and softmax layers:
``lm_hybrid_arith.step_model_flops`` (6 x matmul weights a token meets x
tokens, the routed experts by the rows ``ps_lm_expert_rows_total`` says
they computed, causal attention in the softmax layers, the recurrence by
the token-layers ``ps_lm_kda_scan_tokens_total`` counted; no
recomputation) over the peak of ``peaks.json`` times the step program's
device time (whole events of the module with the most device time, as
``step_device_ms`` reads it).
"""

from chipbench import arith, lm_hybrid_arith
from chipbench.readers import lm_common, lm_hybrid_common


def read(ctx: dict, spec: dict):
    if "lm" not in ctx:
        return None
    rows = lm_common.expert_rows_per_step(ctx)
    scanned = lm_hybrid_common.scan_token_layers_per_step(ctx)
    step = lm_common.step_seconds_and_count(ctx["trace"])
    if rows is None or scanned is None or step is None:
        return None
    lm = ctx["lm"]
    flops = lm_hybrid_arith.step_model_flops(
        lm["desc"], lm["seq_len"], lm["sequences"], rows, scanned
    )
    peak = arith.peak(ctx["device_kind"], "bf16_flops_per_s")
    return 100.0 * flops / (peak * step[0])

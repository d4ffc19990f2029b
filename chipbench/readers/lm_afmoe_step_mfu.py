"""Share of the chip's bf16 peak that a training step's model work is, in
percent, for a model of gated window and full attention layers beside
shared and routed experts: ``lm_afmoe_arith.step_model_flops`` (6 x
matmul weights a token meets x tokens, the routed experts by the rows
``ps_lm_expert_rows_total`` says they computed, the gates' product for
the token-layers ``ps_lm_attention_gate_token_layers_total`` counted,
the scores by the mask for those ``ps_lm_attention_token_layers_total``
counted; no recomputation) over the peak of ``peaks.json`` times the
step program's device time (whole events of the module with the most
device time, as ``step_device_ms`` reads it).
"""

from chipbench import arith, lm_afmoe_arith
from chipbench.readers import lm_afmoe_common, lm_common, lm_swa_common


def read(ctx: dict, spec: dict):
    if "lm" not in ctx:
        return None
    rows = lm_common.expert_rows_per_step(ctx)
    attention = lm_swa_common.token_layers_per_step(ctx)
    gates = lm_afmoe_common.gate_token_layers_per_step(ctx)
    step = lm_common.step_seconds_and_count(ctx["trace"])
    if None in (rows, attention, gates, step):
        return None
    lm = ctx["lm"]
    flops = lm_afmoe_arith.step_model_flops(
        lm["desc"], lm["seq_len"], lm["sequences"], rows, attention, gates,
    )
    peak = arith.peak(ctx["device_kind"], "bf16_flops_per_s")
    return 100.0 * flops / (peak * step[0])

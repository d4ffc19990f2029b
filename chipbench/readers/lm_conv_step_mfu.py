"""Share of the chip's bf16 peak that a training step's model work is, in
percent, for a model of gated short-convolution and GQA layers:
``lm_conv_arith.step_model_flops`` (6 x matmul weights a token meets x
tokens, the routed experts by the rows ``ps_lm_expert_rows_total`` says
they computed, the causal scores for the token-layers
``ps_lm_attention_token_layers_total{kind="full"}`` counted, the taps
for those ``ps_lm_conv_token_layers_total`` counted; no recomputation)
over the peak of ``peaks.json`` times the step program's device time
(whole events of the module with the most device time, as
``step_device_ms`` reads it).
"""

from chipbench import arith, lm_conv_arith
from chipbench.readers import lm_common, lm_conv_common, lm_swa_common


def read(ctx: dict, spec: dict):
    if "lm" not in ctx:
        return None
    rows = lm_common.expert_rows_per_step(ctx)
    attention = lm_swa_common.token_layers_per_step(ctx)
    conv = lm_conv_common.conv_token_layers_per_step(ctx)
    step = lm_common.step_seconds_and_count(ctx["trace"])
    if None in (rows, attention, conv, step):
        return None
    lm = ctx["lm"]
    flops = lm_conv_arith.step_model_flops(
        lm["desc"], lm["seq_len"], lm["sequences"], rows, attention["full"],
        conv,
    )
    peak = arith.peak(ctx["device_kind"], "bf16_flops_per_s")
    return 100.0 * flops / (peak * step[0])

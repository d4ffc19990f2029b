"""Share of the chip's bf16 peak that a training step's model work is, in
percent, for a model of sliding-window and full attention layers:
``lm_swa_arith.step_model_flops`` (6 x matmul weights a token meets x
tokens, the routed experts by the rows ``ps_lm_expert_rows_total`` says
they computed, the scores by the mask for the token-layers of each kind
that ``ps_lm_attention_token_layers_total`` counted; no recomputation)
over the peak of ``peaks.json`` times the step program's device time
(whole events of the module with the most device time, as
``step_device_ms`` reads it).
"""

from chipbench import arith, lm_swa_arith
from chipbench.readers import lm_common, lm_swa_common


def read(ctx: dict, spec: dict):
    if "lm" not in ctx:
        return None
    rows = lm_common.expert_rows_per_step(ctx)
    token_layers = lm_swa_common.token_layers_per_step(ctx)
    step = lm_common.step_seconds_and_count(ctx["trace"])
    if rows is None or token_layers is None or step is None:
        return None
    lm = ctx["lm"]
    flops = lm_swa_arith.step_model_flops(
        lm["desc"], lm["seq_len"], lm["sequences"], rows, token_layers
    )
    peak = arith.peak(ctx["device_kind"], "bf16_flops_per_s")
    return 100.0 * flops / (peak * step[0])

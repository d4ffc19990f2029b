"""Roofline share of one group of the step's device ops, in percent, for
a model of gated short-convolution and GQA layers: the least time the
chip could take for the group's work of one step, over the group's op
self time brought to one step (the capture's total over the steps in the
capture). The group is selected as ``lm_kernel_roofline`` selects it
(``scopes``: every string of one of the lists in an op's
``args.tf_op``; ``category``).

``work`` ``conv_mix``: the mix of the convolutions (``lm_conv_arith``:
15 vectors of the hidden size moved a token-layer, a few operations a
channel) for the token-layers ``ps_lm_conv_token_layers_total`` counted;
the least time is the larger of operations over the bf16 peak and bytes
over the HBM peak, and the first call on a capture prints one
``{"chipbench": "lm_conv_mix"}`` line with both bounds and the ops'
time a step. ``flash_kernels``: the flash kernels' 7 score-sized
products by the causal mask for the token-layers
``ps_lm_attention_token_layers_total{kind="full"}`` counted, over the
bf16 peak.

None where the program has no such counter or no op is in the group.
"""

import json

from chipbench import arith, lm_conv_arith
from chipbench.readers import lm_common, lm_conv_common, lm_swa_common

_printed = set()


def read(ctx: dict, spec: dict):
    if "lm" not in ctx:
        return None
    tr, lm = ctx["trace"], ctx["lm"]
    step = lm_common.step_seconds_and_count(tr)
    if step is None:
        return None
    kind, desc = ctx["device_kind"], lm["desc"]
    if spec["work"] == "conv_mix":
        counted = lm_conv_common.conv_token_layers_per_step(ctx)
        if counted is None:
            return None
        by_compute = lm_conv_arith.mix_flops(
            desc, counted, lm["remat"]
        ) / arith.peak(kind, "bf16_flops_per_s")
        by_memory = lm_conv_arith.mix_bytes(desc, counted) / arith.peak(
            kind, "hbm_bytes_per_s"
        )
    elif spec["work"] == "flash_kernels":
        counted = lm_swa_common.token_layers_per_step(ctx)
        if counted is None:
            return None
        by_compute = lm_conv_arith.flash_kernels_flops(
            desc, lm["seq_len"], counted["full"]
        ) / arith.peak(kind, "bf16_flops_per_s")
        by_memory = 0.0
    else:
        raise ValueError(f"work {spec['work']!r}")
    seconds = sum(
        o.self_s for ops in tr.ops.values() for o in ops
        if any(all(s in o.scope for s in holds) for holds in spec["scopes"])
        and spec.get("category", o.category) == o.category
    ) / step[1]
    if spec["work"] == "conv_mix" and id(tr) not in _printed:
        _printed.add(id(tr))
        print(json.dumps({
            "chipbench": "lm_conv_mix", "token_layers_a_step": counted,
            "least_s_by_compute": by_compute, "least_s_by_memory": by_memory,
            "ops_s_a_step": seconds,
            "bound_by": "memory" if by_memory > by_compute else "compute",
        }), flush=True)
    if seconds <= 0:
        return None
    return 100.0 * max(by_compute, by_memory) / seconds

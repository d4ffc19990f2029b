"""Roofline share of one group of the hybrid LM step's device ops, in
percent, bound by the bf16 peak: ``lm_kernel_roofline`` for a model
whose softmax layers are some of its layers. The operations the group
executes in one step (``work``: ``flash_kernels`` by
``lm_hybrid_arith.flash_kernels_flops``, the softmax layers only;
``expert_products`` by ``lm_arith.expert_products_executed_flops`` for
the rows ``ps_lm_expert_rows_total`` counted) over the peak times the
group's op self time brought to one step. The group is selected as
``lm_kernel_roofline`` selects it (``scopes``, ``category``), and the
first call on a capture prints its ``{"chipbench": "lm_kernels"}`` line
of the custom calls by scope, so that a renamed kernel is seen.

No op in the group reads 0 where the counter says no row was computed,
and nothing otherwise.
"""

import json

from chipbench import arith, lm_arith, lm_hybrid_arith
from chipbench.readers import lm_common, lm_kernel_roofline

_printed = set()


def read(ctx: dict, spec: dict):
    if "lm" not in ctx:
        return None
    tr, lm = ctx["trace"], ctx["lm"]
    if id(tr) not in _printed:
        _printed.add(id(tr))
        print(json.dumps(lm_kernel_roofline.kernels_line(tr)), flush=True)
    step = lm_common.step_seconds_and_count(tr)
    if step is None:
        return None
    if spec["work"] == "expert_products":
        rows = lm_common.expert_rows_per_step(ctx)
        if rows is None:
            return None
        flops = lm_arith.expert_products_executed_flops(
            lm["desc"], rows, lm["remat"]
        )
    elif spec["work"] == "flash_kernels":
        flops = lm_hybrid_arith.flash_kernels_flops(
            lm["desc"], lm["seq_len"], lm["sequences"]
        )
    else:
        raise ValueError(f"work {spec['work']!r}")
    seconds = sum(
        o.self_s for ops in tr.ops.values() for o in ops
        if any(all(s in o.scope for s in holds) for holds in spec["scopes"])
        and spec.get("category", o.category) == o.category
    ) / step[1]
    if seconds <= 0:
        return 0.0 if flops == 0 else None
    peak = arith.peak(ctx["device_kind"], "bf16_flops_per_s")
    return 100.0 * flops / (peak * seconds)

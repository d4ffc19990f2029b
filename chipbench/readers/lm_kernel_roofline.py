"""Roofline share of one group of the LM step's device ops, in percent,
bound by the bf16 peak: the operations ``lm_arith`` says the group
executes in one step, over the peak times the group's op self time
brought to one step (the capture's total over ``steps in the capture``).

The group is the op events whose ``args.tf_op`` holds every string of
one of the lists in ``scopes`` (and, with ``category``, whose
``hlo_category`` is it). Several lists because the TPU compiler's
expansion of ``ragged_dot`` names its custom calls ``ragged-dot-none``
and drops the ``jax.named_scope`` path: the grouped products are those
calls AND what stays under ``lm_moe_experts``, whatever implements them.
``work`` names the count: ``expert_products`` (the grouped products for
the rows ``ps_lm_expert_rows_total`` counted: forward, recomputation and
backward as executed, the same count whatever implements the product) or
``flash_kernels`` (forward kernel, twice under recomputation, and the
backward's five score-sized products; the duplicate recomputation inside
the two backward kernels is not credited).

No op in the group reads 0 where the counter says no row was computed
(there was nothing to execute), and nothing otherwise.

The first call on a capture prints one ``{"chipbench": "lm_kernels"}``
line: the custom-call events under ``lm_`` scopes (and the unscoped
``ragged-dot`` ones) by scope, with counts and self time, so that a
renamed scope or kernel is seen and not guessed.
"""

import json

from chipbench import arith, lm_arith
from chipbench.readers import lm_common

_printed = set()


def kernels_line(tr) -> dict:
    by_scope: dict = {}
    for ops in tr.ops.values():
        for o in ops:
            if o.category == "custom-call" and (
                "lm_" in o.scope or "ragged" in o.scope
            ):
                row = by_scope.setdefault(o.scope, [0, 0.0])
                row[0] += 1
                row[1] += o.self_s
    return {
        "chipbench": "lm_kernels",
        "custom_calls": [
            {"scope": k, "events": n, "self_s": s}
            for k, (n, s) in sorted(by_scope.items(), key=lambda kv: -kv[1][1])
        ][:16],
    }


def read(ctx: dict, spec: dict):
    if "lm" not in ctx:
        return None
    tr, lm = ctx["trace"], ctx["lm"]
    if id(tr) not in _printed:
        _printed.add(id(tr))
        print(json.dumps(kernels_line(tr)), flush=True)
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
        flops = lm_arith.flash_kernels_executed_flops(
            lm["desc"], lm["seq_len"], lm["sequences"], lm["remat"]
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

"""Roofline share of one group of the step's device ops, in percent,
bound by the bf16 peak, for a model whose attention layers are windowed
or full. ``work`` ``expert_products`` is ``lm_hybrid_kernel_roofline``'s
own (the grouped products for the rows ``ps_lm_expert_rows_total``
counted; it prints the ``lm_kernels`` line). ``work`` ``flash_kernels``
is this reader's: the operations ``lm_swa_arith.flash_kernels_flops``
says the flash kernels need in one step, BY THE MASK, for the
token-layers of each kind that ``ps_lm_attention_token_layers_total``
counted, over the peak times the group's op self time brought to one
step. The group is selected as ``lm_kernel_roofline`` selects it
(``scopes``, ``category``).

The first flash call on a capture prints one
``{"chipbench": "lm_swa_flash_calls"}`` line: every custom call under
``lm_attn`` by name and scope with its events and its self time A STEP,
longest first, so that the window layers' calls and the full layers' are
seen apart whether or not the compiler kept the inner scope on them.

No op in the group reads 0 where the counter says nothing was computed,
and nothing otherwise.
"""

import json

from chipbench import arith, lm_swa_arith
from chipbench.readers import (
    lm_common, lm_hybrid_kernel_roofline, lm_swa_common,
)

_printed = set()


def flash_calls_line(tr, steps: float) -> dict:
    by_call: dict = {}
    for ops in tr.ops.values():
        for o in ops:
            if o.category == "custom-call" and "lm_attn" in o.scope:
                row = by_call.setdefault((o.name, o.scope), [0, 0.0])
                row[0] += 1
                row[1] += o.self_s
    return {
        "chipbench": "lm_swa_flash_calls", "steps_in_capture": steps,
        "calls": [
            {"name": name, "scope": scope, "events": n,
             "self_ms_a_step": 1e3 * s / steps}
            for (name, scope), (n, s) in
            sorted(by_call.items(), key=lambda kv: -kv[1][1])
        ][:48],
    }


def read(ctx: dict, spec: dict):
    if spec["work"] == "expert_products":
        return lm_hybrid_kernel_roofline.read(ctx, spec)
    if spec["work"] != "flash_kernels":
        raise ValueError(f"work {spec['work']!r}")
    if "lm" not in ctx:
        return None
    tr, lm = ctx["trace"], ctx["lm"]
    step = lm_common.step_seconds_and_count(tr)
    token_layers = lm_swa_common.token_layers_per_step(ctx)
    if step is None or token_layers is None:
        return None
    if id(tr) not in _printed:
        _printed.add(id(tr))
        print(json.dumps(flash_calls_line(tr, step[1])), flush=True)
    flops = lm_swa_arith.flash_kernels_flops(
        lm["desc"], lm["seq_len"], token_layers
    )
    seconds = sum(
        o.self_s for ops in tr.ops.values() for o in ops
        if any(all(s in o.scope for s in holds) for holds in spec["scopes"])
        and spec.get("category", o.category) == o.category
    ) / step[1]
    if seconds <= 0:
        return 0.0 if flops == 0 else None
    peak = arith.peak(ctx["device_kind"], "bf16_flops_per_s")
    return 100.0 * flops / (peak * seconds)

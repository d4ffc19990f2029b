"""Roofline share of the gated delta rule's recurrence, in percent: the
least time the chip could take for the recurrence's OWN work of one
step, over the self time of the ops under ``scope`` brought to one step
(the capture's total over the steps in the capture).

The work is ``lm_hybrid_arith``'s, whatever implements the recurrence
and whatever its chunk: 7 K V operations a token, head and layer forward
and 14 backward, and each of q, k, v, g, beta, o and their gradients
moved once; the token-layers are what ``ps_lm_kda_scan_tokens_total``
counted. The least time is the larger of operations over the bf16 peak
and bytes over the HBM peak of ``peaks.json``; the ``lm_hybrid_scan``
line says which of the two bounds.

The first call on a capture prints one ``{"chipbench": "lm_hybrid_scan"}``
line: the two bounds, the ops' time a step, and the op events under
``lm_kda`` scopes by scope and category with counts and self time (the
custom calls first), so that a renamed scope or a kernel that takes the
``jnp`` form's place is seen and not guessed. None where no op is under
the scope or the program has no such counter.
"""

import json

from chipbench import arith, lm_hybrid_arith
from chipbench.readers import lm_common, lm_hybrid_common

_printed = set()


def scopes_line(tr, bounds: dict) -> dict:
    by_scope: dict = {}
    for ops in tr.ops.values():
        for o in ops:
            if "lm_kda" not in o.scope:
                continue
            # the innermost lm_kda_* scope of the op's path
            name = next(
                p for p in reversed(o.scope.split("/")) if "lm_kda" in p
            )
            row = by_scope.setdefault((name, o.category), [0, 0.0])
            row[0] += 1
            row[1] += o.self_s
    rows = sorted(
        by_scope.items(),
        key=lambda kv: (kv[0][1] != "custom-call", -kv[1][1]),
    )
    return {
        "chipbench": "lm_hybrid_scan", **bounds,
        "ops": [
            {"scope": k[0], "category": k[1], "events": n, "self_s": s}
            for k, (n, s) in rows
        ][:24],
    }


def read(ctx: dict, spec: dict):
    if "lm" not in ctx:
        return None
    tr, lm = ctx["trace"], ctx["lm"]
    scanned = lm_hybrid_common.scan_token_layers_per_step(ctx)
    step = lm_common.step_seconds_and_count(tr)
    if scanned is None or step is None:
        return None
    seconds = sum(
        o.self_s for ops in tr.ops.values() for o in ops
        if spec["scope"] in o.scope
    ) / step[1]
    kind = ctx["device_kind"]
    by_compute = lm_hybrid_arith.scan_flops(lm["desc"], scanned) / arith.peak(
        kind, "bf16_flops_per_s"
    )
    by_memory = lm_hybrid_arith.scan_bytes(lm["desc"], scanned) / arith.peak(
        kind, "hbm_bytes_per_s"
    )
    if id(tr) not in _printed:
        _printed.add(id(tr))
        print(json.dumps(scopes_line(tr, {
            "token_layers_a_step": scanned, "least_s_by_compute": by_compute,
            "least_s_by_memory": by_memory, "ops_s_a_step": seconds,
            "bound_by": "memory" if by_memory > by_compute else "compute",
        })), flush=True)
    if seconds <= 0:
        return None
    return 100.0 * max(by_compute, by_memory) / seconds

"""Roofline share of the dense FTRL sweep, in percent, bound by HBM
bandwidth: the least time the chip could take to move the sweep's bytes
(``arith.sweep_bytes`` of the shard, over the peak of ``peaks.json``),
over the kernel's measured time per call. The kernel's events are the op
events whose ``args.tf_op`` holds every string of ``scope_holds``; one
event is one sweep of one shard.

Parameters: ``scope_holds``.
"""

from chipbench import arith


def read(ctx: dict, spec: dict):
    tr = ctx["trace"]
    conf = ctx["conf"]
    shard = conf.num_slots // ctx["config"]["mesh"]["num_server"]
    least_s = arith.sweep_bytes(shard, conf.ftrl_state_dtype) / arith.peak(
        ctx["device_kind"], "hbm_bytes_per_s"
    )
    calls = [
        o.self_s for ops in tr.ops.values() for o in ops
        if o.self_s > 0 and all(s in o.scope for s in spec["scope_holds"])
    ]
    if not calls:
        return None
    return 100.0 * least_s * len(calls) / sum(calls)

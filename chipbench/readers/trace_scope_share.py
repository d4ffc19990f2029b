"""Share of the device's op self time spent under one named scope, in
percent: op events whose ``args.tf_op`` holds ``scope``, over all op
events of all devices.

Parameters: ``scope``.
"""


def read(ctx: dict, spec: dict):
    tr = ctx["trace"]
    total = tr.self_total_s()
    inside = sum(
        o.self_s for ops in tr.ops.values() for o in ops
        if spec["scope"] in o.scope
    )
    if total <= 0 or inside <= 0:
        return None  # a scope that is not in the trace is not "0%"
    return 100.0 * inside / total

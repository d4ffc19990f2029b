"""Share of the device's op self time spent under any of several named
scopes, in percent: op events whose ``args.tf_op`` holds one of
``scopes``, over all op events of all devices. ``trace_scope_share`` with
alternatives, for a layer part of whose ops the compiler renames (the
TPU compiler's expansion of ``ragged_dot`` leaves its custom calls
``tf_op`` ``ragged-dot-none`` and drops the ``jax.named_scope`` path).

Parameters: ``scopes``.
"""


def read(ctx: dict, spec: dict):
    tr = ctx["trace"]
    total = tr.self_total_s()
    inside = sum(
        o.self_s for ops in tr.ops.values() for o in ops
        if any(s in o.scope for s in spec["scopes"])
    )
    if total <= 0 or inside <= 0:
        return None  # scopes that are not in the trace are not "0%"
    return 100.0 * inside / total

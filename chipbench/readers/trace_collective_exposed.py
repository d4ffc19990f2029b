"""Share of the traced window a device spends in collectives while
nothing else runs on it, in percent, mean over devices.

A collective op is one whose ``hlo_category`` or HLO name holds one of
``kinds``. Its exposed time is its interval minus the union of the other
ops' intervals on that device (``while`` containers, which span their
bodies, are not "other work").

Parameters: ``kinds``, ``containers``.
"""

from chipbench.trace import union_s


def _is(op, words) -> bool:
    text = (op.category + " " + op.name).lower()
    return any(w in text for w in words)


def read(ctx: dict, spec: dict):
    tr = ctx["trace"]
    shares, seen = [], 0
    for ops in tr.ops.values():
        coll = [(o.start, o.start + o.dur) for o in ops
                if _is(o, spec["kinds"])]
        other = [(o.start, o.start + o.dur) for o in ops
                 if not _is(o, spec["kinds"] + spec["containers"])]
        seen += len(coll)
        # exposed = |coll ∪ other| - |other|
        exposed = union_s(coll + other) - union_s(other)
        shares.append(exposed / tr.window_s)
    if not seen:
        return None
    return 100.0 * sum(shares) / len(shares)

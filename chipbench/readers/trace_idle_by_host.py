"""The device's idle time by what the host was doing in it, in percent of
the traced window, mean over devices: one bucket per call.

Per device, idle is the complement of the union of its op intervals
inside the trace's window (first op start to last op end over all
devices: ``device_idle_share``'s window, so the buckets of one run sum
to that metric). Idle inside an ``XLA Modules`` event of the device is
``in_program``: the program is on the chip and stalls by itself. Idle
between modules goes to the first of the metric file's ordered
``buckets`` (``{name, spans}``) one of whose host spans (``ps.<span>``,
``hostspans.py``) is open at that instant, else to ``unattributed``. A
capture of a program that emits no ``ps.*`` event has nothing to
attribute with, and all of its idle between modules reads
``unattributed``: which is what it is.

The first call on a capture also prints one ``{"chipbench": "idle_gaps"}``
line: the ten longest gaps of any device, each with its seconds by
bucket, the ``ps.*`` spans open during it and the runtime's own host
events that overlap it most.

Parameters: ``bucket`` (``in_program``, ``unattributed`` or a name in
``buckets``), ``buckets``.
"""

import json

from chipbench import hostspans

IN_PROGRAM, UNATTRIBUTED = "in_program", "unattributed"
_printed = set()  # capture files whose idle_gaps line is out


def union(intervals) -> list:
    """Sorted disjoint ``(a, b)`` covering the same points."""
    out = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], b))
        else:
            out.append((a, b))
    return out


def complement(disjoint, lo: float, hi: float) -> list:
    """``[lo, hi]`` minus sorted disjoint intervals."""
    out, edge = [], lo
    for a, b in disjoint:
        if a > edge:
            out.append((edge, min(a, hi)))
        edge = max(edge, b)
        if edge >= hi:
            break
    if edge < hi:
        out.append((edge, hi))
    return [(a, b) for a, b in out if b > a]


def intersect(xs, ys) -> list:
    """Intersection of two sorted disjoint interval lists."""
    out, i, j = [], 0, 0
    while i < len(xs) and j < len(ys):
        a, b = max(xs[i][0], ys[j][0]), min(xs[i][1], ys[j][1])
        if b > a:
            out.append((a, b))
        if xs[i][1] < ys[j][1]:
            i += 1
        else:
            j += 1
    return out


def length(disjoint) -> float:
    return sum(b - a for a, b in disjoint)


def by_bucket(cap: hostspans.Capture, buckets: list) -> dict:
    """device -> {bucket: idle intervals}, every bucket present."""
    tr = cap.trace
    covers = [
        (b["name"], union(
            (s.start, s.end) for s in cap.spans if s.name in b["spans"]
        ))
        for b in buckets
    ]
    out = {}
    for dev, ops in tr.ops.items():
        idle = complement(
            union((o.start, o.start + o.dur) for o in ops), tr.begin, tr.end
        )
        mods = union(
            (start, start + dur) for _, start, dur in tr.modules.get(dev, [])
        )
        parts = {IN_PROGRAM: intersect(idle, mods)}
        rest = intersect(idle, complement(mods, tr.begin, tr.end))
        for name, cover in covers:
            parts[name] = intersect(rest, cover)
            rest = intersect(rest, complement(cover, tr.begin, tr.end))
        parts[UNATTRIBUTED] = rest
        out[dev] = parts
    return out


def gaps_line(cap: hostspans.Capture, parts_by_dev: dict, top: int = 10):
    """The ten longest gaps of any device, with what the host shows."""
    tr = cap.trace
    gaps = []
    for dev, parts in parts_by_dev.items():
        # the buckets partition the device's idle: their union is its gaps
        idle = union(iv for ivs in parts.values() for iv in ivs)
        gaps += [(b - a, a, b, dev) for a, b in idle]
    rows = []
    for s, a, b, dev in sorted(gaps, reverse=True)[:top]:
        here = [(a, b)]
        split = {
            name: length(intersect(ivs, here))
            for name, ivs in parts_by_dev[dev].items()
        }
        open_spans, runtime = {}, {}
        for sp in cap.spans:
            over = min(sp.end, b) - max(sp.start, a)
            if over > 0:
                open_spans[sp.name] = open_spans.get(sp.name, 0.0) + over
        for name, start, dur, _ in cap.runtime:
            over = min(start + dur, b) - max(start, a)
            if over > 0:
                runtime[name] = max(runtime.get(name, 0.0), over)
        rows.append({
            "s": s, "at_s": a - tr.begin, "device": dev,
            "bucket": max(split, key=split.get),
            "by_bucket_s": {k: v for k, v in split.items() if v > 0},
            "open": sorted(open_spans.items(), key=lambda kv: -kv[1]),
            "runtime": sorted(runtime.items(), key=lambda kv: -kv[1])[:4],
        })
    return {
        "chipbench": "idle_gaps", "file": cap.file,
        "ps_events": len(cap.spans), "gaps": rows,
    }


def read(ctx: dict, spec: dict):
    cap = hostspans.load()
    parts_by_dev = by_bucket(cap, spec["buckets"])
    if cap.file not in _printed:
        _printed.add(cap.file)
        print(json.dumps(gaps_line(cap, parts_by_dev)), flush=True)
    window = cap.trace.window_s
    shares = [
        length(parts[spec["bucket"]]) / window
        for parts in parts_by_dev.values()
    ]
    return 100.0 * sum(shares) / len(shares)

"""From a profiler capture to numbers: the reduction of the device trace.

jax 0.9.0 writes two files per capture under
``<dir>/plugins/profile/<time>/``. The ``.xplane.pb`` read through
``jax.profiler.ProfileData`` has no named scope on a TPU (an op event's
name is its HLO text). The Chrome-trace ``*.trace.json.gz`` beside it
does: every event of a device's ``XLA Ops`` thread carries
``args.tf_op`` (the ``jax.named_scope`` path), ``args.hlo_category``
and ``args.bytes_accessed``. This module reads that file.

``_self_times`` and the choice of device tracks are copies of
``parameter_server_tpu/utils/profiling.py`` (PR 21), proven on the
recorded TPU trace in ``fixtures/`` by ``selfcheck.py``.
"""

from __future__ import annotations

import dataclasses
import glob
import gzip
import json
import os


@dataclasses.dataclass
class Op:
    """One op event of a device's ``XLA Ops`` thread."""

    name: str  # the HLO instruction's name, e.g. ``fusion.46``
    scope: str  # ``args.tf_op``: the named-scope path, may be empty
    category: str  # ``args.hlo_category``
    start: float  # seconds on the trace's clock
    dur: float
    self_s: float  # dur minus the ops nested inside it (a ``while``)


@dataclasses.dataclass
class Trace:
    """The device side of one capture. ``ops`` and ``modules`` are keyed
    by device name (``/device:TPU:0``); a module is ``(name, start, dur)``
    of one executed program on the ``XLA Modules`` thread."""

    ops: dict
    modules: dict
    begin: float  # first op start over all devices
    end: float  # last op end over all devices

    @property
    def window_s(self) -> float:
        return self.end - self.begin

    def busy_s(self) -> float:
        """Seconds in which an op ran, per device as the union of its op
        intervals, then the mean over devices: never above window_s."""
        return sum(
            union_s([(o.start, o.start + o.dur) for o in ops])
            for ops in self.ops.values()
        ) / len(self.ops)

    def self_total_s(self) -> float:
        return sum(o.self_s for ops in self.ops.values() for o in ops)


# the recorded capture selfcheck.py and traced rehearsals reduce:
# criteo_bigtable.text, 4 launches on one chip (PR 23's chip run)
FIXTURE = os.path.join(
    os.path.dirname(os.path.abspath(__file__)), "fixtures",
    "criteo_bigtable.text.trace.json.gz",
)
FIXTURE_DEVICE_KIND = "TPU v5 lite"


def union_s(intervals) -> float:
    """Length of the union of ``(start, end)`` intervals."""
    total, cur_a, cur_b = 0.0, None, None
    for a, b in sorted(intervals):
        if cur_b is None or a > cur_b:
            if cur_b is not None:
                total += cur_b - cur_a
            cur_a, cur_b = a, b
        else:
            cur_b = max(cur_b, b)
    if cur_b is not None:
        total += cur_b - cur_a
    return total


def newest_trace_file(log_dir: str) -> str:
    """The ``*.trace.json.gz`` of the newest capture under ``log_dir``."""
    hits = glob.glob(
        os.path.join(log_dir, "**", "*.trace.json.gz"), recursive=True
    )
    if not hits:
        raise FileNotFoundError(f"no *.trace.json.gz under {log_dir}")
    return max(hits, key=os.path.getmtime)


def _self_times(track_events):
    """Yield ``(event, self_us)`` for the complete events of ONE track:
    the event's duration minus that of the events nested inside it.
    Sorted by (start, -duration) parents precede their children, and a
    stack credits each stretch to the innermost enclosing event."""
    evs = sorted(track_events, key=lambda e: (e["ts"], -e["dur"]))
    stack: list = []  # [event, end_ts, child_us]
    for ev in evs:
        ts, dur = ev["ts"], ev["dur"]
        while stack and ts >= stack[-1][1]:
            top, _, child_us = stack.pop()
            yield top, top["dur"] - child_us
        if stack:
            stack[-1][2] += dur
        stack.append([ev, ts + dur, 0.0])
    while stack:
        top, _, child_us = stack.pop()
        yield top, top["dur"] - child_us


def load(path: str) -> Trace:
    """Read one Chrome-trace file. Raises ValueError when it holds no
    device op: a reduction never reports zeros that stand for "not
    found"."""
    opener = gzip.open if path.endswith(".gz") else open
    with opener(path, "rt", errors="replace") as f:
        doc = json.load(f)
    events = doc["traceEvents"] if isinstance(doc, dict) else doc
    pnames, tnames = {}, {}
    for ev in events:
        if ev.get("ph") == "M":
            name = (ev.get("args") or {}).get("name", "")
            if ev.get("name") == "process_name":
                pnames[ev["pid"]] = name
            elif ev.get("name") == "thread_name":
                tnames[(ev["pid"], ev["tid"])] = name
    devices = {
        pid: name for pid, name in pnames.items()
        if "/device:" in name and "host" not in name.lower()
    }
    op_events: dict = {}
    modules: dict = {}
    for ev in events:
        if ev.get("ph") != "X" or ev.get("pid") not in devices:
            continue
        if not ev.get("dur"):
            continue
        thread = tnames.get((ev["pid"], ev["tid"]), "")
        dev = devices[ev["pid"]]
        if thread == "XLA Ops":
            op_events.setdefault(dev, []).append(ev)
        elif thread == "XLA Modules":
            modules.setdefault(dev, []).append(
                (str(ev.get("name", "")), ev["ts"] / 1e6, ev["dur"] / 1e6)
            )
    if not op_events:
        raise ValueError(
            f"{path}: no event on an 'XLA Ops' thread of a device "
            f"(processes: {sorted(pnames.values())})"
        )
    ops = {}
    for dev, evs in op_events.items():
        out = []
        for ev, self_us in _self_times(evs):
            args = ev.get("args") or {}
            out.append(Op(
                name=str(ev.get("name", "?")),
                scope=str(args.get("tf_op", "")),
                category=str(args.get("hlo_category", "")),
                start=ev["ts"] / 1e6,
                dur=ev["dur"] / 1e6,
                self_s=max(0.0, self_us) / 1e6,
            ))
        ops[dev] = sorted(out, key=lambda o: o.start)
    for dev in modules:
        modules[dev].sort(key=lambda m: m[1])
    return Trace(
        ops=ops,
        modules=modules,
        begin=min(o.start for v in ops.values() for o in v),
        end=max(o.start + o.dur for v in ops.values() for o in v),
    )


def op_label(op: Op) -> str:
    """``<scope>/<op> <hlo name>``: the innermost ``ps_*`` scope with the
    primitive under it, e.g. ``ps_update/scatter fusion.46``."""
    parts = [p for p in op.scope.rstrip(":").split("/") if p]
    scopes = [p for p in parts if p.startswith("ps_")]
    prim = parts[-1] if parts else "unscoped"
    head = scopes[-1] + "/" if scopes and scopes[-1] != prim else ""
    return f"{head}{prim} {op.name}"


def breakdown(capture, buckets=(), top: int = 10) -> dict:
    """The contract's optional ``breakdown`` of one capture
    (``hostspans.Capture``: host and device side of one file): the device
    ops that took most self time (summed over devices and calls), and the
    longest idle gaps of any device, each named by what the host was
    doing in it.

    ``buckets`` is the ordered ``{name, spans}`` list of the idle shares'
    metric files. A gap takes the name under which
    ``readers/trace_idle_by_host`` counts most of it (``in_program``, a
    bucket's name, ``unattributed``), so that the breakdown and the
    ``idle_*`` shares tell one story."""
    # here and not at the top: the reader imports this module
    from chipbench.readers import trace_idle_by_host as by_host

    by_label: dict = {}
    for ops in capture.trace.ops.values():
        for o in ops:
            if o.self_s > 0:
                k = op_label(o)
                by_label[k] = by_label.get(k, 0.0) + o.self_s
    gaps = by_host.gaps_line(
        capture, by_host.by_bucket(capture, list(buckets)), top
    )["gaps"]
    return {
        "device_ops": [
            [k, v] for k, v in
            sorted(by_label.items(), key=lambda kv: -kv[1])[:top]
        ],
        "idle_gaps": [[g["bucket"], g["s"]] for g in gaps],
    }

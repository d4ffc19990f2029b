"""Device-level tracing (§5 tracing/monitoring aux subsystem).

The reference's tracing story is host-side counters
(``src/util/resource_usage.h``, heartbeat/dashboard tables); on TPU the
equivalent visibility tool is an XLA device trace — per-op device
timelines, HBM traffic, and fusion boundaries — captured with
``jax.profiler`` and viewed in TensorBoard's profile plugin or
Perfetto. ``device_trace`` is wired into the CLIs' ``--profile`` flag.
"""

from __future__ import annotations

import contextlib
from typing import Iterator


@contextlib.contextmanager
def device_trace(log_dir: str | None) -> Iterator[None]:
    """Capture a device trace of the enclosed block into ``log_dir``.

    Output is TensorBoard-profile format: an ``.xplane.pb`` under
    ``<log_dir>/plugins/profile/<time>/`` that
    ``jax.profiler.ProfileData.from_file`` reads with nothing but jax.
    ``None`` is a no-op, so callers can pass an optional CLI flag
    straight through. A profiler that does not start or stop raises: a
    run asked to produce a trace must not finish without one."""
    if not log_dir:
        yield
        return
    import jax

    jax.profiler.start_trace(log_dir)
    try:
        yield
    finally:
        jax.profiler.stop_trace()


def annotate(name: str, **keys):
    """Named region inside a capture: an ``X`` event on the calling
    thread's track of the host process, on the device trace's clock,
    with ``keys`` as its ``args``. Usable as a context manager:
    ``with annotate("push"): ...``. The one wrapper of
    ``jax.profiler.TraceAnnotation``; ``telemetry/spans.span`` calls it
    for every span while a sink is installed. Outside a capture it
    records nothing."""
    import jax

    return jax.profiler.TraceAnnotation(name, **keys)


# -- trace post-processing ---------------------------------------------------
#
# jax.profiler writes TensorBoard-profile artifacts; the
# ``*.trace.json.gz`` file inside is Chrome-trace JSON whose complete
# events carry the HLO op name (and, through jax.named_scope, our
# phase prefix) either in the event name or in args.name/args.tf_op.
# Summarizing it here turns a --profile capture into a self-contained
# breakdown table: no TensorBoard needed on the capture host.

_PHASE_PREFIXES = (
    "ps_decode", "ps_pull", "ps_compute", "ps_push", "ps_update",
    "ps_metrics",
)


def _trace_files(log_dir: str) -> "list[str]":
    """Trace files of the NEWEST profiler run only: jax.profiler writes
    each capture under ``<dir>/plugins/profile/<timestamp>/`` (jax
    0.9.0, on a CPU and on a TPU alike: ``<host>.xplane.pb`` and a
    Chrome-trace ``<host>.trace.json.gz``, the file read here), and a
    reused dir accumulates runs — mixing them would sum device time
    across captures."""
    import glob
    import os

    paths = {
        # dedup a side-by-side gunzipped copy of the same trace (key
        # without .gz); prefer the .gz original deterministically
        (p[:-3] if p.endswith(".gz") else p): p
        for pat in ("*.trace.json", "*.trace.json.gz")
        for p in glob.glob(
            os.path.join(log_dir, "**", pat), recursive=True
        )
    }
    if not paths:
        return []
    runs: dict = {}
    for p in paths.values():
        runs.setdefault(os.path.dirname(p), []).append(p)
    newest = max(runs, key=lambda d: os.path.getmtime(d))
    return sorted(runs[newest])


def _iter_trace_events(log_dir: str):
    """Yield (pid->process-name, (pid,tid)->thread-name, events) per
    trace file of the newest run. Chrome-trace JSON, maybe gzipped."""
    import gzip
    import json as _json

    for path in _trace_files(log_dir):
        try:
            if path.endswith(".gz"):
                with gzip.open(path, "rt", errors="replace") as f:
                    doc = _json.load(f)
            else:
                with open(path, errors="replace") as f:
                    doc = _json.load(f)
        except (OSError, ValueError):
            continue
        # both legal Chrome-trace top levels: object with traceEvents,
        # or the bare event array
        events = (
            doc if isinstance(doc, list) else doc.get("traceEvents")
        ) or []
        pnames: dict = {}
        tnames: dict = {}
        for ev in events:
            if not isinstance(ev, dict) or ev.get("ph") != "M":
                continue
            nm = (ev.get("args") or {}).get("name") or ""
            if ev.get("name") == "process_name":
                pnames[ev.get("pid")] = nm
            elif ev.get("name") == "thread_name":
                tnames[(ev.get("pid"), ev.get("tid"))] = nm
        yield pnames, tnames, events


def _device_op_keys(pnames: dict, tnames: dict):
    """(device_pids, keep(pid, tid)): the device-op track filter of
    :func:`summarize_trace`: pids whose process name looks like a
    device, and within them only
    op-level tids (prefer threads named "XLA Ops"; a device pid without
    one keeps its tids minus Module/Step aggregates, which cover the
    sum of their ops and would double everything)."""
    device_pids = {
        pid
        for pid, nm in pnames.items()
        if any(k in nm for k in ("XLA Ops", "TPU", "/device:", "Device"))
        and "host" not in nm.lower()
    }
    op_tids = {
        key
        for key, nm in tnames.items()
        if key[0] in device_pids and "XLA Ops" in nm
    }
    named_pids = {p for p, _ in op_tids}
    excluded = {
        key
        for key, nm in tnames.items()
        if key[0] in device_pids
        and any(k in nm for k in ("Module", "Step", "module"))
    }

    def keep(pid, tid) -> bool:
        if pid not in device_pids:
            return False
        key = (pid, tid)
        if pid in named_pids:
            return key in op_tids
        return key not in excluded

    return device_pids, keep


def _self_times(track_events: "list[dict]"):
    """Yield ``(event, self_us)`` for complete events of ONE trace
    track, where self_us is the event's duration minus the duration of
    child events nested inside it on the same track (Chrome-trace
    nesting: a child starts at/after the parent and ends at/before it).
    Sorting by (start, -duration) makes parents precede their children;
    a span stack then attributes each event's time to the innermost
    enclosing span, which is exactly per-op self time."""
    evs = sorted(
        track_events,
        key=lambda e: (e.get("ts", 0), -(e.get("dur") or 0)),
    )
    stack: list = []  # [event, end_ts, child_us]
    for ev in evs:
        ts = ev.get("ts", 0)
        dur = ev.get("dur") or 0
        while stack and ts >= stack[-1][1]:
            top_ev, _, child_us = stack.pop()
            yield top_ev, (top_ev.get("dur") or 0) - child_us
        if stack:
            stack[-1][2] += dur
        stack.append([ev, ts + dur, 0.0])
    while stack:
        top_ev, _, child_us = stack.pop()
        yield top_ev, (top_ev.get("dur") or 0) - child_us


def summarize_trace(
    log_dir: str, top: int = 12
) -> "dict | None":
    """Bucket device time in a captured trace by named-scope phase and
    by op, from the device ("XLA Ops"-style) tracks only.

    Returns ``{"device_ms": total, "phases": {phase: ms}, "top_ops":
    [{"name", "ms", "calls"}...]}`` or None when no parseable trace
    exists or no device-op track can be identified (counting host
    tracks would report wall-clock as device time). Only op-level
    tracks are summed — a device pid also carries "XLA Modules"/
    "Steps" spans that cover the sum of their ops, and including them
    would double device_ms. Within the op track, control-flow spans
    (``while``/``fusion`` parents) NEST their body ops as child events
    on the same track; each event is therefore credited only its SELF
    time (duration minus time covered by its children), so a scan
    wrapper no longer double-counts its body into a phantom "other"
    bucket. Never raises: result-path code."""
    try:
        phases: dict = {}
        ops: dict = {}
        total_us = 0.0
        seen = False
        all_device_pids: set = set()
        for pnames, tnames, events in _iter_trace_events(log_dir):
            # op-level device tracks only (the shared filter: prefer
            # "XLA Ops"-named threads, exclude Module/Step aggregates)
            device_pids, keep = _device_op_keys(pnames, tnames)
            if not device_pids:
                continue  # no device track in this file
            all_device_pids.update(device_pids)
            tracks: dict = {}
            for ev in events:
                if not isinstance(ev, dict) or ev.get("ph") != "X":
                    continue
                pid = ev.get("pid")
                if not keep(pid, ev.get("tid")):
                    continue
                dur = ev.get("dur")
                if not dur:
                    continue
                tracks.setdefault((pid, ev.get("tid")), []).append(ev)
            for track_events in tracks.values():
                for ev, self_us in _self_times(track_events):
                    if self_us <= 0:
                        continue
                    args = ev.get("args") or {}
                    label = (
                        args.get("name")
                        or args.get("tf_op")
                        or args.get("long_name")
                        or ev.get("name")
                        or "?"
                    )
                    label = str(label)
                    seen = True
                    total_us += self_us
                    phase = next(
                        (p for p in _PHASE_PREFIXES if p in label),
                        "other",
                    )
                    phases[phase] = phases.get(phase, 0.0) + self_us
                    short = str(ev.get("name") or label)[:80]
                    rec = ops.setdefault(short, [0.0, 0])
                    rec[0] += self_us
                    rec[1] += 1
        if not seen:
            return None
        out = {
            # aggregate op-time summed over ALL device tracks (one per
            # core on a multi-core capture) — core-time, not step
            # wall-clock; device_tracks discloses the multiplier
            "device_ms": round(total_us / 1e3, 3),
            "device_tracks": len(all_device_pids),
            "phases": {
                k: round(v / 1e3, 3)
                for k, v in sorted(
                    phases.items(), key=lambda kv: -kv[1]
                )
            },
            "top_ops": [
                {"name": k, "ms": round(v[0] / 1e3, 3), "calls": v[1]}
                for k, v in sorted(
                    ops.items(), key=lambda kv: -kv[1][0]
                )[:top]
            ],
        }
        return out
    except Exception:  # pragma: no cover - defensive: result-path code
        return None

"""The program's host spans and the device ops of ONE capture, so that
host and device can never come from two clocks.

While a span sink is installed, ``parameter_server_tpu/telemetry/spans``
runs every ``span(name)`` inside ``jax.profiler.TraceAnnotation("ps." +
name, flow=, ts=)``. Inside a capture those land in the same
``*.trace.json.gz`` as the device tracks (``trace.py``): ``X`` events of
the ``/host:CPU`` process, one track per thread (``tid``), on the device
trace's timebase. This module reads them, and the file's device side
through ``trace.load``.

Which file: the newest capture under ``cache/trace/`` (run.py empties a
cell's directory before it captures, so that is this run's) if it has a
device track; a rehearsal's CPU capture has none, and the recorded
capture ``FIXTURE`` stands in. A program that emits no ``ps.*`` event
(one from before the bridge) gives an empty ``spans``: every reader here
must cope, because the same benchmark files also run on such a program.
"""

from __future__ import annotations

import dataclasses
import functools
import gzip
import json
import os

from chipbench import trace

HERE = os.path.dirname(os.path.abspath(__file__))
CACHE_TRACES = os.path.join(HERE, "cache", "trace")
PREFIX = "ps."

# this PR's own traced chip run of criteo_dense.text on one TPU v5 lite,
# cut to a few launches: device ops and modules, the ps.* host spans and
# the runtime's PjitFunction calls
FIXTURE = os.path.join(
    HERE, "fixtures", "criteo_dense.text.hostspans.trace.json.gz"
)


@dataclasses.dataclass
class HostSpan:
    """One ``ps.<span>`` interval of the host process."""

    name: str  # with the prefix, e.g. ``ps.executor.run``
    start: float  # seconds on the trace's clock
    dur: float
    tid: int  # the emitting thread's track
    args: dict  # ``flow`` and ``ts`` where the span had them, as strings

    @property
    def end(self) -> float:
        return self.start + self.dur


@dataclasses.dataclass
class Capture:
    file: str
    trace: trace.Trace  # the device side of the same file
    spans: list  # HostSpan, by start; empty if the program emits none
    runtime: list  # (name, start, dur, tid) of the host's other X events


def _events(path: str) -> list:
    opener = gzip.open if path.endswith(".gz") else open
    with opener(path, "rt", errors="replace") as f:
        doc = json.load(f)
    return doc["traceEvents"] if isinstance(doc, dict) else doc


def host_events(path: str):
    """``(spans, runtime)`` of the host processes of one trace file."""
    events = _events(path)
    hosts = {
        ev["pid"] for ev in events
        if ev.get("ph") == "M" and ev.get("name") == "process_name"
        and "/host:" in (ev.get("args") or {}).get("name", "")
    }
    spans, runtime = [], []
    for ev in events:
        if ev.get("ph") != "X" or ev.get("pid") not in hosts:
            continue
        name, start = str(ev.get("name", "")), ev["ts"] / 1e6
        dur = ev.get("dur", 0.0) / 1e6
        if name.startswith(PREFIX):
            spans.append(HostSpan(
                name, start, dur, ev.get("tid", 0), dict(ev.get("args") or {})
            ))
        else:
            runtime.append((name, start, dur, ev.get("tid", 0)))
    spans.sort(key=lambda s: s.start)
    return spans, runtime


@functools.lru_cache(maxsize=2)
def _load(path: str, _mtime: float) -> Capture:
    spans, runtime = host_events(path)
    return Capture(path, trace.load(path), spans, runtime)


def load(path: str | None = None) -> Capture:
    """One capture, host and device from the same file; which file, the
    module docstring says. Loaded once per file however many metrics
    read it."""
    if path is not None:
        return _load(path, os.path.getmtime(path))
    try:
        return load(trace.newest_trace_file(CACHE_TRACES))
    except (FileNotFoundError, ValueError):
        return load(FIXTURE)  # no capture, or a CPU capture: no device track

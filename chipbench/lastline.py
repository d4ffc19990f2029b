"""The last line of a run: built, checked against the cell's entry in
BENCHMARK.json and printed here, in both modes, and nowhere else.

The contract: one JSON object with ``correct``, ``attempted``,
``failed``, ``metrics`` and ``device`` (and ``breakdown`` in a traced
run). ``metrics`` gives each metric of the cell as ``{value, unit}``: the
end-to-end metrics in an untraced run, and in a traced run those and the
cell's per-layer metrics. ``device`` gives ``platform``, ``kind``,
``count`` and ``memory_peak_bytes``, and in a traced run ``window_s`` and
``busy_s`` with ``0 < busy_s <= window_s``. ``checks`` comes last: each
check that decided ``correct`` by its name, as ``{ok, value, limit}``,
the number compared beside what it was held to. A line that fails a
check is not printed: the faults go out on earlier lines and the run
exits 1.
"""

from __future__ import annotations

import json
import math

TOP_KEYS = ("correct", "attempted", "failed", "metrics", "device")
DEVICE_KEYS = ("platform", "kind", "count", "memory_peak_bytes")
TRACE_KEYS = ("busy_s", "window_s")


def cell_metrics(bench: dict, workload: str, group: str) -> list:
    """The metrics of ``group`` (``end_to_end`` or ``per_layer``) that
    this cell reports: those with no ``workloads`` key or one that
    lists it."""
    return [
        m for m in bench[group]
        if workload in m.get("workloads", [workload])
    ]


def expected(bench: dict, workload: str, traced: bool) -> dict:
    """name -> unit of every metric the line must hold."""
    groups = ("end_to_end", "per_layer") if traced else ("end_to_end",)
    return {
        m["name"]: m["unit"]
        for g in groups for m in cell_metrics(bench, workload, g)
    }


def _number(x) -> bool:
    return (
        isinstance(x, (int, float)) and not isinstance(x, bool)
        and math.isfinite(x)
    )


def faults(obj: dict, bench: dict, workload: str, traced: bool) -> list:
    """Every way in which ``obj`` is not the contract's object for this
    cell and mode; empty when it is."""
    out = []
    allowed = TOP_KEYS + (("breakdown",) if traced else ()) + ("checks",)
    out += [f"key {k!r} is missing" for k in TOP_KEYS if k not in obj]
    out += [f"key {k!r} does not belong" for k in obj if k not in allowed]
    if out:
        return out
    if not isinstance(obj["correct"], bool):
        out.append("'correct' is not true or false")
    for k in ("attempted", "failed"):
        if not isinstance(obj[k], int) or isinstance(obj[k], bool) or obj[k] < 0:
            out.append(f"{k!r} is not a count")
    want = expected(bench, workload, traced)
    metrics = obj["metrics"]
    for name, unit in want.items():
        m = metrics.get(name)
        if m is None:
            out.append(f"metric {name!r} is missing")
        elif set(m) != {"value", "unit"} or m["unit"] != unit:
            out.append(f"metric {name!r} is not {{value, unit: {unit!r}}}")
        elif not _number(m["value"]):
            out.append(f"metric {name!r} has no finite number: {m['value']!r}")
    out += [f"metric {n!r} is not one of this cell's" for n in metrics
            if n not in want]
    device = obj["device"]
    keys = DEVICE_KEYS + (TRACE_KEYS if traced else ())
    out += [f"device.{k} is missing" for k in keys if k not in device]
    out += [f"device.{k} does not belong" for k in device if k not in keys]
    if traced and not any(f.startswith("device.") for f in out):
        busy, window = device["busy_s"], device["window_s"]
        if not (_number(busy) and _number(window) and 0 < busy <= window):
            out.append(
                f"device needs 0 < busy_s <= window_s, has {busy!r}, "
                f"{window!r}"
            )
    if "breakdown" in obj:
        b = obj["breakdown"]
        if set(b) != {"device_ops", "idle_gaps"} or any(
            len(v) > 10 or any(
                len(e) != 2 or not isinstance(e[0], str) or not _number(e[1])
                for e in v
            ) for v in b.values()
        ):
            out.append("breakdown is not two lists of at most 10 [name, s]")
    if "checks" in obj:
        if list(obj)[-1] != "checks":
            out.append("'checks' is not the last key")
        if any(set(c) != {"ok", "value", "limit"}
               for c in obj["checks"].values()):
            out.append("a check is not {ok, value, limit}")
    return out


def build(bench: dict, workload: str, traced: bool, *, correct: bool,
          attempted: int, failed: int, values: dict, device: dict,
          breakdown=None, checks=None) -> dict:
    """The object, from plain values: units come from BENCHMARK.json."""
    units = expected(bench, workload, traced)
    obj = {
        "correct": correct, "attempted": attempted, "failed": failed,
        "metrics": {
            name: {"value": values[name], "unit": units[name]}
            for name in units if name in values
        },
        "device": device,
    }
    if traced and breakdown is not None:
        obj["breakdown"] = breakdown
    if checks is not None:
        obj["checks"] = checks
    return obj


def emit(bench: dict, workload: str, traced: bool, **parts) -> int:
    """Print the last line and return the exit code: 0 if the line is
    the contract's object, else 1 with the faults printed instead."""
    obj = build(bench, workload, traced, **parts)
    found = faults(obj, bench, workload, traced)
    if found:
        for f in found:
            print(json.dumps({"chipbench": "last_line_refused", "fault": f}))
        return 1
    print(json.dumps(obj), flush=True)
    return 0

"""The time from the end of one span of a name to the end of the next
(an end is ``t_wall + dur_s``), over ALL the window's span events of that
name (``telemetry/spans``, read through the benchmark's in-memory sink),
not the captured stretch alone.

For ``train.collect.wait`` in a closed loop that the device binds, a
wait ends when the device has finished a launch: the interval is the
launch's device time as the host saw it, launch by launch.

Parameters: ``span`` (the event's name); ``stat``: ``median`` (of the
intervals: the window's own long launch, a traced run's ``stop_trace``
among them, does not move it) or ``late_over_early`` (the median of the
last third of the intervals over the median of the first third, ``None``
under 6 intervals: a step that grows through the window reads over 1);
``scale``.
"""

import statistics


def intervals(spans: list, name: str) -> list:
    ends = sorted(
        e["t_wall"] + e["dur_s"] for e in spans
        if e.get("name") == name and "t_wall" in e and "dur_s" in e
    )
    return [b - a for a, b in zip(ends, ends[1:])]


def read(ctx: dict, spec: dict):
    gaps = intervals(ctx["spans"], spec["span"])
    if spec["stat"] == "median":
        if not gaps:
            return None
        return statistics.median(gaps) * spec.get("scale", 1.0)
    if spec["stat"] != "late_over_early":
        raise ValueError(f"stat {spec['stat']!r}: median or late_over_early")
    if len(gaps) < 6:
        return None
    third = len(gaps) // 3
    return (
        statistics.median(gaps[-third:]) / statistics.median(gaps[:third])
        * spec.get("scale", 1.0)
    )

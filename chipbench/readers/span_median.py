"""The median of one numeric field over the program's span events of one
name emitted inside the window (``telemetry/spans``, read through the
benchmark's in-memory sink).

Parameters: ``span`` (the event's name), ``field``, ``scale``.
"""

import statistics


def read(ctx: dict, spec: dict):
    values = [
        e[spec["field"]] for e in ctx["spans"]
        if e.get("name") == spec["span"] and spec["field"] in e
    ]
    if not values:
        return None
    return statistics.median(values) * spec.get("scale", 1.0)

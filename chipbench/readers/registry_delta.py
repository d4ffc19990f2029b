"""A counter or a histogram field of the program's metrics registry, as
its growth over the window, optionally over the growth of another one.

Parameters: ``metric``; ``field`` (``value`` of a counter, ``sum`` or
``count`` of a histogram); ``labels`` (optional: only series whose labels
include these); ``per`` (optional: an object with the same three keys,
the denominator); ``scale`` (optional multiplier).
"""


def total(state: dict, spec: dict) -> float:
    """Sum of ``field`` over the series of ``metric`` whose labels
    include ``labels``, in one ``export_state()``."""
    want = spec.get("labels", {})
    series = state.get(spec["metric"], {}).get("series", [])
    return sum(
        s.get(spec.get("field", "value"), 0.0) for s in series
        if all(s["labels"].get(k) == v for k, v in want.items())
    )


def _delta(ctx: dict, spec: dict) -> float:
    return total(ctx["after"], spec) - total(ctx["before"], spec)


def read(ctx: dict, spec: dict):
    if spec["metric"] not in ctx["after"]:
        return None
    value = _delta(ctx, spec)
    if "per" in spec:
        base = _delta(ctx, spec["per"])
        if base <= 0:
            return None
        value /= base
    return value * spec.get("scale", 1.0)

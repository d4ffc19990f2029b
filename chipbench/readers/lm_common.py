"""What the ``lm_*`` readers share: the step program's events in a
capture, and the window's growth of the LM trainer's counters."""

from chipbench.readers import registry_delta


def step_modules(tr):
    """Per device, the ``(start, dur)`` of the step program's events (the
    module with the most device time, as ``trace_module_ms`` finds it)."""
    by_name: dict = {}
    for mods in tr.modules.values():
        for name, _, dur in mods:
            by_name[name] = by_name.get(name, 0.0) + dur
    if not by_name:
        return {}
    step = max(by_name, key=by_name.get)
    return {
        dev: [(start, dur) for name, start, dur in mods if name == step]
        for dev, mods in tr.modules.items()
    }


def step_seconds_and_count(tr):
    """``(seconds of one whole step on the device, steps in the
    capture)``: the mean duration of the step program's whole events
    (a device's first and last are cut by the profiler), and all of its
    events' time over that, so that op times summed over the capture can
    be brought to one step. None without two whole events."""
    whole, everything = [], 0.0
    for events in step_modules(tr).values():
        whole += [dur for _, dur in events[1:-1]]
        everything += sum(dur for _, dur in events)
    if not whole:
        return None
    mean = sum(whole) / len(whole)
    return mean, everything / mean


def counter_growth(ctx: dict, metric: str) -> float:
    spec = {"metric": metric}
    return registry_delta.total(ctx["after"], spec) - registry_delta.total(
        ctx["before"], spec
    )


def expert_rows_per_step(ctx: dict):
    """Forward rows the held experts computed in one step, all layers:
    the window's rows over the window's tokens, times a step's tokens.
    None where the program has no such counters."""
    if "ps_lm_expert_rows_total" not in ctx["after"]:
        return None
    tokens = counter_growth(ctx, "ps_lm_tokens_total")
    if tokens <= 0:
        return None
    lm = ctx["lm"]
    return (
        counter_growth(ctx, "ps_lm_expert_rows_total") / tokens
        * lm["seq_len"] * lm["sequences"]
    )

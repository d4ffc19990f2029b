"""Device milliseconds per ministep of the step program: the durations
of its events on the ``XLA Modules`` thread over their ministeps, mean
over devices. The step program is the module with the most device time
in the trace, so a renamed step is still found. A device's first and
last event of it are left out: the profiler cuts the module that runs
when it starts or stops (fixtures/: 1,164 and 60 ms beside three whole
launches of 1,229 to 1,284 ms).

``ministeps_per_launch`` comes from the runner's ``ctx``; a runner whose
launch is one step of its program gives none, and an event is a step.
"""


def read(ctx: dict, spec: dict):
    tr = ctx["trace"]
    by_name: dict = {}
    for mods in tr.modules.values():
        for name, _, dur in mods:
            by_name[name] = by_name.get(name, 0.0) + dur
    if not by_name:
        return None
    step = max(by_name, key=by_name.get)
    per_device = []
    for mods in tr.modules.values():
        whole = [dur for name, _, dur in mods if name == step][1:-1]
        if whole:
            ministeps = len(whole) * ctx.get("ministeps_per_launch", 1)
            per_device.append(sum(whole) / ministeps)
    if not per_device:
        return None
    return 1e3 * sum(per_device) / len(per_device)

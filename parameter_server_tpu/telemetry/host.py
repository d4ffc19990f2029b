"""What the host knows about a stretch of one thread's time. The LM
trainer (``apps/lm/trainer.py``) takes a :func:`mark` at each launch's
end (two clocks, ``getrusage``, the collector's counters) and asks for
:func:`evidence` between two marks only if the launch between stalled."""

import collections
import contextlib
import gc
import resource
import threading
import time

_RUSAGE = ("ru_majflt", "ru_minflt", "ru_nvcsw", "ru_nivcsw", "ru_utime",
           "ru_stime")  # faults, context switches, CPU seconds: getrusage(2)
_gc_lock = threading.Lock()
_gc_t0 = 0.0
# (generation, seconds) of each collection since the last mark
_gc_pauses = collections.deque(maxlen=4096)
_gc_totals = [[0, 0.0] for _ in range(3)]  # count, pause seconds


def _on_gc(phase: str, info: dict) -> None:
    # no lock and no registry in here: a collection begins inside any
    # allocation, under whatever lock the allocating thread holds
    global _gc_t0
    if phase == "start":
        _gc_t0 = time.perf_counter()
    else:
        _gc_pauses.append((info["generation"], time.perf_counter() - _gc_t0))


def install_hooks() -> None:
    """The process's one ``gc.callbacks`` hook and compile listener."""
    from . import device

    device.install_compile_listener()
    with _gc_lock:
        if _on_gc not in gc.callbacks:
            gc.callbacks.append(_on_gc)


def mark() -> dict:
    """This thread's clocks, the process's ``getrusage`` and collections
    so far, their pauses observed on the way (``ps_host_gc_pause_seconds``)."""
    from .instruments import cached_lm_instruments

    tel = cached_lm_instruments()
    while _gc_pauses:
        generation, pause = _gc_pauses.popleft()
        _gc_totals[generation][0] += 1
        _gc_totals[generation][1] += pause
        if tel is not None:
            tel["gc_pause"].labels(generation=str(generation)).observe(pause)
    return {
        "wall": time.perf_counter(), "cpu": time.thread_time(),
        "rusage": resource.getrusage(resource.RUSAGE_SELF),
        "gc": [tuple(t) for t in _gc_totals],
    }


def evidence(before: dict, after: dict) -> dict:
    """What happened between two marks of one thread, and what the
    device's memory and the host's pressure files say now."""
    from . import device

    pressure = {}
    for what in ("cpu", "memory", "io"):
        with contextlib.suppress(OSError), open(f"/proc/pressure/{what}") as f:
            pressure[what] = f.readline().strip()
    return {
        # near each other: the thread was busy; cpu far under wall: blocked
        "thread_wall_s": after["wall"] - before["wall"],
        "thread_cpu_s": after["cpu"] - before["cpu"],
        "rusage": {f: getattr(after["rusage"], f) - getattr(before["rusage"], f)
                   for f in _RUSAGE},
        "gc": {str(g): {"count": a[0] - b[0], "pause_s": a[1] - b[1]}
               for g, (a, b) in enumerate(zip(after["gc"], before["gc"]))},
        "compiles": device.compile_events_since(before["wall"]),
        "hbm": device.hbm_monitor().snapshot()["devices"],
        "pressure": pressure,
    }

"""One run of one cell of BENCHMARK.json on the machine it is started on.

    python3 chipbench/run.py --workload <configuration>.<mix> --seed N \\
        --seconds S --trace 0|1 [--rehearsal]

The part of a run that is the same for every application: the clock
(``T0``, ``setup_s``), the arguments, the look for the chips, the compile
listener and the span sink, the window (which launches count, the
deadline, when the counters are read, where the traced stretch starts and
stops), the end-to-end formulas over the window's rows, the checks that
hold for any program, ``device``, the trace reduction and the last line
(``lastline.emit``, the only place that prints it). The trainer, its
data, its reference and its own checks belong to the runner that the
configuration's file names: ``"app": "<name>"`` is
``chipbench/apps/<name>.py``, found by that name as a reader is by its
own (README.md, "The runner's contract"). Everything that belongs to one
configuration, one traffic mix or one per-layer metric is a file found
by the name in BENCHMARK.json. ``--rehearsal`` (toy sizes on the CPU,
never passed by the driver) is how the harness is debugged without a
chip.
"""

import time

T0 = time.perf_counter()  # process start, as near as Python can say


def steady_malloc() -> None:
    """Fix glibc malloc's thresholds, as an operator does with
    ``MALLOC_TOP_PAD_=67108864 MALLOC_TRIM_THRESHOLD_=1073741824
    MALLOC_MMAP_THRESHOLD_=33554432`` (the driver starts this file
    itself, so no environment can carry them). With the defaults a
    thread's arena gives a 64 MB heap back to the kernel whenever it
    stands empty and maps a new one at the next large block. A host
    thread that makes and frees tens of MB of NumPy temporaries a batch
    then either stays inside one heap or maps, faults in and unmaps one
    every batch, by where its long-lived blocks happen to lie: a run
    reads at one of two levels 13% apart, on any seed (PERF.md sections
    2 and 6). A kept top (``top_pad`` of a heap's size stops the give-
    back, the trim threshold the shrinking) and no block mapped alone
    make every run the first kind. Before the first import, so that
    every arena is born under the same rule; elsewhere than glibc there
    is nothing to fix."""
    try:
        import ctypes

        mallopt = ctypes.CDLL("libc.so.6").mallopt
    except (OSError, AttributeError):
        return
    mallopt(-2, 64 << 20)  # M_TOP_PAD: an empty heap is kept
    mallopt(-1, 1 << 30)  # M_TRIM_THRESHOLD: a free top is kept
    mallopt(-3, 32 << 20)  # M_MMAP_THRESHOLD: the most glibc allows


steady_malloc()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import statistics  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, ROOT)
CACHE = os.path.join(HERE, "cache")  # listed in chipbench/.gitignore

from chipbench import hostspans, lastline, trace  # noqa: E402
from chipbench.readers import registry_delta  # noqa: E402


def note(kind: str, **fields) -> None:
    """One JSON line of evidence on stdout, before the last line."""
    print(json.dumps({"chipbench": kind, **fields}), flush=True)


def load_json(*parts) -> dict:
    with open(os.path.join(ROOT, *parts)) as f:
        return json.load(f)


def counter_total(state: dict, name: str) -> float:
    return registry_delta.total(state, {"metric": name})


class Compiles:
    """Counts jax's backend compiles (a persistent-cache hit counts
    too): none may happen inside the window."""

    def __init__(self, jax):
        self.names = []
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, seconds, fun_name=None, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.names.append(str(fun_name))


class ListSink:
    """In-memory span sink (``telemetry/spans.install_sink``): traced
    runs read the program's ``executor.step`` events from it."""

    def __init__(self):
        self.events = []

    def emit(self, event):
        self.events.append(event)

    def close(self):
        pass


class Window:
    """Which launches count, when the feed may end, when the counters are
    read and when the profiler runs. The runner tells it of each launch:
    ``submitted()`` as the launch is dispatched, ``collected(row, ...)``
    once its results are on the host.

    Until ``open()`` every launch is warm-up. The window's time runs
    from its first submit, where set-up ends; a launch counts if it was
    submitted before the deadline, and the feed ends at the first launch
    boundary after it (``expired()``)."""

    def __init__(self, registry, mix: dict, seconds: float, trace_dir):
        self.rows = []  # one dict per launch, in submission order
        self.before = self.after = self.deadline = None
        self.trace_t0 = self.trace_t1 = None
        self.trace_cost = {}
        self._registry, self._mix = registry, mix
        self._seconds, self._trace_dir = seconds, trace_dir
        self._opened = False

    def submitted(self) -> dict:
        """A launch is about to be dispatched: its row."""
        now = time.perf_counter()
        if self._opened and self.deadline is None:
            self.deadline = now + self._seconds
        row = {"submit": now, "collect": None,
               "counted": self._opened and now < self.deadline}
        self.rows.append(row)
        return row

    def collected(self, row: dict, examples: int, objective: float) -> None:
        """The launch of ``row`` is in: what it trained on and the sum of
        its examples' losses."""
        row["collect"] = time.perf_counter()
        row["examples"], row["objective"] = examples, objective
        if self._opened:
            self._after_collect(row["collect"])

    def expired(self) -> bool:
        """True once the deadline has passed: the runner then ends its
        feed at the next launch boundary."""
        return (
            self.deadline is not None
            and time.perf_counter() >= self.deadline
        )

    def open(self) -> None:
        self.before = self._registry.export_state()
        self._opened = True

    def close(self) -> None:
        if self.after is None:  # no collect came after the deadline
            self.after = self._registry.export_state()

    def counted(self) -> list:
        return [r for r in self.rows if r["counted"]]

    def _after_collect(self, now: float) -> None:
        counted = self.counted()
        done = sum(r["collect"] is not None for r in counted)
        if self.after is None and done == len(counted) and (
            now >= self.deadline
        ):
            # the last counted launch is in: the window's counters end
            self.after = self._registry.export_state()
        if self._trace_dir is None or self.trace_t1 is not None:
            return
        import jax

        # the traced stretch begins and ends here, on the thread that
        # collects: a launch is queued behind, so the device
        # does not wait for the profiler
        if self.trace_t0 is None:
            if done >= self._mix["trace_after_launches"]:
                shutil.rmtree(self._trace_dir, ignore_errors=True)
                # without the Python tracer: its events fill the 1,000,000
                # the trace.json keeps, which then ends early, and make
                # stop_trace take 4 to 6 s in place of 0.4
                options = jax.profiler.ProfileOptions()
                options.python_tracer_level = 0
                jax.profiler.start_trace(
                    self._trace_dir, profiler_options=options
                )
                self.trace_t0 = time.perf_counter()
                self.trace_cost["start_trace_s"] = self.trace_t0 - now
        elif now - self.trace_t0 >= self._mix["trace_seconds"]:
            jax.profiler.stop_trace()
            self.trace_t1 = now
            self.trace_cost["stop_trace_s"] = time.perf_counter() - now


def prepare(args, bench: dict):
    """The cell's files, and the environment jax is imported under."""
    cell = next(w for w in bench["workloads"] if w["name"] == args.workload)
    entry = next(c for c in bench["configs"] if c["name"] == cell["config"])
    cfg = load_json(entry["file"])
    mix = load_json("chipbench", "traffic", cell["traffic"] + ".json")
    if args.rehearsal:
        mix = {**mix, **mix["rehearsal"]}
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ["XLA_FLAGS"] = (
            f"--xla_force_host_platform_device_count={cell['chips']}"
        )
        os.environ.update(cfg["rehearsal"].get("env", {}))
    # the program takes the cache directory from this variable and sets
    # none of its own; a fixed path inside the checkout otherwise
    os.environ.setdefault(
        "JAX_COMPILATION_CACHE_DIR", os.path.join(CACHE, "jax")
    )
    return cell, entry, cfg, mix


@dataclasses.dataclass
class Run:
    """What a runner is given: the cell's entries and files as they are
    run, ``--seed``, where the checkout and its cache are, and ``note``."""

    cell: dict  # the entry under ``workloads``
    entry: dict  # the entry under ``configs``
    cfg: dict  # the configuration's file
    mix: dict  # the traffic mix's file (a rehearsal's toy values merged)
    seed: int
    rehearsal: bool
    root: str = ROOT
    cache: str = CACHE
    note: object = note  # note(kind, **fields): a line of evidence


class Checks:
    """The checks that decide ``correct``, each printed with its numbers
    as it is made: ``value`` is the number compared and ``limit`` what it
    is held to."""

    def __init__(self):
        self.made = {}

    def __call__(self, name, ok, *, value, limit, **numbers) -> None:
        if isinstance(value, float) and not math.isfinite(value):
            value = repr(value)  # the line stays JSON
        self.made[name] = {"ok": bool(ok), "value": value, "limit": limit}
        note("check", name=name, **self.made[name], **numbers)

    def all_hold(self) -> bool:
        return all(c["ok"] for c in self.made.values())

    def to_stderr(self) -> None:
        """Each number compared beside its limit, as the run's last
        lines on standard error."""
        for name, c in self.made.items():
            print(
                f"chipbench check {name}: value {c['value']!r} "
                f"limit {c['limit']!r} ok {c['ok']}", file=sys.stderr,
            )
        sys.stderr.flush()


def any_program_checks(check: Checks, win: Window, warm: list, rows: list,
                       compiled_in_window: list, inv0: dict) -> None:
    """What holds for any program: nothing compiled or fell back inside
    the window, and every launch's loss is finite."""
    from parameter_server_tpu.telemetry import device as device_tel

    inv = device_tel.snapshot()
    fallbacks = "ps_device_dispatch_fallbacks_total"
    numbers = {
        "recompiles": inv["recompiles_post_warmup"],
        "backend_compiles": compiled_in_window,
        "donation_fallbacks": inv["donation_fallbacks_total"]
        - inv0["donation_fallbacks_total"],
        "dispatch_fallbacks": counter_total(win.after, fallbacks)
        - counter_total(win.before, fallbacks),
    }
    check(
        "nothing_compiles_or_falls_back_in_window",
        not any(numbers.values()),
        value=sum(len(v) if isinstance(v, list) else v or 0
                  for v in numbers.values()),
        limit=0, **numbers,
        programs={n: f["calls"] for n, f in inv["functions"].items()
                  if f["calls"]},
    )
    bad = sum(not math.isfinite(r["objective"]) for r in warm + rows)
    check(
        "losses_finite", bad == 0, value=bad, limit=0,
        launches=len(warm + rows),
        first=warm[0]["objective"] / warm[0]["examples"],
        last=rows[-1]["objective"] / rows[-1]["examples"],
    )


def idle_buckets(bench: dict, workload: str) -> list:
    """The ordered host-span buckets by which the cell's idle shares
    split the device's idle time (``buckets`` of their metric files):
    the breakdown names its gaps by the same ones."""
    for m in lastline.cell_metrics(bench, workload, "per_layer"):
        spec = load_json("chipbench", "metrics", m["name"] + ".json")
        if "buckets" in spec:
            return spec["buckets"]
    return []


def per_layer(bench: dict, workload: str, ctx: dict) -> dict:
    """Every per-layer metric of the cell that its reader can read: the
    metric's file names the reader and its parameters."""
    values = {}
    for m in lastline.cell_metrics(bench, workload, "per_layer"):
        spec = load_json("chipbench", "metrics", m["name"] + ".json")
        reader = importlib.import_module("chipbench.readers." + spec["reader"])
        value = reader.read(ctx, spec)
        if value is not None:
            values[m["name"]] = value
    return values


def run_cell(args, bench: dict) -> int:
    cell, entry, cfg, mix = prepare(args, bench)

    import jax

    devices = jax.devices()
    platform, kind = devices[0].platform, devices[0].device_kind
    if not args.rehearsal and (
        platform != "tpu" or len(devices) < cell["chips"]
    ):
        print(
            f"chipbench: {args.workload} needs {cell['chips']} TPU chip(s); "
            f"jax found {len(devices)} x {platform}", file=sys.stderr,
        )
        return 2

    from parameter_server_tpu.telemetry import device as device_tel
    from parameter_server_tpu.telemetry import registry as telreg
    from parameter_server_tpu.telemetry import spans

    # the configuration's file names its application; without the key it
    # is the one the benchmark began with
    app = importlib.import_module(
        "chipbench.apps." + cfg.get("app", "linear")
    )
    runner = app.Runner(
        Run(cell, entry, cfg, mix, args.seed, args.rehearsal)
    )
    compiles = Compiles(jax)
    t_imports = time.perf_counter()
    runner.make_data()
    t_data = time.perf_counter()
    trace_dir = (
        os.path.join(CACHE, "trace", args.workload) if args.trace else None
    )
    win = Window(telreg.default_registry(), mix, args.seconds, trace_dir)
    runner.build(win)
    t_built = time.perf_counter()
    sink = ListSink()
    if args.trace:
        spans.install_sink(sink)
    try:
        runner.warm_up()
        warm = list(win.rows)
        device_tel.mark_warmup()
        inv0 = device_tel.snapshot()
        compiled_before = len(compiles.names)
        wall0 = time.time()
        win.open()
        runner.feed()
        t_drained = time.perf_counter()
        win.close()
        if args.trace and win.trace_t1 is None:
            raise RuntimeError(
                f"the window ended before the traced stretch of "
                f"{mix['trace_seconds']} s did: --seconds is too short"
            )
    finally:
        if win.trace_t0 is not None and win.trace_t1 is None:
            jax.profiler.stop_trace()
        spans.install_sink(None)

    rows = win.counted()
    first_submit = rows[0]["submit"]
    last_collect = max(r["collect"] for r in rows)
    examples = sum(r["examples"] for r in rows)
    latencies = [r["collect"] - r["submit"] for r in rows]
    values = {
        "examples_per_s": examples / (last_collect - first_submit),
        "launch_p50_ms": 1e3 * statistics.median(latencies),
        "setup_s": first_submit - T0,
    }
    note(
        "setup", setup_s=first_submit - T0, imports_s=t_imports - T0,
        data_s=t_data - t_imports,
        trainer_and_warm_up_s=first_submit - t_data,
        trainer_built_s=t_built - t_data,
        warm_up_submits_s=[r["submit"] - t_built for r in warm],
        warm_up_collects_s=[r["collect"] - t_built for r in warm],
        warm_up_compiles=compiles.names[:compiled_before],
        compile_cache=os.environ["JAX_COMPILATION_CACHE_DIR"],
    )
    note(
        "window", workload=args.workload, seed=args.seed,
        seconds=args.seconds, measured_s=last_collect - first_submit,
        launches=len(rows),
        launches_after_deadline=len(win.rows) - len(warm) - len(rows),
        examples=examples, launch_ms_min=1e3 * min(latencies),
        launch_ms_max=1e3 * max(latencies),
        drain_s=t_drained - last_collect, **runner.window_note(),
    )
    runner.notes(win)
    check = Checks()
    runner.checks(win, warm, rows, check)
    any_program_checks(
        check, win, warm, rows, compiles.names[compiled_before:], inv0
    )

    device = {
        "platform": platform, "kind": kind, "count": len(devices),
        "memory_peak_bytes": max(
            (d.memory_stats() or {}).get("peak_bytes_in_use", 0)
            for d in devices
        ),
    }
    breakdown = None
    if args.trace:
        trace_file = trace.newest_trace_file(trace_dir)
        if args.rehearsal:
            # a CPU capture has no device track: rehearse the reduction
            # and the readers on the recorded TPU trace instead
            trace_file, kind = trace.FIXTURE, trace.FIXTURE_DEVICE_KIND
        # host and device side of the one file, parsed once: the readers
        # of host spans find the same capture in hostspans' cache
        capture = hostspans.load(trace_file)
        tr = capture.trace
        device.update(busy_s=tr.busy_s(), window_s=tr.window_s)
        breakdown = trace.breakdown(
            capture, idle_buckets(bench, args.workload)
        )
        values.update(per_layer(bench, args.workload, {
            "before": win.before, "after": win.after, "trace": tr,
            "spans": [e for e in sink.events
                      if e.get("t_wall", 0.0) >= wall0],
            "config": cfg, "device_kind": kind, **runner.ctx(),
        }))
        note(
            "trace", file=os.path.relpath(trace_file, ROOT),
            devices=sorted(tr.ops),
            op_events=sum(map(len, tr.ops.values())),
            traced_from_launch=mix["trace_after_launches"],
            **win.trace_cost,
        )

    runner.stop()
    check.to_stderr()
    return lastline.emit(
        bench, args.workload, bool(args.trace), correct=check.all_hold(),
        attempted=len(rows),
        failed=sum(not math.isfinite(r["objective"]) for r in rows),
        values=values, device=device, breakdown=breakdown,
        checks=check.made,
    )


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearsal", action="store_true")
    args = ap.parse_args(argv)
    bench = load_json("BENCHMARK.json")
    if args.workload not in [w["name"] for w in bench["workloads"]]:
        print(f"chipbench: no workload {args.workload!r}", file=sys.stderr)
        return 2
    return run_cell(args, bench)


if __name__ == "__main__":
    sys.exit(main())

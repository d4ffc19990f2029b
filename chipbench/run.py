"""One run of one cell of BENCHMARK.json on the machine it is started on.

    python3 chipbench/run.py --workload <configuration>.<mix> --seed N \\
        --seconds S --trace 0|1 [--rehearsal]

Sets up the configuration's trainer through ``apps/linear``'s normal
objects, warms up the cell's own shapes, measures a closed loop for
``--seconds``, checks the result and prints the contract's object as the
last line of stdout (``lastline.emit``, the only place that prints it).
Everything that belongs to one configuration, one traffic mix or one
per-layer metric is a file found by the name in BENCHMARK.json; see
README.md. ``--rehearsal`` (toy sizes on the CPU, never passed by the
driver) is how the harness is debugged without a chip.
"""

import time

T0 = time.perf_counter()  # process start, as near as Python can say

import argparse  # noqa: E402
import importlib  # noqa: E402
import itertools  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import re  # noqa: E402
import shutil  # noqa: E402
import statistics  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, ROOT)
CACHE = os.path.join(HERE, "cache")  # listed in chipbench/.gitignore

from chipbench import lastline, oracle, synth, trace  # noqa: E402
from chipbench.readers import registry_delta  # noqa: E402


def note(kind: str, **fields) -> None:
    """One JSON line of evidence on stdout, before the last line."""
    print(json.dumps({"chipbench": kind, **fields}), flush=True)


def load_json(*parts) -> dict:
    with open(os.path.join(ROOT, *parts)) as f:
        return json.load(f)


def conf_text(cfg: dict, rehearsal: bool) -> str:
    """The configuration's ``.conf`` as it is run. A rehearsal swaps the
    sizes the configuration's file lists for toy ones, same keys."""
    with open(os.path.join(ROOT, cfg["conf"])) as f:
        text = f.read()
    if rehearsal:
        for key, value in cfg["rehearsal"]["conf"].items():
            text, n = re.subn(
                rf"(?m)^(\s*{key}:\s*)\S+", rf"\g<1>{value}", text
            )
            if n != 1:
                raise ValueError(f"{cfg['conf']}: {n} lines set {key}")
    return text


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
    """The benchmark's own spans around the trainer's two calls per
    launch, ``_submit_prepped`` and ``collect``, wrapped on the instance
    (the program's code is untouched), and what hangs on them: which
    launches count, when the feed ends, when the counters are read and
    when the profiler runs.

    Until ``open()`` every launch is warm-up. The window's time runs
    from its first submit, where set-up ends; a launch counts if it was
    submitted before the deadline, and the feed ends at the first launch
    boundary after it."""

    def __init__(self, worker, registry, mix: dict, seconds: float,
                 launch_minibatches: int, trace_dir):
        self.rows = []  # one dict per launch, in submission order
        self.first = []  # the first minibatches fed, for the oracle
        self.fed = self.batches = 0
        self.before = self.after = self.deadline = None
        self.trace_t0 = self.trace_t1 = None
        self.trace_cost = {}
        self._registry, self._mix = registry, mix
        self._T, self._trace_dir = launch_minibatches, trace_dir
        self._opened = False
        by_ts = {}
        submit, collect = worker._submit_prepped, worker.collect
        # the worker's running totals, not the record collect() returns:
        # the scheduler's progress printer empties that one
        total = worker.progress

        def timed_submit(prepped, **kw):
            now = time.perf_counter()
            if self._opened and self.deadline is None:
                self.deadline = now + seconds
            row = {"submit": now, "collect": None,
                   "counted": self._opened and now < self.deadline}
            ts = submit(prepped, **kw)
            by_ts[ts] = row
            self.rows.append(row)
            return ts

        def timed_collect(ts):
            n0, k0 = total.num_examples_processed, len(total.objective)
            prog = collect(ts)
            row = by_ts.pop(ts)
            row["collect"] = time.perf_counter()
            row["examples"] = total.num_examples_processed - n0
            row["objective"] = sum(total.objective[k0:])
            if self._opened:
                self._after_collect(row["collect"])
            return prog

        worker._submit_prepped = timed_submit
        worker.collect = timed_collect

    def feed(self, source):
        """What the trainer reads. Runs on the ingest feeder thread."""
        for batch in source:
            if len(self.first) < self._mix["parity_minibatches"]:
                self.first.append(batch)
            self.batches += 1
            self.fed += batch.n
            yield batch
            if (
                self.deadline is not None
                and self.batches % self._T == 0
                and time.perf_counter() >= self.deadline
            ):
                return

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

        # the traced stretch begins and ends here, on the trainer's
        # thread, at a collect: a launch is queued behind, so the device
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


def make_data(cell: dict, mix: dict, seed: int) -> str:
    """The cell's file, made from ``--seed`` and kept by seed, so that
    only the first run with a seed generates it."""
    from parameter_server_tpu.data.text_parser import ExampleParser

    if not ExampleParser(mix["format"]).use_native:
        raise RuntimeError(
            f"format {mix['format']!r} would take the Python line path"
        )
    path = os.path.join(
        CACHE, "data",
        f"{cell['traffic']}.r{mix['rows']}.v{mix['vocabulary']}.s{seed}.txt",
    )
    if not os.path.exists(path):
        synth.write_criteo_file(path, mix["rows"], mix["vocabulary"], seed)
    return path


def build_trainer(entry: dict, cfg: dict, mix: dict, data: str,
                  rehearsal: bool):
    """Postoffice, scheduler, worker and reader as ``apps/linear/main.py``
    builds them, from the benchmark's copy of the conf."""
    from parameter_server_tpu.apps.linear.async_sgd import (
        AsyncSGDScheduler,
        AsyncSGDWorker,
    )
    from parameter_server_tpu.apps.linear.config import parse_conf
    from parameter_server_tpu.learner.sgd import MinibatchReader
    from parameter_server_tpu.system.postoffice import Postoffice

    conf = parse_conf(conf_text(cfg, rehearsal))
    sgd = conf.async_sgd
    if not rehearsal:
        # the configuration's file states the sizes; the .conf runs them
        differ = {k: (v, getattr(sgd, k)) for k, v in cfg["async_sgd"].items()
                  if getattr(sgd, k) != v}
        if differ:
            raise ValueError(f"{entry['file']} and {cfg['conf']}: {differ}")
    po = Postoffice.instance().start(**cfg["mesh"])
    # main.py's --heartbeat-timeout, set as an operator of these tables
    # would: a worker beats only in collect(), and a cold compile of the
    # step (18 s at 2^29) sits inside one
    aux = po.start_aux(heartbeat_timeout=120.0)
    aux.start(check_interval=2.0, dashboard_interval=0.0)
    sched = AsyncSGDScheduler(conf)
    sched.run()
    worker = AsyncSGDWorker(conf)
    worker.attach_monitor(sched)
    aux.register(worker.name)
    reader = MinibatchReader(
        # one file reread in passes. The reader globs every entry, and a
        # stat is dear in the chip machine's sandbox: 65,536 entries took
        # 11 s of every set-up
        files=[data] * 1024,
        minibatch_size=sgd.minibatch,
        data_format=mix["format"],
    )
    if sgd.tail_feature_freq > 0:
        reader.init_filter(
            sgd.countmin_n, sgd.countmin_k, sgd.tail_feature_freq
        )
    return conf, po, worker, reader


def run_checks(win: Window, warm: list, rows: list, conf, cfg: dict,
               worker, compiled_in_window: list, inv0: dict,
               rehearsal: bool) -> bool:
    """The checks that decide ``correct``, each printed with its
    numbers."""
    from parameter_server_tpu.telemetry import device as device_tel
    from parameter_server_tpu.telemetry import learning

    sgd = conf.async_sgd
    T = max(1, sgd.steps_per_launch)
    results = []

    def check(name, ok, **numbers):
        results.append(bool(ok))
        note("check", name=name, ok=bool(ok), **numbers)

    t = time.perf_counter()
    dev_ll = sum(r["objective"] for r in warm) / sum(
        r["examples"] for r in warm
    )
    lambdas = list(conf.penalty.lambda_) + [0.0]
    ref_ll = oracle.progressive_logloss(
        win.first, sgd.num_slots, conf.learning_rate.alpha,
        conf.learning_rate.beta, lambdas[0], lambdas[1],
    )
    tol = max(0.01, 0.02 * ref_ll)
    check(
        "logloss_parity",
        len(warm) * T == len(win.first) and abs(dev_ll - ref_ll) <= tol,
        device=dev_ll, oracle=ref_ll, tolerance=tol,
        minibatches=len(win.first), oracle_s=time.perf_counter() - t,
    )
    plane = learning.snapshot_all()[worker.name]
    st = plane["staleness"]
    tau = cfg["guarantees"]["max_delay"]
    check(
        "staleness_within_max_delay",
        sgd.max_delay == tau and st["observed_max"] <= tau
        and st["within_bound"],
        max_delay=tau, conf_max_delay=sgd.max_delay,
        observed_max=st["observed_max"], live_tau=st.get("live_tau"),
    )
    check(
        "examples_confirmed", plane["examples"] == win.fed,
        confirmed=plane["examples"], fed=win.fed,
    )
    paths = {
        s["labels"]["path"]: s["value"]
        for s in win.after["ps_ftrl_update_path_total"]["series"]
    }
    check(
        "update_path_on_device",
        paths and (rehearsal or not paths.get("ref")),
        ministeps_by_path=paths, note=cfg.get("update_path_today"),
    )
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
        not any(numbers.values()), **numbers,
        programs={n: f["calls"] for n, f in inv["functions"].items()
                  if f["calls"]},
    )
    deaths = counter_total(win.after, "ps_recovery_deaths_total")
    check("no_node_declared_dead", deaths == 0, deaths=deaths)
    bad = sum(not math.isfinite(r["objective"]) for r in warm + rows)
    check(
        "losses_finite", bad == 0, launches=len(warm + rows), not_finite=bad,
        first=warm[0]["objective"] / warm[0]["examples"],
        last=rows[-1]["objective"] / rows[-1]["examples"],
    )
    return all(results)


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

    compiles = Compiles(jax)
    t_imports = time.perf_counter()
    data = make_data(cell, mix, args.seed)
    t_data = time.perf_counter()
    conf, po, worker, reader = build_trainer(
        entry, cfg, mix, data, args.rehearsal
    )
    t_built = time.perf_counter()
    T = max(1, conf.async_sgd.steps_per_launch)
    trace_dir = (
        os.path.join(CACHE, "trace", args.workload) if args.trace else None
    )
    win = Window(worker, telreg.default_registry(), mix, args.seconds, T,
                 trace_dir)
    sink = ListSink()
    if args.trace:
        spans.install_sink(sink)
    try:
        with reader:
            source = win.feed(iter(reader))
            # warm-up: this cell's shapes and no others
            worker.train(
                itertools.islice(source, mix["warmup_launches"] * T)
            )
            warm = list(win.rows)
            device_tel.mark_warmup()
            inv0 = device_tel.snapshot()
            compiled_before = len(compiles.names)
            wall0 = time.time()
            win.open()
            worker.train(source)
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
        examples=examples, ministeps_per_launch=T,
        launch_ms_min=1e3 * min(latencies),
        launch_ms_max=1e3 * max(latencies),
        drain_s=t_drained - last_collect, data_passes=win.fed / mix["rows"],
    )
    counters = {"before": win.before, "after": win.after}
    stages = {
        stage: {
            name: registry_delta.read(counters, {
                "metric": "ps_ingest_stage_seconds", "field": field,
                "labels": {"stage": stage},
            })
            for name, field in (("s", "sum"), ("batches", "count"))
        }
        for stage in sorted({
            x["labels"]["stage"]
            for x in win.after["ps_ingest_stage_seconds"]["series"]
        })
    }
    note("ingest_stages_in_window", **stages)
    correct = run_checks(
        win, warm, rows, conf, cfg, worker,
        compiles.names[compiled_before:], inv0, args.rehearsal,
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
        tr = trace.load(trace_file)
        device.update(busy_s=tr.busy_s(), window_s=tr.window_s)
        breakdown = trace.breakdown(tr)
        values.update(per_layer(bench, args.workload, {
            "before": win.before, "after": win.after, "trace": tr,
            "spans": [e for e in sink.events
                      if e.get("t_wall", 0.0) >= wall0],
            "ministeps_per_launch": T, "config": cfg,
            "conf": conf.async_sgd, "device_kind": kind,
        }))
        note(
            "trace", file=os.path.relpath(trace_file, ROOT),
            devices=sorted(tr.ops),
            op_events=sum(map(len, tr.ops.values())),
            traced_from_launch=mix["trace_after_launches"],
            **win.trace_cost,
        )

    po.stop()  # stops the aux runtime and the executors' threads
    return lastline.emit(
        bench, args.workload, bool(args.trace), correct=correct,
        attempted=len(rows),
        failed=sum(not math.isfinite(r["objective"]) for r in rows),
        values=values, device=device, breakdown=breakdown,
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

"""The program's spans on the profiler's clock (PR 26): the bridge in
``telemetry/spans``, the trainer-loop and dispatch spans and counters,
and the benchmark's readers of them (``chipbench/hostspans.py``,
``readers/trace_idle_by_host.py``). CPU only: a capture here has a host
process and no device track, so the device side of the readers is
checked on the two recorded TPU captures in ``chipbench/fixtures``.
"""

from __future__ import annotations

import importlib
import json
import os
import sys
import threading
import time

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from chipbench import hostspans, trace  # noqa: E402
from chipbench.readers import (  # noqa: E402
    registry_delta,
    trace_idle_by_host,
    trace_idle_share,
)
from parameter_server_tpu.system.postoffice import Postoffice  # noqa: E402
from parameter_server_tpu.telemetry import registry as treg  # noqa: E402
from parameter_server_tpu.telemetry import spans  # noqa: E402

METRICS = os.path.join(REPO, "chipbench", "metrics")
IDLE_METRICS = {
    "idle_in_program_share": "in_program",
    "idle_in_dispatch_share": "dispatch",
    "idle_waiting_ingest_share": "ingest",
    "idle_unattributed_share": "unattributed",
}
# read from the program's own counters and spans; in BENCHMARK.json since
# PR 27, whose parent has them (the harness refuses a line that lacks a
# metric, so they waited for that)
HOST_SIDE = ["reader_wait_share", "reader_serial_s_per_mex", "collect_host_ms"]
with open(os.path.join(REPO, "BENCHMARK.json")) as _f:
    PER_LAYER = {m["name"]: m for m in json.load(_f)["per_layer"]}


class ListSink:
    def __init__(self):
        self.events = []

    def emit(self, event):
        self.events.append(event)

    def close(self):
        pass


@pytest.fixture()
def sink():
    s = ListSink()
    prev = spans.install_sink(s)
    yield s
    spans.install_sink(prev)


def metric_spec(name: str) -> dict:
    with open(os.path.join(METRICS, name + ".json")) as f:
        return json.load(f)


def capture(tmp_path, body) -> list:
    """Run ``body`` inside a CPU profiler capture; its ``ps.*`` events."""
    import jax

    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    try:
        body()
    finally:
        jax.profiler.stop_trace()
    found, _ = hostspans.host_events(trace.newest_trace_file(str(tmp_path)))
    return found


def on_worker_thread():
    def work():
        with spans.flow_scope(5), spans.span("unit.worker", ts=9):
            time.sleep(0.002)

    t = threading.Thread(target=work, name="unit-worker")
    t.start()
    t.join()
    with spans.span("unit.main"):
        time.sleep(0.002)


# -- A. the bridge ---------------------------------------------------------


def test_capture_holds_spans_with_flow_and_ts_while_a_sink_is_installed(
    tmp_path, sink
):
    found = {s.name: s for s in capture(tmp_path, on_worker_thread)}
    assert set(found) == {"ps.unit.worker", "ps.unit.main"}
    worker, main = found["ps.unit.worker"], found["ps.unit.main"]
    assert worker.args == {"flow": "5", "ts": "9"}
    assert main.args == {}  # only the keys that are set
    assert worker.tid != main.tid  # from any thread, on its own track
    assert worker.dur >= 0.002 and main.dur >= 0.002
    assert main.start >= worker.end  # one clock: the join came first
    # and the sink got the same two intervals, as ever
    assert [e["name"] for e in sink.events] == ["unit.worker", "unit.main"]


def test_capture_holds_no_span_without_a_sink(tmp_path):
    assert spans.get_sink() is None
    assert capture(tmp_path, on_worker_thread) == []


def test_span_without_sink_or_histogram_builds_nothing(monkeypatch):
    def boom(*a, **k):
        raise AssertionError("tracing is off")

    for name in ("emit", "_capture_interval", "current_flow"):
        monkeypatch.setattr(spans, name, boom)
    monkeypatch.setattr(spans.time, "time", boom)
    with spans.span("unit.off", ts=3, detail="x") as found:
        assert found is None


def test_span_with_histogram_alone_observes_and_emits_nothing(monkeypatch):
    monkeypatch.setattr(spans, "_capture_interval", None)  # never called
    seen = []

    class Hist:
        def observe(self, seconds):
            seen.append(seconds)

    with spans.span("unit.hist", histogram=Hist()):
        time.sleep(0.001)
    assert len(seen) == 1 and seen[0] >= 0.001


def test_a_block_adds_what_it_learns_to_its_event(sink):
    with spans.span("unit.learns", pipeline="p") as found:
        found["flow"] = 41
    (event,) = sink.events
    assert event["flow"] == 41 and event["pipeline"] == "p"


# -- B. spans and counters where the work happens ---------------------------


@pytest.fixture(scope="module")
def toy_run():
    """One pipelined ``AsyncSGDWorker.train`` behind a slow reader, after
    a warm-up pass that compiles: the registry around it, its wall time
    and its span events. One run for the module (its tests only read it),
    long enough that starting and joining the pipeline's threads, which
    no phase covers, stay far under the 5% the phases are held to on a
    loaded host."""
    from parameter_server_tpu.apps.linear.async_sgd import AsyncSGDWorker
    from parameter_server_tpu.apps.linear.config import (
        Config,
        LearningRateConfig,
        PenaltyConfig,
        SGDConfig,
    )
    from parameter_server_tpu.learner.sgd import MinibatchReader
    from parameter_server_tpu.utils.sparse import random_sparse

    if not treg.enabled():
        pytest.skip("telemetry disabled")
    Postoffice.reset()  # closes any sink: install ours after it
    sink = ListSink()
    spans.install_sink(sink)
    conf = Config()
    conf.penalty = PenaltyConfig(type="l1", lambda_=[0.01])
    conf.learning_rate = LearningRateConfig(type="decay", alpha=0.5, beta=1.0)
    conf.async_sgd = SGDConfig(
        algo="ftrl", ada_grad=True, minibatch=256, num_slots=2048,
        max_delay=2, ell_lanes=8, wire="bits", steps_per_launch=4,
    )
    mesh = Postoffice.instance().start().mesh
    worker = AsyncSGDWorker(conf, mesh=mesh)

    def batches(n, pause):
        for i in range(n):
            time.sleep(pause)
            yield random_sparse(256, 512, 8, seed=i, binary=True)

    worker.train(batches(8, 0.0), pipelined=True)  # compiles
    reg = treg.default_registry()
    del sink.events[:]
    before = reg.export_state()
    t0 = time.perf_counter()
    with MinibatchReader(batches=batches(48, 0.02)) as reader:
        worker.train(iter(reader), pipelined=True)
    wall = time.perf_counter() - t0
    after = reg.export_state()
    yield {"before": before, "after": after, "wall": wall,
           "spans": list(sink.events), "launches": 12}
    spans.install_sink(None)
    Postoffice.reset()


def loop_seconds(run, phase=None) -> float:
    spec = {"metric": "ps_train_loop_seconds", "field": "sum"}
    if phase:
        spec["labels"] = {"phase": phase}
    return registry_delta.read(run, spec)


def test_toy_train_emits_the_loop_dispatch_and_wait_spans(toy_run):
    names = [e["name"] for e in toy_run["spans"]]
    launches = toy_run["launches"]
    for name in ("train.submit", "train.collect.wait", "train.collect.host",
                 "executor.run", "executor.step"):
        assert names.count(name) == launches, (name, names.count(name))
    # one wait per item and one that finds the end of the stream
    assert names.count("train.wait_ingest") == launches + 1
    waits = {e["pipeline"] for e in toy_run["spans"]
             if e["name"] == "ingest.wait"}
    assert waits == {"minibatch_reader", "train_ingest"}
    assert "ingest.heat" in names and "executor.materialize" in names
    # executor.run sits under the submitter's flow and the step's ts
    steps = {e["ts"]: e for e in toy_run["spans"]
             if e["name"] == "executor.step"}
    for run in (e for e in toy_run["spans"] if e["name"] == "executor.run"):
        step = steps[run["ts"]]
        assert run["flow"] == step["flow"]
        assert run["dur_s"] == pytest.approx(step["run_s"], abs=2e-3)
        assert run["thread"] != threading.current_thread().name


def test_the_four_loop_phases_sum_to_the_loops_wall_time(toy_run):
    phases = {p: loop_seconds(toy_run, p) for p in
              ("wait_ingest", "submit", "collect_wait", "collect_host")}
    assert all(v > 0 for v in phases.values()), phases
    assert sum(phases.values()) == pytest.approx(loop_seconds(toy_run))
    assert sum(phases.values()) == pytest.approx(toy_run["wall"], rel=0.05)
    # a reader that sleeps 20 ms a batch keeps the trainer waiting
    assert phases["wait_ingest"] > 0.5 * toy_run["wall"]


def test_stage_seconds_summed_over_stage_are_unchanged_by_the_label(toy_run):
    """``ingest_host_s_per_mex`` sums ``ps_ingest_stage_seconds`` over
    every series: the ``pipeline`` label splits the sum and adds
    nothing to it."""
    after = toy_run["after"]["ps_ingest_stage_seconds"]["series"]
    assert {tuple(sorted(s["labels"])) for s in after} == {
        ("pipeline", "stage")
    }

    def total(**labels):
        return registry_delta.read(toy_run, {
            "metric": "ps_ingest_stage_seconds", "field": "sum",
            "labels": labels,
        })

    by_stage = {s: total(stage=s) for s in ("read", "filter", "prep", "upload")}
    by_pipe = {p: total(pipeline=p) for p in
               ("minibatch_reader", "train_ingest", "device_uploader")}
    assert total() == pytest.approx(sum(by_stage.values()))
    assert total() == pytest.approx(sum(by_pipe.values()))
    assert by_stage["read"] == pytest.approx(
        total(stage="read", pipeline="minibatch_reader")
        + total(stage="read", pipeline="train_ingest")
    )
    # the second feeder's read is its wait on the first one's queue
    waited = registry_delta.read(toy_run, {
        "metric": "ps_ingest_wait_seconds", "field": "sum",
        "labels": {"queue": "minibatch_reader"},
    })
    assert 0 < waited <= total(stage="read", pipeline="train_ingest")


@pytest.mark.parametrize("name", HOST_SIDE + ["executor_run_ms"])
def test_counter_and_span_metrics_read_a_toy_run(toy_run, name):
    spec = metric_spec(name)
    reader = importlib.import_module("chipbench.readers." + spec["reader"])
    value = reader.read(toy_run, spec)
    assert value is not None and value > 0, (name, value)
    if name == "reader_wait_share":
        assert 50 < value < 100
    # and on a program without the counter or the span: nothing, no raise
    bare = {"before": {}, "after": {}, "spans": []}
    assert reader.read(bare, spec) is None


# -- C. the benchmark's files ------------------------------------------------


@pytest.mark.parametrize("name", sorted(PER_LAYER))
def test_metric_file_loads_and_names_a_reader_that_exists(name):
    """Every per-layer metric of BENCHMARK.json: a file or a reader that
    a PR breaks fails here, not as ``output_malformed`` at the driver."""
    spec = metric_spec(name)
    reader = importlib.import_module("chipbench.readers." + spec["reader"])
    assert callable(reader.read)


# the LM trainer's loop metrics (PR 37), each defined on what PR 36's
# trainer already emitted: the three LM cells list them
LM_LOOP = [
    "lm_launch_interval_ms", "lm_launch_interval_late_over_early",
    "lm_submit_host_ms", "lm_collect_host_ms", "lm_moe_tail_pass_share",
]
LM_CELLS = [
    "mistral_small4_ep16.packed8k", "solar_open2_ep40.packed8k_mb1",
    "mellum2_ep4.packed8k_mb1",
]


def test_the_stalled_share_has_a_file_and_no_entry_yet():
    """Its parent has not the counter, and the harness refuses a line
    that lacks a metric: the entry is the next PR's (PERF.md section 7)."""
    spec = metric_spec("lm_stalled_launch_share")
    assert spec["reader"] == "registry_delta"
    assert spec["metric"] == "ps_lm_stalled_launches_total"
    assert spec["per"] == {"metric": "ps_lm_launch_seconds", "field": "count"}
    assert "lm_stalled_launch_share" not in PER_LAYER
    for name in LM_LOOP:
        assert PER_LAYER[name]["workloads"] == LM_CELLS, name
        assert PER_LAYER[name]["moves"] == "examples_per_s", name
    assert list(PER_LAYER)[-5:] == LM_LOOP  # appended, nothing moved


def test_span_interval_on_a_hand_made_list_of_spans():
    from chipbench.readers import span_interval

    def waits(ends):
        # out of order, among other spans, a wait of 0.25 s each
        events = [{"name": "train.collect.wait", "t_wall": e - 0.25,
                   "dur_s": 0.25} for e in reversed(ends)]
        return {"spans": events + [
            {"name": "train.submit", "t_wall": 1.0, "dur_s": 9.0},
            {"name": "train.collect.wait", "t_wall": 3.0},  # abandoned
        ]}

    median = {"span": "train.collect.wait", "stat": "median", "scale": 1e3}
    drift = {"span": "train.collect.wait", "stat": "late_over_early"}
    # seven intervals: 1, 1, 1, 5 (a stalled launch), 2, 2, 2
    ends = [10.0, 11.0, 12.0, 13.0, 18.0, 20.0, 22.0, 24.0]
    assert span_interval.read(waits(ends), median) == pytest.approx(2000.0)
    assert span_interval.read(waits(ends), drift) == pytest.approx(2.0)
    assert span_interval.read(waits(ends[:7]), drift) == pytest.approx(2.0)
    # under six intervals no thirds; one span, or none, no interval
    assert span_interval.read(waits(ends[:6]), drift) is None
    assert span_interval.read(waits(ends[:6]), median) == pytest.approx(1000.0)
    assert span_interval.read(waits(ends[:1]), median) is None
    assert span_interval.read({"spans": []}, median) is None
    with pytest.raises(ValueError, match="late_over_early"):
        span_interval.read(waits(ends), {**median, "stat": "mean"})


@pytest.fixture(scope="module")
def toy_lm_run():
    """Nine launches of a toy LM through ``apps/lm``'s trainer, two in
    flight, after one that compiles: the registry around them and their
    span events, as the harness hands them to a reader."""
    import collections

    import jax
    import numpy as np
    from jax.sharding import Mesh

    from chipbench import lm_reference
    from parameter_server_tpu.apps.lm import trainer as lm_trainer

    if not treg.enabled():
        pytest.skip("telemetry disabled")
    desc = lm_reference.description(os.path.join(
        REPO, "chipbench", "configs", "mistral_small4_ep16.json"
    ), rehearsal=True)
    trainer = lm_trainer.build_trainer(
        lm_trainer.model_from_description(desc, remat=True),
        Mesh(np.array(jax.devices()[:1]).reshape(1, 1), ("data", "server")),
        optimizer="adafactor",
    )
    trainer.init(5)
    pending = collections.deque()

    def launch(i):
        with trainer.loop_phase("wait_ingest"):
            data = trainer.place([np.asarray(jax.random.randint(
                jax.random.PRNGKey(i), (2, 64), 0, 512
            ))])
        pending.append(trainer.submit(data))

    launch(0)
    trainer.collect(pending.popleft())  # compiles
    sink, reg = ListSink(), treg.default_registry()
    prev = spans.install_sink(sink)
    try:
        before = reg.export_state()
        for i in range(1, 10):
            launch(i)
            if len(pending) >= 2:
                trainer.collect(pending.popleft())
        while pending:
            trainer.collect(pending.popleft())
        after = reg.export_state()
    finally:
        spans.install_sink(prev)
    return {"before": before, "after": after, "spans": list(sink.events)}


@pytest.mark.parametrize("name", LM_LOOP + ["lm_stalled_launch_share"])
def test_lm_loop_metrics_read_a_toy_lm_run(toy_lm_run, name):
    spec = metric_spec(name)
    reader = importlib.import_module("chipbench.readers." + spec["reader"])
    value = reader.read(toy_lm_run, spec)
    if name in ("lm_moe_tail_pass_share", "lm_stalled_launch_share"):
        assert value == 0.0  # half of the experts held; no launch stalled
    else:  # the ratio too: eight intervals, thirds of two
        assert value > 0, (name, value)
    # and on a program without the counter or the span: nothing, no raise
    bare = {"before": {}, "after": {}, "spans": []}
    assert reader.read(bare, spec) is None


def test_the_four_idle_metrics_share_one_order_of_buckets():
    assert set(IDLE_METRICS) | set(HOST_SIDE) <= set(PER_LAYER)
    specs = {n: metric_spec(n) for n in IDLE_METRICS}
    assert {n: s["bucket"] for n, s in specs.items()} == IDLE_METRICS
    orders = {json.dumps(s["buckets"]) for s in specs.values()}
    assert len(orders) == 1
    names = [b["name"] for b in specs["idle_in_program_share"]["buckets"]]
    assert names == ["dispatch", "ingest"]  # dispatch is asked first


def test_interval_helpers():
    r = trace_idle_by_host
    assert r.union([(3, 4), (0, 2), (1, 2.5)]) == [(0, 2.5), (3, 4)]
    assert r.complement([(0, 2.5), (3, 4)], 0, 5) == [(2.5, 3), (4, 5)]
    assert r.complement([], 1, 2) == [(1, 2)]
    assert r.complement([(0, 9)], 1, 2) == []
    assert r.intersect([(0, 2), (3, 6)], [(1, 4), (5, 9)]) == [
        (1, 2), (3, 4), (5, 6),
    ]
    assert r.length([(1, 2), (3, 4.5)]) == 2.5


def synthetic_capture() -> hostspans.Capture:
    """Two devices, one module each side of a gap from 4 to 6. Device A
    idles 1-2 inside its program and 4-6 between programs; a dispatch
    span covers 4-5 and a wait span 4.5-5.5. Device B is busy 0-4 and
    6-10 too, with no stall inside."""
    op = lambda a, b: trace.Op("op", "", "", a, b - a, b - a)  # noqa: E731
    tr = trace.Trace(
        ops={"A": [op(0, 1), op(2, 4), op(6, 10)],
             "B": [op(0, 4), op(6, 10)]},
        modules={"A": [("step", 0, 4), ("step", 6, 4)],
                 "B": [("step", 0, 4), ("step", 6, 4)]},
        begin=0, end=10,
    )
    found = [
        hostspans.HostSpan("ps.executor.run", 4, 1, 1, {}),
        hostspans.HostSpan("ps.train.wait_ingest", 4.5, 1, 2, {}),
        hostspans.HostSpan("ps.ingest.read", 0, 10, 3, {}),
    ]
    return hostspans.Capture("synthetic", tr, found, [("Pjit", 4, 1, 1)])


def test_idle_goes_to_the_first_bucket_with_an_open_span():
    cap = synthetic_capture()
    buckets = metric_spec("idle_in_dispatch_share")["buckets"]
    parts = trace_idle_by_host.by_bucket(cap, buckets)
    seconds = {
        dev: {k: trace_idle_by_host.length(v) for k, v in p.items()}
        for dev, p in parts.items()
    }
    assert seconds["A"] == {
        "in_program": 1, "dispatch": 1, "ingest": 0.5, "unattributed": 0.5,
    }
    assert seconds["B"] == {
        "in_program": 0, "dispatch": 1, "ingest": 0.5, "unattributed": 0.5,
    }
    line = trace_idle_by_host.gaps_line(cap, parts)
    top = line["gaps"][0]
    assert line["ps_events"] == 3 and top["s"] == 2 and top["at_s"] == 4
    assert top["bucket"] == "dispatch"
    assert dict(top["open"]) == {
        "ps.ingest.read": 2, "ps.executor.run": 1, "ps.train.wait_ingest": 1,
    }
    assert top["runtime"] == [("Pjit", 1)]


def read_idle(monkeypatch, path, capsys) -> dict:
    """The four idle metrics as run.py reads them, on one file."""
    monkeypatch.setattr(
        hostspans, "load", lambda p=None, _load=hostspans.load: _load(path)
    )
    trace_idle_by_host._printed.discard(path)
    values = {
        name: trace_idle_by_host.read({}, metric_spec(name))
        for name in IDLE_METRICS
    }
    lines = [json.loads(x) for x in capsys.readouterr().out.splitlines()]
    assert [x["chipbench"] for x in lines] == ["idle_gaps"]  # printed once
    return values, lines[0]


# hostspans.FIXTURE's known answers: seconds 0.30 to 1.90 of this PR's
# traced chip run of criteo_dense.text (one TPU v5 lite, seed
# 2147483777), a stretch in which the host was behind: three launches
# of 385 ms, each after a gap in which the trainer waits for the
# uploader while train_ingest's feeder is inside ingest.heat
FIXTURE_KNOWN = {
    "ps_events": 120, "op_events": 4229, "window_s": 1.517352,
    "idle_in_program_share": 0.000415, "idle_in_dispatch_share": 0.126377,
    "idle_waiting_ingest_share": 18.410518,
    "idle_unattributed_share": 0.162784, "device_idle_share": 18.700094,
    "gaps_s": [0.159895, 0.069845, 0.054002],
}


def test_idle_by_host_on_the_recorded_dense_capture(monkeypatch, capsys):
    cap = hostspans.load(hostspans.FIXTURE)
    assert len(cap.spans) == FIXTURE_KNOWN["ps_events"]
    assert sum(map(len, cap.trace.ops.values())) == FIXTURE_KNOWN["op_events"]
    assert cap.trace.window_s == pytest.approx(
        FIXTURE_KNOWN["window_s"], abs=1e-6
    )
    values, line = read_idle(monkeypatch, hostspans.FIXTURE, capsys)
    for name, value in values.items():
        assert value == pytest.approx(FIXTURE_KNOWN[name], abs=1e-5), name
    idle = trace_idle_share.read({"trace": cap.trace}, {})
    assert idle == pytest.approx(FIXTURE_KNOWN["device_idle_share"], abs=1e-5)
    assert sum(values.values()) == pytest.approx(idle, abs=1e-9)
    # the three long gaps: the trainer waits for the uploader, and the
    # line shows why: train_ingest's feeder is inside ingest.heat
    for gap, seconds in zip(line["gaps"], FIXTURE_KNOWN["gaps_s"]):
        assert gap["s"] == pytest.approx(seconds, abs=1e-5)
        assert gap["bucket"] == "ingest"
        open_s = dict(gap["open"])
        assert open_s["ps.train.wait_ingest"] > 0.97 * gap["s"]
        assert open_s["ps.ingest.heat"] > 0.97 * gap["s"]
        # dispatch is asked first: the call that ends the gap is its own
        assert 0 < gap["by_bucket_s"]["dispatch"] < 0.002
    # ps.executor.run is where the runtime's own event of the call is
    runs = [s for s in cap.spans if s.name == "ps.executor.run"]
    calls = [(a, d) for n, a, d, _ in cap.runtime if n.startswith("PjitFunction")]
    assert len(runs) == 4 and len(calls) == 8  # the runtime logs each twice
    for run in runs:
        inside = [(a, d) for a, d in calls
                  if a >= run.start and a + d <= run.end]
        assert len(inside) == 2 and run.args["ts"].isdigit()


def test_idle_by_host_on_a_capture_without_ps_events(monkeypatch, capsys):
    """The recorded bigtable capture is of a program from before the
    bridge: nothing attributes its idle between programs, all of which
    reads ``unattributed`` (the four gaps the ledger's PR 24 rows list),
    and the four still sum to ``device_idle_share``. It does not read as
    nothing: the benchmark's files also run on the parent commit, whose
    last line the harness refuses if a metric is missing."""
    values, line = read_idle(monkeypatch, trace.FIXTURE, capsys)
    idle = trace_idle_share.read({"trace": trace.load(trace.FIXTURE)}, {})
    assert sum(values.values()) == pytest.approx(idle, abs=1e-9)
    assert values["idle_in_dispatch_share"] == 0.0
    assert values["idle_waiting_ingest_share"] == 0.0
    assert values["idle_unattributed_share"] == pytest.approx(0.349060, abs=1e-5)
    assert values["idle_in_program_share"] == pytest.approx(0.000168, abs=1e-5)
    assert line["ps_events"] == 0
    assert [g["bucket"] for g in line["gaps"][:4]] == ["unattributed"] * 4
    # what the runtime's own events say of them, by hand in ISSUE 26
    assert line["gaps"][0]["runtime"][0][0] == "PjitFunction(jit(snap_impl))"


def test_load_falls_back_to_the_fixture_without_a_device_capture(
    monkeypatch, tmp_path
):
    monkeypatch.setattr(hostspans, "CACHE_TRACES", str(tmp_path))
    assert hostspans.load().file == hostspans.FIXTURE  # no capture at all
    run = tmp_path / "cell" / "plugins" / "profile" / "t"
    run.mkdir(parents=True)
    import gzip

    with gzip.open(run / "vm.trace.json.gz", "wt") as f:
        json.dump({"traceEvents": [
            {"ph": "M", "pid": 1, "name": "process_name",
             "args": {"name": "/host:CPU"}},
            {"ph": "X", "pid": 1, "tid": 1, "ts": 0, "dur": 5,
             "name": "ps.x"},
        ]}, f)
    assert hostspans.load().file == hostspans.FIXTURE  # a CPU capture

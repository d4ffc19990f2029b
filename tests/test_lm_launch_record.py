"""The LM trainer's launch record (PR 37): every launch one flow and one
``train.launch`` record, kept in the flight recorder with tracing off,
and a stalled launch named where it happens.

A toy LM on the CPU, one compiled step for the module. The detector's
cases run a closed loop with two launches in flight on a clock the test
hands the trainer and moves itself (the wait for a launch's loss moves
it by a step): no case sleeps and none compares host timings.
"""

from __future__ import annotations

import collections
import gc
import json
import logging
import os
import sys

import jax
import numpy as np
import pytest
from jax.sharding import Mesh

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from chipbench import lm_reference as ref  # noqa: E402
from parameter_server_tpu.apps.lm import trainer as lm_trainer  # noqa: E402
from parameter_server_tpu.telemetry import blackbox, device, host  # noqa: E402
from parameter_server_tpu.telemetry import registry as telreg  # noqa: E402
from parameter_server_tpu.telemetry import spans  # noqa: E402

CONFIG = os.path.join(ROOT, "chipbench", "configs", "mistral_small4_ep16.json")
PHASES = ("train.wait_ingest", "train.submit", "train.collect.wait",
          "train.collect.host")
RECORD_KEYS = {
    "kind", "name", "launch", "flow", "t_wall", "dur_s", "ingest_s",
    "submit_s", "wait_s", "host_s", "outside_s", "interval_s", "tokens",
    "tail_passes", "stalled",
}
EVIDENCE_KEYS = {
    "thread_wall_s", "thread_cpu_s", "rusage", "gc", "compiles", "hbm",
    "pressure",
}


class ListSink:
    def __init__(self):
        self.events = []

    def emit(self, event):
        self.events.append(event)

    def close(self):
        pass


@pytest.fixture(scope="module")
def toy():
    """The cell's rehearsal sizes, compiled once: ``(cfg, step)``."""
    desc = ref.description(CONFIG, rehearsal=True)
    cfg = lm_trainer.model_from_description(desc, remat=True)
    first = lm_trainer.build_trainer(cfg, _mesh(), optimizer="adafactor")
    return cfg, first.step


@pytest.fixture()
def ring():
    """This process's flight recorder, empty and small, dropped after."""
    rec = blackbox.FlightRecorder(capacity=16)
    with blackbox._reg_lock:
        blackbox._recorders[rec.node_id] = rec
    yield rec
    blackbox.drop_recorder(rec.node_id)


@pytest.fixture()
def sink():
    s = ListSink()
    prev = spans.install_sink(s)
    yield s
    spans.install_sink(prev)


def _mesh() -> Mesh:
    return Mesh(np.array(jax.devices()[:1]).reshape(1, 1), ("data", "server"))


def real_trainer(toy):
    cfg, step = toy
    trainer = lm_trainer.build_trainer(cfg, _mesh(), optimizer="adafactor")
    trainer.step = step  # the module's one compiled program
    trainer.init(3)
    return trainer


def batch(i: int = 0) -> np.ndarray:
    return np.asarray(
        jax.random.randint(jax.random.PRNGKey(i), (2, 64), 0, 512)
    )


def two_in_flight(trainer, launches: int) -> list:
    """The benchmark's loop: ``(launch, loss)`` in order of collect."""
    pending, out = collections.deque(), []
    for i in range(launches):
        with trainer.loop_phase("wait_ingest"):
            data = trainer.place([batch(i)])
        pending.append(trainer.submit(data))
        if len(pending) >= 2:
            out.append((pending[0], trainer.collect(pending.popleft())[0]))
    while pending:
        out.append((pending[0], trainer.collect(pending.popleft())[0]))
    return out


def records(ring_or_sink, name: str = "train.launch") -> list:
    events = (
        ring_or_sink.dump()["events"] if hasattr(ring_or_sink, "dump")
        else ring_or_sink.events
    )
    return [e for e in events if e.get("name") == name]


def stalled_total() -> dict:
    state = telreg.default_registry().export_state()
    return {
        s["labels"]["where"]: s["value"]
        for s in state.get("ps_lm_stalled_launches_total", {}).get("series", ())
    }


# -- A. a launch is one flow -------------------------------------------------


def test_the_phases_and_the_record_of_a_launch_share_its_flow(toy, ring, sink):
    done = two_in_flight(real_trainer(toy), 3)
    flows = [launch.flow for launch, _ in done]
    assert len(set(flows)) == 3 and all(flows)
    for launch, _ in done:
        mine = [e for e in sink.events if e.get("flow") == launch.flow]
        assert sorted(e["name"] for e in mine) == sorted(
            PHASES + ("train.launch",)
        )
    # two launches in flight: launch 1 was submitted before launch 0 was
    # collected, and neither's events carry the other's flow
    by_name = {(e["name"], e["flow"]): e for e in sink.events}
    assert (
        by_name["train.submit", flows[1]]["t_wall"]
        <= by_name["train.collect.wait", flows[0]]["t_wall"]
    )
    assert all(e.get("flow") in flows for e in sink.events)


def test_a_capture_holds_the_flow_on_each_train_interval(toy, ring, sink,
                                                         tmp_path):
    from chipbench import hostspans, trace

    trainer = real_trainer(toy)
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    try:
        done = two_in_flight(trainer, 2)
    finally:
        jax.profiler.stop_trace()
    found, _ = hostspans.host_events(trace.newest_trace_file(str(tmp_path)))
    train = [s for s in found if s.name.startswith("ps.train.")]
    assert len(train) == 8
    assert {s.args.get("flow") for s in train} == {
        str(launch.flow) for launch, _ in done
    }


# -- the record ---------------------------------------------------------------


def test_the_record_has_its_fields_and_its_parts_sum_to_the_interval(
        toy, ring, sink):
    done = two_in_flight(real_trainer(toy), 4)
    got = records(sink)
    assert [r["launch"] for r in got] == [0, 1, 2, 3]
    for (launch, loss), r in zip(done, got):
        assert set(r) == RECORD_KEYS | {"thread"}
        assert np.isfinite(loss) and r["flow"] == launch.flow
        assert r["tokens"] == 128 and r["stalled"] is False
        assert r["tail_passes"] == 0  # half of the experts held: no tail
        parts = sum(r[k] for k in (
            "ingest_s", "submit_s", "wait_s", "host_s", "outside_s"
        ))
        assert parts == pytest.approx(r["interval_s"], abs=1e-9)
        assert 0 <= r["outside_s"] < r["interval_s"]
        assert r["dur_s"] >= r["wait_s"] + r["host_s"]
    # a launch's own collect is in ITS record; with two in flight the
    # ingest and submit inside that interval are the next launch's. The
    # record's clock reads enclose the span's own
    span_s = {(e["name"], e["flow"]): e["dur_s"] for e in sink.events}
    assert got[1]["wait_s"] >= span_s["train.collect.wait", got[1]["flow"]]
    assert got[1]["host_s"] >= span_s["train.collect.host", got[1]["flow"]]
    assert got[1]["submit_s"] >= span_s["train.submit", got[2]["flow"]]
    assert got[1]["ingest_s"] >= span_s["train.wait_ingest", got[2]["flow"]]


def test_the_histograms_count_each_launch(toy, ring):
    def counts():
        state = telreg.default_registry().export_state()
        return [
            sum(s["count"] for s in state.get(name, {}).get("series", ()))
            for name in ("ps_lm_launch_seconds",
                         "ps_lm_launch_interval_seconds")
        ]

    before = counts()
    two_in_flight(real_trainer(toy), 3)
    assert [a - b for a, b in zip(counts(), before)] == [3, 3]


# -- B. kept with tracing off -------------------------------------------------


def test_without_a_sink_nothing_is_emitted_and_the_ring_holds_a_record_a_launch(
        toy, ring, monkeypatch):
    def boom(*a, **k):
        raise AssertionError("tracing is off")

    assert spans.get_sink() is None
    monkeypatch.setattr(spans, "emit", boom)
    monkeypatch.setattr(spans, "_capture_interval", boom)
    two_in_flight(real_trainer(toy), 5)
    assert spans.get_sink() is None  # and the trainer installed none
    got = records(ring)
    assert [r["launch"] for r in got] == [0, 1, 2, 3, 4]
    assert all(set(r) == RECORD_KEYS for r in got)


def test_the_ring_is_bounded_by_its_capacity(ring):
    loop(stub_trainer(Clock()), 40)
    dump = ring.dump()
    assert len(dump["events"]) == ring.capacity == 16
    assert dump["events_total"] == 40 and dump["dropped"] == 24
    assert [e["launch"] for e in dump["events"]] == list(range(24, 40))


def test_an_armed_ring_gets_each_record_once(toy, ring):
    blackbox.arm(ring)
    try:
        two_in_flight(real_trainer(toy), 2)
    finally:
        blackbox.disarm()
    assert [r["launch"] for r in records(ring)] == [0, 1]


# -- C. a stalled launch is named where it happens ------------------------------


class Clock:
    """``time.perf_counter`` for the trainer, moved by the test: each
    read moves it a little, so that no duration is 0."""

    def __init__(self):
        self.now = 1000.0

    def __call__(self) -> float:
        self.now += 1e-5
        return self.now


class Loss:
    """A launch's loss: reading it is the wait for the device."""

    def __init__(self, clock, seconds):
        self.clock, self.seconds = clock, seconds

    def __float__(self) -> float:
        self.clock.now += self.seconds
        return 1.5


class Stats(dict):
    """A launch's counts: reading them is the collect's host work."""

    def __init__(self, clock, seconds):
        super().__init__(buffer_passes=np.array([2, 1]))
        self.clock, self.seconds = clock, seconds

    def items(self):
        self.clock.now += self.seconds
        return super().items()


def stub_trainer(clock, step_s=lambda i: 1.0, delays=None):
    """A trainer on ``clock`` whose step is a stand-in: launch ``i``
    takes ``step_s(i)`` of device time, and ``delays[(i, phase)]`` more
    seconds pass inside that phase of launch ``i``."""
    desc = ref.description(CONFIG, rehearsal=True)
    cfg = lm_trainer.model_from_description(desc, remat=True)
    trainer = lm_trainer.build_trainer(cfg, _mesh(), optimizer="adafactor")
    trainer.clock, delays = clock, delays or {}
    submitted = iter(range(10 ** 6))

    def step(params, opt, *data):
        i = next(submitted)
        clock.now += delays.get((i, "submit"), 0.0)
        return params, opt, Loss(
            clock, step_s(i) + delays.get((i, "collect_wait"), 0.0)
        ), Stats(clock, delays.get((i, "collect_host"), 0.0))

    trainer.step, trainer.delays = step, delays
    return trainer


def loop(trainer, launches: int) -> None:
    """``two_in_flight`` without a device: the data is a shape."""
    clock, delays = trainer.clock, getattr(trainer, "delays", {})
    data, pending = (np.zeros((1, 8), np.int32),), collections.deque()

    collected = iter(range(launches))

    def collect():
        trainer.collect(pending.popleft())
        clock.now += delays.get((next(collected), "outside"), 0.0)

    for i in range(launches):
        with trainer.loop_phase("wait_ingest"):
            clock.now += delays.get((i, "wait_ingest"), 0.0)
        pending.append(trainer.submit(data))
        if len(pending) >= 2:
            collect()
    while pending:
        collect()


@pytest.mark.parametrize("phase", [
    "wait_ingest", "submit", "collect_wait", "collect_host", "outside",
])
def test_a_planted_delay_is_named_counted_once_and_logged_once(
        phase, ring, caplog):
    before = stalled_total()
    trainer = stub_trainer(Clock(), delays={(9, phase): 3.0})
    with caplog.at_level(logging.WARNING, logger="parameter_server_tpu"):
        loop(trainer, 14)
    after = stalled_total()
    assert {
        k: v - before.get(k, 0) for k, v in after.items()
        if v != before.get(k, 0)
    } == {phase: 1}
    stalled = [r for r in records(ring) if r["stalled"]]
    assert len(stalled) == 1
    field = lm_trainer._RECORD_FIELDS[phase]
    assert stalled[0][field] == pytest.approx(
        3.0 + (1.0 if phase == "collect_wait" else 0.0), abs=1e-3
    )
    assert stalled[0]["interval_s"] == pytest.approx(4.0, abs=1e-2)
    (line,) = [r for r in caplog.records if "train.launch.stalled" in
               r.getMessage()]
    assert line.levelno == logging.WARNING
    assert line.name == "parameter_server_tpu"
    (event,) = records(ring, "train.launch.stalled")
    assert event["where"] == phase and event["launch"] == stalled[0]


def test_the_stall_line_is_one_json_object_with_the_evidence(ring, caplog):
    trainer = stub_trainer(Clock(), delays={(12, "outside"): 2.0})
    with caplog.at_level(logging.WARNING, logger="parameter_server_tpu"):
        loop(trainer, 16)
    (message,) = [r.getMessage() for r in caplog.records]
    assert "\n" not in message
    event = json.loads(message)
    assert event["name"] == "train.launch.stalled"
    assert event["where"] == "outside"
    assert event["median_interval_s"] == pytest.approx(1.0, abs=1e-2)
    assert event["launch"]["stalled"] is True
    assert event["launch"]["outside_s"] == pytest.approx(2.0, abs=1e-3)
    # the eight records before it, oldest first
    assert [r["launch"] for r in event["before"]] == list(range(
        event["launch"]["launch"] - 8, event["launch"]["launch"]
    ))
    assert set(event["host"]) == EVIDENCE_KEYS
    assert set(event["host"]["rusage"]) == {
        "ru_majflt", "ru_minflt", "ru_nvcsw", "ru_nivcsw", "ru_utime",
        "ru_stime",
    }
    assert set(event["host"]["gc"]) == {"0", "1", "2"}
    assert event["host"]["thread_cpu_s"] <= event["host"]["thread_wall_s"] + 0.05


@pytest.mark.parametrize("case", ["compiling", "steady", "drift"])
def test_no_stall_is_declared(case, ring, caplog):
    """Before four intervals are in (a launch that compiles), on a
    steady run of 64 launches, on a drift of 10% across them."""
    before = stalled_total()
    if case == "compiling":
        trainer, n = stub_trainer(
            Clock(), delays={(0, "submit"): 60.0, (2, "collect_wait"): 9.0}
        ), 5
    elif case == "steady":
        trainer, n = stub_trainer(Clock()), 64
    else:
        trainer, n = stub_trainer(Clock(), step_s=lambda i: 1.0 + i / 640), 64
    with caplog.at_level(logging.WARNING, logger="parameter_server_tpu"):
        loop(trainer, n)
    assert stalled_total() == before
    assert not caplog.records and not records(ring, "train.launch.stalled")
    assert ring.dump()["events_total"] == n


def test_the_compiling_launches_do_not_hide_a_later_stall(ring):
    trainer = stub_trainer(
        Clock(), delays={(0, "submit"): 60.0, (7, "collect_wait"): 2.5}
    )
    loop(trainer, 10)
    (event,) = records(ring, "train.launch.stalled")
    assert event["where"] == "collect_wait"
    assert event["launch"]["launch"] == 7


# -- the process's hooks --------------------------------------------------------


def test_two_trainers_leave_one_gc_hook_and_one_compile_listener(ring):
    from jax._src import monitoring

    loop(stub_trainer(Clock()), 2)
    loop(stub_trainer(Clock()), 2)
    assert gc.callbacks.count(host._on_gc) == 1
    assert [
        fn for fn in monitoring.get_event_duration_listeners()
        if fn is device._on_compile_event
    ] == [device._on_compile_event]


def test_the_hooks_see_a_collection_and_a_compile(ring):
    host.install_hooks()
    t0, before = host.time.perf_counter(), host.mark()
    gc.collect()
    jax.jit(lambda x: x * 3 + 1)(np.arange(7.0)).block_until_ready()
    found = host.evidence(before, host.mark())
    assert found["gc"]["2"]["count"] >= 1 and found["gc"]["2"]["pause_s"] > 0
    assert found["compiles"] == device.compile_events_since(t0)
    backend = found["compiles"]["/jax/core/compile/backend_compile_duration"]
    assert backend["count"] >= 1 and backend["seconds"] > 0
    state = telreg.default_registry().export_state()
    assert any(
        s["labels"] == {"generation": "2"} and s["count"] >= 1
        for s in state["ps_host_gc_pause_seconds"]["series"]
    )

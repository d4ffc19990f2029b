"""Telemetry spine tests: registry semantics, span tracing, per-layer
instrumentation, the metrics-lint gate, and the acceptance run — one
linear-app training on the CPU mesh producing a populated registry
snapshot, a valid JSONL span trace, Prometheus exposition, and a
dashboard telemetry section (ISSUE 1 acceptance criteria)."""

from __future__ import annotations

import json
import math
import re
import threading
import time

import numpy as np
import pytest

from parameter_server_tpu.system.executor import Executor
from parameter_server_tpu.system.postoffice import Postoffice
from parameter_server_tpu.telemetry import (
    DuplicateMetricError,
    JsonlSink,
    MetricsRegistry,
    close_sink,
    default_registry,
    get_sink,
    install_sink,
    set_enabled,
    span,
)
from parameter_server_tpu.telemetry.instruments import install_all


@pytest.fixture(autouse=True)
def _fresh_telemetry():
    Postoffice.reset()  # fresh registry + closed sink
    yield
    Postoffice.reset()


# ---------------------------------------------------------------------------
# registry semantics
# ---------------------------------------------------------------------------


class TestRegistry:
    def test_counter_thread_safety(self):
        reg = MetricsRegistry()
        c = reg.counter("hits_total")
        lc = reg.counter("labeled_total", labelnames=("who",))

        def worker(i):
            child = lc.labels(who=f"t{i % 2}")
            for _ in range(5000):
                c.inc()
                child.inc()

        threads = [threading.Thread(target=worker, args=(i,)) for i in range(8)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert c.value() == 8 * 5000
        assert lc.value(who="t0") + lc.value(who="t1") == 8 * 5000

    def test_histogram_concurrent_observe(self):
        reg = MetricsRegistry()
        h = reg.histogram("obs_seconds", buckets=[1, 10])

        def worker():
            for _ in range(2000):
                h.observe(0.5)

        threads = [threading.Thread(target=worker) for _ in range(6)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert h.count() == 12000
        assert h.sum() == pytest.approx(6000.0)

    def test_histogram_percentile_math(self):
        reg = MetricsRegistry()
        h = reg.histogram("lat_seconds", buckets=list(range(1, 11)))
        for v in range(1, 11):  # one observation per bucket bound
            h.observe(v)
        # ranks land exactly on bucket bounds -> interpolation is exact
        assert h.percentile(0.5) == pytest.approx(5.0)
        assert h.percentile(0.9) == pytest.approx(9.0)
        assert h.percentile(1.0) == pytest.approx(10.0)
        # above the last finite bound clamps to the observed max
        h.observe(500.0)
        assert h.percentile(1.0) == pytest.approx(500.0)
        # empty series
        assert math.isnan(reg.histogram("empty_seconds").percentile(0.5))

    def test_duplicate_name_rejected(self):
        reg = MetricsRegistry()
        reg.counter("dup_total")
        with pytest.raises(DuplicateMetricError):
            reg.counter("dup_total")
        with pytest.raises(DuplicateMetricError):
            reg.gauge("dup_total")  # other kind, same name
        # ensure_* is idempotent on an identical declaration...
        g = reg.ensure_gauge("depth", labelnames=("executor",))
        assert reg.ensure_gauge("depth", labelnames=("executor",)) is g
        # ...but a mismatched re-declaration is still an error
        with pytest.raises(DuplicateMetricError):
            reg.ensure_gauge("depth", labelnames=("other",))
        with pytest.raises(DuplicateMetricError):
            reg.ensure_counter("depth")
        # histogram exposition suffixes are reserved
        reg.histogram("rt_seconds")
        with pytest.raises(DuplicateMetricError):
            reg.counter("rt_seconds_count")

    def test_non_snake_case_rejected(self):
        reg = MetricsRegistry()
        for bad in ("CamelCase", "has-dash", "has.dot", "9leading", ""):
            with pytest.raises(ValueError):
                reg.counter(bad)

    def test_counter_is_monotone(self):
        reg = MetricsRegistry()
        c = reg.counter("mono_total")
        with pytest.raises(ValueError):
            c.inc(-1)

    def test_render_text_prometheus_parseable(self):
        reg = MetricsRegistry()
        install_all(reg)
        reg.counter("plain_total", "with help").inc(3)
        reg.gauge("g_val", labelnames=("node",)).labels(node="W0").set(1.5)
        h = reg.histogram("h_seconds", 'esc"aped\nhelp', labelnames=("ch",))
        h.labels(ch="0").observe(0.02)
        sample = re.compile(
            r"^[a-z_][a-z0-9_]*(\{[a-zA-Z_][a-zA-Z0-9_]*=\"[^\"]*\""
            r"(,[a-zA-Z_][a-zA-Z0-9_]*=\"[^\"]*\")*\})? [^ ]+$"
        )
        text = reg.render_text()
        assert text.endswith("\n")
        for line in text.splitlines():
            assert line.startswith("# ") or sample.match(line), line
        # histogram exposition: cumulative buckets + sum/count present
        assert 'h_seconds_bucket{ch="0",le="+Inf"} 1' in text
        assert 'h_seconds_count{ch="0"} 1' in text

    def test_snapshot_shape(self):
        reg = MetricsRegistry()
        reg.counter("a_total").inc(2)
        h = reg.histogram("b_seconds", buckets=[1, 2])
        h.observe(1.5)
        snap = reg.snapshot()
        assert snap["a_total"]["type"] == "counter"
        assert snap["a_total"]["values"][""] == 2
        hv = snap["b_seconds"]["values"][""]
        assert hv["count"] == 1 and hv["sum"] == pytest.approx(1.5)
        json.dumps(snap)  # JSON-friendly end to end


# ---------------------------------------------------------------------------
# spans + executor emission
# ---------------------------------------------------------------------------


class TestSpans:
    def test_span_records_into_histogram_and_sink(self, tmp_path):
        path = str(tmp_path / "trace.jsonl")
        install_sink(JsonlSink(path))
        reg = MetricsRegistry()
        h = reg.histogram("blk_seconds")
        with span("unit.block", ts=7, histogram=h, phase="test"):
            time.sleep(0.002)
        close_sink()
        assert h.count() == 1 and h.sum() >= 0.002
        (event,) = [json.loads(l) for l in open(path)]
        assert event["name"] == "unit.block" and event["ts"] == 7
        assert event["phase"] == "test" and event["dur_s"] >= 0.002

    def test_executor_span_emission_ordering(self, tmp_path):
        path = str(tmp_path / "steps.jsonl")
        install_sink(JsonlSink(path))
        ex = Executor(name="spans", telemetry=True)
        from parameter_server_tpu.system.message import Task

        submitted = []
        submitted.append(ex.submit(lambda: np.ones(4)))
        # a dependent step: queue-wait spans the dependency's completion
        submitted.append(
            ex.submit(lambda: np.zeros(2), Task(wait_time=[submitted[0]]))
        )
        submitted.append(ex.submit(lambda: 42))
        ex.wait_all()
        ex.stop()
        close_sink()
        events = [json.loads(l) for l in open(path)]
        steps = [e for e in events if e["name"] == "executor.step"]
        assert {e["ts"] for e in steps} == set(submitted)
        for e in steps:
            assert e["executor"] == "spans"
            assert e["queue_wait_s"] >= 0
            assert e["run_s"] >= 0
            assert e["materialize_s"] >= 0
            # phase ordering invariant: queue-wait can never exceed the
            # submit->finished total
            assert e["queue_wait_s"] <= e["total_s"] + 1e-9

    def test_executor_histograms_populate_registry(self):
        ex = Executor(name="histcheck", telemetry=True)
        for _ in range(4):
            ex.submit(lambda: np.arange(8).sum())
        ex.wait_all()
        ex.stop()
        snap = default_registry().snapshot()
        key = "executor=histcheck"
        assert (
            snap["executor_steps_finished_total"]["values"][key] == 4
        )
        for name in (
            "executor_queue_wait_seconds",
            "executor_run_seconds",
            "executor_step_total_seconds",
        ):
            hv = snap[name]["values"][key]
            assert hv["count"] == 4
            assert hv["p50"] is not None


# ---------------------------------------------------------------------------
# teardown hermeticity + lint gate
# ---------------------------------------------------------------------------


def test_postoffice_reset_resets_telemetry(tmp_path):
    reg_before = default_registry()
    reg_before.counter("leftover_total").inc()
    install_sink(JsonlSink(str(tmp_path / "s.jsonl")))
    Postoffice.reset()
    reg_after = default_registry()
    assert reg_after is not reg_before
    assert reg_after.names() == []
    assert get_sink() is None  # sink closed and uninstalled
    # the new Postoffice instance hangs onto the fresh registry
    assert Postoffice.instance().metrics is reg_after


def test_metrics_lint_passes():
    """The Makefile metrics-lint target, run in-process as a tier-1 gate."""
    import importlib.util
    import os

    path = os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "script",
        "metrics_lint.py",
    )
    spec = importlib.util.spec_from_file_location("_metrics_lint", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    assert mod.lint() == []


# ---------------------------------------------------------------------------
# what a step pays for telemetry, by count (the 10% is the chip's to say:
# doc/OBSERVABILITY.md, "What the tracing costs", has the ON-against-OFF pair)
# ---------------------------------------------------------------------------


def test_executor_telemetry_overhead_bounded(monkeypatch):
    """The per-step telemetry cost is ONE buffered record under one
    small lock, flushed outside the dispatch path; with
    ``telemetry=False`` no record is built at all.

    Held by counting the calls, as
    ``test_span_without_sink_or_histogram_builds_nothing`` holds a
    span's. A ratio of two CPU timings was held to 1.10 here and read
    1.13 to 1.42 under six test workers: what the record costs a step
    is a time, and a time comes from the chip."""
    from parameter_server_tpu.system import executor as executor_mod

    class CountingLock:
        def __init__(self, lock):
            self.lock, self.acquired = lock, 0

        def __enter__(self):
            self.acquired += 1
            return self.lock.__enter__()

        def __exit__(self, *exc):
            return self.lock.__exit__(*exc)

    calls = {"record": [], "flush_records": []}
    record = executor_mod._ExecutorTelemetry.record
    flush_records = executor_mod._ExecutorTelemetry._flush_records

    def counted_record(self, *phases):
        calls["record"].append(self.name)
        return record(self, *phases)

    def counted_flush(self, buf):
        calls["flush_records"].append((self.name, len(buf)))
        return flush_records(self, buf)

    monkeypatch.setattr(executor_mod._ExecutorTelemetry, "record",
                        counted_record)
    monkeypatch.setattr(executor_mod._ExecutorTelemetry, "_flush_records",
                        counted_flush)
    steps = 200
    assert steps < executor_mod._ExecutorTelemetry._FLUSH_AT
    work = np.random.default_rng(0).random(4096)
    on = Executor(name="ovh_on", telemetry=True)
    off = Executor(name="ovh_off", telemetry=False)
    try:
        tel = on._tel
        tel._buf_lock = lock = CountingLock(tel._buf_lock)
        for ex in (on, off):
            for _ in range(steps):
                ex.submit(lambda: float(work.sum()))
            ex.wait_all()
        # ON: one record a step, each one acquire of the one lock and one
        # append; nothing reached the registry's instruments meanwhile
        assert calls["record"] == ["ovh_on"] * steps
        assert lock.acquired == steps
        assert len(tel._buf) == steps and all(len(r) == 6 for r in tel._buf)
        assert calls["flush_records"] == []
        assert not on._step_times  # each step's times popped exactly once
        # the flush is the registry read's: the collector hook drains it
        tel.flush()
        assert calls["flush_records"] == [("ovh_on", steps)]
        assert lock.acquired == steps + 1 and tel._buf == []
        assert tel.steps.value == steps
        # OFF: no telemetry object, no times kept, no record built
        assert off._tel is None and not off._step_times
        assert "ovh_off" not in calls["record"]
    finally:
        off.stop()
        on.stop()


# ---------------------------------------------------------------------------
# layer wiring: van accounting + parameter latency + heartbeat traffic
# ---------------------------------------------------------------------------


def _wire_message(sender: str, recver: str):
    from parameter_server_tpu.system.message import Message, Task

    msg = Message(task=Task(), sender=sender, recver=recver)
    msg.values = [np.ones(64, np.float32)]
    return msg


class TestVanAccounting:
    def test_recv_counted_at_receiver(self, mesh8):
        """Satellite: wire_recv_bytes counts where from_wire actually
        ran — a failing decode must not inflate the recv counter."""
        from parameter_server_tpu.system.remote_node import RemoteNode
        from parameter_server_tpu.system.van import Van

        van = Van(mesh8)
        a, b = RemoteNode("S0"), RemoteNode("W0")
        out = van.transfer(a, b, _wire_message("W0", "S0"))
        assert out.values  # round-tripped
        assert van.wire_sent_bytes == a.wire_sent_bytes > 0
        assert van.wire_recv_bytes == b.wire_recv_bytes > 0

        class Broken(RemoteNode):
            def from_wire(self, blob):
                raise RuntimeError("decode exploded")

        sent_before, recv_before = van.wire_sent_bytes, van.wire_recv_bytes
        with pytest.raises(RuntimeError):
            van.transfer(a, Broken("W0"), _wire_message("W0", "S0"))
        assert van.wire_sent_bytes > sent_before  # frame did leave
        assert van.wire_recv_bytes == recv_before  # nothing was received

    def test_transfer_feeds_heartbeat_info(self, mesh8):
        """Satellite: increase_in/out_bytes wired into the real transfer
        path, so dashboards report true traffic."""
        Postoffice.reset()
        po = Postoffice.instance()
        po.start(num_data=4, num_server=2)
        aux = po.start_aux()
        aux.register("W0")
        aux.register("S0")
        from parameter_server_tpu.system.remote_node import RemoteNode

        van = po.van
        van.transfer(
            RemoteNode("S0"), RemoteNode("W0"), _wire_message("W0", "S0")
        )
        w0, s0 = aux.info("W0"), aux.info("S0")
        assert w0.total_out_bytes > 0  # sender side
        assert s0.total_in_bytes > 0  # receiver side
        assert w0.total_out_bytes == s0.total_in_bytes
        # the registry mirrors agree with the van's own counters
        snap = po.metrics.snapshot()
        assert (
            snap["van_wire_sent_bytes_total"]["values"][""]
            == van.wire_sent_bytes
        )
        assert (
            snap["van_wire_recv_bytes_total"]["values"][""]
            == van.wire_recv_bytes
        )
        po.stop()


def test_parameter_push_pull_latency_per_channel(mesh8):
    from parameter_server_tpu.parameter.kv_vector import KVVector

    kv = KVVector(mesh=mesh8, k=1, num_slots=32, hashed=False, name="tel_kv")
    keys = np.array([1, 5, 9], dtype=np.int64)
    kv.set_keys(3, keys)
    kv.wait(
        kv.push(
            kv.request(channel=3), keys=keys, values=np.ones((3, 1), np.float32)
        )
    )
    np.testing.assert_allclose(kv.values(3, keys), np.ones((3, 1)))
    snap = default_registry().snapshot()
    key = "store=tel_kv,channel=3"
    assert snap["ps_push_keys_total"]["values"][key] == 3
    assert snap["ps_pull_keys_total"]["values"][key] >= 3
    assert snap["ps_push_latency_seconds"]["values"][key]["count"] == 1
    assert snap["ps_pull_latency_seconds"]["values"][key]["count"] >= 1
    kv.executor.stop()


# ---------------------------------------------------------------------------
# the acceptance run: one linear-app training on the CPU mesh
# ---------------------------------------------------------------------------


def test_linear_app_run_produces_full_telemetry(tmp_path, mesh8):
    from parameter_server_tpu.apps.linear.async_sgd import AsyncSGDWorker
    from parameter_server_tpu.apps.linear.config import (
        Config,
        LearningRateConfig,
        PenaltyConfig,
        SGDConfig,
    )
    from parameter_server_tpu.utils.sparse import random_sparse

    Postoffice.reset()
    trace_path = str(tmp_path / "run.jsonl")
    install_sink(JsonlSink(trace_path))
    po = Postoffice.instance()
    po.start(num_data=4, num_server=2)
    aux = po.start_aux()
    aux.register("W0")

    conf = Config()
    conf.penalty = PenaltyConfig(type="l1", lambda_=[0.01])
    conf.learning_rate = LearningRateConfig(type="decay", alpha=0.5, beta=1.0)
    conf.async_sgd = SGDConfig(
        algo="ftrl", minibatch=256, num_slots=512, max_delay=1
    )
    worker = AsyncSGDWorker(conf, mesh=po.mesh, name="accept_worker")
    rng = np.random.default_rng(0)
    w_true = (rng.normal(size=512) * (rng.random(512) < 0.2)).astype(np.float32)
    worker.train(
        random_sparse(256, 512, 8, seed=i, w_true=w_true) for i in range(6)
    )
    # exercise the van placement path + a host wire transfer
    po.van.put_table(np.zeros((64, 2), np.float32))
    from parameter_server_tpu.system.remote_node import RemoteNode

    po.van.transfer(RemoteNode("S0"), RemoteNode("W0"), _wire_message("W0", "S0"))
    aux.beat("W0")

    # 1) registry snapshot: non-zero executor step histograms + van bytes
    snap = po.metrics.snapshot()
    key = "executor=accept_worker"
    assert snap["executor_step_total_seconds"]["values"][key]["count"] > 0
    assert snap["executor_queue_wait_seconds"]["values"][key]["count"] > 0
    assert snap["van_placed_bytes_total"]["values"][""] > 0
    assert snap["van_wire_sent_bytes_total"]["values"][""] > 0
    assert snap["app_examples_total"]["values"][""] >= 6 * 256
    assert snap["heartbeat_reports_total"]["values"]["node=W0"] >= 1

    # 2) Prometheus exposition parses
    sample = re.compile(
        r"^[a-z_][a-z0-9_]*(\{[a-zA-Z_][a-zA-Z0-9_]*=\"[^\"]*\""
        r"(,[a-zA-Z_][a-zA-Z0-9_]*=\"[^\"]*\")*\})? [^ ]+$"
    )
    for line in po.metrics.render_text().splitlines():
        assert line.startswith("# ") or sample.match(line), line

    # 3) dashboard report carries the telemetry section
    report = aux.dashboard.report()
    assert "W0" in report
    assert "telemetry:" in report
    assert "executor_step_total_seconds" in report

    # 4) valid JSONL span file with executor step events
    close_sink()
    events = [json.loads(l) for l in open(trace_path)]
    steps = [
        e
        for e in events
        if e["name"] == "executor.step" and e["executor"] == "accept_worker"
    ]
    assert steps, "linear-app run must emit executor.step spans"
    for e in steps:
        assert e["queue_wait_s"] <= e["total_s"] + 1e-9
    worker.executor.stop()
    po.stop()

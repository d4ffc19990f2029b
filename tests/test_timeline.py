"""Timeline tracing (ISSUE 7 tentpole).

Covers: flow-id propagation across the real pipeline threads (feeder →
prep pool → consumer → executor step), the serve path's flow spans
(submit → execute → coalesced flush → reply), the abandoned-span
terminator from the pool's exception-forwarding path, and the Chrome
trace-event export (schema invariants + a committed golden file).
"""

from __future__ import annotations

import json
import os
import threading

import numpy as np
import pytest

from parameter_server_tpu.system.executor import Executor
from parameter_server_tpu.system.postoffice import Postoffice
from parameter_server_tpu.telemetry import (
    JsonlSink,
    close_sink,
    current_flow,
    flow_scope,
    install_sink,
    new_flow,
)
from parameter_server_tpu.telemetry import timeline
from parameter_server_tpu.telemetry import spans as telemetry_spans

GOLDEN = os.path.join(os.path.dirname(__file__), "data", "timeline_golden.json")


@pytest.fixture(autouse=True)
def _fresh_telemetry():
    Postoffice.reset()
    yield
    Postoffice.reset()


def _trace(tmp_path):
    path = str(tmp_path / "trace.jsonl")
    install_sink(JsonlSink(path))
    return path


# ---------------------------------------------------------------------------
# flow primitives
# ---------------------------------------------------------------------------


class TestFlowScope:
    def test_ids_are_unique_and_scoped(self):
        a, b = new_flow(), new_flow()
        assert a != b
        assert current_flow() is None
        with flow_scope(a):
            assert current_flow() == a
            with flow_scope(b):
                assert current_flow() == b
            assert current_flow() == a
        assert current_flow() is None

    def test_none_scope_is_passthrough(self):
        with flow_scope(None):
            assert current_flow() is None

    def test_scope_is_thread_local(self):
        seen = {}

        def other():
            seen["flow"] = current_flow()

        with flow_scope(new_flow()):
            t = threading.Thread(target=other)
            t.start()
            t.join()
        assert seen["flow"] is None

    def test_span_attaches_active_flow(self, tmp_path):
        path = _trace(tmp_path)
        fid = new_flow()
        with flow_scope(fid):
            with telemetry_spans.span("unit.flowed"):
                pass
        with telemetry_spans.span("unit.unflowed"):
            pass
        close_sink()
        events = {e["name"]: e for e in timeline.load_events(path)}
        assert events["unit.flowed"]["flow"] == fid
        assert "flow" not in events["unit.unflowed"]
        # every event carries its emitting thread
        assert events["unit.flowed"]["thread"] == threading.current_thread().name

    def test_span_closes_with_error_attr_on_exception(self, tmp_path):
        path = _trace(tmp_path)
        with pytest.raises(ValueError):
            with telemetry_spans.span("unit.dies"):
                raise ValueError("boom")
        close_sink()
        (event,) = timeline.load_events(path)
        assert event["name"] == "unit.dies"
        assert event["error"] == "ValueError"
        assert event["dur_s"] >= 0.0


# ---------------------------------------------------------------------------
# cross-thread correlation through the real pipeline pieces
# ---------------------------------------------------------------------------


class TestPipelineFlows:
    def test_ingest_flow_rides_feeder_prep_and_executor(self, tmp_path):
        from parameter_server_tpu.learner.ingest import IngestPipeline

        path = _trace(tmp_path)
        pipe = IngestPipeline(
            range(5),
            filter_fn=lambda x: x,
            prep_fn=lambda x: x * 10,
            workers=2,
            name="flows",
        ).start()
        ex = Executor(name="flow_ex", telemetry=True)
        items = []
        for item in pipe:
            items.append(item)
            # the pipeline keeps the item's flow active on the consumer
            # thread, so a submit here correlates without plumbing
            ex.submit(lambda item=item: item + 1)
        ex.wait_all()
        ex.stop()
        close_sink()
        assert items == [0, 10, 20, 30, 40]  # bit-identical order kept
        by_flow = timeline.flows(timeline.load_events(path))
        chains = [
            [e["name"] for e in seq] for seq in by_flow.values()
        ]
        assert len(chains) == 5
        for chain in chains:
            # executor.step is placed at its submit; executor.run is the
            # dispatch thread's interval inside it, under the same flow
            assert chain == [
                "ingest.read", "ingest.filter", "ingest.prep",
                "executor.step", "executor.run",
            ]
        # the stages really ran on different threads
        threads_per_flow = [
            {e["thread"] for e in seq} for seq in by_flow.values()
        ]
        assert all(len(t) >= 2 for t in threads_per_flow)

    def test_ingest_without_sink_pays_nothing(self, monkeypatch):
        from parameter_server_tpu.learner import ingest as ingest_mod
        from parameter_server_tpu.learner.ingest import IngestPipeline

        # tracing off must mean a stage pays its histogram and nothing
        # else: one code path per stage, on which no event is built and
        # no profiler annotation is made — read, filter and prep alike
        def boom(*a, **k):
            raise AssertionError("a span was emitted with tracing off")

        monkeypatch.setattr(ingest_mod.telemetry_spans, "emit", boom)
        monkeypatch.setattr(
            ingest_mod.telemetry_spans, "_capture_interval", boom
        )
        monkeypatch.setattr(ingest_mod.telemetry_spans, "new_flow", boom)
        pipe = IngestPipeline(
            range(4),
            filter_fn=lambda x: x,
            prep_fn=lambda x: x + 1,
            workers=2,
            name="off",
        ).start()
        assert list(pipe) == [1, 2, 3, 4]
        assert current_flow() is None

    def test_device_uploader_hands_flow_to_consumer(self, tmp_path):
        from parameter_server_tpu.apps.linear.async_sgd import DeviceUploader

        path = _trace(tmp_path)

        class Prepped:
            num_examples = 4

        fids = [new_flow() for _ in range(3)]

        def source():
            for fid in fids:
                with flow_scope(fid):
                    yield Prepped(), 4

        up = DeviceUploader(source(), lambda p: p, depth=2)
        popped = []
        for _staged, n in up:
            assert n == 4
            popped.append(up.next_flow())
        up.close()
        close_sink()
        assert popped == fids  # FIFO with the item stream
        uploads = [
            e
            for e in timeline.load_events(path)
            if e["name"] == "ingest.upload"
        ]
        assert [e["flow"] for e in uploads] == fids

    def test_pool_worker_exception_emits_abandoned_terminator(self, tmp_path):
        from parameter_server_tpu.learner.ingest import IngestPipeline

        path = _trace(tmp_path)

        def prep(x):
            if x == 2:
                raise RuntimeError("poisoned batch")
            return x

        pipe = IngestPipeline(
            range(4), prep_fn=prep, workers=2, name="poison"
        ).start()
        got = []
        with pytest.raises(RuntimeError, match="poisoned batch"):
            for item in pipe:
                got.append(item)
        close_sink()
        assert got == [0, 1]  # exception at the position it occurred
        events = timeline.load_events(path)
        tombstones = [e for e in events if e.get("abandoned")]
        assert len(tombstones) == 1
        assert tombstones[0]["name"] == "poison.worker"
        assert tombstones[0]["reason"] == "RuntimeError"
        # the prep span itself closed WITH the error attr (the
        # context-managed-everywhere satellite: no open-ended spans)
        died = [e for e in events if e.get("error") == "RuntimeError"]
        assert any(e["name"] == "ingest.prep" for e in died)

    def test_executor_submit_captures_flow(self, tmp_path):
        path = _trace(tmp_path)
        ex = Executor(name="cap", telemetry=True)
        fid = new_flow()
        with flow_scope(fid):
            ts = ex.submit(lambda: 42)
        ex.wait(ts)
        ex.stop()
        close_sink()
        steps = [
            e
            for e in timeline.load_events(path)
            if e["name"] == "executor.step"
        ]
        assert steps and steps[0]["flow"] == fid


# ---------------------------------------------------------------------------
# serve-path flows: submit → execute → coalesced flush → reply
# ---------------------------------------------------------------------------


class _FakeStore:
    """Minimal pull protocol for the coalescer (no device, no mesh)."""

    def request(self, channel=0):
        return {"channel": channel}

    def pull(self, task, keys):
        self.last_keys = np.asarray(keys)
        return 7

    def wait_pull(self, ts):
        return np.stack([self.last_keys.astype(np.float32)] * 2, axis=1)


class TestServeFlows:
    def test_request_flow_spans_submit_to_reply(self, tmp_path):
        from parameter_server_tpu.serving.frontend import (
            PullRequest,
            ServeConfig,
            ServeFrontend,
        )

        path = _trace(tmp_path)
        fe = ServeFrontend(
            _FakeStore(),
            ServeConfig(replica="off", workers=1, coalesce_window_s=0.001),
        ).start()
        try:
            ticket = fe.submit(PullRequest(keys=np.array([3, 1, 2])))
            vals = ticket.result(timeout=10)
            np.testing.assert_allclose(vals[:, 0], [3, 1, 2])
            assert ticket.flow is not None
        finally:
            fe.close()
        close_sink()
        events = timeline.load_events(path)
        mine = [e for e in events if e.get("flow") == ticket.flow]
        names = [e["name"] for e in mine]
        assert names[0] == "serve.submit"
        assert "serve.execute" in names
        assert names[-1] == "serve.reply"
        # the coalescer's flush span names the request's flow as merged
        flush = [e for e in events if e["name"] == "serve.coalesce.flush"]
        assert flush and ticket.flow in flush[0]["flows"]
        # reply carries the measured latency
        reply = mine[-1]
        assert reply["latency_s"] >= 0.0

    def test_no_sink_means_no_flow_allocation(self, monkeypatch):
        from parameter_server_tpu.serving import frontend as frontend_mod
        from parameter_server_tpu.serving.frontend import (
            PullRequest,
            ServeConfig,
            ServeFrontend,
        )

        # the µs pull lane pays no span machinery when tracing is off:
        # a flow-less ticket must never enter span() on the worker
        def boom(*a, **k):
            raise AssertionError("span() entered on untraced request")

        monkeypatch.setattr(frontend_mod.telemetry_spans, "span", boom)
        fe = ServeFrontend(
            _FakeStore(),
            ServeConfig(replica="off", workers=1, coalesce_window_s=0.001),
        ).start()
        try:
            ticket = fe.submit(PullRequest(keys=np.array([1])))
            ticket.result(timeout=10)
            assert ticket.flow is None
        finally:
            fe.close()


# ---------------------------------------------------------------------------
# synthetic span events
# ---------------------------------------------------------------------------


def _span(name, t, dur, thread, flow=None, **attrs):
    ev = {
        "kind": "span", "name": name, "t_wall": t, "dur_s": dur,
        "thread": thread,
    }
    if flow is not None:
        ev["flow"] = flow
    ev.update(attrs)
    return ev


# ---------------------------------------------------------------------------
# Chrome trace export: schema + golden file
# ---------------------------------------------------------------------------


def _golden_events():
    """Fixed synthetic two-thread, two-flow timeline (stable across
    runs: hand-written wall times)."""
    return [
        _span("ingest.read", 1000.0, 0.010, "feeder", 11),
        _span("ingest.prep", 1000.012, 0.020, "pool-w0", 11),
        _span("ingest.read", 1000.011, 0.010, "feeder", 12),
        _span("ingest.prep", 1000.033, 0.020, "pool-w1", 12),
        _span(
            "serve.coalesce.flush", 1000.060, 0.005, "flusher", 13,
            merged=2, flows=[11, 12],
        ),
        {
            "kind": "span", "name": "poison.worker", "t_wall": 1000.070,
            "dur_s": 0.0, "thread": "pool-w0", "abandoned": True,
            "reason": "RuntimeError",
        },
    ]


class TestChromeExport:
    def test_schema_invariants(self):
        trace = timeline.to_chrome_trace(_golden_events())
        evs = trace["traceEvents"]
        phases = {e["ph"] for e in evs}
        assert phases == {"M", "X", "s", "f", "i"}
        # metadata names every thread track exactly once
        meta = [e for e in evs if e["ph"] == "M" and e["name"] == "thread_name"]
        assert len({m["tid"] for m in meta}) == len(meta) == 4
        # complete events carry µs ts + dur and echo their attrs
        xs = [e for e in evs if e["ph"] == "X"]
        assert all(e["dur"] >= 0 and "args" in e for e in xs)
        # flow arrows pair up: every start has a finish with the same id
        starts = [e for e in evs if e["ph"] == "s"]
        finishes = [e for e in evs if e["ph"] == "f"]
        assert sorted(e["id"] for e in starts) == sorted(
            e["id"] for e in finishes
        )
        # fan-in: both merged request flows arrow into the flush
        assert {e["id"] for e in starts} >= {11, 12}
        # abandoned tombstone is an instant event
        (inst,) = [e for e in evs if e["ph"] == "i"]
        assert "abandoned" in inst["name"]
        # valid JSON end to end
        json.dumps(trace)

    def test_matches_committed_golden(self):
        trace = timeline.to_chrome_trace(_golden_events())
        with open(GOLDEN) as f:
            golden = json.load(f)
        assert trace == golden, (
            "Chrome-trace export drifted from tests/data/timeline_golden"
            ".json — if the schema change is intentional, regenerate the "
            "golden (see its header note) and document it in "
            "doc/OBSERVABILITY.md"
        )

    def test_executor_step_renders_full_interval(self):
        # executor.step stamps t_wall at FINISH with no dur_s; the box
        # must span submit→finish, not sit as a 0-width sliver at the end
        events = [
            _span("ingest.read", 10.0, 0.1, "feeder", 1),
            {
                "kind": "span", "name": "executor.step", "t_wall": 10.8,
                "thread": "MainThread", "flow": 1, "total_s": 0.6,
                "queue_wait_s": 0.2, "run_s": 0.3, "materialize_s": 0.1,
            },
        ]
        trace = timeline.to_chrome_trace(events)
        (step,) = [
            e for e in trace["traceEvents"] if e.get("name") == "executor.step"
        ]
        assert step["dur"] == pytest.approx(0.6e6)
        assert step["ts"] == pytest.approx((10.2 - 10.0) * 1e6)

    def test_fan_in_arrow_anchors_before_flush(self):
        # the merged request's LAST span (serve.reply) postdates the
        # flush — the fan-in arrow must originate from the span
        # preceding the flush, clamped to flush start, never from the
        # future (backwards causality in Perfetto)
        events = [
            _span("serve.submit", 100.0, 0.0, "client", 21),
            _span("serve.execute", 100.010, 0.030, "serve-worker-0", 21),
            _span("serve.reply", 100.040, 0.0, "serve-worker-0", 21),
            _span(
                "serve.coalesce.flush", 100.020, 0.005, "flusher", 22,
                merged=1, flows=[21],
            ),
        ]
        trace = timeline.to_chrome_trace(events)
        flush_ts = next(
            e["ts"]
            for e in trace["traceEvents"]
            if e.get("name") == "serve.coalesce.flush"
        )
        arrows = [
            e for e in trace["traceEvents"]
            if e["ph"] in ("s", "f") and e["id"] == 21
        ]
        assert arrows
        assert all(e["ts"] <= flush_ts for e in arrows)
        assert any(e["ph"] == "f" and e["ts"] == flush_ts for e in arrows)

    def test_export_roundtrip_through_jsonl(self, tmp_path):
        jsonl = tmp_path / "t.jsonl"
        with open(jsonl, "w") as f:
            for ev in _golden_events():
                f.write(json.dumps(ev) + "\n")
            f.write("{half written")  # torn tail line must not break
        out = tmp_path / "t.json"
        trace = timeline.export_chrome_trace(str(jsonl), str(out))
        assert json.load(open(out)) == trace


def test_device_annotation_is_safe_everywhere(tmp_path):
    """The bridge (``spans.span`` inside ``utils.profiling.annotate``)
    is safe with no capture running, with and without a sink, and a
    block that raises still closes its event."""
    from parameter_server_tpu.utils.profiling import annotate

    with annotate("unit.block", flow=1, ts=2):
        pass
    with telemetry_spans.span("unit.block", ts=2):  # no sink: nothing but the block
        pass
    sink = JsonlSink(str(tmp_path / "t.jsonl"))
    prev = install_sink(sink)
    try:
        with flow_scope(7), telemetry_spans.span("unit.block", ts=2):
            pass
        with pytest.raises(KeyError):
            with telemetry_spans.span("unit.raises"):
                raise KeyError("x")
    finally:
        install_sink(prev)
        sink.close()
    events = timeline.load_events(str(tmp_path / "t.jsonl"))
    assert [(e["name"], e.get("flow"), e.get("error")) for e in events] == [
        ("unit.block", 7, None), ("unit.raises", None, "KeyError"),
    ]

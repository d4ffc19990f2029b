"""Cross-node tracing + flight recorder + diagnostic bundles (PR 14).

Covers the three tentpole pieces and their satellites:

- trace context over the Van: ``Task.trace`` stamped from the sending
  thread's flow, re-activated on the receiving side, validated against
  hostile blobs, tolerant of legacy headers (rolling upgrades);
- per-peer clock-offset estimation from report round trips;
- the multi-node timeline merge (node-tagged threads, flow namespacing
  by origin, per-node Perfetto processes, cross-node flow arrows);
- the flight recorder ring (bounded, lock-annotated, zero file IO) and
  its metrics-delta samples;
- diagnostic bundles: capture contents, Van-fetched rings with
  staleness for silent nodes, the trigger plane (rate limit, wedged
  executor wait, degraded serving), the /debug/bundle endpoint, and
  the concurrent-scrape floor (no message-plane re-drives).
"""

import json
import threading
import time
import urllib.request

import numpy as np
import pytest

from parameter_server_tpu.system import faults
from parameter_server_tpu.system.heartbeat import ClockSync
from parameter_server_tpu.system.message import Message, Task
from parameter_server_tpu.system.postoffice import Postoffice
from parameter_server_tpu.system.remote_node import RemoteNode
from parameter_server_tpu.telemetry import blackbox
from parameter_server_tpu.telemetry import spans as telemetry_spans
from parameter_server_tpu.telemetry import timeline as timeline_mod


@pytest.fixture(autouse=True)
def hermetic():
    Postoffice.reset()
    faults.reset()
    blackbox.reset()
    before = set(threading.enumerate())
    yield
    faults.reset()
    blackbox.reset()
    Postoffice.reset()
    deadline = time.time() + 5
    while time.time() < deadline:
        leaked = [
            t for t in set(threading.enumerate()) - before if t.is_alive()
        ]
        if not leaked:
            break
        time.sleep(0.05)
    assert not leaked, f"leaked threads: {leaked}"


def _get(url, timeout=10):
    return urllib.request.urlopen(url, timeout=timeout)


# ---------------------------------------------------------------------------
# trace context over the Van
# ---------------------------------------------------------------------------


class TestTraceContext:
    def test_van_stamps_flow_and_span(self, tmp_path):
        po = Postoffice.instance().start()
        path = str(tmp_path / "trace.jsonl")
        prev = telemetry_spans.install_sink(telemetry_spans.JsonlSink(path))
        try:
            fid = telemetry_spans.new_flow()
            with telemetry_spans.flow_scope(fid):
                out = po.van.transfer(
                    RemoteNode("W0"), RemoteNode("H0"),
                    Message(task=Task(), sender="W0", recver="H0"),
                )
        finally:
            mine = telemetry_spans.install_sink(prev)
            if mine is not None:
                mine.close()
        # the decoded message carries the context (validated on decode)
        assert out.task.trace["flow"] == fid
        assert out.task.trace["node"] == telemetry_spans.node_id()
        assert out.task.trace["t_send"] == pytest.approx(time.time(), abs=60)
        # the wire leg is a span on the same flow, with its frame bytes
        evs = timeline_mod.load_events(path)
        van = [e for e in evs if e["name"] == "van.transfer"]
        assert len(van) == 1
        assert van[0]["flow"] == fid
        assert van[0]["bytes"] > 0
        po.stop()

    def test_presets_respected(self):
        po = Postoffice.instance().start()
        preset = {"flow": 7, "node": "W3", "t_send": 1.0}
        out = po.van.transfer(
            RemoteNode("W3"), RemoteNode("H0"),
            Message(task=Task(trace=dict(preset)), sender="W3", recver="H0"),
        )
        assert out.task.trace == preset
        po.stop()

    @pytest.mark.parametrize(
        "trace",
        [
            ["flow", 1],                        # not a dict
            {"flow": "evil"},                   # non-int flow
            {"flow": 1, "extra": "x"},          # unknown key
            {"flow": -3},                       # out of range
            {"node": "x" * 65},                 # oversized node id
            {"t_send": float("inf")},           # non-finite time
            {"flow": True},                     # bool is not an int here
            {"node": 7},                        # non-str node
        ],
    )
    def test_hostile_trace_blob_rejected_loudly(self, trace):
        msg = Message(task=Task(), sender="A", recver="B")
        msg.task.trace = trace
        blob = msg.to_bytes()
        with pytest.raises(ValueError, match="trace context"):
            Message.from_bytes(blob)

    def test_numpy_scalar_flow_rejected(self):
        msg = Message(task=Task(), sender="A", recver="B")
        msg.task.trace = {"flow": np.int64(4)}
        with pytest.raises(ValueError, match="trace context"):
            Message.from_bytes(msg.to_bytes())

    def test_legacy_header_without_field_decodes(self):
        """Rolling-upgrade tolerance: a peer running the previous
        release pickles a Task with NO trace attribute at all —
        dataclass unpickling restores __dict__ verbatim, so the
        receiver must normalize, not crash."""
        t = Task()
        del t.__dict__["trace"]  # the pre-field wire shape
        blob = Message(task=t, sender="A", recver="B").to_bytes()
        out = Message.from_bytes(blob)
        assert out.task.trace is None

    def test_activate_trace_reenters_flow_with_origin(self):
        with telemetry_spans.activate_trace(
            {"flow": 41, "node": "W9", "t_send": 0.0}
        ):
            assert telemetry_spans.current_flow() == 41
            assert telemetry_spans.current_flow_node() == "W9"
        assert telemetry_spans.current_flow() is None
        # local origin needs no namespacing
        with telemetry_spans.activate_trace(
            {"flow": 5, "node": telemetry_spans.node_id()}
        ):
            assert telemetry_spans.current_flow_node() is None
        # no flow / legacy None: passthrough
        with telemetry_spans.activate_trace(None):
            assert telemetry_spans.current_flow() is None

    def test_rpc_flow_end_to_end(self, tmp_path):
        """The acceptance shape: ONE flow covers the submitting step,
        the Van leg, and work the receiver does — without any stage
        passing ids by hand."""
        import parameter_server_tpu.ps as ps

        path = str(tmp_path / "rpc.jsonl")
        prev = telemetry_spans.install_sink(telemetry_spans.JsonlSink(path))
        flows = []

        class Server(ps.App):
            def process_request(self, req):
                flows.append(telemetry_spans.current_flow())
                with telemetry_spans.span("server.handle"):
                    pass

        class Worker(ps.App):
            def run(self):
                fid = telemetry_spans.new_flow()
                flows.append(fid)
                with telemetry_spans.flow_scope(fid):
                    self.wait(ps.submit(self, Task()))

        def create_app():
            if ps.is_worker():
                return Worker()
            if ps.is_server():
                return Server()
            return ps.App()

        try:
            ps.run_system(create_app, num_workers=1, num_servers=1)
        finally:
            mine = telemetry_spans.install_sink(prev)
            if mine is not None:
                mine.close()
        # the handler observed the worker's flow id (re-activated
        # through the wire context + executor flow hand-off)
        worker_fid = flows[0]
        assert worker_fid in flows[1:]
        evs = timeline_mod.load_events(path)
        by_name = {}
        for e in evs:
            by_name.setdefault(e["name"], []).append(e)
        van_flows = {e.get("flow") for e in by_name.get("van.transfer", [])}
        handle_flows = {e.get("flow") for e in by_name.get("server.handle", [])}
        step_flows = {e.get("flow") for e in by_name.get("executor.step", [])}
        assert worker_fid in van_flows, "flow died at the Van"
        assert worker_fid in handle_flows, "flow died at the receiver"
        assert worker_fid in step_flows, "flow died at the executor"
        # and the Perfetto export draws arrows for that flow across the
        # threads it visited (worker thread -> dispatch thread)
        trace = timeline_mod.to_chrome_trace(evs)["traceEvents"]
        arrow_ids = {e["id"] for e in trace if e.get("ph") in ("s", "f")}
        assert worker_fid in arrow_ids, "no flow arrows drawn for the RPC"


# ---------------------------------------------------------------------------
# clock offsets
# ---------------------------------------------------------------------------


class TestClockSync:
    def test_offset_math_and_min_delay_retention(self):
        cs = ClockSync()
        cs.observe("W0", t_send=100.0, t_recv=102.0, delay_s=1.0)
        # offset = 102 - 1.0 - 100 = 1.0 (delay_s is the ONE-WAY
        # delivery estimate, subtracted whole — not halved)
        assert cs.offset("W0") == pytest.approx(1.0)
        # a noisier (bigger-delay) sample must NOT replace the estimate
        cs.observe("W0", t_send=100.0, t_recv=110.0, delay_s=4.0)
        assert cs.offset("W0") == pytest.approx(1.0)
        # a tighter exchange does
        cs.observe("W0", t_send=100.0, t_recv=101.2, delay_s=0.2)
        assert cs.offset("W0") == pytest.approx(1.0)
        snap = cs.snapshot()["W0"]
        assert snap["samples"] == 3
        assert snap["error_bound_s"] == pytest.approx(0.2)
        # nonsense (negative delay: a clock step mid-exchange) dropped
        cs.observe("W0", t_send=0.0, t_recv=0.0, delay_s=-1.0)
        assert cs.snapshot()["W0"]["samples"] == 3

    def test_measured_delay_cancels_out_of_the_offset(self):
        """The finding this contract encodes: a slow delivery (an
        injected van delay fault during a report) must NOT read as
        clock skew — the delay is measured and subtracted whole, so
        two synchronized clocks estimate ~0 regardless of how long the
        frame sat on the wire."""
        for delay in (0.001, 1.0, 5.0):  # same clock, slower wire
            cs = ClockSync()
            cs.observe("N", t_send=50.0, t_recv=50.0 + delay,
                       delay_s=delay)
            assert cs.offset("N") == pytest.approx(0.0, abs=1e-9)

    def test_aux_report_path_feeds_clock(self):
        po = Postoffice.instance().start()
        aux = po.start_aux(heartbeat_timeout=10.0)
        try:
            aux.register("W0")
            assert aux.report_node("W0")  # wire auto-detects the started po
            off = aux.clock.offset("W0")
            assert off is not None
            # single process: one clock — the offset must read ~zero
            assert abs(off) < 1.0
        finally:
            aux.stop()
            po.stop()


# ---------------------------------------------------------------------------
# multi-node timeline merge
# ---------------------------------------------------------------------------


def _ev(name, t, dur, thread, flow=None, flow_node=None, **kw):
    ev = {"kind": "span", "name": name, "t_wall": t, "dur_s": dur,
          "thread": thread}
    if flow is not None:
        ev["flow"] = flow
    if flow_node is not None:
        ev["flow_node"] = flow_node
    ev.update(kw)
    return ev


class TestNodeMerge:
    def test_merge_tags_aligns_and_namespaces(self):
        # W0's clock runs 10s behind the scheduler's; both nodes used
        # local flow id 1 for DIFFERENT units, and W0's flow 1 also
        # appears on H0 (it crossed the Van, keeping flow_node="W0")
        events = {
            "H0": [
                _ev("a", 100.0, 0.1, "MainThread", flow=1),
                _ev("recv", 100.5, 0.1, "executor:x", flow=1,
                    flow_node="W0"),
            ],
            "W0": [_ev("send", 90.2, 0.1, "MainThread", flow=1)],
        }
        merged = timeline_mod.merge_node_events(events, {"W0": 10.0})
        by_name = {e["name"]: e for e in merged}
        # clock alignment: W0's 90.2 + 10.0 lands between H0's events
        assert by_name["send"]["t_wall"] == pytest.approx(100.2)
        # node-tagged threads + node field
        assert by_name["send"]["thread"] == "W0/MainThread"
        assert by_name["a"]["node"] == "H0"
        # flow namespacing: H0-local flow 1 != W0-origin flow 1, and
        # the Van-crossing pair shares ONE merged id
        assert by_name["send"]["flow"] == by_name["recv"]["flow"]
        assert by_name["a"]["flow"] != by_name["send"]["flow"]
        # time-sorted output
        times = [e["t_wall"] for e in merged]
        assert times == sorted(times)

    def test_chrome_export_one_process_per_node_arrows_cross(self):
        events = {
            "H0": [_ev("recv", 100.5, 0.2, "executor:x", flow=3,
                       flow_node="W0")],
            "W0": [_ev("send", 100.0, 0.2, "MainThread", flow=3)],
        }
        merged = timeline_mod.merge_node_events(events)
        trace = timeline_mod.to_chrome_trace(merged)["traceEvents"]
        procs = {
            m["args"]["name"]: m["pid"]
            for m in trace
            if m.get("ph") == "M" and m["name"] == "process_name"
        }
        assert len(procs) == 2  # one Perfetto process per node
        assert any(":W0" in n for n in procs)
        # the flow arrow's s/f pair crosses the two node processes
        starts = [e for e in trace if e.get("ph") == "s"]
        finishes = [e for e in trace if e.get("ph") == "f"]
        assert starts and finishes
        assert starts[0]["pid"] != finishes[0]["pid"]

    def test_single_node_export_shape_unchanged(self):
        # no node tags: the legacy single-pid schema, exactly
        evs = [_ev("x", 1.0, 0.1, "T1"), _ev("y", 1.2, 0.1, "T2")]
        trace = timeline_mod.to_chrome_trace(evs)["traceEvents"]
        pids = {e["pid"] for e in trace}
        assert pids == {1}
        assert trace[0]["name"] == "process_name"


# ---------------------------------------------------------------------------
# flight recorder
# ---------------------------------------------------------------------------


class TestFlightRecorder:
    def test_ring_bounded_and_dump_shape(self):
        rec = blackbox.FlightRecorder(capacity=4, node_id="T0")
        for i in range(10):
            rec.emit({"name": f"e{i}", "t_wall": float(i), "dur_s": 0.0})
        d = rec.dump()
        assert d["node"] == "T0"
        assert d["capacity"] == 4
        assert len(d["events"]) == 4
        assert d["events_total"] == 10
        assert d["dropped"] == 6
        # oldest evicted, newest kept
        assert d["events"][0]["name"] == "e6"
        assert d["events"][-1]["name"] == "e9"

    def test_tee_records_and_forwards(self, tmp_path):
        path = str(tmp_path / "tee.jsonl")
        prev = telemetry_spans.install_sink(telemetry_spans.JsonlSink(path))
        try:
            rec = blackbox.arm()
            assert blackbox.installed_recorder() is rec
            with telemetry_spans.span("tee.demo"):
                pass
            # both destinations got the event; path proxies the inner
            assert getattr(telemetry_spans.get_sink(), "path") == path
            assert any(
                e["name"] == "tee.demo"
                for e in timeline_mod.load_events(path)
            )
            assert any(
                e["name"] == "tee.demo" for e in rec.dump()["events"]
            )
            blackbox.disarm()
            assert telemetry_spans.get_sink().path == path
        finally:
            mine = telemetry_spans.install_sink(prev)
            if mine is not None:
                mine.close()

    def test_armed_without_inner_sink_no_file_io(self):
        rec = blackbox.arm()
        assert telemetry_spans.get_sink().path is None  # nothing to write
        with telemetry_spans.span("bb.idle"):
            pass
        assert any(
            e["name"] == "bb.idle" for e in rec.dump()["events"]
        )
        assert telemetry_spans.get_sink() is not None

    def test_metrics_delta_samples(self):
        from parameter_server_tpu.telemetry.registry import MetricsRegistry

        reg = MetricsRegistry()
        c = reg.counter("bb_test_total", "t")
        rec = blackbox.FlightRecorder(node_id="T0")
        c.inc(3)
        rec.sample_metrics(reg=reg)
        c.inc(2)
        s = rec.sample_metrics(reg=reg)
        assert s["delta"]["bb_test_total"] == pytest.approx(2.0)
        d = rec.dump()
        assert len(d["metrics_samples"]) == 2
        # first sample's delta is the from-zero baseline
        assert d["metrics_samples"][0]["delta"]["bb_test_total"] == 3.0


# ---------------------------------------------------------------------------
# diagnostic bundles + the trigger plane
# ---------------------------------------------------------------------------


class TestBundles:
    def test_capture_contents_and_perfetto_trace(self):
        rec = blackbox.arm()
        with telemetry_spans.flow_scope(telemetry_spans.new_flow()):
            with telemetry_spans.span("incident.work"):
                pass
        rec.sample_metrics()
        b = blackbox.capture_bundle(trigger="manual", detail="unit")
        assert b["kind"] == "ps_diagnostic_bundle"
        assert b["trigger"]["kind"] == "manual"
        nid = telemetry_spans.node_id()
        assert nid in b["rings"]
        names = [e["name"] for e in b["rings"][nid]["events"]]
        assert "incident.work" in names
        # Perfetto-ready: a traceEvents list with X events in it
        xs = [e for e in b["trace"]["traceEvents"] if e.get("ph") == "X"]
        assert xs
        # JSON-serializable end to end (self-contained artifact)
        json.dumps(b, default=str)
        s = blackbox.summarize_bundle(b)
        assert s["nodes"][nid]["events"] >= 1
        assert not s["section_errors"]

    def test_trigger_rate_limit(self):
        blackbox.set_min_interval(3600.0)
        b1 = blackbox.trigger_bundle("manual", detail="first")
        assert b1 is not None
        assert blackbox.trigger_bundle("manual", detail="second") is None
        assert blackbox.last_bundle() is b1
        blackbox.set_min_interval(0.0)
        assert blackbox.trigger_bundle("manual", detail="third") is not None
        assert len(blackbox.bundles()) == 2

    def test_wedged_executor_wait_triggers_bundle(self):
        from parameter_server_tpu.system.executor import Executor
        from parameter_server_tpu.utils.retry import DeadlineExceeded

        blackbox.set_min_interval(0.0)
        blackbox.arm()
        ex = Executor("wedge-test")
        gate = threading.Event()
        try:
            ts = ex.submit(gate.wait)
            with pytest.raises(DeadlineExceeded):
                ex.wait(ts, timeout=0.05)
            b = blackbox.last_bundle()
            assert b is not None
            assert b["trigger"]["kind"] == "executor_wait_timeout"
            assert "wedge-test" in b["trigger"]["detail"]
            # the executor section pins the wedged state at capture time
            mine = [
                e for e in b["executors"] if e["name"] == "wedge-test"
            ]
            assert mine and (
                mine[0]["running"] is not None or mine[0]["pending"] > 0
            )
        finally:
            gate.set()
            ex.wait_all()
            ex.stop()

    def test_degraded_serving_triggers_bundle(self, mesh8):
        from parameter_server_tpu.parameter.kv_vector import KVVector
        from parameter_server_tpu.serving import (
            DegradedError,
            PullRequest,
            ServeConfig,
            ServeFrontend,
        )

        blackbox.set_min_interval(0.0)
        blackbox.arm()
        kv = KVVector(mesh=mesh8, k=4, num_slots=1 << 10, hashed=True,
                      name="bb_degraded")
        fe = ServeFrontend(
            kv, ServeConfig(replica="off", workers=1,
                            live_pull_deadline_s=2.0)
        ).start()
        try:
            keys = np.arange(8, dtype=np.int64)
            fe.submit(PullRequest(keys=keys)).result(30)  # healthy warm
            faults.arm("serve.pull", kind="raise")
            with pytest.raises(DegradedError):
                fe.submit(PullRequest(keys=keys)).result(30)
            b = blackbox.last_bundle()
            assert b is not None
            assert b["trigger"]["kind"] == "degraded"
            assert "no-replica" in b["trigger"]["detail"]
        finally:
            faults.reset()
            fe.close()
            kv.executor.stop()

    def test_aux_owned_coordinator_death_captures_with_cluster_context(self):
        """A node death detected through an AuxRuntime's coordinator
        captures the FULL-context bundle (cluster metrics snapshot,
        clock offsets, staleness-aware rings) — not the process-local
        fallback a standalone coordinator gets."""
        from parameter_server_tpu.system.aux_runtime import AuxRuntime

        blackbox.set_min_interval(0.0)
        blackbox.arm()
        aux = AuxRuntime(heartbeat_timeout=0.05)
        try:
            assert aux.coordinator.bundle_context is aux
            aux.register("S0")
            time.sleep(0.12)  # past the heartbeat timeout: S0 is dead
            handled = aux.coordinator.check()
            assert handled == ["S0"]
            b = blackbox.last_bundle()
            assert b is not None
            assert b["trigger"]["kind"] == "node_death"
            # cluster-context sections only an aux capture carries
            assert "nodes" in b["metrics"]  # ClusterAggregator.snapshot
            assert b["clock_offsets"] is not None
            assert b["rings"]["S0"]["stale"]
        finally:
            aux.stop()

    def test_fetch_rings_own_node_dumps_even_when_marked_stale(self):
        """A stalled aux loop marks the capturing process's OWN node
        stale — exactly the wedged-process incident a bundle exists to
        diagnose. Its in-memory ring needs no wire and is provably
        alive, so the capture must dump it, not record staleness for
        the node executing the capture."""
        from parameter_server_tpu.system.aux_runtime import AuxRuntime

        aux = AuxRuntime(heartbeat_timeout=30.0, stale_after_s=0.01)
        try:
            rec = blackbox.arm()
            rec.emit({"name": "self.evidence", "t_wall": 1.0,
                      "dur_s": 0.0})
            aux.cluster.update(aux.node_id, {})
            time.sleep(0.03)  # past stale_after_s: self reads stale
            assert aux.node_id in aux.cluster.stale_nodes()
            rings = aux.fetch_rings(wire=False)
            own = rings[aux.node_id]
            assert not own.get("stale"), own
            assert [e["name"] for e in own["events"]] == ["self.evidence"]
        finally:
            aux.stop()

    def test_fetch_rings_over_van_with_staleness(self):
        """Ring dumps ride the real wire; a node whose fetch is lost on
        the wire (injected drop) shows staleness, not a fabricated
        ring — and a node with stale metric reports is not fetched at
        all."""
        po = Postoffice.instance().start()
        aux = po.start_aux(heartbeat_timeout=30.0)
        aux.cluster.stale_after_s = 30.0
        try:
            aux.register("W0")
            aux.register("S0")
            blackbox.recorder("W0").emit({"name": "w0.e", "t_wall": 1.0,
                                          "dur_s": 0.0})
            blackbox.recorder("S0").emit({"name": "s0.e", "t_wall": 1.0,
                                          "dur_s": 0.0})
            sent_before = po.van.wire_sent_bytes
            faults.arm("van.transfer", kind="drop", match="S0->")
            rings = aux.fetch_rings()
            faults.disarm("van.transfer")
            # W0's ring crossed the wire intact
            assert [e["name"] for e in rings["W0"]["events"]] == ["w0.e"]
            assert po.van.wire_sent_bytes > sent_before
            # S0's fetch was lost: staleness, with the loss named
            assert rings["S0"]["stale"]
            assert "lost" in rings["S0"]["reason"]
            # this process's own node dumps locally
            assert aux.node_id in rings
        finally:
            aux.stop()
            po.stop()


# ---------------------------------------------------------------------------
# exposition: /debug/bundle, sink disclosure, concurrent-scrape floor
# ---------------------------------------------------------------------------


class TestExposition:
    def test_snapshot_discloses_sink_state(self, tmp_path):
        from parameter_server_tpu.telemetry.exposition import _timeline_tail

        # absent: no sink was ever installed
        tail = _timeline_tail()
        assert tail["sink"] == "absent"
        assert tail["events"] == []
        sink = telemetry_spans.JsonlSink(str(tmp_path / "t.jsonl"))
        prev = telemetry_spans.install_sink(sink)
        try:
            with telemetry_spans.span("disclose.me"):
                pass
            tail = _timeline_tail()
            assert tail["sink"] == "active"
            assert [e["name"] for e in tail["events"]] == ["disclose.me"]
        finally:
            telemetry_spans.install_sink(prev)
            sink.close()

    def test_bundle_endpoint_and_concurrent_scrape_floor(self):
        """Satellite: N threads hammering /metrics + /debug/bundle must
        ride the scrape-refresh floor — the message plane is driven at
        the floor rate, not the request rate, fault-point call counters
        tick accordingly, and every response is 200 (the hermetic
        fixture asserts no thread leaks)."""
        from parameter_server_tpu.telemetry.exposition import (
            close_cluster,
            expose_cluster,
        )

        po = Postoffice.instance().start()
        blackbox.arm()
        srv = expose_cluster(
            po, metrics_interval=0.0, check_interval=5.0,
            heartbeat_timeout=30.0,
        )
        try:
            aux = srv.aux
            aux.register("W0")
            # warm the floor: one scrape + one bundle so the hammer
            # below measures steady-state behavior, then count fault-
            # point calls without ever firing (a threshold the hammer
            # can never reach makes the spec a pure call counter)
            with _get(srv.url + "/metrics") as r:
                assert r.status == 200
            with _get(srv.url + "/debug/bundle") as r:
                assert r.status == 200
            n_nodes = len(aux.cluster.node_ages()) + 1
            spec_hb = faults.arm(
                "heartbeat.report", kind="raise", after_n_calls=1 << 30
            )
            spec_van = faults.arm(
                "van.transfer", kind="raise", after_n_calls=1 << 30
            )
            n_threads, n_reqs = 6, 10
            codes = []
            codes_lock = threading.Lock()

            def hammer(i):
                for j in range(n_reqs):
                    path = "/metrics" if (i + j) % 2 else "/debug/bundle"
                    with _get(srv.url + path) as r:
                        with codes_lock:
                            codes.append(r.status)

            t0 = time.monotonic()
            threads = [
                threading.Thread(target=hammer, args=(i,))
                for i in range(n_threads)
            ]
            for t in threads:
                t.start()
            for t in threads:
                t.join()
            dur = time.monotonic() - t0
            assert codes and all(c == 200 for c in codes)
            # the floor: at most one metrics sweep / bundle capture per
            # scrape_refresh_min_s window (+ straddle slack) — NOT one
            # per request. Each sweep/capture ticks each point at most
            # once per known node (every manager node is a registered
            # sampler), so the bound scales with cluster size, never
            # with the request count.
            floor = aux.scrape_refresh_min_s
            max_sweeps = dur / floor + 2
            assert spec_hb.calls <= max_sweeps * n_nodes, (
                f"{spec_hb.calls} heartbeat fault-point ticks for "
                f"{len(codes)} requests in {dur:.2f}s over {n_nodes} "
                "nodes — the scrape floor is not holding"
            )
            assert spec_van.calls <= 2 * max_sweeps * n_nodes, (
                f"{spec_van.calls} van fault-point ticks — the message "
                "plane is being re-driven per scrape"
            )
            # far below the request count (the actual re-drive signal)
            assert spec_hb.calls + spec_van.calls < len(codes)
        finally:
            faults.reset()
            close_cluster(srv)
            po.stop()

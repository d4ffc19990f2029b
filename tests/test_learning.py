"""Learning truth plane (PR 15): realized staleness vs the configured
τ, key heat & shard balance, in-jit convergence side outputs, the
shipped alert rules (divergence / staleness breach / shard imbalance),
the cluster scrape with node-labeled ``ps_learning_*``, and the monitor
path's redelivery hardening."""

import json
import urllib.request

import numpy as np
import pytest

from parameter_server_tpu.system import faults
from parameter_server_tpu.system.postoffice import Postoffice
from parameter_server_tpu.telemetry import learning as learning_mod
from parameter_server_tpu.telemetry.registry import MetricsRegistry


def _worker(po, tau=3, minibatch=64, num_slots=1 << 10,
            name="lt_worker", **sgd_kw):
    from parameter_server_tpu.apps.linear.async_sgd import AsyncSGDWorker
    from parameter_server_tpu.apps.linear.config import (
        Config,
        LearningRateConfig,
        PenaltyConfig,
        SGDConfig,
    )

    conf = Config()
    conf.penalty = PenaltyConfig(type="l1", lambda_=[0.1])
    conf.learning_rate = LearningRateConfig(
        type="decay", alpha=0.1, beta=1.0
    )
    conf.async_sgd = SGDConfig(
        algo="ftrl", minibatch=minibatch, num_slots=num_slots,
        max_delay=tau, **sgd_kw,
    )
    return AsyncSGDWorker(conf, mesh=po.mesh, name=name)


def _batches(n, minibatch=64, key_space=1 << 14, lanes=6, seed0=0):
    from parameter_server_tpu.utils.sparse import random_sparse

    out = []
    for i in range(n):
        b = random_sparse(
            minibatch, key_space, lanes, seed=seed0 + i, binary=True
        )
        b.y = np.where(
            np.arange(minibatch) % 3 == 0, 1.0, -1.0
        ).astype(np.float32)
        out.append(b)
    return out


def _divergence_drill(mesh) -> dict:
    """Seeded divergence drill: an LR blow-up (square loss, alpha 1e10)
    NaNs the trajectory within a few steps; the learning plane judges
    the collected steps divergent (``ps_learning_divergence_total``),
    the SHIPPED ``loss_divergence`` rule walks inactive -> pending ->
    firing, and the firing transition captures a flight-recorder
    diagnostic bundle through the alert trigger plane: the same
    listener wiring ``AuxRuntime.set_alerts`` installs. Deterministic
    under a fake clock."""
    from parameter_server_tpu.apps.linear.async_sgd import AsyncSGDWorker
    from parameter_server_tpu.apps.linear.config import (
        Config,
        LearningRateConfig,
        LossConfig,
        PenaltyConfig,
        SGDConfig,
    )
    from parameter_server_tpu.telemetry import alerts as alerts_mod
    from parameter_server_tpu.telemetry import blackbox
    from parameter_server_tpu.utils.sparse import random_sparse

    rule = next(
        r for r in alerts_mod.default_rules() if r.name == "loss_divergence"
    )
    clock = [0.0]
    mgr = alerts_mod.AlertManager([rule], clock=lambda: clock[0])
    prev_interval = blackbox.set_min_interval(0.0)
    was_armed = blackbox.installed_recorder() is not None
    blackbox.arm()
    bundles: list = []

    def on_transition(ev) -> None:
        # the AuxRuntime._maybe_bundle_on_alert wiring, drill-local:
        # a firing alert captures the evidence while it is in the ring
        if ev.to == "firing" and ev.rule == "loss_divergence":
            b = blackbox.trigger_bundle("alert", detail=ev.rule)
            if b is not None:
                bundles.append(b)

    mgr.add_listener(on_transition)
    conf = Config()
    conf.loss = LossConfig(type="square")
    conf.penalty = PenaltyConfig(type="l2", lambda_=[0.0])
    # the blow-up: plain SGD at a constant learning rate orders of
    # magnitude past stability turns the square loss's w-proportional
    # gradient into an exponential: float32 overflows to Inf/NaN
    # within a handful of steps on any data (FTRL would self-damp via
    # its adaptive per-coordinate rate, which is exactly why the drill
    # picks the updater the reference's SGDEntry models)
    conf.learning_rate = LearningRateConfig(
        type="constant", alpha=1e10, beta=1.0
    )
    conf.async_sgd = SGDConfig(
        algo="standard", minibatch=64, num_slots=1 << 9, max_delay=0,
    )
    worker = AsyncSGDWorker(conf, mesh=mesh, name="learning_diverge")
    states = []
    try:
        mgr.evaluate()  # t=0 baseline sample: a rate needs a window
        states.append(mgr.states()[rule.name].state_name)
        for i in range(8):
            b = random_sparse(64, 1 << 12, 6, seed=100 + i, binary=True)
            b.y = np.where(np.arange(64) % 2 == 0, 1.0, -1.0).astype(
                np.float32
            )
            ts = worker._submit_prepped(
                worker.prep(b, device_put=False), with_aux=False
            )
            worker.collect(ts)
        plane = learning_mod.get_plane("learning_diverge")
        divergences = dict(plane.snapshot()["divergence"]) if plane else {}
        clock[0] = 5.0
        mgr.evaluate()  # pending -> firing in one tick (for_s=0)
        states.append(mgr.states()[rule.name].state_name)
        fired = rule.name in mgr.firing()
        # traffic stops; the window slides past the burst -> resolved
        clock[0] = 5.0 + rule.window_s + 10.0
        mgr.evaluate()
        states.append(mgr.states()[rule.name].state_name)
    finally:
        worker.executor.stop()
        blackbox.set_min_interval(prev_interval)
        if not was_armed:
            blackbox.disarm()
    return {
        "divergence_counts": divergences,
        "states_seen": states,
        "fired": bool(fired),
        "resolved": states[-1] in ("resolved", "inactive"),
        "bundle_captured": bool(bundles),
        "bundle_trigger": (
            dict(bundles[0]["trigger"]) if bundles else None
        ),
    }


@pytest.fixture()
def po(mesh8):
    Postoffice.reset()
    faults.reset()
    po = Postoffice.instance().start(num_data=4, num_server=2)
    yield po
    faults.reset()
    po.stop()
    Postoffice.reset()


# ---------------------------------------------------------------------------
# realized staleness: the bounded-delay contract, measured
# ---------------------------------------------------------------------------


class TestRealizedStaleness:
    def test_observed_max_respects_configured_tau(self, po):
        tau = 3
        worker = _worker(po, tau=tau, name="lt_stale")
        try:
            worker.train(iter(_batches(12)))
        finally:
            worker.executor.stop()
        plane = learning_mod.get_plane("lt_stale")
        assert plane is not None
        st = plane.snapshot()["staleness"]
        assert st["configured_tau"] == tau
        assert st["submits"] == 12
        assert st["histogram"]["count"] == 12
        # the measured invariant: realized staleness never exceeds τ
        assert 0 < st["observed_max"] <= tau
        assert st["within_bound"]
        # executor logical-clock lag mirrors the ministep staleness on
        # a 1-ministep-per-submission run
        assert st["executor_clock_lag_max"] >= st["observed_max"]
        # the live gauge the staleness_breach rule watches is <= 0
        export = plane.export()
        over = export["ps_learning_staleness_over_tau"]["series"]
        assert all(s["value"] <= 0 for s in over)

    def test_tau_zero_is_always_fresh(self, po):
        worker = _worker(po, tau=0, name="lt_fresh")
        try:
            worker.train(iter(_batches(4)))
        finally:
            worker.executor.stop()
        st = learning_mod.get_plane("lt_fresh").snapshot()["staleness"]
        assert st["observed_max"] == 0
        assert st["within_bound"]


# ---------------------------------------------------------------------------
# key heat: windowed sketch vs exact, shard fold, decay, hot slots
# ---------------------------------------------------------------------------


class TestKeyHeat:
    def test_sketch_matches_exact_on_small_stream(self):
        heat = learning_mod.KeyHeat(num_slots=512, num_shards=2)
        rng = np.random.default_rng(3)
        exact = np.zeros(512, np.int64)
        for _ in range(16):
            slots = rng.integers(0, 512, 256)
            heat.note(slots)
            np.add.at(exact, slots, 1)
        uniq = np.flatnonzero(exact)
        est = heat.estimate(uniq)
        # CM is upper-biased; at 512 distinct slots in a 2^16 sketch
        # the estimates are exact
        assert (est >= exact[uniq]).all()
        assert float(np.mean(est == exact[uniq])) == 1.0

    def test_shard_fold_follows_assigner_ranges(self):
        # ranges come from the SAME NodeAssigner/Range.even_divide the
        # servers use; all traffic into the last shard's range reads as
        # num_shards x imbalance
        heat = learning_mod.KeyHeat(num_slots=100, num_shards=4)
        heat.note(np.arange(75, 100))  # the 4th shard's key range
        shares = heat.shares()
        assert shares["shares"][3] == 1.0
        assert shares["shares"][:3] == [0.0, 0.0, 0.0]
        assert shares["imbalance"] == 4.0

    def test_sentinel_and_out_of_range_slots_dropped(self):
        heat = learning_mod.KeyHeat(num_slots=64, num_shards=2)
        n = heat.note(np.array([1, 2, 64, 100, -1]))
        assert n == 2  # the sentinel (== num_slots) and beyond dropped

    def test_decay_window_halves_and_cools(self):
        heat = learning_mod.KeyHeat(num_slots=64, num_shards=2)
        heat.note(np.full(32, 7))
        assert heat.estimate(np.array([7]))[0] == 32
        heat.advance()
        assert heat.estimate(np.array([7]))[0] == 16
        total0 = heat.shares()["total_weight"]
        heat.advance()
        assert heat.shares()["total_weight"] == pytest.approx(total0 / 2)

    def test_top_slots_table_ranks_hot_first(self):
        heat = learning_mod.KeyHeat(num_slots=100, num_shards=4, top_k=4)
        heat.note(np.concatenate([np.full(50, 80), np.arange(10)]))
        top = heat.top_slots()
        assert top[0]["slot"] == 80
        assert top[0]["shard"] == 3
        assert top[0]["est"] >= 50


# ---------------------------------------------------------------------------
# convergence side outputs: in-jit scalars, metered host-side
# ---------------------------------------------------------------------------


class TestConvergenceSideOutputs:
    def test_dense_step_metrics_carry_norms(self, po):
        worker = _worker(po, tau=0, name="lt_conv")
        b = _batches(1)[0]
        try:
            ts = worker.process_minibatch(b)
            metrics = worker.executor.wait(ts)
        finally:
            worker.executor.stop()
        for key in ("grad_sq", "update_sq", "weight_sq"):
            assert key in metrics
            assert np.isfinite(float(metrics[key]))
        assert float(metrics["grad_sq"]) > 0
        # first step: the table is all zeros, so the consumed weights are
        assert float(metrics["weight_sq"]) == 0.0

    def test_sparse_update_metrics_carry_norms(self, po):
        worker = _worker(po, tau=0, name="lt_conv_sp", update="sparse")
        b = _batches(1)[0]
        try:
            ts = worker.process_minibatch(b)
            metrics = worker.executor.wait(ts)
        finally:
            worker.executor.stop()
        assert float(metrics["grad_sq"]) > 0
        assert np.isfinite(float(metrics["update_sq"]))

    def test_collect_feeds_plane_trajectory_and_examples(self, po):
        worker = _worker(po, tau=2, name="lt_traj")
        try:
            worker.train(iter(_batches(6)))
        finally:
            worker.executor.stop()
        snap = learning_mod.get_plane("lt_traj").snapshot()
        # device-confirmed example count, wired through collect()
        assert snap["examples"] == 6 * 64
        assert snap["collected_steps"] == 6
        tail = snap["trajectory_tail"]
        assert len(tail) == 6
        for pt in tail:
            assert isinstance(pt["loss"], float)
            assert pt["grad_norm"] > 0
        assert snap["divergence"] == {}


# ---------------------------------------------------------------------------
# shipped alert rules: inactive → pending → firing → resolved
# ---------------------------------------------------------------------------


class TestShippedLearningRules:
    def test_rules_ship_in_default_set(self):
        from parameter_server_tpu.telemetry.alerts import default_rules

        by_name = {r.name: r for r in default_rules()}
        assert by_name["loss_divergence"].kind == "counter_rate"
        assert (
            by_name["loss_divergence"].metric
            == "ps_learning_divergence_total"
        )
        assert by_name["staleness_breach"].kind == "gauge"
        assert (
            by_name["staleness_breach"].metric
            == "ps_learning_staleness_over_tau"
        )
        assert by_name["shard_imbalance"].kind == "gauge"
        assert (
            by_name["shard_imbalance"].metric
            == "ps_learning_shard_imbalance"
        )

    def test_staleness_breach_fires_and_resolves(self):
        """The SHIPPED staleness_breach rule driven through its whole
        lifecycle by a real plane breaching (then re-satisfying) the
        configured τ (PR 11 drill pattern)."""
        from parameter_server_tpu.telemetry.alerts import (
            AlertManager,
            default_rules,
        )

        rule = next(
            r for r in default_rules() if r.name == "staleness_breach"
        )
        reg = MetricsRegistry()
        clock = [0.0]
        mgr = AlertManager([rule], registry=reg, clock=lambda: clock[0])
        plane = learning_mod.LearningPlane(
            "W0", num_slots=256, num_shards=2, max_delay=2, registry=reg
        )
        mgr.evaluate()
        assert mgr.states()[rule.name].state_name == "inactive"
        plane.note_submit(5)  # realized staleness 5 > τ=2: breach
        clock[0] = 1.0
        mgr.evaluate()
        assert mgr.states()[rule.name].state_name == "firing"
        # a fresh plane (rebuilt worker) re-satisfies the bound
        learning_mod.LearningPlane(
            "W0", num_slots=256, num_shards=2, max_delay=2, registry=reg
        )
        clock[0] = 2.0
        mgr.evaluate()
        assert mgr.states()[rule.name].state_name == "resolved"
        clock[0] = 2.0 + rule.resolve_hold_s + 1.0
        mgr.evaluate()
        assert mgr.states()[rule.name].state_name == "inactive"

    def test_shard_imbalance_fires_and_resolves(self):
        from parameter_server_tpu.telemetry.alerts import (
            AlertManager,
            default_rules,
        )

        rule = next(
            r for r in default_rules() if r.name == "shard_imbalance"
        )
        reg = MetricsRegistry()
        clock = [0.0]
        mgr = AlertManager([rule], registry=reg, clock=lambda: clock[0])
        plane = learning_mod.LearningPlane(
            "W0", num_slots=640, num_shards=8, max_delay=0, registry=reg
        )
        mgr.evaluate()
        assert mgr.states()[rule.name].state_name == "inactive"
        # every key lands in one shard's range: imbalance 8 > 4
        plane.note_slots(np.arange(80))
        clock[0] = 1.0
        mgr.evaluate()
        assert mgr.states()[rule.name].state_name == "pending"
        clock[0] = 1.0 + rule.for_s + 1.0
        mgr.evaluate()
        assert mgr.states()[rule.name].state_name == "firing"
        # traffic spreads back out; the windowed view rebalances
        plane.note_slots(np.tile(np.arange(640), 3))
        clock[0] += 1.0
        mgr.evaluate()
        assert mgr.states()[rule.name].state_name == "resolved"

    def test_divergence_drill_fires_with_bundle(self, po):
        """Acceptance: a seeded LR blow-up drives the SHIPPED
        loss_divergence rule to firing, with a diagnostic bundle
        captured through the PR 13 alert trigger plane."""
        out = _divergence_drill(po.mesh)
        assert out["divergence_counts"].get("nonfinite", 0) >= 1
        assert out["fired"]
        assert "firing" in out["states_seen"]
        assert out["resolved"]
        assert out["bundle_captured"]
        assert out["bundle_trigger"]["kind"] == "alert"
        assert out["bundle_trigger"]["detail"] == "loss_divergence"


# ---------------------------------------------------------------------------
# cluster view: ps_learning_* node-labeled on one scrape
# ---------------------------------------------------------------------------


class TestClusterLearningScrape:
    def _plane(self, node, reg):
        p = learning_mod.LearningPlane(
            node, num_slots=256, num_shards=2, max_delay=2, registry=reg
        )
        p.note_submit(1)
        p.note_step({
            "objective": 5.0, "num_ex": 10, "grad_sq": 4.0,
            "update_sq": 4.0, "weight_sq": 1.0,
        })
        p.note_slots(np.arange(64))
        return p

    def test_one_scrape_shows_node_labels_and_rollup(self, po):
        from parameter_server_tpu.telemetry.aggregate import (
            ClusterAggregator,
        )

        cluster = ClusterAggregator()
        master = learning_mod.ClusterFeedMaster(cluster)
        for node in ("W0", "W1"):
            plane = self._plane(node, MetricsRegistry())
            slaver = learning_mod.slaver_over_van(master, node, po.van)
            slaver.report(plane.export())
        text = cluster.render_text()
        # node-labeled series for both workers...
        assert 'ps_learning_loss{node="W0",worker="W0"}' in text
        assert 'ps_learning_loss{node="W1",worker="W1"}' in text
        # ...and the cluster rollup for counters
        assert 'ps_learning_examples_total{node="cluster"' in text
        # the staleness histogram merges bucket-wise into the rollup
        assert "ps_learning_staleness_ministeps_bucket" in text

    def test_duplicate_report_never_double_merges(self, po):
        """The van `duplicate` fault delivers one report frame twice;
        the master's seq guard must merge it once (satellite: a
        duplicated report never double-merges into cluster progress)."""
        from parameter_server_tpu.telemetry.aggregate import (
            ClusterAggregator,
        )

        cluster = ClusterAggregator()
        master = learning_mod.ClusterFeedMaster(cluster)
        plane = self._plane("W0", MetricsRegistry())
        slaver = learning_mod.slaver_over_van(master, "W0", po.van)
        faults.arm("van.transfer", kind="duplicate")
        slaver.report(plane.export())
        faults.reset()
        assert master.monitor.duplicates_dropped() == 1
        merged = cluster.merged()
        ex = [
            s for s in merged["ps_learning_examples_total"]["series"]
            if s["labels"]["node"] == "W0"
        ]
        assert len(ex) == 1 and ex[0]["value"] == 10.0


# ---------------------------------------------------------------------------
# monitor redelivery hardening (satellite): drop → retransmit,
# duplicate → exactly-once merge, on the ADDITIVE progress master
# ---------------------------------------------------------------------------


class TestMonitorRedelivery:
    def _master_slaver(self, po):
        from parameter_server_tpu.system.monitor import (
            MonitorMaster,
            MonitorSlaver,
        )

        master: MonitorMaster[list] = MonitorMaster()
        master.set_data_merger(lambda src, dst: dst.extend(src))
        return master, MonitorSlaver.over_van(master, "W0", po.van)

    def test_duplicate_frame_merges_exactly_once(self, po):
        master, slaver = self._master_slaver(po)
        faults.arm("van.transfer", kind="duplicate")
        slaver.report([1])
        faults.reset()
        slaver.report([2])
        # additive merge: a double-merged [1] would read [1, 1, 2]
        assert master.progress() == {"W0": [1, 2]}
        assert master.duplicates_dropped() == 1

    def test_dropped_frame_is_retransmitted(self, po):
        master, slaver = self._master_slaver(po)
        faults.arm("van.transfer", kind="drop", once=True)
        slaver.report([1])  # first attempt dropped; retry delivers
        faults.reset()
        assert master.progress() == {"W0": [1]}

    def test_exhausted_retries_surface_the_drop(self, po):
        master, slaver = self._master_slaver(po)
        faults.arm("van.transfer", kind="drop")
        with pytest.raises(faults.FaultError):
            slaver.report([1])
        faults.reset()
        assert master.progress() == {}

    def test_direct_path_unchanged(self):
        from parameter_server_tpu.system.monitor import (
            MonitorMaster,
            MonitorSlaver,
        )

        master: MonitorMaster[list] = MonitorMaster()
        master.set_data_merger(lambda src, dst: dst.extend(src))
        s = MonitorSlaver(master, "W0")
        s.report([1])
        s.report([2])  # no seq on the direct path: merge every call
        assert master.progress() == {"W0": [1, 2]}


# ---------------------------------------------------------------------------
# /debug/snapshot: the hot-slot table is served
# ---------------------------------------------------------------------------


class TestDebugSnapshotLearning:
    def test_snapshot_serves_learning_plane(self, po):
        from parameter_server_tpu.telemetry.exposition import (
            close_cluster,
            expose_cluster,
        )

        worker = _worker(po, tau=2, name="lt_snap")
        srv = None
        try:
            worker.train(iter(_batches(4)))
            srv = expose_cluster(po, port=0, metrics_interval=0.1)
            body = urllib.request.urlopen(
                f"{srv.url}/debug/snapshot", timeout=10
            ).read()
            snap = json.loads(body)
            lt = snap["learning"]["lt_snap"]
            assert lt["staleness"]["within_bound"]
            assert isinstance(lt["hot_slots"], list) and lt["hot_slots"]
            assert {"slot", "est", "shard"} <= set(lt["hot_slots"][0])
            # the same scrape point serves ps_learning_* series
            metrics = urllib.request.urlopen(
                f"{srv.url}/metrics", timeout=10
            ).read().decode()
            assert "ps_learning_staleness_ministeps" in metrics
        finally:
            close_cluster(srv)
            worker.executor.stop()

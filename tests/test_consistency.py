"""Self-driving consistency (ISSUE 20): the adaptive τ controller
(widen on stability, clamp on spikes, the full divergence reaction —
τ→0 + LR backoff + snapshot rollback), the in-jit KKT significance
filter with its off-is-bit-identical contract and suppressed-key
reconciliation, the host-side persistent drop, the live-τ breach
accounting, and the τ-sweep zero-recompile pin."""

import numpy as np
import pytest

from parameter_server_tpu.system import faults
from parameter_server_tpu.system.faults import FaultError
from parameter_server_tpu.system.postoffice import Postoffice
from parameter_server_tpu.telemetry import learning as learning_mod


def _worker(po, tau=3, minibatch=64, num_slots=1 << 9,
            name="cons_worker", **sgd_kw):
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


def _batches(n, minibatch=64, key_space=1 << 12, lanes=6, seed0=0):
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


def _state_leaves(worker):
    import jax

    return jax.tree.leaves(worker.state_host()["state"])


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
# adaptive τ: the controller policy
# ---------------------------------------------------------------------------


class TestAdaptiveTau:
    def test_widens_under_stability_and_stays_within_cap(self, po):
        worker = _worker(po, tau=4, name="cons_widen", tau_adaptive=True)
        ctl = worker._consistency.controller
        ctl.stable_steps = 2  # ramp scaled to the short test run
        try:
            worker.train(iter(_batches(12)))
        finally:
            worker.executor.stop()
        # started conservative, earned width, never past the cap
        assert ctl.tau_trace[0] == 1
        assert max(ctl.tau_trace) > 1
        assert max(ctl.tau_trace) <= 4
        st = learning_mod.get_plane("cons_widen").snapshot()["staleness"]
        assert st["live_tau"] == ctl.tau
        assert st["configured_tau"] == 4
        # the bounded-delay contract held against the LIVE τ at every
        # submission (the satellite-1 breach semantics)
        assert st["within_bound"]
        assert st["over_tau_max"] <= 0

    def test_soft_spike_clamps_tau_without_reaction(self, po):
        worker = _worker(po, tau=4, name="cons_spike", tau_adaptive=True)
        ctl = worker._consistency.controller
        try:
            ctl._set_tau(4, "widen")
            for _ in range(10):  # fill the spike window, all healthy
                ctl.on_metrics(0.5, 1.0, False)
            alpha_before = float(worker.lr.alpha)
            ctl.on_metrics(0.5, 50.0, False)  # 50x the window median
        finally:
            worker.executor.stop()
        assert ctl.tau == 2  # halved, not zeroed
        # a clamp is the cheap reversible move: no LR backoff, no
        # rollback episode
        assert float(worker.lr.alpha) == alpha_before
        assert ctl.episodes == []

    def test_react_backs_off_lr_and_rolls_back_state(self, po):
        worker = _worker(po, tau=3, name="cons_react", tau_adaptive=True)
        try:
            worker.train(iter(_batches(4)))
            snap_leaves = [
                np.asarray(x)
                for x in __import__("jax").tree.leaves(
                    worker._consistency.controller._snapshot["state"]
                )
            ]
            alpha_before = float(worker.lr.alpha)
            worker.train(iter(_batches(3, seed0=50)))  # move past it
            moved = _state_leaves(worker)
            assert any(
                not np.array_equal(np.asarray(a), b)
                for a, b in zip(moved, snap_leaves)
            )
            episode = worker._consistency.react("test")
            restored = _state_leaves(worker)
        finally:
            worker.executor.stop()
        assert episode["rolled_back"]
        assert episode["tau_after"] == 0
        assert float(worker.lr.alpha) == alpha_before * 0.5
        # bit-exact rollback to the controller's snapshot
        for a, b in zip(restored, snap_leaves):
            assert np.array_equal(np.asarray(a), b)

    def test_nonfinite_collect_runs_reaction_then_reconverges(self, po):
        worker = _worker(po, tau=3, name="cons_poison", tau_adaptive=True)
        try:
            worker.train(iter(_batches(4)))
            bad = _batches(1, seed0=90)[0]
            bad.y = np.full_like(bad.y, np.float32("inf"))
            worker.train(iter([bad]))
            ctl = worker._consistency.controller
            assert [e["reason"] for e in ctl.episodes] == ["nonfinite"]
            assert ctl.episodes[0]["rolled_back"]
            worker.train(iter(_batches(4, seed0=100)))
        finally:
            worker.executor.stop()
        traj = learning_mod.get_plane("cons_poison").snapshot()[
            "trajectory_tail"
        ]
        # post-rollback steps train on finite state again
        assert all(np.isfinite(p["loss"]) for p in traj[-3:])

    def test_rollback_fault_point_fires_before_any_state_change(self, po):
        worker = _worker(po, tau=3, name="cons_fault", tau_adaptive=True)
        try:
            worker.train(iter(_batches(2)))
            alpha_before = float(worker.lr.alpha)
            faults.arm("consistency.rollback", kind="raise")
            with pytest.raises(FaultError):
                worker._consistency.react("drill")
        finally:
            faults.disarm("consistency.rollback")
            worker.executor.stop()
        # the point fires BEFORE the reaction touches anything: a
        # failed reaction leaves LR, τ, and the episode log untouched
        assert float(worker.lr.alpha) == alpha_before
        assert worker._consistency.controller.episodes == []

    def test_effective_tau_clamped_to_configured_cap(self, po):
        worker = _worker(po, tau=3, name="cons_clamp")
        try:
            assert worker.set_effective_tau(99) == 3
            assert worker.set_effective_tau(-5) == 0
        finally:
            worker.executor.stop()


# ---------------------------------------------------------------------------
# satellite 2: τ moves never recompile
# ---------------------------------------------------------------------------


class TestTauNeverRecompiles:
    def test_tau_sweep_zero_recompiles_post_warmup(self, po):
        from parameter_server_tpu.telemetry import device as device_mod

        device_mod.reset()
        worker = _worker(
            po, tau=8, name="cons_sweep", update="sparse"
        )
        try:
            # warmup compiles every variant the sweep will touch:
            # τ=0 → snap_donate, τ=2 → snap + delay
            worker.set_effective_tau(0)
            worker.train(iter(_batches(2)))
            worker.set_effective_tau(2)
            worker.train(iter(_batches(4, seed0=10)))
            device_mod.mark_warmup()
            for tau in (0, 1, 3, 5, 8, 4, 0, 8):
                worker.set_effective_tau(tau)
                worker.train(iter(_batches(2, seed0=20 + tau)))
        finally:
            worker.executor.stop()
        snap = device_mod.snapshot()
        # the regression pin: τ is a host-side schedule, not a trace
        # constant — sweeping it re-specializes NOTHING
        assert snap["recompiles_post_warmup"] == 0


# ---------------------------------------------------------------------------
# KKT significance filter: contracts and accounting
# ---------------------------------------------------------------------------


class TestKKTFilter:
    def test_filter_off_two_runs_bit_identical(self, po):
        leaves = []
        for i in range(2):
            worker = _worker(
                po, tau=2, name=f"cons_off_{i}", update="sparse"
            )
            try:
                worker.train(iter(_batches(6)))
                leaves.append([np.asarray(x) for x in _state_leaves(worker)])
            finally:
                worker.executor.stop()
        for a, b in zip(*leaves):
            assert np.array_equal(a, b)

    def test_escape_one_filter_is_bit_identical_to_off(self, po):
        """The structural no-op configuration (every suppressed slot
        escapes): the filtered step must land bit-for-bit on the
        unfiltered trajectory — the contract that the mask composes
        without perturbing any update it keeps."""
        results = []
        for name, kw in (
            ("cons_id_off", {}),
            ("cons_id_noop", {"kkt_filter": True, "kkt_escape": 1.0}),
        ):
            worker = _worker(
                po, tau=2, name=name, update="sparse", **kw
            )
            try:
                worker.train(iter(_batches(6)))
                results.append(
                    [np.asarray(x) for x in _state_leaves(worker)]
                )
            finally:
                worker.executor.stop()
        for a, b in zip(*results):
            assert np.array_equal(a, b)

    def test_all_suppressed_leaves_state_bit_untouched(self, po):
        """A margin past every gradient with the escape hatch off:
        every at-zero slot is a provable no-op, so ONE filtered step
        must leave the whole table bit-identical to init."""
        worker = _worker(
            po, tau=0, name="cons_allsup", update="sparse",
            kkt_filter=True, kkt_margin=1e9, kkt_escape=0.0,
        )
        try:
            before = [np.asarray(x) for x in _state_leaves(worker)]
            worker.train(iter(_batches(2)))
            after = [np.asarray(x) for x in _state_leaves(worker)]
            tracker = worker._consistency.tracker
        finally:
            worker.executor.stop()
        assert tracker.candidates > 0
        assert tracker.suppressed == tracker.candidates
        assert tracker.pushed == 0
        for a, b in zip(before, after):
            assert np.array_equal(a, b)

    def test_two_filtered_runs_deterministic(self, po):
        summaries, leaves = [], []
        for i in range(2):
            worker = _worker(
                po, tau=2, name=f"cons_det_{i}", update="sparse",
                kkt_filter=True, kkt_drop_after=2, kkt_revisit_every=4,
                ingest_workers=1,
            )
            try:
                worker.train(iter(_batches(8)))
                summaries.append(worker._consistency.tracker.summary())
                leaves.append([np.asarray(x) for x in _state_leaves(worker)])
            finally:
                worker.executor.stop()
        assert summaries[0] == summaries[1]
        for a, b in zip(*leaves):
            assert np.array_equal(a, b)

    def test_suppression_reconciles_against_push_keys_counter(self, po):
        from parameter_server_tpu.telemetry import (
            registry as telemetry_registry,
        )
        from parameter_server_tpu.telemetry.instruments import (
            parameter_instruments,
        )

        if not telemetry_registry.enabled():
            pytest.skip("telemetry registry disabled")
        push = parameter_instruments(
            telemetry_registry.default_registry()
        )["push_keys"]
        before = push.value(store="cons_recon", channel=0)
        worker = _worker(
            po, tau=2, name="cons_recon", update="sparse",
            kkt_filter=True,
        )
        try:
            worker.train(iter(_batches(6)))
            summary = worker._consistency.tracker.summary()
        finally:
            worker.executor.stop()
        # the shipped margin suppresses some keys and ships the rest...
        assert 0 < summary["suppressed"] < summary["candidates"]
        # ...the in-jit identity, metered host-side...
        assert summary["reconciled"]
        assert summary["pushed"] + summary["suppressed"] == (
            summary["candidates"]
        )
        # ...and credited to the worker's store label, so the bench
        # record's reduction claim reconciles against ps_push_keys_total
        after = push.value(store="cons_recon", channel=0)
        assert after - before == summary["pushed"]

    def test_host_drop_engages_and_revisits(self, po):
        worker = _worker(
            po, tau=1, name="cons_drop", update="sparse",
            kkt_filter=True, kkt_margin=1e9, kkt_escape=0.0,
            kkt_drop_after=2, kkt_revisit_every=5, ingest_workers=1,
        )
        try:
            # same batch repeatedly: every slot is suppressed every
            # sighting, so streaks cross drop_after deterministically
            b = _batches(1)[0]
            worker.train(iter([b] * 10))
            tracker = worker._consistency.tracker
            summary = tracker.summary()
        finally:
            worker.executor.stop()
        assert summary["dropped_slots"] > 0
        assert summary["dropped_entries"] > 0
        assert summary["filtered_batches"] > 0
        # the deterministic revisit cadence shipped unfiltered batches
        assert summary["revisit_batches"] == 2  # preps 5 and 10

    def test_config_validation(self, po):
        with pytest.raises(ValueError, match="sparse"):
            _worker(po, name="cons_bad1", kkt_filter=True, update="dense")
        with pytest.raises(ValueError, match="ingest_workers=1"):
            _worker(
                po, name="cons_bad2", update="sparse",
                kkt_filter=True, kkt_drop_after=2,
            )


# ---------------------------------------------------------------------------
# satellite 1: breach accounting tracks the LIVE τ
# ---------------------------------------------------------------------------


class TestLiveTauAccounting:
    def test_over_tau_margin_uses_tau_at_submit_time(self, po):
        worker = _worker(po, tau=4, name="cons_live")
        try:
            worker.train(iter(_batches(6)))
            plane = worker._learning
            st = plane.staleness_summary()
            assert st["within_bound"] and st["over_tau_max"] <= 0
            # a submission whose realized staleness exceeds the τ in
            # force AT SUBMIT TIME breaches, even under the configured
            # cap — the live-τ semantics the staleness_breach rule
            # now pages on
            plane.note_submit(3, tau=1)
            st = plane.staleness_summary()
        finally:
            worker.executor.stop()
        assert st["over_tau_max"] == 2
        assert not st["within_bound"]
        assert st["configured_tau"] == 4

    def test_live_tau_follows_set_effective_tau(self, po):
        worker = _worker(po, tau=4, name="cons_live2")
        try:
            worker.set_effective_tau(2)
            st = worker._learning.staleness_summary()
        finally:
            worker.executor.stop()
        assert st["live_tau"] == 2
        assert st["configured_tau"] == 4


# ---------------------------------------------------------------------------
# the whole episode in one flight-recorder bundle
# ---------------------------------------------------------------------------


class TestRollbackBundle:
    def test_reaction_captures_one_bundle_when_armed(self, po):
        from parameter_server_tpu.telemetry import blackbox

        prev = blackbox.set_min_interval(0.0)
        was_armed = blackbox.installed_recorder() is not None
        blackbox.arm()
        n0 = len(blackbox.bundles())
        worker = _worker(po, tau=3, name="cons_bundle", tau_adaptive=True)
        try:
            worker.train(iter(_batches(3)))
            bad = _batches(1, seed0=77)[0]
            bad.y = np.full_like(bad.y, np.float32("nan"))
            worker.train(iter([bad]))
        finally:
            worker.executor.stop()
            blackbox.set_min_interval(prev)
            if not was_armed:
                blackbox.disarm()
        new = blackbox.bundles()[n0:]
        triggers = [b["trigger"]["kind"] for b in new]
        assert "consistency_rollback" in triggers
        b = new[triggers.index("consistency_rollback")]
        assert b["trigger"]["detail"] == "nonfinite"

"""Live repartitioning: KVVector.migrate + RebalanceController.

The contracts under test (ISSUE/PERFORMANCE.md "Declarative
partitioning", ROBUSTNESS.md "The backup barrier"):

- a migration moves rows online through the consistent-snapshot
  machinery — per-channel barrier timestamps bound which pushes are in
  the snapshot, journaled pushes past the barrier replay in order with
  translated slots;
- post-migration state is BIT-IDENTICAL to an undisturbed run (all
  parity checks here compare run-vs-run in base layout — never against
  arithmetic identities, which float accumulation order breaks);
- serving degrades (lock/queue latency) during the move, it never
  errors — a pull stream across the migration completes every request;
- recovery COMPOSES with migration: a restore landing mid-flight bumps
  the generation, the migration discards its stale image and
  re-snapshots, and no acked post-restore push is lost.

Every test runs on the conftest-forced 8-device CPU platform (`make
mesh-test` re-runs this file standalone under the same XLA_FLAGS).
"""

import threading
import time

import jax
import numpy as np
import pytest

from parameter_server_tpu.parallel import mesh as meshlib
from parameter_server_tpu.parallel import partition as partlib
from parameter_server_tpu.system import faults


@pytest.fixture(autouse=True)
def hermetic():
    from parameter_server_tpu.system.postoffice import Postoffice

    Postoffice.reset()
    faults.reset()
    yield
    faults.reset()
    Postoffice.reset()


def _store(num_data=4, num_server=2, num_slots=64, k=2, hashed=True,
           name="reb", keys=None):
    """A fresh KVVector on its own mesh (Postoffice untouched)."""
    from parameter_server_tpu.parameter.kv_vector import KVVector

    mesh = meshlib.make_mesh(num_data=num_data, num_server=num_server)
    kv = KVVector(mesh=mesh, k=k, num_slots=num_slots, hashed=hashed,
                  name=name)
    if keys is not None:
        kv.set_keys(0, keys)
    return kv


def _batches(n, k=2, seed=3, n_keys=40, key_space=997):
    rng = np.random.default_rng(seed)
    out = []
    for _ in range(n):
        keys = np.sort(
            rng.choice(key_space, size=n_keys, replace=False)
        ).astype(np.int64)
        vals = rng.normal(size=(n_keys, k)).astype(np.float32)
        out.append((keys, vals))
    return out


def _push_all(kv, batches):
    for keys, vals in batches:
        kv.push(kv.request(channel=0), keys=keys, values=vals)
    kv.executor.wait_all(pop=False)


def _perm(num_slots, seed=11):
    rng = np.random.default_rng(seed)
    return rng.permutation(num_slots).astype(np.int64)


class TestMigrate:
    def test_rejects_non_bijection(self):
        kv = _store(name="rej")
        with pytest.raises(ValueError, match="bijection"):
            kv.migrate(np.zeros(kv.num_slots, dtype=np.int64))
        with pytest.raises(ValueError, match="bijection"):
            kv.migrate(np.arange(kv.num_slots - 1))

    def test_bit_parity_vs_undisturbed_hashed(self):
        """Migrating mid-stream leaves the (base-layout) table
        bit-identical to a run that never migrated."""
        batches = _batches(6)
        perm = _perm(64)

        def run(migrate_at):
            kv = _store(name=f"mig{migrate_at}")
            for i, (keys, vals) in enumerate(batches):
                if i == migrate_at:
                    mig = kv.migrate(perm)
                    assert mig["rows_moved"] > 0
                kv.push(kv.request(channel=0), keys=keys, values=vals)
            kv.executor.wait_all(pop=False)
            return kv.get_replica()[0]

        undisturbed = run(migrate_at=None)
        migrated = run(migrate_at=3)
        assert undisturbed.tobytes() == migrated.tobytes()

    def test_pull_routing_and_values_survive_migration_exact_dir(self):
        """Exact directory: after the move, pulls by key return the
        same bytes as before — the remap routes lookups to the
        relocated rows."""
        keys = np.arange(40, dtype=np.int64)
        kv = _store(hashed=False, name="exact", keys=keys)
        _push_all(kv, [(keys, b) for _, b in _batches(3, n_keys=40)])
        before = kv.wait_pull(kv.pull(kv.request(channel=0), keys=keys))
        mig = kv.migrate(_perm(kv.num_slots, seed=5))
        assert mig["attempts"] == 1
        assert kv.layout(0) is not None
        after = kv.wait_pull(kv.pull(kv.request(channel=0), keys=keys))
        assert np.asarray(before).tobytes() == np.asarray(after).tobytes()
        # and the physical table really is permuted: channel table in
        # current layout != base-layout replica ordering
        base = kv.get_replica()[0]
        cur = np.asarray(kv.table(0, copy=True))
        assert base.tobytes() != cur.tobytes()
        np.testing.assert_array_equal(cur[kv.layout(0)], base)

    def test_composed_migrations_stack(self):
        """Two migrations compose (perm2[perm1]); pulls and the
        base-layout replica stay correct through both."""
        keys = np.arange(40, dtype=np.int64)
        batches = _batches(4, n_keys=40)
        kv = _store(hashed=False, name="twice", keys=keys)
        _push_all(kv, [(keys, b) for _, b in batches[:2]])
        kv.migrate(_perm(kv.num_slots, seed=1))
        _push_all(kv, [(keys, b) for _, b in batches[2:]])
        kv.migrate(_perm(kv.num_slots, seed=2))

        ref = _store(hashed=False, name="twice_ref", keys=keys)
        _push_all(ref, [(keys, b) for _, b in batches])
        assert kv.get_replica()[0].tobytes() == ref.get_replica()[0].tobytes()
        got = kv.wait_pull(kv.pull(kv.request(channel=0), keys=keys))
        want = ref.wait_pull(ref.pull(ref.request(channel=0), keys=keys))
        assert np.asarray(got).tobytes() == np.asarray(want).tobytes()

    def test_snapshot_roundtrip_across_migration(self):
        """Backups are layout-independent: a replica taken pre-move
        restores correctly post-move (set_replica re-applies the
        current perm)."""
        keys = np.arange(40, dtype=np.int64)
        batches = _batches(3, n_keys=40)
        kv = _store(hashed=False, name="roundtrip", keys=keys)
        _push_all(kv, [(keys, b) for _, b in batches])
        snap = kv.get_replica()
        kv.migrate(_perm(kv.num_slots, seed=9))
        kv.set_replica(snap)
        kv.executor.wait_all(pop=False)
        assert kv.get_replica()[0].tobytes() == snap[0].tobytes()
        got = kv.wait_pull(kv.pull(kv.request(channel=0), keys=keys))
        ref = _store(hashed=False, name="roundtrip_ref", keys=keys)
        _push_all(ref, [(keys, b) for _, b in batches])
        want = ref.wait_pull(ref.pull(ref.request(channel=0), keys=keys))
        assert np.asarray(got).tobytes() == np.asarray(want).tobytes()


def _migrate_under_pushes(kv, keys, batches):
    """Push ``batches[0]``, then stall a migration between its snapshot
    and install (rebalance.migrate fault) while the rest keep landing.
    Returns ``migrate``'s record."""
    _push_all(kv, [(keys, batches[0][1])])
    faults.arm("rebalance.migrate", kind="delay", delay_s=0.5, once=True)
    result = {}
    t = threading.Thread(
        target=lambda: result.update(kv.migrate(_perm(kv.num_slots, seed=4)))
    )
    t.start()
    time.sleep(0.1)  # let the migration reach its stalled window
    for _, vals in batches[1:]:
        kv.push(kv.request(channel=0), keys=keys, values=vals)
    t.join(timeout=30)
    assert not t.is_alive()
    kv.executor.wait_all(pop=False)
    return result


class TestJournalReplay:
    def test_pushes_landing_mid_migration_replay_bit_identically(self):
        """Pushes that land while a migration is stalled are journaled,
        replayed past the barrier with translated slots, and the result
        is bit-identical to an undisturbed run."""
        keys = np.arange(40, dtype=np.int64)
        batches = _batches(4, n_keys=40)
        kv = _store(hashed=False, name="journal", keys=keys)
        result = _migrate_under_pushes(kv, keys, batches)
        assert result["journaled"] >= 1
        assert result["replayed"] == result["journaled"]

        ref = _store(hashed=False, name="journal_ref", keys=keys)
        _push_all(ref, [(keys, b) for _, b in batches])
        assert kv.get_replica()[0].tobytes() == ref.get_replica()[0].tobytes()


    def test_a_live_migration_compiles_nothing_new(self):
        """A move under live pushes and pulls is a latency event, not a
        compile event: the table a migration installs keeps the shapes
        and the sharding the data plane compiled for, so a store that
        journals and replays pushes across its own move, and is pulled
        from afterwards, re-specializes no program of ``ops/kv_ops``."""
        from parameter_server_tpu.telemetry import device as device_mod

        keys = np.arange(40, dtype=np.int64)
        batches = _batches(4, n_keys=40)
        device_mod.reset()
        try:
            scratch = _store(hashed=False, name="warm", keys=keys)
            _push_all(scratch, [(keys, batches[0][1])])
            np.asarray(scratch.wait_pull(
                scratch.pull(scratch.request(channel=0), keys=keys)
            ))
            device_mod.mark_warmup()

            kv = _store(hashed=False, name="live", keys=keys)
            result = _migrate_under_pushes(kv, keys, batches)
            np.asarray(
                kv.wait_pull(kv.pull(kv.request(channel=0), keys=keys))
            )
            assert result["journaled"] >= 1
            snap = device_mod.snapshot()
            assert snap["recompiles_post_warmup"] == 0, snap["functions"]
        finally:
            device_mod.reset()


class TestServeContinuity:
    def test_pull_stream_across_migration_completes_every_request(self):
        """Serving degrades (lock/queue latency) during the move — it
        NEVER errors: every pull issued while the migration stalls and
        flips returns the exact pre-migration bytes (no concurrent
        pushes, so any deviation is a routing bug)."""
        keys = np.arange(40, dtype=np.int64)
        kv = _store(hashed=False, name="serve", keys=keys)
        _push_all(kv, [(keys, b) for _, b in _batches(2, n_keys=40)])
        expect = np.asarray(
            kv.wait_pull(kv.pull(kv.request(channel=0), keys=keys))
        ).tobytes()

        faults.arm("rebalance.migrate", kind="delay", delay_s=0.4,
                   once=True)
        done = threading.Event()
        stats = {"ok": 0, "failed": 0}

        def serve():
            while not done.is_set():
                try:
                    got = kv.wait_pull(
                        kv.pull(kv.request(channel=0), keys=keys)
                    )
                    assert np.asarray(got).tobytes() == expect
                    stats["ok"] += 1
                except Exception:
                    stats["failed"] += 1

        server = threading.Thread(target=serve)
        server.start()
        try:
            mig = kv.migrate(_perm(kv.num_slots, seed=6))
        finally:
            done.set()
            server.join(timeout=30)
        assert mig["attempts"] == 1
        assert stats["failed"] == 0
        assert stats["ok"] > 0  # requests really flowed across the move


class TestRecoveryComposition:
    def test_restore_landing_mid_migration_forces_resnapshot(self):
        """Kill-one-shard recovery DURING a live migration: the restore
        bumps the generation, the stalled migration discards its stale
        image and retries, and the final table is bit-identical to the
        same recovery timeline without any migration — no acked
        post-restore push is lost, no pre-restore bytes resurrect."""
        from parameter_server_tpu.parameter.replica import ReplicaManager

        keys = np.arange(40, dtype=np.int64)
        batches = _batches(6, n_keys=40)

        def timeline(kv, rm, migrate):
            # pre-crash training, then the consistent backup
            _push_all(kv, [(keys, b) for _, b in batches[:2]])
            rm.backup_consistent(kv)
            result = {}
            t = None
            if migrate:
                faults.arm("rebalance.migrate", kind="delay",
                           delay_s=0.6, once=True)
                t = threading.Thread(
                    target=lambda: result.update(
                        kv.migrate(_perm(kv.num_slots, seed=8))
                    )
                )
                t.start()
                time.sleep(0.1)  # migration now stalled post-snapshot
            # updates that the recovery will wipe (post-backup, pre-
            # restore — the recovery drill's semantics)...
            _push_all(kv, [(keys, batches[2][1])])
            # ...the shard dies and the snapshot is restored THROUGH
            # the executor (live path: note_external_restore fires)
            assert rm.recover(kv, through_executor=True)
            # acked post-restore updates — these must survive
            for _, vals in (b for b in batches[3:]):
                kv.push(kv.request(channel=0), keys=keys, values=vals)
            if t is not None:
                t.join(timeout=30)
                assert not t.is_alive()
            kv.executor.wait_all(pop=False)
            return result

        kv_ref = _store(hashed=False, name="rec_ref", keys=keys)
        timeline(kv_ref, ReplicaManager(), migrate=False)
        ref = kv_ref.get_replica()[0]

        kv = _store(hashed=False, name="rec_mig", keys=keys)
        result = timeline(kv, ReplicaManager(), migrate=True)
        assert result["attempts"] >= 2  # the stale image was discarded
        assert kv.layout(0) is not None  # ...and the move still landed
        assert kv.get_replica()[0].tobytes() == ref.tobytes()

    def test_migrate_gives_up_after_max_attempts(self):
        kv = _store(name="giveup")
        _push_all(kv, _batches(1))
        orig = kv.snapshot

        def poisoned(ch=0, callback=None):
            kv.note_external_restore()  # every snapshot is born stale
            return orig(ch, callback)

        kv.snapshot = poisoned
        with pytest.raises(RuntimeError, match="could not complete"):
            kv.migrate(_perm(kv.num_slots), max_attempts=2)
        kv.snapshot = orig
        # the store still serves after the failed migration
        kv.executor.wait_all(pop=False)
        assert kv.layout(0) is None


class TestKeyHeatRebase:
    def test_rebase_translates_candidates_and_resets_window(self):
        from parameter_server_tpu.telemetry.learning import KeyHeat

        heat = KeyHeat(num_slots=64, num_shards=8, top_k=16,
                       decay_every=1 << 30)
        hot = np.arange(8)  # all of shard 0
        heat.note(np.repeat(hot, 40))
        assert heat.shares()["imbalance"] == pytest.approx(8.0)
        assert {h["slot"] for h in heat.top_slots()} == set(hot.tolist())

        perm = np.arange(64)
        perm[0], perm[63] = 63, 0  # slot 0 relocated to shard 7
        heat.rebase(perm)
        # the window reset: no weight, no imbalance reading
        s = heat.shares()
        assert s["total_weight"] == 0.0 and s["imbalance"] is None
        # candidates translated across the layout change
        assert 63 in {h["slot"] for h in heat.top_slots()} or not heat.top_slots()
        # post-rebalance traffic for the SAME keys lands spread out
        heat.note(np.repeat(perm[hot], 40))
        counts_max_over_mean = heat.shares()["imbalance"]
        assert counts_max_over_mean < 8.0


class TestRebalanceController:
    def test_alert_fires_controller_rebalances_and_imbalance_recovers(self):
        """End-to-end on 8 server shards: heat-skewed traffic → the
        shipped shard_imbalance rule (threshold 4.0, for 5 s) reaches
        firing → the attached controller plans from the measured
        hot-slot/load-share tables and migrates online → post-rebalance
        traffic re-measures below threshold → table bit-identical to an
        undisturbed run."""
        from parameter_server_tpu.telemetry import alerts as alerts_mod
        from parameter_server_tpu.telemetry import (
            registry as telemetry_registry,
        )
        from parameter_server_tpu.telemetry.instruments import (
            learning_instruments,
        )
        from parameter_server_tpu.telemetry.learning import KeyHeat

        keys = np.arange(48, dtype=np.int64)
        batches = _batches(3, n_keys=48)
        # 1x8 mesh: 8 server shards (max/mean tops out at num_shards,
        # so the shipped threshold 4.0 NEEDS > 4 shards to be exceeded)
        kv = _store(num_data=1, num_server=8, hashed=False, name="ctl",
                    keys=keys)
        assert kv.num_slots == 64
        _push_all(kv, [(keys, b) for _, b in batches])

        heat = KeyHeat(num_slots=64, num_shards=8, top_k=16,
                       decay_every=1 << 30)
        hot = np.arange(8)  # keys 0..7 → slots 0..7: all of shard 0
        for _ in range(4):
            heat.note(np.repeat(hot, 25))
        imb0 = heat.shares()["imbalance"]
        assert imb0 > 4.0

        ctl = partlib.RebalanceController(kv, heat)
        assert ctl.threshold == 4.0  # read from the shipped rule
        assert ctl.should_rebalance()

        reg = telemetry_registry.default_registry()
        gauge = learning_instruments(reg)["shard_imbalance"]
        gauge.set(imb0)
        mgr = alerts_mod.AlertManager(alerts_mod.default_rules(),
                                      registry=reg)
        ctl.attach(mgr)
        assert ctl.history() == []
        mgr.evaluate(now=0.0)  # breach observed → pending
        assert ctl.history() == []  # for_s dwell: not yet
        mgr.evaluate(now=6.0)  # past for_s=5 → firing → rebalance
        hist = ctl.history()
        assert len(hist) == 1
        rec = hist[0]
        assert rec["rows_moved"] > 0
        assert rec["imbalance_before"] == pytest.approx(imb0)
        assert rec["predicted_imbalance"] < 4.0
        assert kv.layout(0) is not None

        # post-rebalance traffic (same hot keys, new layout) stays
        # below the alert threshold
        perm = kv.layout(0)
        for _ in range(4):
            heat.note(np.repeat(perm[hot], 25))
        post = ctl.refresh_post_imbalance()
        assert post is not None and post < 4.0

        # the moved table still matches an undisturbed run bit-for-bit
        ref = _store(num_data=1, num_server=8, hashed=False,
                     name="ctl_ref", keys=keys)
        _push_all(ref, [(keys, b) for _, b in batches])
        assert kv.get_replica()[0].tobytes() == ref.get_replica()[0].tobytes()

        # firing → firing does not re-trigger; a second firing edge
        # after the heat window rebased (imbalance gone) is a no-op
        mgr.evaluate(now=12.0)
        assert len(ctl.history()) == 1

    def test_execute_is_noop_below_threshold(self):
        from parameter_server_tpu.telemetry.learning import KeyHeat

        kv = _store(num_data=1, num_server=8, name="noop")
        heat = KeyHeat(num_slots=kv.num_slots, num_shards=8,
                       decay_every=1 << 30)
        heat.note(np.arange(64))  # perfectly uniform
        ctl = partlib.RebalanceController(kv, heat)
        assert not ctl.should_rebalance()
        assert ctl.execute() is None
        assert kv.layout(0) is None

    def test_plan_rebalance_is_deterministic_and_bijective(self):
        from parameter_server_tpu.telemetry.learning import KeyHeat

        def mk():
            heat = KeyHeat(num_slots=64, num_shards=8, top_k=16,
                           decay_every=1 << 30)
            heat.note(np.repeat(np.arange(8), 30))
            return heat

        p1 = partlib.plan_rebalance(mk(), 64, 8)
        p2 = partlib.plan_rebalance(mk(), 64, 8)
        assert p1 is not None
        np.testing.assert_array_equal(p1.perm, p2.perm)
        np.testing.assert_array_equal(np.sort(p1.perm), np.arange(64))
        assert p1.rows_moved == 2 * len(p1.moves)  # swaps, not drops
        assert p1.predicted_imbalance < p1.imbalance_before

    def test_plan_rebalance_declines_single_shard_and_balance(self):
        from parameter_server_tpu.telemetry.learning import KeyHeat

        heat = KeyHeat(num_slots=64, num_shards=1, decay_every=1 << 30)
        heat.note(np.repeat(np.arange(8), 30))
        assert partlib.plan_rebalance(heat, 64, 1) is None
        cold = KeyHeat(num_slots=64, num_shards=8, decay_every=1 << 30)
        assert partlib.plan_rebalance(cold, 64, 8) is None

"""Chaos plane (doc/ROBUSTNESS.md): the deterministic fault-injection
registry (system/faults.py), the named fault points threaded through
Van/Executor/Heartbeat/Checkpoint/Ingest/serving, the retry/deadline
policy objects (utils/retry.py), the periodic consistent replica
backup, and degraded-mode serving. Every injected failure here is an
exercise of machinery that, before this plane existed, had only ever
been tested politely."""

import os
import threading
import time

import numpy as np
import pytest

from parameter_server_tpu.system import faults
from parameter_server_tpu.utils.retry import (
    Deadline,
    DeadlineExceeded,
    RetryPolicy,
    call_with_retry,
)


@pytest.fixture(autouse=True)
def _hermetic_faults():
    """Every test starts and ends with a disarmed default registry."""
    faults.reset()
    yield
    faults.reset()


# ---------------------------------------------------------------------------
# the registry


class TestFaultRegistry:
    def test_disarmed_check_is_none_and_cheap(self):
        assert faults.check("van.transfer") is None
        assert faults.default_registry().n_armed == 0

    def test_unknown_point_rejected(self):
        with pytest.raises(ValueError, match="unknown fault point"):
            faults.arm("van.transfr")  # typo'd drills must not test nothing

    def test_after_n_calls_and_counters(self):
        faults.arm("executor.step", after_n_calls=2)
        assert faults.check("executor.step") is None
        assert faults.check("executor.step") is None
        assert faults.check("executor.step") is not None
        sp = faults.spec("executor.step")
        assert sp.calls == 3 and sp.fired == 1

    def test_once_disarms_after_first_fire(self):
        faults.arm("executor.step", once=True)
        assert faults.check("executor.step") is not None
        assert faults.check("executor.step") is None
        assert faults.default_registry().n_armed == 0

    def test_match_filters_and_does_not_count_mismatches(self):
        faults.arm("heartbeat.report", kind="silence", match="S0")
        assert faults.check("heartbeat.report", detail="W0") is None
        assert faults.check("heartbeat.report", detail="S0") is not None
        # only the matching call was counted
        faults.arm("heartbeat.report", kind="silence", match="S1",
                   after_n_calls=1)
        assert faults.check("heartbeat.report", detail="W0") is None
        assert faults.check("heartbeat.report", detail="S1") is None  # call 1
        assert faults.check("heartbeat.report", detail="S1") is not None

    def test_probability_deterministic_under_seed(self):
        def pattern(seed):
            reg = faults.FaultRegistry(seed=seed)
            reg.arm("van.transfer", kind="drop", probability=0.5)
            return [reg.check("van.transfer") is not None for _ in range(64)]

        a, b = pattern(123), pattern(123)
        assert a == b  # bit-identical firing pattern under one seed
        assert any(a) and not all(a)  # and it is actually probabilistic
        assert pattern(77) != a  # a different seed is a different drill

    def test_scoped_disarms_even_when_fault_propagates(self):
        with pytest.raises(faults.FaultError):
            with faults.scoped("executor.step", kind="raise"):
                faults.inject("executor.step")
        assert faults.spec("executor.step") is None

    def test_inject_sleeps_then_returns_spec_for_custom_kinds(self):
        faults.arm("serve.pull", kind="stall", delay_s=0.05)
        t0 = time.perf_counter()
        sp = faults.inject("serve.pull")
        assert sp is not None and sp.kind == "stall"
        assert time.perf_counter() - t0 >= 0.045


# ---------------------------------------------------------------------------
# retry / deadline policy


class TestRetryPolicy:
    def test_succeeds_after_transient_failures(self):
        calls = []

        def flaky():
            calls.append(1)
            if len(calls) < 3:
                raise OSError("transient")
            return "ok"

        slept = []
        assert call_with_retry(
            flaky, RetryPolicy(max_attempts=3, base_delay_s=0.01),
            sleep=slept.append,
        ) == "ok"
        assert len(calls) == 3 and len(slept) == 2
        assert slept[1] > slept[0] * 1.2  # exponential growth (jittered)

    def test_backoff_deterministic_under_seed(self):
        def delays(seed):
            out = []
            with pytest.raises(OSError):
                call_with_retry(
                    lambda: (_ for _ in ()).throw(OSError("x")),
                    RetryPolicy(max_attempts=4, base_delay_s=0.01),
                    seed=seed, sleep=out.append,
                )
            return out

        assert delays(5) == delays(5)

    def test_final_attempt_propagates_unwrapped(self):
        with pytest.raises(KeyError):
            call_with_retry(
                lambda: (_ for _ in ()).throw(KeyError("gone")),
                RetryPolicy(max_attempts=2, base_delay_s=0.0, jitter=0.0),
                sleep=lambda s: None,
            )

    def test_non_retryable_propagates_immediately(self):
        calls = []

        def boom():
            calls.append(1)
            raise KeyError("no")

        with pytest.raises(KeyError):
            call_with_retry(
                boom, RetryPolicy(max_attempts=5, retry_on=(OSError,)),
                sleep=lambda s: None,
            )
        assert len(calls) == 1

    def test_deadline_refuses_doomed_backoff(self):
        clock = [0.0]
        with pytest.raises(DeadlineExceeded) as ei:
            call_with_retry(
                lambda: (_ for _ in ()).throw(OSError("x")),
                RetryPolicy(
                    max_attempts=10, base_delay_s=5.0, deadline_s=1.0,
                    jitter=0.0,
                ),
                clock=lambda: clock[0], sleep=lambda s: None,
            )
        assert ei.value.deadline_s == 1.0
        assert isinstance(ei.value, TimeoutError)  # legacy callers fine

    def test_deadline_countdown(self):
        clock = [0.0]
        d = Deadline(2.0, clock=lambda: clock[0])
        assert not d.expired() and d.remaining() == 2.0
        clock[0] = 3.0
        assert d.expired()
        assert Deadline(None).remaining() is None


# ---------------------------------------------------------------------------
# executor fault point + diagnostic wait deadline


class TestExecutorFaults:
    def test_injected_raise_propagates_to_waiter(self):
        from parameter_server_tpu.system.executor import Executor

        ex = Executor(name="chaos")
        assert ex.wait(ex.submit(lambda: 1)) == 1
        with faults.scoped("executor.step", kind="raise", once=True):
            ts = ex.submit(lambda: 2)
            with pytest.raises(faults.FaultError):
                ex.wait(ts, timeout=10)
        # the executor survives the injected failure
        assert ex.wait(ex.submit(lambda: 3)) == 3
        ex.stop()

    def test_injected_stall_delays_dispatch(self):
        from parameter_server_tpu.system.executor import Executor

        ex = Executor(name="chaos_stall")
        with faults.scoped("executor.step", kind="stall", delay_s=0.1,
                           once=True):
            t0 = time.perf_counter()
            assert ex.wait(ex.submit(lambda: 4), timeout=10) == 4
            assert time.perf_counter() - t0 >= 0.09
        ex.stop()

    def test_wait_timeout_names_wedged_deps(self):
        from parameter_server_tpu.system.executor import Executor
        from parameter_server_tpu.system.message import Task

        ex = Executor(name="wedge")
        gate = threading.Event()
        dep = ex.submit(gate.wait)
        blocked = ex.submit(lambda: 9, Task(request=True, time=500,
                                            wait_time=[dep]))
        with pytest.raises(DeadlineExceeded) as ei:
            ex.wait(blocked, timeout=0.15)
        msg = str(ei.value)
        assert str(blocked) in msg and str(dep) in msg
        assert "unsatisfied wait_time deps" in msg
        gate.set()
        assert ex.wait(blocked, timeout=10) == 9  # still claimable after
        ex.stop()

    def test_wait_all_timeout_is_one_budget(self):
        from parameter_server_tpu.system.executor import Executor

        ex = Executor(name="drainwedge")
        gate = threading.Event()
        ex.submit(gate.wait)
        t0 = time.perf_counter()
        with pytest.raises(DeadlineExceeded):
            ex.wait_all(timeout=0.2)
        assert time.perf_counter() - t0 < 5
        gate.set()
        ex.wait_all(timeout=10)
        ex.stop()


# ---------------------------------------------------------------------------
# heartbeat silence + van wire faults


class TestTransportFaults:
    def test_heartbeat_silence_kills_exactly_the_matched_node(self):
        from parameter_server_tpu.system.heartbeat import (
            HeartbeatCollector,
            HeartbeatReport,
        )

        c = HeartbeatCollector(timeout=5.0)
        for nid in ("S0", "W0"):
            c.report(nid, HeartbeatReport(hostname=nid))
        t0 = time.time()
        faults.arm("heartbeat.report", kind="silence", match="S0")
        # both nodes keep "reporting"; only W0's reports arrive
        c.report("S0", HeartbeatReport())
        c.report("W0", HeartbeatReport())
        c._last_seen["W0"] = t0 + 10  # W0 heard from after the horizon
        assert c.dead_nodes(now=t0 + 6) == ["S0"]

    def test_van_drop_raises_and_never_counts_recv(self, mesh8):
        from parameter_server_tpu.system.remote_node import RemoteNode
        from parameter_server_tpu.system.van import Van
        from parameter_server_tpu.system.message import Message, Task

        van = Van(mesh8)
        a, b = RemoteNode("S0"), RemoteNode("W0")

        def msg():
            m = Message(task=Task(), sender="W0", recver="S0")
            m.values = [np.ones(32, np.float32)]
            return m

        van.transfer(a, b, msg())  # healthy round trip
        sent0, recv0 = van.wire_sent_bytes, van.wire_recv_bytes
        with faults.scoped("van.transfer", kind="drop", once=True):
            with pytest.raises(faults.FaultError):
                van.transfer(a, b, msg())
        assert van.wire_sent_bytes > sent0  # the frame left the sender
        assert van.wire_recv_bytes == recv0  # and never arrived

    def test_van_duplicate_delivers_twice(self, mesh8):
        from parameter_server_tpu.system.remote_node import RemoteNode
        from parameter_server_tpu.system.van import Van
        from parameter_server_tpu.system.message import Message, Task

        van = Van(mesh8)
        a, b = RemoteNode("S0"), RemoteNode("W0")

        def msg():
            m = Message(task=Task(), sender="W0", recver="S0")
            m.values = [np.ones(32, np.float32)]
            return m

        out = van.transfer(a, b, msg())
        single = van.wire_recv_bytes
        with faults.scoped("van.transfer", kind="duplicate", once=True):
            out = van.transfer(a, b, msg())
        assert out.values  # the (second) delivery still round-trips
        assert van.wire_recv_bytes == 3 * single  # frame decoded twice

    def test_van_delay_is_late_but_delivered(self, mesh8):
        from parameter_server_tpu.system.remote_node import RemoteNode
        from parameter_server_tpu.system.van import Van
        from parameter_server_tpu.system.message import Message, Task

        van = Van(mesh8)
        a, b = RemoteNode("S0"), RemoteNode("W0")
        m = Message(task=Task(), sender="W0", recver="S0")
        m.values = [np.ones(8, np.float32)]
        with faults.scoped("van.transfer", kind="delay", delay_s=0.08):
            t0 = time.perf_counter()
            out = van.transfer(a, b, m)
            assert time.perf_counter() - t0 >= 0.07
        assert out.values


# ---------------------------------------------------------------------------
# checkpoint crash consistency (die mid-write)


class TestCheckpointCrashConsistency:
    def _tree(self, v=1.0):
        return {"w": np.full((4, 2), v, np.float32),
                "step": np.array([v], np.float64)}

    def test_sync_die_mid_write_never_surfaces_torn_dir(self, tmp_path):
        from parameter_server_tpu.parameter.replica import CheckpointManager

        cm = CheckpointManager(str(tmp_path / "ck"), use_orbax=False)
        cm.save(1, self._tree(1.0))
        with faults.scoped("checkpoint.write", kind="die", once=True):
            with pytest.raises(faults.FaultError):
                cm.save(2, self._tree(2.0))
        # the crash window left a torn tmp dir — never a step dir
        names = os.listdir(cm.directory)
        assert any(n.endswith(".tmp") for n in names)
        assert cm.latest_step() == 1
        # a subsequent save HEALS: same step, fresh tmp, atomic rename
        cm.save(2, self._tree(2.0))
        assert cm.latest_step() == 2
        out = cm.restore(2, like=self._tree())
        np.testing.assert_array_equal(out["w"], self._tree(2.0)["w"])

    def test_async_die_reraises_from_wait_and_heals(self, tmp_path):
        from parameter_server_tpu.parameter.replica import CheckpointManager

        cm = CheckpointManager(str(tmp_path / "ck"), use_orbax=False)
        cm.save(5, self._tree(5.0))
        with faults.scoped("checkpoint.write", kind="die", once=True):
            cm.save_async(6, self._tree(6.0))
            with pytest.raises(RuntimeError, match="async checkpoint"):
                cm.wait()
        # the error was consumed by wait(); the torn step never lists
        assert cm.latest_step() == 5
        cm.save_async(6, self._tree(6.0))
        cm.wait()
        assert cm.latest_step() == 6

    def test_npz_fallback_template_mismatch_is_loud(self, tmp_path):
        from parameter_server_tpu.parameter.replica import CheckpointManager

        cm = CheckpointManager(str(tmp_path / "ck"), use_orbax=False)
        cm.save(1, self._tree(1.0))
        wrong = {"w": np.zeros((4, 2), np.float32),
                 "step": np.zeros(1),
                 "extra_moment": np.zeros(3)}
        with pytest.raises(ValueError, match="different model/optimizer"):
            cm.restore(1, like=wrong)


# ---------------------------------------------------------------------------
# ingest worker death


class TestIngestFaults:
    def test_prep_raise_forwards_at_position_and_joins(self):
        from parameter_server_tpu.learner.ingest import IngestPipeline

        before = threading.active_count()
        faults.arm("ingest.prep", kind="raise", after_n_calls=2, once=True)
        pipe = IngestPipeline(
            iter(range(6)), prep_fn=lambda x: x * 10, workers=2,
            name="chaos_ingest",
        ).start()
        got = []
        with pytest.raises(faults.FaultError):
            for item in pipe:
                got.append(item)
        assert got == [0, 10]  # batches before the dead one arrived
        pipe.close()
        deadline = time.time() + 10
        while threading.active_count() > before and time.time() < deadline:
            time.sleep(0.01)
        assert threading.active_count() <= before  # no leaked threads


# ---------------------------------------------------------------------------
# periodic consistent replica backup + barrier replay contract


class TestReplicaBackups:
    def _store(self, mesh8, name):
        from parameter_server_tpu.parameter.kv_vector import KVVector

        return KVVector(mesh=mesh8, k=2, num_slots=64, hashed=True,
                        name=name)

    def _push(self, kv, seed):
        rng = np.random.default_rng(seed)
        keys = rng.integers(0, 1 << 12, 16).astype(np.int64)
        vals = rng.normal(size=(16, 2)).astype(np.float32)
        ts = kv.push(kv.request(channel=0), keys=keys, values=vals)
        kv.executor.wait(ts, timeout=30)
        return ts, keys, vals

    def test_barrier_separates_snapshot_from_later_pushes(self, mesh8):
        from parameter_server_tpu.parameter.replica import ReplicaManager

        kv = self._store(mesh8, "bk_barrier")
        ts1, _, _ = self._push(kv, 1)
        rm = ReplicaManager()
        meta = rm.backup_consistent(kv)
        barrier = meta["barrier"][0]
        ts2, k2, v2 = self._push(kv, 2)
        assert ts1 < barrier < ts2
        after_two = np.array(kv.table(0, copy=True))
        # crash: wipe, recover from the snapshot, replay past the barrier
        kv.set_table(0, kv._zeros())
        assert rm.recover(kv, through_executor=True)
        kv.executor.wait(
            kv.push(kv.request(channel=0), keys=k2, values=v2), timeout=30
        )
        healed = np.array(kv.table(0, copy=True))
        assert healed.tobytes() == after_two.tobytes()  # bit-exact
        kv.executor.stop()

    def test_backup_consistent_untorn_under_live_pushes(self, mesh8):
        """The whole point of the submitted snapshot: a concurrent
        donated-push stream cannot tear the backup (each snapshot is
        SOME prefix of the push sequence, never a mix)."""
        from parameter_server_tpu.parameter.replica import ReplicaManager

        from parameter_server_tpu.parameter.kv_vector import KVVector

        # exact keys: one slot per key (a hashed directory's slot
        # collisions would double-count rows and fake a "torn" read)
        kv = KVVector(mesh=mesh8, k=2, num_slots=64, hashed=False,
                      name="bk_live")
        keys = np.arange(16, dtype=np.int64)
        kv.set_keys(0, keys)
        ones = np.ones((16, 2), np.float32)
        # one synchronous push first so channel 0 exists before the
        # first backup races the pusher's channel creation
        kv.executor.wait(
            kv.push(kv.request(channel=0), keys=keys, values=ones),
            timeout=30,
        )
        stop = threading.Event()
        err = []

        def pusher():
            try:
                while not stop.is_set():
                    kv.executor.wait(
                        kv.push(kv.request(channel=0), keys=keys,
                                values=ones),
                        timeout=30,
                    )
            except BaseException as e:
                err.append(e)

        t = threading.Thread(target=pusher)
        t.start()
        try:
            rm = ReplicaManager()
            for _ in range(5):
                rm.backup_consistent(kv)
                snap = rm._replicas[kv.name][0]
                rows = snap[kv.slots(0, keys)]
                # every pushed row shows the SAME number of pushes —
                # an integer multiple of ones, identical across rows
                counts = np.unique(rows)
                assert len(counts) == 1, counts
        finally:
            stop.set()
            t.join(timeout=30)
        assert not err
        kv.executor.stop()

    def test_periodic_loop_backs_up_and_joins(self, mesh8):
        from parameter_server_tpu.parameter.replica import ReplicaManager

        kv = self._store(mesh8, "bk_periodic")
        self._push(kv, 3)
        rm = ReplicaManager()
        rm.start_periodic(kv, interval_s=0.03)
        with pytest.raises(RuntimeError, match="already running"):
            rm.start_periodic(kv, interval_s=0.03)
        deadline = time.time() + 10
        while time.time() < deadline:
            meta = rm.meta(kv.name)
            if meta and meta["version"] >= 2:
                break
            time.sleep(0.01)
        rm.stop_periodic()
        meta = rm.meta(kv.name)
        assert meta and meta["version"] >= 2 and meta["consistent"]
        # the loop thread is gone; a second stop is a no-op
        rm.stop_periodic()
        assert rm.recover(kv)
        kv.executor.stop()


# ---------------------------------------------------------------------------
# recovery coordinator: retry + telemetry


class TestRecoveryRetryAndTelemetry:
    def _collector(self):
        from parameter_server_tpu.system.heartbeat import (
            HeartbeatCollector,
            HeartbeatReport,
        )

        c = HeartbeatCollector(timeout=5.0)
        c.report("S0", HeartbeatReport(hostname="S0"))
        return c

    def test_transient_handler_failure_retried_not_counted(self):
        from parameter_server_tpu.system.recovery import RecoveryCoordinator
        from parameter_server_tpu.telemetry.instruments import (
            recovery_instruments,
        )
        from parameter_server_tpu.telemetry.registry import default_registry

        reg = default_registry()
        recovery_instruments(reg)  # ensure the family exists to read
        fails_before = reg.get("ps_recovery_handler_failures_total").value()
        c = self._collector()
        rc = RecoveryCoordinator(
            c, handler_retry=RetryPolicy(max_attempts=3, base_delay_s=0.001)
        )
        attempts = []

        def flaky(nid):
            attempts.append(nid)
            if len(attempts) < 2:
                raise OSError("replacement shard mid-rebuild")

        rc.on_server_dead(flaky)
        assert rc.check(now=c._last_seen["S0"] + 6) == ["S0"]
        assert len(attempts) == 2  # retried once, then succeeded
        reg2 = default_registry()
        assert (
            reg2.get("ps_recovery_handler_failures_total").value()
            == fails_before
        )
        assert reg2.get("ps_recovery_deaths_total").value(role="server") >= 1

    def test_exhausted_handler_counts_failure(self):
        from parameter_server_tpu.system.recovery import RecoveryCoordinator
        from parameter_server_tpu.telemetry.instruments import (
            recovery_instruments,
        )
        from parameter_server_tpu.telemetry.registry import default_registry

        recovery_instruments(default_registry())
        before = default_registry().get(
            "ps_recovery_handler_failures_total"
        ).value()
        c = self._collector()
        rc = RecoveryCoordinator(
            c, handler_retry=RetryPolicy(max_attempts=2, base_delay_s=0.001)
        )
        rc.on_server_dead(
            lambda nid: (_ for _ in ()).throw(OSError("still dead"))
        )
        assert rc.check(now=c._last_seen["S0"] + 6) == ["S0"]
        assert default_registry().get(
            "ps_recovery_handler_failures_total"
        ).value() == before + 1


# ---------------------------------------------------------------------------
# degraded-mode serving (503 vs 429)


class TestDegradedServing:
    def _store(self, mesh8, name):
        from parameter_server_tpu.parameter.kv_vector import KVVector

        kv = KVVector(mesh=mesh8, k=1, num_slots=256, hashed=True, name=name)
        keys = np.arange(64, dtype=np.int64)
        vals = np.arange(64, dtype=np.float32).reshape(-1, 1) + 1.0
        kv.executor.wait(
            kv.push(kv.request(channel=0), keys=keys, values=vals),
            timeout=30,
        )
        return kv

    def _fe(self, kv, **cfg_kw):
        from parameter_server_tpu.serving import ServeConfig, ServeFrontend

        cfg = ServeConfig(workers=1, max_queue_depth=64, **cfg_kw)
        return ServeFrontend(kv, cfg).start()

    def test_fallback_mode_live_when_healthy(self, mesh8):
        from parameter_server_tpu.serving import PullRequest

        kv = self._store(mesh8, "deg_live")
        fe = self._fe(kv, replica="fallback")
        try:
            keys = np.array([1, 5, 9], np.int64)
            out = fe.submit(PullRequest(keys=keys)).result(30)
            np.testing.assert_allclose(out, kv.values(0, keys))
            assert fe.stats()["degraded_served"] == 0
        finally:
            fe.close()
        kv.executor.stop()

    def test_dead_store_degrades_to_stale_replica(self, mesh8):
        from parameter_server_tpu.serving import PullRequest

        kv = self._store(mesh8, "deg_stale")
        fe = self._fe(kv, replica="fallback", degraded_max_staleness_s=60.0)
        try:
            keys = np.array([2, 3], np.int64)
            fresh = fe.submit(PullRequest(keys=keys)).result(30)
            with faults.scoped("serve.pull", kind="raise"):
                stale = fe.submit(PullRequest(keys=keys)).result(30)
            np.testing.assert_array_equal(stale, fresh)
            assert fe.stats()["degraded_served"] == 1
        finally:
            fe.close()
        kv.executor.stop()

    def test_staleness_bound_turns_degraded_into_503(self, mesh8):
        from parameter_server_tpu.serving import DegradedError, PullRequest

        kv = self._store(mesh8, "deg_bound")
        fe = self._fe(kv, replica="fallback", degraded_max_staleness_s=0.0)
        try:
            time.sleep(0.02)  # replica age > 0 bound
            with faults.scoped("serve.pull", kind="raise"):
                with pytest.raises(DegradedError) as ei:
                    fe.submit(
                        PullRequest(keys=np.array([1], np.int64))
                    ).result(30)
            assert ei.value.reason == "stale"
        finally:
            fe.close()
        kv.executor.stop()

    def test_no_replica_is_503_not_429(self, mesh8):
        from parameter_server_tpu.serving import DegradedError, PullRequest

        kv = self._store(mesh8, "deg_noreplica")
        fe = self._fe(kv, replica="off")
        try:
            with faults.scoped("serve.pull", kind="raise"):
                with pytest.raises(DegradedError) as ei:
                    fe.submit(
                        PullRequest(keys=np.array([1], np.int64))
                    ).result(30)
            assert ei.value.reason == "no-replica"
        finally:
            fe.close()
        kv.executor.stop()

    def test_hot_replica_miss_with_dead_store_is_replica_miss(self, mesh8):
        from parameter_server_tpu.serving import DegradedError, PullRequest

        kv = self._store(mesh8, "deg_hotmiss")
        fe = self._fe(
            kv, replica="hot", hot_keys=np.arange(8, dtype=np.int64)
        )
        try:
            with faults.scoped("serve.pull", kind="raise"):
                # fully-hot requests still serve (replica-first path)
                out = fe.submit(
                    PullRequest(keys=np.array([1, 2], np.int64))
                ).result(30)
                assert out.shape == (2, 1)
                # a request with cold keys cannot be covered
                with pytest.raises(DegradedError) as ei:
                    fe.submit(
                        PullRequest(keys=np.array([1, 40], np.int64))
                    ).result(30)
            assert ei.value.reason == "replica-miss"
        finally:
            fe.close()
        kv.executor.stop()

    def test_shed_is_still_a_429_never_degraded(self, mesh8):
        """Overload and failure stay separately observable: a queue shed
        raises RejectedError even while the store path is dead."""
        from parameter_server_tpu.serving import (
            PullRequest,
            RejectedError,
            ServeConfig,
            ServeFrontend,
        )

        kv = self._store(mesh8, "deg_shed")
        fe = ServeFrontend(
            kv,
            ServeConfig(replica="fallback", workers=1, max_queue_depth=1,
                        coalesce_window_s=0.05),
        ).start()
        try:
            with faults.scoped("serve.pull", kind="stall", delay_s=0.2):
                first = fe.submit(PullRequest(keys=np.array([1], np.int64)))
                with pytest.raises(RejectedError) as ei:
                    for _ in range(8):  # the 1-deep lane must shed
                        fe.submit(PullRequest(keys=np.array([2], np.int64)))
                assert ei.value.reason == "queue"
                first.result(30)
        finally:
            fe.close()
        kv.executor.stop()

    def test_refresher_survives_refresh_faults(self, mesh8):
        """A dead shard's replica refresh fails; the background
        refresher keeps the last good snapshot and retries — it must
        not die and must recover once the store returns."""
        from parameter_server_tpu.serving import PullRequest

        kv = self._store(mesh8, "deg_refresh")
        fe = self._fe(kv, replica="fallback", replica_refresh_s=0.03)
        try:
            v0 = fe.replica.version
            faults.arm("serve.refresh", kind="raise")
            time.sleep(0.12)  # several failing refresh ticks
            faults.disarm("serve.refresh")
            deadline = time.time() + 10
            while fe.replica.version <= v0 and time.time() < deadline:
                time.sleep(0.01)
            assert fe.replica.version > v0  # refresher came back
            out = fe.submit(
                PullRequest(keys=np.array([7], np.int64))
            ).result(30)
            assert out.shape == (1, 1)
        finally:
            fe.close()
        kv.executor.stop()

    def test_ticket_deadline_is_diagnosable(self, mesh8):
        from parameter_server_tpu.serving import PullRequest

        kv = self._store(mesh8, "deg_ticket")
        fe = self._fe(kv, replica="fallback")
        try:
            with faults.scoped("serve.pull", kind="stall", delay_s=0.3):
                tk = fe.submit(PullRequest(keys=np.array([1], np.int64)))
                with pytest.raises(DeadlineExceeded):
                    tk.result(0.05)
                tk.result(30)  # the request itself still completes
        finally:
            fe.close()
        kv.executor.stop()


# ---------------------------------------------------------------------------
# the drill itself


def _recovery_drill() -> dict:
    """Kill-one-shard recovery drill under concurrent train + serve load
    (doc/ROBUSTNESS.md "The drill").

    The script, all under live load (a paced training push stream and a
    closed-loop serving client against the SAME store):

    1. **healthy**: periodic consistent replica backups
       (``ReplicaManager.start_periodic`` -> snapshot steps THROUGH the
       store executor, so donated pushes can't tear them) while the
       trainer acks pushes and serving reads live.
    2. **kill**: the backup stream stops, then ``S0`` dies the way real
       shards die: its heartbeats stop arriving (injected
       ``heartbeat.report`` silence), its table is wiped (the
       replacement starts empty), and the serving store path starts
       failing (``serve.pull`` / ``serve.refresh`` faults). Serving
       DEGRADES to the stale read replica (503-distinct accounting)
       instead of erroring; training keeps acking into the void:
       exactly the updates the replay contract must not lose.
    3. **detect + recover**: the RecoveryCoordinator's poll declares
       S0 dead after the heartbeat timeout; the server-death handler
       parks the trainer (bounded-delay semantics: survivors stop
       pushing while the shard recovers), installs the last consistent
       snapshot through the executor, REPLAYS every acked push past the
       snapshot's barrier timestamp in original order, then re-arms the
       store path and resumes.
    4. **verify**: after the stream completes, the drilled table must
       be BIT-identical to an undisturbed run of the same batch
       sequence: zero lost *acknowledged* updates, to the bit.
    """
    import jax
    import jax.numpy as jnp

    from parameter_server_tpu.parallel import mesh as meshlib
    from parameter_server_tpu.parameter.kv_vector import KVVector
    from parameter_server_tpu.parameter.replica import ReplicaManager
    from parameter_server_tpu.serving import (
        PullRequest,
        RejectedError,
        ServeConfig,
        ServeFrontend,
    )
    from parameter_server_tpu.system.heartbeat import (
        HeartbeatCollector,
        HeartbeatReport,
    )
    from parameter_server_tpu.system.postoffice import Postoffice
    from parameter_server_tpu.system.recovery import RecoveryCoordinator

    Postoffice.reset()
    mesh = Postoffice.instance().start().mesh
    seed = 7
    k = 4
    num_slots = 1 << 10
    key_space = 1 << 16
    n_per_batch = 64
    # the stream must OUTLIVE detection in every mode: the drill's
    # whole point is recovery under live load, so the trainer has to
    # still be pushing when the handler parks it. Post-kill batches x
    # (>=4ms pacing) must exceed hb_timeout + poll + margin: with
    # 100 post-kill batches at >=4ms the park is guaranteed (the
    # test's trainer_parked assertion pins it).
    n_batches = 120
    kill_at = n_batches // 6
    hb_timeout = 0.3

    def batch(i: int):
        # regenerable by index, which is what lets the recovery handler
        # REPLAY acked-but-unbacked updates instead of journaling arrays
        rng = np.random.default_rng((seed << 20) + i)
        keys = rng.integers(0, key_space, n_per_batch).astype(np.int64)
        vals = rng.normal(size=(n_per_batch, k)).astype(np.float32)
        return keys, vals

    def push_and_ack(kv, i: int) -> int:
        keys, vals = batch(i)
        ts = kv.push(kv.request(channel=0), keys=keys, values=vals)
        kv.executor.wait(ts, timeout=60)
        return ts

    # -- the undisturbed reference trajectory (also warms every jit:
    # push scatter-add, gather, snapshot copy — so compile stalls can't
    # eat the drill's heartbeat margin) --
    kv_ref = KVVector(
        mesh=mesh, k=k, num_slots=num_slots, hashed=True, name="drill_ref"
    )
    for i in range(n_batches):
        push_and_ack(kv_ref, i)
    t_ref = np.array(kv_ref.table(0, copy=True))
    kv_ref.executor.stop()

    # -- the drilled store + chaos-plane wiring --
    faults.reset()
    # flight recorder (telemetry/blackbox.py): armed for the whole
    # drill so the shard death auto-captures a diagnostic bundle with
    # the pre-death evidence still in the rings. Per-node recorders for
    # the drill's logical nodes; min capture interval dropped so the
    # death trigger is never rate-limit-suppressed by an earlier
    # capture. Cleanup is TARGETED, not a global reset: the drill
    # restores exactly the state it touched (its recorders, the
    # interval, its tee), so bundles another test of this process
    # captured survive the drill.
    from parameter_server_tpu.telemetry import alerts as alerts_mod
    from parameter_server_tpu.telemetry import blackbox
    from parameter_server_tpu.telemetry import (
        registry as telemetry_registry,
    )

    prev_min_interval = blackbox.set_min_interval(0.0)
    was_armed = blackbox.installed_recorder() is not None
    blackbox.arm()
    blackbox.recorder("W0").clear()  # a prior drill in this process
    blackbox.recorder("S0").clear()  # must not leak into this bundle
    node_alerts = None
    if telemetry_registry.enabled():
        node_alerts = alerts_mod.AlertManager(
            [r for r in alerts_mod.default_rules()
             if r.name == "node_deaths"]
        )
        node_alerts.evaluate()  # baseline sample: rate needs a window
    # independently-metered update accounting (the learning truth
    # plane's progress side): baseline the parameter plane's push-key
    # counter for the drilled store BEFORE it exists, so the post-drill
    # delta is exactly this drill's pushed keys
    push_tel = None
    push_keys0 = 0.0
    if telemetry_registry.enabled():
        from parameter_server_tpu.telemetry.instruments import (
            parameter_instruments,
        )

        push_tel = parameter_instruments(
            telemetry_registry.default_registry()
        )["push_keys"]
        push_keys0 = push_tel.value(store="drill_live", channel=0)
    kv = KVVector(
        mesh=mesh, k=k, num_slots=num_slots, hashed=True, name="drill_live"
    )
    rm = ReplicaManager()
    rm.backup_consistent(kv)  # a snapshot exists before any fault can
    rm.start_periodic(kv, interval_s=0.04)

    collector = HeartbeatCollector(timeout=hb_timeout)
    rc = RecoveryCoordinator(collector, handler_retry=None)  # replay is
    # not idempotent: a partial replay retried would double-apply, so
    # the drill's handler runs exactly once and fails loudly instead

    fe = ServeFrontend(
        kv,
        ServeConfig(
            replica="fallback",  # live-first reads; replica = degraded path
            replica_refresh_s=0.15,
            live_pull_deadline_s=2.0,
            degraded_max_staleness_s=60.0,
            workers=2,
            max_queue_depth=256,
        ),
    ).start()
    rng = np.random.default_rng(seed + 1)
    u = rng.random((128, 16))
    pool = (u * u * u * key_space).astype(np.int64)  # hot-headed draws
    fe.submit(PullRequest(keys=pool[0])).result(30)  # warm the pull lane

    counts = {"ok": 0, "shed": 0, "failed": 0}  # serve-thread-only writes
    stop_serve = threading.Event()

    def serve_loop() -> None:
        i = 0
        while not stop_serve.is_set():
            try:
                fe.submit(PullRequest(keys=pool[i % len(pool)])).result(10)
                counts["ok"] += 1
            except RejectedError:
                counts["shed"] += 1
            except Exception:  # DegradedError and organic failures both
                counts["failed"] += 1  # count here; degraded SUCCESSES
                # are counted by the frontend (degraded_served)
            i += 1
            time.sleep(0.002)

    acked: list = []  # (push ts, batch index); guarded-by: ack_lock
    ack_lock = threading.Lock()
    pause_req = threading.Event()
    parked = threading.Event()
    train_err: list = []

    def trainer() -> None:
        try:
            for i in range(n_batches):
                if pause_req.is_set():
                    parked.set()
                    while pause_req.is_set():
                        time.sleep(0.002)
                    parked.clear()
                ts = push_and_ack(kv, i)
                with ack_lock:
                    acked.append((ts, i))
                time.sleep(0.004)  # paced: a continuous live stream,
                # not a burst that outruns the detection window
        except BaseException as e:  # surfaced after join
            train_err.append(e)

    stop_beat = threading.Event()

    def beater() -> None:
        beats = 0
        while not stop_beat.wait(0.04):
            collector.report("S0", HeartbeatReport(hostname="S0"))
            collector.report("W0", HeartbeatReport(hostname="W0"))
            beats += 1
            if beats % 3 == 0:
                # periodic metrics-delta samples into the survivors'
                # flight-recorder rings (the report-timer cadence —
                # what a bundle's per-node metrics history is made of)
                for nid in ("W0", "S0"):
                    rec = blackbox.recorder(nid, create=False)
                    if rec is not None:
                        rec.sample_metrics()

    killed = threading.Event()
    recovered = threading.Event()
    replayed = [0]
    barrier_used = [-1]
    trainer_parked = [False]

    trainer_t = threading.Thread(target=trainer, name="drill-trainer")

    def on_server_dead(nid: str) -> None:
        if not killed.is_set():
            # a loaded host can stall the beater past the heartbeat
            # timeout BEFORE the drill killed anything — that is a
            # false positive, and consuming the exactly-once handler
            # on it would mask the real kill. Revive and keep watching.
            rc.revive(nid)
            return
        # bounded-delay semantics: survivors stop pushing while the
        # shard recovers (park the trainer between batches)
        pause_req.set()
        while not parked.is_set() and trainer_t.is_alive():
            time.sleep(0.002)
        # the under-live-load property CI pins: the trainer was ALIVE
        # and parked (not already finished) when recovery began
        trainer_parked[0] = parked.is_set()
        rec_ok = rm.recover(kv, through_executor=True)
        assert rec_ok, "no replica snapshot to recover from"
        barrier = rm.barrier(kv.name).get(0, -1)
        barrier_used[0] = barrier
        with ack_lock:
            replay = [(ts, i) for ts, i in acked if ts > barrier]
        for _, i in replay:  # original order — FP addition must re-run
            push_and_ack(kv, i)  # in the exact sequence it first ran
        replayed[0] = len(replay)
        # the replacement shard is up: store path + heartbeats return
        faults.disarm("serve.pull")
        faults.disarm("serve.refresh")
        faults.disarm("heartbeat.report")
        recovered.set()
        pause_req.clear()

    rc.on_server_dead(on_server_dead)
    collector.report("S0", HeartbeatReport(hostname="S0"))
    collector.report("W0", HeartbeatReport(hostname="W0"))

    serve_t = threading.Thread(target=serve_loop, name="drill-serve")
    beat_t = threading.Thread(target=beater, name="drill-beater")
    degraded_probes = 0
    try:
        beat_t.start()
        rc.start(interval=0.03)
        trainer_t.start()
        serve_t.start()

        # phase 1 (healthy): run until the kill point has been ACKED
        while True:
            with ack_lock:
                n_acked = len(acked)
            if n_acked >= kill_at or train_err:
                break
            time.sleep(0.005)
        if train_err:
            raise train_err[0]

        # phase 2 (kill): the dead shard's backup stream stops FIRST —
        # a crashed node cannot keep snapshotting — then make sure at
        # least one acked update postdates the final barrier (the
        # replay set must be provably non-empty)
        rm.stop_periodic()
        barrier_before = rm.barrier(kv.name).get(0, -1)
        replay_deadline = time.perf_counter() + 30
        while True:
            with ack_lock:
                if any(ts > barrier_before for ts, _ in acked):
                    break
            assert trainer_t.is_alive() and (
                time.perf_counter() < replay_deadline
            ), "no acked update ever postdated the final backup barrier"
            time.sleep(0.002)
        faults.arm("heartbeat.report", kind="silence", match="S0")
        faults.arm("serve.pull", kind="raise")
        faults.arm("serve.refresh", kind="raise")
        killed.set()
        # wipe the shard through the executor (the replacement starts
        # empty; the submitted step serializes with in-flight pushes)
        zeros = jax.device_put(
            jnp.zeros((kv.num_slots, kv.k), kv.dtype),
            meshlib.table_sharding(kv.mesh),
        )
        kv.executor.wait(
            kv.submit(lambda: kv.set_table(0, zeros), kv.request(channel=0)),
            timeout=60,
        )
        # deterministic degraded evidence: requests in the dead window
        # must be ANSWERED (stale) — the 503-vs-429 story, measured
        for j in range(3):
            try:
                fe.submit(PullRequest(keys=pool[j])).result(10)
                degraded_probes += 1
            except Exception:
                pass

        # phase 3: detection + recovery run on the coordinator thread;
        # phase 4: the trainer finishes the stream
        deadline = time.perf_counter() + 90
        while not recovered.is_set() and time.perf_counter() < deadline:
            if node_alerts is not None:
                node_alerts.evaluate()
            time.sleep(0.005)
        assert recovered.is_set(), "recovery never completed"
        # the node_deaths rule sees the coordinator's deaths counter
        # tick and walks pending->firing (for_s=0: one evaluation)
        if node_alerts is not None:
            alert_deadline = time.perf_counter() + 10
            while (
                "node_deaths" not in node_alerts.firing()
                and time.perf_counter() < alert_deadline
            ):
                node_alerts.evaluate()
                time.sleep(0.01)
        trainer_t.join(timeout=120)
        assert not trainer_t.is_alive(), "trainer wedged"
        if train_err:
            raise train_err[0]
    finally:
        try:
            faults.reset()
            rm.stop_periodic()
            stop_serve.set()
            stop_beat.set()
            rc.stop()
            for t in (serve_t, beat_t, trainer_t):
                if t.ident is not None:
                    t.join(timeout=60)
            fe.close()
        finally:
            # grab the death's bundle BY TRIGGER KIND — last_bundle()
            # could be a later capture (a straggling DegradedError from
            # the dead window fires the degraded trigger with the
            # interval still 0) whose rings carry no staleness override
            # for S0
            death_bundle = next(
                (b for b in reversed(blackbox.bundles())
                 if b["trigger"]["kind"] == "node_death"),
                None,
            )
            # targeted cleanup (never a global reset — see the arm
            # comment): the rate-limit override, the drill's per-node
            # recorders, and the drill's tee (only if the drill armed
            # it) must not leak past the drill even when it raises —
            # its OWN nested finally, so a failing teardown step above
            # (a wedged join, a close error) cannot skip it
            blackbox.set_min_interval(prev_min_interval)
            blackbox.drop_recorder("W0")
            blackbox.drop_recorder("S0")
            if not was_armed:
                blackbox.disarm()

    kv.executor.wait_all(pop=False, timeout=60)
    t_drill = np.array(kv.table(0, copy=True))
    fe_stats = fe.stats()
    kv.executor.stop()
    # the shard death's auto-captured diagnostic bundle (the
    # RecoveryCoordinator's node_death trigger), summarized
    blackbox_section: dict = {"captured": death_bundle is not None}
    if death_bundle is not None:
        blackbox_section = blackbox.summarize_bundle(death_bundle)
    if node_alerts is not None:
        st = node_alerts.states().get("node_deaths")
        blackbox_section["node_deaths_alert"] = (
            st.state_name if st is not None else "absent"
        )
    bit_identical = (
        t_ref.dtype == t_drill.dtype
        and t_ref.shape == t_drill.shape
        and t_ref.tobytes() == t_drill.tobytes()
    )
    # the bit-identity claim, independently METERED (PR 15): every key
    # the trainer acked plus every key the handler replayed must show
    # in the parameter plane's own push-key counter for this store —
    # a replay that silently lost (or double-ran) updates would still
    # reconcile bit-identically on idempotent data, but it cannot fool
    # a counter the push path ticks per request
    update_accounting = None
    if push_tel is not None:
        pushed = int(
            push_tel.value(store="drill_live", channel=0) - push_keys0
        )
        expected = (n_batches + replayed[0]) * n_per_batch
        update_accounting = {
            "pushed_keys_metered": pushed,
            "expected_keys": expected,
            "acked_updates": n_batches,
            "replayed_updates": replayed[0],
            "keys_per_batch": n_per_batch,
            "metered_matches": pushed == expected,
        }
        assert update_accounting["metered_matches"], update_accounting

    return {
        "replayed_updates": replayed[0],
        "acked_updates": n_batches,
        "barrier_ts": barrier_used[0],
        "backup_version_used": (rm.meta(kv.name) or {}).get("version"),
        "trainer_parked": trainer_parked[0],
        "trajectory_bit_identical": bool(bit_identical),
        "update_accounting": update_accounting,
        "blackbox": blackbox_section,
        "serve": {
            "requests": counts["ok"] + counts["shed"] + counts["failed"],
            "completed_ok": counts["ok"],
            "degraded_served": fe_stats["degraded_served"],
            "degraded_probes_in_dead_window": degraded_probes,
            "shed": counts["shed"],
            "failed": counts["failed"],
        },
    }


def test_recovery_drill_smoke():
    """Tier-1 acceptance: injected shard death under live train+serve
    load is detected and recovered with ZERO lost acknowledged updates
    — post-drill trajectory bit-identical to the undisturbed run."""
    from parameter_server_tpu.system.postoffice import Postoffice

    try:
        out = _recovery_drill()
    finally:
        Postoffice.reset()
    assert out["trajectory_bit_identical"] is True
    assert out["trainer_parked"] is True  # recovery ran AGAINST live
    # load (the trainer was parked mid-stream, not already finished)
    assert out["replayed_updates"] >= 1
    assert out["serve"]["degraded_served"] >= 1
    assert out["serve"]["requests"] > 0
    assert out["backup_version_used"] >= 1
    # the shard death's auto-captured bundle: the dead shard stale, the
    # survivor's ring dumped, the shipped node_deaths rule firing
    bb = out["blackbox"]
    assert bb.get("captured")
    assert bb["nodes"].get("S0", {}).get("stale")
    assert not bb["nodes"].get("W0", {}).get("stale", True)
    assert bb.get("node_deaths_alert", "firing") == "firing"

"""Executor: logical clocks and dependency tracking over XLA async dispatch.

Counterpart of ``src/system/executor.{h,cc}`` + ``task_tracker.h``. The
reference runs a per-customer DAG engine thread that picks any received
message whose ``wait_time`` dependencies are finished (executor.cc
PickActiveMsg) — messages behind an unmet dependency do NOT block ready
ones submitted later. This executor reproduces that: ``submit`` enqueues
and returns immediately; a dispatch thread repeatedly runs the
lowest-timestamp *ready* step (all deps finished), skipping over blocked
ones. When nothing is ready it resolves the oldest blocked step's
dependencies by materializing their device futures (XLA async dispatch
means a "run" step may still be computing on device; a dependency counts
as finished only once its results are ready — the reference's handler-ran
== message-finished contract).

``Wait(ts)`` blocks until step ``ts`` has run and its arrays materialized
— ``Customer::Wait`` semantics. Bounded-delay consistency: ``submit``
itself blocks when more than ``max_in_flight`` steps are unfinished (the
reference throttles identically through its message clocks).
"""

from __future__ import annotations

import heapq
import threading
import time
import weakref
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax

from ..telemetry import registry as telemetry_registry
from ..telemetry import spans as telemetry_spans
from ..utils.retry import Deadline, DeadlineExceeded
from . import faults
from .message import INVALID_TIME, Message, Task


class _ExecutorTelemetry:
    """Per-executor bridge into the process registry (telemetry spine).

    The dispatch loop must stay hardware-speed, so the per-step path is
    ONE buffer append under one small lock; the buffered phase records
    flush into the registry instruments lazily — on the registry's
    collector hook (every ``snapshot()``/``render_text()`` read) or when
    the buffer fills. Instrument children are bound once here so the
    flush path does no name/label lookups either.
    """

    __slots__ = (
        "queue_wait", "run", "materialize", "total",
        "steps", "in_flight", "pending", "name",
        "_buf", "_buf_lock", "__weakref__",
    )

    _FLUSH_AT = 4096  # bound buffered memory between registry reads

    def __init__(self, name: str):
        from ..telemetry.instruments import executor_instruments

        reg = telemetry_registry.default_registry()
        insts = executor_instruments(reg)
        self.name = name
        self.queue_wait = insts["queue_wait"].labels(executor=name)
        self.run = insts["run"].labels(executor=name)
        self.materialize = insts["materialize"].labels(executor=name)
        self.total = insts["total"].labels(executor=name)
        self.steps = insts["steps"].labels(executor=name)
        self.in_flight = insts["in_flight"].labels(executor=name)
        self.pending = insts["pending"].labels(executor=name)
        self._buf: list = []  # guarded-by: _buf_lock
        self._buf_lock = threading.Lock()
        reg.add_collector(self.flush)

    def record(
        self,
        queue_wait: float,
        run_s: float,
        mat_s: float,
        total: float,
        in_flight: int,
        pending: int,
    ) -> None:
        """Hot path: one lock, one append (~1µs); flush is amortized."""
        with self._buf_lock:
            self._buf.append(
                (queue_wait, run_s, mat_s, total, in_flight, pending)
            )
            if len(self._buf) < self._FLUSH_AT:
                return
            buf, self._buf = self._buf, []
        self._flush_records(buf)

    def flush(self) -> None:
        """Drain buffered step records into the registry (collector hook)."""
        with self._buf_lock:
            buf, self._buf = self._buf, []
        if buf:
            self._flush_records(buf)

    def _flush_records(self, buf: list) -> None:
        for qw, run_s, mat_s, total, _, _ in buf:
            self.queue_wait.observe(qw)
            self.run.observe(run_s)
            self.materialize.observe(mat_s)
            self.total.observe(total)
        self.steps.inc(len(buf))
        # gauges are point-in-time: the newest record wins
        self.in_flight.set(buf[-1][4])
        self.pending.set(buf[-1][5])


class TaskTracker:
    """Finished/started timestamp bookkeeping (ref task_tracker.h)."""

    def __init__(self) -> None:
        self._finished: set[int] = set()  # guarded-by: _lock
        self._started: set[int] = set()  # guarded-by: _lock
        # in-flight is tracked incrementally: the set difference the
        # old in_flight() computed is O(all steps ever), and it ran
        # once per dispatched step — quadratic across a training run
        self._inflight = 0  # guarded-by: _lock
        self._lock = threading.Lock()

    def start(self, ts: int) -> None:
        with self._lock:
            if ts not in self._started and ts not in self._finished:
                self._inflight += 1
            self._started.add(ts)

    def finish(self, ts: int) -> None:
        with self._lock:
            if ts in self._started and ts not in self._finished:
                self._inflight -= 1
            self._finished.add(ts)

    def is_finished(self, ts: int) -> bool:
        with self._lock:
            return ts in self._finished

    def was_started(self, ts: int) -> bool:
        with self._lock:
            return ts in self._started

    def in_flight(self) -> int:
        """Started (dispatched) but not yet finished. O(1)."""
        with self._lock:
            return self._inflight


#: every live executor, weakly held — the diagnostic-bundle capture
#: (telemetry/blackbox.py) walks this to snapshot pending/in-flight
#: state at the moment of an incident; dead executors fall out with GC.
#: WeakSet is not thread-safe: registration (any thread constructing
#: an Executor) and the capture-thread copy both go through
#: _live_lock, or an incident capture racing a construction would die
#: with set-changed-size-during-iteration — replacing the executors
#: section with an error string at exactly the moment it matters.
_live_executors: "weakref.WeakSet" = weakref.WeakSet()
_live_lock = threading.Lock()


def live_executors() -> List["Executor"]:
    """The process's live executors (for diagnostics; order arbitrary)."""
    with _live_lock:
        return list(_live_executors)


class Executor:
    def __init__(
        self,
        name: str = "",
        max_in_flight: int = 0,
        telemetry: Optional[bool] = None,
    ):
        self.name = name
        self._time = 0  # guarded-by: _cv — the logical clock
        # telemetry spine (doc/OBSERVABILITY.md): per-step phase
        # histograms + depth gauges, and one JSONL span event per
        # finished step correlating host time to the logical clock.
        # ``telemetry=None`` follows the process-wide switch; the
        # decision is cached here so the hot path tests one attribute.
        if telemetry is None:
            telemetry = telemetry_registry.enabled()
        self._tel: Optional[_ExecutorTelemetry] = (
            _ExecutorTelemetry(name) if telemetry else None
        )
        # ts -> [t_submit, t_dispatch, run_s, materialize_s] (perf_counter)
        # Deliberately NOT guarded-by _cv: the dispatch thread mutates a
        # record's cells while waiter threads accumulate materialize
        # time into others; cross-thread hand-off rides dict.pop's
        # atomicity ("popped exactly once", _record_finished) so the
        # per-step hot path never takes the cv twice.
        self._step_times: Dict[int, List[float]] = {}
        self._pending: Dict[int, Tuple[Callable[[], Any], List[int]]] = {}  # guarded-by: _cv
        # dependency-counted readiness (round 5): the original picker
        # re-sorted and re-scanned every pending step per dispatch —
        # O(n² log n) across an n-step burst.
        # Now: unmet-dep counts + a dep→dependents map maintained at
        # submit/finish, and a min-heap of ready timestamps — each
        # step is pushed and popped once.
        self._unmet: Dict[int, int] = {}  # guarded-by: _cv — pending ts -> unmet dep count
        self._dependents: Dict[int, List[int]] = {}  # guarded-by: _cv — dep ts -> waiters
        self._ready: List[int] = []  # guarded-by: _cv — heap of dispatchable timestamps
        # ts -> (flow id, origin node) captured on the SUBMITTING
        # thread; the dispatch loop re-activates it around the step
        # body so spans emitted inside (a ps.py RPC's van.transfer, a
        # wire encode) stay on the batch/request's flow — without this
        # the flow dies at submit and the cross-node timeline cannot
        # stitch the step's downstream work. Only populated while a
        # flow is actually active (tracing on).
        self._flows: Dict[int, Tuple[int, Optional[str]]] = {}  # guarded-by: _cv
        self._running: Optional[int] = None  # guarded-by: _cv — picked, step() executing now
        self._ran: set[int] = set()  # guarded-by: _cv — ran, not finished yet (pruned on finish)
        self._futures: Dict[int, Any] = {}  # guarded-by: _cv — ts -> pytree (run, maybe async)
        self._callbacks: Dict[int, Callable[[], None]] = {}  # guarded-by: _cv
        self._errors: Dict[int, BaseException] = {}  # guarded-by: _cv
        self.tracker = TaskTracker()
        self._cv = threading.Condition()
        self._thread: Optional[threading.Thread] = None  # guarded-by: _cv
        self._stopped = False  # guarded-by: _cv
        self.max_in_flight = max_in_flight  # 0 = unbounded (eventual consistency)
        # telemetry: max |started \ finished| ever observed at dispatch time
        # (τ-bounded-delay proof for the darlin scheduler)
        self.max_dispatched_in_flight = 0
        with _live_lock:
            _live_executors.add(self)

    def time(self) -> int:
        with self._cv:
            return self._time

    def debug_state(self, max_pending: int = 16) -> Dict[str, Any]:
        """Point-in-time diagnostic snapshot for incident bundles
        (telemetry/blackbox.py): logical clock, backlog depth and its
        oldest timestamps, the step executing right now, in-flight
        count. One lock acquire; safe from any thread."""
        with self._cv:
            pending = sorted(self._pending)
            return {
                "name": self.name,
                "logical_time": self._time,
                "pending": len(pending),
                "pending_ts": pending[:max_pending],
                "running": self._running,
                "in_flight": self.tracker.in_flight(),
            }

    def pending_count(self) -> int:
        """Submitted steps not yet picked by the dispatch thread — an
        O(1) backlog read an admission controller can gate on per
        request (serving/admission.py ``depth_fn``; the composed
        frontend gates on its own in-flight count instead, but a bare
        store serving direct pulls has only this signal)."""
        with self._cv:
            return len(self._pending)

    # -- submission (ref Customer::Submit) --

    def submit(
        self,
        step: Callable[[], Any],
        task: Optional[Task] = None,
        callback: Optional[Callable[[], None]] = None,
    ) -> int:
        """Enqueue ``step``; returns its timestamp immediately.

        ``task.wait_time`` lists timestamps that must be *finished* before
        this step runs (ref executor.cc PickActiveMsg dependency check).
        Dependencies must reference already-submitted steps — the reference
        allocates timestamps at Submit, so a dep can never be in the future.
        A dep naming a timestamp that was NEVER submitted counts as
        satisfied, evaluated once at submit time: backfilling that
        timestamp later (an explicit ``Task(time=...)``) does not
        retroactively block this step.
        The step runs on the executor's dispatch thread, possibly after
        later-submitted steps whose dependencies cleared earlier.
        """
        task = task or Task()
        with self._cv:
            if task.time != INVALID_TIME:
                ts = task.time
                if ts < self._time and self.tracker.was_started(ts) or (
                    ts in self._pending
                ):
                    raise ValueError(f"timestamp {ts} already used")
                # keep the auto counter ahead of explicit timestamps so they
                # can never collide with a later auto-assigned one
                self._time = max(self._time, ts + 1)
            else:
                ts = self._time
                self._time += 1
            deps = []
            for dep in task.wait_time:
                if dep == INVALID_TIME:
                    continue
                if dep >= ts:
                    raise ValueError(f"dependency {dep} is not before step {ts}")
                deps.append(dep)
            self._pending[ts] = (step, deps)
            flow = telemetry_spans.current_flow()
            if flow is not None:
                self._flows[ts] = (flow, telemetry_spans.current_flow_node())
            if self._tel is not None:
                # [t_submit, t_dispatch (0 = not picked yet),
                #  run_s (-1 = run not completed yet), materialize_s,
                #  flow id active on the SUBMITTING thread (timeline
                #  flow correlation: the batch/request this step
                #  serves) or None]
                self._step_times[ts] = [
                    time.perf_counter(), 0.0, -1.0, 0.0, flow,
                ]
            # readiness accounting: a dep not yet done registers this
            # step as its dependent; _finish(dep) decrements the count
            # and promotes the step to the ready heap at zero. A dep
            # that is done (or was never submitted) never transitions
            # again, so checking it exactly once here is sound.
            unmet = [d for d in deps if not self._dep_done_locked(d)]
            if unmet:
                self._unmet[ts] = len(unmet)
                for d in unmet:
                    self._dependents.setdefault(d, []).append(ts)
            else:
                heapq.heappush(self._ready, ts)
            if callback is not None:
                self._callbacks[ts] = callback
            self._ensure_thread()
            self._cv.notify_all()
        if self.max_in_flight > 0:
            self._throttle(ts)
        return ts

    def _throttle(self, ts: int) -> None:
        """Bounded-delay window: block until step ts - max_in_flight is done.

        Completion only (pop=False): the step's result stays claimable by a
        later wait()/pop_result() — throttling must not consume metrics the
        caller still wants to collect.
        """
        horizon = ts - self.max_in_flight
        if horizon >= 0:
            self.wait(horizon, pop=False)

    # -- the dispatch thread (ref executor.cc thread + PickActiveMsg) --

    def _ensure_thread(self) -> None:  # holds-lock: _cv (submit calls this)
        if self._thread is None or not self._thread.is_alive():
            self._stopped = False
            self._thread = threading.Thread(
                target=self._dispatch_loop, name=f"executor:{self.name}", daemon=True
            )
            self._thread.start()

    def _dispatch_loop(self) -> None:
        while True:
            dep_fut = None
            with self._cv:
                while not self._pending and not self._stopped:
                    self._cv.wait()
                if self._stopped:
                    return
                pick = self._pick_ready_locked()
                if pick is None:
                    # Nothing ready: resolve the oldest blocked step's first
                    # unmet dep. Every unmet dep is an older timestamp, so
                    # by induction it has already run (or is being waited on
                    # by another thread) — never pending.
                    oldest = min(self._pending)
                    dep = next(
                        (
                            d
                            for d in self._pending[oldest][1]
                            if not self._dep_done_locked(d)
                        ),
                        None,
                    )
                    if dep is None:
                        # every dep of the oldest blocked step is in
                        # fact done, yet the step is not in the ready
                        # heap: either a concurrent wait() finished the
                        # dep between the ready-pick and here, or the
                        # dep was finished through an EXTERNAL
                        # tracker.finish (Customer.reply does this) that
                        # bypasses _finish's promotion. Promote it
                        # directly — without this the loop would spin
                        # forever on a step no _finish will ever push
                        # (duplicate heap entries are skipped lazily).
                        self._unmet.pop(oldest, None)
                        heapq.heappush(self._ready, oldest)
                        continue
                    if dep in self._futures:
                        dep_fut = self._futures[dep]  # materialize below
                    else:
                        # running, or popped by a concurrent wait(): that
                        # path will finish it and notify — do NOT finish an
                        # unmaterialized dep here
                        self._cv.wait()
                        continue
                else:
                    ts, step = pick
                    self._running = ts
                    step_flow = self._flows.pop(ts, None)
            if pick is None:
                if dep_fut is not None:
                    self._materialize_fut(dep, dep_fut)
                self._finish(dep)
                continue
            # run the step outside the lock (it may dispatch device work,
            # or block — submitters and waiters must stay free)
            self.tracker.start(ts)
            self.max_dispatched_in_flight = max(
                self.max_dispatched_in_flight, self.tracker.in_flight()
            )
            tel = self._tel
            if tel is not None:
                t_run0 = time.perf_counter()
                times = self._step_times.get(ts)
                if times is not None:
                    times[1] = t_run0  # dispatch pickup: queue wait ends
            try:
                # fault point (doc/ROBUSTNESS.md): kind="raise" makes
                # this step fail exactly like a raising step body (the
                # error propagates to the waiter); a ``delay_s`` stalls
                # the dispatch thread first (kind="stall" stalls
                # without raising). Inside the try so an injected raise
                # rides the organic error path bit-for-bit.
                faults.inject("executor.step", detail=f"{self.name}:{ts}")
                # the submitter's flow rides into the step body so
                # spans it emits (ps.py RPC transfers, nested submits)
                # keep the unit-of-work correlation across the dispatch
                # thread; flow_scope(None) is a free passthrough
                with telemetry_spans.flow_scope(
                    *(step_flow or (None, None))
                ), telemetry_spans.span(
                    "executor.run", ts=ts, executor=self.name
                ):
                    result = step()
                err = None
            except BaseException as e:  # propagate to the waiter
                result, err = None, e
            if tel is not None and times is not None:
                times[2] = time.perf_counter() - t_run0
            with self._cv:
                self._running = None
                self._ran.add(ts)
                if err is not None:
                    self._errors[ts] = err
                else:
                    self._futures[ts] = result
                self._cv.notify_all()

    def _dep_done_locked(self, d: int) -> bool:  # holds-lock: _cv
        """A dependency is satisfied when finished — or never submitted
        (the reference waits only on timestamps it issued; an unknown ts is
        a no-op there too)."""
        if self.tracker.is_finished(d):
            return True
        return (
            d not in self._pending
            and d != self._running
            and d not in self._ran
            and not self.tracker.was_started(d)
        )

    def _pick_ready_locked(self) -> Optional[Tuple[int, Callable[[], Any]]]:  # holds-lock: _cv
        """Lowest-timestamp READY step (PickActiveMsg: any ready message
        may overtake blocked ones). O(log n) via the ready heap. Lazy
        skips: entries whose step is gone (run or cancelled), and
        entries whose timestamp has an unmet-dep count — a stale heap
        entry must never dispatch a REUSED explicit timestamp past its
        fresh dependencies."""
        while self._ready:
            if self._ready[0] in self._unmet:
                heapq.heappop(self._ready)
                continue
            ts = heapq.heappop(self._ready)
            entry = self._pending.pop(ts, None)
            if entry is not None:
                return ts, entry[0]
        return None

    def _note_materialize(self, ts: int, seconds: float) -> None:
        """Accumulate block_until_ready wall time onto the step's record
        (a step may be forced from several waiters; the phases sum)."""
        if self._tel is None:
            return
        times = self._step_times.get(ts)
        if times is not None:
            times[3] += seconds

    def _materialize_fut(self, ts: int, fut: Any) -> None:
        """block_until_ready tolerant of DONATED futures.

        The zero-copy data plane stores live table handles as step
        results; a LATER step may consume (donate) that buffer in
        place. The dispatch thread is serial, so donation implies the
        producing step already completed — a deleted/donated buffer
        here means 'materialized long ago', not an error. The waiter
        still receives the dead handle; READING it raises jax's
        read-after-donate, which is the documented contract
        (doc/PERFORMANCE.md "Donation rules"). Without this guard a
        fire-and-forget push pipeline crashed (and then wedged — see
        wait()) the moment a snapshot waited on a superseded future.

        Known tradeoff: the message match cannot distinguish a
        legitimately superseded future from an erroneously
        double-donated buffer — the latter is only caught when its
        VALUE is read (which still raises). Narrowing this would need
        the stores to mark superseded timestamps explicitly.
        """
        t0 = time.perf_counter()
        with telemetry_spans.span(
            "executor.materialize", ts=ts, executor=self.name
        ):
            try:
                jax.block_until_ready(fut)
            except RuntimeError as e:
                msg = str(e)
                if "deleted" not in msg and "donated" not in msg:
                    raise
        self._note_materialize(ts, time.perf_counter() - t0)

    def _record_finished(self, ts: int, num_pending: int) -> None:
        """Record the finished step's phases into the registry and emit
        the per-step span event (one line per step, popped exactly once).
        ``num_pending`` is sampled by the caller inside its own _cv
        critical section — this path must not re-take the cv per step."""
        tel = self._tel
        if tel is None:
            return
        times = self._step_times.get(ts)
        if times is None or times[1] == 0.0 or times[2] < 0.0:
            # not dispatched here, or the step body is still executing
            # (an external tracker.finish — Customer.reply — can satisfy
            # a waiter mid-run): leave the record in place so the finish
            # that observes the completed run emits it exactly once
            return
        times = self._step_times.pop(ts, None)
        if times is None:
            return  # a concurrent finish won the pop; it emitted
        now = time.perf_counter()
        t_submit, t_dispatch, run_s, mat_s, flow = times
        queue_wait = max(0.0, t_dispatch - t_submit)
        total = max(0.0, now - t_submit)
        tel.record(
            queue_wait,
            run_s,
            mat_s,
            total,
            self.tracker.in_flight(),
            num_pending,
        )
        if telemetry_spans.get_sink() is not None:
            event = {
                "kind": "span",
                "name": "executor.step",
                "executor": tel.name,
                "ts": ts,
                "t_wall": time.time(),
                "queue_wait_s": queue_wait,
                "run_s": run_s,
                "materialize_s": mat_s,
                "total_s": total,
            }
            if flow is not None:
                event["flow"] = flow
            telemetry_spans.emit(event)

    def _finish(self, ts: int) -> None:
        """Mark finished (results materialized), prune, fire callback
        once, and promote dependents whose last unmet dep this was."""
        if self.tracker.was_started(ts):
            self.tracker.finish(ts)
        with self._cv:
            self._ran.discard(ts)
            self._flows.pop(ts, None)  # externally-finished steps
            for t in self._dependents.pop(ts, ()):
                left = self._unmet.get(t)
                if left is None:
                    continue  # cancelled by stop()
                if left <= 1:
                    del self._unmet[t]
                    if t in self._pending:
                        heapq.heappush(self._ready, t)
                else:
                    self._unmet[t] = left - 1
            cb = self._callbacks.pop(ts, None)
            # sampled here so the telemetry record below needs no
            # second cv acquire on the per-step path
            num_pending = len(self._pending)
            self._cv.notify_all()
        self._record_finished(ts, num_pending)
        if cb is not None:
            cb()

    # -- waiting (ref Customer::Wait) --

    def wait(self, ts: int, pop: bool = True,
             timeout: Optional[float] = None) -> Any:
        """Block until step ``ts`` has run and materialized (Customer::Wait).

        By default evicts the step's future so device buffers are released —
        without this, every intermediate result would stay pinned in HBM.
        ``pop=False`` blocks without consuming (used by the throttle).
        Returns the step's value (None if ts is unknown or already popped).
        Re-raises the step's exception, if it raised.

        ``timeout`` bounds the wait (seconds): on expiry a diagnostic
        :class:`~..utils.retry.DeadlineExceeded` (a TimeoutError) names
        the wedged timestamp, its state, and — the case that used to
        hang callers forever — its unsatisfied ``wait_time``
        dependencies. Completion-only; a timed-out step keeps running
        and a later wait() can still claim its result.
        """
        deadline = Deadline(timeout)
        timed_out: Optional[DeadlineExceeded] = None
        with self._cv:
            known = (
                ts in self._pending
                or ts == self._running
                or ts in self._ran
                or self.tracker.was_started(ts)
                or self.tracker.is_finished(ts)
            )
            if not known:
                return None
            while not (
                ts in self._futures
                or ts in self._errors
                or self.tracker.is_finished(ts)
            ):
                left = deadline.remaining()
                if left is None:
                    self._cv.wait()
                elif left <= 0:
                    timed_out = self._wait_timeout_locked(ts, timeout)
                    break
                else:
                    self._cv.wait(left)
            if timed_out is None:
                err = (
                    self._errors.pop(ts, None) if pop
                    else self._errors.get(ts)
                )
                fut = (
                    self._futures.pop(ts, None) if pop
                    else self._futures.get(ts)
                )
        if timed_out is not None:
            # a wedged wait is a flight-recorder trigger (the evidence
            # — recent spans, executor state — is exactly what rots if
            # diagnosis waits). Raised OUTSIDE the cv: the bundle
            # capture reads executor state through the public API and
            # must not deadlock on our own lock. Best-effort,
            # rate-limited, never masks the diagnostic error.
            from ..telemetry import blackbox

            blackbox.trigger_bundle(
                "executor_wait_timeout", detail=str(timed_out)
            )
            raise timed_out
        if err is not None:
            self._finish(ts)
            raise err
        if fut is not None:
            try:
                self._materialize_fut(ts, fut)
            except BaseException:
                # the step DID run; mark it finished even when forcing
                # its value fails, or every later wait()/wait_all() on
                # this ts would spin forever on a future that is gone
                self._finish(ts)
                raise
        self._finish(ts)
        return fut

    def _wait_timeout_locked(self, ts: int, timeout: float) -> DeadlineExceeded:  # holds-lock: _cv
        """Build the diagnostic deadline error for a wedged wait: which
        state the step is stuck in, and — when it is pending — which
        ``wait_time`` dependencies never finished (a lost dependency is
        the classic way a caller hangs forever)."""
        entry = self._pending.get(ts)
        if entry is not None:
            unmet = [d for d in entry[1] if not self._dep_done_locked(d)]
            state = (
                f"pending with unsatisfied wait_time deps {unmet}"
                if unmet
                else "pending (ready but not yet dispatched)"
            )
        elif ts == self._running:
            state = "executing on the dispatch thread right now"
        elif ts in self._ran:
            state = "ran; result not yet materialized/finished"
        else:
            state = (
                "started externally (tracker), never finished — a "
                "Customer.reply that never arrived?"
            )
        return DeadlineExceeded(
            f"executor {self.name!r}: step {ts} unfinished after "
            f"{timeout}s — {state}",
            op=f"executor:{self.name} wait({ts})", deadline_s=timeout,
        )

    def wait_all(self, pop: bool = True,
                 timeout: Optional[float] = None) -> None:
        """Drain every unfinished step, including the one executing right
        now. ``pop=False`` preserves results for later collection.
        ``timeout`` bounds the WHOLE drain (one budget across steps,
        utils/retry.Deadline); expiry raises the per-step diagnostic
        DeadlineExceeded of whichever step was wedged."""
        deadline = Deadline(timeout)
        while True:
            with self._cv:
                todo = set(self._pending) | self._ran
                if self._running is not None:
                    todo.add(self._running)
            if not todo:
                return
            for ts in sorted(todo):
                left = deadline.remaining()
                self.wait(ts, pop=pop, timeout=left)

    def result(self, ts: int) -> Any:
        """The (possibly still-async) value of step ts (None once waited,
        or if the step has not been dispatched yet)."""
        with self._cv:
            return self._futures.get(ts)

    def pop_result(self, ts: int) -> Any:
        return self.wait(ts)

    def stop(self, cancel_pending: bool = True) -> None:
        """Stop the dispatch thread and join it. ``cancel_pending`` drops
        steps that have not started (the executing one always completes —
        its state mutation cannot be torn). Idempotent."""
        with self._cv:
            if cancel_pending:
                cancelled = set(self._pending)
                for ts in cancelled:
                    self._pending.pop(ts)
                    self._callbacks.pop(ts, None)
                    self._unmet.pop(ts, None)
                    self._step_times.pop(ts, None)  # never dispatched
                    self._flows.pop(ts, None)
                # purge, don't lazy-skip: an explicit timestamp may be
                # REUSED after cancellation, and a stale heap entry
                # (or a stale _dependents registration decrementing
                # the reincarnation's fresh unmet count) would let the
                # new step dispatch before its dependencies
                self._ready = [t for t in self._ready if t not in cancelled]
                heapq.heapify(self._ready)
                for d in list(self._dependents):
                    kept = [
                        t for t in self._dependents[d] if t not in cancelled
                    ]
                    if kept:
                        self._dependents[d] = kept
                    else:
                        del self._dependents[d]
            self._stopped = True
            self._cv.notify_all()
            thread = self._thread
        if thread is not None and thread.is_alive() and (
            thread is not threading.current_thread()
        ):
            thread.join(timeout=60)
        if self._tel is not None:
            # push buffered step records out before this executor (and
            # its collector registration) can be garbage-collected
            self._tel.flush()


class NodeGroups:
    """Symbolic node group ids (ref executor.h kServerGroup et al.).

    On TPU these resolve to mesh axes rather than socket lists; kept for API
    parity so app code reads like the reference.
    """

    SERVER_GROUP = "all_servers"
    WORKER_GROUP = "all_workers"
    COMP_GROUP = "all_comp_nodes"
    REPLICA_GROUP = "all_replicas"
    OWNER_GROUP = "all_owners"
    LIVE_GROUP = "all_lives"

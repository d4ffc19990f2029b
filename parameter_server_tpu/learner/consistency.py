"""Self-driving consistency: adaptive τ + KKT significance accounting.

PR 15 made the bounded-delay contract *measured*; this module makes it
*driven*. The OSDI'14 parameter server exposes flexible consistency as
a first-class dial (Li et al., OSDI'14 §3.4) and the NIPS'14 companion
proves convergence degrades gracefully with delay (Li et al., NIPS'14)
— which together say τ should be earned from gradient geometry, not
hand-picked: wide while the trajectory is stable (async throughput),
clamped the moment divergence leading indicators move. The same papers'
KKT filter says most keys should never ship at all. Both live here, on
the telemetry plane the reference never had:

- :class:`AdaptiveTauController` — moves the worker's *effective* τ
  between submissions (``AsyncSGDWorker.set_effective_tau``; the
  configured ``max_delay`` stays the contract CAP). Policy: widen one
  ministep after every ``stable_steps`` healthy collects; halve on a
  soft grad-norm spike (its own window median, a gentler factor than
  the learning plane's divergence judge — the controller reacts BEFORE
  the alert would); and on a hard divergence signal (non-finite
  loss/gradient, or the plane's spike judgment) run the full reaction:
  τ→0, automatic LR backoff (step cache re-jit — the exceptional
  recompile path, disclosed), and rollback to the controller's last
  healthy in-memory snapshot through the same ``state_host`` /
  ``load_state_host`` surface the PR 9 recovery machinery replays
  through. The ``consistency.rollback`` fault point fires first, so
  drills can fail the reaction itself.
- :class:`SignificanceTracker` — the host half of the in-jit KKT mask
  (``ops/significance.py``). Meters the mask's per-step suppressed /
  candidate counts into the ``ps_consistency_*`` family AND credits
  the actually-shipped keys to ``ps_push_keys_total`` (store = worker
  name), so the reduction reconciles in-record:
  ``pushed + suppressed == candidates``. With ``kkt_drop_after > 0``
  it also consumes the mask's per-slot feedback to build a
  persistent-drop set: a slot suppressed ``drop_after`` consecutive
  sightings leaves future batches HOST-SIDE (``filter_batch``, called
  from ``prep`` before dedup/padding — those keys never cost upload
  bytes either), with every ``kkt_revisit_every``-th batch shipped
  unfiltered so dropped slots are deterministically revisited and can
  re-earn their place.

Threading (the stateless-or-feeder rule): ``on_collect`` runs on the
collect thread only; ``filter_batch`` runs on the prep thread — serial
by construction, ``kkt_drop_after > 0`` requires ``ingest_workers=1``
(enforced at worker init) because the drop set evolves in collect
order and a concurrent pool would apply it nondeterministically. The
shared drop-set handoff is the one cross-thread edge and is guarded by
a lock.

Determinism: the in-jit mask is seeded (the step's own seed stream),
collects arrive in submission order, and the revisit cadence is a
counter — two runs with the same data, seed, and config make identical
suppression, drop, and τ decisions.
"""

# bit-identical: this module is under the replay bit-identity contract (pslint determinism pass)

from __future__ import annotations

import collections
import threading
from typing import Any, Dict, List, Mapping, Optional

import numpy as np

#: healthy collects between τ widenings (one ministep each): stability
#: must be re-earned per notch, so a noisy run climbs slowly
STABLE_STEPS = 8

#: soft-spike factor vs the controller's own grad-norm window median —
#: deliberately far below the learning plane's divergence judge (100x):
#: the controller CLAMPS early so the alert never needs to fire
SOFT_SPIKE_FACTOR = 4.0

#: grad-norm window for the soft-spike median
SPIKE_WINDOW = 32

#: healthy collects before the soft-spike judge activates
SPIKE_MIN_WINDOW = 8

#: healthy collects between rollback snapshots (state_host drains the
#: executor, so this is the knob trading snapshot cost against the
#: rollback blast radius the snapshot_age gauge reports)
SNAPSHOT_EVERY = 16

#: LR multiplier the divergence reaction applies
BACKOFF_FACTOR = 0.5

#: episode records kept for the debug snapshot
EPISODE_CAP = 64


class AdaptiveTauController:
    """Moves one worker's effective τ from its convergence telemetry.

    Collect-thread only (no lock needed on its own state; the runtime
    serializes). Holds the rollback snapshot — plain host arrays from
    ``worker.state_host()`` — and the reaction logic.
    """

    def __init__(
        self,
        worker,
        *,
        stable_steps: int = STABLE_STEPS,
        spike_factor: float = SOFT_SPIKE_FACTOR,
        snapshot_every: int = SNAPSHOT_EVERY,
        backoff_factor: float = BACKOFF_FACTOR,
        tel: Optional[Dict[str, object]] = None,
    ):
        self.worker = worker
        self.tau_max = max(0, int(worker.sgd.max_delay))
        self.stable_steps = max(1, int(stable_steps))
        self.spike_factor = float(spike_factor)
        self.snapshot_every = max(1, int(snapshot_every))
        self.backoff_factor = float(backoff_factor)
        self._tel = tel
        # conservative start: one ministep of slack, widened as
        # stability is earned (τ=0 would serialize warmup for nothing;
        # τ=max would gamble the whole cap on an untested trajectory)
        self.tau = worker.set_effective_tau(min(1, self.tau_max))
        self._stable = 0
        self._grad_window: collections.deque = collections.deque(
            maxlen=SPIKE_WINDOW
        )
        self._snapshot: Optional[dict] = None
        self._snapshot_age = 0
        self._healthy = 0
        self.episodes: List[Dict[str, Any]] = []
        self.tau_trace: List[int] = [self.tau]

    # -- per-collect policy --

    def on_metrics(
        self, loss: float, grad_norm: Optional[float], nonfinite: bool
    ) -> None:
        if nonfinite:
            self.react("nonfinite")
            return
        spike = False
        if grad_norm is not None:
            if len(self._grad_window) >= SPIKE_MIN_WINDOW:
                med = float(np.median(self._grad_window))
                spike = med > 0 and grad_norm > self.spike_factor * med
            self._grad_window.append(grad_norm)
        if spike:
            # leading indicator, not yet divergence: clamp τ hard
            # (halve) but keep LR and state — cheap, reversible, and
            # re-widened within stable_steps collects if it was noise
            self._set_tau(self.tau // 2, "clamp")
            self._stable = 0
            return
        self._healthy += 1
        self._stable += 1
        if self._stable >= self.stable_steps and self.tau < self.tau_max:
            self._set_tau(self.tau + 1, "widen")
            self._stable = 0
        # rollback snapshot on the healthy cadence (first healthy
        # collect included: a reaction before the first cadence tick
        # must still have somewhere to roll back to)
        self._snapshot_age += 1
        if self._snapshot is None or self._healthy % self.snapshot_every == 0:
            self._take_snapshot()
        if self._tel is not None:
            self._tel["snapshot_age"].labels(
                worker=self.worker.name
            ).set(self._snapshot_age)

    def _take_snapshot(self) -> None:
        # state_host drains the executor (pop=False — in-flight
        # metrics stay collectable), so the snapshot is consistent
        self._snapshot = self.worker.state_host()
        self._snapshot_age = 0

    def _set_tau(self, tau: int, direction: str) -> None:
        tau = self.worker.set_effective_tau(tau)
        if tau != self.tau:
            self.tau = tau
            self.tau_trace.append(tau)
            if self._tel is not None:
                self._tel["tau_changes"].labels(
                    worker=self.worker.name, direction=direction
                ).inc()

    # -- the divergence reaction --

    def react(self, reason: str) -> Dict[str, Any]:
        """τ→0 + LR backoff + snapshot rollback. Collect thread only.

        Also the ``loss_divergence`` alert hook: an alert listener can
        call this directly (reason="alert") — it is idempotent per
        episode in effect, since post-rollback state re-earns τ and LR
        stays backed off.
        """
        from ..system import faults

        # the drill point fires BEFORE any state is touched: a drill
        # injecting a raise here proves the caller survives the
        # reaction itself failing (collect propagates the FaultError)
        faults.inject("consistency.rollback", detail=reason)
        worker = self.worker
        self._set_tau(0, "reset")
        self._stable = 0
        self._grad_window.clear()
        # automatic LR backoff. lr.alpha is a trace-time constant
        # closed over by the compiled steps, so the step cache and the
        # weights fn re-jit — the ONE sanctioned recompile path, paid
        # only on the exceptional divergence reaction (the τ sweep
        # stays at recompiles_post_warmup == 0).
        import jax

        worker.lr.alpha = float(worker.lr.alpha) * self.backoff_factor
        worker._steps.clear()
        worker._weights_fn = worker._make_weights_fn()
        rolled_back = False
        if self._snapshot is not None:
            # drain in-flight steps before installing old state:
            # load_state_host does not drain (its migration caller
            # already has), and a poisoned in-flight step must not
            # land on top of the restored table
            worker.executor.wait_all(pop=False)
            worker.load_state_host(self._snapshot)
            rolled_back = True
        self._snapshot_age = 0
        episode = {
            "reason": reason,
            "healthy_collects": self._healthy,
            "alpha_after": float(worker.lr.alpha),
            "tau_after": self.tau,
            "rolled_back": rolled_back,
        }
        self.episodes.append(episode)
        del self.episodes[:-EPISODE_CAP]
        if self._tel is not None:
            self._tel["backoff"].labels(worker=worker.name).inc()
            if rolled_back:
                self._tel["rollback"].labels(
                    worker=worker.name, reason=reason
                ).inc()
        from ..telemetry import blackbox

        if blackbox.installed_recorder() is not None:
            # armed flight recorder: the whole episode (pre-divergence
            # evidence still in the rings + this reaction) lands in
            # one bundle, keyed to the trigger plane like alert
            # firings are
            blackbox.trigger_bundle("consistency_rollback", detail=reason)
        return episode


class SignificanceTracker:
    """Host accounting + persistent-drop set for the in-jit KKT mask.

    ``note_metrics`` runs on the collect thread; ``filter_batch`` on
    the (serial) prep thread. ``_lock`` guards the handoff.
    """

    def __init__(
        self,
        worker,
        *,
        drop_after: int,
        revisit_every: int,
        tel: Optional[Dict[str, object]] = None,
    ):
        self.worker = worker
        self.num_slots = int(worker.num_slots)
        self.drop_after = int(drop_after)
        self.revisit_every = max(1, int(revisit_every))
        self._tel = tel
        self._push_keys = None
        if tel is not None:
            from ..telemetry import registry as telemetry_registry
            from ..telemetry.instruments import parameter_instruments

            # the worker-side analog of the KV stores' pushed-key
            # accounting: what the filtered sparse step actually
            # shipped, under this worker's store label — the number
            # the suppression counters reconcile against
            self._push_keys = parameter_instruments(
                telemetry_registry.default_registry()
            )["push_keys"].labels(store=worker.name, channel=0)
        self._streaks: Dict[int, int] = {}  # collect thread only
        self._dropped: set = set()  # guarded-by: _lock
        self._lock = threading.Lock()
        self._preps = 0  # prep thread only
        # running totals (collect/prep threads as noted; read via
        # summary() from anywhere — ints, torn reads acceptable)
        self.candidates = 0
        self.suppressed = 0
        self.pushed = 0
        self.dropped_entries = 0
        self.filtered_batches = 0
        self.revisit_batches = 0

    # -- collect side: mask accounting + streaks --

    def note_metrics(self, metrics: Mapping[str, Any]) -> None:
        if "kkt_slots" not in metrics:
            return
        cand = int(round(float(metrics["kkt_slots"])))
        sup = int(round(float(metrics["kkt_suppressed"])))
        self.candidates += cand
        self.suppressed += sup
        self.pushed += cand - sup
        if self._tel is not None:
            w = self.worker.name
            self._tel["candidates"].labels(worker=w).inc(cand)
            self._tel["suppressed"].labels(worker=w).inc(sup)
        if self._push_keys is not None:
            self._push_keys.inc(cand - sup)
        if self.drop_after > 0 and "kkt_keep" in metrics:
            self._note_feedback(
                np.asarray(metrics["kkt_uslots"]),
                np.asarray(metrics["kkt_keep"]),
            )

    def _note_feedback(self, uslots: np.ndarray, keep: np.ndarray) -> None:
        uslots = uslots.reshape(-1)
        keep = keep.reshape(-1).astype(bool)
        real = (uslots >= 0) & (uslots < self.num_slots)
        sup = uslots[real & ~keep]
        kept = uslots[real & keep]
        undropped = []
        for s in kept.tolist():
            self._streaks.pop(s, None)
            undropped.append(s)
        newly: List[int] = []
        for s in sup.tolist():
            streak = self._streaks.get(s, 0) + 1
            if streak >= self.drop_after:
                self._streaks.pop(s, None)
                newly.append(s)
            else:
                self._streaks[s] = streak
        if newly or undropped:
            with self._lock:
                # a kept sighting (a revisit batch, or the escape
                # hatch shipping it) re-earns the slot its place
                self._dropped.difference_update(undropped)
                self._dropped.update(newly)

    # -- prep side: the host drop --

    def filter_batch(self, batch, directory):
        """Drop persistently-suppressed slots from one batch before
        prep (CSR rebuild). Every ``revisit_every``-th batch ships
        unfiltered — the deterministic revisit cadence."""
        self._preps += 1
        if self._preps % self.revisit_every == 0:
            self.revisit_batches += 1
            return batch
        with self._lock:
            if not self._dropped:
                return batch
            dropped = np.fromiter(self._dropped, dtype=np.int64)
        slots = directory.slots(batch.indices)
        keep = ~np.isin(slots, dropped)
        n_drop = int(batch.nnz - keep.sum())
        if n_drop == 0:
            return batch
        rows = batch.row_ids()[keep]
        counts = np.zeros(batch.n, dtype=np.int64)
        np.add.at(counts, rows, 1)
        indptr = np.zeros(batch.n + 1, dtype=batch.indptr.dtype)
        np.cumsum(counts, out=indptr[1:])
        from ..utils.sparse import SparseBatch

        out = SparseBatch(
            y=batch.y,
            indptr=indptr,
            indices=batch.indices[keep],
            values=None if batch.values is None else batch.values[keep],
            num_cols=batch.num_cols,
            slot_ids=None if batch.slot_ids is None else batch.slot_ids[keep],
        )
        self.dropped_entries += n_drop
        self.filtered_batches += 1
        if self._tel is not None:
            self._tel["dropped"].labels(worker=self.worker.name).inc(n_drop)
        return out

    def dropped_slots(self) -> int:
        with self._lock:
            return len(self._dropped)

    def summary(self) -> Dict[str, Any]:
        """The accounting, with the reconciliation identity stated
        in-place (tests/test_consistency.py asserts it)."""
        return {
            "candidates": self.candidates,
            "suppressed": self.suppressed,
            "pushed": self.pushed,
            "reconciled": self.pushed + self.suppressed == self.candidates,
            "dropped_slots": self.dropped_slots(),
            "dropped_entries": self.dropped_entries,
            "filtered_batches": self.filtered_batches,
            "revisit_batches": self.revisit_batches,
        }


class ConsistencyRuntime:
    """One worker's consistency plane: controller + tracker + hooks.

    Installed by ``AsyncSGDWorker.__init__`` when ``tau_adaptive`` or
    ``kkt_filter`` is set; ``ISGDCompNode.collect`` calls
    :meth:`on_collect`, ``prep`` calls :meth:`filter_batch`.
    """

    def __init__(self, worker, controller, tracker):
        self.worker = worker
        self.controller: Optional[AdaptiveTauController] = controller
        self.tracker: Optional[SignificanceTracker] = tracker

    @classmethod
    def from_config(cls, worker, sgd, **kw) -> "ConsistencyRuntime":
        from ..telemetry import registry as telemetry_registry

        tel = None
        if telemetry_registry.enabled():
            from ..telemetry.instruments import consistency_instruments

            tel = consistency_instruments(
                telemetry_registry.default_registry()
            )
        controller = None
        if sgd.tau_adaptive:
            controller = AdaptiveTauController(worker, tel=tel, **kw)
        tracker = None
        if sgd.kkt_filter:
            tracker = SignificanceTracker(
                worker,
                drop_after=sgd.kkt_drop_after,
                revisit_every=sgd.kkt_revisit_every,
                tel=tel,
            )
        return cls(worker, controller, tracker)

    # -- hooks --

    def on_collect(self, metrics: Mapping[str, Any]) -> None:
        """Collect-thread hook: fold one step's host-materialized
        metrics into the tracker, then run the controller policy."""
        if self.tracker is not None:
            self.tracker.note_metrics(metrics)
        if self.controller is not None:
            import math

            objective = float(metrics.get("objective", 0.0))
            num_ex = int(metrics.get("num_ex", 0))
            loss = objective / max(1, num_ex)
            grad_sq = metrics.get("grad_sq")
            grad_norm = None
            if grad_sq is not None:
                g = float(grad_sq)
                grad_norm = math.sqrt(g) if math.isfinite(g) and g >= 0 else g
            nonfinite = not math.isfinite(loss) or (
                grad_norm is not None and not math.isfinite(grad_norm)
            )
            self.controller.on_metrics(loss, grad_norm, nonfinite)

    def filter_batch(self, batch, directory):
        if self.tracker is None:
            return batch
        return self.tracker.filter_batch(batch, directory)

    def react(self, reason: str = "alert") -> Optional[Dict[str, Any]]:
        """External reaction entry (the loss_divergence alert listener
        path); no-op without a controller."""
        if self.controller is None:
            return None
        return self.controller.react(reason)

    def snapshot(self) -> Dict[str, Any]:
        """The consistency view of /debug/snapshot."""
        out: Dict[str, Any] = {"worker": self.worker.name}
        if self.controller is not None:
            c = self.controller
            out["tau"] = {
                "live": c.tau,
                "cap": c.tau_max,
                "trace": list(c.tau_trace[-64:]),
                "healthy_collects": c._healthy,
                "snapshot_age": c._snapshot_age,
            }
            out["episodes"] = list(c.episodes)
        if self.tracker is not None:
            out["significance"] = self.tracker.summary()
        return out

"""SGD learner framework (ref ``src/learner/sgd.{h,cc}``).

- ``SGDProgress``: the progress record (ref learner/proto/sgd.proto).
- ``ISGDScheduler``: workload pool + monitor + progress table printing
  (ref ISGDScheduler::Run / ShowProgress / MergeProgress).
- ``ISGDCompNode``: computation node base with a reporter slaver.
- ``MinibatchReader``: prefetching minibatch source with countmin
  tail-feature filtering and key localization (ref MinibatchReader<V>),
  running read + filter on an ``learner.ingest.IngestPipeline`` feeder
  thread (the staged-parallel host-ingest plane).
"""

from __future__ import annotations

import contextlib
import dataclasses
from typing import Dict, Iterator, List, Optional

import numpy as np

from ..data.stream_reader import StreamReader
from ..filter.frequency import FrequencyFilter
from ..parameter.replica import Checkpointable
from ..system.customer import App
from ..system.monitor import MonitorMaster, MonitorSlaver
from ..telemetry import spans as telemetry_spans
from ..utils.localizer import Localizer
from ..utils.sparse import SparseBatch
from .workload_pool import WorkloadPool


# phase of ps_train_loop_seconds -> the span of the same interval
_LOOP_SPANS = {
    "wait_ingest": "train.wait_ingest",
    "submit": "train.submit",
    "collect_wait": "train.collect.wait",
    "collect_host": "train.collect.host",
}
_END = object()  # end of an iterator whose items may be None


@dataclasses.dataclass
class SGDProgress:
    """ref sgd.proto SGDProgress."""

    objective: List[float] = dataclasses.field(default_factory=list)
    num_examples_processed: int = 0
    accuracy: List[float] = dataclasses.field(default_factory=list)
    auc: List[float] = dataclasses.field(default_factory=list)
    nnz: int = 0
    weight_sum: float = 0.0
    delta_sum: float = 0.0

    def merge(self, other: "SGDProgress") -> None:
        """ref ISGDScheduler::MergeProgress."""
        self.objective.extend(other.objective)
        self.accuracy.extend(other.accuracy)
        self.auc.extend(other.auc)
        self.num_examples_processed += other.num_examples_processed
        self.nnz = other.nnz or self.nnz
        self.weight_sum += other.weight_sum
        self.delta_sum += other.delta_sum


class ISGDScheduler(App):
    """Scheduler: hands workloads to comp nodes, merges progress, prints the
    live table (ref ISGDScheduler::Run + ShowProgress)."""

    def __init__(self, workload_pool: Optional[WorkloadPool] = None, name: str = "sgd_scheduler"):
        super().__init__(name=name)
        self.workload_pool = workload_pool or WorkloadPool()
        self.monitor: MonitorMaster[SGDProgress] = MonitorMaster()
        self.monitor.set_data_merger(lambda src, dst: dst.merge(src))
        self._show_prog_head = True
        self.num_ex_processed = 0

    def show_progress(self, elapsed: float, progress: Dict[str, SGDProgress]) -> None:
        """ref ISGDScheduler::ShowProgress — one merged line per interval."""
        total = SGDProgress()
        for p in progress.values():
            total.merge(p)
        if not total.objective:
            return
        if self._show_prog_head:
            print(" sec  examples    loss      auc   accuracy")
            self._show_prog_head = False
        self.num_ex_processed += total.num_examples_processed
        # objective entries are per-minibatch sums; display per-example loss
        per_ex = sum(total.objective) / max(1, total.num_examples_processed)
        print(
            f"{elapsed:4.0f}  {self.num_ex_processed:.2e}  "
            f"{per_ex:.5f}  {np.mean(total.auc or [0]):.4f}  "
            f"{np.mean(total.accuracy or [0]):.4f}"
        )
        for p in progress.values():  # reset accumulation window
            p.objective.clear()
            p.auc.clear()
            p.accuracy.clear()
            p.num_examples_processed = 0

    def run(self) -> None:
        self.monitor.set_printer(self.show_progress, interval=1.0)


class ISGDCompNode(App, Checkpointable):
    """ref ISGDCompNode: has a reporter to the scheduler's monitor.

    Also the single home of the worker-side progress plumbing shared by
    every SGD-family worker (AsyncSGDWorker, FMWorker, DeepCTRWorker):
    ``collect`` (wait on a step, fold metrics into ``self.progress``,
    heartbeat + dashboard timers, per-minibatch AUC incl. the scan-
    superstep layout) and the default ``train`` loop. Subclasses provide
    ``self.progress`` (an SGDProgress) and ``process_minibatch``."""

    def __init__(self, name: str = "sgd_comp", monitor: Optional[MonitorMaster] = None):
        super().__init__(name=name)
        self.reporter: MonitorSlaver[SGDProgress] = MonitorSlaver(monitor, name)
        # app-layer telemetry (doc/OBSERVABILITY.md): device-confirmed
        # training volume, counted in collect() where the step's metrics
        # land — a cold path shared by every SGD-family worker
        self._examples_counter = None
        # learning truth plane (telemetry/learning.py): workers that
        # know their table geometry install one (AsyncSGDWorker does);
        # collect() folds the step's device-confirmed example count and
        # the in-jit convergence side outputs into it
        self._learning = None
        # self-driving consistency (learner/consistency.py): installed
        # by workers running the adaptive τ controller and/or the KKT
        # significance filter; collect() hands it each step's metrics
        # AFTER the learning plane folds them (the controller reads the
        # plane's judgments, it never re-derives them)
        self._consistency = None
        from ..telemetry import registry as telemetry_registry

        # ps_train_loop_seconds children by phase: where the trainer
        # thread's time in the loop goes (_loop_phase)
        self._loop_seconds: Dict[str, object] = {}
        if telemetry_registry.enabled():
            from ..telemetry.instruments import app_instruments

            tel = app_instruments(telemetry_registry.default_registry())
            self._examples_counter = tel["examples"]
            self._loop_seconds = {
                phase: tel["loop_seconds"].labels(phase=phase)
                for phase in _LOOP_SPANS
            }

    def attach_monitor(self, scheduler: ISGDScheduler) -> None:
        self.reporter = MonitorSlaver(scheduler.monitor, self.name)

    @contextlib.contextmanager
    def _loop_phase(self, phase: str):
        """One phase of the training loop on the trainer's thread:
        ``ps_train_loop_seconds{phase}`` and its ``train.*`` span. The
        phases are siblings, so their sum is the thread's time in the
        loop: waiting for ingest, submitting, waiting for the device,
        and the host work of a collect."""
        with telemetry_spans.span(
            _LOOP_SPANS[phase], histogram=self._loop_seconds.get(phase)
        ):
            yield

    def _awaited(self, items):
        """``items``, with the time this thread blocks for each one
        under the loop's ``wait_ingest`` phase."""
        it = iter(items)
        while True:
            with self._loop_phase("wait_ingest"):
                item = next(it, _END)
            if item is _END:
                return
            yield item

    def collect(self, ts: int) -> SGDProgress:
        """Wait for a step and fold its metrics into progress (the
        worker's reporter_.Report path)."""
        with self._loop_phase("collect_wait"):
            self.po.beat(self.name)  # liveness signal (ref heartbeat thread)
            hb = (
                self.po.aux.info(self.name) if self.po.aux is not None
                else None
            )
            if hb is not None:
                hb.start_timer()  # dashboard busy-time (ref heartbeat_info.h)
            metrics = self.executor.wait(ts)
            if hb is not None:
                hb.stop_timer()
        if metrics is None:
            return self.progress
        with self._loop_phase("collect_host"):
            return self._fold(metrics)

    def _fold(self, metrics) -> SGDProgress:
        """The host work of a collect: counters, the learning plane,
        per-minibatch AUC, the reporter."""
        from ..utils import evaluation

        if self._examples_counter is not None:
            self._examples_counter.inc(int(metrics["num_ex"]))
        if self._learning is not None:
            # the progress plane's device-confirmed side: the step's
            # own num_ex output plus the in-jit loss/grad/update/weight
            # side outputs, metered host-side (PR 8 jit-purity pattern)
            self._learning.note_step(metrics)
        if self._consistency is not None:
            # adaptive τ / KKT accounting / divergence reaction — may
            # back off LR, clamp τ, and roll state back to the last
            # healthy snapshot (the exceptional path; collect-thread
            # only, like everything else in this method)
            self._consistency.on_collect(metrics)
        prog = SGDProgress(
            objective=[float(metrics["objective"])],
            num_examples_processed=int(metrics["num_ex"]),
            accuracy=[
                float(metrics["correct"]) / max(1.0, float(metrics["num_ex"]))
            ],
        )
        if "xw" in metrics:  # aux present: per-minibatch AUC (prog.add_auc)
            y = np.asarray(metrics["y"])
            xw = np.asarray(metrics["xw"])
            mask = np.asarray(metrics["mask"])
            if xw.ndim >= 3:
                # scan superstep: leading ministep axis — one AUC per
                # ministep (each scored against its own weight version),
                # preserving the per-minibatch monitoring granularity
                prog.auc = [
                    evaluation.auc(
                        y[t].ravel()[mask[t].ravel() > 0],
                        xw[t].ravel()[mask[t].ravel() > 0],
                    )
                    for t in range(xw.shape[0])
                ]
            else:
                m = mask.ravel() > 0
                prog.auc = [evaluation.auc(y.ravel()[m], xw.ravel()[m])]
        self.progress.merge(prog)
        self.reporter.report(prog)
        return prog

    def train(self, batches) -> SGDProgress:
        """Default minibatch loop: keep a small in-flight window so the
        device pipeline stays fed while metrics drain."""
        pending = []
        for b in batches:
            pending.append(self.process_minibatch(b))
            if len(pending) > 2:
                self.collect(pending.pop(0))
        for ts in pending:
            self.collect(ts)
        return self.progress

    # checkpoint/restore: inherited from replica.Checkpointable via the
    # state_host/load_state_host hooks (state_host drains with
    # pop=False, so metrics of in-flight steps remain collectable)

    def _prep_ell(self, batch):
        """Shared ELL prep for the embedding-table workers (FM, DeepCTR):
        ceil-divide rows over the data shards, size the row padding from
        the conf or the first batch, refuse batches that outgrow the
        compiled padding. Requires ``self.sgd/.directory/.num_slots`` and
        a ``self._rows_pad`` slot (None until first use)."""
        from ..apps.linear.async_sgd import prep_batch_ell  # lazy: apps import us
        from ..parallel import mesh as meshlib

        d = meshlib.num_workers(self.mesh)
        if self._rows_pad is None:
            self._rows_pad = self.sgd.rows_pad or -(-batch.n // d)
        if -(-batch.n // d) > self._rows_pad:
            raise ValueError(
                f"batch of {batch.n} rows exceeds the compiled padding "
                f"({self._rows_pad} rows/shard x {d} shards); set "
                "SGDConfig.rows_pad to the largest minibatch up front"
            )
        return prep_batch_ell(
            batch, self.directory, d, self._rows_pad, self.sgd.ell_lanes,
            self.num_slots,
        )


def apply_tail_filter(
    batch: SparseBatch, filter_: FrequencyFilter, freq: int
) -> SparseBatch:
    """One batch through the countmin tail-feature filter: insert this
    batch's unique keys, drop entries whose estimated frequency is
    below ``freq`` (ref MinibatchReader::Read, sgd.h:117-135). STATEFUL
    — batches must pass through in stream order for a deterministic
    result, which is why the ingest pipeline keeps this stage serial on
    the feeder thread."""
    loc = Localizer()
    # one unique pass serves both the sketch update and the remap
    # (count_uniq_index == count_uniq_keys + the retained inverse)
    keys, cnt = loc.count_uniq_index(batch)
    filter_.insert_keys(keys, cnt)
    keep = filter_.query_keys(keys, freq)
    local = loc.remap_index(keep)
    # restore global key ids so downstream sees a normal batch
    local.indices = keep[local.indices]
    local.num_cols = batch.num_cols
    return local


class MinibatchReader:
    """Prefetching minibatch reader (ref MinibatchReader<V>, sgd.h:60-143).

    Streams SparseBatches from files and filters tail features with a
    countmin sketch, both OFF the trainer thread: reading and filtering
    run on an :class:`~..learner.ingest.IngestPipeline` feeder thread
    behind a bounded queue, so the consumer only pays a queue pop. Keys
    stay global — the worker's ``prep_batch`` does the final remap to
    table slots.

    Lifecycle (enforced): call :meth:`start` before reading (``start``
    is idempotent), and :meth:`close` when done — it stops and joins
    the producer thread. Usable as a context manager.
    """

    def __init__(
        self,
        files: Optional[List[str]] = None,
        minibatch_size: int = 1000,
        data_format: str = "libsvm",
        capacity: int = 16,
        batches: Optional[Iterator[SparseBatch]] = None,
    ):
        self._source: Optional[Iterator[SparseBatch]] = batches
        if self._source is None:
            reader = StreamReader(files or [], data_format)
            # chunked byte parse: raw line-aligned chunks go straight
            # into the GIL-releasing native parser on a small pool
            # (falls back to the line path for formats without one);
            # bit-identical to minibatches() — tests/test_data.py
            # TestByteStreaming
            self._source = reader.minibatches_bytes(
                minibatch_size, threads=2
            )
        self._filter: Optional[FrequencyFilter] = None
        self._freq = 0
        self._capacity = capacity
        self._pipe: Optional["IngestPipeline"] = None
        self._it: Optional[Iterator[SparseBatch]] = None
        self._closed = False

    def init_filter(self, n: int, k: int, freq: int) -> None:
        """Countmin tail-feature filter (ref InitFilter); set before
        :meth:`start`."""
        if self._pipe is not None:
            raise RuntimeError("init_filter() after start()")
        self._filter = FrequencyFilter(n, k)
        self._freq = freq

    def start(self) -> "MinibatchReader":
        """Start the producer thread. Idempotent: a second call is a
        no-op (the reference's _started flag, now enforced)."""
        if self._closed:
            raise RuntimeError("MinibatchReader.start() after close()")
        if self._pipe is not None:
            return self
        from .ingest import IngestPipeline

        filter_fn = None
        if self._filter is not None and self._freq > 0:
            filt, freq = self._filter, self._freq
            filter_fn = lambda b: apply_tail_filter(b, filt, freq)  # noqa: E731
        self._pipe = IngestPipeline(
            self._source,
            filter_fn=filter_fn,
            capacity=self._capacity,
            name="minibatch_reader",
        ).start()
        self._it = iter(self._pipe)
        return self

    def read(self) -> Optional[SparseBatch]:
        """Next minibatch with tail features dropped (ref Read), or
        None at end of stream. Raises if the reader was never started
        or already closed, and re-raises producer exceptions."""
        if self._pipe is None or self._it is None:
            raise RuntimeError(
                "MinibatchReader.read() before start(): call start() "
                "first, or use the reader as a context manager"
            )
        if self._closed:
            raise RuntimeError("MinibatchReader.read() after close()")
        return next(self._it, None)

    def close(self) -> None:
        """Stop the pipeline and join the producer thread; idempotent."""
        self._closed = True
        if self._pipe is not None:
            self._pipe.close()

    def __enter__(self) -> "MinibatchReader":
        return self.start()

    def __exit__(self, *exc) -> None:
        self.close()

    def __iter__(self) -> Iterator[SparseBatch]:
        while True:
            b = self.read()
            if b is None:
                return
            yield b

"""Staged, parallel host-ingest pipeline.

The OSDI'14 parameter server's core throughput lesson is to overlap
data movement with computation via producer/consumer pipelines; on TPU
the host→device link is the scarce resource (the device step is ~100x
faster than the transfer), so every host second spent parsing,
filtering, or packing ON the trainer thread is a second the link sits
idle. This module splits ingest into stages and pins each to the right
concurrency:

    read ──> filter ──> prep (xN workers, ordered) ──> consumer
    (feeder thread,     (OrderedStagePool)             (trainer, or a
     serial, in order)                                  DeviceUploader)

- **read**: pull batches from the source iterator (chunked parse lives
  inside StreamReader — the native parser releases the GIL, so this
  stage runs in true parallel with prep).
- **filter**: the countmin tail-feature filter is STATEFUL (insert
  then query), so it runs serially on the feeder thread in batch order
  — parallelizing it would change which keys pass the frequency
  threshold and break determinism.
- **prep**: localize/remap/ELL-pack/bitpack is stateless per batch —
  it fans out over ``workers`` pool threads, and the pool re-emits
  results IN SOURCE ORDER, so the consumer sees a batch stream
  bit-identical to the serial path (tier-1 parity test in
  tests/test_ingest.py). The stream wire's fused native prep
  (``learner/wire.encode_stream_shard``, one C ABI call per shard) and
  the staging-leg frame encode (``wire_compress``) both run INSIDE
  this stage — stateless, so the pool parallelism applies to them for
  free; the matching frame DECODE belongs to the single uploader
  thread (DeviceUploader), never here.

Exceptions from any stage forward to the consumer at the position they
occurred; ``close()`` joins every thread (early consumer exit leaks
nothing). Telemetry (``ps_ingest_*``, doc/OBSERVABILITY.md) records
per-stage latency histograms, queue-depth gauges, and volume counters.
"""
# bit-identical: this module is under the replay bit-identity contract (pslint determinism pass)

from __future__ import annotations

import contextlib
from typing import Callable, Iterator, Optional

from ..system import faults
from ..telemetry import spans as telemetry_spans
from ..utils.concurrent import OrderedStagePool, iter_on_thread


_END = object()  # end of a stage's input, where None may be an item


def pipeline_instruments():
    """ps_ingest_* instruments against the process registry, or None
    while telemetry is disabled."""
    from ..telemetry import registry as telemetry_registry

    if not telemetry_registry.enabled():
        return None
    from ..telemetry.instruments import ingest_instruments

    return ingest_instruments(telemetry_registry.default_registry())


class IngestPipeline:
    """Multi-stage ingest: serial read+filter on a feeder thread, prep
    on an ordered worker pool, deterministic batch order throughout.

    ``filter_fn`` (optional) runs serially in batch order on the feeder
    thread; ``prep_fn`` (optional) runs on ``workers`` pool threads
    with in-order emission. With no prep_fn (or ``workers == 0``) the
    pipeline degenerates to a single prefetching producer thread —
    the classic MinibatchReader shape (ref sgd.h:60-143).

    Lifecycle: ``start()`` is idempotent; iteration before ``start()``
    raises; ``close()`` stops and joins every pipeline thread and is
    also called automatically when iteration completes. Usable as a
    context manager.
    """

    def __init__(
        self,
        source,
        *,
        filter_fn: Optional[Callable] = None,
        prep_fn: Optional[Callable] = None,
        workers: int = 0,
        capacity: int = 4,
        name: str = "ingest",
    ):
        self._source = iter(source)
        self._filter_fn = filter_fn
        self._prep_fn = prep_fn
        self._workers = max(0, int(workers))
        self._capacity = max(1, int(capacity))
        self._name = name
        self._tel = pipeline_instruments()
        # Single-consumer lifecycle state — deliberately lock-free (in
        # pslint's lock-pass scope, nothing guarded): start()/__iter__/
        # close() all run on the consumer thread; the pool and thread
        # iterator own their cross-thread synchronization internally.
        self._pool: Optional[OrderedStagePool] = None
        self._thread_it = None
        self._it: Optional[Iterator] = None
        self._closed = False
        # timeline tracing (telemetry/timeline.py): while a span sink
        # is installed every batch gets a flow id on the feeder and
        # rides it through filter → prep → the consumer. Items always
        # travel internally as (flow, batch) pairs, flow None with
        # tracing off; the consumer-facing iterator unwraps.

    # -- stage bodies --------------------------------------------------

    @contextlib.contextmanager
    def _stage(self, stage: str, flow=None):
        """One stage's work on one item: ``ps_ingest_stage_seconds`` and
        the ``ingest.<stage>`` span under the item's flow, or under none
        (a feeder that consumes another pipeline still has the flow of
        that one's last item active)."""
        hist = None
        if self._tel is not None:
            hist = self._tel["stage_seconds"].labels(
                stage=stage, pipeline=self._name
            )
        with telemetry_spans.flow_scope(flow), telemetry_spans.span(
            "ingest." + stage, histogram=hist, flow=flow,
            pipeline=self._name,
        ) as found:
            yield found

    def _produced(self) -> Iterator:
        """Feeder-side serial stages: read (source next) + filter. Each
        batch is born here, with a flow id when tracing, and every
        stage span carries it."""
        src = self._source
        while True:
            fid = None
            with self._stage("read") as found:
                batch = next(src, _END)
                if found is not None and batch is not _END:
                    # the read that finds the end of the stream is no
                    # unit of work: only a batch is born with a flow
                    fid = found["flow"] = telemetry_spans.new_flow()
            if batch is _END:
                return
            if self._filter_fn is not None:
                with self._stage("filter", fid):
                    batch = self._filter_fn(batch)
            yield fid, batch

    def _prep(self, item):
        # fault point (doc/ROBUSTNESS.md): an armed raise dies mid-batch
        # on a POOL WORKER thread — exercising the pool's contract that
        # worker exceptions forward to the consumer at the position they
        # occurred and close() still joins every thread
        faults.inject("ingest.prep", detail=self._name)
        fid, batch = item
        with self._stage("prep", fid):
            return fid, self._prep_fn(batch)

    # -- lifecycle -----------------------------------------------------

    def start(self) -> "IngestPipeline":
        """Idempotent: build and start the pipeline threads once."""
        if self._closed:
            raise RuntimeError(f"{self._name}: start() after close()")
        if self._it is not None:
            return self
        if self._prep_fn is not None and self._workers > 0:
            self._pool = OrderedStagePool(
                self._prep,
                self._produced(),
                num_workers=self._workers,
                capacity=self._capacity,
                name=self._name,
            ).start()
            self._it = iter(self._pool)
        else:
            # single producer thread: read + filter (+ prep, serially)
            src = (
                map(self._prep, self._produced())
                if self._prep_fn is not None
                else self._produced()
            )
            self._thread_it = iter_on_thread(src, maxsize=self._capacity)
            self._it = self._thread_it
        return self

    @property
    def started(self) -> bool:
        return self._it is not None

    def qsize(self) -> int:
        """Batches staged ahead of the consumer (0 before start)."""
        return self._pool.qsize() if self._pool is not None else 0

    def __iter__(self) -> Iterator:
        if self._it is None:
            raise RuntimeError(
                f"{self._name}: iterated before start() — call start() "
                "first (or use the pipeline as a context manager)"
            )
        tel = self._tel
        wait_hist = None
        if tel is not None:
            wait_hist = tel["wait_seconds"].labels(queue=self._name)
        try:
            while True:
                # the consumer blocked on this pipeline's queue: which
                # thread waits for which feeder (a chained pipeline's
                # feeder is the consumer of the one before it)
                with telemetry_spans.span(
                    "ingest.wait", histogram=wait_hist, pipeline=self._name
                ):
                    item = next(self._it, _END)
                if item is _END:
                    return
                # items travel as (flow, batch) internally; the
                # consumer sees the bare batch, with the batch's flow
                # active on its thread until it advances to the next
                # item (so a downstream stage's spans correlate)
                fid, item = item
                if tel is not None:
                    tel["queue_depth"].labels(queue=self._name).set(
                        self.qsize()
                    )
                    # volume counters only for batch-shaped items; a
                    # pipeline emitting groups/parts leaves counting to
                    # the downstream stage (DeviceUploader) so batches
                    # are never double-counted
                    n = getattr(item, "n", None) or getattr(
                        item, "num_examples", None
                    )
                    if n:
                        tel["batches"].labels(pipeline=self._name).inc()
                        tel["examples"].labels(pipeline=self._name).inc(
                            int(n)
                        )
                with telemetry_spans.flow_scope(fid):
                    yield item
        finally:
            self.close()

    def close(self) -> None:
        """Stop and join every pipeline thread; safe to call twice."""
        self._closed = True
        if self._pool is not None:
            self._pool.close()
        if self._thread_it is not None:
            self._thread_it.close()

    def __enter__(self) -> "IngestPipeline":
        return self.start()

    def __exit__(self, *exc) -> None:
        self.close()

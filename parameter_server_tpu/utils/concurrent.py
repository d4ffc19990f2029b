"""Host-side concurrency helpers.

Counterparts of ``src/util/threadpool.h``, ``producer_consumer.h``,
``threadsafe_queue.h`` and ``threadsafe_limited_queue.h``. On TPU the device
does the math; these keep the *host* busy — prefetching/parsing minibatches
while the chip runs — which is exactly the role the reference's
ProducerConsumer plays for MinibatchReader.
"""

from __future__ import annotations

import queue
import threading
import time
from typing import Callable, Generic, Iterator, Optional, TypeVar

from ..telemetry import spans as telemetry_spans

T = TypeVar("T")


class ThreadsafeQueue(Generic[T]):
    """Unbounded thread-safe FIFO (ref threadsafe_queue.h)."""

    def __init__(self) -> None:
        self._q: "queue.Queue[T]" = queue.Queue()

    def push(self, item: T) -> None:
        self._q.put(item)

    def wait_and_pop(self, timeout: Optional[float] = None) -> T:
        return self._q.get(timeout=timeout)

    def try_pop(self) -> Optional[T]:
        try:
            return self._q.get_nowait()
        except queue.Empty:
            return None

    def empty(self) -> bool:
        return self._q.empty()


class _ProducerError:
    """Wrapper carrying a producer exception through the queue."""

    __slots__ = ("exc",)

    def __init__(self, exc: BaseException):
        self.exc = exc


class ProducerConsumer(Generic[T]):
    """Bounded producer/consumer with a capacity budget (ref
    producer_consumer.h: startProducer(fn) where fn fills an item and reports
    its size; pop() blocks until data or producer end).

    Contracts the ingest pipelines rely on (tested in
    tests/test_ingest.py): an exception raised by ``produce`` is
    forwarded to the consumer — ``pop()`` re-raises it instead of
    hanging or silently truncating the stream — and :meth:`close` stops
    and joins the producer threads, so a consumer that exits early
    leaks no threads blocked in ``q.put`` (interpreter teardown would
    kill such a thread mid-call)."""

    _END = object()

    def __init__(self, capacity: int = 16):
        self._q: "queue.Queue" = queue.Queue(maxsize=capacity)
        self._threads: list[threading.Thread] = []
        self._live = 0  # guarded-by: _live_lock — producers still running
        self._live_lock = threading.Lock()
        self._stop = threading.Event()
        self._error: Optional[BaseException] = None

    def _put(self, item) -> bool:
        """Stop-aware put: returns False when close() was requested."""
        while not self._stop.is_set():
            try:
                self._q.put(item, timeout=0.2)
                return True
            except queue.Full:
                continue
        return False

    def start_producer(
        self, produce: Callable[[], Optional[T]], num_threads: int = 1
    ) -> None:
        """``produce`` returns the next item or None at end of stream.

        With num_threads > 1, several producers drain the same source
        concurrently (``produce`` must be thread-safe); order of items is
        then unspecified — fine for SGD minibatches, which the reference
        shuffles anyway.
        """
        with self._live_lock:
            self._live = num_threads

        def run():
            try:
                while not self._stop.is_set():
                    item = produce()
                    if item is None:
                        break
                    if not self._put(item):
                        return
            except BaseException as e:  # forward to the consumer
                self._put(_ProducerError(e))
                return
            with self._live_lock:
                self._live -= 1
                if self._live == 0:
                    self._put(self._END)

        for _ in range(num_threads):
            t = threading.Thread(target=run, daemon=True)
            self._threads.append(t)
            t.start()

    def pop(self) -> Optional[T]:
        # a poisoned stream stays poisoned: once an error surfaced,
        # every later pop() re-raises immediately (held in an attribute
        # rather than re-queued — a blocking re-put could deadlock
        # against still-live producers on a full queue)
        if self._error is not None:
            raise self._error
        item = self._q.get()
        if item is self._END:
            # re-queue the sentinel so every later pop() (another consumer,
            # a second iteration) also sees end-of-stream instead of hanging —
            # matches the reference pop() returning false repeatedly at end.
            # Safe: END is only put once ALL producers finished, so no
            # producer can race this slot.
            self._q.put(self._END)
            return None
        if isinstance(item, _ProducerError):
            self._error = item.exc
            raise item.exc
        return item

    def __iter__(self) -> Iterator[T]:
        while True:
            item = self.pop()
            if item is None:
                return
            yield item

    def close(self, join_s: float = 2.5) -> None:
        """Stop producers and join their threads (bounded): the early-
        consumer-exit path. A producer wedged inside ``produce`` itself
        cannot be interrupted and is left to daemon teardown."""
        self._stop.set()
        deadline = time.monotonic() + max(0.0, join_s)
        while time.monotonic() < deadline and any(
            t.is_alive() for t in self._threads
        ):
            # drain so a producer mid-put unblocks at its next timeout tick
            try:
                self._q.get_nowait()
            except queue.Empty:
                pass
            for t in self._threads:
                t.join(timeout=0.05)


class _Slot:
    """One in-flight item of an OrderedStagePool: the ordering token the
    consumer waits on."""

    __slots__ = ("event", "value", "error")

    def __init__(self):
        self.event = threading.Event()
        self.value = None
        self.error: Optional[BaseException] = None


class OrderedStagePool(Generic[T]):
    """Ordered parallel stage: N workers apply ``fn`` to items pulled
    from ``source``, and results are emitted IN SOURCE ORDER through a
    bounded window — the pipeline building block the staged host-ingest
    path needs (parallel localize/pack with a deterministic batch
    stream; ref threadpool.h applied to the MinibatchReader role).

    Structure: a feeder thread iterates ``source`` (so a slow source —
    parsing, filtering — runs OFF the consumer thread too), assigning
    each item a slot that enters the bounded output queue in source
    order; workers fill slots as they finish. ``capacity`` bounds the
    in-flight window (completed-but-unconsumed + in-progress items), so
    the feeder backpressures instead of racing ahead.

    Exception contract (tested): an exception raised by ``source``
    ends the stream and re-raises at the consumer; an exception raised
    by ``fn`` on item k re-raises when the consumer reaches position k
    — deterministic either way. ``close()`` (also called when the
    consumer's iteration ends or breaks early) stops and joins the
    feeder and workers, so early exit leaks no threads.
    """

    _END = object()
    _WSTOP = object()

    def __init__(
        self,
        fn: Callable[[T], object],
        source,
        num_workers: int = 2,
        capacity: Optional[int] = None,
        name: str = "stage",
        close_join_s: float = 2.5,
    ):
        self._fn = fn
        self._source = iter(source)
        self._num = max(1, int(num_workers))
        cap = capacity if capacity is not None else 2 * self._num
        self._capacity = max(1, int(cap))
        self._name = name
        self._close_join_s = close_join_s
        self._out_q: "queue.Queue" = queue.Queue(maxsize=self._capacity)
        self._work_q: "queue.Queue" = queue.Queue(maxsize=self._capacity)
        self._stop = threading.Event()
        self._threads: list[threading.Thread] = []
        self._started = False

    # -- internals ----------------------------------------------------

    def _put(self, q: "queue.Queue", item) -> bool:
        while not self._stop.is_set():
            try:
                q.put(item, timeout=0.2)
                return True
            except queue.Full:
                continue
        return False

    def _feed(self) -> None:
        try:
            for item in self._source:
                slot = _Slot()
                # out_q first: the slot takes its ordinal position in
                # the emission order before any worker can touch it
                if not self._put(self._out_q, slot):
                    return
                if not self._put(self._work_q, (item, slot)):
                    return
            self._put(self._out_q, self._END)
        except BaseException as e:  # source exception -> ordered re-raise
            # timeline terminator: the stream dies HERE — without this
            # tombstone the trace just stops and a reader cannot tell a
            # wedge from a crash (doc/OBSERVABILITY.md, abandoned spans)
            telemetry_spans.abandoned(
                f"{self._name}.source", reason=type(e).__name__
            )
            slot = _Slot()
            slot.error = e
            slot.event.set()
            self._put(self._out_q, slot)

    def _work(self) -> None:
        while True:
            task = self._work_q.get()
            if task is self._WSTOP:
                return
            item, slot = task
            if self._stop.is_set():
                # consumer is gone: don't burn CPU on abandoned items,
                # but mark the slot so no one can block on it
                slot.event.set()
                continue
            try:
                slot.value = self._fn(item)
            except BaseException as e:
                # exception-forwarding path: the item's span (opened by
                # the stage fn) closed with an error attr when the
                # exception unwound it; this explicit terminator marks
                # the POOL abandoning the item, so the timeline shows
                # where the ordered stream was poisoned even when the
                # stage fn opened no span of its own
                telemetry_spans.abandoned(
                    f"{self._name}.worker", reason=type(e).__name__
                )
                slot.error = e
            slot.event.set()

    # -- public surface ----------------------------------------------

    def start(self) -> "OrderedStagePool[T]":
        """Idempotent: spin up the feeder + worker threads once."""
        if self._started:
            return self
        self._started = True
        feeder = threading.Thread(
            target=self._feed, daemon=True, name=f"{self._name}-feed"
        )
        self._threads.append(feeder)
        for i in range(self._num):
            w = threading.Thread(
                target=self._work, daemon=True, name=f"{self._name}-w{i}"
            )
            self._threads.append(w)
        for t in self._threads:
            t.start()
        return self

    def qsize(self) -> int:
        """Completed-or-in-progress items staged ahead of the consumer."""
        return self._out_q.qsize()

    def __iter__(self) -> Iterator:
        self.start()
        try:
            while True:
                slot = self._out_q.get()
                if slot is self._END:
                    return
                slot.event.wait()
                if slot.error is not None:
                    raise slot.error
                yield slot.value
        finally:
            self.close()

    def close(self) -> None:
        """Stop feeder + workers and join them (bounded). Safe to call
        more than once; a worker wedged inside ``fn`` stays alive
        (daemon) and is disclosed to teardown as-is."""
        self._stop.set()
        deadline = time.monotonic() + max(0.0, self._close_join_s)
        # wake idle workers immediately: one stop sentinel each. A full
        # queue drains fast once stop is set (workers skip fn and just
        # mark slots), so a short blocking put suffices — draining here
        # instead could swallow a sentinel another worker never saw.
        workers = self._threads[1:]
        for _ in range(self._num):
            while time.monotonic() < deadline and any(
                t.is_alive() for t in workers
            ):
                try:
                    self._work_q.put(self._WSTOP, timeout=0.05)
                    break
                except queue.Full:
                    continue
        while time.monotonic() < deadline and any(
            t.is_alive() for t in self._threads
        ):
            # drain the output so a feeder mid-put unblocks at its next
            # timeout tick...
            try:
                self._out_q.get_nowait()
            except queue.Empty:
                pass
            # ...and re-seed an END sentinel so a CONSUMER on another
            # thread blocked in out_q.get() (the DeviceUploader nesting)
            # wakes and terminates instead of waiting forever on a slot
            # this drain may have stolen
            try:
                self._out_q.put_nowait(self._END)
            except queue.Full:
                pass
            for t in self._threads:
                t.join(timeout=0.05)


class ThreadPool:
    """Fixed-size pool mirroring ref threadpool.h's add()/startWorkers()."""

    def __init__(self, num_workers: int):
        self._num = max(1, num_workers)
        self._tasks: list[Callable[[], None]] = []

    def add(self, fn: Callable[[], None]) -> None:
        self._tasks.append(fn)

    def start_workers(self) -> None:
        """Run all queued tasks across the pool and join (the reference
        blocks in the destructor; we block here)."""
        it = iter(self._tasks)
        lock = threading.Lock()
        errors: list[BaseException] = []

        def worker():
            while True:
                with lock:
                    task = next(it, None)
                if task is None:
                    return
                try:
                    task()
                except BaseException as e:  # surface to caller, don't die silently
                    with lock:
                        errors.append(e)
                    return

        threads = [threading.Thread(target=worker) for _ in range(self._num)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        self._tasks.clear()
        if errors:
            raise errors[0]


def iter_on_thread(it, maxsize: int, close_join_s: float = 2.5):
    """Run iterator ``it`` on a daemon thread, yielding its items
    through a bounded queue (backpressure: the producer blocks once
    ``maxsize`` items are staged ahead). Exceptions raised by the
    producer propagate to the consumer at the point of iteration.

    The generator-returning sibling of :class:`ProducerConsumer` (ref
    producer_consumer.h), adding the two contracts the training/bench
    pipelines need: producer exceptions forwarded to the consumer, and
    abandonment handling — when the consumer stops iterating early (an
    exception in its loop body, a break, an explicit ``close()``), the
    producer is signalled to stop and briefly joined, because a thread
    left blocked in ``q.put`` forever would be killed mid-call by
    interpreter teardown (observed as 'terminate called / FATAL:
    exception not rethrown' from inside a jax device call). The join
    is bounded by ``close_join_s``: a producer wedged inside the
    SOURCE iterator itself (a stuck read, a stalled device transfer)
    cannot be interrupted from here, and close() must not hold up the
    consumer's own error propagation waiting for it."""
    q: "queue.Queue" = queue.Queue(maxsize=maxsize)
    done = object()
    stop = threading.Event()

    def _put(x) -> bool:
        while not stop.is_set():
            try:
                q.put(x, timeout=0.2)
                return True
            except queue.Full:
                continue
        return False

    def run():
        try:
            for x in it:
                if not _put(x):
                    return
            _put(done)
        except BaseException as e:
            _put(e)

    t = threading.Thread(target=run, daemon=True)
    t.start()
    try:
        while True:
            x = q.get()
            if x is done:
                return
            if isinstance(x, BaseException):
                raise x
            yield x
    finally:
        stop.set()
        # drain so a producer mid-put unblocks at its next timeout
        # tick, then give it a bounded window to finish its current
        # item; a producer stuck in the source iterator stays alive
        # (nothing can stop it) and is disclosed to teardown as-is
        deadline = time.monotonic() + max(0.0, close_join_s)
        while t.is_alive() and time.monotonic() < deadline:
            try:
                q.get_nowait()
            except queue.Empty:
                pass
            t.join(timeout=0.1)

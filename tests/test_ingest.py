"""Staged host-ingest pipeline (PR3): OrderedStagePool contracts,
ProducerConsumer exception/shutdown contracts, MinibatchReader
lifecycle, serial-vs-pipelined determinism parity on the libsvm
fixture (ELL i32 / u24 / bits encodings), and ingest telemetry."""

import os
import threading
import time

import numpy as np
import pytest

FIXTURE = os.path.join(os.path.dirname(__file__), "data", "ingest_parity.libsvm")


def _settle_threads(before, timeout=5.0):
    """Wait for the thread count to drop back to ``before``."""
    t0 = time.time()
    while threading.active_count() > before and time.time() - t0 < timeout:
        time.sleep(0.02)
    return threading.active_count()


class TestOrderedStagePool:
    def test_in_order_emission_under_jitter(self):
        from parameter_server_tpu.utils.concurrent import OrderedStagePool

        def jittered(x):
            time.sleep(0.001 * ((x * 7) % 5))
            return x * x

        out = list(OrderedStagePool(jittered, range(50), num_workers=4))
        assert out == [x * x for x in range(50)]

    def test_fn_exception_forwarded_at_position(self):
        from parameter_server_tpu.utils.concurrent import OrderedStagePool

        def boom(x):
            if x == 3:
                raise ValueError("item three")
            return x

        it = iter(OrderedStagePool(boom, range(8), num_workers=3))
        assert [next(it) for _ in range(3)] == [0, 1, 2]
        with pytest.raises(ValueError, match="item three"):
            next(it)

    def test_source_exception_forwarded(self):
        from parameter_server_tpu.utils.concurrent import OrderedStagePool

        def poisoned():
            yield 1
            yield 2
            raise RuntimeError("source died")

        it = iter(OrderedStagePool(lambda x: x, poisoned(), num_workers=2))
        assert next(it) == 1
        assert next(it) == 2
        with pytest.raises(RuntimeError, match="source died"):
            next(it)

    def test_early_exit_leaks_no_threads(self):
        from parameter_server_tpu.utils.concurrent import OrderedStagePool

        before = threading.active_count()
        pool = OrderedStagePool(
            lambda x: x, range(1000), num_workers=3, capacity=2
        )
        it = iter(pool)
        assert next(it) == 0
        it.close()  # early abandon -> generator finally -> pool.close()
        assert _settle_threads(before) <= before

    def test_close_idempotent_and_joins(self):
        from parameter_server_tpu.utils.concurrent import OrderedStagePool

        before = threading.active_count()
        pool = OrderedStagePool(lambda x: x, range(100), num_workers=2)
        assert list(pool) == list(range(100))
        pool.close()
        pool.close()
        assert _settle_threads(before) <= before

    def test_close_wakes_cross_thread_consumer(self):
        """close() from another thread must wake a consumer blocked in
        the output-queue get (the DeviceUploader nesting), not strand
        it by draining the sentinel it was waiting for."""
        from parameter_server_tpu.utils.concurrent import OrderedStagePool

        def trickle():
            yield 0
            time.sleep(30)  # feeder wedged: consumer will block on item 2
            yield 1

        pool = OrderedStagePool(lambda x: x, trickle(), num_workers=2)
        got = []
        done = threading.Event()

        def consume():
            for x in pool:
                got.append(x)
            done.set()

        t = threading.Thread(target=consume, daemon=True)
        t.start()
        t0 = time.time()
        while not got and time.time() - t0 < 5:
            time.sleep(0.01)
        assert got == [0]
        pool.close()  # consumer is blocked in out_q.get() right now
        assert done.wait(5), "consumer stayed blocked after close()"
        t.join(5)
        assert not t.is_alive()

    def test_backpressure_bounded_window(self):
        from parameter_server_tpu.utils.concurrent import OrderedStagePool

        started = []
        lock = threading.Lock()
        release = threading.Event()

        def slow(x):
            with lock:
                started.append(x)
            release.wait(5)
            return x

        pool = OrderedStagePool(slow, range(100), num_workers=2, capacity=3)
        it = iter(pool)
        time.sleep(0.3)  # let the feeder run as far as it can
        # in-flight window is bounded by capacity: the feeder cannot
        # race ahead of the consumer by more than the out-queue depth
        with lock:
            n_started = len(started)
        assert n_started <= 3 + 2, n_started
        release.set()
        assert next(it) == 0
        it.close()


class TestProducerConsumer:
    def test_producer_exception_forwarded(self):
        from parameter_server_tpu.utils.concurrent import ProducerConsumer

        state = {"n": 0}

        def produce():
            state["n"] += 1
            if state["n"] > 3:
                raise RuntimeError("producer died")
            return state["n"]

        pc = ProducerConsumer(capacity=4)
        pc.start_producer(produce)
        assert [pc.pop(), pc.pop(), pc.pop()] == [1, 2, 3]
        with pytest.raises(RuntimeError, match="producer died"):
            pc.pop()
        # poisoned stream stays poisoned (re-queued like the END marker)
        with pytest.raises(RuntimeError, match="producer died"):
            pc.pop()

    def test_close_leaks_no_threads_on_early_exit(self):
        from parameter_server_tpu.utils.concurrent import ProducerConsumer

        before = threading.active_count()
        pc = ProducerConsumer(capacity=2)
        pc.start_producer(lambda: 7)  # infinite producer, tiny queue
        assert pc.pop() == 7  # consumer exits early after one item
        pc.close()
        assert _settle_threads(before) <= before

    def test_end_of_stream_still_none(self):
        from parameter_server_tpu.utils.concurrent import ProducerConsumer

        it = iter([1, 2])
        pc = ProducerConsumer(capacity=4)
        pc.start_producer(lambda: next(it, None))
        assert [pc.pop(), pc.pop(), pc.pop(), pc.pop()] == [1, 2, None, None]
        pc.close()


class TestMinibatchReaderLifecycle:
    def _batches(self, n=4):
        from parameter_server_tpu.utils.sparse import SparseBatch

        rng = np.random.default_rng(0)
        for _ in range(n):
            idx = np.sort(rng.choice(1 << 20, 32, replace=False))
            yield SparseBatch(
                y=rng.choice((-1.0, 1.0), 8).astype(np.float32),
                indptr=np.arange(0, 33, 4, dtype=np.int64),
                indices=idx.astype(np.int64),
                values=np.ones(32, np.float32),
            )

    def test_read_before_start_raises(self):
        from parameter_server_tpu.learner.sgd import MinibatchReader

        reader = MinibatchReader(batches=self._batches())
        with pytest.raises(RuntimeError, match="before start"):
            reader.read()
        with pytest.raises(RuntimeError, match="before start"):
            next(iter(reader))

    def test_start_idempotent(self):
        from parameter_server_tpu.learner.sgd import MinibatchReader

        before = threading.active_count()
        reader = MinibatchReader(batches=self._batches(3))
        reader.start()
        first_pipe = reader._pipe
        reader.start()  # second call must be a no-op
        assert reader._pipe is first_pipe
        assert len(list(reader)) == 3
        reader.close()
        assert _settle_threads(before) <= before

    def test_close_joins_and_guards(self):
        from parameter_server_tpu.learner.sgd import MinibatchReader

        before = threading.active_count()
        reader = MinibatchReader(batches=self._batches(100))
        reader.start()
        assert reader.read() is not None
        reader.close()
        assert _settle_threads(before) <= before
        with pytest.raises(RuntimeError, match="after close"):
            reader.read()
        with pytest.raises(RuntimeError, match="after close"):
            reader.start()

    def test_context_manager(self):
        from parameter_server_tpu.learner.sgd import MinibatchReader

        before = threading.active_count()
        with MinibatchReader(batches=self._batches(2)) as reader:
            assert sum(1 for _ in reader) == 2
        assert _settle_threads(before) <= before

    def test_init_filter_after_start_raises(self):
        from parameter_server_tpu.learner.sgd import MinibatchReader

        reader = MinibatchReader(batches=self._batches(1))
        reader.start()
        with pytest.raises(RuntimeError, match="after start"):
            reader.init_filter(1 << 10, 2, 1)
        reader.close()

    def test_producer_exception_reaches_read(self):
        from parameter_server_tpu.learner.sgd import MinibatchReader

        def poisoned():
            yield from self._batches(2)
            raise OSError("disk gone")

        with MinibatchReader(batches=poisoned()) as reader:
            assert reader.read() is not None
            assert reader.read() is not None
            with pytest.raises(OSError, match="disk gone"):
                reader.read()


def _prep_fixture_batches(wire):
    """(source batches, prep_fn) for one encoding over the fixture.

    libsvm carries explicit ``:1`` values; the bits/ELL hot paths need
    BINARY batches, so both arms binarize identically (values are all
    ones — dropping them is lossless)."""
    from parameter_server_tpu.apps.linear.async_sgd import (
        prep_batch,
        prep_batch_ell,
        prep_batch_ell_bits,
    )
    from parameter_server_tpu.data.stream_reader import StreamReader
    from parameter_server_tpu.parameter.parameter import KeyDirectory
    from parameter_server_tpu.utils.sparse import SparseBatch

    rows, lanes, num_slots, shards = 128, 8, 4096, 2

    def source():
        for b in StreamReader([FIXTURE], "libsvm").minibatches(rows):
            assert b.values is not None and (b.values == 1).all()
            yield SparseBatch(
                y=b.y, indptr=b.indptr, indices=b.indices, values=None
            )

    directory = KeyDirectory(num_slots, hashed=True)

    if wire == "bits":
        def prep(b):
            out = prep_batch_ell_bits(
                b, directory, shards, rows // shards, lanes, num_slots
            )
            assert out is not None  # fixture is uniform/binary/±1
            return out
    elif wire in ("i32", "u24"):
        def prep(b):
            return prep_batch_ell(
                b, directory, shards, rows // shards, lanes, num_slots,
                pack=wire == "u24",
            )
    else:  # exact COO wire
        def prep(b):
            return prep_batch(
                b, directory, shards, rows // shards, b.nnz, b.nnz,
                num_slots,
            )
    return source, prep


class TestIngestParity:
    """Pipelined ingest must yield bit-identical (batch, uniq_keys)
    sequences to serial ingest on the fixed libsvm fixture — the
    determinism contract that lets the ordered pool replace the
    trainer-thread prep."""

    @pytest.mark.parametrize("wire", ["i32", "u24", "bits", "exact"])
    def test_bit_identical_streams(self, wire):
        import dataclasses

        from parameter_server_tpu.learner.ingest import IngestPipeline
        from parameter_server_tpu.utils.localizer import count_uniq_keys

        source, prep = _prep_fixture_batches(wire)

        def with_keys(b):
            keys, _ = count_uniq_keys(b)
            return prep(b), keys

        serial = [with_keys(b) for b in source()]
        assert len(serial) == 3  # 384 fixture rows / 128

        pipe = IngestPipeline(
            source(), prep_fn=with_keys, workers=3, capacity=2,
            name=f"parity_{wire}",
        ).start()
        pipelined = list(pipe)

        from parameter_server_tpu.apps.linear.async_sgd import ELLBitsBatch
        from parameter_server_tpu.utils.bitpack import slot_bits

        assert len(pipelined) == len(serial)
        for (sp, sk), (pp, pk) in zip(serial, pipelined):
            np.testing.assert_array_equal(sk, pk)
            assert type(sp) is type(pp)
            for f in dataclasses.fields(sp):
                sv, pv = getattr(sp, f.name), getattr(pp, f.name)
                if f.name == "slots_words" and isinstance(sp, ELLBitsBatch):
                    # the bitstream buffer is np.empty by design — only
                    # the live span per shard is meaningful (bits past
                    # it are masked off by the device unpacker)
                    bits = slot_bits(4096)
                    for d in range(sv.shape[0]):
                        live = (int(sp.counts[d]) * 8 * bits + 7) // 8
                        np.testing.assert_array_equal(
                            sv[d].view(np.uint8)[:live],
                            pv[d].view(np.uint8)[:live],
                            err_msg=f"slots_words shard {d}",
                        )
                    continue
                if sv is None:
                    assert pv is None
                elif isinstance(sv, np.ndarray):
                    np.testing.assert_array_equal(sv, pv, err_msg=f.name)
                else:
                    assert sv == pv, f.name

    def test_filtered_reader_parity(self):
        """MinibatchReader with the countmin tail-filter (stateful,
        feeder-serial) matches the inline serial filter application."""
        from parameter_server_tpu.data.stream_reader import StreamReader
        from parameter_server_tpu.filter.frequency import FrequencyFilter
        from parameter_server_tpu.learner.sgd import (
            MinibatchReader,
            apply_tail_filter,
        )

        filt = FrequencyFilter(1 << 14, 2)
        serial = [
            apply_tail_filter(b, filt, 2)
            for b in StreamReader([FIXTURE], "libsvm").minibatches(64)
        ]

        reader = MinibatchReader(files=[FIXTURE], minibatch_size=64)
        reader.init_filter(1 << 14, 2, 2)
        with reader:
            piped = list(reader)

        assert len(piped) == len(serial) == 6
        for s, p in zip(serial, piped):
            np.testing.assert_array_equal(s.indices, p.indices)
            np.testing.assert_array_equal(s.indptr, p.indptr)
            np.testing.assert_array_equal(s.y, p.y)


class TestLocalizerRemapParity:
    """The inverse-based Localizer.remap_index must stay bit-identical
    to the standalone remap() on both the full and filtered key sets
    (the prep hot-path shortcut)."""

    def _batch(self, seed=0, n=64, k=9):
        from parameter_server_tpu.utils.sparse import SparseBatch

        rng = np.random.default_rng(seed)
        idx = rng.integers(0, 1 << 24, n * k).astype(np.int64)
        return SparseBatch(
            y=rng.choice((-1.0, 1.0), n).astype(np.float32),
            indptr=np.arange(0, n * k + 1, k, dtype=np.int64),
            indices=idx,
            values=rng.normal(size=n * k).astype(np.float32),
        )

    def test_full_key_remap_matches(self):
        from parameter_server_tpu.utils.localizer import Localizer, remap

        b = self._batch()
        loc = Localizer()
        keys, _ = loc.count_uniq_index(b)
        fast = loc.remap_index(keys)
        slow = remap(b, keys)
        np.testing.assert_array_equal(fast.indices, slow.indices)
        np.testing.assert_array_equal(fast.indptr, slow.indptr)
        np.testing.assert_array_equal(fast.values, slow.values)
        assert fast.num_cols == slow.num_cols

    def test_filtered_remap_matches(self):
        from parameter_server_tpu.utils.localizer import Localizer, remap

        b = self._batch(seed=3)
        loc = Localizer()
        keys, _ = loc.count_uniq_index(b)
        keep = keys[::3]  # drop two thirds
        fast = loc.remap_index(keep)
        slow = remap(b, keep)
        np.testing.assert_array_equal(fast.indices, slow.indices)
        np.testing.assert_array_equal(fast.indptr, slow.indptr)
        np.testing.assert_array_equal(fast.values, slow.values)
        assert fast.num_cols == slow.num_cols


class TestIngestTelemetry:
    def test_stage_metrics_recorded(self):
        from parameter_server_tpu.learner.ingest import IngestPipeline
        from parameter_server_tpu.telemetry import registry as treg

        if not treg.enabled():
            pytest.skip("telemetry disabled")
        reg = treg.default_registry()
        base = reg.snapshot().get("ps_ingest_examples_total", {})
        base_n = base.get("values", {}).get("pipeline=tel_test", 0.0)

        source, _ = _prep_fixture_batches("i32")

        # no prep workers: batch-shaped items flow through and count
        pipe = IngestPipeline(source(), capacity=2, name="tel_test").start()
        n = sum(b.n for b in pipe)
        assert n == 384

        snap = reg.snapshot()
        total = snap["ps_ingest_examples_total"]["values"]["pipeline=tel_test"]
        assert total - base_n == 384
        stages = set(snap["ps_ingest_stage_seconds"]["values"])
        assert "stage=read,pipeline=tel_test" in stages
        assert "queue=tel_test" in snap["ps_ingest_wait_seconds"]["values"]
        assert "queue=tel_test" in snap["ps_ingest_queue_depth"]["values"]

    def test_instruments_in_catalog(self):
        """ps_ingest_* is part of install_all (metrics-lint surface)."""
        from parameter_server_tpu.telemetry.instruments import install_all
        from parameter_server_tpu.telemetry.registry import MetricsRegistry

        names = set(install_all(MetricsRegistry()))
        assert {
            "ps_ingest_stage_seconds",
            "ps_ingest_wait_seconds",
            "ps_ingest_queue_depth",
            "ps_ingest_examples_total",
            "ps_ingest_batches_total",
            "ps_ingest_uploaded_bytes_total",
        } <= names


class TestDeviceUploader:
    def test_order_exceptions_and_bytes(self, mesh8):
        import jax

        from parameter_server_tpu.apps.linear.async_sgd import DeviceUploader
        from parameter_server_tpu.telemetry import registry as treg

        reg = treg.default_registry() if treg.enabled() else None
        if reg is not None:
            snap = reg.snapshot().get("ps_ingest_uploaded_bytes_total", {})
            before = snap.get("values", {}).get("", 0.0)

        from parameter_server_tpu.apps.linear.async_sgd import HashedBatch

        def mk(i):
            return HashedBatch(
                y=np.full((1, 4), float(i), np.float32),
                mask=np.ones((1, 4), np.float32),
                rows=np.zeros((1, 4), np.int32),
                slots=np.zeros((1, 4), np.int32),
                vals=np.ones((1, 4), np.float32),
            )

        items = [(mk(i), 1) for i in range(8)]
        per_nbytes = sum(
            leaf.nbytes for leaf in jax.tree.leaves(items[0][0])
        )
        up = DeviceUploader(iter(items), lambda h: jax.device_put(h.y))
        got = [(float(np.asarray(a)[0, 0]), n) for a, n in up]
        assert got == [(float(i), 1) for i in range(8)]
        up.close()

        if reg is not None:
            snap = reg.snapshot()["ps_ingest_uploaded_bytes_total"]
            after = snap["values"][""]
            assert after - before == 8 * per_nbytes

        def poisoned():
            yield items[0]
            raise RuntimeError("prep died")

        up = DeviceUploader(poisoned(), lambda h: jax.device_put(h.y))
        it = iter(up)
        next(it)
        with pytest.raises(RuntimeError, match="prep died"):
            next(it)
        up.close()

"""Unit tests for utils — mirrors reference gtest coverage in src/test/
(common_test, bloom_filter_test, countmin_test, localizer_test,
parallel_ordered_match_test, sparse_matrix_test, assign_op_test)."""

import io

import numpy as np
import pytest

from parameter_server_tpu.utils import crc32c, evaluation, recordio
from parameter_server_tpu.utils.assign_op import AssignOp, apply_op
from parameter_server_tpu.utils.bitmap import Bitmap
from parameter_server_tpu.utils.localizer import Localizer, count_uniq_keys, remap
from parameter_server_tpu.utils.ordered_match import ordered_match
from parameter_server_tpu.utils.range import Range
from parameter_server_tpu.utils.sketch import BloomFilter, CountMin
from parameter_server_tpu.utils.sparse import SparseBatch, from_dense, random_sparse


class TestRange:
    def test_even_divide(self):
        r = Range(0, 10)
        parts = r.divide(3)
        assert parts[0] == Range(0, 3)
        assert parts[1] == Range(3, 6)
        assert parts[2] == Range(6, 10)
        assert sum(p.size() for p in parts) == 10

    def test_intersection(self):
        assert Range(0, 5).intersection(Range(3, 9)) == Range(3, 5)
        assert Range(0, 2).intersection(Range(3, 9)).empty()

    def test_contains(self):
        assert 3 in Range(0, 5)
        assert 5 not in Range(0, 5)


class TestSparse:
    def test_from_dense_roundtrip(self, rng):
        x = (rng.random((7, 11)) < 0.3) * rng.normal(size=(7, 11))
        y = np.sign(rng.normal(size=7))
        b = from_dense(x.astype(np.float32), y)
        np.testing.assert_allclose(b.to_dense(), x, rtol=1e-6)

    def test_csc_matches_dense(self, rng):
        b = random_sparse(50, 31, 4, seed=1)
        dense = b.to_dense()
        csc = b.to_csc()
        for j in range(b.cols):
            rows, vals = csc.col(j)
            col = np.zeros(b.n, dtype=np.float32)
            if vals is None:
                col[rows] = 1.0
            else:
                np.add.at(col, rows, vals)
            np.testing.assert_allclose(col, dense[:, j], rtol=1e-5)

    def test_pad_device(self):
        b = random_sparse(10, 20, 3, seed=2)
        pb = b.pad_device(nnz_pad=64, rows_pad=16)
        assert pb.rows_pad == 16 and pb.nnz_pad == 64
        assert pb.row_mask.sum() == 10
        # padded entries point at sentinel col with zero value
        assert (pb.cols[b.nnz :] == b.cols).all()
        assert (pb.vals[b.nnz :] == 0).all()
        # matvec through padding equals dense matvec
        w = np.random.default_rng(0).normal(size=b.cols + 1).astype(np.float32)
        w[-1] = 123.0  # sentinel weight must not matter (value=0)
        xw_pad = np.zeros(16, dtype=np.float32)
        np.add.at(xw_pad, pb.rows, pb.vals * w[pb.cols])
        np.testing.assert_allclose(xw_pad[:10], b.to_dense() @ w[:-1], rtol=1e-4)

    def test_slice_rows(self):
        b = random_sparse(10, 20, 3, seed=3)
        s = b.slice_rows(2, 5)
        np.testing.assert_allclose(s.to_dense(), b.to_dense()[2:5], rtol=1e-6)


class TestLocalizer:
    def test_count_uniq(self):
        b = SparseBatch(
            y=np.ones(2, np.float32),
            indptr=np.array([0, 3, 5]),
            indices=np.array([9, 4, 9, 4, 1]),
            values=np.arange(5, dtype=np.float32),
        )
        keys, cnt = count_uniq_keys(b)
        np.testing.assert_array_equal(keys, [1, 4, 9])
        np.testing.assert_array_equal(cnt, [1, 2, 2])

    def test_remap_keeps_subset(self):
        b = SparseBatch(
            y=np.ones(2, np.float32),
            indptr=np.array([0, 3, 5]),
            indices=np.array([9, 4, 9, 4, 1]),
            values=np.arange(5, dtype=np.float32),
        )
        out = remap(b, np.array([4, 9]))
        assert out.cols == 2
        np.testing.assert_array_equal(out.indices, [1, 0, 1, 0])  # key9->1, key4->0
        np.testing.assert_array_equal(out.indptr, [0, 3, 4])
        np.testing.assert_array_equal(out.values, [0, 1, 2, 3])  # key1 dropped

    def test_localizer_protocol(self):
        b = random_sparse(20, 50, 5, seed=4)
        loc = Localizer()
        keys, cnt = loc.count_uniq_index(b)
        out = loc.remap_index(keys)
        # full keep: dense reconstruction must match with remapped columns
        np.testing.assert_allclose(
            out.to_dense(), b.to_dense()[:, keys.astype(int)], rtol=1e-6
        )


class TestOrderedMatch:
    def test_assign_and_plus(self):
        dst_k = np.array([1, 3, 5, 7])
        dst_v = np.zeros(4, dtype=np.float32)
        src_k = np.array([3, 5, 9])
        src_v = np.array([30.0, 50.0, 90.0], dtype=np.float32)
        n = ordered_match(dst_k, dst_v, src_k, src_v)
        assert n == 2
        np.testing.assert_array_equal(dst_v, [0, 30, 50, 0])
        n = ordered_match(dst_k, dst_v, src_k, src_v, op=AssignOp.PLUS)
        np.testing.assert_array_equal(dst_v, [0, 60, 100, 0])

    def test_width_k(self):
        dst_k = np.array([2, 4])
        dst_v = np.zeros((2, 3), dtype=np.float32)
        src_k = np.array([4])
        src_v = np.array([[1.0, 2.0, 3.0]], dtype=np.float32)
        ordered_match(dst_k, dst_v, src_k, src_v, k=3)
        np.testing.assert_array_equal(dst_v[1], [1, 2, 3])


class TestSketches:
    def test_bloom_no_false_negatives(self, rng):
        bf = BloomFilter(1 << 16, 3)
        keys = rng.integers(0, 1 << 60, size=1000).astype(np.uint64)
        bf.insert(keys)
        assert bf.query(keys).all()

    def test_bloom_low_false_positive(self, rng):
        bf = BloomFilter(1 << 18, 3)
        keys = rng.integers(0, 1 << 60, size=1000).astype(np.uint64)
        bf.insert(keys)
        other = rng.integers(1 << 61, 1 << 62, size=10000).astype(np.uint64)
        assert bf.query(other).mean() < 0.01

    def test_countmin_upper_bound(self, rng):
        cm = CountMin(1 << 16, 3)
        keys = rng.integers(0, 1 << 40, size=500).astype(np.uint64)
        cm.insert(keys, 5)
        est = cm.query(keys)
        assert (est >= 5).all()  # never underestimates
        fresh = rng.integers(1 << 41, 1 << 42, size=500).astype(np.uint64)
        assert cm.query(fresh).mean() < 1.0


class TestEvaluation:
    def test_auc_perfect_and_random(self):
        y = np.array([1, 1, -1, -1], dtype=np.float32)
        assert evaluation.auc(y, np.array([2.0, 1.5, -1.0, -2.0])) == 1.0
        assert evaluation.auc(y, np.array([-2.0, -1.5, 1.0, 2.0])) == 0.0
        assert abs(evaluation.auc(y, np.zeros(4)) - 0.5) < 1e-9

    def test_accuracy(self):
        y = np.array([1, -1, 1, -1], dtype=np.float32)
        assert evaluation.accuracy(y, np.array([1.0, -1.0, -1.0, 1.0])) == 0.5

    def test_logloss(self):
        y = np.array([1.0, -1.0])
        xw = np.array([100.0, -100.0])
        assert evaluation.logloss(y, xw) < 1e-6


class TestCrcRecordio:
    def test_crc_known_value(self):
        # crc32c("123456789") = 0xE3069283 (Castagnoli standard test vector)
        assert crc32c.value(b"123456789") == 0xE3069283

    def test_mask_roundtrip(self):
        c = crc32c.value(b"hello")
        assert crc32c.unmask(crc32c.masked(c)) == c

    def test_recordio_roundtrip(self):
        buf = io.BytesIO()
        w = recordio.RecordWriter(buf)
        recs = [b"alpha", b"", b"x" * 1000]
        for r in recs:
            w.write_record(r)
        buf.seek(0)
        assert list(recordio.RecordReader(buf)) == recs

    def test_recordio_detects_corruption(self):
        buf = io.BytesIO()
        recordio.RecordWriter(buf).write_record(b"payload")
        data = bytearray(buf.getvalue())
        data[-1] ^= 0xFF
        with pytest.raises(IOError):
            recordio.RecordReader(io.BytesIO(bytes(data))).read_record()


class TestBitmapAssign:
    def test_bitmap(self):
        bm = Bitmap(10, True)
        assert bm.nnz() == 10
        bm.clear(3)
        assert not bm.test(3) and bm.nnz() == 9
        bm.fill(False)
        assert bm.nnz() == 0

    def test_assign_ops(self):
        assert apply_op(AssignOp.PLUS, 2.0, 3.0) == 5.0
        assert apply_op(AssignOp.ASSIGN, 2.0, 3.0) == 3.0
        assert apply_op(AssignOp.TIMES, 2.0, 3.0) == 6.0


def test_hash_slots_batchsize_invariant():
    """C++ fused path (large batches) and NumPy fallback (small) must map
    identical keys to identical slots — slot assignment can never depend on
    batch size or native-library availability."""
    from parameter_server_tpu.utils.murmur import hash_slots

    keys = np.random.default_rng(3).integers(0, 1 << 62, size=8192).astype(np.int64)
    big = hash_slots(keys, 1 << 20)
    small = np.concatenate([hash_slots(keys[i : i + 64], 1 << 20) for i in range(0, 8192, 64)])
    np.testing.assert_array_equal(big, small)
    assert big.dtype == np.int32 and big.min() >= 0 and big.max() < (1 << 20)
    # non-pow2 table size exercises the modulo path
    np.testing.assert_array_equal(
        hash_slots(keys, 1_000_003),
        np.concatenate([hash_slots(keys[:4096], 1_000_003), hash_slots(keys[4096:], 1_000_003)]),
    )


class TestBitpack:
    """utils/bitpack: bitstream wire format (pack host-side, unpack in jit)."""

    def test_cpp_matches_numpy(self, rng):
        from parameter_server_tpu.utils import bitpack

        for bits in (7, 22, 23, 24):
            vals = rng.integers(0, 1 << bits, 9000).astype(np.int32)
            np.testing.assert_array_equal(
                bitpack.pack_bits(vals, bits), bitpack.pack_bits_np(vals, bits)
            )

    def test_fused_hash_pack_matches_two_pass(self, rng):
        from parameter_server_tpu.utils import bitpack
        from parameter_server_tpu.utils.murmur import hash_slots

        keys = rng.integers(0, 1 << 62, 50000).astype(np.uint64)
        num_slots = 1 << 18
        want = bitpack.pack_bits_np(hash_slots(keys, num_slots), 18)
        np.testing.assert_array_equal(
            bitpack.hash_slots_packed(keys, num_slots, 18), want
        )

    def test_device_unpack_roundtrip(self, rng):
        import jax

        from parameter_server_tpu.utils import bitpack

        for bits in (13, 22):
            vals = rng.integers(0, 1 << bits, 4096 * 3 + 5).astype(np.int32)
            words = bitpack.stream_to_words(
                bitpack.pack_bits(vals, bits), vals.size, bits
            )
            out = jax.jit(
                lambda w, n=vals.size, b=bits: bitpack.unpack_bits(w, n, b)
            )(words)
            np.testing.assert_array_equal(np.asarray(out), vals)

    def test_tiled_unpack_matches_gather_all_widths(self, rng):
        """The gather-free tiled unpack (the production decode path:
        rows_pad*lanes is always period-aligned) must be bit-exact with
        the general two-gather form at EVERY wire width, including the
        carry lanes that straddle word boundaries."""
        import jax

        from parameter_server_tpu.utils import bitpack

        for bits in range(1, 32):
            v_per, _ = bitpack._bit_period(bits)
            for nper in (1, 7):
                n = v_per * nper
                vals = rng.integers(0, 1 << bits, n, endpoint=False)
                vals = vals.astype(np.int64).astype(np.int32)
                words = bitpack.stream_to_words(
                    bitpack.pack_bits_np(vals, bits), n, bits
                )
                tiled = jax.jit(
                    lambda w, n=n, b=bits: bitpack._unpack_bits_tiled(
                        w, n, b
                    )
                )(words)
                gath = jax.jit(
                    lambda w, n=n, b=bits: bitpack._unpack_bits_gather(
                        w, n, b
                    )
                )(words)
                np.testing.assert_array_equal(np.asarray(tiled), vals)
                np.testing.assert_array_equal(
                    np.asarray(tiled), np.asarray(gath)
                )

    def test_sign_bits_roundtrip(self, rng):
        import jax

        from parameter_server_tpu.utils import bitpack

        y = np.where(rng.random(1000) > 0.5, 1.0, -1.0).astype(np.float32)
        packed = np.packbits(y > 0, bitorder="little")
        out = jax.jit(lambda b: bitpack.unpack_sign_bits(b, y.size))(packed)
        np.testing.assert_array_equal(np.asarray(out), y)


class TestMurmur3:
    """Real MurmurHash3 x64 128 (ref util/murmurhash3.cc; criteo keys)."""

    def test_python_matches_cpp(self):
        import parameter_server_tpu.cpp as cpp
        from parameter_server_tpu.utils.murmur import murmur3_x64_128

        tests = [b"", b"a", b"hello", b"0a1b2c3d", b"x" * 15, b"y" * 16, b"z" * 33]
        want = [murmur3_x64_128(t, 512927377) for t in tests]
        real = cpp.native
        cpp.native = lambda: None
        try:
            got = [murmur3_x64_128(t, 512927377) for t in tests]
        finally:
            cpp.native = real
        assert want == got

    def test_deterministic_and_seeded(self):
        from parameter_server_tpu.utils.murmur import murmur3_x64_128

        a = murmur3_x64_128(b"token", 512927377)
        assert a == murmur3_x64_128(b"token", 512927377)
        assert a != murmur3_x64_128(b"token", 1)
        assert a != murmur3_x64_128(b"tokeN", 512927377)


class TestTraceSummary:
    def test_summarize_synthetic_chrome_trace(self, tmp_path):
        """summarize_trace buckets device-track complete events by
        named-scope phase (ps_* prefixes reach HLO op metadata) and
        ignores host tracks; no trace -> None."""
        import gzip
        import json

        from parameter_server_tpu.utils.profiling import summarize_trace

        assert summarize_trace(str(tmp_path)) is None

        events = [
            {"ph": "M", "pid": 1, "name": "process_name",
             "args": {"name": "/device:TPU:0"}},
            {"ph": "M", "pid": 2, "name": "process_name",
             "args": {"name": "python host threads"}},
            # device tracks: only the op-level tid counts — the
            # module-span tid covers the sum of its ops and would
            # double device_ms if included
            {"ph": "M", "pid": 1, "tid": 1, "name": "thread_name",
             "args": {"name": "XLA Ops"}},
            {"ph": "M", "pid": 1, "tid": 2, "name": "thread_name",
             "args": {"name": "XLA Modules"}},
            # device ops: args.name carries the jax.named_scope path
            {"ph": "X", "pid": 1, "tid": 1, "ts": 0, "dur": 1500,
             "name": "fusion.1",
             "args": {"name": "jit(step)/ps_pull/gather"}},
            {"ph": "X", "pid": 1, "tid": 1, "ts": 1500, "dur": 2500,
             "name": "fusion.2",
             "args": {"name": "jit(step)/ps_update/while"}},
            {"ph": "X", "pid": 1, "tid": 1, "ts": 4000, "dur": 500,
             "name": "copy.3", "args": {}},
            # module aggregate span: must NOT count
            {"ph": "X", "pid": 1, "tid": 2, "ts": 0, "dur": 4500,
             "name": "jit_mini_step", "args": {}},
            # host event on another track: must not count
            {"ph": "X", "pid": 2, "tid": 9, "ts": 0, "dur": 9e6,
             "name": "$main.py:1 run", "args": {}},
        ]
        run = tmp_path / "plugins" / "profile" / "run1"
        run.mkdir(parents=True)
        with gzip.open(run / "host.trace.json.gz", "wt") as f:
            json.dump({"traceEvents": events}, f)

        s = summarize_trace(str(tmp_path))
        assert s is not None
        assert s["device_ms"] == 4.5
        assert s["phases"]["ps_pull"] == 1.5
        assert s["phases"]["ps_update"] == 2.5
        assert s["phases"]["other"] == 0.5
        names = [o["name"] for o in s["top_ops"]]
        assert "fusion.2" in names
        assert "$main.py:1 run" not in names
        assert "jit_mini_step" not in names

    def test_nested_control_flow_spans_credit_self_time_only(
        self, tmp_path
    ):
        """A while/scan wrapper span on the op track NESTS its body ops
        as child events; the parent must be credited only its self time
        (dur minus children) or device_ms double-counts the scan body
        into a phantom 'other' bucket (observed live: while.3 248ms
        over 8 scan steps re-counted the whole step)."""
        import gzip
        import json

        from parameter_server_tpu.utils.profiling import summarize_trace

        events = [
            {"ph": "M", "pid": 1, "name": "process_name",
             "args": {"name": "/device:TPU:0"}},
            {"ph": "M", "pid": 1, "tid": 1, "name": "thread_name",
             "args": {"name": "XLA Ops"}},
            # parent scan wrapper: 10ms, of which 9ms is children
            {"ph": "X", "pid": 1, "tid": 1, "ts": 0, "dur": 10000,
             "name": "while.3", "args": {}},
            # two body iterations: a pull fusion and a nested update,
            # the update itself containing a grandchild kernel
            {"ph": "X", "pid": 1, "tid": 1, "ts": 500, "dur": 4000,
             "name": "fusion.44",
             "args": {"name": "jit(step)/ps_pull/gather"}},
            {"ph": "X", "pid": 1, "tid": 1, "ts": 5000, "dur": 5000,
             "name": "fusion.48",
             "args": {"name": "jit(step)/ps_update/scatter"}},
            {"ph": "X", "pid": 1, "tid": 1, "ts": 6000, "dur": 2000,
             "name": "ftrl_update.7",
             "args": {"name": "jit(step)/ps_update/custom_call"}},
            # op after the scan, top level
            {"ph": "X", "pid": 1, "tid": 1, "ts": 10000, "dur": 1000,
             "name": "copy.9", "args": {}},
        ]
        run = tmp_path / "plugins" / "profile" / "r"
        run.mkdir(parents=True)
        with gzip.open(run / "t.trace.json.gz", "wt") as f:
            json.dump({"traceEvents": events}, f)

        s = summarize_trace(str(tmp_path))
        assert s is not None
        # total = 10ms scan + 1ms copy, NOT 10+9+1
        assert s["device_ms"] == 11.0
        assert s["phases"]["ps_pull"] == 4.0
        # update = 5ms span, of which grandchild 2ms — both ps_update
        assert s["phases"]["ps_update"] == 5.0
        # other = scan self (1ms) + copy (1ms)
        assert s["phases"]["other"] == 2.0
        ops = {o["name"]: o["ms"] for o in s["top_ops"]}
        assert ops["while.3"] == 1.0
        assert ops["fusion.48"] == 3.0
        assert ops["ftrl_update.7"] == 2.0

    def test_summarize_newest_run_only_and_host_only_none(self, tmp_path):
        """A reused profile dir accumulates runs — only the newest
        plugins/profile/<ts> run is summed; a trace with no
        identifiable device track returns None (host wall-clock must
        never be reported as device time)."""
        import gzip
        import json
        import os
        import time as _t

        from parameter_server_tpu.utils.profiling import summarize_trace

        def write_run(name, dur, device=True):
            run = tmp_path / "plugins" / "profile" / name
            run.mkdir(parents=True)
            pname = "/device:TPU:0" if device else "host python"
            events = [
                {"ph": "M", "pid": 1, "name": "process_name",
                 "args": {"name": pname}},
                {"ph": "X", "pid": 1, "tid": 1, "ts": 0, "dur": dur,
                 "name": "fusion.9",
                 "args": {"name": "jit(f)/ps_compute/dot"}},
            ]
            with gzip.open(run / "t.trace.json.gz", "wt") as f:
                json.dump({"traceEvents": events}, f)
            return run

        old = write_run("run_old", 7000)
        _t.sleep(0.05)
        write_run("run_new", 2000)
        # age the old dir so mtime ordering is unambiguous
        os.utime(old, (1, 1))
        s = summarize_trace(str(tmp_path))
        assert s is not None and s["device_ms"] == 2.0

        host_only = tmp_path / "hostonly"
        write_host = host_only / "plugins" / "profile" / "r"
        write_host.mkdir(parents=True)
        events = [
            {"ph": "M", "pid": 5, "name": "process_name",
             "args": {"name": "python host threads"}},
            {"ph": "X", "pid": 5, "tid": 1, "ts": 0, "dur": 5e6,
             "name": "run", "args": {}},
        ]
        with gzip.open(write_host / "t.trace.json.gz", "wt") as f:
            json.dump({"traceEvents": events}, f)
        assert summarize_trace(str(host_only)) is None


class TestIterOnThread:
    def test_items_and_order(self):
        from parameter_server_tpu.utils.concurrent import iter_on_thread

        assert list(iter_on_thread(iter(range(20)), maxsize=3)) == list(
            range(20)
        )

    def test_producer_exception_propagates(self):
        from parameter_server_tpu.utils.concurrent import iter_on_thread

        def boom():
            yield 1
            raise ValueError("dead")

        it = iter_on_thread(boom(), maxsize=2)
        assert next(it) == 1
        with pytest.raises(ValueError, match="dead"):
            list(it)

    def test_abandonment_stops_and_joins_producer(self):
        import threading
        import time

        from parameter_server_tpu.utils.concurrent import iter_on_thread

        alive = {"n": 0}
        started = threading.Event()

        def slow():
            alive["n"] += 1
            started.wait(5)
            for i in range(1000):
                yield i
            # unreachable when abandoned early

        before = threading.active_count()
        it = iter_on_thread(slow(), maxsize=1)
        started.set()
        next(it)
        it.close()  # consumer abandons; producer must stop promptly
        t0 = time.time()
        while threading.active_count() > before and time.time() - t0 < 5:
            time.sleep(0.05)
        assert threading.active_count() <= before

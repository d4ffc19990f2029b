"""Compact host→device wire (learner/wire.py + ops/wire_codec.py).

Contract under test — the PR's wire counterpart of PR 3's ingest
determinism contract:

1. the default ``exact`` mode is BIT-IDENTICAL: every decoded array
   equals the raw wire's, dtype included, and whole training
   trajectories match bit-for-bit (raw vs encoded, serial vs
   pipelined-with-cache);
2. quantized modes stay within the configured logloss-parity bound;
3. encode never guesses: a batch outside a verified encoding domain
   falls back to the raw wire (None), never to wrong bytes;
4. stateful wire stages stay OFF the trainer thread (the
   stateless-or-feeder rule): encode runs on the prep pool,
   UploadCache on the uploader thread, and the cache is single-owner
   by assertion.
"""

import dataclasses
import os
import threading

import numpy as np
import pytest

from parameter_server_tpu.apps.linear.async_sgd import (
    AsyncSGDWorker,
    PreppedBatch,
    prep_batch,
    prep_batch_shared,
)
from parameter_server_tpu.apps.linear.config import (
    Config,
    LearningRateConfig,
    PenaltyConfig,
    SGDConfig,
)
from parameter_server_tpu.learner import wire
from parameter_server_tpu.ops import wire_codec as wc
from parameter_server_tpu.parameter.parameter import KeyDirectory
from parameter_server_tpu.system.postoffice import Postoffice
from parameter_server_tpu.utils.sparse import SparseBatch, random_sparse

FIXTURE = os.path.join(os.path.dirname(__file__), "data", "wire_parity.libsvm")

PREPPED_FIELDS = [f.name for f in dataclasses.fields(PreppedBatch)]


def fixture_batches(binary: bool = False, minibatch: int = 32):
    from parameter_server_tpu.data.stream_reader import StreamReader

    out = []
    for b in StreamReader([FIXTURE], "libsvm").minibatches(minibatch):
        if binary:
            b = SparseBatch(y=b.y, indptr=b.indptr, indices=b.indices)
        out.append(b)
    return out


def synth_batch(n=64, lanes=8, seed=0, binary=True):
    rng = np.random.default_rng(seed)
    keys = rng.integers(0, 1 << 31, (n, lanes)).astype(np.int64)
    indptr = np.arange(0, n * lanes + 1, lanes)
    y = rng.choice((-1.0, 1.0), n).astype(np.float32)
    vals = (
        None if binary else (rng.random(n * lanes) + 0.5).astype(np.float32)
    )
    return SparseBatch(y=y, indptr=indptr, indices=keys.ravel(), values=vals)


def assert_batches_identical(raw: PreppedBatch, dec: tuple, skip=()):
    for name, arr in zip(PREPPED_FIELDS, dec):
        if name in skip:
            continue
        want = np.asarray(getattr(raw, name))
        got = np.asarray(arr)
        assert want.dtype == got.dtype, (name, want.dtype, got.dtype)
        np.testing.assert_array_equal(want, got, err_msg=name)


class TestDecodeOps:
    """Each decode op against its numpy ground truth."""

    def test_row_ids_general(self):
        counts = np.array([3, 0, 2, 0, 0, 4, 1, 0], np.uint8)
        nnz = int(counts.sum())
        nnz_pad = 16
        want = np.zeros(nnz_pad, np.int32)
        want[:nnz] = np.repeat(np.arange(8), counts)
        got = np.asarray(wc.decode_row_ids(counts, nnz, nnz_pad))
        np.testing.assert_array_equal(got, want)

    def test_row_ids_trailing_empty_and_full(self):
        # trailing all-empty rows drop their start markers at exactly
        # nnz == nnz_pad — mode='drop' must not wrap them around
        counts = np.array([4, 4, 0, 0], np.uint8)
        got = np.asarray(wc.decode_row_ids(counts, 8, 8))
        np.testing.assert_array_equal(
            got, np.repeat(np.arange(2), 4).astype(np.int32)
        )

    def test_row_ids_empty_batch(self):
        got = np.asarray(
            wc.decode_row_ids(np.zeros(4, np.uint8), 0, 8)
        )
        np.testing.assert_array_equal(got, np.zeros(8, np.int32))

    def test_sorted_deltas(self):
        uslots = np.array([5, 9, 40, 41, 1000], np.int64)
        deltas = np.diff(uslots, prepend=0).astype(np.uint16)
        padded = np.concatenate([deltas, np.zeros(3, np.uint16)])
        got = np.asarray(wc.decode_sorted_deltas(padded, 5, 4096))
        np.testing.assert_array_equal(
            got, np.concatenate([uslots, [4096] * 3]).astype(np.int32)
        )

    def test_sign_labels_pad_is_zero(self):
        y = np.array([1, -1, -1, 1, 0, 0], np.float32)
        bits = np.packbits(y > 0, bitorder="little")
        got = np.asarray(wc.decode_sign_labels(bits, 4, 6))
        np.testing.assert_array_equal(got, np.array(
            [1, -1, -1, 1, 0, 0], np.float32))

    def test_mask_and_binary_vals(self):
        np.testing.assert_array_equal(
            np.asarray(wc.decode_mask(3, 5)),
            np.array([1, 1, 1, 0, 0], np.float32),
        )
        np.testing.assert_array_equal(
            np.asarray(wc.decode_binary_vals(2, 4)),
            np.array([1, 1, 0, 0], np.float32),
        )

    def test_u24(self):
        import jax.numpy as jnp

        from parameter_server_tpu.apps.linear.async_sgd import pack_u24

        v = np.array([0, 1, 255, 256, (1 << 24) - 1], np.int32)
        np.testing.assert_array_equal(
            np.asarray(wc.decode_u24(jnp.asarray(pack_u24(v)))), v
        )


class TestEncodeExactParity:
    NUM_SLOTS = 1 << 18

    def _directory(self):
        return KeyDirectory(self.NUM_SLOTS, hashed=True)

    def _prep(self, b, shared=False):
        d = self._directory()
        rows_pad = 64
        nnz_pad = rows_pad * 16
        if shared:
            return prep_batch_shared(
                b, d, 2, rows_pad, nnz_pad, 1024, self.NUM_SLOTS
            )
        return prep_batch(
            b, d, 2, rows_pad, nnz_pad, nnz_pad, self.NUM_SLOTS
        )

    @pytest.mark.parametrize("shared", [False, True])
    def test_binary_bit_identical(self, shared):
        for b in fixture_batches(binary=True):
            raw = self._prep(b, shared)
            enc = wire.encode_exact(raw, self.NUM_SLOTS)
            assert enc is not None
            assert enc.vals_mode == "binary"  # value stream elided
            # prep_batch_shared's uslots are np.unique output → the
            # delta wire; prep_batch hashes sorted KEYS → bit-packed
            assert enc.uslots_delta == shared
            dec = wire.decode_exact_host(enc, self.NUM_SLOTS)
            assert_batches_identical(raw, dec)

    @pytest.mark.parametrize("shared", [False, True])
    def test_valued_exact_bit_identical(self, shared):
        for b in fixture_batches(binary=False):
            raw = self._prep(b, shared)
            enc = wire.encode_exact(raw, self.NUM_SLOTS, mode="exact")
            assert enc is not None
            dec = wire.decode_exact_host(enc, self.NUM_SLOTS)
            assert_batches_identical(raw, dec)

    def test_ragged_rows_bit_identical(self):
        # the fixture is ragged (3-10 features/row): row_counts +
        # decode_row_ids must reproduce the repeat structure exactly —
        # covered above; here also a batch with EMPTY rows
        rng = np.random.default_rng(3)
        counts = rng.integers(0, 5, 40)
        counts[[3, 7, 39]] = 0
        indptr = np.concatenate([[0], np.cumsum(counts)])
        idx = rng.integers(0, 1 << 30, indptr[-1]).astype(np.int64)
        b = SparseBatch(
            y=rng.choice((-1.0, 1.0), 40).astype(np.float32),
            indptr=indptr, indices=idx,
        )
        raw = self._prep(b)
        enc = wire.encode_exact(raw, self.NUM_SLOTS)
        dec = wire.decode_exact_host(enc, self.NUM_SLOTS)
        assert_batches_identical(raw, dec)

    def test_regression_labels_keep_f32(self):
        b = synth_batch(binary=False, seed=5)
        b.y[:] = np.linspace(-2, 2, b.n).astype(np.float32)
        raw = self._prep(b)
        enc = wire.encode_exact(raw, self.NUM_SLOTS)
        assert enc is not None and not enc.y_sign  # no silent sign collapse
        dec = wire.decode_exact_host(enc, self.NUM_SLOTS)
        assert_batches_identical(raw, dec)

    @pytest.mark.parametrize("mode,tol", [
        ("int8", 1.0 / 254), ("u16", 1.0 / 65534), ("bf16", 1.0 / 128),
    ])
    def test_quantized_value_error_bound(self, mode, tol):
        b = synth_batch(binary=False, seed=6)
        raw = self._prep(b)
        enc = wire.encode_exact(raw, self.NUM_SLOTS, mode=mode)
        assert enc.vals_mode == mode
        dec = wire.decode_exact_host(enc, self.NUM_SLOTS)
        assert_batches_identical(raw, dec, skip=("vals",))
        v_raw = np.asarray(raw.vals)
        v_dec = np.asarray(dec[PREPPED_FIELDS.index("vals")])
        span = v_raw.max() - v_raw.min()
        rel = np.abs(v_dec - v_raw).max() / max(span, 1e-9)
        assert rel <= 2 * tol, (mode, rel)

    def test_quantized_padding_decodes_to_exact_zero(self):
        # regression: every padding entry carries rows=0/ucols=0, so a
        # dequantized-zero code (0±step noise, and with lo<0 never
        # exactly 0) would scatter-add a padding-sized bias into
        # example 0 and uslots[0] — decode must mask past nnz
        b = synth_batch(n=40, binary=False, seed=21)
        b.values[:] = b.values - 1.0  # span negatives: lo < 0
        raw = self._prep(b)  # rows_pad 64 ⇒ plenty of padding
        enc = wire.encode_exact(raw, self.NUM_SLOTS, mode="int8")
        dec = wire.decode_exact_host(enc, self.NUM_SLOTS)
        v_dec = np.asarray(dec[PREPPED_FIELDS.index("vals")])
        nnz = np.asarray(enc.nnz)
        for d in range(v_dec.shape[0]):
            assert (v_dec[d, nnz[d]:] == 0.0).all()

    def test_quantized_scale_from_live_entries_only(self):
        # all-positive values: [lo, hi] must come from the live slice,
        # not be dragged to 0 by the zero padding (wasted resolution)
        b = synth_batch(n=40, binary=False, seed=22)  # vals in [0.5, 1.5)
        raw = self._prep(b)
        enc = wire.encode_exact(raw, self.NUM_SLOTS, mode="int8")
        assert np.asarray(enc.vals_lo).min() >= 0.5

    def test_quantized_encode_deterministic(self):
        # stochastic rounding must be content-keyed (pool workers may
        # encode in any order): same batch → same bytes, always
        b = synth_batch(binary=False, seed=7)
        raw = self._prep(b)
        e1 = wire.encode_exact(raw, self.NUM_SLOTS, mode="int8")
        e2 = wire.encode_exact(raw, self.NUM_SLOTS, mode="int8")
        np.testing.assert_array_equal(e1.vals, e2.vals)

    def test_domain_violation_falls_back(self):
        raw = self._prep(synth_batch())
        # a hole in the mask is outside the count-coded domain
        bad_mask = np.asarray(raw.mask).copy()
        bad_mask[0, 1] = 0.0
        bad = dataclasses.replace(raw, mask=bad_mask)
        assert wire.encode_exact(bad, self.NUM_SLOTS) is None
        # non-sentinel tail in uslots likewise
        bad_us = np.asarray(raw.uslots).copy()
        bad_us[0, -1] = 7
        bad2 = dataclasses.replace(raw, uslots=bad_us)
        assert wire.encode_exact(bad2, self.NUM_SLOTS) is None

    def test_unknown_mode_raises(self):
        raw = self._prep(synth_batch())
        with pytest.raises(ValueError):
            wire.encode_exact(raw, self.NUM_SLOTS, mode="fp4")

    def test_wire_shrinks(self):
        raw = self._prep(synth_batch(seed=8))
        enc = wire.encode_exact(raw, self.NUM_SLOTS)
        assert wire.tree_nbytes(enc) * 3 < wire.tree_nbytes(raw)

    def test_superbatch_stack_and_static_mismatch(self):
        raws = [self._prep(synth_batch(seed=i)) for i in range(3)]
        encs = [wire.encode_exact(r, self.NUM_SLOTS) for r in raws]
        sb = wire.stack_encoded_batches(encs)
        assert sb.steps == 3
        assert sb.num_examples == sum(e.num_examples for e in encs)
        other = dataclasses.replace(encs[0], ucols_bits=encs[0].ucols_bits + 1)
        with pytest.raises(AssertionError):
            wire.stack_encoded_batches([encs[0], other])


def _conf(update="sparse", wire_encode="", cache_mb=0, spl=1,
          minibatch=256, pull_gather="auto"):
    conf = Config()
    conf.penalty = PenaltyConfig(type="l1", lambda_=[0.05])
    conf.learning_rate = LearningRateConfig(type="decay", alpha=0.5, beta=1.0)
    conf.async_sgd = SGDConfig(
        algo="ftrl", minibatch=minibatch, num_slots=1 << 14, max_delay=0,
        update=update, wire_encode=wire_encode, wire_cache_mb=cache_mb,
        steps_per_launch=spl, pull_gather=pull_gather,
    )
    return conf


@pytest.fixture(autouse=True)
def fresh_po():
    Postoffice.reset()
    yield
    Postoffice.reset()


def _train_state(mesh8, batches, conf, pipelined=None):
    worker = AsyncSGDWorker(conf, mesh=mesh8)
    worker.train(iter(list(batches)), pipelined=pipelined)
    return worker, {k: np.asarray(v) for k, v in worker.state.items()}


class TestTrainParity:
    def _batches(self, n=6, binary=False):
        rng = np.random.default_rng(1)
        w_true = (rng.normal(size=512) * (rng.random(512) < 0.3)).astype(
            np.float32
        )
        return [
            random_sparse(256, 512, 8, seed=i, w_true=w_true, binary=binary)
            for i in range(n)
        ], w_true

    def test_exact_mode_trajectory_bit_identical(self, mesh8):
        batches, _ = self._batches()
        _, raw = _train_state(mesh8, batches, _conf(wire_encode=""))
        Postoffice.reset()
        worker, enc = _train_state(mesh8, batches, _conf(wire_encode="exact"))
        # the encoded path really ran (sparse mode → PreppedBatch → enc)
        assert any(k[0].startswith("exact_enc") for k in worker._steps)
        for k in raw:
            np.testing.assert_array_equal(raw[k], enc[k], err_msg=k)

    def test_pipelined_scan_cache_bit_identical(self, mesh8):
        # two passes over the same data exercise the upload key cache;
        # the trajectory must still match the serial raw wire exactly
        batches, _ = self._batches(4)
        stream = batches + batches
        _, raw = _train_state(
            mesh8, stream, _conf(wire_encode="", spl=2), pipelined=False
        )
        Postoffice.reset()
        worker, enc = _train_state(
            mesh8, stream,
            _conf(wire_encode="exact", cache_mb=32, spl=2), pipelined=True,
        )
        assert any(k[0] == "exact_enc_scan" for k in worker._steps)
        for k in raw:
            np.testing.assert_array_equal(raw[k], enc[k], err_msg=k)

    def test_quantized_mode_logloss_bound(self, mesh8):
        batches, w_true = self._batches(6)
        test = random_sparse(1000, 512, 8, seed=99, w_true=w_true)
        w_exact, _ = _train_state(mesh8, batches, _conf(wire_encode="exact"))
        ll_exact = w_exact.evaluate(test)["logloss"]
        for mode in ("int8", "bf16"):
            Postoffice.reset()
            w_q, _ = _train_state(mesh8, batches, _conf(wire_encode=mode))
            ll_q = w_q.evaluate(test)["logloss"]
            # the configured parity bound for lossy value wires: the
            # same 2% envelope bench.py grants the quantized pull
            assert abs(ll_q - ll_exact) <= max(0.01, 0.02 * ll_exact), (
                mode, ll_q, ll_exact,
            )

    def test_sparse_rejects_narrow_pull(self, mesh8):
        # ADVICE round 5: an explicit narrow gather must fail loudly in
        # sparse mode instead of silently no-op'ing
        batches, _ = self._batches(1)
        worker = AsyncSGDWorker(
            _conf(pull_gather="narrow"), mesh=mesh8
        )
        with pytest.raises(ValueError, match="narrow"):
            worker.process_minibatch(batches[0])
        Postoffice.reset()
        # auto/wide stay fine
        worker = AsyncSGDWorker(_conf(pull_gather="wide"), mesh=mesh8)
        worker.executor.wait(worker.process_minibatch(batches[0]))

    def test_bad_config_rejected(self, mesh8):
        with pytest.raises(ValueError, match="wire_encode"):
            AsyncSGDWorker(_conf(wire_encode="zstd"), mesh=mesh8)


class TestDenseGroupGate:
    """ADVICE round 5: exact-wire scan fusion is sparse-mode only —
    dense groups must stay per-minibatch (snapshot/filter semantics)."""

    def test_sparse_mode_scan_fuses(self, mesh8):
        rng = np.random.default_rng(2)
        w_true = rng.normal(size=512).astype(np.float32)
        batches = [
            random_sparse(64, 512, 8, seed=i, w_true=w_true)
            for i in range(3)
        ]
        worker = AsyncSGDWorker(_conf(update="sparse", spl=3), mesh=mesh8)
        parts = worker._prep_group(batches)
        assert len(parts) == 1 and parts[0][1] == 3

    def test_dense_mode_superbatch_raises(self, mesh8):
        # submit_superbatch carries the same gate as _prep_group: a
        # dense-mode exact group must not silently scan-fuse (the scan
        # bypasses snapshot/filter semantics) — the explicit API raises
        rng = np.random.default_rng(2)
        w_true = rng.normal(size=512).astype(np.float32)
        batches = [
            random_sparse(64, 512, 8, seed=i, w_true=w_true)
            for i in range(3)
        ]
        worker = AsyncSGDWorker(_conf(update="dense", spl=3), mesh=mesh8)
        d = KeyDirectory(1 << 14, hashed=True)
        worker.prep = lambda b, device_put=False: prep_batch(
            b, d, 4, 64, 64 * 8, 64 * 8, 1 << 14
        )
        with pytest.raises(ValueError, match="sparse-update"):
            worker.submit_superbatch(batches)

    def test_dense_mode_stays_per_minibatch(self, mesh8):
        # dense + hashed directory yields HashedBatches — not scan
        # fusible either way; emulate a dense exact-wire group directly
        rng = np.random.default_rng(2)
        w_true = rng.normal(size=512).astype(np.float32)
        batches = [
            random_sparse(64, 512, 8, seed=i, w_true=w_true)
            for i in range(3)
        ]
        worker = AsyncSGDWorker(_conf(update="dense", spl=3), mesh=mesh8)
        d = KeyDirectory(1 << 14, hashed=True)

        def exact_prep(b, device_put=False):
            return prep_batch(b, d, 4, 64, 64 * 8, 64 * 8, 1 << 14)

        worker.prep = exact_prep
        parts = worker._prep_group(batches)
        assert len(parts) == 3 and all(n == 1 for _, n in parts)
        assert all(isinstance(p, PreppedBatch) for p, _ in parts)


class TestUploadCache:
    def test_hit_miss_and_saved_bytes(self):
        uploads = []
        cache = wire.UploadCache(
            upload_leaf=lambda x: (uploads.append(x) or np.asarray(x)),
            min_leaf_bytes=1,
        )
        a = np.arange(4096, dtype=np.int32)
        t1 = cache({"slots": a, "y": np.ones(16, np.float32)})
        n1 = len(uploads)
        t2 = cache({"slots": a.copy(), "y": np.ones(16, np.float32)})
        assert cache.hits == 2 and cache.misses == 2
        assert len(uploads) == n1  # nothing re-uploaded on the repeat
        assert cache.saved_bytes == a.nbytes + 16 * 4
        np.testing.assert_array_equal(t2["slots"], t1["slots"])

    def test_signature_collision_never_serves_wrong_bytes(self):
        # array_signature hashes a 2048-byte prefix: two arrays equal in
        # the prefix but different past it COLLIDE by construction — the
        # exact verify must treat that as a miss
        cache = wire.UploadCache(upload_leaf=np.asarray, min_leaf_bytes=1)
        a = np.zeros(4096, np.uint8)
        b = a.copy()
        b[-1] = 7
        cache({"x": a})
        out = cache({"x": b})
        assert cache.hits == 0 and cache.misses == 2
        np.testing.assert_array_equal(out["x"], b)

    def test_collision_overwrite_releases_accounting(self):
        # regression: overwriting a signature-colliding entry must
        # release the displaced bytes, or phantom accounting grows
        # until the eviction loop permanently thrashes the cache
        cache = wire.UploadCache(
            upload_leaf=np.asarray, max_bytes=1 << 20, min_leaf_bytes=1
        )
        a = np.zeros(4096, np.uint8)
        b = a.copy()
        b[-1] = 7  # same 2048-byte prefix signature, different tail
        for _ in range(10):
            cache({"x": a})
            cache({"x": b})
        assert cache._bytes == 4096  # one retained entry, not phantom 80KB
        assert len(cache._cache) == 1

    def test_eviction_bounds_retained_bytes(self):
        cache = wire.UploadCache(
            upload_leaf=np.asarray, max_bytes=3 * 4096, min_leaf_bytes=1
        )
        for i in range(8):
            cache({"x": np.full(4096, i, np.uint8)})
        assert cache._bytes <= 3 * 4096
        # evicted entries miss again
        cache({"x": np.full(4096, 0, np.uint8)})
        assert cache.hits == 0

    def test_small_leaves_bypass(self):
        cache = wire.UploadCache(upload_leaf=np.asarray, min_leaf_bytes=1024)
        small = np.ones(4, np.float32)
        cache({"x": small})
        cache({"x": small})
        assert cache.hits == 0 and cache.misses == 0

    def test_single_owner_thread_asserted(self):
        cache = wire.UploadCache(upload_leaf=np.asarray, min_leaf_bytes=1)
        cache({"x": np.ones(8, np.float32)})
        err = []

        def other():
            try:
                cache({"x": np.ones(8, np.float32)})
            except RuntimeError as e:
                err.append(e)

        t = threading.Thread(target=other)
        t.start()
        t.join()
        assert err, "cross-thread use must raise (stateful uploader stage)"


class TestOffTrainerThread:
    """The PR-3 ingest rule, wire edition (tier-1 twin of the pslint
    thread checks): encode is a stateless pool stage, the cache a
    serial uploader stage — neither may run on the trainer thread."""

    def test_encode_and_cache_stay_off_trainer_thread(
        self, mesh8, monkeypatch
    ):
        rng = np.random.default_rng(4)
        w_true = rng.normal(size=512).astype(np.float32)
        batches = [
            random_sparse(64, 512, 8, seed=i, w_true=w_true)
            for i in range(6)
        ]
        encode_threads = set()
        real_encode = wire.encode_exact

        def spy_encode(*a, **kw):
            encode_threads.add(threading.get_ident())
            return real_encode(*a, **kw)

        monkeypatch.setattr(wire, "encode_exact", spy_encode)
        caches = []
        real_cache = wire.UploadCache

        def spy_cache(*a, **kw):
            c = real_cache(*a, **kw)
            caches.append(c)
            return c

        monkeypatch.setattr(wire, "UploadCache", spy_cache)
        worker = AsyncSGDWorker(
            _conf(wire_encode="exact", cache_mb=16, spl=2, minibatch=64),
            mesh=mesh8,
        )
        worker.train(iter(batches), pipelined=True)
        me = threading.get_ident()
        assert encode_threads and me not in encode_threads, (
            "wire encode ran on the trainer thread"
        )
        assert caches and all(
            c._owner is not None and c._owner != me for c in caches
        ), "UploadCache ran on the trainer thread"


class TestUploadedBytesWithCache:
    def test_cache_hits_do_not_count_as_link_traffic(self):
        # ps_ingest_uploaded_bytes_total documents REALIZED link
        # traffic: a cache-hit batch re-uses its device buffer, so its
        # bytes must not inflate the counter (regression)
        from parameter_server_tpu.apps.linear.async_sgd import (
            DeviceUploader,
        )
        from parameter_server_tpu.telemetry import registry as treg

        if not treg.enabled():
            pytest.skip("telemetry disabled")
        from parameter_server_tpu.telemetry.instruments import (
            ingest_instruments,
        )

        tel = ingest_instruments(treg.default_registry())
        b0 = tel["uploaded_bytes"].value()
        d = KeyDirectory(1 << 18, hashed=True)
        prepped = prep_batch(
            synth_batch(seed=31), d, 2, 64, 64 * 16, 64 * 16, 1 << 18
        )
        repeat = dataclasses.replace(prepped)  # same bytes, new tree
        # expected first-pass link traffic: the cache also dedups
        # byte-identical leaves WITHIN a batch, so probe that offline
        probe = wire.UploadCache(upload_leaf=np.asarray, min_leaf_bytes=1)
        probe(prepped)
        expected = wire.tree_nbytes(prepped) - probe.saved_bytes
        cache = wire.UploadCache(upload_leaf=np.asarray, min_leaf_bytes=1)
        up = DeviceUploader(iter([(prepped, 1), (repeat, 1)]), cache, depth=2)
        list(up)
        up.close()
        # first pass ships the miss bytes, the repeat ships ~nothing
        shipped = tel["uploaded_bytes"].value() - b0
        assert shipped == expected, (shipped, expected)


class TestWireTelemetry:
    def test_instruments_advance(self):
        from parameter_server_tpu.telemetry import registry as treg

        if not treg.enabled():
            pytest.skip("telemetry disabled")
        reg = treg.default_registry()
        from parameter_server_tpu.telemetry.instruments import (
            wire_instruments,
        )

        tel = wire_instruments(reg)
        b0 = tel["bytes"].labels(encoding="exact").value
        d = KeyDirectory(1 << 18, hashed=True)
        raw = prep_batch(
            synth_batch(seed=11), d, 2, 64, 64 * 16, 64 * 16, 1 << 18
        )
        enc = wire.encode_exact(raw, 1 << 18)
        assert tel["bytes"].labels(encoding="exact").value == (
            b0 + wire.tree_nbytes(enc)
        )
        h0 = tel["cache_hits"].value()
        cache = wire.UploadCache(upload_leaf=np.asarray, min_leaf_bytes=1)
        cache({"x": np.ones(64, np.float32)})
        cache({"x": np.ones(64, np.float32)})
        assert tel["cache_hits"].value() == h0 + 1


class TestMessageWireCodec:
    def test_chain_roundtrip_and_key_cache(self):
        rng = np.random.default_rng(5)
        sender = wire.MessageWireCodec()
        receiver = wire.MessageWireCodec()
        keys = np.sort(rng.choice(1 << 30, 256, replace=False)).astype(
            np.int64
        )
        vals = (rng.random(256) < 0.1).astype(np.float32)
        m1 = sender.encode(keys.copy(), [vals.copy()])
        assert m1.key is not None
        k1, v1 = receiver.decode(m1)
        np.testing.assert_array_equal(k1, keys)
        np.testing.assert_array_equal(v1[0], vals)
        # repeat: keys ride as signature only, receiver restores them
        m2 = sender.encode(keys.copy(), [vals.copy()])
        assert m2.key is None
        k2, v2 = receiver.decode(m2)
        np.testing.assert_array_equal(k2, keys)
        np.testing.assert_array_equal(v2[0], vals)

    def test_quantized_chain_bounded(self):
        rng = np.random.default_rng(6)
        sender = wire.MessageWireCodec(num_bytes=2)
        receiver = wire.MessageWireCodec(num_bytes=2)
        vals = rng.normal(size=512).astype(np.float32)
        k, v = receiver.decode(sender.encode(None, [vals.copy()]))
        assert k is None
        step = (vals.max() - vals.min()) / 65535
        assert np.abs(v[0] - vals).max() <= step + 1e-6


# ---------------------------------------------------------------------------
# Stream-once lane-dictionary wire (wire='stream') — the cache-free
# encoding for single-epoch data, plus its native fused prep and the
# staging-leg codec. Same contract as the exact wire above: decode is
# BIT-IDENTICAL, encode never guesses (domain verify → raw fallback),
# stateless stages pool.
# ---------------------------------------------------------------------------


def _criteo_like_batches(n_batches, rows=256, lanes=8, vocab_small=60,
                         seed=7):
    """Uniform-lane binary batches with the criteo-law lane split:
    half the lanes draw from a tiny per-lane vocabulary (the integer
    count fields), half from a ~2^40 space (hashed categoricals)."""
    rng = np.random.default_rng(seed)
    out = []
    for _ in range(n_batches):
        small = rng.integers(0, vocab_small, (rows, lanes // 2))
        wide = rng.integers(0, 1 << 40, (rows, lanes - lanes // 2))
        keys = np.concatenate(
            [small + (np.arange(lanes // 2) << 50), wide], axis=1
        ).astype(np.int64)
        y = rng.choice((-1.0, 1.0), rows).astype(np.float32)
        out.append(SparseBatch(
            y=y,
            indptr=np.arange(0, rows * lanes + 1, lanes),
            indices=keys.ravel(),
        ))
    return out


class TestStreamStatics:
    NUM_SLOTS = 1 << 18

    def test_lane_split_derivation(self):
        b = _criteo_like_batches(1)[0]
        st = wire.derive_stream_statics(
            b.indices, 8, self.NUM_SLOTS, self.NUM_SLOTS
        )
        assert st is not None
        # the tiny-vocab lanes (0-3) take the dictionary, wide stay raw
        assert st.dict_lanes == (0, 1, 2, 3)
        assert 2 * st.code_bits <= st.raw_bits

    def test_no_win_returns_none(self):
        # every lane wide-vocab: no dictionary split can win
        rng = np.random.default_rng(1)
        keys = rng.integers(0, 1 << 40, 256 * 8).astype(np.int64)
        assert wire.derive_stream_statics(
            keys, 8, self.NUM_SLOTS, self.NUM_SLOTS
        ) is None

    def test_table_cost_guard(self):
        # tiny batch: per-row savings cannot amortize the table → None
        b = _criteo_like_batches(1, rows=4)[0]
        assert wire.derive_stream_statics(
            b.indices, 8, self.NUM_SLOTS, self.NUM_SLOTS
        ) is None


class TestStreamWireParity:
    NUM_SLOTS = 1 << 18

    def _prep(self, b, st, rows_pad=None, shards=2, lanes=8):
        from parameter_server_tpu.apps.linear.async_sgd import (
            prep_batch_ell_stream,
        )

        d = KeyDirectory(self.NUM_SLOTS, hashed=True)
        rows_pad = rows_pad or -(-b.n // shards)
        return prep_batch_ell_stream(
            b, d, shards, rows_pad, lanes, self.NUM_SLOTS, st
        )

    def _statics(self, b, lanes=8):
        return wire.derive_stream_statics(
            b.indices, lanes, self.NUM_SLOTS, self.NUM_SLOTS
        )

    def test_decode_bit_identical(self):
        from parameter_server_tpu.utils.murmur import hash_slots

        for b in _criteo_like_batches(3):
            st = self._statics(b)
            enc = self._prep(b, st)
            assert enc is not None
            per = -(-b.n // 2)
            for d in range(2):
                lo, hi = min(d * per, b.n), min((d + 1) * per, b.n)
                seg = slice(b.indptr[lo], b.indptr[hi])
                want = hash_slots(
                    np.ascontiguousarray(b.indices[seg], np.uint64),
                    self.NUM_SLOTS,
                ).reshape(hi - lo, 8)
                y, mask, slots = wire.decode_stream_shard(enc, d)
                got = np.asarray(slots)
                assert got.dtype == np.int32
                np.testing.assert_array_equal(got[: hi - lo], want)
                np.testing.assert_array_equal(
                    np.asarray(y)[: hi - lo], b.y[lo:hi]
                )
                np.testing.assert_array_equal(
                    np.asarray(mask),
                    (np.arange(enc.rows) < (hi - lo)).astype(np.float32),
                )

    def test_fixture_refuses_ragged(self):
        # the committed wire_parity.libsvm fixture is ragged (3-10
        # features/row) — outside the uniform-lane stream domain: the
        # encoder must REFUSE (raw fallback), never mis-encode; the
        # exact wire stays the fixture's encoded path (tested above)
        for b in fixture_batches(binary=True):
            st = wire.StreamStatics(
                lanes=8, dict_lanes=(0,), code_bits=4, dict_pad=64,
                raw_bits=18,
            )
            assert self._prep(b, st) is None

    def test_valued_and_regression_refused(self):
        b = _criteo_like_batches(1)[0]
        st = self._statics(b)
        valued = SparseBatch(
            y=b.y, indptr=b.indptr, indices=b.indices,
            values=np.ones(b.nnz, np.float32) * 2.0,
        )
        assert self._prep(valued, st) is None
        regress = SparseBatch(
            y=np.linspace(-2, 2, b.n).astype(np.float32),
            indptr=b.indptr, indices=b.indices,
        )
        assert self._prep(regress, st) is None

    def test_statics_overflow_falls_back(self):
        # pinned statics from a tiny-vocab batch; a batch whose lane
        # vocabulary blows past the padded code space must fall back
        b0 = _criteo_like_batches(1, vocab_small=16)[0]
        st = self._statics(b0)
        big = _criteo_like_batches(1, vocab_small=250, seed=9)[0]
        assert self._prep(big, st) is None
        assert self._prep(b0, st) is not None

    def test_superbatch_stack_and_static_mismatch(self):
        batches = _criteo_like_batches(3)
        st = self._statics(batches[0])
        encs = [self._prep(b, st) for b in batches]
        sb = wire.stack_stream_batches(encs)
        assert sb.steps == 3
        assert sb.num_examples == sum(e.num_examples for e in encs)
        other = dataclasses.replace(encs[0], code_bits=encs[0].code_bits + 1)
        with pytest.raises(AssertionError):
            wire.stack_stream_batches([encs[0], other])

    def test_wire_shrinks_vs_bits(self):
        from parameter_server_tpu.apps.linear.async_sgd import (
            prep_batch_ell_bits,
        )

        b = _criteo_like_batches(1, rows=1024)[0]
        st = self._statics(b)
        enc = self._prep(b, st, rows_pad=512)
        d = KeyDirectory(self.NUM_SLOTS, hashed=True)
        bits = prep_batch_ell_bits(b, d, 2, 512, 8, self.NUM_SLOTS)
        assert wire.tree_nbytes(enc) < wire.tree_nbytes(bits)


def _native_stream_encode():
    from conftest import require_native

    return require_native("ps_stream_encode")


class TestNativeFusedPrep:
    """C-vs-Python fused unique+remap+encode parity: the native one-
    pass ps_stream_encode must be BYTE-IDENTICAL to the NumPy path on
    the committed ingest fixture's key stream."""

    NUM_SLOTS = 1 << 18
    LANES = 8

    def _fixture_keys(self):
        # the committed ingest fixture's real key bytes, reshaped to
        # uniform lanes (the stream wire's domain): same keys the PR-3
        # ingest parity contract pins
        import os as _os

        from parameter_server_tpu.data.stream_reader import StreamReader

        fx = _os.path.join(
            _os.path.dirname(__file__), "data", "ingest_parity.libsvm"
        )
        idx = np.concatenate(
            [b.indices for b in StreamReader([fx], "libsvm").minibatches(64)]
        )
        n = (idx.size // self.LANES) * self.LANES
        # fold some keys into a small per-lane vocabulary so the lane
        # dictionary engages (fixture keys are near-unique)
        keys = idx[:n].copy()
        rows = n // self.LANES
        km = keys.reshape(rows, self.LANES)
        km[:, : self.LANES // 2] = (km[:, : self.LANES // 2] % 48) + (
            np.arange(self.LANES // 2) << 50
        )
        return keys, rows

    def test_byte_identical_on_ingest_fixture(self):
        from parameter_server_tpu.utils.murmur import hash_slots

        _native_stream_encode()
        keys, rows = self._fixture_keys()
        st = wire.derive_stream_statics(
            keys, self.LANES, self.NUM_SLOTS, self.NUM_SLOTS
        )
        assert st is not None and st.dict_lanes
        rows_pad = rows + 13  # exercise the zero tail too
        nat = wire.encode_stream_shard(
            keys, rows, rows_pad, self.NUM_SLOTS, st
        )
        py = wire._encode_stream_shard_py(
            hash_slots(np.ascontiguousarray(keys, np.uint64),
                       self.NUM_SLOTS),
            rows, rows_pad, st,
        )
        assert nat is not None and py is not None
        for name, a, c in zip(
            ("raw_words", "code_words", "table_words", "lane_starts",
             "n_uniq"), nat, py,
        ):
            a, c = np.asarray(a), np.asarray(c)
            assert a.dtype == c.dtype and a.shape == c.shape, name
            np.testing.assert_array_equal(a, c, err_msg=name)

    def test_overflow_agreement(self):
        # both paths must refuse the SAME batches (the fallback is part
        # of the wire format): shrink the pinned table/code space and
        # check C and Python agree on rejection
        from parameter_server_tpu.utils.murmur import hash_slots

        _native_stream_encode()
        keys, rows = self._fixture_keys()
        st = wire.derive_stream_statics(
            keys, self.LANES, self.NUM_SLOTS, self.NUM_SLOTS
        )
        tight = dataclasses.replace(st, dict_pad=8, code_bits=2)
        nat = wire.encode_stream_shard(
            keys, rows, rows, self.NUM_SLOTS, tight
        )
        py = wire._encode_stream_shard_py(
            hash_slots(np.ascontiguousarray(keys, np.uint64),
                       self.NUM_SLOTS),
            rows, rows, tight,
        )
        assert nat is None and py is None


class TestStreamTrainParity:
    """The PR-5 whole-trajectory invariant, extended to the stream
    encoder: training on the stream wire (per-minibatch AND scan-fused
    AND staging-leg-compressed, pipelined) is bit-identical to the raw
    bits wire."""

    def _conf(self, wire_fmt, spl=1, compress=""):
        conf = Config()
        conf.penalty = PenaltyConfig(type="l1", lambda_=[0.05])
        conf.learning_rate = LearningRateConfig(
            type="decay", alpha=0.5, beta=1.0
        )
        conf.async_sgd = SGDConfig(
            algo="ftrl", minibatch=256, num_slots=1 << 16, max_delay=0,
            ell_lanes=8, wire=wire_fmt, steps_per_launch=spl,
            wire_compress=compress,
        )
        return conf

    def _run(self, mesh8, wire_fmt, spl=1, compress="", pipelined=None):
        Postoffice.reset()
        worker = AsyncSGDWorker(self._conf(wire_fmt, spl, compress),
                                mesh=mesh8)
        worker.train(iter(_criteo_like_batches(6)), pipelined=pipelined)
        return worker, {k: np.asarray(v) for k, v in worker.state.items()}

    def test_trajectory_bit_identical(self, mesh8):
        _, raw = self._run(mesh8, "bits")
        worker, enc = self._run(mesh8, "stream")
        assert any(k[0] == "ell_stream" for k in worker._steps), (
            "the stream path did not run"
        )
        for k in raw:
            np.testing.assert_array_equal(raw[k], enc[k], err_msg=k)

    def test_scan_compressed_pipelined_bit_identical(self, mesh8):
        _, raw = self._run(mesh8, "bits")
        worker, enc = self._run(
            mesh8, "stream", spl=2, compress="lz", pipelined=True
        )
        assert any(k[0] == "ell_stream_scan" for k in worker._steps)
        for k in raw:
            np.testing.assert_array_equal(raw[k], enc[k], err_msg=k)

    def test_bad_compress_config_rejected(self, mesh8):
        with pytest.raises(ValueError, match="wire_compress"):
            AsyncSGDWorker(self._conf("bits", compress="zstd"), mesh=mesh8)


class TestStagingLegCodec:
    def test_roundtrip_bit_identical(self):
        b = _criteo_like_batches(1)[0]
        st = wire.derive_stream_statics(b.indices, 8, 1 << 18, 1 << 18)
        from parameter_server_tpu.apps.linear.async_sgd import (
            prep_batch_ell_stream,
        )

        d = KeyDirectory(1 << 18, hashed=True)
        enc = prep_batch_ell_stream(b, d, 2, 128, 8, 1 << 18, st)
        cb = wire.compress_batch(enc, encoding="stream")
        assert cb.num_examples == enc.num_examples
        assert cb.wire_nbytes <= cb.raw_nbytes + len(cb.frames)
        dec = wire.decompress_batch(cb)
        assert type(dec) is type(enc)
        for f in dataclasses.fields(type(enc)):
            want = getattr(enc, f.name)
            got = getattr(dec, f.name)
            if isinstance(want, np.ndarray):
                assert want.dtype == got.dtype, f.name
                np.testing.assert_array_equal(want, got, err_msg=f.name)
            else:
                assert want == got, f.name

    def test_maybe_decompress_identity(self):
        x = {"a": np.arange(4)}
        assert wire.maybe_decompress(x) is x

    def test_incompressible_leaves_ride_raw(self):
        rng = np.random.default_rng(3)
        noise = {"x": rng.integers(0, 256, 1 << 15).astype(np.uint8)}
        cb = wire.compress_batch(noise)
        # raw frame: one header byte of overhead, nothing more
        assert cb.wire_nbytes <= cb.raw_nbytes + len(cb.frames)
        got = wire.decompress_batch(cb)
        np.testing.assert_array_equal(got["x"], noise["x"])


# ---------------------------------------------------------------------------
# bytes per example, exact, per wire: what `wire_bytes_per_example` reads
# ---------------------------------------------------------------------------


def _fixture_bytes(valued, mode):
    """Bytes the committed fixture's three minibatches put on the link
    through the exact-wire prep (``mode`` None: the raw PreppedBatch)."""
    t = TestEncodeExactParity()
    total = 0
    for b in fixture_batches(binary=not valued):
        raw = t._prep(b, shared=True)
        total += wire.tree_nbytes(
            raw if mode is None
            else wire.encode_exact(raw, t.NUM_SLOTS, mode=mode)
        )
    return total


def _uniform_bytes(which):
    """One 1,024-row uniform-lane batch through the bits wire or the
    stream-once wire (the committed fixture is ragged: both refuse it)."""
    from parameter_server_tpu.apps.linear.async_sgd import (
        prep_batch_ell_bits,
    )

    t = TestStreamWireParity()
    b = _criteo_like_batches(1, rows=1024)[0]
    if which == "stream":
        return wire.tree_nbytes(t._prep(b, t._statics(b), rows_pad=512))
    d = KeyDirectory(t.NUM_SLOTS, hashed=True)
    return wire.tree_nbytes(prep_batch_ell_bits(b, d, 2, 512, 8, t.NUM_SLOTS))


class TestBytesPerExample:
    """A wire's size is a property of its format: the same rows ship the
    same bytes on every host. A change here moves
    ``wire_bytes_per_example`` in every cell that runs the wire."""

    @pytest.mark.parametrize("measure,args,want", [
        # 1312 B an example: the padded PreppedBatch
        pytest.param(_fixture_bytes, (False, None), 125952, id="raw"),
        # 213.5: binary rows, the value stream elided
        pytest.param(_fixture_bytes, (False, "exact"), 20496, id="exact"),
        # 469.5: the f32 value stream rides along
        pytest.param(_fixture_bytes, (True, "exact"), 45072,
                     id="exact_valued"),
        pytest.param(_fixture_bytes, (True, "int8"), 26688,
                     id="int8_valued"),  # 278
        pytest.param(_uniform_bytes, ("bits",), 18576, id="bits"),  # 18.14
        # 14.95: the lane dictionary under bits
        pytest.param(_uniform_bytes, ("stream",), 15304, id="stream"),
    ])
    def test_exact_byte_count(self, measure, args, want):
        assert measure(*args) == want

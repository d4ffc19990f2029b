"""Fused sparse FTRL kernel (ops/ftrl_sparse.py) — parity + contracts.

The kernel's claim is BIT-identity with the XLA rows path
(``updaters.apply_state_rows`` for FTRL/decay): interpret mode runs the
same kernel body the chip compiles. Everything the predicate rejects —
a bf16 ``sqrt_n`` table included, whose single-row DMA the TPU compiler
refuses — must fall back to the rows path, bit-identically, so the
train step can call one entry point unconditionally.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from parameter_server_tpu.apps.linear.learning_rate import LearningRate
from parameter_server_tpu.apps.linear.penalty import ElasticNet
from parameter_server_tpu.apps.linear.updaters import (
    FTRLUpdater,
    apply_state_rows,
)
from parameter_server_tpu.ops import ftrl_sparse
from parameter_server_tpu.ops.ftrl_sparse import (
    ftrl_sparse_rows_ref,
    ftrl_sparse_update,
    resolve_update_path,
    use_sparse_kernel,
)

KW = dict(alpha=0.5, beta=1.0, l1=0.05, l2=0.01)


def _updater(dtype=jnp.float32):
    return FTRLUpdater(
        LearningRate("decay", alpha=KW["alpha"], beta=KW["beta"]),
        ElasticNet(KW["l1"], KW["l2"]),
        sqrt_n_dtype=dtype,
    )


def _state(p, rng, dtype=jnp.float32):
    return {
        "z": jnp.asarray(rng.normal(size=p).astype(np.float32)),
        "sqrt_n": jnp.asarray(
            (rng.random(p) * 2).astype(np.float32)
        ).astype(dtype),
    }


def _touch(p, u, rng, n_live=None, zero_g_at=()):
    """localize-shaped inputs: sorted unique owned ids, clip-style
    non-ok entries, sentinel tail. Returns (rel, ok, g_u) jnp arrays."""
    n_live = n_live if n_live is not None else u - max(2, u // 8)
    live = np.unique(rng.integers(0, p, n_live))
    rel = np.full(u, p - 1, np.int32)  # high-clip tail (ok False)
    rel[: len(live)] = np.sort(live).astype(np.int32)
    ok = np.zeros(u, bool)
    ok[: len(live)] = True
    g = rng.normal(size=u).astype(np.float32)
    for i in zero_g_at:
        g[i] = 0.0
    return jnp.asarray(rel), jnp.asarray(ok), jnp.asarray(g)


class TestInterpretParity:
    def test_f32_bit_exact_vs_apply_state_rows(self, rng):
        p, u = 1 << 13, 256
        up = _updater()
        st = _state(p, rng)
        rel, ok, g = _touch(p, u, rng, zero_g_at=(3,))
        want = apply_state_rows(up, st, rel, ok, g)
        zk, nk = ftrl_sparse_update(
            st["z"], st["sqrt_n"], rel, ok, g, **KW,
            force_pallas=True, interpret=True,
        )
        np.testing.assert_array_equal(np.asarray(zk), np.asarray(want["z"]))
        np.testing.assert_array_equal(
            np.asarray(nk), np.asarray(want["sqrt_n"])
        )

    def test_rows_past_one_smem_chunk_scan_the_kernel(self, rng,
                                                      monkeypatch):
        """A row set wider than the scalar-prefetch chunk runs as a
        scan of kernel launches (the chip's SMEM holds 2^16 row ids,
        not the training width): shrink the chunk so u = 256 spans
        four launches and a ragged live prefix, and the result must
        stay bit-identical to the single-launch rows path."""
        monkeypatch.setattr(ftrl_sparse, "_SMEM_CHUNK_ROWS", 64)
        p, u = 1 << 13, 256
        up = _updater()
        st = _state(p, rng)
        rel, ok, g = _touch(p, u, rng, n_live=150)
        want = apply_state_rows(up, st, rel, ok, g)
        zk, nk = ftrl_sparse_update(
            st["z"], st["sqrt_n"], rel, ok, g, **KW,
            force_pallas=True, interpret=True, block_rows=16,
        )
        np.testing.assert_array_equal(np.asarray(zk), np.asarray(want["z"]))
        np.testing.assert_array_equal(
            np.asarray(nk), np.asarray(want["sqrt_n"])
        )

    def test_whole_trajectory_serial_vs_fused(self, rng):
        """Multi-step state evolution: N serial apply_state_rows steps
        vs N fused-kernel steps over the same touch stream end
        bit-identical — the trajectory contract, not just one step."""
        p, u = 1 << 13, 128
        up = _updater()
        st_serial = _state(p, rng)
        st_fused = {k: v for k, v in st_serial.items()}
        for step in range(6):
            srng = np.random.default_rng(100 + step)
            rel, ok, g = _touch(p, u, srng)
            st_serial = apply_state_rows(up, st_serial, rel, ok, g)
            zf, nf = ftrl_sparse_update(
                st_fused["z"], st_fused["sqrt_n"], rel, ok, g, **KW,
                force_pallas=True, interpret=True,
            )
            st_fused = {"z": zf, "sqrt_n": nf}
        np.testing.assert_array_equal(
            np.asarray(st_fused["z"]), np.asarray(st_serial["z"])
        )
        np.testing.assert_array_equal(
            np.asarray(st_fused["sqrt_n"]),
            np.asarray(st_serial["sqrt_n"]),
        )

    def test_dense_rows_all_lanes(self, rng):
        """Fully dense touch (every lane of a row range) exercises the
        duplicate-row merge: many slots per 128-lane row must collapse
        into ONE fetched/written row with all lanes live."""
        p = 1 << 13
        rel = jnp.arange(512, dtype=jnp.int32)  # rows 0-3 fully dense
        ok = jnp.ones(512, bool)
        g = jnp.asarray(rng.normal(size=512).astype(np.float32))
        st = _state(p, rng)
        up = _updater()
        want = apply_state_rows(up, st, rel, ok, g)
        zk, nk = ftrl_sparse_update(
            st["z"], st["sqrt_n"], rel, ok, g, **KW,
            force_pallas=True, interpret=True,
        )
        np.testing.assert_array_equal(np.asarray(zk), np.asarray(want["z"]))
        np.testing.assert_array_equal(
            np.asarray(nk), np.asarray(want["sqrt_n"])
        )


class TestEdgeShapes:
    def test_sentinel_padding_rows_dropped(self, rng):
        """An all-sentinel batch (nothing owned) must leave the whole
        table bit-identical — clip-merged rows write back unchanged
        copies, never perturbed ones."""
        p, u = 1 << 13, 64
        st = _state(p, rng)
        rel = jnp.full((u,), p - 1, jnp.int32)
        ok = jnp.zeros((u,), bool)
        g = jnp.asarray(rng.normal(size=u).astype(np.float32))
        zk, nk = ftrl_sparse_update(
            st["z"], st["sqrt_n"], rel, ok, g, **KW,
            force_pallas=True, interpret=True,
        )
        np.testing.assert_array_equal(np.asarray(zk), np.asarray(st["z"]))
        np.testing.assert_array_equal(
            np.asarray(nk), np.asarray(st["sqrt_n"])
        )

    def test_clip_merge_does_not_perturb_shared_rows(self, rng):
        """Non-ok entries clip to row 0 / the last row; when those rows
        are ALSO genuinely touched, the zero-gradient lanes must merge
        into the genuine row group without perturbing its update."""
        p, u = 1 << 13, 64
        st = _state(p, rng)
        up = _updater()
        # rel stays NON-DECREASING (the localize-of-sorted-uslots
        # contract): low-clip non-ok entries lead, genuine rows follow
        # (row 0 and the last row among them), high-clip/sentinel tail
        rel_h = np.full(u, p - 1, np.int32)
        rel_h[:9] = [0, 0, 1, 5, 130, 200, 4000, p - 129, p - 2]
        ok_h = np.zeros(u, bool)
        ok_h[1:9] = True  # entry 0 is a low clip (ok False) onto row 0
        g = rng.normal(size=u).astype(np.float32)
        rel, ok = jnp.asarray(rel_h), jnp.asarray(ok_h)
        gj = jnp.asarray(g)
        want = apply_state_rows(up, st, rel, ok, gj)
        zk, nk = ftrl_sparse_update(
            st["z"], st["sqrt_n"], rel, ok, gj, **KW,
            force_pallas=True, interpret=True,
        )
        np.testing.assert_array_equal(np.asarray(zk), np.asarray(want["z"]))
        np.testing.assert_array_equal(
            np.asarray(nk), np.asarray(want["sqrt_n"])
        )

    def test_negative_sentinel_tail_does_not_lose_updates(self, rng):
        """The ≥2^31-slot sentinel is -1 (slot_sentinel), so localize
        clips the padding tail to rel 0 BELOW the ascending owned ids —
        rel is NOT non-decreasing there. The row dedup must not emit
        row 0 twice (a later stale-fetch write-back would silently
        erase the genuine row-0 update — the review-confirmed bug
        shape): remapping non-ok rows through the ok-row running max
        keeps the sequence monotone, and slots in row 0 keep their
        updates bit-exactly."""
        p, u = 1 << 13, 64
        st = _state(p, rng)
        up = _updater()
        rel_h = np.zeros(u, np.int32)
        # genuine ascending ids, rows 0 and upward among them
        rel_h[:8] = [5, 9, 140, 300, 2000, 4096, 8000, p - 1]
        ok_h = np.zeros(u, bool)
        ok_h[:8] = True
        # the -1 sentinel tail clipped to 0 (ok False) AFTER the
        # ascending ids — out of order by construction
        g = rng.normal(size=u).astype(np.float32)
        rel, ok, gj = jnp.asarray(rel_h), jnp.asarray(ok_h), jnp.asarray(g)
        want = apply_state_rows(up, st, rel, ok, gj)
        zk, nk = ftrl_sparse_update(
            st["z"], st["sqrt_n"], rel, ok, gj, **KW,
            force_pallas=True, interpret=True, block_rows=8,
        )
        # the genuine row-0 slots (5, 9) must carry their updates
        assert np.asarray(zk)[5] != np.asarray(st["z"])[5]
        np.testing.assert_array_equal(np.asarray(zk), np.asarray(want["z"]))
        np.testing.assert_array_equal(
            np.asarray(nk), np.asarray(want["sqrt_n"])
        )

    def test_non_tile_multiple_row_count_falls_back(self, rng):
        """u % 8 != 0 cannot be tiled: the predicate rejects it and the
        entry point must return the rows-path result bit-identically
        (even under force_pallas — never onto an untileable shape)."""
        p, u = 1 << 13, 12
        assert not use_sparse_kernel(p, u, False, True)
        st = _state(p, rng)
        rel, ok, g = _touch(p, u, rng, n_live=8)
        want = ftrl_sparse_rows_ref(
            st["z"], st["sqrt_n"], rel, ok, g, **KW
        )
        got = ftrl_sparse_update(
            st["z"], st["sqrt_n"], rel, ok, g, **KW,
            force_pallas=True, interpret=True,
        )
        np.testing.assert_array_equal(np.asarray(got[0]), np.asarray(want[0]))
        np.testing.assert_array_equal(np.asarray(got[1]), np.asarray(want[1]))

    def test_non_tileable_table_falls_back(self, rng):
        p = (1 << 13) + 128  # not a multiple of 8*128
        assert not use_sparse_kernel(p, 64, False, True)

    def test_bf16_table_falls_back(self, rng):
        """bf16 ``sqrt_n`` is outside the kernel (the TPU compiler
        refuses its single-row DMA): the predicate rejects it even
        under force_pallas and the entry point returns the rows-path
        result, seeded stochastic narrow included."""
        p, u = 1 << 13, 64
        assert not use_sparse_kernel(p, u, True, True)
        assert use_sparse_kernel(p, u, False, True)
        st = _state(p, rng, jnp.bfloat16)
        rel, ok, g = _touch(p, u, rng)
        seed = jnp.uint32(7)
        # jitted like the entry point: XLA contracts the z multiply-add
        # under jit, so an eager reference differs in the last bit
        want = jax.jit(
            lambda z, n: ftrl_sparse_rows_ref(
                z, n, rel, ok, g, **KW, seed=seed
            )
        )(st["z"], st["sqrt_n"])
        got = ftrl_sparse_update(
            st["z"], st["sqrt_n"], rel, ok, g, **KW, seed=seed,
            force_pallas=True, interpret=True,
        )
        np.testing.assert_array_equal(np.asarray(got[0]), np.asarray(want[0]))
        np.testing.assert_array_equal(
            np.asarray(got[1]).view(np.uint16),
            np.asarray(want[1]).view(np.uint16),
        )

    def test_duplicate_uslots_contract_asserted(self, rng):
        """apply_state_rows' duplicate-free contract is ASSERTED on
        concrete host inputs: a duplicated ok row would double-apply
        nonlinearly in every formulation."""
        p = 1 << 13
        up = _updater()
        st = _state(p, rng)
        rel = np.asarray([3, 3, 7, 9, 10, 11, 12, 13], np.int32)
        ok = np.ones(8, bool)
        g = np.ones(8, np.float32)
        with pytest.raises(AssertionError, match="duplicate-free"):
            apply_state_rows(up, st, rel, ok, g)

    def test_block_rows_env_and_arg(self, rng, monkeypatch):
        """Block-size resolution: explicit arg wins, env override
        applies, non-dividing values round down — and every block size
        is bit-identical (the grid carve cannot change results)."""
        p, u = 1 << 13, 256
        st = _state(p, rng)
        rel, ok, g = _touch(p, u, rng)
        base = ftrl_sparse_update(
            st["z"], st["sqrt_n"], rel, ok, g, **KW,
            force_pallas=True, interpret=True,
        )
        for br in (8, 32, 256):
            got = ftrl_sparse_update(
                st["z"], st["sqrt_n"], rel, ok, g, **KW,
                force_pallas=True, interpret=True, block_rows=br,
            )
            np.testing.assert_array_equal(
                np.asarray(got[0]), np.asarray(base[0]), err_msg=str(br)
            )
        monkeypatch.setenv("PS_FTRL_SPARSE_BLOCK_ROWS", "64")
        assert ftrl_sparse._sparse_block_rows(256) == 64
        assert ftrl_sparse._sparse_block_rows(256, 32) == 32
        # non-dividing request rounds down to a dividing power of two
        assert ftrl_sparse._sparse_block_rows(24, 512) == 8


@pytest.mark.slow
class TestHeavySweep:
    """Broader shape/block sweep — interpret mode over bigger tables is
    minutes-scale on this 2-core host, so it rides outside tier-1
    (ROADMAP 870s budget); `pytest -m slow` runs it."""

    @pytest.mark.parametrize("u", [1024, 4096])
    def test_parity_sweep(self, rng, u):
        p = 1 << 16
        up = _updater()
        st = _state(p, rng)
        rel, ok, g = _touch(p, u, rng)
        want = apply_state_rows(up, st, rel, ok, g)
        for br in (128, 1024):
            zk, nk = ftrl_sparse_update(
                st["z"], st["sqrt_n"], rel, ok, g, **KW,
                force_pallas=True, interpret=True, block_rows=br,
            )
            np.testing.assert_array_equal(
                np.asarray(zk), np.asarray(want["z"]), err_msg=str(br)
            )
            np.testing.assert_array_equal(
                np.asarray(nk), np.asarray(want["sqrt_n"]),
                err_msg=str(br),
            )


class TestPathResolution:
    def test_predicate_off_tpu(self):
        # off-TPU without force: never the kernel (this container)
        assert not use_sparse_kernel(1 << 13, 256, False, False)

    def test_resolve_update_path_names(self):
        assert resolve_update_path(
            "sparse", on_tpu=True, shard=1 << 20, u=1024,
            bf16_n=False, has_seed=True,
        ) == "pallas_sparse"
        assert resolve_update_path(
            "sparse", on_tpu=False, shard=1 << 20, u=1024,
            bf16_n=False, has_seed=True,
        ) == "xla_rows"
        # non-tileable unique width: sparse mode falls to the rows path
        assert resolve_update_path(
            "sparse", on_tpu=True, shard=1 << 20, u=1023,
            bf16_n=False, has_seed=True,
        ) == "xla_rows"
        # bf16 sqrt_n: out of the kernel's dispatch on every backend
        assert resolve_update_path(
            "sparse", on_tpu=True, shard=1 << 20, u=1024,
            bf16_n=True, has_seed=True,
        ) == "xla_rows"
        # dense mode on this CPU container resolves to the jnp ref
        assert resolve_update_path(
            "dense", on_tpu=False, shard=1 << 20, u=0,
            bf16_n=False, has_seed=True,
        ) == "ref"

    def test_worker_dispatch_counters(self, mesh8):
        """A sparse-mode training run ticks ps_ftrl_update_path_total
        {path=xla_rows} (this CPU container's resolution) and
        ps_ftrl_rows_total by the deduped gather width per ministep."""
        from parameter_server_tpu.apps.linear.config import (
            Config,
            LearningRateConfig,
            PenaltyConfig,
            SGDConfig,
        )
        from parameter_server_tpu.apps.linear.async_sgd import (
            AsyncSGDWorker,
        )
        from parameter_server_tpu.system.postoffice import Postoffice
        from parameter_server_tpu.telemetry import registry as telreg
        from parameter_server_tpu.utils.sparse import random_sparse

        Postoffice.reset()
        try:
            conf = Config()
            conf.penalty = PenaltyConfig(type="l1", lambda_=[0.05])
            conf.learning_rate = LearningRateConfig(
                type="decay", alpha=0.5, beta=1.0
            )
            conf.async_sgd = SGDConfig(
                algo="ftrl", minibatch=256, num_slots=1 << 14,
                max_delay=0, update="sparse",
            )
            worker = AsyncSGDWorker(conf, mesh=mesh8)
            for i in range(3):
                worker.process_minibatch(random_sparse(256, 512, 8, seed=i))
            worker.executor.wait_all()
            snap = telreg.default_registry().snapshot()
            paths = snap["ps_ftrl_update_path_total"]["values"]
            assert paths.get("path=xla_rows", 0) == 3
            rows = snap["ps_ftrl_rows_total"]["values"].get("", 0)
            assert rows > 0 and rows % 3 == 0
        finally:
            Postoffice.reset()

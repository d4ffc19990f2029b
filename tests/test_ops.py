"""Ops tests: the XLA segment-sum spmv formulation vs dense, FTRL kernel
fallback parity, quantize roundtrip error bounds (CPU fallback paths; the
Pallas variants are exercised on TPU by bench/verify runs).

The spmv helpers below are the canonical formulations the fused app steps
inline (darlin/async_sgd); a Pallas spmv kernel was probed on v5e and
rejected — Mosaic has no 1-D table gather — see SURVEY §3."""

import jax
import jax.numpy as jnp
import numpy as np

from parameter_server_tpu.ops.ftrl import ftrl_update, ftrl_update_ref
from parameter_server_tpu.ops.quantize import dequantize, quantize
from parameter_server_tpu.utils.sparse import random_sparse


def spmv(vals, cols, rows, w, n):
    """Xw over localized COO (loss.h::compute's Eigen matvec)."""
    return jax.ops.segment_sum(vals * w[cols], rows, num_segments=n)


def spmv_t(vals, cols, rows, g, u):
    """X^T g (loss.h transTimes)."""
    return jax.ops.segment_sum(vals * g[rows], cols, num_segments=u)


def spmv_t_sq(vals, cols, rows, h, u):
    """(X.^2)^T h (loss.h dotTimes path)."""
    return jax.ops.segment_sum(vals * vals * h[rows], cols, num_segments=u)


class TestSpmv:
    def setup_method(self, _):
        # duplicate-free CSR (spmv_t_sq squares per entry; dup (row,col)
        # pairs would differ from the dense-merged oracle)
        from parameter_server_tpu.utils.sparse import from_dense

        rng = np.random.default_rng(0)
        dense = (rng.random((40, 60)) < 0.1) * rng.normal(size=(40, 60))
        self.b = from_dense(dense.astype(np.float32), np.sign(rng.normal(size=40)))
        loc_rows = self.b.row_ids()
        # localized: treat raw indices as unique-index space directly
        self.rows = jnp.asarray(loc_rows, jnp.int32)
        self.cols = jnp.asarray(self.b.indices, jnp.int32)
        self.vals = jnp.asarray(self.b.value_array())
        self.dense = self.b.to_dense()

    def test_spmv_matches_dense(self):
        w = np.random.default_rng(1).normal(size=60).astype(np.float32)
        out = spmv(self.vals, self.cols, self.rows, jnp.asarray(w), 40)
        np.testing.assert_allclose(np.asarray(out), self.dense @ w, rtol=2e-5, atol=1e-5)

    def test_spmv_t_matches_dense(self):
        g = np.random.default_rng(2).normal(size=40).astype(np.float32)
        out = spmv_t(self.vals, self.cols, self.rows, jnp.asarray(g), 60)
        np.testing.assert_allclose(np.asarray(out), self.dense.T @ g, rtol=2e-5, atol=1e-5)

    def test_spmv_t_sq_matches_dense(self):
        h = np.abs(np.random.default_rng(3).normal(size=40)).astype(np.float32)
        out = spmv_t_sq(self.vals, self.cols, self.rows, jnp.asarray(h), 60)
        np.testing.assert_allclose(
            np.asarray(out), (self.dense**2).T @ h, rtol=2e-5, atol=1e-5
        )


class TestFtrlOp:
    def test_fallback_matches_reference(self):
        rng = np.random.default_rng(0)
        p = 2048
        z = jnp.asarray(rng.normal(size=p), jnp.float32)
        n = jnp.abs(jnp.asarray(rng.normal(size=p), jnp.float32))
        g = jnp.asarray(rng.normal(size=p) * (rng.random(p) < 0.2), jnp.float32)
        t = g != 0
        z1, n1 = ftrl_update(z, n, g, t, alpha=0.5, beta=1.0, l1=0.1, l2=0.01)
        z2, n2 = ftrl_update_ref(z, n, g, t, alpha=0.5, beta=1.0, l1=0.1, l2=0.01)
        np.testing.assert_allclose(np.asarray(z1), np.asarray(z2), atol=1e-6)
        np.testing.assert_allclose(np.asarray(n1), np.asarray(n2), atol=1e-6)

    def test_untouched_slots_frozen(self):
        p = 1024
        z = jnp.ones(p)
        n = jnp.ones(p)
        g = jnp.ones(p)
        t = jnp.zeros(p, bool)
        z1, n1 = ftrl_update(z, n, g, t, alpha=0.5, beta=1.0, l1=0.1)
        np.testing.assert_allclose(np.asarray(z1), 1.0)
        np.testing.assert_allclose(np.asarray(n1), 1.0)

    def test_bf16_kernel_matches_reference(self):
        """_kernel_bf16 numerics (interpret mode — the same kernel body
        Mosaic compiles): z must EQUAL the f32 reference (z math is
        deterministic); stored sqrt_n must be one of the two bf16
        neighbors of the f32 value (stochastic rounding never moves
        more than one ulp); untouched slots must be bit-frozen."""
        rng = np.random.default_rng(1)
        p = 2048
        z = jnp.asarray(rng.normal(size=p), jnp.float32)
        n_f32 = jnp.abs(jnp.asarray(rng.normal(size=p), jnp.float32))
        n = n_f32.astype(jnp.bfloat16)
        g = jnp.asarray(rng.normal(size=p) * (rng.random(p) < 0.5),
                        jnp.float32)
        t = g != 0
        kw = dict(alpha=0.5, beta=1.0, l1=0.1, l2=0.01)
        zk, nk = ftrl_update(z, n, g, t, seed=jnp.uint32(9),
                             force_pallas=True, interpret=True, **kw)
        assert nk.dtype == jnp.bfloat16
        # reference on the SAME widened operands, f32 result
        zr, nr = ftrl_update_ref(z, n.astype(jnp.float32), g, t, **kw)
        np.testing.assert_allclose(np.asarray(zk), np.asarray(zr),
                                   atol=1e-6)
        # each stored value is a bf16 neighbor of the exact f32 value
        nk32 = np.asarray(nk.astype(jnp.float32))
        nr32 = np.asarray(nr)
        down = np.asarray(jnp.asarray(nr32).astype(jnp.bfloat16)
                          .astype(jnp.float32))
        ulp = np.maximum(np.abs(nr32) * 2.0**-7, 1e-30)
        assert np.all(np.abs(nk32 - nr32) <= ulp), (
            np.abs(nk32 - nr32).max(), ulp.min()
        )
        # untouched slots: exact round-trip of the stored bf16 value
        frozen = ~np.asarray(t)
        np.testing.assert_array_equal(
            nk32[frozen], np.asarray(n.astype(jnp.float32))[frozen]
        )
        del down

    def test_touched_none_equals_support_mask(self):
        """touched=None (the unquantized-push contract: membership IS
        grad's support, derived in-kernel so no table-sized mask
        operand exists — the 2^30 single-chip fit depends on it) must
        be BIT-identical to passing touched=(g != 0) explicitly, on
        the ref path, the f32 kernel, and the bf16 kernel."""
        rng = np.random.default_rng(3)
        p = 2048
        z = jnp.asarray(rng.normal(size=p), jnp.float32)
        n = jnp.abs(jnp.asarray(rng.normal(size=p), jnp.float32))
        g = jnp.asarray(
            rng.normal(size=p) * (rng.random(p) < 0.2), jnp.float32
        )
        kw = dict(alpha=0.5, beta=1.0, l1=0.1, l2=0.01)
        for extra in (
            {},  # ref fallback (cpu)
            {"force_pallas": True, "interpret": True},  # f32 kernel
        ):
            za, na = ftrl_update(z, n, g, g != 0, **kw, **extra)
            zb, nb = ftrl_update(z, n, g, None, **kw, **extra)
            np.testing.assert_array_equal(np.asarray(za), np.asarray(zb))
            np.testing.assert_array_equal(np.asarray(na), np.asarray(nb))
        nb16 = n.astype(jnp.bfloat16)
        za, na = ftrl_update(z, nb16, g, g != 0, seed=jnp.uint32(7),
                             force_pallas=True, interpret=True, **kw)
        zb, nb = ftrl_update(z, nb16, g, None, seed=jnp.uint32(7),
                             force_pallas=True, interpret=True, **kw)
        np.testing.assert_array_equal(np.asarray(za), np.asarray(zb))
        np.testing.assert_array_equal(
            np.asarray(na.astype(jnp.float32)),
            np.asarray(nb.astype(jnp.float32)),
        )

    def test_kernel_in_place_aliasing_keeps_results(self):
        """input_output_aliases={z,sqrt_n} makes the kernel update in
        place (the alias is why one chip holds a 2^30 table: no fresh
        8 GB z'/n' next to the live table). Two halves: (a) interpret
        mode reproduces the reference numerics under the donation
        contract, (b) the alias ACTUALLY SURVIVES into the lowered TPU
        program — asserted on the exported StableHLO, because the
        numeric half alone would still pass if the alias were dropped
        (and 2^30 would quietly OOM again)."""
        rng = np.random.default_rng(5)
        p = 4096
        z = jnp.asarray(rng.normal(size=p), jnp.float32)
        n = jnp.abs(jnp.asarray(rng.normal(size=p), jnp.float32))
        g = jnp.asarray(
            rng.normal(size=p) * (rng.random(p) < 0.3), jnp.float32
        )
        kw = dict(alpha=0.5, beta=1.0, l1=0.1, l2=0.01)
        zr, nr = ftrl_update_ref(z, n, g, g != 0, **kw)
        zk, nk = ftrl_update(z, n, g, None, force_pallas=True,
                             interpret=True, **kw)
        np.testing.assert_allclose(np.asarray(zk), np.asarray(zr),
                                   atol=1e-6)
        np.testing.assert_allclose(np.asarray(nk), np.asarray(nr),
                                   atol=1e-6)
        # (b) lowering contract, f32 and bf16-state variants
        import re

        for n_in, seed in ((n, None), (n.astype(jnp.bfloat16), 7)):
            exp = jax.export.export(
                jax.jit(lambda z, n, g: ftrl_update(
                    z, n, g, None, seed=(None if seed is None
                                         else jnp.uint32(seed)),
                    force_pallas=True, **kw)),
                platforms=["tpu"],
            )(z, n_in, g)
            aliases = re.findall(
                r"output_operand_alias<output_tuple_indices = \[(\d)\], "
                r"operand_index = (\d)", exp.mlir_module()
            )
            assert ("0", "0") in aliases and ("1", "1") in aliases, (
                f"z/sqrt_n not aliased in lowered TPU program: {aliases}"
            )

    def test_bf16_stochastic_rounding_unbiased(self):
        """Across many seeds the bf16 narrow must average to the exact
        f32 value (unbiased walk) — deterministic truncation would
        bias low and stall accumulators (absorption)."""
        from parameter_server_tpu.ops.ftrl import stochastic_round_bf16

        x = jnp.full(256, 1.0 + 1.0 / 512.0, jnp.float32)  # mid-ulp
        acc = np.zeros(256, np.float64)
        k = 200
        for s in range(k):
            acc += np.asarray(
                stochastic_round_bf16(x, np.uint32(s)).astype(jnp.float32)
            )
        mean = acc / k
        np.testing.assert_allclose(mean, np.asarray(x), rtol=2e-3)


class TestQuantizeOp:
    def test_error_within_one_step(self):
        rng = np.random.default_rng(0)
        x = jnp.asarray(rng.normal(size=5000), jnp.float32)
        for nbytes in (1, 2):
            q, lo, hi = quantize(x, seed=3, num_bytes=nbytes)
            back = dequantize(q, lo, hi, nbytes)
            step = float(hi - lo) / ((1 << (8 * nbytes)) - 1)
            assert float(jnp.abs(back - x).max()) <= step + 1e-6

    def test_unbiased(self):
        x = jnp.full(20000, 0.37, jnp.float32).at[0].set(0.0).at[1].set(1.0)
        q, lo, hi = quantize(x, seed=11, num_bytes=1)
        back = dequantize(q, lo, hi, 1)
        assert abs(float(back[2:].mean()) - 0.37) < 2e-3


def test_ftrl_block_rows_knob_is_math_invariant(monkeypatch):
    """block_rows (arg or PS_FTRL_BLOCK_ROWS) only retiles the grid —
    results must match the reference bit-for-bit at every block size
    (the on-chip sweep relies on this being a pure perf knob)."""
    import numpy as np

    rng = np.random.default_rng(7)
    p = 64 * 1024  # rows = 512: small enough for interpret mode, big
    # enough that the sweep below genuinely retiles (grids 64 and 8)
    z = jnp.asarray(rng.normal(size=p), jnp.float32)
    n = jnp.abs(jnp.asarray(rng.normal(size=p), jnp.float32))
    g = jnp.asarray(rng.normal(size=p), jnp.float32)
    t = jnp.asarray(rng.random(p) < 0.5, jnp.float32)
    kw = dict(alpha=0.5, beta=1.0, l1=0.1, l2=0.01)
    zr, nr = ftrl_update_ref(z, n, g, t > 0, **kw)
    # retiling must be bit-invariant KERNEL-vs-KERNEL (the math per
    # element is identical; only the grid changes) and track the jnp
    # reference to normal fp tolerance
    z0, n0 = ftrl_update(z, n, g, t, force_pallas=True, interpret=True,
                         block_rows=512, **kw)
    for br in (8, 64):
        zk, nk = ftrl_update(z, n, g, t, force_pallas=True,
                             interpret=True, block_rows=br, **kw)
        np.testing.assert_array_equal(np.asarray(zk), np.asarray(z0))
        np.testing.assert_array_equal(np.asarray(nk), np.asarray(n0))
    np.testing.assert_allclose(np.asarray(z0), np.asarray(zr), rtol=2e-5,
                               atol=2e-6)
    # the selection helper is the observable seam for the env knob
    # (bit-equality across block sizes makes an end-to-end env assert
    # vacuous by construction)
    from parameter_server_tpu.ops.ftrl import _choose_block_rows

    assert _choose_block_rows(4096, 1536) == 1024  # pow2 round-down
    assert _choose_block_rows(4096, 4096) == 4096
    assert _choose_block_rows(24, 2048) == 8       # halves to a divisor
    import pytest as _pytest

    with _pytest.raises(ValueError):
        _choose_block_rows(12, 2048)  # untileable rows fail loud
    monkeypatch.setenv("PS_FTRL_BLOCK_ROWS", "512")
    assert _choose_block_rows(4096) == 512         # env honored
    monkeypatch.setenv("PS_FTRL_BLOCK_ROWS", "bogus")
    assert _choose_block_rows(4096) == 2048        # bad env falls back


def test_ftrl_path_selection_predicate(monkeypatch):
    """Path selection is a pure predicate: Pallas everywhere by default
    (the corrected chained A/B has the kernel ahead at every size —
    see ops.ftrl.xla_min_slots), the XLA path only via the env sweep
    knob, and force_pallas pinning the kernel except where it cannot
    run (misaligned tile, unseeded bf16 narrow)."""
    from parameter_server_tpu.ops import ftrl

    monkeypatch.setattr(ftrl, "use_pallas", lambda: True)
    assert not ftrl.use_ref_path(1 << 20, False, False, False)
    assert not ftrl.use_ref_path(1 << 28, False, False, False)
    assert not ftrl.use_ref_path(1 << 30, True, True, False)
    # correctness gates hold regardless of force_pallas
    assert ftrl.use_ref_path((1 << 20) + 8, False, False, True)  # tile
    assert ftrl.use_ref_path(1 << 20, True, False, True)  # unseeded bf16
    # off-TPU always ref unless forced
    monkeypatch.setattr(ftrl, "use_pallas", lambda: False)
    assert ftrl.use_ref_path(1 << 20, False, False, False)
    # env override enables the flip for crossover sweeps
    monkeypatch.setattr(ftrl, "use_pallas", lambda: True)
    monkeypatch.setenv("PS_FTRL_XLA_MIN_SLOTS", str(1 << 16))
    assert ftrl.use_ref_path(1 << 16, False, False, False)
    assert not ftrl.use_ref_path(1 << 15, False, False, False)
    assert not ftrl.use_ref_path(1 << 16, False, False, True)  # forced

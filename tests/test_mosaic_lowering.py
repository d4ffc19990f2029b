"""Real-Mosaic lowering regression tests (no chip needed).

Round 2 shipped flash kernels validated only in Pallas interpret mode; on
first contact with the chip they failed Mosaic's (8, 128) block-tiling
check — exactly the class of bug the interpreter cannot catch.
``jax.export`` with ``platforms=['tpu']`` runs the full Pallas->Mosaic
lowering pipeline on the CPU host, so every kernel variant is lowered for
TPU in CI. This does not execute anything on a TPU, and it stops short
of the chip's own compiler: the bf16 row DMA of the sparse FTRL kernel
lowered here for months and was refused on a TPU v5 lite ("Slice shape
along dimension 0 must be aligned to tiling (8), but is 1"). Backend
compile and run are chip_smoke.py's job; this pins the lowering
contract.
"""

import jax
import jax.numpy as jnp
import pytest

from parameter_server_tpu.ops.flash_attention import flash_attention, flash_mha
from parameter_server_tpu.ops.ftrl import ftrl_update
from parameter_server_tpu.ops.ftrl_sparse import ftrl_sparse_update
from parameter_server_tpu.ops.quantize import quantize


def lower_tpu(fn, *args):
    jax.export.export(jax.jit(fn), platforms=["tpu"])(*args)


def _fa(**kw):
    def fn(q, k, v):
        return flash_attention(q, k, v, use_pallas=True, interpret=False, **kw)

    return fn


def _fa_grad(**kw):
    def fn(q, k, v):
        return jax.grad(
            lambda *a: _fa(**kw)(*a).astype(jnp.float32).sum(), argnums=(0, 1, 2)
        )(q, k, v)

    return fn


Z = jnp.zeros


@pytest.mark.parametrize(
    "shape,dtype,kw",
    [
        ((4, 1024, 64), jnp.float32, dict(causal=True)),
        ((4, 1024, 64), jnp.float32, dict(causal=False)),
        ((4, 1024, 64), jnp.bfloat16, dict(causal=True)),
        ((4, 1024, 64), jnp.float32, dict(causal=True, window=256)),
        ((2, 96, 40), jnp.float32, dict(causal=True)),  # sub-block, odd D
        ((1, 384, 128), jnp.float32, dict(causal=True)),  # S % block != 0
        # sub-SUBLANE decode shapes (the BENCH_ONCHIP small-shape
        # block-spec crash class): a speculative gamma+1 verify chunk
        # and a single-row serving query — block specs must stay
        # (8, 128)-tileable even when S < 8
        ((4, 5, 64), jnp.float32, dict(causal=True)),  # spec verify chunk
        ((4, 1, 64), jnp.float32, dict(causal=False)),  # 1-row query

        # the 512x512 default blocking with a wide head dim: the largest
        # VMEM tile shape the model paths can request
        ((2, 1024, 128), jnp.bfloat16, dict(causal=True)),
    ],
    ids=["causal", "full", "bf16", "window", "small", "s384",
         "spec_chunk", "one_row", "d128"],
)
def test_flash_fwd_and_bwd_lower(shape, dtype, kw):
    q = Z(shape, dtype)
    lower_tpu(_fa(**kw), q, q, q)
    lower_tpu(_fa_grad(**kw), q, q, q)


def test_flash_short_query_long_keys_lowers():
    """The serving decode shape: a sub-sublane query block against a
    long key axis (speculative verify reads the whole cache with a
    gamma+1-row chunk). Fwd and bwd must lower with sq < 8 < sk."""
    q = Z((4, 5, 64), jnp.float32)
    k = Z((4, 1024, 64), jnp.float32)

    def fn(q, k, v):
        return flash_attention(
            q, k, v, causal=True, q_offset=1019, use_pallas=True,
            interpret=False, with_lse=True,
        )

    lower_tpu(fn, q, k, k)

    def g(q, k, v):
        return jax.grad(
            lambda *a: fn(*a)[0].astype(jnp.float32).sum(), argnums=(0, 1, 2)
        )(q, k, v)

    lower_tpu(g, q, k, k)


def test_flash_traced_offsets_lower():
    q = Z((4, 512, 64), jnp.float32)

    def fn(q, k, v, off):
        return flash_attention(
            q, k, v, causal=True, q_offset=off, k_offset=off,
            use_pallas=True, interpret=False, with_lse=True,
        )

    lower_tpu(fn, q, q, q, jnp.int32(512))


def test_flash_gqa_lowers():
    x = Z((2, 512, 256), jnp.float32)
    kv = Z((2, 512, 64), jnp.float32)

    def fn(a, b, c):
        return flash_mha(
            a, b, c, 8, n_kv_heads=2, causal=True,
            use_pallas=True, interpret=False,
        )

    lower_tpu(fn, x, kv, kv)


def test_ftrl_kernel_lowers():
    p = 1 << 14

    def fn(z, n, g, t):
        return ftrl_update(
            z, n, g, t, alpha=0.1, beta=1.0, l1=1.0, l2=0.1, force_pallas=True
        )

    lower_tpu(fn, Z(p), Z(p), Z(p), Z(p, jnp.bool_))


def test_ftrl_bf16_kernel_lowers():
    """The bf16-sqrt_n variant (on-core PRNG stochastic narrow) must
    lower under real Mosaic rules — bitcasts, prng_seed/random_bits,
    and the bf16 VMEM output ref."""
    p = 1 << 14

    def fn(z, n, g, t, seed):
        return ftrl_update(
            z, n, g, t, alpha=0.1, beta=1.0, l1=1.0, l2=0.1,
            seed=seed, force_pallas=True,
        )

    lower_tpu(
        fn, Z(p), Z(p, jnp.bfloat16), Z(p), Z(p, jnp.bool_),
        jnp.uint32(3),
    )


def test_ftrl_sparse_kernel_lowers():
    """The fused sparse gather→update→scatter kernel: scalar-prefetched
    row ids, manual double-buffered row DMAs from/to ANY-space refs,
    aliased in-place outputs — all must survive real Mosaic rules."""
    p, u = 1 << 14, 1024

    def fn(z, n, rel, ok, g):
        return ftrl_sparse_update(
            z, n, rel, ok, g, alpha=0.1, beta=1.0, l1=1.0, l2=0.1,
            force_pallas=True,
        )

    lower_tpu(fn, Z(p), Z(p), Z(u, jnp.int32), Z(u, jnp.bool_), Z(u))


def test_ftrl_sparse_chunked_kernel_lowers(monkeypatch):
    """Past one SMEM chunk of row ids the kernel runs inside a scan
    over equal chunks: the aliased in-place call must lower as a scan
    body too."""
    from parameter_server_tpu.ops import ftrl_sparse

    monkeypatch.setattr(ftrl_sparse, "_SMEM_CHUNK_ROWS", 256)
    p, u = 1 << 14, 1024

    def fn(z, n, rel, ok, g):
        return ftrl_sparse_update(
            z, n, rel, ok, g, alpha=0.1, beta=1.0, l1=1.0, l2=0.1,
            force_pallas=True, block_rows=64,
        )

    lower_tpu(fn, Z(p), Z(p), Z(u, jnp.int32), Z(u, jnp.bool_), Z(u))


def test_ftrl_sparse_donated_step_lowers():
    """The production form: an enclosing donated jit around the aliased
    kernel (what the fused train step compiles to)."""
    p, u = 1 << 14, 1024

    def fn(z, n, rel, ok, g):
        return ftrl_sparse_update(
            z, n, rel, ok, g, alpha=0.1, beta=1.0, l1=1.0, l2=0.1,
            force_pallas=True,
        )

    jax.export.export(jax.jit(fn, donate_argnums=(0, 1)), platforms=["tpu"])(
        Z(p), Z(p), Z(u, jnp.int32), Z(u, jnp.bool_), Z(u)
    )


def test_quantize_kernel_lowers():
    def fn(x, seed):
        return quantize(x, seed, num_bytes=1, force_pallas=True)

    lower_tpu(fn, Z((512, 256), jnp.float32), jnp.uint32(7))


def test_prefill_flash_attention_lowers():
    # the generate path's prefill uses the flash kernel on TPU backends,
    # folded/broadcast from GQA-narrow K/V — lower that exact plumbing
    from parameter_server_tpu.models.transformer import _prefill_attention

    q = Z((2, 256, 4, 64), jnp.float32)
    kv = Z((2, 256, 2, 64), jnp.float32)

    def fn(q, k, v):
        return _prefill_attention(
            q, k, v, None, use_flash=True, interpret=False
        )

    lower_tpu(fn, q, kv, kv)

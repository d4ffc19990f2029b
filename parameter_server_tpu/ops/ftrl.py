"""Fused FTRL-proximal update — Pallas TPU kernel.

The server-side hot op (ref FTRLEntry::Set, async_sgd.h:131-151) as one
VMEM-resident pass: reads (z, √n, g, touched), emits (z', √n') with the
weight derivation inlined, so the whole per-shard state update is a single
HBM round trip. Grid tiles the slot dimension in (8,128)-aligned blocks.

``sqrt_n`` may be stored bf16 (``SGDConfig.ftrl_state_dtype`` — 12
B/slot table state): math widens to f32 and the write-back narrows with
STOCHASTIC rounding (on-core PRNG in the kernel; hash dither in the jnp
path) — deterministic truncation would saturate the accumulator by
absorption once n >> per-update increment, freezing the per-coordinate
learning-rate decay for hot features.

``ftrl_update(z, n, g, touched, ...)`` auto-selects: Pallas on TPU backends,
pure-jnp elsewhere (bit-identical math in f32; tests compare both).
"""
# bit-identical: this module is under the replay bit-identity contract (pslint determinism pass)

from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp
import numpy as np

from . import use_pallas

_LANES = 128
_SUBLANES = 8
_TILE = _LANES * _SUBLANES


def xla_min_slots() -> int:
    """Dense-update formulation flip point, in slots — DISABLED by
    default (2^62 ≈ never): no measurement on the current code decides
    between the Pallas sweep and the XLA reference at big shards. The
    comparison that can is a chip run of the dense cell
    (``criteo_dense.text``) with the flip forced either way, both
    updates donated so the kernel runs with its production aliasing
    (ROADMAP "Design" 3; no crossover → stays 2^62). Env
    ``PS_FTRL_XLA_MIN_SLOTS`` remains as the sweep override; the value
    is baked at trace time per shape (jit static caching)."""
    try:
        return int(os.environ.get("PS_FTRL_XLA_MIN_SLOTS", 1 << 62))
    except ValueError:
        return 1 << 62


def use_ref_path(p: int, bf16_n: bool, has_seed: bool,
                 force_pallas: bool) -> bool:
    """Pure path-selection predicate for ``ftrl_update`` (testable off
    device): the jnp/XLA reference path runs off-TPU, for non-tileable
    shards, for an unseeded bf16 narrow, and — by measurement — for
    big tables (``xla_min_slots``). ``force_pallas`` pins the kernel
    for A/B sweeps and kernel tests, but never onto a shard the kernel
    cannot tile or narrow correctly."""
    if not force_pallas and not use_pallas():
        return True
    if p % _TILE != 0 or (bf16_n and not has_seed):
        return True
    if force_pallas:
        return False
    return p >= xla_min_slots()


def dither_hash_u32(i: jnp.ndarray, seed) -> jnp.ndarray:
    """THE dither stream: a counter-based integer hash of
    (index, seed) — cheap, stateless, vectorized; rounding dither
    needs uniformity, not cryptographic quality. ``i`` is a uint32
    index array (position counters, or the sparse kernel's u-position
    map); ``seed`` a uint32 scalar. Single copy shared by
    :func:`stochastic_round_bf16`, :func:`_hash_dither_bits`, and the
    sparse kernel's dither substitute (ops/ftrl_sparse.py), so the
    interpret-mode parity contract — same (index, seed) in, same
    dither out — cannot drift between the jnp path and a kernel."""
    h = (i * np.uint32(2654435761)) ^ (
        jnp.asarray(seed, jnp.uint32) * np.uint32(0x9E3779B9)
    )
    h = (h ^ (h >> 15)) * np.uint32(0x85EBCA6B)
    h = (h ^ (h >> 13)) * np.uint32(0xC2B2AE35)
    return h ^ (h >> 16)


def stochastic_round_bf16(x: jnp.ndarray, seed) -> jnp.ndarray:
    """Unbiased f32 -> bf16 narrowing (jnp path): add hash-derived
    uniform dither in [0, 2^16) to the f32 bits, then truncate the low
    mantissa bits. E[rounded] = x, so a bf16 accumulator performs an
    unbiased walk instead of stalling by absorption. The dither indexes
    :func:`dither_hash_u32` by flat position. Values whose f32 form is
    already exactly bf16 (e.g. untouched slots round-tripped through
    storage) are returned unchanged for every dither draw."""
    bits = jax.lax.bitcast_convert_type(x.astype(jnp.float32), jnp.uint32)
    i = jax.lax.iota(jnp.uint32, max(1, x.size)).reshape(x.shape)
    rnd = dither_hash_u32(i, jnp.uint32(seed)) & np.uint32(0xFFFF)
    out = (bits + rnd) & np.uint32(0xFFFF0000)
    return jax.lax.bitcast_convert_type(out, jnp.float32).astype(
        jnp.bfloat16
    )


def _ftrl_math(z, n, g, *, alpha, beta, l1, l2):
    """The FTRL-proximal step on f32 operands — THE single copy of the
    math, shared by the jnp reference and both kernel variants (a fix
    applied to one copy cannot miss the others)."""
    eta = alpha / (n + beta)
    zt = -z * eta
    w = jnp.sign(zt) * jnp.maximum(jnp.abs(zt) - l1 * eta, 0.0) / (1.0 + l2 * eta)
    n_new = jnp.sqrt(n * n + g * g)
    sigma = (n_new - n) / alpha
    z_new = z + g - sigma * w
    return z_new, n_new


def ftrl_update_ref(z, sqrt_n, grad, touched, *, alpha, beta, l1, l2,
                    seed=None):
    """Pure-jnp reference (identical to updaters.FTRLUpdater.apply math).
    bf16 sqrt_n widens for math; the narrow is stochastically rounded
    when ``seed`` is given, else deterministically. ``touched=None``
    derives membership as ``grad != 0`` (the unquantized-push
    contract, async_sgd.make_push_touched)."""
    if touched is None:
        touched = grad != 0
    store_dtype = sqrt_n.dtype
    sqrt_n = sqrt_n.astype(jnp.float32)
    z_new, sqrt_n_new = _ftrl_math(
        z, sqrt_n, grad, alpha=alpha, beta=beta, l1=l1, l2=l2
    )
    n_out = jnp.where(touched, sqrt_n_new, sqrt_n)
    if store_dtype == jnp.bfloat16 and seed is not None:
        n_out = stochastic_round_bf16(n_out, seed)
    return jnp.where(touched, z_new, z), n_out.astype(store_dtype)


def _kernel(z_ref, n_ref, g_ref, t_ref, z_out, n_out, *, alpha, beta, l1, l2):
    # t_ref=None: membership derived in-block as g != 0 (the
    # unquantized-push contract) — at 2^30 slots the f32 mask operand
    # alone is 4 GB of HBM, so deriving it is what lets the table fit
    z = z_ref[:]
    n = n_ref[:]
    g = g_ref[:]
    z_new, n_new = _ftrl_math(z, n, g, alpha=alpha, beta=beta, l1=l1, l2=l2)
    keep = (t_ref[:] > 0) if t_ref is not None else (g != 0)
    z_out[:] = jnp.where(keep, z_new, z)
    n_out[:] = jnp.where(keep, n_new, n)


def _kernel_nomask(z_ref, n_ref, g_ref, z_out, n_out, *, alpha, beta, l1,
                   l2):
    _kernel(z_ref, n_ref, g_ref, None, z_out, n_out,
            alpha=alpha, beta=beta, l1=l1, l2=l2)


def _hash_dither_bits(seed_scalar, shape):
    """Interpret-mode dither source: the same counter-hash used by
    :func:`stochastic_round_bf16`, as raw uint32 bits. Interpret mode
    cannot execute ``pltpu.prng_*`` (no CPU lowering), so the kernel
    body is tested with this substitute while the PRNG path itself is
    pinned by tests/test_mosaic_lowering.py."""
    n = 1
    for d in shape:
        n *= d
    i = jax.lax.iota(jnp.uint32, n).reshape(shape)
    return dither_hash_u32(i, seed_scalar.astype(jnp.uint32))


def _kernel_bf16(z_ref, n_ref, g_ref, t_ref, seed_ref, z_out, n_out, *,
                 alpha, beta, l1, l2, dither_fn=None):
    """bf16-``sqrt_n`` variant: widen in VMEM, stochastically round the
    narrow with the on-core PRNG (per-block stream — block-correlated
    rounding noise is biased in aggregate, ops/quantize.py note).
    ``dither_fn``: interpret-mode substitute for the PRNG (see
    :func:`_hash_dither_bits`). ``t_ref=None``: membership derived
    in-block as ``g != 0`` (see :func:`_kernel`)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    z = z_ref[:]
    n = n_ref[:].astype(jnp.float32)
    g = g_ref[:]
    z_new, n_new = _ftrl_math(z, n, g, alpha=alpha, beta=beta, l1=l1, l2=l2)
    keep = (t_ref[:] > 0) if t_ref is not None else (g != 0)
    z_out[:] = jnp.where(keep, z_new, z)
    n_keep = jnp.where(keep, n_new, n)
    # stochastic f32->bf16: dither the low 16 bits, truncate. An
    # already-bf16-exact value (untouched slots) is unchanged by
    # construction (its low mantissa bits are zero).
    if dither_fn is None:
        pltpu.prng_seed(seed_ref[0] + pl.program_id(0))
        rnd = pltpu.bitcast(
            pltpu.prng_random_bits(n_keep.shape), jnp.uint32
        )
        bits = pltpu.bitcast(n_keep, jnp.uint32)
        rounded = (bits + (rnd & jnp.uint32(0xFFFF))) & jnp.uint32(0xFFFF0000)
        n_out[:] = pltpu.bitcast(rounded, jnp.float32).astype(jnp.bfloat16)
    else:
        rnd = dither_fn(seed_ref[0] + pl.program_id(0), n_keep.shape)
        bits = jax.lax.bitcast_convert_type(n_keep, jnp.uint32)
        rounded = (bits + (rnd & jnp.uint32(0xFFFF))) & jnp.uint32(0xFFFF0000)
        n_out[:] = jax.lax.bitcast_convert_type(
            rounded, jnp.float32
        ).astype(jnp.bfloat16)


def _kernel_bf16_nomask(z_ref, n_ref, g_ref, seed_ref, z_out, n_out, *,
                        alpha, beta, l1, l2, dither_fn=None):
    _kernel_bf16(z_ref, n_ref, g_ref, None, seed_ref, z_out, n_out,
                 alpha=alpha, beta=beta, l1=l1, l2=l2,
                 dither_fn=dither_fn)


def _choose_block_rows(rows: int, requested: "int | None" = None) -> int:
    """Resolve the Pallas tile height: the requested value (arg, else
    PS_FTRL_BLOCK_ROWS, else 2048) rounded DOWN to a power of two ≥ 8,
    then halved until it divides ``rows``. Pure so the selection is
    directly testable — a naive halving loop preserved odd factors
    (1536 → ... → 3 → 1) and could emit a sub-(8,128)-tile block."""
    # loud, not partial: a non-multiple-of-8 rows cannot be tiled by
    # any power-of-two ≥ 8 and grid=rows//br would silently skip the
    # tail. ValueError, not assert: input validation must survive
    # python -O (ftrl_update's p % _TILE gate guarantees it; direct
    # callers get the error)
    if rows % 8:
        raise ValueError(f"rows={rows} not a multiple of 8")
    if requested is None:
        try:
            requested = int(os.environ.get("PS_FTRL_BLOCK_ROWS", 2048))
        except ValueError:
            requested = 2048
    br = 1 << max(3, int(requested).bit_length() - 1)
    while rows % br and br > 8:
        br //= 2
    return br


# The public z/n entry point is used by parity tests and snapshot
# paths that keep their inputs; the fused train steps donate at THEIR
# boundary (and the Pallas path aliases in-block via
# input_output_aliases), so jit-level donation here would only poison
# callers' buffers without removing a copy.
@functools.partial(
    jax.jit,  # no-donate: see above — callers keep their z/n inputs
    static_argnames=("alpha", "beta", "l1", "l2", "force_pallas",
                     "interpret", "block_rows"),
)
def ftrl_update(
    z: jax.Array,
    sqrt_n: jax.Array,
    grad: jax.Array,
    touched: jax.Array,
    *,
    alpha: float,
    beta: float,
    l1: float,
    l2: float = 0.0,
    seed=None,
    force_pallas: bool = False,
    interpret: bool = False,
    block_rows: "int | None" = None,
):
    """Fused update over a 1-D slot shard. touched: bool/float mask,
    or ``None`` to derive membership in-kernel as ``grad != 0`` (valid
    exactly when the push is unquantized — async_sgd.make_push_touched
    — and worth it: no table-sized mask operand, which at 2^30 slots
    saves 4 GB of HBM).
    ``seed`` (traced uint32 scalar) drives the stochastic narrow when
    ``sqrt_n`` is stored bf16; without it the bf16 narrow truncates
    (callers that care about long-horizon LR decay must pass one).

    The Pallas kernel updates z/sqrt_n IN PLACE (input_output_aliases
    — what lets one chip hold a 2^30 table). Callers whose enclosing
    jit DONATES the state (the fused production step, max_delay=0)
    get the update copy-free; at a non-donating call site XLA inserts
    defensive whole-table copies of z/sqrt_n to preserve the caller's
    buffers — correct, but one extra table read+write. A timing
    must therefore be of the donated form.

    ``block_rows`` tiles the slot dimension (default 2048 = 1 MB/ref;
    env ``PS_FTRL_BLOCK_ROWS`` overrides so a cross-process on-chip
    block-size sweep needs no code edit); non-dividing values round
    down to the largest dividing power-of-two slice. The env value is
    baked at FIRST trace of the ``block_rows=None`` variant (jit
    static caching) — an in-process sweep must pass ``block_rows``
    explicitly, which retraces per value.

    Falls back to the jnp reference path off-TPU and for shards that are not
    tile-aligned, so any caller can use it unconditionally.
    """
    p = z.shape[0]
    bf16_n = sqrt_n.dtype == jnp.bfloat16
    if z.ndim != 1 or use_ref_path(
        p, bf16_n, seed is not None, force_pallas
    ):
        return ftrl_update_ref(
            z, sqrt_n, grad,
            None if touched is None else touched.astype(jnp.float32) > 0,
            alpha=alpha, beta=beta, l1=l1, l2=l2, seed=seed,
        )
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    rows = p // _LANES
    shape2d = (rows, _LANES)
    # big blocks: 6 refs/block (4 in + 2 out) must fit VMEM, but a tiny
    # (8,128) block makes the grid enormous on multi-M-slot tables (2^26
    # slots -> 65536 steps) and grid overhead swamps the math. 2048x128
    # = 1MB/ref keeps the grid <= a few hundred steps at every real size.
    block_rows = _choose_block_rows(rows, block_rows)
    grid = (rows // block_rows,)
    spec = pl.BlockSpec(
        (block_rows, _LANES), lambda i: (i, 0), memory_space=pltpu.VMEM
    )
    out_shape = (
        jax.ShapeDtypeStruct(shape2d, z.dtype),
        jax.ShapeDtypeStruct(shape2d, sqrt_n.dtype),
    )
    # z/sqrt_n update IN PLACE (input_output_aliases): without the
    # alias the call materializes fresh z'/n' buffers next to the live
    # table — at 2^30 slots that extra 8 GB is the difference between
    # one chip holding the table or RESOURCE_EXHAUSTED (the donated
    # step's own aliasing only covers program input->output, not this
    # call's operands). Block i is read before it is written, so the
    # grid pipeline never observes its own output.
    operands = [z.reshape(shape2d), sqrt_n.reshape(shape2d),
                grad.reshape(shape2d)]
    in_specs = [spec, spec, spec]
    if touched is not None:
        operands.append(touched.astype(jnp.float32).reshape(shape2d))
        in_specs.append(spec)
    if bf16_n:
        kernel = functools.partial(
            _kernel_bf16 if touched is not None else _kernel_bf16_nomask,
            alpha=alpha, beta=beta, l1=l1, l2=l2,
            dither_fn=_hash_dither_bits if interpret else None,
        )
        operands.append(jnp.asarray(seed, jnp.int32).reshape(1))
        in_specs.append(pl.BlockSpec(memory_space=pltpu.SMEM))
    else:
        kernel = functools.partial(
            _kernel if touched is not None else _kernel_nomask,
            alpha=alpha, beta=beta, l1=l1, l2=l2,
        )
    z_new, n_new = pl.pallas_call(
        kernel,
        grid=grid,
        out_shape=out_shape,
        in_specs=in_specs,
        out_specs=(spec, spec),
        input_output_aliases={0: 0, 1: 1},
        interpret=interpret,
    )(*operands)
    return z_new.reshape(p), n_new.reshape(p)

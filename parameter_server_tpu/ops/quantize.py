"""Stochastic fixed-point quantization — Pallas TPU kernel.

Device-side half of the fixing_float filter (ref src/filter/fixing_float.h):
compress push payloads to uint8/uint16 with stochastic rounding before they
cross chips, decompress after. The kernel fuses min/max-normalize +
add-noise + floor in VMEM using the on-core PRNG; outside TPU the jnp
reference path (filter/fixing_float.quantize_jax) is used.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

# re-exported: the production push/pull wires key off qops.use_pallas()
# (async_sgd.make_push_reduce)
from . import use_pallas


_LANES = 128
_SUBLANES = 8
_TILE = _LANES * _SUBLANES


def _kernel(x_ref, lo_ref, hi_ref, seed_ref, out_ref, *, levels):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    # fold the grid position into the seed: every block must draw its OWN
    # noise, not replay block 0's stream (block-correlated rounding noise
    # is biased in aggregate)
    pltpu.prng_seed(seed_ref[0] + pl.program_id(0))
    x = x_ref[:]
    lo = lo_ref[0]
    hi = hi_ref[0]
    scaled = (x - lo) / (hi - lo) * levels
    bits = pltpu.bitcast(pltpu.prng_random_bits(x.shape), jnp.uint32)
    # uniform [0,1) noise from the top 24 bits (mosaic lacks uint32->f32;
    # the value fits int32, so route the cast through it)
    noise = (bits >> 8).astype(jnp.int32).astype(jnp.float32) * (1.0 / (1 << 24))
    q = jnp.clip(jnp.floor(scaled + noise), 0.0, levels)
    out_ref[:] = q


def quantize_traced(x: jax.Array, seed, *, num_bytes: int = 1):
    """Traceable quantize for use INSIDE jitted/shard_mapped steps (the
    production push/pull wire, async_sgd.make_push_reduce): ``seed`` is a
    traced int32 scalar. On TPU this lowers to the fused Pallas kernel;
    elsewhere to the jnp reference chain."""
    from ..filter.fixing_float import quantize_jax

    if not use_pallas():
        key = jax.random.fold_in(
            jax.random.PRNGKey(0x9A17), jnp.asarray(seed, jnp.uint32)
        )
        return quantize_jax(x, num_bytes, key)
    return _quantize_pallas(x, jnp.asarray(seed, jnp.int32), num_bytes)


def _quantize_pallas(x: jax.Array, seed, num_bytes: int):
    levels = float((1 << (8 * num_bytes)) - 1)
    lo = jnp.min(x)
    hi = jnp.maximum(jnp.max(x), lo + 1e-12)
    dt = jnp.uint8 if num_bytes == 1 else jnp.uint16
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    n = x.shape[0]
    # big blocks (same lesson as ops/ftrl.py): an (8,128) block makes the
    # grid enormous on multi-M-slot shards and grid overhead dominates.
    # Large arrays pad up to a whole 2048x128 block (≤1MB of padding —
    # lo/hi come from the UNpadded x, and padded tail rows are sliced
    # off) so non-power-of-two shard sizes still run big blocks; small
    # arrays fall back to the largest power-of-two divisor.
    block_rows = 2048
    if n >= _LANES * block_rows:
        pad = (-n) % (_LANES * block_rows)
    else:
        pad = (-n) % _TILE
    xp = jnp.pad(x, (0, pad)).reshape(-1, _LANES)
    rows = xp.shape[0]
    while rows % block_rows:
        block_rows //= 2
    spec = pl.BlockSpec(
        (block_rows, _LANES), lambda i: (i, 0), memory_space=pltpu.VMEM
    )
    q = pl.pallas_call(
        functools.partial(_kernel, levels=levels),
        grid=(rows // block_rows,),
        out_shape=jax.ShapeDtypeStruct(xp.shape, jnp.float32),
        in_specs=[
            spec,
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ],
        out_specs=spec,
    )(
        xp,
        lo.reshape(1),
        hi.reshape(1),
        seed.reshape(1),
    )
    return q.reshape(-1)[:n].astype(dt), lo, hi


@functools.partial(jax.jit, static_argnames=("num_bytes", "force_pallas"))
def quantize(x: jax.Array, seed, *, num_bytes: int = 1, force_pallas: bool = False):
    """Quantize a 1-D float array to n-byte fixed point.

    Returns (q, lo, hi); q is uint8/uint16. Padding to the TPU tile is
    handled internally.
    """
    from ..filter.fixing_float import quantize_jax

    if not (force_pallas or use_pallas()):
        return quantize_jax(x, num_bytes, jax.random.PRNGKey(seed))
    return _quantize_pallas(x, jnp.asarray(seed, jnp.int32), num_bytes)


def dequantize(q: jax.Array, lo, hi, num_bytes: int = 1) -> jax.Array:
    from ..filter.fixing_float import dequantize_jax

    return dequantize_jax(q, lo, hi, num_bytes)

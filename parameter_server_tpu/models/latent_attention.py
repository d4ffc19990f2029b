"""Latent attention (MLA): low-rank query and key/value paths, one rotary
key shared by every head, interleaved rope with YaRN frequencies.

The training side of ``LMConfig(layers=(("mla", ...), ...))``. Per token
``h`` (the layer's normed input):

    c_q = RMSNorm(h W_qa);  q = c_q W_qb  -> heads of [q_nope, q_rope]
    [c_kv, k_r] = h W_kva;  c_kv = RMSNorm(c_kv)
    [k_nope, v] per head = c_kv W_kvb

``q_rope`` and the single ``k_r`` (broadcast over heads) are rotated by
position; ``q = [q_nope, q_rope]``, ``k = [k_nope, k_r]``; attention is
causal softmax(q k s) v with ``s = softmax_scale(cfg)``. The flash
kernels (ops/flash_attention.py) scale by 1/sqrt(D) themselves, so the
query is pre-multiplied by ``s sqrt(D)``; they want one width for q, k
and v, so a narrower v is zero-padded to it and the pad sliced off.

Only the training forward lives here: a latent cache for decoding is not
built, and the serving forwards refuse the layer kind by name.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np


@dataclasses.dataclass(frozen=True)
class YarnRope:
    """YaRN's change of the rotary frequencies (Peng et al. 2023), with
    the keys of the DeepSeek-V3 family's ``rope_parameters``. Latent
    attention's rotary key and the "mha" / "swa" layers of
    ``models/transformer.py`` read it through the same two functions,
    ``rope_inv_freq`` and ``rope_attention_factor``."""

    factor: float
    original_max_position: int
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    mscale: float = 1.0
    mscale_all_dim: float = 0.0
    # the query at position p is scaled by 1 + beta ln(1 + floor(p /
    # original_max_position)) (``llama_4_scaling_beta``); 0 = off
    position_scale_beta: float = 0.0
    # the factor on cos and sin where the description states it
    # (``attention_factor``): used as given, in place of the mscales
    attention_factor: Optional[float] = None


@dataclasses.dataclass(frozen=True)
class MLAConfig:
    q_lora_rank: int
    kv_lora_rank: int
    qk_nope_head_dim: int
    qk_rope_head_dim: int
    v_head_dim: int
    # True: dimensions (2i, 2i+1) form a rotary pair; False: (i, i+half)
    rope_interleave: bool = True
    yarn: Optional[YarnRope] = None

    def __post_init__(self):
        if self.qk_rope_head_dim % 2:
            raise ValueError(
                f"qk_rope_head_dim pairs dimensions: {self.qk_rope_head_dim} "
                "must be even"
            )
        if self.v_head_dim > self.qk_head_dim:
            raise ValueError(
                f"v_head_dim {self.v_head_dim} > qk_head_dim "
                f"{self.qk_head_dim}: the attention kernels take one width "
                "(a narrower v is padded, a wider one is not built)"
            )

    @property
    def qk_head_dim(self) -> int:
        return self.qk_nope_head_dim + self.qk_rope_head_dim


def _yarn_mscale(factor: float, mscale: float) -> float:
    return 0.1 * mscale * math.log(factor) + 1.0 if factor > 1 else 1.0


def rope_inv_freq(dim: int, theta: float, yarn: Optional[YarnRope]):
    """``dim/2`` rotary frequencies (float64 NumPy): theta^(-2i/dim), and
    under YaRN the blend of those (extrapolation) with the same divided
    by ``factor`` (interpolation) over the linear ramp between the two
    correction dimensions."""
    pos_freqs = theta ** (np.arange(0, dim, 2, dtype=np.float64) / dim)
    if yarn is None:
        return 1.0 / pos_freqs

    def correction_dim(rotations: float) -> float:
        return (
            dim * math.log(yarn.original_max_position
                           / (rotations * 2 * math.pi))
        ) / (2 * math.log(theta))

    low = max(math.floor(correction_dim(yarn.beta_fast)), 0)
    high = min(math.ceil(correction_dim(yarn.beta_slow)), dim - 1)
    if low == high:
        high += 0.001  # the family's guard against a zero-width ramp
    ramp = np.clip((np.arange(dim // 2) - low) / (high - low), 0.0, 1.0)
    extrapolation = 1.0 - ramp
    return (
        (1.0 / (yarn.factor * pos_freqs)) * (1.0 - extrapolation)
        + (1.0 / pos_freqs) * extrapolation
    )


def rope_attention_factor(yarn: Optional[YarnRope]) -> float:
    """The factor on cos and sin: ``attention_factor`` where it is
    stated; else mscale(factor, mscale) over mscale(factor,
    mscale_all_dim) when both keys are set (1 when they are equal), the
    plain mscale(factor) otherwise."""
    if yarn is None:
        return 1.0
    if yarn.attention_factor is not None:
        return yarn.attention_factor
    if yarn.mscale and yarn.mscale_all_dim:
        return _yarn_mscale(yarn.factor, yarn.mscale) / _yarn_mscale(
            yarn.factor, yarn.mscale_all_dim
        )
    return _yarn_mscale(yarn.factor, 1.0)


def softmax_scale(mla: MLAConfig) -> float:
    """qk_head_dim^(-1/2) m^2, m = 0.1 mscale_all_dim ln(factor) + 1
    (the DeepSeek-V3 convention for ``mscale_all_dim``)."""
    scale = mla.qk_head_dim ** -0.5
    if mla.yarn is not None and mla.yarn.mscale_all_dim:
        m = _yarn_mscale(mla.yarn.factor, mla.yarn.mscale_all_dim)
        scale *= m * m
    return scale


def rope_tables(positions, mla: MLAConfig, theta: float):
    """(cos, sin) [..., rope/2] in f32 for integer ``positions``."""
    inv = jnp.asarray(
        rope_inv_freq(mla.qk_rope_head_dim, theta, mla.yarn), jnp.float32
    )
    ang = jnp.asarray(positions, jnp.float32)[..., None] * inv
    f = rope_attention_factor(mla.yarn)
    return jnp.cos(ang) * f, jnp.sin(ang) * f


def rotate_pairs(x: jax.Array, cos, sin, interleave: bool) -> jax.Array:
    """Rotate the pairs of ``x`` [..., rope] by the tables. Interleaved
    pairs are (2i, 2i+1), and the result is written de-interleaved (all
    first members, then all second members): query and key go through
    the same permutation, which leaves every q . k unchanged."""
    if interleave:
        pairs = x.reshape(*x.shape[:-1], x.shape[-1] // 2, 2)
        x1, x2 = pairs[..., 0], pairs[..., 1]
    else:
        x1, x2 = jnp.split(x, 2, axis=-1)
    c, s = cos.astype(x.dtype), sin.astype(x.dtype)
    return jnp.concatenate([x1 * c - x2 * s, x1 * s + x2 * c], -1)


def position_scale(positions, yarn: Optional[YarnRope]):
    """1 + beta ln(1 + floor(pos / original_max_position)) in f32, or
    None where the configuration has no such scale."""
    if yarn is None or not yarn.position_scale_beta:
        return None
    steps = jnp.floor(
        jnp.asarray(positions, jnp.float32) / yarn.original_max_position
    )
    return 1.0 + yarn.position_scale_beta * jnp.log1p(steps)


def rms_norm(x: jax.Array, scale: jax.Array, eps: float) -> jax.Array:
    """RMSNorm with its statistics in f32, result in ``x.dtype``."""
    x32 = x.astype(jnp.float32)
    y = x32 * jax.lax.rsqrt(jnp.mean(x32 * x32, -1, keepdims=True) + eps)
    return (y * scale.astype(jnp.float32)).astype(x.dtype)


def init_mla(key, d_model: int, n_heads: int, mla: MLAConfig, std: float):
    ks = jax.random.split(key, 5)
    shapes = {
        "wq_a": (d_model, mla.q_lora_rank),
        "wq_b": (mla.q_lora_rank, n_heads * mla.qk_head_dim),
        "wkv_a": (d_model, mla.kv_lora_rank + mla.qk_rope_head_dim),
        "wkv_b": (mla.kv_lora_rank,
                  n_heads * (mla.qk_nope_head_dim + mla.v_head_dim)),
        "wo": (n_heads * mla.v_head_dim, d_model),
    }
    p = {
        name: std * jax.random.normal(k, shape, jnp.float32)
        for k, (name, shape) in zip(ks, shapes.items())
    }
    p["q_norm"] = jnp.ones((mla.q_lora_rank,), jnp.float32)
    p["kv_norm"] = jnp.ones((mla.kv_lora_rank,), jnp.float32)
    return p


def mla_qkv(h, lp, mla: MLAConfig, n_heads: int, eps: float, tables, qscale,
            dtype):
    """The latent projections: ``h`` [B, S, d] (normed) to head-major
    ``q, k, v`` [B*H, S, qk_head_dim], the query carrying the softmax
    scale the kernels do not apply (``qscale`` [S] or a number: the
    scale over 1/sqrt(D), times the position scale)."""
    b, s, _ = h.shape
    nope, rope, vd = mla.qk_nope_head_dim, mla.qk_rope_head_dim, mla.v_head_dim
    cast = lambda k: lp[k].astype(dtype)  # noqa: E731
    c_q = rms_norm(h @ cast("wq_a"), lp["q_norm"], eps)
    q = (c_q @ cast("wq_b")).reshape(b, s, n_heads, nope + rope)
    kv_a = h @ cast("wkv_a")
    c_kv = rms_norm(kv_a[..., : mla.kv_lora_rank], lp["kv_norm"], eps)
    k_r = kv_a[..., mla.kv_lora_rank:].reshape(b, s, 1, rope)
    kv = (c_kv @ cast("wkv_b")).reshape(b, s, n_heads, nope + vd)
    cos, sin = tables
    q_r = rotate_pairs(q[..., nope:], cos, sin, mla.rope_interleave)
    k_r = rotate_pairs(k_r, cos, sin, mla.rope_interleave)
    q = jnp.concatenate([q[..., :nope], q_r], -1)
    q = (q * jnp.asarray(qscale, jnp.float32).reshape(-1, 1, 1)).astype(dtype)
    k = jnp.concatenate(
        [kv[..., :nope], jnp.broadcast_to(k_r, (b, s, n_heads, rope))], -1
    )
    v = kv[..., nope:]
    if vd < nope + rope:
        v = jnp.pad(v, ((0, 0),) * 3 + ((0, nope + rope - vd),))

    def heads(t):  # [B, S, H, D] -> [B*H, S, D]
        return t.transpose(0, 2, 1, 3).reshape(b * n_heads, s, nope + rope)

    return heads(q), heads(k), heads(v)

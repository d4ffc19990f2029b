"""Kimi Delta Attention (KDA): a linear-attention layer whose state is
updated by the gated delta rule with a per-channel decay (arXiv
2510.26692), laid out as ``flash-linear-attention``'s
``KimiDeltaAttention`` is.

The training side of ``LMConfig(layers=(("kda", ...), ...))``. Per token
``h`` (the layer's normed input), per head (K = V = ``head_dim``):

    q~, k~, v~ = SiLU(conv(h W_q)), SiLU(conv(h W_k)), SiLU(conv(h W_v))
    q = q~ / |q~|_2,  k = k~ / |k~|_2,  v = v~
    g = -exp(A_log) softplus((h W_fa) W_fb + dt_bias)        [K, <= 0]
    beta = 2 sigmoid(h W_beta)  (in (0, 2): a transition may reflect) [1]
    S_t = (I - beta k k^T) Diag(exp(g)) S_{t-1} + beta k v^T
    o = K^-1/2 S_t^T q
    out = (RMSNorm_head(o) * o_norm * sigmoid((h W_ga) W_gb + b_g)) W_o

``conv`` is a causal depthwise convolution over time of ``conv_size``
taps, y_t = sum_j w_j * x_{t-conv_size+1+j}, zero before the sequence's
start, no bias. The recurrence is ``ops/kda.kda_chunked``. Nothing is
reset inside a sequence: packed documents share state, convolution and
(in the softmax layers) the causal mask.

In f32 whatever the compute dtype: the L2 norms, the decay, beta, the
recurrence's state (ops/kda.py says what else), the head norm's
statistics and the gate's sigmoid. Two of them are handed back as they
were computed with (``kda_attention``'s probes): a state or a log-decay
held in bf16 moves the layer's output by less than the products'
bf16 inputs do, so no comparison of values tells it; the bits do.

Named scopes, for the device trace: ``lm_kda_proj`` (the layer's norm
and the q/k/v projections), ``lm_kda_conv`` (convolutions, SiLU, L2
norms), ``lm_kda_gate`` (decay and beta), ``lm_kda_scan`` (the
recurrence), ``lm_kda_out`` (head norm, output gate, W_o): ``lm_kda``
selects them all.

Only the training forward lives here: a decode path would carry the
``[heads, K, V]`` state and the last ``conv_size - 1`` inputs of each
convolution beside the softmax layers' K/V cache, and is not built; the
serving forwards refuse the layer kind by name.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from ..ops.kda import kda_chunked
from .latent_attention import rms_norm

# tokens of a chunk of the recurrence (ops/kda.py)
CHUNK = 64
# tokens of the first sequence whose log-decay, in the first head, is
# handed back in the bits ``decay_gate`` gives it (``probe_kda_g``)
PROBE_TOKENS = 256


@dataclasses.dataclass(frozen=True)
class KDAConfig:
    n_heads: int
    head_dim: int
    conv_size: int = 4
    # rank of the two low-rank pairs (decay gate, output gate)
    gate_rank: int = 128

    @property
    def width(self) -> int:
        return self.n_heads * self.head_dim


def init_kda(key, d_model: int, cfg: KDAConfig, std: float):
    """Matrices (the convolutions' taps among them) normal with ``std``;
    ``a_log`` = ln U(1, 16) a head and ``dt_bias`` = softplus^-1 of
    exp U(ln 0.001, ln 0.1) a channel, so that the decay is neither 0
    nor 1 at random weights; the head norm's scale 1, the gate's bias 0."""
    w, r = cfg.width, cfg.gate_rank
    shapes = {
        "wq": (d_model, w), "wk": (d_model, w), "wv": (d_model, w),
        "wo": (w, d_model), "conv_q": (cfg.conv_size, w),
        "conv_k": (cfg.conv_size, w), "conv_v": (cfg.conv_size, w),
        "wf_a": (d_model, r), "wf_b": (r, w), "wbeta": (d_model, cfg.n_heads),
        "wg_a": (d_model, r), "wg_b": (r, w),
    }
    ks = jax.random.split(key, len(shapes) + 2)
    p = {
        name: std * jax.random.normal(k, shape, jnp.float32)
        for k, (name, shape) in zip(ks, shapes.items())
    }
    p["a_log"] = jnp.log(
        jax.random.uniform(ks[-2], (cfg.n_heads,), jnp.float32, 1.0, 16.0)
    )
    dt = jnp.exp(jax.random.uniform(
        ks[-1], (w,), jnp.float32, jnp.log(0.001), jnp.log(0.1)
    ))
    p["dt_bias"] = dt + jnp.log(-jnp.expm1(-dt))  # softplus^-1(dt)
    p["bg"] = jnp.zeros((w,), jnp.float32)
    p["o_norm"] = jnp.ones((cfg.head_dim,), jnp.float32)
    return p


def causal_conv(x, taps):
    """y_t = sum_j taps[j] * x_{t - n + 1 + j} along axis 1 of ``x``
    [B, S, W], ``taps`` [n, W], zero before the start."""
    n, s = taps.shape[0], x.shape[1]
    padded = jnp.pad(x, ((0, 0), (n - 1, 0), (0, 0)))
    return sum(padded[:, j:j + s] * taps[j] for j in range(n))


def log_decay(f, a_log, dt_bias):
    """g = -exp(A_log) softplus(f + dt_bias) <= 0, f32: ``f`` [..., H, K]
    the decay gate's output, ``a_log`` [H], ``dt_bias`` [H, K]."""
    f32 = jnp.float32
    return -jnp.exp(a_log.astype(f32))[:, None] * jax.nn.softplus(
        f.astype(f32) + dt_bias.astype(f32)
    )


def decay_gate(h, lp, cfg: KDAConfig, dtype):
    """The log-decay g [..., H, K] f32 of normed inputs ``h`` [..., d]:
    the low-rank pair, then ``log_decay``."""
    f = jnp.dot(
        h @ lp["wf_a"].astype(dtype), lp["wf_b"].astype(dtype),
        preferred_element_type=jnp.float32,
    )
    shape = (cfg.n_heads, cfg.head_dim)
    return log_decay(
        f.reshape(*h.shape[:-1], *shape), lp["a_log"],
        lp["dt_bias"].reshape(shape),
    )


def kda_attention(h, lp, cfg: KDAConfig, eps: float, dtype):
    """``(out, probes)``: what the layer adds to the residual stream for
    its normed input ``h`` [B, S, d], in ``dtype`` (``lp``: the layer's
    leaves), and what the recurrence computed with, cast to f32 without
    rounding: ``probe_kda_state`` [B, H, K, V], the state after the last
    token as the scan carried it, and ``probe_kda_g`` [<= PROBE_TOKENS,
    K], the log-decay of the first sequence's first head at strided
    tokens as ``decay_gate`` computes it."""
    b, s, _ = h.shape
    nh, hd = cfg.n_heads, cfg.head_dim
    cast = lambda k: lp[k].astype(dtype)  # noqa: E731
    heads = lambda t: t.reshape(b, s, nh, hd)  # noqa: E731
    f32 = jnp.float32
    with jax.named_scope("lm_kda_proj"):
        q, k, v = h @ cast("wq"), h @ cast("wk"), h @ cast("wv")
    with jax.named_scope("lm_kda_conv"):
        q, k, v = (
            heads(jax.nn.silu(causal_conv(t, cast(name))))
            for t, name in ((q, "conv_q"), (k, "conv_k"), (v, "conv_v"))
        )

        def unit(t):
            t = t.astype(f32)
            return t * jax.lax.rsqrt(
                jnp.sum(t * t, -1, keepdims=True) + 1e-6
            )

        q, k = unit(q), unit(k)
    with jax.named_scope("lm_kda_gate"):
        g = decay_gate(h, lp, cfg, dtype)
        beta = 2.0 * jax.nn.sigmoid(
            jnp.dot(h, cast("wbeta"), preferred_element_type=f32)
        )
        # the gate again at a few tokens, for the probe: a slice of the
        # whole g among the step's outputs added 0.77 GB to the step's
        # plan (8,192 tokens, three layers; compiled for a v5e, PR 33)
        g_probed = decay_gate(
            h[0, ::-(-s // PROBE_TOKENS)], lp, cfg, dtype
        )[:, 0]
    with jax.named_scope("lm_kda_scan"):
        o, state = kda_chunked(q, k, v, g, beta, chunk=CHUNK, dtype=dtype)
    probes = {"probe_kda_state": state.astype(f32), "probe_kda_g": g_probed}
    with jax.named_scope("lm_kda_out"):
        gate = jax.nn.sigmoid(
            jnp.dot(
                h @ cast("wg_a"), cast("wg_b"), preferred_element_type=f32
            ) + lp["bg"].astype(f32)
        )
        o = rms_norm(o.astype(f32), lp["o_norm"], eps) * heads(gate)
        return o.reshape(b, s, nh * hd).astype(dtype) @ cast("wo"), probes

"""The plain reference of the ``lm_swa`` runner's configurations: a
decoder whose every layer is grouped-query softmax attention with a q/k
norm and a rotation, over the last ``sliding_window`` keys
(``sliding_attention``) or over all of them (``full_attention``), each
kind of layer with rotary tables of its own, followed by a top-k expert
layer without a shared expert; its loss and, through ``jax.grad``, its
gradients, in ``jax.numpy`` and float32 under
``jax.default_matmul_precision("highest")``. No kernel, no blocks of the
mask, no broadcast of K/V. It imports nothing of
``parameter_server_tpu``; the norm, the expert layer and the head are
``lm_reference.py``'s and ``lm_hybrid_reference.py``'s (the benchmark's
own files beside this one).

Every layer, x [B, S, d], every norm RMSNorm(eps), no bias anywhere:

    x += Attn(norm(x)) W_o;  x += Experts(norm(x))   (lm_reference.experts)

Attention, layer i of kind ``layer_types[i]``, h = norm(x):

    q = h W_q [heads x D];  k = h W_k, v = h W_v [kv_heads x D]
    q_h <- norm_D(q_h) * w_qn,  k_g <- norm_D(k_g) * w_kn    (one scale of
          D each a layer)
    q_h <- q_h * C_t + rot(q_h) * S_t, the same for k: half-split pairs
          (dimension j with j + D/2), rot([a, b]) = [-b, a],
          C_t = c cos(t f), S_t = c sin(t f), t the position, (f, c) the
          layer kind's (``rope_tables``: plain, or YaRN as
          ``transformers`` computes it)
    query head h reads K/V head floor(h / (heads / kv_heads))
    s_tu = q_t . k_u / sqrt(D), kept where u <= t and, in a
          sliding_attention layer, t - u < sliding_window
    out = concat_h(softmax(s) v)

Nothing is masked at a packed document's boundary (the file's
``assumed.packing``). What the experts that are not held here would add
is left out, as the program leaves it out; the vocabulary is the slice
the configuration states.

``blocked=True`` computes the same arithmetic with each layer, each
(sequence, head) of the attention, each held expert (through one traced
body) and each block of ``HEAD_BLOCK`` rows of the head under
``jax.checkpoint``, so that the backward pass recomputes them one at a
time: what fits a chip beside the weights and their gradients. The
tests hold blocked and plain to each other.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import lm_hybrid_reference as hybrid
from chipbench import lm_reference as base
from chipbench.lm_reference import (  # noqa: F401  (the runner's names)
    description, layer_params, mm, rms,
)

SIGMA = base.SIGMA
# of the embedding's rows: large beside what the layers add to every
# token alike, so that a token's own row decides where its routers send
# it (the file's assumed.initialisation has the readings)
EMB_SIGMA = 2.0
HEAD_BLOCK = 1024  # rows of the head's logits alive at a time
KINDS = {"sliding_attention": "window", "full_attention": "full"}


def model(desc: dict) -> dict:
    """The sizes the reference computes with, from the configuration's
    file (the published ``config.json`` keys, ``published`` for the
    counts that were cut, ``share`` for which experts are held)."""
    published = desc.get("published", {})
    share = desc.get("share", {})
    return {
        "d": desc["hidden_size"], "vocab": desc["vocab_size"],
        "layers": desc["num_hidden_layers"],
        "kinds": tuple(KINDS[t] for t in desc["layer_types"]),
        "heads": desc["num_attention_heads"],
        "kv_heads": desc["num_key_value_heads"],
        "head_dim": desc["head_dim"], "window": desc["sliding_window"],
        "rope": {
            KINDS[t]: rp for t, rp in desc["rope_parameters"].items()
        },
        "eps": desc["rms_norm_eps"],
        "d_expert": desc["moe_intermediate_size"],
        "experts": published.get("num_experts", desc["num_experts"]),
        "held": desc["num_experts"],
        "offset": share.get("expert_offset", 0),
        "top_k": desc["num_experts_per_tok"], "shared": 0,
        "norm_topk": desc["norm_topk_prob"], "routed_scale": 1.0,
    }


def shapes(m: dict) -> dict:
    """Every leaf of the model as held here: name -> shape."""
    d, f, hd = m["d"], m["d_expert"], m["head_dim"]
    wide, narrow = m["heads"] * hd, m["kv_heads"] * hd
    out = {"emb": (m["vocab"], d), "head": (d, m["vocab"]), "ln_f": (d,)}
    layer = {
        "ln1": (d,), "ln2": (d,), "wq": (d, wide), "wk": (d, narrow),
        "wv": (d, narrow), "wo": (wide, d), "q_norm": (hd,),
        "k_norm": (hd,), "router": (d, m["experts"]),
        "we_gate": (m["held"], d, f), "we_up": (m["held"], d, f),
        "we_down": (m["held"], f, d),
    }
    for i in range(m["layers"]):
        out.update({f"l{i}/{name}": shape for name, shape in layer.items()})
    return out


def weights_fn(m: dict, sharding=None):
    """A jitted ``key -> {name: f32 array}``: every matrix normal with
    sigma ``SIGMA`` but the embedding, whose rows have ``EMB_SIGMA``, a
    key of its own each (by its place among the sorted names), every
    norm scale 1 (the q/k norms' too); made on the device, placed by
    ``sharding`` where one is given."""
    names = sorted(shapes(m).items())

    def make(key):
        return {
            name: jnp.ones(shape, jnp.float32) if len(shape) == 1
            else (EMB_SIGMA if name == "emb" else SIGMA) * jax.random.normal(
                jax.random.fold_in(key, i), shape, jnp.float32
            )
            for i, (name, shape) in enumerate(names)
        }

    return jax.jit(make, out_shardings=sharding)


def weights(seed: int, m: dict) -> dict:
    return weights_fn(m)(jax.random.PRNGKey(seed))


# -- rope ------------------------------------------------------------------


def yarn_ramp(dim: int, rp: dict):
    """``(low, high, ramp [dim/2])`` of a YaRN block: the correction
    dimensions of ``beta_fast`` and ``beta_slow`` rotations over the
    original length, and the linear ramp between them."""
    theta, length = rp["rope_theta"], rp["original_max_position_embeddings"]

    def dim_of(rotations):
        return dim * math.log(length / (2 * math.pi * rotations)) / (
            2 * math.log(theta)
        )

    low = max(math.floor(dim_of(rp["beta_fast"])), 0)
    high = min(math.ceil(dim_of(rp["beta_slow"])), dim - 1)
    width = high - low if high != low else 0.001  # transformers' guard
    j = np.arange(dim // 2, dtype=np.float64)
    return low, high, np.clip((j - low) / width, 0.0, 1.0)


def rope_freq_and_factor(dim: int, rp: dict):
    """``(f [dim/2] float64, c)`` of one kind of layer: f_j =
    theta^(-2j/dim), c = 1; under YaRN f_j = (1 - ramp_j) theta^(-2j/dim)
    + ramp_j theta^(-2j/dim) / factor and c = ``attention_factor`` as the
    block gives it (0.1 ln(factor) + 1 where it gives none)."""
    j = np.arange(dim // 2, dtype=np.float64)
    f = float(rp["rope_theta"]) ** (-2.0 * j / dim)
    if rp.get("rope_type", "default") == "default":
        return f, 1.0
    if rp["rope_type"] != "yarn":
        raise ValueError(f"rope_type {rp['rope_type']!r}")
    ramp = yarn_ramp(dim, rp)[2]
    c = rp.get("attention_factor")
    if c is None:
        c = 0.1 * math.log(rp["factor"]) + 1.0
    return (1.0 - ramp) * f + ramp * f / rp["factor"], float(c)


def rope_tables(positions, dim: int, rp: dict):
    """``(C, S)`` [S, dim/2] in f32: c cos(t f), c sin(t f)."""
    f, c = rope_freq_and_factor(dim, rp)
    ang = positions.astype(jnp.float32)[:, None] * jnp.asarray(f, jnp.float32)
    return c * jnp.cos(ang), c * jnp.sin(ang)


def rotate(x, cos, sin):
    """x [B, S, heads, D] * C + rot(x) * S, dimension j paired with
    j + D/2: rot([a, b]) = [-b, a]."""
    a, b = jnp.split(x, 2, axis=-1)
    cos, sin = cos[:, None, :], sin[:, None, :]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], -1)


# -- the attention layer -----------------------------------------------------


def attend_one(q, k, v, window):
    """One head of one sequence: q, k, v [S, D]; the mask a plain [S, S]
    comparison of positions; ``window`` None sees every earlier key."""
    n, dim = q.shape
    s = mm(q, k.T) * dim ** -0.5
    t, u = jnp.arange(n)[:, None], jnp.arange(n)[None, :]
    keep = u <= t
    if window is not None:
        keep = keep & (t - u < window)
    return mm(jax.nn.softmax(jnp.where(keep, s, -jnp.inf), axis=-1), v)


def attention(lp, h, m: dict, kind: str, blocked: bool):
    b, s, _ = h.shape
    nh, kvh, hd = m["heads"], m["kv_heads"], m["head_dim"]
    q = rms(mm(h, lp["wq"]).reshape(b, s, nh, hd), lp["q_norm"], m["eps"])
    k = rms(mm(h, lp["wk"]).reshape(b, s, kvh, hd), lp["k_norm"], m["eps"])
    v = mm(h, lp["wv"]).reshape(b, s, kvh, hd)
    tables = rope_tables(jnp.arange(s), hd, m["rope"][kind])
    q, k = rotate(q, *tables), rotate(k, *tables)
    window = m["window"] if kind == "window" else None
    # [B, S, H, D] -> [B, H, S, D]; query head h reads K/V head h // group
    q, k, v = (t.transpose(0, 2, 1, 3) for t in (q, k, v))
    group = nh // kvh

    def one(i):  # the i-th (sequence, query head)
        seq, head = i // nh, i % nh
        return attend_one(
            q[seq, head], k[seq, head // group], v[seq, head // group], window
        )

    pairs = jnp.arange(b * nh)
    if blocked:
        out = jax.lax.map(jax.checkpoint(one), pairs)
    else:
        out = jax.vmap(one)(pairs)
    out = out.reshape(b, nh, s, hd).transpose(0, 2, 1, 3)
    return mm(out.reshape(b, s, nh * hd), lp["wo"])


# -- the model ---------------------------------------------------------------


def experts(lp, x, m: dict, blocked: bool, given=None, held=None,
            offset=None):
    """``lm_reference.experts`` (plain) or ``lm_hybrid_reference.experts``
    (blocked: the held experts through one traced body): the held
    experts' part of the sum and the router's own top-k. ``held`` and
    ``offset`` name another share of the layer (the share test)."""
    if held is not None:
        m = {**m, "held": held, "offset": offset}
    return hybrid.experts(lp, x, m, blocked, given=given)


def hidden(params: dict, tokens, m: dict, blocked: bool = False,
           given=None):
    """``(x, chosen)``: the last layer's output [B, S, d], before the
    final norm, and every layer's own top-k [layers, T, k]. ``given``
    [layers, T, k]: the choices to compute with instead (-1: its own)."""

    def layer(kind, x, lp, given_here):
        h = rms(x, lp["ln1"], m["eps"])
        x = x + attention(lp, h, m, kind, blocked)
        y, chosen = experts(lp, x, m, blocked, given=given_here)
        return x + y, chosen

    x = params["emb"][tokens]
    chosen = []
    for i, kind in enumerate(m["kinds"]):
        one = jax.checkpoint(layer, static_argnums=0) if blocked else layer
        x, c = one(
            kind, x, layer_params(params, i),
            None if given is None else given[i],
        )
        chosen.append(c)
    return x, jnp.stack(chosen)


def forward(params: dict, tokens, m: dict, blocked: bool = False):
    """Logits [B, S, vocab] in f32."""
    return base.head(params, hidden(params, tokens, m, blocked)[0], m)


def loss(params: dict, tokens, m: dict, blocked: bool = False, given=None):
    """``(loss, chosen)``: mean next-token cross entropy (position i
    predicts token i+1 of its sequence, the last position of a sequence
    predicts nothing) and the routers' own choices."""

    def nll_sum(x_and_targets):  # rows of any sequences
        x, targets = x_and_targets
        logp = jax.nn.log_softmax(base.head(params, x, m), axis=-1)
        return -jnp.sum(jnp.take_along_axis(logp, targets[:, None], axis=-1))

    x, chosen = hidden(params, tokens, m, blocked, given)
    b, s = tokens.shape
    x = x[:, :-1].reshape(b * (s - 1), -1)
    targets = tokens[:, 1:].reshape(-1)
    n = x.shape[0]
    if blocked and n > HEAD_BLOCK:
        # whole blocks of rows one at a time, then what is left
        whole = n // HEAD_BLOCK * HEAD_BLOCK
        sums = jnp.sum(jax.lax.map(jax.checkpoint(nll_sum), (
            x[:whole].reshape(-1, HEAD_BLOCK, x.shape[-1]),
            targets[:whole].reshape(-1, HEAD_BLOCK),
        ))) + jax.checkpoint(nll_sum)((x[whole:], targets[whole:]))
    else:
        sums = nll_sum((x, targets))
    return sums / n, chosen


def loss_and_grads(params: dict, tokens, m: dict, blocked: bool = False):
    """``(loss, grads)`` with the routers choosing for themselves."""
    (value, _), grads = loss_grads_choices(params, tokens, m, blocked)
    return value, grads


def loss_grads_choices(params: dict, tokens, m: dict, blocked: bool = False,
                       given=None):
    """``((loss, chosen), grads)``; ``given``: see ``lm_reference``."""
    with jax.default_matmul_precision("highest"):
        return jax.value_and_grad(loss, has_aux=True)(
            params, tokens, m, blocked, given
        )

"""The plain reference of the ``lm`` runner's configurations: a decoder
with latent attention and a top-k expert layer beside a shared expert,
its loss and, through ``jax.grad``, its gradients, in ``jax.numpy`` and
float32 under ``jax.default_matmul_precision("highest")``. No kernel, no
sorting (a loop over the experts held, each over every token, weighted
by what the router gave it), no cache. It imports nothing of
``parameter_server_tpu``: it reads the configuration's file itself
(``description``), says which leaves the model has (``shapes``: a flat
dict under the names the program's checkpoint has: ``emb``, ``head``,
``ln_f``, ``l<i>/<leaf>``) and makes the initial weights from a seed
(``weights_fn``: normal, sigma 0.02, norm scales 1, as the file's
``assumed.initialisation`` says), so that program and reference start
from data that neither made for the other.

The layer, x [T, d], every norm RMSNorm(eps), no bias anywhere:

    h = norm(x);  c_q = norm(h W_qa);  q = c_q W_qb -> heads [q_nope, q_rope]
    [c_kv, k_r] = h W_kva;  c_kv = norm(c_kv);  [k_nope, v] = c_kv W_kvb
    q_rope, k_r rotated by position (interleaved pairs, YaRN frequencies)
    x += softmax_causal([q_nope, q_rope] . [k_nope, k_r] s) v  W_o
    h2 = norm(x);  p = softmax(h2 W_g) over ALL experts;  top-k, / their sum
    x += sum_{e in top-k, e held here} w_e FFN_e(h2) + FFN_shared(h2)
    FFN(h) = W_down(SiLU(W_gate h) * W_up h)

What the experts that are not held here would add is left out, as the
program leaves it out (``experts_held``, ``expert_offset``); the
vocabulary is the slice the configuration states.

At the benchmark's size nothing of this fits a chip if every
intermediate is kept for the backward pass (the scores alone are 8,192^2
x 32 heads x 4 layers). ``blocked=True`` computes the same arithmetic
with each layer, each expert and each (sequence, head) of the attention
under ``jax.checkpoint``, so that the backward pass recomputes them one
at a time. The tests hold blocked and plain to each other on the CPU.
"""

from __future__ import annotations

import json
import math

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST


SIGMA = 0.02  # of the initial weights (the file's assumed.initialisation)


def description(path: str, rehearsal: bool = False) -> dict:
    """The configuration's file as it is run. ``rehearsal=True`` lays
    its toy sizes (``rehearsal.model``, ``rehearsal.train``) over it."""
    with open(path) as f:
        desc = json.load(f)
    if rehearsal:
        toy = desc["rehearsal"]
        desc = {**desc, **toy.get("model", {})}
        desc["train"] = {**desc.get("train", {}), **toy.get("train", {})}
    return desc


def model(desc: dict) -> dict:
    """The sizes the reference computes with, from the configuration's
    file (the published ``config.json`` keys, ``published`` for the
    counts that were cut, ``share`` for which experts are held)."""
    published = desc.get("published", {})
    share = desc.get("share", {})
    return {
        "d": desc["hidden_size"], "vocab": desc["vocab_size"],
        "q_rank": desc["q_lora_rank"],
        "d_expert": desc["moe_intermediate_size"],
        "heads": desc["num_attention_heads"],
        "layers": desc["num_hidden_layers"], "kv_rank": desc["kv_lora_rank"],
        "nope": desc["qk_nope_head_dim"], "rope": desc["qk_rope_head_dim"],
        "v": desc["v_head_dim"], "eps": desc["rms_norm_eps"],
        "interleave": desc["rope_interleave"],
        "rope_parameters": desc["rope_parameters"],
        "experts": published.get(
            "n_routed_experts", desc["n_routed_experts"]
        ),
        "held": desc["n_routed_experts"],
        "offset": share.get("expert_offset", 0),
        "top_k": desc["num_experts_per_tok"],
        "shared": desc["n_shared_experts"],
        "norm_topk": desc["norm_topk_prob"],
        "routed_scale": desc["routed_scaling_factor"],
    }


def shapes(m: dict) -> dict:
    """Every leaf of the model as held here: name -> shape."""
    d, nh, f = m["d"], m["heads"], m["d_expert"]
    out = {"emb": (m["vocab"], d), "head": (d, m["vocab"]), "ln_f": (d,)}
    layer = {
        "ln1": (d,), "ln2": (d,), "q_norm": (m["q_rank"],),
        "kv_norm": (m["kv_rank"],), "wq_a": (d, m["q_rank"]),
        "wq_b": (m["q_rank"], nh * (m["nope"] + m["rope"])),
        "wkv_a": (d, m["kv_rank"] + m["rope"]),
        "wkv_b": (m["kv_rank"], nh * (m["nope"] + m["v"])),
        "wo": (nh * m["v"], d), "router": (d, m["experts"]),
        "we_gate": (m["held"], d, f), "we_up": (m["held"], d, f),
        "we_down": (m["held"], f, d),
    }
    if m["shared"]:
        fs = m["shared"] * f
        layer.update(ws_gate=(d, fs), ws_up=(d, fs), ws_down=(fs, d))
    for i in range(m["layers"]):
        out.update({f"l{i}/{name}": shape for name, shape in layer.items()})
    return out


def weights_fn(m: dict, sharding=None):
    """A jitted ``key -> {name: f32 array}``: every matrix normal with
    sigma ``SIGMA``, a key of its own each (by its place among the
    sorted names), every norm scale 1; made on the device, placed by
    ``sharding`` where one is given."""
    names = sorted(shapes(m).items())

    def make(key):
        return {
            name: jnp.ones(shape, jnp.float32) if len(shape) == 1
            else SIGMA * jax.random.normal(
                jax.random.fold_in(key, i), shape, jnp.float32
            )
            for i, (name, shape) in enumerate(names)
        }

    return jax.jit(make, out_shardings=sharding)


def weights(seed: int, m: dict) -> dict:
    return weights_fn(m)(jax.random.PRNGKey(seed))


# -- rope ------------------------------------------------------------------


def yarn_inv_freq(dim: int, rp: dict) -> np.ndarray:
    """theta^(-2i/dim), blended with the same over ``factor`` by the
    linear ramp between the correction dimensions of ``beta_fast`` and
    ``beta_slow`` rotations over the original length."""
    theta = rp["rope_theta"]
    i = np.arange(dim // 2, dtype=np.float64)
    plain = theta ** (-2.0 * i / dim)
    if rp.get("rope_type", rp.get("type")) != "yarn":
        return plain

    def dim_of(rotations):
        return dim * math.log(
            rp["original_max_position_embeddings"] / (rotations * 2 * math.pi)
        ) / (2 * math.log(theta))

    low = max(math.floor(dim_of(rp["beta_fast"])), 0)
    high = min(math.ceil(dim_of(rp["beta_slow"])), dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((i - low) / (high - low), 0.0, 1.0)
    return plain / rp["factor"] * ramp + plain * (1.0 - ramp)


def mscale(factor: float, m: float) -> float:
    return 0.1 * m * math.log(factor) + 1.0 if factor > 1 else 1.0


def softmax_scale(m: dict) -> float:
    rp = m["rope_parameters"]
    s = (m["nope"] + m["rope"]) ** -0.5
    if rp.get("mscale_all_dim"):
        s *= mscale(rp["factor"], rp["mscale_all_dim"]) ** 2
    return s


def rotate_interleaved(x, positions, m: dict):
    """Rotate the pairs (2i, 2i+1) of ``x`` [..., S, heads, rope] by
    ``positions`` [S] times the frequencies, in place (pair order kept)."""
    rp = m["rope_parameters"]
    inv = jnp.asarray(yarn_inv_freq(m["rope"], rp), jnp.float32)
    factor = 1.0
    if rp.get("mscale") and rp.get("mscale_all_dim"):
        factor = mscale(rp["factor"], rp["mscale"]) / mscale(
            rp["factor"], rp["mscale_all_dim"]
        )
    ang = positions.astype(jnp.float32)[:, None] * inv  # [S, rope/2]
    cos = (jnp.cos(ang) * factor)[:, None, :]
    sin = (jnp.sin(ang) * factor)[:, None, :]
    if m["interleave"]:
        a, b = x[..., 0::2], x[..., 1::2]
        return jnp.stack([a * cos - b * sin, a * sin + b * cos], -1).reshape(
            x.shape
        )
    a, b = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([a * cos - b * sin, a * sin + b * cos], -1)


def position_scale(positions, rp: dict):
    beta = rp.get("llama_4_scaling_beta", 0.0)
    steps = jnp.floor(
        positions.astype(jnp.float32) / rp["original_max_position_embeddings"]
    )
    return 1.0 + beta * jnp.log1p(steps)


# -- the layer -------------------------------------------------------------


def rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def mm(a, b):
    return jnp.matmul(a, b, precision=HIGHEST)


def attend_one(q, k, v, scale):
    """One head of one sequence: q, k [S, D], v [S, Dv], causal."""
    s = mm(q, k.T) * scale
    n = q.shape[0]
    s = jnp.where(jnp.tril(jnp.ones((n, n), bool)), s, -jnp.inf)
    return mm(jax.nn.softmax(s, axis=-1), v)


def attention(lp, x, m: dict, blocked: bool):
    b, s, _ = x.shape
    nh, nope, rope, vd = m["heads"], m["nope"], m["rope"], m["v"]
    pos = jnp.arange(s)
    h = rms(x, lp["ln1"], m["eps"])
    c_q = rms(mm(h, lp["wq_a"]), lp["q_norm"], m["eps"])
    q = mm(c_q, lp["wq_b"]).reshape(b, s, nh, nope + rope)
    kv_a = mm(h, lp["wkv_a"])
    c_kv = rms(kv_a[..., : m["kv_rank"]], lp["kv_norm"], m["eps"])
    k_r = kv_a[..., m["kv_rank"]:].reshape(b, s, 1, rope)
    kv = mm(c_kv, lp["wkv_b"]).reshape(b, s, nh, nope + vd)
    q_r = rotate_interleaved(q[..., nope:], pos, m)
    k_r = rotate_interleaved(k_r, pos, m)
    q = jnp.concatenate([q[..., :nope], q_r], -1)
    q = q * position_scale(pos, m["rope_parameters"])[:, None, None]
    k = jnp.concatenate(
        [kv[..., :nope], jnp.broadcast_to(k_r, (b, s, nh, rope))], -1
    )
    v = kv[..., nope:]
    scale = softmax_scale(m)
    # [B, S, H, D] -> [B*H, S, D]
    flat = lambda t: t.transpose(0, 2, 1, 3).reshape(b * nh, s, -1)  # noqa
    one = lambda qkv: attend_one(*qkv, scale)  # noqa: E731
    if blocked:
        out = jax.lax.map(jax.checkpoint(one), (flat(q), flat(k), flat(v)))
    else:
        out = jax.vmap(lambda q, k, v: attend_one(q, k, v, scale))(
            flat(q), flat(k), flat(v)
        )
    out = out.reshape(b, nh, s, vd).transpose(0, 2, 1, 3).reshape(b, s, nh * vd)
    return mm(out, lp["wo"])


def ffn(h, w_gate, w_up, w_down):
    return mm(jax.nn.silu(mm(h, w_gate)) * mm(h, w_up), w_down)


def router_weights(lp, h2, m: dict, given=None):
    """``([T, experts], [T, k])``: each token's weight on each of ALL the
    experts, 0 where the expert is not among its top-k, and the k experts
    the router chose. With ``given`` [T, k] the weights are the router's
    own probabilities at THOSE experts (renormalised over them), so that
    a comparison with a program that chose them sees arithmetic and not
    a near-tie that fell the other way; the choice returned is still the
    router's own, to be compared as a choice. A ``given`` of -1 leaves
    the router its own choice (one compiled program serves both)."""
    p = jax.nn.softmax(mm(h2, lp["router"]), axis=-1)
    top_p, top_e = jax.lax.top_k(p, m["top_k"])
    chosen = top_e
    if given is not None:
        top_e = jnp.where(given >= 0, given, chosen)
        top_p = jnp.take_along_axis(p, top_e, axis=-1)
    if m["norm_topk"]:
        top_p = top_p / jnp.sum(top_p, -1, keepdims=True)
    top_p = top_p * m["routed_scale"]
    onehot = jax.nn.one_hot(top_e, m["experts"], dtype=p.dtype)  # [T, k, E]
    return jnp.einsum("tk,tke->te", top_p, onehot), chosen


def experts(lp, x, m: dict, blocked: bool, held=None, offset=None,
            shared: bool = True, given=None):
    """``(y, chosen)``: the expert layer's addition to the residual (the
    held experts' part, and the shared expert's unless ``shared`` is
    False: the share test counts it once over all the shares) and the
    router's own top-k [T, k]. ``given``: see :func:`router_weights`."""
    held = m["held"] if held is None else held
    offset = m["offset"] if offset is None else offset
    shape = x.shape
    h2 = rms(x, lp["ln2"], m["eps"]).reshape(-1, shape[-1])
    w, chosen = router_weights(lp, h2, m, given)

    def weighted(h, w_e, gate, up, down):
        return w_e[:, None] * ffn(h, gate, up, down)

    one = jax.checkpoint(weighted) if blocked else weighted
    y = jnp.zeros_like(h2)
    for j in range(held):
        y = y + one(
            h2, w[:, offset + j], lp["we_gate"][j], lp["we_up"][j],
            lp["we_down"][j],
        )
    if shared and m["shared"]:
        y = y + one(
            h2, jnp.ones_like(w[:, 0]), lp["ws_gate"], lp["ws_up"],
            lp["ws_down"],
        )
    return y.reshape(shape), chosen


def layer_params(params: dict, i: int) -> dict:
    pre = f"l{i}/"
    return {k[len(pre):]: v for k, v in params.items() if k.startswith(pre)}


def hidden(params: dict, tokens, m: dict, blocked: bool = False,
           given=None):
    """``(x, chosen)``: the last layer's output [B, S, d], before the
    final norm, and every layer's own top-k [layers, T, k]. ``given``
    [layers, T, k]: the choices to compute with instead."""

    def layer(x, lp, given_here):
        x = x + attention(lp, x, m, blocked)
        y, chosen = experts(lp, x, m, blocked, given=given_here)
        return x + y, chosen

    if blocked:
        layer = jax.checkpoint(layer)
    x = params["emb"][tokens]
    chosen = []
    for i in range(m["layers"]):
        x, c = layer(
            x, layer_params(params, i), None if given is None else given[i]
        )
        chosen.append(c)
    return x, jnp.stack(chosen)


def head(params: dict, x, m: dict):
    return mm(rms(x, params["ln_f"], m["eps"]), params["head"])


def forward(params: dict, tokens, m: dict, blocked: bool = False):
    """Logits [B, S, vocab] in f32."""
    return head(params, hidden(params, tokens, m, blocked)[0], m)


def loss(params: dict, tokens, m: dict, blocked: bool = False, given=None):
    """``(loss, chosen)``: mean next-token cross entropy (position i
    predicts token i+1 of its sequence, the last position of a sequence
    predicts nothing) and the routers' own choices."""

    def nll_sum(x_and_tokens):  # one sequence
        x, toks = x_and_tokens
        logp = jax.nn.log_softmax(head(params, x[:-1], m), axis=-1)
        return -jnp.sum(jnp.take_along_axis(logp, toks[1:, None], axis=-1))

    x, chosen = hidden(params, tokens, m, blocked, given)
    if blocked:  # a sequence's logits at a time
        sums = jax.lax.map(jax.checkpoint(nll_sum), (x, tokens))
    else:
        sums = jax.vmap(lambda x, t: nll_sum((x, t)))(x, tokens)
    return jnp.sum(sums) / (tokens.shape[0] * (tokens.shape[1] - 1)), chosen


def loss_and_grads(params: dict, tokens, m: dict, blocked: bool = False):
    """``(loss, grads)`` with the routers choosing for themselves."""
    (value, _), grads = loss_grads_choices(params, tokens, m, blocked)
    return value, grads


def loss_grads_choices(params: dict, tokens, m: dict, blocked: bool = False,
                       given=None):
    """``((loss, chosen), grads)``; ``given``: see :func:`router_weights`."""
    with jax.default_matmul_precision("highest"):
        return jax.value_and_grad(loss, has_aux=True)(
            params, tokens, m, blocked, given
        )

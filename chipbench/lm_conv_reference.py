"""The plain reference of the ``lm_conv`` runner's configurations: a
decoder whose layers are gated short convolutions (LFM2's
``Lfm2ShortConv``) or grouped-query softmax attention with a q/k norm
under one plain rotation, each followed by a dense SwiGLU (the first
``num_dense_layers``) or a top-k expert layer whose router scores by a
sigmoid and picks by the scores plus a bias; a head tied to the
embedding. Its loss and, through ``jax.grad``, its gradients, in
``jax.numpy`` and float32 under ``jax.default_matmul_precision
("highest")``; the bias's update by its own rule. It imports nothing of
``parameter_server_tpu``; the norm, the products, the SwiGLU and the
attention layer are ``lm_reference.py``'s and ``lm_swa_reference.py``'s
(the benchmark's own files beside this one).

Every layer, x [B, S, d], every norm RMSNorm(eps), no bias anywhere:

    x += Op(norm(x));  x += FFN(norm(x))

Convolution (``layer_types[i]`` is ``conv``), h = norm(x):

    [B | C | x~] = h W_in          (W_in [d, 3d], split in that order)
    u = B * x~
    z_t = w_0 * u_{t-2} + w_1 * u_{t-1} + w_2 * u_t   (taps [3, d], each
          channel on its own, zero before the sequence starts: three
          shifted copies of u)
    Op = (C * z) W_out

Attention (``full_attention``): ``lm_swa_reference.attention`` of a full
layer: q, k normed by head (scales [D]), rotated in half-split pairs by
the plain table of ``rope_parameters``, query head h reading K/V head
h // (heads / kv_heads), a plain causal mask.

FFN: layer i < ``num_dense_layers``, the dense W_down(SiLU(W_gate h) *
W_up h) of ``intermediate_size``. Every other layer, over h2 = norm(x):

    s = sigmoid(h2 W_r)          [T, E] over ALL the router's experts
    chosen = the k experts of the largest s + b, by an explicit sort
    w_e = s_e / sum_{chosen} s (``norm_topk_prob``) * routed_scaling_factor
          at the chosen e, 0 elsewhere: b picks and does not weight
    FFN = sum_{e held here} w_e SwiGLU_e(h2), every held expert computed
          over every token and masked by its weight

b [E] takes no gradient. After each step b_e += rate * sign(mean(load) -
load_e), load_e the assignments of the step's tokens to expert e, over
all E columns (``update_bias``). The head: logits = norm(x) emb^T over
the vocabulary slice.

Nothing is masked at a packed document's boundary (the file's
``assumed.packing``). What the experts that are not held here would add
is left out, as the program leaves it out.

``blocked=True`` computes the same arithmetic with each layer, each
(sequence, head) of the attention, each held expert (through one traced
body) and each block of ``HEAD_BLOCK`` rows of the head under
``jax.checkpoint``, so that the backward pass recomputes them one at a
time: what fits a chip beside the weights and their gradients. The
tests hold blocked and plain to each other.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from chipbench import lm_reference as base
from chipbench import lm_swa_reference as swa
from chipbench.lm_reference import (  # noqa: F401  (the runner's names)
    description, layer_params, mm, rms,
)

SIGMA = base.SIGMA
HEAD_BLOCK = 1024  # rows of the head's logits alive at a time
KINDS = {"conv": "conv", "full_attention": "full"}
BIAS = "router_bias"  # the leaf of each expert layer's selection bias
# the bias's step (assumed.expert_bias_update in configs/lfm2_ep8.json)
BIAS_RATE = 0.001


def model(desc: dict) -> dict:
    """The sizes the reference computes with, from the configuration's
    file (the published ``config.json`` keys, ``published`` for the
    counts that were cut, ``share`` for which experts are held)."""
    published = desc.get("published", {})
    share = desc.get("share", {})
    d, heads = desc["hidden_size"], desc["num_attention_heads"]
    return {
        "d": d, "vocab": desc["vocab_size"],
        "layers": desc["num_hidden_layers"],
        "kinds": tuple(KINDS[t] for t in desc["layer_types"]),
        "dense_layers": desc["num_dense_layers"],
        "heads": heads, "kv_heads": desc["num_key_value_heads"],
        "head_dim": desc.get("head_dim") or d // heads, "window": None,
        "rope": {"full": desc["rope_parameters"]},
        "eps": desc["norm_eps"], "taps": desc["conv_L_cache"],
        "d_ff": desc["intermediate_size"],
        "d_expert": desc["moe_intermediate_size"],
        "experts": published.get("num_experts", desc["num_experts"]),
        "held": desc["num_experts"],
        "offset": share.get("expert_offset", 0),
        "top_k": desc["num_experts_per_tok"], "shared": 0,
        "norm_topk": desc["norm_topk_prob"],
        "routed_scale": float(desc.get("routed_scaling_factor", 1.0)),
    }


def expert_layers(m: dict) -> list:
    """The layers with an expert layer, in order: the index of a layer's
    choices in ``given``, ``chosen`` and the bias's stack."""
    return list(range(m["dense_layers"], m["layers"]))


def shapes(m: dict) -> dict:
    """Every leaf of the model as held here: name -> shape."""
    d, f, hd = m["d"], m["d_expert"], m["head_dim"]
    wide, narrow = m["heads"] * hd, m["kv_heads"] * hd
    out = {"emb": (m["vocab"], d), "ln_f": (d,)}
    for i, kind in enumerate(m["kinds"]):
        layer = {"ln1": (d,), "ln2": (d,)}
        if kind == "conv":
            layer.update(
                conv_in=(d, 3 * d), conv_taps=(m["taps"], d),
                conv_out=(d, d),
            )
        else:
            layer.update(
                wq=(d, wide), wk=(d, narrow), wv=(d, narrow), wo=(wide, d),
                q_norm=(hd,), k_norm=(hd,),
            )
        if i < m["dense_layers"]:
            layer.update(
                w_gate=(d, m["d_ff"]), w_up=(d, m["d_ff"]),
                w_down=(m["d_ff"], d),
            )
        else:
            layer.update({
                "router": (d, m["experts"]), BIAS: (m["experts"],),
                "we_gate": (m["held"], d, f), "we_up": (m["held"], d, f),
                "we_down": (m["held"], f, d),
            })
        out.update({f"l{i}/{name}": shape for name, shape in layer.items()})
    return out


def weights_fn(m: dict, sharding=None):
    """A jitted ``key -> {name: f32 array}``: every matrix normal with
    sigma ``SIGMA`` (the embedding too), a key of its own each (by its
    place among the sorted names); the convolutions' taps uniform over
    +-1/sqrt(taps) (torch's ``Conv1d`` default); every norm scale 1 and
    every selection bias 0. Made on the device, placed by ``sharding``
    where one is given."""
    names = sorted(shapes(m).items())
    bound = m["taps"] ** -0.5

    def make(key):
        out = {}
        for i, (name, shape) in enumerate(names):
            k = jax.random.fold_in(key, i)
            if name.endswith(BIAS):
                out[name] = jnp.zeros(shape, jnp.float32)
            elif len(shape) == 1:
                out[name] = jnp.ones(shape, jnp.float32)
            elif name.endswith("conv_taps"):
                out[name] = jax.random.uniform(
                    k, shape, jnp.float32, -bound, bound
                )
            else:
                out[name] = SIGMA * jax.random.normal(k, shape, jnp.float32)
        return out

    return jax.jit(make, out_shardings=sharding)


def weights(seed: int, m: dict) -> dict:
    return weights_fn(m)(jax.random.PRNGKey(seed))


def trained(params: dict) -> dict:
    """The leaves the gradient and the optimizer see: all but the
    selection biases."""
    return {k: v for k, v in params.items() if not k.endswith(BIAS)}


# -- the layers --------------------------------------------------------------


def shifted(u, by: int):
    """u [B, S, W] moved ``by`` positions later along S, zeros first."""
    if by == 0:
        return u
    return jnp.pad(u, ((0, 0), (by, 0), (0, 0)))[:, : u.shape[1]]


def short_conv(lp, h, m: dict):
    b_gate, c_gate, xt = jnp.split(mm(h, lp["conv_in"]), 3, axis=-1)
    u = b_gate * xt
    n, taps = m["taps"], lp["conv_taps"]
    z = sum(taps[j] * shifted(u, n - 1 - j) for j in range(n))
    return mm(c_gate * z, lp["conv_out"])


def router_weights(lp, h2, m: dict, given=None):
    """``([T, experts], [T, k])``: each token's weight on each of ALL the
    experts, 0 where it did not choose it, and the k experts it chose:
    those of the largest sigmoid score plus bias, by a sort. With
    ``given`` [T, k] the weights are the scores at THOSE experts (-1:
    its own choice), as in ``lm_reference.router_weights``."""
    s = jax.nn.sigmoid(mm(h2, lp["router"]))
    order = jnp.argsort(-(s + lp[BIAS]), axis=-1, stable=True)
    chosen = order[:, : m["top_k"]].astype(jnp.int32)
    top_e = chosen if given is None else jnp.where(given >= 0, given, chosen)
    top_w = jnp.take_along_axis(s, top_e, axis=-1)
    if m["norm_topk"]:
        top_w = top_w / jnp.sum(top_w, -1, keepdims=True)
    top_w = top_w * m["routed_scale"]
    onehot = jax.nn.one_hot(top_e, m["experts"], dtype=s.dtype)
    return jnp.einsum("tk,tke->te", top_w, onehot), chosen


def experts(lp, x, m: dict, blocked: bool, given=None, held=None,
            offset=None):
    """``(y, chosen)``: the held experts' part of the layer's sum and the
    router's own top-k. ``blocked`` runs the held experts one after the
    other through one traced body (``lm_hybrid_reference.experts``
    says why). ``held`` and ``offset`` name another share of the layer
    (the share test)."""
    held = m["held"] if held is None else held
    offset = m["offset"] if offset is None else offset
    shape = x.shape
    h2 = rms(x, lp["ln2"], m["eps"]).reshape(-1, shape[-1])
    w, chosen = router_weights(lp, h2, m, given)

    def weighted(h, w_e, gate, up, down):
        return w_e[:, None] * base.ffn(h, gate, up, down)

    mine = w[:, offset:offset + held].T  # [held, T]
    stacked = tuple(lp[k][:held] for k in ("we_gate", "we_up", "we_down"))
    if blocked:
        y, _ = jax.lax.scan(
            lambda y, e: (y + jax.checkpoint(weighted)(h2, *e), None),
            jnp.zeros_like(h2), (mine, *stacked),
        )
    else:
        y = jnp.zeros_like(h2)
        for j in range(held):
            y = y + weighted(h2, mine[j], *(t[j] for t in stacked))
    return y.reshape(shape), chosen


def dense(lp, x, m: dict):
    h2 = rms(x, lp["ln2"], m["eps"])
    return base.ffn(h2, lp["w_gate"], lp["w_up"], lp["w_down"])


# -- the model ---------------------------------------------------------------


def hidden(params: dict, tokens, m: dict, blocked: bool = False,
           given=None):
    """``(x, chosen)``: the last layer's output [B, S, d], before the
    final norm, and every expert layer's own top-k [expert layers, T,
    k]. ``given`` [expert layers, T, k]: the choices to compute with
    instead (-1: its own)."""

    def layer(kind, moe, x, lp, given_here):
        h = rms(x, lp["ln1"], m["eps"])
        if kind == "conv":
            x = x + short_conv(lp, h, m)
        else:
            x = x + swa.attention(lp, h, m, "full", blocked)
        if not moe:
            return x + dense(lp, x, m), None
        y, chosen = experts(lp, x, m, blocked, given=given_here)
        return x + y, chosen

    x = params["emb"][tokens]
    chosen = []
    for i, kind in enumerate(m["kinds"]):
        moe = i >= m["dense_layers"]
        one = (
            jax.checkpoint(layer, static_argnums=(0, 1)) if blocked
            else layer
        )
        j = i - m["dense_layers"]
        x, c = one(
            kind, moe, x, layer_params(params, i),
            None if given is None or not moe else given[j],
        )
        if moe:
            chosen.append(c)
    return x, jnp.stack(chosen)


def head(params: dict, x, m: dict):
    """Logits over the slice: the final norm times the embedding's
    transpose (the tied head)."""
    return mm(rms(x, params["ln_f"], m["eps"]), params["emb"].T)


def forward(params: dict, tokens, m: dict, blocked: bool = False):
    """Logits [B, S, vocab] in f32."""
    return head(params, hidden(params, tokens, m, blocked)[0], m)


def loss(params: dict, tokens, m: dict, blocked: bool = False, given=None):
    """``(loss, chosen)``: mean next-token cross entropy (position i
    predicts token i+1 of its sequence, the last position of a sequence
    predicts nothing) and the routers' own choices."""

    def nll_sum(x_and_targets):  # rows of any sequences
        x, targets = x_and_targets
        logp = jax.nn.log_softmax(head(params, x, m), axis=-1)
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
    """``((loss, chosen), grads)``: the gradients of the ``trained``
    leaves only; ``given``: see :func:`router_weights`."""
    biases = {k: v for k, v in params.items() if k.endswith(BIAS)}
    with jax.default_matmul_precision("highest"):
        return jax.value_and_grad(
            lambda t: loss({**t, **biases}, tokens, m, blocked, given),
            has_aux=True,
        )(trained(params))


def load(used, m: dict):
    """[expert layers, experts]: each expert's assignments among the
    choices ``used`` [expert layers, T, k]."""
    return jnp.sum(
        jax.nn.one_hot(used, m["experts"], dtype=jnp.float32), axis=(1, 2)
    )


def update_bias(bias, counts):
    """The selection bias after a step whose assignments were ``counts``
    [.., experts]: every entry moved by ``BIAS_RATE`` towards the mean
    load of its layer, b += rate * sign(mean(counts) - counts)."""
    counts = jnp.asarray(counts, jnp.float32)
    return bias + BIAS_RATE * jnp.sign(
        jnp.mean(counts, axis=-1, keepdims=True) - counts
    )


def step_biases(params: dict, used, m: dict) -> dict:
    """Every expert layer's ``router_bias`` moved by :func:`update_bias`
    for the choices ``used`` [expert layers, T, k]."""
    names = [f"l{i}/{BIAS}" for i in expert_layers(m)]
    moved = update_bias(
        jnp.stack([params[k] for k in names]), load(used, m)
    )
    return dict(zip(names, moved))

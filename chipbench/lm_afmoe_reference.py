"""The plain reference of the ``lm_afmoe`` runner's configurations
(``model_type`` ``afmoe``, Arcee's Trinity family): a decoder whose every
layer is gated grouped-query softmax attention with a q/k norm, over the
last ``sliding_window`` keys and rotated (``sliding_attention``) or over
all of them and not rotated (``full_attention``, NoPE), each block's
output normed before its residual add (sandwich norms), followed by a
dense SwiGLU (the first ``num_dense_layers``) or a top-k expert layer
whose router scores by a sigmoid and picks by the scores plus a bias,
beside a shared expert; an embedding scaled by sqrt(d) and an untied
head. Its loss and, through ``jax.grad``, its gradients, in
``jax.numpy`` and float32 under ``jax.default_matmul_precision
("highest")``; the bias's update by its own rule. It imports nothing of
``parameter_server_tpu``; the norm, the products, the SwiGLU, the masked
attention of one head, the rotation, the sigmoid router and the bias's
rule are ``lm_reference.py``'s, ``lm_swa_reference.py``'s and
``lm_conv_reference.py``'s (the benchmark's own files beside this one).

The equations, x [B, S, d], every norm RMSNorm(eps), no bias anywhere
(the ``afmoe`` modeling code of ``transformers``):

    x_0 = E[tok] * sqrt(d)                      (mup_enabled)
    h = norm_in(x)
    q = norm_D(h W_q) by head, k = norm_D(h W_k) by K/V head, v = h W_v
    q, k rotated in half-split pairs by the plain table of rope_theta
          ONLY in a sliding_attention layer; a full layer is NoPE
    s_tu = q_t . k_u / sqrt(D), kept where u <= t and, in a
          sliding_attention layer, t - u < sliding_window; query head h
          reads K/V head h // (heads / kv_heads)
    a = concat_h(softmax(s) v) * sigmoid(h W_g)     (W_g [d, heads x D])
    x = x + norm_post_attn(a W_o)
    u = norm_pre_ffn(x)
    f = W_down(SiLU(W_gate u) * W_up u)  in the first num_dense_layers;
        else s = sigmoid(u W_r) over ALL the router's experts, chosen =
        the k of the largest s + b, w_e = route_scale * s_e / sum_chosen s
        (route_norm; the modeling code adds 1e-20 to the sum, which no
        f32 sum of eight sigmoids notices), and
        f = SwiGLU_shared(u) + sum_{e chosen, held here} w_e SwiGLU_e(u)
    x = x + norm_post_ffn(f)
    logits = norm_f(x) W_head

b [E] takes no gradient and moves after each step by
``lm_conv_reference.update_bias`` (b_e += 0.001 sign(mean load -
load_e)).

Where it departs from the published model, as the file's ``assumed``
and ``reduced`` say: the cut (9 of 32 layers, 16 of 128 experts held,
a slice of the vocabulary): what the experts that are not held here
would add is left out, as the program leaves it out, and the post-FFN
norm norms what this chip's share gives (``share_ffn`` is the sum
before it, for the share test); the initialisation (``weights_fn``);
nothing masked at a packed document's boundary (``assumed.packing``);
the step's optimizer is the runner's Adafactor, not the family's.

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

from chipbench import lm_conv_reference as conv
from chipbench import lm_reference as base
from chipbench import lm_swa_reference as swa
from chipbench.lm_conv_reference import (  # noqa: F401  (the runner's names)
    BIAS, expert_layers, load, step_biases, trained, update_bias,
)
from chipbench.lm_reference import (  # noqa: F401  (the runner's names)
    description, layer_params, mm, rms,
)

SIGMA = base.SIGMA
# of the embedding's rows, before the muP scale: four times SIGMA, so
# that a token's own row weighs in the residual stream beside what each
# post-normed block adds (the file's assumed.initialisation has the
# readings)
EMB_SIGMA = 4 * SIGMA
HEAD_BLOCK = 1024  # rows of the head's logits alive at a time
KINDS = {"sliding_attention": "window", "full_attention": "full"}


def model(desc: dict) -> dict:
    """The sizes the reference computes with, from the configuration's
    file (the published ``config.json`` keys, ``published`` for the
    counts that were cut, ``share`` for which experts are held)."""
    published = desc.get("published", {})
    share = desc.get("share", {})
    d = desc["hidden_size"]
    return {
        "d": d, "vocab": desc["vocab_size"],
        "layers": desc["num_hidden_layers"],
        "kinds": tuple(KINDS[t] for t in desc["layer_types"]),
        "dense_layers": desc["num_dense_layers"],
        "heads": desc["num_attention_heads"],
        "kv_heads": desc["num_key_value_heads"],
        "head_dim": desc["head_dim"], "window": desc["sliding_window"],
        "rope": {"window": {"rope_theta": desc["rope_theta"]}},
        "eps": desc["rms_norm_eps"],
        "emb_scale": math.sqrt(d) if desc["mup_enabled"] else 1.0,
        "d_ff": desc["intermediate_size"],
        "d_expert": desc["moe_intermediate_size"],
        "experts": published.get("num_experts", desc["num_experts"]),
        "held": desc["num_experts"],
        "offset": share.get("expert_offset", 0),
        "top_k": desc["num_experts_per_tok"],
        "shared": desc["num_shared_experts"],
        "norm_topk": desc["route_norm"],
        "routed_scale": float(desc["route_scale"]),
    }


def shapes(m: dict) -> dict:
    """Every leaf of the model as held here: name -> shape."""
    d, f, hd = m["d"], m["d_expert"], m["head_dim"]
    wide, narrow = m["heads"] * hd, m["kv_heads"] * hd
    out = {"emb": (m["vocab"], d), "head": (d, m["vocab"]), "ln_f": (d,)}
    for i in range(m["layers"]):
        layer = {
            "ln1": (d,), "ln2": (d,), "post_attn_norm": (d,),
            "post_ffn_norm": (d,), "wq": (d, wide), "wk": (d, narrow),
            "wv": (d, narrow), "wo": (wide, d), "wg": (d, wide),
            "q_norm": (hd,), "k_norm": (hd,),
        }
        if i < m["dense_layers"]:
            layer.update(
                w_gate=(d, m["d_ff"]), w_up=(d, m["d_ff"]),
                w_down=(m["d_ff"], d),
            )
        else:
            fs = m["shared"] * f
            layer.update({
                "router": (d, m["experts"]), BIAS: (m["experts"],),
                "we_gate": (m["held"], d, f), "we_up": (m["held"], d, f),
                "we_down": (m["held"], f, d), "ws_gate": (d, fs),
                "ws_up": (d, fs), "ws_down": (fs, d),
            })
        out.update({f"l{i}/{name}": shape for name, shape in layer.items()})
    return out


def weights_fn(m: dict, sharding=None):
    """A jitted ``key -> {name: f32 array}``: every matrix normal with
    sigma ``SIGMA`` (the untied head too) but the embedding, whose rows
    have ``EMB_SIGMA``, a key of its own each (by its place among the
    sorted names); every norm scale 1 and every selection bias 0. Made
    on the device, placed by ``sharding`` where one is given."""
    names = sorted(shapes(m).items())

    def make(key):
        out = {}
        for i, (name, shape) in enumerate(names):
            if name.endswith(BIAS):
                out[name] = jnp.zeros(shape, jnp.float32)
            elif len(shape) == 1:
                out[name] = jnp.ones(shape, jnp.float32)
            else:
                out[name] = (EMB_SIGMA if name == "emb" else SIGMA) * (
                    jax.random.normal(
                        jax.random.fold_in(key, i), shape, jnp.float32
                    )
                )
        return out

    return jax.jit(make, out_shardings=sharding)


def weights(seed: int, m: dict) -> dict:
    return weights_fn(m)(jax.random.PRNGKey(seed))


# -- the layers --------------------------------------------------------------


def attention(lp, h, m: dict, kind: str, blocked: bool):
    """The gated attention's output, before W_o: [B, S, heads x D]."""
    b, s, _ = h.shape
    nh, kvh, hd = m["heads"], m["kv_heads"], m["head_dim"]
    q = rms(mm(h, lp["wq"]).reshape(b, s, nh, hd), lp["q_norm"], m["eps"])
    k = rms(mm(h, lp["wk"]).reshape(b, s, kvh, hd), lp["k_norm"], m["eps"])
    v = mm(h, lp["wv"]).reshape(b, s, kvh, hd)
    window = None
    if kind == "window":  # the full layers are NoPE
        tables = swa.rope_tables(jnp.arange(s), hd, m["rope"]["window"])
        q, k = swa.rotate(q, *tables), swa.rotate(k, *tables)
        window = m["window"]
    q, k, v = (t.transpose(0, 2, 1, 3) for t in (q, k, v))
    group = nh // kvh

    def one(i):  # the i-th (sequence, query head)
        seq, head = i // nh, i % nh
        return swa.attend_one(
            q[seq, head], k[seq, head // group], v[seq, head // group], window
        )

    pairs = jnp.arange(b * nh)
    if blocked:
        out = jax.lax.map(jax.checkpoint(one), pairs)
    else:
        out = jax.vmap(one)(pairs)
    out = out.reshape(b, nh, s, hd).transpose(0, 2, 1, 3).reshape(b, s, -1)
    return out * jax.nn.sigmoid(mm(h, lp["wg"]))


def share_ffn(lp, x, m: dict, blocked: bool, given=None, held=None,
              offset=None):
    """``(f, chosen)``: what a chip that holds ``held`` experts from
    ``offset`` (this configuration's share by default) adds to the
    shared expert's output, before the post-FFN norm, and the router's
    own top-k (``given``: see ``lm_conv_reference.router_weights``)."""
    routed, chosen = conv.experts(
        lp, x, m, blocked, given=given, held=held, offset=offset
    )
    u = rms(x, lp["ln2"], m["eps"])
    return routed + base.ffn(u, lp["ws_gate"], lp["ws_up"], lp["ws_down"]), (
        chosen
    )


# -- the model ---------------------------------------------------------------


def hidden(params: dict, tokens, m: dict, blocked: bool = False,
           given=None):
    """``(x, chosen)``: the last layer's output [B, S, d], before the
    final norm, and every expert layer's own top-k [expert layers, T,
    k]. ``given`` [expert layers, T, k]: the choices to compute with
    instead (-1: its own)."""

    def layer(kind, moe, x, lp, given_here):
        h = rms(x, lp["ln1"], m["eps"])
        a = mm(attention(lp, h, m, kind, blocked), lp["wo"])
        x = x + rms(a, lp["post_attn_norm"], m["eps"])
        chosen = None
        if moe:
            f, chosen = share_ffn(lp, x, m, blocked, given=given_here)
        else:
            f = conv.dense(lp, x, m)
        return x + rms(f, lp["post_ffn_norm"], m["eps"]), chosen

    x = params["emb"][tokens] * m["emb_scale"]
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


def forward(params: dict, tokens, m: dict, blocked: bool = False):
    """Logits [B, S, vocab] in f32 (``lm_reference.head``: the final norm
    and the untied head over the slice)."""
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
    """``((loss, chosen), grads)``: the gradients of the ``trained``
    leaves only; ``given``: see ``lm_conv_reference.router_weights``."""
    biases = {k: v for k, v in params.items() if k.endswith(BIAS)}
    with jax.default_matmul_precision("highest"):
        return jax.value_and_grad(
            lambda t: loss({**t, **biases}, tokens, m, blocked, given),
            has_aux=True,
        )(trained(params))

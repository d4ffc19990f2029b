"""The plain reference of the ``lm_hybrid`` runner's configurations: a
decoder whose layers are gated delta-rule linear attention (KDA: Kimi
Linear, arXiv 2510.26692, laid out as ``flash-linear-attention``'s
``KimiDeltaAttention``) or gated softmax attention over grouped K/V
heads without rope, each followed by a top-k expert layer beside a
shared expert; its loss and, through ``jax.grad``, its gradients, in
``jax.numpy`` and float32 under ``jax.default_matmul_precision
("highest")``. No kernel, no chunked algebra: the recurrence runs TOKEN
BY TOKEN. It imports nothing of ``parameter_server_tpu``; the expert
layer, the norm and the softmax head's one-head attention are
``lm_reference.py``'s (the benchmark's own file beside this one).

Every layer, x [B, S, d], every norm RMSNorm(eps), no bias but ``bg``:

    x += Attn(norm(x));  x += Experts(norm(x))     (lm_reference.experts)

GQA layer (``i`` in ``gqa_layers``), h = norm(x):

    q = h W_q [heads x D];  k = h W_k, v = h W_v [kv_heads x D], no rotation
    att = softmax_causal(q k^T / sqrt(D)) v, each K/V head serving
          heads / kv_heads query heads
    out = (att * sigmoid(h W_g)) W_o                 (``use_gqa_gate``)

KDA layer (every other layer), per head, K = V = D:

    q~, k~, v~ = SiLU(conv(h W_q)), SiLU(conv(h W_k)), SiLU(conv(h W_v))
      conv: y_t = sum_{j<taps} w_j * x_{t-taps+1+j}, zero before the start
    q = q~ / sqrt(|q~|^2 + 1e-6),  k likewise,  v = v~
    g_t = -exp(A_log) softplus((h W_fa) W_fb + dt_bias);  alpha_t = exp(g_t)
    beta_t = 2 sigmoid(h W_beta)          (``kda_allow_neg_eigval``: the 2)
    S_t = (I - beta_t k_t k_t^T) Diag(alpha_t) S_{t-1} + beta_t k_t v_t^T
    o_t = D^-1/2 S_t^T q_t,   S_0 = 0 at the start of the sequence
    out = (norm_head(o_t) * o_norm * sigmoid((h W_ga) W_gb + bg)) W_o

Nothing is reset at a packed document's boundary (the file's
``assumed.packing``). What the experts that are not held here would add
is left out, as the program leaves it out; the vocabulary is the slice
the configuration states.

``blocked=True`` computes the same arithmetic with each layer, each
(sequence, head) of the softmax attention, each expert, each block of
``KDA_HEADS_BLOCK`` heads of a KDA layer, each block of ``SCAN_BLOCK``
tokens of the recurrence and each block of ``HEAD_BLOCK`` rows of the
head under ``jax.checkpoint``, so that the backward pass
recomputes them one at a time: what fits a chip beside the weights and
their gradients. The tests hold blocked and plain to each other.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from chipbench import lm_reference as base
from chipbench.lm_reference import (  # noqa: F401  (the runner's names)
    description, layer_params, mm, rms,
)

SIGMA = base.SIGMA
L2_EPS = 1e-6
SCAN_BLOCK = 64  # tokens of the recurrence recomputed at a time
KDA_HEADS_BLOCK = 16  # heads of a KDA layer computed at a time
HEAD_BLOCK = 1024  # rows of the head's logits alive at a time


def model(desc: dict) -> dict:
    """The sizes the reference computes with, from the configuration's
    file (the published ``config.json`` keys, ``published`` for the
    counts that were cut, ``share`` for which experts are held)."""
    published = desc.get("published", {})
    share = desc.get("share", {})
    lin = desc["linear_attn_config"]
    gqa = set(desc["gqa_layers"])
    return {
        "d": desc["hidden_size"], "vocab": desc["vocab_size"],
        "layers": desc["num_hidden_layers"],
        "kinds": tuple(
            "gqa" if i in gqa else "kda"
            for i in range(desc["num_hidden_layers"])
        ),
        "heads": desc["num_attention_heads"],
        "kv_heads": desc["num_key_value_heads"],
        "head_dim": desc["head_dim"], "gqa_gate": desc["use_gqa_gate"],
        "lin_heads": lin["num_heads"], "lin_dim": lin["head_dim"],
        "taps": lin["short_conv_kernel_size"],
        "rank": lin.get("gate_rank", lin["head_dim"]),
        "neg_eigval": desc["kda_allow_neg_eigval"],
        "eps": desc["rms_norm_eps"],
        "d_expert": desc["moe_intermediate_size"],
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


def attention_shapes(m: dict, kind: str) -> dict:
    """The leaves of one layer's attention, by kind."""
    d = m["d"]
    if kind == "gqa":
        wide, narrow = (n * m["head_dim"] for n in (m["heads"], m["kv_heads"]))
        out = {"wq": (d, wide), "wk": (d, narrow), "wv": (d, narrow),
               "wo": (wide, d)}
        if m["gqa_gate"]:
            out["wg"] = (d, wide)
        return out
    w, r, taps = m["lin_heads"] * m["lin_dim"], m["rank"], m["taps"]
    return {
        "wq": (d, w), "wk": (d, w), "wv": (d, w), "wo": (w, d),
        "conv_q": (taps, w), "conv_k": (taps, w), "conv_v": (taps, w),
        "wf_a": (d, r), "wf_b": (r, w), "a_log": (m["lin_heads"],),
        "dt_bias": (w,), "wbeta": (d, m["lin_heads"]), "wg_a": (d, r),
        "wg_b": (r, w), "bg": (w,), "o_norm": (m["lin_dim"],),
    }


def shapes(m: dict) -> dict:
    """Every leaf of the model as held here: name -> shape."""
    d, f = m["d"], m["d_expert"]
    out = {"emb": (m["vocab"], d), "head": (d, m["vocab"]), "ln_f": (d,)}
    rest = {
        "ln1": (d,), "ln2": (d,), "router": (d, m["experts"]),
        "we_gate": (m["held"], d, f), "we_up": (m["held"], d, f),
        "we_down": (m["held"], f, d),
    }
    if m["shared"]:
        fs = m["shared"] * f
        rest.update(ws_gate=(d, fs), ws_up=(d, fs), ws_down=(fs, d))
    for i, kind in enumerate(m["kinds"]):
        for name, shape in {**attention_shapes(m, kind), **rest}.items():
            out[f"l{i}/{name}"] = shape
    return out


def weights_fn(m: dict, sharding=None):
    """A jitted ``key -> {name: f32 array}``, a key of its own for each
    leaf (by its place among the sorted names): every matrix (the
    convolutions' taps too) normal with sigma ``SIGMA``; norm scales 1,
    ``bg`` 0; ``a_log`` = ln U(1, 16) and ``dt_bias`` = softplus^-1 of
    exp U(ln 0.001, ln 0.1), so that the decay is neither 0 nor 1 at
    random weights (the file's ``assumed.initialisation``)."""
    names = sorted(shapes(m).items())

    def one(key, name, shape):
        leaf = name.rsplit("/", 1)[-1]
        if leaf == "a_log":
            return jnp.log(
                jax.random.uniform(key, shape, jnp.float32, 1.0, 16.0)
            )
        if leaf == "dt_bias":
            dt = jnp.exp(jax.random.uniform(
                key, shape, jnp.float32, jnp.log(0.001), jnp.log(0.1)
            ))
            return dt + jnp.log(-jnp.expm1(-dt))
        if leaf == "bg":
            return jnp.zeros(shape, jnp.float32)
        if len(shape) == 1:
            return jnp.ones(shape, jnp.float32)
        return SIGMA * jax.random.normal(key, shape, jnp.float32)

    def make(key):
        return {
            name: one(jax.random.fold_in(key, i), name, shape)
            for i, (name, shape) in enumerate(names)
        }

    return jax.jit(make, out_shardings=sharding)


def weights(seed: int, m: dict) -> dict:
    return weights_fn(m)(jax.random.PRNGKey(seed))


# -- the softmax layer -----------------------------------------------------


def gqa_attention(lp, h, m: dict, blocked: bool):
    b, s, _ = h.shape
    nh, kvh, hd = m["heads"], m["kv_heads"], m["head_dim"]
    q = mm(h, lp["wq"]).reshape(b, s, nh, hd)
    # each K/V head serves heads / kv_heads query heads
    widen = lambda t: jnp.repeat(  # noqa: E731
        t.reshape(b, s, kvh, hd), nh // kvh, axis=2
    )
    k, v = widen(mm(h, lp["wk"])), widen(mm(h, lp["wv"]))
    flat = lambda t: t.transpose(0, 2, 1, 3).reshape(b * nh, s, hd)  # noqa
    one = lambda qkv: base.attend_one(*qkv, hd ** -0.5)  # noqa: E731
    if blocked:
        out = jax.lax.map(jax.checkpoint(one), (flat(q), flat(k), flat(v)))
    else:
        out = jax.vmap(lambda *qkv: one(qkv))(flat(q), flat(k), flat(v))
    out = out.reshape(b, nh, s, hd).transpose(0, 2, 1, 3)
    out = out.reshape(b, s, nh * hd)
    if m["gqa_gate"]:
        out = out * jax.nn.sigmoid(mm(h, lp["wg"]))
    return mm(out, lp["wo"])


# -- the linear-attention layer ----------------------------------------------


def causal_conv(x, taps):
    """y_t = sum_j taps[j] * x_{t - n + 1 + j}, x [B, S, W], taps [n, W]."""
    n, s = taps.shape[0], x.shape[1]
    padded = jnp.concatenate(
        [jnp.zeros((x.shape[0], n - 1, x.shape[2]), x.dtype), x], axis=1
    )
    y = jnp.zeros_like(x)
    for j in range(n):
        y = y + padded[:, j:j + s] * taps[j]
    return y


def delta_rule(q, k, v, alpha, beta, blocked: bool):
    """The recurrence token by token: q, k, alpha [B, S, H, K], v
    [B, S, H, V], beta [B, S, H]; o [B, S, H, V]."""
    b, s, h, dk = q.shape

    def token(state, x):
        q_t, k_t, v_t, a_t, b_t = x
        state = a_t[..., None] * state  # Diag(alpha) S
        u = v_t - jnp.sum(k_t[..., None] * state, axis=-2)
        state = state + (b_t[..., None] * k_t)[..., None] * u[..., None, :]
        return state, jnp.sum(q_t[..., None] * state, axis=-2) * dk ** -0.5

    def block(state, xs):
        return jax.lax.scan(token, state, xs)

    time_first = lambda t: jnp.moveaxis(t, 1, 0)  # noqa: E731
    xs = tuple(map(time_first, (q, k, v, alpha, beta)))
    state = jnp.zeros((b, h, dk, v.shape[-1]), jnp.float32)
    if blocked and s % SCAN_BLOCK == 0:
        xs = jax.tree.map(
            lambda t: t.reshape(s // SCAN_BLOCK, SCAN_BLOCK, *t.shape[1:]), xs
        )
        _, out = jax.lax.scan(jax.checkpoint(block), state, xs)
        out = out.reshape(s, *out.shape[2:])
    else:
        _, out = block(state, xs)
    return jnp.moveaxis(out, 0, 1)


KDA_LEAVES_BY_HEAD = {  # leaf -> the axis its heads lie along
    "wq": 1, "wk": 1, "wv": 1, "conv_q": 1, "conv_k": 1, "conv_v": 1,
    "wf_b": 1, "a_log": 0, "dt_bias": 0, "wbeta": 1, "wg_b": 1, "bg": 0,
    "wo": 0,
}


def kda_heads(lp, h, m: dict, heads: int, blocked: bool):
    """The part of the layer's output that ``heads`` of its heads give:
    ``lp`` holds those heads' slices of the leaves that have heads
    (``KDA_LEAVES_BY_HEAD``) and the others whole. The heads do not see
    each other before ``wo`` sums them."""
    b, s, _ = h.shape
    hd = m["lin_dim"]
    split = lambda t: t.reshape(b, s, heads, hd)  # noqa: E731

    def short(name):
        return split(jax.nn.silu(
            causal_conv(mm(h, lp["w" + name]), lp["conv_" + name])
        ))

    unit = lambda t: t / jnp.sqrt(  # noqa: E731
        jnp.sum(t * t, -1, keepdims=True) + L2_EPS
    )
    q, k, v = unit(short("q")), unit(short("k")), short("v")
    g = -jnp.exp(lp["a_log"])[:, None] * jax.nn.softplus(
        split(mm(mm(h, lp["wf_a"]), lp["wf_b"]) + lp["dt_bias"])
    )
    beta = jax.nn.sigmoid(mm(h, lp["wbeta"])) * (
        2.0 if m["neg_eigval"] else 1.0
    )
    o = delta_rule(q, k, v, jnp.exp(g), beta, blocked)
    o = rms(o, lp["o_norm"], m["eps"]) * split(jax.nn.sigmoid(
        mm(mm(h, lp["wg_a"]), lp["wg_b"]) + lp["bg"]
    ))
    return mm(o.reshape(b, s, heads * hd), lp["wo"])


def kda_attention(lp, h, m: dict, blocked: bool):
    nh = m["lin_heads"]
    if not blocked or nh % KDA_HEADS_BLOCK:
        return kda_heads(lp, h, m, nh, blocked)
    # blocked: KDA_HEADS_BLOCK heads at a time, one after the other,
    # each block recomputed from h in the backward pass (a head's
    # intermediates are a dozen [S, D] arrays in f32)
    blocks = nh // KDA_HEADS_BLOCK

    def by_block(name, leaf):
        axis = KDA_LEAVES_BY_HEAD.get(name)
        if axis is None:  # no heads: every block reads it whole
            return jnp.broadcast_to(leaf, (blocks,) + leaf.shape)
        shape = leaf.shape
        leaf = leaf.reshape(
            shape[:axis] + (blocks, shape[axis] // blocks) + shape[axis + 1:]
        )
        return jnp.moveaxis(leaf, axis, 0)

    one = jax.checkpoint(
        lambda part: kda_heads(part, h, m, KDA_HEADS_BLOCK, blocked)
    )
    return jnp.sum(jax.lax.map(
        one, {k: by_block(k, lp[k]) for k in attention_shapes(m, "kda")}
    ), axis=0)


# -- the expert layer --------------------------------------------------------


def experts(lp, x, m: dict, blocked: bool, given=None):
    """``lm_reference.experts``: the held experts' part of the sum, the
    shared expert whole, and the router's own top-k. ``blocked`` runs
    the held experts one after the other through ONE traced body (a
    ``lax.scan`` over the stacked leaves that adds each expert's term to
    the sum in the order the plain loop adds them) where the plain form
    repeats the body for every expert of every layer: eight experts'
    three f32 products at "highest", forward, recomputed and backward,
    in each of four layers were two thirds of the step's compiled code
    (0.54 of 0.81 GB, compiled for a v5e, PR 33)."""
    if not blocked:
        return base.experts(lp, x, m, False, given=given)
    shape = x.shape
    h2 = rms(x, lp["ln2"], m["eps"]).reshape(-1, shape[-1])
    w, chosen = base.router_weights(lp, h2, m, given)

    @jax.checkpoint
    def weighted(h, w_e, gate, up, down):
        return w_e[:, None] * base.ffn(h, gate, up, down)

    held = w[:, m["offset"]:m["offset"] + m["held"]].T  # [held, T]
    y, _ = jax.lax.scan(
        lambda y, e: (y + weighted(h2, *e), None), jnp.zeros_like(h2),
        (held, lp["we_gate"], lp["we_up"], lp["we_down"]),
    )
    if m["shared"]:
        y = y + weighted(
            h2, jnp.ones_like(w[:, 0]), lp["ws_gate"], lp["ws_up"],
            lp["ws_down"],
        )
    return y.reshape(shape), chosen


# -- the model ---------------------------------------------------------------


def hidden(params: dict, tokens, m: dict, blocked: bool = False,
           given=None):
    """``(x, chosen)``: the last layer's output [B, S, d], before the
    final norm, and every layer's own top-k [layers, T, k]. ``given``
    [layers, T, k]: the choices to compute with instead (-1: its own)."""

    def layer(kind, x, lp, given_here):
        h = rms(x, lp["ln1"], m["eps"])
        attend = gqa_attention if kind == "gqa" else kda_attention
        x = x + attend(lp, h, m, blocked)
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

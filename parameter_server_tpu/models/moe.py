"""Mixture-of-experts FFNs: two layers, one grouped product.

``moe_ffn`` is the switch layer of ``LMConfig.moe_every``: top-1 routing
with a per-token-shard capacity, experts sharded over a mesh axis, tokens
routed with two ``all_to_all`` collectives (the Switch/GShard dispatch):
each shard builds a [tokens, E, C] dispatch one-hot (C = capacity per
expert per shard), the 2-layer FFN runs as dense [E/n, n*C, d] batched
matmuls, and tokens over capacity pass through on the residual path.

``topk_moe_ffn`` is the dropless layer of ``LMConfig.moe``
(``TopKMoEConfig``): softmax over ALL ``n_experts`` (or a sigmoid of
each, with a bias that picks the experts and does not weight them), the
``top_k`` largest renormalised, no capacity and no dropped token, gated-SiLU
experts beside an optional shared expert. The layer is told which
experts it holds (``experts_held`` from ``expert_offset``): it routes
over all of them and computes its own experts' part, which is what one
chip of an expert-parallel deployment computes before the exchange; what
the absent experts would add is left out. Its tokens are sorted by
expert into one buffer with a row for every assignment (tokens x
``top_k``), the held ones first, and the experts run as grouped matrix
products over it (``grouped_matmul``: every caller of a grouped product
in this repo goes through that function). On one chip it runs without
its exchange; an exchange over a mesh axis is not built.

A layer that holds few of the experts fills few of those rows, and only
the grouped product skips an empty one: gathers, SiLU, casts and masks
move it. So the buffer is computed in two parts of static size
(``head_rows``): the HEAD, ``HEAD_FACTOR`` times the rows the held
experts expect (tokens x ``top_k`` x held / ``n_experts``), always; the
TAIL, every row after it, under ``lax.cond`` in the steps in which a
held assignment falls there, its output added to the head's. Every
assignment still has its row whatever the router does. Past an eighth
of the experts held the head stops at half the buffer (a quarter held:
twice the expected rows); with half of the experts or more held it is
the whole buffer, and there is no tail and no conditional. The branch
not taken costs nothing because ``_head_and_tail`` differentiates the
tail itself: autodiff would carry the tail's residuals out of the
conditional and fill them with zeros where it did not run, and add
three zero weight gradients; here the backward pass is again a
conditional, which recomputes the tail from its inputs and adds its
gradients to the head's inside the branch taken, so that the other
branch is an identity.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from jax import shard_map
from jax.ad_checkpoint import checkpoint_name
from jax.sharding import Mesh, PartitionSpec as P


def init_moe(key, d_model: int, d_ff: int, n_experts: int) -> Dict[str, jax.Array]:
    k1, k2, k3 = jax.random.split(key, 3)
    scale = 1.0 / np.sqrt(d_model)
    return {
        "router": jax.random.normal(k1, (d_model, n_experts)) * scale,
        "w_in": jax.random.normal(k2, (n_experts, d_model, d_ff)) * scale,
        "w_out": jax.random.normal(k3, (n_experts, d_ff, d_model))
        * (1.0 / np.sqrt(d_ff)),
    }


def _route(x, router, n_experts: int, capacity: int):
    """Shard-local switch routing: returns (dispatch [T,E,C] one-hot,
    combine [T,E,C] gate-weighted) for this shard's T tokens."""
    logits = x @ router  # [T, E]
    gates = jax.nn.softmax(logits, axis=-1)
    expert = jnp.argmax(gates, axis=-1)  # [T]
    gate = jnp.take_along_axis(gates, expert[:, None], axis=1)[:, 0]  # [T]
    onehot = jax.nn.one_hot(expert, n_experts, dtype=jnp.float32)  # [T, E]
    # position of each token within its expert's buffer (arrival order)
    pos = (jnp.cumsum(onehot, axis=0) - 1.0) * onehot  # [T, E]
    keep = (pos < capacity) * onehot  # over-capacity tokens drop
    pos_clipped = jnp.minimum(pos, capacity - 1).astype(jnp.int32)
    dispatch = keep[:, :, None] * jax.nn.one_hot(
        pos_clipped, capacity, dtype=jnp.float32
    )  # [T, E, C]
    combine = dispatch * gate[:, None, None]
    return dispatch, combine


def _expert_ffn(w_in, w_out, h):
    return jnp.einsum(
        "ecf,efo->eco", jax.nn.relu(jnp.einsum("ecd,edf->ecf", h, w_in)), w_out
    )


def moe_ffn_dense(params, x, n_shards: int, capacity_factor: float = 1.25):
    """Single-device reference: identical math to the sharded layer —
    tokens processed in ``n_shards`` chunks with per-chunk routing and
    capacity, experts all local. For tests."""
    b, s, d = x.shape
    n_experts = params["router"].shape[1]
    s_loc = s // n_shards
    t_loc = b * s_loc
    capacity = max(1, int(capacity_factor * t_loc / n_experts))
    outs = []
    for i in range(n_shards):
        # mirror the sharded layer exactly: a shard owns a SEQUENCE slice
        # (all batch rows), flattened in the same [B, s_loc] order
        xt = x[:, i * s_loc : (i + 1) * s_loc, :].reshape(-1, d)
        dispatch, combine = _route(xt, params["router"], n_experts, capacity)
        h = jnp.einsum("tec,td->ecd", dispatch, xt)
        out_e = _expert_ffn(params["w_in"], params["w_out"], h)
        outs.append(
            jnp.einsum("tec,ecd->td", combine, out_e).reshape(b, s_loc, d)
        )
    return jnp.concatenate(outs, axis=1)


@functools.partial(
    jax.jit, static_argnames=("mesh", "axis", "capacity_factor")
)
def moe_ffn(
    params,
    x: jax.Array,
    *,
    mesh: Mesh,
    axis: str = "data",
    capacity_factor: float = 1.25,
) -> jax.Array:
    """Expert-parallel MoE FFN. ``x``: [B, S, d] sequence-sharded over
    ``axis``; expert tables sharded over the same axis (E % n == 0).
    Output keeps x's sharding."""
    n = mesh.shape[axis]
    n_experts = params["router"].shape[1]
    assert n_experts % n == 0, f"experts {n_experts} must divide mesh axis {n}"

    def local(router, w_in, w_out, x):
        b, s_loc, d = x.shape
        xt = x.reshape(-1, d)  # [T_loc, d]
        t_loc = xt.shape[0]
        capacity = max(1, int(capacity_factor * t_loc / n_experts))
        dispatch, combine = _route(xt, router, n_experts, capacity)
        h = jnp.einsum("tec,td->ecd", dispatch, xt)  # [E, C, d]
        # a2a: scatter experts, gather token-shards -> local experts see
        # every shard's buffer: [E/n, n*C, d]
        h = jax.lax.all_to_all(h, axis, split_axis=0, concat_axis=1, tiled=True)
        out_e = _expert_ffn(w_in, w_out, h)  # [E/n, n*C, d]
        out_e = jax.lax.all_to_all(
            out_e, axis, split_axis=1, concat_axis=0, tiled=True
        )  # [E, C, d]
        out = jnp.einsum("tec,ecd->td", combine, out_e)
        return out.reshape(b, s_loc, d)

    return shard_map(
        local,
        mesh=mesh,
        in_specs=(P(), P(axis), P(axis), P(None, axis, None)),
        out_specs=P(None, axis, None),
        check_vma=False,
    )(params["router"], params["w_in"], params["w_out"], x)


# ---------------------------------------------------------------------------
# the dropless top-k layer
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class TopKMoEConfig:
    n_experts: int  # the router's width: every expert of the layer
    top_k: int
    d_expert: int  # width of one expert's gated FFN
    n_shared: int = 0  # shared experts, computed for every token
    # the share this program holds: ``experts_held`` experts from
    # ``expert_offset``; None = all of them
    experts_held: Optional[int] = None
    expert_offset: int = 0
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 1.0
    # how a token scores the experts: "softmax" over all of them, or
    # "sigmoid" of each logit on its own
    scoring: str = "softmax"
    # a bias [n_experts] (leaf ``router_bias``) added to the scores where
    # the experts are chosen and nowhere else: the weights are the scores
    # at the choices. No gradient reaches it; the train step moves it
    # against each expert's load (``update_expert_bias``)
    expert_bias: bool = False

    def __post_init__(self):
        if not 1 <= self.top_k <= self.n_experts:
            raise ValueError(
                f"top_k {self.top_k} must be in [1, n_experts="
                f"{self.n_experts}]"
            )
        if self.scoring not in ("softmax", "sigmoid"):
            raise ValueError(
                f"scoring {self.scoring!r}: 'softmax' or 'sigmoid'"
            )
        if self.expert_bias and self.scoring != "sigmoid":
            raise ValueError(
                f"expert_bias with {self.scoring} scoring: the selection "
                "bias is built for sigmoid scoring only"
            )
        if not (0 <= self.expert_offset
                and self.expert_offset + self.held <= self.n_experts):
            raise ValueError(
                f"experts [{self.expert_offset}, {self.expert_offset} + "
                f"{self.held}) are not among the {self.n_experts}"
            )

    @property
    def held(self) -> int:
        return (
            self.n_experts if self.experts_held is None else self.experts_held
        )


def init_topk_moe(key, d_model: int, cfg: TopKMoEConfig, std: float):
    ks = jax.random.split(key, 7)
    e, f, fs = cfg.held, cfg.d_expert, cfg.n_shared * cfg.d_expert
    shapes = {
        "router": (d_model, cfg.n_experts),
        "we_gate": (e, d_model, f), "we_up": (e, d_model, f),
        "we_down": (e, f, d_model),
    }
    if cfg.n_shared:
        shapes.update(
            ws_gate=(d_model, fs), ws_up=(d_model, fs), ws_down=(fs, d_model)
        )
    out = {
        name: std * jax.random.normal(k, shape, jnp.float32)
        for k, (name, shape) in zip(ks, shapes.items())
    }
    if cfg.expert_bias:
        out["router_bias"] = jnp.zeros((cfg.n_experts,), jnp.float32)
    return out


def grouped_matmul(x, w, group_sizes):
    """``x[rows of group g] @ w[g]`` for every group, f32 out: x [R, k]
    sorted by group, w [G, k, n], ``group_sizes`` [G] int32 with sum <=
    R. Rows past the sum come out zero."""
    return jax.lax.ragged_dot(
        x, w, group_sizes, preferred_element_type=jnp.float32
    )


def swiglu(h, w_gate, w_up, w_down):
    """W_down(SiLU(W_gate h) * W_up h), activations in ``h.dtype``."""
    return (jax.nn.silu(h @ w_gate) * (h @ w_up)) @ w_down


# the name of each token's choices for ``jax.checkpoint`` policies
# (``save_only_these_names``): a recomputed forward that chose again
# would decide a near-tie by its own rounding, and the backward pass
# would differentiate another routing than the forward pass computed
TOP_E = "moe_top_e"


def route_topk(h32, router, cfg: TopKMoEConfig, bias=None):
    """The scores (``cfg.scoring``: softmax over all experts, or a
    sigmoid each) in f32 (the matmul too: HIGHEST, or the TPU rounds its
    inputs to bf16), the ``top_k`` largest, renormalised: (weights
    [T, k] f32, experts [T, k] int32). With ``bias`` [n_experts] the
    experts are the ``top_k`` largest of scores + bias and the weights
    the scores alone at them; no gradient reaches the bias. The weights
    are read at the choices as named (``TOP_E``), so that a layer
    rematerialised under a policy that saves them routes as its forward
    pass did."""
    logits = jnp.dot(
        h32, router.astype(jnp.float32),
        precision=jax.lax.Precision.HIGHEST,
    )
    if cfg.scoring == "sigmoid":
        p = jax.nn.sigmoid(logits)
    else:
        p = jax.nn.softmax(logits, axis=-1)
    pick = p if bias is None else p + jax.lax.stop_gradient(bias)
    top_e = checkpoint_name(
        jax.lax.top_k(pick, cfg.top_k)[1].astype(jnp.int32), TOP_E
    )
    top_p = jnp.take_along_axis(p, top_e, axis=-1)
    if cfg.norm_topk_prob:
        top_p = top_p / jnp.sum(top_p, axis=-1, keepdims=True)
    return top_p * cfg.routed_scaling_factor, top_e


@jax.custom_vjp
def _dispatch(h, row_token, slot_row):
    """xs[r] = h[row_token[r]]: each buffer row's token."""
    del slot_row
    return h[row_token]


def _combine_rows(ys, slot_row):
    """y[t] = sum_j ys[slot_row[t, j]], a slot with no row (== len(ys))
    adding nothing."""
    ext = jnp.concatenate([ys, jnp.zeros_like(ys[:1])])
    return jnp.sum(ext[slot_row], axis=1)


@jax.custom_vjp
def _combine(ys, row_token, slot_row):
    del row_token
    return _combine_rows(ys, slot_row)


# dispatch and combine are each other's transpose, and ``slot_row`` is
# the inverse of ``row_token``: both backward passes are gathers, where
# autodiff would scatter-add (T*k rows onto the one empty slot's row)
def _dispatch_fwd(h, row_token, slot_row):
    return h[row_token], (row_token, slot_row)


def _dispatch_bwd(res, g):
    _, slot_row = res
    return _combine_rows(g, slot_row), None, None


def _combine_fwd(ys, row_token, slot_row):
    return _combine_rows(ys, slot_row), (row_token, slot_row)


def _combine_bwd(res, g):
    row_token, _ = res
    return g[row_token], None, None


_dispatch.defvjp(_dispatch_fwd, _dispatch_bwd)
_combine.defvjp(_combine_fwd, _combine_bwd)


def sort_by_expert(top_e, cfg: TopKMoEConfig):
    """The sorted-token buffer's index arrays for assignments ``top_e``
    [T, k]: one row for every assignment (T * k rows, so none can be
    dropped whatever the router does), those whose expert is held here
    first, sorted by expert. So rows [0, held assignments) carry a token
    and the rest are empty: ``split_buffer`` cuts the arrays at
    ``head_rows`` into the head that is always computed and the tail
    that is computed when a held assignment falls in it.

    Returns ``row_token`` [T*k] (the token of each row; rows past the
    last held assignment point at token 0 and are masked by
    ``row_valid``), ``row_slot`` [T*k] (which of the token's k choices),
    ``row_valid`` [T*k] bool, ``slot_row`` [T, k] (the row of each
    assignment, or ``T*k`` where its expert is not held) and
    ``group_sizes`` [held]."""
    t, k = top_e.shape
    local = top_e.reshape(-1) - cfg.expert_offset
    held = (local >= 0) & (local < cfg.held)
    key = jnp.where(held, local, cfg.held)  # not held: sorted to the end
    order = jnp.argsort(key, stable=True).astype(jnp.int32)  # [T*k]
    # counted by comparison, inverted by a second sort: a scatter of T*k
    # single elements is the slow way to do either on a TPU
    group_sizes = jnp.sum(
        key[:, None] == jnp.arange(cfg.held, dtype=key.dtype), axis=0
    ).astype(jnp.int32)
    row_valid = jnp.arange(t * k, dtype=jnp.int32) < jnp.sum(group_sizes)
    picked = jnp.where(row_valid, order, 0)
    # the inverse permutation: assignment -> its row, if it has one
    rank = jnp.argsort(order).astype(jnp.int32)
    slot_row = jnp.where(held, rank, t * k).reshape(t, k)
    return picked // k, picked % k, row_valid, slot_row, group_sizes


# the stats of ``topk_moe_ffn`` that are counts: they add up over layers
# and steps, where the others are kept by layer and of the last step
MOE_COUNTS = ("expert_rows", "buffer_passes")
# the stat of a layer with ``expert_bias``: how many tokens chose each of
# ALL ``n_experts`` (held here or not), what its bias is moved against
EXPERT_LOAD = "expert_load"

# the router's input, choices and weights are returned at this many
# tokens (evenly strided), for a caller that holds the router's
# arithmetic to a reference on the same input
PROBE_TOKENS = 256

# The head of the sorted buffer, in multiples of the rows the held
# experts expect (T * k * held / n_experts). The records (PERF.md, PR 29
# and 30, mistral_small4_ep16.packed8k: 8 of 128 experts held, 65,536
# rows): only the held experts' outputs reach the loss, so the router
# moves their load to 43-190% of the expected within a window of 25
# steps; past 400% it went in one step of PR 29's fifty runs (484%) and
# in 7 of the 1,960 passes of PR 30's sixteen. A buffer row costs
# 2.2 ms a step per 1,024: a head of 2x would buy 18 ms of 760 more and
# flip between the paths inside ordinary windows; at 4x the tail runs
# in the rare step. A layer, forward and backward: the head alone 70 ms
# where the whole buffer took 93.5; head and tail 24 ms more than the
# whole buffer at the same load (each part's combine gathers every
# slot).
#
# The multiple shrinks as the share held grows (``head_rows``): the sum
# over 16 of 64 experts moves less against its mean than the sum over 8
# of 128, and cannot pass 4x at all. The records at a quarter held
# (PERF.md, PR 35 and 36, mellum2_ep4.packed8k_mb1: 16 of 64, 65,536
# rows, 16,384 expected): a layer draws 75-117% of the expected at a
# run's first launch and, in the late layers, 150-268% by its 33rd: the
# tail ran in 55 of 3,640 counted passes (1.5%) of windows of 33
# launches and in 13% of the passes of windows of 85, so that share is
# a window's and rises with its length. A pass that takes the tail
# costs 10.5 ms more than the whole buffer at the same load and 17 more
# than a head that held its rows (one layer alone, forward and backward:
# 84.0, 73.6 and 66.9 ms at 34,078 rows). A head of 2.5x costs 13.9 ms
# of 698 in every step, 1.7 a layer pass: it pays from a tenth of the
# passes drawing between 2x and 2.5x. On shared seeds it read 0.9-1.8%
# under the head of 2x in windows of 33 launches and -0.4%, +0.6% in
# windows of 85. So past an eighth held the head stops at half the
# buffer, which is 2x at a quarter: from a half held on it is the whole
# buffer.
HEAD_FACTOR = 4
# the least multiple, which a share past a quarter held falls back on
# (three eighths: 2x is 3/4 of the buffer). No cell holds such a share:
# that band is run by the CPU tests alone and its 2 is not measured
HEAD_FLOOR = 2
# the head is rounded up to this many rows: a whole number of the tiles
# the gathers and ``ragged_dot`` work in
ROW_TILE = 512


def head_rows(tokens: int, cfg: TopKMoEConfig) -> int:
    """Rows of the sorted buffer's head for ``tokens`` tokens, from the
    share of the experts held: ``HEAD_FACTOR`` times the rows they
    expect, but at most half the buffer and never under ``HEAD_FLOOR``
    times those rows, in whole tiles; ``tokens * top_k`` (no tail) where
    that share is a half or more, or the buffer under a tile."""
    rows = tokens * cfg.top_k
    expected = -(-rows * cfg.held // cfg.n_experts)
    head = max(
        HEAD_FLOOR * expected, min(HEAD_FACTOR * expected, rows // 2)
    )
    return min(rows, -(-head // ROW_TILE) * ROW_TILE)


def split_buffer(index, n_head: int):
    """``sort_by_expert``'s arrays cut into rows [0, n_head) and the
    rest, each part indexed from its own row 0: a group that straddles
    the cut has rows in both, in the order it had; an assignment whose
    row is in the other part has none in this one."""
    row_token, row_slot, row_valid, slot_row, group_sizes = index
    ends = jnp.cumsum(group_sizes)
    head_sizes = jnp.minimum(ends, n_head) - jnp.minimum(
        ends - group_sizes, n_head
    )

    def part(lo, hi, sizes):
        mine = (slot_row >= lo) & (slot_row < hi)
        return (
            row_token[lo:hi], row_slot[lo:hi], row_valid[lo:hi],
            jnp.where(mine, slot_row - lo, hi - lo), sizes,
        )

    return (
        part(0, n_head, head_sizes),
        part(n_head, row_token.shape[0], group_sizes - head_sizes),
    )


def _buffer_part(x, top_w, weights, part):
    """What the held experts add for the assignments with a row in
    ``part`` (index arrays as ``sort_by_expert`` returns them) [T, d]:
    dispatch, the three grouped products, weighting, combine, all in
    ``x.dtype``, which is the ``weights``' too."""
    row_token, row_slot, row_valid, slot_row, group_sizes = part
    w_gate, w_up, w_down = weights
    with jax.named_scope("lm_moe_route"):
        row_w = top_w[row_token, row_slot]
        xs = _dispatch(x, row_token, slot_row)
    with jax.named_scope("lm_moe_experts"):
        gm = functools.partial(grouped_matmul, group_sizes=group_sizes)
        mid = jax.nn.silu(gm(xs, w_gate)) * gm(xs, w_up)
        ys = gm(mid.astype(x.dtype), w_down)
    with jax.named_scope("lm_moe_route"):
        # rows past the last assignment hold whatever the product left
        ys = jnp.where(row_valid[:, None], ys * row_w[:, None], 0.0)
        return _combine(ys.astype(x.dtype), row_token, slot_row)


# The tail runs in pieces of at most this many times the head's rows,
# one after the other (``lax.fori_loop``): the compiler plans a
# conditional's branch whether or not a step takes it, and a tail of 9
# heads (8 of 320 experts held: 58,880 rows of 4,096) planned 4.9 GB
# that no step but the rare one touches. A tail of up to 4 heads (8 of
# 128 held: 3) is one piece, the program it was before there were pieces.
TAIL_PIECE_HEADS = 4


def _tail_pieces(n_head: int, n_tail: int):
    """``(pieces, rows of each)`` of a tail of ``n_tail`` rows behind a
    head of ``n_head``: equal pieces of whole tiles."""
    pieces = -(-n_tail // (TAIL_PIECE_HEADS * n_head))
    if pieces == 1:
        return 1, n_tail
    return pieces, -(-n_tail // (pieces * ROW_TILE)) * ROW_TILE


def _over_the_tail(step, carry, head, tail):
    """``carry = step(carry, part)`` for every piece of ``tail`` in turn,
    each piece a part of its own as ``split_buffer`` cuts one (``head``
    only says how large a piece may be)."""
    row_token, row_slot, row_valid, slot_row, group_sizes = tail
    n_tail = row_token.shape[0]
    pieces, rows = _tail_pieces(head[0].shape[0], n_tail)
    if pieces == 1:
        return step(carry, tail)
    pad = lambda t: jnp.pad(t, (0, pieces * rows - n_tail))  # noqa: E731
    row_token, row_slot, row_valid = map(pad, (row_token, row_slot, row_valid))
    ends = jnp.cumsum(group_sizes)

    def piece(i, carry):
        lo = i * rows
        hi = jnp.minimum(lo + rows, n_tail)
        cut = lambda t: jax.lax.dynamic_slice(t, (lo,), (rows,))  # noqa: E731
        mine = (slot_row >= lo) & (slot_row < hi)
        return step(carry, (
            cut(row_token), cut(row_slot), cut(row_valid),
            jnp.where(mine, slot_row - lo, rows),
            jnp.clip(ends, lo, hi) - jnp.clip(ends - group_sizes, lo, hi),
        ))

    return jax.lax.fori_loop(0, pieces, piece, carry)


def _add_tail(y, x, top_w, weights, head, tail, need_tail):
    """``y`` plus the tail's ``_buffer_part``, piece by piece, where
    ``need_tail``; the other branch hands ``y`` back."""
    return jax.lax.cond(
        need_tail,
        lambda y: _over_the_tail(
            lambda y, part: y + _buffer_part(x, top_w, weights, part),
            y, head, tail,
        ),
        lambda y: y,
        y,
    )


@jax.custom_vjp
def _head_and_tail(x, top_w, weights, head, tail, need_tail):
    """``_buffer_part`` of the head, plus that of the tail where
    ``need_tail``."""
    y = _buffer_part(x, top_w, weights, head)
    return _add_tail(y, x, top_w, weights, head, tail, need_tail)


def _head_and_tail_fwd(x, top_w, weights, head, tail, need_tail):
    y, head_vjp = jax.vjp(
        lambda *a: _buffer_part(*a, head), x, top_w, weights
    )
    # of the tail only its inputs: the backward pass computes it again
    return _add_tail(y, x, top_w, weights, head, tail, need_tail), (
        head_vjp, x, top_w, weights, head, tail, need_tail
    )


def _head_and_tail_bwd(res, g):
    head_vjp, x, top_w, weights, head, tail, need_tail = res

    def add_a_piece(grads, part):
        part_vjp = jax.vjp(
            lambda *a: _buffer_part(*a, part), x, top_w, weights
        )[1]
        return jax.tree.map(jnp.add, grads, part_vjp(g))

    def add_the_tails(grads):
        return _over_the_tail(add_a_piece, grads, head, tail)

    grads = jax.lax.cond(
        need_tail, add_the_tails, lambda grads: grads, head_vjp(g)
    )
    return (*grads, None, None, None)


_head_and_tail.defvjp(_head_and_tail_fwd, _head_and_tail_bwd)


def topk_moe_ffn(lp, h2, cfg: TopKMoEConfig, dtype):
    """The dropless top-k layer on ``h2`` [..., d] (the normed input):
    this program's experts' part plus the shared expert, in ``dtype``,
    and stats: ``expert_rows`` [held] int32, ``buffer_passes`` [2] int32
    (1 for the head of the sorted buffer, and 1 or 0 for its tail: did
    this pass need it), ``top_e`` [T, k] int32 (the experts each token
    chose, of all ``n_experts``), and at every ``T // PROBE_TOKENS``-th
    token ``probe_x`` (the router's input as it read it), ``probe_e``
    and ``probe_w`` (its choices and weights); with ``expert_bias`` also
    ``expert_load`` [n_experts] int32 (``EXPERT_LOAD``).

    Named scopes: ``lm_moe_route`` (router, top-k, sort, gather and
    combine), ``lm_moe_experts`` (the grouped products), ``lm_moe_shared``.
    """
    shape = h2.shape
    x = h2.reshape(-1, shape[-1]).astype(dtype)
    with jax.named_scope("lm_moe_route"):
        # a layer without a bias calls ``route_topk(h, router, cfg)``:
        # the planted router faults of chipbench/tests/test_lm_cell.py
        # replace it with functions of those three arguments
        bias = (lp["router_bias"],) if cfg.expert_bias else ()
        top_w, top_e = route_topk(
            x.astype(jnp.float32), lp["router"], cfg, *bias
        )
        index = sort_by_expert(top_e, cfg)
    with jax.named_scope("lm_moe_experts"):
        # cast once, outside the conditional: the parts hand their
        # gradients on in ``dtype``, as the grouped product leaves them;
        # in f32 they would be written out before the conditional (8 ms
        # a step of the cell, PERF.md PR 30)
        weights = tuple(
            lp[name].astype(dtype) for name in ("we_gate", "we_up", "we_down")
        )
    group_sizes = index[-1]
    n_head = head_rows(x.shape[0], cfg)
    if n_head == x.shape[0] * cfg.top_k:  # the whole buffer: no conditional
        need_tail = jnp.zeros((), bool)
        y = _buffer_part(x, top_w, weights, index)
    else:
        need_tail = jnp.sum(group_sizes) > n_head
        y = _head_and_tail(
            x, top_w, weights, *split_buffer(index, n_head), need_tail
        )
    if cfg.n_shared:
        with jax.named_scope("lm_moe_shared"):
            y = y + swiglu(
                x, lp["ws_gate"].astype(dtype), lp["ws_up"].astype(dtype),
                lp["ws_down"].astype(dtype),
            )
    stride = max(1, x.shape[0] // PROBE_TOKENS)
    stats = {
        "expert_rows": group_sizes, "top_e": top_e,
        "buffer_passes": jnp.stack(
            [jnp.ones((), jnp.int32), need_tail.astype(jnp.int32)]
        ),
        "probe_x": jax.lax.stop_gradient(x[::stride]),
        "probe_e": top_e[::stride],
        "probe_w": jax.lax.stop_gradient(top_w[::stride]),
    }
    if cfg.expert_bias:
        stats[EXPERT_LOAD] = expert_load(top_e, cfg)
    return y.reshape(shape), stats


def expert_load(top_e, cfg: TopKMoEConfig):
    """[n_experts] int32: the assignments ``top_e`` [T, k] of each
    expert, counted by comparison (see ``sort_by_expert``)."""
    return jnp.sum(
        top_e.reshape(-1)[:, None]
        == jnp.arange(cfg.n_experts, dtype=top_e.dtype), axis=0,
    ).astype(jnp.int32)


# the step of ``update_expert_bias``: DeepSeek-V3's rate; no published
# configuration of a model with a selection bias states one
EXPERT_BIAS_RATE = 0.001


def update_expert_bias(bias, load):
    """The auxiliary-loss-free balancing step (DeepSeek-V3): every
    expert's bias moves by ``EXPERT_BIAS_RATE`` towards the mean load,
    ``b_e += rate * sign(mean(load) - load_e)`` over all the router's
    columns; an expert at the mean stays where it is."""
    load = load.astype(jnp.float32)
    return bias + EXPERT_BIAS_RATE * jnp.sign(
        jnp.mean(load, axis=-1, keepdims=True) - load
    )

"""Mixture-of-experts FFN with expert parallelism (TPU-native).

Beyond-parity extension rounding out the parallelism modes: dp (data
axis), table/model parallel (server axis), sp (ring + all-to-all
attention) — and here ep: experts sharded over a mesh axis, tokens
routed to them with two ``all_to_all`` collectives (the standard
Switch/GShard dispatch, jax-native).

Top-1 (switch) routing with a per-token-shard capacity: each shard of
tokens computes router gates locally, builds a [tokens, E, C] dispatch
one-hot (C = capacity per expert per shard), and einsum-dispatches its
tokens to expert buffers; an all_to_all re-shards the EXPERT axis so
every device holds the full token buffers of its E/n local experts, the
2-layer FFN runs as dense [E/n, n*C, d] batched matmuls (MXU-shaped),
and the inverse all_to_all + combine einsum route outputs back. Dropped
tokens (over capacity) pass through on the residual path, as in Switch.
"""

from __future__ import annotations

import functools
from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np

from jax import shard_map
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

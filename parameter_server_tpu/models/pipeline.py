"""Pipeline parallelism: stage-sharded layers, microbatched fill-drain.

The remaining parallelism mode (pp) beside dp / table-model / sp / ep:
a deep stack of identical blocks is sharded over a mesh axis — device d
holds a contiguous BLOCK of k = n_stages/n stages (k = 1 being one
stage per device) — and microbatches stream through the pipeline with
activations hopping device-to-device over ``ppermute`` (GPipe
fill-drain schedule: M microbatches finish in M + n - 1 ticks, every
tick running all DEVICES in parallel on different microbatches, each
chaining its local stage block).

Everything is a single jitted program: the schedule is a ``lax.scan``
over ticks, stage selection is mask arithmetic (no data-dependent
control flow), and autodiff through the scan + ppermute gives exact
pipeline-parallel gradients.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P


@functools.partial(jax.jit, static_argnames=("stage_fn", "mesh", "axis"))
def pipeline_apply(
    stage_fn,
    stage_params,
    x: jax.Array,
    *,
    mesh: Mesh,
    axis: str = "data",
):
    """Run ``x`` through the pipeline stages sharded over ``axis``.

    ``stage_params``: pytree whose leaves have leading dim n_stages (one
    slice per stage), sharded over ``axis``; n_stages may be any MULTIPLE
    of the axis size — device d holds the contiguous block of k =
    n_stages/n stages starting at d*k and chains it per tick. ``x``:
    [M, mb, ...] microbatches, replicated. ``stage_fn(params_slice,
    x_mb) -> y_mb`` applies one stage. Returns [M, mb, ...] outputs,
    replicated.
    """
    n = mesh.shape[axis]
    n_stages = jax.tree.leaves(stage_params)[0].shape[0]
    if n_stages == 0 or n_stages % n:
        raise ValueError(
            f"stage count {n_stages} must be a MULTIPLE of mesh axis "
            f"{axis}={n} (each device holds one contiguous stage block)"
        )
    k = n_stages // n  # stages chained locally per device per tick

    def local(params, x):
        # params leaves arrive as [k, ...] (this device's stage block);
        # a tick runs the whole block in sequence — same fill-drain
        # bubble as one-stage-per-device (the (n-1)-tick ramp just costs
        # k stage-times per tick), so deep stacks need no extra devices
        m = x.shape[0]
        stage = jax.lax.axis_index(axis)
        is_first = stage == 0
        is_last = stage == n - 1
        ticks = m + n - 1

        def tick(carry, t):
            held, out = carry
            # stage 0 ingests microbatch t (while valid); others use the
            # activation handed over from the previous tick's ppermute
            feed = x[jnp.minimum(t, m - 1)]
            y = jnp.where(is_first, feed, held)
            for j in range(k):
                y = stage_fn(jax.tree.map(lambda l: l[j], params), y)
            # the last stage completed microbatch t - (n-1) this tick
            done_idx = jnp.maximum(t - (n - 1), 0)
            valid = is_last & (t - (n - 1) >= 0)
            prev = jax.lax.dynamic_index_in_dim(out, done_idx, keepdims=False)
            out = jax.lax.dynamic_update_index_in_dim(
                out, jnp.where(valid, y, prev), done_idx, axis=0
            )
            # hand activations forward around the ring (stage s -> s+1);
            # the wrap-around into stage 0 is ignored (it re-feeds from x)
            held = jax.lax.ppermute(
                y, axis, [(i, (i + 1) % n) for i in range(n)]
            )
            return (held, out), None

        held0 = jnp.zeros_like(x[0])
        out0 = jnp.zeros_like(x)
        (_, out), _ = jax.lax.scan(
            tick, (held0, out0), jnp.arange(ticks), length=ticks
        )
        # only the last stage holds real outputs: share them with all
        return jax.lax.psum(out, axis) / 1.0  # replicate via sum (others 0)

    return shard_map(
        local,
        mesh=mesh,
        in_specs=(P(axis), P()),
        out_specs=P(),
        check_vma=False,
    )(stage_params, x)


def sequential_apply(stage_fn, stage_params, x: jax.Array):
    """Dense reference: apply the n stages in order to every microbatch."""
    n = jax.tree.leaves(stage_params)[0].shape[0]

    def one(mb):
        y = mb
        for s in range(n):
            p = jax.tree.map(lambda l: l[s], stage_params)
            y = stage_fn(p, y)
        return y

    return jax.vmap(one)(x)

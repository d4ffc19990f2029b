"""Row write-back of the sparse-touched update (``update='sparse'``).

The one scatter both copies of the gather→apply→scatter formulation
use (``updaters.apply_state_rows``'s generic body and
``ops/ftrl_sparse.ftrl_sparse_rows_ref``), and the index vector it is
given. What the index vector promises is what the scatter costs: on a
TPU v5 lite, 639,488 rows into a 2^30-slot table take 58 ms with
nothing declared and 16.5 ms with ``indices_are_sorted`` (PERF.md §6,
PR 27), because XLA may then walk the table once instead of one
read-modify-write per index. It does not check the promise, so it is
made only where the caller has established it.
"""
# bit-identical: this module is under the replay bit-identity contract (pslint determinism pass)

from __future__ import annotations

import jax.numpy as jnp


def write_index(rel, ok, shard: int):
    """uint32 scatter indices for the rows ``rel`` of a ``shard``-slot
    table: ``rel`` where ``ok``, else ``shard + position``.

    Entries that are not ``ok`` (prep's padding tail, ids another server
    shard owns) are routed past the end in UNSIGNED index space and
    dropped by the scatter (``mode='drop'``). A signed -1 would WRAP to
    the shard's real last row and scatter-set a stale value over its
    genuine update (observed: the last slot of every shard losing its
    step); uint32 never wraps and still holds ``2^31 + U`` for the
    maximal 2^31-row shard. Each dropped entry gets an index of its
    own, so the vector is

    - duplicate-free whenever ``rel`` is among its ``ok`` entries (host
      prep dedups at slot level), dropped entries included;
    - strictly increasing whenever the ``ok`` entries of ``rel`` ascend
      and none follows a dropped one — ``localize`` of prep's
      ``uslots`` (``np.unique`` output, or its delta decode on the
      wire, padded at the tail with a sentinel no shard owns) on ONE
      server shard. On a later shard of a multi-server mesh the ids a
      lower shard owns come first and are dropped first, and the vector
      is only duplicate-free.
    """
    pos = jnp.arange(rel.shape[0], dtype=jnp.uint32)
    return jnp.where(ok, rel.astype(jnp.uint32), jnp.uint32(shard) + pos)


def write_rows(full, idx, new, *, rows_ascend: bool):
    """``full`` with ``new`` written at :func:`write_index`'s ``idx``,
    out-of-range entries dropped. ``rows_ascend`` declares ``idx``
    strictly increasing (``indices_are_sorted``): pass True only where
    that is established (see :func:`write_index`), a wrong promise is
    undefined behaviour on the device and invisible on the CPU. The
    result is the same bits either way."""
    return full.at[idx].set(
        new.astype(full.dtype), mode="drop",
        indices_are_sorted=rows_ascend, unique_indices=True,
    )

"""Sharded key-value pull/push — the device data plane.

This is where the reference's ``KVVector::Push/Pull`` message traffic
(kv_vector.h + van.cc sends) becomes XLA collectives over the mesh:

- **pull**: every (data, server) device gathers the slots it owns for the
  requested indices, then a ``psum`` over the *server* axis assembles full
  rows (each slot is owned by exactly one server shard, so summation is
  assembly). Cross-chip traffic rides ICI, sized ``n_idx × k`` — the same
  payload the reference puts on the wire, minus serialization.
- **push**: per-worker values are first combined across the *data* axis
  (``psum`` — gradient aggregation, the reference's server-side merge of
  worker messages), then every server shard scatter-adds the entries whose
  slot falls in its key range. Duplicate indices within a request
  scatter-add correctly (segment aggregation).

All shapes are static: indices are int32 slot ids produced by the host-side
localizer/directory; out-of-range or padding entries use slot id ``P``
(one-past-the-end sentinel) and are dropped by range masking.

**Donation (the zero-copy data plane).** ``push``/``push_pull`` come in
two flavors per update: the plain entry points leave the input table
alive (XLA materializes a fresh ``[P, k]`` output — a full HBM table
copy per push), and the ``*_donated`` entry points alias input→output
(``donate_argnums``) so the scatter-add happens in place. Callers that
OWN their table (KVVector/KVMap channel tables, staged push buffers)
use the donated path; anyone still holding the input array afterwards
gets jax's read-after-donate ``RuntimeError`` rather than silent
staleness. Checkpoint/replica paths must therefore copy BEFORE the
push dispatches — see doc/PERFORMANCE.md "Donation rules".

``push_pull`` fuses the reference's server-side "aggregate then reply"
round trip (push message + pull reply) into ONE dispatched program:
scatter-add, then gather from the freshly-updated shard, bit-identical
to ``push`` followed by ``pull``.
"""

from __future__ import annotations

import functools
import time

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from ..parallel.mesh import DATA_AXIS, SERVER_AXIS
from ..parallel.partition import BATCH_SPEC, REPLICATED_SPEC, TABLE_SPEC
from ..telemetry import device as _device
from ..telemetry.instruments import cached_kvops_instruments as _tel


def index_spec(batch_sharded: bool) -> P:
    """The slot-index spec: per-worker key sets ride the data axis,
    replicated otherwise (spec constants owned by parallel/partition.py
    — the declarative home of every layout here)."""
    return BATCH_SPEC if batch_sharded else REPLICATED_SPEC


def localize(idx: jnp.ndarray, shard: int):
    """Shard-relative index + ownership mask for this server's key range.

    Computes ``lo = axis_index(server) * shard`` internally, so it must be
    called inside a ``shard_map`` over SERVER_AXIS. int32-safe up to
    ``shard == 2**31``: a single-server 2^31-slot table's ids occupy the
    whole non-negative int32 lattice, but the Python constant ``2**31``
    overflows jnp's operand parsing (jnp ops are jitted; an int operand
    above int32max raises OverflowError before tracing), so the one-shard
    case short-circuits to ``lo = 0`` and masks sentinels by sign alone —
    any padding/foreign id is negative there (see ``slot_sentinel``).
    """
    if shard > (1 << 31):
        raise ValueError(
            f"shard of {shard} slots exceeds int32 slot ids; "
            "spread the table over more server shards"
        )
    if shard == (1 << 31):
        ok = idx >= 0
        return jnp.clip(idx, 0, (1 << 31) - 1), ok
    lo = jax.lax.axis_index(SERVER_AXIS) * shard
    rel = idx - lo
    ok = (rel >= 0) & (rel < shard)
    return jnp.clip(rel, 0, shard - 1), ok


def slot_sentinel(num_slots: int) -> int:
    """Padding slot id for host-side preps: one-past-the-end when that
    fits int32 (the documented sentinel), else -1 — a 2^31-slot table's
    ``num_slots`` overflows np.int32, and any un-owned id works because
    every shard's ownership mask (``localize``) drops it."""
    return num_slots if num_slots < (1 << 31) else -1


def valid_slots(slots: jnp.ndarray, num_slots: int) -> jnp.ndarray:
    """Mask of non-sentinel slot ids, int32-safe at ``num_slots == 2**31``
    (where the sentinel is -1 and the comparison against ``num_slots``
    would overflow operand parsing)."""
    if num_slots >= (1 << 31):
        return slots >= 0
    return slots < num_slots


def _pull_impl(table, idx, *, mesh: Mesh, batch_sharded: bool = True):
    p_total, _ = table.shape
    n_server = mesh.shape[SERVER_AXIS]
    shard = p_total // n_server
    idx_spec = index_spec(batch_sharded)

    def local(tbl, ix):
        rel, ok = localize(ix, shard)
        vals = jnp.where(ok[:, None], tbl[rel], 0)
        return jax.lax.psum(vals, SERVER_AXIS)

    return shard_map(
        local,
        mesh=mesh,
        in_specs=(TABLE_SPEC, idx_spec),
        out_specs=idx_spec,
    )(table, idx)


# no-donate: pull reads the table; the store keeps serving it afterwards.
# Every public entry point below is wrapped into the device inventory
# (telemetry/device.py): each lower().compile() lands its cost/memory
# analysis in ``device.snapshot()``, recompiles are counted per
# name, and the donated paths' aliasing is runtime-verified.
pull = _device.instrument(
    "kv_pull",
    # no-donate: pull reads the table; the store keeps serving it
    functools.partial(jax.jit, static_argnames=("mesh", "batch_sharded"))(
        _pull_impl
    ),
    static_argnames=("mesh", "batch_sharded"),
)
pull.__doc__ = """Gather rows ``table[idx]`` from a server-sharded table.

table: [P, k] sharded P(SERVER, None); idx: [n] int32, sharded over DATA
if batch_sharded (each worker pulls its own key set — the common case)
else replicated. Returns [n, k] with the same batch sharding.
"""


def _push_local_fn(shard, n_data, average, combined):
    """Per-shard push body shared by push and push_pull (bit-identical
    aggregation between the plain and fused dispatches)."""

    def local(tbl, ix, v):
        if combined:
            ix = jax.lax.all_gather(ix, DATA_AXIS, tiled=True)
            v = jax.lax.all_gather(v, DATA_AXIS, tiled=True)
        if average and combined:
            # average only when contributions were actually combined
            v = v / n_data
        rel, ok = localize(ix, shard)
        v = jnp.where(ok[:, None], v, 0)
        return tbl.at[rel].add(v, mode="drop")

    return local


def _push_impl(
    table,
    idx,
    vals,
    *,
    mesh: Mesh,
    batch_sharded: bool = True,
    average: bool = False,
    combine_data: bool = True,
):
    p_total, k = table.shape
    n_server = mesh.shape[SERVER_AXIS]
    n_data = mesh.shape[DATA_AXIS]
    shard = p_total // n_server
    idx_spec = index_spec(batch_sharded)
    combined = batch_sharded and combine_data and n_data > 1
    local = _push_local_fn(shard, n_data, average, combined)

    return shard_map(
        local,
        mesh=mesh,
        in_specs=(TABLE_SPEC, idx_spec, idx_spec),
        out_specs=TABLE_SPEC,
    )(table, idx, vals)


_PUSH_STATICS = ("mesh", "batch_sharded", "average", "combine_data")

# no-donate: the copying path — for callers whose input table must
# survive the push (checkpoint staging, A/B benches); owners use
# push_donated
push = _device.instrument(
    "kv_push",
    # no-donate: the copying path — for callers whose input table must
    # survive the push (checkpoint staging, A/B benches)
    functools.partial(jax.jit, static_argnames=_PUSH_STATICS)(_push_impl),
    static_argnames=_PUSH_STATICS,
)
push.__doc__ = """Scatter-add ``vals`` at ``idx`` into the server-sharded table.

table: [P, k] sharded P(SERVER, None); idx: [n] int32; vals: [n, k].
With batch_sharded, each worker contributes its own (idx, vals): entries
are all-gathered over the DATA axis so every server shard sees every
contribution (the reference's sliced push messages to each server).
``average`` divides by the worker count (scaled gradient aggregation).

This entry point COPIES: XLA materializes a fresh table output. Callers
that own their table should use :func:`push_donated` (in-place).
"""

_push_donated_jit = _device.instrument(
    "kv_push_donated",
    functools.partial(
        jax.jit, static_argnames=_PUSH_STATICS, donate_argnums=(0,)
    )(_push_impl),
    static_argnames=_PUSH_STATICS,
    donate_argnums=(0,),
)


def push_donated(table, idx, vals, **kw):
    """In-place :func:`push`: the input table buffer is DONATED to the
    update (XLA aliases input→output; no ``[P, k]`` copy). The caller
    must own ``table`` exclusively — any other live reference to it
    raises on next use (read-after-donate). Same math as ``push``."""
    tel = _tel()
    if tel is not None:
        tel["donated_pushes"].inc()
    return _push_donated_jit(table, idx, vals, **kw)


def _push_pull_impl(
    table,
    idx,
    vals,
    pull_idx,
    *,
    mesh: Mesh,
    batch_sharded: bool = True,
    average: bool = False,
    combine_data: bool = True,
):
    p_total, k = table.shape
    n_server = mesh.shape[SERVER_AXIS]
    n_data = mesh.shape[DATA_AXIS]
    shard = p_total // n_server
    idx_spec = index_spec(batch_sharded)
    combined = batch_sharded and combine_data and n_data > 1
    push_local = _push_local_fn(shard, n_data, average, combined)

    def local(tbl, ix, v, pix):
        new = push_local(tbl, ix, v)
        rel, ok = localize(pix, shard)
        out = jnp.where(ok[:, None], new[rel], 0)
        return new, jax.lax.psum(out, SERVER_AXIS)

    return shard_map(
        local,
        mesh=mesh,
        in_specs=(TABLE_SPEC, idx_spec, idx_spec, idx_spec),
        out_specs=(TABLE_SPEC, idx_spec),
    )(table, idx, vals, pull_idx)


# no-donate: the copying fused path (A/B benches, shared-table callers)
_push_pull_jit = _device.instrument(
    "kv_push_pull",
    # no-donate: the copying fused path (A/B benches, shared tables)
    functools.partial(
        jax.jit, static_argnames=_PUSH_STATICS
    )(_push_pull_impl),
    static_argnames=_PUSH_STATICS,
)
_push_pull_donated_jit = _device.instrument(
    "kv_push_pull_donated",
    functools.partial(
        jax.jit, static_argnames=_PUSH_STATICS, donate_argnums=(0,)
    )(_push_pull_impl),
    static_argnames=_PUSH_STATICS,
    donate_argnums=(0,),
)


def _dispatch_fused(jit_fn, table, idx, vals, pull_idx, kw):
    if pull_idx is None:
        pull_idx = idx
    tel = _tel()
    if tel is None:
        return jit_fn(table, idx, vals, pull_idx, **kw)
    t0 = time.perf_counter()
    out = jit_fn(table, idx, vals, pull_idx, **kw)
    # dispatch wall time (host side), not device completion — the win
    # this kernel buys is one launch instead of two
    tel["fused_dispatch"].observe(time.perf_counter() - t0)
    return out


def push_pull(table, idx, vals, pull_idx=None, **kw):
    """Fused scatter-add + gather in ONE dispatched program: returns
    ``(new_table, pulled)`` where ``pulled = pull(push(table, idx, vals),
    pull_idx)`` bit-for-bit. ``pull_idx`` defaults to ``idx`` (the
    common push→pull-same-keys round trip — the reference's server-side
    "aggregate then reply" in one launch). This entry point copies the
    table; owners use :func:`push_pull_donated`."""
    return _dispatch_fused(_push_pull_jit, table, idx, vals, pull_idx, kw)


def push_pull_donated(table, idx, vals, pull_idx=None, **kw):
    """:func:`push_pull` with the table donated (in-place update, no
    ``[P, k]`` copy). Caller must own ``table`` exclusively."""
    tel = _tel()
    if tel is not None:
        tel["donated_pushes"].inc()
    return _dispatch_fused(
        _push_pull_donated_jit, table, idx, vals, pull_idx, kw
    )


def scatter_grad_dense(
    idx: jax.Array, vals: jax.Array, p_total: int, k: int
) -> jax.Array:
    """Densify a sparse push into a [P, k] gradient table (single-shard
    helper used by fused learner steps; padding slot P drops)."""
    g = jnp.zeros((p_total, k), vals.dtype)
    return g.at[idx].add(vals, mode="drop")

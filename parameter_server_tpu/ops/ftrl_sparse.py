"""Fused sparse FTRL-proximal update — Pallas TPU gather→update→scatter.

The big-table row path (``update='sparse'``, updaters.apply_state_rows)
runs as four separate XLA ops — gather z, gather √n, scatter z',
scatter √n' — with intermediate row vectors materialized between them.
What each costs is per index, not per byte (TPU v5 lite, 639,488 rows
of a 2^30-slot table, PERF.md §6, PR 27): a gather 8 ms, a scatter
58 ms while XLA knows nothing of the index vector and 15–17 ms once it
is told the indices ascend — which host prep guarantees on one server
shard and ``ops/rows.py`` declares. This kernel is the IO-aware
formulation (the FlashAttention lesson, arXiv:2205.14135): ONE pass
over exactly the touched rows —

- the deduped slot ids are reduced to unique 128-lane TABLE ROWS and
  scalar-prefetched (``PrefetchScalarGridSpec``), so the kernel can
  issue row DMAs before any tensor work runs;
- each grid block DMAs its rows HBM→VMEM double-buffered (block b+1's
  fetches are in flight while block b computes — the grid is
  sequential, scratch persists across steps);
- the FTRL-proximal step (``_ftrl_math`` from ops/ftrl.py — the single
  copy of the math) runs vectorized in VMEM, membership derived per
  lane as ``g != 0`` (the unquantized-push contract);
- updated rows DMA straight back to the SAME HBM buffers
  (``input_output_aliases`` — no fresh table copy, the constraint that
  lets one chip hold a 2^30-slot table), write-back overlapping the
  next block's compute.

Gradients arrive as a per-unique-row dense [U, 128] scatter (built
in-program from the deduped ``g_u`` vector): prep's slot-unique
contract makes every genuine (row, lane) target unique, padding and
non-owned entries carry g = 0 and merge into real rows as pass-through
lanes, so the kernel never needs a mask operand or a sentinel row.

The kernel covers f32 ``sqrt_n`` only. A bf16 ``sqrt_n`` table
(``SGDConfig.ftrl_state_dtype``) cannot be moved one table row per
DMA: Mosaic tiles a bf16 HBM ref (8,128)(2,1) and refuses the row
slice ("Slice shape along dimension 0 must be aligned to tiling (8),
but is 1", TPU v5 lite, jax 0.9.0), and a uint32 view of the same ref
tiles (4,128) with the same refusal — its DMA granule is 8 table
rows. Such tables take the XLA rows path until a kernel with an
8-row granule exists (ROADMAP Speed 2).

``ftrl_sparse_update`` auto-selects: Pallas on TPU backends for
tileable f32 shapes, the XLA rows reference elsewhere (bit-identical
formulation of updaters.apply_state_rows for the FTRL/decay case).
"""
# bit-identical: this module is under the replay bit-identity contract (pslint determinism pass)

from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp

from . import use_pallas
from .ftrl import (
    _LANES,
    _TILE,
    _choose_block_rows,
    _ftrl_math,
    ftrl_update,
)
from .rows import write_index, write_rows

#: update-path names reported by :func:`resolve_update_path` and the
#: ``ps_ftrl_update_path_total`` telemetry counter
PATH_PALLAS_SPARSE = "pallas_sparse"
PATH_PALLAS_DENSE = "pallas_dense"
PATH_XLA_ROWS = "xla_rows"
PATH_REF = "ref"


#: touched-row ids scalar-prefetched per kernel call. The whole vector
#: lands in SMEM (1 MiB a core on TPU v5 lite: a 639,488-row set, the
#: width of a 16,384 x 39-lane minibatch, asked for 2.56 MB and the
#: compiler refused it), so wider row sets run as a scan of calls.
_SMEM_CHUNK_ROWS = 1 << 16


def use_sparse_kernel(p: int, u: int, bf16_n: bool,
                      force_pallas: bool) -> bool:
    """Pure path-selection predicate for the fused sparse kernel
    (testable off device): the kernel runs on TPU backends, for
    (8,128)-tileable tables with f32 ``sqrt_n`` (module docstring: the
    compiler refuses single-row DMAs of a bf16 table), and for row
    counts the (8-sublane) block machinery can tile. Everything else
    falls back to the XLA rows path (:func:`ftrl_sparse_rows_ref`),
    bit-identically. ``force_pallas`` pins the kernel for A/B sweeps
    and interpret tests, but never onto a shape it cannot tile.
    """
    if not force_pallas and not use_pallas():
        return False
    if bf16_n:
        return False
    return p % _TILE == 0 and u >= 8 and u % 8 == 0


def resolve_update_path(update_mode: str, *, on_tpu: bool, shard: int,
                        u: int, bf16_n: bool, has_seed: bool) -> str:
    """Which FTRL update path a train step with these statics will
    trace — the host-side twin of the in-jit dispatch (the decision is
    static, so the host can name it without touching the device).
    Feeds the ``ps_ftrl_update_path_total`` counter:

    - ``pallas_sparse`` — update='sparse' through the fused kernel;
    - ``xla_rows``      — update='sparse' through the XLA
      gather→apply→scatter rows path;
    - ``pallas_dense``  — dense whole-shard sweep, Pallas kernel;
    - ``ref``           — dense sweep, jnp/XLA reference path.

    ``on_tpu`` is an explicit parameter (not re-probed) so the
    resolution is a pure function of its arguments — callable from
    tests and dashboards describing a remote device's dispatch.
    ``force_pallas=True`` below is how the backend gate is replaced by
    the parameter while every SHAPE gate still applies.
    """
    from .ftrl import _TILE, xla_min_slots

    if update_mode == "sparse":
        if on_tpu and use_sparse_kernel(shard, u, bf16_n, True):
            return PATH_PALLAS_SPARSE
        return PATH_XLA_ROWS
    # the dense resolution mirrors ops/ftrl.use_ref_path with the
    # backend probe swapped for the parameter (use_ref_path's
    # force_pallas skips its xla_min_slots gate, so it cannot be
    # reused here verbatim)
    if (
        not on_tpu
        or shard % _TILE != 0
        or (bf16_n and not has_seed)
        or shard >= xla_min_slots()
    ):
        return PATH_REF
    return PATH_PALLAS_DENSE


def ftrl_sparse_rows_ref(z, sqrt_n, rel, ok, g_u, *, alpha, beta, l1,
                         l2, seed=None, rows_ascend=False):
    """XLA rows reference: the exact gather→apply→scatter formulation
    ``updaters.apply_state_rows`` runs for the FTRL/decay case, inlined
    here so kernel tests can call it without an
    updater object. Gathers the ``rel`` rows, applies the JITTED
    :func:`ops.ftrl.ftrl_update` exactly as ``FTRLUpdater.apply`` does
    (same ``_ftrl_math``, same position-hash bf16 narrow; calling the
    un-jitted reference here instead would diverge in the last bit at
    EAGER call sites — XLA contracts the z-accumulator multiply-add
    under jit), and writes back through the one scatter the two
    formulations share (``ops/rows.py``: non-``ok`` entries past the
    end in UNSIGNED index space and dropped; ``rows_ascend`` is the
    caller's promise that the ``ok`` entries of ``rel`` ascend with
    every non-``ok`` entry behind them)."""
    z_u = z[rel]
    n_u = sqrt_n[rel]
    g = jnp.where(ok, g_u, 0.0)
    z_new, n_new = ftrl_update(
        z_u, n_u, g, None, alpha=alpha, beta=beta, l1=l1, l2=l2,
        seed=seed,
    )
    idx = write_index(rel, ok, z.shape[0])
    return (
        write_rows(z, idx, z_new, rows_ascend=rows_ascend),
        write_rows(sqrt_n, idx, n_new, rows_ascend=rows_ascend),
    )


def _row_gradient(rel, ok, g_u, width: int):
    """Unique-row decomposition of the deduped slot vector (in-program,
    O(U) elementwise/scan work — small next to the row traffic it
    organizes). The ``ok`` subsequence of ``rel`` ascends strictly:
    host prep guarantees it (``prep_batch_shared``'s ``np.unique``, the
    wire's sorted-delta decode) and ``localize`` keeps the order.
    Non-``ok`` entries are clip artifacts and may land OUT of order in
    ``rel`` itself — the ≥2^31-slot sentinel is -1 (``slot_sentinel``),
    so the padding tail clips to rel 0 BELOW the ascending owned ids,
    and on a later shard of a multi-server mesh the ids a lower shard
    owns clip to rel 0 AHEAD of them (``ops/rows.write_index`` is what
    turns the tail into an ascending vector for the XLA rows path; this
    kernel orders rows itself). Every non-``ok`` entry carries g=0
    and merges into whichever row group absorbs it, so each is
    remapped to the running max of the ok rows (``cummax``): the row
    sequence is monotone again and the neighbor-compare dedup can
    never emit a duplicate row — a duplicate would make the later
    block's stale fetch WRITE BACK over the genuine update (a silent
    lost update, caught in review by exactly the -1-tail shape).

    Returns ``(urows [W] int32, nrows [1] int32, g_rows [W,128] f32)``
    with ``W = width >= U``: ``urows[:nrows]`` are the distinct
    128-lane table rows touched (filler 0 past ``nrows`` — fetch-safe,
    never written back) and ``g_rows`` the per-row dense gradient
    (scatter-ADD: genuine (row, lane) targets are unique by the
    slot-unique contract, padding/non-owned entries add 0).
    """
    g = jnp.where(ok, g_u, 0.0).astype(jnp.float32)
    relc = rel.astype(jnp.int32)
    lane = relc % _LANES
    row = jax.lax.cummax(jnp.where(ok, relc // _LANES, 0))
    first = jnp.concatenate(
        [jnp.ones((1,), jnp.int32), (row[1:] != row[:-1]).astype(jnp.int32)]
    )
    inv = jnp.cumsum(first) - 1
    nrows = (inv[-1] + 1).reshape(1)
    urows = jnp.zeros((width,), jnp.int32).at[inv].set(row)
    g_rows = jnp.zeros((width, _LANES), jnp.float32).at[inv, lane].add(g)
    return urows, nrows, g_rows


def _grid_params(interpret: bool):
    """Sequential-grid compiler params: the double-buffer recurrence
    (scratch slots + DMA semaphores carried across grid steps) requires
    'arbitrary' dimension semantics."""
    if interpret:
        return {}
    from jax.experimental.pallas import tpu as pltpu

    return {
        "compiler_params": pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)
        )
    }


def _kernel(urows_ref, nrows_ref, z_hbm, n_hbm, g_ref, z_out, n_out,
            zin, nin, zco, nco, in_sem, out_sem, *, br, alpha, beta, l1,
            l2):
    """Kernel body: double-buffered row-DMA pipeline around one VMEM
    FTRL block. Grid steps run sequentially; scratch slot b%2
    alternates, so block b's fetch was issued at block b-1 and its
    write-back drains under block b+1's compute."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b = pl.program_id(0)
    nb = pl.num_programs(0)
    slot = jax.lax.rem(b, 2)
    nxt = jax.lax.rem(b + 1, 2)

    def dma_pair(method, inbound, s, blk):
        # one (z, n) DMA pair per touched table row; starts and waits
        # are gated by the SAME `gi < nrows` predicate, so their counts
        # match exactly and filler rows past nrows move no bytes
        def body(j, _):
            gi = blk * br + j

            @pl.when(gi < nrows_ref[0])
            def _():
                r = urows_ref[gi]
                if inbound:
                    cz = pltpu.make_async_copy(
                        z_hbm.at[r], zin.at[s, j], in_sem.at[s, 0]
                    )
                    cn = pltpu.make_async_copy(
                        n_hbm.at[r], nin.at[s, j], in_sem.at[s, 1]
                    )
                else:
                    cz = pltpu.make_async_copy(
                        zco.at[s, j], z_out.at[r], out_sem.at[s, 0]
                    )
                    cn = pltpu.make_async_copy(
                        nco.at[s, j], n_out.at[r], out_sem.at[s, 1]
                    )
                getattr(cz, method)()
                getattr(cn, method)()

            return 0

        jax.lax.fori_loop(0, br, body, 0)

    # warm-up: the first block fetches its own rows
    @pl.when(b == 0)
    def _():
        dma_pair("start", True, slot, b)

    dma_pair("wait", True, slot, b)

    # prefetch the NEXT block's rows while this block computes — the
    # double buffer that overlaps fetch with compute
    @pl.when(b + 1 < nb)
    def _():
        dma_pair("start", True, nxt, b + 1)

    # the compute below overwrites compute-out slot b%2; block b-2's
    # write-back DMA reads from it, so drain that first
    @pl.when(b >= 2)
    def _():
        dma_pair("wait", False, slot, b - 2)

    # trailing blocks past nrows (the grid is statically sized from the
    # PADDED unique width; row-dedup shrinks the live prefix) have every
    # DMA predicated off — skip their compute too instead of running
    # the full FTRL step on stale scratch
    @pl.when(b * br < nrows_ref[0])
    def _():
        z = zin[slot]
        n = nin[slot]
        g = g_ref[:]
        z_new, n_new = _ftrl_math(z, n, g, alpha=alpha, beta=beta,
                                  l1=l1, l2=l2)
        # membership per lane: g != 0 (the unquantized-push contract —
        # padding/non-owned lanes carry g = 0, passing through unchanged)
        keep = g != 0
        zco[slot] = jnp.where(keep, z_new, z)
        nco[slot] = jnp.where(keep, n_new, n)

    dma_pair("start", False, slot, b)

    # drain: the final block waits its own write-back and the previous
    # block's still-in-flight one
    @pl.when(b == nb - 1)
    def _():
        dma_pair("wait", False, slot, b)

        @pl.when(b >= 1)
        def _():
            dma_pair("wait", False, nxt, b - 1)


def _sparse_block_rows(u: int, requested: "int | None" = None) -> int:
    """Pallas tile height for the sparse kernel: the requested value
    (arg, else ``PS_FTRL_SPARSE_BLOCK_ROWS``, else 512) through the
    same power-of-two-dividing resolution as the dense kernel. 512
    rows/block keeps the 8 double-buffered [BR, 128] scratch refs
    ~2.5 MB of VMEM while amortizing grid overhead to ~U/512 steps."""
    if requested is None:
        try:
            requested = int(
                os.environ.get("PS_FTRL_SPARSE_BLOCK_ROWS", 512)
            )
        except ValueError:
            requested = 512
    return _choose_block_rows(u, requested)


def _fused_rows_call(z2d, n2d, urows, nrows, g_rows, *, br, alpha, beta,
                     l1, l2, interpret):
    """One kernel launch over ``urows.shape[0]`` candidate rows (at
    most :data:`_SMEM_CHUNK_ROWS`): ``urows``/``nrows`` are
    scalar-prefetched, ``g_rows`` streams through VMEM in ``br``-row
    blocks, and the z/sqrt_n tables stay in HBM and are updated in
    place."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    any_spec = lambda: pl.BlockSpec(memory_space=pl.ANY)  # noqa: E731
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(urows.shape[0] // br,),
        in_specs=[
            any_spec(),
            any_spec(),
            pl.BlockSpec(
                (br, _LANES), lambda i, *_: (i, 0), memory_space=pltpu.VMEM
            ),
        ],
        out_specs=(any_spec(), any_spec()),
        scratch_shapes=[
            pltpu.VMEM((2, br, _LANES), jnp.float32),       # z fetch
            pltpu.VMEM((2, br, _LANES), jnp.float32),       # n fetch
            pltpu.VMEM((2, br, _LANES), jnp.float32),       # z compute
            pltpu.VMEM((2, br, _LANES), jnp.float32),       # n compute
            pltpu.SemaphoreType.DMA((2, 2)),                # fetch sems
            pltpu.SemaphoreType.DMA((2, 2)),                # write sems
        ],
    )
    # z/sqrt_n update IN PLACE: without the alias the call materializes
    # fresh z'/n' buffers next to the live table. Alias indices count
    # the scalar-prefetch operands first. Every touched row is read
    # (fetch) strictly before its write-back is issued, and rows are
    # unique across the grid, so the pipeline never observes its own
    # output.
    return pl.pallas_call(
        functools.partial(
            _kernel, br=br, alpha=alpha, beta=beta, l1=l1, l2=l2
        ),
        grid_spec=grid_spec,
        out_shape=(
            jax.ShapeDtypeStruct(z2d.shape, z2d.dtype),
            jax.ShapeDtypeStruct(n2d.shape, n2d.dtype),
        ),
        input_output_aliases={2: 0, 3: 1},
        interpret=interpret,
        **_grid_params(interpret),
    )(urows, nrows, z2d, n2d, g_rows)


# no-donate: the public z/n entry point is used by parity tests,
# which keep their inputs; the fused train step donates at
# ITS boundary and the kernel aliases in-block via input_output_aliases
# (same rule as ops/ftrl.ftrl_update).
@functools.partial(
    jax.jit,  # no-donate: see above — callers keep their z/n inputs
    static_argnames=("alpha", "beta", "l1", "l2", "force_pallas",
                     "interpret", "block_rows"),
)
def ftrl_sparse_update(
    z: jax.Array,
    sqrt_n: jax.Array,
    rel: jax.Array,
    ok: jax.Array,
    g_u: jax.Array,
    *,
    alpha: float,
    beta: float,
    l1: float,
    l2: float = 0.0,
    seed=None,
    force_pallas: bool = False,
    interpret: bool = False,
    block_rows: "int | None" = None,
):
    """Fused sparse-touched FTRL update over a 1-D slot shard.

    ``rel``/``ok`` are ``localize``'s shard-relative ids + ownership
    mask for the batch's globally-deduped ``uslots`` (strictly
    ascending among ``ok`` entries — localize of a sorted unique vector
    — hence duplicate-free there: the update is nonlinear in the summed
    gradient, so host prep dedups at slot level; the same
    apply_state_rows contract).
    ``g_u`` is the per-unique-slot aggregated gradient. Returns
    ``(z', sqrt_n')`` — bit-identical to
    ``updaters.apply_state_rows(FTRLUpdater(decay), ...)``.

    The Pallas path updates the touched rows IN PLACE
    (``input_output_aliases``; callers whose enclosing jit donates the
    state — the fused production step — get it copy-free, same
    defensive-copy caveat as the dense kernel) and moves ONE HBM round
    trip of 128-lane rows: ~1 KB fetched + ~1 KB written per distinct
    touched row (z + f32 √n) plus the in-program [U, 128] gradient
    scatter — against the XLA rows path's four separate gather/scatter
    dispatches. Row sets wider than :data:`_SMEM_CHUNK_ROWS` run as a
    scan of kernel launches over equal chunks (rows are unique across
    the whole set, so the chunks are independent). ``seed`` only
    reaches the XLA rows path (the stochastic bf16 narrow);
    ``block_rows`` tiles the row axis (default 512, env
    ``PS_FTRL_SPARSE_BLOCK_ROWS`` — baked at first trace like the
    dense kernel's knob).

    Falls back to :func:`ftrl_sparse_rows_ref` off-TPU and for shapes
    the kernel does not cover (``use_sparse_kernel``), so any caller
    can use it unconditionally; the fallback makes no order promise
    about ``rel``.
    """
    p = z.shape[0]
    u = rel.shape[0]
    if z.ndim != 1 or not use_sparse_kernel(
        p, u, sqrt_n.dtype == jnp.bfloat16, force_pallas
    ):
        return ftrl_sparse_rows_ref(
            z, sqrt_n, rel, ok, g_u,
            alpha=alpha, beta=beta, l1=l1, l2=l2, seed=seed,
        )
    shape2d = (p // _LANES, _LANES)
    chunk = min(u, _SMEM_CHUNK_ROWS)
    n_chunks = -(-u // chunk)
    br = _sparse_block_rows(chunk, block_rows)
    # filler rows past ``nrows`` move no bytes, so padding the row set
    # to whole chunks costs only the zeroed tail of g_rows
    urows, nrows, g_rows = _row_gradient(rel, ok, g_u, n_chunks * chunk)
    call = functools.partial(
        _fused_rows_call, br=br, alpha=alpha, beta=beta, l1=l1, l2=l2,
        interpret=interpret,
    )
    tables = (z.reshape(shape2d), sqrt_n.reshape(shape2d))
    if n_chunks == 1:
        z_new, n_new = call(*tables, urows, nrows, g_rows)
    else:
        def body(carry, xs):
            rows_c, start, g_c = xs
            live = jnp.clip(nrows - start, 0, chunk)
            return call(*carry, rows_c, live, g_c), None

        (z_new, n_new), _ = jax.lax.scan(
            body,
            tables,
            (
                urows.reshape(n_chunks, chunk),
                jnp.arange(n_chunks, dtype=jnp.int32) * chunk,
                g_rows.reshape(n_chunks, chunk, _LANES),
            ),
        )
    return z_new.reshape(p), n_new.reshape(p)

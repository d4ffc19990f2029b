"""Flash attention — Pallas TPU kernels (forward + backward).

The long-context compute core: blockwise attention with an online-softmax
accumulator held in VMEM, so the [Sq, Sk] score matrix never touches HBM
(memory O(block) instead of O(S^2)) and every matmul is an MXU-shaped
``dot_general``. This is the per-device building block that
``models.attention`` composes with sequence parallelism: ring attention
calls it once per ICI hop with the visiting K/V chunk's global offset, and
merges chunks with the returned logsumexp.

Layout note (why everything is "transposed"): scores are computed as
``s_t[k, q]`` — K on sublanes, Q on lanes — so the per-row softmax
statistics (max, sum, lse, delta) are naturally ``[1, block_q]`` lane
vectors, which is the layout Mosaic wants for broadcasting against both
the score block and the ``[D, block_q]`` output accumulator. No in-kernel
transposes; the output is materialized as ``[BH, D, Sq]`` and transposed
once by XLA outside the kernel.

Reference parity: the reference has no attention op (linear methods +
CXXNET convnets); this kernel exists for the framework's first-class
long-context requirement. Math follows Dao et al.'s FlashAttention-2
recurrence. The grid is ``(batch*heads, steps)``: a step is one pair of
a q block and a k block that can hold a kept pair, taken from tables the
kernels read by scalar prefetch (``grid_tables``), so a block the mask
drops whole is neither copied into VMEM nor stepped over. The pairs of
one output block are consecutive (k innermost for the forward and dq,
q innermost for dkv) and its accumulators live in VMEM scratch from the
row's first pair to its last.

``flash_attention(q, k, v, ...)`` auto-selects: Pallas on TPU backends,
an identical-math XLA path elsewhere (tests force the kernels through
interpret mode and compare both, values and gradients).

Parity tolerance on the chip: under default precision the TPU's MXU
truncates matmul inputs to bf16 (eps ~8e-3 relative; ~1e-4..1e-3
absolute at unit-scale operands), and the two paths accumulate P·V in
different orders (flash: chunked online-softmax rescaling; XLA: one
matmul over the full row). Forward outputs are therefore compared at
5e-4 absolute for f32 inputs on the chip (2e-5 in interpret mode, exact
f32 both paths) with lse at 2e-4 — tight enough to catch a recurrence
break, loose enough not to flag the MXU's number format. Serving decode
rides this kernel; the guarantee that matters there (speculative greedy
== plain greedy, token-for-token) is integer-exact and pinned
separately in tests/test_speculative.py.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import use_pallas as _on_tpu

_LANE = 128
_SUBLANE = 8  # f32 sublane tile: stat vectors are stored [.., 8, S] because
# Mosaic requires block shapes tileable to (8, 128) — row 0 carries the data
_NEG = -1e30  # finite mask value: keeps exp/max arithmetic NaN-free


def _pad_to(x: jax.Array, axis: int, mult: int) -> jax.Array:
    n = x.shape[axis]
    pad = (-n) % mult
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


# ---------------------------------------------------------------------------
# reference path (XLA): identical math, used off-TPU and in tests
# ---------------------------------------------------------------------------


def flash_attention_ref(q, k, v, q_offset, k_offset, *, causal, window=None):
    """[BH, Sq, D] x [BH, Sk, D] -> (out [BH, Sq, D], lse [BH, Sq]).

    lse is the base-e logsumexp of the masked score rows; fully-masked
    rows return out=0 and lse=_NEG (the merge weight then underflows to
    zero exactly like the kernel path). ``window`` (with causal) keeps
    only keys with 0 <= q_pos - k_pos < window (sliding-window/local
    attention)."""
    scale = 1.0 / np.sqrt(q.shape[-1])
    s = jnp.einsum(
        "bqd,bkd->bqk", q.astype(jnp.float32), k.astype(jnp.float32)
    ) * scale
    if causal:
        qp = q_offset + jnp.arange(q.shape[1])
        kp = k_offset + jnp.arange(k.shape[1])
        keep = qp[:, None] >= kp[None, :]
        if window is not None:
            keep &= (qp[:, None] - kp[None, :]) < window
        s = jnp.where(keep[None], s, _NEG)
    m = jnp.max(s, axis=-1)
    p = jnp.exp(s - m[..., None])
    p = jnp.where(s <= _NEG / 2, 0.0, p)
    l = jnp.sum(p, axis=-1)
    out = jnp.einsum("bqk,bkd->bqd", p, v.astype(jnp.float32))
    out = out / jnp.maximum(l, 1e-30)[..., None]
    lse = jnp.where(l > 0, m + jnp.log(jnp.maximum(l, 1e-30)), _NEG)
    return out.astype(q.dtype), lse


# ---------------------------------------------------------------------------
# the grid: the block pairs a call visits
# ---------------------------------------------------------------------------


def _block_live(q_off, iq, block_q, k_off, k_base, block_k, causal, window):
    """Whether a block can hold a kept pair: the one home of the bound.
    ``grid_tables`` evaluates it over the rectangle for the forward's
    list and both backward kernels', so they can never diverge: a block
    is dead when (causal) even the LAST q row precedes the FIRST k row,
    or (window) even the FIRST q row is past the LAST k row's window."""
    if not causal:
        return True
    live = q_off + iq * block_q + block_q - 1 >= k_off + k_base
    if window is not None:
        live &= q_off + iq * block_q - (k_off + k_base + block_k - 1) < window
    return live


def _is_static(offset) -> bool:
    """An offset known while the call is traced (a Python int), against
    one that is a value of the program (``axis_index`` on a ring)."""
    return isinstance(offset, (int, np.integer))


def _grid_extent(sq, sk, block_q, block_k, causal, window, static, order):
    """``(nq, nk, rows, width)`` of a kernel's list: the blocks of both
    axes, the outer blocks of ``order`` and the most steps one of them
    is given. With ``delta`` known the list is exact and a row may hold
    every inner block; with it traced the length has to be fixed before
    the offsets are: a window row of ``b`` positions sees ``window + b -
    1`` of the inner axis, which at any alignment touches at most
    ``ceil((window + b - 2) / inner block) + 1`` blocks, and plain
    causal or unmasked rows keep the whole axis."""
    nq, nk = -(-sq // block_q), -(-sk // block_k)
    (rows, b_outer), (n_inner, b_inner) = (nq, block_q), (nk, block_k)
    if order == "k":
        rows, b_outer, n_inner, b_inner = n_inner, b_inner, rows, b_outer
    width = n_inner
    if causal and window is not None and not static:
        width = min(n_inner, -(-(window + b_outer - 2) // b_inner) + 1)
    return nq, nk, rows, width


def grid_tables(sq, sk, block_q, block_k, *, causal, window, delta,
                order):
    """``(outer, inner, live)``, int32 ``[steps]``: the pairs of blocks
    a kernel visits, in its order. ``order="q"`` is the forward's and
    dq's (``outer`` a q block, ``inner`` its k blocks, ascending),
    ``order="k"`` dkv's (``outer`` a k block, ``inner`` its q blocks).
    ``delta = q_offset - k_offset``.

    The live set is ``_block_live`` over the rectangle and nothing else.
    Every outer block has at least one step, so its output is written
    even where no pair of it is kept; that step has ``live`` 0.
    A ``delta`` that is a Python int gives NumPy tables of exactly the
    live pairs. A traced one gives ``jnp`` tables whose length is fixed
    by ``_grid_extent``: a row's spare steps repeat its last live pair
    with ``live`` 0, so they copy nothing (the block index does not
    change) and compute nothing."""
    static = _is_static(delta)
    xp = np if static else jnp
    nq, nk, rows, width = _grid_extent(
        sq, sk, block_q, block_k, causal, window, static, order
    )
    live = xp.broadcast_to(
        _block_live(
            delta, xp.arange(nq)[:, None], block_q,
            0, xp.arange(nk)[None, :] * block_k, block_k, causal, window,
        ),
        (nq, nk),
    )
    if order == "k":
        live = live.T
    count = live.sum(axis=1)
    # a row's live inner blocks first, ascending (the sort is stable)
    live_first = (
        np.argsort(~live, axis=1, kind="stable") if static
        else jnp.argsort(~live, axis=1, stable=True)
    )[:, :width]
    t = xp.arange(width)[None, :]
    inner = xp.take_along_axis(
        live_first, xp.minimum(t, xp.maximum(count - 1, 0)[:, None]), axis=1
    )
    outer = xp.broadcast_to(xp.arange(rows)[:, None], inner.shape)
    flag = t < count[:, None]
    if static:
        keep = t < np.maximum(count, 1)[:, None]
        outer, inner, flag = outer[keep], inner[keep], flag[keep]
    return tuple(
        x.reshape(-1).astype(xp.int32) for x in (outer, inner, flag)
    )


def grid_steps(sq, sk, block_q, block_k, *, causal, window, delta, order):
    """``(visited, live)`` a head: the steps of ``grid_tables``' list
    and how many of them hold a kept pair. ``delta=None`` stands for a
    traced one: ``visited`` is then the list's fixed length and ``live``,
    which only the run knows, is None."""
    if delta is None:
        _, _, rows, width = _grid_extent(
            sq, sk, block_q, block_k, causal, window, False, order
        )
        return rows * width, None
    outer, _, live = grid_tables(
        sq, sk, block_q, block_k, causal=causal, window=window,
        delta=int(delta), order=order,
    )
    return int(outer.shape[0]), int(live.sum())


def _grid_step(outer_ref, inner_ref):
    """``(outer, inner, first, last)`` of this step of the list: its
    pair of blocks, and whether it opens and closes its outer block's
    run, where the accumulators are initialised and written."""
    step, n = pl.program_id(1), pl.num_programs(1)
    row = outer_ref[step]
    first = (step == 0) | (outer_ref[jnp.maximum(step - 1, 0)] != row)
    last = (step == n - 1) | (outer_ref[jnp.minimum(step + 1, n - 1)] != row)
    return row, inner_ref[step], first, last


# ---------------------------------------------------------------------------
# forward kernel
# ---------------------------------------------------------------------------


def _fwd_kernel(
    outer_ref, inner_ref, live_ref, off_ref, q_ref, k_ref, v_ref, out_ref,
    lse_ref, acc_ref, m_ref, l_ref, *, causal, scale, k_len, block_q,
    block_k, window,
):
    iq, ik, first, last = _grid_step(outer_ref, inner_ref)

    @pl.when(first)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, _NEG)
        l_ref[...] = jnp.zeros_like(l_ref)

    q_off = off_ref[0]
    k_off = off_ref[1]
    q_pos = q_off + iq * block_q + jax.lax.broadcasted_iota(
        jnp.int32, (1, block_q), 1
    )
    k_base = ik * block_k

    @pl.when(live_ref[pl.program_id(1)] != 0)
    def _update():
        # dot OPERANDS stay in the input dtype (bf16 runs the MXU in one
        # pass; an f32 upcast would force multi-pass emulation) while
        # every dot ACCUMULATES in f32 via preferred_element_type and
        # all softmax/statistics math is f32 — the FlashAttention-on-TPU
        # standard precision recipe. For f32 inputs nothing changes.
        q = q_ref[0]  # [bq, D]
        k = k_ref[0]  # [bk, D]
        v = v_ref[0]
        s_t = jax.lax.dot_general(  # [bk, bq]: K sublanes, Q lanes
            k, q, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale
        k_pos = k_base + jax.lax.broadcasted_iota(jnp.int32, (block_k, 1), 0)
        valid = k_pos < k_len  # tail padding of the K axis
        if causal:
            valid = valid & (k_off + k_pos <= q_pos)
            if window is not None:
                valid = valid & (q_pos - (k_off + k_pos) < window)
        s_t = jnp.where(valid, s_t, _NEG)
        m_prev = m_ref[...]  # [1, bq]
        m_cur = jnp.max(s_t, axis=0, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        p_t = jnp.exp(s_t - m_new)
        p_t = jnp.where(valid, p_t, 0.0)
        corr = jnp.exp(m_prev - m_new)  # [1, bq]
        l_ref[...] = l_ref[...] * corr + jnp.sum(p_t, axis=0, keepdims=True)
        acc_ref[...] = acc_ref[...] * corr + jax.lax.dot_general(
            v, p_t.astype(v.dtype), (((0,), (0,)), ((), ())),  # [D, bq]
            preferred_element_type=jnp.float32,
        )
        m_ref[...] = m_new

    @pl.when(last)
    def _write():
        l = l_ref[...]
        out_ref[0] = (acc_ref[...] / jnp.maximum(l, 1e-30)).astype(
            out_ref.dtype
        )
        lse = jnp.where(
            l > 0, m_ref[...] + jnp.log(jnp.maximum(l, 1e-30)), _NEG
        )  # [1, bq] -> broadcast over the sublane-tile dim
        lse_ref[...] = jnp.broadcast_to(lse[None], lse_ref.shape)


# ---------------------------------------------------------------------------
# backward kernels
# ---------------------------------------------------------------------------


def _recompute_pt(q, k, lse_blk, *, causal, scale, q_pos, k_pos, k_len,
                  window=None):
    """Shared bwd score recomputation: p_t [bk, bq] from saved lse.
    ``q_pos`` arrives with k_offset already subtracted, so the window
    test is directly q_pos - k_pos."""
    s_t = jax.lax.dot_general(
        k, q, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    ) * scale
    valid = k_pos < k_len
    if causal:
        valid = valid & (k_pos + 0 <= q_pos)
        if window is not None:
            valid = valid & (q_pos - k_pos < window)
    # exp(s - lse): rows with lse=_NEG (fully masked) still produce 0
    # because s itself is masked to _NEG there as well
    s_t = jnp.where(valid, s_t, _NEG)
    p_t = jnp.exp(s_t - lse_blk)
    return jnp.where(valid, p_t, 0.0)


def _bwd_dq_kernel(
    outer_ref, inner_ref, live_ref, off_ref, q_ref, k_ref, v_ref, do_ref,
    lse_ref, c_ref, dq_ref, acc_ref, *, causal, scale, k_len, block_q,
    block_k, window,
):
    iq, ik, first, last = _grid_step(outer_ref, inner_ref)

    @pl.when(first)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q_off = off_ref[0]
    k_off = off_ref[1]

    @pl.when(live_ref[pl.program_id(1)] != 0)
    def _update():
        # native-dtype dot operands, f32 accumulation + f32 softmax math
        # (see _fwd_kernel's precision note); ds is cast back to the
        # input dtype for the dk/dq matmuls, as in the reference TPU
        # flash kernels
        q = q_ref[0]
        k = k_ref[0]
        v = v_ref[0]
        do = do_ref[0]  # [bq, D]
        q_pos = q_off + iq * block_q + jax.lax.broadcasted_iota(
            jnp.int32, (1, block_q), 1
        ) - k_off
        k_pos = ik * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (block_k, 1), 0
        )
        p_t = _recompute_pt(
            q, k, lse_ref[0][:1], causal=causal, scale=scale,
            q_pos=q_pos, k_pos=k_pos, k_len=k_len, window=window,
        )
        dp_t = jax.lax.dot_general(  # [bk, bq] = v . do^T
            v, do, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )
        ds_t = p_t * (dp_t - c_ref[0][:1]) * scale
        acc_ref[...] += jax.lax.dot_general(  # [D, bq] += k^T . ds_t
            k, ds_t.astype(k.dtype), (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    @pl.when(last)
    def _write():
        dq_ref[0] = acc_ref[...].astype(dq_ref.dtype)


def _bwd_dkv_kernel(
    outer_ref, inner_ref, live_ref, off_ref, q_ref, k_ref, v_ref, do_ref,
    lse_ref, c_ref, dk_ref, dv_ref, dk_acc, dv_acc, *, causal, scale,
    k_len, block_q, block_k, window,
):
    ik, iq, first, last = _grid_step(outer_ref, inner_ref)  # q innermost

    @pl.when(first)
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    q_off = off_ref[0]
    k_off = off_ref[1]

    @pl.when(live_ref[pl.program_id(1)] != 0)
    def _update():
        # native-dtype dot operands, f32 accumulation + f32 softmax math
        # (see _fwd_kernel's precision note)
        q = q_ref[0]
        k = k_ref[0]
        v = v_ref[0]
        do = do_ref[0]
        q_pos = q_off + iq * block_q + jax.lax.broadcasted_iota(
            jnp.int32, (1, block_q), 1
        ) - k_off
        k_pos = ik * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (block_k, 1), 0
        )
        p_t = _recompute_pt(
            q, k, lse_ref[0][:1], causal=causal, scale=scale,
            q_pos=q_pos, k_pos=k_pos, k_len=k_len, window=window,
        )
        dv_acc[...] += jax.lax.dot_general(  # [bk, D] += p_t . do
            p_t.astype(do.dtype), do, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        dp_t = jax.lax.dot_general(
            v, do, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )
        ds_t = p_t * (dp_t - c_ref[0][:1]) * scale
        dk_acc[...] += jax.lax.dot_general(  # [bk, D] += ds_t . q
            ds_t.astype(q.dtype), q, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    @pl.when(last)
    def _write():
        dk_ref[0] = dk_acc[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[...].astype(dv_ref.dtype)


# ---------------------------------------------------------------------------
# pallas_call drivers
# ---------------------------------------------------------------------------

def _blocks(sq: int, sk: int, block_q: int, block_k: int):
    """Block sizes clamped to the (sublane-rounded) sequence lengths.

    Small-shape hardening: a block's trailing dims must be
    (8, 128)-tileable or exactly equal to the array dims, and tiny decode-path shapes (a gamma+1 speculative
    verify chunk, a 1-row serving prompt) land BELOW the sublane tile.
    Rounding the clamp up to a multiple of ``_SUBLANE`` — with the
    sequence axes padded to match in the drivers — keeps every block
    spec divisible-by-(8,128) unconditionally instead of leaning on the
    equal-to-array escape hatch, which is exactly the clause that has
    shifted between Mosaic versions. Padding rows are masked the same
    way the lane padding already is (k via ``k_len``; q rows are
    sliced off, and the bwd drivers force their lse so p underflows
    to 0)."""
    bq = min(block_q, -(-max(sq, 1) // _SUBLANE) * _SUBLANE)
    bk = min(block_k, -(-max(sk, 1) // _SUBLANE) * _SUBLANE)
    return bq, bk


def _grid_params(interpret: bool):
    """Grid semantics for Mosaic: batch*heads is parallel (independent
    accumulator streams); the axis of steps is 'arbitrary' (sequential:
    it carries the online-softmax / accumulator recurrence from a row's
    first pair to its last). Interpret mode takes no compiler params."""
    if interpret:
        return {}
    return {
        "compiler_params": pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")
        )
    }


def _listed_grid(kernel, sq, sk, bq, bk, q_offset, k_offset, *, bh, causal,
                 window, static_delta, order):
    """The scalar-prefetch operands of one kernel, ``(outer, inner,
    live, offsets)``, and its grid ``(bh, steps)``; ticks the kernel's
    series of ``ps_flash_grid_steps``."""
    tables = grid_tables(
        sq, sk, bq, bk, causal=causal, window=window,
        delta=(
            q_offset - k_offset if static_delta is None else static_delta
        ),
        order=order,
    )
    steps = tables[0].shape[0]
    _count_grid_steps(
        kernel, steps,
        None if static_delta is None else int(tables[2].sum()),
    )
    offsets = jnp.stack([q_offset, k_offset]).astype(jnp.int32)
    return (*map(jnp.asarray, tables), offsets), (bh, steps)


def _count_grid_steps(kernel, visited, live):
    from ..telemetry.instruments import cached_flash_instruments

    tel = cached_flash_instruments()
    if tel is None:
        return
    tel["grid_steps"].labels(kernel=kernel, what="visited").inc(visited)
    if live is not None:
        tel["grid_steps"].labels(kernel=kernel, what="live").inc(live)


# index maps of the listed grid: (b, step, *scalar-prefetch refs). The
# q-side blocks follow the table that holds q blocks, the k side the other
def _at(table, transposed=False):
    if transposed:
        return lambda b, s, *refs: (b, 0, refs[table][s])
    return lambda b, s, *refs: (b, refs[table][s], 0)


def _fwd_pallas(q, k, v, q_offset, k_offset, *, causal, block_q, block_k,
                interpret, window, static_delta):
    bh, sq, d = q.shape
    sk = k.shape[1]
    scale = 1.0 / np.sqrt(d)
    bq, bk = _blocks(sq, sk, block_q, block_k)
    qp = _pad_to(_pad_to(q, 1, bq), 2, _LANE)
    kp = _pad_to(_pad_to(k, 1, bk), 2, _LANE)
    vp = _pad_to(_pad_to(v, 1, bk), 2, _LANE)
    dp_ = qp.shape[2]
    prefetch, grid = _listed_grid(
        "fwd", sq, sk, bq, bk, q_offset, k_offset, bh=bh, causal=causal,
        window=window, static_delta=static_delta, order="q",
    )
    out_t, lse = pl.pallas_call(
        functools.partial(
            _fwd_kernel, causal=causal, scale=scale, k_len=sk,
            block_q=bq, block_k=bk, window=window,
        ),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(prefetch),
            grid=grid,
            in_specs=[
                pl.BlockSpec((1, bq, dp_), _at(0)),
                pl.BlockSpec((1, bk, dp_), _at(1)),
                pl.BlockSpec((1, bk, dp_), _at(1)),
            ],
            out_specs=(
                pl.BlockSpec((1, dp_, bq), _at(0, transposed=True)),
                pl.BlockSpec((1, _SUBLANE, bq), _at(0, transposed=True)),
            ),
            scratch_shapes=[
                pltpu.VMEM((dp_, bq), jnp.float32),
                pltpu.VMEM((1, bq), jnp.float32),
                pltpu.VMEM((1, bq), jnp.float32),
            ],
        ),
        out_shape=(
            jax.ShapeDtypeStruct((bh, dp_, qp.shape[1]), q.dtype),
            jax.ShapeDtypeStruct((bh, _SUBLANE, qp.shape[1]), jnp.float32),
        ),
        interpret=interpret,
        **_grid_params(interpret),
    )(*prefetch, qp, kp, vp)
    out = jnp.swapaxes(out_t, 1, 2)[:, :sq, :d]
    return out, lse[:, 0, :sq]


def _bwd_pallas(q, k, v, do, lse, c, q_offset, k_offset, *, causal,
                block_q, block_k, interpret, window, static_delta):
    bh, sq, d = q.shape
    sk = k.shape[1]
    scale = 1.0 / np.sqrt(d)
    bq, bk = _blocks(sq, sk, block_q, block_k)
    qp = _pad_to(_pad_to(q, 1, bq), 2, _LANE)
    kp = _pad_to(_pad_to(k, 1, bk), 2, _LANE)
    vp = _pad_to(_pad_to(v, 1, bk), 2, _LANE)
    dop = _pad_to(_pad_to(do, 1, bq), 2, _LANE)
    # padded q rows: lse=_NEG there would make exp(s-lse) explode for
    # in-range k; force a huge lse so p underflows to 0 on padding
    lsep = _pad_to(lse, 1, bq)
    if lsep.shape[1] != sq:
        pad_rows = (
            jax.lax.broadcasted_iota(jnp.int32, lsep.shape, 1) >= sq
        )
        lsep = jnp.where(pad_rows, -_NEG, lsep)
    cp = _pad_to(c, 1, bq)
    # stat vectors enter the kernels sublane-tiled: [BH, 8, Sq] (row 0 live)
    lsep = jnp.broadcast_to(lsep[:, None, :], (bh, _SUBLANE, lsep.shape[1]))
    cp = jnp.broadcast_to(cp[:, None, :], (bh, _SUBLANE, cp.shape[1]))
    dp_ = qp.shape[2]
    listed = functools.partial(
        _listed_grid, sq=sq, sk=sk, bq=bq, bk=bk, q_offset=q_offset,
        k_offset=k_offset, bh=bh, causal=causal, window=window,
        static_delta=static_delta,
    )
    static = dict(
        causal=causal, scale=scale, k_len=sk, block_q=bq, block_k=bk,
        window=window,
    )

    def specs(q_table, k_table):
        qspec = pl.BlockSpec((1, bq, dp_), _at(q_table))
        kspec = pl.BlockSpec((1, bk, dp_), _at(k_table))
        vec_q = pl.BlockSpec((1, _SUBLANE, bq), _at(q_table, transposed=True))
        return [qspec, kspec, kspec, qspec, vec_q, vec_q]

    # dq: q blocks outer, k blocks inner (accumulated)
    prefetch, grid = listed("dq", order="q")
    dq_t = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, **static),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(prefetch),
            grid=grid,
            in_specs=specs(0, 1),
            out_specs=pl.BlockSpec((1, dp_, bq), _at(0, transposed=True)),
            scratch_shapes=[pltpu.VMEM((dp_, bq), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((bh, dp_, qp.shape[1]), q.dtype),
        interpret=interpret,
        **_grid_params(interpret),
    )(*prefetch, qp, kp, vp, dop, lsep, cp)
    # dkv: k blocks outer, q blocks inner (accumulated)
    prefetch, grid = listed("dkv", order="k")
    dk, dv = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, **static),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(prefetch),
            grid=grid,
            in_specs=specs(1, 0),
            out_specs=(
                pl.BlockSpec((1, bk, dp_), _at(0)),
                pl.BlockSpec((1, bk, dp_), _at(0)),
            ),
            scratch_shapes=[
                pltpu.VMEM((bk, dp_), jnp.float32),
                pltpu.VMEM((bk, dp_), jnp.float32),
            ],
        ),
        out_shape=(
            jax.ShapeDtypeStruct((bh, kp.shape[1], dp_), k.dtype),
            jax.ShapeDtypeStruct((bh, kp.shape[1], dp_), v.dtype),
        ),
        interpret=interpret,
        **_grid_params(interpret),
    )(*prefetch, qp, kp, vp, dop, lsep, cp)
    dq = jnp.swapaxes(dq_t, 1, 2)[:, :sq, :d]
    return dq, dk[:, :sk, :d], dv[:, :sk, :d]


# ---------------------------------------------------------------------------
# public API with custom VJP
# ---------------------------------------------------------------------------


@functools.partial(
    jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8, 9, 10, 11)
)
def _flash(q, k, v, q_offset, k_offset, causal, block_q, block_k,
           use_pallas, interpret, window, static_delta):
    """``static_delta`` is ``q_offset - k_offset`` where both arrived as Python
    ints, else None: what the grid's list is built from, static beside
    the arrays the kernels mask by."""
    if use_pallas:
        return _fwd_pallas(
            q, k, v, q_offset, k_offset, causal=causal,
            block_q=block_q, block_k=block_k, interpret=interpret,
            window=window, static_delta=static_delta,
        )
    return flash_attention_ref(
        q, k, v, q_offset, k_offset, causal=causal, window=window
    )


# the names of the forward rule's two residuals that are also its
# outputs, for ``jax.checkpoint`` policies (``save_only_these_names``):
# a rematerialised layer whose policy lists them hands the backward rule
# the values its forward pass made, and does not run the forward kernel
# again (O(Sq*Sk*D) work to make, O(Sq*D) bytes to keep). They sit on the
# values the backward rule receives: a name on the caller's copy of the
# result would keep that copy and still recompute these. No policy
# listing them, a name is the identity.
FLASH_OUT = "flash_attention_out"
FLASH_LSE = "flash_attention_lse"


def _flash_fwd(q, k, v, q_offset, k_offset, causal, block_q, block_k,
               use_pallas, interpret, window, static_delta):
    out, lse = _flash(
        q, k, v, q_offset, k_offset, causal, block_q, block_k,
        use_pallas, interpret, window, static_delta,
    )
    out = checkpoint_name(out, FLASH_OUT)
    lse = checkpoint_name(lse, FLASH_LSE)
    return (out, lse), (q, k, v, out, lse, q_offset, k_offset)


def _flash_bwd(causal, block_q, block_k, use_pallas, interpret, window,
               static_delta, res, ct):
    q, k, v, out, lse, q_offset, k_offset = res
    do, dlse = ct
    do32 = do.astype(jnp.float32)
    delta = jnp.sum(do32 * out.astype(jnp.float32), axis=-1)  # [BH, Sq]
    dlse32 = (
        jnp.zeros_like(delta) if dlse is None else dlse.astype(jnp.float32)
    )
    # d s = p * (dp - delta + dlse); fold into one lane vector
    c = delta - dlse32
    if use_pallas:
        dq, dk, dv = _bwd_pallas(
            q, k, v, do, lse, c, q_offset, k_offset, causal=causal,
            block_q=block_q, block_k=block_k, interpret=interpret,
            window=window, static_delta=static_delta,
        )
    else:
        scale = 1.0 / np.sqrt(q.shape[-1])
        s = jnp.einsum(
            "bqd,bkd->bqk", q.astype(jnp.float32), k.astype(jnp.float32)
        ) * scale
        if causal:
            qp_ = q_offset + jnp.arange(q.shape[1])
            kp_ = k_offset + jnp.arange(k.shape[1])
            keep = qp_[:, None] >= kp_[None, :]
            if window is not None:
                keep &= (qp_[:, None] - kp_[None, :]) < window
            s = jnp.where(keep[None], s, _NEG)
        p = jnp.exp(s - lse[..., None])
        p = jnp.where(s <= _NEG / 2, 0.0, p)
        dp = jnp.einsum("bqd,bkd->bqk", do32, v.astype(jnp.float32))
        ds = p * (dp - c[..., None]) * scale
        dq = jnp.einsum("bqk,bkd->bqd", ds, k.astype(jnp.float32))
        dk = jnp.einsum("bqk,bqd->bkd", ds, q.astype(jnp.float32))
        dv = jnp.einsum("bqk,bqd->bkd", p, do32)
    z = np.zeros((), jax.dtypes.float0)  # int offsets: symbolic-zero tangent
    return (
        dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype), z, z
    )


_flash.defvjp(_flash_fwd, _flash_bwd)


def flash_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = False,
    q_offset=0,
    k_offset=0,
    block_q: int = 512,
    block_k: int = 512,
    use_pallas: Optional[bool] = None,
    interpret: bool = False,
    with_lse: bool = False,
    window: Optional[int] = None,
):
    """Blockwise exact attention over [BH, S, D] head-major arrays.

    Default blocking is 512x512: fewer grid steps and longer MXU
    contractions than 128x128 at a working set that still fits VMEM
    (the block sweep behind the choice is not measured on the current
    code). Blocks clamp to the sequence length (short callers
    unaffected) and, in window mode, to the window scale (the
    whole-block skip contract below).

    ``use_pallas=None`` selects the kernels on a TPU backend and the
    XLA path elsewhere. ``interpret=True`` runs the kernels in Pallas
    interpret mode and is for tests only: nothing selects it implicitly.

    ``q_offset``/``k_offset`` are the GLOBAL sequence positions of row 0
    (traced values allowed — ring attention over several devices passes
    ``axis_index``-derived offsets), so causal masking is correct on
    sequence-sharded chunks. ``window`` (requires causal) restricts each
    query to the ``window`` most recent keys (0 <= q_pos - k_pos < window
    — sliding-window / local attention).

    Blocks the mask drops whole (past the diagonal, out of the window)
    are not on the kernels' grid: neither fetched nor stepped over, so
    compute AND traffic per query are O(window), not O(S). Offsets that
    arrive as Python ints give the exact list of live blocks; traced
    ones a list of a fixed length (a window: the most blocks a row can
    touch; plain causal: the rectangle) whose spare steps fetch and
    compute nothing (``grid_tables``).
    Returns ``out`` or ``(out, lse)`` — lse is what chunk-merging needs.
    """
    if window is not None:
        if not causal:
            raise ValueError("window requires causal=True (sliding window)")
        if window < 1:
            raise ValueError(f"window must be >= 1, got {window}")
        window = int(window)
        # the O(window)-per-query contract rests on whole-block skips
        # (_block_live): a 512-wide block is never fully outside a 256
        # window, so the large default blocking would compute ~2 extra
        # block-widths of masked work per query row. Clamp blocks to the
        # window scale (pow2, floor 128 — the sweep's win came from
        # fewer grid steps, which small windows cap anyway).
        cap = max(128, 1 << (window - 1).bit_length())
        block_q = min(block_q, cap)
        block_k = min(block_k, cap)
    if use_pallas is None:
        use_pallas = _on_tpu()
    static_delta = None
    if _is_static(q_offset) and _is_static(k_offset):
        static_delta = int(q_offset) - int(k_offset)
    q_offset = jnp.asarray(q_offset, jnp.int32)
    k_offset = jnp.asarray(k_offset, jnp.int32)
    out, lse = _flash(
        q, k, v, q_offset, k_offset, causal, block_q, block_k,
        bool(use_pallas), bool(interpret), window, static_delta,
    )
    return (out, lse) if with_lse else out


def flash_mha(
    x_q: jax.Array,
    x_k: jax.Array,
    x_v: jax.Array,
    n_heads: int,
    *,
    causal: bool = False,
    q_offset=0,
    k_offset=0,
    use_pallas: Optional[bool] = None,
    interpret: bool = False,
    window: Optional[int] = None,
    n_kv_heads: Optional[int] = None,
) -> jax.Array:
    """Multi-head wrapper: [B, S, H] with H = n_heads * dh, like dense_mha.

    ``n_kv_heads`` (grouped-query attention): K/V carry only that many
    heads (``x_k``/``x_v`` are [B, S, n_kv_heads * dh]) and each K/V head
    serves ``n_heads // n_kv_heads`` query heads — the KV-cache/bandwidth
    reduction of GQA/MQA (n_kv_heads=1). The kernel itself is unchanged:
    K/V heads are broadcast to the query-head grouping at the wrapper."""
    b, sq, h = x_q.shape
    sk = x_k.shape[1]
    dh = h // n_heads
    kvh = n_kv_heads if n_kv_heads is not None else n_heads
    if n_heads % kvh:
        raise ValueError(f"n_heads={n_heads} must divide by n_kv_heads={kvh}")

    def split(x, s, nh):
        return (
            x.reshape(b, s, nh, dh)
            .transpose(0, 2, 1, 3)
            .reshape(b * nh, s, dh)
        )

    def expand_kv(x):  # [B*kvh, S, dh] -> [B*n_heads, S, dh] (group repeat)
        x = x.reshape(b, kvh, sk, dh)
        x = jnp.repeat(x, n_heads // kvh, axis=1)
        return x.reshape(b * n_heads, sk, dh)

    out = flash_attention(
        split(x_q, sq, n_heads),
        expand_kv(split(x_k, sk, kvh)),
        expand_kv(split(x_v, sk, kvh)),
        causal=causal, q_offset=q_offset, k_offset=k_offset,
        use_pallas=use_pallas, interpret=interpret, window=window,
    )
    return (
        out.reshape(b, n_heads, sq, dh).transpose(0, 2, 1, 3).reshape(b, sq, h)
    )

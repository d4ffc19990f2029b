"""The gated delta rule with a per-channel decay (Kimi Delta Attention,
arXiv 2510.26692): a linear-attention recurrence over the sequence, in
its chunk-parallel form.

Per head, with a state ``S`` [K, V] that starts at zero, a decay
``alpha_t = exp(g_t)`` in (0, 1]^K, a step ``beta_t`` and L2-normalised
``q_t``, ``k_t``:

    S_t = (I - beta_t k_t k_t^T) Diag(alpha_t) S_{t-1} + beta_t k_t v_t^T
    o_t = K^-1/2 S_t^T q_t

``kda_recurrent`` is that, token by token (the definition: tests hold
the chunked form to it). ``kda_chunked`` computes the same in chunks of
``C`` tokens. With ``G_r`` the sum of ``g`` over the chunk's tokens up to
``r``, ``u_t = v_t - (Diag(alpha_t) S_{t-1})^T k_t`` obeys inside a chunk
that starts from ``S0``

    (I + A Diag(beta)) U = V - (K * exp(G)) S0,
    A[i, j] = sum_d k_i[d] k_j[d] exp(G_i[d] - G_j[d])   for j < i,

a unit lower-triangular solve, and

    o_r = K^-1/2 [(q_r * exp(G_r))^T S0 + sum_{i<=r} P[r, i] beta_i u_i],
    P[r, i] = sum_d q_r[d] k_i[d] exp(G_r[d] - G_i[d]),
    S_C = Diag(exp(G_C)) S0 + sum_i (k_i * exp(G_C - G_i)) beta_i u_i^T.

Everything that does not involve ``S0`` (``A``, ``P``, the inverse, its
products with ``V`` and ``K * exp(G)``) is computed for all chunks at
once; a ``lax.scan`` over the chunks carries the state through four
small products a chunk.

No exponential here has a positive argument, whatever the decay:
``exp(G_i - G_j)`` with ``j <= i`` is formed as such inside blocks of
``SUB`` tokens (an elementwise product reduced over the channels), and
between blocks as ``exp(G_i - r) exp(r - G_j)`` around the later block's
first row ``r`` (``G_j >= r >= G_i``), so that a decay strong enough to
underflow ``exp(G)`` costs the terms that are zero anyway and no
overflow. In f32: ``g``, its sums and exponentials, ``beta``, the state
and what is added to it, ``A``, ``P`` and the solve; in the backward
pass besides ``dS``, ``dA``, ``dP`` and the solve's gradient. ``dtype``
(bf16 in a bf16 model) is what the matrix products read, forward and
backward, accumulated in f32.

The gradient is written by hand (``jax.custom_vjp`` around the whole of
``kda_chunked``): autodiff never sees the inside. Per chunk, with
``T = (I + A Diag(beta))^-1``, ``W = T (K * exp(G))``,
``U = T V - W S0``, ``Ub = beta * U``, ``O = (Q * exp(G)) S0 + P Ub``,
``S1 = Diag(exp(G_C)) S0 + (K * exp(G_C - G))^T Ub``:

* a reverse ``lax.scan`` over the chunks carries ``dS`` [K, V], f32,
  from the cotangent of the returned last state:
  ``dUb = P^T dO + (K * exp(G_C - G)) dS1``, ``dU = beta * dUb``,
  ``dS0 = (Q * exp(G))^T dO + Diag(exp(G_C)) dS1 - W^T dU``, and hands
  out ``dUb`` with what comes through ``S1``:
  ``d(K * exp(G_C - G)) = Ub dS1^T``, ``d exp(G_C) = rowsum(dS1 * S0)``;
* for all chunks at once: ``dP = tril(dO Ub^T)``, ``dW = -dU S0^T``,
  ``d(Q * exp(G)) = dO S0^T``, ``dT = dW (K * exp(G))^T + dU V^T``,
  ``dV = T^T dU``, ``d(K * exp(G)) = T^T dW``; the inverse through
  ``dM = -T^T dT T^T`` (strictly lower), ``dA = dM Diag(beta)``,
  ``dbeta = rowsum(dUb * U) + colsum(dM * A)``; from ``dA``, ``dP`` the
  gradients of q and k as the forward forms the pairs: inside blocks of
  ``SUB`` as multiply-reduces over the pairs (no ``[SUB, SUB, K]`` array
  is written), between blocks as products around the later block's
  first row;
* a term ``x_i y_j exp(G_i - G_j)`` gives ``G_i`` what it gives ``x_i``
  times ``x_i`` and ``G_j`` the opposite of what it gives ``y_j`` times
  ``y_j``, so ``dG = q dq + k (dk as the later token - dk as the
  earlier)`` and needs no pass of its own; ``dg`` is the reverse running
  sum of ``dG`` inside the chunk. No exponential with a positive
  argument here either.

Nothing but the five inputs is kept from the forward: the backward rule
computes the chunks' matrices again and runs the forward's scan again
without its two products for the output (``_starts``: two of the four
products) for the state every chunk starts from. Kept
across the rules those states were 1.0 GB more of a 14.0 GB plan at
8,192 tokens and 64 heads (compiled for a v5e, PR 34).
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp

# tokens of a block inside which exp(G_i - G_j) is formed pair by pair
SUB = 16
HIGHEST = jax.lax.Precision.HIGHEST
# what the chunked scan carries its state in from chunk to chunk
STATE_DTYPE = jnp.float32
# heads computed at a time, at most, forward and backward: a pass holds its
# chunks' q, k, g, their products with the decays, the gradients of each
# and the state at every chunk's start, some thirty arrays of the inputs'
# size. At 8,192 tokens, 64 heads of 128 a training step plans 8.79 GB of
# temporaries with 16 at a time (the parent's 8.80: the peak lies
# elsewhere) and 11.02 with 32 (compiled for a v5e, PR 34): beside 5.2 GB
# of f32 weights only the first stays under the parent's plan
HEADS_PER_PASS = 16


def kda_recurrent(q, k, v, g, beta):
    """The recurrence token by token, in f32: q, k, g [B, S, H, K],
    v [B, S, H, V], beta [B, S, H]; returns ``(o, S_last)``, o
    [B, S, H, V] and the state after the last token [B, H, K, V], f32."""
    f32 = lambda t: jnp.moveaxis(t.astype(jnp.float32), 1, 0)  # noqa: E731
    b, _, h, dk = q.shape
    scale = dk ** -0.5

    def step(state, x):
        q_t, k_t, v_t, g_t, b_t = x
        state = jnp.exp(g_t)[..., None] * state
        u = v_t - jnp.einsum("bhk,bhkv->bhv", k_t, state, precision=HIGHEST)
        state = state + (b_t[..., None] * k_t)[..., None] * u[..., None, :]
        return state, scale * jnp.einsum(
            "bhk,bhkv->bhv", q_t, state, precision=HIGHEST
        )

    state = jnp.zeros((b, h, dk, v.shape[-1]), jnp.float32)
    state, out = jax.lax.scan(
        step, state, tuple(map(f32, (q, k, v, g, beta)))
    )
    return jnp.moveaxis(out, 0, 1), state


def _pair_decay(gc):
    """``exp(G_i - G_j)`` [..., i, j, K] of blocks gc [..., SUB, K], 1
    above the diagonal (masked by every reader)."""
    return jnp.exp(
        jnp.minimum(gc[..., :, None, :] - gc[..., None, :, :], 0.0)
    )


def _within_blocks(q, k, gc):
    """``(A, P)`` [..., SUB, SUB] f32 of blocks q, k, gc [..., SUB, K]
    (gc the running sum of g): every pair's decay formed as such."""
    decay = _pair_decay(gc)
    kj = k[..., None, :, :] * decay
    return (
        jnp.sum(k[..., :, None, :] * kj, -1),
        jnp.sum(q[..., :, None, :] * kj, -1),
    )


def _within_blocks_vjp(q, k, gc, d_a, d_p):
    """``(dq, dk_row, dk_col)`` [..., SUB, K] of blocks q, k, gc from the
    cotangents [..., SUB, SUB] of their ``(A, P)``, zero where those are
    masked: ``dk_row`` what reaches k as the later token of a pair (the
    rows of A), ``dk_col`` as the earlier one (the columns of A and P).
    Each a multiply-reduce over the pairs."""
    decay = _pair_decay(gc)
    kj = k[..., None, :, :] * decay
    d_a, d_p = d_a[..., None], d_p[..., None]
    return (
        jnp.sum(d_p * kj, -2),
        jnp.sum(d_a * kj, -2),
        jnp.sum(
            (d_a * k[..., :, None, :] + d_p * q[..., :, None, :]) * decay, -3
        ),
    )


def _across_blocks(q, k, gc, k_before, gc_before, dtype):
    """``(A, P)`` rows [..., SUB, J] of a block (q, k, gc [..., SUB, K])
    against the J tokens of its chunk before it (``k_before``,
    ``gc_before`` [..., J, K]), around the block's first row."""
    first = gc[..., :1, :]
    rows = jnp.exp(gc - first)
    cols = (k_before * jnp.exp(first - gc_before)).astype(dtype)
    dot = lambda a: jnp.einsum(  # noqa: E731
        "...ik,...jk->...ij", (a * rows).astype(dtype), cols,
        preferred_element_type=jnp.float32,
    )
    return dot(k), dot(q)


def _across_blocks_vjp(q, k, gc, k_before, gc_before, d_a, d_p, dtype):
    """``(dq, dk_row, dk_col, d_first)`` from the cotangents
    [..., SUB, J] of ``_across_blocks``'s ``(A, P)``: ``dq``, ``dk_row``
    [..., SUB, K] of the block, ``dk_col`` [..., J, K] of the tokens
    before it, and ``d_first`` [..., 1, K], what the running sum of g at
    the block's first row gets for being the row the pairs are formed
    around: what the columns give it less what the rows do, nothing but
    the products' rounding. It is what cancels that rounding in the
    sum of dG over the later tokens, for every token before the pair."""
    first = gc[..., :1, :]
    rows = jnp.exp(gc - first)
    to_first = jnp.exp(first - gc_before)
    cols = (k_before * to_first).astype(dtype)
    d_a, d_p = d_a.astype(dtype), d_p.astype(dtype)
    dot = functools.partial(jnp.einsum, preferred_element_type=jnp.float32)
    back = lambda d: dot("...ij,...jk->...ik", d, cols) * rows  # noqa: E731
    dq, dk_row = back(d_p), back(d_a)
    dk_col = to_first * (
        dot("...ij,...ik->...jk", d_a, (k * rows).astype(dtype))
        + dot("...ij,...ik->...jk", d_p, (q * rows).astype(dtype))
    )
    d_first = jnp.sum(k_before * dk_col, -2, keepdims=True) - jnp.sum(
        q * dq + k * dk_row, -2, keepdims=True
    )
    return dq, dk_row, dk_col, d_first


def _chunk_matrices(q, k, gc, dtype):
    """``A`` (rows above and on the diagonal zero) and ``P`` (rows above
    it zero) [..., C, C] f32 of chunks q, k, gc [..., C, K] f32."""
    c = q.shape[-2]
    sub = min(SUB, c)
    n = c // sub
    blocks = lambda t: t.reshape(*t.shape[:-2], n, sub, t.shape[-1])  # noqa
    a_in, p_in = _within_blocks(blocks(q), blocks(k), blocks(gc))
    lower = jnp.tril(jnp.ones((sub, sub), bool))
    a_in = jnp.where(lower & ~jnp.eye(sub, dtype=bool), a_in, 0.0)
    p_in = jnp.where(lower, p_in, 0.0)
    a_rows, p_rows = [], []
    for i in range(n):
        lo, hi = i * sub, (i + 1) * sub
        a_row, p_row = [a_in[..., i, :, :]], [p_in[..., i, :, :]]
        if i:
            a_out, p_out = _across_blocks(
                q[..., lo:hi, :], k[..., lo:hi, :], gc[..., lo:hi, :],
                k[..., :lo, :], gc[..., :lo, :], dtype,
            )
            a_row.insert(0, a_out)
            p_row.insert(0, p_out)
        if hi < c:
            pad = jnp.zeros((*q.shape[:-2], sub, c - hi), jnp.float32)
            a_row.append(pad)
            p_row.append(pad)
        a_rows.append(jnp.concatenate(a_row, -1))
        p_rows.append(jnp.concatenate(p_row, -1))
    return jnp.concatenate(a_rows, -2), jnp.concatenate(p_rows, -2)


def _chunk_matrices_vjp(q, k, gc, d_a, d_p, dtype):
    """``(dq, dk, d_gc)`` [..., C, K] f32 from the cotangents
    [..., C, C] f32 of ``_chunk_matrices``'s ``(A, P)``, ``d_a`` zero on
    and above the diagonal and ``d_p`` above it. A term
    ``x_i y_j exp(G_i - G_j)`` gives G_i what it gives x_i, times x_i,
    and G_j the opposite of what it gives y_j, times y_j: ``d_gc`` is
    ``q dq + k (dk_row - dk_col)``, k's gradient as the later token of a
    pair less its gradient as the earlier one."""
    c = q.shape[-2]
    sub = min(SUB, c)
    n = c // sub
    blocks = lambda t: t.reshape(*t.shape[:-2], n, sub, t.shape[-1])  # noqa
    diagonal = lambda t: jnp.stack(  # noqa: E731
        [t[..., i * sub:(i + 1) * sub, i * sub:(i + 1) * sub]
         for i in range(n)], -3,
    )
    dq, dk_row, dk_col = (
        t.reshape(q.shape) for t in _within_blocks_vjp(
            blocks(q), blocks(k), blocks(gc), diagonal(d_a), diagonal(d_p)
        )
    )
    if n > 1:
        zero = jnp.zeros_like(q[..., :sub, :])
        dq_rows, dk_rows, d_firsts = [zero], [zero], [zero]
        for i in range(1, n):
            lo, hi = i * sub, (i + 1) * sub
            dq_i, dk_i, dk_before, d_first = _across_blocks_vjp(
                q[..., lo:hi, :], k[..., lo:hi, :], gc[..., lo:hi, :],
                k[..., :lo, :], gc[..., :lo, :],
                d_a[..., lo:hi, :lo], d_p[..., lo:hi, :lo], dtype,
            )
            dq_rows.append(dq_i)
            dk_rows.append(dk_i)
            d_firsts += [d_first, zero[..., 1:, :]]
            dk_col += jnp.pad(
                dk_before, ((0, 0),) * (q.ndim - 2) + ((0, c - lo), (0, 0))
            )
        dq += jnp.concatenate(dq_rows, -2)
        dk_row += jnp.concatenate(dk_rows, -2)
    d_gc = q * dq + k * (dk_row - dk_col)
    if n > 1:
        d_gc += jnp.concatenate(d_firsts, -2)
    return dq, dk_row + dk_col, d_gc


def unit_lower_inverse(low):
    """``(I + low)^-1`` for ``low`` [..., C, C] strictly lower
    triangular (C a power of two), f32: forward substitution inside
    blocks of ``SUB`` rows, then the blocks merged two by two,
    [[P, 0], [R, Q]]^-1 = [[P^-1, 0], [-Q^-1 R P^-1, Q^-1]]."""
    c = low.shape[-1]
    sub = min(SUB, c)
    n = c // sub
    mm = functools.partial(jnp.matmul, precision=HIGHEST)
    diag = jnp.stack(
        [low[..., i * sub:(i + 1) * sub, i * sub:(i + 1) * sub]
         for i in range(n)], -3,
    )  # [..., n, sub, sub]
    eye = jnp.eye(sub, dtype=low.dtype)
    rows = [jnp.broadcast_to(eye[0], diag.shape[:-2] + (sub,))]
    for i in range(1, sub):
        # row i of the inverse from the rows above it
        above = jnp.stack(rows, -2)  # [..., i, sub]
        rows.append(eye[i] - jnp.einsum(
            "...j,...jk->...k", diag[..., i, :i], above, precision=HIGHEST
        ))
    inv = [jnp.stack(rows, -2)[..., i, :, :] for i in range(n)]
    size = sub
    while len(inv) > 1:
        merged = []
        for i in range(0, len(inv), 2):
            lo = i * size
            r = low[..., lo + size:lo + 2 * size, lo:lo + size]
            p, q = inv[i], inv[i + 1]
            bottom = jnp.concatenate([-mm(mm(q, r), p), q], -1)
            top = jnp.concatenate([p, jnp.zeros_like(p)], -1)
            merged.append(jnp.concatenate([top, bottom], -2))
        inv, size = merged, 2 * size
    return inv[0]


def kda_chunked(q, k, v, g, beta, *, chunk: int = 64, dtype=None):
    """The recurrence in chunks of ``chunk`` tokens (a power of two, a
    multiple of ``SUB`` or below it): q, k, g [B, S, H, K], v
    [B, S, H, V], beta [B, S, H]; returns ``(o, S_last)``: o
    [B, S, H, V] in ``dtype`` (default: q's), which is also what the
    matrix products read, and the state after the last token
    [B, H, K, V] as the scan carried it (``STATE_DTYPE``). S need not
    divide by the chunk: the tail is padded with tokens that leave the
    state as it is.

    The heads are independent: forward and backward each run at most
    ``HEADS_PER_PASS`` of them at a time (the largest divisor of H that
    is no more), so that only one pass's chunk matrices are alive."""
    dtype = q.dtype if dtype is None else dtype
    if chunk & (chunk - 1) or (chunk > SUB and chunk % SUB):
        raise ValueError(f"chunk {chunk}: a power of two")
    return _kda(q, k, v, g, beta, chunk, jnp.dtype(dtype))


def _forward(q, k, v, g, beta, chunk, dtype):
    hp = _heads_of_a_pass(q.shape[2])
    out, state = _map_passes(
        functools.partial(_chunked, chunk=chunk, dtype=dtype),
        tuple(_split(t, 2, hp) for t in (q, k, v, g, beta)),
    )
    return _join(out, 2), _join(state, 1)


_kda = jax.custom_vjp(_forward, nondiff_argnums=(5, 6))


def _kda_fwd(q, k, v, g, beta, chunk, dtype):
    return _forward(q, k, v, g, beta, chunk, dtype), (q, k, v, g, beta)


def _kda_bwd(chunk, dtype, inputs, cotangents):
    d_out, d_state = cotangents
    hp = _heads_of_a_pass(d_out.shape[2])
    grads = _map_passes(
        functools.partial(_chunked_vjp, chunk=chunk, dtype=dtype),
        (*(_split(t, 2, hp) for t in (*inputs, d_out)),
         _split(d_state, 1, hp)),
    )
    return tuple(_join(t, 2) for t in grads)


_kda.defvjp(_kda_fwd, _kda_bwd)


def _heads_of_a_pass(h):
    return max(n for n in range(1, min(h, HEADS_PER_PASS) + 1) if h % n == 0)


def _split(t, axis, hp):
    """[..., H, ...] (H at ``axis``) -> [H / hp, ..., hp, ...]."""
    t = t.reshape(*t.shape[:axis], -1, hp, *t.shape[axis + 1:])
    return jnp.moveaxis(t, axis, 0)


def _join(t, axis):
    """``_split``'s inverse."""
    t = jnp.moveaxis(t, 0, axis)
    return t.reshape(*t.shape[:axis], -1, *t.shape[axis + 2:])


def _map_passes(fn, args):
    """``fn`` over the leading axis of every one of ``args``, one pass
    after the other; no loop where there is one pass."""
    if args[0].shape[0] == 1:
        return tuple(t[None] for t in fn(*(t[0] for t in args)))
    return jax.lax.map(lambda x: fn(*x), args)


class _Chunks(NamedTuple):
    """What of a pass needs no neighbouring chunk, [N, B, H, C, ...]:
    f32 but for what the matrix products read (``dtype``)."""

    q: jax.Array  # times K^-1/2
    k: jax.Array
    beta: jax.Array  # [N, B, H, C]
    gc: jax.Array  # the running sum of g inside the chunk
    decay: jax.Array  # exp(gc)
    to_end: jax.Array  # exp(gc's last row - gc)
    decay_end: jax.Array  # exp(gc's last row) [N, B, H, K]
    a: jax.Array  # A [N, B, H, C, C]
    inv: jax.Array  # (I + A Diag(beta))^-1
    p: jax.Array  # P, dtype
    v: jax.Array  # dtype
    k_decay: jax.Array  # k * decay, dtype
    q_decay: jax.Array  # q * decay, dtype
    k_end: jax.Array  # k * to_end, dtype
    w: jax.Array  # inv (k * decay), dtype
    uv: jax.Array  # inv v, f32


def _chunks(q, k, v, g, beta, chunk, dtype) -> _Chunks:
    def chunks(t):  # [B, S, H, ...] -> [N, B, H, C, ...] f32
        return _to_chunks(t.astype(jnp.float32), chunk)

    q, k, v, g, beta = map(
        chunks, (q * q.shape[-1] ** -0.5, k, v, g, beta)
    )
    gc = jnp.cumsum(g, axis=-2)
    a, p = _chunk_matrices(q, k, gc, dtype)
    inv = unit_lower_inverse(a * beta[..., None, :])
    decay = jnp.exp(gc)
    last = gc[..., -1:, :]
    to_end = jnp.exp(last - gc)
    v, k_decay = v.astype(dtype), (k * decay).astype(dtype)
    return _Chunks(
        q=q, k=k, beta=beta, gc=gc, decay=decay, to_end=to_end,
        decay_end=jnp.exp(last[..., 0, :]), a=a, inv=inv,
        p=p.astype(dtype), v=v, k_decay=k_decay,
        q_decay=(q * decay).astype(dtype), k_end=(k * to_end).astype(dtype),
        w=_dot(inv.astype(dtype), k_decay).astype(dtype),
        uv=_dot(inv.astype(dtype), v),
    )


def _to_chunks(t, chunk):
    """[B, S, H, ...] -> [N, B, H, C, ...], the tail padded with zeros."""
    b, s = t.shape[:2]
    n = -(-s // chunk)
    t = jnp.pad(t, ((0, 0), (0, n * chunk - s)) + ((0, 0),) * (t.ndim - 2))
    t = t.reshape(b, n, chunk, *t.shape[2:])
    return jnp.moveaxis(jnp.moveaxis(t, 1, 0), 2, 3)


def _from_chunks(t, s):
    """``_to_chunks``'s inverse: [N, B, H, C, ...] -> [B, S, H, ...]."""
    t = jnp.moveaxis(jnp.moveaxis(t, 3, 2), 0, 1)
    return t.reshape(t.shape[0], -1, *t.shape[3:])[:, :s]


_dot = functools.partial(jnp.matmul, preferred_element_type=jnp.float32)
_ein = functools.partial(jnp.einsum, preferred_element_type=jnp.float32)


def _advance(state, w, uv, k_end, beta, decay_end, dtype):
    """One chunk of the recurrence: ``(S0 in dtype, Ub in dtype, the
    state after the chunk)`` from the state before it."""
    s0 = state.astype(dtype)
    ub = (beta[..., None] * (uv - _dot(w, s0))).astype(dtype)
    after = decay_end[..., None] * state + _dot(
        jnp.swapaxes(k_end, -1, -2), ub
    )
    return s0, ub, after.astype(STATE_DTYPE)


def _zero_state(x: _Chunks):
    _, b, h, _, dk = x.q.shape
    return jnp.zeros((b, h, dk, x.v.shape[-1]), STATE_DTYPE)


def _scan(x: _Chunks, dtype):
    """The state through the chunks, four products each: ``(S_last,
    o [N, B, H, C, V])``."""

    def step(state, x):
        w, uv, qg, p, k_end, beta, decay_end = x
        s0, ub, after = _advance(state, w, uv, k_end, beta, decay_end, dtype)
        return after, (_dot(qg, s0) + _dot(p, ub)).astype(dtype)

    return jax.lax.scan(
        step, _zero_state(x),
        (x.w, x.uv, x.q_decay, x.p, x.k_end, x.beta, x.decay_end),
    )


def _starts(x: _Chunks, dtype):
    """The state each chunk starts from [N, B, H, K, V]: the forward's
    scan without its two products for the output."""

    def step(state, x):
        return _advance(state, *x, dtype)[2], state

    return jax.lax.scan(
        step, _zero_state(x), (x.w, x.uv, x.k_end, x.beta, x.decay_end)
    )[1]


def _chunked(q, k, v, g, beta, *, chunk, dtype):
    state, out = _scan(_chunks(q, k, v, g, beta, chunk, dtype), dtype)
    return _from_chunks(out, q.shape[1]), state


def _chunked_vjp(q, k, v, g, beta, d_out, d_state, *, chunk, dtype):
    """The gradients of ``_chunked``'s five inputs from the cotangents
    of its ``(o, S_last)``. The chunks' matrices and the state each
    starts from are computed again, not kept."""
    f32 = jnp.float32
    s, dk_width = q.shape[1], q.shape[-1]
    x = _chunks(q, k, v, g, beta, chunk, dtype)
    starts = _starts(x, dtype)
    d_out = _to_chunks(d_out, chunk).astype(dtype)
    s0 = starts.astype(dtype)
    step_size = x.beta[..., None]
    u = x.uv - _dot(x.w, s0)
    ub = (step_size * u).astype(dtype)
    # the reverse scan carries dS: what of dUb and dS0 needs the chunk
    # after, and what of dk and dG comes through the chunk's last state
    d_ub = _ein("...rc,...rv->...cv", x.p, d_out)

    def step(d_s1, x):
        w, k_end, q_decay, beta, decay_end, d_out, d_ub, ub, start = x
        d_s1d = d_s1.astype(dtype)
        d_ub = d_ub + _dot(k_end, d_s1d)
        d_s0 = (
            _ein("...ck,...cv->...kv", q_decay, d_out)
            + decay_end[..., None] * d_s1
            - _ein("...ck,...cv->...kv", w, (beta * d_ub).astype(dtype))
        )
        dk_end = _ein("...cv,...kv->...ck", ub, d_s1d)
        d_decay_end = jnp.sum(d_s1 * start.astype(f32), -1)
        return d_s0, (d_ub, dk_end, d_decay_end)

    _, (d_ub, dk_end, d_decay_end) = jax.lax.scan(
        step, d_state.astype(f32),
        (x.w, x.k_end, x.q_decay, step_size, x.decay_end, d_out, d_ub, ub,
         starts),
        reverse=True,
    )
    # and everything else, for all chunks at once
    d_u = (step_size * d_ub).astype(dtype)
    d_w = (-_ein("...cv,...kv->...ck", d_u, s0)).astype(dtype)
    inv = x.inv.astype(dtype)
    d_inv = _ein("...ik,...jk->...ij", d_w, x.k_decay) + _ein(
        "...iv,...jv->...ij", d_u, x.v
    )
    inv_t = jnp.swapaxes(x.inv, -1, -2)
    mm = functools.partial(jnp.matmul, precision=HIGHEST)
    d_m = -jnp.tril(mm(mm(inv_t, d_inv), inv_t), -1)
    d_a = d_m * x.beta[..., None, :]
    d_p = jnp.tril(_ein("...rv,...cv->...rc", d_out, ub))
    dq, dk, d_gc = _chunk_matrices_vjp(x.q, x.k, x.gc, d_a, d_p, dtype)
    # and what the products with the state give q, k and G: a factor
    # exp(+-G) gives G what it gives its q or k, times that q or k
    dq_state = _ein("...cv,...kv->...ck", d_out, s0) * x.decay
    dk_state = _ein("...ji,...jk->...ik", inv, d_w) * x.decay
    dk_end *= x.to_end
    d_gc += x.q * dq_state + x.k * (dk_state - dk_end)
    # the chunk's last row besides, from exp(G_C) and k * exp(G_C - G)
    d_last = d_decay_end * x.decay_end + jnp.sum(x.k * dk_end, -2)
    d_g = jax.lax.cumsum(d_gc, d_gc.ndim - 2, reverse=True) + d_last[
        ..., None, :
    ]
    d_beta = jnp.sum(d_ub * u, -1) + jnp.sum(d_m * x.a, -2)
    d_v = _ein("...ji,...jv->...iv", inv, d_u)
    grads = (
        (dq + dq_state) * dk_width ** -0.5, dk + dk_state + dk_end, d_v, d_g,
        d_beta,
    )
    return tuple(
        _from_chunks(d, s).astype(t.dtype)
        for d, t in zip(grads, (q, k, v, g, beta))
    )

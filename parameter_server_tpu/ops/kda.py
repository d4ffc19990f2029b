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
once; a ``lax.scan`` over the chunks carries the state through three
small products a chunk.

No exponential here has a positive argument, whatever the decay:
``exp(G_i - G_j)`` with ``j <= i`` is formed as such inside blocks of
``SUB`` tokens (an elementwise product reduced over the channels), and
between blocks as ``exp(G_i - r) exp(r - G_j)`` around the later block's
first row ``r`` (``G_j >= r >= G_i``), so that a decay strong enough to
underflow ``exp(G)`` costs the terms that are zero anyway and no
overflow. In f32: ``g``, its sums and exponentials, ``beta``, the state
and what is added to it, ``A``, ``P`` and the solve. ``dtype`` (bf16 in
a bf16 model) is what the matrix products read.

Gradients are autodiff's. The two elementwise blocks are rematerialised
(``jax.checkpoint``): their residuals would be ``SUB`` times the inputs.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

# tokens of a block inside which exp(G_i - G_j) is formed pair by pair
SUB = 16
HIGHEST = jax.lax.Precision.HIGHEST
# what the chunked scan carries its state in from chunk to chunk
STATE_DTYPE = jnp.float32
# heads computed at a time, at most: what the backward pass keeps of a
# pass is a few dozen times its inputs. At 8,192 tokens, 64 heads of 128
# a training step plans 12.9 GB of temporaries with all 64 at once and
# 8.8 GB with 16 (compiled for a v5e, PR 33): beside 5.2 GB of f32
# weights only the second fits 16.9 GB
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


@jax.checkpoint
def _within_blocks(q, k, gc):
    """``(A, P)`` [..., SUB, SUB] f32 of blocks q, k, gc [..., SUB, K]
    (gc the running sum of g): every pair's decay formed as such."""
    decay = jnp.exp(
        jnp.minimum(gc[..., :, None, :] - gc[..., None, :, :], 0.0)
    )
    kj = k[..., None, :, :] * decay
    return (
        jnp.sum(k[..., :, None, :] * kj, -1),
        jnp.sum(q[..., :, None, :] * kj, -1),
    )


@functools.partial(jax.checkpoint, static_argnums=(5,))
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

    The heads are independent: they run at most ``HEADS_PER_PASS`` at a
    time (the largest divisor of H that is no more), each pass
    rematerialised, so that only one pass's residuals are alive."""
    dtype = q.dtype if dtype is None else dtype
    if chunk & (chunk - 1) or (chunk > SUB and chunk % SUB):
        raise ValueError(f"chunk {chunk}: a power of two")
    one = functools.partial(_chunked, chunk=chunk, dtype=dtype)
    h = q.shape[2]
    hp = max(n for n in range(1, min(h, HEADS_PER_PASS) + 1) if h % n == 0)
    if hp == h:
        return one(q, k, v, g, beta)

    def passes(t):  # [B, S, H, ...] -> [H / hp, B, S, hp, ...]
        t = t.reshape(*t.shape[:2], h // hp, hp, *t.shape[3:])
        return jnp.moveaxis(t, 2, 0)

    out, state = jax.lax.map(
        jax.checkpoint(lambda x: one(*x)),
        tuple(map(passes, (q, k, v, g, beta))),
    )  # [H / hp, B, S, hp, V], [H / hp, B, hp, K, V]
    return (
        jnp.moveaxis(out, 0, 2).reshape(*q.shape[:3], v.shape[-1]),
        jnp.moveaxis(state, 0, 1).reshape(q.shape[0], h, *state.shape[3:]),
    )


def _chunked(q, k, v, g, beta, *, chunk, dtype):
    b, s, h, dk = q.shape
    dv = v.shape[-1]
    n = -(-s // chunk)

    def chunks(t):  # [B, S, H, ...] -> [N, B, H, C, ...] f32
        t = t.astype(jnp.float32)
        t = jnp.pad(t, ((0, 0), (0, n * chunk - s)) + ((0, 0),) * (t.ndim - 2))
        t = t.reshape(b, n, chunk, *t.shape[2:])
        return jnp.moveaxis(jnp.moveaxis(t, 1, 0), 2, 3)

    q, k, v, g = chunks(q * dk ** -0.5), chunks(k), chunks(v), chunks(g)
    beta = chunks(beta)  # [N, B, H, C]
    gc = jnp.cumsum(g, axis=-2)
    a, p = _chunk_matrices(q, k, gc, dtype)
    inv = unit_lower_inverse(a * beta[..., None, :]).astype(dtype)
    decay = jnp.exp(gc)
    last = gc[..., -1:, :]
    dot = functools.partial(jnp.matmul, preferred_element_type=jnp.float32)
    w = dot(inv, (k * decay).astype(dtype)).astype(dtype)
    uv = dot(inv, v.astype(dtype))
    xs = (
        w, uv, (q * decay).astype(dtype), p.astype(dtype),
        (k * jnp.exp(last - gc)).astype(dtype), beta,
        jnp.exp(last[..., 0, :]),
    )

    def step(state, x):
        w, uv, qg, p, k_end, beta, decay_end = x
        s0 = state.astype(dtype)
        ub = (beta[..., None] * (uv - dot(w, s0))).astype(dtype)
        out = dot(qg, s0) + dot(p, ub)
        state = decay_end[..., None] * state + dot(
            jnp.swapaxes(k_end, -1, -2), ub
        )
        return state.astype(STATE_DTYPE), out.astype(dtype)

    state = jnp.zeros((b, h, dk, dv), STATE_DTYPE)
    state, out = jax.lax.scan(step, state, xs)  # [N, B, H, C, V]
    out = jnp.moveaxis(jnp.moveaxis(out, 3, 2), 0, 1)
    return out.reshape(b, n * chunk, h, dv)[:, :s], state

"""Sequence-parallel attention schedules over sharded inputs.

Long-context support is first-class in this framework: sequences longer
than one chip's memory are sharded over a mesh axis and attention runs
blockwise. Two schedules, each exact (matches dense attention to float
tolerance):

- :func:`ring_attention` — K/V shards stream around the ICI ring
  (ppermute) while each device keeps a numerically-stable online-softmax
  accumulator; O(seq/n) memory per device. Chunk computes: ``impl="xla"``
  (materialized score block), ``impl="flash"`` (Pallas kernel, O(block)
  VMEM), ``impl="zigzag"`` (flash over the zigzag-permuted layout for
  balanced causal work per hop — see :func:`zigzag_permutation`).
- :func:`ulysses_attention` — all_to_all seq<->head reshard, dense (or
  flash) per-head attention, two collectives total.

Both accept ``window=`` (with the flash computes) for sliding-window
attention. ``ring_attention(q, k, v, mesh, axis)`` expects [B, S, H]
arrays sharded on S over ``axis``; causal masking accounts for the
global block offsets.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from ..parallel.ring import ring_next


def _block_attn(q, k, v, mask):
    """Scores for one (q-block, kv-block) pair: returns (scores, values)."""
    s = jnp.einsum("bqh,bkh->bqk", q, k) / jnp.sqrt(q.shape[-1])
    s = jnp.where(mask, s, -jnp.inf)
    return s


def _axis_index(axis: str, n: int):
    """This device's place on ``axis``. On a one-device axis it is the
    Python int 0, so that what is computed from it (a chunk's global
    offsets) is known while the program is traced: the flash kernels then
    list exactly the blocks their mask keeps."""
    return 0 if n == 1 else jax.lax.axis_index(axis)


def _ring_hops(k, v, axis: str, n: int):
    """Yield ``(kb, vb, src)`` for each of the n ring hops: the K/V chunk
    currently held and WHICH device's shard it is. The single home of the
    schedule invariant — ``ring_next``'s ppermute shifts blocks forward,
    so the held chunk's source index DEcrements — shared by both
    ring-attention impls so their causal offsets cannot desynchronize."""
    src = _axis_index(axis, n)
    kb, vb = k, v
    for step in range(n):
        yield kb, vb, src
        if step + 1 < n:
            kb = ring_next(kb, axis)
            vb = ring_next(vb, axis)
            src = (src - 1) % n


@functools.partial(
    jax.jit,
    static_argnames=(
        "mesh", "axis", "causal", "impl", "use_pallas", "interpret", "window",
    ),
)
def ring_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    mesh: Mesh,
    axis: str = "data",
    causal: bool = False,
    impl: str = "xla",
    use_pallas=None,
    interpret=False,
    window=None,
) -> jax.Array:
    """Exact attention with S sharded over ``axis``. q,k,v: [B, S, H].

    ``impl="xla"`` materializes each visiting chunk's [s_loc, s_loc]
    score block (fine for moderate chunks); ``impl="flash"`` computes
    each chunk with the Pallas flash kernel (ops/flash_attention.py) —
    O(block) VMEM per chunk — and merges chunks by logsumexp, so BOTH
    levels of the blocking (across devices and within a chunk) stream.
    ``impl="zigzag"`` is flash over the zigzag-permuted layout
    (:func:`zigzag_permutation`) — balanced causal work per ring hop;
    inputs and outputs must already be in that layout.
    """
    if impl == "flash":
        return _ring_attention_flash(
            q, k, v, mesh=mesh, axis=axis, causal=causal,
            use_pallas=use_pallas, interpret=interpret, window=window,
        )
    if impl == "zigzag":
        return _ring_attention_zigzag(
            q, k, v, mesh=mesh, axis=axis, causal=causal,
            use_pallas=use_pallas, interpret=interpret, window=window,
        )
    if impl != "xla":
        raise ValueError(
            f"ring_attention impl must be 'xla', 'flash' or 'zigzag', got "
            f"{impl!r} — all are exact, so a silent fallback would hide "
            "the memory profile choice"
        )
    if window is not None:
        raise ValueError(
            "window (sliding-window attention) is implemented by the "
            "flash kernels — use impl='flash' or 'zigzag'"
        )
    if use_pallas is not None or interpret:
        raise ValueError(
            "use_pallas/interpret only apply to impl='flash'/'zigzag'; "
            "the xla impl would silently ignore them (and you would "
            "believe you benchmarked the Pallas kernel)"
        )
    n = mesh.shape[axis]

    def local(q, k, v):
        b, s_loc, h = q.shape
        my = jax.lax.axis_index(axis)
        # online softmax accumulators
        acc = jnp.zeros((b, s_loc, h), jnp.float32)
        row_max = jnp.full((b, s_loc), -jnp.inf, jnp.float32)
        row_sum = jnp.zeros((b, s_loc), jnp.float32)
        q_pos = my * s_loc + jnp.arange(s_loc)
        for kb, vb, src in _ring_hops(k, v, axis, n):
            k_pos = src * s_loc + jnp.arange(s_loc)
            if causal:
                mask = q_pos[:, None] >= k_pos[None, :]
            else:
                mask = jnp.ones((s_loc, s_loc), bool)
            scores = _block_attn(q, kb, vb, mask[None, :, :])
            blk_max = jnp.max(scores, axis=-1)
            new_max = jnp.maximum(row_max, blk_max)
            # guard fully-masked rows (all -inf)
            safe_max = jnp.where(jnp.isinf(new_max), 0.0, new_max)
            p = jnp.exp(scores - safe_max[..., None])
            p = jnp.where(jnp.isinf(scores), 0.0, p)
            correction = jnp.where(
                jnp.isinf(row_max), 0.0, jnp.exp(row_max - safe_max)
            )
            acc = acc * correction[..., None] + jnp.einsum("bqk,bkh->bqh", p, vb)
            row_sum = row_sum * correction + jnp.sum(p, axis=-1)
            row_max = new_max
        out = acc / jnp.maximum(row_sum, 1e-30)[..., None]
        return out.astype(q.dtype)

    spec = P(None, axis, None)
    return shard_map(
        local, mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec, check_vma=False
    )(q, k, v)


def _merge_chunk(out, lse, out_i, lse_i):
    """Exact combination of two normalized partial-attention results via
    their logsumexps (the FlashAttention-2 chunk merge): order-invariant,
    and a fully-masked chunk (lse_i ~ -1e30) contributes weight 0."""
    new_lse = jnp.logaddexp(lse, lse_i)
    w_old = jnp.exp(lse - new_lse)
    w_new = jnp.exp(lse_i - new_lse)
    out = out * w_old[..., None] + out_i.astype(jnp.float32) * w_new[..., None]
    return out, new_lse


def _ring_attention_flash(q, k, v, *, mesh, axis, causal, use_pallas,
                          interpret, window=None):
    """Ring schedule with the Pallas flash kernel as the chunk compute.

    Each hop produces a NORMALIZED chunk output plus its logsumexp; two
    chunks merge exactly via softmax-of-lse weights (the FlashAttention-2
    chunk combination), so the result matches dense attention to float
    tolerance regardless of hop order."""
    from ..ops.flash_attention import flash_attention

    n = mesh.shape[axis]

    def local(q, k, v):
        b, s_loc, h = q.shape
        my = _axis_index(axis, n)
        out = jnp.zeros((b, s_loc, h), jnp.float32)
        lse = jnp.full((b, s_loc), -1e30, jnp.float32)
        for kb, vb, src in _ring_hops(k, v, axis, n):
            out_i, lse_i = flash_attention(
                q, kb, vb, causal=causal,
                q_offset=my * s_loc, k_offset=src * s_loc,
                use_pallas=use_pallas, interpret=interpret, with_lse=True,
                window=window,
            )
            out, lse = _merge_chunk(out, lse, out_i, lse_i)
        return out.astype(q.dtype)

    spec = P(None, axis, None)
    return shard_map(
        local, mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
        check_vma=False,
    )(q, k, v)


def zigzag_permutation(seq_len: int, n: int) -> np.ndarray:
    """Token permutation for the zigzag causal layout: the sequence is
    split into 2n half-blocks and device i holds half-blocks
    ``(i, 2n-1-i)``. ``x[:, perm]`` re-orders a natural-layout sequence
    so a plain ``P(axis)`` sharding lands those pairs on device i;
    ``argsort(perm)`` inverts. Why: under the contiguous causal layout
    device 0's queries precede almost every visiting K/V chunk, so it
    skips most hops while the last device computes on all of them — the
    hop wall-clock is set by the busiest device. Pairing the i-th and
    (2n-1-i)-th half-blocks gives every device the same causal workload
    per hop (the zigzag schedule of Brandon et al.'s striped-attention
    line of work), while K/V still streams over the same ICI ring."""
    if seq_len % (2 * n):
        raise ValueError(f"seq_len {seq_len} must divide by 2n={2 * n}")
    h = seq_len // (2 * n)
    blocks = []
    for i in range(n):
        blocks.append(np.arange(i * h, (i + 1) * h))
        blocks.append(np.arange((2 * n - 1 - i) * h, (2 * n - i) * h))
    return np.concatenate(blocks)


def _ring_attention_zigzag(q, k, v, *, mesh, axis, causal, use_pallas,
                           interpret, window=None):
    """Ring attention over ZIGZAG-sharded inputs (see
    :func:`zigzag_permutation` — inputs/outputs are in the permuted
    layout). Each device holds two half-blocks with different global
    offsets, so every hop runs four half×half flash calls (q half × kv
    half) with the right offset pairs and merges by logsumexp; the
    kernel's causal block-skip makes the fully-masked combinations
    cheap. Exact for causal and non-causal alike."""
    from ..ops.flash_attention import flash_attention

    n = mesh.shape[axis]

    def local(q, k, v):
        b, s_loc, h_feat = q.shape
        if s_loc % 2:
            raise ValueError(
                f"zigzag needs an even per-device sequence length, got "
                f"{s_loc} — shard a seq divisible by 2*{n} (see "
                "zigzag_permutation)"
            )
        half = s_loc // 2
        my = _axis_index(axis, n)
        q_halves = (q[:, :half], q[:, half:])
        q_offs = (my * half, (2 * n - 1 - my) * half)
        outs = [jnp.zeros((b, half, h_feat), jnp.float32) for _ in range(2)]
        lses = [jnp.full((b, half), -1e30, jnp.float32) for _ in range(2)]
        for kb, vb, src in _ring_hops(k, v, axis, n):
            kv_halves = ((kb[:, :half], vb[:, :half]), (kb[:, half:], vb[:, half:]))
            kv_offs = (src * half, (2 * n - 1 - src) * half)
            for qi in range(2):
                for ki in range(2):
                    out_i, lse_i = flash_attention(
                        q_halves[qi], kv_halves[ki][0], kv_halves[ki][1],
                        causal=causal,
                        q_offset=q_offs[qi], k_offset=kv_offs[ki],
                        use_pallas=use_pallas, interpret=interpret,
                        with_lse=True, window=window,
                    )
                    outs[qi], lses[qi] = _merge_chunk(
                        outs[qi], lses[qi], out_i, lse_i
                    )
        return jnp.concatenate(outs, axis=1).astype(q.dtype)

    spec = P(None, axis, None)
    return shard_map(
        local, mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
        check_vma=False,
    )(q, k, v)


def dense_attention(q, k, v, causal: bool = False):
    """Reference implementation for tests."""
    s = jnp.einsum("bqh,bkh->bqk", q, k) / jnp.sqrt(q.shape[-1])
    if causal:
        n = q.shape[1]
        mask = jnp.tril(jnp.ones((n, n), bool))
        s = jnp.where(mask[None], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bqk,bkh->bqh", p, v)


def dense_mha(q, k, v, n_heads: int, causal: bool = False):
    """Multi-head reference: [B, S, H] with H = n_heads * dh."""
    b, s, h = q.shape
    dh = h // n_heads

    def split(x):
        return x.reshape(b, s, n_heads, dh).transpose(0, 2, 1, 3)

    qh, kh, vh = split(q), split(k), split(v)
    scores = jnp.einsum("bnqd,bnkd->bnqk", qh, kh) / jnp.sqrt(dh)
    if causal:
        mask = jnp.tril(jnp.ones((s, s), bool))
        scores = jnp.where(mask[None, None], scores, -jnp.inf)
    p = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bnqk,bnkd->bnqd", p, vh)
    return out.transpose(0, 2, 1, 3).reshape(b, s, h)


@functools.partial(
    jax.jit,
    static_argnames=(
        "mesh", "axis", "n_heads", "causal", "impl", "use_pallas",
        "interpret", "window",
    ),
)
def ulysses_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    mesh: Mesh,
    axis: str = "data",
    n_heads: int,
    causal: bool = False,
    impl: str = "xla",
    use_pallas=None,
    interpret=False,
    window=None,
) -> jax.Array:
    """All-to-all (DeepSpeed-Ulysses-style) sequence parallelism: the
    complement of :func:`ring_attention` for long sequences.

    Inputs arrive sequence-sharded ([B, S, H] with S over ``axis``); one
    all_to_all re-shards to HEAD-sharded (each device owns n_heads/n full
    -sequence heads), attention runs densely per local head — a single
    big MXU matmul instead of a ring of n block steps — and a second
    all_to_all restores sequence sharding. Two collectives total (vs n-1
    ppermutes): cheaper when heads divide evenly and the full sequence's
    scores fit on-chip; ring wins when S^2 memory must stay blocked —
    unless ``impl="flash"``, which runs the per-head full-sequence
    attention through the Pallas flash kernel (O(block) VMEM), removing
    exactly that S^2 limit while keeping the two-collective schedule.
    """
    n = mesh.shape[axis]
    assert n_heads % n == 0, f"n_heads={n_heads} must divide by mesh axis {n}"
    if impl not in ("xla", "flash"):
        raise ValueError(
            f"ulysses_attention impl must be 'xla' or 'flash', got {impl!r}"
        )
    if impl == "xla" and (use_pallas is not None or interpret):
        raise ValueError(
            "use_pallas/interpret only apply to impl='flash'; the xla "
            "impl would silently ignore them"
        )
    if window is not None and impl != "flash":
        raise ValueError(
            "window (sliding-window attention) is implemented by the "
            "flash kernel — use impl='flash'"
        )

    def local(q, k, v):
        b, s_loc, h = q.shape
        dh = h // n_heads

        def to_heads(x):
            # [B, s_loc, H] -> [B, s_loc, nh, dh] -> a2a: scatter heads,
            # gather sequence -> [B, S, nh/n, dh]
            x = x.reshape(b, s_loc, n_heads, dh)
            return jax.lax.all_to_all(
                x, axis, split_axis=2, concat_axis=1, tiled=True
            )

        qh, kh, vh = to_heads(q), to_heads(k), to_heads(v)  # [B, S, nh/n, dh]
        s_full = qh.shape[1]
        nh_loc = qh.shape[2]
        if impl == "flash":
            from ..ops.flash_attention import flash_attention

            def to_bh(x):  # [B, S, nh/n, dh] -> [B*nh/n, S, dh]
                return x.transpose(0, 2, 1, 3).reshape(b * nh_loc, s_full, dh)

            out = flash_attention(
                to_bh(qh), to_bh(kh), to_bh(vh), causal=causal,
                use_pallas=use_pallas, interpret=interpret, window=window,
            )
            out = out.reshape(b, nh_loc, s_full, dh).transpose(0, 2, 1, 3)
        else:
            scores = jnp.einsum("bqnd,bknd->bnqk", qh, kh) / jnp.sqrt(dh)
            if causal:
                mask = jnp.tril(jnp.ones((s_full, s_full), bool))
                scores = jnp.where(mask[None, None], scores, -jnp.inf)
            p = jax.nn.softmax(scores, axis=-1)
            out = jnp.einsum("bnqk,bknd->bqnd", p, vh)  # [B, S, nh/n, dh]
        # inverse a2a: scatter sequence, gather heads
        out = jax.lax.all_to_all(
            out, axis, split_axis=1, concat_axis=2, tiled=True
        )
        return out.reshape(b, s_loc, h)

    spec = P(None, axis, None)
    return shard_map(
        local, mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
        check_vma=False,
    )(q, k, v)

"""Compact host→device wire: encoded batch buffers for the upload path.

Where the host→device link binds, bytes/example × link MB/s is the
throughput ceiling (``wire_bytes_per_example`` in the benchmark's
per-layer metrics). This module is the host half of the compact wire: the
ingest pipeline's prep stage emits *encoded* batch buffers, the jitted
train step decodes them on device (ops/wire_codec.py), and decoded
batches never cross the link.

It is the upload-path realization of the reference's wire filter stack
(src/filter/): each encoding below names the filter whose byte-economy
it transplants from the server wire onto the host→device leg —

- **bit-packed indices** (``ucols``/``uslots`` at ceil(log2 S) bits,
  utils/bitpack.py): the key-stream analog of fixing_float's
  fixed-width values.
- **delta-coded sorted slot arrays** (``uslots`` is np.unique output —
  strictly increasing — so gaps fit u16 and the device reconstructs
  with one exact int32 cumsum): the compressing filter's instinct,
  restricted to a transform XLA can invert.
- **structure elision** (mask → live-row count, COO row ids → per-row
  feature counts, binary values → nothing, ±1 labels → sign bits): the
  sparse filter's drop-what-reconstructs rule.
- **fixed-point / bf16 values** (``wire_encode='int8'|'u16'|'bf16'``,
  filter/fixing_float quantize): the FIXING_FLOAT filter verbatim —
  lossy, stochastic-rounded, gated behind config with a logloss-parity
  bound (tests/test_wire.py).
- **key caching** (:class:`UploadCache`): a repeated array uploads only
  its crc32c signature — filter/key_caching.py semantics (signature
  routes, exact verify against a retained copy decides, same
  ``MAX_SIG_LEN`` prefix budget) with the device-resident buffer as the
  receiver's cache. Multi-epoch passes and eval/replay loops re-ship
  ~nothing.

The default ``exact`` mode is **lossless and bit-identical**: every
encoder VERIFIES its domain assumptions on the actual batch (and
returns None so the caller falls back to the raw wire when they fail),
so decode-on-device reproduces the unencoded stream bit-for-bit —
parity-tested like PR 3's ingest contract.

Concurrency contract (the PR-3 ingest determinism rule): ``encode_*``
are STATELESS and deterministic — pool-able prep stages.
:class:`UploadCache` is STATEFUL and single-owner: it must live on the
(serial) uploader thread, never in the prep pool; it asserts its owner
thread at every call.

``MessageWireCodec`` drives the actual host-side FilterChain
(filter/base.py: compressing → key_caching → fixing_float, decode in
reverse) over batch payloads for the host↔host legs (multi-host ingest
hand-off, replay spill) and for chain round-trip tests — on the
host→device leg the chain's transforms are realized by the jit-side
decode ops instead, which is what keeps the decode inside the step.
"""
# bit-identical: this module is under the replay bit-identity contract (pslint determinism pass)

from __future__ import annotations

import dataclasses
import threading
import time
from collections import OrderedDict
from typing import List, Optional, Tuple

import jax
import numpy as np

from ..filter.fixing_float import quantize
from ..system.message import FilterSpec, Message, Task
from ..utils import crc32c
from ..utils.bitpack import pack_bits, slot_bits, stream_to_words

# the key-caching filter's signature prefix budget — one constant,
# shared semantics (filter/key_caching.py, parameter.KeyDirectory)
MAX_SIG_LEN = 2048

#: value-stream encodings: mode -> (code dtype, fixing_float num_bytes)
_QUANT_MODES = {"int8": (np.uint8, 1), "u16": (np.uint16, 2)}
WIRE_ENCODE_MODES = ("", "exact", "int8", "u16", "bf16")


def wire_instruments():
    """ps_wire_* instruments against the process registry, or None while
    telemetry is disabled. Cached per registry (the encode runs once
    per batch on every prep-pool worker — telemetry.instruments owns
    the one hot-path cache, same shape as cached_kvops_instruments)."""
    from ..telemetry.instruments import cached_wire_instruments

    return cached_wire_instruments()


def tree_nbytes(tree) -> int:
    """Host bytes of a (possibly encoded) batch tree — what would cross
    the link if uploaded as-is."""
    return int(
        sum(getattr(leaf, "nbytes", 0) for leaf in jax.tree.leaves(tree))
    )


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class EncodedExactBatch:
    """PreppedBatch on the compact wire (fields [D, ...] per data shard).

    Static fields pin the decode program (jit keys on them); array
    fields are exactly what crosses the link. ``y`` is sign bits
    (uint8 [D, ceil(R/8)]) when ``y_sign`` else raw float32 [D, R];
    ``uslots`` is a u16 gap stream when ``uslots_delta`` else a
    ceil(log2 S+1)-bit word stream; ``vals`` is absent for binary
    batches, float32 for exact valued ones, u8/u16 codes (+ per-shard
    ``vals_lo``/``vals_hi``) for fixed-point, bfloat16 for bf16."""

    y: np.ndarray
    counts: np.ndarray  # [D] int32 live rows
    row_counts: np.ndarray  # [D, R] u8/u16 features per row
    nnz: np.ndarray  # [D] int32 live COO entries
    ucols_words: np.ndarray  # [D, W] uint32 bit-packed ucols
    uslots: np.ndarray  # [D, U] u16 deltas | [D, W2] uint32 words
    n_uniq: np.ndarray  # [D] int32 live unique slots
    vals: Optional[np.ndarray]
    vals_lo: Optional[np.ndarray]  # [D] float32 (fixed-point modes)
    vals_hi: Optional[np.ndarray]
    rows_pad: int = dataclasses.field(metadata=dict(static=True), default=0)
    nnz_pad: int = dataclasses.field(metadata=dict(static=True), default=0)
    uniq_pad: int = dataclasses.field(metadata=dict(static=True), default=0)
    ucols_bits: int = dataclasses.field(metadata=dict(static=True), default=0)
    uslots_bits: int = dataclasses.field(metadata=dict(static=True), default=0)
    y_sign: bool = dataclasses.field(metadata=dict(static=True), default=False)
    uslots_delta: bool = dataclasses.field(
        metadata=dict(static=True), default=True
    )
    vals_mode: str = dataclasses.field(
        metadata=dict(static=True), default="binary"
    )

    @property
    def num_examples(self) -> int:
        return int(np.asarray(self.counts).sum())

    def static_key(self) -> tuple:
        """The decode-program cache key (everything jit specializes on)."""
        return (
            self.rows_pad, self.nnz_pad, self.uniq_pad, self.ucols_bits,
            self.uslots_bits, self.y_sign, self.uslots_delta, self.vals_mode,
        )


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class EncodedExactSuperBatch:
    """T stacked EncodedExactBatches (fields [T, D, ...]) — the compact
    wire's scan superbatch: one launch decodes and runs T sequential
    ministeps (the PreppedSuperBatch twin)."""

    y: np.ndarray
    counts: np.ndarray
    row_counts: np.ndarray
    nnz: np.ndarray
    ucols_words: np.ndarray
    uslots: np.ndarray
    n_uniq: np.ndarray
    vals: Optional[np.ndarray]
    vals_lo: Optional[np.ndarray]
    vals_hi: Optional[np.ndarray]
    rows_pad: int = dataclasses.field(metadata=dict(static=True), default=0)
    nnz_pad: int = dataclasses.field(metadata=dict(static=True), default=0)
    uniq_pad: int = dataclasses.field(metadata=dict(static=True), default=0)
    ucols_bits: int = dataclasses.field(metadata=dict(static=True), default=0)
    uslots_bits: int = dataclasses.field(metadata=dict(static=True), default=0)
    y_sign: bool = dataclasses.field(metadata=dict(static=True), default=False)
    uslots_delta: bool = dataclasses.field(
        metadata=dict(static=True), default=True
    )
    vals_mode: str = dataclasses.field(
        metadata=dict(static=True), default="binary"
    )

    @property
    def steps(self) -> int:
        return int(self.counts.shape[0])

    @property
    def num_examples(self) -> int:
        return int(np.asarray(self.counts).sum())

    def static_key(self) -> tuple:
        return (
            self.rows_pad, self.nnz_pad, self.uniq_pad, self.ucols_bits,
            self.uslots_bits, self.y_sign, self.uslots_delta, self.vals_mode,
        )


def stack_encoded_batches(
    parts: List[EncodedExactBatch],
) -> EncodedExactSuperBatch:
    """Stack T encoded minibatches into one scan superbatch. Statics
    must agree across T (they pin ONE decode program)."""
    if not parts:
        raise ValueError("empty superbatch")
    key = parts[0].static_key()
    assert all(p.static_key() == key for p in parts), (
        "encoded superbatch needs uniform static encoding parameters"
    )
    opt = lambda name: (  # noqa: E731
        None
        if getattr(parts[0], name) is None
        else np.stack([getattr(p, name) for p in parts])
    )
    return EncodedExactSuperBatch(
        y=np.stack([p.y for p in parts]),
        counts=np.stack([p.counts for p in parts]),
        row_counts=np.stack([p.row_counts for p in parts]),
        nnz=np.stack([p.nnz for p in parts]),
        ucols_words=np.stack([p.ucols_words for p in parts]),
        uslots=np.stack([p.uslots for p in parts]),
        n_uniq=np.stack([p.n_uniq for p in parts]),
        vals=opt("vals"),
        vals_lo=opt("vals_lo"),
        vals_hi=opt("vals_hi"),
        rows_pad=parts[0].rows_pad,
        nnz_pad=parts[0].nnz_pad,
        uniq_pad=parts[0].uniq_pad,
        ucols_bits=parts[0].ucols_bits,
        uslots_bits=parts[0].uslots_bits,
        y_sign=parts[0].y_sign,
        uslots_delta=parts[0].uslots_delta,
        vals_mode=parts[0].vals_mode,
    )


def _derived_nnz(p) -> np.ndarray:
    """Live COO entries per shard: the index past the last entry where
    anything is nonzero. Entries beyond the true nnz are all-zero by
    construction (prep zero-pads rows/ucols/vals), and an interior
    all-zero entry reconstructs to the same zeros either way, so this
    bound is exact for bit-identical decode."""
    live = (
        (np.asarray(p.rows) != 0)
        | (np.asarray(p.ucols) != 0)
        | (np.asarray(p.vals) != 0)
    )
    nz = p.rows.shape[1]
    rev = live[:, ::-1]
    any_live = rev.any(axis=1)
    return np.where(any_live, nz - rev.argmax(axis=1), 0).astype(np.int32)


def _quantize_vals(
    vals: np.ndarray, nnz: np.ndarray, mode: str
) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Per-shard fixed-point encode with a DETERMINISTIC, content-keyed
    rounding stream: the prep pool may encode batches in any order, and
    the ingest contract requires the emitted stream to be independent
    of worker interleaving — so the stochastic-rounding rng is seeded
    from the shard's own bytes, never from shared mutable state.

    Only the LIVE entries (``[:nnz]``) are quantized — the [lo, hi]
    scale must not be widened (and resolution wasted) by the zero
    padding, and padding codes are meaningless anyway: the device
    decode masks everything past ``nnz`` back to the raw wire's exact
    0.0 (a dequantized zero is 0±step noise that would otherwise
    scatter-add a padding-sized bias into row 0 / uslots[0])."""
    dt, num_bytes = _QUANT_MODES[mode]
    q = np.zeros(vals.shape, dtype=dt)
    lo = np.zeros(vals.shape[0], np.float32)
    hi = np.ones(vals.shape[0], np.float32)
    for d in range(vals.shape[0]):
        n = int(nnz[d])
        if n == 0:
            continue
        rng = np.random.default_rng(crc32c.value(vals[d, :n].tobytes()))
        q[d, :n], lo[d], hi[d] = quantize(vals[d, :n], num_bytes, rng)
    return q, lo, hi


def encode_exact(
    prepped,
    num_slots: int,
    mode: str = "exact",
) -> Optional[EncodedExactBatch]:
    """Encode a PreppedBatch for the compact wire, or None when the
    batch falls outside an encoding's verified domain (caller ships the
    raw wire — never wrong bytes).

    STATELESS + deterministic (pool-able prep stage). ``mode``:
    ``"exact"`` is lossless/bit-identical; ``"int8"``/``"u16"``/
    ``"bf16"`` additionally narrow the value stream (lossy — config-
    gated behind a logloss-parity bound; binary batches have no value
    stream, so every mode is exact for them).

    With a span sink installed, the encode emits one ``wire.encode``
    timeline span carrying the active flow id."""
    from ..telemetry import spans as telemetry_spans

    if telemetry_spans.get_sink() is None:
        return _encode_exact_impl(prepped, num_slots, mode)
    with telemetry_spans.span("wire.encode", mode=mode):
        return _encode_exact_impl(prepped, num_slots, mode)


def _encode_exact_impl(
    prepped,
    num_slots: int,
    mode: str = "exact",
) -> Optional[EncodedExactBatch]:
    from ..apps.linear.async_sgd import PreppedBatch
    from ..ops.kv_ops import slot_sentinel

    if not isinstance(prepped, PreppedBatch):
        return None
    if mode not in WIRE_ENCODE_MODES or mode == "":
        raise ValueError(
            f"unknown wire_encode mode {mode!r}; expected one of "
            f"{WIRE_ENCODE_MODES[1:]}"
        )
    tel = wire_instruments()
    t0 = time.perf_counter()
    y = np.asarray(prepped.y)
    mask = np.asarray(prepped.mask)
    rows = np.asarray(prepped.rows)
    ucols = np.asarray(prepped.ucols)
    vals = np.asarray(prepped.vals)
    uslots = np.asarray(prepped.uslots)
    umask = np.asarray(prepped.umask)
    d_shards, rows_pad = y.shape
    nnz_pad = rows.shape[1]
    uniq_pad = uslots.shape[1]
    sentinel = slot_sentinel(num_slots)

    # -- verified structure elisions (each check is the exact domain of
    # its decode op; any failure → raw wire) --
    counts = mask.sum(axis=1).astype(np.int32)
    if not (mask == (np.arange(rows_pad) < counts[:, None])).all():
        return None
    n_uniq = umask.sum(axis=1).astype(np.int32)
    if not (umask == (np.arange(uniq_pad) < n_uniq[:, None])).all():
        return None
    nnz = _derived_nnz(prepped)
    live = np.arange(nnz_pad) < nnz[:, None]
    # rows must be the repeat(arange, counts) form — verified exactly,
    # per shard, below (bincount then reconstruct-and-compare)
    row_counts = np.zeros((d_shards, rows_pad), np.int64)
    for d in range(d_shards):
        if nnz[d] and rows[d, : nnz[d]].min() < 0:
            return None
        rc = np.bincount(rows[d, : nnz[d]], minlength=rows_pad)
        if rc.size > rows_pad:
            return None
        row_counts[d, : rc.size] = rc
        if not (
            rows[d, : nnz[d]]
            == np.repeat(np.arange(rows_pad), row_counts[d])
        ).all():
            return None
    rc_dtype = np.uint8 if row_counts.max(initial=0) < 256 else np.uint16
    if row_counts.max(initial=0) >= (1 << 16):
        return None

    # -- ucols: bit-packed at ceil(log2 uniq_pad) bits --
    ucols_bits = slot_bits(uniq_pad)
    if (ucols < 0).any() or (ucols >= uniq_pad).any():
        return None
    if (~live & (ucols != 0)).any():
        return None
    ucols_words = np.stack(
        [
            stream_to_words(pack_bits(ucols[d], ucols_bits), nnz_pad, ucols_bits)
            for d in range(d_shards)
        ]
    )

    # -- uslots: sorted unique slots (prep_batch_shared's np.unique
    # output) → u16 gap stream with the sentinel tail elided; unsorted
    # (prep_batch hashes sorted KEYS, so its slots arrive shuffled) or
    # wide-gapped arrays → ceil(log2 S+1)-bit packed words instead --
    if sentinel < 0 or num_slots >= (1 << 31):
        return None  # 2^31 tables use the -1 sentinel; keep the raw wire
    uslots_bits = slot_bits(num_slots, sentinel=True)
    ok_sorted = True
    deltas = np.zeros((d_shards, uniq_pad), np.int64)
    for d in range(d_shards):
        u = n_uniq[d]
        seg = uslots[d, :u].astype(np.int64)
        if (uslots[d, u:] != sentinel).any():
            return None
        if (seg < 0).any() or (seg >= num_slots).any():
            return None
        if u and ok_sorted:
            dd = np.diff(seg, prepend=0)
            if (dd[1:] <= 0).any() or dd.max(initial=0) >= (1 << 16):
                ok_sorted = False
            else:
                deltas[d, :u] = dd
    if ok_sorted:
        uslots_enc = deltas.astype(np.uint16)
        uslots_delta = True
    else:
        uslots_enc = np.stack(
            [
                stream_to_words(
                    pack_bits(uslots[d], uslots_bits), uniq_pad, uslots_bits
                )
                for d in range(d_shards)
            ]
        )
        uslots_delta = False

    # -- labels: sign bits when exactly ±1 on live rows, 0 on padding --
    y_sign = bool((np.abs(y) == mask).all())
    if y_sign:
        y_enc = np.stack(
            [np.packbits(y[d] > 0, bitorder="little") for d in range(d_shards)]
        )
    else:
        y_enc = y

    # -- values: elide (binary), narrow (quant modes), or ship f32 --
    vals_lo = vals_hi = None
    binary = bool((vals == live.astype(np.float32)).all())
    if binary:
        vals_enc, vals_mode = None, "binary"
    elif mode == "exact":
        vals_enc, vals_mode = vals, "f32"
    elif mode == "bf16":
        import ml_dtypes

        vals_enc, vals_mode = vals.astype(ml_dtypes.bfloat16), "bf16"
    else:
        vals_enc, vals_lo, vals_hi = _quantize_vals(vals, nnz, mode)
        vals_mode = mode

    out = EncodedExactBatch(
        y=y_enc,
        counts=counts,
        row_counts=row_counts.astype(rc_dtype),
        nnz=nnz,
        ucols_words=ucols_words,
        uslots=uslots_enc,
        n_uniq=n_uniq,
        vals=vals_enc,
        vals_lo=vals_lo,
        vals_hi=vals_hi,
        rows_pad=rows_pad,
        nnz_pad=nnz_pad,
        uniq_pad=uniq_pad,
        ucols_bits=ucols_bits,
        uslots_bits=uslots_bits,
        y_sign=y_sign,
        uslots_delta=uslots_delta,
        vals_mode=vals_mode,
    )
    if tel is not None:
        enc_b, raw_b = tree_nbytes(out), tree_nbytes(prepped)
        tel["encode_seconds"].observe(time.perf_counter() - t0)
        tel["bytes"].labels(encoding=mode).inc(enc_b)
        tel["saved_bytes"].labels(reason="encoding").inc(max(0, raw_b - enc_b))
    return out


def decode_exact_shard(enc, num_slots: int, d: int = None, *, _leaves=None):
    """Decode ONE data shard of an EncodedExactBatch with the REAL
    jit-side ops (ops/wire_codec) — the shared body the device step
    builders trace and the host parity oracle runs on CPU.

    Returns ``(y, mask, rows, ucols, vals, uslots, umask)`` shaped like
    one shard of the raw PreppedBatch. ``_leaves`` lets a traced caller
    pass already-sliced per-shard operands (inside shard_map the slicing
    happened outside); the host path slices shard ``d`` itself."""
    import jax.numpy as jnp

    from ..ops import wire_codec as wc
    from ..ops.kv_ops import slot_sentinel

    if _leaves is not None:
        y_e, count, row_counts, nnz, ucw, usl, n_uniq, vals, vlo, vhi = _leaves
    else:
        y_e, count, row_counts, nnz, ucw, usl, n_uniq = (
            enc.y[d], enc.counts[d], enc.row_counts[d], enc.nnz[d],
            enc.ucols_words[d], enc.uslots[d], enc.n_uniq[d],
        )
        vals = None if enc.vals is None else enc.vals[d]
        vlo = None if enc.vals_lo is None else enc.vals_lo[d]
        vhi = None if enc.vals_hi is None else enc.vals_hi[d]

    if enc.y_sign:
        y = wc.decode_sign_labels(y_e, count, enc.rows_pad)
    else:
        y = y_e
    mask = wc.decode_mask(count, enc.rows_pad)
    rows = wc.decode_row_ids(row_counts, nnz, enc.nnz_pad)
    ucols = wc.decode_bitstream(ucw, enc.nnz_pad, enc.ucols_bits)
    # the raw wire zero-pads ucols past nnz; the packed stream's tail
    # bits are zero too, but mask explicitly so the contract is local
    ucols = jnp.where(jnp.arange(enc.nnz_pad) < nnz, ucols, 0)
    if enc.uslots_delta:
        uslots = wc.decode_sorted_deltas(usl, n_uniq, slot_sentinel(num_slots))
    else:
        uslots = wc.decode_bitstream(usl, enc.uniq_pad, enc.uslots_bits)
    umask = wc.decode_mask(n_uniq, enc.uniq_pad)
    if enc.vals_mode == "binary":
        v = wc.decode_binary_vals(nnz, enc.nnz_pad)
    elif enc.vals_mode == "f32":
        v = vals
    elif enc.vals_mode == "bf16":
        v = wc.decode_bf16(vals)
    else:
        # mask the dequantized stream back to the raw wire's exact 0.0
        # past nnz: a dequantized zero code is 0±step noise, and every
        # padding entry carries rows=0/ucols=0 — unmasked they would
        # scatter-add a padding-sized bias into row 0 and uslots[0]
        # (f32/bf16/binary are safe: 0.0 round-trips exactly there)
        v = jnp.where(
            jnp.arange(enc.nnz_pad) < nnz,
            wc.decode_fixed_point(
                vals, vlo, vhi, _QUANT_MODES[enc.vals_mode][1]
            ),
            0.0,
        )
    return y, mask, rows, ucols, v, uslots, umask


def decode_exact_host(enc: EncodedExactBatch, num_slots: int) -> tuple:
    """Host parity oracle: decode every shard on CPU and stack — shaped
    exactly like the raw PreppedBatch fields
    ``(y, mask, rows, ucols, vals, uslots, umask)``."""
    if isinstance(enc, EncodedExactSuperBatch):
        raise ValueError("host oracle decodes per-minibatch; index T first")
    parts = [
        tuple(
            np.asarray(x)
            for x in decode_exact_shard(enc, num_slots, d)
        )
        for d in range(enc.counts.shape[0])
    ]
    return tuple(np.stack(x) for x in zip(*parts))


# ---------------------------------------------------------------------------
# Stream-once lane-dictionary wire (the cache-free encoding).
#
# The UploadCache amortizes repeated traffic, but the production CTR
# shape — stream a multi-GB criteo file ONCE — repeats nothing, so the
# bits wire's ceil(log2 S) bits/feature stood as the recorded
# 126.9 B/example upload bound. The exploitable structure that survives
# the hash is per-FIELD: a lane whose per-batch vocabulary is small
# (criteo's 13 integer count fields hash to ~90 distinct slots per 16k
# batch) ships a per-lane sorted unique-slot table (``uslots``) plus
# bit-packed per-row table indices (``ucols``) at ~7 bits instead of
# 26, while high-vocabulary lanes (hashed categorical tokens, ~98%
# unique per batch — incompressible past the hash; delta-coding the
# global unique-slot set was measured and LOSES at ≥60% unique) keep
# the raw bit stream. Measured on the criteo-law shape: 96.4 B/example
# at 2^26 slots vs the 126.9 raw-bits baseline, no cache anywhere.
#
# Statics (which lanes take the dictionary, the shared code width, the
# table capacity) are derived once from the worker's first batch
# (`derive_stream_statics`, the `_padding` pattern) and pinned: encode
# itself stays STATELESS (pool-able — the PR-3 ingest rule) and
# VERIFIES each batch fits the pinned statics, returning None so the
# caller ships the raw bits wire when it doesn't — never wrong bytes.
# ---------------------------------------------------------------------------


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class EncodedEllStreamBatch:
    """ELL batch on the stream-once lane-dictionary wire (fields
    [D, ...] per data shard). ``raw_words`` is the row-major bit stream
    of the raw lanes at ``raw_bits`` each; ``code_words`` the row-major
    dictionary codes (``ucols``) of the dict lanes at ``code_bits``;
    ``table_words`` the concatenated per-lane sorted unique slots
    (``uslots``) at ``raw_bits``, ``lane_starts`` their start offsets.
    Bits past each live prefix are zero; garbage decodes on padding
    rows are gated by the row mask exactly like the bits wire."""

    y_bits: np.ndarray  # [D, ceil(R/8)] uint8 little-endian sign bits
    counts: np.ndarray  # [D] int32 live rows
    raw_words: np.ndarray  # [D, Wr] uint32
    code_words: np.ndarray  # [D, Wc] uint32
    table_words: np.ndarray  # [D, Wt] uint32
    lane_starts: np.ndarray  # [D, n_dict] int32
    n_uniq: np.ndarray  # [D] int32 live table entries
    rows: int = dataclasses.field(metadata=dict(static=True), default=0)
    lanes: int = dataclasses.field(metadata=dict(static=True), default=0)
    dict_lanes: tuple = dataclasses.field(
        metadata=dict(static=True), default=()
    )
    code_bits: int = dataclasses.field(metadata=dict(static=True), default=0)
    dict_pad: int = dataclasses.field(metadata=dict(static=True), default=0)
    raw_bits: int = dataclasses.field(metadata=dict(static=True), default=0)

    @property
    def num_examples(self) -> int:
        return int(np.asarray(self.counts).sum())

    def static_key(self) -> tuple:
        return (
            self.rows, self.lanes, self.dict_lanes, self.code_bits,
            self.dict_pad, self.raw_bits,
        )


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class EncodedEllStreamSuperBatch:
    """T stacked EncodedEllStreamBatches (fields [T, D, ...]) — one
    scan launch decodes and runs T sequential ministeps."""

    y_bits: np.ndarray
    counts: np.ndarray
    raw_words: np.ndarray
    code_words: np.ndarray
    table_words: np.ndarray
    lane_starts: np.ndarray
    n_uniq: np.ndarray
    rows: int = dataclasses.field(metadata=dict(static=True), default=0)
    lanes: int = dataclasses.field(metadata=dict(static=True), default=0)
    dict_lanes: tuple = dataclasses.field(
        metadata=dict(static=True), default=()
    )
    code_bits: int = dataclasses.field(metadata=dict(static=True), default=0)
    dict_pad: int = dataclasses.field(metadata=dict(static=True), default=0)
    raw_bits: int = dataclasses.field(metadata=dict(static=True), default=0)

    @property
    def steps(self) -> int:
        return int(self.counts.shape[0])

    @property
    def num_examples(self) -> int:
        return int(np.asarray(self.counts).sum())

    def static_key(self) -> tuple:
        return (
            self.rows, self.lanes, self.dict_lanes, self.code_bits,
            self.dict_pad, self.raw_bits,
        )


def stack_stream_batches(
    parts: List[EncodedEllStreamBatch],
) -> EncodedEllStreamSuperBatch:
    """Stack T stream-wire minibatches into one scan superbatch.
    Statics must agree across T (they pin ONE decode program)."""
    if not parts:
        raise ValueError("empty superbatch")
    key = parts[0].static_key()
    assert all(p.static_key() == key for p in parts), (
        "stream superbatch needs uniform static encoding parameters"
    )
    arrays = (
        "y_bits", "counts", "raw_words", "code_words", "table_words",
        "lane_starts", "n_uniq",
    )
    return EncodedEllStreamSuperBatch(
        **{f: np.stack([getattr(p, f) for p in parts]) for f in arrays},
        rows=parts[0].rows,
        lanes=parts[0].lanes,
        dict_lanes=parts[0].dict_lanes,
        code_bits=parts[0].code_bits,
        dict_pad=parts[0].dict_pad,
        raw_bits=parts[0].raw_bits,
    )


@dataclasses.dataclass(frozen=True)
class StreamStatics:
    """Pinned static parameters of the stream wire (one decode
    program). Derived from the worker's first batch, then every encode
    verifies against them — the `_padding` pattern."""

    lanes: int
    dict_lanes: tuple
    code_bits: int
    dict_pad: int
    raw_bits: int


def _pow2ceil(n: int) -> int:
    return 1 << max(0, int(max(1, n)) - 1).bit_length()


def _lane_code_bits(n_uniq: int) -> int:
    """Bit width of a lane's dictionary codes, with 25% headroom padded
    to a power of two so small vocabulary drift between batches cannot
    flip the static width (each flip would compile a new decode
    program)."""
    return max(1, (_pow2ceil(n_uniq + (n_uniq >> 2)) - 1).bit_length())


def derive_stream_statics(
    keys: np.ndarray, lanes: int, hash_num_slots: int, num_slots: int
) -> Optional[StreamStatics]:
    """Derive the pinned stream-wire statics from one batch's key
    stream (uniform ``lanes``-wide rows, row-major). Returns None when
    no lane-dictionary split wins over the plain bits wire — the
    caller then stays on the bits wire for the run.

    The lane rule is the lane's own net win: shipping codes at the
    lane's padded code width instead of raw bits must save more row
    bits than the lane's padded uslot table costs to ship. That keeps
    high-vocabulary lanes raw automatically — an all-unique lane
    (hashed categorical tokens, ~98% unique per batch) pays a
    rows-sized table for zero code savings, and past the hash those
    streams are ~incompressible anyway (measured: delta-coding the
    global unique slot set loses at the criteo-law ~65% unique
    fraction). A final combined check re-verifies the win at the
    SHARED code width (the widest chosen lane's) before pinning."""
    from ..utils.bitpack import slot_bits as _slot_bits
    from ..utils.murmur import hash_slots

    k = np.ascontiguousarray(keys, dtype=np.uint64).ravel()
    if lanes <= 0 or k.size == 0 or k.size % lanes:
        return None
    raw_bits = _slot_bits(num_slots)
    cols = hash_slots(k, hash_num_slots).reshape(-1, lanes)
    n_rows = cols.shape[0]
    lane_u = [int(len(np.unique(cols[:, j]))) for j in range(lanes)]
    dict_lanes = tuple(
        j
        for j in range(lanes)
        if n_rows * (raw_bits - _lane_code_bits(lane_u[j]))
        > _pow2ceil(lane_u[j] + (lane_u[j] >> 2)) * raw_bits
    )
    if not dict_lanes:
        return None
    code_bits = max(_lane_code_bits(lane_u[j]) for j in dict_lanes)
    total = sum(lane_u[j] for j in dict_lanes)
    dict_pad = _pow2ceil(total + (total >> 2))
    # net-win check against the plain bits wire at THIS batch's shape:
    # per-row code savings must beat the shipped table + offsets
    rows = cols.shape[0]
    saved_bits = rows * len(dict_lanes) * (raw_bits - code_bits)
    table_bits = dict_pad * raw_bits + 32 * len(dict_lanes)
    if saved_bits <= table_bits:
        return None
    return StreamStatics(
        lanes=lanes, dict_lanes=dict_lanes, code_bits=code_bits,
        dict_pad=dict_pad, raw_bits=raw_bits,
    )


def _encode_stream_shard_py(
    slots: np.ndarray, nsub: int, rows_pad: int, st: StreamStatics
):
    """NumPy reference encode of ONE shard's hashed slot matrix —
    bit-identical to the native fused pass (parity tier-1 tested).
    Returns (raw_words, code_words, table_words, lane_starts, n_uniq)
    or None when the batch falls outside the pinned statics."""
    from ..utils.bitpack import pack_bits

    n_dict = len(st.dict_lanes)
    n_raw = st.lanes - n_dict
    cols = slots.reshape(nsub, st.lanes)
    dict_set = frozenset(st.dict_lanes)
    raw_lanes = [j for j in range(st.lanes) if j not in dict_set]
    tables = []
    lane_starts = np.zeros(n_dict, np.int32)
    codes = np.empty((nsub, n_dict), np.int32)
    total = 0
    for i, j in enumerate(st.dict_lanes):
        u, inv = np.unique(cols[:, j], return_inverse=True)
        if len(u) > (1 << st.code_bits) or total + len(u) > st.dict_pad:
            return None
        lane_starts[i] = total
        total += len(u)
        tables.append(u.astype(np.int32, copy=False))
        codes[:, i] = inv
    raw_vals = (
        cols[:, raw_lanes].reshape(-1) if n_raw else np.zeros(0, np.int32)
    )
    table_vals = np.concatenate(tables) if tables else np.zeros(0, np.int32)
    raw_words = stream_to_words(
        pack_bits(raw_vals, st.raw_bits), rows_pad * n_raw, st.raw_bits
    )
    code_words = stream_to_words(
        pack_bits(codes.reshape(-1), st.code_bits),
        rows_pad * n_dict,
        st.code_bits,
    )
    table_words = stream_to_words(
        pack_bits(table_vals, st.raw_bits), st.dict_pad, st.raw_bits
    )
    return raw_words, code_words, table_words, lane_starts, np.int32(total)


def encode_stream_shard(
    keys: np.ndarray,
    nsub: int,
    rows_pad: int,
    hash_num_slots: int,
    st: StreamStatics,
    seed: int = 0,
):
    """Fused hash→unique→remap→bit-pack over ONE shard's key stream
    (the Localizer-prep host stage, fused): native one-pass C ABI call
    when ``libpsnative`` is loaded, bit-identical NumPy fallback
    otherwise. STATELESS + deterministic (pool-able prep stage).
    Returns (raw_words, code_words, table_words, lane_starts, n_uniq)
    or None when the shard falls outside the pinned statics (caller
    ships the raw bits wire)."""
    import ctypes

    from ..cpp import native
    from ..utils.bitpack import packed_nwords
    from ..utils.murmur import hash_slots

    k = np.ascontiguousarray(keys, dtype=np.uint64).ravel()
    assert k.size == nsub * st.lanes, (k.size, nsub, st.lanes)
    lib = native()
    if (
        lib is None
        or getattr(lib, "ps_stream_encode", None) is None
        or k.size < 4096
    ):
        return _encode_stream_shard_py(
            hash_slots(k, hash_num_slots, seed), nsub, rows_pad, st
        )
    n_dict = len(st.dict_lanes)
    n_raw = st.lanes - n_dict
    dict_mask = np.zeros(st.lanes, np.uint8)
    dict_mask[list(st.dict_lanes)] = 1
    # zeroed full-capacity buffers: the native packers write only the
    # live prefix; the zero tail is part of the wire bytes (parity)
    raw_buf = np.zeros(
        packed_nwords(rows_pad * n_raw, st.raw_bits) * 4, np.uint8
    )
    code_buf = np.zeros(
        packed_nwords(rows_pad * n_dict, st.code_bits) * 4, np.uint8
    )
    table_buf = np.zeros(
        packed_nwords(st.dict_pad, st.raw_bits) * 4, np.uint8
    )
    starts = np.zeros(n_dict + 1, np.int32)
    u8p = ctypes.POINTER(ctypes.c_uint8)
    got = lib.ps_stream_encode(
        k.ctypes.data_as(ctypes.POINTER(ctypes.c_uint64)),
        ctypes.c_int64(nsub),
        ctypes.c_int32(st.lanes),
        ctypes.c_uint64(seed),
        ctypes.c_uint64(hash_num_slots),
        dict_mask.ctypes.data_as(u8p),
        ctypes.c_uint32(st.raw_bits),
        ctypes.c_uint32(st.code_bits),
        ctypes.c_int32(st.dict_pad),
        starts.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
        raw_buf.ctypes.data_as(u8p),
        code_buf.ctypes.data_as(u8p),
        table_buf.ctypes.data_as(u8p),
    )
    if got < 0:
        return None
    return (
        raw_buf.view("<u4"),
        code_buf.view("<u4"),
        table_buf.view("<u4"),
        starts[:n_dict].copy(),
        np.int32(got),
    )


def decode_stream_shard(enc: EncodedEllStreamBatch, d: int):
    """Decode ONE data shard of an EncodedEllStreamBatch with the REAL
    jit-side ops (ops/wire_codec) — the shared body the device step
    traces and the host parity oracle runs on CPU. Returns
    ``(y, mask, slots)`` with ``slots`` int32 [rows, lanes]."""
    from ..ops import wire_codec as wc

    y = wc.decode_sign_labels(enc.y_bits[d], enc.counts[d], enc.rows)
    mask = wc.decode_mask(enc.counts[d], enc.rows)
    slots = wc.decode_stream_slots(
        enc.raw_words[d],
        enc.code_words[d],
        enc.table_words[d],
        enc.lane_starts[d],
        rows=enc.rows,
        lanes=enc.lanes,
        dict_lanes=enc.dict_lanes,
        code_bits=enc.code_bits,
        dict_pad=enc.dict_pad,
        raw_bits=enc.raw_bits,
    )
    return y, mask, slots


class UploadCache:
    """Key caching on the host→device leg: a repeated array re-uses its
    device-resident buffer, identified by crc32c signature and VERIFIED
    by exact comparison against a retained host copy (the signature
    routes, it never decides — filter/key_caching.py +
    KeyDirectory-slot-cache semantics, so a collision can never serve
    wrong bytes).

    STATEFUL, single-owner: lives on the serial uploader thread (the
    PR-3 ingest rule — stateless stages pool, stateful stages stay
    serial); the owner-thread assert makes a violation loud instead of
    racy. LRU-evicts by retained host bytes (``max_bytes``); leaves
    smaller than ``min_leaf_bytes`` upload directly (signature overhead
    would exceed the win)."""

    def __init__(
        self,
        upload_leaf=None,
        max_bytes: int = 64 << 20,
        min_leaf_bytes: int = 4096,
    ):
        self._upload_leaf = upload_leaf or jax.device_put
        self._max_bytes = int(max_bytes)
        self._min_leaf_bytes = int(min_leaf_bytes)
        # sig -> [host_copy, device_buf]; MRU at the end. Single-owner
        # by contract (asserted) — no lock on purpose.
        self._cache: "OrderedDict[tuple, list]" = OrderedDict()
        self._bytes = 0
        self._owner: Optional[int] = None
        self.hits = 0
        self.misses = 0
        self.saved_bytes = 0
        self._tel = wire_instruments()

    def _assert_owner(self) -> None:
        me = threading.get_ident()
        if self._owner is None:
            self._owner = me
        elif self._owner != me:
            raise RuntimeError(
                "UploadCache is single-owner (stateful upload stages run "
                "serially on the uploader thread — doc/PERFORMANCE.md "
                f"'Wire format'); owned by thread {self._owner}, called "
                f"from {me}"
            )

    def _sig(self, arr: np.ndarray) -> tuple:
        return (
            crc32c.array_signature(arr, MAX_SIG_LEN),
            arr.shape,
            arr.dtype.str,
        )

    def _put_leaf(self, leaf):
        arr = np.asarray(leaf)
        if arr.nbytes < self._min_leaf_bytes:
            return self._upload_leaf(leaf)
        sig = self._sig(arr)
        entry = self._cache.get(sig)
        if entry is not None and np.array_equal(entry[0], arr):
            self._cache.move_to_end(sig)
            self.hits += 1
            self.saved_bytes += arr.nbytes
            if self._tel is not None:
                self._tel["cache_hits"].inc()
                self._tel["saved_bytes"].labels(reason="cache_hit").inc(
                    arr.nbytes
                )
            return entry[1]
        self.misses += 1
        if self._tel is not None:
            self._tel["cache_misses"].inc()
        dev = self._upload_leaf(leaf)
        if entry is not None:
            # signature collision overwrite: release the displaced
            # entry's accounting or phantom bytes accumulate until the
            # eviction loop permanently thrashes the cache
            self._bytes -= entry[0].nbytes
        self._cache[sig] = [arr.copy(), dev]
        self._bytes += arr.nbytes
        while self._bytes > self._max_bytes and len(self._cache) > 1:
            _, (old, _dev) = self._cache.popitem(last=False)
            self._bytes -= old.nbytes
        return dev

    def __call__(self, prepped):
        """Upload a batch tree, reusing device buffers for leaves whose
        bytes the device already holds."""
        self._assert_owner()
        return jax.tree.map(self._put_leaf, prepped)


def wire_filter_specs(num_bytes: int = 0) -> List[FilterSpec]:
    """The upload wire's host-side filter chain in the reference's
    WORKING order (example/linear/ctr confs → Van::Send applies in
    list order, Recv in reverse): key_caching, then fixing_float
    (``num_bytes`` 0 disables quantization), then compressing — values
    must quantize BEFORE the byte codec sees them (the codec emits
    uint8 frames, which fixing_float would skip), and the round-trip
    property itself holds under ANY ordering (tests/test_filters.py
    pins both this order and the swapped one)."""
    return [
        FilterSpec(type="key_caching"),
        FilterSpec(type="fixing_float", num_bytes=num_bytes),
        FilterSpec(type="compressing"),
    ]


class MessageWireCodec:
    """Drive the host-side FilterChain over batch payloads — the
    host↔host legs of the upload path (multi-host ingest hand-off,
    replay spill) and the chain round-trip contract tests.

    One stateful chain per peer per direction (ref RemoteNode): the
    key-caching filter's per-(channel, range) cache lives in the chain,
    so a repeated key array crosses as its signature only."""

    def __init__(self, num_bytes: int = 0, channel: int = 0):
        from ..filter.base import FilterChain

        self._encode_chain = FilterChain()
        self._decode_chain = FilterChain()
        self._num_bytes = num_bytes
        self._channel = channel

    def encode(self, key: Optional[np.ndarray], values: List[np.ndarray]) -> Message:
        msg = Message(task=Task(key_channel=self._channel))
        msg.task.filters = wire_filter_specs(self._num_bytes)
        msg.key = key
        msg.values = list(values)
        return self._encode_chain.encode(msg)

    def decode(self, msg: Message) -> Tuple[Optional[np.ndarray], List[np.ndarray]]:
        out = self._decode_chain.decode(msg)
        return out.key, list(out.values)


# ---------------------------------------------------------------------------
# LZ on the host→device STAGING leg (the reference's compressing filter,
# upload edition).
#
# The reference compresses every filtered message's value arrays on the
# wire (src/filter/compressing.h, snappy). Our upload path's analog is
# the STAGING leg: prep-pool workers compress each encoded batch's
# leaves into self-describing codec frames (utils/codec.py — native LZ,
# zlib fallback, incompressible payloads ride raw), and the serial
# uploader thread decompresses them immediately before ``device_put``.
# That split honors the stateless-or-feeder rule (compress is stateless
# → pool; decompress rides the single uploader thread) and mirrors the
# reference's chain order: quantize/encode first, byte-codec last.
#
# Byte accounting: ``ps_wire_bytes_total{encoding="<mode>+lz"}`` and
# ``ps_wire_saved_bytes_total{reason="compression"}`` record the staged
# (compressed) bytes — the modeled disaggregated feeder→device-host
# leg — while ``ps_ingest_uploaded_bytes_total`` stays the REALIZED
# PJRT link traffic (arrays decompress BEFORE device_put, so the
# host→device link ships decoded wire bytes; doc/PERFORMANCE.md "Wire
# format" spells out which legs compression does and does not shrink).
# ---------------------------------------------------------------------------


class CompressedBatch:
    """A host-prepped batch tree with its array leaves compressed into
    codec frames — the staging-leg container handed from the prep pool
    to the uploader. NOT a jax pytree: it never reaches a jitted step;
    ``decompress_batch`` restores the original tree bit-identically
    (np.frombuffer of the decoded frame, dtype/shape from the retained
    meta)."""

    __slots__ = (
        "frames", "meta", "treedef", "n", "raw_nbytes", "wire_nbytes",
        "encoding",
    )

    def __init__(self, frames, meta, treedef, n, raw_nbytes, wire_nbytes,
                 encoding):
        self.frames = frames  # List[bytes] codec frames, leaf order
        self.meta = meta  # List[(dtype str, shape)] per leaf
        self.treedef = treedef
        self.n = n  # example count (uploader telemetry)
        self.raw_nbytes = raw_nbytes
        self.wire_nbytes = wire_nbytes  # staged bytes, net of compression
        self.encoding = encoding

    @property
    def num_examples(self) -> int:
        return int(self.n)


def compress_batch(prepped, encoding: str = "") -> CompressedBatch:
    """Compress a host-prepped batch tree's leaves for the staging leg
    (STATELESS — pool-able prep stage). Incompressible leaves ride raw
    inside their self-describing frame (utils/codec.compress), so the
    worst case is one header byte per leaf."""
    from ..utils import codec

    leaves, treedef = jax.tree.flatten(prepped)
    frames, meta = [], []
    raw_nbytes = wire_nbytes = 0
    for leaf in leaves:
        arr = np.ascontiguousarray(leaf)
        frame = codec.compress(arr.tobytes())
        frames.append(frame)
        meta.append((arr.dtype.str, arr.shape))
        raw_nbytes += arr.nbytes
        wire_nbytes += len(frame)
    n = getattr(prepped, "num_examples", 0)
    out = CompressedBatch(
        frames, meta, treedef, n, raw_nbytes, wire_nbytes, encoding
    )
    tel = wire_instruments()
    if tel is not None:
        if encoding:
            tel["bytes"].labels(encoding=f"{encoding}+lz").inc(wire_nbytes)
        tel["saved_bytes"].labels(reason="compression").inc(
            max(0, raw_nbytes - wire_nbytes)
        )
    return out


def decompress_batch(cb: CompressedBatch):
    """Uploader-side inverse of :func:`compress_batch`: restore the
    original batch tree bit-for-bit before ``device_put``. Runs on the
    single uploader/staging thread (the feeder half of the
    stateless-or-feeder rule)."""
    from ..utils import codec

    leaves = []
    for frame, (dtype, shape) in zip(cb.frames, cb.meta):
        dt = np.dtype(dtype)
        expected = dt.itemsize * int(np.prod(shape, dtype=np.int64))
        raw = codec.decompress(frame, expected_size=expected)
        leaves.append(np.frombuffer(raw, dtype=dt).reshape(shape))
    return jax.tree.unflatten(cb.treedef, leaves)


def maybe_decompress(item):
    """Identity for plain batch trees; frame decode for CompressedBatch
    (the uploader calls this on every staged item so compression stays
    a config choice, not a code path fork)."""
    return decompress_batch(item) if isinstance(item, CompressedBatch) else item

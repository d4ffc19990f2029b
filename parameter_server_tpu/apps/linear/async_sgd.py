"""Asynchronous SGD for linear methods — the flagship pipeline.

Counterpart of ``src/app/linear_method/async_sgd.h``. The reference splits
into scheduler (workload dispatch), workers (minibatch gradient: pull w →
Xw → loss grad → push g) and servers (FTRL/AdaGrad entry updates). Here the
worker+server roles fuse into ONE jitted SPMD step over the (data, server)
mesh — the push/pull messages become the collectives inside it:

    pull:  gather (z, √n) at the batch's unique slots from server shards,
           psum over the *server* axis assembles rows; weights derived
           lazily (FTRL w is a function of state, as in FTRLEntry).
    work:  Xw, per-row loss gradient, X^T g — segment-sums over the
           padded-COO batch (ops/spmv), on-shard, MXU/VPU-friendly.
    push:  scatter per-unique gradients densely into the owned server
           shard, psum over the *data* axis aggregates workers, then the
           updater (FTRL/AdaGrad) applies the touched-masked dense update.

Bounded-delay consistency (SGDConfig.max_delay = τ): gradients are computed
against a weight snapshot refreshed every τ steps while updates land on the
live state — the same staleness the reference's message clocks permit —
and the host executor additionally pipelines up to τ+1 steps in flight.
"""

from __future__ import annotations

import dataclasses
import functools
import os
import time
from typing import Dict, Iterator, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ...utils import file as psfile

from jax import shard_map
from jax.sharding import NamedSharding, PartitionSpec as P

from ...learner.sgd import ISGDCompNode, ISGDScheduler, SGDProgress
from ...ops.kv_ops import localize, slot_sentinel, valid_slots
from ...ops.wire_codec import decode_u24
from ...parallel import mesh as meshlib
from ...parallel import partition as partlib
from ...parallel.mesh import DATA_AXIS, SERVER_AXIS
from ...system.message import Task
from ...telemetry import spans as telemetry_spans
from ...utils import evaluation
from ...utils.bitpack import (
    hash_slots_packed,
    packed_nwords,
    slot_bits,
    unpack_bits,
    unpack_sign_bits,
)
from ...utils.localizer import Localizer
from ...utils.sparse import SparseBatch
from .config import Config, SGDConfig
from .learning_rate import LearningRate
from .loss import create_loss
from .penalty import create_penalty
from .updaters import create_updater


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class PreppedBatch:
    """Static-shape localized minibatch, per data shard (leading dim D)."""

    y: np.ndarray  # [D, R]
    mask: np.ndarray  # [D, R]
    rows: np.ndarray  # [D, NZ] int32
    ucols: np.ndarray  # [D, NZ] int32 — index into uslots
    vals: np.ndarray  # [D, NZ] float32
    uslots: np.ndarray  # [D, U] int32 slot ids (sentinel = num_slots)
    umask: np.ndarray  # [D, U] float32

    @property
    def num_examples(self) -> int:
        return int(self.mask.sum())


def prep_batch(
    batch: SparseBatch,
    directory,
    num_shards: int,
    rows_pad: int,
    nnz_pad: int,
    uniq_pad: int,
    num_slots: int,
) -> PreppedBatch:
    """Host-side localize+pad: the MinibatchReader::Read tail (sgd.h:117-135)
    — unique keys, remap to batch-local ids, map keys to table slots."""
    shards = []
    per = -(-batch.n // num_shards)
    for d in range(num_shards):
        sub = batch.slice_rows(min(d * per, batch.n), min((d + 1) * per, batch.n))
        loc = Localizer()
        keys, _ = loc.count_uniq_index(sub)
        local = loc.remap_index(keys)
        if local.nnz > nnz_pad or len(keys) > uniq_pad or local.n > rows_pad:
            raise ValueError(
                f"batch exceeds padding: nnz {local.nnz}>{nnz_pad} or "
                f"uniq {len(keys)}>{uniq_pad} or rows {local.n}>{rows_pad}"
            )
        y = np.zeros(rows_pad, np.float32)
        y[: local.n] = local.y
        mask = np.zeros(rows_pad, np.float32)
        mask[: local.n] = 1.0
        rows = np.zeros(nnz_pad, np.int32)
        ucols = np.zeros(nnz_pad, np.int32)
        vals = np.zeros(nnz_pad, np.float32)
        rows[: local.nnz] = local.row_ids()
        ucols[: local.nnz] = local.indices
        vals[: local.nnz] = local.value_array()
        uslots = np.full(uniq_pad, slot_sentinel(num_slots), np.int32)
        umask = np.zeros(uniq_pad, np.float32)
        uslots[: len(keys)] = directory.slots(keys)
        umask[: len(keys)] = 1.0
        shards.append((y, mask, rows, ucols, vals, uslots, umask))
    stack = [np.stack(x) for x in zip(*shards)]
    return PreppedBatch(*stack)


def prep_batch_shared(
    batch: SparseBatch,
    directory,
    num_shards: int,
    rows_pad: int,
    nnz_pad: int,
    uniq_pad: int,
    num_slots: int,
) -> PreppedBatch:
    """Globally-deduped prep for the sparse-update formulation: ONE
    slot-unique table for the whole minibatch, replicated to every data
    shard (identical ``uslots``/``umask`` rows), so the device step can
    aggregate per-slot gradients with an elementwise data-axis psum and
    scatter state rows back without cross-shard duplicates.

    Dedup happens at SLOT level (after the directory hash), not key
    level: two keys hash-colliding into one slot must have their
    gradients summed before the nonlinear entry update — the same
    aggregation the dense scatter-add performs implicitly. Vectorized
    (unique + searchsorted), no per-shard Localizer sort."""
    keys_all = np.unique(np.asarray(batch.indices))
    slots_of_key = directory.slots(keys_all)
    uniq_slots, key_to_ucol = np.unique(slots_of_key, return_inverse=True)
    u = len(uniq_slots)
    if u > uniq_pad:
        raise ValueError(f"batch exceeds padding: uniq {u}>{uniq_pad}")
    uslots = np.full(uniq_pad, slot_sentinel(num_slots), np.int32)
    uslots[:u] = uniq_slots
    umask = np.zeros(uniq_pad, np.float32)
    umask[:u] = 1.0
    key_to_ucol = key_to_ucol.astype(np.int32)

    shards = []
    per = -(-batch.n // num_shards)
    for d in range(num_shards):
        lo_r = min(d * per, batch.n)
        hi_r = min((d + 1) * per, batch.n)
        lo, hi = batch.indptr[lo_r], batch.indptr[hi_r]
        nsub, nnz = hi_r - lo_r, hi - lo
        if nnz > nnz_pad or nsub > rows_pad:
            raise ValueError(
                f"batch exceeds padding: nnz {nnz}>{nnz_pad} or "
                f"rows {nsub}>{rows_pad}"
            )
        y = np.zeros(rows_pad, np.float32)
        y[:nsub] = batch.y[lo_r:hi_r]
        mask = np.zeros(rows_pad, np.float32)
        mask[:nsub] = 1.0
        counts = np.diff(batch.indptr[lo_r : hi_r + 1])
        rows = np.zeros(nnz_pad, np.int32)
        rows[:nnz] = np.repeat(np.arange(nsub, dtype=np.int32), counts)
        ucols = np.zeros(nnz_pad, np.int32)
        ucols[:nnz] = key_to_ucol[
            np.searchsorted(keys_all, batch.indices[lo:hi])
        ]
        vals = np.zeros(nnz_pad, np.float32)
        vals[:nnz] = batch.values[lo:hi] if not batch.binary else 1.0
        shards.append((y, mask, rows, ucols, vals, uslots, umask))
    stack = [np.stack(x) for x in zip(*shards)]
    return PreppedBatch(*stack)


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class PreppedSuperBatch:
    """T stacked PreppedBatches — the exact wire's scan superbatch
    (fields [T, D, ...]; one device launch scans T sequential
    ministeps, the ELLBitsSuperBatch twin for the dedup wire)."""

    y: np.ndarray
    mask: np.ndarray
    rows: np.ndarray
    ucols: np.ndarray
    vals: np.ndarray
    uslots: np.ndarray
    umask: np.ndarray

    @property
    def steps(self) -> int:
        return int(self.y.shape[0])

    @property
    def num_examples(self) -> int:
        return int(self.mask.sum())


def stack_prepped_batches(batches: "List[PreppedBatch]") -> PreppedSuperBatch:
    """Stack T localized exact-wire minibatches along a new leading T
    axis for one scan-fused launch."""
    if not batches:
        raise ValueError("empty superbatch")
    return PreppedSuperBatch(
        *(
            np.stack([getattr(b, f.name) for b in batches])
            for f in dataclasses.fields(PreppedBatch)
        )
    )


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class HashedBatch:
    """Fast-path batch for hashed directories: per-entry slot ids, no
    uniquification. Duplicate slots aggregate correctly in the push
    scatter-add, so the host needn't sort/unique at all — the whole prep is
    a vectorized hash + pad, which is what makes the TPU pipeline
    host-bound-free (the reference pays a per-minibatch Localizer sort,
    sgd.h:121-134; we only need that for exact-key directories)."""

    y: np.ndarray  # [D, R]
    mask: np.ndarray  # [D, R]
    rows: np.ndarray  # [D, NZ] int32
    slots: np.ndarray  # [D, NZ] int32 (sentinel = num_slots for padding)
    vals: np.ndarray  # [D, NZ] float32

    @property
    def num_examples(self) -> int:
        return int(self.mask.sum())


def prep_batch_hashed(
    batch: SparseBatch,
    directory,
    num_shards: int,
    rows_pad: int,
    nnz_pad: int,
    num_slots: int,
) -> HashedBatch:
    """Vectorized hash+pad prep (no sort): ~20x cheaper than prep_batch."""
    shards = []
    per = -(-batch.n // num_shards)
    for d in range(num_shards):
        lo_r, hi_r = min(d * per, batch.n), min((d + 1) * per, batch.n)
        lo, hi = batch.indptr[lo_r], batch.indptr[hi_r]
        nsub = hi_r - lo_r
        nnz = hi - lo
        if nnz > nnz_pad or nsub > rows_pad:
            raise ValueError(f"batch exceeds padding: {nnz}>{nnz_pad} or {nsub}>{rows_pad}")
        y = np.zeros(rows_pad, np.float32)
        y[:nsub] = batch.y[lo_r:hi_r]
        mask = np.zeros(rows_pad, np.float32)
        mask[:nsub] = 1.0
        counts = np.diff(batch.indptr[lo_r : hi_r + 1])
        rows = np.zeros(nnz_pad, np.int32)
        rows[:nnz] = np.repeat(np.arange(nsub, dtype=np.int32), counts)
        slots = np.full(nnz_pad, slot_sentinel(num_slots), np.int32)
        slots[:nnz] = directory.slots(batch.indices[lo:hi])
        vals = np.zeros(nnz_pad, np.float32)
        vals[:nnz] = (
            batch.values[lo:hi] if not batch.binary else 1.0
        )
        shards.append((y, mask, rows, slots, vals))
    stack = [np.stack(x) for x in zip(*shards)]
    return HashedBatch(*stack)


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class ELLBatch:
    """ELL-packed batch: the TPU-native row-block format.

    Each example owns exactly K feature lanes — ``slots[r, k]`` (sentinel
    ``num_slots`` for missing) and optional ``vals`` (None ⇒ binary
    features, the common CTR case; ref sparse_matrix.h ``binary()``).
    Row ids are *implicit* in the layout, Xw is a lane-sum (no scatter),
    and the wire/PCIe payload drops to 4 bytes per feature. This is the
    "HBM-resident row-block" encoding the design targets: dense [R, K]
    tiles that XLA vectorizes directly.
    """

    y: np.ndarray  # [D, R]
    mask: np.ndarray  # [D, R] float32
    slots: np.ndarray  # [D, R, K] int32
    vals: Optional[np.ndarray]  # [D, R, K] float32 or None (binary)

    @property
    def num_examples(self) -> int:
        return int(self.mask.sum())


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class ELLPackedBatch:
    """ELLBatch with slot ids packed to 3 bytes on the wire.

    Every byte here crosses the host→device link once per example.
    Slot ids address ``num_slots`` < 2^24 entries, so
    int32 wastes a byte per feature; we ship little-endian u24 and
    reassemble with three cheap VPU ops inside the jitted step. This is the
    same byte-economy instinct as the reference's fixing_float filter
    (filter/fixing_float.h) applied to the key stream instead of values.
    """

    y: np.ndarray  # [D, R] float32
    mask: np.ndarray  # [D, R] uint8
    slots_u24: np.ndarray  # [D, R, K, 3] uint8, little-endian
    vals: Optional[np.ndarray]  # [D, R, K] float32 or None (binary)

    @property
    def num_examples(self) -> int:
        return int(self.mask.sum())


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class ELLBitsBatch:
    """ELLBatch on the minimal wire: ceil(log2 S)-bit slot ids, 1-bit
    labels, row counts instead of a mask.

    Only produced for the CTR hot path (hashed directory, binary features,
    uniform rows): no sentinel is needed, so a 4M-slot table ships 22
    bits/feature — 31% fewer bytes than int32, 8% fewer than u24 — plus
    2KB of label bits per 16K rows instead of 64KB of float32. On a
    transfer-bound single-core host this is a direct throughput win; see
    utils/bitpack.py for the stream layout.
    """

    y_bits: np.ndarray  # [D, ceil(R/8)] uint8 little-endian sign bits
    counts: np.ndarray  # [D] int32 live-row count per data shard
    slots_words: np.ndarray  # [D, W] uint32 bitstream words
    # static row padding (R): y_bits rounds R to bytes, so the true row
    # count must ride along for the consumer's step builder
    rows: int = dataclasses.field(metadata=dict(static=True), default=0)

    @property
    def num_examples(self) -> int:
        return int(self.counts.sum())


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class ELLBitsSuperBatch:
    """T minibatches of ELLBits wire stacked on a leading scan axis.

    The device steps through all T minibatches in ONE launch
    (``lax.scan`` inside the jitted step): one dispatch and one
    transfer per T ministeps — the idiomatic XLA shape for a
    sequential optimizer loop.
    Within a superbatch the weights advance every ministep (staleness 0);
    the configured ``max_delay`` bound still governs the snapshot taken
    across superbatch submissions, so the delay bound is never exceeded.
    """

    y_bits: np.ndarray  # [T, D, ceil(R/8)] uint8
    counts: np.ndarray  # [T, D] int32
    slots_words: np.ndarray  # [T, D, W] uint32
    rows: int = dataclasses.field(metadata=dict(static=True), default=0)

    @property
    def steps(self) -> int:
        return len(self.counts)

    @property
    def num_examples(self) -> int:
        return int(self.counts.sum())


def stack_bits_batches(parts: List[ELLBitsBatch]) -> ELLBitsSuperBatch:
    """Stack T prepped ELLBitsBatch minibatches into one scan superbatch."""
    rows = parts[0].rows
    assert all(p.rows == rows for p in parts), "superbatch needs uniform rows"
    return ELLBitsSuperBatch(
        y_bits=np.stack([p.y_bits for p in parts]),
        counts=np.stack([p.counts for p in parts]),
        slots_words=np.stack([p.slots_words for p in parts]),
        rows=rows,
    )


def pack_u24(idx: np.ndarray) -> np.ndarray:
    """int32 [..] → uint8 [.., 3] little-endian (values must be < 2^24)."""
    flat = np.ascontiguousarray(idx, dtype="<u4")
    return flat.view(np.uint8).reshape(*idx.shape, 4)[..., :3].copy()


# jit-side inverse of pack_u24 — the canonical implementation lives in
# ops/wire_codec (decode_u24, with the rest of the wire decode ops);
# re-exported under the historical name for the ELLPackedBatch step
unpack_u24 = decode_u24


def prep_batch_ell(
    batch: SparseBatch,
    directory,
    num_shards: int,
    rows_pad: int,
    lanes: int,
    num_slots: int,
    pack: bool = False,
) -> ELLBatch:
    """Pack a CSR batch into ELL lanes.

    A row with more than ``lanes`` features cannot be represented — the
    reference never drops data, so neither do we: raises ValueError with
    the dropped-entry count (``prep`` pre-checks and falls back to the
    hashed COO path instead of calling in)."""
    max_row = int(np.diff(batch.indptr).max()) if batch.n else 0
    if max_row > lanes:
        dropped = int(
            np.maximum(np.diff(batch.indptr) - lanes, 0).sum()
        )
        raise ValueError(
            f"ELL lane budget {lanes} < widest row {max_row}: packing would "
            f"silently drop {dropped} features; raise ell_lanes or use the "
            "hashed COO path"
        )
    shards = []
    per = -(-batch.n // num_shards)
    binary = batch.binary
    for d in range(num_shards):
        lo_r, hi_r = min(d * per, batch.n), min((d + 1) * per, batch.n)
        nsub = hi_r - lo_r
        y = np.zeros(rows_pad, np.float32)
        y[:nsub] = batch.y[lo_r:hi_r]
        mask = np.zeros(rows_pad, np.float32)
        mask[:nsub] = 1.0
        counts = np.diff(batch.indptr[lo_r : hi_r + 1]).astype(np.int64)
        seg = slice(batch.indptr[lo_r], batch.indptr[hi_r])
        slot_ids = directory.slots(batch.indices[seg])
        uniform = bool(nsub) and bool((counts == lanes).all())
        if uniform and nsub == rows_pad:
            # full uniform batch (the CTR hot path): the freshly-hashed ids
            # ARE the ELL array — reshape in place, no fill, no copy
            slots = slot_ids.reshape(nsub, lanes)
            vals = (
                None
                if binary
                else batch.values[seg].astype(np.float32, copy=False).reshape(nsub, lanes)
            )
            shards.append((y, mask, slots, vals))
            continue
        slots = np.full((rows_pad, lanes), slot_sentinel(num_slots), np.int32)
        vals = None if binary else np.zeros((rows_pad, lanes), np.float32)
        if uniform:
            # uniform rows (fixed-width data): ELL packing is a reshape
            slots[:nsub] = slot_ids.reshape(nsub, lanes)
            if not binary:
                vals[:nsub] = batch.values[seg].reshape(nsub, lanes)
        else:
            lane_idx = _lane_positions(counts, lanes)
            keep = lane_idx >= 0
            flat_rows = np.repeat(np.arange(nsub), counts)[keep]
            flat_lanes = lane_idx[keep]
            slots[flat_rows, flat_lanes] = slot_ids[keep]
            if not binary:
                vals[flat_rows, flat_lanes] = batch.values[seg][keep]
        shards.append((y, mask, slots, vals))
    ys, masks, slotss, valss = zip(*shards)
    if num_shards == 1:
        # single data shard: add the leading axis as a view, not a stack copy
        stack = lambda xs: xs[0][None]  # noqa: E731
    else:
        stack = np.stack
    if pack:
        assert num_slots < (1 << 24), "u24 wire format needs num_slots < 2^24"
        out = ELLPackedBatch(
            y=stack(ys),
            mask=stack(masks).astype(np.uint8),
            slots_u24=pack_u24(stack(slotss)),
            vals=None if binary else stack(valss),
        )
    else:
        out = ELLBatch(
            y=stack(ys),
            mask=stack(masks),
            slots=stack(slotss),
            vals=None if binary else stack(valss),
        )
    return out


def prep_batch_ell_bits(
    batch: SparseBatch,
    directory,
    num_shards: int,
    rows_pad: int,
    lanes: int,
    num_slots: int,
) -> Optional[ELLBitsBatch]:
    """Minimal-wire ELL prep: fused hash→slot→bitstream (one C++ pass per
    shard), labels as sign bits, mask as a row count. Applies only to the
    hashed/binary/uniform-row case — returns None otherwise so the caller
    falls back to the u24 format (which carries sentinels and values).
    Returns host arrays; device placement goes through the worker's
    ``upload`` (which handles multi-process assembly)."""
    if not (batch.binary and directory.hashed):
        return None
    counts_all = np.diff(batch.indptr)
    if not (counts_all == lanes).all():
        return None
    # labels travel as sign bits — lossless only for ±1 classification
    # labels (what the parsers emit); regression targets must keep a fat
    # wire or they'd silently collapse to their sign
    if not (np.abs(batch.y) == 1).all():
        return None
    bits = slot_bits(num_slots)
    per = -(-batch.n // num_shards)
    nwords = packed_nwords(rows_pad * lanes, bits)
    y_nbytes = (rows_pad + 7) // 8
    # np.empty, not zeros: the hash→pack pass overwrites every payload
    # byte in place, and bits past each value's own span are masked off by
    # the device unpacker — zeroing 2MB/batch would just burn host cycles.
    # Bits belonging to PADDING rows decode to garbage slots, which is
    # fine: their gradients, touched-flags and metrics are all gated on
    # the row mask inside the step.
    slots_words = np.empty((num_shards, nwords), "<u4")
    y_bits = np.zeros((num_shards, y_nbytes), np.uint8)
    counts = np.zeros((num_shards,), np.int32)
    for d in range(num_shards):
        lo_r, hi_r = min(d * per, batch.n), min((d + 1) * per, batch.n)
        nsub = hi_r - lo_r
        if nsub > rows_pad:
            raise ValueError(f"batch exceeds padding: {nsub}>{rows_pad}")
        seg = slice(batch.indptr[lo_r], batch.indptr[hi_r])
        nbytes = (nsub * lanes * bits + 7) // 8
        hash_slots_packed(
            batch.indices[seg],
            # hash modulus = the directory's CONFIGURED slot count — the
            # same map as every other path (and stable across elastic
            # resizes); bit width / storage sizing stays padded
            directory.num_slots,
            bits,
            out=slots_words[d].view(np.uint8)[:nbytes],
        )
        yb = np.packbits(batch.y[lo_r:hi_r] > 0, bitorder="little")
        y_bits[d, : yb.size] = yb
        counts[d] = nsub
    return ELLBitsBatch(
        y_bits=y_bits, counts=counts, slots_words=slots_words, rows=rows_pad
    )


def prep_batch_ell_stream(
    batch: SparseBatch,
    directory,
    num_shards: int,
    rows_pad: int,
    lanes: int,
    num_slots: int,
    statics,
):
    """Stream-once lane-dictionary wire prep: the fused
    hash→unique→remap→bit-pack pass (one native C ABI call per shard,
    learner/wire.encode_stream_shard; NumPy fallback bit-identical).
    Small-vocabulary lanes ship per-lane uslot tables + packed ucols,
    high-vocabulary lanes keep the raw bit stream — the cache-free
    encoding for single-epoch data, where the UploadCache never hits.

    Applies to the same domain as the bits wire (hashed directory,
    binary features, uniform rows, ±1 labels) AND only while every
    shard fits the pinned ``statics`` — returns None otherwise so the
    caller falls back to the raw bits wire (never wrong bytes, only
    fat ones). STATELESS given ``statics`` (pool-able prep stage)."""
    from ...learner.wire import (
        EncodedEllStreamBatch,
        encode_stream_shard,
        tree_nbytes,
        wire_instruments,
    )

    tel = wire_instruments()

    def fallback(reason: str):
        if tel is not None:
            tel["fallbacks"].labels(reason=reason).inc()
        return None

    if statics is None or not (batch.binary and directory.hashed):
        return fallback("domain")
    if statics.lanes != lanes:
        return fallback("domain")
    counts_all = np.diff(batch.indptr)
    if not (counts_all == lanes).all():
        return fallback("ragged")
    if not (np.abs(batch.y) == 1).all():
        return fallback("labels")
    t0 = time.perf_counter()
    per = -(-batch.n // num_shards)
    n_dict = len(statics.dict_lanes)
    y_nbytes = (rows_pad + 7) // 8
    y_bits = np.zeros((num_shards, y_nbytes), np.uint8)
    counts = np.zeros((num_shards,), np.int32)
    raw_ws, code_ws, table_ws = [], [], []
    lane_starts = np.zeros((num_shards, n_dict), np.int32)
    n_uniq = np.zeros((num_shards,), np.int32)
    for d in range(num_shards):
        lo_r, hi_r = min(d * per, batch.n), min((d + 1) * per, batch.n)
        nsub = hi_r - lo_r
        if nsub > rows_pad:
            raise ValueError(f"batch exceeds padding: {nsub}>{rows_pad}")
        seg = slice(batch.indptr[lo_r], batch.indptr[hi_r])
        got = encode_stream_shard(
            batch.indices[seg], nsub, rows_pad,
            # hash modulus = the directory's CONFIGURED slot count (the
            # same map as every other path, stable across elastic
            # resizes); raw_bits sizing uses the padded table
            directory.num_slots,
            statics,
        )
        if got is None:
            # a shard overflowed the pinned statics (vocabulary drift
            # past the padded code space / table capacity)
            return fallback("statics_overflow")
        raw_w, code_w, table_w, starts, total = got
        raw_ws.append(raw_w)
        code_ws.append(code_w)
        table_ws.append(table_w)
        lane_starts[d] = starts
        n_uniq[d] = total
        yb = np.packbits(batch.y[lo_r:hi_r] > 0, bitorder="little")
        y_bits[d, : yb.size] = yb
        counts[d] = nsub
    out = EncodedEllStreamBatch(
        y_bits=y_bits,
        counts=counts,
        raw_words=np.stack(raw_ws),
        code_words=np.stack(code_ws),
        table_words=np.stack(table_ws),
        lane_starts=lane_starts,
        n_uniq=n_uniq,
        rows=rows_pad,
        lanes=lanes,
        dict_lanes=statics.dict_lanes,
        code_bits=statics.code_bits,
        dict_pad=statics.dict_pad,
        raw_bits=statics.raw_bits,
    )
    if tel is not None:
        enc_b = tree_nbytes(out)
        # the raw alternative these bytes displace: the bits wire at
        # the same shape (what prep_batch_ell_bits would have shipped)
        bits_b = num_shards * (
            packed_nwords(rows_pad * lanes, statics.raw_bits) * 4
            + y_nbytes + 4
        )
        tel["encode_seconds"].observe(time.perf_counter() - t0)
        tel["bytes"].labels(encoding="stream").inc(enc_b)
        tel["saved_bytes"].labels(reason="encoding").inc(
            max(0, bits_b - enc_b)
        )
    return out


def _lane_positions(counts: np.ndarray, lanes: int) -> np.ndarray:
    """Per-entry lane index within its row; -1 when beyond the lane budget."""
    total = int(counts.sum())
    if total == 0:
        return np.zeros(0, np.int64)
    starts = np.zeros(len(counts), np.int64)
    np.cumsum(counts[:-1], out=starts[1:])
    pos = np.arange(total, dtype=np.int64) - np.repeat(starts, counts)
    return np.where(pos < lanes, pos, -1)


def _make_perturb(noise, salt: int):
    """ADD_NOISE wire op: N(mean, std) on nonzero entries, or None when
    disabled. A mean-only filter (std=0, mean!=0) still applies — the
    reference's normal_distribution(mean, 0) degenerates to adding the
    constant. The key folds BOTH mesh coordinates so every shard of every
    worker draws its own iid stream."""
    if noise is None:
        return None
    mean, std = float(noise[0]), float(noise[1])
    if mean == 0.0 and std <= 0.0:
        return None

    def perturb(g, seed):
        key = jax.random.fold_in(jax.random.PRNGKey(salt), seed)
        key = jax.random.fold_in(key, jax.lax.axis_index(DATA_AXIS))
        key = jax.random.fold_in(key, jax.lax.axis_index(SERVER_AXIS))
        n = mean + std * jax.random.normal(key, g.shape, g.dtype)
        return jnp.where(g != 0, g + n, g)

    return perturb


def make_push_reduce(push_quant: int, noise=None):
    """Cross-worker gradient reduction, optionally through the quantized
    wire: the device-side realization of the reference's FIXING_FLOAT
    push filter (src/filter/fixing_float.h) — each worker stochastically
    rounds its shard gradient to ``push_quant``-byte fixed point with its
    OWN [min, max] scale (the reference's per-message scale, reusing
    filter/fixing_float.quantize_jax) and the decoded values are summed.
    Zero entries are masked back to exactly zero so slots a worker never
    touched contribute nothing — the sparse_filter ∘ fixing_float chain
    of the reference's confs (absent keys get no quantization noise).

    ``noise=(mean, std)`` applies the ADD_NOISE filter device-side:
    N(mean, std) on each worker's own contribution (only where it is
    nonzero — absent keys get no noise), before quantization and
    aggregation, exactly the wire position of src/filter/add_noise.h."""
    perturb = _make_perturb(noise, 0xA015E)

    if not push_quant:
        if perturb is None:
            return lambda g, seed: jax.lax.psum(g, DATA_AXIS)
        return lambda g, seed: jax.lax.psum(perturb(g, seed), DATA_AXIS)
    from ...filter.fixing_float import dequantize_jax, quantize_jax
    from ...ops import quantize as qops

    use_pallas = qops.use_pallas()

    def reduce(g, seed):
        if perturb is not None:
            g = perturb(g, seed)  # ADD_NOISE rides the wire before quantize
        if use_pallas:
            # fused Pallas normalize+noise+floor (measured ~4% faster than
            # the XLA chain on v5e for 2M-slot shards; BENCH_r2 notes)
            s = seed.astype(jnp.int32) * jnp.int32(1000003) + jax.lax.axis_index(
                DATA_AXIS
            ).astype(jnp.int32)
            q, lo, hi = qops.quantize_traced(g, s, num_bytes=push_quant)
        else:
            key = jax.random.fold_in(jax.random.PRNGKey(0x5EED), seed)
            key = jax.random.fold_in(key, jax.lax.axis_index(DATA_AXIS))
            q, lo, hi = quantize_jax(g, push_quant, key)
        dec = dequantize_jax(q, lo, hi, push_quant)
        dec = jnp.where(g != 0, dec, 0.0)
        return jax.lax.psum(dec, DATA_AXIS)

    return reduce


def make_push_touched(push_quant: int, noise=None):
    """(g_shard, seed) -> (reduced g, touched membership mask).

    touched gates ``updater.apply`` (untouched slots pass through, ref
    per-entry Set on received keys only). Without quantization the
    reduced gradient's support IS membership — up to exact float
    cancellation across contributions, which is a no-op update for FTRL
    and a skipped proximal shrink for AdaGrad/SGD on that measure-zero
    event (the price of dropping a second 640k-index scatter, ~8ms/step
    on v5e). Under a quantized push that shortcut would be wrong —
    fixed-point rounding deterministically zeroes small gradients — so
    membership is collected PRE-quantization with a psum of the support
    mask (a cheap dense collective, still no scatter)."""
    push_reduce = make_push_reduce(push_quant, noise=noise)
    if not push_quant:

        def run(g_shard, seed):
            # touched=None: membership IS the reduced gradient's
            # support; updaters derive it on the fly (the FTRL kernel
            # in-block), so no table-sized mask array ever
            # materializes — 4 GB of the 2^30-table OOM budget
            return push_reduce(g_shard, seed), None

    else:

        def run(g_shard, seed):
            touched = (
                jax.lax.psum((g_shard != 0).astype(jnp.float32), DATA_AXIS) > 0
            )
            return push_reduce(g_shard, seed), touched

    return run


def make_pull_lookup(updater, pull_quant: int, noise=None,
                     narrow: "bool | None" = None):
    """Server-side weight derivation + per-slot lookup for the pull
    path, optionally through the quantized wire (FIXING_FLOAT
    pull_filter): each server shard derives its dense weight vector
    from its live state — the reference's servers send WEIGHTS, not raw
    state — and, when ``pull_quant`` is set, stochastically rounds it
    to n-byte fixed point (per-shard scale) before workers gather it.
    Exact zeros (L1-pruned coordinates) stay exactly zero, as under the
    sparse_filter chain. ``noise`` applies ADD_NOISE to the sent
    weights (pull_filter), the server→worker direction of
    src/filter/add_noise.h.

    Returns ``(derive, lookup)``:

    - ``derive(pulled, seed)`` — once per shard per step: the
      representation workers gather from.
    - ``lookup(rep, rel, ok)`` — flat f32 weights at gather indices
      ``rel``, zero where ``ok`` is False.

    ``narrow`` gathers the quantized CODES plus a 1-byte zero-mask
    and dequantizes AFTER the gather, instead of materializing and
    gathering a dense f32 shard — the byte-economy instinct behind
    the reference's production 1-byte fixing_float pull
    (example/linear/ctr/online_l1lr.conf). On a v5e a gather is priced
    by the row and not by the byte (ROADMAP "Speed" 4: PR 27's
    isolation table), so two narrow gathers lose to one wide one. ``narrow=None`` therefore resolves to the WIDE
    path for every width; narrow stays selectable
    (``pull_gather: "narrow"``) for parts where bytes do bind.
    Exactness-preserving either way: dequantize is elementwise with
    per-shard scalar lo/hi, so dequantize(gather(q)) ==
    gather(dequantize(q)) bit-for-bit, and the gathered zero-mask
    reproduces the exact-zero rule."""
    perturb = _make_perturb(noise, 0xA015F)

    def wide_lookup(w, rel, ok):
        return jnp.where(ok, w[rel], 0.0)

    if not pull_quant:
        def derive_plain(pulled, seed):
            w = updater.weights(pulled)
            return w if perturb is None else perturb(w, seed)

        return derive_plain, wide_lookup

    if narrow is None:
        narrow = False  # wide wins on TPU at every width (docstring)
    from ...filter.fixing_float import dequantize_jax, quantize_jax
    from ...ops import quantize as qops

    use_pallas = qops.use_pallas()

    def quantized(pulled, seed):
        w = updater.weights(pulled)
        if perturb is not None:
            w = perturb(w, seed)
        if use_pallas:
            s = seed.astype(jnp.int32) * jnp.int32(999983) + jax.lax.axis_index(
                SERVER_AXIS
            ).astype(jnp.int32)
            q, lo, hi = qops.quantize_traced(w, s, num_bytes=pull_quant)
        else:
            key = jax.random.fold_in(jax.random.PRNGKey(0xF00D), seed)
            key = jax.random.fold_in(key, jax.lax.axis_index(SERVER_AXIS))
            q, lo, hi = quantize_jax(w, pull_quant, key)
        return w, q, lo, hi

    if narrow:
        def derive_narrow(pulled, seed):
            w, q, lo, hi = quantized(pulled, seed)
            return q, w != 0, lo, hi

        def narrow_lookup(rep, rel, ok):
            q, nz, lo, hi = rep
            dec = dequantize_jax(q[rel], lo, hi, pull_quant)
            return jnp.where(ok & nz[rel], dec, 0.0)

        return derive_narrow, narrow_lookup

    def derive_wide(pulled, seed):
        w, q, lo, hi = quantized(pulled, seed)
        dec = dequantize_jax(q, lo, hi, pull_quant)
        return jnp.where(w != 0, dec, 0.0)

    return derive_wide, wide_lookup


def _convergence_metrics(metrics, g_push, update, w_used,
                         final_is_global: bool = False):
    """Cheap in-jit convergence side outputs for the learning truth
    plane (telemetry/learning.py): squared L2 norms of the per-worker
    gradient actually pushed (summed over workers), the aggregated
    post-filter update handed to the updater, and the weights the step
    consumed (per-occurrence touched weights — a blow-up detector and
    trend line, NOT the global table norm). Trace-pure raw scalars on
    the metrics dict, metered host-side in ``ISGDCompNode.collect``
    (the PR 8 jit-purity pattern); donation-safe — every input predates
    the state update.

    Replication contract (metrics ride out_specs P()): in the dense
    formulations ``g_push`` is the ownership-masked (``ok``) gradient —
    each real entry lives on exactly ONE server shard — so the grad
    fold sums over BOTH axes; ``update`` is a per-server shard vector
    replicated over data (server fold only); ``w_used`` is the
    server-assembled weights replicated over server (data fold only).
    ``final_is_global``: the sparse formulation's psum'd per-unique
    update and its gathered weights are already identical on every
    shard — only the per-worker gradient still folds over data."""
    grad = jnp.sum(jnp.square(g_push))
    upd = jnp.sum(jnp.square(update))
    w = jnp.sum(jnp.square(w_used))
    if final_is_global:
        metrics["grad_sq"] = jax.lax.psum(grad, DATA_AXIS)
        metrics["update_sq"] = upd
        metrics["weight_sq"] = w
    else:
        metrics["grad_sq"] = jax.lax.psum(
            jax.lax.psum(grad, SERVER_AXIS), DATA_AXIS
        )
        metrics["update_sq"] = jax.lax.psum(upd, SERVER_AXIS)
        metrics["weight_sq"] = jax.lax.psum(w, DATA_AXIS)
    return metrics


def _progress_metrics(loss, y, xw, mask, with_aux: bool):
    """SGDProgress scalars (padding rows masked out of the objective); the
    per-example xw/y/mask aux — needed only for host-side AUC — costs three
    all_gathers + a device→host minibatch transfer, so it's optional."""
    metrics = {
        "objective": jax.lax.psum(jnp.sum(loss.row_loss(y, xw) * mask), DATA_AXIS),
        "num_ex": jax.lax.psum(jnp.sum(mask), DATA_AXIS),
        "correct": jax.lax.psum(jnp.sum(((xw > 0) == (y > 0)) * mask), DATA_AXIS),
    }
    if with_aux:
        metrics["xw"] = jax.lax.all_gather(xw, DATA_AXIS)
        metrics["y"] = jax.lax.all_gather(y, DATA_AXIS)
        metrics["mask"] = jax.lax.all_gather(mask, DATA_AXIS)
    return metrics


def _donation_variants(step_impl, name: str = "train_step"):
    """Wrap a traced ``(live, pull, batch, seed) -> (new_state, metrics)``
    step with input-buffer donation where it is legal.

    Donating the live table lets XLA alias input->output: the update
    writes every slot anyway, and aliasing removes the extra whole-table
    output buffer — at 2^28+ slots that buffer is the difference between
    a table fitting on one chip or not. Legality depends on aliasing at
    CALL time (donating a buffer also passed as another argument is a
    runtime error — ``f(donate(a), a)``):

    - ``pull is live`` (a snapshot step) and the caller says the snapshot
      never outlives the call (``donate_ok``, i.e. max_delay == 0): a
      single-argument donated program.
    - ``pull is live`` otherwise: a single-argument non-donated program —
      the snapshot buffer must survive for future delayed steps.
    - distinct buffers (delayed step): donate live, pull is safe.

    Each jitted variant is wrapped into the device inventory
    (telemetry/device.py) under ``<name>.<variant>``: per-step-builder
    cost/memory analysis lands in ``device.snapshot()``, new-aval
    recompiles are counted (zero post-warmup on a
    healthy run), and the donated variants' input→output aliasing is
    runtime-verified (a fallback means the step silently paid a
    whole-table copy).
    """
    from ...telemetry import device as device_tel

    step_delay = device_tel.instrument(
        f"{name}.delay",
        functools.partial(jax.jit, donate_argnums=(0,))(step_impl),
        donate_argnums=(0,),
    )

    def snap_impl(live_state, batch, seed):
        return step_impl(live_state, live_state, batch, seed)

    # no-donate: the snapshot buffer must survive for future delayed
    # steps (max_delay > 0); the donate_ok path below covers delay 0
    step_snap = device_tel.instrument(f"{name}.snap", jax.jit(snap_impl))
    step_snap_donate = device_tel.instrument(
        f"{name}.snap_donate",
        functools.partial(jax.jit, donate_argnums=(0,))(snap_impl),
        donate_argnums=(0,),
    )

    def step(live_state, pull_state, batch, seed=np.uint32(0),
             donate_ok: bool = False):
        if pull_state is live_state:
            fn = step_snap_donate if donate_ok else step_snap
            return fn(live_state, batch, seed)
        return step_delay(live_state, pull_state, batch, seed)

    return step


def make_train_step_ell(
    updater,
    loss,
    mesh,
    num_slots: int,
    binary: bool,
    with_aux: bool = True,
    packed: bool = False,
    push_quant: int = 0,
    pull_quant: int = 0,
    push_noise=None,
    pull_noise=None,
    pull_narrow: "bool | None" = None,
):
    """Fused SPMD step over ELL batches: Xw is a lane reduction (no row
    scatter); only the push keeps a scatter-add. ``packed`` accepts the
    u24-wire ELLPackedBatch and unpacks indices on device."""
    n_server = meshlib.num_servers(mesh)
    shard = num_slots // n_server
    push_touched = make_push_touched(push_quant, noise=push_noise)
    pull_derive, pull_lookup = make_pull_lookup(
        updater, pull_quant, noise=pull_noise, narrow=pull_narrow
    )

    def local_step(live, pulled, seed, y, mask, slots, vals):
        y, mask, slots = y[0], mask[0], slots[0]
        vals = None if binary else vals[0]
        if packed:
            mask = mask.astype(jnp.float32)
            slots = unpack_u24(slots)
        flat = slots.reshape(-1)
        rel, ok = localize(flat, shard)

        # pull: each server derives (and optionally quantizes) its
        # representation once, workers gather entries + assemble via psum
        w_rep = pull_derive(pulled, seed)
        w_e = jax.lax.psum(
            pull_lookup(w_rep, rel, ok), SERVER_AXIS
        ).reshape(slots.shape)  # [R, K]
        x = w_e if binary else w_e * vals
        xw = x.sum(axis=1)

        gr = loss.row_grad(y, xw) * mask  # [R]
        g_e = gr[:, None] if binary else gr[:, None] * vals  # [R, K]
        valid = valid_slots(slots, num_slots) if binary else (vals != 0)
        g_flat = jnp.where(valid, g_e, 0.0).reshape(-1)

        g_push = jnp.where(ok, g_flat, 0.0)
        g_shard = jnp.zeros((shard,), jnp.float32).at[rel].add(g_push)
        g_shard, touched = push_touched(g_shard, seed)
        new_state = updater.apply(live, g_shard, touched, seed=seed)

        metrics = _progress_metrics(loss, y, xw, mask, with_aux)
        _convergence_metrics(metrics, g_push, g_shard, w_e * mask[:, None])
        return new_state, metrics

    def state_spec(state):
        # declared in parallel/partition.py — one spec rule for every
        # updater-state leaf, fitted to rank (scalars replicate)
        return partlib.state_partition_spec(state)

    def step_impl(live_state, pull_state, batch, seed=np.uint32(0)):
        specs = state_spec(live_state)
        slots = batch.slots_u24 if packed else batch.slots
        # binary batches carry no vals; pass slots as an unused placeholder
        vals = slots if binary else batch.vals
        batch_specs = tuple(P(DATA_AXIS) for _ in range(4))
        return shard_map(
            local_step,
            mesh=mesh,
            in_specs=(specs, specs, P(), *batch_specs),
            out_specs=(specs, P()),
            check_vma=False,
        )(live_state, pull_state, seed, batch.y, batch.mask, slots, vals)

    return _donation_variants(step_impl, name="step_ell")


def _make_uniform_ell_mini_step(
    updater, loss, shard, decode_fn, with_aux, push_quant,
    pull_quant, push_noise=None, pull_noise=None, pull_narrow=None,
):
    """Shared single-minibatch body for the uniform-row binary ELL wire
    step builders (bits + stream): ``decode_fn(*wire_operands)`` →
    ``(y, mask, slots[R, K])`` inside the jit, then the one pull →
    lane-sum → push → update body both wires share."""
    push_touched = make_push_touched(push_quant, noise=push_noise)
    pull_derive, pull_lookup = make_pull_lookup(
        updater, pull_quant, noise=pull_noise, narrow=pull_narrow
    )

    def mini_step(live, pulled, seed, *wire_operands):
        # named_scope phases: HLO op metadata carries these, so a
        # --profile trace buckets step time into wire-decode / pull /
        # compute / push / update (utils/profiling.summarize_trace)
        with jax.named_scope("ps_decode"):
            y, mask, slots = decode_fn(*wire_operands)
            # slot-localization arithmetic belongs to decode: it turns
            # wire slots into shard-relative gather indices
            flat = slots.reshape(-1)
            rel, ok = localize(flat, shard)

        with jax.named_scope("ps_pull"):
            w_rep = pull_derive(pulled, seed)
            w_e = jax.lax.psum(
                pull_lookup(w_rep, rel, ok), SERVER_AXIS
            ).reshape(slots.shape)  # [R, K]
        with jax.named_scope("ps_compute"):
            xw = w_e.sum(axis=1)

            gr = loss.row_grad(y, xw) * mask  # [R]
            # uniform rows: every lane of a live row is a real feature,
            # and padding rows are killed by the mask folded into gr
            g_flat = jnp.broadcast_to(gr[:, None], slots.shape).reshape(-1)

        with jax.named_scope("ps_push"):
            g_push = jnp.where(ok, g_flat, 0.0)
            g_shard = jnp.zeros((shard,), jnp.float32).at[rel].add(g_push)
            g_shard, touched = push_touched(g_shard, seed)
        with jax.named_scope("ps_update"):
            new_state = updater.apply(live, g_shard, touched, seed=seed)

        with jax.named_scope("ps_metrics"):
            metrics = _progress_metrics(loss, y, xw, mask, with_aux)
            # padding rows' garbage-decoded slots gather real weights;
            # the mask gates them out of the consumed-weight norm just
            # like it gates their gradients
            _convergence_metrics(
                metrics, g_push, g_shard, w_e * mask[:, None]
            )
        return new_state, metrics

    return mini_step


def _make_bits_mini_step(
    updater, loss, num_slots, shard, rows, lanes, with_aux, push_quant,
    pull_quant, push_noise=None, pull_noise=None, pull_narrow=None,
):
    """Single-minibatch body for the bits-wire step builders:
    (live, pulled, seed, per-device y_bits/count/words) -> (state, metrics)."""
    bits = slot_bits(num_slots)

    def decode_fn(y_bits, count, words):
        y = unpack_sign_bits(y_bits, rows)
        mask = (jnp.arange(rows) < count).astype(jnp.float32)
        slots = unpack_bits(words, rows * lanes, bits).reshape(rows, lanes)
        return y, mask, slots

    return _make_uniform_ell_mini_step(
        updater, loss, shard, decode_fn, with_aux, push_quant,
        pull_quant, push_noise, pull_noise, pull_narrow,
    )


def _make_stream_mini_step(
    updater, loss, shard, static_key, with_aux, push_quant,
    pull_quant, push_noise=None, pull_noise=None, pull_narrow=None,
):
    """Single-minibatch body for the stream-wire (lane-dictionary) step
    builders: (live, pulled, seed, per-device y_bits/count/raw_words/
    code_words/table_words/lane_starts) -> (state, metrics). The lane
    split, code width and table capacity are static (they pin the
    decode program — one jit per ``static_key``)."""
    from ...ops.wire_codec import decode_stream_slots

    rows, lanes, dict_lanes, code_bits, dict_pad, raw_bits = static_key

    def decode_fn(y_bits, count, raw_words, code_words, table_words,
                  lane_starts):
        y = unpack_sign_bits(y_bits, rows)
        mask = (jnp.arange(rows) < count).astype(jnp.float32)
        slots = decode_stream_slots(
            raw_words, code_words, table_words, lane_starts,
            rows=rows, lanes=lanes, dict_lanes=dict_lanes,
            code_bits=code_bits, dict_pad=dict_pad, raw_bits=raw_bits,
        )
        return y, mask, slots

    return _make_uniform_ell_mini_step(
        updater, loss, shard, decode_fn, with_aux, push_quant,
        pull_quant, push_noise, pull_noise, pull_narrow,
    )


def _bits_state_spec(state):
    # declared in parallel/partition.py (same rule as state_spec)
    return partlib.state_partition_spec(state)


def make_train_step_ell_bits(
    updater,
    loss,
    mesh,
    num_slots: int,
    rows: int,
    lanes: int,
    with_aux: bool = True,
    push_quant: int = 0,
    pull_quant: int = 0,
    push_noise=None,
    pull_noise=None,
    pull_narrow: "bool | None" = None,
):
    """Fused SPMD step over the minimal-wire ELLBitsBatch (binary,
    uniform-row): slot ids unpack from the bitstream, labels from sign
    bits, the mask from the row count — all inside the jitted step, so the
    host ships ~bits/8 bytes per feature and nothing else."""
    n_server = meshlib.num_servers(mesh)
    shard = num_slots // n_server
    mini_step = _make_bits_mini_step(
        updater, loss, num_slots, shard, rows, lanes, with_aux,
        push_quant, pull_quant, push_noise, pull_noise, pull_narrow,
    )

    def local_step(live, pulled, seed, y_bits, counts, words):
        return mini_step(live, pulled, seed, y_bits[0], counts[0], words[0])

    def step_impl(live_state, pull_state, batch, seed=np.uint32(0)):
        specs = _bits_state_spec(live_state)
        batch_specs = tuple(P(DATA_AXIS) for _ in range(3))
        return shard_map(
            local_step,
            mesh=mesh,
            in_specs=(specs, specs, P(), *batch_specs),
            out_specs=(specs, P()),
            check_vma=False,
        )(live_state, pull_state, seed, batch.y_bits, batch.counts,
          batch.slots_words)

    return _donation_variants(step_impl, name="step_ell_bits")


def make_train_step_ell_bits_scan(
    updater,
    loss,
    mesh,
    num_slots: int,
    rows: int,
    lanes: int,
    with_aux: bool = True,
    push_quant: int = 0,
    pull_quant: int = 0,
    push_noise=None,
    pull_noise=None,
    pull_narrow: "bool | None" = None,
):
    """Scan-fused superstep: T bits-wire minibatches per launch.

    ``lax.scan`` drives the shared mini-step over the leading T axis
    inside ONE jitted program — the weights advance every ministep (the
    sequential-optimizer semantics), while the host pays a single
    dispatch/transfer round trip for T steps. Metrics come back summed
    over the superbatch (stacked per-ministep when ``with_aux``)."""
    n_server = meshlib.num_servers(mesh)
    shard = num_slots // n_server
    mini_step = _make_bits_mini_step(
        updater, loss, num_slots, shard, rows, lanes, with_aux,
        push_quant, pull_quant, push_noise, pull_noise, pull_narrow,
    )

    def local_step(live, pulled, seed, y_bits, counts, words):
        del pulled  # staleness 0 inside the superstep (≤ any delay bound)
        t_steps = y_bits.shape[0]

        def body(carry, xs):
            state, i = carry
            yb, cc, ww = xs
            new_state, metrics = mini_step(
                state, state, seed + i, yb[0], cc[0], ww[0]
            )
            return (new_state, i + np.uint32(1)), metrics

        (new_state, _), metrics = jax.lax.scan(
            body, (live, np.uint32(0)), (y_bits, counts, words),
            length=t_steps,
        )
        if not with_aux:
            metrics = jax.tree.map(lambda m: m.sum(axis=0), metrics)
        else:
            # scalars fold; per-example aux stays stacked per ministep
            metrics = {
                k: (v.sum(axis=0) if v.ndim == 1 else v)
                for k, v in metrics.items()
            }
        return new_state, metrics

    def step_impl(live_state, pull_state, batch, seed=np.uint32(0)):
        specs = _bits_state_spec(live_state)
        batch_specs = tuple(P(None, DATA_AXIS) for _ in range(3))
        return shard_map(
            local_step,
            mesh=mesh,
            in_specs=(specs, specs, P(), *batch_specs),
            out_specs=(specs, P()),
            check_vma=False,
        )(live_state, pull_state, seed, batch.y_bits, batch.counts,
          batch.slots_words)

    return _donation_variants(step_impl, name="step_ell_bits_scan")


_STREAM_FIELDS = (
    "y_bits", "counts", "raw_words", "code_words", "table_words",
    "lane_starts",
)


def make_train_step_ell_stream(
    updater,
    loss,
    mesh,
    num_slots: int,
    static_key: tuple,
    with_aux: bool = True,
    push_quant: int = 0,
    pull_quant: int = 0,
    push_noise=None,
    pull_noise=None,
    pull_narrow: "bool | None" = None,
):
    """Fused SPMD step over the stream-once lane-dictionary wire
    (EncodedEllStreamBatch): dictionary lanes decode as
    ``uslots[lane_start + ucol]`` gathers, raw lanes unpack from the
    bit stream — all inside the jitted step, so only the encoded bytes
    cross the host→device link."""
    n_server = meshlib.num_servers(mesh)
    shard = num_slots // n_server
    mini_step = _make_stream_mini_step(
        updater, loss, shard, static_key, with_aux,
        push_quant, pull_quant, push_noise, pull_noise, pull_narrow,
    )

    def local_step(live, pulled, seed, *wire):
        return mini_step(live, pulled, seed, *(w[0] for w in wire))

    def step_impl(live_state, pull_state, batch, seed=np.uint32(0)):
        specs = _bits_state_spec(live_state)
        batch_specs = tuple(P(DATA_AXIS) for _ in _STREAM_FIELDS)
        return shard_map(
            local_step,
            mesh=mesh,
            in_specs=(specs, specs, P(), *batch_specs),
            out_specs=(specs, P()),
            check_vma=False,
        )(live_state, pull_state, seed,
          *(getattr(batch, f) for f in _STREAM_FIELDS))

    return _donation_variants(step_impl, name="step_ell_stream")


def make_train_step_ell_stream_scan(
    updater,
    loss,
    mesh,
    num_slots: int,
    static_key: tuple,
    with_aux: bool = True,
    push_quant: int = 0,
    pull_quant: int = 0,
    push_noise=None,
    pull_noise=None,
    pull_narrow: "bool | None" = None,
):
    """Scan-fused superstep over T stream-wire minibatches per launch
    (the make_train_step_ell_bits_scan twin — see its semantics note:
    weights advance every ministep, one dispatch per T steps)."""
    n_server = meshlib.num_servers(mesh)
    shard = num_slots // n_server
    mini_step = _make_stream_mini_step(
        updater, loss, shard, static_key, with_aux,
        push_quant, pull_quant, push_noise, pull_noise, pull_narrow,
    )

    def local_step(live, pulled, seed, *wire):
        del pulled  # staleness 0 inside the superstep (≤ any delay bound)
        t_steps = wire[0].shape[0]

        def body(carry, xs):
            state, i = carry
            new_state, metrics = mini_step(
                state, state, seed + i, *(w[0] for w in xs)
            )
            return (new_state, i + np.uint32(1)), metrics

        (new_state, _), metrics = jax.lax.scan(
            body, (live, np.uint32(0)), wire, length=t_steps,
        )
        if not with_aux:
            metrics = jax.tree.map(lambda m: m.sum(axis=0), metrics)
        else:
            metrics = {
                k: (v.sum(axis=0) if v.ndim == 1 else v)
                for k, v in metrics.items()
            }
        return new_state, metrics

    def step_impl(live_state, pull_state, batch, seed=np.uint32(0)):
        specs = _bits_state_spec(live_state)
        batch_specs = tuple(P(None, DATA_AXIS) for _ in _STREAM_FIELDS)
        return shard_map(
            local_step,
            mesh=mesh,
            in_specs=(specs, specs, P(), *batch_specs),
            out_specs=(specs, P()),
            check_vma=False,
        )(live_state, pull_state, seed,
          *(getattr(batch, f) for f in _STREAM_FIELDS))

    return _donation_variants(step_impl, name="step_ell_stream_scan")


def make_train_step_hashed(
    updater, loss, mesh, num_slots: int, with_aux: bool = True,
    push_quant: int = 0, pull_quant: int = 0, push_noise=None,
    pull_noise=None, pull_narrow: "bool | None" = None,
):
    """Per-entry fused SPMD step (hashed fast path): gather state at each
    nnz slot, segment-sum Xw by row, scatter per-entry gradients densely —
    duplicates fold in the scatter, so no uniquification anywhere."""
    n_server = meshlib.num_servers(mesh)
    shard = num_slots // n_server
    push_touched = make_push_touched(push_quant, noise=push_noise)
    pull_derive, pull_lookup = make_pull_lookup(
        updater, pull_quant, noise=pull_noise, narrow=pull_narrow
    )

    def local_step(live, pulled, seed, y, mask, rows, slots, vals):
        y, mask, rows, slots, vals = y[0], mask[0], rows[0], slots[0], vals[0]
        rel, ok = localize(slots, shard)

        # sentinel/padding slots are owned by no shard -> gathered weight 0,
        # and their vals are 0, so they vanish from Xw and g
        w_rep = pull_derive(pulled, seed)
        w_e = jax.lax.psum(pull_lookup(w_rep, rel, ok), SERVER_AXIS)

        xw = jax.ops.segment_sum(vals * w_e, rows, num_segments=y.shape[0])
        gr = loss.row_grad(y, xw) * mask
        g_e = vals * gr[rows]

        g_push = jnp.where(ok, g_e, 0.0)
        g_shard = jnp.zeros((shard,), jnp.float32).at[rel].add(g_push)
        g_shard, touched = push_touched(g_shard, seed)
        new_state = updater.apply(live, g_shard, touched, seed=seed)

        metrics = _progress_metrics(loss, y, xw, mask, with_aux)
        _convergence_metrics(metrics, g_push, g_shard, w_e)
        return new_state, metrics

    def state_spec(state):
        # declared in parallel/partition.py — one spec rule for every
        # updater-state leaf, fitted to rank (scalars replicate)
        return partlib.state_partition_spec(state)

    def step_impl(live_state, pull_state, batch, seed=np.uint32(0)):
        specs = state_spec(live_state)
        batch_specs = tuple(P(DATA_AXIS) for _ in range(5))
        return shard_map(
            local_step,
            mesh=mesh,
            in_specs=(specs, specs, P(), *batch_specs),
            out_specs=(specs, P()),
            check_vma=False,
        )(
            live_state,
            pull_state,
            seed,
            batch.y,
            batch.mask,
            batch.rows,
            batch.slots,
            batch.vals,
        )

    return _donation_variants(step_impl, name="step_hashed")


#: slots per device-side weight derivation in ``weights_dense`` (256 MB
#: of f32 at a time)
_WEIGHTS_WINDOW = 1 << 26


def sparse_update_min_slots() -> int:
    """``SGDConfig.update="auto"`` flip point, in PER-SERVER shard
    slots: below it the dense sweep wins (the whole-shard Pallas pass
    is cheap); at and above it the row
    formulation wins — and 2^31 REQUIRES it (the dense gradient temp
    alone is 8.6 GB). The 2^30 default predates the ledger; the cells
    either side of it are ``criteo_dense.text`` (2^29, the sweep) and
    ``criteo_bigtable.text`` (2^30, the rows). The fused sparse kernel
    (ops/ftrl_sparse.py) would move the row side of that comparison
    and has never been timed on the chip (ROADMAP "Speed" 4, "Design"
    3; doc/PERFORMANCE.md, "FTRL roofline"): it can only LOWER this
    threshold, so 2^30 stays a safe default until a chip run judges
    it. Env ``PS_SPARSE_UPDATE_MIN_SLOTS`` overrides meanwhile."""
    try:
        return int(os.environ.get("PS_SPARSE_UPDATE_MIN_SLOTS", 1 << 30))
    except ValueError:
        return 1 << 30


def _make_exact_mini_step(
    updater, loss, shard, with_aux, update, push_quant, pull_quant,
    push_noise, pull_noise, pull_narrow, significance=None,
):
    """Shared single-minibatch body for the exact (host-dedup) wire:
    (live, pulled, seed, per-device y/mask/rows/ucols/vals/uslots/umask)
    -> (state, metrics). Two update formulations:

    - ``"dense"``: scatter per-unique gradients into a dense shard
      vector, psum over the data axis (inside push_reduce), run the
      updater over the WHOLE shard with a touched mask. O(shard) HBM
      traffic per ministep — wins while the table sweep is cheap.
    - ``"sparse"``: psum the per-unique-slot gradients directly (prep
      guarantees every data shard carries the SAME globally-deduped
      ``uslots``, so the psum is elementwise-aligned), then
      gather→apply→scatter only the touched rows
      (updaters.apply_state_rows). O(unique) traffic — the 2^30+/2^31
      formulation, and the only one that fits 2^31 on one chip (no
      dense gradient temp). The reference's servers likewise only run
      entry ``Set`` on received keys (async_sgd.h:131-151).

    The sparse form composes with the EXACT wire only: quantized/noisy
    push/pull filters are defined on dense shard vectors (per-shard
    scale factors), so they stay with ``"dense"``.

    ``significance`` (ops/significance.SignificanceSpec, sparse-only):
    the in-jit KKT filter — slots whose aggregated update provably
    leaves the FTRL proximal weight at zero are masked out of the
    update entirely (their gradient is zeroed, so their rows are
    written back with the bits they were read with).
    ``None`` traces the literal pre-filter program (the off =
    bit-identical contract).
    """
    if significance is not None and update != "sparse":
        raise ValueError(
            "the KKT significance filter composes with update='sparse' "
            "only (its mask is defined on the globally-deduped unique-"
            "slot vectors)"
        )
    if update == "sparse":
        if push_quant or pull_quant or push_noise or pull_noise:
            raise ValueError(
                "update='sparse' composes with the exact (unfiltered) "
                "wire only; quantized/noisy filters need update='dense'"
            )
        # pull_narrow only modifies a QUANTIZED pull (gather codes+mask
        # instead of dequantized weights); with pull_quant rejected
        # above it has nothing to modify, and the row-gather below
        # ignores it entirely. Fail loudly on an explicit 'narrow'
        # rather than silently dropping it, so a future
        # narrow-without-quant mode cannot diverge here unnoticed
        # (ADVICE round 5). `None` ("auto") stays fine.
        if pull_narrow:
            raise ValueError(
                "update='sparse' does not implement pull_gather="
                "'narrow' (narrow modifies the quantized pull, which "
                "sparse mode rejects); use pull_gather='auto'/'wide'"
            )
        from .updaters import apply_state_rows

        def mini_step_sparse(live, pulled, seed, y, mask, rows, ucols,
                             vals, uslots, umask):
            rel, ok = localize(uslots, shard)
            # static at trace time, and what the write-back's order
            # promise rests on (ops/rows.py): prep hands every shard
            # the SAME ascending unique ``uslots`` with a sentinel tail
            # (prep_batch_shared's np.unique; the wire's delta decode),
            # so on ONE server shard the owned rows ascend and every
            # non-owned entry sits behind them. On a later shard of a
            # wider server axis the ids a lower shard owns come first.
            rows_ascend = jax.lax.axis_size(SERVER_AXIS) == 1
            with jax.named_scope("ps_pull"):
                # derive weights from the GATHERED rows of the pull
                # state — no whole-table weight derivation. Exact:
                # updater.weights is elementwise, so gather∘derive ==
                # derive∘gather bit-for-bit.
                pulled_u = jax.tree.map(
                    lambda a: a[rel] if a.ndim >= 1 else a, pulled
                )
                w_own = jnp.where(ok, updater.weights(pulled_u), 0.0)
                w_u = jax.lax.psum(w_own, SERVER_AXIS) * umask
            with jax.named_scope("ps_compute"):
                xw = jax.ops.segment_sum(
                    vals * w_u[ucols], rows, num_segments=y.shape[0]
                )
                gr = loss.row_grad(y, xw) * mask
                g_u = jax.ops.segment_sum(
                    vals * gr[rows], ucols, num_segments=uslots.shape[0]
                )
                g_u = g_u * umask
            with jax.named_scope("ps_push"):
                # workers share one global uslots table, so gradient
                # aggregation is an elementwise psum of the U-vector —
                # no dense scatter, no shard-sized temp
                g_local = g_u
                g_u = jax.lax.psum(g_u, DATA_AXIS)
            if significance is not None:
                with jax.named_scope("ps_kkt"):
                    from ...ops.significance import kkt_mask

                    # assemble the global z accumulator the same way
                    # w_u was (one extra U-vector collective, disclosed
                    # in doc/PERFORMANCE.md): the KKT test needs the
                    # slot's z, owned by exactly one server shard
                    z_own = jnp.where(ok, pulled_u["z"], 0.0)
                    z_u = jax.lax.psum(z_own, SERVER_AXIS) * umask
                    keep, n_suppressed = kkt_mask(
                        z_u, g_u, w_u, umask, seed, spec=significance
                    )
                    # suppressed slots leave the push entirely: their
                    # aggregated gradient zeroes, and a row with g = 0
                    # is written back with the bits it was read with —
                    # state bit-untouched. They keep their place in the
                    # index vector (dropping them would leave holes
                    # between the kept rows and break its order)
                    g_u = jnp.where(keep, g_u, 0.0)
            with jax.named_scope("ps_update"):
                new_state = apply_state_rows(
                    updater, live, rel, ok, g_u, seed=seed,
                    rows_ascend=rows_ascend,
                )
            with jax.named_scope("ps_metrics"):
                metrics = _progress_metrics(loss, y, xw, mask, with_aux)
                # g_u / w_u are the GLOBAL unique vectors (identical on
                # every shard after their psums) — no further fold
                _convergence_metrics(
                    metrics, g_local, g_u, w_u, final_is_global=True
                )
                if significance is not None:
                    # suppressed-key accounting, metered host-side in
                    # collect (learner/consistency.py reconciles these
                    # against ps_push_keys_total in-record)
                    metrics["kkt_slots"] = jnp.sum(
                        (umask > 0).astype(jnp.float32)
                    )
                    metrics["kkt_suppressed"] = n_suppressed
                    if significance.feedback:
                        # per-slot keep/ids for the host drop tracker —
                        # global vectors, identical on every shard
                        metrics["kkt_keep"] = keep
                        metrics["kkt_uslots"] = uslots
            return new_state, metrics

        return mini_step_sparse

    if update != "dense":
        raise ValueError(f"unknown update mode {update!r}")
    push_touched = make_push_touched(push_quant, noise=push_noise)
    pull_derive, pull_lookup = make_pull_lookup(
        updater, pull_quant, noise=pull_noise, narrow=pull_narrow
    )

    def mini_step(live, pulled, seed, y, mask, rows, ucols, vals,
                  uslots, umask):
        rel, ok = localize(uslots, shard)

        # named_scope: phase names reach HLO op metadata, so a
        # --profile trace (utils/profiling.summarize_trace) can bucket
        # device time by pull/compute/push/update instead of opaque
        # fusion numbers — the r3 verdict's "where do the step's 96%
        # of roofline go" question needs this attribution
        # -- pull (server-side weight derivation, gather + psum assembly) --
        with jax.named_scope("ps_pull"):
            w_rep = pull_derive(pulled, seed)
            w_u = (
                jax.lax.psum(pull_lookup(w_rep, rel, ok), SERVER_AXIS)
                * umask
            )

        # -- worker compute (Xw, row grad, X^T g) --
        with jax.named_scope("ps_compute"):
            xw = jax.ops.segment_sum(
                vals * w_u[ucols], rows, num_segments=y.shape[0]
            )
            gr = loss.row_grad(y, xw) * mask
            g_u = jax.ops.segment_sum(
                vals * gr[rows], ucols, num_segments=uslots.shape[0]
            )
            g_u = g_u * umask

        # -- push (dense scatter into owned shard + psum over data axis) --
        with jax.named_scope("ps_push"):
            g_push = jnp.where(ok, g_u, 0.0)
            g_shard = jnp.zeros((shard,), jnp.float32).at[rel].add(g_push)
            g_shard, touched = push_touched(g_shard, seed)

        with jax.named_scope("ps_update"):
            new_state = updater.apply(live, g_shard, touched, seed=seed)

        # -- progress (ref SGDProgress fields) --
        with jax.named_scope("ps_metrics"):
            metrics = _progress_metrics(loss, y, xw, mask, with_aux)
            _convergence_metrics(metrics, g_push, g_shard, w_u)
        return new_state, metrics

    return mini_step


def make_train_step_scan(
    updater, loss, mesh, num_slots: int, with_aux: bool = True,
    push_quant: int = 0, pull_quant: int = 0, push_noise=None,
    pull_noise=None, pull_narrow: "bool | None" = None,
    update: str = "dense", significance=None,
):
    """Scan-fused superstep over the exact wire: T host-dedup'd
    minibatches per launch (the PreppedSuperBatch twin of
    make_train_step_ell_bits_scan — one dispatch/transfer round trip
    for T sequential ministeps, weights advancing every ministep)."""
    n_server = meshlib.num_servers(mesh)
    shard = num_slots // n_server
    # feedback vectors are per-ministep; the scan metric fold would sum
    # them into garbage — scan supersteps keep the mask, drop the echo
    if significance is not None:
        significance = significance.without_feedback()
    mini_step = _make_exact_mini_step(
        updater, loss, shard, with_aux, update, push_quant, pull_quant,
        push_noise, pull_noise, pull_narrow, significance=significance,
    )

    def local_step(live, pulled, seed, y, mask, rows, ucols, vals,
                   uslots, umask):
        del pulled  # staleness 0 inside the superstep (≤ any delay bound)
        t_steps = y.shape[0]

        def body(carry, xs):
            state, i = carry
            yb, mb, rb, ub, vb, usb, umb = xs
            new_state, metrics = mini_step(
                state, state, seed + i, yb[0], mb[0], rb[0], ub[0],
                vb[0], usb[0], umb[0],
            )
            return (new_state, i + np.uint32(1)), metrics

        (new_state, _), metrics = jax.lax.scan(
            body, (live, np.uint32(0)),
            (y, mask, rows, ucols, vals, uslots, umask),
            length=t_steps,
        )
        if not with_aux:
            metrics = jax.tree.map(lambda m: m.sum(axis=0), metrics)
        else:
            # scalars fold; per-example aux stays stacked per ministep
            metrics = {
                k: (v.sum(axis=0) if v.ndim == 1 else v)
                for k, v in metrics.items()
            }
        return new_state, metrics

    def state_spec(state):
        # declared in parallel/partition.py — one spec rule for every
        # updater-state leaf, fitted to rank (scalars replicate)
        return partlib.state_partition_spec(state)

    def step_impl(live_state, pull_state, batch, seed=np.uint32(0)):
        specs = state_spec(live_state)
        batch_specs = tuple(P(None, DATA_AXIS) for _ in range(7))
        return shard_map(
            local_step,
            mesh=mesh,
            in_specs=(specs, specs, P(), *batch_specs),
            out_specs=(specs, P()),
            check_vma=False,
        )(
            live_state,
            pull_state,
            seed,
            batch.y,
            batch.mask,
            batch.rows,
            batch.ucols,
            batch.vals,
            batch.uslots,
            batch.umask,
        )

    return _donation_variants(step_impl, name="step_exact_scan")


def _encoded_shard_decoder(num_slots: int):
    """Per-shard decode closure for the compact wire (ops/wire_codec via
    learner.wire.decode_exact_shard): EncodedExactBatch leaves with a
    leading local-shard dim of 1 → the raw per-shard exact-wire arrays.
    The static encoding parameters ride on the batch object itself (the
    batch and superbatch classes both carry them)."""
    from ...learner.wire import decode_exact_shard

    def decode(eb):
        leaves = (
            eb.y[0], eb.counts[0], eb.row_counts[0], eb.nnz[0],
            eb.ucols_words[0], eb.uslots[0], eb.n_uniq[0],
            None if eb.vals is None else eb.vals[0],
            None if eb.vals_lo is None else eb.vals_lo[0],
            None if eb.vals_hi is None else eb.vals_hi[0],
        )
        # named_scope: wire decode shows up as its own phase in the
        # --profile trace (utils/profiling.summarize_trace), so the
        # bytes-for-VPU-cycles trade stays measurable
        with jax.named_scope("ps_wire_decode"):
            return decode_exact_shard(eb, num_slots, _leaves=leaves)

    return decode


def make_train_step_encoded(
    updater, loss, mesh, num_slots: int, with_aux: bool = True,
    push_quant: int = 0, pull_quant: int = 0, push_noise=None,
    pull_noise=None, pull_narrow: "bool | None" = None,
    update: str = "dense", significance=None,
):
    """Fused SPMD step over the compact wire's EncodedExactBatch: only
    the encoded buffers cross the host→device link; the jit decodes
    them per shard (ops/wire_codec, trace-pure) and runs the SAME exact
    mini-step as make_train_step — exact-mode parity is bit-for-bit
    (tests/test_wire.py)."""
    n_server = meshlib.num_servers(mesh)
    shard = num_slots // n_server
    mini_step = _make_exact_mini_step(
        updater, loss, shard, with_aux, update, push_quant, pull_quant,
        push_noise, pull_noise, pull_narrow, significance=significance,
    )
    decode = _encoded_shard_decoder(num_slots)

    def local_step(live, pulled, seed, eb):
        y, mask, rows, ucols, vals, uslots, umask = decode(eb)
        return mini_step(
            live, pulled, seed, y, mask, rows, ucols, vals, uslots, umask
        )

    def step_impl(live_state, pull_state, batch, seed=np.uint32(0)):
        specs = _bits_state_spec(live_state)
        bspec = jax.tree.map(lambda _: P(DATA_AXIS), batch)
        return shard_map(
            local_step,
            mesh=mesh,
            in_specs=(specs, specs, P(), bspec),
            out_specs=(specs, P()),
            check_vma=False,
        )(live_state, pull_state, seed, batch)

    return _donation_variants(step_impl, name="step_encoded")


def make_train_step_encoded_scan(
    updater, loss, mesh, num_slots: int, with_aux: bool = True,
    push_quant: int = 0, pull_quant: int = 0, push_noise=None,
    pull_noise=None, pull_narrow: "bool | None" = None,
    update: str = "dense", significance=None,
):
    """Scan-fused superstep over the compact wire: T encoded minibatches
    per launch (the EncodedExactSuperBatch twin of make_train_step_scan
    — decode AND ministep both live inside the one jitted program)."""
    n_server = meshlib.num_servers(mesh)
    shard = num_slots // n_server
    if significance is not None:  # scan fold: mask yes, echo no
        significance = significance.without_feedback()
    mini_step = _make_exact_mini_step(
        updater, loss, shard, with_aux, update, push_quant, pull_quant,
        push_noise, pull_noise, pull_narrow, significance=significance,
    )
    decode = _encoded_shard_decoder(num_slots)

    def local_step(live, pulled, seed, eb):
        del pulled  # staleness 0 inside the superstep (≤ any delay bound)
        t_steps = eb.counts.shape[0]

        def body(carry, xs):
            state, i = carry
            y, mask, rows, ucols, vals, uslots, umask = decode(xs)
            new_state, metrics = mini_step(
                state, state, seed + i, y, mask, rows, ucols, vals,
                uslots, umask,
            )
            return (new_state, i + np.uint32(1)), metrics

        (new_state, _), metrics = jax.lax.scan(
            body, (live, np.uint32(0)), eb, length=t_steps
        )
        if not with_aux:
            metrics = jax.tree.map(lambda m: m.sum(axis=0), metrics)
        else:
            metrics = {
                k: (v.sum(axis=0) if v.ndim == 1 else v)
                for k, v in metrics.items()
            }
        return new_state, metrics

    def step_impl(live_state, pull_state, batch, seed=np.uint32(0)):
        specs = _bits_state_spec(live_state)
        bspec = jax.tree.map(lambda _: P(None, DATA_AXIS), batch)
        return shard_map(
            local_step,
            mesh=mesh,
            in_specs=(specs, specs, P(), bspec),
            out_specs=(specs, P()),
            check_vma=False,
        )(live_state, pull_state, seed, batch)

    return _donation_variants(step_impl, name="step_encoded_scan")


def make_train_step(
    updater, loss, mesh, num_slots: int, with_aux: bool = True,
    push_quant: int = 0, pull_quant: int = 0, push_noise=None,
    pull_noise=None, pull_narrow: "bool | None" = None,
    update: str = "dense", significance=None,
):
    """Build the fused SPMD train step. Returns jitted
    ``step(live_state, pull_state, batch_arrays) -> (new_state, metrics)``.

    ``update="sparse"`` swaps the dense scatter+whole-shard sweep for
    the gather→apply→scatter row formulation (see
    updaters.apply_state_rows) — the big-table mode the scale captures
    flip to above ``sparse_update_min_slots``.
    """
    n_server = meshlib.num_servers(mesh)
    shard = num_slots // n_server
    mini_step = _make_exact_mini_step(
        updater, loss, shard, with_aux, update, push_quant, pull_quant,
        push_noise, pull_noise, pull_narrow, significance=significance,
    )

    def local_step(live, pulled, seed, y, mask, rows, ucols, vals, uslots, umask):
        # squeeze the per-shard leading dim added by stacking
        return mini_step(
            live, pulled, seed, y[0], mask[0], rows[0], ucols[0],
            vals[0], uslots[0], umask[0],
        )

    def state_spec(state):
        # declared in parallel/partition.py — one spec rule for every
        # updater-state leaf, fitted to rank (scalars replicate)
        return partlib.state_partition_spec(state)

    def step_impl(live_state, pull_state, batch, seed=np.uint32(0)):
        specs = state_spec(live_state)
        batch_specs = tuple(P(DATA_AXIS) for _ in range(7))
        return shard_map(
            local_step,
            mesh=mesh,
            in_specs=(specs, specs, P(), *batch_specs),
            out_specs=(specs, P()),
            check_vma=False,
        )(
            live_state,
            pull_state,
            seed,
            batch.y,
            batch.mask,
            batch.rows,
            batch.ucols,
            batch.vals,
            batch.uslots,
            batch.umask,
        )

    return _donation_variants(step_impl, name="step_exact")


_SUPPORTED_FILTERS = (
    "fixing_float", "key_caching", "sparse", "compressing", "add_noise",
)


def _add_noise_params(filters):
    """(mean, std) of an ADD_NOISE entry in a conf filter list, or None.
    Applied device-side to each worker's gradient contribution before
    aggregation — the wire position of the reference's filter
    (src/filter/add_noise.h encodes worker->server messages)."""
    for f in filters or ():
        if isinstance(f, dict):
            ftype = str(f.get("type", "")).lower()
            mean, std = f.get("mean", 0.0), f.get("std", 0.0)
        else:
            ftype = str(getattr(f, "type", "")).lower()
            mean, std = getattr(f, "mean", 0.0), getattr(f, "std", 0.0)
        if ftype == "add_noise":
            return float(mean or 0.0), float(std or 0.0)
    return None


def _fixing_float_bytes(filters, where: str) -> int:
    """num_bytes of a FIXING_FLOAT entry in a conf filter list (0 = none),
    validated; accepts dicts (conf parse) or FilterSpec-likes."""
    import logging

    nb = 0
    for f in filters or ():
        if isinstance(f, dict):
            ftype, fnb = f.get("type"), f.get("num_bytes", 1)
        else:
            ftype, fnb = getattr(f, "type", None), getattr(f, "num_bytes", 1)
        ftype = str(ftype).lower() if ftype is not None else ""
        if ftype == "fixing_float":
            nb = int(fnb or 1)
            if nb not in (1, 2):
                raise ValueError(
                    f"{where} FIXING_FLOAT num_bytes must be 1 or 2, got {nb}"
                )
        elif ftype not in _SUPPORTED_FILTERS:
            logging.getLogger(__name__).warning(
                "%s filter %r is not applied by the fused async-SGD step",
                where, ftype,
            )
    return nb


def _wire_encoding_name(prepped) -> str:
    """Telemetry label for the wire a prepped batch rides
    (``ps_wire_bytes_total{encoding="<name>+lz"}`` on the staging leg)."""
    from ...learner.wire import (
        EncodedEllStreamBatch,
        EncodedEllStreamSuperBatch,
        EncodedExactBatch,
        EncodedExactSuperBatch,
    )

    if isinstance(
        prepped, (EncodedEllStreamBatch, EncodedEllStreamSuperBatch)
    ):
        return "stream"
    if isinstance(prepped, (EncodedExactBatch, EncodedExactSuperBatch)):
        return "exact"
    if isinstance(prepped, (ELLBitsBatch, ELLBitsSuperBatch)):
        return "bits"
    return "raw"


# owner-thread: consumer
class DeviceUploader:
    """Double-buffered host→device stage of the ingest pipeline.

    Issues ``upload_fn`` (a ``jax.device_put`` under the hood) for
    batch t+1 on its own thread while the consumer runs step t, so the
    host→device transfer — the pipeline's scarce resource — overlaps
    device compute instead of serializing in front of it. ``depth``
    bounds the staged-ahead window (default 2: the classic double
    buffer — one batch on the wire while one is being consumed), which
    also bounds the extra device memory pinned by staged batches.

    Donation-safety: batch buffers are only ever INPUTS to the jitted
    steps (never donated — only the table state is, via
    ``donate_argnums=(0,)``), and submission stays on the consumer
    thread under the executor's ``max_in_flight`` bound, so staging
    ahead can never alias a donated buffer.

    Exceptions from the upload thread forward to the consumer;
    ``close()`` stops and joins the thread (also called when iteration
    ends)."""

    def __init__(self, source, upload_fn, depth: int = 2):
        import collections

        from ...learner.ingest import pipeline_instruments
        from ...utils.concurrent import iter_on_thread

        tel = pipeline_instruments()
        # timeline flow hand-off: the uploader thread records each
        # staged batch's flow id (set by the ingest pipeline while this
        # thread pulled the item) in FIFO order; the consumer pops one
        # per item (iter_on_thread preserves order) so the trainer-step
        # submit can run under the SAME flow — feeder → prep pool →
        # uploader → trainer step all correlate. deque append/popleft
        # are atomic (no lock needed; single producer, single consumer).
        self._flows: "collections.deque" = collections.deque()

        def uploaded():
            from ...learner.wire import maybe_decompress

            for prepped, n in source:
                t0 = time.perf_counter()
                # staging-leg frames (wire_compress) decode HERE, on
                # the single uploader thread, immediately before the
                # device_put — the feeder half of the stateless-or-
                # feeder rule; everything below sees plain arrays and
                # uploaded_bytes stays the REALIZED link traffic
                prepped = maybe_decompress(prepped)
                fid = telemetry_spans.current_flow()
                if tel is not None:
                    tel["batches"].labels(pipeline="device_uploader").inc()
                    tel["examples"].labels(pipeline="device_uploader").inc(
                        int(prepped.num_examples)
                    )
                # sample BEFORE the upload: when upload_fn is a caching
                # uploader (learner/wire.UploadCache), leaves served
                # from the device-resident cache never cross the link —
                # uploaded_bytes must stay the REALIZED link traffic
                # (doc/OBSERVABILITY.md), so hit bytes are subtracted
                saved0 = int(getattr(upload_fn, "saved_bytes", 0))
                # span (not a hand-built emit): an upload_fn failure
                # still closes the event with an `error` attr, so the
                # traced flow shows WHERE it died instead of silently
                # ending at ingest.prep
                with telemetry_spans.flow_scope(fid), telemetry_spans.span(
                    "ingest.upload", pipeline="device_uploader"
                ):
                    staged = upload_fn(prepped)
                if tel is not None:
                    hit_bytes = (
                        int(getattr(upload_fn, "saved_bytes", 0)) - saved0
                    )
                    tel["uploaded_bytes"].inc(
                        max(
                            0,
                            sum(
                                int(getattr(leaf, "nbytes", 0))
                                for leaf in jax.tree.leaves(prepped)
                            )
                            - hit_bytes,
                        )
                    )
                    tel["stage_seconds"].labels(
                        stage="upload", pipeline="device_uploader"
                    ).observe(time.perf_counter() - t0)
                self._flows.append(fid)
                yield staged, n

        # maxsize = depth - 1 staged in the queue + 1 held by the
        # consumer = `depth` device-staged batches in flight.
        # No locks here (pslint lock-pass scope, nothing guarded):
        # iter_on_thread owns the cross-thread queue + join contract,
        # and _it is only touched from the consumer thread.
        self._it = iter_on_thread(uploaded(), maxsize=max(1, depth - 1))

    def next_flow(self):
        """The flow id of the next yielded batch (FIFO with the item
        stream; None when tracing is off). Consumer thread only."""
        try:
            return self._flows.popleft()
        except IndexError:
            return None

    def __iter__(self):
        return self._it

    def close(self) -> None:
        self._it.close()


class AsyncSGDWorker(ISGDCompNode):
    """Fused worker+server node (ref AsyncSGDWorker + AsyncSGDServer).

    Consumes minibatches, runs the SPMD step, reports SGDProgress to the
    scheduler's monitor. max_delay>0 computes gradients on a τ-stale weight
    snapshot and keeps τ+1 steps in flight (bounded-delay consistency).
    """

    def __init__(self, conf: Config, mesh=None, name: str = "async_sgd_worker"):
        super().__init__(name=name)
        self.conf = conf
        sgd = conf.async_sgd or SGDConfig()
        self.sgd = sgd
        if mesh is None:
            mesh = self.po.mesh
        assert mesh is not None, "Postoffice.start() first"
        self.mesh = mesh
        self.loss = create_loss(conf.loss.type)
        self.penalty = create_penalty(conf.penalty.type, conf.penalty.lambda_)
        self.lr = LearningRate(
            conf.learning_rate.type, conf.learning_rate.alpha, conf.learning_rate.beta
        )
        self.updater = create_updater(
            sgd.algo, sgd.ada_grad, self.lr, self.penalty,
            ftrl_state_dtype=sgd.ftrl_state_dtype,
        )

        from ...parameter.parameter import KeyDirectory, pad_slots

        if sgd.wire not in ("", "i32", "u24", "bits", "stream"):
            raise ValueError(
                f"unknown SGDConfig.wire {sgd.wire!r}; expected "
                "'i32', 'u24', 'bits', 'stream', or '' (legacy "
                "wire_u24 flag)"
            )
        if sgd.wire_compress not in ("", "lz"):
            raise ValueError(
                f"unknown SGDConfig.wire_compress {sgd.wire_compress!r}; "
                "expected '' or 'lz'"
            )
        from ...learner.wire import WIRE_ENCODE_MODES

        if sgd.wire_encode not in WIRE_ENCODE_MODES:
            raise ValueError(
                f"unknown SGDConfig.wire_encode {sgd.wire_encode!r}; "
                f"expected one of {WIRE_ENCODE_MODES}"
            )
        if sgd.wire_cache_mb < 0:
            raise ValueError(
                f"SGDConfig.wire_cache_mb must be >= 0, got {sgd.wire_cache_mb}"
            )
        # FIXING_FLOAT push/pull filters → n-byte quantized wire inside the
        # fused step (KEY_CACHING needs no device work here — streaming
        # minibatches never repeat key sets, and darlin keeps its blocks
        # device-resident outright; SPARSE's zero-masking is folded into
        # the quantized paths)
        self._push_quant = _fixing_float_bytes(sgd.push_filter, "push_filter")
        self._pull_quant = _fixing_float_bytes(sgd.pull_filter, "pull_filter")
        # ADD_NOISE push filter -> device-side per-worker gradient noise
        self._push_noise = _add_noise_params(sgd.push_filter)
        self._pull_noise = _add_noise_params(sgd.pull_filter)
        try:
            self._pull_narrow = {
                "auto": None, "narrow": True, "wide": False
            }[sgd.pull_gather]
        except KeyError:
            raise ValueError(
                f"unknown SGDConfig.pull_gather {sgd.pull_gather!r}; "
                "expected 'auto', 'narrow', or 'wide'"
            ) from None
        self._seed_counter = 0
        self._warned_ell_overflow = False
        self._warned_scan_fallback = False
        self._warned_stream_multiproc = False
        # stream-wire statics: derived ONCE from the first batch on the
        # feeder/trainer thread and pinned (the `_padding` pattern), so
        # every pool worker encodes against the same decode program.
        # None after derivation = no lane-dictionary split wins on this
        # data → the run stays on the plain bits wire.
        self._stream_statics = None
        self._stream_statics_set = False
        self.num_slots = pad_slots(sgd.num_slots, meshlib.num_servers(mesh))
        self._update_mode = self._resolve_update_mode(sgd)
        # the hash modulus is the CONFIGURED slot count, not the padded
        # table size: padding depends on the server count, and keys must
        # keep their slots across elastic resizes (the reference's key
        # space is likewise fixed while server key ranges move,
        # manager.cc NodeAdd / Range::EvenDivide). Padded tail slots are
        # storage only — never addressed.
        self.directory = KeyDirectory(sgd.num_slots, hashed=True)
        # direct-to-sharded init (no transient whole-array copy — the
        # 2^30-table OOM lesson; rationale at meshlib.init_sharded)
        self.state = meshlib.init_sharded(
            lambda: self.updater.init(self.num_slots), mesh
        )
        # step functions cached per (encoding, binary, with_aux)
        self._steps: Dict[Tuple[str, bool, bool], object] = {}
        self._weights_window = min(self.num_slots, _WEIGHTS_WINDOW)
        self._weights_fn = self._make_weights_fn()
        # max_delay=0 still bounds in-flight work to one step ahead — 0 here
        # would mean *unbounded* (executor semantics), pinning every metrics
        # future in memory
        self.executor.max_in_flight = max(0, sgd.max_delay) + 1
        self._pull_state = self.state
        self._steps_since_snapshot = 0
        # ongoing replication (ref Parameter::SetReplica, executor.cc
        # num_replicas_): every replica_every steps the whole table rolls
        # one shard right, so shard s's segment is mirrored in shard s+1's
        # HBM — a dead shard loses ≤ replica_every steps
        self._replica_state = None
        self._steps_since_replica = 0
        if sgd.num_replicas > 0:
            per = self.num_slots // meshlib.num_servers(mesh)

            def _roll(state):
                return jax.tree.map(
                    lambda x: jnp.roll(x, per, axis=0) if x.ndim >= 1 else x,
                    state,
                )

            self._replicate_fn = jax.jit(_roll, donate_argnums=())
        else:
            self._replicate_fn = None
        self._pads: Optional[Tuple[int, int, int]] = None
        self._num_shards_cache: Optional[int] = None
        self.progress = SGDProgress()
        # learning truth plane (telemetry/learning.py): realized
        # staleness per submission, key heat folded by server key
        # range, convergence metering in collect(). Created fresh per
        # worker so it binds the CURRENT default registry.
        from ...telemetry import registry as telemetry_registry

        if telemetry_registry.enabled():
            from ...telemetry import learning as learning_mod

            self._learning = learning_mod.plane(
                self.name,
                num_slots=self.num_slots,
                num_shards=meshlib.num_servers(mesh),
                max_delay=max(0, sgd.max_delay),
            )
        self._heat_counter = 0  # feeder/trainer thread only
        self._snapshot_ts: Optional[int] = None  # submit thread only
        # -- self-driving consistency (learner/consistency.py) --
        # live effective τ: SGDConfig.max_delay is the CAP; the
        # adaptive controller moves this between submissions. Plain
        # int, single-writer (the collect thread via set_effective_tau)
        # / read by the submit thread — int rebinding is atomic and
        # the value is advisory scheduling state, never a shape.
        self._effective_tau = max(0, sgd.max_delay)
        self._tau_adaptive = bool(sgd.tau_adaptive)
        self._significance = None
        if sgd.kkt_filter:
            if self._update_mode != "sparse":
                raise ValueError(
                    "SGDConfig.kkt_filter requires update='sparse' (the "
                    "mask is defined on the globally-deduped unique-slot "
                    f"vectors); resolved update mode is "
                    f"{self._update_mode!r}"
                )
            if sgd.algo != "ftrl" or getattr(
                self.penalty, "lambda1", 0.0
            ) <= 0.0:
                raise ValueError(
                    "SGDConfig.kkt_filter derives its threshold from the "
                    "FTRL proximal dead zone: algo='ftrl' and an L1 "
                    "penalty (lambda1 > 0) are required"
                )
            if sgd.kkt_drop_after > 0 and sgd.ingest_workers != 1:
                # the drop set evolves in collect order; a concurrent
                # prep pool would apply it in racy, nondeterministic
                # order (the stateless-or-feeder rule). ingest_workers
                # defaults to 0 ("auto", multi-worker) — require the
                # explicit serial setting.
                raise ValueError(
                    "SGDConfig.kkt_drop_after > 0 (host-side key drop) "
                    "requires the serial prep path: set ingest_workers=1"
                )
            from ...ops.significance import SignificanceSpec

            self._significance = SignificanceSpec(
                l1=float(self.penalty.lambda1),
                margin=float(sgd.kkt_margin),
                escape=float(sgd.kkt_escape),
                feedback=sgd.kkt_drop_after > 0,
            )
        if sgd.tau_adaptive or sgd.kkt_filter:
            from ...learner.consistency import ConsistencyRuntime

            self._consistency = ConsistencyRuntime.from_config(self, sgd)

    def _make_weights_fn(self):
        """The jitted weight derivation behind ``weights_dense``, over a
        window of slots at a traced start (one program). Windowed
        because the whole weight vector of a 2^30 table is a 4 GiB
        temporary that does not fit beside the live state and its
        bounded-delay snapshot (RESOURCE_EXHAUSTED with 2.63 GiB free
        on a 16 GB chip). Closes over ``lr.alpha``: rebuilt when the
        consistency controller backs the learning rate off."""
        window = self._weights_window

        def weights_window(state, start):
            return self.updater.weights(jax.tree.map(
                lambda a: jax.lax.dynamic_slice_in_dim(a, start, window)
                if a.ndim >= 1 else a,
                state,
            ))

        # no-donate: derives FROM the live state, which keeps training
        return jax.jit(weights_window)

    def set_effective_tau(self, tau: int) -> int:
        """Move the live bounded-delay τ (between submissions; the
        adaptive controller's actuator). Clamped to [0, max_delay] —
        the configured value stays the contract CAP, so realized
        staleness under any live τ also satisfies the configured bound.
        Never recompiles: τ only schedules snapshot refreshes (a host
        counter), and adaptive mode pins one step executable."""
        tau = int(min(max(0, self.sgd.max_delay), max(0, int(tau))))
        self._effective_tau = tau
        if self._learning is not None:
            self._learning.set_tau(tau)
        return tau

    def _resolve_update_mode(self, sgd: SGDConfig) -> str:
        """``SGDConfig.update`` → concrete formulation. "auto" flips to
        sparse at big per-server shards (sparse_update_min_slots)
        unless push/pull filters are configured — those are defined on
        dense shard vectors, so auto quietly stays dense; an EXPLICIT
        "sparse" + filters is a config error (raised in the builder)."""
        mode = sgd.update or "auto"
        if mode not in ("auto", "dense", "sparse"):
            raise ValueError(
                f"unknown SGDConfig.update {mode!r}; expected "
                "'auto', 'dense', or 'sparse'"
            )
        filtered = bool(
            self._push_quant or self._pull_quant
            or self._push_noise or self._pull_noise
        )
        from ...parallel import distributed

        multi = distributed.is_multiprocess()
        if mode == "auto":
            shard = self.num_slots // meshlib.num_servers(self.mesh)
            if (
                shard >= sparse_update_min_slots()
                and not filtered
                and not multi
            ):
                return "sparse"
            return "dense"
        if mode == "sparse" and multi:
            # each host preps its own data partition, so hosts would
            # build DIFFERENT global-unique slot tables and the
            # elementwise gradient psum would misalign
            raise ValueError(
                "update='sparse' is single-process for now; multi-host "
                "big tables shard the dense update over servers instead"
            )
        return mode

    def _ingest_workers(self) -> int:
        """Prep-pool width for the pipelined train path.
        ``SGDConfig.ingest_workers`` wins when set; the default scales
        to the host: cores-1 (capped at 4) so the feeder thread (parse
        + filter) and the trainer keep a core to breathe on — on a
        2-core host that is ONE prep worker, which still moves all
        localize/pack work off this thread (doc/PERFORMANCE.md,
        "Host-ingest pipeline")."""
        if self.sgd.ingest_workers > 0:
            return self.sgd.ingest_workers
        return max(1, min(4, (os.cpu_count() or 2) - 1))

    def _num_shards(self) -> int:
        """Data shards THIS process preps. Single-process: the whole data
        axis. Multi-process: only the rows this host's devices own — each
        host localizes its own file partition (ref DataAssigner) and the
        shards assemble into one global batch in :meth:`upload`.
        Cached: the mesh is fixed for the worker's lifetime and the walk
        is O(mesh size), too slow for the per-minibatch prep path."""
        if self._num_shards_cache is None:
            from ...parallel import distributed

            if distributed.is_multiprocess():
                self._num_shards_cache = distributed.local_data_shards(self.mesh)
            else:
                self._num_shards_cache = meshlib.num_workers(self.mesh)
        return self._num_shards_cache

    def _padding(self, batch: SparseBatch) -> Tuple[int, int, int]:
        if self._pads is None:
            from ...parallel import distributed

            d = self._num_shards()
            if distributed.is_multiprocess():
                # every process must jit the SAME shapes or the collectives
                # mismatch: derive padding from config (identical on all
                # hosts), never from this host's first batch
                rows = self.sgd.rows_pad or -(-self.sgd.minibatch // d)
                if self.sgd.ell_lanes > 0:
                    nnz = self.sgd.nnz_pad or rows * self.sgd.ell_lanes
                elif self.sgd.nnz_pad:
                    nnz = self.sgd.nnz_pad
                else:
                    raise ValueError(
                        "multi-process runs need SGDConfig.nnz_pad set "
                        "explicitly (auto-sizing from the first local batch "
                        "would give each host different compiled shapes)"
                    )
                self._pads = (rows, nnz, nnz)
                return self._pads
            rows = self.sgd.rows_pad or -(-batch.n // d)
            per_nnz = -(-batch.nnz // d)
            # tight padding: 25% headroom rounded to 4k — transfer bytes are
            # the pipeline's scarce resource, not compile-shape variety
            nnz = self.sgd.nnz_pad or max(
                4096, -(-int(per_nnz * 1.25) // 4096) * 4096,
                # never below the lane budget: the first batch through
                # the tail-feature filter is nearly empty (its counts
                # are cold), and pads pinned from it are exceeded as
                # soon as features start to pass
                rows * self.sgd.ell_lanes,
            )
            self._pads = (rows, nnz, nnz)
        return self._pads

    def _note_heat(self, batch: SparseBatch) -> None:
        """Key-heat feed (learning truth plane): hash this batch's keys
        to table slots and fold them into the worker's windowed count
        sketch + per-shard load shares. Called ONLY from the
        feeder/trainer thread (the sketch is stateful — the
        stateless-or-feeder ingest rule), sampled every
        ``plane.heat_every`` batches so the feeder never stalls on it;
        the hash is the same vectorized murmur the prep pays."""
        lp = self._learning
        if lp is None or not batch.n:
            return
        self._heat_counter += 1
        if self._heat_counter % lp.heat_every:
            return
        lp.note_slots(self.directory.slots(np.asarray(batch.indices)))

    def process_minibatch(self, batch: SparseBatch, report: bool = True) -> int:
        """Pull → gradient → push, one async step (ref UpdateModel inner loop
        + ComputeGradient)."""
        self._note_heat(batch)
        return self._submit_prepped(self.prep(batch, device_put=False))

    def upload(self, prepped):
        """Host-prepped shards → device arrays. Multi-process: assemble
        this host's shards into the global data-sharded batch (the data
        axis sits at dim 1 for scan superbatches, after the T axis).
        Staging-leg frames (wire_compress) decode here, immediately
        before device placement — the uploader half of the
        stateless-or-feeder rule."""
        from ...learner.wire import (
            EncodedEllStreamSuperBatch,
            EncodedExactSuperBatch,
            maybe_decompress,
        )
        from ...parallel import distributed

        prepped = maybe_decompress(prepped)
        axis_dim = (
            1
            if isinstance(
                prepped,
                (
                    ELLBitsSuperBatch,
                    PreppedSuperBatch,
                    EncodedExactSuperBatch,
                    EncodedEllStreamSuperBatch,
                ),
            )
            else 0
        )
        return distributed.global_from_local(self.mesh, prepped, axis_dim=axis_dim)

    def _maybe_encode(self, out):
        """Compact-wire encode for exact-wire (PreppedBatch) preps —
        STATELESS (pool-safe prep stage, the PR-3 ingest rule); falls
        back to the raw wire when the batch lies outside a verified
        encoding domain, so the wire is never wrong, only fat."""
        if not self.sgd.wire_encode:
            return out
        from ...learner.wire import encode_exact

        enc = encode_exact(out, self.num_slots, mode=self.sgd.wire_encode)
        return out if enc is None else enc

    def _get_stream_statics(self, batch: SparseBatch):
        """Pinned stream-wire statics, derived from the FIRST eligible
        batch (like ``_padding``: pinned on the feeder/trainer thread
        before parallel preps could race to different lane splits).
        None = the lane-dictionary wire never wins on this data — the
        run stays on the bits wire."""
        if not self._stream_statics_set:
            from ...learner.wire import derive_stream_statics

            counts = np.diff(batch.indptr)
            if (
                batch.binary
                and batch.n
                and (counts == self.sgd.ell_lanes).all()
            ):
                self._stream_statics = derive_stream_statics(
                    batch.indices,
                    self.sgd.ell_lanes,
                    self.directory.num_slots,
                    self.num_slots,
                )
                self._stream_statics_set = True
        return self._stream_statics

    def prep(self, batch: SparseBatch, device_put: bool = True):
        """Localize+pad a batch for this worker (producer-thread safe)."""
        if self._consistency is not None:
            # host-side significance drop (learner/consistency.py):
            # persistently-suppressed slots leave the batch BEFORE
            # dedup/padding, so they never cost upload keys or bytes.
            # A no-op unless kkt_drop_after > 0 (serial prep enforced
            # at init — the drop set evolves in collect order).
            batch = self._consistency.filter_batch(batch, self.directory)
        rows_pad, nnz_pad, uniq_pad = self._padding(batch)
        num_shards = self._num_shards()
        if self._update_mode == "sparse":
            # the sparse row-update needs globally slot-unique batches
            # (scatter-set correctness) — one shared dedup table for
            # all data shards, regardless of wire/ELL settings. Padded
            # to a (8,128)-tileable length so the row-apply can take
            # the Pallas kernel.
            uniq = min(nnz_pad * num_shards, self.num_slots)
            uniq = -(-uniq // 1024) * 1024
            out = self._maybe_encode(prep_batch_shared(
                batch, self.directory, num_shards, rows_pad, nnz_pad,
                uniq, self.num_slots,
            ))
            return self.upload(out) if device_put else out
        out = None
        use_ell = self.sgd.ell_lanes > 0 and self.directory.hashed
        if use_ell and batch.n:
            # ELL truncation guard (the reference never drops features): a
            # row wider than the lane budget falls back to the hashed COO
            # path — except multiprocess, where a per-host program change
            # would desync the collectives, so fail loudly instead
            max_row = int(np.diff(batch.indptr).max())
            if max_row > self.sgd.ell_lanes:
                from ...parallel import distributed

                if distributed.is_multiprocess():
                    raise ValueError(
                        f"row with {max_row} features exceeds ell_lanes="
                        f"{self.sgd.ell_lanes}; raise ell_lanes (the wire "
                        "format must be identical on every host)"
                    )
                if not self._warned_ell_overflow:
                    import logging

                    logging.getLogger(__name__).warning(
                        "batch has a %d-feature row > ell_lanes=%d; "
                        "falling back to the hashed COO path (no features "
                        "dropped, ELL fast path disabled for such batches)",
                        max_row, self.sgd.ell_lanes,
                    )
                    self._warned_ell_overflow = True
                use_ell = False
        if use_ell:
            wire = self.sgd.wire or ("u24" if self.sgd.wire_u24 else "i32")
            if wire == "stream":
                from ...parallel import distributed

                if distributed.is_multiprocess():
                    # statics are DATA-derived (which lanes take the
                    # dictionary) — per-host derivation could compile
                    # different programs and desync the collectives, so
                    # multi-process runs keep the uniform bits wire
                    if not self._warned_stream_multiproc:
                        import logging

                        logging.getLogger(__name__).warning(
                            "wire='stream' is single-process (its lane "
                            "split is derived from data); multi-process "
                            "runs use the bits wire"
                        )
                        self._warned_stream_multiproc = True
                    wire = "bits"
                else:
                    out = prep_batch_ell_stream(
                        batch,
                        self.directory,
                        num_shards,
                        rows_pad,
                        self.sgd.ell_lanes,
                        self.num_slots,
                        self._get_stream_statics(batch),
                    )
                    if out is None:
                        wire = "bits"  # raw fallback: never wrong bytes
            if out is None and wire == "bits":
                out = prep_batch_ell_bits(
                    batch,
                    self.directory,
                    num_shards,
                    rows_pad,
                    self.sgd.ell_lanes,
                    self.num_slots,
                )
                if out is None:
                    from ...parallel import distributed

                    if distributed.is_multiprocess():
                        # a silent per-host fallback would jit DIFFERENT
                        # step programs on different hosts -> collective
                        # mismatch/hang; the wire must be uniform
                        raise ValueError(
                            "wire='bits' needs binary features, uniform "
                            f"{self.sgd.ell_lanes}-lane rows and ±1 labels "
                            "on every host; this host's batch does not "
                            "qualify — use wire='u24' for this data"
                        )
                    wire = "u24"  # non-uniform/valued batch: sentinel wire
            if out is None:
                out = prep_batch_ell(
                    batch,
                    self.directory,
                    num_shards,
                    rows_pad,
                    self.sgd.ell_lanes,
                    self.num_slots,
                    pack=wire == "u24" and self.num_slots < (1 << 24),
                )
        elif self.directory.hashed:
            out = prep_batch_hashed(
                batch,
                self.directory,
                num_shards,
                rows_pad,
                nnz_pad,
                self.num_slots,
            )
        else:
            out = self._maybe_encode(prep_batch(
                batch,
                self.directory,
                num_shards,
                rows_pad,
                nnz_pad,
                uniq_pad,
                self.num_slots,
            ))
        return self.upload(out) if device_put else out

    def _get_step(self, prepped, with_aux: bool):
        from ...learner.wire import (
            EncodedEllStreamBatch,
            EncodedEllStreamSuperBatch,
            EncodedExactBatch,
            EncodedExactSuperBatch,
        )

        if isinstance(prepped, EncodedEllStreamSuperBatch):
            key = ("ell_stream_scan", (prepped.steps, prepped.static_key()),
                   with_aux)
            builder = lambda: make_train_step_ell_stream_scan(  # noqa: E731
                self.updater, self.loss, self.mesh, self.num_slots,
                static_key=prepped.static_key(), with_aux=with_aux,
                push_quant=self._push_quant, pull_quant=self._pull_quant,
                push_noise=self._push_noise, pull_noise=self._pull_noise,
                pull_narrow=self._pull_narrow,
            )
        elif isinstance(prepped, EncodedEllStreamBatch):
            key = ("ell_stream", prepped.static_key(), with_aux)
            builder = lambda: make_train_step_ell_stream(  # noqa: E731
                self.updater, self.loss, self.mesh, self.num_slots,
                static_key=prepped.static_key(), with_aux=with_aux,
                push_quant=self._push_quant, pull_quant=self._pull_quant,
                push_noise=self._push_noise, pull_noise=self._pull_noise,
                pull_narrow=self._pull_narrow,
            )
        elif isinstance(prepped, EncodedExactSuperBatch):
            key = (
                "exact_enc_scan",
                (prepped.steps, prepped.static_key(), self._update_mode),
                with_aux,
            )
            builder = lambda: make_train_step_encoded_scan(  # noqa: E731
                self.updater, self.loss, self.mesh, self.num_slots,
                with_aux=with_aux, push_quant=self._push_quant,
                pull_quant=self._pull_quant, push_noise=self._push_noise,
                pull_noise=self._pull_noise, pull_narrow=self._pull_narrow,
                update=self._update_mode, significance=self._significance,
            )
        elif isinstance(prepped, EncodedExactBatch):
            key = (
                "exact_enc",
                (prepped.static_key(), self._update_mode),
                with_aux,
            )
            builder = lambda: make_train_step_encoded(  # noqa: E731
                self.updater, self.loss, self.mesh, self.num_slots,
                with_aux=with_aux, push_quant=self._push_quant,
                pull_quant=self._pull_quant, push_noise=self._push_noise,
                pull_noise=self._pull_noise, pull_narrow=self._pull_narrow,
                update=self._update_mode, significance=self._significance,
            )
        elif isinstance(prepped, PreppedSuperBatch):
            key = ("exact_scan", (prepped.steps, self._update_mode), with_aux)
            builder = lambda: make_train_step_scan(  # noqa: E731
                self.updater, self.loss, self.mesh, self.num_slots,
                with_aux=with_aux, push_quant=self._push_quant,
                pull_quant=self._pull_quant, push_noise=self._push_noise,
                pull_noise=self._pull_noise, pull_narrow=self._pull_narrow,
                update=self._update_mode, significance=self._significance,
            )
        elif isinstance(prepped, ELLBitsSuperBatch):
            key = ("ell_bits_scan", (prepped.rows, prepped.steps), with_aux)
            builder = lambda: make_train_step_ell_bits_scan(  # noqa: E731
                self.updater, self.loss, self.mesh, self.num_slots,
                rows=prepped.rows, lanes=self.sgd.ell_lanes, with_aux=with_aux,
                push_quant=self._push_quant, pull_quant=self._pull_quant,
                push_noise=self._push_noise, pull_noise=self._pull_noise,
                pull_narrow=self._pull_narrow,
            )
        elif isinstance(prepped, ELLBitsBatch):
            key = ("ell_bits", prepped.rows, with_aux)
            builder = lambda: make_train_step_ell_bits(  # noqa: E731
                self.updater, self.loss, self.mesh, self.num_slots,
                rows=prepped.rows, lanes=self.sgd.ell_lanes, with_aux=with_aux,
                push_quant=self._push_quant, pull_quant=self._pull_quant,
                push_noise=self._push_noise, pull_noise=self._pull_noise,
                pull_narrow=self._pull_narrow,
            )
        elif isinstance(prepped, (ELLBatch, ELLPackedBatch)):
            packed = isinstance(prepped, ELLPackedBatch)
            key = ("ell_packed" if packed else "ell", prepped.vals is None, with_aux)
            builder = lambda: make_train_step_ell(  # noqa: E731
                self.updater, self.loss, self.mesh, self.num_slots,
                binary=prepped.vals is None, with_aux=with_aux, packed=packed,
                push_quant=self._push_quant, pull_quant=self._pull_quant,
                push_noise=self._push_noise, pull_noise=self._pull_noise,
                pull_narrow=self._pull_narrow,
            )
        elif isinstance(prepped, HashedBatch):
            key = ("hashed", False, with_aux)
            builder = lambda: make_train_step_hashed(  # noqa: E731
                self.updater, self.loss, self.mesh, self.num_slots,
                with_aux=with_aux, push_quant=self._push_quant,
                pull_quant=self._pull_quant, push_noise=self._push_noise,
                pull_noise=self._pull_noise,
                pull_narrow=self._pull_narrow,
            )
        else:
            key = ("exact", self._update_mode, with_aux)
            builder = lambda: make_train_step(  # noqa: E731
                self.updater, self.loss, self.mesh, self.num_slots,
                with_aux=with_aux, push_quant=self._push_quant,
                pull_quant=self._pull_quant, push_noise=self._push_noise,
                pull_noise=self._pull_noise,
                pull_narrow=self._pull_narrow,
                update=self._update_mode,
                significance=self._significance,
            )
        if key not in self._steps:
            self._steps[key] = builder()
        return self._steps[key]

    def _submit_prepped(self, prepped, with_aux: bool = True) -> int:
        """Dispatch one SPMD step on an already-localized batch.

        ``with_aux=False`` skips the per-example xw/y/mask outputs (host AUC)
        — the cheap mode for throughput-critical loops.
        """
        from ...parallel import distributed

        if distributed.is_multiprocess() and any(
            isinstance(leaf, np.ndarray) for leaf in jax.tree.leaves(prepped)
        ):
            # host shards can't be auto-sharded across processes by jit;
            # assemble the global batch explicitly
            prepped = self.upload(prepped)
        from ...learner.wire import (
            EncodedEllStreamSuperBatch,
            EncodedExactSuperBatch,
        )

        # the LIVE bounded-delay τ (== SGDConfig.max_delay unless the
        # adaptive controller moved it; always <= the configured cap)
        tau = self._effective_tau
        # a scan superbatch advances the weights n_steps times in one
        # submission (staleness 0 inside it — within any delay bound)
        n_steps = (
            prepped.steps
            if isinstance(
                prepped,
                (
                    ELLBitsSuperBatch,
                    PreppedSuperBatch,
                    EncodedExactSuperBatch,
                    EncodedEllStreamSuperBatch,
                ),
            )
            else 1
        )
        # snapshot *scheduling* happens at submit time (deterministic in
        # submission order), but the snapshot itself must be taken when the
        # step RUNS on the executor's dispatch thread — self.state is only
        # advanced there, and steps execute in submission order
        do_snapshot = tau <= 0 or self._steps_since_snapshot >= tau
        # realized staleness of THIS submission, in ministeps: how far
        # its weight snapshot lags the apply clock. A snapshot-taking
        # step applies against the snapshot it itself pulls (staleness
        # 0); otherwise the snapshot is _steps_since_snapshot ministeps
        # old. Steps run in submission order (no deps → the executor's
        # ready heap dispatches by timestamp), so the submit-time value
        # IS the realized one.
        staleness = 0 if do_snapshot else self._steps_since_snapshot
        if do_snapshot:
            self._steps_since_snapshot = 0
        step_fn = self._get_step(prepped, with_aux)
        self._seed_counter += n_steps
        seed = np.uint32(self._seed_counter - (n_steps - 1))

        def step():
            if do_snapshot:
                self._pull_state = self.state
            # donate_ok: with max_delay == 0 every step snapshots, so the
            # pull snapshot never outlives this call and the live table
            # can be donated (halves table HBM footprint). Adaptive τ
            # pins the NON-donated variant even at τ=0: the donated and
            # non-donated programs are different executables, and a
            # controller clamping τ to 0 mid-run must never buy the
            # donation with a recompile (the τ-sweep zero-recompile
            # regression pin, tests/test_consistency.py)
            donated = tau <= 0 and not self._tau_adaptive
            new_state, metrics = step_fn(
                self.state, self._pull_state, prepped, seed,
                donate_ok=donated,
            )
            self.state = new_state
            if donated:
                # the donated call consumed the buffer _pull_state points
                # at; re-anchor the snapshot on the newest state so a
                # LATER max_delay change never reads a deleted buffer
                # (staleness 0 satisfies any future bound)
                self._pull_state = new_state
            if self._replicate_fn is not None:
                self._steps_since_replica += n_steps
                if (
                    self._replica_state is None
                    or self._steps_since_replica >= self.sgd.replica_every
                ):
                    self._steps_since_replica = 0
                    self._replica_state = self._replicate_fn(self.state)
            return metrics

        self._steps_since_snapshot += n_steps
        self._note_ftrl_dispatch(prepped, n_steps)
        ts = self.submit(step, Task())
        if self._learning is not None:
            # logical-clock stamp: the executor timestamp of the
            # snapshot-taking submission vs this one (the Executor
            # timestamps the contract is defined over)
            if do_snapshot or self._snapshot_ts is None:
                self._snapshot_ts = ts
            self._learning.note_submit(
                staleness, n_steps=n_steps,
                clock_lag=ts - self._snapshot_ts,
                tau=tau,
            )
        return ts

    def _note_ftrl_dispatch(self, prepped, n_steps: int) -> None:
        """Host-side FTRL update-path accounting (ps_ftrl_rows_total /
        ps_ftrl_update_path_total): the path is STATIC per compiled
        step (trace-time predicate), so the submit thread names it via
        the same pure predicates the trace uses — an in-jit counter
        would fire once at trace time and never again (pslint
        jit-purity). No-op for non-FTRL/non-decay updaters and while
        telemetry is off."""
        from ...ops import use_pallas
        from ...ops.ftrl_sparse import resolve_update_path
        from ...telemetry.instruments import cached_ftrl_instruments
        from .updaters import FTRLUpdater

        tel = cached_ftrl_instruments()
        if tel is None:
            return
        if not (
            isinstance(self.updater, FTRLUpdater)
            and self.updater.lr.type == LearningRate.DECAY
        ):
            return
        shard = self.num_slots // meshlib.num_servers(self.mesh)
        u = 0
        if self._update_mode == "sparse":
            u = int(
                getattr(prepped, "uniq_pad", 0)
                or getattr(prepped, "uslots", np.empty((0, 0))).shape[-1]
            )
        path = resolve_update_path(
            self._update_mode, on_tpu=use_pallas(), shard=shard, u=u,
            bf16_n=self.updater.sqrt_n_dtype == jnp.bfloat16,
            has_seed=True,  # _submit_prepped always threads a seed
        )
        rows = u if self._update_mode == "sparse" else shard
        tel["path"].labels(path=path).inc(n_steps)
        tel["rows"].inc(rows * n_steps)

    def _submit_fused(self, prepped: List[ELLBitsBatch], with_aux: bool) -> int:
        """The one fused-submit path both grouping APIs share."""
        return self._submit_prepped(
            self.upload(stack_bits_batches(prepped)), with_aux=with_aux
        )

    def submit_superbatch(
        self, batches: List[SparseBatch], with_aux: bool = False
    ) -> int:
        """Prep + stack T minibatches and run them as ONE scan-fused
        device launch (see ELLBitsSuperBatch). Requires the bits wire —
        raises on ineligible batches (the training loop's submit_group is
        the tolerant variant)."""
        from ...learner.wire import (
            EncodedEllStreamBatch,
            EncodedExactBatch,
            stack_encoded_batches,
            stack_stream_batches,
        )

        prepped = [self.prep(b, device_put=False) for b in batches]
        if all(isinstance(p, ELLBitsBatch) for p in prepped):
            return self._submit_fused(prepped, with_aux)
        if all(isinstance(p, EncodedEllStreamBatch) for p in prepped) and (
            len({p.static_key() for p in prepped}) == 1
        ):
            return self._submit_prepped(
                self.upload(stack_stream_batches(prepped)),
                with_aux=with_aux,
            )
        # exact-wire (raw or compact-encoded) scan fusion is SPARSE-
        # update only, same gate and rationale as _prep_group: the scan
        # runs ministeps on the live state (staleness 0), which is
        # sparse mode's contract but would silently drop dense mode's
        # snapshot-pull / per-ministep filter semantics (ADVICE r5)
        if self._update_mode == "sparse":
            if all(isinstance(p, PreppedBatch) for p in prepped):
                return self._submit_prepped(
                    self.upload(stack_prepped_batches(prepped)),
                    with_aux=with_aux,
                )
            if all(isinstance(p, EncodedExactBatch) for p in prepped) and (
                len({p.static_key() for p in prepped}) == 1
            ):
                # compact-wire superbatch: decode rides inside the scan
                return self._submit_prepped(
                    self.upload(stack_encoded_batches(prepped)),
                    with_aux=with_aux,
                )
        raise ValueError(
            "superbatch needs the bits wire (hashed directory, binary "
            "uniform-row batches) or the exact wire in sparse-update "
            "mode (dense-mode exact groups run per-minibatch: the scan "
            "would bypass snapshot/filter semantics); got a "
            "mixed/fallback encoding or a dense-mode exact group"
        )

    def _prep_group(self, batches: List[SparseBatch]):
        """Host side of tolerant grouping (prep + stack, no device
        work ordering constraints — safe to run on a pipeline thread):
        one scan superbatch when every batch takes the bits wire, else
        per-minibatch parts. Returns ``[(host_prepped, n_ministeps)]``.
        With ``wire_compress`` set, every emitted part's leaves are
        framed through the staging-leg codec here — ON the pool
        (stateless), decoded on the uploader thread by ``upload``."""
        from ...learner.wire import (
            EncodedEllStreamBatch,
            EncodedExactBatch,
            stack_encoded_batches,
            stack_stream_batches,
        )

        prepped = [self.prep(b, device_put=False) for b in batches]
        if len(prepped) > 1 and all(
            isinstance(p, EncodedEllStreamBatch) for p in prepped
        ) and len({p.static_key() for p in prepped}) == 1:
            return self._maybe_compress(
                [(stack_stream_batches(prepped), len(prepped))]
            )
        if len(prepped) > 1 and all(
            isinstance(p, ELLBitsBatch) for p in prepped
        ):
            return self._maybe_compress(
                [(stack_bits_batches(prepped), len(prepped))]
            )
        # exact-wire (raw or compact-encoded) scan fusion is gated on
        # SPARSE update mode: make_train_step_scan runs every ministep
        # against the LIVE state (`del pulled`, staleness 0), which is
        # sparse mode's documented contract but would silently change
        # dense-mode semantics (snapshot pulls every max_delay steps,
        # push/pull filters per ministep) — dense exact-wire groups
        # stay per-minibatch (ADVICE round 5).
        if len(prepped) > 1 and self._update_mode == "sparse":
            if all(isinstance(p, PreppedBatch) for p in prepped):
                return self._maybe_compress(
                    [(stack_prepped_batches(prepped), len(prepped))]
                )
            if all(isinstance(p, EncodedExactBatch) for p in prepped) and (
                len({p.static_key() for p in prepped}) == 1
            ):
                return self._maybe_compress(
                    [(stack_encoded_batches(prepped), len(prepped))]
                )
        if len(prepped) > 1 and not self._warned_scan_fallback:
            import logging

            logging.getLogger(__name__).info(
                "steps_per_launch=%d requested but the batch group is not "
                "bits-wire eligible (needs hashed directory + binary "
                "uniform rows); running per-minibatch steps",
                self.sgd.steps_per_launch,
            )
            self._warned_scan_fallback = True
        return self._maybe_compress([(p, 1) for p in prepped])

    def _maybe_compress(self, parts):
        """Staging-leg codec for emitted prep parts (``wire_compress``):
        stateless frame encode on the pool; ``upload`` decodes on the
        uploader thread right before device placement. Off = identity."""
        if not self.sgd.wire_compress:
            return parts
        from ...learner.wire import compress_batch

        return [
            (compress_batch(p, encoding=_wire_encoding_name(p)), n)
            for p, n in parts
        ]

    def submit_group(self, batches: List[SparseBatch], with_aux: bool = True):
        """Tolerant grouping for the training loop: scan-fuse when every
        batch takes the bits wire, fall back to per-minibatch steps
        otherwise (ragged rows, valued features, ...). Returns
        ``[(timestamp, n_ministeps), ...]`` so callers can bound
        in-flight work in MINISTEPS, not launches."""
        return [
            (self._submit_prepped(self.upload(p), with_aux=with_aux), n)
            for p, n in self._prep_group(batches)
        ]

    # collect: inherited from ISGDCompNode (shared worker plumbing, incl.
    # the scan-superstep per-ministep AUC layout)

    def train(
        self,
        batches: Iterator[SparseBatch],
        pipelined: "bool | None" = None,
    ) -> SGDProgress:
        """Drive a pass over an iterator of minibatches.

        With ``steps_per_launch > 1`` (and the bits wire) minibatches are
        grouped into scan-fused supersteps — one device launch per T
        steps; a trailing group smaller than T still runs (its own scan
        length). Weights advance every ministep either way.

        ``pipelined`` (default: on when T > 1) moves prep + stack +
        device staging onto a daemon thread behind a bounded queue, so
        localization CPU time and the host→device wire overlap the
        device steps this thread is collecting — the TPU twin of the
        reference's MinibatchReader producer/consumer overlap
        (src/learner/sgd.h:60-143). Submission still happens HERE, in
        order, so seeds, snapshot scheduling (max_delay), and therefore
        the entire trajectory are bit-identical to the unpipelined
        path (asserted in tests)."""
        T = max(1, self.sgd.steps_per_launch)
        if pipelined is None:
            pipelined = T > 1
        try:
            return self._train_impl(batches, T, pipelined)
        except BaseException:
            # a poisoned reader or mid-run failure must not leave
            # in-flight device steps behind: interpreter teardown would
            # kill the executor thread inside a C++ device wait
            # ('terminate called / FATAL: exception not rethrown')
            import contextlib

            with contextlib.suppress(Exception):
                self.executor.wait_all(pop=False)
            raise

    def _train_impl(
        self, batches: Iterator[SparseBatch], T: int, pipelined: bool
    ) -> SGDProgress:
        pending: List[Tuple[int, int]] = []  # (ts, n_ministeps)
        # backpressure in MINISTEPS (aux memory scales with them), while
        # always allowing at least one full launch in flight
        bound = max(T, self.sgd.max_delay + 1)

        if pipelined:
            # staged ingest (learner/ingest.py): grouping runs on the
            # pipeline's feeder thread, localize/pack fans out over the
            # ordered prep pool, and the double-buffered DeviceUploader
            # issues the device_put for batch t+1 while step t runs —
            # prep_batch work leaves this thread entirely. No
            # submission off-thread: ordered device dispatch (seeds,
            # snapshot schedule) stays HERE, so the trajectory is
            # bit-identical to the serial path.
            from ...learner.ingest import IngestPipeline

            def grouped():
                group: List[SparseBatch] = []
                for batch in batches:
                    # padding is derived from the FIRST batch exactly as
                    # on the serial path — pin it before parallel preps
                    # could race to different pads
                    if self._pads is None:
                        self._padding(batch)
                    # same for the stream wire's lane-split statics:
                    # pinned here on the feeder, before the pool forks
                    if (
                        self.sgd.wire == "stream"
                        and not self._stream_statics_set
                    ):
                        self._get_stream_statics(batch)
                    # key heat rides the FEEDER thread (this generator
                    # runs on the ingest pipeline's feeder) — the
                    # stateless-or-feeder home for the stateful sketch
                    with telemetry_spans.span("ingest.heat"):
                        self._note_heat(batch)
                    group.append(batch)
                    if len(group) >= T:
                        yield group
                        group = []
                if group:
                    yield group

            workers = self._ingest_workers()
            pipe = IngestPipeline(
                grouped(),
                prep_fn=self._prep_group,
                workers=workers,
                # the in-flight window must scale with the pool or the
                # extra workers idle (the pool admits at most `capacity`
                # groups); each staged group holds T prepped host
                # batches, so this is also the host-memory bound
                capacity=2 * workers,
                name="train_ingest",
            ).start()

            def flattened():
                for parts in pipe:
                    yield from parts

            # upload key caching (learner/wire.UploadCache): stateful,
            # so it lives on the uploader's serial thread (the PR-3
            # stateless-or-feeder ingest rule), never in the prep pool.
            # Multi-process keeps the plain path — global batch
            # assembly owns placement there.
            upload_fn = self.upload
            if self.sgd.wire_cache_mb > 0:
                from ...parallel import distributed

                if not distributed.is_multiprocess():
                    from ...learner.wire import UploadCache

                    upload_fn = UploadCache(
                        max_bytes=self.sgd.wire_cache_mb << 20
                    )
            uploader = DeviceUploader(flattened(), upload_fn, depth=2)
            try:
                for staged_batch, n in self._awaited(uploader):
                    # submit under the batch's flow id (popped FIFO from
                    # the uploader) so the executor.step span correlates
                    # back through upload → prep → read in the timeline
                    with telemetry_spans.flow_scope(
                        uploader.next_flow()
                    ), self._loop_phase("submit"):
                        pending.append(
                            (self._submit_prepped(
                                staged_batch, with_aux=True),
                             n)
                        )
                    while sum(n for _, n in pending) > bound:
                        self.collect(pending.pop(0)[0])
            finally:
                # close BEFORE the exception propagates out of this
                # frame: the traceback would otherwise pin the
                # generators (and their pipeline threads) alive past
                # train()'s cleanup, letting teardown kill a thread
                # mid-device-call
                uploader.close()
                pipe.close()
            for ts, _ in pending:
                self.collect(ts)
            return self.progress

        group: List[SparseBatch] = []

        def flush_group():
            if not group:
                return
            # unpipelined: prep and upload run here, inside the submit
            with self._loop_phase("submit"):
                pending.extend(
                    self.submit_group(list(group), with_aux=True)
                )
            group.clear()

        for batch in self._awaited(batches):
            self._note_heat(batch)
            group.append(batch)
            if len(group) >= T:
                flush_group()
            # collect finished steps opportunistically to keep memory flat
            while sum(n for _, n in pending) > bound:
                self.collect(pending.pop(0)[0])
        flush_group()
        for ts, _ in pending:
            self.collect(ts)
        return self.progress

    def weights_dense(self) -> np.ndarray:
        # drain in-flight steps (state advances on the executor thread)
        # WITHOUT popping: metrics stay claimable by a later collect()
        self.executor.wait_all(pop=False)
        n, window = self.num_slots, self._weights_window
        out = np.empty(n, np.float32)
        for lo in range(0, n, window):
            lo = min(lo, n - window)  # the last window may overlap
            # sixteen windows of a 2^30 table outlast the heartbeat
            # timeout: a worker writing its model out is not dead
            self.po.beat(self.name)
            out[lo:lo + window] = np.asarray(
                self._weights_fn(self.state, np.int32(lo))
            )
        return out

    def recover_server_shard(self, shard: int) -> bool:
        """Rebuild a dead server shard's slot segment from the live
        neighbor replica (ref Parameter::Recover pulling the dead node's
        key segment from kReplicaGroup). The restored segment is at most
        ``replica_every`` steps stale. Submitted through the executor so
        it is ordered with in-flight training steps."""
        if self._replica_state is None:
            return False
        n_servers = meshlib.num_servers(self.mesh)
        per = self.num_slots // n_servers

        def do_recover():
            seg = (jnp.arange(self.num_slots) // per) == shard

            def fix(prim, rep):
                if getattr(prim, "ndim", 0) < 1:
                    return prim
                recovered = jnp.roll(rep, -per, axis=0)
                m = seg.reshape((-1,) + (1,) * (prim.ndim - 1))
                return jnp.where(m, recovered, prim)

            self.state = jax.tree.map(fix, self.state, self._replica_state)
            self._pull_state = self.state
            return True

        ts = self.submit(do_recover)
        return bool(self.executor.wait(ts))

    def wipe_server_shard(self, shard: int) -> None:
        """Test/chaos helper: zero a shard's slot segment, simulating a
        replacement server that boots empty (ref recovery tests)."""
        n_servers = meshlib.num_servers(self.mesh)
        per = self.num_slots // n_servers

        def do_wipe():
            seg = (jnp.arange(self.num_slots) // per) == shard

            def z(prim):
                if getattr(prim, "ndim", 0) < 1:
                    return prim
                m = seg.reshape((-1,) + (1,) * (prim.ndim - 1))
                return jnp.where(m, jnp.zeros_like(prim), prim)

            self.state = jax.tree.map(z, self.state)
            self._pull_state = self.state

        self.executor.wait(self.submit(do_wipe))

    def evaluate(self, batch: SparseBatch) -> Dict[str, float]:
        """Validation metrics on a batch (ref COMPUTE_VALIDATION_AUC)."""
        w = self.weights_dense()
        slots = self.directory.slots(batch.indices)
        vals = batch.value_array()
        xw = np.zeros(batch.n, np.float32)
        contrib = np.where(slots < self.num_slots, w[np.minimum(slots, self.num_slots - 1)], 0.0)
        np.add.at(xw, batch.row_ids(), vals * contrib)
        return {
            "auc": evaluation.auc(batch.y, xw),
            "accuracy": evaluation.accuracy(batch.y, xw),
            "logloss": evaluation.logloss(batch.y, xw),
        }

    def save_model(self, path: str) -> List[str]:
        """Nonzero weights as key\\tvalue text, one file per server shard
        named ``{path}_S{k}`` (ref AsyncSGDServer::SaveModel writes
        ``file + "_" + MyNodeID()`` — example eval configs match
        ``model_S.*``). Shard k holds its owned slot range, exactly the
        device sharding of the table.

        With a hashed directory the original keys are unrecoverable, so the
        keys written are table slots and a ``#hashed <num_slots>`` header
        tells consumers (ModelEvaluation) to route lookups through the same
        hash. Exact directories write true global keys.
        """
        w = self.weights_dense()
        nz = np.flatnonzero(w)
        keys = self.directory.keys
        n_server = meshlib.num_servers(self.mesh)
        shard_size = self.num_slots // n_server
        written = []
        for s in range(n_server):
            spath = f"{path}_S{s}"
            sel = nz[(nz >= s * shard_size) & (nz < (s + 1) * shard_size)]
            with psfile.open_write(spath) as f:
                if self.directory.hashed:
                    # header modulus = the directory's CONFIGURED count
                    # (what evaluation must hash with), not the padded
                    # table size — they differ on non-divisible tables
                    f.write(f"#hashed\t{self.directory.num_slots}\n")
                    for i in sel:
                        f.write(f"{i}\t{float(w[i])!r}\n")
                else:
                    for i in sel:
                        if i < len(keys):
                            f.write(f"{keys[i]}\t{float(w[i])!r}\n")
            written.append(spath)
        return written

    # -- full-state checkpoint/resume (ref save_model_every_n_iter +
    #    Parameter::Recover: the durable analog of server replicas) --

    def state_host(self) -> dict:
        """Snapshot the full optimizer state to host memory (device->host,
        no files) — the live-migration path for elastic resizes (ref
        Parameter::GetReplica feeding manager.cc NodeAdd key-range moves)."""
        # pop=False: a mid-training snapshot must not swallow in-flight
        # steps' metrics — collect(ts) afterwards still accounts them
        self.executor.wait_all(pop=False)
        return {
            "state": jax.tree.map(np.asarray, self.state),
            "seed_counter": np.int64(self._seed_counter),
        }

    def load_state_host(self, snap: dict) -> None:
        """Install a host snapshot onto THIS worker's mesh — the receiving
        half of a live migration. The table may be padded differently
        under a different server count: the configured slots always carry
        over; only dead padding is trimmed or zero-extended."""
        def fit(leaf):
            leaf = np.asarray(leaf)
            if leaf.ndim >= 1 and leaf.shape[0] != self.num_slots:
                if leaf.shape[0] > self.num_slots:
                    leaf = leaf[: self.num_slots]
                else:
                    pad = np.zeros(
                        (self.num_slots - leaf.shape[0],) + leaf.shape[1:],
                        leaf.dtype,
                    )
                    leaf = np.concatenate([leaf, pad])
            spec = partlib.state_partition_spec(leaf)
            return jax.device_put(leaf, NamedSharding(self.mesh, spec))

        self.state = jax.tree.map(fit, snap["state"])
        self._pull_state = self.state
        self._steps_since_snapshot = 0
        self._replica_state = None
        self._seed_counter = int(snap["seed_counter"])

    # checkpoint: inherited from Checkpointable — state_host already
    # drains (pop=False) and carries the seed counter

    def restore(self, manager, step: Optional[int] = None) -> int:
        """Restore state from the latest (or given) checkpoint and return
        its step. Training resumed from here replays bit-identically:
        the seed counter (quantization noise stream) comes back too."""
        if step is None:
            step = manager.latest_step()
            assert step is not None, "no checkpoint found"
        like = {"state": self.state, "seed_counter": np.int64(0)}
        tree = manager.restore(step, like=like)
        self.state = jax.tree.map(
            lambda leaf: jax.device_put(
                np.asarray(leaf),
                NamedSharding(
                    self.mesh,
                    partlib.state_partition_spec(np.asarray(leaf)),
                ),
            ),
            tree["state"],
        )
        self._pull_state = self.state
        self._steps_since_snapshot = 0
        self._seed_counter = int(tree["seed_counter"])
        return step


class AsyncSGDScheduler(ISGDScheduler):
    """Workload dispatch + progress display (ref AsyncSGDScheduler)."""

    def __init__(self, conf: Config, name: str = "async_sgd_scheduler"):
        from ...learner.workload_pool import Workload, WorkloadPool

        sgd = conf.async_sgd or SGDConfig()
        load = Workload(
            files=list(conf.training_data.file),
            replica=sgd.num_data_pass,
            shuffle=True,
        )
        super().__init__(workload_pool=WorkloadPool(load), name=name)
        self.conf = conf

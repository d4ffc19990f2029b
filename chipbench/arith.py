"""Peaks of the chip and the bytes a kernel must move, kept with the
benchmark so that no later PR changes the roofline it is held to."""

from __future__ import annotations

import json
import os

_DTYPE_BYTES = {"float32": 4, "bfloat16": 2}


def peak(device_kind: str, what: str) -> float:
    """A peak of ``peaks.json``. An unlisted device is an error, never a
    default."""
    with open(os.path.join(os.path.dirname(__file__), "peaks.json")) as f:
        table = json.load(f)["by_device_kind"]
    if device_kind not in table:
        raise KeyError(
            f"device_kind {device_kind!r} is not in chipbench/peaks.json "
            f"(has {sorted(table)})"
        )
    return table[device_kind][what]


def sweep_bytes(shard_slots: int, state_dtype: str) -> int:
    """Bytes one dense FTRL sweep must move over a shard of
    ``shard_slots``: read z (f32), sqrt_n and the gradient (f32), write z
    and sqrt_n. 20 bytes a slot with f32 sqrt_n, 16 with bf16."""
    n = _DTYPE_BYTES[state_dtype]
    return shard_slots * (4 + n + 4 + 4 + n)

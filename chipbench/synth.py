"""Criteo-format text generated from a seed: the benchmark's traffic.

A copy of ``parameter_server_tpu/data/criteo_synth.py`` as of PR 21, kept
here so that the yardstick does not move when the program's file does.
Rows are fixed-width (1 label, 13 two-digit integer fields, 26
eight-hex-digit categorical fields, 275 bytes), categorical token
frequencies follow a power law (cube of a uniform) like real CTR logs,
and labels carry signal through a sparse ground-truth weight vector.
"""

from __future__ import annotations

import os

import numpy as np

_HEXD = np.frombuffer(b"0123456789abcdef", np.uint8)
ROW_BYTES = 275  # 1 label + 13 2-digit ints + 26 8-hex cats + 39 tabs + \n


def _write_chunk(f, rng, n: int, w_true: np.ndarray) -> None:
    p_cat = w_true.size
    u = rng.random((n, 26))
    cats = (u * u * u * p_cat).astype(np.int64)
    ints = rng.integers(10, 100, size=(n, 13))
    y = w_true[cats].sum(axis=1) > 0
    buf = np.empty((n, ROW_BYTES), np.uint8)
    buf[:, 0] = ord("0") + y
    buf[:, 1] = 9  # \t
    for j in range(13):
        c = 2 + 3 * j
        buf[:, c] = ord("0") + ints[:, j] // 10
        buf[:, c + 1] = ord("0") + ints[:, j] % 10
        buf[:, c + 2] = 9
    nib = (cats[:, :, None] >> np.arange(28, -4, -4)) & 0xF
    hexs = _HEXD[nib]  # [n, 26, 8] ascii
    for j in range(26):
        c = 41 + 9 * j
        buf[:, c : c + 8] = hexs[:, j]
        buf[:, c + 8] = 9
    buf[:, ROW_BYTES - 1] = 10  # \n
    buf.tofile(f)


def write_criteo_file(path: str, rows: int, vocabulary: int, seed: int) -> str:
    """Write ``rows`` rows to ``path`` atomically (a temporary name, then
    a rename). 5% of the categorical ``vocabulary`` carries a normal
    ground-truth weight."""
    os.makedirs(os.path.dirname(path), exist_ok=True)
    rng = np.random.default_rng(seed)
    w_true = (
        rng.normal(size=vocabulary) * (rng.random(vocabulary) < 0.05)
    ).astype(np.float32)
    with open(path + ".tmp", "wb") as f:
        left = rows
        while left > 0:
            n = min(left, 1 << 18)
            _write_chunk(f, rng, n, w_true)
            left -= n
    os.replace(path + ".tmp", path)
    return path

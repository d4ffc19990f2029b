"""The plain reference: sequential NumPy FTRL with L1 on hashed slots.

Written from the update rule (McMahan et al. 2013 as the OSDI'14
parameter server's ``FTRLEntry::Set`` applies it: per-coordinate rate
``alpha / (beta + sqrt_n)``, elastic-net proximal step) and independent of the
trainer under test. Its one import from the program is
``utils/murmur.hash_slots``, the key-to-slot map: that map is part of the
data's definition (which features collide), not of the system measured.
State is kept only for the slots the given minibatches touch, so a 2^30
table costs the host nothing.
"""

from __future__ import annotations

import numpy as np


def progressive_logloss(batches, num_slots: int, alpha: float, beta: float,
                        l1: float, l2: float = 0.0) -> float:
    """Mean logloss over ``batches`` (CSR, labels in {-1, +1}), each scored
    with the weights before its own update, then applied: what a trainer
    with no delay between minibatches reports."""
    from parameter_server_tpu.utils.murmur import hash_slots

    # one sort maps every entry's slot to its rank among the touched
    touched, ranks = np.unique(
        np.concatenate([hash_slots(b.indices, num_slots) for b in batches]),
        return_inverse=True,
    )
    ends = np.cumsum([b.nnz for b in batches])
    z = np.zeros(touched.size, np.float32)
    sqrt_n = np.zeros(touched.size, np.float32)
    total, examples = 0.0, 0
    for b, s in zip(batches, np.split(ranks, ends[:-1])):
        u, inv = np.unique(s, return_inverse=True)
        eta = alpha / (sqrt_n[u] + beta)
        zt = -z[u] * eta
        w = np.sign(zt) * np.maximum(np.abs(zt) - l1 * eta, 0.0)
        w /= 1.0 + l2 * eta
        x = np.ones(s.size, np.float32) if b.values is None else b.values
        rows = np.repeat(np.arange(b.n), np.diff(b.indptr))
        xw = np.bincount(rows, weights=w[inv] * x, minlength=b.n)
        y = b.y
        total += float(np.logaddexp(0.0, -y * xw).sum())
        examples += b.n
        p = 1.0 / (1.0 + np.exp(np.clip(y * xw, -60, 60)))
        g = np.bincount(
            inv, weights=(-y * p)[rows] * x, minlength=u.size
        ).astype(np.float32)
        n_new = np.sqrt(sqrt_n[u] ** 2 + g * g)
        z[u] += g - (n_new - sqrt_n[u]) / alpha * w
        sqrt_n[u] = n_new
    return total / examples

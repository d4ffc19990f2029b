"""NumPy FTRL oracle — the reference the device trainer's logloss is
held to (``chip_smoke.py``)."""

from __future__ import annotations

import numpy as np


class FtrlOracle:
    """NumPy FTRL on hashed slots — the device step's math
    (updaters.py FTRLUpdater / ref FTRLEntry::Set) restricted to touched
    slots, using the SAME murmur hash→slot localization. Sequential:
    comparable to a device run at ``max_delay=0``, where every step
    pulls the latest state (identical math modulo f32 reduction
    order)."""

    def __init__(self, num_slots: int, alpha: float, beta: float, l1: float):
        self.num_slots = num_slots
        self.alpha, self.beta, self.l1 = alpha, beta, l1
        self.z = np.zeros(num_slots, np.float32)
        self.sqrt_n = np.zeros(num_slots, np.float32)

    def step(self, batch) -> float:
        """One uniform-lane binary minibatch: returns the summed logloss
        (pre-update weights, matching the device metrics' objective)."""
        from ...utils.murmur import hash_slots

        n_rows = batch.n
        lanes = batch.nnz // n_rows
        slots = hash_slots(batch.indices, self.num_slots)
        u, inv = np.unique(slots, return_inverse=True)
        eta = self.alpha / (self.sqrt_n[u] + self.beta)
        zt = -self.z[u] * eta
        w_u = np.sign(zt) * np.maximum(np.abs(zt) - self.l1 * eta, 0.0)
        xw = w_u[inv].reshape(n_rows, lanes).sum(axis=1)
        y = batch.y
        ll = float(np.logaddexp(0.0, -y * xw).sum())
        tau = 1.0 / (1.0 + np.exp(np.clip(y * xw, -60, 60)))
        gr = (-y * tau).astype(np.float32)
        g_u = np.bincount(
            inv, weights=np.repeat(gr, lanes), minlength=u.size
        ).astype(np.float32)
        n_new = np.sqrt(self.sqrt_n[u] ** 2 + g_u**2)
        self.z[u] += g_u - (n_new - self.sqrt_n[u]) / self.alpha * w_u
        self.sqrt_n[u] = n_new
        return ll

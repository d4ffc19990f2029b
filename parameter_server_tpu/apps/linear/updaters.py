"""Server-side updaters: FTRL, AdaGrad, SGD.

Counterparts of the per-key entry structs in
``src/app/linear_method/async_sgd.h`` (FTRLEntry, AdaGradEntry, SGDEntry)
— vectorized over slots. Each updater defines:

- ``init(num_slots)``: struct-of-arrays state,
- ``weights(state_u)``: model weights from (gathered) state — FTRL derives
  w from (z, √n) exactly like FTRLEntry which "not necessary to store w",
- ``apply(state, grad, touched)``: the entry ``Set`` step, fused dense over
  a server shard with a touched mask (untouched slots pass through).

The same objects plug into KVMap as entries (parameter/kv_map.py protocol)
and into the fused SPMD train step (async_sgd.py).
"""

from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np

from .learning_rate import LearningRate
from .penalty import ElasticNet


class FTRLUpdater:
    """FTRL-proximal (ref FTRLEntry::Set, async_sgd.h:131-151):

        n' = sqrt(n² + g²); σ = (n' − n)/α; z += g − σ w; n = n'
        w = prox(−z·η, η),  η = lr.eval(n') = α/(n' + β)

    ``sqrt_n_dtype="bfloat16"`` stores the gradient-magnitude
    accumulator at half width (state 16 B/slot -> 12 B/slot; the
    single-chip slot ceiling grows ~1.33x). All MATH stays f32 —
    sqrt_n is widened at read and narrowed at write — and the narrow
    is STOCHASTICALLY rounded when the caller passes a ``seed``
    (the fused SPMD step does): deterministic truncation would stall
    the accumulator by absorption once n >> per-update increment,
    freezing the per-coordinate learning-rate decay for hot features
    (see ops/ftrl.py stochastic_round_bf16 / the kernel's on-core
    PRNG). Without a seed (the KVMap entry protocol) the narrow
    truncates deterministically — fine for short-lived tables,
    disclosed here. z, the model accumulator, is always f32.
    """

    def __init__(self, lr: LearningRate, penalty: ElasticNet,
                 sqrt_n_dtype=jnp.float32):
        self.lr = lr
        self.penalty = penalty
        self.sqrt_n_dtype = jnp.dtype(sqrt_n_dtype)

    def init(self, num_slots: int) -> Dict[str, jnp.ndarray]:
        return {
            "z": jnp.zeros(num_slots, jnp.float32),
            "sqrt_n": jnp.zeros(num_slots, self.sqrt_n_dtype),
        }

    def weights(self, state):
        eta = self.lr.eval(state["sqrt_n"].astype(jnp.float32))
        return self.penalty.proximal(-state["z"] * eta, eta)

    def apply(self, state, grad, touched, seed=None):
        z = state["z"]
        if self.lr.type == LearningRate.DECAY and z.ndim == 1:
            # fused op (ops/ftrl.py): Pallas single-HBM-pass kernel on
            # TPU (f32 AND bf16-sqrt_n variants — the bf16 kernel
            # stochastically rounds with the on-core PRNG), jnp
            # reference path elsewhere; the op owns every fallback
            from ...ops.ftrl import ftrl_update

            z_new, n_new = ftrl_update(
                z, state["sqrt_n"], grad, touched,
                alpha=self.lr.alpha, beta=self.lr.beta,
                l1=self.penalty.lambda1, l2=self.penalty.lambda2,
                seed=seed,
            )
            return {"z": z_new, "sqrt_n": n_new}
        if touched is None:  # unquantized push: membership == support
            touched = grad != 0
        sqrt_n = state["sqrt_n"].astype(jnp.float32)
        w = self.weights(state)
        sqrt_n_new = jnp.sqrt(sqrt_n * sqrt_n + grad * grad)
        sigma = (sqrt_n_new - sqrt_n) / self.lr.alpha
        z_new = z + grad - sigma * w
        masked_n = jnp.where(touched, sqrt_n_new, sqrt_n)
        if self.sqrt_n_dtype == jnp.bfloat16 and seed is not None:
            from ...ops.ftrl import stochastic_round_bf16

            # untouched slots round-trip exactly (their f32 value IS a
            # bf16 value), so the dither cannot drift idle slots
            masked_n = stochastic_round_bf16(masked_n, seed)
        return {
            "z": jnp.where(touched, z_new, z),
            "sqrt_n": masked_n.astype(self.sqrt_n_dtype),
        }


class AdaGradUpdater:
    """AdaGrad (ref AdaGradEntry::Set): sum_sq += g²;
    w = prox(w − η g, η), η = lr.eval(√sum_sq)."""

    def __init__(self, lr: LearningRate, penalty: ElasticNet):
        self.lr = lr
        self.penalty = penalty

    def init(self, num_slots: int) -> Dict[str, jnp.ndarray]:
        return {
            "w": jnp.zeros(num_slots, jnp.float32),
            "sum_sq": jnp.zeros(num_slots, jnp.float32),
        }

    def weights(self, state):
        return state["w"]

    def apply(self, state, grad, touched, seed=None):
        if touched is None:  # unquantized push: membership == support
            touched = grad != 0
        sum_sq = state["sum_sq"] + grad * grad
        eta = self.lr.eval(jnp.sqrt(sum_sq))
        w = self.penalty.proximal(state["w"] - eta * grad, eta)
        return {
            "w": jnp.where(touched, w, state["w"]),
            "sum_sq": jnp.where(touched, sum_sq, state["sum_sq"]),
        }


class SGDUpdater:
    """Plain (proximal) SGD with a global step count — the reference's
    commented-out SGDEntry, completed: w = prox(w − η g, η), η = lr.eval(√t)."""

    def __init__(self, lr: LearningRate, penalty: ElasticNet):
        self.lr = lr
        self.penalty = penalty

    def init(self, num_slots: int) -> Dict[str, jnp.ndarray]:
        return {
            "w": jnp.zeros(num_slots, jnp.float32),
            "t": jnp.zeros((), jnp.float32),
        }

    def weights(self, state):
        return state["w"]

    def apply(self, state, grad, touched, seed=None):
        if touched is None:  # unquantized push: membership == support
            touched = grad != 0
        t = state["t"] + 1.0
        eta = self.lr.eval(jnp.sqrt(t))
        w = self.penalty.proximal(state["w"] - eta * grad, eta)
        return {"w": jnp.where(touched, w, state["w"]), "t": t}


def apply_state_rows(updater, state, rel, ok, g_u, seed=None, *,
                     rows_ascend=False, force_pallas=False,
                     interpret=False):
    """Sparse-touched update: run ``updater.apply`` on just the gathered
    rows ``rel`` of a server shard and scatter the results back.

    The big-table formulation — the reference's servers only ever run
    the per-key entry ``Set`` on RECEIVED keys (async_sgd.h:131-151,
    kv_map's per-message loop); the dense whole-shard sweep is the
    TPU-friendly variant that wins at small tables, but per ministep it
    moves O(shard) HBM traffic and needs a dense gradient temp — at
    2^30 slots that sweep is ~130 ms and at 2^31 the f32 temp alone
    (8.6 GB) pushes the table off-chip. This form moves
    O(unique-touched) state instead: gather the touched rows, update
    them with the SAME per-row math (so every updater and the Pallas
    FTRL kernel apply unchanged), scatter the new rows back.

    ``rel`` must be unique among ``ok`` entries — host prep dedups at
    slot level (hash collisions included) because the update is
    nonlinear in the summed gradient. Non-owned/padding entries
    (``ok`` False) are dropped by the write-back (``ops/rows.py``: the
    one scatter this body shares with ``ftrl_sparse_rows_ref``, and the
    unsigned past-the-end index that drops them). Their gradient is
    zeroed so the rows they DO gather (clipped indices) can't perturb
    anything. An ``ok`` row whose ``g_u`` is exactly 0 — a slot the KKT
    filter masked, a feature whose examples cancelled — is written back
    with the bits it was read with: every updater's membership is
    ``g != 0``, and the bf16 narrow of an unchanged value round-trips.
    Scalar state leaves (e.g. SGDUpdater's step count) take the updated
    value directly — there is nothing to scatter.

    ``rows_ascend`` is the caller's promise, made from what is static
    at trace time, that the ``ok`` entries of ``rel`` ascend and every
    non-``ok`` entry sits behind them: ``localize`` of prep's sorted
    unique ``uslots`` on ONE server shard. The write-back then tells
    XLA its indices are sorted, which is most of what a scatter into a
    2^30-slot table costs (``ops/rows.py``); the result is bit-equal
    either way.

    FTRL/decay takes the FUSED path when the shapes allow it
    (ops/ftrl_sparse.py — one Pallas gather→update→scatter pass over
    the touched 128-lane rows instead of four XLA dispatches, in-place
    via input_output_aliases); ``use_sparse_kernel`` is the testable
    path predicate and every fallback is bit-identical to the generic
    gather/apply/scatter below. ``force_pallas``/``interpret`` pin the
    kernel for parity tests and A/B sweeps (never onto a shape it
    cannot tile).
    """
    # the duplicate-free contract, asserted where it CAN be (concrete
    # host arrays — direct calls and tests; traced production inputs
    # are guaranteed by prep's slot-level np.unique): the update is
    # nonlinear in the summed gradient, so a duplicated ok row would
    # silently double-apply in BOTH formulations. The order promise is
    # checked the same way: the CPU ignores a wrong one, the chip does
    # not
    if isinstance(rel, np.ndarray) and isinstance(ok, np.ndarray):
        okb = np.asarray(ok, bool)
        r = rel[okb]
        assert len(np.unique(r)) == len(r), (
            "apply_state_rows: rel must be duplicate-free among ok "
            "entries (host prep dedups at slot level)"
        )
        assert not rows_ascend or (
            np.all(np.diff(r.astype(np.int64)) > 0)
            and np.all(okb[1:] <= okb[:-1])
        ), (
            "apply_state_rows: rows_ascend promises ascending ok rows "
            "with every non-ok entry behind them"
        )
    from ...ops.rows import write_index, write_rows
    from .learning_rate import LearningRate

    if (
        isinstance(updater, FTRLUpdater)
        and updater.lr.type == LearningRate.DECAY
        and state["z"].ndim == 1
    ):
        from ...ops import ftrl_sparse

        if ftrl_sparse.use_sparse_kernel(
            state["z"].shape[0], rel.shape[0],
            updater.sqrt_n_dtype == jnp.bfloat16, force_pallas,
        ):
            z_new, n_new = ftrl_sparse.ftrl_sparse_update(
                state["z"], state["sqrt_n"], rel, ok, g_u,
                alpha=updater.lr.alpha, beta=updater.lr.beta,
                l1=updater.penalty.lambda1, l2=updater.penalty.lambda2,
                seed=seed, force_pallas=force_pallas,
                interpret=interpret,
            )
            return {"z": z_new, "sqrt_n": n_new}
    state_u = jax.tree.map(lambda a: a[rel] if a.ndim >= 1 else a, state)
    new_u = updater.apply(state_u, jnp.where(ok, g_u, 0.0), None, seed=seed)

    def _write_back(full, new_leaf):
        if full.ndim < 1:
            return new_leaf
        idx = write_index(rel, ok, full.shape[0])
        return write_rows(full, idx, new_leaf, rows_ascend=rows_ascend)

    return jax.tree.map(_write_back, state, new_u)


def create_updater(algo: str, ada_grad: bool, lr: LearningRate,
                   penalty: ElasticNet, ftrl_state_dtype: str = "float32"):
    """ref AsyncSGDServer ctor dispatch (async_sgd.h:46-58)."""
    a = algo.lower()
    if a == "ftrl":
        return FTRLUpdater(lr, penalty, sqrt_n_dtype=ftrl_state_dtype)
    if a == "standard":
        return AdaGradUpdater(lr, penalty) if ada_grad else SGDUpdater(lr, penalty)
    raise ValueError(f"unknown sgd algo: {algo}")

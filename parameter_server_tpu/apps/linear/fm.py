"""Factorization machine on the parameter-server pipeline (TPU-first).

Beyond-parity extension: the reference's linear_method family covers
linear models (its sibling project DiFacto adds FM); this module brings
second-order feature interactions to the same ELL/mesh machinery so a
CTR user of the framework gets FM without leaving it.

Model (binary features, the CTR case):

    f(x) = b + sum_i w_i + 0.5 * (||sum_i v_i||^2 - sum_i ||v_i||^2)

over the active slots i of a row — the O(nnz * k) identity for the
pairwise term. Embeddings live in a ``[slots, k]`` table sharded over the
server mesh axis exactly like the linear table (key-range sharding);
gradients scatter-add per shard and psum across the data axis, and every
parameter updates with AdaGrad + proximal elastic-net (ref
AdaGradEntry::Set semantics, async_sgd.h).

The wire is the ELL row-block format from async_sgd (``prep_batch_ell``):
uniform lanes, hashed directory, binary features.
"""

from __future__ import annotations

from typing import Dict, Optional

import functools

import jax
import jax.numpy as jnp
import numpy as np

from jax import shard_map
from jax.sharding import NamedSharding, PartitionSpec as P

from ...learner.sgd import ISGDCompNode, SGDProgress
from ...ops.kv_ops import localize, valid_slots
from ...parallel import mesh as meshlib
from ...parallel.mesh import DATA_AXIS, SERVER_AXIS
from ...parameter.parameter import KeyDirectory, pad_slots
from ...system.message import Task
from ...utils import evaluation
from ...utils.sparse import SparseBatch
from .async_sgd import _progress_metrics
from .config import Config
from .learning_rate import LearningRate
from .loss import create_loss
from .penalty import create_penalty


def make_fm_step(
    mesh,
    num_slots: int,
    k: int,
    loss,
    penalty,
    lr: LearningRate,
    v_lr_scale: float,
    with_aux: bool = True,
):
    """Fused SPMD FM step over an ELLBatch (binary): pull w and V at the
    batch's slots, forward with the O(nnz*k) pairwise identity, scatter
    per-slot gradients, AdaGrad-update both tables + the global bias."""
    n_server = meshlib.num_servers(mesh)
    shard = num_slots // n_server

    def local_step(state, y, mask, slots):
        y, mask, slots = y[0], mask[0], slots[0]  # [R], [R], [R, K]
        flat = slots.reshape(-1)
        rel, ok = localize(flat, shard)

        # -- pull: gather w and V entries from the owning shard --
        w_e = jax.lax.psum(
            jnp.where(ok, state["w"][rel], 0.0), SERVER_AXIS
        ).reshape(slots.shape)  # [R, K]
        v_e = jax.lax.psum(
            jnp.where(ok[:, None], state["v"][rel], 0.0), SERVER_AXIS
        ).reshape(slots.shape + (k,))  # [R, K, k]
        live = valid_slots(slots, num_slots).astype(jnp.float32)  # sentinels -> 0
        w_e = w_e * live
        v_e = v_e * live[..., None]

        # -- forward: linear + O(nnz*k) pairwise identity --
        s = v_e.sum(axis=1)  # [R, k]
        pair = 0.5 * (
            jnp.sum(s * s, axis=1) - jnp.sum(v_e * v_e, axis=(1, 2))
        )  # [R]
        xw = state["b"] + w_e.sum(axis=1) + pair

        gr = loss.row_grad(y, xw) * mask  # [R]

        # -- push: per-entry grads, scatter-add into the owned shard --
        gw_flat = jnp.broadcast_to(gr[:, None], slots.shape).reshape(-1)
        gv = gr[:, None, None] * (s[:, None, :] - v_e)  # [R, K, k]
        gv_flat = gv.reshape(-1, k)
        lanes_live = (live.reshape(-1) > 0) & ok
        g_w = jnp.zeros((shard,), jnp.float32).at[rel].add(
            jnp.where(lanes_live, gw_flat, 0.0)
        )
        g_v = jnp.zeros((shard, k), jnp.float32).at[rel].add(
            jnp.where(lanes_live[:, None], gv_flat, 0.0)
        )
        g_w = jax.lax.psum(g_w, DATA_AXIS)
        g_v = jax.lax.psum(g_v, DATA_AXIS)
        g_b = jax.lax.psum(jnp.sum(gr), DATA_AXIS)
        touched = g_w != 0  # FM embeddings ride the linear support

        # -- AdaGrad + proximal update (ref AdaGradEntry::Set) --
        w_ss = state["w_ss"] + g_w * g_w
        eta_w = lr.eval(jnp.sqrt(w_ss))
        w_new = penalty.proximal(state["w"] - eta_w * g_w, eta_w)
        v_ss = state["v_ss"] + g_v * g_v
        eta_v = v_lr_scale * lr.eval(jnp.sqrt(v_ss))
        v_new = state["v"] - eta_v * g_v  # embeddings: no L1 (dense factors)
        b_ss = state["b_ss"] + g_b * g_b
        b_new = state["b"] - lr.eval(jnp.sqrt(b_ss)) * g_b

        new_state = {
            "w": jnp.where(touched, w_new, state["w"]),
            "w_ss": jnp.where(touched, w_ss, state["w_ss"]),
            "v": jnp.where(touched[:, None], v_new, state["v"]),
            "v_ss": jnp.where(touched[:, None], v_ss, state["v_ss"]),
            "b": b_new,
            "b_ss": b_ss,
        }
        return new_state, _progress_metrics(loss, y, xw, mask, with_aux)

    def state_spec(state):
        return jax.tree.map(
            lambda leaf: P(SERVER_AXIS) if leaf.ndim >= 1 else P(), state
        )

    # donate the sharded tables: the update writes them anyway and
    # the worker always rebinds (self.state = new_state); aliasing
    # input->output halves the table HBM footprint (as in async_sgd)
    @functools.partial(jax.jit, donate_argnums=(0,))
    def step(state, batch_y, batch_mask, batch_slots):
        specs = state_spec(state)
        return shard_map(
            local_step,
            mesh=mesh,
            in_specs=(specs, P(DATA_AXIS), P(DATA_AXIS), P(DATA_AXIS)),
            out_specs=(specs, P()),
            check_vma=False,
        )(state, batch_y, batch_mask, batch_slots)

    return step


class FMWorker(ISGDCompNode):
    """Async FM trainer on the data x server mesh.

    Same consumption API as AsyncSGDWorker (``process_minibatch`` /
    ``collect`` / ``train`` / ``evaluate``); the table is hashed with the
    configured modulus (elastic-resize stable) and the batch wire is the
    ELL row-block format."""

    def __init__(
        self,
        conf: Config,
        k: int = 8,
        mesh=None,
        v_init_std: float = 0.01,
        v_lr_scale: float = 1.0,
        seed: int = 0,
        name: str = "fm_worker",
    ):
        super().__init__(name=name)
        sgd = conf.async_sgd
        assert sgd is not None and sgd.ell_lanes > 0, (
            "FM needs async_sgd conf with ell_lanes (uniform ELL rows)"
        )
        if mesh is None:
            mesh = self.po.mesh
        self.mesh = mesh
        self.sgd = sgd
        self.k = int(k)
        self.loss = create_loss(conf.loss.type)
        self.penalty = create_penalty(conf.penalty.type, conf.penalty.lambda_)
        self.lr = LearningRate(
            conf.learning_rate.type, conf.learning_rate.alpha,
            conf.learning_rate.beta,
        )
        self.num_slots = pad_slots(sgd.num_slots, meshlib.num_servers(mesh))
        self.directory = KeyDirectory(sgd.num_slots, hashed=True)
        # direct-to-sharded init (rationale at meshlib.init_sharded);
        # v uses on-device PRNG so the table never crosses the host link
        def _init():
            n, k = self.num_slots, self.k
            return {
                "w": jnp.zeros((n,), jnp.float32),
                "w_ss": jnp.zeros((n,), jnp.float32),
                "v": v_init_std * jax.random.normal(
                    jax.random.PRNGKey(seed), (n, k), jnp.float32
                ),
                "v_ss": jnp.zeros((n, k), jnp.float32),
                "b": jnp.zeros((), jnp.float32),
                "b_ss": jnp.zeros((), jnp.float32),
            }

        self.state = meshlib.init_sharded(_init, mesh)
        self._step = make_fm_step(
            mesh, self.num_slots, self.k, self.loss, self.penalty, self.lr,
            v_lr_scale,
        )
        self._rows_pad: Optional[int] = None
        self.progress = SGDProgress()

    def process_minibatch(self, batch: SparseBatch) -> int:
        prepped = self._prep_ell(batch)  # shared base prep (ISGDCompNode)

        def run():
            new_state, metrics = self._step(
                self.state, prepped.y, prepped.mask, prepped.slots
            )
            self.state = new_state
            return metrics

        return self.submit(run, Task())

    def wipe_server_shard(self, shard: int) -> None:
        """Simulate/acknowledge a dead server shard: zero its segment
        (same contract as AsyncSGDWorker.wipe_server_shard)."""
        n_server = meshlib.num_servers(self.mesh)
        per = self.num_slots // n_server
        lo, hi = shard * per, (shard + 1) * per

        def z(leaf):
            if np.ndim(leaf) >= 1:
                return leaf.at[lo:hi].set(0.0)
            return leaf

        self.executor.wait_all(pop=False)
        self.state = jax.tree.map(z, self.state)

    def recover_server_shard(self, shard: int) -> bool:
        """FM keeps no ongoing replica (configure checkpoints for
        durability): crash recovery reports failure so the elastic
        coordinator shrinks around the dead range instead."""
        del shard
        return False

    # collect/train: inherited from ISGDCompNode (shared worker plumbing)

    def state_host(self) -> dict:
        """Host snapshot for live migration (same contract as
        AsyncSGDWorker.state_host — ElasticCoordinator.resize uses it)."""
        self.executor.wait_all(pop=False)
        return {"state": jax.tree.map(np.asarray, self.state)}

    def load_state_host(self, snap: dict) -> None:
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
            return jax.device_put(
                leaf,
                NamedSharding(
                    self.mesh, P(SERVER_AXIS, *([None] * (np.ndim(leaf) - 1)))
                    if np.ndim(leaf) >= 1 else P()
                ),
            )

        self.state = jax.tree.map(fit, snap["state"])

    def predict_margin(self, batch: SparseBatch) -> np.ndarray:
        """Host-side vectorized forward pass (evaluation path): per-row
        segment sums via ``np.add.reduceat`` — O(nnz*k), no Python loop."""
        # settle in-flight steps (state swaps on the executor thread) so
        # the margin reads ONE consistent state version, not a mix
        self.executor.wait_all(pop=False)
        w = np.asarray(self.state["w"]).astype(np.float64)
        v = np.asarray(self.state["v"]).astype(np.float64)
        b = float(self.state["b"])
        if batch.n == 0:
            return np.zeros(0, np.float32)
        slots = self.directory.slots(batch.indices)
        counts = np.diff(batch.indptr)
        seg = batch.indptr[:-1].astype(np.int64)
        # reduceat misbehaves on empty segments (repeated offsets) — mask
        # those rows to the bias afterwards
        safe_seg = np.minimum(seg, max(batch.nnz - 1, 0))
        vs = v[slots]  # [nnz, k]
        sum_w = np.add.reduceat(w[slots], safe_seg) if batch.nnz else np.zeros(batch.n)
        sum_v = np.add.reduceat(vs, safe_seg, axis=0) if batch.nnz else np.zeros((batch.n, v.shape[1]))
        sum_v2 = (
            np.add.reduceat((vs * vs).sum(axis=1), safe_seg)
            if batch.nnz
            else np.zeros(batch.n)
        )
        out = b + sum_w + 0.5 * ((sum_v * sum_v).sum(axis=1) - sum_v2)
        out = np.where(counts > 0, out, b)
        return out.astype(np.float32)

    def evaluate(self, batch: SparseBatch) -> Dict[str, float]:
        xw = self.predict_margin(batch)
        y = batch.y
        ll = float(np.mean(np.logaddexp(0.0, -y * xw)))
        return {"auc": evaluation.auc(y, xw), "logloss": ll}

"""Wide & Deep CTR model on the parameter-server pipeline (TPU-first).

Beyond-parity extension, one rung up from :mod:`fm`: the classic deep-CTR
architecture — a WIDE linear term over hashed sparse features plus a DEEP
MLP over concatenated per-lane embeddings — on the exact same ELL/mesh
machinery as the linear and FM apps, so the sparse side still rides the
sharded parameter-server tables:

    f(x) = b + sum_i w_i  +  MLP([e_1 | e_2 | ... | e_K])      e_i = V[slot_i]

with ``w`` ([slots]) and ``V`` ([slots, k]) key-range-sharded over the
server mesh axis (pull = masked gather + psum, push = scatter-add into
the owning shard + psum over the data axis — KVVector semantics, ref
``parameter/kv_vector.h``), and the dense MLP replicated like a small
KVLayer (below the partition threshold, ref ``parameter/kv_layer.h``).
The deep gradients come from ``jax.vjp`` of the fused forward instead of
hand-derived chain rule — the functional-transform dividend of the
TPU-first design. Everything updates with AdaGrad (+ proximal L1 on the
wide table only; ref AdaGradEntry::Set, async_sgd.h).

The wire is the ELL row-block format from async_sgd (``prep_batch_ell``):
uniform lanes, hashed directory, binary features — for criteo each of the
39 lanes IS a feature slot, so the concatenated embedding layout matches
the per-slot embedding-bag structure of production CTR models.
"""

from __future__ import annotations

from typing import Dict, Optional, Sequence

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


def _mlp_forward(h, mlp):
    """ReLU MLP over [R, lanes*k] -> [R] (jnp; mirrored in numpy below)."""
    n_layers = len(mlp) // 2
    for i in range(n_layers - 1):
        h = jax.nn.relu(h @ mlp[2 * i] + mlp[2 * i + 1])
    return (h @ mlp[-2] + mlp[-1])[:, 0]


def make_deep_ctr_step(
    mesh,
    num_slots: int,
    k: int,
    lanes: int,
    loss,
    penalty,
    lr: LearningRate,
    with_aux: bool = True,
):
    """Fused SPMD wide&deep step over an ELLBatch (binary): pull w and V
    at the batch's slots, forward wide+deep, vjp the deep part, scatter
    per-slot gradients, AdaGrad-update tables + MLP + bias."""
    n_server = meshlib.num_servers(mesh)
    shard = num_slots // n_server

    def local_step(state, y, mask, slots):
        y, mask, slots = y[0], mask[0], slots[0]  # [R], [R], [R, K]
        flat = slots.reshape(-1)
        rel, ok = localize(flat, shard)

        # -- pull: gather w and V entries from the owning shard --
        w_e = jax.lax.psum(
            jnp.where(ok, state["table"]["w"][rel], 0.0), SERVER_AXIS
        ).reshape(slots.shape)  # [R, K]
        v_e = jax.lax.psum(
            jnp.where(ok[:, None], state["table"]["v"][rel], 0.0), SERVER_AXIS
        ).reshape(slots.shape + (k,))  # [R, K, k]
        live = valid_slots(slots, num_slots).astype(jnp.float32)  # sentinels -> 0
        mlp = state["mlp"]

        def fwd(v_e, mlp):
            # live-mask INSIDE the differentiated fn so sentinel-lane
            # embedding gradients vanish through the vjp
            e = (v_e * live[..., None]).reshape(v_e.shape[0], lanes * k)
            return state["b"] + (w_e * live).sum(axis=1) + _mlp_forward(e, mlp)

        xw, pullback = jax.vjp(fwd, v_e, mlp)
        gr = loss.row_grad(y, xw) * mask  # [R]
        g_ve, g_mlp = pullback(gr)

        # -- push: wide grads per entry; deep grads from the vjp --
        gw_flat = (jnp.broadcast_to(gr[:, None], slots.shape) * live).reshape(-1)
        gv_flat = g_ve.reshape(-1, k)
        g_w = jnp.zeros((shard,), jnp.float32).at[rel].add(
            jnp.where(ok, gw_flat, 0.0)
        )
        g_v = jnp.zeros((shard, k), jnp.float32).at[rel].add(
            jnp.where(ok[:, None], gv_flat, 0.0)
        )
        g_w = jax.lax.psum(g_w, DATA_AXIS)
        g_v = jax.lax.psum(g_v, DATA_AXIS)
        g_mlp = jax.lax.psum(g_mlp, DATA_AXIS)
        g_b = jax.lax.psum(jnp.sum(gr), DATA_AXIS)
        touched = (g_w != 0) | (jnp.abs(g_v).sum(axis=1) != 0)

        # -- AdaGrad updates (proximal L1 on the wide table only) --
        w_ss = state["table"]["w_ss"] + g_w * g_w
        eta_w = lr.eval(jnp.sqrt(w_ss))
        w_new = penalty.proximal(state["table"]["w"] - eta_w * g_w, eta_w)
        v_ss = state["table"]["v_ss"] + g_v * g_v
        v_new = state["table"]["v"] - lr.eval(jnp.sqrt(v_ss)) * g_v
        mlp_ss = [s + g * g for s, g in zip(state["mlp_ss"], g_mlp)]
        mlp_new = [
            p - lr.eval(jnp.sqrt(s)) * g
            for p, s, g in zip(mlp, mlp_ss, g_mlp)
        ]
        b_ss = state["b_ss"] + g_b * g_b
        b_new = state["b"] - lr.eval(jnp.sqrt(b_ss)) * g_b

        new_state = {
            "table": {
                "w": jnp.where(touched, w_new, state["table"]["w"]),
                "w_ss": jnp.where(touched, w_ss, state["table"]["w_ss"]),
                "v": jnp.where(touched[:, None], v_new, state["table"]["v"]),
                "v_ss": jnp.where(
                    touched[:, None], v_ss, state["table"]["v_ss"]
                ),
            },
            "mlp": mlp_new,
            "mlp_ss": mlp_ss,
            "b": b_new,
            "b_ss": b_ss,
        }
        return new_state, _progress_metrics(loss, y, xw, mask, with_aux)

    def state_spec(state):
        return {
            "table": jax.tree.map(
                lambda leaf: P(SERVER_AXIS) if leaf.ndim >= 1 else P(),
                state["table"],
            ),
            "mlp": jax.tree.map(lambda _: P(), state["mlp"]),
            "mlp_ss": jax.tree.map(lambda _: P(), state["mlp_ss"]),
            "b": P(),
            "b_ss": P(),
        }

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


class DeepCTRWorker(ISGDCompNode):
    """Async wide&deep trainer on the data x server mesh.

    Same consumption API as AsyncSGDWorker/FMWorker (``process_minibatch``
    / ``collect`` / ``train`` / ``evaluate`` / ``state_host``); the table
    is hashed with the configured modulus (elastic-resize stable) and the
    batch wire is the ELL row-block format."""

    def __init__(
        self,
        conf: Config,
        k: int = 8,
        hidden: Sequence[int] = (64, 32),
        mesh=None,
        v_init_std: float = 0.01,
        seed: int = 0,
        name: str = "deep_ctr_worker",
    ):
        super().__init__(name=name)
        sgd = conf.async_sgd
        assert sgd is not None and sgd.ell_lanes > 0, (
            "deep CTR needs async_sgd conf with ell_lanes (uniform ELL rows)"
        )
        if mesh is None:
            mesh = self.po.mesh
        self.mesh = mesh
        self.sgd = sgd
        self.k = int(k)
        self.lanes = int(sgd.ell_lanes)
        self.hidden = tuple(int(h) for h in hidden)
        self.loss = create_loss(conf.loss.type)
        self.penalty = create_penalty(conf.penalty.type, conf.penalty.lambda_)
        self.lr = LearningRate(
            conf.learning_rate.type, conf.learning_rate.alpha,
            conf.learning_rate.beta,
        )
        self.num_slots = pad_slots(sgd.num_slots, meshlib.num_servers(mesh))
        self.directory = KeyDirectory(sgd.num_slots, hashed=True)
        rng = np.random.default_rng(seed)
        dims = (self.lanes * self.k,) + self.hidden + (1,)
        mlp = []
        for d_in, d_out in zip(dims[:-1], dims[1:]):
            # the MLP is small and replicated: host init is fine (and
            # keeps He-init reproducibility with numpy rng)
            mlp.append(
                jnp.asarray(
                    rng.normal(0.0, np.sqrt(2.0 / d_in), (d_in, d_out)),
                    jnp.float32,
                )
            )
            mlp.append(jnp.zeros((d_out,), jnp.float32))

        # the server-sharded TABLE (the scale-bearing state) goes
        # direct-to-sharded (rationale at meshlib.init_sharded)
        def _init_table():
            n, k = self.num_slots, self.k
            return {
                "w": jnp.zeros((n,), jnp.float32),
                "w_ss": jnp.zeros((n,), jnp.float32),
                "v": v_init_std * jax.random.normal(
                    jax.random.PRNGKey(seed), (n, k), jnp.float32
                ),
                "v_ss": jnp.zeros((n, k), jnp.float32),
            }

        table = meshlib.init_sharded(_init_table, mesh)
        self.state = {
            "table": table,
            "mlp": mlp,
            "mlp_ss": [jnp.zeros_like(p) for p in mlp],
            "b": jnp.zeros((), jnp.float32),
            "b_ss": jnp.zeros((), jnp.float32),
        }
        self._step = make_deep_ctr_step(
            mesh, self.num_slots, self.k, self.lanes, self.loss,
            self.penalty, self.lr,
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
        """Zero a dead server shard's TABLE segment (the replicated MLP
        survives a server death by construction — every rank holds it)."""
        n_server = meshlib.num_servers(self.mesh)
        per = self.num_slots // n_server
        lo, hi = shard * per, (shard + 1) * per
        self.executor.wait_all(pop=False)
        self.state["table"] = jax.tree.map(
            lambda leaf: leaf.at[lo:hi].set(0.0), self.state["table"]
        )

    def recover_server_shard(self, shard: int) -> bool:
        """No ongoing replica (configure checkpoints for durability):
        report failure so the elastic coordinator reshards around it."""
        del shard
        return False

    # collect/train: inherited from ISGDCompNode (shared worker plumbing)

    def state_host(self) -> dict:
        """Host snapshot for live migration (ElasticCoordinator.resize)."""
        self.executor.wait_all(pop=False)
        return {"state": jax.tree.map(np.asarray, self.state)}

    def load_state_host(self, snap: dict) -> None:
        def fit_table(leaf):
            leaf = np.asarray(leaf)
            if leaf.shape[0] != self.num_slots:
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
                    self.mesh, P(SERVER_AXIS, *([None] * (leaf.ndim - 1)))
                ),
            )

        st = snap["state"]
        self.state = {
            "table": jax.tree.map(fit_table, st["table"]),
            "mlp": [jnp.asarray(p) for p in st["mlp"]],
            "mlp_ss": [jnp.asarray(p) for p in st["mlp_ss"]],
            "b": jnp.asarray(st["b"]),
            "b_ss": jnp.asarray(st["b_ss"]),
        }

    def predict_margin(self, batch: SparseBatch) -> np.ndarray:
        """Host-side vectorized forward (evaluation path): the SAME
        lanes-layout as the device step — short rows pad with sentinel
        (zero) embeddings; rows WIDER than the lane budget are rejected
        exactly like the training path (never silently drop features)."""
        # settle in-flight steps (state swaps on the executor thread) so
        # the margin reads ONE consistent state version, not a mix
        self.executor.wait_all(pop=False)
        w = np.asarray(self.state["table"]["w"]).astype(np.float64)
        v = np.asarray(self.state["table"]["v"]).astype(np.float64)
        mlp = [np.asarray(p).astype(np.float64) for p in self.state["mlp"]]
        b = float(self.state["b"])
        if batch.n == 0:
            return np.zeros(0, np.float32)
        lanes, kk = self.lanes, self.k
        counts = np.diff(batch.indptr)
        if counts.max(initial=0) > lanes:
            raise ValueError(
                f"row with {int(counts.max())} features exceeds the ELL "
                f"lane budget ({lanes}); predict_margin refuses to drop "
                "features (same contract as the training path)"
            )
        slots = self.directory.slots(batch.indices)
        # scatter the CSR stream into a dense [n, lanes] lane matrix
        mat = np.zeros((batch.n, lanes), np.int64)
        ok = np.arange(lanes)[None, :] < counts[:, None]
        rows_idx = np.repeat(np.arange(batch.n), counts)
        lane_idx = np.arange(batch.nnz) - np.repeat(
            batch.indptr[:-1].astype(np.int64), counts
        )
        mat[rows_idx, lane_idx] = slots
        e = v[mat] * ok[..., None]  # [n, lanes, k]
        wide = (w[mat] * ok).sum(axis=1)
        h = e.reshape(batch.n, lanes * kk)
        for i in range(len(mlp) // 2 - 1):
            h = np.maximum(h @ mlp[2 * i] + mlp[2 * i + 1], 0.0)
        deep = (h @ mlp[-2] + mlp[-1])[:, 0]
        return (b + wide + deep).astype(np.float32)

    def evaluate(self, batch: SparseBatch) -> Dict[str, float]:
        xw = self.predict_margin(batch)
        y = batch.y
        ll = float(np.mean(np.logaddexp(0.0, -y * xw)))
        return {"auc": evaluation.auc(y, xw), "logloss": ll}

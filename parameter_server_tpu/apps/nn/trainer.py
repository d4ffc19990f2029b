"""Neural-net worker trained through KVLayer dense push/pull.

Role of the reference's CXXNET/Minerva integration: the NN worker computes
layer gradients, pushes them to the KVLayer servers whose Updater applies
the optimizer, and pulls fresh weights each minibatch (kv_layer.h Push/Pull
with partition_thr slicing).

TPU-native: one fused SPMD step — per-data-shard forward/backward inside
``shard_map``, gradient ``psum`` over the data axis (the push), optimizer
update (the server-side Updater), all compiled together. The KVLayer object
remains the parameter store (sharding per its partition threshold) so the
replica/checkpoint machinery applies unchanged.
"""

from __future__ import annotations

from typing import Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from jax import shard_map
from jax.sharding import PartitionSpec as P

from ...models.convnet import cross_entropy
from ...parallel import mesh as meshlib
from ...parallel.mesh import DATA_AXIS
from ...parameter.kv_layer import KVLayer
from ...parameter.replica import Checkpointable
from ...system.message import Task


class OptaxUpdater:
    """KVLayer Updater backed by an optax optimizer (server-side optimizer,
    ref KVLayerUpdater::Update)."""

    def __init__(self, tx):
        self.tx = tx
        self.opt_state = None

    def init(self, name, shape, dtype=jnp.float32):
        return jnp.zeros(shape, dtype)

    def init_opt(self, params):
        self.opt_state = self.tx.init(params)

    def update(self, name, weight, recv):  # single-layer path (API parity)
        updates, _ = self.tx.update({name: recv}, self.tx.init({name: weight}), {name: weight})
        return weight + updates[name]


class NNTrainer(Checkpointable):
    def __init__(
        self,
        model,
        input_shape: Tuple[int, ...],
        mesh=None,
        optimizer=None,
        partition_thr: int = 100_000,
        loss_fn: Callable = cross_entropy,
        seed: int = 0,
    ):
        from ...system.postoffice import Postoffice

        import optax

        self.model = model
        self.mesh = mesh if mesh is not None else Postoffice.instance().mesh
        assert self.mesh is not None, "Postoffice.start() first"
        self.tx = optimizer or optax.sgd(0.05, momentum=0.9)
        self.loss_fn = loss_fn
        rng = jax.random.PRNGKey(seed)
        params = model.init(rng, jnp.zeros((1,) + tuple(input_shape)))["params"]
        # KVLayer is the parameter store (sharded per partition threshold)
        self.kv = KVLayer(partition_thr=partition_thr, mesh=self.mesh, name="nn_layers")
        flat = jax.tree_util.tree_leaves_with_path(params)
        self.params = {}
        for path, leaf in flat:
            key = "/".join(str(p.key) for p in path)
            self.kv.layers[key] = jax.device_put(leaf, self.kv._sharding(leaf.shape))
        self._param_struct = jax.tree.structure(params)
        self.opt_state = self.tx.init(self._pack())
        self._step = self._build_step()
        self.steps_done = 0

    def _pack(self):
        # drain in-flight KVLayer pushes first: they donate layer
        # buffers on the store's executor thread (donate=True default),
        # and packing must never read — or feed into the donating train
        # step — a buffer a queued push is about to consume
        self.kv.executor.wait_all(pop=False)
        leaves = [self.kv.layers[k] for k in sorted(self.kv.layers)]
        return jax.tree.unflatten(self._param_struct, leaves)

    def _unpack(self, params) -> None:
        leaves = jax.tree.leaves(params)
        for k, leaf in zip(sorted(self.kv.layers), leaves):
            self.kv.layers[k] = leaf

    def _build_step(self):
        model, loss_fn, tx = self.model, self.loss_fn, self.tx

        def local_step(params, opt_state, x, y):
            x, y = x[0], y[0]

            def loss(p):
                logits = model.apply({"params": p}, x)
                return loss_fn(logits, y), logits

            (lval, logits), grads = jax.value_and_grad(loss, has_aux=True)(params)
            # the KVLayer push: combine worker gradients over the data axis
            grads = jax.lax.pmean(grads, DATA_AXIS)
            updates, new_opt = tx.update(grads, opt_state, params)
            import optax

            new_params = optax.apply_updates(params, updates)
            acc = jnp.mean((jnp.argmax(logits, -1) == y).astype(jnp.float32))
            metrics = {
                "loss": jax.lax.pmean(lval, DATA_AXIS),
                "accuracy": jax.lax.pmean(acc, DATA_AXIS),
            }
            return new_params, new_opt, metrics

        import functools

        # the trainer owns params (the KVLayer arrays it re-installs via
        # _unpack) and opt_state, and replaces both every step — donate
        # them so the fused step updates weights/momenta in place instead
        # of materializing a full parameter copy per step (the KVLayer
        # donation contract; checkpoints copy to host first)
        @functools.partial(jax.jit, donate_argnums=(0, 1))
        def step(params, opt_state, x, y):
            specs = jax.tree.map(lambda _: P(), params)
            opt_specs = jax.tree.map(lambda _: P(), opt_state)
            return shard_map(
                local_step,
                mesh=self.mesh,
                in_specs=(specs, opt_specs, P(DATA_AXIS), P(DATA_AXIS)),
                out_specs=(specs, opt_specs, P()),
                check_vma=False,
            )(params, opt_state, x, y)

        return step

    def state_host(self) -> dict:
        """HOST-ARRAY snapshot for checkpoint/restore and live migration
        (the Checkpointable/ElasticCoordinator hook pair — same contract
        as the linear/FM/DeepCTR workers: numpy out, resharded in)."""
        return jax.tree.map(
            np.asarray,
            {
                "params": self._pack(),
                "opt": self.opt_state,
                "steps_done": np.int64(self.steps_done),
            },
        )

    def load_state_host(self, snap: dict) -> None:
        # params back onto the KVLayer's partition-threshold shardings;
        # optimizer leaves as uncommitted host arrays (jit re-places them
        # alongside the params on the next step)
        placed = jax.tree.map(
            lambda leaf: jax.device_put(
                np.asarray(leaf), self.kv._sharding(np.shape(leaf))
            ),
            snap["params"],
        )
        self._unpack(placed)
        self.opt_state = jax.tree.map(np.asarray, snap["opt"])
        self.steps_done = int(snap["steps_done"])

    # checkpoint/restore: inherited from replica.Checkpointable

    def shard_batch(self, x: np.ndarray, y: np.ndarray):
        d = meshlib.num_workers(self.mesh)
        n = len(y)
        per = n // d
        assert per * d == n, f"batch {n} not divisible by {d} workers"
        xs = x.reshape((d, per) + x.shape[1:]).astype(np.float32)
        ys = y.reshape(d, per).astype(np.int32)
        sh = meshlib.batch_sharding(self.mesh)
        return jax.device_put(xs, sh), jax.device_put(ys, sh)

    def train_step(self, x: np.ndarray, y: np.ndarray) -> Dict[str, float]:
        xs, ys = self.shard_batch(x, y)
        params = self._pack()
        new_params, self.opt_state, metrics = self._step(params, self.opt_state, xs, ys)
        self._unpack(new_params)
        self.steps_done += 1
        return {k: float(v) for k, v in metrics.items()}

    def evaluate(self, x: np.ndarray, y: np.ndarray) -> Dict[str, float]:
        logits = self.model.apply({"params": self._pack()}, jnp.asarray(x, jnp.float32))
        acc = float(jnp.mean((jnp.argmax(logits, -1) == jnp.asarray(y)).astype(jnp.float32)))
        loss = float(self.loss_fn(logits, jnp.asarray(y)))
        return {"accuracy": acc, "loss": loss}

    # -- KVLayer API parity passthroughs --

    def push(self, key, grad, task: Optional[Task] = None) -> int:
        return self.kv.push(task or self.kv.request(), key, grad)

    def pull(self, key, task: Optional[Task] = None):
        return self.kv.wait_pull(self.kv.pull(task or self.kv.request(), key))

    def push_pull(self, key, grad, task: Optional[Task] = None):
        """Fused gradient push + weight refresh: one submitted step
        returns the post-update layer (KVLayer.push_pull) — the worker's
        push-then-pull-same-key round trip in a single dispatch."""
        return self.kv.wait_pull(
            self.kv.push_pull(task or self.kv.request(), key, grad)
        )

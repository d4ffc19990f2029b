"""KVMap: sharded key-value store with user-defined entry updaters.

Counterpart of ``src/parameter/kv_map.h`` (KVMap<K,V,E,S>): the reference
applies ``Entry::Set(recv_data, state)`` per key on push and
``Entry::Get(data, state)`` on pull, with a shared mutable ``State``
(learning rate, penalty, progress counters). The TPU inversion: an Entry is
a *vectorized functional updater* over struct-of-arrays state sharded across
the server axis —

    state' = entry.update(state, agg_grads, touched_mask)
    values = entry.get(state)

Push densifies the (idx, grad) request into the owned shard, aggregates
duplicates by addition (the reference receives pre-aggregated worker
messages), and applies the entry update only on touched slots. All shapes
static; the whole update is one fused XLA kernel per shard (VPU,
bandwidth-bound) — this is the server-side compute of the parameter server.
"""

from __future__ import annotations

import functools
from typing import Dict, Optional, Protocol

import jax
import jax.numpy as jnp
import numpy as np

from ..utils import file as psfile

from jax import shard_map
from jax.sharding import PartitionSpec as P

from ..ops.kv_ops import localize
from ..parallel import mesh as meshlib
from ..parallel import partition as partlib
from ..parallel.mesh import SERVER_AXIS
from ..system.message import Task
from .parameter import KeyDirectory, Parameter, pad_slots


class Entry(Protocol):
    """Vectorized entry semantics (ref kv_map.h KVMapEntry)."""

    def init(self, num_slots: int, k: int) -> dict: ...

    def update(self, state: dict, grad: jnp.ndarray, touched: jnp.ndarray) -> dict: ...

    def get(self, state: dict) -> jnp.ndarray: ...


class AssignEntry:
    """Plain value store: push overwrites, pull reads (default KVMapEntry)."""

    def init(self, num_slots, k):
        return {"value": jnp.zeros((num_slots, k), jnp.float32)}

    def update(self, state, grad, touched):
        return {"value": jnp.where(touched[:, None], grad, state["value"])}

    def get(self, state):
        return state["value"]


class AddEntry:
    """Accumulator: push adds (aggregation server, ref aggregation_ps.cc)."""

    def init(self, num_slots, k):
        return {"value": jnp.zeros((num_slots, k), jnp.float32)}

    def update(self, state, grad, touched):
        return {"value": state["value"] + grad}

    def get(self, state):
        return state["value"]


class KVMap(Parameter):
    def __init__(
        self,
        entry: Entry,
        mesh=None,
        k: int = 1,
        num_slots: int = 1 << 20,
        hashed: bool = True,
        keys: Optional[np.ndarray] = None,
        id: Optional[int] = None,
        name: str = "",
    ):
        super().__init__(id=id, name=name)
        if mesh is None:
            assert self.po.mesh is not None, "Postoffice.start() first"
            mesh = self.po.mesh
        self.mesh = mesh
        self.k = int(k)
        self.entry = entry
        self.num_slots = pad_slots(num_slots, meshlib.num_servers(mesh))
        # convention: HASHED directories use the CONFIGURED modulus (keys
        # keep their slots across elastic resizes — async_sgd.py's note);
        # EXACT directories use the PADDED capacity so the miss sentinel
        # (== capacity) falls outside every shard's range and unknown
        # keys are dropped, not scattered into a padding slot
        is_hashed = keys is None and hashed
        self.directory = KeyDirectory(
            int(num_slots) if is_hashed else self.num_slots,
            keys=keys,
            hashed=is_hashed,
        )
        # resolved ONCE through the mesh's declarative partitioner
        # (parallel/partition.py owns the table spec)
        self.partitioner = partlib.for_mesh(mesh)
        sharding = self.partitioner.table_sharding()
        self.state: Dict[str, jax.Array] = {
            name_: jax.device_put(arr, sharding)
            for name_, arr in entry.init(self.num_slots, self.k).items()
        }
        self._push_fn = self._build_push()

    def _build_push(self):
        n_server = meshlib.num_servers(self.mesh)
        shard = self.num_slots // n_server
        entry = self.entry

        def local(state, ix, v):
            rel, ok = localize(ix, shard)
            g = jnp.zeros((shard, v.shape[-1]), v.dtype)
            g = g.at[rel].add(jnp.where(ok[:, None], v, 0))
            touched = jnp.zeros((shard,), jnp.bool_).at[rel].max(ok)
            new = entry.update(state, g, touched)
            return jax.tree.map(
                lambda n, o: jnp.where(
                    touched.reshape((-1,) + (1,) * (n.ndim - 1)), n, o
                ),
                new,
                state,
            )

        # declared, not hand-built: the updater-state spec tree is
        # the partitioner's one rule (every array leaf row-sharded
        # over the server key ranges)
        state_specs = partlib.state_partition_spec(
            {k_: self.state[k_] for k_ in self.state}
        )

        # the store owns self.state exclusively and replaces it on every
        # push, so the state buffers are donated: the entry update runs
        # in place instead of materializing a fresh struct-of-arrays
        # copy per push (zero-copy contract, doc/PERFORMANCE.md)
        @functools.partial(jax.jit, donate_argnums=(0,))
        def push_fn(state, ix, v):
            return shard_map(
                local,
                mesh=self.mesh,
                in_specs=(state_specs, P(), P()),
                out_specs=state_specs,
            )(state, ix, v)

        return push_fn

    def slots(self, keys: np.ndarray) -> jnp.ndarray:
        # signature-cached host mapping + device upload (KeyDirectory)
        return self.directory.slots_device(keys)

    def push(self, task: Task, keys, values, callback=None) -> int:
        slots = self.slots(keys)
        vals = jnp.asarray(values, jnp.float32).reshape(-1, self.k)

        def step():
            from ..telemetry.instruments import cached_kvops_instruments

            tel = cached_kvops_instruments()
            if tel is not None:
                tel["donated_pushes"].inc()
            self.state = self._push_fn(self.state, slots, vals)
            return self.state

        return self.instrumented_submit(
            "push", task.key_channel, len(slots), step, task, callback
        )

    def pull(self, task: Task, keys, callback=None) -> int:
        slots = self.slots(keys)

        def step():
            from ..ops import kv_ops

            values = self.entry.get(self.state)
            return kv_ops.pull(values, slots, mesh=self.mesh, batch_sharded=False)

        return self.instrumented_submit(
            "pull", task.key_channel, len(slots), step, task, callback
        )

    def wait_pull(self, ts: int) -> jax.Array:
        return self.executor.pop_result(ts)

    def values(self, keys: np.ndarray) -> np.ndarray:
        ts = self.pull(self.request(), keys)
        return np.asarray(self.wait_pull(ts))

    def write_to_file(self, path: str) -> None:
        """Nonzero weights as text (ref KVMap::WriteToFile)."""
        self.executor.wait_all(pop=False)  # donated pushes settle first
        vals = np.asarray(self.entry.get(self.state))
        keys = (
            self.directory.keys
            if self.directory.keys is not None
            else np.arange(self.num_slots)
        )
        vals = vals[: len(keys)]
        nz = np.any(vals != 0, axis=1)
        with psfile.open_write(path) as f:
            for key, val in zip(np.asarray(keys)[nz], vals[nz]):
                f.write(f"{key}\t" + "\t".join(repr(float(x)) for x in val) + "\n")

    def get_replica(self) -> dict:
        # drain in-flight (donated) pushes, then host copies — the
        # snapshot is immune to later in-place updates
        self.executor.wait_all(pop=False)
        return {k_: np.asarray(v) for k_, v in self.state.items()}

    def set_replica(self, snapshot: dict) -> None:
        sharding = self.partitioner.table_sharding()
        self.state = {
            k_: jax.device_put(jnp.asarray(v), sharding) for k_, v in snapshot.items()
        }

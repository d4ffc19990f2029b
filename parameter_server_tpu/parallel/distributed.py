"""Multi-host (multi-process) support — the DCN side of the fabric.

The reference scales across machines with ZMQ sockets bootstrapped by a
scheduler node (``src/system/van.cc Van::Connect``; launched by
``script/local.sh`` / ``mpi_node.sh``). The TPU-native equivalent is one
JAX process per host joined through ``jax.distributed`` (gRPC coordination
service = the scheduler rendezvous), after which every process sees the
GLOBAL device list and a single ``Mesh`` spans all hosts — collectives
ride ICI within a slice and DCN across slices, chosen by XLA from the mesh
axis layout.

What this module adds on top of ``jax.distributed.initialize``:

- :func:`initialize` — env-driven bootstrap (PS_COORDINATOR_ADDRESS /
  PS_NUM_PROCESSES / PS_PROCESS_ID, the analog of the reference's
  scheduler node string in ``env.cc``), with the CPU cross-process
  collective backend (gloo) configured and clear errors for the
  backend-already-initialized trap.
- :func:`global_from_local` — assemble a process-local batch pytree into
  global device arrays sharded over the mesh's data axis
  (``jax.make_array_from_process_local_data``): each host feeds its own
  examples, the SPMD step sees one global batch. This is the reference's
  "every worker reads its own file partition" (DataAssigner) made
  explicit.
- :func:`local_data_shards` — how many data-axis rows this process owns
  (its share of the worker group).
"""

from __future__ import annotations

import os
from typing import Optional

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from . import mesh as meshlib

_initialized = False


def initialize(
    coordinator_address: Optional[str] = None,
    num_processes: Optional[int] = None,
    process_id: Optional[int] = None,
) -> bool:
    """Join the multi-process rendezvous. Returns True when running
    multi-process, False for plain single-process use.

    Args default from the environment (set by ``script/local.sh`` or the
    cluster launcher): ``PS_COORDINATOR_ADDRESS`` (host:port of process
    0's coordination service — the reference's scheduler node),
    ``PS_NUM_PROCESSES``, ``PS_PROCESS_ID``.

    Must run before the first JAX computation. If another component
    already initialized the backend, joining is impossible — we raise
    with the fix rather than silently degrading to process_count()==1.
    """
    global _initialized
    addr = coordinator_address or os.environ.get("PS_COORDINATOR_ADDRESS")
    if not addr:
        return False
    if _initialized:
        return True
    n = int(num_processes or os.environ.get("PS_NUM_PROCESSES", "1"))
    pid = int(process_id if process_id is not None else os.environ.get("PS_PROCESS_ID", "0"))
    if n <= 1:
        return False
    jax.distributed.initialize(
        coordinator_address=addr, num_processes=n, process_id=pid
    )
    if jax.process_count() != n:
        raise RuntimeError(
            f"jax.distributed joined {jax.process_count()} processes, expected "
            f"{n}. A backend was initialized before the rendezvous: call "
            "init_distributed() before any other jax use (and set "
            "JAX_PLATFORMS=cpu for multi-process runs on one CPU box)."
        )
    _initialized = True
    return True


def is_multiprocess() -> bool:
    return jax.process_count() > 1


# -- control-plane byte transport (ref van.cc ZMQ send/recv over DCN) --
#
# Host-to-host Messages ride the jax.distributed coordination service's
# key-value store (the same gRPC channel that bootstrapped the cluster —
# the reference reuses its scheduler connection for control traffic the
# same way). This is for CONTROL-plane frames: workload grants, progress
# reports, filtered parameter messages in tests; bulk tensor traffic
# belongs to XLA collectives over ICI/DCN, never here.


def _kv_client():
    from jax._src import distributed as _dist

    c = _dist.global_state.client
    if c is None:
        raise RuntimeError(
            "no jax.distributed client — control-plane messaging needs a "
            "multi-process rendezvous (PS_COORDINATOR_ADDRESS et al.)"
        )
    return c


def post_bytes(tag: str, blob: bytes) -> None:
    """Publish one control-plane frame under a UNIQUE tag (the store is
    write-once per key: include sender/seq in the tag, e.g. "w0/3")."""
    _kv_client().key_value_set_bytes(f"psmsg/{tag}", blob)


def fetch_bytes(tag: str, timeout_ms: int = 120_000) -> bytes:
    """Block until the frame tagged ``tag`` is published, return it."""
    return _kv_client().blocking_key_value_get_bytes(
        f"psmsg/{tag}", timeout_ms
    )


def local_data_shards(mesh: Mesh) -> int:
    """Number of data-axis rows whose devices belong to this process.

    A data row must be WHOLLY owned by one process: the batch is sharded
    P(data) and replicated over the server axis, and
    ``make_array_from_process_local_data`` has no way to check that two
    processes feeding the same row agree — split ownership would let
    divergent per-host batches masquerade as one global row (silent
    corruption). We raise instead; pick num_server / devices-per-host so
    each host owns whole rows (e.g. num_server ≤ local device count and
    divides it).
    """
    this = jax.process_index()
    rows = 0
    axes = dict(zip(mesh.axis_names, range(len(mesh.axis_names))))
    arr = np.asarray(mesh.devices)
    if arr.ndim == 1:
        arr = arr[:, None]
    data_dim = axes.get(meshlib.DATA_AXIS, 0)
    for r in range(arr.shape[data_dim]):
        row = arr[r] if data_dim == 0 else arr[:, r]
        owners = {d.process_index for d in np.ravel(row)}
        if this in owners:
            if len(owners) > 1:
                raise ValueError(
                    f"data row {r} spans processes {sorted(owners)}; each "
                    "data-axis row must be wholly owned by one process — "
                    "choose num_server to divide the per-host device count"
                )
            rows += 1
    if rows == 0:
        raise ValueError(
            f"process {this} owns no data-axis rows of mesh {dict(mesh.shape)} "
            "(its devices were left idle by the mesh layout); every process "
            "must own at least one row — grow num_data or shrink the job"
        )
    return rows


def global_from_local(mesh: Mesh, tree, axis_name: str = None, axis_dim: int = 0):
    """Assemble per-process host arrays into global jax.Arrays sharded
    over the data axis. Single-process: plain device_put.

    ``axis_dim`` selects which leaf dimension carries the data shards —
    0 for per-minibatch trees ([D_local, ...]), 1 for scan superbatches
    ([T, D_local, ...]); that dim grows from this process's local shard
    count to the full data axis.
    """
    axis = axis_name or meshlib.DATA_AXIS
    if not is_multiprocess():
        return jax.device_put(tree)
    d_global = mesh.shape[axis]

    def put(leaf):
        if leaf is None:
            return None
        leaf = np.asarray(leaf)
        spec = [None] * leaf.ndim
        spec[axis_dim] = axis
        sharding = NamedSharding(mesh, P(*spec))
        global_shape = tuple(
            d_global if i == axis_dim else s for i, s in enumerate(leaf.shape)
        )
        return jax.make_array_from_process_local_data(sharding, leaf, global_shape)

    return jax.tree.map(put, tree, is_leaf=lambda x: x is None)

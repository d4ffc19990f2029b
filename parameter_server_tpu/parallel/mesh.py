"""Device mesh construction — the TPU replacement for node groups.

The reference organizes nodes into groups (``src/system/executor.h``:
kServerGroup/kWorkerGroup/kCompGroup) connected by ZMQ. Here those roles are
axes of a ``jax.sharding.Mesh``:

- ``data`` axis ≙ kWorkerGroup — examples are sharded along it; gradient
  aggregation is a psum/reduce_scatter across it (rides ICI).
- ``server`` axis ≙ kServerGroup — parameter tables are sharded along it by
  contiguous key range, like the reference's server key ranges
  (``Range<Key>::EvenDivide`` in manager.cc).

A chip may sit on both axes (2-D mesh): that's the common TPU layout where
every chip holds a parameter shard *and* computes gradients, unlike the
reference where workers and servers are disjoint processes.
"""

from __future__ import annotations

import logging
import os
from typing import Optional, Sequence

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

DATA_AXIS = "data"
SERVER_AXIS = "server"


def make_mesh(
    num_data: Optional[int] = None,
    num_server: int = 1,
    devices: Optional[Sequence[jax.Device]] = None,
) -> Mesh:
    """Build a ``(data, server)`` mesh over available devices.

    Defaults to all devices on the data axis (pure data parallel with
    replicated-then-sharded tables handled by NamedSharding specs).
    """
    devs = list(devices if devices is not None else jax.devices())
    n = len(devs)
    log = logging.getLogger(__name__)
    if num_data is None:
        # auto-shape must factor the FULL device count: the old
        # ``n // num_server`` rounding made num_server=3 on 8 devices a
        # 2x3 mesh with 2 chips idle. When the requested server count
        # does not divide n, step it down to the largest divisor of n
        # that still fits — 8 devices never run 6-wide.
        num_server = max(1, min(int(num_server), n))
        if n % num_server != 0:
            adjusted = next(
                d for d in range(num_server, 0, -1) if n % d == 0
            )
            log.warning(
                "auto-shape: %d server shards do not divide %d devices; "
                "using %d server shards (largest divisor <= requested) "
                "so no chip idles",
                num_server, n, adjusted,
            )
            num_server = adjusted
        num_data = n // num_server
        log.info(
            "auto-shaped mesh %dx%d (data x server) over %d devices, 0 idle",
            num_data, num_server, n,
        )
    need = num_data * num_server
    if need > n:
        raise ValueError(f"mesh {num_data}x{num_server} needs {need} > {n} devices")
    if need < n:
        log.warning(
            "mesh %dx%d leaves %d of %d devices idle",
            num_data, num_server, n - need, n,
        )
    # fewer nodes than devices is fine (ref script/local.sh runs any N/M on
    # one box): take a prefix of the device list
    arr = np.asarray(devs[:need]).reshape(num_data, num_server)
    return Mesh(arr, (DATA_AXIS, SERVER_AXIS))


def table_sharding(mesh: Mesh) -> NamedSharding:
    """Parameter tables: sharded by key range over the server axis,
    replicated over data. Resolved through the mesh's (cached)
    declarative partitioner — parallel/partition.py owns the spec."""
    from . import partition  # deferred: partition imports our axis names

    return partition.for_mesh(mesh).table_sharding()


def init_sharded(init_fn, mesh: Mesh, axis: str = SERVER_AXIS):
    """Materialize ``init_fn()``'s pytree DIRECTLY into its sharded
    layout: every leaf with rank >= 1 is row-sharded over ``axis``
    (trailing dims replicated), scalars replicated.

    The point is peak memory and the host link: building a leaf whole
    on the default device and then device_put-resharding transiently
    doubles its HBM footprint (that pushed a 2^30-slot, 8.6 GB FTRL
    table into RESOURCE_EXHAUSTED on a 16 GB chip), and a host-side
    init would push the whole table through the host<->device link.
    jit + out_shardings writes zeros/
    random values straight into the sharded buffers; on-device PRNG
    (jax.random.*) inside ``init_fn`` stays device-resident too."""
    from . import partition

    shapes = jax.eval_shape(init_fn)
    shardings = jax.tree.map(
        lambda s: NamedSharding(
            mesh, partition.fit_spec(P(axis), len(s.shape))
        ),
        shapes,
    )
    with mesh:
        return jax.jit(init_fn, out_shardings=shardings)()


def batch_sharding(mesh: Mesh) -> NamedSharding:
    """Example batches: sharded over the data axis, replicated over
    server (spec owned by parallel/partition.py)."""
    from . import partition

    return partition.for_mesh(mesh).batch_sharding()


def replicated(mesh: Mesh) -> NamedSharding:
    from . import partition

    return partition.for_mesh(mesh).replicated()


def num_servers(mesh: Mesh) -> int:
    return mesh.shape[SERVER_AXIS]


def num_workers(mesh: Mesh) -> int:
    return mesh.shape[DATA_AXIS]


def force_host_mesh(n: int = 8) -> None:
    """Test helper: must run before jax initializes. Forces an n-device CPU
    platform so multi-chip sharding logic is exercised without TPUs."""
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + f" --xla_force_host_platform_device_count={n}"
        ).strip()

"""Postoffice: the process-wide system singleton.

Counterpart of ``src/system/postoffice.{h,cc}``: owns the manager (node and
customer registry) and the van (transport). ``start`` boots the system —
in the reference that spawns send/recv threads and connects ZMQ; here it
builds the device mesh (and, multi-host, joins the jax.distributed
rendezvous), which *is* the connected network on TPU.
"""

from __future__ import annotations

import threading
from typing import Optional

from jax.sharding import Mesh

from ..parallel import mesh as meshlib
from ..telemetry import registry as telemetry_registry
from ..telemetry import spans as telemetry_spans
from ..utils.range import Range
from .manager import Manager
from .van import Van, init_distributed


class Postoffice:
    _instance: Optional["Postoffice"] = None  # guarded-by: _lock
    _lock = threading.Lock()

    def __init__(self) -> None:
        self.manager = Manager()
        self.mesh: Optional[Mesh] = None
        self.van: Optional[Van] = None
        self.aux = None  # AuxRuntime once start_aux() is called
        # the process telemetry spine: every layer's instruments register
        # here (doc/OBSERVABILITY.md); reset() swaps in a fresh registry
        self.metrics = telemetry_registry.default_registry()
        self._started = False

    @classmethod
    def instance(cls) -> "Postoffice":
        with cls._lock:
            if cls._instance is None:
                cls._instance = cls()
            return cls._instance

    @classmethod
    def reset(cls) -> None:
        """Tear down the singleton (ref Postoffice::Stop): stop the
        running instance (its aux threads and its customers' executors,
        which would otherwise keep their apps' device state alive) and
        drop it. Also resets the telemetry spine (fresh default
        registry, span sink closed) so metrics never leak across
        hermetic tests."""
        with cls._lock:
            old, cls._instance = cls._instance, None
        if old is not None:
            old.stop()
        telemetry_registry.reset_default_registry()
        telemetry_spans.close_sink()
        # learning truth planes bind per-worker registries; drop them
        # with the spine so a hermetic test never reads a prior run's
        # staleness/heat through learning.snapshot_all()
        from ..telemetry import learning as telemetry_learning

        telemetry_learning.reset()

    def start(
        self,
        num_data: Optional[int] = None,
        num_server: int = 1,
        key_space: Optional[Range] = None,
    ) -> "Postoffice":
        if self._started:
            return self
        # persistent compile cache before the first jit (and before
        # the rendezvous: enabling it initializes no backend)
        from ..utils import compile_cache

        compile_cache.enable()
        init_distributed()
        self.mesh = meshlib.make_mesh(num_data=num_data, num_server=num_server)
        self.van = Van(self.mesh)
        self.manager.init_nodes(
            num_servers=meshlib.num_servers(self.mesh),
            num_workers=meshlib.num_workers(self.mesh),
            key_space=key_space or Range.all(),
        )
        self._started = True
        return self

    def start_aux(self, heartbeat_timeout: float = 10.0, print_fn=print):
        """Create (once) the heartbeat/dashboard/recovery runtime — the
        reference boots these with every node (postoffice.cc heartbeat
        thread, manager.cc dead-node flow, dashboard.cc)."""
        if self.aux is None:
            from .aux_runtime import AuxRuntime

            self.aux = AuxRuntime(
                heartbeat_timeout=heartbeat_timeout, print_fn=print_fn
            )
        return self.aux

    def beat(self, node_id: str) -> None:
        """Heartbeat passthrough for hot loops; no-op before start_aux."""
        if self.aux is not None:
            self.aux.beat(node_id)

    def stop(self) -> None:
        if self.aux is not None:
            self.aux.stop()
            self.aux = None
        self.manager.stop()
        self._started = False

    @property
    def started(self) -> bool:
        return self._started

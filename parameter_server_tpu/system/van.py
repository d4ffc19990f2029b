"""Van: the transport layer, rebuilt on XLA collectives.

Counterpart of ``src/system/van.{h,cc}``. The reference moves bytes between
nodes with ZMQ sockets; on TPU the equivalent "wire" is the ICI/DCN fabric
driven by XLA collectives inside jitted programs. The Van therefore exposes:

- device placement (``put``) with the right NamedSharding — the analog of
  addressing a message to a node group;
- the collective primitives push/pull compile down to (psum, all_gather,
  reduce_scatter, ppermute) bound to mesh axes;
- host-side filter-chain encode/decode for control-plane messages (the
  reference applies filters in Van::Send/Recv via RemoteNode).

Multi-host bootstrap (the reference's scheduler rendezvous in
``Van::Connect``) maps to ``jax.distributed.initialize``; gated here because
this environment is single-host.
"""

from __future__ import annotations

from typing import Optional, Sequence

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..parallel import mesh as meshlib
from ..telemetry import registry as telemetry_registry
from ..telemetry import spans as telemetry_spans
from . import faults
from .message import Message


class Van:
    def __init__(self, mesh: Mesh):
        self.mesh = mesh
        self.placed_bytes = 0  # device placement volume (put_* below)
        # serialized host frames through transfer() — kept separate from
        # placement bytes so each counter means ONE thing (ref van.cc
        # send_bytes_/recv_bytes_ count wire frames)
        self.wire_sent_bytes = 0
        self.wire_recv_bytes = 0
        # registry mirrors of the counters above (telemetry spine): one
        # process-wide series each, shared with dashboard/bench snapshots
        self._tel = None
        if telemetry_registry.enabled():
            from ..telemetry.instruments import van_instruments

            self._tel = van_instruments(telemetry_registry.default_registry())
        # ident -> node id, for heartbeat traffic attribution: app names
        # resolve through the manager's customer table (a linear scan) —
        # cache positive resolutions so chatty RPC traffic pays it once
        # per peer, not per frame
        self._ident_nodes: dict = {}

    # -- placement (addressing) --

    def _count_placed(self, nbytes: int) -> None:
        self.placed_bytes += nbytes
        if self._tel is not None:
            self._tel["placed_bytes"].inc(nbytes)

    def put_table(self, arr) -> jax.Array:
        """Place a parameter table sharded by key range over servers."""
        out = jax.device_put(arr, meshlib.table_sharding(self.mesh))
        self._count_placed(arr.nbytes)
        return out

    def put_batch(self, arr) -> jax.Array:
        """Place a batch sharded over the data (worker) axis."""
        out = jax.device_put(arr, meshlib.batch_sharding(self.mesh))
        self._count_placed(arr.nbytes)
        return out

    def put_replicated(self, arr) -> jax.Array:
        out = jax.device_put(arr, meshlib.replicated(self.mesh))
        self._count_placed(arr.nbytes)
        return out

    # -- host wire (control plane) --

    def transfer(self, sender, recver, msg: Message) -> Message:
        """The full host wire path between two per-peer endpoints (ref
        van.cc Send then Recv): the sender's RemoteNode filter-encodes
        and serializes, the frame crosses the "wire" (loopback within a
        process, the jax.distributed KV transport across hosts), and the
        receiver's RemoteNode deserializes and decodes. Van keeps the
        process-level byte counters (ref Van send_bytes_/recv_bytes_);
        the per-peer counters live on the RemoteNodes.

        Every ps.py group RPC — request AND response — crosses here.

        Byte accounting is side-correct: sent bytes are counted at
        serialization, recv bytes only after ``from_wire`` actually ran
        on the receiving endpoint (measured as that endpoint's counter
        delta) — a decode failure, or a multi-host split where the
        receiving process does its own ``from_wire``, never inflates
        this process's recv counter with sender-side frame lengths.
        Both directions also feed the nodes' HeartbeatInfo so the
        dashboard reports true traffic.

        Trace context: the sending thread's active flow id (plus this
        process's node id and the send wall time) is stamped onto
        ``Task.trace`` before serialization — flow ids used to die
        right here, making a multi-node timeline unstitchable. The
        receiving side re-activates it (``spans.activate_trace``) so
        one batch/request is ONE flow across processes, and the leg
        itself is a ``van.transfer`` span. An explicitly pre-set trace is
        respected (re-sends keep their origin)."""
        if getattr(msg.task, "trace", None) is None:
            msg.task.trace = telemetry_spans.trace_context()
        blob = sender.to_wire(msg)
        sent = len(blob)
        with telemetry_spans.span(
            "van.transfer", sender=msg.sender, recver=msg.recver,
            bytes=sent,
        ):
            self.wire_sent_bytes += sent
            self._account(msg.sender, out_bytes=sent)
            # fault point (doc/ROBUSTNESS.md) — the wire between
            # serialize and deliver, where real networks fail. Placed
            # AFTER the send accounting so a dropped frame costs sender
            # bytes but never receiver bytes (the side-correct counting
            # contract above):
            #   drop      → FaultError; the RPC layer sees a lost frame
            #   delay     → the frame arrives late (delay_s)
            #   duplicate → at-least-once delivery: from_wire runs
            #               twice, probing receiver idempotence under
            #               redelivery
            fault = faults.check(
                "van.transfer", detail=f"{msg.sender}->{msg.recver}"
            )
            duplicate = False
            if fault is not None:
                if fault.delay_s:
                    import time as _time

                    _time.sleep(fault.delay_s)
                if fault.kind == "drop":
                    raise fault.make_error(
                        f"frame {msg.sender}->{msg.recver} dropped"
                    )
                duplicate = fault.kind == "duplicate"
            recv_before = recver.wire_recv_bytes
            if duplicate:
                recver.from_wire(blob)
            out = recver.from_wire(blob)
            recv = recver.wire_recv_bytes - recv_before
            self.wire_recv_bytes += recv
            self._account(msg.recver, in_bytes=recv)
            if self._tel is not None:
                self._tel["wire_sent_bytes"].inc(sent)
                self._tel["wire_recv_bytes"].inc(recv)
                self._tel["transfers"].inc()
        return out

    def _account(self, ident: str, in_bytes: int = 0, out_bytes: int = 0) -> None:
        """Feed a transfer's bytes into the node's HeartbeatInfo (ref
        heartbeat_info.cc: Van::Send/Recv bump the traffic counters the
        dashboard's in(MB)/out(MB) columns report). ``ident`` may be a
        node id ("W0") or a customer/app name — resolved best-effort;
        silently skipped before start_aux or for unregistered nodes."""
        if not ident or (not in_bytes and not out_bytes):
            return
        from .postoffice import Postoffice

        po = Postoffice._instance  # never create the singleton from here
        if po is None or po.aux is None:
            return
        info = po.aux.info(ident)
        if info is None:
            # app names differ from node ids (ps.py submits under the
            # customer name); map through the registered customer's node
            node_id = self._ident_nodes.get(ident)
            if node_id is None:
                cust = po.manager.find_customer_by_name(ident)
                node = getattr(cust, "node", None)
                if node is None:
                    return  # unresolved now; may register later — no
                    # negative caching
                node_id = self._ident_nodes[ident] = node.id
            info = po.aux.info(node_id)
            if info is None:
                return
        if in_bytes:
            info.increase_in_bytes(in_bytes)
        if out_bytes:
            info.increase_out_bytes(out_bytes)

    def send(self, msg: Message, filters: Optional[Sequence] = None) -> Message:
        from ..filter.base import encode_chain

        return encode_chain(msg, filters or msg.task.filters)

    def recv(self, msg: Message, filters: Optional[Sequence] = None) -> Message:
        from ..filter.base import decode_chain

        return decode_chain(msg, filters or msg.task.filters)


def init_distributed() -> None:
    """Multi-host bootstrap (ref Van::Connect scheduler rendezvous).

    Joins jax.distributed when coordinator env vars are present
    (PS_COORDINATOR_ADDRESS / PS_NUM_PROCESSES / PS_PROCESS_ID — the
    reference's scheduler host:port + node ids in env.cc); no-op on a
    single host. Full logic in parallel/distributed.py.
    """
    from ..parallel import distributed

    distributed.initialize()

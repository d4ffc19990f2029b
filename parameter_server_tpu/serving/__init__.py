"""Serving plane: the request-path frontend over live parameter tables.

The reference parameter server trains AND serves (OSDI'14 §5: "heavy
traffic from millions of users"); PRs 1-5 built only the training half.
This package is the read path: concurrent client sessions issuing
sparse pulls / predictions against KVVector/KVMap tables and LM decode
against the transformer stack, with the three production mechanisms a
latency SLO needs —

- **admission control** (:mod:`.admission`): token-bucket rate limiting
  + queue-depth shedding with explicit 429-style rejection
  (:class:`RejectedError`), so p99 stays bounded under overload instead
  of collapsing into an unbounded queue.
- **request coalescing** (:mod:`.coalescer`): concurrent pulls for
  overlapping key ranges merge into ONE executor submit over the union
  key set (dedup'd host-side, slot mapping served by the KeyDirectory
  signature cache), inside a bounded coalesce window.
- **read replicas** (:mod:`.replica`): snapshot-consistent read copies
  refreshed OFF the push path (the donation-safe ``table(copy=True)``
  contract from the zero-copy data plane), so serving reads never
  contend with — and can never be invalidated by — training pushes.
- **continuous batching** (:mod:`.batcher`): concurrent decode
  sessions share ONE running speculative-decode call, joining at round
  boundaries into free batch slots and retiring between rounds — fleet
  throughput from the batched-matmul weights-read-once property, with
  per-session greedy token parity as the correctness contract.
- **degraded-mode serving** (chaos plane, doc/ROBUSTNESS.md): a live
  pull that fails or misses ``live_pull_deadline_s`` falls back to the
  read replica inside a staleness bound; past it, requests fail with
  the 503-style :class:`DegradedError` — DISTINCT from the admission
  429, so overload shedding and failure degradation are separately
  observable (``ps_serve_degraded_total`` vs ``ps_serve_shed_total``).

:mod:`.frontend` composes them into :class:`ServeFrontend`;
:mod:`.loadgen` is the open-loop Poisson load generator + latency
recorder behind ``apps/serve`` (p50/p99/p99.9 + goodput-vs-offered-load).
"""

from .admission import AdmissionController, RejectedError, TokenBucket
from .batcher import BatcherConfig, ContinuousBatcher
from .coalescer import PullCoalescer
from .frontend import (
    DecodeRequest,
    DegradedError,
    PredictRequest,
    PullRequest,
    ServeConfig,
    ServeFrontend,
)
from .loadgen import LatencyStats, open_loop_bench
from .replica import ReadReplica

__all__ = [
    "AdmissionController",
    "BatcherConfig",
    "ContinuousBatcher",
    "DecodeRequest",
    "DegradedError",
    "LatencyStats",
    "PredictRequest",
    "PullCoalescer",
    "PullRequest",
    "ReadReplica",
    "RejectedError",
    "ServeConfig",
    "ServeFrontend",
    "TokenBucket",
    "open_loop_bench",
]

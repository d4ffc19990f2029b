#!/usr/bin/env python
"""Headline benchmark: Criteo-style sparse logistic regression (async FTRL).

Mirrors the reference's flagship workload (example/linear criteo
online_l1lr: async SGD + FTRL + L1, BASELINE.json) on TPU: the fused SPMD
step in apps/linear/async_sgd.py — pull(gather+psum) → Xw/grad segment-sums
→ push(scatter+psum) → FTRL dense update — driven by a host prefetch thread
doing localization, so device steps and host prep overlap exactly like the
reference's MinibatchReader producer/consumer.

Record protocol: one JSON record, the LAST line on stdout, naming the
device it ran on (``device``: platform, kind, count). A failure raises;
no partial or substitute record is printed. A non-smoke run refuses to
start off a TPU: a rate comes from the chip, and ``--smoke`` is the CPU
correctness pass at toy sizes whose numbers are not measurements.

Baseline: BASELINE.json publishes no number for the 8-node ZMQ cluster; we
use 500k examples/sec as the documented estimate for 8-node async FTRL on
Criteo-scale data (order of magnitude from the parameter-server OSDI'14
evaluation: ~65k examples/sec/node with sparse LR at ~100 nnz/example).

MEASUREMENT NOTE: every timed window ends in a value fetch (``flush``:
a state scalar to the host), a real device->host dependency, so a
window can never measure the enqueue rate. Scan-fused supersteps
(ELLBitsSuperBatch: T minibatches per launch) amortize the per-launch
dispatch.
"""

import argparse
import contextlib
import json
import os
import sys
import time

import numpy as np

from parameter_server_tpu.telemetry import spans as telemetry_spans
from parameter_server_tpu.utils.concurrent import iter_on_thread

REF_8NODE_EXAMPLES_PER_SEC = 500_000.0


def ensure_trace_sink() -> "str | None":
    """Install a JSONL span sink for the run's timeline when none is
    installed yet (telemetry/timeline.py); returns the trace path, or
    None when an externally installed non-file sink owns the stream.

    MUST run after Postoffice.reset() (reset closes the sink). The
    timeline is the raw material of the record's ``attribution``
    section — every stage span (prep/stack/upload on their threads,
    executor step phases) lands here, flow-correlated per superbatch.

    The flight recorder (telemetry/blackbox.py) arms as a tee over the
    sink, so every bench run also carries the always-on black box —
    an alert firing or a timed-out wait mid-run auto-captures a
    diagnostic bundle with the last ring of spans in it (the record's
    ``blackbox.bundles_captured`` discloses how many).
    """
    import tempfile

    from parameter_server_tpu.telemetry import blackbox

    sink = telemetry_spans.get_sink()
    if sink is not None:
        blackbox.arm()
        return getattr(sink, "path", None)
    path = os.path.join(
        tempfile.gettempdir(), f"ps_bench_trace_{os.getpid()}.jsonl"
    )
    with contextlib.suppress(OSError):
        os.remove(path)  # fresh capture: never mix runs
    telemetry_spans.install_sink(telemetry_spans.JsonlSink(path))
    blackbox.arm()
    return path


def attach_attribution(
    rec_or_headline: dict,
    trace_path: "str | None",
    e2e_window: "tuple[float, float] | None" = None,
) -> None:
    """Embed the critical-path attribution section derived from the
    run's span timeline (telemetry/attribution.py) — the trace-derived
    replacement for hand-computed upload-bound arithmetic. Never breaks
    a record.

    Top-level shares/binding come from the SERIALIZED breakdown-phase
    spans (phase="breakdown": the same launches the legacy
    ``breakdown_*`` fields price, so the two must agree — the
    ``agrees_with_hand_breakdown`` cross-check says so explicitly);
    ``e2e`` holds the pipelined phase's resource utilizations and
    queue-wait over its wall window, where overlap and queueing are
    visible. ``trace_jsonl`` points at the raw timeline; export it with
    ``python -m parameter_server_tpu.benchmarks trace`` or
    ``telemetry.timeline.export_chrome_trace`` and open in Perfetto.
    """
    if trace_path is None:
        return
    try:
        from parameter_server_tpu.telemetry import attribution as attr_mod
        from parameter_server_tpu.telemetry import timeline as timeline_mod

        events = timeline_mod.load_events(trace_path)
        # a --profile run's device track rides the same JSONL (emitted
        # by phase_breakdown): stitch it to the submitting executor.step
        # spans so the breakdown summary below grows the per-kernel
        # device_compute_breakdown and flows cross the host/chip line
        dev_events = [e for e in events if attr_mod.is_device_event(e)]
        if dev_events:
            events = timeline_mod.merge_device_track(
                [e for e in events if not attr_mod.is_device_event(e)],
                dev_events,
            )
        section: dict = {"trace_jsonl": trace_path}
        breakdown = [e for e in events if e.get("phase") == "breakdown"]
        if breakdown:
            summary = attr_mod.summarize(breakdown)
            section.update(summary)
        if e2e_window is not None:
            section["e2e"] = attr_mod.summarize(events, window=e2e_window)
        fracs = rec_or_headline.get("breakdown_fracs")
        shares = section.get("shares")
        if fracs and shares:
            # the hand math's categories map 1:1 onto attribution's
            pairs = (
                ("host_prep", "host_prep"), ("upload", "upload"),
                ("device", "device_compute"),
            )
            section["agrees_with_hand_breakdown"] = all(
                abs(fracs.get(hand, 0.0) - shares.get(cat, 0.0)) <= 0.10
                for hand, cat in pairs
            )
        rec_or_headline["attribution"] = section
    except Exception as e:
        rec_or_headline["attribution_error"] = (
            f"{type(e).__name__}: {str(e)[:200]}"
        )


def telemetry_snapshot() -> "dict | None":
    """Best-effort host-side telemetry snapshot for the bench record.

    The process registry (parameter_server_tpu.telemetry) collects
    executor step phases, Van byte counters and push/pull latency during
    the run; persisting the snapshot next to summarize_trace's device
    phases gives every BENCH_*.json host-side counters alongside the
    device trace. Never allowed to break a record."""
    try:
        from parameter_server_tpu.telemetry import default_registry

        snap = default_registry().snapshot()
        return snap or None
    except Exception:
        return None


def kv_dataplane_microbench(mesh, smoke: bool) -> dict:
    """Zero-copy data-plane A/B at the kernel level, on the live backend:
    the seed's copying push (fresh [P, k] table output per call) vs the
    donated in-place push, and the fused single-dispatch push→pull vs
    push-then-pull as two launches (ops/kv_ops). Ticks the PR's
    telemetry counters (ps_kvops_donated_pushes_total, fused-dispatch
    histogram) so they land in the record's telemetry snapshot; the
    returned dict embeds under ``kv_dataplane``. Cheap by construction
    (seconds), guarded at the call site. Deliberately kernel-level
    (raw kv_ops on this worker's live mesh, no Postoffice reset); the
    STORE-level twin — executor round trips included — lives in benchmarks/components.py kv_vector_perf; keep
    their A/B shapes in sync when either changes."""
    import jax
    import jax.numpy as jnp

    from parameter_server_tpu.ops import kv_ops
    from parameter_server_tpu.parallel import mesh as meshlib

    n_keys = 1 << (10 if smoke else 16)
    k = 4
    p = 2 * n_keys
    rng = np.random.default_rng(0)
    slots = jax.device_put(rng.integers(0, p, n_keys).astype(np.int32))
    vals = jax.device_put(rng.normal(size=(n_keys, k)).astype(np.float32))
    table0 = jax.device_put(
        jnp.zeros((p, k), jnp.float32), meshlib.table_sharding(mesh)
    )
    jax.block_until_ready(table0)
    reps = 3 if smoke else 20

    def timed(fn):
        fn()  # warm (compile)
        t0 = time.perf_counter()
        for _ in range(reps):
            fn()
        return (time.perf_counter() - t0) / reps

    tbl_nd = jax.block_until_ready(jnp.array(table0, copy=True))

    def push_nodonate():
        jax.block_until_ready(
            kv_ops.push(tbl_nd, slots, vals, mesh=mesh, batch_sharded=False)
        )

    box = [jnp.array(table0, copy=True)]

    def push_donated():
        box[0] = kv_ops.push_donated(
            box[0], slots, vals, mesh=mesh, batch_sharded=False
        )
        jax.block_until_ready(box[0])

    def push_then_pull():
        t = kv_ops.push(tbl_nd, slots, vals, mesh=mesh, batch_sharded=False)
        jax.block_until_ready(
            kv_ops.pull(t, slots, mesh=mesh, batch_sharded=False)
        )

    def push_pull_fused():
        box[0], out = kv_ops.push_pull_donated(
            box[0], slots, vals, mesh=mesh, batch_sharded=False
        )
        jax.block_until_ready(out)

    sec_nd = timed(push_nodonate)
    sec_d = timed(push_donated)
    sec_seq = timed(push_then_pull)
    sec_f = timed(push_pull_fused)
    return {
        "n_keys": n_keys,
        "table_shape": [p, k],
        "push_nodonate_steps_per_sec": round(1.0 / sec_nd, 1),
        "push_donated_steps_per_sec": round(1.0 / sec_d, 1),
        "push_donated_speedup": round(sec_nd / sec_d, 3),
        "push_then_pull_rt_per_sec": round(1.0 / sec_seq, 1),
        "push_pull_fused_rt_per_sec": round(1.0 / sec_f, 1),
        "push_pull_fused_speedup": round(sec_seq / sec_f, 3),
        # structural: the [P, k] output buffer the donated path never
        # materializes — bytes NOT moved per push, by construction
        "table_copy_bytes_avoided_per_push": int(p * k * 4),
    }


def attach_kv_dataplane(rec_or_headline: dict, mesh, smoke: bool) -> None:
    """Guarded embed of the kv data-plane A/B (never breaks a record)."""
    try:
        rec_or_headline["kv_dataplane"] = kv_dataplane_microbench(mesh, smoke)
    except Exception as e:
        rec_or_headline["kv_dataplane_error"] = (
            f"{type(e).__name__}: {str(e)[:200]}"
        )


def attach_host_ingest(rec_or_headline: dict, smoke: bool) -> None:
    """Guarded embed of the serial-vs-pipelined host-ingest A/B
    (benchmarks/components.host_ingest_ab — the PR3 ingest plane) so
    every bench record carries the ingest win under ``host_ingest``,
    next to the ps_ingest_* counters in the telemetry snapshot. Host
    CPU only (no device), seconds of wall time; never breaks a
    record."""
    try:
        from parameter_server_tpu.benchmarks.components import host_ingest_ab

        # parked: the A/B's pipelined arm drives a real IngestPipeline
        # whose per-batch span emits would tax only that arm of the
        # paired ratio and flood the trace with off-window ingest flows
        with telemetry_spans.parked_sink():
            rec_or_headline["host_ingest"] = host_ingest_ab(smoke)
    except Exception as e:
        rec_or_headline["host_ingest_error"] = (
            f"{type(e).__name__}: {str(e)[:200]}"
        )


def attach_wire(rec_or_headline: dict, smoke: bool) -> None:
    """Guarded embed of the compact-wire encoded-vs-raw A/B
    (benchmarks/components.wire_ab) under ``wire`` in every bench
    record: bytes/example per encoding, the multi-pass amortized bytes
    through the upload key cache, exact-mode parity, and encode cost.
    Host CPU only. When the record already carries a measured link rate
    (``host_to_device_mb_s``), also derives the link-bound ceiling each
    encoding implies — the e2e rate that bytes/example CAPS at that
    link speed (ceiling = MB/s × 1e6 ÷ bytes/example), which is the
    motivation for the whole wire: the recorded baseline sat at
    34-69k examples/sec because 107.4 B/example met a 5-27 MB/s link."""
    try:
        from parameter_server_tpu.benchmarks.components import wire_ab

        # parked: encode_exact emits a wire.encode span per call, which
        # would tax the encode arm of the paired encode-over-prep ratio
        # and land off-window noise in the trace
        with telemetry_spans.parked_sink():
            out = wire_ab(smoke)
        mb_s = rec_or_headline.get("host_to_device_mb_s")
        if mb_s:
            per_enc = {}
            for table in ("bytes_per_example", "amortized_bytes_per_example"):
                for k, v in out[table].items():
                    if v:
                        per_enc[k] = round(mb_s * 1e6 / v, 1)
            out["link_bound_examples_per_sec_at_measured_mb_s"] = per_enc
        rec_or_headline["wire"] = out
    except Exception as e:
        rec_or_headline["wire_error"] = f"{type(e).__name__}: {str(e)[:200]}"


def attach_ftrl(rec_or_headline: dict, smoke: bool) -> None:
    """Guarded embed of the sparse-FTRL update A/B
    (benchmarks/components.ftrl_sparse_ab — XLA rows path vs the fused
    Pallas gather→update→scatter kernel, ops/ftrl_sparse.py) under
    ``ftrl_sparse`` in every bench record: per-ministep ms for both
    arms, median-of-paired-reps speedup, the disclosed bytes model with
    ``hbm_gb_s``/``frac_of_peak``, and the on-chip 10x
    ``ftrl_hbm_frac_of_peak`` target the next device capture is judged
    against. On this CPU host the fused arm falls back to the rows path
    (``fused_is_fallback``) — the record is shape truth, not a speedup
    headline; never breaks a record."""
    try:
        from parameter_server_tpu.benchmarks.components import ftrl_sparse_ab

        rec_or_headline["ftrl_sparse"] = ftrl_sparse_ab(smoke)
    except Exception as e:
        rec_or_headline["ftrl_sparse_error"] = (
            f"{type(e).__name__}: {str(e)[:200]}"
        )


def attach_serve(rec_or_headline: dict, smoke: bool) -> None:
    """Guarded embed of the request-path serving bench
    (benchmarks/components.serve_ab — the serving plane, doc/SERVING.md)
    under ``serve`` in every bench record: open-loop p50/p99/p99.9 at
    two offered-load points (below capacity + 3x overload), the
    admission on/off p99 A/B (bounded tail vs queue collapse), the
    coalescer's submits-per-request merge factor, and the speculative
    LM decode lane. Rates self-calibrate to the host, so the record is
    meaningful on CPU and on chip alike; never breaks a record."""
    try:
        from parameter_server_tpu.benchmarks.components import serve_ab

        # parked: the SLO bench fires thousands of requests/s and three
        # timeline events per request (submit/execute/reply + per-line
        # fsync in the JSONL sink) would load the very tail latencies
        # being measured — and flood the trace with off-window noise
        with telemetry_spans.parked_sink():
            rec_or_headline["serve"] = serve_ab(smoke)
    except Exception as e:
        rec_or_headline["serve_error"] = f"{type(e).__name__}: {str(e)[:200]}"


def attach_decode_batching(rec_or_headline: dict, smoke: bool) -> None:
    """Guarded embed of the continuous-batching decode A/B
    (benchmarks/components.decode_batching_ab — serving/batcher.py,
    doc/SERVING.md "Continuous batching") under ``decode_batching`` in
    every bench record: batched-vs-sequential tokens/s at each slot
    count under join/leave churn (median of paired reps, token parity
    asserted in-bench), the ``speedup_at_8`` headline with its
    ``onchip_target``, and the device-resident replica serving a table
    over the host budget with zero degrades; never breaks a record."""
    try:
        from parameter_server_tpu.benchmarks.components import (
            decode_batching_ab,
        )

        # parked: the A/B times back-to-back decode lanes at
        # millisecond granularity — per-line fsync in the span sink
        # would load the very dispatch overhead being measured
        with telemetry_spans.parked_sink():
            rec_or_headline["decode_batching"] = decode_batching_ab(smoke)
    except Exception as e:
        rec_or_headline["decode_batching_error"] = (
            f"{type(e).__name__}: {str(e)[:200]}"
        )


def attach_recovery(rec_or_headline: dict, smoke: bool) -> None:
    """Guarded embed of the kill-one-shard recovery drill
    (benchmarks/components.recovery_drill — the chaos plane,
    doc/ROBUSTNESS.md) under ``recovery`` in every bench record:
    detection/recovery/MTTR wall times for an injected shard death
    under concurrent train+serve load, replayed-update count, the
    degraded/shed/failed serve accounting, the post-recovery
    bit-parity verdict, and the disarmed-overhead paired check. This
    section is DRILL METADATA, not a throughput metric —
    script/bench_diff.py's sentinel explicitly excludes it from
    banding (METADATA_SECTIONS); never breaks a record."""
    try:
        from parameter_server_tpu.benchmarks.components import recovery_drill

        # parked: the drill fires its own serve traffic and three span
        # events per request would load the dead-window latencies —
        # and flood the bench trace with off-window chaos flows
        with telemetry_spans.parked_sink():
            rec_or_headline["recovery"] = recovery_drill(smoke)
    except Exception as e:
        rec_or_headline["recovery_error"] = (
            f"{type(e).__name__}: {str(e)[:200]}"
        )


def attach_blackbox(rec_or_headline: dict, smoke: bool) -> None:
    """Guarded embed of the flight-recorder evidence under ``blackbox``
    in every bench record: the steady-state overhead paired-median A/B
    (armed ring vs no sink on the same span-instrumented work stream —
    the PR 9 disarmed-overhead pattern; the honest claim is the ratio
    straddling this host's noise floor, with the tight-loop absolute
    ns/event that a capacity flap cannot fake), the run's ring
    occupancy, and how many diagnostic bundles the trigger plane
    captured during the run. Run METADATA, not a throughput metric —
    script/bench_diff.py excludes this section from banding
    (METADATA_SECTIONS); never breaks a record."""
    try:
        from parameter_server_tpu.telemetry import blackbox

        # parked: the A/B measures its own private tee — the run's
        # JSONL sink must neither pay for nor record the probe spans
        with telemetry_spans.parked_sink():
            overhead = blackbox.overhead_ab(reps=3 if smoke else 5)
        section: dict = {"overhead": overhead}
        rec = blackbox.installed_recorder()
        if rec is not None:
            d = rec.dump()
            section["ring"] = {
                "node": d["node"],
                "events": len(d["events"]),
                "events_total": d["events_total"],
                "dropped": d["dropped"],
                "capacity": d["capacity"],
                "metrics_samples": len(d["metrics_samples"]),
            }
        section["bundles_captured"] = len(blackbox.bundles())
        rec_or_headline["blackbox"] = section
    except Exception as e:
        rec_or_headline["blackbox_error"] = (
            f"{type(e).__name__}: {str(e)[:200]}"
        )


def attach_history(rec_or_headline: dict, smoke: bool) -> None:
    """Guarded embed of the history plane under ``history`` in every
    bench record (telemetry/history.py, doc/OBSERVABILITY.md "History
    plane"): the fold-hook overhead paired-median A/B (the identical
    metric-churn workload with the ring cascade installed vs absent —
    the honest claim is the ratio straddling this host's noise floor,
    with the tight-loop per-fold cost over the full instrument catalog
    that a capacity flap cannot fake) plus the run's own installed
    store's retention/occupancy snapshot when one is live. Run
    METADATA, not a throughput metric — script/bench_diff.py excludes
    this section from banding (METADATA_SECTIONS); never breaks a
    record."""
    try:
        from parameter_server_tpu.benchmarks.components import history_ab
        from parameter_server_tpu.telemetry import history as history_mod

        # parked: the A/B churns its own private registries — the
        # run's JSONL sink must neither pay for nor record the probe
        with telemetry_spans.parked_sink():
            section: dict = {"overhead": history_ab(smoke)}
        store = history_mod.installed_store()
        if store is not None:
            store.fold(force=True)
            section["store"] = store.snapshot()
        rec_or_headline["history"] = section
    except Exception as e:
        rec_or_headline["history_error"] = (
            f"{type(e).__name__}: {str(e)[:200]}"
        )


def attach_history_drift(rec: dict, samples) -> None:
    """Live steady-state drift verdict over the run's OWN timed
    (elapsed_s, examples/sec) windows, folded into the record's
    ``history`` section after the e2e phase: the tail of the run judged
    against its post-warmup baseline — same host, same run, so no
    cross-run capacity drift can alibi or fake the verdict
    (telemetry/history.drift_check; the online twin of bench_diff's
    cross-run sentinel). Never breaks a record."""
    try:
        from parameter_server_tpu.telemetry.history import drift_check

        rec.setdefault("history", {})["live_drift"] = drift_check(
            list(samples)
        )
    except Exception as e:
        rec["history_drift_error"] = f"{type(e).__name__}: {str(e)[:200]}"


def attach_learning(rec_or_headline: dict, smoke: bool) -> None:
    """Guarded embed of the learning truth plane under ``learning`` in
    every bench record (benchmarks/components.learning_truth +
    telemetry/learning.py): the RUN's own planes first (realized
    staleness of the submissions made so far, with the in-record
    observed<=τ verdict, key-heat shard shares, convergence tail),
    then the self-contained probe — a bounded-delay training run with
    the staleness histogram, sketch-vs-exact heat parity, shard
    balance, loss/grad-norm trajectory, and the seeded LR-blow-up
    divergence drill (shipped ``loss_divergence`` rule to firing, with
    a diagnostic bundle attached). Convergence trajectories are run
    METADATA, never banded as perf — script/bench_diff.py excludes
    this section (METADATA_SECTIONS); never breaks a record. Harvest
    order matters: the probe builds its own mini-cluster
    (Postoffice.reset), which drops the run's registered planes — so
    the run view is read FIRST."""
    try:
        from parameter_server_tpu.benchmarks.components import (
            learning_truth,
        )
        from parameter_server_tpu.telemetry import learning as learning_mod

        section: dict = {}
        run = learning_mod.snapshot_all()
        if run:
            section["run"] = run
        with telemetry_spans.parked_sink():
            section["probe"] = learning_truth(smoke)
        rec_or_headline["learning"] = section
    except Exception as e:
        rec_or_headline["learning_error"] = (
            f"{type(e).__name__}: {str(e)[:200]}"
        )


def attach_consistency(rec_or_headline: dict, smoke: bool) -> None:
    """Guarded embed of the self-driving consistency A/B under
    ``consistency`` (benchmarks/components.consistency_ab): the three
    τ arms (fixed 0 / fixed max / adaptive) with the
    throughput-vs-final-loss frontier verdict, the KKT significance
    filter off/on with its suppression accounting reconciled against
    ``ps_push_keys_total``, and the seeded divergence drill through
    the controller's backoff + rollback reaction. Paired-rep medians
    with the emulated pull-RTT disclosed in-record — run METADATA,
    never banded (script/bench_diff.py METADATA_SECTIONS); never
    breaks a record. Builds its own mini-cluster (Postoffice reset),
    so it must run among the component sections, after the run planes
    are harvested."""
    try:
        from parameter_server_tpu.benchmarks.components import (
            consistency_ab,
        )

        with telemetry_spans.parked_sink():
            rec_or_headline["consistency"] = consistency_ab(smoke)
    except Exception as e:
        rec_or_headline["consistency_error"] = (
            f"{type(e).__name__}: {str(e)[:200]}"
        )


def attach_learning_run(rec: dict, worker) -> None:
    """Fold the MAIN run worker's own learning plane into the record's
    ``learning`` section AFTER the timed windows — the plane object
    rides the worker (module registration does not survive the
    component sections' Postoffice resets), and harvesting here means
    the staleness/trajectory view covers the e2e phase itself. Carries
    the in-record bounded-delay verdict for the run's OWN submissions
    (``run_staleness_within_bound``: observed max <= the configured
    max_delay); the probe asserts its own. Never breaks a record."""
    try:
        plane = getattr(worker, "_learning", None)
        if plane is None:
            return
        section = rec.setdefault("learning", {})
        snap = plane.snapshot()
        section.setdefault("run", {})[plane.worker] = snap
        ok = all(
            s["staleness"]["within_bound"]
            for s in section["run"].values()
        )
        section["run_staleness_within_bound"] = ok
        if not ok:
            section["run_staleness_breaches"] = [
                w for w, s in section["run"].items()
                if not s["staleness"]["within_bound"]
            ]
    except Exception as e:
        rec["learning_run_error"] = f"{type(e).__name__}: {str(e)[:200]}"


def attach_device(rec_or_headline: dict, smoke: bool) -> None:
    """Guarded embed of the device truth plane
    (parameter_server_tpu/telemetry/device.py) under ``device`` in
    every bench record: per-jit cost-analysis FLOPs/bytes and buffer
    sizes from the compiled-function inventory (the kv_ops entry
    points + every step builder wrap into it), recompile counts with
    the post-warmup total (the warmup mark is set right before the
    timed e2e phase, so a healthy record reads zero), the runtime
    donation-fallback count (zero on the data plane — a nonzero means
    XLA silently turned an in-place table update into a copy), HBM /
    live-buffer high-water, and the roofline cross-checks: the
    ``ftrl_sparse`` hand bytes model vs the XLA-derived bytes (ratio
    disclosed in the A/B section itself) and the flash fwd hand-FLOPs
    vs cost-analysis probe. Capture-hardware facts, not trajectory
    points — script/bench_diff.py excludes this section from banding
    (METADATA_SECTIONS); never breaks a record."""
    try:
        from parameter_server_tpu.telemetry import device as device_mod

        section = device_mod.snapshot()
        rooflines: dict = {}
        fs = rec_or_headline.get("ftrl_sparse")
        if isinstance(fs, dict) and isinstance(
            fs.get("bytes_model_cross_check"), dict
        ):
            rooflines["ftrl_sparse"] = dict(fs["bytes_model_cross_check"])
        try:
            from parameter_server_tpu.benchmarks.components import (
                flash_cost_crosscheck,
            )

            rooflines["flash"] = flash_cost_crosscheck(smoke)
        except Exception as e:
            rooflines["flash_error"] = f"{type(e).__name__}: {str(e)[:200]}"
        if rooflines:
            section["rooflines"] = rooflines
        rec_or_headline["device"] = section
    except Exception as e:
        rec_or_headline["device_error"] = (
            f"{type(e).__name__}: {str(e)[:200]}"
        )


_EXPOSITION = None  # live ExpositionServer while --expose-port is up


def _maybe_expose(po, args) -> None:
    """--expose-port: stand the cluster metrics plane up over this run
    (telemetry/exposition.py) — /metrics serves the node-labeled
    aggregate, /healthz the heartbeat+recovery verdict, and the default
    SLO alert rules evaluate live against the run's registry. Port 0
    binds ephemeral; the chosen port is printed to stderr so a scraper
    (or a human with curl) can attach mid-run."""
    global _EXPOSITION
    if getattr(args, "expose_port", None) is None:
        return
    from parameter_server_tpu.telemetry.exposition import expose_cluster

    _EXPOSITION = expose_cluster(
        po, port=args.expose_port, metrics_interval=1.0
    )
    print(f"bench: metrics exposed at {_EXPOSITION.url}/metrics "
          f"(/healthz, /debug/snapshot)", file=sys.stderr)


def _expose_summary(rec: dict) -> None:
    """One self-scrape before teardown: the record carries proof the
    endpoint served node-labeled series while the run was live."""
    if _EXPOSITION is None:
        return
    try:
        import urllib.request

        txt = urllib.request.urlopen(
            f"{_EXPOSITION.url}/metrics", timeout=10
        ).read().decode()
        nodes = sorted({
            line.split('node="', 1)[1].split('"', 1)[0]
            for line in txt.splitlines()
            if line.startswith("ps_cluster_node_up{")
        })
        ok, health = _EXPOSITION.aux.health()
        firing = health.get("alerts_firing", [])
        rec["expose"] = {
            "url": _EXPOSITION.url,
            "nodes": nodes,
            "series_lines": sum(
                1 for l in txt.splitlines() if l and not l.startswith("#")
            ),
            "healthz_ok": ok,
            "alerts_firing": firing,
        }
    except Exception as e:
        rec["expose"] = {"error": f"{type(e).__name__}: {str(e)[:200]}"}


def _close_exposition() -> None:
    global _EXPOSITION
    if _EXPOSITION is not None:
        from parameter_server_tpu.telemetry.exposition import close_cluster

        close_cluster(_EXPOSITION)
        _EXPOSITION = None


def _finish(rec: dict) -> None:
    """Print the run's one record: the last JSON line on stdout."""
    _expose_summary(rec)
    _close_exposition()
    rec["device"] = device_identity()
    if "telemetry" not in rec:
        snap = telemetry_snapshot()
        if snap is not None:
            rec["telemetry"] = snap
    print(json.dumps(rec))


# ---------------------------------------------------------------------------
# --real mode: stream actual criteo-format TEXT from disk through the C++
# parser → localization → fused device step, parsing INSIDE the timed
# pipeline, with a logloss-parity check against a NumPy FTRL oracle
# (BASELINE.json north star: "Criteo-1TB ... at logloss parity").
# ---------------------------------------------------------------------------

# HBM peak bandwidth by device_kind (public spec sheets) for utilization
# reporting; kinds not listed just omit the fraction. ONE table, shared
# with the component benches (ftrl_sparse_ab/ftrl_chain frac-of-peak).
from parameter_server_tpu.benchmarks import (  # noqa: E402
    HBM_PEAK_GB_S,
    device_identity,
)


def tree_host_nbytes(prepped) -> int:
    """Wire footprint of one prepped (host-side) batch: what actually
    crosses host->device per launch."""
    import jax

    return int(
        sum(
            getattr(leaf, "nbytes", 0)
            for leaf in jax.tree.leaves(prepped)
        )
    )


def timed_upload(prepped):
    """(staged_tree, seconds): device_put timed until every array has
    really LANDED — fetch one element of EVERY leaf, because device_put
    is async."""
    import jax

    t0 = time.perf_counter()
    dev = jax.device_put(prepped)
    for leaf in jax.tree.leaves(dev):
        np.asarray(leaf.ravel()[:1])
    return dev, time.perf_counter() - t0


class UploadPipeline:
    """Dedicated uploader thread: stacks T host-prepped minibatches
    into a superbatch and stages it to the device, overlapping the
    host→device transfer with the producer's parse/localize work and
    the main thread's device waits.

    Why a thread helps even on a ONE-core host (this image): the wire
    transfer is socket I/O inside the PJRT client (GIL-free) and the
    C++ parser releases the GIL too, so parse CPU time and upload wire
    time genuinely overlap; only the numpy stack/localize slices
    compete for the core. Before this, ``jax.device_put`` ran serially
    on the main thread between submits — with the link at ~10-25 MB/s
    the wire time dominated the loop and the breakdown fields read
    upload-bound (r4 verdict item 5: push e2e to the link ceiling).

    Iterating yields ``(device_superbatch, num_examples, nbytes)`` —
    ``nbytes`` is what actually CROSSED the link: with an upload key
    cache attached (``cache=``, learner/wire.UploadCache — the encoded-
    wire default since the wire flip), leaves the device already holds
    ship ~signature bytes, and the yielded count subtracts the cache's
    saved bytes so the e2e bytes/example and the link-ceiling
    reconciliation stay honest. A trailing partial group (< T
    minibatches) is skipped — it would compile a second scan shape
    inside the timed window — and reported via ``skipped_examples``
    after iteration ends. Exceptions on the uploader thread propagate
    to the consuming iterator (the plumbing is :func:`iter_on_thread`;
    this class only adds the staging generator and the accounting).
    The cache is stateful and single-owner by contract — it lives on
    THIS pipeline's one staging thread, satisfying the PR-3
    stateless-or-feeder rule (UploadCache asserts it)."""

    def __init__(self, parts_iter, T: int, queue_depth: int = 2, cache=None):
        self.skipped_examples = 0
        # staging-leg codec accounting (wire_compress): frames decoded
        # on THIS pipeline's one staging thread, right before the
        # stack+device_put — raw vs framed bytes disclosed so the
        # record can quote the staging leg net of compression while
        # ``nbytes`` (what reconcile_link_ceiling divides) stays the
        # REALIZED host→device traffic
        self.staged_raw_bytes = 0
        self.staged_compressed_bytes = 0
        self._cache = cache
        self._it = iter_on_thread(
            self._stage(parts_iter, T), maxsize=queue_depth
        )

    def _stage(self, parts_iter, T: int):
        # runs on iter_on_thread's daemon thread
        import jax

        from parameter_server_tpu.learner.wire import CompressedBatch

        parts = []
        for item in parts_iter:
            if isinstance(item, CompressedBatch):
                self.staged_raw_bytes += item.raw_nbytes
                self.staged_compressed_bytes += item.wire_nbytes
                from parameter_server_tpu.learner.wire import (
                    decompress_batch,
                )

                item = decompress_batch(item)
            parts.append(item)
            if len(parts) < T:
                continue
            # one timeline flow per superbatch: stack → upload here,
            # then the consumer submits the trainer step under the same
            # id (the 4th yielded element), so the executor.step span
            # joins the flow and the critical path reads end to end
            fid = telemetry_spans.maybe_new_flow()
            with telemetry_spans.flow_scope(fid):
                with telemetry_spans.span("bench.stack", phase="e2e"):
                    sb = stack_supersteps(parts, T)
                parts = []
                nb = tree_host_nbytes(sb)
                # device_put returns promptly with transfer in flight;
                # the bounded queue keeps at most a couple of
                # superbatches staged ahead so host memory stays flat.
                with telemetry_spans.span(
                    "bench.upload", phase="e2e", nbytes=nb
                ):
                    if self._cache is not None:
                        saved0 = self._cache.saved_bytes
                        staged = self._cache(sb)
                        nb = max(
                            0, nb - (self._cache.saved_bytes - saved0)
                        )
                    else:
                        staged = jax.device_put(sb)
            yield staged, int(sb.num_examples), nb, fid
        self.skipped_examples = sum(int(p.num_examples) for p in parts)

    def __iter__(self):
        return self._it


def measure_upload_mb_s(prepped, reps: int = 3) -> float:
    """Median host->device bandwidth moving a real prepped batch."""
    nbytes = tree_host_nbytes(prepped)
    obs = []
    for _ in range(reps):
        _, sec = timed_upload(prepped)
        obs.append(nbytes / sec / 1e6)
    return float(np.median(obs))


def roofline_fields(prepped, num_slots: int, device_step_sec: float,
                    examples_per_launch: int, t_mb: int | None = None) -> dict:
    """The measurement VERDICT r2 asked for: separate the machine from
    the link. Reports wire bytes/example, observed upload MB/s, and the
    FTRL table pass's HBM traffic vs chip peak (the dense update reads+
    writes z and sqrt_n: 16 B/slot/minibatch — the dominant HBM term at
    2^26+; gathers add O(nnz) on top, ignored here as <2%).

    ``prepped`` should be a SMALL representative batch (one minibatch):
    bytes/example, MB/s and the link-bound ceiling are all size-invariant
    ratios, and probing bandwidth with a deep-T superbatch would move GBs
    for no informational gain. Pass
    ``t_mb`` explicitly when ``device_step_sec`` covers more minibatches
    than ``prepped`` holds (the sweep's winning launch depth)."""
    import jax

    dev = jax.devices()[0]
    wire_bytes = tree_host_nbytes(prepped)
    up_mb_s = measure_upload_mb_s(prepped)
    # device_step_sec covers t_mb minibatches (one launch); the table is
    # touched once per MINIBATCH by the scan superstep
    if t_mb is None:
        t_mb = getattr(prepped, "steps", 1)
    hbm_bytes = 16.0 * num_slots * t_mb
    hbm_gb_s = hbm_bytes / device_step_sec / 1e9 if device_step_sec else None
    out = {
        "bytes_per_example": round(wire_bytes / max(1, examples_per_launch), 1),
        "host_to_device_mb_s": round(up_mb_s, 1),
        "device_kind": dev.device_kind,
        "ftrl_hbm_gb_s": round(hbm_gb_s, 1) if hbm_gb_s else None,
        "num_slots": num_slots,
    }
    peak = HBM_PEAK_GB_S.get(dev.device_kind)
    if peak and hbm_gb_s:
        out["ftrl_hbm_frac_of_peak"] = round(hbm_gb_s / peak, 3)
    # the link-bound ceiling this bytes/example implies, for honesty
    # about what e2e rates the host→device link allows
    if wire_bytes:
        out["link_bound_examples_per_sec_at_measured_mb_s"] = round(
            up_mb_s * 1e6 / (wire_bytes / max(1, examples_per_launch)), 1
        )
    return out


def flush(worker):
    """REAL pipeline drain: fetch a state scalar to the host — a true
    device->host dependency, which cannot return before the device
    finishes."""
    import jax

    np.asarray(jax.tree.leaves(worker.state)[0][:1])


def phase_breakdown(worker, make_parts, T: int, launches: int = 3,
                    profile_dir: "str | None" = None) -> dict:
    """Serialized prep -> upload -> device timing for a few launches.

    The pipelined e2e loops overlap these stages (that is the point of
    the pipeline), which also HIDES where a launch's time goes — r3
    verdict: "1.018x with 96% of the roofline unexplained". Outside the
    timed windows, run each stage to completion with a flush between:
    the sum exceeds a pipelined launch (overlap removed) but the RATIO
    answers which stage bounds the pipeline. ``profile_dir`` wraps the
    first launch's device step in a jax.profiler trace
    (utils/profiling.device_trace) for op-level attribution."""
    import jax

    from parameter_server_tpu.utils.profiling import annotate, device_trace

    prep_s = up_s = dev_s = 0.0
    bytes_moved = 0
    for i in range(launches):
        # one timeline flow per serialized launch: the three stage
        # spans below (phase="breakdown") are what the record's
        # ``attribution`` section is computed from — the trace-derived
        # twin of the hand accumulators in this loop, kept in lockstep
        # by attach_attribution's agrees_with_hand_breakdown check
        fid = telemetry_spans.maybe_new_flow()
        with telemetry_spans.flow_scope(fid):
            t0 = time.perf_counter()
            with telemetry_spans.span("bench.prep", phase="breakdown"):
                sb = stack_supersteps(make_parts(i), T)
            prep_s += time.perf_counter() - t0
            nb = tree_host_nbytes(sb)
            bytes_moved += nb
            with telemetry_spans.span(
                "bench.upload", phase="breakdown", nbytes=nb
            ):
                staged, sec_up = timed_upload(sb)
            up_s += sec_up
            if profile_dir and i == 0:
                # fresh capture: a reused directory accumulates runs,
                # and summarize_trace must not mix this run with stale
                # traces from a previous bench (or code version).
                # Remove ONLY the profiler's own plugins/ subtree — the
                # user may have pointed --profile at a directory
                # holding other files
                import shutil

                shutil.rmtree(
                    os.path.join(profile_dir, "plugins"), ignore_errors=True
                )
            ctx = (
                device_trace(profile_dir) if (profile_dir and i == 0)
                else contextlib.nullcontext()
            )
            if i == 0:
                # wall anchor for the merged device track: the profiler
                # clock has no wall reference, so the capture's ops are
                # shifted to start at this launch's host wall time
                dev_wall0 = time.time()
            t0 = time.perf_counter()
            with ctx:
                # the profiler's device tracks line up with the host
                # timeline through this named annotation (no-op off-TPU)
                with telemetry_spans.span("bench.device", phase="breakdown"):
                    with annotate("bench.device"):
                        worker.executor.wait(
                            worker._submit_prepped(staged, with_aux=False)
                        )
                        flush(worker)
            dev_s += time.perf_counter() - t0
    total = prep_s + up_s + dev_s
    out = {
        "breakdown_launches": launches,
        "breakdown_prep_s_per_launch": round(prep_s / launches, 4),
        "breakdown_upload_s_per_launch": round(up_s / launches, 4),
        "breakdown_device_s_per_launch": round(dev_s / launches, 4),
        "breakdown_bound": max(
            (prep_s, "host_prep"), (up_s, "upload"), (dev_s, "device")
        )[1],
        "breakdown_fracs": {
            "host_prep": round(prep_s / total, 3),
            "upload": round(up_s / total, 3),
            "device": round(dev_s / total, 3),
        } if total else None,
    }
    if up_s:
        out["breakdown_upload_mb_s"] = round(bytes_moved / up_s / 1e6, 1)
    if profile_dir:
        out["profile_dir"] = profile_dir
        from parameter_server_tpu.utils.profiling import (
            device_track_events,
            summarize_trace,
        )

        summary = summarize_trace(profile_dir)
        if summary:
            # self-contained phase attribution (ps_pull/ps_compute/
            # ps_push/ps_update named scopes) — the record answers
            # "where does the device step time go" without TensorBoard
            out["profile_device_ms"] = summary["device_ms"]
            out["profile_phases_ms"] = summary["phases"]
            out["profile_top_ops"] = summary["top_ops"][:6]
        # the capture's device ops land in the run's span timeline as a
        # device:<pid> track (anchored at the profiled launch's wall
        # time), so the Chrome export renders them under the host
        # tracks and attach_attribution grows its device_compute
        # sub-breakdown + flow arrows from the submitting step spans
        dev_events = device_track_events(profile_dir, host_anchor=dev_wall0)
        for ev in dev_events:
            ev["phase"] = "breakdown"
            telemetry_spans.emit(dict(ev))
        if dev_events:
            out["profile_device_track_events"] = len(dev_events)
    return out


def reconcile_link_ceiling(rec: dict, bytes_moved: int, done_ex: int,
                           dt: float) -> None:
    """Make the link-bound ceiling consistent with what the e2e phase
    itself observed (r3 verdict: e2e beat its own 'ceiling' by 1.6x —
    the probe-based MB/s was measured at a different moment on a link
    that drifts several x over minutes). The phase's own achieved wire
    rate (bytes actually staged / phase wall time) is a PROVEN lower
    bound on link capacity during the phase; the published ceiling uses
    whichever of probe/achieved is higher, with both disclosed."""
    if not (bytes_moved and done_ex and dt):
        return
    bpe = bytes_moved / done_ex
    achieved_mb_s = bytes_moved / dt / 1e6
    rec["e2e_bytes_per_example"] = round(bpe, 1)
    rec["e2e_achieved_wire_mb_s"] = round(achieved_mb_s, 1)
    probe = rec.get("host_to_device_mb_s")
    used = max(achieved_mb_s, probe or 0.0)
    rec["link_mb_s_used_for_ceiling"] = round(used, 1)
    rec["link_bound_examples_per_sec_at_measured_mb_s"] = round(
        used * 1e6 / bpe, 1
    )
    if probe and achieved_mb_s > probe:
        rec["link_probe_underestimated"] = (
            "in-phase achieved wire rate exceeded the probe's MB/s — "
            "the probe hit a throttled stretch; ceiling uses achieved"
        )


def stack_supersteps(parts, t: int):
    """Cycle ``parts`` to exactly ``t`` minibatches and stack them into
    one scan superbatch — every launch must reuse the ONE compiled
    scan program for its (wire, t) shape; a mid-benchmark shape change
    would put tens of seconds of XLA compile inside a timed window.
    Dispatches on the prepped wire type: ELL-bits batches (the legacy
    headline wire) and compact-encoded exact batches (the default since
    the wire flip — see run_synthetic's config note) stack into their
    respective scan superbatches."""
    from parameter_server_tpu.apps.linear.async_sgd import stack_bits_batches
    from parameter_server_tpu.learner.wire import (
        EncodedEllStreamBatch,
        EncodedExactBatch,
        stack_encoded_batches,
        stack_stream_batches,
    )

    full = [parts[i % len(parts)] for i in range(t)]
    if t == 1:
        return full[0]
    if isinstance(full[0], EncodedExactBatch):
        return stack_encoded_batches(full)
    if isinstance(full[0], EncodedEllStreamBatch):
        return stack_stream_batches(full)
    return stack_bits_batches(full)


def device_only_sweep(worker, prep_parts, base_t: int, minibatch: int,
                      smoke: bool):
    """Device-only rate at increasing scan depths T (minibatches fused
    per launch).

    Each launch pays a fixed dispatch cost, so at small T the
    "device-only" rate still tracks the dispatch. Deeper supersteps
    amortize it toward the true device rate, and the scan
    applies minibatches SEQUENTIALLY on device, so depth does not add
    staleness — convergence semantics match running the minibatches one
    by one (async delay applies across launches, not within). The sweep
    deepens ×4 adaptively while the rate keeps improving ≥10%, capped
    at T=512 (the superbatch upload is the cost of each probe). Every
    swept T is a real streaming
    configuration (the e2e phases run the configured T), and the full
    sweep is disclosed next to the winner.

    Returns ``(best_t, best_rate, best_sec_per_launch, swept)`` where
    swept maps T -> rate. (The staged superbatch is deliberately NOT
    returned: at T=512 it is ~GB-scale, and the roofline probe only
    needs a single-minibatch representative.)"""
    import jax

    best = None
    swept = {}
    t = base_t
    prev_rate = None
    while True:
        try:
            sb = stack_supersteps(prep_parts, t)
            staged = jax.device_put(sb)
            # untimed: compile this T's scan program + settle the pipeline
            worker.executor.wait(
                worker._submit_prepped(staged, with_aux=False)
            )
            flush(worker)
            launches = max(3, 96 // t)
            pending = []
            t0 = time.perf_counter()
            for _ in range(launches):
                pending.append(
                    worker._submit_prepped(staged, with_aux=False)
                )
                if len(pending) > 2:
                    worker.executor.wait(pending.pop(0))
            while pending:
                worker.executor.wait(pending.pop(0))
            flush(worker)
            sec = time.perf_counter() - t0
        except Exception as e:  # e.g. RESOURCE_EXHAUSTED at deep T —
            # possibly only once >2 launches are in flight, so the timed
            # loop is inside the guard too. The warmup already ran the
            # user-configured base_t; never let an oversized sweep depth
            # zero the whole run — disclose and stop (larger only gets
            # worse)
            swept[t] = f"failed: {type(e).__name__}"
            break
        rate = t * minibatch * launches / sec
        swept[t] = round(rate, 1)
        if best is None or rate > best[1]:
            best = (t, rate, sec / launches)
        if smoke or t >= 512:
            break
        if prev_rate is not None and rate < prev_rate * 1.1:
            break  # diminishing returns: dispatch is amortized
        prev_rate = rate
        t *= 4
    if best is None:
        # even base_t failed (warmup ran it, so this is in-flight
        # pressure, not shape trouble) — callers catch this and continue
        # with the e2e phase so the run still produces a record
        raise RuntimeError(f"device_only_sweep: no depth succeeded ({swept})")
    return best + (swept,)


def headline_phase(worker, prep_parts, base_t: int, minibatch: int,
                   smoke: bool, num_slots: int, note: str,
                   extra: dict | None = None) -> dict:
    """The device-only headline, measured BEFORE the long e2e phase.
    Shared by both bench modes: sweep → headline fields → HBM stats →
    roofline. On total sweep
    failure the run continues to the e2e phase with value 0 and the
    failure disclosed."""
    import jax

    try:
        best_t, dev_rate, dev_sec, swept = device_only_sweep(
            worker, prep_parts, base_t, minibatch, smoke
        )
    except RuntimeError as e:
        headline = {
            "value": 0,
            "vs_baseline": 0,
            "sweep_error": str(e),
            "note": note,
        }
        headline.update(extra or {})
        return headline
    headline = {
        "value": round(dev_rate, 1),
        "vs_baseline": round(dev_rate / REF_8NODE_EXAMPLES_PER_SEC, 3),
        "steps_per_launch_best": best_t,
        "steps_per_launch_swept": swept,
        "note": note,
    }
    headline.update(extra or {})
    hbm = jax.devices()[0].memory_stats() or {}
    if hbm.get("bytes_in_use") is not None:
        headline["hbm_bytes_in_use"] = hbm["bytes_in_use"]
        headline["hbm_bytes_limit"] = hbm.get("bytes_limit")
    # bandwidth/bytes ratios are size-invariant: probe with ONE minibatch
    # (a deep-T superbatch would re-move GBs over the host link); the HBM
    # accounting still uses the winning launch depth via t_mb
    headline.update(
        roofline_fields(prep_parts[0], num_slots, dev_sec,
                        minibatch, t_mb=best_t)
    )
    return headline


from parameter_server_tpu.apps.linear.oracle import FtrlOracle  # noqa: E402
from parameter_server_tpu.data.criteo_synth import (  # noqa: E402
    ROW_BYTES as _ROW_BYTES,
    write_criteo_file,
)


def ensure_criteo_file(path: str, target_mb: int, p_cat: int = 1 << 24) -> str:
    """Generate (once, cached on disk) a criteo-format text file of
    ~target_mb MB. Deterministic: seed 0."""
    want = target_mb << 20
    if os.path.exists(path) and abs(os.path.getsize(path) - want) < (_ROW_BYTES << 12):
        return path
    t0 = time.perf_counter()
    write_criteo_file(path, -(-want // _ROW_BYTES), p_cat=p_cat)
    print(
        f"# generated {os.path.getsize(path) >> 20}MB criteo text in "
        f"{time.perf_counter() - t0:.1f}s -> {path}",
        file=sys.stderr,
    )
    return path


def run_real(args) -> int:
    """End-to-end real-data bench: criteo TEXT on disk → chunked C++ parse
    (thread pool) → hash/bit-pack localization → device submit, all inside
    the timed loop; then a device-only rate on pre-staged batches; plus a
    logloss-parity phase vs FtrlOracle. One JSON line with all three."""
    import jax

    from parameter_server_tpu.apps.linear.async_sgd import AsyncSGDWorker
    from parameter_server_tpu.apps.linear.config import (
        Config,
        LearningRateConfig,
        PenaltyConfig,
        SGDConfig,
    )
    from parameter_server_tpu.data.stream_reader import StreamReader
    from parameter_server_tpu.system.postoffice import Postoffice

    num_slots = args.num_slots if args.num_slots >= (1 << 26) else (1 << 26)
    if args.smoke:
        num_slots = 1 << 18
    path = os.path.join(
        os.path.dirname(os.path.abspath(__file__)),
        "data",
        "criteo_bench",
        f"part-{args.real_mb}mb.txt",
    )
    ensure_criteo_file(path, args.real_mb)
    file_rows = os.path.getsize(path) // _ROW_BYTES

    Postoffice.reset()
    po = Postoffice.instance().start()
    trace_path = ensure_trace_sink()
    # HBM/live-buffer gauges refresh on every snapshot/scrape from here
    # on (telemetry/device.py collector; feeds the record's device.hbm
    # section and the ps_device_hbm_* families on /metrics)
    from parameter_server_tpu.telemetry.device import install_hbm_monitor

    install_hbm_monitor()
    _maybe_expose(po, args)

    alpha, beta, l1 = 0.1, 1.0, 1.0
    conf = Config()
    conf.penalty = PenaltyConfig(type="l1", lambda_=[l1])
    conf.learning_rate = LearningRateConfig(type="decay", alpha=alpha, beta=beta)
    # THE STREAM-ONCE WIRE FLIP (ROADMAP item 1, --real half): the
    # production-shaped path streams each example ONCE, so the upload
    # key cache never hits and the exact encoding loses to raw bits —
    # the lane-dictionary stream wire is the cache-free encoding built
    # for exactly this regime (~96 B/example vs the recorded 126.9 at
    # 2^26 slots, bit-identical decode on device). Falls back to the
    # bits wire per batch when a batch leaves the pinned lane statics
    # (fallbacks disclosed under e2e_wire).
    conf.async_sgd = SGDConfig(
        algo="ftrl",
        minibatch=args.minibatch,
        num_slots=num_slots,
        max_delay=0,  # parity first; the timed phase relaxes to 4
        ell_lanes=39,
        wire=args.real_wire,
        wire_compress=args.wire_compress,
        pull_filter=(
            [{"type": "fixing_float", "num_bytes": args.pull_bytes}]
            if args.pull_bytes else []
        ),
    )
    worker = AsyncSGDWorker(conf, mesh=po.mesh)

    def stream():
        return StreamReader([path], "criteo").minibatches_bytes(
            args.minibatch, threads=args.parse_threads
        )

    # -- phase 1: logloss parity vs the NumPy oracle (sequential weights:
    # max_delay=0 means the device pulls the latest state every step, so
    # the oracle sees identical math modulo f32 reduction order) --
    # parse-only ceiling: disk -> C++ parse, no localize/upload/device —
    # the host-parse term of the pipeline roofline (the breakdown fields
    # price localize/upload/device). Direct parser-core measurement
    # (reader/prefetch machinery would measure its BUFFER drain rate,
    # not parsing), taken BEFORE the parity stream exists so its
    # thread-pool's in-flight chunk parses can't contend for the core.
    from parameter_server_tpu.data.text_parser import ExampleParser

    with open(path, "rb") as f:
        chunk = f.read(2 << 20 if args.smoke else 16 << 20)
    chunk = chunk[: chunk.rfind(b"\n") + 1]
    pparser = ExampleParser("criteo")
    # warm (C++ lib load, caches) with a LINE-ALIGNED prefix — a
    # mid-row cut is outside parse_text's documented contract
    pparser.parse_text(chunk[: chunk.rfind(b"\n", 0, 1 << 18) + 1])
    t0 = time.perf_counter()
    pb = pparser.parse_text(chunk)
    parse_sec = time.perf_counter() - t0
    parse_only_ex_s = (
        round(pb.n / parse_sec, 1) if parse_sec and pb.n else None
    )
    del chunk, pb

    oracle = FtrlOracle(num_slots, alpha, beta, l1)
    parity_steps = 4 if args.smoke else args.parity_steps
    dev_obj = orc_obj = parity_ex = 0.0
    batches = stream()
    kept = []
    for i in range(parity_steps):
        b = next(batches)
        if b.n < args.minibatch:
            break
        kept.append(b)
        prepped = jax.device_put(worker.prep(b, device_put=False))
        m = worker.executor.wait(worker._submit_prepped(prepped, with_aux=False))
        dev_obj += float(m["objective"])
        orc_obj += oracle.step(b)
        parity_ex += b.n
    assert parity_ex > 0, (
        f"file too small for parity: need >= {args.minibatch} rows, "
        f"have {file_rows}"
    )
    ll_dev = dev_obj / parity_ex
    ll_orc = orc_obj / parity_ex
    # under a quantized pull (--pull-bytes) the oracle stays EXACT while
    # the device trains on stochastically rounded weights; the rounding
    # is unbiased (measured drift ~1e-5 on smoke) but the gate widens
    # 2x to absorb compounding over the full parity window, disclosed
    # in the record
    tol_scale = 2.0 if args.pull_bytes else 1.0
    parity_ok = abs(ll_dev - ll_orc) <= tol_scale * max(0.01, 0.02 * ll_orc)
    assert parity_ok, (
        f"logloss parity FAILED: device {ll_dev:.5f} vs oracle {ll_orc:.5f}"
    )


    # -- phase 2: end-to-end timed stream, parsing inside the pipeline.
    # Three stages on three threads: a producer parses (C++ releases
    # the GIL) + localizes, an UploadPipeline thread stacks supersteps
    # and stages them to the device (the transfer is GIL-free), and
    # the main thread keeps launches in flight. Even on a SINGLE-core
    # host (this image) the stages overlap: parse CPU runs while the
    # wire moves bytes and the device steps — only the numpy
    # stack/localize slices compete for the core. --
    worker.sgd.max_delay = 4
    worker.executor.max_in_flight = 5
    T = max(1, args.steps_per_launch)

    # untimed warmup: compile BOTH step programs before the clock starts
    # (the donation split jits the snapshot and delayed paths
    # separately, and which one a launch takes depends on the snapshot
    # counter — the timed stream must never pay a compile). One normal
    # launch compiles the snapshot program; a direct call with copied
    # buffers compiles the delayed program (jitted steps are pure — the
    # discarded result mutates nothing, and copies keep donation away
    # from the live table).
    from parameter_server_tpu.apps.linear.async_sgd import (
        prep_batch_ell_bits,
    )
    from parameter_server_tpu.learner.wire import EncodedEllStreamBatch

    prep_parts = [worker.prep(b, device_put=False) for b in kept]
    # e2e_wire: the --real twin of the synthetic record's section (the
    # stream-once path's wire choice must be visible in the record) —
    # which wire the stream actually rides, the per-encoding
    # bytes/example A/B on THIS run's first real batch, and the pinned
    # lane statics. bench_diff treats it as metadata, never a band.
    stream_mode = isinstance(prep_parts[0], EncodedEllStreamBatch)
    warmup_fallbacks = 0
    if stream_mode:
        # a kept batch past the pinned lane statics fell back to the
        # bits wire — a mixed list cannot stack into the one compiled
        # scan shape (same guard the timed stream applies), so drop
        # fallback parts from the warm pool and disclose
        n0 = len(prep_parts)
        prep_parts = [
            p for p in prep_parts
            if isinstance(p, EncodedEllStreamBatch)
        ]
        warmup_fallbacks = n0 - len(prep_parts)
    rows_pad, _, _ = worker._padding(kept[0])
    bits_part = prep_batch_ell_bits(
        kept[0], worker.directory, worker._num_shards(), rows_pad, 39,
        worker.num_slots,
    )
    e2e_wire = {
        "wire": conf.async_sgd.wire,
        "wire_actual": "stream" if stream_mode else "bits",
        "wire_compress": conf.async_sgd.wire_compress or None,
        "max_delay": 4,  # the timed phase's delay bound (set below)
        "bytes_per_example": {
            "bits": round(
                tree_host_nbytes(bits_part) / args.minibatch, 1
            ),
            **(
                {
                    "stream": round(
                        tree_host_nbytes(prep_parts[0]) / args.minibatch,
                        1,
                    )
                }
                if stream_mode
                else {}
            ),
        },
    }
    if stream_mode:
        e2e_wire["warmup_fallback_parts"] = warmup_fallbacks
        st = worker._stream_statics
        e2e_wire["stream_statics"] = {
            "dict_lanes": len(st.dict_lanes),
            "raw_lanes": st.lanes - len(st.dict_lanes),
            "code_bits": st.code_bits,
            "raw_bits": st.raw_bits,
            "dict_pad": st.dict_pad,
        }
    warm = stack_supersteps(prep_parts, T)
    warm = jax.device_put(warm)
    worker.executor.wait(worker._submit_prepped(warm, with_aux=False))
    flush(worker)
    step_fn = worker._get_step(warm, False)
    live_copy = jax.tree.map(lambda x: x.copy(), worker.state)
    pull_copy = jax.tree.map(lambda x: x.copy(), worker.state)
    jax.block_until_ready(
        step_fn(live_copy, pull_copy, warm, np.uint32(0))[1]["num_ex"]
    )
    del live_copy, pull_copy

    headline = headline_phase(
        worker, prep_parts,
        T, args.minibatch, args.smoke, num_slots,
        note="value = device-only rate (pre-staged, no parsing; best "
        "scan depth of the disclosed sweep); "
        "e2e_stream = disk->parse->localize->upload->step",
        extra={
            "logloss_device": round(ll_dev, 5),
            "logloss_oracle": round(ll_orc, 5),
            "parity_ok": parity_ok,
            **({"parity_tol_relaxed_for_quantized_pull": tol_scale}
               if args.pull_bytes else {}),
            "parse_only_examples_per_sec": parse_only_ex_s,
            "e2e_wire": e2e_wire,
        },
    )
    # serialized stage pricing (localize+pack / upload / device) — the
    # --real stream adds PARSE on top, priced by comparing e2e below.
    # Guarded + re-beaten (see run_synthetic's breakdown note).
    try:
        headline.update(phase_breakdown(
            worker,
            lambda i: [
                worker.prep(kept[(i * T + j) % len(kept)], device_put=False)
                for j in range(T)
            ],
            T,
            profile_dir=args.profile,
        ))
    except Exception as e:
        headline["breakdown_error"] = f"{type(e).__name__}: {str(e)[:200]}"
    attach_kv_dataplane(headline, worker.mesh, args.smoke)
    attach_host_ingest(headline, args.smoke)
    attach_wire(headline, args.smoke)
    attach_ftrl(headline, args.smoke)
    attach_serve(headline, args.smoke)
    attach_decode_batching(headline, args.smoke)
    attach_recovery(headline, args.smoke)
    attach_blackbox(headline, args.smoke)
    # history-plane fold-hook overhead A/B + the live store snapshot
    # (doc/OBSERVABILITY.md "History plane")
    attach_history(headline, args.smoke)
    # learning truth plane (staleness vs τ, heat/shard balance,
    # convergence trajectory, divergence drill). Runs LAST among the
    # component sections: its probe resets the Postoffice, and the run
    # planes it harvests first must still cover the phases above.
    attach_learning(headline, args.smoke)
    # self-driving consistency A/B (adaptive τ + KKT filter + rollback
    # drill) — also Postoffice-resetting, so it rides with learning at
    # the tail of the component sections
    attach_consistency(headline, args.smoke)

    wire_fallback = {"parts": 0, "rows": 0}

    def host_prepped():
        for b in batches:  # rest of the file
            if b.n < args.minibatch:
                break  # keep superstep shapes static
            with telemetry_spans.span("bench.prep", phase="e2e"):
                part = worker.prep(b, device_put=False)
            if stream_mode and not isinstance(part, EncodedEllStreamBatch):
                # a batch left the pinned lane statics and fell back to
                # the bits wire — a mixed group cannot stack into the
                # one compiled scan shape, so the batch is dropped from
                # the timed stream and DISCLOSED (never silently mixed;
                # rows dropped are excluded from the rate's numerator)
                wire_fallback["parts"] += 1
                wire_fallback["rows"] += int(b.n)
                continue
            if args.wire_compress:
                # staging-leg codec on the producer (prep) thread; the
                # UploadPipeline's staging thread decodes before the
                # stack+device_put (the stateless-or-feeder split)
                from parameter_server_tpu.learner.wire import (
                    compress_batch,
                )

                part = compress_batch(
                    part, encoding="stream" if stream_mode else "bits"
                )
            yield part

    def prepped_stream():
        # producer thread even on one core: parse is GIL-free C++, so
        # it overlaps the uploader's socket writes and the device steps
        return iter_on_thread(host_prepped(), maxsize=3 * T)

    # warmup mark for the device inventory: every program the timed
    # stream below will run has compiled by now (warmup + headline +
    # the A/B attaches) — recompiles_post_warmup must read zero
    from parameter_server_tpu.telemetry import device as _device_mod

    _device_mod.mark_warmup()
    e2e_wall0 = time.time()
    t0 = time.perf_counter()
    done_ex = 0
    wire_bytes_moved = 0
    pending = []
    # (elapsed_s, examples/sec) per ~2 s stretch for the live_drift
    # verdict (no flush per sample: submissions are pipelined, so each
    # stretch's rate is approximate — the drift check medians segments)
    drift_samples = []
    win_ex, win_t = 0, t0
    pipe = UploadPipeline(prepped_stream(), T)
    for dev_sb, n_ex, nb, fid in pipe:
        done_ex += n_ex
        win_ex += n_ex
        _now = time.perf_counter()
        if _now - win_t >= 2.0:
            drift_samples.append((_now - t0, win_ex / (_now - win_t)))
            win_ex, win_t = 0, _now
        wire_bytes_moved += nb  # actual staged bytes, not a dtype model
        # device_put returned with the transfer possibly still in
        # flight: the wait below may pay the wire time, so grace it on
        # THIS thread (the beater) like the pre-pipeline code did
        with telemetry_spans.flow_scope(fid):
            pending.append(worker._submit_prepped(dev_sb, with_aux=False))
        if len(pending) > 2:
            worker.executor.wait(pending.pop(0))
    # a trailing partial group would compile a second scan shape inside
    # the timed window; the pipeline skips it — disclose the drop
    skipped_tail = pipe.skipped_examples
    for ts in pending:
        worker.executor.wait(ts)
    flush(worker)
    dt = time.perf_counter() - t0
    e2e_wall1 = time.time()
    e2e_rate = done_ex / dt

    rec = {
        "metric": args.metric,  # the ONE definition lives in main()
        "unit": "examples/sec",
        "e2e_stream": round(e2e_rate, 1),
        "e2e_vs_baseline": round(e2e_rate / REF_8NODE_EXAMPLES_PER_SEC, 3),
        "file_mb": os.path.getsize(path) >> 20,
        "file_rows": int(file_rows),
        "skipped_tail_rows": int(skipped_tail),
    }
    e2e_wire["fallback_parts"] = wire_fallback["parts"]
    e2e_wire["fallback_rows_dropped"] = wire_fallback["rows"]
    if pipe.staged_raw_bytes:
        # staging leg net of compression (the ps_wire accounting twin);
        # the link bytes in reconcile_link_ceiling stay REALIZED
        e2e_wire["staging_leg"] = {
            "raw_mb": round(pipe.staged_raw_bytes / 1e6, 1),
            "compressed_mb": round(pipe.staged_compressed_bytes / 1e6, 1),
            "ratio": round(
                pipe.staged_raw_bytes
                / max(1, pipe.staged_compressed_bytes),
                3,
            ),
        }
    rec.update(headline)
    reconcile_link_ceiling(rec, wire_bytes_moved, done_ex, dt)
    # the run worker's OWN learning plane, harvested after the timed
    # stream so its staleness/trajectory view covers the e2e phase
    attach_learning_run(rec, worker)
    # live steady-state drift: the run's tail stretches vs its own
    # post-warmup baseline (doc/OBSERVABILITY.md "History plane")
    attach_history_drift(rec, drift_samples)
    # device truth plane AFTER the timed stream: the post-warmup
    # recompile count covers the phase that must not re-specialize
    attach_device(rec, args.smoke)
    attach_attribution(rec, trace_path, (e2e_wall0, e2e_wall1))
    _finish(rec)
    return 0


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true", help="tiny quick run (CI)")
    ap.add_argument("--minibatch", type=int, default=16384)
    # criteo shape: 13 numeric + 26 categorical = 39 features/example,
    # categorical dominating (binary). We bench the binary/ELL hot path.
    ap.add_argument("--nnz-per-row", type=int, default=39)
    ap.add_argument("--num-slots", type=int, default=1 << 22)
    ap.add_argument("--steps", type=int, default=240)
    ap.add_argument("--warmup", type=int, default=8)
    ap.add_argument(
        "--real",
        action="store_true",
        help="stream a real criteo-format text file with parsing inside the "
        "timed pipeline + logloss parity vs the numpy oracle (table 2^26)",
    )
    ap.add_argument("--real-mb", type=int, default=2048, help="file size to stream")
    ap.add_argument(
        "--real-wire",
        default="stream",
        choices=("stream", "bits"),
        help="--real path's ELL wire: DEFAULT 'stream' — the stream-once "
        "lane-dictionary encoding (cache-free: small-vocabulary lanes "
        "ship uslot tables + packed ucols, ~96 B/ex vs bits' 126.9 at "
        "2^26; ROADMAP item 1's --real half); 'bits' restores the "
        "legacy raw bit stream. Per-batch fallbacks to bits are "
        "disclosed under e2e_wire",
    )
    ap.add_argument(
        "--wire-compress",
        default="",
        choices=("", "lz"),
        help="staging-leg byte codec for the --real stream: prep "
        "compresses each encoded batch's leaves (native LZ, "
        "incompressible rides raw), the uploader thread decodes before "
        "device_put. Shrinks the modeled feeder→trainer staging leg "
        "(disclosed under e2e_wire.staging_leg), NOT the host→device "
        "bytes — default off since the decode costs serial "
        "uploader-thread time for zero link-byte gain",
    )
    ap.add_argument("--parse-threads", type=int, default=4)
    ap.add_argument("--parity-steps", type=int, default=24)
    ap.add_argument(
        "--steps-per-launch",
        type=int,
        default=8,
        help="minibatches scanned per device launch (ELLBitsSuperBatch); "
        "amortizes the per-launch dispatch",
    )
    ap.add_argument(
        "--wire-encode",
        default="exact",
        choices=("", "exact", "int8", "u16", "bf16"),
        help="compact host→device wire for the headline e2e path "
        "(learner/wire.py): DEFAULT 'exact' — sparse update + encoded "
        "batches + the upload key cache, so the e2e stream stops "
        "paying the raw bits wire's 107.4 B/ex. '' restores the "
        "legacy bits-wire config; "
        "quantized-pull runs (--pull-bytes) keep bits regardless "
        "(sparse composes with unfiltered pulls only)",
    )
    ap.add_argument(
        "--wire-cache-mb",
        type=int,
        default=64,
        help="upload key-cache budget (MB of retained host copies) for "
        "the encoded-wire e2e stream; 0 disables",
    )
    ap.add_argument(
        "--pull-bytes",
        type=int,
        default=0,
        choices=(0, 1, 2),
        help="FIXING_FLOAT pull filter width: servers send n-byte "
        "quantized weights (the reference's production criteo pull, "
        "example/linear/ctr/online_l1lr.conf). The step dequantizes "
        "shard-wide then gathers f32 (pull_gather auto => wide). "
        "Metric name gains a "
        "_qN suffix so captures pool separately from the exact-pull "
        "headline",
    )
    ap.add_argument(
        "--profile",
        default=None,
        metavar="DIR",
        help="capture a jax.profiler device trace of one serialized "
        "launch into DIR (utils/profiling.device_trace; view in "
        "TensorBoard/Perfetto). DIR/plugins from any previous capture "
        "is removed first so the summary reflects this run only",
    )
    ap.add_argument(
        "--expose-port",
        type=int,
        default=None,
        metavar="PORT",
        help="serve the cluster metrics plane while the bench runs "
        "(telemetry/exposition.py): /metrics = node-labeled Prometheus "
        "aggregate, /healthz = heartbeat+recovery verdict (503 on a "
        "dead/stale shard), /debug/snapshot = registry+alerts+timeline "
        "JSON; default SLO alert rules from configs/alerts/default.json "
        "evaluate live. 0 binds an ephemeral port (printed to stderr); "
        "the record gains an 'expose' section with the scrape summary",
    )
    args = ap.parse_args()
    if args.smoke:
        args.minibatch, args.steps, args.warmup = 1024, 10, 2
        args.num_slots = 1 << 16
        args.real_mb = min(args.real_mb, 8)
    args.metric = (
        "criteo_real_examples_per_sec"
        if args.real
        else "criteo_sparse_lr_examples_per_sec"
    ) + (f"_q{args.pull_bytes}" if args.pull_bytes else "")
    # a rate comes from the chip: `--smoke` is the CPU correctness pass
    # (toy sizes, its numbers are not measurements) and everything else
    # refuses to run off a TPU. jax picks the platform; this script
    # sets none.
    device = device_identity()
    if not args.smoke and device["platform"] != "tpu":
        raise SystemExit(
            f"bench.py measures on a TPU and jax.devices() reports "
            f"{device}; run `python bench.py --smoke` for the CPU "
            "correctness pass"
        )
    if args.real:
        return run_real(args)
    return run_synthetic(args)


def run_synthetic(args) -> int:
    import jax

    from parameter_server_tpu.apps.linear.async_sgd import AsyncSGDWorker
    from parameter_server_tpu.apps.linear.config import (
        Config,
        LearningRateConfig,
        PenaltyConfig,
        SGDConfig,
    )
    from parameter_server_tpu.parallel import mesh as meshlib
    from parameter_server_tpu.system.postoffice import Postoffice
    from parameter_server_tpu.utils.sparse import random_sparse

    Postoffice.reset()
    po = Postoffice.instance().start()  # all local devices, 1 server axis
    trace_path = ensure_trace_sink()
    # HBM/live-buffer gauges refresh on every snapshot/scrape from here
    # on (telemetry/device.py collector; feeds the record's device.hbm
    # section and the ps_device_hbm_* families on /metrics)
    from parameter_server_tpu.telemetry.device import install_hbm_monitor

    install_hbm_monitor()
    _maybe_expose(po, args)
    n_workers = meshlib.num_workers(po.mesh)

    conf = Config()
    conf.penalty = PenaltyConfig(type="l1", lambda_=[1.0])
    conf.learning_rate = LearningRateConfig(type="decay", alpha=0.1, beta=1.0)
    # THE WIRE FLIP (ROADMAP item 1): the headline e2e path rides the
    # compact encoded wire by default — sparse update + wire_encode +
    # the upload key cache — so the record's e2e bytes/example reflects
    # the PR-5 codec instead of the raw 107.4 B/ex bits wire the
    # breakdown kept quoting. Sparse mode is the exact-wire scan-fusion
    # gate (ADVICE r5) and composes with UNFILTERED pulls only, so a
    # quantized-pull run (--pull-bytes, the _qN metric) keeps the
    # legacy bits-wire config — disclosed in the record either way.
    encoded = bool(args.wire_encode) and not args.pull_bytes
    conf.async_sgd = SGDConfig(
        algo="ftrl",
        minibatch=args.minibatch,
        num_slots=args.num_slots,
        # sparse ministeps run on the live state (staleness 0, within
        # any delay bound); the bits path keeps the reference criteo
        # conf's bounded delay
        max_delay=0 if encoded else 4,
        ell_lanes=args.nnz_per_row,
        # legacy minimal wire: 22-bit slot stream + 1-bit labels, fused
        # C++ hash→pack (the --pull-bytes / --no-encoded-wire path)
        wire="" if encoded else "bits",
        update="sparse" if encoded else "auto",
        wire_encode=args.wire_encode if encoded else "",
        wire_cache_mb=args.wire_cache_mb if encoded else 0,
        pull_filter=(
            [{"type": "fixing_float", "num_bytes": args.pull_bytes}]
            if args.pull_bytes else []
        ),
    )
    worker = AsyncSGDWorker(conf, mesh=po.mesh)

    p_space = 1 << 24  # raw key universe (hashed into num_slots)

    def gen(i: int):
        b = random_sparse(
            args.minibatch, p_space, args.nnz_per_row, seed=i, binary=True
        )
        # cheap synthetic labels keyed off low-id features for signal
        b.y = np.where(
            (b.indices.reshape(args.minibatch, -1) % 1024 < 256).mean(1) > 0.24,
            1.0,
            -1.0,
        ).astype(np.float32)
        return b

    # pre-generate raw batches (parsing is benchmarked separately — the
    # --real mode streams actual criteo text with parsing in the loop);
    # LOCALIZATION (hash→slot + bit packing), superbatch stacking and the
    # device upload all run inside the timed loop — the honest host cost.
    T = max(1, args.steps_per_launch)
    raw = [gen(i) for i in range(min(args.steps + args.warmup, 32))]
    worker._padding(raw[0])

    wire_counter = {"bytes": 0}

    def prep_upload_submit(i: int):
        # with_aux=False: skip the per-example AUC outputs in the hot loop
        parts = [
            worker.prep(raw[(i + j) % len(raw)], device_put=False)
            for j in range(T)
        ]
        sb = stack_supersteps(parts, T)
        nb = tree_host_nbytes(sb)
        wire_counter["bytes"] += nb  # actual staged bytes, not a model
        return worker._submit_prepped(jax.device_put(sb), with_aux=False)

    # warmup (compile)
    pending = []
    for i in range(max(1, args.warmup // T)):
        pending.append(prep_upload_submit(i * T))
    for ts in pending:
        worker.executor.wait(ts)
    flush(worker)
    # compile the delayed-step program too (see run_real's warmup note):
    # with T < max_delay the snapshot counter decides mid-stream which
    # jitted variant runs, and the timed windows must never pay a
    # compile. The encoded-wire config needs no second warmup: max_delay
    # is 0 there, so EVERY launch snapshots+donates — the one variant
    # the warmup submits above already compiled.
    prep_parts = [
        worker.prep(raw[j % len(raw)], device_put=False) for j in range(T)
    ]
    if not encoded:
        warm_host = stack_supersteps(prep_parts, T)
        warm_sb = jax.device_put(warm_host)
        del warm_host
        step_fn = worker._get_step(warm_sb, False)
        live_copy = jax.tree.map(lambda x: x.copy(), worker.state)
        pull_copy = jax.tree.map(lambda x: x.copy(), worker.state)
        jax.block_until_ready(
            step_fn(live_copy, pull_copy, warm_sb, np.uint32(0))[1]["num_ex"]
        )
        del live_copy, pull_copy, warm_sb

    headline = headline_phase(
        worker, prep_parts,
        T, args.minibatch, args.smoke, args.num_slots,
        note="value = device-only rate (pre-staged batches; best scan "
        "depth of the disclosed sweep); "
        "e2e_median_window = prep+upload+step",
    )
    # serialized stage pricing (+ optional device trace): which of
    # prep/upload/device bounds the pipeline below. Guarded like
    # device_only_sweep: a transient failure in these EXTRA launches
    # must not cost the e2e phase.
    try:
        headline.update(phase_breakdown(
            worker,
            lambda i: [
                worker.prep(raw[(i * T + j) % len(raw)], device_put=False)
                for j in range(T)
            ],
            T,
            profile_dir=args.profile,
        ))
    except Exception as e:
        headline["breakdown_error"] = f"{type(e).__name__}: {str(e)[:200]}"
    # zero-copy data-plane A/B rides along in the record (donated vs
    # copying push, fused vs sequenced round trip) + ticks the kvops
    # telemetry counters for the snapshot
    attach_kv_dataplane(headline, po.mesh, args.smoke)
    # host-ingest serial-vs-pipelined A/B rides along too (PR3): the
    # ingest plane is the post-zero-copy bottleneck this record tracks
    attach_host_ingest(headline, args.smoke)
    attach_wire(headline, args.smoke)
    # sparse-FTRL update A/B rides along (ROADMAP item 4): XLA rows
    # path vs the fused Pallas kernel, with the on-chip frac-of-peak
    # target stated in the record schema
    attach_ftrl(headline, args.smoke)
    # serving-plane SLO bench rides along (open-loop p50/p99 + the
    # admission/coalescing evidence, doc/SERVING.md)
    attach_serve(headline, args.smoke)
    # continuous-batching decode A/B rides along (batched-vs-sequential
    # tokens/s under churn + the device-replica-over-budget gate,
    # doc/SERVING.md "Continuous batching")
    attach_decode_batching(headline, args.smoke)
    # chaos-plane recovery drill rides along (kill-one-shard MTTR +
    # bit-parity + degraded/shed accounting, doc/ROBUSTNESS.md)
    attach_recovery(headline, args.smoke)
    # flight-recorder overhead A/B + ring state (doc/OBSERVABILITY.md
    # "Flight recorder & diagnostic bundles")
    attach_blackbox(headline, args.smoke)
    # history-plane fold-hook overhead A/B + the live store snapshot
    # (doc/OBSERVABILITY.md "History plane")
    attach_history(headline, args.smoke)
    # learning truth plane (staleness vs τ, heat/shard balance,
    # convergence trajectory, divergence drill) — last among the
    # component sections; see attach_learning's harvest-order note
    attach_learning(headline, args.smoke)
    # self-driving consistency A/B (adaptive τ + KKT filter + rollback
    # drill) — Postoffice-resetting, rides with learning at the tail
    attach_consistency(headline, args.smoke)
    # disclose which wire the e2e stream actually rode
    headline["e2e_wire"] = {
        "wire_encode": conf.async_sgd.wire_encode or conf.async_sgd.wire,
        "update": conf.async_sgd.update,
        "wire_cache_mb": conf.async_sgd.wire_cache_mb,
        "max_delay": conf.async_sgd.max_delay,
    }

    # A single long average is hostage to one slow stretch (a noisy
    # host). Time fixed-size windows — each FLUSHED (scalar fetched, so
    # the device really finished) before its clock stops — and report the
    # MEDIAN window rate: robust to transient throttling in either
    # direction and not biased upward the way best-of-K would be. best/avg
    # are disclosed alongside.
    n_launches = max(1, args.steps // T)
    # each window flush pays a device round trip and drains the pipeline;
    # keep windows >= 5 launches so the flush cost stays amortized
    window = max(5, n_launches // 5) if n_launches >= 5 else n_launches
    def host_parts():
        for i in range(n_launches * T):
            with telemetry_spans.span("bench.prep", phase="e2e"):
                part = worker.prep(raw[i % len(raw)], device_put=False)
            yield part

    # upload key cache on the e2e stream (stateful → single-owner: it
    # lives on the UploadPipeline's one staging thread). The synthetic
    # stream CYCLES a fixed batch pool, so repeated key/column arrays
    # re-use their device buffers — the cross-batch half of the wire
    # win, with shipped bytes accounted net of cache hits
    cache = None
    if encoded and conf.async_sgd.wire_cache_mb > 0:
        from parameter_server_tpu.learner.wire import UploadCache

        cache = UploadCache(max_bytes=conf.async_sgd.wire_cache_mb << 20)
    rates = []
    drift_samples = []  # (elapsed_s, window examples/sec) for live_drift
    done = 0
    wire_counter["bytes"] = 0  # count the TIMED phase only (not warmup)
    # warmup mark for the device inventory (see run_real): the timed
    # windows below must trigger zero new compiles
    from parameter_server_tpu.telemetry import device as _device_mod

    _device_mod.mark_warmup()
    e2e_wall0 = time.time()
    t0 = time.perf_counter()
    pending = []
    win_done, win_t0 = 0, t0
    # uploader thread overlaps localize/pack + the host→device wire with the
    # device steps the main thread is waiting on (see UploadPipeline)
    for dev_sb, _n_ex, nb, fid in UploadPipeline(host_parts(), T, cache=cache):
        wire_counter["bytes"] += nb
        done += 1
        win_done += 1
        # the wait below may pay the staged transfer's wire time
        with telemetry_spans.flow_scope(fid):
            pending.append(worker._submit_prepped(dev_sb, with_aux=False))
        if len(pending) > 2:
            worker.executor.wait(pending.pop(0))
        if win_done >= window:
            while pending:
                worker.executor.wait(pending.pop(0))
            flush(worker)
            now = time.perf_counter()
            rates.append(win_done * T * args.minibatch / (now - win_t0))
            drift_samples.append((now - t0, rates[-1]))
            win_done, win_t0 = 0, now
    for ts in pending:
        worker.executor.wait(ts)
    flush(worker)
    dt = time.perf_counter() - t0
    e2e_wall1 = time.time()
    done *= T

    avg_rate = done * args.minibatch / dt
    e2e_rate = float(np.median(rates)) if rates else avg_rate

    rec = {
        "metric": args.metric,
        "unit": "examples/sec",
        "e2e_median_window": round(e2e_rate, 1),
        "e2e_vs_baseline": round(e2e_rate / REF_8NODE_EXAMPLES_PER_SEC, 3),
        "avg": round(avg_rate, 1),
        "best": round(max(rates), 1) if rates else None,
    }
    rec.update(headline)
    if cache is not None:
        rec["e2e_upload_cache"] = {
            "hits": cache.hits,
            "misses": cache.misses,
            "saved_mb": round(cache.saved_bytes / 1e6, 1),
        }
    reconcile_link_ceiling(
        rec, wire_counter["bytes"], done * args.minibatch, dt
    )
    # the run worker's OWN learning plane, harvested after the timed
    # windows so its staleness/trajectory view covers the e2e phase
    attach_learning_run(rec, worker)
    # live steady-state drift: the run's tail windows vs its own
    # post-warmup baseline (doc/OBSERVABILITY.md "History plane")
    attach_history_drift(rec, drift_samples)
    # device truth plane AFTER the timed windows (post-warmup
    # recompiles cover the phase that must not re-specialize)
    attach_device(rec, args.smoke)
    attach_attribution(rec, trace_path, (e2e_wall0, e2e_wall1))
    _finish(rec)
    return 0


if __name__ == "__main__":
    sys.exit(main())

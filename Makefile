# Top-level build (role of the reference's make/ directory)

.PHONY: all native native-test test bench chip-smoke smoke lint pslint metrics-lint donation-lint mesh-test ingest-bench wire-bench stream-prep-bench serve-bench decode-bench ftrl-bench chaos-bench rebalance-bench learning-bench consistency-bench history-bench roofline trace bundle bench-diff metrics-serve clean

all: native

# the loader builds the host library on first use (named after its
# source, flags and this host's CPU) and raises with the compiler's
# output when it cannot; this target only does so ahead of time
native:
	python -c "from parameter_server_tpu.cpp import native; native()"

# native-vs-Python parity of the fused-prep and codec paths
native-test: native
	env JAX_PLATFORMS=cpu python -m pytest \
		tests/test_wire.py -k "stream or native or staging" \
		-q -p no:cacheprovider
	env JAX_PLATFORMS=cpu python -m pytest \
		tests/test_codec.py -q -p no:cacheprovider

test: native
	python -m pytest tests/ -x -q

bench: native
	python bench.py

# the quickest proof that the system still starts on the chip: the
# three CLIs end to end on a TPU (fails off the chip; `--rehearsal`
# walks the same control flow at toy shapes on the CPU)
chip-smoke:
	python chip_smoke.py

smoke: native
	env JAX_PLATFORMS=cpu python bench.py --smoke

# the full static-analysis suite (script/pslint/, doc/STATIC_ANALYSIS.md):
# lock-discipline race detector (+ lock-order deadlock cycles),
# thread-lifecycle, jit-purity, donation, metrics, spans, plus the v2
# interprocedural passes — use-after-donate dataflow, thread-affinity,
# determinism, cross-artifact consistency — one engine, one findings
# report (`path:line rule message`, editor-clickable), exit 1 on any
# unsuppressed finding. --timings prints per-pass wall-clock and cache
# hit counts; --budget fails the target (exit 2) if the suite drifts
# past its stated wall-clock (cold run is ~7s; per-file passes cache
# by content hash in .pslint-cache.json, gitignored). Fast, no
# accelerator; also a tier-1 test in tests/test_pslint.py.
pslint:
	python script/pslint/cli.py --timings --budget 60

# the multi-device partitioning suite on a FORCED 8-device CPU
# platform: partitioner spec resolution, mesh auto-shaping (8 -> 4x2,
# never 3x2-with-2-idle), the sharded-table parity tests, and the
# live-rebalance / migration drills — multi-chip paths exercised on
# every dev box, not only when silicon appears (tier-1: the same
# tests run under tests/ via conftest's forced device count)
mesh-test:
	env JAX_PLATFORMS=cpu XLA_FLAGS="--xla_force_host_platform_device_count=8" \
		python -m pytest tests/test_partition.py tests/test_rebalance.py \
		-q -p no:cacheprovider

# all static checks + the multi-device partitioning suite (mesh-test
# rides along so layout changes can't pass lint while breaking the
# 8-device paths)
lint: pslint mesh-test

# alias: the telemetry-catalog pass alone (duplicate / non-snake_case
# names, naming drift, unparseable exposition; also a tier-1 test in
# tests/test_telemetry.py)
metrics-lint:
	python script/pslint/cli.py --rules metrics

# alias: the donation pass alone — every data-plane jit site either
# donates its table buffers or justifies not doing so (# no-donate:),
# the defensive-copy trap guard (also a tier-1 test in
# tests/test_donation.py)
donation-lint:
	python script/pslint/cli.py --rules donation

# serial-vs-pipelined host-ingest A/B (components bench): one JSON
# summary line per metric — serial/pipelined examples/sec + the median
# paired speedup (fast, CPU-only, no accelerator; the same A/B is
# embedded in every bench.py record under "host_ingest")
ingest-bench: native
	env JAX_PLATFORMS=cpu python -m parameter_server_tpu.benchmarks host_ingest

# compact-wire encoded-vs-raw A/B (components bench): bytes/example
# per encoding at the headline shape, multi-pass amortized bytes
# through the upload key cache, exact-mode parity, encode cost (fast,
# CPU-only; the same A/B is embedded in every bench.py record under
# "wire" with per-encoding link-bound ceilings)
wire-bench: native
	env JAX_PLATFORMS=cpu python -m parameter_server_tpu.benchmarks wire

# native-vs-Python fused stream-prep A/B (components bench): the one
# C ABI call (hash→per-lane unique→remap→bit-pack) against the NumPy
# passes it replaces — byte-identical output asserted, median paired
# speedup disclosed (also embedded in wire_ab under "fused_prep")
stream-prep-bench: native
	env JAX_PLATFORMS=cpu python -m parameter_server_tpu.benchmarks stream_prep

# FTRL update-path benches (components): the sparse-touched XLA-rows
# vs fused-Pallas-kernel A/B (embedded in every bench.py record under
# "ftrl_sparse", with hbm_gb_s / frac-of-peak and the on-chip 10x
# target), and the dense-formulation 8-update chain A/B whose
# ftrl_dense_*_chain_* captures re-judge ops/ftrl.xla_min_slots.
# CPU-runnable (fused arm falls back — shape truth, not a headline).
ftrl-bench: native
	env JAX_PLATFORMS=cpu python -m parameter_server_tpu.benchmarks ftrl_sparse_ab
	env JAX_PLATFORMS=cpu python -m parameter_server_tpu.benchmarks ftrl_chain

# request-path serving SLO bench (components bench): open-loop Poisson
# load against the serving frontend — p50/p99/p99.9 at >=2 offered-load
# points, admission on/off A/B (bounded p99 under overload vs queue
# collapse), coalescing merge factor, speculative-decode lane (fast,
# CPU-runnable, self-calibrating rates; the same dict is embedded in
# every bench.py record under "serve")
serve-bench: native
	env JAX_PLATFORMS=cpu python -m parameter_server_tpu.benchmarks serve

# continuous-batching decode A/B (components bench, doc/SERVING.md
# "Continuous batching"): batched vs sequential speculative decode
# tokens/s at each slot count under join/leave churn — wave admission +
# fused round blocks, token parity asserted in-bench, plus the
# device-resident replica serving a table over the host budget with
# zero degrades (the same dict is embedded in every bench.py record
# under "decode_batching")
decode-bench: native
	env JAX_PLATFORMS=cpu python -m parameter_server_tpu.benchmarks decode_batching

# chaos-plane recovery drill (components bench, doc/ROBUSTNESS.md):
# kill a server shard via injected heartbeat silence under concurrent
# train+serve load — detection/recovery/MTTR, requests
# degraded/shed/failed, replayed-update count, and the post-recovery
# trajectory bit-parity verdict vs an undisturbed run (fast,
# CPU-runnable, deterministic under the drill seed; the same dict is
# embedded in every bench.py record under "recovery")
chaos-bench: native
	env JAX_PLATFORMS=cpu python -m parameter_server_tpu.benchmarks recovery_drill

# heat-driven live-repartitioning drill (components bench,
# doc/PERFORMANCE.md "Declarative partitioning"): a heat-skewed
# workload drives the shipped shard_imbalance alert to firing, the
# RebalanceController recomputes slot ownership from the measured
# hot-slot/load-share tables and migrates rows online through the
# consistent-snapshot machinery — serve stream completes every request
# across the move, post-rebalance imbalance re-measured below the
# alert threshold, post-migration table bit-identical to an
# undisturbed run (8 forced CPU devices, deterministic)
rebalance-bench:
	env JAX_PLATFORMS=cpu XLA_FLAGS="--xla_force_host_platform_device_count=8" \
		python -m parameter_server_tpu.benchmarks rebalance

# learning truth plane probe (components bench, doc/OBSERVABILITY.md
# "Learning truth plane"): a bounded-delay training run through the
# collect path — realized staleness vs the configured τ (asserted),
# sketch-vs-exact key-heat parity, per-shard load shares + imbalance,
# the loss/grad-norm trajectory from the in-jit side outputs, and the
# seeded LR-blow-up divergence drill (shipped loss_divergence rule to
# firing with a diagnostic bundle attached). Fast, CPU-only; the same
# dict is embedded in every bench.py record under "learning"
learning-bench:
	env JAX_PLATFORMS=cpu python -m parameter_server_tpu.benchmarks learning

# self-driving consistency A/B (components bench, doc/PERFORMANCE.md
# "Consistency–throughput frontier"): fixed τ=0 vs fixed τ=max vs the
# adaptive controller on one planted-regression workload (paired-rep
# medians, emulated pull RTT disclosed in-record), the KKT-style
# significance filter off/on with its suppression accounting
# reconciled against ps_push_keys_total, and the seeded divergence
# drill through the controller's LR-backoff + snapshot-rollback
# reaction (episode captured in one flight-recorder bundle). Full
# record lands at $PS_CONSISTENCY_OUT (default /tmp/ps_consistency.json)
consistency-bench:
	env JAX_PLATFORMS=cpu XLA_FLAGS="--xla_force_host_platform_device_count=8" \
		python -m parameter_server_tpu.benchmarks consistency

# history plane overhead probe (components bench, doc/OBSERVABILITY.md
# "History plane"): the multi-resolution ring-cascade fold hook priced
# against the identical metric-churn workload without it — paired
# back-to-back reps (on, off, off, on), MEDIAN ratio quoted, plus the
# tight-loop per-fold cost over the full instrument catalog. The same
# dict is embedded in every bench.py record under "history"
history-bench:
	env JAX_PLATFORMS=cpu python -m parameter_server_tpu.benchmarks history_ab

# device truth plane probe (components bench, doc/OBSERVABILITY.md
# "Device truth plane"): an HBM-bound FTRL chain + a FLOPs-bound flash
# fwd through instrumented wrappers with per-dispatch roofline
# sampling — achieved GB/s / GFLOP/s per kernel against the XLA cost
# analysis, frac-of-peak where the peak tables know the chip, and the
# zero-steady-state-recompile sanity (fast, CPU-runnable; the full
# per-jit inventory is embedded in every bench.py record under
# "device")
roofline:
	env JAX_PLATFORMS=cpu python -m parameter_server_tpu.benchmarks roofline

# capture a short synthetic run's flow-correlated timeline and export
# it as Chrome trace / Perfetto JSON (open at https://ui.perfetto.dev;
# doc/OBSERVABILITY.md "Reading a timeline"). Override the output with
# PS_TRACE_OUT=/path.json; the raw JSONL span stream lands next to it
trace:
	env JAX_PLATFORMS=cpu PS_TRACE_OUT=$${PS_TRACE_OUT:-/tmp/ps_timeline_trace.json} \
		python -m parameter_server_tpu.benchmarks trace
	@echo "timeline: $${PS_TRACE_OUT:-/tmp/ps_timeline_trace.json} (open at https://ui.perfetto.dev)"

# capture a diagnostic bundle from a live mini-cluster
# (doc/OBSERVABILITY.md "Flight recorder & diagnostic bundles"): the
# flight-recorder rings of every node (one deliberately silent ->
# marked stale), metrics snapshot, alert states, executor state, and a
# Perfetto-ready trace — the same artifact an alert firing, a
# DegradedError, a shard death, or a wedged executor wait auto-captures,
# and what /debug/bundle serves live. Override the output with
# PS_BUNDLE_OUT=/path.json
bundle:
	env JAX_PLATFORMS=cpu PS_BUNDLE_OUT=$${PS_BUNDLE_OUT:-/tmp/ps_bundle.json} \
		python -m parameter_server_tpu.benchmarks bundle
	@echo "bundle: $${PS_BUNDLE_OUT:-/tmp/ps_bundle.json} (open its 'trace' member at https://ui.perfetto.dev)"

# cluster metrics plane demo (doc/OBSERVABILITY.md "Cluster metrics
# plane"): a tiny live system on the CPU mesh with the full plane up —
# scrape http://127.0.0.1:$(METRICS_PORT)/metrics (also /healthz,
# /debug/snapshot) while it trains; default SLO alert rules from
# configs/alerts/default.json evaluate live. Ctrl-C stops it cleanly.
# The same endpoint rides any real run via `python bench.py
# --expose-port 9100` or `apps/serve ... --expose-port 9100`.
METRICS_PORT ?= 9100
metrics-serve:
	env JAX_PLATFORMS=cpu python -m parameter_server_tpu.telemetry.exposition --port $(METRICS_PORT)

# bench regression sentinel: compare the newest valid BENCH_r*.json
# against the prior trajectory (median-of-priors baseline, tolerance
# band from the trajectory's own spread — ROADMAP bench discipline);
# exit 1 on an out-of-band throughput regression (tier-1 tested
# against fixture records in tests/data/bench_diff/)
bench-diff:
	python script/bench_diff.py

clean:
	$(MAKE) -C parameter_server_tpu/cpp clean
	find . -name __pycache__ -type d -exec rm -rf {} +

# Top-level build (role of the reference's make/ directory)

.PHONY: all native native-test test chip-smoke smoke lint pslint metrics-lint donation-lint mesh-test metrics-serve clean

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

# the quickest proof that the system still starts on the chip: the
# three CLIs end to end on a TPU (fails off the chip; `--rehearsal`
# walks the same control flow at toy shapes on the CPU)
chip-smoke:
	python chip_smoke.py

# the measured path on the CPU: the benchmark's known answers, then
# every cell of BENCHMARK.json rehearsed at toy sizes through the
# harness the driver runs. Checks results and counts; a speed comes
# from the driver's run of BENCHMARK.json's command on the chip.
smoke: native
	python -m chipbench.selfcheck
	python3 chipbench/run.py --workload criteo_bigtable.text --seed 2147483693 --seconds 2 --trace 0 --rehearsal
	python3 chipbench/run.py --workload criteo_dense.text --seed 2147483693 --seconds 2 --trace 0 --rehearsal
	python3 chipbench/run.py --workload mistral_small4_ep16.packed8k --seed 3000000019 --seconds 2 --trace 0 --rehearsal
	python3 chipbench/run.py --workload solar_open2_ep40.packed8k_mb1 --seed 3000000019 --seconds 2 --trace 0 --rehearsal
	python3 chipbench/run.py --workload mellum2_ep4.packed8k_mb1 --seed 3000000019 --seconds 2 --trace 0 --rehearsal

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

# cluster metrics plane demo (doc/OBSERVABILITY.md "Cluster metrics
# plane"): a tiny live system on the CPU mesh with the full plane up —
# scrape http://127.0.0.1:$(METRICS_PORT)/metrics (also /healthz,
# /debug/snapshot) while it trains; default SLO alert rules from
# configs/alerts/default.json evaluate live. Ctrl-C stops it cleanly.
# The same endpoint rides a serving run via `apps/serve ...
# --expose-port 9100`.
METRICS_PORT ?= 9100
metrics-serve:
	env JAX_PLATFORMS=cpu python -m parameter_server_tpu.telemetry.exposition --port $(METRICS_PORT)

clean:
	$(MAKE) -C parameter_server_tpu/cpp clean
	find . -name __pycache__ -type d -exec rm -rf {} +

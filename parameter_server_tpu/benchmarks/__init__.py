"""Per-component performance tests.

Counterpart of the reference's perf binaries in ``src/test/``:
``kv_vector_perf_ps.cc``, ``kv_map_perf_ps.cc``, ``kv_layer_perf_ps.cc``,
``network_perf_ps.cc``, ``sparse_matrix_perf.cc``. Each module times one
subsystem on the live backend (the real chip, or a virtual CPU mesh under
``JAX_PLATFORMS=cpu``) and prints one JSON line per metric:
``{"metric": ..., "value": ..., "unit": ...}``.

Run all:    python -m parameter_server_tpu.benchmarks [--smoke]
Run one:    python -m parameter_server_tpu.benchmarks kv_vector [--smoke]
"""

from __future__ import annotations

import json
import time
from typing import Callable, Dict

REGISTRY: Dict[str, Callable[[bool], None]] = {}

#: The one peaks table, keyed by ``jax.devices()[0].device_kind`` (a
#: TPU v5e reports "TPU v5 lite"). Sources: Google Cloud TPU
#: documentation, "System architecture" pages per generation ("TPU
#: v5e": 197 TFLOP/s bf16, 393 TOP/s int8, 819 GB/s HBM). On a chip a
#: kind missing here is an error (:func:`device_peaks`); a CPU host
#: resolves to None and every frac-of-peak field is then null, never
#: faked.
#:
#: chip HBM peak bandwidth (GB/s) — the roofline denominator for every
#: frac-of-peak field (bench.py roofline_fields,
#: components.ftrl_sparse_ab/ftrl_chain)
HBM_PEAK_GB_S = {
    "TPU v4": 1228.0,
    "TPU v5 lite": 819.0,
    "TPU v5e": 819.0,
    "TPU v5": 2765.0,
    "TPU v5p": 2765.0,
    "TPU v6 lite": 1640.0,
    "TPU v6e": 1640.0,
}

#: chip bf16 matmul peak (TFLOP/s) — the utilization denominator for
#: every flops roofline frac (telemetry/device.py roofline gauges, the
#: bench record's ``device`` section)
FLOPS_PEAK_TFLOPS = {
    "TPU v4": 275.0,
    "TPU v5 lite": 197.0,
    "TPU v5e": 197.0,
    "TPU v5": 459.0,
    "TPU v5p": 459.0,
    "TPU v6 lite": 918.0,
    "TPU v6e": 918.0,
}


def device_identity() -> dict:
    """The device every result names, as jax reports it (initializes
    the backend)."""
    import jax

    devs = jax.devices()
    return {
        "platform": devs[0].platform,
        "kind": devs[0].device_kind,
        "count": len(devs),
    }


def device_peaks(device_kind: str) -> dict:
    """``{"hbm_gb_s", "bf16_tflops"}`` of a chip; raises for a kind the
    table does not list (a chip measured against no peak would report
    no roofline share and nobody would notice)."""
    if device_kind not in HBM_PEAK_GB_S or device_kind not in FLOPS_PEAK_TFLOPS:
        raise KeyError(
            f"device_kind {device_kind!r} is not in the peaks table "
            f"(parameter_server_tpu/benchmarks/__init__.py); add it with "
            "its source before measuring on it"
        )
    return {
        "hbm_gb_s": HBM_PEAK_GB_S[device_kind],
        "bf16_tflops": FLOPS_PEAK_TFLOPS[device_kind],
    }


def benchmark(name: str):
    def deco(fn):
        REGISTRY[name] = fn
        return fn

    return deco


def report(metric: str, value: float, unit: str) -> None:
    print(json.dumps({"metric": metric, "value": round(value, 2), "unit": unit}), flush=True)


def timeit(fn, n: int, warmup: int = 3, budget_s: float = 90.0) -> float:
    """Median of up to 3 windows of up to n calls; returns seconds/call.

    A wall-clock budget bounds the whole measurement, so a slow path
    (a kv push costing seconds) cannot run the un-budgeted 3+3x10 call
    schedule. Fast paths still get the full median-of-3.
    """
    t_start = time.perf_counter()
    fn()  # always warm at least once (compile/transfer caches)
    # estimate per-call cost from a SECOND, post-compile call: the first
    # includes jit compilation, which would collapse n_eff to 1 for
    # every jitted fast path
    t1 = time.perf_counter()
    fn()
    per = max(time.perf_counter() - t1, 1e-9)
    for _ in range(warmup - 2):
        if time.perf_counter() - t_start > budget_s / 4:
            break
        fn()
    n_eff = max(1, min(n, int(budget_s / (3 * per)) or 1))
    times = []
    t_meas = time.perf_counter()
    for _ in range(3):
        w0 = time.perf_counter()
        for _ in range(n_eff):
            fn()
        times.append((time.perf_counter() - w0) / n_eff)
        if time.perf_counter() - t_meas > budget_s:
            break
    # lower median: with 2 windows (budget break) this picks the FASTER
    # one — a stall-spiked window must not become the reported rate
    return sorted(times)[(len(times) - 1) // 2]

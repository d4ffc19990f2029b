#!/usr/bin/env python3
"""The quickest proof that the system still starts on the chip.

    python chip_smoke.py                      # on a TPU: exit 0 = every leg passed
    JAX_PLATFORMS=cpu python chip_smoke.py --rehearsal   # here: exit 3 = walked

One process drives the three command-line entry points a user would
call, on whatever ``jax.devices()`` reports, and checks what comes out:

- **K** each Pallas kernel alone (quantize, dense FTRL f32/bf16, fused
  sparse FTRL, flash forward+backward at d_head 128 and 64) against its
  XLA reference, so that a kernel the compiler refuses costs seconds and
  not a leg; and the row write-back (``ops/rows.py``) at 2^30 slots and
  639,488 rows against the undeclared scatter, every slot bit-equal;
- **A** the Criteo trainer, ``apps.linear.main``: a dense-sweep conf at
  2^26 slots that this script writes, then
  ``configs/criteo/online_l1lr_bigtable.conf`` as committed (2^30 slots),
  then the sequential logloss parity against the NumPy FTRL oracle once
  per update formulation, then a profiler capture of two launches. With
  four or more devices it adds the 2^30 conf on a 1x4 and on a 2x2
  mesh (and leg B at tensor-parallel 2);
- **B** the LM CLI, ``apps.lm.main``, at the widest shape the repository
  claims (``apps/lm/shapes.WIDEST``, 403M parameters, bf16, ring_flash),
  a few optimizer steps, a 64-token KV-cache decode, and decode logits
  against the training forward on the trained weights;
- **C** the serve front end, ``apps.serve.main``, with pulls answered by
  the live table on the device (``--replica off``) and the decode lane
  through the continuous batcher. A plane check at the CLI's own model
  sizes, not a full-width run.

The legs run one after another in THIS process, which holds the chip
(``Postoffice.reset()`` between them); it starts no child process. It
sets no platform. Any exception in any leg ends the run with its
traceback and a non-zero exit; nothing is retried or skipped.

Output: a first JSON line naming the device and the installation, one
JSON line per leg, a summary line that ends with ``"claim": null``, and
on the chip, as the last line, the verdict: ``{"ok": true, "device":
{"platform": "tpu", "kind": "...", "count": 1}}``, those keys and no
others. The seconds a leg reports (``compile_s`` from jax's own
backend-compile events, ``run_s`` the rest of its ``wall_s``: tracing,
host work and the device) say how to budget chip time; they are not a
performance metric. ``recompiles_post_warmup``
counts compiles of a train step beyond its first (a function's first
compile is its warm-up): the inventoried jits in leg A, the CLI's own
step jit in leg B. Leg C reports null there and the compiles per
function instead: its pulls are as wide as the coalescer's key unions,
one executable per width, so the CLI has no warm-up boundary (ROADMAP
Speed 5). With ``donation_fallbacks_total`` and
``dispatch_fallbacks_total``, which every leg reports, it must be 0.

``--rehearsal`` walks the same control flow at toy shapes on the CPU
(kernels in Pallas interpret mode, requested explicitly), marks every
line ``"rehearsal": true``, prints no verdict and exits 3 on success,
never 0. ``--legs`` runs a subset while bringing a leg up; its verdict
is ``"ok": false`` and it exits 4 on success. Generated data, confs,
logs and the capture go under ``--out`` (default
``chiprun_out/chip_smoke``), never into tracked paths; the data is
deleted again once leg A is done.
"""

from __future__ import annotations

import argparse
import contextlib
import functools
import gc
import glob
import json
import math
import os
import shutil
import sys
import time

NO_CHIP_EXIT = 2
REHEARSAL_EXIT = 3
PARTIAL_EXIT = 4

REPO = os.path.dirname(os.path.abspath(__file__))
BIGTABLE_CONF = os.path.join(
    REPO, "configs", "criteo", "online_l1lr_bigtable.conf"
)
ALL_LEGS = ("K", "A26", "A30", "Aparity", "Aprofile", "A1x4", "A2x2", "B",
            "Btp2", "C")
#: legs that shard over a mesh: run where there are four devices or more
FOUR_CHIP_LEGS = {"A1x4", "A2x2", "Btp2"}

#: decode-vs-forward logit tolerance, absolute, by compute dtype. bf16
#: carries 8 mantissa bits (eps 2^-8) through 8 layers of matmuls that
#: the two paths block and order differently (flash kernels against a
#: KV-cache walk): agreement to a few 1e-2 is the format, a cache or
#: mask bug moves logits by whole units.
DECODE_ATOL = {"bfloat16": 0.25, "float32": 2e-3}


def emit(rec: dict) -> None:
    print(json.dumps(rec), flush=True)


def verdict(ok: bool, device: dict) -> dict:
    """The last line of a run on the chip, for whoever reads only that:
    exactly these keys, the device as jax reports it."""
    return {
        "ok": bool(ok),
        "device": {k: device[k] for k in ("platform", "kind", "count")},
    }


def require(ok, what) -> None:
    """A check that survives ``python -O``."""
    if not ok:
        raise AssertionError(what)


class Sizes:
    """The shapes of one run: the real ones, or the rehearsal's toys."""

    def __init__(self, rehearsal: bool):
        r = rehearsal
        self.minibatch = 256 if r else 16384
        self.steps_per_launch = 2 if r else 8
        self.launches = 4
        self.p_cat = 1 << 12 if r else 1 << 20
        self.slots_dense = 1 << 18 if r else 1 << 26
        self.slots_big = 1 << 20 if r else 1 << 30
        self.parity_steps = 4 if r else 24
        self.lm_steps = 4 if r else 8
        self.gen_tokens = 8 if r else 64
        self.serve_duration = 0.3 if r else 1.0

    @property
    def launch_rows(self) -> int:
        return self.minibatch * self.steps_per_launch


class CompileMeter:
    """Sums jax's own compile events and counts persistent-cache
    traffic. ``compile_s`` is backend compile time (what a warm cache
    removes; a cache hit's retrieval counts here too); tracing and
    lowering, whose events nest and would count twice, are kept apart
    as ``trace_lower_s``."""

    _TRACE_LOWER = (
        "/jax/core/compile/jaxpr_trace_duration",
        "/jax/core/compile/jaxpr_to_mlir_module_duration",
    )
    _BACKEND = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax

        self.seconds = 0.0
        self.trace_lower_seconds = 0.0
        self.backend_compiles = 0
        self.by_name = {}  # jitted function name -> backend compiles
        self.cache_hits = 0
        self.cache_requests = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, seconds, fun_name=None, **_):
        if event in self._TRACE_LOWER:
            self.trace_lower_seconds += seconds
        elif event == self._BACKEND:
            self.seconds += seconds
            self.backend_compiles += 1
            self.by_name[fun_name] = self.by_name.get(fun_name, 0) + 1

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1
        elif event == "/jax/compilation_cache/compile_requests_use_cache":
            self.cache_requests += 1

    def mark(self):
        return (self.seconds, self.trace_lower_seconds,
                self.backend_compiles, self.cache_hits, self.cache_requests)

    def since(self, mark) -> dict:
        s, t, n, h, q = mark
        return {
            "compile_s": round(self.seconds - s, 2),
            "trace_lower_s": round(self.trace_lower_seconds - t, 2),
            "backend_compiles": self.backend_compiles - n,
            "persistent_cache_hits": self.cache_hits - h,
            "persistent_cache_requests": self.cache_requests - q,
        }


class Run:
    """State shared by the legs of one run."""

    def __init__(self, args, device: dict):
        self.rehearsal = args.rehearsal
        self.sizes = Sizes(args.rehearsal)
        self.out = os.path.abspath(args.out)
        self.device = device
        self.on_tpu = device["platform"] == "tpu"
        self.meter = CompileMeter()
        self.legs = {}

    def path(self, *parts) -> str:
        p = os.path.join(self.out, *parts)
        os.makedirs(os.path.dirname(p), exist_ok=True)
        return p

    def tag(self, rec: dict) -> dict:
        if self.rehearsal:
            rec["rehearsal"] = True
        return rec


# ---------------------------------------------------------------------------
# start-up
# ---------------------------------------------------------------------------


def start(args) -> Run:
    import jax

    from parameter_server_tpu.telemetry.device import (
        device_identity,
        device_peaks,
    )

    device = device_identity()
    if not args.rehearsal and device["platform"] != "tpu":
        forced = os.environ.get("JAX_PLATFORMS")
        print(
            f"chip_smoke: no TPU: jax.devices() reports platform "
            f"{device['platform']!r}"
            + (f" and JAX_PLATFORMS={forced!r} is set in the environment "
               "(unset it on the chip)" if forced else "")
            + ". Nothing ran. `--rehearsal` walks the control flow on "
            "the CPU.",
            file=sys.stderr,
        )
        raise SystemExit(NO_CHIP_EXIT)

    import jaxlib

    from parameter_server_tpu import cpp
    from parameter_server_tpu.utils import compile_cache

    cache_dir = compile_cache.enable()
    cpp.native()  # builds the host library here, or raises with g++'s output
    try:
        import libtpu

        libtpu_version = getattr(libtpu, "__version__", "unknown")
    except ImportError:
        libtpu_version = None
    run = Run(args, device)
    emit(run.tag({
        "chip_smoke": "start",
        "platform": device["platform"],
        "device_kind": device["kind"],
        "device_count": device["count"],
        # a chip the one peaks table does not know is an error here
        "peaks": device_peaks(device["kind"]) if run.on_tpu else None,
        "jax": jax.__version__,
        "jaxlib": jaxlib.__version__,
        "libtpu": libtpu_version,
        "compile_cache_dir": cache_dir,
        "compile_cache_from_env": bool(
            os.environ.get(compile_cache.ENV_VAR)
        ),
        "compile_cache_entries_at_start": (
            len(os.listdir(cache_dir)) if os.path.isdir(cache_dir) else 0
        ),
        "libpsnative": os.path.basename(cpp.lib_path()),
        "libpsnative_built_and_loaded": True,  # native() raises otherwise
        "legs": list(args.legs),
        "out": run.out,
    }))
    return run


def reset_process_state() -> None:
    """Between legs: drop the system singleton (mesh, registry, planes),
    the device inventory, and every buffer the last leg left behind."""
    from parameter_server_tpu.system.postoffice import Postoffice
    from parameter_server_tpu.telemetry import device as device_tel

    # reset() stops the old instance: its customers' executor threads
    # would otherwise pin the last leg's tables on the device
    Postoffice.reset()
    device_tel.reset()
    gc.collect()


def memory_per_device() -> list:
    import jax

    out = []
    for d in jax.devices():
        ms = d.memory_stats() or {}
        out.append({
            "id": d.id,
            "bytes_in_use": ms.get("bytes_in_use"),
            "peak_bytes_in_use": ms.get("peak_bytes_in_use"),
            "bytes_limit": ms.get("bytes_limit"),
        })
    return out


def device_contracts() -> dict:
    """The three counts every leg must leave at zero, read from the
    device inventory and the live registry."""
    from parameter_server_tpu.telemetry import device as device_tel
    from parameter_server_tpu.telemetry import registry as telreg

    snap = device_tel.snapshot()
    fns = snap["functions"]
    fallbacks = telreg.default_registry().snapshot().get(
        "ps_device_dispatch_fallbacks_total", {}
    ).get("values", {})
    return {
        "inventory_recompiles": sum(f["recompiles"] for f in fns.values()),
        "donation_fallbacks_total": snap["donation_fallbacks_total"],
        # ps_device_dispatch_fallbacks_total and the inventory count the
        # same events; a registry reset mid-leg must not hide them
        "dispatch_fallbacks_total": max(
            int(sum(fallbacks.values())),
            sum(f.get("dispatch_fallbacks", 0) for f in fns.values()),
        ),
        "inventory": {
            name: {
                "compiles": f["compiles"], "calls": f["calls"],
                "custom_calls": f.get("custom_calls", []),
            }
            for name, f in fns.items() if f["calls"]
        },
    }


def run_leg(run: Run, name: str, fn) -> None:
    reset_process_state()
    before = memory_per_device()
    mark = run.meter.mark()
    t0 = time.perf_counter()
    rec = fn(run)
    wall = time.perf_counter() - t0
    rec = {"leg": name, "device": run.device, **rec}
    rec.update(run.meter.since(mark))
    rec["wall_s"] = round(wall, 2)
    rec["run_s"] = round(wall - rec["compile_s"], 2)
    contracts = device_contracts()
    # a leg that names its own warm-up boundary says so; the others
    # count every inventoried compile beyond a function's first
    rec.setdefault(
        "recompiles_post_warmup", contracts["inventory_recompiles"]
    )
    rec.update(contracts)
    for key in ("recompiles_post_warmup", "donation_fallbacks_total",
                "dispatch_fallbacks_total"):
        require(rec[key] in (0, None), f"leg {name}: {key} = {rec[key]}")
    after = memory_per_device()
    rec["memory"] = [
        {**a, "peak_rose_in_leg": (
            a["peak_bytes_in_use"] is not None
            and a["peak_bytes_in_use"] > (b["peak_bytes_in_use"] or 0)
        )}
        for a, b in zip(after, before)
    ]
    rec["pass"] = True
    run.legs[name] = rec
    emit(run.tag(rec))


# ---------------------------------------------------------------------------
# leg K: each kernel alone, against its reference
# ---------------------------------------------------------------------------


def leg_kernels(run: Run) -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from parameter_server_tpu.ops.flash_attention import flash_attention
    from parameter_server_tpu.ops.ftrl import ftrl_update, ftrl_update_ref
    from parameter_server_tpu.ops.ftrl_sparse import (
        ftrl_sparse_rows_ref,
        ftrl_sparse_update,
    )
    from parameter_server_tpu.ops.quantize import quantize

    r = run.rehearsal
    # on the chip the kernels are what the dispatch picks by itself; the
    # rehearsal asks for them, interpreted, by name
    pin = dict(force_pallas=True, interpret=True) if r else {}
    hp = dict(alpha=0.1, beta=1.0, l1=1.0, l2=0.1)
    out = {}
    key = jax.random.PRNGKey(0)

    def has_mosaic(jitted, *a, **kw) -> bool:
        text = jitted.lower(*a, **kw).compile().as_text()
        return "tpu_custom_call" in text

    mark = [run.meter.mark()]

    def compile_s() -> float:
        """Compile seconds since the previous kernel was done."""
        spent = run.meter.since(mark[0])["compile_s"]
        mark[0] = run.meter.mark()
        return spent

    # -- dense FTRL, f32 and bf16 sqrt_n, at the dense leg's shard ------
    p = 1 << 13 if r else run.sizes.slots_dense
    z = jax.random.normal(key, (p,), jnp.float32)
    n32 = jnp.abs(jax.random.normal(jax.random.fold_in(key, 1), (p,)))
    g = jax.random.normal(jax.random.fold_in(key, 2), (p,), jnp.float32)
    g = jnp.where(
        jax.random.uniform(jax.random.fold_in(key, 3), (p,)) < 0.1, g, 0.0
    )
    ref = jax.jit(functools.partial(ftrl_update_ref, **hp))
    for name, n, seed in (
        ("ftrl_dense_f32", n32, None),
        ("ftrl_dense_bf16", n32.astype(jnp.bfloat16), jnp.uint32(7)),
    ):
        z1, n1 = ftrl_update(z, n, g, None, seed=seed, **hp, **pin)
        zr, nr = ref(z, n, g, g != 0, seed=seed)
        dz = float(jnp.max(jnp.abs(z1 - zr)))
        # the bf16 narrow is stochastic (on-core PRNG against the
        # reference's hash dither): one bf16 ulp of the largest value
        dn = float(jnp.max(jnp.abs(
            n1.astype(jnp.float32) - nr.astype(jnp.float32)
        )))
        n_tol = 0.0 if seed is None else float(jnp.max(n32)) * 2.0 ** -7
        out[name] = {"slots": p, "z_maxdiff": dz, "n_maxdiff": dn}
        if run.on_tpu:
            out[name]["mosaic"] = has_mosaic(
                ftrl_update, z, n, g, None, seed=seed, **hp
            )
            require(out[name]["mosaic"], f"{name}: no Mosaic call compiled")
        require(dz <= 1e-6 and dn <= n_tol, (name, out[name]))
        out[name]["compile_s"] = compile_s()

    # -- fused sparse FTRL (f32) at the training unique width ----------
    u = 64 if r else run.sizes.minibatch * 39
    rng = np.random.default_rng(0)
    live = np.unique(rng.integers(0, p - 1, 2 * u))
    live = np.sort(rng.permutation(live)[: u - 8])
    rel = jnp.asarray(
        np.concatenate([live, np.full(u - len(live), p - 1)]).astype(np.int32)
    )
    ok = jnp.asarray(np.arange(u) < len(live))
    g_u = jnp.asarray(rng.normal(size=u).astype(np.float32))
    # the oracle writes its rows back under the order promise (live
    # rows ascending, padding behind them): the kernel, which orders
    # its rows itself, is then also the check of that scatter
    want = jax.jit(
        functools.partial(ftrl_sparse_rows_ref, rows_ascend=True, **hp)
    )(z, n32, rel, ok, g_u)
    sparse_step = jax.jit(
        functools.partial(ftrl_sparse_update, **hp, **pin),
        donate_argnums=(0, 1),
    )
    z_in, n_in = z + 0.0, n32 + 0.0  # the step donates its tables
    if run.on_tpu:
        require(
            has_mosaic(sparse_step, z_in, n_in, rel, ok, g_u),
            "ftrl_sparse_f32: no Mosaic call compiled",
        )
    got = sparse_step(z_in, n_in, rel, ok, g_u)
    out["ftrl_sparse_f32"] = {
        "slots": p, "unique_rows": u,
        "z_maxdiff": float(jnp.max(jnp.abs(got[0] - want[0]))),
        "n_maxdiff": float(jnp.max(jnp.abs(got[1] - want[1]))),
    }
    require(
        max(out["ftrl_sparse_f32"]["z_maxdiff"],
            out["ftrl_sparse_f32"]["n_maxdiff"]) <= 1e-6,
        out["ftrl_sparse_f32"],
    )
    out["ftrl_sparse_f32"]["compile_s"] = compile_s()
    del z, n32, g, z1, n1, zr, nr, want, got, z_in, n_in

    # -- the row write-back at the big table's own shapes --------------
    # ops/rows.py (indices ascending and unique, declared) against the
    # plain scatter built here: every dropped entry at the one index
    # one-past-the-end, nothing declared. Every slot of a 2^30 f32 and
    # of a 2^30 bf16 table bit-equal. What each costs is
    # script/price_row_writeback.py's to say.
    from parameter_server_tpu.ops.rows import write_index, write_rows

    pb = run.sizes.slots_big
    ub = 1024 if r else 639488
    live_b = np.unique(rng.integers(0, pb, 2 * ub, dtype=np.int64))
    live_b = np.sort(rng.permutation(live_b)[: ub - ub // 64])
    rel_b = jnp.asarray(np.concatenate(
        [live_b, np.full(ub - len(live_b), pb - 1)]
    ).astype(np.int32))
    ok_b = jnp.asarray(np.arange(ub) < len(live_b))

    def bits(a):
        return jax.lax.bitcast_convert_type(
            a, jnp.uint16 if a.dtype.itemsize == 2 else jnp.uint32
        )

    declared = jax.jit(
        lambda t, v: write_rows(
            t, write_index(rel_b, ok_b, pb), v, rows_ascend=True
        ),
        donate_argnums=(0,),
    )
    plain = jax.jit(
        lambda t, v: t.at[
            jnp.where(ok_b, rel_b.astype(jnp.uint32), jnp.uint32(pb))
        ].set(v, mode="drop"),
        donate_argnums=(0,),
    )
    for tname, dtype in (("z_f32", jnp.float32), ("sqrt_n_bf16",
                                                  jnp.bfloat16)):
        fill = jax.jit(
            lambda dtype=dtype: (
                jax.lax.iota(jnp.int32, pb) % 977
            ).astype(dtype) / 8
        )
        vals = jnp.asarray(rng.normal(size=ub), dtype)
        t_new = declared(fill(), vals)
        t_old = plain(fill(), vals)
        differ = int(jax.jit(
            lambda a, b: jnp.sum(bits(a) != bits(b))
        )(t_old, t_new))
        del t_old
        written = int(jax.jit(
            lambda a: jnp.sum(bits(a) != bits(fill()))
        )(t_new))
        del t_new
        out[f"row_write_back_{tname}"] = {
            "slots": pb, "rows": ub, "live_rows": len(live_b),
            "slots_that_differ": differ, "slots_written": written,
        }
        # nearly every live row lands on a value that is not the
        # fill's; a write-back that wrote nothing would read 0
        require(
            differ == 0 and len(live_b) * 0.9 < written <= len(live_b),
            out[f"row_write_back_{tname}"],
        )
        out[f"row_write_back_{tname}"]["compile_s"] = compile_s()

    # -- quantize (on-core PRNG; no interpret form) --------------------
    if run.on_tpu:
        x = jax.random.normal(key, (1 << 22,), jnp.float32)
        q, lo, hi = quantize(x, 3, num_bytes=1)
        deq = q.astype(jnp.float32) / 255.0 * (hi - lo) + lo
        step = float((hi - lo) / 255.0)
        out["quantize"] = {
            "err_max": float(jnp.max(jnp.abs(deq - x))), "step": step,
            "mean_bias": float(jnp.mean(deq - x)),
        }
        # stochastic rounding: off by less than one level, unbiased
        require(
            out["quantize"]["err_max"] <= step * 1.001
            and abs(out["quantize"]["mean_bias"]) <= step * 0.01,
            out["quantize"],
        )
        out["quantize"]["compile_s"] = compile_s()

    # -- flash forward + both backward kernels at the LM leg's shape ---
    bh, s = (2, 64) if r else (64, 2048)
    fpin = dict(use_pallas=True, interpret=True) if r else {}
    for d in (128, 64):
        q_, k_, v_ = (
            jax.random.normal(jax.random.fold_in(key, 10 + i), (bh, s, d),
                              jnp.bfloat16)
            for i in range(3)
        )

        def loss(q, k, v, **kw):
            return flash_attention(q, k, v, causal=True, **kw).astype(
                jnp.float32
            ).sum()

        gk = jax.jit(jax.grad(functools.partial(loss, **fpin), (0, 1, 2)))
        gr = jax.jit(
            jax.grad(functools.partial(loss, use_pallas=False), (0, 1, 2))
        )
        grads = gk(q_, k_, v_)
        # the reference materializes [BH, S, S] scores: two heads only
        gsub = gk(q_[:2], k_[:2], v_[:2])
        gref = gr(q_[:2], k_[:2], v_[:2])
        rel_err = max(
            float(jnp.max(jnp.abs(a.astype(jnp.float32)
                                  - b.astype(jnp.float32)))
                  / jnp.max(jnp.abs(b.astype(jnp.float32))))
            for a, b in zip(gsub, gref)
        )
        finite = all(
            bool(jnp.isfinite(a.astype(jnp.float32)).all()) for a in grads
        )
        out[f"flash_d{d}"] = {
            "bh": bh, "seq": s, "grad_rel_err": rel_err, "finite": finite,
        }
        # bf16 gradients: one ulp (2^-8) of the largest entry
        require(finite and rel_err <= 2.0 ** -7, out[f"flash_d{d}"])
        out[f"flash_d{d}"]["compile_s"] = compile_s()
    return {"kernels": out}


# ---------------------------------------------------------------------------
# leg A: the Criteo trainer
# ---------------------------------------------------------------------------


def criteo_data(run: Run, subdir: str, launches: int) -> str:
    """Criteo-format text from seed 0 under ``<out>/data/criteo/<subdir>``
    (where the committed conf's relative glob finds ``train``)."""
    from parameter_server_tpu.data.criteo_synth import write_criteo_file

    path = run.path("data", "criteo", subdir, "part.001")
    if not os.path.exists(path):
        write_criteo_file(
            path, launches * run.sizes.launch_rows, p_cat=run.sizes.p_cat
        )
    return path


def dense_conf(run: Run, data_glob: str) -> str:
    """The dense-sweep side of the ``update: auto`` flip — the shape
    ``bench.py --real`` uses: f32 state, bits wire, no tail filter (the
    bits wire needs uniform 39-lane rows)."""
    s = run.sizes
    path = run.path("confs", "criteo_dense.conf")
    with open(path, "w") as f:
        f.write(f'''# written by chip_smoke.py
training_data {{
  format: TEXT
  text: CRITEO
  file: "{data_glob}"
}}
loss {{ type: LOGIT }}
penalty {{ type: L1 lambda: 1 lambda: 0.1 }}
learning_rate {{ type: DECAY alpha: 0.1 beta: 1 }}
async_sgd {{
  algo: FTRL
  minibatch: {s.minibatch}
  max_delay: 4
  num_slots: {s.slots_dense}
  ell_lanes: 39
  wire: "bits"
  steps_per_launch: {s.steps_per_launch}
}}
''')
    return path


def bigtable_conf(run: Run) -> str:
    """``online_l1lr_bigtable.conf`` as committed; the rehearsal cuts a
    copy of it to toy size (same keys, smaller numbers)."""
    if not run.rehearsal:
        return BIGTABLE_CONF
    s = run.sizes
    with open(BIGTABLE_CONF) as f:
        text = f.read()
    for old, new in (
        ("minibatch: 16384", f"minibatch: {s.minibatch}"),
        ("num_slots: 1073741824", f"num_slots: {s.slots_big}"),
        ("steps_per_launch: 8", f"steps_per_launch: {s.steps_per_launch}"),
        ("countmin_n: 100000000", "countmin_n: 100000"),
    ):
        require(old in text, f"{BIGTABLE_CONF} no longer holds {old!r}")
        text = text.replace(old, new)
    path = run.path("confs", "criteo_bigtable_rehearsal.conf")
    with open(path, "w") as f:
        f.write(text)
    return path


def linear_cli(run: Run, name: str, conf: str, extra=()) -> dict:
    """One ``apps.linear.main`` run from ``<out>`` as working directory,
    then what the process knows about it: the learning plane's loss
    trajectory (one entry per launch), the FTRL update path counter and
    the instrumented steps' custom calls."""
    from parameter_server_tpu.apps.linear import main as linear_main
    from parameter_server_tpu.telemetry import learning

    log = run.path("logs", f"{name}.stdout")
    cwd = os.getcwd()
    os.chdir(run.out)
    try:
        with open(log, "w") as f, contextlib.redirect_stdout(f):
            rc = linear_main.main([conf, *extra])
    finally:
        os.chdir(cwd)
    require(rc == 0, f"{name}: apps.linear.main returned {rc}")
    plane = learning.snapshot_all()["async_sgd_worker"]
    losses = [t["loss"] for t in plane["trajectory_tail"]]
    rec = {
        "conf": os.path.relpath(conf, REPO),
        "argv": list(extra),
        "examples": plane["examples"],
        "launches": plane["collected_steps"],
        "first_loss": losses[0],
        "last_loss": losses[-1],
        "staleness": plane["staleness"],
        "ftrl_update_path": ftrl_update_paths(),
        "stdout": os.path.relpath(log, run.out),
    }
    # the plane writes a non-finite loss as a string
    require(
        all(isinstance(x, float) and math.isfinite(x) for x in losses),
        f"{name}: non-finite loss in {losses}",
    )
    return rec


def ftrl_update_paths() -> dict:
    """``ps_ftrl_update_path_total`` by path: which FTRL update the
    submitted ministeps rode (``ref`` off the TPU)."""
    from parameter_server_tpu.telemetry import registry as telreg

    values = telreg.default_registry().snapshot()[
        "ps_ftrl_update_path_total"
    ]["values"]
    return {k.split("=", 1)[1]: int(v) for k, v in values.items()}


def check_training(run: Run, name: str, rec: dict, want_path: str,
                   launches: int) -> None:
    want_examples = launches * run.sizes.launch_rows
    require(
        rec["examples"] == want_examples,
        f"{name}: trained {rec['examples']} examples, fed {want_examples}",
    )
    # ln 2 is the loss of the zero model; a trainer that learns falls
    # from there (a run that filtered every feature sits at 0.69315)
    require(
        rec["last_loss"] < rec["first_loss"] < math.log(2) + 1e-3,
        f"{name}: loss did not fall: first {rec['first_loss']} "
        f"last {rec['last_loss']}",
    )
    require(
        set(rec["ftrl_update_path"]) == {want_path},
        f"{name}: FTRL update path {rec['ftrl_update_path']}, "
        f"expected only {want_path!r}",
    )


def step_custom_calls() -> dict:
    from parameter_server_tpu.telemetry import device as device_tel

    return {
        n: f.get("custom_calls", [])
        for n, f in device_tel.snapshot()["functions"].items()
        if n.startswith("step_") and f["calls"]
    }


def leg_a_dense(run: Run) -> dict:
    data = criteo_data(run, "train", run.sizes.launches)
    conf = dense_conf(run, os.path.join(os.path.dirname(data), "part.*"))
    rec = linear_cli(run, "A26", conf)
    check_training(
        run, "A26", rec, "pallas_dense" if run.on_tpu else "ref",
        run.sizes.launches,
    )
    rec["num_slots"] = run.sizes.slots_dense
    rec["step_custom_calls"] = calls = step_custom_calls()
    require(
        not run.on_tpu or all("tpu_custom_call" in c for c in calls.values()),
        f"A26: a train step holds no Mosaic call: {calls}",
    )
    return rec


def leg_a_bigtable(run: Run, extra=(), name="A30") -> dict:
    criteo_data(run, "train", run.sizes.launches)
    flip = "PS_SPARSE_UPDATE_MIN_SLOTS"
    if run.rehearsal:
        # a toy table sits below the `update: auto` flip: move the flip
        # (the sweep override that exists) so the rehearsal walks the
        # side of it the 2^30 table lands on
        os.environ[flip] = str(run.sizes.slots_big)
    try:
        rec = linear_cli(run, name, bigtable_conf(run), extra)
    finally:
        if run.rehearsal:
            del os.environ[flip]
    # one chip: `update: auto` flips to sparse at a 2^30 shard, and a
    # bf16 sqrt_n table is outside the fused sparse kernel (the compiler
    # refuses its single-row DMA — ops/ftrl_sparse.py), so the XLA rows
    # path runs. Split over servers the 2^29 shards are dense sweeps.
    dense = "--num-servers" in extra
    check_training(
        run, name, rec,
        ("pallas_dense" if run.on_tpu else "ref") if dense else "xla_rows",
        run.sizes.launches,
    )
    rec["num_slots"] = run.sizes.slots_big
    rec["step_custom_calls"] = step_custom_calls()
    model = glob.glob(os.path.join(run.out, "model", "*"))
    rec["model_files"] = {
        os.path.basename(m): os.path.getsize(m) for m in model
    }
    require(
        model and all(rec["model_files"].values()),
        f"{name}: no model written: {rec['model_files']}",
    )
    shutil.rmtree(os.path.join(run.out, "model"))
    return rec


def leg_a_1x4(run: Run) -> dict:
    return leg_a_bigtable(run, ("--num-servers", "4"), name="A1x4")


def leg_a_2x2(run: Run) -> dict:
    return leg_a_bigtable(
        run, ("--num-workers", "2", "--num-servers", "2"), name="A2x2"
    )


def leg_a_parity(run: Run) -> dict:
    """Sequential parity in the library: ``parity_steps`` minibatches at
    ``max_delay=0`` against the NumPy FTRL oracle, device logloss within
    ``max(0.01, 0.02*ll)``, once per update formulation."""
    import jax

    from parameter_server_tpu.apps.linear.async_sgd import AsyncSGDWorker
    from parameter_server_tpu.apps.linear.config import (
        Config,
        LearningRateConfig,
        PenaltyConfig,
        SGDConfig,
    )
    from parameter_server_tpu.apps.linear.oracle import FtrlOracle
    from parameter_server_tpu.data.stream_reader import StreamReader
    from parameter_server_tpu.system.postoffice import Postoffice
    from parameter_server_tpu.telemetry import device as device_tel

    s = run.sizes
    data = criteo_data(run, "train", s.launches)
    stream = StreamReader([data], "criteo").minibatches_bytes(
        s.minibatch, threads=2
    )
    batches = [next(stream) for _ in range(s.parity_steps)]
    alpha, beta, l1 = 0.1, 1.0, 1.0
    oracle = FtrlOracle(s.slots_dense, alpha, beta, l1)
    n_ex = sum(b.n for b in batches)
    ll_oracle = sum(oracle.step(b) for b in batches) / n_ex
    tol = max(0.01, 0.02 * ll_oracle)
    out = {"steps": s.parity_steps, "examples": n_ex,
           "num_slots": s.slots_dense, "logloss_oracle": ll_oracle,
           "tolerance": tol, "formulations": {}}
    want_paths = (
        {"dense": "pallas_dense", "sparse": "pallas_sparse"} if run.on_tpu
        else {"dense": "ref", "sparse": "xla_rows"}
    )
    recompiles = 0
    for update in ("dense", "sparse"):
        reset_process_state()
        po = Postoffice.instance().start()
        conf = Config()
        conf.penalty = PenaltyConfig(type="l1", lambda_=[l1])
        conf.learning_rate = LearningRateConfig(
            type="decay", alpha=alpha, beta=beta
        )
        conf.async_sgd = SGDConfig(
            algo="ftrl", minibatch=s.minibatch, num_slots=s.slots_dense,
            max_delay=0, ell_lanes=39, wire="bits", update=update,
        )
        worker = AsyncSGDWorker(conf, mesh=po.mesh)
        objective = 0.0
        for i, b in enumerate(batches):
            prepped = jax.device_put(worker.prep(b, device_put=False))
            m = worker.executor.wait(
                worker._submit_prepped(prepped, with_aux=False)
            )
            objective += float(m["objective"])
            if i == 0:
                device_tel.mark_warmup()
        ll = objective / n_ex
        rec = {
            "logloss_device": ll,
            "abs_diff": abs(ll - ll_oracle),
            "ftrl_update_path": ftrl_update_paths(),
            "recompiles_post_warmup": device_tel.snapshot()[
                "recompiles_post_warmup"
            ],
            "step_custom_calls": step_custom_calls(),
        }
        out["formulations"][update] = rec
        recompiles += rec["recompiles_post_warmup"]
        require(
            rec["abs_diff"] <= tol
            and set(rec["ftrl_update_path"]) == {want_paths[update]}
            and (not run.on_tpu or all(
                "tpu_custom_call" in c
                for c in rec["step_custom_calls"].values()
            )),
            f"parity ({update}): {rec} vs oracle {ll_oracle}, "
            f"expected {want_paths[update]}",
        )
        po.stop()
        del worker
    out["recompiles_post_warmup"] = recompiles  # after mark_warmup()
    return out


def leg_a_profile(run: Run) -> dict:
    """One profiler capture of two launches of the dense conf, kept in
    the output directory: the small recorded trace later reductions are
    written against."""
    import jax

    data = criteo_data(run, "profile", 2)
    conf = dense_conf(run, os.path.join(os.path.dirname(data), "part.*"))
    trace_dir = os.path.join(run.out, "profile")
    shutil.rmtree(trace_dir, ignore_errors=True)
    rec = linear_cli(run, "Aprofile", conf, ("--profile", trace_dir))
    files = {
        os.path.relpath(p, trace_dir): os.path.getsize(p)
        for p in glob.glob(os.path.join(trace_dir, "**", "*"), recursive=True)
        if os.path.isfile(p)
    }
    planes = {}
    for path in glob.glob(
        os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True
    ):
        for plane in jax.profiler.ProfileData.from_file(path).planes:
            planes[plane.name] = planes.get(plane.name, 0) + sum(
                1 for line in plane.lines for _ in line.events
            )
    want = "TPU" if run.on_tpu else "CPU"
    device_events = sum(n for name, n in planes.items() if want in name)
    require(
        device_events,
        f"profile: no {want} plane with events in {files}: {planes}",
    )
    return {**rec, "trace_dir": os.path.relpath(trace_dir, run.out),
            "files": files, "planes": planes,
            "device_plane_events": device_events}


# ---------------------------------------------------------------------------
# leg B: the LM CLI
# ---------------------------------------------------------------------------


def lm_argv(run: Run, log: str, extra=()) -> list:
    from parameter_server_tpu.apps.lm.shapes import WIDEST, mfu_modes

    if run.rehearsal:
        cfg = dict(d_model=64, n_heads=4, n_layers=2, d_ff=128)
        ov = {"seq": 64, "batch": 2}
        remat = False
    else:
        cfg, ov = next(
            (kw, ov) for name, kw, ov in mfu_modes() if name == WIDEST
        )
        remat = cfg["remat"]
    return [
        "--d-model", str(cfg["d_model"]), "--n-heads", str(cfg["n_heads"]),
        "--n-layers", str(cfg["n_layers"]), "--d-ff", str(cfg["d_ff"]),
        "--seq-len", str(ov["seq"]), "--batch", str(ov["batch"]),
        "--bf16", "--attention", "ring_flash",
        *(["--remat"] if remat else []),
        "--steps", str(run.sizes.lm_steps), "--report-every", "1",
        "--lr", "3e-4", "--clip-norm", "1.0",
        # 64 bytes: with 8 or 64 generated tokens the total divides by
        # any data axis the forward below may be sharded over
        "--prompt", ("the parameter server " * 4)[:64], "--gen-tokens",
        str(run.sizes.gen_tokens), "--log-file", log, *extra,
    ]


def leg_b(run: Run, extra=(), name="B") -> dict:
    import jax
    import numpy as np

    from parameter_server_tpu.apps.lm import main as lm_main
    from parameter_server_tpu.models.transformer import (
        lm_forward,
        lm_generate,
        shard_tokens,
    )

    log = run.path("logs", f"{name}.stdout")
    argv = lm_argv(run, run.path("logs", f"{name}.steps.jsonl"), extra)
    step_compiles = run.meter.by_name.get("jit(one)", 0)
    with open(log, "w") as f, contextlib.redirect_stdout(f):
        # run() is main() without the exit code: it hands back the
        # trained weights for the decode-vs-forward check below
        res = lm_main.run(argv)
    cfg, params = res["cfg"], res["params"]
    losses = [ll for _, ll in res["losses"]]
    n_params = sum(int(np.prod(x.shape)) for x in jax.tree.leaves(params))
    # the CLI's step is `jax.jit(one)`: compiled once, at the first step
    step_compiles = run.meter.by_name.get("jit(one)", 0) - step_compiles
    rec = {
        "argv": argv, "parameters": n_params,
        "train_step_compiles": step_compiles,
        "recompiles_post_warmup": step_compiles - 1,
        "tokens": run.sizes.lm_steps * int(argv[argv.index("--batch") + 1])
        * int(argv[argv.index("--seq-len") + 1]),
        "first_loss": losses[0], "last_loss": losses[-1],
        "losses": [round(x, 4) for x in losses],
        "stdout": os.path.relpath(log, run.out),
    }
    # Not "from ln 256": with tied embeddings at std 0.02 the untrained
    # model's logit for the CURRENT byte is 0.02 * d_model (41 at
    # d_model 2048) against ~N(0, 1) for the rest, so a wide model
    # starts far above ln 256 = 5.545 on any backend (a float32 CPU run
    # of one 2048-wide layer starts at 26.4).
    require(
        all(math.isfinite(x) for x in losses) and losses[-1] < losses[0],
        f"{name}: loss is not finite or did not fall: {losses}",
    )

    # decode logits against the training forward, on the trained
    # weights, over the prompt and the generated tokens
    toks = np.asarray(res["generated"], np.int32)[None, :]
    p_len = toks.shape[1] - run.sizes.gen_tokens
    out, dec = lm_generate(
        params, toks[:, :p_len], cfg, steps=run.sizes.gen_tokens,
        return_logits=True,
    )
    require(
        np.array_equal(np.asarray(out), toks),
        f"{name}: greedy decode is not reproducible",
    )
    mesh = res["mesh"]
    full = jax.jit(lambda p, t: lm_forward(p, t, cfg, mesh, "data"))(
        params, shard_tokens(toks, mesh)
    )
    dec, full = np.asarray(dec), np.asarray(full)[:, :-1]
    atol = DECODE_ATOL[cfg.compute_dtype]
    rec["decode_parity"] = {
        "positions": int(dec.shape[1]),
        "max_abs_logit_diff": float(np.max(np.abs(dec - full))),
        "logit_absmax": float(np.max(np.abs(full))),
        "argmax_agreement": float(
            np.mean(dec.argmax(-1) == full.argmax(-1))
        ),
        "atol": atol, "compute_dtype": cfg.compute_dtype,
    }
    require(
        np.isfinite(dec).all()
        and rec["decode_parity"]["max_abs_logit_diff"] <= atol,
        f"{name}: decode parity {rec['decode_parity']}",
    )
    return rec


def leg_b_tp2(run: Run) -> dict:
    return leg_b(run, ("--num-servers", "2"), name="Btp2")


# ---------------------------------------------------------------------------
# leg C: the serve front end
# ---------------------------------------------------------------------------


def leg_c(run: Run) -> dict:
    from parameter_server_tpu.apps.serve import main as serve_main

    log = run.path("logs", "C.stdout")
    argv = [
        "--replica", "off", "--decode", "--batch-slots", "8",
        "--duration", str(run.sizes.serve_duration),
        "--train-while-serving",
    ]
    with open(log, "w") as f, contextlib.redirect_stdout(f):
        rc = serve_main.main(argv)
    require(rc == 0, f"C: apps.serve.main returned {rc}")
    with open(log) as f:
        lines = [json.loads(x) for x in f if x.startswith("{")]
    by_metric = {}
    for rec in lines:
        by_metric.setdefault(rec["metric"], []).append(rec)
    points = by_metric["serve_open_loop_point"]
    stats = by_metric["serve_frontend_stats"][0]
    decode = by_metric["serve_decode_latency_ms"][0]
    out = {
        "note": "plane check at the CLI's own model sizes, not full width",
        "argv": argv,
        "requests_completed": stats["completed"],
        "n_errors": sum(p["n_errors"] for p in points),
        "degraded_served": stats["degraded_served"],
        "pulls_answered_by": "live table on the device (replica off)",
        "coalescer_submits": stats["coalescer"]["submits"],
        "open_loop_points": [
            {k: p[k] for k in ("offered", "accepted", "completed", "n_errors")}
            for p in points
        ],
        "batcher": decode["batcher"],
        "recompiles_post_warmup": None,  # see the module docstring
        "stdout": os.path.relpath(log, run.out),
    }
    require(
        not out["n_errors"] and not out["degraded_served"]
        and "replica" not in stats,
        f"C: {out}",
    )
    require(
        out["batcher"]["rounds"] > 0 and out["batcher"]["retired"] > 0
        and out["coalescer_submits"] > 0 and stats["completed"] > 0,
        f"C: a lane did not run: {out}",
    )
    return out


# ---------------------------------------------------------------------------


LEG_FNS = {
    "K": leg_kernels, "A26": leg_a_dense, "A30": leg_a_bigtable,
    "Aparity": leg_a_parity, "Aprofile": leg_a_profile, "A1x4": leg_a_1x4,
    "A2x2": leg_a_2x2, "B": leg_b, "Btp2": leg_b_tp2, "C": leg_c,
}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--rehearsal", action="store_true",
                    help="toy shapes on the CPU; exits 3 on success")
    ap.add_argument("--out", default=os.path.join(
        REPO, "chiprun_out", "chip_smoke"))
    ap.add_argument("--legs", default=",".join(ALL_LEGS),
                    help="subset to run while bringing a leg up "
                    f"(of {','.join(ALL_LEGS)}); exits 4 on success")
    args = ap.parse_args(argv)
    args.legs = [x for x in args.legs.split(",") if x]
    unknown = set(args.legs) - set(ALL_LEGS)
    if unknown:
        ap.error(f"unknown legs {sorted(unknown)}")
    partial = set(args.legs) != set(ALL_LEGS)

    run = start(args)
    try:
        for name in ALL_LEGS:
            if name not in args.legs:
                continue
            if name in FOUR_CHIP_LEGS and run.device["count"] < 4:
                continue  # one chip does not pretend to be four
            run_leg(run, name, LEG_FNS[name])
    finally:
        # regenerable from the seed, and too big to carry back
        shutil.rmtree(os.path.join(run.out, "data"), ignore_errors=True)
    reset_process_state()
    ok = not (run.rehearsal or partial)
    emit(run.tag({
        "chip_smoke": "summary",
        "ok": ok,
        "device": run.device,
        "legs": {k: v["pass"] for k, v in run.legs.items()},
        "partial": partial,
        "compile_s": {k: v["compile_s"] for k, v in run.legs.items()},
        "run_s": {k: v["run_s"] for k, v in run.legs.items()},
        "peak_bytes_in_use": [
            m["peak_bytes_in_use"] for m in memory_per_device()
        ],
        "claim": None,
    }))
    if run.rehearsal:
        return REHEARSAL_EXIT
    emit(verdict(ok, run.device))
    return PARTIAL_EXIT if partial else 0


if __name__ == "__main__":
    sys.exit(main())

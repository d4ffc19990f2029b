"""Device truth plane: per-jit compile inventory, recompile/donation
tracking, roofline gauges, and HBM accounting.

Every observability layer before this one (the PR 1 registry, the PR 7
timeline, the PR 10 cluster plane) sees the *host*: spans and counters
say when a dispatch left and when its future resolved, but the chip
itself stays a black box — which is why the ROADMAP's roofline items
still quote hand-built bytes models. This module makes chip-side facts
first-class:

- **Compiled-function inventory** (:class:`DeviceInventory` /
  :func:`instrument`): wraps a jitted entry point so every
  ``lower().compile()`` is *owned* by the inventory. Per named
  function it records XLA ``cost_analysis()`` FLOPs / bytes-accessed
  and ``memory_analysis()`` buffer sizes, detects recompiles (a call
  with new avals/statics → ``ps_device_recompiles_total{fn}``), and
  verifies that declared donation actually aliased
  (``memory_analysis().alias_size_in_bytes`` against the donated
  argument bytes → ``ps_device_donation_fallbacks_total{fn}``) — the
  runtime twin of
  the static donation lint (doc/PERFORMANCE.md "Donation rules").
- **Roofline gauges**: with sampling enabled
  (:func:`set_sampling`), every N-th instrumented dispatch is timed to
  device completion; achieved GB/s and TFLOP/s derive from the
  cost-analysis bytes/FLOPs and land as
  ``ps_device_kernel_gb_s{fn}`` / ``ps_device_kernel_tflops{fn}``,
  with ``ps_device_roofline_frac{fn,resource}`` against the
  ``HBM_PEAK_GB_S`` / ``FLOPS_PEAK_TFLOPS`` peak tables below
  (unknown device kinds report no frac, never a faked one).
- **HBM accounting** (:class:`HbmMonitor`): a registry collector
  sampling ``jax.local_devices()[*].memory_stats()`` (bytes in use /
  peak / limit, TPU backends) and the live-buffer total from
  ``jax.live_arrays()`` with a process-lifetime high-water mark —
  the ``ps_device_hbm_*`` / ``ps_device_live_buffer_*`` families.

Dispatch semantics: the wrapper maintains its own signature →
``Compiled`` cache and calls the compiled executable directly, so
instrumentation adds no second compile. The original callable only
takes calls the inventory cannot describe: a signature that cannot be
read (foreign leaf types) or a plain Python callable that is no jit,
both counted under ``ps_device_dispatch_fallbacks_total{fn}``, and
tracer-stage calls (the function inlined inside an enclosing jit,
which owns the compile). A refused lowering, a failed compile or a
failed compiled dispatch RAISES: dropping to the plain jit would
compile the step a second time and hide the refusal behind a counter.
Statics must be passed as
keyword arguments at instrumented call sites (true for every wrap
point: ops/kv_ops, ops jit entry points, the async_sgd step builders).

``doc/OBSERVABILITY.md`` ("Device truth plane") documents how to
read :func:`snapshot`.
"""

from __future__ import annotations

import collections
import math
import threading
import time
from typing import Any, Dict, Optional, Sequence, Tuple

from . import registry as telemetry_registry


#: The package's one peaks table, keyed by
#: ``jax.devices()[0].device_kind`` (a TPU v5e reports "TPU v5 lite").
#: Sources: Google Cloud TPU documentation, "System architecture" pages
#: per generation ("TPU v5e": 197 TFLOP/s bf16, 393 TOP/s int8, 819
#: GB/s HBM). On a chip a kind missing here is an error
#: (:func:`device_peaks`); a CPU host resolves to None and every
#: frac-of-peak field is then null, never faked.
#:
#: chip HBM peak bandwidth (GB/s): the denominator of
#: ``ps_device_roofline_frac{resource="hbm"}``
HBM_PEAK_GB_S = {
    "TPU v4": 1228.0,
    "TPU v5 lite": 819.0,
    "TPU v5e": 819.0,
    "TPU v5": 2765.0,
    "TPU v5p": 2765.0,
    "TPU v6 lite": 1640.0,
    "TPU v6e": 1640.0,
}

#: chip bf16 matmul peak (TFLOP/s): the denominator of
#: ``ps_device_roofline_frac{resource="flops"}``
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
            f"(parameter_server_tpu/telemetry/device.py); add it with "
            "its source before measuring on it"
        )
    return {
        "hbm_gb_s": HBM_PEAK_GB_S[device_kind],
        "bf16_tflops": FLOPS_PEAK_TFLOPS[device_kind],
    }


def _peaks(device_kind: str) -> Tuple[Optional[float], Optional[float]]:
    """(HBM peak GB/s, bf16 peak TFLOP/s) for a device kind, or Nones."""
    return HBM_PEAK_GB_S.get(device_kind), FLOPS_PEAK_TFLOPS.get(device_kind)


def _leaf_sig(leaf) -> Tuple:
    """Hashable signature of one pytree leaf: (shape, dtype, weak_type,
    sharding). Sharding is part of the key because a Compiled is
    specialized to the shardings it was lowered with — two same-aval
    call patterns with different shardings need their own entries, or
    the second would raise (and fall back) on every dispatch."""
    import jax

    aval = jax.api_util.shaped_abstractify(leaf)
    sharding = getattr(leaf, "sharding", None)
    return (
        aval.shape,
        str(aval.dtype),
        bool(getattr(aval, "weak_type", False)),
        sharding,
    )


def _static_key(value) -> Any:
    """Statics are hashable by jit's contract; an unhashable oddity
    degrades to repr rather than poisoning the cache key."""
    try:
        hash(value)
        return value
    except TypeError:
        return repr(value)


def _canonical_call(sig, statics, args, kwargs):
    """Bind a call against the function's signature, apply declared
    defaults, and split it into ``(dyn_args, dyn_kwargs,
    static_vals)`` — statics extracted BY NAME regardless of how the
    caller spelled them. This mirrors jit's own cache normalization:
    ``f(x)``, ``f(x, seed_default)`` and ``f(x, k=<default>)`` all
    resolve to one canonical form, so an omitted default vs its
    explicit spelling cannot double-compile (and tick a spurious
    recompile). Returns None when binding fails — the caller then uses
    the raw call shape and the jit raises its own arity error."""
    import inspect

    try:
        bound = sig.bind(*args, **kwargs)
        bound.apply_defaults()
    except TypeError:
        return None
    P = inspect.Parameter
    dyn_args: list = []
    dyn_kwargs: Dict[str, Any] = {}
    static_vals: list = []
    for pname, param in sig.parameters.items():
        if pname not in bound.arguments:
            continue
        v = bound.arguments[pname]
        if pname in statics:
            static_vals.append((pname, v))
        elif param.kind in (P.POSITIONAL_ONLY, P.POSITIONAL_OR_KEYWORD):
            dyn_args.append(v)
        elif param.kind == P.VAR_POSITIONAL:
            dyn_args.extend(v)
        elif param.kind == P.KEYWORD_ONLY:
            dyn_kwargs[pname] = v
        else:  # VAR_KEYWORD: a dict of extra keywords
            for k, vv in v.items():
                if k in statics:
                    static_vals.append((k, vv))
                else:
                    dyn_kwargs[k] = vv
    static_vals.sort(key=lambda kv: kv[0])
    return tuple(dyn_args), dyn_kwargs, tuple(static_vals)


def _cost_dict(compiled) -> Optional[Dict[str, float]]:
    """Normalized ``cost_analysis()``: {"flops", "bytes_accessed"} or
    None when the backend offers no analysis."""
    try:
        ca = compiled.cost_analysis()
    except Exception:
        return None
    if isinstance(ca, (list, tuple)):
        ca = ca[0] if ca else None
    if not isinstance(ca, dict):
        return None
    out: Dict[str, float] = {}
    if ca.get("flops") is not None:
        out["flops"] = float(ca["flops"])
    if ca.get("bytes accessed") is not None:
        out["bytes_accessed"] = float(ca["bytes accessed"])
    return out or None


def _memory_dict(compiled) -> Optional[Dict[str, int]]:
    """Normalized ``memory_analysis()`` buffer sizes, or None."""
    try:
        ma = compiled.memory_analysis()
    except Exception:
        return None
    if ma is None:
        return None
    try:
        return {
            "argument_bytes": int(ma.argument_size_in_bytes),
            "output_bytes": int(ma.output_size_in_bytes),
            "alias_bytes": int(ma.alias_size_in_bytes),
            "temp_bytes": int(ma.temp_size_in_bytes),
            "generated_code_bytes": int(ma.generated_code_size_in_bytes),
        }
    except Exception:
        return None


def _custom_calls(compiled) -> Tuple[str, ...]:
    """The distinct ``custom_call_target`` names in a compiled
    executable's text — what says whether a Pallas kernel
    (``tpu_custom_call``) or an XLA reference made it into the program."""
    import re

    return tuple(sorted(set(
        re.findall(r'custom_call_target="([^"]+)"', compiled.as_text())
    )))


class _FnRecord:
    """Inventory state of one named function (all fields guarded by
    the owning inventory's lock)."""

    __slots__ = (
        "name", "compiles", "recompiles", "donation_fallbacks",
        "dispatch_fallbacks", "calls", "cost", "memory",
        "donated_bytes", "last_timing", "custom_calls",
    )

    def __init__(self, name: str):
        self.name = name
        self.compiles = 0
        self.recompiles = 0
        self.donation_fallbacks = 0
        self.dispatch_fallbacks = 0
        self.calls = 0
        self.cost: Optional[Dict[str, float]] = None      # latest compile
        self.memory: Optional[Dict[str, int]] = None      # latest compile
        self.donated_bytes = 0                            # latest compile
        self.last_timing: Optional[Dict[str, Any]] = None # latest sample
        self.custom_calls: Tuple[str, ...] = ()           # latest compile

    def as_dict(self) -> Dict[str, Any]:
        out: Dict[str, Any] = {
            "compiles": self.compiles,
            "recompiles": self.recompiles,
            "donation_fallbacks": self.donation_fallbacks,
            "calls": self.calls,
        }
        if self.dispatch_fallbacks:
            out["dispatch_fallbacks"] = self.dispatch_fallbacks
        if self.cost:
            out["cost"] = dict(self.cost)
        if self.memory:
            out["memory"] = dict(self.memory)
        if self.donated_bytes:
            out["donated_bytes"] = self.donated_bytes
        if self.custom_calls:
            out["custom_calls"] = list(self.custom_calls)
        if self.last_timing:
            out["roofline"] = dict(self.last_timing)
        return out


def _device_tel():
    """The ps_device_* instruments against the current default
    registry, or None while telemetry is off (hot-path cached)."""
    from .instruments import cached_device_instruments

    return cached_device_instruments()


class _WrapperCache(dict):
    """A wrapper-local signature → Compiled dict. A plain dict is not
    weakref-able; the inventory holds these by weakref so reset() can
    clear live caches without keeping dead wrappers' executables
    alive."""

    __slots__ = ("__weakref__",)


class DeviceInventory:
    """Per-function chip-truth records + the instrument() wrap factory.

    Each wrapper owns its OWN signature → Compiled cache (a closure
    dict): when a wrapper and its jit are dropped — a rebuilt step
    builder, a dead worker — the cached executables die with them,
    exactly jax's own cache-lifetime semantics (a process-global cache
    would strongly leak every executable of every builder ever made).
    The inventory holds only the small per-NAME records. Thread-safe:
    compiles happen outside the lock (they are seconds on a real chip;
    serializing them would wedge concurrent call sites), bookkeeping
    inside it — a racing duplicate compile records once — and the
    steady-state dispatch path takes NO inventory lock (dict read +
    benign GIL-atomic counters).
    """

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self._records: Dict[str, _FnRecord] = {}       # guarded-by: _lock
        # read lock-free on every dispatch (a GIL-atomic int; set under
        # the lock only for write ordering) — sampling cadence is
        # advisory, a stale read costs at most one mistimed sample
        self._sample_every = 0
        self._warmup_marks: Dict[str, Tuple[int, int]] = {}  # guarded-by: _lock
        # WEAK refs to live wrapper caches, so reset() can clear them
        # (module-level wrappers like kv_ops outlive any test) without
        # the inventory owning their lifetime — a dead wrapper's cache,
        # executables included, is garbage the moment the wrapper is
        self._cache_refs: list = []                    # guarded-by: _lock

    # -- configuration ----------------------------------------------------

    def set_sampling(self, every: int) -> int:
        """Time every N-th instrumented dispatch to device completion
        for the roofline gauges (0 disables — the production default:
        a timed call blocks on its result, which an async pipeline
        should only pay when someone is measuring). Returns the
        previous value so a caller can restore it."""
        with self._lock:
            prev, self._sample_every = self._sample_every, max(0, int(every))
        return prev

    def mark_warmup(self) -> None:
        """Record current compile/recompile counts per function; the
        snapshot's ``recompiles_post_warmup`` counts only growth past
        this mark (the steady-state contract: zero after warmup)."""
        with self._lock:
            self._warmup_marks = {
                name: (rec.compiles, rec.recompiles)
                for name, rec in self._records.items()
            }

    def reset(self) -> None:
        """Test hook: clear the per-name records, warmup marks, and
        every LIVE wrapper's compiled cache (so a module-level wrapper
        like kv_ops recompiles — and re-registers its record — on its
        next call). Dead wrappers' caches are already garbage."""
        with self._lock:
            self._records.clear()
            self._warmup_marks.clear()
            live = []
            for ref in self._cache_refs:
                cache = ref()
                if cache is not None:
                    cache.clear()
                    live.append(ref)
            self._cache_refs = live

    # -- the wrapper ------------------------------------------------------

    def instrument(
        self,
        name: str,
        fn,
        static_argnames: Sequence[str] = (),
        donate_argnums: Sequence[int] = (),
    ):
        """Wrap a jitted callable into the inventory under ``name``.

        ``static_argnames`` must mirror the jit's own declaration and
        the call sites must pass those as keywords (every wrap point in
        this repo does). ``donate_argnums`` mirrors the jit's donation
        so the verifier knows how many argument bytes SHOULD alias.
        The wrapper is drop-in: same outputs bit-for-bit, donation
        semantics preserved (the compiled executable consumes donated
        buffers exactly like the jit would).

        Hot-path cost: one pytree flatten + per-leaf aval hash per call
        (the signature check jax's C++ dispatch does natively) and NO
        lock — the cache is a wrapper-local dict (reads GIL-atomic,
        writes under the inventory lock in ``_compile``) and the call
        counter is a benign GIL-racy int (advisory: a lost increment
        shifts a sample, never a result). The cache being PER WRAPPER
        is also the correctness boundary: two builders can share an
        inventory name with the same avals yet close over different
        configs — any shared aval-keyed cache would hand one the
        other's executable (regression-tested)."""
        import inspect
        import weakref

        import jax

        statics = tuple(static_argnames)
        donate = tuple(donate_argnums)
        cache = _WrapperCache()
        rec_box: list = []  # [_FnRecord], refreshed by each compile
        try:
            # canonical call binding: jit's own cache treats f(x),
            # f(x, seed_default) and f(x, k=<declared default>) as ONE
            # entry — without the same normalization, an omitted
            # default vs its explicit spelling would double-compile and
            # tick a spurious recompile (breaking the zero-post-warmup
            # contract on a healthy run)
            call_sig = inspect.signature(fn)
        except (TypeError, ValueError):
            call_sig = None
        with self._lock:
            # prune dead wrappers' refs while registering (reset() is
            # a test hook — production must not grow this unbounded)
            self._cache_refs = [
                r for r in self._cache_refs if r() is not None
            ]
            self._cache_refs.append(weakref.ref(cache))

        def wrapper(*args, **kwargs):
            try:
                split = None
                if call_sig is not None:
                    split = _canonical_call(call_sig, statics, args, kwargs)
                if split is not None:
                    dyn_args, dyn_kwargs, static_vals = split
                    # lower with statics spelled as KEYWORDS: the
                    # Compiled then expects only the dynamic args at
                    # call time, independent of how the caller spelled
                    # (or omitted) the statics
                    lower_args, lower_kwargs = dyn_args, {
                        **dyn_kwargs, **dict(static_vals)
                    }
                else:
                    # no usable signature: original call shape, statics
                    # recognized as keywords only (every in-repo wrap
                    # point passes them that way)
                    dyn_args = args
                    dyn_kwargs = {
                        k: v for k, v in kwargs.items() if k not in statics
                    }
                    static_vals = tuple(
                        (k, kwargs[k]) for k in statics if k in kwargs
                    )
                    lower_args, lower_kwargs = args, kwargs
                static_items = tuple(
                    (k, _static_key(v)) for k, v in static_vals
                )
                sig = []
                for leaf in jax.tree_util.tree_leaves((dyn_args, dyn_kwargs)):
                    if isinstance(leaf, jax.core.Tracer):
                        # inlined inside an enclosing trace: the
                        # enclosing jit owns the compile — pass through
                        return fn(*args, **kwargs)
                    sig.append(_leaf_sig(leaf))
                treedef = jax.tree_util.tree_structure((dyn_args, dyn_kwargs))
                key = (treedef, tuple(sig), static_items)
            except Exception:
                self._count_fallback(name)
                return fn(*args, **kwargs)

            compiled = cache.get(key)
            if compiled is None:
                if not hasattr(fn, "lower"):
                    # a plain callable, not a jit: nothing to compile
                    # or verify — route to it untouched, counted
                    self._count_fallback(name)
                    return fn(*args, **kwargs)
                compiled = self._compile(
                    name, cache, rec_box, key, fn, lower_args, lower_kwargs,
                    donate,
                )

            rec_sample = False
            if rec_box:
                rec = rec_box[0]
                rec.calls += 1  # benign GIL race: advisory counter
                se = self._sample_every
                rec_sample = se > 0 and rec.calls % se == 0
            # a failed dispatch raises: re-running it through the plain
            # jit would compile the step a second time and then fail
            # the same way (or hide a sharding the cache key missed)
            if rec_sample:
                t0 = time.perf_counter()
                out = compiled(*dyn_args, **dyn_kwargs)
                jax.block_until_ready(out)
                self._observe_timing(name, time.perf_counter() - t0)
                return out
            return compiled(*dyn_args, **dyn_kwargs)

        wrapper.__name__ = f"instrumented_{name}"
        wrapper.__qualname__ = wrapper.__name__
        wrapper.__doc__ = fn.__doc__
        wrapper.__wrapped__ = fn
        return wrapper

    # -- internals --------------------------------------------------------

    def _compile(self, name, cache, rec_box, key, fn, args, kwargs, donate):
        import jax

        # compiles run outside any lock and capture NO warnings state:
        # warnings.catch_warnings mutates process-global filters and is
        # not thread-safe, so two concurrent compiles could
        # cross-attribute the donation warning — the alias-bytes
        # comparison below is the deterministic signal and subsumes it
        # (an unusable donation aliases fewer bytes than were donated).
        # A refused lowering or compile raises here, once, with the
        # compiler's own message.
        compiled = fn.lower(*args, **kwargs).compile()
        cost = _cost_dict(compiled)
        memory = _memory_dict(compiled)
        custom_calls = _custom_calls(compiled)
        # per DEVICE, like memory_analysis(): a table sharded over the
        # server axis donates one shard's bytes on each chip, and
        # counting the global array would flag every sharded donation
        # as a fallback
        donated_bytes = 0
        for i in donate:
            if i < len(args):
                for leaf in jax.tree_util.tree_leaves(args[i]):
                    try:
                        aval = jax.api_util.shaped_abstractify(leaf)
                        shape = aval.shape
                        sharding = getattr(leaf, "sharding", None)
                        if sharding is not None:
                            shape = sharding.shard_shape(shape)
                        donated_bytes += int(
                            math.prod(shape) * aval.dtype.itemsize
                        )
                    except Exception:
                        pass
        alias = (memory or {}).get("alias_bytes", 0)
        fallback = donated_bytes > 0 and alias < donated_bytes
        tel = _device_tel()
        with self._lock:
            if key in cache:
                return cache[key]  # racing compile: theirs won
            cache[key] = compiled
            rec = self._records.get(name)
            if rec is None:
                rec = self._records[name] = _FnRecord(name)
            rec_box[:] = [rec]  # refresh: reset() may have swapped it
            rec.compiles += 1
            recompile = rec.compiles > 1
            if recompile:
                rec.recompiles += 1
            if fallback:
                rec.donation_fallbacks += 1
            rec.cost = cost
            rec.memory = memory
            rec.custom_calls = custom_calls
            rec.donated_bytes = donated_bytes
        if tel is not None:
            tel["compiles"].labels(fn=name).inc()
            if recompile:
                tel["recompiles"].labels(fn=name).inc()
            if fallback:
                tel["donation_fallbacks"].labels(fn=name).inc()
        return compiled

    def _count_fallback(self, name: str) -> None:
        with self._lock:
            rec = self._records.get(name)
            if rec is None:
                rec = self._records[name] = _FnRecord(name)
            rec.dispatch_fallbacks += 1
        tel = _device_tel()
        if tel is not None:
            tel["dispatch_fallbacks"].labels(fn=name).inc()

    def _observe_timing(self, name: str, wall_s: float) -> None:
        """Fold one timed dispatch into the function's roofline view
        and the live gauges. Achieved rates derive from the latest
        compile's cost analysis; fracs only exist when the peak tables
        know this device kind."""
        import jax

        with self._lock:
            rec = self._records.get(name)
            cost = dict(rec.cost) if rec and rec.cost else None
        if cost is None or wall_s <= 0:
            return
        try:
            kind = jax.devices()[0].device_kind
        except Exception:
            kind = "?"
        hbm_peak, flops_peak = _peaks(kind)
        timing: Dict[str, Any] = {"wall_ms": round(wall_s * 1e3, 4)}
        tel = _device_tel()
        gb_s = tflops = None
        if cost.get("bytes_accessed"):
            gb_s = cost["bytes_accessed"] / wall_s / 1e9
            timing["achieved_gb_s"] = round(gb_s, 3)
        if cost.get("flops"):
            tflops = cost["flops"] / wall_s / 1e12
            timing["achieved_tflops"] = round(tflops, 5)
        if hbm_peak and gb_s is not None:
            timing["frac_of_hbm_peak"] = round(gb_s / hbm_peak, 5)
        if flops_peak and tflops is not None:
            timing["mfu"] = round(tflops / flops_peak, 6)
        with self._lock:
            if rec is not None:
                rec.last_timing = timing
        if tel is not None:
            if gb_s is not None:
                tel["kernel_gb_s"].labels(fn=name).set(gb_s)
            if tflops is not None:
                tel["kernel_tflops"].labels(fn=name).set(tflops)
            if "frac_of_hbm_peak" in timing:
                tel["roofline_frac"].labels(fn=name, resource="hbm").set(
                    timing["frac_of_hbm_peak"]
                )
            if "mfu" in timing:
                tel["roofline_frac"].labels(fn=name, resource="flops").set(
                    timing["mfu"]
                )

    # -- reads ------------------------------------------------------------

    def snapshot(self) -> Dict[str, Any]:
        """The record-embeddable inventory view: per-function compile /
        recompile / donation-fallback counts with the latest cost and
        memory analyses, plus the post-warmup recompile total (zero on
        a healthy steady-state run)."""
        with self._lock:
            fns = {
                name: rec.as_dict()
                for name, rec in sorted(self._records.items())
            }
            marks = dict(self._warmup_marks)
            recs = dict(self._records)
        post_warmup = 0
        for name, rec in recs.items():
            c0, r0 = marks.get(name, (0, 0))
            # a function first compiled AFTER the mark is warmup debt
            # too: steady state means no new programs at all
            post_warmup += (rec.compiles - c0) if name in marks else (
                rec.compiles
            )
            # avoid double counting: recompiles are included in
            # compiles growth above
        out: Dict[str, Any] = {
            "functions": fns,
            "recompiles_post_warmup": post_warmup if marks else None,
            "donation_fallbacks_total": sum(
                rec.donation_fallbacks for rec in recs.values()
            ),
        }
        return out


class HbmMonitor:
    """Registry collector for device-memory truth.

    ``collect()`` runs before every snapshot/render (the registry
    collector contract): per-device ``memory_stats()`` where the
    backend provides them (TPU: bytes_in_use / peak_bytes_in_use /
    bytes_limit) and the cross-backend live-buffer total from
    ``jax.live_arrays()`` with a process-lifetime high-water mark — so
    a CPU-container test run still exercises the same family the chip
    capture reads. The owner must keep a strong reference (collectors
    are weakrefs); :func:`install_hbm_monitor` parks it module-side.
    """

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self._live_high_water = 0     # guarded-by: _lock
        self._last: Dict[str, Any] = {}  # guarded-by: _lock

    def collect(self) -> None:
        import jax

        tel = _device_tel()
        live_bytes = 0
        try:
            for arr in jax.live_arrays():
                live_bytes += int(getattr(arr, "nbytes", 0) or 0)
        except Exception:
            live_bytes = 0
        devices: Dict[str, Dict[str, int]] = {}
        try:
            for d in jax.local_devices():
                try:
                    ms = d.memory_stats()
                except Exception:
                    ms = None
                if not ms:
                    continue
                label = f"{d.platform}:{d.id}"
                stats = {k: int(ms.get(k, 0)) for k in (
                    "bytes_in_use", "peak_bytes_in_use", "bytes_limit",
                    "largest_free_block_bytes",  # 0 where the backend has none
                )}
                devices[label] = stats
        except Exception:
            pass
        with self._lock:
            self._live_high_water = max(self._live_high_water, live_bytes)
            high = self._live_high_water
            self._last = {
                "live_buffer_bytes": live_bytes,
                "live_buffer_high_water_bytes": high,
                "devices": devices,
            }
        if tel is None:
            return
        tel["live_buffers"].set(live_bytes)
        tel["live_high_water"].set(high)
        for label, stats in devices.items():
            tel["hbm_bytes_in_use"].labels(device=label).set(
                stats["bytes_in_use"]
            )
            tel["hbm_high_water"].labels(device=label).set(
                stats["peak_bytes_in_use"]
            )
            tel["hbm_limit"].labels(device=label).set(stats["bytes_limit"])
            if stats["bytes_limit"]:
                tel["hbm_frac_used"].labels(device=label).set(
                    stats["bytes_in_use"] / stats["bytes_limit"]
                )

    def snapshot(self) -> Dict[str, Any]:
        """Freshly collected HBM view."""
        self.collect()
        with self._lock:
            return dict(self._last)


# -- module-level plumbing (the process-default inventory) -----------------

_default_inventory = DeviceInventory()
_hbm_monitor: Optional[HbmMonitor] = None
_hbm_lock = threading.Lock()


def inventory() -> DeviceInventory:
    return _default_inventory


def instrument(
    name: str,
    fn,
    static_argnames: Sequence[str] = (),
    donate_argnums: Sequence[int] = (),
):
    """``DeviceInventory.instrument`` against the process inventory —
    the one-liner for module-level wrap points (ops/kv_ops, the step
    builders)."""
    return _default_inventory.instrument(
        name, fn, static_argnames=static_argnames,
        donate_argnums=donate_argnums,
    )


def set_sampling(every: int) -> int:
    return _default_inventory.set_sampling(every)


def mark_warmup() -> None:
    _default_inventory.mark_warmup()


def reset() -> None:
    """Test hook: clear the process inventory (compiled cache included)."""
    _default_inventory.reset()


def hbm_monitor() -> HbmMonitor:
    """The process HbmMonitor (created on first use; NOT yet registered
    as a collector — see :func:`install_hbm_monitor`)."""
    global _hbm_monitor
    with _hbm_lock:
        if _hbm_monitor is None:
            _hbm_monitor = HbmMonitor()
        return _hbm_monitor


def install_hbm_monitor(reg=None) -> Optional[HbmMonitor]:
    """Register the HBM collector on ``reg`` (default registry when
    None) so every snapshot/scrape carries fresh ``ps_device_hbm_*`` /
    live-buffer gauges. Idempotent per registry (re-adding a weakref'd
    bound method is harmless but avoided). No-op returning None while
    telemetry is disabled."""
    if reg is None:
        if not telemetry_registry.enabled():
            return None
        reg = telemetry_registry.default_registry()
    mon = hbm_monitor()
    installed = getattr(reg, "_ps_device_hbm_installed", False)
    if not installed:
        reg.add_collector(mon.collect)
        try:
            reg._ps_device_hbm_installed = True
        except Exception:
            pass
    return mon


# every compile and cache event of jax's, not only those the inventory's
# own wrapper reached: (perf_counter at the end, event, seconds)
_compile_events: collections.deque = collections.deque(maxlen=256)
_compile_listener = False  # guarded-by: _hbm_lock


def _on_compile_event(event: str, seconds: float, **_) -> None:
    if event.startswith(("/jax/core/compile/", "/jax/compilation_cache/")):
        _compile_events.append((time.perf_counter(), event, seconds))


def install_compile_listener() -> None:
    """The process's one ``jax.monitoring`` duration listener."""
    global _compile_listener
    import jax

    with _hbm_lock:
        if not _compile_listener:
            jax.monitoring.register_event_duration_secs_listener(
                _on_compile_event
            )
        _compile_listener = True


def compile_events_since(t0: float) -> Dict[str, Dict[str, float]]:
    """``{event: {count, seconds}}`` ended after ``t0`` (perf_counter)."""
    out: Dict[str, Dict[str, float]] = {}
    for t, event, seconds in list(_compile_events):
        if t >= t0:
            got = out.setdefault(event, {"count": 0, "seconds": 0.0})
            got["count"] += 1
            got["seconds"] += seconds
    return out


def snapshot() -> Dict[str, Any]:
    """Inventory counters + cost analyses + the HBM view, stamped with
    the backend identity."""
    out = _default_inventory.snapshot()
    try:
        import jax

        dev = jax.devices()[0]
        out["backend"] = jax.default_backend()
        out["device_kind"] = dev.device_kind
        hbm_peak, flops_peak = _peaks(dev.device_kind)
        out["hbm_peak_gb_s"] = hbm_peak
        out["flops_peak_tflops"] = flops_peak
    except Exception:
        pass
    out["hbm"] = hbm_monitor().snapshot()
    return out

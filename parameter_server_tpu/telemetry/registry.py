"""Process-wide metrics registry: Counter / Gauge / Histogram.

The reference scatters its counters across layers (Van send_bytes_/
recv_bytes_, HeartbeatInfo traffic, Dashboard columns, MonitorMaster
progress merging). This module is the single spine those feed: named
instruments registered once per process, each guarded by its own lock
(the record path is one lock acquire + O(1) arithmetic; histograms add
a bisect over a fixed bucket list), snapshotted as JSON-friendly dicts
and rendered as Prometheus text exposition so humans and scrapers read
the same numbers.

Registration semantics: registering a *name* twice is an error
(``DuplicateMetricError``) — two call sites silently sharing (or
shadowing) a series is how counters go wrong. Instrumentation that runs
per-instance (every Executor, every parameter store) goes through the
``ensure_*`` accessors, which return the existing instrument when the
declaration matches exactly and raise when it does not — idempotent
without masking a genuine collision.

The default registry is process-global and hangs off ``Postoffice``
(``Postoffice.instance().metrics``); ``Postoffice.reset()`` swaps in a
fresh one so tests stay hermetic.
"""

from __future__ import annotations

import bisect
import math
import re
import threading
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

_NAME_RE = re.compile(r"^[a-z][a-z0-9_]*$")

# default latency buckets: 10us .. ~100s, x~3.2 per step — wide enough
# for both a CPU-mesh unit test and a TPU step
DEFAULT_BUCKETS = (
    1e-5, 3.2e-5, 1e-4, 3.2e-4, 1e-3, 3.2e-3, 1e-2, 3.2e-2,
    1e-1, 3.2e-1, 1.0, 3.2, 10.0, 32.0, 100.0,
)


class DuplicateMetricError(ValueError):
    """A metric name was registered twice (or re-declared differently)."""


def _validate_name(name: str) -> str:
    if not _NAME_RE.match(name):
        raise ValueError(
            f"metric name {name!r} is not snake_case "
            "([a-z][a-z0-9_]*; no dots, dashes or capitals)"
        )
    return name


def _label_key(
    labelnames: Tuple[str, ...], labels: Dict[str, str]
) -> Tuple[str, ...]:
    if set(labels) != set(labelnames):
        raise ValueError(
            f"labels {sorted(labels)} do not match declared {labelnames}"
        )
    return tuple(str(labels[n]) for n in labelnames)


def _escape(value: str) -> str:
    return value.replace("\\", "\\\\").replace('"', '\\"').replace("\n", "\\n")


def _fmt(v: float) -> str:
    if v == math.inf:
        return "+Inf"
    f = float(v)
    return repr(int(f)) if f == int(f) and abs(f) < 1e15 else repr(f)


def _help_line(name: str, help: str) -> str:
    """One ``# HELP`` line, escaped per the exposition spec (backslash
    and newline only — HELP text is not quoted, so no quote escaping)."""
    return (
        f"# HELP {name} "
        + help.replace("\\", "\\\\").replace("\n", "\\n")
    )


def _histogram_lines(
    name: str, label_fmt, bounds, bucket_counts, count: int, total: float
) -> List[str]:
    """The Prometheus histogram text series (cumulative ``_bucket``
    lines, ``+Inf``, ``_sum``, ``_count``) — the ONE renderer shared by
    the live registry and the cluster aggregator
    (telemetry/aggregate.py), so the text format cannot drift between
    the two /metrics producers. ``label_fmt(extra)`` renders the series'
    label block with ``extra`` (the ``le`` pair) appended."""
    lines: List[str] = []
    cum = 0
    for bound, c in zip(bounds, bucket_counts):
        cum += c
        le = 'le="%s"' % _fmt(bound)
        lines.append(f"{name}_bucket{label_fmt(le)} {cum}")
    inf = 'le="+Inf"'
    lines.append(f"{name}_bucket{label_fmt(inf)} {count}")
    lines.append(f"{name}_sum{label_fmt('')} {_fmt(total)}")
    lines.append(f"{name}_count{label_fmt('')} {count}")
    return lines


class Instrument:
    """Base: name/help/labelnames + the per-instrument lock."""

    kind = "untyped"

    def __init__(self, name: str, help: str = "", labelnames: Sequence[str] = ()):
        self.name = _validate_name(name)
        self.help = help
        self.labelnames = tuple(labelnames)
        for ln in self.labelnames:
            _validate_name(ln)
        self._lock = threading.Lock()

    # -- declaration identity (ensure_* matching) --

    def _decl(self) -> tuple:
        return (self.kind, self.name, self.labelnames)

    def _series_lines(self) -> List[str]:
        raise NotImplementedError

    def _snapshot_values(self):
        raise NotImplementedError

    def _export_series(self) -> List[dict]:
        """Raw, JSON-able series state (telemetry/aggregate.py): unlike
        ``_snapshot_values`` this keeps histogram BUCKET COUNTS rather
        than derived percentiles, so exports from different nodes can be
        merged bucket-wise without losing information."""
        raise NotImplementedError

    def _export_decl(self) -> dict:
        out = {
            "type": self.kind,
            "help": self.help,
            "labelnames": list(self.labelnames),
            "series": self._export_series(),
        }
        return out

    def _label_str(self, key: Tuple[str, ...]) -> str:
        if not self.labelnames:
            return ""
        return ",".join(f"{n}={v}" for n, v in zip(self.labelnames, key))

    def _prom_labels(self, key: Tuple[str, ...], extra: str = "") -> str:
        parts = [
            f'{n}="{_escape(v)}"' for n, v in zip(self.labelnames, key)
        ]
        if extra:
            parts.append(extra)
        return "{" + ",".join(parts) + "}" if parts else ""


class _ScalarChild:
    """One labeled series of a Counter/Gauge — the O(1) hot-path handle."""

    __slots__ = ("_parent", "_key")

    def __init__(self, parent: "Instrument", key: Tuple[str, ...]):
        self._parent = parent
        self._key = key

    def inc(self, n: float = 1.0) -> None:
        self._parent._inc(self._key, n)

    def set(self, v: float) -> None:
        self._parent._set(self._key, v)

    def dec(self, n: float = 1.0) -> None:
        self._parent._inc(self._key, -n)

    @property
    def value(self) -> float:
        return self._parent.value(
            **dict(zip(self._parent.labelnames, self._key))
        )


class Counter(Instrument):
    """Monotone counter. ``inc`` only; negative increments are an error."""

    kind = "counter"

    def __init__(self, name, help="", labelnames=()):
        super().__init__(name, help, labelnames)
        self._values: Dict[Tuple[str, ...], float] = {}

    def labels(self, **labels: str) -> _ScalarChild:
        return _ScalarChild(self, _label_key(self.labelnames, labels))

    def inc(self, n: float = 1.0) -> None:
        if self.labelnames:
            raise ValueError(f"{self.name} requires labels(); use .labels()")
        self._inc((), n)

    def _inc(self, key: Tuple[str, ...], n: float) -> None:
        if n < 0:
            raise ValueError(f"counter {self.name} cannot decrease (n={n})")
        with self._lock:
            self._values[key] = self._values.get(key, 0.0) + n

    def value(self, **labels: str) -> float:
        key = _label_key(self.labelnames, labels)
        with self._lock:
            return self._values.get(key, 0.0)

    def _series_lines(self) -> List[str]:
        with self._lock:
            items = sorted(self._values.items())
        return [
            f"{self.name}{self._prom_labels(k)} {_fmt(v)}" for k, v in items
        ]

    def _snapshot_values(self):
        with self._lock:
            return {self._label_str(k): v for k, v in sorted(self._values.items())}

    def _export_series(self) -> List[dict]:
        with self._lock:
            items = sorted(self._values.items())
        return [
            {"labels": dict(zip(self.labelnames, k)), "value": v}
            for k, v in items
        ]


class Gauge(Counter):
    """Point-in-time value: ``set``/``inc``/``dec``."""

    kind = "gauge"

    def set(self, v: float) -> None:
        if self.labelnames:
            raise ValueError(f"{self.name} requires labels(); use .labels()")
        self._set((), v)

    def dec(self, n: float = 1.0) -> None:
        self._inc((), -n)

    def _inc(self, key: Tuple[str, ...], n: float) -> None:  # signed ok
        with self._lock:
            self._values[key] = self._values.get(key, 0.0) + n

    def _set(self, key: Tuple[str, ...], v: float) -> None:
        with self._lock:
            self._values[key] = float(v)


class _HistogramSeries:
    __slots__ = ("bucket_counts", "count", "sum", "min", "max")

    def __init__(self, nbuckets: int):
        self.bucket_counts = [0] * nbuckets  # per finite upper bound
        self.count = 0
        self.sum = 0.0
        self.min = math.inf
        self.max = -math.inf


class _HistogramChild:
    __slots__ = ("_parent", "_key")

    def __init__(self, parent: "Histogram", key: Tuple[str, ...]):
        self._parent = parent
        self._key = key

    def observe(self, v: float) -> None:
        self._parent._observe(self._key, v)


class Histogram(Instrument):
    """Cumulative histogram over fixed buckets (Prometheus ``le`` style).

    ``percentile(q)`` interpolates linearly inside the bucket holding the
    rank — exact when observations sit on bucket bounds, within one
    bucket's width otherwise.
    """

    kind = "histogram"

    def __init__(self, name, help="", labelnames=(), buckets: Optional[Sequence[float]] = None):
        super().__init__(name, help, labelnames)
        bs = tuple(sorted(float(b) for b in (buckets or DEFAULT_BUCKETS)))
        if not bs or any(b != b or b == math.inf for b in bs):
            raise ValueError(f"bad buckets for {name}: {buckets}")
        self.buckets = bs  # finite upper bounds; +Inf is implicit
        self._series: Dict[Tuple[str, ...], _HistogramSeries] = {}

    def _decl(self) -> tuple:
        return (self.kind, self.name, self.labelnames, self.buckets)

    def labels(self, **labels: str) -> _HistogramChild:
        return _HistogramChild(self, _label_key(self.labelnames, labels))

    def observe(self, v: float) -> None:
        if self.labelnames:
            raise ValueError(f"{self.name} requires labels(); use .labels()")
        self._observe((), v)

    def _observe(self, key: Tuple[str, ...], v: float) -> None:
        v = float(v)
        # first bucket whose upper bound is >= v (cumulative `le` style)
        idx = bisect.bisect_left(self.buckets, v)
        with self._lock:
            s = self._series.get(key)
            if s is None:
                s = self._series[key] = _HistogramSeries(len(self.buckets))
            if idx < len(self.buckets):
                s.bucket_counts[idx] += 1
            s.count += 1
            s.sum += v
            if v < s.min:
                s.min = v
            if v > s.max:
                s.max = v

    # -- reads --

    def count(self, **labels: str) -> int:
        key = _label_key(self.labelnames, labels)
        with self._lock:
            s = self._series.get(key)
            return s.count if s else 0

    def sum(self, **labels: str) -> float:
        key = _label_key(self.labelnames, labels)
        with self._lock:
            s = self._series.get(key)
            return s.sum if s else 0.0

    def percentile(self, q: float, **labels: str) -> float:
        """q in [0, 1]. Linear interpolation inside the owning bucket;
        observations above the last bound clamp to the observed max."""
        if not 0.0 <= q <= 1.0:
            raise ValueError(f"q={q} outside [0, 1]")
        key = _label_key(self.labelnames, labels)
        with self._lock:
            return self._percentile_locked(self._series.get(key), q)

    def _percentile_locked(self, s: Optional[_HistogramSeries], q: float) -> float:
        if s is None or s.count == 0:
            return math.nan
        rank = q * s.count
        cum = 0.0
        for i, c in enumerate(s.bucket_counts):
            if c == 0:
                continue
            # bucket 0 has no finite lower bound; the observed min is
            # the tightest honest edge
            lo = self.buckets[i - 1] if i else min(s.min, self.buckets[0])
            if cum + c >= rank:
                frac = (rank - cum) / c
                hi = self.buckets[i]
                return lo + frac * (hi - lo)
            cum += c
        return s.max  # rank lives above the last finite bound

    def _series_lines(self) -> List[str]:
        lines: List[str] = []
        with self._lock:
            items = [
                (key, list(s.bucket_counts), s.count, s.sum)
                for key, s in sorted(self._series.items())
            ]
        for key, counts, count, total in items:
            lines.extend(_histogram_lines(
                self.name,
                lambda extra, key=key: self._prom_labels(key, extra),
                self.buckets, counts, count, total,
            ))
        return lines

    def _snapshot_values(self):
        # percentiles computed from the series objects directly — the
        # formatted label string is display-only and cannot be parsed
        # back (label values may contain commas or '=')
        out = {}
        with self._lock:
            for key, s in sorted(self._series.items()):
                out[self._label_str(key)] = {
                    "count": s.count,
                    "sum": s.sum,
                    "avg": s.sum / s.count if s.count else None,
                    "min": None if s.count == 0 else s.min,
                    "max": None if s.count == 0 else s.max,
                    "p50": self._percentile_locked(s, 0.5),
                    "p90": self._percentile_locked(s, 0.9),
                    "p99": self._percentile_locked(s, 0.99),
                }
        return out

    def _export_series(self) -> List[dict]:
        out = []
        with self._lock:
            for key, s in sorted(self._series.items()):
                out.append({
                    "labels": dict(zip(self.labelnames, key)),
                    "buckets": list(s.bucket_counts),
                    "count": s.count,
                    "sum": s.sum,
                    "min": None if s.count == 0 else s.min,
                    "max": None if s.count == 0 else s.max,
                })
        return out

    def _export_decl(self) -> dict:
        out = super()._export_decl()
        out["buckets"] = list(self.buckets)
        return out


class MetricsRegistry:
    """Name → instrument, with strict and idempotent registration.

    Hot-path producers that cannot afford per-event instrument locks
    (the executor dispatch loop) buffer locally and register a
    *collector* — a zero-arg callable invoked before every
    ``snapshot()``/``render_text()`` so reads always see flushed data.
    Collectors are held by weak reference: a producer that dies simply
    stops being collected.
    """

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self._instruments: Dict[str, Instrument] = {}
        self._collectors: List[object] = []  # weakref.ref / WeakMethod
        #: wall seconds the last collect() pass took — the history
        #: plane publishes it as ``ps_registry_collect_seconds`` (meta-
        #: monitoring: who watches the watcher). Single float, atomic
        #: in CPython; None until the first pass runs.
        self.last_collect_s: Optional[float] = None

    def add_collector(self, fn) -> None:
        """Register a flush hook (bound methods are weakly referenced)."""
        import weakref

        ref = (
            weakref.WeakMethod(fn)
            if hasattr(fn, "__self__")
            else weakref.ref(fn)
        )
        with self._lock:
            self._collectors.append(ref)

    def collect(self) -> None:
        """Run every live collector; prune the dead ones."""
        import time as _time

        t0 = _time.perf_counter()
        with self._lock:
            refs = list(self._collectors)
        dead = []
        for ref in refs:
            fn = ref()
            if fn is None:
                dead.append(ref)
                continue
            try:
                fn()
            except Exception:
                pass  # a broken producer must not poison the snapshot
        self.last_collect_s = _time.perf_counter() - t0
        if dead:
            with self._lock:
                self._collectors = [
                    r for r in self._collectors if r not in dead
                ]

    # -- strict registration: duplicate name is an error --

    def counter(self, name: str, help: str = "", labelnames: Sequence[str] = ()) -> Counter:
        return self._register(Counter(name, help, labelnames))

    def gauge(self, name: str, help: str = "", labelnames: Sequence[str] = ()) -> Gauge:
        return self._register(Gauge(name, help, labelnames))

    def histogram(
        self,
        name: str,
        help: str = "",
        labelnames: Sequence[str] = (),
        buckets: Optional[Sequence[float]] = None,
    ) -> Histogram:
        return self._register(Histogram(name, help, labelnames, buckets))

    def _register(self, inst: Instrument) -> Instrument:
        with self._lock:
            if inst.name in self._instruments:
                raise DuplicateMetricError(
                    f"metric {inst.name!r} already registered"
                )
            # histogram suffixes collide with scalar series of the same
            # base name in the exposition — reserve them
            for other in self._instruments.values():
                if isinstance(other, Histogram) or isinstance(inst, Histogram):
                    h, o = (inst, other) if isinstance(inst, Histogram) else (other, inst)
                    if o.name in (f"{h.name}_bucket", f"{h.name}_sum", f"{h.name}_count"):
                        raise DuplicateMetricError(
                            f"metric {o.name!r} collides with histogram "
                            f"{h.name!r} exposition series"
                        )
            self._instruments[inst.name] = inst
            return inst

    # -- idempotent accessors for per-instance instrumentation --

    def _ensure(self, inst: Instrument) -> Instrument:
        with self._lock:
            existing = self._instruments.get(inst.name)
            if existing is not None:
                if existing._decl() != inst._decl():
                    raise DuplicateMetricError(
                        f"metric {inst.name!r} re-declared differently: "
                        f"{existing._decl()} vs {inst._decl()}"
                    )
                return existing
            self._instruments[inst.name] = inst
            return inst

    def ensure_counter(self, name, help="", labelnames=()) -> Counter:
        return self._ensure(Counter(name, help, labelnames))

    def ensure_gauge(self, name, help="", labelnames=()) -> Gauge:
        return self._ensure(Gauge(name, help, labelnames))

    def ensure_histogram(self, name, help="", labelnames=(), buckets=None) -> Histogram:
        return self._ensure(Histogram(name, help, labelnames, buckets))

    # -- reads --

    def get(self, name: str) -> Optional[Instrument]:
        with self._lock:
            return self._instruments.get(name)

    def names(self) -> List[str]:
        with self._lock:
            return sorted(self._instruments)

    def _sorted_instruments(self) -> List[Instrument]:
        with self._lock:
            return [self._instruments[n] for n in sorted(self._instruments)]

    def snapshot(self) -> Dict[str, dict]:
        """JSON-friendly view of every instrument's current series."""
        self.collect()
        out = {}
        for inst in self._sorted_instruments():
            out[inst.name] = {
                "type": inst.kind,
                "help": inst.help,
                "values": inst._snapshot_values(),
            }
        return out

    def export_state(self, collect: bool = True) -> Dict[str, dict]:
        """Raw serializable state of every instrument — the unit a node
        ships over the message plane for cluster aggregation
        (telemetry/aggregate.py). Plain dicts/lists/floats only, so the
        export survives the restricted wire unpickler and ``json.dumps``
        alike. Histograms keep raw bucket counts (mergeable); the
        derived-percentile view stays in :meth:`snapshot`.
        ``collect=False`` skips the collector pass — the history fold
        (telemetry/history.py) runs AS a collector and reading back
        through :meth:`collect` would recurse."""
        if collect:
            self.collect()
        return {
            inst.name: inst._export_decl()
            for inst in self._sorted_instruments()
        }

    def render_text(self) -> str:
        """Prometheus text exposition (one snapshot, trailing newline)."""
        self.collect()
        lines: List[str] = []
        for inst in self._sorted_instruments():
            if inst.help:
                lines.append(_help_line(inst.name, inst.help))
            lines.append(f"# TYPE {inst.name} {inst.kind}")
            lines.extend(inst._series_lines())
        return "\n".join(lines) + "\n" if lines else ""


# -- the process default registry (hung off Postoffice) --

_default_lock = threading.Lock()
_default_registry = MetricsRegistry()
_enabled = True


def default_registry() -> MetricsRegistry:
    with _default_lock:
        return _default_registry


def reset_default_registry() -> MetricsRegistry:
    """Swap in a fresh default registry (Postoffice.reset test hook).
    Instruments handed out from the old registry keep working but write
    to the orphaned registry — re-ensure after a reset."""
    global _default_registry
    with _default_lock:
        _default_registry = MetricsRegistry()
        return _default_registry


def set_enabled(flag: bool) -> bool:
    """Process-wide instrumentation switch; returns the previous value.
    Call sites cache their decision at construction time, so flip this
    BEFORE building the component under test."""
    global _enabled
    with _default_lock:
        prev = _enabled
        _enabled = bool(flag)
        return prev


def enabled() -> bool:
    with _default_lock:
        return _enabled

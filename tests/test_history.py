"""The history plane (PR 16): multi-resolution telemetry rings,
multi-window burn alerts, trend/drift detection, and the cluster-wide
range-query surface.

The contracts pinned here are the ones doc/OBSERVABILITY.md "History
plane" sells:

- typed downsampling is EXACT per kind: counters fold to per-cell rate
  deltas (reset-aware), gauges keep a last/min/max envelope, histograms
  merge bucket-count deltas so windowed percentiles come out of cells;
- fold attribution is midpoint-clamped, so a fold landing exactly on a
  cell boundary never writes a second's accrual into a ~zero-width
  open cell (the rate-explosion bug class);
- retention is BOUNDED: ring laps forget, series caps drop NEW series
  one-shot-counted under ps_history_dropped_series_total, and
  export_ring truncation is disclosed, never silent;
- the alert evaluator reads history on the STORE's clock: multi-window
  burn rules fire on sustained overload and stay quiet on a brief
  spike, trend rules gate Theil-Sen slope on monotonic concordance,
  and the meta-monitoring lag gauge walks the starvation rule through
  its states;
- the seeded leak drill: a ramping gauge drives the shipped hbm_leak
  trend rule inactive→pending→firing, and the auto-captured bundle's
  embedded history CONTAINS the ramp (asserted on bundle contents);
- per-node rings ride the metric-report frame: a silenced node's ring
  goes stale by age (disclosed, never merged into any cluster rollup)
  and a torn frame drops one shipment without poisoning the stored
  ring;
- /metrics/history answers range queries as JSON and 400s on malformed
  params instead of guessing.
"""

import json
import threading
import time
import urllib.error
import urllib.request
from urllib.parse import quote

import pytest

from parameter_server_tpu.system import faults
from parameter_server_tpu.system.postoffice import Postoffice
from parameter_server_tpu.telemetry import alerts as alerts_mod
from parameter_server_tpu.telemetry import blackbox
from parameter_server_tpu.telemetry import history as history_mod
from parameter_server_tpu.telemetry import registry as telemetry_registry
from parameter_server_tpu.telemetry.aggregate import (
    CLUSTER_NODE,
    ClusterAggregator,
)
from parameter_server_tpu.telemetry.alerts import AlertManager, AlertRule
from parameter_server_tpu.telemetry.exposition import (
    ExpositionServer,
    _parse_history_query,
)
from parameter_server_tpu.telemetry.history import (
    HistoryStore,
    monotonic_fractions,
    percentile_from_buckets,
    theil_sen,
)
from parameter_server_tpu.telemetry.registry import MetricsRegistry


@pytest.fixture(autouse=True)
def hermetic():
    Postoffice.reset()
    faults.reset()
    blackbox.reset()
    history_mod.reset_default_store()
    before = set(threading.enumerate())
    yield
    faults.reset()
    blackbox.reset()
    history_mod.reset_default_store()
    Postoffice.reset()
    deadline = time.time() + 5
    while time.time() < deadline:
        leaked = [
            t for t in set(threading.enumerate()) - before if t.is_alive()
        ]
        if not leaked:
            break
        time.sleep(0.05)
    assert not leaked, f"leaked threads: {leaked}"


def _get(url, timeout=10):
    return urllib.request.urlopen(url, timeout=timeout)


def _store(reg, t, resolutions=((1.0, 600), (10.0, 720), (60.0, 720))):
    return HistoryStore(reg, resolutions=resolutions, clock=lambda: t[0])


# ---------------------------------------------------------------------------
# estimators: Theil-Sen, concordance, bucket percentiles
# ---------------------------------------------------------------------------


class TestEstimators:
    def test_theil_sen_exact_on_linear(self):
        pts = [(float(i), 2.0 + 0.5 * i) for i in range(10)]
        assert theil_sen(pts) == pytest.approx(0.5)

    def test_theil_sen_robust_to_outlier(self):
        # one wild point must not drag the median slope (the property
        # that makes a trend rule usable on jittery gauges)
        pts = [(float(i), 1.0 + 0.1 * i) for i in range(11)]
        pts[5] = (5.0, 1e6)
        assert theil_sen(pts) == pytest.approx(0.1, rel=0.05)

    def test_theil_sen_degenerate(self):
        assert theil_sen([(0.0, 1.0)]) is None
        assert theil_sen([(1.0, 1.0), (1.0, 2.0)]) is None  # zero dt

    def test_monotonic_fractions(self):
        up, down = monotonic_fractions([1, 2, 3, 4])
        assert (up, down) == (1.0, 0.0)
        up, down = monotonic_fractions([4, 3, 2, 1])
        assert (up, down) == (0.0, 1.0)
        up, down = monotonic_fractions([1, 2, 1, 2, 1])
        assert up == pytest.approx(0.5)
        assert down == pytest.approx(0.5)

    def test_percentile_from_buckets_interpolates_and_clamps(self):
        bounds = [0.1, 1.0, 10.0]
        # 10 obs in (0, 0.1], 10 in (1, 10]
        dcounts = [10, 0, 10]
        assert percentile_from_buckets(bounds, dcounts, 20, 0.5) == (
            pytest.approx(0.1)
        )
        assert percentile_from_buckets(bounds, dcounts, 20, 0.9) == (
            pytest.approx(8.2)
        )
        # rank past every bucket clamps to the top bound, never raises
        assert percentile_from_buckets(bounds, [0, 0, 0], 0, 0.5) is None


# ---------------------------------------------------------------------------
# the store: typed downsampling, bounded retention, queries
# ---------------------------------------------------------------------------


class TestHistoryStore:
    def test_counter_rate_cells_and_midpoint_attribution(self):
        """Folds landing EXACTLY on cell boundaries — the worst case
        for open-cell width math — must yield the true rate at every
        level, not an exploded rate in a ~zero-width cell."""
        reg = MetricsRegistry()
        c = reg.counter("h_req_total", "r")
        t = [0.0]
        st = _store(reg, t)
        st.fold()  # first sight: baseline, no attribution window
        for i in range(1, 31):
            t[0] = float(i)
            c.inc(5)
            st.fold()
        r = st.query("h_req_total", window_s=20.0, resolution=1.0)
        rates = [p["rate"] for p in r["series"][0]["points"]]
        assert rates and all(x == pytest.approx(5.0) for x in rates)
        assert st.window_rate("h_req_total", None, 20.0) == (
            pytest.approx(5.0)
        )
        # the 10s level saw the same traffic, just coarser
        coarse = st.query("h_req_total", window_s=20.0, resolution=10.0)
        closed = [
            p for p in coarse["series"][0]["points"] if p["t"] + 10 <= t[0]
        ]
        assert closed and all(
            p["delta"] == pytest.approx(50.0) for p in closed
        )

    def test_counter_reset_contributes_post_reset_total(self):
        """A registry swap (process restart mid-run) must contribute
        the post-reset total as the delta — never a negative delta."""
        reg = MetricsRegistry()
        c = reg.counter("h_reset_total", "r")
        t = [0.0]
        st = _store(reg, t)
        st.fold()
        t[0] = 1.0
        c.inc(100)
        st.fold()
        reg2 = MetricsRegistry()
        c2 = reg2.counter("h_reset_total", "r")
        st.registry = reg2  # the restarted process's registry
        t[0] = 2.0
        c2.inc(3)
        st.fold()
        pts = st.query("h_reset_total", window_s=5.0, resolution=1.0)
        deltas = [p["delta"] for p in pts["series"][0]["points"]]
        assert min(deltas) >= 0.0
        assert 3.0 in [pytest.approx(d) for d in deltas]

    def test_gauge_envelope_last_min_max(self):
        reg = MetricsRegistry()
        g = reg.gauge("h_depth", "d")
        t = [2.0]
        st = _store(reg, t, resolutions=((1.0, 60), (10.0, 60)))
        g.set(9)
        st.fold()
        for tt, v in ((11.0, 3.0), (14.0, 1.0), (17.0, 5.0)):
            t[0] = tt
            g.set(v)
            st.fold()
        t[0] = 19.0
        r = st.query("h_depth", window_s=20.0, resolution=10.0)
        by_t = {p["t"]: p for p in r["series"][0]["points"]}
        cell = by_t[10.0]  # all three later folds land in [10, 20)
        assert cell["last"] == pytest.approx(5.0)
        assert cell["min"] == pytest.approx(1.0)
        assert cell["max"] == pytest.approx(5.0)

    def test_histogram_cells_and_window_quantile(self):
        reg = MetricsRegistry()
        h = reg.histogram("h_lat_seconds", "l", buckets=(0.1, 1.0, 10.0))
        t = [1.0]
        st = _store(reg, t)
        h.observe(0.05)
        st.fold()  # first sight: baseline only, no attribution window
        for _ in range(10):
            h.observe(0.05)
        for _ in range(10):
            h.observe(5.0)
        t[0] = 2.0
        st.fold()
        assert st.window_quantile(
            "h_lat_seconds", None, 10.0, 0.5
        ) == pytest.approx(0.1)
        assert st.window_quantile(
            "h_lat_seconds", None, 10.0, 0.9
        ) == pytest.approx(8.2)
        r = st.query("h_lat_seconds", window_s=10.0, q=0.9)
        pts = [p for p in r["series"][0]["points"] if p["count"] > 0]
        assert pts and pts[-1]["q"] == pytest.approx(8.2)
        assert pts[-1]["count"] == pytest.approx(20.0)

    def test_fold_floor_and_force(self):
        reg = MetricsRegistry()
        reg.counter("h_floor_total", "r")
        t = [0.0]
        st = _store(reg, t)
        assert st.fold()
        t[0] = 0.2  # inside half the 1s base resolution
        assert not st.fold()
        assert st.fold(force=True)
        assert st.snapshot()["folds"] == 2

    def test_series_caps_drop_one_shot_counted(self):
        reg = MetricsRegistry()
        c = reg.counter("h_capped_total", "r", labelnames=("k",))
        t = [0.0]
        st = HistoryStore(
            reg, resolutions=((1.0, 60),), max_series_per_metric=2,
            clock=lambda: t[0],
        )
        for k in "abcd":
            c.labels(k=k).inc()
        st.fold()
        snap = st.snapshot()
        assert snap["series_dropped"] == 2
        # re-folding the same overflow must not re-count the drops
        t[0] = 1.0
        for k in "abcd":
            c.labels(k=k).inc()
        st.fold()
        assert st.snapshot()["series_dropped"] == 2
        ex = reg.export_state()["ps_history_dropped_series_total"]
        assert [s["value"] for s in ex["series"]] == [2.0]

    def test_the_shipped_catalog_folds_whole(self):
        """One live series of every instrument the package ships
        (``instruments.install_all``) fits the store's caps: a forced
        fold tracks one series an instrument and drops none, and every
        forced fold is counted."""
        from parameter_server_tpu.telemetry.instruments import install_all

        reg = MetricsRegistry()
        catalog = install_all(reg)
        for inst in catalog.values():
            target = (
                inst.labels(**{ln: "probe" for ln in inst.labelnames})
                if inst.labelnames else inst
            )
            if inst.kind == "histogram":
                target.observe(0.001)
            elif inst.kind == "gauge":
                target.set(1.0)
            else:
                target.inc()
        st = HistoryStore(reg)
        for _ in range(3):
            assert st.fold(force=True)
        snap = st.snapshot()
        assert snap["folds"] == 3
        assert snap["series_dropped"] == 0
        live = {
            name for name, decl in reg.export_state().items()
            if decl["series"]
        }
        assert snap["series"] == len(live) >= len(catalog)

    def test_ring_laps_forget_beyond_span(self):
        reg = MetricsRegistry()
        g = reg.gauge("h_lap", "g")
        t = [0.0]
        st = _store(reg, t, resolutions=((1.0, 4), (10.0, 6)))
        g.set(1.0)
        st.fold()
        t[0] = 100.0
        g.set(2.0)
        st.fold()
        # the t=0 cells are lapped out of every level's live window
        pts = st.value_points("h_lap", None, window_s=200.0)
        assert pts and all(tc >= 50.0 for tc, _ in pts)
        assert pts[-1][1] == pytest.approx(2.0)

    def test_value_points_max_points_coarsens_level(self):
        reg = MetricsRegistry()
        g = reg.gauge("h_trendy", "g")
        t = [0.0]
        st = _store(reg, t)
        for i in range(200):
            t[0] = float(i)
            g.set(float(i))
            st.fold()
        fine = st.value_points("h_trendy", None, window_s=150.0)
        coarse = st.value_points(
            "h_trendy", None, window_s=150.0, max_points=16
        )
        assert len(fine) > 64
        assert 0 < len(coarse) <= 16
        tr = st.trend("h_trendy", None, window_s=150.0, max_points=16)
        assert tr["n"] <= 16
        assert tr["slope_per_s"] == pytest.approx(1.0, rel=0.05)
        assert tr["frac_up"] == 1.0

    def test_trend_needs_min_points(self):
        reg = MetricsRegistry()
        g = reg.gauge("h_thin", "g")
        t = [0.0]
        st = _store(reg, t)
        for i in range(3):
            t[0] = float(i)
            g.set(float(i))
            st.fold()
        assert st.trend("h_thin", None, window_s=60.0, min_points=4) is None

    def test_export_ring_shape_and_truncation_disclosed(self):
        reg = MetricsRegistry()
        c = reg.counter("h_ship_total", "r", labelnames=("k",))
        g = reg.gauge("h_ship_depth", "d")
        t = [0.0]
        st = _store(reg, t)
        for i in range(5):
            t[0] = float(i)
            for k in "abc":
                c.labels(k=k).inc()
            g.set(float(i))
            st.fold()
        ring = st.export_ring(window_s=60.0)
        assert ring["series"] >= 4 and ring["series_truncated"] == 0
        assert ring["t"] == t[0]
        assert set(ring["metrics"]) >= {"h_ship_total", "h_ship_depth"}
        decl = ring["metrics"]["h_ship_total"]
        assert decl["kind"] == "counter" and decl["series"]
        # a max_series smaller than one metric's fan-out truncates that
        # metric WHOLE and discloses the count — never half a metric
        tight = st.export_ring(window_s=60.0, max_series=2)
        assert tight["series_truncated"] > 0
        assert "h_ship_total" not in tight["metrics"]

    def test_default_store_identity_and_installed(self):
        assert history_mod.installed_store() is None
        s = history_mod.default_store()
        assert history_mod.installed_store() is s
        assert history_mod.default_store() is s
        history_mod.reset_default_store()
        assert history_mod.installed_store() is None

    def test_set_default_store_swaps_and_restores(self):
        reg = telemetry_registry.default_registry()
        mine = HistoryStore(reg, clock=lambda: 123.0).install()
        prev = history_mod.set_default_store(mine)
        try:
            assert prev is None
            assert history_mod.installed_store() is mine
            assert history_mod.default_store() is mine
        finally:
            history_mod.set_default_store(prev)


# ---------------------------------------------------------------------------
# history-backed alerting: multi-window burn, trend rules, meta-monitoring
# ---------------------------------------------------------------------------


def _transitions(events):
    return [(e.frm, e.to) for e in events]


class TestMultiWindowBurn:
    def _manager(self, rules):
        reg = MetricsRegistry()
        c = reg.counter("mw_req_total", "r")
        t = [0.0]
        st = _store(reg, t)
        mgr = AlertManager(
            rules, registry=reg, clock=lambda: t[0], history=st
        )
        return reg, c, t, mgr

    def test_sustained_overload_fires(self):
        rule = AlertRule(
            name="burn", kind="counter_rate", metric="mw_req_total",
            threshold=5.0, window_s=30, slow_window_s=300, for_s=0,
        )
        _, c, t, mgr = self._manager([rule])
        for i in range(37):  # 0..360s: 10/s the whole way
            t[0] = 10.0 * i
            if i:
                c.inc(100)
            mgr.evaluate()
        st = mgr.states()["burn"]
        assert st.state_name == "firing"
        # the conjunction reports the less-violating window's value —
        # both windows sit at the true 10/s here
        assert st.value == pytest.approx(10.0, rel=0.05)

    def test_brief_spike_stays_quiet_while_single_window_flaps(self):
        """A burst shorter than the slow window: the single-window
        rule goes pending (detection speed), the multi-window burn
        stays INACTIVE throughout (sustain proof) — the page-noise
        contract multi-window burn exists for."""
        burn = AlertRule(
            name="burn", kind="counter_rate", metric="mw_req_total",
            threshold=5.0, window_s=30, slow_window_s=300, for_s=0,
        )
        fast = AlertRule(
            name="fast", kind="counter_rate", metric="mw_req_total",
            threshold=5.0, window_s=30, for_s=40,
        )
        _, c, t, mgr = self._manager([burn, fast])
        burn_transitions = []
        mgr.add_listener(
            lambda ev: burn_transitions.append(ev) if ev.rule == "burn"
            else None
        )
        for i in range(31):  # 0..300s quiet
            t[0] = 10.0 * i
            mgr.evaluate()
        t[0] = 310.0
        c.inc(400)  # one hot 10s stretch: 13.3/s fast, 1.3/s slow
        mgr.evaluate()
        assert mgr.states()["fast"].state_name == "pending"
        assert mgr.states()["burn"].state_name == "inactive"
        for i in range(32, 36):  # quiet again: the flap clears
            t[0] = 10.0 * i
            mgr.evaluate()
        assert mgr.states()["fast"].state_name == "inactive"
        assert mgr.states()["burn"].state_name == "inactive"
        assert not burn_transitions  # never even went pending


class TestTrendRules:
    def test_monotonic_gate_keeps_noise_quiet(self):
        """Jitter around a level has nonzero Theil-Sen slope samples —
        the concordance gate is what separates noise from a leak."""
        reg = MetricsRegistry()
        g = reg.gauge("tr_level", "g")
        t = [0.0]
        st = _store(reg, t)
        rule = AlertRule(
            name="leak", kind="trend", metric="tr_level",
            threshold=1e-4, window_s=300, for_s=0, min_points=6,
            monotonic_frac=0.7,
        )
        mgr = AlertManager(
            [rule], registry=reg, clock=lambda: t[0], history=st
        )
        for i in range(20):  # saw-tooth with a slight upward bias
            t[0] = 10.0 * i
            g.set(1.0 + 0.002 * i + (0.5 if i % 2 else -0.5))
            mgr.evaluate()
        stt = mgr.states()["leak"]
        assert stt.state_name == "inactive"
        assert stt.value == pytest.approx(0.0)  # gated, not thresholded

    def test_ramp_walks_pending_then_firing(self):
        reg = MetricsRegistry()
        g = reg.gauge("tr_ramp", "g")
        t = [1000.0]
        st = _store(reg, t)
        rule = AlertRule(
            name="leak", kind="trend", metric="tr_ramp",
            threshold=1e-4, window_s=600, for_s=60, min_points=6,
            monotonic_frac=0.7,
        )
        mgr = AlertManager(
            [rule], registry=reg, clock=lambda: t[0], history=st
        )
        events = []
        mgr.add_listener(events.append)
        for i in range(12):
            t[0] = 1000.0 + 30.0 * i
            g.set(0.5 + 0.01 * i)  # +3.3e-4/s, strictly monotone
            mgr.evaluate()
        assert mgr.states()["leak"].state_name == "firing"
        walk = _transitions(events)
        assert ("inactive", "pending") in walk
        assert ("pending", "firing") in walk
        assert walk.index(("inactive", "pending")) < walk.index(
            ("pending", "firing")
        )


class TestEvaluatorStarvation:
    def test_lag_gauge_walks_starvation_rule(self):
        """Meta-monitoring: a starved evaluator tick reports its OWN
        lag (the gauge is set BEFORE sampling), so the rule fires on
        the very tick that was late — then resolves once the cadence
        recovers."""
        rule = AlertRule(
            name="starved", kind="gauge",
            metric="ps_alert_eval_lag_seconds", threshold=2.0,
            window_s=10, for_s=0, resolve_hold_s=20, severity="page",
        )
        t = [0.0]
        mgr = AlertManager([rule], clock=lambda: t[0])  # default registry
        assert mgr.period_s == pytest.approx(1.0)
        mgr.evaluate()  # first tick: no previous tick, no lag sample
        t[0] = 1.0
        mgr.evaluate()  # on-cadence: lag 0
        assert mgr.states()["starved"].state_name == "inactive"
        t[0] = 50.0  # a 49s gap on a 1s period: 48s of pure lag
        mgr.evaluate()
        st = mgr.states()["starved"]
        assert st.state_name == "firing"
        assert st.value == pytest.approx(48.0)
        t[0] = 51.0
        mgr.evaluate()  # cadence recovered
        assert mgr.states()["starved"].state_name == "resolved"
        # the jump past resolve_hold_s is ITSELF a 28s gap — the meta
        # rule re-fires on it (for_s=0: pending→firing in one tick)
        t[0] = 80.0
        mgr.evaluate()
        assert mgr.states()["starved"].state_name == "firing"
        # back on cadence: resolved again, then quiet ticks inside the
        # hold window keep it resolved until the hold elapses
        for tt in (81.0, 82.0, 83.0):
            t[0] = tt
            mgr.evaluate()
        assert mgr.states()["starved"].state_name == "resolved"
        t[0] = 83.5  # half-tick cadence: faster than the period, 0 lag
        mgr.evaluate()
        t[0] = 84.0
        mgr.evaluate()
        assert mgr.states()["starved"].state_name == "resolved"

    def test_shipped_starvation_rule_matches_catalog(self):
        rules = {r.name: r for r in alerts_mod.default_rules()}
        r = rules["alert_evaluator_starved"]
        assert r.metric == "ps_alert_eval_lag_seconds"
        assert r.kind == "gauge" and r.severity == "page"


# ---------------------------------------------------------------------------
# the seeded leak drill: ramp → trend rule fires → bundle embeds the ramp
# ---------------------------------------------------------------------------


class TestLeakDrillBundle:
    def test_hbm_ramp_fires_shipped_rule_and_bundle_contains_ramp(self):
        """End-to-end acceptance: a seeded HBM-fraction ramp drives the
        SHIPPED hbm_leak trend rule inactive→pending→firing through a
        real AuxRuntime listener, and the auto-captured diagnostic
        bundle's embedded history visibly contains the ramp — the
        evidence a human needs is IN the bundle, not in a dashboard
        that has already scrolled past."""
        from parameter_server_tpu.system.aux_runtime import AuxRuntime

        t = [1000.0]
        reg = telemetry_registry.default_registry()
        g = reg.ensure_gauge("ps_device_hbm_frac_used", "hbm frac")
        store = HistoryStore(reg, clock=lambda: t[0]).install()
        prev_store = history_mod.set_default_store(store)
        blackbox.set_min_interval(0.0)
        rule = next(
            r for r in alerts_mod.default_rules() if r.name == "hbm_leak"
        )
        mgr = AlertManager([rule], clock=lambda: t[0])
        events = []
        mgr.add_listener(events.append)
        aux = AuxRuntime(heartbeat_timeout=30.0)
        try:
            aux.set_alerts(mgr)
            for i in range(12):
                t[0] = 1000.0 + 30.0 * i
                g.set(0.50 + 0.01 * i)  # +3.3e-4/s >> the 1e-4 threshold
                mgr.evaluate()
            walk = _transitions(events)
            assert ("inactive", "pending") in walk
            assert ("pending", "firing") in walk
            assert mgr.states()["hbm_leak"].state_name == "firing"

            b = blackbox.last_bundle()
            assert b is not None, "firing transition captured no bundle"
            assert b["trigger"]["kind"] == "alert"
            assert b["trigger"]["detail"] == "hbm_leak"
            hist = b["history"]
            assert hist is not None and "history" not in (
                b.get("section_errors") or {}
            )
            decl = hist["metrics"]["ps_device_hbm_frac_used"]
            assert decl["kind"] == "gauge"
            lasts = [p["last"] for p in decl["series"][0]["points"]]
            # the ramp is IN the bundle: monotone and spanning the seed
            assert len(lasts) >= 6
            assert lasts == sorted(lasts)
            assert lasts[-1] - lasts[0] >= 0.05
            # the bundle's alert section caught the breach state too
            assert b["alerts"]["states"]["hbm_leak"]["state_name"] == (
                "firing"
            )
            summary = blackbox.summarize_bundle(b)
            assert summary["history_series"] >= 1
            assert summary["history_window_s"] == pytest.approx(3600.0)
        finally:
            aux.stop()
            history_mod.set_default_store(prev_store)


# ---------------------------------------------------------------------------
# cluster history: staleness, no rollup, torn frames
# ---------------------------------------------------------------------------


def _mini_ring(value=1.0, t0=100.0):
    reg = MetricsRegistry()
    g = reg.gauge("ring_gauge", "g")
    t = [t0]
    st = _store(reg, t)
    g.set(value)
    st.fold()
    return st.export_ring(window_s=60.0)


class TestClusterHistory:
    def test_ages_staleness_and_no_cluster_rollup(self):
        tq = [0.0]
        agg = ClusterAggregator(stale_after_s=5.0, clock=lambda: tq[0])
        agg.update_history("S0", _mini_ring(1.0))
        tq[0] = 7.0
        agg.update_history("S1", _mini_ring(2.0))
        tq[0] = 10.0
        ages = agg.history_ages()
        assert ages["S0"] == pytest.approx(10.0)
        assert ages["S1"] == pytest.approx(3.0)
        hq = agg.history_query("ring_gauge")
        assert hq["nodes"]["S0"]["stale"] is True
        assert hq["nodes"]["S1"]["stale"] is False
        # the stale ring is still DISCLOSED — it is evidence
        assert hq["nodes"]["S0"]["series"]
        # histories never merge into any cluster rollup
        assert CLUSTER_NODE not in hq["nodes"]
        snap = agg.history_snapshot()
        assert snap["nodes"]["S0"]["stale"] is True
        assert snap["stale_after_s"] == pytest.approx(5.0)

    def test_window_filter_trims_points(self):
        tq = [0.0]
        agg = ClusterAggregator(stale_after_s=5.0, clock=lambda: tq[0])
        reg = MetricsRegistry()
        g = reg.gauge("ring_gauge", "g")
        t = [100.0]
        st = _store(reg, t)
        for i in range(5):
            t[0] = 100.0 + 30.0 * i
            g.set(float(i))
            st.fold()
        agg.update_history("S0", st.export_ring(window_s=600.0))
        hq = agg.history_query("ring_gauge", window_s=60.0)
        pts = hq["nodes"]["S0"]["series"][0]["points"]
        assert pts and all(p["t"] >= 220.0 - 60.0 for p in pts)

    def test_torn_frame_keeps_previous_ring(self):
        """A report frame without a well-formed ring loses THAT
        shipment only: the stored ring is never replaced with garbage
        — it ages into staleness instead."""
        from parameter_server_tpu.system.aux_runtime import AuxRuntime

        aux = AuxRuntime(heartbeat_timeout=30.0)
        try:
            good = _mini_ring(3.0)
            aux.handle_metrics_message(
                {"node": "S9", "metrics": {}, "history": good}
            )
            before_t = dict(aux.cluster._history_t)
            # torn frames: history missing, not a dict, missing metrics
            for bad in (None, "garbage", {"t": 1.0, "series": 0}):
                payload = {"node": "S9", "metrics": {}}
                if bad is not None:
                    payload["history"] = bad
                aux.handle_metrics_message(payload)
            hq = aux.cluster.history_query("ring_gauge")
            assert hq["nodes"]["S9"]["series"]  # the good ring survived
            assert dict(aux.cluster._history_t) == before_t
        finally:
            aux.stop()

    def test_silenced_node_history_goes_stale(self):
        """The heartbeat.report silence fault: the silenced node ships
        NO history (a crashed node reports nothing), so its ring age
        grows past stale_after_s while live nodes keep refreshing."""
        from parameter_server_tpu.system.aux_runtime import AuxRuntime

        aux = AuxRuntime(heartbeat_timeout=30.0, stale_after_s=0.2)
        try:
            aux.register("S0")
            aux.register("S1")
            assert aux.report_all(wire=False) >= 2
            snap = aux.cluster.history_snapshot()
            assert {"S0", "S1"} <= set(snap["nodes"])
            faults.arm("heartbeat.report", kind="silence", match="S0")
            time.sleep(0.3)
            aux.report_all(wire=False)
            ages = aux.cluster.history_ages()
            assert ages["S0"] > 0.2 > ages["S1"]
            hq = aux.cluster.history_query("ps_node_rss_mb")
            assert hq["nodes"]["S0"]["stale"] is True
            assert hq["nodes"]["S1"]["stale"] is False
            assert CLUSTER_NODE not in hq["nodes"]
        finally:
            aux.stop()


# ---------------------------------------------------------------------------
# /metrics/history: the range-query endpoint
# ---------------------------------------------------------------------------


class TestHistoryEndpoint:
    def test_parse_history_query(self):
        p, err = _parse_history_query(
            "/metrics/history?name=m&window=60&resolution=10&q=0.5"
            "&labels=" + quote('{"k": "v"}')
        )
        assert err is None
        assert p == {
            "name": "m", "window_s": 60.0, "resolution": 10.0,
            "q": 0.5, "labels": {"k": "v"},
        }
        for path, frag in (
            ("/metrics/history", "missing required"),
            ("/metrics/history?name=m&window=abc", "numeric"),
            ("/metrics/history?name=m&window=-5", "window must be > 0"),
            ("/metrics/history?name=m&labels=notjson", "JSON object"),
            ("/metrics/history?name=m&labels=" + quote("[1]"),
             "JSON object"),
        ):
            p, err = _parse_history_query(path)
            assert p is None and frag in err, (path, err)

    def test_route_answers_echoes_and_400s(self):
        seen = []

        def history_fn(params):
            seen.append(params)
            return {"query": params, "local": {"series": []}}

        srv = ExpositionServer(
            lambda: "# empty\n", history_fn=history_fn
        ).start()
        try:
            body = json.load(
                _get(f"{srv.url}/metrics/history?name=ps_x&window=60")
            )
            assert body["query"]["name"] == "ps_x"
            assert body["query"]["window_s"] == 60.0
            assert seen and seen[-1]["name"] == "ps_x"
            with pytest.raises(urllib.error.HTTPError) as ei:
                _get(f"{srv.url}/metrics/history?window=60")
            assert ei.value.code == 400
            assert "name" in ei.value.read().decode()
            with pytest.raises(urllib.error.HTTPError) as ei:
                _get(f"{srv.url}/metrics/history?name=m&window=bogus")
            assert ei.value.code == 400
            # the root index advertises the route
            root = _get(srv.url).read().decode()
            assert "/metrics/history" in root
        finally:
            srv.close()

    def test_404_without_history_source(self):
        srv = ExpositionServer(lambda: "# empty\n").start()
        try:
            with pytest.raises(urllib.error.HTTPError) as ei:
                _get(f"{srv.url}/metrics/history?name=m")
            assert ei.value.code == 404
        finally:
            srv.close()

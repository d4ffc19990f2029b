"""Device truth plane (telemetry/device.py): the contracts
doc/OBSERVABILITY.md "Device truth plane" sells.

- the compiled-function inventory is a DROP-IN wrapper: identical
  outputs, donation semantics preserved, tracer-stage calls pass
  through, unreadable signatures fall back to the plain jit path —
  and two builders sharing a name with different closures NEVER get
  each other's executable (the aval-only-key bug this module's cache
  key regression-tests);
- recompiles are counted per name (new avals or statics), zero on a
  steady-shape stream after the warmup mark — including through the
  real kv_ops data plane;
- the runtime donation verifier counts a deliberately non-donatable
  jit (shape-mismatched alias) and stays silent on a healthy one;
- roofline sampling turns measured dispatch wall time + cost analysis
  into achieved GB/s (+ frac-of-peak only when the peak tables know
  the chip — a CPU host reports rates, never a faked frac);
- the HBM monitor collects live-buffer totals with a monotone
  high-water mark on every backend;
- the recompile-storm alert rule (configs/alerts/default.json) walks
  inactive→pending→firing on a shape-churning jit and resolves when
  shapes steady;
- the peaks table: a listed chip has both peaks, an unlisted one is
  an error, a CPU resolves to None, and the benchmark's own copy
  (``chipbench/peaks.json``) agrees;
- one live /metrics scrape shows the ``ps_device_*`` families
  node-labeled with the recompile-storm rule evaluating.
"""

import json
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from parameter_server_tpu.system.postoffice import Postoffice
from parameter_server_tpu.telemetry import device as device_mod
from parameter_server_tpu.telemetry import registry as telemetry_registry


@pytest.fixture()
def fresh_plane():
    """Hermetic inventory + registry per test (the process-global
    inventory is shared with every other module's wrap points)."""
    Postoffice.reset()
    device_mod.reset()
    yield device_mod.inventory()
    device_mod.reset()
    Postoffice.reset()


def _recompiles_total(name: str) -> float:
    reg = telemetry_registry.default_registry()
    decl = reg.export_state().get("ps_device_recompiles_total")
    if decl is None:
        return 0.0
    return sum(
        s["value"] for s in decl["series"] if s["labels"].get("fn") == name
    )


class TestInventory:
    def test_wrapper_parity_and_compile_accounting(self, fresh_plane):
        f = jax.jit(lambda x, y: x * 2.0 + y)
        w = device_mod.instrument("t_parity", f)
        x = jnp.arange(32, dtype=jnp.float32)
        y = jnp.ones(32, jnp.float32)
        np.testing.assert_array_equal(np.asarray(w(x, y)), np.asarray(f(x, y)))
        w(x, y)  # same avals: no new compile
        rec = fresh_plane.snapshot()["functions"]["t_parity"]
        assert rec["compiles"] == 1
        assert rec["recompiles"] == 0
        assert rec["calls"] == 2
        # the XLA analyses landed with the compile
        assert rec["cost"]["flops"] > 0
        assert rec["cost"]["bytes_accessed"] > 0
        assert rec["memory"]["output_bytes"] > 0

    def test_recompile_on_new_avals_counted_and_metered(self, fresh_plane):
        w = device_mod.instrument("t_recompile", jax.jit(lambda x: x + 1))
        w(jnp.ones(8))
        assert _recompiles_total("t_recompile") == 0
        w(jnp.ones(16))  # new shape → re-specialization
        w(jnp.ones(16))  # cached: no growth
        rec = fresh_plane.snapshot()["functions"]["t_recompile"]
        assert rec["compiles"] == 2
        assert rec["recompiles"] == 1
        assert _recompiles_total("t_recompile") == 1

    def test_static_change_is_a_recompile(self, fresh_plane):
        import functools

        f = functools.partial(jax.jit, static_argnames=("k",))(
            lambda x, k: x * k
        )
        w = device_mod.instrument("t_static", f, static_argnames=("k",))
        x = jnp.ones(8)
        assert float(np.asarray(w(x, k=3))[0]) == 3.0
        assert float(np.asarray(w(x, k=5))[0]) == 5.0
        rec = fresh_plane.snapshot()["functions"]["t_static"]
        assert rec["compiles"] == 2 and rec["recompiles"] == 1

    def test_tracer_stage_calls_pass_through(self, fresh_plane):
        w = device_mod.instrument("t_traced", jax.jit(lambda x: x * 3.0))

        @jax.jit
        def outer(a):
            return w(a) + 1.0

        assert float(np.asarray(outer(jnp.ones(4)))[0]) == 4.0
        # the enclosing jit owned the compile: no inventory entry
        assert "t_traced" not in fresh_plane.snapshot()["functions"]

    def test_unlowerable_callable_falls_back(self, fresh_plane):
        # a plain python callable has no .lower: the wrapper must
        # route to it untouched and count the dispatch fallback
        w = device_mod.instrument("t_fallback", lambda x: x + 1)
        assert w(1) == 2
        rec = fresh_plane.snapshot()["functions"]["t_fallback"]
        assert rec["dispatch_fallbacks"] == 1

    def test_same_name_different_closures_not_cross_served(self, fresh_plane):
        """REGRESSION (caught live by test_async_sgd's noise tests):
        two builders share an inventory name and avals but close over
        different constants — any SHARED aval-keyed executable cache
        hands the second the FIRST one's compiled program (the cache
        must be per-wrapper)."""
        def build(c):
            return device_mod.instrument(
                "t_closure", jax.jit(lambda x: x + c)
            )

        a, b = build(1.0), build(100.0)
        x = jnp.zeros(8)
        assert float(np.asarray(a(x))[0]) == 1.0
        assert float(np.asarray(b(x))[0]) == 100.0  # not 1.0
        # and the second build's compile is visible as a recompile
        rec = fresh_plane.snapshot()["functions"]["t_closure"]
        assert rec["compiles"] == 2

    def test_default_spelling_variants_are_one_compile(self, fresh_plane):
        """jit's own cache treats f(x), f(x, seed_default) and
        f(x, k=<declared default>) as ONE entry; the wrapper must
        normalize the same way or an omitted-vs-explicit default
        double-compiles and ticks a spurious recompile — breaking the
        zero-post-warmup contract (and the storm page rule) on a
        healthy run."""
        import functools

        f = functools.partial(jax.jit, static_argnames=("k",))(
            lambda x, seed=0, *, k=2: x * k + seed
        )
        w = device_mod.instrument("t_spelling", f, static_argnames=("k",))
        x = jnp.ones(8)
        w(x)                # all defaults omitted
        w(x, 0, k=2)        # same call, spelled out
        w(x, seed=0, k=2)   # same call, keyword spelling
        rec = fresh_plane.snapshot()["functions"]["t_spelling"]
        assert rec["compiles"] == 1 and rec["recompiles"] == 0
        assert rec.get("dispatch_fallbacks", 0) == 0

    def test_distinct_shardings_get_distinct_entries(
        self, fresh_plane, mesh8
    ):
        """Sharding is part of the cache key: a Compiled is specialized
        to the shardings it was lowered with, so two same-aval call
        patterns with different shardings need their own entries — a
        shared entry would make the second pattern raise-and-fall-back
        on EVERY dispatch (per-call exception on the hot data plane,
        chip accounting silently skipped)."""
        from jax.sharding import NamedSharding, PartitionSpec as P

        w = device_mod.instrument("t_shard", jax.jit(lambda t: t * 2.0))
        base = np.arange(8 * 128, dtype=np.float32).reshape(8, 128)
        x_sh = jax.device_put(base, NamedSharding(mesh8, P("server")))
        x_rep = jax.device_put(base, NamedSharding(mesh8, P()))
        a = np.asarray(w(x_sh))
        b = np.asarray(w(x_rep))
        np.testing.assert_array_equal(a, b)
        rec = fresh_plane.snapshot()["functions"]["t_shard"]
        assert rec["compiles"] == 2
        assert rec.get("dispatch_fallbacks", 0) == 0

    def test_donated_input_consumed_like_plain_jit(self, fresh_plane):
        w = device_mod.instrument(
            "t_donate", jax.jit(lambda x: x + 1, donate_argnums=(0,)),
            donate_argnums=(0,),
        )
        x = jnp.ones(128)
        out = w(x)
        assert float(np.asarray(out)[0]) == 2.0
        assert x.is_deleted()  # the buffer was really donated


class TestDonationVerifier:
    def test_shape_mismatched_alias_counted(self, fresh_plane):
        """Satellite: the runtime verifier's discriminating case — a
        deliberately non-donatable jit (the donated [N] input cannot
        alias the scalar output) must count a fallback; the static
        donation lint cannot see this, only the compiled program
        can."""
        w = device_mod.instrument(
            "t_bad_donate",
            jax.jit(lambda x: x.sum(), donate_argnums=(0,)),
            donate_argnums=(0,),
        )
        w(jnp.ones((8, 128)))
        snap = fresh_plane.snapshot()
        assert snap["functions"]["t_bad_donate"]["donation_fallbacks"] == 1
        assert snap["donation_fallbacks_total"] == 1
        reg = telemetry_registry.default_registry()
        decl = reg.export_state()["ps_device_donation_fallbacks_total"]
        assert sum(
            s["value"] for s in decl["series"]
            if s["labels"].get("fn") == "t_bad_donate"
        ) == 1

    def test_healthy_donation_silent(self, fresh_plane):
        w = device_mod.instrument(
            "t_good_donate",
            jax.jit(lambda x: x * 2.0, donate_argnums=(0,)),
            donate_argnums=(0,),
        )
        w(jnp.ones((8, 128)))
        rec = fresh_plane.snapshot()["functions"]["t_good_donate"]
        assert rec["donation_fallbacks"] == 0
        # and the analysis shows the aliased bytes
        assert rec["memory"]["alias_bytes"] == 8 * 128 * 4
        assert rec["donated_bytes"] == 8 * 128 * 4


class TestRoofline:
    def test_sampling_sets_gauges_no_faked_frac_on_cpu(self, fresh_plane):
        prev = device_mod.set_sampling(1)
        try:
            w = device_mod.instrument("t_roof", jax.jit(lambda x: x @ x))
            w(jnp.ones((64, 64)))
        finally:
            device_mod.set_sampling(prev)
        rec = fresh_plane.snapshot()["functions"]["t_roof"]
        tl = rec["roofline"]
        assert tl["wall_ms"] > 0
        assert tl["achieved_gb_s"] > 0
        assert tl["achieved_tflops"] >= 0
        # CPU host: the peak tables do not know this kind — no frac
        assert "frac_of_hbm_peak" not in tl
        assert "mfu" not in tl
        reg = telemetry_registry.default_registry()
        export = reg.export_state()
        gb = export["ps_device_kernel_gb_s"]
        assert any(
            s["labels"].get("fn") == "t_roof" and s["value"] > 0
            for s in gb["series"]
        )
        assert not export.get("ps_device_roofline_frac", {}).get("series")

    def test_sampling_off_by_default(self, fresh_plane):
        w = device_mod.instrument("t_unsampled", jax.jit(lambda x: x + 1))
        w(jnp.ones(8))
        assert "roofline" not in fresh_plane.snapshot()["functions"][
            "t_unsampled"
        ]


class TestHbmMonitor:
    def test_live_buffer_accounting_and_high_water(self, fresh_plane):
        mon = device_mod.install_hbm_monitor()
        assert mon is not None
        big = jax.device_put(np.zeros(1 << 16, np.float32))
        snap1 = mon.snapshot()
        assert snap1["live_buffer_bytes"] >= big.nbytes
        hw1 = snap1["live_buffer_high_water_bytes"]
        del big
        snap2 = mon.snapshot()
        # high water is monotone even after buffers die
        assert snap2["live_buffer_high_water_bytes"] >= hw1
        reg = telemetry_registry.default_registry()
        export = reg.export_state()
        assert export["ps_device_live_buffer_bytes"]["series"]
        assert export["ps_device_live_buffer_high_water_bytes"]["series"]

    def test_snapshot_shape(self, fresh_plane):
        device_mod.install_hbm_monitor()
        snap = device_mod.snapshot()
        assert "functions" in snap
        assert "hbm" in snap and "live_buffer_bytes" in snap["hbm"]
        assert snap["backend"] == "cpu"
        # the no-faked-peak rule rides into the snapshot
        assert snap["hbm_peak_gb_s"] is None
        assert snap["flops_peak_tflops"] is None


class TestSteadyState:
    def test_zero_recompiles_post_warmup_through_kv_data_plane(
        self, fresh_plane, mesh8
    ):
        """Satellite: the steady-state contract on the REAL data plane
        — after warmup, a fixed-shape push/pull stream through the
        instrumented kv_ops entry points must re-specialize nothing."""
        from parameter_server_tpu.ops import kv_ops
        from parameter_server_tpu.parallel import mesh as meshlib

        rng = np.random.default_rng(0)
        p, n, k = 1 << 10, 1 << 7, 4
        tbl = jax.device_put(
            jnp.zeros((p, k), jnp.float32), meshlib.table_sharding(mesh8)
        )
        idx = jax.device_put(rng.integers(0, p, n).astype(np.int32))
        vals = jax.device_put(rng.normal(size=(n, k)).astype(np.float32))
        # warmup: compile both programs
        tbl2 = kv_ops.push(tbl, idx, vals, mesh=mesh8, batch_sharded=False)
        kv_ops.pull(tbl2, idx, mesh=mesh8, batch_sharded=False)
        device_mod.mark_warmup()
        for _ in range(4):
            tbl2 = kv_ops.push(
                tbl, idx, vals, mesh=mesh8, batch_sharded=False
            )
            kv_ops.pull(tbl2, idx, mesh=mesh8, batch_sharded=False)
        snap = fresh_plane.snapshot()
        assert snap["recompiles_post_warmup"] == 0
        assert snap["functions"]["kv_push"]["compiles"] == 1
        assert snap["functions"]["kv_pull"]["compiles"] == 1

    def test_post_warmup_counts_churn(self, fresh_plane):
        w = device_mod.instrument("t_churn", jax.jit(lambda x: x + 1))
        w(jnp.ones(8))
        device_mod.mark_warmup()
        assert fresh_plane.snapshot()["recompiles_post_warmup"] == 0
        w(jnp.ones(9))
        w(jnp.ones(10))
        assert fresh_plane.snapshot()["recompiles_post_warmup"] == 2


class TestRecompileStormAlert:
    def test_storm_rule_fires_and_resolves(self, fresh_plane):
        """Satellite: the shipped device_recompile_storm rule
        (configs/alerts/default.json) driven by a real shape-churning
        jit against the live registry: inactive → pending → firing
        while shapes churn, resolved once they steady."""
        from parameter_server_tpu.telemetry.alerts import (
            AlertManager,
            default_rules,
        )

        rule = next(
            r for r in default_rules()
            if r.name == "device_recompile_storm"
        )
        assert rule.kind == "counter_rate"
        assert rule.metric == "ps_device_recompiles_total"
        clock = [0.0]
        mgr = AlertManager([rule], clock=lambda: clock[0])
        w = device_mod.instrument("t_storm", jax.jit(lambda x: x + 1))
        w(jnp.ones(4))  # first compile: not a recompile
        mgr.evaluate()
        assert mgr.states()["device_recompile_storm"].state_name == "inactive"
        # churn: 8 new shapes in 10s → 0.8/s > the 0.2/s threshold
        for i in range(8):
            w(jnp.ones(5 + i))
        clock[0] = 10.0
        mgr.evaluate()
        assert mgr.states()["device_recompile_storm"].state_name == "pending"
        clock[0] = 10.0 + rule.for_s + 1.0
        mgr.evaluate()
        assert mgr.states()["device_recompile_storm"].state_name == "firing"
        # steady shapes: the windowed rate decays to zero → resolved
        clock[0] += rule.window_s + 5.0
        for _ in range(4):
            w(jnp.ones(4))
        mgr.evaluate()
        assert mgr.states()["device_recompile_storm"].state_name == "resolved"

    def test_hbm_rule_parses(self):
        from parameter_server_tpu.telemetry.alerts import default_rules

        rule = next(
            r for r in default_rules() if r.name == "device_hbm_high_water"
        )
        assert rule.kind == "gauge"
        assert rule.metric == "ps_device_hbm_frac_used"


# -- the peaks table ---------------------------------------------------------


class TestPeaksTable:
    """The package's one peaks table (telemetry/device.py), which the
    roofline gauges and ``chip_smoke.py``'s device line read."""

    @pytest.mark.parametrize("kind,hbm,flops", [
        ("TPU v5 lite", 819.0, 197.0),  # what a v5e reports
        ("TPU v5e", 819.0, 197.0),
        ("TPU v4", 1228.0, 275.0),
    ])
    def test_a_listed_chip_has_both_peaks(self, kind, hbm, flops):
        assert device_mod.device_peaks(kind) == {
            "hbm_gb_s": hbm, "bf16_tflops": flops,
        }
        assert device_mod._peaks(kind) == (hbm, flops)

    def test_an_unlisted_chip_is_an_error_and_a_cpu_has_no_peak(self):
        with pytest.raises(KeyError, match="peaks table"):
            device_mod.device_peaks("TPU v9 ultra")
        ident = device_mod.device_identity()
        assert ident["platform"] == "cpu" and ident["count"] == 8
        # no faked peak: the gauges' lookup resolves a CPU to None
        assert device_mod._peaks(ident["kind"]) == (None, None)

    def test_the_benchmarks_own_copy_agrees(self):
        """``chipbench/peaks.json`` is the benchmark's copy (it imports
        nothing of the program): a chip in both has the same peaks."""
        path = os.path.join(
            os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
            "chipbench", "peaks.json",
        )
        with open(path) as f:
            copy = json.load(f)["by_device_kind"]
        assert copy
        for kind, peaks in copy.items():
            mine = device_mod.device_peaks(kind)
            assert peaks["hbm_bytes_per_s"] == mine["hbm_gb_s"] * 1e9
            assert peaks["bf16_flops_per_s"] == mine["bf16_tflops"] * 1e12


# -- one live scrape ---------------------------------------------------------


class TestClusterScrape:
    def test_scrape_shows_device_families_node_labeled_and_storm_rule(
        self, fresh_plane, mesh8
    ):
        """ACCEPTANCE: one live /metrics scrape shows the
        ``ps_device_*`` families node-labeled through the PR 10
        aggregator, and the recompile-storm rule is evaluating (its
        ``ps_alert_state`` series exists on the same scrape)."""
        import time
        import urllib.request

        from parameter_server_tpu.telemetry.exposition import (
            close_cluster,
            expose_cluster,
        )

        po = Postoffice.instance().start(num_data=4, num_server=2)
        srv = expose_cluster(po, port=0, metrics_interval=0.05)
        try:
            w = device_mod.instrument("t_scrape", jax.jit(lambda x: x + 1))
            w(jnp.ones(4))
            w(jnp.ones(5))  # one recompile on the wire
            def storm_lines(text):
                return [
                    ln for ln in text.splitlines()
                    if ln.startswith("ps_device_recompiles_total{")
                    and 'fn="t_scrape"' in ln
                ]

            def rule_live(text):
                return any(
                    ln.startswith("ps_alert_state{")
                    and 'rule="device_recompile_storm"' in ln
                    for ln in text.splitlines()
                )

            deadline = time.time() + 10
            txt = ""
            while time.time() < deadline:
                time.sleep(0.1)
                txt = urllib.request.urlopen(
                    f"{srv.url}/metrics", timeout=10
                ).read().decode()
                if storm_lines(txt) and rule_live(txt):
                    break
            lines = storm_lines(txt)
            assert lines, "ps_device_recompiles_total never reached /metrics"
            assert any('node="' in ln for ln in lines)  # node-labeled
            assert any(ln.rstrip().endswith(" 1") for ln in lines)
            assert rule_live(txt), "recompile-storm rule not evaluating live"
        finally:
            close_cluster(srv)
            Postoffice.reset()

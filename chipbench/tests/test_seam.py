"""The seam between ``run.py`` and a configuration's application (PR 28).

A stand-in application of a few lines goes from files to a last line,
untraced and traced, with no edit to ``run.py``, ``lastline.py`` or any
reader; and the two Criteo cells rehearse through the same seam. CPU
only: ``python -m pytest chipbench/tests -q`` from the root of the repo.
"""

from __future__ import annotations

import argparse
import importlib
import json
import os
import shutil
import subprocess
import sys
import types

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH_DIR = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH_DIR)
sys.path.insert(0, ROOT)

from chipbench import hostspans, lastline  # noqa: E402

# read what only the linear trainer has: BENCHMARK.json lists their cells
LINEAR_ONLY = {
    "ingest_host_s_per_mex", "wire_bytes_per_example", "update_share",
    "idle_in_dispatch_share", "idle_waiting_ingest_share",
    "reader_wait_share", "reader_serial_s_per_mex", "collect_host_ms",
}
# read the device trace alone: every cell reports them
EVERY_CELL = {
    "step_device_ms", "device_idle_share", "idle_in_program_share",
    "idle_unattributed_share",
}
CELL = "standin.loop"
BATCH, WIDTH = 256, 32


class StandIn:
    """One jitted least-squares step a launch; an example is a row."""

    def __init__(self, run):
        self.run = run

    def make_data(self):
        import numpy as np

        rng = np.random.default_rng(self.run.seed)
        self.x = rng.normal(size=(BATCH, WIDTH)).astype(np.float32)
        self.y = self.x @ rng.normal(size=WIDTH).astype(np.float32)

    def build(self, win):
        import jax
        import jax.numpy as jnp

        def step(w, x, y):
            err = x @ w - y
            return w - 0.1 * (x.T @ err) / len(y), jnp.sum(err * err)

        self.win, self.step = win, jax.jit(step)
        self.w = jnp.zeros(WIDTH, jnp.float32)

    def launch(self):
        row = self.win.submitted()
        self.w, loss = self.step(self.w, self.x, self.y)
        self.win.collected(row, examples=BATCH, objective=float(loss))

    def warm_up(self):
        for _ in range(self.run.mix["warmup_launches"]):
            self.launch()

    def feed(self):
        while not self.win.expired():
            self.launch()

    def window_note(self):
        return {"rows_per_launch": BATCH}

    def notes(self, win):
        self.run.note("standin", width=WIDTH)

    def checks(self, win, warm, rows, check):
        # the plain reference: the first launch's loss at w = 0 is |y|^2
        want = float((self.y.astype("float64") ** 2).sum())
        gap = abs(warm[0]["objective"] - want) / want
        check("first_loss_matches_numpy", gap <= 1e-5, value=gap, limit=1e-5)

    def ctx(self):
        return {"standin_width": WIDTH}

    def stop(self):
        self.stopped = True


@pytest.fixture()
def harness(tmp_path, monkeypatch):
    """``run.py`` as a module, looking for its files under a root of the
    test's own that holds the stand-in's configuration and mix beside a
    copy of the committed metric files."""
    run = importlib.import_module("chipbench.run")
    bench_dir = tmp_path / "chipbench"
    (bench_dir / "traffic").mkdir(parents=True)
    (bench_dir / "configs").mkdir()
    shutil.copytree(
        os.path.join(BENCH_DIR, "metrics"), bench_dir / "metrics"
    )
    (bench_dir / "configs" / "standin.json").write_text(json.dumps({
        "name": "standin", "app": "standin", "rehearsal": {},
    }))
    (bench_dir / "traffic" / "loop.json").write_text(json.dumps({
        "name": "loop", "warmup_launches": 2, "trace_after_launches": 2,
        "trace_seconds": 0.2, "rehearsal": {},
    }))
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    bench["configs"] = [{
        "name": "standin", "source": "this test",
        "file": "chipbench/configs/standin.json", "reduced": [],
        "why": "a second runner",
    }]
    bench["workloads"] = [{
        "name": CELL, "config": "standin", "traffic": "loop", "chips": 1,
        "why": "one jitted step a launch",
    }]
    module = types.ModuleType("chipbench.apps.standin")
    module.Runner = StandIn
    monkeypatch.setitem(sys.modules, "chipbench.apps.standin", module)
    monkeypatch.setattr(run, "ROOT", str(tmp_path))
    monkeypatch.setattr(run, "CACHE", str(tmp_path / "cache"))
    monkeypatch.setattr(hostspans, "CACHE_TRACES", str(tmp_path / "cache"))
    # prepare() sets these for the process it runs in: undone afterwards
    for name in ("JAX_PLATFORMS", "XLA_FLAGS", "JAX_COMPILATION_CACHE_DIR"):
        monkeypatch.setenv(name, os.environ.get(name, ""))
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "jax"))
    return run, bench


def last_line(capsys) -> dict:
    out = capsys.readouterr().out.strip().splitlines()
    return json.loads(out[-1])


@pytest.mark.parametrize("traced", [0, 1])
def test_a_second_runner_goes_from_files_to_a_last_line(
    harness, capsys, traced
):
    run, bench = harness
    args = argparse.Namespace(
        workload=CELL, seed=2147483659, seconds=1.0, trace=traced,
        rehearsal=True,
    )
    assert run.run_cell(args, bench) == 0
    line = last_line(capsys)
    assert lastline.faults(line, bench, CELL, bool(traced)) == []
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] >= 10
    assert list(line)[-1] == "checks"
    assert line["checks"]["first_loss_matches_numpy"]["ok"] is True
    assert "losses_finite" in line["checks"]  # the harness's own
    want = {"examples_per_s", "setup_s"} | (EVERY_CELL if traced else set())
    assert set(line["metrics"]) == want
    assert not set(line["metrics"]) & LINEAR_ONLY
    if traced:
        assert {name for name, _ in line["breakdown"]["idle_gaps"]} <= {
            "in_program", "unattributed", "dispatch", "ingest",
        }


def test_a_step_that_returns_its_state_unchanged_is_not_correct(
    harness, capsys, monkeypatch
):
    """The timed path broken underneath: the compiled step of the dense
    cell runs, and its new state is dropped. The table stays at zero, so
    the first 24 minibatches read log 2 where the reference learns."""
    from chipbench.apps import linear

    class Frozen(linear.Runner):
        def build(self, win):
            super().build(win)
            get_step = self.worker._get_step

            def frozen(prepped, with_aux):
                step = get_step(prepped, with_aux)

                def keep_state(state, pull, prepped, seed, donate_ok):
                    return state, step(
                        state, pull, prepped, seed, donate_ok=donate_ok
                    )[1]

                return keep_state

            self.worker._get_step = frozen

    run, bench = harness
    module = types.ModuleType("chipbench.apps.linear_frozen")
    module.Runner = Frozen
    monkeypatch.setitem(sys.modules, "chipbench.apps.linear_frozen", module)
    with open(os.path.join(BENCH_DIR, "configs", "criteo_dense.json")) as f:
        cfg = json.load(f)
    cfg["app"] = "linear_frozen"
    frozen_file = os.path.join(
        run.ROOT, "chipbench", "configs", "frozen.json"
    )
    with open(frozen_file, "w") as f:
        json.dump(cfg, f)
    shutil.copy(
        os.path.join(BENCH_DIR, "traffic", "text.json"),
        os.path.join(run.ROOT, "chipbench", "traffic", "text.json"),
    )
    cell = "frozen.text"
    bench["configs"].append({"name": "frozen", "file": frozen_file})
    bench["workloads"].append(
        {"name": cell, "config": "frozen", "traffic": "text", "chips": 1}
    )
    args = argparse.Namespace(
        workload=cell, seed=2147483701, seconds=1.0, trace=0, rehearsal=True
    )
    assert run.run_cell(args, bench) == 0
    line = last_line(capsys)
    assert line["correct"] is False
    parity = line["checks"]["logloss_parity"]
    assert parity["ok"] is False and parity["value"] > 3 * parity["limit"]
    others = {
        k: c["ok"] for k, c in line["checks"].items()
        if k != "logloss_parity"
    }
    assert all(others.values()), others


def test_the_benchmark_lists_the_cells_of_the_linear_only_metrics():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    by_name = {m["name"]: m for m in bench["per_layer"]}
    linear_cells = ["criteo_bigtable.text", "criteo_dense.text"]
    for name in LINEAR_ONLY:
        assert by_name[name]["workloads"] == linear_cells, name
    for name in EVERY_CELL:
        assert "workloads" not in by_name[name], name


def test_run_py_names_nothing_of_the_linear_trainer():
    with open(os.path.join(BENCH_DIR, "run.py")) as f:
        source = f.read()
    for word in ("apps.linear", "apps/linear", "ps_ingest", "ps_ftrl",
                 "oracle", "synth", "riteo", "FTRL"):
        assert word not in source, word
    with open(os.path.join(BENCH_DIR, "apps", "linear.py")) as f:
        moved = f.read()
    for word in ("parse_conf", "AsyncSGDWorker", "MinibatchReader",
                 "oracle.progressive_logloss", "ps_ingest_stage_seconds",
                 "ps_ftrl_update_path_total", "ps_recovery_deaths_total"):
        assert word in moved, word


def test_malloc_thresholds_are_fixed_before_the_first_import():
    """``steady_malloc`` is what holds bigtable on one level (PERF.md
    section 2): it runs before anything is imported that allocates, and
    glibc takes all three values (``mallopt`` returns 1)."""
    with open(os.path.join(BENCH_DIR, "run.py")) as f:
        source = f.read()
    assert source.index("\nsteady_malloc()\n") < source.index(
        "\nimport argparse"
    )
    ctypes = pytest.importorskip("ctypes")
    try:
        mallopt = ctypes.CDLL("libc.so.6").mallopt
    except OSError:
        pytest.skip("no glibc here")
    for param, value in ((-2, 64 << 20), (-1, 1 << 30), (-3, 32 << 20)):
        assert f"mallopt({param}, " in source, param
        assert mallopt(param, value) == 1, param


@pytest.mark.parametrize("traced", [0, 1])
@pytest.mark.parametrize("cell", ["criteo_bigtable.text", "criteo_dense.text"])
def test_the_criteo_cells_rehearse_through_the_seam(cell, traced):
    """A configuration without ``"app"`` runs as before: the committed
    files, in a process of its own as the driver starts one."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    done = subprocess.run(
        [sys.executable, os.path.join("chipbench", "run.py"),
         "--workload", cell, "--seed", "2147483693", "--seconds", "2",
         "--trace", str(traced), "--rehearsal"],
        cwd=ROOT, capture_output=True, text=True, timeout=300,
    )
    assert done.returncode == 0, done.stderr[-2000:]
    line = json.loads(done.stdout.strip().splitlines()[-1])
    assert lastline.faults(line, bench, cell, bool(traced)) == []
    assert line["correct"] is True
    assert set(line["metrics"]) == set(
        lastline.expected(bench, cell, bool(traced))
    )
    assert set(line["checks"]) == {
        "logloss_parity", "staleness_within_max_delay", "examples_confirmed",
        "update_path_on_device", "no_node_declared_dead",
        "nothing_compiles_or_falls_back_in_window", "losses_finite",
    }
    # the same numbers, beside their limits, end standard error
    tail = [ln for ln in done.stderr.splitlines() if ln.strip()][-7:]
    assert all(ln.startswith("chipbench check ") for ln in tail), tail
    if traced:
        assert LINEAR_ONLY | EVERY_CELL <= set(line["metrics"])

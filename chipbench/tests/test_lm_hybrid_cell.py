"""The hybrid language-model cell through the seam (PR 33): it rehearses
from its committed files, untraced and traced, and a planted fault in
the program turns ``correct`` false by the check that should see it.

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

CELL = "solar_open2_ep40.packed8k_mb1"
HYBRID_METRICS = {
    "lm_hybrid_step_mfu", "lm_hybrid_kda_share",
    "lm_hybrid_kda_scan_roofline", "lm_hybrid_attention_share",
    "lm_hybrid_moe_share", "lm_hybrid_expert_load_max_over_mean",
    "lm_hybrid_optimizer_share", "lm_hybrid_flash_attention_roofline",
    "lm_hybrid_expert_matmul_roofline",
}
EVERY_CELL = latent_cell.EVERY_CELL
CHECKS = latent_cell.CHECKS | {"kda_state_and_decay_in_f32"}


# -- planted faults: run in the child, before anything is traced ------------


def _wrap_step(wrap) -> None:
    """The runner's trainer steps through ``wrap(trainer, step)``."""
    import jax

    from chipbench.apps import lm_hybrid

    build = lm_hybrid.Runner.build

    def planted_build(self, win):
        build(self, win)
        self.trainer.step = jax.jit(
            wrap(self.trainer, self.trainer.step), donate_argnums=(0, 1)
        )

    lm_hybrid.Runner.build = planted_build


def plant(fault: str) -> None:
    import dataclasses

    import jax
    import jax.numpy as jnp

    from parameter_server_tpu.models import kda as kdalib
    from parameter_server_tpu.models import transformer as tfm
    from parameter_server_tpu.ops import kda as kda_op

    scan = kdalib.kda_chunked
    if fault == "state_in_bf16":  # the state between chunks, not in f32
        kda_op.STATE_DTYPE = jnp.bfloat16
    elif fault == "decay_in_bf16":  # the log-decay rounded before its sums
        decay = kdalib.log_decay
        kdalib.log_decay = lambda *a: jax.lax.reduce_precision(
            decay(*a), 8, 7
        )
    elif fault == "decay_dropped":  # alpha = 1
        kdalib.kda_chunked = lambda q, k, v, g, beta, **kw: scan(
            q, k, v, jnp.zeros_like(g), beta, **kw
        )
    elif fault == "beta_without_its_factor":  # sigmoid, not 2 sigmoid
        kdalib.kda_chunked = lambda q, k, v, g, beta, **kw: scan(
            q, k, v, g, 0.5 * beta, **kw
        )
    elif fault == "convolution_dropped":
        kdalib.causal_conv = lambda x, taps: x
    elif fault == "output_gate_dropped":  # sigmoid(. + 1e4) = 1
        attend = kdalib.kda_attention
        kdalib.kda_attention = lambda h, lp, *rest: attend(
            h, {**lp, "bg": lp["bg"] + 1e4}, *rest
        )
    elif fault == "gqa_gate_dropped":
        forward = tfm.lm_forward_with_stats
        tfm.lm_forward_with_stats = lambda p, t, cfg, *rest: forward(
            p, t, dataclasses.replace(cfg, attn_gate=False), *rest
        )
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
    else:  # the expert layer's: one_expert_fewer, router_in_bf16, ...
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
         "from chipbench.tests.test_lm_hybrid_cell import child; "
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
def test_the_hybrid_cell_rehearses_through_the_seam(traced):
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
        want |= HYBRID_METRICS | EVERY_CELL
        # a share of a peak stays under it
        for name in HYBRID_METRICS:
            if name.endswith(("_mfu", "_roofline")):
                assert 0 < line["metrics"][name]["value"] <= 100, name
        shares = sum(
            line["metrics"][name]["value"] for name in (
                "lm_hybrid_kda_share", "lm_hybrid_attention_share",
                "lm_hybrid_moe_share",
            )
        )
        assert 50 < shares <= 100
        scan = next(
            json.loads(ln) for ln in done.stdout.splitlines()
            if ln.startswith('{"chipbench": "lm_hybrid_scan"')
        )
        assert scan["bound_by"] == "memory" and scan["ops"]
        assert {o["scope"].split("/")[0] for o in scan["ops"]} >= {
            "lm_kda_proj", "lm_kda_conv", "lm_kda_gate", "lm_kda_scan",
            "lm_kda_out",
        }
    assert set(line["metrics"]) == want
    tail = [ln for ln in done.stderr.splitlines() if ln.strip()][-len(CHECKS):]
    assert all(ln.startswith("chipbench check ") for ln in tail), tail
    window = next(
        json.loads(ln) for ln in done.stdout.splitlines()
        if ln.startswith('{"chipbench": "window"')
    )
    assert window["tokens_per_launch"] == 128


def test_the_benchmark_lists_the_hybrid_cell_for_each_hybrid_metric():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    by_name = {m["name"]: m for m in bench["per_layer"]}
    assert {n for n in by_name if n.startswith("lm_hybrid_")} == HYBRID_METRICS
    for name in HYBRID_METRICS:
        assert by_name[name]["workloads"] == [CELL], name
        assert by_name[name]["moves"] == "examples_per_s", name
        assert os.path.exists(
            os.path.join(ROOT, "chipbench", "metrics", name + ".json")
        ), name
    # and no lm_* metric of the latent-attention cell lists this one
    for name in latent_cell.LM_METRICS:
        assert CELL not in by_name[name]["workloads"], name
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    entry = next(c for c in bench["configs"] if c["name"] == cell["config"])
    assert cell["chips"] == 1 and cell["traffic"] == "packed8k_mb1"
    assert len(cell["why"]) <= 200 and len(entry["why"]) <= 200
    assert len(entry["source"]) <= 200
    assert entry["source"].startswith("https://huggingface.co/upstage/")
    reported = {
        m["name"] for m in lastline.cell_metrics(bench, CELL, "end_to_end")
    }
    assert reported == {"examples_per_s", "setup_s"}


def test_the_mix_is_packed8k_at_one_sequence_a_launch():
    def mix(name):
        with open(os.path.join(ROOT, "chipbench", "traffic", name)) as f:
            return json.load(f)

    mine, theirs = mix("packed8k_mb1.json"), mix("packed8k.json")
    assert mine["sequences_per_launch"] == 1
    assert theirs["sequences_per_launch"] == 2
    for key in set(theirs) - {"name", "what", "sequences_per_launch"}:
        assert mine[key] == theirs[key], key


def test_the_hybrid_reference_imports_nothing_of_the_program():
    for name in ("lm_hybrid_reference.py", "lm_hybrid_arith.py"):
        with open(os.path.join(ROOT, "chipbench", name)) as f:
            source = f.read()
        assert "parameter_server_tpu" not in source.split('"""', 2)[2], name


def test_the_arithmetic_is_the_issues():
    """1,295.1M parameters; 34.0 TFLOP in matrices a token meets, 3.3 in
    the one causal layer, ~0.5 in the recurrence; the scan bound by
    memory (5.9 ms of bytes, 2.7 of operations on a v5e)."""
    from chipbench import arith, lm_hybrid_arith, lm_hybrid_reference

    path = os.path.join(ROOT, "chipbench", "configs", "solar_open2_ep40.json")
    desc = lm_hybrid_reference.description(path)
    shapes = lm_hybrid_reference.shapes(lm_hybrid_reference.model(desc))
    total = 0
    for shape in shapes.values():
        n = 1
        for dim in shape:
            n *= dim
        total += n
    assert total == 1_295_110_720
    dense = 6.0 * lm_hybrid_arith.dense_params_per_token(desc) * 8192
    assert abs(dense / 1e12 - 34.0) < 0.1
    causal = lm_hybrid_arith.causal_attention_flops(desc, 8192, 1, 6)
    assert abs(causal / 1e12 - 3.3) < 0.05
    scanned = 3 * 8192
    assert abs(lm_hybrid_arith.scan_flops(desc, scanned) / 1e12 - 0.54) < 0.01
    kind = "TPU v5 lite"
    by_compute = lm_hybrid_arith.scan_flops(desc, scanned) / arith.peak(
        kind, "bf16_flops_per_s"
    )
    by_memory = lm_hybrid_arith.scan_bytes(desc, scanned) / arith.peak(
        kind, "hbm_bytes_per_s"
    )
    assert abs(by_memory * 1e3 - 5.9) < 0.1 and by_memory > 2 * by_compute
    step = lm_hybrid_arith.step_model_flops(desc, 8192, 1, 4 * 1638.4, scanned)
    assert abs(step / 1e12 - 38.4) < 0.2


@pytest.mark.parametrize("fault,check", [
    ("decay_dropped", "update_parity"),
    ("beta_without_its_factor", "update_parity"),
    ("convolution_dropped", "update_parity"),
    ("output_gate_dropped", "update_parity"),
    ("gqa_gate_dropped", "update_parity"),
    ("one_expert_fewer", "update_parity"),
    ("weights_in_bf16", "update_parity"),
    ("router_in_bf16", "router_arithmetic"),
    ("optimizer_state_dropped", "second_update_parity"),
    ("state_in_bf16", "kda_state_and_decay_in_f32"),
    ("decay_in_bf16", "kda_state_and_decay_in_f32"),
])
def test_a_planted_fault_turns_the_hybrid_cell_incorrect(fault, check):
    """A lower precision than the file states (the weights kept in bf16,
    the router computed in bf16, the recurrence's state carried or its
    log-decay held in bf16), a missing term (the decay, beta's factor 2,
    the convolution, either gate, the last of the top-k) and an
    optimizer state that is not carried each fail the check that should
    see it, by half its limit at least, and nothing compiles for it."""
    line = rehearsed(fault)
    assert line["correct"] is False
    failed = line["checks"][check]
    assert failed["ok"] is False
    if check == "kda_state_and_decay_in_f32":  # a share that must be had
        assert failed["value"] < 0.5 * failed["limit"]
    else:
        assert failed["value"] > 1.5 * failed["limit"]
    for name in ("examples_confirmed", "losses_finite",
                 "nothing_compiles_or_falls_back_in_window"):
        assert line["checks"][name]["ok"] is True, name


@pytest.mark.parametrize("fault", ["state_in_bf16", "decay_in_bf16"])
def test_a_bf16_state_or_decay_is_told_by_its_bits_alone(fault):
    """No comparison of values tells either (the configuration's
    ``correct.why``): every other check holds."""
    checks = rehearsed(fault)["checks"]
    assert [k for k, c in checks.items() if not c["ok"]] == [
        "kda_state_and_decay_in_f32"
    ]
    assert rehearsed("none")["checks"]["kda_state_and_decay_in_f32"][
        "value"
    ] > 0.99


def test_a_dropped_optimizer_state_passes_the_hybrid_cells_first_update():
    checks = rehearsed("optimizer_state_dropped")["checks"]
    assert checks["update_parity"]["ok"] is True
    assert checks["second_update_parity"]["ok"] is False

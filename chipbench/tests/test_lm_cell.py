"""The language-model cell through the seam (PR 29): it rehearses from
its committed files, untraced and traced, and a planted fault in the
program turns ``correct`` false by the check that should see it.

Each run is a process of its own, as the driver starts one: jax caches
traced functions by identity, and a fault planted after a clean run in
the same process would not be traced again. CPU only:
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

CELL = "mistral_small4_ep16.packed8k"
LM_METRICS = {
    "lm_step_mfu", "lm_attention_share", "lm_moe_share", "lm_optimizer_share",
    "lm_expert_matmul_roofline", "lm_flash_attention_roofline",
    "lm_expert_load_max_over_mean",
}
EVERY_CELL = {
    "step_device_ms", "device_idle_share", "idle_in_program_share",
    "idle_unattributed_share",
}
CHECKS = {
    "loss_trajectory", "router_arithmetic", "router_choices_same_input",
    "routing_agreement", "update_parity_own_routing", "update_parity",
    "second_update_parity", "examples_confirmed",
    "nothing_compiles_or_falls_back_in_window", "losses_finite",
}


# -- planted faults: run in the child, before anything is traced ------------


def plant(fault: str) -> None:
    import jax
    import jax.numpy as jnp

    from chipbench.apps import lm
    from parameter_server_tpu.models import moe

    if fault == "shared_expert_dropped":
        moe.swiglu = lambda h, *weights: jnp.zeros_like(h)
    elif fault == "one_expert_fewer":  # top-3 for top-4 (toy: 1 for 2)
        route = moe.route_topk

        def fewer(h, router, cfg):
            w, e = route(h, router, cfg)
            w = w.at[:, -1].set(0.0)
            return w / jnp.sum(w, -1, keepdims=True), e

        moe.route_topk = fewer
    elif fault == "router_in_bf16":
        # reduce_precision and not astype: the TPU compiler may keep an
        # f32 -> bf16 -> f32 round trip in f32 (excess precision)
        def bf16(x):
            return jax.lax.reduce_precision(
                x.astype(jnp.float32), exponent_bits=8, mantissa_bits=7
            )

        def router(h, router_w, cfg):
            logits = bf16(jnp.dot(
                bf16(h), bf16(router_w),
                precision=jax.lax.Precision.HIGHEST,
            ))
            p = bf16(jax.nn.softmax(logits, axis=-1))
            top_p, top_e = jax.lax.top_k(p, cfg.top_k)
            top_p = bf16(top_p / bf16(jnp.sum(top_p, -1, keepdims=True)))
            return top_p, top_e.astype(jnp.int32)

        moe.route_topk = router
    elif fault == "weights_in_bf16":
        # the nearest precision below the f32 weights the file states:
        # the step's new weights rounded to bf16
        build = lm.Runner.build

        def rounded_build(self, win):
            build(self, win)
            step = self.trainer.step

            def rounded(p, opt, *data):
                p, opt, loss, stats = step(p, opt, *data)
                p = jax.tree.map(
                    lambda x: jax.lax.reduce_precision(x, 8, 7), p
                )
                return p, opt, loss, stats

            self.trainer.step = jax.jit(rounded, donate_argnums=(0, 1))

        lm.Runner.build = rounded_build
    elif fault == "optimizer_state_dropped":
        # every step starts from a fresh optimizer state: the first
        # update is right, the second has lost what the first left
        build = lm.Runner.build

        def forgetful_build(self, win):
            build(self, win)
            step, fresh = self.trainer.step, self.trainer.tx.init

            def forgetful(p, opt, *data):
                p, _, loss, stats = step(p, opt, *data)
                return p, fresh(p), loss, stats

            self.trainer.step = jax.jit(forgetful, donate_argnums=(0, 1))

        lm.Runner.build = forgetful_build
    elif fault == "other_initial_weights":
        # the trainer starts from weights of its own, not the given ones
        from parameter_server_tpu.apps.lm import trainer as lm_trainer

        load = lm_trainer.Trainer.load

        def own(self, params, **placement):
            load(self, {
                k: v * 1.001 if k == "l0/wo" else v for k, v in params.items()
            }, **placement)

        lm_trainer.Trainer.load = own
    else:
        raise ValueError(fault)


def child(fault: str, seed: str, traced: str = "0",
          rehearsal: bool = True, seconds: str = "1.5") -> int:
    """One run of the cell with ``fault`` planted. ``rehearsal=False``
    is the same on a chip, at the cell's size (PERF.md's second reading
    of each limit was taken that way)."""
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
         "from chipbench.tests.test_lm_cell import child; "
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
def test_the_lm_cell_rehearses_through_the_seam(traced):
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
        want |= LM_METRICS | EVERY_CELL
        # a zero count is 0 and a share of a peak stays under it
        for name in ("lm_step_mfu", "lm_expert_matmul_roofline",
                     "lm_flash_attention_roofline"):
            assert 0 <= line["metrics"][name]["value"] <= 100, name
    assert set(line["metrics"]) == want
    assert "launch_p50_ms" not in line["metrics"]  # lists bigtable only
    # each number compared stands beside its limit, on stderr too
    tail = [ln for ln in done.stderr.splitlines() if ln.strip()][-len(CHECKS):]
    assert all(ln.startswith("chipbench check ") for ln in tail), tail
    window = next(
        json.loads(ln) for ln in done.stdout.splitlines()
        if ln.startswith('{"chipbench": "window"')
    )
    assert window["tokens_per_launch"] == 2 * 128


def test_the_benchmark_lists_the_lm_cell_for_each_lm_metric():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    by_name = {m["name"]: m for m in bench["per_layer"]}
    for name in LM_METRICS:
        assert by_name[name]["workloads"] == [CELL], name
        assert by_name[name]["moves"] == "examples_per_s", name
        assert os.path.exists(
            os.path.join(ROOT, "chipbench", "metrics", name + ".json")
        ), name
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert cell["chips"] == 1 and len(cell["why"]) <= 200
    reported = {
        m["name"] for m in lastline.cell_metrics(bench, CELL, "end_to_end")
    }
    assert reported == {"examples_per_s", "setup_s"}


def test_the_reference_imports_nothing_of_the_program():
    with open(os.path.join(ROOT, "chipbench", "lm_reference.py")) as f:
        source = f.read()
    assert "parameter_server_tpu" not in source.split('"""', 2)[2]
    assert "import jax" in source


@pytest.mark.parametrize("fault,check", [
    ("shared_expert_dropped", "update_parity"),
    ("shared_expert_dropped", "update_parity_own_routing"),
    ("one_expert_fewer", "update_parity"),
    ("weights_in_bf16", "update_parity"),
    ("router_in_bf16", "router_arithmetic"),
    ("router_in_bf16", "router_choices_same_input"),
    ("optimizer_state_dropped", "second_update_parity"),
])
def test_a_planted_fault_turns_correct_false(fault, check):
    """A missing term (the shared expert; the last of the top-k), a lower
    precision than the file states (the weights kept in bf16; the router
    computed in bf16) and an optimizer state that is not carried each
    fail the check that should see it, by half its limit at least, and
    nothing compiles for it."""
    line = rehearsed(fault)
    assert line["correct"] is False
    failed = line["checks"][check]
    assert failed["ok"] is False and failed["value"] > 1.5 * failed["limit"]
    for name in ("examples_confirmed", "losses_finite",
                 "nothing_compiles_or_falls_back_in_window"):
        assert line["checks"][name]["ok"] is True, name


def test_an_optimizer_state_that_is_dropped_passes_the_first_update():
    """Only the second update sees it: that is why it is compared."""
    checks = rehearsed("optimizer_state_dropped")["checks"]
    assert checks["update_parity"]["ok"] is True
    assert checks["second_update_parity"]["ok"] is False


def test_a_program_that_starts_from_other_weights_is_seen():
    """The initial weights are the benchmark's (``lm_reference.weights_fn``
    from ``--seed``), handed to the trainer: one that starts elsewhere
    fails ``loss_trajectory`` by its sampled rows, whatever its losses."""
    _, done = rehearse("other_initial_weights")
    note = next(
        json.loads(ln) for ln in done.stdout.splitlines()
        if '"name": "loss_trajectory"' in ln
    )
    assert note["same_initial_weights"] is False and note["ok"] is False

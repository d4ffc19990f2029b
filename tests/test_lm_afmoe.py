"""Trinity's language model (``model_type`` ``afmoe``) on the training
path of ``apps/lm``: gated GQA layers with a q/k norm, over a window and
rotated or full and NoPE by ``layer_types``, each block's output normed
before its residual add (sandwich norms), an embedding scaled by
sqrt(d), a leading dense layer, and a sigmoid router whose bias picks
experts and does not weight them, renormalised and scaled by
``route_scale``, beside a shared expert; an untied head. Held to the
benchmark's plain reference (``chipbench/lm_afmoe_reference.py``: one
copy, imported from there) at the cell's rehearsal sizes on the CPU: d
64, 4 query / 2 K/V heads of 16, a window of 32, a router of 8 experts
with 2 held, top-2, vocabulary 512, 5 layers (window + dense, window,
full, window, window), sequences of 48. Each planted fault of
:func:`plant` fails that comparison; the cell's runner plants the same
faults at the cell's size on a chip (:func:`child`).
"""

from __future__ import annotations

import dataclasses
import inspect
import json
import os
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax.sharding import Mesh

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from chipbench import lm_afmoe_arith as arith  # noqa: E402
from chipbench import lm_afmoe_reference as ref  # noqa: E402
from parameter_server_tpu.apps.lm import trainer as lm_trainer  # noqa: E402
from parameter_server_tpu.models import moe as moelib  # noqa: E402
from parameter_server_tpu.models import transformer as tfm  # noqa: E402

CONFIG = os.path.join(ROOT, "chipbench", "configs", "trinity_mini_ep8.json")
CELL = "trinity_mini_ep8.packed8k_mb1"
AFMOE_METRICS = {
    "lm_afmoe_step_mfu", "lm_afmoe_attention_share",
    "lm_afmoe_window_attention_share", "lm_afmoe_gate_share",
    "lm_afmoe_post_norm_share", "lm_afmoe_flash_attention_roofline",
    "lm_afmoe_moe_share", "lm_afmoe_expert_matmul_roofline",
    "lm_afmoe_expert_load_max_over_mean",
}
CHECKS = {
    "loss_trajectory", "router_arithmetic", "router_choices_same_input",
    "routing_agreement", "update_parity_own_routing", "update_parity",
    "second_update_parity", "examples_confirmed", "expert_bias",
    "nothing_compiles_or_falls_back_in_window", "losses_finite",
}
TOL = 1e-4  # the clean program's gradients against the reference's


def toy_desc(**over) -> dict:
    desc = ref.description(CONFIG, rehearsal=True)
    desc.update(over)
    return desc


def mesh_of(n: int) -> Mesh:
    return Mesh(np.array(jax.devices()[:n]).reshape(n, 1), ("data", "server"))


def rel(a, b) -> float:
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.linalg.norm(a - b) / max(np.linalg.norm(b), 1e-30))


@pytest.fixture(scope="module")
def setup():
    """The toy's reference weights with a selection bias that is not 0,
    so that it picks in every test that routes, and two sequences of
    48, past the window of 32."""
    desc = toy_desc()
    cfg = lm_trainer.model_from_description(desc)
    m = ref.model(desc)
    params = ref.weights(5, m)
    biases = sorted(k for k in params if k.endswith(ref.BIAS))
    moved = 0.05 * jax.random.normal(
        jax.random.PRNGKey(6), (len(biases), m["experts"])
    )
    params = {**params, **dict(zip(biases, moved))}
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 48), 0, 512)
    with jax.default_matmul_precision("highest"):
        want = jax.jit(lambda p, t: ref.loss_and_grads(p, t, m))(
            params, tokens
        )
    return desc, cfg, m, params, tokens, want


def program_loss_and_grads(cfg, params, tokens):
    mesh = mesh_of(1)
    with jax.default_matmul_precision("highest"):
        return jax.jit(jax.value_and_grad(
            lambda p, t: tfm.lm_loss(p, t, cfg, mesh)
        ))(params, tokens)


def worst_gap(setup, cfg=None) -> tuple:
    """``(gap, leaf)``: the program's loss and every trained leaf's
    gradient against the reference's, as the largest relative error
    (the loss's as an absolute one)."""
    _, cfg0, _, params, tokens, (want, theirs) = setup
    loss, grads = program_loss_and_grads(cfg or cfg0, params, tokens)
    gaps = [(abs(float(loss) - float(want)), "loss")]
    gaps += [(rel(grads[k], theirs[k]), k) for k in theirs]
    return max(gaps)


# -- the description ---------------------------------------------------------


def test_the_description_builds_the_layers_and_the_router(setup):
    _, cfg, m, params, _, _ = setup
    assert cfg.layer_kinds == (
        ("swa", "dense"), ("swa", "moe"), ("mha", "moe"), ("swa", "moe"),
        ("swa", "moe"),
    )
    assert cfg.rope and cfg.rope_kinds == ("swa",) and cfg.window == 32
    assert cfg.attn_gate and cfg.post_norms and cfg.qk_norm
    assert cfg.scale_emb and not cfg.tie_head
    assert (cfg.head_width, cfg.kv_heads, cfg.d_ff) == (16, 2, 96)
    assert (cfg.rope_theta, cfg.rope_yarn, cfg.norm_eps) == (1e4, None, 1e-5)
    assert cfg.moe == moelib.TopKMoEConfig(
        n_experts=8, top_k=2, d_expert=32, n_shared=1, experts_held=2,
        norm_topk_prob=True, routed_scaling_factor=2.826, scoring="sigmoid",
        expert_bias=True,
    )
    want = jax.eval_shape(lambda: tfm.init_lm(jax.random.PRNGKey(0), cfg))
    assert {k: v.shape for k, v in want.items()} == {
        k: v.shape for k, v in params.items()
    }


def test_post_norms_draw_no_key_and_leave_the_other_leaves_as_ever():
    plain = tfm.LMConfig(n_layers=2, norm="rmsnorm", attn_gate=True)
    a = tfm.init_lm(jax.random.PRNGKey(0), plain)
    b = tfm.init_lm(
        jax.random.PRNGKey(0), dataclasses.replace(plain, post_norms=True)
    )
    assert set(b) - set(a) == {
        f"l{i}/{n}" for i in range(2)
        for n in ("post_attn_norm", "post_ffn_norm")
    }
    assert all(np.array_equal(a[k], b[k]) for k in a)
    assert all(np.all(b[k] == 1.0) for k in set(b) - set(a))


@pytest.mark.parametrize("over,message", [
    (dict(n_group=2), "n_group 2"),
    (dict(num_expert_groups=4), "num_expert_groups 4"),
    (dict(num_limited_groups=2), "num_limited_groups 2"),
    (dict(topk_group=2), "topk_group 2"),
    (dict(load_balance_coeff=5e-05), "load_balance_coeff 5e-05"),
    (dict(rope_scaling={"rope_type": "yarn", "factor": 4.0}),
     "rope_scaling"),
    (dict(mup_enabled=False), "mup_enabled false"),
    (dict(layer_types=["sliding_attention", "conv", "full_attention",
                       "sliding_attention", "sliding_attention"]),
     "'conv'"),
    (dict(num_dense_layers=6), "num_dense_layers 6"),
    (dict(first_k_dense_replace=1), "lfm2_moe or afmoe"),
])
def test_a_description_of_what_is_not_built_is_refused(over, message):
    with pytest.raises(ValueError, match=message):
        lm_trainer.model_from_description(toy_desc(**over))


@pytest.mark.parametrize("over,message", [
    (dict(rope=True, rope_kinds=("swa",)), "rotation by kind of layer"),
    (dict(post_norms=True), "post-block norms"),
])
def test_serving_refuses_what_it_would_compute_otherwise(over, message):
    cfg = tfm.LMConfig(n_layers=1, **over)
    with pytest.raises(NotImplementedError, match=message):
        tfm.refuse_serving(cfg, "here")


# -- the program against the reference ---------------------------------------


def test_logits_loss_and_every_gradient_match_the_reference_in_f32(setup):
    """The program's logits, loss and gradients (every trained leaf;
    none reaches a bias) against the reference's, and the blocked
    reference against the plain one."""
    _, cfg, m, params, tokens, (want, theirs) = setup
    mesh = mesh_of(1)
    with jax.default_matmul_precision("highest"):
        logits = jax.jit(lambda p, t: tfm.lm_forward(p, t, cfg, mesh))(
            params, tokens
        )
    assert rel(logits, jax.jit(lambda p, t: ref.forward(p, t, m))(
        params, tokens
    )) < 1e-5
    loss, grads = program_loss_and_grads(cfg, params, tokens)
    assert abs(float(loss) - float(want)) < 1e-5
    assert set(theirs) == set(ref.trained(params))
    worst = max((rel(grads[k], theirs[k]), k) for k in theirs)
    assert worst[0] < TOL, worst
    biases = [k for k in grads if k.endswith(ref.BIAS)]
    assert len(biases) == 4
    assert all(float(jnp.abs(grads[k]).max()) == 0.0 for k in biases)
    blocked, by_block = jax.jit(
        lambda p, t: ref.loss_and_grads(p, t, m, True)
    )(params, tokens)
    assert abs(float(blocked) - float(want)) < 1e-6
    assert max(rel(by_block[k], theirs[k]) for k in theirs) < 1e-5


def test_two_steps_match_the_reference_and_its_bias_rule(setup):
    """One step of Adafactor (factoring from 8, as the rehearsal does)
    and a second, program and reference from the same weights and
    tokens, the reference at the program's choices: each trained leaf's
    update within 1e-3 relative L2 of the reference's, and each bias
    after each of the two steps exactly the reference's rule."""
    _, cfg, m, params, tokens, _ = setup
    tx = optax.adafactor(learning_rate=3e-4, min_dim_size_to_factor=8)
    trainer = lm_trainer.Trainer(cfg, mesh_of(1), tx)
    trainer.load(jax.tree.map(jnp.copy, params))  # the step donates them
    batches = [np.asarray(tokens), np.asarray(tokens[:, ::-1])]
    mine = []
    for batch in batches:
        launch = trainer.submit(trainer.place([batch]))
        trainer.collect(launch)
        mine.append((jax.device_get(trainer.params),
                     np.asarray(launch.stats["top_e"])))

    @jax.jit
    def step(p, opt, batch, given):
        (_, _), g = ref.loss_grads_choices(p, batch, m, given=given)
        trained = ref.trained(p)
        up, opt = tx.update(g, opt, trained)
        new = optax.apply_updates(trained, up)
        return {**new, **ref.step_biases(p, given, m)}, opt

    p, opt = params, tx.init(ref.trained(params))
    theirs = []
    for batch, (_, chosen) in zip(batches, mine):
        p, opt = step(p, opt, jnp.asarray(batch), jnp.asarray(chosen))
        theirs.append(jax.device_get(p))
    befores = [(params, params), (mine[0][0], theirs[0])]
    for (have, _), want, (before, ref_before) in zip(mine, theirs, befores):
        worst = max(
            (rel(np.asarray(have[k]) - np.asarray(before[k]),
                 np.asarray(want[k]) - np.asarray(ref_before[k])), k)
            for k in ref.trained(params)
        )
        assert worst[0] < 1e-3, worst
        for k in set(params) - set(ref.trained(params)):
            assert np.array_equal(have[k], want[k]), k
    name = "l1/" + ref.BIAS
    moved = np.abs(mine[1][0][name] - np.asarray(params[name]))
    assert (moved > 0).sum() >= 6  # two steps of the rate, or none


def test_the_shares_add_up_to_the_uncut_reference_layer(setup):
    """The four shares of 2 held experts of 8 (the program's layer, told
    which experts it holds; the shared expert in the first share only,
    so that it counts once) add up to the uncut reference layer before
    its post-norm, the bias picking alike in all."""
    _, cfg, m, params, _, _ = setup
    lp = {k[3:]: v for k, v in params.items() if k.startswith("l1/")}
    experts = m["experts"]
    whole = {**lp, **{
        k: 5.0 * jax.random.normal(jax.random.PRNGKey(i), (experts,)
                                   + lp[k].shape[1:])
        for i, k in enumerate(("we_gate", "we_up", "we_down"))
    }}
    x = jax.random.normal(jax.random.PRNGKey(3), (2, 24, 64))
    with jax.default_matmul_precision("highest"):
        want, _ = ref.share_ffn(whole, x, m, False, held=experts, offset=0)
        h2 = ref.rms(x, lp["ln2"], m["eps"])
        total = jnp.zeros_like(x)
        for offset in range(0, experts, m["held"]):
            share = dataclasses.replace(
                cfg.moe, expert_offset=offset,
                n_shared=cfg.moe.n_shared if offset == 0 else 0,
            )
            mine = {
                k: v[offset:offset + m["held"]] if k.startswith("we_") else v
                for k, v in whole.items()
            }
            y, _ = moelib.topk_moe_ffn(mine, h2, share, jnp.float32)
            total = total + y
    assert rel(total, want) < 1e-5


# -- planted faults ----------------------------------------------------------


def _mutate(old: str, new: str, setattr=setattr) -> None:
    """The program's training forward with ``old``, which its source
    holds once, replaced by ``new``: a fault no option of the model can
    express."""
    source = textwrap.dedent(inspect.getsource(tfm.lm_forward_with_stats))
    assert source.count(old) == 1, old
    made: dict = {}
    exec(compile(source.replace(old, new), tfm.__file__, "exec"),
         vars(tfm), made)
    setattr(tfm, "lm_forward_with_stats", made["lm_forward_with_stats"])


def _with_config(change, setattr=setattr) -> None:
    forward = tfm.lm_forward_with_stats
    setattr(tfm, "lm_forward_with_stats", lambda p, t, cfg, *rest: forward(
        p, t, change(cfg), *rest
    ))


def _with_router(**over):
    return lambda cfg: dataclasses.replace(
        cfg, moe=dataclasses.replace(cfg.moe, **over)
    )


def _bias_in_the_weights(route):
    def planted(h, r, cfg, bias=None):
        w, e = route(h, r, cfg, bias)
        s = jax.nn.sigmoid(jnp.dot(
            h, r, precision=jax.lax.Precision.HIGHEST
        )) + bias
        at = jnp.take_along_axis(s, e, axis=-1)
        return at / at.sum(-1, keepdims=True) * cfg.routed_scaling_factor, e

    return planted


# fault -> what it changes: a change of the model's config, of the
# forward's source (``(old, new)``) or of the router (``route_topk``)
FAULTS = {
    "gate_dropped": lambda cfg: dataclasses.replace(cfg, attn_gate=False),
    "gate_from_x": (
        "else gated(mha(h, lp, att_kind), h, lp)",
        "else gated(mha(h, lp, att_kind), x, lp)",
    ),
    "rope_on_full_layers": lambda cfg: dataclasses.replace(
        cfg, rope_kinds=None
    ),
    "no_rope_on_window_layers": lambda cfg: dataclasses.replace(
        cfg, rope_kinds=()
    ),
    "post_attn_norm_dropped": (
        "y = norm(y, lp[name])",
        "y = y if name == 'post_attn_norm' else norm(y, lp[name])",
    ),
    "post_ffn_norm_dropped": (
        "y = norm(y, lp[name])",
        "y = y if name == 'post_ffn_norm' else norm(y, lp[name])",
    ),
    "route_scale_1": _with_router(routed_scaling_factor=1.0),
    "weights_not_renormalised": _with_router(norm_topk_prob=False),
    "bias_in_the_weights": _bias_in_the_weights,
    "routing_on_scores_alone": lambda route: (
        lambda h, r, cfg, bias=None: route(h, r, cfg)
    ),
    "mup_dropped": lambda cfg: dataclasses.replace(cfg, scale_emb=False),
    "shared_expert_dropped": _with_router(n_shared=0),
    "window_1024": lambda cfg: dataclasses.replace(cfg, window=1024),
}


def _weights_in_bf16(setattr=setattr) -> None:
    """The trainer's weights rounded to bf16 after every step: the
    nearest precision below the stated one. A run's fault only: one
    forward's gradients do not see it."""
    from chipbench.apps import lm_afmoe

    build = lm_afmoe.Runner.build

    def planted_build(self, win):
        build(self, win)
        step = self.trainer.step

        def rounded(p, opt, *data):
            p, opt, loss, stats = step(p, opt, *data)
            return jax.tree.map(
                lambda x: jax.lax.reduce_precision(x, 8, 7), p
            ), opt, loss, stats

        self.trainer.step = jax.jit(rounded, donate_argnums=(0, 1))

    setattr(lm_afmoe.Runner, "build", planted_build)


def plant(fault: str, setattr=setattr) -> None:
    """A fault in the program, before anything is traced (``setattr``:
    a test's ``monkeypatch.setattr``, which undoes it)."""
    if fault == "none":
        return
    if fault == "weights_in_bf16":
        return _weights_in_bf16(setattr)
    what = FAULTS[fault]
    if isinstance(what, tuple):
        _mutate(*what, setattr=setattr)
    elif fault in ("bias_in_the_weights", "routing_on_scores_alone"):
        setattr(moelib, "route_topk", what(moelib.route_topk))
    else:
        _with_config(what, setattr=setattr)


def test_the_clean_program_passes_the_comparison(setup):
    assert worst_gap(setup)[0] < TOL


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_a_planted_fault_fails_the_comparison(fault, setup, monkeypatch):
    """Each fault moves the loss or some trained leaf's gradient a
    hundred times the clean program's tolerance at least."""
    plant(fault, monkeypatch.setattr)
    gap, where = worst_gap(setup)
    assert gap > 100 * TOL, (gap, where)


def child(fault: str, seed: str, traced: str = "0",
          rehearsal: bool = True, seconds: str = "0.5") -> int:
    """One run of the cell with ``fault`` planted (``rehearsal=False``:
    the same on a chip, at the cell's size)."""
    from chipbench import run

    if rehearsal:  # prepare() would set it after plant() imports jax
        os.environ["JAX_PLATFORMS"] = "cpu"
    plant(fault)
    return run.main([
        "--workload", CELL, "--seed", seed, "--seconds", seconds,
        "--trace", traced,
    ] + (["--rehearsal"] if rehearsal else []))


# -- counters, scopes, the CLI -----------------------------------------------


def test_a_collect_counts_gated_token_layers_by_kind(setup):
    from parameter_server_tpu.telemetry import registry as telemetry_registry

    _, cfg, _, params, tokens, _ = setup
    trainer = lm_trainer.build_trainer(cfg, mesh_of(1), optimizer="adafactor")
    trainer.load(jax.tree.map(jnp.copy, params))  # the step donates them
    reg = telemetry_registry.default_registry()

    def value(name, kind):
        series = reg.export_state().get(name, {}).get("series", [])
        return sum(
            s["value"] for s in series if s["labels"].get("kind") == kind
        )

    name = "ps_lm_attention_gate_token_layers_total"
    before = [value(name, kind) for kind in ("window", "full")]
    _, counts = trainer.collect(
        trainer.submit(trainer.place([np.asarray(tokens)]))
    )
    assert counts[tfm.ATTN_TOKEN_LAYERS].tolist() == [2 * 48 * 4, 2 * 48]
    assert counts[tfm.ATTN_GATE_TOKEN_LAYERS].tolist() == [384, 96]
    after = [value(name, kind) for kind in ("window", "full")]
    assert [a - b for a, b in zip(after, before)] == [384, 96]


def test_the_gate_and_the_post_norms_are_scoped_inside_their_blocks(setup):
    """Every op under ``attn_gate`` is under ``lm_attn`` too, and every
    op under ``lm_post_norm`` under the block it norms: the readers
    that select ``lm_attn``, ``lm_ffn`` or ``lm_moe_*`` read what they
    read before."""
    import re

    _, cfg, _, params, tokens, _ = setup
    mesh = mesh_of(1)
    text = jax.jit(
        lambda p, t: tfm.lm_forward(p, t, cfg, mesh)
    ).lower(params, tokens).as_text(debug_info=True)
    paths = re.findall(r'loc\("([^"]*)"', text)
    gate = [p for p in paths if "/attn_gate/" in p]
    post = [p for p in paths if "/lm_post_norm/" in p]
    assert gate and post
    assert all("/lm_attn/" in p for p in gate)
    inside = [
        re.search(r"/(lm_attn|lm_ffn|lm_moe_route)/(attn_\w+/)?lm_post_norm/",
                  p)
        for p in post
    ]
    assert all(inside)
    assert {m.group(1) for m in inside} == {
        "lm_attn", "lm_ffn", "lm_moe_route",
    }


def test_the_cli_trains_the_description(tmp_path, monkeypatch, capsys):
    from parameter_server_tpu.apps.lm import main as lm_main

    path = tmp_path / "toy.json"
    path.write_text(json.dumps(toy_desc()))
    devices = jax.devices()[:1]
    monkeypatch.setattr(jax, "devices", lambda *a, **k: devices)
    lm_main.run([
        "--model-config", str(path), "--optimizer", "adafactor", "--bf16",
        "--remat", "--steps", "2", "--seq-len", "64", "--batch", "1",
    ])
    assert "loss" in capsys.readouterr().out
    with pytest.raises(SystemExit):
        lm_main.run(["--model-config", str(path), "--steps", "1",
                     "--prompt", "x"])
    assert "lm_forward only" in capsys.readouterr().err


# -- the configuration and its arithmetic ------------------------------------


def test_the_configuration_file_keeps_every_published_number():
    """Every key of the catalog row's config, at its value but the four
    counts and ``layer_types`` that ``reduced`` lists."""
    catalog = os.environ.get("MODEL_CATALOG", "")  # architectures.jsonl
    with open(CONFIG) as f:
        mine = json.load(f)
    assert mine["source"] == (
        "https://huggingface.co/arcee-ai/Trinity-Mini/blob/main/config.json"
    )
    cut = {"num_hidden_layers": 9, "num_dense_layers": 1,
           "num_experts": 16, "vocab_size": 25024}
    published = mine["published"]
    types = published["layer_types"]
    assert len(types) == 32 and types.count("full_attention") == 8
    assert mine["layer_types"] == types[:1] + types[2:10]
    if os.path.exists(catalog):
        with open(catalog) as f:
            row = next(
                r for r in map(json.loads, f) if r["name"] == "Trinity-Mini"
            )
        for key, value in row["config"].items():
            if key == "layer_types":
                assert published[key] == value
            else:
                assert mine[key] == cut.get(key, value), key
                assert published.get(key, value) == value, key
    assert {k: published[k] for k in cut} == {
        "num_hidden_layers": 32, "num_dense_layers": 2,
        "num_experts": 128, "vocab_size": 200192,
    }
    assert set(mine["reduced"]) == set(cut) | {"layer_types", "training_data"}
    assert mine["share"]["chips_per_layer"] * 16 == 128
    for key in ("mup_enabled", "attention", "rope_pairing", "sandwich_norms",
                "router_scoring", "shared_expert", "expert_bias_update",
                "optimizer", "initialisation", "packing"):
        assert key in mine["assumed"], key
    for key in ("precision", "correct", "deployment", "reduced_why"):
        assert key in mine, key


def test_the_arithmetic_of_the_cut():
    """1,243,427,072 trained parameters (and 8 x 128 biases); 437M
    matmul weights a token meets; the flash work at 8k by the mask."""
    desc = ref.description(CONFIG)
    m = ref.model(desc)
    shapes = ref.shapes(m)
    count = lambda names: sum(  # noqa: E731
        int(np.prod(shapes[k])) for k in names
    )
    assert count(ref.trained(shapes)) == 1_243_427_072
    assert count(set(shapes) - set(ref.trained(shapes))) == 8 * 128
    attention = arith.attention_matmul_params(desc) + arith.gate_params(desc)
    assert attention == 27_262_976
    assert arith.shared_expert_params(desc) == 6_291_456
    per_token = (
        arith.dense_params_per_token(desc) + 9 * arith.gate_params(desc)
        + 8 * 8 * 16 // 128 * 3 * 2048 * 1024
    )
    assert per_token == (
        9 * 27_262_976 + 8 * (262_144 + 2 * 6_291_456) + 37_748_736
        + 25_024 * 2048
    )
    assert round(per_token / 1e6) == 437
    # one layer of each kind, forward (2 products) and backward (4)
    flash = {
        kind: arith.flash_kernels_flops(
            desc, 8192, {k: 8192 * (k == kind) for k in arith.KINDS}
        ) * 6 / 7
        for kind in arith.KINDS
    }
    assert round(flash["full"] / 1e12, 2) == 1.65
    assert round(flash["window"] / 1e12, 2) == 0.72
    step = arith.step_model_flops(
        desc, 8192, 1, 8192 * 8 * 8 * 16 / 128,
        {"window": 7 * 8192, "full": 2 * 8192},
        {"window": 7 * 8192, "full": 2 * 8192},
    )
    assert round(step / 1e12, 1) == 29.8


def test_the_reference_imports_nothing_of_the_program():
    for name in ("lm_afmoe_reference.py", "lm_afmoe_arith.py"):
        with open(os.path.join(ROOT, "chipbench", name)) as f:
            source = f.read()
        assert "parameter_server_tpu" not in source.split('"""', 2)[2], name


def test_the_benchmark_lists_the_cell_for_each_afmoe_metric():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    by_name = {m["name"]: m for m in bench["per_layer"]}
    assert {n for n in by_name if n.startswith("lm_afmoe_")} == AFMOE_METRICS
    for name in AFMOE_METRICS:
        assert by_name[name]["workloads"] == [CELL], name
        assert by_name[name]["moves"] == "examples_per_s", name
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    entry = next(c for c in bench["configs"] if c["name"] == cell["config"])
    assert cell["chips"] == 1 and cell["traffic"] == "packed8k_mb1"
    assert len(cell["why"]) <= 200 and len(entry["why"]) <= 200
    with open(os.path.join(ROOT, entry["file"])) as f:
        assert json.load(f)["reduced"] == entry["reduced"]
    # appended after the cells before it (later cells come after it)
    assert bench["workloads"].index(cell) == 6
    assert bench["configs"].index(entry) == 6

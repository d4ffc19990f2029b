"""LFM2's language model (``model_type`` ``lfm2_moe``) on the training
path of ``apps/lm``: gated short-convolution layers ("conv") beside GQA
layers with a q/k norm, a leading dense layer, and a sigmoid router whose
bias picks experts and does not weight them, moved by the step against
the experts' load. Held to the benchmark's plain reference
(``chipbench/lm_conv_reference.py``: one copy, imported from there) at
the cell's rehearsal sizes on the CPU: d 64, 4 query / 2 K/V heads of
16, a router of 8 experts with 2 held, top-2, vocabulary 512, 5 layers
(conv + dense, attention, conv, conv, conv). And the cell's runner,
rehearsed from its committed files, clean and with faults planted.
"""

from __future__ import annotations

import dataclasses
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax.sharding import Mesh

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from chipbench import lastline  # noqa: E402
from chipbench import lm_conv_arith as arith  # noqa: E402
from chipbench import lm_conv_reference as ref  # noqa: E402
from parameter_server_tpu.apps.lm import trainer as lm_trainer  # noqa: E402
from parameter_server_tpu.models import kda as kdalib  # noqa: E402
from parameter_server_tpu.models import moe as moelib  # noqa: E402
from parameter_server_tpu.models import transformer as tfm  # noqa: E402

CONFIG = os.path.join(ROOT, "chipbench", "configs", "lfm2_ep8.json")
CELL = "lfm2_ep8.packed8k_mb1"
CONV_METRICS = {
    "lm_conv_step_mfu", "lm_conv_operator_share", "lm_conv_mix_roofline",
    "lm_conv_attention_share", "lm_conv_flash_attention_roofline",
    "lm_conv_moe_share", "lm_conv_expert_load_max_over_mean",
}
DEVICE_METRICS = {
    "step_device_ms", "device_idle_share", "idle_in_program_share",
    "idle_unattributed_share",
}
CHECKS = {
    "loss_trajectory", "router_arithmetic", "router_choices_same_input",
    "routing_agreement", "update_parity_own_routing", "update_parity",
    "second_update_parity", "examples_confirmed", "expert_bias",
    "nothing_compiles_or_falls_back_in_window", "losses_finite",
}


def toy_desc(**over) -> dict:
    desc = ref.description(CONFIG, rehearsal=True)
    desc.update(over)
    return desc


def mesh_of(n: int) -> Mesh:
    return Mesh(np.array(jax.devices()[:n]).reshape(n, 1), ("data", "server"))


def rel(a, b) -> float:
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.linalg.norm(a - b) / max(np.linalg.norm(b), 1e-30))


def toy_weights(m: dict, seed: int = 5) -> dict:
    """The reference's initial weights with a selection bias that is not
    0, so that it picks in every test that routes."""
    params = ref.weights(seed, m)
    biases = sorted(k for k in params if k.endswith(ref.BIAS))
    moved = 0.01 * jax.random.normal(
        jax.random.PRNGKey(seed + 1), (len(biases), m["experts"])
    )
    return {**params, **dict(zip(biases, moved))}


@pytest.fixture(scope="module")
def setup():
    desc = toy_desc()
    cfg = lm_trainer.model_from_description(desc)
    m = ref.model(desc)
    params = toy_weights(m)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 48), 0, 512)
    return desc, cfg, m, params, tokens


# -- the convolution ---------------------------------------------------------


def conv_loop(gates, taps, order=(0, 1, 2), shift=0):
    """The layer's equations token by token in float64: [B | C | x~] in
    ``order``, z_t = sum_j w_j u_{t - 2 + j + shift}, zero outside."""
    g = np.asarray(gates, np.float64)
    b, c, x = (np.split(g, 3, axis=-1)[i] for i in order)
    u = b * x
    w = np.asarray(taps, np.float64)
    n, s = w.shape[0], g.shape[1]
    z = np.zeros_like(u)
    for t in range(s):
        for j in range(n):
            at = t - (n - 1) + j + shift
            if 0 <= at < s:
                z[:, t] += w[j] * u[:, at]
    return c * z


@pytest.mark.parametrize("planted,order,shift", [
    ("as_written", (0, 1, 2), 0),
    ("b_and_c_swapped", (1, 0, 2), 0),
    ("c_and_x_swapped", (0, 2, 1), 0),
    ("taps_one_late", (0, 1, 2), 1),
    ("taps_one_early", (0, 1, 2), -1),
])
def test_the_conv_mix_is_the_equations(planted, order, shift):
    """``conv_mix`` against a loop of the equations; a chunk order or a
    tap alignment other than LFM2's is told."""
    gates = jax.random.normal(jax.random.PRNGKey(0), (2, 11, 3 * 8))
    taps = jax.random.normal(jax.random.PRNGKey(1), (3, 8))
    got = np.asarray(tfm.conv_mix(gates, taps))
    want = conv_loop(gates, taps, order, shift)
    if planted == "as_written":
        assert np.abs(got - want).max() < 1e-5
    else:
        assert np.abs(got - want).max() > 0.1


def test_the_conv_layer_sees_no_later_token(setup):
    _, cfg, _, params, tokens = setup
    mesh = mesh_of(1)
    layer0 = tfm.LMConfig(**{
        **{f.name: getattr(cfg, f.name) for f in dataclasses.fields(cfg)},
        "n_layers": 1, "layers": (("conv", "dense"),),
    })
    keep = {
        k: v for k, v in params.items() if "/" not in k or k[:3] == "l0/"
    }
    a = tfm.lm_forward(keep, tokens, layer0, mesh)
    b = tfm.lm_forward(keep, tokens.at[:, 30].set(7), layer0, mesh)
    assert np.allclose(a[:, :30], b[:, :30])
    assert not np.allclose(a[:, 30:33], b[:, 30:33])


# -- the router and its bias -------------------------------------------------


def sigmoid_cfg(**over):
    return moelib.TopKMoEConfig(
        n_experts=4, top_k=2, d_expert=8, scoring="sigmoid",
        expert_bias=True, **over,
    )


def test_a_bias_flips_a_choice_and_not_its_weight():
    """Scores 0.73, 0.71, 0.70, 0.10: the bias lifts the third over the
    second; the weights are the scores at the choices over their sum."""
    scores = jnp.array([0.73, 0.71, 0.70, 0.10])
    # one token whose logits are sigmoid^-1 of those scores
    router = jnp.diag(jnp.log(scores) - jnp.log1p(-scores))
    cfg = sigmoid_cfg()
    _, e0 = moelib.route_topk(jnp.ones((1, 4)), router, cfg)
    assert sorted(e0[0].tolist()) == [0, 1]
    bias = jnp.array([0.0, 0.0, 0.02, 0.0])
    w1, e1 = moelib.route_topk(jnp.ones((1, 4)), router, cfg, bias)
    assert sorted(e1[0].tolist()) == [0, 2]
    at = dict(zip(e1[0].tolist(), np.asarray(w1[0])))
    assert at[0] == pytest.approx(0.73 / 1.43, rel=1e-5)
    assert at[2] == pytest.approx(0.70 / 1.43, rel=1e-5)


def test_the_update_rule_moves_each_bias_against_its_load():
    bias = jnp.array([[0.5, 0.5, 0.5, 0.5], [0.0, 0.0, 0.0, 0.0]])
    load = jnp.array([[9, 1, 5, 5], [0, 0, 0, 20]])
    got = moelib.update_expert_bias(bias, load)
    assert np.allclose(got, [[0.499, 0.501, 0.5, 0.5],
                             [0.001, 0.001, 0.001, -0.001]])
    assert np.array_equal(got, ref.update_bias(bias, load))


def test_softmax_routing_with_a_bias_is_refused():
    with pytest.raises(ValueError, match="sigmoid scoring only"):
        moelib.TopKMoEConfig(n_experts=4, top_k=2, d_expert=8,
                             expert_bias=True)
    with pytest.raises(ValueError, match="'cosine'"):
        moelib.TopKMoEConfig(n_experts=4, top_k=2, d_expert=8,
                             scoring="cosine")


# -- the shares add up -------------------------------------------------------


def test_the_shares_add_up_to_the_uncut_reference_layer(setup):
    """The four shares of 2 held experts of 8 (the program's layer, told
    which experts it holds; no shared expert to count once) add up to
    the uncut reference layer, the bias picking alike in all."""
    desc, cfg, m, params, _ = setup
    lp = {k[3:]: v for k, v in params.items() if k.startswith("l1/")}
    experts = m["experts"]
    whole = {**lp, **{
        k: 5.0 * jax.random.normal(jax.random.PRNGKey(i), (experts,)
                                   + lp[k].shape[1:])
        for i, k in enumerate(("we_gate", "we_up", "we_down"))
    }}
    x = jax.random.normal(jax.random.PRNGKey(3), (2, 24, 64))
    with jax.default_matmul_precision("highest"):
        want, _ = ref.experts(whole, x, m, False, held=experts, offset=0)
        h2 = ref.rms(x, lp["ln2"], m["eps"])
        total = jnp.zeros_like(x)
        for offset in range(0, experts, m["held"]):
            share = dataclasses.replace(cfg.moe, expert_offset=offset)
            mine = {
                k: v[offset:offset + m["held"]] if k.startswith("we_") else v
                for k, v in whole.items()
            }
            y, _ = moelib.topk_moe_ffn(mine, h2, share, jnp.float32)
            total = total + y
    assert rel(total, want) < 1e-5


# -- descriptions ------------------------------------------------------------


def test_the_description_builds_the_layers_and_the_router(setup):
    _, cfg, _, params, _ = setup
    assert cfg.layer_kinds == (
        ("conv", "dense"), ("mha", "moe"), ("conv", "moe"), ("conv", "moe"),
        ("conv", "moe"),
    )
    assert cfg.tie_head and cfg.qk_norm and cfg.rope
    assert (cfg.conv_size, cfg.head_width, cfg.kv_heads) == (3, 16, 2)
    assert (cfg.rope_theta, cfg.rope_yarn, cfg.norm_eps) == (1e6, None, 1e-5)
    assert cfg.moe == moelib.TopKMoEConfig(
        n_experts=8, top_k=2, d_expert=32, experts_held=2,
        norm_topk_prob=True, scoring="sigmoid", expert_bias=True,
    )
    assert tfm.router_bias_names(cfg) == tuple(
        f"l{i}/router_bias" for i in range(1, 5)
    )
    want = jax.eval_shape(lambda: tfm.init_lm(jax.random.PRNGKey(0), cfg))
    assert {k: v.shape for k, v in want.items()} == {
        k: v.shape for k, v in params.items()
    }


@pytest.mark.parametrize("over,message", [
    (dict(conv_bias=True), "conv_bias true"),
    (dict(layer_types=["conv", "sliding_attention", "conv", "conv",
                       "conv"]), "'sliding_attention'"),
    (dict(layer_types=["conv"] * 4), "describes 4"),
    (dict(rope_parameters={"rope_theta": 1e6, "rope_type": "yarn"}),
     "rope_type 'yarn'"),
    (dict(scoring_func="softmax"), "expert_bias with softmax scoring"),
    (dict(num_dense_layers=6), "num_dense_layers 6"),
    (dict(model_type="lfm3"), "lfm2_moe"),
])
def test_a_description_of_what_is_not_built_is_refused(over, message):
    with pytest.raises(ValueError, match=message):
        lm_trainer.model_from_description(toy_desc(**over))


def test_serving_refuses_the_conv_layer_by_name():
    cfg = tfm.LMConfig(n_layers=1, layers=(("conv", "dense"),))
    with pytest.raises(NotImplementedError, match="'conv'.*last conv_size"):
        tfm.refuse_serving(cfg, "here")


def test_a_conv_layer_refuses_the_zigzag_layout():
    with pytest.raises(ValueError, match="zigzag"):
        tfm.LMConfig(
            n_layers=1, layers=(("conv", "dense"),),
            attention="ring_zigzag",
        )


# -- the program against the reference ---------------------------------------


def test_logits_match_the_reference_in_f32(setup):
    _, cfg, m, params, tokens = setup
    mesh = mesh_of(1)
    with jax.default_matmul_precision("highest"):
        mine = jax.jit(lambda p, t: tfm.lm_forward(p, t, cfg, mesh))(
            params, tokens
        )
    assert rel(mine, jax.jit(lambda p, t: ref.forward(p, t, m))(
        params, tokens
    )) < 1e-5


def test_loss_and_every_gradient_match_the_reference_in_f32(setup):
    """One program's loss and gradients (every trained leaf; none
    reaches a bias) against the reference's, and the blocked reference
    against the plain one: one compile of each, in one test."""
    _, cfg, m, params, tokens = setup
    mesh = mesh_of(1)
    with jax.default_matmul_precision("highest"):
        loss, grads = jax.jit(jax.value_and_grad(
            lambda p, t: tfm.lm_loss(p, t, cfg, mesh)
        ))(params, tokens)
    want, theirs = jax.jit(lambda p, t: ref.loss_and_grads(p, t, m))(
        params, tokens
    )
    blocked, by_block = jax.jit(
        lambda p, t: ref.loss_and_grads(p, t, m, True)
    )(params, tokens)
    assert abs(float(loss) - float(want)) < 1e-5
    assert abs(float(blocked) - float(want)) < 1e-6
    assert set(theirs) == set(ref.trained(params))
    worst = max((rel(grads[k], theirs[k]), k) for k in theirs)
    assert worst[0] < 1e-4, worst
    assert max(rel(by_block[k], theirs[k]) for k in theirs) < 1e-5
    biases = [k for k in grads if k.endswith(ref.BIAS)]
    assert len(biases) == 4
    assert all(float(jnp.abs(grads[k]).max()) == 0.0 for k in biases)


def test_two_steps_match_the_reference_and_its_bias_rule(setup):
    """Two steps of the program's trainer (f32, Adafactor factoring from
    8 as the rehearsal does) and of the reference's step, from the same
    weights and tokens, the reference at the program's choices: each
    trained leaf's update within 1e-3 relative L2 of the reference's
    (Adafactor divides by the root of the moments, so an entry whose
    gradient is rounding noise moves as far as any), and each bias
    after each step exactly the reference's rule over the choices."""
    _, cfg, m, params, tokens = setup
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
    # and it moved, by one step of the rate, in every column off the mean
    name = "l1/" + ref.BIAS
    moved = np.abs(mine[0][0][name] - np.asarray(params[name]))
    assert (moved > 0).sum() >= 6
    assert np.allclose(moved[moved > 0], 0.001, rtol=1e-3)


def test_the_optimizer_state_holds_no_bias(setup):
    _, cfg, _, params, _ = setup
    trainer = lm_trainer.build_trainer(cfg, mesh_of(1), optimizer="adafactor")
    trainer.load(params)
    shapes = {x.shape for x in jax.tree.leaves(trainer.opt)}
    assert (8,) not in shapes  # the biases' shape, of the router's width
    assert set(trainer.trained(params)) == set(ref.trained(params))


# -- counters, scopes, head --------------------------------------------------


def test_a_collect_counts_conv_token_layers_and_the_bias_spread(setup):
    from parameter_server_tpu.telemetry import registry as telemetry_registry

    _, cfg, _, params, tokens = setup
    trainer = lm_trainer.build_trainer(cfg, mesh_of(1), optimizer="adafactor")
    trainer.load({  # copies: the step donates them
        k: jnp.zeros_like(v) if k.endswith(ref.BIAS) else jnp.copy(v)
        for k, v in params.items()
    })
    reg = telemetry_registry.default_registry()

    def value(name, kind=None):
        series = reg.export_state().get(name, {}).get("series", [])
        return sum(
            s["value"] for s in series
            if kind is None or s["labels"].get("kind") == kind
        )

    before = value("ps_lm_conv_token_layers_total")
    full = value("ps_lm_attention_token_layers_total", "full")
    _, counts = trainer.collect(
        trainer.submit(trainer.place([np.asarray(tokens)]))
    )
    assert counts[tfm.CONV_TOKEN_LAYERS] == 2 * 48 * 4
    assert counts[tfm.ATTN_TOKEN_LAYERS].tolist() == [0, 2 * 48]
    assert value("ps_lm_conv_token_layers_total") - before == 384
    assert value("ps_lm_attention_token_layers_total", "full") - full == 96
    # one step from 0: every column moved by the rate, some each way
    assert value("ps_lm_expert_bias_spread") == pytest.approx(0.002)


def test_the_conv_layers_are_scoped(setup):
    _, cfg, _, params, tokens = setup
    mesh = mesh_of(1)
    text = jax.jit(
        lambda p, t: tfm.lm_forward(p, t, cfg, mesh)
    ).lower(params, tokens).as_text(debug_info=True)
    for scope in ("lm_conv_proj", "lm_conv_mix", "lm_conv_out", "lm_attn",
                  "lm_ffn", "lm_moe_route"):
        assert scope in text, scope


def test_the_tied_head_runs_in_the_compute_dtype(setup):
    """bf16 inputs and f32 accumulation, as the untied head; in f32 the
    byte LM's head is the product it was."""
    _, cfg, _, params, tokens = setup
    bf16 = dataclasses.replace(cfg, compute_dtype="bfloat16")
    jaxpr = jax.make_jaxpr(
        lambda p: tfm.lm_forward(p, tokens, bf16, mesh_of(1))
    )(params)
    head = [
        e for e in jaxpr.jaxpr.eqns if e.primitive.name == "dot_general"
    ][-1]
    assert [v.aval.dtype for v in head.invars] == [jnp.bfloat16] * 2
    assert head.outvars[0].aval.dtype == jnp.float32
    byte = tfm.LMConfig(n_layers=1)
    p = tfm.init_lm(jax.random.PRNGKey(0), byte)
    jaxpr = jax.make_jaxpr(
        lambda p: tfm.lm_forward(p, tokens[:, :5] % 256, byte, mesh_of(1))
    )(p)
    head = [
        e for e in jaxpr.jaxpr.eqns if e.primitive.name == "dot_general"
    ][-1]
    assert [v.aval.dtype for v in head.invars] == [jnp.float32] * 2


# -- the configuration and its arithmetic ------------------------------------


# the published config.json of LiquidAI/LFM2-24B-A2B, every number and
# key that says something about the model's shape
PUBLISHED = {
    "conv_L_cache": 3, "conv_bias": False, "hidden_size": 2048,
    "intermediate_size": 11776,
    "layer_types": ["conv", "conv"] + (
        ["full_attention", "conv", "conv", "conv"] * 9
    ) + ["full_attention", "conv"],
    "max_position_embeddings": 128000, "model_type": "lfm2_moe",
    "moe_intermediate_size": 1536, "norm_eps": 1e-05,
    "norm_topk_prob": True, "num_attention_heads": 32,
    "num_dense_layers": 2, "num_experts": 64, "num_experts_per_tok": 4,
    "num_hidden_layers": 40, "num_key_value_heads": 8,
    "rope_parameters": {"rope_theta": 1000000, "rope_type": "default"},
    "routed_scaling_factor": 1, "use_expert_bias": True,
    "vocab_size": 65536,
}


def test_the_configuration_file_keeps_every_published_number():
    with open(CONFIG) as f:
        mine = json.load(f)
    assert mine["source"] == (
        "https://huggingface.co/LiquidAI/LFM2-24B-A2B/blob/main/config.json"
    )
    assert len(PUBLISHED["layer_types"]) == 40
    assert PUBLISHED["layer_types"].count("full_attention") == 10
    cut = {"num_hidden_layers": 13, "num_dense_layers": 1,
           "num_experts": 8, "vocab_size": 8192}
    for key, value in PUBLISHED.items():
        if key == "layer_types":
            assert mine[key] == ["conv"] + value[2:14]
        else:
            assert mine[key] == cut.get(key, value), key
    assert set(mine["reduced"]) == set(cut) | {"layer_types", "training_data"}
    assert mine["published"] == {
        k: PUBLISHED[k] for k in (*cut, "layer_types")
    }
    assert mine["share"]["chips_per_layer"] * 8 == 64
    for key in ("head_dim", "tie_word_embeddings", "expert_bias_update",
                "optimizer", "initialisation", "packing", "rope_pairing"):
        assert key in mine["assumed"], key
    for key in ("precision", "correct", "deployment", "reduced_why"):
        assert key in mine, key


def test_the_arithmetic_is_the_issues():
    """1,196,018,048 trained parameters (and 12 x 64 biases); 61,440
    bytes a convolution token-layer of the mix; the flash kernels' 7
    score-sized products at head width 64 by the causal mask."""
    desc = ref.description(CONFIG)
    m = ref.model(desc)
    shapes = ref.shapes(m)
    count = lambda names: sum(  # noqa: E731
        int(np.prod(shapes[k])) for k in names
    )
    assert count(ref.trained(shapes)) == 1_196_018_048
    assert count(set(shapes) - set(ref.trained(shapes))) == 12 * 64
    assert arith.conv_matmul_params(desc) + 3 * 2048 == 16_783_360
    assert arith.attention_matmul_params(desc) == 10_485_760
    assert arith.mix_bytes(desc, 1) == 61_440
    full = 3 * 8192
    flash = arith.flash_kernels_flops(desc, 8192, full)
    assert flash == 7 * 2 * 64 * 32 * (8192 * 8193 // 2) * 3
    dense = arith.dense_params_per_token(desc)
    assert dense == (
        10 * 4 * 2048 ** 2 + 3 * 10_485_760 + 3 * 2048 * 11776
        + 12 * 2048 * 64 + 2048 * 8192
    )


def test_the_reference_imports_nothing_of_the_program():
    for name in ("lm_conv_reference.py", "lm_conv_arith.py"):
        with open(os.path.join(ROOT, "chipbench", name)) as f:
            source = f.read()
        assert "parameter_server_tpu" not in source.split('"""', 2)[2], name


def test_the_benchmark_lists_the_cell_for_each_conv_metric():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    by_name = {m["name"]: m for m in bench["per_layer"]}
    assert {n for n in by_name if n.startswith("lm_conv_")} == CONV_METRICS
    for name in CONV_METRICS:
        assert by_name[name]["workloads"] == [CELL], name
        assert by_name[name]["moves"] == "examples_per_s", name
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    entry = next(c for c in bench["configs"] if c["name"] == cell["config"])
    assert cell["chips"] == 1 and cell["traffic"] == "packed8k_mb1"
    assert len(cell["why"]) <= 200 and len(entry["why"]) <= 200
    with open(os.path.join(ROOT, entry["file"])) as f:
        assert json.load(f)["reduced"] == entry["reduced"]
    # appended after the cells before it (later cells come after it)
    assert bench["workloads"].index(cell) == 5
    assert bench["configs"].index(entry) == 5


# -- the cell's runner, rehearsed --------------------------------------------


def _with_config(change) -> None:
    forward = tfm.lm_forward_with_stats
    tfm.lm_forward_with_stats = lambda p, t, cfg, *rest: forward(
        p, t, change(cfg), *rest
    )


def _route_with(make) -> None:
    """``moe.route_topk`` as ``make(route_topk)`` computes it."""
    moelib.route_topk = make(moelib.route_topk)


def plant(fault: str) -> None:
    """A fault in the program, before anything is traced."""
    replace = dataclasses.replace
    mix = tfm.conv_mix
    if fault == "b_and_c_swapped":
        def planted(gates, taps):
            b, c, x = jnp.split(gates, 3, axis=-1)
            return mix(jnp.concatenate([c, b, x], -1), taps)

        tfm.conv_mix = planted
    elif fault == "taps_anti_causal":  # z_t from u_t, u_{t+1}, u_{t+2}
        conv = kdalib.causal_conv
        kdalib.causal_conv = lambda x, taps: conv(
            x[:, ::-1], taps
        )[:, ::-1]
    elif fault == "conv_over_x_alone":  # conv(x~) in place of conv(B x~)
        def planted(gates, taps):
            b, c, x = jnp.split(gates, 3, axis=-1)
            return c * kdalib.causal_conv(x, taps)

        tfm.conv_mix = planted
    elif fault == "softmax_scoring":
        _route_with(lambda route: lambda h, r, cfg, bias=None: route(
            h, r, replace(cfg, scoring="softmax", expert_bias=False)
        ))
    elif fault == "bias_in_the_weights":
        def bias_weights(route):
            def planted(h, r, cfg, bias=None):
                w, e = route(h, r, cfg, bias)
                s = jax.nn.sigmoid(jnp.dot(
                    h, r, precision=jax.lax.Precision.HIGHEST
                )) + bias
                at = jnp.take_along_axis(s, e, axis=-1)
                return at / at.sum(-1, keepdims=True), e

            return planted

        _route_with(bias_weights)
    elif fault == "routing_on_scores_alone":
        _route_with(lambda route: lambda h, r, cfg, bias=None: route(
            h, r, cfg
        ))
    elif fault == "bias_not_updated":
        moelib.update_expert_bias = lambda bias, load: bias
    elif fault == "qk_norm_dropped":
        _with_config(lambda cfg: replace(cfg, qk_norm=False))
    elif fault == "weights_in_bf16":
        from chipbench.apps import lm_conv

        build = lm_conv.Runner.build

        def planted_build(self, win):
            build(self, win)
            step = self.trainer.step

            def rounded(p, opt, *data):
                p, opt, loss, stats = step(p, opt, *data)
                return jax.tree.map(
                    lambda x: jax.lax.reduce_precision(x, 8, 7), p
                ), opt, loss, stats

            self.trainer.step = jax.jit(rounded, donate_argnums=(0, 1))

        lm_conv.Runner.build = planted_build
    elif fault != "none":
        raise ValueError(fault)


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


def rehearse(fault: str = "none", seed: int = 2147483659, traced: int = 0):
    # a traced run needs the traffic's traced stretch (0.5 s) in its window
    seconds = "2" if traced else "0.5"
    done = subprocess.run(
        [sys.executable, "-c",
         "import sys; sys.path[:0] = ['.', 'tests']; "
         "from test_lm_conv import child; "
         f"sys.exit(child({fault!r}, {str(seed)!r}, {str(traced)!r}, "
         f"True, {seconds!r}))"],
        cwd=ROOT, capture_output=True, text=True, timeout=600,
    )
    assert done.returncode == 0, done.stderr[-3000:]
    return json.loads(done.stdout.strip().splitlines()[-1]), done


def test_the_cell_rehearses_through_the_seam_traced():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    line, done = rehearse(seed=3000000019, traced=1)
    assert lastline.faults(line, bench, CELL, True) == []
    assert line["correct"] is True and line["failed"] == 0
    assert set(line["checks"]) == CHECKS
    layers = {
        m["name"] for m in lastline.cell_metrics(bench, CELL, "per_layer")
    }
    assert set(line["metrics"]) == (
        {"examples_per_s", "setup_s"} | CONV_METRICS | DEVICE_METRICS
    ) == {"examples_per_s", "setup_s"} | layers
    for name in CONV_METRICS:
        if name.endswith(("_mfu", "_roofline", "_share")):
            assert 0 < line["metrics"][name]["value"] <= 100, name
    value = lambda name: line["metrics"][name]["value"]  # noqa: E731
    assert 50 < value("lm_conv_operator_share") + value(
        "lm_conv_attention_share"
    ) + value("lm_conv_moe_share") <= 100
    notes = [json.loads(ln) for ln in done.stdout.splitlines()
             if ln.startswith('{"chipbench"')]
    rows = next(n for n in notes
                if n["chipbench"] == "lm_conv_held_rows_by_layer")
    assert len(rows["first_launch"]) == 4 and rows["expected"] == 64
    assert next(n for n in notes if n["chipbench"] == "lm_conv_mix")[
        "bound_by"
    ] == "memory"


# A rehearsal is a process of its own, 15 to 30 s of a worker: tier-1
# plants one fault of each check (the layer, the router's weights, its
# choices, the bias's rule); the rest run with ``-m slow``, and all nine
# were planted at the cell's size on the chip (PERF.md, the LFM2 cell)
slow = pytest.mark.slow


@pytest.mark.parametrize("fault,check", [
    ("b_and_c_swapped", "update_parity"),
    pytest.param("taps_anti_causal", "update_parity", marks=slow),
    pytest.param("conv_over_x_alone", "update_parity", marks=slow),
    pytest.param("softmax_scoring", "router_arithmetic", marks=slow),
    ("bias_in_the_weights", "router_arithmetic"),
    ("routing_on_scores_alone", "router_choices_same_input"),
    ("bias_not_updated", "expert_bias"),
    pytest.param("qk_norm_dropped", "update_parity", marks=slow),
    pytest.param("weights_in_bf16", "update_parity", marks=slow),
])
def test_a_planted_fault_turns_the_cell_incorrect(fault, check):
    """Each fault of the layer, the router, the bias's rule, the norm and
    the precision fails the check that should see it, by half its limit
    at least, and nothing compiles for it."""
    line, _ = rehearse(fault)
    assert line["correct"] is False
    failed = line["checks"][check]
    assert failed["ok"] is False
    assert failed["value"] > 1.5 * failed["limit"]
    for name in ("examples_confirmed", "losses_finite",
                 "nothing_compiles_or_falls_back_in_window"):
        assert line["checks"][name]["ok"] is True, name

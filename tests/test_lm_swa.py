"""Sliding-window GQA layers beside full-attention ones, each kind with
rotary tables of its own (plain; YaRN) and a q/k norm, on the training
path of ``apps/lm``, held to the benchmark's plain reference
(``chipbench/lm_swa_reference.py``: one copy, imported from there) at
small widths on the CPU: d 64, 4 query / 2 K/V heads of 32, window 32,
YaRN over an original length of 64, 4 experts top-2, vocabulary 512, 8
layers (window, window, window, full, twice over), 90 tokens a sequence.
"""

from __future__ import annotations

import dataclasses
import json
import math
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from chipbench import lm_reference  # noqa: E402
from chipbench import lm_swa_reference as ref  # noqa: E402
from parameter_server_tpu.apps.lm import trainer as lm_trainer  # noqa: E402
from parameter_server_tpu.models import latent_attention as latent  # noqa: E402
from parameter_server_tpu.models import transformer as tfm  # noqa: E402

CONFIG = os.path.join(ROOT, "chipbench", "configs", "mellum2_ep4.json")
LAYER_LEAVES = (
    "ln1", "ln2", "wq", "wk", "wv", "wo", "q_norm", "k_norm", "router",
    "we_gate", "we_up", "we_down",
)
LEAVES = ["emb", "head", "ln_f"] + [
    f"l{i}/{leaf}" for i in range(8) for leaf in LAYER_LEAVES
]
EXPERTS = 4


def small_desc(**over) -> dict:
    """The configuration's rehearsal sizes, all 4 experts held."""
    desc = ref.description(CONFIG, rehearsal=True)
    desc["num_experts"] = EXPERTS
    desc.update(over)
    return desc


def mesh_of(n: int) -> Mesh:
    return Mesh(np.array(jax.devices()[:n]).reshape(n, 1), ("data", "server"))


def rel(a, b) -> float:
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.linalg.norm(a - b) / max(np.linalg.norm(b), 1e-30))


@pytest.fixture(scope="module")
def setup():
    desc = small_desc()
    cfg = lm_trainer.model_from_description(desc)
    params = tfm.init_lm(jax.random.PRNGKey(0), cfg)
    # away from the flat start: norms and logits that matter
    params = jax.tree.map(
        lambda x: 5.0 * x if x.ndim > 1 else x
        * (1.0 + 0.1 * jnp.cos(jnp.arange(x.size, dtype=jnp.float32))),
        params,
    )
    # 90 tokens: past the window (32) and YaRN's original length (64)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 90), 0, 512)
    return desc, cfg, params, tokens


def loss_and_grads_of(cfg):
    mesh = mesh_of(1)
    return jax.jit(
        lambda p, t: jax.value_and_grad(tfm.lm_loss)(p, t, cfg, mesh)
    )


def reference_loss_and_grads(m, blocked=False):
    return jax.jit(lambda p, t: ref.loss_and_grads(p, t, m, blocked))


@pytest.fixture(scope="module")
def both_grads(setup):
    desc, cfg, params, tokens = setup
    with jax.default_matmul_precision("highest"):
        mine = loss_and_grads_of(cfg)(params, tokens)
    return mine, reference_loss_and_grads(ref.model(desc))(params, tokens)


# -- the model against the reference -----------------------------------------


def test_the_description_gives_the_layer_kinds_and_leaves(setup):
    desc, cfg, params, _ = setup
    assert [a for a, _ in cfg.layer_kinds] == ["swa", "swa", "swa", "mha"] * 2
    assert {f for _, f in cfg.layer_kinds} == {"moe"}
    assert sorted(params) == sorted(LEAVES)
    assert {k: v.shape for k, v in params.items()} == ref.shapes(
        ref.model(desc)
    )
    assert cfg.window == 32 and cfg.qk_norm and cfg.rope and not cfg.tie_head
    assert cfg.head_width == 32 and cfg.n_heads * cfg.head_width != cfg.d_model
    assert params["l0/wq"].shape == (64, 128)  # 4 heads of 32 over d 64
    assert params["l0/wk"].shape == (64, 64)  # 2 K/V heads
    assert params["l0/q_norm"].shape == params["l3/k_norm"].shape == (32,)
    # rotary tables per kind: the full layers' under YaRN, the factor as
    # the description gives it; the window layers' plain
    assert cfg.rope_theta == 500000.0 and cfg.swa_rope == tfm.Rope(500000.0)
    assert cfg.rope_yarn.factor == 16
    assert cfg.rope_yarn.original_max_position == 64
    assert cfg.rope_yarn.attention_factor == 1.2772588722239782
    assert (cfg.moe.n_experts, cfg.moe.top_k, cfg.moe.held) == (4, 2, 4)
    assert cfg.moe.n_shared == 0 and cfg.moe.routed_scaling_factor == 1.0
    assert not any(k.split("/")[-1].startswith("ws_") for k in params)


def test_the_cells_description_is_the_published_model_cut():
    cfg = lm_trainer.model_from_description(
        lm_trainer.load_description(CONFIG), remat=True, bf16=True
    )
    assert cfg.layer_kinds == (
        (("swa", "moe"),) * 3 + (("mha", "moe"),)
    ) * 2
    assert (cfg.vocab, cfg.d_model, cfg.n_heads, cfg.kv_heads) == (
        24576, 2304, 32, 4
    )
    assert cfg.head_width == 128 and cfg.window == 1024
    assert (cfg.moe.n_experts, cfg.moe.top_k, cfg.moe.held,
            cfg.moe.d_expert) == (64, 8, 16, 896)
    assert cfg.rope_yarn.original_max_position == 8192
    assert cfg.norm_eps == 1e-6 and cfg.remat


def test_logits_match_the_reference_in_f32(setup):
    desc, cfg, params, tokens = setup
    with jax.default_matmul_precision("highest"):
        mine = tfm.lm_forward(params, tokens, cfg, mesh_of(1))
        want = ref.forward(params, tokens, ref.model(desc))
    assert mine.dtype == jnp.float32 and mine.shape == (2, 90, 512)
    assert rel(mine, want) < 1e-5


def test_loss_matches_the_reference_in_f32(both_grads):
    (loss, _), (want, _) = both_grads
    assert abs(float(loss) - float(want)) < 1e-5


@pytest.mark.parametrize("leaf", LEAVES)
def test_every_gradient_leaf_matches_the_reference_in_f32(both_grads, leaf):
    (_, mine), (_, want) = both_grads
    assert np.linalg.norm(want[leaf]) > 0
    assert rel(mine[leaf], want[leaf]) < 3e-5, leaf


def test_remat_changes_nothing(setup, both_grads):
    desc, _, params, tokens = setup
    cfg = lm_trainer.model_from_description(desc, remat=True)
    with jax.default_matmul_precision("highest"):
        loss, grads = loss_and_grads_of(cfg)(params, tokens)
    (want, want_grads), _ = both_grads
    assert abs(float(loss) - float(want)) < 1e-6
    assert max(rel(grads[k], want_grads[k]) for k in LEAVES) < 1e-5


def test_the_blocked_reference_is_the_plain_one(setup, both_grads):
    """Each layer, (sequence, head), held expert and block of rows of
    the head under ``jax.checkpoint``: 2 x 89 rows are three whole
    blocks of 48 and a rest."""
    desc, _, params, tokens = setup
    m = ref.model(desc)
    old = ref.HEAD_BLOCK
    ref.HEAD_BLOCK = 48
    try:
        loss, grads = reference_loss_and_grads(m, True)(params, tokens)
    finally:
        ref.HEAD_BLOCK = old
    _, (want, want_grads) = both_grads
    assert abs(float(loss) - float(want)) < 1e-6
    assert max(rel(grads[k], want_grads[k]) for k in LEAVES) < 1e-5


def routed(k: str) -> bool:
    return "/we_" in k or "router" in k or k.endswith("ln2")


def test_the_bf16_path_stays_within_its_tolerance(setup):
    """bf16 matmul inputs and activations against the program in f32, at
    the weights ``init_lm`` draws (sigma 0.02): the loss
    within 2e-3 (5e-5 read), every leaf's gradient within 6% in relative
    L2 (4.1% read, the last layer's ``q_norm``; the matrices under 3%)
    but the routers' and the routed experts', within 35% (24.4% read,
    the last layer's router: with top-2 of 4 a choice that flips on a
    rounded router input moves a token between experts, and eight
    layers of them feed each other)."""
    desc, cfg, _, tokens = setup
    params = tfm.init_lm(jax.random.PRNGKey(0), cfg)
    low = lm_trainer.model_from_description(desc, bf16=True, remat=True)
    want, want_grads = loss_and_grads_of(cfg)(params, tokens)
    loss, grads = loss_and_grads_of(low)(params, tokens)
    assert abs(float(loss) - float(want)) < 2e-3
    worst = {k: rel(grads[k], want_grads[k]) for k in LEAVES}
    assert max(v for k, v in worst.items() if routed(k)) < 0.35, worst
    assert max(v for k, v in worst.items() if not routed(k)) < 0.06, worst


# -- the rotary tables, per kind ---------------------------------------------


def test_yarn_at_the_published_numbers():
    """dim(r) = 128 ln(8192 / (2 pi r)) / (2 ln 500000): low =
    floor(dim(32)) = 18, high = ceil(dim(1)) = 35; the ramp 0 up to
    dimension 18, 1 from 35 on; the factor 0.1 ln 16 + 1 as the config
    gives it. The program's frequencies (``latent_attention``'s, the one
    implementation) are the formula's."""
    with open(CONFIG) as f:
        rp = json.load(f)["rope_parameters"]
    full, sliding = rp["full_attention"], rp["sliding_attention"]
    low, high, ramp = ref.yarn_ramp(128, full)
    assert (low, high) == (18, 35)
    assert ramp[18] == 0.0 and ramp[35] == 1.0 and 0 < ramp[26] < 1
    assert abs(ramp[19] - 1 / 17) < 1e-12
    f, c = ref.rope_freq_and_factor(128, full)
    j = np.arange(64)
    plain = 500000.0 ** (-j / 64.0)
    np.testing.assert_allclose(f[:19], plain[:19], rtol=1e-12)
    np.testing.assert_allclose(f[35:], plain[35:] / 16, rtol=1e-12)
    assert c == 1.2772588722239782 == full["attention_factor"]
    assert abs(c - (0.1 * math.log(16) + 1)) < 1e-15
    cfg = lm_trainer.model_from_description(lm_trainer.load_description(CONFIG))
    mine = latent.rope_inv_freq(128, cfg.rope_theta, cfg.rope_yarn)
    np.testing.assert_allclose(mine, f, rtol=1e-12)
    assert latent.rope_attention_factor(cfg.rope_yarn) == c
    # a description that gives no factor gets YaRN's own
    bare = dataclasses.replace(cfg.rope_yarn, attention_factor=None)
    assert abs(latent.rope_attention_factor(bare) - c) < 1e-12
    f, c = ref.rope_freq_and_factor(128, sliding)
    np.testing.assert_allclose(f, plain, rtol=1e-12)
    assert c == 1.0 and cfg.swa_rope == tfm.Rope(500000.0, None)


@pytest.mark.parametrize("kind", ["sliding_attention", "full_attention"])
def test_the_programs_tables_are_the_formulas(kind):
    """At 8,192 positions neither kind's table is the identity, and the
    program's are the reference's to f32 rounding of the angle."""
    with open(CONFIG) as f:
        rp = json.load(f)["rope_parameters"][kind]
    cfg = lm_trainer.model_from_description(lm_trainer.load_description(CONFIG))
    rope = cfg.swa_rope if kind == "sliding_attention" else tfm.Rope(
        cfg.rope_theta, cfg.rope_yarn
    )
    pos = jnp.arange(8192)
    cos, sin = tfm._rope_tables(pos, 128, rope.theta, rope.yarn)
    want_cos, want_sin = ref.rope_tables(pos, 128, rp)
    assert cos.shape == (8192, 64) and cos.dtype == jnp.float32
    # an angle of up to 8,191 radians in f32: 5e-4 of rounding
    assert float(jnp.max(jnp.abs(cos - want_cos))) < 2e-3
    assert float(jnp.max(jnp.abs(sin - want_sin))) < 2e-3
    scale = 1.2772588722239782 if kind == "full_attention" else 1.0
    assert abs(float(cos[0, 0]) - scale) < 1e-6
    assert float(jnp.max(jnp.abs(cos[1:] - scale))) > 0.5


def test_the_two_kinds_rotate_by_tables_of_their_own(setup):
    """Swapping the kinds' tables, or dropping YaRN's, changes the
    logits: neither table stands in for the other."""
    desc, cfg, params, tokens = setup
    want = tfm.lm_forward(params, tokens, cfg, mesh_of(1))
    swapped = dataclasses.replace(
        cfg, rope_yarn=None, swa_rope=tfm.Rope(cfg.rope_theta, cfg.rope_yarn)
    )
    plain = dataclasses.replace(cfg, rope_yarn=None)
    for other in (swapped, plain):
        got = tfm.lm_forward(params, tokens, other, mesh_of(1))
        assert rel(got, want) > 1e-3


# -- the window --------------------------------------------------------------


def one_kind(cfg, kind: str, n_layers: int = 1):
    """``n_layers`` of one kind, one table; "mha" alone is full only
    without ``window`` (which spans every layer where none is "swa")."""
    return dataclasses.replace(
        cfg, n_layers=n_layers, layers=((kind, "moe"),) * n_layers,
        swa_rope=None, window=cfg.window if kind == "swa" else None,
    )


def test_a_window_layer_is_a_full_layer_up_to_the_window(setup):
    """Equal where the sequence is no longer than the window (32), and
    not where it is longer."""
    _, cfg, params, tokens = setup
    window, full = one_kind(cfg, "swa", 2), one_kind(cfg, "mha", 2)
    mesh = mesh_of(1)
    short = tokens[:, :32]
    a = tfm.lm_forward(params, short, window, mesh)
    b = tfm.lm_forward(params, short, full, mesh)
    assert rel(a, b) < 1e-6
    long = tokens[:, :48]
    a = tfm.lm_forward(params, long, window, mesh)
    b = tfm.lm_forward(params, long, full, mesh)
    assert rel(a[:, :32], b[:, :32]) < 1e-6 and rel(a[:, 32:], b[:, 32:]) > 1e-3


def test_the_windows_edge(setup):
    """One windowed layer: position t sees key u where t - u = 31 and
    not where t - u = 32. Another token at position 0 changes the logits
    at positions 0-31 and at none from 32 on; in a full layer at all."""
    _, cfg, params, tokens = setup
    mesh = mesh_of(1)
    other = tokens.at[:, 0].set((tokens[:, 0] + 1) % 512)
    for kind, reach in (("swa", 32), ("mha", 48)):
        one = one_kind(cfg, kind)
        a = np.asarray(tfm.lm_forward(params, tokens[:, :48], one, mesh))
        b = np.asarray(tfm.lm_forward(params, other[:, :48], one, mesh))
        moved = np.abs(a - b).max(axis=(0, 2)) > 0
        assert moved[:reach].all() and not moved[reach:].any(), kind


def test_query_head_h_reads_kv_head_h_over_the_group(setup):
    """4 query heads over 2 K/V heads: head h reads K/V head h // 2 (1
    reads 0, 2 reads 1: h mod 2 would say 1 and 0). ``wo`` keeps one
    query head's rows; the values of one K/V head are changed."""
    _, cfg, params, tokens = setup
    one, mesh = one_kind(cfg, "mha"), mesh_of(1)
    for head in range(4):
        wo = jnp.zeros_like(params["l0/wo"]).at[32 * head:32 * (head + 1)].set(
            params["l0/wo"][32 * head:32 * (head + 1)]
        )
        kept = {**params, "l0/wo": wo}
        want = tfm.lm_forward(kept, tokens, one, mesh)
        for kv in range(2):
            wv = kept["l0/wv"].at[:, 32 * kv:32 * (kv + 1)].multiply(-1.0)
            got = tfm.lm_forward({**kept, "l0/wv": wv}, tokens, one, mesh)
            assert (rel(got, want) > 1e-4) == (kv == head // 2), (head, kv)


def test_a_byte_lm_with_a_window_still_windows_every_layer():
    """No ``layers``: ``window`` spans every layer, one table, as ever."""
    cfg = tfm.LMConfig(n_layers=2, window=8, rope=True)
    assert [a for a, _ in cfg.layer_kinds] == ["mha", "mha"]
    params = tfm.init_lm(jax.random.PRNGKey(0), cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (1, 24), 0, 256)
    other = tokens.at[:, 0].set((tokens[:, 0] + 1) % 256)
    mesh = mesh_of(1)
    a, stats = tfm.lm_forward_with_stats(params, tokens, cfg, mesh)
    b = tfm.lm_forward(params, other, cfg, mesh)
    moved = np.abs(np.asarray(a - b)).max(axis=(0, 2)) > 0
    # two layers of 8: position 0 reaches position 14 and no further
    assert moved[:15].all() and not moved[15:].any()
    assert tfm.ATTN_TOKEN_LAYERS not in stats
    every = dataclasses.replace(cfg, layers=(("swa", "dense"),) * 2)
    assert rel(tfm.lm_forward(params, tokens, every, mesh), a) < 1e-6


def test_a_swa_layer_needs_a_window_and_a_flash_mode():
    with pytest.raises(ValueError, match="needs LMConfig.window"):
        tfm.LMConfig(n_layers=1, layers=(("swa", "dense"),))
    with pytest.raises(ValueError, match="flash attention mode"):
        tfm.LMConfig(
            n_layers=1, layers=(("swa", "dense"),), window=4, attention="ring"
        )
    with pytest.raises(ValueError, match="need LMConfig.rope"):
        tfm.LMConfig(swa_rope=tfm.Rope(500000.0))


# -- counters and scopes -----------------------------------------------------


def test_the_step_returns_token_layers_by_kind(setup):
    _, cfg, params, tokens = setup
    _, stats = tfm.lm_forward_with_stats(params, tokens, cfg, mesh_of(1))
    assert tfm.ATTN_TOKEN_LAYER_KINDS == ("window", "full")
    assert stats[tfm.ATTN_TOKEN_LAYERS].tolist() == [2 * 90 * 6, 2 * 90 * 2]
    assert tfm.ATTN_TOKEN_LAYERS in tfm.STEP_COUNTS


def test_a_collect_counts_token_layers_by_kind(setup):
    from parameter_server_tpu.telemetry import registry as telemetry_registry

    _, cfg, params, tokens = setup
    trainer = lm_trainer.build_trainer(cfg, mesh_of(1), optimizer="adafactor")
    trainer.load(params)
    name = "ps_lm_attention_token_layers_total"
    reg = telemetry_registry.default_registry()

    def by_kind():
        return {
            s["labels"]["kind"]: s["value"]
            for s in reg.export_state().get(name, {}).get("series", [])
        }

    before = by_kind()
    _, counts = trainer.collect(
        trainer.submit(trainer.place([np.asarray(tokens)]))
    )
    assert counts[tfm.ATTN_TOKEN_LAYERS].tolist() == [1080, 360]
    after = by_kind()
    assert after["window"] - before.get("window", 0) == 1080
    assert after["full"] - before.get("full", 0) == 360


def test_window_and_full_layers_are_scoped_apart_and_together(setup):
    """``lm_attn/attn_window`` and ``lm_attn/attn_full`` in the lowered
    step of a model that has both; the accepted cells' models keep
    ``lm_attn`` alone."""
    _, cfg, params, tokens = setup
    mesh = mesh_of(1)
    text = jax.jit(
        lambda p, t: tfm.lm_forward(p, t, cfg, mesh)
    ).lower(params, tokens).as_text(debug_info=True)
    assert "lm_attn/attn_window" in text and "lm_attn/attn_full" in text
    plain = tfm.LMConfig(n_layers=1, window=8)
    p = tfm.init_lm(jax.random.PRNGKey(0), plain)
    text = jax.jit(
        lambda p, t: tfm.lm_forward(p, t, plain, mesh)
    ).lower(p, tokens % 256).as_text(debug_info=True)
    assert "lm_attn" in text and "attn_window" not in text
    assert "attn_full" not in text


# -- the shares add up -------------------------------------------------------


@pytest.mark.parametrize("held", [1, 2, 4])
def test_the_shares_add_up_to_the_uncut_reference_layer(held):
    """The parts of the expert layer's result that all 4 / ``held``
    shares give (the program's layer, told which experts it holds; no
    shared expert to count once) are the uncut reference layer. 1 held
    of 4 is the cell's quarter, but 48 tokens x 2 choices are a buffer
    under a tile: still whole, no tail (the quarter's head and tail are
    held to the reference in ``test_lm_mla_moe.py``)."""
    from parameter_server_tpu.models import moe as moelib

    desc = small_desc()
    cfg = lm_trainer.model_from_description(desc)
    m = ref.model(desc)
    params = tfm.init_lm(jax.random.PRNGKey(2), cfg)
    lp = {k[3:]: 5.0 * v for k, v in params.items() if k.startswith("l1/")}
    x = jax.random.normal(jax.random.PRNGKey(3), (2, 24, 64))
    with jax.default_matmul_precision("highest"):
        whole, _ = lm_reference.experts(lp, x, m, blocked=False)
        h2 = lm_reference.rms(x, lp["ln2"], m["eps"])
        total = jnp.zeros_like(x)
        for offset in range(0, EXPERTS, held):
            share = dataclasses.replace(
                cfg.moe, experts_held=held, expert_offset=offset
            )
            mine = {
                k: v[offset:offset + held] if k.startswith("we_") else v
                for k, v in lp.items()
            }
            y, _ = moelib.topk_moe_ffn(mine, h2, share, jnp.float32)
            part, _ = ref.experts(
                mine, x, m, blocked=False, held=held, offset=offset
            )
            assert rel(y, part) < 1e-5, offset
            total = total + y
    assert rel(total, whole) < 1e-5


# -- descriptions ------------------------------------------------------------


def _rope(**over):
    rp = small_desc()["rope_parameters"]
    return {**rp, "full_attention": {**rp["full_attention"], **over}}


@pytest.mark.parametrize("over,message", [
    (dict(mlp_layer_types=["sparse"] * 7 + ["dense"]), "'dense' entry"),
    (dict(use_sliding_window=False), "use_sliding_window false"),
    (dict(rope_parameters=_rope(rope_type="llama3")), "rope_type 'llama3'"),
    (dict(attention_bias=True), "attention_bias true"),
    (dict(tie_word_embeddings=True), "tie_word_embeddings true"),
    (dict(layer_types=["sliding_attention"] * 7), "describe 7"),
    (dict(layer_types=["chunked_attention"] * 8), "'chunked_attention'"),
    (dict(model_type="mellum9"), "mistral4, solar_open2, mellum"),
    (dict(n_group=2), "group-limited"),
])
def test_a_description_of_what_is_not_built_is_refused(over, message):
    with pytest.raises(ValueError, match=message):
        lm_trainer.model_from_description(small_desc(**over))


def test_full_layers_alone_are_described_without_a_window():
    cfg = lm_trainer.model_from_description(small_desc(
        layer_types=["full_attention"] * 8, use_sliding_window=False
    ))
    assert cfg.window is None and cfg.swa_rope is None
    assert {a for a, _ in cfg.layer_kinds} == {"mha"}


def _config(name: str):
    return lm_trainer.model_from_description(
        lm_trainer.load_description(
            os.path.join(ROOT, "chipbench", "configs", name)
        ), remat=True, bf16=True,
    )


def test_the_accepted_descriptions_build_the_config_they_built():
    """``mistral4`` and ``solar_open2`` descriptions give the
    ``LMConfig`` they gave before this family: every field that is new
    at its default, no "swa" layer, no table of a second kind."""
    from parameter_server_tpu.models.kda import KDAConfig
    from parameter_server_tpu.models.moe import TopKMoEConfig

    mistral = _config("mistral_small4_ep16.json")
    solar = _config("solar_open2_ep40.json")
    for cfg in (mistral, solar):
        assert (cfg.window, cfg.rope_yarn, cfg.swa_rope, cfg.qk_norm) == (
            None, None, None, False
        )
        assert not cfg.rope
    assert mistral.layer_kinds == (("mla", "moe"),) * 4
    assert mistral.mla.yarn == latent.YarnRope(
        factor=128, original_max_position=8192, beta_fast=32, beta_slow=1,
        mscale=1, mscale_all_dim=1, position_scale_beta=0.1,
    )
    assert mistral.mla.yarn.attention_factor is None
    assert solar == tfm.LMConfig(
        vocab=24576, d_model=4096, n_heads=64, n_layers=4, d_ff=10240,
        attention="ring_flash", remat=True, compute_dtype="bfloat16",
        tie_head=False, norm="rmsnorm", norm_eps=1e-5, ffn_act="swiglu",
        scale_emb=False, n_kv_heads=8, head_dim=128, attn_gate=True,
        rope=False,
        layers=(("mha", "moe"),) + (("kda", "moe"),) * 3,
        moe=TopKMoEConfig(
            n_experts=320, top_k=8, d_expert=1280, n_shared=1,
            experts_held=8, expert_offset=0, norm_topk_prob=True,
            routed_scaling_factor=1.0,
        ),
        kda=KDAConfig(n_heads=64, head_dim=128, conv_size=4, gate_rank=128),
    )


# -- the CLI and the file ----------------------------------------------------


@pytest.fixture(scope="module")
def toy_file(tmp_path_factory):
    path = tmp_path_factory.mktemp("swa") / "toy.json"
    with open(path, "w") as f:
        json.dump(ref.description(CONFIG, rehearsal=True), f)
    return str(path)


def test_the_cli_trains_a_described_model(toy_file, monkeypatch, capsys):
    from parameter_server_tpu.apps.lm import main as lm_main

    devices = jax.devices()[:1]
    monkeypatch.setattr(jax, "devices", lambda *a, **k: devices)
    lm_main.run([
        "--model-config", toy_file, "--optimizer", "adafactor", "--bf16",
        "--remat", "--steps", "4", "--seq-len", "128", "--batch", "1",
    ])
    assert "loss" in capsys.readouterr().out


def test_the_cli_refuses_to_generate_by_name(toy_file, capsys):
    from parameter_server_tpu.apps.lm import main as lm_main

    with pytest.raises(SystemExit):
        lm_main.run(
            ["--model-config", toy_file, "--steps", "1", "--prompt", "x"]
        )
    assert "'swa' beside 'mha'" in capsys.readouterr().err


def test_the_configuration_file_keeps_every_published_number():
    """Every key of the catalog row's config under the same value, but
    what ``reduced`` lists: the three counts, and the two lists of layer
    types cut to the layers kept."""
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        pytest.skip("no catalog here")
    with open(catalog) as f:
        row = next(
            json.loads(line) for line in f
            if '"Mellum2-12B-A2.5B-Instruct"' in line
        )
    with open(CONFIG) as f:
        mine = json.load(f)
    assert mine["source"] == row["source_url"]
    cut = {"num_hidden_layers": 8, "num_experts": 16, "vocab_size": 24576}
    for key, value in row["config"].items():
        if key in ("layer_types", "mlp_layer_types"):
            assert mine[key] == value[:8], key
        else:
            assert mine[key] == cut.get(key, value), key
    assert set(mine["reduced"]) == set(cut) | {
        "layer_types", "mlp_layer_types", "training_data"
    }
    assert mine["published"] == {k: row["config"][k] for k in cut}
    assert mine["share"]["chips_per_layer"] * 16 == 64
    assert next(iter(mine["assumed"])) == "qk_norm"
    for key in ("router_scoring", "multi_token_prediction", "optimizer",
                "initialisation", "packing"):
        assert key in mine["assumed"], key
    for key in ("precision", "correct", "deployment", "reduced_why"):
        assert key in mine, key
    m = ref.model(ref.description(CONFIG))
    assert sum(
        int(np.prod(shape)) for shape in ref.shapes(m).values()
    ) == 1_077_059_840

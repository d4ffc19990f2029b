"""Latent attention and the dropless top-k expert layer on the training
path of ``apps/lm``, held to the benchmark's plain reference
(``chipbench/lm_reference.py``: one copy, imported from there) at small
widths on the CPU: d 64, 4 heads, q/kv ranks 32/16, 8 experts top-2
beside 1 shared, vocabulary 512, 2 layers.
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

from chipbench import lm_reference as ref  # noqa: E402
from conftest import jaxpr_eqns, pallas_calls  # noqa: E402
from parameter_server_tpu.apps.lm import trainer as lm_trainer  # noqa: E402
from parameter_server_tpu.models import latent_attention as latent  # noqa: E402
from parameter_server_tpu.models import moe as moelib  # noqa: E402
from parameter_server_tpu.models import transformer as tfm  # noqa: E402

CONFIG = os.path.join(ROOT, "chipbench", "configs", "mistral_small4_ep16.json")
LAYER_LEAVES = (
    "ln1", "ln2", "wq_a", "q_norm", "wq_b", "wkv_a", "kv_norm", "wkv_b", "wo",
    "router", "we_gate", "we_up", "we_down", "ws_gate", "ws_up", "ws_down",
)
LEAVES = ["emb", "head", "ln_f"] + [
    f"l{i}/{leaf}" for i in range(2) for leaf in LAYER_LEAVES
]


def small_desc(**over) -> dict:
    """The configuration's rehearsal sizes, all 8 experts held."""
    desc = ref.description(CONFIG, rehearsal=True)
    desc["n_routed_experts"] = 8
    desc["published"] = {**desc["published"], "n_routed_experts": 8}
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
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 96), 0, 512)
    return desc, cfg, params, tokens


@pytest.fixture(scope="module")
def both_grads(setup):
    desc, cfg, params, tokens = setup
    with jax.default_matmul_precision("highest"):
        mine = jax.value_and_grad(tfm.lm_loss)(params, tokens, cfg, mesh_of(1))
    return mine, ref.loss_and_grads(params, tokens, ref.model(desc))


def test_the_description_gives_the_layer_kinds_and_leaves(setup):
    _, cfg, params, _ = setup
    assert cfg.layer_kinds == (("mla", "moe"),) * 2
    assert sorted(params) == sorted(LEAVES)
    assert params["head"].shape == (64, 512) and not cfg.tie_head
    assert params["l0/router"].shape == (64, 8)
    assert params["l0/we_gate"].shape == (8, 64, 32)


def test_logits_match_the_reference_in_f32(setup):
    desc, cfg, params, tokens = setup
    with jax.default_matmul_precision("highest"):
        mine = tfm.lm_forward(params, tokens, cfg, mesh_of(1))
        want = ref.forward(params, tokens, ref.model(desc))
    assert mine.dtype == jnp.float32 and mine.shape == (2, 96, 512)
    assert rel(mine, want) < 1e-5


def test_loss_matches_the_reference_in_f32(both_grads):
    (loss, _), (want, _) = both_grads
    assert abs(float(loss) - float(want)) < 1e-5


@pytest.mark.parametrize("leaf", LEAVES)
def test_every_gradient_leaf_matches_the_reference_in_f32(both_grads, leaf):
    (_, mine), (_, want) = both_grads
    assert np.linalg.norm(want[leaf]) > 0
    assert rel(mine[leaf], want[leaf]) < 2e-5, leaf


def test_remat_changes_nothing(setup, both_grads):
    desc, _, params, tokens = setup
    cfg = lm_trainer.model_from_description(desc, remat=True)
    with jax.default_matmul_precision("highest"):
        loss, grads = jax.value_and_grad(tfm.lm_loss)(
            params, tokens, cfg, mesh_of(1)
        )
    (want, want_grads), _ = both_grads
    assert abs(float(loss) - float(want)) < 1e-6
    assert max(rel(grads[k], want_grads[k]) for k in LEAVES) < 1e-5


def _under_the_parents_policy(fn):
    """``fn()`` with every checkpoint policy cut to ``TOP_E`` alone: what
    a rematerialised layer kept before it kept the flash kernel's output
    and log-sum-exp."""
    save = jax.checkpoint_policies.save_only_these_names
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(
            jax.checkpoint_policies, "save_only_these_names",
            lambda *names: save(moelib.TOP_E),
        )
        return fn()


@pytest.fixture(scope="module")
def kept_and_recomputed(setup):
    """Loss and gradients of the rematerialised model, as it is and under
    the policy that makes the attention's residuals again."""
    desc, _, params, tokens = setup
    cfg = lm_trainer.model_from_description(desc, remat=True)
    run = lambda: jax.value_and_grad(tfm.lm_loss)(  # noqa: E731
        params, tokens, cfg, mesh_of(1)
    )
    return run(), _under_the_parents_policy(run)


def test_a_kept_attention_output_gives_the_recomputed_loss(
    kept_and_recomputed
):
    (kept, _), (again, _) = kept_and_recomputed
    assert np.isfinite(float(kept)) and float(kept) == float(again)


@pytest.mark.parametrize("leaf", LEAVES)
def test_a_kept_attention_output_gives_the_recomputed_gradient(
    kept_and_recomputed, leaf
):
    """A kept value is the value the recomputation made: to the last bit."""
    (_, kept), (_, again) = kept_and_recomputed
    assert np.any(np.asarray(kept[leaf]))
    np.testing.assert_array_equal(kept[leaf], again[leaf])


def test_remat_runs_one_flash_forward_a_layer(setup, flash_as_on_the_chip):
    """The gradient program holds forward, dq and dkv for every ``mla``
    layer; under the parent's policy the forward twice."""
    desc, _, params, tokens = setup
    cfg = lm_trainer.model_from_description(desc, remat=True)
    n_mla = sum(att == "mla" for att, _ in cfg.layer_kinds)
    # a function made anew for each count: make_jaxpr caches by function
    count = lambda: pallas_calls(  # noqa: E731
        jax.grad(lambda p: tfm.lm_loss(p, tokens, cfg, mesh_of(1))), params
    )
    assert n_mla == 2 and count() == 3 * n_mla
    assert _under_the_parents_policy(count) == 4 * n_mla


def test_the_blocked_reference_is_the_plain_one(setup, both_grads):
    desc, _, params, tokens = setup
    loss, grads = ref.loss_and_grads(
        params, tokens, ref.model(desc), blocked=True
    )
    _, (want, want_grads) = both_grads
    assert abs(float(loss) - float(want)) < 1e-6
    assert max(rel(grads[k], want_grads[k]) for k in LEAVES) < 1e-5


def test_the_bf16_path_stays_within_its_tolerance(setup, both_grads):
    """bf16 matmul inputs and activations, f32 accumulation and f32
    router, norms' statistics, softmaxes and loss: the loss within 2e-3
    of the f32 reference, and every leaf's gradient within 5% in
    relative L2 (bf16 keeps 8 bits: 0.4% a rounding, a few dozen
    roundings deep; 3.2% is the most read here) but the router's and the
    routed experts', within 12%: a top-k choice that flips on a rounded
    router INPUT moves a whole token between experts (7.1% read)."""
    desc, _, params, tokens = setup
    cfg = lm_trainer.model_from_description(desc, bf16=True, remat=True)
    loss, grads = jax.value_and_grad(tfm.lm_loss)(
        params, tokens, cfg, mesh_of(1)
    )
    _, (want, want_grads) = both_grads
    assert abs(float(loss) - float(want)) < 2e-3
    worst = {k: rel(grads[k], want_grads[k]) for k in LEAVES}
    routed = {k: v for k, v in worst.items() if "/we_" in k or "router" in k}
    assert max(routed.values()) < 0.12, routed
    assert max(v for k, v in worst.items() if k not in routed) < 0.05, worst


def test_a_sequence_sharded_mesh_gives_the_same_logits(setup):
    desc, cfg, params, tokens = setup
    with jax.default_matmul_precision("highest"):
        mine = tfm.lm_forward(params, tokens, cfg, mesh_of(4))
        want = ref.forward(params, tokens, ref.model(desc))
    assert rel(mine, want) < 1e-5


# -- the share ---------------------------------------------------------------


def _layer_inputs():
    key = jax.random.PRNGKey(3)
    full = moelib.TopKMoEConfig(n_experts=8, top_k=2, d_expert=32, n_shared=1)
    lp = moelib.init_topk_moe(key, 64, full, 0.3)
    lp["ln2"] = jnp.ones((64,))
    x = jax.random.normal(jax.random.PRNGKey(4), (2, 40, 64))
    return full, lp, x


@pytest.mark.parametrize("held", [1, 2, 4, 8])
def test_the_shares_add_up_to_the_uncut_reference_layer(held):
    """What all ``n_experts / experts_held`` shares compute, the shared
    expert counted once, is the uncut layer."""
    full, lp, x = _layer_inputs()
    m = ref.model(small_desc())
    with jax.default_matmul_precision("highest"):
        want, _ = ref.experts(lp, x, m, blocked=False)  # all 8, shared once
        h2 = latent.rms_norm(x, lp["ln2"], m["eps"])
        total = jnp.zeros_like(x)
        for offset in range(0, 8, held):
            share = moelib.TopKMoEConfig(
                n_experts=8, top_k=2, d_expert=32,
                n_shared=1 if offset == 0 else 0,
                experts_held=held, expert_offset=offset,
            )
            mine = {
                k: v[offset: offset + held] if k.startswith("we_") else v
                for k, v in lp.items()
            }
            y, _ = moelib.topk_moe_ffn(mine, h2, share, jnp.float32)
            total = total + y
            # and the reference, given the same share, computes the same
            part, _ = ref.experts(
                mine, x, m, blocked=False, held=held, offset=offset,
                shared=offset == 0,
            )
            assert rel(y, part) < 1e-5
    assert rel(total, want) < 1e-5


def test_every_assignment_is_a_row_of_some_share():
    full, lp, x = _layer_inputs()
    h2 = x.reshape(-1, 64)
    _, top_e = moelib.route_topk(h2, lp["router"], full)
    rows = 0
    for offset in range(0, 8, 2):
        share = moelib.TopKMoEConfig(
            n_experts=8, top_k=2, d_expert=32, experts_held=2,
            expert_offset=offset,
        )
        *_, valid, _, sizes = moelib.sort_by_expert(top_e, share)
        assert int(valid.sum()) == int(sizes.sum())
        rows += int(sizes.sum())
    assert rows == h2.shape[0] * 2


def test_dropless_under_a_router_forced_onto_one_expert():
    """Every token picks expert 5 first: it computes all of them (the
    buffer has a row for every assignment), and the result is the dense
    computation."""
    full, lp, x = _layer_inputs()
    lp["router"] = lp["router"].at[:, 5].set(0.0).at[0, 5].set(50.0)
    x = x.at[..., 0].set(jnp.abs(x[..., 0]) + 1.0)
    share = moelib.TopKMoEConfig(
        n_experts=8, top_k=2, d_expert=32, n_shared=1, experts_held=2,
        expert_offset=4,
    )
    mine = {k: v[4:6] if k.startswith("we_") else v for k, v in lp.items()}
    h2 = latent.rms_norm(x, lp["ln2"], 1e-6)
    with jax.default_matmul_precision("highest"):
        y, stats = moelib.topk_moe_ffn(mine, h2, share, jnp.float32)
        want, _ = ref.experts(
            mine, x, ref.model(small_desc()), False, held=2, offset=4
        )
    assert int(stats["expert_rows"][1]) == 80  # expert 5: every token
    assert rel(y, want) < 1e-5


def test_the_layer_returns_the_routers_input_choices_and_weights():
    """At every ``T // PROBE_TOKENS``-th token: what a caller needs to
    hold the router's arithmetic to a reference on the same input."""
    full, lp, x = _layer_inputs()
    x = jnp.tile(x, (1, 16, 1))  # 2 x 640 tokens: stride 5
    h2 = latent.rms_norm(x, lp["ln2"], 1e-6)
    _, stats = moelib.topk_moe_ffn(lp, h2, full, jnp.bfloat16)
    assert stats["probe_x"].shape == (256, 64)
    assert stats["probe_x"].dtype == jnp.bfloat16
    flat = h2.reshape(-1, 64).astype(jnp.bfloat16)
    np.testing.assert_array_equal(stats["probe_x"], flat[::5])
    np.testing.assert_array_equal(stats["probe_e"], stats["top_e"][::5])
    p = jax.nn.softmax(
        np.asarray(stats["probe_x"], np.float64) @ np.asarray(
            lp["router"], np.float64
        ), axis=-1,
    )
    at = np.take_along_axis(np.asarray(p), np.asarray(stats["probe_e"]), -1)
    assert np.abs(
        stats["probe_w"] - at / at.sum(-1, keepdims=True)
    ).max() < 1e-6


def test_a_rematerialised_layer_routes_as_its_forward_pass_did():
    """The choices are saved across ``jax.checkpoint`` (``TOP_E``): the
    recomputed forward reads them and does not choose again, where a
    near-tie could fall the other way by its own rounding."""
    desc = small_desc()
    cfg = lm_trainer.model_from_description(desc, remat=True, bf16=True)
    params = ref.weights(0, ref.model(desc))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 64), 0, 512)
    mesh = mesh_of(1)
    jaxpr = str(jax.make_jaxpr(
        jax.grad(lambda p: tfm.lm_loss(p, tokens, cfg, mesh))
    )(params))
    # one top_k a layer: the forward's; none in the recomputation
    assert jaxpr.count("top_k[") == cfg.n_layers, jaxpr.count("top_k[")


def test_the_router_computes_in_f32():
    """Weights within 1e-6 of a float64 softmax and top-k; the same
    computed in bf16 misses that by three orders of magnitude."""
    full, lp, x = _layer_inputs()
    h = x.reshape(-1, 64)
    w, e = moelib.route_topk(h, lp["router"], full)
    logits = np.asarray(h, np.float64) @ np.asarray(lp["router"], np.float64)
    p = np.exp(logits - logits.max(-1, keepdims=True))
    p /= p.sum(-1, keepdims=True)
    top = np.argsort(-p, axis=-1)[:, :2]
    want = np.take_along_axis(p, top, -1)
    want /= want.sum(-1, keepdims=True)
    np.testing.assert_array_equal(np.asarray(e), top)
    assert np.abs(np.asarray(w) - want).max() < 1e-6
    b = jnp.bfloat16
    low = jax.nn.softmax(h.astype(b) @ lp["router"].astype(b), axis=-1)
    low_w, _ = jax.lax.top_k(low, 2)
    low_w = (low_w / low_w.sum(-1, keepdims=True)).astype(jnp.float32)
    assert np.abs(np.asarray(low_w) - want).max() > 1e-3


def test_topk_config_refuses_a_share_outside_the_experts():
    with pytest.raises(ValueError, match="are not among"):
        moelib.TopKMoEConfig(
            n_experts=8, top_k=2, d_expert=4, experts_held=4, expert_offset=6
        )
    with pytest.raises(ValueError, match="top_k"):
        moelib.TopKMoEConfig(n_experts=4, top_k=5, d_expert=4)


# -- the head and the tail of the sorted buffer ------------------------------

# 1 of 16 experts held, top-2, 1,024 tokens: 2,048 rows, 128 expected, a
# head of 512 and a tail of 1,536
TAIL_TOKENS = 1024


def _tail_layer(routing: str = "alone", held: int = 1):
    """``(share, lp, x, m)``: a layer that holds expert 5 of 16, or with
    ``held`` 4 experts 4-7 (a quarter: 512 rows expected, a head of
    1,024 and a tail of 1,024). Left ``alone`` the router sends it about
    the expected rows; ``forced`` every token picks expert 5 first (and
    with 4 held some pick another held one second: past the head); at
    the ``boundary`` exactly the head's rows are held: the first 512
    tokens pick expert 5 and no other does, or with 4 held every token
    picks expert 5 and none picks 4, 6 or 7. The head is full and the
    tail empty."""
    full = moelib.TopKMoEConfig(n_experts=16, top_k=2, d_expert=32, n_shared=1)
    lp = moelib.init_topk_moe(jax.random.PRNGKey(3), 64, full, 0.3)
    offset = 5 if held == 1 else 4
    lp = {
        k: v[offset:offset + held] if k.startswith("we_") else v
        for k, v in lp.items()
    }
    lp["ln2"] = jnp.ones((64,))
    x = jax.random.normal(jax.random.PRNGKey(4), (2, TAIL_TOKENS // 2, 64))
    if routing != "alone":
        # the held expert reads the first coordinate alone; the others'
        # logits stay small, so its weight is neither 0 nor 1
        lp["router"] = (0.1 * lp["router"]).at[:, 5].set(0.0).at[0, 5].set(4.0)
        sign = 1.0
        if routing == "boundary" and held == 1:
            first = jnp.arange(TAIL_TOKENS).reshape(2, -1) < 512
            sign = jnp.where(first, 1.0, -1.0)
        elif routing == "boundary":
            others = jnp.array([4, 6, 7])
            lp["router"] = lp["router"].at[0, others].set(-4.0)
        x = x.at[..., 0].set(sign * (jnp.abs(x[..., 0]) + 1.0))
    share = dataclasses.replace(full, experts_held=held, expert_offset=offset)
    m = {
        **ref.model(small_desc()), "experts": 16, "held": held,
        "offset": offset,
    }
    return share, lp, x, m


@pytest.mark.parametrize("remat", [False, True], ids=["plain", "remat"])
@pytest.mark.parametrize("held,head,routing,tail", [
    (1, 512, "alone", 0), (1, 512, "forced", 1), (1, 512, "boundary", 0),
    (4, 1024, "alone", 0), (4, 1024, "forced", 1), (4, 1024, "boundary", 0),
])
def test_head_and_tail_are_the_reference_layer(
        held, head, routing, tail, remat):
    """Output and every gradient, whether the tail runs or not, also
    when the layer is rematerialised around the saved choices; with a
    sixteenth of the experts held (a head of 4 x the expected rows) and
    with a quarter (2 x: half the buffer)."""
    share, lp, x, m = _tail_layer(routing, held)
    assert moelib.head_rows(TAIL_TOKENS, share) == head
    weigh = jnp.cos(jnp.arange(x.size, dtype=jnp.float32)).reshape(x.shape)

    def mine(lp, x):
        h2 = latent.rms_norm(x, lp["ln2"], m["eps"])
        y, stats = moelib.topk_moe_ffn(lp, h2, share, jnp.float32)
        return jnp.sum(y * weigh), (y, stats)

    def theirs(lp, x):
        y, _ = ref.experts(lp, x, m, blocked=False)
        return jnp.sum(y * weigh), y

    if remat:
        mine = jax.checkpoint(
            mine,
            policy=jax.checkpoint_policies.save_only_these_names(moelib.TOP_E),
        )
    with jax.default_matmul_precision("highest"):
        (_, (y, stats)), (g_lp, g_x) = jax.value_and_grad(
            mine, argnums=(0, 1), has_aux=True
        )(lp, x)
        (_, want), (w_lp, w_x) = jax.value_and_grad(
            theirs, argnums=(0, 1), has_aux=True
        )(lp, x)
    rows = int(stats["expert_rows"].sum())
    if routing == "alone":
        assert 0 < rows < head
    elif routing == "boundary":
        assert rows == head
    else:
        assert rows > head and (held > 1 or rows == TAIL_TOKENS)
    assert stats["buffer_passes"].tolist() == [1, tail]
    assert rel(y, want) < 1e-5
    assert rel(g_x, w_x) < 1e-5
    for leaf in ("router", "we_gate", "we_up", "we_down"):
        assert float(jnp.abs(w_lp[leaf]).max()) > 1e-3, leaf
        assert rel(g_lp[leaf], w_lp[leaf]) < 1e-5, leaf


@pytest.mark.parametrize("tokens,held,experts,top_k,want", [
    # the three cells: 4 x 4,096 expected, a quarter of 65,536 rows; 4 x
    # 1,639 in whole tiles; 4 x 16,384 is the buffer, so half of it
    (16384, 8, 128, 4, 16384),
    (8192, 8, 320, 8, 6656),
    (8192, 16, 64, 8, 32768),
    (TAIL_TOKENS, 1, 16, 2, 512),
    (4096, 1, 128, 4, 512),  # 4 x 128 expected: one tile
    (4000, 3, 128, 4, 1536),  # 4 x 375 expected, rounded up to the tile
    (8192, 9, 64, 8, 32768),  # just past an eighth: half the buffer
    (8192, 24, 64, 8, 49152),  # three eighths: 2 x the expected rows
    (8192, 32, 64, 8, 65536),  # a half held: the whole buffer
    (80, 2, 8, 2, 160),  # fewer rows than a tile: the whole buffer
    (80, 1, 16, 2, 160),
    (16384, None, 128, 4, 65536),  # all held
])
def test_the_head_follows_the_share_of_the_experts_held(
        tokens, held, experts, top_k, want):
    cfg = moelib.TopKMoEConfig(
        n_experts=experts, top_k=top_k, d_expert=4, experts_held=held
    )
    assert moelib.head_rows(tokens, cfg) == want


@pytest.mark.parametrize("held,experts,conds", [
    (1, 16, 1), (4, 16, 1), (6, 16, 1), (8, 16, 0), (None, 16, 0),
])
def test_half_of_the_experts_held_means_no_conditional(
        held, experts, conds):
    """With no tail (half of the experts or more held) the traced layer
    has no ``cond``, forward or backward; with one, one each way (the
    recomputed forward's is the forward's again)."""
    cfg = moelib.TopKMoEConfig(
        n_experts=experts, top_k=2, d_expert=32, experts_held=held
    )
    lp = moelib.init_topk_moe(jax.random.PRNGKey(3), 64, cfg, 0.3)
    x = jnp.ones((TAIL_TOKENS, 64))

    def out(lp, x):
        return jnp.sum(moelib.topk_moe_ffn(lp, x, cfg, jnp.float32)[0])

    count = lambda f: sum(  # noqa: E731
        e.primitive.name == "cond"
        for e in jaxpr_eqns(jax.make_jaxpr(f)(lp, x).jaxpr)
    )
    assert count(out) == conds
    assert count(jax.grad(out, argnums=(0, 1))) == 2 * conds


def test_the_split_adds_no_scatter_add_as_wide_as_the_model():
    """Dispatch and combine stay gathers both ways, head and tail: the
    gradient's only scatter-adds are the router's own, onto the
    probabilities [T, experts], and those of the rows' weights onto
    ``top_w`` [T, k], one for each part."""
    share, lp, x, _ = _tail_layer()

    def out(lp, x):
        return jnp.sum(moelib.topk_moe_ffn(lp, x, share, jnp.float32)[0])

    jaxpr = jax.make_jaxpr(jax.grad(out, argnums=(0, 1)))(
        lp, x.reshape(-1, 64)
    ).jaxpr
    onto = [
        e.outvars[0].aval.shape for e in jaxpr_eqns(jaxpr)
        if e.primitive.name in ("scatter-add", "scatter_add")
    ]
    assert sorted(onto) == [(TAIL_TOKENS, 2)] * 2 + [(TAIL_TOKENS, 16)], onto


# -- rope --------------------------------------------------------------------


def test_yarn_frequencies_against_hand_written_values():
    """dim 8, theta 10000, factor 8 over an original length of 64,
    beta_fast 32, beta_slow 1. Correction dimensions: 8 ln(64/(32 2pi)) /
    (2 ln 1e4) = -0.497 -> low 0 (clamped); 8 ln(64/(2pi)) / (2 ln 1e4)
    = 1.008 -> high 2. Ramp over pairs 0..3: 0, 1/2, 1, 1: pair 0 keeps
    its frequency, pair 1 is the mean of divided and undivided, pairs 2
    and 3 are divided by 8."""
    yarn = latent.YarnRope(factor=8.0, original_max_position=64)
    got = latent.rope_inv_freq(8, 10000.0, yarn)
    plain = [1.0, 10000 ** -0.25, 10000 ** -0.5, 10000 ** -0.75]
    want = [plain[0], (plain[1] + plain[1] / 8) / 2, plain[2] / 8,
            plain[3] / 8]
    np.testing.assert_allclose(got, want, rtol=1e-12)
    np.testing.assert_allclose(
        ref.yarn_inv_freq(8, {
            "rope_type": "yarn", "rope_theta": 10000.0, "factor": 8.0,
            "original_max_position_embeddings": 64, "beta_fast": 32,
            "beta_slow": 1,
        }), want, rtol=1e-12,
    )
    np.testing.assert_allclose(
        latent.rope_inv_freq(8, 10000.0, None), plain, rtol=1e-12
    )


def test_the_softmax_scale_and_the_factor_on_cos_and_sin():
    mla = latent.MLAConfig(
        q_lora_rank=32, kv_lora_rank=16, qk_nope_head_dim=64,
        qk_rope_head_dim=64, v_head_dim=128,
        yarn=latent.YarnRope(
            factor=128.0, original_max_position=8192, mscale=1.0,
            mscale_all_dim=1.0,
        ),
    )
    m = 0.1 * math.log(128.0) + 1.0
    assert abs(m - 1.4852) < 1e-4
    assert latent.softmax_scale(mla) == pytest.approx(128 ** -0.5 * m * m)
    assert latent.rope_attention_factor(mla.yarn) == 1.0
    plain = latent.MLAConfig(
        q_lora_rank=32, kv_lora_rank=16, qk_nope_head_dim=8,
        qk_rope_head_dim=8, v_head_dim=16,
    )
    assert latent.softmax_scale(plain) == pytest.approx(0.25)


def test_interleaved_pairs_rotate_by_position_times_frequency():
    """x = e_0 (first member of pair 0) at position p turns into
    (cos p, sin p) on that pair; written de-interleaved, that is entries
    0 and rope/2."""
    mla = latent.MLAConfig(
        q_lora_rank=4, kv_lora_rank=4, qk_nope_head_dim=4,
        qk_rope_head_dim=4, v_head_dim=8,
    )
    pos = jnp.array([0, 1, 3])
    cos, sin = latent.rope_tables(pos, mla, 10000.0)
    x = jnp.zeros((3, 4)).at[:, 0].set(1.0).at[:, 3].set(2.0)
    got = np.asarray(latent.rotate_pairs(x, cos, sin, interleave=True))
    f1 = 10000.0 ** -0.5  # pair 1's frequency
    for i, p in enumerate([0.0, 1.0, 3.0]):
        want = [math.cos(p), -2 * math.sin(p * f1),
                math.sin(p), 2 * math.cos(p * f1)]
        np.testing.assert_allclose(got[i], want, atol=1e-6)


def test_the_rotation_leaves_every_score_as_the_reference_has_it():
    m = ref.model(small_desc())
    cfg = lm_trainer.model_from_description(small_desc())
    pos = jnp.arange(50)
    q = jax.random.normal(jax.random.PRNGKey(5), (1, 50, 4, 8))
    k = jax.random.normal(jax.random.PRNGKey(6), (1, 50, 1, 8))
    tables = latent.rope_tables(pos[None, :, None], cfg.mla, cfg.rope_theta)
    mine = jnp.einsum(
        "bqhd,bkgd->bhqk", latent.rotate_pairs(q, *tables, True),
        latent.rotate_pairs(k, *tables, True),
    )
    want = jnp.einsum(
        "bqhd,bkgd->bhqk", ref.rotate_interleaved(q, pos, m),
        ref.rotate_interleaved(k, pos, m),
    )
    assert rel(mine, want) < 1e-5


def test_the_position_scale_steps_at_the_original_length():
    yarn = latent.YarnRope(
        factor=8.0, original_max_position=64, position_scale_beta=0.1
    )
    got = np.asarray(latent.position_scale(
        jnp.array([0, 63, 64, 127, 128, 200]), yarn
    ))
    want = [1.0, 1.0, 1 + 0.1 * math.log(2), 1 + 0.1 * math.log(2),
            1 + 0.1 * math.log(3), 1 + 0.1 * math.log(4)]
    np.testing.assert_allclose(got, want, rtol=1e-6)
    assert latent.position_scale(jnp.arange(4), None) is None
    # the cell's 8,192 positions: exactly 1, as its file says
    cell = latent.YarnRope(
        factor=128.0, original_max_position=8192, position_scale_beta=0.1
    )
    assert np.all(np.asarray(latent.position_scale(jnp.arange(8192), cell))
                  == 1.0)


def test_positions_past_the_original_length_match_the_reference():
    """The toy original length is 64: positions 64..95 of the module's
    sequences carry a position scale above 1 and divided frequencies."""
    desc = small_desc()
    assert desc["rope_parameters"]["original_max_position_embeddings"] == 64
    cfg = lm_trainer.model_from_description(desc)
    assert cfg.mla.yarn.position_scale_beta == 0.1


# -- what does not serve -----------------------------------------------------


def _serving_calls():
    from parameter_server_tpu.models import speculative
    from parameter_server_tpu.serving import batcher

    prompt = jnp.zeros((1, 4), jnp.int32)

    def caches(cfg):
        byte = tfm.LMConfig()
        return tfm._alloc_kv_caches(byte, 1, 8)

    return {
        "lm_generate": lambda p, c: tfm.lm_generate(p, prompt, c, steps=2),
        "lm_beam_search": lambda p, c: tfm.lm_beam_search(
            p, prompt, c, steps=2, beam_width=2
        ),
        "_prefill": lambda p, c: tfm._prefill(p, c, prompt, *caches(c)),
        "_decode_step": lambda p, c: tfm._decode_step(
            p, c, prompt[:, 0], *caches(c), 0
        ),
        "_chunk_decode": lambda p, c: tfm._chunk_decode(
            p, c, prompt, *caches(c), jnp.zeros((1,), jnp.int32)
        ),
        "speculative_generate": lambda p, c: speculative.speculative_generate(
            p, c, p, c, prompt, steps=2, gamma=1
        ),
        "ContinuousBatcher": lambda p, c: batcher.ContinuousBatcher(
            p, c, p, c, batcher.BatcherConfig(slots=1, max_prompt=4, max_new=2)
        ),
    }


@pytest.mark.parametrize("entry", [
    "lm_generate", "lm_beam_search", "_prefill", "_decode_step",
    "_chunk_decode", "speculative_generate", "ContinuousBatcher",
])
@pytest.mark.parametrize("kind,word", [
    ("mla", "latent attention"), ("moe", "top-k expert layer"),
    ("kda", "gated delta-rule layer"),
    ("swa", "windowed layers beside full ones"),
    ("rope_by_kind", "rotary tables per kind"),
    ("qk_norm", "no q/k norm"),
])
def test_serving_entry_points_refuse_the_layer_kind_by_name(
    setup, entry, kind, word
):
    _, cfg, params, _ = setup
    if kind == "swa":  # a windowed layer beside a full one, dense FFNs
        cfg = tfm.LMConfig(
            n_layers=2, layers=(("swa", "dense"), ("mha", "dense")), window=4,
        )
    if kind == "rope_by_kind":  # every layer windowed, a table of its own
        cfg = tfm.LMConfig(
            n_layers=1, layers=(("swa", "dense"),), window=4, rope=True,
            swa_rope=tfm.Rope(500000.0),
        )
    if kind == "qk_norm":
        cfg = tfm.LMConfig(n_layers=1, qk_norm=True)
    if kind == "moe":  # plain heads, the new expert layer
        cfg = tfm.LMConfig(
            vocab=512, d_model=64, n_heads=4, n_layers=1,
            layers=(("mha", "moe"),), moe=cfg.moe,
        )
    if kind == "kda":  # a dense FFN under the linear-attention layer
        from parameter_server_tpu.models.kda import KDAConfig

        cfg = tfm.LMConfig(
            vocab=512, d_model=64, n_heads=4, n_layers=1,
            layers=(("kda", "dense"),),
            kda=KDAConfig(n_heads=4, head_dim=16, gate_rank=8),
        )
    with pytest.raises(NotImplementedError, match=word):
        _serving_calls()[entry](params, cfg)


def test_serving_refuses_rmsnorm_and_an_untied_head_too():
    cfg = tfm.LMConfig(norm="rmsnorm", tie_head=False)
    with pytest.raises(NotImplementedError, match="rmsnorm"):
        tfm.refuse_serving(cfg, "here")
    tfm.refuse_serving(tfm.LMConfig(moe_every=2), "here")  # the byte LM serves


@pytest.mark.parametrize("bad,match", [
    (dict(layers=(("mha", "dense"),)), "describes 1 layers"),
    (dict(layers=(("mla", "dense"), ("mha", "dense"))), "needs LMConfig.mla"),
    (dict(layers=(("mha", "moe"), ("mha", "dense"))), "needs LMConfig.moe"),
    (dict(layers=(("gqa", "dense"), ("mha", "dense"))), "is not one of"),
    (dict(norm="batchnorm"), "LMConfig.norm"),
    (dict(ffn_act="relu"), "LMConfig.ffn_act"),
])
def test_lmconfig_refuses_a_description_it_cannot_run(bad, match):
    with pytest.raises(ValueError, match=match):
        tfm.LMConfig(n_layers=2, **bad)


def test_the_byte_lm_describes_itself_by_layer():
    cfg = tfm.LMConfig(n_layers=4, moe_every=2)
    assert cfg.layer_kinds == (
        ("mha", "dense"), ("mha", "switch"), ("mha", "dense"),
        ("mha", "switch"),
    )


def test_a_gated_silu_ffn_under_rmsnorm_and_an_untied_head_trains():
    cfg = tfm.LMConfig(
        vocab=300, n_layers=1, norm="rmsnorm", ffn_act="swiglu",
        tie_head=False, scale_emb=False, rope=True,
    )
    params = tfm.init_lm(jax.random.PRNGKey(0), cfg)
    assert {"l0/w_gate", "l0/w_up", "l0/w_down", "head"} <= set(params)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 32), 0, 300)
    with jax.default_matmul_precision("highest"):
        logits = tfm.lm_forward(params, tokens, cfg, mesh_of(1))
        # the same layer by hand
        lp = tfm._layer_params(params, 0)
        x = params["emb"][tokens]
        h = latent.rms_norm(x, lp["ln1"], 1e-6)
        cos, sin = tfm._rope_tables(jnp.arange(32)[None, :, None], 16, 1e4)
        q, k, v = (
            (h @ lp[w]).reshape(2, 32, 4, 16) for w in ("wq", "wk", "wv")
        )
        q, k = tfm._rotate(q, cos, sin), tfm._rotate(k, cos, sin)
        s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / 4.0
        s = jnp.where(jnp.tril(jnp.ones((32, 32), bool)), s, -jnp.inf)
        att = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, -1), v)
        x = x + att.reshape(2, 32, 64) @ lp["wo"]
        h2 = latent.rms_norm(x, lp["ln2"], 1e-6)
        x = x + (jax.nn.silu(h2 @ lp["w_gate"]) * (h2 @ lp["w_up"])) @ lp[
            "w_down"
        ]
        want = latent.rms_norm(x, params["ln_f"], 1e-6) @ params["head"]
    assert rel(logits, want) < 1e-5
    grads = jax.grad(tfm.lm_loss)(params, tokens, cfg, mesh_of(1))
    assert all(float(jnp.abs(g).sum()) > 0 for g in grads.values())


# -- the description, and the weights the benchmark makes --------------------


@pytest.mark.parametrize("over,message", [
    ({"model_type": "deepseek_v3"}, "described here are mistral4, solar_open2"),
    ({"first_k_dense_replace": 1}, "leading dense layers"),
    ({"n_group": 2}, "group-limited"),
    ({"hidden_act": "gelu"}, "gated SiLU"),
])
def test_a_description_of_what_is_not_built_is_refused(over, message):
    with pytest.raises(ValueError, match=message):
        lm_trainer.model_from_description(small_desc(**over))


def test_the_reference_describes_the_leaves_init_lm_makes(setup):
    desc, cfg, params, _ = setup
    want = ref.shapes(ref.model(desc))
    assert {k: v.shape for k, v in params.items()} == want
    made = ref.weights(7, ref.model(desc))
    assert {k: (v.shape, v.dtype) for k, v in made.items()} == {
        k: (v, jnp.float32) for k, v in want.items()
    }


def test_both_initialisations_are_sigma_0_02_and_scales_of_one():
    """The file's ``assumed.initialisation``, by the program's
    ``init_lm`` and by the benchmark's ``weights``: neither is made
    from the other."""
    desc = small_desc()
    cfg = lm_trainer.model_from_description(desc)
    for made in (tfm.init_lm(jax.random.PRNGKey(3), cfg),
                 ref.weights(3, ref.model(desc))):
        for name, w in made.items():
            if w.ndim == 1:
                np.testing.assert_array_equal(w, 1.0)
            else:
                four_sigma = 4.0 / math.sqrt(w.size)  # of a mean of w / 0.02
                assert abs(float(jnp.std(w)) / 0.02 - 1) < four_sigma, name
                assert abs(float(jnp.mean(w))) / 0.02 < four_sigma, name
    a, b = ref.weights(3, ref.model(desc)), ref.weights(4, ref.model(desc))
    assert rel(a["l0/wo"], b["l0/wo"]) > 1.0  # the seed decides
    assert rel(a["l0/wo"], a["l1/wo"]) > 1.0  # a key a leaf


def test_the_trainer_adopts_weights_a_caller_made():
    desc = ref.description(CONFIG, rehearsal=True)
    cfg = lm_trainer.model_from_description(desc, remat=True)
    trainer = lm_trainer.build_trainer(cfg, mesh_of(1), optimizer="adafactor")
    made = ref.weights(11, ref.model(desc))
    trainer.load(dict(made))
    assert all(trainer.params[k] is made[k] for k in made)
    assert trainer.opt is not None
    with pytest.raises(ValueError, match="l0/wo"):
        trainer.load({**made, "l0/wo": made["l0/wo"][:, :8]})
    with pytest.raises(ValueError, match="l1/ws_up"):
        trainer.load({k: v for k, v in made.items() if k != "l1/ws_up"})


def test_a_given_of_minus_one_leaves_the_router_its_choice(setup):
    desc, _, params, tokens = setup
    m = ref.model(desc)
    own = jnp.full((2, tokens.size, 2), -1, jnp.int32)
    (a, chosen), ga = ref.loss_grads_choices(params, tokens, m)
    (b, _), gb = ref.loss_grads_choices(params, tokens, m, given=own)
    (c, _), gc = ref.loss_grads_choices(params, tokens, m, given=chosen)
    assert float(a) == float(b) == float(c)
    assert max(rel(gb[k], ga[k]) for k in ga) < 1e-6
    assert max(rel(gc[k], ga[k]) for k in ga) < 1e-6


# -- the trainer and the CLI -------------------------------------------------


def _toy_trainer(spl: int, seed: int = 3):
    desc = ref.description(CONFIG, rehearsal=True)
    cfg = lm_trainer.model_from_description(desc, remat=True)
    trainer = lm_trainer.build_trainer(
        cfg, mesh_of(1), optimizer="adafactor", lr=3e-3,
        steps_per_launch=spl,
    )
    trainer.init(seed)
    return trainer


def test_a_fused_launch_is_the_same_two_steps():
    batches = [
        np.asarray(jax.random.randint(jax.random.PRNGKey(i), (2, 64), 0, 512))
        for i in range(2)
    ]
    one, two = _toy_trainer(1), _toy_trainer(2)
    rows = 0
    for b in batches:
        loss, stats = one.collect(one.submit(one.place([b])))
        rows += stats["expert_rows"]
    fused_loss, fused = two.collect(two.submit(two.place(batches)))
    assert fused_loss == pytest.approx(loss, abs=1e-6)
    np.testing.assert_array_equal(fused["expert_rows"], rows)
    for k in one.params:
        assert rel(two.params[k], one.params[k]) < 1e-6, k
    with pytest.raises(ValueError, match="a launch is 2 batches"):
        two.place(batches[:1])


def _one_of_sixteen_trainer():
    """A trainer whose layers hold expert 0 of 16 (1,024 tokens: a head
    of 512 rows) under routers of zeros: every token's top-2 are experts
    0 and 1, so each layer's pass needs the tail."""
    desc = ref.description(CONFIG, rehearsal=True)
    desc["n_routed_experts"] = 1
    desc["published"] = {**desc["published"], "n_routed_experts": 16}
    cfg = lm_trainer.model_from_description(desc, remat=True)
    trainer = lm_trainer.build_trainer(cfg, mesh_of(1), optimizer="adafactor")
    made = ref.weights(3, ref.model(desc))
    trainer.load({
        k: jnp.zeros_like(v) if k.endswith("/router") else v
        for k, v in made.items()
    })
    return trainer


@pytest.mark.parametrize("make,shape,tail", [
    (lambda: _toy_trainer(1), (2, 64), 0),  # 4 of 8 held: no tail at all
    (_one_of_sixteen_trainer, (2, 512), 2),
], ids=["half_held", "tail_taken"])
def test_a_collect_counts_tokens_and_rows(make, shape, tail):
    from parameter_server_tpu.telemetry import registry as telreg

    trainer = make()
    batch = np.asarray(
        jax.random.randint(jax.random.PRNGKey(9), shape, 0, 512)
    )
    tokens = shape[0] * shape[1]

    def totals():
        state = telreg.default_registry().export_state()
        out = {
            name: sum(s["value"] for s in state[name]["series"])
            for name in ("ps_lm_tokens_total", "ps_lm_expert_rows_total")
            if name in state
        }
        for s in state.get("ps_lm_moe_buffer_passes_total", {}).get(
                "series", ()):
            out[s["labels"]["part"]] = s["value"]
        return out

    before = totals()
    _, stats = trainer.collect(trainer.submit(trainer.place([batch])))
    after = totals()
    grew = {k: v - before.get(k, 0) for k, v in after.items()}
    assert grew["ps_lm_tokens_total"] == tokens
    assert grew["ps_lm_expert_rows_total"] == int(stats["expert_rows"].sum())
    # choices and probes stay on the device
    assert set(stats) == {"expert_rows", "buffer_passes"}
    # 2 layers x top-2, of which the held experts get a part
    assert 0 < int(stats["expert_rows"].sum()) <= 2 * tokens * 2
    # a pass a layer a step over the head, and over the tail where needed
    assert stats["buffer_passes"].tolist() == [2, tail]
    assert (grew["head"], grew["tail"]) == (2, tail)
    if tail:
        assert stats["expert_rows"].tolist() == [2 * tokens]


@pytest.fixture(scope="module")
def toy_file(tmp_path_factory):
    """The configuration's toy sizes as a description file of their own."""
    path = tmp_path_factory.mktemp("lm") / "toy.json"
    path.write_text(json.dumps(ref.description(CONFIG, rehearsal=True)))
    return str(path)


def test_the_cli_trains_a_described_model(toy_file, capsys):
    from parameter_server_tpu.apps.lm import main as lm_main

    out = lm_main.run([
        "--model-config", toy_file, "--optimizer",
        "adafactor", "--bf16", "--remat", "--steps", "4", "--report-every",
        "2", "--seq-len", "64", "--batch", "2",
    ])
    assert out["cfg"].layer_kinds == (("mla", "moe"),) * 2
    assert [s for s, _ in out["losses"]] == [2, 4]
    assert all(math.isfinite(v) for _, v in out["losses"])
    assert out["params"]["head"].shape == (64, 512)


@pytest.mark.parametrize("argv,message", [
    (["--model-config", "TOY", "--prompt", "hi"], "latent attention"),
    (["--model-config", "TOY", "--num-servers", "2"], "no tensor-parallel"),
    (["--temperature", "1", "--top-k", "300"], r"\[1, 256\]"),
])
def test_the_cli_refuses_by_name(argv, message, toy_file, capsys):
    from parameter_server_tpu.apps.lm import main as lm_main

    argv = [toy_file if a == "TOY" else a for a in argv]
    with pytest.raises(SystemExit):
        lm_main.run(argv + ["--steps", "1"])
    assert __import__("re").search(message, capsys.readouterr().err)


def test_top_k_is_checked_against_the_models_vocabulary(toy_file):
    """300 is a valid --top-k for the described model's 512 ids: the
    flag check passes and the run stops at the generation it cannot do."""
    from parameter_server_tpu.apps.lm import main as lm_main

    with pytest.raises(SystemExit):
        lm_main.run([
            "--model-config", toy_file, "--temperature",
            "1", "--top-k", "300", "--prompt", "x", "--steps", "1",
        ])


def test_the_configuration_file_keeps_every_published_number():
    """Every number of the catalog row's config under the same key,
    but the three that ``reduced`` lists."""
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        pytest.skip("no catalog here")
    with open(catalog) as f:
        row = next(
            json.loads(line) for line in f
            if '"Mistral-Small-4-119B-2603"' in line
        )
    with open(CONFIG) as f:
        mine = json.load(f)
    assert mine["source"] == row["source_url"]
    cut = {"num_hidden_layers": 4, "n_routed_experts": 8, "vocab_size": 16384}
    for key, value in row["config"].items():
        assert mine[key] == cut.get(key, value), key
    assert set(cut) <= set(mine["reduced"])
    assert mine["published"] == {
        k: row["config"][k] for k in cut
    }
    for key in ("router_scoring", "softmax_scale", "optimizer",
                "initialisation", "packing_mask"):
        assert key in mine["assumed"], key

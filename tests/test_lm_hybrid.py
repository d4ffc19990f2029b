"""Gated delta-rule (KDA) layers beside gated NoPE GQA layers on the
training path of ``apps/lm``, held to the benchmark's plain reference
(``chipbench/lm_hybrid_reference.py``: one copy, imported from there,
its recurrence token by token) at small widths on the CPU: d 64, 4
query / 2 K/V heads of 32, 4 KDA heads of 16, gate rank 8, 8 experts
top-2 beside 1 shared, vocabulary 512, 4 layers (GQA, KDA, KDA, KDA).
"""

from __future__ import annotations

import dataclasses
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from chipbench import lm_hybrid_reference as ref  # noqa: E402
from chipbench import lm_reference  # noqa: E402
from parameter_server_tpu.apps.lm import trainer as lm_trainer  # noqa: E402
from parameter_server_tpu.models import kda as kdalib  # noqa: E402
from parameter_server_tpu.models import transformer as tfm  # noqa: E402
from parameter_server_tpu.ops import kda as kda_op  # noqa: E402

CONFIG = os.path.join(ROOT, "chipbench", "configs", "solar_open2_ep40.json")
MOE_LEAVES = (
    "ln1", "ln2", "router", "we_gate", "we_up", "we_down", "ws_gate",
    "ws_up", "ws_down",
)
GQA_LEAVES = ("wq", "wk", "wv", "wo", "wg")
KDA_LEAVES = (
    "wq", "wk", "wv", "wo", "conv_q", "conv_k", "conv_v", "wf_a", "wf_b",
    "a_log", "dt_bias", "wbeta", "wg_a", "wg_b", "bg", "o_norm",
)
LEAVES = ["emb", "head", "ln_f"] + [
    f"l{i}/{leaf}" for i in range(4)
    for leaf in (GQA_LEAVES if i == 0 else KDA_LEAVES) + MOE_LEAVES
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
    # away from the flat start: norms, gates and logits that matter
    params = jax.tree.map(
        lambda x: 5.0 * x if x.ndim > 1 else x
        * (1.0 + 0.1 * jnp.cos(jnp.arange(x.size, dtype=jnp.float32))),
        params,
    )
    # 90 tokens: no multiple of the scan's chunk of 64
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 90), 0, 512)
    return desc, cfg, params, tokens


def loss_and_grads_of(cfg):
    """The program's ``(loss, grads)`` as ONE compiled function: op by
    op, the scan's hundreds of small operations each compile alone (80 s
    a worker for the fixture below, 14 s so)."""
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
    assert [a for a, _ in cfg.layer_kinds] == ["mha", "kda", "kda", "kda"]
    assert {f for _, f in cfg.layer_kinds} == {"moe"}
    assert sorted(params) == sorted(LEAVES)
    assert {k: v.shape for k, v in params.items()} == ref.shapes(
        ref.model(desc)
    )
    assert cfg.head_width == 32 and cfg.n_heads * cfg.head_width != cfg.d_model
    assert params["l0/wq"].shape == (64, 128)  # 4 heads of 32 over d 64
    assert params["l0/wk"].shape == (64, 64)  # 2 K/V heads
    assert params["l0/wg"].shape == (64, 128) and cfg.attn_gate
    assert params["l1/conv_q"].shape == (4, 64)
    assert params["l1/wf_a"].shape == (64, 8)
    assert params["l1/a_log"].shape == (4,)
    assert params["l1/dt_bias"].shape == (64,)
    assert not cfg.rope and not cfg.tie_head


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
    """64 tokens: a whole block of the recurrence, under the blocks'
    ``jax.checkpoint``; the KDA layers' 4 heads 2 at a time; and rows
    of the head in blocks."""
    desc, _, params, tokens = setup
    m = ref.model(desc)
    tokens = tokens[:, :64]
    old = ref.HEAD_BLOCK, ref.KDA_HEADS_BLOCK
    # 2 x 63 rows: two whole blocks of rows and a rest
    ref.HEAD_BLOCK, ref.KDA_HEADS_BLOCK = 48, 2
    try:
        loss, grads = reference_loss_and_grads(m, True)(params, tokens)
    finally:
        ref.HEAD_BLOCK, ref.KDA_HEADS_BLOCK = old
    want, want_grads = reference_loss_and_grads(m)(params, tokens)
    assert abs(float(loss) - float(want)) < 1e-6
    assert max(rel(grads[k], want_grads[k]) for k in LEAVES) < 1e-5


@pytest.fixture(scope="module")
def bf16_grads(setup):
    """``(params, the bf16 model, (loss, grads) in f32, the same in
    bf16)`` at the weights ``init_lm`` draws (sigma 0.02, the cell's)."""
    desc, cfg, _, tokens = setup
    params = tfm.init_lm(jax.random.PRNGKey(0), cfg)
    low = lm_trainer.model_from_description(desc, bf16=True, remat=True)
    return (
        params, low, loss_and_grads_of(cfg)(params, tokens),
        loss_and_grads_of(low)(params, tokens),
    )


def routed(k: str) -> bool:
    return "/we_" in k or "router" in k or k.endswith("ln2")


def test_the_bf16_path_stays_within_its_tolerance(bf16_grads):
    """bf16 matmul inputs and activations against the program in f32:
    the loss within 2e-3, every leaf's gradient within 5% in relative L2
    (most read 2.5-4%) but the routers' and the routed experts', within
    20% (17.1% read: with top-2 of 8 a choice that flips on a rounded
    router input moves a token between experts, and four layers of them
    feed each other), and ``a_log``, within 5.5%: four numbers a layer
    that each sum a head's whole log-decay gradient, 5.15% read in the
    last layer. That reading is the FORWARD's rounding, not the
    backward rule's (the test below: the same forward differentiated in
    f32 reads 5.17%); PR 33's autodiff read 4.88% and sat under 5% by
    where its own roundings fell. At the fixture's weights x 5 the flips
    cascade and every leaf reads 30%: a statement about toy routers, not
    about bf16."""
    _, _, (want, want_grads), (loss, grads) = bf16_grads
    assert abs(float(loss) - float(want)) < 2e-3
    worst = {k: rel(grads[k], want_grads[k]) for k in LEAVES}
    assert max(v for k, v in worst.items() if routed(k)) < 0.2, worst
    a_log = {k: v for k, v in worst.items() if k.endswith("a_log")}
    assert len(a_log) == 3 and max(a_log.values()) < 0.055, a_log
    assert max(
        v for k, v in worst.items() if not routed(k) and k not in a_log
    ) < 0.05, worst


def test_the_rule_in_bf16_rounds_no_more_than_autodiff_did(
    setup, bf16_grads, monkeypatch
):
    """What the backward rule's own bf16 costs, apart from the
    forward's: the same bf16 forward differentiated (a) by the rule run
    in f32, the chunks' matrices it computes again and every product,
    and (b) by autodiff, as PR 33 did. The rule as it runs is within
    1.5% of (a) in every leaf (1.31% read) and no further from it than
    autodiff is (1.45%). And (a) itself reads 5.17% on the last layer's
    ``a_log`` against the f32 program: no backward of this forward meets
    the 5% the other leaves are held to. When that line fails the
    forward has become more exact: hold ``a_log`` to 5% again."""
    _, _, _, tokens = setup
    params, low, (_, f32_grads), (_, rule) = bf16_grads

    def by_autodiff(q, k, v, g, beta, *, chunk, dtype):
        return kda_op._forward(q, k, v, g, beta, chunk, jnp.dtype(dtype))

    def in_f32(q, k, v, g, beta, *, chunk, dtype):
        forward = lambda *a: by_autodiff(  # noqa: E731
            *a, chunk=chunk, dtype=dtype
        )
        fn = jax.custom_vjp(forward)
        fn.defvjp(
            lambda *a: (forward(*a), a),
            lambda a, ct: kda_op._kda_bwd(
                chunk, jnp.dtype(jnp.float32), a, ct
            ),
        )
        return fn(q, k, v, g, beta)

    monkeypatch.setattr(kdalib, "kda_chunked", in_f32)
    _, exact = loss_and_grads_of(low)(params, tokens)
    monkeypatch.setattr(kdalib, "kda_chunked", by_autodiff)
    _, autodiff = loss_and_grads_of(low)(params, tokens)
    off = lambda grads: max(  # noqa: E731
        rel(grads[k], exact[k]) for k in LEAVES if not routed(k)
    )
    assert 0.002 < off(rule) < 0.015
    assert off(rule) < 1.05 * off(autodiff)
    last = "l3/a_log"
    assert rel(exact[last], f32_grads[last]) > 0.05
    assert abs(
        rel(rule[last], f32_grads[last]) - rel(exact[last], f32_grads[last])
    ) < 0.001


def test_the_step_returns_the_scans_token_layers(setup, monkeypatch):
    _, cfg, params, tokens = setup
    _, stats = tfm.lm_forward_with_stats(params, tokens, cfg, mesh_of(1))
    assert int(stats[tfm.KDA_SCAN_TOKENS]) == 2 * 90 * 3
    assert tfm.KDA_SCAN_TOKENS in tfm.STEP_COUNTS
    # whatever the chunk of the scan and the heads of a pass
    monkeypatch.setattr(kdalib, "CHUNK", 16)
    monkeypatch.setattr(kda_op, "HEADS_PER_PASS", 2)
    logits, stats = tfm.lm_forward_with_stats(params, tokens, cfg, mesh_of(1))
    assert int(stats[tfm.KDA_SCAN_TOKENS]) == 2 * 90 * 3
    assert rel(logits, tfm.lm_forward(params, tokens, cfg, mesh_of(1))) < 1e-4


def test_a_collect_counts_the_scans_token_layers(setup):
    from parameter_server_tpu.telemetry import registry as telemetry_registry

    desc, cfg, params, tokens = setup
    trainer = lm_trainer.build_trainer(cfg, mesh_of(1), optimizer="adafactor")
    trainer.load(params)
    name = "ps_lm_kda_scan_tokens_total"
    reg = telemetry_registry.default_registry()
    total = lambda: sum(  # noqa: E731
        s["value"] for s in reg.export_state()[name]["series"]
    )
    before = total()
    _, counts = trainer.collect(
        trainer.submit(trainer.place([np.asarray(tokens)]))
    )
    assert int(counts[tfm.KDA_SCAN_TOKENS]) == 2 * 90 * 3
    assert total() - before == 2 * 90 * 3


def test_a_sequence_sharded_mesh_is_refused_by_name(setup):
    _, cfg, params, tokens = setup
    if len(jax.devices()) < 2:
        pytest.skip("one device")
    with pytest.raises(NotImplementedError, match="'kda'.*sequence-sharded"):
        tfm.lm_forward(params, tokens, cfg, mesh_of(2))


# -- the chunked scan against the recurrence ---------------------------------


def scan_inputs(seed: int, s: int = 100, strength: float = 1.0, h: int = 3):
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    unit = lambda t: t / jnp.linalg.norm(t, axis=-1, keepdims=True)  # noqa
    q = unit(jax.random.normal(ks[0], (2, s, h, 16)))
    k = unit(jax.random.normal(ks[1], (2, s, h, 16)))
    v = jax.random.normal(ks[2], (2, s, h, 16))
    g = -strength * jax.nn.softplus(jax.random.normal(ks[3], (2, s, h, 16)))
    beta = 2.0 * jax.nn.sigmoid(jax.random.normal(ks[4], (2, s, h)))
    return q, k, v, g, beta


def reference_scan(q, k, v, g, beta):
    """The benchmark's token-by-token recurrence (``delta_rule``)."""
    return ref.delta_rule(q, k, v, jnp.exp(g), beta, blocked=False)


@pytest.mark.parametrize("chunk", [16, 32, 64])
@pytest.mark.parametrize("s,strength", [
    (128, 1.0),
    (100, 1.0),  # no multiple of any of the chunks
    (128, 12.0),  # exp(G) underflows f32 inside a chunk of 16
], ids=["whole", "ragged", "strong_decay"])
def test_the_chunked_scan_is_the_token_by_token_recurrence(chunk, s, strength):
    args = scan_inputs(chunk + s, s, strength)
    if strength > 2:  # the sum of g over 16 tokens is below f32's exp range
        assert float(jnp.min(jnp.sum(args[3][:, :16], 1))) < -104.0
    loss = lambda fn: lambda *a: jnp.sum(jnp.sin(fn(*a)))  # noqa: E731
    chunked = lambda *a: kda_op.kda_chunked(*a, chunk=chunk)[0]  # noqa: E731
    with jax.default_matmul_precision("highest"):
        want = jax.jit(reference_scan)(*args)
        got, state = jax.jit(
            lambda *a: kda_op.kda_chunked(*a, chunk=chunk)
        )(*args)
        want_g = jax.jit(
            jax.grad(loss(reference_scan), argnums=range(5))
        )(*args)
        got_g = jax.jit(jax.grad(loss(chunked), argnums=range(5)))(*args)
        own, own_state = jax.jit(kda_op.kda_recurrent)(*args)
    assert bool(jnp.isfinite(got).all())
    assert rel(got, want) < 5e-6
    for name, a, b in zip("q k v g beta".split(), got_g, want_g):
        assert bool(jnp.isfinite(a).all()), name
        assert rel(a, b) < 5e-5, name
    # and the package's own recurrence is the benchmark's; the state
    # after the last token (the padded tail leaves it as it is) is its
    assert rel(own, want) < 1e-6
    assert state.shape == own_state.shape == (2, 3, 16, 16)
    assert rel(state, own_state) < 5e-6


@pytest.mark.parametrize("heads_per_pass,passes", [
    (1, 3), (2, 3), (3, 1), (16, 1),
], ids=["one", "no_divisor", "all", "more_than_there_are"])
def test_the_scan_takes_the_largest_divisor_of_the_heads(
    heads_per_pass, passes, monkeypatch
):
    """3 heads: 2 at a time cannot be had, so 1; the passes change
    nothing but what is alive."""
    monkeypatch.setattr(kda_op, "HEADS_PER_PASS", heads_per_pass)
    args = scan_inputs(7, 64)
    with jax.default_matmul_precision("highest"):
        want, want_state = jax.jit(kda_op.kda_recurrent)(*args)
        fn = lambda *a: kda_op.kda_chunked(*a, chunk=16)  # noqa: E731
        got, state = jax.jit(fn)(*args)
        loops = str(jax.make_jaxpr(fn)(*args)).count("scan[")
    assert loops == (passes > 1) + 1  # over the passes, over the chunks
    assert rel(got, want) < 5e-6 and rel(state, want_state) < 5e-6


# -- the scan's gradient, written by hand (ops/kda.py's custom_vjp) -----------

INPUTS = "q k v g beta".split()


def loss_of_both(fn):
    """A loss of the outputs AND of the state after the last token."""
    def loss(*a):
        out, state = fn(*a)
        return jnp.sum(jnp.sin(out)) + jnp.sum(jnp.cos(2.0 * state))
    return loss


def grads_of_both(fn, args):
    return jax.jit(jax.grad(loss_of_both(fn), argnums=range(5)))(*args)


def all_eqns(jaxpr):
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from all_eqns(sub)


def scans_in(jaxpr) -> list:
    """Every ``scan`` equation of a jaxpr, those inside others too."""
    return [eqn for eqn in all_eqns(jaxpr) if eqn.primitive.name == "scan"]


def over_the_chunks(scans) -> list:
    """Those that carry a state [B, H, K, V]: ``(reverse, carry aval)``."""
    out = []
    for eqn in scans:
        lo = eqn.params["num_consts"]
        carry = [v.aval for v in eqn.invars[lo:lo + eqn.params["num_carry"]]]
        if len(carry) == 1 and carry[0].ndim == 4:
            out.append((eqn.params["reverse"], carry[0]))
    return out


@pytest.mark.parametrize("chunk,s", [(16, 128), (64, 100), (32, 40)],
                         ids=["whole", "ragged", "ragged_below_two_chunks"])
def test_a_cotangent_on_the_last_state_is_honoured(chunk, s):
    args = scan_inputs(11 + s, s)
    with jax.default_matmul_precision("highest"):
        want = grads_of_both(kda_op.kda_recurrent, args)
        got = grads_of_both(
            lambda *a: kda_op.kda_chunked(*a, chunk=chunk), args
        )
        # the state's share alone: none of q's, and no rounding of the
        # others'
        state_only = jax.jit(jax.grad(
            lambda *a: jnp.sum(jnp.cos(
                2.0 * kda_op.kda_chunked(*a, chunk=chunk)[1]
            )), argnums=range(5),
        ))(*args)
    for name, a, b, c in zip(INPUTS, got, want, state_only):
        assert bool(jnp.isfinite(a).all()), name
        assert rel(a, b) < 5e-5, name
        share = float(jnp.linalg.norm(c)) / float(jnp.linalg.norm(b))
        assert share == 0.0 if name == "q" else share > 0.01, name


@pytest.mark.parametrize("heads,heads_per_pass,passes", [
    (3, 1, 3), (3, 2, 3), (4, 2, 2),
], ids=["one", "no_divisor", "two"])
def test_the_gradient_in_passes_is_the_gradient(
    heads, heads_per_pass, passes, monkeypatch
):
    args = scan_inputs(23, 100, h=heads)
    fn = lambda *a: kda_op.kda_chunked(*a, chunk=16)  # noqa: E731
    with jax.default_matmul_precision("highest"):
        want = grads_of_both(kda_op.kda_recurrent, args)
        at_once = grads_of_both(lambda *a: fn(*a), args)
        monkeypatch.setattr(kda_op, "HEADS_PER_PASS", heads_per_pass)
        got = grads_of_both(lambda *a: fn(*a), args)
        jaxpr = jax.make_jaxpr(
            jax.grad(loss_of_both(lambda *a: fn(*a)), argnums=range(5))
        )(*args)
    # a loop over the passes in each rule, and in them a pass's scans
    # over its chunks: one forward, and forward again and in reverse
    scans = scans_in(jaxpr.jaxpr)
    assert len(scans) == 5
    assert [r for r, _ in over_the_chunks(scans)] == [False, False, True]
    for name, a, b, c in zip(INPUTS, got, want, at_once):
        assert a.shape == b.shape and a.dtype == b.dtype, name
        assert rel(a, b) < 5e-5, name
        assert rel(a, c) < 2e-6, name


def test_the_gradient_in_bf16_is_near_the_f32_one_and_its_state_is_f32():
    """bf16 inputs and products: the gradients come back in their
    inputs' types, finite, and within what bf16 products cost (measured
    0.006-0.02 of the f32 gradient's norm); the forward's state and the
    backward's dS are carried in f32 whatever the products read."""
    args = scan_inputs(31, 128)
    low = tuple(t.astype(jnp.bfloat16) for t in args)
    fn = lambda *a: kda_op.kda_chunked(*a, chunk=32)  # noqa: E731
    want = grads_of_both(fn, tuple(t.astype(jnp.float32) for t in low))
    got = grads_of_both(lambda *a: fn(*a), low)
    for name, a, b in zip(INPUTS, got, want):
        assert a.dtype == jnp.bfloat16, name
        assert bool(jnp.isfinite(a.astype(jnp.float32)).all()), name
        assert rel(a.astype(jnp.float32), b) < 0.04, name
    out, state = jax.jit(fn)(*low)
    assert out.dtype == jnp.bfloat16 and state.dtype == jnp.float32
    jaxpr = jax.make_jaxpr(
        jax.grad(loss_of_both(lambda *a: fn(*a)), argnums=range(5))
    )(*low)
    carried = over_the_chunks(scans_in(jaxpr.jaxpr))
    assert [reverse for reverse, _ in carried] == [False, False, True]
    for _, aval in carried:
        assert aval.shape == (2, 3, 16, 16) and aval.dtype == jnp.float32


def test_the_padded_tail_gets_no_gradient_and_leaves_ds_as_it_is():
    """100 tokens in chunks of 16 are padded to 112. The same sequence
    handed over padded to 128 by the caller (q, k, v, beta of zeros and
    a log-decay of zero: tokens that leave the state as it is), its
    last chunk nothing but padding: the real tokens get the gradient
    they got, bit for bit where the arithmetic is the same (dS passes
    the empty chunk as it is), and the padding gets exactly none. Its
    log-decay alone gets what a decay of the state would change."""
    s, padded = 100, 128
    args = scan_inputs(41, s)
    wide = tuple(
        jnp.pad(t, ((0, 0), (0, padded - s)) + ((0, 0),) * (t.ndim - 2))
        for t in args
    )
    fn = lambda *a: kda_op.kda_chunked(*a, chunk=16)  # noqa: E731

    def real_tokens_only(*a):
        out, state = fn(*a)
        return out[:, :s], state

    with jax.default_matmul_precision("highest"):
        want = grads_of_both(fn, args)
        got = grads_of_both(real_tokens_only, wide)
    for name, a, b in zip(INPUTS, got, want):
        assert rel(a[:, :s], b) < 1e-6, name
        if name != "g":
            assert float(jnp.max(jnp.abs(a[:, s:]))) == 0.0, name
    # one value for all the padding of a head's channel: the sum of dS * S
    tail = got[3][:, s:]
    assert float(jnp.max(jnp.abs(tail))) > 0.0
    assert float(jnp.max(jnp.abs(tail - tail[:, :1]))) < 1e-5


def test_the_gradient_is_the_rule_written_by_hand():
    """Autodiff does not see the inside of the recurrence: the forward
    is one ``custom_vjp`` call, and its gradient holds the forward
    rule's scan over the chunks and the backward rule's two (forward
    again for the state each chunk starts from, then in reverse with
    dS), no rematerialised region and no loop transposed: the chunk's
    inverse is substituted forward in each rule and its gradient is two
    products."""
    args = scan_inputs(3, 64)
    fn = lambda *a: kda_op.kda_chunked(*a, chunk=16)  # noqa: E731
    forward = jax.make_jaxpr(fn)(*args)
    assert str(forward).count("custom_vjp_call") == 1
    grad = jax.make_jaxpr(
        jax.grad(loss_of_both(fn), argnums=range(5))
    )(*args)
    assert "custom_vjp_call" not in str(grad)  # taken by its rules
    assert "checkpoint" not in str(grad) and "remat" not in str(grad)
    scans = scans_in(grad.jaxpr)
    assert str(grad).count("scan[") == len(scans) == 3
    assert [r for r, _ in over_the_chunks(scans)] == [False, False, True]
    # SUB - 1 substitution steps a rule; autodiff's transpose of them
    # would be as many again, twice over
    steps = lambda jaxpr: sum(  # noqa: E731
        eqn.primitive.name == "dot_general"
        and eqn.outvars[0].aval.ndim == eqn.invars[0].aval.ndim
        and eqn.invars[1].aval.ndim == eqn.invars[0].aval.ndim + 1
        for eqn in all_eqns(jaxpr)
    )
    assert steps(forward.jaxpr) == kda_op.SUB - 1
    assert steps(grad.jaxpr) == 2 * (kda_op.SUB - 1)


def test_a_capture_splits_by_phase_of_the_step():
    """``script/lm_scope_split.py`` on the benchmark's recorded capture
    (PR 33's program: each pass rematerialised inside the layer): the
    three phases are the whole of the scope, and the recomputation, the
    layer's and the passes', is no part of the backward's."""
    import importlib.util

    from chipbench import trace
    from chipbench.readers import lm_common

    spec = importlib.util.spec_from_file_location(
        "lm_scope_split", os.path.join(ROOT, "script", "lm_scope_split.py")
    )
    tool = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(tool)
    tr = trace.load(os.path.join(
        ROOT, "chipbench", "fixtures",
        "solar_open2_ep40.packed8k_mb1.trace.json.gz",
    ))
    steps = lm_common.step_seconds_and_count(tr)[1]
    out = tool.split(tr, steps, "lm_kda_scan")
    assert list(out) == ["forward", "recomputation", "backward"]
    total = 1e3 * sum(
        o.self_s for ops in tr.ops.values() for o in ops
        if "lm_kda_scan" in o.scope
    ) / steps
    assert abs(sum(out.values()) - total) < 1e-6 * total
    assert out["recomputation"] > out["forward"] > 0
    assert out["backward"] > 0
    assert sum(tool.split(tr, steps, "no_such_scope").values()) == 0.0


def test_the_scan_refuses_a_chunk_that_is_no_power_of_two():
    with pytest.raises(ValueError, match="power of two"):
        kda_op.kda_chunked(*scan_inputs(0), chunk=48)


def test_the_unit_lower_inverse_is_the_inverse():
    # entries as a chunk's are: products of unit vectors times beta
    low = 0.2 * jnp.tril(
        jax.random.normal(jax.random.PRNGKey(3), (5, 64, 64)), -1
    )
    inv = kda_op.unit_lower_inverse(low)
    eye = jnp.eye(64)
    with jax.default_matmul_precision("highest"):
        back = inv @ (eye + low)
    assert float(jnp.max(jnp.abs(back - eye))) < 1e-4
    assert float(jnp.max(jnp.abs(jnp.triu(inv, 1)))) == 0.0


def test_the_causal_convolution_looks_back_and_never_ahead():
    x = jnp.zeros((1, 8, 2)).at[0, 3].set(1.0)
    taps = jnp.arange(1.0, 9.0).reshape(4, 2)
    y = kdalib.causal_conv(x, taps)
    # the impulse at t = 3 reaches t = 3 through the LAST tap
    np.testing.assert_array_equal(np.asarray(y[0, :3]), 0.0)
    np.testing.assert_array_equal(np.asarray(y[0, 3:7]), taps[::-1])
    np.testing.assert_array_equal(np.asarray(y[0, 7]), 0.0)
    np.testing.assert_allclose(y, ref.causal_conv(x, taps))


def test_the_initial_decay_is_neither_zero_nor_one():
    p = kdalib.init_kda(
        jax.random.PRNGKey(0), 64, kdalib.KDAConfig(n_heads=4, head_dim=16,
                                                    gate_rank=8), 0.02,
    )
    dt = jax.nn.softplus(p["dt_bias"])
    assert 0.001 <= float(dt.min()) and float(dt.max()) <= 0.1 * 1.0001
    a = jnp.exp(p["a_log"])
    assert 1.0 <= float(a.min()) and float(a.max()) <= 16.0
    assert float(jnp.abs(p["bg"]).max()) == 0.0
    assert float(jnp.abs(p["o_norm"] - 1).max()) == 0.0


def test_both_initialisations_draw_the_same_distributions(setup):
    desc, cfg, _, _ = setup
    mine = tfm.init_lm(jax.random.PRNGKey(5), cfg)
    theirs = ref.weights(5, ref.model(desc))
    for name in LEAVES:
        a, b = np.asarray(mine[name]), np.asarray(theirs[name])
        assert a.shape == b.shape, name
        leaf = name.rsplit("/", 1)[-1]
        if a.ndim > 1:
            assert abs(a.std() - 0.02) < 0.004 and abs(b.std() - 0.02) < 0.004
        elif leaf in ("a_log", "dt_bias"):
            assert a.min() >= b.min() - 3.0 and a.max() <= b.max() + 3.0
            assert not np.array_equal(a, b)
        else:
            np.testing.assert_array_equal(a, b)


# -- the shares add up -------------------------------------------------------


@pytest.mark.parametrize("held", [1, 2, 4, 8])
def test_the_shares_add_up_to_the_uncut_reference_layer(held):
    """The parts of the expert layer's result that all 8 / ``held``
    shares give (the program's layer, told which experts it holds), the
    shared expert counted once, are the uncut reference layer."""
    desc = small_desc()
    cfg = lm_trainer.model_from_description(desc)
    m = ref.model(desc)
    params = tfm.init_lm(jax.random.PRNGKey(2), cfg)
    lp = {k[3:]: 5.0 * v for k, v in params.items() if k.startswith("l1/")}
    x = jax.random.normal(jax.random.PRNGKey(3), (2, 24, 64))
    with jax.default_matmul_precision("highest"):
        whole, _ = lm_reference.experts(lp, x, m, blocked=False)
        h2 = lm_reference.rms(x, lp["ln2"], m["eps"])
        shared = lm_reference.ffn(
            h2, lp["ws_gate"], lp["ws_up"], lp["ws_down"]
        )
        from parameter_server_tpu.models import moe as moelib

        total = jnp.zeros_like(x)
        for offset in range(0, 8, held):
            share = dataclasses.replace(
                cfg.moe, experts_held=held, expert_offset=offset
            )
            mine = {
                k: v[offset:offset + held] if k.startswith("we_") else v
                for k, v in lp.items()
            }
            y, _ = moelib.topk_moe_ffn(mine, h2, share, jnp.float32)
            total = total + (y - shared)
        total = total + shared
    assert rel(total, whole) < 1e-5


def test_a_tail_of_few_heads_is_one_piece_and_a_long_one_equal_pieces():
    """8 of 128 held at 16,384 tokens top-4 (a tail of 3 heads): one
    piece, the program it was; 16 of 64 at 8,192 tokens top-8 (a tail of
    one head) too. 8 of 320 at 8,192 tokens top-8 (a tail of 8.8
    heads): three pieces of whole tiles that cover it."""
    from parameter_server_tpu.models import moe as moelib

    mistral = moelib.TopKMoEConfig(
        n_experts=128, top_k=4, d_expert=8, experts_held=8
    )
    assert moelib.head_rows(16384, mistral) == 16384
    assert moelib._tail_pieces(16384, 65536 - 16384) == (1, 49152)
    mellum = moelib.TopKMoEConfig(
        n_experts=64, top_k=8, d_expert=8, experts_held=16
    )
    assert moelib.head_rows(8192, mellum) == 32768
    assert moelib._tail_pieces(32768, 65536 - 32768) == (1, 32768)
    solar = moelib.TopKMoEConfig(
        n_experts=320, top_k=8, d_expert=8, experts_held=8
    )
    n_head = moelib.head_rows(8192, solar)
    assert n_head == 6656
    pieces, rows = moelib._tail_pieces(n_head, 65536 - n_head)
    assert (pieces, rows) == (3, 19968) and rows % moelib.ROW_TILE == 0
    assert pieces * rows >= 65536 - n_head > (pieces - 1) * rows
    assert rows <= moelib.TAIL_PIECE_HEADS * n_head


@pytest.mark.parametrize("forced", [100, 800, 2048])
def test_a_tail_in_pieces_is_the_reference_layer(forced):
    """A layer that holds 2 of 64 experts, 2,048 tokens top-2: a head of
    512 rows and a tail of 3,584 in 2 pieces of 2,048 (the second ends
    past the buffer). ``forced`` tokens choose the two held experts, the
    others neither: 200 held rows end in the head, 1,600 in the tail's
    first piece, 4,096 fill every piece to the buffer's last row. Output
    and every gradient are the reference layer's."""
    from parameter_server_tpu.models import latent_attention as latent
    from parameter_server_tpu.models import moe as moelib

    full = moelib.TopKMoEConfig(n_experts=64, top_k=2, d_expert=16, n_shared=0)
    lp = moelib.init_topk_moe(jax.random.PRNGKey(3), 32, full, 0.3)
    lp = {k: v[9:11] if k.startswith("we_") else v for k, v in lp.items()}
    lp["ln2"] = jnp.ones((32,))
    # the held experts read the first coordinate alone; the others'
    # logits stay small, so the held weights are neither 0 nor 1
    lp["router"] = (0.1 * lp["router"]).at[:, 9:11].set(0.0).at[0, 9].set(
        4.0
    ).at[0, 10].set(3.0)
    tokens = 2048
    x = jax.random.normal(jax.random.PRNGKey(4), (1, tokens, 32))
    sign = jnp.where(jnp.arange(tokens) < forced, 1.0, -1.0)
    x = x.at[..., 0].set(sign * (jnp.abs(x[..., 0]) + 1.0))
    share = dataclasses.replace(full, experts_held=2, expert_offset=9)
    assert moelib.head_rows(tokens, share) == 512
    assert moelib._tail_pieces(512, tokens * 2 - 512) == (2, 2048)
    m = {
        "eps": 1e-5, "experts": 64, "held": 2, "offset": 9, "top_k": 2,
        "shared": 0, "norm_topk": True, "routed_scale": 1.0,
    }
    weigh = jnp.cos(jnp.arange(x.size, dtype=jnp.float32)).reshape(x.shape)

    def mine(lp, x):
        h2 = latent.rms_norm(x, lp["ln2"], m["eps"])
        y, stats = moelib.topk_moe_ffn(lp, h2, share, jnp.float32)
        return jnp.sum(y * weigh), (y, stats)

    def theirs(lp, x):
        y, _ = lm_reference.experts(lp, x, m, blocked=False)
        return jnp.sum(y * weigh), y

    with jax.default_matmul_precision("highest"):
        (_, (y, stats)), (g_lp, g_x) = jax.value_and_grad(
            mine, argnums=(0, 1), has_aux=True
        )(lp, x)
        (_, want), (w_lp, w_x) = jax.value_and_grad(
            theirs, argnums=(0, 1), has_aux=True
        )(lp, x)
    assert stats["expert_rows"].tolist() == [forced, forced]
    assert stats["buffer_passes"].tolist() == [1, int(2 * forced > 512)]
    assert rel(y, want) < 1e-5 and rel(g_x, w_x) < 1e-5
    for leaf in ("router", "we_gate", "we_up", "we_down"):
        assert rel(g_lp[leaf], w_lp[leaf]) < 1e-5, leaf


# -- descriptions ------------------------------------------------------------


@pytest.mark.parametrize("over,message", [
    (dict(use_rope=True), "use_rope true"),
    (dict(first_k_dense_replace=1), "first_k_dense_replace"),
    (dict(kda_use_full_proj=True), "kda_use_full_proj true"),
    (dict(kda_allow_neg_eigval=False), "kda_allow_neg_eigval false"),
    (dict(model_type="solar_open9"), "mistral4, solar_open2"),
    (dict(n_group=2), "group-limited"),
])
def test_a_description_of_what_is_not_built_is_refused(over, message):
    with pytest.raises(ValueError, match=message):
        lm_trainer.model_from_description(small_desc(**over))


def test_mistral4_descriptions_build_the_config_they_built():
    """The fields of the ``LMConfig`` a ``mistral4`` description gives,
    as PR 32 built it: the new fields at their defaults."""
    mistral = os.path.join(
        ROOT, "chipbench", "configs", "mistral_small4_ep16.json"
    )
    cfg = lm_trainer.model_from_description(
        lm_trainer.load_description(mistral), remat=True, bf16=True
    )
    assert cfg.layer_kinds == (("mla", "moe"),) * 4
    assert (cfg.vocab, cfg.d_model, cfg.n_heads, cfg.d_ff) == (
        16384, 4096, 32, 12288
    )
    assert (cfg.head_dim, cfg.attn_gate, cfg.kda, cfg.n_kv_heads) == (
        None, False, None, None
    )
    assert cfg.head_width == 128 and cfg.rope_theta == 10000
    assert cfg.norm == "rmsnorm" and cfg.norm_eps == 1e-6
    assert cfg.ffn_act == "swiglu" and not cfg.scale_emb and not cfg.tie_head
    assert (cfg.moe.n_experts, cfg.moe.top_k, cfg.moe.held) == (128, 4, 8)
    assert cfg.mla.q_lora_rank == 1024 and cfg.mla.yarn.factor == 128
    assert cfg.remat and cfg.compute_dtype == "bfloat16"


@pytest.mark.parametrize("bad,match", [
    (dict(layers=(("kda", "dense"), ("mha", "dense"))), "needs LMConfig.kda"),
    (dict(layers=(("gla", "dense"), ("mha", "dense"))),
     r"attention \('mha', 'swa', 'mla', 'kda'\)"),
])
def test_lmconfig_names_the_kinds_that_exist(bad, match):
    with pytest.raises(ValueError, match=match):
        tfm.LMConfig(n_layers=2, **bad)


def test_serving_refuses_a_head_width_and_a_gate_by_name():
    for cfg in (tfm.LMConfig(head_dim=32), tfm.LMConfig(attn_gate=True)):
        with pytest.raises(NotImplementedError, match="head_dim / attn_gate"):
            tfm.refuse_serving(cfg, "here")


def test_a_gated_mha_of_a_stated_head_width_trains_beside_a_dense_ffn():
    """The two new ``mha`` fields outside the described model: heads of
    24 over d 64, gated, rope on, a GELU FFN under LayerNorm."""
    cfg = tfm.LMConfig(
        vocab=300, n_layers=1, head_dim=24, attn_gate=True, rope=True,
        n_kv_heads=2, attention="ring",
    )
    params = tfm.init_lm(jax.random.PRNGKey(0), cfg)
    assert params["l0/wq"].shape == (64, 96)
    assert params["l0/wk"].shape == (64, 48)
    assert params["l0/wg"].shape == (64, 96)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 32), 0, 300)
    loss, grads = jax.value_and_grad(tfm.lm_loss)(
        params, tokens, cfg, mesh_of(1)
    )
    assert np.isfinite(float(loss))
    assert all(float(jnp.linalg.norm(g)) > 0 for g in grads.values())


# -- the CLI and the file ----------------------------------------------------


@pytest.fixture(scope="module")
def toy_file(tmp_path_factory):
    path = tmp_path_factory.mktemp("hybrid") / "toy.json"
    with open(path, "w") as f:
        json.dump(ref.description(CONFIG, rehearsal=True), f)
    return str(path)


@pytest.fixture
def one_device(monkeypatch):
    """The CLI shards the sequence over every device it finds, and a
    'kda' layer runs on one chip's sequence."""
    devices = jax.devices()[:1]
    monkeypatch.setattr(jax, "devices", lambda *a, **k: devices)


def test_the_cli_trains_a_described_model(toy_file, one_device, capsys):
    from parameter_server_tpu.apps.lm import main as lm_main

    lm_main.run([
        "--model-config", toy_file, "--optimizer", "adafactor", "--bf16",
        "--remat", "--steps", "4", "--seq-len", "128", "--batch", "1",
    ])
    assert "loss" in capsys.readouterr().out


@pytest.mark.parametrize("argv,message", [
    (["--prompt", "x"], "'kda'"),
    (["--num-servers", "2"], "--num-servers 1"),
])
def test_the_cli_refuses_by_name(argv, message, toy_file, capsys):
    from parameter_server_tpu.apps.lm import main as lm_main

    if len(jax.devices()) < 2 and "--num-servers" in argv:
        pytest.skip("one device")
    with pytest.raises(SystemExit):
        lm_main.run(["--model-config", toy_file, "--steps", "1"] + argv)
    assert message in capsys.readouterr().err


def test_the_cli_refuses_a_sharded_sequence_by_name(toy_file, capsys):
    from parameter_server_tpu.apps.lm import main as lm_main

    if len(jax.devices()) < 2:
        pytest.skip("one device")
    with pytest.raises(SystemExit):
        lm_main.run(["--model-config", toy_file, "--steps", "1"])
    assert "one device" in capsys.readouterr().err


def test_the_configuration_file_keeps_every_published_number():
    """Every number of the catalog row's config under the same key,
    but the three that ``reduced`` lists."""
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        pytest.skip("no catalog here")
    with open(catalog) as f:
        row = next(
            json.loads(line) for line in f if '"Solar-Open2-250B"' in line
        )
    with open(CONFIG) as f:
        mine = json.load(f)
    assert mine["source"] == row["source_url"]
    cut = {"num_hidden_layers": 4, "n_routed_experts": 8, "vocab_size": 24576}
    for key, value in row["config"].items():
        assert mine[key] == cut.get(key, value), key
    assert set(cut) <= set(mine["reduced"])
    assert mine["published"] == {k: row["config"][k] for k in cut}
    assert mine["share"]["chips_per_layer"] * 8 == 320
    for key in ("router_scoring", "gate_widths", "gate_rank", "optimizer",
                "initialisation", "packing"):
        assert key in mine["assumed"], key
    for key in ("precision", "correct", "deployment", "reduced_why"):
        assert key in mine, key

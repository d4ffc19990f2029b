"""Flash attention kernels (ops/flash_attention.py).

The Pallas kernels run in interpret mode on the CPU test mesh (identical
program, no Mosaic compile), compared against the XLA reference path and
dense attention — forward values, logsumexp, and all three gradients —
including unaligned shapes (block padding) and nonzero global offsets
(the ring-attention chunk case). Ring integration: impl="flash" must
match dense attention through the chunk-merge on the 8-device mesh.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from parameter_server_tpu.models.attention import (
    dense_attention,
    dense_mha,
    ring_attention,
)
from parameter_server_tpu.ops import flash_attention as fa
from parameter_server_tpu.ops.flash_attention import (
    flash_attention,
    flash_attention_ref,
    flash_mha,
)
from parameter_server_tpu.parallel.mesh import make_mesh

# Promoted to the slow tier (PR 2, per the PR-1 ROADMAP note): the
# shard_map-shim unlock made the full 'not slow' suite overrun the
# 870s tier-1 budget on a 2-core host. Run via `pytest -m slow`. The
# listed grid's tests at the end of the file are tier-1.
slow = pytest.mark.slow


def _rand(shape, seed=0):
    return jnp.asarray(
        np.random.default_rng(seed).normal(size=shape), jnp.float32
    )


@slow
@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("qo,ko", [(0, 0), (64, 0), (0, 128)])
def test_flash_kernel_matches_ref(causal, qo, ko):
    # deliberately unaligned: exercises block and lane padding
    bh, sq, sk, d = 3, 200, 264, 48
    q, k, v = _rand((bh, sq, d), 1), _rand((bh, sk, d), 2), _rand((bh, sk, d), 3)
    o_ref, lse_ref = flash_attention(
        q, k, v, causal=causal, q_offset=qo, k_offset=ko,
        use_pallas=False, with_lse=True,
    )
    o_pal, lse_pal = flash_attention(
        q, k, v, causal=causal, q_offset=qo, k_offset=ko,
        use_pallas=True, interpret=True, with_lse=True,
    )
    np.testing.assert_allclose(o_ref, o_pal, atol=2e-5, rtol=1e-5)
    np.testing.assert_allclose(lse_ref, lse_pal, atol=2e-5, rtol=1e-5)


@slow
@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize(
    "sq,sk,d",
    [
        (200, 136, 48),  # unaligned: block/lane padding path
        # d_head 128 — every MFU-push LM config's head size
        # (mfu_d1024/mfu_d2048/h4 run d_model/n_heads = 128); a d=128
        # regression must not surface only on-chip mid-capture-window
        (160, 192, 128),
    ],
)
def test_flash_kernel_gradients(causal, sq, sk, d):
    bh = 2
    q, k, v = _rand((bh, sq, d), 1), _rand((bh, sk, d), 2), _rand((bh, sk, d), 3)
    w = _rand((bh, sq, d), 4)

    def make_loss(use_pallas):
        def loss(q, k, v):
            out = flash_attention(
                q, k, v, causal=causal, q_offset=8, k_offset=0,
                use_pallas=use_pallas, interpret=use_pallas,
            )
            return jnp.sum(out * w)

        return jax.grad(loss, argnums=(0, 1, 2))

    for a, b in zip(make_loss(False)(q, k, v), make_loss(True)(q, k, v)):
        np.testing.assert_allclose(a, b, atol=5e-5, rtol=1e-4)


@slow
def test_flash_kernel_d128_fwd():
    """d=128 forward parity (grad coverage lives in the parametrized
    test_flash_kernel_gradients shape (160, 192, 128))."""
    bh, sq, sk, d = 2, 160, 192, 128
    q, k, v = _rand((bh, sq, d), 1), _rand((bh, sk, d), 2), _rand((bh, sk, d), 3)
    o_ref = flash_attention(q, k, v, causal=True, use_pallas=False)
    o_pal = flash_attention(
        q, k, v, causal=True, use_pallas=True, interpret=True
    )
    np.testing.assert_allclose(o_ref, o_pal, atol=2e-5, rtol=1e-5)


@slow
def test_flash_ref_matches_dense():
    bh, s, d = 2, 96, 32
    q, k, v = _rand((bh, s, d), 1), _rand((bh, s, d), 2), _rand((bh, s, d), 3)
    for causal in (False, True):
        o, _ = flash_attention_ref(
            q, k, v, jnp.int32(0), jnp.int32(0), causal=causal
        )
        np.testing.assert_allclose(
            o, dense_attention(q, k, v, causal=causal), atol=2e-5, rtol=1e-5
        )


@slow
def test_flash_fully_masked_chunk_is_zero_with_neg_lse():
    # a kv chunk entirely AFTER the queries (ring hop k_offset > q rows):
    # every row is masked — out must be exactly 0 and lse ~ -inf so the
    # chunk-merge weight underflows to zero
    bh, s, d = 1, 64, 32
    q, k, v = _rand((bh, s, d), 1), _rand((bh, s, d), 2), _rand((bh, s, d), 3)
    out, lse = flash_attention(
        q, k, v, causal=True, q_offset=0, k_offset=1024,
        use_pallas=True, interpret=True, with_lse=True,
    )
    assert float(jnp.max(jnp.abs(out))) == 0.0
    assert float(jnp.max(lse)) < -1e29


@slow
def test_flash_mha_matches_dense_mha():
    b, s, h, nh = 2, 80, 64, 4
    q, k, v = _rand((b, s, h), 1), _rand((b, s, h), 2), _rand((b, s, h), 3)
    for causal in (False, True):
        got = flash_mha(
            q, k, v, nh, causal=causal, use_pallas=True, interpret=True
        )
        np.testing.assert_allclose(
            got, dense_mha(q, k, v, nh, causal=causal), atol=2e-5, rtol=1e-5
        )


@slow
@pytest.mark.parametrize("causal", [False, True])
def test_ring_flash_matches_dense(causal):
    mesh = make_mesh(num_data=8, num_server=1)
    b, s, h = 2, 128, 32
    q, k, v = _rand((b, s, h), 1), _rand((b, s, h), 2), _rand((b, s, h), 3)
    got = ring_attention(
        q, k, v, mesh=mesh, axis="data", causal=causal, impl="flash"
    )
    np.testing.assert_allclose(
        got, dense_attention(q, k, v, causal=causal), atol=2e-5, rtol=1e-5
    )


@slow
def test_ring_flash_gradients_match_dense():
    mesh = make_mesh(num_data=4, num_server=1)
    b, s, h = 1, 64, 16
    q, k, v = _rand((b, s, h), 1), _rand((b, s, h), 2), _rand((b, s, h), 3)
    w = _rand((b, s, h), 4)

    def loss_ring(q, k, v):
        out = ring_attention(
            q, k, v, mesh=mesh, axis="data", causal=True, impl="flash"
        )
        return jnp.sum(out * w)

    def loss_dense(q, k, v):
        return jnp.sum(dense_attention(q, k, v, causal=True) * w)

    gr = jax.grad(loss_ring, argnums=(0, 1, 2))(q, k, v)
    gd = jax.grad(loss_dense, argnums=(0, 1, 2))(q, k, v)
    for a, b_ in zip(gr, gd):
        np.testing.assert_allclose(a, b_, atol=5e-5, rtol=1e-4)


@slow
def test_flash_kernel_gradients_through_lse():
    # exercises the dlse cotangent path IN THE PALLAS KERNELS (the ring
    # merge differentiates through lse; the c = delta - dlse folding in
    # the backward kernels must carry it)
    bh, s, d = 2, 136, 32
    q, k, v = _rand((bh, s, d), 1), _rand((bh, s, d), 2), _rand((bh, s, d), 3)
    w = _rand((bh, s, d), 4)
    wl = _rand((bh, s), 5)

    def make_loss(use_pallas):
        def loss(q, k, v):
            out, lse = flash_attention(
                q, k, v, causal=True, use_pallas=use_pallas,
                interpret=use_pallas, with_lse=True,
            )
            return jnp.sum(out * w) + jnp.sum(lse * wl)

        return jax.grad(loss, argnums=(0, 1, 2))

    for a, b in zip(make_loss(False)(q, k, v), make_loss(True)(q, k, v)):
        np.testing.assert_allclose(a, b, atol=5e-5, rtol=1e-4)


def _kept_layer(policy):
    """A layer around the interpret-mode kernel, rematerialised under
    ``policy`` (``None``: not rematerialised), and its inputs."""
    bh, s, d = 2, 136, 32
    x, wq, wo = _rand((bh, s, d), 1), _rand((d, d), 2), _rand((d, d), 3)

    def layer(x, wq, wo):
        att = flash_attention(
            x @ wq, x, x, causal=True, use_pallas=True, interpret=True
        )
        return x + jnp.tanh(att) @ wo

    if policy is not None:
        layer = jax.checkpoint(layer, policy=policy)
    grad = jax.grad(
        lambda x, wq, wo: jnp.sum(layer(x, wq, wo) ** 2), argnums=(0, 1, 2)
    )
    return grad, (x, wq, wo)


@slow
def test_a_policy_that_lists_the_residuals_runs_the_forward_kernel_once():
    """The forward rule names its output and log-sum-exp: a checkpoint
    policy that lists the names keeps them, and the gradient program
    holds forward, dq and dkv where one that lists none holds the
    forward twice. The values do not know the difference."""
    from conftest import pallas_calls

    save = jax.checkpoint_policies.save_only_these_names
    plain, args = _kept_layer(None)
    kept, _ = _kept_layer(save(fa.FLASH_OUT, fa.FLASH_LSE))
    again, _ = _kept_layer(save())
    assert pallas_calls(plain, *args) == 3
    assert pallas_calls(kept, *args) == 3
    assert pallas_calls(again, *args) == 4
    for a, b, c in zip(kept(*args), again(*args), plain(*args)):
        np.testing.assert_array_equal(a, b)
        np.testing.assert_array_equal(a, c)


@slow
@pytest.mark.parametrize("differentiated", [False, True])
def test_the_names_change_no_program_without_a_checkpoint(
    differentiated, monkeypatch
):
    """No policy, no effect: serving's program (the primal, which never
    meets the forward rule) and plain training's are the ones they
    were, apart from training's two identity ``name`` equations."""
    from conftest import jaxpr_eqns

    q, k, v = (_rand((2, 136, 32), seed) for seed in (1, 2, 3))

    def f(q, k, v):
        return jnp.sum(flash_attention(
            q, k, v, causal=True, use_pallas=True, interpret=True
        ))

    if differentiated:
        f = jax.grad(f, argnums=(0, 1, 2))

    def program():
        eqns = list(jaxpr_eqns(jax.make_jaxpr(f)(q, k, v).jaxpr))
        names = [e.params["name"] for e in eqns if e.primitive.name == "name"]
        return names, [
            (e.primitive.name, [str(o.aval) for o in e.outvars])
            for e in eqns if e.primitive.name != "name"
        ]

    names, named = program()
    # the forward rule's trace is cached by function: forget it before
    # tracing without the names, and again before anyone else traces
    try:
        with monkeypatch.context() as patch:
            patch.setattr(fa, "checkpoint_name", lambda x, name: x)
            jax.clear_caches()
            no_names, unnamed = program()
    finally:
        jax.clear_caches()
    assert named == unnamed and no_names == []
    assert names == ([fa.FLASH_OUT, fa.FLASH_LSE] if differentiated else [])


@slow
def test_ring_flash_with_interpret_kernel_on_mesh():
    # the pallas kernel itself (interpret mode) under shard_map: one hop
    # per device with nonzero traced offsets
    mesh = make_mesh(num_data=2, num_server=1)
    b, s, h = 1, 256, 32
    q, k, v = _rand((b, s, h), 1), _rand((b, s, h), 2), _rand((b, s, h), 3)
    got = ring_attention(
        q, k, v, mesh=mesh, axis="data", causal=True, impl="flash",
        use_pallas=True, interpret=True,
    )
    np.testing.assert_allclose(
        got, dense_attention(q, k, v, causal=True), atol=2e-5, rtol=1e-5
    )


@slow
@pytest.mark.parametrize("causal", [False, True])
def test_zigzag_ring_matches_dense(causal):
    from parameter_server_tpu.models.attention import zigzag_permutation

    mesh = make_mesh(num_data=4, num_server=1)
    n = 4
    b, s, h = 2, 128, 32
    q, k, v = _rand((b, s, h), 1), _rand((b, s, h), 2), _rand((b, s, h), 3)
    perm = zigzag_permutation(s, n)
    inv = np.argsort(perm)
    got_z = ring_attention(
        q[:, perm], k[:, perm], v[:, perm], mesh=mesh, axis="data",
        causal=causal, impl="zigzag",
    )
    got = np.asarray(got_z)[:, inv]
    np.testing.assert_allclose(
        got, dense_attention(q, k, v, causal=causal), atol=2e-5, rtol=1e-5
    )


@slow
def test_zigzag_gradients_match_dense():
    from parameter_server_tpu.models.attention import zigzag_permutation

    mesh = make_mesh(num_data=2, num_server=1)
    b, s, h = 1, 64, 16
    q, k, v = _rand((b, s, h), 1), _rand((b, s, h), 2), _rand((b, s, h), 3)
    w = _rand((b, s, h), 4)
    perm = zigzag_permutation(s, 2)
    inv = np.argsort(perm)

    def loss_z(q, k, v):
        out = ring_attention(
            q[:, perm], k[:, perm], v[:, perm], mesh=mesh, axis="data",
            causal=True, impl="zigzag",
        )
        return jnp.sum(out[:, inv] * w)

    def loss_d(q, k, v):
        return jnp.sum(dense_attention(q, k, v, causal=True) * w)

    gz = jax.grad(loss_z, argnums=(0, 1, 2))(q, k, v)
    gd = jax.grad(loss_d, argnums=(0, 1, 2))(q, k, v)
    for a, b_ in zip(gz, gd):
        np.testing.assert_allclose(a, b_, atol=5e-5, rtol=1e-4)


@slow
def test_zigzag_permutation_roundtrip_and_validation():
    from parameter_server_tpu.models.attention import zigzag_permutation

    perm = zigzag_permutation(48, 3)
    assert sorted(perm.tolist()) == list(range(48))
    # device 0 must hold half-blocks 0 and 2n-1 (here 0 and 5)
    assert perm[:16].tolist() == list(range(0, 8)) + list(range(40, 48))
    with pytest.raises(ValueError, match="divide"):
        zigzag_permutation(50, 3)


def dense_swa(q, k, v, window):
    """Dense sliding-window reference: causal + (q_pos - k_pos) < window."""
    s = jnp.einsum("bqh,bkh->bqk", q, k) / jnp.sqrt(q.shape[-1])
    n = q.shape[1]
    pos = jnp.arange(n)
    keep = (pos[:, None] >= pos[None, :]) & (
        pos[:, None] - pos[None, :] < window
    )
    s = jnp.where(keep[None], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bqk,bkh->bqh", p, v)


@slow
@pytest.mark.parametrize("window", [1, 16, 100])
def test_sliding_window_kernel_matches_dense(window):
    bh, s, d = 2, 200, 48  # unaligned: exercises padding + block skip
    q, k, v = _rand((bh, s, d), 1), _rand((bh, s, d), 2), _rand((bh, s, d), 3)
    want = dense_swa(q, k, v, window)
    for up in (False, True):
        got = flash_attention(
            q, k, v, causal=True, window=window, use_pallas=up, interpret=up
        )
        np.testing.assert_allclose(got, want, atol=2e-5, rtol=1e-5)


@slow
def test_sliding_window_gradients():
    bh, s, d = 2, 136, 32
    q, k, v = _rand((bh, s, d), 1), _rand((bh, s, d), 2), _rand((bh, s, d), 3)
    w = _rand((bh, s, d), 4)

    def make_loss(up):
        def loss(q, k, v):
            out = flash_attention(
                q, k, v, causal=True, window=24, use_pallas=up, interpret=up
            )
            return jnp.sum(out * w)

        return jax.grad(loss, argnums=(0, 1, 2))

    def loss_dense(q, k, v):
        return jnp.sum(dense_swa(q, k, v, 24) * w)

    gd = jax.grad(loss_dense, argnums=(0, 1, 2))(q, k, v)
    for up in (False, True):
        for a, b in zip(make_loss(up)(q, k, v), gd):
            np.testing.assert_allclose(a, b, atol=5e-5, rtol=1e-4)


@slow
@pytest.mark.parametrize("impl", ["flash", "zigzag"])
def test_sliding_window_on_ring(impl):
    from parameter_server_tpu.models.attention import zigzag_permutation

    mesh = make_mesh(num_data=4, num_server=1)
    b, s, h, window = 2, 128, 32, 40
    q, k, v = _rand((b, s, h), 1), _rand((b, s, h), 2), _rand((b, s, h), 3)
    want = np.asarray(dense_swa(q, k, v, window))
    if impl == "zigzag":
        perm = zigzag_permutation(s, 4)
        got = np.asarray(
            ring_attention(
                q[:, perm], k[:, perm], v[:, perm], mesh=mesh, axis="data",
                causal=True, impl="zigzag", window=window,
            )
        )[:, np.argsort(perm)]
    else:
        got = np.asarray(
            ring_attention(
                q, k, v, mesh=mesh, axis="data", causal=True, impl="flash",
                window=window,
            )
        )
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=1e-5)


@slow
def test_window_validation():
    x = _rand((1, 16, 8), 0)
    with pytest.raises(ValueError, match="causal"):
        flash_attention(x, x, x, causal=False, window=4)
    with pytest.raises(ValueError, match=">= 1"):
        flash_attention(x, x, x, causal=True, window=0)
    mesh = make_mesh(num_data=2, num_server=1)
    with pytest.raises(ValueError, match="flash"):
        ring_attention(
            x, x, x, mesh=mesh, axis="data", causal=True, window=4
        )


@slow
@pytest.mark.parametrize("n_kv_heads", [1, 2])
def test_gqa_matches_expanded_dense(n_kv_heads):
    # grouped-query attention == dense MHA with the K/V heads repeated
    b, s, nh, dh = 2, 64, 4, 16
    q = _rand((b, s, nh * dh), 1)
    k = _rand((b, s, n_kv_heads * dh), 2)
    v = _rand((b, s, n_kv_heads * dh), 3)
    got = flash_mha(
        q, k, v, nh, causal=True, n_kv_heads=n_kv_heads,
        use_pallas=True, interpret=True,
    )
    # expand kv to full heads for the dense reference
    rep = nh // n_kv_heads

    def expand(x):
        x = x.reshape(b, s, n_kv_heads, dh)
        return np.repeat(np.asarray(x), rep, axis=2).reshape(b, s, nh * dh)

    want = dense_mha(q, jnp.asarray(expand(k)), jnp.asarray(expand(v)),
                     nh, causal=True)
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=1e-5)


@slow
def test_gqa_rejects_nondivisible():
    x = _rand((1, 16, 12), 0)
    with pytest.raises(ValueError, match="divide"):
        flash_mha(x, x, x, 4, n_kv_heads=3)


@slow
@pytest.mark.parametrize("causal", [False, True])
def test_ulysses_flash_matches_dense(causal):
    from parameter_server_tpu.models.attention import ulysses_attention

    mesh = make_mesh(num_data=4, num_server=1)
    b, s, nh, h = 2, 64, 4, 32
    q, k, v = _rand((b, s, h), 1), _rand((b, s, h), 2), _rand((b, s, h), 3)
    got = ulysses_attention(
        q, k, v, mesh=mesh, axis="data", n_heads=nh, causal=causal,
        impl="flash", use_pallas=True, interpret=True,
    )
    np.testing.assert_allclose(
        got, dense_mha(q, k, v, nh, causal=causal), atol=2e-5, rtol=1e-5
    )


@slow
def test_ulysses_flash_gradients_match_dense():
    from parameter_server_tpu.models.attention import ulysses_attention

    mesh = make_mesh(num_data=2, num_server=1)
    b, s, nh, h = 1, 32, 2, 16
    q, k, v = _rand((b, s, h), 1), _rand((b, s, h), 2), _rand((b, s, h), 3)
    w = _rand((b, s, h), 4)

    def loss_u(q, k, v):
        out = ulysses_attention(
            q, k, v, mesh=mesh, axis="data", n_heads=nh, causal=True,
            impl="flash",
        )
        return jnp.sum(out * w)

    def loss_d(q, k, v):
        return jnp.sum(dense_mha(q, k, v, nh, causal=True) * w)

    gu = jax.grad(loss_u, argnums=(0, 1, 2))(q, k, v)
    gd = jax.grad(loss_d, argnums=(0, 1, 2))(q, k, v)
    for a, b_ in zip(gu, gd):
        np.testing.assert_allclose(a, b_, atol=5e-5, rtol=1e-4)


@slow
def test_ulysses_flash_sliding_window():
    from parameter_server_tpu.models.attention import ulysses_attention

    mesh = make_mesh(num_data=2, num_server=1)
    b, s, nh, h, window = 1, 64, 2, 16, 12
    q, k, v = _rand((b, s, h), 1), _rand((b, s, h), 2), _rand((b, s, h), 3)
    got = ulysses_attention(
        q, k, v, mesh=mesh, axis="data", n_heads=nh, causal=True,
        impl="flash", window=window,
    )
    # dense SWA per head
    dh = h // nh
    qh = np.asarray(q).reshape(b, s, nh, dh)
    kh = np.asarray(k).reshape(b, s, nh, dh)
    vh = np.asarray(v).reshape(b, s, nh, dh)
    want = np.zeros_like(qh)
    for hh in range(nh):
        want[:, :, hh] = np.asarray(
            dense_swa(
                jnp.asarray(qh[:, :, hh]), jnp.asarray(kh[:, :, hh]),
                jnp.asarray(vh[:, :, hh]), window,
            )
        )
    np.testing.assert_allclose(
        got, want.reshape(b, s, h), atol=2e-5, rtol=1e-5
    )
    with pytest.raises(ValueError, match="flash"):
        ulysses_attention(
            q, k, v, mesh=mesh, axis="data", n_heads=nh, causal=True,
            window=window,
        )


@slow
def test_ulysses_rejects_bad_impl_and_stray_flags():
    from parameter_server_tpu.models.attention import ulysses_attention

    mesh = make_mesh(num_data=2, num_server=1)
    x = _rand((1, 16, 8), 0)
    with pytest.raises(ValueError, match="impl"):
        ulysses_attention(
            x, x, x, mesh=mesh, axis="data", n_heads=2, impl="dense"
        )
    with pytest.raises(ValueError, match="use_pallas"):
        ulysses_attention(
            x, x, x, mesh=mesh, axis="data", n_heads=2, interpret=True
        )


@slow
def test_lm_ring_flash_mode_matches_ring():
    from parameter_server_tpu.models.transformer import (
        LMConfig,
        init_lm,
        lm_forward,
    )

    mesh = make_mesh(num_data=4, num_server=1)
    cfg_r = LMConfig(vocab=64, d_model=32, n_heads=2, n_layers=2, d_ff=64)
    cfg_f = LMConfig(
        vocab=64, d_model=32, n_heads=2, n_layers=2, d_ff=64,
        attention="ring_flash",
    )
    params = init_lm(jax.random.PRNGKey(0), cfg_r)
    toks = jnp.asarray(
        np.random.default_rng(0).integers(0, 64, size=(2, 64)), jnp.int32
    )
    lr = lm_forward(params, toks, cfg_r, mesh)
    lf = lm_forward(params, toks, cfg_f, mesh)
    np.testing.assert_allclose(lr, lf, atol=2e-5, rtol=1e-5)


# -- the listed grid (tier-1) ------------------------------------------------
#
# The kernels visit the block pairs of ``fa.grid_tables`` and no other:
# exact where the offsets are Python ints, of a fixed length with skipped
# spare steps where they are traced.

MASKS = {
    "causal": dict(causal=True),
    "window1": dict(causal=True, window=1),
    "window16": dict(causal=True, window=16),
    "window100": dict(causal=True, window=100),
    "full": dict(causal=False),
}
# (q_offset, k_offset): delta 0, a multiple of the block, not a multiple,
# and keys ahead of every query (a fully masked chunk)
OFFSETS = {"d0": (0, 0), "d128": (128, 0), "d70": (70, 0), "ahead": (0, 448)}
# (sq, sk, block_q, block_k): square, sq != sk with blocks that differ,
# lengths that are no multiple of their block
SHAPES = {
    "square": (256, 256, 64, 64),
    "wide": (192, 320, 64, 128),
    "ragged": (200, 136, 128, 64),
}


def _grid_case(mask, offsets, shape):
    """What ``grid_tables`` is given for a case of the parity test: the
    lengths, the blocks as ``flash_attention`` clamps them (a window caps
    them at 128), the mask and ``delta``."""
    sq, sk, block_q, block_k = SHAPES[shape]
    bq, bk = fa._blocks(sq, sk, block_q, block_k)
    qo, ko = OFFSETS[offsets]
    kw = {"causal": False, "window": None, **MASKS[mask]}
    return (sq, sk, bq, bk), kw, qo - ko


@pytest.mark.parametrize("shape", list(SHAPES))
@pytest.mark.parametrize("offsets", list(OFFSETS))
@pytest.mark.parametrize("traced", [False, True], ids=["ints", "traced"])
@pytest.mark.parametrize("mask", list(MASKS))
def test_listed_grid_matches_ref(mask, traced, offsets, shape):
    """Values, lse and the three gradients of the kernels on their listed
    grid against the XLA path, the offsets as Python ints (the exact
    list) and traced through ``jit`` (the bounded one)."""
    sq, sk, block_q, block_k = SHAPES[shape]
    qo, ko = OFFSETS[offsets]
    bh, d = 2, 32
    q, k, v = _rand((bh, sq, d), 1), _rand((bh, sk, d), 2), _rand((bh, sk, d), 3)
    w, w_lse = _rand((bh, sq, d), 4), _rand((bh, sq), 5)

    def both(use_pallas):
        def loss(q, k, v, qo, ko):
            out, lse = flash_attention(
                q, k, v, q_offset=qo, k_offset=ko, block_q=block_q,
                block_k=block_k, use_pallas=use_pallas, interpret=use_pallas,
                with_lse=True, **MASKS[mask],
            )
            # a fully masked row's lse is the constant -1e30
            kept = jnp.where(lse > -1e29, lse, 0.0)
            return jnp.sum(out * w) + jnp.sum(kept * w_lse), (out, lse)

        return jax.value_and_grad(loss, argnums=(0, 1, 2), has_aux=True)

    (_, (o_ref, lse_ref)), g_ref = jax.jit(both(False))(q, k, v, qo, ko)
    if traced:
        (_, (o_pal, lse_pal)), g_pal = jax.jit(both(True))(
            q, k, v, jnp.int32(qo), jnp.int32(ko)
        )
    else:  # the offsets stay Python ints inside the program
        (_, (o_pal, lse_pal)), g_pal = jax.jit(
            lambda q, k, v: both(True)(q, k, v, qo, ko)
        )(q, k, v)
    np.testing.assert_allclose(o_ref, o_pal, atol=2e-5, rtol=1e-5)
    np.testing.assert_allclose(lse_ref, lse_pal, atol=2e-5, rtol=1e-5)
    for a, b in zip(g_ref, g_pal):
        np.testing.assert_allclose(a, b, atol=5e-5, rtol=1e-4)
    if offsets == "ahead" and MASKS[mask]["causal"]:
        assert float(jnp.max(jnp.abs(o_pal))) == 0.0
        assert float(jnp.max(lse_pal)) < -1e29


def _live_set(outer, inner, live, order):
    pairs = {(int(o), int(i)) for o, i, f in zip(outer, inner, live) if f}
    return pairs if order == "q" else {(i, o) for o, i in pairs}


@pytest.mark.parametrize("shape", list(SHAPES))
@pytest.mark.parametrize("offsets", list(OFFSETS))
@pytest.mark.parametrize("mask", list(MASKS))
def test_the_listed_pairs_are_block_live_over_the_rectangle(
    mask, offsets, shape
):
    """The exact list (``delta`` an int) and the bounded one (traced)
    hold every pair ``_block_live`` keeps, once, and flag no other; each
    outer block has a step and its steps are consecutive; the bounded
    list's spare steps repeat a pair, so they fetch nothing."""
    (sq, sk, bq, bk), kw, delta = _grid_case(mask, offsets, shape)
    nq, nk = -(-sq // bq), -(-sk // bk)
    want = {
        (i, j) for i in range(nq) for j in range(nk)
        if fa._block_live(delta, i, bq, 0, j * bk, bk, kw["causal"],
                          kw["window"])
    }
    for order, n_outer in (("q", nq), ("k", nk)):
        exact = fa.grid_tables(sq, sk, bq, bk, delta=delta, order=order, **kw)
        bounded = jax.jit(
            lambda d: fa.grid_tables(sq, sk, bq, bk, delta=d, order=order, **kw)
        )(jnp.int32(delta))
        visited, live = fa.grid_steps(
            sq, sk, bq, bk, delta=delta, order=order, **kw
        )
        assert (visited, live) == (len(exact[0]), len(want))
        assert visited <= nq * nk
        unknown = fa.grid_steps(sq, sk, bq, bk, delta=None, order=order, **kw)
        assert unknown == (len(bounded[0]), None) and unknown[0] <= nq * nk
        for outer, inner, flag in (exact, np.asarray(bounded)):
            assert _live_set(outer, inner, flag, order) == want
            assert int(np.sum(flag)) == len(want)  # no pair twice
            # every outer block, in ascending runs
            assert np.all(np.diff(outer) >= 0)
            assert sorted(set(outer.tolist())) == list(range(n_outer))
            # a step that is not live repeats the blocks of the step
            # before it, or opens the row of a block no key reaches
            for s in np.flatnonzero(flag == 0):
                if s and outer[s] == outer[s - 1]:
                    assert inner[s] == inner[s - 1]
                else:
                    assert not np.any(flag[outer == outer[s]])


@pytest.mark.parametrize("order", ["q", "k"])
def test_grid_steps_at_the_cells_shapes(order):
    """8,192 tokens in 512 x 512 blocks, the LM cells' attention: a
    causal layer visits the 136 blocks of its triangle and a window
    layer (1,024) its 45, where the rectangle has 256; with a traced
    delta the window's list is 4 steps a row."""
    at = dict(sq=8192, sk=8192, block_q=512, block_k=512, order=order)
    assert fa.grid_steps(causal=True, window=None, delta=0, **at) == (136, 136)
    assert fa.grid_steps(causal=True, window=1024, delta=0, **at) == (45, 45)
    assert fa.grid_steps(causal=False, window=None, delta=0, **at) == (256, 256)
    visited, live = fa.grid_steps(causal=True, window=1024, delta=None, **at)
    assert visited == 64 and live is None
    assert fa.grid_steps(
        causal=True, window=None, delta=None, **at
    ) == (256, None)
    # a chunk whose keys are all ahead: one step a block, none live
    assert fa.grid_steps(
        causal=True, window=None, delta=-8192, **at
    ) == (16, 0)


def test_the_lm_step_on_one_device_lists_its_grids(flash_as_on_the_chip):
    """The offsets reach the kernels as Python ints from a one-device
    ring: the gradient program of a model with window and full layers
    holds Pallas grids of the exact lists' lengths, and the gauge counts
    as many live steps as visited ones."""
    from conftest import jaxpr_eqns
    from jax.sharding import Mesh

    from parameter_server_tpu.models import transformer as tfm
    from parameter_server_tpu.telemetry import registry as telreg

    cfg = tfm.LMConfig(
        vocab=256, d_model=64, n_heads=2, n_kv_heads=1, n_layers=3,
        d_ff=64, window=32, attention="ring_flash",
        layers=(("swa", "dense"), ("swa", "dense"), ("mha", "dense")),
    )
    params = tfm.init_lm(jax.random.PRNGKey(0), cfg)
    tokens = jnp.zeros((1, 1100), jnp.int32)
    mesh = Mesh(np.array(jax.devices()[:1]).reshape(1, 1), ("data", "server"))

    def series():
        state = telreg.default_registry().export_state()
        return {
            (s["labels"]["kernel"], s["labels"]["what"]): s["value"]
            for s in state.get("ps_flash_grid_steps", {}).get("series", [])
        }

    before = series()
    jaxpr = jax.make_jaxpr(
        jax.grad(lambda p: tfm.lm_loss(p, tokens, cfg, mesh))
    )(params).jaxpr
    grids = sorted(
        e.params["grid_mapping"].grid[1] for e in jaxpr_eqns(jaxpr)
        if e.primitive.name == "pallas_call"
    )
    # window 32 caps the blocks at 128: 9 of them, each row its own block
    # and the one before; full layers keep 512: a triangle of 3
    assert fa.grid_steps(
        1100, 1100, 128, 128, causal=True, window=32, delta=0, order="q"
    ) == (17, 17)
    assert grids == [6] * 3 + [17] * 6
    after = series()
    moved = {k: after[k] - before.get(k, 0.0) for k in after}
    for kernel in ("fwd", "dq", "dkv"):
        assert moved[kernel, "visited"] == moved[kernel, "live"] > 0

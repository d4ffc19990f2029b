"""The row write-back's contract (ops/rows.py, PR 27).

``indices_are_sorted`` is a promise XLA does not check and the CPU
ignores: a wrong one shows only on the chip, as wrong tables. So these
tests look at the index vector and at the flags of the traced scatter,
not only at results: wherever the code passes the promise the vector is
strictly increasing, and where it does not the test says so.
"""


import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import shard_map
from jax.sharding import PartitionSpec as P

from parameter_server_tpu.apps.linear.config import (
    Config,
    LearningRateConfig,
    PenaltyConfig,
    SGDConfig,
)
from parameter_server_tpu.apps.linear.learning_rate import LearningRate
from parameter_server_tpu.apps.linear.penalty import ElasticNet
from parameter_server_tpu.apps.linear.updaters import (
    FTRLUpdater,
    apply_state_rows,
)
from parameter_server_tpu.ops import rows as rowlib
from parameter_server_tpu.ops.ftrl_sparse import ftrl_sparse_rows_ref
from parameter_server_tpu.ops.kv_ops import localize, slot_sentinel
from parameter_server_tpu.parallel import mesh as meshlib
from parameter_server_tpu.parallel.mesh import SERVER_AXIS
from parameter_server_tpu.system.postoffice import Postoffice
from parameter_server_tpu.utils.sparse import random_sparse


@pytest.fixture(autouse=True)
def fresh_po():
    Postoffice.reset()
    yield
    Postoffice.reset()


@pytest.fixture(scope="module")
def mesh1():
    """One server shard on one device: the mesh of a one-chip run."""
    return meshlib.make_mesh(num_data=1, num_server=1)


def _uslots(num_slots: int, live: int, width: int, seed: int = 0):
    """What prep hands the step: ascending unique slots, then the
    sentinel no shard owns."""
    rng = np.random.default_rng(seed)
    hi = min(num_slots, 1 << 31)
    own = np.unique(rng.integers(0, hi, 4 * live + 4, dtype=np.int64))
    own = np.sort(rng.permutation(own)[:live])
    out = np.full(width, slot_sentinel(num_slots), np.int32)
    out[:live] = own
    return out


def _index_per_shard(mesh, uslots, shard: int):
    """(rel, ok, idx) of every server shard, stacked on axis 0."""
    def local(us):
        rel, ok = localize(us, shard)
        idx = rowlib.write_index(rel, ok, shard)
        return rel[None], ok[None], idx[None]

    spec = P(SERVER_AXIS)
    rel, ok, idx = jax.jit(shard_map(
        local, mesh=mesh, in_specs=P(), out_specs=(spec, spec, spec),
        check_vma=False,
    ))(jnp.asarray(uslots))
    return np.asarray(rel), np.asarray(ok), np.asarray(idx)


def _ascends(idx) -> bool:
    return bool(np.all(np.diff(np.asarray(idx).astype(np.int64)) > 0))


def _check_index(rel, ok, idx, shard: int):
    """What every index vector owes, ordered or not: kept entries are
    their row and in range, dropped ones past the end, none repeats."""
    assert idx.dtype == np.uint32
    wide = idx.astype(np.int64)
    assert np.array_equal(wide[ok], rel[ok].astype(np.int64))
    assert np.all(wide[ok] < shard) and np.all(wide[~ok] >= shard)
    assert len(np.unique(wide)) == len(wide)


class TestWriteIndex:
    @pytest.mark.parametrize("live", [200, 0, 256], ids=[
        "padded_tail", "all_padding", "no_padding",
    ])
    def test_one_shard_is_strictly_increasing(self, mesh1, live):
        shard = 1 << 14
        us = _uslots(shard, live, 256)
        rel, ok, idx = _index_per_shard(mesh1, us, shard)
        assert ok[0].sum() == live
        _check_index(rel[0], ok[0], idx[0], shard)
        assert _ascends(idx[0])

    def test_2p31_table_minus_one_sentinel(self):
        """At 2^31 slots the sentinel is -1 and clips to rel 0, BELOW
        the owned ids; the index vector still puts it past the end,
        where uint32 holds 2^31 + U."""
        shard = 1 << 31
        us = _uslots(shard, 200, 256)
        us[199] = (1 << 31) - 1  # the table's last row is owned
        assert us[-1] == -1
        rel, ok = localize(jnp.asarray(us), shard)
        idx = np.asarray(rowlib.write_index(rel, ok, shard))
        rel, ok = np.asarray(rel), np.asarray(ok)
        assert rel[-1] == 0 and not ok[-1]
        _check_index(rel, ok, idx, shard)
        assert _ascends(idx)
        assert int(idx[-1]) == (1 << 31) + 255

    def test_later_shard_of_a_server_mesh_is_not_ordered(self, mesh8):
        """Two server shards: on the second the ids the first owns come
        first and are dropped first, so its vector is duplicate-free
        and NOT increasing. The step therefore passes no order promise
        there (TestStepPromise)."""
        shard = 1 << 13
        us = _uslots(2 * shard, 200, 256)
        rel, ok, idx = _index_per_shard(mesh8, us, shard)
        assert ok[0].any() and ok[1].any()
        for s in range(2):
            _check_index(rel[s], ok[s], idx[s], shard)
        assert _ascends(idx[0])
        assert not _ascends(idx[1])


# ---------------------------------------------------------------------------
# the step: what it promises, on which mesh, with and without KKT holes
# ---------------------------------------------------------------------------


def _conf(num_slots=1 << 14, state_dtype="float32", **sgd_kw) -> Config:
    conf = Config()
    conf.penalty = PenaltyConfig(type="l1", lambda_=[0.1])
    conf.learning_rate = LearningRateConfig(type="decay", alpha=0.1, beta=1.0)
    conf.async_sgd = SGDConfig(
        algo="ftrl", minibatch=64, num_slots=num_slots, max_delay=0,
        update="sparse", ftrl_state_dtype=state_dtype, **sgd_kw,
    )
    return conf


def _batches(n, seed0=0):
    out = []
    for i in range(n):
        b = random_sparse(64, 1 << 12, 6, seed=seed0 + i, binary=True)
        b.y = np.where(np.arange(64) % 3 == 0, 1.0, -1.0).astype(np.float32)
        out.append(b)
    return out


#: a margin past every gradient, escape hatch off: the KKT filter masks
#: every slot, so every owned row is a hole
ALL_HOLES = dict(kkt_filter=True, kkt_margin=1e9, kkt_escape=0.0)


class TestStepPromise:
    @pytest.mark.parametrize("kkt", [{}, ALL_HOLES], ids=["plain", "kkt"])
    @pytest.mark.parametrize("servers", [1, 2])
    def test_index_vectors_of_a_training_run(self, monkeypatch, mesh1,
                                             mesh8, servers, kkt):
        from parameter_server_tpu.apps.linear.async_sgd import AsyncSGDWorker

        seen = []
        real = rowlib.write_rows

        def spy(full, idx, new, *, rows_ascend):
            jax.debug.callback(
                lambda i: seen.append(
                    (np.asarray(i), rows_ascend, full.shape[0])
                ),
                idx,
            )
            return real(full, idx, new, rows_ascend=rows_ascend)

        monkeypatch.setattr(rowlib, "write_rows", spy)
        worker = AsyncSGDWorker(
            _conf(**kkt), mesh=mesh1 if servers == 1 else mesh8
        )
        try:
            worker.train(iter(_batches(3)))
            jax.effects_barrier()
        finally:
            worker.executor.stop()
        assert seen
        for idx, promised, shard in seen:
            wide = idx.astype(np.int64)
            assert len(np.unique(wide)) == len(wide)
            # masked slots keep their place: rows ARE written
            assert (wide < shard).any()
            # one server shard: the promise is made, and it holds.
            # Two: it is not made (the second shard's vector does not
            # ascend, see TestWriteIndex)
            assert promised == (servers == 1)
            if promised:
                assert _ascends(idx)
        if servers == 2:
            assert not all(_ascends(i) for i, _, _ in seen)


def _subjaxprs(jaxpr):
    for eqn in jaxpr.eqns:
        yield eqn
        for v in eqn.params.values():
            for j in v if isinstance(v, (list, tuple)) else (v,):
                inner = getattr(j, "jaxpr", j)
                if hasattr(inner, "eqns"):
                    yield from _subjaxprs(inner)


def test_bigtable_shaped_ministep_scatters_carry_both_hints(mesh1):
    """criteo_bigtable.text at toy size: FTRL with bf16 sqrt_n and a
    snapshot (max_delay 4), one server shard, a launch of ministeps
    scanned on the live state. Its only two scatter-sets are the row
    write-backs of z and sqrt_n, and both tell XLA what prep
    guarantees, so the hint cannot vanish unnoticed."""
    from parameter_server_tpu.apps.linear.async_sgd import AsyncSGDWorker

    conf = _conf(state_dtype="bfloat16", steps_per_launch=2)
    conf.async_sgd.max_delay = 4
    worker = AsyncSGDWorker(conf, mesh=mesh1)
    try:
        (host, n_steps), = worker._prep_group(_batches(2))
        assert n_steps == 2
        prepped = worker.upload(host)
        step = worker._get_step(prepped, with_aux=False)
        state = worker.state
        jaxpr = jax.make_jaxpr(
            lambda st, b: step(st, st, b, np.uint32(3))
        )(state, prepped)
    finally:
        worker.executor.stop()
    sets = [
        e for e in _subjaxprs(jaxpr.jaxpr) if e.primitive.name == "scatter"
    ]
    tables = sorted(
        str(e.invars[0].aval.dtype) for e in sets
        if e.invars[0].aval.shape == (1 << 14,)
    )
    assert tables == ["bfloat16", "float32"], [str(e) for e in sets]
    for e in sets:
        if e.invars[0].aval.shape == (1 << 14,):
            assert e.params["indices_are_sorted"] is True
            assert e.params["unique_indices"] is True


# ---------------------------------------------------------------------------
# old against new, bit for bit
# ---------------------------------------------------------------------------


def _old_write_back(full, rel, ok, new_leaf):
    """The plain scatter: every dropped entry at the ONE index
    one-past-the-end, nothing declared."""
    oob = jnp.where(ok, rel.astype(jnp.uint32), jnp.uint32(full.shape[0]))
    return full.at[oob].set(new_leaf.astype(full.dtype), mode="drop")


def _old_apply_state_rows(updater, state, rel, ok, g_u, seed):
    state_u = jax.tree.map(lambda a: a[rel], state)
    new_u = updater.apply(state_u, jnp.where(ok, g_u, 0.0), None, seed=seed)
    return jax.tree.map(
        lambda full, new: _old_write_back(full, rel, ok, new), state, new_u
    )


def _bits(a):
    a = np.asarray(a)
    return a.view(np.uint16 if a.dtype.itemsize == 2 else np.uint32)


@pytest.mark.parametrize("via", ["apply_state_rows", "ftrl_sparse_rows_ref"])
@pytest.mark.parametrize("kkt", [False, True], ids=["plain", "kkt_holes"])
@pytest.mark.parametrize("servers", [1, 2], ids=["one_shard", "mesh8"])
@pytest.mark.parametrize("n_dtype,seed", [
    (jnp.float32, None), (jnp.bfloat16, 11),
], ids=["sqrt_n_f32", "sqrt_n_bf16_seeded"])
def test_write_back_bit_equal_to_the_unhinted_scatter(
        mesh1, mesh8, n_dtype, seed, servers, kkt, via):
    """The same rows, gradients and seed through the plain scatter
    (holes of the KKT mask DROPPED, ``ok & keep``) and through the
    write-back (holes keep their index and are rewritten with the bits
    they were read with; the index vector strictly increasing on one
    shard): every slot of z and sqrt_n bit-equal."""
    mesh = mesh1 if servers == 1 else mesh8
    num_slots, width, live = 1 << 14, 512, 400
    shard = num_slots // servers
    hp = dict(alpha=0.1, beta=1.0, l1=1.0, l2=0.1)
    updater = FTRLUpdater(
        LearningRate("decay", alpha=hp["alpha"], beta=hp["beta"]),
        ElasticNet(hp["l1"], hp["l2"]), sqrt_n_dtype=n_dtype,
    )
    rng = np.random.default_rng(5)
    state = {
        "z": jnp.asarray(rng.normal(size=num_slots), jnp.float32),
        "sqrt_n": jnp.asarray(rng.random(num_slots) * 2, n_dtype),
    }
    us = jnp.asarray(_uslots(num_slots, live, width, seed=6))
    g_u = jnp.asarray(rng.normal(size=width), jnp.float32)
    keep = jnp.asarray(
        rng.random(width) < 0.5 if kkt else np.ones(width, bool)
    )
    sd = None if seed is None else jnp.uint32(seed)

    def local(st, us, g_u, keep):
        rel, ok = localize(us, shard)
        ascend = jax.lax.axis_size(SERVER_AXIS) == 1
        g = jnp.where(keep, g_u, 0.0)
        if via == "apply_state_rows":
            new = apply_state_rows(
                updater, st, rel, ok, g, seed=sd, rows_ascend=ascend
            )
            old = _old_apply_state_rows(updater, st, rel, ok & keep, g, sd)
        else:
            z, n = ftrl_sparse_rows_ref(
                st["z"], st["sqrt_n"], rel, ok, g, seed=sd,
                rows_ascend=ascend, **hp,
            )
            new = {"z": z, "sqrt_n": n}
            old = _old_apply_state_rows(updater, st, rel, ok & keep, g, sd)
        return new, old

    spec = {"z": P(SERVER_AXIS), "sqrt_n": P(SERVER_AXIS)}
    new, old = jax.jit(shard_map(
        local, mesh=mesh, in_specs=(spec, P(), P(), P()),
        out_specs=(spec, spec), check_vma=False,
    ))(state, us, g_u, keep)
    touched = 0
    for k in ("z", "sqrt_n"):
        assert new[k].dtype == state[k].dtype
        assert np.array_equal(_bits(new[k]), _bits(old[k])), k
        touched += int((_bits(new[k]) != _bits(state[k])).sum())
    assert touched > 0  # the comparison is not of two untouched tables

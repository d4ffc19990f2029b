"""The benchmark's language-model steps whose expert layers' tail is one
piece (the Mistral and the Mellum cell), compiled at their real size for
a TPU v5e that is described and not attached (no chip time, no result,
no timing): what the chip's compiler would refuse, and the memory it
plans.

The topology is described inside a fixture, never while a module is
imported, and only in this file: one process at a time may load the
TPU's library.
"""

from __future__ import annotations

import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CONFIGS = os.path.join(ROOT, "chipbench", "configs")
# configuration -> its f32 parameters, and what a tail's untaken branch
# may hold beside parameters and tuples (the last test says why)
CELLS = {
    "mistral_small4_ep16": (1_154_524_160, []),
    "mellum2_ep4": (1_077_059_840, ["copy"]),
}
CHIP_BYTES = 16_909_336_064


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # noqa: BLE001
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def no_compile_cache():
    """A compile for a described chip is written to the persistent cache
    and cannot be read back without one: keep it out."""
    from jax.experimental.compilation_cache import compilation_cache

    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", before)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module", params=list(CELLS))
def step(request, topo, no_compile_cache):
    """``(cfg, compiled, moved)``: a cell's step as its trainer builds
    it, compiled once for both tests, and ``CELLS``' expectation of its
    untaken branches."""
    from parameter_server_tpu.apps.lm import trainer as lm_trainer
    from parameter_server_tpu.models.transformer import init_lm
    from parameter_server_tpu.ops import flash_attention as fa

    desc = lm_trainer.load_description(
        os.path.join(CONFIGS, request.param + ".json")
    )
    t = desc["train"]
    cfg = lm_trainer.model_from_description(
        desc, attention=t["attention"], remat=t["remat"], bf16=t["bf16"]
    )
    mesh = Mesh(np.array(topo.devices[:1]).reshape(1, 1), ("data", "server"))
    trainer = lm_trainer.build_trainer(
        cfg, mesh, optimizer=t["optimizer"], lr=t["lr"]
    )
    here = NamedSharding(mesh, P())
    spec = lambda x: jax.ShapeDtypeStruct(  # noqa: E731
        x.shape, x.dtype, sharding=here
    )
    params = jax.tree.map(
        spec, jax.eval_shape(lambda k: init_lm(k, cfg), jax.random.PRNGKey(0))
    )
    opt = jax.tree.map(spec, jax.eval_shape(trainer.tx.init, params))
    tokens = jax.ShapeDtypeStruct(
        (t["batch"], t["seq_len"]), jnp.int32,
        sharding=NamedSharding(mesh, P(None, "data")),
    )
    # the code asks the backend which attention to take, and the backend
    # here is the CPU: steer it to the kernels the chip runs
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(fa, "_on_tpu", lambda: True)
        compiled = trainer.step.lower(params, opt, tokens).compile()
    n_params = sum(int(np.prod(x.shape)) for x in jax.tree.leaves(params))
    expected_params, moved = CELLS[request.param]
    assert n_params == expected_params
    return cfg, compiled, moved


def test_the_cells_step_compiles_for_one_v5e_chip(step):
    cfg, compiled, _ = step
    # the flash kernels, the only pallas_call of the step: forward, dq and
    # dkv once a layer (latent attention in one cell, window and full GQA
    # layers in the other). The forward's output and log-sum-exp are kept
    # across jax.checkpoint, so the recomputed layer holds no fourth
    kernels = re.findall(
        r' custom-call\([^\n]*custom_call_target="tpu_custom_call"'
        r'[^\n]*op_name="[^"]*pallas_call"', compiled.as_text()
    )
    assert len(kernels) == 3 * len(cfg.layer_kinds), len(kernels)
    memory = compiled.memory_analysis()
    planned = (
        memory.argument_size_in_bytes + memory.output_size_in_bytes
        + memory.temp_size_in_bytes - memory.alias_size_in_bytes
    )
    # weights once (donated) and the step's temporaries, the sorted-token
    # buffers of the head and each layer's kept attention output among
    # them: 11.37 GB (Mistral) and 7.67 GB (Mellum, its untaken tail of
    # 32,768 rows planned too) when written. A plan over 80% of the chip
    # leaves the allocator no room
    assert 0.25 * CHIP_BYTES < planned < 0.80 * CHIP_BYTES, planned


def test_the_buffers_tail_not_taken_moves_nothing(step):
    """Every expert layer's tail (``models/moe.py``) is a conditional
    forward and one backward (the recomputed forward's is dead code),
    and the branch taken when no held assignment passed the head hands
    its operands back: no zeros, no gather, no product. In the Mistral
    step no instruction at all, as since PR 30. The Mellum step keeps
    its residual stream with the tokens minor ([8192, 2304]
    column-major), and the change of layout that the step compiled
    without a tail made once a layer after the combine now ends both
    branches of every conditional: exactly one ``copy``, of the one
    [tokens, d_model] array the branch hands back, and nothing else.
    Each cell is held to its own list."""
    cfg, compiled, expected = step
    text = compiled.as_text()
    bodies = dict(re.findall(
        r"^%(\S+) \([^\n]*\{\n(.*?)^\}", text, flags=re.M | re.S
    ))
    branches = re.findall(
        r" conditional\(.*?branch_computations=\{([^}]*)\}", text
    )
    n_moe = sum(ffn == "moe" for _, ffn in cfg.layer_kinds)
    assert len(branches) == 2 * n_moe
    for names in branches:
        not_taken = bodies[names.split(",")[0].strip().lstrip("%")]
        # an opcode is the lower-case word before a bracket (the
        # layouts' ``T(8,128)`` and ``S(1)`` are upper-case)
        ops = re.findall(r" ([a-z][a-z-]*)\(", not_taken)
        assert ops
        moved = sorted(
            op for op in ops
            if op not in ("parameter", "get-tuple-element", "tuple")
        )
        assert moved == expected, moved
        copied = re.findall(
            r"= (\w+\[[\d,]*\])\{[^}]*\} copy\(", not_taken
        )
        assert len(copied) == len(expected) and all(
            re.fullmatch(rf"bf16\[\d+,{cfg.d_model}\]", c) for c in copied
        ), copied

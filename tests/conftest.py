"""Test harness: force a virtual 8-device CPU mesh before jax initializes.

Mirrors the reference's ``local.sh`` multi-process test launcher
(src/test/*.cc run with N servers + M workers): here the "nodes" are 8
virtual XLA CPU devices, so every sharding/collective path is exercised
without TPU hardware.
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (_flags + " --xla_force_host_platform_device_count=8").strip()
os.environ.setdefault("JAX_ENABLE_X64", "0")

import jax  # noqa: E402

import numpy as np  # noqa: E402
import pytest  # noqa: E402


@pytest.fixture(scope="session")
def mesh8():
    from parameter_server_tpu.parallel import mesh as meshlib

    assert len(jax.devices()) == 8, jax.devices()
    return meshlib.make_mesh(num_data=4, num_server=2)


@pytest.fixture()
def rng():
    return np.random.default_rng(0)


def require_native(symbol: str = None):
    """The loaded libpsnative handle for native-vs-NumPy parity tests.
    The loader builds it on first use and raises with the compiler's
    output when it cannot; a build that lacks ``symbol`` fails here."""
    from parameter_server_tpu.cpp import native

    lib = native()
    if symbol is not None and getattr(lib, symbol, None) is None:
        pytest.fail(f"libpsnative has no symbol {symbol}")
    return lib


def repo_texts(tops, suffixes):
    """``(path relative to the repo, text)`` of every file under the
    repo's ``tops`` (files or directories) whose name ends with one of
    ``suffixes``: what the tests that police names across files read."""
    import os

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    for top in tops:
        path = os.path.join(repo, top)
        walk = os.walk(path) if os.path.isdir(path) else [(repo, [], [top])]
        for root, dirs, names in walk:
            dirs[:] = [d for d in dirs if d != "__pycache__"]
            for name in sorted(names):
                if name.endswith(suffixes):
                    full = os.path.join(root, name)
                    with open(full, encoding="utf-8", errors="replace") as f:
                        yield os.path.relpath(full, repo), f.read()


@pytest.fixture()
def flash_as_on_the_chip(monkeypatch):
    """The backend here is the CPU and the code asks it which attention
    to take: steer it to the kernels the chip runs, for tests that count
    a program's kernel calls and never lower it. Traces cached under
    either answer are forgotten on the way in and on the way out."""
    from parameter_server_tpu.ops import flash_attention as fa

    jax.clear_caches()
    monkeypatch.setattr(fa, "_on_tpu", lambda: True)
    yield
    jax.clear_caches()


def jaxpr_eqns(jaxpr):
    """Every equation of a jaxpr and of the jaxprs inside it: what the
    tests that count a program's kernel calls walk."""
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from jaxpr_eqns(sub)


def pallas_calls(fn, *args) -> int:
    """How many ``pallas_call`` equations the program of ``fn(*args)``
    holds. A jaxpr is never lowered, so the CPU counts what a chip runs."""
    jaxpr = jax.make_jaxpr(fn)(*args).jaxpr
    return sum(e.primitive.name == "pallas_call" for e in jaxpr_eqns(jaxpr))

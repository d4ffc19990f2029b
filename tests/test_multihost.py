"""Multi-process (multi-host) integration: the DCN story.

Counterpart of the reference's local.sh-driven ``*_ps.cc`` runs with
separate server/worker OS processes. Here N processes join via
jax.distributed (gloo collectives on CPU standing in for DCN), form one
global mesh, and run real training steps where each process feeds its own
data partition — see tests/multihost_child.py.
"""

import os
import socket
import subprocess
import sys

import pytest

# Promoted to the slow tier (PR 2, per the PR-1 ROADMAP note): the
# shard_map-shim unlock made the full 'not slow' suite overrun the
# 870s tier-1 budget on a 2-core host. Run via `pytest -m slow`.
pytestmark = pytest.mark.slow

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


@pytest.mark.parametrize("nproc", [2, 4])
def test_local_sh_n_hosts(nproc):
    """script/local.sh launches N federated processes; every one trains
    the same global model and reports the psum'd example count. nproc=4
    exercises cross-host server sharding seams (2x2 data x server per
    host pair) that 2 processes cannot; processes 0/1 additionally
    exchange filter-chained control frames over the DCN transport and
    assert the compression + key-cache byte reductions."""
    env = dict(os.environ)
    env["PS_PORT"] = str(_free_port())
    env["PS_LOCAL_DEVICES"] = "2"
    # local.sh overrides JAX_PLATFORMS/XLA_FLAGS itself
    proc = subprocess.run(
        ["bash", os.path.join(REPO, "script", "local.sh"), str(nproc),
         sys.executable, os.path.join(REPO, "tests", "multihost_child.py")],
        env=env,
        capture_output=True,
        text=True,
        timeout=600,
        cwd=REPO,
    )
    # processes share the pipe, so two PS_OK prints can interleave on one
    # line — parse occurrences, not lines
    import re

    oks = re.findall(r"PS_OK (\d+)", proc.stdout)
    assert proc.returncode == 0, (proc.stdout[-2000:], proc.stderr[-2000:])
    assert len(oks) == nproc, proc.stdout[-2000:]
    # all processes agree on the global example count
    assert len(set(oks)) == 1
    # the filtered control-plane exchange ran and its byte reductions
    # held (asserted in the child; the marker proves it executed)
    assert "PS_FILTER_OK" in proc.stdout, proc.stdout[-2000:]
    # the LM segment ran on every process (seq-sharded + FSDP over the
    # same multi-process mesh) and all processes agree on the
    # replicated loss to the printed precision
    lm = re.findall(r"PS_LM_OK ([0-9.]+)", proc.stdout)
    assert len(lm) == nproc, proc.stdout[-2000:]
    assert len(set(lm)) == 1, lm


def test_mpi_root_sh_4_ranks():
    """script/mpi_root.sh (the reference's mpi_root.sh/mpi_node.sh
    twins): ranks reach the SAME multihost training path through the
    mpi_node.sh env adapter — with no MPI runtime installed the
    launcher emulates local ranks, and mpi_node.sh still performs the
    rank->PS_* translation (the part a real mpirun would exercise
    per-host)."""
    import re

    env = dict(os.environ)
    env["PS_PORT"] = str(_free_port())
    env["PS_LOCAL_DEVICES"] = "2"
    # force the emulation branch even on machines WITH an MPI runtime —
    # this test pins the adapter/emulation path, not mpirun itself
    env["PS_MPIRUN"] = "/nonexistent/mpirun-for-test"
    proc = subprocess.run(
        ["bash", os.path.join(REPO, "script", "mpi_root.sh"), "4",
         sys.executable, os.path.join(REPO, "tests", "multihost_child.py")],
        env=env,
        capture_output=True,
        text=True,
        timeout=600,
        cwd=REPO,
    )
    assert proc.returncode == 0, (proc.stdout[-2000:], proc.stderr[-2000:])
    assert "emulating 4 local ranks" in proc.stderr
    oks = re.findall(r"PS_OK (\d+)", proc.stdout)
    assert len(oks) == 4 and len(set(oks)) == 1, proc.stdout[-2000:]


@pytest.mark.skipif(
    not os.environ.get("PS_MULTIHOST_8"),
    reason="8 federated jax processes on one core takes minutes; "
    "set PS_MULTIHOST_8=1 to run (verified live 2026-08-02, r5)",
)
def test_local_sh_8_hosts():
    """The launcher path at 8 ranks (r4 verdict item 8): 8 federated
    processes × 2 virtual devices = a 16-device global mesh with
    cross-host server shards 4 deep — seams that 4 ranks cannot
    reach. Same contract as test_local_sh_n_hosts."""
    import re

    env = dict(os.environ)
    env["PS_PORT"] = str(_free_port())
    env["PS_LOCAL_DEVICES"] = "2"
    proc = subprocess.run(
        ["bash", os.path.join(REPO, "script", "local.sh"), "8",
         sys.executable, os.path.join(REPO, "tests", "multihost_child.py")],
        env=env, capture_output=True, text=True, timeout=900, cwd=REPO,
    )
    assert proc.returncode == 0, (proc.stdout[-2000:], proc.stderr[-2000:])
    oks = re.findall(r"PS_OK (\d+)", proc.stdout)
    assert len(oks) == 8 and len(set(oks)) == 1, proc.stdout[-2000:]
    lm = re.findall(r"PS_LM_OK ([0-9.]+)", proc.stdout)
    assert len(lm) == 8 and len(set(lm)) == 1, lm

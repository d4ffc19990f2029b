"""Determinism pass (rule ``determinism``).

The replay invariant (ROADMAP, PR 6/11): re-running the same workload
byte-stream must reproduce the same bytes — wire encodings, FTRL
updates, checkpoint contents are all bit-identity contracts. Modules
under that contract declare it with a ``# bit-identical`` marker
comment (conventionally in the module docstring's vicinity); this pass
sweeps each scoped module for sources of run-to-run nondeterminism:

- **set iteration feeding output** — ``for k in someset``, packing a
  set/set-comprehension into ``list()``/``tuple()``/``sorted`` absent,
  or a set/dict comprehension flowing into an ``np.array``-shaped
  packing call: Python set order varies with hash seeding;
- **unsorted directory walks** — ``os.listdir``, ``glob.glob`` /
  ``iglob``, ``scandir``, ``iterdir`` return OS order; wrap in
  ``sorted(...)``;
- **unseeded RNG** — module-global ``random.*`` draws,
  ``random.Random()`` / ``np.random.default_rng()`` with no seed, and
  the legacy ``np.random.*`` draw functions;
- **wall-clock reads** — ``time.time`` / ``time_ns``,
  ``datetime.now`` / ``utcnow`` / ``today``: anything derived from them
  differs per run. ``perf_counter`` / ``monotonic`` are allowed — they
  time telemetry, they must never feed output (that is a review
  contract this pass cannot check).

A scoped module MISSING the ``# bit-identical`` marker is itself a
finding — the scope list below and the in-file annotations stay in
lockstep, so moving a module out of the contract is an explicit edit
in both places.

Syntactic only: a set bound to a variable and iterated two lines later
is invisible, as is a wall-clock value laundered through a helper. The
pass catches the direct forms; the replay tests catch the rest.
Suppress deliberate uses (telemetry timestamps in a wire header, a
seeded-by-caller RNG) with
``# pslint: disable=determinism — <reason>``.
"""

from __future__ import annotations

import ast
from typing import Dict, List, Sequence

from .engine import (
    BIT_IDENTICAL_RE,
    Finding,
    Rule,
    SourceFile,
    callee_chain,
)

#: the bit-identity contract surface (doc/STATIC_ANALYSIS.md)
SCOPE = (
    "parameter_server_tpu/learner/wire.py",
    "parameter_server_tpu/learner/ingest.py",
    "parameter_server_tpu/ops/wire_codec.py",
    "parameter_server_tpu/ops/ftrl.py",
    "parameter_server_tpu/ops/ftrl_sparse.py",
    "parameter_server_tpu/ops/rows.py",
    "parameter_server_tpu/ops/significance.py",
    "parameter_server_tpu/learner/consistency.py",
    "parameter_server_tpu/parameter/kv_vector.py",
    "parameter_server_tpu/parameter/replica.py",
)

_DIR_WALKS = {"listdir", "glob", "iglob", "scandir", "iterdir"}
_RANDOM_DRAWS = {
    "random", "randint", "randrange", "choice", "choices", "shuffle",
    "sample", "uniform", "gauss", "normalvariate", "betavariate",
}
_NP_DRAWS = {
    "rand", "randn", "randint", "random", "random_sample", "choice",
    "permutation", "shuffle", "uniform", "normal", "standard_normal",
}
_WALL_CLOCK = {
    ("time", "time"),
    ("time", "time_ns"),
    ("datetime", "now"),
    ("datetime", "utcnow"),
    ("date", "today"),
}
_NP_PACKERS = {"array", "asarray", "fromiter", "stack", "concatenate", "hstack", "vstack"}


def _is_set_expr(node: ast.AST) -> bool:
    if isinstance(node, (ast.Set, ast.SetComp)):
        return True
    if isinstance(node, ast.Call):
        chain = callee_chain(node)
        if chain[-1] == "set":
            return True
        # set ops that yield sets: a.union(b) etc on literal sets
        if (
            chain[-1] in ("union", "intersection", "difference")
            and node.args
            and isinstance(node.func, ast.Attribute)
        ):
            return _is_set_expr(node.func.value)
    if isinstance(node, ast.BinOp) and isinstance(
        node.op, (ast.BitOr, ast.BitAnd, ast.Sub)
    ):
        return _is_set_expr(node.left) or _is_set_expr(node.right)
    return False


class DeterminismRule(Rule):
    name = "determinism"
    version = "1"
    per_file = True  # purely per-file: content-hash cacheable

    def __init__(self, scope: Sequence[str] = SCOPE):
        self.scope = tuple(scope)

    def paths(self, root: str) -> Sequence[str]:
        return self.scope

    def check(self, files: Dict[str, SourceFile], root: str) -> List[Finding]:
        findings: List[Finding] = []
        for sf in files.values():
            findings.extend(self._check_file(sf))
        return findings

    def _check_file(self, sf: SourceFile) -> List[Finding]:
        findings: List[Finding] = []
        marked = any(
            BIT_IDENTICAL_RE.search(c) for c in sf.comments.values()
        ) or BIT_IDENTICAL_RE.search(
            ast.get_docstring(sf.tree) or ""
        )
        if not marked:
            findings.append(
                Finding(
                    sf.rel,
                    1,
                    "determinism",
                    "module is in the bit-identity scope but carries no "
                    "'# bit-identical' marker comment — add the marker "
                    "(or move the module out of the determinism scope)",
                )
            )
        parents = sf.parents()

        def inside_sorted(node: ast.AST) -> bool:
            p = parents.get(node)
            hops = 0
            while p is not None and hops < 3:
                if isinstance(p, ast.Call) and callee_chain(p)[-1] in (
                    "sorted", "frozenset", "set", "len", "min", "max", "sum",
                ):
                    # sorted() restores order; the others are
                    # order-insensitive consumers
                    return True
                p = parents.get(p)
                hops += 1
            return False

        def flag(node, msg):
            findings.append(Finding(sf.rel, node.lineno, "determinism", msg))

        for node in ast.walk(sf.tree):
            # set iteration feeding anything ordered
            if isinstance(node, (ast.For, ast.AsyncFor)) and _is_set_expr(
                node.iter
            ):
                flag(node.iter, "iterating a set: order varies per run; "
                     "iterate sorted(...) instead")
            elif isinstance(node, ast.comprehension) and _is_set_expr(
                node.iter
            ):
                flag(node.iter, "comprehension over a set: order varies "
                     "per run; use sorted(...)")
            elif isinstance(node, ast.Call):
                chain = callee_chain(node)
                tail = chain[-1]
                # list(someset) / tuple(someset) packs set order
                if (
                    tail in ("list", "tuple")
                    and node.args
                    and _is_set_expr(node.args[0])
                ):
                    flag(node, f"{tail}() over a set packs hash order; "
                         "use sorted(...)")
                elif tail in _DIR_WALKS and not inside_sorted(node):
                    flag(node, f"{tail}() returns OS order; wrap in "
                         "sorted(...)")
                elif (
                    len(chain) >= 2
                    and chain[-2] == "random"
                    and chain[0] in ("np", "numpy")
                    and tail in _NP_DRAWS
                ):
                    flag(node, f"legacy np.random.{tail}() draws from the "
                         "process-global unseeded stream; thread a "
                         "seeded Generator through instead")
                elif (
                    len(chain) == 2
                    and chain[0] == "random"
                    and tail in _RANDOM_DRAWS
                ):
                    flag(node, f"random.{tail}() uses the unseeded global "
                         "RNG; use a seeded random.Random(seed)")
                elif (
                    tail in ("Random", "default_rng") and not node.args
                    and not node.keywords
                ):
                    flag(node, f"{tail}() with no seed is seeded from the "
                         "OS; pass an explicit seed")
                elif len(chain) >= 2 and chain[-2:] in _WALL_CLOCK:
                    flag(node, f"wall-clock read {'.'.join(chain)}() is "
                         "nondeterministic across runs; derive from the "
                         "replayed stream or suppress with a reason")
                elif tail in _NP_PACKERS and chain[0] in ("np", "numpy"):
                    for arg in node.args:
                        if _is_set_expr(arg) or isinstance(
                            arg, ast.DictComp
                        ):
                            flag(node, f"np.{tail}() packing a set/dict "
                                 "comprehension bakes hash/insertion "
                                 "order into an array; sort the keys "
                                 "first")
                            break
        return findings

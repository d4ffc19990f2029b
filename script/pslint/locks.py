"""Lock-discipline race detector (pass ``locks``).

The concurrency surface declares which lock protects each shared
mutable attribute with a ``# guarded-by: <lockattr>`` comment on the
attribute's initialization (trailing, or on the line directly above):

    self._pending = {}  # guarded-by: _cv

The pass then flags every read or write of a guarded attribute that is
not lexically inside a ``with self.<lock>:`` block for the declared
lock. Escapes, in discipline order:

- ``__init__`` / ``__del__`` bodies are exempt (construction and
  teardown happen-before/after sharing);
- a method whose entire body runs with the lock already held declares
  it with ``# holds-lock: <lockattr>`` on (or directly above) its
  ``def`` line — the convention behind the repo's ``*_locked`` method
  names, made checkable;
- ``# pslint: disable=guarded-access — <reason>`` for the rare
  deliberate lock-free access (single-writer counters and the like).

Lock model (purely syntactic, per class):

- a *lock* is any attribute assigned ``threading.Lock()``, ``RLock()``
  or ``Condition()`` in the class (instance or class-level);
- ``threading.Condition(self._x)`` ALIASES ``_x``: acquiring the
  condition acquires the wrapped lock, so either satisfies a guard on
  the other;
- nested ``def``s drop the held-lock set (they may escape the block
  and run on another thread — a Thread target defined under a lock is
  NOT protected by it); ``lambda``s keep it (the ``Condition.wait_for``
  predicate idiom runs with the lock held).

**Lock-order graph.** Acquiring lock B while holding lock A adds the
edge A→B; edges are also derived one call level deep — a call made
while holding A, to a method of self or of a typed attribute
(``self.x = ClassName(...)`` in ``__init__`` types ``x``), contributes
A→{locks that method acquires directly}. A cycle in the resulting
directed graph is a potential deadlock (rule ``lock-order``); the
repo's invariant is that the graph stays acyclic.
"""

from __future__ import annotations

import ast
from typing import Dict, List, Sequence, Set, Tuple

from .engine import (
    GUARDED_BY_RE,
    HOLDS_LOCK_RE,
    LOCK_FACTORIES,
    ClassModel,
    Finding,
    Rule,
    direct_acquires,
    self_attr,
)

# the concurrency surface: every module with threads or locks on the
# training/system path (doc/STATIC_ANALYSIS.md "Rule catalog")
SCOPE = (
    "parameter_server_tpu/system/executor.py",
    "parameter_server_tpu/system/postoffice.py",
    "parameter_server_tpu/system/heartbeat.py",
    "parameter_server_tpu/system/aux_runtime.py",
    "parameter_server_tpu/system/dashboard.py",
    "parameter_server_tpu/system/recovery.py",
    "parameter_server_tpu/system/monitor.py",
    "parameter_server_tpu/system/faults.py",
    "parameter_server_tpu/telemetry/aggregate.py",
    "parameter_server_tpu/telemetry/alerts.py",
    "parameter_server_tpu/telemetry/blackbox.py",
    "parameter_server_tpu/telemetry/device.py",
    "parameter_server_tpu/telemetry/exposition.py",
    "parameter_server_tpu/telemetry/history.py",
    "parameter_server_tpu/telemetry/learning.py",
    "parameter_server_tpu/utils/concurrent.py",
    "parameter_server_tpu/parallel/partition.py",
    "parameter_server_tpu/parameter/parameter.py",
    "parameter_server_tpu/parameter/kv_vector.py",
    "parameter_server_tpu/parameter/replica.py",
    "parameter_server_tpu/serving/admission.py",
    "parameter_server_tpu/serving/batcher.py",
    "parameter_server_tpu/serving/coalescer.py",
    "parameter_server_tpu/serving/frontend.py",
    "parameter_server_tpu/serving/loadgen.py",
    "parameter_server_tpu/serving/replica.py",
    "parameter_server_tpu/system/autoscale.py",
    "parameter_server_tpu/learner/ingest.py",
    "parameter_server_tpu/learner/workload_pool.py",
    "parameter_server_tpu/learner/wire.py",
    "parameter_server_tpu/learner/consistency.py",
    "parameter_server_tpu/apps/linear/async_sgd.py",
)

# engine-hosted symbol-table pieces, re-exported for existing callers
_ClassModel = ClassModel
_self_attr = self_attr
_direct_acquires = direct_acquires


class LockDisciplineRule(Rule):
    name = "locks"

    def __init__(self, scope: Sequence[str] = SCOPE):
        self.scope = tuple(scope)

    def paths(self, root: str) -> Sequence[str]:
        return self.scope

    def check(self, files, root: str) -> List[Finding]:
        findings: List[Finding] = []
        # EVERY class is modeled and checked, even when two scope files
        # reuse a name — a name-keyed dict would silently drop one
        # class from all checking. Cross-class call resolution uses
        # the by-name index and simply skips ambiguous names
        # (conservative: no edges rather than wrong-class edges).
        project = self.get_project(files)
        all_models: List[_ClassModel] = []
        for rel in files:
            all_models.extend(project.classes(rel))
        models: Dict[str, _ClassModel] = {}
        ambiguous: set = set()
        for m in all_models:
            if m.name in ambiguous:
                continue
            if m.name in models:
                del models[m.name]
                ambiguous.add(m.name)
            else:
                models[m.name] = m

        # validate guard declarations before checking accesses
        for model in all_models:
            for attr, (lock, line) in model.guards.items():
                if model.canonical(lock) not in {
                    model.canonical(l) for l in model.locks
                }:
                    findings.append(
                        Finding(
                            model.sf.rel,
                            line,
                            "unknown-lock",
                            f"{model.name}.{attr} declares guarded-by: "
                            f"{lock}, but {lock} is not a threading.Lock/"
                            "RLock/Condition attribute of the class",
                        )
                    )

        # edge -> (path, line) of the acquisition that created it
        edges: Dict[Tuple[str, str], Tuple[str, int]] = {}

        for model in all_models:
            acquires = {
                name: _direct_acquires(fn, model)
                for name, fn in model.methods.items()
            }
            for mname, fn in model.methods.items():
                if mname in ("__init__", "__del__"):
                    continue
                held0: Set[str] = set()
                order0: List[str] = []
                m = HOLDS_LOCK_RE.search(
                    model.sf.comment_at_or_above(fn.lineno)
                )
                if m is not None:
                    held0 = model.held_closure(m.group(1))
                    # the annotated lock participates in the lock-order
                    # graph exactly like a lexical `with` — a lock
                    # acquired inside a holds-lock method is an edge
                    order0 = [model.canonical(m.group(1))]
                self._visit(
                    fn.body, model, models, held0, order0, edges,
                    acquires, findings,
                )

        findings.extend(self._find_cycles(edges))
        return findings

    # -- access + acquisition walk ------------------------------------

    def _visit(
        self,
        body,
        model: _ClassModel,
        models: Dict[str, _ClassModel],
        held: Set[str],
        held_order: List[str],
        edges,
        acquires,
        findings,
    ) -> None:
        for node in body:
            self._visit_node(
                node, model, models, held, held_order, edges, acquires,
                findings,
            )

    def _visit_node(
        self, node, model, models, held, held_order, edges, acquires,
        findings,
    ) -> None:
        sf = model.sf
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            # nested defs may escape (thread targets, callbacks): they
            # inherit NO held locks — unless annotated holds-lock
            inner: Set[str] = set()
            inner_order: List[str] = []
            m = HOLDS_LOCK_RE.search(sf.comment_at_or_above(node.lineno))
            if m is not None:
                inner = model.held_closure(m.group(1))
                inner_order = [model.canonical(m.group(1))]
            self._visit(
                node.body, model, models, inner, inner_order, edges,
                acquires, findings,
            )
            return
        if isinstance(node, ast.Lambda):
            # wait_for predicates & sort keys run in the calling context
            self._visit_node(
                node.body, model, models, held, held_order, edges,
                acquires, findings,
            )
            return
        if isinstance(node, (ast.With, ast.AsyncWith)):
            gained: List[str] = []
            # acquisition order within one multi-item `with self._a,
            # self._b:` counts too — item k is acquired holding items
            # 0..k-1, so the intra-statement edges must be recorded
            cur_order = list(held_order)
            for item in node.items:
                attr = _self_attr(item.context_expr)
                if attr is not None and attr in model.locks:
                    canon = model.canonical(attr)
                    for h in cur_order:
                        edge = (f"{model.name}.{h}", f"{model.name}.{canon}")
                        if edge[0] != edge[1]:
                            edges.setdefault(edge, (sf.rel, item.context_expr.lineno))
                    gained.append(canon)
                    if canon not in cur_order:
                        cur_order.append(canon)
                else:
                    self._visit_node(
                        item.context_expr, model, models, held,
                        held_order, edges, acquires, findings,
                    )
                if item.optional_vars is not None:
                    self._visit_node(
                        item.optional_vars, model, models, held,
                        held_order, edges, acquires, findings,
                    )
            new_held = set(held)
            new_order = list(held_order)
            for g in gained:
                for name in model.held_closure(g):
                    if name not in new_held:
                        new_held.add(name)
                if g not in new_order:
                    new_order.append(g)
            self._visit(
                node.body, model, models, new_held, new_order, edges,
                acquires, findings,
            )
            return
        if isinstance(node, ast.Call):
            self._resolve_call_edges(
                node, model, models, held_order, edges, acquires
            )
        if isinstance(node, ast.Attribute):
            attr = _self_attr(node)
            if attr is not None and attr in model.guards:
                lock = model.guards[attr][0]
                if not (model.held_closure(lock) & held):
                    kind = (
                        "written" if isinstance(node.ctx, (ast.Store, ast.Del))
                        else "read"
                    )
                    findings.append(
                        Finding(
                            sf.rel,
                            node.lineno,
                            "guarded-access",
                            f"{model.name}.{attr} (guarded-by: {lock}) "
                            f"{kind} without holding self.{lock}",
                        )
                    )
        for child in ast.iter_child_nodes(node):
            self._visit_node(
                child, model, models, held, held_order, edges, acquires,
                findings,
            )

    def _resolve_call_edges(
        self, node: ast.Call, model, models, held_order, edges, acquires
    ) -> None:
        """One level of call resolution under held locks: self.m(),
        self.attr.m() for typed attrs, and ClassName() constructors."""
        if not held_order:
            return
        fn = node.func
        target: Set[str] = set()
        callee_file = model.sf.rel
        if isinstance(fn, ast.Attribute):
            owner = fn.value
            if isinstance(owner, ast.Name) and owner.id in ("self", "cls"):
                target = acquires.get(fn.attr, set())
                target = {f"{model.name}.{t}" for t in target}
            else:
                attr = _self_attr(owner)
                if attr is not None and attr in model.attr_types:
                    other = models.get(model.attr_types[attr])
                    if other is not None:
                        ofn = other.methods.get(fn.attr)
                        if ofn is not None:
                            callee_file = other.sf.rel
                            target = {
                                f"{other.name}.{t}"
                                for t in _direct_acquires(ofn, other)
                            }
        elif isinstance(fn, ast.Name) and fn.id in models:
            other = models[fn.id]
            ofn = other.methods.get("__init__")
            if ofn is not None:
                callee_file = other.sf.rel
                target = {
                    f"{other.name}.{t}"
                    for t in _direct_acquires(ofn, other)
                }
        if not target:
            return
        for h in held_order:
            src = f"{model.name}.{h}"
            for dst in target:
                if src != dst:
                    edges.setdefault(
                        (src, dst), (model.sf.rel, node.lineno)
                    )
        # note: callee_file kept for possible richer reporting
        del callee_file

    # -- cycle detection ----------------------------------------------

    def _find_cycles(self, edges) -> List[Finding]:
        graph: Dict[str, List[str]] = {}
        for (a, b) in edges:
            graph.setdefault(a, []).append(b)
        findings: List[Finding] = []
        seen_cycles: Set[frozenset] = set()
        WHITE, GRAY, BLACK = 0, 1, 2
        color: Dict[str, int] = {}

        def dfs(n: str, stack: List[str]):
            color[n] = GRAY
            stack.append(n)
            for m in graph.get(n, ()):
                if color.get(m, WHITE) == WHITE:
                    dfs(m, stack)
                elif color.get(m) == GRAY:
                    cycle = stack[stack.index(m):] + [m]
                    key = frozenset(cycle)
                    if key not in seen_cycles:
                        seen_cycles.add(key)
                        path, line = edges[(stack[-1], m)]
                        findings.append(
                            Finding(
                                path,
                                line,
                                "lock-order",
                                "potential deadlock: lock-order cycle "
                                + " -> ".join(cycle),
                            )
                        )
            stack.pop()
            color[n] = BLACK

        for n in sorted(graph):
            if color.get(n, WHITE) == WHITE:
                dfs(n, [])
        return findings

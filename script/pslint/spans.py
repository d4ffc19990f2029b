"""Span-discipline pass (rule ``span-with``, pass ``spans``).

``telemetry.spans.span(...)`` is a context manager: called bare, it
builds a generator that never runs — the block is silently untimed and,
worse, a *partially* entered span (``ctx = span(...)`` stored for
later) can die with its owner and leave an open-ended track that
corrupts the timeline (the PR-1 span-leak hazard; the dynamic half of
the fix is the pool's ``abandoned`` terminator in
utils/concurrent.OrderedStagePool). This pass enforces the static half:
every ``span(...)`` / ``<alias>.span(...)`` call must be the context
expression of a ``with`` statement (or an ``ExitStack.enter_context``
argument, which gives it an owner with the same exit guarantee).

Matched call shapes — chosen so regex-``Match.span()`` and other
unrelated ``.span`` attributes never trip the rule:

- bare ``span(...)`` (the ``from telemetry import span`` idiom);
- ``<mod>.span(...)`` where ``<mod>`` is a name containing "span" or
  "tracer" (``spans.span``, ``telemetry_spans.span``, ``tracer.span``).

Genuinely deferred spans declare their owner:

    # pslint: disable=span-with — <who enters/closes it and why>
"""

from __future__ import annotations

import ast
from typing import Dict, List, Optional, Sequence

from .engine import Finding, Rule, SourceFile, walk_package

_ALIAS_HINTS = ("span", "tracer")


def _is_span_call(node: ast.Call) -> bool:
    fn = node.func
    if isinstance(fn, ast.Name):
        return fn.id == "span"
    if isinstance(fn, ast.Attribute) and fn.attr == "span":
        base = fn.value
        if isinstance(base, ast.Name):
            return any(h in base.id.lower() for h in _ALIAS_HINTS)
    return False


class SpanDisciplineRule(Rule):
    name = "spans"
    version = "2"
    per_file = True  # no cross-file state: content-hash cacheable

    def __init__(self, scope: Optional[Sequence[str]] = None):
        self.scope = scope

    def paths(self, root: str) -> Sequence[str]:
        if self.scope is not None:
            return self.scope
        return list(walk_package(root))

    def check(self, files: Dict[str, SourceFile], root: str) -> List[Finding]:
        findings: List[Finding] = []
        for sf in files.values():
            findings.extend(self._check_file(sf))
        return findings

    def _check_file(self, sf: SourceFile) -> List[Finding]:
        findings: List[Finding] = []
        # the defining module itself (telemetry/spans.py) declares the
        # contextmanager; its internals are not call sites
        if sf.rel.endswith("telemetry/spans.py"):
            return findings
        parents = sf.parents()  # engine-shared parent chain
        for node in ast.walk(sf.tree):
            if not (isinstance(node, ast.Call) and _is_span_call(node)):
                continue
            parent = parents.get(node)
            # `with span(...):` / `with a, span(...) as s:` — the call
            # is a withitem's context expression
            if isinstance(parent, ast.withitem) and parent.context_expr is node:
                continue
            # `stack.enter_context(span(...))` — the stack owns exit
            if (
                isinstance(parent, ast.Call)
                and isinstance(parent.func, ast.Attribute)
                and parent.func.attr == "enter_context"
                and node in parent.args
            ):
                continue
            findings.append(
                Finding(
                    sf.rel,
                    node.lineno,
                    "span-with",
                    "tracer span(...) used outside a `with` statement — "
                    "the block is untimed and the span can leak "
                    "open-ended into the timeline; write `with "
                    "span(...):` (or enter_context), or disable with "
                    "a reason",
                )
            )
        return findings

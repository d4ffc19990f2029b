#!/usr/bin/env python
"""metrics-lint: validate the telemetry metric catalog (fast, CPU-only).

Instantiates every instrument family from
``parameter_server_tpu.telemetry.instruments`` against a fresh registry
and fails on:

- duplicate metric names, or one name re-declared with a different
  kind/labels/buckets across families (the registry raises);
- non-snake_case metric or label names (the registry raises);
- counters missing the ``_total`` suffix / histograms missing a
  ``_seconds`` or ``_bytes`` unit suffix (naming-convention drift);
- a render_text() exposition that does not parse as Prometheus text;
- **orphan registrations**: any ``ps_*`` instrument registered by name
  anywhere in the package outside the canonical catalog.
  The exposition endpoint serves whatever the registry holds, so a
  call-site-invented name would ship undocumented, un-linted series —
  every ``ps_*`` name must exist in ``instruments.py`` (satellite of
  the cluster-metrics-plane PR; static AST scan, no imports).

Runs as the ``metrics`` pass of the pslint static-analysis suite
(``make pslint``, doc/STATIC_ANALYSIS.md) — the logic lives here as the
single source of truth and pslint wraps it. ``make metrics-lint``
aliases the single-pass pslint run; this file also stays directly
runnable and is exercised as a tier-1 test in tests/test_telemetry.py
so catalog drift fails CI before it ships.
"""

from __future__ import annotations

import ast
import os
import re
import sys

EXPOSITION_LINE = re.compile(
    r"^[a-z_][a-z0-9_]*(\{[a-zA-Z_][a-zA-Z0-9_]*=\"[^\"]*\""
    r"(,[a-zA-Z_][a-zA-Z0-9_]*=\"[^\"]*\")*\})? [^ ]+$"
)

#: registry methods whose first positional arg is a metric name
_REGISTER_METHODS = frozenset({
    "counter", "gauge", "histogram",
    "ensure_counter", "ensure_gauge", "ensure_histogram",
})

#: the one module allowed to declare ps_* names (the canonical catalog)
_CATALOG_REL = os.path.join("telemetry", "instruments.py")


def orphan_problems(root: str, catalog_names: "set[str]") -> list:
    """Static AST sweep: every ``reg.counter("ps_...")``-shaped call in
    the package must name a metric the canonical catalog
    declares. Catches runtime-registered orphans that would be served
    by the exposition endpoint but documented and linted nowhere."""
    problems = []
    pkg = os.path.join(root, "parameter_server_tpu")
    paths = []
    for dirpath, dirnames, filenames in os.walk(pkg):
        dirnames[:] = [d for d in dirnames if d != "__pycache__"]
        paths.extend(
            os.path.join(dirpath, f) for f in filenames if f.endswith(".py")
        )
    for path in sorted(paths):
        rel = os.path.relpath(path, root)
        if rel.endswith(_CATALOG_REL):
            continue
        try:
            with open(path, encoding="utf-8") as f:
                tree = ast.parse(f.read(), filename=rel)
        except SyntaxError as e:
            problems.append(f"{rel}: unparseable for orphan scan: {e}")
            continue
        for node in ast.walk(tree):
            if not (
                isinstance(node, ast.Call)
                and isinstance(node.func, ast.Attribute)
                and node.func.attr in _REGISTER_METHODS
                and node.args
                and isinstance(node.args[0], ast.Constant)
                and isinstance(node.args[0].value, str)
            ):
                continue
            name = node.args[0].value
            if name.startswith("ps_") and name not in catalog_names:
                problems.append(
                    f"{rel}:{node.lineno} registers ps_* metric "
                    f"{name!r} outside the instruments.py catalog "
                    "(orphan: served but undocumented/unlinted)"
                )
    return problems


def lint(root: "str | None" = None) -> list:
    """Returns a list of problem strings (empty = clean).

    ``root`` selects which checkout's ``parameter_server_tpu`` to
    validate (pslint passes its ``--root`` through); default is this
    script's own repo. Caveat: Python's module cache wins — in a
    process that already imported the package (pytest), the cached
    import is what gets validated regardless of ``root``; the pslint
    CLI runs fresh, where ``root`` is honored. The orphan scan is
    static (AST over ``root``) and honors ``root`` either way."""
    if root is None:
        root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    sys.path.insert(0, root)
    os.environ.setdefault("JAX_PLATFORMS", "cpu")

    from parameter_server_tpu.telemetry.instruments import install_all
    from parameter_server_tpu.telemetry.registry import MetricsRegistry

    problems = []
    reg = MetricsRegistry()
    try:
        instruments = install_all(reg)  # raises on dup / bad names
        install_all(reg)  # second pass must be idempotent
    except Exception as e:
        return [f"catalog failed to install: {type(e).__name__}: {e}"]

    for name, inst in sorted(instruments.items()):
        if inst.kind == "counter" and not name.endswith("_total"):
            problems.append(f"counter {name!r} should end in '_total'")
        # histograms carry their unit in the name; ministeps is the
        # learning plane's staleness unit (a logical count, like the
        # Prometheus convention's base units — never an alias for time)
        if inst.kind == "histogram" and not name.endswith(
            ("_seconds", "_bytes", "_ministeps")
        ):
            problems.append(
                f"histogram {name!r} should carry a unit suffix "
                "('_seconds', '_bytes' or '_ministeps')"
            )

    # exposition must parse even with every series present: record one
    # sample per instrument (labeled instruments get a probe label set)
    for inst in instruments.values():
        target = (
            inst.labels(**{ln: "probe" for ln in inst.labelnames})
            if inst.labelnames
            else inst
        )
        if inst.kind == "histogram":
            target.observe(0.001)
        elif inst.kind == "gauge":
            target.set(1.0)
        else:
            target.inc()
    for line in reg.render_text().splitlines():
        if not line or line.startswith("#"):
            continue
        if not EXPOSITION_LINE.match(line):
            problems.append(f"unparseable exposition line: {line!r}")

    problems.extend(orphan_problems(root, set(instruments)))
    return problems


def main() -> int:
    problems = lint()
    if problems:
        for p in problems:
            print(f"metrics-lint: {p}", file=sys.stderr)
        print(f"metrics-lint: FAILED ({len(problems)} problems)", file=sys.stderr)
        return 1
    print("metrics-lint: OK")
    return 0


if __name__ == "__main__":
    sys.exit(main())

"""Cross-artifact consistency pass (rule ``cross-artifact``).

Names that cross an artifact boundary — a fault-point string in code, a
metric name in an alert JSON, a benchmark key in the Makefile — have no
compiler: when one side drifts, the other becomes a silent no-op (an
alert that never fires, a drill that never injects). This pass pins
each reference side to its truth side and fails the lint on drift.
Finding sub-rules (suppression keys):

- ``fault-point`` — the point name at every ``faults.inject`` /
  ``faults.arm`` / ``faults.scoped`` call site must be a member of
  ``faults.POINTS`` (the runtime rejects unknown names too, but only
  when that code path actually runs — a drill nobody exercises drifts
  silently);
- ``alert-metric`` — every ``"metric"`` / ``"den"`` name in
  ``configs/alerts/*.json`` must exist in the instruments catalog
  (``telemetry/instruments.py`` string constants): a rule over a
  renamed metric evaluates forever against an absent series;
- ``bench-wiring`` — every benchmark key the Makefile invokes
  (``python -m parameter_server_tpu.benchmarks <key>``) must exist in
  the ``@benchmark("<key>")`` REGISTRY; every REGISTRY key must be
  referenced somewhere (Makefile or ``tests/test_benchmarks.py``) so
  registered benchmarks cannot become unreachable dead code;
- ``metadata-section`` — every name in ``script/bench_diff.py``'s
  ``METADATA_SECTIONS`` must appear as a string constant in the bench
  record producers (``bench.py`` / ``benchmarks/components.py``): a
  section nobody writes is stale exclusion config.

Direction matters: each check points from the REFERENCE (call site,
config, Makefile) at its TRUTH (POINTS, catalog, REGISTRY). The
reverse direction — e.g. a POINTS entry no drill arms — is reported
only for REGISTRY keys, where an unreferenced entry is definitionally
dead; POINTS / catalog entries may be armed by tests or operators at
runtime.

Findings in non-Python artifacts (JSON, Makefile) cannot carry inline
suppressions; fix the drift or adjust the truth side instead.
"""

from __future__ import annotations

import ast
import glob
import json
import os
import re
from typing import Dict, List, Sequence, Set

from .engine import Finding, Rule, SourceFile, callee_chain, walk_package

_FAULT_FNS = {"inject", "arm", "scoped"}
_BENCH_INVOKE_RE = re.compile(
    r"-m\s+parameter_server_tpu\.benchmarks\s+([A-Za-z_][A-Za-z0-9_]*)"
)

_FAULTS_MOD = "parameter_server_tpu/system/faults.py"
_INSTRUMENTS_MOD = "parameter_server_tpu/telemetry/instruments.py"
_COMPONENTS_MOD = "parameter_server_tpu/benchmarks/components.py"
_BENCH_MOD = "bench.py"  # the record assembler lives at the repo root
_BENCH_DIFF = "script/bench_diff.py"


def _string_constants(tree: ast.AST) -> Set[str]:
    return {
        n.value
        for n in ast.walk(tree)
        if isinstance(n, ast.Constant) and isinstance(n.value, str)
    }


def _find_line(text: str, needle: str, start: int = 0) -> int:
    """1-based line of the first occurrence of ``needle`` at/after
    character ``start`` (1 if absent — a finding beats no finding)."""
    idx = text.find(needle, start)
    if idx < 0:
        return 1
    return text.count("\n", 0, idx) + 1


class CrossArtifactRule(Rule):
    name = "cross-artifact"
    version = "1"

    def paths(self, root: str) -> Sequence[str]:
        return tuple(walk_package(root)) + (_BENCH_MOD, _BENCH_DIFF)

    def check(self, files: Dict[str, SourceFile], root: str) -> List[Finding]:
        findings: List[Finding] = []
        findings.extend(self._check_fault_points(files))
        findings.extend(self._check_alert_metrics(files, root))
        findings.extend(self._check_bench_wiring(files, root))
        findings.extend(self._check_metadata_sections(files))
        return findings

    # -- fault points --------------------------------------------------

    def _points(self, files) -> Set[str]:
        sf = files.get(_FAULTS_MOD)
        if sf is None:
            return set()
        for node in sf.tree.body:
            if isinstance(node, ast.Assign) and any(
                isinstance(t, ast.Name) and t.id == "POINTS"
                for t in node.targets
            ):
                return {
                    el.value
                    for el in getattr(node.value, "elts", ())
                    if isinstance(el, ast.Constant)
                    and isinstance(el.value, str)
                }
        return set()

    def _check_fault_points(self, files) -> List[Finding]:
        points = self._points(files)
        if not points:
            return []  # fixture trees without faults.py: nothing to pin
        findings: List[Finding] = []
        for sf in files.values():
            if sf.rel == _FAULTS_MOD:
                continue  # the catalog's own docstring examples
            for node in ast.walk(sf.tree):
                if not isinstance(node, ast.Call):
                    continue
                chain = callee_chain(node)
                # qualified calls only: blackbox.arm() is a different arm
                if len(chain) < 2 or chain[-2] != "faults":
                    continue
                if chain[-1] not in _FAULT_FNS or not node.args:
                    continue
                arg = node.args[0]
                if not (
                    isinstance(arg, ast.Constant)
                    and isinstance(arg.value, str)
                ):
                    continue
                if arg.value not in points:
                    findings.append(
                        Finding(
                            sf.rel,
                            node.lineno,
                            "fault-point",
                            f"faults.{chain[-1]}('{arg.value}') names a "
                            "point not in faults.POINTS — the injection "
                            "is a silent no-op; add the point or fix "
                            "the name",
                        )
                    )
        return findings

    # -- alert metrics -------------------------------------------------

    def _catalog(self, files) -> Set[str]:
        sf = files.get(_INSTRUMENTS_MOD)
        if sf is None:
            return set()
        return {
            s for s in _string_constants(sf.tree) if s.startswith("ps_")
        }

    def _check_alert_metrics(self, files, root: str) -> List[Finding]:
        catalog = self._catalog(files)
        if not catalog:
            return []
        findings: List[Finding] = []
        for path in sorted(
            glob.glob(os.path.join(root, "configs", "alerts", "*.json"))
        ):
            rel = os.path.relpath(path, root).replace(os.sep, "/")
            try:
                with open(path, "r", encoding="utf-8") as f:
                    text = f.read()
                data = json.loads(text)
            except (OSError, ValueError) as e:
                findings.append(
                    Finding(rel, 1, "alert-metric", f"unreadable: {e}")
                )
                continue
            names: List[str] = []

            def collect(obj):
                if isinstance(obj, dict):
                    for key in ("metric", "den"):
                        v = obj.get(key)
                        if isinstance(v, str):
                            names.append(v)
                        elif isinstance(v, list):
                            names.extend(x for x in v if isinstance(x, str))
                    for v in obj.values():
                        collect(v)
                elif isinstance(obj, list):
                    for v in obj:
                        collect(v)

            collect(data)
            for name in names:
                if name not in catalog:
                    findings.append(
                        Finding(
                            rel,
                            _find_line(text, f'"{name}"'),
                            "alert-metric",
                            f"alert rule references metric '{name}' which "
                            "is not in the instruments catalog "
                            f"({_INSTRUMENTS_MOD}) — the rule will never "
                            "see a sample",
                        )
                    )
        return findings

    # -- benchmark wiring ----------------------------------------------

    def _registry(self, files) -> Dict[str, int]:
        """@benchmark("key") -> decorator line."""
        sf = files.get(_COMPONENTS_MOD)
        out: Dict[str, int] = {}
        if sf is None:
            return out
        for node in ast.walk(sf.tree):
            if not isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                continue
            for dec in node.decorator_list:
                if (
                    isinstance(dec, ast.Call)
                    and callee_chain(dec)[-1] == "benchmark"
                    and dec.args
                    and isinstance(dec.args[0], ast.Constant)
                    and isinstance(dec.args[0].value, str)
                ):
                    out[dec.args[0].value] = dec.lineno
        return out

    def _check_bench_wiring(self, files, root: str) -> List[Finding]:
        registry = self._registry(files)
        if not registry:
            return []
        findings: List[Finding] = []
        mk_path = os.path.join(root, "Makefile")
        try:
            with open(mk_path, "r", encoding="utf-8") as f:
                mk_text = f.read()
        except OSError:
            mk_text = ""
        for i, line in enumerate(mk_text.splitlines(), start=1):
            for m in _BENCH_INVOKE_RE.finditer(line):
                key = m.group(1)
                if key not in registry:
                    findings.append(
                        Finding(
                            "Makefile",
                            i,
                            "bench-wiring",
                            f"Makefile invokes benchmark '{key}' which is "
                            "not a registered @benchmark key in "
                            f"{_COMPONENTS_MOD}",
                        )
                    )
        # reverse direction: a REGISTRY key nothing references is dead
        ref_texts = [mk_text]
        for rel in ("tests/test_benchmarks.py",):
            try:
                with open(
                    os.path.join(root, rel), "r", encoding="utf-8"
                ) as f:
                    ref_texts.append(f.read())
            except OSError:
                pass
        for key, line in sorted(registry.items()):
            if not any(f'"{key}"' in t or f"'{key}'" in t or
                       re.search(rf"\b{re.escape(key)}\b", t)
                       for t in ref_texts):
                findings.append(
                    Finding(
                        _COMPONENTS_MOD,
                        line,
                        "bench-wiring",
                        f"benchmark '{key}' is registered but referenced "
                        "by no Makefile target or "
                        "tests/test_benchmarks.py — unreachable "
                        "registration",
                    )
                )
        return findings

    # -- metadata sections ---------------------------------------------

    def _check_metadata_sections(self, files) -> List[Finding]:
        diff_sf = files.get(_BENCH_DIFF)
        if diff_sf is None:
            return []
        sections: Dict[str, int] = {}
        for node in ast.walk(diff_sf.tree):
            if isinstance(node, ast.Assign) and any(
                isinstance(t, ast.Name) and t.id == "METADATA_SECTIONS"
                for t in node.targets
            ):
                for c in ast.walk(node.value):
                    if isinstance(c, ast.Constant) and isinstance(
                        c.value, str
                    ):
                        sections[c.value] = c.lineno
        if not sections:
            return []
        producers: Set[str] = set()
        for rel in (_BENCH_MOD, _COMPONENTS_MOD):
            sf = files.get(rel)
            if sf is not None:
                producers |= _string_constants(sf.tree)
        if not producers:
            return []
        findings: List[Finding] = []
        for name, line in sorted(sections.items()):
            if name not in producers:
                findings.append(
                    Finding(
                        _BENCH_DIFF,
                        line,
                        "metadata-section",
                        f"METADATA_SECTIONS entry '{name}' is written by "
                        f"no bench record producer ({_BENCH_MOD} / "
                        f"{_COMPONENTS_MOD}) — stale exclusion config",
                    )
                )
        return findings

"""Cross-artifact consistency pass (rule ``cross-artifact``).

Names that cross an artifact boundary (a fault-point string in code, a
metric name in an alert JSON) have no compiler: when one side drifts,
the other becomes a silent no-op (an alert that never fires, a drill
that never injects). This pass pins each reference side to its truth
side and fails the lint on drift. Finding sub-rules (suppression keys):

- ``fault-point``: the point name at every ``faults.inject`` /
  ``faults.arm`` / ``faults.scoped`` call site must be a member of
  ``faults.POINTS`` (the runtime rejects unknown names too, but only
  when that code path actually runs: a drill nobody exercises drifts
  silently);
- ``alert-metric``: every ``"metric"`` / ``"den"`` name in
  ``configs/alerts/*.json`` must exist in the instruments catalog
  (``telemetry/instruments.py`` string constants): a rule over a
  renamed metric evaluates forever against an absent series.

Direction matters: each check points from the REFERENCE (call site,
config) at its TRUTH (POINTS, catalog). The reverse direction (a POINTS
entry no drill arms) is not reported: POINTS and catalog entries may be
armed by tests or operators at runtime.

Findings in non-Python artifacts (JSON) cannot carry inline
suppressions; fix the drift or adjust the truth side instead.
"""

from __future__ import annotations

import ast
import glob
import json
import os
from typing import Dict, List, Sequence, Set

from .engine import Finding, Rule, SourceFile, callee_chain, walk_package

_FAULT_FNS = {"inject", "arm", "scoped"}

_FAULTS_MOD = "parameter_server_tpu/system/faults.py"
_INSTRUMENTS_MOD = "parameter_server_tpu/telemetry/instruments.py"


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
        return tuple(walk_package(root))

    def check(self, files: Dict[str, SourceFile], root: str) -> List[Finding]:
        findings: List[Finding] = []
        findings.extend(self._check_fault_points(files))
        findings.extend(self._check_alert_metrics(files, root))
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

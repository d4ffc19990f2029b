#!/usr/bin/env python
"""Generate doc/API_REFERENCE.md from package/module docstrings and public
signatures (counterpart of the reference's doc/Doxyfile + gendoc.sh —
their Doxygen run over src/; ours is dependency-free introspection)."""

from __future__ import annotations

import importlib
import inspect
import os
import pkgutil
import re
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def first_line(doc: str | None) -> str:
    line = (doc or "").strip().splitlines()[0] if doc else ""
    # dataclass-generated docstrings embed object reprs with process
    # memory addresses; scrub them or regeneration churns the file
    return re.sub(r" at 0x[0-9a-f]+", "", line)


def describe_module(name: str, lines: list) -> None:
    try:
        mod = importlib.import_module(name)
    except Exception as e:  # noqa: BLE001 — optional deps may be stubbed
        lines.append(f"### `{name}`\n\n*(import skipped: {e})*\n")
        return
    lines.append(f"### `{name}`\n")
    doc = inspect.getdoc(mod)
    if doc:
        lines.append(doc.strip().split("\n\n")[0] + "\n")
    members = []
    for attr, obj in sorted(vars(mod).items()):
        if attr.startswith("_"):
            continue
        # jit-wrapped functions (PjitFunction) are not inspect.isfunction
        # but carry the original via __wrapped__ — unwrap so public jitted
        # APIs (pipeline_apply, moe_ffn, ...) appear in the reference
        target = inspect.unwrap(obj) if callable(obj) else obj
        if getattr(target, "__module__", None) != name:
            continue
        if inspect.isclass(target) or inspect.isfunction(target):
            obj = target
            try:
                sig = str(inspect.signature(obj))
                # default-value reprs embed process-specific memory
                # addresses ('<function f at 0x7f...>'): normalize so
                # regeneration never churns the file
                sig = re.sub(r" at 0x[0-9a-f]+", "", sig)
            except (ValueError, TypeError):
                sig = "(...)"
            kind = "class" if inspect.isclass(obj) else "def"
            members.append(f"- `{kind} {attr}{sig}` — {first_line(obj.__doc__)}")
    if members:
        lines.extend(members)
        lines.append("")


def main() -> int:
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    import parameter_server_tpu as pkg

    lines = [
        "# API reference",
        "",
        "Generated by `doc/gendoc.py` (do not edit by hand; regenerate "
        "with `doc/gendoc.sh`). One section per module: the module "
        "docstring's lead paragraph — which cites the reference file it "
        "is the counterpart of — then public classes/functions.",
        "",
    ]
    names = [pkg.__name__] + sorted(
        m.name
        for m in pkgutil.walk_packages(pkg.__path__, prefix=pkg.__name__ + ".")
    )
    for name in names:
        describe_module(name, lines)
    out = os.path.join(ROOT, "doc", "API_REFERENCE.md")
    with open(out, "w") as f:
        f.write("\n".join(lines) + "\n")
    print(f"wrote {out} ({len(names)} modules)")
    return 0


if __name__ == "__main__":
    sys.exit(main())

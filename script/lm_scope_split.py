#!/usr/bin/env python3
"""Which phase of the step a scope's device time falls in, from the capture
a traced run of an LM cell leaves behind:

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds 20 --trace 1
    python3 script/lm_scope_split.py <cell> lm_kda_scan [--trace FILE]

Op self times in ms a step, of the ops whose scope path holds the scope:
``forward`` where the path holds no ``transpose(``; of the rest
``recomputation`` where it holds ``rematted_computation`` (a jax.checkpoint
runs its forward again), else ``backward``. The scope's total, and its ops by
category, are the benchmark's own ``lm_hybrid_scan`` line: this is only what
that line cannot tell apart (PERF.md, PR 34).
"""

from __future__ import annotations

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def split(tr, steps: float, scope: str) -> dict:
    out = {"forward": 0.0, "recomputation": 0.0, "backward": 0.0}
    for ops in tr.ops.values():
        for o in ops:
            if scope in o.scope:
                out[
                    "forward" if "transpose(" not in o.scope
                    else "recomputation" if "rematted_computation" in o.scope
                    else "backward"
                ] += 1e3 * o.self_s / steps
    return out


def main(argv=None) -> int:
    sys.path.insert(0, ROOT)
    from chipbench import trace
    from chipbench.readers import lm_common

    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("cell")
    ap.add_argument("scope")
    ap.add_argument("--trace", help="a *.trace.json.gz (default: the "
                    "newest under chipbench/cache/trace/<cell>)")
    args = ap.parse_args(argv)
    path = args.trace or trace.newest_trace_file(
        os.path.join(ROOT, "chipbench", "cache", "trace", args.cell)
    )
    tr = trace.load(path)
    step = lm_common.step_seconds_and_count(tr)
    if step is None:
        print(f"{path}: no two whole steps in the capture", file=sys.stderr)
        return 1
    print(json.dumps({
        "file": path, "scope": args.scope, "steps": step[1],
        "ms_a_step": split(tr, step[1], args.scope),
    }))
    return 0


if __name__ == "__main__":
    sys.exit(main())

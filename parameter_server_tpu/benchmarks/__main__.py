"""CLI: python -m parameter_server_tpu.benchmarks [name ...] [--smoke]"""

from __future__ import annotations

import argparse
import sys

from . import REGISTRY
from . import components  # noqa: F401 — populates REGISTRY


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument(
        "names",
        nargs="*",
        help=f"benchmarks to run (default all): {', '.join(sorted(REGISTRY))}",
    )
    ap.add_argument("--smoke", action="store_true", help="tiny quick run")
    args = ap.parse_args(argv)
    names = args.names or sorted(REGISTRY)
    for name in names:
        if name not in REGISTRY:
            ap.error(f"unknown benchmark {name!r}; have {sorted(REGISTRY)}")
        REGISTRY[name](args.smoke)
    return 0


if __name__ == "__main__":
    sys.exit(main())

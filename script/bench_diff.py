#!/usr/bin/env python
"""bench_diff: regression sentinel over the BENCH_r*.json trajectory.

Compares a new bench record against the prior records' trajectory and
exits nonzero when a watched throughput metric lands out of band — the
automated version of the per-round VERDICT eyeball, so a perf PR that
silently costs 30% of e2e throughput fails `make bench-diff` instead of
shipping.

Noise discipline (the ROADMAP bench invariant): every quoted number in
a record is already the MEDIAN of back-to-back paired reps/windows, and
this tool compares the new value against the MEDIAN of the prior valid
records — never best-of, never a single A/B. The tolerance band is
derived from the trajectory's own observed spread (how far priors sit
from their median), floored at ``--band-floor`` (default 20%: this
host's CPU capacity flaps seconds-scale) and capped at ``--band-cap``
(a trajectory that noisy cannot alibi arbitrary regressions).

Record handling: accepts both raw bench records and the round driver's
wrapper shape (``{"n", "cmd", "rc", "tail", "parsed"}`` — the committed
BENCH_r*.json files). Failure records (``error`` set, or no watched
metric > 0) are skipped: an unreachable-accelerator round is an outage,
not a baseline.

    python script/bench_diff.py                 # repo BENCH_r*.json:
                                                # newest valid vs priors
    python script/bench_diff.py --new NEW.json --records A.json B.json
    make bench-diff

Exit codes: 0 in band (or no baseline yet) / 1 regression / 2 usage.
One JSON report line per watched metric plus a summary line, so CI logs
stay machine-parseable.
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import re
import statistics
import sys
from typing import Dict, List, Optional, Tuple

#: watched throughput metrics, in report order (only those present in
#: both the new record and >=1 prior are compared)
WATCHED = (
    ("value", "device-only examples/sec (headline)"),
    ("e2e_median_window", "e2e examples/sec, median window (synthetic)"),
    ("e2e_stream", "e2e examples/sec (--real stream)"),
)

#: record sections that are drill/A-B METADATA, not throughput metrics
#: the sentinel may band: ``recovery`` carries MTTR/degraded counts
#: whose host-dependent wall times would false-flag every round, and
#: the embedded A/B sections quote their own paired medians with their
#: own disclosure. A WATCHED key must never point into one of these —
#: enforced at import so a future metric addition cannot silently band
#: drill metadata.
METADATA_SECTIONS = frozenset(
    {
        "recovery",
        "serve",
        "wire",
        "host_ingest",
        "kv_dataplane",
        "ftrl_sparse",
        # continuous-batching decode A/B: quotes its own paired-rep
        # medians (batched vs sequential tokens/s under churn) with the
        # on-chip target stated in-record — self-disclosing A/B, not a
        # series the sentinel may band
        "decode_batching",
        "attribution",
        "telemetry",
        # the --expose-port self-scrape summary (node list, series-line
        # count, alerts firing at teardown) — run metadata, not a
        # throughput the sentinel may band
        "expose",
        # the device truth plane (per-jit cost analysis, recompile /
        # donation-fallback counts, HBM high-water, roofline
        # cross-checks) — capture-HARDWARE facts: fracs of peak move
        # with the chip the record was taken on, not with the code,
        # so banding them would false-flag every capture-host change
        "device",
        # which wire the e2e stream rode (config + per-encoding
        # bytes/example + pinned lane statics + fallback counts — both
        # the synthetic and the --real records carry it since the
        # stream-once wire flip): disclosure metadata, not a
        # throughput the sentinel may band
        "e2e_wire",
        "e2e_upload_cache",
        # flight-recorder evidence (telemetry/blackbox.py): the
        # steady-state overhead A/B quotes its own paired medians with
        # its own disclosure, the drill's auto-captured bundle summary
        # carries host-dependent counts — banding either would
        # false-flag every round
        "blackbox",
        # the learning truth plane (telemetry/learning.py): realized
        # staleness, key-heat shard shares, loss/grad-norm convergence
        # trajectories, the divergence drill — LEARNING evidence, not
        # throughput; banding a loss trajectory as perf would flag
        # every data/seed change as a regression
        "learning",
        # the history plane (telemetry/history.py): the fold-hook
        # overhead A/B quotes its own paired medians, the store
        # snapshot is retention config, and live_drift is the run
        # judging ITSELF (tail vs its own baseline) — banding any of
        # it cross-run would double-count the e2e metric it rides on
        "history",
        # mesh shape disclosure (parallel/mesh.py auto-shaping): which
        # (data, server) factorization was chosen and that 0 devices
        # idle — capture-host facts, asserted in the record itself,
        # not a throughput the sentinel may band
        "mesh",
        # the live-rebalance drill (parallel/partition.py
        # RebalanceController + KVVector.migrate): imbalance
        # before/after, rows moved, migration wall seconds, serve
        # continuity counts, the bit-parity verdict — drill evidence
        # with host-dependent wall times; banding it would false-flag
        # every round
        "rebalance",
        # self-driving consistency (adaptive τ + KKT filter): quotes
        # its own paired-rep A/B medians (τ arms, filter off/on key
        # and byte reductions) plus the divergence-drill episode —
        # self-disclosing, never banded by the sentinel
        "consistency",
    }
)
assert not ({k for k, _ in WATCHED} & METADATA_SECTIONS), (
    "WATCHED must not band metadata sections"
)


def load_record(path: str) -> Optional[dict]:
    """The bench record inside ``path`` (unwrapping the round driver's
    {parsed: ...} shape), or None if unreadable."""
    try:
        with open(path, "r", encoding="utf-8") as f:
            data = json.load(f)
    except (OSError, ValueError):
        return None
    if not isinstance(data, dict):
        return None
    if "parsed" in data and isinstance(data.get("parsed"), (dict, type(None))):
        data = data["parsed"]
    return data if isinstance(data, dict) else None


#: fields every record measured under the flushed-window protocol
#: carries (the MEASUREMENT NOTE in bench.py: a window that does not end
#: in a value fetch measures the dispatch rate, so pre-protocol numbers
#: are not comparable and must not seed the baseline)
PROTOCOL_FIELDS = (
    "steps_per_launch_best",
    "e2e_median_window",
    "e2e_stream",
    "breakdown_bound",
    "attribution",
)


def is_valid(rec: Optional[dict]) -> bool:
    """A usable, protocol-comparable measurement: no failure marker,
    >=1 watched metric > 0, and measured under the flushed-window
    protocol (schema gate: any PROTOCOL_FIELDS present)."""
    if not rec or rec.get("error"):
        return False
    if not any(k in rec for k in PROTOCOL_FIELDS):
        return False
    return any(
        isinstance(rec.get(k), (int, float)) and rec.get(k) > 0
        for k, _ in WATCHED
    )


def _round_key(path: str) -> Tuple[int, str]:
    m = re.search(r"BENCH_r(\d+)", os.path.basename(path))
    return (int(m.group(1)) if m else 1 << 30, path)


def discover_trajectory(root: str) -> List[str]:
    return sorted(glob.glob(os.path.join(root, "BENCH_r*.json")), key=_round_key)


def band_for(priors: List[float], floor: float, cap: float) -> float:
    """Tolerance band from the trajectory's own spread: the maximum
    relative deviation of any prior from the prior median, widened 1.5x
    (one-sided safety), floored and capped."""
    med = statistics.median(priors)
    if med <= 0:
        return cap
    max_dev = max(abs(v - med) / med for v in priors)
    return max(floor, min(cap, 1.5 * max_dev))


def diff(
    new: dict,
    priors: List[dict],
    band_floor: float = 0.20,
    band_cap: float = 0.45,
) -> Tuple[List[dict], bool]:
    """Per-metric comparison rows + overall regression flag."""
    rows: List[dict] = []
    regressed = False
    for key, desc in WATCHED:
        if key in METADATA_SECTIONS:  # second line of defense behind
            continue  # the import-time assert: never band drill metadata
        new_v = new.get(key)
        if not isinstance(new_v, (int, float)) or new_v <= 0:
            continue
        prior_vs = [
            r[key]
            for r in priors
            if isinstance(r.get(key), (int, float)) and r[key] > 0
        ]
        row: Dict = {"metric": key, "description": desc, "new": new_v}
        if not prior_vs:
            row["status"] = "no-baseline"
            rows.append(row)
            continue
        baseline = statistics.median(prior_vs)
        band = band_for(prior_vs, band_floor, band_cap)
        ratio = new_v / baseline
        row.update(
            {
                "baseline_median": round(baseline, 1),
                "priors": len(prior_vs),
                "ratio": round(ratio, 3),
                "band": round(band, 3),
            }
        )
        if ratio < 1.0 - band:
            row["status"] = "REGRESSION"
            regressed = True
        else:
            row["status"] = "ok" if ratio <= 1.0 + band else "improved"
        rows.append(row)
    return rows, regressed


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(prog="bench_diff", description=__doc__)
    ap.add_argument(
        "--new",
        help="record to judge (default: newest VALID record of --records)",
    )
    ap.add_argument(
        "--records",
        nargs="*",
        help="trajectory record files, oldest first (default: the repo's "
        "BENCH_r*.json sorted by round)",
    )
    ap.add_argument("--band-floor", type=float, default=0.20)
    ap.add_argument("--band-cap", type=float, default=0.45)
    args = ap.parse_args(argv)

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    paths = (
        list(args.records)
        if args.records
        else discover_trajectory(root)
    )
    trajectory = [
        (p, load_record(p)) for p in paths
    ]
    valid = [(p, r) for p, r in trajectory if is_valid(r)]

    if args.new:
        new_rec = load_record(args.new)
        if not is_valid(new_rec):
            print(
                f"bench_diff: --new {args.new} is not a valid measurement "
                "record",
                file=sys.stderr,
            )
            return 2
        new_name = args.new
        # the record under judgment must not seed its own baseline: a
        # committed-but-regressed BENCH_r*.json judged via --new would
        # otherwise pull the median toward itself and widen the band
        new_real = os.path.realpath(args.new)
        priors = [r for p, r in valid if os.path.realpath(p) != new_real]
    else:
        if not valid:
            print(
                json.dumps(
                    {
                        "summary": "bench_diff",
                        "status": "no-valid-records",
                        "records_seen": len(trajectory),
                    }
                )
            )
            return 0
        new_name, new_rec = valid[-1]
        priors = [r for _, r in valid[:-1]]

    rows, regressed = diff(
        new_rec, priors, band_floor=args.band_floor, band_cap=args.band_cap
    )
    for row in rows:
        print(json.dumps(row))
    print(
        json.dumps(
            {
                "summary": "bench_diff",
                "new": os.path.basename(new_name),
                "priors": len(priors),
                "status": "REGRESSION" if regressed else "ok",
            }
        )
    )
    return 1 if regressed else 0


if __name__ == "__main__":
    sys.exit(main())

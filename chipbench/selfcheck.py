"""``python -m chipbench.selfcheck``: seconds, on the CPU, no jax.

Reduces the recorded TPU trace in ``fixtures/`` and compares with the
known answer, runs every trace reader on it, and feeds the last-line
check one good and several bad objects per mode. Exits 1 on the first
difference.
"""

from __future__ import annotations

import copy
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, ROOT)

from chipbench import arith, hostspans, lastline, trace  # noqa: E402
from chipbench.readers import (  # noqa: E402
    trace_collective_exposed,
    trace_idle_share,
    trace_module_ms,
    trace_scope_share,
)

# trace.FIXTURE's known answer: the same totals as
# parameter_server_tpu/utils/profiling.summarize_trace gives for it
KNOWN = {
    "devices": 1, "op_events": 3494, "self_ms": 4981.068,
    "ps_update_ms": 4281.841, "ps_compute_ms": 618.700,
    "update_share": 85.96, "busy_s": 4.981068, "window_s": 4.998525,
    "step_device_ms": 156.563,
}


# hostspans.FIXTURE's idle gaps as ``breakdown`` names them under the
# idle shares' buckets: the three long ones lie between programs while the
# trainer waits for the uploader, the short ones inside a program
KNOWN_GAPS = [
    ("ingest", 0.15989466), ("ingest", 0.06984482), ("ingest", 0.054001905),
    ("in_program", 2.3e-06), ("in_program", 2.253e-06),
]


def near(name, got, want, tol):
    if abs(got - want) > tol:
        raise SystemExit(f"selfcheck: {name} is {got!r}, expected {want!r}")
    print(f"ok  {name} = {got:.6g}")


def check_trace() -> None:
    tr = trace.load(trace.FIXTURE)
    ops = [o for v in tr.ops.values() for o in v]
    near("device tracks", len(tr.ops), KNOWN["devices"], 0)
    near("op events", len(ops), KNOWN["op_events"], 0)
    near("op self time ms", 1e3 * tr.self_total_s(), KNOWN["self_ms"], 1e-3)
    for scope in ("ps_update", "ps_compute"):
        ms = 1e3 * sum(o.self_s for o in ops if scope in o.scope)
        near(f"{scope} ms", ms, KNOWN[f"{scope}_ms"], 1e-3)
    near("busy_s", tr.busy_s(), KNOWN["busy_s"], 1e-6)
    near("window_s", tr.window_s, KNOWN["window_s"], 1e-6)
    ctx = {"trace": tr, "ministeps_per_launch": 8}
    near("update_share", trace_scope_share.read(ctx, {"scope": "ps_update"}),
         KNOWN["update_share"], 0.01)
    near("step_device_ms", trace_module_ms.read(ctx, {}),
         KNOWN["step_device_ms"], 1e-3)
    near("device_idle_share", trace_idle_share.read(ctx, {}),
         100 * (1 - KNOWN["busy_s"] / KNOWN["window_s"]), 1e-3)
    if trace_scope_share.read(ctx, {"scope": "ps_no_such_scope"}) is not None:
        raise SystemExit("selfcheck: a scope that is not there read as a number")
    spec = {"kinds": ["all-reduce", "all-gather"], "containers": ["while"]}
    if trace_collective_exposed.read(ctx, spec) is not None:
        raise SystemExit("selfcheck: one chip's trace read as having collectives")
    print("ok  absent scope and absent collectives read as nothing")
    top = trace.breakdown(hostspans.load(trace.FIXTURE))["device_ops"][0]
    if top[0] != "ps_update/scatter fusion.46":
        raise SystemExit(f"selfcheck: top op is {top}")
    print(f"ok  top op {top[0]} {top[1]:.4f} s")
    near("union of overlapping intervals",
         trace.union_s([(0, 2), (1, 3), (5, 6)]), 4.0, 0)
    near("sweep bytes 2^29 f32", arith.sweep_bytes(1 << 29, "float32"),
         20 * (1 << 29), 0)
    try:
        arith.peak("TPU v9", "hbm_bytes_per_s")
    except KeyError:
        print("ok  an unlisted device_kind raises")
    else:
        raise SystemExit("selfcheck: an unlisted device_kind has a peak")


def check_idle_gaps() -> None:
    with open(os.path.join(
        HERE, "metrics", "idle_waiting_ingest_share.json"
    )) as f:
        buckets = json.load(f)["buckets"]
    capture = hostspans.load(hostspans.FIXTURE)
    gaps = trace.breakdown(capture, buckets)["idle_gaps"]
    if len(gaps) != 10:
        raise SystemExit(f"selfcheck: {len(gaps)} idle gaps, expected 10")
    for i, (name, seconds) in enumerate(KNOWN_GAPS):
        if gaps[i][0] != name:
            raise SystemExit(f"selfcheck: idle gap {i} is {gaps[i]}")
        near(f"idle gap {i} ({name}) s", gaps[i][1], seconds, 1e-9)
    bare = trace.breakdown(capture)["idle_gaps"]
    if {g[0] for g in bare} != {"in_program", "unattributed"} or [
        g[1] for g in bare
    ] != [g[1] for g in gaps]:
        raise SystemExit(f"selfcheck: gaps without buckets are {bare}")
    print("ok  without buckets the same gaps read in_program or unattributed")


def check_last_line() -> None:
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cell = bench["workloads"][0]["name"]
    for traced in (False, True):
        device = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1,
                  "memory_peak_bytes": 15450472448}
        if traced:
            device.update(busy_s=4.98, window_s=5.0)
        good = lastline.build(
            bench, cell, traced, correct=True, attempted=16, failed=0,
            values={n: 1.5 for n in lastline.expected(bench, cell, traced)},
            device=device,
            breakdown={"device_ops": [["a b", 1.0]], "idle_gaps": []},
            checks={"losses_finite": {"ok": True, "value": 0, "limit": 0}},
        )
        if lastline.faults(good, bench, cell, traced):
            raise SystemExit(
                f"selfcheck: good line refused (traced={traced}): "
                f"{lastline.faults(good, bench, cell, traced)}"
            )

        refused = []

        def bad(label, change):
            refused.append(label)
            obj = copy.deepcopy(good)
            change(obj)
            if not lastline.faults(obj, bench, cell, traced):
                raise SystemExit(
                    f"selfcheck: traced={traced}: {label} was accepted"
                )

        last = list(good["metrics"])[-1]
        bad("a missing metric", lambda o: o["metrics"].pop(last))
        bad("a metric without unit", lambda o: o["metrics"][last].pop("unit"))
        bad("a NaN value",
            lambda o: o["metrics"][last].update(value=float("nan")))
        bad("a metric of another cell",
            lambda o: o["metrics"].update(x={"value": 1, "unit": "s"}))
        bad("an extra top-level key", lambda o: o.update(seconds=20))
        bad("a missing device key", lambda o: o["device"].pop("count"))
        bad("a check without its limit",
            lambda o: o["checks"]["losses_finite"].pop("limit"))
        bad("a key after checks", lambda o: o.update(breakdown=o.pop(
            "breakdown", {"device_ops": [], "idle_gaps": []})))
        if traced:
            bad("busy_s 0", lambda o: o["device"].update(busy_s=0.0))
            bad("busy_s > window_s", lambda o: o["device"].update(busy_s=5.1))
            bad("no window_s", lambda o: o["device"].pop("window_s"))
            bad("11 breakdown entries", lambda o: o["breakdown"].update(
                idle_gaps=[["unattributed", 0.1]] * 11))
        else:
            bad("busy_s in an untraced run",
                lambda o: o["device"].update(busy_s=1.0))
        print(f"ok  last line, traced={traced}: 1 good, "
              f"{len(refused)} bad refused")


if __name__ == "__main__":
    check_trace()
    check_idle_gaps()
    check_last_line()
    print("selfcheck: passed")

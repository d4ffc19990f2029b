#!/usr/bin/env python3
"""Price the big-table row write-back alone, on the chip.

    python script/price_row_writeback.py            # on a TPU
    JAX_PLATFORMS=cpu python script/price_row_writeback.py --rehearsal

At ``criteo_bigtable.text``'s own shapes (2^30 slots, ``z`` f32 and
``sqrt_n`` bf16, each donated and updated in place; 639,488 row ids,
sorted and unique with a padding tail) it times, per call: the
plain scatter-set (every dropped entry at one repeated index, nothing
declared), the same with what host prep guarantees declared, the gather of the same
rows, a scatter-add of the same rows, and the overwrite written as an
integer scatter-add of bit differences. One JSON line per variant and
table, host clock around ``--calls`` calls that end in
``block_until_ready``; ``temp_bytes`` says whether the table was copied.
PERF.md section 6 holds the table this printed for PR 27.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--rehearsal", action="store_true")
    ap.add_argument("--calls", type=int, default=8)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp
    import numpy as np

    from parameter_server_tpu.ops.rows import write_index

    dev = jax.devices()[0]
    if not args.rehearsal and dev.platform != "tpu":
        print("price_row_writeback: no TPU; --rehearsal walks it on the CPU",
              file=sys.stderr)
        return 2
    p = 1 << 16 if args.rehearsal else 1 << 30
    u = 1024 if args.rehearsal else 639488
    live = u - (16 if args.rehearsal else 512 + 7001)  # a padding tail

    rng = np.random.default_rng(args.seed)
    rows = np.unique(rng.integers(0, p, 2 * u, dtype=np.int64))
    rows = np.sort(rng.permutation(rows)[:live]).astype(np.int32)
    rel = jnp.asarray(np.concatenate([rows, np.full(u - live, p - 1, np.int32)]))
    ok = jnp.asarray(np.arange(u) < live)
    # the plain index: every dropped entry at the one index
    # one-past-the-end
    idx_rep = jnp.where(ok, rel.astype(jnp.uint32), jnp.uint32(p))
    # strictly increasing, dropped entries included
    idx_inc = write_index(rel, ok, p)

    hints = {
        "none": {},
        "unique": dict(unique_indices=True),
        "sorted": dict(indices_are_sorted=True),
        "sorted_unique": dict(indices_are_sorted=True, unique_indices=True),
    }
    uint = {jnp.dtype(jnp.float32): jnp.uint32,
            jnp.dtype(jnp.bfloat16): jnp.uint16}

    def bits(a):
        return jax.lax.bitcast_convert_type(a, uint[a.dtype])

    def variants(dtype):
        out = {}
        for name, kw in hints.items():
            idx = idx_rep if name == "none" else idx_inc
            out[f"set.{name}"] = (
                lambda t, v, o, idx=idx, kw=kw:
                t.at[idx].set(v, mode="drop", **kw)
            )
            out[f"add.{name}"] = (
                lambda t, v, o, idx=idx, kw=kw:
                t.at[idx].add(v, mode="drop", **kw)
            )
        # the overwrite as an integer add of bit differences: exact by
        # wraparound where the indices are unique
        for name in ("none", "sorted_unique"):
            idx = idx_rep if name == "none" else idx_inc
            out[f"bitadd.{name}"] = (
                lambda t, v, o, idx=idx, kw=hints[name]:
                jax.lax.bitcast_convert_type(
                    bits(t).at[idx].add(bits(v) - bits(o), mode="drop", **kw),
                    t.dtype,
                )
            )
        return out

    def emit(rec):
        rec.update(device=dev.device_kind, slots=p, rows=u, live_rows=live)
        if args.rehearsal:
            rec["rehearsal"] = True
        print(json.dumps(rec), flush=True)

    def timed(fn, *a):
        t0 = time.perf_counter()
        out = fn(*a)
        jax.block_until_ready(out)
        return out, (time.perf_counter() - t0) * 1e3

    for dtype in (jnp.float32, jnp.bfloat16):
        name_t = "z.f32" if dtype == jnp.float32 else "sqrt_n.bf16"
        table = jnp.zeros((p,), dtype) + jnp.asarray(0.5, dtype)

        for gname, kw in (("none", {}), ("sorted_unique",
                                         hints["sorted_unique"])):
            g = jax.jit(lambda t, kw=kw: t.at[rel].get(**kw))
            g(table).block_until_ready()
            ms = [timed(g, table)[1] for _ in range(args.calls)]
            emit({"op": f"gather.{gname}", "table": name_t,
                  "ms_median": float(np.median(ms)), "ms_min": min(ms)})
        for k, (vname, fn) in enumerate(variants(jnp.dtype(dtype)).items()):
            step = jax.jit(fn, donate_argnums=(0,))
            # fresh values per variant, so a write that did not happen shows
            new = jnp.asarray(rng.normal(size=u) + k, dtype)
            old = table[rel]
            last = bits(table[p - 1])
            mem = step.lower(table, new, old).compile().memory_analysis()
            table, _ = timed(step, table, new, old)  # warm, and checked
            rec = {"op": vname, "table": name_t,
                   "temp_bytes": int(mem.temp_size_in_bytes),
                   "alias_bytes": int(mem.alias_size_in_bytes)}
            if not vname.startswith("add."):
                rec["rows_hold_new_bits"] = bool(jnp.array_equal(
                    bits(table[rel])[:live], bits(new)[:live]
                ))
                rec["padding_dropped"] = bool(
                    rows[-1] == p - 1 or bits(table[p - 1]) == last
                )
            ms = []
            for _ in range(args.calls):
                table, dt = timed(step, table, new, old)
                ms.append(dt)
            rec.update(ms_median=float(np.median(ms)), ms_min=min(ms))
            emit(rec)
        del table, new, old
    return 0


if __name__ == "__main__":
    sys.exit(main())

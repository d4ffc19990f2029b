"""Component benchmarks (see package docstring for the reference map)."""

from __future__ import annotations

import numpy as np

from . import HBM_PEAK_GB_S, benchmark, report, timeit


def _mesh():
    from ..system.postoffice import Postoffice

    Postoffice.reset()
    return Postoffice.instance().start().mesh


@benchmark("kv_vector")
def kv_vector_perf(smoke: bool = False) -> None:
    """Push/pull throughput of the sharded dense table
    (ref src/test/kv_vector_perf_ps.cc).

    Three paths are A/B'd at the kernel level on the SAME shapes:
    the seed's copying push (fresh [P, k] output per call), the donated
    in-place push, and the fused push→pull single-dispatch round trip —
    the zero-copy data plane's two wins, quoted with the structural
    bytes each donated push stops moving."""
    import jax

    from ..ops import kv_ops
    from ..parameter.kv_vector import KVVector

    mesh = _mesh()
    n_keys = 1 << (12 if smoke else 18)
    k = 4
    kv = KVVector(mesh=mesh, k=k, num_slots=2 * n_keys, hashed=True)
    keys = np.random.default_rng(0).integers(0, 1 << 40, n_keys).astype(np.int64)
    vals = np.ones((n_keys, k), np.float32)

    def push():
        kv.wait(kv.push(kv.request(channel=0), keys=keys, values=vals))

    def pull():
        jax.block_until_ready(kv.wait_pull(kv.pull(kv.request(channel=0), keys=keys)))

    def push_pull_fused():
        jax.block_until_ready(
            kv.wait_pull(kv.push_pull(kv.request(channel=0), keys=keys, values=vals))
        )

    def push_then_pull():
        kv.wait(kv.push(kv.request(channel=0), keys=keys, values=vals))
        jax.block_until_ready(kv.wait_pull(kv.pull(kv.request(channel=0), keys=keys)))

    n = 3 if smoke else 10
    sec = timeit(push, n)
    report("kv_vector_push_keys_per_sec", n_keys / sec, "keys/sec")
    report("kv_vector_push_mb_per_sec", vals.nbytes / sec / 1e6, "MB/s")
    sec = timeit(pull, n)
    report("kv_vector_pull_keys_per_sec", n_keys / sec, "keys/sec")

    # fused vs sequenced round trip (same store-level machinery both ways)
    sec = timeit(push_pull_fused, n)
    report("kv_vector_push_pull_fused_rt_per_sec", 1.0 / sec, "rt/sec")
    sec = timeit(push_then_pull, n)
    report("kv_vector_push_then_pull_rt_per_sec", 1.0 / sec, "rt/sec")

    # kernel-level donate/copy A/B: same jitted scatter-add, only the
    # aliasing differs — the delta IS the [P, k] table copy
    slots = jax.block_until_ready(kv.slots(0, keys))
    vjnp = jax.block_until_ready(jax.device_put(vals))
    table_copy = jax.block_until_ready(kv.table(0, copy=True))
    tbl_box = [kv.table(0, copy=True)]

    def push_nodonate():
        jax.block_until_ready(
            kv_ops.push(table_copy, slots, vjnp, mesh=mesh, batch_sharded=False)
        )

    def push_donated():
        tbl_box[0] = kv_ops.push_donated(
            tbl_box[0], slots, vjnp, mesh=mesh, batch_sharded=False
        )
        jax.block_until_ready(tbl_box[0])

    sec_nd = timeit(push_nodonate, n)
    report("kv_vector_push_nodonate_keys_per_sec", n_keys / sec_nd, "keys/sec")
    sec_d = timeit(push_donated, n)
    report("kv_vector_push_donated_keys_per_sec", n_keys / sec_d, "keys/sec")
    report(
        "kv_vector_push_copy_bytes_avoided_per_push",
        float(table_copy.nbytes),
        "bytes",
    )


@benchmark("kv_map")
def kv_map_perf(smoke: bool = False) -> None:
    """Entry-update throughput (ref src/test/kv_map_perf_ps.cc): vectorized
    FTRL entries over the sharded struct-of-arrays state."""
    from ..parameter.kv_map import AddEntry, KVMap

    mesh = _mesh()
    n_keys = 1 << (12 if smoke else 18)
    m = KVMap(AddEntry(), mesh=mesh, k=1, num_slots=2 * n_keys, hashed=True)
    keys = np.random.default_rng(0).integers(0, 1 << 40, n_keys).astype(np.int64)
    vals = np.ones((n_keys, 1), np.float32)

    def push():
        m.wait(m.push(m.request(), keys, vals))

    sec = timeit(push, 3 if smoke else 10)
    report("kv_map_entry_updates_per_sec", n_keys / sec, "entries/sec")


@benchmark("kv_layer")
def kv_layer_perf(smoke: bool = False) -> None:
    """Dense-layer push/pull throughput (ref src/test/kv_layer_perf_ps.cc).

    A/B: donated in-place updater (the default) vs the seed's copying
    updater (``donate=False``), plus the fused push_pull round trip."""
    import jax

    from ..parameter.kv_layer import KVLayer, SGDUpdater

    mesh = _mesh()
    shape = (256, 64) if smoke else (4096, 512)
    layer = KVLayer(partition_thr=1024, updater=SGDUpdater(lr=0.1), mesh=mesh)
    layer.init_layer("w", shape)
    grad = np.ones(shape, np.float32)
    nbytes = grad.nbytes

    def push():
        layer.wait(layer.push(layer.request(), "w", grad))

    def pull():
        jax.block_until_ready(layer.wait_pull(layer.pull(layer.request(), "w")))

    def push_pull_fused():
        jax.block_until_ready(
            layer.wait_pull(layer.push_pull(layer.request(), "w", grad))
        )

    n = 3 if smoke else 10
    report("kv_layer_push_mb_per_sec", nbytes / timeit(push, n) / 1e6, "MB/s")
    report("kv_layer_pull_mb_per_sec", nbytes / timeit(pull, n) / 1e6, "MB/s")
    sec = timeit(push_pull_fused, n)
    report("kv_layer_push_pull_fused_rt_per_sec", 1.0 / sec, "rt/sec")
    report("kv_layer_push_copy_bytes_avoided_per_push", float(nbytes), "bytes")

    # copying-mode A/B (the seed path): same updater, donation off
    nodon = KVLayer(
        partition_thr=1024, updater=SGDUpdater(lr=0.1), mesh=mesh,
        donate=False,
    )
    nodon.init_layer("w", shape)

    def push_nodonate():
        nodon.wait(nodon.push(nodon.request(), "w", grad))

    report(
        "kv_layer_push_nodonate_mb_per_sec",
        nbytes / timeit(push_nodonate, n) / 1e6,
        "MB/s",
    )

    def push_then_pull():
        layer.wait(layer.push(layer.request(), "w", grad))
        jax.block_until_ready(layer.wait_pull(layer.pull(layer.request(), "w")))

    sec = timeit(push_then_pull, n)
    report("kv_layer_push_then_pull_rt_per_sec", 1.0 / sec, "rt/sec")


@benchmark("network")
def network_perf(smoke: bool = False) -> None:
    """Wire latency/bandwidth by message size (ref
    src/test/network_perf_ps.cc): host→device transfer (the PCIe hop)
    and the in-mesh psum collective."""
    import jax
    import jax.numpy as jnp
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    from ..parallel.mesh import DATA_AXIS

    mesh = _mesh()
    sizes_kb = [8, 64] if smoke else [8, 64, 1024, 8192]
    for kb in sizes_kb:
        x = np.ones(kb * 1024 // 4, np.float32)

        def h2d():
            jax.block_until_ready(jax.device_put(x))

        sec = timeit(h2d, 3 if smoke else 10)
        report(f"network_h2d_{kb}kb_ms", sec * 1e3, "ms")
        report(f"network_h2d_{kb}kb_mb_per_sec", x.nbytes / sec / 1e6, "MB/s")

    x = np.ones((64 if smoke else 1024) * 256, np.float32)
    xd = jax.device_put(x)
    psum = jax.jit(
        shard_map(
            lambda v: jax.lax.psum(v, DATA_AXIS),
            mesh=mesh,
            in_specs=P(),
            out_specs=P(),
            check_vma=False,
        )
    )
    jax.block_until_ready(psum(xd))

    def coll():
        jax.block_until_ready(psum(xd))

    sec = timeit(coll, 5 if smoke else 20)
    report("network_psum_ms", sec * 1e3, "ms")


@benchmark("sparse_matrix")
def sparse_matrix_perf(smoke: bool = False) -> None:
    """Host sparse-matrix pipeline (ref src/test/sparse_matrix_perf.cc):
    key uniquification (countUniqIndex), localization, and the device
    SpMV."""
    import jax
    import jax.numpy as jnp

    from ..utils.localizer import Localizer, count_uniq_keys
    from ..utils.sparse import random_sparse

    _mesh()
    n = 1 << (10 if smoke else 14)
    nnz = 64
    batch = random_sparse(n, 1 << 24, nnz, seed=0)

    def uniq():
        count_uniq_keys(batch)

    sec = timeit(uniq, 3 if smoke else 10)
    report("sparse_uniq_keys_per_sec", batch.nnz / sec, "keys/sec")

    loc = Localizer()
    keys, _ = loc.count_uniq_index(batch)

    def localize():
        loc.remap_index(keys)

    sec = timeit(localize, 3 if smoke else 10)
    report("sparse_localize_keys_per_sec", batch.nnz / sec, "keys/sec")

    local = loc.remap_index(keys)
    w = np.random.default_rng(0).normal(size=len(keys)).astype(np.float32)
    rows = local.row_ids().astype(np.int32)
    ucols = local.indices.astype(np.int32)
    vals = (
        np.ones(local.nnz, np.float32)
        if local.binary
        else local.values.astype(np.float32)
    )
    args = [jax.device_put(a) for a in (vals, ucols, rows, w)]
    # Xw = segment-sum over the localized COO — the XLA formulation the
    # fused app steps use (a Pallas spmv was probed and rejected: Mosaic
    # has no 1-D table gather; see SURVEY §3)
    fn = jax.jit(
        lambda v, c, r, w: jax.ops.segment_sum(v * w[c], r, num_segments=n)
    )
    jax.block_until_ready(fn(*args))

    def mv():
        jax.block_until_ready(fn(*args))

    sec = timeit(mv, 5 if smoke else 20)
    report("sparse_spmv_mnnz_per_sec", batch.nnz / sec / 1e6, "Mnnz/s")


@benchmark("attention")
def attention_perf(smoke: bool = False) -> None:
    """Flash-kernel vs XLA dense attention on one device (the per-chunk
    compute that ring/ulysses sequence parallelism schedules). Flushes by
    fetching a scalar (see bench.py's measurement note). CHAIN attention
    calls run inside one jitted lax.scan (each feeding its output back
    as the next query, so nothing dead-codes), so a timed rep is
    kernel time and not per-launch dispatch."""
    import jax

    from ..ops import use_pallas as on_tpu_backend
    from ..ops.flash_attention import flash_attention

    bh = 4
    s = 512 if smoke else 4096
    d = 64
    chain = 2 if smoke else 16
    rng = np.random.default_rng(0)
    q, k, v = (
        jax.device_put(rng.normal(size=(bh, s, d)).astype(np.float32))
        for _ in range(3)
    )

    def make_run(use_pallas, dtype=np.float32):
        # jit the whole chain so the XLA path is the FUSED program the
        # model paths embed, not an eager per-op chain
        @jax.jit
        def fn(q0, kk, vv):
            def body(qc, _):
                o = flash_attention(
                    qc, kk, vv, causal=True, use_pallas=use_pallas,
                )
                return o.astype(qc.dtype), None

            qf, _ = jax.lax.scan(body, q0, None, length=chain)
            return qf

        args = [x.astype(dtype) for x in (q, k, v)]

        def run():
            np.asarray(fn(*args)[0, 0, 0], np.float32)  # true flush

        return run

    # 2 matmuls, causal ~half but count full (the convention MFU tables use)
    flops = 4.0 * bh * s * s * d * chain
    n = 2 if smoke else 10
    sec = timeit(make_run(False), n)
    report("attention_xla_gflops", flops / sec / 1e9, "GFLOP/s")
    if on_tpu_backend():  # Mosaic on TPU only (interpret is not a perf path)
        sec = timeit(make_run(True), n)
        report("attention_flash_gflops", flops / sec / 1e9, "GFLOP/s")
        # bf16 inputs (fp32 accumulation in-kernel): the dtype the LM
        # decoder actually feeds, and the MXU's native input width
        sec = timeit(make_run(True, np.dtype("bfloat16")), n)
        report("attention_flash_bf16_gflops", flops / sec / 1e9, "GFLOP/s")
        sec = timeit(make_run(False, np.dtype("bfloat16")), n)
        report("attention_xla_bf16_gflops", flops / sec / 1e9, "GFLOP/s")


@benchmark("step_phases")
def step_phases_perf(smoke: bool = False) -> None:
    """Each phase of the fused async-SGD bits step as its OWN jitted
    program at the headline bench shapes (rows 16384 x 39 lanes), at
    BOTH headline table sizes — 2^22 slots (synthetic bench) and 2^26
    (--real criteo) — the decomposition of bench.py's ~26-32 ms device
    step.

    The r3 sweep data shows the device-only rate is step-bound, not
    dispatch-bound (T=8->32 moved it 1%), while the step's HBM traffic
    justifies <1 ms: one of these phases is eating ~95% of the time,
    and this bench names it without a profiler trace (insurance for
    --profile). Phase sum !=
    fused-step time exactly (XLA fuses across phase boundaries), but a
    300x structural outlier dwarfs that error bar.
    """
    rows, lanes = (1024, 8) if smoke else (16384, 39)
    # both headline table sizes: 2^22 (synthetic bench) and 2^26
    # (--real criteo) — the structural loss may be size-dependent
    # (gather working set 16 MB vs 256 MB spans VMEM-resident to
    # HBM-bound regimes)
    for num_slots in ([1 << 14] if smoke else [1 << 22, 1 << 26]):
        _step_phases_at(rows, lanes, num_slots, smoke)


def _step_phases_at(
    rows: int, lanes: int, num_slots: int, smoke: bool
) -> None:
    import jax
    import jax.numpy as jnp

    from ..apps.linear.learning_rate import LearningRate
    from ..apps.linear.penalty import ElasticNet
    from ..apps.linear.updaters import FTRLUpdater
    from ..utils.bitpack import (
        pack_bits,
        slot_bits,
        stream_to_words,
        unpack_bits,
        unpack_sign_bits,
    )

    tag = f"_s{num_slots.bit_length() - 1}"
    bits = slot_bits(num_slots)
    rng = np.random.default_rng(0)

    slots_host = rng.integers(0, num_slots, rows * lanes, np.int64)
    # the SAME <u4 word layout the production decode consumes
    # (async_sgd.py unpack path): a raw byte stream would make the
    # timed gathers byte-granular and the decode verdict wrong
    words = jax.device_put(
        stream_to_words(pack_bits(slots_host, bits), rows * lanes, bits)
    )
    y_bits = jax.device_put(
        np.packbits(rng.integers(0, 2, rows).astype(np.uint8))
    )
    updater = FTRLUpdater(
        LearningRate(type_=LearningRate.DECAY, alpha=0.1, beta=1.0),
        ElasticNet(1.0, 0.0),
    )
    state = {
        "z": jax.device_put(
            rng.normal(size=num_slots).astype(np.float32)
        ),
        "sqrt_n": jax.device_put(
            np.abs(rng.normal(size=num_slots)).astype(np.float32)
        ),
    }
    rel = jax.device_put(slots_host.astype(np.int32))
    gr = jax.device_put(rng.normal(size=rows).astype(np.float32))
    grad = jax.device_put(rng.normal(size=num_slots).astype(np.float32))

    def timed_phase(name, fn, *args):
        jf = jax.jit(fn)
        jax.block_until_ready(jf(*args))  # compile untimed
        # tight per-phase budget: 12 phases x 2 sizes (timeit
        # docstring)
        n = 3 if smoke else 10
        sec = timeit(
            lambda: jax.block_until_ready(jf(*args)), n, budget_s=25.0
        )
        report(f"step_phase_{name}{tag}_ms", sec * 1e3, "ms")
        return sec

    total = 0.0
    total += timed_phase(
        "decode",
        lambda w, yb: (
            unpack_bits(w, rows * lanes, bits),
            unpack_sign_bits(yb, rows),
        ),
        words, y_bits,
    )
    total += timed_phase(
        "weights_dense", lambda st: updater.weights(st), state
    )

    # gather timed on a PRECOMPUTED dense weight vector: the dense
    # transform is already its own phase above, and the production
    # updater.weights is reused rather than re-derived
    w_dense = jax.block_until_ready(jax.jit(updater.weights)(state))
    total += timed_phase(
        "gather_sum",
        lambda w, idx: w[idx].reshape(rows, lanes).sum(axis=1),
        w_dense, rel,
    )
    total += timed_phase(
        "scatter_add",
        lambda idx, g: jnp.zeros((num_slots,), jnp.float32)
        .at[idx]
        .add(jnp.broadcast_to(g[:, None], (rows, lanes)).reshape(-1)),
        rel, gr,
    )
    # the ftrl phase must time the PRODUCTION configuration: the fused
    # step donates the table and the kernel updates it in place
    # (ops/ftrl.py input_output_aliases), with membership derived from
    # grad's support (touched=None, the unquantized-push contract). A
    # non-donated call would instead time kernel + XLA's defensive
    # whole-table copies — a different program than the one shipped.
    jf_ftrl = jax.jit(
        lambda st, g: updater.apply(st, g, None, seed=np.uint32(1)),
        donate_argnums=(0,),
    )
    st_ftrl = jax.tree.map(jnp.copy, state)
    st_ftrl = jax.block_until_ready(jf_ftrl(st_ftrl, grad))
    _st_box = [st_ftrl]

    def _ftrl_once():
        _st_box[0] = jf_ftrl(_st_box[0], grad)
        jax.block_until_ready(_st_box[0])

    sec = timeit(_ftrl_once, 3 if smoke else 10, budget_s=25.0)
    report(f"step_phase_ftrl_update{tag}_ms", sec * 1e3, "ms")
    total += sec
    report(f"step_phase_sum{tag}_ms", total * 1e3, "ms")
    report(
        f"step_phase_sum{tag}_equiv_examples_per_sec",
        rows / total,
        "examples/sec",
    )


def _write_synth_libsvm(path: str, rows: int, lanes: int, seed: int = 0) -> None:
    """Synthetic libsvm text: ``rows`` examples x ``lanes`` sorted
    uint features, ±1 labels — the criteo-like shape the headline bench
    streams."""
    rng = np.random.default_rng(seed)
    keys = np.sort(rng.integers(0, 1 << 31, (rows, lanes)), axis=1)
    labels = rng.choice((-1, 1), rows)
    with open(path, "w") as f:
        for i in range(rows):
            f.write(
                f"{labels[i]} "
                + " ".join(f"{k}:1" for k in keys[i])
                + "\n"
            )


def host_ingest_ab(
    smoke: bool = False, workers: "int | None" = None
) -> dict:
    """Serial-vs-pipelined host-ingest A/B (HOST side only, no device).

    Both arms ingest the same libsvm file at the headline bench shape
    (16384-row x 39-lane criteo-like batches) through the same
    exact-wire prep (``prep_batch``: unique → inverse-remap → pad).
    The **serial** arm is the seed MinibatchReader critical path:
    line-based parse + prep inline on the caller's thread, batch by
    batch. The **pipelined** arm is the PR's staged ingest plane end to
    end: chunked byte parse (``StreamReader.minibatches_bytes`` — raw
    chunks into the GIL-releasing native parser on a small pool)
    feeding ``learner.ingest.IngestPipeline``'s ordered prep workers —
    the consumer just drains, like a trainer whose thread is free for
    device dispatch. The countmin tail-filter is deliberately absent
    from BOTH arms: it is off in the default config
    (``tail_feature_freq=0``) and, being stateful, would run serially
    on the feeder either way. Arms run strictly alternating and the
    quoted rates aggregate over all reps — this host's effective CPU
    capacity flaps on a seconds timescale (sandboxed kernel), so
    single-shot or best-of numbers are a lottery. Returns the dict
    ``bench.py`` embeds under ``host_ingest``; batch streams are
    bit-identical across arms (tier-1 parity test in
    tests/test_ingest.py)."""
    import os
    import tempfile
    import time as _time

    from ..apps.linear.async_sgd import prep_batch
    from ..data.stream_reader import StreamReader
    from ..learner.ingest import IngestPipeline
    from ..parameter.parameter import KeyDirectory

    # smoke stays criteo-lane-shaped but smaller; going much below this
    # makes per-rep work so short that thread spin-up and capacity
    # flaps swamp the overlap being measured
    rows_per_batch = 8192 if smoke else 16384
    n_batches = 4 if smoke else 6
    lanes = 24 if smoke else 39
    num_shards = 2
    num_slots = 1 << 22
    if workers is None:
        workers = max(2, min(4, os.cpu_count() or 2))
    directory = KeyDirectory(num_slots, hashed=True)
    rows_pad = -(-rows_per_batch // num_shards)
    nnz_pad = rows_pad * lanes

    def prep(b):
        return prep_batch(
            b, directory, num_shards, rows_pad, nnz_pad, nnz_pad, num_slots
        )

    with tempfile.TemporaryDirectory() as td:
        path = f"{td}/ingest_ab.libsvm"
        _write_synth_libsvm(path, rows_per_batch * n_batches, lanes)

        def run_serial() -> float:
            n_ex = 0
            t0 = _time.perf_counter()
            for b in StreamReader([path], "libsvm").minibatches(
                rows_per_batch
            ):
                n_ex += prep(b).num_examples
            sec = _time.perf_counter() - t0
            assert n_ex == rows_per_batch * n_batches, n_ex
            return sec

        def run_pipelined() -> float:
            # 3 parse threads / capacity 8: measured sweet spot on the
            # 2-core host — the deep buffer rides out capacity flaps
            # (a shallow one stalls the prep pool at every hiccup)
            src = StreamReader([path], "libsvm").minibatches_bytes(
                rows_per_batch, chunk_bytes=2 << 20, threads=3
            )
            pipe = IngestPipeline(
                src,
                prep_fn=prep,
                workers=workers,
                capacity=8,
                name="host_ingest_ab",
            ).start()
            n_ex = 0
            t0 = _time.perf_counter()
            for p in pipe:
                n_ex += p.num_examples
            sec = _time.perf_counter() - t0
            assert n_ex == rows_per_batch * n_batches, n_ex
            return sec

        # one shared warm pass heats the file/prep caches, then the
        # arms run in back-to-back (pipelined, serial) pairs: the two
        # members of a pair see the same machine state, so the MEDIAN
        # pair ratio isolates the pipelining effect from capacity
        # flaps, while the quoted per-arm rates aggregate all reps
        run_serial()
        reps = 5
        sers, pips = [], []
        for _ in range(reps):
            pips.append(run_pipelined())
            sers.append(run_serial())
    per_rep = rows_per_batch * n_batches
    n_ex = per_rep * reps
    ratios = sorted(s / p for s, p in zip(sers, pips))
    return {
        "examples": n_ex,
        "minibatch": rows_per_batch,
        "lanes": lanes,
        "workers": workers,
        "reps": reps,
        "serial_examples_per_sec": round(n_ex / sum(sers), 1),
        "pipelined_examples_per_sec": round(n_ex / sum(pips), 1),
        # median of paired ratios (see measurement note above)
        "pipelined_speedup": round(ratios[len(ratios) // 2], 3),
    }


def _criteo_shape_batches(
    rows: int, lanes: int, n_batches: int, valued: bool = False,
    seed: int = 0,
):
    """Synthetic batches following the headline bench's data law
    (data/criteo_synth.write_criteo_chunk): 13 small-vocab integer fields +
    26 power-law (cube-of-uniform) categorical fields, field-salted
    keys, ±1 labels — the distribution the recorded 107.4 B/example
    baseline was measured on. ``valued`` attaches float values (the
    quantized-wire arm; the binary CTR stream has no value bytes)."""
    from ..utils.sparse import SparseBatch

    rng = np.random.default_rng(seed)
    n_int = min(13, lanes)
    n_cat = lanes - n_int
    out = []
    for _ in range(n_batches):
        ints = rng.integers(10, 100, size=(rows, n_int))
        u = rng.random((rows, n_cat))
        cats = (u * u * u * (1 << 24)).astype(np.int64)
        # field-salted keys: distinct key spaces per field, like the
        # criteo parser's (field, token) hash
        keys = np.concatenate(
            [
                (j << 40) | ints[:, j : j + 1] for j in range(n_int)
            ] + [
                ((100 + j) << 40) | cats[:, j : j + 1] for j in range(n_cat)
            ],
            axis=1,
        ).astype(np.int64)
        y = rng.choice((-1.0, 1.0), rows).astype(np.float32)
        vals = (
            (rng.random(rows * lanes) + 0.5).astype(np.float32)
            if valued else None
        )
        out.append(SparseBatch(
            y=y,
            indptr=np.arange(0, rows * lanes + 1, lanes),
            indices=keys.ravel(),
            values=vals,
        ))
    return out


# signature-only wire cost of an upload-cache hit: crc32c (4B) +
# shape/dtype routing metadata — what a repeated array actually costs
# the link (filter/key_caching.py semantics)
_SIG_BYTES = 16


def wire_ab(smoke: bool = False) -> dict:
    """Encoded-vs-raw compact-wire A/B (HOST side only, no device).

    Measures what each wire format ships per example at the headline
    bench shape, on data following the headline generator's law, plus
    the encode cost and the exact-mode parity bit. Arms:

    - ``raw_exact``  — the raw exact (host-dedup) PreppedBatch buffers
    - ``exact``      — learner/wire.encode_exact, lossless default mode
      (decode verified BIT-IDENTICAL here, every batch)
    - ``bits``       — the ELL bits wire (today's e2e default; this is
      the recorded 107.4 B/example raw baseline at 2^22 slots)
    - ``raw_valued``/``int8_valued`` — the valued stream raw vs
      fixed-point (the lossy mode, logloss-gated in tests)

    Multi-pass amortization: CTR training makes ``num_data_pass``
    passes over the shard, and pass ≥2 re-ships only crc32c signatures
    through the upload key cache (learner/wire.UploadCache, exact-
    verified) — ``amortized_bytes_per_example`` quotes the per-pass
    average with the pass count disclosed; the single-pass numbers
    stand alone above it. Encode throughput quotes the MEDIAN of
    back-to-back paired reps (the PR-3 bench discipline: this host's
    CPU capacity flaps on a seconds timescale)."""
    import time as _time

    from ..apps.linear.async_sgd import (
        prep_batch_ell_bits,
        prep_batch_ell_stream,
        prep_batch_shared,
    )
    from ..learner.wire import (
        UploadCache,
        compress_batch,
        decode_exact_host,
        decode_stream_shard,
        derive_stream_statics,
        encode_exact,
        tree_nbytes,
    )
    from ..parameter.parameter import KeyDirectory
    from ..utils.murmur import hash_slots

    rows = 4096 if smoke else 16384
    lanes = 39
    n_batches = 2 if smoke else 4
    passes = 3
    num_shards = 2
    num_slots = 1 << 22
    directory = KeyDirectory(num_slots, hashed=True)
    rows_pad = rows // num_shards
    nnz_pad = rows_pad * lanes
    uniq_pad = -(-min(nnz_pad * num_shards, num_slots) // 1024) * 1024

    batches = _criteo_shape_batches(rows, lanes, n_batches)
    n_ex = rows * n_batches

    def prep(b):
        return prep_batch_shared(
            b, directory, num_shards, rows_pad, nnz_pad, uniq_pad,
            num_slots,
        )

    # -- bytes per example, per encoding (with exact-mode parity) --
    raws = [prep(b) for b in batches]
    encs = [encode_exact(p, num_slots) for p in raws]
    assert all(e is not None for e in encs)
    parity = True
    for p, e in zip(raws, encs):
        dec = decode_exact_host(e, num_slots)
        import dataclasses as _dc

        for f, arr in zip(_dc.fields(type(p)), dec):
            want = np.asarray(getattr(p, f.name))
            parity &= bool(
                want.dtype == np.asarray(arr).dtype
                and np.array_equal(want, np.asarray(arr))
            )
    bits = [
        prep_batch_ell_bits(
            b, directory, num_shards, rows_pad, lanes, num_slots
        )
        for b in batches
    ]
    assert all(x is not None for x in bits)

    # -- the stream-once lane-dictionary wire (cache-free arm): statics
    # pinned from the first batch exactly like the worker does, decode
    # verified bit-identical against the hashed slot matrix --
    st = derive_stream_statics(
        batches[0].indices, lanes, num_slots, num_slots
    )
    streams = [
        prep_batch_ell_stream(
            b, directory, num_shards, rows_pad, lanes, num_slots, st
        )
        for b in batches
    ]
    stream_parity = st is not None and all(s is not None for s in streams)
    if stream_parity:
        for b, s in zip(batches, streams):
            per = -(-b.n // num_shards)
            for d in range(num_shards):
                lo, hi = min(d * per, b.n), min((d + 1) * per, b.n)
                seg = slice(b.indptr[lo], b.indptr[hi])
                want = hash_slots(
                    np.ascontiguousarray(b.indices[seg], np.uint64),
                    num_slots,
                ).reshape(hi - lo, lanes)
                y, mask, slots = decode_stream_shard(s, d)
                stream_parity &= bool(
                    np.array_equal(np.asarray(slots)[: hi - lo], want)
                    and np.array_equal(
                        np.asarray(y)[: hi - lo], b.y[lo:hi]
                    )
                )
    bpe = {
        "raw_exact": sum(tree_nbytes(p) for p in raws) / n_ex,
        "exact": sum(tree_nbytes(e) for e in encs) / n_ex,
        "bits": sum(tree_nbytes(x) for x in bits) / n_ex,
        **(
            {"stream": sum(tree_nbytes(s) for s in streams) / n_ex}
            if stream_parity
            else {}
        ),
    }

    # staging-leg codec per encoding (net of compression, utils/codec —
    # incompressible streams ride raw so the worst case is ~free):
    # quoted separately from bpe because it shrinks the host↔host
    # staging leg, NOT the PJRT host→device bytes
    lz_bpe = {}
    for name, parts in (
        ("exact", encs),
        ("bits", bits),
        *((("stream", streams),) if stream_parity else ()),
    ):
        lz_bpe[name] = round(
            sum(compress_batch(p).wire_nbytes for p in parts) / n_ex, 1
        )

    # valued stream: raw f32 vs int8 fixed-point (the lossy mode)
    vbatches = _criteo_shape_batches(rows, lanes, n_batches, valued=True,
                                     seed=1)
    vraws = [prep(b) for b in vbatches]
    vencs = [encode_exact(p, num_slots, mode="int8") for p in vraws]
    assert all(e is not None for e in vencs)
    bpe["raw_valued"] = sum(tree_nbytes(p) for p in vraws) / n_ex
    bpe["int8_valued"] = sum(tree_nbytes(e) for e in vencs) / n_ex

    # -- multi-pass amortization through the upload key cache --
    def amortize(parts):
        shipped = 0
        cache = UploadCache(upload_leaf=lambda leaf: leaf,
                            max_bytes=1 << 30)
        for _ in range(passes):
            for part in parts:
                b0, h0 = cache.saved_bytes, cache.hits
                cache(part)
                shipped += tree_nbytes(part) - (cache.saved_bytes - b0)
                shipped += _SIG_BYTES * (cache.hits - h0)
        return shipped / (n_ex * passes), cache

    amort_exact, cache_e = amortize(encs)
    amort_bits, cache_b = amortize(bits)
    amortized = {
        "exact_cached": round(amort_exact, 1),
        "bits_cached": round(amort_bits, 1),
    }

    # -- encode cost: median of back-to-back (prep, prep+encode) pairs --
    reps = 3 if smoke else 5
    t_prep, t_enc = [], []
    for _ in range(reps):
        t0 = _time.perf_counter()
        for b in batches:
            prep(b)
        t_prep.append(_time.perf_counter() - t0)
        t0 = _time.perf_counter()
        for b in batches:
            encode_exact(prep(b), num_slots)
        t_enc.append(_time.perf_counter() - t0)
    ratios = sorted(e / p for e, p in zip(t_enc, t_prep))

    raw_baseline = bpe["bits"]  # the recorded 107.4 B/ex configuration
    out = {
        "minibatch": rows,
        "lanes": lanes,
        "num_slots": num_slots,
        "batches": n_batches,
        "passes": passes,
        "bytes_per_example": {k: round(v, 1) for k, v in bpe.items()},
        "amortized_bytes_per_example": amortized,
        "raw_baseline_bytes_per_example": round(raw_baseline, 1),
        # the acceptance ratios, vs the recorded 107.4 B/ex baseline,
        # amortized over the disclosed pass count. Named precisely:
        # "lossless_default" is the e2e default BITS wire + the upload
        # key cache (the cache is the cross-batch half of the exact/
        # lossless contract — the bits stream itself is unchanged);
        # "exact_encode" is the new encoded exact (PreppedBatch) wire
        # under the same cache. Per-batch encode ratios are reported
        # separately below against each wire's own raw form.
        "reduction_vs_raw_baseline": {
            "lossless_default_amortized": round(
                raw_baseline / amort_bits, 2
            ),
            "exact_encode_amortized": round(
                raw_baseline / amort_exact, 2
            ),
            # the CACHE-FREE column (stream-once data gets no cache
            # repeats — the production --real regime): single-pass
            # bytes, no UploadCache anywhere in the arm
            **(
                {
                    "stream_cache_free": round(
                        raw_baseline / bpe["stream"], 2
                    )
                }
                if stream_parity
                else {}
            ),
        },
        "lz_staging_bytes_per_example": lz_bpe,
        "stream_parity_bit_identical": bool(stream_parity),
        "exact_reduction_vs_raw_exact": round(
            bpe["raw_exact"] / bpe["exact"], 2
        ),
        "int8_reduction_vs_raw_valued": round(
            bpe["raw_valued"] / bpe["int8_valued"], 2
        ),
        "exact_parity_bit_identical": bool(parity),
        "cache": {
            "hits": cache_e.hits + cache_b.hits,
            "misses": cache_e.misses + cache_b.misses,
            "saved_mb": round(
                (cache_e.saved_bytes + cache_b.saved_bytes) / 1e6, 1
            ),
        },
        "encode_over_prep_median_ratio": round(
            ratios[len(ratios) // 2], 3
        ),
        "prep_examples_per_sec": round(n_ex * reps / sum(t_prep), 1),
        "prep_encode_examples_per_sec": round(n_ex * reps / sum(t_enc), 1),
    }
    out["fused_prep"] = stream_prep_ab(smoke)
    return out


def stream_prep_ab(smoke: bool = False) -> dict:
    """Native-vs-Python fused stream-prep A/B (HOST side only).

    The stream wire's prep is the named multi-ms host stage fused into
    one C ABI call (``ps_stream_encode``: hash → per-lane unique →
    remap → bit-pack); the Python arm is the NumPy path it replaces
    (hash pass, per-lane ``np.unique``/``searchsorted`` passes, then
    the bit-packer). Both arms produce BYTE-IDENTICAL wire buffers
    (asserted here, every rep) — the native lib is a speedup, never a
    format. Quotes the MEDIAN of back-to-back paired reps with both
    arms disclosed (the bench discipline: this host's CPU capacity
    flaps seconds-scale). Without ``libpsnative`` the native arm is
    absent and the dict says so (``native_available``)."""
    import time as _time

    from ..cpp import native
    from ..learner import wire as wire_mod
    from ..learner.wire import derive_stream_statics, encode_stream_shard
    from ..utils.murmur import hash_slots

    rows = 4096 if smoke else 16384
    lanes = 39
    num_slots = 1 << 22
    b = _criteo_shape_batches(rows, lanes, 1, seed=3)[0]
    keys = np.ascontiguousarray(b.indices, np.uint64)
    st = derive_stream_statics(keys, lanes, num_slots, num_slots)
    assert st is not None, "criteo-law data must take the lane dictionary"
    lib = native()
    native_ok = (
        lib is not None and getattr(lib, "ps_stream_encode", None) is not None
    )
    out = {
        "minibatch": rows,
        "lanes": lanes,
        "num_slots": num_slots,
        "native_available": bool(native_ok),
        "dict_lanes": len(st.dict_lanes),
    }

    def run_py():
        return wire_mod._encode_stream_shard_py(
            hash_slots(keys, num_slots), rows, rows, st
        )

    def run_native():
        return encode_stream_shard(keys, rows, rows, num_slots, st)

    # parity first: byte-identical output, every field, before any
    # timing is quoted (the fallback contract)
    ref = run_py()
    assert ref is not None
    if native_ok:
        nat = run_native()
        for a, c in zip(nat, ref):
            assert np.array_equal(np.asarray(a), np.asarray(c)), (
                "native fused prep diverged from the Python path"
            )

    reps = 3 if smoke else 5
    t_py, t_nat = [], []
    for _ in range(reps):
        t0 = _time.perf_counter()
        run_py()
        t_py.append(_time.perf_counter() - t0)
        if native_ok:
            t0 = _time.perf_counter()
            run_native()
            t_nat.append(_time.perf_counter() - t0)
    py_ms = sorted(t_py)[len(t_py) // 2] * 1e3
    out["python_ms_median"] = round(py_ms, 3)
    out["python_examples_per_sec"] = round(rows / (py_ms / 1e3), 1)
    out["reps"] = reps
    if native_ok:
        nat_ms = sorted(t_nat)[len(t_nat) // 2] * 1e3
        out["native_ms_median"] = round(nat_ms, 3)
        out["native_examples_per_sec"] = round(rows / (nat_ms / 1e3), 1)
        out["speedup_median_paired"] = round(py_ms / nat_ms, 2)
        out["parity_byte_identical"] = True
    return out


@benchmark("stream_prep")
def stream_prep_perf(smoke: bool = False) -> None:
    """Native-vs-Python fused stream-prep A/B (see stream_prep_ab):
    one C ABI call (hash→unique→remap→bit-pack) against the NumPy
    passes it replaces, byte-identical output asserted."""
    out = stream_prep_ab(smoke)
    report(
        "stream_prep_python_examples_per_sec",
        out["python_examples_per_sec"], "examples/sec",
    )
    if out["native_available"]:
        report(
            "stream_prep_native_examples_per_sec",
            out["native_examples_per_sec"], "examples/sec",
        )
        report(
            "stream_prep_speedup_median_paired",
            out["speedup_median_paired"], "x",
        )


@benchmark("wire")
def wire_perf(smoke: bool = False) -> None:
    """Compact-wire encoded-vs-raw A/B (see wire_ab). CPU-only — bytes
    and encode cost; the link-bound ceiling each bytes/example implies
    is attached by bench.py from its measured link MB/s."""
    out = wire_ab(smoke)
    for k, v in out["bytes_per_example"].items():
        report(f"wire_bytes_per_example_{k}", v, "bytes")
    for k, v in out["amortized_bytes_per_example"].items():
        report(
            f"wire_amortized_bytes_per_example_{k}", v,
            f"bytes ({out['passes']} passes)",
        )
    for k, v in out["reduction_vs_raw_baseline"].items():
        report(f"wire_{k}_reduction_vs_raw_baseline", v, "x")
    report(
        "wire_encode_over_prep_median_ratio",
        out["encode_over_prep_median_ratio"], "x",
    )


@benchmark("host_ingest")
def host_ingest_perf(smoke: bool = False) -> None:
    """Serial vs pipelined host-ingest throughput (see host_ingest_ab).
    CPU-only — no mesh, no device: this isolates the ingest plane the
    way network_perf isolates the wire."""
    out = host_ingest_ab(smoke)
    report(
        "host_ingest_serial_examples_per_sec",
        out["serial_examples_per_sec"],
        "examples/sec",
    )
    report(
        "host_ingest_pipelined_examples_per_sec",
        out["pipelined_examples_per_sec"],
        "examples/sec",
    )
    report("host_ingest_pipelined_speedup", out["pipelined_speedup"], "x")


@benchmark("executor")
def executor_perf(smoke: bool = False) -> None:
    """Host-side dispatch overhead of the executor runtime (the
    counterpart of the reference's per-message Customer/Executor path,
    src/system/executor.cc) — CPU-measurable: how many trivial steps
    per second the submit → dependency-check → dispatch-thread →
    wait machinery moves, with and without dependency chains. The
    device-facing loops batch T minibatches per submit precisely
    because this ceiling exists; the number prices that design
    choice."""
    from ..system.executor import Executor, Task

    n = 500 if smoke else 5000

    ex = Executor("bench")

    def burst_independent():
        ts = [ex.submit(lambda: None) for _ in range(n)]
        ex.wait(ts[-1])
        for t in ts[:-1]:
            ex.wait(t)

    sec = timeit(burst_independent, 1 if smoke else 3)
    report("executor_dispatch_steps_per_sec", n / sec, "steps/sec")

    ex2 = Executor("bench-chain")

    def burst_chained():
        prev = ex2.submit(lambda: None)
        for _ in range(n - 1):
            prev = ex2.submit(lambda: None, task=Task(wait_time=[prev]))
        ex2.wait(prev)

    sec = timeit(burst_chained, 1 if smoke else 3)
    report("executor_chained_steps_per_sec", n / sec, "steps/sec")


def _serve_store(num_slots: int, key_space: int, seed: int = 0):
    """A trained-looking KVVector weight table + a power-law key draw
    (the serving workload shape: a small hot set carries most traffic)."""
    from ..parameter.kv_vector import KVVector

    mesh = _mesh()
    kv = KVVector(
        mesh=mesh, k=1, num_slots=num_slots, hashed=True, name="serve_w"
    )
    rng = np.random.default_rng(seed)
    warm_keys = np.unique(rng.integers(0, key_space, 4096))
    vals = rng.normal(size=(len(warm_keys), 1)).astype(np.float32)
    kv.wait(kv.push(kv.request(channel=0), keys=warm_keys, values=vals))

    # cube-of-uniform power law (the criteo-ish hot-key shape) over the
    # key space — requests OVERLAP heavily on the hot head, which is
    # what coalescing and the hot replica monetize. PRE-DRAWN pool: the
    # arrival thread must sustain thousands of submits/sec, and a fresh
    # Generator per request would throttle the offered load itself
    # (repeating key arrays also exercise the slot-signature caches the
    # way real repeated request shapes do)
    u = rng.random((256, 64))
    pool = (u * u * u * key_space).astype(np.int64)

    def draw_keys(i: int, n: int = 16) -> np.ndarray:
        return pool[i % len(pool), :n]

    return kv, draw_keys


def serve_ab(smoke: bool = False) -> dict:
    """Latency-first serving bench: open-loop Poisson load against the
    request-path frontend (serving/ — doc/SERVING.md).

    Four sections, one dict (embedded by bench.py under ``serve``):

    - **capacity**: closed-loop calibration of this host's per-request
      cost (replica-served pulls), from which the offered-load points
      are derived — the bench self-scales instead of hardcoding rates
      this flapping host would invalidate.
    - **points**: open-loop runs at ~0.25x capacity and ~3x capacity
      (overload) WITH admission control: the acceptance claim is that
      overload p99 stays within a small factor of the low-load p99
      because the door sheds (``shed_frac > 0``) instead of queueing.
    - **no_admission_overload**: the same overload WITHOUT admission —
      the p99 collapse the controller exists to prevent, quoted so the
      win is a measured A/B, not an assertion.
    - **coalesce**: concurrent overlapping-key pulls through the
      frontend (replica off, so every pull rides the live-table path):
      ``submits_per_request < 1`` is the executor-relief win, and
      ``key_dedup_factor`` the gather-volume win.
    - **decode**: the LM lane — speculative decoding
      (models/speculative.py over ops/flash_attention.py) served as
      DecodeRequests. Tiny random-init models on CPU (wiring + latency
      accounting; the TRAINED speedup evidence lives in BENCH_ONCHIP's
      serve/spec_big tasks: 2.33x bandwidth-bound).

    Open-loop + percentiles per the bench discipline: quoting a mean
    under overload would hide exactly the tail the SLO bounds.
    """
    import time as _time

    from ..serving import (
        DecodeRequest,
        PullRequest,
        ServeConfig,
        ServeFrontend,
        open_loop_bench,
    )

    num_slots = 1 << (12 if smoke else 16)
    key_space = 1 << 20
    keys_per_req = 16
    kv, draw_keys = _serve_store(num_slots, key_space)

    # every frontend below closes through ONE finally: a mid-bench
    # failure (the parity assert, an open_loop error) would otherwise
    # leak live worker/flusher threads into bench.py's subsequent TIMED
    # e2e phase, silently skewing the headline record. close() is
    # idempotent, so the success path's own closes are fine.
    fe = None
    try:
        # -- capacity: closed-loop per-request cost through the frontend --
        fe = ServeFrontend(
            kv, ServeConfig(replica="full", workers=2, max_queue_depth=4096)
        ).start()
        n_cal = 60 if smoke else 300
        for i in range(10):  # warm caches/queues
            fe.submit(PullRequest(keys=draw_keys(i, keys_per_req))).result(30)
        t0 = _time.perf_counter()
        for i in range(n_cal):
            fe.submit(PullRequest(keys=draw_keys(i, keys_per_req))).result(30)
        closed_loop_rate = n_cal / (_time.perf_counter() - t0)

        # -- offered-load points (open-loop, admission ON) --
        # the door admits ~0.6x the closed-loop calibration (the open-loop
        # harness itself costs CPU on this small host, so true service
        # capacity sits below the calibrated number) and bounds the backlog
        # at a depth whose drain time IS the p99 budget: p99 ≈ depth x
        # service_time, so the depth — not the arrival process — sets the
        # tail under overload
        admit_rate = max(50.0, 0.6 * closed_loop_rate)
        max_depth = 32 if smoke else 64
        duration = 1.0 if smoke else 2.5
        fe.close()
        fe = ServeFrontend(
            kv,
            ServeConfig(
                replica="full", workers=2,
                admission_rate=admit_rate, admission_burst=admit_rate / 10,
                max_queue_depth=max_depth,
            ),
        ).start()
        points = []
        for mult in (0.25, 3.0):
            points.append(
                open_loop_bench(
                    fe,
                    lambda i: PullRequest(keys=draw_keys(i, keys_per_req)),
                    rate=mult * closed_loop_rate,
                    duration_s=duration,
                    seed=int(mult * 10),
                    collectors=2,
                    warmup_requests=5,
                )
                | {"offered_multiple_of_capacity": mult, "admission": "on"}
            )
        fe.close()

        # -- the counterfactual: same overload, admission OFF (unbounded
        # queue; p99 grows with the backlog, i.e. with how long the
        # overload lasts — the collapse the door exists to prevent) --
        fe = ServeFrontend(
            kv, ServeConfig(replica="full", workers=2, max_queue_depth=0)
        ).start()
        no_adm = open_loop_bench(
            fe,
            lambda i: PullRequest(keys=draw_keys(i, keys_per_req)),
            rate=3.0 * closed_loop_rate,
            duration_s=duration,
            seed=30,
            collectors=2,
            warmup_requests=5,
        ) | {"offered_multiple_of_capacity": 3.0, "admission": "off"}
        fe.close()

        # -- coalescing: overlapping-key pulls on the live-table path --
        fe = ServeFrontend(
            kv,
            ServeConfig(
                replica="off", workers=8, coalesce_window_s=0.002,
                max_queue_depth=4096,
            ),
        ).start()
        n_co = 200 if smoke else 600
        tickets = [
            fe.submit(PullRequest(keys=draw_keys(i, keys_per_req)))
            for i in range(n_co)
        ]
        for t in tickets:
            t.result(60)
        co_stats = fe.stats()["coalescer"]
        # correctness spot-check rides along: coalesced rows == direct pull
        probe = draw_keys(3, keys_per_req)
        direct = kv.values(0, np.unique(probe))
        served = fe.submit(PullRequest(keys=np.unique(probe))).result(30)
        assert np.allclose(served, direct), "coalesced pull diverged"
        fe.close()

        # -- decode lane: speculative generation as served requests --
        import jax

        from ..models.speculative import speculative_generate
        from ..models.transformer import LMConfig, init_lm

        tcfg = LMConfig(vocab=64, d_model=32, n_heads=2, n_layers=2, d_ff=64)
        dcfg = LMConfig(vocab=64, d_model=16, n_heads=2, n_layers=1, d_ff=32)
        tparams = init_lm(jax.random.PRNGKey(0), tcfg)
        dparams = init_lm(jax.random.PRNGKey(1), dcfg)
        gamma = 4
        batch, prompt_len, steps = 2, 16, 8 if smoke else 16
        last_stats = {}

        def decode_fn(req: DecodeRequest):
            out, st = speculative_generate(
                tparams, tcfg, dparams, dcfg,
                jax.numpy.asarray(req.prompt), req.steps, gamma=gamma,
                return_stats=True,
            )
            last_stats["rounds"] = int(np.asarray(st["rounds"]))
            last_stats["accepted_frac"] = round(
                float(np.asarray(st["accepted_frac"])), 3
            )
            return out

        fe = ServeFrontend(
            kv, ServeConfig(replica="full", workers=1, max_queue_depth=64),
            decode_fn=decode_fn,
        ).start()
        rng = np.random.default_rng(11)

        def decode_req(i: int) -> DecodeRequest:
            return DecodeRequest(
                prompt=rng.integers(0, 64, (batch, prompt_len)).astype(np.int32),
                steps=steps,
            )

        t0 = _time.perf_counter()
        fe.submit(decode_req(0)).result(300)  # compile, excluded
        compile_s = _time.perf_counter() - t0
        n_dec = 2 if smoke else 4
        lat = []
        t0 = _time.perf_counter()
        for i in range(n_dec):
            tk = fe.submit(decode_req(1 + i))
            tk.result(300)
            lat.append(tk.latency_s())
        dec_wall = _time.perf_counter() - t0
        fe.close()

        return {
            "closed_loop_capacity_per_sec": round(closed_loop_rate, 1),
            "keys_per_request": keys_per_req,
            "points": points,
            "no_admission_overload": no_adm,
            # the acceptance ratio: overload p99 / low-load p99 with the
            # door on, vs the same ratio with it off
            "p99_overload_over_low_admitted": round(
                points[1]["latency_ms"]["p99_ms"]
                / max(1e-9, points[0]["latency_ms"]["p99_ms"]), 2,
            ),
            "p99_overload_over_low_unprotected": round(
                no_adm["latency_ms"]["p99_ms"]
                / max(1e-9, points[0]["latency_ms"]["p99_ms"]), 2,
            ),
            "coalesce": {
                "concurrent_requests": n_co,
                **co_stats,
            },
            "decode": {
                "model": "byte-LM random-init (wiring; trained evidence: "
                "BENCH_ONCHIP serve/spec_big)",
                "gamma": gamma,
                "batch": batch,
                "prompt_len": prompt_len,
                "steps": steps,
                "requests": n_dec,
                "compile_s": round(compile_s, 2),
                "tokens_per_sec": round(n_dec * batch * steps / dec_wall, 1),
                "latency_ms": {
                    "p50_ms": round(float(np.median(lat)) * 1e3, 1),
                    "max_ms": round(float(np.max(lat)) * 1e3, 1),
                },
                **last_stats,
            },
        }
    finally:
        if fe is not None:
            fe.close()


@benchmark("serve")
def serve_perf(smoke: bool = False) -> None:
    """Request-path serving SLO bench (see serve_ab). CPU-runnable:
    rates self-calibrate to the host; on-chip runs quote the same
    record shape with real device pulls."""
    out = serve_ab(smoke)
    low, over = out["points"]
    report(
        "serve_closed_loop_capacity",
        out["closed_loop_capacity_per_sec"], "requests/sec",
    )
    report("serve_p99_low_load", low["latency_ms"]["p99_ms"], "ms")
    report("serve_p99_overload_admitted", over["latency_ms"]["p99_ms"], "ms")
    report(
        "serve_p99_overload_unprotected",
        out["no_admission_overload"]["latency_ms"]["p99_ms"], "ms",
    )
    report("serve_goodput_overload", over["goodput_per_sec"], "requests/sec")
    report("serve_overload_shed_frac", over["shed_frac"], "fraction")
    report(
        "serve_coalesce_merge_factor",
        out["coalesce"]["requests"] / max(1, out["coalesce"]["submits"]),
        "requests/submit",
    )
    report(
        "serve_decode_tokens_per_sec",
        out["decode"]["tokens_per_sec"], "tokens/sec",
    )


def decode_batching_ab(smoke: bool = False) -> dict:
    """Continuous-batching decode A/B (serving/batcher.py): batched
    vs sequential tokens/s under join/leave churn, plus the
    device-resident replica serving a table LARGER than the host
    budget with zero degrades.

    Two sections, one dict (embedded by bench.py under
    ``decode_batching``):

    - **arms**: for each slot count B, the same session mix decoded
      two ways — sequentially (per-request ``speculative_generate``,
      the pre-batcher serving path: ONE fused while_loop per request)
      and through :class:`ContinuousBatcher` with wave admission
      (``admit_many``) and fused round blocks (``step_block``),
      sessions joining as slots free (join/leave churn, the serving
      arrival shape). Arms alternate back-to-back; the speedup quotes
      the MEDIAN of paired ratios (PR-3 bench discipline). TOKEN
      PARITY is asserted in-bench every rep: each session's batched
      stream must equal its own solo run.
    - **device_replica**: a :class:`ServeFrontend` with
      ``replica_device=True`` serving a weight table ~2x the
      configured host-replica budget (host mode refuses this loudly)
      through a live donated-push stream — the acceptance gate is
      ``degraded_served == 0`` across the refresh churn.

    ``gamma=2`` (not the batcher's default 4) because the A/B contrast
    is what this bench measures: sequential decode is weight-read
    bound, so the fewer tokens a round commits the more the batch
    amortizes each weight read. ``onchip_target`` states the bar the
    next device capture is judged against — this host is a SINGLE
    CPU core (no GEMM parallelism), where the measured roofline for
    batch-8 amortization sits near 3x and churn/join overhead lands
    the end-to-end median near 2.6x; the chip's bandwidth-bound
    batched matmuls are what the 3x bar describes.
    """
    import time as _time

    import jax

    from ..models.speculative import speculative_generate
    from ..models.transformer import LMConfig, init_lm
    from ..serving import (
        BatcherConfig,
        ContinuousBatcher,
        DecodeRequest,
        PullRequest,
        ServeConfig,
        ServeFrontend,
    )

    if smoke:
        tcfg = LMConfig(
            vocab=256, d_model=256, n_heads=4, n_layers=2, d_ff=512
        )
        dcfg = LMConfig(
            vocab=256, d_model=64, n_heads=2, n_layers=1, d_ff=128
        )
        arms, steps_mix, reps, sess_per_slot = (8,), (16, 24), 1, 2
    else:
        tcfg = LMConfig(
            vocab=256, d_model=512, n_heads=8, n_layers=2, d_ff=1024
        )
        dcfg = LMConfig(
            vocab=256, d_model=128, n_heads=2, n_layers=1, d_ff=256
        )
        arms, steps_mix, reps, sess_per_slot = (1, 4, 8, 16), (40, 48), 3, 6
    gamma = 2
    prompt_len = 8
    max_new = max(steps_mix)
    tparams = init_lm(jax.random.PRNGKey(0), tcfg)
    dparams = init_lm(jax.random.PRNGKey(1), dcfg)

    def mk_reqs(n: int, seed0: int = 0):
        rng = np.random.default_rng(seed0)
        return [
            DecodeRequest(
                prompt=rng.integers(
                    0, tcfg.vocab, (1, prompt_len)
                ).astype(np.int32),
                steps=steps_mix[i % len(steps_mix)],
            )
            for i in range(n)
        ]

    def run_seq(reqs):
        return [
            np.asarray(
                speculative_generate(
                    tparams, tcfg, dparams, dcfg,
                    jax.numpy.asarray(r.prompt), r.steps, gamma=gamma,
                )
            )
            for r in reqs
        ]

    def run_batched(b, reqs):
        # the churn harness: sessions join in waves as slots free,
        # finished sessions retire between (fused) rounds
        outs = {}
        pending = list(reqs)
        order = {id(r): i for i, r in enumerate(reqs)}
        for _ in range(100000):
            wave = []
            while pending and len(wave) < b.free_slots():
                wave.append((pending.pop(0), None))
            if wave:
                b.admit_many(wave)
            for h in b.step_block():
                outs[order[id(h.req)]] = h.out
            if not pending and b.active_sessions() == 0:
                return [outs[i] for i in range(len(reqs))]
        raise AssertionError("continuous batch failed to drain")

    arm_records = []
    for slots in arms:
        b = ContinuousBatcher(
            tparams, tcfg, dparams, dcfg,
            BatcherConfig(
                slots=slots, max_prompt=prompt_len, max_new=max_new,
                gamma=gamma, max_block=16,
            ),
        )
        t0 = _time.perf_counter()
        b.warmup()  # round + block + every pow2 join wave size
        run_seq(mk_reqs(len(steps_mix)))
        run_batched(b, mk_reqs(slots, seed0=999))
        compile_s = _time.perf_counter() - t0
        nsess = sess_per_slot * slots
        reqs = mk_reqs(nsess)
        total_toks = sum(r.steps for r in reqs)
        ratios, seq_tps, bat_tps = [], [], []
        for _ in range(reps):
            t0 = _time.perf_counter()
            seq_out = run_seq(reqs)
            t_seq = _time.perf_counter() - t0
            t0 = _time.perf_counter()
            bat_out = run_batched(b, reqs)
            t_bat = _time.perf_counter() - t0
            # the correctness contract, enforced inside the bench:
            # every session token-identical to its sequential run
            for s, c in zip(seq_out, bat_out):
                np.testing.assert_array_equal(s, c)
            ratios.append(t_seq / t_bat)
            seq_tps.append(total_toks / t_seq)
            bat_tps.append(total_toks / t_bat)
        st = b.stats()
        arm_records.append(
            {
                "slots": slots,
                "sessions": nsess,
                "tokens_per_session": sorted(set(steps_mix)),
                "compile_s": round(compile_s, 1),
                "seq_tokens_per_sec": round(float(np.median(seq_tps)), 1),
                "batched_tokens_per_sec": round(
                    float(np.median(bat_tps)), 1
                ),
                "speedup": round(float(np.median(ratios)), 2),
                "speedup_reps": [round(r, 2) for r in ratios],
                "accepted_frac": round(st["accepted_frac"], 3),
                "parity": "token-identical per session (asserted)",
            }
        )

    # -- device-resident replica over the host budget ------------------
    from ..parameter.kv_vector import KVVector

    mesh = _mesh()
    kv = KVVector(
        mesh=mesh, k=8, num_slots=1 << (10 if smoke else 14),
        hashed=True, name="serve_dev",
    )
    rng = np.random.default_rng(7)
    keys = np.unique(rng.integers(0, 1 << 20, 512))
    vals = rng.normal(size=(len(keys), 8)).astype(np.float32)
    kv.wait(kv.push(kv.request(channel=0), keys=keys, values=vals))
    table_bytes = int(kv.table(0).nbytes)
    budget = table_bytes // 2  # host replica mode refuses this table
    fe = ServeFrontend(
        kv,
        ServeConfig(
            replica="full", replica_device=True,
            replica_host_budget_bytes=budget, replica_refresh_s=0.02,
            workers=2, max_queue_depth=256,
        ),
    ).start()
    try:
        stop = _time.perf_counter() + (0.3 if smoke else 0.8)
        served = 0
        while _time.perf_counter() < stop:
            # pushes churn the table while reads ride the device
            # snapshot: every refresh consumes a consistent snapshot
            # of a donated-update stream
            kv.push(
                kv.request(channel=0), keys=keys[:64],
                values=rng.normal(size=(64, 8)).astype(np.float32),
            )
            fe.submit(
                PullRequest(keys=keys[rng.integers(0, len(keys), 16)])
            ).result(30)
            served += 1
        degraded = fe.degraded_served
        device_mode = bool(fe.stats()["replica"]["device"])
    finally:
        fe.close()

    by8 = next((a for a in arm_records if a["slots"] == 8), arm_records[-1])
    return {
        "model": {
            "target": "d512 2-layer byte-LM (random-init; self-"
            "agreeing draft => accepted_frac ~1.0)"
            if not smoke else "d256 2-layer byte-LM (smoke)",
            "draft": "d128 1-layer" if not smoke else "d64 1-layer",
            "gamma": gamma,
            "prompt_len": prompt_len,
        },
        "reps": reps,
        "arms": arm_records,
        "speedup_at_8": by8["speedup"],
        "device_replica": {
            "table_bytes": table_bytes,
            "host_budget_bytes": budget,
            "over_budget_factor": round(table_bytes / budget, 2),
            "refresh_s": 0.02,
            "requests_served": served,
            "degraded_served": int(degraded),
            "device": device_mode,
        },
        # the PR 8 pattern: the CPU record states the bar the next
        # reachable-device capture is judged against. This host is one
        # CPU core — batched GEMMs gain no parallelism and the batch-8
        # amortization roofline (weight reads + per-op dispatch over 8
        # rows) measures ~3x, of which churn/joins keep ~2.6x. On
        # chip the batched verify matmul is bandwidth-bound (weights
        # read once per round for the whole batch), which is what the
        # 3x bar describes.
        "onchip_target": {
            "decode_batched_speedup_at_8": ">= 3x sequential under "
            "join/leave churn (token parity asserted)",
            "measured_on": "not measured: the first chip run of this bench",
        },
    }


@benchmark("decode_batching")
def decode_batching_perf(smoke: bool = False) -> None:
    """Continuous-batching decode A/B (see decode_batching_ab):
    batched-vs-sequential tokens/s with in-bench token parity, plus
    the device-replica-over-host-budget zero-degrade gate."""
    out = decode_batching_ab(smoke)
    by8 = next(
        (a for a in out["arms"] if a["slots"] == 8), out["arms"][-1]
    )
    report("decode_batched_speedup_at_8", out["speedup_at_8"], "x")
    report(
        "decode_batched_tokens_per_sec",
        by8["batched_tokens_per_sec"], "tokens/sec",
    )
    report(
        "decode_sequential_tokens_per_sec",
        by8["seq_tokens_per_sec"], "tokens/sec",
    )
    # served count MINUS degrades: positive only while the over-budget
    # device replica answers every request un-degraded (the report
    # contract wants values > 0; zero degrades is the gate, so quote
    # the clean-served count rather than the zero itself)
    dr = out["device_replica"]
    report(
        "decode_device_replica_clean_requests",
        dr["requests_served"] - dr["degraded_served"], "requests",
    )


@benchmark("trace")
def trace_perf(smoke: bool = False) -> None:
    """Capture a short synthetic run's flow-correlated timeline and
    export it as Chrome trace / Perfetto JSON (``make trace``).

    Drives the real pipeline pieces — an IngestPipeline (feeder +
    ordered prep pool) feeding executor steps submitted under each
    batch's flow id — with a JSONL span sink installed, then writes the
    merged timeline where ``PS_TRACE_OUT`` points (default
    ``<tmp>/ps_timeline_trace.json``; the raw JSONL lands next to it)
    and runs the critical-path analyzer over it. Open the export at
    https://ui.perfetto.dev — doc/OBSERVABILITY.md "Reading a timeline"
    walks what you see. Reported metrics double as liveness checks:
    zero captured events or uncorrelated flows would fail the registry
    smoke test."""
    import os
    import tempfile
    import time as _time

    from ..learner.ingest import IngestPipeline
    from ..system.executor import Executor
    from ..telemetry import attribution as attribution_mod
    from ..telemetry import spans as telemetry_spans
    from ..telemetry import timeline as timeline_mod

    out_path = os.environ.get("PS_TRACE_OUT") or os.path.join(
        tempfile.gettempdir(), "ps_timeline_trace.json"
    )
    jsonl_path = out_path + ".jsonl"
    try:
        os.remove(jsonl_path)  # fresh capture, never mix runs
    except OSError:
        pass
    n_batches = 6 if smoke else 24
    rng = np.random.default_rng(0)
    work = rng.random(1 << (12 if smoke else 16))

    def batches():
        for i in range(n_batches):
            yield i

    def prep(i):
        return float(np.sort(work).sum()) + i  # real CPU work

    prev = telemetry_spans.install_sink(telemetry_spans.JsonlSink(jsonl_path))
    t0 = _time.perf_counter()
    try:
        pipe = IngestPipeline(
            batches(), prep_fn=prep, workers=2, name="trace"
        ).start()
        ex = Executor(name="trace_bench", telemetry=True)
        for item in pipe:
            # the pipeline keeps the batch's flow active on this thread
            # until the next item, so the step correlates automatically
            ex.submit(lambda item=item: float(work[:1024].sum()) + item)
        ex.wait_all()
        ex.stop()
    finally:
        mine = telemetry_spans.install_sink(prev)
        if mine is not None and mine is not prev:
            mine.close()
    capture_s = _time.perf_counter() - t0

    events = timeline_mod.load_events(jsonl_path)
    trace = timeline_mod.to_chrome_trace(events)
    import json as _json

    with open(out_path, "w", encoding="utf-8") as f:
        _json.dump(trace, f)
    summary = attribution_mod.summarize(events)
    report("trace_events_captured", len(events), "events")
    report("trace_flows_correlated", summary["flows"].get("count", 0), "flows")
    report("trace_capture_events_per_sec", len(events) / capture_s, "events/sec")


@benchmark("bundle")
def bundle_probe(smoke: bool = False) -> None:
    """Capture a diagnostic bundle from a live mini-cluster and write
    it where ``PS_BUNDLE_OUT`` points (``make bundle``; default
    ``<tmp>/ps_bundle.json``) — the operator's "what was the system
    doing just now" artifact, identical in shape to what an alert
    firing or a shard death auto-captures (telemetry/blackbox.py).

    Drives the real pieces: the flight recorder armed as a tee (zero
    file IO), per-node recorders with metrics-delta samples, traced
    work under flow scopes, an AuxRuntime with two registered nodes —
    one of which goes SILENT before capture, so the bundle demonstrably
    carries staleness instead of a fabricated ring. The ``trace``
    section opens directly at https://ui.perfetto.dev."""
    import json as _json
    import os
    import tempfile
    import time as _time

    from ..system.aux_runtime import AuxRuntime
    from ..telemetry import blackbox
    from ..telemetry import spans as telemetry_spans

    out_path = os.environ.get("PS_BUNDLE_OUT") or os.path.join(
        tempfile.gettempdir(), "ps_bundle.json"
    )
    # targeted setup/cleanup like recovery_drill's (never a global
    # blackbox.reset(): that would disarm an enclosing run's tee, drop
    # its recorders, and clobber its rate-limit interval)
    prev_interval = blackbox.set_min_interval(0.0)
    was_armed = blackbox.installed_recorder() is not None
    aux = AuxRuntime(heartbeat_timeout=5.0, stale_after_s=0.08)
    try:
        aux.register("W0")
        aux.register("S0")
        blackbox.arm()
        for nid in ("W0", "S0"):
            blackbox.recorder(nid).clear()
            blackbox.recorder(nid).sample_metrics()
        # traced work: flows whose spans land in the ring
        n = 8 if smoke else 32
        work = np.random.default_rng(0).random(1 << 14)
        for i in range(n):
            with telemetry_spans.flow_scope(telemetry_spans.new_flow()):
                with telemetry_spans.span("bundle.demo", i=i):
                    float(np.sort(work).sum())
        for nid in ("W0", "S0"):
            blackbox.recorder(nid).sample_metrics()
        # S0 goes silent: only W0 keeps reporting past the staleness
        # window, so the capture must mark S0 stale (the honest half
        # of "ring dumps from every node")
        _time.sleep(0.1)
        aux.report_node("W0", wire=False)
        t0 = _time.perf_counter()
        bundle = aux.bundle(trigger="manual", force=True)
        capture_ms = (_time.perf_counter() - t0) * 1e3
        with open(out_path, "w", encoding="utf-8") as f:
            _json.dump(bundle, f, default=str)
        summary = blackbox.summarize_bundle(bundle)
        stale_nodes = [
            nid for nid, d in summary["nodes"].items() if d.get("stale")
        ]
        assert "S0" in stale_nodes, "silent node S0 not marked stale"
        assert summary["nodes"].get("W0", {}).get("events") is not None or (
            summary["nodes"].get("W0", {}).get("stale") is False
        ), "live node W0 has no ring dump"
        # no free-form print here: the benchmark runner's stdout is one
        # JSON line per metric (test_benchmarks parses every line); the
        # Makefile target echoes the output path for humans
        report("bundle_ring_nodes", len(summary["nodes"]), "nodes")
        report("bundle_stale_nodes", len(stale_nodes), "nodes")
        report("bundle_trace_events", summary["trace_events"], "events")
        report("bundle_capture_ms", capture_ms, "ms")
    finally:
        blackbox.set_min_interval(prev_interval)
        blackbox.drop_recorder("W0")
        blackbox.drop_recorder("S0")
        if not was_armed:
            blackbox.disarm()
        aux.stop()


def history_ab(smoke: bool = False) -> dict:
    """Steady-state overhead of the history plane (telemetry/history.py)
    priced the bench-discipline way: the SAME metric-churn workload
    (counter bumps, gauge sets, histogram observes, a periodic registry
    export — the report timer's read, which is exactly where the
    installed fold hook rides) with the HistoryStore installed vs
    absent, both orders inside one rep (on, off, off, on) so a monotone
    capacity drift on this flapping host cancels out of the paired
    ratio. The quoted claim is the MEDIAN ratio; because a seconds-scale
    capacity flap can still fake a stream ratio, the absolute per-fold
    cost is ALSO priced as a tight-loop ``fold_us_median`` over the
    full canonical instrument catalog. The A/B store runs at a 10 ms
    base resolution — two orders of magnitude HOTTER than the
    production 1 s cadence — so the quoted overhead is an upper bound,
    never a best case."""
    import time as _time

    from ..telemetry.history import HistoryStore
    from ..telemetry.instruments import install_all
    from ..telemetry.registry import MetricsRegistry

    n = 1500 if smoke else 6000
    reps = 3 if smoke else 5

    def build(with_history: bool):
        reg = MetricsRegistry()
        cs = [
            reg.counter(f"ab_hist_c{i}_total", "history A/B churn",
                        labelnames=("k",))
            for i in range(4)
        ]
        gs = [reg.gauge(f"ab_hist_g{i}", "history A/B churn")
              for i in range(4)]
        hist = reg.histogram(
            "ab_hist_lat_seconds", "history A/B churn",
            buckets=(1e-5, 1e-4, 1e-3, 1e-2),
        )
        if with_history:
            HistoryStore(reg, resolutions=((0.01, 600), (0.1, 720))).install()
        return reg, cs, gs, hist

    def run(world) -> None:
        reg, cs, gs, hist = world
        for i in range(n):
            cs[i & 3].labels(k=str(i & 7)).inc()
            gs[i & 3].set(float(i))
            hist.observe((i & 15) * 1e-4 + 1e-5)
            if i % 50 == 0:
                # the scrape/report read; with the store installed this
                # is what invokes the (rate-limited) fold hook
                reg.export_state()

    on, off = build(True), build(False)

    def timed(world) -> float:
        t0 = _time.perf_counter()
        run(world)
        return _time.perf_counter() - t0

    timed(on)  # warm both shapes
    timed(off)
    ratios, on_s, off_s = [], [], []
    for _ in range(reps):
        a1 = timed(on)
        o = (timed(off) + timed(off)) / 2
        a2 = timed(on)
        ratios.append(((a1 + a2) / 2) / max(o, 1e-9))
        on_s.append((a1 + a2) / 2)
        off_s.append(o)
    ratios.sort()
    on_s.sort()
    off_s.sort()

    # tight-loop absolute: one forced fold over the FULL canonical
    # catalog (every instrument family, one live series each) — the
    # pure per-fold cost no workload flap can fake
    cat_reg = MetricsRegistry()
    instruments = install_all(cat_reg)
    for inst in instruments.values():
        target = (
            inst.labels(**{ln: "probe" for ln in inst.labelnames})
            if inst.labelnames else inst
        )
        if inst.kind == "histogram":
            target.observe(0.001)
        elif inst.kind == "gauge":
            target.set(1.0)
        else:
            target.inc()
    store = HistoryStore(cat_reg)
    m = 50 if smoke else 200
    folds = []
    for _ in range(m):
        t0 = _time.perf_counter()
        store.fold(force=True)
        folds.append(_time.perf_counter() - t0)
    folds.sort()
    snap = store.snapshot()
    return {
        "reps": reps,
        "steps_per_rep": n,
        "ratio_median": round(ratios[len(ratios) // 2], 3),
        "on_ms_median": round(on_s[len(on_s) // 2] * 1e3, 3),
        "off_ms_median": round(off_s[len(off_s) // 2] * 1e3, 3),
        "fold_us_median": round(folds[len(folds) // 2] * 1e6, 1),
        "fold_series": snap["series"],
        "resolutions": snap["resolutions"],
    }


@benchmark("history_ab")
def history_ab_perf(smoke: bool = False) -> None:
    """History-plane overhead A/B (see history_ab): metric-churn
    workload with the ring-cascade fold hook installed vs absent,
    paired-median ratio + tight-loop per-fold cost over the full
    instrument catalog."""
    out = history_ab(smoke)
    report("history_overhead_ratio_median", out["ratio_median"], "x")
    report("history_fold_us_median", out["fold_us_median"], "us")
    report("history_fold_series", out["fold_series"], "series")


def _drill_batch(seed: int, i: int, key_space: int, n: int, k: int):
    """Deterministic training batch ``i`` — regenerable by index, which
    is what lets the recovery handler REPLAY acked-but-unbacked updates
    instead of journaling arrays (doc/ROBUSTNESS.md "The drill")."""
    rng = np.random.default_rng((seed << 20) + i)
    keys = rng.integers(0, key_space, n).astype(np.int64)
    vals = rng.normal(size=(n, k)).astype(np.float32)
    return keys, vals


def _learning_mesh():
    """A mesh with >= 2 server shards when the host has the devices —
    the learning probe's shard-balance evidence needs real per-shard
    key ranges, not a single-shard triviality."""
    import jax

    from ..system.postoffice import Postoffice

    Postoffice.reset()
    n = len(jax.devices())
    if n >= 2:
        return Postoffice.instance().start(
            num_data=n // 2, num_server=2
        ).mesh
    return Postoffice.instance().start().mesh


def _divergence_drill(mesh, smoke: bool = False) -> dict:
    """Seeded divergence drill: an LR blow-up (square loss, alpha 1e12)
    NaNs the trajectory within a few steps; the learning plane judges
    the collected steps divergent (``ps_learning_divergence_total``),
    the SHIPPED ``loss_divergence`` rule walks inactive → pending →
    firing, and the firing transition captures a flight-recorder
    diagnostic bundle through the PR 13 alert trigger plane — the same
    listener wiring ``AuxRuntime.set_alerts`` installs. Deterministic
    under a fake clock; all tier-1-tested (tests/test_learning.py)."""
    from ..apps.linear.async_sgd import AsyncSGDWorker
    from ..apps.linear.config import (
        Config,
        LearningRateConfig,
        LossConfig,
        PenaltyConfig,
        SGDConfig,
    )
    from ..telemetry import alerts as alerts_mod
    from ..telemetry import blackbox
    from ..telemetry import learning as learning_mod
    from ..utils.sparse import random_sparse

    rule = next(
        r for r in alerts_mod.default_rules() if r.name == "loss_divergence"
    )
    clock = [0.0]
    mgr = alerts_mod.AlertManager([rule], clock=lambda: clock[0])
    prev_interval = blackbox.set_min_interval(0.0)
    was_armed = blackbox.installed_recorder() is not None
    blackbox.arm()
    bundles: list = []

    def on_transition(ev) -> None:
        # the AuxRuntime._maybe_bundle_on_alert wiring, drill-local:
        # a firing alert captures the evidence while it is in the ring
        if ev.to == "firing" and ev.rule == "loss_divergence":
            b = blackbox.trigger_bundle("alert", detail=ev.rule)
            if b is not None:
                bundles.append(b)

    mgr.add_listener(on_transition)
    conf = Config()
    conf.loss = LossConfig(type="square")
    conf.penalty = PenaltyConfig(type="l2", lambda_=[0.0])
    # the blow-up: plain SGD at a constant learning rate orders of
    # magnitude past stability turns the square loss's w-proportional
    # gradient into an exponential — float32 overflows to Inf/NaN
    # within a handful of steps on any data (FTRL would self-damp via
    # its adaptive per-coordinate rate, which is exactly why the drill
    # picks the updater the reference's SGDEntry models)
    conf.learning_rate = LearningRateConfig(
        type="constant", alpha=1e10, beta=1.0
    )
    conf.async_sgd = SGDConfig(
        algo="standard", minibatch=64, num_slots=1 << 9, max_delay=0,
    )
    worker = AsyncSGDWorker(conf, mesh=mesh, name="learning_diverge")
    states = []
    try:
        mgr.evaluate()  # t=0 baseline sample — a rate needs a window
        states.append(mgr.states()[rule.name].state_name)
        n_steps = 8 if smoke else 12
        for i in range(n_steps):
            b = random_sparse(64, 1 << 12, 6, seed=100 + i, binary=True)
            b.y = np.where(np.arange(64) % 2 == 0, 1.0, -1.0).astype(
                np.float32
            )
            ts = worker._submit_prepped(
                worker.prep(b, device_put=False), with_aux=False
            )
            worker.collect(ts)
        plane = learning_mod.get_plane("learning_diverge")
        divergences = dict(plane.snapshot()["divergence"]) if plane else {}
        clock[0] = 5.0
        mgr.evaluate()  # pending → firing in one tick (for_s=0)
        states.append(mgr.states()[rule.name].state_name)
        fired = rule.name in mgr.firing()
        # traffic stops; the window slides past the burst → resolved
        clock[0] = 5.0 + rule.window_s + 10.0
        mgr.evaluate()
        states.append(mgr.states()[rule.name].state_name)
    finally:
        worker.executor.stop()
        blackbox.set_min_interval(prev_interval)
        if not was_armed:
            blackbox.disarm()
    return {
        "divergence_counts": divergences,
        "states_seen": states,
        "fired": bool(fired),
        "resolved": states[-1] in ("resolved", "inactive"),
        "bundle_captured": bool(bundles),
        "bundle_trigger": (
            dict(bundles[0]["trigger"]) if bundles else None
        ),
    }


def learning_truth(smoke: bool = False) -> dict:
    """The learning truth plane probe (telemetry/learning.py), embedded
    under ``learning`` in every bench record and run standalone via
    ``make learning-bench``.

    A short real training run through the collect path on a bounded-
    delay config (τ=3) yields: the REALIZED staleness histogram with
    the in-record bound verdict (``within_bound``: observed max <= the
    configured ``SGDConfig.max_delay`` — the OSDI'14 contract as a
    measured invariant), per-server-shard key-heat load shares + the
    imbalance ratio + the top-k hot-slot table, the loss/grad-norm
    convergence trajectory from the step builders' in-jit side outputs,
    and a sketch-vs-exact heat parity check (the windowed count-min
    against exact slot counts over the same stream). A seeded LR
    blow-up then drives the shipped ``loss_divergence`` rule to firing
    with a diagnostic bundle attached. Record METADATA, never banded by
    the bench-diff sentinel (script/bench_diff.py METADATA_SECTIONS)."""
    from ..apps.linear.async_sgd import AsyncSGDWorker
    from ..apps.linear.config import (
        Config,
        LearningRateConfig,
        PenaltyConfig,
        SGDConfig,
    )
    from ..parallel import mesh as meshlib
    from ..telemetry import learning as learning_mod
    from ..utils.sparse import random_sparse

    mesh = _learning_mesh()
    tau = 3
    minibatch = 128
    n_batches = 24 if smoke else 48
    conf = Config()
    conf.penalty = PenaltyConfig(type="l1", lambda_=[0.1])
    conf.learning_rate = LearningRateConfig(
        type="decay", alpha=0.1, beta=1.0
    )
    conf.async_sgd = SGDConfig(
        algo="ftrl", minibatch=minibatch, num_slots=1 << 10, max_delay=tau,
    )
    worker = AsyncSGDWorker(conf, mesh=mesh, name="learning_probe")

    def batch(i: int):
        b = random_sparse(minibatch, 1 << 16, 8, seed=i, binary=True)
        b.y = np.where(
            (b.indices.reshape(minibatch, -1) % 64 < 16).mean(1) > 0.24,
            1.0, -1.0,
        ).astype(np.float32)
        return b

    batches = [batch(i) for i in range(n_batches)]
    # exact heat ground truth over the SAME stream, hashed through the
    # SAME directory the sketch sees
    exact = np.zeros(worker.num_slots, np.int64)
    for b in batches:
        np.add.at(
            exact, worker.directory.slots(np.asarray(b.indices)), 1
        )
    try:
        worker.train(iter(batches))
        plane = learning_mod.get_plane("learning_probe")
        snap = plane.snapshot()
        uniq = np.flatnonzero(exact)
        est = plane.heat.estimate(uniq)
        # no decay window elapses on a run this short, so CM semantics
        # apply directly: estimates are exact up to hash collisions
        # (upper-biased, never under)
        parity = {
            "distinct_slots": int(uniq.size),
            "exact_match_frac": round(float(np.mean(est == exact[uniq])), 4),
            "upper_bound_frac": round(float(np.mean(est >= exact[uniq])), 4),
        }
    finally:
        worker.executor.stop()
    return {
        "config": {
            "max_delay": tau,
            "n_batches": n_batches,
            "minibatch": minibatch,
            "num_slots": worker.num_slots,
            "num_shards": meshlib.num_servers(mesh),
        },
        "staleness": snap["staleness"],
        "shards": snap["shards"],
        "hot_slots": snap["hot_slots"][:8],
        "examples": snap["examples"],
        "collected_steps": snap["collected_steps"],
        "trajectory_tail": snap["trajectory_tail"][-8:],
        "heat_parity": parity,
        "divergence_drill": _divergence_drill(mesh, smoke),
    }


@benchmark("learning")
def learning_perf(smoke: bool = False) -> None:
    """The learning truth plane headline (``make learning-bench``):
    realized staleness must respect the configured τ, the sketch must
    agree with exact heat on a small run, shard shares must cover the
    traffic, the convergence trajectory must be finite on a healthy
    run — and the seeded divergence drill must fire the shipped rule
    with a bundle attached."""
    out = learning_truth(smoke)
    st = out["staleness"]
    assert st["within_bound"], (
        f"realized staleness {st['observed_max']} breached the "
        f"configured tau {st['configured_tau']}"
    )
    assert out["heat_parity"]["upper_bound_frac"] == 1.0, out["heat_parity"]
    drill = out["divergence_drill"]
    assert drill["fired"] and drill["bundle_captured"], drill
    # the >0 report contract forbids printing a raw observed_max that
    # can legitimately be 0 (an always-snapshotting run) — the honest
    # headline is the verdict, asserted above, with the raw value in
    # the record's learning.probe.staleness section
    report("learning_staleness_within_bound", 1.0, "bool")
    report("learning_staleness_submits", st["submits"], "submissions")
    report(
        "learning_heat_exact_match",
        out["heat_parity"]["exact_match_frac"],
        "fraction",
    )
    report(
        "learning_shard_imbalance",
        out["shards"]["imbalance"] or 0.0,
        "max_over_mean",
    )
    report("learning_examples_confirmed", out["examples"], "examples")


def recovery_drill(smoke: bool = False) -> dict:
    """Kill-one-shard recovery drill under concurrent train + serve load
    (doc/ROBUSTNESS.md — ROADMAP item 2's acceptance drill, embedded in
    every bench record under ``recovery``).

    The script, all under live load (a paced training push stream and a
    closed-loop serving client against the SAME store):

    1. **healthy** — periodic consistent replica backups
       (``ReplicaManager.start_periodic`` → snapshot steps THROUGH the
       store executor, so donated pushes can't tear them) while the
       trainer acks pushes and serving reads live.
    2. **kill** — the backup stream stops, then ``S0`` dies the way real
       shards die: its heartbeats stop arriving (injected
       ``heartbeat.report`` silence), its table is wiped (the
       replacement starts empty), and the serving store path starts
       failing (``serve.pull`` / ``serve.refresh`` faults). Serving
       DEGRADES to the stale read replica (503-distinct accounting)
       instead of erroring; training keeps acking into the void —
       exactly the updates the replay contract must not lose.
    3. **detect + recover** — the RecoveryCoordinator's poll declares
       S0 dead after the heartbeat timeout; the server-death handler
       parks the trainer (bounded-delay semantics: survivors stop
       pushing while the shard recovers), installs the last consistent
       snapshot through the executor, REPLAYS every acked push past the
       snapshot's barrier timestamp in original order, then re-arms the
       store path and resumes.
    4. **verify** — after the stream completes, the drilled table must
       be BIT-identical to an undisturbed run of the same batch
       sequence: zero lost *acknowledged* updates, to the bit.

    Also measured: detection / recovery / MTTR wall times, serve
    requests completed/degraded/shed/failed, and the disarmed-overhead
    paired check (fault points present-but-disarmed vs stripped) that
    keeps the "zero overhead when disarmed" claim honest.
    """
    import threading
    import time as _time

    import jax
    import jax.numpy as jnp

    from ..parallel import mesh as meshlib
    from ..parameter.kv_vector import KVVector
    from ..parameter.replica import ReplicaManager
    from ..serving import (
        DegradedError,
        PullRequest,
        RejectedError,
        ServeConfig,
        ServeFrontend,
    )
    from ..system import faults
    from ..system.heartbeat import HeartbeatCollector, HeartbeatReport
    from ..system.recovery import RecoveryCoordinator

    mesh = _mesh()
    seed = 7
    k = 4
    num_slots = 1 << (10 if smoke else 12)
    key_space = 1 << 16
    n_per_batch = 64
    # the stream must OUTLIVE detection in every mode: the drill's
    # whole point is recovery under live load, so the trainer has to
    # still be pushing when the handler parks it. Post-kill batches x
    # (>=4ms pacing) must exceed hb_timeout + poll + margin — with
    # 100+ post-kill batches at >=4ms the park is guaranteed even in
    # smoke (the record's trainer_parked field pins it in CI).
    n_batches = 120 if smoke else 240
    kill_at = n_batches // 6
    hb_timeout = 0.3

    def batch(i: int):
        return _drill_batch(seed, i, key_space, n_per_batch, k)

    def push_and_ack(kv, i: int) -> int:
        keys, vals = batch(i)
        ts = kv.push(kv.request(channel=0), keys=keys, values=vals)
        kv.executor.wait(ts, timeout=60)
        return ts

    # -- the undisturbed reference trajectory (also warms every jit:
    # push scatter-add, gather, snapshot copy — so compile stalls can't
    # eat the drill's heartbeat margin) --
    kv_ref = KVVector(
        mesh=mesh, k=k, num_slots=num_slots, hashed=True, name="drill_ref"
    )
    for i in range(n_batches):
        push_and_ack(kv_ref, i)
    t_ref = np.array(kv_ref.table(0, copy=True))
    kv_ref.executor.stop()

    # -- the drilled store + chaos-plane wiring --
    faults.reset()
    # flight recorder (telemetry/blackbox.py): armed for the whole
    # drill so the shard death auto-captures a diagnostic bundle with
    # the pre-death evidence still in the rings. Per-node recorders for
    # the drill's logical nodes; min capture interval dropped so the
    # death trigger is never rate-limit-suppressed by an earlier
    # capture. The bench sink is parked around the drill
    # (attach_recovery) — the tee records into memory only. Cleanup is
    # TARGETED, not a global reset: the drill restores exactly the
    # state it touched (its recorders, the interval, its tee), so an
    # enclosing bench run's bundle deque — which attach_blackbox
    # discloses as bundles_captured — survives the drill.
    from ..telemetry import alerts as alerts_mod
    from ..telemetry import blackbox
    from ..telemetry import registry as telemetry_registry

    prev_min_interval = blackbox.set_min_interval(0.0)
    was_armed = blackbox.installed_recorder() is not None
    blackbox.arm()
    blackbox.recorder("W0").clear()  # a prior drill in this process
    blackbox.recorder("S0").clear()  # must not leak into this bundle
    node_alerts = None
    if telemetry_registry.enabled():
        node_alerts = alerts_mod.AlertManager(
            [r for r in alerts_mod.default_rules()
             if r.name == "node_deaths"]
        )
        node_alerts.evaluate()  # baseline sample: rate needs a window
    # independently-metered update accounting (the learning truth
    # plane's progress side): baseline the parameter plane's push-key
    # counter for the drilled store BEFORE it exists, so the post-drill
    # delta is exactly this drill's pushed keys
    push_tel = None
    push_keys0 = 0.0
    if telemetry_registry.enabled():
        from ..telemetry.instruments import parameter_instruments

        push_tel = parameter_instruments(
            telemetry_registry.default_registry()
        )["push_keys"]
        push_keys0 = push_tel.value(store="drill_live", channel=0)
    kv = KVVector(
        mesh=mesh, k=k, num_slots=num_slots, hashed=True, name="drill_live"
    )
    rm = ReplicaManager()
    rm.backup_consistent(kv)  # a snapshot exists before any fault can
    rm.start_periodic(kv, interval_s=0.04)

    collector = HeartbeatCollector(timeout=hb_timeout)
    rc = RecoveryCoordinator(collector, handler_retry=None)  # replay is
    # not idempotent: a partial replay retried would double-apply, so
    # the drill's handler runs exactly once and fails loudly instead

    fe = ServeFrontend(
        kv,
        ServeConfig(
            replica="fallback",  # live-first reads; replica = degraded path
            replica_refresh_s=0.15,
            live_pull_deadline_s=2.0,
            degraded_max_staleness_s=60.0,
            workers=2,
            max_queue_depth=256,
        ),
    ).start()
    rng = np.random.default_rng(seed + 1)
    u = rng.random((128, 16))
    pool = (u * u * u * key_space).astype(np.int64)  # hot-headed draws
    fe.submit(PullRequest(keys=pool[0])).result(30)  # warm the pull lane

    counts = {"ok": 0, "shed": 0, "failed": 0}  # serve-thread-only writes
    stop_serve = threading.Event()

    def serve_loop() -> None:
        i = 0
        while not stop_serve.is_set():
            try:
                fe.submit(PullRequest(keys=pool[i % len(pool)])).result(10)
                counts["ok"] += 1
            except RejectedError:
                counts["shed"] += 1
            except Exception:  # DegradedError and organic failures both
                counts["failed"] += 1  # count here; degraded SUCCESSES
                # are counted by the frontend (degraded_served)
            i += 1
            _time.sleep(0.002)

    acked: list = []  # (push ts, batch index); guarded-by: ack_lock
    ack_lock = threading.Lock()
    pause_req = threading.Event()
    parked = threading.Event()
    train_err: list = []

    def trainer() -> None:
        try:
            for i in range(n_batches):
                if pause_req.is_set():
                    parked.set()
                    while pause_req.is_set():
                        _time.sleep(0.002)
                    parked.clear()
                ts = push_and_ack(kv, i)
                with ack_lock:
                    acked.append((ts, i))
                _time.sleep(0.004)  # paced: a continuous live stream,
                # not a burst that outruns the detection window
        except BaseException as e:  # surfaced after join
            train_err.append(e)

    stop_beat = threading.Event()

    def beater() -> None:
        beats = 0
        while not stop_beat.wait(0.04):
            collector.report("S0", HeartbeatReport(hostname="S0"))
            collector.report("W0", HeartbeatReport(hostname="W0"))
            beats += 1
            if beats % 3 == 0:
                # periodic metrics-delta samples into the survivors'
                # flight-recorder rings (the report-timer cadence —
                # what a bundle's per-node metrics history is made of)
                for nid in ("W0", "S0"):
                    rec = blackbox.recorder(nid, create=False)
                    if rec is not None:
                        rec.sample_metrics()

    t_kill = [0.0]
    t_detect = [0.0]
    t_recovered = [0.0]
    replayed = [0]
    barrier_used = [-1]
    trainer_parked = [False]

    trainer_t = threading.Thread(target=trainer, name="drill-trainer")

    def on_server_dead(nid: str) -> None:
        if t_kill[0] == 0.0:
            # a loaded host can stall the beater past the heartbeat
            # timeout BEFORE the drill killed anything — that is a
            # false positive, and consuming the exactly-once handler
            # on it would mask the real kill. Revive and keep watching.
            rc.revive(nid)
            return
        t_detect[0] = _time.perf_counter()
        # bounded-delay semantics: survivors stop pushing while the
        # shard recovers (park the trainer between batches)
        pause_req.set()
        while not parked.is_set() and trainer_t.is_alive():
            _time.sleep(0.002)
        # the under-live-load property CI pins: the trainer was ALIVE
        # and parked (not already finished) when recovery began
        trainer_parked[0] = parked.is_set()
        rec_ok = rm.recover(kv, through_executor=True)
        assert rec_ok, "no replica snapshot to recover from"
        barrier = rm.barrier(kv.name).get(0, -1)
        barrier_used[0] = barrier
        with ack_lock:
            replay = [(ts, i) for ts, i in acked if ts > barrier]
        for _, i in replay:  # original order — FP addition must re-run
            push_and_ack(kv, i)  # in the exact sequence it first ran
        replayed[0] = len(replay)
        # the replacement shard is up: store path + heartbeats return
        faults.disarm("serve.pull")
        faults.disarm("serve.refresh")
        faults.disarm("heartbeat.report")
        t_recovered[0] = _time.perf_counter()
        pause_req.clear()

    rc.on_server_dead(on_server_dead)
    collector.report("S0", HeartbeatReport(hostname="S0"))
    collector.report("W0", HeartbeatReport(hostname="W0"))

    serve_t = threading.Thread(target=serve_loop, name="drill-serve")
    beat_t = threading.Thread(target=beater, name="drill-beater")
    degraded_probes = 0
    try:
        beat_t.start()
        rc.start(interval=0.03)
        trainer_t.start()
        serve_t.start()

        # phase 1 (healthy): run until the kill point has been ACKED
        while True:
            with ack_lock:
                n_acked = len(acked)
            if n_acked >= kill_at or train_err:
                break
            _time.sleep(0.005)
        if train_err:
            raise train_err[0]

        # phase 2 (kill): the dead shard's backup stream stops FIRST —
        # a crashed node cannot keep snapshotting — then make sure at
        # least one acked update postdates the final barrier (the
        # replay set must be provably non-empty)
        rm.stop_periodic()
        barrier_before = rm.barrier(kv.name).get(0, -1)
        replay_deadline = _time.perf_counter() + 30
        while True:
            with ack_lock:
                if any(ts > barrier_before for ts, _ in acked):
                    break
            assert trainer_t.is_alive() and (
                _time.perf_counter() < replay_deadline
            ), "no acked update ever postdated the final backup barrier"
            _time.sleep(0.002)
        faults.arm("heartbeat.report", kind="silence", match="S0")
        faults.arm("serve.pull", kind="raise")
        faults.arm("serve.refresh", kind="raise")
        t_kill[0] = _time.perf_counter()
        # wipe the shard through the executor (the replacement starts
        # empty; the submitted step serializes with in-flight pushes)
        zeros = jax.device_put(
            jnp.zeros((kv.num_slots, kv.k), kv.dtype),
            meshlib.table_sharding(kv.mesh),
        )
        kv.executor.wait(
            kv.submit(lambda: kv.set_table(0, zeros), kv.request(channel=0)),
            timeout=60,
        )
        # deterministic degraded evidence: requests in the dead window
        # must be ANSWERED (stale) — the 503-vs-429 story, measured
        for j in range(3):
            try:
                fe.submit(PullRequest(keys=pool[j])).result(10)
                degraded_probes += 1
            except Exception:
                pass

        # phase 3: detection + recovery run on the coordinator thread;
        # phase 4: the trainer finishes the stream
        deadline = _time.perf_counter() + 90
        while t_recovered[0] == 0.0 and _time.perf_counter() < deadline:
            if node_alerts is not None:
                node_alerts.evaluate()
            _time.sleep(0.005)
        assert t_recovered[0] > 0.0, "recovery never completed"
        # the node_deaths rule sees the coordinator's deaths counter
        # tick and walks pending->firing (for_s=0: one evaluation)
        if node_alerts is not None:
            alert_deadline = _time.perf_counter() + 10
            while (
                "node_deaths" not in node_alerts.firing()
                and _time.perf_counter() < alert_deadline
            ):
                node_alerts.evaluate()
                _time.sleep(0.01)
        trainer_t.join(timeout=120)
        assert not trainer_t.is_alive(), "trainer wedged"
        if train_err:
            raise train_err[0]
    finally:
        try:
            faults.reset()
            rm.stop_periodic()
            stop_serve.set()
            stop_beat.set()
            rc.stop()
            for t in (serve_t, beat_t, trainer_t):
                if t.ident is not None:
                    t.join(timeout=60)
            fe.close()
        finally:
            # grab the death's bundle BY TRIGGER KIND — last_bundle()
            # could be a later capture (a straggling DegradedError from
            # the dead window fires the degraded trigger with the
            # interval still 0) whose rings carry no staleness override
            # for S0
            death_bundle = next(
                (b for b in reversed(blackbox.bundles())
                 if b["trigger"]["kind"] == "node_death"),
                None,
            )
            # targeted cleanup (never a global reset — see the arm
            # comment): the rate-limit override, the drill's per-node
            # recorders, and the drill's tee (only if the drill armed
            # it) must not leak past the drill even when it raises —
            # its OWN nested finally, so a failing teardown step above
            # (a wedged join, a close error) cannot skip it
            blackbox.set_min_interval(prev_min_interval)
            blackbox.drop_recorder("W0")
            blackbox.drop_recorder("S0")
            if not was_armed:
                blackbox.disarm()

    kv.executor.wait_all(pop=False, timeout=60)
    t_drill = np.array(kv.table(0, copy=True))
    fe_stats = fe.stats()
    kv.executor.stop()
    # the shard death's auto-captured diagnostic bundle (the
    # RecoveryCoordinator's node_death trigger): summarized into the
    # record under ``blackbox`` — drill METADATA the bench-diff
    # sentinel never bands (script/bench_diff.py METADATA_SECTIONS)
    blackbox_section: dict = {"captured": death_bundle is not None}
    if death_bundle is not None:
        blackbox_section = blackbox.summarize_bundle(death_bundle)
    if node_alerts is not None:
        st = node_alerts.states().get("node_deaths")
        blackbox_section["node_deaths_alert"] = (
            st.state_name if st is not None else "absent"
        )
    bit_identical = (
        t_ref.dtype == t_drill.dtype
        and t_ref.shape == t_drill.shape
        and t_ref.tobytes() == t_drill.tobytes()
    )
    # the bit-identity claim, independently METERED (PR 15): every key
    # the trainer acked plus every key the handler replayed must show
    # in the parameter plane's own push-key counter for this store —
    # a replay that silently lost (or double-ran) updates would still
    # reconcile bit-identically on idempotent data, but it cannot fool
    # a counter the push path ticks per request
    update_accounting = None
    if push_tel is not None:
        pushed = int(
            push_tel.value(store="drill_live", channel=0) - push_keys0
        )
        expected = (n_batches + replayed[0]) * n_per_batch
        update_accounting = {
            "pushed_keys_metered": pushed,
            "expected_keys": expected,
            "acked_updates": n_batches,
            "replayed_updates": replayed[0],
            "keys_per_batch": n_per_batch,
            "metered_matches": pushed == expected,
        }
        assert update_accounting["metered_matches"], update_accounting

    # -- disarmed-overhead paired check: the SAME push stream with the
    # fault points live-but-disarmed vs check() stubbed out (the
    # no-call-sites counterfactual), back-to-back per rep, median of
    # paired ratios (ROADMAP bench discipline) --
    kv2 = KVVector(
        mesh=mesh, k=k, num_slots=1 << 10, hashed=True, name="drill_ovh"
    )
    okeys, ovals = batch(0)

    def ovh_stream(m: int = 24) -> None:
        for _ in range(m):
            kv2.executor.wait(
                kv2.push(kv2.request(channel=0), keys=okeys, values=ovals)
            )

    ovh_stream()  # warm
    real_check = faults.check
    ratios = []
    reps = 3 if smoke else 5
    for _ in range(reps):
        # both orders inside one rep (disarmed, stripped, stripped,
        # disarmed) so a monotone capacity drift on this flapping host
        # cancels out of the paired ratio instead of biasing it
        t0 = _time.perf_counter()
        ovh_stream()
        disarmed_s = _time.perf_counter() - t0
        faults.check = lambda point, detail=None: None  # stripped arm
        try:
            t0 = _time.perf_counter()
            ovh_stream()
            ovh_stream()
            stripped_s = (_time.perf_counter() - t0) / 2
        finally:
            faults.check = real_check
        t0 = _time.perf_counter()
        ovh_stream()
        disarmed_s = (disarmed_s + (_time.perf_counter() - t0)) / 2
        ratios.append(disarmed_s / max(stripped_s, 1e-9))
    kv2.executor.stop()
    # the stream ratio is hostage to this host's seconds-scale capacity
    # flap (ROADMAP bench discipline), so ALSO time the disarmed check
    # itself — a tight-loop ns/call that a flap cannot fake. This is
    # the per-step cost every fault point adds when nothing is armed.
    n_calls = 200_000
    t0 = _time.perf_counter()
    for _ in range(n_calls):
        faults.check("executor.step")
    check_ns = (_time.perf_counter() - t0) / n_calls * 1e9

    return {
        "config": {
            "n_batches": n_batches,
            "kill_at_batch": kill_at,
            "keys_per_batch": n_per_batch,
            "k": k,
            "num_slots": num_slots,
            "backup_interval_s": 0.04,
            "heartbeat_timeout_s": hb_timeout,
        },
        "detection_ms": round((t_detect[0] - t_kill[0]) * 1e3, 1),
        "recovery_ms": round((t_recovered[0] - t_detect[0]) * 1e3, 1),
        "mttr_ms": round((t_recovered[0] - t_kill[0]) * 1e3, 1),
        "replayed_updates": replayed[0],
        "acked_updates": n_batches,
        "barrier_ts": barrier_used[0],
        "backup_version_used": (rm.meta(kv.name) or {}).get("version"),
        "trainer_parked": trainer_parked[0],
        "trajectory_bit_identical": bool(bit_identical),
        "update_accounting": update_accounting,
        "blackbox": blackbox_section,
        "serve": {
            "requests": counts["ok"] + counts["shed"] + counts["failed"],
            "completed_ok": counts["ok"],
            "degraded_served": fe_stats["degraded_served"],
            "degraded_probes_in_dead_window": degraded_probes,
            "shed": counts["shed"],
            "failed": counts["failed"],
        },
        "disarmed_overhead": {
            "reps": reps,
            "ratio_median": round(float(np.median(ratios)), 3),
            "check_ns_per_call": round(check_ns, 1),
        },
    }


@benchmark("recovery_drill")
def recovery_drill_perf(smoke: bool = False) -> None:
    """The chaos-plane headline (``make chaos-bench``): injected shard
    death under live train+serve load must be detected, degraded
    around, and recovered with zero lost acknowledged updates — the
    post-drill table bit-identical to an undisturbed run. Reported
    times are this host's; the same drill shape runs on chip."""
    out = recovery_drill(smoke)
    assert out["trajectory_bit_identical"], (
        "post-recovery trajectory diverged from the undisturbed run — "
        "acknowledged updates were lost"
    )
    assert out["replayed_updates"] > 0, (
        "drill proved nothing: no acked update postdated the barrier"
    )
    assert out["trainer_parked"], (
        "drill proved nothing: the trainer finished before detection, "
        "so recovery never ran against live load — size n_batches/"
        "pacing so the stream outlives the heartbeat timeout"
    )
    bb = out["blackbox"]
    assert bb.get("captured"), (
        "shard death did not auto-capture a diagnostic bundle"
    )
    assert bb["nodes"].get("S0", {}).get("stale"), (
        "dead shard S0 is not marked stale in the bundle"
    )
    assert not bb["nodes"].get("W0", {}).get("stale", True), (
        "surviving node W0's ring dump is missing from the bundle"
    )
    assert bb.get("node_deaths_alert", "firing") == "firing", (
        "node_deaths alert never reached firing during the drill"
    )
    report("recovery_detection_ms", out["detection_ms"], "ms")
    report("recovery_recovery_ms", out["recovery_ms"], "ms")
    report("recovery_mttr_ms", out["mttr_ms"], "ms")
    report("recovery_replayed_updates", out["replayed_updates"], "updates")
    report(
        "recovery_serve_degraded",
        out["serve"]["degraded_served"], "requests",
    )
    report(
        "recovery_disarmed_overhead_ratio",
        out["disarmed_overhead"]["ratio_median"], "x",
    )
    report(
        "recovery_disarmed_check_ns",
        out["disarmed_overhead"]["check_ns_per_call"], "ns/call",
    )
    report("recovery_bit_identical", 1.0, "bool")
    report(
        "recovery_bundle_ring_nodes", len(bb.get("nodes", {})), "nodes"
    )


def _sparse_touch_pattern(p: int, u: int, seed: int = 0):
    """A realistic deduped-touch draw for the sparse-update A/B: sorted
    unique slot ids (prep's np.unique output shape) for ~7/8 of the
    padded width, sentinel-style tail (clipped, ``ok`` False) for the
    rest — the localize contract apply_state_rows sees."""
    rng = np.random.default_rng(seed)
    live = rng.choice(p, min(u - u // 8, p // 2), replace=False)
    rel = np.full(u, p - 1, np.int32)
    rel[: len(live)] = np.sort(live).astype(np.int32)
    ok = np.zeros(u, bool)
    ok[: len(live)] = True
    g = rng.normal(size=u).astype(np.float32)
    return rel, ok, g, len(live)


def ftrl_sparse_ab(smoke: bool = False) -> dict:
    """XLA-rows vs fused-kernel A/B for the sparse-touched FTRL update
    (the ``update='sparse'`` big-table path, ops/ftrl_sparse.py).

    Both arms run the DONATED form (the production configuration: the
    fused step donates the table, so the kernel's in-place aliasing is
    copy-free) over identical state and touch patterns:

    - ``xla_rows`` — the gather→apply→scatter rows formulation
      (``ftrl_sparse_rows_ref``, today's apply_state_rows path): four
      separate XLA dispatches with intermediate row vectors.
    - ``fused``    — the Pallas gather→update→scatter kernel: one pass,
      scalar-prefetched row ids, double-buffered row DMAs, in-place
      write-back. Off-TPU this arm falls back to the same rows path
      (``fused_is_fallback: true`` — the A/B is then a record-shape
      smoke, not a speedup claim; re-measure on chip).

    Arms alternate back-to-back and the speedup quotes the MEDIAN of
    paired ratios (this host's CPU capacity flaps seconds-scale — the
    PR-3 bench discipline). ``hbm_gb_s``/``frac_of_peak`` use the
    disclosed bytes model below; ``onchip_target`` states the roofline
    goal the next device capture is judged against (ROADMAP item 4:
    10x the 0.007-0.015 dense-sweep frac of BENCH_r05)."""
    import time as _time

    import jax

    from ..ops import use_pallas
    from ..ops.ftrl import _LANES
    from ..ops.ftrl_sparse import ftrl_sparse_rows_ref, ftrl_sparse_update

    on_tpu = use_pallas()
    p = 1 << (18 if smoke else 22)
    u = 1 << (11 if smoke else 16)
    kw = dict(alpha=0.1, beta=1.0, l1=0.05, l2=0.0)
    rel_h, ok_h, g_h, n_live = _sparse_touch_pattern(p, u)
    rows_touched = len(np.unique(rel_h[ok_h] // _LANES))
    rng = np.random.default_rng(1)
    z0 = rng.normal(size=p).astype(np.float32)
    n0 = np.abs(rng.normal(size=p)).astype(np.float32)
    rel = jax.device_put(rel_h)
    ok = jax.device_put(ok_h)
    g = jax.device_put(g_h)

    arms = {
        "xla_rows": jax.jit(
            # the touch pattern ascends with its padding behind it, so
            # this arm makes the order promise a one-shard step makes
            lambda z, n: ftrl_sparse_rows_ref(
                z, n, rel, ok, g, rows_ascend=True, **kw
            ),
            donate_argnums=(0, 1),
        ),
        "fused": jax.jit(
            lambda z, n: ftrl_sparse_update(
                z, n, rel, ok, g, **kw, force_pallas=on_tpu
            ),
            donate_argnums=(0, 1),
        ),
    }
    boxes = {
        name: [jax.device_put(z0.copy()), jax.device_put(n0.copy())]
        for name in arms
    }
    for name, fn in arms.items():  # compile + warm, untimed
        boxes[name] = list(fn(*boxes[name]))
        jax.block_until_ready(boxes[name][0])

    reps = 3 if smoke else 5
    calls = 2 if smoke else 4
    times = {name: [] for name in arms}
    for _ in range(reps):
        for name, fn in arms.items():
            t0 = _time.perf_counter()
            for _ in range(calls):
                boxes[name] = list(fn(*boxes[name]))
            jax.block_until_ready(boxes[name][0])
            times[name].append((_time.perf_counter() - t0) / calls)
    ratios = sorted(
        x / f for x, f in zip(times["xla_rows"], times["fused"])
    )
    # medians for the headline ms too (not means): one capacity-flap
    # rep would otherwise make the quoted ms pair contradict the
    # paired-median speedup in the same record
    sec = {k: sorted(v)[len(v) // 2] for k, v in times.items()}

    # bytes model (disclosed, doc/PERFORMANCE.md "FTRL roofline"):
    # every indexed access to the f32 tables moves a 512 B 128-lane row
    # granule. fused: fetch + write-back of each DISTINCT touched row,
    # z and √n, plus the in-program [U,128] gradient scatter (write +
    # kernel read). xla_rows: 4 passes (gather z, gather √n, scatter
    # z', scatter √n'), each touching U row granules (duplicates not
    # deduped by XLA), plus the gathered/updated row vectors.
    row_b = _LANES * 4
    fused_bytes = rows_touched * row_b * 2 * 2 + u * row_b * 2
    xla_bytes = 4 * u * row_b + 4 * u * 4
    dev = jax.devices()[0]
    peak = HBM_PEAK_GB_S.get(dev.device_kind)
    fused_gb_s = fused_bytes / sec["fused"] / 1e9
    out = {
        "num_slots": p,
        "uniq_pad": u,
        "live_slots": n_live,
        "rows_touched": rows_touched,
        "backend": jax.default_backend(),
        "device_kind": dev.device_kind,
        "fused_is_fallback": not on_tpu,
        "xla_rows_ms": round(sec["xla_rows"] * 1e3, 3),
        "fused_ms": round(sec["fused"] * 1e3, 3),
        "fused_speedup_median_paired": round(
            ratios[len(ratios) // 2], 3
        ),
        "reps": reps,
        "calls_per_rep": calls,
        "bytes_model": {
            "fused_bytes_per_ministep": int(fused_bytes),
            "xla_rows_bytes_per_ministep": int(xla_bytes),
            "note": "512 B row granule per indexed access; fused = "
            "2 passes x distinct rows x {z,sqrt_n} + [U,128] grad "
            "scatter; xla_rows = 4 single-array passes x U accesses",
        },
        "hbm_gb_s": round(fused_gb_s, 2),
        "xla_rows_hbm_gb_s": round(xla_bytes / sec["xla_rows"] / 1e9, 2),
        "hbm_peak_gb_s": peak,
        "frac_of_peak": (
            round(fused_gb_s / peak, 4) if peak else None
        ),
        # the record-schema statement of the on-chip goal: BENCH_r05
        # measured the dense sweep at ftrl_hbm_frac_of_peak
        # 0.007-0.015; the fused sparse kernel's acceptance bar on the
        # next reachable-device capture is 10x that.
        "onchip_target": {
            "ftrl_hbm_frac_of_peak": ">= 0.07 (10x the 0.007-0.015 "
            "BENCH_r05 dense-sweep capture)",
            "measured_on": "not measured: the first chip run of this bench",
        },
    }
    # XLA-derived bytes cross-check (device truth plane, telemetry/
    # device.py): the hand 512B-granule model above is the TPU DMA
    # story; cost_analysis() is the compiler's own count. The ratio is
    # DISCLOSED, not gated — XLA counts element bytes (no row-granule
    # rounding), so disagreement off-TPU is expected and its size says
    # how much of the hand model is granule overhead vs real traffic.
    from ..telemetry.device import aot_analyze

    analyses = {
        name: aot_analyze(fn, *boxes[name]) for name, fn in arms.items()
    }
    fused_an = analyses.get("fused") or {}
    rows_an = analyses.get("xla_rows") or {}
    if fused_an.get("bytes_accessed"):
        xla_fused_b = fused_an["bytes_accessed"]
        xla_gb_s = xla_fused_b / sec["fused"] / 1e9
        out["bytes_model_cross_check"] = {
            "hand_fused_bytes": int(fused_bytes),
            "xla_fused_bytes_accessed": int(xla_fused_b),
            "hand_over_xla_ratio": round(fused_bytes / xla_fused_b, 3),
            "hand_xla_rows_bytes": int(xla_bytes),
            "xla_rows_bytes_accessed": (
                int(rows_an["bytes_accessed"])
                if rows_an.get("bytes_accessed") else None
            ),
            "xla_fused_hbm_gb_s": round(xla_gb_s, 2),
            "frac_of_peak_xla": (
                round(xla_gb_s / peak, 4) if peak else None
            ),
            "fused_flops": (
                int(fused_an["flops"]) if fused_an.get("flops") else None
            ),
            "donation_aliased": (
                fused_an.get("alias_bytes", 0) > 0
                and not fused_an.get("donation_warned", False)
            ),
            "note": "hand = 512B row-granule DMA model; XLA cost "
            "analysis counts element bytes — the ratio is disclosure, "
            "not a gate (re-judge on a device capture)",
        }
    return out


def flash_cost_crosscheck(smoke: bool = False) -> dict:
    """Flash-attention fwd: hand FLOPs model vs XLA cost analysis.

    The MFU tables (doc/PERFORMANCE.md "Byte-LM training MFU") divide
    by the hand ``4·bh·s²·d`` convention; this probe asks the compiler
    what it actually counted at the same shape and disclosed the ratio
    — the flash half of the bench record's roofline cross-check. Runs
    the XLA formulation on every backend (a Pallas custom call is
    opaque to cost analysis); one timed flush gives the achieved
    TFLOP/s both models imply, with frac-of-peak only where the peak
    table knows the chip."""
    import time as _time

    import jax

    from ..ops.flash_attention import flash_attention
    from ..telemetry.device import aot_analyze
    from . import FLOPS_PEAK_TFLOPS

    bh, d = 4, 64
    s = 256 if smoke else 1024
    rng = np.random.default_rng(0)
    q, k, v = (
        jax.device_put(rng.normal(size=(bh, s, d)).astype(np.float32))
        for _ in range(3)
    )
    fn = jax.jit(
        lambda qq, kk, vv: flash_attention(
            qq, kk, vv, causal=True, use_pallas=False
        )
    )
    hand_flops = 4.0 * bh * s * s * d
    an = aot_analyze(fn, q, k, v) or {}
    jax.block_until_ready(fn(q, k, v))  # compile + warm untimed
    reps = 3
    t0 = _time.perf_counter()
    for _ in range(reps):
        jax.block_until_ready(fn(q, k, v))
    sec = (_time.perf_counter() - t0) / reps
    dev = jax.devices()[0]
    peak = FLOPS_PEAK_TFLOPS.get(dev.device_kind)
    out = {
        "shape_bh_s_d": [bh, s, d],
        "device_kind": dev.device_kind,
        "hand_flops": int(hand_flops),
        "hand_tflops": round(hand_flops / sec / 1e12, 5),
        "xla_path": True,  # cost analysis needs the non-Pallas program
    }
    if an.get("flops"):
        out["xla_flops"] = int(an["flops"])
        out["hand_over_xla_ratio"] = round(hand_flops / an["flops"], 3)
        out["xla_tflops"] = round(an["flops"] / sec / 1e12, 5)
    if an.get("bytes_accessed"):
        out["xla_bytes_accessed"] = int(an["bytes_accessed"])
    if peak:
        out["mfu_hand"] = round(hand_flops / sec / 1e12 / peak, 6)
        if an.get("flops"):
            out["mfu_xla"] = round(an["flops"] / sec / 1e12 / peak, 6)
    else:
        out["mfu_hand"] = None  # CPU host: no faked peak (HBM table rule)
    return out


@benchmark("ftrl_sparse_ab")
def ftrl_sparse_perf(smoke: bool = False) -> None:
    """Sparse-update A/B (see ftrl_sparse_ab). The same dict is
    embedded in every bench.py record under ``ftrl_sparse``."""
    out = ftrl_sparse_ab(smoke)
    report("ftrl_sparse_xla_rows_ms", out["xla_rows_ms"], "ms")
    report("ftrl_sparse_fused_ms", out["fused_ms"], "ms")
    report(
        "ftrl_sparse_fused_speedup",
        out["fused_speedup_median_paired"], "x",
    )
    report("ftrl_sparse_fused_hbm_gb_s", out["hbm_gb_s"], "GB/s")
    # `is not None`, NOT truthiness: a frac that rounds to 0.0 is a
    # catastrophic roofline regression the capture must report
    if out["frac_of_peak"] is not None:
        report(
            "ftrl_sparse_fused_frac_of_peak", out["frac_of_peak"],
            "fraction",
        )


@benchmark("ftrl_chain")
def ftrl_chain_perf(smoke: bool = False) -> None:
    """Dense-formulation chain A/B: 8 chained FTRL updates per
    dispatch, donated form — the measurement the
    ``ops/ftrl.xla_min_slots`` docstring waits for. A single
    non-donated update is confounded twice over: XLA inserts defensive
    whole-table copies for the Pallas aliasing, and the per-dispatch
    floor buries both arms. Chaining 8 updates inside ONE donated
    dispatch amortizes the dispatch floor 8x and gives the kernel its
    production aliasing, so the per-update delta is the formulation
    difference. Emits ``ftrl_dense_{pallas,xla}_2e{K}_chain_*``,
    against which the 2^62 default is re-judged in a chip run (flip
    point = smallest size where the xla per-update median beats
    pallas's)."""
    import jax

    from ..ops import use_pallas
    from ..ops.ftrl import ftrl_update, ftrl_update_ref

    on_tpu = use_pallas()
    chain_len = 8
    kw = dict(alpha=0.1, beta=1.0, l1=0.05, l2=0.0)
    if smoke:
        sizes = (1 << 14,)
    elif on_tpu:
        sizes = (1 << 24, 1 << 26, 1 << 28)
    else:
        sizes = (1 << 18, 1 << 20)

    def make_chain(pallas: bool):
        def chain(z, n, g):
            for _ in range(chain_len):
                if pallas:
                    z, n = ftrl_update(z, n, g, None, **kw,
                                       force_pallas=True)
                else:
                    z, n = ftrl_update_ref(z, n, g, None, **kw)
            return z, n

        return jax.jit(chain, donate_argnums=(0, 1))

    for p in sizes:
        tag = f"2e{p.bit_length() - 1}"
        rng = np.random.default_rng(0)
        z0 = rng.normal(size=p).astype(np.float32)
        n0 = np.abs(rng.normal(size=p)).astype(np.float32)
        g = jax.device_put(rng.normal(size=p).astype(np.float32))
        arms = {"xla": make_chain(False)}
        # off-TPU the forced-Pallas arm cannot run (no interpret in a
        # timed bench); the xla arm still pins the record shape
        if on_tpu:
            arms["pallas"] = make_chain(True)
        for name, fn in arms.items():
            box = [jax.device_put(z0.copy()), jax.device_put(n0.copy())]
            box = list(fn(*box, g))  # compile untimed
            jax.block_until_ready(box[0])

            def once(fn=fn, box=box):
                box[:] = fn(*box, g)
                jax.block_until_ready(box[0])

            sec = timeit(once, 2 if smoke else 5, budget_s=30.0)
            report(f"ftrl_dense_{name}_{tag}_chain_ms", sec * 1e3, "ms")
            report(
                f"ftrl_dense_{name}_{tag}_chain_per_update_ms",
                sec / chain_len * 1e3, "ms",
            )
            # dense sweep traffic: z rw + sqrt_n rw = 16 B/slot/update
            report(
                f"ftrl_dense_{name}_{tag}_chain_gb_s",
                16.0 * p * chain_len / sec / 1e9, "GB/s",
            )


@benchmark("roofline")
def roofline_probe(smoke: bool = False) -> None:
    """``make roofline``: drive the device truth plane end to end on
    the live backend (telemetry/device.py).

    Two representative kernels — a dense FTRL chain (HBM-bound) and a
    flash-attention fwd (FLOPs-bound) — run through instrumented
    wrappers with per-call roofline sampling, so each dispatch lands
    its measured wall time against its own XLA cost analysis. Reports
    achieved GB/s / TFLOP/s per kernel, frac-of-peak where the peak
    tables know the chip (CPU hosts report the achieved rates only —
    the frac is never faked), and the inventory's compile/recompile
    sanity (a steady-shape probe must recompile zero times after its
    first call). The same families are node-labeled on /metrics
    (``ps_device_kernel_*``, ``ps_device_roofline_frac``)."""
    import jax

    from ..ops.flash_attention import flash_attention
    from ..ops.ftrl import ftrl_update_ref
    from ..telemetry import device as device_tel

    inv = device_tel.DeviceInventory()
    inv.set_sampling(1)  # every dispatch timed: this is a measurement run
    rng = np.random.default_rng(0)

    # HBM-bound probe: 8 chained dense FTRL updates in one program
    p = 1 << (14 if smoke else 20)
    kw = dict(alpha=0.1, beta=1.0, l1=0.05, l2=0.0)

    def chain(z, n, g):
        for _ in range(8):
            z, n = ftrl_update_ref(z, n, g, None, **kw)
        return z, n

    ftrl_fn = inv.instrument(
        "roofline_ftrl_chain",
        jax.jit(chain, donate_argnums=(0, 1)),
        donate_argnums=(0, 1),
    )
    box = [
        jax.device_put(rng.normal(size=p).astype(np.float32)),
        jax.device_put(np.abs(rng.normal(size=p)).astype(np.float32)),
    ]
    g = jax.device_put(rng.normal(size=p).astype(np.float32))
    for _ in range(3 if smoke else 5):
        box = list(ftrl_fn(*box, g))
    jax.block_until_ready(box[0])

    # FLOPs-bound probe: flash fwd, XLA formulation (cost-analyzable)
    bh, s, d = 4, 256 if smoke else 1024, 64
    q, k, v = (
        jax.device_put(rng.normal(size=(bh, s, d)).astype(np.float32))
        for _ in range(3)
    )
    flash_fn = inv.instrument(
        "roofline_flash_fwd",
        jax.jit(
            lambda qq, kk, vv: flash_attention(
                qq, kk, vv, causal=True, use_pallas=False
            )
        ),
    )
    for _ in range(3 if smoke else 5):
        jax.block_until_ready(flash_fn(q, k, v))

    snap = inv.snapshot()
    recompiles = sum(
        rec["recompiles"] for rec in snap["functions"].values()
    )
    report("roofline_functions", len(snap["functions"]), "fns")
    report("roofline_steady_recompiles_plus_one", recompiles + 1, "compiles")
    for name, rec in sorted(snap["functions"].items()):
        tl = rec.get("roofline") or {}
        # every guard below is `is not None`, not truthiness — an
        # achieved rate or frac that rounds to 0.0 is a catastrophic
        # regression the capture must report, not omit (PR 8 rule)
        if tl.get("achieved_gb_s") is not None:
            report(f"{name}_gb_s", tl["achieved_gb_s"], "GB/s")
        if tl.get("achieved_tflops") is not None:
            # GFLOP/s (and pct below): report()'s 2-decimal rounding
            # would flatten a CPU-host TFLOP/s figure to 0.0
            report(f"{name}_gflops", tl["achieved_tflops"] * 1e3,
                   "GFLOP/s")
        if tl.get("frac_of_hbm_peak") is not None:
            report(f"{name}_hbm_peak_pct",
                   tl["frac_of_hbm_peak"] * 100.0, "pct")
        if tl.get("mfu") is not None:
            report(f"{name}_mfu_pct", tl["mfu"] * 100.0, "pct")


def rebalance_drill(smoke: bool = False) -> dict:
    """Heat-driven live-repartitioning drill on a forced 8-device mesh
    (doc/PERFORMANCE.md "Declarative partitioning" — the ISSUE's
    one-mesh-every-chip acceptance, embedded in MULTICHIP-style records
    under ``rebalance``).

    The script:

    1. **mesh** — auto-shaping is demonstrated (num_server=3 on 8
       devices becomes 4x2, never 3x2-with-2-idle) and the drill mesh
       (1x8: 8 server shards, so the max/mean imbalance ratio CAN
       exceed the shipped 4.0 threshold) is asserted to use 8/8
       devices, 0 idle.
    2. **parity** — a table spanning 2 server shards (4x2) trains
       bit-identically to the single-shard path (4x1) on the same
       data-axis width.
    3. **skew → alert → rebalance** — a live train stream (80% of
       traffic on one shard's keys) feeds the KeyHeat plane; the
       measured imbalance rides the ``ps_learning_shard_imbalance``
       gauge into the SHIPPED ``shard_imbalance`` rule; the attached
       RebalanceController plans from the hot-slot/load-share tables
       and migrates rows online through the consistent-snapshot
       machinery while a ``rebalance.migrate`` delay fault widens the
       journal window (pushes landing mid-move must journal + replay).
    4. **verify** — a closed-loop serve stream across the move
       completes EVERY request (degraded-to-lock-latency allowed,
       errors not); post-rebalance traffic re-measures imbalance below
       the alert threshold; the final base-layout table is
       bit-identical to an undisturbed run; and the live phase compiles
       nothing new (``recompiles_post_warmup == 0``).
    """
    import threading
    import time as _time

    import jax

    from ..parallel import mesh as meshlib
    from ..parallel import partition as partlib
    from ..parameter.kv_vector import KVVector
    from ..system import faults
    from ..system.postoffice import Postoffice
    from ..telemetry import alerts as alerts_mod
    from ..telemetry import device as _device
    from ..telemetry import registry as telemetry_registry
    from ..telemetry.instruments import learning_instruments
    from ..telemetry.learning import KeyHeat

    n_dev = len(jax.devices())
    assert n_dev == 8, (
        f"rebalance drill needs the forced 8-device platform, got "
        f"{n_dev} (run via `make rebalance-bench`)"
    )
    Postoffice.reset()
    faults.reset()
    _device.reset()

    # -- 1. mesh: auto-shape demo + the 1x8 drill mesh, 0 idle --------
    demo = meshlib.make_mesh(num_server=3)  # -> 4x2, never 3x2+2 idle
    assert demo.devices.size == n_dev, dict(demo.shape)
    assert dict(demo.shape) == {meshlib.DATA_AXIS: 4, meshlib.SERVER_AXIS: 2}
    mesh = meshlib.make_mesh(num_data=1, num_server=8)
    mesh_section = {
        "devices_total": n_dev,
        "devices_used": int(mesh.devices.size),
        "devices_idle": n_dev - int(mesh.devices.size),
        "shape": {"data": int(mesh.shape[meshlib.DATA_AXIS]),
                  "server": int(mesh.shape[meshlib.SERVER_AXIS])},
        "auto_shape_demo": {
            "requested_server": 3,
            "chosen": {"data": int(demo.shape[meshlib.DATA_AXIS]),
                       "server": int(demo.shape[meshlib.SERVER_AXIS])},
            "devices_idle": n_dev - int(demo.devices.size),
        },
    }
    assert mesh_section["devices_idle"] == 0, mesh_section
    assert mesh_section["auto_shape_demo"]["devices_idle"] == 0

    k = 4
    keys = np.arange(48, dtype=np.int64)
    hot = keys[:8]  # one server shard's key range (slots 0..7 of 64)
    n_batches = 160 if smoke else 280
    batch_n = 64

    def mk_batch(i: int):
        r = np.random.default_rng(1000 + i)
        pick_hot = r.random(batch_n) < 0.8
        ks = np.where(
            pick_hot,
            r.choice(hot, size=batch_n),
            r.choice(keys[8:], size=batch_n),
        ).astype(np.int64)
        vals = r.normal(size=(batch_n, k)).astype(np.float32)
        return ks, vals

    batches = [mk_batch(i) for i in range(n_batches)]

    def new_store(name: str, m=mesh) -> KVVector:
        kv = KVVector(mesh=m, k=k, num_slots=64, hashed=False, name=name)
        kv.set_keys(0, keys)
        return kv

    def train(kv: KVVector, bs) -> np.ndarray:
        for ks, vs in bs:
            kv.push(kv.request(channel=0), keys=ks, values=vs)
        kv.executor.wait_all(pop=False)
        return kv.get_replica()[0]

    # -- 2. >1-server-shard table trains bit-identically to 1-shard ---
    devs = jax.devices()[:4]
    single = train(
        new_store("reb_1shard",
                  meshlib.make_mesh(num_data=4, num_server=1,
                                    devices=devs)),
        batches[:6],
    )
    multi = train(
        new_store("reb_2shard",
                  meshlib.make_mesh(num_data=4, num_server=2)),
        batches[:6],
    )
    parity_single_multi = single.tobytes() == multi.tobytes()
    assert parity_single_multi, (
        "2-server-shard table diverged from the single-shard run"
    )

    # -- undisturbed reference (doubles as shape warmup for the live
    # run: push [64,k], pull [48], snapshot/install/replay) -----------
    ref = new_store("reb_ref")
    ref_table = train(ref, batches)
    np.asarray(ref.wait_pull(ref.pull(ref.request(channel=0), keys=keys)))
    scratch = new_store("reb_scratch")
    train(scratch, batches[:1])
    scratch.migrate(np.random.default_rng(2).permutation(64))
    np.asarray(
        scratch.wait_pull(
            scratch.pull(scratch.request(channel=0), keys=keys)
        )
    )
    _device.mark_warmup()

    # -- 3. the live phase: skewed train + serve + alert + controller -
    kv = new_store("reb_live")
    heat = KeyHeat(num_slots=kv.num_slots, num_shards=8, top_k=16,
                   decay_every=1 << 30)
    ctl = partlib.RebalanceController(kv, heat)
    reg = telemetry_registry.default_registry()
    gauge = learning_instruments(reg)["shard_imbalance"]
    mgr = alerts_mod.AlertManager(alerts_mod.default_rules(),
                                  registry=reg)
    transitions = []
    mgr.add_listener(
        lambda ev: transitions.append(f"{ev.frm}->{ev.to}")
        if ev.rule == "shard_imbalance" else None
    )
    ctl.attach(mgr)
    # widen the copy window so the serve/train streams demonstrably
    # cross the move (journaled + replayed pushes > 0)
    faults.arm("rebalance.migrate", kind="delay", delay_s=0.25,
               once=True)

    progress = {"t": 0.0, "acked": 0}
    serve_stats = {"ok": 0, "failed": 0}
    stop_serve = threading.Event()

    def serve():
        while not stop_serve.is_set():
            try:
                got = kv.wait_pull(
                    kv.pull(kv.request(channel=0), keys=keys)
                )
                np.asarray(got)
                serve_stats["ok"] += 1
            except Exception:
                serve_stats["failed"] += 1
            _time.sleep(0.001)

    def trainer():
        for i, (ks, vs) in enumerate(batches):
            kv.push(kv.request(channel=0), keys=ks, values=vs)
            progress["acked"] += 1
            heat.note(np.asarray(kv.slots(0, ks)))
            imb = heat.shares().get("imbalance")
            if imb is not None:
                gauge.set(imb)
            progress["t"] = float(i + 1)  # the drill's logical clock
            _time.sleep(0.002)

    serve_t = threading.Thread(target=serve, name="reb-serve")
    train_t = threading.Thread(target=trainer, name="reb-train")
    serve_t.start()
    train_t.start()
    # evaluate the shipped rules on the drill's LOGICAL clock (batch
    # index), so the for_s dwell is deterministic, not host-paced
    while train_t.is_alive():
        mgr.evaluate(now=progress["t"])
        _time.sleep(0.004)
    train_t.join()
    mgr.evaluate(now=progress["t"] + 6.0)  # let the alert resolve
    stop_serve.set()
    serve_t.join(timeout=30)
    kv.executor.wait_all(pop=False)

    # -- 4. verify ----------------------------------------------------
    hist = ctl.history()
    assert len(hist) == 1, (
        f"expected exactly one alert-triggered rebalance, got {hist}"
    )
    rec = dict(hist[0])
    assert kv.layout(0) is not None
    assert rec["journaled_pushes"] > 0 and rec["replayed_pushes"] > 0, (
        "the move missed the live stream: nothing journaled/replayed "
        f"({rec})"
    )
    post_imb = ctl.refresh_post_imbalance()
    assert post_imb is not None and post_imb < ctl.threshold, (
        f"post-rebalance imbalance {post_imb} still over "
        f"{ctl.threshold}"
    )
    assert serve_stats["failed"] == 0 and serve_stats["ok"] > 0, (
        f"serve stream across the migration broke: {serve_stats}"
    )
    live_table = kv.get_replica()[0]
    bit_identical = live_table.tobytes() == ref_table.tobytes()
    assert bit_identical, (
        "post-migration table diverged from the undisturbed run"
    )
    dev_snap = _device.snapshot()
    rpw = dev_snap.get("recompiles_post_warmup")
    assert rpw == 0, (
        f"live rebalance phase compiled new programs: {rpw}"
    )

    return {
        "mesh": mesh_section,
        "rebalance": {
            "alert": {
                "rule": "shard_imbalance",
                "threshold": ctl.threshold,
                "transitions": transitions,
            },
            "imbalance_before": rec["imbalance_before"],
            "predicted_imbalance": rec["predicted_imbalance"],
            "post_rebalance_imbalance": round(float(post_imb), 4),
            "rows_moved": rec["rows_moved"],
            "moves": rec["moves"],
            "migration_seconds": rec["migration_seconds"],
            "journaled_pushes": rec["journaled_pushes"],
            "replayed_pushes": rec["replayed_pushes"],
            "attempts": rec["attempts"],
            "barrier_ts": rec["barrier_ts"],
            "install_ts": rec["install_ts"],
            "acked_pushes": progress["acked"],
            "serve": {
                "requests": serve_stats["ok"] + serve_stats["failed"],
                "completed_ok": serve_stats["ok"],
                "failed": serve_stats["failed"],
            },
            "sharded_vs_single_bit_identical": parity_single_multi,
            "trajectory_bit_identical": bit_identical,
            "recompiles_post_warmup": rpw,
        },
        "device": {
            "recompiles_post_warmup": rpw,
            "backend": dev_snap.get("backend"),
            "device_kind": dev_snap.get("device_kind"),
        },
    }


@benchmark("rebalance")
def rebalance_perf(smoke: bool = False) -> None:
    """`make rebalance-bench`: the heat-driven live-repartitioning
    acceptance drill. Every contract is asserted inside
    :func:`rebalance_drill`; this wrapper reports the headline numbers
    and writes the full record where ``PS_REBALANCE_OUT`` points
    (default ``<tmp>/ps_rebalance.json``) for MULTICHIP-style capture."""
    import json as _json
    import os as _os
    import tempfile as _tempfile

    out_path = _os.environ.get("PS_REBALANCE_OUT") or _os.path.join(
        _tempfile.gettempdir(), "ps_rebalance.json"
    )
    out = rebalance_drill(smoke)
    reb = out["rebalance"]
    report("rebalance_imbalance_before", reb["imbalance_before"], "ratio")
    report("rebalance_post_imbalance", reb["post_rebalance_imbalance"],
           "ratio")
    report("rebalance_rows_moved", reb["rows_moved"], "rows")
    report("rebalance_migration_seconds", reb["migration_seconds"], "s")
    report("rebalance_replayed_pushes", reb["replayed_pushes"], "pushes")
    # serve failures are asserted == 0 inside the drill and recorded in
    # the JSON record; report the completions (always > 0) instead
    report("rebalance_serve_ok", reb["serve"]["completed_ok"], "requests")
    with open(out_path, "w") as f:
        _json.dump({"rebalance_record": out}, f, indent=2)

def _consistency_conf(tau, *, adaptive=False, kkt=False, drop_after=0):
    """One consistency-arm config. The τ arms run the stability-frontier
    workload (standard SGD, square loss, constant α at the edge where
    delayed gradients visibly cost accuracy); the KKT arms run the FTRL
    + L1 workload the filter's threshold is derived from."""
    from ..apps.linear.config import (
        Config,
        LearningRateConfig,
        LossConfig,
        PenaltyConfig,
        SGDConfig,
    )

    conf = Config()
    if kkt:
        conf.penalty = PenaltyConfig(type="l1", lambda_=[0.1])
        conf.learning_rate = LearningRateConfig(
            type="decay", alpha=0.1, beta=1.0
        )
        conf.async_sgd = SGDConfig(
            algo="ftrl", minibatch=128, num_slots=1 << 10, max_delay=tau,
            update="sparse", tau_adaptive=adaptive, kkt_filter=True,
            kkt_drop_after=drop_after,
            kkt_revisit_every=8,
            ingest_workers=1,
        )
    else:
        conf.loss = LossConfig(type="square")
        conf.penalty = PenaltyConfig(type="l2", lambda_=[0.0])
        # α at the delayed-stability frontier: τ=0 converges cleanly,
        # τ=max pays a measured final-loss penalty from stale
        # gradients (the NIPS'14 bounded-delay degradation, made
        # visible on purpose) — the regime where an adaptive τ earns
        # its keep
        conf.learning_rate = LearningRateConfig(
            type="constant", alpha=0.03, beta=1.0
        )
        conf.async_sgd = SGDConfig(
            algo="standard", minibatch=128, num_slots=1 << 10,
            max_delay=tau, tau_adaptive=adaptive,
        )
    return conf


def _consistency_batches(n, directory, num_slots, seed0=0):
    """Planted-regression batches, labeled through the SAME key→slot
    hash the workers use, so every arm sees an identical learnable
    problem with a known optimum."""
    from ..utils.sparse import random_sparse

    rng = np.random.default_rng(7)
    wstar = rng.normal(size=num_slots).astype(np.float32)
    noise = np.random.default_rng(11)
    out = []
    for i in range(n):
        b = random_sparse(128, 1 << 14, 8, seed=seed0 + i, binary=True)
        slots = directory.slots(b.indices)
        rows = b.row_ids()
        xw = np.zeros(b.n, np.float32)
        np.add.at(xw, rows, wstar[np.minimum(slots, num_slots - 1)])
        b.y = (xw / 8.0 + 0.05 * noise.normal(size=b.n)).astype(np.float32)
        out.append(b)
    return out


def _final_loss(worker_name) -> float:
    from ..telemetry import learning as learning_mod

    snap = learning_mod.get_plane(worker_name).snapshot()
    tail = [
        p["loss"] for p in snap["trajectory_tail"][-8:]
        if isinstance(p["loss"], float)
    ]
    return float(np.median(tail)) if tail else float("inf")


def _attach_pull_rtt(worker, rtt_s: float) -> None:
    """Emulate the cross-host weight-pull RTT on snapshot-refresh
    submissions — the latency τ exists to hide (OSDI'14's wait-time
    model: a worker blocks on a fresh pull only when its snapshot has
    aged past the delay bound).

    DISCLOSED in-record as ``emulated_pull_rtt_ms``: on this CPU
    container host and device share the same cores, so the real
    overlap win of bounded staleness cannot physically show (there is
    no idle resource for τ>0 to reclaim — measured here as ±25%
    run-to-run noise around a flat line). The sleep lands exactly
    where a multi-host deployment blocks: at the submit that refreshes
    the pulled snapshot (async_sgd.py's ``do_snapshot``), so τ=0 pays
    it every step, τ=max every τ-th, and the adaptive arm at its
    CURRENT live τ — the loss trajectories stay real measurements,
    untouched by the emulation."""
    import time as _time

    orig = worker._submit_prepped

    def submit(prepped, with_aux: bool = True) -> int:
        tau = worker._effective_tau
        if tau <= 0 or worker._steps_since_snapshot >= tau:
            _time.sleep(rtt_s)
        return orig(prepped, with_aux=with_aux)

    worker._submit_prepped = submit


def _consistency_divergence_drill(mesh, smoke: bool) -> dict:
    """Seeded divergence drill through the CONTROLLER's reaction path:
    a poisoned batch (non-finite labels) NaNs one collected step; the
    learning plane judges it divergent (the shipped ``loss_divergence``
    rule fires on the counter, fake clock), and the adaptive controller
    reacts in the same collect — τ→0, automatic LR backoff, rollback to
    its last healthy snapshot — then the run re-converges on clean
    data. The whole episode lands in ONE flight-recorder bundle: the
    controller's own ``consistency_rollback`` trigger captures while
    the pre-divergence evidence is still in the rings."""
    from ..apps.linear.async_sgd import AsyncSGDWorker
    from ..telemetry import alerts as alerts_mod
    from ..telemetry import blackbox
    from ..telemetry import learning as learning_mod

    rule = next(
        r for r in alerts_mod.default_rules() if r.name == "loss_divergence"
    )
    clock = [0.0]
    mgr = alerts_mod.AlertManager([rule], clock=lambda: clock[0])
    prev_interval = blackbox.set_min_interval(0.0)
    was_armed = blackbox.installed_recorder() is not None
    blackbox.arm()
    blackbox.recorder().clear()  # a prior drill in this process must
    # not leak into this bundle
    conf = _consistency_conf(4, adaptive=True)
    worker = AsyncSGDWorker(conf, mesh=mesh, name="consistency_diverge")
    n_good = 8 if smoke else 12
    bundles0 = len(blackbox.bundles())
    try:
        mgr.evaluate()  # t=0 baseline sample — a rate needs a window
        batches = _consistency_batches(
            n_good + 4, worker.directory, worker.num_slots, seed0=300
        )
        losses = []
        for b in batches[:n_good]:
            ts = worker._submit_prepped(
                worker.prep(b, device_put=False), with_aux=False
            )
            worker.collect(ts)
            losses.append(_final_loss("consistency_diverge"))
        pre_alpha = float(worker.lr.alpha)
        pre_tau = worker._consistency.controller.tau
        bad = batches[n_good]
        bad.y = np.full_like(bad.y, np.float32("inf"))
        ts = worker._submit_prepped(
            worker.prep(bad, device_put=False), with_aux=False
        )
        worker.collect(ts)  # the reaction happens inside this collect
        clock[0] = 5.0
        mgr.evaluate()  # pending → firing in one tick (for_s=0)
        fired = rule.name in mgr.firing()
        post = []
        for b in batches[n_good + 1:]:
            ts = worker._submit_prepped(
                worker.prep(b, device_put=False), with_aux=False
            )
            worker.collect(ts)
            post.append(_final_loss("consistency_diverge"))
        episodes = list(worker._consistency.controller.episodes)
        plane = learning_mod.get_plane("consistency_diverge")
        divergences = dict(plane.snapshot()["divergence"])
        bundles = blackbox.bundles()[bundles0:]
        rollback_bundle = next(
            (
                b for b in bundles
                if b["trigger"]["kind"] == "consistency_rollback"
            ),
            None,
        )
    finally:
        worker.executor.stop()
        blackbox.set_min_interval(prev_interval)
        if not was_armed:
            blackbox.disarm()
    return {
        "good_steps": n_good,
        "loss_before_poison": losses[-1] if losses else None,
        "pre_reaction": {"alpha": pre_alpha, "tau": pre_tau},
        "episodes": episodes,
        "divergence_counts": divergences,
        "alert_fired": bool(fired),
        "post_rollback_losses": [round(x, 6) for x in post],
        "reconverged": bool(post)
        and all(np.isfinite(post))
        and post[-1] <= losses[0],
        "bundle_captured": rollback_bundle is not None,
        "bundle_trigger": (
            dict(rollback_bundle["trigger"]) if rollback_bundle else None
        ),
    }


def consistency_ab(smoke: bool = False) -> dict:
    """Self-driving consistency A/B (ISSUE 20), embedded under
    ``consistency`` in every bench record and run standalone via
    ``make consistency-bench``.

    Three τ arms on ONE workload (the delayed-stability frontier:
    planted regression, constant α where staleness measurably costs
    accuracy), back-to-back paired reps with medians: fixed τ=0
    (serialized, fresh gradients), fixed τ=max (full async overlap,
    stale gradients), and adaptive (the controller earns τ from
    stability). The frontier claim quoted in-record: adaptive ≥ τ=0 on
    e2e throughput AND < τ=max on final loss. Then the KKT significance
    filter off/on on the FTRL+L1 workload it is derived from — shipped
    keys/bytes measured with the suppression counters reconciled
    against ``ps_push_keys_total`` in-record, final-loss delta
    disclosed (the filter is lossy BY DESIGN) — and the seeded
    divergence drill through the controller's backoff + rollback
    reaction. Record METADATA, never banded by the bench-diff sentinel
    (script/bench_diff.py METADATA_SECTIONS)."""
    import time as _time

    from ..apps.linear.async_sgd import AsyncSGDWorker
    from ..parallel import mesh as meshlib
    from ..telemetry import learning as learning_mod
    from ..telemetry import registry as telemetry_registry
    from ..telemetry.instruments import parameter_instruments

    mesh = _learning_mesh()
    tau_max = 8
    n_batches = 24 if smoke else 64
    n_warm = 4
    reps = 1 if smoke else 3
    rtt_s = 0.025  # emulated pull RTT — see _attach_pull_rtt

    # one shared batch list, labeled through the shared hash (every
    # worker with the same num_slots config hashes identically)
    probe = AsyncSGDWorker(
        _consistency_conf(0), mesh=mesh, name="consistency_probe"
    )
    batches = _consistency_batches(
        n_batches, probe.directory, probe.num_slots
    )
    probe.executor.stop()

    arms = {}
    arm_specs = (
        ("tau0", 0, False),
        ("taumax", tau_max, False),
        ("adaptive", tau_max, True),
    )
    for rep in range(reps):
        for arm_name, tau, adaptive in arm_specs:
            name = f"consistency_{arm_name}_{rep}"
            worker = AsyncSGDWorker(
                _consistency_conf(tau, adaptive=adaptive),
                mesh=mesh, name=name,
            )
            _attach_pull_rtt(worker, rtt_s)
            if adaptive and worker._consistency is not None:
                # ramp scaled to the 60-batch window: the production
                # default (+1 per 8 healthy collects,
                # learner/consistency.py STABLE_STEPS) would spend the
                # ENTIRE bench run below cap — disclosed in-record as
                # adaptive_stable_steps
                worker._consistency.controller.stable_steps = 2
            try:
                worker.train(iter(batches[:n_warm]))  # compile warmup
                t0 = _time.perf_counter()
                worker.train(iter(batches[n_warm:]))
                dt = _time.perf_counter() - t0
            finally:
                worker.executor.stop()
            st = learning_mod.get_plane(name).snapshot()["staleness"]
            rec = arms.setdefault(
                arm_name,
                {"tau": tau, "adaptive": adaptive, "reps": [],
                 "final_loss": None, "staleness": None},
            )
            rec["reps"].append(
                round((n_batches - n_warm) * 128 / dt, 1)
            )
            if rep == 0:
                rec["final_loss"] = round(_final_loss(name), 6)
                rec["staleness"] = st
                if adaptive and worker._consistency is not None:
                    rec["controller"] = worker._consistency.snapshot()["tau"]
    for rec in arms.values():
        rec["examples_per_s_median"] = float(np.median(rec["reps"]))

    # paired-rep discipline: each rep ran all arms back-to-back, so
    # the adaptive-vs-τ0 throughput verdict is the median of PER-REP
    # ratios (machine drift cancels pairwise), not a ratio of medians
    pair_ratios = [
        a / b
        for a, b in zip(arms["adaptive"]["reps"], arms["tau0"]["reps"])
    ]
    frontier = {
        "adaptive_vs_tau0_throughput_ratio": round(
            float(np.median(pair_ratios)), 4
        ),
        "adaptive_beats_tau0_throughput": float(
            np.median(pair_ratios)
        ) > 1.0,
        "adaptive_beats_taumax_loss": (
            arms["adaptive"]["final_loss"] < arms["taumax"]["final_loss"]
        ),
        "tau0_loss": arms["tau0"]["final_loss"],
        "taumax_loss": arms["taumax"]["final_loss"],
        "adaptive_loss": arms["adaptive"]["final_loss"],
    }

    # -- KKT significance filter off/on (FTRL + L1, update='sparse') --
    kkt_batches = _consistency_batches(
        12 if smoke else 32, probe.directory, probe.num_slots, seed0=100
    )
    for b in kkt_batches:  # classification labels for the logit loss
        b.y = np.where(b.y > 0, 1.0, -1.0).astype(np.float32)
    kkt = {}
    for arm_name, on in (("off", False), ("on", True)):
        name = f"consistency_kkt_{arm_name}"
        conf = (
            _consistency_conf(2, kkt=True, drop_after=3)
            if on
            else _consistency_conf(2, kkt=True)
        )
        if not on:
            conf.async_sgd.kkt_filter = False
        worker = AsyncSGDWorker(conf, mesh=mesh, name=name)
        # counters are process-global per label set: reconcile against
        # the DELTA so a prior run of this bench in the same process
        # (the test suite smoke-runs every REGISTRY entry) can't skew
        counter0 = 0.0
        if on and telemetry_registry.enabled():
            counter0 = parameter_instruments(
                telemetry_registry.default_registry()
            )["push_keys"].value(store=name, channel=0)
        try:
            worker.train(iter(kkt_batches))
        finally:
            worker.executor.stop()
        entry = {"final_loss": round(_final_loss(name), 6)}
        if on:
            summary = worker._consistency.tracker.summary()
            counter = None
            if telemetry_registry.enabled():
                counter = parameter_instruments(
                    telemetry_registry.default_registry()
                )["push_keys"].value(store=name, channel=0) - counter0
            baseline_nnz = sum(b.nnz for b in kkt_batches)
            entry.update(
                {
                    "accounting": summary,
                    "push_keys_counter": counter,
                    "counter_reconciled": (
                        counter is None or counter == summary["pushed"]
                    ),
                    "suppressed_key_frac": round(
                        summary["suppressed"] / max(1, summary["candidates"]),
                        4,
                    ),
                    "baseline_nnz": baseline_nnz,
                    "dropped_entry_frac": round(
                        summary["dropped_entries"] / max(1, baseline_nnz), 4
                    ),
                }
            )
        kkt[arm_name] = entry
    kkt["loss_delta"] = round(
        kkt["on"]["final_loss"] - kkt["off"]["final_loss"], 6
    )

    return {
        "workload": {
            "n_batches": n_batches,
            "warmup_batches": n_warm,
            "minibatch": 128,
            "num_slots": probe.num_slots,
            "num_shards": meshlib.num_servers(mesh),
            "tau_max": tau_max,
            "reps": reps,
            "emulated_pull_rtt_ms": rtt_s * 1000.0,
            "adaptive_stable_steps": 2,
            "pairing": "back-to-back per rep; verdicts are medians of "
                       "per-rep paired ratios; throughput includes the "
                       "emulated pull RTT on refresh submissions "
                       "(_attach_pull_rtt disclosure), losses are real",
        },
        "tau_arms": arms,
        "frontier": frontier,
        "significance_filter": kkt,
        "divergence_drill": _consistency_divergence_drill(mesh, smoke),
    }


@benchmark("consistency")
def consistency_perf(smoke: bool = False) -> None:
    """`make consistency-bench`: the self-driving consistency A/B.
    Structural contracts assert in every mode (bounded-delay holds per
    arm, the controller widened τ, KKT accounting reconciles against
    ``ps_push_keys_total``, the divergence drill backed off + rolled
    back + re-converged with the episode bundled); the wall-clock
    frontier verdicts (adaptive beats fixed τ=0 on throughput, beats
    fixed τ=max on final loss) assert only on full runs — smoke runs on
    a 2-core CI container where a throughput ordering would be noise."""
    import json as _json
    import os as _os
    import tempfile as _tempfile

    out = consistency_ab(smoke)
    for arm in out["tau_arms"].values():
        assert arm["staleness"]["within_bound"], arm["staleness"]
    ctl = out["tau_arms"]["adaptive"]["controller"]
    assert max(ctl["trace"]) > ctl["trace"][0], (
        f"adaptive controller never widened tau: {ctl['trace']}"
    )
    kkt_on = out["significance_filter"]["on"]
    assert kkt_on["accounting"]["reconciled"], kkt_on
    assert kkt_on["counter_reconciled"], kkt_on
    assert kkt_on["accounting"]["suppressed"] > 0, kkt_on
    drill = out["divergence_drill"]
    assert drill["episodes"] and drill["episodes"][0]["rolled_back"], drill
    assert drill["alert_fired"] and drill["bundle_captured"], drill
    assert drill["reconverged"], drill
    if not smoke:
        assert out["frontier"]["adaptive_beats_tau0_throughput"], (
            out["frontier"]
        )
        assert out["frontier"]["adaptive_beats_taumax_loss"], (
            out["frontier"]
        )
    report(
        "consistency_adaptive_examples_per_s",
        out["tau_arms"]["adaptive"]["examples_per_s_median"],
        "examples/s",
    )
    report(
        "consistency_tau0_examples_per_s",
        out["tau_arms"]["tau0"]["examples_per_s_median"],
        "examples/s",
    )
    report(
        "consistency_adaptive_tau_reached", max(ctl["trace"]), "ministeps"
    )
    report(
        "consistency_kkt_suppressed_keys",
        kkt_on["accounting"]["suppressed"],
        "keys",
    )
    report(
        "consistency_drill_rollbacks", len(drill["episodes"]), "episodes"
    )
    out_path = _os.environ.get("PS_CONSISTENCY_OUT") or _os.path.join(
        _tempfile.gettempdir(), "ps_consistency.json"
    )
    with open(out_path, "w") as f:
        _json.dump({"consistency_record": out}, f, indent=2)

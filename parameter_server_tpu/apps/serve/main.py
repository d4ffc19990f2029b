"""Serving CLI — stand up the request-path frontend over a live table
(and optionally an LM) and drive it with open-loop Poisson load:

    python -m parameter_server_tpu.apps.serve.main \
        [--num-slots N] [--keys-per-request K] [--workers W] \
        [--rate R | --rate-multiplier M] [--duration S] \
        [--admission-rate R] [--max-queue-depth D] \
        [--coalesce-window-ms MS] [--replica full|hot|off] \
        [--train-while-serving] [--decode] [--gamma G] [--json]

The serving analog of apps/lm's train-and-generate CLI: it synthesizes
a trained-looking FTRL weight table (KVVector, hashed directory),
wraps it in a :class:`~parameter_server_tpu.serving.ServeFrontend`
(admission control → worker pool → read replica → request coalescing),
and reports p50/p99/p99.9 + goodput per offered-load point as JSON
lines (doc/SERVING.md has the knob guide).

``--train-while-serving`` streams concurrent donated pushes into the
live table from a background thread while the load runs — the
demonstration that replica-served reads never contend with (or get
invalidated by) the training push path. ``--decode`` adds a
speculative-decoding LM lane; ``--draft trained`` trains the
(target, draft) byte-model pair on a structured corpus
(:func:`_spec_corpus`), so the reported acceptance rate
reflects a draft that actually tracks its target instead of the
random-init wiring models. ``--batch-slots N`` serves the decode lane
through the continuous batcher (serving/batcher.py) instead of one
sequential call per request.
"""

from __future__ import annotations

import argparse
import json
import sys
import threading
import time

import numpy as np


def _spec_corpus(rng):
    """The structured byte corpus the trained draft learns: a 16-byte
    cycle with 10% uniform noise — regular enough that a tiny draft tracks the target, noisy
    enough that losses stay informative."""
    pat = np.tile(np.arange(97, 113, dtype=np.int32), 1 << 12)
    noise = rng.integers(0, 256, pat.size, np.int32)
    return np.where(rng.random(pat.size) < 0.1, noise, pat)


def _decode_models(draft: str, seed: int):
    """The decode lane's (target, draft) pair. ``draft="random"`` is
    the old wiring (random-init weights, acceptance ~1/vocab);
    ``draft="trained"`` trains both models on the spec_big corpus —
    CPU-scaled shapes of the measured on-chip config — so the
    frontend's acceptance rate means something."""
    import jax

    from ...models.transformer import LMConfig, init_lm

    tcfg = LMConfig(vocab=256, d_model=64, n_heads=4, n_layers=2, d_ff=128)
    dcfg = LMConfig(vocab=256, d_model=32, n_heads=2, n_layers=1, d_ff=64)
    tparams = init_lm(jax.random.PRNGKey(0), tcfg)
    dparams = init_lm(jax.random.PRNGKey(1), dcfg)
    info = {"draft": draft}
    if draft == "trained":
        from ...parallel.mesh import make_mesh
        from ...models.transformer import make_lm_train_step, shard_tokens

        mesh = make_mesh()
        rng = np.random.default_rng(seed)
        corpus = _spec_corpus(rng)
        seq = 64
        losses = {}
        # lr-per-width + enough steps that the pair actually converges
        # on the cycle (undertrained pairs quote accepted_frac ~0 and
        # defeat the point of --draft trained; this recipe lands
        # ~0.85-0.9 in ~15s of CPU)
        for nm, cfg_i, p_i, lr_i, nst in (
            ("target", tcfg, tparams, 0.2, 300),
            ("draft", dcfg, dparams, 0.4, 200),
        ):
            step_i = make_lm_train_step(cfg_i, mesh, lr=lr_i)
            tl = None
            for _ in range(nst):
                starts = rng.integers(0, corpus.size - seq - 1, 8)
                toks = np.stack([corpus[s:s + seq + 1] for s in starts])
                p_i, tl = step_i(p_i, shard_tokens(toks, mesh))
            if not np.isfinite(float(tl)):
                raise RuntimeError(
                    f"--draft trained: {nm} training diverged "
                    f"(loss={float(tl)})"
                )
            losses[f"{nm}_loss"] = round(float(tl), 3)
            if nm == "target":
                tparams = p_i
            else:
                dparams = p_i
        info.update(losses)
    return tparams, tcfg, dparams, dcfg, info


def _decode_lane(gamma: int, models):
    """A speculative-decoding decode_fn over the pair from
    :func:`_decode_models` (sequential: one call per request)."""
    import jax

    from ...models.speculative import speculative_generate

    tparams, tcfg, dparams, dcfg, _ = models

    def decode_fn(req):
        return speculative_generate(
            tparams, tcfg, dparams, dcfg,
            jax.numpy.asarray(req.prompt, jax.numpy.int32), req.steps,
            gamma=gamma, eos_id=req.eos_id,
        )

    return decode_fn


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--num-slots", type=int, default=1 << 18)
    ap.add_argument("--key-space", type=int, default=1 << 24)
    ap.add_argument("--keys-per-request", type=int, default=32)
    ap.add_argument("--workers", type=int, default=2)
    ap.add_argument("--rate", type=float, default=0.0,
                    help="offered load, requests/s (0 = calibrate)")
    ap.add_argument("--rate-multiplier", type=float, nargs="*",
                    default=[0.25, 3.0],
                    help="offered-load points as multiples of the "
                    "calibrated closed-loop capacity (used when --rate "
                    "is 0)")
    ap.add_argument("--duration", type=float, default=3.0)
    ap.add_argument("--admission-rate", type=float, default=-1.0,
                    help="token-bucket accept rate (requests/s); -1 = "
                    "0.6x calibrated capacity, 0 = no rate gate")
    ap.add_argument("--max-queue-depth", type=int, default=64)
    ap.add_argument("--coalesce-window-ms", type=float, default=2.0)
    ap.add_argument("--replica", default="full",
                    choices=("full", "hot", "off"))
    ap.add_argument("--hot-fraction", type=float, default=0.01,
                    help="fraction of the key space snapshotted by the "
                    "hot replica, capped at the request pool's distinct "
                    "keys (--replica hot)")
    ap.add_argument("--train-while-serving", action="store_true",
                    help="stream donated pushes into the live table "
                    "while serving (replica isolation demo)")
    ap.add_argument("--decode", action="store_true",
                    help="add the speculative-decode LM lane")
    ap.add_argument("--draft", default="random",
                    choices=("random", "trained"),
                    help="decode-lane model pair: random-init wiring "
                    "models, or a pair trained on the spec_big corpus "
                    "so acceptance reflects the measured config")
    ap.add_argument("--batch-slots", type=int, default=0,
                    help="serve decode through the continuous batcher "
                    "with this many slots (0 = sequential decode_fn)")
    ap.add_argument("--gamma", type=int, default=4)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--expose-port", type=int, default=None, metavar="PORT",
                    help="serve the cluster metrics plane while the CLI "
                    "runs (telemetry/exposition.py): /metrics (node-"
                    "labeled aggregate incl. the live ps_serve_* "
                    "family), /healthz, /debug/snapshot; the default "
                    "SLO alert rules evaluate against this process — "
                    "overload points past the serve p99 rule show "
                    "ps_alert_state flip live. 0 = ephemeral")
    args = ap.parse_args(argv)

    from ...parameter.kv_vector import KVVector
    from ...serving import (
        DecodeRequest,
        PullRequest,
        ServeConfig,
        ServeFrontend,
        open_loop_bench,
    )
    from ...system.postoffice import Postoffice

    Postoffice.reset()
    po = Postoffice.instance().start()
    exposition = None
    if args.expose_port is not None:
        from ...telemetry.exposition import expose_cluster

        exposition = expose_cluster(
            po, port=args.expose_port, metrics_interval=1.0
        )
        print(f"serve: metrics exposed at {exposition.url}/metrics "
              f"(/healthz, /debug/snapshot)", file=sys.stderr)
    kv = KVVector(
        mesh=po.mesh, k=1, num_slots=args.num_slots, hashed=True,
        name="serve_w",
    )
    rng = np.random.default_rng(args.seed)
    warm = np.unique(rng.integers(0, args.key_space, 1 << 14))
    kv.wait(kv.push(
        kv.request(channel=0), keys=warm,
        values=rng.normal(size=(len(warm), 1)).astype(np.float32),
    ))

    u = rng.random((512, args.keys_per_request))
    pool = (u * u * u * args.key_space).astype(np.int64)  # power-law keys

    def make_request(i: int):
        return PullRequest(keys=pool[i % len(pool)])

    hot_keys = None
    if args.replica == "hot":
        # the hot set is the HEAD of the actual request-key pool (most
        # frequent keys first) — an independent random draw over the
        # 2^24 key space would miss nearly every requested key and demo
        # only the fallthrough path instead of a hot working set
        uniq, counts = np.unique(pool, return_counts=True)
        n_hot = max(1, min(len(uniq), int(args.hot_fraction * args.key_space)))
        hot_keys = uniq[np.argsort(counts, kind="stable")[::-1][:n_hot]]

    models = _decode_models(args.draft, args.seed) if args.decode else None

    def make_batcher():
        from ...serving import BatcherConfig, ContinuousBatcher

        tparams, tcfg, dparams, dcfg, _ = models
        return ContinuousBatcher(
            tparams, tcfg, dparams, dcfg,
            BatcherConfig(
                slots=args.batch_slots, max_prompt=64, max_new=64,
                gamma=args.gamma,
            ),
        )

    def build(admission_rate: float) -> ServeFrontend:
        batched = args.decode and args.batch_slots > 0
        return ServeFrontend(
            kv,
            ServeConfig(
                admission_rate=max(0.0, admission_rate),
                admission_burst=max(1.0, admission_rate / 10),
                max_queue_depth=args.max_queue_depth,
                coalesce_window_s=args.coalesce_window_ms / 1e3,
                replica=args.replica,
                hot_keys=hot_keys,
                workers=args.workers,
            ),
            decode_fn=(
                _decode_lane(args.gamma, models)
                if args.decode and not batched else None
            ),
            batcher=make_batcher() if batched else None,
        ).start()

    def emit(rec: dict) -> None:
        print(json.dumps(rec), flush=True)

    # calibrate capacity closed-loop
    fe = build(0.0)
    for i in range(10):
        fe.submit(make_request(i)).result(30)
    n_cal = 200
    t0 = time.perf_counter()
    for i in range(n_cal):
        fe.submit(make_request(i)).result(30)
    capacity = n_cal / (time.perf_counter() - t0)
    emit({"metric": "serve_closed_loop_capacity", "value": round(capacity, 1),
          "unit": "requests/sec", "replica": args.replica,
          "workers": args.workers})
    fe.close()

    admission = (
        0.6 * capacity if args.admission_rate < 0 else args.admission_rate
    )
    fe = build(admission)

    stop_training = threading.Event()
    trainer = None
    if args.train_while_serving:
        def train_loop():
            i = 0
            while not stop_training.is_set():
                keys = pool[i % len(pool)]
                kv.wait(kv.push(
                    kv.request(channel=0), keys=np.unique(keys),
                    values=np.ones((len(np.unique(keys)), 1), np.float32),
                ))
                i += 1
        trainer = threading.Thread(
            target=train_loop, name="serve-trainer", daemon=True
        )
        trainer.start()

    rates = (
        [args.rate] if args.rate > 0
        else [m * capacity for m in args.rate_multiplier]
    )
    for rate in rates:
        rec = open_loop_bench(
            fe, make_request, rate=rate, duration_s=args.duration,
            seed=args.seed, warmup_requests=5,
        )
        rec["metric"] = "serve_open_loop_point"
        rec["admission_rate"] = round(admission, 1)
        rec["train_while_serving"] = bool(trainer)
        emit(rec)

    if args.decode:
        from ...serving import RejectedError

        def submit_decode(req, deadline_s: float = 30.0):
            # the open-loop overload points just drained the token
            # bucket, so the first decode submits can legitimately see
            # the 429 — honor retry_after_s instead of crashing the CLI
            # on the rejection the subsystem explicitly models
            t_end = time.monotonic() + deadline_s
            while True:
                try:
                    return fe.submit(req)
                except RejectedError as e:
                    if time.monotonic() >= t_end:
                        raise
                    time.sleep(max(e.retry_after_s, 0.05))

        if args.draft == "trained":
            # prompts FROM the corpus the pair was trained on — an
            # acceptance rate quoted on uniform-random bytes would
            # measure the noise floor, not the draft
            corpus = _spec_corpus(np.random.default_rng(args.seed))
            starts = rng.integers(0, corpus.size - 32, 4)
            prompt = np.stack(
                [corpus[s:s + 32] for s in starts]
            ).astype(np.int32)
        else:
            prompt = rng.integers(0, 256, (4, 32)).astype(np.int32)
        t = submit_decode(DecodeRequest(prompt=prompt, steps=32))
        t.result(600)  # compile
        lat = []
        for _ in range(3):
            t = submit_decode(DecodeRequest(prompt=prompt, steps=32))
            t.result(600)
            lat.append(t.latency_s())
        # acceptance measured on the served pair directly (the number
        # that decides whether the draft pays for itself; ~0 for
        # --draft random, high for --draft trained)
        from ...models.speculative import speculative_generate

        tparams, tcfg, dparams, dcfg, draft_info = models
        _, spec_stats = speculative_generate(
            tparams, tcfg, dparams, dcfg, prompt, 32, gamma=args.gamma,
            return_stats=True,
        )
        rec = {
            "metric": "serve_decode_latency_ms",
            "value": round(float(np.median(lat)) * 1e3, 1),
            "unit": "ms", "gamma": args.gamma,
            "tokens_per_request": int(prompt.shape[0]) * 32,
            "accepted_frac": round(float(spec_stats["accepted_frac"]), 3),
            **draft_info,
        }
        if args.batch_slots > 0:
            rec["batcher"] = fe.batcher.stats()
        emit(rec)

    if trainer is not None:
        stop_training.set()
        trainer.join(timeout=60)
    emit({"metric": "serve_frontend_stats", "value": 1, "unit": "ok",
          **fe.stats()})
    fe.close()
    if exposition is not None:
        ok, health = exposition.aux.health()
        emit({"metric": "serve_exposition", "value": 1, "unit": "ok",
              "url": exposition.url, "healthz_ok": ok,
              "alerts_firing": health.get("alerts_firing", [])})
        from ...telemetry.exposition import close_cluster

        close_cluster(exposition)
    return 0


if __name__ == "__main__":
    sys.exit(main())

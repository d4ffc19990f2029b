"""Byte-level LM CLI — train the sequence-parallel transformer on a text
file (or a built-in synthetic corpus) and generate from it:

    python -m parameter_server_tpu.apps.lm.main \
        [--data FILE] [--steps N] [--seq-len S] [--batch B] \
        [--attention ring|ring_flash|ring_zigzag|a2a] [--window W] \
        [--remat] [--bf16] [--moe-every K] [--num-servers T] \
        [--ckpt-dir DIR] [--save-every N] [--resume] \
        [--prompt "text"] [--gen-tokens N] [--temperature T] [--top-k K] \
        [--top-p P] [--n-kv-heads G] [--model-config FILE]

The model family's end-to-end surface, like apps/linear (conf CLI) and
apps/nn: tokens are raw bytes (vocab 256, no tokenizer dependency), the
sequence axis shards over every available device, and every parallelism/
memory knob of models/transformer.py is reachable from the command line.
Without --data it trains on a synthetic periodic-byte corpus so the demo
runs anywhere.

``--model-config FILE`` takes the model from a description file instead
of the width flags (``trainer.model_from_description``: a ``mistral4``
description's latent attention, a ``solar_open2`` description's gated
delta-rule and gated GQA layers or a ``mellum`` description's windowed
and full GQA layers with rotary tables per kind, a dropless top-k expert
layer with or without a shared expert, RMSNorm, an untied head over the
file's vocabulary); bytes are then ids below 256 of that vocabulary.
Such a model trains here; the generation flags refuse it by name,
because the serving forwards have no latent cache, carry no recurrent
state and read one window and one rotary table for every layer.
"""

from __future__ import annotations

import argparse
import sys

import numpy as np


def _load_corpus(path: str | None, rng: np.random.Generator) -> np.ndarray:
    """The training byte stream. Synthetic fallback: a periodic pattern
    with noise — learnable only by attending a full period back."""
    if path:
        data = np.frombuffer(open(path, "rb").read(), np.uint8)
        if data.size < 1 << 12:
            print(f"warning: tiny corpus ({data.size} bytes)", file=sys.stderr)
        return data
    base = rng.integers(0, 256, 64, dtype=np.uint8)
    reps = np.tile(base, 4096)
    noise = rng.integers(0, 256, reps.size, dtype=np.uint8)
    return np.where(rng.random(reps.size) < 0.02, noise, reps)


def main(argv=None) -> int:
    run(argv)
    return 0


def run(argv=None) -> dict:
    """The CLI's body. Returns what a caller in the same process may
    want to look at afterwards (``chip_smoke.py`` checks decode against
    the training forward on the trained weights): ``params``, ``cfg``,
    ``mesh``, ``losses`` as ``[(step, loss)]`` at the report steps, and
    ``generated`` (the decoded token ids, or None without --prompt)."""
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--data", default=None, help="text/bytes file (default: synthetic)")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--seq-len", type=int, default=256)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--d-model", type=int, default=64)
    ap.add_argument("--n-heads", type=int, default=4)
    ap.add_argument(
        "--n-kv-heads", type=int, default=None,
        help="grouped-query attention: K/V heads (< n-heads shrinks the "
        "decode KV cache by the group factor; 1 = MQA; default: n-heads)",
    )
    ap.add_argument("--n-layers", type=int, default=2)
    ap.add_argument("--d-ff", type=int, default=128)
    ap.add_argument(
        "--attention", default="ring_flash",
        choices=("ring", "ring_flash", "ring_zigzag", "a2a"),
        help="sequence-parallel schedule (default ring_flash: the "
        "Pallas flash kernel per ring hop on a TPU, the XLA chunk path "
        "elsewhere)",
    )
    ap.add_argument("--window", type=int, default=None,
                    help="sliding-window span (flash modes)")
    ap.add_argument("--rope", action="store_true",
                    help="rotary position embeddings (parameter-free "
                    "relative positions; default is NoPE)")
    ap.add_argument("--rope-theta", type=float, default=10000.0)
    ap.add_argument("--remat", action="store_true",
                    help="rematerialize layers (jax.checkpoint), keeping "
                    "each layer's input, its expert choices and the flash "
                    "kernel's output and log-sum-exp")
    ap.add_argument("--bf16", action="store_true",
                    help="bfloat16 decoder activations")
    ap.add_argument("--moe-every", type=int, default=0)
    ap.add_argument("--model-config", metavar="FILE", default=None,
                    help="take the model from a description file (the "
                    "published config.json keys, which experts and "
                    "vocabulary rows this program holds) instead of the "
                    "width flags; see chipbench/configs/"
                    "mistral_small4_ep16.json")
    ap.add_argument("--zero1", action="store_true",
                    help="ZeRO-1: shard Adam moments over the data axis "
                    "(per-device optimizer memory / n_data; composes "
                    "with --num-servers tensor parallelism)")
    ap.add_argument("--fsdp", action="store_true",
                    help="FSDP/ZeRO-3: shard the parameters themselves "
                    "over the data axis (grads and Adam moments inherit "
                    "it) — per-device param+grad+optimizer memory / "
                    "n_data; GSPMD all-gathers weights at use and "
                    "reduce-scatters grads; composes with --num-servers "
                    "and --zero1 is implied for the moments")
    ap.add_argument("--kv-cache", choices=("auto", "int8"), default="auto",
                    help="decode KV-cache storage: auto = the compute "
                    "dtype; int8 = per-token quantized cache (half of "
                    "bf16's traffic again; decode is cache-bandwidth-"
                    "bound under GQA). Generation only — training is "
                    "unaffected")
    ap.add_argument("--log-file", metavar="PATH", default=None,
                    help="append one JSON line per report interval "
                    "(step, loss, bits/byte, eval loss when measured, "
                    "tokens/sec, wall time) — machine-readable training "
                    "telemetry beside the printed table")
    ap.add_argument("--profile", metavar="DIR", default=None,
                    help="capture a jax.profiler device trace of the "
                    "training loop into DIR (TensorBoard profile / "
                    "Perfetto format)")
    ap.add_argument("--num-servers", type=int, default=1,
                    help="tensor-parallel axis size: LM weights Megatron-"
                    "split over a 'server' mesh axis (sp x tp on one 2-D "
                    "mesh); must divide the device count")
    ap.add_argument("--optimizer", choices=("adam", "adafactor", "lion"),
                    default="adam",
                    help="adam (default; 2 f32 moments/param), adafactor "
                    "(factored second moment — rows+cols instead of a "
                    "full moment tensor, the low-memory choice beside "
                    "--zero1/--fsdp), or lion (sign momentum, 1 moment)")
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--warmup", type=int, default=0,
                    help="linear LR warmup steps, then cosine decay to "
                    "10%% of --lr by --steps (0 = constant LR)")
    ap.add_argument("--clip-norm", type=float, default=None,
                    help="global-norm gradient clipping")
    ap.add_argument("--grad-accum", type=int, default=1,
                    help="average N microbatch gradients per optimizer "
                    "step (optax.MultiSteps); effective batch = "
                    "--batch * N with unchanged memory per forward")
    ap.add_argument("--eval-every", type=int, default=0,
                    help="evaluate held-out loss every N steps (holds "
                    "out the corpus tail; see --eval-frac)")
    ap.add_argument("--eval-frac", type=float, default=0.1,
                    help="fraction of the corpus tail held out for "
                    "--eval-every (never trained on)")
    ap.add_argument(
        "--steps-per-launch", type=int, default=1,
        help="fuse N sequential optimizer steps into one compiled launch "
        "(lax.scan carries params+opt; identical training trajectory, "
        "N-1 fewer dispatch round trips — the lever for high-latency "
        "links); must divide --steps and --save-every",
    )
    ap.add_argument("--report-every", type=int, default=10)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--ckpt-dir", default=None,
                    help="checkpoint directory (enables save/resume)")
    ap.add_argument("--save-every", type=int, default=0,
                    help="checkpoint every N steps (ref "
                    "save_model_every_n_iter; needs --ckpt-dir)")
    ap.add_argument("--resume", action="store_true",
                    help="resume from the latest checkpoint in --ckpt-dir")
    ap.add_argument("--prompt", default=None,
                    help="generate after training from this text")
    ap.add_argument("--gen-tokens", type=int, default=64)
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--top-k", type=int, default=None)
    ap.add_argument(
        "--top-p", type=float, default=None,
        help="nucleus sampling: keep the smallest probability mass >= "
        "top-p (composes with --top-k; needs --temperature > 0)",
    )
    ap.add_argument(
        "--beam", type=int, default=0, metavar="W",
        help="beam search with W beams instead of greedy/sampled "
        "decoding (prints the best beam; deterministic — ignores "
        "--temperature/--top-k/--top-p)",
    )
    ap.add_argument(
        "--eos-byte", type=int, default=None, metavar="B",
        help="stop-token byte: a generation that emits byte B freezes "
        "('eos then pads'); works with greedy/sampled and --beam",
    )
    args = ap.parse_args(argv)

    from ...utils import compile_cache

    compile_cache.enable()  # before the first jit; this CLI owns its mesh

    import jax
    import optax

    from ...models.transformer import (
        LMConfig,
        lm_generate,
        lm_loss,
        lm_loss_with_targets,
        refuse_serving,
        shard_tokens,
        zigzag_lm_arrays,
    )
    from ...parallel import mesh as meshlib
    from .trainer import (
        build_trainer,
        load_description,
        model_from_description,
    )

    n_dev = len(jax.devices())
    if args.num_servers < 1 or n_dev % args.num_servers:
        ap.error(
            f"--num-servers {args.num_servers} must divide the device "
            f"count ({n_dev})"
        )
    n_data = n_dev // args.num_servers
    mesh = meshlib.make_mesh(num_data=n_data, num_server=args.num_servers)
    try:
        if args.model_config:
            cfg = model_from_description(
                load_description(args.model_config),
                attention=args.attention, remat=args.remat, bf16=args.bf16,
            )
        else:
            cfg = LMConfig(
                vocab=256, d_model=args.d_model, n_heads=args.n_heads,
                n_layers=args.n_layers, d_ff=args.d_ff,
                attention=args.attention,
                window=args.window, remat=args.remat,
                compute_dtype="bfloat16" if args.bf16 else "float32",
                moe_every=args.moe_every, n_kv_heads=args.n_kv_heads,
                rope=args.rope, rope_theta=args.rope_theta,
                kv_cache_dtype=(
                    None if args.kv_cache == "auto" else args.kv_cache
                ),
            )
    except ValueError as e:
        # LMConfig rejects invalid combinations (e.g. --window with
        # --attention a2a); surface them as flag errors, not tracebacks
        ap.error(str(e))
    if args.model_config:
        if args.num_servers > 1 or args.fsdp:
            ap.error(
                "--model-config: the new layer kinds have no tensor-"
                "parallel or FSDP placement yet (--num-servers 1, no "
                "--fsdp)"
            )
        if n_data > 1 and any(a == "kda" for a, _ in cfg.layer_kinds):
            ap.error(
                "--model-config: a 'kda' layer's recurrence is not built "
                f"over a sequence-sharded mesh ({n_data} devices share the "
                "sequence here): run it on one device"
            )
        if args.prompt is not None:
            try:
                refuse_serving(cfg, "--prompt")
            except NotImplementedError as e:
                ap.error(str(e))
    zig = args.attention == "ring_zigzag"
    if args.seq_len % (2 * n_data if zig else n_data):
        ap.error(f"--seq-len must divide by {2 * n_data if zig else n_data}")
    if args.attention == "a2a" and args.n_heads % n_data:
        ap.error(
            f"--attention a2a needs --n-heads divisible by the "
            f"{n_data}-device data axis (got {args.n_heads})"
        )
    # fail flag mistakes BEFORE the training loop, not after it
    if args.temperature < 0:
        ap.error(f"--temperature must be >= 0, got {args.temperature}")
    if args.top_k is not None:
        if args.temperature == 0:
            ap.error("--top-k requires --temperature > 0 (sampling)")
        if not 1 <= args.top_k <= cfg.vocab:
            ap.error(
                f"--top-k must be in [1, {cfg.vocab}], got {args.top_k}"
            )
    if args.top_p is not None:
        if args.temperature == 0:
            ap.error("--top-p requires --temperature > 0 (sampling)")
        if not 0.0 < args.top_p <= 1.0:
            ap.error(f"--top-p must be in (0, 1], got {args.top_p}")
    spl = args.steps_per_launch
    if spl < 1:
        ap.error(f"--steps-per-launch must be >= 1, got {spl}")
    if spl > 1:
        if args.steps % spl:
            ap.error(
                f"--steps-per-launch {spl} must divide --steps {args.steps}"
            )
        if args.save_every and args.save_every % spl:
            ap.error(
                f"--steps-per-launch {spl} must divide --save-every "
                f"{args.save_every} (checkpoints land on launch boundaries)"
            )

    rng = np.random.default_rng(args.seed)
    corpus = _load_corpus(args.data, rng)
    if corpus.size <= args.seq_len + 1:
        ap.error(
            f"corpus has {corpus.size} bytes but --seq-len {args.seq_len} "
            "needs at least seq_len+2"
        )
    if args.grad_accum < 1:
        ap.error(f"--grad-accum must be >= 1, got {args.grad_accum}")
    if args.grad_accum > args.steps:
        ap.error(
            f"--grad-accum {args.grad_accum} exceeds --steps "
            f"{args.steps}: no accumulation window would ever complete, "
            "so the model would never update"
        )
    if args.steps % args.grad_accum:
        ap.error(
            f"--grad-accum {args.grad_accum} must divide --steps "
            f"{args.steps}: a trailing partial window would compute "
            "gradients that never reach the optimizer"
        )
    if args.clip_norm is not None and args.clip_norm <= 0:
        ap.error(f"--clip-norm must be > 0, got {args.clip_norm}")
    if args.warmup and args.warmup >= args.steps:
        ap.error(
            f"--warmup {args.warmup} must be < --steps {args.steps}"
        )
    if args.eval_every < 0:
        ap.error(f"--eval-every must be >= 0, got {args.eval_every}")
    eval_corpus = None
    if args.eval_every:
        if not 0.0 < args.eval_frac < 1.0:
            ap.error(f"--eval-frac must be in (0, 1), got {args.eval_frac}")
        split = int(corpus.size * (1.0 - args.eval_frac))
        corpus, eval_corpus = corpus[:split], corpus[split:]
        if min(corpus.size, eval_corpus.size) <= args.seq_len + 1:
            ap.error(
                f"--eval-frac {args.eval_frac} leaves a split too small "
                f"for --seq-len {args.seq_len} "
                f"(train {corpus.size} / eval {eval_corpus.size} bytes)"
            )
    # LR schedule -> clip -> optimizer -> (optional) microbatch
    # accumulation. The schedule/accumulation counters live in the
    # optimizer state, so checkpoint resume continues the schedule where
    # it left off.
    lr_sched = (
        optax.warmup_cosine_decay_schedule(
            init_value=0.0, peak_value=args.lr,
            warmup_steps=max(1, args.warmup // args.grad_accum),
            decay_steps=max(2, args.steps // args.grad_accum),
            end_value=0.1 * args.lr,
        )
        if args.warmup
        else args.lr
    )
    trainer = build_trainer(
        cfg, mesh, optimizer=args.optimizer, lr=lr_sched,
        clip_norm=args.clip_norm, grad_accum=args.grad_accum,
        steps_per_launch=spl,
    )
    trainer.init(
        args.seed, tensor_parallel=args.num_servers > 1, fsdp=args.fsdp,
        zero1=args.zero1,
    )

    mgr = None
    start_step = 0
    if args.ckpt_dir:
        from ...parameter.replica import CheckpointManager

        mgr = CheckpointManager(args.ckpt_dir)
        if args.resume:
            latest = mgr.latest_step()
            if latest is not None:
                tree = mgr.restore(
                    latest,
                    like={"params": trainer.params, "opt": trainer.opt},
                )
                # restore device_puts every leaf onto the template's
                # sharding — which carries the real training placement
                # (replicated, or Megatron-split under --num-servers)
                trainer.params, trainer.opt = tree["params"], tree["opt"]
                start_step = latest
                print(f"resumed from step {latest}", flush=True)
    elif args.save_every or args.resume:
        ap.error("--save-every/--resume need --ckpt-dir")

    def sample_tokens():
        starts = rng.integers(0, corpus.size - args.seq_len - 1, args.batch)
        return np.stack(
            [corpus[s : s + args.seq_len] for s in starts]
        ).astype(np.int32)

    if spl > 1 and (args.steps - start_step) % spl:
        ap.error(
            f"resumed at step {start_step}: the remaining "
            f"{args.steps - start_step} steps must divide by "
            f"--steps-per-launch {spl}"
        )

    def launch_data():
        """Sharded device arrays for one launch ([spl, ...] when fused)."""
        with trainer.loop_phase("wait_ingest"):
            return trainer.place([sample_tokens() for _ in range(spl)])

    eval_fn = None
    if args.eval_every:
        # fixed held-out batches (never trained on), scored with the
        # same loss the training step uses — zigzag included
        erng = np.random.default_rng(args.seed + 7)
        raw_eval = []
        for _ in range(4):
            starts = erng.integers(
                0, eval_corpus.size - args.seq_len - 1, args.batch
            )
            raw_eval.append(
                np.stack(
                    [eval_corpus[s : s + args.seq_len] for s in starts]
                ).astype(np.int32)
            )
        if zig:
            ev_jit = jax.jit(
                lambda p, t, g, w: lm_loss_with_targets(
                    p, t, g, w, cfg, mesh, "data"
                )
            )
            fixed_eval = [
                tuple(
                    shard_tokens(a, mesh)
                    for a in zigzag_lm_arrays(t, n_data)
                )
                for t in raw_eval
            ]
            eval_fn = lambda p: float(  # noqa: E731
                np.mean([float(ev_jit(p, *tpl)) for tpl in fixed_eval])
            )
        else:
            ev_jit = jax.jit(lambda p, t: lm_loss(p, t, cfg, mesh, "data"))
            fixed_eval = [shard_tokens(t, mesh) for t in raw_eval]
            eval_fn = lambda p: float(  # noqa: E731
                np.mean([float(ev_jit(p, t)) for t in fixed_eval])
            )

    print(f"devices={n_dev} (data={n_data} x server={args.num_servers}) "
          f"attention={cfg.attention} corpus={corpus.size} bytes"
          + (f" (+{eval_corpus.size} held out)" if eval_corpus is not None
             else ""))
    print(f"{'step':>5} {'loss':>9} {'bits/byte':>10}")
    import json as _json
    import time as _time

    from ...utils.profiling import device_trace

    log_f = open(args.log_file, "a") if args.log_file else None
    t_start = _time.perf_counter()
    last_t, last_i = t_start, start_step
    loop_raised = False
    losses = []
    generated = None
    pending = None  # the launch dispatched last, not yet collected
    try:
        with device_trace(args.profile):
            for i in range(start_step + spl, args.steps + 1, spl):
                # the next launch is queued before the last one is waited
                # for, so collecting (the loss, the counters) costs the
                # device nothing
                launch = trainer.submit(launch_data())
                if pending is not None:
                    trainer.collect(pending)
                pending = launch
                report = i % args.report_every < spl or i == args.steps
                ev = None
                rec = None
                if report:
                    ll, _ = trainer.collect(pending)
                    pending = None
                    losses.append((i, ll))
                    print(f"{i:>5} {ll:>9.4f} {ll / np.log(2):>10.4f}",
                          flush=True)
                    # throughput window closes BEFORE any eval below so
                    # held-out evaluation never pollutes tokens_per_sec
                    now = _time.perf_counter()
                    rec = {
                        "step": i,
                        "wall_s": round(now - t_start, 2),
                        "loss": round(ll, 6),
                        "bits_per_byte": round(ll / float(np.log(2)), 6),
                        "tokens_per_sec": round(
                            (i - last_i) * args.batch * args.seq_len
                            / max(now - last_t, 1e-9),
                            1,
                        ),
                    }
                    last_t, last_i = now, i
                if eval_fn is not None and (
                    i % args.eval_every < spl or i == args.steps
                ):
                    ev_t0 = _time.perf_counter()
                    ev = eval_fn(trainer.params)
                    # shift the open window past the eval's wall time
                    last_t += _time.perf_counter() - ev_t0
                    print(
                        f" eval@{i:<4} {ev:>8.4f} {ev / np.log(2):>10.4f}",
                        flush=True,
                    )
                # telemetry: a line per report interval, PLUS a line for
                # any eval measured off the report grid (an eval curve
                # point must never be silently dropped from the log)
                if log_f is not None and (rec is not None or ev is not None):
                    if rec is None:
                        rec = {
                            "step": i,
                            "wall_s": round(
                                _time.perf_counter() - t_start, 2
                            ),
                        }
                    if ev is not None:
                        rec["eval_loss"] = round(float(ev), 6)
                    log_f.write(_json.dumps(rec) + "\n")
                    log_f.flush()
                if mgr is not None and (
                    i == args.steps
                    or (args.save_every and i % args.save_every == 0)
                ):
                    # --ckpt-dir always saves the final step, so a later
                    # --resume has something to find even without
                    # --save-every. Async: the host snapshot is copied
                    # here (donation-safe), the disk write overlaps the
                    # next training steps.
                    mgr.save_async(
                        i, {"params": trainer.params, "opt": trainer.opt}
                    )
    except BaseException:
        # an explicit flag, NOT sys.exc_info(): inside the drain's
        # except handler below exc_info reports the exception BEING
        # HANDLED (always true there), and even read at the top of the
        # finally it reports handled exceptions from CALLER frames —
        # both readings swallowed a save failure on a clean run (exit
        # 0 with the final checkpoint missing)
        loop_raised = True
        raise
    finally:
        if log_f is not None:
            log_f.close()
        if mgr is not None:
            # drain even when the loop raises: the daemon writer thread
            # would otherwise be killed at interpreter exit (the atomic
            # rename in _write means a kill can only ever leave a .tmp
            # dir, but a completed save beats a discarded one)
            try:
                mgr.wait()
            except RuntimeError as e:
                # an async-save failure is the primary error only when
                # the loop exited cleanly — never mask the loop's own
                # exception (or a Ctrl-C) with the drain's
                if not loop_raised:
                    raise
                print(f"async checkpoint failure during shutdown: {e}",
                      file=sys.stderr)

    params = trainer.params
    if args.prompt is not None:
        prompt = np.frombuffer(
            args.prompt.encode("utf-8", "replace") or b"\n", np.uint8
        ).astype(np.int32)[None, :]
        if args.beam:
            from ...models.transformer import lm_beam_search

            beams, scores = lm_beam_search(
                params, prompt, cfg, steps=args.gen_tokens,
                beam_width=args.beam, eos_id=args.eos_byte,
            )
            out = np.asarray(beams)[0, 0]
            note = f"beam {args.beam}, logprob {float(scores[0, 0]):.2f}"
        else:
            out = np.asarray(
                lm_generate(
                    params, prompt, cfg, steps=args.gen_tokens,
                    temperature=args.temperature, top_k=args.top_k,
                    top_p=args.top_p, eos_id=args.eos_byte,
                    key=jax.random.PRNGKey(args.seed + 1),
                )
            )[0]
            note = "greedy" if not args.temperature else "sampled"
        if args.eos_byte is not None:
            # "eos then pads": truncate at the first stop byte inside
            # the GENERATED region so the terminal never sees the pads
            gen_start = prompt.shape[1]
            hits = np.flatnonzero(out[gen_start:] == args.eos_byte)
            if hits.size:
                out = out[: gen_start + hits[0] + 1]
        generated = out
        text = bytes(out.astype(np.uint8)).decode("utf-8", "replace")
        print(f"--- generation ({args.gen_tokens} tokens, {note}) ---")
        print(text)
    return {
        "params": params, "cfg": cfg, "mesh": mesh, "losses": losses,
        "generated": generated,
    }


if __name__ == "__main__":
    sys.exit(main())

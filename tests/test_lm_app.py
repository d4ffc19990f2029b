"""LM app CLI (apps/lm/main.py): end-to-end train + generate in-process
on the virtual mesh, across attention modes."""

import numpy as np
import pytest

from parameter_server_tpu.apps.lm.main import main

# Promoted to the slow tier (PR 2, per the PR-1 ROADMAP note): the
# shard_map-shim unlock made the full 'not slow' suite overrun the
# 870s tier-1 budget on a 2-core host. Run via `pytest -m slow`.
pytestmark = pytest.mark.slow


def run_cli(capsys, *extra):
    rc = main(
        [
            "--steps", "30", "--seq-len", "64", "--batch", "4",
            "--d-model", "32", "--n-heads", "2", "--d-ff", "64",
            "--report-every", "10", "--prompt", "ab", "--gen-tokens", "8",
            *extra,
        ]
    )
    assert rc == 0
    out = capsys.readouterr().out
    rows = [
        line.split() for line in out.splitlines()
        if line and line.split()[0].isdigit()
    ]
    losses = [float(r[1]) for r in rows]
    return out, losses


def resume_cli(capsys, ck, *extra):
    """Resume from a run_cli checkpoint (same base hyperparameters —
    repeated flags in ``extra`` override, argparse last-wins) and train
    to step 40."""
    rc = main(
        [
            "--steps", "40", "--seq-len", "64", "--batch", "4",
            "--d-model", "32", "--n-heads", "2", "--d-ff", "64",
            "--report-every", "5", "--ckpt-dir", ck, "--resume", *extra,
        ]
    )
    assert rc == 0
    return capsys.readouterr().out


def _write_corpus(tmp_path):
    """8-periodic corpus shared by the corpus-consuming CLI tests: the
    model should get well under 1 bit/byte on it fast."""
    f = tmp_path / "corpus.txt"
    f.write_bytes(b"abcdefgh" * 4096)
    return f


def test_lm_cli_trains_and_generates(mesh8, capsys):
    out, losses = run_cli(capsys)
    assert losses[-1] < losses[0], losses
    assert "--- generation" in out


def test_lm_cli_beam_and_eos(mesh8, capsys):
    out, losses = run_cli(capsys, "--beam", "3", "--eos-byte", "10")
    assert losses[-1] < losses[0], losses
    assert "beam 3, logprob" in out


def test_lm_cli_moe_generates(mesh8, capsys):
    """Round 4: MoE models generate from the CLI (the old path printed
    'generation skipped' and exited)."""
    out, _ = run_cli(capsys, "--moe-every", "2")
    assert "--- generation" in out
    assert "generation skipped" not in out


def test_lm_cli_zigzag_mode(mesh8, capsys):
    out, losses = run_cli(capsys, "--attention", "ring_zigzag")
    assert losses[-1] < losses[0], losses
    assert "--- generation" in out


def test_lm_cli_flash_window_remat(mesh8, capsys):
    out, losses = run_cli(
        capsys, "--attention", "ring_flash", "--window", "16", "--remat",
    )
    assert losses[-1] < losses[0], losses


def test_lm_cli_corpus_file(mesh8, capsys, tmp_path):
    out, losses = run_cli(capsys, "--data", str(_write_corpus(tmp_path)))
    assert losses[-1] < 0.7 * losses[0], losses


@pytest.mark.parametrize("extra", [(), ("--num-servers", "2")])
def test_lm_cli_checkpoint_resume(mesh8, capsys, tmp_path, extra):
    """Save, resume, and TRAIN ON (restored leaves must land on the
    template's training placement — replicated, or Megatron-split under
    --num-servers; ref save_model_every_n_iter parity)."""
    ck = str(tmp_path / "ck")
    run_cli(capsys, "--ckpt-dir", ck, *extra)  # saves the final step (30)
    out = resume_cli(capsys, ck, *extra)
    assert "resumed from step 30" in out
    rows = [
        line.split() for line in out.splitlines()
        if line and line.split()[0].isdigit()
    ]
    # trains exactly the REMAINING steps (35, 40 reported)
    assert [int(r[0]) for r in rows] == [35, 40], rows


def test_lm_cli_async_save_failure_fails_clean_run(mesh8, tmp_path,
                                                   monkeypatch):
    """An async checkpoint-save failure on a CLEAN run must propagate
    (the '--ckpt-dir always saves the final step' resume contract) —
    r3 advisor: sys.exc_info() read INSIDE the except handler always
    saw the drain's own RuntimeError, so the CLI swallowed the failure
    and exited 0 with the final checkpoint missing."""
    from parameter_server_tpu.parameter import replica

    def boom(self, path, host_tree):
        raise OSError("disk full (simulated)")

    monkeypatch.setattr(replica.CheckpointManager, "_write", boom)
    with pytest.raises(RuntimeError, match="async checkpoint save failed"):
        main(
            [
                "--steps", "4", "--seq-len", "64", "--batch", "2",
                "--d-model", "32", "--n-heads", "2", "--d-ff", "64",
                "--report-every", "4",
                "--ckpt-dir", str(tmp_path / "ck"),
            ]
        )


def test_lm_cli_tensor_parallel(mesh8, capsys):
    # sp x tp on one 2-D mesh: 4 data x 2 server, flash attention
    out, losses = run_cli(
        capsys, "--num-servers", "2", "--attention", "ring_flash"
    )
    assert losses[-1] < losses[0], losses
    assert "data=4 x server=2" in out
    with pytest.raises(SystemExit):  # 3 does not divide 8
        main(["--steps", "2", "--seq-len", "64", "--num-servers", "3"])


def test_lm_cli_fsdp(mesh8, capsys, tmp_path):
    """--fsdp through the CLI surface: trains, composes with --zero1 and
    --num-servers (the sharded params serve as the checkpoint restore
    template), and resume trains on from FSDP-placed leaves."""
    out, losses = run_cli(capsys, "--fsdp", "--zero1")
    assert losses[-1] < losses[0], losses
    ck = str(tmp_path / "ck")
    run_cli(capsys, "--fsdp", "--num-servers", "2", "--ckpt-dir", ck)
    out = resume_cli(capsys, ck, "--fsdp", "--num-servers", "2")
    assert "resumed from step 30" in out


def test_lm_cli_log_file(mesh8, capsys, tmp_path):
    """--log-file appends one JSON line per report interval (full
    telemetry), plus a line for every eval measured OFF the report
    grid — no eval-curve point is ever dropped from the log."""
    import json

    log = tmp_path / "train.jsonl"
    run_cli(  # report grid 10/20/30; eval grid 6/12/18/24/30
        capsys, "--log-file", str(log), "--eval-every", "6",
        "--data", str(_write_corpus(tmp_path)),
    )
    recs = [json.loads(ln) for ln in log.read_text().splitlines()]
    assert recs, "no telemetry written"
    assert [r["step"] for r in recs] == sorted(r["step"] for r in recs)
    full = [r for r in recs if "tokens_per_sec" in r]
    assert [r["step"] for r in full] == [10, 20, 30], full
    for r in full:
        assert {"step", "loss", "bits_per_byte", "wall_s"} <= set(r)
        assert r["tokens_per_sec"] > 0
    evals = [r["step"] for r in recs if "eval_loss" in r]
    assert evals == [6, 12, 18, 24, 30], evals  # off-grid ones kept


def test_lm_cli_profile_trace(mesh8, capsys, tmp_path):
    """--profile captures a device trace of the training loop (works on
    the CPU backend too — the capture machinery is backend-agnostic)."""
    prof = tmp_path / "trace"
    out, losses = run_cli(capsys, "--profile", str(prof))
    assert losses[-1] < losses[0], losses
    captured = [
        p for p in prof.rglob("*") if p.is_file()
    ]
    assert captured, "no trace artifacts written"


@pytest.mark.parametrize(
    "opt,extra",
    [
        # d-model 128: optax.adafactor only factors dims >= its
        # min_dim_size_to_factor (128), so the emb [256, 128] creates
        # the real v_row/v_col factored state — the point of the flag —
        # and resume round-trips it
        ("adafactor", ("--d-model", "128")),
        ("lion", ()),
    ],
)
def test_lm_cli_optimizer_choice(mesh8, capsys, tmp_path, opt, extra):
    """--optimizer variants train AND resume (their state trees differ
    from adam's — the checkpoint template walk must rebuild each)."""
    ck = str(tmp_path / "ck")
    out, losses = run_cli(
        capsys, "--optimizer", opt, "--ckpt-dir", ck, *extra
    )
    assert losses[-1] < losses[0], (opt, losses)
    out = resume_cli(capsys, ck, "--optimizer", opt, *extra)
    assert "resumed from step 30" in out


def test_lm_cli_a2a_mode(mesh8, capsys):
    # a2a needs n_heads divisible by the 8-device axis
    out, losses = run_cli(capsys, "--attention", "a2a", "--n-heads", "8")
    assert losses[-1] < losses[0], losses


def test_lm_cli_training_hygiene_flags(mesh8, capsys):
    """Warmup-cosine LR, global-norm clipping, and microbatch gradient
    accumulation run together and still train."""
    out, losses = run_cli(
        capsys, "--warmup", "5", "--clip-norm", "1.0", "--grad-accum", "2",
    )
    assert losses[-1] < losses[0], losses
    assert "--- generation" in out


def test_lm_cli_eval_holdout(mesh8, capsys, tmp_path):
    """--eval-every scores fixed held-out batches the model never
    trains on, printed alongside the train rows."""
    out, losses = run_cli(
        capsys, "--data", str(_write_corpus(tmp_path)), "--eval-every",
        "10",
    )
    assert "held out" in out
    evals = [
        float(line.split()[1])
        for line in out.splitlines()
        if line.strip().startswith("eval@")
    ]
    assert len(evals) >= 3, out
    assert all(np.isfinite(e) for e in evals)
    # periodic text: held-out loss must drop along with train loss
    assert evals[-1] < evals[0], evals


def test_lm_cli_resume_with_schedule_and_accum(mesh8, capsys, tmp_path):
    """The LR-schedule and accumulation counters live in the optimizer
    state: a resumed run must rebuild the same tx and restore onto it."""
    ck = str(tmp_path / "ck")
    hygiene = ["--warmup", "5", "--clip-norm", "1.0", "--grad-accum", "2"]
    run_cli(capsys, "--ckpt-dir", ck, *hygiene)
    rc = main(
        [
            "--steps", "40", "--seq-len", "64", "--batch", "4",
            "--d-model", "32", "--n-heads", "2", "--d-ff", "64",
            "--report-every", "5", "--ckpt-dir", ck, "--resume", *hygiene,
        ]
    )
    assert rc == 0
    assert "resumed from step 30" in capsys.readouterr().out


def test_lm_cli_flag_mistakes_fail_fast(mesh8):
    base = ["--steps", "5", "--seq-len", "64", "--batch", "2"]
    with pytest.raises(SystemExit):  # a2a heads not divisible by devices
        main([*base, "--attention", "a2a", "--n-heads", "2"])
    with pytest.raises(SystemExit):  # top_k without sampling
        main([*base, "--top-k", "3"])
    with pytest.raises(SystemExit):  # negative temperature
        main([*base, "--temperature", "-1"])
    with pytest.raises(SystemExit):  # launch must divide the step budget
        main([*base, "--steps-per-launch", "3"])
    with pytest.raises(SystemExit):  # warmup must fit inside the run
        main([*base, "--warmup", "5"])
    with pytest.raises(SystemExit):  # accumulation must be positive
        main([*base, "--grad-accum", "0"])
    with pytest.raises(SystemExit):  # ...and fit inside the run
        main([*base, "--grad-accum", "10"])
    with pytest.raises(SystemExit):  # ...and divide it (no partial window)
        main([*base, "--grad-accum", "2"])
    with pytest.raises(SystemExit):  # negative clip flips gradients
        main([*base, "--clip-norm", "-1"])
    with pytest.raises(SystemExit):  # eval fraction out of range
        main([*base, "--eval-every", "2", "--eval-frac", "1.5"])
    with pytest.raises(SystemExit):  # negative eval cadence
        main([*base, "--eval-every", "-10"])
    with pytest.raises(SystemExit):  # ...and the checkpoint cadence
        main(
            [*base, "--steps", "6", "--steps-per-launch", "3",
             "--save-every", "4", "--ckpt-dir", "/tmp/unused-lm-ckpt"]
        )


@pytest.mark.parametrize("extra", [(), ("--attention", "ring_zigzag")])
def test_lm_cli_scanned_supersteps(mesh8, capsys, extra):
    """--steps-per-launch fuses optimizer steps into scanned launches
    (plain and zigzag three-array layouts): training still converges
    and reports land on launch boundaries."""
    out, losses = run_cli(capsys, "--steps-per-launch", "5", *extra)
    assert losses[-1] < losses[0], losses
    assert "--- generation" in out


def test_lm_cli_tiny_corpus_rejected(mesh8, tmp_path):
    f = tmp_path / "tiny.txt"
    f.write_bytes(b"x" * 32)
    with pytest.raises(SystemExit):
        main(["--steps", "2", "--seq-len", "64", "--data", str(f)])


def test_lm_cli_save_needs_dir(mesh8):
    with pytest.raises(SystemExit):
        main(["--save-every", "5"])


def test_lm_cli_rejects_bad_seq_len(mesh8):
    with pytest.raises(SystemExit):
        main(["--seq-len", "65"])  # not divisible by the 8-device axis


@pytest.mark.parametrize("argv", [
    ["--attention", "a2a", "--window", "8"],   # window needs a flash mode
    ["--window", "0"],                         # window must be >= 1
])
def test_lm_cli_invalid_config_is_a_flag_error(mesh8, argv):
    """LMConfig-rejected combinations surface as argparse errors
    (SystemExit 2), not raw ValueError tracebacks."""
    with pytest.raises(SystemExit) as e:
        main(["--steps", "1", *argv])
    assert e.value.code == 2


def test_mfu_queue_configs_trace_and_lower():
    """The utilization-push configs (apps/lm/shapes.mfu_modes — the ONE
    definition chip_smoke.py also takes its widest LM shape from) must
    build and lower at their REAL shapes on a SINGLE-device mesh,
    exactly as one chip runs them: a latent shape bug would otherwise
    surface only on the chip. Abstract tracing only — no
    151M/403M-param allocation."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from parameter_server_tpu.apps.lm.shapes import mfu_modes
    from parameter_server_tpu.models.transformer import (
        LMConfig,
        init_lm,
        make_lm_train_step,
    )
    from parameter_server_tpu.system.postoffice import Postoffice

    modes = mfu_modes()
    assert len(modes) == 6
    # single-device mesh: the modes run on ONE chip, and the
    # per-device chunk shapes (where shape bugs live) must match it
    from jax.sharding import Mesh

    mesh = Mesh(np.asarray(jax.devices()[:1]), ("data",))
    Postoffice.reset()
    try:
        for _name, kw, ov in modes:
            cfg = LMConfig(**kw)
            spl = ov.get("spl", 8)
            params = jax.eval_shape(
                lambda k, c=cfg: init_lm(k, c), jax.random.PRNGKey(0)
            )
            step = make_lm_train_step(
                cfg, mesh, donate=True, steps_per_launch=spl
            )
            toks = jax.ShapeDtypeStruct(
                (spl, ov["batch"], ov["seq"]), jnp.int32
            )
            step.lower(params, toks)  # raises on any shape bug
    finally:
        Postoffice.reset()

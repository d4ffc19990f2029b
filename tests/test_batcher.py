"""Continuous batching (serving/batcher.py): concurrent decode
sessions sharing ONE running speculative-decode call.

The correctness contract pinned here is GREEDY TOKEN PARITY: every
session's output is token-for-token identical to its own sequential
``speculative_generate`` run — regardless of who shared the batch, when
they joined, or who retired mid-flight. Plus the serving-side edges:
the single-owner feeder rule, capacity validation before any slot is
consumed, EOS retiring a slot while the rest keep stepping, admission
shedding while the batch is full, and serve continuity through a
stalled ``rebalance.migrate``."""

import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from parameter_server_tpu.models.speculative import speculative_generate
from parameter_server_tpu.models.transformer import LMConfig, init_lm
from parameter_server_tpu.parameter.kv_vector import KVVector
from parameter_server_tpu.serving import (
    BatcherConfig,
    ContinuousBatcher,
    DecodeRequest,
    RejectedError,
    ServeConfig,
    ServeFrontend,
)
from parameter_server_tpu.system import faults
from parameter_server_tpu.system.postoffice import Postoffice


@pytest.fixture(autouse=True)
def fresh_po():
    Postoffice.reset()
    faults.reset()
    yield
    faults.reset()
    Postoffice.reset()


TCFG = LMConfig(vocab=64, d_model=32, n_heads=2, n_layers=2, d_ff=64)
DCFG = LMConfig(vocab=64, d_model=16, n_heads=2, n_layers=1, d_ff=32)
GAMMA = 2


@pytest.fixture(scope="module")
def models():
    tparams = init_lm(jax.random.PRNGKey(0), TCFG)
    dparams = init_lm(jax.random.PRNGKey(1), DCFG)
    return tparams, dparams


def _batcher(models, slots=4, max_prompt=8, max_new=16):
    tparams, dparams = models
    return ContinuousBatcher(
        tparams, TCFG, dparams, DCFG,
        BatcherConfig(slots=slots, max_prompt=max_prompt,
                      max_new=max_new, gamma=GAMMA),
    )


def _prompt(seed, b, p):
    return np.asarray(
        jax.random.randint(jax.random.PRNGKey(seed), (b, p), 0, TCFG.vocab),
        np.int32,
    )


def _sequential(models, req):
    """The per-session reference: this request decoded ALONE."""
    tparams, dparams = models
    kw = {}
    if req.prompt_lengths is not None:
        kw["prompt_lengths"] = jnp.asarray(req.prompt_lengths)
    if req.eos_id is not None:
        kw["eos_id"] = int(req.eos_id)
    return np.asarray(speculative_generate(
        tparams, TCFG, dparams, DCFG, jnp.asarray(req.prompt),
        int(req.steps), gamma=GAMMA, **kw,
    ))


def _drain(b, handles_done, max_rounds=500):
    for _ in range(max_rounds):
        if b.active_sessions() == 0:
            return
        handles_done.extend(b.step())
    raise AssertionError("batch failed to drain")


def _drain_waves(b, reqs, max_rounds=500):
    """The throughput path under join/leave churn: sessions join in
    waves as slots free (``admit_many``), rounds fuse per dispatch
    (``step_block``). Returns the retired handles."""
    done, pending = [], list(reqs)
    for _ in range(max_rounds):
        wave = []
        while pending and len(wave) < b.free_slots():
            wave.append((pending.pop(0), None))
        assert len(b.admit_many(wave)) == len(wave)
        done.extend(b.step_block())
        if not pending and b.active_sessions() == 0:
            return done
    raise AssertionError("batch failed to drain")


class TestTokenParity:
    def test_identity_under_join_leave_churn(self, models):
        """Six sessions with DIFFERENT lengths and budgets through a
        4-slot batch: late joiners enter as early finishers retire, and
        every output still equals its own solo run."""
        reqs = [
            DecodeRequest(prompt=_prompt(10 + i, 1, 3 + (i % 5)),
                          steps=4 + 3 * (i % 4))
            for i in range(6)
        ]
        b = _batcher(models)
        b.warmup()
        done, pending = [], list(reqs)
        admitted = []
        for _ in range(500):
            while pending and b.free_slots() >= pending[0].prompt.shape[0]:
                admitted.append(b.admit(pending.pop(0)))
            if not pending and b.active_sessions() == 0:
                break
            done.extend(b.step())
        assert len(done) == len(reqs)
        assert b.stats()["joins"] == 6 and b.stats()["retired"] == 6
        for h in admitted:
            np.testing.assert_array_equal(
                h.out, _sequential(models, h.req)
            )

    def test_wave_admit_and_block_step_identity(self, models):
        """The throughput path — admit_many joining mixed requests in
        one fused call (with its pow2 padding) and step_block fusing
        rounds per dispatch — commits exactly the same tokens as the
        one-by-one admit/step path pins above. Mixed per-request eos
        in a wave exercises the per-row eos vector; eos presence also
        forces the block back to single-round stepping."""
        reqs = [
            DecodeRequest(prompt=_prompt(40 + i, 1, 3 + (i % 4)),
                          steps=5 + 2 * (i % 3),
                          eos_id=(63 if i == 2 else None))
            for i in range(7)
        ]
        b = _batcher(models)
        b.warmup()
        done = _drain_waves(b, reqs)
        assert len(done) == len(reqs)
        for h in done:
            np.testing.assert_array_equal(
                h.out, _sequential(models, h.req)
            )

    @pytest.mark.parametrize("slots", [1, 4, 8, 16])
    def test_no_compile_after_warmup_at_each_slot_count(self, models, slots):
        """``warmup()`` claims to be every compilation traffic can
        trigger (the round, the block, one join per power-of-two wave):
        under join/leave churn through the wave path, at each slot
        count, the three jitted entry points gain no cache entry, and
        every session still equals its solo run."""
        from parameter_server_tpu.models import speculative

        jits = (speculative._spec_round_jit,
                speculative._spec_round_block_jit,
                speculative._spec_join_many_jit)
        b = _batcher(models, slots=slots)
        b.warmup()
        compiled = [f._cache_size() for f in jits]
        reqs = [
            DecodeRequest(prompt=_prompt(70 + i, 1, 3 + (i % 4)),
                          steps=5 + 2 * (i % 3))
            for i in range(2 * slots + 1)
        ]
        done = _drain_waves(b, reqs)
        assert len(done) == len(reqs)
        assert [f._cache_size() for f in jits] == compiled
        for h in done:
            np.testing.assert_array_equal(
                h.out, _sequential(models, h.req)
            )

    def test_block_step_fuses_rounds(self, models):
        """With no eos-armed session resident, step_block fuses
        exactly ceil(min_remaining/(gamma+1)) rounds into one dispatch
        — the bound is host-computable, so the fused count is
        deterministic regardless of acceptance luck."""
        b = _batcher(models)
        b.warmup()
        b.admit_many([
            (DecodeRequest(prompt=_prompt(50 + i, 1, 4), steps=12), None)
            for i in range(4)
        ])
        before = b.stats()["rounds"]
        b.step_block()
        # after join committed = len+1, so remaining = 11 and a round
        # commits at most gamma+1 = 3 tokens: ceil(11/3) = 4 rounds
        assert b.stats()["rounds"] - before == 4

    def test_wave_validation_never_leaks_slots(self, models):
        """One malformed request in a wave fails the whole admit_many
        BEFORE any slot is consumed — the frontend then isolates the
        bad one by re-admitting individually."""
        b = _batcher(models)
        good = DecodeRequest(prompt=_prompt(1, 1, 4), steps=4)
        bad = DecodeRequest(prompt=_prompt(2, 1, 4), steps=999)
        with pytest.raises(ValueError, match="steps"):
            b.admit_many([(good, None), (bad, None)])
        assert b.free_slots() == 4 and b.active_sessions() == 0

    def test_multi_row_ragged_request(self, models):
        """One request, three rows, ragged lengths: rows decode as
        independent sessions and reassemble in original row order."""
        prompt = _prompt(3, 3, 6)
        req = DecodeRequest(
            prompt=prompt, steps=5,
            prompt_lengths=np.array([6, 3, 4]),
        )
        b = _batcher(models)
        h = b.admit(req)
        done = []
        _drain(b, done)
        assert done == [h]
        np.testing.assert_array_equal(h.out, _sequential(models, req))

    def test_eos_retires_mid_batch_without_stalling_rest(self, models):
        """A session whose target commits EOS frees its slot EARLY
        while a longer session keeps decoding — and both still match
        their solo runs (EOS row: eos then zero-pads, the
        speculative_generate contract)."""
        short = DecodeRequest(prompt=_prompt(7, 1, 4), steps=12)
        # pick the eos from the short request's own solo continuation
        # so the batched run provably hits it mid-budget
        solo = _sequential(models, short)
        eos = int(solo[0, 4 + 2])  # the 3rd generated token
        short = DecodeRequest(prompt=short.prompt, steps=12, eos_id=eos)
        long = DecodeRequest(prompt=_prompt(8, 1, 4), steps=16)

        b = _batcher(models, slots=2)
        hs = b.admit(short)
        hl = b.admit(long)
        finished_order = []
        done = []
        for _ in range(500):
            if b.active_sessions() == 0:
                break
            for h in b.step():
                finished_order.append(h)
                done.append(h)
        assert finished_order[0] is hs  # eos retired first
        assert b.stats()["retired"] == 2
        np.testing.assert_array_equal(hs.out, _sequential(models, short))
        np.testing.assert_array_equal(hl.out, _sequential(models, long))
        # the eos actually cut the short session's output
        row = hs.out[0]
        assert eos in row[4:]
        cut = 4 + int(np.argmax(row[4:] == eos))
        assert (row[cut + 1:] == 0).all()


class TestSchedulerContract:
    def test_single_owner_enforced(self, models):
        b = _batcher(models)
        b.admit(DecodeRequest(prompt=_prompt(1, 1, 4), steps=3))
        errs = []

        def intruder():
            try:
                b.step()
            except RuntimeError as e:
                errs.append(e)

        t = threading.Thread(target=intruder)
        t.start()
        t.join(timeout=30)
        assert errs and "single-owner" in str(errs[0])
        done = []
        _drain(b, done)  # the owner thread still drives fine
        assert len(done) == 1

    def test_validate_rejects_before_consuming_slots(self, models):
        b = _batcher(models, slots=2, max_prompt=8, max_new=16)
        bad = [
            DecodeRequest(prompt=_prompt(1, 1, 9), steps=4),   # too wide
            DecodeRequest(prompt=_prompt(1, 3, 4), steps=4),   # B > slots
            DecodeRequest(prompt=_prompt(1, 1, 4), steps=17),  # > max_new
            DecodeRequest(prompt=_prompt(1, 1, 4), steps=0),
            DecodeRequest(prompt=_prompt(1, 1, 4), steps=4, eos_id=64),
            DecodeRequest(prompt=_prompt(1, 1, 4), steps=4,
                          prompt_lengths=np.array([5])),  # len > width
        ]
        for req in bad:
            with pytest.raises(ValueError):
                b.admit(req)
        assert b.free_slots() == 2  # nothing leaked

    def test_admit_past_capacity_raises(self, models):
        b = _batcher(models, slots=1)
        b.admit(DecodeRequest(prompt=_prompt(1, 1, 4), steps=8))
        with pytest.raises(RuntimeError, match="batch full"):
            b.admit(DecodeRequest(prompt=_prompt(2, 1, 4), steps=8))


# ---------------------------------------------------------------------------
# through the frontend: the decode worker as the batcher's scheduler
# ---------------------------------------------------------------------------


def _store(mesh, n_keys=128):
    kv = KVVector(mesh=mesh, k=1, num_slots=1 << 10, hashed=True,
                  name="batch_serve")
    rng = np.random.default_rng(0)
    keys = np.unique(rng.integers(0, 1 << 16, n_keys))
    kv.wait(kv.push(kv.request(channel=0), keys=keys,
                    values=np.ones((len(keys), 1), np.float32)))
    return kv, keys


class TestFrontendBatched:
    def test_concurrent_sessions_match_solo_runs(self, models, mesh8):
        """The tentpole end to end: concurrent DecodeRequests through
        ``ServeFrontend(batcher=...)`` — different prompts, budgets and
        arrival times sharing one running decode — each returning
        exactly its solo ``speculative_generate`` tokens."""
        kv, _ = _store(mesh8)
        fe = ServeFrontend(
            kv, ServeConfig(replica="off", workers=1),
            batcher=_batcher(models),
        ).start()
        try:
            reqs = [
                DecodeRequest(prompt=_prompt(20 + i, 1, 3 + (i % 5)),
                              steps=4 + 3 * (i % 4))
                for i in range(6)
            ]
            tickets = [fe.submit(r) for r in reqs]
            for r, tk in zip(reqs, tickets):
                np.testing.assert_array_equal(
                    tk.result(300), _sequential(models, r)
                )
            st = fe.stats()["batcher"]
            assert st["joins"] == 6 and st["retired"] == 6
            assert st["rounds"] >= 1
            snap = Postoffice.instance().metrics.snapshot()
            for m in ("ps_serve_batch_joins_total",
                      "ps_serve_batch_rounds_total",
                      "ps_serve_batch_retired_total"):
                assert sum(snap[m]["values"].values()) >= 1, m
        finally:
            fe.close()

    def test_admission_sheds_while_batch_full(self, models, mesh8):
        """The door still bounds the decode lane: with one slot pinned
        by a long session and the lane at its depth bound, the next
        decode sheds with the explicit 429 — it never queues unbounded
        behind the busy batch."""
        kv, _ = _store(mesh8)
        fe = ServeFrontend(
            kv, ServeConfig(replica="off", workers=1, max_queue_depth=2),
            batcher=_batcher(models, slots=1, max_new=16),
        ).start()
        try:
            t1 = fe.submit(DecodeRequest(prompt=_prompt(1, 1, 4), steps=16))
            t2 = fe.submit(DecodeRequest(prompt=_prompt(2, 1, 4), steps=16))
            with pytest.raises(RejectedError) as ei:
                fe.submit(DecodeRequest(prompt=_prompt(3, 1, 4), steps=4))
            assert ei.value.reason == "queue"
            assert ei.value.retry_after_s >= 0
            for tk in (t1, t2):  # the resident sessions still finish
                assert tk.result(300).shape == (1, 4 + 16)
        finally:
            fe.close()

    def test_serve_continuity_through_stalled_migration(self, models,
                                                        mesh8):
        """Batched decode touches only device model state — never the
        store — so a live ``rebalance.migrate`` stalling mid-move must
        not stall resident sessions (the pause-keeps-stepping
        semantics): decodes submitted before AND during the stall all
        complete with solo-run parity."""
        kv, keys = _store(mesh8)
        fe = ServeFrontend(
            kv, ServeConfig(replica="off", workers=1),
            batcher=_batcher(models),
        ).start()
        try:
            faults.arm("rebalance.migrate", kind="delay", delay_s=0.5,
                       once=True)
            mig = threading.Thread(
                target=lambda: kv.migrate(
                    np.random.default_rng(0).permutation(kv.num_slots)
                )
            )
            req0 = DecodeRequest(prompt=_prompt(30, 1, 4), steps=12)
            t0 = fe.submit(req0)
            mig.start()
            time.sleep(0.1)  # inside the stalled window
            reqs = [
                DecodeRequest(prompt=_prompt(31 + i, 1, 5), steps=8)
                for i in range(3)
            ]
            tickets = [fe.submit(r) for r in reqs]
            np.testing.assert_array_equal(
                t0.result(300), _sequential(models, req0)
            )
            for r, tk in zip(reqs, tickets):
                np.testing.assert_array_equal(
                    tk.result(300), _sequential(models, r)
                )
            mig.join(timeout=60)
            assert not mig.is_alive()
        finally:
            fe.close()

    def test_batcher_and_decode_fn_are_exclusive(self, models, mesh8):
        kv, _ = _store(mesh8)
        with pytest.raises(ValueError, match="decode_fn"):
            ServeFrontend(
                kv, ServeConfig(replica="off"),
                decode_fn=lambda req: req.prompt,
                batcher=_batcher(models),
            )

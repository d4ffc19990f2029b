"""The runner of ``apps/lm``: a language model's training loop under the
harness's window (README.md, "The runner's contract").

The configuration's file is a model description as ``apps/lm``'s
``--model-config`` reads it; the runner builds model, optimizer chain and
donated step through ``apps/lm/trainer.build_trainer`` (what the CLI
calls), hands the trainer initial weights that ``lm_reference.py`` made
from ``--seed`` (the reference starts from the same arrays: neither side
made them for the other), feeds it packed batches from ``lm_synth.py``
in a closed loop with at most ``launches_in_flight`` launches
dispatched, and holds it to the reference (the first launches are the
warm-up launches: the window's program at the window's shapes):

- ``loss_trajectory``: the loss of each of the first launches against
  the reference's own trajectory from the same weights, batches and
  ``optax.adafactor``;
- ``router_arithmetic``, ``router_choices_same_input``: the router's
  weights and choices at the probed tokens of the first launch against
  a float64 softmax and top-k of the SAME input (the step returns the
  router's input as it read it) and the initial router matrix: what the
  router computes, apart from what feeds it;
- ``routing_agreement``: the share of the first launches' top-k choices
  (every layer, every token) that the reference's own f32 router does
  not make on its own f32 residual stream. A choice is discrete: where
  two experts are nearly tied, the bf16 residual stream decides the
  other way, and with Zipf tokens one such tie moves a tenth of a batch
  between experts;
- ``update_parity_own_routing``: the first update of a sampled slice of
  every group of leaves against the reference's, the reference choosing
  for itself, as relative L2 error. The leaves no routed row reaches
  directly decide; the routed experts' and the routers' are printed
  beside them (they carry the moved tokens);
- ``update_parity``: the same with the reference computing AT the
  program's choices (its own probabilities there, renormalised), so
  that a near-tie is counted once, as a choice, and the routed leaves
  read arithmetic too. Every sampled leaf decides;
- ``second_update_parity``: the second launch's update against the
  reference's second, on the same slices: what the optimizer carries
  from one step to the next;
- ``examples_confirmed``.

The loss at sigma 0.02 moves little whatever the layers do, so the
update checks are the ones that see a wrong layer. Their error is
measured in the gradient's units: Adafactor divides entry (i, j) by the
root of its row's and its column's mean squared gradient, which makes
every row and column of an update equally large, those whose gradient
is rounding noise too (a router column of an expert that no token chose
beside a held one: pure noise, in f32 as well; a row of ``wo`` with a
fiftieth of the largest row's gradient: 30% off in bf16). So each entry
of the two updates is weighted by that root, taken from the REFERENCE's
gradient, before the relative L2 error is taken (``_parity``).

Program state and reference do not fit one chip together: the reference
runs in ``checks``, after the window, once the program's weights and
optimizer state are deleted, as one donated program a step (gradients
and update together), so that it holds no more live buffers than the
program did and the process's ``memory_peak_bytes`` stays the
program's (both peaks are noted: ``lm_memory``). It costs ``setup_s``
its compile, nothing else.

An example is one packed sequence; a launch is one optimizer step on
``sequences_per_launch`` of them.
"""

from __future__ import annotations

import collections
import gc
import os
import time

import numpy as np

from chipbench import lm_reference, lm_synth, trace

# here, at import: a program without the trainer (a commit before it)
# fails at once and cleanly, before anything touches its data or a device
from parameter_server_tpu.apps.lm import trainer as lm_trainer

# the recorded capture a traced rehearsal reduces in place of the Criteo
# one (a CPU capture has no device track): this cell's own traced chip
# run, cut to a few steps
FIXTURE = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    "fixtures", "mistral_small4_ep16.packed8k.trace.json.gz",
)
SLICE_ROWS = 16
# the leaves sampled, in the first and the last layer
SAMPLED = (
    "wq_a", "wkv_b", "wo", "router", "we_gate", "we_up", "we_down",
    "ws_gate", "ws_down",
)


class Runner:
    def __init__(self, run):
        self.run = run
        # the description as it is run: a rehearsal's toy sizes laid over
        self.desc = lm_reference.description(
            os.path.join(run.root, run.entry["file"]), run.rehearsal
        )
        self.m = lm_reference.model(self.desc)
        self.train = self.desc["train"]
        self.limits = (
            run.cfg["rehearsal"] if run.rehearsal else run.cfg
        )["correct"]
        self.parity_launches = run.mix["parity_launches"]
        self.fed = 0  # examples fed
        self.first = []  # the first launches' batches, for the reference
        self.first_stats = []  # and what their steps returned, on the device
        self.slices = []  # sampled rows before and after the first two

    # -- set-up ------------------------------------------------------------

    def make_data(self) -> None:
        mix = self.run.mix
        if (mix["seq_len"], mix["sequences_per_launch"]) != (
            self.train["seq_len"], self.train["batch"]
        ):
            raise ValueError(
                f"{self.run.entry['file']} trains {self.train['batch']} x "
                f"{self.train['seq_len']}, the mix feeds "
                f"{mix['sequences_per_launch']} x {mix['seq_len']}"
            )
        self.batches = lm_synth.PackedBatches(
            self.run.seed, self.desc["vocab_size"], mix
        )

    def build(self, win) -> None:
        import jax
        from jax.sharding import NamedSharding, PartitionSpec

        from parameter_server_tpu.parallel import mesh as meshlib
        from parameter_server_tpu.utils import compile_cache

        compile_cache.enable()
        t = self.train
        model = lm_trainer.model_from_description(
            self.desc, attention=t["attention"], remat=t["remat"],
            bf16=t["bf16"],
        )
        mesh = meshlib.make_mesh(num_data=1, num_server=1)
        if self.run.rehearsal:
            # the trainer around the rehearsal's own optimizer
            self.trainer = lm_trainer.Trainer(
                model, mesh, self._adafactor(),
                steps_per_launch=t["steps_per_launch"],
            )
        else:  # the CLI's builder
            self.trainer = lm_trainer.build_trainer(
                model, mesh, optimizer=t["optimizer"], lr=t["lr"],
                steps_per_launch=t["steps_per_launch"],
            )
        self.here = NamedSharding(mesh, PartitionSpec())
        self.key = jax.random.PRNGKey(self.run.seed)
        self.make_weights = lm_reference.weights_fn(self.m, self.here)
        weights = self.make_weights(self.key)
        self.routers = [
            np.asarray(weights[f"l{i}/router"], np.float64)
            for i in range(self.m["layers"])
        ]
        self.trainer.load(weights)
        del weights
        self.win = win
        self.pending = collections.deque()
        self._compile_reference()

    def _adafactor(self):
        """The reference's optimizer: ``optax.adafactor`` as the trainer
        builds it from the file's ``train``. A rehearsal's matrices are
        narrower than optax factors (128): unfactored, the first update
        is sign(g), which any rounding flips. The rehearsal factors them
        as the cell's are, in program and reference alike."""
        import optax

        if self.train["optimizer"] != "adafactor":
            raise ValueError("the reference steps with optax.adafactor")
        toy = {"min_dim_size_to_factor": 8} if self.run.rehearsal else {}
        return optax.adafactor(learning_rate=self.train["lr"], **toy)

    def _compile_reference(self) -> None:
        """The reference's programs, compiled here for the shapes they
        will see: they run after the window (``checks``), where a compile
        would count as one inside it. A step is ONE program, weights and
        optimizer state donated: the gradient tree, as large as the
        weights, lives only inside it."""
        import jax
        import optax

        m, tx = self.m, self._adafactor()
        blocked = not self.run.rehearsal
        names = self._sampled_names()

        def step(params, opt, tokens, given):
            (loss, chosen), g = lm_reference.loss_grads_choices(
                params, tokens, m, blocked, given=given
            )
            # mean square of each row and of each column of the sampled
            # leaves' gradients: the weights of ``_parity``
            rms = {
                k: (jax.numpy.mean(g[k] * g[k], axis=-1),
                    jax.numpy.mean(g[k] * g[k], axis=-2))
                for k in names
            }
            up, opt = tx.update(g, opt, params)
            return optax.apply_updates(params, up), opt, loss, chosen, rms

        spec = lambda x: jax.ShapeDtypeStruct(  # noqa: E731
            x.shape, x.dtype, sharding=x.sharding
        )
        params = jax.tree.map(spec, self.trainer.params)
        tokens = jax.tree.map(spec, self.trainer.place([np.zeros(
            (self.train["batch"], self.train["seq_len"]), np.int32
        )])[0])
        self.ref_opt_init = jax.jit(tx.init).lower(params).compile()
        opt = jax.eval_shape(tx.init, params)
        self.choices_shape = (
            m["layers"], self.train["batch"] * self.train["seq_len"],
            m["top_k"],
        )
        choices = jax.ShapeDtypeStruct(
            self.choices_shape, np.int32, sharding=self.here
        )
        self.ref_step = jax.jit(step, donate_argnums=(0, 1)).lower(
            params, opt, tokens, choices
        ).compile()

    def _sampled_names(self) -> list:
        return ["emb", "head"] + [
            f"l{i}/{leaf}" for i in sorted({0, self.m["layers"] - 1})
            for leaf in SAMPLED
        ]

    def _sampler(self, first_batch: np.ndarray):
        """A jitted gather of ``SLICE_ROWS`` rows of one leaf of every
        group, in the first and the last layer: {name: [rows, cols]}."""
        import jax

        # embedding rows that occurred: the batch's most frequent ids
        seen = np.bincount(first_batch.ravel()).argsort()[::-1][:SLICE_ROWS]
        rows = self.rows = {}
        for name in self._sampled_names():
            n = self.trainer.params[name].shape[-2]
            rows[name] = (
                np.sort(seen) if name == "emb"
                else np.linspace(0, n - 1, SLICE_ROWS).astype(np.int64)
            )

        def sample(params):
            out = {}
            for name, r in rows.items():
                leaf = params[name]
                # [held, ., .]: every held expert's rows; checks() takes
                # the one the launch gave the most tokens
                out[name] = leaf[:, r] if leaf.ndim == 3 else leaf[r]
            return out

        return jax.jit(sample)

    # -- launches ----------------------------------------------------------

    def _submit(self, batch=None) -> None:
        with self.trainer.loop_phase("wait_ingest"):
            if batch is None:
                batch = self.batches.next()
            data = self.trainer.place([batch])
        row = self.win.submitted()
        launch = self.trainer.submit(data)
        if len(self.first) < self.parity_launches:
            self.first.append(batch)
            self.first_stats.append(launch.stats)
        self.pending.append((row, launch, len(batch)))
        self.fed += len(batch)

    def _collect(self) -> None:
        row, launch, examples = self.pending.popleft()
        loss, _ = self.trainer.collect(launch)
        self.win.collected(row, examples=examples, objective=loss * examples)

    def warm_up(self) -> None:
        """This cell's shapes and no others. The sampled slices are read
        before the first launch and after each of the first two, by a
        small program queued between the launches."""
        in_flight = self.run.mix["launches_in_flight"]
        batch = self.batches.next()
        self.sample = self._sampler(batch)
        self.slices.append(self.sample(self.trainer.params))
        for i in range(self.run.mix["warmup_launches"]):
            self._submit(batch if i == 0 else None)
            if i < 2:
                self.slices.append(self.sample(self.trainer.params))
            if len(self.pending) >= in_flight:
                self._collect()
        while self.pending:
            self._collect()

    def feed(self) -> None:
        in_flight = self.run.mix["launches_in_flight"]
        while not self.win.expired():
            self._submit()
            if len(self.pending) >= in_flight:
                self._collect()
        while self.pending:
            self._collect()
        self.program_peak = _memory()

    # -- evidence, checks, context -----------------------------------------

    def window_note(self) -> dict:
        return {
            "tokens_per_launch": self.run.mix["seq_len"]
            * self.run.mix["sequences_per_launch"],
            "program_memory_peak_bytes": self.program_peak,
        }

    def notes(self, win) -> None:
        rows = {
            s["labels"]["expert"]: s["value"]
            for s in win.after["ps_lm_expert_rows_total"]["series"]
        }
        self.run.note("lm_expert_rows_total", **rows)

    def _reference(self) -> dict:
        """The reference from its own initial weights (made again from
        the seed: the program's state is gone by now) over the first
        launches' batches: once choosing for itself (the first launch),
        then its trajectory at the program's choices."""
        import jax

        t0 = time.perf_counter()
        host = lambda tree: {  # noqa: E731
            k: np.asarray(v) for k, v in tree.items()
        }
        pairs = lambda tree: {  # noqa: E731
            k: tuple(np.asarray(x) for x in v) for k, v in tree.items()
        }
        stages = []  # (what was just done, bytes in use, peak so far)
        mark = lambda what: stages.append(  # noqa: E731
            (what, _memory("bytes_in_use"), _memory())
        )
        mark("window closed")
        out = {
            "program": [host(s) for s in self.slices],
            "probe": host({
                k: v for k, v in self.first_stats[0].items()
                if k.startswith("probe_")
            }),
        }
        mine = [s["top_e"] for s in self.first_stats]
        self.trainer.params = self.trainer.opt = None
        self.slices = self.first_stats = None
        gc.collect()
        mark("program state deleted")
        place = lambda batch: self.trainer.place([batch])[0]  # noqa: E731

        params = self.make_weights(self.key)
        mark("weights made again")
        opt = self.ref_opt_init(params)
        out["start"] = host(self.sample(params))
        own = jax.device_put(
            np.full(self.choices_shape, -1, np.int32), self.here
        )
        params, opt, loss, _, rms = self.ref_step(
            params, opt, place(self.first[0]), own
        )
        out["own"] = host(self.sample(params))
        out["own_loss"], out["own_rms"] = float(loss), pairs(rms)
        mark("stepped, choosing for itself")
        del params, opt, own
        mark("deleted")

        params = self.make_weights(self.key)
        opt = self.ref_opt_init(params)
        out["given"], out["losses"], out["differ"] = [], [], []
        out["busiest"], out["rms"] = [], []
        held = np.arange(self.m["held"]) + self.m["offset"]
        for i, batch in enumerate(self.first):
            params, opt, loss, chosen, rms = self.ref_step(
                params, opt, place(batch), mine[i]
            )
            out["losses"].append(float(loss))
            if i < 2:
                out["given"].append(host(self.sample(params)))
                out["rms"].append(pairs(rms))
            a, b = np.asarray(mine[i]), np.asarray(chosen)
            out["differ"].append(1.0 - float(np.mean(
                (a[..., :, None] == b[..., None, :]).any(-1)
            )))
            # per layer, the held expert with the most rows: an expert
            # that saw a handful of tokens has a gradient of noise
            out["busiest"].append([
                int(np.argmax([(layer == e).sum() for e in held]))
                for layer in a
            ])
        mark("stepped at the program's choices")
        del params, opt
        gc.collect()
        out["seconds"], out["memory"] = time.perf_counter() - t0, stages
        return out

    def _router_on_its_own_input(self, probe: dict):
        """``(largest weight error, share of tokens chosen otherwise)``
        of the first launch's router at its probed tokens, against a
        float64 softmax, top-k and renormalisation of the same input and
        the initial router matrix."""
        k, worst, other = self.m["top_k"], 0.0, []
        for i, w_g in enumerate(self.routers):
            logits = probe["probe_x"][i].astype(np.float64) @ w_g
            p = np.exp(logits - logits.max(-1, keepdims=True))
            p /= p.sum(-1, keepdims=True)
            e = probe["probe_e"][i]
            at = np.take_along_axis(p, e, axis=-1)
            if self.m["norm_topk"]:
                at = at / at.sum(-1, keepdims=True)
            at = at * self.m["routed_scale"]
            worst = max(worst, float(np.abs(probe["probe_w"][i] - at).max()))
            want = np.sort(np.argsort(-p, axis=-1)[:, :k], axis=-1)
            other.append(float(np.mean(
                (np.sort(e, axis=-1) != want).any(-1)
            )))
        return worst, other

    def _parity(self, before, after, ref_before, ref_after, busiest, squares):
        """Relative L2 error of ``after - before`` against the
        reference's by sampled leaf (of the routed experts' leaves the
        layer's busiest expert's), entry (i, j) of both weighted by the
        fourth root of ``squares``' row i times column j: the reference
        gradient's ``(row, column)`` mean squares, summed over the steps
        whose second moments the update divided by."""
        errors = {}
        for name in before:
            want = ref_after[name].astype(np.float64) - ref_before[name]
            have = after[name].astype(np.float64) - before[name]
            row, col = (
                sum(s[name][i].astype(np.float64) for s in squares)
                for i in (0, 1)
            )
            row = row[..., self.rows[name]]
            if want.ndim == 3:  # [held, rows, cols]
                e = busiest[int(name[1:name.index("/")])]
                want, have, row, col = want[e], have[e], row[e], col[e]
            weight = np.sqrt(np.sqrt(np.outer(row, col)))
            norm = np.linalg.norm(want * weight)
            errors[name] = float(
                np.linalg.norm((have - want) * weight) / norm if norm > 0
                else np.linalg.norm(have)
            )
        return errors

    def checks(self, win, warm: list, rows: list, check) -> None:
        ref = self._reference()
        self.run.note(
            "lm_memory", program_peak_bytes=self.program_peak,
            process_peak_after_reference_bytes=_memory(),
            reference_s=ref["seconds"], reference_stages=ref["memory"],
        )
        limits = self.limits
        before, after1, after2 = ref["program"]
        same = all(
            np.array_equal(ref["start"][k], before[k]) for k in before
        )
        n = len(ref["losses"])
        got = [r["objective"] / r["examples"] for r in warm[:n]]
        gaps = [abs(a - b) for a, b in zip(got, ref["losses"])]
        check(
            "loss_trajectory",
            same and len(got) == n and max(gaps) <= limits["loss_abs"],
            value=max(gaps), limit=limits["loss_abs"], program=got,
            reference=ref["losses"], same_initial_weights=same,
            reference_choosing_for_itself=ref["own_loss"],
        )
        worst, other = self._router_on_its_own_input(ref["probe"])
        check(
            "router_arithmetic", worst <= limits["router_weight_abs"],
            value=worst, limit=limits["router_weight_abs"],
            probed_tokens_a_layer=len(ref["probe"]["probe_e"][0]),
        )
        check(
            "router_choices_same_input",
            max(other) <= limits["router_choices_same_input"],
            value=max(other), limit=limits["router_choices_same_input"],
            by_layer=other,
        )
        check(
            "routing_agreement",
            max(ref["differ"]) <= limits["routing_disagreement"],
            value=max(ref["differ"]), limit=limits["routing_disagreement"],
            share_of_choices_not_the_references=ref["differ"],
        )

        def held_to(name, errors, limit, deciding=None):
            deciding = set(errors) if deciding is None else deciding
            worst = max(deciding, key=errors.get)
            check(
                name, errors[worst] <= limit, value=errors[worst],
                limit=limit, worst=worst,
                relative_l2_by_leaf={k: errors[k] for k in sorted(deciding)},
                not_deciding={
                    k: errors[k] for k in sorted(set(errors) - deciding)
                },
            )

        busiest, rms = ref["busiest"], ref["rms"]
        held_to(
            "update_parity_own_routing",
            self._parity(
                before, after1, ref["start"], ref["own"], busiest[0],
                [ref["own_rms"]],
            ),
            limits["update_parity_own_routing"],
            {k for k in before if "/we_" not in k and "/router" not in k},
        )
        held_to(
            "update_parity",
            self._parity(
                before, after1, ref["start"], ref["given"][0], busiest[0],
                rms[:1],
            ),
            limits["update_parity"],
        )
        held_to(
            "second_update_parity",
            self._parity(
                after1, after2, ref["given"][0], ref["given"][1],
                busiest[1], rms,
            ),
            limits["second_update_parity"],
        )
        collected = sum(r["examples"] for r in win.rows)
        check(
            "examples_confirmed", collected == self.fed,
            value=collected, limit=self.fed,
        )

    def ctx(self) -> dict:
        """The readers' keys that only this application has. A traced
        rehearsal gives them this cell's recorded capture to reduce."""
        out = {"lm": {
            "desc": self.desc, "seq_len": self.run.mix["seq_len"],
            "sequences": self.run.mix["sequences_per_launch"],
            "remat": self.train["remat"],
        }}
        if self.run.rehearsal and os.path.exists(FIXTURE):
            out["trace"] = trace.load(FIXTURE)
        return out

    def stop(self) -> None:
        self.trainer = None


def _memory(what: str = "peak_bytes_in_use") -> int:
    import jax

    return max((d.memory_stats() or {}).get(what, 0) for d in jax.devices())

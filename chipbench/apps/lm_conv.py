"""The runner of ``apps/lm`` for a model of gated short-convolution and
GQA layers whose sigmoid routers pick experts by a bias that the step
moves: ``apps/lm_swa.py``'s runner (the feed loop, the launches, the
reference's compile on a thread beside the program's, the sampling of a
leaf that Adafactor does not factor as one row, the checks and their
arithmetic) held to ``lm_conv_reference.py``.

What is its own:

- the reference's step: the gradient and Adafactor over every leaf but
  the selection biases (``lm_conv_reference.trained``), the biases moved
  by the reference's rule from the choices the step computed with;
- which leaves are sampled: ``emb`` (the head is tied to it) and, in the
  dense layer, the first attention layer, the first convolution layer
  after it and the last layer, ``conv_in``, ``conv_taps``, ``conv_out``
  or ``wq``, ``wk``, ``wv``, ``wo``, ``q_norm``, ``k_norm``, with
  ``w_gate``, ``w_up``, ``w_down`` (dense) or the router and the busiest
  held expert's three matrices. The taps ``[3, d]`` are not factored and
  are sampled whole, as one row;
- the router's checks on the probes of EVERY parity launch, each with
  the bias it ran under (0 at the first): ``router_arithmetic`` against
  a float64 sigmoid of the same input at the program's choices,
  renormalised without the bias, ``router_choices_same_input`` against
  the float64 top-k of scores + bias. The first launch's bias is 0, so
  a bias that weights or a router that ignores it is seen from the
  second launch on;
- ``expert_bias``: the program's bias after each of the first launches
  (read back after each submit) against ``lm_conv_reference.update_bias``
  run from 0 over the program's own choices of those launches: largest
  absolute difference.

The dense layer has no expert layer: the step's choices, the busiest
experts and the biases are stacked over the expert layers, which are the
layers after it.

It imports, as it is imported, a name of the program that came with this
family (``models/transformer.CONV_TOKEN_LAYERS``), so that a commit
without the layer fails at once with exit code 1.
"""

from __future__ import annotations

import collections
import os
import threading

import numpy as np

from chipbench import lm_conv_reference as ref
from chipbench import trace
from chipbench.apps import lm, lm_swa

# here, at import: see above
from parameter_server_tpu.models.transformer import (  # noqa: F401
    CONV_TOKEN_LAYERS as _program_has_the_conv_layer,
)

lm_trainer = lm.lm_trainer
FIXTURE = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    "fixtures", "lfm2_ep8.packed8k_mb1.trace.json.gz",
)
SAMPLED = {
    "conv": ("conv_in", "conv_taps", "conv_out"),
    "full": ("wq", "wk", "wv", "wo", "q_norm", "k_norm"),
    "dense": ("w_gate", "w_up", "w_down"),
    "moe": ("router", "we_gate", "we_up", "we_down"),
}


class Runner(lm_swa.Runner):
    def __init__(self, run):
        self.run = run
        self.desc = ref.description(
            os.path.join(run.root, run.entry["file"]), run.rehearsal
        )
        self.m = ref.model(self.desc)
        self.moe_layers = ref.expert_layers(self.m)
        self.train = self.desc["train"]
        self.limits = (
            run.cfg["rehearsal"] if run.rehearsal else run.cfg
        )["correct"]
        self.parity_launches = run.mix["parity_launches"]
        self.fed = 0
        self.first, self.first_stats, self.slices = [], [], []
        # the program's routers and biases after each parity launch
        self.routing = []
        self.last_top_e = None  # the choices of the last launch collected

    def build(self, win) -> None:
        import jax
        import jax.numpy as jnp
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
        self.make_weights = ref.weights_fn(self.m, self.here)
        weights = self.make_weights(self.key)
        self.routers = [
            np.asarray(weights[f"l{i}/router"], np.float64)
            for i in self.moe_layers
        ]
        self.trainer.load(weights)
        del weights
        self.read_routing = jax.jit(lambda p: tuple(
            jnp.stack([p[f"l{i}/{leaf}"] for i in self.moe_layers])
            for leaf in ("router", ref.BIAS)
        ))
        self.win = win
        self.pending = collections.deque()
        self._compile_reference()

    def _compile_reference(self) -> None:
        """The reference's step as ONE program, weights and optimizer
        state donated, lowered here for the shapes it will see and
        compiled on a thread by the time ``warm_up`` returns. Adafactor
        steps the ``trained`` leaves; the biases move by the
        reference's rule from the choices it computed with."""
        import jax
        import jax.numpy as jnp
        import optax

        m, tx = self.m, self._adafactor()
        blocked = not self.run.rehearsal
        names = self._sampled_names()

        def step(params, opt, tokens, given):
            (loss, chosen), g = ref.loss_grads_choices(
                params, tokens, m, blocked, given=given
            )
            rms = {}
            for k in names:
                gk = self._as_sampled(g[k])
                rms[k] = (
                    jnp.mean(gk * gk, axis=-1), jnp.mean(gk * gk, axis=-2)
                )
            trained = ref.trained(params)
            up, opt = tx.update(g, opt, trained)
            new = optax.apply_updates(trained, up)
            used = jnp.where(given >= 0, given, chosen)
            new = {**new, **ref.step_biases(params, used, m)}
            return new, opt, loss, chosen, rms

        spec = lambda x: jax.ShapeDtypeStruct(  # noqa: E731
            x.shape, x.dtype, sharding=x.sharding
        )
        params = jax.tree.map(spec, self.trainer.params)
        tokens = jax.tree.map(spec, self.trainer.place([np.zeros(
            (self.train["batch"], self.train["seq_len"]), np.int32
        )])[0])
        init = lambda p: tx.init(ref.trained(p))  # noqa: E731
        self.ref_opt_init = jax.jit(init).lower(params).compile()
        opt = jax.eval_shape(init, params)
        self.choices_shape = (
            len(self.moe_layers),
            self.train["batch"] * self.train["seq_len"], m["top_k"],
        )
        choices = jax.ShapeDtypeStruct(
            self.choices_shape, np.int32, sharding=self.here
        )
        # the rule alone, for ``expert_bias``: compiled here, as it runs
        # after the window
        biases = jax.ShapeDtypeStruct(
            (len(self.moe_layers), m["experts"]), np.float32,
            sharding=self.here,
        )
        self.bias_rule = jax.jit(
            lambda b, chosen: ref.update_bias(b, ref.load(chosen, m))
        ).lower(biases, choices).compile()
        lowered = jax.jit(step, donate_argnums=(0, 1)).lower(
            params, opt, tokens, choices
        )

        def compile_it():
            try:
                self.ref_step = lowered.compile()
            except BaseException as e:  # handed to warm_up, which raises it
                self.ref_step = e

        self._reference_compiling = threading.Thread(
            target=compile_it, name="reference-compile"
        )
        self._reference_compiling.start()

    def _sampled_names(self) -> list:
        kinds = self.m["kinds"]
        dense = self.m["dense_layers"]
        layers = sorted({
            0, kinds.index("full"), kinds.index("conv", dense),
            len(kinds) - 1,
        })
        return ["emb"] + [
            f"l{i}/{leaf}" for i in layers
            for leaf in SAMPLED[kinds[i]]
            + SAMPLED["dense" if i < dense else "moe"]
        ]

    # -- launches ----------------------------------------------------------

    def _submit(self, batch=None) -> None:
        super()._submit(batch)
        if len(self.routing) < self.parity_launches:
            self.routing.append(self.read_routing(self.trainer.params))

    def _collect(self) -> None:
        self.last_top_e = self.pending[0][1].stats["top_e"]
        super()._collect()

    # -- evidence, checks, context -----------------------------------------

    def notes(self, win) -> None:
        super().notes(win)
        held = self.m["offset"] + np.arange(self.m["held"])

        def rows(top_e):  # [expert layers, T, k] -> held rows a layer
            top_e = np.asarray(top_e)
            return [int(np.isin(layer, held).sum()) for layer in top_e]

        self.run.note(
            "lm_conv_held_rows_by_layer",
            first_launch=rows(self.first_stats[0]["top_e"]),
            last_launch=rows(self.last_top_e),
            expected=self.train["batch"] * self.train["seq_len"]
            * self.m["top_k"] * self.m["held"] // self.m["experts"],
        )

    def _reference(self) -> dict:
        host = lambda tree: {  # noqa: E731
            k: np.asarray(v) for k, v in tree.items()
        }
        self.probes = [
            host({k: v for k, v in s.items() if k.startswith("probe_")})
            for s in self.first_stats
        ]
        self.chosen = [np.asarray(s["top_e"]) for s in self.first_stats]
        self.routing = [
            tuple(np.asarray(x) for x in r) for r in self.routing
        ]
        out = super()._reference()
        # by layer number, as the sampled leaves are named
        out["busiest"] = [
            dict(zip(self.moe_layers, b)) for b in out["busiest"]
        ]
        return out

    def _reference_biases(self) -> list:
        """The biases the reference's rule gives after each parity
        launch, run from 0 over the program's own choices."""
        import jax

        b = np.zeros((len(self.moe_layers), self.m["experts"]), np.float32)
        out = []
        for chosen in self.chosen:
            b = np.asarray(self.bias_rule(*jax.device_put(
                (b, chosen), self.here
            )))
            out.append(b)
        return out

    def _router_on_its_own_input(self, probe):
        """``(largest weight error, share of tokens chosen otherwise by
        layer)`` over the probes of every parity launch, each against a
        float64 sigmoid of the same input and the router matrix the
        launch ran with (read back before it), the choices against the
        top-k of scores + the bias the reference's rule gives before
        the launch, the weights the scores at the program's choices over
        their sum."""
        del probe  # the first launch's; every launch's are read here
        k, worst = self.m["top_k"], 0.0
        other = [0.0] * len(self.routers)
        biases = [np.zeros_like(self.routing[0][1])]
        biases += self._reference_biases()
        routers = [np.stack(self.routers)] + [r for r, _ in self.routing]
        for probe, bias, w in zip(self.probes, biases, routers):
            for i in range(len(self.routers)):
                w_g = w[i].astype(np.float64)
                logits = probe["probe_x"][i].astype(np.float64) @ w_g
                s = 1.0 / (1.0 + np.exp(-logits))
                e = probe["probe_e"][i]
                at = np.take_along_axis(s, e, axis=-1)
                if self.m["norm_topk"]:
                    at = at / at.sum(-1, keepdims=True)
                at = at * self.m["routed_scale"]
                worst = max(
                    worst, float(np.abs(probe["probe_w"][i] - at).max())
                )
                pick = s + bias[i].astype(np.float64)
                want = np.sort(np.argsort(-pick, axis=-1)[:, :k], axis=-1)
                other[i] = max(other[i], float(np.mean(
                    (np.sort(e, axis=-1) != want).any(-1)
                )))
        return worst, other

    def checks(self, win, warm: list, rows: list, check) -> None:
        super().checks(win, warm, rows, check)
        want = self._reference_biases()
        gaps = [
            float(np.abs(have - w).max())
            for (_, have), w in zip(self.routing, want)
        ]
        check(
            "expert_bias", len(gaps) == self.parity_launches
            and max(gaps) <= self.limits["expert_bias_abs"],
            value=max(gaps), limit=self.limits["expert_bias_abs"],
            by_launch=gaps,
            spread_by_launch=[float((w.max(-1) - w.min(-1)).max())
                              for w in want],
        )

    def ctx(self) -> dict:
        """The readers' keys that only this application has (``lm``: what
        ``readers/lm_common.py`` reads). A traced rehearsal gives them
        this cell's recorded capture to reduce."""
        out = {"lm": {
            "desc": self.desc, "seq_len": self.run.mix["seq_len"],
            "sequences": self.run.mix["sequences_per_launch"],
            "remat": self.train["remat"],
        }}
        if self.run.rehearsal and os.path.exists(FIXTURE):
            out["trace"] = trace.load(FIXTURE)
        return out

"""The runner of ``apps/lm`` for a model of gated window and full GQA
layers under sandwich norms whose sigmoid routers pick experts by a bias
that the step moves, beside a shared expert: ``apps/lm_conv.py``'s
runner (the feed loop, the launches, the reference's compile on a thread
beside the program's, the sampling, the checks and their arithmetic: the
sigmoid router's checks over the probes of every parity launch, each
with the bias it ran under, and ``expert_bias``) held to
``lm_afmoe_reference.py``.

What is its own: the reference it names (its step, weights and bias
rule are lowered here as ``lm_conv.py`` lowers its own), and which
leaves are sampled: ``emb``, ``head`` and, in the dense layer (a window
layer), the first window layer after it, the first full layer and the
last layer, ``wq``, ``wk``, ``wv``, ``wo``, the gate ``wg``, ``q_norm``,
``k_norm`` and the post-block norms' scales ``post_attn_norm``,
``post_ffn_norm``, with ``w_gate``, ``w_up``, ``w_down`` (dense) or the
router, the busiest held expert's three matrices and the shared
expert's three. A norm's scale is not factored and is sampled whole, as
one row. The held rows by layer are noted under ``lm_conv.py``'s name
(``lm_conv_held_rows_by_layer``).

It imports, as it is imported, a name of the program that came with this
family (``models/transformer.ATTN_GATE_TOKEN_LAYERS``), so that a commit
without it fails at once with exit code 1.
"""

from __future__ import annotations

import collections
import os
import threading

import numpy as np

from chipbench import lm_afmoe_reference as ref
from chipbench import trace
from chipbench.apps import lm, lm_conv

# here, at import: see above
from parameter_server_tpu.models.transformer import (  # noqa: F401
    ATTN_GATE_TOKEN_LAYERS as _program_has_the_gate_count,
)

lm_trainer = lm.lm_trainer
FIXTURE = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    "fixtures", "trinity_mini_ep8.packed8k_mb1.trace.json.gz",
)
SAMPLED = {
    "attention": (
        "wq", "wk", "wv", "wo", "wg", "q_norm", "k_norm", "post_attn_norm",
        "post_ffn_norm",
    ),
    "dense": ("w_gate", "w_up", "w_down"),
    "moe": (
        "router", "we_gate", "we_up", "we_down", "ws_gate", "ws_up",
        "ws_down",
    ),
}


class Runner(lm_conv.Runner):
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
        else:  # build_trainer, as the CLI builds it
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
        """``lm_conv.Runner._compile_reference`` of this reference: ONE
        donated program a step, Adafactor over the ``trained`` leaves and
        the biases moved by the reference's rule from the choices it
        computed with, compiled on a thread by the time ``warm_up``
        returns; the rule alone compiled here for ``expert_bias``."""
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
        kinds, dense = self.m["kinds"], self.m["dense_layers"]
        layers = sorted({
            0, kinds.index("window", dense), kinds.index("full"),
            len(kinds) - 1,
        })
        return ["emb", "head"] + [
            f"l{i}/{leaf}" for i in layers
            for leaf in SAMPLED["attention"]
            + SAMPLED["dense" if i < dense else "moe"]
        ]

    def ctx(self) -> dict:
        """``lm_conv.Runner.ctx`` with this cell's recorded capture for a
        traced rehearsal to reduce."""
        out = {"lm": {
            "desc": self.desc, "seq_len": self.run.mix["seq_len"],
            "sequences": self.run.mix["sequences_per_launch"],
            "remat": self.train["remat"],
        }}
        if self.run.rehearsal and os.path.exists(FIXTURE):
            out["trace"] = trace.load(FIXTURE)
        return out

"""The runner of ``apps/lm`` for a model of sliding-window and
full-attention GQA layers, each kind with rotary tables of its own:
``apps/lm.py``'s runner (the feed loop, the launches, the checks and
their arithmetic: ``_parity``, ``_router_on_its_own_input``,
``_reference``, ``checks``, ``_memory``) held to ``lm_swa_reference.py``.
It is a subclass of ``apps/lm_hybrid.py``'s, which is one of
``apps/lm.py``'s: from there come the reference's compile on a thread of
its own beside the program's (``warm_up``) and the sampling of a leaf
that Adafactor does not factor, whole, as one row (``_factored``,
``_as_sampled``, ``_sampler``: the q/k norms' ``[128]`` scales are such
leaves, and that file's docstring says why they are sampled so). That
runner's one check of its own, of the recurrence's bits, is not run:
there is no recurrence here.

What is its own: the reference it names and that reference's compiled
step, and which leaves are sampled: ``emb``, ``head`` and, in the first
layer (window), the first full layer and the last layer (full), ``wq``,
``wk``, ``wv``, ``wo``, ``q_norm``, ``k_norm`` with the router and the
busiest held expert's three matrices. No shared expert.

It imports, as it is imported, a name of the program that came with this
family (``models/transformer.Rope``: rotary tables per kind of layer),
so that a commit without it fails at once with exit code 1.
"""

from __future__ import annotations

import collections
import os
import threading

import numpy as np

from chipbench import lm_swa_reference, trace
from chipbench.apps import lm, lm_hybrid

# here, at import: see above
from parameter_server_tpu.models.transformer import (  # noqa: F401
    Rope as _program_has_rope_by_kind,
)

lm_trainer = lm.lm_trainer
FIXTURE = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    "fixtures", "mellum2_ep4.packed8k_mb1.trace.json.gz",
)
SAMPLED = (
    "wq", "wk", "wv", "wo", "q_norm", "k_norm", "router", "we_gate",
    "we_up", "we_down",
)


class Runner(lm_hybrid.Runner):
    def __init__(self, run):
        self.run = run
        self.desc = lm_swa_reference.description(
            os.path.join(run.root, run.entry["file"]), run.rehearsal
        )
        self.m = lm_swa_reference.model(self.desc)
        self.train = self.desc["train"]
        self.limits = (
            run.cfg["rehearsal"] if run.rehearsal else run.cfg
        )["correct"]
        self.parity_launches = run.mix["parity_launches"]
        self.fed = 0
        self.first, self.first_stats, self.slices = [], [], []

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
        self.make_weights = lm_swa_reference.weights_fn(self.m, self.here)
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

    def _compile_reference(self) -> None:
        """The reference's step as ONE program, weights and optimizer
        state donated, lowered here for the shapes it will see and
        compiled by the time ``warm_up`` returns (it runs after the
        window, where a compile would count as one inside)."""
        import jax
        import jax.numpy as jnp
        import optax

        m, tx = self.m, self._adafactor()
        blocked = not self.run.rehearsal
        names = self._sampled_names()

        def step(params, opt, tokens, given):
            (loss, chosen), g = lm_swa_reference.loss_grads_choices(
                params, tokens, m, blocked, given=given
            )
            # mean square of each row and of each column of the sampled
            # leaves' gradients: the weights of ``_parity``
            rms = {}
            for k in names:
                gk = self._as_sampled(g[k])
                rms[k] = (
                    jnp.mean(gk * gk, axis=-1), jnp.mean(gk * gk, axis=-2)
                )
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
        layers = sorted({0, kinds.index("full"), len(kinds) - 1})
        return ["emb", "head"] + [
            f"l{i}/{leaf}" for i in layers for leaf in SAMPLED
        ]

    def checks(self, win, warm: list, rows: list, check) -> None:
        # apps/lm.py's, and not the recurrence's check between
        lm.Runner.checks(self, win, warm, rows, check)

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

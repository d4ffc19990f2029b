"""The runner of ``apps/lm`` for a model of gated delta-rule (KDA) and
gated softmax layers: ``apps/lm.py``'s runner (the feed loop, the
launches, the checks and their arithmetic: ``_parity``,
``_router_on_its_own_input``, ``_reference``, ``checks``) held to
``lm_hybrid_reference.py`` in place of the latent-attention reference.

What is its own: the reference and its compiled step, which leaves are
sampled, and how a leaf that Adafactor does not factor is sampled.
``optax.adafactor`` factors a leaf's second moment over its two largest
dimensions where both are at least 128 (8 in a rehearsal, as
``_adafactor`` sets it for program and reference alike); every other
leaf (``a_log`` and ``dt_bias``, the convolutions' ``[4, W]`` taps,
``wbeta`` ``[d, heads]``) is divided ENTRY by entry by the root of its
own squared gradient, so its first update is ``lr * sign(g)`` and an
entry whose gradient is rounding noise moves as far as any other. Such
a leaf is sampled whole, as one row: ``_parity``'s weight of entry j,
the fourth root of (the row's mean square x the column's), is then
``sqrt(|g_j|)`` up to a constant, the entry's own gradient, as the
weight of a factored leaf's entry is its row's and its column's.

The sampled leaves: ``emb``, ``head`` and, in the first layer (softmax)
and the last (KDA), every matrix of the attention kind there (for KDA
the four projections, both low-rank pairs, beta, the convolutions,
``a_log``, ``dt_bias``) with the router, the busiest held expert and the
shared expert.

One check of its own, ``kda_state_and_decay_in_f32``: the file states
the recurrence's state and its log-decay as f32, and no comparison of
values tells a bf16 one (the products read the state in bf16 anyway, and
the delta rule overwrites what a rounding left within a chunk or two:
the update checks read 0.045-0.055 either way). So the step hands back
the last state of every KDA layer as its scan carried it and the
log-decay as the layer's decay gate computes it at strided tokens, cast
to f32 without rounding (``probe_kda_state``, ``probe_kda_g``), and the
check is the
share of their entries that bf16 cannot hold (some of the low 16 bits of
the f32 pattern set): all but one in 65,536 of an f32 quantity's, none
of a bf16 one's.

It imports the program's KDA module as it is imported, so that a commit
without one fails at once with exit code 1, before a device is touched.
"""

from __future__ import annotations

import collections
import os
import threading

import numpy as np

from chipbench import lm_hybrid_reference, trace
from chipbench.apps import lm

# here, at import: see above
from parameter_server_tpu.models import kda as _program_has_kda  # noqa: F401

lm_trainer = lm.lm_trainer
FIXTURE = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    "fixtures", "solar_open2_ep40.packed8k_mb1.trace.json.gz",
)
EXPERT_LEAVES = (
    "router", "we_gate", "we_up", "we_down", "ws_gate", "ws_up", "ws_down",
)
SAMPLED = {
    "gqa": ("wq", "wk", "wv", "wo", "wg"),
    "kda": (
        "wq", "wk", "wv", "wo", "wf_a", "wf_b", "wg_a", "wg_b", "wbeta",
        "conv_q", "conv_k", "conv_v", "a_log", "dt_bias",
    ),
}


class Runner(lm.Runner):
    def __init__(self, run):
        self.run = run
        self.desc = lm_hybrid_reference.description(
            os.path.join(run.root, run.entry["file"]), run.rehearsal
        )
        self.m = lm_hybrid_reference.model(self.desc)
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
        self.make_weights = lm_hybrid_reference.weights_fn(self.m, self.here)
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

    def warm_up(self) -> None:
        """The program's step compiles in its first launch while the
        reference's, lowered in ``build``, compiles on a thread of its
        own (the compiler holds no lock of the interpreter's): of a cold
        ``setup_s`` the longer of the two and not their sum. Both are
        done before the window opens."""
        super().warm_up()
        self._reference_compiling.join()
        if isinstance(self.ref_step, BaseException):
            raise self.ref_step

    def _factored(self, shape) -> bool:
        """Does ``optax.adafactor`` factor a leaf of this shape."""
        least = 8 if self.run.rehearsal else 128
        return len(shape) >= 2 and sorted(shape)[-2] >= least

    def _as_sampled(self, leaf):
        """A leaf as ``_sampler`` and ``_parity`` see it: one that is
        not factored as a single row."""
        return leaf if self._factored(leaf.shape) else leaf.reshape(1, -1)

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
            (loss, chosen), g = lm_hybrid_reference.loss_grads_choices(
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
        return ["emb", "head"] + [
            f"l{i}/{leaf}" for i in sorted({0, self.m["layers"] - 1})
            for leaf in SAMPLED[self.m["kinds"][i]] + EXPERT_LEAVES
        ]

    def _sampler(self, first_batch: np.ndarray):
        """A jitted gather of ``lm.SLICE_ROWS`` rows of every sampled
        leaf, {name: [rows, cols]}; a leaf that is not factored whole,
        as its one row."""
        import jax

        seen = np.bincount(first_batch.ravel()).argsort()[::-1][
            :lm.SLICE_ROWS
        ]
        rows = self.rows = {}
        for name in self._sampled_names():
            shape = self.trainer.params[name].shape
            if not self._factored(shape):
                rows[name] = np.zeros(1, np.int64)
            elif name == "emb":  # rows that occurred
                rows[name] = np.sort(seen)
            else:
                rows[name] = np.linspace(
                    0, shape[-2] - 1, lm.SLICE_ROWS
                ).astype(np.int64)

        def sample(params):
            out = {}
            for name, r in rows.items():
                leaf = self._as_sampled(params[name])
                # [held, ., .]: every held expert's rows; checks() takes
                # the one the launch gave the most tokens
                out[name] = leaf[:, r] if leaf.ndim == 3 else leaf[r]
            return out

        return jax.jit(sample)

    def _reference(self) -> dict:
        out = super()._reference()
        self.probe = out["probe"]  # the first launch's, on the host
        return out

    def checks(self, win, warm: list, rows: list, check) -> None:
        super().checks(win, warm, rows, check)
        beyond_bf16 = {
            name: float(np.mean(
                np.ascontiguousarray(v, np.float32).view(np.uint32) & 0xFFFF
                != 0
            ))
            for name, v in self.probe.items() if name.startswith("probe_kda_")
        }
        least = min(beyond_bf16, key=beyond_bf16.get)
        check(
            "kda_state_and_decay_in_f32",
            beyond_bf16[least] >= self.limits["kda_f32_entries_share"],
            value=beyond_bf16[least],
            limit=self.limits["kda_f32_entries_share"], least=least,
            share_of_entries_bf16_cannot_hold=beyond_bf16,
            entries={k: int(v.size) for k, v in self.probe.items()
                     if k in beyond_bf16},
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

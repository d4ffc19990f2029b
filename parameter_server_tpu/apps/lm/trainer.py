"""The LM trainer the CLI builds, reachable by any caller in the process:
model from flags or from a description file, optimizer chain, donated
step, ``steps_per_launch``, and a launch's two halves (``submit``,
``collect``) with their spans and counters.

``apps/lm/main.py`` and the benchmark's runner (``chipbench/apps/lm.py``)
both go through :func:`build_trainer`.
"""

from __future__ import annotations

import collections
import contextlib
import dataclasses
import functools
import json
import logging
import statistics
import time
from typing import Optional

import numpy as np

# trainer-thread phases, named as the linear trainer names its own
# (learner/sgd.py): the idle buckets of a device trace read these spans
_LOOP_SPANS = {
    "wait_ingest": "train.wait_ingest",
    "submit": "train.submit",
    "collect_wait": "train.collect.wait",
    "collect_host": "train.collect.host",
}
# the phases, and the caller's time between them, in a launch's record
_RECORD_FIELDS = dict(zip(
    _LOOP_SPANS, ("ingest_s", "submit_s", "wait_s", "host_s")
), outside="outside_s")
# A launch is stalled when its interval (one collect's end to the next)
# is over twice the running median and over it by half a second: an
# interval is 0.69 to 1.08 s in the three LM cells (ledger, PR 36), the
# launches seen took 1.6 to 4.5 s more than their neighbours (PERF.md
# section 7). None is judged before 4 intervals are in, so the launches
# that compile are not; a stall's line holds the 8 records before it.
STALL_RATIO, STALL_EXCESS_S = 2.0, 0.5
STALL_HISTORY, STALL_MIN_INTERVALS, STALL_CONTEXT = 32, 4, 8


def load_description(path: str) -> dict:
    """A model description file: the published ``config.json`` keys at the
    top level, ``published`` (counts that were cut), ``share`` (which
    experts this program holds) and ``train`` (what the CLI's flags
    would say)."""
    with open(path) as f:
        return json.load(f)


def model_from_description(desc: dict, *, attention: str = "ring_flash",
                           remat: bool = False, bf16: bool = False):
    """An ``LMConfig`` for a description of one of ``MODEL_TYPES``, each
    with a top-k expert layer in every layer, RMSNorm and an untied
    head: ``mistral4`` (latent attention, shared experts beside the
    routed), ``solar_open2`` (gated NoPE GQA layers at ``gqa_layers``,
    gated delta-rule layers, "kda", everywhere else; shared experts) or
    ``mellum`` (rotated GQA layers with a q/k norm, windowed or full by
    ``layer_types``, rotary tables per kind; no shared expert)."""
    from ...models.moe import TopKMoEConfig

    kind = desc.get("model_type")
    if kind not in MODEL_TYPES:
        raise ValueError(
            f"model_type {kind!r}: the model types described here are "
            f"{', '.join(MODEL_TYPES)}"
        )
    if desc.get("hidden_act", "silu") != "silu":
        raise ValueError("the layer is a gated SiLU FFN without biases")
    for key in ("attention_bias", "mlp_bias"):
        if desc.get(key):
            raise ValueError(
                f"{key} true: the layers are built without biases"
            )
    if desc.get("n_group", 1) != 1 or desc.get("topk_group", 1) != 1:
        raise ValueError("group-limited routing is not built")
    if desc.get("first_k_dense_replace", 0) != 0:
        raise ValueError(
            "first_k_dense_replace != 0: leading dense layers are not "
            "described here"
        )
    published, share = desc.get("published", {}), desc.get("share", {})
    # the experts held here and the router's width, under the family's
    # key: ``n_routed_experts``, or ``num_experts`` (mellum)
    experts = "num_experts" if kind == "mellum" else "n_routed_experts"
    common = dict(
        vocab=desc["vocab_size"], d_model=desc["hidden_size"],
        n_heads=desc["num_attention_heads"],
        n_layers=desc["num_hidden_layers"], d_ff=desc["intermediate_size"],
        attention=attention, remat=remat,
        compute_dtype="bfloat16" if bf16 else "float32",
        tie_head=desc["tie_word_embeddings"], norm="rmsnorm",
        norm_eps=desc["rms_norm_eps"], ffn_act="swiglu", scale_emb=False,
        moe=TopKMoEConfig(
            n_experts=published.get(experts, desc[experts]),
            top_k=desc["num_experts_per_tok"],
            d_expert=desc["moe_intermediate_size"],
            n_shared=desc.get("n_shared_experts", 0),
            experts_held=desc[experts],
            expert_offset=share.get("expert_offset", 0),
            norm_topk_prob=desc["norm_topk_prob"],
            routed_scaling_factor=float(
                desc.get("routed_scaling_factor", 1.0)
            ),
        ),
    )
    return _ATTENTION_OF[kind](desc, common)


def _yarn(rp: dict):
    """A ``rope_parameters`` block's ``YarnRope``; None for a plain
    table. Another ``rope_type`` is refused by name."""
    from ...models.latent_attention import YarnRope

    rope_type = rp.get("rope_type", rp.get("type", "default"))
    if rope_type == "default":
        return None
    if rope_type != "yarn":
        raise ValueError(
            f"rope_type {rope_type!r}: rotary tables are built plain "
            "('default') or under 'yarn'"
        )
    return YarnRope(
        factor=rp["factor"],
        original_max_position=rp["original_max_position_embeddings"],
        beta_fast=rp["beta_fast"], beta_slow=rp["beta_slow"],
        mscale=rp.get("mscale", 1.0),
        mscale_all_dim=rp.get("mscale_all_dim", 0.0),
        position_scale_beta=rp.get("llama_4_scaling_beta", 0.0),
        attention_factor=rp.get("attention_factor"),
    )


def _mistral4(desc: dict, common: dict):
    from ...models.latent_attention import MLAConfig
    from ...models.transformer import LMConfig

    rp = desc["rope_parameters"]
    return LMConfig(
        **common, rope_theta=rp["rope_theta"],
        layers=(("mla", "moe"),) * common["n_layers"],
        mla=MLAConfig(
            q_lora_rank=desc["q_lora_rank"], kv_lora_rank=desc["kv_lora_rank"],
            qk_nope_head_dim=desc["qk_nope_head_dim"],
            qk_rope_head_dim=desc["qk_rope_head_dim"],
            v_head_dim=desc["v_head_dim"],
            rope_interleave=desc["rope_interleave"], yarn=_yarn(rp),
        ),
    )


def _solar_open2(desc: dict, common: dict):
    """Layer ``i`` is a gated GQA layer without rope where ``i`` is in
    ``gqa_layers`` and a gated delta-rule layer elsewhere, its widths
    from ``linear_attn_config``."""
    from ...models.kda import KDAConfig
    from ...models.transformer import LMConfig

    if desc.get("use_rope"):
        raise ValueError(
            "use_rope true: a solar_open2 description's GQA layers are "
            "built without rope (NoPE) only"
        )
    if desc.get("kda_use_full_proj"):
        raise ValueError(
            "kda_use_full_proj true: the decay gate is built as its "
            "low-rank pair only"
        )
    if not desc.get("kda_allow_neg_eigval", True):
        raise ValueError(
            "kda_allow_neg_eigval false: the gated delta-rule layer is "
            "built with beta = 2 sigmoid(.) only"
        )
    lin = desc["linear_attn_config"]
    if lin.get("num_kv_heads") not in (None, lin["num_heads"]):
        raise ValueError(
            "linear_attn_config.num_kv_heads: the gated delta-rule layer "
            "is built with as many key/value heads as query heads"
        )
    gqa = set(desc["gqa_layers"])
    return LMConfig(
        **common, n_kv_heads=desc["num_key_value_heads"],
        head_dim=desc["head_dim"], attn_gate=bool(desc.get("use_gqa_gate")),
        rope=False,
        layers=tuple(
            ("mha" if i in gqa else "kda", "moe")
            for i in range(common["n_layers"])
        ),
        kda=KDAConfig(
            n_heads=lin["num_heads"], head_dim=lin["head_dim"],
            conv_size=lin["short_conv_kernel_size"],
            gate_rank=lin.get("gate_rank", lin["head_dim"]),
        ),
    )


def _mellum(desc: dict, common: dict):
    """Layer ``i`` is a GQA layer with a q/k norm, rotated, that sees
    the last ``sliding_window`` keys where ``layer_types[i]`` is
    ``sliding_attention`` ("swa") and all of them where it is
    ``full_attention`` ("mha"), each kind with the rotary tables of
    ``rope_parameters[<layer type>]``."""
    from ...models.transformer import LMConfig, Rope

    kinds = {"sliding_attention": "swa", "full_attention": "mha"}
    types = desc["layer_types"]
    ffns = desc.get("mlp_layer_types", ["sparse"] * len(types))
    if len(types) != common["n_layers"] or len(ffns) != len(types):
        raise ValueError(
            f"layer_types / mlp_layer_types describe {len(types)} / "
            f"{len(ffns)} layers, num_hidden_layers is {common['n_layers']}"
        )
    for name in types:
        if name not in kinds:
            raise ValueError(
                f"layer_types: {name!r} is not one of {', '.join(kinds)}"
            )
    if set(ffns) != {"sparse"}:
        raise ValueError(
            f"mlp_layer_types {sorted(set(ffns))}: a mellum description's "
            "layers are built with the expert layer ('sparse') only; a "
            "'dense' entry is not built"
        )
    windowed = "sliding_attention" in types
    if windowed and not desc.get("use_sliding_window", True):
        raise ValueError(
            "use_sliding_window false beside sliding_attention layers: "
            "which of the two the model means is not described here"
        )
    if desc.get("tie_word_embeddings"):
        raise ValueError(
            "tie_word_embeddings true: a mellum description is built with "
            "an untied head only"
        )
    rp = desc["rope_parameters"]
    full = rp.get("full_attention", rp.get("sliding_attention"))
    window = rp.get("sliding_attention")
    swa_rope = None
    if windowed and window != full:
        swa_rope = Rope(float(window["rope_theta"]), _yarn(window))
    return LMConfig(
        **common, n_kv_heads=desc["num_key_value_heads"],
        head_dim=desc["head_dim"], qk_norm=True, rope=True,
        rope_theta=float(full["rope_theta"]), rope_yarn=_yarn(full),
        swa_rope=swa_rope,
        window=desc["sliding_window"] if windowed else None,
        layers=tuple((kinds[name], "moe") for name in types),
    )


_ATTENTION_OF = {
    "mistral4": _mistral4, "solar_open2": _solar_open2, "mellum": _mellum,
}
MODEL_TYPES = tuple(_ATTENTION_OF)


def make_optimizer(name: str, lr, *, clip_norm: Optional[float] = None,
                   grad_accum: int = 1):
    """clip -> adam | adafactor | lion -> (optional) microbatch
    accumulation. ``lr`` is a number or an optax schedule; the schedule
    and accumulation counters live in the optimizer state."""
    import optax

    chain = []
    if clip_norm:
        chain.append(optax.clip_by_global_norm(clip_norm))
    # adafactor: factored second moment, the per-param optimizer state is
    # O(rows+cols), the low-memory choice beside --zero1/--fsdp
    makers = {
        "adam": optax.adam, "adafactor": optax.adafactor, "lion": optax.lion,
    }
    if name not in makers:
        raise ValueError(f"optimizer {name!r}: adam, adafactor or lion")
    chain.append(makers[name](learning_rate=lr))
    tx = optax.chain(*chain)
    if grad_accum > 1:
        # each "step" is one microbatch; the inner optimizer (and its
        # schedule) advances every grad_accum-th
        tx = optax.MultiSteps(tx, every_k_schedule=grad_accum)
    return tx


@dataclasses.dataclass
class Launch:
    """One dispatched launch: what ``collect`` waits for."""

    loss: object  # device scalar: the launch's last step's loss
    # device arrays (``lm_forward_with_stats``): the counts
    # (``transformer.STEP_COUNTS``: ``expert_rows``, ``buffer_passes``,
    # ``kda_scan_tokens``, ``attn_token_layers``) summed over the
    # launch's steps; its last step's choices and router probes
    stats: dict
    tokens: int
    flow: int = 0  # the launch's flow id: its four phases and its record
    t_submit: float = 0.0  # the trainer's clock as its submit began


class Trainer:
    """Params, optimizer state and the donated step of one LM.

    A launch is ``steps_per_launch`` optimizer steps in one program
    (``lax.scan`` carries params and optimizer state): ``submit(data)``
    dispatches it and rebinds the state, ``collect(launch)`` waits for
    its loss and counts what it computed."""

    def __init__(self, cfg, mesh, tx, *, steps_per_launch: int = 1):
        import jax
        import optax

        from ...models.transformer import (
            STEP_COUNTS,
            lm_loss_and_stats,
            next_token_targets,
        )
        from ...telemetry import registry as telemetry_registry

        self.cfg, self.mesh, self.tx = cfg, mesh, tx
        self.spl = steps_per_launch
        self.zig = cfg.attention == "ring_zigzag"
        self.n_data = mesh.shape["data"]
        self.params = self.opt = None
        self._counters, self._loop_seconds = None, {}
        # the launch record, timed by ``clock`` (a test hands another)
        self.clock, self._collected = time.perf_counter, 0
        self._flow = None  # the next launch's, from its first phase on
        self._last_end = self._mark = None  # the previous collect's end
        self._since = dict.fromkeys(_RECORD_FIELDS, 0.0)  # by phase, since
        self._history = {k: collections.deque(maxlen=STALL_HISTORY) for k in
                         (*_RECORD_FIELDS.values(), "interval_s")}
        self._recent = collections.deque(maxlen=STALL_CONTEXT)
        if telemetry_registry.enabled():
            from ...telemetry.instruments import (
                app_instruments,
                lm_instruments,
            )

            reg = telemetry_registry.default_registry()
            self._counters = lm_instruments(reg)
            loop = app_instruments(reg)["loop_seconds"]
            self._loop_seconds = {
                phase: loop.labels(phase=phase) for phase in _LOOP_SPANS
            }

        # donate params + opt state: a launch always rebinds both, and
        # the aliasing halves the model-state HBM footprint. One step:
        def one(p, opt, tokens, *targets):
            if not targets:
                targets = next_token_targets(tokens)
            (loss, stats), g = jax.value_and_grad(
                lm_loss_and_stats, has_aux=True
            )(p, tokens, *targets, cfg, mesh, "data")
            with jax.named_scope("lm_opt"):
                up, opt = tx.update(g, opt, p)
                p = optax.apply_updates(p, up)
            return p, opt, loss, stats

        if self.spl == 1:
            self.step = jax.jit(one, donate_argnums=(0, 1))
        else:
            # launch = spl sequential steps in one program (each data
            # array gains a leading [spl] dim) — identical trajectory,
            # spl-1 fewer dispatch round trips
            @functools.partial(jax.jit, donate_argnums=(0, 1))
            def step(p, opt, *stacks):
                def body(carry, xs):
                    p2, opt2, loss, stats = one(*carry, *xs)
                    return (p2, opt2), (loss, stats)

                (p, opt), (losses, stats) = jax.lax.scan(
                    body, (p, opt), stacks
                )
                # counts add up over the launch's steps; the choices
                # and probes kept are the last step's
                return p, opt, losses[-1], {
                    k: v.sum(0) if k in STEP_COUNTS else v[-1]
                    for k, v in stats.items()
                }

            self.step = step

    # -- state -------------------------------------------------------------

    def init(self, seed: int, **placement) -> None:
        """Weights from ``seed``, made on the device (``init_lm``);
        ``placement``: see :meth:`load`."""
        import jax
        from jax.sharding import NamedSharding, PartitionSpec

        from ...models.transformer import init_lm

        # explicitly REPLICATED over the mesh (not an uncommitted
        # single-device default): checkpoint restore places leaves onto
        # the template's sharding, so the template must carry the real
        # training placement or a resumed run would train mis-placed
        replicated = NamedSharding(self.mesh, PartitionSpec())
        self.load(jax.jit(
            init_lm, static_argnums=1, out_shardings=replicated
        )(jax.random.PRNGKey(seed), self.cfg), **placement)

    def load(self, params: dict, *, tensor_parallel: bool = False,
             fsdp: bool = False, zero1: bool = False) -> None:
        """Adopt weights a caller made, replicated over the mesh (the
        leaves of ``init_lm``, by name and shape, or it raises), place
        them, and start the optimizer's state from them."""
        import jax
        from jax.sharding import NamedSharding, PartitionSpec

        from ...models.transformer import (
            fsdp_shard_lm_params,
            init_lm,
            shard_lm_params,
            zero1_shard_opt_state,
        )

        want = jax.eval_shape(
            lambda: init_lm(jax.random.PRNGKey(0), self.cfg)
        )
        got = {k: (v.shape, v.dtype) for k, v in params.items()}
        if got != {k: (v.shape, v.dtype) for k, v in want.items()}:
            odd = sorted(
                k for k in set(got) | set(want)
                if k not in want or got.get(k) != (want[k].shape, want[k].dtype)
            )
            raise ValueError(
                f"these are not the model's leaves: {odd[:4]} differ in "
                "name, shape or dtype"
            )
        replicated = NamedSharding(self.mesh, PartitionSpec())
        if tensor_parallel:
            # Megatron column/row placement; GSPMD inserts the psums and
            # the optimizer update preserves the sharding
            params = shard_lm_params(params, self.mesh, "server")
        if fsdp:
            # ZeRO-3: params (and, via tx.init inheritance, grads +
            # moments) sharded over the data axis; composes with tensor
            # parallelism (those leaves keep their server dim)
            params = fsdp_shard_lm_params(params, self.mesh, "data")
        opt = self.tx.init(params)  # zeros_like inherits each placement
        if zero1:
            # ZeRO-1: moments sharded over the data axis (every leaf
            # comes back mesh-committed, scalars replicated)
            opt = zero1_shard_opt_state(opt, self.mesh, "data")
        else:
            # freshly-created leaves (a step count) aren't mesh-placed —
            # pin them replicated so a restore template is committed
            opt = jax.tree.map(
                lambda x: x
                if isinstance(getattr(x, "sharding", None), NamedSharding)
                else jax.device_put(x, replicated),
                opt,
            )
        self.params, self.opt = params, opt

    # -- a launch ----------------------------------------------------------

    @contextlib.contextmanager
    def loop_phase(self, phase: str, launch: Optional[Launch] = None):
        """One phase of the training loop on the trainer's thread:
        ``ps_train_loop_seconds{phase}`` and its ``train.*`` span, in the
        flow of ``launch`` (a collect's) or of the next launch, allotted
        when its first phase opens and taken by ``submit``."""
        from ...telemetry import host, spans

        if self._last_end is None:  # the loop begins here
            host.install_hooks()
            self._last_end, self._mark = self.clock(), host.mark()
        if launch is None and self._flow is None:
            self._flow = spans.new_flow()
        t0 = self.clock()
        try:
            with spans.flow_scope(
                self._flow if launch is None else launch.flow
            ), spans.span(
                _LOOP_SPANS[phase], histogram=self._loop_seconds.get(phase)
            ):
                yield
        finally:
            self._since[phase] += self.clock() - t0

    def place(self, batches) -> tuple:
        """Device arrays for one launch from its ``steps_per_launch``
        host batches [B, S] (a leading [spl] dim when fused), the
        sequence sharded over the data axis."""
        from ...models.transformer import shard_tokens, zigzag_lm_arrays

        if len(batches) != self.spl:
            raise ValueError(
                f"a launch is {self.spl} batches, got {len(batches)}"
            )
        if self.zig:
            grouped = list(zip(
                *(zigzag_lm_arrays(t, self.n_data) for t in batches)
            ))  # (toks), (tgts), (wts)
        else:
            grouped = [batches]
        return tuple(
            shard_tokens(g[0] if self.spl == 1 else np.stack(g), self.mesh)
            for g in grouped
        )

    def submit(self, data: tuple) -> Launch:
        t_submit = self.clock()
        with self.loop_phase("submit"):
            self.params, self.opt, loss, stats = self.step(
                self.params, self.opt, *data
            )
        flow, self._flow = self._flow, None
        return Launch(loss, stats, int(np.prod(data[0].shape)), flow, t_submit)

    def collect(self, launch: Launch):
        """``(loss, counts)`` of a launch on the host, counted: its
        ``expert_rows`` and ``buffer_passes`` where the model has the
        dropless layer, its ``kda_scan_tokens`` where it has the gated
        delta-rule layer, its ``attn_token_layers`` where it has
        windowed layers beside full ones. The launch's choices and
        probes stay on the device (``launch.stats``)."""
        from ...models.transformer import (
            ATTN_TOKEN_LAYER_KINDS,
            ATTN_TOKEN_LAYERS,
            KDA_SCAN_TOKENS,
            STEP_COUNTS,
        )

        with self.loop_phase("collect_wait", launch):
            loss = float(launch.loss)
        with self.loop_phase("collect_host", launch):
            counts = {
                k: np.asarray(v) for k, v in launch.stats.items()
                if k in STEP_COUNTS
            }
            if self._counters is not None:
                self._counters["tokens"].inc(launch.tokens)
                if KDA_SCAN_TOKENS in counts:
                    self._counters["kda_scan_tokens"].inc(
                        int(counts[KDA_SCAN_TOKENS])
                    )
                for kind, n in zip(
                    ATTN_TOKEN_LAYER_KINDS, counts.get(ATTN_TOKEN_LAYERS, ())
                ):
                    self._counters["attention_token_layers"].labels(
                        kind=kind
                    ).inc(int(n))
                for j, n in enumerate(counts.get("expert_rows", ())):
                    self._counters["expert_rows"].labels(
                        expert=str(self.cfg.moe.expert_offset + j)
                    ).inc(int(n))
                for part, n in zip(
                    ("head", "tail"), counts.get("buffer_passes", ())
                ):
                    self._counters["buffer_passes"].labels(part=part).inc(
                        int(n)
                    )
        self._record(launch, int(sum(counts.get("buffer_passes", (0, 0))[1:])))
        return loss, counts

    def _record(self, launch: Launch, tail_passes: int) -> None:
        """ONE record of a collected launch, ``train.launch``: into the
        process's flight recorder always (a lock and an append), through
        the span sink if there is one; a stalled launch is also logged."""
        from ...telemetry import blackbox, host, spans

        now, mark = self.clock(), host.mark()
        interval = now - self._last_end
        since, self._since = self._since, dict.fromkeys(_RECORD_FIELDS, 0.0)
        # the thread's time since the previous collect that no phase held
        since["outside"] = max(0.0, interval - sum(since.values()))
        past = self._history["interval_s"]
        judged = len(past) >= STALL_MIN_INTERVALS
        m = statistics.median(past) if judged else None
        stalled = m is not None and interval > max(
            STALL_RATIO * m, m + STALL_EXCESS_S
        )
        record = {
            "kind": "span", "name": "train.launch", "launch": self._collected,
            "flow": launch.flow, "dur_s": now - launch.t_submit,
            "t_wall": time.time() - (now - launch.t_submit),  # its submit
            **{_RECORD_FIELDS[k]: v for k, v in since.items()},
            "interval_s": interval, "tokens": launch.tokens,
            "tail_passes": tail_passes, "stalled": stalled,
        }
        events = [record]
        if stalled:
            # the phase whose excess over its own median is largest
            where = max(since, key=lambda k: since[k] - statistics.median(
                self._history[_RECORD_FIELDS[k]]
            ))
            events.append({
                "kind": "span", "name": "train.launch.stalled",
                "t_wall": time.time(), "dur_s": 0.0, "flow": launch.flow,
                "where": where, "median_interval_s": m,
                "launch": record, "before": list(self._recent),
                "host": host.evidence(self._mark, mark),
            })
            logging.getLogger("parameter_server_tpu").warning(
                "%s", json.dumps(events[1], default=str)
            )
            if self._counters is not None:
                self._counters["stalled"].labels(where=where).inc()
        ring, sink = blackbox.recorder(), spans.get_sink()
        for event in events:
            if sink is not None:
                spans.emit(event)
            if getattr(sink, "recorder", None) is not ring:
                ring.emit(event)  # an armed ring has it from the sink
        if self._counters is not None:
            self._counters["launch_seconds"].observe(record["dur_s"])
            self._counters["launch_interval"].observe(interval)
        for k, h in self._history.items():
            h.append(record[k])
        self._recent.append(record)
        self._last_end, self._mark, self._collected = (
            now, mark, self._collected + 1
        )


def build_trainer(cfg, mesh, *, optimizer: str = "adam", lr=3e-3,
                  clip_norm: Optional[float] = None, grad_accum: int = 1,
                  steps_per_launch: int = 1) -> Trainer:
    """THE builder: the CLI's flags and a description file's ``train``
    block both end here."""
    if steps_per_launch < 1:
        raise ValueError(
            f"steps_per_launch must be >= 1, got {steps_per_launch}"
        )
    tx = make_optimizer(
        optimizer, lr, clip_norm=clip_norm, grad_accum=grad_accum
    )
    return Trainer(cfg, mesh, tx, steps_per_launch=steps_per_launch)

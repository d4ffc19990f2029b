"""Sequence-parallel decoder-only transformer LM.

The long-context story end to end: activations are sharded along the
SEQUENCE axis of the mesh, attention runs as the exact ring schedule
(models/attention.py — ppermute streams K/V blocks over ICI), and every
other op (layernorm, MLP, embedding lookup, the shifted next-token loss)
auto-partitions under jit, XLA inserting the halo/collective traffic.
Parameters are replicated (small-model regime); gradient psums across
shards come out of auto-SPMD.

The reference has no transformer — this extends the framework beyond
parity to show the sequence-parallel design carries a real model: train
sequences n× longer than one chip's memory by adding chips to the seq
axis, at exact-attention quality.
"""

from __future__ import annotations

import contextlib
import dataclasses
import functools
from typing import Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from . import kda as kdalib
from . import latent_attention as latent
from .attention import ring_attention, ulysses_attention
from .kda import KDAConfig
from .latent_attention import MLAConfig, YarnRope, rms_norm
from .moe import (
    MOE_COUNTS, TOP_E, TopKMoEConfig, init_moe, init_topk_moe, moe_ffn, swiglu,
    topk_moe_ffn,
)

ATTENTION_KINDS = ("mha", "swa", "mla", "kda", "conv")
FFN_KINDS = ("dense", "switch", "moe")
# the stats of ``lm_forward_with_stats`` that are counts: they add up
# over layers and steps, where the others are kept by layer and of the
# last step. ``kda_scan_tokens``: token-layers the recurrence of the
# "kda" layers computed (forward count: tokens x such layers).
# ``attn_token_layers`` [2]: tokens x "swa" layers and tokens x "mha"
# layers, of a model that has both (``ATTN_TOKEN_LAYER_KINDS``).
# ``conv_token_layers``: tokens x "conv" layers computed.
# ``attn_gate_token_layers`` [2]: of those of ``attn_token_layers``, the
# token-layers whose output gate was computed (a gated model's)
KDA_SCAN_TOKENS = "kda_scan_tokens"
ATTN_TOKEN_LAYERS = "attn_token_layers"
ATTN_TOKEN_LAYER_KINDS = ("window", "full")
CONV_TOKEN_LAYERS = "conv_token_layers"
ATTN_GATE_TOKEN_LAYERS = "attn_gate_token_layers"
STEP_COUNTS = MOE_COUNTS + (
    KDA_SCAN_TOKENS, ATTN_TOKEN_LAYERS, CONV_TOKEN_LAYERS,
    ATTN_GATE_TOKEN_LAYERS,
)


@dataclasses.dataclass(frozen=True)
class Rope:
    """The rotary tables of one kind of layer: theta and, optionally,
    YaRN's change of the frequencies (``latent_attention.YarnRope``)."""

    theta: float = 10000.0
    yarn: "YarnRope | None" = None


@dataclasses.dataclass(frozen=True)
class LMConfig:
    vocab: int = 256
    d_model: int = 64
    n_heads: int = 4
    n_layers: int = 2
    d_ff: int = 128
    # sequence-parallel attention schedule: "ring" (ppermute K/V ring,
    # O(S/n) memory), "ring_flash" (same ring, but each visiting chunk
    # runs the Pallas flash kernel — O(block) VMEM, scores never hit
    # HBM), "ring_zigzag" (flash over the zigzag-permuted layout for
    # balanced causal work per hop; train via zigzag_lm_arrays +
    # lm_loss_with_targets), or "a2a" (Ulysses: all_to_all seq<->head
    # reshard, dense per-head matmuls; needs n_heads % mesh-axis == 0).
    # "ring_flash" is the MEASURED training default on one v5e chip
    # (BENCH_ONCHIP.md 2026-07-31 04:27/04:30): with the swept 512x512
    # kernel blocking, flash trains the s=8192/bf16 LM at 30.6k tok/s
    # vs the XLA chunk path's 21.1k (1.45x; kernel-level fwd+bwd 19.8k
    # vs 8.8k GFLOP/s, 2.2x) AND keeps O(block) memory where XLA saves
    # per-chunk P matrices. With the original 128x128 blocking this
    # comparison went the OTHER way (14.6k vs 19.4k) — the default
    # follows the measurement, not the architecture diagram.
    attention: str = "ring_flash"
    # >0: every moe_every-th layer's FFN is an expert-parallel MoE
    # (models/moe.py) with n_experts switch-routed experts
    moe_every: int = 0
    n_experts: int = 8
    capacity_factor: float = 2.0
    # rematerialize each decoder layer in the backward pass
    # (jax.checkpoint): activations are recomputed instead of stored, so
    # training memory drops from O(layers * S) activations to O(S) +
    # per-layer recompute — THE long-context memory lever alongside
    # sequence parallelism. Gradients are numerically identical up to
    # compiler reassociation of the recomputed ops. Kept besides each
    # layer's input: the expert layer's choices and the flash kernel's
    # output and log-sum-exp (lm_forward_with_stats says why).
    remat: bool = False
    # "bfloat16" runs decoder activations in bf16 (MXU-native): params
    # and the softmax/logits stay float32, attention accumulates f32
    compute_dtype: str = "float32"
    # sliding-window (local) attention span: each position attends to
    # the `window` most recent tokens only. Implemented by the flash
    # kernels (out-of-window blocks are skipped — O(window)/query), so
    # it requires a flash attention mode; None = full causal attention.
    # It spans the "swa" layers of ``layers`` and leaves the "mha"
    # layers beside them full; in a model without a "swa" layer (the
    # byte LM, the serving forwards) it spans every "mha" layer
    window: "int | None" = None
    # grouped-query attention: K/V carry only this many heads, each
    # serving n_heads/n_kv_heads query heads (1 = MQA). Shrinks wk/wv
    # params AND the decode KV cache by the group factor — the cache is
    # the dominant serving HBM traffic. None = n_heads (standard MHA).
    # The training forward broadcasts K/V over their groups BEFORE the
    # attention kernel (it keeps the parameter saving; the kernels see
    # n_heads heads); only the decode path stays grouped
    n_kv_heads: "int | None" = None
    # width of an "mha" head where it is not d_model / n_heads (wq and
    # the gate [d_model, n_heads * head_dim], wo its transpose's shape);
    # None = d_model // n_heads. Training only: the serving forwards
    # refuse an explicit width by name
    head_dim: "int | None" = None
    # an "mha" or "swa" layer's output gate: out = (att * sigmoid(h W_g))
    # W_o, W_g [d_model, n_heads * head_dim], elementwise. Training only
    attn_gate: bool = False
    # rotary position embedding (RoFormer, Su et al. 2021): q/k head
    # vectors are rotated by position-dependent angles before attention,
    # so scores depend only on RELATIVE offsets — parameter-free and
    # length-extrapolating, vs the default NoPE (causal masking alone
    # carries order). Composes with every schedule here: the training
    # forward rotates on the GLOBAL [B, S] view (GSPMD partitions the
    # position iota with the sequence; zigzag uses its permutation as
    # the position ids), the decode path rotates at the absolute cache
    # slot, and window/GQA are unaffected (rotation acts per head-dim
    # pair before any masking/grouping). Where a model has two kinds of
    # such layer the tables are per kind: ``rope_theta`` and
    # ``rope_yarn`` make the "mha" layers', ``swa_rope`` the "swa"
    # layers' (None: the same table). Each kind's tables are made once,
    # outside the layers. ``rope_kinds`` names the kinds that are rotated
    # where the others are NoPE (("swa",): window layers rotated, full
    # layers not); None = both. YaRN, ``swa_rope`` and ``rope_kinds``
    # train only
    rope: bool = False
    rope_theta: float = 10000.0
    rope_yarn: "YarnRope | None" = None
    swa_rope: "Rope | None" = None
    rope_kinds: "tuple | None" = None
    # RMSNorm over each q and each k head's ``head_dim`` channels before
    # the rotation, one scale vector each a layer (leaves ``q_norm``,
    # ``k_norm`` [head_dim], ``norm_eps``). Training only
    qk_norm: bool = False
    # decode KV-cache storage: None = the compute dtype (bf16 under
    # bfloat16 — the existing behavior); "int8" = per-token-per-head
    # symmetric int8 quantization (one f32 scale per [layer, batch,
    # kv-head, position] row: 4/head_dim = 6.25% over the int8 payload
    # at head_dim 64, i.e. ~0.53x of the bf16 cache it replaces). Decode is
    # cache-bandwidth-bound once GQA narrows the weights (measured:
    # BENCH_ONCHIP.md kv2 decode), so int8 halves the remaining bf16
    # cache traffic; dequantization fuses into the attention einsum.
    # Scores/softmax still accumulate f32. Training is unaffected.
    kv_cache_dtype: "str | None" = None
    # -- what a published decoder needs beyond the byte LM ----------------
    # False: an untied output head ``head`` [d_model, vocab]
    tie_head: bool = True
    # "layernorm" (mean and variance) or "rmsnorm", with ``norm_eps``
    norm: str = "layernorm"
    norm_eps: float = 1e-6
    # the dense FFN: "gelu" (w1, w2) or "swiglu" (w_gate, w_up, w_down:
    # W_down(SiLU(W_gate h) * W_up h))
    ffn_act: str = "gelu"
    # multiply the embedding rows by sqrt(d_model) (the byte LM does)
    scale_emb: bool = True
    # the per-layer description the training forward reads: one
    # ``(attention kind, FFN kind)`` per layer, attention "mha" (q/k/v
    # heads, GQA and rope as above) or "mla" (latent attention, ``mla``
    # below), FFN "dense", "switch" (models/moe.py's top-1 capacity
    # layer, ``n_experts``/``capacity_factor`` above) or "moe" (its
    # dropless top-k layer, ``moe`` below). None = n_layers of "mha"
    # with every ``moe_every``-th FFN "switch". The serving forwards run
    # "mha" with "dense" or "switch" and refuse the others by name.
    # Attention "kda" is the gated delta-rule linear-attention layer
    # (models/kda.py, ``kda`` below): one chip's sequence only.
    # Attention "swa" is an "mha" layer that sees the last ``window``
    # keys, beside "mha" layers that see them all. Attention "conv" is
    # a gated short convolution (LFM2's): [B | C | x~] = h W_in, then
    # (C * conv(B * x~)) W_out, the convolution depthwise and causal over
    # ``conv_size`` tokens (leaves ``conv_in`` [d, 3d], ``conv_taps``
    # [conv_size, d], ``conv_out`` [d, d]); training only
    layers: "tuple | None" = None
    # sandwich norms: the output of each layer's attention (or other
    # operator) and of its FFN normed (``norm``, scales ``post_attn_norm``
    # and ``post_ffn_norm`` [d_model], ones) before its residual add.
    # Training only
    post_norms: bool = False
    mla: "MLAConfig | None" = None
    moe: "TopKMoEConfig | None" = None
    kda: "KDAConfig | None" = None
    conv_size: int = 3

    def __post_init__(self):
        if self.norm not in ("layernorm", "rmsnorm"):
            raise ValueError(
                f"LMConfig.norm must be 'layernorm' or 'rmsnorm', got "
                f"{self.norm!r}"
            )
        if self.ffn_act not in ("gelu", "swiglu"):
            raise ValueError(
                f"LMConfig.ffn_act must be 'gelu' or 'swiglu', got "
                f"{self.ffn_act!r}"
            )
        if self.layers is not None:
            if len(self.layers) != self.n_layers:
                raise ValueError(
                    f"LMConfig.layers describes {len(self.layers)} layers, "
                    f"n_layers is {self.n_layers}"
                )
            for att, ffn in self.layers:
                if att not in ATTENTION_KINDS or ffn not in FFN_KINDS:
                    raise ValueError(
                        f"LMConfig.layers: ({att!r}, {ffn!r}) is not one of "
                        f"attention {ATTENTION_KINDS} x FFN {FFN_KINDS}"
                    )
        kinds = self.layer_kinds
        if any(a == "mla" for a, _ in kinds):
            if self.mla is None:
                raise ValueError("an 'mla' layer needs LMConfig.mla")
            if self.attention == "a2a" or self.window is not None:
                raise ValueError(
                    "an 'mla' layer runs the ring schedules without a "
                    "window: a2a and sliding windows are not built for it"
                )
        if any(a == "kda" for a, _ in kinds) and self.kda is None:
            raise ValueError("a 'kda' layer needs LMConfig.kda")
        if any(a == "conv" for a, _ in kinds):
            if self.attention == "ring_zigzag":
                raise ValueError(
                    "a 'conv' layer mixes neighbouring positions: the "
                    "zigzag layout puts other tokens next to each other"
                )
            if self.conv_size < 1:
                raise ValueError(
                    f"LMConfig.conv_size must be >= 1, got {self.conv_size}"
                )
        if any(a == "swa" for a, _ in kinds) and self.window is None:
            raise ValueError(
                "a 'swa' layer needs LMConfig.window (the span it sees)"
            )
        if any(f == "moe" for _, f in kinds) and self.moe is None:
            raise ValueError("a 'moe' layer needs LMConfig.moe")
        if self.kv_cache_dtype not in (None, "int8"):
            raise ValueError(
                f"LMConfig.kv_cache_dtype must be None or 'int8', got "
                f"{self.kv_cache_dtype!r}"
            )
        if self.attention not in ("ring", "ring_flash", "ring_zigzag", "a2a"):
            raise ValueError(
                f"LMConfig.attention must be 'ring', 'ring_flash', "
                f"'ring_zigzag' or 'a2a', got {self.attention!r} — all "
                "are exact, so a silent fallback would hide the "
                "memory/collective profile choice"
            )
        if self.compute_dtype not in ("float32", "bfloat16"):
            raise ValueError(
                f"LMConfig.compute_dtype must be 'float32' or 'bfloat16', "
                f"got {self.compute_dtype!r}"
            )
        if self.window is not None:
            if self.attention not in ("ring_flash", "ring_zigzag"):
                raise ValueError(
                    "LMConfig.window (sliding-window attention) needs a "
                    "flash attention mode ('ring_flash' or 'ring_zigzag')"
                )
            if self.window < 1:
                raise ValueError(
                    f"LMConfig.window must be >= 1, got {self.window}"
                )
        if self.n_kv_heads is not None:
            if not 1 <= self.n_kv_heads <= self.n_heads:
                raise ValueError(
                    f"LMConfig.n_kv_heads must be in [1, n_heads="
                    f"{self.n_heads}], got {self.n_kv_heads}"
                )
            if self.n_heads % self.n_kv_heads:
                raise ValueError(
                    f"n_heads={self.n_heads} must be a multiple of "
                    f"n_kv_heads={self.n_kv_heads} (each K/V head serves "
                    "an equal group of query heads)"
                )
        if self.rope and self.head_width % 2:
            raise ValueError(
                f"LMConfig.rope pairs head dimensions: head_dim="
                f"{self.head_width} must be even"
            )
        if not self.rope and (
            self.rope_yarn or self.swa_rope or self.rope_kinds is not None
        ):
            raise ValueError(
                "LMConfig.rope_yarn / swa_rope / rope_kinds describe rotary "
                "tables: they need LMConfig.rope"
            )
        if self.rope_kinds is not None and not set(self.rope_kinds) <= {
            "mha", "swa"
        }:
            raise ValueError(
                f"LMConfig.rope_kinds {self.rope_kinds!r}: the rotated kinds "
                "are 'mha' and 'swa'"
            )

    @property
    def kv_heads(self) -> int:
        return self.n_kv_heads if self.n_kv_heads is not None else self.n_heads

    @property
    def head_width(self) -> int:
        """Width of an "mha" head."""
        if self.head_dim is not None:
            return self.head_dim
        return self.d_model // self.n_heads

    @property
    def layer_kinds(self) -> tuple:
        """``(attention kind, FFN kind)`` of every layer."""
        if self.layers is not None:
            return tuple(tuple(kinds) for kinds in self.layers)
        return tuple(
            ("mha", "switch" if _is_moe_layer(self, i) else "dense")
            for i in range(self.n_layers)
        )


def refuse_serving(cfg: LMConfig, where: str) -> None:
    """The cached forwards compute "mha" attention (heads of d_model /
    n_heads, no output gate, ONE ``cfg.window`` and one plain rotary
    table for every layer) over a K/V cache and a dense or switch FFN.
    A layer of another kind ("mla", "kda", "conv", "moe"), windowed layers
    beside full ones ("swa" beside "mha"), rotary tables per kind of
    layer or under YaRN, rotation of one kind of layer only, a q/k norm,
    post-block norms, an explicit head width or an output gate is
    refused by name: decoding it through those would be a different
    model."""
    attention_kinds = {att for att, _ in cfg.layer_kinds}
    if {"swa", "mha"} <= attention_kinds:
        raise NotImplementedError(
            f"{where}: windowed layers beside full ones ('swa' beside "
            "'mha') have no decode path yet (the cached forwards read one "
            "LMConfig.window for every layer, and a ring of window tokens "
            "beside a full cache is not built); this model trains through "
            "lm_forward only"
        )
    if cfg.rope_yarn is not None or cfg.swa_rope is not None:
        raise NotImplementedError(
            f"{where}: the cached forwards rotate by one plain table; "
            "rotary tables per kind of layer or under YaRN "
            "(LMConfig.swa_rope / rope_yarn) train through lm_forward only"
        )
    if cfg.rope_kinds is not None:
        raise NotImplementedError(
            f"{where}: the cached forwards rotate every layer or none; "
            "rotation by kind of layer (LMConfig.rope_kinds: "
            f"{cfg.rope_kinds!r} rotated, the rest NoPE) trains through "
            "lm_forward only"
        )
    if cfg.post_norms:
        raise NotImplementedError(
            f"{where}: the cached forwards add each block's output "
            "unnormed; post-block norms (LMConfig.post_norms: "
            "post_attn_norm, post_ffn_norm) train through lm_forward only"
        )
    if cfg.qk_norm:
        raise NotImplementedError(
            f"{where}: the cached forwards have no q/k norm; "
            "LMConfig.qk_norm trains through lm_forward only"
        )
    for att, ffn in cfg.layer_kinds:
        if att == "kda":
            raise NotImplementedError(
                f"{where}: the gated delta-rule layer ('kda') has no "
                "decode path yet (its recurrent state and convolution "
                "tail are not carried beside a K/V cache); this model "
                "trains through lm_forward only"
            )
        if att == "conv":
            raise NotImplementedError(
                f"{where}: the gated short convolution ('conv') has no "
                "decode path yet (the last conv_size - 1 rows of B * x~ a "
                "layer are not carried beside a K/V cache); this model "
                "trains through lm_forward only"
            )
        if att == "mla":
            raise NotImplementedError(
                f"{where}: latent attention ('mla') has no decode cache "
                "yet (a latent K/V cache is not built); this model trains "
                "through lm_forward only"
            )
        if ffn == "moe":
            raise NotImplementedError(
                f"{where}: the dropless top-k expert layer ('moe', "
                "TopKMoEConfig) is not built into the serving forwards; "
                "this model trains through lm_forward only"
            )
    if cfg.attn_gate or cfg.head_width * cfg.n_heads != cfg.d_model:
        raise NotImplementedError(
            f"{where}: the serving forwards compute heads of d_model / "
            "n_heads without an output gate; LMConfig.head_dim / "
            "attn_gate train through lm_forward only"
        )
    if cfg.norm != "layernorm" or cfg.ffn_act != "gelu" or not (
        cfg.tie_head and cfg.scale_emb
    ):
        raise NotImplementedError(
            f"{where}: the serving forwards compute LayerNorm, a GELU FFN "
            "and a tied, scaled embedding; rmsnorm / swiglu / an untied "
            "head train through lm_forward only"
        )


def init_lm(key: jax.Array, cfg: LMConfig) -> Dict[str, jax.Array]:
    ks = jax.random.split(key, 2 + 4 * cfg.n_layers)
    s = 0.02
    p = {
        "emb": s * jax.random.normal(ks[0], (cfg.vocab, cfg.d_model)),
        "ln_f": jnp.ones((cfg.d_model,)),
    }
    if not cfg.tie_head:
        p["head"] = s * jax.random.normal(ks[1], (cfg.d_model, cfg.vocab))
    for i, (att_kind, ffn_kind) in enumerate(cfg.layer_kinds):
        k1, k2, k3, k4 = ks[2 + 4 * i : 6 + 4 * i]
        p[f"l{i}/ln1"] = jnp.ones((cfg.d_model,))
        p[f"l{i}/ln2"] = jnp.ones((cfg.d_model,))
        if cfg.post_norms:  # no key drawn: the other leaves as ever
            p[f"l{i}/post_attn_norm"] = jnp.ones((cfg.d_model,))
            p[f"l{i}/post_ffn_norm"] = jnp.ones((cfg.d_model,))
        if att_kind == "mla":
            for name, w in latent.init_mla(
                k1, cfg.d_model, cfg.n_heads, cfg.mla, s
            ).items():
                p[f"l{i}/{name}"] = w
        elif att_kind == "kda":
            for name, w in kdalib.init_kda(
                k1, cfg.d_model, cfg.kda, s
            ).items():
                p[f"l{i}/{name}"] = w
        elif att_kind == "conv":
            d = cfg.d_model
            p[f"l{i}/conv_in"] = s * jax.random.normal(k1, (d, 3 * d))
            p[f"l{i}/conv_out"] = s * jax.random.normal(k2, (d, d))
            # torch's Conv1d default: uniform over +-1/sqrt(taps)
            bound = cfg.conv_size ** -0.5
            p[f"l{i}/conv_taps"] = jax.random.uniform(
                jax.random.fold_in(k1, 1), (cfg.conv_size, d),
                minval=-bound, maxval=bound,
            )
        else:
            # separate q/k/v projections (not a fused [d, 3d]): under
            # tensor parallelism each projection column-shards on its
            # own, so the qkv split boundaries stay shard-local (the
            # fused-QKV TP pitfall puts K across two shards and forces
            # per-layer reshards). wq, the gate and wo's rows are
            # n_heads * head_width wide (d_model unless ``head_dim`` says
            # otherwise), wk and wv kv_heads * head_width
            wide = cfg.n_heads * cfg.head_width
            narrow = cfg.kv_heads * cfg.head_width  # GQA: narrow K/V
            wqkv = s * jax.random.normal(k1, (cfg.d_model, 3 * wide))
            wq, wk, wv = jnp.split(wqkv, 3, axis=1)
            p[f"l{i}/wq"] = wq
            p[f"l{i}/wk"], p[f"l{i}/wv"] = wk[:, :narrow], wv[:, :narrow]
            p[f"l{i}/wo"] = s * jax.random.normal(k2, (wide, cfg.d_model))
            if cfg.attn_gate:  # a key of its own: the four above as ever
                p[f"l{i}/wg"] = s * jax.random.normal(
                    jax.random.fold_in(k2, 1), (cfg.d_model, wide)
                )
            if cfg.qk_norm:
                p[f"l{i}/q_norm"] = jnp.ones((cfg.head_width,))
                p[f"l{i}/k_norm"] = jnp.ones((cfg.head_width,))
        if ffn_kind == "switch":
            moe = init_moe(k3, cfg.d_model, cfg.d_ff, cfg.n_experts)
            p[f"l{i}/moe_router"] = moe["router"]
            p[f"l{i}/moe_w_in"] = moe["w_in"]
            p[f"l{i}/moe_w_out"] = moe["w_out"]
        elif ffn_kind == "moe":
            for name, w in init_topk_moe(k3, cfg.d_model, cfg.moe, s).items():
                p[f"l{i}/{name}"] = w
        elif cfg.ffn_act == "swiglu":
            kg, ku = jax.random.split(k3)
            p[f"l{i}/w_gate"] = s * jax.random.normal(kg, (cfg.d_model, cfg.d_ff))
            p[f"l{i}/w_up"] = s * jax.random.normal(ku, (cfg.d_model, cfg.d_ff))
            p[f"l{i}/w_down"] = s * jax.random.normal(k4, (cfg.d_ff, cfg.d_model))
        else:
            p[f"l{i}/w1"] = s * jax.random.normal(k3, (cfg.d_model, cfg.d_ff))
            p[f"l{i}/w2"] = s * jax.random.normal(k4, (cfg.d_ff, cfg.d_model))
    return jax.tree.map(lambda x: x.astype(jnp.float32), p)


def conv_mix(gates, taps):
    """The mix of a "conv" layer: ``gates`` [B, S, 3W] = [B | C | x~]
    (h W_in, split in that order), the output C * conv(B * x~) [B, S, W],
    the convolution depthwise and causal (``kda.causal_conv``: tap j of
    ``taps`` [n, W] weighs position t - n + 1 + j), in ``gates.dtype``."""
    b_gate, c_gate, xt = jnp.split(gates, 3, axis=-1)
    return c_gate * kdalib.causal_conv(b_gate * xt, taps)


def router_bias_names(cfg: LMConfig) -> tuple:
    """The leaves of the routers' selection biases
    (``TopKMoEConfig.expert_bias``), in the order in which the training
    forward's stats stack the expert layers; none without such a bias."""
    if cfg.moe is None or not cfg.moe.expert_bias:
        return ()
    return tuple(
        f"l{i}/router_bias"
        for i, (_, ffn) in enumerate(cfg.layer_kinds) if ffn == "moe"
    )


def _is_moe_layer(cfg: LMConfig, i: int) -> bool:
    return cfg.moe_every > 0 and (i + 1) % cfg.moe_every == 0


def _moe_layer_params(params, i: int):
    """The MoE leaves of layer ``i`` for the serving path (one place —
    three forwards consume this slice)."""
    return {
        "moe_router": params[f"l{i}/moe_router"],
        "moe_w_in": params[f"l{i}/moe_w_in"],
        "moe_w_out": params[f"l{i}/moe_w_out"],
    }


def _moe_ffn_dropless(lp, h2, n_experts: int):
    """Serving-side MoE FFN: DROPLESS per-token top-1 routing.

    The training layer (models/moe.py) drops over-capacity tokens, and
    which tokens drop depends on every other token in the shard — a
    decision incremental decoding cannot reproduce (the cache sees
    tokens one at a time). Serving therefore routes every token
    independently with no capacity: self-consistent across prefill /
    chunk-ingest / one-token decode (the generate-family exactness
    contracts hold), and equal to the training forward whenever the
    training capacity did not bind (capacity_factor >= n_experts
    guarantees that; tests pin it). Math mirrors moe_ffn: routing and
    experts in f32, relu activation, gate-weighted output.

    Implementation is a static per-expert loop with masking — every
    expert's weights are read once regardless of batch (decode is
    weights-bound anyway) and no [T, E, C] dispatch tensor or per-token
    weight gather is materialized. COST NOTE: this computes every
    expert's FFN over all T tokens (n_experts x the dense-FFN FLOPs),
    which is the right trade for the one-token decode step but makes
    MoE PREFILL compute-heavy on long prompts. The sorted form exists:
    ``models/moe.py`` sorts tokens by expert and runs ONE grouped
    product over them (``grouped_matmul``, used by ``topk_moe_ffn``);
    a prefill that needs it calls that function, not a second one."""
    shape = h2.shape
    x = h2.reshape(-1, shape[-1]).astype(jnp.float32)  # [T, d]
    router = lp["moe_router"].astype(jnp.float32)
    gates = jax.nn.softmax(x @ router, axis=-1)  # [T, E]
    expert = jnp.argmax(gates, axis=-1)  # [T]
    gate = jnp.take_along_axis(gates, expert[:, None], axis=1)[:, 0]
    out = jnp.zeros_like(x)
    for e in range(n_experts):
        y = jax.nn.relu(
            x @ lp["moe_w_in"][e].astype(jnp.float32)
        ) @ lp["moe_w_out"][e].astype(jnp.float32)
        out = out + jnp.where((expert == e)[:, None], y, 0.0)
    return (out * gate[:, None]).reshape(shape)


def _ln(x, scale):
    mu = x.mean(-1, keepdims=True)
    var = ((x - mu) ** 2).mean(-1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + 1e-6) * scale


def _rope_tables(positions, head_dim: int, theta: float,
                 yarn: "YarnRope | None" = None):
    """cos/sin rotation tables (f32) for ``apply_rope``: angles are
    pos * theta^(-i/half). Computed in f32 regardless of the activation
    dtype — bf16 positions lose integer precision past 256. Hoist these
    out of per-layer code: they depend only on positions and theta, and
    inside a ``jax.checkpoint`` region they would be recomputed in every
    layer's backward pass. Under ``yarn`` the frequencies and the factor
    on cos and sin are ``latent_attention``'s (the one implementation)."""
    if yarn is None:
        half = head_dim // 2
        inv = theta ** (jnp.arange(half, dtype=jnp.float32) / -half)
    else:
        inv = jnp.asarray(
            latent.rope_inv_freq(head_dim, theta, yarn), jnp.float32
        )
    ang = jnp.asarray(positions, jnp.float32)[..., None] * inv
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    factor = latent.rope_attention_factor(yarn)  # 1.0 without YaRN
    return (cos, sin) if factor == 1.0 else (cos * factor, sin * factor)


def _rotate(x: jax.Array, cos, sin) -> jax.Array:
    """Apply precomputed rotation tables in ``x.dtype``."""
    x1, x2 = jnp.split(x, 2, axis=-1)
    c = cos.astype(x.dtype)
    s = sin.astype(x.dtype)
    return jnp.concatenate([x1 * c - x2 * s, x1 * s + x2 * c], -1)


def apply_rope(x: jax.Array, positions, theta: float = 10000.0) -> jax.Array:
    """Rotary position embedding (RoFormer, Su et al. 2021), GPT-NeoX
    half-split pairing: dimension i of the first half pairs with
    dimension i of the second, each pair rotated by pos * theta^(-i/half).

    ``x`` is [..., head_dim] (head_dim even); ``positions`` is an int
    array broadcastable to ``x.shape[:-1]`` (a scalar for single-slot
    decode, ``[1, S, 1]`` for a [B, S, heads, hd] batch)."""
    cos, sin = _rope_tables(positions, x.shape[-1], theta)
    return _rotate(x, cos, sin)


def _rope_position_ids(cfg: LMConfig, s: int, mesh: Mesh, axis: str):
    """Global position ids for the training forward: natural order, or
    the zigzag permutation when the sequence is laid out zigzag (token
    at layout index j sits at global position perm[j])."""
    if cfg.attention == "ring_zigzag":
        from .attention import zigzag_permutation

        return jnp.asarray(
            zigzag_permutation(s, mesh.shape[axis]), jnp.int32
        )
    return jnp.arange(s, dtype=jnp.int32)


def _layer_params(params: Dict[str, jax.Array], i: int) -> Dict[str, jax.Array]:
    """The i-th decoder layer's parameter sub-dict (explicit argument so
    jax.checkpoint sees them as inputs and differentiates through)."""
    pre = f"l{i}/"
    return {k[len(pre):]: v for k, v in params.items() if k.startswith(pre)}


def lm_forward(
    params: Dict[str, jax.Array],
    tokens: jax.Array,  # [B, S] int32, S sharded over `axis`
    cfg: LMConfig,
    mesh: Mesh,
    axis: str = "data",
) -> jax.Array:
    """Logits [B, S, vocab] (always float32; decoder activations run in
    ``cfg.compute_dtype``, rematerialized per layer when ``cfg.remat``)."""
    return lm_forward_with_stats(params, tokens, cfg, mesh, axis)[0]


def lm_forward_with_stats(params, tokens, cfg: LMConfig, mesh: Mesh,
                          axis: str = "data"):
    """THE training forward: ``(logits, stats)``. Each layer is what
    ``cfg.layer_kinds`` says it is. ``stats`` holds what the dropless
    expert layers returned (``models/moe.topk_moe_ffn``): summed over
    them ``expert_rows`` [held] int32 (rows each held expert computed)
    and ``buffer_passes`` [2] int32 (passes over the sorted buffer's
    head, and how many of them needed its tail), stacked by layer
    ``top_e`` [layers, T, k] (the experts each token chose) and the
    router probes ``probe_x``, ``probe_e``, ``probe_w``; none of these
    for a model with no such layer. With "kda" layers also
    ``kda_scan_tokens`` (int32: tokens x such layers, what their
    recurrence computed in this forward pass) and, stacked by such
    layer, ``probe_kda_state`` and ``probe_kda_g`` (models/kda.py: the
    recurrence's last state and log-decay, in the bits it computed with).
    With "swa" or "conv" layers also ``attn_token_layers`` [2] int32
    (tokens x "swa" layers, tokens x "mha" layers:
    ``ATTN_TOKEN_LAYER_KINDS``), and in a gated model
    ``attn_gate_token_layers`` [2] (those whose output gate was
    computed); with "conv" layers ``conv_token_layers`` (int32: tokens x
    such layers). With a router bias (``TopKMoEConfig.expert_bias``) also
    ``expert_load`` [layers, n_experts], what the step moves it against.
    Layers without an expert layer add nothing to the stacks: their
    index is the expert layers' own (a leading dense layer is not one).

    Named scopes, for the device trace: ``lm_attn`` (an "mha", "swa" or
    "mla" layer's projections and attention; in a model with "swa"
    layers ``attn_window`` or ``attn_full`` nested inside it, by the
    layer's kind; ``attn_gate`` inside it, the output gate's product,
    sigmoid and multiply), ``lm_post_norm`` (a post-block norm, inside
    the scope of the block it norms: ``lm_attn``, ``lm_conv_out``,
    ``lm_ffn``, or ``lm_moe_route`` for an expert layer), ``lm_kda_*``
    (models/kda.py), ``lm_conv_proj`` (a
    "conv" layer's norm and W_in), ``lm_conv_mix`` (the gates and the
    causal convolution), ``lm_conv_out`` (W_out), ``lm_ffn`` or
    ``lm_moe_*`` (models/moe.py), ``lm_head``.
    """
    b, s = tokens.shape
    hd = cfg.head_width
    width = cfg.n_heads * hd  # of q, the gate and the attention's output
    dtype = jnp.bfloat16 if cfg.compute_dtype == "bfloat16" else jnp.float32
    kinds = cfg.layer_kinds
    n_kda = sum(a == "kda" for a, _ in kinds)
    n_swa = sum(a == "swa" for a, _ in kinds)
    if n_kda and mesh.shape[axis] > 1:
        raise NotImplementedError(
            "a 'kda' layer over a sequence-sharded mesh (its state handed "
            f"from chip to chip) is not built: the {axis!r} axis has "
            f"{mesh.shape[axis]} devices"
        )
    if cfg.norm == "rmsnorm":
        norm = functools.partial(rms_norm, eps=cfg.norm_eps)
    else:
        norm = lambda x, scale: _ln(x, scale.astype(x.dtype))  # noqa: E731

    # RoPE tables, computed ONCE on the GLOBAL sequence view (GSPMD
    # shards them with the tokens; zigzag's position ids are its
    # permutation) and closed over by every layer — under remat they
    # enter jax.checkpoint as inputs, not per-layer recomputation
    positions = _rope_position_ids(cfg, s, mesh, axis)
    rope_cs, made = {}, {}  # by kind of layer present and rotated; by Rope
    if cfg.rope:
        full = Rope(cfg.rope_theta, cfg.rope_yarn)
        for kind, rope in (("mha", full), ("swa", cfg.swa_rope or full)):
            if not any(a == kind for a, _ in kinds) or (
                cfg.rope_kinds is not None and kind not in cfg.rope_kinds
            ):
                continue
            if rope not in made:  # two kinds may share one table
                made[rope] = _rope_tables(
                    positions[None, :, None], hd, rope.theta, rope.yarn
                )
            rope_cs[kind] = made[rope]
    mla_cs = mla_qscale = None
    if any(a == "mla" for a, _ in kinds):
        mla_cs = latent.rope_tables(
            positions[None, :, None], cfg.mla, cfg.rope_theta
        )
        # the kernels scale by 1/sqrt(D): the query carries the rest
        mla_qscale = latent.softmax_scale(cfg.mla) * np.sqrt(
            cfg.mla.qk_head_dim
        )
        pos_scale = latent.position_scale(positions, cfg.mla.yarn)
        if pos_scale is not None:
            mla_qscale = mla_qscale * pos_scale
    impl = {
        "ring": "xla", "ring_flash": "flash", "ring_zigzag": "zigzag",
        "a2a": None,
    }[cfg.attention]

    def mha(h, lp, kind):
        cast = lambda k: lp[k].astype(dtype)  # noqa: E731
        q = h @ cast("wq")
        k = h @ cast("wk")
        v = h @ cast("wv")
        if cfg.qk_norm or kind in rope_cs:
            # by head, BEFORE the GQA broadcast: k is still narrow
            q = q.reshape(b, s, cfg.n_heads, hd)
            k = k.reshape(b, s, cfg.kv_heads, hd)
            if cfg.qk_norm:
                q = rms_norm(q, lp["q_norm"], cfg.norm_eps)
                k = rms_norm(k, lp["k_norm"], cfg.norm_eps)
            if kind in rope_cs:
                q = _rotate(q, *rope_cs[kind])
                k = _rotate(k, *rope_cs[kind])
            q = q.reshape(b, s, width)
            k = k.reshape(b, s, cfg.kv_heads * hd)
        if cfg.kv_heads != cfg.n_heads:
            # GQA: broadcast each K/V head over its query-head group up
            # front; every attention schedule below then sees full-width
            # [B, S, n_heads * hd] (training keeps the PARAM saving; the
            # cache saving is the decode path's, which stays grouped)
            def expand(t):
                t = t.reshape(b, s, cfg.kv_heads, 1, hd)
                t = jnp.broadcast_to(
                    t, (b, s, cfg.kv_heads, cfg.n_heads // cfg.kv_heads, hd)
                )
                return t.reshape(b, s, width)

            k = expand(k)
            v = expand(v)

        def heads(t):  # [B, S, d] -> [B*nh, S, hd]
            t = t.reshape(b, s, cfg.n_heads, hd)
            return t.transpose(0, 2, 1, 3).reshape(b * cfg.n_heads, s, hd)

        if cfg.attention == "a2a":
            # Ulysses: q/k/v stay [B, S, d]; the layer splits heads itself
            return ulysses_attention(
                q, k, v, mesh=mesh, axis=axis, n_heads=cfg.n_heads,
                causal=True,
            )
        att = ring_attention(
            heads(q), heads(k), heads(v), mesh=mesh, axis=axis,
            causal=True, impl=impl,
            # the "swa" layers' span; every layer's where none is "swa"
            window=cfg.window if kind == "swa" or not n_swa else None,
        )
        return (
            att.reshape(b, cfg.n_heads, s, hd)
            .transpose(0, 2, 1, 3)
            .reshape(b, s, width)
        )

    def gated(att, h, lp):
        """An "mha" or "swa" layer's output times its gate, sigmoid(h
        W_g), where the model has one."""
        if not cfg.attn_gate:
            return att
        with jax.named_scope("attn_gate"):
            gate = jax.nn.sigmoid(jnp.dot(
                h, lp["wg"].astype(dtype), preferred_element_type=jnp.float32
            ))
            return (att.astype(jnp.float32) * gate).astype(dtype)

    def residual(x, y, lp, name):
        """x + y, y normed by ``lp[name]`` first where the model has
        post-block norms."""
        if cfg.post_norms:
            with jax.named_scope("lm_post_norm"):
                y = norm(y, lp[name])
        return x + y

    def mla(h, lp):
        m = cfg.mla
        q, k, v = latent.mla_qkv(
            h, lp, m, cfg.n_heads, cfg.norm_eps, mla_cs, mla_qscale, dtype
        )
        att = ring_attention(
            q, k, v, mesh=mesh, axis=axis, causal=True, impl=impl,
        )  # [B*H, S, qk_head_dim]: v's zero pad comes back as zeros
        return (
            att.reshape(b, cfg.n_heads, s, m.qk_head_dim)[..., : m.v_head_dim]
            .transpose(0, 2, 1, 3)
            .reshape(b, s, cfg.n_heads * m.v_head_dim)
        )

    def layer(x, lp, att_kind, ffn_kind):
        cast = lambda k: lp[k].astype(dtype)  # noqa: E731
        stats = {}
        if att_kind == "kda":
            with jax.named_scope("lm_kda_proj"):
                h = norm(x, lp["ln1"])
            att, stats = kdalib.kda_attention(
                h, lp, cfg.kda, cfg.norm_eps, dtype
            )
            x = residual(x, att, lp, "post_attn_norm")
        elif att_kind == "conv":
            with jax.named_scope("lm_conv_proj"):
                gates = norm(x, lp["ln1"]) @ cast("conv_in")
            with jax.named_scope("lm_conv_mix"):
                mixed = conv_mix(gates, cast("conv_taps"))
            with jax.named_scope("lm_conv_out"):
                x = residual(x, mixed @ cast("conv_out"), lp, "post_attn_norm")
        else:
            # a model with "swa" layers names each layer's kind
            inner = contextlib.nullcontext() if not n_swa else (
                jax.named_scope(
                    "attn_window" if att_kind == "swa" else "attn_full"
                )
            )
            with jax.named_scope("lm_attn"), inner:
                h = norm(x, lp["ln1"])
                att = (
                    mla(h, lp) if att_kind == "mla"
                    else gated(mha(h, lp, att_kind), h, lp)
                )
                x = residual(
                    x, att.astype(dtype) @ cast("wo"), lp, "post_attn_norm"
                )
        h2 = norm(x, lp["ln2"])
        if ffn_kind == "switch":
            moe_p = {
                "router": lp["moe_router"],
                "w_in": lp["moe_w_in"],
                "w_out": lp["moe_w_out"],
            }
            # MoE routing (top-1 argmax + capacity bookkeeping) stays in
            # the params' dtype — f32 — for stable expert selection
            with jax.named_scope("lm_ffn"):
                x = residual(x, moe_ffn(
                    moe_p, h2.astype(jnp.float32), mesh=mesh, axis=axis,
                    capacity_factor=cfg.capacity_factor,
                ).astype(dtype), lp, "post_ffn_norm")
        elif ffn_kind == "moe":
            y, moe_stats = topk_moe_ffn(lp, h2, cfg.moe, dtype)
            # a post-norm counts with the expert layer it norms
            with jax.named_scope(
                "lm_moe_route"
            ) if cfg.post_norms else contextlib.nullcontext():
                x = residual(x, y, lp, "post_ffn_norm")
            stats = {**stats, **moe_stats}
        else:
            with jax.named_scope("lm_ffn"):
                if cfg.ffn_act == "swiglu":
                    y = swiglu(
                        h2, cast("w_gate"), cast("w_up"), cast("w_down")
                    )
                else:
                    y = jax.nn.gelu(h2 @ cast("w1")) @ cast("w2")
                x = residual(x, y, lp, "post_ffn_norm")
        return x, stats

    if cfg.remat:
        # everything is recomputed but, beside the layer's input (which
        # jax.checkpoint always keeps): the expert layer's choices,
        # because a near-tie decided again could fall the other way; and
        # the flash kernel's output and log-sum-exp, because they cost
        # O(S^2 * d) to make and O(S * d) to keep, B*S*H*2 bytes a device
        # a layer at bf16 (over a ring of n chips, n chunk outputs of S/n
        # positions each: the same bytes whatever n), as much as one more
        # layer input. The XLA attention modes bear no such name.
        from ..ops.flash_attention import FLASH_LSE, FLASH_OUT

        layer = jax.checkpoint(
            layer, static_argnums=(2, 3),
            policy=jax.checkpoint_policies.save_only_these_names(
                TOP_E, FLASH_OUT, FLASH_LSE
            ),
        )

    x = params["emb"][tokens]
    if cfg.scale_emb:
        x = x * np.sqrt(cfg.d_model)
    x = x.astype(dtype)
    per_layer = []
    for i, (att_kind, ffn_kind) in enumerate(kinds):
        x, stats = layer(x, _layer_params(params, i), att_kind, ffn_kind)
        if stats:
            per_layer.append(stats)
    total = {}
    for k in sorted({k for stats in per_layer for k in stats}):
        have = [stats[k] for stats in per_layer if k in stats]
        total[k] = sum(have) if k in MOE_COUNTS else jnp.stack(have)
    if n_kda:
        total[KDA_SCAN_TOKENS] = jnp.asarray(b * s * n_kda, jnp.int32)
    n_conv = sum(a == "conv" for a, _ in kinds)
    if n_swa or n_conv:
        n_full = sum(a == "mha" for a, _ in kinds)
        total[ATTN_TOKEN_LAYERS] = jnp.asarray(
            [b * s * n_swa, b * s * n_full], jnp.int32
        )
        if cfg.attn_gate:  # every such layer is gated
            total[ATTN_GATE_TOKEN_LAYERS] = total[ATTN_TOKEN_LAYERS]
    if n_conv:
        total[CONV_TOKEN_LAYERS] = jnp.asarray(b * s * n_conv, jnp.int32)
    with jax.named_scope("lm_head"):
        xn = norm(x.astype(jnp.float32), params["ln_f"])
        # tied or not, the head's product in ``dtype``, accumulated in f32
        head = params["emb"].T if cfg.tie_head else params["head"]
        logits = jnp.dot(
            xn.astype(dtype), head.astype(dtype),
            preferred_element_type=jnp.float32,
        )
    return logits, total


def _quant_kv_i8(x):
    """Symmetric per-row int8: x [..., hd] -> (int8 rows, f32 scale per
    row). scale = max|x|/127 so the row's peak maps to ±127; an all-zero
    row gets scale 0 and quantizes to zeros (dequant is exact there)."""
    amax = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=-1)
    scale = amax / 127.0
    q = jnp.round(
        x.astype(jnp.float32) / jnp.maximum(scale, 1e-30)[..., None]
    ).astype(jnp.int8)
    return q, scale


def _cache_write(cache, idx, val):
    """Write ``val`` [..., hd] into a cache pytree at slice ``idx``.
    Cache is ``(data, scale)``: scale None = plain dtype cast (the
    existing path); scale array = int8 data + per-row scales. ``idx``
    indexes [layer, :, :, position(s)] on both arrays."""
    data, scale = cache
    if scale is None:
        return (data.at[idx].set(val.astype(data.dtype)), None)
    q, s = _quant_kv_i8(val)
    return (data.at[idx].set(q), scale.at[idx].set(s))


def _cache_layer(cache, i):
    """Layer ``i`` of a cache pytree as f32 [B, kvh, T, hd] — for int8
    the per-row dequant multiply fuses into the consuming einsum (the
    HBM read stays 1 byte/element + scales)."""
    data, scale = cache
    full = data[i].astype(jnp.float32)
    if scale is not None:
        full = full * scale[i][..., None]
    return full


def _alloc_kv_caches(cfg: LMConfig, b: int, total: int):
    """(kcache, vcache) pytrees for ``total`` slots — the ONE home of
    the cache layout/dtype policy (lm_generate and speculative decoding
    both allocate here). Caches live in the compute dtype (bf16 halves
    per-token cache streaming) or, under ``kv_cache_dtype="int8"``, as
    (int8 data, f32 per-row scale); ``cfg.kv_heads`` not n_heads —
    under GQA the cache carries only the K/V heads."""
    refuse_serving(cfg, "_alloc_kv_caches")
    hd = cfg.d_model // cfg.n_heads
    shape = (cfg.n_layers, b, cfg.kv_heads, total, hd)
    dtype = (
        jnp.bfloat16 if cfg.compute_dtype == "bfloat16" else jnp.float32
    )
    if cfg.kv_cache_dtype == "int8":
        k = (jnp.zeros(shape, jnp.int8), jnp.zeros(shape[:-1], jnp.float32))
    else:
        k = (jnp.zeros(shape, dtype), None)
    return k, jax.tree.map(jnp.zeros_like, k)


def _cache_write_rows(cache, i, qpos, val):
    """Write ``val`` [B, C, kvh, hd] into layer ``i`` at PER-ROW
    absolute positions ``qpos`` [B, C]. Advanced-index layout: indexing
    data[i] with (rows [B,1], :, qpos [B,C]) puts the broadcast [B, C]
    dims first -> slot shape [B, C, kvh, hd], matching val."""
    data, scale = cache
    rows = jnp.arange(val.shape[0])[:, None]
    if scale is None:
        return (data.at[i, rows, :, qpos].set(val.astype(data.dtype)), None)
    q, s = _quant_kv_i8(val)
    return (
        data.at[i, rows, :, qpos].set(q),
        scale.at[i, rows, :, qpos].set(s),
    )


def _chunk_decode(params, cfg: LMConfig, toks, kcache, vcache, pos):
    """The ONE home of cached decoding: ``toks`` [B, C] live at
    absolute positions ``pos[:, None] + arange(C)`` (per-row ``pos``
    [B]). Writes both caches at those slots — each chunk position
    attends everything cached up to itself, including earlier chunk
    positions — and returns (logits [B, C, vocab], caches). C=1 is the
    lm_generate scan step (see :func:`_decode_step`); C=gamma+1 is
    speculative decoding's target verify pass. Runs in
    ``cfg.compute_dtype`` like the training forward (softmax and
    logits in f32), so decode matches training numerics dtype for
    dtype."""
    refuse_serving(cfg, "_chunk_decode")
    b, c = toks.shape
    nh = cfg.n_heads
    kvh = cfg.kv_heads
    g = nh // kvh  # query heads per K/V head (1 = MHA)
    hd = cfg.d_model // nh
    t_max = kcache[0].shape[3]
    dtype = jnp.bfloat16 if cfg.compute_dtype == "bfloat16" else jnp.float32
    x = (params["emb"][toks] * np.sqrt(cfg.d_model)).astype(dtype)  # [B,C,d]
    qpos = pos[:, None] + jnp.arange(c)  # [B, C]
    t_range = jnp.arange(t_max)
    keep = t_range[None, None, :] <= qpos[..., None]  # [B, C, T]
    if cfg.window is not None:  # sliding window, mirroring lm_forward
        keep &= (qpos[..., None] - t_range[None, None, :]) < cfg.window
    rope_cs = (
        _rope_tables(qpos, hd, cfg.rope_theta) if cfg.rope else None
    )
    for i in range(cfg.n_layers):
        cast = lambda k: params[f"l{i}/{k}"].astype(dtype)  # noqa: E731,B023
        h = _ln(x, cast("ln1"))
        q = (h @ cast("wq")).reshape(b, c, kvh, g, hd)
        k = (h @ cast("wk")).reshape(b, c, kvh, hd)
        v = (h @ cast("wv")).reshape(b, c, kvh, hd)
        if cfg.rope:  # rotate at the absolute slot; the cache stores
            # ROTATED k, matching the prefill/training convention
            cos, sin = rope_cs
            q = _rotate(q, cos[:, :, None, None, :], sin[:, :, None, None, :])
            k = _rotate(k, cos[:, :, None, :], sin[:, :, None, :])
        kcache = _cache_write_rows(kcache, i, qpos, k)
        vcache = _cache_write_rows(vcache, i, qpos, v)
        s = jnp.einsum(
            "bckgd,bktd->bckgt",
            q.astype(jnp.float32),
            _cache_layer(kcache, i),
        ) / np.sqrt(hd)
        s = jnp.where(keep[:, :, None, None, :], s, -1e30)
        p = jax.nn.softmax(s, axis=-1)
        att = (
            jnp.einsum("bckgt,bktd->bckgd", p, _cache_layer(vcache, i))
            .reshape(b, c, cfg.d_model)
            .astype(dtype)
        )
        x = x + att @ cast("wo")
        h2 = _ln(x, cast("ln2"))
        if _is_moe_layer(cfg, i):
            x = x + _moe_ffn_dropless(
                _moe_layer_params(params, i), h2, cfg.n_experts
            ).astype(dtype)
        else:
            x = x + jax.nn.gelu(h2 @ cast("w1")) @ cast("w2")
    x32 = x.astype(jnp.float32)
    return _ln(x32, params["ln_f"]) @ params["emb"].T, kcache, vcache


def _decode_step(params, cfg: LMConfig, tok, kcache, vcache, pos):
    """One KV-cached decoder step (lm_generate's scan body): tok [B],
    SCALAR pos. This is the specialized fast path of
    :func:`_chunk_decode` (C=1, uniform position): the scalar position
    lets cache writes lower to dynamic-update-slice instead of the
    per-row scatter and keeps the mask/rope tables scalar — measured
    ~2x per-token over routing through the generic chunk path. The two
    must stay semantically identical; tests/test_transformer.py pins
    ``_decode_step == _chunk_decode`` output across rope/GQA/window/
    int8 variants so they cannot drift."""
    refuse_serving(cfg, "_decode_step")
    b = tok.shape[0]
    nh = cfg.n_heads
    kvh = cfg.kv_heads
    g = nh // kvh  # query heads per K/V head (1 = MHA)
    hd = cfg.d_model // nh
    t_max = kcache[0].shape[3]
    dtype = jnp.bfloat16 if cfg.compute_dtype == "bfloat16" else jnp.float32
    x = (params["emb"][tok] * np.sqrt(cfg.d_model)).astype(dtype)  # [B, d]
    t_range = jnp.arange(t_max)
    keep = t_range <= pos
    if cfg.window is not None:  # sliding window, mirroring lm_forward
        keep &= (pos - t_range) < cfg.window
    mask = keep[None, None, None, :]  # [1, 1, 1, T]
    rope_cs = (
        _rope_tables(pos, hd, cfg.rope_theta) if cfg.rope else None
    )
    for i in range(cfg.n_layers):
        cast = lambda k: params[f"l{i}/{k}"].astype(dtype)  # noqa: E731,B023
        h = _ln(x, cast("ln1"))
        q = (h @ cast("wq")).reshape(b, kvh, g, hd)
        k = (h @ cast("wk")).reshape(b, kvh, hd)
        v = (h @ cast("wv")).reshape(b, kvh, hd)
        if cfg.rope:  # rotate at the absolute slot; the cache stores
            # ROTATED k, matching the prefill/training convention
            q = _rotate(q, *rope_cs)
            k = _rotate(k, *rope_cs)
        kcache = _cache_write(kcache, (i, slice(None), slice(None), pos), k)
        vcache = _cache_write(vcache, (i, slice(None), slice(None), pos), v)
        s = jnp.einsum(
            "bkgd,bktd->bkgt", q.astype(jnp.float32), _cache_layer(kcache, i)
        ) / np.sqrt(hd)
        s = jnp.where(mask, s, -1e30)
        p = jax.nn.softmax(s, axis=-1)
        att = (
            jnp.einsum("bkgt,bktd->bkgd", p, _cache_layer(vcache, i))
            .reshape(b, cfg.d_model)
            .astype(dtype)
        )
        x = x + att @ cast("wo")
        h2 = _ln(x, cast("ln2"))
        if _is_moe_layer(cfg, i):
            x = x + _moe_ffn_dropless(
                _moe_layer_params(params, i), h2, cfg.n_experts
            ).astype(dtype)
        else:
            x = x + jax.nn.gelu(h2 @ cast("w1")) @ cast("w2")
    x32 = x.astype(jnp.float32)
    return _ln(x32, params["ln_f"]) @ params["emb"].T, kcache, vcache


def _chunked_causal_attn(q, k, v, window, chunk: int = 256):
    """Causal attention, q [B, P, nh, hd] x k/v [B, P, kvh, hd] ->
    [B, P, nh*hd], scanned over query blocks: transient memory is ONE
    [B, kvh, g, chunk, P] score block instead of the full [B, nh, P, P]
    tensor (which at batch 8, 8 heads, P=2048 would be >1 GB f32 per
    layer). K/V stay at their NARROW head count (kvh <= nh, GQA) — the
    grouped einsums never materialize the broadcast."""
    b, p_len, nh, hd = q.shape
    kvh = k.shape[2]
    g = nh // kvh  # query heads per K/V head (1 = MHA)
    c = min(chunk, p_len)
    pad = (-p_len) % c
    qp = jnp.pad(q, ((0, 0), (0, pad), (0, 0), (0, 0)))
    nc = (p_len + pad) // c
    k32 = k.astype(jnp.float32)
    v32 = v.astype(jnp.float32)
    kpos = jnp.arange(p_len)

    def body(_, inp):
        ci, qblk = inp  # qblk [B, c, nh, hd] -> grouped [B, c, kvh, g, hd]
        qg = qblk.astype(jnp.float32).reshape(b, c, kvh, g, hd)
        qpos = ci * c + jnp.arange(c)
        keep = qpos[:, None] >= kpos[None, :]
        if window is not None:  # sliding window, mirroring _decode_step
            keep &= (qpos[:, None] - kpos[None, :]) < window
        s = jnp.einsum("bqhgd,bthd->bhgqt", qg, k32) / np.sqrt(hd)
        s = jnp.where(keep[None, None, None], s, -1e30)
        p = jax.nn.softmax(s, axis=-1)
        att = jnp.einsum("bhgqt,bthd->bqhgd", p, v32)
        return None, att.reshape(b, c, nh * hd)

    _, out = jax.lax.scan(
        body, None,
        (jnp.arange(nc), jnp.moveaxis(qp.reshape(b, nc, c, nh, hd), 1, 0)),
    )
    out = jnp.moveaxis(out, 0, 1).reshape(b, nc * c, nh * hd)
    return out[:, :p_len]


def _prefill_attention(q, k, v, window, use_flash=None, interpret=False):
    """Prefill attention dispatch: q [B, P, nh, hd], k/v [B, P, kvh, hd]
    -> [B, P, nh*hd]. On a one-chip TPU process the Pallas flash kernel
    does the O(P^2) work (MXU-shaped matmuls, O(block) VMEM, window
    blocks skipped); elsewhere the chunked XLA path bounds transient
    memory. ``use_flash=None`` auto-selects; tests force the flash
    path in interpret mode and compare against the chunked path.

    One chip only, because the serving forwards run under plain jits
    that GSPMD partitions when the weights are Megatron-split or
    replicated over a mesh, and a Mosaic kernel cannot ride that
    ("Mosaic kernels cannot be automatically partitioned. Please wrap
    the call in a shard_map" — TP decode on four TPU v5 lite chips).
    Whether the enclosing jit spans several chips is not visible at
    trace time; the process's device count is."""
    from ..ops import use_pallas
    from ..ops.flash_attention import flash_mha

    if use_flash is None:
        use_flash = use_pallas() and jax.device_count() == 1
    if not use_flash:
        return _chunked_causal_attn(q, k, v, window)
    b, p_len, nh, hd = q.shape
    kvh = k.shape[2]
    # flash_mha owns the head fold + GQA group-broadcast (one home for
    # the kv-major head-order convention, shared with training)
    return flash_mha(
        q.reshape(b, p_len, nh * hd),
        k.reshape(b, p_len, kvh * hd),
        v.reshape(b, p_len, kvh * hd),
        nh, n_kv_heads=kvh, causal=True, window=window,
        use_pallas=True, interpret=interpret,
    )


def _prefill(params, cfg: LMConfig, prompt, kcache, vcache):
    """Batched prompt ingestion: ONE causal forward over [B, P] writes
    cache slots [0, P) for every layer and returns all prompt logits
    [B, P, vocab] — O(1) forward passes instead of P sequential decode
    iterations (for a 2048-token prompt that is the serving-latency
    difference between one batched pass and 2048 scan steps). Numerics
    mirror ``_decode_step`` op for op: compute in ``cfg.compute_dtype``,
    scores/softmax/logits in f32, caches stored in the caller's cache
    dtype (the compute dtype — bf16 under bfloat16); attention runs in
    query chunks so transient memory stays bounded."""
    refuse_serving(cfg, "_prefill")
    b, p_len = prompt.shape
    nh = cfg.n_heads
    kvh = cfg.kv_heads
    hd = cfg.d_model // nh
    dtype = jnp.bfloat16 if cfg.compute_dtype == "bfloat16" else jnp.float32
    x = (params["emb"][prompt] * np.sqrt(cfg.d_model)).astype(dtype)
    rope_cs = (
        _rope_tables(
            jnp.arange(p_len)[None, :, None], hd, cfg.rope_theta
        )
        if cfg.rope
        else None
    )
    for i in range(cfg.n_layers):
        cast = lambda k: params[f"l{i}/{k}"].astype(dtype)  # noqa: E731,B023
        h = _ln(x, cast("ln1"))
        q = (h @ cast("wq")).reshape(b, p_len, nh, hd)
        k = (h @ cast("wk")).reshape(b, p_len, kvh, hd)
        v = (h @ cast("wv")).reshape(b, p_len, kvh, hd)
        if cfg.rope:
            q = _rotate(q, *rope_cs)
            k = _rotate(k, *rope_cs)
        idx = (i, slice(None), slice(None), slice(None, p_len))
        kcache = _cache_write(kcache, idx, jnp.swapaxes(k, 1, 2))
        vcache = _cache_write(vcache, idx, jnp.swapaxes(v, 1, 2))
        att = _prefill_attention(q, k, v, cfg.window).astype(dtype)
        x = x + att @ cast("wo")
        h2 = _ln(x, cast("ln2"))
        if _is_moe_layer(cfg, i):
            x = x + _moe_ffn_dropless(
                _moe_layer_params(params, i), h2, cfg.n_experts
            ).astype(dtype)
        else:
            x = x + jax.nn.gelu(h2 @ cast("w1")) @ cast("w2")
    x32 = x.astype(jnp.float32)
    logits = _ln(x32, params["ln_f"]) @ params["emb"].T
    return logits, kcache, vcache


def _pick_token(logits, k_step, temperature, top_p, *, greedy, top_k,
                has_top_p):
    """Greedy argmax or temperature/top-k/top-p sampling of one token
    per row — shared by lm_generate and lm_generate_continue."""
    if greedy:
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)
    z = logits / temperature
    if top_k is not None:
        kth = jnp.sort(z, axis=-1)[:, -top_k][:, None]
        z = jnp.where(z >= kth, z, -jnp.inf)
    if has_top_p:
        # nucleus: keep the smallest sorted prefix with cumulative
        # probability >= top_p. A token stays iff the cumulative mass
        # STRICTLY BEFORE it (descending order) is < top_p — the
        # argmax token always survives (cum-before = 0 < top_p)
        zs = jnp.sort(z, axis=-1)[:, ::-1]  # descending
        ps = jax.nn.softmax(zs, axis=-1)
        before = jnp.cumsum(ps, axis=-1) - ps
        zs_masked = jnp.where(before < top_p, zs, jnp.inf)
        cutoff = jnp.min(zs_masked, axis=-1, keepdims=True)
        z = jnp.where(z >= cutoff, z, -jnp.inf)
    return jax.random.categorical(k_step, z, axis=-1).astype(jnp.int32)


@dataclasses.dataclass(frozen=True)
class GenState:
    """Resumable generation state (multi-turn serving). Opaque to
    callers. ``capacity`` (cache slots) bounds how far
    :func:`lm_generate_continue` can extend.

    Two boundary shapes exist, and the state records which:
    ``boundary_cached=False`` — the last token's cache slot is NOT yet
    written (a generation scan ended; the same invariant speculative
    decoding uses) and the continuation processes it first.
    ``boundary_cached=True`` — every token's slot IS written (prefill-
    only or ingest-only states) and ``last_logits`` carries the next-
    token logits so the continuation never recomputes (and never
    re-writes) an already-cached slot: every path stays EXACTLY equal
    to single-shot generation."""

    kcache: tuple
    vcache: tuple
    last_tok: jax.Array  # [B] int32
    length: int  # tokens emitted so far (prompt + generated)
    boundary_cached: bool = False
    last_logits: "jax.Array | None" = None  # [B, vocab], f32

    @property
    def capacity(self) -> int:
        return self.kcache[0].shape[3]


def _validate_prompt_lengths(prompt_lengths, prompt) -> jax.Array:
    """Shared ragged-batch validation (lm_generate + speculative):
    out-of-range lengths would SILENTLY produce garbage under jit
    (clamped gathers, dropped scatters) — fail here where the values
    are concrete."""
    lens_np = np.asarray(prompt_lengths)
    if lens_np.ndim != 1 or lens_np.shape[0] != prompt.shape[0]:
        raise ValueError(
            f"prompt_lengths must be [B={prompt.shape[0]}], got "
            f"shape {lens_np.shape}"
        )
    if lens_np.min() < 1 or lens_np.max() > prompt.shape[1]:
        raise ValueError(
            "prompt_lengths must lie in [1, padded width="
            f"{prompt.shape[1]}], got range "
            f"[{lens_np.min()}, {lens_np.max()}]"
        )
    return jnp.asarray(lens_np, jnp.int32)


def _sampling_args(cfg, temperature, top_k, top_p, key):
    """Shared wrapper-side validation for the generate family; returns
    (greedy, temperature-array, top_p-array, key)."""
    concrete = isinstance(temperature, (int, float))
    greedy = temperature is None or (concrete and temperature == 0)
    if concrete and temperature < 0:
        raise ValueError(f"temperature must be >= 0, got {temperature}")
    if not greedy and key is None:
        raise ValueError("sampling (temperature > 0) needs a PRNG key")
    if top_k is not None:
        if greedy:
            raise ValueError(
                "top_k requires sampling — pass temperature > 0 (greedy "
                "argmax would silently ignore the truncation)"
            )
        if not 1 <= top_k <= cfg.vocab:
            raise ValueError(
                f"top_k must be in [1, vocab={cfg.vocab}], got {top_k}"
            )
    if top_p is not None:
        if greedy:
            raise ValueError(
                "top_p requires sampling — pass temperature > 0 (greedy "
                "argmax would silently ignore the truncation)"
            )
        if not 0.0 < top_p <= 1.0:
            raise ValueError(f"top_p must be in (0, 1], got {top_p}")
    if key is None:
        key = jax.random.PRNGKey(0)  # unused by the greedy path
    if greedy:
        temperature = 1.0  # dead operand on the greedy trace
    return (
        greedy,
        jnp.asarray(temperature, jnp.float32),
        jnp.asarray(1.0 if top_p is None else top_p, jnp.float32),
        key,
    )


def lm_generate(
    params: Dict[str, jax.Array],
    prompt: jax.Array,  # [B, P] int32
    cfg: LMConfig,
    steps: int,
    *,  # options are keyword-only: inserting new ones can never silently
    # rebind a positional caller's arguments
    return_logits: bool = False,
    return_state: bool = False,
    max_len: "int | None" = None,
    prompt_lengths: "jax.Array | None" = None,
    eos_id: "int | None" = None,
    temperature=None,
    top_k: "int | None" = None,
    top_p: "float | None" = None,
    key: "jax.Array | None" = None,
) -> jax.Array:
    """KV-cached decoding (the serving path — single device; the
    sharded-mesh schedules are the TRAINING story): ingests the prompt
    with ONE batched causal forward that fills the KV caches
    (``_prefill``), then a lax.scan extends it ``steps`` tokens one at a
    time. Sampling consumes one PRNG split for the first generated token
    plus one per scan step (NOT one per prompt position — the per-token
    prompt walk is gone).

    ``eos_id`` freezes a row after it EMITS that token: the rest of
    its fixed-length budget fills with the pad token 0 ("eos then
    pads" — lax.scan cannot end early, so all rows still run
    ``steps`` iterations; frozen rows keep caching their pad tokens,
    which nothing meaningful attends). Works in dense and ragged
    modes.

    ``prompt_lengths`` [B] enables RAGGED batches: ``prompt`` is
    right-padded to a common width and each row decodes from its own
    length — row b's continuation lands at positions
    ``[len_b, len_b + steps)``, and under GREEDY decoding every row's
    output equals what a single-row call on its unpadded prompt would
    produce (pad slots are progressively OVERWRITTEN by generated
    tokens, and the per-row position masks in the chunked decode path
    never attend a slot that still holds pad garbage). Sampled rows
    see the same DISTRIBUTION but not the same draws as a single-row
    call — the per-step Gumbel noise is shaped by the batch. Positions past ``len_b + steps`` in the
    returned array are zeros. Ragged mode returns tokens only
    (``return_logits``/``return_state`` are dense-batch features).
    ``temperature=None`` (or 0) is greedy argmax; otherwise samples from
    softmax(logits/temperature), optionally truncated to the ``top_k``
    most likely tokens and/or the nucleus holding ``top_p`` probability
    mass (smallest prefix of the sorted distribution with cumulative
    probability >= top_p; both filters compose — k-truncate, then
    nucleus). Sampling needs ``key``. A non-zero temperature is a
    TRACED operand of the jitted core — sweeping it does not recompile
    the decode scan. Returns [B, P+steps]. MoE layers are served with DROPLESS
    per-token routing (see :func:`_moe_ffn_dropless` — capacity drops
    are a whole-batch decision incremental decoding cannot reproduce;
    outputs match the training forward exactly whenever its capacity
    did not bind).

    ``return_state=True`` appends a :class:`GenState` to the return —
    resumable by :func:`lm_generate_continue` for multi-turn serving
    without re-prefilling the history; pass ``max_len`` to pre-size
    the caches for the expected conversation length (default: exactly
    prompt+steps, leaving no continuation headroom).

    This wrapper is EAGER on purpose: argument validation (greedy
    detection, sign/range checks) needs concrete Python values, which a
    jitted body never sees — the heavy lifting lives in the jitted core
    below."""
    greedy, temperature, top_p_arr, key = _sampling_args(
        cfg, temperature, top_k, top_p, key
    )
    total = prompt.shape[1] + steps
    capacity = max_len if max_len is not None else total
    if capacity < total:
        raise ValueError(
            f"max_len={max_len} < prompt+steps={total}: the caches "
            "cannot hold the generation being requested"
        )
    if eos_id is not None and not 0 <= eos_id < cfg.vocab:
        raise ValueError(
            f"eos_id must be in [0, vocab={cfg.vocab}), got {eos_id}"
        )
    if eos_id is not None and (return_state or return_logits):
        # a frozen row's GenState is poisoned (pad tokens fill its
        # cache, last_tok is the pad) and its gen_logits tail no longer
        # satisfies "row t predicts token t+1" — reject rather than
        # hand back silently-wrong continuations/parity hooks
        raise ValueError(
            "eos_id does not compose with return_state/return_logits: "
            "frozen rows cache pad tokens, which breaks the multi-turn "
            "and logits-parity contracts"
        )
    # eos rides as a TRACED operand (same contract as temperature/
    # top_p: serving different stop tokens must not recompile); only
    # its PRESENCE is static
    eos_arr = jnp.asarray(
        0 if eos_id is None else eos_id, jnp.int32
    )
    if prompt_lengths is not None:
        if return_logits or return_state:
            raise ValueError(
                "prompt_lengths (ragged batches) does not compose with "
                "return_logits/return_state — pad-split the batch or "
                "use the dense path for those"
            )
        if steps == 0:
            raise ValueError("ragged generation needs steps >= 1")
        return _lm_generate_ragged_jit(
            params, prompt, _validate_prompt_lengths(prompt_lengths, prompt),
            temperature, top_p_arr, key,
            cfg=cfg, steps=steps, top_k=top_k,
            has_top_p=top_p is not None, greedy=greedy, capacity=capacity,
            eos=eos_arr, has_eos=eos_id is not None,
        )
    # top_p rides as a TRACED operand (sweeping it must not recompile,
    # same contract as temperature); only its PRESENCE is static, so the
    # disabled path pays no sort/cumsum
    out = _lm_generate_jit(
        params, prompt, temperature, top_p_arr, key,
        cfg=cfg, steps=steps, return_logits=return_logits, top_k=top_k,
        has_top_p=top_p is not None, greedy=greedy, capacity=capacity,
        return_state=return_state, eos=eos_arr, has_eos=eos_id is not None,
    )
    if not return_state:
        return out
    *rest, last_logits, kcache, vcache = out
    toks = rest[0]
    state = GenState(
        kcache=kcache, vcache=vcache, last_tok=toks[:, total - 1],
        length=total,
        # steps=0: prefill wrote EVERY slot; the prompt's next-token
        # logits ride along so a continuation never re-touches slots
        boundary_cached=steps == 0,
        last_logits=last_logits,
    )
    return (*rest, state) if len(rest) > 1 else (toks, state)


@functools.partial(
    jax.jit,
    static_argnames=(
        "cfg", "steps", "return_logits", "top_k", "has_top_p", "greedy",
        "capacity", "return_state", "has_eos",
    ),
)
def _lm_generate_jit(
    params, prompt, temperature, top_p, key, *, cfg, steps, return_logits,
    top_k, has_top_p, greedy, capacity=None, return_state=False,
    eos=None, has_eos=False,
):
    b, p_len = prompt.shape
    total = p_len + steps
    kcache, vcache = _alloc_kv_caches(
        cfg, b, total if capacity is None else capacity
    )
    toks = jnp.concatenate(
        [prompt.astype(jnp.int32), jnp.zeros((b, steps), jnp.int32)], axis=1
    )

    def pick(logits, k_step):
        return _pick_token(
            logits, k_step, temperature, top_p, greedy=greedy,
            top_k=top_k, has_top_p=has_top_p,
        )

    def ret(*main, last_logits=None):
        return (*main, last_logits, kcache, vcache) if return_state else (
            main if len(main) > 1 else main[0]
        )

    # batched prefill: one causal forward ingests the whole prompt; the
    # sequential scan below covers only the GENERATED tokens
    prefill_logits, kcache, vcache = _prefill(
        params, cfg, prompt.astype(jnp.int32), kcache, vcache
    )
    if steps == 0:
        # contract: total-1 logit rows (row t predicts token t+1); the
        # last prompt position's prediction has no output slot here —
        # it rides into the GenState instead (boundary_cached)
        last = prefill_logits[:, -1]
        if return_logits:
            return ret(toks, prefill_logits[:, :-1], last_logits=last)
        return ret(toks, last_logits=last)
    key, k0 = jax.random.split(key)
    first = pick(prefill_logits[:, -1], k0)
    toks = toks.at[:, p_len].set(first)
    # eos freeze mask: a row that has EMITTED the (traced) eos token
    # keeps emitting the pad token 0 for the rest of the fixed-length
    # scan (lax.scan cannot end early; the contract is "eos then
    # pads"). Only carried when the feature is on (has_eos is static).
    done = first == eos if has_eos else jnp.zeros(b, bool)

    def body(carry, pos):
        toks, kcache, vcache, key, done = carry
        key, k_step = jax.random.split(key)
        tok = jax.lax.dynamic_index_in_dim(toks, pos, axis=1, keepdims=False)
        logits, kcache, vcache = _decode_step(
            params, cfg, tok, kcache, vcache, pos
        )
        nxt = pick(logits, k_step)
        if has_eos:
            nxt = jnp.where(done, 0, nxt)
            done = done | (nxt == eos)
        toks = jax.lax.dynamic_update_index_in_dim(toks, nxt, pos + 1, axis=1)
        return (toks, kcache, vcache, key, done), logits

    # positions p_len .. total-2: each processes an already-written token
    # and writes the next one (the final position total-1 is written by
    # the last iteration and needs no processing)
    (toks, kcache, vcache, _, _), gen_logits = jax.lax.scan(
        body, (toks, kcache, vcache, key, done), jnp.arange(p_len, total - 1)
    )
    if return_logits:
        # [B, T-1, vocab]: row t predicts token t+1 — the decode-vs-full-
        # forward parity hook for tests (prefill rows + generated rows)
        return ret(toks, jnp.concatenate(
            [prefill_logits, jnp.swapaxes(gen_logits, 0, 1)], axis=1
        ))
    return ret(toks)


@functools.partial(
    jax.jit,
    static_argnames=(
        "cfg", "steps", "top_k", "has_top_p", "greedy", "capacity",
        "has_eos",
    ),
)
def _lm_generate_ragged_jit(
    params, prompt, lengths, temperature, top_p, key, *, cfg, steps,
    top_k, has_top_p, greedy, capacity, eos=None, has_eos=False,
):
    """Ragged-batch core: right-padded prompt [B, P] + per-row lengths.

    One padded prefill fills cache slots [0, len_b) correctly per row
    (pad rows' garbage K/V lands at [len_b, P) — never attended: the
    chunked decode's ``keep`` mask admits only slots <= the row's
    CURRENT position, and every slot up to there has been overwritten
    by a real generated token by the time it becomes admissible). The
    decode loop runs :func:`_chunk_decode` with C=1 and per-row
    positions ``lengths + t`` — cache writes, rope tables and window
    masks all follow the row's own clock."""
    b, p_len = prompt.shape
    kcache, vcache = _alloc_kv_caches(cfg, b, capacity)
    prompt = prompt.astype(jnp.int32)
    rows = jnp.arange(b)
    # output: prompt with pad slots zeroed (so rows are comparable
    # regardless of what padding value the caller used), widened to
    # hold each row's continuation at [len_b, len_b + steps)
    col = jnp.arange(p_len)
    out = jnp.zeros((b, p_len + steps), jnp.int32)
    out = out.at[:, :p_len].set(
        jnp.where(col[None, :] < lengths[:, None], prompt, 0)
    )

    def pick(logits, k_step):
        return _pick_token(
            logits, k_step, temperature, top_p, greedy=greedy,
            top_k=top_k, has_top_p=has_top_p,
        )

    prefill_logits, kcache, vcache = _prefill(
        params, cfg, prompt, kcache, vcache
    )
    # each row's next-token logits live at ITS last real position
    last = jnp.take_along_axis(
        prefill_logits, (lengths - 1)[:, None, None], axis=1
    )[:, 0]
    key, k0 = jax.random.split(key)
    cur = pick(last, k0)
    out = out.at[rows, lengths].set(cur)
    done = cur == eos if has_eos else jnp.zeros(b, bool)

    def body(carry, t):
        out, kcache, vcache, cur, key, done = carry
        key, k_step = jax.random.split(key)
        pos = lengths + t  # [B]: absolute slot of `cur`, per row
        logits, kcache, vcache = _chunk_decode(
            params, cfg, cur[:, None], kcache, vcache, pos
        )
        nxt = pick(logits[:, 0], k_step)
        if has_eos:
            nxt = jnp.where(done, 0, nxt)
            done = done | (nxt == eos)
        out = out.at[rows, pos + 1].set(nxt)
        return (out, kcache, vcache, nxt, key, done), None

    (out, kcache, vcache, _, _, _), _ = jax.lax.scan(
        body, (out, kcache, vcache, cur, key, done), jnp.arange(steps - 1)
    )
    return out


def lm_beam_search(
    params: Dict[str, jax.Array],
    prompt: jax.Array,  # [B, P] int32
    cfg: LMConfig,
    steps: int,
    *,
    beam_width: int = 4,
    eos_id: "int | None" = None,
    length_penalty: float = 0.0,
    prompt_lengths: "jax.Array | None" = None,
) -> "Tuple[jax.Array, jax.Array]":
    """Beam search over the KV-cached decode path: maintains the
    ``beam_width`` highest-logprob continuations per prompt and returns
    ``(tokens [B, W, P+steps], scores [B, W])`` best-first.

    One prefill on [B, P] fills the caches, which are then tiled W×
    (beam-major rows ``b*W + w``); every step scores all ``W * vocab``
    candidates, keeps the global top W, and REORDERS the caches by each
    survivor's parent beam (the gather is the classic beam cost).
    ``scores`` are exact sums of next-token log-probabilities under the
    model — tests pin them against teacher-forcing the returned
    sequences through the training forward.

    ``eos_id``: a beam that emits it is FINISHED — its score freezes
    and it pads (it competes as a single candidate; an unfinished beam
    can still overtake it). ``length_penalty`` alpha applies the GNMT
    normalization ``score / ((5 + len) / 6)^alpha`` at the FINAL
    ranking only (len = generated tokens incl. eos; without eos all
    beams share one length and the ranking is unaffected).

    ``prompt_lengths`` [B] enables RAGGED batches (same contract as
    lm_generate): right-padded prompts, each prompt's beams expanding
    from its own length — row b's beams carry their continuations at
    ``[len_b, len_b + steps)`` (zeros beyond), and every prompt's beam
    set equals what a single-prompt call on the unpadded prompt
    produces. The ragged path steps through the per-row-position chunk
    decode; dense batches keep the scalar-position fast path.

    Deterministic (no sampling)."""
    if beam_width < 1:
        raise ValueError(f"beam_width must be >= 1, got {beam_width}")
    if steps < 1:
        raise ValueError(f"steps must be >= 1, got {steps}")
    if eos_id is not None and not 0 <= eos_id < cfg.vocab:
        raise ValueError(
            f"eos_id must be in [0, vocab={cfg.vocab}), got {eos_id}"
        )
    if beam_width > cfg.vocab:
        raise ValueError(
            f"beam_width {beam_width} > vocab {cfg.vocab}: the first "
            "expansion cannot fill the beams"
        )
    if prompt_lengths is not None:
        lengths = _validate_prompt_lengths(prompt_lengths, prompt)
    else:
        lengths = jnp.full(prompt.shape[0], prompt.shape[1], jnp.int32)
    toks, scores, gen_len = _beam_jit(
        params, prompt, lengths,
        jnp.asarray(0 if eos_id is None else eos_id, jnp.int32),
        cfg=cfg, steps=steps, beam_width=beam_width,
        has_eos=eos_id is not None, ragged=prompt_lengths is not None,
    )
    # final ranking on the host: length_penalty only scales the [B, W]
    # ranking, so sweeping alpha must never recompile the decode program
    if length_penalty:
        norm = ((5.0 + gen_len.astype(jnp.float32)) / 6.0) ** float(
            length_penalty
        )
        ranked = scores / norm
    else:
        ranked = scores
    order = jnp.argsort(-ranked, axis=1)
    return (
        jnp.take_along_axis(toks, order[:, :, None], axis=1),
        jnp.take_along_axis(scores, order, axis=1),
    )


@functools.partial(
    jax.jit,
    static_argnames=("cfg", "steps", "beam_width", "has_eos", "ragged"),
)
def _beam_jit(params, prompt, lengths, eos, *, cfg, steps, beam_width,
              has_eos, ragged):
    b, p_len = prompt.shape
    w = beam_width
    total = p_len + steps
    prompt = prompt.astype(jnp.int32)
    kc, vc = _alloc_kv_caches(cfg, b, total)
    prefill_logits, kc, vc = _prefill(params, cfg, prompt, kc, vc)
    # each prompt's first-expansion logits live at ITS last real
    # position (== column -1 for dense batches)
    last = (
        jnp.take_along_axis(
            prefill_logits, (lengths - 1)[:, None, None], axis=1
        )[:, 0]
        if ragged else prefill_logits[:, -1]  # static slice, no gather
    )
    logp0 = jax.nn.log_softmax(last.astype(jnp.float32), axis=-1)  # [B, V]
    scores, tok0 = jax.lax.top_k(logp0, w)  # [B, W] each
    # beam-major tiling: row r = b*W + w_idx shares prompt history
    tile = lambda a: jnp.repeat(a, w, axis=1)  # noqa: E731  [L,B,...] -> [L,B*W,...]
    kc, vc = (
        jax.tree.map(lambda x: tile(x) if x is not None else None, c,
                     is_leaf=lambda x: x is None)
        for c in (kc, vc)
    )
    col = jnp.arange(p_len)
    base_prompt = (
        jnp.where(col[None, :] < lengths[:, None], prompt, 0)
        if ragged else prompt
    )
    toks = jnp.broadcast_to(base_prompt[:, None, :], (b, w, p_len))
    toks = jnp.concatenate(
        [toks, jnp.zeros((b, w, steps), jnp.int32)], axis=2
    )
    rows_b = jnp.arange(b)[:, None]
    if ragged:
        toks = toks.at[
            rows_b, jnp.arange(w)[None, :], lengths[:, None]
        ].set(tok0)
    else:
        toks = toks.at[:, :, p_len].set(tok0)
    done = (tok0 == eos) if has_eos else jnp.zeros((b, w), bool)
    gen_len = jnp.ones((b, w), jnp.int32)  # tokens emitted (incl. eos)
    batch_base = (jnp.arange(b) * w)[:, None]  # [B, 1]
    lengths_rows = jnp.repeat(lengths, w)  # [B*W], beam-major

    def body(carry, t):
        toks, kc, vc, scores, done, gen_len, cur = carry
        if ragged:
            # per-row clocks through the chunk path (cache writes,
            # rope, masks all follow each prompt's own position)
            pos_rows = lengths_rows + t
            logits, kc, vc = _chunk_decode(
                params, cfg, cur.reshape(b * w)[:, None], kc, vc,
                pos_rows,
            )
            logits = logits[:, 0]
        else:
            # dense: scalar-position fast path (~2x per token)
            logits, kc, vc = _decode_step(
                params, cfg, cur.reshape(b * w), kc, vc, lengths[0] + t
            )
        logp = jax.nn.log_softmax(
            logits.astype(jnp.float32), axis=-1
        ).reshape(b, w, cfg.vocab)
        if has_eos:
            # a finished beam competes as ONE candidate: pad (token 0)
            # at unchanged score; every other continuation is -inf
            frozen = jnp.full_like(logp, -jnp.inf).at[:, :, 0].set(0.0)
            logp = jnp.where(done[:, :, None], frozen, logp)
        cand = scores[:, :, None] + logp  # [B, W, V]
        scores, idx = jax.lax.top_k(cand.reshape(b, w * cfg.vocab), w)
        parent = idx // cfg.vocab  # [B, W]
        tok = (idx % cfg.vocab).astype(jnp.int32)
        # reorder beam state by parent
        toks = jnp.take_along_axis(toks, parent[:, :, None], axis=1)
        done = jnp.take_along_axis(done, parent, axis=1)
        gen_len = jnp.take_along_axis(gen_len, parent, axis=1)
        flat_parent = (batch_base + parent).reshape(-1)  # [B*W]

        def reorder(x):
            return None if x is None else x[:, flat_parent]

        kc = jax.tree.map(reorder, kc, is_leaf=lambda x: x is None)
        vc = jax.tree.map(reorder, vc, is_leaf=lambda x: x is None)
        if ragged:
            toks = toks.at[
                rows_b, jnp.arange(w)[None, :], (lengths + t + 1)[:, None]
            ].set(tok)
        else:
            # dense: one dynamic-update-slice, not a general scatter
            toks = jax.lax.dynamic_update_slice_in_dim(
                toks, tok[:, :, None], p_len + 1 + t, axis=2
            )
        if has_eos:
            gen_len = gen_len + (~done).astype(jnp.int32)
            done = done | (tok == eos)
        else:
            gen_len = gen_len + 1
        return (toks, kc, vc, scores, done, gen_len, tok), None

    (toks, kc, vc, scores, done, gen_len, _), _ = jax.lax.scan(
        body, (toks, kc, vc, scores, done, gen_len, tok0),
        jnp.arange(steps - 1),
    )
    return toks, scores, gen_len


def lm_generate_continue(
    params: Dict[str, jax.Array],
    state: GenState,
    cfg: LMConfig,
    steps: int,
    *,
    new_tokens: "jax.Array | None" = None,
    temperature=None,
    top_k: "int | None" = None,
    top_p: "float | None" = None,
    key: "jax.Array | None" = None,
) -> "Tuple[jax.Array, GenState]":
    """Extend a :class:`GenState` by ``steps`` tokens — multi-turn
    serving without re-prefilling the history.

    ``new_tokens`` [B, M] (e.g. the next user turn) is ingested first
    in ONE multi-token cache pass (:func:`_chunk_decode` — weights read
    once for the whole turn), then the usual one-token decode scan
    generates. Returns ``(generated [B, steps], new_state)``. The
    state's cache capacity (``lm_generate(..., max_len=)``) must hold
    ``state.length + M + steps`` slots. The same sampling options as
    lm_generate apply. The window/rope/GQA/int8-cache config must be
    the one the state was created with (the caches carry its layout).

    ``steps=0`` with ``new_tokens`` is the ingest-only call ("absorb
    the user's turn now, generate later"): the returned state carries
    ``boundary_cached=True`` plus the turn's next-token logits, so the
    follow-up continuation starts from those logits and never touches
    an already-written cache slot — every path stays exactly equal to
    single-shot generation.

    ``state.length`` rides as a TRACED operand: turns of the same
    (new-turn width, steps) shape reuse one compiled program no matter
    how long the conversation has grown."""
    greedy, temperature, top_p_arr, key = _sampling_args(
        cfg, temperature, top_k, top_p, key
    )
    m = 0 if new_tokens is None else new_tokens.shape[1]
    if steps == 0 and m == 0:
        return (
            jnp.zeros((state.last_tok.shape[0], 0), jnp.int32), state
        )
    need = state.length + m + steps
    if need > state.capacity:
        raise ValueError(
            f"continuation needs {need} cache slots but the state was "
            f"allocated {state.capacity} — create it with "
            f"lm_generate(..., max_len={need}) or more"
        )
    if new_tokens is None:
        new_tokens = jnp.zeros((state.last_tok.shape[0], 0), jnp.int32)
    gen, kcache, vcache, last, last_logits = _lm_continue_jit(
        params, state.kcache, state.vcache, state.last_tok,
        state.last_logits, new_tokens.astype(jnp.int32),
        jnp.int32(state.length), temperature, top_p_arr, key,
        cfg=cfg, steps=steps, top_k=top_k,
        has_top_p=top_p is not None, greedy=greedy,
        boundary_cached=state.boundary_cached,
    )
    return gen, GenState(
        kcache=kcache, vcache=vcache, last_tok=last, length=need,
        boundary_cached=steps == 0, last_logits=last_logits,
    )


@functools.partial(
    jax.jit,
    static_argnames=("cfg", "steps", "top_k", "has_top_p", "greedy",
                     "boundary_cached"),
)
def _lm_continue_jit(
    params, kcache, vcache, last_tok, last_logits, new_tokens, length,
    temperature, top_p, key, *, cfg, steps, top_k, has_top_p, greedy,
    boundary_cached,
):
    b, m = new_tokens.shape

    def pick(logits, k_step):
        return _pick_token(
            logits, k_step, temperature, top_p, greedy=greedy,
            top_k=top_k, has_top_p=has_top_p,
        )

    if boundary_cached:
        # every existing slot is written (prefill-/ingest-only state):
        # ingest ONLY the new turn at positions length..length+m-1; with
        # no new turn the carried last_logits already predict the next
        # token (m=0 AND steps=0 was dispatched in the wrapper)
        if m > 0:
            logits_c, kcache, vcache = _chunk_decode(
                params, cfg, new_tokens, kcache, vcache,
                jnp.full((b,), length, jnp.int32),
            )
            src_logits = logits_c[:, -1]
        else:
            src_logits = last_logits
    else:
        # ingest [last_tok, new turn] as one chunk: writes the boundary
        # token's pending cache slot (length-1) plus the turn's slots;
        # the final row's logits predict the first generated token
        chunk = jnp.concatenate([last_tok[:, None], new_tokens], axis=1)
        logits_c, kcache, vcache = _chunk_decode(
            params, cfg, chunk, kcache, vcache,
            jnp.full((b,), length - 1, jnp.int32),
        )
        src_logits = logits_c[:, -1]
    if steps == 0:  # ingest-only: hand the logits to the next turn
        return (
            jnp.zeros((b, 0), jnp.int32), kcache, vcache,
            new_tokens[:, -1], src_logits,
        )
    key, k0 = jax.random.split(key)
    first = pick(src_logits, k0)
    start = length + m  # absolute position of the first generated token
    gen = jnp.zeros((b, steps), jnp.int32).at[:, 0].set(first)

    def body(carry, i):
        gen, kcache, vcache, key = carry
        key, k_step = jax.random.split(key)
        tok = jax.lax.dynamic_index_in_dim(gen, i, axis=1, keepdims=False)
        logits, kcache, vcache = _decode_step(
            params, cfg, tok, kcache, vcache, start + i
        )
        nxt = pick(logits, k_step)
        gen = jax.lax.dynamic_update_index_in_dim(gen, nxt, i + 1, axis=1)
        return (gen, kcache, vcache, key), None

    if steps > 1:
        (gen, kcache, vcache, _), _ = jax.lax.scan(
            body, (gen, kcache, vcache, key), jnp.arange(steps - 1)
        )
    return gen, kcache, vcache, gen[:, -1], None


def lm_loss(params, tokens, cfg, mesh, axis="data"):
    """Mean next-token cross entropy; the [:, 1:] shift crosses shard
    boundaries — GSPMD emits the halo exchange."""
    if cfg.attention == "ring_zigzag":
        raise ValueError(
            "lm_loss's [:, 1:] shift assumes NATURAL token order; the "
            "zigzag layout breaks that adjacency — use "
            "zigzag_lm_arrays + lm_loss_with_targets instead"
        )
    return lm_loss_with_targets(
        params, tokens, *next_token_targets(tokens), cfg, mesh, axis
    )


def lm_loss_with_targets(params, tokens, targets, weights, cfg, mesh, axis="data"):
    """Weighted next-token cross entropy with EXPLICIT per-position
    targets — the layout-agnostic loss: under a permuted token layout
    (zigzag) "next token" is not position+1 locally, so the caller maps
    labels (see :func:`zigzag_lm_arrays`) instead of the loss shifting."""
    return lm_loss_and_stats(
        params, tokens, targets, weights, cfg, mesh, axis
    )[0]


def lm_loss_and_stats(params, tokens, targets, weights, cfg, mesh,
                      axis="data"):
    """``(loss, stats)``: :func:`lm_loss_with_targets` beside what
    :func:`lm_forward_with_stats` counted (``jax.value_and_grad(...,
    has_aux=True)`` takes it as it is)."""
    logits, stats = lm_forward_with_stats(params, tokens, cfg, mesh, axis)
    with jax.named_scope("lm_head"):
        logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
        nll = -jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]
        w = weights.astype(jnp.float32)
        # eps only guards all-zero weights (loss 0); fractional weight
        # sums must divide through unscaled
        return (nll * w).sum() / jnp.maximum(w.sum(), 1e-9), stats


def next_token_targets(tokens):
    """``(targets, weights)`` of the natural layout: position i's target
    is token i+1, and the last position weighs nothing."""
    targets = jnp.concatenate(
        [tokens[:, 1:], jnp.zeros_like(tokens[:, :1])], axis=1
    )
    weights = jnp.ones(tokens.shape, jnp.float32).at[:, -1].set(0.0)
    return targets, weights


def zigzag_lm_arrays(tokens: np.ndarray, n: int):
    """Host-side prep for the zigzag LM layout: permute NATURAL-order
    tokens into the zigzag sharding and carry each position's next-token
    target along (the last natural position gets weight 0). Feed the
    results to :func:`lm_loss_with_targets` with
    ``LMConfig(attention="ring_zigzag")``."""
    from .attention import zigzag_permutation

    b, s = tokens.shape
    perm = zigzag_permutation(s, n)
    tgt = np.concatenate(
        [tokens[:, 1:], np.zeros((b, 1), tokens.dtype)], axis=1
    )
    weights = np.ones((b, s), np.float32)
    weights[:, -1] = 0.0
    return tokens[:, perm], tgt[:, perm], weights[:, perm]


def make_lm_train_step(cfg: LMConfig, mesh: Mesh, axis: str = "data",
                       lr: float = 0.3, donate: bool = False,
                       steps_per_launch: int = 1):
    """SGD train step; tokens must be placed sharded P(..., axis).

    ``donate=True`` donates the incoming params (input/output aliasing —
    halves param HBM footprint). Opt-in: a donated call consumes the
    caller's buffers, which breaks patterns like stepping two configs
    from the SAME initial params; enable it in owned training loops that
    always rebind (``params, loss = step(params, toks)``).

    ``steps_per_launch > 1`` fuses that many sequential SGD steps into
    ONE compiled program via ``lax.scan`` (the LM analogue of the linear
    app's ELL supersteps): ``step(params, tokens)`` then takes a stacked
    ``[T, B, S]`` batch, consumes one ``[B, S]`` slice per scan step with
    the params carried through, and returns ``(params, losses[T])`` —
    bit-identical training semantics to T separate calls, minus T-1
    dispatch round trips (dominant on high-latency links). Activations
    live one step at a time, so peak memory matches a single step."""
    if cfg.attention == "ring_zigzag":
        raise ValueError(
            "the zigzag layout needs explicit targets — use "
            "make_lm_train_step_with_targets (+ zigzag_lm_arrays)"
        )
    if steps_per_launch < 1:
        raise ValueError(f"steps_per_launch must be >= 1, got {steps_per_launch}")

    def one(params, tokens):
        loss, grads = jax.value_and_grad(lm_loss)(params, tokens, cfg, mesh, axis)
        new = jax.tree.map(lambda p, g: p - lr * g, params, grads)
        return new, loss

    if steps_per_launch == 1:
        return jax.jit(one, donate_argnums=(0,) if donate else ())

    @functools.partial(jax.jit, donate_argnums=(0,) if donate else ())
    def step(params, tokens_stack):
        return jax.lax.scan(one, params, tokens_stack)

    return step


def make_lm_train_step_with_targets(
    cfg: LMConfig, mesh: Mesh, axis: str = "data", lr: float = 0.3,
    donate: bool = False,
):
    """SGD train step on (tokens, targets, weights) — the layout-agnostic
    factory: works for any attention mode, and is the sanctioned one for
    ``ring_zigzag`` (feed it ``zigzag_lm_arrays`` outputs). ``donate``:
    see make_lm_train_step."""

    @functools.partial(jax.jit, donate_argnums=(0,) if donate else ())
    def step(params, tokens, targets, weights):
        loss, grads = jax.value_and_grad(lm_loss_with_targets)(
            params, tokens, targets, weights, cfg, mesh, axis
        )
        new = jax.tree.map(lambda p, g: p - lr * g, params, grads)
        return new, loss

    return step


def shard_tokens(tokens: np.ndarray, mesh: Mesh, axis: str = "data") -> jax.Array:
    """Place ``[B, S]`` (or a stacked ``[T, B, S]`` superbatch) with the
    sequence dimension sharded over ``axis``."""
    spec = P(*([None] * (tokens.ndim - 1)), axis)
    return jax.device_put(tokens, NamedSharding(mesh, spec))


def shard_lm_params(
    params: Dict[str, jax.Array], mesh: Mesh, axis: str = "server"
) -> Dict[str, jax.Array]:
    """Tensor parallelism by placement (Megatron-style): project-in
    weights (wq/wk/wv, w1) column-sharded over ``axis``, project-out weights
    (wo, w2) row-sharded; GSPMD inserts the partial-sum psums under jit.
    Composes with sequence parallelism on the other mesh axis — on the
    framework's data x server mesh the same 2-D mesh carries sp x tp.
    Embedding/layernorm/MoE tables stay replicated (MoE experts shard
    over the sp axis inside moe_ffn itself)."""

    def place(k, v):
        if k.endswith(("/wq", "/wk", "/wv", "/w1")):
            spec = P(None, axis)
        elif k.endswith("/wo") or k.endswith("/w2"):
            spec = P(axis, None)
        else:
            spec = P()
        return jax.device_put(v, NamedSharding(mesh, spec))

    return {k: place(k, v) for k, v in params.items()}


def _shard_tree_over_axis(tree, mesh: Mesh, axis: str):
    """Split every array leaf over ``axis`` on its largest free
    dimension divisible by the axis size; keep existing ``axis``
    placements; pin scalars and indivisible leaves replicated so the
    whole tree stays mesh-committed. Shared placement engine behind
    :func:`zero1_shard_opt_state` and :func:`fsdp_shard_lm_params`."""
    n = mesh.shape[axis]

    def place(x):
        if (
            not hasattr(x, "shape") or x.ndim == 0 or n == 1
        ):
            # nothing to split: keep an existing mesh placement (a
            # tensor-parallel moment must NOT be gathered back to
            # replicated just because the data axis is trivial), pin
            # anything unplaced replicated so the tree stays committed
            if isinstance(getattr(x, "sharding", None), NamedSharding):
                return x
            return jax.device_put(x, NamedSharding(mesh, P()))
        cur = getattr(x, "sharding", None)
        spec = (
            list(cur.spec) + [None] * (x.ndim - len(cur.spec))
            if isinstance(cur, NamedSharding)
            else [None] * x.ndim
        )
        if axis in spec:  # already data-sharded; keep as is
            return x
        for d in sorted(range(x.ndim), key=lambda d: -x.shape[d]):
            if spec[d] is None and x.shape[d] % n == 0:
                spec[d] = axis
                return jax.device_put(x, NamedSharding(mesh, P(*spec)))
        return jax.device_put(x, NamedSharding(mesh, P(*spec)))

    return jax.tree.map(place, tree)


def zero1_shard_opt_state(opt_state, mesh: Mesh, axis: str = "data"):
    """ZeRO-1 optimizer-state sharding (Rajbhandari et al. 2020) by
    placement: every state leaf is split over the ``axis`` mesh axis on
    its largest free dimension divisible by the axis size. Params stay
    however the caller placed them (replicated, or Megatron-split via
    :func:`shard_lm_params`) — under jit, GSPMD partitions the
    elementwise moment update to match the state sharding and
    all-gathers only the final parameter delta, so the per-device
    optimizer footprint drops by the data-axis size at the cost of one
    gather of the update. Composes with tensor parallelism: a leaf
    already sharded over the server axis keeps that placement and gains
    the data axis on another dimension. Scalar leaves (adam's step
    count) and leaves with no divisible free dimension are pinned
    replicated, so the whole tree is mesh-committed (the checkpoint
    restore template relies on that)."""
    return _shard_tree_over_axis(opt_state, mesh, axis)


def fsdp_shard_lm_params(
    params: Dict[str, jax.Array], mesh: Mesh, axis: str = "data"
) -> Dict[str, jax.Array]:
    """FSDP / ZeRO-3 parameter sharding (Rajbhandari et al. 2020; the
    reference's analogue is its server-sharded KVLayer partitioning,
    kv_layer.h partition threshold) by placement: every parameter leaf
    is split over ``axis`` on its largest free dimension divisible by
    the axis size. Under jit GSPMD all-gathers each weight just before
    use and reduce-scatters its gradient — per-device parameter AND
    gradient memory divided by the axis size, at the cost of one
    gather per weight per materialization (twice under remat: forward
    and recompute). Semantics are placement-only, but NOT bit-exact
    (unlike ZeRO-1): the gradient reduction becomes a reduce-scatter,
    whose summation order differs from the all-reduce, so trajectories
    track the replicated run to float reduction-order tolerance
    (~1e-4 over a few adam steps — tests/test_fsdp.py).

    Composes with Megatron tensor parallelism (a leaf already sharded
    over the server axis keeps that dim and gains the data axis on
    another) and with :func:`zero1_shard_opt_state` — optax moments
    initialized from FSDP params inherit the sharding, which together
    is the full ZeRO-3 stack: params, grads, and optimizer state all
    sharded over the data axis."""
    return _shard_tree_over_axis(params, mesh, axis)

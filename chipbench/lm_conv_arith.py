"""Operations and bytes of the ``lm_conv`` runner's models, computed from
the configuration's file, kept with the benchmark so that no later PR
changes what a share of the peak is a share of.

``desc`` is a model description as it is run (the published
``config.json`` keys; ``published.num_experts`` is the router's width,
``num_experts`` the experts held here; layer ``i`` is a gated short
convolution where ``layer_types[i]`` is ``conv`` and a full GQA layer
where it is ``full_attention``; the first ``num_dense_layers`` layers
have a dense SwiGLU of ``intermediate_size``, the others the expert
layer; the head is the embedding's transpose). A matrix product of an
[m, k] by a [k, n] matrix is 2 m k n operations; a training step needs
three such products per weight matrix and token, hence the 6.
Recomputation is never counted as model work.

Attention's score-sized products are counted by the causal mask for the
token-layers the program counted (``ps_lm_attention_token_layers_total
{kind="full"}``); a convolution's taps are a multiply and an add each,
a token, channel and layer, for the token-layers it counted
(``ps_lm_conv_token_layers_total``).

The convolution's mix (``lm_conv_mix``: u = B * x~, the taps, C * z) is
elementwise and bound by memory: a token-layer moves u, B, C, x~ and the
mix's output y once forward, their gradients once backward, and the
forward again under recomputation: 15 vectors of ``hidden_size`` in the
activations' dtype (bf16: 61,440 bytes at 2,048). Its operations: u
(1), the taps (2 x taps) and C * z (1) a channel forward, the same
twice backward, the forward twice under recomputation.
"""

from __future__ import annotations

from chipbench.lm_arith import (
    ATTENTION_BACKWARD_MATMULS, ATTENTION_FORWARD_MATMULS,
    FLASH_BACKWARD_MATMULS, expert_params,
)
from chipbench.lm_swa_arith import kept_pairs

ACTIVATION_BYTES = 2  # bf16
MIX_VECTORS = 15  # 5 forward, twice under recomputation, and 5 gradients


def head_dim(desc: dict) -> int:
    return desc.get("head_dim") or (
        desc["hidden_size"] // desc["num_attention_heads"]
    )


def conv_matmul_params(desc: dict) -> int:
    """W_in [d, 3d] and W_out [d, d]: the products of a convolution
    layer. The taps are elementwise (``conv_flops``)."""
    return 4 * desc["hidden_size"] ** 2


def attention_matmul_params(desc: dict) -> int:
    """q and the output projection over the query heads, k and v over
    the K/V heads. The q/k norms and the rotation are elementwise."""
    d, hd = desc["hidden_size"], head_dim(desc)
    return d * (
        2 * desc["num_attention_heads"] * hd
        + 2 * desc["num_key_value_heads"] * hd
    )


def dense_params_per_token(desc: dict) -> int:
    """Matmul weights every token meets in a step outside the routed
    experts: per layer its operator's projections and either the dense
    FFN or the router (its published width), and once the tied head over
    the vocabulary slice. The embedding is a row read."""
    d = desc["hidden_size"]
    experts = desc.get("published", {}).get(
        "num_experts", desc["num_experts"]
    )
    op = {
        "conv": conv_matmul_params(desc),
        "full_attention": attention_matmul_params(desc),
    }
    total = d * desc["vocab_size"]
    for i, kind in enumerate(desc["layer_types"]):
        ffn = (
            3 * d * desc["intermediate_size"]
            if i < desc["num_dense_layers"] else d * experts
        )
        total += op[kind] + ffn
    return total


def conv_flops(desc: dict, token_layers: float) -> float:
    """The taps' multiply-adds, forward and backward (twice the forward),
    for ``token_layers`` convolution token-layers."""
    return 3.0 * 2.0 * desc["conv_L_cache"] * desc["hidden_size"] * (
        token_layers
    )


def score_flops(desc: dict, seq_len: int, full_token_layers: float,
                matmuls: float) -> float:
    """``matmuls`` score-sized products over the causally kept pairs of
    every query head, in ``full_token_layers / seq_len``
    sequence-layers."""
    return (
        matmuls * 2.0 * head_dim(desc) * desc["num_attention_heads"]
        * kept_pairs(seq_len) * full_token_layers / seq_len
    )


def flash_kernels_flops(desc: dict, seq_len: int,
                        full_token_layers: float) -> float:
    """The flash kernels' 7 score-sized products by the mask (the
    forward's 2 once: a recomputed layer keeps its output; the
    backward's 5), as ``lm_swa_arith.flash_kernels_flops``."""
    return score_flops(
        desc, seq_len, full_token_layers,
        ATTENTION_FORWARD_MATMULS + FLASH_BACKWARD_MATMULS,
    )


def mix_bytes(desc: dict, token_layers: float) -> float:
    return MIX_VECTORS * ACTIVATION_BYTES * desc["hidden_size"] * (
        token_layers
    )


def mix_flops(desc: dict, token_layers: float, remat: bool) -> float:
    forward = (2 + 2 * desc["conv_L_cache"]) * desc["hidden_size"]
    return (forward * (2 if remat else 1) + 2 * forward) * token_layers


def step_model_flops(desc: dict, seq_len: int, sequences: int,
                     expert_rows: float, full_token_layers: float,
                     conv_token_layers: float) -> float:
    """Model work of one training step as held here: 6 x the matmul
    weights a token meets x tokens, the routed experts by the rows they
    really computed (``expert_rows``: forward rows of one step, summed
    over experts and layers), the causal scores for the counted
    attention token-layers and the taps for the counted convolution
    token-layers, forward and backward. No recomputation."""
    return (
        6.0 * dense_params_per_token(desc) * seq_len * sequences
        + 6.0 * expert_params(desc) * expert_rows
        + score_flops(
            desc, seq_len, full_token_layers,
            ATTENTION_FORWARD_MATMULS + ATTENTION_BACKWARD_MATMULS,
        )
        + conv_flops(desc, conv_token_layers)
    )

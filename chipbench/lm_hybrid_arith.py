"""Operations and bytes of the ``lm_hybrid`` runner's models, computed
from the configuration's file, kept with the benchmark so that no later
PR changes what a share of the peak is a share of.

``desc`` is a model description as it is run (the published
``config.json`` keys; ``published.n_routed_experts`` is the router's
width, ``n_routed_experts`` the experts held here; layer ``i`` is a
softmax GQA layer where ``i`` is in ``gqa_layers`` and a gated
delta-rule (KDA) layer elsewhere). A matrix product of an [m, k] by a
[k, n] matrix is 2 m k n operations; a training step needs three such
products per weight matrix and token, hence the 6. Recomputation is
never counted as model work.

The recurrence's own work, whatever implements it and whatever its
chunk: per token, head and layer the state S [K, V] is decayed (K V
operations), read by k (2 K V), updated by the outer product (2 K V)
and read by q (2 K V): 7 K V forward, and twice that backward, as a
product's backward is twice its forward.
"""

from __future__ import annotations

from chipbench.lm_arith import (
    ATTENTION_BACKWARD_MATMULS, ATTENTION_FORWARD_MATMULS,
    FLASH_BACKWARD_MATMULS, expert_params,
)

SCAN_FORWARD_OPS = 7  # x K x V, a token, head and layer
SCAN_BACKWARD_OPS = 14


def layer_kinds(desc: dict) -> list:
    gqa = set(desc["gqa_layers"])
    return [
        "gqa" if i in gqa else "kda" for i in range(desc["num_hidden_layers"])
    ]


def gqa_matmul_params(desc: dict) -> int:
    """Weights of one softmax layer's projections: q, k, v, the output
    gate (``use_gqa_gate``) and the output projection."""
    d, hd = desc["hidden_size"], desc["head_dim"]
    wide = desc["num_attention_heads"] * hd
    narrow = desc["num_key_value_heads"] * hd
    gate = wide if desc["use_gqa_gate"] else 0
    return d * (2 * wide + 2 * narrow + gate)


def kda_matmul_params(desc: dict) -> int:
    """Weights of one KDA layer's products with a token: q, k, v and
    output projections, the two low-rank pairs (decay gate, output
    gate) and beta. The convolutions' taps, ``A_log``, ``dt_bias`` and
    the norm are elementwise, not products."""
    lin = desc["linear_attn_config"]
    d, w = desc["hidden_size"], lin["num_heads"] * lin["head_dim"]
    rank = lin.get("gate_rank", lin["head_dim"])
    return 4 * d * w + 2 * (d * rank + rank * w) + d * lin["num_heads"]


def dense_params_per_token(desc: dict) -> int:
    """Matmul weights every token meets in a step outside the routed
    experts: per layer its attention kind's projections, the shared
    experts and the router (its published width), and once the output
    head over the vocabulary slice. The embedding is a row read."""
    experts = desc.get("published", {}).get(
        "n_routed_experts", desc["n_routed_experts"]
    )
    beside = (
        desc["n_shared_experts"] * expert_params(desc)
        + desc["hidden_size"] * experts
    )
    attention = {
        "gqa": gqa_matmul_params(desc), "kda": kda_matmul_params(desc),
    }
    return (
        sum(attention[kind] + beside for kind in layer_kinds(desc))
        + desc["hidden_size"] * desc["vocab_size"]
    )


def causal_attention_flops(desc: dict, seq_len: int, sequences: int,
                           matmuls: float) -> float:
    """``matmuls`` score-sized products over the causal half of
    ``sequences`` x heads squares of ``seq_len``, in each softmax
    layer (the K/V heads are broadcast: every query head has its own
    products)."""
    return (
        matmuls * 2.0 * (seq_len * seq_len / 2.0) * desc["head_dim"]
        * desc["num_attention_heads"] * sequences
        * layer_kinds(desc).count("gqa")
    )


def flash_kernels_flops(desc: dict, seq_len: int, sequences: int) -> float:
    """What the flash kernels need to execute in one step, in the
    softmax layers: the forward kernel's 2 score-sized products ONCE (a
    rematerialised layer keeps the kernel's output and log-sum-exp, so
    the forward is not run again) and the backward's 5. The two backward
    kernels each recompute s and dp; that duplicate is theirs and is not
    credited."""
    return causal_attention_flops(
        desc, seq_len, sequences,
        ATTENTION_FORWARD_MATMULS + FLASH_BACKWARD_MATMULS,
    )


def scan_flops(desc: dict, token_layers: float) -> float:
    """The recurrence's own operations for ``token_layers`` tokens x KDA
    layers (forward count), forward and backward."""
    lin = desc["linear_attn_config"]
    return (
        (SCAN_FORWARD_OPS + SCAN_BACKWARD_OPS) * lin["head_dim"] ** 2
        * lin["num_heads"] * token_layers
    )


def scan_bytes(desc: dict, token_layers: float) -> float:
    """What the recurrence must move for ``token_layers`` (forward
    count), forward and backward: each of q, k, v, o (bf16, the stated
    precision of activations), g and beta (f32) once, and the gradient
    of each once."""
    lin = desc["linear_attn_config"]
    heads, width = lin["num_heads"], lin["num_heads"] * lin["head_dim"]
    a_token = width * (2 + 2 + 2 + 2 + 4) + heads * 4
    return 2.0 * a_token * token_layers


def step_model_flops(desc: dict, seq_len: int, sequences: int,
                     expert_rows: float, scan_token_layers: float) -> float:
    """Model work of one training step as held here: 6 x the matmul
    weights a token meets x tokens, the routed experts by the rows they
    really computed (``expert_rows``: forward rows of one step, summed
    over experts and layers), causal attention in the softmax layers
    and the recurrence in the others (``scan_token_layers``: tokens x
    KDA layers of one step), forward and backward. No recomputation."""
    return (
        6.0 * dense_params_per_token(desc) * seq_len * sequences
        + 6.0 * expert_params(desc) * expert_rows
        + causal_attention_flops(
            desc, seq_len, sequences,
            ATTENTION_FORWARD_MATMULS + ATTENTION_BACKWARD_MATMULS,
        )
        + scan_flops(desc, scan_token_layers)
    )

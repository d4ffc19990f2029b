"""Operations of the ``lm`` runner's models, computed from the
configuration's file, kept with the benchmark so that no later PR
changes what a share of the peak is a share of.

``desc`` is a model description as it is run (the published
``config.json`` keys; ``published.n_routed_experts`` is the router's
width, ``n_routed_experts`` the experts held here). A matrix product of
an [m, k] by a [k, n] matrix is 2 m k n operations; a training step needs
three such products per weight matrix and token (forward, and two in the
backward pass), hence the 6. Recomputation is never counted as model
work; the two rooflines below count what their kernels execute, and say
so.
"""

from __future__ import annotations


def attention_matmul_params(desc: dict) -> int:
    """Weights of the latent projections one token is multiplied by."""
    d, h = desc["hidden_size"], desc["num_attention_heads"]
    qk = desc["qk_nope_head_dim"] + desc["qk_rope_head_dim"]
    return (
        d * desc["q_lora_rank"]
        + desc["q_lora_rank"] * h * qk
        + d * (desc["kv_lora_rank"] + desc["qk_rope_head_dim"])
        + desc["kv_lora_rank"] * h
        * (desc["qk_nope_head_dim"] + desc["v_head_dim"])
        + h * desc["v_head_dim"] * d
    )


def expert_params(desc: dict) -> int:
    """One routed expert: gate, up and down."""
    return 3 * desc["hidden_size"] * desc["moe_intermediate_size"]


def dense_params_per_token(desc: dict) -> int:
    """Matmul weights every token meets in a step outside the routed
    experts: per layer the latent projections, the shared experts and
    the router (its published width), and once the output head over the
    vocabulary slice. The embedding is a row read, not a product."""
    experts = desc.get("published", {}).get(
        "n_routed_experts", desc["n_routed_experts"]
    )
    per_layer = (
        attention_matmul_params(desc)
        + desc["n_shared_experts"] * expert_params(desc)
        + desc["hidden_size"] * experts
    )
    return (
        desc["num_hidden_layers"] * per_layer
        + desc["hidden_size"] * desc["vocab_size"]
    )


def causal_attention_flops(desc: dict, seq_len: int, sequences: int,
                           matmuls: float) -> float:
    """``matmuls`` score-sized products (q k^T, p v, and their backward
    kin) over the causal half of ``sequences`` x heads x layers squares
    of ``seq_len``. Query/key and value widths are averaged: q k^T runs
    over qk_head_dim, p v over v_head_dim, and the backward products
    split the same way."""
    width = (
        desc["qk_nope_head_dim"] + desc["qk_rope_head_dim"]
        + desc["v_head_dim"]
    ) / 2.0
    return (
        matmuls * 2.0 * (seq_len * seq_len / 2.0) * width
        * desc["num_attention_heads"] * sequences * desc["num_hidden_layers"]
    )


# score-sized products of one attention call. The algorithm needs 2
# forward (s = q k^T, o = p v) and 4 more backward (dv = p^T do,
# dp = do v^T, dq = ds k, dk = ds^T q); a flash backward also needs s
# again, since p was never kept: 5.
ATTENTION_FORWARD_MATMULS = 2
ATTENTION_BACKWARD_MATMULS = 4
FLASH_BACKWARD_MATMULS = 5


def step_model_flops(desc: dict, seq_len: int, sequences: int,
                     expert_rows: float) -> float:
    """Model work of one training step as held here: 6 x the matmul
    weights a token meets x tokens, the routed experts by the rows they
    really computed (``expert_rows``: forward rows of one step, summed
    over experts and layers), and causal attention forward and backward.
    No recomputation."""
    tokens = seq_len * sequences
    return (
        6.0 * dense_params_per_token(desc) * tokens
        + 6.0 * expert_params(desc) * expert_rows
        + causal_attention_flops(
            desc, seq_len, sequences,
            ATTENTION_FORWARD_MATMULS + ATTENTION_BACKWARD_MATMULS,
        )
    )


def expert_products_executed_flops(desc: dict, expert_rows: float,
                                   remat: bool) -> float:
    """What the grouped products execute in one step for ``expert_rows``
    forward rows: forward, the backward pass's two products per matrix,
    and the forward again where the layer is recomputed."""
    passes = 4.0 if remat else 3.0
    return passes * 2.0 * expert_params(desc) * expert_rows


def flash_kernels_executed_flops(desc: dict, seq_len: int, sequences: int,
                                 remat: bool) -> float:
    """What the flash kernels need to execute in one step: the forward
    kernel's 2 products (twice under recomputation) and the backward's 5.
    The two backward kernels of ops/flash_attention.py each recompute s
    and dp; that duplicate is theirs and is not credited."""
    forward = ATTENTION_FORWARD_MATMULS * (2 if remat else 1)
    return causal_attention_flops(
        desc, seq_len, sequences, forward + FLASH_BACKWARD_MATMULS
    )

"""Operations of the ``lm_afmoe`` runner's models, computed from the
configuration's file, kept with the benchmark so that no later PR
changes what a share of the peak is a share of.

``desc`` is a model description as it is run (the published
``config.json`` keys; ``published.num_experts`` is the router's width,
``num_experts`` the experts held here; layer ``i`` sees the last
``sliding_window`` keys where ``layer_types[i]`` is ``sliding_attention``
and every earlier key where it is ``full_attention``; the first
``num_dense_layers`` layers have a dense SwiGLU of ``intermediate_size``,
the others the expert layer beside ``num_shared_experts`` shared
experts; the head is untied). A matrix product of an [m, k] by a [k, n]
matrix is 2 m k n operations; a training step needs three such products
per weight matrix and token, hence the 6. Recomputation is never counted
as model work.

Attention's score-sized products are counted BY THE MASK, as
``lm_swa_arith`` counts them, for the token-layers of each kind that the
program counted (``ps_lm_attention_token_layers_total{kind}``); the
output gate's product (``h W_g``, [d, heads x head_dim]) for the
token-layers whose gate the program counted
(``ps_lm_attention_gate_token_layers_total{kind}``). The sigmoid, the
gate's multiply and the norms are elementwise, not products.
"""

from __future__ import annotations

from chipbench import lm_swa_arith
from chipbench.lm_arith import (
    ATTENTION_BACKWARD_MATMULS, ATTENTION_FORWARD_MATMULS, expert_params,
)

attention_matmul_params = lm_swa_arith.attention_matmul_params
flash_kernels_flops = lm_swa_arith.flash_kernels_flops
KINDS = lm_swa_arith.KINDS


def gate_params(desc: dict) -> int:
    """W_g [d, heads x head_dim]: the output gate's product."""
    return (
        desc["hidden_size"] * desc["num_attention_heads"] * desc["head_dim"]
    )


def shared_expert_params(desc: dict) -> int:
    """The shared experts' gate, up and down."""
    return desc["num_shared_experts"] * expert_params(desc)


def dense_params_per_token(desc: dict) -> int:
    """Matmul weights every token meets in a step outside the routed
    experts and the gates: per layer the attention's projections and
    either the dense FFN or the router (its published width) and the
    shared experts, and once the untied head over the vocabulary slice.
    The embedding is a row read."""
    d = desc["hidden_size"]
    experts = desc.get("published", {}).get(
        "num_experts", desc["num_experts"]
    )
    ffn = {
        "dense": 3 * d * desc["intermediate_size"],
        "moe": d * experts + shared_expert_params(desc),
    }
    return d * desc["vocab_size"] + sum(
        attention_matmul_params(desc)
        + ffn["dense" if i < desc["num_dense_layers"] else "moe"]
        for i in range(desc["num_hidden_layers"])
    )


def step_model_flops(desc: dict, seq_len: int, sequences: int,
                     expert_rows: float, token_layers: dict,
                     gate_token_layers: dict) -> float:
    """Model work of one training step as held here: 6 x the matmul
    weights a token meets x tokens, the routed experts by the rows they
    really computed (``expert_rows``: forward rows of one step, summed
    over experts and layers), the gates' product for the counted gated
    token-layers, and the scores by the mask (``token_layers``: tokens x
    layers of each kind of one step), forward and backward. No
    recomputation."""
    return (
        6.0 * dense_params_per_token(desc) * seq_len * sequences
        + 6.0 * expert_params(desc) * expert_rows
        + 6.0 * gate_params(desc) * sum(gate_token_layers.values())
        + lm_swa_arith.score_flops(
            desc, seq_len, token_layers,
            ATTENTION_FORWARD_MATMULS + ATTENTION_BACKWARD_MATMULS,
        )
    )

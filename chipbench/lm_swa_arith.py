"""Operations of the ``lm_swa`` runner's models, computed from the
configuration's file, kept with the benchmark so that no later PR
changes what a share of the peak is a share of.

``desc`` is a model description as it is run (the published
``config.json`` keys; ``published.num_experts`` is the router's width,
``num_experts`` the experts held here; layer ``i`` sees the last
``sliding_window`` keys where ``layer_types[i]`` is ``sliding_attention``
and every earlier key where it is ``full_attention``). A matrix product
of an [m, k] by a [k, n] matrix is 2 m k n operations; a training step
needs three such products per weight matrix and token, hence the 6.
Recomputation is never counted as model work.

Attention's score-sized products are counted BY THE MASK: query t of a
sequence may see min(t + 1, window) keys in a sliding layer and t + 1 in
a full one, and a product over a (query, key) pair that the mask drops
is not work, whatever the blocks a kernel visits to step over it. How
many token-layers of each kind a step ran is the program's count
(``ps_lm_attention_token_layers_total{kind}``), not the file's.
"""

from __future__ import annotations

from chipbench.lm_arith import (
    ATTENTION_BACKWARD_MATMULS, ATTENTION_FORWARD_MATMULS,
    FLASH_BACKWARD_MATMULS, expert_params,
)

KINDS = ("window", "full")  # the counter's ``kind`` labels


def attention_matmul_params(desc: dict) -> int:
    """Weights of one layer's projections: q and the output projection
    over the query heads, k and v over the K/V heads. The q/k norms and
    the rotation are elementwise, not products."""
    d, hd = desc["hidden_size"], desc["head_dim"]
    wide = desc["num_attention_heads"] * hd
    narrow = desc["num_key_value_heads"] * hd
    return d * (2 * wide + 2 * narrow)


def dense_params_per_token(desc: dict) -> int:
    """Matmul weights every token meets in a step outside the routed
    experts: per layer the attention's projections and the router (its
    published width), and once the output head over the vocabulary
    slice. No shared expert. The embedding is a row read."""
    experts = desc.get("published", {}).get(
        "num_experts", desc["num_experts"]
    )
    per_layer = attention_matmul_params(desc) + desc["hidden_size"] * experts
    return (
        desc["num_hidden_layers"] * per_layer
        + desc["hidden_size"] * desc["vocab_size"]
    )


def kept_pairs(seq_len: int, window=None) -> int:
    """(query, key) pairs of one sequence that the mask keeps: key u for
    query t where u <= t and, under a window, t - u < window."""
    if window is None or window >= seq_len:
        return seq_len * (seq_len + 1) // 2
    return window * (window + 1) // 2 + (seq_len - window) * window


def score_flops(desc: dict, seq_len: int, token_layers: dict,
                matmuls: float) -> float:
    """``matmuls`` score-sized products over the kept pairs of every
    query head (the K/V heads are broadcast: each query head has its own
    products), in ``token_layers[kind] / seq_len`` sequence-layers of
    each kind."""
    per_pair = (
        matmuls * 2.0 * desc["head_dim"] * desc["num_attention_heads"]
    )
    windows = {"window": desc["sliding_window"], "full": None}
    return sum(
        per_pair * kept_pairs(seq_len, windows[kind])
        * token_layers[kind] / seq_len
        for kind in KINDS
    )


def flash_kernels_flops(desc: dict, seq_len: int, token_layers: dict) -> float:
    """What the flash kernels need to execute in one step, by the mask:
    the forward kernel's 2 score-sized products ONCE (a rematerialised
    layer keeps the kernel's output and log-sum-exp, so the forward is
    not run again) and the backward's 5. The two backward kernels each
    recompute s and dp; that duplicate is theirs and is not credited,
    nor is any block the grid visits and the mask empties."""
    return score_flops(
        desc, seq_len, token_layers,
        ATTENTION_FORWARD_MATMULS + FLASH_BACKWARD_MATMULS,
    )


def step_model_flops(desc: dict, seq_len: int, sequences: int,
                     expert_rows: float, token_layers: dict) -> float:
    """Model work of one training step as held here: 6 x the matmul
    weights a token meets x tokens, the routed experts by the rows they
    really computed (``expert_rows``: forward rows of one step, summed
    over experts and layers) and the scores by the mask
    (``token_layers``: tokens x layers of each kind of one step),
    forward and backward. No recomputation."""
    return (
        6.0 * dense_params_per_token(desc) * seq_len * sequences
        + 6.0 * expert_params(desc) * expert_rows
        + score_flops(
            desc, seq_len, token_layers,
            ATTENTION_FORWARD_MATMULS + ATTENTION_BACKWARD_MATMULS,
        )
    )

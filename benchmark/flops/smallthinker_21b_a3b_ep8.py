"""Operations one training sample (one sequence) of the
SmallThinker-21BA3B share needs, from the configuration's ``model`` block.
jax-free.

Per token: 6 x (the parameters a token meets in a matrix multiplication
here) — 2 FLOPs a multiply-add, forward + both gradients — plus the
attention scores. A token meets, on this chip: the head (vocabulary rows
held x hidden: a matrix of its own; the embedding lookup is a gather), and
in each layer the attention projections (q and out hidden x heads x
head_dim each, k and v hidden x kv_heads x head_dim each), the router
(hidden x ``router_width``) and ``moe_num_active_primary_experts x held /
router_width`` experts (3 x hidden x expert width each): the pairs the
router sends here in expectation, not the dropless bound the buffers are
sized for. Scores: two products of 2 x keys x heads x head_dim a query
forward, twice that backward, where a query of a global layer sees (L+1)/2
keys on average and a query of a window layer (W(W+1)/2 + (L-W) W) / L
(the first W queries see their whole prefix, the rest W keys each).
Norms, rotary, activations, the optimizer and what the expert layers'
remat recomputes are left out, as is usual for model FLOPs.
"""

from __future__ import annotations


def experts_per_token_here(m: dict) -> float:
    """``moe_num_primary_experts`` counts the experts held,
    ``router_width`` the published ones the router scores."""
    return (m["moe_num_active_primary_experts"]
            * m["moe_num_primary_experts"] / m["router_width"])


def attention_params(m: dict) -> int:
    d, dim = m["hidden_size"], m["head_dim"]
    return (2 * d * m["num_attention_heads"] * dim
            + 2 * d * m["num_key_value_heads"] * dim)


def expert_params(m: dict) -> int:
    return 3 * m["hidden_size"] * m["moe_ffn_hidden_size"]


def matmul_params_per_token(config: dict) -> float:
    m = config["model"]
    layer = (attention_params(m) + m["hidden_size"] * m["router_width"]
             + experts_per_token_here(m) * expert_params(m))
    return (m["vocab_size"] * m["hidden_size"]
            + m["num_hidden_layers"] * layer)


def keys_per_query(length: int, window=None) -> float:
    """Mean number of keys a query sees in a sequence of ``length``."""
    if window is None or window >= length:
        return (length + 1) / 2
    return (window * (window + 1) / 2 + (length - window) * window) / length


def attention_flops_per_token(config: dict) -> float:
    m = config["model"]
    length = config["tokens_per_sample"]
    keys = sum(
        keys_per_query(length, m["sliding_window_size"] if windowed else None)
        for windowed in m["sliding_window_layout"])
    return 3 * 2 * 2 * keys * m["num_attention_heads"] * m["head_dim"]


def flops_per_token(config: dict) -> float:
    return (6 * matmul_params_per_token(config)
            + attention_flops_per_token(config))


def flops_per_sample(config: dict) -> float:
    return flops_per_token(config) * config["tokens_per_sample"]


def parameters(config: dict) -> int:
    """Every parameter the share holds (what 16 bytes each are paid for)."""
    m = config["model"]
    d = m["hidden_size"]
    layer = (attention_params(m) + d * m["router_width"] + 2 * d
             + m["moe_num_primary_experts"] * expert_params(m))
    # embedding, head (untied), final norm
    return 2 * m["vocab_size"] * d + d + m["num_hidden_layers"] * layer

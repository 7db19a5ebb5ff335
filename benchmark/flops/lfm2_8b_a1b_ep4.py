"""Operations one training sample (one sequence) of the LFM2-8B-A1B share
needs, from the configuration's ``model`` block. jax-free.

Per token: 6 x (the parameters a token meets in a matrix multiplication
here) — 2 FLOPs a multiply-add, forward + both gradients — plus causal
attention. A token meets, on this chip: the tied head (vocabulary rows
held x hidden; the embedding lookup is a gather), each layer's operator
(conv: in 3 d^2 + out d^2; attention: q and out d^2 each, k and v d x
kv_heads x head_dim each), the dense MLP (3 d x intermediate) in the
leading layers, and in the others the router (d x 32) and
``num_experts_per_tok x held / published`` experts (3 d x moe width each):
the pairs the router sends here in expectation, not the dropless bound the
buffers are sized for. Causal attention makes (L+1)/2 scores a query on
average: two products of 2 x (L+1)/2 x d each forward, twice that
backward. The 3-tap convolution, norms, activations, the optimizer and
what the blocks' remat recomputes are left out, as is usual for model
FLOPs.
"""

from __future__ import annotations


def experts_per_token_here(m: dict) -> float:
    """``num_experts`` counts the experts held, ``router_width`` the
    published ones the router scores."""
    return m["num_experts_per_tok"] * m["num_experts"] / m["router_width"]


def operator_params(m: dict, kind: str) -> int:
    d = m["hidden_size"]
    if kind == "conv":
        return 3 * d * d + d * d
    kv = m["num_key_value_heads"] * m["head_dim"]
    return 2 * d * d + 2 * d * kv


def layer_matmul_params(m: dict, layer: int, experts_here: float) -> float:
    d = m["hidden_size"]
    op = operator_params(m, m["layer_types"][layer])
    if layer < m["num_dense_layers"]:
        return op + 3 * d * m["intermediate_size"]
    return (op + d * m["router_width"]
            + experts_here * 3 * d * m["moe_intermediate_size"])


def matmul_params_per_token(config: dict) -> float:
    m = config["model"]
    here = experts_per_token_here(m)
    return (m["vocab_size"] * m["hidden_size"]
            + sum(layer_matmul_params(m, i, here)
                  for i in range(m["num_hidden_layers"])))


def attention_flops_per_token(config: dict) -> float:
    m = config["model"]
    layers = sum(kind == "full_attention" for kind in m["layer_types"])
    length = config["tokens_per_sample"]
    return layers * 3 * 2 * 2 * (length + 1) / 2 * m["hidden_size"]


def flops_per_token(config: dict) -> float:
    return (6 * matmul_params_per_token(config)
            + attention_flops_per_token(config))


def flops_per_sample(config: dict) -> float:
    return flops_per_token(config) * config["tokens_per_sample"]


def parameters(config: dict) -> int:
    """Every parameter the share holds (what 16 bytes each are paid for),
    the (router_width,) expert-bias buffers left out."""
    m = config["model"]
    d = m["hidden_size"]
    total = m["vocab_size"] * d + d               # tied table, final norm
    for i, kind in enumerate(m["layer_types"]):
        total += operator_params(m, kind) + 2 * d     # two norms a layer
        total += (m["conv_L_cache"] * d if kind == "conv"
                  else 2 * m["head_dim"])             # taps | q/k norms
        if i < m["num_dense_layers"]:
            total += 3 * d * m["intermediate_size"]
        else:
            total += (d * m["router_width"]
                      + m["num_experts"] * 3 * d * m["moe_intermediate_size"])
    return total

"""Operations one training sample (one sequence) of the GLM-4.7-Flash share
needs, from the configuration's ``model`` block. jax-free.

Per token: 6 x (the parameters a token meets in a matrix multiplication
here) — 2 FLOPs a multiply-add, forward + both gradients — plus the
attention scores. A token meets, on this chip: in each of the
``num_hidden_layers`` layers and the ``num_nextn_predict_layers``
prediction modules' layers, latent attention's five projections (down to
the query latent and to the key-value latent with its rotary key, up from
each latent to the heads, out); in the ``first_k_dense_replace`` dense
layers the SwiGLU (3 x hidden x ``intermediate_size``); in every other
layer the router (hidden x ``router_width``), the shared experts (3 x
hidden x expert width each) and ``num_experts_per_tok x held /
router_width`` routed experts: the pairs the router sends here in
expectation, not the dropless bound the buffers are sized for. Each
prediction module adds ``eh_proj`` (2 x hidden x hidden), and the head
(vocabulary rows held x hidden, a matrix of its own; the embedding lookup
is a gather) is met once a depth. Scores: a query sees (L+1)/2 keys on
average, two products a key forward (q.k at ``qk_nope_head_dim +
qk_rope_head_dim``, p.v at ``v_head_dim``), twice that backward. Norms,
rotary, activations, the optimizer and what the expert layers' remat
recomputes are left out, as is usual for model FLOPs.
"""

from __future__ import annotations


def qk_dim(m: dict) -> int:
    return m["qk_nope_head_dim"] + m["qk_rope_head_dim"]


def mla_params(m: dict) -> int:
    """The five projections of one latent-attention layer."""
    d, heads = m["hidden_size"], m["num_attention_heads"]
    q_rank, kv_rank = m["q_lora_rank"], m["kv_lora_rank"]
    return (d * q_rank + q_rank * heads * qk_dim(m)
            + d * (kv_rank + m["qk_rope_head_dim"])
            + kv_rank * heads * (m["qk_nope_head_dim"] + m["v_head_dim"])
            + heads * m["v_head_dim"] * d)


def expert_params(m: dict) -> int:
    return 3 * m["hidden_size"] * m["moe_intermediate_size"]


def experts_per_token_here(m: dict) -> float:
    """``n_routed_experts`` counts the experts held, ``router_width`` the
    published ones the router scores."""
    return (m["num_experts_per_tok"] * m["n_routed_experts"]
            / m["router_width"])


def expert_layers(m: dict) -> int:
    """Layers with experts: the kept ones after the dense ones, and the
    prediction modules'."""
    return (m["num_hidden_layers"] - m["first_k_dense_replace"]
            + m["num_nextn_predict_layers"])


def depths(m: dict) -> int:
    return 1 + m["num_nextn_predict_layers"]


def matmul_params_per_token(config: dict) -> float:
    m = config["model"]
    d = m["hidden_size"]
    ffn = (d * m["router_width"]
           + (m["n_shared_experts"] + experts_per_token_here(m))
           * expert_params(m))
    return (depths(m) * m["vocab_size"] * d
            + m["num_nextn_predict_layers"] * 2 * d * d
            + (m["num_hidden_layers"] + m["num_nextn_predict_layers"])
            * mla_params(m)
            + m["first_k_dense_replace"] * 3 * d * m["intermediate_size"]
            + expert_layers(m) * ffn)


def attention_flops_per_token(config: dict) -> float:
    m = config["model"]
    keys = (config["tokens_per_sample"] + 1) / 2
    layers = m["num_hidden_layers"] + m["num_nextn_predict_layers"]
    return (3 * 2 * keys * m["num_attention_heads"]
            * (qk_dim(m) + m["v_head_dim"]) * layers)


def flops_per_token(config: dict) -> float:
    return (6 * matmul_params_per_token(config)
            + attention_flops_per_token(config))


def flops_per_sample(config: dict) -> float:
    return flops_per_token(config) * config["tokens_per_sample"]


def parameters(config: dict) -> int:
    """Every parameter the share holds (what 16 bytes each are paid for)."""
    m = config["model"]
    d = m["hidden_size"]
    mla = mla_params(m) + m["q_lora_rank"] + m["kv_lora_rank"]
    norms = 2 * d
    dense = mla + norms + 3 * d * m["intermediate_size"]
    moe = (mla + norms + d * m["router_width"] + m["router_width"]
           + (m["n_routed_experts"] + m["n_shared_experts"])
           * expert_params(m))
    mtp = 3 * d + 2 * d * d + moe      # enorm, hnorm, its norm; eh_proj
    return (2 * m["vocab_size"] * d + d
            + m["first_k_dense_replace"] * dense
            + (m["num_hidden_layers"] - m["first_k_dense_replace"]) * moe
            + m["num_nextn_predict_layers"] * mtp)

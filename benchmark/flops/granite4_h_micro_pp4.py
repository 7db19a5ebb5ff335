"""Operations one training sample (one sequence) of the Granite-4.0-H-Micro
share needs, from the configuration's ``model`` block. jax-free.

Per token: 6 x (the parameters a token meets in a matrix multiplication
here) — 2 FLOPs a multiply-add, forward + both gradients — plus the
attention scores and the state-space scan. A token meets, on this chip: in
each Mamba layer ``in_proj`` (hidden x (2 d_in + 2 N + H), d_in = H x P)
and ``out_proj`` (d_in x hidden); in each attention layer q, k, v and o
(hidden x (heads + 2 KV heads) x 64 and back); in every layer the gated
MLP (3 x hidden x ``intermediate_size``); and the tied head (vocabulary
rows held x hidden; the embedding lookup is a gather). Scores: a query
sees (L+1)/2 keys on average, two products a key forward, twice that
backward. The scan is counted as the chunked algorithm computes it
forward (``granite4_kernels.ssd``'s forward count over Q positions), and
twice that backward, as a matrix product is. Norms, the convolution,
gating, the optimizer and what each block's recomputation repeats are left
out, as is usual for model FLOPs.
"""

from __future__ import annotations


def mamba_layers(m: dict) -> int:
    return sum(1 for kind in m["layer_types"] if kind == "mamba")


def attention_layers(m: dict) -> int:
    return len(m["layer_types"]) - mamba_layers(m)


def d_inner(m: dict) -> int:
    return m["mamba_n_heads"] * m["mamba_d_head"]


def in_proj_width(m: dict) -> int:
    """[z | x B C | dt]."""
    return (2 * d_inner(m) + 2 * m["mamba_n_groups"] * m["mamba_d_state"]
            + m["mamba_n_heads"])


def mixer_matmul_params(m: dict) -> int:
    return m["hidden_size"] * in_proj_width(m) + d_inner(m) * m["hidden_size"]


def head_dim(m: dict) -> int:
    return m["hidden_size"] // m["num_attention_heads"]


def attention_params(m: dict) -> int:
    d, hd = m["hidden_size"], head_dim(m)
    return d * hd * (2 * m["num_attention_heads"]
                     + 2 * m["num_key_value_heads"])


def mlp_params(m: dict) -> int:
    return 3 * m["hidden_size"] * m["intermediate_size"]


def matmul_params_per_token(config: dict) -> float:
    m = config["model"]
    return (mamba_layers(m) * mixer_matmul_params(m)
            + attention_layers(m) * attention_params(m)
            + len(m["layer_types"]) * mlp_params(m)
            + m["vocab_size"] * m["hidden_size"])


def attention_flops_per_token(config: dict) -> float:
    m = config["model"]
    keys = (config["tokens_per_sample"] + 1) / 2
    return (3 * 2 * keys * m["num_attention_heads"] * 2 * head_dim(m)
            * attention_layers(m))


def scan_flops_per_token(config: dict) -> float:
    """Forward and backward of the chunked scan, counted as the forward's
    operations three times (``granite4_kernels`` has the kernels' own
    backward count)."""
    m = config["model"]
    q, n, p = m["mamba_chunk_size"], m["mamba_d_state"], m["mamba_d_head"]
    # a chunk: C B^T once a group, then a head's masked product, the
    # carried state out and the state update
    chunk = (2 * q * q * n * m["mamba_n_groups"]
             + m["mamba_n_heads"] * (2 * q * q * p + 4 * q * n * p))
    return 3 * chunk / q * mamba_layers(m)


def flops_per_token(config: dict) -> float:
    return (6 * matmul_params_per_token(config)
            + attention_flops_per_token(config)
            + scan_flops_per_token(config))


def flops_per_sample(config: dict) -> float:
    return flops_per_token(config) * config["tokens_per_sample"]


def parameters(config: dict) -> int:
    """Every parameter the share holds (what 16 bytes each are paid for)."""
    m = config["model"]
    d, heads = m["hidden_size"], m["mamba_n_heads"]
    conv = in_proj_width(m) - d_inner(m) - heads
    mixer = (mixer_matmul_params(m) + (m["mamba_d_conv"] + 1) * conv
             + 3 * heads + d_inner(m))     # taps, bias; dt_bias, A_log, D; norm
    norms = 2 * d
    return (m["vocab_size"] * d + d
            + mamba_layers(m) * (mixer + mlp_params(m) + norms)
            + attention_layers(m) * (attention_params(m) + mlp_params(m)
                                     + norms))

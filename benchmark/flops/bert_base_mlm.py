"""Operations one BERT-base MLM training sample (one sequence) needs,
from shapes. jax-free.

Per token: 6 x (parameters that sit in a matrix multiplication) — 2 FLOPs
a multiply-add, forward + both gradients — plus attention's two L x L
products, 4·L·d forward and twice that backward, per layer. The tied
decoder is a (d x vocab) product over **all** positions (the program
computes every position's logits, masked or not) and is counted once; the
embedding lookup is a gather, not a product. Recomputed operations (none
here: no remat) would not count. LayerNorm, GELU, softmax and the
optimizer are left out, as is usual for model FLOPs.
"""

from __future__ import annotations


def matmul_params(config: dict) -> int:
    m = config["model"]
    d, ff = m["hidden_size"], m["intermediate_size"]
    per_layer = 4 * d * d + 2 * d * ff
    return (m["num_hidden_layers"] * per_layer
            + d * d                     # MLM transform
            + d * m["vocab_size"])      # tied decoder


def flops_per_token(config: dict) -> float:
    m = config["model"]
    attention = 3 * 4 * config["tokens_per_sample"] * m["hidden_size"]
    return float(6 * matmul_params(config)
                 + m["num_hidden_layers"] * attention)


def flops_per_sample(config: dict) -> float:
    return flops_per_token(config) * config["tokens_per_sample"]

"""Operations and HBM bytes of the attention calls the GLM-4.7-Flash share
adds to the benchmark (latent attention's up-projected heads through the
flash kernels) — the numerators of their roofline share. jax-free.

As ``flops/kernels.py``: FLOPs count the matrix products only (2 a
multiply-add), bytes are operands read once and results written once, and
``readers/kernels.py:_share`` takes the bytes from the trace's own text
where it has them. A query and key head is ``qk_dim`` wide, a value head
``v_dim`` (both 256 in this configuration).
"""

from __future__ import annotations


def causal_scores(length: int) -> int:
    """Scores a head must compute over a causal sequence: L(L+1)/2."""
    return length * (length + 1) // 2


def attention(batch: int, heads: int, length: int, qk_dim: int, v_dim: int,
              itemsize: int = 2) -> dict:
    """The three calls of one causal attention layer, counted over the
    scores the mask keeps. Forward: q.k and p.v a score; dq: q.k again,
    dO.v and dS.k; dkv: q.k again, p.dO (dv), dO.v and dS.q (dk)."""
    bh = batch * heads
    s = 2 * bh * causal_scores(length)
    qk = bh * length * qk_dim * itemsize
    v = bh * length * v_dim * itemsize
    row = bh * length * 4
    return {
        "fwd": {"flops": s * (qk_dim + v_dim), "bytes": 2 * qk + 2 * v + row},
        "dq": {"flops": s * (2 * qk_dim + v_dim),
               "bytes": 3 * qk + 2 * v + 2 * row},
        "dkv": {"flops": s * (2 * qk_dim + 2 * v_dim),
                "bytes": 3 * qk + 3 * v + 2 * row},
    }

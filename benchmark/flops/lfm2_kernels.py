"""Operations and HBM bytes of the kernels the LFM2 share adds to the
benchmark — the numerators of their roofline shares. jax-free.

As ``flops/kernels.py``: FLOPs count the matrix products only (2 a
multiply-add), bytes are operands read once and results written once, and
``readers/lfm2.py`` takes the bytes from the trace's own text where it has
them.
"""

from __future__ import annotations


def grouped_matmul(rows: float, hidden: int, width: int,
                   experts: int, itemsize: int = 2) -> dict:
    """One expert layer's calls over ``rows`` (token, expert) pairs — the
    rows the program counted, not the buffer's bound and not the tiles'
    padding. Per pass a layer makes two calls, x (rows, hidden) @ w13
    (hidden, 2 width) and h (rows, width) @ w2 (width, hidden): the cost
    given is the mean of the two, which is what a call costs on average
    whatever the pass, 3 x rows x hidden x width FLOPs.

    gmm   out = x @ w[e]           (forward, recomputed forward, dx)
    tgmm  dw[e] = x_e^T @ g_e      (float32 out)
    """
    product = 2 * rows * hidden * width          # one (hidden x width) pass
    flops = 1.5 * product
    acts = rows * (hidden + 2 * width + width + hidden) / 2 * itemsize
    weights = experts * 1.5 * hidden * width
    return {
        "gmm": {"flops": flops, "bytes": acts + weights * itemsize},
        "tgmm": {"flops": flops, "bytes": acts + weights * 4},
    }


def causal_flash_attention(batch: int, heads: int, length: int,
                           head_dim: int, itemsize: int = 2) -> dict:
    """The three calls of one causal attention layer: the L(L+1)/2 scores
    a causal kernel must compute, not the L^2 a kernel that masks after
    the product computes. Products a score as ``flops/kernels.py`` counts
    the full kernel's: forward 2, dq 3, dkv 4."""
    bh = batch * heads
    product = 2 * bh * (length * (length + 1) // 2) * head_dim
    tensor = bh * length * head_dim * itemsize
    row = bh * length * 4
    return {
        "fwd": {"flops": 2 * product, "bytes": 4 * tensor + row},
        "dq": {"flops": 3 * product, "bytes": 5 * tensor + 2 * row},
        "dkv": {"flops": 4 * product, "bytes": 6 * tensor + 2 * row},
    }

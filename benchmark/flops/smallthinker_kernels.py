"""Operations and HBM bytes of the attention calls the SmallThinker share
adds to the benchmark — the numerators of their roofline shares. jax-free.

As ``flops/kernels.py``: FLOPs count the matrix products only (2 a
multiply-add), bytes are operands read once and results written once, and
``readers/kernels.py:_share`` takes the bytes from the trace's own text
where it has them.
"""

from __future__ import annotations


def scores(length: int, window=None) -> int:
    """Scores a head must compute over a sequence of ``length``: the
    causal triangle, L(L+1)/2, or with a ``window`` (keys a query sees, its
    own included) the band: the first ``window`` queries see their whole
    prefix, every later one ``window`` keys."""
    if window is None or window >= length:
        return length * (length + 1) // 2
    return window * (window + 1) // 2 + (length - window) * window


def attention(batch: int, heads: int, length: int, head_dim: int,
              window=None, itemsize: int = 2) -> dict:
    """The three calls of one attention layer, causal (``window`` None) or
    banded: the scores the mask keeps, not the blocks a kernel sweeps to
    get them. Products a score as ``flops/kernels.py`` counts the full
    kernel's: forward 2, dq 3, dkv 4."""
    bh = batch * heads
    product = 2 * bh * scores(length, window) * head_dim
    tensor = bh * length * head_dim * itemsize
    row = bh * length * 4
    return {
        "fwd": {"flops": 2 * product, "bytes": 4 * tensor + row},
        "dq": {"flops": 3 * product, "bytes": 5 * tensor + 2 * row},
        "dkv": {"flops": 4 * product, "bytes": 6 * tensor + 2 * row},
    }

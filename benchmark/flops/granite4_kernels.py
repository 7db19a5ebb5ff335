"""Operations and HBM bytes of the state-space scan calls the Granite-4.0-H
share adds to the benchmark (``ops/ssd.py``) — the numerators of their
roofline share. jax-free.

FLOPs are the chunked algorithm's own matrix products (2 a multiply-add),
counted per (sequence, chunk of Q) whatever implements the scan; decays,
exponentials and the elementwise tail ride along. Forward, a chunk:

    C B^T                        2 Q^2 N     once a group
    a head's masked product      2 Q^2 P     (C B^T * decay * dt) x
    the carried state out        2 Q N P     C s^T
    the state update             2 Q N P     (x * w)^T B

Backward, a chunk: C B^T again (the algorithm does not keep the Q x Q
panels), and a head's dM = dy x^T and dx = M^T dy (2 x 2 Q^2 P), dC and
dB of the masked panel (2 x 2 Q^2 N), and five products with the (P, N)
state: the carried term's dC, its recomputed output and the state's
gradient, B ds^T and x ds (5 x 2 Q N P).

Bytes are the operands read once and the results written once (bf16
activations at ``itemsize``, float32 rows and states); as everywhere,
``readers/kernels.py:_share`` takes them from the trace's own text where it
has them.
"""

from __future__ import annotations


def ssd(batch: int, length: int, heads: int, head_dim: int, state: int,
        chunk: int, groups: int = 1, itemsize: int = 2) -> dict:
    """The two calls of one Mamba layer's scan over ``batch`` sequences."""
    q, p, n = chunk, head_dim, state
    chunks = batch * (length // q)
    shared = 2 * q * q * n * groups
    fwd = shared + heads * (2 * q * q * p + 4 * q * n * p)
    bwd = shared + heads * (4 * q * q * p + 4 * q * q * n + 10 * q * n * p)
    x = batch * length * heads * p * itemsize
    rows = batch * length * heads * 4                  # dt or a, float32
    bc = batch * length * groups * n * itemsize        # B or C
    states = chunks * heads * p * n * 4
    return {
        "fwd": {"flops": chunks * fwd,
                "bytes": x + 2 * rows + 2 * bc + x + states},
        "bwd": {"flops": chunks * bwd,
                "bytes": (2 * x + 2 * rows + 2 * bc + states      # in
                          + x + 2 * rows + 2 * bc * 4 // itemsize)},  # out
    }

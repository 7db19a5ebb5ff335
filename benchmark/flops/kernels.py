"""Operations and HBM bytes each Pallas kernel call needs, from shapes —
the numerators of the kernels' roofline shares. jax-free.

Bytes are the operands read once and the results written once: what the
algorithm must move, not what a given blocking re-reads. FLOPs count the
matrix products only (2 a multiply-add); exponentials and the elementwise
tail ride along. Shapes are per call on one chip.
"""

from __future__ import annotations


def flash_attention(batch: int, heads: int, length: int, head_dim: int,
                    itemsize: int = 2) -> dict:
    """The three calls of one attention layer, non-causal, full length.

    fwd   S = QK^T, O = PV                      2 products
    dq    S (recomputed), dP = dO V^T, dQ = dS K   3 products
    dkv   S (recomputed), dP, dV = P^T dO, dK = dS^T Q   4 products

    The recomputed S products are work the blockwise algorithm needs (it
    never stores the L x L scores), so they count here — this is the
    kernel's own roofline, not the model's FLOPs.
    """
    bh = batch * heads
    product = 2 * bh * length * length * head_dim
    tensor = bh * length * head_dim * itemsize       # one of q, k, v, o, do
    row = bh * length * 4                            # lse or delta, f32
    return {
        "fwd": {"flops": 2 * product, "bytes": 4 * tensor + row},
        "dq": {"flops": 3 * product, "bytes": 5 * tensor + 2 * row},
        "dkv": {"flops": 4 * product, "bytes": 6 * tensor + 2 * row},
    }


def fused_layer_norm(rows: int, width: int, in_itemsize: int,
                     out_itemsize: int) -> dict:
    """One LayerNorm over (rows, width): forward reads x and writes y
    (plus a mean and an rstd per row); backward reads x and dy and writes
    dx (plus the two (width,) parameter gradients). About 8 and 12 FLOPs
    an element — far under the bandwidth bound at any width."""
    n = rows * width
    stats = 2 * rows * 4
    return {
        "fwd": {"flops": 8 * n,
                "bytes": n * in_itemsize + n * out_itemsize + stats},
        "bwd": {"flops": 12 * n,
                "bytes": n * in_itemsize + n * out_itemsize
                + n * in_itemsize + stats + 2 * width * 4},
    }


def min_seconds(cost: dict, peak: dict) -> tuple:
    """(least seconds the chip could take, which bound sets it)."""
    compute = cost["flops"] / peak["bf16_flops_per_s"]
    memory = cost["bytes"] / peak["hbm_bytes_per_s"]
    return (compute, "compute") if compute >= memory else (memory, "memory")

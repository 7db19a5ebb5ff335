"""Pallas TPU kernels for the hot ops.

The reference has no custom kernels at all — its compute is ATen/cuDNN
(SURVEY.md §2.3); on TPU the XLA-generated kernels already cover the CNN
zoo. These kernels target the two places where hand-fusion beats stock XLA:

- **Flash attention, forward AND backward** (`pallas_attention`): blockwise
  softmax attention that never materializes the L×L score matrix in either
  direction; running max / normalizer accumulate in f32 (the same math as
  parallel/ring_attention.py's per-device inner loop — this is the
  single-chip analogue of a ring step), and the per-row log-sum-exp is
  saved as the backward residual. Backward: two kernels recompute
  probabilities per block from (q, k, lse) — dq sweeps K/V per Q block,
  dk/dv sweep Q/dO per K block — so training memory is O(L·D), not
  O(L²). HYBRID dispatch on L x head width (`_resident`): through
  L=8192 at width 64 the swept operands are VMEM-resident per program
  (fastest); past that, streamed-grid variants move them through a third
  grid dimension with scratch accumulators, so L is bounded by HBM (clean
  full-gradient timings to L=32768 on one v5e chip; L=65536 executes but
  its only timing capture was DCE-tainted — PERF.md "long-context"
  notes). Causal sweeps end (forward, dq) or start (dkv) at the diagonal
  in both families, and with a ``window`` (a query sees the ``window``
  keys that end with its own) they start / end where the band does: the
  resident loops take their bounds from `_causal_sweep`; the streamed
  grids are as long as the longest sweep and their index maps place each
  step's block, so they neither fetch nor compute a block outside it.
  Only the blocks the diagonal or the band's edge crosses compute scores
  they then mask. The streamed forward keeps its running max and sum
  lane-replicated in (bq, 128) scratch.
  Registered as a model attention impl (``attn_fn=pallas_attention``).
- **Int8 stochastic-rounding quantization**: `quantize_int8_scaled` is the
  quantize step of the int8 gradient collective — ops/compression.py calls
  it for large leaves on TPU, one VMEM pass on the hardware PRNG.
  `quantize_int8`/`dequantize_int8` are the standalone (own-scale) codec
  for point-to-point payloads such as checkpoint shipping (reference
  counterpart: the Blosc codec, src/compression.py:18-46, which compressed
  on the CPU before every MPI send).
- **Fused LayerNorm fwd+bwd** (`fused_layer_norm`): one VMEM pass per
  direction, f32 stats, output written directly in the requested dtype —
  targets the BERT-base roofline's bandwidth-bound LN tail (PERF.md);
  enabled by ``TransformerConfig.fused_ln`` / ``--fused-ln``.

All kernels run in interpret mode off-TPU, so the same tests run on the CPU
mesh (tests/test_pallas_kernels.py) and compiled on real chips.
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NEG_INF = -1e30

# Streamed flash grids: (batch*head, output block, streamed block). The
# first two dims are independent programs; the innermost dim carries the
# running state in scratch and must execute sequentially ("arbitrary").
_STREAM_PARAMS = pltpu.CompilerParams(
    dimension_semantics=("parallel", "parallel", "arbitrary"),
)


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


# ---------------------------------------------------------------------------
# Flash attention
# ---------------------------------------------------------------------------



def _block_scores(q_blk, k_blk, bias_row, causal, q0, k0, scale,
                  window=None):
    """Masked f32 score panel shared by all six flash kernels.

    q_blk (BQ, D) x k_blk (BK, D) -> s (BQ, BK), plus the additive
    lane-major bias row (1, BK) and, when causal, the (q0 + i >= k0 + j)
    triangle mask; with a ``window`` a query also sees no key further
    back than ``window - 1`` positions (``q_pos - k_pos < window``: the
    query's own key and the ``window - 1`` before it). The single home of
    the scoring/masking convention — the resident and streamed kernel
    variants differ only in where their operands and accumulators live.
    A row that sees nothing of a block takes p = 1 there while its
    running max is still ``_NEG_INF``; the first block that holds a key it
    sees (its own diagonal block at the latest) rescales that to zero.
    """
    BQ = q_blk.shape[0]
    BK = k_blk.shape[0]
    s = jax.lax.dot_general(
        q_blk, k_blk, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    ) * scale  # (BQ, BK)
    s = s + jnp.broadcast_to(bias_row, (BQ, BK))
    if causal:
        q_pos = q0 + jax.lax.broadcasted_iota(jnp.int32, (BQ, BK), 0)
        k_pos = k0 + jax.lax.broadcasted_iota(jnp.int32, (BQ, BK), 1)
        seen = q_pos >= k_pos
        if window is not None:
            seen = seen & (q_pos - k_pos < window)
        s = jnp.where(seen, s, _NEG_INF)
    return s


def _causal_sweep(causal: bool, j, own: int, swept: int, n: int,
                  own_is_query: bool, window=None):
    """Blocks ``[lo, hi)`` of the swept operand that program ``j`` needs.

    The single home of the rule "a (query block, key block) pair counts
    iff it holds at least one score the mask keeps" (``q_pos >= k_pos``
    and, with a ``window``, ``q_pos - k_pos < window``). ``j`` indexes
    blocks of ``own`` rows, the sweep runs over ``n`` blocks of ``swept``
    rows. A query block (forward, dq) needs the key blocks that start at
    or before its last row and, with a window, end at or after its first
    row less ``window - 1``; a key block (dkv) needs the query blocks
    whose last row reaches its first column and, with a window, whose
    first row lies within ``window - 1`` of its last column. The causal
    ``hi <= n`` needs no clamp: the last own row is < L = n * swept. Not
    causal: the Python ints ``(0, n)``, so the loop lowers with a static
    trip count. ``j`` may be a traced program index (in a kernel or in an
    index map) or a Python int (tests, ``_streamed_sweep``).
    """
    if not causal:
        return 0, n
    at_least, at_most = ((max, min) if isinstance(j, int)
                         else (jnp.maximum, jnp.minimum))
    if own_is_query:
        lo = 0 if window is None else (
            at_least(j * own - (window - 1), 0) // swept)
        return lo, (j * own + own - 1) // swept + 1
    hi = n if window is None else at_most(
        (j * own + own - 1 + window - 1) // swept + 1, n)
    return (j * own) // swept, hi


def _streamed_sweep(causal: bool, n_own: int, own: int, swept: int, n: int,
                    own_is_query: bool, window=None):
    """``(steps, at)`` for a streamed grid whose ``n_own`` programs each
    sweep ``_causal_sweep``'s range. ``steps`` is the longest sweep any of
    them makes, the grid's innermost dimension: ``n``, or under a window
    its band (``ceil((window - 1) / block) + 1`` blocks at equal block
    sizes). ``at(j, t)`` is the swept operand's block at step ``t`` of
    program ``j``, for the index maps: ``lo + t`` while the sweep lasts
    and its last block from then on, which Pallas does not fetch again —
    so a causal grid fetches, and not only computes, nothing past the
    diagonal, and a window's grid nothing outside its band. Not causal:
    ``(n, t itself)``."""
    if not causal:
        return n, lambda j, t: t

    def sweep(j):
        return _causal_sweep(True, j, own, swept, n, own_is_query, window)

    def at(j, t):
        lo, hi = sweep(j)
        return jnp.minimum(lo + t, hi - 1)

    return max(hi - lo for lo, hi in map(sweep, range(n_own))), at


# Lanes of the streamed forward's running max and sum: each row's value
# is held replicated across one lane tile, (BQ, 128), so that it meets a
# (BQ, BK) score panel or a (BQ, D) accumulator as a tile repeat and not
# as a broadcast out of a one-lane (BQ, 1) layout.
_STAT_LANES = 128


def _lanes(x, n: int):
    """(BQ, _STAT_LANES) lane-replicated statistics -> (BQ, n): whole lane
    tiles repeated, the last one cut to ``n`` (a no-op where ``n`` is a
    multiple of 128, as the cells' block and head widths are)."""
    return jnp.tile(x, (1, pl.cdiv(n, _STAT_LANES)))[:, :n]


def _flash_fwd_kernel(q_ref, k_ref, v_ref, mask_ref, o_ref, lse_ref,
                      acc_ref, m_ref, l_ref, *,
                      block_k: int, causal: bool, q_block: int,
                      scale: float, n_k: int, window=None):
    """Grid (B*H, L/bq, sweep), the sweep innermost: K/V STREAM through
    VMEM as (bk, D) grid blocks while the (o, m, l) running state lives in
    scratch across the sweep. Nothing full-length is ever VMEM-resident,
    so sequence length is bounded by HBM, not VMEM. Step ``t`` of the
    sweep holds key block ``lo + t`` of ``_causal_sweep``'s range (the
    index maps place it); the grid is as long as the longest sweep
    (``_streamed_sweep``: all ``n_k`` blocks, or a window's band) and a
    program whose own sweep is shorter idles through the rest. Also
    emits the per-row log-sum-exp (m + log l) — the residual the
    blockwise backward needs.

    The running max ``m`` and sum ``l`` live lane-replicated in
    (BQ, _STAT_LANES) scratch; ``lse`` takes lane 0 once, at the end.
    """
    j = pl.program_id(1)
    t = pl.program_id(2)
    nt = pl.num_programs(2)
    q = q_ref[0]  # (BQ, D)
    BQ, D = q.shape
    lo, hi = _causal_sweep(causal, j, q_block, block_k, n_k, True, window)
    kb = lo + t

    @pl.when(t == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)
        m_ref[:] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)

    def compute():
        k_blk = k_ref[0]  # (BK, D)
        v_blk = v_ref[0]
        # mask is (1, 1, L) holding an ADDITIVE bias (0 keep / -1e30
        # drop), L on the LANE axis: a (1, L, 1) sublane layout pads the
        # lane dim 1->128 in VMEM (16x the bytes) and the (1, BK) slice
        # broadcasts straight along the sublane (row) axis. (Do NOT
        # collapse to 1-D and re-expand with [None, :]: that
        # sublane->lane relayout compiles pathologically in multi-output
        # kernels.)
        s = _block_scores(q, k_blk, mask_ref[0], causal,
                          j * q_block, kb * block_k, scale, window)
        m = m_ref[:]  # (BQ, 128), each row's max in every lane
        m_new = jnp.maximum(m, s.max(axis=1, keepdims=True))
        corr = jnp.exp(m - m_new)
        p = jnp.exp(s - _lanes(m_new, block_k))
        l_ref[:] = l_ref[:] * corr + p.sum(axis=1, keepdims=True)
        acc_ref[:] = acc_ref[:] * _lanes(corr, D) + jax.lax.dot_general(
            p.astype(v_blk.dtype), v_blk, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        m_ref[:] = m_new

    if causal:
        # past the sweep's end (the diagonal) nothing contributes
        @pl.when(kb < hi)
        def _():
            compute()
    else:
        compute()

    @pl.when(t == nt - 1)
    def _finalize():
        l = jnp.maximum(l_ref[:], 1e-30)
        o_ref[0] = (acc_ref[:] / _lanes(l, D)).astype(o_ref.dtype)
        # Fully-masked rows: m stays at ~_NEG_INF so lse bottoms out
        # there too. The backward recomputes p = exp(s + bias - lse); for
        # rows with at least one valid key the -1e30 bias makes masked
        # entries underflow to 0, while fully-masked rows degenerate to
        # an ordinary softmax over masked keys — same
        # garbage-in-garbage-out as stock XLA attention.
        lse_ref[0] = (m_ref[:] + jnp.log(l))[:, :1]


def _to_bh(x):
    """(B, L, H, D) -> (B*H, L, D): batch and head are grid-parallel."""
    B, L, H, D = x.shape
    return x.transpose(0, 2, 1, 3).reshape(B * H, L, D)


def _from_bh(x, B, H):
    BH, L, D = x.shape
    return x.reshape(B, H, L, D).transpose(0, 2, 1, 3)


def _mask_bh(mask, B, L, H):
    """(B, L) or None -> (B*H, 1, L) f32 ADDITIVE bias (0 keep, -1e30
    drop), L on the LANE axis (see the fwd kernel's layout note)."""
    if mask is None:
        return jnp.zeros((B * H, 1, L), jnp.float32)
    bias = jnp.where(mask.astype(bool), 0.0, _NEG_INF).astype(jnp.float32)
    return jnp.repeat(bias, H, axis=0)[:, None, :]


def _flash_forward(q, k, v, mask, causal: bool, block_q: int, block_k: int,
                   window=None):
    """q/k/v: (B, L, H, D); mask: (B, L) or None → (out, lse).

    ``lse`` is the (B*H, L, 1) per-row log-sum-exp residual consumed by the
    blockwise backward.
    """
    B, L, H, D = q.shape
    scale = 1.0 / np.sqrt(D)
    bq = min(block_q, L)
    bk = min(block_k, L)
    if L % bq or L % bk:  # callers pick valid blocks via _pick_block
        raise ValueError(f"L={L} must be divisible by block sizes {bq},{bk}")

    qb, kb, vb = _to_bh(q), _to_bh(k), _to_bh(v)
    mask_bh = _mask_bh(mask, B, L, H)

    if _resident(L, D):  # fast path: K/V resident per program
        out, lse = pl.pallas_call(
            functools.partial(
                _flash_fwd_kernel_res,
                block_k=bk, causal=causal, q_block=bq, scale=scale,
                window=window,
            ),
            out_shape=(
                jax.ShapeDtypeStruct((B * H, L, D), q.dtype),
                jax.ShapeDtypeStruct((B * H, L, 1), jnp.float32),
            ),
            grid=(B * H, L // bq),
            in_specs=[
                pl.BlockSpec((1, bq, D), lambda i, j: (i, j, 0),
                             memory_space=pltpu.VMEM),
                pl.BlockSpec((1, L, D), lambda i, j: (i, 0, 0),
                             memory_space=pltpu.VMEM),
                pl.BlockSpec((1, L, D), lambda i, j: (i, 0, 0),
                             memory_space=pltpu.VMEM),
                pl.BlockSpec((1, 1, L), lambda i, j: (i, 0, 0),
                             memory_space=pltpu.VMEM),
            ],
            out_specs=(
                pl.BlockSpec((1, bq, D), lambda i, j: (i, j, 0),
                             memory_space=pltpu.VMEM),
                pl.BlockSpec((1, bq, 1), lambda i, j: (i, j, 0),
                             memory_space=pltpu.VMEM),
            ),
            interpret=_interpret(),
        )(qb, kb, vb, mask_bh)
        return _from_bh(out, B, H), lse

    n_k = L // bk
    steps, kblock = _streamed_sweep(causal, L // bq, bq, bk, n_k, True, window)
    grid = (B * H, L // bq, steps)
    out, lse = pl.pallas_call(
        functools.partial(
            _flash_fwd_kernel,
            block_k=bk, causal=causal, q_block=bq, scale=scale,
            n_k=n_k, window=window,
        ),
        out_shape=(
            jax.ShapeDtypeStruct((B * H, L, D), q.dtype),
            jax.ShapeDtypeStruct((B * H, L, 1), jnp.float32),
        ),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bq, D), lambda i, j, t: (i, j, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, bk, D), lambda i, j, t: (i, kblock(j, t), 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, bk, D), lambda i, j, t: (i, kblock(j, t), 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, 1, bk), lambda i, j, t: (i, 0, kblock(j, t)),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=(
            pl.BlockSpec((1, bq, D), lambda i, j, t: (i, j, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, bq, 1), lambda i, j, t: (i, j, 0),
                         memory_space=pltpu.VMEM),
        ),
        scratch_shapes=[
            pltpu.VMEM((bq, D), jnp.float32),
            pltpu.VMEM((bq, _STAT_LANES), jnp.float32),
            pltpu.VMEM((bq, _STAT_LANES), jnp.float32),
        ],
        compiler_params=_STREAM_PARAMS,
        interpret=_interpret(),
    )(qb, kb, vb, mask_bh)
    return _from_bh(out, B, H), lse


def _flash_dq_kernel(q_ref, k_ref, v_ref, mask_ref, lse_ref, delta_ref,
                     do_ref, dq_ref, acc_ref, *, block_k: int, causal: bool,
                     q_block: int, scale: float, n_k: int, window=None):
    """dq: grid (B*H, L/bq, sweep), K/V streaming, dq accumulates in
    scratch; the sweep is the forward's (key block ``lo + t``).

    Recomputes p = exp(s*scale - lse) per block from the forward's lse
    residual — no L×L materialization. ds = p ⊙ (dp − delta); dq = ds @ K.
    """
    j = pl.program_id(1)
    t = pl.program_id(2)
    nt = pl.num_programs(2)
    q = q_ref[0]  # (BQ, D)
    BQ, D = q.shape
    lo, hi = _causal_sweep(causal, j, q_block, block_k, n_k, True, window)
    kb = lo + t

    @pl.when(t == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)

    def compute():
        k_blk = k_ref[0]  # (BK, D)
        v_blk = v_ref[0]
        do = do_ref[0].astype(jnp.float32)  # (BQ, D)
        # lse/delta are lane-major (1, 1, BQ) blocks; expand to per-row
        # (BQ, BK) panels via sublane broadcast + transpose
        lse = jnp.broadcast_to(lse_ref[0], (block_k, BQ)).T
        delta = jnp.broadcast_to(delta_ref[0], (block_k, BQ)).T
        s = _block_scores(q, k_blk, mask_ref[0], causal,
                          j * q_block, kb * block_k, scale, window)
        # masked entries carry s ≈ -1e30, so exp(s - lse) underflows to 0
        # for any row with at least one valid key (same additive-bias
        # convention as the forward).
        p = jnp.exp(s - lse)  # (BQ, BK) f32
        dp = jax.lax.dot_general(
            do, v_blk.astype(jnp.float32), (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # (BQ, BK)
        ds = p * (dp - delta) * scale
        acc_ref[:] = acc_ref[:] + jax.lax.dot_general(
            ds.astype(k_blk.dtype), k_blk, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    if causal:
        @pl.when(kb < hi)
        def _():
            compute()
    else:
        compute()

    @pl.when(t == nt - 1)
    def _finalize():
        dq_ref[0] = acc_ref[:].astype(dq_ref.dtype)


def _flash_dkv_kernel(k_ref, v_ref, q_ref, mask_ref, lse_ref, delta_ref,
                      do_ref, dk_ref, dv_ref, dk_acc, dv_acc, *,
                      block_q: int, causal: bool, k_block: int,
                      scale: float, n_q: int, window=None):
    """dk/dv: grid (B*H, L/bk, sweep), Q/dO streaming, dk/dv in scratch;
    step ``t`` holds query block ``lo + t`` of the key block's sweep,
    which starts at the diagonal and, with a window, ends with its band."""
    j = pl.program_id(1)
    t = pl.program_id(2)
    nt = pl.num_programs(2)
    k = k_ref[0]  # (BK, D)
    BK, D = k.shape
    lo, hi = _causal_sweep(causal, j, k_block, block_q, n_q, False, window)
    qb = lo + t

    @pl.when(t == 0)
    def _init():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    def compute():
        q_blk = q_ref[0]  # (BQ, D)
        do_blk = do_ref[0].astype(jnp.float32)
        # additive key bias: lane-major (1, BK) broadcasts straight along
        # the sublane axis; lse/delta (1, BQ) become per-ROW vectors via
        # sublane broadcast + transpose (the lane dim must index BK)
        lse_blk = jnp.broadcast_to(lse_ref[0], (BK, block_q)).T  # (BQ, BK)
        delta_blk = jnp.broadcast_to(delta_ref[0], (BK, block_q)).T
        s = _block_scores(q_blk, k, mask_ref[0], causal,
                          qb * block_q, j * k_block, scale, window)
        p = jnp.exp(s - lse_blk)  # (BQ, BK)
        dv_acc[:] = dv_acc[:] + jax.lax.dot_general(
            p, do_blk, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # (BK, D)
        dp = jax.lax.dot_general(
            do_blk, v_ref[0].astype(jnp.float32), (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # (BQ, BK)
        ds = p * (dp - delta_blk) * scale
        dk_acc[:] = dk_acc[:] + jax.lax.dot_general(
            ds, q_blk.astype(jnp.float32), (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # (BK, D)

    if causal:
        # the sweep starts at the diagonal (lo); past its end (the
        # sequence's, or the window's band's) nothing contributes
        @pl.when(qb < hi)
        def _():
            compute()
    else:
        compute()

    @pl.when(t == nt - 1)
    def _finalize():
        dk_ref[0] = dk_acc[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[:].astype(dv_ref.dtype)


# --- resident variants (_resident(L, D)) ----------------------------------
#
# K/V (fwd, dq) / Q,dO (dkv) stay VMEM-resident for the whole program and
# an in-kernel fori_loop sweeps them. ~5-20% faster than the streamed
# grid at short L (no per-block re-fetch of the resident operands, no 3-D
# grid overhead) but VMEM-bounded: past L~8k the resident copies plus
# double buffering abort the Mosaic compiler, so _flash_forward /
# _flash_backward dispatch to the streamed kernels above that point.
# When causal the loop covers only _causal_sweep's blocks (a trip count
# that depends on the program's index: (n + 1) / 2n of the n x n blocks,
# fewer under a window); when not, its bounds are the Python ints 0 and
# n: a static trip count.
#
# The limit was found at head width 64, on an earlier compiler, and it is a
# limit on bytes, so the choice goes by L x head width: a resident K/V copy
# at width 128 is twice the bytes of one at 64. Widths under 64 count as 64,
# so at every width through 64 the choice is by L alone, as it was. It is a
# cautious limit: for the v5e, libtpu 0.0.34 compiles the resident kernels
# at 8192 x 128 and at 16,384 x 64 too (sandbox, PR 38; not run), and which
# family is the faster past the limit has not been measured (PERF.md
# section 7).

_RESIDENT_MAX_L = 8192        # at head width _RESIDENT_HEAD_DIM
_RESIDENT_HEAD_DIM = 64


def _resident(L: int, D: int) -> bool:
    return (L * max(D, _RESIDENT_HEAD_DIM)
            <= _RESIDENT_MAX_L * _RESIDENT_HEAD_DIM)


def _flash_fwd_kernel_res(q_ref, k_ref, v_ref, mask_ref, o_ref, lse_ref, *,
                          block_k: int, causal: bool, q_block: int,
                          scale: float, window=None):
    """One (batch*head, q-block) program: resident K/V, fori_loop sweep."""
    j = pl.program_id(1)
    q = q_ref[0]  # (BQ, D)
    BQ, D = q.shape
    L = k_ref.shape[1]
    lo, hi = _causal_sweep(causal, j, q_block, block_k, L // block_k, True,
                           window)

    def body(kb, carry):
        o, m, l = carry
        k_blk = k_ref[0, pl.ds(kb * block_k, block_k), :]  # (BK, D)
        v_blk = v_ref[0, pl.ds(kb * block_k, block_k), :]
        bias = mask_ref[0, :, pl.ds(kb * block_k, block_k)]  # (1, BK)
        s = _block_scores(q, k_blk, bias, causal,
                          j * q_block, kb * block_k, scale, window)
        m_new = jnp.maximum(m, s.max(axis=1, keepdims=True))
        corr = jnp.exp(m - m_new)
        p = jnp.exp(s - m_new)
        l_new = l * corr + p.sum(axis=1, keepdims=True)
        pv = jax.lax.dot_general(
            p.astype(v_blk.dtype), v_blk, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        return o * corr + pv, m_new, l_new

    o = jnp.zeros((BQ, D), jnp.float32)
    m = jnp.full((BQ, 1), _NEG_INF, jnp.float32)
    l = jnp.zeros((BQ, 1), jnp.float32)
    o, m, l = jax.lax.fori_loop(lo, hi, body, (o, m, l))
    o_ref[0] = (o / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)
    lse_ref[0] = m + jnp.log(jnp.maximum(l, 1e-30))


def _flash_dq_kernel_res(q_ref, k_ref, v_ref, mask_ref, lse_ref, delta_ref,
                         do_ref, dq_ref, *, block_k: int, causal: bool,
                         q_block: int, scale: float, window=None):
    """dq for one (batch*head, q-block) program: resident K/V sweep."""
    j = pl.program_id(1)
    q = q_ref[0]  # (BQ, D)
    BQ, D = q.shape
    L = k_ref.shape[1]
    lo, hi = _causal_sweep(causal, j, q_block, block_k, L // block_k, True,
                           window)
    lse = jnp.broadcast_to(lse_ref[0], (block_k, BQ)).T    # (BQ, BK) f32
    delta = jnp.broadcast_to(delta_ref[0], (block_k, BQ)).T  # (BQ, BK)
    do = do_ref[0].astype(jnp.float32)  # (BQ, D)

    def body(kb, dq):
        k_blk = k_ref[0, pl.ds(kb * block_k, block_k), :]  # (BK, D)
        v_blk = v_ref[0, pl.ds(kb * block_k, block_k), :]
        bias = mask_ref[0, :, pl.ds(kb * block_k, block_k)]  # (1, BK)
        s = _block_scores(q, k_blk, bias, causal,
                          j * q_block, kb * block_k, scale, window)
        p = jnp.exp(s - lse)  # (BQ, BK) f32
        dp = jax.lax.dot_general(
            do, v_blk.astype(jnp.float32), (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # (BQ, BK)
        ds = p * (dp - delta) * scale
        return dq + jax.lax.dot_general(
            ds.astype(k_blk.dtype), k_blk, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    dq = jax.lax.fori_loop(lo, hi, body, jnp.zeros((BQ, D), jnp.float32))
    dq_ref[0] = dq.astype(dq_ref.dtype)


def _flash_dkv_kernel_res(k_ref, v_ref, q_ref, mask_ref, lse_ref, delta_ref,
                          do_ref, dk_ref, dv_ref, *, block_q: int,
                          causal: bool, k_block: int, scale: float,
                          window=None):
    """dk/dv for one (batch*head, k-block) program: resident Q/dO sweep."""
    j = pl.program_id(1)
    k = k_ref[0]  # (BK, D)
    BK, D = k.shape
    L = q_ref.shape[1]
    lo, hi = _causal_sweep(causal, j, k_block, block_q, L // block_q, False,
                           window)

    def body(qb, carry):
        dk, dv = carry
        q_blk = q_ref[0, pl.ds(qb * block_q, block_q), :]  # (BQ, D)
        do_blk = do_ref[0, pl.ds(qb * block_q, block_q), :].astype(jnp.float32)
        lse_blk = jnp.broadcast_to(
            lse_ref[0, :, pl.ds(qb * block_q, block_q)], (BK, block_q)
        ).T  # (BQ, BK)
        delta_blk = jnp.broadcast_to(
            delta_ref[0, :, pl.ds(qb * block_q, block_q)], (BK, block_q)
        ).T
        s = _block_scores(q_blk, k, mask_ref[0], causal,
                          qb * block_q, j * k_block, scale, window)
        p = jnp.exp(s - lse_blk)  # (BQ, BK)
        dv = dv + jax.lax.dot_general(
            p, do_blk, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # (BK, D)
        dp = jax.lax.dot_general(
            do_blk, v_ref[0].astype(jnp.float32), (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # (BQ, BK)
        ds = p * (dp - delta_blk) * scale
        dk = dk + jax.lax.dot_general(
            ds, q_blk.astype(jnp.float32), (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # (BK, D)
        return dk, dv

    dk, dv = jax.lax.fori_loop(
        lo, hi, body,
        (jnp.zeros((BK, D), jnp.float32), jnp.zeros((BK, D), jnp.float32)),
    )
    dk_ref[0] = dk.astype(dk_ref.dtype)
    dv_ref[0] = dv.astype(dv_ref.dtype)


def _flash_backward(q, k, v, mask, out, lse, g, causal: bool,
                    block_q: int, block_k: int, window=None):
    """Blockwise VJP: O(L) memory (never materializes the L×L scores).

    Replaces the closed-form jnp backward the round-1 build shipped (which
    recomputed the full score matrix — O(L²) memory, defeating the flash
    forward's point for training). delta = rowsum(dO ⊙ O) is the standard
    softmax-VJP rank-1 correction, computed outside the kernels (one fused
    O(L·D) pass). Where `_resident(L, D)` the swept operands (K/V for
    dq, Q/dO for dkv) are VMEM-resident; past it they stream as grid
    blocks. Either way the per-row lse/delta vectors ride lane-major
    (BH, 1, L) tiles.
    """
    B, L, H, D = q.shape
    scale = 1.0 / np.sqrt(D)
    bq = min(block_q, L)
    bk = min(block_k, L)

    qb, kb, vb = _to_bh(q), _to_bh(k), _to_bh(v)
    gb = _to_bh(g)
    ob = _to_bh(out)
    mask_bh = _mask_bh(mask, B, L, H)
    lse_t = jnp.transpose(lse, (0, 2, 1))  # (BH, 1, L) lane-major
    delta_t = jnp.sum(
        gb.astype(jnp.float32) * ob.astype(jnp.float32), axis=-1,
    )[:, None, :]  # (BH, 1, L)

    if _resident(L, D):  # fast path: resident-operand kernels
        full = lambda i, j: (i, 0, 0)
        blk_q = lambda i, j: (i, j, 0)
        lane_blk = lambda i, j: (i, 0, j)
        r_full_d = pl.BlockSpec((1, L, D), full, memory_space=pltpu.VMEM)
        r_full_lane = pl.BlockSpec((1, 1, L), full, memory_space=pltpu.VMEM)
        r_bq_d = pl.BlockSpec((1, bq, D), blk_q, memory_space=pltpu.VMEM)
        r_bq_lane = pl.BlockSpec((1, 1, bq), lane_blk,
                                 memory_space=pltpu.VMEM)
        r_bk_d = pl.BlockSpec((1, bk, D), blk_q, memory_space=pltpu.VMEM)
        r_bk_lane = pl.BlockSpec((1, 1, bk), lane_blk,
                                 memory_space=pltpu.VMEM)
        dq = pl.pallas_call(
            functools.partial(
                _flash_dq_kernel_res,
                block_k=bk, causal=causal, q_block=bq, scale=scale,
                window=window,
            ),
            out_shape=jax.ShapeDtypeStruct((B * H, L, D), q.dtype),
            grid=(B * H, L // bq),
            in_specs=[r_bq_d, r_full_d, r_full_d, r_full_lane,
                      r_bq_lane, r_bq_lane, r_bq_d],
            out_specs=r_bq_d,
            interpret=_interpret(),
        )(qb, kb, vb, mask_bh, lse_t, delta_t, gb)
        dk, dv = pl.pallas_call(
            functools.partial(
                _flash_dkv_kernel_res,
                block_q=bq, causal=causal, k_block=bk, scale=scale,
                window=window,
            ),
            out_shape=(
                jax.ShapeDtypeStruct((B * H, L, D), k.dtype),
                jax.ShapeDtypeStruct((B * H, L, D), v.dtype),
            ),
            grid=(B * H, L // bk),
            in_specs=[r_bk_d, r_bk_d, r_full_d, r_bk_lane,
                      r_full_lane, r_full_lane, r_full_d],
            out_specs=(r_bk_d, r_bk_d),
            interpret=_interpret(),
        )(kb, vb, qb, mask_bh, lse_t, delta_t, gb)
        return (
            _from_bh(dq, B, H),
            _from_bh(dk, B, H),
            _from_bh(dv, B, H),
        )

    n_q, n_k = L // bq, L // bk
    k_steps, kblock = _streamed_sweep(causal, n_q, bq, bk, n_k, True, window)
    spec_q_d = pl.BlockSpec((1, bq, D), lambda i, j, t: (i, j, 0),
                            memory_space=pltpu.VMEM)
    spec_k_stream = pl.BlockSpec(
        (1, bk, D), lambda i, j, t: (i, kblock(j, t), 0),
        memory_space=pltpu.VMEM)
    spec_mask_stream = pl.BlockSpec(
        (1, 1, bk), lambda i, j, t: (i, 0, kblock(j, t)),
        memory_space=pltpu.VMEM)
    spec_lane_j = pl.BlockSpec((1, 1, bq), lambda i, j, t: (i, 0, j),
                               memory_space=pltpu.VMEM)

    dq = pl.pallas_call(
        functools.partial(
            _flash_dq_kernel,
            block_k=bk, causal=causal, q_block=bq, scale=scale,
            n_k=n_k, window=window,
        ),
        out_shape=jax.ShapeDtypeStruct((B * H, L, D), q.dtype),
        grid=(B * H, n_q, k_steps),
        in_specs=[spec_q_d, spec_k_stream, spec_k_stream,
                  spec_mask_stream, spec_lane_j, spec_lane_j, spec_q_d],
        out_specs=spec_q_d,
        scratch_shapes=[pltpu.VMEM((bq, D), jnp.float32)],
        compiler_params=_STREAM_PARAMS,
        interpret=_interpret(),
    )(qb, kb, vb, mask_bh, lse_t, delta_t, gb)

    q_steps, qblock = _streamed_sweep(causal, n_k, bk, bq, n_q, False, window)
    spec_k_d = pl.BlockSpec((1, bk, D), lambda i, j, t: (i, j, 0),
                            memory_space=pltpu.VMEM)
    spec_q_stream = pl.BlockSpec(
        (1, bq, D), lambda i, j, t: (i, qblock(j, t), 0),
        memory_space=pltpu.VMEM)
    spec_mask_j = pl.BlockSpec((1, 1, bk), lambda i, j, t: (i, 0, j),
                               memory_space=pltpu.VMEM)
    spec_lane_stream = pl.BlockSpec(
        (1, 1, bq), lambda i, j, t: (i, 0, qblock(j, t)),
        memory_space=pltpu.VMEM)

    dk, dv = pl.pallas_call(
        functools.partial(
            _flash_dkv_kernel,
            block_q=bq, causal=causal, k_block=bk, scale=scale,
            n_q=n_q, window=window,
        ),
        out_shape=(
            jax.ShapeDtypeStruct((B * H, L, D), k.dtype),
            jax.ShapeDtypeStruct((B * H, L, D), v.dtype),
        ),
        grid=(B * H, n_k, q_steps),
        in_specs=[spec_k_d, spec_k_d, spec_q_stream, spec_mask_j,
                  spec_lane_stream, spec_lane_stream, spec_q_stream],
        out_specs=(spec_k_d, spec_k_d),
        scratch_shapes=[pltpu.VMEM((bk, D), jnp.float32),
                        pltpu.VMEM((bk, D), jnp.float32)],
        compiler_params=_STREAM_PARAMS,
        interpret=_interpret(),
    )(kb, vb, qb, mask_bh, lse_t, delta_t, gb)

    return (
        _from_bh(dq, B, H),
        _from_bh(dk, B, H),
        _from_bh(dv, B, H),
    )


def _make_flash(causal: bool, block_q: int, block_k: int, window=None):
    @jax.custom_vjp
    def flash(q, k, v, mask):
        out, _ = _flash_forward(q, k, v, mask, causal, block_q, block_k,
                                window)
        return out

    def fwd(q, k, v, mask):
        out, lse = _flash_forward(q, k, v, mask, causal, block_q, block_k,
                                  window)
        return out, (q, k, v, mask, out, lse)

    def bwd(res, g):
        q, k, v, mask, out, lse = res
        dq, dk, dv = _flash_backward(
            q, k, v, mask, out, lse, g, causal, block_q, block_k, window
        )
        return dq, dk, dv, None

    flash.defvjp(fwd, bwd)
    return flash


# Preferred block size, tuned on TPU v5e: bq=bk=512 (both the resident
# kernels' sweep block and the streamed kernels' grid block). Which
# kernel family runs is decided by _resident(L, D), not block size.
_PREFERRED_BLOCK = 512
_FLASH_CACHE = {}


def _pick_block(L: int) -> int:
    """Largest valid block <= _PREFERRED_BLOCK for sequence length L.

    L <= preferred: the block is the whole sequence (Mosaic allows a block
    dim equal to the array dim). Otherwise the block must divide L and be a
    multiple of 8 (Mosaic sublane tiling).
    """
    if L <= _PREFERRED_BLOCK:
        return L
    for d in range(_PREFERRED_BLOCK, 7, -8):
        if L % d == 0:
            return d
    raise ValueError(
        f"no valid flash-attention block for L={L}: pad the sequence "
        f"length to a multiple of 8 with a divisor <= {_PREFERRED_BLOCK}"
    )


def pallas_attention(q, k, v, mask=None, causal: bool = False,
                     window: Optional[int] = None):
    """Model-zoo attention impl backed by the flash kernel.

    Drop-in for `models.transformer.full_attention`: q/k/v (B, L, H, D),
    optional (B, L) pad mask, and with ``causal`` an optional ``window``:
    query i sees keys ``i - window + 1 .. i``. A window that covers the
    sequence is no window (the causal kernels, as they are without the
    argument). Differentiable (custom VJP). Block sizes are chosen per
    sequence length (cached per (causal, block, window)).
    """
    if window is not None:
        if not causal or window < 1:
            raise ValueError(
                f"window={window} needs causal=True and at least the "
                "query's own key")
        if window >= q.shape[1]:
            window = None
    b = _pick_block(q.shape[1])
    key = (causal, b, window)
    if key not in _FLASH_CACHE:
        _FLASH_CACHE[key] = _make_flash(causal, b, b, window)
    return _FLASH_CACHE[key](q, k, v, mask)


# ---------------------------------------------------------------------------
# Decode-mode flash attention (generative serving, serving/generate/)
# ---------------------------------------------------------------------------


def _decode_attn_kernel(q_ref, k_ref, v_ref, bias_ref, o_ref, *,
                        scale: float):
    """One (batch, head) program: a single query row against the whole
    cached K/V panel, VMEM-resident.

    Decode attention has no L×L matrix to tile away — the working set is
    the (S, D) cache panel itself, read once per token: the textbook
    HBM-bound op the decode roofline (analysis/costmodel.py) models. The
    additive bias row carries the validity mask (0 keep / -1e30 drop for
    cache rows past the sequence's current position), the same lane-major
    layout convention as the training flash kernels.
    """
    q = q_ref[0]  # (1, D)
    k = k_ref[0]  # (S, D)
    v = v_ref[0]
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    ) * scale + bias_ref[0]  # (1, S)
    m = jnp.max(s, axis=1, keepdims=True)
    p = jnp.exp(s - m)
    l = jnp.maximum(p.sum(axis=1, keepdims=True), 1e-30)
    o = jax.lax.dot_general(
        (p / l).astype(v.dtype), v, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    o_ref[0] = o.astype(o_ref.dtype)


def pallas_decode_attention(q, k, v, positions):
    """Fused single-position decode attention against a KV cache — the
    TPU fast path for ``models.transformer.decode_attention`` (same
    signature: q (B, 1, H, D), k/v (B, S, H, D), positions (B,) int32 →
    (B, 1, H, D); allclose to the exact reference, not bitwise — the
    fused kernel owns its reduction order).

    Grid is (B*H,) with the K/V panels VMEM-resident per program: at
    serving cache lengths (S ≤ a few thousand) a (S, D) panel is far
    under the VMEM budget, and one HBM read of the panel per token is
    the whole cost — exactly the bandwidth term the decode roofline
    bills. Runs in interpret mode off-TPU like every kernel here.
    """
    B, _, H, D = q.shape
    S = k.shape[1]
    scale = 1.0 / np.sqrt(D)
    qb = _to_bh(q)  # (B*H, 1, D)
    kb, vb = _to_bh(k), _to_bh(v)
    valid = jnp.arange(S)[None, :] <= positions[:, None]  # (B, S)
    bias = jnp.where(valid, 0.0, _NEG_INF).astype(jnp.float32)
    bias = jnp.repeat(bias, H, axis=0)[:, None, :]  # (B*H, 1, S)
    out = pl.pallas_call(
        functools.partial(_decode_attn_kernel, scale=scale),
        out_shape=jax.ShapeDtypeStruct((B * H, 1, D), q.dtype),
        grid=(B * H,),
        in_specs=[
            pl.BlockSpec((1, 1, D), lambda i: (i, 0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, S, D), lambda i: (i, 0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, S, D), lambda i: (i, 0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, 1, S), lambda i: (i, 0, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((1, 1, D), lambda i: (i, 0, 0),
                               memory_space=pltpu.VMEM),
        interpret=_interpret(),
    )(qb, kb, vb, bias)
    return _from_bh(out, B, H)


# ---------------------------------------------------------------------------
# Int8 quantization codec
# ---------------------------------------------------------------------------


def _quant_body(x, u, q_ref, scale_ref):
    amax = jnp.max(jnp.abs(x))
    scale = jnp.where(amax > 0, amax / 127.0, 1.0)
    scale_ref[0, 0] = scale
    # stochastic rounding: floor(x/scale + u), u ~ U[0,1)
    q = jnp.floor(x / scale + u)
    q_ref[:] = jnp.clip(q, -127, 127).astype(jnp.int8)


def _quant_kernel_prng(x_ref, seed_ref, q_ref, scale_ref):
    """TPU path: noise from the on-chip PRNG, single VMEM pass."""
    pltpu.prng_seed(seed_ref[0])
    x = x_ref[:].astype(jnp.float32)
    bits = pltpu.bitcast(pltpu.prng_random_bits(x.shape), jnp.uint32)
    # top 24 bits -> [0, 2^24); route the cast through int32 (Mosaic has no
    # direct uint32 -> float32 lowering; the value fits in int32)
    u = pltpu.bitcast(bits >> 8, jnp.int32).astype(jnp.float32) * (
        1.0 / (1 << 24)
    )
    _quant_body(x, u, q_ref, scale_ref)


def _quant_kernel_noise(x_ref, u_ref, q_ref, scale_ref):
    """Interpret/CPU path: pltpu.prng_* has no CPU lowering, so uniform
    noise is generated outside and passed in."""
    _quant_body(x_ref[:].astype(jnp.float32), u_ref[:], q_ref, scale_ref)


def quantize_int8(x: jnp.ndarray, seed) -> tuple:
    """One-pass int8 quantization with stochastic rounding on the TPU PRNG.

    Returns ``(q_int8, scale_f32)`` with ``x ≈ q * scale``. 2-D inputs only
    (flatten first); rows should be lane-aligned for peak throughput.
    """
    if x.ndim != 2:
        raise ValueError(f"quantize_int8 expects 2-D input, got {x.shape}")
    interpret = _interpret()
    if interpret:
        kernel = _quant_kernel_noise
        aux = jax.random.uniform(jax.random.PRNGKey(seed), x.shape)
        aux_spec = pl.BlockSpec(memory_space=pltpu.VMEM)
    else:
        kernel = _quant_kernel_prng
        aux = jnp.asarray([seed], jnp.int32)
        aux_spec = pl.BlockSpec(memory_space=pltpu.SMEM)
    q, scale = pl.pallas_call(
        kernel,
        out_shape=(
            jax.ShapeDtypeStruct(x.shape, jnp.int8),
            jax.ShapeDtypeStruct((1, 1), jnp.float32),
        ),
        in_specs=[pl.BlockSpec(memory_space=pltpu.VMEM), aux_spec],
        out_specs=(
            pl.BlockSpec(memory_space=pltpu.VMEM),
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ),
        interpret=interpret,
    )(x, aux)
    return q, scale[0, 0]


# Elements per grid program in the scaled-quantize kernel: 128k f32 = 512 KB
# of VMEM input + 128 KB int8 output — far under the ~16 MB budget, so any
# leaf size is safe (the grid streams chunks through VMEM).
_QUANT_CHUNK = 131072


def _quant_scaled_kernel_prng(x_ref, seed_ref, scale_ref, q_ref):
    """Fixed-scale variant for the collective path: the scale is a
    cross-replica pmax computed OUTSIDE (quantized ints must be summable
    across replicas), so the kernel only scales + stochastically rounds.
    One grid program per _QUANT_CHUNK chunk; the seed is folded with the
    program id so chunks draw distinct noise."""
    pltpu.prng_seed(seed_ref[0] + pl.program_id(0))
    x = x_ref[:].astype(jnp.float32)
    bits = pltpu.bitcast(pltpu.prng_random_bits(x.shape), jnp.uint32)
    u = pltpu.bitcast(bits >> 8, jnp.int32).astype(jnp.float32) * (
        1.0 / (1 << 24)
    )
    q = jnp.floor(x / scale_ref[0] + u)
    q_ref[:] = jnp.clip(q, -127, 127).astype(jnp.int8)


def _quant_scaled_kernel_noise(x_ref, u_ref, scale_ref, q_ref):
    q = jnp.floor(x_ref[:].astype(jnp.float32) / scale_ref[0] + u_ref[:])
    q_ref[:] = jnp.clip(q, -127, 127).astype(jnp.int8)


def quantize_int8_scaled(x: jnp.ndarray, seed, scale) -> jnp.ndarray:
    """Stochastic int8 rounding with an externally-supplied scale.

    Used on the gradient-compression collective path
    (ops/compression.int8_psum_mean): the scale is the pmax'd |g|max/127 so
    that per-replica int8 payloads are summable. 2-D input, int8 output.
    Arbitrarily large inputs stream through VMEM in _QUANT_CHUNK pieces
    (zero-padded internally; padding quantizes to 0 and is dropped).
    """
    if x.ndim != 2:
        raise ValueError(f"quantize_int8_scaled expects 2-D, got {x.shape}")
    interpret = _interpret()
    scale_arr = jnp.reshape(jnp.asarray(scale, jnp.float32), (1,))
    shape, n = x.shape, x.size
    flat = x.reshape(-1)
    if n <= _QUANT_CHUNK:
        # one block equal to the whole (1, n) array — always a legal tile
        grid_x = flat.reshape(1, -1)
        block = (1, n)
    else:
        # (8, 16384) tiles: sublane dim divisible by 8, lane dim by 128 —
        # Mosaic's tiling rule for blocks smaller than the array
        chunks = -(-n // _QUANT_CHUNK)
        if chunks * _QUANT_CHUNK != n:
            flat = jnp.pad(flat, (0, chunks * _QUANT_CHUNK - n))
        grid_x = flat.reshape(chunks * 8, _QUANT_CHUNK // 8)
        block = (8, _QUANT_CHUNK // 8)
    cols = grid_x.shape[1]
    tile = pl.BlockSpec(block, lambda i: (i, 0), memory_space=pltpu.VMEM)
    if interpret:
        kernel = _quant_scaled_kernel_noise
        if jnp.ndim(seed) == 0 and not isinstance(seed, jax.core.Tracer):
            key = jax.random.PRNGKey(int(seed))
        else:
            key = jax.random.PRNGKey(jnp.asarray(seed, jnp.int32).ravel()[0])
        aux = jax.random.uniform(key, grid_x.shape)
        aux_spec = pl.BlockSpec(block, lambda i: (i, 0),
                                memory_space=pltpu.VMEM)
    else:
        kernel = _quant_scaled_kernel_prng
        aux = jnp.reshape(jnp.asarray(seed, jnp.int32), (1,))
        aux_spec = pl.BlockSpec(memory_space=pltpu.SMEM)
    q = pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct(grid_x.shape, jnp.int8),
        grid=(grid_x.shape[0] // block[0],),
        in_specs=[
            tile,
            aux_spec,
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ],
        out_specs=pl.BlockSpec(block, lambda i: (i, 0),
                               memory_space=pltpu.VMEM),
        interpret=interpret,
    )(grid_x, aux, scale_arr)
    return q.reshape(-1)[:n].reshape(shape)


def _dequant_kernel(q_ref, scale_ref, out_ref):
    out_ref[:] = q_ref[:].astype(jnp.float32) * scale_ref[0, 0]


def dequantize_int8(q: jnp.ndarray, scale) -> jnp.ndarray:
    # same rank contract as quantize_int8: interpret mode on CPU accepts
    # other ranks but Mosaic compilation on real TPU may not
    if q.ndim != 2:
        raise ValueError(f"dequantize_int8 expects 2-D input, got {q.shape}")
    scale_arr = jnp.reshape(jnp.asarray(scale, jnp.float32), (1, 1))
    return pl.pallas_call(
        _dequant_kernel,
        out_shape=jax.ShapeDtypeStruct(q.shape, jnp.float32),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.VMEM),
            pl.BlockSpec((1, 1), memory_space=pltpu.SMEM),
        ],
        out_specs=pl.BlockSpec(memory_space=pltpu.VMEM),
        interpret=_interpret(),
    )(q, scale_arr)


# ---------------------------------------------------------------------------
# Fused LayerNorm (fwd + bwd)
# ---------------------------------------------------------------------------
#
# Round-4 verdict item 4: the BERT-base roofline's ~26 ms bandwidth-bound
# tail is LN / softmax-xent / bias-grad traffic (PERF.md). Stock XLA emits
# LayerNorm as separate reduce + broadcast fusions that read the (N, D)
# activation more than once per direction and — with the parity-default
# ln_dtype=float32 — materialize a full-width copy of it. This kernel does
# each direction in ONE VMEM pass: stats accumulate in f32 regardless of
# input dtype, the normalized output is written directly in the requested
# out_dtype (no separate f32 materialization), and the backward emits dx
# plus the dgamma/dbeta sums in the same sweep. Reference
# counterpart: none — LN itself is torch's ATen (SURVEY.md §2.3); the
# *fusion* is the TPU-side perf mechanism.

_LN_BLOCK_ROWS = 256
# Byte budget for the BACKWARD kernel's per-block f32 working set —
# roughly _LN_WORKING_COPIES copies of the (BN, D) block (x, dy, dx plus
# the xhat/dxhat intermediates). 4 MiB is a quarter of a core's ~16 MiB
# VMEM, leaving headroom for Pallas's double-buffered in/out pipeline
# blocks and whatever else the surrounding fusion keeps live.
_LN_VMEM_BUDGET = 4 << 20
_LN_WORKING_COPIES = 5


def _ln_fwd_kernel(x_ref, g_ref, b_ref, y_ref, mu_ref, rs_ref, *, eps):
    x = x_ref[...].astype(jnp.float32)            # (BN, D)
    mu = jnp.mean(x, axis=1, keepdims=True)
    xc = x - mu
    var = jnp.mean(xc * xc, axis=1, keepdims=True)
    rs = jax.lax.rsqrt(var + eps)
    g = g_ref[...].astype(jnp.float32)            # (1, D)
    b = b_ref[...].astype(jnp.float32)
    y_ref[...] = (xc * rs * g + b).astype(y_ref.dtype)
    mu_ref[...] = mu
    rs_ref[...] = rs


def _ln_bwd_kernel(x_ref, g_ref, mu_ref, rs_ref, dy_ref,
                   dx_ref, dg_ref, db_ref):
    x = x_ref[...].astype(jnp.float32)
    dy = dy_ref[...].astype(jnp.float32)
    gam = g_ref[...].astype(jnp.float32)
    xhat = (x - mu_ref[...]) * rs_ref[...]
    dxhat = dy * gam
    m1 = jnp.mean(dxhat, axis=1, keepdims=True)
    m2 = jnp.mean(dxhat * xhat, axis=1, keepdims=True)
    dx_ref[...] = (rs_ref[...] * (dxhat - m1 - xhat * m2)).astype(dx_ref.dtype)

    # dgamma/dbeta are ONE (1, D) block revisited by every grid step
    # (Mosaic rejects a (1, D) block of a (G, D) partials array: a
    # sublane block of 1 is neither a multiple of 8 nor the extent), so
    # they accumulate across the sequential row-block sweep.
    @pl.when(pl.program_id(0) == 0)
    def _init():
        dg_ref[...] = jnp.zeros_like(dg_ref)
        db_ref[...] = jnp.zeros_like(db_ref)

    dg_ref[...] += jnp.sum(dy * xhat, axis=0, keepdims=True)
    db_ref[...] += jnp.sum(dy, axis=0, keepdims=True)


def _ln_geometry(N, D):
    """(rows_per_block, row_padding), or None if no legal tiling exists.

    Blocks smaller than the array need the lane dim (D) divisible by 128
    (Mosaic's tiling rule — see quantize_int8_scaled); otherwise the only
    legal layout is a single whole-array block. Either way the block's
    row count is derived from _LN_VMEM_BUDGET: the backward kernel keeps
    ~_LN_WORKING_COPIES f32 copies of the (BN, D) block live, so a fixed
    BN=256 at d_model ≳ 1600 used to blow past a core's ~16 MiB of VMEM
    (the round-5 advisor finding); now BN shrinks with D (multiple-of-8
    sublanes), and a D too wide for even an 8-row block falls back to
    the plain-jnp path instead of a Mosaic OOM.
    """
    if N == 0:
        return None  # empty batch: the plain-jnp fallback handles it
    row_bytes = _LN_WORKING_COPIES * D * 4
    if D % 128 == 0:
        fit = (_LN_VMEM_BUDGET // row_bytes) // 8 * 8
        if fit >= 8:
            # when N < fit the single block IS the whole (padded-free)
            # array, which is legal at any row count
            BN = min(_LN_BLOCK_ROWS, fit, N)
            return BN, (-N) % BN
    if N * row_bytes <= _LN_VMEM_BUDGET and N * D * 4 <= (1 << 20):
        return N, 0
    return None


def _ln_fwd_call(x2, gamma, beta, eps, out_dtype):
    N, D = x2.shape
    BN, pad = _ln_geometry(N, D)
    if pad:
        x2 = jnp.pad(x2, ((0, pad), (0, 0)))
    Np = N + pad
    y, mu, rs = pl.pallas_call(
        functools.partial(_ln_fwd_kernel, eps=eps),
        out_shape=(
            jax.ShapeDtypeStruct((Np, D), out_dtype),
            jax.ShapeDtypeStruct((Np, 1), jnp.float32),
            jax.ShapeDtypeStruct((Np, 1), jnp.float32),
        ),
        grid=(Np // BN,),
        in_specs=[
            pl.BlockSpec((BN, D), lambda i: (i, 0)),
            pl.BlockSpec((1, D), lambda i: (0, 0)),
            pl.BlockSpec((1, D), lambda i: (0, 0)),
        ],
        out_specs=(
            pl.BlockSpec((BN, D), lambda i: (i, 0)),
            pl.BlockSpec((BN, 1), lambda i: (i, 0)),
            pl.BlockSpec((BN, 1), lambda i: (i, 0)),
        ),
        interpret=_interpret(),
    )(x2, gamma.reshape(1, -1), beta.reshape(1, -1))
    return y[:N], mu[:N], rs[:N]


def _ln_bwd_call(x2, gamma, mu, rs, dy2, x_dtype):
    N, D = x2.shape
    BN, pad = _ln_geometry(N, D)
    if pad:
        x2 = jnp.pad(x2, ((0, pad), (0, 0)))
        dy2 = jnp.pad(dy2, ((0, pad), (0, 0)))
        mu = jnp.pad(mu, ((0, pad), (0, 0)))
        # padded rows have dy == 0, so every partial they touch is 0
        # regardless of the padded mu/rs values
        rs = jnp.pad(rs, ((0, pad), (0, 0)))
    Np = N + pad
    dx, dg, db = pl.pallas_call(
        _ln_bwd_kernel,
        out_shape=(
            jax.ShapeDtypeStruct((Np, D), x_dtype),
            jax.ShapeDtypeStruct((1, D), jnp.float32),
            jax.ShapeDtypeStruct((1, D), jnp.float32),
        ),
        grid=(Np // BN,),
        in_specs=[
            pl.BlockSpec((BN, D), lambda i: (i, 0)),
            pl.BlockSpec((1, D), lambda i: (0, 0)),
            pl.BlockSpec((BN, 1), lambda i: (i, 0)),
            pl.BlockSpec((BN, 1), lambda i: (i, 0)),
            pl.BlockSpec((BN, D), lambda i: (i, 0)),
        ],
        out_specs=(
            pl.BlockSpec((BN, D), lambda i: (i, 0)),
            pl.BlockSpec((1, D), lambda i: (0, 0)),
            pl.BlockSpec((1, D), lambda i: (0, 0)),
        ),
        # the dg/db accumulators carry state from one row block to the next
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
        ),
        interpret=_interpret(),
    )(x2, gamma.reshape(1, -1), mu, rs, dy2)
    return dx[:N], dg[0], db[0]


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _fused_ln(x2, gamma, beta, eps, out_dtype):
    y, _, _ = _ln_fwd_call(x2, gamma, beta, eps, out_dtype)
    return y


def _fused_ln_fwd(x2, gamma, beta, eps, out_dtype):
    y, mu, rs = _ln_fwd_call(x2, gamma, beta, eps, out_dtype)
    return y, (x2, gamma, mu, rs)


def _fused_ln_bwd(eps, out_dtype, res, dy2):
    x2, gamma, mu, rs = res
    dx, dg, db = _ln_bwd_call(x2, gamma, mu, rs, dy2, x2.dtype)
    return dx, dg.astype(gamma.dtype), db.astype(gamma.dtype)


_fused_ln.defvjp(_fused_ln_fwd, _fused_ln_bwd)


def fused_layer_norm(x, gamma, beta, eps=1e-6, out_dtype=None):
    """One-pass Pallas LayerNorm over the last axis, forward and backward.

    Stats always accumulate in f32 (better than flax's in-dtype stats at
    bf16); ``out_dtype`` (default: x.dtype) is written directly by the
    kernel rather than via a separate f32 materialization. Differentiable
    in x/gamma/beta via custom VJP; falls back to plain jnp (identical
    math) for shapes with no legal Mosaic tiling.
    """
    D = x.shape[-1]
    out_dtype = jnp.dtype(x.dtype if out_dtype is None else out_dtype)
    lead = x.shape[:-1]
    N = int(np.prod(lead)) if lead else 1
    if _ln_geometry(N, D) is None:
        xf = x.astype(jnp.float32)
        mu = jnp.mean(xf, axis=-1, keepdims=True)
        xc = xf - mu
        var = jnp.mean(xc * xc, axis=-1, keepdims=True)
        y = xc * jax.lax.rsqrt(var + eps) * gamma + beta
        return y.astype(out_dtype)
    y = _fused_ln(x.reshape(N, D), gamma, beta, float(eps), out_dtype)
    return y.reshape(*lead, D)


# ---------------------------------------------------------------------------
# Grouped matmul (expert FFN, models/lfm2.py)
# ---------------------------------------------------------------------------
#
# ``x (R, K)`` holds the rows of G groups one after the other, each group
# starting on a row tile (``group_tiles`` lays them out), and ``w (G, K, N)``
# one matrix a group: ``out[r] = x[r] @ w[group of r]``. R is a static bound
# (a rung of ``models/lfm2.ladder``: at most every pair a dropless router
# could send here); the rows really routed
# fill the first ``active`` tiles and the grid steps past them compute
# nothing and fetch nothing (their block indices are clamped to the last
# active tile's), so the device's work follows the rows, not the bound.
# Rows past the last active tile are NOT written: callers read a row only
# through an index that says it is real.

#: rows a tile; a group is padded to a whole number of them
GMM_TILE_M = 256
_GMM_TILE_N = 1024          # preferred columns of a weight block
_GMM_PARAMS = pltpu.CompilerParams(
    dimension_semantics=("parallel", "arbitrary"),
    vmem_limit_bytes=48 * 2 ** 20,
)
_TGMM_PARAMS = pltpu.CompilerParams(
    dimension_semantics=("parallel", "parallel", "arbitrary"),
    vmem_limit_bytes=48 * 2 ** 20,
)
_SUM_PARAMS = pltpu.CompilerParams(
    dimension_semantics=("parallel", "arbitrary", "arbitrary"),
    vmem_limit_bytes=48 * 2 ** 20,
)


def group_tiles(group_sizes, rows: int, tile_m: int = GMM_TILE_M):
    """Where each group's rows go in a buffer of ``rows`` rows.

    ``group_sizes (G,) int32`` -> ``(starts (G,), meta (rows/tile_m + 1,))``:
    group g's rows start at row ``starts[g]`` (a multiple of ``tile_m``) and
    every group owns at least one tile, so that each weight gradient block
    is visited (an empty group's rows are zeros). ``meta[t]`` is the group
    tile t belongs to (the last group for the tiles no group owns) and
    ``meta[-1]`` the number of tiles that are owned: what the kernels
    prefetch. ``rows`` must be at least ``sum(sizes) + G * tile_m``.
    """
    if rows % tile_m:
        raise ValueError(f"rows={rows} is not a multiple of tile_m={tile_m}")
    groups = group_sizes.shape[0]
    tiles = jnp.maximum(1, -(-group_sizes // tile_m)).astype(jnp.int32)
    ends = jnp.cumsum(tiles)
    starts = (ends - tiles) * tile_m
    owner = jnp.searchsorted(
        ends, jnp.arange(rows // tile_m, dtype=jnp.int32), side="right")
    meta = jnp.concatenate(
        [jnp.minimum(owner, groups - 1).astype(jnp.int32), ends[-1:]])
    return starts, meta


def _pick_tile(n: int, prefer: int) -> int:
    """The largest multiple of 128 that divides ``n`` and is <= prefer
    (``n`` itself where it has no such divisor: a block may span a dim)."""
    for t in range(min(prefer, n) // 128 * 128, 0, -128):
        if n % t == 0:
            return t
    return n


def _gmm_kernel(meta_ref, x_ref, w_ref, o_ref, *, transpose_rhs: bool):
    i = pl.program_id(1)

    @pl.when(i < meta_ref[pl.num_programs(1)])
    def _():
        contract = (((1,), (1,)), ((), ())) if transpose_rhs else (
            ((1,), (0,)), ((), ()))
        o_ref[...] = jax.lax.dot_general(
            x_ref[...], w_ref[0], contract,
            preferred_element_type=jnp.float32,
        ).astype(o_ref.dtype)


def _gmm_call(meta, x, w, transpose_rhs: bool, tile_m: int):
    """``x (R, K) @ w[g] (K, N)`` (``w[g] (N, K)`` transposed) -> (R, N).
    Grid (column blocks, row tiles), rows innermost: a weight block stays
    in VMEM while its group's row tiles pass."""
    R, K = x.shape
    N = w.shape[1] if transpose_rhs else w.shape[2]
    tn = _pick_tile(N, _GMM_TILE_N)
    n_tiles = R // tile_m

    def row(i, meta_ref):
        return jnp.minimum(i, meta_ref[n_tiles] - 1)

    if transpose_rhs:
        w_spec = pl.BlockSpec(
            (1, tn, K), lambda j, i, m: (m[row(i, m)], j, 0))
    else:
        w_spec = pl.BlockSpec(
            (1, K, tn), lambda j, i, m: (m[row(i, m)], 0, j))
    return pl.pallas_call(
        functools.partial(_gmm_kernel, transpose_rhs=transpose_rhs),
        out_shape=jax.ShapeDtypeStruct((R, N), x.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(N // tn, n_tiles),
            in_specs=[
                pl.BlockSpec((tile_m, K), lambda j, i, m: (row(i, m), 0)),
                w_spec,
            ],
            out_specs=pl.BlockSpec(
                (tile_m, tn), lambda j, i, m: (row(i, m), j)),
        ),
        compiler_params=_GMM_PARAMS,
        interpret=_interpret(),
    )(meta, x, w)


def _tgmm_kernel(meta_ref, first_ref, x_ref, g_ref, o_ref):
    i = pl.program_id(2)

    @pl.when(first_ref[i] == 1)
    def _():
        o_ref[...] = jnp.zeros_like(o_ref)

    @pl.when(i < meta_ref[pl.num_programs(2)])
    def _():
        o_ref[0] += jax.lax.dot_general(
            x_ref[...], g_ref[...], (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )


def _tgmm_call(meta, x, g, groups: int, tile_m: int):
    """Weight gradients: ``out[g] = x_g^T @ g_g`` over group g's rows,
    (G, K, N) float32. Grid (K blocks, N blocks, row tiles), rows innermost:
    a group's block accumulates in VMEM while its row tiles pass."""
    R, K = x.shape
    N = g.shape[1]
    tk, tn = _pick_tile(K, _GMM_TILE_N), _pick_tile(N, _GMM_TILE_N)
    n_tiles = R // tile_m
    # 1 where a tile is the first of its group: its block starts from zero
    owner = meta[:n_tiles]
    first = jnp.concatenate([
        jnp.ones((1,), jnp.int32),
        (owner[1:] != owner[:-1]).astype(jnp.int32)])

    def row(i, meta_ref):
        return jnp.minimum(i, meta_ref[n_tiles] - 1)

    return pl.pallas_call(
        _tgmm_kernel,
        out_shape=jax.ShapeDtypeStruct((groups, K, N), jnp.float32),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(K // tk, N // tn, n_tiles),
            in_specs=[
                pl.BlockSpec((tile_m, tk),
                             lambda a, b, i, m, f: (row(i, m), a)),
                pl.BlockSpec((tile_m, tn),
                             lambda a, b, i, m, f: (row(i, m), b)),
            ],
            out_specs=pl.BlockSpec(
                (1, tk, tn), lambda a, b, i, m, f: (m[row(i, m)], a, b)),
        ),
        compiler_params=_TGMM_PARAMS,
        interpret=_interpret(),
    )(meta, first, x, g)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _grouped_matmul(x, w, meta, tile_m):
    return _gmm_call(meta, x, w.astype(x.dtype), False, tile_m)


def _grouped_matmul_fwd(x, w, meta, tile_m):
    return _grouped_matmul(x, w, meta, tile_m), (x, w, meta)


def _grouped_matmul_bwd(tile_m, res, g):
    x, w, meta = res
    # dx is the forward kernel on the transposed weight blocks
    dx = _gmm_call(meta, g, w.astype(g.dtype), True, tile_m)
    dw = _tgmm_call(meta, x, g, w.shape[0], tile_m)
    return dx, dw.astype(w.dtype), None


_grouped_matmul.defvjp(_grouped_matmul_fwd, _grouped_matmul_bwd)


def grouped_matmul(x, w, meta, tile_m: int = GMM_TILE_M):
    """``out[r] = x[r] @ w[group of r]`` for rows laid out by
    ``group_tiles`` (``meta`` is its second result). x (R, K) in the
    compute dtype, w (G, K, N) in any float dtype (cast to x's here, so the
    weight gradient comes back in w's), out (R, N) in x's dtype with
    float32 accumulation. Differentiable in x and w. Rows past the last
    owned tile are not written, in the result or in dx."""
    return _grouped_matmul(x, w, meta, tile_m)


# ---------------------------------------------------------------------------
# Row sum (the expert layer's combine and dispatch's transpose)
# ---------------------------------------------------------------------------
#
# ``out[t] = sum over the rows r of token t of weight[r] * values[r]`` for
# rows laid out by ``group_tiles`` with each group's rows in token order
# (``models/lfm2.dispatch``): a scatter-add in XLA, which sorts the indices,
# gathers the rows in that order and adds them one by one. Here a grid step
# takes a block of tokens, a group and a chunk of that group's rows, builds
# the (tokens x rows) matrix that has ``weight[r]`` where row r is token t's
# and multiplies it with the chunk on the matrix unit: within a group the
# rows of a token block are consecutive (token order), at most a block of
# them (a token meets a group once), so a step reads the few chunks they
# lie in and skips the rest as the grouped matmul skips its unowned tiles.
# A float32 weight goes in as three bfloat16 terms, exactly, so the products
# are the float32 ones and only the order of a token's sum is the kernel's.

_SUM_TOKENS = 256           # tokens a block (the block's float32 sum in VMEM)
_SUM_CHUNK = 128            # rows a step


def _sum_rows_kernel(first_ref, count_ref, token_ref, weight_ref, v_ref,
                     o_ref, *, weighted: bool):
    b, g, c = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    tb, chunk = o_ref.shape[0], v_ref.shape[0]

    @pl.when((g == 0) & (c == 0))
    def _():
        o_ref[...] = jnp.zeros_like(o_ref)

    at = b * pl.num_programs(1) + g

    @pl.when(c < count_ref[at])
    def _():
        j = first_ref[at] + c
        token = jnp.broadcast_to(token_ref[pl.ds(j, 1), :], (tb, chunk))
        mine = (token == b * tb + jax.lax.broadcasted_iota(
            jnp.int32, (tb, chunk), 0)).astype(jnp.float32)
        values = v_ref[...]

        def product(onehot):
            return jax.lax.dot_general(
                onehot.astype(values.dtype), values, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)

        if not weighted:
            o_ref[...] += product(mine)
            return
        left = jnp.broadcast_to(weight_ref[pl.ds(j, 1), :], (tb, chunk))
        total = jnp.zeros(o_ref.shape, jnp.float32)
        for _ in range(3):      # 3 x 8 bits: a float32 weight's mantissa
            term = left.astype(jnp.bfloat16).astype(jnp.float32)
            left = left - term
            total += product(mine * term)
        o_ref[...] += total


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7))
def _sum_rows(values, weight, token, real, meta, groups: int, tokens: int,
              tile_m: int):
    rows, d = values.shape
    tb, chunk = math.gcd(tokens, _SUM_TOKENS), math.gcd(tile_m, _SUM_CHUNK)
    blocks, chunks = tokens // tb, -(-tb // chunk) + 1
    # rows sorted by (group, token block), a group's empty rows after its
    # real ones and the unowned tiles (the last group's) after everything:
    # [lo, hi) are the rows of block b in group g
    owner = jnp.repeat(meta[:rows // tile_m], tile_m)
    key = owner * (blocks + 1) + jnp.where(real, token // tb, blocks)
    wanted = (jnp.arange(blocks, dtype=jnp.int32)[:, None]
              + (blocks + 1) * jnp.arange(groups, dtype=jnp.int32)[None, :]
              ).reshape(-1)
    lo = jnp.searchsorted(key, wanted, side="left")
    hi = jnp.searchsorted(key, wanted, side="right")
    first = (lo // chunk).astype(jnp.int32)
    count = jnp.where(hi > lo, (hi - 1) // chunk - first + 1, 0).astype(
        jnp.int32)
    by_chunk = (rows // chunk, chunk)
    weighted = weight is not None

    def rows_of(b, g, c, first_ref, count_ref):
        at = b * groups + g
        last = jnp.maximum(count_ref[at], 1) - 1
        return first_ref[at] + jnp.minimum(c, last), 0

    def whole(b, g, c, first_ref, count_ref):
        return 0, 0

    return pl.pallas_call(
        functools.partial(_sum_rows_kernel, weighted=weighted),
        out_shape=jax.ShapeDtypeStruct((tokens, d), jnp.float32),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(blocks, groups, chunks),
            in_specs=[
                pl.BlockSpec(by_chunk, whole),
                pl.BlockSpec(by_chunk, whole),
                pl.BlockSpec((chunk, d), rows_of),
            ],
            out_specs=pl.BlockSpec(
                (tb, d), lambda b, g, c, first_ref, count_ref: (b, 0)),
        ),
        compiler_params=_SUM_PARAMS,
        interpret=_interpret(),
    )(first, count,
      jnp.where(real, token, -1).reshape(by_chunk),
      (weight if weighted else jnp.zeros((rows,), jnp.float32)).reshape(
          by_chunk),
      values)


def _sum_rows_fwd(values, weight, token, real, meta, groups, tokens, tile_m):
    out = _sum_rows(values, weight, token, real, meta, groups, tokens, tile_m)
    return out, (values, weight, token, real)


def _sum_rows_bwd(groups, tokens, tile_m, res, g):
    values, weight, token, real = res
    mine = jnp.where(real[:, None], jnp.take(g, token, axis=0), 0)
    if weight is None:
        return mine.astype(values.dtype), None, None, None, None
    dots = jnp.sum(mine * values.astype(jnp.float32), axis=-1)
    return ((weight[:, None] * mine).astype(values.dtype),
            jnp.where(real, dots, 0), None, None, None)


_sum_rows.defvjp(_sum_rows_fwd, _sum_rows_bwd)


def sum_rows(values, weight, token, real, meta, groups: int, tokens: int,
             tile_m: int = GMM_TILE_M):
    """``out[t] = sum of weight[r] * values[r] over the rows r with
    token[r] == t and real[r]``, (tokens, d) float32 with float32 products
    and accumulation. values (R, d) in the compute dtype, laid out by
    ``group_tiles`` (``meta`` is its second result, ``groups`` its group
    count) with each group's real rows first and in ascending token order,
    a token at most once a group; weight (R,) float32, or None for ones;
    token (R,) int32; real (R,) bool. A row that is not real may hold
    anything (it was never written): it is matched by no token.
    Differentiable in values and weight."""
    return _sum_rows(values, weight, token, real, meta, groups, tokens, tile_m)

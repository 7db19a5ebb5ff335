"""Mamba-2 state-space-duality scan (SSD, Dao & Gu 2024, section 6) as
Pallas TPU kernels, forward and backward.

The recurrence, per head h with a (P, N) state:

    s_t = exp(dt_t A_h) s_{t-1} + dt_t x_t B_t^T        y_t = s_t C_t

is computed a chunk of Q positions at a time. Within chunk c, with
``a_i = sum_{t <= i} dt_t A_h`` (the decay from the chunk's start, a
float32 cumsum made here before the kernel):

    y_i    = sum_{j <= i} (C_i . B_j) exp(a_i - a_j) dt_j x_j     (intra)
           + exp(a_i) s_prev C_i                                  (carried)
    s_next = exp(a_last) s_prev + sum_j exp(a_last - a_j) dt_j x_j B_j^T

Every decay is the exp of a non-positive difference: ``a`` falls along
the chunk, the masked upper triangle is never exponentiated, and the
factored ``exp(a_i) exp(-a_j)`` (which overflows float32 once a chunk's
cumsum passes -88) is never formed.

Grid ``(batch, head blocks, chunks)``, chunks innermost and sequential:
a head block's states live in VMEM scratch while its chunks pass. A step
computes ``C B^T`` once for the block's heads (one group) and loops over
them. The forward writes the state each chunk starts from (``states``,
float32): the backward's only residual beside the inputs. The backward
sweeps the chunks in reverse, carrying ``dL/ds`` in scratch, and
recomputes what it needs of the forward from those states.

Matrix-unit operands are in ``x``'s dtype (bfloat16 in the benchmark's
cells) with float32 accumulation; the state, the cumsums, the exps and the
gradients of ``dt`` and ``a`` are float32. One group of B and C
(``mamba_n_groups`` 1). The D skip and the gating are the model's.
Interpret mode off the TPU, as ``ops/pallas_kernels``.

Shapes: x (batch, L, H, P), dt (batch, L, H) after softplus, A (H,) < 0,
B and C (batch, L, 1, N) -> y (batch, L, H, P) in x's dtype, and the
largest |s| at a chunk boundary (float32 scalar, not differentiated).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from pytorch_distributed_nn_tpu.ops import pallas_kernels

#: heads a grid step takes (fewer where the head count is not a multiple):
#: they share the step's C B^T and its grid overhead
HEAD_BLOCK = 8
#: masked entries of a chunk's decay exponent; exp() of it is 0
_NEG = -1e30

_PARAMS = pltpu.CompilerParams(
    dimension_semantics=("parallel", "parallel", "arbitrary"),
    vmem_limit_bytes=48 * 2 ** 20,
)

_AB_T = (((1,), (1,)), ((), ()))     # a @ b^T
_AT_B = (((0,), (0,)), ((), ()))     # a^T @ b
_A_B = (((1,), (0,)), ((), ()))      # a @ b


def _dot(a, b, dims):
    return jax.lax.dot_general(a, b, dims,
                               preferred_element_type=jnp.float32)


def _sum_all(v):
    """(1, 1) sum of a 2-D array."""
    return jnp.sum(jnp.sum(v, axis=0, keepdims=True), axis=1, keepdims=True)


def _chunk_terms(a, dt, s):
    """What forward and backward both derive from a chunk's rows ``a`` and
    ``dt`` (1, Q) and ``s`` = C B^T (Q, Q): the decay panel
    ``exp(a_i - a_j)`` on and under the diagonal, the columns ``a_i``
    and ``dt_i`` (Q, 1), and ``a_last`` (1, 1)."""
    q = s.shape[0]
    a_i = jnp.broadcast_to(a, (q, q)).T           # [i, j] = a_i
    tri = (jax.lax.broadcasted_iota(jnp.int32, (q, q), 0)
           >= jax.lax.broadcasted_iota(jnp.int32, (q, q), 1))
    decay = jnp.exp(jnp.where(tri, a_i - a, _NEG))
    dt_col = jnp.broadcast_to(dt, (q, q)).T[:, :1]
    # a masked sum, not a lane slice: Mosaic broadcasts the (1, 1) it
    # gives, not one cut from lane q - 1
    last = jax.lax.broadcasted_iota(jnp.int32, (1, q), 1) == q - 1
    a_last = jnp.sum(jnp.where(last, a, 0.0), axis=1, keepdims=True)
    return decay, a_i[:, :1], dt_col, a_last


def _fwd_kernel(x_ref, dt_ref, a_ref, b_ref, c_ref, y_ref, st_ref, s_scr):
    @pl.when(pl.program_id(2) == 0)
    def _():
        s_scr[...] = jnp.zeros_like(s_scr)

    bm, cm = b_ref[0], c_ref[0]                   # (Q, N)
    cb = _dot(cm, bm, _AB_T)                      # (Q, Q), once a group
    cd = x_ref.dtype

    def head(k, carry):
        x = x_ref[0, k]                           # (Q, P)
        dt, a = dt_ref[0, k], a_ref[0, k]         # (1, Q) float32
        decay, a_col, dt_col, a_last = _chunk_terms(a, dt, cb)
        y = _dot((cb * decay * dt).astype(cd), x, _A_B)
        s = s_scr[k]                              # (P, N) float32
        st_ref[0, k, 0] = s
        y = y + jnp.exp(a_col) * _dot(cm, s.astype(cd), _AB_T)
        y_ref[0, k] = y.astype(y_ref.dtype)
        w = jnp.exp(a_last - a_col) * dt_col      # (Q, 1)
        s_scr[k] = jnp.exp(a_last) * s + _dot(
            (x.astype(jnp.float32) * w).astype(cd), bm, _AT_B)
        return carry

    jax.lax.fori_loop(0, x_ref.shape[1], head, 0)


def _bwd_kernel(x_ref, dt_ref, a_ref, b_ref, c_ref, st_ref, dy_ref,
                dx_ref, ddt_ref, da_ref, db_ref, dc_ref, ds_scr):
    @pl.when(pl.program_id(2) == 0)
    def _():
        ds_scr[...] = jnp.zeros_like(ds_scr)

    bm, cm = b_ref[0], c_ref[0]
    cb = _dot(cm, bm, _AB_T)
    q, cd = cb.shape[0], x_ref.dtype
    lane = jax.lax.broadcasted_iota(jnp.int32, (q, q), 1)
    last = jax.lax.broadcasted_iota(jnp.int32, (1, q), 1) == q - 1

    def head(k, carry):
        db, dc = carry
        x, dy = x_ref[0, k], dy_ref[0, k]         # (Q, P)
        xf, dyf = x.astype(jnp.float32), dy.astype(jnp.float32)
        dt, a = dt_ref[0, k], a_ref[0, k]
        decay, a_col, dt_col, a_last = _chunk_terms(a, dt, cb)
        # intra-chunk: y = M x, M = (C B^T) * decay * dt_j
        masked = cb * decay
        m = masked * dt
        dm = _dot(dy, x, _AB_T)                   # (Q, Q)
        dx = _dot(m.astype(cd), dy, _AT_B)        # (Q, P)
        ddt_row = jnp.sum(dm * masked, axis=0, keepdims=True)
        g = dm * m                                # d decay_ij * decay_ij
        da_row = -jnp.sum(g, axis=0, keepdims=True)
        da_col = jnp.sum(g, axis=1, keepdims=True)
        d_cb = (dm * decay * dt).astype(cd)
        dc = dc + _dot(d_cb, bm, _A_B)
        db = db + _dot(d_cb, cm, _AT_B)
        # the carried state's term: y_i += exp(a_i) s C_i
        s = st_ref[0, k, 0]
        sb = s.astype(cd)
        dy_e = (dyf * jnp.exp(a_col)).astype(cd)
        dc = dc + _dot(dy_e, sb, _A_B)
        da_col = da_col + jnp.sum(
            dyf * jnp.exp(a_col) * _dot(cm, sb, _AB_T), axis=1, keepdims=True)
        # the state update: s' = exp(a_last) s + sum_j w_j x_j B_j^T
        ds = ds_scr[k]
        dsb = ds.astype(cd)
        el = jnp.exp(a_last - a_col)              # (Q, 1)
        w = el * dt_col
        u = _dot(bm, dsb, _AB_T)                  # (Q, P): B ds^T
        dx = dx + w * u
        db = db + w * _dot(x, dsb, _A_B)
        dw = jnp.sum(xf * u, axis=1, keepdims=True)
        da_col = da_col - w * dw
        e_last = jnp.exp(a_last)
        da_last = _sum_all(w * dw) + e_last * _sum_all(ds * s)
        ds_scr[k] = e_last * ds + _dot(dy_e, cm, _AT_B)
        # columns to rows: one transpose of a panel holding both
        rows = jnp.where(lane == 0, da_col,
                         jnp.where(lane == 1, el * dw, 0.0)).T
        dx_ref[0, k] = dx.astype(dx_ref.dtype)
        da_ref[0, k] = da_row + rows[0:1] + jnp.where(last, da_last, 0.0)
        ddt_ref[0, k] = ddt_row + rows[1:2]
        return db, dc

    zero = jnp.zeros(bm.shape, jnp.float32)
    db, dc = jax.lax.fori_loop(0, x_ref.shape[1], head, (zero, zero))
    db_ref[0, 0] = db
    dc_ref[0, 0] = dc


def _head_block(heads: int) -> int:
    return HEAD_BLOCK if heads % HEAD_BLOCK == 0 else heads


def _specs(x, n: int, chunk: int):
    """The grid, the head block and the block specs of (x, dt, a, b, c),
    which both kernels read."""
    batch, heads, length, p = x.shape
    hb = _head_block(heads)
    grid = (batch, heads // hb, length // chunk)
    row = pl.BlockSpec((1, hb, 1, chunk), lambda b, h, c: (b, h, 0, c))
    group = pl.BlockSpec((1, chunk, n), lambda b, h, c: (b, c, 0))
    return grid, hb, [
        pl.BlockSpec((1, hb, chunk, p), lambda b, h, c: (b, h, c, 0)),
        row, row, group, group]


def _rev(spec: pl.BlockSpec, nc: int) -> pl.BlockSpec:
    """``spec`` with its chunk index swept from the last chunk back."""
    index = spec.index_map
    return pl.BlockSpec(spec.block_shape,
                        lambda b, h, c: index(b, h, nc - 1 - c))


def _forward(x, dt, a, b, c, chunk: int):
    """x (batch, H, L, P); dt, a (batch, H, 1, L) float32; b, c (batch, L,
    N) -> (y like x, states (batch, H, L / chunk, P, N) float32)."""
    batch, heads, length, p = x.shape
    n = b.shape[-1]
    grid, hb, specs = _specs(x, n, chunk)
    return pl.pallas_call(
        _fwd_kernel,
        grid=grid,
        in_specs=specs,
        out_specs=[
            specs[0],
            pl.BlockSpec((1, hb, 1, p, n), lambda b, h, c: (b, h, c, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(x.shape, x.dtype),
            jax.ShapeDtypeStruct((batch, heads, grid[2], p, n), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((hb, p, n), jnp.float32)],
        compiler_params=_PARAMS,
        interpret=pallas_kernels._interpret(),
    )(x, dt, a, b, c)


def _backward(x, dt, a, b, c, states, dy, chunk: int):
    batch, heads, length, p = x.shape
    n = b.shape[-1]
    grid, hb, specs = _specs(x, n, chunk)
    nc = grid[2]
    state = pl.BlockSpec((1, hb, 1, p, n), lambda b, h, c: (b, h, c, 0, 0))
    part = pl.BlockSpec((1, 1, chunk, n), lambda b, h, c: (b, h, c, 0))
    ins = [_rev(s, nc) for s in specs + [state, specs[0]]]
    return pl.pallas_call(
        _bwd_kernel,
        grid=grid,
        in_specs=ins,
        out_specs=[ins[0], ins[1], ins[2], _rev(part, nc), _rev(part, nc)],
        out_shape=[
            jax.ShapeDtypeStruct(x.shape, x.dtype),
            jax.ShapeDtypeStruct(dt.shape, jnp.float32),
            jax.ShapeDtypeStruct(a.shape, jnp.float32),
            jax.ShapeDtypeStruct((batch, heads // hb, length, n), jnp.float32),
            jax.ShapeDtypeStruct((batch, heads // hb, length, n), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((hb, p, n), jnp.float32)],
        compiler_params=_PARAMS,
        interpret=pallas_kernels._interpret(),
    )(x, dt, a, b, c, states, dy)


def _ssd_fwd(x, dt, a, b, c, chunk: int):
    y, states = _forward(x, dt, a, b, c, chunk)
    return (y, jnp.max(jnp.abs(states))), (x, dt, a, b, c, states)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5,))
def _ssd(x, dt, a, b, c, chunk: int):
    return _ssd_fwd(x, dt, a, b, c, chunk)[0]


def _ssd_bwd(chunk: int, res, cotangents):
    x, dt, a, b, c, states = res
    dy, _ = cotangents                 # the boundary statistic has none
    dx, ddt, da, db, dc = _backward(x, dt, a, b, c, states, dy, chunk)
    return (dx, ddt, da, db.sum(1).astype(b.dtype),
            dc.sum(1).astype(c.dtype))


_ssd.defvjp(_ssd_fwd, _ssd_bwd)


def ssd_scan(x, dt, A, B, C, chunk: int):
    """The chunked scan; see the module docstring. Returns ``(y, the
    largest |state| at a chunk boundary)``."""
    batch, length, heads = x.shape[:3]
    if B.shape[2] != 1 or C.shape[2] != 1:
        raise ValueError(f"one group of B and C, not {B.shape[2]}")
    if length % chunk:
        raise ValueError(f"length {length} is not a multiple of the chunk "
                         f"{chunk}")
    # the float32 cumsum of dt A within each chunk
    a = jnp.cumsum((dt.astype(jnp.float32) * A).reshape(
        batch, length // chunk, chunk, heads), axis=2).reshape(dt.shape)

    def rows(v):                       # (batch, L, H) -> (batch, H, 1, L)
        return v.astype(jnp.float32).transpose(0, 2, 1)[:, :, None, :]

    y, absmax = _ssd(x.transpose(0, 2, 1, 3), rows(dt), rows(a),
                     B[:, :, 0], C[:, :, 0], chunk)
    return y.transpose(0, 2, 1, 3), absmax

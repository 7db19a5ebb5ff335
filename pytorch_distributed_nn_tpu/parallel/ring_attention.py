"""Sequence/context parallelism: ring attention and Ulysses all-to-all.

The reference has no sequence dimension at all (SURVEY.md §2.2 — CNNs only);
this module is the charter's first-class long-context support. Two standard
TPU-native strategies over a ``seq`` mesh axis:

- **Ring attention** (`ring_attention`): K/V blocks rotate around the ring
  of devices via `lax.ppermute` while each device's Q stays resident; partial
  softmax statistics accumulate flash-attention-style (running max +
  normalizer in f32), so the full L×L score matrix never materializes and
  sequence length scales linearly with the number of devices. ppermute hops
  ride neighbor ICI links — bandwidth-optimal on a torus.
- **Ulysses all-to-all** (`ulysses_attention`): `lax.all_to_all` re-shards
  activations from sequence-sharded to head-sharded, runs dense attention on
  full-length sequences for a subset of heads, and re-shards back. Cheaper
  at moderate L (two all-to-alls instead of S-1 permutes) when
  heads % seq_devices == 0.

Both conform to the model-zoo attention signature
``fn(q, k, v, mask, causal=...)`` with q/k/v ``(B, Lc, H, D)`` (local
sequence chunk) and MUST be called inside `shard_map` with the named axis
present (the SPMD transformer step in training/spmd.py does this; tests use
an 8-device CPU mesh).
"""

from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from pytorch_distributed_nn_tpu.parallel.mesh import SEQ_AXIS

_NEG_INF = -1e30


def _block_update(q, k, v, kv_mask, q_pos, k_pos, causal, o, m, l):
    """One flash-style accumulation step against a K/V block (f32 stats)."""
    D = q.shape[-1]
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32)
    scores = scores / np.sqrt(D)
    if kv_mask is not None:
        scores = jnp.where(kv_mask[:, None, None, :].astype(bool), scores, _NEG_INF)
    if causal:
        allowed = q_pos[:, None] >= k_pos[None, :]
        scores = jnp.where(allowed[None, None], scores, _NEG_INF)
    m_new = jnp.maximum(m, scores.max(axis=-1))
    # guard: rows with everything masked keep m at -inf scale; exp underflows to 0
    corr = jnp.exp(m - m_new)
    p = jnp.exp(scores - m_new[..., None])
    l_new = l * corr + p.sum(axis=-1)
    pv = jnp.einsum("bhqk,bkhd->bqhd", p.astype(q.dtype), v).astype(jnp.float32)
    o_new = o * jnp.transpose(corr, (0, 2, 1))[..., None] + pv
    return o_new, m_new, l_new


def _ring_forward(q, k, v, mask, causal, axis_name):
    """Ring forward pass; returns (out, lse) with lse = m + log l (B,H,Lc)."""
    S = lax.axis_size(axis_name)
    rank = lax.axis_index(axis_name)
    B, Lc, H, D = q.shape
    q_pos = rank * Lc + jnp.arange(Lc)

    o = jnp.zeros((B, Lc, H, D), jnp.float32)
    m = jnp.full((B, H, Lc), _NEG_INF, jnp.float32)
    l = jnp.zeros((B, H, Lc), jnp.float32)
    perm = [(i, (i + 1) % S) for i in range(S)]

    # Block 0 (resident K/V) before the loop; each iteration then rotates
    # first and computes — S-1 rotations total, no dead final permute. The
    # dataflow is identical to rotate-after-compute, so XLA's scheduler can
    # still overlap each permute with the previous block's matmuls.
    o, m, l = _block_update(
        q, k, v, mask, q_pos, rank * Lc + jnp.arange(Lc), causal, o, m, l
    )

    def body(j, carry):
        o, m, l, k, v, kv_mask = carry
        k = lax.ppermute(k, axis_name, perm)
        v = lax.ppermute(v, axis_name, perm)
        if kv_mask is not None:
            kv_mask = lax.ppermute(kv_mask, axis_name, perm)
        src = (rank - j) % S  # origin rank of the block now held
        k_pos = src * Lc + jnp.arange(Lc)
        o, m, l = _block_update(q, k, v, kv_mask, q_pos, k_pos, causal, o, m, l)
        return o, m, l, k, v, kv_mask

    o, m, l, *_ = lax.fori_loop(1, S, body, (o, m, l, k, v, mask))
    out = o / jnp.maximum(jnp.transpose(l, (0, 2, 1)), 1e-30)[..., None]
    # Fully-masked rows keep m=-inf, l=0: lse bottoms out; the backward
    # re-applies the mask with `where`, so the value never reaches a grad.
    lse = m + jnp.log(jnp.maximum(l, 1e-30))
    return out.astype(q.dtype), lse


def _ring_block_grads(q, k, v, g, delta, lse, kv_mask, q_pos, k_pos, causal):
    """Per-(q-chunk, kv-block) gradients from the saved lse residual.

    p is recomputed per block (transient Lc×Lc, never saved), exactly like
    the Pallas flash backward (ops/pallas_kernels._flash_dq_kernel) — the
    ring backward IS the flash backward with blocks arriving over ICI.
    """
    D = q.shape[-1]
    scale = 1.0 / np.sqrt(D)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32) * scale
    keep = jnp.ones(s.shape, bool)
    if kv_mask is not None:
        keep = jnp.logical_and(keep, kv_mask[:, None, None, :].astype(bool))
    if causal:
        keep = jnp.logical_and(keep, (q_pos[:, None] >= k_pos[None, :])[None, None])
    # `where` AFTER exp: fully-masked rows have a meaningless lse and exp
    # may overflow, but every such entry is discarded here (select, not
    # multiply — no inf*0 NaNs).
    p = jnp.where(keep, jnp.exp(s - lse[..., None]), 0.0)  # (B,H,Lq,Lk) f32
    gf = g.astype(jnp.float32)
    dv = jnp.einsum("bhqk,bqhd->bkhd", p, gf)
    dp = jnp.einsum("bqhd,bkhd->bhqk", gf, v.astype(jnp.float32))
    ds = p * (dp - delta[..., None]) * scale
    dq = jnp.einsum("bhqk,bkhd->bqhd", ds, k.astype(jnp.float32))
    dk = jnp.einsum("bhqk,bqhd->bkhd", ds, q.astype(jnp.float32))
    return dq, dk, dv


def _ring_backward(q, k, v, mask, out, lse, g, causal, axis_name):
    """Second ring pass: dq accumulates in place; dk/dv accumulate on the
    rotating (k, v) pair and arrive home after a full loop of S hops.

    Residual memory is O(Lc·D) per device (out + lse + the rotating
    blocks); probabilities are recomputed per hop. This replaces reverse-
    mode autodiff through the forward fori_loop, which saved every hop's
    (B,H,Lc,Lc) probability block — O(S·Lc²) — as scan residuals.
    """
    S = lax.axis_size(axis_name)
    rank = lax.axis_index(axis_name)
    B, Lc, H, D = q.shape
    q_pos = rank * Lc + jnp.arange(Lc)
    perm = [(i, (i + 1) % S) for i in range(S)]
    delta = jnp.einsum(
        "bqhd,bqhd->bhq", g.astype(jnp.float32), out.astype(jnp.float32)
    )  # rowsum(dO ⊙ O): the softmax-VJP rank-1 correction

    def hop(j, k, v, dk, dv, kv_mask):
        src = (rank - j) % S  # origin rank of the block currently held
        k_pos = src * Lc + jnp.arange(Lc)
        dq_b, dk_b, dv_b = _ring_block_grads(
            q, k, v, g, delta, lse, kv_mask, q_pos, k_pos, causal
        )
        return dq_b, dk + dk_b, dv + dv_b

    def body(j, carry):
        dq, k, v, dk, dv, kv_mask = carry
        dq_b, dk, dv = hop(j, k, v, dk, dv, kv_mask)
        # rotate the block AND its accumulated gradient together; after S
        # total hops (S-1 here + 1 final below) both are back at the
        # block's home device.
        k = lax.ppermute(k, axis_name, perm)
        v = lax.ppermute(v, axis_name, perm)
        dk = lax.ppermute(dk, axis_name, perm)
        dv = lax.ppermute(dv, axis_name, perm)
        if kv_mask is not None:
            kv_mask = lax.ppermute(kv_mask, axis_name, perm)
        return dq + dq_b, k, v, dk, dv, kv_mask

    zeros = jnp.zeros((B, Lc, H, D), jnp.float32)
    dq, k, v, dk, dv, mask = lax.fori_loop(
        0, S - 1, body, (zeros, k, v, zeros, zeros, mask)
    )
    # Final hop: compute, then rotate ONLY the gradient accumulators home —
    # the k/v/mask blocks would be discarded, so permuting them is dead ICI
    # traffic.
    dq_b, dk, dv = hop(S - 1, k, v, dk, dv, mask)
    dq = dq + dq_b
    dk = lax.ppermute(dk, axis_name, perm)
    dv = lax.ppermute(dv, axis_name, perm)
    return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype)


_RING_CACHE = {}


def _make_ring(causal: bool, axis_name: str):
    @jax.custom_vjp
    def ring(q, k, v, mask):
        out, _ = _ring_forward(q, k, v, mask, causal, axis_name)
        return out

    def fwd(q, k, v, mask):
        out, lse = _ring_forward(q, k, v, mask, causal, axis_name)
        return out, (q, k, v, mask, out, lse)

    def bwd(res, g):
        q, k, v, mask, out, lse = res
        dq, dk, dv = _ring_backward(
            q, k, v, mask, out, lse, g, causal, axis_name
        )
        dmask = None if mask is None else jnp.zeros_like(mask)
        return dq, dk, dv, dmask

    ring.defvjp(fwd, bwd)
    return ring


def ring_attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    mask: Optional[jnp.ndarray] = None,
    causal: bool = False,
    axis_name: str = SEQ_AXIS,
) -> jnp.ndarray:
    """Blockwise ring attention over the ``axis_name`` mesh axis.

    Inside shard_map each device holds the (B, Lc, H, D) chunk of q/k/v for
    its sequence slice; K/V (and the key-side pad mask) rotate one hop per
    iteration. Output matches `full_attention` on the gathered sequence to
    f32 accumulation tolerance.

    Differentiable with O(Lc·D) residual memory: a custom VJP runs a second
    ring pass (rotating dk/dv accumulators home) instead of reverse-mode
    autodiff through the forward loop — see `_ring_backward`.
    """
    key = (causal, axis_name)
    if key not in _RING_CACHE:
        _RING_CACHE[key] = _make_ring(causal, axis_name)
    return _RING_CACHE[key](q, k, v, mask)


def ulysses_attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    mask: Optional[jnp.ndarray] = None,
    causal: bool = False,
    axis_name: str = SEQ_AXIS,
) -> jnp.ndarray:
    """All-to-all sequence parallelism (Ulysses): seq-sharded → head-sharded.

    Requires num_heads % axis_size == 0. The pad mask must be identical
    across sequence shards is NOT assumed — it is all-gathered (it is (B, Lc),
    tiny next to activations).
    """
    S = lax.axis_size(axis_name)
    B, Lc, H, D = q.shape
    if H % S:
        raise ValueError(f"num_heads={H} not divisible by seq axis size {S}")

    def to_heads(x):  # (B, Lc, H, D) -> (B, S*Lc, H/S, D)
        x = lax.all_to_all(x, axis_name, split_axis=2, concat_axis=1, tiled=True)
        return x

    def to_seq(x):  # inverse
        return lax.all_to_all(x, axis_name, split_axis=1, concat_axis=2, tiled=True)

    from pytorch_distributed_nn_tpu.models.transformer import full_attention

    qg, kg, vg = to_heads(q), to_heads(k), to_heads(v)
    full_mask = None
    if mask is not None:
        full_mask = lax.all_gather(mask, axis_name, axis=1, tiled=True)
    out = full_attention(qg, kg, vg, full_mask, causal=causal)
    return to_seq(out)


def make_seq_attn(impl: str, axis_name: str = SEQ_AXIS):
    """Factory: attention fn for the model zoo. impl: 'ring' | 'ulysses'."""
    if impl == "ring":
        return partial(ring_attention, axis_name=axis_name)
    if impl == "ulysses":
        return partial(ulysses_attention, axis_name=axis_name)
    raise ValueError(f"unknown sequence-parallel attention impl {impl!r}")


def _make_sharded_attn(mesh: Mesh, inner, seq_axis):
    """Shared shard_map wrapper for mesh-sharded attention impls.

    ``seq_axis=SEQ_AXIS`` shards the length dim (the sp wrappers);
    ``seq_axis=None`` keeps the full sequence per shard (tp-only flash).

    Composes with an enclosing manual region: the int8-compressed GSPMD
    step (training/spmd._int8_spmd_step) wraps the model in a shard_map
    manual over "data" only. Inside it the batch dim is already
    per-dp-rank, so this nested shard_map must manualize just the
    (seq,) model axes over the AMBIENT abstract mesh — re-splitting
    "data" would double-shard the batch (and JAX rejects a concrete
    mesh whose axis types disagree with the context).
    """
    from pytorch_distributed_nn_tpu.parallel.mesh import (
        DATA_AXIS,
        MODEL_AXIS,
    )

    def attn_fn(q, k, v, mask=None, causal: bool = False):
        if mask is None:
            mask = jnp.ones(q.shape[:2], jnp.float32)

        ambient = jax.sharding.get_abstract_mesh()
        if DATA_AXIS in ambient.manual_axes:
            qkv_spec = P(None, seq_axis, MODEL_AXIS, None)
            mask_spec = P(None, seq_axis)
            manual = {a for a in (seq_axis, MODEL_AXIS) if a is not None}
            sm_kw = {"mesh": ambient, "axis_names": manual}
        else:
            qkv_spec = P(DATA_AXIS, seq_axis, MODEL_AXIS, None)
            mask_spec = P(DATA_AXIS, seq_axis)
            sm_kw = {"mesh": mesh}

        @partial(
            jax.shard_map,
            in_specs=(qkv_spec, qkv_spec, qkv_spec, mask_spec),
            out_specs=qkv_spec,
            check_vma=False,
            **sm_kw,
        )
        def sharded(q, k, v, m):
            return inner(q, k, v, m, causal=causal)

        return sharded(q, k, v, mask)

    return attn_fn


def make_mesh_attn(mesh: Mesh, impl: str = "ring"):
    """Attention fn for the GSPMD (jit) path: shard_map over the full mesh.

    Returns a model-zoo-compatible ``attn_fn(q, k, v, mask, causal=...)``
    that re-shards q/k/v to (data, seq, model-split heads) and runs ring or
    Ulysses attention over the ``seq`` axis, independently per head shard —
    composing sequence parallelism with tensor parallelism. Call it from
    inside a jitted GSPMD step (training/spmd.py); shard_map-in-jit is the
    supported composition.
    """
    return _make_sharded_attn(mesh, make_seq_attn(impl), SEQ_AXIS)


def make_tp_flash_attn(mesh: Mesh):
    """Head-sharded Pallas flash attention for tp-only meshes (sp=1).

    Round-4 verdict item 5: the framework's best kernel must work on its
    scale-out path. Attention is embarrassingly parallel over heads, so
    under tensor parallelism each model-axis shard simply runs the
    single-device flash kernel on its local head slice — the same
    shard_map-in-jit pattern ``make_mesh_attn`` uses on the seq axis,
    here over (data, model) with the full sequence resident per shard.
    No collectives are needed inside attention itself; GSPMD still
    inserts the tp all-reduces around the projections as usual.

    Returns a model-zoo-compatible ``attn_fn(q, k, v, mask, causal=...)``
    with q/k/v ``(B, L, H, D)``; requires H % tp == 0 (validated by the
    Trainer). Composes with the int8-compressed GSPMD step's enclosing
    manual-over-"data" region the same way ``make_mesh_attn`` does.
    """
    from pytorch_distributed_nn_tpu.ops.pallas_kernels import (
        pallas_attention,
    )

    return _make_sharded_attn(mesh, pallas_attention, None)

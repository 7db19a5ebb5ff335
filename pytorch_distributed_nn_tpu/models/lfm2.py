"""LFM2-MoE decoder (LiquidAI ``lfm2_moe``), TPU-native (flax.linen).

A decoder of two kinds of operator — a gated short convolution and
grouped-query attention with per-head QK-norm and rotary positions — under
RMSNorm pre-norm residual blocks, with a gated (SwiGLU) MLP in the leading
dense layers and a sparse expert layer in the rest: a sigmoid router over
all ``num_experts``, top-k on score + expert bias, weights normalised over
the selected scores.

The expert layer is told which experts it holds (``experts_held`` =
``(first, count)``) beside the published ``num_experts``: it routes over
all of them and computes the part of the result its own experts give — one
chip's share of expert parallelism. What the absent experts would add is
left out and there is no exchange here (an expert axis in ``parallel/`` is
its own piece of work); with ``experts_held = (0, num_experts)`` the layer
is the whole one. It is dropless with static shapes: every (token, held
expert) pair the router selects is computed. The rows move in *row space*
(a gather of the owned rows, a float32 sum of the result rows into their
tokens) through buffers sized by a *rung*: the smallest of a short ladder
of static row counts that covers the tiles this step's routing owns,
chosen on the device, the last rung being the bound (every pair local).
Within a rung the grouped matmul skips the tiles no expert owns
(``ops/pallas_kernels.grouped_matmul``).

Shapes: tokens ``(B, L) int32`` -> logits ``(B, L, vocab) float32``, the
head tied to the embedding. Matmuls and activations run in ``dtype``
(bfloat16); parameters, RMSNorm statistics, softmax and the whole router
(logits, sigmoid, top-k, normalisation) are float32. Training only: the
serving path (``serving/generate/``) needs a cache for the convolution's
state beside the KV cache and a KV-head axis, and ``__call__`` says so.
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp
from flax import linen as nn
from jax.ad_checkpoint import checkpoint_name

from pytorch_distributed_nn_tpu.models.transformer import (
    EMBED,
    HEADS,
    KV,
    MLP,
    VOCAB,
    AttnFn,
    _dense_init,
    _norm_dtype,
    full_attention,
)
from pytorch_distributed_nn_tpu.ops.metrics import COUNTERS
from pytorch_distributed_nn_tpu.ops.pallas_kernels import (
    GMM_TILE_M,
    group_tiles,
    grouped_matmul,
    sum_rows,
)

#: the router's scores, kept for the backward pass under either remat
ROUTER_SCORES = "router_scores"

#: LiquidAI/LFM2-8B-A1B ``layer_types``: attention at layers 2, 6, 10, 14,
#: 18, 21, the gated short convolution everywhere else
_PUBLISHED_LAYERS = tuple(
    "full_attention" if i in (2, 6, 10, 14, 18, 21) else "conv"
    for i in range(24))


@dataclasses.dataclass(frozen=True)
class Lfm2Config:
    """LFM2-8B-A1B as published (config.json), under its own key names."""

    vocab_size: int = 65536
    hidden_size: int = 2048
    intermediate_size: int = 7168          # the leading dense layers' MLP
    moe_intermediate_size: int = 1792      # one expert
    num_attention_heads: int = 32
    num_key_value_heads: int = 8
    head_dim: int = 64
    layer_types: Tuple[str, ...] = _PUBLISHED_LAYERS   # one entry a layer
    num_dense_layers: int = 2
    num_experts: int = 32                  # the router's width, never cut
    num_experts_per_tok: int = 4
    #: (first, count): the experts this chip holds, of ``num_experts``
    experts_held: Tuple[int, int] = (0, 32)
    conv_L_cache: int = 3
    norm_eps: float = 1e-5
    rope_theta: float = 1_000_000.0
    # norm_topk_prob and use_expert_bias are true as published and are not
    # switches here: the router always normalises and always adds the bias
    routed_scaling_factor: float = 1.0
    max_len: int = 128_000                 # rotary: no table, no limit
    dtype: Any = jnp.bfloat16
    # recompute every block in the backward pass, not only the expert
    # layers' part after the routing (which always is: ``held_experts``
    # keeps its inputs and recomputes the chosen rung)
    remat: bool = False
    dropout_rate: float = 0.0              # the family has none

    @property
    def num_hidden_layers(self) -> int:
        return len(self.layer_types)

    # what the trainer reads of a text model's config
    @property
    def num_heads(self) -> int:
        return self.num_attention_heads


def rms_norm(x, scale, eps: float):
    """``x * rsqrt(mean(x^2) + eps) * scale`` in float32 (the caller casts)."""
    x = x.astype(jnp.float32)
    return x * jax.lax.rsqrt(
        jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps) * scale


class RMSNorm(nn.Module):
    eps: float = 1e-5

    @nn.compact
    def __call__(self, x):
        scale = self.param(
            "scale", nn.with_logical_partitioning(
                nn.initializers.ones, (None,)),
            (x.shape[-1],), jnp.float32)
        return rms_norm(x, scale, self.eps)


def rotary(x, theta: float):
    """Rotate-half rotary embedding over the whole head. x (B, L, H, D),
    positions 0..L-1; angles in float32."""
    L, D = x.shape[1], x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, D, 2, dtype=jnp.float32) / D))
    angle = jnp.arange(L, dtype=jnp.float32)[:, None] * inv[None, :]
    cos = jnp.concatenate([jnp.cos(angle)] * 2, axis=-1)[None, :, None, :]
    sin = jnp.concatenate([jnp.sin(angle)] * 2, axis=-1)[None, :, None, :]
    x = x.astype(jnp.float32)
    half = jnp.concatenate([-x[..., D // 2:], x[..., :D // 2]], axis=-1)
    return x * cos + half * sin


def repeat_kv(x, groups: int):
    """(B, L, Hkv, D) -> (B, L, Hkv * groups, D): query head h reads KV
    head h // groups. The attention kernels take one head count, so K and
    V are repeated before the call (same mathematics as a head mapping in
    the kernels' index maps; it costs (groups - 1) x the K/V bytes in HBM,
    100 MB at 2 x 8192 tokens, and autodiff sums dK/dV over each group)."""
    return x if groups == 1 else jnp.repeat(x, groups, axis=2)


def _proj(features, logical, name, dtype, axis=-1):
    return nn.DenseGeneral(
        features, axis=axis, use_bias=False, dtype=dtype,
        kernel_init=nn.with_logical_partitioning(_dense_init(), logical),
        name=name)


class GroupedQueryAttention(nn.Module):
    """Causal attention, ``num_key_value_heads`` KV heads under
    ``num_attention_heads`` query heads, RMSNorm over each head of q and k
    (learned weight each), then rotary; no bias anywhere."""

    config: Lfm2Config
    attn_fn: Optional[AttnFn] = None

    @nn.compact
    def __call__(self, x):
        cfg = self.config
        H, Hkv, D = (cfg.num_attention_heads, cfg.num_key_value_heads,
                     cfg.head_dim)
        q = _proj((H, D), (EMBED, HEADS, KV), "query", cfg.dtype)(x)
        k = _proj((Hkv, D), (EMBED, HEADS, KV), "key", cfg.dtype)(x)
        v = _proj((Hkv, D), (EMBED, HEADS, KV), "value", cfg.dtype)(x)
        q = RMSNorm(cfg.norm_eps, name="q_norm")(q)
        k = RMSNorm(cfg.norm_eps, name="k_norm")(k)
        q = rotary(q, cfg.rope_theta).astype(cfg.dtype)
        k = rotary(k, cfg.rope_theta).astype(cfg.dtype)
        attn = self.attn_fn if self.attn_fn is not None else full_attention
        out = attn(q, repeat_kv(k, H // Hkv), repeat_kv(v, H // Hkv), None,
                   causal=True)
        return _proj(cfg.hidden_size, (HEADS, KV, EMBED), "out", cfg.dtype,
                     axis=(-2, -1))(out)


def short_conv(z, taps):
    """Depthwise causal convolution along the sequence: ``c[t] = sum_j
    taps[j] * z[t - (K-1) + j]``, zeros before the start. z (B, L, C),
    taps (K, C) float32; float32 accumulation, z's dtype out."""
    K, L = taps.shape[0], z.shape[1]
    padded = jnp.pad(z, ((0, 0), (K - 1, 0), (0, 0)))
    out = sum(padded[:, j:j + L].astype(jnp.float32) * taps[j]
              for j in range(K))
    return out.astype(z.dtype)


class GatedShortConv(nn.Module):
    """``[B, C, u] = split3(x W_in)``; ``out = (C * conv(B * u)) W_out``."""

    config: Lfm2Config

    @nn.compact
    def __call__(self, x):
        cfg = self.config
        d = cfg.hidden_size
        bcu = _proj(3 * d, (EMBED, MLP), "in_proj", cfg.dtype)(x)
        b, c, u = jnp.split(bcu, 3, axis=-1)
        taps = self.param(
            "filter", nn.with_logical_partitioning(
                nn.initializers.normal(stddev=0.02), (None, EMBED)),
            (cfg.conv_L_cache, d), jnp.float32)
        with jax.named_scope("conv/short"):
            y = c * short_conv(b * u, taps)
        return _proj(d, (MLP, EMBED), "out_proj", cfg.dtype)(y)


class GatedMLP(nn.Module):
    """``W2(silu(W1 x) * W3 x)``, no bias."""

    config: Lfm2Config

    @nn.compact
    def __call__(self, x):
        cfg = self.config
        f = cfg.intermediate_size
        gate = _proj(f, (EMBED, MLP), "w1", cfg.dtype)(x)
        up = _proj(f, (EMBED, MLP), "w3", cfg.dtype)(x)
        return _proj(cfg.hidden_size, (MLP, EMBED), "w2", cfg.dtype)(
            nn.silu(gate) * up)


def top_k(ranked, values, k: int):
    """``(sel (T, k) int32, values[sel] (T, k))``: the k largest entries of
    ``ranked (T, E)`` by repeated argmax (ties go to the lower index), and
    ``values (T, E)`` at them."""
    picks = []
    for _ in range(k):
        i = jnp.argmax(ranked, axis=-1)
        picks.append(i)
        ranked = jnp.where(
            jax.nn.one_hot(i, values.shape[-1], dtype=bool), -jnp.inf, ranked)
    sel = jnp.stack(picks, axis=-1).astype(jnp.int32)
    # values[sel] as a masked sum: its transpose is elementwise, where a
    # gather's would be a scatter of T x k scalars
    chosen = sel[..., None] == jnp.arange(values.shape[-1], dtype=jnp.int32)
    return sel, jnp.sum(jnp.where(chosen, values[:, None, :], 0.0), axis=-1)


def route(scores, bias, k: int, scaling: float = 1.0):
    """``scores (T, E)`` float32 sigmoid outputs -> ``(sel (T, k) int32,
    weights (T, k) float32)``: the k largest of ``scores + bias`` (the bias
    enters only the selection; ties go to the lower index), weighted by
    their own scores, normalised over the k selected, times ``scaling``."""
    sel, weights = top_k(scores + bias, scores, k)
    weights = weights / (weights.sum(axis=-1, keepdims=True) + 1e-6)
    return sel, weights * scaling


def dispatch(sel, first: int, count: int, tile_rows: int = GMM_TILE_M):
    """Where each (token, held expert) pair goes in the experts' row
    buffer. ``sel (T, k)``: expert ids over all published experts.

    Returns ``(pair (R,), real (R,), dest (T, k), local (T, k), meta,
    counts)``: buffer row r holds pair ``pair[r]`` (its index in the
    flattened (T, k), token ``pair[r] // k``) where ``real[r]``, and nothing
    otherwise; pair (t, j) sits in row ``dest[t, j]`` where ``local[t, j]``;
    ``meta`` is the grouped matmul's tile table and ``counts (count,)`` the
    pairs of each held expert. ``R = T * k + count * tile_rows``: every
    pair local, each expert's rows padded to whole tiles — the dropless
    bound, static. These are index vectors (4 bytes an entry), computed
    once at the bound; the rows themselves are moved at a rung's size
    (``ladder``): the owned rows are a prefix of the buffer (tiles
    ``0 .. meta[-1]``), so a rung reads the first ``rows`` entries of
    ``pair`` and ``real`` and every local pair's ``dest`` lies in it."""
    T, k = sel.shape
    pairs = T * k
    rows = pairs + count * tile_rows
    rows += -rows % tile_rows
    held = sel - first
    local = (held >= 0) & (held < count)
    key = jnp.where(local, held, count).reshape(-1)
    # pairs sorted by held expert (stable: token order within an expert),
    # the pairs of absent experts last
    key_sorted, order = jax.lax.sort_key_val(
        key, jnp.arange(pairs, dtype=jnp.int32))
    counts = jnp.sum(
        key[:, None] == jnp.arange(count, dtype=jnp.int32)[None, :],
        axis=0, dtype=jnp.int32)
    starts, meta = group_tiles(counts, rows, tile_rows)
    packed = jnp.cumsum(counts) - counts      # starts without the padding
    # buffer row -> the sorted pair it holds
    r = jnp.arange(rows, dtype=jnp.int32)
    owner = meta[r // tile_rows]
    offset = r - starts[owner]
    real = (offset < counts[owner]) & (r // tile_rows < meta[-1])
    pair = order[jnp.clip(packed[owner] + offset, 0, pairs - 1)]
    # sorted pair -> its buffer row, then back to (token, j) order
    e = jnp.minimum(key_sorted, count - 1)
    row_sorted = starts[e] + jnp.arange(pairs, dtype=jnp.int32) - packed[e]
    _, dest = jax.lax.sort_key_val(order, row_sorted)
    dest = jnp.where(local, dest.reshape(T, k), 0)
    return pair, real, dest, local, meta, counts


def ladder(pairs: int, count: int, num_experts: int,
           tile_rows: int = GMM_TILE_M) -> Tuple[int, ...]:
    """The static row counts the held experts' buffers may take (the
    *rungs*), ascending, each a whole number of tiles. The first holds
    even routing (``pairs * count / num_experts`` pairs here) with a
    quarter of headroom and a tile an expert, the second twice that share;
    the last is the dropless bound (``dispatch``'s ``R``: every pair
    here), so every routing has a rung. Three at most: each rung is a
    compiled copy of the expert computation a layer."""
    bound = -(-pairs // tile_rows) + count
    even = pairs * count / num_experts
    tiles = sorted({math.ceil(share * even / tile_rows) + count
                    for share in (1.25, 2.5)})
    return tuple(t * tile_rows for t in tiles if t < bound) + (
        bound * tile_rows,)


# Rows move between the (T, d) tokens and a rung's (rows, d) buffer by two
# primitives: G, a gather of ``rows`` rows, and S, a float32 sum of ``rows``
# rows into their tokens (``ops/pallas_kernels.sum_rows``). Each is the
# other's transpose. Autodiff's own transpose of a bfloat16 gather is a
# bfloat16 scatter-add, so G says that its transpose is S in float32, and
# the weights' pick says that its transpose is a gather of scalars through
# the inverse index.

@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5))
def rows_of_tokens(x, token, real, meta, groups: int, tile: int):
    """G: (T, d) tokens -> (rows, d) buffer rows, row r token ``token[r]``
    where ``real[r]`` and zeros elsewhere."""
    return jnp.where(real[:, None], jnp.take(x, token, axis=0), 0)


def _rows_fwd(x, token, real, meta, groups, tile):
    return (rows_of_tokens(x, token, real, meta, groups, tile),
            (token, real, meta, x.shape[0]))


def _rows_bwd(groups, tile, res, g):
    token, real, meta, tokens = res
    dx = sum_rows(g, None, token, real, meta, groups, tokens, tile)
    return dx.astype(g.dtype), None, None, None


rows_of_tokens.defvjp(_rows_fwd, _rows_bwd)


@jax.custom_vjp
def weights_of_rows(weights, pair, real, dest, local):
    """(T, k) float32 weights -> (rows,): row r's pair's weight where
    ``real[r]``, zero elsewhere."""
    return jnp.where(real, jnp.take(weights.reshape(-1), pair), 0)


def _weights_fwd(weights, pair, real, dest, local):
    return weights_of_rows(weights, pair, real, dest, local), (dest, local)


def _weights_bwd(res, g):
    dest, local = res
    # every local pair's row lies in the rung: the rung covers the owned
    # tiles
    return (jnp.where(local, jnp.take(g, dest, mode="clip"), 0),
            None, None, None, None)


weights_of_rows.defvjp(_weights_fwd, _weights_bwd)


#: the gate of an expert's FFN: ``W2(gate(W1 x) * W3 x)``
GATES = {"silu": nn.silu, "relu": nn.relu}


def _rung(rows: int, tile: int, gate, tokens, weights, w13, w2, where):
    """The held experts' computation in a buffer of ``rows`` rows (static:
    a rung that covers the owned tiles). tokens (T, d) in the compute
    dtype, weights (T, k) float32 -> (T, d) float32. Nothing here is sized
    by the dropless bound but ``where``, dispatch's index vectors, of
    which the first ``rows`` entries are read."""
    pair, real, dest, local, meta = where
    k, f = weights.shape[1], w2.shape[1]
    pair, real = pair[:rows], real[:rows]
    meta = jnp.concatenate([meta[:rows // tile], meta[-1:]])
    token, groups = pair // k, w13.shape[0]
    with jax.named_scope("moe/dispatch"):
        x = rows_of_tokens(tokens, token, real, meta, groups, tile)
    with jax.named_scope("moe/experts"):
        h = grouped_matmul(x, w13, meta, tile)
        h = gate(h[:, :f]) * h[:, f:]
        out_rows = grouped_matmul(h, w2, meta, tile)
    with jax.named_scope("moe/combine"):
        # rows past the last owned tile are never written: S reads the
        # tiles that hold a real row and no other
        w_row = weights_of_rows(weights, pair, real, dest, local)
        return sum_rows(out_rows, w_row, token, real, meta, groups,
                        tokens.shape[0], tile)


# Both switches are jitted on their own (and inlined where they are traced
# into a step): every expert layer of a model calls them at the same
# shapes, so the rungs and their kernels are traced once a model, not once
# a layer and pass. ``static = (rungs, tile, gate)``, the gate as the
# function itself: the trace is cached under it.

@functools.partial(jax.jit, static_argnums=(0,), inline=True)
def _switch_forward(static, rung, tokens, weights, w13, w2, where):
    rungs, tile, gate = static
    return jax.lax.switch(
        rung, [functools.partial(_rung, rows, tile, gate) for rows in rungs],
        tokens, weights, w13, w2, where)


@functools.partial(jax.jit, static_argnums=(0,), inline=True)
def _switch_backward(static, rung, g, tokens, weights, w13, w2, where):
    rungs, tile, gate = static

    def backward(rows):
        # the checkpoint keeps the Mosaic calls' names what the trace's
        # readers look for (experts.<n>), recomputed and transposed alike
        body = jax.checkpoint(functools.partial(_rung, rows, tile, gate))

        def branch(g, tokens, weights, w13, w2, where):
            return jax.vjp(lambda *inputs: body(*inputs, where),
                           tokens, weights, w13, w2)[1](g)
        return branch

    return jax.lax.switch(
        rung, [backward(rows) for rows in rungs],
        g, tokens, weights, w13, w2, where)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def held_experts(static, rung, tokens, weights, w13, w2, where):
    """``switch(rung)`` over one ``_rung`` a rung of ``static = (rungs,
    tile, gate)``. Differentiated inside each rung, not through the switch
    (autodiff through it hands every branch every other branch's residuals,
    zero-filled at the dropless bound): the forward pass keeps its inputs
    and nothing else, and the backward pass recomputes the chosen rung."""
    return _switch_forward(static, rung, tokens, weights, w13, w2, where)


def _held_fwd(static, rung, *operands):
    return held_experts(static, rung, *operands), (rung, operands)


def _held_bwd(static, res, g):
    rung, operands = res
    return (None, *_switch_backward(static, rung, g, *operands), None)


held_experts.defvjp(_held_fwd, _held_bwd)


class Experts(nn.Module):
    """The held experts' part of ``y = sum_{e in sel} w_e FFN_e(x)`` for a
    routing already decided: dispatch, then in row space a gather of the
    owned rows, two grouped matmuls (``w13`` = [W1 | W3] side by side, then
    ``w2``) and a weighted float32 sum of the rows into their tokens.
    Dropless: the buffers are sized by the smallest rung of ``ladder`` that
    covers the tiles this step's routing owns, chosen on the device, and
    the last rung is the bound. Shared by every sparse-expert model:
    ``config`` is read for ``experts_held``, ``num_experts``,
    ``hidden_size``, ``moe_intermediate_size`` and ``dtype`` only, and
    ``gate`` names the FFN's gate. Sows, for the step's
    records (summed there over the expert layers): ``moe_pairs`` (pairs
    computed here), ``moe_rows`` (rows the grouped matmul's tiles covered),
    ``moe_rows_bound`` (the chosen rung's rows: what the buffers held),
    ``moe_load_max`` / ``moe_load_mean`` (the fullest held expert's pairs,
    the mean one's) and ``moe_layers`` (1: how many layers were summed)."""

    config: Any
    gate: str = "silu"

    @nn.compact
    def __call__(self, tokens, sel, weights):
        """tokens (T, d) float32, sel (T, k) int32, weights (T, k) float32
        -> (T, d) float32."""
        cfg = self.config
        first, count = cfg.experts_held
        d, f = cfg.hidden_size, cfg.moe_intermediate_size
        w13 = self.param(
            "w13", nn.with_logical_partitioning(
                _dense_init(), (None, EMBED, MLP)),
            (count, d, 2 * f), jnp.float32)
        w2 = self.param(
            "w2", nn.with_logical_partitioning(
                _dense_init(), (None, MLP, EMBED)),
            (count, f, d), jnp.float32)
        tile = GMM_TILE_M
        with jax.named_scope("moe/dispatch"):
            pair, real, dest, local, meta, counts = dispatch(
                sel, first, count, tile)
            rungs = ladder(sel.size, count, cfg.num_experts, tile)
            rung_rows = jnp.array(rungs, jnp.int32)
            rung = jnp.searchsorted(rung_rows, meta[-1] * tile).astype(
                jnp.int32)
        y = held_experts(
            (rungs, tile, GATES[self.gate]), rung, tokens.astype(cfg.dtype),
            weights, w13, w2, (pair, real, dest, local, meta))
        for name, value in (
            ("moe_pairs", counts.sum()),
            ("moe_rows", meta[-1] * tile),
            ("moe_rows_bound", rung_rows[rung]),
            ("moe_load_max", counts.max()),
            ("moe_load_mean", counts.sum() / count),
            ("moe_layers", jnp.ones((), jnp.float32)),
        ):
            self.sow(COUNTERS, name, value.astype(jnp.float32))
        return y


class SparseExperts(nn.Module):
    """Router over all ``num_experts`` + this chip's experts' part of the
    layer (``Experts``). The routing is decided once, in the forward pass,
    and kept for the backward pass (scores, selection and weights: 2 MB a
    layer at 16,384 tokens); what is recomputed there is everything after
    it, the held experts' computation at the chosen rung. Recomputed, the
    decisions do not come out the same: XLA keeps more than bfloat16 inside
    a fusion, the recomputation starts from the rounded residual stream,
    and 0.3-0.5 % of the selections differed on the chip (PERF.md section
    6, PR 34) - the gradient of another function than the forward pass
    computed."""

    config: Lfm2Config

    @nn.compact
    def __call__(self, x):
        """x: (B, L, d) float32 (the block's normed input)."""
        cfg = self.config
        B, L, d = x.shape
        tokens = x.reshape(B * L, d)
        gate = self.param(
            "router", nn.with_logical_partitioning(
                _dense_init(), (EMBED, None)),
            (d, cfg.num_experts), jnp.float32)
        bias = self.param(
            "expert_bias", nn.with_logical_partitioning(
                nn.initializers.normal(stddev=0.01), (None,)),
            (cfg.num_experts,), jnp.float32)
        with jax.named_scope("moe/route"):
            scores = checkpoint_name(jax.nn.sigmoid(jnp.dot(
                tokens, gate, precision=jax.lax.Precision.HIGHEST)),
                ROUTER_SCORES)
            # a buffer, not a weight: no gradient reaches it (the
            # published training moves it by a rule the config does not
            # give; here it keeps its seeded value)
            sel, weights = route(
                scores, jax.lax.stop_gradient(bias),
                cfg.num_experts_per_tok, cfg.routed_scaling_factor)
        experts = Experts if cfg.remat else nn.remat(Experts)
        y = experts(cfg, name="experts")(tokens, sel, weights)
        return y.astype(cfg.dtype).reshape(B, L, d)


class Lfm2Block(nn.Module):
    """``x + operator(norm(x))`` then ``x + ffn(norm(x))``."""

    config: Lfm2Config
    layer: int
    attn_fn: Optional[AttnFn] = None

    @nn.compact
    def __call__(self, x):
        cfg = self.config
        h = RMSNorm(cfg.norm_eps, name="operator_norm")(x).astype(cfg.dtype)
        if cfg.layer_types[self.layer] == "full_attention":
            h = GroupedQueryAttention(cfg, self.attn_fn, name="attn")(h)
        else:
            h = GatedShortConv(cfg, name="conv")(h)
        x = x + h
        h = RMSNorm(cfg.norm_eps, name="ffn_norm")(x)
        if self.layer < cfg.num_dense_layers:
            h = GatedMLP(cfg, name="mlp")(h.astype(cfg.dtype))
        else:
            h = SparseExperts(cfg, name="moe")(h)
        return x + h


class Lfm2MoE(nn.Module):
    """Decoder-only LM. The zoo's call signature
    (``model.apply(vars, tokens, train=...)`` -> float32 logits), so the
    train step, loss and evaluator drive it like every other text model."""

    config: Lfm2Config = Lfm2Config()
    attn_fn: Optional[AttnFn] = None

    @nn.compact
    def __call__(self, tokens, train: bool = False, mask=None, cache=None,
                 positions=None, return_kv: bool = False):
        del train, mask              # no dropout; sequences are full length
        if cache is not None or return_kv:
            raise NotImplementedError(
                "Lfm2MoE trains only: decoding needs a cache for the short "
                "convolution's state beside the KV cache and a KV-head axis "
                "in serving/generate/ (ROADMAP R1, R4)")
        cfg = self.config
        embed = nn.Embed(
            cfg.vocab_size, cfg.hidden_size, dtype=cfg.dtype,
            embedding_init=nn.with_logical_partitioning(
                nn.initializers.normal(stddev=0.02), (VOCAB, EMBED)),
            name="embed")
        x = embed(tokens)
        block = Lfm2Block
        if cfg.remat:
            # every block recomputed, but not its routing (SparseExperts)
            block = nn.remat(Lfm2Block, policy=jax.checkpoint_policies
                .save_only_these_names(ROUTER_SCORES))
        for i in range(cfg.num_hidden_layers):
            x = block(cfg, i, self.attn_fn, name=f"layer_{i}")(x)
        x = RMSNorm(cfg.norm_eps, name="final_norm")(x)
        return embed.attend(x.astype(cfg.dtype)).astype(jnp.float32)


def _build(defaults: dict, attn_fn, kw: dict) -> Lfm2MoE:
    cfg = {**defaults, **_norm_dtype(kw)}
    return Lfm2MoE(Lfm2Config(**cfg), attn_fn=attn_fn)


def lfm2_8b_a1b_ep4(num_classes: int = 0,
                    attn_fn: Optional[AttnFn] = None, **kw) -> Lfm2MoE:
    """LFM2-8B-A1B, one chip's share of four-way expert parallelism: every
    width as published; experts 0-7 of 32 and 16,384 of the 65,536
    vocabulary rows held here; one of the two leading dense layers and one
    whole period of four expert layers (published layers 2-5). 507.8 M
    parameters. The expert layers are recomputed in the backward pass
    (with their buffers kept at the dropless bound, 4 x 16,384 rows, the
    step did not fit: 16.0 GB compiled; 12.9 GB recomputed)."""
    del num_classes
    return _build(dict(
        vocab_size=16384, num_dense_layers=1, experts_held=(0, 8),
        layer_types=("conv", "full_attention", "conv", "conv", "conv"),
        max_len=8192,
    ), attn_fn, kw)


def lfm2_tiny(num_classes: int = 0, attn_fn: Optional[AttnFn] = None,
              **kw) -> Lfm2MoE:
    """The same shape at toy widths for the CPU tests and the benchmark's
    rehearsal: 64 wide, 4 query / 2 KV heads of 16, one dense layer + one
    period, 8 experts of which experts 2-5 are held, top-2."""
    del num_classes
    return _build(dict(
        vocab_size=512, hidden_size=64, intermediate_size=128,
        moe_intermediate_size=128, num_attention_heads=4,
        num_key_value_heads=2, head_dim=16, num_dense_layers=1,
        layer_types=("conv", "full_attention", "conv", "conv", "conv"),
        num_experts=8, num_experts_per_tok=2, experts_held=(2, 4),
        max_len=64, dtype=jnp.float32,
    ), attn_fn, kw)

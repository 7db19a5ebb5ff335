"""Transformer encoder + BERT-style MLM head, TPU-native (flax.linen).

The reference is CNN-only (SURVEY.md §2.2: no attention, no sequence dim);
BASELINE.json's stretch config asks for BERT-base MLM, and the charter makes
long-context / sequence parallelism first-class. This module is therefore
designed mesh-first:

- attention is a pluggable function (``attn_fn``) so the same model runs
  full softmax attention on one chip, **ring attention** over a ``seq`` mesh
  axis (parallel/ring_attention.py), or a fused Pallas kernel on TPU;
- every weight matrix is annotated with logical axes via
  ``nn.with_partitioning`` so tensor parallelism is a partition-rule lookup
  (parallel/partitioning.py), not a model rewrite — Megatron-style column/
  row splits ride XLA's SPMD partitioner over the ``model`` mesh axis;
- matmuls run in bfloat16 for the MXU; softmax/layernorm accumulate f32;
  params stay float32.

Shapes: tokens ``(B, L) int32`` → logits ``(B, L, vocab)``.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np
from flax import linen as nn

# Logical axis names used for parameter partitioning annotations. The
# partition-rule table in parallel/partitioning.py maps these to mesh axes
# ("model" for the TP-split dimension, None for replicated).
EMBED = "embed"      # d_model dimension
HEADS = "heads"      # attention-head dimension (TP-split)
KV = "kv"            # per-head feature dimension
MLP = "mlp"          # ffn hidden dimension (TP-split)
VOCAB = "vocab"      # vocabulary dimension


def _dense_init():
    # BERT's truncated-normal(0.02); fan-in scaling is not used (parity with
    # the original initialization scheme).
    return nn.initializers.normal(stddev=0.02)


@dataclasses.dataclass(frozen=True)
class TransformerConfig:
    """BERT-base defaults (Devlin et al.); shrink for tests via replace()."""

    vocab_size: int = 30522
    max_len: int = 512
    d_model: int = 768
    num_heads: int = 12
    num_layers: int = 12
    d_ff: int = 3072
    dropout_rate: float = 0.1
    dtype: Any = jnp.bfloat16
    causal: bool = False
    tie_embeddings: bool = True
    # One (d_model -> 3*d_model) GEMM for Q/K/V instead of three separate
    # projections: same parameter count and per-element init distribution,
    # 3x fewer (wider) MXU launches and fewer residual-stream relayouts
    # (the round-3 trace's 11.3 ms copy family). Same math — pinned by
    # test_fused_qkv_matches_unfused. Off by default for checkpoint-tree
    # compatibility with earlier rounds.
    fused_qkv: bool = False
    # LayerNorm computation dtype. float32 (default) materializes f32
    # normalized activations that the next matmul casts back down — part
    # of the round-3 trace's bandwidth-bound %convert_reduce family.
    # bfloat16 keeps the elementwise traffic half-width (flax still
    # accumulates mean/var stats in float32 regardless); an opt-in
    # experiment lever, not the parity default.
    ln_dtype: Any = jnp.float32
    # Rematerialize each encoder block on the backward pass: activation
    # memory drops from O(num_layers * L * d_model) to O(L * d_model) at
    # the cost of one extra forward per block — the standard long-context
    # memory lever, composing with flash/ring attention (which already
    # keeps the O(L^2) scores unmaterialized).
    remat: bool = False
    # Pallas one-pass LayerNorm (ops/pallas_kernels.fused_layer_norm):
    # f32 stats in a single VMEM sweep per direction, output written
    # directly in ln_dtype — attacks the roofline's bandwidth-bound LN
    # tail. Same params ("scale"/"bias", f32) as nn.LayerNorm, so
    # checkpoints interchange with the unfused path. Off by default
    # (parity); single-process/dp meshes only (the trainer rejects it
    # under GSPMD tp/sp, where the custom call has no partitioning rule).
    fused_ln: bool = False


def full_attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    mask: Optional[jnp.ndarray],
    causal: bool = False,
    window: Optional[int] = None,
) -> jnp.ndarray:
    """Reference softmax attention. q/k/v: (B, L, H, D) → (B, L, H, D).

    Softmax statistics accumulate in float32 regardless of input dtype
    (bf16-safe); matmuls stay in the input dtype for the MXU. With
    ``causal``, a ``window`` keeps to each query the ``window`` keys that
    end with its own (``ops/pallas_kernels.pallas_attention``'s rule).
    """
    B, Lq, H, D = q.shape
    Lk = k.shape[1]
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32)
    scores = scores / np.sqrt(D)
    if mask is not None:
        # mask: (B, Lk) with 1 = attend, 0 = pad
        scores = jnp.where(mask[:, None, None, :].astype(bool), scores, -1e30)
    if causal:
        idx_q = jnp.arange(Lq)[:, None]
        idx_k = jnp.arange(Lk)[None, :]
        seen = idx_q >= idx_k
        if window is not None:
            seen = seen & (idx_q - idx_k < window)
        scores = jnp.where(seen, scores, -1e30)
    elif window is not None:
        raise ValueError(f"window={window} needs causal=True")
    probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v)


# An attention implementation takes (q, k, v, mask) with q/k/v (B, L, H, D)
# and returns (B, L, H, D). Ring attention conforms to this signature.
AttnFn = Callable[..., jnp.ndarray]


class FusedLayerNorm(nn.Module):
    """Drop-in nn.LayerNorm replacement backed by the Pallas kernel.

    Parameter names/shapes ("scale"/"bias", f32) match nn.LayerNorm so
    checkpoints interchange between the fused and unfused paths. ``dtype``
    is the OUTPUT dtype (stats are always f32 inside the kernel — at
    bf16 that is strictly more precise than flax's in-dtype stats).
    """

    epsilon: float = 1e-6
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x):
        from pytorch_distributed_nn_tpu.ops.pallas_kernels import (
            fused_layer_norm,
        )

        D = x.shape[-1]
        scale = self.param("scale", nn.initializers.ones, (D,), jnp.float32)
        bias = self.param("bias", nn.initializers.zeros, (D,), jnp.float32)
        return fused_layer_norm(x, scale, bias, self.epsilon,
                                out_dtype=self.dtype)


def _layer_norm(cfg: "TransformerConfig", name: str, dtype=None):
    """nn.LayerNorm or its fused Pallas twin, per cfg.fused_ln."""
    dt = cfg.ln_dtype if dtype is None else dtype
    if cfg.fused_ln:
        return FusedLayerNorm(dtype=dt, name=name)
    return nn.LayerNorm(dtype=dt, name=name)


class MultiHeadAttention(nn.Module):
    """Multi-head attention with TP-annotated projections.

    QKV projections are column-parallel over the head axis; the output
    projection is row-parallel — the Megatron split, expressed as logical
    axis annotations that the partitioner maps onto the "model" mesh axis.
    """

    config: TransformerConfig
    attn_fn: Optional[AttnFn] = None

    @nn.compact
    def __call__(self, x, mask, deterministic: bool):
        cfg = self.config
        H, D = cfg.num_heads, cfg.d_model // cfg.num_heads

        def proj(name, logical_out):
            return nn.DenseGeneral(
                (H, D),
                axis=-1,
                dtype=cfg.dtype,
                kernel_init=nn.with_logical_partitioning(
                    _dense_init(), (EMBED,) + logical_out
                ),
                bias_init=nn.with_logical_partitioning(
                    nn.initializers.zeros, logical_out
                ),
                name=name,
            )

        if cfg.fused_qkv:
            qkv = nn.DenseGeneral(
                (3, H, D),
                axis=-1,
                dtype=cfg.dtype,
                kernel_init=nn.with_logical_partitioning(
                    _dense_init(), (EMBED, None, HEADS, KV)
                ),
                bias_init=nn.with_logical_partitioning(
                    nn.initializers.zeros, (None, HEADS, KV)
                ),
                name="qkv",
            )(x)
            q, k, v = qkv[..., 0, :, :], qkv[..., 1, :, :], qkv[..., 2, :, :]
        else:
            q = proj("query", (HEADS, KV))(x)
            k = proj("key", (HEADS, KV))(x)
            v = proj("value", (HEADS, KV))(x)

        attn = self.attn_fn if self.attn_fn is not None else full_attention
        out = attn(q, k, v, mask, causal=cfg.causal)

        out = nn.DenseGeneral(
            cfg.d_model,
            axis=(-2, -1),
            dtype=cfg.dtype,
            kernel_init=nn.with_logical_partitioning(
                _dense_init(), (HEADS, KV, EMBED)
            ),
            bias_init=nn.with_logical_partitioning(nn.initializers.zeros, (EMBED,)),
            name="out",
        )(out)
        out = nn.Dropout(cfg.dropout_rate)(out, deterministic=deterministic)
        return out


class EncoderBlock(nn.Module):
    """Pre-LN transformer block (stabler than BERT's post-LN at bf16)."""

    config: TransformerConfig
    attn_fn: Optional[AttnFn] = None

    @nn.compact
    def __call__(self, x, mask, deterministic: bool):
        cfg = self.config
        h = _layer_norm(cfg, "ln_attn")(x)
        h = MultiHeadAttention(cfg, self.attn_fn, name="attn")(
            h.astype(cfg.dtype), mask, deterministic
        )
        x = x + h

        h = _layer_norm(cfg, "ln_mlp")(x)
        h = nn.Dense(
            cfg.d_ff,
            dtype=cfg.dtype,
            kernel_init=nn.with_logical_partitioning(
                _dense_init(), (EMBED, MLP)
            ),
            bias_init=nn.with_logical_partitioning(nn.initializers.zeros, (MLP,)),
            name="mlp_in",
        )(h.astype(cfg.dtype))
        h = nn.gelu(h)
        h = nn.Dense(
            cfg.d_model,
            dtype=cfg.dtype,
            kernel_init=nn.with_logical_partitioning(
                _dense_init(), (MLP, EMBED)
            ),
            bias_init=nn.with_logical_partitioning(nn.initializers.zeros, (EMBED,)),
            name="mlp_out",
        )(h)
        h = nn.Dropout(cfg.dropout_rate)(h, deterministic=deterministic)
        return x + h


class TransformerEncoder(nn.Module):
    """Token+position embeddings → N pre-LN blocks → final LayerNorm."""

    config: TransformerConfig
    attn_fn: Optional[AttnFn] = None

    @nn.compact
    def __call__(self, tokens, mask=None, *, deterministic: bool = True):
        cfg = self.config
        embed = nn.Embed(
            cfg.vocab_size,
            cfg.d_model,
            dtype=cfg.dtype,
            embedding_init=nn.with_logical_partitioning(
                nn.initializers.normal(stddev=0.02), (VOCAB, EMBED)
            ),
            name="token_embed",
        )
        x = embed(tokens)
        pos = self.param(
            "pos_embed",
            nn.with_logical_partitioning(
                nn.initializers.normal(stddev=0.02), (None, EMBED)
            ),
            (cfg.max_len, cfg.d_model),
            jnp.float32,
        )
        L = tokens.shape[1]
        x = x + jax.lax.dynamic_slice_in_dim(pos, 0, L, axis=0).astype(cfg.dtype)
        x = nn.Dropout(cfg.dropout_rate)(x, deterministic=deterministic)

        block_cls = (
            nn.remat(EncoderBlock, static_argnums=(3,))
            if cfg.remat
            else EncoderBlock
        )
        for i in range(cfg.num_layers):
            x = block_cls(cfg, self.attn_fn, name=f"block_{i}")(
                x, mask, deterministic
            )
        x = _layer_norm(cfg, "ln_final")(x)
        return x, embed


class BertMLM(nn.Module):
    """BERT-style masked-LM: encoder + transform head + vocab projection.

    Call signature matches the CNN zoo (``model.apply(vars, x, train=...)``)
    so the SPMD train step (training/train_step.py) drives CNNs and
    transformers identically: ``x`` is ``(B, L) int32`` tokens, output is
    ``(B, L, vocab) float32`` logits.
    """

    config: TransformerConfig = TransformerConfig()
    attn_fn: Optional[AttnFn] = None

    @nn.compact
    def __call__(self, tokens, train: bool = False, mask=None):
        cfg = self.config
        x, embed = TransformerEncoder(cfg, self.attn_fn, name="encoder")(
            tokens, mask, deterministic=not train
        )
        x = nn.Dense(
            cfg.d_model,
            dtype=cfg.dtype,
            kernel_init=nn.with_logical_partitioning(
                _dense_init(), (None, EMBED)
            ),
            name="mlm_transform",
        )(x.astype(cfg.dtype))
        x = nn.gelu(x)
        x = _layer_norm(cfg, "mlm_ln", dtype=jnp.float32)(x)
        if cfg.tie_embeddings:
            logits = embed.attend(x.astype(cfg.dtype))
        else:
            logits = nn.Dense(
                cfg.vocab_size,
                dtype=cfg.dtype,
                kernel_init=nn.with_logical_partitioning(
                    _dense_init(), (EMBED, VOCAB)
                ),
                name="mlm_out",
            )(x)
        bias = self.param(
            "mlm_bias",
            nn.with_logical_partitioning(nn.initializers.zeros, (VOCAB,)),
            (cfg.vocab_size,),
            jnp.float32,
        )
        return logits.astype(jnp.float32) + bias


# ---------------------------------------------------------------------------
# Causal decoder (generative serving, docs/serving.md "Generative serving")
# ---------------------------------------------------------------------------


def decode_attention(q, k, v, positions):
    """Single-position attention against a KV cache — the exact-math
    decode the KV-cache engine runs by default.

    q: (B, 1, H, D) — the new token's query. k/v: (B, S, H, D) — the
    cache AFTER the new token's K/V were written at ``positions``.
    ``positions``: (B,) int32, the cache index of the new token; keys at
    indices > position are dead (free slots / other requests' stale
    rows) and masked out.

    Exactness trick: the query is BROADCAST over all S rows and routed
    through :func:`full_attention` with the validity mask, then the row
    at ``positions`` is taken. The score and probs@V matmuls therefore
    have the SAME shapes as a full recompute forward at padded length S
    — identical kernel blocking, identical reduction order — which is
    what makes KV-cache decode bitwise-equal to full recompute at every
    generated position (tests/test_generate.py pins this; an Lq=1
    einsum differs from the Lq=S one by an ulp on CPU). The redundant
    rows cost O(S) extra score FLOPs per step — decode stays
    bandwidth-bound on the cache read either way; the single-query
    fast path is :func:`decode_attention_fast` /
    ``ops.pallas_kernels.pallas_decode_attention``.
    """
    B, _, H, D = q.shape
    S = k.shape[1]
    valid = jnp.arange(S)[None, :] <= positions[:, None]  # (B, S)
    qb = jnp.broadcast_to(q, (B, S, H, D))
    out = full_attention(qb, k, v, valid.astype(jnp.int32), causal=False)
    return out[jnp.arange(B), positions][:, None]  # (B, 1, H, D)


def decode_attention_fast(q, k, v, positions):
    """Single-query decode attention (Lq=1 end to end): the cheap path
    for backends where the broadcast trick's extra score rows would
    cost real time. Same math as :func:`decode_attention` up to
    floating-point reduction order (allclose, not bitwise)."""
    S = k.shape[1]
    valid = jnp.arange(S)[None, :] <= positions[:, None]
    return full_attention(q, k, v, valid.astype(jnp.int32), causal=False)


#: decode-mode attention impl: (q(B,1,H,D), k(B,S,H,D), v, positions(B,))
#: -> (B,1,H,D). ``decode_attention`` is the exact reference;
#: ops/pallas_kernels.pallas_decode_attention is the fused TPU fast path.
DecodeAttnFn = Callable[..., jnp.ndarray]


class CausalSelfAttention(nn.Module):
    """Multi-head CAUSAL self-attention with an explicit-KV decode mode.

    Same TP-annotated projections (and parameter names) as
    :class:`MultiHeadAttention`, so the partition-rule table applies
    unchanged. Two call modes:

    - full (``cache=None``): causal attention over the whole sequence;
      returns ``(out, (k, v))`` with k/v ``(B, L, H, D)`` — the prefill
      path hands these to the engine's KV-cache pools.
    - decode (``cache=(k_cache, v_cache)``, ``positions`` (B,) int32):
      ``x`` is the single new token ``(B, 1, d_model)``; its K/V are
      written into the cache at ``positions`` and attention runs against
      the updated cache. Returns ``(out, (k_cache', v_cache'))``. The
      cache rides OUTSIDE the module as a plain operand — no flax
      mutable collections, so the jitted decode step stays a pure
      function of (params, cache, tokens, positions) and the PR-7
      zero-retrace contract extends to it unchanged.
    """

    config: TransformerConfig
    attn_fn: Optional[AttnFn] = None
    decode_attn_fn: Optional[DecodeAttnFn] = None

    @nn.compact
    def __call__(self, x, mask, deterministic: bool, cache=None,
                 positions=None):
        cfg = self.config
        H, D = cfg.num_heads, cfg.d_model // cfg.num_heads

        def proj(name, logical_out):
            return nn.DenseGeneral(
                (H, D),
                axis=-1,
                dtype=cfg.dtype,
                kernel_init=nn.with_logical_partitioning(
                    _dense_init(), (EMBED,) + logical_out
                ),
                bias_init=nn.with_logical_partitioning(
                    nn.initializers.zeros, logical_out
                ),
                name=name,
            )

        q = proj("query", (HEADS, KV))(x)
        k = proj("key", (HEADS, KV))(x)
        v = proj("value", (HEADS, KV))(x)

        if cache is None:
            attn = self.attn_fn if self.attn_fn is not None \
                else full_attention
            out = attn(q, k, v, mask, causal=True)
            new_kv = (k, v)
        else:
            k_cache, v_cache = cache  # (B, S, H, D)
            rows = jnp.arange(k_cache.shape[0])
            k_cache = k_cache.at[rows, positions].set(
                k[:, 0].astype(k_cache.dtype)
            )
            v_cache = v_cache.at[rows, positions].set(
                v[:, 0].astype(v_cache.dtype)
            )
            dec = self.decode_attn_fn if self.decode_attn_fn is not None \
                else decode_attention
            out = dec(q, k_cache.astype(q.dtype),
                      v_cache.astype(q.dtype), positions)
            new_kv = (k_cache, v_cache)

        out = nn.DenseGeneral(
            cfg.d_model,
            axis=(-2, -1),
            dtype=cfg.dtype,
            kernel_init=nn.with_logical_partitioning(
                _dense_init(), (HEADS, KV, EMBED)
            ),
            bias_init=nn.with_logical_partitioning(
                nn.initializers.zeros, (EMBED,)
            ),
            name="out",
        )(out)
        out = nn.Dropout(cfg.dropout_rate)(out, deterministic=deterministic)
        return out, new_kv


class DecoderBlock(nn.Module):
    """Pre-LN causal block: :class:`EncoderBlock` with KV threading."""

    config: TransformerConfig
    attn_fn: Optional[AttnFn] = None
    decode_attn_fn: Optional[DecodeAttnFn] = None

    @nn.compact
    def __call__(self, x, mask, deterministic: bool, cache=None,
                 positions=None):
        cfg = self.config
        h = _layer_norm(cfg, "ln_attn")(x)
        h, new_kv = CausalSelfAttention(
            cfg, self.attn_fn, self.decode_attn_fn, name="attn"
        )(h.astype(cfg.dtype), mask, deterministic, cache=cache,
          positions=positions)
        x = x + h

        h = _layer_norm(cfg, "ln_mlp")(x)
        h = nn.Dense(
            cfg.d_ff,
            dtype=cfg.dtype,
            kernel_init=nn.with_logical_partitioning(
                _dense_init(), (EMBED, MLP)
            ),
            bias_init=nn.with_logical_partitioning(
                nn.initializers.zeros, (MLP,)
            ),
            name="mlp_in",
        )(h.astype(cfg.dtype))
        h = nn.gelu(h)
        h = nn.Dense(
            cfg.d_model,
            dtype=cfg.dtype,
            kernel_init=nn.with_logical_partitioning(
                _dense_init(), (MLP, EMBED)
            ),
            bias_init=nn.with_logical_partitioning(
                nn.initializers.zeros, (EMBED,)
            ),
            name="mlp_out",
        )(h)
        h = nn.Dropout(cfg.dropout_rate)(h, deterministic=deterministic)
        return x + h, new_kv


class CausalLM(nn.Module):
    """GPT-style decoder-only LM over the repo's transformer blocks.

    Full mode matches the zoo call signature
    (``model.apply(vars, tokens, train=...)`` → ``(B, L, vocab)`` f32
    logits) so the train step, evaluator, exporter and shardlint drive
    it like every other model. Two extra modes feed the generative
    serving engine (serving/generate/):

    - ``return_kv=True``: the PREFILL call — also returns the per-layer
      ``((k, v), ...)`` projections for the engine's cache pools.
    - ``cache=((k, v), ...)`` + ``positions``: the DECODE call — tokens
      is ``(B, 1)`` (one new token per row), K/V are written into the
      cache at each row's position, and the return is
      ``(next_logits (B, vocab), new_cache)``.

    Per-token math (embedding, LayerNorm, MLP, head) is position-local
    and attention's decode mode reuses the full path's score/softmax
    code, so decode logits are bitwise-equal to a full recompute at the
    same padded length.
    """

    config: TransformerConfig = TransformerConfig(causal=True)
    attn_fn: Optional[AttnFn] = None
    decode_attn_fn: Optional[DecodeAttnFn] = None

    @nn.compact
    def __call__(self, tokens, train: bool = False, mask=None, cache=None,
                 positions=None, return_kv: bool = False):
        cfg = self.config
        decode = cache is not None
        embed = nn.Embed(
            cfg.vocab_size,
            cfg.d_model,
            dtype=cfg.dtype,
            embedding_init=nn.with_logical_partitioning(
                nn.initializers.normal(stddev=0.02), (VOCAB, EMBED)
            ),
            name="token_embed",
        )
        x = embed(tokens)
        pos = self.param(
            "pos_embed",
            nn.with_logical_partitioning(
                nn.initializers.normal(stddev=0.02), (None, EMBED)
            ),
            (cfg.max_len, cfg.d_model),
            jnp.float32,
        )
        if decode:
            # one new token per row at its own absolute position
            x = x + jnp.take(pos, positions, axis=0)[:, None].astype(
                cfg.dtype
            )
        else:
            L = tokens.shape[1]
            x = x + jax.lax.dynamic_slice_in_dim(pos, 0, L, axis=0).astype(
                cfg.dtype
            )
        x = nn.Dropout(cfg.dropout_rate)(x, deterministic=not train)

        kvs = []
        for i in range(cfg.num_layers):
            x, kv = DecoderBlock(
                cfg, self.attn_fn, self.decode_attn_fn, name=f"block_{i}"
            )(x, mask, not train, cache=cache[i] if decode else None,
              positions=positions)
            kvs.append(kv)
        x = _layer_norm(cfg, "ln_final")(x)
        logits = embed.attend(x.astype(cfg.dtype))
        bias = self.param(
            "lm_bias",
            nn.with_logical_partitioning(nn.initializers.zeros, (VOCAB,)),
            (cfg.vocab_size,),
            jnp.float32,
        )
        logits = logits.astype(jnp.float32) + bias
        if decode:
            return logits[:, 0], tuple(kvs)
        if return_kv:
            return logits, tuple(kvs)
        return logits


def _norm_dtype(kw: dict) -> dict:
    """model_kw dicts ride in JSON manifests, so dtype may arrive as a
    string name ("float32"/"bfloat16"); normalize to the jnp dtype."""
    for key in ("dtype", "ln_dtype"):
        v = kw.get(key)
        if isinstance(v, str):
            kw[key] = jnp.dtype(v).type if v != "bfloat16" else jnp.bfloat16
    return kw


def gpt_tiny(
    num_classes: int = 0, attn_fn: Optional[AttnFn] = None,
    decode_attn_fn: Optional[DecodeAttnFn] = None, **kw
) -> CausalLM:
    """2-layer/64-wide causal decoder for tests, smoke and CPU serving.

    float32 by default: the generative smoke/chaos gates pin KV-cache
    decode bitwise-equal to full recompute, and f32 keeps that exact on
    every backend (bf16 is the opt-in perf lever, as everywhere else).
    """
    del num_classes
    cfg = dict(
        vocab_size=256, max_len=64, d_model=64, num_heads=4, num_layers=2,
        d_ff=256, dtype=jnp.float32, causal=True,
    )
    cfg.update(_norm_dtype(kw))
    return CausalLM(TransformerConfig(**cfg), attn_fn=attn_fn,
                    decode_attn_fn=decode_attn_fn)


def gpt_mini(
    num_classes: int = 0, attn_fn: Optional[AttnFn] = None,
    decode_attn_fn: Optional[DecodeAttnFn] = None, **kw
) -> CausalLM:
    """bert_tiny-sized decoder (4 layers / 128 wide, 1k vocab)."""
    del num_classes
    cfg = dict(
        vocab_size=1024, max_len=128, d_model=128, num_heads=4,
        num_layers=4, d_ff=512, dtype=jnp.float32, causal=True,
    )
    cfg.update(_norm_dtype(kw))
    return CausalLM(TransformerConfig(**cfg), attn_fn=attn_fn,
                    decode_attn_fn=decode_attn_fn)


def bert_base(
    num_classes: int = 0, attn_fn: Optional[AttnFn] = None, **kw
) -> BertMLM:
    """BERT-base MLM (110M params). num_classes ignored (vocab-sized output)."""
    del num_classes
    cfg = TransformerConfig(**kw) if kw else TransformerConfig()
    return BertMLM(cfg, attn_fn=attn_fn)


def bert_tiny(
    num_classes: int = 0, attn_fn: Optional[AttnFn] = None, **kw
) -> BertMLM:
    """4-layer/128-wide variant for tests and CPU smoke runs."""
    del num_classes
    cfg = dict(
        vocab_size=1024, max_len=128, d_model=128, num_heads=4,
        num_layers=4, d_ff=512,
    )
    cfg.update(kw)
    return BertMLM(TransformerConfig(**cfg), attn_fn=attn_fn)

"""Granite-4.0-H hybrid decoder (ibm-granite ``granitemoehybrid``, dense),
TPU-native (flax.linen).

Two kinds of mixer under RMSNorm pre-norm residual blocks, told by the
source's ``layer_types``: a Mamba-2 layer (``mamba``) and grouped-query
attention without positions (``attention``, NoPE). Every layer has the
same SiLU-gated MLP after its mixer (the source's ``shared_mlp``: no
routed experts in the dense models). Granite's muP multipliers: the
embedding times ``embedding_multiplier``, each residual branch times
``residual_multiplier``, attention's softmax at scale
``attention_multiplier`` (1/64, not 1/sqrt(64)), the logits divided by
``logits_scaling``; the head is the embedding (tied).

Layer i:  h = x + r * Mixer_i(RMSNorm(x));  x' = h + r * MLP(RMSNorm(h)).

The Mamba-2 mixer (Dao & Gu 2024; the source's ``GraniteMoeHybridMambaLayer``):

    [z | xBC | dt] = x W_in           widths d_in / d_in + 2 N / H, no bias
    xBC = silu(conv_K(xBC) + b)       depthwise, causal
    [x | B | C] = split(xBC)          d_in = H P, then N and N (one group)
    dt = softplus(dt + dt_bias);  A = -exp(A_log)
    y = ssd(x, dt, A, B, C) + D x     the state-space scan, ops/ssd.py
    out = RMSNorm(y * silu(z)) W_out  gated before the norm, over all d_in

What is this model's is here: its config, the Mamba-2 mixer, its
attention, its block and its presets. RMSNorm, the gated MLP, the
depthwise causal convolution and the KV-head repeat are
``models/lfm2.py``'s; the scan is ``ops/ssd.py``'s chunked kernel.

Counters in every step record (``ops/metrics.COUNTERS``): ``ssd_calls``
(Mamba layers a forward pass runs), ``ssd_chunks`` (sequence chunks they
scan) and ``ssd_state_absmax``, the largest |state| at a chunk boundary
over the Mamba layers, read from the boundary states the scan writes.

Shapes: tokens ``(B, L) int32`` -> logits ``(B, L, vocab) float32``.
Matmuls and activations run in ``dtype`` (bfloat16); parameters, RMSNorm
statistics, softmax and the scan's state, cumsums and decays are float32.
Training only: the serving path has no cache for a recurrent state, and
``__call__`` says so.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp
from flax import linen as nn

from pytorch_distributed_nn_tpu.models.lfm2 import (
    GatedMLP,
    RMSNorm,
    _proj,
    repeat_kv,
    rms_norm,
    short_conv,
)
from pytorch_distributed_nn_tpu.models.transformer import (
    EMBED,
    HEADS,
    KV,
    MLP,
    VOCAB,
    AttnFn,
    _norm_dtype,
    full_attention,
)
from pytorch_distributed_nn_tpu.ops.metrics import COUNTERS
from pytorch_distributed_nn_tpu.ops.ssd import ssd_scan

#: ibm-granite/granite-4.0-h-micro ``layer_types``: attention at layers 5,
#: 15, 25, 35, Mamba-2 everywhere else
_PUBLISHED_LAYERS = tuple(
    "attention" if i % 10 == 5 else "mamba" for i in range(40))


@dataclasses.dataclass(frozen=True)
class Granite4HybridConfig:
    """Granite-4.0-H-Micro as published (config.json), under its own key
    names."""

    vocab_size: int = 100352
    hidden_size: int = 2048
    intermediate_size: int = 8192       # shared_intermediate_size: every MLP
    num_attention_heads: int = 32
    num_key_value_heads: int = 8
    layer_types: Tuple[str, ...] = _PUBLISHED_LAYERS   # one entry a layer
    mamba_n_heads: int = 64
    mamba_d_head: int = 64
    mamba_d_state: int = 128
    mamba_d_conv: int = 4
    mamba_n_groups: int = 1
    mamba_chunk_size: int = 256
    embedding_multiplier: float = 12.0
    residual_multiplier: float = 0.22
    logits_scaling: float = 8.0
    attention_multiplier: float = 1 / 64
    rms_norm_eps: float = 1e-5
    # mamba_expand (2) is mamba_n_heads x mamba_d_head / hidden_size;
    # position_embedding_type nope, tie_word_embeddings true and no bias
    # anywhere but the convolution's are as published and not switches
    max_len: int = 131072               # no positions: no table, no limit
    dtype: Any = jnp.bfloat16
    remat: bool = False                 # recompute every block backward
    dropout_rate: float = 0.0           # the family has none

    @property
    def num_hidden_layers(self) -> int:
        return len(self.layer_types)

    # what the trainer reads of a text model's config
    @property
    def num_heads(self) -> int:
        return self.num_attention_heads

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_attention_heads


def _uniform(low: float, high: float):
    def init(key, shape, dtype=jnp.float32):
        return jax.random.uniform(key, shape, dtype, low, high)

    return init


def _a_log_init(key, shape, dtype=jnp.float32):
    """log of A ~ U[1, 16] a head (state-spaces/mamba ``Mamba2``)."""
    return jnp.log(jax.random.uniform(key, shape, dtype, 1.0, 16.0))


def _dt_bias_init(key, shape, dtype=jnp.float32):
    """softplus^-1 of dt ~ log-uniform [1e-3, 1e-1] a head, dt >= 1e-4
    (state-spaces/mamba ``Mamba2``)."""
    dt = jnp.exp(jax.random.uniform(
        key, shape, dtype, math.log(1e-3), math.log(1e-1)))
    dt = jnp.maximum(dt, 1e-4)
    return dt + jnp.log(-jnp.expm1(-dt))


class GatedRMSNorm(nn.Module):
    """``RMSNorm(y * silu(z))`` over the whole width (one group), the gate
    before the norm (the source's ``norm_before_gate`` false); float32."""

    eps: float = 1e-5

    @nn.compact
    def __call__(self, y, z):
        scale = self.param("scale", nn.initializers.ones, (y.shape[-1],),
                           jnp.float32)
        return rms_norm(
            y.astype(jnp.float32) * nn.silu(z.astype(jnp.float32)), scale,
            self.eps)


class Mamba2Mixer(nn.Module):
    """The Mamba-2 mixer (module docstring). Returns ``(out, the largest
    |state| at a chunk boundary)``."""

    config: Granite4HybridConfig

    @nn.compact
    def __call__(self, x):
        cfg = self.config
        H, P, N = cfg.mamba_n_heads, cfg.mamba_d_head, cfg.mamba_d_state
        batch, length, _ = x.shape
        d_in = H * P
        conv_dim = d_in + 2 * cfg.mamba_n_groups * N
        zxbcdt = _proj(d_in + conv_dim + H, (EMBED, MLP), "in_proj",
                       cfg.dtype)(x)
        z, xbc, dt = jnp.split(zxbcdt, [d_in, d_in + conv_dim], axis=-1)
        bound = 1 / math.sqrt(cfg.mamba_d_conv)   # torch Conv1d's default
        taps = self.param("conv_weight", _uniform(-bound, bound),
                          (cfg.mamba_d_conv, conv_dim), jnp.float32)
        bias = self.param("conv_bias", _uniform(-bound, bound), (conv_dim,),
                          jnp.float32)
        with jax.named_scope("conv"):
            xbc = nn.silu(short_conv(xbc, taps).astype(jnp.float32)
                          + bias).astype(cfg.dtype)
        xs, b, c = jnp.split(xbc, [d_in, d_in + N], axis=-1)
        xs = xs.reshape(batch, length, H, P)
        dt = jax.nn.softplus(dt.astype(jnp.float32) + self.param(
            "dt_bias", _dt_bias_init, (H,), jnp.float32))
        a = -jnp.exp(self.param("A_log", _a_log_init, (H,), jnp.float32))
        d = self.param("D", nn.initializers.ones, (H,), jnp.float32)
        # the innermost scope names the Mosaic calls in a trace (ssd.<n>)
        with jax.named_scope("ssd"):
            y, absmax = ssd_scan(xs, dt, a, b[:, :, None], c[:, :, None],
                                 cfg.mamba_chunk_size)
        y = y.astype(jnp.float32) + d[:, None] * xs.astype(jnp.float32)
        with jax.named_scope("gate_norm"):
            y = GatedRMSNorm(cfg.rms_norm_eps, name="norm")(
                y.reshape(batch, length, d_in), z)
        return _proj(cfg.hidden_size, (MLP, EMBED), "out_proj", cfg.dtype)(
            y.astype(cfg.dtype)), absmax


class NoPEAttention(nn.Module):
    """Causal grouped-query attention with no positions and no bias,
    softmax at scale ``attention_multiplier``: q is scaled by
    ``attention_multiplier * sqrt(head_dim)`` (1/8 here, exact in
    bfloat16) before an attention that divides by sqrt(head_dim)."""

    config: Granite4HybridConfig
    attn_fn: Optional[AttnFn] = None

    @nn.compact
    def __call__(self, x):
        cfg = self.config
        H, Hkv, D = (cfg.num_attention_heads, cfg.num_key_value_heads,
                     cfg.head_dim)
        q = _proj((H, D), (EMBED, HEADS, KV), "q_proj", cfg.dtype)(x)
        k = _proj((Hkv, D), (EMBED, HEADS, KV), "k_proj", cfg.dtype)(x)
        v = _proj((Hkv, D), (EMBED, HEADS, KV), "v_proj", cfg.dtype)(x)
        q = q * jnp.asarray(cfg.attention_multiplier * math.sqrt(D), q.dtype)
        attn = self.attn_fn if self.attn_fn is not None else full_attention
        out = attn(q, repeat_kv(k, H // Hkv), repeat_kv(v, H // Hkv), None,
                   causal=True)
        return _proj(cfg.hidden_size, (HEADS, KV, EMBED), "o_proj", cfg.dtype,
                     axis=(-2, -1))(out)


class Granite4Block(nn.Module):
    """One layer (module docstring). Returns ``(x', the mixer's largest
    boundary state, 0 for attention)``."""

    config: Granite4HybridConfig
    kind: str
    attn_fn: Optional[AttnFn] = None

    @nn.compact
    def __call__(self, x):
        cfg = self.config
        r = cfg.residual_multiplier
        h = RMSNorm(cfg.rms_norm_eps, name="input_layernorm")(x)
        if self.kind == "mamba":
            h, absmax = Mamba2Mixer(cfg, name="mamba")(h.astype(cfg.dtype))
        else:
            h = NoPEAttention(cfg, self.attn_fn, name="self_attn")(
                h.astype(cfg.dtype))
            absmax = jnp.zeros((), jnp.float32)
        x = x + r * h
        h = RMSNorm(cfg.rms_norm_eps, name="post_attention_layernorm")(x)
        return x + r * GatedMLP(cfg, name="shared_mlp")(
            h.astype(cfg.dtype)), absmax


class Granite4Hybrid(nn.Module):
    """Decoder-only LM. The zoo's call signature (``model.apply(vars,
    tokens, train=...)`` -> float32 logits)."""

    config: Granite4HybridConfig = Granite4HybridConfig()
    attn_fn: Optional[AttnFn] = None

    @nn.compact
    def __call__(self, tokens, train: bool = False, mask=None, cache=None,
                 positions=None, return_kv: bool = False):
        del train, mask              # no dropout; sequences are full length
        if cache is not None or return_kv:
            raise NotImplementedError(
                "Granite4Hybrid trains only: decoding needs a cache of each "
                "Mamba layer's state (H x P x N) and its convolution's last "
                "K - 1 inputs beside the KV cache (ROADMAP R4)")
        cfg = self.config
        embed = nn.Embed(
            cfg.vocab_size, cfg.hidden_size, dtype=cfg.dtype,
            embedding_init=nn.with_logical_partitioning(
                nn.initializers.normal(stddev=0.02), (VOCAB, EMBED)),
            name="embed")
        x = embed(tokens) * jnp.asarray(cfg.embedding_multiplier, cfg.dtype)
        block = nn.remat(Granite4Block) if cfg.remat else Granite4Block
        absmax = []
        for i, kind in enumerate(cfg.layer_types):
            x, m = block(cfg, kind, self.attn_fn, name=f"layer_{i}")(x)
            if kind == "mamba":
                absmax.append(m)
        mamba = len(absmax)
        batch, length = tokens.shape
        self.sow(COUNTERS, "ssd_calls", jnp.float32(mamba))
        self.sow(COUNTERS, "ssd_chunks", jnp.float32(
            mamba * batch * (length // cfg.mamba_chunk_size)))
        if absmax:
            self.sow(COUNTERS, "ssd_state_absmax", jnp.max(jnp.stack(absmax)))
        h = RMSNorm(cfg.rms_norm_eps, name="final_norm")(x).astype(cfg.dtype)
        return embed.attend(h).astype(jnp.float32) / cfg.logits_scaling


def _build(defaults: dict, attn_fn, kw: dict) -> Granite4Hybrid:
    cfg = {**defaults, **_norm_dtype(kw)}
    return Granite4Hybrid(Granite4HybridConfig(**cfg), attn_fn=attn_fn)


def granite4_h_micro_pp4(num_classes: int = 0,
                         attn_fn: Optional[AttnFn] = None,
                         **kw) -> Granite4Hybrid:
    """Granite-4.0-H-Micro, one chip's share of pipeline stage 1 of four:
    every width as published; published layers 0-9 (one period: nine
    Mamba-2 layers and the NoPE attention layer at 5) and 12,544 of the
    100,352 vocabulary rows (an eighth: vocabulary-parallel over eight
    chips). 772.2 M parameters. Every block is recomputed in the backward
    pass: 12.35 GB of weights, gradient and Adam's moments leave the chip
    too little for ten blocks' activations at 4096 tokens."""
    del num_classes
    return _build(dict(
        vocab_size=12544, layer_types=_PUBLISHED_LAYERS[:10], max_len=4096,
        remat=True,
    ), attn_fn, kw)


def granite4_tiny(num_classes: int = 0, attn_fn: Optional[AttnFn] = None,
                  **kw) -> Granite4Hybrid:
    """The same shape at toy widths for the CPU tests and the benchmark's
    rehearsal: 64 wide, two Mamba heads of 64 (state 16, chunks of 16),
    four attention heads of 16 over two KV heads, layers mamba, attention,
    mamba."""
    del num_classes
    return _build(dict(
        vocab_size=512, hidden_size=64, intermediate_size=128,
        num_attention_heads=4, num_key_value_heads=2,
        layer_types=("mamba", "attention", "mamba"), mamba_n_heads=2,
        mamba_d_head=64, mamba_d_state=16, mamba_chunk_size=16,
        attention_multiplier=1 / 16, max_len=64, dtype=jnp.float32,
    ), attn_fn, kw)

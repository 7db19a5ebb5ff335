"""SmallThinker sparse-expert decoder (PowerInfer ``smallthinker``),
TPU-native (flax.linen).

Every layer is an expert layer under RMSNorm pre-norm residual blocks.
Attention is grouped-query and comes in two kinds, told by the source's
two per-layer lists: a *global* layer (``sliding_window_layout`` 0) sees
the whole causal prefix and has no positions at all (``rope_layout`` 0:
NoPE), a *window* layer (1 / 1) sees the ``sliding_window_size`` keys that
end with the query's own and turns q and k by rotary positions. No QK-norm,
no bias. The router reads the layer's input itself — the residual stream
before the input norm, so before attention — scores all
``moe_num_primary_experts``, takes the top
``moe_num_active_primary_experts`` and weighs them by a softmax over those
selected; the experts' FFN is ReLU-gated and reads the normed stream after
attention. The head is a matrix of its own.

What is this model's is here: its config, its attention, its router, its
block, its presets. RMSNorm, rotary, the KV-head repeat, dispatch, the row
gathers and the expert computation are ``models/lfm2.py``'s, imported: the
expert layer is told which experts it holds (``experts_held`` = ``(first,
count)``), routes over all of them and computes its own experts' part of
the result, dropless, with no exchange and nothing standing in for the
absent chips. The routing is decided once, in the forward pass, and kept
for the backward pass (as LFM2's: PERF.md section 6, PR 34).

Shapes: tokens ``(B, L) int32`` -> logits ``(B, L, vocab) float32``.
Matmuls and activations run in ``dtype`` (bfloat16); parameters, RMSNorm
statistics, softmax, rotary angles and the whole router (logits at matmul
precision highest, top-k, softmax) are float32. Training only: the serving
path has no cache whose size differs by layer kind and no decode kernel
with a window, and ``__call__`` says so.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp
from flax import linen as nn
from jax.ad_checkpoint import checkpoint_name

from pytorch_distributed_nn_tpu.models.lfm2 import (
    ROUTER_SCORES,
    Experts,
    RMSNorm,
    _proj,
    repeat_kv,
    rotary,
    top_k,
)
from pytorch_distributed_nn_tpu.models.transformer import (
    EMBED,
    HEADS,
    KV,
    VOCAB,
    AttnFn,
    _dense_init,
    _norm_dtype,
    full_attention,
)

#: PowerInfer/SmallThinker-21BA3B-Instruct ``sliding_window_layout`` and
#: ``rope_layout``: one global layer without positions, then three window
#: layers with rotary positions, thirteen times
_PUBLISHED_LAYOUT = (0, 1, 1, 1) * 13


@dataclasses.dataclass(frozen=True)
class SmallThinkerConfig:
    """SmallThinker-21BA3B-Instruct as published (config.json), under its
    own key names."""

    vocab_size: int = 151936
    hidden_size: int = 2560
    moe_ffn_hidden_size: int = 768         # one expert
    num_attention_heads: int = 28
    num_key_value_heads: int = 4
    head_dim: int = 128
    sliding_window_layout: Tuple[int, ...] = _PUBLISHED_LAYOUT  # 1 = window
    rope_layout: Tuple[int, ...] = _PUBLISHED_LAYOUT            # 1 = rotary
    sliding_window_size: int = 4096
    moe_num_primary_experts: int = 64      # the router's width, never cut
    moe_num_active_primary_experts: int = 6
    #: (first, count): the experts this chip holds, of the router's width
    experts_held: Tuple[int, int] = (0, 64)
    rms_norm_eps: float = 1e-6
    rope_theta: float = 1_500_000.0
    # moe_primary_router_apply_softmax, norm_topk_prob (true) and
    # tie_word_embeddings (false) are as published and are not switches
    max_len: int = 16384                   # rotary: no table, no limit
    dtype: Any = jnp.bfloat16
    # recompute every block in the backward pass, not only the expert
    # computation after the routing (which always is, as in lfm2.py)
    remat: bool = False
    dropout_rate: float = 0.0              # the family has none

    @property
    def num_hidden_layers(self) -> int:
        return len(self.sliding_window_layout)

    # what the trainer reads of a text model's config
    @property
    def num_heads(self) -> int:
        return self.num_attention_heads

    # what lfm2.Experts reads of a sparse-expert model's config
    @property
    def moe_intermediate_size(self) -> int:
        return self.moe_ffn_hidden_size

    @property
    def num_experts(self) -> int:
        return self.moe_num_primary_experts


class Attention(nn.Module):
    """Causal grouped-query attention over the whole prefix (``window``
    None) or over the ``window`` keys that end with the query's own, with
    rotary positions on q and k or none (``positions``). No QK-norm, no
    bias. An ``attn_fn`` is handed ``window=`` only on a window layer."""

    config: SmallThinkerConfig
    window: Optional[int] = None
    positions: bool = True
    attn_fn: Optional[AttnFn] = None

    @nn.compact
    def __call__(self, x):
        cfg = self.config
        H, Hkv, D = (cfg.num_attention_heads, cfg.num_key_value_heads,
                     cfg.head_dim)
        q = _proj((H, D), (EMBED, HEADS, KV), "query", cfg.dtype)(x)
        k = _proj((Hkv, D), (EMBED, HEADS, KV), "key", cfg.dtype)(x)
        v = _proj((Hkv, D), (EMBED, HEADS, KV), "value", cfg.dtype)(x)
        if self.positions:
            q = rotary(q, cfg.rope_theta).astype(cfg.dtype)
            k = rotary(k, cfg.rope_theta).astype(cfg.dtype)
        attn = self.attn_fn if self.attn_fn is not None else full_attention
        kind = {} if self.window is None else {"window": self.window}
        out = attn(q, repeat_kv(k, H // Hkv), repeat_kv(v, H // Hkv), None,
                   causal=True, **kind)
        return _proj(cfg.hidden_size, (HEADS, KV, EMBED), "out", cfg.dtype,
                     axis=(-2, -1))(out)


def route(logits, k: int):
    """``logits (T, E)`` float32 -> ``(sel (T, k) int32, weights (T, k)
    float32)``: the k largest logits (ties go to the lower index), weighted
    by a softmax over those k — the softmax over all E renormalised over
    the selected, written once."""
    sel, picked = top_k(logits, logits, k)
    return sel, jax.nn.softmax(picked, axis=-1)


class Router(nn.Module):
    """Scores all ``moe_num_primary_experts`` from the layer's own input
    and decides the routing, once: the logits are kept for the backward
    pass under either remat (``ROUTER_SCORES``)."""

    config: SmallThinkerConfig

    @nn.compact
    def __call__(self, tokens):
        """tokens (T, d), the residual stream -> (sel, weights)."""
        cfg = self.config
        gate = self.param(
            "gate", nn.with_logical_partitioning(
                _dense_init(), (EMBED, None)),
            (tokens.shape[-1], cfg.moe_num_primary_experts), jnp.float32)
        with jax.named_scope("moe/route"):
            logits = checkpoint_name(jnp.dot(
                tokens.astype(jnp.float32), gate,
                precision=jax.lax.Precision.HIGHEST), ROUTER_SCORES)
            return route(logits, cfg.moe_num_active_primary_experts)


class SmallThinkerBlock(nn.Module):
    """``routing = router(x)``; ``x = x + attention(norm(x))``; ``x = x +
    experts(norm(x), routing)``."""

    config: SmallThinkerConfig
    layer: int
    attn_fn: Optional[AttnFn] = None

    @nn.compact
    def __call__(self, x):
        cfg = self.config
        B, L, d = x.shape
        sel, weights = Router(cfg, name="router")(x.reshape(B * L, d))
        h = RMSNorm(cfg.rms_norm_eps, name="input_norm")(x).astype(cfg.dtype)
        windowed = bool(cfg.sliding_window_layout[self.layer])
        # the module's name is the innermost scope: it names the Mosaic
        # calls in a trace (swa.<n> | attn.<n>)
        with jax.named_scope("attn/window" if windowed else "attn/global"):
            h = Attention(
                cfg, cfg.sliding_window_size if windowed else None,
                bool(cfg.rope_layout[self.layer]), self.attn_fn,
                name="swa" if windowed else "attn")(h)
        x = x + h
        h = RMSNorm(cfg.rms_norm_eps, name="post_attention_norm")(x)
        experts = Experts if cfg.remat else nn.remat(Experts)
        y = experts(cfg, "relu", name="experts")(
            h.reshape(B * L, d), sel, weights)
        return x + y.astype(cfg.dtype).reshape(B, L, d)


class SmallThinker(nn.Module):
    """Decoder-only LM. The zoo's call signature
    (``model.apply(vars, tokens, train=...)`` -> float32 logits), so the
    train step, loss and evaluator drive it like every other text model."""

    config: SmallThinkerConfig = SmallThinkerConfig()
    attn_fn: Optional[AttnFn] = None

    @nn.compact
    def __call__(self, tokens, train: bool = False, mask=None, cache=None,
                 positions=None, return_kv: bool = False):
        del train, mask              # no dropout; sequences are full length
        if cache is not None or return_kv:
            raise NotImplementedError(
                "SmallThinker trains only: decoding needs a cache whose "
                "size differs by layer kind (window / global), a KV-head "
                "axis and decode kernels with a window in "
                "serving/generate/ (ROADMAP R1, R3)")
        cfg = self.config
        x = nn.Embed(
            cfg.vocab_size, cfg.hidden_size, dtype=cfg.dtype,
            embedding_init=nn.with_logical_partitioning(
                nn.initializers.normal(stddev=0.02), (VOCAB, EMBED)),
            name="embed")(tokens)
        block = SmallThinkerBlock
        if cfg.remat:
            # every block recomputed, but not its routing (Router)
            block = nn.remat(SmallThinkerBlock, policy=jax.checkpoint_policies
                .save_only_these_names(ROUTER_SCORES))
        for i in range(cfg.num_hidden_layers):
            x = block(cfg, i, self.attn_fn, name=f"layer_{i}")(x)
        x = RMSNorm(cfg.rms_norm_eps, name="final_norm")(x)
        return _proj(cfg.vocab_size, (EMBED, VOCAB), "lm_head", cfg.dtype)(
            x.astype(cfg.dtype)).astype(jnp.float32)


def _build(defaults: dict, attn_fn, kw: dict) -> SmallThinker:
    cfg = {**defaults, **_norm_dtype(kw)}
    return SmallThinker(SmallThinkerConfig(**cfg), attn_fn=attn_fn)


def smallthinker_21b_a3b_ep8(num_classes: int = 0,
                             attn_fn: Optional[AttnFn] = None,
                             **kw) -> SmallThinker:
    """SmallThinker-21BA3B-Instruct, one chip's share of eight-way expert
    parallelism: every width as published; experts 0-7 of 64 and 18,992 of
    the 151,936 vocabulary rows held here; published layers 0-3, one whole
    period (global, window, window, window). 370.5 M parameters."""
    del num_classes
    return _build(dict(
        vocab_size=18992, experts_held=(0, 8),
        sliding_window_layout=(0, 1, 1, 1), rope_layout=(0, 1, 1, 1),
    ), attn_fn, kw)


def smallthinker_tiny(num_classes: int = 0,
                      attn_fn: Optional[AttnFn] = None,
                      **kw) -> SmallThinker:
    """The same shape at toy widths for the CPU tests and the benchmark's
    rehearsal: 64 wide, 4 query / 2 KV heads of 16, one period, a window
    of 16 (at L = 64), 8 experts of which experts 2-5 are held, top-2."""
    del num_classes
    return _build(dict(
        vocab_size=512, hidden_size=64, moe_ffn_hidden_size=128,
        num_attention_heads=4, num_key_value_heads=2, head_dim=16,
        sliding_window_layout=(0, 1, 1, 1), rope_layout=(0, 1, 1, 1),
        sliding_window_size=16, moe_num_primary_experts=8,
        moe_num_active_primary_experts=2, experts_held=(2, 4),
        max_len=64, dtype=jnp.float32,
    ), attn_fn, kw)

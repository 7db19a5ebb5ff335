"""GLM-4.7-Flash sparse-expert decoder (zai-org ``glm4_moe_lite``),
TPU-native (flax.linen).

Every layer attends by multi-head latent attention (MLA, DeepSeek-V2
section 2.1): the query comes out of a ``q_lora_rank`` latent and the key
and value out of a ``kv_lora_rank`` latent, each latent behind an RMSNorm;
a query head and a key head are ``qk_nope_head_dim`` channels without
positions and ``qk_rope_head_dim`` rotary channels, and the key's rotary
channels are one vector a token, shared by every head. The training form
up-projects the latents to whole heads and hands them to the attention
kernel (the absorbed form, which attends in the latent, is for a serving
cache). The first ``first_k_dense_replace`` layers have a SiLU-gated MLP;
every later one has sparse experts beside a shared expert: a sigmoid
router over all ``n_routed_experts`` selects the top
``num_experts_per_tok`` of score + expert bias, weighs them by their own
scores normalised over the selected, times ``routed_scaling_factor``, and
the shared expert (a SiLU-gated MLP of ``n_shared_experts`` x
``moe_intermediate_size``) reads every token. The head is a matrix of its
own. ``num_nextn_predict_layers`` prediction modules (DeepSeek-V3 section
2.2) each join the normed embedding of the token one further ahead to the
normed hidden state of the depth before, project the pair back to the
hidden size, run one expert layer and predict through the same embedding
and head: logits ``(B, L, 1 + num_nextn_predict_layers, vocab)`` against
labels with the same depth axis (``data/text.next_token_labels``).

What is this model's is here: its config, latent attention, its blocks,
the prediction module and its presets. RMSNorm, rotary, the gated MLP,
the sigmoid router with its expert bias and scale, and the expert layer
that is told which experts it holds (``experts_held``) are
``models/lfm2.py``'s, imported: the routed part is computed for the held
experts only, dropless, with no exchange and nothing standing in for the
absent chips; the routing is decided once, in the forward pass, and kept
for the backward pass.

Shapes: tokens ``(B, L) int32`` -> logits ``(B, L, depth, vocab)
float32``. Matmuls and activations run in ``dtype`` (bfloat16);
parameters, RMSNorm statistics, softmax, rotary angles and the router are
float32. Training only: the serving path has no latent cache, and
``__call__`` says so.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp
from flax import linen as nn

from pytorch_distributed_nn_tpu.models.lfm2 import (
    ROUTER_SCORES,
    GatedMLP,
    RMSNorm,
    SparseExperts,
    _proj,
    rotary,
    top_k,
)
from pytorch_distributed_nn_tpu.models.transformer import (
    EMBED,
    HEADS,
    KV,
    VOCAB,
    AttnFn,
    _norm_dtype,
    full_attention,
)


#: the flax collection each expert layer's normed input is sown into, for
#: ``Glm47Flash.balance_routing``; no training step makes it mutable, so
#: there the sow is a no-op
ROUTER_INPUTS = "router_inputs"


@dataclasses.dataclass(frozen=True)
class Glm47FlashConfig:
    """GLM-4.7-Flash as published (config.json), under its own key names."""

    vocab_size: int = 154880
    hidden_size: int = 2048
    intermediate_size: int = 10240         # the leading dense layers' MLP
    moe_intermediate_size: int = 1536      # one expert, the shared one too
    num_attention_heads: int = 20
    q_lora_rank: int = 768
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 192
    qk_rope_head_dim: int = 64
    v_head_dim: int = 256
    num_hidden_layers: int = 47
    first_k_dense_replace: int = 1
    n_routed_experts: int = 64             # the router's width, never cut
    n_shared_experts: int = 1
    num_experts_per_tok: int = 4
    routed_scaling_factor: float = 1.8
    #: (first, count): the experts this chip holds, of ``n_routed_experts``
    experts_held: Tuple[int, int] = (0, 64)
    num_nextn_predict_layers: int = 1
    rms_norm_eps: float = 1e-5
    rope_theta: float = 1_000_000.0
    # topk_method noaux_tc with n_group = topk_group = 1 (a group-limited
    # step that selects nothing away), norm_topk_prob (true) and
    # tie_word_embeddings (false) are as published and are not switches
    max_len: int = 202752                  # rotary: no table, no limit
    dtype: Any = jnp.bfloat16
    # recompute every block in the backward pass, not only the expert
    # computation after the routing (which always is, as in lfm2.py)
    remat: bool = False
    dropout_rate: float = 0.0              # the family has none

    # what the trainer reads of a text model's config
    @property
    def num_heads(self) -> int:
        return self.num_attention_heads

    @property
    def label_depth(self) -> int:
        """Targets a position is trained on: the next token and one more
        a prediction module."""
        return 1 + self.num_nextn_predict_layers

    # what lfm2.SparseExperts and lfm2.Experts read of a sparse-expert
    # model's config
    @property
    def num_experts(self) -> int:
        return self.n_routed_experts

    @property
    def shared_expert(self) -> "Glm47FlashConfig":
        """The config lfm2.GatedMLP builds the shared expert from."""
        return dataclasses.replace(
            self, intermediate_size=self.n_shared_experts
            * self.moe_intermediate_size)


def tokens_ahead(tokens, depth: int):
    """What prediction module ``depth`` (1, 2, ...) reads at position i:
    token i + depth, and token 0 where that lies past the end (the
    position's targets at that depth are ignored there)."""
    return jnp.pad(tokens[:, depth:], ((0, 0), (0, depth)))


class LatentAttention(nn.Module):
    """Causal MLA in its training form: both latents up-projected to
    ``num_attention_heads`` whole heads of ``qk_nope_head_dim +
    qk_rope_head_dim`` (query, key) and ``v_head_dim`` (value), the one
    rotary key vector a token repeated to every head, softmax at scale
    1/sqrt(query head width). No bias."""

    config: Glm47FlashConfig
    attn_fn: Optional[AttnFn] = None

    @nn.compact
    def __call__(self, x):
        cfg = self.config
        H, nope, rope = (cfg.num_attention_heads, cfg.qk_nope_head_dim,
                         cfg.qk_rope_head_dim)
        rank, eps, dt = cfg.kv_lora_rank, cfg.rms_norm_eps, cfg.dtype
        c_q = _proj(cfg.q_lora_rank, (EMBED, None), "q_a_proj", dt)(x)
        c_q = RMSNorm(eps, name="q_a_norm")(c_q).astype(dt)
        q = _proj((H, nope + rope), (None, HEADS, KV), "q_b_proj", dt)(c_q)
        c_kv = _proj(rank + rope, (EMBED, None), "kv_a_proj", dt)(x)
        # the rotary key: one vector a token (a head axis of 1)
        c_kv, k_rope = c_kv[..., :rank], c_kv[..., None, rank:]
        c_kv = RMSNorm(eps, name="kv_a_norm")(c_kv).astype(dt)
        kv = _proj((H, nope + cfg.v_head_dim), (None, HEADS, KV),
                   "kv_b_proj", dt)(c_kv)
        k_nope, v = kv[..., :nope], kv[..., nope:]
        q_rope = rotary(q[..., nope:], cfg.rope_theta).astype(dt)
        k_rope = rotary(k_rope, cfg.rope_theta).astype(dt)
        q = jnp.concatenate([q[..., :nope], q_rope], axis=-1)
        k = jnp.concatenate(
            [k_nope, jnp.broadcast_to(k_rope, k_nope.shape[:-1] + (rope,))],
            axis=-1)
        attn = self.attn_fn if self.attn_fn is not None else full_attention
        out = attn(q, k, v, None, causal=True)
        return _proj(cfg.hidden_size, (HEADS, KV, EMBED), "o_proj", dt,
                     axis=(-2, -1))(out)


#: noaux_tc's rule as ``balance_bias`` runs it: 400 updates whose step
#: shrinks geometrically from 0.05 to 1e-4 (sigmoid scores lie in (0, 1))
BALANCE_GAMMAS = (0.05, 1e-4, 400)
#: the sequences ``Glm47Flash.balance_routing`` balances the load over
BALANCE_SEQUENCES = 16


def balance_bias(scores, bias, k: int):
    """noaux_tc's balancing rule (DeepSeek-V3 section 2.1.2) run on fixed
    router scores ``(T, E)`` until it settles: an expert's bias rises by
    gamma where it takes fewer than its share ``T k / E`` of the top-k of
    score + bias, and falls by gamma where it takes more. gamma shrinks
    geometrically (``BALANCE_GAMMAS``), so the bias comes to rest where a
    fixed gamma would swing about. Returns the bias ``(E,)``."""
    T, E = scores.shape
    share = T * k / E
    first, last, steps = BALANCE_GAMMAS
    gammas = first * (last / first) ** (jnp.arange(steps) / (steps - 1))

    def step(b, gamma):
        sel, _ = top_k(scores + b, scores, k)
        load = jnp.sum(sel[..., None] == jnp.arange(E), axis=(0, 1))
        return b + gamma * jnp.sign(share - load), None

    return jax.lax.scan(step, bias, gammas)[0]


class Glm47Block(nn.Module):
    """``x + mla(norm(x))`` then ``x + ffn(norm(x))``, the ffn a gated MLP
    (``dense``) or the routed experts held here plus the shared expert."""

    config: Glm47FlashConfig
    dense: bool
    attn_fn: Optional[AttnFn] = None

    @nn.compact
    def __call__(self, x):
        cfg = self.config
        h = RMSNorm(cfg.rms_norm_eps, name="input_layernorm")(x)
        # the module's name is the innermost scope: it names the Mosaic
        # calls in a trace (mla.<n>)
        with jax.named_scope("attn/latent"):
            h = LatentAttention(cfg, self.attn_fn, name="mla")(
                h.astype(cfg.dtype))
        x = x + h
        h = RMSNorm(cfg.rms_norm_eps, name="post_attention_layernorm")(x)
        if self.dense:
            return x + GatedMLP(cfg, name="mlp")(h.astype(cfg.dtype))
        self.sow(ROUTER_INPUTS, "x", h)
        routed = SparseExperts(cfg, name="moe")(h)
        shared = GatedMLP(cfg.shared_expert, name="shared_expert")(
            h.astype(cfg.dtype))
        return x + routed + shared


def _block(cfg: Glm47FlashConfig):
    if cfg.remat:
        # every block recomputed, but not its routing (SparseExperts)
        return nn.remat(Glm47Block, policy=jax.checkpoint_policies
                        .save_only_these_names(ROUTER_SCORES))
    return Glm47Block


class NextTokenPredictor(nn.Module):
    """One prediction module: ``h' = W_eh [enorm(emb) ; hnorm(h)]``, then
    one expert layer. Returns the layer's residual output (what a further
    module would read) and the same through the module's own final norm
    (what the head reads)."""

    config: Glm47FlashConfig
    attn_fn: Optional[AttnFn] = None

    @nn.compact
    def __call__(self, h, emb):
        cfg = self.config
        joined = jnp.concatenate([
            RMSNorm(cfg.rms_norm_eps, name="enorm")(emb),
            RMSNorm(cfg.rms_norm_eps, name="hnorm")(h)], axis=-1)
        h = _proj(cfg.hidden_size, (None, EMBED), "eh_proj", cfg.dtype)(
            joined.astype(cfg.dtype))
        h = _block(cfg)(cfg, False, self.attn_fn, name="layer")(h)
        return h, RMSNorm(cfg.rms_norm_eps, name="norm")(h)


class Glm47Flash(nn.Module):
    """Decoder-only LM with next-token prediction modules. The zoo's call
    signature (``model.apply(vars, tokens, train=...)`` -> float32 logits),
    with a depth axis before the vocabulary: position i, depth j predicts
    token i + 1 + j."""

    config: Glm47FlashConfig = Glm47FlashConfig()
    attn_fn: Optional[AttnFn] = None

    @nn.compact
    def __call__(self, tokens, train: bool = False, mask=None, cache=None,
                 positions=None, return_kv: bool = False):
        del train, mask              # no dropout; sequences are full length
        if cache is not None or return_kv:
            raise NotImplementedError(
                "Glm47Flash trains only: decoding needs a cache of the "
                "latent (c_kv and the rotary key) and an attention that "
                "reads it in serving/generate/ (ROADMAP R2)")
        cfg = self.config
        embed = nn.Embed(
            cfg.vocab_size, cfg.hidden_size, dtype=cfg.dtype,
            embedding_init=nn.with_logical_partitioning(
                nn.initializers.normal(stddev=0.02), (VOCAB, EMBED)),
            name="embed")
        x = embed(tokens)
        block = _block(cfg)
        for i in range(cfg.num_hidden_layers):
            x = block(cfg, i < cfg.first_k_dense_replace, self.attn_fn,
                      name=f"layer_{i}")(x)
        normed = [RMSNorm(cfg.rms_norm_eps, name="final_norm")(x)]
        h = x
        for depth in range(1, cfg.num_nextn_predict_layers + 1):
            h, out = NextTokenPredictor(
                cfg, self.attn_fn, name=f"mtp_{depth - 1}")(
                    h, embed(tokens_ahead(tokens, depth)))
            normed.append(out)
        head = _proj(cfg.vocab_size, (EMBED, VOCAB), "lm_head", cfg.dtype)
        return head(jnp.stack(normed, axis=2).astype(cfg.dtype)).astype(
            jnp.float32)

    def balance_routing(self, params, rng, length: int):
        """``params`` with each expert layer's bias where noaux_tc's rule
        settles (``balance_bias``) on ``BALANCE_SEQUENCES`` sequences of
        ``length`` tokens drawn uniformly from ``rng``: the state that balances the
        load, as the published training keeps it. Several sequences,
        because at initialisation a sequence's router inputs share one
        offset of their own (causal attention near uniform averages each
        prefix), so one sequence balances itself and no other. Layer by
        layer in the order the stream meets them, each on the scores of
        its own input under the biases already set before it (a layer's
        bias moves what the later layers read, not what it reads); the
        sequences go through the model one at a time. The trainer calls
        this on the weights it seeds."""
        cfg = self.config
        tokens = jax.random.randint(
            rng, (BALANCE_SEQUENCES, 1, length), 0, cfg.vocab_size)
        blocks = [(f"layer_{i}",) for i in range(
            cfg.first_k_dense_replace, cfg.num_hidden_layers)] + [
            (f"mtp_{d}", "layer")
            for d in range(cfg.num_nextn_predict_layers)]

        @jax.jit
        def scores(params, tokens):
            def one(t):
                sown = self.apply({"params": params}, t,
                                  mutable=[ROUTER_INPUTS])[1][ROUTER_INPUTS]
                return [jax.nn.sigmoid(jnp.dot(
                    _at(sown, path)["x"][0].reshape(-1, cfg.hidden_size),
                    _at(params, path)["moe"]["router"],
                    precision=jax.lax.Precision.HIGHEST)) for path in blocks]

            return [s.reshape(-1, cfg.num_experts)
                    for s in jax.lax.map(one, tokens)]

        solve = jax.jit(functools.partial(
            balance_bias, k=cfg.num_experts_per_tok))
        for j, path in enumerate(blocks):
            bias = solve(scores(params, tokens)[j],
                         _at(params, path)["moe"]["expert_bias"])
            params = _with(params, path + ("moe", "expert_bias"), bias)
        return params


def _at(tree, path):
    for key in path:
        tree = tree[key]
    return tree


def _with(tree, path, value):
    """A copy of the nested dict ``tree`` with ``value`` at ``path``; the
    other leaves are the same objects."""
    if not path:
        return value
    return {**tree, path[0]: _with(tree[path[0]], path[1:], value)}


def _build(defaults: dict, attn_fn, kw: dict) -> Glm47Flash:
    cfg = {**defaults, **_norm_dtype(kw)}
    return Glm47Flash(Glm47FlashConfig(**cfg), attn_fn=attn_fn)


def glm47_flash_ep8(num_classes: int = 0, attn_fn: Optional[AttnFn] = None,
                    **kw) -> Glm47Flash:
    """GLM-4.7-Flash, one chip's share of eight-way expert parallelism:
    every width as published; experts 0-7 of 64 and 19,360 of the 154,880
    vocabulary rows held here; published layers 0-4 (the dense layer and
    four expert layers) and the prediction module. 706.5 M parameters."""
    del num_classes
    return _build(dict(
        vocab_size=19360, num_hidden_layers=5, experts_held=(0, 8),
        max_len=4096,
    ), attn_fn, kw)


def glm47_flash_tiny(num_classes: int = 0, attn_fn: Optional[AttnFn] = None,
                     **kw) -> Glm47Flash:
    """The same shape at toy widths for the CPU tests and the benchmark's
    rehearsal: 64 wide, latents of 48 and 32, 4 heads of 16 + 8 (value
    24), one dense layer and two expert layers, 8 experts of which experts
    2-5 are held, top-2, one shared expert, one prediction module."""
    del num_classes
    return _build(dict(
        vocab_size=512, hidden_size=64, intermediate_size=128,
        moe_intermediate_size=32, num_attention_heads=4, q_lora_rank=48,
        kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8,
        v_head_dim=24, num_hidden_layers=3, n_routed_experts=8,
        num_experts_per_tok=2, experts_held=(2, 4), max_len=64,
        dtype=jnp.float32,
    ), attn_fn, kw)

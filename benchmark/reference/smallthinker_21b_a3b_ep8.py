"""SmallThinker-21BA3B-Instruct (one chip's share of eight-way expert
parallelism): next-token loss and gradients in plain float32 jax.numpy.

Independent of the program: no flax module, no kernel, nothing imported
from ``pytorch_distributed_nn_tpu``. Only the parameter *tree* is shared
(its names are read below), because the comparison needs the same weights
on both sides.

The equations are PowerInfer's ``smallthinker`` (config.json of
PowerInfer/SmallThinker-21BA3B-Instruct; ``x`` is the residual stream
entering layer l, T tokens x 2560):

  r      = x                         the router's input: the layer's input
                                     itself, before the input norm
  h      = RMSNorm_in(x);  RMSNorm(x) = x * rsqrt(mean(x^2) + 1e-6) * w
  q,k,v  = h W_q (28 x 128), h W_k (4 x 128), h W_v (4 x 128); no bias,
           no QK-norm; KV head j serves query heads 7j .. 7j+6
  q,k    = rotary(q, k; rotate-half, theta 1.5e6) where rope_layout[l]
           is 1 (the window layers); no positions at all where it is 0
  a_i    = softmax_j(q_i . k_j / sqrt(128)) v_j over j <= i and, where
           sliding_window_layout[l] is 1, i - j < 4096
  x'     = x + concat(a) W_o
  logits = r W_r (2560 x 64); S = top6(logits); w = softmax(logits[S])
  u      = RMSNorm_post(x')
  y      = sum_{e in S, e held here} w_e (relu(u W_g^e) * (u W_u^e)) W_d^e
  x_out  = x' + y
  model    embed -> the layers -> RMSNorm_final -> x W_h, a head of its own

Departures, each in the configuration's ``assumed``: the router's input is
taken as the layer's raw input (``router_input``); the window counts the
query's own key (``window``); no secondary experts (the source's config
has keys for the primary ones only); no auxiliary loss (the config has
none).

The share: this chip holds experts ``first_expert .. first_expert +
moe_num_primary_experts - 1`` of the ``router_width`` the router scores.
Every held expert runs on every token here, weighted by w_e (zero where
the token did not select it); what the absent experts would add is left
out, as in the program, and the weights are a softmax over all six
selected, absent or not.

Memory (``check_batch`` 1 x 16,384 beside three 1.48 GB parameter trees):
each layer is a ``jax.checkpoint`` and attention's scores are made a block
of 512 queries at a time (whole, they are 28 x 16,384^2 x 4 B = 30 GB).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

IGNORE = -1
HIGHEST = lax.Precision.HIGHEST
QUERY_BLOCK = 512

# Agreement the comparison demands between the program (bfloat16 matmuls
# and activations, residual stream included; float32 parameters, RMSNorm
# statistics, softmax, rotary angles and router) and this file (float32
# throughout, matmul precision "highest"), on one seeded sequence of
# 16,384 at seeded weights. Measured on the TPU v5e at the published widths
# (my chip calls 1 and 2, PR 38): the program's readings over 19 seeds (9
# benchmark runs, 10 seeds of ``benchmark/tools/smallthinker_controls.py``),
# and this file's own over those 10 seeds when computed in bfloat16
# throughout (parameters, activations, statistics, softmax, router, loss:
# the nearest precision below the configuration's).
#
# What the gradient distance is made of. Rounding, mostly: 0.0116 .. 0.0147
# is BERT's size (0.013) and the LFM2 share's with its routing planted
# (0.0118). Of the 98,304 routing decisions a layer makes, 139 .. 1,098
# differ between the program and this file (0.15 % in the first layer, whose
# router reads the embedding rounded to bfloat16, 0.6 .. 1.1 % in the later
# ones), but a swapped expert is held here one time in eight and weighs a
# sixth, so the swaps add little on average; they are what varies seed to
# seed (sd 0.0008). The bfloat16 control swaps 1.4 .. 2.4 x as many and
# reads 1.17 .. 1.23 x the program seed for seed.
TOLERANCE = {
    # program 7.4e-7 .. 1.55e-5; bfloat16 control 1.6e-4 .. 2.1e-3 (its
    # loss is a bfloat16 number, 10.375 on every seed, so its reading is how
    # far the float32 loss, 10.35 .. 10.38, happens to lie from that). This
    # is the limit the lower precision fails, on all 10 seeds: 3.2 x the
    # program's largest reading, 3.2 x under the control's smallest (a seed
    # whose float32 loss lies within 5e-4 of a bfloat16 number would let it
    # pass: about one in thirty). A label shifted the wrong way, the last
    # position counted, a tied head or a dropped expert move the loss by
    # far more.
    "loss_rel": 0.00005,
    # program 6.8e-5 .. 1.13e-3; bfloat16 control 3.2e-5 .. 1.07e-3: the
    # norm does not tell them apart. The harness's accepted limit for a cut
    # configuration (LFM2's), 1.8 x the program's largest: swapped
    # decisions and rounding turn the gradient and leave its norm; a lost
    # term does not.
    "grad_norm_rel": 0.002,
    # program 0.0116 .. 0.0147 (mean 0.0135, sd 0.0008); bfloat16 control
    # 0.0143 .. 0.0174 (mean 0.0160, sd 0.0009). Seed for seed the control
    # is 1.2 x the program, but seed to seed each varies by as much, so the
    # ranges overlap and no limit lies between them: this one is the
    # program's mean + 5 sd, which a sound seed does not cross, and the
    # control passes it on 10 seeds of 10. It is not a guard of precision.
    # Planted at the tiny preset on the CPU (tests/test_smallthinker.py,
    # where the sound program reads 1e-6): rotary on the global layer
    # reads 0.73, the window dropped 0.79, the router fed the normed
    # input 0.46, SiLU for ReLU 0.57, a softmax over all 64 not
    # renormalised 0.84, the head tied to the embedding 1.36.
    "grad_rel_err": 0.0175,
}


def make_batch(key, n: int, config: dict):
    """``n`` seeded sequences of uniform token ids over the rows held;
    labels are the tokens shifted by one, nothing to predict at the end."""
    length = config["tokens_per_sample"]
    vocab = config["model"]["vocab_size"]
    tokens = jax.random.randint(key, (n, length), 0, vocab).astype(jnp.int32)
    last = jnp.full((n, 1), IGNORE, jnp.int32)
    return tokens, jnp.concatenate([tokens[:, 1:], last], axis=1)


def _rms(x, w, eps):
    return x * lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def _mm(x, w, spec: str):
    return jnp.einsum(spec, x, w, precision=HIGHEST)


def _rope(x, theta):
    """x (B, L, heads, D): pairs (i, i + D/2) turn by pos * theta^(-2i/D)."""
    length, dim = x.shape[1], x.shape[-1]
    freq = theta ** (-jnp.arange(dim // 2, dtype=jnp.float32) * 2.0 / dim)
    angle = jnp.arange(length, dtype=jnp.float32)[:, None] * freq
    # in x's precision: float32 tables would lift a lower-precision control
    # back to float32 from the first window layer on
    cos = jnp.cos(angle)[:, None, :].astype(x.dtype)
    sin = jnp.sin(angle)[:, None, :].astype(x.dtype)
    a, b = x[..., : dim // 2], x[..., dim // 2:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def _attention(p, x, m, window, positions: bool):
    """``window``: keys a query sees, its own included, or None for the
    whole causal prefix."""
    heads, kv_heads = m["num_attention_heads"], m["num_key_value_heads"]
    group = heads // kv_heads
    q = _mm(x, p["query"]["kernel"], "bld,dhk->blhk")
    k = _mm(x, p["key"]["kernel"], "bld,dhk->blhk")
    v = _mm(x, p["value"]["kernel"], "bld,dhk->blhk")
    if positions:
        q, k = _rope(q, m["rope_theta"]), _rope(k, m["rope_theta"])
    batch, length, _, dim = q.shape
    q = q.reshape(batch, length, kv_heads, group, dim)
    block = min(QUERY_BLOCK, length)
    starts = jnp.arange(0, length, block)

    @jax.checkpoint
    def one_block(start):
        qb = lax.dynamic_slice_in_dim(q, start, block, axis=1)
        scores = jnp.einsum("bqjgk,btjk->bjgqt", qb, k,
                            precision=HIGHEST) / jnp.sqrt(float(dim))
        i = (start + jnp.arange(block))[:, None]
        j = jnp.arange(length)[None, :]
        seen = j <= i
        if window is not None:
            seen = seen & (i - j < window)
        probs = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), axis=-1)
        return jnp.einsum("bjgqt,btjk->bqjgk", probs, v, precision=HIGHEST)

    out = lax.map(one_block, starts)             # (blocks, B, block, ...)
    out = jnp.moveaxis(out, 0, 1).reshape(batch, length, heads, dim)
    return _mm(out, p["out"]["kernel"], "blhk,hkd->bld")


def routing(p, r, m):
    """(sel (B, L, k), weights (B, L, k)) over all the published experts,
    from the layer's raw input ``r``."""
    logits = _mm(r, p["gate"], "bld,de->ble")
    picked, sel = lax.top_k(logits, m["moe_num_active_primary_experts"])
    return sel, jax.nn.softmax(picked, axis=-1)


def _expert_ffn(p, u, sel, weights, m):
    w13, w2 = p["w13"], p["w2"]
    width = w2.shape[1]
    y = jnp.zeros_like(u)
    for e in range(w13.shape[0]):                 # the experts held here
        share = jnp.sum(
            jnp.where(sel == m["first_expert"] + e, weights, 0.0), axis=-1)
        h = _mm(u, w13[e], "bld,df->blf")
        h = jax.nn.relu(h[..., :width]) * h[..., width:]
        y = y + share[..., None] * _mm(h, w2[e], "blf,fd->bld")
    return y


def _layer(p, x, m, windowed: bool, positions: bool):
    sel, weights = routing(p["router"], x, m)
    h = _rms(x, p["input_norm"]["scale"], m["rms_norm_eps"])
    x = x + _attention(
        p["swa" if windowed else "attn"], h, m,
        m["sliding_window_size"] if windowed else None, positions)
    u = _rms(x, p["post_attention_norm"]["scale"], m["rms_norm_eps"])
    return x + _expert_ffn(p["experts"], u, sel, weights, m)


def logits(params, tokens, config: dict):
    m = config["model"]
    x = params["embed"]["embedding"][tokens]
    for i, (windowed, positions) in enumerate(
            zip(m["sliding_window_layout"], m["rope_layout"])):
        x = jax.checkpoint(
            lambda p, x, w=bool(windowed), r=bool(positions):
            _layer(p, x, m, w, r))(params[f"layer_{i}"], x)
    x = _rms(x, params["final_norm"]["scale"], m["rms_norm_eps"])
    return _mm(x, params["lm_head"]["kernel"], "bld,dv->blv")


def loss(params, batch, config: dict):
    """Cross-entropy summed over the positions that predict / their number."""
    tokens, labels = batch
    logp = jax.nn.log_softmax(logits(params, tokens, config), axis=-1)
    keep = labels != IGNORE
    picked = jnp.take_along_axis(
        logp, jnp.where(keep, labels, 0)[..., None], axis=-1)[..., 0]
    return -jnp.sum(jnp.where(keep, picked, 0.0)) / jnp.maximum(
        jnp.sum(keep), 1)

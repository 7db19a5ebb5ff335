"""LFM2-8B-A1B (one chip's share of four-way expert parallelism):
next-token loss and gradients in plain float32 jax.numpy.

Independent of the program: no flax module, no kernel, nothing imported
from ``pytorch_distributed_nn_tpu``. Only the parameter *tree* is shared
(its names are read below), because the comparison needs the same weights
on both sides.

The equations are LiquidAI's ``lfm2_moe`` (config.json of
LiquidAI/LFM2-8B-A1B; ``x`` is the residual stream):

  block      x = x + operator(RMSNorm(x));  x = x + ffn(RMSNorm(x));
             RMSNorm(x) = x * rsqrt(mean(x^2) + 1e-5) * w. One more RMSNorm
             after the last block, then the head, tied to the embedding.
  conv       [B, C, u] = split3(x W_in); z = B * u;
             c_t = k_0 z_{t-2} + k_1 z_{t-1} + k_2 z_t (depthwise, zeros
             before the sequence start); out = (C * c) W_out. No bias.
  attention  q = x W_q (32 x 64), k = x W_k, v = x W_v (8 x 64); RMSNorm
             over the 64 of each head of q and of k, rotary (rotate-half,
             theta 1e6); causal softmax at scale 1/8, KV head j serves
             query heads 4j .. 4j+3; out = concat W_o. No bias.
  dense FFN  W_2(silu(W_1 x) * W_3 x), width 7168 (the leading layers).
  expert FFN s = sigmoid(x W_g) over all 32 experts; sel = top4(s + b), b
             the expert bias (it enters only the selection);
             w = s[sel] / (sum s[sel] + 1e-6); y = sum_{e in sel} w_e
             W_2^e(silu(W_1^e x) * W_3^e x), width 1792.

The share: this chip holds experts ``first_expert .. first_expert +
num_experts - 1`` of the 32 the router scores. Every held expert runs on
every token here, weighted by w_e (zero where the token did not select
it); what the absent experts would add is left out, as in the program, and
the weights are normalised over all four selected, absent or not.

Memory (``check_batch`` 1 x 8192 beside three 2.03 GB parameter trees):
each layer is a ``jax.checkpoint`` and attention's scores are made a block
of queries at a time (whole, they are 32 x 8192^2 x 4 B = 8.6 GB).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

IGNORE = -1
HIGHEST = lax.Precision.HIGHEST
QUERY_BLOCK = 512

# Agreement the comparison demands between the program (bfloat16 matmuls
# and activations; float32 parameters, RMSNorm statistics, softmax and
# router) and this file (float32 throughout, matmul precision "highest"),
# on one seeded sequence of 8192 at seeded weights. Measured on the TPU v5e
# at the published widths with ``benchmark/tools/lfm2_controls.py`` (my chip
# calls 2, 6 and 7, PR 34: 12 + 8 + 8 seeds) and in eight benchmark runs
# (call 3). Each limit is given with its two readings: the program's over
# its 36 seeds, and this file's own when computed in bfloat16 throughout
# (parameters, activations, statistics, softmax, router, loss: the nearest
# precision below the configuration's) over 16 seeds.
#
# What the gradient distance is made of. Of the 8192 x 4 = 32,768 routing
# decisions an expert layer makes, 222 .. 489 differ between the program
# and this file (0.7 .. 1.5 %; more in the later layers), 1,370 .. 1,497
# over the four layers: the router is float32 on both sides but its input
# has passed bfloat16 layers, and a fourth and a fifth score closer than
# that rounding swap. Made to route as the program did, this file is
# 0.0118 away (two seeds, call 7): that is rounding alone, BERT's size,
# even over the leaves. The flipped decisions are the rest, 0.050 in
# quadrature, and they are the floor: a precision below the configuration's
# shows as more flips, not as more rounding.
TOLERANCE = {
    # program 4.7e-6 .. 6.5e-5; bfloat16 control 1.2e-5 .. 2.4e-3 (its loss
    # is a bfloat16 number, 10.125 on every seed, so its reading is how far
    # the float32 loss happens to lie from that: over the limit on 14 seeds
    # of 16). The harness's accepted limit, 7.7 x the program's largest. A
    # label shifted the wrong way, the last position counted, an untied
    # head or a dropped expert move the loss by far more.
    "loss_rel": 0.0005,
    # program 2.4e-7 .. 2.0e-4; bfloat16 control 3.7e-4 .. 6.6e-4. The two
    # lie within a factor of two, too close for a limit between them that
    # a sound seed would not cross once in a few hundred runs: this limit
    # is 10 x the program's largest and does not separate them. Flipped
    # decisions turn the gradient and leave its norm; a lost term or
    # bfloat16 accumulation does not.
    "grad_norm_rel": 0.002,
    # program 0.0494 .. 0.0536 (mean 0.0518, sd 0.0013); bfloat16 control
    # 0.0607 .. 0.0637 (mean 0.0623, sd 0.0009): the limit lies 4.8 sd
    # from either mean, 8 % over the program's largest reading and 4 %
    # under the control's smallest. This is the limit the lower precision
    # fails, on all 16 seeds. Planted at the tiny preset on the CPU
    # (tests/test_lfm2.py, where the sound program reads 1e-6): KV heads
    # mapped to the wrong query heads read 0.62, tiles handed the next
    # expert's weights 3.6, a dispatch that drops every token's second
    # choice 0.14. A convolution that sees one position ahead reads only
    # 0.011 there and a bias that leaks into the weights 0.026: this limit
    # does not hold those two, the unit tests do.
    "grad_rel_err": 0.058,
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
    # back to float32 from the first attention layer on
    cos = jnp.cos(angle)[:, None, :].astype(x.dtype)
    sin = jnp.sin(angle)[:, None, :].astype(x.dtype)
    a, b = x[..., : dim // 2], x[..., dim // 2:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def _attention(p, x, m):
    heads, kv_heads = m["num_attention_heads"], m["num_key_value_heads"]
    group = heads // kv_heads
    q = _mm(x, p["query"]["kernel"], "bld,dhk->blhk")
    k = _mm(x, p["key"]["kernel"], "bld,dhk->blhk")
    v = _mm(x, p["value"]["kernel"], "bld,dhk->blhk")
    q = _rope(_rms(q, p["q_norm"]["scale"], m["norm_eps"]), m["rope_theta"])
    k = _rope(_rms(k, p["k_norm"]["scale"], m["norm_eps"]), m["rope_theta"])
    batch, length, _, dim = q.shape
    q = q.reshape(batch, length, kv_heads, group, dim)
    block = min(QUERY_BLOCK, length)
    starts = jnp.arange(0, length, block)

    @jax.checkpoint
    def one_block(start):
        qb = lax.dynamic_slice_in_dim(q, start, block, axis=1)
        scores = jnp.einsum("bqjgk,btjk->bjgqt", qb, k,
                            precision=HIGHEST) / jnp.sqrt(float(dim))
        seen = (start + jnp.arange(block))[:, None] >= jnp.arange(length)
        probs = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), axis=-1)
        return jnp.einsum("bjgqt,btjk->bqjgk", probs, v, precision=HIGHEST)

    out = lax.map(one_block, starts)             # (blocks, B, block, ...)
    out = jnp.moveaxis(out, 0, 1).reshape(batch, length, heads, dim)
    return _mm(out, p["out"]["kernel"], "blhk,hkd->bld")


def _conv(p, x):
    bcu = _mm(x, p["in_proj"]["kernel"], "bld,de->ble")
    width = x.shape[-1]
    gate_b, gate_c, u = (bcu[..., :width], bcu[..., width:2 * width],
                         bcu[..., 2 * width:])
    z = gate_b * u
    taps = p["filter"]                            # (3, width)
    reach = taps.shape[0] - 1
    c = jnp.zeros_like(z)
    for j in range(taps.shape[0]):
        back = reach - j                          # z_{t - back}
        shifted = z if back == 0 else jnp.concatenate(
            [jnp.zeros_like(z[:, :back]), z[:, :-back]], axis=1)
        c = c + taps[j] * shifted
    return _mm(gate_c * c, p["out_proj"]["kernel"], "bld,de->ble")


def _dense_ffn(p, x):
    gate = _mm(x, p["w1"]["kernel"], "bld,df->blf")
    up = _mm(x, p["w3"]["kernel"], "bld,df->blf")
    return _mm(jax.nn.silu(gate) * up, p["w2"]["kernel"], "blf,fd->bld")


def routing(p, x, m):
    """(sel (B, L, k), weights (B, L, k)) over all the published experts."""
    scores = jax.nn.sigmoid(_mm(x, p["router"], "bld,de->ble"))
    _, sel = lax.top_k(scores + lax.stop_gradient(p["expert_bias"]),
                       m["num_experts_per_tok"])
    picked = jnp.take_along_axis(scores, sel, axis=-1)
    weights = picked / (picked.sum(axis=-1, keepdims=True) + 1e-6)
    return sel, weights * m["routed_scaling_factor"]


def _expert_ffn(p, x, m):
    sel, weights = routing(p, x, m)
    w13, w2 = p["experts"]["w13"], p["experts"]["w2"]
    width = w2.shape[1]
    y = jnp.zeros_like(x)
    for e in range(w13.shape[0]):                 # the experts held here
        share = jnp.sum(
            jnp.where(sel == m["first_expert"] + e, weights, 0.0), axis=-1)
        h = _mm(x, w13[e], "bld,df->blf")
        h = jax.nn.silu(h[..., :width]) * h[..., width:]
        y = y + share[..., None] * _mm(h, w2[e], "blf,fd->bld")
    return y


def _layer(p, x, m, kind: str, dense: bool):
    h = _rms(x, p["operator_norm"]["scale"], m["norm_eps"])
    x = x + (_attention(p["attn"], h, m) if kind == "full_attention"
             else _conv(p["conv"], h))
    h = _rms(x, p["ffn_norm"]["scale"], m["norm_eps"])
    return x + (_dense_ffn(p["mlp"], h) if dense
                else _expert_ffn(p["moe"], h, m))


def logits(params, tokens, config: dict):
    m = config["model"]
    table = params["embed"]["embedding"]
    x = table[tokens]
    for i, kind in enumerate(m["layer_types"]):
        dense = i < m["num_dense_layers"]
        x = jax.checkpoint(
            lambda p, x, kind=kind, dense=dense: _layer(p, x, m, kind, dense)
        )(params[f"layer_{i}"], x)
    x = _rms(x, params["final_norm"]["scale"], m["norm_eps"])
    return _mm(x, table, "bld,vd->blv")


def loss(params, batch, config: dict):
    """Cross-entropy summed over the positions that predict / their number."""
    tokens, labels = batch
    logp = jax.nn.log_softmax(logits(params, tokens, config), axis=-1)
    keep = labels != IGNORE
    picked = jnp.take_along_axis(
        logp, jnp.where(keep, labels, 0)[..., None], axis=-1)[..., 0]
    return -jnp.sum(jnp.where(keep, picked, 0.0)) / jnp.maximum(
        jnp.sum(keep), 1)

"""GLM-4.7-Flash (one chip's share of eight-way expert parallelism):
next-token and multi-token-prediction loss and gradients in plain float32
jax.numpy.

Independent of the program: no flax module, no kernel, nothing imported
from ``pytorch_distributed_nn_tpu``. Only the parameter *tree* is shared
(its names are read below), because the comparison needs the same weights
on both sides.

The equations are zai-org's ``glm4_moe_lite`` (config.json of
zai-org/GLM-4.7-Flash; ``x`` is the residual stream, T tokens x 2048;
RMSNorm(x) = x * rsqrt(mean(x^2) + 1e-5) * w):

  MLA     c_q = RMSNorm(x W_dq) (768); [q_nope | q_pe] = c_q W_uq, 20 heads
          of 192 + 64; [c_kv | k_pe] = x W_dkv (512 + 64), c_kv =
          RMSNorm(c_kv); [k_nope | v] = c_kv W_ukv, 20 heads of 192 + 256;
          rotary (rotate-half, theta 1e6) on q_pe and on the one k_pe a
          token, which every head reads; score_h(i, j) = (q_nope . k_nope +
          q_pe . k_pe) / sqrt(256) over j <= i; o = concat_h(softmax v) W_o
  dense   layer 0: x += MLA(RMSNorm(x)); x += W_2(silu(W_1 u) * W_3 u),
          u = RMSNorm(x), width 10,240
  MoE     layers 1-4: x += MLA(RMSNorm(x)); u = RMSNorm(x);
          s = sigmoid(u W_r) over all 64 experts; sel = top4(s + b), b the
          expert bias (it enters only the selection); w = 1.8 s[sel] /
          sum s[sel]; x += SwiGLU_1536^shared(u) + sum_{e in sel, held}
          w_e SwiGLU_1536^e(u)
  MTP     h' = W_eh [RMSNorm_e(Emb(t_{i+1})) ; RMSNorm_h(h_i)] (token 0 past
          the end), h_i the last layer's output before the final norm;
          then one MoE layer (MLA + MoE as above), RMSNorm, and the same
          head
  loss    one mean of cross-entropy over every (position, depth) with a
          target: depth 0 predicts t_{i+1} from RMSNorm_final(h_i) W_h,
          depth 1 t_{i+2} from the MTP module; the head is a matrix of its
          own (tie_word_embeddings false), shared by both depths

Departures, each in the configuration's ``assumed``: rotary channels
rotate-half (the config does not say; interleaved differs by a fixed
permutation of the weights' rotary rows); both depths weigh alike in the
loss (the MTP weight is a training setting config.json does not hold); the
order [embedding ; hidden] in ``eh_proj``; no auxiliary loss.

The share: this chip holds experts ``first_expert .. first_expert +
n_routed_experts - 1`` of the ``router_width`` the router scores. Every
held expert runs on every token here, weighted by w_e (zero where the
token did not select it); what the absent experts would add is left out,
as in the program, and the weights are normalised over all four selected,
absent or not. The shared expert is every chip's alike.

Memory (``check_batch`` 1 x 4096 beside three 2.83 GB parameter trees):
each layer is a ``jax.checkpoint``, attention's scores are made a block of
512 queries at a time (whole, they are 20 x 4096^2 x 4 B = 1.3 GB a
layer), and each depth's logits are made, scored and dropped in turn.
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
# throughout, matmul precision "highest"), on one seeded sequence of 4096
# at seeded weights with the expert biases balanced. Measured on the TPU
# v5e (one chip) at the published widths: the program's readings over
# 14 seeds (seven benchmark runs, seven seeds of
# ``benchmark/tools/glm47_controls.py``); this file's own over those seven
# seeds when computed in bfloat16 (parameters, activations, statistics,
# softmax, router, up to the logits; their log-softmax and mean in
# float32, as in the program: the nearest precision below the
# configuration's); the tool's five planted faults over the same seeds.
#
# What the gradient distance is made of (the tool's ``parts``): the same
# relative error in every part of the model, the dense layer's 0.042 ..
# 0.049 as much as latent attention's 0.041 .. 0.047 or the embedding's,
# the routed experts 0.14 .. 0.16 and the router 0.18 .. 0.24 with 9 % and
# 0.1 % of the distance squared: an error of the backward signal that
# every layer shares, not rounding in one of them. The bfloat16 control
# raises it alike in every part, 1.26 .. 1.34 x the program seed for seed.
TOLERANCE = {
    # program 8.7e-6 .. 6.2e-5 (8.5e-5 with the seeded bias alone);
    # bfloat16 control 1.7e-5 .. 5.7e-5: the loss does not tell them apart
    # once both reduce it in float32. The harness's accepted limit
    # (BERT's and the LFM2 share's), 5.9 x the program's largest reading.
    # The prediction module fed its own target reads up to 1.5e-3.
    "loss_rel": 0.0005,
    # program 1.5e-4 .. 1.02e-3 (1.44e-3 with the seeded bias); bfloat16
    # control 1.0e-4 .. 1.0e-3: the norm does not tell them apart. 2.1 x
    # the program's largest reading; the routed weights summing to 1, not
    # 1.8, read 8.1e-3 .. 0.0128, the shared expert dropped 0.14 .. 0.16.
    "grad_norm_rel": 0.003,
    # program 0.0407 .. 0.0483 (mean 0.0442, sd 0.0024); bfloat16 control
    # 0.0542 .. 0.0628 (mean 0.0574, sd 0.0033). This is the limit the
    # lower precision fails, on all seven seeds: 7.6 % over the program's
    # largest reading (3.3 sd over its mean), 4.2 % under the control's
    # smallest (1.6 sd under its mean). The planted faults: the prediction
    # module fed its own target 0.43 .. 0.44, the rotary key taken per head
    # 0.46 .. 0.47, the routed weights summing to 1 0.130 .. 0.158, the
    # shared expert dropped 0.76 .. 0.78. The expert bias put in the
    # weights as well as the selection reads as the program does (0.0422
    # .. 0.0498): at initialisation the four selected sigmoid scores are
    # nearly equal, and the balanced bias moves their normalised weights
    # by little. The CPU tests catch it at a bias of the scores' order
    # (tests/test_glm47_flash.py); this limit does not.
    "grad_rel_err": 0.052,
}


def depth(m: dict) -> int:
    """Targets a position is trained on: the next token, and one more a
    prediction module."""
    return 1 + m["num_nextn_predict_layers"]


def make_batch(key, n: int, config: dict):
    """``n`` seeded sequences of uniform token ids over the rows held;
    labels ``(n, L, depth)``: ``[..., j]`` is the token 1 + j ahead,
    nothing to predict past the end."""
    length = config["tokens_per_sample"]
    m = config["model"]
    tokens = jax.random.randint(
        key, (n, length), 0, m["vocab_size"]).astype(jnp.int32)
    labels = [jnp.concatenate(
        [tokens[:, 1 + j:], jnp.full((n, 1 + j), IGNORE, jnp.int32)], axis=1)
        for j in range(depth(m))]
    return tokens, jnp.stack(labels, axis=-1)


def _rms(x, w, eps):
    return x * lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def _mm(x, w, spec: str):
    return jnp.einsum(spec, x, w, precision=HIGHEST)


def _rope(x, theta):
    """x (B, L, ..., D): pairs (i, i + D/2) turn by pos * theta^(-2i/D)."""
    length, dim = x.shape[1], x.shape[-1]
    freq = theta ** (-jnp.arange(dim // 2, dtype=jnp.float32) * 2.0 / dim)
    angle = jnp.arange(length, dtype=jnp.float32)[:, None] * freq
    shape = (length,) + (1,) * (x.ndim - 3) + (dim // 2,)
    # in x's precision: float32 tables would lift a lower-precision control
    # back to float32 in every layer
    cos = jnp.cos(angle).reshape(shape).astype(x.dtype)
    sin = jnp.sin(angle).reshape(shape).astype(x.dtype)
    a, b = x[..., : dim // 2], x[..., dim // 2:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def mla(p, x, m):
    """Latent attention of the normed stream ``x`` (B, L, d)."""
    nope, rope = m["qk_nope_head_dim"], m["qk_rope_head_dim"]
    rank, eps = m["kv_lora_rank"], m["rms_norm_eps"]
    c_q = _rms(_mm(x, p["q_a_proj"]["kernel"], "bld,dr->blr"),
               p["q_a_norm"]["scale"], eps)
    q = _mm(c_q, p["q_b_proj"]["kernel"], "blr,rhk->blhk")
    c_kv = _mm(x, p["kv_a_proj"]["kernel"], "bld,dr->blr")
    k_pe = _rope(c_kv[..., rank:], m["rope_theta"])        # (B, L, 64)
    c_kv = _rms(c_kv[..., :rank], p["kv_a_norm"]["scale"], eps)
    kv = _mm(c_kv, p["kv_b_proj"]["kernel"], "blr,rhk->blhk")
    q_nope, q_pe = q[..., :nope], _rope(q[..., nope:], m["rope_theta"])
    k_nope, v = kv[..., :nope], kv[..., nope:]
    batch, length, heads, _ = q.shape
    scale = 1.0 / jnp.sqrt(jnp.asarray(nope + rope, q.dtype))
    block = min(QUERY_BLOCK, length)

    @jax.checkpoint
    def one_block(start):
        qn = lax.dynamic_slice_in_dim(q_nope, start, block, axis=1)
        qp = lax.dynamic_slice_in_dim(q_pe, start, block, axis=1)
        scores = (jnp.einsum("bqhk,bthk->bhqt", qn, k_nope, precision=HIGHEST)
                  + jnp.einsum("bqhk,btk->bhqt", qp, k_pe, precision=HIGHEST)
                  ) * scale
        i = (start + jnp.arange(block))[:, None]
        j = jnp.arange(length)[None, :]
        probs = jax.nn.softmax(jnp.where(j <= i, scores, -jnp.inf), axis=-1)
        return jnp.einsum("bhqt,bthk->bqhk", probs, v, precision=HIGHEST)

    out = lax.map(one_block, jnp.arange(0, length, block))
    out = jnp.moveaxis(out, 0, 1).reshape(batch, length, heads, -1)
    return _mm(out, p["o_proj"]["kernel"], "blhk,hkd->bld")


def swiglu(p, u):
    h = jax.nn.silu(_mm(u, p["w1"]["kernel"], "bld,df->blf")) * _mm(
        u, p["w3"]["kernel"], "bld,df->blf")
    return _mm(h, p["w2"]["kernel"], "blf,fd->bld")


def routing(p, u, m):
    """(sel (B, L, k), weights (B, L, k)) over all the published experts."""
    scores = jax.nn.sigmoid(_mm(u, p["router"], "bld,de->ble"))
    _, sel = lax.top_k(scores + p["expert_bias"], m["num_experts_per_tok"])
    picked = jnp.take_along_axis(scores, sel, axis=-1)
    return sel, m["routed_scaling_factor"] * picked / picked.sum(
        -1, keepdims=True)


def routed(p, u, sel, weights, m):
    """The held experts' part of the routed sum."""
    w13, w2 = p["w13"], p["w2"]
    width = w2.shape[1]
    y = jnp.zeros_like(u)
    for e in range(w13.shape[0]):                 # the experts held here
        share = jnp.sum(
            jnp.where(sel == m["first_expert"] + e, weights, 0.0), axis=-1)
        h = _mm(u, w13[e], "bld,df->blf")
        h = jax.nn.silu(h[..., :width]) * h[..., width:]
        y = y + share[..., None] * _mm(h, w2[e], "blf,fd->bld")
    return y


def moe(p, shared, u, m):
    """Shared expert + the held experts' part, for the normed stream u."""
    sel, weights = routing(p, u, m)
    return swiglu(shared, u) + routed(p["experts"], u, sel, weights, m)


def layer(p, x, m, dense: bool):
    eps = m["rms_norm_eps"]
    x = x + mla(p["mla"], _rms(x, p["input_layernorm"]["scale"], eps), m)
    u = _rms(x, p["post_attention_layernorm"]["scale"], eps)
    if dense:
        return x + swiglu(p["mlp"], u)
    return x + moe(p["moe"], p["shared_expert"], u, m)


def hidden(params, tokens, config: dict) -> list:
    """The normed stream the head reads at each depth: the main model's,
    then each prediction module's."""
    m = config["model"]
    eps, emb = m["rms_norm_eps"], params["embed"]["embedding"]
    x = emb[tokens]
    for i in range(m["num_hidden_layers"]):
        x = jax.checkpoint(
            lambda p, x, d=i < m["first_k_dense_replace"]: layer(p, x, m, d))(
                params[f"layer_{i}"], x)
    out = [_rms(x, params["final_norm"]["scale"], eps)]
    for k in range(1, depth(m)):
        p = params[f"mtp_{k - 1}"]
        ahead = jnp.concatenate(
            [tokens[:, k:], jnp.zeros((tokens.shape[0], k), tokens.dtype)],
            axis=1)
        joined = jnp.concatenate([_rms(emb[ahead], p["enorm"]["scale"], eps),
                                  _rms(x, p["hnorm"]["scale"], eps)], axis=-1)
        x = _mm(joined, p["eh_proj"]["kernel"], "bld,de->ble")
        x = jax.checkpoint(lambda p, x: layer(p, x, m, False))(p["layer"], x)
        out.append(_rms(x, p["norm"]["scale"], eps))
    return out


@jax.checkpoint
def _scored(head, x, labels):
    """(sum of -log p(label), count) of one depth; its logits are dropped.
    The log-softmax and the sum are float32 whatever the logits are, as in
    the program: a control in a lower precision computes everything before
    them in its own."""
    logp = jax.nn.log_softmax(
        _mm(x, head, "bld,dv->blv").astype(jnp.float32), axis=-1)
    keep = labels != IGNORE
    picked = jnp.take_along_axis(
        logp, jnp.where(keep, labels, 0)[..., None], axis=-1)[..., 0]
    return -jnp.sum(jnp.where(keep, picked, 0.0)), jnp.sum(keep)


def loss(params, batch, config: dict):
    """Cross-entropy summed over every (position, depth) with a target /
    their number."""
    tokens, labels = batch
    total, count = 0.0, 0
    for j, x in enumerate(hidden(params, tokens, config)):
        s, n = _scored(params["lm_head"]["kernel"], x, labels[..., j])
        total, count = total + s, count + n
    return total / jnp.maximum(count, 1)

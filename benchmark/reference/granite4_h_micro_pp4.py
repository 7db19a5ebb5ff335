"""Granite-4.0-H-Micro (one chip's share of pipeline stage 1 of four):
next-token loss and gradients in plain float32 jax.numpy.

Independent of the program: no flax module, no kernel, nothing imported
from ``pytorch_distributed_nn_tpu``. Only the parameter *tree* is shared
(its names are read below), because the comparison needs the same weights
on both sides.

The equations are ibm-granite's ``granitemoehybrid`` with no routed
experts (config.json of ibm-granite/granite-4.0-h-micro; ``x`` is the
residual stream, T tokens x 2048; RMSNorm(x) = x * rsqrt(mean(x^2) +
1e-5) * w; r = residual_multiplier 0.22):

  embed   x = 12 Emb(t)                               (embedding_multiplier)
  layer   x += r Mixer(RMSNorm(x)); x += r W_2(silu(W_1 u) * W_3 u),
          u = RMSNorm(x), width 8192; Mixer by layer_types:
  mamba   [z | xBC | dt] = u W_in (4096 / 4352 / 64); xBC = silu(conv4(xBC)
          + b), depthwise and causal; [x | B | C] = split(xBC, 4096 / 128 /
          128), x as 64 heads of 64; dt = softplus(dt + dt_bias); A =
          -exp(A_log); per head the recurrence itself, position by position:
              s_t = exp(dt_t A) s_{t-1} + dt_t x_t B_t^T,  y_t = s_t C_t
          (s a 64 x 128 state); y += D x; out = RMSNorm(y * silu(z)) W_out,
          the norm over all 4096 channels (one group), gated before it
  attn    32 query heads of 64 over 8 KV heads, no positions, no bias,
          softmax of q.k / 64 (attention_multiplier) over j <= i
  head    logits = RMSNorm_final(x) Emb^T / 8             (logits_scaling)
  loss    mean cross-entropy over every position with a next token

The state-space layer here is the recurrence, never the chunked form the
program computes: the comparison checks the duality, not only the
arithmetic.

Memory (``check_batch`` 1 x 4096 beside three 3.09 GB parameter trees):
each layer is a ``jax.checkpoint``; the recurrence runs in blocks of
``SCAN_BLOCK`` positions, each a ``jax.checkpoint``, so its gradient keeps
one 2 MB state a block and one block's steps at a time; attention's scores
are made a block of ``QUERY_BLOCK`` queries at a time (whole, they are 32 x
4096^2 x 4 B = 2.1 GB).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

IGNORE = -1
HIGHEST = lax.Precision.HIGHEST
QUERY_BLOCK = 512
SCAN_BLOCK = 64

# Agreement the comparison demands between the program (bfloat16 matmuls
# and activations, residual stream included, the scan's matrix-unit
# operands bfloat16; float32 parameters, RMSNorm statistics, softmax, the
# scan's state, cumsums and decays) and this file (float32 throughout,
# matmul precision "highest"), on one seeded sequence of 4096 at seeded
# weights. Measured on the TPU v5e (one chip) at the published widths: the
# program's readings over 9 seeds (six benchmark runs, three seeds of
# ``benchmark/tools/granite4_controls.py``); this file's own over those
# three seeds when computed in bfloat16 (parameters, activations, the
# recurrence's state, statistics, softmax, up to the logits; their
# log-softmax and mean in float32, as in the program: the nearest precision
# below the configuration's); the tool's six planted faults over the same
# seeds. The program's gradient distance is the same in every part of the
# model (0.019 attention, 0.025 .. 0.026 everywhere else, the scan's own
# parameters included): an error of the backward signal that every layer
# shares, from the bfloat16 activations. The bfloat16 control raises it
# alike in every part, 1.16 .. 1.21 x the program seed for seed.
TOLERANCE = {
    # program 1.0e-7 .. 5.6e-6; bfloat16 control 1.0e-6 .. 3.4e-6: the loss
    # does not tell them apart once both reduce it in float32. The
    # harness's accepted limit (BERT's, the LFM2 and GLM shares'), 90 x the
    # program's largest reading.
    "loss_rel": 0.0005,
    # program 4.7e-4 .. 5.7e-4; bfloat16 control 4.3e-4 .. 5.0e-4: the
    # norm does not tell them apart. 5.3 x the program's largest reading;
    # the planted state reset at each chunk reads 4.1e-3 .. 5.2e-3, the
    # gate after the norm 0.080 .. 0.082, the residual multiplier left out
    # 0.29, D left out 0.99 .. 1.14.
    "grad_norm_rel": 0.003,
    # program 0.02534 .. 0.02563 (sd 0.0001); bfloat16 control 0.02955 ..
    # 0.03081. This is the limit the lower precision fails, on all three
    # seeds: 7.3 % over the program's largest reading, 6.9 % under the
    # control's smallest. The planted faults: attention at 1/sqrt(64) in
    # place of 1/64 0.0317 .. 0.0319 (at initialisation the NoPE scores are
    # small and the softmax near flat at either scale), the state reset at
    # each chunk boundary 0.136 .. 0.191, the gate after the norm 1.013,
    # the convolution one position ahead 1.39, the residual multiplier left
    # out 1.39, D left out 2.20 .. 2.35.
    "grad_rel_err": 0.0275,
}


def make_batch(key, n: int, config: dict):
    """``n`` seeded sequences of uniform token ids over the rows held;
    labels the tokens shifted by one, nothing to predict at the end."""
    length = config["tokens_per_sample"]
    tokens = jax.random.randint(
        key, (n, length), 0, config["model"]["vocab_size"]).astype(jnp.int32)
    labels = jnp.concatenate(
        [tokens[:, 1:], jnp.full((n, 1), IGNORE, jnp.int32)], axis=1)
    return tokens, labels


def _rms(x, w, eps):
    return x * lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def _mm(x, w, spec: str):
    return jnp.einsum(spec, x, w, precision=HIGHEST)


def _blocks(v, block: int):
    """(b, L, ...) -> (L / block, block, b, ...), time-major."""
    v = jnp.moveaxis(v, 1, 0)
    return v.reshape((v.shape[0] // block, block) + v.shape[1:])


def recurrence(x, dt, A, B, C):
    """y_t = s_t C_t, s_t = exp(dt_t A) s_{t-1} + dt_t x_t B_t^T from s = 0,
    one position at a time. x (b, L, H, P), dt (b, L, H), A (H,), B and C
    (b, L, N) -> y (b, L, H, P)."""
    batch, length, heads, p = x.shape
    block = min(SCAN_BLOCK, length)

    def step(s, t):
        x_t, dt_t, b_t, c_t = t
        s = (jnp.exp(dt_t * A)[..., None, None] * s
             + (dt_t[..., None, None] * x_t[..., None]) * b_t[:, None, None])
        return s, jnp.einsum("bhpn,bn->bhp", s, c_t, precision=HIGHEST)

    @jax.checkpoint
    def run(s, seg):
        return lax.scan(step, s, seg)

    s0 = jnp.zeros((batch, heads, p, B.shape[-1]), x.dtype)
    _, y = lax.scan(run, s0, tuple(_blocks(v, block) for v in (x, dt, B, C)))
    return jnp.moveaxis(y.reshape((length,) + y.shape[2:]), 0, 1)


def causal_conv(v, w, b):
    """Depthwise causal convolution along the sequence: out[t] =
    sum_j w[j] v[t - (K-1) + j] + b, zeros before the start."""
    k, length = w.shape[0], v.shape[1]
    padded = jnp.pad(v, ((0, 0), (k - 1, 0), (0, 0)))
    return sum(padded[:, j:j + length] * w[j] for j in range(k)) + b


def mamba(p, u, m):
    """The Mamba-2 mixer of the normed stream ``u`` (b, L, d)."""
    heads, dh, n = m["mamba_n_heads"], m["mamba_d_head"], m["mamba_d_state"]
    d_in = heads * dh
    batch, length, _ = u.shape
    zxbcdt = _mm(u, p["in_proj"]["kernel"], "bld,de->ble")
    z, xbc, dt = (zxbcdt[..., :d_in], zxbcdt[..., d_in:-heads],
                  zxbcdt[..., -heads:])
    xbc = jax.nn.silu(causal_conv(xbc, p["conv_weight"], p["conv_bias"]))
    x, b, c = xbc[..., :d_in], xbc[..., d_in:d_in + n], xbc[..., d_in + n:]
    x = x.reshape(batch, length, heads, dh)
    dt = jax.nn.softplus(dt + p["dt_bias"])
    y = recurrence(x, dt, -jnp.exp(p["A_log"]), b, c) + p["D"][:, None] * x
    y = y.reshape(batch, length, d_in) * jax.nn.silu(z)
    y = _rms(y, p["norm"]["scale"], m["rms_norm_eps"])
    return _mm(y, p["out_proj"]["kernel"], "blf,fd->bld")


def attention(p, u, m):
    """NoPE grouped-query attention of the normed stream ``u``."""
    q = _mm(u, p["q_proj"]["kernel"], "bld,dhk->blhk")
    k = _mm(u, p["k_proj"]["kernel"], "bld,dhk->blhk")
    v = _mm(u, p["v_proj"]["kernel"], "bld,dhk->blhk")
    groups = q.shape[2] // k.shape[2]
    k, v = jnp.repeat(k, groups, axis=2), jnp.repeat(v, groups, axis=2)
    batch, length, heads, _ = q.shape
    block = min(QUERY_BLOCK, length)

    @jax.checkpoint
    def one_block(start):
        qb = lax.dynamic_slice_in_dim(q, start, block, axis=1)
        scores = jnp.einsum("bqhk,bthk->bhqt", qb, k,
                            precision=HIGHEST) * m["attention_multiplier"]
        i = (start + jnp.arange(block))[:, None]
        j = jnp.arange(length)[None, :]
        probs = jax.nn.softmax(jnp.where(j <= i, scores, -jnp.inf), axis=-1)
        return jnp.einsum("bhqt,bthk->bqhk", probs, v, precision=HIGHEST)

    out = lax.map(one_block, jnp.arange(0, length, block))
    out = jnp.moveaxis(out, 0, 1).reshape(batch, length, heads, -1)
    return _mm(out, p["o_proj"]["kernel"], "blhk,hkd->bld")


def mlp(p, u):
    h = jax.nn.silu(_mm(u, p["w1"]["kernel"], "bld,df->blf")) * _mm(
        u, p["w3"]["kernel"], "bld,df->blf")
    return _mm(h, p["w2"]["kernel"], "blf,fd->bld")


def layer(p, x, m, kind: str):
    eps, r = m["rms_norm_eps"], m["residual_multiplier"]
    u = _rms(x, p["input_layernorm"]["scale"], eps)
    mixed = (mamba(p["mamba"], u, m) if kind == "mamba"
             else attention(p["self_attn"], u, m))
    x = x + r * mixed
    return x + r * mlp(p["shared_mlp"],
                       _rms(x, p["post_attention_layernorm"]["scale"], eps))


def hidden(params, tokens, config: dict):
    """The normed stream the head reads."""
    m = config["model"]
    x = params["embed"]["embedding"][tokens] * m["embedding_multiplier"]
    for i, kind in enumerate(m["layer_types"]):
        x = jax.checkpoint(lambda p, x, kind=kind: layer(p, x, m, kind))(
            params[f"layer_{i}"], x)
    return _rms(x, params["final_norm"]["scale"], m["rms_norm_eps"])


def loss(params, batch, config: dict):
    """Mean cross-entropy over the positions with a target. The
    log-softmax and the mean are float32 whatever the logits are, as in the
    program: a control in a lower precision computes everything before
    them in its own."""
    tokens, labels = batch
    m = config["model"]
    logits = _mm(hidden(params, tokens, config),
                 params["embed"]["embedding"], "bld,vd->blv")
    logp = jax.nn.log_softmax(
        logits.astype(jnp.float32) / m["logits_scaling"], axis=-1)
    keep = labels != IGNORE
    picked = jnp.take_along_axis(
        logp, jnp.where(keep, labels, 0)[..., None], axis=-1)[..., 0]
    return -jnp.sum(jnp.where(keep, picked, 0.0)) / jnp.maximum(
        jnp.sum(keep), 1)

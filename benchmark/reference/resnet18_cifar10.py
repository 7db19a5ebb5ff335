"""ResNet-18 (CIFAR variant) loss and gradients in plain float32 jax.numpy.

Independent of the program: no flax module, no kernel, nothing imported
from ``pytorch_distributed_nn_tpu``. Only the parameter *tree* is shared
(its names are read below), because the comparison needs the same
weights on both sides.

Follows He et al. 2015 (arXiv:1512.03385) as the reference system builds
it for 32x32 inputs (src/model_ops/resnet.py there). Departures from the
ImageNet description in the paper, all inherited from that system:
  - 3x3 stride-1 stem with 64 filters and no max-pool (the paper: 7x7
    stride 2, then a 3x3 max-pool);
  - 1x1 strided projection shortcuts where the shape changes (option B);
  - global average pooling over the final 4x4 map, then a 10-way linear
    layer.
Batch normalisation is in training mode (batch statistics, biased
variance, eps 1e-5), which is what the train step's loss path runs.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

# Agreement the comparison demands between the program (bfloat16 compute,
# float32 parameters and batch statistics) and this file (float32
# throughout, matmul precision "highest"), on 64 seeded images at seeded
# weights. Each bound is about five times the largest value measured on
# the TPU v5e over six seeds (my chip runs, PR 22; PERF.md has the table).
#
# NOT A PRECISION GUARD. These bounds catch a wrong shortcut, stride,
# normalisation or a lost gradient. They do not catch a kernel or a
# fusion that drops precision: the gradient's direction is checked to 0.5
# against a measured 0.24 .. 0.31, i.e. hardly at all, and loss and norm
# average rounding away. A later perf_opt PR that computes in fewer bits
# would still read ``correct: true``. Closing that needs a reference that
# rounds matmul inputs to bf16 where the configuration does, or a
# per-layer comparison — the next ``benchmark`` PR's first item (PERF.md,
# Open questions); only that kind of PR may change this file.
TOLERANCE = {
    # measured 2e-4 .. 9e-4: bf16 rounds every activation to 8 bits of
    # mantissa, and 20 normalised layers average most of that out of the
    # loss. A wrong shortcut, stride or normalisation moves it by percent.
    "loss_rel": 0.005,
    # measured 3e-5 .. 2e-3: the norm sums squares over 11 M parameters,
    # so independent roundings cancel out of it. Batch statistics kept in
    # a lower precision, or a layer whose gradient is lost, bias it.
    "grad_norm_rel": 0.01,
    # measured 0.27 .. 0.29, in float32 compute 0.002: at random weights
    # the *direction* of a ReLU + batch-norm network's gradient is this
    # sensitive to bf16 rounding (the same on CPU, on structured inputs
    # and at batch 256), so this number can only catch what is grossly
    # wrong — a gradient that is missing or belongs to another layer
    # gives 0.7 and more.
    "grad_rel_err": 0.5,
}


def make_batch(key, n: int, config: dict):
    """``n`` seeded images as the loader hands them over (normalised
    float32, NHWC) and their labels."""
    kx, ky = jax.random.split(key)
    h, w, c = config["model"]["image"]
    x = jax.random.normal(kx, (n, h, w, c), jnp.float32)
    y = jax.random.randint(ky, (n,), 0, config["model"]["num_classes"])
    return x, y.astype(jnp.int32)


def _conv(x, kernel, stride: int):
    return lax.conv_general_dilated(
        x, kernel, (stride, stride), "SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
        precision=lax.Precision.HIGHEST,
    )


def _batch_norm(x, p, eps: float = 1e-5):
    mean = jnp.mean(x, axis=(0, 1, 2))
    var = jnp.mean(jnp.square(x - mean), axis=(0, 1, 2))
    return (x - mean) * lax.rsqrt(var + eps) * p["scale"] + p["bias"]


def _block(p, x, stride: int):
    y = _conv(x, p["Conv_0"]["kernel"], stride)
    y = jax.nn.relu(_batch_norm(y, p["BatchNorm_0"]))
    y = _batch_norm(_conv(y, p["Conv_1"]["kernel"], 1), p["BatchNorm_1"])
    if "Conv_2" in p:  # projection shortcut
        x = _batch_norm(_conv(x, p["Conv_2"]["kernel"], stride),
                        p["BatchNorm_2"])
    return jax.nn.relu(y + x)


def logits(params, x, config: dict):
    m = config["model"]
    x = _conv(x, params["conv_stem"]["kernel"], 1)
    x = jax.nn.relu(_batch_norm(x, params["bn_stem"]))
    for s, blocks in enumerate(m["blocks_per_stage"]):
        for b in range(blocks):
            stride = 2 if (s > 0 and b == 0) else 1
            x = _block(params[f"stage{s + 1}_block{b}"], x, stride)
    x = jnp.mean(x, axis=(1, 2))
    c = params["classifier"]
    return jnp.dot(x, c["kernel"], precision=lax.Precision.HIGHEST) + c["bias"]


def loss(params, batch, config: dict):
    """Mean softmax cross-entropy over the batch."""
    x, y = batch
    logp = jax.nn.log_softmax(logits(params, x, config), axis=-1)
    return -jnp.mean(jnp.take_along_axis(logp, y[:, None], axis=-1))

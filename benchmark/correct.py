"""Is the program computing the configuration it claims? Decided here,
after the measured window and outside ``setup_s``, on the device the cell
runs on.

The program side goes through ``build_train_step`` — the shard_map, the
model in the cell's dtype with its kernels, the cell's loss function and
gradient sync — with one substitution: an optimizer that applies nothing
and keeps the synced gradient as its state, so the step hands back the
loss and the exact gradient instead of new weights. The reference side
is the configuration's plain float32 ``jax.numpy`` file under matmul
precision "highest". Same seeded weights (the trainer's own initial
parameters), same seeded batch, three numbers compared against the
tolerances written in the reference file: the loss, the global gradient
norm, and the relative L2 distance between the two gradient vectors.
Those tolerances catch a wrong term, count, mask or kernel. They do NOT
guard precision: the bf16 rounding of matmul inputs the configuration
asks for is as large as what an all-bf16 LayerNorm or softmax adds (each
reference file's ``TOLERANCE`` block has the measurements).

On more than one chip the same batch also goes through a one-device mesh,
and the two program results must agree with each other.
"""

from __future__ import annotations

import math


def _capture_optimizer():
    """(updates = 0, state = the gradient it was given)."""
    import jax
    import jax.numpy as jnp
    import optax

    def init(params):
        return jax.tree.map(jnp.zeros_like, params)

    def update(grads, state, params=None):
        del state, params
        return jax.tree.map(jnp.zeros_like, grads), grads

    return optax.GradientTransformation(init, update)


def _program_loss_and_grads(trainer, model, mesh, params, batch_stats, batch):
    """One pass through the program's train step on ``mesh``."""
    import jax
    import jax.numpy as jnp

    from pytorch_distributed_nn_tpu.parallel import (
        batch_sharding,
        replicated_sharding,
    )
    from pytorch_distributed_nn_tpu.training.train_step import (
        TrainState,
        build_train_step,
    )

    c = trainer.config
    fns = {}
    if trainer.is_text:
        # the trainer's own wiring of the MLM loss (training/trainer.py)
        from pytorch_distributed_nn_tpu.ops.metrics import (
            make_global_masked_cross_entropy,
            make_global_mlm_metrics,
        )
        from pytorch_distributed_nn_tpu.parallel.mesh import DATA_AXIS

        fns = {"loss_fn": make_global_masked_cross_entropy(DATA_AXIS),
               "metrics_fn": make_global_mlm_metrics(DATA_AXIS)}
    capture = _capture_optimizer()
    step = build_train_step(
        model, capture, trainer.grad_sync, mesh,
        bn_stats_sync=c.bn_stats_sync, donate=False, **fns,
    )
    rep = replicated_sharding(mesh)
    params = jax.device_put(params, rep)
    state = TrainState(
        step=jax.device_put(jnp.zeros([], jnp.int32), rep),
        params=params,
        opt_state=jax.jit(capture.init, out_shardings=rep)(params),
        batch_stats=jax.device_put(batch_stats, rep),
        ef_state=None,
    )
    batch = jax.device_put(batch, batch_sharding(mesh))
    new_state, metrics = step(state, batch, jax.random.PRNGKey(0))
    return metrics["loss"], new_state.opt_state


def _compare(a, b):
    """(|a|, |b|, |a - b|) over two gradient trees, in float32 — one
    program, not three small ones per leaf."""
    import jax
    import jax.numpy as jnp

    def norm(tree):
        return jnp.sqrt(sum(jnp.sum(jnp.square(x.astype(jnp.float32)))
                            for x in jax.tree.leaves(tree)))

    return norm(a), norm(b), norm(jax.tree.map(lambda x, y: x - y, a, b))


def check(trainer, cell, seed: int, params, batch_stats) -> dict:
    """Compare program and reference on ``params`` / ``batch_stats`` (the
    driver hands over the weights the run began with: the tolerances were
    measured at initialisation). Returns the measured numbers, the
    tolerances and ``ok``."""
    import dataclasses
    import time

    import jax

    from pytorch_distributed_nn_tpu.parallel import make_mesh

    config = cell.config
    ref = cell.module("reference")
    n = config["check_batch"]
    batch = ref.make_batch(jax.random.PRNGKey(seed + 7), n, config)
    model = trainer.model
    if trainer.is_text:
        # the reference cannot redraw flax's dropout mask (see its header)
        model = model.clone(config=dataclasses.replace(
            model.config, dropout_rate=0.0))
    if trainer.state.ef_state is not None:
        raise ValueError("error-feedback state is not a thing the "
                         "reference comparison knows how to carry")

    t0 = time.monotonic()
    loss_p, grads_p = _program_loss_and_grads(
        trainer, model, trainer.mesh, params, batch_stats, batch)
    jax.block_until_ready(grads_p)
    t1 = time.monotonic()

    # the training state and what its step set aside are still on the
    # chips: keep one gradient tree at a time beside the one compared with
    one = jax.devices()[0]
    put = lambda tree: jax.device_put(tree, one)  # noqa: E731
    grads_here = put(grads_p)
    del grads_p
    with jax.default_matmul_precision("highest"):
        loss_r, grads_r = jax.jit(
            jax.value_and_grad(lambda p, b: ref.loss(p, b, config))
        )(put(params), put(batch))
    compare = jax.jit(_compare)
    gn_p, gn_r, dist = (float(v) for v in compare(grads_here, grads_r))
    del grads_r
    out = {
        "batch": n,
        "seconds": {"program": t1 - t0, "reference": time.monotonic() - t1},
        "loss_program": float(loss_p),
        "loss_reference": float(loss_r),
        "grad_norm_program": gn_p,
        "grad_norm_reference": gn_r,
        "grad_rel_err": dist / gn_r,
    }
    out["loss_rel"] = abs(out["loss_program"] - out["loss_reference"]) / abs(
        out["loss_reference"])
    out["grad_norm_rel"] = abs(
        out["grad_norm_program"] - out["grad_norm_reference"]
    ) / out["grad_norm_reference"]
    tol = dict(ref.TOLERANCE)
    out["tolerance"] = tol
    ok = all(out[k] <= tol[k] for k in tol)

    if trainer.n_workers > 1:
        mesh1 = make_mesh(1, devices=[one])
        loss_1, grads_1 = _program_loss_and_grads(
            trainer, model, mesh1, params, batch_stats, batch)
        _, gn_1, dist = (float(v) for v in compare(grads_here, put(grads_1)))
        out["one_device"] = {
            "loss": float(loss_1),
            "grad_norm": gn_1,
            "loss_rel": abs(float(loss_1) - out["loss_program"])
            / abs(float(loss_1)),
            "grad_rel_err": dist / gn_1,
        }
        ok = ok and out["one_device"]["loss_rel"] <= tol["loss_rel"] and (
            out["one_device"]["grad_rel_err"] <= tol["grad_rel_err"])
    finite = all(math.isfinite(out[k]) for k in (
        "loss_program", "grad_norm_program", "grad_rel_err"))
    out["ok"] = bool(ok and finite)
    return out

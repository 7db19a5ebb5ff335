"""The SPMD train step — the whole PS cycle as one compiled function.

The reference's distributed step spans four processes and ~40 MPI calls:
master broadcasts the step id and per-layer weights, workers forward/backward
and isend per-layer gradients, master Waitany-drains L×P messages, averages,
and applies SGD (reference: src/sync_replicas_master_nn.py:133-197 +
src/distributed_worker.py:104-180). Here the entire cycle is ONE jitted
SPMD function over a `jax.sharding.Mesh`: weights live on-chip (no weight
broadcast — that's what "the PS role disappears" means), each data-parallel
replica computes gradients on its batch shard, the gradient-sync stage
averages over ICI, and every replica applies the identical optimizer update.
XLA's latency-hiding scheduler overlaps the psum with backward — subsuming
the reference's hand-written split-backward overlap
(src/model_ops/resnet_split.py:365-501).

BatchNorm running stats: the reference deliberately never syncs them across
workers (src/distributed_worker.py:245); checkpoints carry whichever
worker's stats won the NFS write race (src/distributed_worker.py:304-307).
We default to the principled fix (`bn_stats_sync="mean"` — pmean over
replicas) and offer `"rank0"` for closest-to-reference behavior.
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
import optax
from flax import struct
from jax import lax, shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from pytorch_distributed_nn_tpu.ops.metrics import (
    COUNTERS,
    cross_entropy_loss,
    topk_accuracy,
)
from pytorch_distributed_nn_tpu.parallel.grad_sync import GradSync
from pytorch_distributed_nn_tpu.parallel.mesh import DATA_AXIS, MODEL_AXIS


class TrainState(struct.PyTreeNode):
    """Training state: the global model the reference PS held.

    Everything is replicated across the mesh except ``ef_state`` — the
    per-replica error-feedback residuals for topk compression — which is
    stored with a leading replica axis and sharded over the data axis
    (``None`` when compression is off).
    """

    step: jnp.ndarray
    params: Any
    opt_state: Any
    batch_stats: Any
    ef_state: Any


def create_train_state(
    model,
    optimizer: optax.GradientTransformation,
    grad_sync: GradSync,
    rng: jax.Array,
    input_shape,
    num_replicas: int = 1,
    input_dtype=jnp.float32,
) -> TrainState:
    """Initialize params/opt-state/BN-stats.

    ``input_shape`` is per-example: (H, W, C) for the CNN zoo, (L,) with
    ``input_dtype=jnp.int32`` for the transformer family. Any flax
    partitioning boxes from logically-annotated params are stripped — this
    path keeps params replicated; the sharded path is training/spmd.py.
    """
    from pytorch_distributed_nn_tpu.parallel.partitioning import unbox

    x = jnp.zeros((1, *input_shape), input_dtype)
    variables = unbox(
        model.init({"params": rng, "dropout": rng}, x, train=False)
    )
    params = variables["params"]
    ef = grad_sync.init_state(params)
    if ef is not None:
        # leading replica axis, sharded over the data mesh axis in the step
        ef = jax.tree.map(
            lambda z: jnp.zeros((num_replicas, *z.shape), z.dtype), ef
        )
    return TrainState(
        step=jnp.zeros([], jnp.int32),
        params=params,
        opt_state=optimizer.init(params),
        batch_stats=variables.get("batch_stats", {}),
        ef_state=ef,
    )


def param_count(tree) -> int:
    """Total elements across the leaves of ``tree`` — the model-size figure
    recorded in every run manifest (observability/core.run_manifest)."""
    import numpy as np

    return int(sum(np.size(leaf) for leaf in jax.tree.leaves(tree)))


def tree_bytes(tree) -> int:
    """Total bytes across the leaves of ``tree`` (dtype-aware) — feeds the
    manifest's ``param_bytes`` and the grad-sync traffic gauges."""
    import numpy as np

    return int(
        sum(
            np.size(leaf) * np.dtype(
                getattr(leaf, "dtype", np.float32)
            ).itemsize
            for leaf in jax.tree.leaves(tree)
        )
    )


def _classification_metrics(logits, labels):
    acc1, acc5 = topk_accuracy(logits, labels, (1, 5))
    return {"acc1": acc1, "acc5": acc5}


def _counter_metrics(mutated) -> dict:
    """{name: sum over every module that sowed ``name``} of the
    ``counters`` collection; empty for a model that sows none."""
    out: dict = {}
    for path, leaf in jax.tree_util.tree_leaves_with_path(
        mutated.get(COUNTERS, {})
    ):
        name = next(
            k.key for k in reversed(path)
            if isinstance(k, jax.tree_util.DictKey)
        )
        out[name] = out.get(name, 0.0) + leaf
    return out


def _bn_reduce(batch_stats, mode: str, axis_name: str):
    if not batch_stats:
        return batch_stats
    if mode == "mean":
        return lax.pmean(batch_stats, axis_name)
    if mode == "rank0":
        keep = (lax.axis_index(axis_name) == 0).astype(jnp.float32)
        return jax.tree.map(lambda s: lax.psum(s * keep, axis_name), batch_stats)
    raise ValueError(f"unknown bn_stats_sync {mode!r}")


def build_train_step(
    model,
    optimizer: optax.GradientTransformation,
    grad_sync: GradSync,
    mesh: Mesh,
    bn_stats_sync: str = "mean",
    loss_fn: Callable = cross_entropy_loss,
    metrics_fn: Optional[Callable] = None,
    donate: bool = True,
    grad_accum: int = 1,
    pair_accum_fn: Optional[Callable] = None,
    nonfinite_guard: bool = False,
):
    """Compile the full distributed training step.

    Returns ``step_fn(state, batch, rng) -> (state, metrics)`` where
    ``batch = (images, labels)`` is globally-shaped and sharded over the
    data axis, ``state`` is replicated, and ``metrics`` contains scalar
    ``loss`` / ``acc1`` / ``acc5`` averaged over the global batch.

    ``grad_accum=K`` splits each replica's shard into K microbatches and
    runs them through a ``lax.scan`` that accumulates gradients before
    the ONE gradient sync + optimizer update — activation memory drops
    K× while the effective batch (and, for equal-size microbatches, the
    averaged loss/metrics) is unchanged. EXACT only when ``loss_fn``
    weights every sample uniformly (the image CE path — pinned by
    test_grad_accum_matches_full_batch). Losses normalized by a
    data-dependent count (the global-masked-mean MLM loss) need
    ``pair_accum_fn`` instead: a function ``(logits, labels) -> sums``
    returning UNNORMALIZED reductions with a ``"loss_sum"`` (the
    differentiated objective) and a ``"count"`` key (plus any metric
    sums, e.g. `ops.metrics.mlm_sums`). The scan then accumulates
    ``(Σ ∂loss_sum, Σ count)`` pairs and normalizes ONCE by the
    cross-replica mean count at the sync — gradients are linear in
    sums, so this reproduces the global masked mean exactly (pinned by
    test_mlm_grad_accum_matches_full_batch). BatchNorm statistics update
    sequentially per microbatch (the same semantics K small steps would
    have produced); dropout draws a distinct key per microbatch. The
    reference had no equivalent — its per-worker batch WAS the memory
    ceiling.
    """
    axis = grad_sync.config.axis_name
    if metrics_fn is None:
        metrics_fn = _classification_metrics
    if grad_accum < 1:
        raise ValueError(f"grad_accum must be >= 1, got {grad_accum}")

    def per_replica(state: TrainState, images, labels, rng):
        rank = lax.axis_index(axis)
        # distinct dropout randomness per replica & step; the sync rng must be
        # IDENTICAL across replicas (arrival permutation) so it is not folded
        # with the rank.
        dropout_rng = jax.random.fold_in(jax.random.fold_in(rng, rank), state.step)
        sync_rng = jax.random.fold_in(rng, state.step)

        def forward(params, stats, images, labels, drng):
            out, mutated = model.apply(
                {"params": params, "batch_stats": stats},
                images,
                train=True,
                mutable=["batch_stats", COUNTERS],
                rngs={"dropout": drng},
            )
            return loss_fn(out, labels), (
                out, mutated.get("batch_stats", {}), _counter_metrics(mutated)
            )

        if grad_accum == 1:
            (loss, (logits, new_stats, counted)), grads = jax.value_and_grad(
                forward, has_aux=True
            )(state.params, state.batch_stats, images, labels, dropout_rng)
            metrics = {"loss": loss, **metrics_fn(logits, labels),
                       **jax.lax.stop_gradient(counted)}
            return _finish(state, grads, new_stats, metrics, sync_rng)

        n = images.shape[0]
        if n % grad_accum:
            raise ValueError(
                f"per-replica batch {n} not divisible by "
                f"grad_accum={grad_accum}"
            )
        mb_images = images.reshape(
            (grad_accum, n // grad_accum) + images.shape[1:]
        )
        mb_labels = labels.reshape(
            (grad_accum, n // grad_accum) + labels.shape[1:]
        )
        if pair_accum_fn is not None:
            # Exact count-normalized (MLM) accumulation: differentiate the
            # raw sum objective per microbatch, accumulate gradient-sums
            # and count-sums, divide once by the cross-replica mean count.
            # pmean-of-grads then equals global-Σxent / global-count — the
            # identical math the grad_accum=1 global-masked-mean path does.
            def forward_sum(params, stats, images, labels, drng):
                out, mutated = model.apply(
                    {"params": params, "batch_stats": stats},
                    images,
                    train=True,
                    mutable=["batch_stats"],
                    rngs={"dropout": drng},
                )
                sums = pair_accum_fn(out, labels)
                return sums["loss_sum"], (
                    sums, mutated.get("batch_stats", {})
                )

            def body(carry, mb):
                stats, gsum = carry
                im, lb, i = mb
                (_, (sums, stats_new)), g = jax.value_and_grad(
                    forward_sum, has_aux=True
                )(state.params, stats, im, lb,
                  jax.random.fold_in(dropout_rng, i))
                gsum = jax.tree.map(jnp.add, gsum, g)
                return (stats_new, gsum), sums

            gz = jax.tree.map(jnp.zeros_like, state.params)
            (new_stats, gsum), stacked = lax.scan(
                body, (state.batch_stats, gz),
                (mb_images, mb_labels, jnp.arange(grad_accum)),
            )
            ssum = jax.tree.map(lambda x: x.sum(0), stacked)
            # mean count over replicas: pmean-of-grads × this divisor ==
            # global sum / global count (same divisor on every replica).
            denom = jnp.maximum(lax.pmean(ssum["count"], axis), 1.0)
            grads = jax.tree.map(lambda g: g / denom, gsum)
            metrics = {
                "loss": ssum["loss_sum"] / denom,
                **{
                    k: v / denom
                    for k, v in ssum.items()
                    if k not in ("loss_sum", "count")
                },
            }
        else:
            def body(carry, mb):
                stats, gsum = carry
                im, lb, i = mb
                # (a model's counters are not carried through microbatches)
                (loss, (logits, stats_new, _)), g = jax.value_and_grad(
                    forward, has_aux=True
                )(state.params, stats, im, lb,
                  jax.random.fold_in(dropout_rng, i))
                m = {"loss": loss, **metrics_fn(logits, lb)}
                gsum = jax.tree.map(jnp.add, gsum, g)
                return (stats_new, gsum), m

            zeros = jax.tree.map(jnp.zeros_like, state.params)
            (new_stats, gsum), ms = lax.scan(
                body, (state.batch_stats, zeros),
                (mb_images, mb_labels, jnp.arange(grad_accum)),
            )
            grads = jax.tree.map(lambda g: g / grad_accum, gsum)
            metrics = jax.tree.map(lambda x: x.mean(), ms)

        return _finish(state, grads, new_stats, metrics, sync_rng)

    def _finish(state, grads, new_stats, metrics, sync_rng):
        """Shared sync + optimizer-update + metric-pmean tail."""
        ef_local = (
            jax.tree.map(lambda x: x[0], state.ef_state)
            if state.ef_state is not None
            else None
        )
        # step is 1-indexed here (state.step counts COMPLETED steps) so
        # the straggler simulator's delay@N entries line up with the
        # trainer's displayed step numbers and the FaultPlan grammar.
        synced, new_ef = grad_sync(grads, ef_local, sync_rng,
                                   step=state.step + 1)
        metrics = {**metrics, **grad_sync.pop_report()}
        if new_ef is not None:
            new_ef = jax.tree.map(lambda x: x[None], new_ef)
        updates, new_opt_state = optimizer.update(
            synced, state.opt_state, state.params
        )
        new_params = optax.apply_updates(state.params, updates)
        new_stats = _bn_reduce(new_stats, bn_stats_sync, axis)

        if nonfinite_guard:
            # Resilience guard (resilience/faults.py): a NaN/Inf anywhere
            # in the SYNCED gradient (one poisoned replica poisons all via
            # the psum) skips this update wholesale — params, optimizer
            # state, BN stats and EF residuals all keep their previous
            # values; only the step counter advances, and the step is
            # flagged in the metrics. The check is on the synced tree so
            # every replica takes the identical branch (no desync).
            from pytorch_distributed_nn_tpu.resilience.faults import (
                all_finite,
            )

            ok = all_finite(synced)

            def keep(new, old):
                return jax.tree.map(
                    lambda n, o: jnp.where(ok, n, o), new, old
                )

            new_params = keep(new_params, state.params)
            new_opt_state = keep(new_opt_state, state.opt_state)
            new_stats = keep(new_stats, state.batch_stats)
            if new_ef is not None:
                new_ef = keep(new_ef, state.ef_state)
            metrics["skipped_nonfinite"] = 1.0 - ok.astype(jnp.float32)

        metrics = {k: lax.pmean(v, axis) for k, v in metrics.items()}
        new_state = state.replace(
            step=state.step + 1,
            params=new_params,
            opt_state=new_opt_state,
            batch_stats=new_stats,
            ef_state=new_ef,
        )
        return new_state, metrics

    has_ef = grad_sync.config.compression == "topk" and grad_sync.config.mode != "local"
    # Pytree-prefix spec over TrainState: everything replicated except the
    # per-replica error-feedback residuals (leading replica axis).
    state_spec = TrainState(
        step=P(),
        params=P(),
        opt_state=P(),
        batch_stats=P(),
        ef_state=P(DATA_AXIS) if has_ef else P(),
    )

    @partial(
        shard_map,
        mesh=mesh,
        in_specs=(state_spec, P(DATA_AXIS), P(DATA_AXIS), P()),
        out_specs=(state_spec, P()),
        check_vma=False,
    )
    def spmd_step(state, images, labels, rng):
        return per_replica(state, images, labels, rng)

    jit_kwargs = {"donate_argnums": (0,)} if donate else {}
    return jax.jit(
        lambda state, batch, rng: spmd_step(state, batch[0], batch[1], rng),
        **jit_kwargs,
    )


def dp_audit_bundle(
    model,
    optimizer: optax.GradientTransformation,
    grad_sync: GradSync,
    mesh: Mesh,
    input_shape,
    global_batch: int,
    input_dtype=jnp.float32,
    seed: int = 0,
    donate: bool = False,
    **build_kw,
) -> dict:
    """Build the shard_map (dp/PS) step plus ``analysis.audit`` kwargs.

    The data-parallel twin of ``training.spmd.spmd_audit_bundle``: params
    are replicated by design here, so only the concrete param tree rides
    along (SL001 falls back to its size heuristic; SL005 needs sharding
    expectations and does not apply). ``donate=True`` builds the
    production state-consuming step for the SL007 donation audit.
    """
    from pytorch_distributed_nn_tpu.parallel.mesh import num_workers

    state = create_train_state(
        model, optimizer, grad_sync, jax.random.PRNGKey(seed),
        input_shape, num_replicas=num_workers(mesh), input_dtype=input_dtype,
    )
    step = build_train_step(
        model, optimizer, grad_sync, mesh, donate=donate, **build_kw
    )
    x = jnp.zeros((global_batch, *input_shape), input_dtype)
    y = jnp.zeros((global_batch,), jnp.int32)
    return {
        "step_fn": step,
        "args": (state, (x, y), jax.random.PRNGKey(seed + 1)),
        "mesh": mesh,
        "params": state.params,
    }


def build_eval_step(
    model,
    mesh: Mesh,
    loss_fn: Callable = cross_entropy_loss,
    metrics_fn: Optional[Callable] = None,
):
    """Compile the evaluation step: ``(state, batch) -> metrics`` (no grad)."""
    if metrics_fn is None:
        metrics_fn = _classification_metrics

    @partial(
        shard_map,
        mesh=mesh,
        in_specs=(P(), P(DATA_AXIS), P(DATA_AXIS)),
        out_specs=P(),
        check_vma=False,
    )
    def spmd_eval(state, images, labels):
        out = model.apply(
            {"params": state.params, "batch_stats": state.batch_stats},
            images,
            train=False,
        )
        metrics = {"loss": loss_fn(out, labels), **metrics_fn(out, labels)}
        return {k: lax.pmean(v, DATA_AXIS) for k, v in metrics.items()}

    return jax.jit(lambda state, batch: spmd_eval(state, batch[0], batch[1]))


def run_eval_pass(eval_step, state, loader) -> dict:
    """Mean loss/acc1/acc5 over one pass of ``loader.epoch_batches()``.

    The single source of truth for the eval accumulate/mean loop, shared
    by `Trainer.evaluate` and the polling `Evaluator` so the two surfaces
    can never drift in what they score. Returns {} for an empty eval set
    (--eval-batches 0): a skipped eval, never fabricated 0.0 metrics.
    """
    # Accumulate ON DEVICE and fetch once at the end: a float() per metric
    # per batch is 3 blocking device->host fetches x batches, each of
    # which drains the dispatch queue.
    totals, n = None, 0
    # The intra-process multi-device CPU backend can deadlock its
    # collective rendezvous with several eval steps in flight (XLA then
    # aborts the process after 40 s); finishing each one first serializes
    # them, as DeviceDataLoader._batch_for does. TPU keeps the overlap.
    serialize = jax.default_backend() == "cpu"
    for batch in loader.epoch_batches():
        m = eval_step(state, batch)
        if serialize:
            jax.block_until_ready(m)
        totals = m if totals is None else jax.tree.map(jnp.add, totals, m)
        n += 1
    if n == 0:
        return {}
    fetched = jax.device_get(totals)
    return {k: float(v) / n for k, v in fetched.items()}

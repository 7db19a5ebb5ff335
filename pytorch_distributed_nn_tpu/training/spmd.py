"""GSPMD training path: jit + shardings over a (data, seq, model) mesh.

The shard_map step in training/train_step.py reproduces the reference's PS
*semantics* (num-aggregate drops, compression) for the CNN zoo. This module
is the scale-out path the reference never had: transformers trained
dp × tp × sp, with parameter shardings derived from the model's logical axis
annotations (parallel/partitioning.py) and gradient synchronization left to
XLA's SPMD partitioner — the compiler inserts the all-reduces over ICI and
overlaps them with backward, subsuming the reference's hand-rolled
split-backward/isend overlap (reference: src/model_ops/resnet_split.py:
365-501) at zero lines of comm code.

Sequence parallelism composes in via `make_mesh_attn` (nested shard_map over
the "seq" axis inside this jitted step).
"""

from __future__ import annotations

from functools import partial
from typing import Callable, Optional, Tuple

import jax
import jax.numpy as jnp
import optax
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from pytorch_distributed_nn_tpu.ops.metrics import (
    masked_cross_entropy,
    mlm_metrics,
)
from pytorch_distributed_nn_tpu.parallel.mesh import DATA_AXIS, SEQ_AXIS
from pytorch_distributed_nn_tpu.parallel.partitioning import (
    DEFAULT_RULES,
    mesh_shardings,
    unbox,
)
from pytorch_distributed_nn_tpu.training.train_step import TrainState


def text_batch_sharding(mesh: Mesh) -> NamedSharding:
    """Token batches shard (batch → data, length → seq)."""
    return NamedSharding(mesh, P(DATA_AXIS, SEQ_AXIS))


def _boxed_init_fn(model, optimizer: optax.GradientTransformation, tokens_shape):
    tokens = jnp.zeros(tokens_shape, jnp.int32)

    def boxed_init(r):
        variables = model.init({"params": r, "dropout": r}, tokens, train=False)
        params = variables["params"]
        return TrainState(
            step=jnp.zeros([], jnp.int32),
            params=params,
            opt_state=optimizer.init(params),
            batch_stats=variables.get("batch_stats", {}),
            ef_state=None,
        )

    return boxed_init


def abstract_spmd_state(
    model,
    optimizer: optax.GradientTransformation,
    rng: jax.Array,
    tokens_shape: Tuple[int, int],
):
    """Boxed abstract TrainState (shapes + logical axis names, no arrays).

    The lowering hook the sharding auditor builds on: the flax
    Partitioned boxes in this tree carry the logical axis names that,
    joined with a rule table, say what every weight's sharding *should*
    be (analysis/auditor SL001/SL005).
    """
    return jax.eval_shape(_boxed_init_fn(model, optimizer, tokens_shape), rng)


def create_spmd_state(
    model,
    optimizer: optax.GradientTransformation,
    rng: jax.Array,
    tokens_shape: Tuple[int, int],
    mesh: Mesh,
    rules=DEFAULT_RULES,
):
    """Initialize a sharded TrainState directly on the mesh.

    ``tokens_shape`` must be divisible by the mesh's (data, seq) extents
    (it is traced through the model, including any nested shard_map
    attention). Returns ``(state, state_shardings)``; parameters land on
    devices already partitioned — no host-side full-model materialization.
    """
    boxed_init = _boxed_init_fn(model, optimizer, tokens_shape)
    abstract = jax.eval_shape(boxed_init, rng)
    shardings = mesh_shardings(abstract, mesh, rules)
    state = jax.jit(
        lambda r: unbox(boxed_init(r)), out_shardings=shardings
    )(rng)
    return state, shardings


def spmd_audit_bundle(
    model,
    optimizer: optax.GradientTransformation,
    mesh: Mesh,
    tokens_shape: Tuple[int, int],
    rules=DEFAULT_RULES,
    compression: str = "none",
    grad_accum: int = 1,
    seed: int = 0,
    donate: bool = False,
) -> dict:
    """Build the GSPMD step plus everything ``analysis.audit`` wants.

    Returns kwargs for ``analysis.audit(**bundle)``: the compiled-lowerable
    step (``donate=False`` by default so the auditor may execute it twice
    for the recompile check), example args on the mesh, and the three
    param-side trees (concrete params for attribution, actual shardings,
    boxed abstract tree for rule-derived expectations). ``rules`` here is
    the table used to BUILD the state — pass a broken table to reproduce
    a finding; the auditor always compares against the reference rules it
    is given separately. ``donate=True`` builds the production
    (state-consuming) step instead — the configuration the SL007
    donation audit judges (``audit(..., donation="step")``); don't
    combine it with the SL006 ``second_args`` double execution.
    """
    rng = jax.random.PRNGKey(seed)
    abstract = abstract_spmd_state(model, optimizer, rng, tokens_shape)
    state, shardings = create_spmd_state(
        model, optimizer, rng, tokens_shape, mesh, rules=rules
    )
    step = build_spmd_train_step(
        model, optimizer, mesh, shardings,
        donate=donate, compression=compression, grad_accum=grad_accum,
    )
    tok = jnp.zeros(tokens_shape, jnp.int32)
    return {
        "step_fn": step,
        "args": (state, (tok, tok), jax.random.PRNGKey(seed + 1)),
        "mesh": mesh,
        "params": state.params,
        "param_shardings": shardings.params,
        "abstract_params": abstract.params,
    }


def build_spmd_train_step(
    model,
    optimizer: optax.GradientTransformation,
    mesh: Mesh,
    state_shardings,
    loss_fn: Callable = masked_cross_entropy,
    metrics_fn: Callable = mlm_metrics,
    donate: bool = True,
    compression: str = "none",
    grad_accum: int = 1,
):
    """Compile the dp×tp×sp step: ``(state, (tokens, labels), rng)``.

    ``grad_accum=K`` (round-4 verdict item 6) splits the global batch into
    K microbatches scanned before the one update, cutting activation
    memory K× exactly where pods need it (tp/sp runs). Same exact
    pair-accumulation math as the shard_map path
    (train_step.py:194-240): each microbatch differentiates the
    UNNORMALIZED Σ masked-xent (``mlm_sums_dense``), the scan accumulates
    (Σ grad, Σ count), and ONE division by the global masked count at the
    end reproduces the global-masked-mean gradient bit-close to the
    full-batch step. Microbatches are re-sharded to the data axis with a
    sharding constraint so each scan iteration keeps the full dp width.

    ``compression="none"``: gradients need no explicit sync stage — the
    loss is a global mean over the batch/length axes, so XLA emits the
    cross-replica reduction as part of backward.

    ``compression="int8"``: the reference compressed gradients on its only
    comm path (src/compression.py:18-46 applied at
    src/distributed_worker.py:265-268); here the data-parallel gradient
    reduction is taken over explicitly so the same int8 codec rides the
    tp/sp path. The grad computation + sync runs inside a `shard_map`
    MANUAL over the data axis with the seq/model axes left in ``auto``
    (still GSPMD-partitioned): each dp rank differentiates the UNNORMALIZED
    Σ masked-xent on its batch shard, quantizes with the pmax-shared scale
    (ops/compression.int8_psum_mean — jnp quantizer; a Pallas custom call
    cannot be auto-partitioned over the model axis), psums int32 over the
    data axis, and normalizes once by the GLOBAL masked-token count — the
    identical global-masked-mean math of the dense path, with the dp wire
    payload quantized. tp/sp collectives (per-layer psum, ring permute /
    all-to-all) are unchanged: those reductions are partial-sum exchanges
    XLA schedules inside backward, not gradient averages, so the codec
    applies where the reference's did — the data-parallel sync.
    """
    bspec = text_batch_sharding(mesh)
    rspec = NamedSharding(mesh, P())
    if compression not in ("none", "int8"):
        raise ValueError(
            f"GSPMD path supports compression 'none'|'int8', got "
            f"{compression!r} (topk needs per-replica EF state — a "
            "shard_map-DP feature)"
        )
    if grad_accum < 1:
        raise ValueError(f"grad_accum must be >= 1, got {grad_accum}")
    if (compression == "int8" or grad_accum > 1) and (
        loss_fn is not masked_cross_entropy or metrics_fn is not mlm_metrics
    ):
        raise ValueError(
            "compression='int8' and grad_accum>1 hardwire the Σ-masked-xent "
            "pair objective (ops.metrics.mlm_sums_dense) — custom "
            "loss_fn/metrics_fn would be silently ignored; pass the "
            "defaults or use compression='none', grad_accum=1"
        )
    if compression == "int8" and grad_accum > 1:
        raise ValueError(
            "grad_accum>1 with compression='int8' on the GSPMD path is not "
            "implemented (the quantized dp sync would need the microbatch "
            "scan inside its manual region); use one or the other"
        )

    def step(state: TrainState, batch, rng):
        tokens, labels = batch
        dropout_rng = jax.random.fold_in(rng, state.step)

        def loss_of(params):
            logits = model.apply(
                {"params": params},
                tokens,
                train=True,
                rngs={"dropout": dropout_rng},
            )
            return loss_fn(logits, labels), logits

        (loss, logits), grads = jax.value_and_grad(loss_of, has_aux=True)(
            state.params
        )
        updates, new_opt = optimizer.update(grads, state.opt_state, state.params)
        new_params = optax.apply_updates(state.params, updates)
        metrics = {"loss": loss, **metrics_fn(logits, labels)}
        new_state = state.replace(
            step=state.step + 1, params=new_params, opt_state=new_opt
        )
        return new_state, metrics

    def accum_step(state: TrainState, batch, rng):
        from pytorch_distributed_nn_tpu.ops.metrics import mlm_sums_dense

        tokens, labels = batch
        dropout_rng = jax.random.fold_in(rng, state.step)
        n = tokens.shape[0]
        if n % grad_accum:
            raise ValueError(
                f"global batch {n} not divisible by grad_accum={grad_accum}"
            )
        # (K, B/K, L), each microbatch re-sharded over (data, seq): the
        # reshape regroups rows across dp shards, so pin the sharding or
        # the scan would run each microbatch on a fraction of the mesh.
        mb_spec = NamedSharding(mesh, P(None, DATA_AXIS, SEQ_AXIS))
        mb_tokens = jax.lax.with_sharding_constraint(
            tokens.reshape(grad_accum, n // grad_accum, -1), mb_spec
        )
        mb_labels = jax.lax.with_sharding_constraint(
            labels.reshape(grad_accum, n // grad_accum, -1), mb_spec
        )

        def forward_sum(params, tok, lab, drng):
            logits = model.apply(
                {"params": params}, tok, train=True, rngs={"dropout": drng}
            )
            return_sums = mlm_sums_dense(logits, lab)
            return return_sums["loss_sum"], return_sums

        def body(gsum, mb):
            tok, lab, i = mb
            (_, sums), g = jax.value_and_grad(forward_sum, has_aux=True)(
                state.params, tok, lab, jax.random.fold_in(dropout_rng, i)
            )
            return jax.tree.map(jnp.add, gsum, g), sums

        gz = jax.tree.map(jnp.zeros_like, state.params)
        gsum, stacked = jax.lax.scan(
            body, gz, (mb_tokens, mb_labels, jnp.arange(grad_accum))
        )
        ssum = jax.tree.map(lambda x: x.sum(0), stacked)
        denom = jnp.maximum(ssum["count"], 1.0)
        grads = jax.tree.map(lambda g: g / denom, gsum)
        metrics = {
            "loss": ssum["loss_sum"] / denom,
            "acc1": ssum["acc1"] / denom,
            "acc5": ssum["acc5"] / denom,
        }
        updates, new_opt = optimizer.update(grads, state.opt_state, state.params)
        new_params = optax.apply_updates(state.params, updates)
        new_state = state.replace(
            step=state.step + 1, params=new_params, opt_state=new_opt
        )
        return new_state, metrics

    if compression == "int8":
        body_fn = _int8_spmd_step(model, optimizer, mesh)
    elif grad_accum > 1:
        body_fn = accum_step
    else:
        body_fn = step
    kw = {"donate_argnums": (0,)} if donate else {}
    return jax.jit(
        body_fn,
        in_shardings=(state_shardings, (bspec, bspec), rspec),
        out_shardings=(state_shardings, None),
        **kw,
    )


def _int8_spmd_step(model, optimizer: optax.GradientTransformation, mesh: Mesh):
    """The int8-compressed dp sync step body (see build_spmd_train_step).

    Manual over the data axis only; seq/model stay in GSPMD ``auto`` so
    tp shardings and the nested ring/Ulysses shard_map compose unchanged.
    """
    from jax import lax

    from pytorch_distributed_nn_tpu.ops.compression import int8_psum_mean
    from pytorch_distributed_nn_tpu.ops.metrics import mlm_sums_dense

    if mesh.shape[DATA_AXIS] == 1:
        # dp=1: there is no data-parallel wire, and a psum over the
        # size-1 manual axis trips an XLA partitioner RET_CHECK
        # ("Cross-partition allreduce must be in (partial) manual
        # partitioning mode") under the mixed manual(data)/auto(seq,
        # model) mesh. Keep the CODEC semantics (stochastic-round
        # quantize -> dequantize noise on the gradients — what a 1-rank
        # contributor adds to any sum) via int8_psum_mean's
        # single-contributor mode (axis_name=None, no collectives):
        # plain GSPMD grads of the Σ objective, normalized by the
        # global masked count.
        def step1(state: TrainState, batch, rng):
            tokens, labels = batch
            base_rng = jax.random.fold_in(rng, state.step)

            def loss_sum_of(params):
                logits = model.apply(
                    {"params": params},
                    tokens,
                    train=True,
                    rngs={"dropout": base_rng},
                )
                sums = mlm_sums_dense(logits, labels)
                return sums["loss_sum"], sums

            (_, sums), grads = jax.value_and_grad(
                loss_sum_of, has_aux=True
            )(state.params)
            count = jnp.maximum(sums["count"], 1.0)
            grads = int8_psum_mean(
                grads, base_rng, None, denom=count, allow_pallas=False
            )
            updates, new_opt = optimizer.update(
                grads, state.opt_state, state.params
            )
            new_params = optax.apply_updates(state.params, updates)
            metrics = {
                "loss": sums["loss_sum"] / count,
                "acc1": sums["acc1"] / count,
                "acc5": sums["acc5"] / count,
            }
            return state.replace(
                step=state.step + 1, params=new_params, opt_state=new_opt
            ), metrics

        return step1

    def step(state: TrainState, batch, rng):
        tokens, labels = batch
        # Token/label arrays are tiny (B×L int32); replicate them over the
        # seq axis before entering the manual region — XLA's partitioner
        # aborts (device-group check failure) partitioning the embedding
        # gather when its index operand stays seq-sharded under a mixed
        # manual(data)/auto(seq,model) mesh. Activation shardings still
        # propagate from the attention shard_map's seq/model specs.
        tokens = jax.lax.with_sharding_constraint(
            tokens, NamedSharding(mesh, P(DATA_AXIS, None))
        )
        labels = jax.lax.with_sharding_constraint(
            labels, NamedSharding(mesh, P(DATA_AXIS, None))
        )
        base_rng = jax.random.fold_in(rng, state.step)

        @partial(
            shard_map,
            mesh=mesh,
            in_specs=(P(), P(DATA_AXIS), P(DATA_AXIS), P()),
            out_specs=(P(), P()),
            axis_names={DATA_AXIS},  # seq/model stay GSPMD-auto inside
            check_vma=False,
        )
        def grads_and_metrics(params, tokens, labels, rng):
            rank = lax.axis_index(DATA_AXIS)
            dropout_rng = jax.random.fold_in(rng, rank)
            sync_rng = rng  # identical across dp ranks (shared quant noise keys)

            def loss_sum_of(params):
                logits = model.apply(
                    {"params": params},
                    tokens,
                    train=True,
                    rngs={"dropout": dropout_rng},
                )
                sums = mlm_sums_dense(logits, labels)
                return sums["loss_sum"], sums

            (_, sums), grads = jax.value_and_grad(
                loss_sum_of, has_aux=True
            )(params)
            global_count = jnp.maximum(
                lax.psum(sums["count"], DATA_AXIS), 1.0
            )
            # Σ-objective grads ÷ global count == the global masked mean —
            # with the dp-sync payload quantized (int8_psum_mean docstring).
            synced = int8_psum_mean(
                grads, sync_rng, DATA_AXIS, denom=global_count,
                allow_pallas=False,
            )
            metrics = {
                "loss": lax.psum(sums["loss_sum"], DATA_AXIS) / global_count,
                "acc1": lax.psum(sums["acc1"], DATA_AXIS) / global_count,
                "acc5": lax.psum(sums["acc5"], DATA_AXIS) / global_count,
            }
            return synced, metrics

        grads, metrics = grads_and_metrics(
            state.params, tokens, labels, base_rng
        )
        updates, new_opt = optimizer.update(grads, state.opt_state, state.params)
        new_params = optax.apply_updates(state.params, updates)
        new_state = state.replace(
            step=state.step + 1, params=new_params, opt_state=new_opt
        )
        return new_state, metrics

    return step


def build_spmd_eval_step(
    model,
    mesh: Mesh,
    state_shardings,
    loss_fn: Callable = masked_cross_entropy,
    metrics_fn: Callable = mlm_metrics,
):
    """Compile the no-grad eval step: ``(state, (tokens, labels)) -> metrics``."""
    bspec = text_batch_sharding(mesh)

    def evaluate(state: TrainState, batch):
        tokens, labels = batch
        logits = model.apply({"params": state.params}, tokens, train=False)
        return {"loss": loss_fn(logits, labels), **metrics_fn(logits, labels)}

    return jax.jit(
        evaluate, in_shardings=(state_shardings, (bspec, bspec))
    )

"""Classification metrics.

Parity with the reference's `accuracy(output, target, topk=(1,5))`
(reference: src/nn_ops.py:14-27), used by the single-machine trainer and the
evaluator (src/distributed_evaluator.py:90-106).
"""

from __future__ import annotations

from typing import Sequence, Tuple

import jax
import jax.numpy as jnp
import optax

# Label sentinel for positions excluded from masked (MLM) objectives.
# data.text produces labels with this value; keep it the single source.
IGNORE_INDEX = -1

#: The flax collection a model may ``sow`` scalars into during the training
#: forward pass (models/lfm2.py: the expert layers' pairs, rows, fullest
#: expert). The train step sums each name over the modules that sowed it
#: and reports it beside the loss, so it lands in every step record.
COUNTERS = "counters"


def cross_entropy_loss(logits: jnp.ndarray, labels: jnp.ndarray) -> jnp.ndarray:
    """Mean softmax cross-entropy over integer labels (torch CrossEntropyLoss)."""
    return optax.softmax_cross_entropy_with_integer_labels(logits, labels).mean()


def _in_top_k(logits: jnp.ndarray, labels: jnp.ndarray, k: int) -> jnp.ndarray:
    """Is each label among the k highest logits? (f32 0/1 per position.)

    Rank-counting, NOT `lax.top_k`: one fused comparison+reduce pass over
    the class axis. On TPU, `lax.top_k` lowers to a full sort of the
    class axis, which at BERT vocab width (30522) cost 320 ms/step — 74%
    of a BERT-base step — just to report acc5.

    Conventions chosen to fail safe: ties count AGAINST the label
    (all-equal logits — e.g. a zero-init head at step 0 — score 0, not
    1), and a non-finite label logit is never a hit (a diverged run
    reports ~0 accuracy, not 100%).
    """
    label_logit = jnp.take_along_axis(logits, labels[..., None], axis=-1)
    # >= counts strictly-greater logits plus OTHER logits tied with the
    # label; the label's own self-comparison contributes the -1.
    n_above = (logits >= label_logit).sum(axis=-1) - 1
    hit = jnp.logical_and(n_above < k, jnp.isfinite(label_logit[..., 0]))
    return hit.astype(jnp.float32)


def masked_cross_entropy(
    logits: jnp.ndarray, labels: jnp.ndarray, ignore_index: int = IGNORE_INDEX
) -> jnp.ndarray:
    """MLM loss: mean CE over positions where ``labels != ignore_index``.

    logits (B, L, V), labels (B, L) int32 with ``ignore_index`` at unmasked
    positions (the BERT MLM objective; no reference counterpart — the
    reference is CNN-only, SURVEY.md §2.2).
    """
    mask = (labels != ignore_index).astype(jnp.float32)
    safe = jnp.where(labels == ignore_index, 0, labels)
    losses = optax.softmax_cross_entropy_with_integer_labels(logits, safe)
    return (losses * mask).sum() / jnp.maximum(mask.sum(), 1.0)


def make_global_mlm_metrics(axis_name: str):
    """MLM acc1/acc5 normalized by the GLOBAL masked-token count.

    Same rationale as `make_global_masked_cross_entropy`: per-replica mask
    counts differ, so pmean-ing per-replica accuracies over-weights replicas
    with few masked tokens. Dividing local hit counts by the *mean* count
    makes the step's pmean exactly global-hits / global-count. Must run
    inside shard_map with ``axis_name`` bound.
    """
    from jax import lax

    def metrics(logits, labels, ignore_index: int = IGNORE_INDEX):
        mask = (labels != ignore_index).astype(jnp.float32)
        safe = jnp.where(labels == ignore_index, 0, labels)
        mean_count = jnp.maximum(lax.pmean(mask.sum(), axis_name), 1.0)
        # Both via _in_top_k so the same tie/NaN conventions apply and
        # acc5 >= acc1 holds even with tied logits (argmax lets a tied
        # label win at k=1 while rank counting scores it 0 at k=5).
        hit1 = (_in_top_k(logits, safe, 1) * mask).sum()
        hit5 = (_in_top_k(logits, safe, 5) * mask).sum()
        return {"acc1": hit1 / mean_count, "acc5": hit5 / mean_count}

    return metrics


def make_global_masked_cross_entropy(axis_name: str):
    """Masked CE normalized by the GLOBAL masked-token count across replicas.

    `masked_cross_entropy` divides by the replica's own masked count; when
    per-replica counts differ, uniformly averaging those per-replica means
    (what pmean-of-grads does) is biased vs the global masked mean. Dividing
    the local sum by the *mean* count across replicas instead makes
    pmean-of-grads exactly the gradient of global-sum / global-count.
    Must be called inside shard_map with ``axis_name`` bound.
    """
    from jax import lax

    def loss(logits, labels, ignore_index: int = IGNORE_INDEX):
        mask = (labels != ignore_index).astype(jnp.float32)
        safe = jnp.where(labels == ignore_index, 0, labels)
        losses = optax.softmax_cross_entropy_with_integer_labels(logits, safe)
        mean_count = lax.pmean(mask.sum(), axis_name)
        return (losses * mask).sum() / jnp.maximum(mean_count, 1.0)

    return loss


def depth_loss_names(depth: int) -> Tuple[str, ...]:
    """Metric names of each prediction depth's loss: ``loss_main`` (the
    next token) and ``loss_mtp`` (one prediction module, the only depth a
    model has yet)."""
    if depth != 2:
        raise ValueError(f"no metric names for {depth} prediction depths: "
                         "a model has one prediction module or none")
    return ("loss_main", "loss_mtp")


def make_global_depth_losses(axis_name: str, depth: int):
    """Metrics of a model with next-token prediction modules: logits
    ``(..., depth, V)`` against labels ``(..., depth)``. Each depth's
    masked cross-entropy over that depth's global count
    (`make_global_masked_cross_entropy` on its slice), under the names of
    `depth_loss_names`. Must run inside shard_map with ``axis_name``
    bound."""
    loss = make_global_masked_cross_entropy(axis_name)
    names = depth_loss_names(depth)

    def metrics(logits, labels, ignore_index: int = IGNORE_INDEX):
        return {name: loss(logits[..., j, :], labels[..., j], ignore_index)
                for j, name in enumerate(names)}

    return metrics


def mlm_sums(
    logits: jnp.ndarray, labels: jnp.ndarray, ignore_index: int = IGNORE_INDEX
) -> dict:
    """UNNORMALIZED masked sums — the exact-gradient-accumulation pair.

    Returns ``{"loss_sum", "count", "acc1", "acc5"}`` where ``loss_sum``
    is the raw Σ masked-xent (the differentiated objective) and the
    metric entries are HIT COUNTS keyed by their final metric name — the
    accumulating step divides every non-(loss_sum/count) entry by the
    accumulated count once at the end. Gradients are linear in sums, so
    accumulating ``(∂ loss_sum, count)`` per microbatch and dividing
    ONCE by the global count at the sync reproduces the global masked
    mean exactly — per-microbatch normalization (what uniform averaging
    of `masked_cross_entropy` grads would do) is biased whenever random
    masking gives microbatches different counts. Used by
    `build_train_step(pair_accum_fn=...)` for text-model grad_accum.
    """
    mask = (labels != ignore_index).astype(jnp.float32)
    safe = jnp.where(labels == ignore_index, 0, labels)
    losses = optax.softmax_cross_entropy_with_integer_labels(logits, safe)
    return {
        "loss_sum": (losses * mask).sum(),
        "count": mask.sum(),
        "acc1": (_in_top_k(logits, safe, 1) * mask).sum(),
        "acc5": (_in_top_k(logits, safe, 5) * mask).sum(),
    }


def mlm_sums_dense(
    logits: jnp.ndarray, labels: jnp.ndarray, ignore_index: int = IGNORE_INDEX
) -> dict:
    """Gather-free `mlm_sums` (same keys, same tie/NaN conventions).

    XLA's SPMD partitioner hard-aborts (device-group check failure in
    PartitionGather) on the take-along-axis gathers that `optax`'s xent
    and `_in_top_k` lower to, when the gather's batch dims are sharded
    under a mixed manual(data)/auto(seq,model) mesh with BOTH auto axes
    >1 — the exact regime of the int8-compressed GSPMD step
    (training/spmd._int8_spmd_step). This variant extracts the label
    logit with a broadcasted-iota compare + masked reduce over the vocab
    axis (elementwise + reduction only — partitions trivially), and
    counts ranks with the same >=-and-subtract-self rule as `_in_top_k`.
    """
    from jax import lax

    mask = (labels != ignore_index).astype(jnp.float32)
    safe = jnp.where(labels == ignore_index, 0, labels)
    f32 = logits.astype(jnp.float32)
    sel = (
        lax.broadcasted_iota(jnp.int32, logits.shape, logits.ndim - 1)
        == safe[..., None]
    )
    label_logit = jnp.sum(jnp.where(sel, f32, 0.0), axis=-1)
    losses = jax.nn.logsumexp(f32, axis=-1) - label_logit
    # rank counting: >= counts strictly-greater plus ties; the label's
    # self-comparison contributes the -1 (same convention as _in_top_k,
    # so ties fail and a non-finite label logit never scores)
    n_above = (f32 >= label_logit[..., None]).sum(axis=-1) - 1
    finite = jnp.isfinite(label_logit)
    hit1 = jnp.logical_and(n_above < 1, finite).astype(jnp.float32)
    hit5 = jnp.logical_and(n_above < 5, finite).astype(jnp.float32)
    return {
        "loss_sum": (losses * mask).sum(),
        "count": mask.sum(),
        "acc1": (hit1 * mask).sum(),
        "acc5": (hit5 * mask).sum(),
    }


def masked_accuracy(
    logits: jnp.ndarray, labels: jnp.ndarray, ignore_index: int = IGNORE_INDEX
) -> jnp.ndarray:
    """Fraction of masked positions predicted exactly (MLM top-1).

    Implemented as top-1 rank counting (not argmax) so its tie/NaN
    conventions match `masked_topk_accuracy` and acc5 >= acc1 always.
    """
    return masked_topk_accuracy(logits, labels, 1, ignore_index)


def masked_topk_accuracy(
    logits: jnp.ndarray,
    labels: jnp.ndarray,
    k: int,
    ignore_index: int = IGNORE_INDEX,
) -> jnp.ndarray:
    """Top-k accuracy over masked positions only (MLM counterpart of
    `topk_accuracy`)."""
    mask = (labels != ignore_index).astype(jnp.float32)
    safe = jnp.where(labels == ignore_index, 0, labels)
    hit = _in_top_k(logits, safe, k)
    return (hit * mask).sum() / jnp.maximum(mask.sum(), 1.0)


def mlm_metrics(logits: jnp.ndarray, labels: jnp.ndarray) -> dict:
    """Metrics dict for the MLM objective (drop-in for the train step)."""
    return {
        "acc1": masked_accuracy(logits, labels),
        "acc5": masked_topk_accuracy(logits, labels, 5),
    }


def topk_accuracy(
    logits: jnp.ndarray, labels: jnp.ndarray, topk: Sequence[int] = (1, 5)
) -> Tuple[jnp.ndarray, ...]:
    """Fraction (in [0,1]) of samples whose label is in the top-k predictions."""
    return tuple(_in_top_k(logits, labels, k).mean() for k in topk)

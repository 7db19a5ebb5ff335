"""Gradient synchronization — the comm backend, as a pluggable SPMD stage.

This replaces the reference's entire layer-1 communication machinery: the
master's bcast-step / bcast-weights / L×P-irecv / Waitany-drain /
aggregate / average cycle (reference: src/sync_replicas_master_nn.py:133-197)
and the worker's per-layer isend pipeline (src/distributed_worker.py:254-272,
src/model_ops/resnet_split.py:365-501). Under SPMD all of it collapses into
one collective inside the jitted step; XLA's latency-hiding scheduler
overlaps it with backward, which is what the reference's hand-rolled "split
backward" was for.

Three modes:

- ``allreduce`` — plain ``pmean`` over the data axis (the TPU-idiomatic
  default; the reference's dead-code DistributedDataParallel intent,
  src/data_parallel_dist/data_parallel_dist.py:146-267, realized natively).
- ``ps`` — parameter-server semantics emulation: only the first
  ``num_aggregate`` workers (by a per-step simulated arrival order)
  contribute, the rest are dropped exactly like backup workers
  (src/sync_replicas_master_nn.py:179-182), and the sum is divided by
  ``num_aggregate`` (src/sync_replicas_master_nn.py:207). This also covers
  the straggler-kill capability (src/model_ops/resnet_split.py:503-728):
  a killed straggler's observable effect is its gradient being excluded
  from the step.
- ``local`` — no sync (the single-machine baseline, src/nn_ops.py).

Compression (``none`` / ``int8`` / ``topk``) is fused around the collective
(see ops/compression.py). Everything here runs inside ``shard_map``.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Optional

import jax
import jax.numpy as jnp
from jax import lax

from pytorch_distributed_nn_tpu.ops import compression as C
from pytorch_distributed_nn_tpu.parallel.mesh import DATA_AXIS


@dataclasses.dataclass(frozen=True)
class GradSyncConfig:
    """Configuration for the gradient-sync stage.

    mode: "allreduce" | "ps" | "local"
    num_aggregate: PS mode — how many workers' gradients are aggregated per
        step (reference CLI --num-aggregate, src/distributed_nn.py:46-47).
        None means all workers.
    arrival: PS mode — how the simulated arrival order is drawn:
        "rank" (lowest ranks always first — deterministic) or "random"
        (fresh permutation each step, the realistic emulation).
    compression: "none" | "int8" | "topk"
        (reference CLI --compress-grad, src/distributed_nn.py:60-62).
    topk_ratio: fraction of coordinates kept by topk.
    topk_method: "auto" | "exact" | "approx" — threshold selection
        (ops/compression._topk_mask_leaf; auto = TPU-fast approx_max_k on
        TPU, exact top_k elsewhere).
    axis_name: mesh axis to synchronize over.
    """

    mode: str = "allreduce"
    num_aggregate: Optional[int] = None
    arrival: str = "random"
    compression: str = "none"
    topk_ratio: float = 0.01
    topk_method: str = "auto"
    axis_name: str = DATA_AXIS
    # Bucketed collectives (reference C12: the dead DDP path's ~1 MB NCCL
    # buckets, src/data_parallel_dist/data_parallel_dist.py:181-209). None
    # disables. Applies to compression "none" and "int8" (topk needs leaf
    # shapes for its masks).
    bucket_bytes: Optional[int] = None
    # Straggler mitigation (reference C6, SURVEY.md §2): the reference's
    # signal-kill (tag-77 Iprobe aborts a straggler's backward mid-flight,
    # src/model_ops/resnet_split.py:503-615) and timeout-kill (step-stamped
    # tags let the PS ignore gradients older than --kill-threshold,
    # :617-728) both have ONE observable effect on training: the named
    # workers' gradients are excluded from the aggregate. `kill_ranks`
    # reproduces exactly that under SPMD — the listed replicas compute but
    # never contribute (their batch shard is dropped for the step, like a
    # killed worker's batch was).
    kill_ranks: tuple = ()
    # Deadline-based straggler dropping (resilience/stragglers.StragglerSim):
    # per-step seeded arrival times decide which replicas miss the deadline;
    # their gradients are masked out and the aggregate renormalized by the
    # live count (unbiased — the drop is value-independent). None disables.
    # Complements the static policies above: kill_ranks is "these workers
    # are dead", num_aggregate is "always take the first K", the simulator
    # is "drop whoever is slow *this step*".
    straggler: Optional[Any] = None

    def __post_init__(self):
        if self.mode not in ("allreduce", "ps", "local"):
            raise ValueError(f"unknown grad-sync mode {self.mode!r}")
        if self.compression not in ("none", "int8", "topk"):
            raise ValueError(f"unknown compression {self.compression!r}")
        if self.arrival not in ("rank", "random"):
            raise ValueError(f"unknown arrival order {self.arrival!r}")
        if self.topk_method not in ("auto", "exact", "approx"):
            raise ValueError(f"unknown topk_method {self.topk_method!r}")
        if self.kill_ranks and self.mode == "local":
            raise ValueError("kill_ranks requires a distributed sync mode")
        if self.straggler is not None:
            if self.mode == "local":
                raise ValueError(
                    "straggler simulation requires a distributed sync mode"
                )
            if self.compression == "topk":
                raise ValueError(
                    "straggler simulation is incompatible with topk "
                    "compression: a dropped replica's sent coordinates "
                    "would leave its error-feedback residual inconsistent; "
                    "use compression 'none' or 'int8'"
                )
        if self.bucket_bytes is not None:
            if self.bucket_bytes <= 0:
                raise ValueError("bucket_bytes must be positive")
            if self.compression == "topk":
                raise ValueError(
                    "bucketing is incompatible with topk compression "
                    "(top-k masks are per-leaf)"
                )


class GradSync:
    """Callable sync stage: ``(grads, state, key) -> (avg_grads, state)``.

    ``state`` carries error-feedback residuals when topk compression is on
    (else None). Must be invoked inside shard_map with ``axis_name`` bound —
    except mode="local", which never performs a collective.
    """

    def __init__(self, config: GradSyncConfig):
        self.config = config
        self._report: dict = {}

    def init_state(self, params) -> Any:
        if self.config.compression == "topk" and self.config.mode != "local":
            return C.init_ef_state(params)
        return None

    def _alive_mask(self) -> Optional[jnp.ndarray]:
        """Scalar 0/1: 0 for replicas on the straggler kill list."""
        cfg = self.config
        if not cfg.kill_ranks:
            return None
        rank = lax.axis_index(cfg.axis_name)
        alive = jnp.float32(1.0)
        for k in cfg.kill_ranks:
            alive = alive * (rank != k).astype(jnp.float32)
        return alive

    def _contribution_mask(self, key) -> Optional[jnp.ndarray]:
        """Scalar 0/1: does *this* replica's gradient make the aggregate?

        Emulates the master taking only the first num_aggregate arrivals
        per step (src/sync_replicas_master_nn.py:179-182), combined with the
        straggler kill list (killed workers never arrive).
        """
        cfg = self.config
        n = lax.axis_size(cfg.axis_name)
        alive = self._alive_mask()
        if cfg.num_aggregate is None or cfg.num_aggregate >= n:
            return alive
        rank = lax.axis_index(cfg.axis_name)
        if cfg.arrival == "rank":
            position = rank
        else:
            # Same key on every replica -> identical permutation of ranks;
            # position = where this rank lands in the arrival order.
            perm = jax.random.permutation(key, n)
            position = jnp.argmax(perm == rank)
        mask = (position < cfg.num_aggregate).astype(jnp.float32)
        return mask if alive is None else mask * alive

    def __call__(self, grads, state, key, step=None):
        """``step`` (1-indexed, may be traced) lets the straggler
        simulator match `delay@step` fault entries; omitted means no
        injected delays can fire (the seeded arrival noise still does)."""
        cfg = self.config
        self._report = {}
        if cfg.mode == "local":
            return grads, state

        mask_key, quant_key = jax.random.split(key)
        mask = (
            self._contribution_mask(mask_key)
            if cfg.mode == "ps"
            else self._alive_mask()
        )
        if cfg.straggler is not None:
            # fold_in (not a wider split) so the mask/quant streams stay
            # bitwise identical to a simulator-free run of the same seed
            smask, self._report = cfg.straggler.mask_and_report(
                jax.random.fold_in(key, 0x57A6),
                0 if step is None else step,
                cfg.axis_name,
            )
            mask = smask if mask is None else mask * smask

        if cfg.compression == "topk":
            grads, state = C.topk_compress_ef(
                grads, state, cfg.topk_ratio, cfg.topk_method
            )
            if (
                mask is not None
                and cfg.mode == "ps"
                and cfg.arrival == "random"
            ):
                # A replica dropped by the random arrival order this step
                # never gets its sent coordinates into the psum — put them
                # back in its residual so the EF contract holds ("dropped
                # coordinates are re-injected later", ops/compression.py).
                # Each replica contributes with prob num_aggregate/n per
                # step, so the retained residual stays bounded in
                # expectation. Deterministic exclusions (kill_ranks, rank
                # arrival past num_aggregate) are NOT re-injected: those
                # replicas are excluded every step — the semantics of a
                # killed/backup worker is that its gradient is dropped —
                # and retention would grow the residual without bound.
                alive = self._alive_mask()
                transient = (
                    (1.0 - mask) if alive is None else alive * (1.0 - mask)
                )
                state = jax.tree.map(
                    lambda e, s: e + s * transient, state, grads
                )

        bucket_meta = None
        if cfg.bucket_bytes is not None:
            grads, bucket_meta = C.flatten_buckets(grads, cfg.bucket_bytes)

        if cfg.compression == "int8":
            # PS mode keeps the fixed-num_aggregate divisor, identical to the
            # uncompressed branch below — kill semantics must not change with
            # the compression flag.
            fixed = (
                cfg.num_aggregate
                if cfg.mode == "ps" and cfg.num_aggregate is not None
                else None
            )
            avg = C.int8_psum_mean(
                grads, quant_key, cfg.axis_name, mask=mask, denom=fixed
            )
        elif mask is not None:
            total = lax.psum(jax.tree.map(lambda g: g * mask, grads), cfg.axis_name)
            # Reference parity: in PS mode the sum is divided by the FIXED
            # num_aggregate (src/sync_replicas_master_nn.py:207); otherwise
            # by the live contributor count.
            if cfg.mode == "ps" and cfg.num_aggregate is not None:
                denom = jnp.float32(cfg.num_aggregate)
            else:
                denom = jnp.maximum(lax.psum(mask, cfg.axis_name), 1.0)
            avg = jax.tree.map(lambda s: s / denom, total)
        else:
            avg = C.psum_mean(grads, cfg.axis_name)
        if bucket_meta is not None:
            avg = C.unflatten_buckets(avg, bucket_meta)
        return avg, state

    def pop_report(self) -> dict:
        """Straggler report captured during the LAST ``__call__`` (traced
        values — read it inside the same trace; the train step merges it
        into the step metrics). Empty dict when no simulator is set.

        Report fields (all scalar, identical on every replica, so they
        survive the metrics pmean and land in each step record):
        ``straggler_dropped``, ``straggler_dropped_mask`` (n <= 24),
        ``straggler_skew``, and the per-rank attribution pair
        ``straggler_slowest_rank`` / ``straggler_arrival_max`` that the
        cross-rank summary (``obs summary --by-rank``) aggregates into
        its straggler table — the SPMD replacement for the reference's
        per-worker timing logs (src/distributed_worker.py:146-173)."""
        r, self._report = self._report, {}
        return r

    def estimate_sync_bytes(self, grads_template) -> int:
        """Estimated bytes of gradient payload this sync moves per step.

        The telemetry layer's ``sync_bytes_per_step`` gauge (per replica,
        one direction — the quantity the reference measured as per-layer
        isend volume, src/distributed_worker.py:254-272). A host-side
        static estimate from leaf shapes: f32 words for uncompressed
        grads, 1 byte/element + one f32 scale per leaf for int8, and
        (value + index) words for the topk_ratio-sized coordinate set.
        Ring-allreduce constant factors (2(n-1)/n) are deliberately left
        out: the gauge tracks payload, not algorithm.
        """
        import numpy as np

        cfg = self.config
        if cfg.mode == "local":
            return 0
        leaves = jax.tree.leaves(grads_template)
        elems = [int(np.size(leaf)) for leaf in leaves]
        total = sum(elems)
        if cfg.compression == "int8":
            return total + 4 * len(leaves)
        if cfg.compression == "topk":
            kept = sum(max(1, int(n * cfg.topk_ratio)) for n in elems)
            return kept * 8  # f32 value + i32 index per kept coordinate
        return total * 4


def make_grad_sync(
    mode: str = "allreduce",
    num_aggregate: Optional[int] = None,
    compression: str = "none",
    topk_ratio: float = 0.01,
    arrival: str = "random",
    axis_name: str = DATA_AXIS,
    kill_ranks: tuple = (),
    bucket_bytes: Optional[int] = None,
    topk_method: str = "auto",
    straggler=None,
) -> GradSync:
    return GradSync(
        GradSyncConfig(
            mode=mode,
            num_aggregate=num_aggregate,
            arrival=arrival,
            compression=compression,
            topk_ratio=topk_ratio,
            topk_method=topk_method,
            axis_name=axis_name,
            kill_ranks=tuple(kill_ranks),
            bucket_bytes=bucket_bytes,
            straggler=straggler,
        )
    )

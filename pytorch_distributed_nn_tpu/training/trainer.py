"""The high-level trainer: config, loop, logging, checkpoints, resume.

This is the role layer of the reference collapsed into one class: the
master's step loop (reference: src/sync_replicas_master_nn.py:133-197), the
worker's train loop (src/distributed_worker.py:104-180), and the
single-machine trainer (src/nn_ops.py:48-88) are all the same code path
here — only the mesh size and the grad-sync mode differ. `mode="local"` on a
1-device mesh IS the single-machine baseline.
"""

from __future__ import annotations

import dataclasses
import logging
import os
import time
from typing import Optional

import jax
import numpy as np

from pytorch_distributed_nn_tpu.data import DataLoader, load_dataset
from pytorch_distributed_nn_tpu.data.text import MLMLoader, TEXT_DATASETS
from pytorch_distributed_nn_tpu.models import (
    build_model,
    input_spec,
    is_text_model,
)
from pytorch_distributed_nn_tpu.ops.metrics import (
    make_global_depth_losses,
    make_global_masked_cross_entropy,
    make_global_mlm_metrics,
)
from pytorch_distributed_nn_tpu.optim import build_optimizer
from pytorch_distributed_nn_tpu.parallel import (
    batch_sharding,
    make_grad_sync,
    make_mesh,
    num_workers,
    replicated_sharding,
)
from pytorch_distributed_nn_tpu.observability import compiles
from pytorch_distributed_nn_tpu.observability import core as obs
from pytorch_distributed_nn_tpu.observability.spans import SetupLog, span
from pytorch_distributed_nn_tpu.resilience.faults import (
    FaultPlan,
    InjectedCrash,
)
from pytorch_distributed_nn_tpu.training import checkpoint as ckpt
from pytorch_distributed_nn_tpu.training.config import TrainConfig  # noqa: F401
from pytorch_distributed_nn_tpu.training.train_step import (
    build_eval_step,
    build_train_step,
    create_train_state,
    param_count,
    run_eval_pass,
    tree_bytes,
)
from pytorch_distributed_nn_tpu.utils.timing import MetricsLogger

logger = logging.getLogger(__name__)

# A step whose input wait exceeds this gets its own `input_wait` telemetry
# event (docs/data.md): per-step percentiles live in the step records
# (`input_wait_ms` -> `obs summary` input_wait phase); the event marks the
# outliers worth a human's attention without one event per step.
INPUT_WAIT_EVENT_MS = 100.0


def _abstract(a) -> jax.ShapeDtypeStruct:
    """``a`` as jit sees it at a call: shape, dtype, weak type, and the
    sharding of a committed array (an uncommitted one, or a host array,
    leaves its placement to the program)."""
    aval = jax.typeof(a)
    return jax.ShapeDtypeStruct(
        aval.shape, aval.dtype, weak_type=aval.weak_type,
        sharding=a.sharding if getattr(a, "committed", False) else None,
    )


class _StepProgram:
    """The program the step loop dispatches, traced and lowered once a
    Trainer and compiled (or fetched from the persistent cache) from that
    same ``Lowered`` at the first dispatch.

    ``args()`` gives the abstract arguments, each taken from the array it
    stands for, so the module is the one jit would make at that call.
    From the first dispatch on the loop calls the ``Compiled``, which
    refuses arguments whose avals or shardings differ: an argument the
    lowering did not foresee fails loudly instead of recompiling.
    """

    def __init__(self, fn, args):
        self._fn, self._args = fn, args
        self._lowered = self._compiled = None

    def lowered(self):
        if self._lowered is None:
            self._lowered = self._fn.lower(*self._args())
        return self._lowered

    def __call__(self, *args):
        if self._compiled is None:
            self._compiled = self.lowered().compile()
            self._lowered = None  # the executable is all that runs now
        return self._compiled(*args)


# TrainConfig lives in training/config.py (jax-free — the sweep/fleet
# orchestrators import it without backend startup); re-exported here so
# `from ...training.trainer import TrainConfig` keeps working everywhere.
class Trainer:
    def _host_state(self):
        """The state as host-fetchable (np) arrays — replicated (non-SPMD)
        path only. The GSPMD path never materializes full state on a host:
        it saves/restores per-process shards (checkpoint.save_sharded /
        restore_sharded), so this method no longer gathers anything.
        """
        assert not self.use_spmd, (
            "GSPMD states use sharded checkpoints; full-state "
            "materialization would be an O(model) gather per host"
        )
        return self.state

    def __init__(self, config: TrainConfig, devices=None):
        # set-up's spans (observability/spans.py): the run's registry is
        # made first, so the spans that close before the stream opens
        # observe into it; the compile listener charges each program to
        # the span that caused it
        self._setup = SetupLog(obs.MetricRegistry())
        compiles.install()
        with self._setup.span("setup/init"):
            self._init(config, devices)

    def _init(self, config: TrainConfig, devices) -> None:
        self.config = c = config
        import jax.numpy as jnp

        self._fused_step = None  # set when batch prep fuses into the step
        # Fail a bad --flightrec spec FIRST: a typo'd detector must cost
        # seconds at flag validation, never a warmed-up run.
        self._flightrec_spec = None
        if c.flightrec:
            from pytorch_distributed_nn_tpu.observability.detect import (
                DetectorSpec,
            )

            self._flightrec_spec = DetectorSpec.parse(c.flightrec)
        self.is_text = is_text_model(c.network)
        self.use_spmd = c.tensor_parallel > 1 or c.seq_parallel > 1
        if self.use_spmd:
            if not self.is_text:
                raise ValueError(
                    "tensor/sequence parallelism applies to text models "
                    f"(got network={c.network!r}; the CNN zoo has no "
                    "sharded-parameter annotations)"
                )
            if (
                c.sync_mode != "allreduce"
                or c.compression not in ("none", "int8")
                or c.kill_ranks
            ):
                raise ValueError(
                    "tp/sp use the GSPMD path: gradient sync is the "
                    "compiler-inserted all-reduce (sync_mode='allreduce') "
                    "or its int8-quantized form (compression='int8', "
                    "training/spmd._int8_spmd_step); PS emulation, topk "
                    "compression and kill_ranks are shard_map-DP features "
                    "(tp=sp=1)"
                )
            if c.grad_accum > 1 and c.compression == "int8":
                raise ValueError(
                    "grad_accum>1 with compression='int8' under tp/sp is "
                    "not implemented (the quantized dp sync would need "
                    "the microbatch scan inside its manual region); use "
                    "one or the other"
                )
            if c.seq_attn not in ("ring", "ulysses"):
                raise ValueError(f"unknown seq_attn {c.seq_attn!r}")
            if c.attn_impl == "pallas" and c.seq_parallel > 1:
                raise ValueError(
                    "attn_impl='pallas' composes with tensor parallelism "
                    "(heads shard over the model axis and each shard runs "
                    "the flash kernel) but not with seq_parallel > 1: sp "
                    "uses ring/ulysses attention, whose per-device inner "
                    "step is already flash-style"
                )
        # --- elastic resume (resilience/elastic.py) ---
        # BEFORE the mesh is built: when the fleet shrank, make_mesh with
        # the old num_workers would fail outright; the plan re-derives a
        # legal data-parallel degree from the devices actually present and
        # the checkpoint's recorded geometry, preserving the global batch.
        self._elastic_plan = None
        if c.resume:
            from pytorch_distributed_nn_tpu.resilience import elastic

            avail = len(devices) if devices is not None else len(jax.devices())
            plan = elastic.plan_resume(
                c.train_dir, avail,
                batch_size=c.batch_size, num_workers=c.num_workers,
                grad_accum=c.grad_accum, tensor_parallel=c.tensor_parallel,
                seq_parallel=c.seq_parallel,
            )
            if plan is not None and plan.changed and c.strict_geometry:
                raise elastic.strict_geometry_error(plan, c.train_dir)
            # Adopt the derived dp when the geometry changed, OR when the
            # REQUESTED degree cannot build on the live fleet at all —
            # e.g. re-running the original `--num-workers 8` command
            # against a train_dir whose newest checkpoint was already
            # written on the shrunk 4-device mesh: geometry "unchanged",
            # but make_mesh(8) would still die on 4 devices.
            cap = avail // max(c.tensor_parallel * c.seq_parallel, 1)
            impossible = c.num_workers is not None and c.num_workers > cap
            if plan is not None and not c.strict_geometry and (
                plan.changed or impossible
            ):
                if impossible and not plan.changed:
                    logger.warning(
                        "Elastic resume: --num-workers %d exceeds the %d "
                        "available device(s); continuing on the "
                        "checkpoint's own dp=%d",
                        c.num_workers, avail, plan.num_workers,
                    )
                # the EFFECTIVE config (what the run manifest records):
                # dp degree and microbatching follow the live fleet
                c.num_workers = plan.num_workers
                c.grad_accum = plan.grad_accum
                if plan.changed:
                    self._elastic_plan = plan
                    logger.warning(
                        "Elastic resume engaged: %s", plan.describe()
                    )
        self.mesh = make_mesh(
            c.num_workers, c.tensor_parallel, c.seq_parallel, devices=devices
        )
        self.n_workers = num_workers(self.mesh)
        # written-on geometry: stamped into every checkpoint manifest this
        # run publishes, the telemetry run-manifest and heartbeat.json —
        # what the NEXT resume's elastic plan compares against
        self._geometry = ckpt.mesh_geometry(self.mesh)
        if c.batch_size % self.n_workers:
            raise ValueError(
                f"global batch {c.batch_size} not divisible by "
                f"{self.n_workers} data-parallel workers"
            )
        if c.grad_accum < 1:
            raise ValueError(f"grad_accum must be >= 1, got {c.grad_accum}")
        if c.warmup_steps < 0:
            raise ValueError(
                f"warmup_steps must be >= 0, got {c.warmup_steps}"
            )
        if c.keep_last is not None and c.keep_last < 1:
            raise ValueError(f"keep_last must be >= 1, got {c.keep_last}")
        if c.overlap_eval and not (c.async_ckpt and c.eval_freq):
            raise ValueError(
                "overlap_eval runs the eval pass on the async checkpoint "
                "snapshot; it requires async_ckpt=True and eval_freq > 0"
            )
        if c.batch_size % (self.n_workers * c.grad_accum):
            raise ValueError(
                f"global batch {c.batch_size} not divisible by "
                f"{self.n_workers} workers x grad_accum={c.grad_accum} "
                "microbatches"
            )
        if c.sync_mode == "local" and self.n_workers > 1:
            raise ValueError("sync_mode='local' requires a single-device mesh")
        if c.kill_ranks:
            bad = [k for k in c.kill_ranks if not 0 <= k < self.n_workers]
            if bad:
                raise ValueError(
                    f"kill_ranks {bad} out of range for "
                    f"{self.n_workers} data-parallel workers"
                )
            if len(set(c.kill_ranks)) >= self.n_workers:
                raise ValueError(
                    "kill_ranks names every data-parallel worker — "
                    "no gradients would ever be aggregated"
                )

        num_classes = 100 if c.dataset == "Cifar100" else 10
        dtype = {"float32": jnp.float32, "bfloat16": jnp.bfloat16}[c.dtype]
        if self.is_text and c.dataset not in TEXT_DATASETS:
            raise ValueError(
                f"text model {c.network!r} requires dataset='MLMSynth' "
                f"or 'NextTokenSynth' (got {c.dataset!r})"
            )
        if not self.is_text and c.dataset in TEXT_DATASETS:
            raise ValueError(
                f"dataset={c.dataset!r} requires a text model "
                f"(got {c.network!r})"
            )
        model_kw = {"dtype": dtype}
        if self.is_text and c.vocab_size is not None:
            model_kw["vocab_size"] = c.vocab_size
        if self.is_text and c.seq_len is not None:
            model_kw["max_len"] = c.seq_len
        if c.remat:
            if not self.is_text:
                raise ValueError(
                    "remat applies to text models (the CNN zoo's "
                    "activations are small; use it for long sequences)"
                )
            model_kw["remat"] = True
        if c.fused_ln:
            if not self.is_text:
                raise ValueError(
                    "fused_ln only applies to text models "
                    f"(got network={c.network!r})"
                )
            if self.use_spmd:
                # the pallas_call has no GSPMD partitioning rule — under
                # tp/sp the partitioner would replicate it (gathering the
                # full activation), a silent pessimization; the shard_map
                # dp path runs it on concrete per-device shards instead
                raise ValueError(
                    "fused_ln is not supported under tensor/sequence "
                    "parallelism yet (GSPMD has no partitioning rule for "
                    "the LN custom call); drop --fused-ln or tp/sp"
                )
            model_kw["fused_ln"] = True
        if c.attn_impl not in ("full", "pallas"):
            raise ValueError(f"unknown attn_impl {c.attn_impl!r}")
        if c.attn_impl == "pallas":
            if not self.is_text:
                raise ValueError(
                    "attn_impl='pallas' only applies to text models "
                    f"(got network={c.network!r}, which has no attention)"
                )
            if self.use_spmd:
                # tp-only (sp=1, already validated): run the flash kernel
                # per head shard under shard_map over (data, model)
                from pytorch_distributed_nn_tpu.parallel.ring_attention import (
                    make_tp_flash_attn,
                )

                model_kw["attn_fn"] = make_tp_flash_attn(self.mesh)
            else:
                from pytorch_distributed_nn_tpu.ops.pallas_kernels import (
                    pallas_attention,
                )

                model_kw["attn_fn"] = pallas_attention
        if self.use_spmd and c.seq_parallel > 1:
            from pytorch_distributed_nn_tpu.parallel.ring_attention import (
                make_mesh_attn,
            )

            model_kw["attn_fn"] = make_mesh_attn(self.mesh, c.seq_attn)
        with self._setup.span("setup/model"):
            self.model = build_model(c.network, num_classes, **model_kw)
            if self.use_spmd:
                heads = self.model.config.num_heads
                if heads % c.tensor_parallel:
                    raise ValueError(
                        f"num_heads={heads} not divisible by "
                        f"tensor_parallel={c.tensor_parallel} (heads shard "
                        "over the model axis)"
                    )
                if (
                    c.seq_parallel > 1
                    and c.seq_attn == "ulysses"
                    and (heads // c.tensor_parallel) % c.seq_parallel
                ):
                    raise ValueError(
                        f"ulysses needs heads/tp={heads // c.tensor_parallel} "
                        f"divisible by seq_parallel={c.seq_parallel} "
                        "(all-to-all re-shards seq->heads); use seq_attn='ring'"
                    )
            if c.warmup_steps or c.lr_decay_steps:
                # Linear warmup 0 -> lr over warmup_steps, then (optionally)
                # step decay. The reference had NO schedule at all; decay came
                # in round 2 for the CIFAR recipes, warmup in round 3 because
                # large-vocab transformer runs need it (an un-warmed Adam at
                # transformer-scale lr sits at the uniform plateau — measured
                # on the BERT-base convergence runs, docs/artifacts).
                warm = c.warmup_steps
                decay_every = c.lr_decay_steps

                def lr(count):
                    scale = 1.0
                    if warm:
                        scale = jnp.minimum(1.0, (count + 1) / warm)
                    if decay_every:
                        scale = scale * (
                            c.lr_decay_factor ** (count // decay_every)
                        )
                    return c.lr * scale
            else:
                lr = c.lr
            self.optimizer = build_optimizer(
                c.optimizer, lr, momentum=c.momentum,
                weight_decay=c.weight_decay, nesterov=c.nesterov,
            )
            self.fault_plan = None
            if c.faults:
                self.fault_plan = FaultPlan.parse(c.faults, seed=c.seed)
                bad_rank = self.fault_plan.max_rank_referenced()
                if bad_rank >= self.n_workers:
                    raise ValueError(
                        f"fault plan references rank p{bad_rank} but the mesh "
                        f"has {self.n_workers} data-parallel workers"
                    )
                if self.is_text and any(
                    e.kind == "nan_grad" for e in self.fault_plan.entries
                ):
                    raise ValueError(
                        "nan_grad faults poison the float image batch; text "
                        "batches are integer token ids (no NaN representation)"
                    )
                logger.info("Fault plan: %s", self.fault_plan.describe())
            self._straggler_sim = None
            if c.straggler_deadline is not None:
                if self.use_spmd:
                    raise ValueError(
                        "straggler simulation masks per-replica gradients "
                        "inside the shard_map DP sync; the GSPMD (tp/sp) "
                        "all-reduce has no per-replica contribution to drop"
                    )
                from pytorch_distributed_nn_tpu.resilience.stragglers import (
                    make_straggler_sim,
                )

                self._straggler_sim = make_straggler_sim(
                    c.straggler_deadline,
                    min_keep=c.straggler_min_keep,
                    fault_plan=self.fault_plan,
                )
            if c.skip_nonfinite and self.use_spmd:
                raise ValueError(
                    "skip_nonfinite guards the shard_map DP step; the GSPMD "
                    "(tp/sp) step has no non-finite guard yet"
                )
            self.grad_sync = make_grad_sync(
                c.sync_mode,
                num_aggregate=c.num_aggregate,
                compression=c.compression,
                topk_ratio=c.topk_ratio,
                bucket_bytes=c.bucket_bytes,
                kill_ranks=tuple(c.kill_ranks),
                straggler=self._straggler_sim,
            )
            if self.is_text:
                self.seq_len = c.seq_len or input_spec(c.network)[0]
                self.vocab_size = c.vocab_size or self.model.config.vocab_size
                # targets a position is trained on: a model with next-token
                # prediction modules is trained on tokens further ahead too
                # (labels and logits gain a depth axis)
                self.label_depth = getattr(
                    self.model.config, "label_depth", 1)
                if self.label_depth > 1 and (
                        c.dataset != "NextTokenSynth" or c.data_path
                        or self.use_spmd):
                    raise ValueError(
                        f"{c.network!r} predicts {self.label_depth} tokens "
                        "a position: it trains on dataset='NextTokenSynth' "
                        "(synthetic, not streamed from --data-path) on the "
                        "data-parallel path (tp = sp = 1)")
                in_shape, in_dtype = (self.seq_len,), jnp.int32
                if self.seq_len % c.seq_parallel:
                    raise ValueError(
                        f"seq_len {self.seq_len} not divisible by "
                        f"seq_parallel={c.seq_parallel}"
                    )
            else:
                in_shape, in_dtype = input_spec(c.network), jnp.float32
            if self.use_spmd:
                from pytorch_distributed_nn_tpu.training.spmd import (
                    create_spmd_state,
                )

                self.state, self._spmd_shardings = create_spmd_state(
                    self.model, self.optimizer, jax.random.PRNGKey(c.seed),
                    (c.batch_size, self.seq_len), self.mesh,
                )
            else:
                self.state = create_train_state(
                    self.model,
                    self.optimizer,
                    self.grad_sync,
                    jax.random.PRNGKey(c.seed),
                    in_shape,
                    num_replicas=self.n_workers,
                    input_dtype=in_dtype,
                )
                # a model whose expert bias is a balancing buffer starts
                # where its rule settles (Glm47Flash.balance_routing)
                balance = getattr(self.model, "balance_routing", None)
                if balance is not None:
                    self.state = self.state.replace(params=balance(
                        self.state.params,
                        jax.random.fold_in(jax.random.PRNGKey(c.seed), 1),
                        self.seq_len))
            self.start_step = 0
            if c.warm_start:
                if c.resume:
                    raise ValueError(
                        "warm_start and resume are mutually exclusive: resume "
                        "restores this run's own checkpoints (same geometry + "
                        "optimizer state); warm_start performs cross-geometry "
                        "parameter surgery from another run's checkpoint"
                    )
                from pytorch_distributed_nn_tpu.training.warm_start import (
                    warm_start_params,
                )

                tgt = self.state.params
                if self.use_spmd and jax.process_count() > 1:
                    # GSPMD params span processes (non-addressable shards);
                    # np.asarray on them raises. Fetch the replicated global
                    # value on every host for the (host-side) merge surgery —
                    # tiled=True is the global-array mode of process_allgather.
                    from jax.experimental import multihost_utils

                    tgt = multihost_utils.process_allgather(tgt, tiled=True)
                merged = warm_start_params(
                    c.warm_start, jax.tree.map(np.asarray, tgt)
                )
                if jax.process_count() > 1:
                    # The copied overlap comes from the shared file, but the
                    # fresh/resized-tail values come from each process's own
                    # model init — identical only while init stays seeded and
                    # process-independent. A divergent init would silently
                    # desync the "replicated" params across hosts, so verify
                    # the whole merged tree agrees before materializing it.
                    import hashlib

                    from jax.experimental import multihost_utils

                    h = hashlib.sha256()
                    for leaf in jax.tree.leaves(merged):
                        h.update(np.ascontiguousarray(leaf).tobytes())
                    # int32 pair, not int64: x64-disabled JAX would silently
                    # truncate the device round-trip inside process_allgather
                    dig = np.frombuffer(h.digest()[:8], dtype=np.int32)
                    all_dig = multihost_utils.process_allgather(dig)
                    if not (all_dig == dig).all():
                        raise RuntimeError(
                            "warm_start produced different merged params on "
                            "different processes (digests "
                            f"{np.unique(all_dig).tolist()}); model init must "
                            "be seeded identically on every host"
                        )

                def _put(a, old):
                    a = np.asarray(a, dtype=old.dtype)
                    if self.use_spmd:
                        # create_spmd_state built real global shardings.
                        target = old.sharding
                    else:
                        # The shard_map path keeps params REPLICATED over the
                        # mesh (state_spec P() in build_train_step). old's
                        # arrays are uncommitted (SingleDeviceSharding), and
                        # committing the merged params there would pin the
                        # whole state to device 0 — fatal under multi-process
                        # meshes ("incompatible devices" at the first step).
                        target = jax.sharding.NamedSharding(
                            self.mesh, jax.sharding.PartitionSpec()
                        )
                    if jax.process_count() > 1:
                        # Multi-host: the merged tree is host-global and
                        # deterministic (every process reads the same file),
                        # so each process materializes just its addressable
                        # shards. c.warm_start must be readable on all hosts
                        # (same contract as the pod tooling's shared dirs).
                        return jax.make_array_from_callback(
                            a.shape, target, lambda idx, a=a: a[idx]
                        )
                    return jax.device_put(jnp.asarray(a), target)

                self.state = self.state.replace(
                    params=jax.tree.map(_put, merged, self.state.params)
                )
            if c.resume and self.use_spmd:
                # Sharded resume: every process reads its OWN shards from the
                # shared train_dir and the state lands on the mesh already
                # partitioned — no host ever holds the full model. Elastic
                # resumes route through restore_resharded (file-or-dir,
                # reshard-on-load); exact-geometry resumes keep the direct
                # restore_sharded path.
                def _restore(path, template):
                    if self._elastic_plan is not None:
                        return ckpt.restore_resharded(
                            path, template, self._spmd_shardings
                        )
                    return ckpt.restore_sharded(
                        path, template, self._spmd_shardings
                    )

                if jax.process_count() > 1:
                    # the step to resume from is agreed via a tiny int
                    # broadcast (hosts could otherwise race a checkpoint
                    # being published); no quarantine walk — renames on a
                    # shared dir cannot be coordinated from here
                    from jax.experimental import multihost_utils

                    step = ckpt.latest_step(c.train_dir)
                    step = int(
                        multihost_utils.broadcast_one_to_all(
                            np.int64(-1 if step is None else step)
                        )
                    )
                    step = None if step < 0 else step
                    if step is not None:
                        self.state = _restore(
                            ckpt.checkpoint_path(c.train_dir, step), self.state
                        )
                        self.start_step = step
                        logger.info("Resumed from step %d (sharded)", step)
                else:
                    # single-controller: the VALIDATED scan — per-shard CRCs
                    # are checked per candidate, corrupt steps (including one
                    # convicted mid-reshard) are quarantined and the scan
                    # falls back to the previous valid step
                    from pytorch_distributed_nn_tpu.resilience.supervisor import (
                        resume_latest_valid,
                    )

                    restored = resume_latest_valid(
                        c.train_dir, self.state, restore_fn=_restore
                    )
                    if restored is not None:
                        self.state = restored
                        self.start_step = int(jax.device_get(restored.step))
                        logger.info(
                            "Resumed from step %d (sharded)", self.start_step
                        )
            elif c.resume:
                # only process 0 reads the checkpoint (it is the only writer);
                # the others receive the state via the broadcast below rather
                # than each pulling GBs from a shared train_dir. The scan is
                # the VALIDATED one: each candidate is checked against its
                # CRC32 manifest, corrupt entries are quarantined into
                # <train_dir>/quarantine/, and the newest intact step wins —
                # a torn checkpoint costs one interval, never the run.
                from pytorch_distributed_nn_tpu.resilience.supervisor import (
                    resume_latest_valid,
                )

                template = self._host_state()
                # elastic: restore_resharded tolerates a geometry change (the
                # replicated state is mesh-independent except the per-replica
                # EF residuals, which it resets with a warning); exact-match
                # resumes keep the existing restore_checkpoint path bitwise.
                restore_fn = None
                if self._elastic_plan is not None:
                    restore_fn = lambda p, t: ckpt.restore_resharded(p, t, None)
                restored = (
                    resume_latest_valid(
                        c.train_dir, template, restore_fn=restore_fn
                    )
                    if jax.process_index() == 0
                    else None
                )
                if jax.process_count() > 1:
                    # Only process 0 writes checkpoints, and train_dir may be
                    # host-local: without a broadcast the other processes would
                    # restore nothing, start at step 0 while process 0 starts at
                    # step N, and the per-process step loops would issue
                    # different numbers of collectives (desync/hang).
                    from jax.experimental import multihost_utils

                    found = bool(
                        multihost_utils.broadcast_one_to_all(
                            np.int32(1 if restored is not None else 0)
                        )
                    )
                    if found:
                        restored = multihost_utils.broadcast_one_to_all(
                            restored if restored is not None else template
                        )
                    else:
                        restored = None
                if restored is not None:
                    self.state = restored
                    self.start_step = int(restored.step)
                    logger.info("Resumed from step %d", self.start_step)

        with self._setup.span("setup/step_build"):
            if self.use_spmd:
                from pytorch_distributed_nn_tpu.training.spmd import (
                    build_spmd_eval_step,
                    build_spmd_train_step,
                    text_batch_sharding,
                )

                # Under GSPMD jit the loss's masked mean is computed over the
                # GLOBAL (unsharded) arrays — no per-replica normalization
                # wrappers needed; the partitioner inserts the reductions.
                self.train_step = build_spmd_train_step(
                    self.model, self.optimizer, self.mesh, self._spmd_shardings,
                    compression=c.compression, grad_accum=c.grad_accum,
                )
                self.eval_step = build_spmd_eval_step(
                    self.model, self.mesh, self._spmd_shardings
                )
                sharding = text_batch_sharding(self.mesh)
            else:
                step_fns = {}
                if self.is_text:
                    from pytorch_distributed_nn_tpu.parallel.mesh import DATA_AXIS

                    step_fns = {
                        # normalize by the GLOBAL masked-token count
                        # (per-replica counts differ; see
                        # make_global_masked_cross_entropy)
                        "loss_fn": make_global_masked_cross_entropy(DATA_AXIS),
                        "metrics_fn": make_global_mlm_metrics(DATA_AXIS),
                    }
                    if self.label_depth > 1:
                        # beside the loss over every depth: each depth's own
                        # (loss_main, loss_mtp)
                        mlm = step_fns["metrics_fn"]
                        depths = make_global_depth_losses(
                            DATA_AXIS, self.label_depth)
                        step_fns["metrics_fn"] = lambda logits, labels: {
                            **mlm(logits, labels), **depths(logits, labels)}
                train_step_fns = step_fns
                if self.is_text:
                    from pytorch_distributed_nn_tpu.ops.metrics import mlm_sums

                    # grad_accum>1: exact (Σ masked-xent, Σ count)
                    # accumulation — the same global masked mean, never the
                    # biased mean-of-masked-means (mlm_sums docstring).
                    # Train-step only; eval never accumulates.
                    train_step_fns = {**step_fns, "pair_accum_fn": mlm_sums}
                self.train_step = build_train_step(
                    self.model, self.optimizer, self.grad_sync, self.mesh,
                    bn_stats_sync=c.bn_stats_sync, grad_accum=c.grad_accum,
                    nonfinite_guard=c.skip_nonfinite,
                    **train_step_fns,
                )
                self.eval_step = build_eval_step(self.model, self.mesh, **step_fns)
                sharding = batch_sharding(self.mesh)
                if jax.process_count() == 1:
                    # A fresh or restored state is uncommitted, while the step
                    # returns it committed to the mesh: left alone, step 2
                    # misses the jit cache and the whole train step compiles
                    # twice. Commit it to the step's own output shardings:
                    # replicated, except the per-replica EF residuals, whose
                    # leading axis is split over the data axis like a batch.
                    rep = replicated_sharding(self.mesh)
                    placed = jax.tree.map(lambda _: rep, self.state)
                    if self.state.ef_state is not None:
                        placed = placed.replace(ef_state=jax.tree.map(
                            lambda _: sharding, self.state.ef_state
                        ))
                    self.state = jax.device_put(self.state, placed)
        self._batch_sharding = sharding  # what the train loader places
        with self._setup.span("setup/data"):
            stream_meta = None
            if c.data_path:
                from pytorch_distributed_nn_tpu.data.streaming import load_meta

                stream_meta = load_meta(c.data_path)
                want = "tokens" if self.is_text else "image"
                if stream_meta["kind"] != want:
                    raise ValueError(
                        f"{c.data_path} holds {stream_meta['kind']!r} shards "
                        f"but network {c.network!r} needs {want!r} data"
                    )
            if self.is_text:
                if stream_meta is not None:
                    from pytorch_distributed_nn_tpu.data.streaming import (
                        StreamingLoader,
                    )

                    if int(stream_meta["vocab_size"]) > self.vocab_size:
                        raise ValueError(
                            f"shard corpus vocab {stream_meta['vocab_size']} "
                            f"exceeds the model's vocab_size={self.vocab_size};"
                            " pass --vocab-size >= the exported corpus's"
                        )
                    self.train_loader = StreamingLoader(
                        c.data_path, c.batch_size, seq_len=self.seq_len,
                        mask_prob=c.mask_prob, vocab_size=self.vocab_size,
                        seed=c.seed, sharding=sharding,
                        prefetch=c.stream_prefetch, workers=c.loader_workers,
                    )
                else:
                    self.train_loader = MLMLoader(
                        TEXT_DATASETS[c.dataset](
                            vocab_size=self.vocab_size, seq_len=self.seq_len,
                            batch_size=c.batch_size, seed=c.seed,
                            mask_prob=c.mask_prob, branching=c.corpus_branching,
                            **self._depth_kw(),
                        ),
                        sharding=sharding,
                    )
                test_bs = max(
                    self.n_workers,
                    c.test_batch_size - c.test_batch_size % self.n_workers,
                )
                self.test_loader = MLMLoader(
                    TEXT_DATASETS[c.dataset](
                        vocab_size=self.vocab_size, seq_len=self.seq_len,
                        batch_size=test_bs, seed=c.seed + 10_000,
                        mask_prob=c.mask_prob, branching=c.corpus_branching,
                        corpus_seed=c.seed,  # same language as training
                        **self._depth_kw(),
                    ),
                    sharding=sharding,
                    eval_batches=c.eval_batches,
                )
            elif stream_meta is not None:
                # Streaming image input: the training set never materializes
                # in host RAM (per-host shard files + bounded prefetch); only
                # the (small) test split stays in-memory for the eval pass.
                from pytorch_distributed_nn_tpu.data.streaming import (
                    StreamingLoader,
                )

                num_classes_meta = int(stream_meta.get("num_classes", 0))
                if num_classes_meta and num_classes_meta != num_classes:
                    raise ValueError(
                        f"{c.data_path} was exported from a "
                        f"{num_classes_meta}-class dataset "
                        f"({stream_meta.get('name')!r}) but --dataset "
                        f"{c.dataset!r} has {num_classes} classes"
                    )
                self.train_loader = StreamingLoader(
                    c.data_path, c.batch_size, seed=c.seed, sharding=sharding,
                    prefetch=c.stream_prefetch, workers=c.loader_workers,
                )
                test_ds = load_dataset(c.dataset, train=False,
                                       data_dir=c.data_dir,
                                       synthetic_size=c.synthetic_size)
                test_bs = min(
                    c.test_batch_size,
                    (len(test_ds) // self.n_workers) * self.n_workers,
                )
                test_bs = max(self.n_workers, test_bs - test_bs % self.n_workers)
                self.test_loader = DataLoader(
                    test_ds, test_bs, shuffle=False, sharding=sharding,
                )
            else:
                if c.data_layout not in ("auto", "device", "host"):
                    raise ValueError(f"unknown data_layout {c.data_layout!r}")
                train_ds = load_dataset(c.dataset, train=True, data_dir=c.data_dir,
                                        synthetic_size=c.synthetic_size)
                test_ds = load_dataset(c.dataset, train=False, data_dir=c.data_dir,
                                       synthetic_size=c.synthetic_size)
                # auto: device-resident when the uint8 datasets fit a modest
                # HBM budget (every reference dataset does — CIFAR 184 MB
                # total); past that, the host prefetch loader.
                data_bytes = train_ds.raw_images.nbytes + test_ds.raw_images.nbytes
                use_device = c.data_layout == "device" or (
                    c.data_layout == "auto" and data_bytes < 2 << 30
                )
                test_bs = min(
                    c.test_batch_size,
                    (len(test_ds) // self.n_workers) * self.n_workers,
                )
                test_bs = max(self.n_workers, test_bs - test_bs % self.n_workers)
                if use_device:
                    if c.loader_workers > 0:
                        logger.warning(
                            "--loader-workers %d ignored: data_layout resolved "
                            "to 'device' (batches are built on-chip; there is "
                            "no host loader to parallelize). Pass "
                            "--data-layout host to use the worker threads.",
                            c.loader_workers,
                        )
                    from pytorch_distributed_nn_tpu.data.loader import (
                        DeviceDataLoader,
                    )

                    self.train_loader = DeviceDataLoader(
                        train_ds, c.batch_size, self.mesh, shuffle=True,
                        seed=c.seed,
                    )
                    self.test_loader = DeviceDataLoader(
                        test_ds, test_bs, self.mesh, shuffle=False,
                    )
                    # Fuse batch construction INTO the jitted train step: one
                    # program (and one dispatch) per step does gather + augment
                    # + normalize + fwd/bwd + sync + update. Rebuild the step
                    # WITHOUT donation (state donation moves to the fused
                    # wrapper) and keep exactly one step function around.
                    self.train_step = inner = build_train_step(
                        self.model, self.optimizer, self.grad_sync, self.mesh,
                        bn_stats_sync=c.bn_stats_sync, donate=False,
                        grad_accum=c.grad_accum,
                        nonfinite_guard=c.skip_nonfinite,
                    )
                    prep = self.train_loader.prep_fn

                    self._fused_step = jax.jit(
                        lambda state, images, labels, idx, key, rng: inner(
                            state, prep(images, labels, idx, key), rng
                        ),
                        donate_argnums=(0,),
                    )
                else:
                    self.train_loader = DataLoader(
                        train_ds, c.batch_size, shuffle=True, seed=c.seed,
                        sharding=sharding, workers=c.loader_workers,
                    )
                    self.test_loader = DataLoader(
                        test_ds, test_bs, shuffle=False, sharding=sharding,
                    )
        if (
            self.fault_plan is not None
            and self._fused_step is not None
            and any(e.kind == "nan_grad" for e in self.fault_plan.entries)
        ):
            raise ValueError(
                "nan_grad faults poison the HOST batch, but data_layout "
                "resolved to 'device' (batches are built on-chip and "
                "never pass through the host); run with "
                "data_layout='host' to use nan_grad injection"
            )
        # The program the loop dispatches: the fused step where the batch
        # is built on the device, the train step otherwise. Lowered once,
        # for the manifest's step cost below or else at the first dispatch,
        # and compiled from that same Lowered.
        self._step = _StepProgram(
            self.train_step if self._fused_step is None else self._fused_step,
            self._step_args,
        )
        # --- unified telemetry (observability/, docs/observability.md) ---
        # One self-describing JSONL stream per run: explicit --metrics-path
        # wins; otherwise any run that already owns a train_dir (supervised
        # or checkpointing) gets its per-process stream there — rank 0
        # keeps <train_dir>/telemetry.jsonl, other processes of a pod get
        # telemetry-rank<k>.jsonl so a shared train_dir never interleaves
        # appends (obs summary --by-rank merges the family). Plain
        # in-memory runs (unit tests, sweeps) keep a sink-less registry.
        telemetry_path = c.metrics_path
        if telemetry_path is None and (c.supervise or c.eval_freq):
            telemetry_path = os.path.join(
                c.train_dir, obs.stream_basename(jax.process_index())
            )
        from pytorch_distributed_nn_tpu.parallel.mesh import axis_sizes

        mesh_shape = axis_sizes(self.mesh)
        sync_bytes = (
            None if self.use_spmd
            else self.grad_sync.estimate_sync_bytes(self.state.params)
        )
        # Static efficiency accounting (docs/observability.md "Efficiency"):
        # stamp the step's FLOPs/bytes + backend peaks into the manifest so
        # every consumer — the live MFU gauges (core._derive_efficiency),
        # `obs summary`'s efficiency section, incident reports — derives
        # utilization from ONE recorded cost, read from the Lowered that the
        # first dispatch compiles. Sink-less runs (unit tests, sweeps) skip
        # it: their step is lowered at its first dispatch instead.
        step_cost = None
        if telemetry_path is not None:
            try:
                with self._setup.span("setup/step_cost"):
                    step_cost = self._static_step_cost(
                        self._step.lowered(), sync_bytes
                    )
            except Exception:
                # On an accelerator a run without efficiency telemetry is
                # a run nobody can price: fail it. On the CPU (tests,
                # planning) utilization is a relative signal and the run
                # is worth more than its manifest block.
                if jax.default_backend() != "cpu":
                    raise
                logger.exception(
                    "static step-cost accounting failed (run continues "
                    "without efficiency telemetry)"
                )
        manifest = obs.run_manifest(
            config=dataclasses.asdict(c),
            mesh_shape=mesh_shape,
            # full geometry record (device/process counts + mesh factors):
            # what elastic resume falls back to for pre-geometry
            # checkpoints, and what lets `obs summary` / incident bundles
            # attribute elastic transitions across a run's lifetimes
            geometry=self._geometry,
            param_count=param_count(self.state.params),
            param_bytes=tree_bytes(self.state.params),
            sync_bytes_per_step=sync_bytes,
            start_step=self.start_step,
            step_cost=step_cost,
        )
        self.telemetry = obs.Telemetry.for_run(
            telemetry_path, manifest, registry=self._setup.registry
        )
        self._setup.telemetry = self.telemetry
        reg = self.telemetry.registry
        reg.gauge("num_workers", help="data-parallel degree").set(
            self.n_workers
        )
        if sync_bytes is not None:
            reg.gauge(
                "sync_bytes_per_step",
                help="estimated per-replica gradient payload per sync",
            ).set(sync_bytes)
        # process default for the run: retry/checkpoint/fault/eval emitters
        # land their events in THIS run's stream
        self._prev_telemetry = obs.install(self.telemetry)
        self._compiles = compiles.route(self.telemetry)

        if self._elastic_plan is not None:
            # typed record of the geometry transition — first event of the
            # resumed lifetime, right after its manifest header
            self.telemetry.emit(
                "elastic_resume", step=self.start_step,
                **self._elastic_plan.event_fields(),
            )

        # --- flight recorder (observability/flightrec.py) ---
        # Built AFTER the telemetry install so the detectors see every
        # event the run emits. Process 0 only: bundles live under the
        # (possibly shared) train_dir and the profiler window is already
        # cluster-wide on a pod.
        self._flightrec = None
        if self._flightrec_spec is not None and jax.process_index() == 0:
            from pytorch_distributed_nn_tpu.observability.flightrec import (
                FlightRecorder,
            )

            self._flightrec = FlightRecorder(
                c.train_dir, self.telemetry, self._flightrec_spec,
            )
            logger.info(
                "Flight recorder armed: %s", self._flightrec_spec.describe()
            )

        # --- zero-stall checkpoint pipeline (training/async_ckpt.py) ---
        # Built AFTER the telemetry install so the writer thread's events
        # land in this run's stream. Emergency saves stay synchronous and
        # drain this pipeline first (_emergency_save).
        self._async_ckpt = None
        self._overlap_eval_thread = None
        if c.eval_freq and c.async_ckpt:
            from pytorch_distributed_nn_tpu.training.async_ckpt import (
                AsyncCheckpointer,
            )

            self._async_ckpt = AsyncCheckpointer(
                c.train_dir, sharded=self.use_spmd, keep_last=c.keep_last,
                geometry=self._geometry,
            )

        if self.start_step:
            # Resume continues the DATA stream too: without this, a
            # resumed run replays the stream from batch 0 (the reference
            # shared the same gap — its workers restarted their loader
            # from scratch, src/distributed_worker.py:104-180).
            # Preferred path: the checkpoint's iterator-state sidecar
            # (`model_step_<N>.data.json`) restores the EXACT stream
            # position — shard cursor, packer carry, prefetch-consumed
            # count — which is what makes the batch sequence (not just
            # the params) bitwise-deterministic across a crash (chaos
            # scenario data_resume). Sidecar-less checkpoints (legacy, or
            # a torn sidecar) fall back to counter-based skip; the image
            # DeviceDataLoader reshuffles per epoch and has neither (same
            # epoch-boundary semantics as torch's sampler on restart).
            data_state = ckpt.load_data_state(
                ckpt.checkpoint_path(c.train_dir, self.start_step)
            )
            repart = getattr(
                self.train_loader, "restore_repartitioned", None
            )
            restore = getattr(self.train_loader, "restore", None)
            if data_state is not None and callable(repart):
                # streaming loader: handles BOTH the exact-layout restore
                # and an elastic host-count change — the per-host
                # `shards[k::n]` assignment is re-partitioned for the new
                # host count and global progress is preserved, instead of
                # the old silent skip-based fallback
                try:
                    info = repart(data_state)
                    if info.get("repartitioned"):
                        logger.warning(
                            "Input-pipeline shard layout changed "
                            "(%s -> %s host shards): re-partitioned at "
                            "consumed=%s", info.get("saved_shards"),
                            info.get("shards"), info.get("consumed"),
                        )
                        self.telemetry.emit(
                            "data_refastforward", step=self.start_step,
                            mode="repartition", **info,
                        )
                    else:
                        logger.info(
                            "Restored input-pipeline state at step %d "
                            "(consumed=%s)", self.start_step,
                            info.get("consumed"),
                        )
                except Exception:
                    logger.exception(
                        "iterator-state restore failed; falling back to "
                        "skip-based fast-forward"
                    )
                    data_state = None
            elif data_state is not None and callable(restore):
                try:
                    restore(data_state)
                    logger.info(
                        "Restored input-pipeline state at step %d "
                        "(consumed=%s)", self.start_step,
                        data_state.get("consumed",
                                       data_state.get("counter")),
                    )
                except Exception:
                    logger.exception(
                        "iterator-state restore failed; falling back to "
                        "skip-based fast-forward"
                    )
                    data_state = None
            if data_state is None and hasattr(self.train_loader, "skip"):
                # the replayed skip path is no longer silent: the warning
                # + typed event make a resumed run that fast-forwarded
                # (missing/torn sidecar, failed restore) visible in
                # `obs summary` (docs/data.md)
                logger.warning(
                    "Input pipeline fast-forwarding %d batch(es) by skip "
                    "(no usable iterator-state sidecar)", self.start_step,
                )
                self.telemetry.emit(
                    "data_refastforward", step=self.start_step,
                    mode="skip", batches=self.start_step,
                )
                self.train_loader.skip(self.start_step)
        self.metrics = MetricsLogger(telemetry=self.telemetry)

    def _depth_kw(self) -> dict:
        """The next-token batches' ``depth`` where the model predicts
        further ahead; nothing for every other text model."""
        return {"depth": self.label_depth} if self.label_depth > 1 else {}

    def _step_args(self) -> tuple:
        """The loop's arguments to its step as abstract values, each taken
        from the array it stands for (``_abstract``): the state; the batch
        as the train loader places it, or, fused, the resident set and what
        ``next_indices()`` returns; and the rng ``train()`` makes."""
        import jax.numpy as jnp

        c = self.config
        state = jax.tree.map(_abstract, self.state)
        rng = _abstract(jax.random.PRNGKey(0))
        if self._fused_step is not None:
            loader = self.train_loader
            return (state, _abstract(loader.images), _abstract(loader.labels),
                    *loader.indices_spec(), rng)
        if self.is_text:
            x = y = jax.ShapeDtypeStruct(
                (c.batch_size, self.seq_len), jnp.int32,
                sharding=self._batch_sharding,
            )
            if self.label_depth > 1:
                y = jax.ShapeDtypeStruct(
                    (*x.shape, self.label_depth), jnp.int32,
                    sharding=self._batch_sharding,
                )
        else:
            x = jax.ShapeDtypeStruct(
                (c.batch_size, *input_spec(c.network)), jnp.float32,
                sharding=self._batch_sharding,
            )
            y = jax.ShapeDtypeStruct(
                (c.batch_size,), jnp.int32, sharding=self._batch_sharding
            )
        return state, (x, y), rng

    def _static_step_cost(self, lowered, sync_bytes) -> Optional[dict]:
        """Static FLOPs/bytes of one training step, as the run manifest's
        ``step_cost`` record (docs/observability.md "Efficiency").

        Read from ``lowered``, the step program's one lowering, which the
        first dispatch then compiles: the numbers come from unoptimized
        HLO, so FLOP totals are corrected by XLA's own ``cost_analysis``
        (exact counting), the family split is coarse (no fusions yet) and
        HBM bytes are a pre-fusion UPPER bound; ``source: "lowered"``
        records the flavor, and ``cli analyze --cost`` is the
        optimized-HLO twin when exact bytes matter. On the device data
        layout the program is the fused one, batch construction included.
        All quantities are GLOBAL per step except ``ici_bytes``
        (per-device link traffic, the ring estimate).
        """
        from pytorch_distributed_nn_tpu.analysis import costmodel
        from pytorch_distributed_nn_tpu.analysis.calibration import (
            default_profile,
            peak_flops_per_device,
            predict_step_ms,
        )

        xla_flops = None
        try:
            ca = lowered.cost_analysis()
            if isinstance(ca, (list, tuple)):
                ca = ca[0] if ca else {}
            xla_flops = ca.get("flops")
        except Exception:
            pass
        cost = costmodel.step_cost_from_hlo(
            lowered.as_text(dialect="hlo"),
            xla_flops=xla_flops,
            source="lowered",
        )
        devices = len(self.mesh.devices.reshape(-1))
        if cost.ici_bytes == 0 and sync_bytes and self.n_workers > 1:
            # pre-partition HLO may not spell the collectives out yet;
            # fall back to the ring estimate over the known sync payload
            cost.ici_bytes = (
                2.0 * float(sync_bytes)
                * (self.n_workers - 1) / self.n_workers
            )
        backend = jax.default_backend()
        kind = jax.devices()[0].device_kind
        peak_dev = peak_flops_per_device(backend, kind)
        prof = default_profile(backend, kind)
        d = cost.to_dict()
        # roofline prediction over the per-device share (the planner's
        # scoring fn expects per-instance cost)
        per_dev = dict(d)
        scale = 1.0 / max(devices, 1)
        per_dev["flops"] = d["flops"] * scale
        per_dev["hbm_bytes"] = d["hbm_bytes"] * scale
        per_dev["families"] = {
            f: {**fc, "flops": fc["flops"] * scale,
                "hbm_bytes": fc["hbm_bytes"] * scale}
            for f, fc in (d.get("families") or {}).items()
        }
        pred = predict_step_ms(per_dev, prof, devices=devices)
        return {
            "flops": d["flops"],
            "hbm_bytes": d["hbm_bytes"],
            "ici_bytes": d["ici_bytes"],
            "families": d["families"],
            "source": d["source"],
            "devices": devices,
            "backend": backend,
            "device_kind": kind,
            "peak_flops_per_s": peak_dev * devices,
            "peak_hbm_bytes_per_s": prof.hbm_peak_bytes_per_s * devices,
            "predicted_ms": round(pred["predicted_ms"], 3),
            "calibration": prof.name,
        }

    def train(self) -> list:
        """Run the training loop; returns per-step metric records.

        Device metrics are fetched lazily on ``log_every`` boundaries: in
        between, steps are dispatched without a host sync, so the device
        stays busy. With the default ``log_every=1`` every step is synced,
        matching the reference's per-iteration logging
        (src/distributed_worker.py:169).
        Step time on non-boundary steps is the window average.
        """
        c = self.config
        rng = jax.random.PRNGKey(c.seed + 1)
        steps_per_epoch = self.train_loader.steps_per_epoch
        total_steps = (
            c.max_steps
            if c.max_steps is not None
            else steps_per_epoch * c.epochs
        )
        history = []
        pending = []  # records whose metric values are still device futures
        window_t0 = time.perf_counter()
        window_data = 0.0
        profile_at = self.start_step + 1 if c.profile_steps > 0 else None
        profile_stop = None

        def publish(fetched, step_time, wall_ms):
            """Finalize the window's records: the stream write, the
            derived events, the parity log line and the rate gauges."""
            for record, m in zip(pending, fetched):
                record.update(
                    loss=float(m["loss"]),
                    acc1=float(m["acc1"]),
                    acc5=float(m["acc5"]),
                    step_time=step_time,
                    imgs_per_sec=c.batch_size / step_time,
                    wall_ms=wall_ms,
                )
                # resilience extras ride along: straggler_dropped[_mask]/
                # straggler_skew (grad_sync report) and skipped_nonfinite
                # (the non-finite-update guard) land in every record
                for k, v in m.items():
                    if k not in ("loss", "acc1", "acc5"):
                        record[k] = float(v)
                if self.is_text:
                    record["tokens_per_sec"] = (
                        c.batch_size * self.seq_len / step_time
                    )
                history.append(record)
                self.metrics.log(record)
                # derived events AFTER their step record, so the stream
                # reads causally under `obs tail`
                if record.get("straggler_dropped", 0):
                    from pytorch_distributed_nn_tpu.resilience import (
                        stragglers as _st,
                    )

                    ranks = (
                        _st.dropped_ranks(record["straggler_dropped_mask"])
                        if "straggler_dropped_mask" in record else None
                    )
                    logger.warning(
                        "Step %d: dropped %d straggler(s)%s, skew %.2fx",
                        record["step"], int(record["straggler_dropped"]),
                        f" (ranks {ranks})" if ranks is not None else "",
                        record.get("straggler_skew", float("nan")),
                    )
                    self.telemetry.emit(
                        "straggler_drop", step=record["step"],
                        dropped=int(record["straggler_dropped"]),
                        ranks=ranks,
                        skew=record.get("straggler_skew"),
                        slowest_rank=(
                            int(record["straggler_slowest_rank"])
                            if "straggler_slowest_rank" in record else None
                        ),
                    )
                if record.get("skipped_nonfinite", 0):
                    self.telemetry.emit(
                        "nonfinite_skip", step=record["step"],
                    )
                if record.get("input_wait_ms", 0.0) >= INPUT_WAIT_EVENT_MS:
                    # a slow loader is no longer invisible: the stall gets
                    # its own typed event instead of being billed to the
                    # step (docs/data.md)
                    self.telemetry.emit(
                        "input_wait", step=record["step"],
                        wait_ms=record["input_wait_ms"],
                    )
            last = pending[-1]
            # log-line parity: src/distributed_worker.py:169-173
            logger.info(
                "Workers: %d, Step: %d, Epoch: %d, Loss: %.4f, "
                "Prec@1: %.4f, Prec@5: %.4f, DataTime: %.4f, "
                "StepTime: %.4f",
                self.n_workers, last["step"], last["epoch"], last["loss"],
                last["acc1"], last["acc5"],
                last["data_time"], last["step_time"],
            )
            # step-rate / ETA gauges: exported via metrics.prom on every
            # heartbeat tick and carried in heartbeat.json itself, so an
            # external babysitter reads progress without parsing the stream
            rate = 1000.0 / wall_ms
            eta = max(total_steps - last["step"], 0) / rate
            reg = self.telemetry.registry
            reg.gauge("step_rate", help="steps/s over the last log window") \
                .set(rate)
            reg.gauge("eta_seconds", help="projected seconds to completion") \
                .set(eta)
            if sup is not None:
                sup.extra.update(
                    step_rate=round(rate, 4), eta_seconds=round(eta, 2)
                )

        def flush():
            """Fetch pending device metrics and finalize their records.

            The device_get is a synchronous fetch that closes the timing
            window: the metrics cannot reach the host before the steps
            that produced them have run, so it is a correct completion
            point, and it bounds how far dispatch runs ahead of the
            device. Cost: one blocking fetch per log_every window.
            """
            nonlocal window_t0, window_data, wall_t0
            if not pending:
                return
            with span("train/flush"):
                with span("train/flush_fetch"):
                    fetched = jax.device_get(
                        [r.pop("_metrics") for r in pending]
                    )
                now = time.perf_counter()
                # the wall clock: fetch to fetch, nothing subtracted, not
                # restarted by a save (step_time below leaves out the data
                # phase, where the dispatch thread blocks once the
                # runtime's launch queue is full, and restarts after saves)
                wall_ms = (now - wall_t0) * 1000.0 / len(pending)
                wall_t0 = now
                step_time = max(
                    (now - window_t0 - window_data) / len(pending),
                    1e-9,
                )
                with span("train/flush_publish"):
                    publish(fetched, step_time, wall_ms)
                pending.clear()
                window_t0 = time.perf_counter()
                window_data = 0.0

        import contextlib

        plan = self.fault_plan
        sup = None
        if c.supervise:
            from pytorch_distributed_nn_tpu.resilience.supervisor import (
                RunSupervisor,
            )

            sup = RunSupervisor(
                c.train_dir, grace=c.heartbeat_grace,
                telemetry=self.telemetry,
            )
            # heartbeat.json carries the mesh geometry (device count, mesh
            # factors, process count): an external babysitter — or the
            # next resume's elastic plan, for manifest-less checkpoints —
            # reads the fleet this run ACTUALLY trained on
            sup.extra["geometry"] = self._geometry
            if self._flightrec is not None:
                # watchdog -> detector: a convicted stall opens an
                # incident bundle at the next step boundary (i.e. the
                # moment the wedged loop recovers)
                sup.add_stall_hook(self._flightrec.notify_stall)

        def preempt_exit(completed_step: int):
            flush()
            self.telemetry.emit(
                "preempt", step=completed_step,
                signal=getattr(sup, "stop_signal", None),
            )
            self._emergency_save()
            # the whole point of a graceful preemption is that nothing is
            # lost: force the stream (final step records + the preempt
            # event) to stable storage before the process exits
            self.telemetry.flush(fsync=True)
            logger.warning(
                "Preempted after step %d: emergency checkpoint written, "
                "exiting cleanly", completed_step,
            )

        ok = False  # set only when the loop body completes
        step = self.start_step - 1  # last completed step when the loop is empty
        # the call's first iteration is set-up too: the step's compile or
        # cache fetch (and its lowering, on a run without a stream), and
        # the snapshot warm-up
        first_step = self._setup.span(
            "setup/first_step", parent="train/step", step=self.start_step + 1
        )
        not_first = contextlib.nullcontext()
        wall_t0 = time.perf_counter()  # the first window's wall_ms starts here
        dispatched_at = time.monotonic()  # the first step's dispatch_gap_ms
        try:
          with (sup if sup is not None else contextlib.nullcontext()):
            for step in range(self.start_step, total_steps):
                self._compiles.step = step + 1
                with span("train/step"), (
                    first_step if step == self.start_step else not_first
                ):
                    if plan is not None:
                        # 1-indexed fault steps; delay entries become real
                        # host sleeps only when no straggler simulator is
                        # consuming them as simulated arrival time
                        plan.pre_step(
                            step + 1, sleep_delays=self._straggler_sim is None
                        )
                    if sup is not None and sup.should_stop:
                        preempt_exit(step)
                        break
                    if profile_at is not None and step == profile_at:
                        pdir = c.profile_dir or f"{c.train_dir}/profile"
                        jax.profiler.start_trace(pdir)
                        profile_stop = step + c.profile_steps
                        logger.info(
                            "Profiling steps %d..%d to %s",
                            step + 1, profile_stop, pdir,
                        )
                    if self._fused_step is not None:
                        with span("train/data") as data:
                            idx, key = self.train_loader.next_indices()
                        window_data += data.seconds
                        with span("train/dispatch"):
                            self.state, m = self._step(
                                self.state, self.train_loader.images,
                                self.train_loader.labels, idx, key, rng,
                            )
                    else:
                        with span("train/data") as data:
                            batch = self.train_loader.next_batch()
                        window_data += data.seconds
                        if plan is not None:
                            batch = plan.poison_batch(step + 1, batch)
                        with span("train/dispatch"):
                            self.state, m = self._step(self.state, batch, rng)
                    if step == self.start_step and self._async_ckpt is not None:
                        # Warm the snapshot clone on the POST-step state: its
                        # avals/shardings are what every save sees (the init
                        # state's signature differs, so warming there would
                        # compile a program no save ever uses and the first
                        # checkpoint would still pay the ~100 ms retrace).
                        # Rides the compile step, off every timed window.
                        self._async_ckpt.warmup(self.state)
                    # input-wait accounting: how long the loop actually
                    # BLOCKED on the loader (its own measurement: the
                    # input/produce span, without the dispatch to the
                    # device — near zero when prefetch kept up); loaders
                    # without the attribute bill the whole data phase.
                    data_time = data.seconds
                    wait_ms = getattr(self.train_loader, "last_wait_ms", None)
                    if wait_ms is None:
                        wait_ms = data_time * 1000.0
                    # host time from the previous step's dispatch returning
                    # to this one's (the first of a call: from loop entry).
                    # Dispatch leads the device by the runtime's launch
                    # queue, so after a flush one gap is the wait for the
                    # device to drain, the next few are near zero and the
                    # rest are the step time; a gap over that pattern is a
                    # step the loop was kept from dispatching (a save's
                    # stall, a thread holding the interpreter lock).
                    now = time.monotonic()
                    gap_ms, dispatched_at = (now - dispatched_at) * 1e3, now
                    pending.append({
                        "step": step + 1,
                        "epoch": step // max(steps_per_epoch, 1),
                        "_metrics": m,
                        "data_time": data_time,
                        "input_wait_ms": round(wait_ms, 3),
                        "dispatch_gap_ms": round(gap_ms, 3),
                    })
                    if (step + 1) % c.log_every == 0:
                        flush()
                    if profile_stop is not None and step + 1 >= profile_stop:
                        flush()  # force completion so the trace has real steps
                        jax.profiler.stop_trace()
                        profile_stop = profile_at = None
                    if c.eval_freq and (step + 1) % c.eval_freq == 0:
                        flush()  # checkpoint below reads the live state
                        with span("ckpt/save"):
                            self._save_periodic(step + 1, plan)
                        # don't bill the checkpoint blockage to the next
                        # window's step_time. Sync: the blockage is the full
                        # write; async: only the snapshot/backpressure stall —
                        # either way stall_ms on the checkpoint_write event is
                        # what the loop actually lost (the write itself
                        # overlaps the following steps and shows up, if at
                        # all, as their own wall time).
                        window_t0 = time.perf_counter()
                    if self._flightrec is not None:
                        # step boundary: finish a due capture window / open a
                        # pending one. The recorder never nests a trace inside
                        # a user --profile span (two jax traces cannot nest).
                        self._flightrec.tick(
                            step + 1, trace_ok=profile_stop is None
                        )
                    if sup is not None:
                        sup.beat(step + 1)
                        # a signal that landed DURING the step exits here, so
                        # the grace window is one step + checkpoint, not two
                        if sup.should_stop:
                            preempt_exit(step + 1)
                            break
            ok = True
        except InjectedCrash:
            # An abrupt injected failure: persist what we have (the state
            # after the last COMPLETED step — pre_step fires before any
            # compute) and let the crash propagate; the resume path picks
            # this checkpoint up bitwise (chaos scenario crash_resume).
            self._emergency_save()
            self.telemetry.flush(fsync=True)
            raise
        finally:
            # Crash-path cleanup: keep whatever metrics already completed
            # and ALWAYS finalize an in-flight profiler trace (a crashed
            # run is exactly when the trace matters). On the SUCCESS path
            # a cleanup failure must still propagate (silently truncated
            # history would be worse) — but only after stop_trace has had
            # its chance. `ok` (not sys.exc_info(), which also reports a
            # CALLER's in-flight exception) distinguishes the paths.
            cleanup_error = None
            self._compiles.step = None
            # Flight recorder first: an in-flight capture stops its trace
            # and writes its report NOW (a crashed run is exactly when the
            # bundle matters), before the user-profile stop_trace below
            # could race the same profiler session.
            if self._flightrec is not None:
                try:
                    self._flightrec.finalize(step + 1)
                except Exception:
                    logger.exception("flight recorder finalize failed")
            # Drain the async checkpoint pipeline FIRST (the loop's final
            # wait point): the last enqueued save must publish before the
            # run is declared done, and a writer-thread failure must fail
            # the run exactly like a sync write would have — but only on
            # the success path (a crash already has its own error).
            try:
                self._finish_background_io(raise_errors=ok)
            except Exception as e:
                if ok:
                    cleanup_error = e
                else:
                    logger.exception("async drain failed during shutdown")
            if sup is not None:
                # the drain may have landed checkpoint_write/gc events
                # AFTER the last in-loop beat exported metrics.prom —
                # re-publish so the final scrape surface reflects the
                # fully-drained registry
                try:
                    sup.beat(step + 1)
                except Exception:
                    logger.exception("final heartbeat failed")
            try:
                flush()
                self.telemetry.flush()
            except Exception as e:
                if ok:
                    cleanup_error = e
                else:
                    logger.exception("metric flush failed during shutdown")
            if profile_stop is not None:  # run ended inside traced span
                try:
                    jax.profiler.stop_trace()
                except Exception as e:
                    if ok and cleanup_error is None:
                        cleanup_error = e
                    else:
                        logger.exception("stop_trace failed during shutdown")
            if cleanup_error is not None:
                raise cleanup_error
        return history

    def _loader_state(self) -> Optional[dict]:
        """The train loader's serializable iterator state (or None) —
        captured on the SAVE path so every checkpoint carries the exact
        stream position it corresponds to (docs/data.md). Host-side and
        tiny; failure degrades to a sidecar-less checkpoint (skip-based
        resume), never fails the save."""
        fn = getattr(self.train_loader, "state", None)
        if not callable(fn):
            return None
        try:
            return fn()
        except Exception:
            logger.exception("loader state capture failed (non-fatal)")
            return None

    def _save_periodic(self, step: int, plan) -> None:
        """One periodic checkpoint at ``step`` (the --eval-freq path).

        Async (default): on-device snapshot + enqueue to the background
        writer — the loop blocks only for ``handle.stall_ms``; byte
        output, manifests and resume semantics are identical to sync
        (training/async_ckpt.py contracts). Sync (--no-async-ckpt): the
        pre-existing inline writers. Either way ``--keep-last`` GC runs
        after a successful publish.
        """
        c = self.config
        data_state = self._loader_state()
        if self._async_ckpt is not None:
            # non-GSPMD multihost: only process 0 writes (same guard as
            # sync); GSPMD saves are collective — every process enqueues
            # its own shard fetch.
            if not self.use_spmd and jax.process_index() != 0:
                return
            handle = self._async_ckpt.save(
                self.state, step=step, fault_plan=plan,
                retain_device_state=c.overlap_eval,
                data_state=data_state,
            )
            logger.info(
                "Checkpoint step %d handed to the async writer "
                "(loop stalled %.1f ms)", step, handle.stall_ms,
            )
            if c.overlap_eval:
                self._start_overlap_eval(handle)
            return
        if self.use_spmd:
            # Sharded save: collective — every process writes its
            # own shards; nobody gathers the full state
            # (checkpoint.save_sharded).
            path = ckpt.save_sharded(c.train_dir, self.state, step=step,
                                     data_state=data_state,
                                     geometry=self._geometry)
            if jax.process_index() == 0:
                if c.keep_last is not None:
                    ckpt.gc_checkpoints(c.train_dir, c.keep_last)
                logger.info(
                    "Checkpointed step %d to %s (sharded)", step, path
                )
        elif jax.process_index() == 0:
            # Process-0 only: on a multi-host pod every process
            # runs this loop; unguarded writes reproduce the
            # reference's NFS race (all workers race-writing the
            # same model_step_<N> path,
            # src/distributed_worker.py:304-307).
            with span("ckpt/fetch"):
                host = self._host_state()
            path = ckpt.save_checkpoint(
                c.train_dir, host, step=step,
                fault_plan=plan, data_state=data_state,
                geometry=self._geometry,
            )
            if c.keep_last is not None:
                ckpt.gc_checkpoints(c.train_dir, c.keep_last)
            logger.info("Checkpointed step %d to %s", step, path)

    def _start_overlap_eval(self, handle) -> None:
        """Eval pass on the checkpoint's on-device snapshot, off the step
        loop (--overlap-eval). Depth-1 like the writer: a new boundary
        joins the previous eval instead of stacking threads. The snapshot
        is donation-safe (it is a fresh device copy), so the train loop
        keeps stepping while this runs; results land in the stream as
        ``eval_result`` events with ``source="overlap"``.
        """
        import threading

        prev = self._overlap_eval_thread
        if prev is not None and prev.is_alive():
            prev.join()
        telemetry = self.telemetry

        def _run():
            dev_state = handle.dev_state  # local ref: writer may drop its own
            try:
                out = run_eval_pass(
                    self.eval_step, dev_state, self.test_loader
                )
                if out:
                    seqs = getattr(self.test_loader, "eval_sequences", None)
                    telemetry.emit(
                        "eval_result", step=handle.step,
                        loss=float(out["loss"]), acc1=float(out["acc1"]),
                        acc5=float(out["acc5"]), sequences=seqs,
                        source="overlap",
                    )
                    logger.info(
                        "Overlapped eval @ step %d: loss %.4f, "
                        "prec@1 %.4f, prec@5 %.4f",
                        handle.step, out["loss"], out["acc1"], out["acc5"],
                    )
            except Exception:
                logger.exception("overlapped eval failed (non-fatal)")
            finally:
                handle.dev_state = None  # free the device snapshot

        self._overlap_eval_thread = threading.Thread(
            target=_run, name="pdtn-overlap-eval", daemon=True
        )
        self._overlap_eval_thread.start()

    def _finish_background_io(self, raise_errors: bool) -> None:
        """Join the overlap-eval thread and drain the async writer — the
        end-of-loop / preemption wait point where worker faults surface.
        """
        prev = self._overlap_eval_thread
        if prev is not None and prev.is_alive():
            prev.join()
        if self._async_ckpt is not None:
            self._async_ckpt.drain(raise_errors=raise_errors)

    def _emergency_save(self):
        """Atomic checkpoint of the live state at the CURRENT step —
        the preemption/crash path (resilience/supervisor.py). Reuses the
        normal writers, so an emergency checkpoint is indistinguishable
        from a scheduled one (same naming, same manifest, same resume).
        Multihost non-GSPMD note: only process 0 writes, same as the
        periodic path; sharded (GSPMD) saves are collective, which a
        single-host signal cannot coordinate — covered on single-process
        runs only.

        Always SYNCHRONOUS (the process is exiting — there is nothing to
        overlap with), and drains any in-flight async save first so the
        writer thread never races this write on the same
        ``model_step_<N>`` path; the emergency checkpoint supersedes it.
        """
        c = self.config
        try:
            self._finish_background_io(raise_errors=False)
        except Exception:
            logger.exception("async drain before emergency save failed")
        try:
            data_state = self._loader_state()
            if self.use_spmd:
                path = ckpt.save_sharded(c.train_dir, self.state,
                                         data_state=data_state,
                                         geometry=self._geometry)
            elif jax.process_index() == 0:
                path = ckpt.save_checkpoint(
                    c.train_dir, self._host_state(),
                    fault_plan=self.fault_plan, data_state=data_state,
                    geometry=self._geometry,
                )
            else:
                return None
            logger.info("Emergency checkpoint: %s", path)
            return path
        except Exception:
            # best effort by definition: the process is going down anyway,
            # and an older periodic checkpoint may still exist
            logger.exception("emergency checkpoint failed")
            return None

    def evaluate(self) -> dict:
        """Test-set pass (reference: src/nn_ops.py:90-106).

        Image datasets: the full test set. Text (MLM) models: the fixed
        deterministic eval set of ``eval_batches`` x test-batch sequences
        (data/text.MLMBatches.eval_set) — the same sequences every call;
        the logged line records how many.
        """
        out = run_eval_pass(self.eval_step, self.state, self.test_loader)
        if not out:  # --eval-batches 0: a skipped eval, not a 0.0-loss one
            logger.info("Validation skipped: eval set is empty")
            return {}
        seqs = getattr(self.test_loader, "eval_sequences", None)
        logger.info(
            "Validation: loss %.4f, prec@1 %.4f, prec@5 %.4f%s",
            out["loss"], out["acc1"], out["acc5"],
            f" ({seqs} sequences)" if seqs is not None else "",
        )
        # train and eval telemetry share the run's stream (obs summary's
        # accuracy-vs-step section)
        self.telemetry.emit(
            "eval_result", step=int(self.state.step), loss=float(out["loss"]),
            acc1=float(out["acc1"]), acc5=float(out["acc5"]),
            sequences=seqs, source="trainer",
        )
        return out

    def close(self):
        if self._flightrec is not None:
            try:
                self._flightrec.close()
            except Exception:
                logger.exception("flight recorder close failed")
        try:
            self._finish_background_io(raise_errors=False)
            if self._async_ckpt is not None:
                self._async_ckpt.close()
        except Exception:
            logger.exception("async checkpointer close failed")
        self.train_loader.close()
        self.test_loader.close()
        self.metrics.close()
        self.telemetry.close()
        compiles.unroute(self.telemetry)
        obs.uninstall(self.telemetry, self._prev_telemetry)

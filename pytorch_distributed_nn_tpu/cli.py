"""CLI entry points.

Parity with the reference's entry points (SURVEY.md §1 layer 4):

- ``train``     — src/distributed_nn.py (the `mpirun` binary; here a single
                  process drives the whole mesh — no mpirun, no ranks)
- ``single``    — src/single_machine.py (1-device mesh, local sync)
- ``evaluator`` — src/distributed_evaluator.py (checkpoint-dir polling)
- ``obs``       — telemetry inspection: summary / tail / compare / export
                  / incidents over the unified per-run JSONL stream
                  (observability/obs_cli.py, docs/observability.md) —
                  the replacement for the reference's regex-over-logs
                  notebooks (src/tiny_tuning_parser.py)
- ``serve``     — serving tier (serving/, docs/serving.md): export a
                  checkpoint to a frozen inference artifact and serve /
                  bench it with continuous batching — the capability the
                  reference's NFS-polling evaluator hinted at but never
                  grew
- ``sweep``     — experiment orchestration (experiments/,
                  docs/experiments.md): resumable multi-trial sweeps over
                  TrainConfig fields as supervised subprocesses with an
                  ASHA-style early-stopping scheduler — the grown-up form
                  of the reference's tune.sh + EC2 fan-out provisioner

Flag names follow src/distributed_nn.py:24-68 where the concept survives on
TPU; flags that only existed because of MPI (--comm-type Bcast/Async, ranks)
map onto --sync-mode. Unlike the reference, flags are validated.
"""

from __future__ import annotations

import argparse
import logging
import os
import sys


def _add_common_train_flags(p: argparse.ArgumentParser):
    p.add_argument("--batch-size", type=int, default=128,
                   help="GLOBAL training batch size (split over the mesh)")
    p.add_argument("--test-batch-size", type=int, default=1000)
    p.add_argument("--learning-rate", "--lr", dest="lr", type=float, default=0.01)
    p.add_argument("--lr-decay-steps", type=int, default=None,
                   help="decay lr by --lr-decay-factor every N steps "
                        "(reference parity: no schedule when unset)")
    p.add_argument("--lr-decay-factor", type=float, default=0.1)
    p.add_argument("--warmup-steps", type=int, default=0,
                   help="linear lr warmup over the first N steps "
                        "(composes with --lr-decay-steps); transformer "
                        "runs at vocab~30k need it")
    p.add_argument("--momentum", type=float, default=0.9)
    p.add_argument("--optimizer", choices=["sgd", "adam"], default="sgd")
    p.add_argument("--weight-decay", type=float, default=0.0)
    p.add_argument("--nesterov", action="store_true")
    p.add_argument("--max-steps", type=int, default=None)
    p.add_argument("--epochs", type=int, default=1)
    p.add_argument("--network", default="ResNet18")
    p.add_argument("--dataset", default="Cifar10",
                   choices=["MNIST", "Cifar10", "Cifar100", "SVHN", "MLMSynth",
                            "NextTokenSynth"])
    p.add_argument("--seq-len", type=int, default=None,
                   help="MLM: sequence length (default: model max_len spec)")
    p.add_argument("--vocab-size", type=int, default=None,
                   help="MLM: vocabulary size (default: model config)")
    p.add_argument("--mask-prob", type=float, default=0.15,
                   help="MLM: masking probability")
    p.add_argument("--corpus-branching", type=int, default=8,
                   help="MLM: branching factor of the synthetic bigram "
                        "corpus (the evaluator must use the same value)")
    p.add_argument("--eval-batches", type=int, default=64,
                   help="MLM: size of the fixed deterministic eval set in "
                        "batches of --test-batch-size (every reported "
                        "accuracy covers eval-batches * test-batch "
                        "sequences)")
    p.add_argument("--attn-impl", choices=["full", "pallas"], default="full",
                   help="MLM: attention implementation (pallas = fused "
                        "flash kernel)")
    p.add_argument("--remat", action="store_true",
                   help="MLM: rematerialize encoder blocks on backward "
                        "(activation memory O(L*d) instead of "
                        "O(layers*L*d); the long-context lever)")
    p.add_argument("--fused-ln", action="store_true",
                   help="MLM: Pallas one-pass LayerNorm fwd+bwd (f32 "
                        "stats, no separate f32 materialization) — the "
                        "bandwidth-tail lever; dp meshes only")
    p.add_argument("--eval-freq", type=int, default=0,
                   help="checkpoint every N steps (0 = off)")
    p.add_argument("--async-ckpt", action=argparse.BooleanOptionalAction,
                   default=True,
                   help="overlap periodic checkpoints with training: "
                        "on-device snapshot + background writer thread, "
                        "byte-identical to sync output "
                        "(docs/checkpointing.md). Emergency saves are "
                        "always synchronous. --no-async-ckpt restores the "
                        "inline writers")
    p.add_argument("--keep-last", type=int, default=None, metavar="N",
                   help="checkpoint retention: after each successful "
                        "publish delete verified checkpoints older than "
                        "the newest N (never the resume target, never "
                        "corrupt evidence); default keeps everything")
    p.add_argument("--overlap-eval", action="store_true",
                   help="run the periodic eval pass on the checkpoint "
                        "snapshot in a background thread instead of "
                        "blocking the step loop (requires --async-ckpt "
                        "and --eval-freq)")
    p.add_argument("--train-dir", default="./train_dir")
    p.add_argument("--resume", action="store_true",
                   help="resume from the latest checkpoint in --train-dir; "
                        "by default ELASTIC — a changed device fleet is "
                        "adapted to (mesh re-derived, global batch "
                        "preserved, reshard-on-load; "
                        "docs/resilience.md#elastic-resume)")
    p.add_argument("--strict-geometry", action="store_true",
                   help="disable elastic resume: require the live mesh to "
                        "exactly match the checkpoint's recorded geometry "
                        "(a mismatch fails fast, naming both geometries)")
    p.add_argument("--warm-start", default=None, metavar="CKPT",
                   help="vocabulary-curriculum warm start: initialize "
                        "trunk weights from this FILE checkpoint (smaller "
                        "vocab/max_len allowed; overlapping embedding rows "
                        "copied, new rows keep fresh init; optimizer cold)")
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--dtype", choices=["float32", "bfloat16"], default="float32")
    p.add_argument("--data-dir", default="./data")
    p.add_argument("--data-layout", choices=["auto", "device", "host"],
                   default="auto",
                   help="'device' keeps the image dataset HBM-resident and "
                        "builds batches on-device (4 KB/step host traffic); "
                        "'host' is the prefetch-thread loader")
    p.add_argument("--loader-workers", type=int, default=0,
                   help="host layout and --data-path: threads that prepare "
                        "(gather, normalize or decode, augment) batches "
                        "ahead of the loop (0 = one prefetch thread); the "
                        "reference's fork-worker loader capability")
    p.add_argument("--data-path", default=None, metavar="DIR",
                   help="sharded streaming input (docs/data.md): read the "
                        "TRAINING stream from this shard directory "
                        "(`cli data export` writes one) — per-host file "
                        "shards, background decode, bounded device "
                        "prefetch; the iterator state rides in every "
                        "checkpoint so --resume continues the exact batch "
                        "sequence. Datasets no longer need to fit in RAM")
    p.add_argument("--stream-prefetch", type=int, default=2, metavar="N",
                   help="streaming loader: ready-batch prefetch depth "
                        "(0 = synchronous reads on the step loop)")
    p.add_argument("--synthetic-size", type=int, default=None,
                   help="use synthetic data with this many samples")
    p.add_argument("--metrics-path", default=None,
                   help="write per-step JSONL metrics here")
    p.add_argument("--log-every", type=int, default=1,
                   help="fetch/log metrics every N steps; between "
                        "boundaries steps run without a host sync")
    p.add_argument("--bn-stats-sync", choices=["mean", "rank0"], default="mean")
    p.add_argument("--grad-accum", type=int, default=1, metavar="K",
                   help="accumulate gradients over K microbatches per "
                        "step (one sync + update): K x less activation "
                        "memory at the same effective batch (image "
                        "models; MLM uses --remat)")
    p.add_argument("--profile", type=int, default=0, metavar="N",
                   help="trace N training steps with jax.profiler "
                        "(summarize with python -m pytorch_distributed_"
                        "nn_tpu.observability.xplane <profile-dir>)")
    p.add_argument("--profile-dir", default=None,
                   help="trace output dir (default: <train-dir>/profile)")
    p.add_argument("--faults", default=None, metavar="SPEC",
                   help="deterministic fault injection, e.g. "
                        "'delay@120:p3:2.5s,crash@200,nan_grad@150,"
                        "torn_ckpt@100' (docs/resilience.md grammar; "
                        "steps are 1-indexed)")
    p.add_argument("--skip-nonfinite", action="store_true",
                   help="skip the optimizer update when the synced "
                        "gradient holds NaN/Inf (params/opt/BN keep "
                        "their previous values; the step is flagged in "
                        "the metrics)")
    p.add_argument("--supervise", action="store_true",
                   help="preemption-safe run: SIGTERM/SIGINT triggers an "
                        "atomic emergency checkpoint + clean exit, and a "
                        "heartbeat file is beaten every step")
    p.add_argument("--heartbeat-grace", type=float, default=None,
                   metavar="SECS",
                   help="with --supervise: flag the run as STALLED when "
                        "the heartbeat goes quiet this long")
    p.add_argument("--flightrec", default=None, metavar="SPEC",
                   help="arm the flight recorder: 'default' or a detector "
                        "spec (e.g. 'step_regression:factor=2.5,stall,"
                        "cooldown=100'; docs/observability.md grammar). "
                        "Anomalies convicted against the run's own "
                        "baseline capture an incident bundle — profiler "
                        "trace window, event ring, manifest, env, "
                        "report.md — under <train-dir>/incidents/; "
                        "inspect with 'obs incidents'")


def _trainer_from_args(args, sync_mode: str, num_workers):
    from pytorch_distributed_nn_tpu.training.trainer import TrainConfig, Trainer
    from pytorch_distributed_nn_tpu.utils import compile_cache

    compile_cache.configure()
    cfg = TrainConfig(
        network=args.network,
        dataset=args.dataset,
        batch_size=args.batch_size,
        test_batch_size=args.test_batch_size,
        lr=args.lr,
        lr_decay_steps=getattr(args, "lr_decay_steps", None),
        lr_decay_factor=getattr(args, "lr_decay_factor", 0.1),
        warmup_steps=getattr(args, "warmup_steps", 0),
        momentum=args.momentum,
        optimizer=args.optimizer,
        weight_decay=args.weight_decay,
        nesterov=args.nesterov,
        max_steps=args.max_steps,
        epochs=args.epochs,
        num_workers=num_workers,
        sync_mode=sync_mode,
        num_aggregate=getattr(args, "num_aggregate", None),
        kill_ranks=tuple(
            int(r) for r in getattr(args, "kill_ranks", None).split(",")
        ) if getattr(args, "kill_ranks", None) else (),
        compression=getattr(args, "compress_grad", "none"),
        grad_accum=getattr(args, "grad_accum", 1),
        topk_ratio=getattr(args, "topk_ratio", 0.01),
        bucket_bytes=(args.bucket_kb * 1024
                      if getattr(args, "bucket_kb", None) else None),
        eval_freq=args.eval_freq,
        train_dir=args.train_dir,
        async_ckpt=getattr(args, "async_ckpt", True),
        keep_last=getattr(args, "keep_last", None),
        overlap_eval=getattr(args, "overlap_eval", False),
        resume=args.resume,
        strict_geometry=getattr(args, "strict_geometry", False),
        warm_start=getattr(args, "warm_start", None),
        seed=args.seed,
        bn_stats_sync=args.bn_stats_sync,
        dtype=args.dtype,
        data_layout=getattr(args, "data_layout", "auto"),
        loader_workers=getattr(args, "loader_workers", 0),
        data_path=getattr(args, "data_path", None),
        stream_prefetch=getattr(args, "stream_prefetch", 2),
        data_dir=args.data_dir,
        synthetic_size=args.synthetic_size,
        metrics_path=args.metrics_path,
        log_every=args.log_every,
        profile_steps=getattr(args, "profile", 0),
        profile_dir=getattr(args, "profile_dir", None),
        seq_len=getattr(args, "seq_len", None),
        vocab_size=getattr(args, "vocab_size", None),
        mask_prob=getattr(args, "mask_prob", 0.15),
        corpus_branching=getattr(args, "corpus_branching", 8),
        eval_batches=getattr(args, "eval_batches", 64),
        attn_impl=getattr(args, "attn_impl", "full"),
        remat=getattr(args, "remat", False),
        fused_ln=getattr(args, "fused_ln", False),
        tensor_parallel=getattr(args, "tensor_parallel", 1),
        seq_parallel=getattr(args, "seq_parallel", 1),
        seq_attn=getattr(args, "seq_attn", "ring"),
        faults=getattr(args, "faults", None),
        skip_nonfinite=getattr(args, "skip_nonfinite", False),
        straggler_deadline=getattr(args, "straggler_deadline", None),
        straggler_min_keep=getattr(args, "straggler_min_keep", 1),
        supervise=getattr(args, "supervise", False),
        heartbeat_grace=getattr(args, "heartbeat_grace", None),
        flightrec=getattr(args, "flightrec", None),
    )
    return Trainer(cfg)


def main_train(argv=None) -> int:
    """Distributed training (reference: src/distributed_nn.py)."""
    p = argparse.ArgumentParser(
        "pdtn-train", description=main_train.__doc__
    )
    _add_common_train_flags(p)
    p.add_argument("--num-workers", type=int, default=None,
                   help="data-parallel degree (default: all devices / "
                        "(tensor-parallel * seq-parallel))")
    p.add_argument("--tensor-parallel", type=int, default=1,
                   help="text models: shard heads/MLP over a 'model' mesh "
                        "axis (GSPMD path)")
    p.add_argument("--seq-parallel", type=int, default=1,
                   help="text models: shard the sequence over a 'seq' "
                        "mesh axis (ring/Ulysses attention)")
    p.add_argument("--seq-attn", choices=["ring", "ulysses"], default="ring",
                   help="sequence-parallel attention strategy")
    p.add_argument("--sync-mode", choices=["allreduce", "ps"],
                   default="allreduce")
    p.add_argument("--num-aggregate", type=int, default=None,
                   help="PS mode: aggregate only the first N gradients/step")
    p.add_argument("--kill-ranks", default=None, metavar="R1,R2,...",
                   help="straggler mitigation (reference --mode/"
                        "--kill-threshold): comma-separated data-parallel "
                        "ranks whose gradients are excluded from every "
                        "aggregate, the observable effect of killing those "
                        "workers")
    p.add_argument("--straggler-deadline", type=float, default=None,
                   metavar="SECS",
                   help="deadline-based straggler dropping "
                        "(resilience/stragglers.py): contributions with a "
                        "simulated arrival time past the deadline are "
                        "dropped and the aggregate renormalized by the "
                        "live count; --faults delay@N:pR:Ts entries feed "
                        "the simulated times")
    p.add_argument("--straggler-min-keep", type=int, default=1, metavar="K",
                   help="the fastest K contributions always aggregate, "
                        "whatever the deadline says (backup-worker floor)")
    p.add_argument("--compress-grad", choices=["none", "int8", "topk"],
                   default="none")
    p.add_argument("--topk-ratio", type=float, default=0.01)
    p.add_argument("--bucket-kb", type=int, default=None,
                   help="bucket gradients into N-KB flat collectives "
                        "(the dead DDP path's 1024 KB buckets); 0 = off")
    p.add_argument("--multihost", action="store_true",
                   help="initialize jax.distributed for a TPU pod slice: "
                        "run the SAME command on every host "
                        "(tools/tpu_pod.py train does this); replaces the "
                        "reference's mpirun + hostfile + rank branch "
                        "(src/distributed_nn.py:109-126)")
    args = p.parse_args(argv)
    if args.multihost:
        import jax

        from pytorch_distributed_nn_tpu.resilience.retry import retry_call

        # topology from the TPU metadata server — eventually consistent
        # during pod bring-up, so transient failures retry with backoff
        # instead of wasting the whole pod allocation on a flaky probe
        retry_call(
            jax.distributed.initialize,
            attempts=4, base_delay=2.0, max_delay=15.0,
            retry_on=(RuntimeError, OSError, ValueError),
            label="jax.distributed.initialize",
        )
    trainer = _trainer_from_args(args, args.sync_mode, args.num_workers)
    try:
        trainer.train()
        trainer.evaluate()
    finally:
        trainer.close()
    return 0


def main_single(argv=None) -> int:
    """Single-machine baseline (reference: src/single_machine.py)."""
    p = argparse.ArgumentParser("pdtn-single", description=main_single.__doc__)
    _add_common_train_flags(p)
    args = p.parse_args(argv)
    trainer = _trainer_from_args(args, "local", 1)
    try:
        trainer.train()
        trainer.evaluate()
    finally:
        trainer.close()
    return 0


def main_evaluator(argv=None) -> int:
    """Checkpoint-polling evaluator (reference: src/distributed_evaluator.py)."""
    p = argparse.ArgumentParser(
        "pdtn-evaluator", description=main_evaluator.__doc__
    )
    p.add_argument("--model-dir", required=True)
    p.add_argument("--network", default="ResNet18")
    p.add_argument("--dataset", default="Cifar10",
                   choices=["MNIST", "Cifar10", "Cifar100", "SVHN", "MLMSynth",
                            "NextTokenSynth"])
    p.add_argument("--eval-freq", type=int, default=100)
    p.add_argument("--eval-interval", type=float, default=10.0,
                   help="poll period in seconds (reference hardcoded 10)")
    p.add_argument("--test-batch-size", type=int, default=1000)
    p.add_argument("--max-evals", type=int, default=None)
    p.add_argument("--timeout", type=float, default=None)
    p.add_argument("--follow-latest", action="store_true")
    p.add_argument("--data-dir", default="./data")
    p.add_argument("--data-layout", choices=["auto", "device", "host"],
                   default="auto",
                   help="image datasets: 'device' keeps the test set "
                        "HBM-resident between polls (see train --help)")
    p.add_argument("--synthetic-size", type=int, default=None)
    p.add_argument("--seed", type=int, default=0,
                   help="MLM: must match the trainer's --seed (same corpus)")
    p.add_argument("--seq-len", type=int, default=None,
                   help="MLM: must match the trainer's --seq-len")
    p.add_argument("--vocab-size", type=int, default=None,
                   help="MLM: must match the trainer's --vocab-size")
    p.add_argument("--mask-prob", type=float, default=0.15,
                   help="MLM: must match the trainer's --mask-prob")
    p.add_argument("--corpus-branching", type=int, default=8,
                   help="MLM: must match the trainer's --corpus-branching "
                        "(a different branching is a different language)")
    p.add_argument("--eval-batches", type=int, default=64,
                   help="MLM: fixed deterministic eval set size in batches "
                        "of --test-batch-size")
    args = p.parse_args(argv)

    import jax

    from pytorch_distributed_nn_tpu.data import DataLoader, load_dataset
    from pytorch_distributed_nn_tpu.models import (
        build_model,
        input_spec,
        is_text_model,
    )
    from pytorch_distributed_nn_tpu.optim import build_optimizer
    from pytorch_distributed_nn_tpu.parallel import (
        batch_sharding,
        make_grad_sync,
        make_mesh,
        num_workers,
    )
    from pytorch_distributed_nn_tpu.training.evaluator import Evaluator
    from pytorch_distributed_nn_tpu.training.train_step import create_train_state
    from pytorch_distributed_nn_tpu.utils import compile_cache

    compile_cache.configure()
    mesh = make_mesh()
    n = num_workers(mesh)
    num_classes = 100 if args.dataset == "Cifar100" else 10
    sync = make_grad_sync("allreduce")
    bs = max(n, args.test_batch_size - args.test_batch_size % n)
    eval_kw = {}
    if is_text_model(args.network):
        import jax.numpy as jnp

        from pytorch_distributed_nn_tpu.data.text import (
            MLMLoader,
            TEXT_DATASETS,
        )
        from pytorch_distributed_nn_tpu.ops.metrics import (
            masked_cross_entropy,
            mlm_metrics,
        )

        model_kw = {}
        if args.vocab_size is not None:
            model_kw["vocab_size"] = args.vocab_size
        if args.seq_len is not None:
            model_kw["max_len"] = args.seq_len
        model = build_model(args.network, num_classes, **model_kw)
        seq_len = args.seq_len or input_spec(args.network)[0]
        template = create_train_state(
            model, build_optimizer("sgd", 0.1), sync, jax.random.PRNGKey(0),
            (seq_len,), num_replicas=n, input_dtype=jnp.int32,
        )
        loader = MLMLoader(
            TEXT_DATASETS.get(args.dataset, TEXT_DATASETS["MLMSynth"])(
                vocab_size=model.config.vocab_size, seq_len=seq_len,
                batch_size=bs, seed=args.seed + 10_000,
                corpus_seed=args.seed,  # same language the trainer used
                mask_prob=args.mask_prob,
                branching=args.corpus_branching,
            ),
            sharding=batch_sharding(mesh),
            eval_batches=args.eval_batches,
        )
        # The evaluator runs ONE jitted apply over the GLOBAL batch (the
        # serving engine's shared helper), so the plain masked-mean loss
        # IS the global masked mean — no per-replica normalization
        # wrappers (make_global_*) needed; same number the trainer logs.
        eval_kw = {
            "loss_fn": masked_cross_entropy,
            "metrics_fn": mlm_metrics,
        }
    else:
        model = build_model(args.network, num_classes)
        template = create_train_state(
            model, build_optimizer("sgd", 0.1), sync, jax.random.PRNGKey(0),
            input_spec(args.network), num_replicas=n,
        )
        test_ds = load_dataset(args.dataset, train=False,
                               data_dir=args.data_dir,
                               synthetic_size=args.synthetic_size)
        raw = getattr(test_ds, "raw_images", None)
        use_device = args.data_layout == "device" or (
            args.data_layout == "auto"
            and raw is not None
            and raw.nbytes < 2 << 30
        )
        if use_device:
            from pytorch_distributed_nn_tpu.data.loader import DeviceDataLoader

            loader = DeviceDataLoader(test_ds, bs, mesh, shuffle=False)
        else:
            loader = DataLoader(test_ds, bs, shuffle=False,
                                sharding=batch_sharding(mesh))
    Evaluator(
        model, template, mesh, loader, args.model_dir,
        eval_freq=args.eval_freq, eval_interval=args.eval_interval,
        follow_latest=args.follow_latest, **eval_kw,
    ).run(max_evals=args.max_evals, timeout=args.timeout)
    return 0


def main_tune(argv=None) -> int:
    """LR grid search (reference: src/tune.sh + src/tiny_tuning_parser.py).

    Now a shim over the sweep runner (experiments/, docs/experiments.md):
    candidates run as isolated subprocesses under a bounded pool, every
    trial writes a telemetry stream, and the sweep is journaled under
    ``<train-dir>/lr_sweep`` — a killed tune continues where it stopped.
    ``cli sweep`` is the full surface (ASHA scheduler, arbitrary fields).
    """
    p = argparse.ArgumentParser("pdtn-tune", description=main_tune.__doc__)
    _add_common_train_flags(p)
    p.add_argument("--num-workers", type=int, default=None)
    p.add_argument("--sync-mode", choices=["allreduce", "ps"],
                   default="allreduce")
    p.add_argument("--num-aggregate", type=int, default=None)
    p.add_argument("--compress-grad", choices=["none", "int8", "topk"],
                   default="none")
    p.add_argument("--candidates", default=None,
                   help="comma-separated lr candidates "
                        "(default: the reference's tune.sh grid)")
    p.add_argument("--tune-steps", type=int, default=100,
                   help="steps per candidate (reference: tune.sh --max-steps=100)")
    p.add_argument("--concurrency", type=int, default=2,
                   help="concurrent candidate subprocesses (keep 1 on an "
                        "accelerator host — trials share the chip)")
    p.add_argument("--sweep-dir", default=None,
                   help="journal + per-trial dirs (default: "
                        "<train-dir>/lr_sweep)")
    args = p.parse_args(argv)

    from pytorch_distributed_nn_tpu.training.trainer import TrainConfig
    from pytorch_distributed_nn_tpu.tuning import DEFAULT_CANDIDATES, lr_sweep

    cfg = TrainConfig(
        network=args.network, dataset=args.dataset,
        batch_size=args.batch_size, test_batch_size=args.test_batch_size,
        momentum=args.momentum, optimizer=args.optimizer,
        num_workers=args.num_workers, sync_mode=args.sync_mode,
        num_aggregate=args.num_aggregate, compression=args.compress_grad,
        seed=args.seed, dtype=args.dtype, data_dir=args.data_dir,
        train_dir=args.train_dir,
        synthetic_size=args.synthetic_size, log_every=10**9,
        seq_len=args.seq_len, vocab_size=args.vocab_size,
        mask_prob=args.mask_prob, corpus_branching=args.corpus_branching,
        attn_impl=args.attn_impl,
    )
    candidates = (
        tuple(float(c) for c in args.candidates.split(","))
        if args.candidates else DEFAULT_CANDIDATES
    )
    try:
        results = lr_sweep(cfg, candidates, steps=args.tune_steps,
                           sweep_dir=args.sweep_dir,
                           concurrency=args.concurrency)
    except ValueError as e:
        # e.g. an interrupted tune's journal records a different grid —
        # surface the resume contract instead of a traceback
        print(f"tune: {e}", file=sys.stderr)
        return 2
    for r in results:
        print(f"lr {r.lr:g}: final loss {r.final_loss:.4f}")
    print(f"best lr: {results[0].lr:g}")
    return 0


def _add_pool_flags(sp):
    """The trial-pool knobs `sweep run/resume` and `fleet run` share."""
    sp.add_argument("--concurrency", type=int, default=None,
                    help="concurrent trial subprocesses (default 2; "
                         "keep 1 on an accelerator host; a fleet run "
                         "derives it from the hosts' total capacity)")
    sp.add_argument("--trial-timeout", type=float, default=None,
                    metavar="SECS",
                    help="per-attempt wall budget; a trial past it is "
                         "terminated (SIGTERM -> emergency checkpoint) "
                         "and retried")
    sp.add_argument("--retries", type=int, default=None,
                    help="extra attempts per trial after a "
                         "crash/timeout (default 1); retried attempts "
                         "resume from the trial's last checkpoint")
    sp.add_argument("--heartbeat-grace", type=float, default=None,
                    metavar="SECS",
                    help="convict a RUNNING trial whose heartbeat "
                         "goes quiet past this many seconds (the "
                         "supervisor Watchdog grace routed through "
                         "the pool): it is terminated and re-queued "
                         "immediately instead of waiting out "
                         "--trial-timeout")
    sp.add_argument("--json", action="store_true",
                    help="emit the result record as JSON on stdout")


def _sweep_finish(result: dict, as_json: bool) -> int:
    """Shared tail of ``sweep run``/``resume``: print + exit code."""
    import json as _json

    from pytorch_distributed_nn_tpu.experiments import render_leaderboard

    if as_json:
        print(_json.dumps(result, default=str))
    else:
        print(
            f"sweep {result['scheduler']}: {result['trials']} trial(s), "
            f"{len(result['rungs'])} rung(s), "
            f"{result['executed_steps']} step(s) executed of "
            f"{result['planned_steps']} planned, "
            f"{result['wall_s']:.1f}s wall"
        )
        print(render_leaderboard(result["leaderboard"]))
        if result["best"] is not None:
            best = result["best"]
            cfg_s = " ".join(
                f"{k}={v}" for k, v in best["overrides"].items()
            )
            print(f"best: trial {best['trial']} ({cfg_s}) "
                  f"loss {best['loss']:.4f}")
        if result["failed"]:
            print(f"{len(result['failed'])} trial(s) failed after "
                  f"retries: {result['failed']}", file=sys.stderr)
    return 1 if result["failed"] else 0


def main_sweep(argv=None) -> int:
    """Sweep orchestrator (experiments/, docs/experiments.md).

    - ``run``     — execute a sweep spec: N trials as supervised
      subprocesses (bounded concurrency, per-trial timeout + retry with
      backoff), full-grid or ASHA-style successive-halving scheduling,
      everything journaled in ``<sweep-dir>/sweep.jsonl``.
    - ``resume``  — continue an interrupted sweep from its journal:
      completed trials are skipped (results reused byte-identically),
      dead trials re-queued, in-flight trials resume from their last
      valid checkpoint.
    - ``status``  — per-trial state straight off the journal.
    - ``report``  — ranked leaderboard with trailing-loss, step-rate and
      MFU columns sourced from the trial telemetry streams.
    - ``--selftest`` — <15 s scheduler/journal invariant gate
      (tools/lint.sh).
    """
    argv = list(argv) if argv is not None else sys.argv[1:]
    if "--selftest" in argv:
        from pytorch_distributed_nn_tpu.experiments.selftest import (
            run_selftest,
        )

        return run_selftest()

    p = argparse.ArgumentParser("pdtn-sweep", description=main_sweep.__doc__)
    sub = p.add_subparsers(dest="cmd", required=True)

    pr = sub.add_parser("run", help="execute a sweep spec")
    pr.add_argument("--sweep-dir", required=True,
                    help="journal + trials/<id>/ live here")
    pr.add_argument("--spec", default=None,
                    help="sweep spec, e.g. 'lr=0.1,0.01;batch_size=32,64' "
                         "or 'lr=log:1e-4..1e-1' with --samples "
                         "(docs/experiments.md grammar; default: the "
                         "reference tune.sh lr grid)")
    pr.add_argument("--samples", type=int, default=None,
                    help="random search: number of trials drawn from the "
                         "spec's ranges/lists")
    pr.add_argument("--sweep-seed", type=int, default=0,
                    help="seeds trial enumeration AND per-trial seeds "
                         "(SeedSequence((sweep_seed, trial_index)))")
    pr.add_argument("--steps", type=int, default=100,
                    help="full per-trial step budget (tune.sh: 100)")
    pr.add_argument("--tail", type=int, default=10,
                    help="trailing-loss ranking window")
    pr.add_argument("--scheduler", choices=["grid", "asha"], default="grid",
                    help="asha: successive-halving rungs — the top 1/eta "
                         "per rung continue (via checkpoint resume) to "
                         "eta x the budget")
    pr.add_argument("--eta", type=int, default=3,
                    help="asha reduction factor")
    pr.add_argument("--min-steps", type=int, default=None,
                    help="asha: first-rung budget (default: derived from "
                         "the trial count)")
    pr.add_argument("--ckpt-every", type=int, default=None,
                    help="per-trial checkpoint cadence (default: one "
                         "checkpoint at the rung budget); set it so a "
                         "killed sweep resumes trials mid-rung")
    pr.add_argument("--resume", action="store_true",
                    help="continue this sweep-dir's journal")
    pr.add_argument("--plan-mesh", type=int, default=0, metavar="DEVICES",
                    help="ask the roofline planner (cli analyze --plan, "
                         "docs/analysis.md) for each trial model's "
                         "predicted-fastest mesh over this many devices "
                         "and train the trial on it")
    # base config: every trial starts from these and applies its overrides
    pr.add_argument("--network", default="LeNet")
    pr.add_argument("--dataset", default="MNIST",
                    choices=["MNIST", "Cifar10", "Cifar100", "SVHN",
                             "MLMSynth", "NextTokenSynth"])
    pr.add_argument("--batch-size", type=int, default=32)
    pr.add_argument("--test-batch-size", type=int, default=32)
    pr.add_argument("--optimizer", choices=["sgd", "adam"], default="sgd")
    pr.add_argument("--momentum", type=float, default=0.9)
    pr.add_argument("--num-workers", type=int, default=None)
    pr.add_argument("--synthetic-size", type=int, default=None)
    pr.add_argument("--data-dir", default="./data")
    pr.add_argument("--data-path", default=None, metavar="DIR",
                    help="sharded streaming input for every trial "
                         "(docs/data.md) — the loader whose checkpointed "
                         "iterator state makes interrupted trials resume "
                         "bitwise (chaos sweep_resume relies on it; the "
                         "in-memory image loaders replay their epoch)")
    pr.add_argument("--dtype", choices=["float32", "bfloat16"],
                    default="float32")
    pr.add_argument("--seq-len", type=int, default=None)
    pr.add_argument("--vocab-size", type=int, default=None)
    pr.add_argument("--faults", default=None, metavar="SPEC",
                    help="per-trial deterministic fault injection "
                         "(docs/resilience.md grammar) — every trial "
                         "trains under this plan; the sweep_resume chaos "
                         "scenario uses it to widen its kill window")
    _add_pool_flags(pr)

    pres = sub.add_parser(
        "resume", help="continue an interrupted sweep from its journal "
                       "(spec, config and scheduler are read back from "
                       "the manifest)")
    pres.add_argument("--sweep-dir", required=True)
    _add_pool_flags(pres)

    ps = sub.add_parser("status", help="per-trial state off the journal")
    ps.add_argument("--sweep-dir", required=True)

    prep = sub.add_parser("report", help="ranked leaderboard from the "
                                         "journal + trial streams")
    prep.add_argument("--sweep-dir", required=True)
    prep.add_argument("--tail", type=int, default=10)
    prep.add_argument("--json", action="store_true")

    args = p.parse_args(argv)

    from pytorch_distributed_nn_tpu.experiments import (
        SweepInterrupted,
        load_journal,
    )

    if args.cmd == "status":
        from pytorch_distributed_nn_tpu.experiments.report import (
            render_status,
        )

        jstate = load_journal(args.sweep_dir)
        if jstate is None:
            print(f"no sweep journal under {args.sweep_dir}",
                  file=sys.stderr)
            return 2
        print(render_status(jstate))
        return 0

    if args.cmd == "report":
        import json as _json

        from pytorch_distributed_nn_tpu.experiments import (
            leaderboard,
            render_leaderboard,
        )

        jstate = load_journal(args.sweep_dir)
        if jstate is None:
            print(f"no sweep journal under {args.sweep_dir}",
                  file=sys.stderr)
            return 2
        rows = leaderboard(args.sweep_dir, jstate, tail=args.tail)
        print(_json.dumps(rows, default=str) if args.json
              else render_leaderboard(rows))
        return 0

    from pytorch_distributed_nn_tpu.experiments import (
        RunnerConfig,
        SweepRunner,
        SweepSpec,
    )
    from pytorch_distributed_nn_tpu.experiments.spec import DEFAULT_SPEC

    if args.cmd == "resume":
        jstate = load_journal(args.sweep_dir)
        if jstate is None:
            print(f"no sweep journal under {args.sweep_dir}",
                  file=sys.stderr)
            return 2
        meta = jstate.sweep_meta
        sched = meta.get("scheduler") or {}
        runner_meta = meta.get("runner") or {}
        base_cfg = dict(jstate.base_config or {})
        try:
            spec = SweepSpec.parse(
                meta.get("spec") or DEFAULT_SPEC,
                samples=meta.get("samples"),
                sweep_seed=int(meta.get("sweep_seed") or 0),
            )
            rcfg = RunnerConfig(
                sweep_dir=args.sweep_dir,
                max_steps=int(sched.get("max_steps") or 100),
                tail=int(runner_meta.get("tail") or 10),
                concurrency=int(
                    args.concurrency
                    or runner_meta.get("concurrency") or 2
                ),
                trial_timeout=(
                    args.trial_timeout
                    if args.trial_timeout is not None
                    else runner_meta.get("trial_timeout")
                ),
                retries=int(
                    args.retries if args.retries is not None
                    else runner_meta.get("retries", 1)
                ),
                ckpt_every=runner_meta.get("ckpt_every"),
                scheduler=sched.get("kind") or "grid",
                eta=int(sched.get("eta") or 3),
                min_steps=sched.get("min_steps"),
                plan_mesh=int(runner_meta.get("plan_mesh") or 0),
                heartbeat_grace=(
                    args.heartbeat_grace
                    if args.heartbeat_grace is not None
                    else runner_meta.get("heartbeat_grace")
                ),
                resume=True,
            )
        except ValueError as e:
            print(f"sweep resume: {e}", file=sys.stderr)
            return 2
        runner = SweepRunner(spec, base_cfg, rcfg)
        try:
            return _sweep_finish(runner.run(), args.json)
        except SweepInterrupted as e:
            print(f"sweep interrupted: {e} — continue with "
                  f"'sweep resume --sweep-dir {args.sweep_dir}'",
                  file=sys.stderr)
            return 3

    # run
    if args.plan_mesh:
        # the planner lowers over virtual meshes (analyze's pattern):
        # request enough host devices BEFORE any backend initializes;
        # trial subprocesses inherit the flag, which is what --plan-mesh
        # plans for
        flags = os.environ.get("XLA_FLAGS", "")
        if "xla_force_host_platform_device_count" not in flags:
            os.environ["XLA_FLAGS"] = (
                flags + " --xla_force_host_platform_device_count="
                f"{args.plan_mesh}"
            ).strip()

    from pytorch_distributed_nn_tpu.training.trainer import TrainConfig

    base = TrainConfig(
        network=args.network, dataset=args.dataset,
        batch_size=args.batch_size, test_batch_size=args.test_batch_size,
        optimizer=args.optimizer, momentum=args.momentum,
        num_workers=args.num_workers,
        synthetic_size=args.synthetic_size, data_dir=args.data_dir,
        data_path=args.data_path,
        dtype=args.dtype, seq_len=args.seq_len, vocab_size=args.vocab_size,
        seed=args.sweep_seed, faults=args.faults,
    )
    try:
        spec = SweepSpec.parse(
            args.spec or DEFAULT_SPEC,
            samples=args.samples, sweep_seed=args.sweep_seed,
        )
        runner = SweepRunner(
            spec, base,
            RunnerConfig(
                sweep_dir=args.sweep_dir, max_steps=args.steps,
                tail=args.tail,
                concurrency=args.concurrency or 2,
                trial_timeout=args.trial_timeout,
                retries=args.retries if args.retries is not None else 1,
                ckpt_every=args.ckpt_every,
                scheduler=args.scheduler, eta=args.eta,
                min_steps=args.min_steps, resume=args.resume,
                plan_mesh=args.plan_mesh,
                heartbeat_grace=args.heartbeat_grace,
            ),
        )
    except ValueError as e:
        print(f"sweep: {e}", file=sys.stderr)
        return 2
    try:
        return _sweep_finish(runner.run(), args.json)
    except ValueError as e:
        print(f"sweep: {e}", file=sys.stderr)
        return 2
    except SweepInterrupted as e:
        print(f"sweep interrupted: {e} — continue with "
              f"'sweep resume --sweep-dir {args.sweep_dir}'",
              file=sys.stderr)
        return 3


def main_fleet(argv=None) -> int:
    """Multi-host experiment fleet (experiments/fleet/,
    docs/experiments.md "Fleet").

    - ``agent``  — run a host agent: registers capacity (device count,
      labels, planner profile) over a JSON-line TCP protocol and runs
      assigned trials as supervised subprocesses; SIGTERM forwards to
      the trials (emergency checkpoints) before the agent exits.
    - ``run``    — the sweep orchestrator over a fleet: trials placed by
      host capacity, per-host planner-assigned meshes, dead hosts'
      in-flight trials migrated to survivors and elastically resumed
      from their last valid checkpoint. ``--resume`` continues an
      interrupted fleet sweep from its journal — including after the
      ORCHESTRATOR died.
    - ``status`` — journal-reconstructed fleet + trial state.
    - ``agents`` — probe ``--hosts`` agents live (hello each).
    - ``drain``  — stop new assignments on the named agents; running
      trials finish.
    - ``--selftest`` — <15 s transport/placement/migration invariant
      gate over local agents (tools/lint.sh); asserts the orchestrator
      process never imports jax.
    """
    argv = list(argv) if argv is not None else sys.argv[1:]
    if "--selftest" in argv:
        from pytorch_distributed_nn_tpu.experiments.fleet.selftest import (
            run_selftest,
        )

        return run_selftest()

    p = argparse.ArgumentParser("pdtn-fleet", description=main_fleet.__doc__)
    sub = p.add_subparsers(dest="cmd", required=True)

    pa = sub.add_parser("agent", help="run a host agent")
    pa.add_argument("--listen", default="127.0.0.1:0", metavar="HOST:PORT",
                    help="bind address (port 0 = ephemeral; pair with "
                         "--register so the orchestrator can find it)")
    pa.add_argument("--agent-id", default=None,
                    help="stable identity in the journal (default: "
                         "host-pid)")
    pa.add_argument("--devices", type=int, default=1,
                    help="device count advertised to the scheduler; with "
                         "--platform cpu also forced onto trial children "
                         "via xla_force_host_platform_device_count")
    pa.add_argument("--capacity", type=int, default=1,
                    help="concurrent trials this host accepts (keep 1 on "
                         "an accelerator host)")
    pa.add_argument("--label", action="append", default=None,
                    metavar="K=V", help="placement label (repeatable)")
    pa.add_argument("--register", default=None, metavar="FILE",
                    help="write a registration file (agent id, bound "
                         "address, pid, capacity) once listening")
    pa.add_argument("--platform", default="cpu",
                    help="JAX_PLATFORMS for trial children ('' = leave "
                         "the environment alone, e.g. on a TPU host)")
    pa.add_argument("--idle-timeout", type=float, default=0.0,
                    metavar="SECS",
                    help="exit (terminating trials into emergency "
                         "checkpoints) after this much orchestrator "
                         "silence; 0 = never (the local transport "
                         "always sets it for its agents)")

    def _add_fleet_flags(sp):
        sp.add_argument("--transport", choices=["local", "tcp"],
                        default="local")
        sp.add_argument("--agents", type=int, default=3,
                        help="local transport: agent subprocesses to "
                             "spawn")
        sp.add_argument("--agent-devices", default=None, metavar="N,N,...",
                        help="local transport: per-agent device counts "
                             "(cycled; default 1 each)")
        sp.add_argument("--agent-capacity", type=int, default=1,
                        help="local transport: trials per agent")
        sp.add_argument("--hosts", default=None, metavar="H:P,H:P,...",
                        help="tcp transport: running agents to attach to "
                             "(sweep dir must be on shared storage)")
        sp.add_argument("--lease", type=float, default=10.0,
                        help="seconds of silence before a host is "
                             "declared dead and its trials migrate")
        sp.add_argument("--call-timeout", type=float, default=2.0,
                        help="per-RPC socket timeout")
        sp.add_argument("--plan-hosts", action="store_true",
                        help="assign each trial's mesh from the roofline "
                             "planner ranked against its host's profile "
                             "(memoized in the shared fleet cache)")

    pr = sub.add_parser("run", help="run a sweep over the fleet")
    pr.add_argument("--sweep-dir", required=True)
    pr.add_argument("--spec", default=None)
    pr.add_argument("--samples", type=int, default=None)
    pr.add_argument("--sweep-seed", type=int, default=0)
    pr.add_argument("--steps", type=int, default=100)
    pr.add_argument("--tail", type=int, default=10)
    pr.add_argument("--scheduler", choices=["grid", "asha"],
                    default="grid")
    pr.add_argument("--eta", type=int, default=3)
    pr.add_argument("--min-steps", type=int, default=None)
    pr.add_argument("--ckpt-every", type=int, default=None)
    pr.add_argument("--resume", action="store_true",
                    help="continue this sweep-dir's journal (fresh fleet; "
                         "completed trials reused byte-identically, "
                         "in-flight ones re-dispatched with resume)")
    # base config (every trial starts from these, like `sweep run`)
    pr.add_argument("--network", default="LeNet")
    pr.add_argument("--dataset", default="MNIST",
                    choices=["MNIST", "Cifar10", "Cifar100", "SVHN",
                             "MLMSynth", "NextTokenSynth"])
    pr.add_argument("--batch-size", type=int, default=32)
    pr.add_argument("--test-batch-size", type=int, default=32)
    pr.add_argument("--optimizer", choices=["sgd", "adam"], default="sgd")
    pr.add_argument("--momentum", type=float, default=0.9)
    pr.add_argument("--num-workers", type=int, default=None)
    pr.add_argument("--synthetic-size", type=int, default=None)
    pr.add_argument("--data-dir", default="./data")
    pr.add_argument("--data-path", default=None, metavar="DIR")
    pr.add_argument("--dtype", choices=["float32", "bfloat16"],
                    default="float32")
    pr.add_argument("--seq-len", type=int, default=None)
    pr.add_argument("--vocab-size", type=int, default=None)
    pr.add_argument("--faults", default=None, metavar="SPEC")
    pr.add_argument("--synthetic-trials", action="store_true",
                    help="run the jax-free synthetic trial main instead "
                         "of real training — the orchestration surface "
                         "without the training cost (tests/CI)")
    pr.add_argument("--step-sleep", type=float, default=0.0,
                    metavar="SECS",
                    help="synthetic trials: uniform per-step pacing")
    _add_fleet_flags(pr)
    _add_pool_flags(pr)

    ps = sub.add_parser("status", help="journal-reconstructed fleet + "
                                       "trial state")
    ps.add_argument("--sweep-dir", required=True)

    pag = sub.add_parser("agents", help="probe running agents (hello)")
    pag.add_argument("--hosts", required=True, metavar="H:P,H:P,...")
    pag.add_argument("--call-timeout", type=float, default=2.0)

    pd = sub.add_parser("drain", help="stop new assignments on agents")
    pd.add_argument("--hosts", required=True, metavar="H:P,H:P,...")
    pd.add_argument("--call-timeout", type=float, default=2.0)

    args = p.parse_args(argv)

    if args.cmd == "agent":
        from pytorch_distributed_nn_tpu.experiments.fleet.agent import (
            agent_main,
        )

        if args.agent_id is None:
            import platform as _plat

            args.agent_id = f"{_plat.node()}-{os.getpid()}"
        try:
            return agent_main(args)
        except (ValueError, OSError) as e:
            print(f"fleet agent: {e}", file=sys.stderr)
            return 2

    if args.cmd == "status":
        from pytorch_distributed_nn_tpu.experiments import load_journal
        from pytorch_distributed_nn_tpu.experiments.report import (
            render_fleet,
            render_status,
        )

        jstate = load_journal(args.sweep_dir)
        if jstate is None:
            print(f"no sweep journal under {args.sweep_dir}",
                  file=sys.stderr)
            return 2
        print(render_fleet(jstate))
        print(render_status(jstate))
        return 0

    if args.cmd in ("agents", "drain"):
        from pytorch_distributed_nn_tpu.experiments.fleet.transport import (
            call_once,
            probe_hosts,
        )

        addrs = [a for a in args.hosts.split(",") if a]
        rows = probe_hosts(addrs, timeout=args.call_timeout)
        rc = 0
        for addr, info, err in rows:
            if info is None:
                print(f"{addr}: UNREACHABLE ({err})")
                rc = 1
                continue
            if args.cmd == "drain":
                host, _, port = addr.rpartition(":")
                resp = call_once((host, int(port)), {"op": "drain"},
                                 timeout=args.call_timeout)
                print(f"{addr}: {info.agent_id} draining "
                      f"(running: {resp.get('running')})")
            else:
                print(f"{addr}: {info.agent_id} devices={info.devices} "
                      f"capacity={info.capacity} "
                      f"draining={info.draining} labels={info.labels}")
        return rc

    # run
    from pytorch_distributed_nn_tpu.experiments import (
        SweepInterrupted,
        SweepSpec,
    )
    from pytorch_distributed_nn_tpu.experiments.fleet import (
        FleetConfig,
        FleetScheduler,
    )
    from pytorch_distributed_nn_tpu.experiments.fleet.transport import (
        FleetError,
    )
    from pytorch_distributed_nn_tpu.experiments.spec import DEFAULT_SPEC
    # jax-free config split (training/config.py): the fleet orchestrator
    # never imports jax — trials do, in their own processes on their hosts
    from pytorch_distributed_nn_tpu.training.config import TrainConfig

    if args.synthetic_trials:
        base = {
            "network": "SynthNet", "lr": 0.1, "faults": args.faults,
            "batch_size": args.batch_size, "step_sleep": args.step_sleep,
        }
    else:
        base = TrainConfig(
            network=args.network, dataset=args.dataset,
            batch_size=args.batch_size,
            test_batch_size=args.test_batch_size,
            optimizer=args.optimizer, momentum=args.momentum,
            num_workers=args.num_workers,
            synthetic_size=args.synthetic_size, data_dir=args.data_dir,
            data_path=args.data_path,
            dtype=args.dtype, seq_len=args.seq_len,
            vocab_size=args.vocab_size,
            seed=args.sweep_seed, faults=args.faults,
        )
    try:
        if args.transport == "tcp" and not args.hosts:
            raise ValueError("--transport tcp needs --hosts")
        spec = SweepSpec.parse(
            args.spec or DEFAULT_SPEC,
            samples=args.samples, sweep_seed=args.sweep_seed,
        )
        runner = FleetScheduler(
            spec, base,
            FleetConfig(
                sweep_dir=args.sweep_dir, max_steps=args.steps,
                tail=args.tail,
                trial_timeout=args.trial_timeout,
                retries=args.retries if args.retries is not None else 1,
                ckpt_every=args.ckpt_every,
                scheduler=args.scheduler, eta=args.eta,
                min_steps=args.min_steps, resume=args.resume,
                heartbeat_grace=args.heartbeat_grace,
                transport=args.transport, agents=args.agents,
                agent_devices=tuple(
                    int(d) for d in args.agent_devices.split(",") if d
                ) if args.agent_devices else (),
                agent_capacity=args.agent_capacity,
                hosts=tuple(
                    a for a in (args.hosts or "").split(",") if a
                ),
                lease=args.lease, call_timeout=args.call_timeout,
                plan_hosts=args.plan_hosts,
                trial_main_name=(
                    "synthetic" if args.synthetic_trials else "default"
                ),
            ),
        )
    except ValueError as e:
        print(f"fleet: {e}", file=sys.stderr)
        return 2
    try:
        return _sweep_finish(runner.run(), args.json)
    except ValueError as e:
        print(f"fleet: {e}", file=sys.stderr)
        return 2
    except SweepInterrupted as e:
        print(f"fleet sweep interrupted: {e} — continue with "
              f"'fleet run --resume --sweep-dir {args.sweep_dir}'",
              file=sys.stderr)
        return 3
    except FleetError as e:
        # every host dead (or the fleet failed to start): the journal
        # holds all completed work — resumable, like an interruption
        print(f"fleet: {e}", file=sys.stderr)
        return 3


def main_prepare_data(argv=None) -> int:
    """Pre-download datasets (reference: src/data/data_prepare.py +
    data_prepare.sh). Run once on a host with network egress; training
    hosts then load from --data-dir without fetching."""
    p = argparse.ArgumentParser(
        "pdtn-prepare-data", description=main_prepare_data.__doc__
    )
    p.add_argument("--data-dir", default="./data")
    p.add_argument("--datasets", default=None,
                   help="comma-separated subset (default: all of "
                        "MNIST,Cifar10,Cifar100,SVHN)")
    args = p.parse_args(argv)

    from pytorch_distributed_nn_tpu.data.datasets import DATASETS, prepare_data

    names = (
        tuple(args.datasets.split(",")) if args.datasets else DATASETS
    )
    results = prepare_data(args.data_dir, names)
    failed = 0
    for name, status in results.items():
        print(f"{name}: {status}")
        failed += status.startswith("failed")
    if failed:
        print(f"{failed}/{len(results)} datasets unavailable (offline?); "
              "training falls back to synthetic data for those",
              file=sys.stderr)
    return 1 if failed == len(results) else 0


def _parse_mesh_arg(mesh_arg: str):
    """'4x2' → (data=4, model=2, seq=1); '2x2x2' → (data, model, seq)."""
    try:
        parts = [int(p) for p in mesh_arg.lower().split("x")]
    except ValueError:
        raise SystemExit(f"--mesh must look like '8', '4x2' or '2x2x2', "
                         f"got {mesh_arg!r}")
    if not 1 <= len(parts) <= 3 or any(p < 1 for p in parts):
        raise SystemExit(f"--mesh must have 1-3 positive extents, "
                         f"got {mesh_arg!r}")
    parts += [1] * (3 - len(parts))
    return tuple(parts)  # (data, model, seq)


_MODEL_ALIASES = {"bert_tiny": "BertTiny", "bert_base": "BertBase",
                  "lenet": "LeNet", "gpt_tiny": "GptTiny",
                  "gpt_mini": "GptMini"}


def _decode_cost_block(args, model_name):
    """The decode-phase roofline of ``analyze --cost`` for causal
    decoders (docs/analysis.md "Decode roofline"): per-token FLOPs +
    KV-cache HBM bytes from the closed-form model, plus the calibrated
    backend's predicted tokens/s (a prediction: no benchmark cell
    measures decode yet). Returns the dict (for --json) or None for
    non-generative models."""
    from pytorch_distributed_nn_tpu.models import (
        build_model,
        is_generative_model,
    )

    if not is_generative_model(model_name):
        return None
    import numpy as np

    from pytorch_distributed_nn_tpu.analysis.calibration import live_profile
    from pytorch_distributed_nn_tpu.analysis.costmodel import (
        decode_phase_cost,
    )

    model_kw = {k: v for k, v in {
        "vocab_size": args.vocab_size,
        "max_len": args.seq_len,
        "d_model": args.d_model,
        "num_layers": args.num_layers,
        "num_heads": args.num_heads,
        "d_ff": args.d_ff,
    }.items() if v is not None}
    cfg = build_model(model_name, 0, **model_kw).config
    cache_len = args.seq_len or cfg.max_len
    batch = args.batch_size or 8
    dc = decode_phase_cost(
        num_layers=cfg.num_layers, d_model=cfg.d_model, d_ff=cfg.d_ff,
        vocab_size=cfg.vocab_size, cache_len=cache_len, batch=batch,
        weight_bytes_per_param=4,
        kv_bytes_per_elem=np.dtype(cfg.dtype).itemsize,
    )
    prof = live_profile()
    pred = dc.predicted_tokens_per_s(
        prof.peak_flops_per_s, prof.hbm_peak_bytes_per_s
    )
    out = dc.to_dict()
    out["predicted_tokens_per_s"] = round(pred, 1)
    out["calibration_backend"] = prof.backend
    out["text"] = (
        dc.to_text()
        + f"\n  roofline tokens/s (per sequence, {prof.backend} "
        f"calibration): {pred:,.0f}"
    )
    return out


def _build_analyze_bundle(args, num_data, num_model, num_seq):
    """Model + mesh + audit bundle for the analyze/calibrate surfaces.

    Returns the ``analysis.audit(**bundle)`` kwargs, or None (after an
    actionable stderr message) when the combination is unbuildable.
    """
    from pytorch_distributed_nn_tpu.models import build_model, is_text_model
    from pytorch_distributed_nn_tpu.optim import build_optimizer
    from pytorch_distributed_nn_tpu.parallel import (
        make_grad_sync,
        make_mesh,
        make_mesh_attn,
    )

    model_name = _MODEL_ALIASES.get(args.model, args.model)
    mesh = make_mesh(num_data, num_model, num_seq)
    opt = build_optimizer(args.optimizer, 1e-3)
    batch = args.batch_size or 2 * num_data

    if is_text_model(model_name):
        from pytorch_distributed_nn_tpu.training import spmd_audit_bundle

        model_kw = {k: v for k, v in {
            "vocab_size": args.vocab_size,
            "max_len": args.seq_len,
            "d_model": args.d_model,
            "num_layers": args.num_layers,
            "num_heads": args.num_heads,
            "d_ff": args.d_ff,
        }.items() if v is not None}
        attn_fn = make_mesh_attn(mesh, args.seq_attn) if num_seq > 1 else None
        model = build_model(model_name, 0, attn_fn=attn_fn, **model_kw)
        seq_len = args.seq_len or model.config.max_len
        return spmd_audit_bundle(
            model, opt, mesh, (batch, seq_len),
            compression=args.compress_grad, grad_accum=args.grad_accum,
            donate=getattr(args, "check_donation", False),
        )
    from pytorch_distributed_nn_tpu.models import input_spec
    from pytorch_distributed_nn_tpu.training import dp_audit_bundle

    if num_model > 1 or num_seq > 1:
        print(f"{model_name} audits the data-parallel path; use a "
              f"pure-data mesh (e.g. --mesh "
              f"{num_data * num_model * num_seq})", file=sys.stderr)
        return None
    model = build_model(model_name, 10)
    sync = make_grad_sync("allreduce")
    return dp_audit_bundle(
        model, opt, sync, mesh, input_spec(model_name), batch,
        donate=getattr(args, "check_donation", False),
    )


def _run_plan(args) -> int:
    """``cli analyze --plan``: ranked mesh table under the roofline."""
    import json as _json

    from pytorch_distributed_nn_tpu.analysis import planner
    from pytorch_distributed_nn_tpu.analysis.calibration import (
        CalibrationProfile,
    )

    profile = (
        CalibrationProfile.load(args.calibration)
        if args.calibration else None
    )
    try:
        result = planner.plan(
            args.model, args.devices, profile=profile,
            batch_size=args.batch_size, optimizer=args.optimizer,
            seq_len=args.seq_len, validate=args.validate,
            seq_attn=args.seq_attn,
        )
    except ValueError as e:
        print(f"plan: {e}", file=sys.stderr)
        return 2
    payload = _json.dumps(result, indent=2)
    if args.out:
        with open(args.out, "w") as f:
            f.write(payload + "\n")
    print(payload if args.json else planner.render_plan(result))
    if args.check:
        ok = (
            result.get("top") is not None
            and len([c for c in result["candidates"]
                     if not c.get("skipped")]) >= 2
            and all(c["predicted_ms"] > 0 for c in result["candidates"]
                    if not c.get("skipped"))
        )
        print(f"plan --check: {'PASS' if ok else 'FAIL'}", file=sys.stderr)
        return 0 if ok else 1
    return 0


def _run_calibrate(args, num_data, num_model, num_seq) -> int:
    """``cli analyze --calibrate``: fit + persist a calibration.json."""
    from pytorch_distributed_nn_tpu.analysis import calibration

    prof = calibration.live_profile()
    if args.trace:
        from pytorch_distributed_nn_tpu import analysis

        bundle = _build_analyze_bundle(args, num_data, num_model, num_seq)
        if bundle is None:
            return 2
        report = analysis.audit(**{
            k: v for k, v in bundle.items()
            if k in ("step_fn", "args", "mesh", "params",
                     "param_shardings", "abstract_params")
        })
        if report.cost is None:
            print("calibrate: cost walk failed for the --model step",
                  file=sys.stderr)
            return 2
        try:
            prof = calibration.fit_from_trace(
                args.trace, report.cost.to_dict(), args.trace_steps,
                base=prof,
            )
        except Exception as e:
            print(f"calibrate: trace fit failed: {e}", file=sys.stderr)
            return 2
    if args.microbench:
        prof = calibration.fit_microbench(base=prof)
    out = args.out or calibration.CALIBRATION_BASENAME
    prof.save(out)
    print(f"wrote {out}: profile {prof.name} (source {prof.source}), "
          f"peak {prof.peak_flops_per_s / 1e12:.2f} TFLOP/s, "
          f"HBM {prof.hbm_bytes_per_s / 1e9:.1f} GB/s, "
          f"ICI {prof.ici_bytes_per_s / 1e9:.1f} GB/s")
    return 0


def main_analyze(argv=None) -> int:
    """Compile-time SPMD sharding & collective audit (no TPU needed).

    Lowers the real train step for --model over a virtual --mesh, lints
    the optimized HLO (rules SL001-SL006, docs/analysis.md), and prints a
    collective inventory with estimated ICI bytes per step. Exits
    non-zero when any --fail-on rule fires, so CI can gate sharding
    regressions on CPU.
    """
    from pytorch_distributed_nn_tpu.analysis.rules import DEFAULT_FAIL_ON

    p = argparse.ArgumentParser("pdtn-analyze", description=main_analyze.__doc__)
    p.add_argument("--model", default="bert_tiny",
                   help="model zoo name (bert_tiny/bert_base aliases or any "
                        "registry name; image models audit the dp path)")
    p.add_argument("--mesh", default="4x2",
                   help="data[xmodel[xseq]] extents of the virtual mesh, "
                        "e.g. 8, 4x2, 2x2x2")
    p.add_argument("--batch-size", type=int, default=None,
                   help="global batch (default: 2 per data-parallel rank)")
    p.add_argument("--seq-len", type=int, default=None,
                   help="text models: sequence length (default: model spec)")
    p.add_argument("--vocab-size", type=int, default=None)
    p.add_argument("--d-model", type=int, default=None)
    p.add_argument("--num-layers", type=int, default=None)
    p.add_argument("--num-heads", type=int, default=None)
    p.add_argument("--d-ff", type=int, default=None)
    p.add_argument("--optimizer", choices=["sgd", "adam"], default="adam")
    p.add_argument("--seq-attn", choices=["ring", "ulysses"], default="ring",
                   help="attention impl when the seq mesh axis is > 1")
    p.add_argument("--compress-grad", choices=["none", "int8"], default="none")
    p.add_argument("--grad-accum", type=int, default=1)
    p.add_argument("--check-recompile", action="store_true",
                   help="also execute the step twice and flag SL006 on "
                        "recompilation")
    p.add_argument("--check-donation", action="store_true",
                   help="build the PRODUCTION (donating) step and run the "
                        "SL007 buffer-donation audit on its compiled "
                        "input_output_alias table — incompatible with "
                        "--check-recompile (a donating step cannot be "
                        "executed twice on the same buffers)")
    p.add_argument("--cost", action="store_true",
                   help="print the static FLOPs/bytes accounting of the "
                        "step (analysis/costmodel.py): per-family FLOPs, "
                        "HBM operand+result bytes, ICI bytes — the "
                        "roofline planner's inputs (always present in "
                        "--json output)")
    p.add_argument("--plan", action="store_true",
                   help="rank mesh factorizations x partitioning-rule "
                        "overrides for --model over --devices devices "
                        "under the calibrated roofline "
                        "(docs/analysis.md 'Cost model & planner'); "
                        "--validate also measures each candidate")
    p.add_argument("--devices", type=int, default=8,
                   help="--plan/--calibrate: device count to plan for "
                        "(virtual CPU devices are provisioned, like the "
                        "audit's --mesh)")
    p.add_argument("--validate", action="store_true",
                   help="--plan: execute every candidate a few steps and "
                        "report measured ms next to predicted (the "
                        "cross-validation harness)")
    p.add_argument("--check", action="store_true",
                   help="--plan: <10s CI smoke — plan LeNet over 2 CPU "
                        "devices with the default calibration and verify "
                        "the table's invariants (tools/lint.sh)")
    p.add_argument("--calibrate", action="store_true",
                   help="fit per-family roofline ceilings into a "
                        "calibration.json: from an xplane trace "
                        "(--trace, using --model/--mesh for the static "
                        "cost) and/or bounded microbenches "
                        "(--microbench); no source writes the checked-in "
                        "defaults for this backend")
    p.add_argument("--trace", default=None, metavar="DIR",
                   help="--calibrate: xplane trace directory (a "
                        "--profile run's profile dir)")
    p.add_argument("--trace-steps", type=int, default=1,
                   help="--calibrate: how many steps the trace covers")
    p.add_argument("--microbench", action="store_true",
                   help="--calibrate: run the bounded matmul/copy "
                        "microbenches on the live backend")
    p.add_argument("--calibration", default=None, metavar="FILE",
                   help="--plan: load ceilings from this calibration.json "
                        "instead of the backend's default profile")
    p.add_argument("--suppress", default="",
                   help="comma-separated rule IDs to drop (e.g. SL002)")
    p.add_argument("--fail-on", default=",".join(DEFAULT_FAIL_ON),
                   help="comma-separated rule IDs that force exit code 1 "
                        "('' disables gating)")
    p.add_argument("--json", action="store_true",
                   help="emit the full report as JSON on stdout")
    p.add_argument("--out", default=None,
                   help="also write the JSON report to this file")
    args = p.parse_args(argv)

    if args.check and not args.plan:
        print("--check only applies with --plan", file=sys.stderr)
        return 2
    if args.check_donation and args.check_recompile:
        print("--check-donation builds a donating step; it cannot be "
              "combined with --check-recompile's double execution",
              file=sys.stderr)
        return 2
    if args.plan and args.check:
        # the lint-time smoke: tiny model, 2 virtual devices, default
        # calibration, no measurement — seconds, not minutes
        args.model = "lenet"
        args.devices = 2
        args.validate = False
    num_data, num_model, num_seq = _parse_mesh_arg(args.mesh)
    needed = num_data * num_model * num_seq
    if args.plan:
        needed = max(needed, args.devices)

    # The audit is a CPU tool by design: force the host platform and ask
    # XLA for enough virtual devices BEFORE the backend initializes.
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + f" --xla_force_host_platform_device_count={needed}"
        ).strip()

    import jax

    if args.plan:
        return _run_plan(args)
    if args.calibrate:
        return _run_calibrate(args, num_data, num_model, num_seq)

    if len(jax.devices()) < needed:
        print(f"mesh {args.mesh} needs {needed} devices but only "
              f"{len(jax.devices())} are available (JAX backend was "
              f"initialized before the analyzer could request virtual CPU "
              f"devices)", file=sys.stderr)
        return 2

    from pytorch_distributed_nn_tpu import analysis

    bundle = _build_analyze_bundle(args, num_data, num_model, num_seq)
    if bundle is None:
        return 2

    audit_kw = {}
    if args.suppress:
        audit_kw["suppress"] = tuple(
            s for s in args.suppress.split(",") if s
        )
    if args.check_recompile:
        audit_kw["second_args"] = bundle["args"]
    if args.check_donation:
        audit_kw["donation"] = "step"
    report = analysis.audit(**bundle, **audit_kw)

    payload = report.to_json()
    decode_cost = (
        _decode_cost_block(
            args, _MODEL_ALIASES.get(args.model, args.model)
        )
        if args.cost else None
    )
    if decode_cost is not None:
        # ride the decode-phase roofline on the JSON report (the
        # training-step audit knows nothing about serving phases)
        import json as _json

        doc = _json.loads(payload)
        doc["decode_cost"] = {
            k: v for k, v in decode_cost.items() if k != "text"
        }
        payload = _json.dumps(doc)
    if args.out:
        with open(args.out, "w") as f:
            f.write(payload + "\n")
    print(payload if args.json else report.to_text())
    if args.cost and not args.json:
        print()
        print(report.cost.to_text() if report.cost is not None
              else "step cost: unavailable (cost walk failed)")
        if decode_cost is not None:
            print()
            print(decode_cost["text"])

    fail_on = {s for s in args.fail_on.split(",") if s}
    fired = fail_on.intersection(report.fired_rules())
    if fired:
        print(f"analyze: gating rule(s) fired: {sorted(fired)}",
              file=sys.stderr)
        return 1
    return 0


def main_lint(argv=None) -> int:
    """Project-native source lint (docs/analysis.md "Source lint").

    Audits the package's OWN source with stdlib ``ast`` — concurrency
    discipline (PL001-PL004), contract drift against the hand-maintained
    catalogues (PL010-PL012) and the static jax-purity import graph
    (PL020). Never imports jax, zero third-party deps: this is the lint
    gate that still runs on the hermetic TPU image where ruff/mypy were
    never installed (tools/lint.sh runs it unconditionally). Exits 1
    when any unsuppressed finding stands.
    """
    p = argparse.ArgumentParser("pdtn-lint", description=main_lint.__doc__)
    p.add_argument("--json", action="store_true",
                   help="machine-readable report (findings + suppressions "
                        "+ rule catalogue versions)")
    p.add_argument("--select", action="append", default=None,
                   metavar="PREFIX",
                   help="only run rules matching these id prefixes "
                        "(repeatable / comma-separated: --select PL00 "
                        "runs the concurrency family)")
    p.add_argument("--ignore", action="append", default=None,
                   metavar="PREFIX",
                   help="drop rules matching these id prefixes")
    p.add_argument("--path", action="append", default=None, metavar="PATH",
                   help="restrict the per-file rules to these repo-"
                        "relative files/dirs; the global catalogue + "
                        "purity rules only run on a whole-repo pass")
    p.add_argument("--root", default=None,
                   help="repo root (default: auto-detected from the "
                        "installed package location)")
    p.add_argument("--selftest", action="store_true",
                   help="fixture-driven proof the linter itself works: "
                        "plants one bug per rule family in a temp tree "
                        "and asserts each fires exactly where planted "
                        "(<10s, no jax)")
    args = p.parse_args(argv)

    if args.selftest:
        from pytorch_distributed_nn_tpu.analysis.sourcelint.selftest import (
            run_selftest,
        )

        return run_selftest()

    from pytorch_distributed_nn_tpu.analysis.sourcelint import audit_sources

    def _split(vals):
        if vals is None:
            return None
        out = [s.strip() for v in vals for s in v.split(",") if s.strip()]
        return tuple(out) or None

    report = audit_sources(
        args.root,
        paths=args.path,
        select=_split(args.select),
        ignore=_split(args.ignore) or (),
    )
    print(report.to_json() if args.json else report.to_text())
    return 1 if report.findings else 0


def main_data(argv=None) -> int:
    """Streaming shard tooling (docs/data.md): `export` converts the
    in-memory datasets into the length-prefixed `.pdsr` shard format the
    streaming loader (`train --data-path`) reads; `info` prints a shard
    directory's manifest. Pure host-side numpy — no accelerator needed.
    """
    p = argparse.ArgumentParser("pdtn-data", description=main_data.__doc__)
    sub = p.add_subparsers(dest="cmd", required=True)

    pe = sub.add_parser(
        "export", help="write a shard directory from an in-memory dataset"
    )
    pe.add_argument("--out", required=True, metavar="DIR",
                    help="shard directory to write (dataset.json + "
                         "shard-*.pdsr)")
    pe.add_argument("--kind", choices=["image", "tokens"], default="image")
    pe.add_argument("--shards", type=int, default=8,
                    help="number of shard files (>= the host count the "
                         "training run will use)")
    # image kind
    pe.add_argument("--dataset", default="Cifar10",
                    choices=["MNIST", "Cifar10", "Cifar100", "SVHN"],
                    help="image kind: which dataset to export")
    pe.add_argument("--data-dir", default="./data")
    pe.add_argument("--synthetic-size", type=int, default=None,
                    help="image kind: force synthetic data of this size")
    pe.add_argument("--split", choices=["train", "test"], default="train")
    # tokens kind
    pe.add_argument("--sequences", type=int, default=4096,
                    help="tokens kind: number of sequences to draw")
    pe.add_argument("--vocab-size", type=int, default=1024)
    pe.add_argument("--corpus-branching", type=int, default=8)
    pe.add_argument("--min-len", type=int, default=16)
    pe.add_argument("--max-len", type=int, default=128)
    pe.add_argument("--seed", type=int, default=0)

    pi = sub.add_parser("info", help="print a shard directory's manifest")
    pi.add_argument("path")
    args = p.parse_args(argv)

    import json as _json

    from pytorch_distributed_nn_tpu.data.streaming import (
        export_image_dataset,
        export_text_corpus,
        load_meta,
    )

    if args.cmd == "info":
        print(_json.dumps(load_meta(args.path), indent=2, sort_keys=True))
        return 0
    if args.kind == "image":
        from pytorch_distributed_nn_tpu.data.datasets import load_dataset

        ds = load_dataset(args.dataset, train=args.split == "train",
                          data_dir=args.data_dir,
                          synthetic_size=args.synthetic_size)
        meta = export_image_dataset(ds, args.out, shards=args.shards)
    else:
        meta = export_text_corpus(
            args.out, shards=args.shards, sequences=args.sequences,
            vocab_size=args.vocab_size, branching=args.corpus_branching,
            min_len=args.min_len, max_len=args.max_len, seed=args.seed,
        )
    print(f"wrote {len(meta['shards'])} shard(s), "
          f"{meta['num_records']} records to {args.out}")
    return 0


def main_registry(argv=None) -> int:
    """Model registry (serving/registry.py, docs/serving.md "Deployment
    lifecycle"): versioned serving artifacts with labels and rollback.

    - ``publish``  — register an exported artifact (CRC-verified; torn
      artifacts are refused) under its immutable version id
      ``<train_dir>@<step>:<quantize>``, optionally labeling it.
    - ``list``     — entries with their labels.
    - ``label``    — atomically point ``stable``/``canary`` at a version
      (``-`` clears the label).
    - ``rollback`` — restore a label's previous holder (the operator
      undo; the canary router calls the same primitive automatically).
    - ``gc``       — retire entries that are neither labeled nor among
      the newest K and RELEASE their checkpoint protection in the source
      train_dir's ``published.json``.
    - ``watch``    — poll a directory for new exports and publish them
      (the reference evaluator's NFS loop, pointed at exports).
    - ``verify``   — CRC-check one entry end to end.
    - ``--selftest`` — <2 s invariant gate (tools/lint.sh).

    Pure host-side json/os — runs on a login node, like ``obs``.
    """
    argv = list(argv) if argv is not None else sys.argv[1:]
    if "--selftest" in argv:
        from pytorch_distributed_nn_tpu.serving.registry import selftest

        return selftest()

    import json as _json

    from pytorch_distributed_nn_tpu.serving.registry import (
        Registry,
        RegistryError,
        render_entries,
    )

    p = argparse.ArgumentParser(
        "pdtn-registry", description=main_registry.__doc__
    )
    sub = p.add_subparsers(dest="cmd", required=True)

    def _add(name, help):
        sp = sub.add_parser(name, help=help)
        sp.add_argument("--registry", required=True, metavar="DIR",
                        help="registry root (registry.json lives here)")
        return sp

    pp = _add("publish", "register an exported artifact")
    pp.add_argument("--artifact", required=True, metavar="DIR")
    pp.add_argument("--label", default=None, metavar="L1,L2",
                    help="also point these labels (stable,canary) at it")
    pl = _add("list", "entries + labels")
    pl.add_argument("--json", action="store_true")
    pla = _add("label", "atomically move a label")
    pla.add_argument("name", choices=["stable", "canary"])
    pla.add_argument("version",
                     help="version id to point the label at ('-' clears)")
    prb = _add("rollback", "restore a label's previous holder")
    prb.add_argument("--label", default="stable",
                     choices=["stable", "canary"])
    pg = _add("gc", "retire unlabeled old entries + release their "
                    "checkpoint protection")
    pg.add_argument("--keep-last", type=int, required=True, metavar="K")
    pg.add_argument("--delete-artifacts", action="store_true",
                    help="also remove the retired artifact directories")
    pg.add_argument("--json", action="store_true")
    pw = _add("watch", "poll a directory for new exports")
    pw.add_argument("--dir", required=True, metavar="DIR",
                    help="directory whose child artifact dirs are "
                         "published as they appear")
    pw.add_argument("--label", default=None, metavar="L1,L2",
                    help="labels for every picked-up export (e.g. "
                         "'stable' to make publishing deploy)")
    pw.add_argument("--interval", type=float, default=5.0, metavar="SECS")
    pw.add_argument("--max-polls", type=int, default=None,
                    help="stop after N polls (default: forever)")
    pv = _add("verify", "CRC-check one entry")
    pv.add_argument("version")
    args = p.parse_args(argv)

    reg = Registry(args.registry)
    labels = tuple(
        s for s in (getattr(args, "label", None) or "").split(",") if s
    ) if getattr(args, "label", None) else ()
    try:
        if args.cmd == "publish":
            entry = reg.publish(args.artifact, labels=labels)
            print(f"published {entry['version']} -> {entry['artifact']}"
                  + (f" labels={list(labels)}" if labels else ""))
        elif args.cmd == "list":
            doc = reg.load()
            print(_json.dumps(doc, indent=2, sort_keys=True)
                  if args.json else render_entries(doc))
        elif args.cmd == "label":
            version = None if args.version == "-" else args.version
            print(reg.label(args.name, version))
        elif args.cmd == "rollback":
            frm, to = reg.rollback(args.label)
            print(f"rolled back {args.label}: {frm} -> {to}")
        elif args.cmd == "gc":
            res = reg.gc(args.keep_last,
                         delete_artifacts=args.delete_artifacts)
            print(_json.dumps(res) if args.json else
                  f"retired {len(res['retired'])} entr(ies) "
                  f"{res['retired']}; kept {res['kept']}")
        elif args.cmd == "watch":
            import time as _time

            polls = 0
            while args.max_polls is None or polls < args.max_polls:
                if polls:
                    _time.sleep(args.interval)
                polls += 1
                for entry in reg.scan_dir(args.dir, labels=labels):
                    print(f"picked up {entry['version']} "
                          f"({entry['artifact']})")
        elif args.cmd == "verify":
            ok, reason = reg.verify(args.version)
            print(f"{args.version}: {'OK' if ok else 'FAIL'} — {reason}")
            return 0 if ok else 1
    except RegistryError as e:
        print(f"registry: {e}", file=sys.stderr)
        return 2
    except KeyboardInterrupt:
        pass
    return 0


def _serve_loop(server, port_file=None, drain_timeout: float = 30.0) -> bool:
    """Run a bound ServingServer until a signal stops it.

    SIGTERM is the ZERO-DOWNTIME drain (docs/serving.md "Availability &
    overload"): /readyz flips 503 so the frontend re-routes, admissions
    stop, in-flight requests finish, then the process exits — the
    rolling-restart primitive. SIGINT/Ctrl-C is a plain stop. With
    ``port_file`` the bound {host, port, pid} is published atomically
    first (how ``serve frontend`` discovers an ephemeral-port replica).
    Returns True when the exit was a drain."""
    import json as _json
    import signal
    import threading

    if port_file:
        tmp = port_file + ".tmp"
        with open(tmp, "w") as f:
            _json.dump({"host": server.host, "port": server.port,
                        "pid": os.getpid()}, f)
        os.replace(tmp, port_file)
    stop = threading.Event()
    drain = threading.Event()

    def _on_term(signum, frame):
        drain.set()
        stop.set()

    def _on_int(signum, frame):
        stop.set()

    prev_term = signal.signal(signal.SIGTERM, _on_term)
    prev_int = signal.signal(signal.SIGINT, _on_int)
    server.start()
    try:
        while not stop.wait(0.2):
            pass
    except KeyboardInterrupt:
        pass
    finally:
        signal.signal(signal.SIGTERM, prev_term)
        signal.signal(signal.SIGINT, prev_int)
    if drain.is_set():
        print("SIGTERM: draining — admissions stopped, finishing "
              "in-flight requests", file=sys.stderr)
        clean = server.drain_and_close(timeout=drain_timeout)
        print(f"drain {'complete' if clean else 'TIMED OUT'}; exiting",
              file=sys.stderr)
        return True
    server.close()
    return False


def _main_serve_frontend(args) -> int:
    """``serve frontend``: bring up the replicated frontend. Spawned
    replicas are real ``serve run`` subprocesses; the frontend process
    stays jax-free. SIGTERM drains every replica (rolling, zero drops)
    before exiting; SIGINT stops immediately."""
    import signal
    import threading

    from pytorch_distributed_nn_tpu.serving.frontend import (
        Frontend,
        frontend_telemetry,
    )

    workdir = args.workdir or os.path.join(args.artifact, "frontend")
    serve_dir = args.serve_dir or os.path.join(workdir, "serve")
    telemetry = frontend_telemetry(serve_dir, extra={
        "artifact": args.artifact,
        "replicas": args.replicas if not args.attach else None,
        "attach": args.attach,
        "max_inflight": args.max_inflight,
    })
    fe = Frontend(
        workdir, telemetry=telemetry, host=args.host, port=args.port,
        timeout_s=args.timeout,
        max_inflight=(args.max_inflight if args.max_inflight > 0
                      else None),
        retries=args.retries, hedge_ms=args.hedge_ms,
        breaker_threshold=args.breaker_threshold,
        breaker_cooldown_s=args.breaker_cooldown,
        lease_s=args.lease, poll_s=args.poll,
        replica_max_queue=(args.replica_max_queue
                           if args.replica_max_queue > 0 else None),
    )
    try:
        if args.attach:
            for i, hp in enumerate(args.attach.split(",")):
                host, port = hp.rsplit(":", 1)
                fe.attach_replica(f"r{i}", host, int(port))
        else:
            for i in range(args.replicas):
                fe.spawn_replica(f"r{i}", args.artifact)
        fe.start()
        fe.wait_ready()
    except Exception as e:
        print(f"serve frontend: {e}", file=sys.stderr)
        fe.close()
        telemetry.close()
        return 1
    print(f"frontend on http://{fe.host}:{fe.port} — "
          f"{len(fe.replicas)} replica(s) ready (stream: {serve_dir})",
          file=sys.stderr)
    if args.port_file:
        import json as _json

        tmp = args.port_file + ".tmp"
        with open(tmp, "w") as f:
            _json.dump({"host": fe.host, "port": fe.port,
                        "pid": os.getpid()}, f)
        os.replace(tmp, args.port_file)
    stop = threading.Event()
    drain = threading.Event()

    def _on_term(signum, frame):
        drain.set()
        stop.set()

    def _on_int(signum, frame):
        stop.set()

    signal.signal(signal.SIGTERM, _on_term)
    signal.signal(signal.SIGINT, _on_int)
    try:
        while not stop.wait(0.2):
            pass
    except KeyboardInterrupt:
        pass
    finally:
        if drain.is_set():
            print("SIGTERM: draining replicas", file=sys.stderr)
        fe.close(stop_replicas=not args.attach, drain=drain.is_set())
        telemetry.close()
    return 0


def main_serve(argv=None) -> int:
    """Serving tier (docs/serving.md): freeze a trained checkpoint into a
    self-describing inference artifact and serve it with continuous
    batching.

    - ``export`` — newest *valid* checkpoint (CRC32-verified; torn or
      quarantined steps are never exported) → artifact dir (msgpack
      params, optional per-tensor int8, ``artifact.json`` manifest); the
      source step is registered so ``--keep-last`` GC never deletes it.
    - ``run``    — HTTP server over the padded-bucket engine (all buckets
      pre-traced at startup: steady state never recompiles); every
      request is traced (X-Request-Id + span breakdown + artifact
      version on its stream record); ``--slo`` attaches the live SLO
      engine and ``--flightrec`` the flight recorder (a burning error
      budget captures one incident bundle). With ``--registry`` the
      server follows the model registry (docs/serving.md "Deployment
      lifecycle"): ``--reload-poll`` hot-swaps on a moved ``stable``
      label and canaries a set ``canary`` label (``--canary`` policy:
      ramp, per-version percentile gate, auto-promote/auto-rollback);
      ``--admin-token`` enables ``POST /v1/admin/swap``.
    - ``bench``  — in-process open-loop load sweep: sustained req/s +
      latency percentiles with a per-span breakdown, no-retrace
      assertion, a ``serving.jsonl`` telemetry stream for
      ``obs summary`` / ``obs compare``.
    - ``smoke``  — the <10 s lint-gate scenario (tools/lint.sh).
    """
    p = argparse.ArgumentParser("pdtn-serve", description=main_serve.__doc__)
    sub = p.add_subparsers(dest="cmd", required=True)

    pe = sub.add_parser("export", help="freeze a checkpoint into an "
                                       "inference artifact")
    pe.add_argument("--train-dir", required=True)
    pe.add_argument("--out", required=True, metavar="DIR")
    pe.add_argument("--step", type=int, default=None,
                    help="checkpoint step to freeze (default: newest step "
                         "that passes integrity validation)")
    pe.add_argument("--quantize", choices=["none", "int8"], default="none",
                    help="int8: per-tensor symmetric weight quantization "
                         "with stored scales (ops/compression.py), "
                         "dequantized on load")
    pe.add_argument("--network", default=None,
                    help="model architecture (default: sniffed from the "
                         "run's telemetry manifest)")
    pe.add_argument("--num-classes", type=int, default=None)

    def _add_engine_flags(sp, artifact_required=True):
        sp.add_argument("--artifact", required=artifact_required,
                        metavar="DIR")
        sp.add_argument("--buckets", default=None, metavar="B1,B2,...",
                        help="batch-size buckets requests are padded up "
                             "to (default 1,2,4,8,16,32); all are "
                             "pre-traced at startup")
        sp.add_argument("--batch-window-ms", type=float, default=2.0,
                        help="max time the oldest queued request waits "
                             "for coalescing")
        sp.add_argument("--timeout", type=float, default=2.0,
                        help="default request deadline in seconds "
                             "(late requests are dropped, never served "
                             "stale)")
        sp.add_argument("--max-queue", type=int, default=1024,
                        help="admission-queue bound: submits past it are "
                             "SHED with 429 + Retry-After (typed "
                             "request_shed event) instead of growing the "
                             "queue until every deadline is missed; 0 = "
                             "unbounded (docs/serving.md 'Availability & "
                             "overload')")

    pr = sub.add_parser("run", help="serve an artifact over HTTP")
    _add_engine_flags(pr, artifact_required=False)
    pr.add_argument("--host", default="127.0.0.1")
    pr.add_argument("--port", type=int, default=8000)
    pr.add_argument("--registry", default=None, metavar="DIR",
                    help="model registry (cli registry, docs/serving.md "
                         "'Deployment lifecycle'): resolves --artifact "
                         "by version/label (default: the 'stable' label "
                         "when --artifact is omitted) and receives the "
                         "router's label moves on promote/rollback")
    pr.add_argument("--reload-poll", type=float, default=None,
                    metavar="SECS",
                    help="with --registry: follow its labels — a moved "
                         "'stable' label hot-swaps the serving weights "
                         "under live traffic (zero downtime, zero "
                         "retraces), a set 'canary' label starts a "
                         "canary ramp")
    pr.add_argument("--canary", default=None, metavar="SPEC",
                    help="canary policy, e.g. 'ramp=5:25:50,stage=200,"
                         "threshold=0.5,window=400,min=50,nonfinite=0' "
                         "(serving/router.py grammar). The gate combines "
                         "the obs compare --by-version percentile rows, "
                         "--slo burn over the canary's records, and the "
                         "non-finite output check; a conviction is ONE "
                         "typed rollback event and an atomic label "
                         "restore")
    pr.add_argument("--admin-token", default=None, metavar="TOKEN",
                    help="enable POST /v1/admin/swap (X-Admin-Token "
                         "header): {'artifact': DIR-or-version[, "
                         "'canary': true]} or {'rollback': true}. "
                         "Without this flag the endpoint always 403s")
    pr.add_argument("--serve-dir", default=None, metavar="DIR",
                    help="write the serving.jsonl telemetry stream here "
                         "(default: <artifact>/serve)")
    pr.add_argument("--slo", default=None, metavar="SPEC",
                    help="live SLO objectives, e.g. "
                         "'lat_p99<25ms@60s,avail>99.5%%@300s' "
                         "(observability/slo.py): burn-rate gauges in "
                         "the registry, status on GET /stats, an "
                         "slo_breach event when the budget burns")
    pr.add_argument("--flightrec", default=None, metavar="SPEC",
                    help="arm the flight recorder over the serving "
                         "stream (detect.py grammar; 'default' arms "
                         "every detector — with --slo, a burning budget "
                         "captures exactly one incident bundle under "
                         "the serve dir)")
    pr.add_argument("--port-file", default=None, metavar="FILE",
                    help="write {host, port, pid} JSON here once the "
                         "listener is bound — how the replica frontend "
                         "(serve frontend) discovers an ephemeral-port "
                         "replica it spawned")
    pr.add_argument("--faults", default=None, metavar="SPEC",
                    help="serving-side fault injection, request-count "
                         "keyed (resilience/faults.py grammar): e.g. "
                         "'slow_infer@1:0.06s:x400,conn_reset@25,"
                         "http_503@40:x3' — chaos scenarios inject "
                         "latency burns and replica misbehaviour "
                         "without bespoke engine subclasses")

    pb = sub.add_parser("bench", help="open-loop load sweep against an "
                                      "artifact (no HTTP)")
    _add_engine_flags(pb)
    pb.add_argument("--offered", default="500,1000,2000",
                    metavar="R1,R2,...",
                    help="offered request rates (req/s) to sweep")
    pb.add_argument("--duration", type=float, default=2.0,
                    help="seconds per offered rate")
    pb.add_argument("--out", default=None, metavar="DIR",
                    help="serving.jsonl stream + JSON result dir "
                         "(default: <artifact>/bench)")
    pb.add_argument("--json", action="store_true",
                    help="emit the result record as JSON on stdout")

    psm = sub.add_parser("smoke", help="~5s serving invariant gate "
                                       "(tools/lint.sh)")
    psm.add_argument("--keep", default=None, metavar="DIR",
                     help="run under this dir and keep the artifacts")

    pfe = sub.add_parser(
        "frontend",
        help="replicated frontend (docs/serving.md 'Availability & "
             "overload'): spawn N local replica servers and route over "
             "them with admission control, per-replica circuit "
             "breakers, hedged retries and zero-downtime drain — the "
             "frontend process itself never imports jax",
    )
    pfe.add_argument("--artifact", required=True, metavar="DIR")
    pfe.add_argument("--replicas", type=int, default=2,
                     help="local replica servers to spawn (own process "
                          "groups, ephemeral ports via --port-file)")
    pfe.add_argument("--attach", default=None, metavar="H:P,H:P",
                     help="attach to already-running replica servers "
                          "instead of spawning")
    pfe.add_argument("--host", default="127.0.0.1")
    pfe.add_argument("--port", type=int, default=8000)
    pfe.add_argument("--workdir", default=None, metavar="DIR",
                     help="replica workdirs + logs (default: "
                          "<artifact>/frontend)")
    pfe.add_argument("--serve-dir", default=None, metavar="DIR",
                     help="frontend serving.jsonl stream dir (default: "
                          "<workdir>/serve)")
    pfe.add_argument("--timeout", type=float, default=5.0,
                     help="default request deadline in seconds")
    pfe.add_argument("--max-inflight", type=int, default=256,
                     help="admission bound: forwards in flight past it "
                          "are shed with 429 + Retry-After; 0 = "
                          "unbounded")
    pfe.add_argument("--retries", type=int, default=2,
                     help="extra attempts (hedge included) on other "
                          "replicas per request")
    pfe.add_argument("--hedge-ms", type=float, default=None,
                     help="fixed hedge delay in ms; default: auto "
                          "(observed p95, floored at 25 ms)")
    pfe.add_argument("--breaker-threshold", type=int, default=3,
                     help="consecutive failures that open a replica's "
                          "circuit breaker")
    pfe.add_argument("--breaker-cooldown", type=float, default=2.0,
                     help="seconds an open breaker waits before its "
                          "half-open probe")
    pfe.add_argument("--lease", type=float, default=2.0,
                     help="readiness lease: a replica unreachable past "
                          "it is declared down (fleet-transport "
                          "liveness semantics)")
    pfe.add_argument("--poll", type=float, default=0.2,
                     help="readiness poll interval in seconds")
    pfe.add_argument("--replica-max-queue", type=int, default=256,
                     help="--max-queue forwarded to each spawned "
                          "replica")
    pfe.add_argument("--port-file", default=None, metavar="FILE",
                     help="write {host, port, pid} JSON here once the "
                          "pool is ready (ephemeral-port discovery, "
                          "same contract as serve run)")

    args = p.parse_args(argv)

    if args.cmd == "frontend":
        return _main_serve_frontend(args)

    # every other serve command takes the device in this process
    from pytorch_distributed_nn_tpu.utils import compile_cache

    compile_cache.configure()

    if args.cmd == "smoke":
        from pytorch_distributed_nn_tpu.serving.loadgen import smoke

        return smoke(keep_dir=args.keep)

    if args.cmd == "export":
        from pytorch_distributed_nn_tpu.serving.artifact import (
            export_artifact,
        )

        manifest = export_artifact(
            args.train_dir, args.out, step=args.step,
            quantize=args.quantize, network=args.network,
            num_classes=args.num_classes,
        )
        print(f"exported step {manifest['source']['step']} of "
              f"{args.train_dir} -> {args.out} "
              f"({manifest['quantize']}, {manifest['param_count']} params, "
              f"{manifest['bytes'] / 1e3:.1f} KB)")
        return 0

    buckets = (
        tuple(int(b) for b in args.buckets.split(",")) if args.buckets
        else None
    )
    if args.cmd == "bench":
        import json as _json

        from pytorch_distributed_nn_tpu.serving.loadgen import sweep

        out = args.out or os.path.join(args.artifact, "bench")
        rec = sweep(
            args.artifact,
            offered=tuple(float(r) for r in args.offered.split(",")),
            duration_s=args.duration, out_dir=out,
            batch_buckets=buckets,
            batch_window_s=args.batch_window_ms / 1000.0,
            timeout_s=args.timeout,
            max_queue=(args.max_queue if args.max_queue > 0 else None),
            log=lambda msg: print(msg, file=sys.stderr),
        )
        if args.json:
            print(_json.dumps(rec))
        else:
            print(f"retraces after warmup: {rec['retraces_after_warmup']} "
                  f"(stream: {rec['stream']} — inspect with "
                  "'obs summary')")
        return 0

    # run
    from pytorch_distributed_nn_tpu.observability.detect import DetectorSpec
    from pytorch_distributed_nn_tpu.observability.slo import parse_slos
    from pytorch_distributed_nn_tpu.serving.batcher import Batcher
    from pytorch_distributed_nn_tpu.serving.engine import InferenceEngine
    from pytorch_distributed_nn_tpu.serving.loadgen import serving_telemetry
    from pytorch_distributed_nn_tpu.serving.router import (
        CanaryPolicy,
        CanaryRouter,
        RegistryWatcher,
    )
    from pytorch_distributed_nn_tpu.serving.server import ServingServer

    # parse-first fail-fast (the --flightrec/--faults discipline): a typo
    # in any spec dies before the engine pays warmup
    slos = parse_slos(args.slo) if args.slo else None
    frspec = DetectorSpec.parse(args.flightrec) if args.flightrec else None
    try:
        policy = CanaryPolicy.parse(args.canary, slo=args.slo)
    except ValueError as e:
        print(f"serve run: {e}", file=sys.stderr)
        return 2
    fault_plan = None
    if args.faults:
        from pytorch_distributed_nn_tpu.resilience.faults import FaultPlan

        try:
            fault_plan = FaultPlan.parse(args.faults)
            if not fault_plan.has_serving_faults():
                raise ValueError(
                    f"--faults {args.faults!r} has no serving-side "
                    "entries (slow_infer/conn_reset/http_503) — nothing "
                    "would ever fire on the request path"
                )
        except ValueError as e:
            print(f"serve run: {e}", file=sys.stderr)
            return 2
    max_queue = args.max_queue if args.max_queue > 0 else None
    registry = None
    artifact = args.artifact
    if args.registry:
        from pytorch_distributed_nn_tpu.serving.registry import (
            Registry,
            RegistryError,
        )

        registry = Registry(args.registry)
        try:
            # --artifact may be a version id or label; omitted = the
            # stable label (publishing IS deploying)
            if artifact is None:
                artifact = registry.resolve("stable")["artifact"]
            elif not os.path.isdir(artifact):
                artifact = registry.resolve(artifact)["artifact"]
        except RegistryError as e:
            print(f"serve run: {e}", file=sys.stderr)
            return 2
    elif artifact is None:
        print("serve run: --artifact is required without --registry",
              file=sys.stderr)
        return 2
    if args.reload_poll is not None and registry is None:
        print("serve run: --reload-poll needs --registry",
              file=sys.stderr)
        return 2

    # generative artifacts (causal decoders) serve the KV-cache decode
    # path: POST /v1/generate over the per-token continuous-batching
    # scheduler (docs/serving.md "Generative serving"); hot swap rides
    # the admin endpoint (KV pages of the outgoing engine are fenced)
    from pytorch_distributed_nn_tpu.models import is_generative_model
    from pytorch_distributed_nn_tpu.serving.artifact import load_manifest

    if is_generative_model(load_manifest(artifact).get("network", "")):
        from pytorch_distributed_nn_tpu.serving.generate import (
            GenerativeEngine,
            GenerateScheduler,
        )

        if args.canary or args.reload_poll is not None:
            print("serve run: canary/label-follow is not wired for "
                  "generative artifacts yet — use /v1/admin/swap "
                  "(KV-fenced hot swap)", file=sys.stderr)
            return 2
        engine = (
            GenerativeEngine(artifact, batch_buckets=buckets)
            if buckets else GenerativeEngine(artifact)
        )
        engine.warmup()
        serve_dir = args.serve_dir or os.path.join(artifact, "serve")
        os.makedirs(serve_dir, exist_ok=True)
        telemetry = serving_telemetry(
            serve_dir, engine,
            extra={"generative": True,
                   **({"slo": args.slo} if args.slo else {})},
        )
        slo_engine = None
        if slos is not None:
            from pytorch_distributed_nn_tpu.observability.slo import (
                SLOEngine,
            )

            slo_engine = SLOEngine(slos, telemetry=telemetry)
        gen_faults = None
        if fault_plan is not None:
            from pytorch_distributed_nn_tpu.serving.faultinject import (
                ServingFaultInjector,
            )

            gen_faults = ServingFaultInjector(fault_plan,
                                              telemetry=telemetry)
            if hasattr(engine, "infer"):  # generative engines have no
                gen_faults.attach_engine(engine)  # single-pass infer
        scheduler = GenerateScheduler(
            engine, telemetry=telemetry,
            default_timeout_s=args.timeout, max_queue=max_queue,
        )
        server = ServingServer(
            engine, None, host=args.host, port=args.port,
            slo=slo_engine, admin_token=args.admin_token,
            generator=scheduler, faults=gen_faults,
        )
        print(f"serving GENERATIVE {artifact} on "
              f"http://{server.host}:{server.port} "
              f"(stream: {serve_dir})", file=sys.stderr)
        try:
            _serve_loop(server, port_file=args.port_file)
        finally:
            scheduler.close()
            if slo_engine is not None:
                slo_engine.close()
            telemetry.close()
        return 0

    engine = (
        InferenceEngine(artifact, batch_buckets=buckets)
        if buckets else InferenceEngine(artifact)
    )
    engine.warmup()
    serve_dir = args.serve_dir or os.path.join(artifact, "serve")
    os.makedirs(serve_dir, exist_ok=True)
    telemetry = serving_telemetry(
        serve_dir, engine,
        extra={"slo": args.slo} if args.slo else None,
    )
    slo_engine = recorder = None
    if slos is not None:
        from pytorch_distributed_nn_tpu.observability.slo import SLOEngine

        slo_engine = SLOEngine(slos, telemetry=telemetry)
    if frspec is not None:
        from pytorch_distributed_nn_tpu.observability.flightrec import (
            FlightRecorder,
        )

        recorder = FlightRecorder(serve_dir, telemetry, frspec)
    injector = None
    if fault_plan is not None:
        from pytorch_distributed_nn_tpu.serving.faultinject import (
            ServingFaultInjector,
        )

        injector = ServingFaultInjector(fault_plan, telemetry=telemetry)
        injector.attach_engine(engine)
    batcher = Batcher(
        engine, telemetry=telemetry,
        batch_window_s=args.batch_window_ms / 1000.0,
        default_timeout_s=args.timeout,
        max_queue=max_queue,
        # the serving twin of the trainer's per-step tick: the recorder
        # opens/closes captures at batch boundaries (request-id "steps")
        on_batch=(recorder.tick if recorder is not None else None),
    )
    router = CanaryRouter(batcher, telemetry=telemetry, registry=registry,
                          policy=policy)
    watcher = None
    if args.reload_poll is not None:
        watcher = RegistryWatcher(registry, router,
                                  poll_s=args.reload_poll)
        watcher.start()
    server = ServingServer(engine, router, host=args.host, port=args.port,
                           slo=slo_engine, router=router,
                           admin_token=args.admin_token, faults=injector)
    print(f"serving {artifact} on http://{server.host}:{server.port} "
          f"(stream: {serve_dir})", file=sys.stderr)
    if registry is not None:
        print(f"registry: {args.registry}"
              + (f" (label follow every {args.reload_poll:g}s)"
                 if watcher is not None else ""), file=sys.stderr)
    if slos is not None:
        print(f"SLOs: {args.slo} (status on GET /stats)", file=sys.stderr)
    try:
        _serve_loop(server, port_file=args.port_file)
    finally:
        if watcher is not None:
            watcher.close()
        router.close()
        batcher.close()
        if recorder is not None:
            recorder.close()
        if slo_engine is not None:
            slo_engine.close()
        telemetry.close()
    return 0


def main_chaos(argv=None) -> int:
    """Chaos suite: canned fault scenarios with CI-gateable invariants.

    Each scenario (resilience/chaos.py) trains a tiny model on CPU with
    injected faults and asserts the resilience contract — crash+resume
    bitwise equivalence, straggler K-of-N drop + renormalization, torn-
    checkpoint conviction/quarantine, NaN-update skipping, SIGTERM clean
    exit. Exits nonzero when any invariant is violated, so CI can gate
    fault handling exactly like a unit test.
    """
    p = argparse.ArgumentParser("pdtn-chaos", description=main_chaos.__doc__)
    p.add_argument("--scenario", default="smoke",
                   help="scenario name, or 'list' to enumerate "
                        "(smoke is the <30s lint-time composite)")
    p.add_argument("--workdir", default=None,
                   help="run under this directory and keep the artifacts "
                        "(default: a temp dir, removed unless --keep)")
    p.add_argument("--keep", action="store_true",
                   help="keep the default temp workdir for inspection")
    p.add_argument("--cases", default=None, metavar="C1,C2,...",
                   help="for scenarios with sub-cases (elastic_resume: "
                        "shrink,regrow,corrupt; live_reload: "
                        "swap,canary): run only these — the lint gate "
                        "runs fast single cases alone")
    args = p.parse_args(argv)

    # Chaos is a CPU tool like analyze: force the host platform and ask
    # for virtual devices BEFORE the backend initializes, so the DP
    # scenarios get a real multi-worker mesh on any machine.
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + " --xla_force_host_platform_device_count=8"
        ).strip()

    from pytorch_distributed_nn_tpu.resilience import chaos

    if args.scenario == "list":
        for name, fn in chaos.SCENARIOS.items():
            doc = (fn.__doc__ or "").strip().splitlines()
            print(f"{name}: {doc[0] if doc else ''}")
        return 0
    cases = (
        tuple(c for c in args.cases.split(",") if c) if args.cases else None
    )
    return chaos.run_scenario(args.scenario, workdir=args.workdir,
                              keep=args.keep, cases=cases)


def main(argv=None) -> int:
    logging.basicConfig(
        level=logging.INFO,
        format="%(asctime)s %(name)s %(levelname)s %(message)s",
    )
    argv = sys.argv[1:] if argv is None else argv
    if not argv or argv[0] in ("-h", "--help"):
        print("usage: python -m pytorch_distributed_nn_tpu "
              "{train|single|evaluator|serve|registry|sweep|fleet|tune|"
              "analyze|lint|chaos|obs|data|prepare-data} [flags]")
        return 0 if argv else 2
    cmd, rest = argv[0], argv[1:]
    if cmd == "obs":
        # host-side file inspection only — never pays jax/backend startup
        from pytorch_distributed_nn_tpu.observability.obs_cli import main_obs

        return main_obs(rest)
    if cmd == "registry":
        # host-side json/os only, like obs
        return main_registry(rest)
    if cmd == "data":
        # host-side numpy only, like obs
        return main_data(rest)
    if cmd == "train":
        return main_train(rest)
    if cmd == "single":
        return main_single(rest)
    if cmd == "evaluator":
        return main_evaluator(rest)
    if cmd == "serve":
        # CPU-friendly like chaos: serving works on whatever backend jax
        # exposes; no platform forcing here (a TPU host serves on TPU)
        return main_serve(rest)
    if cmd == "sweep":
        # orchestrator-side: spawns trial subprocesses, reads streams —
        # the PARENT never initializes an accelerator backend
        return main_sweep(rest)
    if cmd == "fleet":
        # fleet orchestrator/agent: jax-free host-side process — trials
        # import jax in their own subprocesses on their own hosts
        return main_fleet(rest)
    if cmd == "tune":
        return main_tune(rest)
    if cmd == "analyze":
        return main_analyze(rest)
    if cmd == "lint":
        # stdlib-ast source lint: jax-free by contract (PL020 guards the
        # other jax-free surfaces; this one guards itself via --selftest)
        return main_lint(rest)
    if cmd == "chaos":
        return main_chaos(rest)
    if cmd == "prepare-data":
        return main_prepare_data(rest)
    print(f"unknown command {cmd!r}; expected "
          "train|single|evaluator|serve|registry|sweep|fleet|tune|analyze|"
          "lint|chaos|obs|data|prepare-data")
    return 2


if __name__ == "__main__":
    sys.exit(main())

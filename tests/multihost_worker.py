"""Worker script for the 2-process multi-host smoke test (not a test module).

Run by tests/test_multihost.py in two subprocesses against a local
coordinator — the CPU-backend stand-in for a 2-host TPU pod slice. Each
process owns 2 virtual CPU devices; the Trainer sees a 4-device global
mesh. Verifies, from inside a REAL multi-process jax.distributed runtime:

- mode "dp" (default): process-0-only checkpoint writes (the reference's
  NFS race — every worker race-writing model_step_<N>, reference
  src/distributed_worker.py:304-307 — provably fixed rather than
  inherited); resume with the broadcast handshake (training/trainer.py):
  process 0 reads, both processes agree on start_step and state.
- mode "spmd": BertTiny with tensor_parallel=4 — the model axis spans
  both processes, so each process's `save_sharded` writes shards the
  other process does not hold; resume restores per-process shards and
  must be BIT-EXACT against the state that wrote the checkpoint (the pod
  checkpoint scenario end-to-end; round-4 verdict item 8).
- mode "warm": vocabulary-curriculum warm start inside the multi-process
  runtime — run 1 trains vocab=32 (process 0 writes the FILE
  checkpoint), run 2 builds the vocab=64 model with --warm-start and
  both processes materialize the merged params via
  make_array_from_callback; the copied embedding overlap is verified
  against the source checkpoint on every process.
- mode "warm_spmd": same curriculum, but run 2 is GSPMD with
  tensor_parallel=4 spanning both processes — the target params are
  non-addressable, so the trainer must process_allgather them before the
  host-side merge and re-shard the result per old.sharding; the overlap
  is verified shard-by-shard via each shard's global index.

Prints "WORKER_OK <pid> start_step=<n> ckpts=<names>" on success.
"""

import faulthandler
import os
import signal
import sys

# kill -USR1 <pid> dumps all thread stacks to stderr — the only way to
# localize a cross-process collective deadlock in this harness
faulthandler.register(signal.SIGUSR1)


def main() -> int:
    import logging

    logging.basicConfig(level=logging.INFO)  # the test dumps these on failure
    pid = int(sys.argv[1])
    nprocs = int(sys.argv[2])
    port = sys.argv[3]
    train_dir = sys.argv[4]
    mode = sys.argv[5] if len(sys.argv) > 5 else "dp"

    # The parent test harness exports an 8-device flag, so REPLACE any
    # inherited count — each of the 2 processes must own exactly 2
    # virtual devices.
    os.environ["JAX_PLATFORMS"] = "cpu"
    flags = [
        f for f in os.environ.get("XLA_FLAGS", "").split()
        if "xla_force_host_platform_device_count" not in f
    ]
    flags.append("--xla_force_host_platform_device_count=2")
    os.environ["XLA_FLAGS"] = " ".join(flags)

    import jax

    jax.distributed.initialize(
        coordinator_address=f"localhost:{port}",
        num_processes=nprocs,
        process_id=pid,
    )
    assert jax.process_index() == pid
    assert jax.device_count() == 2 * nprocs

    import numpy as np

    from pytorch_distributed_nn_tpu.training.trainer import (
        TrainConfig,
        Trainer,
    )

    def cfg(**kw):
        if mode in ("warm", "warm_spmd"):
            base = dict(
                network="BertTiny", dataset="MLMSynth", batch_size=8,
                test_batch_size=8, optimizer="adam", lr=1e-3,
                seq_len=32, vocab_size=32, eval_batches=2,
                num_workers=4, max_steps=2, eval_freq=2,
                train_dir=train_dir, log_every=100,
            )
        elif mode == "spmd":
            # tp spans BOTH processes (model axis = all 4 devices), so
            # each process's save_sharded writes shards the other does
            # not hold — the pod checkpoint scenario.
            base = dict(
                network="BertTiny", dataset="MLMSynth", batch_size=8,
                test_batch_size=8, optimizer="adam", lr=1e-3,
                seq_len=32, vocab_size=64, eval_batches=2,
                num_workers=1, tensor_parallel=4,
                max_steps=4, eval_freq=2, train_dir=train_dir,
                log_every=100,
            )
        else:
            base = dict(
                network="LeNet", dataset="MNIST", batch_size=16,
                test_batch_size=16, max_steps=4, eval_freq=2,
                synthetic_size=64, train_dir=train_dir, log_every=100,
            )
        base.update(kw)
        return TrainConfig(**base)

    def local_shards(state):
        """This process's addressable shard data, in deterministic order."""
        return [
            np.asarray(s.data)
            for leaf in jax.tree.leaves(state)
            if isinstance(leaf, jax.Array)
            for s in leaf.addressable_shards
        ]

    if mode in ("warm", "warm_spmd"):
        from jax.experimental import multihost_utils

        from pytorch_distributed_nn_tpu.training import checkpoint as ckpt

        t1 = Trainer(cfg())
        try:
            t1.train()
        finally:
            t1.close()
        # process 0 writes the checkpoint host-side AFTER the final
        # step's collectives complete, so process 1 can reach load_raw
        # first — barrier before any process reads the file (the
        # FileNotFoundError race this harness originally hit; a real
        # curriculum launch reads a checkpoint from a FINISHED job, so
        # the trainer itself needs no such barrier)
        multihost_utils.sync_global_devices("warm_ckpt_written")
        src = ckpt.load_raw(os.path.join(train_dir, "model_step_2"))
        src_emb = np.asarray(src["params"]["encoder"]["token_embed"]["embedding"])

        spmd_kw = (
            dict(num_workers=1, tensor_parallel=4)
            if mode == "warm_spmd" else {}
        )
        t2 = Trainer(cfg(
            vocab_size=64, train_dir=train_dir + "_v64",
            warm_start=os.path.join(train_dir, "model_step_2"),
            eval_freq=0, **spmd_kw,
        ))
        try:
            emb = t2.state.params["encoder"]["token_embed"]["embedding"]
            assert emb.shape[0] == 64
            # the merged embedding's overlap (rows 0..31) must equal the
            # source checkpoint on every process. Under warm_spmd the
            # leaf is sharded across processes, so verify shard-by-shard
            # via each shard's global index; NaN marks the fresh rows
            # (random init, not comparable).
            overlap = np.full(emb.shape, np.nan, np.float64)
            overlap[:32, :] = src_emb
            for s in emb.addressable_shards:
                got = np.asarray(s.data, np.float64)
                assert np.isfinite(got).all()
                exp = overlap[s.index]
                m = ~np.isnan(exp)
                np.testing.assert_array_equal(got[m], exp[m])
            hist = t2.train()
            assert len(hist) == 2
        finally:
            t2.close()
        start = 0
    else:
        # run 1: fresh training, checkpoints at steps 2 and 4
        t1 = Trainer(cfg())
        try:
            t1.train()
            final_shards = local_shards(t1.state)
        finally:
            t1.close()

        # run 2: resume — both processes must agree on start_step via the
        # process-0-read + broadcast handshake (replicated path) / the
        # latest-step broadcast + per-process sharded restore (GSPMD path)
        t2 = Trainer(cfg(max_steps=6, resume=True, eval_freq=0))
        try:
            start = t2.start_step
            if mode == "spmd":
                # restore re-shards BIT-EXACTLY: every addressable shard
                # of the restored state equals the state that wrote step 4
                restored = local_shards(t2.state)
                assert len(restored) == len(final_shards)
                for a, b in zip(final_shards, restored):
                    np.testing.assert_array_equal(a, b)
            hist = t2.train()
            assert start == 4, f"proc {pid}: start_step {start} != 4"
            assert len(hist) == 2
        finally:
            t2.close()

    ckpts = sorted(
        f for f in os.listdir(train_dir) if f.startswith("model_step_")
    )
    print(f"WORKER_OK {pid} start_step={start} ckpts={','.join(ckpts)}",
          flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())

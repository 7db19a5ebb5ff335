"""Multi-process (simulated multi-host) smoke test.

The reference's multi-node story was mpirun + per-rank branch; here a
2-process jax.distributed runtime (local coordinator, CPU backend, 2
virtual devices per process = one 4-device global mesh) runs the REAL
trainer end-to-end twice (fresh + resume), asserting the multi-host
contracts from inside an actual multi-process runtime:

- exactly one writer: process 0 owns every checkpoint (no NFS-style race,
  reference src/distributed_worker.py:304-307);
- both processes resume from the same step via the broadcast handshake.

Runs the workers as subprocesses because a jax.distributed client is
process-global (can't host two in one pytest process).
"""

import os
import signal
import socket
import subprocess
import sys
import time

from pytorch_distributed_nn_tpu.observability import core as obs_core
from pytorch_distributed_nn_tpu.observability import reader
from pytorch_distributed_nn_tpu.training import checkpoint as ckpt


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("localhost", 0))
        return s.getsockname()[1]


def _run_workers(train_dir: str, mode: str, expect_start: int = 4,
                 timeout: int = 570):
    port = _free_port()
    worker = os.path.join(os.path.dirname(__file__), "multihost_worker.py")
    env = dict(
        os.environ,
        PYTHONPATH=os.path.dirname(os.path.dirname(worker)),
        JAX_PLATFORMS="",  # let the worker's jax.config force cpu
    )
    procs = [
        subprocess.Popen(
            [sys.executable, worker, str(pid), "2", str(port), train_dir,
             mode],
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
            env=env, cwd=os.path.dirname(os.path.dirname(worker)),
        )
        for pid in range(2)
    ]
    outs = []
    try:
        for p in procs:
            out, _ = p.communicate(timeout=timeout)
            outs.append(out)
    except subprocess.TimeoutExpired:
        # a hang here is almost always a cross-process collective
        # deadlock — harvest evidence before killing: the workers
        # register a SIGUSR1 faulthandler, so ask each survivor for its
        # thread stacks, then kill and collect whatever was written
        for p in procs:
            if p.poll() is None:
                p.send_signal(signal.SIGUSR1)
        time.sleep(5)
        dumps = []
        for pid, p in enumerate(procs):
            if pid < len(outs):
                # this worker finished before the timeout — its output is
                # already drained (a second communicate() would raise)
                dumps.append(f"--- proc {pid} (rc={p.returncode}, "
                             f"finished) ---\n{outs[pid][-3000:]}")
                continue
            if p.poll() is None:
                p.kill()
            try:
                out, _ = p.communicate(timeout=30)
            except Exception:
                out = "<no output>"
            dumps.append(f"--- proc {pid} (rc={p.returncode}) ---\n"
                         f"{out[-3000:]}")
        raise AssertionError(
            f"multihost workers timed out after {timeout}s; "
            "worker tails + SIGUSR1 stack dumps:\n" + "\n".join(dumps)
        )
    finally:
        # never leak workers: one dead process leaves its peer blocked
        # in a collective forever (and contending for the 1-vCPU host)
        for p in procs:
            if p.poll() is None:
                p.kill()
        for p in procs:
            if p.returncode is None:
                p.wait(timeout=30)
    for pid, (p, out) in enumerate(zip(procs, outs)):
        assert p.returncode == 0, f"proc {pid} failed:\n{out[-4000:]}"
        assert f"WORKER_OK {pid} start_step={expect_start}" in out, (
            out[-2000:]
        )
    return outs


def _checkpoint_writes(train_dir: str, rank: int):
    """Steps of the ``checkpoint_write`` events in one process's stream."""
    rs = reader.read_stream(
        os.path.join(train_dir, obs_core.stream_basename(rank)))
    return [e["step"] for e in rs.events
            if e.get("type") == "checkpoint_write"]


def test_two_process_train_checkpoint_resume(tmp_path):
    train_dir = str(tmp_path / "train")
    os.makedirs(train_dir)
    _run_workers(train_dir, "dp")

    # run-1 wrote steps 2 and 4; no duplicate/torn files from a second
    # writer: every write is an event in the stream of the process that
    # made it, and process 1's stream holds none. all_steps matches
    # checkpoint entries only, never their .meta.json CRC manifests.
    assert ckpt.all_steps(train_dir) == [2, 4]
    assert _checkpoint_writes(train_dir, 0) == [2, 4]
    assert _checkpoint_writes(train_dir, 1) == []


def test_two_process_gspmd_sharded_checkpoint_resume(tmp_path):
    """The pod checkpoint scenario end-to-end: 2 jax.distributed processes
    with tensor_parallel=4 (model axis across processes). Each process
    writes ONLY its own shards; restore re-shards; resume is bit-exact
    (asserted inside the workers). Here: both per-process shard files
    exist and both carry real parameter shards — neither process gathered
    the other's state."""
    import numpy as np

    train_dir = str(tmp_path / "train")
    os.makedirs(train_dir)
    _run_workers(train_dir, "spmd")

    assert ckpt.all_steps(train_dir) == [2, 4]
    ckpts = [f"model_step_{s}" for s in ckpt.all_steps(train_dir)]
    for step_dir in ckpts:
        files = sorted(os.listdir(os.path.join(train_dir, step_dir)))
        assert "shards_p00000.npz" in files and "shards_p00001.npz" in files
        for shard_file in ("shards_p00000.npz", "shards_p00001.npz"):
            with np.load(
                os.path.join(train_dir, step_dir, shard_file)
            ) as z:
                param_keys = [k for k in z.files if "params" in k]
                assert param_keys, (
                    f"{step_dir}/{shard_file} holds no parameter shards — "
                    "one process is not writing its share"
                )


def test_two_process_warm_start(tmp_path):
    """Vocabulary-curriculum warm start inside a REAL 2-process runtime:
    both processes read the same source FILE checkpoint and materialize
    the merged (resized) params via make_array_from_callback; the copied
    embedding overlap is verified against the checkpoint on each process
    (asserted inside the workers)."""
    train_dir = str(tmp_path / "train")
    os.makedirs(train_dir)
    # two model geometries compile back-to-back in each process — the
    # slowest multihost case on a contended 1-vCPU host
    _run_workers(train_dir, "warm", expect_start=0, timeout=1500)


def test_two_process_warm_start_gspmd(tmp_path):
    """Curriculum warm start INTO a GSPMD run: the vocab=32 source trains
    dp (full-file checkpoint, the realistic curriculum source), then the
    vocab=64 target is tensor_parallel=4 spanning both processes — its
    params are non-addressable, so the trainer must process_allgather
    the target template before the host-side merge and re-shard per leaf
    sharding; overlap checked shard-by-shard (asserted in the workers)."""
    train_dir = str(tmp_path / "train")
    os.makedirs(train_dir)
    _run_workers(train_dir, "warm_spmd", expect_start=0, timeout=1500)

"""resilience/: fault injection, preemption-safe training, stragglers.

The reference was only ever fault-"tested" by real cluster failures
(SURVEY.md §4); here every failure mode is a deterministic, seeded test on
the 8-device virtual mesh: crash/resume bitwise equivalence, deadline
straggler drops with renormalization, torn-checkpoint conviction +
quarantine, the NaN-update guard, retry backoff, and the supervisor's
heartbeat/watchdog. The full CLI chaos scenarios are @slow; the invariants
themselves are covered fast here.
"""

import json
import os
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from jax import shard_map
from pytorch_distributed_nn_tpu.parallel import make_grad_sync, make_mesh
from pytorch_distributed_nn_tpu.resilience import (
    FaultPlan,
    InjectedCrash,
    StragglerSim,
    Watchdog,
    backoff_delays,
    dropped_ranks,
    resume_latest_valid,
    retry_call,
    write_heartbeat,
)
from pytorch_distributed_nn_tpu.training import checkpoint as ckpt
from pytorch_distributed_nn_tpu.training.trainer import TrainConfig, Trainer


class TestFaultPlan:
    def test_parse_full_grammar_roundtrip(self):
        spec = "delay@120:p3:2.5s,crash@200,nan_grad@150,torn_ckpt@100"
        plan = FaultPlan.parse(spec, seed=7)
        assert plan.describe() == spec
        assert plan.delay_table() == ((120, 3, 2.5),)
        assert plan.max_rank_referenced() == 3
        assert plan.should_tear(100) and not plan.should_tear(99)
        assert plan.poison_step(150) and not plan.poison_step(151)

    def test_delay_defaults(self):
        plan = FaultPlan.parse("delay@5")
        assert plan.delay_table() == ((5, None, 1.0),)
        assert plan.max_rank_referenced() == -1

    @pytest.mark.parametrize("bad", [
        "boom@3",            # unknown kind
        "crash@0",           # steps are 1-indexed
        "crash@3:p1",        # rank arg on a non-delay fault
        "delay@3:q7",        # malformed arg
        "delay",             # no step
    ])
    def test_parse_rejects(self, bad):
        with pytest.raises(ValueError):
            FaultPlan.parse(bad)

    def test_pre_step_crash_and_noop(self):
        plan = FaultPlan.parse("crash@4")
        plan.pre_step(3)  # no fault -> no effect
        with pytest.raises(InjectedCrash):
            plan.pre_step(4)

    def test_poison_batch(self):
        plan = FaultPlan.parse("nan_grad@2")
        imgs = np.ones((4, 2, 2, 1), np.float32)
        labels = np.zeros((4,), np.int32)
        out = plan.poison_batch(1, (imgs, labels))
        assert out[0] is imgs  # untouched off the fault step
        pi, pl = plan.poison_batch(2, (imgs, labels))
        assert np.all(np.isnan(pi))
        assert np.array_equal(pl, labels)  # int leaves untouched
        with pytest.raises(ValueError, match="no float leaves"):
            plan.poison_batch(2, (labels,))


class TestRetry:
    def test_schedule_is_seeded_and_capped(self):
        a = backoff_delays(5, base_delay=0.1, max_delay=0.3, jitter=0.5, seed=3)
        b = backoff_delays(5, base_delay=0.1, max_delay=0.3, jitter=0.5, seed=3)
        assert a == b and len(a) == 4
        assert all(d <= 0.3 * 1.5 for d in a)
        assert a[0] >= 0.1  # jitter only ever lengthens

    def test_retries_then_succeeds(self):
        calls, slept = [], []

        def flaky():
            calls.append(1)
            if len(calls) < 3:
                raise OSError("transient")
            return "ok"

        assert retry_call(flaky, attempts=4, sleep=slept.append,
                          seed=0) == "ok"
        assert len(calls) == 3 and len(slept) == 2

    def test_exhausted_raises_and_unlisted_propagates(self):
        def boom():
            raise OSError("always")

        with pytest.raises(OSError):
            retry_call(boom, attempts=2, sleep=lambda d: None)

        def typeerr():
            raise TypeError("not retried")

        seen = []
        with pytest.raises(TypeError):
            retry_call(typeerr, attempts=3, sleep=seen.append)
        assert seen == []  # never backed off on a non-retryable error


class TestSupervisorWatchdog:
    def test_heartbeat_roundtrip(self, tmp_path):
        from pytorch_distributed_nn_tpu.resilience import read_heartbeat

        d = str(tmp_path)
        assert read_heartbeat(d) is None
        write_heartbeat(d, 17)
        beat = read_heartbeat(d)
        assert beat["step"] == 17 and beat["pid"] == os.getpid()

    def test_watchdog_flags_stall_and_recovery(self, tmp_path):
        d = str(tmp_path)
        write_heartbeat(d, 1)
        hb = os.path.join(d, "heartbeat.json")
        stalls = []
        dog = Watchdog(hb, grace=0.2, on_stall=stalls.append)
        assert dog.check_once() is None  # fresh beat: healthy
        # age the beat beyond the grace period
        with open(hb, "w") as f:
            json.dump({"step": 1, "time": time.time() - 10.0}, f)
        age = dog.check_once()
        assert age is not None and age > 0.2
        assert stalls and dog.stalled.is_set()
        marker = os.path.join(d, "STALLED")
        assert os.path.exists(marker)
        # a fresh beat clears the episode
        write_heartbeat(d, 2)
        assert dog.check_once() is None
        assert not dog.stalled.is_set()
        # only one callback per episode
        assert len(stalls) == 1

    def test_supervisor_request_stop(self, tmp_path):
        from pytorch_distributed_nn_tpu.resilience import RunSupervisor

        with RunSupervisor(str(tmp_path)) as sup:
            assert not sup.should_stop
            sup.request_stop()
            assert sup.should_stop
            sup.beat(3)
            assert os.path.exists(os.path.join(str(tmp_path),
                                               "heartbeat.json"))


def _cfg(tmp_path, **kw):
    base = dict(
        network="LeNet", dataset="MNIST", batch_size=32, test_batch_size=32,
        lr=0.01, momentum=0.9, max_steps=4, num_workers=4,
        synthetic_size=64, train_dir=str(tmp_path), log_every=100,
    )
    base.update(kw)
    return TrainConfig(**base)


def _text_cfg(tmp_path, **kw):
    # smallest geometry that still exercises the counter-based MLM
    # stream + adam moments (the bitwise-resume preconditions); kept
    # tiny so the crash/resume determinism test stays tier-1-cheap
    base = dict(
        network="BertTiny", dataset="MLMSynth", batch_size=4,
        test_batch_size=4, optimizer="adam", lr=1e-3, max_steps=4,
        num_workers=2, seq_len=16, vocab_size=32, train_dir=str(tmp_path),
        log_every=100, eval_batches=1,
    )
    base.update(kw)
    return TrainConfig(**base)


class TestCheckpointIntegrity:
    def _one_checkpoint(self, tmp_path, **kw):
        t = Trainer(_cfg(tmp_path, max_steps=2, eval_freq=2, **kw))
        try:
            t.train()
        finally:
            t.close()
        return t, ckpt.checkpoint_path(str(tmp_path), 2)

    def test_manifest_written_and_verifies(self, tmp_path):
        _, path = self._one_checkpoint(tmp_path)
        assert os.path.exists(ckpt.meta_path(path))
        ok, reason = ckpt.verify_checkpoint(path)
        assert ok, reason
        with open(ckpt.meta_path(path)) as f:
            meta = json.load(f)
        assert meta["bytes"] == os.path.getsize(path)

    def test_truncation_detected_and_quarantined(self, tmp_path):
        _, path = self._one_checkpoint(tmp_path)
        with open(path, "r+b") as f:
            f.truncate(os.path.getsize(path) // 2)
        ok, reason = ckpt.verify_checkpoint(path)
        assert not ok and "mismatch" in reason
        qpath = ckpt.quarantine_checkpoint(path)
        assert not os.path.exists(path)
        assert not os.path.exists(ckpt.meta_path(path))
        assert os.path.exists(qpath) and os.path.exists(ckpt.meta_path(qpath))
        assert ckpt.latest_step(str(tmp_path)) is None

    def test_bitflip_detected_by_crc(self, tmp_path):
        """Same size, flipped payload byte: only the CRC can convict."""
        _, path = self._one_checkpoint(tmp_path)
        with open(path, "r+b") as f:
            f.seek(os.path.getsize(path) // 2)
            byte = f.read(1)
            f.seek(-1, os.SEEK_CUR)
            f.write(bytes([byte[0] ^ 0xFF]))
        ok, reason = ckpt.verify_checkpoint(path)
        assert not ok and "CRC32" in reason

    def test_legacy_checkpoint_without_manifest_still_loads(self, tmp_path):
        t, path = self._one_checkpoint(tmp_path)
        os.remove(ckpt.meta_path(path))
        ok, reason = ckpt.verify_checkpoint(path)
        assert ok and "legacy" in reason
        restored = resume_latest_valid(str(tmp_path), t._host_state())
        assert restored is not None and int(restored.step) == 2

    def test_resume_latest_valid_falls_back(self, tmp_path):
        t = Trainer(_cfg(tmp_path, max_steps=4, eval_freq=2))
        try:
            t.train()
        finally:
            t.close()
        path4 = ckpt.checkpoint_path(str(tmp_path), 4)
        with open(path4, "r+b") as f:
            f.truncate(10)
        restored = resume_latest_valid(str(tmp_path), t._host_state())
        assert int(restored.step) == 2
        qdir = os.path.join(str(tmp_path), ckpt.QUARANTINE_DIR)
        assert "model_step_4" in os.listdir(qdir)
        # nothing valid at all -> None
        path2 = ckpt.checkpoint_path(str(tmp_path), 2)
        with open(path2, "r+b") as f:
            f.truncate(10)
        assert resume_latest_valid(str(tmp_path), t._host_state()) is None


class TestStragglerAggregation:
    """Deterministic K-of-N drop semantics at the grad-sync level:
    sigma=0 makes every simulated arrival time exactly `mean`, so the
    only variation is the injected fault delay — fully predictable."""

    def _run_sync(self, sim, grads_stacked, step):
        mesh = make_mesh(8, 1)
        sync = make_grad_sync("allreduce", straggler=sim)

        @jax.jit
        @shard_map(mesh=mesh, in_specs=(P("data"), P()), out_specs=P("data"))
        def run(g_block, key):
            g = g_block[0]
            out, _ = sync(g, None, key, step=step)
            return out[None]

        out = run(jnp.asarray(grads_stacked), jax.random.PRNGKey(0))
        return np.asarray(out)

    def test_delayed_rank_dropped_and_renormalized(self):
        sim = StragglerSim(deadline=1.0, mean=0.01, sigma=0.0,
                           delays=((3, 2, 50.0),))
        g = np.random.RandomState(0).randn(8, 4, 3).astype(np.float32)
        # off the fault step: everyone contributes -> plain mean
        out = self._run_sync(sim, g, step=2)
        np.testing.assert_allclose(out[0], g.mean(0), rtol=1e-5)
        # at the fault step: rank 2 is dropped, mean over the other 7
        out = self._run_sync(sim, g, step=3)
        live = np.delete(g, 2, axis=0).mean(0)
        np.testing.assert_allclose(out[0], live, rtol=1e-5)

    def test_drop_is_value_independent(self):
        """Perturbing the DROPPED rank's gradient must not change the
        update (the unbiasedness precondition: masking depends only on
        (key, step, rank), never on gradient values)."""
        sim = StragglerSim(deadline=1.0, mean=0.01, sigma=0.0,
                           delays=((1, 5, 99.0),))
        g = np.random.RandomState(1).randn(8, 6).astype(np.float32)
        base = self._run_sync(sim, g, step=1)
        g2 = g.copy()
        g2[5] = 1e6
        np.testing.assert_array_equal(base, self._run_sync(sim, g2, step=1))

    def test_min_keep_floor(self):
        """All ranks past the deadline -> the fastest min_keep still
        aggregate; the update never goes empty (0/0)."""
        sim = StragglerSim(deadline=1e-6, mean=0.5, sigma=0.0, min_keep=2)
        g = np.random.RandomState(2).randn(8, 5).astype(np.float32)
        out = self._run_sync(sim, g, step=1)
        # sigma=0 ties everywhere -> index tie-break keeps ranks 0 and 1
        np.testing.assert_allclose(out[0], g[:2].mean(0), rtol=1e-5)
        assert np.all(np.isfinite(out))

    def test_report_metrics_flow_to_history(self, tmp_path):
        t = Trainer(_cfg(tmp_path, straggler_deadline=1.0,
                         faults="delay@2:p1:9s", max_steps=3))
        try:
            hist = t.train()
        finally:
            t.close()
        by_step = {r["step"]: r for r in hist}
        assert by_step[2]["straggler_dropped"] == 1.0
        assert dropped_ranks(by_step[2]["straggler_dropped_mask"]) == [1]
        assert by_step[1]["straggler_dropped"] == 0.0
        assert by_step[3]["straggler_dropped"] == 0.0
        assert by_step[2]["straggler_skew"] > 5.0

    def test_validation(self, tmp_path):
        with pytest.raises(ValueError, match="topk"):
            make_grad_sync("allreduce", compression="topk",
                           straggler=StragglerSim(deadline=1.0))
        with pytest.raises(ValueError, match="distributed"):
            make_grad_sync("local", straggler=StragglerSim(deadline=1.0))
        with pytest.raises(ValueError, match="rank p9"):
            Trainer(_cfg(tmp_path, faults="delay@1:p9:1s",
                         straggler_deadline=1.0))


class TestNonfiniteGuard:
    def test_poisoned_update_skipped(self, tmp_path):
        t = Trainer(_cfg(tmp_path, num_workers=2, batch_size=16,
                         max_steps=3, faults="nan_grad@2",
                         skip_nonfinite=True, data_layout="host"))
        try:
            hist = t.train()
        finally:
            t.close()
        flags = {r["step"]: r["skipped_nonfinite"] for r in hist}
        assert flags == {1: 0.0, 2: 1.0, 3: 0.0}
        for leaf in jax.tree.leaves(t.state.params):
            assert np.all(np.isfinite(np.asarray(leaf)))
        assert int(t.state.step) == 3  # the step counter still advanced

    def test_nan_grad_rejected_on_device_layout_and_text(self, tmp_path):
        with pytest.raises(ValueError, match="data_layout"):
            Trainer(_cfg(tmp_path, faults="nan_grad@1",
                         data_layout="device"))
        with pytest.raises(ValueError, match="token ids"):
            Trainer(_text_cfg(tmp_path, faults="nan_grad@1"))


class TestCrashResume:
    def test_checkpoint_roundtrip_step_bitwise(self, tmp_path):
        """The kernel of crash/resume determinism, one compile: stepping
        through a checkpoint save/restore round trip is bitwise identical
        to stepping straight through — params AND optimizer (momentum)
        state. The full-stack version (emergency checkpoint, Trainer
        resume, data-stream skip) is the @slow test below plus the
        CI-gated `cli chaos --scenario crash_resume`."""
        t = Trainer(_cfg(tmp_path, max_steps=1))
        rt_dir = str(tmp_path / "rt")
        try:
            rng = jax.random.PRNGKey(42)
            rs = np.random.RandomState(0)
            batches = [
                (jnp.asarray(rs.rand(32, 28, 28, 1), jnp.float32),
                 jnp.asarray(rs.randint(0, 10, 32), jnp.int32))
                for _ in range(4)
            ]
            # device data layout -> t.train_step is the non-donating
            # inner step, safe to drive with explicit batches
            state = t.state
            for i, b in enumerate(batches):
                if i == 2:
                    ckpt.save_checkpoint(rt_dir, state)
                state, _ = t.train_step(state, b, rng)
            ref = jax.device_get({"p": state.params, "o": state.opt_state})

            restored = ckpt.restore_latest(rt_dir, state)
            assert int(restored.step) == 2
            s2 = restored
            for b in batches[2:]:
                s2, _ = t.train_step(s2, b, rng)
            got = jax.device_get({"p": s2.params, "o": s2.opt_state})
        finally:
            t.close()
        for a, b in zip(jax.tree.leaves(ref), jax.tree.leaves(got)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    @pytest.mark.slow
    def test_crash_resume_bitwise_equivalence(self, tmp_path):
        """The satellite invariant full-stack: train 2N uninterrupted vs
        train N, crash, resume from the EMERGENCY checkpoint —
        bitwise-identical params AND optimizer state (adam moments
        included). @slow: three separate BertTiny step compiles (~50s on
        CPU); the same invariant is CI-gated by `cli chaos --scenario
        crash_resume` and its kernel is tier-1-covered by
        test_checkpoint_roundtrip_step_bitwise above."""
        total, crash_at = 4, 3
        dir_a, dir_b = tmp_path / "a", tmp_path / "b"

        t = Trainer(_text_cfg(dir_a, max_steps=total))
        try:
            t.train()
            ref = jax.device_get(
                {"p": t.state.params, "o": t.state.opt_state}
            )
        finally:
            t.close()

        t = Trainer(_text_cfg(dir_b, max_steps=total,
                              faults=f"crash@{crash_at}"))
        with pytest.raises(InjectedCrash):
            try:
                t.train()
            finally:
                t.close()
        assert ckpt.latest_step(str(dir_b)) == crash_at - 1
        ok, reason = ckpt.verify_checkpoint(
            ckpt.checkpoint_path(str(dir_b), crash_at - 1)
        )
        assert ok, reason

        t = Trainer(_text_cfg(dir_b, max_steps=total, resume=True))
        try:
            assert t.start_step == crash_at - 1
            t.train()
            got = jax.device_get(
                {"p": t.state.params, "o": t.state.opt_state}
            )
        finally:
            t.close()
        ref_l, got_l = jax.tree.leaves(ref), jax.tree.leaves(got)
        assert len(ref_l) == len(got_l)
        for a, b in zip(ref_l, got_l):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    def test_torn_checkpoint_quarantined_on_resume(self, tmp_path):
        """Satellite: a torn checkpoint is quarantined and resume picks
        the previous valid step — through the Trainer's own resume path."""
        t = Trainer(_cfg(tmp_path, max_steps=4, eval_freq=2,
                         faults="torn_ckpt@4"))
        try:
            t.train()
        finally:
            t.close()
        ok, _ = ckpt.verify_checkpoint(
            ckpt.checkpoint_path(str(tmp_path), 4)
        )
        assert not ok

        t2 = Trainer(_cfg(tmp_path, max_steps=4, resume=True))
        try:
            assert t2.start_step == 2
        finally:
            t2.close()
        qdir = os.path.join(str(tmp_path), ckpt.QUARANTINE_DIR)
        assert "model_step_4" in os.listdir(qdir)

    def test_preempt_request_checkpoints_and_exits_cleanly(
        self, tmp_path, monkeypatch
    ):
        """request_stop (exactly what the SIGTERM handler sets) ends the
        run right after the in-flight step, with an emergency checkpoint
        and a clean (non-raising) return — the preemption contract."""
        from pytorch_distributed_nn_tpu.resilience import supervisor as sv

        orig_beat = sv.RunSupervisor.beat

        def beat_then_stop(self, step):
            orig_beat(self, step)
            if step >= 2:  # the signal "lands" during step 2
                self.request_stop()

        monkeypatch.setattr(sv.RunSupervisor, "beat", beat_then_stop)
        t = Trainer(_cfg(tmp_path, max_steps=50, supervise=True))
        try:
            hist = t.train()
        finally:
            t.close()
        assert len(hist) == 2  # stopped long before max_steps=50
        assert ckpt.latest_step(str(tmp_path)) == 2
        with open(os.path.join(str(tmp_path), "heartbeat.json")) as f:
            assert json.load(f)["step"] == 2


class TestEvaluatorSurvivesCorruption:
    def test_corrupt_checkpoint_skipped_not_fatal(self, tmp_path):
        from pytorch_distributed_nn_tpu.data import DataLoader, load_dataset
        from pytorch_distributed_nn_tpu.parallel import batch_sharding
        from pytorch_distributed_nn_tpu.training.evaluator import Evaluator

        t = Trainer(_cfg(tmp_path, max_steps=4, eval_freq=2))
        try:
            t.train()
        finally:
            t.close()
        # tear the FIRST checkpoint; the second stays valid
        with open(ckpt.checkpoint_path(str(tmp_path), 2), "r+b") as f:
            f.truncate(100)

        test_ds = load_dataset("MNIST", train=False, synthetic_size=64)
        loader = DataLoader(test_ds, 32, shuffle=False, prefetch=0,
                            sharding=batch_sharding(t.mesh))
        ev = Evaluator(t.model, t.state, t.mesh, loader, str(tmp_path),
                       eval_freq=2, eval_interval=0.01)
        assert ev.evaluate_checkpoint(2) is Evaluator.CORRUPT
        seen = []
        ev.run(max_evals=1, timeout=30,
               on_metrics=lambda s, m: seen.append(s))
        # the poll loop skipped the torn step 2 and scored step 4
        assert seen == [4]


class TestChaosCLI:
    def test_scenario_list(self, capsys):
        from pytorch_distributed_nn_tpu.cli import main

        assert main(["chaos", "--scenario", "list"]) == 0
        out = capsys.readouterr().out
        for name in ("smoke", "crash_resume", "straggler", "torn_ckpt"):
            assert name in out

    def test_unknown_scenario(self):
        from pytorch_distributed_nn_tpu.cli import main

        assert main(["chaos", "--scenario", "nope"]) == 2

    @pytest.mark.slow
    def test_smoke_scenario(self, tmp_path):
        from pytorch_distributed_nn_tpu.cli import main

        assert main(["chaos", "--scenario", "smoke",
                     "--workdir", str(tmp_path)]) == 0

    @pytest.mark.slow
    def test_crash_resume_scenario(self, tmp_path):
        from pytorch_distributed_nn_tpu.cli import main

        assert main(["chaos", "--scenario", "crash_resume",
                     "--workdir", str(tmp_path)]) == 0

    @pytest.mark.slow
    def test_straggler_scenario(self, tmp_path):
        from pytorch_distributed_nn_tpu.cli import main

        assert main(["chaos", "--scenario", "straggler",
                     "--workdir", str(tmp_path)]) == 0

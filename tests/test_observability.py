"""observability/: registry semantics, stream crash-safety, exposition
format validity, obs summary/compare over golden fixtures, and the
trainer's end-to-end telemetry wiring.

The layer's contract (docs/observability.md): one self-describing JSONL
stream per run (manifest header first), a registry that always agrees with
the stream, valid Prometheus exposition on every heartbeat tick, and a
`obs compare` CI gate that convicts step-time regressions.
"""

import json
import math
import os

import pytest

from pytorch_distributed_nn_tpu.observability import core, promexport, reader
from pytorch_distributed_nn_tpu.observability.obs_cli import main_obs


class TestRegistry:
    def test_counter_semantics(self):
        reg = core.MetricRegistry()
        c = reg.counter("requests_total", help="x")
        c.inc()
        c.inc(2.5)
        assert reg.counter("requests_total").value == 3.5  # get-or-create
        with pytest.raises(ValueError):
            c.inc(-1)

    def test_gauge_set(self):
        reg = core.MetricRegistry()
        g = reg.gauge("temperature")
        g.set(3)
        g.set(-1.5)
        assert reg.gauge("temperature").value == -1.5

    def test_labels_are_identity(self):
        reg = core.MetricRegistry()
        a = reg.counter("events_total", labels={"type": "retry"})
        b = reg.counter("events_total", labels={"type": "stall"})
        a.inc()
        assert b.value == 0
        assert reg.counter("events_total", labels={"type": "retry"}).value == 1

    def test_type_conflict_raises(self):
        reg = core.MetricRegistry()
        reg.counter("x_total")
        with pytest.raises(TypeError):
            reg.gauge("x_total")

    def test_bad_names_rejected(self):
        reg = core.MetricRegistry()
        with pytest.raises(ValueError):
            reg.counter("bad name")
        with pytest.raises(ValueError):
            reg.counter("ok_total", labels={"bad-label": "x"})

    def test_histogram_buckets_and_cumulative(self):
        reg = core.MetricRegistry()
        h = reg.histogram("lat", buckets=(0.1, 1.0, 10.0))
        for v in (0.05, 0.5, 0.5, 5.0, 50.0):
            h.observe(v)
        assert h.count == 5
        assert h.sum == pytest.approx(56.05)
        assert h.counts == [1, 2, 1, 1]  # per-bucket, +Inf last
        cum = h.cumulative()
        assert cum == [(0.1, 1), (1.0, 3), (10.0, 4), (float("inf"), 5)]

    def test_histogram_merge(self):
        a = core.Histogram("h", buckets=(1.0, 2.0))
        b = core.Histogram("h", buckets=(1.0, 2.0))
        a.observe(0.5)
        b.observe(1.5)
        b.observe(9.0)
        a.merge(b)
        assert a.counts == [1, 1, 1] and a.count == 3
        assert a.sum == pytest.approx(11.0)
        with pytest.raises(ValueError):
            a.merge(core.Histogram("h", buckets=(1.0, 3.0)))

    def test_histogram_rejects_unsorted_buckets(self):
        with pytest.raises(ValueError):
            core.Histogram("h", buckets=(2.0, 1.0))


class TestSinkAndStream:
    def test_manifest_is_always_the_first_record(self, tmp_path):
        path = os.path.join(str(tmp_path), "t.jsonl")
        t = core.Telemetry.for_run(path, core.run_manifest(config={"a": 1}))
        t.log_step({"step": 1, "loss": 1.0})
        t.emit("retry", label="x", attempt=1)
        t.close()
        with open(path) as f:
            records = [json.loads(line) for line in f]
        assert records[0]["kind"] == "manifest"
        assert records[0]["schema"] == core.SCHEMA_VERSION
        assert records[0]["config"] == {"a": 1}
        assert [r["kind"] for r in records[1:]] == ["step", "event"]

    def test_reopen_appends_restart_manifest(self, tmp_path):
        path = os.path.join(str(tmp_path), "t.jsonl")
        for _ in range(2):
            t = core.Telemetry.for_run(path)
            t.log_step({"step": 1})
            t.close()
        rs = reader.read_stream(path)
        assert len(rs.manifests) == 2
        assert rs.manifest is rs.manifests[0]  # header stays the header

    def test_torn_tail_is_valid_prefix(self, tmp_path):
        """Kill-mid-write crash contract: truncating the stream anywhere
        inside the last line leaves a readable valid prefix."""
        path = os.path.join(str(tmp_path), "t.jsonl")
        t = core.Telemetry.for_run(path)
        for i in range(1, 6):
            t.log_step({"step": i, "loss": float(i)})
        t.close()
        size = os.path.getsize(path)
        with open(path, "r+b") as f:
            f.truncate(size - 7)  # tear the final record mid-JSON
        rs = reader.read_stream(path)
        assert rs.truncated
        assert rs.bad_lines == 0
        assert [r["step"] for r in rs.steps] == [1, 2, 3, 4]
        assert rs.manifest is not None

    def test_corrupt_interior_line_counted_not_fatal(self, tmp_path):
        path = os.path.join(str(tmp_path), "t.jsonl")
        t = core.Telemetry.for_run(path)
        t.log_step({"step": 1})
        t.close()
        with open(path, "a") as f:
            f.write("NOT JSON\n")
            f.write(json.dumps({"kind": "step", "step": 2}) + "\n")
        rs = reader.read_stream(path)
        assert rs.bad_lines == 1 and not rs.truncated
        assert [r["step"] for r in rs.steps] == [1, 2]

    def test_registry_agrees_with_stream(self):
        t = core.Telemetry()
        t.log_step({"step": 1, "step_time": 0.5, "skipped_nonfinite": 1.0})
        t.emit("retry", label="x")
        t.emit("retry", label="y")
        reg = t.registry
        assert reg.counter("steps_total").value == 1
        assert reg.counter("events_total", labels={"type": "retry"}).value == 2
        assert reg.counter("nonfinite_skips_total").value == 1
        assert reg.histogram("step_time_seconds").count == 1

    def test_install_uninstall_default(self):
        prev = core.get_telemetry()
        mine = core.Telemetry()
        before = core.install(mine)
        try:
            assert core.get_telemetry() is mine
            core.get_telemetry().emit("retry", label="t")
            assert mine.registry.counter(
                "events_total", labels={"type": "retry"}
            ).value == 1
        finally:
            core.uninstall(mine, before)
        assert core.get_telemetry() is prev
        # out-of-order uninstall must not clobber the active default
        core.uninstall(mine, before)
        assert core.get_telemetry() is prev


class TestPromExposition:
    def _registry(self):
        reg = core.MetricRegistry()
        reg.counter("events_total", help="ev", labels={"type": "retry"}).inc(3)
        reg.counter("events_total", labels={"type": "stall"}).inc()
        reg.gauge("step_rate", help="sps").set(12.5)
        h = reg.histogram("step_time_seconds", buckets=(0.01, 0.1, 1.0))
        for v in (0.005, 0.05, 0.5, 5.0):
            h.observe(v)
        return reg

    def test_render_is_valid_exposition(self):
        text = promexport.render(self._registry())
        assert promexport.validate_exposition(text) == []
        assert '# TYPE pdtn_events_total counter' in text
        assert 'pdtn_events_total{type="retry"} 3' in text
        assert 'pdtn_step_time_seconds_bucket{le="+Inf"} 4' in text
        assert "pdtn_step_time_seconds_count 4" in text

    def test_histogram_bucket_counts_are_cumulative(self):
        text = promexport.render(self._registry())
        got = {}
        for line in text.splitlines():
            if line.startswith("pdtn_step_time_seconds_bucket"):
                le = line.split('le="')[1].split('"')[0]
                got[le] = int(line.rsplit(" ", 1)[1])
        assert got == {"0.01": 1, "0.1": 2, "1": 3, "+Inf": 4}

    def test_validator_catches_violations(self):
        bad_samples = "pdtn_x_total 3\n"  # no TYPE line
        assert promexport.validate_exposition(bad_samples)
        neg = "# TYPE pdtn_x_total counter\npdtn_x_total -1\n"
        assert any("negative" in e
                   for e in promexport.validate_exposition(neg))
        broken_hist = (
            "# TYPE pdtn_h histogram\n"
            'pdtn_h_bucket{le="1"} 5\n'
            'pdtn_h_bucket{le="+Inf"} 3\n'  # non-monotone + != count
            "pdtn_h_sum 1\n"
            "pdtn_h_count 9\n"
        )
        errs = promexport.validate_exposition(broken_hist)
        assert any("monotone" in e for e in errs)
        assert any("_count" in e for e in errs)

    def test_write_textfile_atomic(self, tmp_path):
        path = os.path.join(str(tmp_path), "m.prom")
        promexport.write_textfile(self._registry(), path)
        assert not os.path.exists(path + ".tmp")
        with open(path) as f:
            assert promexport.validate_exposition(f.read()) == []


class TestSummaryAndCompare:
    @pytest.fixture()
    def golden(self, tmp_path):
        d = os.path.join(str(tmp_path), "golden")
        os.makedirs(d)
        reader.write_synthetic_run(d, steps=60, step_time=0.01, jitter=0.0)
        return d

    def test_summary_percentiles_and_events(self, golden):
        s = reader.summarize_run(reader.read_stream(golden))
        assert s["steps"] == 60
        assert s["phases"]["step"]["p50"] == pytest.approx(0.01)
        assert s["phases"]["step"]["p99"] == pytest.approx(0.01)
        assert s["phases"]["checkpoint"]["count"] == 2
        assert s["events"]["retry"] == 1
        assert s["events"]["straggler_drop"] == 1
        assert s["events"]["checkpoint_write"] == 2
        assert [e["step"] for e in s["evals"]] == [30, 60]
        assert not math.isnan(s["step_rate"]["overall"])

    def test_io_stall_sets_what_a_save_cost_beside_its_stall(self, golden):
        """Per save: the dispatch gaps from the save to the next over the
        run's steady pace, beside the program's stall_ms. The gaps are the
        chip's shape: dispatch runs a launch queue ahead of the device, so
        a flush's gap is long and the next few near zero."""
        def window(first, wall_ms, gaps):
            return [{"step": first + i, "wall_ms": wall_ms,
                     "dispatch_gap_ms": g} for i, g in enumerate(gaps)]

        steady = [398.5, 0.5, 0.5, 0.5] + [100.0] * 4  # 8 steps of 100 ms
        steps = (
            window(1, 900.0, [7000.0] + steady[1:])   # the compile
            + window(9, 100.0, steady)
            # the save of step 16: the loop dispatches 380 ms late, the
            # device runs dry; one more dispatch returns at once
            + window(17, 147.5, [778.0, 0.5, 0.5, 0.5, 0.5] + [100.0] * 3)
            # ... and the next flush waits for the late device
            + window(25, 100.0, [598.5, 0.5, 0.5, 0.5] + [100.0] * 4)
            # the save of step 32 cost its stall and no more
            + window(33, 101.0, [406.5, 0.5, 0.5, 0.5] + [100.0] * 4)
            + window(41, 100.0, steady)
        )
        events = [
            {"type": "checkpoint_write", "step": s, "stall_ms": 8.0,
             "write_ms": 2900.0, "async": True, "bytes": 100}
            # nothing after the save of step 48: no figure, no entry
            for s in (16, 32, 48)
        ]
        rs = reader.RunStream("x", None, [], steps, events)
        io = reader.io_stall_summary(rs)
        assert [v["step"] for v in io["saves"]] == [16, 32]
        assert io["saves"][0]["late_ms"] == pytest.approx(280.0 + 200.0)
        assert io["saves"][1]["late_ms"] == pytest.approx(8.0)
        assert io["saves"][0]["stall_ms"] == 8.0
        assert io["late_ms"]["count"] == 2 and io["stall_ms"]["count"] == 3
        # the first window of a second train() call: its gaps do not add
        # up to its wall time, so the save before it gets no figure
        second = steps + window(49, 100.0, [1.0, 0.5, 0.5, 0.5] + [100.0] * 4)
        assert [v["step"] for v in reader.io_stall_summary(reader.RunStream(
            "x", None, [], second, events))["saves"]] == [16, 32]
        # a save after every flush leaves no steady window: no figures
        every = [dict(e, step=s) for e in events[:1] for s in range(8, 49, 8)]
        assert reader.io_stall_summary(
            reader.RunStream("x", None, [], steps, every))["saves"] == []
        # a stream from before the field: the section is as it was
        old = reader.summarize_run(reader.read_stream(golden))
        assert old["io_stall"]["late_ms"] is None
        assert old["io_stall"]["saves"] == []
        assert "loop late" not in reader.render_summary(old)
        text = reader.render_summary(old | {"io_stall": io})
        assert "loop late (ms)" in text
        assert "save @ step 16: stall 8.0 ms, late 480.0 ms" in text

    def test_percentile_nearest_rank(self):
        vals = [1.0, 2.0, 3.0, 4.0]
        assert reader.percentile(vals, 50) == 2.0
        assert reader.percentile(vals, 95) == 4.0
        assert math.isnan(reader.percentile([], 50))

    def test_compare_flags_2x_regression(self, golden, tmp_path):
        slow = os.path.join(str(tmp_path), "slow")
        os.makedirs(slow)
        reader.write_synthetic_run(slow, steps=60, step_time=0.02,
                                   jitter=0.0)
        sa = reader.summarize_run(reader.read_stream(golden))
        sb = reader.summarize_run(reader.read_stream(slow))
        _, regs = reader.compare_runs(sa, sb, threshold=0.2)
        assert any("step p50" in r["metric"] for r in regs)
        _, none = reader.compare_runs(sa, sa, threshold=0.2)
        assert none == []

    def test_replayed_registry_renders_valid_exposition(self, golden):
        reg = reader.replay_registry(reader.read_stream(golden))
        text = promexport.render(reg)
        assert promexport.validate_exposition(text) == []
        assert 'pdtn_run_info{' in text
        assert reg.counter("steps_total").value == 60


class TestObsCli:
    @pytest.fixture()
    def run_dir(self, tmp_path):
        d = os.path.join(str(tmp_path), "run")
        os.makedirs(d)
        reader.write_synthetic_run(d, steps=30, step_time=0.01)
        return d

    def test_summary_human_and_json(self, run_dir, capsys):
        assert main_obs(["summary", run_dir]) == 0
        out = capsys.readouterr().out
        assert "phases (seconds)" in out and "step rate:" in out
        assert "events:" in out and "retry" in out
        assert main_obs(["summary", run_dir, "--json"]) == 0
        payload = json.loads(capsys.readouterr().out)
        assert payload["steps"] == 30

    def test_compare_exit_codes(self, run_dir, tmp_path, capsys):
        slow = os.path.join(str(tmp_path), "slow")
        os.makedirs(slow)
        reader.write_synthetic_run(slow, steps=30, step_time=0.02)
        assert main_obs(["compare", run_dir, run_dir]) == 0
        assert main_obs(["compare", run_dir, slow]) == 1
        out = capsys.readouterr().out
        assert "REGRESSION" in out

    def test_export_stdout_is_valid(self, run_dir, capsys):
        assert main_obs(["export", run_dir]) == 0
        text = capsys.readouterr().out
        assert promexport.validate_exposition(text) == []

    def test_tail_bounded(self, run_dir, capsys):
        assert main_obs(["tail", run_dir, "--max-seconds", "0.05",
                         "--context", "5"]) == 0
        out = capsys.readouterr().out.splitlines()
        assert len(out) == 5
        assert any(line.startswith("event") or line.startswith("step")
                   for line in out)

    def test_selftest_passes(self, capsys):
        assert main_obs(["summary", "--selftest"]) == 0
        assert "invariants held" in capsys.readouterr().out

    def test_missing_run_dir_is_rc2(self, tmp_path):
        assert main_obs(["summary", os.path.join(str(tmp_path), "nope")]) == 2

    def test_main_cli_dispatch(self, capsys):
        from pytorch_distributed_nn_tpu.cli import main

        assert main(["obs", "summary", "--selftest"]) == 0


class TestCrossRankMerge:
    """merge_streams: per-process stream families merged on (step, rank)
    with the clock skew between hosts estimated from the shared per-step
    completion instants (the synchronous-SPMD barrier) and subtracted."""

    def test_find_streams_rank_order(self, tmp_path):
        d = str(tmp_path)
        for name in ("telemetry-rank10.jsonl", "telemetry.jsonl",
                     "telemetry-rank2.jsonl"):
            with open(os.path.join(d, name), "w") as f:
                f.write("{}\n")
        names = [os.path.basename(p) for p in reader.find_streams(d)]
        # rank 0's basename first, then numeric rank order (not lexicographic)
        assert names == ["telemetry.jsonl", "telemetry-rank2.jsonl",
                         "telemetry-rank10.jsonl"]

    def test_stream_basename(self):
        assert core.stream_basename() == "telemetry.jsonl"
        assert core.stream_basename(0) == "telemetry.jsonl"
        assert core.stream_basename(3) == "telemetry-rank3.jsonl"

    def test_manifest_carries_rank_host_clock(self):
        mf = core.run_manifest()
        assert mf["rank"] == 0
        assert mf["host"]
        assert mf["clock"]["wall"] > 0 and mf["clock"]["mono"] > 0

    def test_merge_aligns_skewed_clocks(self, tmp_path):
        d = str(tmp_path)
        reader.write_synthetic_pod(d, ranks=3, steps=40, clock_skew=7.0,
                                   straggler_rank=2)
        merged = reader.merge_streams(reader.read_streams(d))
        assert merged.ranks == [0, 1, 2]
        # after alignment the shared completion instants must collapse
        by_step = {}
        for rec in merged.steps:
            by_step.setdefault(rec["step"], []).append(rec["time_aligned"])
        spreads = [max(v) - min(v) for v in by_step.values()]
        assert max(spreads) < 0.05
        # raw wall clocks disagreed by ~7s/rank: alignment was real work
        raw = {}
        for rec in merged.steps:
            raw.setdefault(rec["step"], []).append(rec["time"])
        assert max(max(v) - min(v) for v in raw.values()) > 10.0

    def test_by_rank_summary_and_attribution(self, tmp_path):
        d = str(tmp_path)
        reader.write_synthetic_pod(d, ranks=2, steps=40, clock_skew=5.0,
                                   straggler_rank=1)
        merged = reader.merge_streams(reader.read_streams(d))
        s = reader.summarize_by_rank(merged)
        assert set(s["ranks"]) == {0, 1}
        assert s["ranks"][0]["steps"] == 40
        assert s["ranks"][1]["host"] == "host-1"
        assert s["ranks"][0]["phases"]["step"]["p50"] == pytest.approx(
            0.01, rel=0.01
        )
        # the planted rank-1 straggler: dropped every 10th step, slowest
        # on every step
        assert s["straggler"]["dropped_by_rank"] == {1: 4}
        assert s["straggler"]["slowest_by_rank"] == {1: 40}
        text = reader.render_by_rank(s)
        assert "per-rank phases" in text
        assert "straggler attribution" in text

    def test_merge_single_stream_is_identity(self, tmp_path):
        d = str(tmp_path)
        reader.write_synthetic_run(d, steps=10)
        merged = reader.merge_streams(reader.read_streams(d))
        assert merged.clock_offsets == {0: 0.0}
        assert len(merged.steps) == 10
        assert all(r["rank"] == 0 for r in merged.steps)

    def test_merge_falls_back_to_wall_clocks(self, tmp_path):
        """Pre-`mono` streams (older schema): alignment still works on
        wall clocks — the offset then includes the wall skew itself."""
        d = str(tmp_path)
        reader.write_synthetic_pod(d, ranks=2, steps=30, clock_skew=4.0)
        for path in reader.find_streams(d):
            lines = []
            with open(path) as f:
                for line in f:
                    rec = json.loads(line)
                    rec.pop("mono", None)
                    lines.append(json.dumps(rec))
            with open(path, "w") as f:
                f.write("\n".join(lines) + "\n")
        merged = reader.merge_streams(reader.read_streams(d))
        assert merged.clock_offsets[1] == pytest.approx(-4.0, abs=0.05)
        by_step = {}
        for rec in merged.steps:
            by_step.setdefault(rec["step"], []).append(rec["time_aligned"])
        assert max(max(v) - min(v) for v in by_step.values()) < 0.05

    def test_by_rank_cli(self, tmp_path, capsys):
        d = str(tmp_path)
        reader.write_synthetic_pod(d, ranks=2, steps=20, clock_skew=3.0,
                                   straggler_rank=0)
        assert main_obs(["summary", d, "--by-rank"]) == 0
        out = capsys.readouterr().out
        assert "per-rank phases" in out and "host-1" in out
        assert main_obs(["summary", d, "--by-rank", "--json"]) == 0
        payload = json.loads(capsys.readouterr().out)
        assert payload["straggler"]["slowest_by_rank"] == {"0": 20}


class TestTailModes:
    def test_tail_without_follow_exits(self, tmp_path, capsys):
        d = os.path.join(str(tmp_path), "run")
        os.makedirs(d)
        reader.write_synthetic_run(d, steps=8)
        # no --follow, no --max-seconds: prints the tail and returns
        assert main_obs(["tail", d, "--context", "3"]) == 0
        assert len(capsys.readouterr().out.splitlines()) == 3

    def test_tail_from_start_without_follow_prints_all(self, tmp_path,
                                                       capsys):
        d = os.path.join(str(tmp_path), "run")
        os.makedirs(d)
        reader.write_synthetic_run(d, steps=5, with_events=False)
        assert main_obs(["tail", d, "--from-start"]) == 0
        out = capsys.readouterr().out.splitlines()
        assert len(out) == 6  # manifest + 5 steps
        assert out[0].startswith("manifest")


class TestTimingShim:
    def test_metrics_logger_legacy_path_writes_stream(self, tmp_path):
        from pytorch_distributed_nn_tpu.analysis.run_metrics import (
            load_metrics,
        )
        from pytorch_distributed_nn_tpu.utils.timing import MetricsLogger

        path = os.path.join(str(tmp_path), "m.jsonl")
        ml = MetricsLogger(path)
        ml.log({"step": 1, "loss": 2.0, "step_time": 0.1, "data_time": 0.0,
                "imgs_per_sec": 10.0})
        ml.log({"step": 2, "loss": 1.0, "step_time": 0.1, "data_time": 0.0,
                "imgs_per_sec": 10.0})
        ml.close()
        with open(path) as f:
            first = json.loads(f.readline())
        assert first["kind"] == "manifest"
        # the offline analysis loader sees exactly the step records
        records = load_metrics(path)
        assert [r["step"] for r in records] == [1, 2]


class TestTrainerIntegration:
    """One tiny end-to-end run: the stream carries manifest + steps +
    events, the heartbeat carries the rate gauges, metrics.prom is valid
    exposition — the acceptance shape of the telemetry layer."""

    def test_supervised_run_produces_unified_stream(self, tmp_path):
        from pytorch_distributed_nn_tpu.training.trainer import (
            TrainConfig,
            Trainer,
        )

        d = str(tmp_path)
        cfg = TrainConfig(
            network="LeNet", dataset="MNIST", batch_size=16, num_workers=2,
            synthetic_size=32, max_steps=4, eval_freq=2, supervise=True,
            train_dir=d, log_every=2, test_batch_size=16,
            straggler_deadline=1.0, faults="delay@2:p1:5s,flaky_io@2",
        )
        t = Trainer(cfg)
        try:
            history = t.train()
            t.evaluate()
        finally:
            t.close()
        assert len(history) == 4

        rs = reader.read_stream(d)
        assert rs.manifest is not None
        assert rs.manifest["schema"] == core.SCHEMA_VERSION
        assert rs.manifest["config"]["network"] == "LeNet"
        assert rs.manifest["mesh_shape"]["data"] == 2
        assert rs.manifest["param_count"] > 0
        assert rs.manifest["sync_bytes_per_step"] > 0
        assert [r["step"] for r in rs.steps] == [1, 2, 3, 4]
        types = {e["type"] for e in rs.events}
        assert {"checkpoint_write", "retry", "straggler_drop",
                "fault_injected", "eval_result"} <= types

        s = reader.summarize_run(rs)
        assert s["events"]["checkpoint_write"] == 2
        assert s["events"]["retry"] == 1  # flaky_io's injected EIO
        assert s["straggler_dropped"] == 1
        # per-rank attribution fields (grad_sync report -> step records
        # and the straggler_drop event): the 5s-delayed rank 1 is the
        # slowest arrival at the fault step
        by_step = {r["step"]: r for r in rs.steps}
        assert by_step[2]["straggler_slowest_rank"] == 1.0
        assert by_step[2]["straggler_arrival_max"] > 1.0
        drop = [e for e in rs.events if e["type"] == "straggler_drop"][0]
        assert drop["slowest_rank"] == 1

        with open(os.path.join(d, "heartbeat.json")) as f:
            hb = json.load(f)
        assert hb["step"] == 4
        assert hb["step_rate"] > 0 and "eta_seconds" in hb

        with open(os.path.join(d, "metrics.prom")) as f:
            text = f.read()
        assert promexport.validate_exposition(text) == []
        assert "pdtn_step_rate" in text
        assert 'pdtn_events_total{type="checkpoint_write"} 2' in text
        assert "pdtn_phase_seconds_bucket" in text

    def test_sync_bytes_estimates(self):
        import numpy as np

        from pytorch_distributed_nn_tpu.parallel import make_grad_sync

        tree = {"a": np.zeros((10, 10), np.float32),
                "b": np.zeros((100,), np.float32)}
        assert make_grad_sync("allreduce").estimate_sync_bytes(tree) == 800
        assert make_grad_sync("local").estimate_sync_bytes(tree) == 0
        assert make_grad_sync(
            "allreduce", compression="int8"
        ).estimate_sync_bytes(tree) == 200 + 8
        topk = make_grad_sync("allreduce", compression="topk",
                              topk_ratio=0.01)
        assert topk.estimate_sync_bytes(tree) == (1 + 1) * 8

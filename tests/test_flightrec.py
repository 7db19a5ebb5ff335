"""Flight recorder (observability/detect.py + flightrec.py): spec
grammar, detector math on golden record sequences, capture rate-limiting
and bundle layout with a fake tracer, the `obs incidents` CLI, and one
tiny end-to-end trainer run with a real injected delay.

The layer's contract (docs/observability.md "Flight recorder"): anomalies
are convicted against the run's OWN baseline (EWMA warmup, no false
trigger on the compile step), at most one capture is ever in flight,
cooldown and max_bundles rate-limit hard, and every bundle is
self-contained (trace + ring + manifest + env + report).
"""

import json
import os

import pytest

from pytorch_distributed_nn_tpu.observability import (
    core,
    detect,
    flightrec,
    promexport,
    xplane,
)
from pytorch_distributed_nn_tpu.observability.obs_cli import main_obs


def _step(i, st=0.01, **kw):
    return {"kind": "step", "step": i, "step_time": st, **kw}


def _event(etype, step=None, **kw):
    rec = {"kind": "event", "type": etype, **kw}
    if step is not None:
        rec["step"] = step
    return rec


class TestSpecGrammar:
    def test_default_arms_every_detector(self):
        spec = detect.DetectorSpec.parse("default")
        assert [k for k, _ in spec.detectors] == list(detect.DETECTOR_KINDS)
        assert spec.cooldown == 50 and spec.max_bundles == 4
        assert spec.capture_steps == 4 and spec.ring == 256

    def test_custom_detectors_and_options(self):
        spec = detect.DetectorSpec.parse(
            "step_regression:factor=2.5:warmup=5,stall,"
            "cooldown=100,max_bundles=2,capture_steps=8,ring=64"
        )
        kinds = dict(spec.detectors)
        assert set(kinds) == {"step_regression", "stall"}
        assert kinds["step_regression"]["factor"] == 2.5
        assert kinds["step_regression"]["warmup"] == 5
        assert kinds["step_regression"]["alpha"] == 0.2  # default kept
        assert (spec.cooldown, spec.max_bundles) == (100, 2)
        assert (spec.capture_steps, spec.ring) == (8, 64)

    def test_describe_reparses_to_itself(self):
        spec = detect.DetectorSpec.parse("ckpt_stall:factor=4,cooldown=10")
        again = detect.DetectorSpec.parse(spec.describe())
        assert again == spec

    @pytest.mark.parametrize("bad", [
        "bogus",
        "step_regression:nope=1",
        "step_regression:factor",
        "cooldown=abc",
        "cooldown=5:x=1",
        "unknown_option=3",
        "cooldown=10",  # options only: no detector armed
    ])
    def test_bad_specs_rejected(self, bad):
        with pytest.raises(ValueError):
            detect.DetectorSpec.parse(bad)


class TestStepRegressionDetector:
    def _det(self, **kw):
        params = dict(factor=3.0, warmup=3, alpha=0.2, min_ms=10.0)
        params.update(kw)
        return detect.StepRegressionDetector(**params)

    def test_compile_step_never_triggers_or_seeds_baseline(self):
        det = self._det()
        # a 100x compile step first, then normal steps: no trigger, and
        # the baseline must come from the normal steps (a later normal
        # step would trigger against a compile-seeded EWMA's ghost)
        assert det.observe(_step(1, st=1.0)) is None
        for i in range(2, 8):
            assert det.observe(_step(i, st=0.01)) is None

    def test_no_trigger_during_warmup(self):
        det = self._det(warmup=10)
        det.observe(_step(1))  # compile
        for i in range(2, 8):
            det.observe(_step(i, st=0.01))
        assert det.observe(_step(8, st=1.0)) is None  # still warming up

    def test_post_warmup_spike_triggers_with_detail(self):
        det = self._det()
        det.observe(_step(1))
        for i in range(2, 8):
            assert det.observe(_step(i, st=0.01)) is None
        trig = det.observe(_step(8, st=0.5))
        assert trig is not None and trig.kind == "step_regression"
        assert trig.step == 8
        assert trig.detail["ewma"] == pytest.approx(0.01)

    def test_anomaly_does_not_poison_baseline(self):
        det = self._det()
        det.observe(_step(1))
        for i in range(2, 8):
            det.observe(_step(i, st=0.01))
        assert det.observe(_step(8, st=0.5)) is not None
        # if the 0.5 spike had entered the EWMA, a second identical spike
        # would no longer clear factor x baseline
        assert det.observe(_step(9, st=0.5)) is not None

    def test_restart_manifest_re_skips_compile(self):
        det = self._det()
        det.observe(_step(1))
        for i in range(2, 8):
            det.observe(_step(i, st=0.01))
        det.observe({"kind": "manifest", "run_id": "x"})  # resume
        # first record after the restart is the re-compile: no trigger
        assert det.observe(_step(8, st=2.0)) is None

    def test_min_ms_floor_ignores_micro_jitter(self):
        det = self._det(min_ms=50.0)
        det.observe(_step(1))
        for i in range(2, 8):
            det.observe(_step(i, st=0.001))
        # 10x regression but only ~9ms absolute: below the floor
        assert det.observe(_step(8, st=0.01)) is None


class TestEventDetectors:
    def test_straggler_burst_counts_within_window(self):
        det = detect.StragglerBurstDetector(count=3, window=10)
        assert det.observe(_event("straggler_drop", step=1)) is None
        assert det.observe(_event("straggler_drop", step=4)) is None
        trig = det.observe(_event("straggler_drop", step=8))
        assert trig is not None and trig.kind == "straggler_burst"
        assert trig.detail["steps"] == [1, 4, 8]

    def test_straggler_burst_window_expiry(self):
        det = detect.StragglerBurstDetector(count=3, window=10)
        det.observe(_event("straggler_drop", step=1))
        det.observe(_event("straggler_drop", step=4))
        # step 1 and 4 have fallen out of the window by step 20
        assert det.observe(_event("straggler_drop", step=20)) is None

    def test_nonfinite_burst(self):
        det = detect.NonfiniteDetector(count=2, window=50)
        assert det.observe(_event("nonfinite_skip", step=3)) is None
        assert det.observe(_event("nonfinite_skip", step=9)) is not None

    def test_stall_triggers_immediately(self):
        det = detect.StallDetector()
        trig = det.observe(_event("stall", step=7, age_seconds=12.5,
                                  grace=5.0))
        assert trig is not None and trig.kind == "stall"
        assert trig.detail["age_seconds"] == 12.5

    def test_ckpt_stall_relative_breach(self):
        det = detect.CkptStallDetector(factor=3.0, warmup=2, min_ms=50.0)
        assert det.observe(_event("checkpoint_write", step=10,
                                  stall_ms=40.0)) is None
        assert det.observe(_event("checkpoint_write", step=20,
                                  stall_ms=60.0)) is None
        # 10x the median of {40, 60}: convicted
        trig = det.observe(_event("checkpoint_write", step=30,
                                  stall_ms=500.0))
        assert trig is not None and trig.kind == "ckpt_stall"
        # pre-async streams: `seconds` fallback (the write WAS the stall)
        det2 = detect.CkptStallDetector(factor=3.0, warmup=1, min_ms=50.0)
        det2.observe(_event("checkpoint_write", step=1, seconds=0.05))
        assert det2.observe(_event("checkpoint_write", step=2,
                                   seconds=1.0)) is not None

    def test_ckpt_stall_needs_warmup(self):
        det = detect.CkptStallDetector(factor=3.0, warmup=2, min_ms=50.0)
        assert det.observe(_event("checkpoint_write", step=10,
                                  stall_ms=5000.0)) is None  # first write


class TestRecorder:
    def _recorder(self, tmp_path, spec_str, tracer_calls=None):
        calls = tracer_calls if tracer_calls is not None else []
        tracer = (
            lambda d: calls.append(("start", d)),
            lambda: calls.append(("stop",)),
        )
        tel = core.Telemetry.for_run(
            os.path.join(str(tmp_path), "telemetry.jsonl"),
            core.run_manifest(config={"network": "X"}),
        )
        spec = detect.DetectorSpec.parse(spec_str)
        fr = flightrec.FlightRecorder(str(tmp_path), tel, spec,
                                      tracer=tracer)
        return tel, fr, calls

    SPEC = ("step_regression:factor=3:warmup=3:min_ms=10,"
            "cooldown=10,capture_steps=2,max_bundles=2,ring=32")

    def _drive(self, tel, fr, n, spike_at=(), start=1):
        for i in range(start, start + n):
            tel.log_step(_step(i, st=0.5 if i in spike_at else 0.01))
            fr.tick(i)

    def test_bundle_layout_and_rate_limit(self, tmp_path):
        tel, fr, calls = self._recorder(tmp_path, self.SPEC)
        try:
            # spike at 8 -> capture 9..10; second spike at 12 is inside
            # the cooldown (10 steps past the capture close) -> suppressed
            self._drive(tel, fr, 14, spike_at={8, 12})
            assert len(fr.bundles) == 1
            assert fr.suppressed >= 1
            bundle = fr.bundles[0]
            assert os.path.basename(bundle) == "8-step_regression"
            for name in ("incident.json", "events.jsonl", "manifest.json",
                         "env.json"):
                assert os.path.isfile(os.path.join(bundle, name)), name
            with open(os.path.join(bundle, "incident.json")) as f:
                meta = json.load(f)
            assert meta["kind"] == "step_regression" and meta["step"] == 8
            assert meta["capture_until_step"] == 10
            # the ring snapshot holds the records up to the trigger
            with open(os.path.join(bundle, "events.jsonl")) as f:
                ring = [json.loads(line) for line in f]
            assert ring[0]["kind"] == "manifest"
            assert ring[-1]["step"] == 8
            # tracer bracketed exactly one window
            assert calls == [
                ("start", os.path.join(bundle, "trace")), ("stop",),
            ]
        finally:
            fr.close()
            tel.close()
        # report written on finalize (background thread joined)
        with open(os.path.join(fr.bundles[0], "report.md")) as f:
            report = f.read()
        assert "step_regression" in report and "Event ring" in report

    def test_incident_event_and_registry(self, tmp_path):
        tel, fr, _ = self._recorder(tmp_path, self.SPEC)
        try:
            self._drive(tel, fr, 10, spike_at={8})
            reg = tel.registry
            assert reg.counter(
                "incidents_total", labels={"kind": "step_regression"}
            ).value == 1
            assert reg.gauge("detector_armed").value == 0.0  # cooling down
        finally:
            fr.close()
            tel.close()
        from pytorch_distributed_nn_tpu.observability import reader

        rs = reader.read_stream(str(tmp_path))
        incidents = [e for e in rs.events if e.get("type") == "incident"]
        assert len(incidents) == 1
        assert incidents[0]["incident"] == "step_regression"
        assert incidents[0]["step"] == 8
        assert incidents[0]["bundle"].startswith("incidents/")

    def test_max_bundles_hard_cap(self, tmp_path):
        tel, fr, _ = self._recorder(
            tmp_path,
            "step_regression:factor=3:warmup=3:min_ms=10,"
            "cooldown=1,capture_steps=1,max_bundles=2",
        )
        try:
            self._drive(tel, fr, 40, spike_at={8, 15, 22, 29})
            assert len(fr.bundles) == 2  # cap, not 4
            assert fr.suppressed >= 2
            assert tel.registry.gauge("detector_armed").value == 0.0
        finally:
            fr.close()
            tel.close()

    def test_armed_gauge_lifecycle(self, tmp_path):
        tel, fr, _ = self._recorder(tmp_path, self.SPEC)
        try:
            g = tel.registry.gauge("detector_armed")
            assert g.value == 1.0
            self._drive(tel, fr, 9, spike_at={8})  # capture in flight
            assert g.value == 0.0
            # past capture end + cooldown: re-armed
            self._drive(tel, fr, 13, start=10)
            assert g.value == 1.0
        finally:
            fr.close()
            tel.close()

    def test_trace_failure_still_writes_bundle(self, tmp_path):
        def boom(_):
            raise RuntimeError("profiler busy")

        tel = core.Telemetry.for_run(
            os.path.join(str(tmp_path), "telemetry.jsonl"),
            core.run_manifest(),
        )
        fr = flightrec.FlightRecorder(
            str(tmp_path), tel, detect.DetectorSpec.parse(self.SPEC),
            tracer=(boom, lambda: None),
        )
        try:
            self._drive(tel, fr, 12, spike_at={8})
        finally:
            fr.close()
            tel.close()
        assert len(fr.bundles) == 1
        with open(os.path.join(fr.bundles[0], "report.md")) as f:
            assert "trace not captured" in f.read()

    def test_new_prom_families_validate(self, tmp_path):
        """Satellite: the exposition validator covers incidents_total and
        detector_armed."""
        tel, fr, _ = self._recorder(tmp_path, self.SPEC)
        try:
            self._drive(tel, fr, 10, spike_at={8})
            text = promexport.render(tel.registry)
        finally:
            fr.close()
            tel.close()
        assert promexport.validate_exposition(text) == []
        assert 'pdtn_incidents_total{kind="step_regression"} 1' in text
        assert "pdtn_detector_armed 0" in text

    def test_incidents_cli(self, tmp_path, capsys):
        tel, fr, _ = self._recorder(tmp_path, self.SPEC)
        try:
            self._drive(tel, fr, 12, spike_at={8})
        finally:
            fr.close()
            tel.close()
        d = str(tmp_path)
        assert main_obs(["incidents", d]) == 0
        out = capsys.readouterr().out
        assert "8-step_regression" in out and "1 incident(s)" in out
        assert main_obs(["incidents", d, "8-step_regression"]) == 0
        out = capsys.readouterr().out
        assert "reason:" in out and "# Incident" in out
        # lookup by step number
        assert main_obs(["incidents", d, "8"]) == 0
        capsys.readouterr()
        assert main_obs(["incidents", d, "nope"]) == 2
        assert main_obs(["incidents", d, "--json"]) == 0
        payload = json.loads(capsys.readouterr().out)
        assert payload[0]["kind"] == "step_regression"

    def test_incidents_cli_empty_dir_rc0(self, tmp_path, capsys):
        assert main_obs(["incidents", str(tmp_path)]) == 0
        assert "no incidents" in capsys.readouterr().out

    def test_notify_stall_direct_hook(self, tmp_path):
        tel, fr, _ = self._recorder(tmp_path, "stall,cooldown=5")
        try:
            fr.notify_stall(12.0)  # the supervisor watchdog hook
            fr.tick(1)
            assert fr._capture is not None  # capture opened this tick
        finally:
            fr.close()  # finalize closes the window and writes the report
            tel.close()
        assert len(fr.bundles) == 1
        assert "stall" in os.path.basename(fr.bundles[0])


class TestReportGeneration:
    def test_report_degrades_without_device_planes(self, tmp_path,
                                                   monkeypatch):
        monkeypatch.setattr(xplane, "summarize_xplane",
                            lambda *a, **k: {})
        bundle = os.path.join(str(tmp_path), "7-stall")
        plane_dir = os.path.join(bundle, "trace", "plugins", "profile", "t")
        os.makedirs(plane_dir)
        with open(os.path.join(plane_dir, "host.xplane.pb"), "w") as f:
            f.write("x")
        with open(os.path.join(bundle, "incident.json"), "w") as f:
            json.dump({"kind": "stall", "step": 7, "reason": "r",
                       "triggered_time": 1.0, "spec": "s"}, f)
        with open(os.path.join(bundle, "events.jsonl"), "w") as f:
            f.write(json.dumps({"kind": "step", "step": 7,
                                "step_time": 0.5}) + "\n")
        path = xplane.write_incident_report(bundle)
        with open(path) as f:
            report = f.read()
        assert "# Incident: stall @ step 7" in report
        assert "no device planes" in report
        assert "step=7" in report


class TestTrainerFlightrec:
    """End-to-end: a real injected host delay under --flightrec produces
    one incident bundle with a REAL jax.profiler trace (CPU)."""

    def test_delay_produces_one_bundle(self, tmp_path):
        from pytorch_distributed_nn_tpu.observability import reader
        from pytorch_distributed_nn_tpu.training.trainer import (
            TrainConfig,
            Trainer,
        )

        d = str(tmp_path)
        cfg = TrainConfig(
            network="LeNet", dataset="MNIST", batch_size=16, num_workers=2,
            synthetic_size=32, max_steps=12, test_batch_size=16,
            train_dir=d, log_every=1, metrics_path=os.path.join(
                d, "telemetry.jsonl"),
            faults="delay@7:p0:2.5s",
            # warmup=5 arms the detector exactly at the fault step (the
            # compile step is skipped, records 2..6 are the baseline), so
            # a loaded CI host's jitter can neither false-trigger earlier
            # nor inflate the baseline past the 2.5s injected delay
            flightrec=("step_regression:factor=2.5:warmup=5:min_ms=100,"
                       "cooldown=50,capture_steps=2"),
        )
        t = Trainer(cfg)
        try:
            history = t.train()
        finally:
            t.close()
        assert len(history) == 12
        incidents = flightrec.list_incidents(d)
        assert len(incidents) == 1
        inc = incidents[0]
        assert inc["kind"] == "step_regression" and inc["step"] == 7
        assert inc["has_trace"], "CPU jax.profiler trace should be captured"
        assert inc["has_report"]
        # the report's trace section is the real reader on the real trace
        with open(os.path.join(inc["path"], "report.md")) as f:
            assert "no device planes" in f.read()
        rs = reader.read_stream(os.path.join(d, "telemetry.jsonl"))
        assert sum(
            1 for e in rs.events if e.get("type") == "incident"
        ) == 1
        # the ring carried the fault that caused the anomaly
        with open(os.path.join(inc["path"], "events.jsonl")) as f:
            ring = [json.loads(line) for line in f if line.strip()]
        assert any(
            r.get("type") == "fault_injected" and r.get("step") == 7
            for r in ring
        )

    def test_bad_spec_fails_before_compile(self, tmp_path):
        from pytorch_distributed_nn_tpu.training.trainer import (
            TrainConfig,
            Trainer,
        )

        with pytest.raises(ValueError, match="unknown detector"):
            Trainer(TrainConfig(
                network="LeNet", dataset="MNIST", batch_size=16,
                num_workers=2, synthetic_size=32, max_steps=2,
                train_dir=str(tmp_path), flightrec="bogus_detector",
            ))

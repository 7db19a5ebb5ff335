"""Efficiency layer: cost model, calibration, planner, MFU telemetry.

Covers the ISSUE-9 contract: hand-checked FLOPs/bytes for known conv and
matmul shapes, cost additivity across a real training step, planner
ranking monotonicity (more ICI bytes on a slower link never wins),
calibration round-trip from a synthetic xplane trace, CLI rc codes, and
the old-stream/new-stream compatibility both directions.
"""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from pytorch_distributed_nn_tpu.analysis import costmodel
from pytorch_distributed_nn_tpu.analysis.calibration import (
    DEFAULT_PROFILES,
    CalibrationProfile,
    TPU_V5E,
    default_profile,
    fit_from_trace,
    peak_flops_per_device,
    predict_step_ms,
)
from pytorch_distributed_nn_tpu.analysis import planner


class TestOpFamily:
    """The shared classifier: one implementation for traces and HLO."""

    def test_families(self):
        f = costmodel.op_family
        assert f("%convert_reduce_fusion.3") == "convert_reduce_fusion"
        assert f("convert_reduce_fusion") == "convert_reduce_fusion"
        assert f("%multiply_add_fusion.12") == "multiply_add_fusion"
        assert f("%convolution_add_fusion") == "multiply_add_fusion"
        assert f("broadcast_add_fusion.1") == "elementwise"
        assert f("fusion.7") == "elementwise"
        assert f("add.3") == "elementwise"
        assert f("%copy.4") == "other"
        assert f("all-reduce.5") == "other"
        assert f("%convolution.5") == "other"  # refined by metadata only


class TestCostWalk:
    """Hand-checked FLOPs/bytes on known shapes + additivity."""

    def _lower(self, fn, *args):
        low = jax.jit(fn).lower(*args)
        return low, low.compile()

    def test_hand_checked_matmul(self):
        a = jnp.zeros((64, 128))
        b = jnp.zeros((128, 32))
        _, comp = self._lower(lambda a, b: a @ b, a, b)
        sc = costmodel.step_cost_from_hlo(comp.as_text())
        assert sc.hlo_flops == pytest.approx(2 * 64 * 32 * 128)
        # operand + result traffic: a + b + out, f32
        assert sc.hbm_bytes == pytest.approx(
            4 * (64 * 128 + 128 * 32 + 64 * 32)
        )

    def test_hand_checked_conv(self):
        # VALID padding: the naive 2*out*taps count is exact
        x = jnp.zeros((2, 8, 8, 4))
        k = jnp.zeros((3, 3, 4, 8))

        def conv(x, k):
            return jax.lax.conv_general_dilated(
                x, k, (1, 1), "VALID",
                dimension_numbers=("NHWC", "HWIO", "NHWC"),
            )

        _, comp = self._lower(conv, x, k)
        sc = costmodel.step_cost_from_hlo(comp.as_text())
        out_elems = 2 * 6 * 6 * 8
        assert sc.hlo_flops == pytest.approx(2 * out_elems * 3 * 3 * 4)
        # within 5% of XLA's own count (the acceptance tolerance)
        ca = comp.cost_analysis()
        ca = ca[0] if isinstance(ca, (list, tuple)) else ca
        assert sc.hlo_flops == pytest.approx(ca["flops"], rel=0.05)

    def test_lenet_step_cost_additivity_and_oracle(self):
        """The real dp train step: the XLA-scaled total IS the oracle
        count, families sum to it exactly (additivity), and the ICI
        estimate matches the collective inventory."""
        from pytorch_distributed_nn_tpu import analysis
        from pytorch_distributed_nn_tpu.models import build_model, input_spec
        from pytorch_distributed_nn_tpu.optim import build_optimizer
        from pytorch_distributed_nn_tpu.parallel import (
            make_grad_sync,
            make_mesh,
        )
        from pytorch_distributed_nn_tpu.training import dp_audit_bundle

        mesh = make_mesh(2, 1, 1)
        bundle = dp_audit_bundle(
            build_model("LeNet", 10), build_optimizer("sgd", 0.1),
            make_grad_sync("allreduce"), mesh, input_spec("LeNet"), 8,
        )
        report = analysis.audit(**bundle)
        sc = report.cost
        assert sc is not None
        assert sc.flops > 0 and sc.hbm_bytes > 0
        # additivity: the family split partitions the total
        fam_sum = sum(fc.flops for fc in sc.families.values())
        assert fam_sum == pytest.approx(sc.flops, rel=1e-6)
        byte_sum = sum(fc.hbm_bytes for fc in sc.families.values())
        assert byte_sum == pytest.approx(sc.hbm_bytes, rel=1e-6)
        # the XLA oracle was found and adopted on this backend
        assert sc.xla_flops is not None
        assert sc.flops == pytest.approx(sc.xla_flops)
        # walk-vs-oracle drift stays inside the documented band (the
        # padded dgrad overcount); the REPORTED number is exact
        assert sc.hlo_flops == pytest.approx(sc.xla_flops, rel=0.30)
        # ICI matches the collective inventory the report carries
        assert sc.ici_bytes == pytest.approx(
            report.est_ici_bytes_per_step()
        )
        # compute families are populated (fwd + bwd split)
        assert sc.families["convert_reduce_fusion"].flops > 0
        assert sc.families["multiply_add_fusion"].flops > 0
        # and the cost rides the JSON report for CI consumers
        assert report.to_dict()["cost"]["flops"] == pytest.approx(sc.flops)

    @pytest.mark.slow
    def test_resnet18_within_5pct_of_oracle(self):
        from pytorch_distributed_nn_tpu.models import build_model, input_spec
        from pytorch_distributed_nn_tpu.optim import build_optimizer
        from pytorch_distributed_nn_tpu.parallel import (
            make_grad_sync,
            make_mesh,
        )
        from pytorch_distributed_nn_tpu.training import dp_audit_bundle

        mesh = make_mesh(1, 1, 1)
        bundle = dp_audit_bundle(
            build_model("ResNet18", 10), build_optimizer("sgd", 0.1),
            make_grad_sync("local"), mesh, input_spec("ResNet18"), 8,
        )
        compiled = bundle["step_fn"].lower(*bundle["args"]).compile()
        ca = compiled.cost_analysis()
        ca = ca[0] if isinstance(ca, (list, tuple)) else ca
        sc = costmodel.step_cost_from_hlo(
            compiled.as_text(), xla_flops=ca["flops"]
        )
        # the reported (scaled) total matches the oracle exactly; 5% is
        # the acceptance band for the hand-derived comparison
        assert sc.flops == pytest.approx(ca["flops"], rel=1e-6)
        assert sc.flops > 1e9  # ResNet-18 b8 fwd+bwd is giga-scale


class TestCalibration:
    def test_default_profiles_and_roundtrip(self, tmp_path):
        prof = default_profile("tpu", TPU_V5E)
        assert prof.peak_flops_per_s == pytest.approx(197e12)
        assert prof.compute_ceilings["multiply_add_fusion"] == (
            pytest.approx(118.7e12)
        )
        assert not prof.shared_substrate
        cpu = default_profile("cpu")
        assert cpu.shared_substrate
        path = str(tmp_path / "calibration.json")
        prof.save(path)
        loaded = CalibrationProfile.load(path)
        assert loaded.compute_ceilings == prof.compute_ceilings
        assert loaded.hbm_bytes_per_s == prof.hbm_bytes_per_s
        assert loaded.source == "file"

    def test_peak_table_is_keyed_by_device_kind(self):
        """The v5e reports "TPU v5 lite"; an accelerator the table does
        not hold is an error where MFU is computed, never v5e's peak."""
        assert peak_flops_per_device("tpu", "TPU v5 lite") == 197e12
        assert peak_flops_per_device("cpu", "whatever") == (
            default_profile("cpu").peak_flops_per_s
        )
        for backend, kind in (("tpu", "TPU v9 imaginary"), ("tpu", ""),
                              ("gpu", "NVIDIA H100")):
            with pytest.raises(ValueError, match="no calibration profile"):
                peak_flops_per_device(backend, kind)
            with pytest.raises(ValueError, match="no calibration profile"):
                default_profile(backend, kind)

    def test_v5e_peaks_agree_with_the_benchmarks_table(self):
        """One decision, two files: the package may not import
        ``benchmark/`` and a program PR may not edit ``peaks.json``, so
        this is what keeps the two tables of nominal peaks the same."""
        with open(os.path.join(os.path.dirname(os.path.abspath(__file__)),
                               "..", "benchmark", "peaks.json")) as f:
            peaks = json.load(f)
        tpu_kinds = {k for k, p in DEFAULT_PROFILES.items()
                     if p.backend == "tpu"}
        assert tpu_kinds == set(peaks)
        for kind in tpu_kinds:
            prof = default_profile("tpu", kind)
            assert prof.peak_flops_per_s == peaks[kind]["bf16_flops_per_s"]
            assert prof.hbm_peak_bytes_per_s == peaks[kind]["hbm_bytes_per_s"]

    @staticmethod
    def _write_trace(path, op_ms):
        """One chip's ``XLA Ops`` line, back to back, as a text XSpace."""
        events, meta, at = [], [], 0
        for i, (name, ms) in enumerate(op_ms, start=1):
            ps = int(ms * 1e9)
            events.append(f"events {{ metadata_id: {i} offset_ps: {at} "
                          f"duration_ps: {ps} }}")
            meta.append(f'event_metadata {{ key: {i} value {{ id: {i} '
                        f'name: "{name}" }} }}')
            at += ps
        with open(path, "w") as f:
            f.write('planes { id: 1 name: "/device:TPU:0" lines { id: 1 '
                    'name: "XLA Ops" ' + " ".join(events) + " } "
                    + " ".join(meta) + " }\n")

    def test_fit_from_synthetic_trace_roundtrip(self, tmp_path):
        """Calibration round-trip from a synthetic xplane trace: fitted
        ceiling == family flops x steps / family device time, persisted
        and reloaded bit-equal."""
        trace = str(tmp_path / "step.textproto")
        self._write_trace(trace, [
            ("convert_reduce_fusion.1", 10.0),
            ("multiply_add_fusion.2", 5.0),
            ("fusion.3", 2.0),
            ("all-reduce.4", 2.0),
        ])
        cost = {
            "flops": 1.51e9,
            "ici_bytes": 1e6,
            "families": {
                "convert_reduce_fusion": {"flops": 1e9, "hbm_bytes": 1e8},
                "multiply_add_fusion": {"flops": 5e8, "hbm_bytes": 5e7},
                "elementwise": {"flops": 1e7, "hbm_bytes": 2e7},
                "other": {"flops": 0.0, "hbm_bytes": 0.0},
            },
        }
        prof = fit_from_trace(trace, cost, steps=4,
                              base=default_profile("tpu", TPU_V5E))
        assert prof.source == "trace"
        assert prof.compute_ceilings["convert_reduce_fusion"] == (
            pytest.approx(1e9 * 4 / 0.010)
        )
        assert prof.compute_ceilings["multiply_add_fusion"] == (
            pytest.approx(5e8 * 4 / 0.005)
        )
        # elementwise family is the HBM fit source
        assert prof.hbm_bytes_per_s == pytest.approx(2e7 * 4 / 0.002)
        # collective device time fits the ICI ceiling
        assert prof.ici_bytes_per_s == pytest.approx(1e6 * 4 / 0.002)
        # zero-flop family keeps the base ceiling, never div-by-zero
        assert prof.compute_ceilings["other"] == (
            default_profile("tpu", TPU_V5E).compute_ceilings["other"]
        )
        path = str(tmp_path / "calibration.json")
        prof.save(path)
        loaded = CalibrationProfile.load(path)
        assert loaded.compute_ceilings == prof.compute_ceilings
        assert loaded.ici_bytes_per_s == prof.ici_bytes_per_s


class TestPlannerScoring:
    """Monotonicity of the roofline score — no lowering needed."""

    def _cost(self, flops=1e9, hbm=1e7, ici=0.0):
        return {
            "flops": flops, "hbm_bytes": hbm, "ici_bytes": ici,
            "families": {
                "convert_reduce_fusion": {"flops": flops, "hbm_bytes": hbm},
            },
        }

    def test_more_ici_bytes_never_wins(self):
        prof = default_profile("tpu", TPU_V5E)
        lo = predict_step_ms(self._cost(ici=1e6), prof)
        hi = predict_step_ms(self._cost(ici=2e6), prof)
        assert hi["predicted_ms"] > lo["predicted_ms"]

    def test_slower_link_never_wins(self):
        fast = default_profile("tpu", TPU_V5E)
        slow = default_profile("tpu", TPU_V5E)
        slow.ici_bytes_per_s = fast.ici_bytes_per_s / 4
        cost = self._cost(ici=1e6)
        assert (
            predict_step_ms(cost, slow)["predicted_ms"]
            > predict_step_ms(cost, fast)["predicted_ms"]
        )

    def test_ranking_monotone_in_ici(self):
        """A candidate with identical compute but more ICI bytes on a
        slower link ranks strictly worse — the acceptance invariant."""
        fast = default_profile("tpu", TPU_V5E)
        slow = default_profile("tpu", TPU_V5E)
        slow.ici_bytes_per_s = fast.ici_bytes_per_s / 10
        light, heavy = self._cost(ici=1e6), self._cost(ici=8e6)
        scores = sorted(
            (predict_step_ms(c, p)["predicted_ms"], name)
            for name, c, p in (
                ("light_fast", light, fast),
                ("heavy_slow", heavy, slow),
                ("light_slow", light, slow),
                ("heavy_fast", heavy, fast),
            )
        )
        assert scores[0][1] == "light_fast"
        assert scores[-1][1] == "heavy_slow"

    def test_shared_substrate_charges_global_work(self):
        cpu = default_profile("cpu")
        one = predict_step_ms(self._cost(), cpu, devices=1)
        four = predict_step_ms(self._cost(), cpu, devices=4)
        assert four["compute_ms"] == pytest.approx(4 * one["compute_ms"])


class TestPlannerEndToEnd:
    def test_plan_lenet_two_devices(self):
        result = planner.plan("lenet", 2, batch_size=4, optimizer="sgd")
        live = [c for c in result["candidates"] if not c["skipped"]]
        assert len(live) == 2  # dp in {1, 2}
        assert result["top"] is not None
        # CPU profile is shared-substrate: the collective-free dp=1
        # candidate must rank first (more virtual devices never speed a
        # single core up)
        assert result["candidates"][0]["mesh"] == {
            "data": 1, "model": 1, "seq": 1,
        }
        assert all(c["predicted_ms"] > 0 for c in live)

    @pytest.mark.slow
    def test_plan_validation_agreement_lenet(self):
        """The acceptance cross-validation: the planner's top choice
        agrees with the measured-fastest candidate mesh."""
        result = planner.plan(
            "lenet", 4, batch_size=8, optimizer="sgd", validate=True,
        )
        assert "measured_fastest" in result
        assert result["agreement"], (
            f"predicted {result['top']} but measured fastest "
            f"{result['measured_fastest']}"
        )


class TestAnalyzeCLI:
    """rc codes of the new analyze surfaces (in-process, conftest mesh)."""

    def test_plan_check_rc0(self, capsys):
        from pytorch_distributed_nn_tpu.cli import main_analyze

        rc = main_analyze(["--plan", "--check"])
        out = capsys.readouterr()
        assert rc == 0
        assert "predicted fastest" in out.out
        assert "PASS" in out.err

    def test_cost_flag_prints_section(self, capsys):
        from pytorch_distributed_nn_tpu.cli import main_analyze

        rc = main_analyze(["--model", "lenet", "--mesh", "2", "--cost"])
        out = capsys.readouterr().out
        assert rc == 0
        assert "step cost (optimized HLO):" in out
        assert "convert_reduce_fusion" in out

    def test_cost_rides_json_report(self, capsys):
        from pytorch_distributed_nn_tpu.cli import main_analyze

        rc = main_analyze(["--model", "lenet", "--mesh", "2", "--json"])
        assert rc == 0
        payload = json.loads(capsys.readouterr().out)
        assert payload["cost"]["flops"] > 0
        assert "families" in payload["cost"]

    def test_calibrate_writes_defaults(self, tmp_path, capsys):
        from pytorch_distributed_nn_tpu.cli import main_analyze

        out = str(tmp_path / "calibration.json")
        rc = main_analyze(["--calibrate", "--out", out])
        assert rc == 0
        prof = CalibrationProfile.load(out)
        assert prof.backend == "cpu" and prof.shared_substrate

    def test_check_without_plan_rc2(self, capsys):
        from pytorch_distributed_nn_tpu.cli import main_analyze

        assert main_analyze(["--check"]) == 2


class TestStreamCompatibility:
    """Satellite: old->new and new->old stream directions both work."""

    def test_pre_efficiency_stream_skips_section(self, tmp_path):
        from pytorch_distributed_nn_tpu.observability import reader

        old = str(tmp_path / "old")
        new = str(tmp_path / "new")
        os.makedirs(old)
        os.makedirs(new)
        reader.write_synthetic_run(old, steps=20, with_cost=False)
        reader.write_synthetic_run(new, steps=20, with_cost=True)
        s_old = reader.summarize_run(reader.read_stream(old))
        s_new = reader.summarize_run(reader.read_stream(new))
        assert s_old["efficiency"] is None
        assert s_new["efficiency"] is not None
        # render never crashes on the absent section
        assert "efficiency" not in reader.render_summary(s_old)
        assert "MFU" in reader.render_summary(s_new)
        # compares in BOTH directions never raise an mfu false-fail
        for a, b in ((s_old, s_new), (s_new, s_old)):
            lines, regs = reader.compare_runs(a, b, threshold=1e9)
            assert not any(r["metric"] == "mfu" for r in regs)
            assert not any(ln.lstrip().startswith("mfu") for ln in lines)

    def test_load_metrics_tolerates_new_manifest_fields(self, tmp_path):
        from pytorch_distributed_nn_tpu.analysis.run_metrics import (
            load_metrics,
        )
        from pytorch_distributed_nn_tpu.observability import reader

        new = str(tmp_path / "new")
        os.makedirs(new)
        path = reader.write_synthetic_run(new, steps=15, with_cost=True)
        records = load_metrics(path)
        assert len(records) == 15
        assert all("step_time" in r for r in records)

    def test_mfu_jitter_floor(self, tmp_path):
        """A sub-floor MFU wobble never regresses; a real drop does."""
        from pytorch_distributed_nn_tpu.observability.reader import (
            compare_runs,
        )

        def summary(mfu):
            return {
                "steps": 10, "events": {},
                "phases": {}, "step_rate": {},
                "efficiency": {"mfu": {"overall": mfu}},
            }

        # -20% relative but only 0.004 absolute: inside the 0.01 floor
        _, regs = compare_runs(summary(0.020), summary(0.016),
                               threshold=0.10)
        assert not regs
        # same relative drop at production MFU scale: convicted
        _, regs = compare_runs(summary(0.40), summary(0.32),
                               threshold=0.10)
        assert [r["metric"] for r in regs] == ["mfu"]


class TestServingFlops:
    def test_engine_reports_bucket_flops(self, tmp_path):
        from pytorch_distributed_nn_tpu.observability import reader
        from pytorch_distributed_nn_tpu.serving.batcher import Batcher
        from pytorch_distributed_nn_tpu.serving.engine import (
            InferenceEngine,
        )
        from pytorch_distributed_nn_tpu.serving.loadgen import (
            make_tiny_artifact,
            sample_inputs,
            serving_telemetry,
        )

        artifact = make_tiny_artifact(str(tmp_path))
        engine = InferenceEngine(artifact, batch_buckets=(1, 2, 4))
        engine.warmup()
        assert any(v for v in engine._bucket_flops.values()), (
            "no bucket flops estimated"
        )
        outs, stats = engine.infer(sample_inputs(engine, 3))
        assert len(outs) == 3
        assert stats["flops"] and stats["flops"] > 0
        assert engine.flops_total == pytest.approx(stats["flops"])
        serve_dir = str(tmp_path / "serve")
        os.makedirs(serve_dir)
        telemetry = serving_telemetry(serve_dir, engine)
        batcher = Batcher(engine, telemetry=telemetry)
        reqs = [batcher.submit(x, timeout_s=10.0)
                for x in sample_inputs(engine, 8)]
        for r in reqs:
            r.wait(timeout=30.0)
        batcher.close()
        telemetry.close()
        rs = reader.read_stream(serve_dir)
        assert all(r.get("flops", 0) > 0 for r in rs.steps)
        sv = reader.summarize_run(rs)["serving"]
        assert sv["achieved_flops_per_s"] and sv["achieved_flops_per_s"] > 0


class TestTrainerEfficiencyE2E:
    def test_manifest_cost_and_mfu_trend(self, tmp_path):
        from pytorch_distributed_nn_tpu.observability import (
            promexport,
            reader,
        )
        from pytorch_distributed_nn_tpu.training.trainer import (
            TrainConfig,
            Trainer,
        )

        d = str(tmp_path)
        trainer = Trainer(TrainConfig(
            network="LeNet", dataset="MNIST", batch_size=16,
            num_workers=2, synthetic_size=32, max_steps=6,
            test_batch_size=16, train_dir=d,
            metrics_path=os.path.join(d, "telemetry.jsonl"),
        ))
        try:
            trainer.train()
        finally:
            trainer.close()
        rs = reader.read_stream(d)
        sc = (rs.manifest or {}).get("step_cost")
        assert sc and sc["flops"] > 0 and sc["source"] == "lowered"
        assert sc["peak_flops_per_s"] > 0
        assert sc["ici_bytes"] > 0  # 2-replica allreduce payload
        eff = reader.summarize_run(rs)["efficiency"]
        assert eff is not None
        assert eff["mfu"]["overall"] > 0
        assert eff["cost_gap_pct"] is not None
        text = promexport.render(reader.replay_registry(rs))
        assert "pdtn_mfu " in text
        assert "pdtn_hbm_util " in text
        assert "pdtn_ici_bytes_per_s " in text
        assert not promexport.validate_exposition(text)

    def test_sinkless_run_skips_accounting(self):
        """Unit-test-style runs (no telemetry sink) never pay the extra
        lowering — and never carry a step cost."""
        from pytorch_distributed_nn_tpu.training.trainer import (
            TrainConfig,
            Trainer,
        )

        trainer = Trainer(TrainConfig(
            network="LeNet", dataset="MNIST", batch_size=8,
            num_workers=1, synthetic_size=16, max_steps=1,
            test_batch_size=8,
        ))
        try:
            assert "step_cost" not in (trainer.telemetry.manifest or {})
        finally:
            trainer.close()


class TestXplaneFamilyTable:
    def test_family_summary_and_columns(self):
        from pytorch_distributed_nn_tpu.utils.profiling import (
            OpTime,
            family_summary,
            format_family_summary,
        )

        summary = {"/device:TPU:0": [
            OpTime("convert_reduce_fusion.1", 10.0, 5, 50.0),
            OpTime("multiply_add_fusion.2", 6.0, 5, 30.0),
            OpTime("fusion.3", 3.0, 9, 15.0),
            OpTime("copy.4", 1.0, 2, 5.0),
        ]}
        fams = family_summary(summary)
        assert fams["convert_reduce_fusion"]["total_ms"] == 10.0
        assert fams["elementwise"]["total_ms"] == 3.0
        assert fams["other"]["total_ms"] == 1.0
        assert sum(f["pct"] for f in fams.values()) == pytest.approx(100.0)
        cost = {"convert_reduce_fusion": {"flops": 1e9, "hbm_bytes": 1e7}}
        text = format_family_summary(fams, cost=cost, steps=5)
        # achieved = 1e9 * 5 / 0.010s = 5e11 = 0.5 TFLOP/s
        assert "TFLOP/s" in text
        assert "0.50" in text
        # without a cost the table renders ms/% only
        bare = format_family_summary(fams)
        assert "TFLOP/s" not in bare

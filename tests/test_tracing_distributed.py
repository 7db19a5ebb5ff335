"""Distributed tracing (docs/observability.md "Distributed tracing").

Covers the propagation layer (``TraceContext`` header parse/mint/child
lineage, the ``run_manifest`` env relay), the assembly half
(``reader.assemble_trace`` over the synthetic frontend fixture: hedge
branches, winner marking, orphan flagging, clock-offset recovery), the
``obs trace`` CLI, the submit-signature contract the serving tier relies
on, and the sweep orchestrator -> trial manifest lineage. The LIVE
cross-process path (real frontend + replicas under SIGKILL) is the chaos
``replica_loss --cases kill`` invariant.
"""

import glob
import inspect
import json
import os

import pytest

from pytorch_distributed_nn_tpu.observability import reader, tracing
from pytorch_distributed_nn_tpu.observability.core import run_manifest
from pytorch_distributed_nn_tpu.observability.obs_cli import main_obs
from pytorch_distributed_nn_tpu.observability.tracing import TraceContext


# ---------------------------------------------------------------------------
# context propagation
# ---------------------------------------------------------------------------


class TestTraceContext:
    def test_mint_and_header_roundtrip(self):
        ctx = tracing.new_trace_context()
        assert len(ctx.trace_id) == 32 and len(ctx.span_id) == 16
        assert ctx.parent_id is None  # a mint is the root
        parsed = TraceContext.from_header(ctx.header())
        assert parsed.trace_id == ctx.trace_id
        assert parsed.span_id == ctx.span_id
        # the parsed span is the CALLER's: no parent is recoverable
        assert parsed.parent_id is None
        assert ctx.header().endswith("-01")  # always sampled

    def test_child_keeps_trace_and_parents_to_caller(self):
        root = tracing.new_trace_context()
        child = root.child()
        grand = child.child()
        assert child.trace_id == grand.trace_id == root.trace_id
        assert child.parent_id == root.span_id
        assert grand.parent_id == child.span_id
        assert len({root.span_id, child.span_id, grand.span_id}) == 3
        # fields(): the record stamp — parent only when not the root
        assert root.fields() == {"trace": root.trace_id,
                                 "span": root.span_id}
        assert child.fields() == {"trace": root.trace_id,
                                  "span": child.span_id,
                                  "parent": root.span_id}

    def test_from_header_normalizes_case_and_whitespace(self):
        ctx = tracing.new_trace_context()
        raw = f"  {ctx.header().upper()}  "
        assert TraceContext.from_header(raw).trace_id == ctx.trace_id

    @pytest.mark.parametrize("bad", [
        "",
        "garbage",
        "01-" + "a" * 32 + "-" + "b" * 16 + "-01",   # wrong version
        "00-" + "a" * 31 + "-" + "b" * 16 + "-01",   # short trace
        "00-" + "a" * 32 + "-" + "b" * 15 + "-01",   # short span
        "00-" + "a" * 32 + "-" + "b" * 16,           # missing flags
        "00-" + "g" * 32 + "-" + "b" * 16 + "-01",   # non-hex
        "00-" + "a" * 32 + "-" + "b" * 16 + "-01-x",  # trailing junk
    ])
    def test_from_header_rejects_garbage(self, bad):
        with pytest.raises(ValueError):
            TraceContext.from_header(bad)


class TestManifestEnvRelay:
    def test_relayed_context_stamps_child_span(self, monkeypatch):
        root = tracing.new_trace_context()
        monkeypatch.setenv(tracing.TRACE_ENV, root.header())
        monkeypatch.setenv("PDTN_TRACE_VIA", "agent7")
        tc = run_manifest()["trace_context"]
        assert tc["trace"] == root.trace_id
        assert tc["parent"] == root.span_id  # child OF the relayed span
        assert tc["span"] != root.span_id
        assert tc["via"] == "agent7"

    def test_unset_and_malformed_env_stamp_nothing(self, monkeypatch):
        monkeypatch.delenv(tracing.TRACE_ENV, raising=False)
        assert "trace_context" not in run_manifest()
        monkeypatch.setenv(tracing.TRACE_ENV, "not-a-traceparent")
        assert "trace_context" not in run_manifest()


class TestSubmitContract:
    def test_every_serving_submit_accepts_the_trace_kwarg(self):
        """The HTTP layer passes ``trace=`` to whatever fronts the
        batcher — a proxy submit missing the kwarg crashes the handler
        thread mid-request (the bug chaos ``replica_loss`` caught in the
        router)."""
        from pytorch_distributed_nn_tpu.serving.batcher import Batcher
        from pytorch_distributed_nn_tpu.serving.generate.scheduler import (
            GenerateScheduler,
        )
        from pytorch_distributed_nn_tpu.serving.router import CanaryRouter

        for cls in (Batcher, CanaryRouter, GenerateScheduler):
            params = inspect.signature(cls.submit).parameters
            assert "trace" in params, f"{cls.__name__}.submit lost trace="
            assert params["trace"].default is None


# ---------------------------------------------------------------------------
# cross-process assembly (synthetic frontend fixture)
# ---------------------------------------------------------------------------


@pytest.fixture()
def frontend_run(tmp_path):
    run_dir = str(tmp_path / "fe")
    reader.write_synthetic_frontend_run(run_dir)
    return run_dir


class TestAssembleTrace:
    def test_plain_request_one_won_attempt_joined(self, frontend_run):
        asm = reader.assemble_trace(frontend_run, "fe-000001")
        assert asm["request_id"] == "fe-000001"
        assert asm["frontend"] is not None
        assert [a["outcome"] for a in asm["attempts"]] == ["won"]
        rrec = asm["attempts"][0]["replica_record"]
        assert rrec is not None and rrec["request_id"] == "fe-000001"
        assert rrec["parent"] == asm["attempts"][0]["span"]
        assert asm["orphans"] == []

    def test_hedge_assembles_as_competing_branches(self, frontend_run):
        asm = reader.assemble_trace(frontend_run, "fe-000002")
        tags = {a["tag"]: a for a in asm["attempts"]}
        assert set(tags) == {"first", "hedge"}
        assert tags["hedge"]["outcome"] == "won"
        assert tags["first"]["outcome"] == "discarded"
        # the LOSER's replica-side work still joins the tree: the
        # batcher served it after the frontend had already answered
        assert tags["first"]["replica_record"] is not None
        assert tags["first"]["replica_record"]["latency_ms"] == 45.0
        assert sum(a["outcome"] == "won" for a in asm["attempts"]) == 1

    def test_retry_keeps_failed_branch_with_annotation(self, frontend_run):
        asm = reader.assemble_trace(frontend_run, "fe-000003")
        tags = {a["tag"]: a for a in asm["attempts"]}
        assert tags["first"]["outcome"] == "failed"
        assert "breaker_open" in (tags["first"].get("annotations") or [])
        assert tags["first"]["replica_record"] is None
        assert tags["retry"]["outcome"] == "won"
        assert tags["retry"]["replica_record"] is not None

    def test_trace_id_and_request_id_resolve_identically(self, frontend_run):
        by_rid = reader.assemble_trace(frontend_run, "fe-000002")
        by_tid = reader.assemble_trace(frontend_run, by_rid["trace"])
        assert by_tid["request_id"] == "fe-000002"
        assert ([a["span"] for a in by_tid["attempts"]]
                == [a["span"] for a in by_rid["attempts"]])

    def test_clock_offset_recovered_from_shared_requests(self, frontend_run):
        asm = reader.assemble_trace(frontend_run, "fe-000002")
        offs = asm["clock_offsets"]
        r1 = [v for k, v in offs.items() if "r1" in k]
        assert r1, f"no r1 offset in {offs}"
        # the fixture runs r1's wall clock ~120.5 s fast; recovery must
        # land within a second (medians over shared request ids)
        assert abs(abs(r1[0]) - 120.5) < 1.0

    def test_orphan_span_flagged_never_dropped(self, frontend_run):
        asm = reader.assemble_trace(frontend_run, "fe-000004")
        assert len(asm["orphans"]) == 1
        orphan = asm["orphans"][0]
        # its record still appears in the joined set
        assert any(e["record"].get("request_id") == "fe-000004"
                   for e in asm["records"])
        assert orphan["parent"] not in {
            e["record"].get("span") for e in asm["records"]
        }

    def test_frontend_traces_carry_no_orphans(self, frontend_run):
        for rid in ("fe-000001", "fe-000002", "fe-000003"):
            assert reader.assemble_trace(frontend_run, rid)["orphans"] == []

    def test_unknown_key_raises(self, frontend_run):
        with pytest.raises(FileNotFoundError):
            reader.assemble_trace(frontend_run, "no-such-request")

    def test_preloaded_streams_short_circuit_discovery(self, frontend_run):
        streams = reader.load_trace_streams(frontend_run)
        asm = reader.assemble_trace(frontend_run, "fe-000001",
                                    streams=streams)
        assert [a["outcome"] for a in asm["attempts"]] == ["won"]

    def test_render_marks_winner_and_orphan_count(self, frontend_run):
        out = tracing.render_assembled_trace(
            reader.assemble_trace(frontend_run, "fe-000002"))
        assert "[WON]" in out
        assert "discarded" in out
        assert "hedged" in out
        assert "orphan spans: 0" in out
        out = tracing.render_assembled_trace(
            reader.assemble_trace(frontend_run, "fe-000004"))
        assert "orphan spans: 1" in out


# ---------------------------------------------------------------------------
# obs trace CLI
# ---------------------------------------------------------------------------


class TestObsTraceCLI:
    def test_accepts_any_directory_and_json(self, frontend_run, capsys):
        assert main_obs(["trace", frontend_run, "fe-000002",
                         "--json"]) == 0
        doc = json.loads(capsys.readouterr().out)
        assert doc["request_id"] == "fe-000002"
        assert len(doc["attempts"]) == 2

    def test_waterfall_render(self, frontend_run, capsys):
        assert main_obs(["trace", frontend_run, "fe-000003"]) == 0
        out = capsys.readouterr().out
        assert "retry" in out and "[WON]" in out and "breaker_open" in out

    def test_unknown_id_exits_2(self, frontend_run, capsys):
        assert main_obs(["trace", frontend_run, "nope"]) == 2

    def test_selftest_passes(self, capsys):
        assert main_obs(["trace", "--selftest"]) == 0


def test_sweep_trial_manifests_carry_trace_lineage(tmp_path):
    from pytorch_distributed_nn_tpu.experiments import (
        RunnerConfig,
        SweepRunner,
        SweepSpec,
        load_journal,
        trial_dir,
    )
    from pytorch_distributed_nn_tpu.experiments import journal as jr
    from pytorch_distributed_nn_tpu.experiments.runner import (
        synthetic_trial_main,
    )

    sdir = str(tmp_path / "sweep")
    result = SweepRunner(
        SweepSpec.parse("lr=0.5,0.05"),
        {"network": "SynthNet", "lr": 0.1, "batch_size": 32,
         "faults": None},
        RunnerConfig(sweep_dir=sdir, max_steps=4, concurrency=2,
                     retries=0),
        trial_main=synthetic_trial_main,
    ).run()
    assert result["failed"] == []

    # journal header: the sweep's ROOT context (no parent)
    with open(jr.journal_path(sdir)) as f:
        head = json.loads(f.readline())
    root = head["sweep"]["trace"]
    assert set(root) == {"trace", "span"}

    # every trial_start is a child span of the sweep root
    starts = {
        e["trial"]: e for e in load_journal(sdir).events
        if e.get("type") == "trial_start"
    }
    assert set(starts) == {0, 1}
    for ev in starts.values():
        assert ev["trace"] == root["trace"]
        assert ev["parent"] == root["span"]
    assert starts[0]["span"] != starts[1]["span"]

    # each trial process's manifest derives its own child under the
    # relayed attempt span: orchestrator -> trial, joined by stamps
    for trial, ev in starts.items():
        manifests = []
        pattern = os.path.join(trial_dir(sdir, trial), "**", "*.jsonl")
        for path in glob.glob(pattern, recursive=True):
            with open(path) as f:
                line = f.readline()
            if not line.strip():
                continue
            rec = json.loads(line)
            if rec.get("kind") == "manifest" and "trace_context" in rec:
                manifests.append(rec["trace_context"])
        assert manifests, f"trial {trial}: no manifest carries lineage"
        for tc in manifests:
            assert tc["trace"] == root["trace"]
            assert tc["parent"] == ev["span"]
            assert tc["span"] not in (root["span"], ev["span"])

"""The command itself: it refuses to measure without a TPU, and one
``--rehearse`` run of a tiny cell goes end to end through
``Trainer.train()`` in this process (no subprocess, CPU only)."""

import json
import os

import pytest

from benchmark import run

import bench_tree

LINE_KEYS = {"correct", "attempted", "failed", "metrics", "device"}


def test_without_a_tpu_it_exits_nonzero_and_prints_no_result(capsys):
    rc = run.main(["--workload", "resnet18_b4096", "--seed", "1",
                   "--seconds", "1", "--trace", "0"])
    out, err = capsys.readouterr()
    assert rc != 0 and out == ""
    assert "no TPU" in err


def test_an_unknown_cell_or_too_few_chips_is_refused(capsys, tmp_path):
    assert run.main(["--workload", "nope"]) != 0
    out, err = capsys.readouterr()
    assert out == "" and "unknown workload" in err
    # a tree without the program: nothing to measure, nothing printed
    root = bench_tree.add_cell(str(tmp_path))
    assert run.main(["--root", root, "--workload", "nope"]) != 0
    assert capsys.readouterr().out == ""


@pytest.fixture(scope="module")
def rehearsal(tmp_path_factory):
    """One traced ``--rehearse`` run of the throw-away cell that
    ``bench_tree`` adds to a copy of the tree as data."""
    root = bench_tree.add_cell(str(tmp_path_factory.mktemp("tree")))
    import contextlib
    import io
    import time

    from benchmark import correct

    checked = {}
    real_check = correct.check

    def spy(trainer, cell, seed, params, batch_stats):
        checked["at"] = time.monotonic()
        checked["params"] = params
        return real_check(trainer, cell, seed, params, batch_stats)

    buf = io.StringIO()
    correct.check = spy
    try:
        with contextlib.redirect_stdout(buf):
            rc = run.main(["--root", root, "--workload", "lenet_tiny",
                           "--seed", "3", "--seconds", "2", "--trace", "1",
                           "--rehearse"])
    finally:
        correct.check = real_check
    checked["root"] = root
    return rc, buf.getvalue(), checked


def test_rehearsal_ends_in_one_result_line_with_the_contracts_keys(rehearsal):
    rc, out, _ = rehearsal
    assert rc == 0
    line = json.loads(out.strip().splitlines()[-1])
    assert LINE_KEYS <= set(line)
    assert set(line["device"]) >= {"platform", "kind", "count",
                                   "memory_peak_bytes"}
    assert line["device"]["platform"] == "cpu"


def test_rehearsal_counts_and_checks_but_reports_no_number(rehearsal):
    line = json.loads(rehearsal[1].strip().splitlines()[-1])
    # traced: the cell's per-layer metrics, the added one among them, and
    # every value null — a CPU number is never a device metric
    assert "loss_at_close" in line["metrics"]
    assert "device_ms_per_step" in line["metrics"]
    assert all(m["value"] is None for m in line["metrics"].values())
    assert "busy_s" not in line["device"] and "breakdown" not in line
    # counts and correctness are real
    assert line["correct"] is True, line
    assert line["attempted"] >= 10 and line["failed"] == 0
    assert line["problems"] == []
    check = line["check"]
    assert check["ok"] and check["grad_rel_err"] < 1e-3
    assert check["loss_program"] == pytest.approx(check["loss_reference"],
                                                  rel=1e-4)


def test_the_reference_check_runs_after_the_window_on_the_first_weights(
        rehearsal):
    # the check is the benchmark's work: it is in neither setup_s nor the
    # window, and gets a host copy of the weights the run began with (the
    # step donates its state)
    import numpy as np
    import jax

    from benchmark import window

    checked = rehearsal[2]
    records = window.read_stream(os.path.join(
        checked["root"], ".benchmark_work", "lenet_tiny", "stream.jsonl"))
    last = max(r["mono"] for r in records if r.get("kind") == "step")
    assert checked["at"] > last
    leaves = jax.tree.leaves(checked["params"])
    assert leaves and all(isinstance(x, np.ndarray) for x in leaves)


def test_metric_line_leaves_out_what_a_reader_did_not_find():
    metrics = [{"name": "a", "unit": "ms"}, {"name": "b", "unit": "%"},
               {"name": "c", "unit": "s"}]
    values = {"a": 1.25, "b": None, "c": float("nan")}
    assert run.metric_line(metrics, values, null=False) == {
        "a": {"value": 1.25, "unit": "ms"}}
    assert run.metric_line(metrics, values, null=True) == {
        n: {"value": None, "unit": u}
        for n, u in (("a", "ms"), ("b", "%"), ("c", "s"))}

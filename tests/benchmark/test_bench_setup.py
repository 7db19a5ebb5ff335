"""Set-up's per-layer metrics (benchmark/readers/setup.py): a hand-made
stream whose every number is worked out below, a stream from a program
older than the set-up spans, and one ``--rehearse`` run of the tiny cell.

The hand stream, on the monotonic clock, process start at 100 s:

    100 - 105   imports and backend            (phases_s: setup_s 50 less
                                                trainer_built 31 less
                                                warm_up_to_window 14)
    105 - 106   the driver, before the trainer (residual)
    106 - 136   setup/init: model 106.5-120, step_build 120-121,
                data 121-129, step_cost 129-131
    136 - 136.5 device_get of the first weights (residual)
    136.5-146.5 setup/first_step of call 1 (step 1)
    146.5-148   the rest of call 1: steps 2-4, step 4 closes at 148;
                a program compiled outside every span at step 3 (147)
    148 - 148.5 between the calls (residual)
    148.5-150   call 2 from its first iteration (148.5-148.6, step 5) to
                the opening stamp: step 6 closes at 150
    150 - 156   the window; a program fetched at step 7 (150.5)
"""

import json
import os
import types

import pytest

from benchmark import manifest, window
from benchmark.readers import setup

import bench_tree

NAMES = ("setup_init_s", "setup_first_step_s", "setup_compile_s",
         "setup_programs_compiled")


def _span(name, parent, mono0, mono1, trace=0.0, lower=0.0, backend=0.0,
          compiled=0, cached=0):
    return {"name": name, "parent": parent, "mono0": mono0, "mono1": mono1,
            "seconds": mono1 - mono0, "fetch_s": 0.0,
            "compile_s": {"trace": trace, "lower": lower, "backend": backend},
            "programs": {"compiled": compiled, "cached": cached}}


def _event(etype, mono, **fields):
    return {"kind": "event", "type": etype, "mono": mono, **fields}


SETUP_EVENTS = [
    _event("setup", 136.0, step=None, spans=[
        _span("setup/model", "setup/init", 106.5, 120.0, 0.5, 1.0, 2.0, 3, 40),
        _span("setup/step_build", "setup/init", 120.0, 121.0),
        _span("setup/data", "setup/init", 121.0, 129.0, backend=0.25, cached=2),
        _span("setup/step_cost", "setup/init", 129.0, 131.0, 1.0, 0.5),
        _span("setup/init", None, 106.0, 136.0),
    ], slowest=[{"fun_name": "init", "seconds": 2.0, "source": "cached",
                 "span": "setup/model"}]),
    _event("setup", 146.5, step=1, spans=[
        _span("setup/first_step", "train/step", 136.5, 146.5, 0.75, 0.5,
              7.0, 1, 2),
    ], slowest=[{"fun_name": "train_step", "seconds": 8.0,
                 "source": "compiled", "span": "setup/first_step"}]),
    _event("compile", 147.0, step=3, fun_name="late_in_warm_up",
           source="compiled", fetch_s=0.0,
           compile_s={"trace": 0.125, "lower": 0.125, "backend": 0.25}),
    _event("setup", 148.6, step=5, spans=[
        _span("setup/first_step", "train/step", 148.5, 148.6),
    ], slowest=[]),
    _event("compile", 150.5, step=7, fun_name="in_window", source="cached",
           fetch_s=0.5, compile_s={"trace": 1.0, "lower": 1.0, "backend": 1.0}),
]
STEPS = [{"kind": "step", "step": s, "mono": m} for s, m in (
    (1, 146.6), (2, 146.6), (3, 148.0), (4, 148.0), (5, 150.0), (6, 150.0),
    (7, 152.0), (8, 152.0), (9, 154.0), (10, 154.0), (11, 156.0), (12, 156.0))]


def _ctx(records):
    w = window.Window(first_step=6, last_step=12, log_every=2,
                      global_batch=8, opened=150.0, closed=156.0)
    return types.SimpleNamespace(notes={}, result={
        "records": records, "window": w,
        "end_to_end": {"setup_s": w.opened - 100.0},
        "phases_s": {"trainer_built": 136.0 - 105.0,
                     "warm_up_to_window": w.opened - 136.0},
    })


def test_readers_on_the_hand_stream():
    ctx = _ctx(STEPS + SETUP_EVENTS)
    assert setup.setup_init_s(ctx) == pytest.approx(30.0)
    # the first call's first iteration, not the second's
    assert setup.setup_first_step_s(ctx) == pytest.approx(10.0)
    # every span's stages + the compile event before the opening stamp;
    # the one inside the window is not set-up
    assert setup.setup_compile_s(ctx) == pytest.approx(
        3.5 + 0.25 + 1.5 + 8.25 + 0.5)
    assert setup.setup_programs_compiled(ctx) == 3 + 1 + 1


def test_the_note_cuts_setup_s_at_the_spans():
    ctx = _ctx(STEPS + SETUP_EVENTS)
    setup.setup_init_s(ctx)
    note = ctx.notes["setup"]
    assert set(note["spans"]) == {
        "setup/init", "setup/model", "setup/step_build", "setup/data",
        "setup/step_cost", "setup/first_step", "setup/first_step@5"}
    assert note["spans"]["setup/model"] == {
        "s": 13.5, "compile_s": 3.5, "fetch_s": 0.0, "compiled": 3,
        "cached": 40}
    assert [f["fun_name"] for f in note["slowest"]] == ["train_step", "init"]
    assert note["compile_events"] == {
        "before_window": 1, "after_window": 0,
        "in_window": [{"step": 7, "fun_name": "in_window",
                       "source": "cached"}]}
    cut = note["setup_s"]
    expected = {"total": 50.0, "imports_and_backend": 5.0, "init": 30.0,
                "first_step": 10.0, "warm_up_windows": 1.5, "ramp": 1.5,
                "residual": 2.0, "residual_pct": 4.0}
    assert {k: cut[k] for k in expected} == pytest.approx(expected)
    assert cut["residual_parts"] == pytest.approx(
        {"before_init": 1.0, "init_to_first_step": 0.5, "between_calls": 0.5})


def test_a_program_older_than_the_spans_reads_nothing():
    ctx = _ctx(STEPS)
    assert [getattr(setup, name)(ctx) for name in NAMES] == [None] * 4
    assert ctx.notes == {}


# the expert cells' own tests (test_bench_lfm2.py, test_bench_smallthinker.py)
# hold their cell's metrics to the end of its list, so the set-up metrics
# list the other four cells until a benchmark PR relaxes that
LISTED = ["resnet18_b4096", "resnet18_b4096_ckpt", "bert_base_b32_L512",
          "bert_base_dp4_b128_L512"]


@pytest.mark.parametrize("cell_name", LISTED)
def test_the_listed_cells_report_the_four_set_up_metrics(cell_name):
    bench = manifest.load()
    declared = {m["name"]: m for m in bench["per_layer"]}
    for name in NAMES:
        assert declared[name]["layer"] == "setup"
        assert declared[name]["moves"] == "setup_s"
        assert declared[name]["workloads"] == LISTED
    cell = manifest.resolve(cell_name, bench=bench)
    readers = {m["name"]: m["reader"] for m in cell.per_layer}
    assert {readers[n] for n in NAMES} == {
        f"benchmark/readers/setup.py:{n}" for n in NAMES}


def test_a_rehearsal_line_names_the_four_and_leaves_the_split(tmp_path):
    import contextlib
    import io

    from benchmark import run

    root = bench_tree.add_cell(str(tmp_path))
    path = os.path.join(root, "BENCHMARK.json")
    with open(path) as f:
        bench = json.load(f)
    for m in bench["per_layer"]:
        if m["name"] in NAMES:
            m["workloads"].append("lenet_tiny")
    with open(path, "w") as f:
        json.dump(bench, f)
    buf = io.StringIO()
    with contextlib.redirect_stdout(buf):
        rc = run.main(["--root", root, "--workload", "lenet_tiny", "--seed",
                       "2147483999", "--seconds", "2", "--trace", "1",
                       "--rehearse"])
    assert rc == 0
    line = json.loads(buf.getvalue().strip().splitlines()[-1])
    for name in NAMES:
        assert line["metrics"][name]["value"] is None   # no CPU number
    note = line["notes"]["setup"]
    assert {"setup/init", "setup/first_step"} <= set(note["spans"])
    assert note["compile_events"]["in_window"] == []
    cut = note["setup_s"]
    assert cut["residual"] == pytest.approx(
        sum(cut["residual_parts"].values()), abs=1e-6)
    stream = os.path.join(root, ".benchmark_work", "lenet_tiny", "stream.jsonl")
    steps = [e.get("step") for e in window.read_stream(stream)
             if e.get("type") == "setup"]
    assert steps[0] is None and len(steps) == 3         # init + two calls

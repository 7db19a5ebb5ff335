"""Device idle time attributed to the program's spans (benchmark/spans.py
and its readers): a hand-made trace whose every number is worked out in
its header, the four-chip fixture under hand-made spans, and a trace of
the checkpoint cell cut from a run on the TPU v5e (PR 24)."""

import os
import types

import pytest

from benchmark import spans, trace
from benchmark.readers import spans as readers

FIXTURES = os.path.join(os.path.dirname(os.path.abspath(__file__)), "fixtures")
HAND = os.path.join(FIXTURES, "hand_spans.textproto")
V5E = os.path.join(FIXTURES, "resnet18_ckpt_spans_v5e.textproto")
US = 1e3   # ns


@pytest.fixture(scope="module")
def hand():
    return trace.load(HAND)


@pytest.fixture(scope="module")
def hand_summary(hand):
    return spans.summarize(hand)


# per window (three steps), mean of the two chips, in us: the header of
# hand_spans.textproto works each one out
HAND_US = {
    "unattributed": 20, "train/flush_publish": 37.5, "train/flush": 17.5,
    "train/step": 17, "train/data": 8, "input/produce": 60,
    "train/dispatch": 12.5, "ckpt/fetch": 17.5, "ckpt/write": 20,
    "ckpt/serialize": 130, "ckpt/compress": 52.5, "train/flush_fetch": 87.5,
}


@pytest.mark.parametrize("name", sorted(HAND_US))
def test_hand_trace_idle_by_span(hand_summary, name):
    per_step_ms = hand_summary["ms_per_step"][name]
    assert per_step_ms * 3 * 1e3 == pytest.approx(HAND_US[name])


def test_hand_trace_partition_is_whole(hand_summary):
    s = hand_summary
    assert s["steps"] == 3 and s["saves"] == 1
    assert set(s["ms_per_step"]) == set(HAND_US)
    assert s["idle_ms_per_step"] * 3 * 1e3 == pytest.approx(480)
    assert sum(s["ms_per_step"].values()) == pytest.approx(s["idle_ms_per_step"])
    # the same window and idle time as the device's own reduction
    device = trace.summarize(trace.load(HAND))
    assert device["steps"] == 3
    assert (device["window_s"] - device["busy_s"]) * 1e3 / 3 == pytest.approx(
        s["idle_ms_per_step"])


# chip 0's gaps, one for each rule that can decide (and the open span)
GAPS = {
    "rule 4: no span is open": ((1400, 1420), {"unattributed": 20}),
    "rule 1, rule 3 around it": ((1900, 2000), {
        "train/flush_publish": 40, "train/flush": 5, "train/step": 7,
        "train/data": 8, "input/produce": 30, "train/dispatch": 10}),
    "rule 2 under train/dispatch": ((2400, 2600), {
        "ckpt/fetch": 20, "ckpt/write": 20, "ckpt/serialize": 130,
        "ckpt/compress": 30}),
    "rule 1 beats 2, 2 beats 3": ((2950, 3000), {
        "input/produce": 30, "ckpt/compress": 20}),
    "rule 3": ((3700, 3800), {"train/flush_fetch": 90, "train/flush": 10}),
    "rule 3 under a span left open": ((3990, 4000), {"train/step": 10}),
}


@pytest.mark.parametrize("case", sorted(GAPS))
def test_each_gap_goes_to_one_rule_and_sums_to_its_length(hand, case):
    (a, b), expect = GAPS[case]
    loop, writers = spans.threads(hand)
    assert len(writers) == 1
    by = spans.partition([(a * US, b * US)], loop, writers)
    assert sum(by.values()) == pytest.approx((b - a) * US, abs=1)   # 1 ns
    assert {k: v for k, v in by.items() if v} == {
        k: pytest.approx(v * US, abs=1) for k, v in expect.items()}


def test_nested_spans_give_self_time():
    nested = [(0, 100, "train/step"), (10, 40, "train/data"),
              (15, 30, "input/produce"), (50, 90, "train/dispatch"),
              (100, 160, "train/step")]
    assert spans.innermost(nested) == [
        (0, 10, "train/step"), (10, 15, "train/data"),
        (15, 30, "input/produce"), (30, 40, "train/data"),
        (40, 50, "train/step"), (50, 90, "train/dispatch"),
        (90, 100, "train/step"), (100, 160, "train/step")]
    # a child the clock lets outlive its parent is cut to it
    assert spans.innermost([(0, 10, "train/flush"), (5, 12, "train/flush_fetch")]
                           ) == [(0, 5, "train/flush"), (5, 10, "train/flush_fetch")]


def test_hand_trace_span_table_and_longest_gaps(hand_summary):
    table = hand_summary["spans"]
    assert table["ckpt/write"] == {
        "calls": 1, "total_ms": pytest.approx(1.010),
        "self_ms": pytest.approx(0.040)}      # 1010 - 110 - 130 - 630 - 100
    assert table["train/step"]["calls"] == 4    # the open one included
    assert table["train/step"]["total_ms"] == pytest.approx(2.550)
    gaps = hand_summary["longest_gaps"]
    assert [round(g["ms"] * 1e3) for g in gaps] == [200, 100, 100, 50, 20]
    assert [(g["loop_span"], g["writer_span"]) for g in gaps] == [
        ("train/dispatch", "ckpt/serialize"), ("train/step", None),
        ("train/flush_fetch", None), ("input/produce", "ckpt/compress"),
        (None, None)]


def _events(*rows):
    return [trace.Event(text, a, b) for text, a, b in rows]


def test_a_span_the_trace_cut_off_is_read_from_its_begin_event():
    thread = _events(
        ("ckpt/write:begin", 99, 100), ("ckpt/fetch:begin", 101, 102),
        ("ckpt/fetch", 102, 150), ("ckpt/serialize:begin", 151, 152),
        ("$checkpoint.py:190 save_checkpoint", 150, 400),
        # an earlier write that closed: its begin is not the last
        ("ckpt/write:begin", 9, 10), ("ckpt/write", 10, 50),
    )
    assert sorted(spans.span_events(thread)) == [
        (10, 50, "ckpt/write"), (99, spans.OPEN, "ckpt/write"),
        (102, 150, "ckpt/fetch"), (151, spans.OPEN, "ckpt/serialize")]
    # ... and its idle goes to the innermost open one
    loop = [(0, 400, "train/step"), (0, 400, "train/dispatch")]
    by = spans.partition([(120, 130), (300, 320)], loop,
                         [spans.span_events(thread)])
    assert by == {"ckpt/fetch": 10, "ckpt/serialize": 20, "unattributed": 0}


def test_one_chip_of_two_reads_that_chips_numbers(hand):
    one = trace.Trace({"/device:TPU:1": hand.chips["/device:TPU:1"]}, hand.host)
    by = spans.summarize(one)["ms_per_step"]
    assert by["train/flush_publish"] * 3e3 == pytest.approx(35)
    assert by["train/dispatch"] * 3e3 == pytest.approx(15)
    assert by["ckpt/compress"] * 3e3 == pytest.approx(55)


def test_four_chips_are_averaged():
    """The four-chip v5e fixture (PR 22) under one hand-made span over its
    whole window: all idle time goes to it, and the mean over the chips is
    the one the device's own reduction gives."""
    dp4 = trace.load(os.path.join(FIXTURES, "bert_base_dp4_step_v5e.textproto"))
    host = {"python3#0": _events(("train/step", 0, 1e12),
                                 ("train/dispatch", 0, 1e12))}
    s = spans.summarize(trace.Trace(dp4.chips, host))
    device = trace.summarize(dp4)
    assert s["steps"] == device["steps"] == 1 and len(device["chips"]) == 4
    assert set(s["ms_per_step"]) == {"train/dispatch", "unattributed"}
    assert s["ms_per_step"]["unattributed"] == pytest.approx(0, abs=1e-9)
    assert s["ms_per_step"]["train/dispatch"] == pytest.approx(
        1e3 * (device["window_s"] - device["busy_s"]))


def _ctx(xplane, steps=()):
    window = types.SimpleNamespace(steps=list(steps), global_batch=4096)
    return types.SimpleNamespace(
        result={"xplane": xplane, "window": window}, notes={})


SPAN_READERS = [readers.input_exposed_ms_per_step,
                readers.flush_exposed_ms_per_step,
                readers.ckpt_exposed_ms_per_save,
                readers.idle_unattributed_pct]


@pytest.mark.parametrize("xplane", [
    os.path.join(FIXTURES, "resnet18_ckpt_save_v5e.textproto"),  # PR 22: no spans
    None,                                                       # no trace
])
def test_without_spans_every_reader_returns_nothing(xplane):
    if xplane:
        assert spans.reduce(xplane) is None
    ctx = _ctx(xplane)
    assert [r(ctx) for r in SPAN_READERS] == [None] * 4
    assert ctx.notes == {"idle_by_span": None}
    assert readers.wall_clock_samples_per_s(_ctx(None, [{"step_time": 0.1}])) is None


def test_a_trace_without_device_planes_or_a_whole_step_reduces_to_nothing(hand):
    assert spans.summarize(trace.Trace({}, hand.host)) is None      # CPU
    chip = hand.chips["/device:TPU:0"]
    short = {trace.MODULES_LINE: chip[trace.MODULES_LINE][:2],
             trace.OPS_LINE: chip[trace.OPS_LINE]}
    assert spans.summarize(trace.Trace({"/device:TPU:0": short}, hand.host)) is None


def test_readers_on_the_hand_trace():
    ctx = _ctx(HAND, [{"wall_ms": 140.0}, {"wall_ms": 142.0}, {"step_time": 0.08}])
    assert readers.input_exposed_ms_per_step(ctx) == pytest.approx(0.060 / 3)
    assert readers.flush_exposed_ms_per_step(ctx) == pytest.approx(0.0375 / 3)
    assert readers.idle_unattributed_pct(ctx) == pytest.approx(100 * 20 / 480)
    # one save in three steps: fetch 17.5 + write 20 + serialize 130 + compress 52.5
    assert readers.ckpt_exposed_ms_per_save(ctx) == pytest.approx(0.220)
    assert ctx.notes["ckpt_exposed_ms_per_save"]["ckpt/serialize"] == pytest.approx(0.130)
    assert set(ctx.notes["idle_by_span"]) == {
        "steps", "idle_ms_per_step", "ms_per_step", "saves", "spans",
        "longest_gaps"}
    assert readers.wall_clock_samples_per_s(ctx) == pytest.approx(4096 / 0.141)


def test_chip_trace_of_a_save_splits_its_idle_by_writer_span():
    """Twelve ResNet-18 b4096 steps around an async checkpoint, cut from a
    traced run of the checkpoint cell on the TPU v5e (PR 24, ops of 2 us
    and more): the device idles 215 ms while the loop's thread sits in
    ``train/dispatch`` and the writer holds the interpreter lock, first in
    ``ckpt/serialize``, then at the start of ``ckpt/compress`` — a span
    the trace's end cut off, like its parent ``ckpt/write``, and read from
    its ``:begin`` event."""
    t = trace.load(V5E)
    s = spans.summarize(t)
    assert s["steps"] == 12 and s["saves"] == 1
    device = trace.summarize(t)
    idle_ms = 1e3 * (device["window_s"] - device["busy_s"])
    assert s["idle_ms_per_step"] * 12 == pytest.approx(idle_ms, rel=1e-9)
    by = {name: ms * 12 for name, ms in s["ms_per_step"].items()}   # per save
    assert sum(by.values()) == pytest.approx(idle_ms, rel=1e-9)
    assert by["unattributed"] < 0.05 * idle_ms      # >= 95 % is attributed
    assert by["unattributed"] == pytest.approx(0.019, abs=0.001)
    assert by["ckpt/serialize"] == pytest.approx(119.5, abs=0.1)
    assert by["ckpt/compress"] == pytest.approx(96.6, abs=0.1)
    assert by["ckpt/fetch"] == pytest.approx(22.1, abs=0.1)
    assert by["ckpt/snapshot"] == pytest.approx(7.3, abs=0.1)
    assert by["ckpt/write"] < 1 and "ckpt/file" not in by
    ckpt = sum(ms for name, ms in by.items() if name.startswith("ckpt/"))
    assert ckpt == pytest.approx(246.5, abs=0.5) and ckpt > 0.96 * idle_ms
    longest = s["longest_gaps"][0]
    assert longest["ms"] == pytest.approx(215.5, abs=0.1)
    assert (longest["loop_span"], longest["writer_span"]) == (
        "train/dispatch", "ckpt/serialize")
    assert all(g["loop_span"] for g in s["longest_gaps"])
    # open when the trace stopped: no closed event, one :begin each
    _, writers = spans.threads(t)
    cut_off = {name for w in writers for _, end, name in w if end == spans.OPEN}
    assert cut_off == {"ckpt/write", "ckpt/compress"}
    assert s["spans"]["ckpt/serialize"]["total_ms"] == pytest.approx(259.1, abs=0.1)

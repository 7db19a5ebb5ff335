"""The trace reduction: interval arithmetic, the instruction grammar, a
hand-made trace whose every number is worked out in its header, and three
traces cut from runs on the TPU v5e (PR 22) that pin how libtpu 0.0.34
names things."""

import os

import pytest

from benchmark import manifest, trace

FIXTURES = os.path.join(os.path.dirname(os.path.abspath(__file__)), "fixtures")
KERNELS = manifest.resolve("bert_base_b32_L512").config["kernels"]
US = 1e-6


def fixture(name):
    return trace.load(os.path.join(FIXTURES, name))


def test_interval_arithmetic():
    merged = trace.union([(5, 7), (0, 2), (1, 3), (7, 9), (20, 20)])
    assert merged == [(0, 3), (5, 9)]
    assert trace.length(merged) == 7
    assert trace.clip(merged, 2, 6) == [(2, 3), (5, 6)]
    assert trace.subtract([(0, 10)], merged) == [(3, 5), (9, 10)]
    assert trace.subtract(merged, [(0, 10)]) == []
    assert trace.subtract([(0, 4), (6, 8)], [(1, 2), (3, 7)]) == [
        (0, 1), (2, 3), (7, 8)]


@pytest.mark.parametrize("text, expect", [
    ('%fusion.593 = bf16[32,12,512,64]{2,3,1,0:T(8,128)(2,1)S(1)} fusion('
     'bf16[32,512,768,1]{2,1,3,0:T(8,128)(2,1)S(1)} %bitcast.2333, '
     'f32[768,12,64]{0,2,1:T(8,128)S(1)} %custom-call.129), kind=kOutput, '
     'calls=%fused_computation.735',
     ("fusion.593", "fusion", 1, 2, "kOutput", "", "fusion:kOutput", None)),
    ('%attn.36 = (bf16[384,512,64]{2,1,0:T(8,128)(2,1)S(1)}, '
     'f32[384,512,1]{2,1,0:T(8,128)}) custom-call(bf16[384,512,64]{2,1,0} '
     '%bitcast.2483, bf16[384,512,64]{2,1,0} %bitcast.2477, '
     'bf16[384,512,64]{2,1,0} %bitcast.2480, f32[384,1,512]{2,1,0} '
     '%broadcast.133), custom_call_target="tpu_custom_call", '
     'operand_layout_constraints={bf16[384,512,64]{2,1,0}}',
     ("attn.36", "custom-call", 2, 4, "", "tpu_custom_call", "attn", None)),
    ('%psum.1428 = f32[30522,768]{1,0:T(8,128)} all-reduce(f32[30522,768]'
     '{1,0:T(8,128)} %convert_add_fusion.1), channel_id=1, '
     'replica_groups={{0,1,2,3}}, to_apply=%region_285.288',
     ("psum.1428", "all-reduce", 1, 1, "", "", "psum", "sync")),
    ('%all-gather-start.2 = (f32[4]{0}, f32[8]{0}) all-gather-start('
     'f32[4]{0} %w), dimensions={0}',
     ("all-gather-start.2", "all-gather-start", 2, 1, "", "",
      "all-gather-start", "start")),
    ('%copy-done.1003 = f32[3072,768]{1,0:T(8,128)} copy-done((f32[3072,768]'
     '{1,0:T(8,128)}, f32[3072,768]{1,0:T(8,128)S(1)}, u32[]{:S(2)}) '
     '%copy-start.1003)',
     ("copy-done.1003", "copy-done", 1, 1, "", "", "copy-done", None)),
    ("fusion.7", ("fusion.7", "fusion", 1, 0, "", "", "fusion", None)),
])
def test_instruction_text_is_parsed(text, expect):
    op = trace.parse_op(text)
    assert (op.name, op.opcode, op.outputs, op.operands, op.kind, op.target,
            op.group, op.collective) == expect


def test_kernels_are_told_apart_by_name_and_arity():
    def op(name, operands, outputs):
        return trace.Op(name, "custom-call", outputs, operands,
                        target=trace.MOSAIC_TARGET)

    classify = lambda *a: trace.classify_kernel(op(*a), KERNELS)  # noqa: E731
    assert classify("attn.36", 4, 2) == ("flash_attention", "fwd")
    assert classify("attn.2", 7, 1) == ("flash_attention", "dq")
    assert classify("attn", 7, 2) == ("flash_attention", "dkv")
    assert classify("ln_mlp.24", 3, 3) == ("fused_ln", "fwd")
    assert classify("mlm_ln.1", 5, 3) == ("fused_ln", "bwd")
    assert classify("ln_final", 9, 1) == ("fused_ln", "unknown")
    assert classify("quantize.3", 2, 2) == ("unknown", "unknown")
    assert trace.classify_kernel(op("attn.1", 4, 2), None) == (
        "unknown", "unknown")


@pytest.fixture(scope="module")
def hand():
    return trace.summarize(fixture("hand.textproto"), kernels=KERNELS)


def test_hand_trace_window_busy_and_idle(hand):
    # the first start of the step program is the profiler's clipping: the
    # window runs from the second (1000 us) to the last (3000 us)
    assert hand["step_program"] == "jit__lambda(111)"
    assert hand["steps"] == 2
    assert hand["window_s"] == pytest.approx(2000 * US)
    # a step is busy 0-450 and 500-900; chip 0 also runs 10 us of another
    # program in the second gap
    chip0, chip1 = hand["chips"]
    assert chip0["busy_s"] == pytest.approx(2 * (450 + 400 + 10) * US)
    assert chip1["busy_s"] == pytest.approx(2 * (450 + 400) * US)
    assert hand["busy_s"] == pytest.approx(1710 * US)
    idle_share = 1 - hand["busy_s"] / hand["window_s"]
    assert idle_share == pytest.approx(0.145)


def test_hand_trace_collectives_in_flight_and_exposed(hand):
    # per step: all-reduce.1 500-700, psum.5 700-720 (an all-reduce under
    # its primitive's name), all-gather 720-850 in flight (async line);
    # only multiply_add_fusion.3, 721-800, overlaps them
    c = hand["collectives"]
    assert c["count"] == 6
    assert c["in_flight_s"] == pytest.approx(2 * 350 * US)
    assert c["exposed_s"] == pytest.approx(2 * (350 - 79) * US)


def test_hand_trace_kernel_sums(hand):
    rows = {(k["family"], k["kind"]): k for k in hand["kernels"]}
    assert rows[("flash_attention", "fwd")]["seconds"] == pytest.approx(200 * US)
    assert rows[("flash_attention", "dq")]["seconds"] == pytest.approx(60 * US)
    assert rows[("flash_attention", "dkv")]["seconds"] == pytest.approx(40 * US)
    assert rows[("fused_ln", "fwd")]["seconds"] == pytest.approx(100 * US)
    assert all(k["calls"] == 2 for k in hand["kernels"])
    assert hand["device_ops"][0] == ["fusion:kOutput", pytest.approx(600 * US)]
    assert ["all-reduce", pytest.approx(400 * US)] in hand["device_ops"]


def test_hand_trace_idle_gaps_are_named_by_the_host(hand):
    assert [g[0] for g in hand["idle_gaps"]] == [
        "loader.py:400 next_indices",      # 1450-1500
        "trainer.py:1139 flush",           # 1900-1950
        # the step loop's thread is between frames: the interpreter lock
        # is with the thread inside msgpack
        "other thread in __init__.py:30 packb",   # 2450-2500
        "trainer.py:1139 flush",           # 2900-2950
        "core.py:493 log_step",            # 1960-2000
        "trainer.py:1139 flush",           # 2960-3000
    ]
    assert [g[1] for g in hand["idle_gaps"]] == pytest.approx(
        [50 * US] * 4 + [40 * US] * 2)


def test_a_trace_without_a_device_plane_reduces_to_nothing():
    assert trace.summarize(trace.Trace({}, {})) is None


def test_chip_trace_of_a_save_names_the_writer_thread():
    """Three ResNet-18 b4096 steps around an async checkpoint, cut from a
    run on the TPU v5e (PR 22): the device idles 236 ms while the step
    loop's thread waits for the interpreter lock the writer holds inside
    msgpack. Also pins the names libtpu gives planes, lines and ops."""
    s = trace.summarize(fixture("resnet18_ckpt_save_v5e.textproto"))
    assert s["steps"] == 3
    assert 1e3 * s["window_s"] == pytest.approx(699.17, abs=0.01)
    # three back-to-back steps would be 3 x 140.7 ms busy
    assert 1e3 * s["busy_s"] / 3 == pytest.approx(140.7, abs=0.1)
    what, seconds = s["idle_gaps"][0]
    assert what == "other thread in __init__.py:30 packb"
    assert 1e3 * seconds == pytest.approx(235.7, abs=0.1)
    assert s["collectives"]["count"] == 0 and s["kernels"] == []
    top = dict(s["device_ops"])
    assert set(top) >= {"convert_reduce_fusion", "multiply_add_fusion",
                        "fusion:kCustom", "fusion:kLoop", "fusion:kOutput"}


def test_chip_trace_of_a_bert_step_finds_the_88_mosaic_calls():
    """One BERT-base b32 x L512 step (ops of 10 us and more) cut from a
    run on the TPU v5e (PR 22): 36 flash-attention and 52 LayerNorm calls,
    told apart by the configuration's ``kernels`` block."""
    s = trace.summarize(fixture("bert_base_step_v5e.textproto"), kernels=KERNELS)
    assert s["steps"] == 1
    assert 1e3 * s["window_s"] == pytest.approx(138.36, abs=0.01)
    calls = {(k["family"], k["kind"]): k["calls"] for k in s["kernels"]}
    assert calls == {
        ("flash_attention", "fwd"): 12, ("flash_attention", "dq"): 12,
        ("flash_attention", "dkv"): 12,
        ("fused_ln", "fwd"): 26, ("fused_ln", "bwd"): 26,
    }
    ms = {(k["family"], k["kind"]): 1e3 * k["seconds"] / k["calls"]
          for k in s["kernels"]}
    assert ms[("flash_attention", "fwd")] == pytest.approx(0.4355, abs=1e-3)
    assert ms[("flash_attention", "dkv")] == pytest.approx(0.6484, abs=1e-3)
    assert ms[("fused_ln", "fwd")] == pytest.approx(0.0662, abs=1e-3)


def test_every_chip_is_cut_to_the_same_number_of_steps():
    """The trace's edges can catch one start more on one chip than on
    another (seen on the four-chip host, PR 22: 19, 18, 19, 19)."""
    def chip(n_starts, busy_us):
        modules = [trace.Event("jit_step(1)", 1000.0 * k, 1000.0 * k + 900)
                   for k in range(n_starts)]
        ops = [trace.Event("fusion.1", 1000.0 * k, 1000.0 * k + busy_us,
                           trace.parse_op("fusion.1"))
               for k in range(n_starts)]
        return {trace.MODULES_LINE: modules, trace.OPS_LINE: ops}

    s = trace.summarize(trace.Trace(
        {"/device:TPU:0": chip(6, 800), "/device:TPU:1": chip(5, 600)}, {}))
    # chip 1 holds 5 starts: first left out, 3 whole steps; chip 0 is cut
    # to 3 as well
    assert s["steps"] == 3 and [c["steps"] for c in s["chips"]] == [3, 3]
    assert s["window_s"] == pytest.approx(3000e-9)
    assert s["busy_s"] == pytest.approx((3 * 800 + 3 * 600) / 2 * 1e-9)


def test_chip_trace_of_a_four_chip_step_reads_the_all_reduces():
    """One BERT-base dp=4 step (ops of 250 us and more) cut from a run on
    a four-chip v5e host (PR 22): on every chip four synchronous
    all-reduces — three tuple-combined ones and the embedding gradient,
    named ``psum`` after its primitive — 7.6 ms in flight, none of it
    behind another op."""
    s = trace.summarize(fixture("bert_base_dp4_step_v5e.textproto"),
                        kernels=KERNELS)
    assert len(s["chips"]) == 4 and s["steps"] == 1
    assert [c["collective_count"] for c in s["chips"]] == [4] * 4
    c = s["collectives"]
    assert 1e3 * c["in_flight_s"] == pytest.approx(7.598, abs=0.01)
    assert c["exposed_s"] == pytest.approx(c["in_flight_s"])
    groups = dict(s["device_ops"])
    assert "all-reduce" in groups
    calls = {(k["family"], k["kind"]): k["calls"] for k in s["kernels"]}
    assert calls == {("flash_attention", "fwd"): 12,
                     ("flash_attention", "dq"): 12,
                     ("flash_attention", "dkv"): 12}

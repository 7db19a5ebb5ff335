"""The SmallThinker-21BA3B share (configuration ``smallthinker_21b_a3b_ep8``,
cell ``smallthinker_21b_a3b_ep8_b1_L16384``): its parameters, FLOPs and
kernel costs against counts made by hand, its file against the preset the
program builds and against the catalog's row, its readers on a made-up
context, and one ``--rehearse`` run on the CPU: the harness takes the cell
as files and entries."""

import contextlib
import io
import json
import types

import pytest

from benchmark import manifest, run

import cut_rule

CELL = "smallthinker_21b_a3b_ep8_b1_L16384"
D, F, V, L, W = 2560, 768, 18992, 16384, 4096
HEADS, KV_HEADS, DIM = 28, 4, 128
NEW = ["window_attention_roofline", "global_attention_roofline",
       "attention_ms_per_step", "relu_expert_ffn_ms_per_step",
       "relu_expert_pad_rows_pct"]


@pytest.fixture(scope="module")
def cell():
    return manifest.resolve(CELL)


def test_parameters_and_flops_by_hand(cell):
    f = cell.module("flops")
    q = D * HEADS * DIM
    kv = D * KV_HEADS * DIM
    assert (q, kv) == (9_175_040, 1_310_720)
    expert = 3 * D * F
    layer = 2 * q + 2 * kv + D * 64 + 2 * D + 8 * expert
    assert layer == 68_326_400
    total = 4 * layer + 2 * V * D + D
    assert f.parameters(cell.config) == total == 370_547_200
    assert cell.config["model"]["parameters"] == total
    # a token meets three quarters of an expert here: 6 x 8 / 64
    assert f.experts_per_token_here(cell.config["model"]) == 0.75
    met = V * D + 4 * (2 * q + 2 * kv + D * 64 + 0.75 * expert)
    assert f.matmul_params_per_token(cell.config) == met
    # keys a query sees on average: the triangle, the band
    assert f.keys_per_query(L) == (L + 1) / 2
    band = (W * (W + 1) / 2 + (L - W) * W) / L
    assert f.keys_per_query(L, W) == band == pytest.approx(3584.125)
    assert f.keys_per_query(W, W) == f.keys_per_query(W) == (W + 1) / 2
    scores = 3 * 2 * 2 * ((L + 1) / 2 + 3 * band) * HEADS * DIM
    assert f.attention_flops_per_token(cell.config) == scores
    assert f.flops_per_token(cell.config) == 6 * met + scores
    # ISSUE 38's arithmetic: 573 M FLOPs a token forward, 28.2 TFLOP a step
    assert f.flops_per_token(cell.config) / 3 == pytest.approx(573.3e6, rel=1e-3)
    assert f.flops_per_sample(cell.config) == pytest.approx(28.18e12, rel=1e-3)
    # attention's scores are 47 % of it (window 27 %, global 20 %)
    assert scores / f.flops_per_token(cell.config) == pytest.approx(0.474, abs=2e-3)


def test_kernel_costs_by_hand(cell):
    k = cell.module("kernel_costs")
    triangle, band = L * (L + 1) // 2, W * (W + 1) // 2 + (L - W) * W
    assert (k.scores(L), k.scores(L, W)) == (triangle, band) == (
        134_225_920, 58_722_304)
    assert k.scores(W, W) == k.scores(W) and k.scores(100, 1) == 100
    # the band is what the mask keeps of the 252 blocks of 512 x 512 a
    # head sweeps, the triangle of 528
    assert band / (252 * 512 * 512) == pytest.approx(0.8889, abs=1e-4)
    assert triangle / (528 * 512 * 512) == pytest.approx(0.9698, abs=1e-4)
    for window, count in ((None, triangle), (W, band)):
        cost = k.attention(1, HEADS, L, DIM, window)
        product = 2 * HEADS * count * DIM
        assert cost["fwd"]["flops"] == 2 * product
        assert cost["dq"]["flops"] == 3 * product
        assert cost["dkv"]["flops"] == 4 * product
    full = manifest.load_module(
        manifest.ROOT, "benchmark/flops/kernels.py").flash_attention(
        1, HEADS, L, DIM)
    causal = k.attention(1, HEADS, L, DIM)
    assert causal["fwd"]["flops"] / full["fwd"]["flops"] == (L + 1) / (2 * L)
    assert causal["dkv"]["bytes"] == full["dkv"]["bytes"]
    # and the LFM2 cell's causal count is the same function
    lfm2 = manifest.load_module(
        manifest.ROOT, "benchmark/flops/lfm2_kernels.py")
    assert lfm2.causal_flash_attention(2, 32, 8192, 64) == k.attention(
        2, 32, 8192, 64)


def test_the_file_states_what_the_preset_builds(cell):
    from pytorch_distributed_nn_tpu.models import build_model, input_spec

    m, tc = cell.config["model"], cell.config["train_config"]
    cfg = build_model(tc["network"]).config
    assert (m["hidden_size"], m["moe_ffn_hidden_size"],
            m["num_attention_heads"], m["num_key_value_heads"],
            m["head_dim"], m["vocab_size"], m["num_hidden_layers"],
            m["moe_num_active_primary_experts"], m["sliding_window_size"],
            m["rms_norm_eps"], m["rope_theta"]) == (
        cfg.hidden_size, cfg.moe_ffn_hidden_size, cfg.num_attention_heads,
        cfg.num_key_value_heads, cfg.head_dim, cfg.vocab_size,
        cfg.num_hidden_layers, cfg.moe_num_active_primary_experts,
        cfg.sliding_window_size, cfg.rms_norm_eps, cfg.rope_theta)
    assert tuple(m["sliding_window_layout"]) == cfg.sliding_window_layout
    assert tuple(m["rope_layout"]) == cfg.rope_layout
    assert (m["first_expert"], m["moe_num_primary_experts"]) == cfg.experts_held
    assert m["router_width"] == cfg.moe_num_primary_experts == 64
    assert (m["moe_primary_router_apply_softmax"], m["norm_topk_prob"],
            m["tie_word_embeddings"]) == (True, True, False)
    assert input_spec(tc["network"]) == (tc["seq_len"],) == (
        cell.config["tokens_per_sample"],) == (
        m["max_position_embeddings"],)
    assert cell.config["per_chip_batch"] == cell.config["check_batch"] == 1
    assert (tc["attn_impl"], tc["dtype"], tc["optimizer"]) == (
        "pallas", "bfloat16", "adam")
    # the kernels block names the calls as the model's modules do
    kernels = cell.config["kernels"]
    assert set(kernels) == {"window_attention", "global_attention",
                            "grouped_matmul"}
    assert kernels["window_attention"]["scores"] == "banded"
    assert kernels["global_attention"]["scores"] == "causal"
    windows = sum(m["sliding_window_layout"])
    assert kernels["window_attention"]["calls_per_step"]["dq"] == windows == 3
    assert kernels["global_attention"]["calls_per_step"]["dq"] == 4 - windows
    # the rehearsal's tiny preset has the same shape
    tiny = build_model("SmallThinkerTiny").config
    r = cell.config["rehearse"]["model"]
    assert (r["hidden_size"], r["moe_num_primary_experts"], r["first_expert"],
            r["router_width"], r["moe_num_active_primary_experts"],
            r["vocab_size"], r["sliding_window_size"]) == (
        tiny.hidden_size, tiny.experts_held[1], tiny.experts_held[0],
        tiny.moe_num_primary_experts, tiny.moe_num_active_primary_experts,
        tiny.vocab_size, tiny.sliding_window_size)
    assert (tuple(r["sliding_window_layout"]) == tiny.sliding_window_layout
            == cfg.sliding_window_layout)


def test_the_file_is_a_cut_of_the_catalogs_row_and_nothing_else(cell):
    config = cell.config
    entry = next(c for c in manifest.load()["configs"]
                 if c["name"] == cell.config_name)
    assert cut_rule.problems(config, entry) == []
    assert manifest.validate() == []
    assert entry["reduced"] == config["reduced"]
    assert sorted(entry["reduced"]) == sorted([
        "num_hidden_layers", "rope_layout", "sliding_window_layout",
        "moe_num_primary_experts", "vocab_size"])
    assert entry["source"] == config["source"] == (
        "https://huggingface.co/PowerInfer/SmallThinker-21BA3B-Instruct"
        "/blob/main/config.json")
    # the contract's layout: every key of the source's config at the top
    # level, as it is run; equal to `model`, and to `published` but for the
    # keys that `reduced` names
    for key, value in config["published"].items():
        assert config[key] == config["model"][key]
        assert (config[key] == value) == (key not in config["reduced"]), key
    published = config["published"]
    assert published["sliding_window_layout"] == [0, 1, 1, 1] * 13
    assert published["rope_layout"] == published["sliding_window_layout"]
    assert (published["num_hidden_layers"], published["vocab_size"],
            published["moe_num_primary_experts"]) == (52, 151936, 64)
    assert config["vocab_size"] * 8 == published["vocab_size"]
    deployment = config["deployment"]
    assert (deployment["chips_per_layer"], deployment["layer_period"],
            deployment["leading_dense_layers"],
            deployment["kept_layer_ids"]) == (8, 4, 0, [0, 1, 2, 3])
    assert {"router_input", "window", "qk_norm", "bias", "secondary_experts",
            "activation", "dtype", "recomputed", "dropless", "kv_heads",
            "data", "optimizer", "routing"} <= set(config["assumed"])
    workload = next(w for w in manifest.load()["workloads"]
                    if w["name"] == CELL)
    assert (workload["chips"], workload["traffic"]) == (1, "train_steady")
    listed = [m["name"] for m in cell.per_layer]
    assert listed[-5:] == NEW
    # the other configurations' kernel metrics keep their lists
    for name in ("flash_attention_roofline", "fused_ln_roofline",
                 "grouped_matmul_roofline", "causal_flash_attention_roofline",
                 "expert_ffn_ms_per_step", "expert_pad_rows_pct"):
        assert name not in listed
    for name in ("bert_base_b32_L512", "resnet18_b4096",
                 "lfm2_8b_a1b_ep4_b2_L8192"):
        old = [m["name"] for m in manifest.resolve(name).per_layer]
        assert not set(old) & set(NEW)
    for m in cell.per_layer[-5:]:
        assert (m["workloads"], m["moves"]) == ([CELL], "samples_per_s")


def _context(cell, steps, kernels=None, peak=None):
    window = types.SimpleNamespace(steps=steps)
    trace = None if kernels is None else {
        "kernels": kernels, "steps": 10, "busy_s": 5.0}
    return run.Context(
        cell=cell, result={"window": window, "global_batch": 1,
                           "records": []},
        peak=peak, trace=trace, flops_per_sample=1.0, notes={})


def _calls(family, seconds, calls=10):
    return [{"family": family, "kind": kind, "calls": calls,
             "seconds": s, "hbm_bytes": None}
            for kind, s in zip(("fwd", "dq", "dkv"), seconds)]


def test_the_readers_on_a_made_up_trace_and_stream(cell):
    ref = "benchmark/readers/smallthinker.py:"
    window, glob, both, ffn, pad = (
        manifest.load_function(manifest.ROOT, ref + name) for name in NEW)
    peak = manifest.peak("TPU v5 lite")
    # a program without the counters, an untraced run: nothing, no raise
    bare = _context(cell, [{"loss": 1.0}], peak=peak)
    assert [r(bare) for r in (window, glob, both, ffn, pad)] == [None] * 5
    # a trace of a program that has neither family (the parent): nothing
    other = _context(cell, [], peak=peak, kernels=[
        {"family": "unknown", "kind": "unknown", "calls": 3,
         "seconds": 0.1, "hbm_bytes": None}])
    assert [r(other) for r in (window, glob, both, ffn)] == [None] * 4
    steps = [{"moe_pairs": 4 * 12288.0, "moe_rows": 4 * 13312.0,
              "moe_load_max": 4 * 1800.0}] * 3
    ctx = _context(cell, steps, peak=peak, kernels=(
        _calls("window_attention", (0.3, 0.45, 0.6), calls=30)
        + _calls("global_attention", (0.2, 0.3, 0.4))
        + [{"family": "grouped_matmul", "kind": "gmm", "calls": 240,
            "seconds": 0.08, "hbm_bytes": None},
           {"family": "grouped_matmul", "kind": "tgmm", "calls": 80,
            "seconds": 0.02, "hbm_bytes": None}]))
    band = 2 * HEADS * 58_722_304 * DIM
    triangle = 2 * HEADS * 134_225_920 * DIM
    assert window(ctx) == pytest.approx(
        100 * (30 * 9 * band / 197e12) / 1.35)
    assert glob(ctx) == pytest.approx(
        100 * (10 * 9 * triangle / 197e12) / 0.9)
    note = ctx.notes["window_attention"]
    assert set(note) == {"fwd", "dq", "dkv"}
    assert note["dq"]["ms_per_call"] == pytest.approx(15.0)
    assert note["fwd"]["bound"] == "compute"
    assert note["dkv"]["roofline_pct"] == pytest.approx(
        100 * (30 * 4 * band / 197e12) / 0.6)
    assert both(ctx) == pytest.approx(225.0)
    assert ctx.notes["attention"]["ms_per_step"] == {
        "window_attention": pytest.approx(135.0),
        "global_attention": pytest.approx(90.0)}
    assert ctx.notes["attention"]["share_of_device_ms_pct"] == pytest.approx(45.0)
    assert ffn(ctx) == pytest.approx(10.0)
    assert ctx.notes["expert_ffn"]["share_of_device_ms_pct"] == pytest.approx(2.0)
    assert pad(ctx) == pytest.approx(100 * 1024 / 13312)
    load = ctx.notes["relu_expert_load"]
    assert load["pairs_per_token"] == pytest.approx(0.75)
    assert load["max_over_mean_load"] == pytest.approx(1800 / 1536)
    # a call the configuration's kinds do not explain: say nothing
    odd = _context(cell, steps, peak=peak, kernels=[
        {"family": "window_attention", "kind": "unknown", "calls": 1,
         "seconds": 0.1, "hbm_bytes": None}])
    assert window(odd) is None and glob(odd) is None


def test_the_harness_takes_the_cell_as_files_and_entries():
    """One untraced ``--rehearse`` run through ``Trainer.train()`` on the
    CPU, the tiny preset against the plain reference."""
    buf = io.StringIO()
    with contextlib.redirect_stdout(buf):
        rc = run.main(["--workload", CELL, "--rehearse", "--trace", "0",
                       "--seed", "3838000007", "--seconds", "2"])
    assert rc == 0
    line = json.loads(buf.getvalue().strip().splitlines()[-1])
    assert line["correct"] is True, line
    assert line["workload"] == CELL and line["failed"] == 0
    assert set(line["metrics"]) == {"samples_per_s", "setup_s"}
    assert all(m["value"] is None for m in line["metrics"].values())
    check = line["check"]
    assert check["ok"] and check["batch"] == 2
    assert check["grad_rel_err"] < 1e-3 and check["loss_rel"] < 1e-5

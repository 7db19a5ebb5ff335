"""The GLM-4.7-Flash share (configuration ``glm47_flash_ep8``, cell
``glm47_flash_ep8_b1_L4096``): its parameters, FLOPs and kernel costs
against counts made by hand, its file against the preset the program
builds and against the catalog's row, its readers on a made-up context,
and one ``--rehearse`` run on the CPU: the harness takes the cell as files
and entries."""

import contextlib
import io
import json
import types

import pytest

from benchmark import manifest, run

import cut_rule

CELL = "glm47_flash_ep8_b1_L4096"
D, V, L, HEADS = 2048, 19360, 4096, 20
QK, VD = 192 + 64, 256
NEW = ["mla_attention_roofline", "mla_attention_ms_per_step",
       "glm_expert_ffn_ms_per_step", "glm_expert_pad_rows_pct"]


@pytest.fixture(scope="module")
def cell():
    return manifest.resolve(CELL)


def test_parameters_and_flops_by_hand(cell):
    f = cell.module("flops")
    m = cell.config["model"]
    # q_a, q_b, kv_a (with the rotary key), kv_b, o
    mla = (D * 768 + 768 * HEADS * QK + D * (512 + 64)
           + 512 * HEADS * (192 + VD) + HEADS * VD * D)
    assert f.mla_params(m) == mla == 21_757_952
    expert = 3 * D * 1536
    dense = mla + 768 + 512 + 2 * D + 3 * D * 10240
    moe = mla + 768 + 512 + 2 * D + D * 64 + 64 + (8 + 1) * expert
    mtp = 3 * D + 2 * D * D + moe
    assert (dense, moe, mtp) == (84_677_888, 106_829_120, 115_223_872)
    total = 2 * V * D + D + dense + 4 * moe + mtp
    assert f.parameters(cell.config) == total == m["parameters"] == 706_518_848
    # 11.3 GB at 16 bytes a parameter (weights, gradient, Adam's moments)
    assert 16 * total == pytest.approx(11.30e9, rel=1e-3)
    # half a routed expert a token here: 4 x 8 / 64
    assert f.experts_per_token_here(m) == 0.5
    assert f.expert_layers(m) == 5
    met = (2 * V * D + 2 * D * D + 6 * mla + 3 * D * 10240
           + 5 * (D * 64 + 1.5 * expert))
    assert f.matmul_params_per_token(cell.config) == met
    scores = 3 * 2 * (L + 1) / 2 * HEADS * (QK + VD) * 6
    assert f.attention_flops_per_token(cell.config) == scores
    assert f.flops_per_token(cell.config) == 6 * met + scores
    # by hand from the configuration: 957 M FLOPs a token forward, 11.8 TFLOP a step
    assert f.flops_per_token(cell.config) / 3 == pytest.approx(956.9e6, rel=1e-3)
    assert f.flops_per_sample(cell.config) == pytest.approx(11.76e12, rel=1e-3)
    # latent attention, projections and scores, is 54 % of it
    share = (6 * 6 * mla + scores) / f.flops_per_token(cell.config)
    assert share == pytest.approx(0.536, abs=2e-3)
    assert scores / f.flops_per_token(cell.config) == pytest.approx(
        0.263, abs=2e-3)


def test_kernel_costs_by_hand(cell):
    k = cell.module("kernel_costs")
    triangle = L * (L + 1) // 2
    assert k.causal_scores(L) == triangle == 8_390_656
    cost = k.attention(1, HEADS, L, QK, VD)
    s = 2 * HEADS * triangle
    assert cost["fwd"]["flops"] == s * (QK + VD)
    assert cost["dq"]["flops"] == s * (2 * QK + VD)
    assert cost["dkv"]["flops"] == s * (2 * QK + 2 * VD)
    tensor, row = HEADS * L * 256 * 2, HEADS * L * 4
    assert cost["fwd"]["bytes"] == 4 * tensor + row
    assert cost["dq"]["bytes"] == 5 * tensor + 2 * row
    assert cost["dkv"]["bytes"] == 6 * tensor + 2 * row
    # at equal widths: the SmallThinker share's causal count, the same
    # products and bytes
    smallthinker = manifest.load_module(
        manifest.ROOT, "benchmark/flops/smallthinker_kernels.py")
    assert cost == smallthinker.attention(1, HEADS, L, 256)
    # in blocks of 512 a head's causal grid computes 36 of the 8 x 8
    # blocks, and the mask keeps 89 % of what they hold
    assert triangle / (36 * 512 * 512) == pytest.approx(0.8891, abs=1e-4)


def test_the_file_states_what_the_preset_builds(cell):
    from pytorch_distributed_nn_tpu.models import build_model, input_spec

    m, tc = cell.config["model"], cell.config["train_config"]
    cfg = build_model(tc["network"]).config
    for key in ("hidden_size", "intermediate_size", "moe_intermediate_size",
                "num_attention_heads", "q_lora_rank", "kv_lora_rank",
                "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim",
                "num_hidden_layers", "first_k_dense_replace",
                "n_shared_experts", "num_experts_per_tok",
                "routed_scaling_factor", "num_nextn_predict_layers",
                "rms_norm_eps", "rope_theta", "vocab_size"):
        assert m[key] == getattr(cfg, key), key
    assert (m["first_expert"], m["n_routed_experts"]) == cfg.experts_held
    assert m["router_width"] == cfg.n_routed_experts == 64
    assert (m["topk_method"], m["n_group"], m["topk_group"],
            m["norm_topk_prob"], m["tie_word_embeddings"],
            m["partial_rotary_factor"]) == ("noaux_tc", 1, 1, True, False, 1)
    assert input_spec(tc["network"]) == (tc["seq_len"],) == (
        cell.config["tokens_per_sample"],) == (4096,)
    assert cell.config["per_chip_batch"] == cell.config["check_batch"] == 1
    assert (tc["attn_impl"], tc["dtype"], tc["optimizer"],
            tc["dataset"], tc["warmup_steps"]) == (
        "pallas", "bfloat16", "adam", "NextTokenSynth", 2000)
    assert "remat" not in tc and not cfg.remat
    # the kernels block names the calls as the model's modules do
    kernels = cell.config["kernels"]
    assert set(kernels) == {"mla_attention", "grouped_matmul"}
    assert kernels["mla_attention"]["scores"] == "causal"
    assert kernels["mla_attention"]["calls_per_step"] == {
        "fwd": 6, "dq": 6, "dkv": 6}
    assert kernels["grouped_matmul"]["calls_per_step"] == {
        "gmm": 6 * 5, "tgmm": 2 * 5}
    # the rehearsal's tiny preset has the same shape
    tiny = build_model("GLM47FlashTiny").config
    r = cell.config["rehearse"]["model"]
    for key in ("hidden_size", "q_lora_rank", "kv_lora_rank",
                "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim",
                "num_hidden_layers", "num_experts_per_tok", "vocab_size",
                "first_k_dense_replace", "num_nextn_predict_layers"):
        assert r[key] == getattr(tiny, key), key
    assert (r["first_expert"], r["n_routed_experts"]) == tiny.experts_held
    assert r["router_width"] == tiny.n_routed_experts


def test_the_file_is_a_cut_of_the_catalogs_row_and_nothing_else(cell):
    config = cell.config
    entry = next(c for c in manifest.load()["configs"]
                 if c["name"] == cell.config_name)
    assert cut_rule.problems(config, entry) == []
    assert manifest.validate() == []
    assert entry["reduced"] == config["reduced"] == [
        "num_hidden_layers", "n_routed_experts", "vocab_size"]
    assert entry["source"] == config["source"] == (
        "https://huggingface.co/zai-org/GLM-4.7-Flash/blob/main/config.json")
    # the contract's layout: every key of the source's config at the top
    # level, as it is run; equal to `model`, and to `published` but for the
    # keys that `reduced` names
    for key, value in config["published"].items():
        assert config[key] == config["model"][key]
        assert (config[key] == value) == (key not in config["reduced"]), key
    published = config["published"]
    assert (published["num_hidden_layers"], published["vocab_size"],
            published["n_routed_experts"]) == (47, 154880, 64)
    assert config["vocab_size"] * 8 == published["vocab_size"]
    deployment = config["deployment"]
    assert (deployment["chips_per_layer"], deployment["layer_period"],
            deployment["leading_dense_layers"],
            deployment["kept_layer_ids"]) == (8, 1, 1, [0, 1, 2, 3, 4])
    assert "prediction module" in deployment["how"]
    assert {"rotary_layout", "mtp_loss_weight", "eh_proj_order",
            "mtp_position", "expert_bias", "dtype", "recomputed", "dropless",
            "data", "optimizer", "routing"} <= set(config["assumed"])
    workload = next(w for w in manifest.load()["workloads"]
                    if w["name"] == CELL)
    assert (workload["chips"], workload["traffic"]) == (1, "train_steady")
    listed = [m["name"] for m in cell.per_layer]
    assert listed[-4:] == NEW
    for m in cell.per_layer[-4:]:
        assert (m["workloads"], m["moves"]) == ([CELL], "samples_per_s")
    # the other cells do not list them
    for name in ("bert_base_b32_L512", "lfm2_8b_a1b_ep4_b2_L8192",
                 "smallthinker_21b_a3b_ep8_b1_L16384"):
        old = [m["name"] for m in manifest.resolve(name).per_layer]
        assert not set(old) & set(NEW)


def _context(cell, steps, kernels=None, peak=None):
    window = types.SimpleNamespace(steps=steps)
    trace = None if kernels is None else {
        "kernels": kernels, "steps": 10, "busy_s": 2.0}
    return run.Context(
        cell=cell, result={"window": window, "global_batch": 1,
                           "records": []},
        peak=peak, trace=trace, flops_per_sample=1.0, notes={})


def test_the_readers_on_a_made_up_trace_and_stream(cell):
    ref = "benchmark/readers/glm47.py:"
    roof, ms, ffn, pad = (
        manifest.load_function(manifest.ROOT, ref + name) for name in NEW)
    peak = manifest.peak("TPU v5 lite")
    # a program without the counters, an untraced run: nothing, no raise
    bare = _context(cell, [{"loss": 1.0}], peak=peak)
    assert [r(bare) for r in (roof, ms, ffn, pad)] == [None] * 4
    # a trace of a program without these calls (the parent): nothing
    other = _context(cell, [], peak=peak, kernels=[
        {"family": "unknown", "kind": "unknown", "calls": 3,
         "seconds": 0.1, "hbm_bytes": None}])
    assert [r(other) for r in (roof, ms, ffn)] == [None] * 3
    steps = [{"moe_pairs": 5 * 2048.0, "moe_rows": 5 * 3072.0,
              "moe_load_max": 5 * 400.0}] * 3
    ctx = _context(cell, steps, peak=peak, kernels=(
        [{"family": "mla_attention", "kind": kind, "calls": 60,
          "seconds": s, "hbm_bytes": None}
         for kind, s in (("fwd", 0.06), ("dq", 0.09), ("dkv", 0.12))]
        + [{"family": "grouped_matmul", "kind": "gmm", "calls": 300,
            "seconds": 0.08, "hbm_bytes": None},
           {"family": "grouped_matmul", "kind": "tgmm", "calls": 100,
            "seconds": 0.02, "hbm_bytes": None}]))
    s = 2 * HEADS * (L * (L + 1) // 2)
    fwd, dq, dkv = s * (QK + VD), s * (2 * QK + VD), s * (2 * QK + 2 * VD)
    assert roof(ctx) == pytest.approx(
        100 * 60 * (fwd + dq + dkv) / 197e12 / 0.27)
    note = ctx.notes["mla_attention"]
    assert set(note) == {"fwd", "dq", "dkv"}
    assert note["dq"]["ms_per_call"] == pytest.approx(1.5)
    assert note["fwd"]["bound"] == "compute"
    assert ms(ctx) == pytest.approx(27.0)
    assert ctx.notes["mla_attention_time"] == {
        "share_of_device_ms_pct": pytest.approx(13.5),
        "calls_per_step": 18.0}
    assert ffn(ctx) == pytest.approx(10.0)
    assert ctx.notes["expert_ffn"]["share_of_device_ms_pct"] == pytest.approx(5.0)
    assert pad(ctx) == pytest.approx(100 * 1024 / 3072)
    load = ctx.notes["glm_expert_load"]
    assert load["expert_layers"] == 5
    assert load["pairs_per_token"] == pytest.approx(0.5)
    assert load["even_pairs_per_token"] == 0.5
    assert load["max_over_mean_load"] == pytest.approx(400 / 256)
    # a call the configuration's kinds do not explain: say nothing
    odd = _context(cell, steps, peak=peak, kernels=[
        {"family": "mla_attention", "kind": "unknown", "calls": 1,
         "seconds": 0.1, "hbm_bytes": None}])
    assert roof(odd) is None


def test_the_harness_takes_the_cell_as_files_and_entries():
    """One untraced ``--rehearse`` run through ``Trainer.train()`` on the
    CPU, the tiny preset against the plain reference."""
    buf = io.StringIO()
    with contextlib.redirect_stdout(buf):
        rc = run.main(["--workload", CELL, "--rehearse", "--trace", "0",
                       "--seed", "3838000043", "--seconds", "2"])
    assert rc == 0
    line = json.loads(buf.getvalue().strip().splitlines()[-1])
    assert line["correct"] is True, line
    assert line["workload"] == CELL and line["failed"] == 0
    assert set(line["metrics"]) == {"samples_per_s", "setup_s"}
    assert all(m["value"] is None for m in line["metrics"].values())
    check = line["check"]
    assert check["ok"] and check["batch"] == 2
    assert check["grad_rel_err"] < 1e-3 and check["loss_rel"] < 1e-5

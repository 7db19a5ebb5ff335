"""The Granite-4.0-H-Micro share (configuration ``granite4_h_micro_pp4``,
cell ``granite4_h_micro_pp4_b1_L4096``): its parameters, FLOPs and the scan
kernel's costs against counts made by hand, its file against the preset
the program builds and against the published configuration, its readers
on a made-up context, and one ``--rehearse`` run on the CPU: the harness
takes the cell as files and entries."""

import contextlib
import io
import json
import types

import pytest

from benchmark import manifest, run

import cut_rule

CELL = "granite4_h_micro_pp4_b1_L4096"
D, V, L = 2048, 12544, 4096
H, P, N, Q = 64, 64, 128, 256
NEW = ["ssd_scan_roofline", "ssd_scan_ms_per_step"]


@pytest.fixture(scope="module")
def cell():
    return manifest.resolve(CELL)


def test_parameters_and_flops_by_hand(cell):
    f = cell.module("flops")
    m = cell.config["model"]
    in_proj = D * (2 * H * P + 2 * N + H)              # [z | x B C | dt]
    assert in_proj == D * 8512 == 17_432_576
    mixer = in_proj + (4 + 1) * (H * P + 2 * N) + 3 * H + H * P + H * P * D
    assert mixer == 25_847_232
    mlp = 3 * D * 8192
    attention = D * 64 * (32 + 8 + 8) + 32 * 64 * D
    mamba_layer, attention_layer = mixer + mlp + 2 * D, attention + mlp + 2 * D
    assert (mamba_layer, attention_layer) == (76_182_976, 60_821_504)
    total = V * D + D + 9 * mamba_layer + attention_layer
    assert f.parameters(cell.config) == total == m["parameters"] == 772_160_448
    # 12.35 GB at 16 bytes a parameter (weights, gradient, Adam's moments)
    assert 16 * total == pytest.approx(12.35e9, rel=1e-3)
    met = 9 * (in_proj + H * P * D) + attention + 10 * mlp + V * D
    assert f.matmul_params_per_token(cell.config) == met == 771_883_008
    scores = 3 * 2 * (L + 1) / 2 * 32 * 2 * 64
    assert f.attention_flops_per_token(cell.config) == scores
    chunk = 2 * Q * Q * N + H * (2 * Q * Q * P + 4 * Q * N * P)
    assert f.scan_flops_per_token(cell.config) == 3 * 9 * chunk / Q
    # 38.3 MFLOP a token forward over the nine Mamba layers
    assert 9 * chunk / Q == pytest.approx(38.34e6, rel=1e-3)
    per_token = 6 * met + scores + 3 * 9 * chunk / Q
    assert f.flops_per_token(cell.config) == per_token
    assert f.flops_per_sample(cell.config) == pytest.approx(19.647e12,
                                                            rel=1e-3)
    # the shares the cell's why gives
    shares = {"mlp": 6 * 10 * mlp, "mamba_proj": 6 * 9 * (in_proj + H * P * D),
              "scan": 3 * 9 * chunk / Q}
    got = {k: v / per_token for k, v in shares.items()}
    assert got == pytest.approx({"mlp": 0.630, "mamba_proj": 0.291,
                                 "scan": 0.024}, abs=2e-3)


def test_kernel_costs_by_hand(cell):
    k = cell.module("kernel_costs")
    # the tiny preset: 2 sequences of 64, 2 heads of 64, state 16, chunk 16
    cost = k.ssd(2, 64, 2, 64, 16, 16, 1, 4)
    chunks = 2 * 4
    fwd = 2 * 16 * 16 * 16 + 2 * (2 * 16 * 16 * 64 + 4 * 16 * 16 * 64)
    bwd = 2 * 16 * 16 * 16 + 2 * (4 * 16 * 16 * 64 + 4 * 16 * 16 * 16
                                  + 10 * 16 * 16 * 64)
    assert cost["fwd"]["flops"] == chunks * fwd
    assert cost["bwd"]["flops"] == chunks * bwd
    x, rows, bc = 2 * 64 * 2 * 64 * 4, 2 * 64 * 2 * 4, 2 * 64 * 16 * 4
    states = chunks * 2 * 64 * 16 * 4
    assert cost["fwd"]["bytes"] == 2 * x + 2 * rows + 2 * bc + states
    assert cost["bwd"]["bytes"] == 3 * x + 4 * rows + 4 * bc + states
    # the cell's call: 16 chunks of 256 for one sequence
    full = k.ssd(1, L, H, P, N, Q, 1, 2)
    assert full["fwd"]["flops"] == 16 * (2 * Q * Q * N + H * (
        2 * Q * Q * P + 4 * Q * N * P)) == 17_448_304_640
    assert full["bwd"]["flops"] == pytest.approx(73.28e9, rel=1e-3)
    # the forward's floor is its bytes (the boundary states among them),
    # the backward's its operations
    peak = manifest.peak("TPU v5 lite")
    assert (full["fwd"]["bytes"] / peak["hbm_bytes_per_s"]
            > full["fwd"]["flops"] / peak["bf16_flops_per_s"])
    assert (full["bwd"]["bytes"] / peak["hbm_bytes_per_s"]
            < full["bwd"]["flops"] / peak["bf16_flops_per_s"])


def test_the_file_states_what_the_preset_builds(cell):
    from pytorch_distributed_nn_tpu.models import build_model, input_spec

    m, tc = cell.config["model"], cell.config["train_config"]
    cfg = build_model(tc["network"]).config
    for key in ("hidden_size", "num_attention_heads", "num_key_value_heads",
                "mamba_n_heads", "mamba_d_head", "mamba_d_state",
                "mamba_d_conv", "mamba_n_groups", "mamba_chunk_size",
                "embedding_multiplier", "residual_multiplier",
                "logits_scaling", "attention_multiplier", "rms_norm_eps",
                "num_hidden_layers", "vocab_size"):
        assert m[key] == getattr(cfg, key), key
    assert m["intermediate_size"] == m["shared_intermediate_size"] == (
        cfg.intermediate_size)
    assert tuple(m["layer_types"]) == cfg.layer_types
    assert m["mamba_expand"] * m["hidden_size"] == (
        cfg.mamba_n_heads * cfg.mamba_d_head)
    assert (m["num_local_experts"], m["num_experts_per_tok"],
            m["tie_word_embeddings"], m["position_embedding_type"],
            m["mamba_conv_bias"], m["mamba_proj_bias"],
            m["attention_bias"]) == (0, 0, True, "nope", True, False, False)
    assert input_spec(tc["network"]) == (tc["seq_len"],) == (
        cell.config["tokens_per_sample"],) == (L,)
    assert cell.config["per_chip_batch"] == cell.config["check_batch"] == 1
    assert (tc["attn_impl"], tc["dtype"], tc["optimizer"],
            tc["dataset"], tc["warmup_steps"]) == (
        "pallas", "bfloat16", "adam", "NextTokenSynth", 2000)
    # every block recomputed: the preset's, not an option of the cell
    assert "remat" not in tc and cfg.remat
    kernels = cell.config["kernels"]
    assert set(kernels) == {"ssd_scan"}
    assert kernels["ssd_scan"]["calls_per_step"] == {"fwd": 2 * 9, "bwd": 9}
    assert kernels["ssd_scan"]["kinds"] == {
        "fwd": {"operands": 5, "outputs": 2},
        "bwd": {"operands": 7, "outputs": 5}}
    # the rehearsal's tiny preset has the same shape
    tiny = build_model("Granite4Tiny").config
    r = cell.config["rehearse"]["model"]
    for key in ("hidden_size", "intermediate_size", "num_attention_heads",
                "num_key_value_heads", "mamba_n_heads", "mamba_d_head",
                "mamba_d_state", "mamba_chunk_size", "attention_multiplier",
                "vocab_size", "num_hidden_layers"):
        assert r[key] == getattr(tiny, key), key
    assert tuple(r["layer_types"]) == tiny.layer_types


def test_the_file_is_a_cut_of_the_published_config_and_nothing_else(cell):
    config = cell.config
    entry = next(c for c in manifest.load()["configs"]
                 if c["name"] == cell.config_name)
    assert cut_rule.problems(config, entry) == []
    assert manifest.validate() == []
    assert entry["reduced"] == config["reduced"] == [
        "num_hidden_layers", "layer_types", "vocab_size"]
    assert entry["source"] == config["source"] == (
        "https://huggingface.co/ibm-granite/granite-4.0-h-micro/blob/main/"
        "config.json")
    # every key of the source's config at the top level, as it is run;
    # equal to `model`, and to `published` but for the keys `reduced` names
    for key, value in config["published"].items():
        assert config[key] == config["model"][key]
        assert (config[key] == value) == (key not in config["reduced"]), key
    published = config["published"]
    assert (published["num_hidden_layers"], published["vocab_size"]) == (
        40, 100352)
    assert published["layer_types"][:10] * 4 == published["layer_types"]
    assert config["vocab_size"] * 8 == published["vocab_size"]
    deployment = config["deployment"]
    assert (deployment["chips_per_layer"], deployment["layer_period"],
            deployment["leading_dense_layers"],
            deployment["kept_layer_ids"]) == (8, 10, 0, list(range(10)))
    assert "four-stage pipeline" in deployment["how"]
    assert {"initialisation", "dtype", "optimizer", "recomputed",
            "attention_scale", "scan", "data"} <= set(config["assumed"])
    workload = next(w for w in manifest.load()["workloads"]
                    if w["name"] == CELL)
    assert (workload["chips"], workload["traffic"]) == (1, "train_steady")


def test_the_new_metrics_come_after_the_existing_ones_and_list_the_cell(
        cell):
    bench = manifest.load()
    names = [m["name"] for m in bench["per_layer"]]
    assert names[-2:] == NEW
    assert [w["name"] for w in bench["workloads"]][-1] == CELL
    assert [c["name"] for c in bench["configs"]][-1] == cell.config_name
    listed = [m["name"] for m in cell.per_layer]
    assert listed[-2:] == NEW
    for m in cell.per_layer[-2:]:
        assert (m["workloads"], m["moves"]) == ([CELL], "samples_per_s")
    for name in ("bert_base_b32_L512", "lfm2_8b_a1b_ep4_b2_L8192",
                 "glm47_flash_ep8_b1_L4096"):
        old = [m["name"] for m in manifest.resolve(name).per_layer]
        assert not set(old) & set(NEW)


def _context(cell, steps, kernels=None, peak=None):
    window = types.SimpleNamespace(steps=steps)
    trace = None if kernels is None else {
        "kernels": kernels, "steps": 10, "busy_s": 3.0}
    return run.Context(
        cell=cell, result={"window": window, "global_batch": 1,
                           "records": []},
        peak=peak, trace=trace, flops_per_sample=1.0, notes={})


def test_the_readers_on_a_made_up_trace_and_stream(cell):
    ref = "benchmark/readers/granite4.py:"
    roof, ms = (manifest.load_function(manifest.ROOT, ref + name)
                for name in NEW)
    peak = manifest.peak("TPU v5 lite")
    # an untraced run: nothing, no raise
    bare = _context(cell, [{"loss": 1.0}], peak=peak)
    assert [r(bare) for r in (roof, ms)] == [None, None]
    # a trace of a program without the scan (the parent): nothing
    other = _context(cell, [], peak=peak, kernels=[
        {"family": "unknown", "kind": "unknown", "calls": 30,
         "seconds": 0.1, "hbm_bytes": None}])
    assert [r(other) for r in (roof, ms)] == [None, None]
    steps = [{"ssd_calls": 9.0, "ssd_chunks": 144.0,
              "ssd_state_absmax": a} for a in (3.0, 5.0)]
    fwd_bytes, bwd_bytes = 50e6, 120e6
    ctx = _context(cell, steps, peak=peak, kernels=[
        {"family": "ssd_scan", "kind": "fwd", "calls": 180, "seconds": 0.09,
         "hbm_bytes": 180 * fwd_bytes},
        {"family": "ssd_scan", "kind": "bwd", "calls": 90, "seconds": 0.09,
         "hbm_bytes": 90 * bwd_bytes}])
    costs = cell.module("kernel_costs").ssd(1, L, H, P, N, Q, 1, 2)
    least = [max(costs[k]["flops"] / 197e12, b / 819e9)
             for k, b in (("fwd", fwd_bytes), ("bwd", bwd_bytes))]
    assert roof(ctx) == pytest.approx(
        100 * (180 * least[0] + 90 * least[1]) / 0.18)
    note = ctx.notes["ssd_scan"]
    assert set(note) == {"fwd", "bwd"}
    assert note["fwd"]["ms_per_call"] == pytest.approx(0.5)
    assert note["bwd"]["bound"] == "compute"
    assert ms(ctx) == pytest.approx(18.0)
    time = ctx.notes["ssd_scan_time"]
    assert time["share_of_device_ms_pct"] == pytest.approx(6.0)
    assert time["calls_per_step"] == {"fwd": 18.0, "bwd": 9.0}
    assert time["counters"] == {"ssd_calls": 9.0, "ssd_chunks": 144.0,
                                "ssd_state_absmax": 4.0,
                                "ssd_state_absmax_max": 5.0}
    # a call the configuration's kinds do not explain: say nothing
    odd = _context(cell, steps, peak=peak, kernels=[
        {"family": "ssd_scan", "kind": "unknown", "calls": 1,
         "seconds": 0.1, "hbm_bytes": None}])
    assert roof(odd) is None


def test_the_harness_takes_the_cell_as_files_and_entries():
    """One untraced ``--rehearse`` run through ``Trainer.train()`` on the
    CPU, the tiny preset against the plain reference."""
    buf = io.StringIO()
    with contextlib.redirect_stdout(buf):
        rc = run.main(["--workload", CELL, "--rehearse", "--trace", "0",
                       "--seed", "3838000045", "--seconds", "2"])
    assert rc == 0
    line = json.loads(buf.getvalue().strip().splitlines()[-1])
    assert line["correct"] is True, line
    assert line["workload"] == CELL and line["failed"] == 0
    assert set(line["metrics"]) == {"samples_per_s", "setup_s"}
    assert all(m["value"] is None for m in line["metrics"].values())
    check = line["check"]
    assert check["ok"] and check["batch"] == 2
    assert check["grad_rel_err"] < 1e-4 and check["loss_rel"] < 1e-5

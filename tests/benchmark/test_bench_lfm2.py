"""The LFM2-8B-A1B share (configuration ``lfm2_8b_a1b_ep4``, cell
``lfm2_8b_a1b_ep4_b2_L8192``): its FLOPs and kernel cost functions against
counts made by hand, its file against the preset the program builds and
against the catalog's layout, its readers on a made-up context, and one
``--rehearse`` run on the CPU: the harness takes the cell as files and
entries."""

import contextlib
import io
import json
import types

import pytest

from benchmark import manifest, run

import cut_rule

CELL = "lfm2_8b_a1b_ep4_b2_L8192"
D, F_DENSE, F_EXPERT, V, L = 2048, 7168, 1792, 16384, 8192


@pytest.fixture(scope="module")
def cell():
    return manifest.resolve(CELL)


def test_parameters_and_flops_by_hand(cell):
    f = cell.module("flops")
    conv = D * 3 * D + D * D
    attention = 2 * D * D + 2 * D * 512
    expert = 3 * D * F_EXPERT
    assert expert == 11_010_048
    dense_layer = conv + 3 * D * F_DENSE + 3 * D + 2 * D
    attention_layer = attention + 2 * 64 + 8 * expert + D * 32 + 2 * D
    conv_layer = conv + 3 * D + 8 * expert + D * 32 + 2 * D
    assert (dense_layer, attention_layer, conv_layer) == (
        60_827_648, 98_635_904, 104_933_376)
    total = V * D + dense_layer + attention_layer + 3 * conv_layer + D
    assert f.parameters(cell.config) == total == 507_820_160
    # the four (32,) expert-bias buffers ride in the parameter tree
    assert cell.config["model"]["parameters"] == total + 4 * 32
    # a token meets one of its four experts here: 4 x 8 / 32
    assert f.experts_per_token_here(cell.config["model"]) == 1.0
    met = (V * D + conv + 3 * D * F_DENSE
           + attention + 3 * conv + 4 * (D * 32 + expert))
    assert f.matmul_params_per_token(cell.config) == met == 199_491_584
    causal = 3 * 2 * 2 * (L + 1) / 2 * D
    assert f.attention_flops_per_token(cell.config) == causal
    assert f.flops_per_token(cell.config) == 6 * met + causal
    assert f.flops_per_token(cell.config) == pytest.approx(1.2976e9, rel=1e-4)
    assert f.flops_per_sample(cell.config) == pytest.approx(10.63e12, rel=1e-3)


def test_kernel_costs_by_hand(cell):
    k = cell.module("kernel_costs")
    rows = 16000.0
    cost = k.grouped_matmul(rows, D, F_EXPERT, experts=8)
    # a layer's two calls a pass: (rows, 2048) @ (2048, 3584) and
    # (rows, 1792) @ (1792, 2048); a call costs their mean
    both = 2 * rows * D * 2 * F_EXPERT + 2 * rows * F_EXPERT * D
    assert cost["gmm"]["flops"] == cost["tgmm"]["flops"] == both / 2
    acts = rows * (D + 2 * F_EXPERT + F_EXPERT + D) * 2 / 2
    weights = 8 * 3 * D * F_EXPERT / 2
    assert cost["gmm"]["bytes"] == acts + 2 * weights
    assert cost["tgmm"]["bytes"] == acts + 4 * weights
    flash = k.causal_flash_attention(batch=2, heads=32, length=L, head_dim=64)
    scores = 64 * (L * (L + 1) // 2)
    product = 2 * scores * 64
    assert flash["fwd"]["flops"] == 2 * product
    assert flash["dq"]["flops"] == 3 * product
    assert flash["dkv"]["flops"] == 4 * product
    # half the full kernel's count, plus the diagonal
    full = manifest.load_module(
        manifest.ROOT, "benchmark/flops/kernels.py").flash_attention(
        2, 32, L, 64)
    assert flash["fwd"]["flops"] / full["fwd"]["flops"] == (L + 1) / (2 * L)
    assert flash["dkv"]["bytes"] == full["dkv"]["bytes"]


def test_the_file_states_what_the_preset_builds(cell):
    from pytorch_distributed_nn_tpu.models import build_model, input_spec

    m, tc = cell.config["model"], cell.config["train_config"]
    cfg = build_model(tc["network"]).config
    assert (m["hidden_size"], m["intermediate_size"],
            m["moe_intermediate_size"], m["num_attention_heads"],
            m["num_key_value_heads"], m["head_dim"], m["vocab_size"],
            m["num_hidden_layers"], m["num_dense_layers"],
            m["num_experts_per_tok"], m["conv_L_cache"], m["norm_eps"],
            m["rope_theta"], m["routed_scaling_factor"]) == (
        cfg.hidden_size, cfg.intermediate_size, cfg.moe_intermediate_size,
        cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head_dim,
        cfg.vocab_size, cfg.num_hidden_layers, cfg.num_dense_layers,
        cfg.num_experts_per_tok, cfg.conv_L_cache, cfg.norm_eps,
        cfg.rope_theta, cfg.routed_scaling_factor)
    assert tuple(m["layer_types"]) == cfg.layer_types
    assert (m["first_expert"], m["num_experts"]) == cfg.experts_held
    assert m["router_width"] == cfg.num_experts == 32
    assert (m["norm_topk_prob"], m["use_expert_bias"]) == (True, True)
    assert input_spec(tc["network"]) == (tc["seq_len"],) == (
        cell.config["tokens_per_sample"],)
    assert cell.config["per_chip_batch"] * tc["seq_len"] == 16384
    # the kept layers are a period of the published pattern after a dense one
    published = cell.config["published"]["layer_types"]
    assert m["layer_types"][1:] == published[2:6]
    assert m["layer_types"][0] == published[1] == "conv"
    # the rehearsal's tiny preset has the same shape
    tiny = build_model("Lfm2Tiny").config
    r = cell.config["rehearse"]["model"]
    assert (r["hidden_size"], r["num_experts"], r["first_expert"],
            r["router_width"], r["num_experts_per_tok"], r["vocab_size"]) == (
        tiny.hidden_size, tiny.experts_held[1], tiny.experts_held[0],
        tiny.num_experts, tiny.num_experts_per_tok, tiny.vocab_size)
    assert tuple(r["layer_types"]) == tiny.layer_types == cfg.layer_types


def test_the_file_is_a_cut_of_the_catalogs_row_and_nothing_else(cell):
    config = cell.config
    entry = next(c for c in manifest.load()["configs"]
                 if c["name"] == cell.config_name)
    assert cut_rule.problems(config, entry) == []
    assert entry["reduced"] == config["reduced"] == [
        "num_hidden_layers", "num_dense_layers", "layer_types",
        "num_experts", "vocab_size"]
    assert entry["source"] == config["source"]
    # the contract's layout: every key of the source's config at the top
    # level, as it is run; equal to `model`, and to `published` but for the
    # keys that `reduced` names
    for key, value in config["published"].items():
        assert config[key] == config["model"][key]
        assert (config[key] == value) == (key not in config["reduced"]), key
    assert config["deployment"]["chips_per_layer"] == 4
    assert {"head_dim", "tie_word_embeddings", "expert_bias", "dtype",
            "recomputed", "kv_heads", "data"} <= set(config["assumed"])
    workload = next(w for w in manifest.load()["workloads"]
                    if w["name"] == CELL)
    assert (workload["chips"], workload["traffic"]) == (1, "train_steady")
    listed = [m["name"] for m in cell.per_layer]
    assert listed[-4:] == [
        "grouped_matmul_roofline", "causal_flash_attention_roofline",
        "expert_ffn_ms_per_step", "expert_pad_rows_pct"]
    assert "flash_attention_roofline" not in listed
    assert "fused_ln_roofline" not in listed
    for name in ("bert_base_b32_L512", "resnet18_b4096"):
        old = [m["name"] for m in manifest.resolve(name).per_layer]
        assert not set(old) & set(listed[-4:])


def _context(cell, steps, kernels=None, peak=None, traced=()):
    """``steps``: the window's step records; ``traced``: the records of the
    steps the trace holds (22..31: two warm-up windows of 10, then 10)."""
    window = types.SimpleNamespace(steps=steps)
    trace = None if kernels is None else {
        "kernels": kernels, "steps": 10, "busy_s": 3.0}
    records = [{"kind": "step", "step": 20, "moe_pairs": 1.0, "moe_rows": 1.0,
                "moe_load_max": 1.0}]
    records += [{"kind": "step", "step": 22 + i, **r}
                for i, r in enumerate(traced)]
    return run.Context(
        cell=cell, result={"window": window, "global_batch": 2,
                           "records": records},
        peak=peak, trace=trace, flops_per_sample=1.0, notes={})


def test_the_readers_on_a_made_up_trace_and_stream(cell):
    load = manifest.load_function
    ref = "benchmark/readers/lfm2.py:"
    pad = load(manifest.ROOT, ref + "expert_pad_rows_pct")
    gmm = load(manifest.ROOT, ref + "grouped_matmul_roofline")
    flash = load(manifest.ROOT, ref + "causal_flash_attention_roofline")
    ffn = load(manifest.ROOT, ref + "expert_ffn_ms_per_step")
    peak = manifest.peak("TPU v5 lite")
    # a program without the counters, an untraced run: nothing, no raise
    bare = _context(cell, [{"loss": 1.0}], peak=peak)
    assert [r(bare) for r in (pad, gmm, flash, ffn)] == [None] * 4
    steps = [{"moe_pairs": 4 * 16000.0, "moe_rows": 4 * 17024.0,
              "moe_load_max": 4 * 2600.0}] * 3
    # the window's routing may have drifted from the traced steps': the
    # roofline counts the work of the calls the trace holds
    later = [{**steps[0], "moe_pairs": 4 * 20000.0, "moe_rows": 4 * 21024.0}]
    assert pad(_context(cell, later)) == pytest.approx(100 * 1024 / 21024)
    ctx = _context(cell, steps, peak=peak, traced=steps * 3 + later * 0, kernels=[
        {"family": "grouped_matmul", "kind": "gmm", "calls": 240,
         "seconds": 0.5, "hbm_bytes": None},
        {"family": "grouped_matmul", "kind": "tgmm", "calls": 80,
         "seconds": 0.25, "hbm_bytes": None},
        {"family": "flash_attention", "kind": "fwd", "calls": 10,
         "seconds": 0.2, "hbm_bytes": None},
        {"family": "flash_attention", "kind": "dq", "calls": 10,
         "seconds": 0.3, "hbm_bytes": None},
        {"family": "flash_attention", "kind": "dkv", "calls": 10,
         "seconds": 0.4, "hbm_bytes": None},
    ])
    assert pad(ctx) == pytest.approx(100 * 1024 / 17024)
    load_note = ctx.notes["expert_load"]
    assert load_note["pairs_per_token"] == pytest.approx(16000 / 16384)
    assert load_note["max_over_mean_load"] == pytest.approx(2600 / 2000)
    call = 3 * 16000 * D * F_EXPERT          # FLOPs of a mean call
    assert gmm(ctx) == pytest.approx(
        100 * (320 * call / 197e12) / 0.75)
    assert ctx.notes["grouped_matmul"]["tgmm"]["bound"] == "compute"
    scores = 64 * (L * (L + 1) // 2) * 64 * 2
    assert flash(ctx) == pytest.approx(
        100 * (10 * 9 * scores / 197e12) / 0.9)
    assert ffn(ctx) == pytest.approx(75.0)
    assert ctx.notes["expert_ffn"]["share_of_device_ms_pct"] == pytest.approx(25.0)
    # a call the configuration's kinds do not explain: say nothing
    assert ctx.notes["expert_load_traced"]["steps"] == 9
    odd = _context(cell, steps, peak=peak, traced=steps, kernels=[
        {"family": "grouped_matmul", "kind": "unknown", "calls": 1,
         "seconds": 0.1, "hbm_bytes": None}])
    assert gmm(odd) is None


def test_the_harness_takes_the_cell_as_files_and_entries():
    """One untraced ``--rehearse`` run through ``Trainer.train()`` on the
    CPU, the tiny preset against the plain reference."""
    buf = io.StringIO()
    with contextlib.redirect_stdout(buf):
        rc = run.main(["--workload", CELL, "--rehearse", "--trace", "0",
                       "--seed", "2434000007", "--seconds", "2"])
    assert rc == 0
    line = json.loads(buf.getvalue().strip().splitlines()[-1])
    assert line["correct"] is True, line
    assert line["workload"] == CELL and line["failed"] == 0
    assert set(line["metrics"]) == {"samples_per_s", "setup_s"}
    assert all(m["value"] is None for m in line["metrics"].values())
    check = line["check"]
    assert check["ok"] and check["batch"] == 2
    assert check["grad_rel_err"] < 1e-3 and check["loss_rel"] < 1e-5

"""The harness as data: every name in BENCHMARK.json resolves to files,
and a cell can be added as files and entries only. (The contract's own
rules are the driver's to check; ``test_contract_shape`` pins the few
this benchmark's design rests on.)"""

import copy
import json
import os
import re

import pytest

from benchmark import manifest

import bench_tree
import cut_rule

BENCH = manifest.load()
CELLS = [w["name"] for w in BENCH["workloads"]]
LAYER_METRICS = [m["name"] for m in BENCH["per_layer"]]


def _config_entry(bench, name):
    return next(c for c in bench["configs"] if c["name"] == name)


def test_every_name_in_the_manifest_leads_to_its_file():
    assert manifest.validate() == []


def test_contract_shape():
    assert BENCH["paths"] == ["benchmark", "tests/benchmark"]
    assert BENCH["command"] == ["python3", "-m", "benchmark.run"]
    four = [w for w in BENCH["workloads"] if w["chips"] == 4]
    assert len(four) == 1, "one four-chip cell: it costs four times a run"
    for m in BENCH["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.1
    assert {m["name"] for m in BENCH["end_to_end"]} >= {"setup_s", "samples_per_s"}
    # the driver refuses a name or a layer with a space in it before any run
    plain = re.compile(r"[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}")
    named = [e for key in ("configs", "workloads", "end_to_end", "per_layer")
             for e in BENCH[key]]
    for text in [e["name"] for e in named] + [m["layer"] for m in BENCH["per_layer"]]:
        assert plain.fullmatch(text), text


@pytest.mark.parametrize("name", CELLS)
def test_every_cell_resolves_to_files_by_name(name):
    cell = manifest.resolve(name)
    assert cell.config["name"] == cell.config_name
    assert os.path.isfile(os.path.join(manifest.ROOT, cell.driver))
    assert cell.mix["driver"] == "train"
    assert [m["name"] for m in cell.end_to_end] == ["samples_per_s", "setup_s"]
    assert cell.per_layer, "every cell reports a per-layer metric"
    # the files a configuration names are there and say what they must
    flops = cell.module("flops").flops_per_sample(cell.config)
    assert flops > 1e9
    ref = cell.module("reference")
    assert set(ref.TOLERANCE) == {"loss_rel", "grad_norm_rel", "grad_rel_err"}
    assert cut_rule.problems(
        cell.config, _config_entry(BENCH, cell.config_name)) == []
    assert cell.config["assumed"]
    assert cell.config["tokens_per_sample"] >= 1


@pytest.mark.parametrize("name", LAYER_METRICS)
def test_every_per_layer_metric_is_a_file_with_a_reader(name):
    spec = manifest.layer_metric_file(manifest.ROOT, "benchmark", name)
    entry = next(m for m in BENCH["per_layer"] if m["name"] == name)
    for key, value in entry.items():
        assert spec[key] == value, f"{name}: {key} differs from its file's"
    assert callable(manifest.load_function(manifest.ROOT, spec["reader"]))
    assert spec["what"]


def test_ckpt_and_dp4_cells_differ_from_their_controls_by_one_factor():
    steady = manifest.resolve("resnet18_b4096")
    ckpt = manifest.resolve("resnet18_b4096_ckpt")
    assert steady.config == ckpt.config and steady.chips == ckpt.chips
    assert ckpt.mix["train_config"]["eval_freq"] > 0
    assert steady.mix["train_config"]["eval_freq"] == 0
    one = manifest.resolve("bert_base_b32_L512")
    four = manifest.resolve("bert_base_dp4_b128_L512")
    assert one.config == four.config and (one.chips, four.chips) == (1, 4)


def test_peak_lookup_knows_the_v5e_and_refuses_the_rest():
    v5e = manifest.peak("TPU v5 lite")
    assert v5e["bf16_flops_per_s"] == 197e12
    assert v5e["hbm_bytes_per_s"] == 819e9 and "v5e" in v5e["source"]
    for kind in ("TPU v4", "cpu", ""):
        with pytest.raises(manifest.ManifestError, match="not in peaks.json"):
            manifest.peak(kind)


def test_a_cell_is_added_as_files_and_entries_only(tmp_path):
    root = bench_tree.add_cell(str(tmp_path))
    assert manifest.validate(root) == []
    cell = manifest.resolve("lenet_tiny", root)
    assert cell.config["train_config"]["network"] == "LeNet"
    assert cell.mix_name == "train_short"
    assert [m["name"] for m in cell.per_layer][-1] == "loss_at_close"
    assert cell.module("flops").flops_per_sample(cell.config) > 1e6
    # the cells that were there still resolve, and do not see the new metric
    old = manifest.resolve("resnet18_b4096", root)
    assert "loss_at_close" not in [m["name"] for m in old.per_layer]
    # a configuration that is cut to one chip is files and entries too,
    # and so is one whose source spells its layer pattern its own way
    for name in ("lenet_cut_tiny", "lenet_patterned_tiny"):
        cut = manifest.resolve(name, root)
        entry = _config_entry(manifest.load(root), cut.config_name)
        assert entry["reduced"]
        assert cut_rule.problems(cut.config, entry) == []


#: A cut whose source gives its layer pattern as two lists of layer ids,
#: counted from 1, inside a nested block (the keys of the catalog's
#: linear-attention expert decoder with 27 layers): 32 chips share each
#: layer; kept are the leading dense layer and one whole period of four.
_NESTED_WIDTHS = {"hidden_size": 2304, "moe_intermediate_size": 1024,
                  "num_experts_per_token": 8, "num_shared_experts": 1,
                  "first_k_dense_replace": 1, "kv_lora_rank": 512}
_NESTED_BLOCK = {"head_dim": 128, "num_heads": 32, "short_conv_kernel_size": 4}
CUT_NESTED = {
    "reduced": ["num_hidden_layers", "linear_attn_config", "num_experts",
                "vocab_size"],
    "model": {**_NESTED_WIDTHS, "num_hidden_layers": 5, "num_experts": 8,
              "vocab_size": 20480,
              "linear_attn_config": {**_NESTED_BLOCK,
                                     "full_attn_layers": [4],
                                     "kda_layers": [1, 2, 3, 5]}},
    "published": {**_NESTED_WIDTHS, "num_hidden_layers": 27,
                  "num_experts": 256, "vocab_size": 163840,
                  "linear_attn_config": {
                      **_NESTED_BLOCK,
                      "full_attn_layers": [4, 8, 12, 16, 20, 24, 27],
                      "kda_layers": [i for i in range(1, 27) if i % 4]}},
    "deployment": {**bench_tree.CUT["deployment"], "chips_per_layer": 32,
                   "layer_ids_from": 1, "kept_layer_ids": [1, 2, 3, 4, 5]},
}
#: A cut whose source gives its layer pattern as a string, one character a
#: layer: the second period of six is kept, no dense layer leads.
CUT_STRING = {
    "reduced": ["num_hidden_layers", "hybrid_override_pattern",
                "n_routed_experts"],
    "model": {"hidden_size": 2688, "num_hidden_layers": 6,
              "hybrid_override_pattern": "MEME*E", "n_routed_experts": 16},
    "published": {"hidden_size": 2688, "num_hidden_layers": 24,
                  "hybrid_override_pattern": "MEME*E" * 4,
                  "n_routed_experts": 128},
    "deployment": {**bench_tree.CUT["deployment"], "layer_period": 6,
                   "leading_dense_layers": 0,
                   "kept_layer_ids": [6, 7, 8, 9, 10, 11]},
}


PATTERNED = bench_tree.CUT_PATTERNED


def _cut(base=bench_tree.CUT, **changes):
    """``base`` (a cut file) with ``block__key=value`` changes (``None``
    drops the key or the block), and the entry that agrees with it."""
    config = copy.deepcopy(base)
    for path, value in changes.items():
        *blocks, key = path.split("__")
        target = config
        for b in blocks:
            target = target[b]
        if value is None:
            del target[key]
        else:
            target[key] = value
    return config, {"reduced": list(config["reduced"])}


@pytest.mark.parametrize("config, entry, complaint", [
    ({"reduced": []}, {"reduced": []}, None),
    (*_cut(), None),
    # the contract's layout for a model in the catalog: sizes at top level
    ({**{k: v for k, v in bench_tree.CUT.items() if k != "model"},
      **bench_tree.CUT["model"]}, {"reduced": bench_tree.CUT["reduced"]},
     None),
    (*_cut(reduced=bench_tree.CUT["reduced"] + ["moe_intermediate_size"],
           model__moe_intermediate_size=512), "only counts may be named"),
    (*_cut(model__hidden_size=1024), "'hidden_size' differs from published"),
    (*_cut(model__num_experts=4), "under 8"),
    (*_cut(model__vocab_size=200192 // 16), "under an eighth"),
    (*_cut(model__num_hidden_layers=4), "keeps 3 layers after the 1 leading"),
    (*_cut(deployment__layer_period=6), "a whole period of 6"),
    (*_cut(model__num_experts=128), "not smaller than published"),
    (*_cut(deployment=None), "deployment missing"),
    (*_cut(deployment__chips_per_layer=None), "`chips_per_layer`"),
    (*_cut(published=None), "published missing"),
    (_cut()[0], {"reduced": []}, "BENCHMARK.json disagrees"),
    # a count is told by path, by shape or by name (PR 37)
    (*_cut(PATTERNED), None),
    (*_cut(CUT_NESTED), None),
    (*_cut(CUT_STRING), None),
    (*_cut(deployment__kept_layer_ids=[1, 2, 3, 4, 5]), None),
    (*_cut(reduced=bench_tree.CUT["reduced"] + [
               "n_dense_first_layers", "swa_num_attention_heads",
               "swa_num_key_value_heads"],
           model__n_dense_first_layers=1, published__n_dense_first_layers=2,
           model__swa_num_attention_heads=8,
           published__swa_num_attention_heads=64,
           model__swa_num_key_value_heads=2,
           published__swa_num_key_value_heads=16), None),
    (*_cut(CUT_NESTED, model__linear_attn_config__head_dim=64),
     "only counts may be named: 'linear_attn_config.head_dim' is a width"),
    (*_cut(CUT_NESTED, reduced=[k for k in CUT_NESTED["reduced"]
                                if k != "linear_attn_config"]),
     "'linear_attn_config.full_attn_layers', 'linear_attn_config.kda_layers'"
     " differ from published and 'linear_attn_config' is not in reduced"),
    (*_cut(PATTERNED, model__sliding_window_layout=[1, 1, 1, 1]),
     "'sliding_window_layout' keeps [1, 1, 1, 1], the published layers 0-3 "
     "are [0, 1, 1, 1]"),
    (*_cut(PATTERNED, deployment__kept_layer_ids=[0, 1, 2, 7]),
     "kept after the 0 leading dense are consecutive"),
    (*_cut(PATTERNED, deployment__kept_layer_ids=None),
     "deployment needs `kept_layer_ids`"),
    (*_cut(CUT_NESTED, deployment__kept_layer_ids=None),
     "which layers 'linear_attn_config.full_attn_layers', "
     "'linear_attn_config.kda_layers' keep"),
    (*_cut(CUT_NESTED, model__linear_attn_config__full_attn_layers=[8]),
     "'linear_attn_config.full_attn_layers' keeps [8]: a list of layer ids "
     "is strictly increasing and its ids lie within 1-5"),
    (*_cut(CUT_NESTED, model__linear_attn_config__kda_layers=[1, 2, 3]),
     "'linear_attn_config.kda_layers' keeps [1, 2, 3], the published ids "
     "among layers 1-5, renumbered, are [1, 2, 3, 5]"),
    (*_cut(CUT_NESTED, deployment__layer_ids_from=None,
           deployment__kept_layer_ids=[0, 1, 2, 3, 4],
           model__linear_attn_config__kda_layers=[1, 2, 3]),
     "'linear_attn_config.full_attn_layers' is neither"),
    (*_cut(PATTERNED, reduced=PATTERNED["reduced"] + ["mrope_section"],
           model__mrope_section=[8, 12, 12],
           published__mrope_section=[16, 24, 24]),
     "'mrope_section' is neither a per-layer pattern (one entry for each of "
     "the 52 published layers) nor a list of layer ids"),
    (*_cut(PATTERNED, model__rope_layout=[0, 1, 1]),
     "a per-layer pattern has one entry for each of the 4 kept layers"),
    (*_cut(CUT_STRING, model__hybrid_override_pattern="MEMEME"),
     "keeps 'MEMEME', the published layers 6-11 are 'MEME*E'"),
    (*_cut(PATTERNED, deployment__kept_layer_ids=[0, 1, 2]),
     "kept_layer_ids gives the published index of each of the 4 kept"),
    *[(*_cut(PATTERNED, reduced=PATTERNED["reduced"] + [key],
             **{f"model__{key}": kept, f"published__{key}": full}),
       f"only counts may be named: {key!r} is a width or a setting")
      for key, kept, full in (("num_shared_experts", 1, 2),
                              ("moe_num_active_primary_experts", 3, 6),
                              ("num_nextn_predict_layers", 1, 3),
                              ("dense_mlp_idx", 1, 2))],
], ids=["nothing_reduced", "good_cut", "good_cut_top_level", "a_width",
        "a_width_unlisted", "four_experts", "a_sixteenth_of_the_vocabulary",
        "three_layers_after_the_dense", "under_a_whole_period",
        "listed_but_not_cut", "no_deployment", "no_chips_per_layer",
        "no_published", "manifest_disagrees",
        "good_patterned_cut", "good_nested_id_lists", "good_layer_string",
        "good_cut_says_its_layers", "the_catalogs_other_spellings",
        "a_nested_width", "a_nested_block_unlisted",
        "four_window_layers_no_global", "a_layer_skipped_in_the_period",
        "a_pattern_with_no_kept_layer_ids", "id_lists_with_no_kept_layer_ids",
        "an_id_past_the_kept_depth", "ids_not_the_published_ones",
        "ids_from_one_read_from_nought", "a_list_of_neither_shape",
        "a_pattern_of_another_length", "a_string_not_the_published_one",
        "kept_layer_ids_too_few", "shared_experts", "experts_a_token",
        "prediction_modules", "an_index_or_a_count"])
def test_reduced_is_held_to_the_guides_floors(config, entry, complaint):
    found = cut_rule.problems(config, entry)
    if complaint is None:
        assert found == []
    else:
        assert len(found) == 1 and complaint in found[0], found


def _edit_manifest(root, edit):
    path = os.path.join(root, "BENCHMARK.json")
    with open(path) as f:
        bench = json.load(f)
    edit(bench)
    with open(path, "w") as f:
        json.dump(bench, f)


def _edit_json(root, rel, **changes):
    path = os.path.join(root, rel)
    with open(path) as f:
        data = json.load(f)
    data.update(changes)
    with open(path, "w") as f:
        json.dump(data, f)


METRIC = "benchmark/layer_metrics/device_ms_per_step.json"


@pytest.mark.parametrize("breakage, complaint", [
    (lambda r: _edit_manifest(r, lambda b: b["workloads"][0].update(
        config="nope")), "unknown config 'nope'"),
    (lambda r: _edit_manifest(r, lambda b: b["workloads"][0].update(
        traffic="train_nope")), "no mix file benchmark/mixes/train_nope.json"),
    (lambda r: os.remove(os.path.join(
        r, "benchmark/configs/bert_base_mlm.json")), "bert_base_b32_L512"),
    (lambda r: _edit_json(r, "benchmark/mixes/train_dp4.json",
                          driver="generate"), "no driver"),
    (lambda r: os.remove(os.path.join(
        r, "benchmark/flops/resnet18_cifar10.py")), "no flops file"),
    (lambda r: os.remove(os.path.join(r, METRIC)), "has no file"),
    (lambda r: _edit_json(r, METRIC, moves="setup_s"),
     "['moves'] differ from its file's"),
    (lambda r: _edit_json(r, METRIC,
                          reader="benchmark/readers/device.py:nope"),
     "no such function"),
])
def test_validate_names_what_does_not_lead_to_its_file(tmp_path, breakage,
                                                       complaint):
    root = bench_tree.add_cell(str(tmp_path))
    breakage(root)
    found = manifest.validate(root)
    assert any(complaint in p for p in found), found

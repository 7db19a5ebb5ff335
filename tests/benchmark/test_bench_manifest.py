"""The harness as data: every name in BENCHMARK.json resolves to files,
and a cell can be added as files and entries only. (The contract's own
rules are the driver's to check; ``test_contract_shape`` pins the few
this benchmark's design rests on.)"""

import json
import os
import re

import pytest

from benchmark import manifest

import bench_tree

BENCH = manifest.load()
CELLS = [w["name"] for w in BENCH["workloads"]]
LAYER_METRICS = [m["name"] for m in BENCH["per_layer"]]


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
    assert cell.config["reduced"] == [] and cell.config["assumed"]
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

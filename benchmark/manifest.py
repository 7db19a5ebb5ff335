"""``BENCHMARK.json`` -> files, by name. jax-free.

A cell is one ``workloads`` entry. Resolving it touches only data:

    workloads[name] -> configs[config].file          (sizes, TrainConfig)
                    -> benchmark/mixes/<traffic>.json (overrides, driver)
                    -> benchmark/drivers/<driver>.py  (named by the mix)
    per_layer[*]    -> benchmark/layer_metrics/<metric>.json
                    -> "<file>.py:<function>"         (its reader)

Python files are loaded by path, not by package name, so a copy of the
tree that only *adds* files (a new configuration, mix, driver, reader)
resolves with no edit to a file that is there.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
import os
import re
from typing import Any, Callable, List, Optional

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


class ManifestError(ValueError):
    pass


def _read_json(path: str) -> Any:
    with open(path) as f:
        return json.load(f)


def load(root: str = ROOT) -> dict:
    return _read_json(os.path.join(root, "BENCHMARK.json"))


def load_module(root: str, relpath: str):
    """Import ``<root>/<relpath>`` by path (a module of its own, named
    after the file — never through ``sys.modules['benchmark']``)."""
    path = os.path.join(root, relpath)
    if not os.path.isfile(path):
        raise ManifestError(f"no such file: {relpath}")
    name = "_benchmark_file_" + re.sub(r"\W", "_", os.path.abspath(path))
    spec = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def load_function(root: str, ref: str) -> Callable:
    """``"benchmark/readers/device.py:device_ms_per_step"`` -> callable."""
    relpath, _, func = ref.partition(":")
    module = load_module(root, relpath)
    if not func or not callable(getattr(module, func, None)):
        raise ManifestError(f"{ref}: no such function")
    return getattr(module, func)


@dataclasses.dataclass
class Cell:
    """One workload, resolved to its files."""

    name: str
    chips: int
    why: str
    config_name: str
    config: dict            # the configuration file, as it is run
    mix_name: str
    mix: dict               # the mix file
    driver: str             # relative path of the driver module
    end_to_end: List[dict]  # the metrics this cell reports, untraced
    per_layer: List[dict]   # ... and traced; each with its "reader"
    root: str = ROOT
    run_seconds: int = 0

    def module(self, key: str):
        """The module a configuration names under ``key`` (its FLOPs
        function, its plain reference)."""
        return load_module(self.root, self.config[key])


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def layer_metric_file(root: str, bench_dir: str, name: str) -> dict:
    path = os.path.join(bench_dir, "layer_metrics", name + ".json")
    if not os.path.isfile(os.path.join(root, path)):
        raise ManifestError(f"per-layer metric {name!r} has no file {path}")
    return _read_json(os.path.join(root, path))


def resolve(name: str, root: str = ROOT, bench: Optional[dict] = None) -> Cell:
    bench = bench if bench is not None else load(root)
    bench_dir = bench["paths"][0]
    entries = {w["name"]: w for w in bench["workloads"]}
    if name not in entries:
        raise ManifestError(
            f"unknown workload {name!r}; BENCHMARK.json has {sorted(entries)}")
    w = entries[name]
    configs = {c["name"]: c for c in bench["configs"]}
    if w["config"] not in configs:
        raise ManifestError(f"{name}: unknown config {w['config']!r}")
    config = _read_json(os.path.join(root, configs[w["config"]]["file"]))
    mix_path = os.path.join(bench_dir, "mixes", w["traffic"] + ".json")
    if not os.path.isfile(os.path.join(root, mix_path)):
        raise ManifestError(f"{name}: no mix file {mix_path}")
    mix = _read_json(os.path.join(root, mix_path))
    driver = os.path.join(bench_dir, "drivers", mix["driver"] + ".py")
    if not os.path.isfile(os.path.join(root, driver)):
        raise ManifestError(f"{mix_path}: no driver {driver}")
    per_layer = []
    for m in bench["per_layer"]:
        if _applies(m, name):
            spec = layer_metric_file(root, bench_dir, m["name"])
            per_layer.append({**m, "reader": spec["reader"]})
    return Cell(
        name=name, chips=int(w["chips"]), why=w["why"],
        config_name=w["config"], config=config,
        mix_name=w["traffic"], mix=mix, driver=driver,
        end_to_end=[m for m in bench["end_to_end"] if _applies(m, name)],
        per_layer=per_layer, root=root,
        run_seconds=int(bench["run_seconds"]),
    )


def peak(device_kind: str, root: str = ROOT, bench: Optional[dict] = None) -> dict:
    """The published peaks of one device kind. A device that is not in
    the table is an error, never a default."""
    bench = bench if bench is not None else load(root)
    table = _read_json(os.path.join(root, bench["paths"][0], "peaks.json"))
    if device_kind not in table:
        raise ManifestError(
            f"device_kind {device_kind!r} is not in peaks.json "
            f"(has {sorted(table)}): add its published peaks with their "
            "source; an unknown device never borrows another's")
    return table[device_kind]


def validate(root: str = ROOT) -> List[str]:
    """Every name in ``BENCHMARK.json`` that does not lead to its file, as
    text. The contract's own rules (counts, bounds, what ``reduced`` may
    name) are the driver's to check and are not copied here."""
    bad: List[str] = []
    bench = load(root)
    for w in bench["workloads"]:
        try:
            cell = resolve(w["name"], root, bench)
            for key in ("flops", "reference"):
                if not os.path.isfile(os.path.join(root, cell.config[key])):
                    bad.append(f"{w['name']}: no {key} file {cell.config[key]}")
        except (ManifestError, OSError, KeyError, ValueError) as e:
            bad.append(f"{w['name']} does not resolve: {e}")
    for m in bench["per_layer"]:
        try:
            spec = layer_metric_file(root, bench["paths"][0], m["name"])
            differ = [k for k in m if spec.get(k) != m[k]]
            if differ:
                bad.append(f"{m['name']}: {differ} differ from its file's")
            load_function(root, spec["reader"])
        except (ManifestError, OSError, KeyError) as e:
            bad.append(f"{m['name']}: {e}")
    return bad

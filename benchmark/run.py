"""One run of one cell, one JSON result line.

    python3 -m benchmark.run --workload <cell> --seed <n> --seconds <s> --trace <0|1>

runs from the root of a checkout, in one process that holds the cell's
chips. Without a TPU of a kind ``peaks.json`` knows, or with fewer chips
than the cell asks for, it exits non-zero and prints nothing on stdout.
``--rehearse`` (the benchmark's own flag, for its tests) runs the cell's
tiny ``rehearse`` shapes on whatever backend jax has and prints every
metric as ``null``: a CPU number is never written under a device metric's
name.

The last line of stdout is the result: ``correct``, ``attempted``,
``failed``, ``metrics`` (the cell's end-to-end metrics untraced, its
per-layer metrics traced), ``device`` and, traced, ``breakdown``. Keys
beyond those (``check``, ``phases_s``, ``cache``, ``memory_stats``,
``notes``) are for PERF.md and are ignored by the driver.
"""

from __future__ import annotations

import time

T0 = time.monotonic()  # as near process start as Python code gets

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import sys  # noqa: E402
from typing import Optional  # noqa: E402

from benchmark import manifest  # noqa: E402


@dataclasses.dataclass
class Context:
    """What a per-layer reader is handed."""

    cell: manifest.Cell          # with the configuration as it was run
    result: dict                 # the driver's record of the run
    peak: Optional[dict]         # this device kind's row of peaks.json
    trace: Optional[dict]        # benchmark.trace.summarize(), traced runs
    flops_per_sample: float
    notes: dict                  # readers may leave remarks for PERF.md


def parse(argv):
    p = argparse.ArgumentParser(prog="python3 -m benchmark.run")
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--seconds", type=float, default=None)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    p.add_argument("--rehearse", action="store_true",
                   help="tiny shapes, any backend, every metric null")
    p.add_argument("--root", default=manifest.ROOT, help=argparse.SUPPRESS)
    return p.parse_args(argv)


class Refusal(Exception):
    """Why this run cannot be a measurement; exit non-zero, print no result."""


def devices_and_peak(cell, rehearse: bool, root: str):
    """jax's devices and this device kind's row of ``peaks.json`` (None
    under ``--rehearse``), or a ``Refusal``. Initialises the backend."""
    import jax

    devices = jax.devices()
    platform = devices[0].platform
    if len(devices) < cell.chips:
        raise Refusal(f"{cell.name} needs {cell.chips} chips, jax sees "
                      f"{len(devices)} {platform} device(s)")
    if rehearse:
        return devices, None
    if platform != "tpu":
        raise Refusal(f"no TPU: jax's devices are {platform!r}. A "
                      "measurement never falls back to another backend "
                      "(--rehearse runs the tiny shapes anywhere).")
    try:
        return devices, manifest.peak(devices[0].device_kind, root)
    except manifest.ManifestError as e:
        raise Refusal(str(e)) from None


def metric_line(metrics: list, values: dict, null: bool) -> dict:
    out = {}
    for m in metrics:
        v = values.get(m["name"])
        if null:
            out[m["name"]] = {"value": None, "unit": m["unit"]}
        elif v is not None and math.isfinite(v):
            out[m["name"]] = {"value": v, "unit": m["unit"]}
    return out


def main(argv=None) -> int:
    try:
        return run(parse(argv))
    except Refusal as e:
        print(f"benchmark.run: {e}", file=sys.stderr)
        return 3


def run(args) -> int:
    try:
        bench = manifest.load(args.root)
        cell = manifest.resolve(args.workload, args.root, bench)
    except (manifest.ManifestError, OSError) as e:
        raise Refusal(str(e)) from None
    seconds = args.seconds if args.seconds is not None else cell.run_seconds
    try:
        from pytorch_distributed_nn_tpu.utils import compile_cache
    except ImportError as e:
        raise Refusal(f"the program is not in this directory: {e}") from None
    t_imported = time.monotonic()
    devices, peak = devices_and_peak(cell, args.rehearse, args.root)
    t_devices = time.monotonic()

    import jax

    # the program's own rule for where the compile cache lives
    # (JAX_COMPILATION_CACHE_DIR, else <checkout>/.jax_cache; none on
    # CPU); every program goes into it, however quickly it compiled
    compile_cache.configure()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)

    driver = manifest.load_module(args.root, cell.driver)
    result = driver.run(cell, seed=args.seed, seconds=seconds,
                        trace=bool(args.trace), rehearse=args.rehearse,
                        t0=T0, t_ready=t_devices)
    cell = dataclasses.replace(cell, config=result["config"])

    summary = None
    if args.trace and result.get("xplane"):
        from benchmark import trace

        summary = trace.summarize(trace.load(result["xplane"]),
                                  kernels=cell.config.get("kernels"))
    ctx = Context(
        cell=cell, result=result, peak=peak, trace=summary,
        flops_per_sample=cell.module("flops").flops_per_sample(cell.config),
        notes={},
    )

    device = {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
        # peak_bytes_in_use + peak_bytes_reserved of the fullest chip
        # (drivers/train.py peak_bytes says why the sum); the line's
        # "memory_stats" has the two counters apart
        "memory_peak_bytes": result["memory_peak_bytes"],
    }
    line = {"correct": result["correct"]}
    w = result["window"]
    line["attempted"], line["failed"] = w.attempted, w.failed
    if args.trace:
        values = {}
        for m in cell.per_layer:
            reader = manifest.load_function(args.root, m["reader"])
            values[m["name"]] = reader(ctx)
        line["metrics"] = metric_line(cell.per_layer, values, args.rehearse)
        if summary is not None and not args.rehearse:
            device["busy_s"] = summary["busy_s"]
            device["window_s"] = summary["window_s"]
            line["breakdown"] = {
                "device_ops": summary["device_ops"][:10],
                "idle_gaps": summary["idle_gaps"][:10],
            }
    else:
        line["metrics"] = metric_line(
            cell.end_to_end, result["end_to_end"], args.rehearse)
    line["device"] = device
    line["workload"] = cell.name
    line["check"] = result["check"]
    line["checkpoint_verified"] = result["checkpoint_verified"]
    line["problems"] = w.problems
    line["cache"] = result["cache"]
    line["memory_stats"] = result.get("memory_stats")
    line["notes"] = ctx.notes
    if not args.rehearse:
        line["phases_s"] = {"imports": t_imported - T0,
                            "backend_up": t_devices - t_imported,
                            **result["phases_s"]}
        line["window"] = {
            "steps": w.n_steps, "wall_s": w.wall_s, "log_windows": len(w.walls),
            "saves": len(w.saves_started),
        }
        if peak and w.samples_per_s:
            line["mfu_wall_pct"] = (
                100.0 * w.samples_per_s * ctx.flops_per_sample
                / (cell.chips * peak["bf16_flops_per_s"]))
    sys.stdout.flush()
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

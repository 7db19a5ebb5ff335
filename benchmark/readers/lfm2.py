"""Per-layer metrics of the expert layer and of causal attention
(configuration ``lfm2_8b_a1b_ep4``): roofline shares of the grouped-matmul
and causal flash-attention calls in the trace, the expert calls' device
time a step, and the share of the grouped matmul's rows that is padding,
from the program's own counters. jax-free.

Every reader returns nothing where what it reads is absent: a program
without the expert layer's counters, a configuration without these
kernels, an untraced run.
"""

from __future__ import annotations

import statistics

from benchmark import manifest

GMM, FLASH = "grouped_matmul", "flash_attention"


def _costs(ctx):
    rel = ctx.cell.config.get("kernel_costs")
    return manifest.load_module(ctx.cell.root, rel) if rel else None


def _traced_steps(ctx) -> list:
    """The step records of the steps the trace holds: the program traces
    steps start+2 .. start+1+trace_steps of the driver's second call
    (``drivers/train.py``), which starts after the warm-up windows."""
    config = ctx.cell.config
    log_every = int({**config["train_config"],
                     **ctx.cell.mix.get("train_config", {})}["log_every"])
    start = int(ctx.cell.mix["warmup_windows"]) * log_every
    traced = range(start + 2, start + 2 + int(config["trace_steps"]))
    return [r for r in ctx.result.get("records", [])
            if r.get("kind") == "step" and r.get("step") in traced]


def _counters(ctx, steps):
    """Means over ``steps`` (step records) of the expert layers' counters
    (each summed over the expert layers by the step), with what follows
    from them; None where the program counts none."""
    steps = [r for r in steps if "moe_pairs" in r]
    if not steps:
        return None
    m = ctx.cell.config["model"]
    layers = sum(i >= m["num_dense_layers"]
                 for i in range(m["num_hidden_layers"]))
    tokens = (ctx.result["global_batch"] // ctx.cell.chips
              * ctx.cell.config["tokens_per_sample"])
    pairs = statistics.fmean(r["moe_pairs"] for r in steps)
    rows = statistics.fmean(r["moe_rows"] for r in steps)
    fullest = statistics.fmean(r["moe_load_max"] for r in steps)
    return {
        "steps": len(steps),
        "expert_layers": layers,
        "pairs_per_layer": pairs / layers,
        "rows_per_layer": rows / layers,
        "pairs_per_token": pairs / layers / tokens,
        "max_over_mean_load": fullest / (pairs / m["num_experts"]),
    }


def _family(ctx, family: str):
    if not ctx.trace:
        return []
    return [r for r in ctx.trace["kernels"] if r["family"] == family]


def _share(ctx, family: str, costs: dict):
    """100 x least time / device time over the family's calls, per kind in
    the line's ``notes[family]``: the accepted kernels' own reduction."""
    shared = manifest.load_module(ctx.cell.root, "benchmark/readers/kernels.py")
    return shared._share(ctx, family, costs)


def grouped_matmul_roofline(ctx):
    config = ctx.cell.config
    spec = config.get("kernels", {}).get(GMM)
    # the pairs of the steps the trace holds: the FLOPs those calls did
    costs, counted = _costs(ctx), _counters(ctx, _traced_steps(ctx))
    if not spec or costs is None or counted is None:
        return None
    ctx.notes["expert_load_traced"] = counted
    m = config["model"]
    return _share(ctx, GMM, costs.grouped_matmul(
        counted["pairs_per_layer"], m["hidden_size"],
        m["moe_intermediate_size"], m["num_experts"], spec["itemsize"]))


def causal_flash_attention_roofline(ctx):
    config = ctx.cell.config
    spec = config.get("kernels", {}).get(FLASH)
    costs = _costs(ctx)
    if not spec or costs is None:
        return None
    m = config["model"]
    return _share(ctx, FLASH, costs.causal_flash_attention(
        ctx.result["global_batch"] // ctx.cell.chips,
        m["num_attention_heads"], config["tokens_per_sample"],
        m["head_dim"], spec["itemsize"]))


def expert_ffn_ms_per_step(ctx):
    rows = _family(ctx, GMM)
    if not rows or not ctx.trace["steps"]:
        return None
    ms = 1e3 * sum(r["seconds"] for r in rows) / ctx.trace["steps"]
    busy_ms = 1e3 * ctx.trace["busy_s"] / ctx.trace["steps"]
    ctx.notes["expert_ffn"] = {
        "share_of_device_ms_pct": 100.0 * ms / busy_ms if busy_ms else None,
        "calls_per_step": sum(r["calls"] for r in rows) / ctx.trace["steps"],
    }
    return ms


def expert_pad_rows_pct(ctx):
    counted = _counters(ctx, ctx.result["window"].steps)
    if counted is None or not counted["rows_per_layer"]:
        return None
    ctx.notes["expert_load"] = counted
    return 100.0 * (counted["rows_per_layer"] - counted["pairs_per_layer"]
                    ) / counted["rows_per_layer"]

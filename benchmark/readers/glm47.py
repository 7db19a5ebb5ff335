"""Per-layer metrics of latent attention and of the expert layers with a
shared expert (configuration ``glm47_flash_ep8``): the roofline share of
the flash-attention calls on latent attention's heads, their device time a
step, the expert calls' device time a step, and the share of the grouped
matmul's rows that is padding, from the program's own counters. jax-free.

Every reader returns nothing where what it reads is absent: a program
without the expert layer's counters, a configuration without these
kernels, an untraced run.
"""

from __future__ import annotations

import statistics

from benchmark import manifest

MLA = "mla_attention"


def _module(ctx, relpath: str):
    return manifest.load_module(ctx.cell.root, relpath)


def _busy_ms(ctx):
    return 1e3 * ctx.trace["busy_s"] / ctx.trace["steps"]


def mla_attention_roofline(ctx):
    """100 x least time / device time over the ``mla`` calls, per kind in
    the line's ``notes["mla_attention"]``, against the causal count at
    the configuration's query-key and value widths."""
    config = ctx.cell.config
    spec = config.get("kernels", {}).get(MLA)
    rel = config.get("kernel_costs")
    if not spec or not rel:
        return None
    m = config["model"]
    costs = _module(ctx, rel).attention(
        ctx.result["global_batch"] // ctx.cell.chips,
        m["num_attention_heads"], config["tokens_per_sample"],
        m["qk_nope_head_dim"] + m["qk_rope_head_dim"], m["v_head_dim"],
        spec["itemsize"])
    return _module(ctx, "benchmark/readers/kernels.py")._share(
        ctx, MLA, costs)


def mla_attention_ms_per_step(ctx):
    if not ctx.trace or not ctx.trace["steps"]:
        return None
    rows = [r for r in ctx.trace["kernels"] if r["family"] == MLA]
    if not rows:
        return None
    ms = 1e3 * sum(r["seconds"] for r in rows) / ctx.trace["steps"]
    busy_ms = _busy_ms(ctx)
    ctx.notes["mla_attention_time"] = {
        "share_of_device_ms_pct": 100.0 * ms / busy_ms if busy_ms else None,
        "calls_per_step": sum(r["calls"] for r in rows) / ctx.trace["steps"],
    }
    return ms


def glm_expert_ffn_ms_per_step(ctx):
    return _module(ctx, "benchmark/readers/lfm2.py").expert_ffn_ms_per_step(
        ctx)


def glm_expert_pad_rows_pct(ctx):
    """From the window's step records, summed there over the expert
    layers: the kept ones after the dense layers and the prediction
    modules'."""
    steps = [r for r in ctx.result["window"].steps if "moe_pairs" in r]
    if not steps:
        return None
    pairs, rows, fullest = (statistics.fmean(r[key] for r in steps) for key in
                            ("moe_pairs", "moe_rows", "moe_load_max"))
    if not pairs or not rows:
        return None
    m = ctx.cell.config["model"]
    layers = _module(ctx, ctx.cell.config["flops"]).expert_layers(m)
    tokens = (ctx.result["global_batch"] // ctx.cell.chips
              * ctx.cell.config["tokens_per_sample"])
    ctx.notes["glm_expert_load"] = {
        "steps": len(steps),
        "expert_layers": layers,
        "pairs_per_token": pairs / layers / tokens,
        "even_pairs_per_token": m["num_experts_per_tok"]
        * m["n_routed_experts"] / m["router_width"],
        "max_over_mean_load": fullest / (pairs / m["n_routed_experts"]),
    }
    return 100.0 * (rows - pairs) / rows

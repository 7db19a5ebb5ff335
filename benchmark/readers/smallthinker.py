"""Per-layer metrics of window and global attention and of the ReLU-gated
expert layer (configuration ``smallthinker_21b_a3b_ep8``): roofline shares
of the two families of flash-attention calls in the trace, both families'
device time a step, the expert calls' device time a step, and the share of
the grouped matmul's rows that is padding, from the program's own
counters. jax-free.

Every reader returns nothing where what it reads is absent: a program
without the expert layer's counters, a configuration without these
kernels, an untraced run.
"""

from __future__ import annotations

import statistics

from benchmark import manifest

WINDOW, GLOBAL = "window_attention", "global_attention"


def _module(ctx, relpath: str):
    return manifest.load_module(ctx.cell.root, relpath)


def _attention_roofline(ctx, family: str):
    """100 x least time / device time over one family's calls, per kind in
    the line's ``notes[family]``. The score count is the family's own: its
    ``scores`` in the configuration's ``kernels`` block says ``banded``
    (the model's window) or ``causal``."""
    config = ctx.cell.config
    spec = config.get("kernels", {}).get(family)
    rel = config.get("kernel_costs")
    if not spec or not rel:
        return None
    m = config["model"]
    window = m["sliding_window_size"] if spec["scores"] == "banded" else None
    costs = _module(ctx, rel).attention(
        ctx.result["global_batch"] // ctx.cell.chips,
        m["num_attention_heads"], config["tokens_per_sample"],
        m["head_dim"], window, spec["itemsize"])
    return _module(ctx, "benchmark/readers/kernels.py")._share(
        ctx, family, costs)


def window_attention_roofline(ctx):
    return _attention_roofline(ctx, WINDOW)


def global_attention_roofline(ctx):
    return _attention_roofline(ctx, GLOBAL)


def attention_ms_per_step(ctx):
    if not ctx.trace or not ctx.trace["steps"]:
        return None
    steps = ctx.trace["steps"]
    ms = {family: 1e3 * sum(r["seconds"] for r in ctx.trace["kernels"]
                            if r["family"] == family) / steps
          for family in (WINDOW, GLOBAL)}
    total = sum(ms.values())
    if not total:
        return None
    busy_ms = 1e3 * ctx.trace["busy_s"] / steps
    ctx.notes["attention"] = {
        "share_of_device_ms_pct": 100.0 * total / busy_ms if busy_ms else None,
        "ms_per_step": ms,
    }
    return total


def relu_expert_ffn_ms_per_step(ctx):
    return _module(ctx, "benchmark/readers/lfm2.py").expert_ffn_ms_per_step(
        ctx)


def relu_expert_pad_rows_pct(ctx):
    """From the window's step records; every layer is an expert layer."""
    steps = [r for r in ctx.result["window"].steps if "moe_pairs" in r]
    if not steps:
        return None
    pairs, rows, fullest = (statistics.fmean(r[key] for r in steps) for key in
                            ("moe_pairs", "moe_rows", "moe_load_max"))
    if not pairs or not rows:
        return None
    m = ctx.cell.config["model"]
    tokens = (ctx.result["global_batch"] // ctx.cell.chips
              * ctx.cell.config["tokens_per_sample"])
    ctx.notes["relu_expert_load"] = {
        "steps": len(steps),
        "pairs_per_token": pairs / m["num_hidden_layers"] / tokens,
        "max_over_mean_load": fullest / (pairs / m["moe_num_primary_experts"]),
    }
    return 100.0 * (rows - pairs) / rows

"""Roofline shares of the Pallas kernels: the least time the chip could
take for the calls the trace holds — the larger of operations over peak
FLOP/s and bytes over peak bytes/s, from shapes (``benchmark/flops/
kernels.py``) — over the device time those calls took. jax-free."""

from __future__ import annotations

from benchmark import manifest


def _formulas(ctx):
    return manifest.load_module(ctx.cell.root, "benchmark/flops/kernels.py")


def _per_chip_batch(ctx) -> int:
    return ctx.result["global_batch"] // ctx.cell.chips


def _share(ctx, family: str, costs: dict):
    if not ctx.trace:
        return None
    rows = [r for r in ctx.trace["kernels"] if r["family"] == family]
    if not rows:
        return None
    min_seconds = _formulas(ctx).min_seconds
    least = took = 0.0
    detail = {}
    for r in rows:
        cost = costs.get(r["kind"])
        if cost is None:
            return None     # a call the shapes do not explain: say nothing
        seconds, bound = min_seconds(cost, ctx.peak)
        least += seconds * r["calls"]
        took += r["seconds"]
        detail[r["kind"]] = {
            "calls": r["calls"], "ms_per_call": 1e3 * r["seconds"] / r["calls"],
            "roofline_pct": 100.0 * seconds * r["calls"] / r["seconds"],
            "bound": bound,
        }
    ctx.notes[family] = detail
    return 100.0 * least / took if took else None


def flash_attention_roofline(ctx):
    config = ctx.cell.config
    spec = config.get("kernels", {}).get("flash_attention")
    if not spec:
        return None
    m = config["model"]
    costs = _formulas(ctx).flash_attention(
        _per_chip_batch(ctx), m["num_attention_heads"],
        config["tokens_per_sample"], m["head_dim"], spec["itemsize"])
    return _share(ctx, "flash_attention", costs)


def fused_ln_roofline(ctx):
    config = ctx.cell.config
    spec = config.get("kernels", {}).get("fused_ln")
    if not spec:
        return None
    rows = _per_chip_batch(ctx) * config["tokens_per_sample"]
    costs = _formulas(ctx).fused_layer_norm(
        rows, config["model"]["hidden_size"], spec["in_itemsize"],
        spec["out_itemsize"])
    return _share(ctx, "fused_ln", costs)

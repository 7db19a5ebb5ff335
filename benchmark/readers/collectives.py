"""Gradient-sync metrics from the traced window's reduction. jax-free."""

from __future__ import annotations


def collective_ms_per_step(ctx):
    """Time a collective was in flight (start to done), per step, mean of
    the chips. Absent on one chip: there is no collective to read."""
    t = ctx.trace
    if not t or not t["steps"] or not t["collectives"]["count"]:
        return None
    return 1000.0 * t["collectives"]["in_flight_s"] / t["steps"]


def collective_exposed_ms_per_step(ctx):
    """The part of that time during which no other operation ran on the
    chip: what the backward pass failed to hide."""
    t = ctx.trace
    if not t or not t["steps"] or not t["collectives"]["count"]:
        return None
    return 1000.0 * t["collectives"]["exposed_s"] / t["steps"]

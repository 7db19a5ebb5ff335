"""Straggler-tolerant aggregation: deadline-based K-of-N gradient dropping.

The reference's backup-worker design (Chen et al., "Revisiting Distributed
Synchronous SGD"; src/sync_replicas_master_nn.py:179-182) let the PS take
the first ``num_aggregate`` gradients per step and drop the rest — the
slowest workers never block the update. Our PS emulation reproduces the
*fixed-K* policy (grad_sync mode="ps"); this module adds the *deadline*
policy the reference's timeout-kill mode approximated
(src/model_ops/resnet_split.py:617-728): a contribution slower than
``deadline`` seconds is dropped, however many that is, and the aggregate is
renormalized by the live contributor count.

Under single-program SPMD no rank is ever actually late — the collective is
compiled in — so arrival times are *simulated*: a seeded per-(step, rank)
draw (lognormal-shaped: ``mean * exp(sigma * N(0,1))``), plus any
``delay@step[:pR]`` entries from the run's FaultPlan. Because every replica
draws the identical time vector from the shared sync key, each replica
knows the full arrival picture: its own 0/1 contribution mask AND the
global report (who was dropped, observed skew) — no extra collectives.

Unbiasedness: dropping is independent of the gradient *values* (times are
a function of (key, step, rank) only), and the masked sum is renormalized
by the realized contributor count, so the update is an unweighted average
of a random subset of i.i.d. per-shard gradient estimates — unbiased in
expectation, with variance growing as contributors shrink. That is the
same trade the backup-worker paper makes; docs/resilience.md quantifies
it. ``min_keep`` guarantees the fastest K contributions always land, so a
pathological deadline can never produce an empty (0/0) update.
"""

from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

# Dropped-rank bitmask is reported while every rank index fits exact f32
# integer arithmetic through the metrics pmean (2^24); past that only the
# count/skew scalars are reported.
_MAX_MASK_RANKS = 24


@dataclasses.dataclass(frozen=True)
class StragglerSim:
    """Seeded arrival-time model + deadline drop policy for the DP sync.

    deadline: simulated seconds after which a contribution is dropped.
    min_keep: the fastest ``min_keep`` ranks always contribute (backup-
        worker floor: the update can never go empty).
    mean/sigma: arrival model ``mean * exp(sigma * N(0, 1))`` — per
        (step, rank), deterministic given the sync key.
    delays: ``((step, rank_or_None, seconds), ...)`` injected extra
        latencies (FaultPlan.delay_table()); ``rank=None`` hits every rank.
    """

    deadline: float
    min_keep: int = 1
    mean: float = 0.1
    sigma: float = 0.1
    delays: Tuple[Tuple[int, Optional[int], float], ...] = ()

    def __post_init__(self):
        if self.deadline <= 0:
            raise ValueError(f"deadline must be > 0, got {self.deadline}")
        if self.min_keep < 1:
            raise ValueError(f"min_keep must be >= 1, got {self.min_keep}")
        if self.mean <= 0 or self.sigma < 0:
            raise ValueError(
                f"arrival model needs mean > 0, sigma >= 0 "
                f"(got mean={self.mean}, sigma={self.sigma})"
            )

    def times(self, key, step, n: int) -> jnp.ndarray:
        """(n,) simulated arrival seconds for 1-indexed ``step``.

        ``step`` may be a traced scalar; the (few) delay entries are
        unrolled statically, so `delay@s` matching compiles to a
        ``where`` rather than a host lookup.
        """
        t = self.mean * jnp.exp(self.sigma * jax.random.normal(key, (n,)))
        step = jnp.asarray(step, jnp.int32)
        for s, rank, seconds in self.delays:
            hit = (step == s).astype(jnp.float32) * seconds
            if rank is None:
                t = t + hit
            elif rank < n:
                t = t.at[rank].add(hit)
        return t

    def mask_and_report(self, key, step, axis_name: str):
        """(scalar 0/1 mask for THIS replica, report dict) — call inside
        shard_map with ``axis_name`` bound.

        The report is identical on every replica (all draw the same time
        vector), so its entries survive the metrics pmean untouched:

        - ``straggler_dropped``: how many ranks missed the deadline;
        - ``straggler_dropped_mask``: bitmask of dropped ranks
          (rank r -> bit 2^r; only emitted for n <= 24);
        - ``straggler_skew``: max/min simulated arrival time this step;
        - ``straggler_slowest_rank``: which rank arrived last — the
          per-rank attribution field ``obs summary --by-rank`` counts
          into its straggler table (a persistently-slowest rank is a
          sick worker even while it still makes the deadline);
        - ``straggler_arrival_max``: that rank's arrival time (seconds),
          so the margin to the deadline is reconstructable per step.
        """
        n = jax.lax.axis_size(axis_name)
        rank = jax.lax.axis_index(axis_name)
        t = self.times(key, step, n)
        # Deadline keep-set, floored by the fastest min_keep arrivals.
        # Rank position in arrival order with index tie-break, so the
        # floor is always exactly min_keep ranks.
        idx = jnp.arange(n)
        pos = jnp.sum(
            (t[None, :] < t[:, None])
            | ((t[None, :] == t[:, None]) & (idx[None, :] < idx[:, None])),
            axis=1,
        )
        keep = (t <= self.deadline) | (pos < min(self.min_keep, n))
        keepf = keep.astype(jnp.float32)
        report = {
            "straggler_dropped": jnp.float32(n) - keepf.sum(),
            "straggler_skew": t.max() / t.min(),
            "straggler_slowest_rank": jnp.argmax(t).astype(jnp.float32),
            "straggler_arrival_max": t.max(),
        }
        if n <= _MAX_MASK_RANKS:
            report["straggler_dropped_mask"] = jnp.sum(
                (1.0 - keepf) * (2.0 ** jnp.arange(n, dtype=jnp.float32))
            )
        return keepf[rank], report


def dropped_ranks(mask_value: float) -> list:
    """Decode a ``straggler_dropped_mask`` metric back to rank indices."""
    bits, out, r = int(round(mask_value)), [], 0
    while bits:
        if bits & 1:
            out.append(r)
        bits >>= 1
        r += 1
    return out


def make_straggler_sim(
    deadline: float,
    min_keep: int = 1,
    fault_plan=None,
    mean: float = 0.1,
    sigma: float = 0.1,
) -> StragglerSim:
    """Build a sim, folding in a FaultPlan's delay entries if present."""
    return StragglerSim(
        deadline=deadline,
        min_keep=min_keep,
        mean=mean,
        sigma=sigma,
        delays=fault_plan.delay_table() if fault_plan is not None else (),
    )

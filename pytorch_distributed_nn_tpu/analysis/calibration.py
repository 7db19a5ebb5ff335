"""Roofline calibration: per-family ceilings, persisted profiles, fitting.

The peaks and ceilings of each device the repo has a record for live in
ONE table here, keyed by the ``device_kind`` jax reports; the planner
(``analysis/planner.py``) and the live MFU telemetry consume it:

- :class:`CalibrationProfile` — per-family compute ceilings + HBM/ICI
  bandwidths + the nominal peak (the MFU denominator), JSON round-trip
  (``calibration.json``).
- ``default_profile(backend, device_kind)`` — the checked-in table: the
  TPU v5e entry under its ``device_kind``, and an explicitly-labelled CPU
  planning entry so MFU is a meaningful (relative) signal on hosts with no
  published peak. An accelerator whose ``device_kind`` is not in the table
  is an error, never a default: a utilization against somebody else's
  peak is worse than none. The CPU profile sets
  ``shared_substrate=True``: virtual CPU devices share the host's cores,
  so the planner charges a candidate mesh the *global* FLOPs, not
  per-device — which is also what makes CPU plan validation honest (more
  virtual devices never speed a single core up).
- ``fit_from_trace`` — calibrate ceilings from an xplane trace: per-family
  achieved FLOP/s = static family FLOPs x steps / measured family device
  time (the shared ``op_family`` classifier guarantees the two sides
  bucket identically).
- ``fit_microbench`` — bounded on-device microbenches (one dense matmul,
  one large copy) for hosts without a trace.

Everything except ``fit_microbench`` and ``live_profile`` is jax-free.
"""

from __future__ import annotations

import dataclasses
import json
import time
from typing import Dict, Optional

from pytorch_distributed_nn_tpu.utils.profiling import FAMILIES, op_family

CALIBRATION_BASENAME = "calibration.json"


@dataclasses.dataclass
class CalibrationProfile:
    """Per-family roofline ceilings for one device family."""

    name: str
    backend: str                       # cpu | tpu | gpu
    peak_flops_per_s: float            # nominal per-device peak (MFU denom)
    compute_ceilings: Dict[str, float]  # family -> achieved FLOP/s ceiling
    hbm_bytes_per_s: float             # measured/fit HBM ceiling
    hbm_peak_bytes_per_s: float        # nominal HBM peak (util denominator)
    ici_bytes_per_s: float             # per-device interconnect ceiling
    shared_substrate: bool = False     # virtual devices share host cores
    source: str = "default"            # default | trace | microbench | file

    def ceiling(self, family: str) -> float:
        return float(
            self.compute_ceilings.get(family)
            or self.compute_ceilings.get("other")
            or self.peak_flops_per_s
        )

    def to_dict(self) -> dict:
        return dataclasses.asdict(self)

    @classmethod
    def from_dict(cls, d: dict) -> "CalibrationProfile":
        known = {f.name for f in dataclasses.fields(cls)}
        return cls(**{k: v for k, v in d.items() if k in known})

    def save(self, path: str) -> str:
        with open(path, "w") as f:
            json.dump(self.to_dict(), f, indent=2, sort_keys=True)
            f.write("\n")
        return path

    @classmethod
    def load(cls, path: str) -> "CalibrationProfile":
        with open(path) as f:
            d = json.load(f)
        prof = cls.from_dict(d)
        prof.source = "file"
        return prof


#: what jax reports as ``device_kind`` for one TPU v5e chip
TPU_V5E = "TPU v5 lite"

#: the checked-in profiles, keyed by ``device_kind`` ("cpu" for the host
#: backend). TPU v5e nominal peaks: 197 TFLOP/s bf16, 819 GB/s HBM
#: (source: Cloud TPU v5e documentation). Its achieved ceilings —
#: multiply_add 118.7 TFLOP/s (dense chain), convert_reduce ~60 TFLOP/s
#: (blended forward conv, stage-1 lane underfill), HBM 690 GB/s — were
#: measured in July 2026 on an environment that no longer exists and have
#: not been re-measured; elementwise is bandwidth-bound (ceiling = nominal
#: peak so the HBM term dominates). ICI is a one-link planning default —
#: calibrate on real hardware before trusting pod plans. The CPU entry is
#: a PLANNING DEFAULT (no meaningful published peak for "whatever core
#: the CI box has"): CPU MFU is a relative, trend-able signal only.
DEFAULT_PROFILES = {
    TPU_V5E: CalibrationProfile(
        name="tpu_v5e",
        backend="tpu",
        peak_flops_per_s=197e12,
        compute_ceilings={
            "convert_reduce_fusion": 60e12,
            "multiply_add_fusion": 118.7e12,
            "elementwise": 197e12,
            "other": 60e12,
        },
        hbm_bytes_per_s=690e9,
        hbm_peak_bytes_per_s=819e9,
        ici_bytes_per_s=9e10,
    ),
    "cpu": CalibrationProfile(
        name="cpu_planning",
        backend="cpu",
        peak_flops_per_s=5e10,
        compute_ceilings={f: 5e10 for f in FAMILIES},
        hbm_bytes_per_s=2e10,
        hbm_peak_bytes_per_s=2e10,
        # virtual-device "ICI" is a memcpy through host RAM; still finite,
        # so plans on CPU correctly charge for collective payload bytes
        ici_bytes_per_s=1e10,
        shared_substrate=True,
    ),
}


def default_profile(backend: str, device_kind: str = "") -> CalibrationProfile:
    """The checked-in profile for one device: the CPU planning entry for
    the host backend, otherwise the entry for ``device_kind``. Raises for
    an accelerator the table does not hold."""
    key = "cpu" if (backend or "cpu").lower() == "cpu" else device_kind
    if key not in DEFAULT_PROFILES:
        raise ValueError(
            f"no calibration profile for {backend} device_kind "
            f"{device_kind!r}: add its published peaks (with their source) "
            "to analysis/calibration.DEFAULT_PROFILES — an unknown "
            "accelerator never borrows another device's peak"
        )
    # defensive copy: callers mutate ceilings when fitting
    return CalibrationProfile.from_dict(DEFAULT_PROFILES[key].to_dict())


def live_profile() -> CalibrationProfile:
    """``default_profile`` for the device this process runs on."""
    import jax

    return default_profile(
        jax.default_backend(), jax.devices()[0].device_kind
    )


def peak_flops_per_device(backend: str, device_kind: str = "") -> float:
    """Nominal per-device peak FLOP/s — the MFU denominator."""
    return default_profile(backend, device_kind).peak_flops_per_s


# ---------------------------------------------------------------------------
# Roofline prediction (the planner's scoring function; jax-free)
# ---------------------------------------------------------------------------


def predict_step_ms(
    cost: dict,
    profile: CalibrationProfile,
    devices: int = 1,
) -> dict:
    """Predicted step milliseconds for one program under the roofline.

    ``cost`` is a ``StepCost.to_dict()`` (per program instance — per
    device for SPMD-partitioned HLO). Per family the time is the roofline
    max of the compute term and the HBM term; families sum (XLA overlaps
    *within* a fusion, not across the step's serial schedule), and the
    collective payload is charged additively at the ICI ceiling — the
    conservative no-overlap model, which is exactly what makes the
    ranking monotone: more ICI bytes on a slower link can never win.

    ``shared_substrate`` profiles (CPU virtual devices) multiply the
    per-device work by ``devices``: N virtual devices share one physical
    substrate, so partitioning buys no compute time at all there.
    """
    mult = float(devices) if profile.shared_substrate else 1.0
    compute_ms = 0.0
    hbm_bound_ms = 0.0
    fams = cost.get("families") or {}
    if fams:
        for fam, fc in fams.items():
            flops = float(fc.get("flops", 0.0)) * mult
            nbytes = float(fc.get("hbm_bytes", 0.0)) * mult
            t_compute = flops / profile.ceiling(fam)
            t_mem = nbytes / profile.hbm_bytes_per_s
            compute_ms += max(t_compute, t_mem) * 1000.0
            hbm_bound_ms += t_mem * 1000.0
    else:
        flops = float(cost.get("flops", 0.0)) * mult
        nbytes = float(cost.get("hbm_bytes", 0.0)) * mult
        compute_ms = max(
            flops / profile.ceiling("other"),
            nbytes / profile.hbm_bytes_per_s,
        ) * 1000.0
        hbm_bound_ms = nbytes / profile.hbm_bytes_per_s * 1000.0
    ici_ms = (
        float(cost.get("ici_bytes", 0.0)) * mult
        / profile.ici_bytes_per_s * 1000.0
    )
    return {
        "predicted_ms": compute_ms + ici_ms,
        "compute_ms": compute_ms,
        "hbm_ms": hbm_bound_ms,
        "ici_ms": ici_ms,
    }


# ---------------------------------------------------------------------------
# Fitting
# ---------------------------------------------------------------------------


def fit_from_trace(
    trace_dir: str,
    cost: dict,
    steps: int,
    base: CalibrationProfile,
) -> CalibrationProfile:
    """Fit per-family ceilings from a captured xplane trace.

    ``cost`` is the step's ``StepCost.to_dict()`` and ``steps`` how many
    steps the trace covers; each family's fitted ceiling is its static
    FLOPs x steps divided by its measured device time (the achieved rate
    IS the calibrated ceiling — what this hardware actually sustains on
    this op mix). Families with no flops or no trace time keep the base
    profile's ceiling. HBM is fit from the elementwise family (bandwidth
    bound by construction); ICI from the collective ops' device time when
    the trace has any.
    """
    from pytorch_distributed_nn_tpu.utils.profiling import (
        family_summary,
        summarize_xplane,
    )

    summary = summarize_xplane(trace_dir, top=10 ** 6)
    if not summary:
        raise ValueError(
            f"no device planes with XLA op events under {trace_dir} — "
            "CPU-only captures cannot calibrate; use --microbench"
        )
    prof = base
    fams = family_summary(summary)
    cost_fams = cost.get("families") or {}
    for fam in FAMILIES:
        flops = float((cost_fams.get(fam) or {}).get("flops", 0.0))
        ms = float((fams.get(fam) or {}).get("total_ms", 0.0))
        if flops > 0 and ms > 0:
            prof.compute_ceilings[fam] = flops * steps / (ms / 1000.0)
    ew_bytes = float(
        (cost_fams.get("elementwise") or {}).get("hbm_bytes", 0.0)
    )
    ew_ms = float((fams.get("elementwise") or {}).get("total_ms", 0.0))
    if ew_bytes > 0 and ew_ms > 0:
        prof.hbm_bytes_per_s = ew_bytes * steps / (ew_ms / 1000.0)
    coll_ms = 0.0
    for rows in summary.values():
        for r in rows:
            if any(k in r.name for k in (
                "all-reduce", "all-gather", "reduce-scatter",
                "collective-permute", "all-to-all",
            )):
                coll_ms += r.total_ms
    ici = float(cost.get("ici_bytes", 0.0))
    if ici > 0 and coll_ms > 0:
        prof.ici_bytes_per_s = ici * steps / (coll_ms / 1000.0)
    prof.source = "trace"
    prof.name = prof.name + "+trace"
    return prof


def fit_microbench(
    base: Optional[CalibrationProfile] = None,
    matmul_n: int = 1024,
    copy_mb: int = 64,
    repeats: int = 5,
) -> CalibrationProfile:
    """Bounded on-device microbenches: one dense matmul chain sets every
    compute ceiling, one large device copy sets the HBM ceiling. A few
    hundred milliseconds on CPU; never calibrates ICI (needs a real
    multi-chip trace)."""
    import jax
    import jax.numpy as jnp

    backend = jax.default_backend()
    prof = base or live_profile()

    @jax.jit
    def chain(a, b):
        for _ in range(4):
            a = a @ b
        return a

    a = jnp.ones((matmul_n, matmul_n), jnp.float32)
    chain(a, a).block_until_ready()  # compile outside the timing
    best = float("inf")
    for _ in range(repeats):
        t0 = time.perf_counter()
        chain(a, a).block_until_ready()
        best = min(best, time.perf_counter() - t0)
    flops = 4 * 2 * matmul_n ** 3
    measured = flops / best
    for fam in FAMILIES:
        prof.compute_ceilings[fam] = measured
    if prof.backend == "cpu":
        # CPU fallback peak: the measured rate IS the best this host can
        # do, so MFU reads as "fraction of measured-achievable"
        prof.peak_flops_per_s = measured

    n = copy_mb * (1 << 20) // 4
    src = jnp.ones((n,), jnp.float32)
    copy = jax.jit(lambda x: x + 1.0)
    copy(src).block_until_ready()
    best = float("inf")
    for _ in range(repeats):
        t0 = time.perf_counter()
        copy(src).block_until_ready()
        best = min(best, time.perf_counter() - t0)
    prof.hbm_bytes_per_s = 2.0 * src.nbytes / best  # read + write
    if prof.backend == "cpu":
        prof.hbm_peak_bytes_per_s = prof.hbm_bytes_per_s
    prof.source = "microbench"
    prof.name = f"{backend}_microbench"
    return prof

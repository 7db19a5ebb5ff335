"""Mesh/partitioning planner: search configs under the calibrated roofline.

ROADMAP item 4's "shardlint grows from linter to planner": ``analysis/``
could already lower any step over any virtual mesh and inventory its
collectives; with the cost model (``costmodel.py``) and calibrated
ceilings (``calibration.py``) every candidate config now gets a predicted
step time, turning "which mesh?" into ``cli analyze --plan``.

Search space:

- **Mesh factorizations.** Text models: every ``dp x tp x sp`` whose
  product is ``--devices`` (minus candidates the model shapes reject —
  heads not divisible by tp, seq not divisible by sp). Image models run
  the shard_map data-parallel path only, so candidates are ``dp`` over the
  device-count's divisors: using *fewer* devices is a legal answer, and on
  shared-substrate hosts (CPU validation) frequently the right one.
- **Partitioning-rule overrides.** For tp>1 candidates the reference
  rule table (``parallel.partitioning.DEFAULT_RULES``) is searched against
  targeted overrides via the exported ``override_rule`` — e.g. a
  replicated LM head (``vocab -> None``) trades the head all-reduce
  pattern for HBM; whether that wins depends on the calibrated ICI/HBM
  ratio, which is exactly what the roofline scores.

Every candidate is REALLY lowered and compiled over its virtual mesh (the
same CPU-device trick the auditor uses), so the collectives being charged
are the ones XLA actually inserts — not a guess. ``validate=True``
additionally executes each candidate a few times and reports measured
step time next to the prediction (the cross-validation harness of the
acceptance criteria).
"""

from __future__ import annotations

import dataclasses
import logging
import statistics
import time
from typing import Dict, List, Optional, Tuple

from pytorch_distributed_nn_tpu.analysis.calibration import (
    CalibrationProfile,
    live_profile,
    predict_step_ms,
)

logger = logging.getLogger(__name__)

MODEL_ALIASES = {"bert_tiny": "BertTiny", "bert_base": "BertBase",
                 "lenet": "LeNet", "resnet18": "ResNet18", "vgg11": "VGG11"}


@dataclasses.dataclass
class Candidate:
    """One planned configuration and its roofline score."""

    mesh: Tuple[int, int, int]          # (data, model, seq)
    rules: str                          # "default" or the override label
    devices: int
    predicted_ms: float
    compute_ms: float
    ici_ms: float
    cost: dict                          # StepCost.to_dict (per device)
    measured_ms: Optional[float] = None
    skipped: Optional[str] = None       # reason when not lowerable

    def label(self) -> str:
        d, m, s = self.mesh
        out = f"{d}x{m}x{s}" if (m > 1 or s > 1) else str(d)
        if self.rules != "default":
            out += f" [{self.rules}]"
        return out

    def to_dict(self) -> dict:
        return {
            "mesh": {"data": self.mesh[0], "model": self.mesh[1],
                     "seq": self.mesh[2]},
            "rules": self.rules,
            "devices": self.devices,
            "predicted_ms": round(self.predicted_ms, 3),
            "compute_ms": round(self.compute_ms, 3),
            "ici_ms": round(self.ici_ms, 3),
            "measured_ms": (
                round(self.measured_ms, 3)
                if self.measured_ms is not None else None
            ),
            "flops_per_device": self.cost.get("flops"),
            "ici_bytes_per_device": self.cost.get("ici_bytes"),
            "skipped": self.skipped,
        }


def _divisors(n: int) -> List[int]:
    return [d for d in range(1, n + 1) if n % d == 0]


def enumerate_meshes(devices: int, text_model: bool) -> List[Tuple[int, int, int]]:
    """Candidate (dp, tp, sp) meshes for ``devices`` devices."""
    if not text_model:
        return [(d, 1, 1) for d in _divisors(devices)]
    out = []
    for tp in _divisors(devices):
        for sp in _divisors(devices // tp):
            dp = devices // (tp * sp)
            out.append((dp, tp, sp))
    return sorted(set(out))


def _rule_variants(tp: int):
    from pytorch_distributed_nn_tpu.parallel.partitioning import (
        DEFAULT_RULES,
        override_rule,
    )

    variants = [("default", DEFAULT_RULES)]
    if tp > 1:
        variants += [
            ("vocab->replicated",
             override_rule(DEFAULT_RULES, "vocab", None)),
            ("mlp->replicated", override_rule(DEFAULT_RULES, "mlp", None)),
        ]
    return variants


def _step_cost(step_fn, args) -> dict:
    """Lower+compile one candidate's step and walk its cost."""
    from pytorch_distributed_nn_tpu.analysis import costmodel

    compiled = step_fn.lower(*args).compile()
    xla_flops = None
    try:
        ca = compiled.cost_analysis()
        if isinstance(ca, (list, tuple)):
            ca = ca[0] if ca else {}
        xla_flops = ca.get("flops")
    except Exception:
        pass
    return costmodel.step_cost_from_hlo(
        compiled.as_text(), xla_flops=xla_flops
    ).to_dict()


def _measure_ms(step_fn, args, warmup: int = 2, inner: int = 5) -> float:
    """Median-of-3 measured step milliseconds (bundle steps never donate,
    so re-invoking with the same args is legal)."""
    import jax

    out = None
    for _ in range(warmup):
        out = step_fn(*args)
    jax.block_until_ready(out)
    samples = []
    for _ in range(3):
        t0 = time.perf_counter()
        for _ in range(inner):
            out = step_fn(*args)
        jax.block_until_ready(out)
        samples.append((time.perf_counter() - t0) / inner * 1000.0)
    return statistics.median(samples)


def plan(
    model: str,
    devices: int,
    profile: Optional[CalibrationProfile] = None,
    batch_size: Optional[int] = None,
    optimizer: str = "adam",
    seq_len: Optional[int] = None,
    model_kw: Optional[Dict] = None,
    rule_search: bool = True,
    validate: bool = False,
    seq_attn: str = "ring",
) -> dict:
    """Rank candidate configurations for ``model`` on ``devices`` devices.

    Returns ``{"model", "devices", "global_batch", "profile", "candidates":
    [Candidate.to_dict(), ...ranked fastest-first], "top": <label>}``.
    Requires a jax backend with >= ``devices`` devices (the CLI arranges
    virtual CPU devices before the backend initializes, same as the
    auditor).
    """
    import jax

    from pytorch_distributed_nn_tpu.models import (
        build_model,
        input_spec,
        is_text_model,
    )
    from pytorch_distributed_nn_tpu.optim import build_optimizer
    from pytorch_distributed_nn_tpu.parallel import (
        make_grad_sync,
        make_mesh,
        make_mesh_attn,
    )

    if len(jax.devices()) < devices:
        raise ValueError(
            f"--plan over {devices} devices needs that many jax devices; "
            f"only {len(jax.devices())} available"
        )
    model_name = MODEL_ALIASES.get(model, model)
    text = is_text_model(model_name)
    if profile is None:
        profile = live_profile()
    model_kw = dict(model_kw or {})
    batch = batch_size or 2 * devices
    opt = build_optimizer(optimizer, 1e-3)

    candidates: List[Candidate] = []
    for dp, tp, sp in enumerate_meshes(devices, text):
        total = dp * tp * sp
        variants = _rule_variants(tp) if (text and rule_search) else [
            ("default", None)
        ]
        for rules_label, rules in variants:
            cand = Candidate(
                mesh=(dp, tp, sp), rules=rules_label, devices=total,
                predicted_ms=float("inf"), compute_ms=0.0, ici_ms=0.0,
                cost={},
            )
            try:
                if batch % dp:
                    raise ValueError(
                        f"global batch {batch} not divisible by dp={dp}"
                    )
                mesh = make_mesh(dp, tp, sp)
                if text:
                    from pytorch_distributed_nn_tpu.training import (
                        spmd_audit_bundle,
                    )

                    kw = dict(model_kw)
                    attn_fn = (
                        make_mesh_attn(mesh, seq_attn) if sp > 1 else None
                    )
                    m = build_model(model_name, 0, attn_fn=attn_fn, **kw)
                    heads = m.config.num_heads
                    if heads % tp:
                        raise ValueError(
                            f"num_heads={heads} not divisible by tp={tp}"
                        )
                    L = seq_len or m.config.max_len
                    if L % sp:
                        raise ValueError(
                            f"seq_len={L} not divisible by sp={sp}"
                        )
                    bundle = spmd_audit_bundle(
                        m, opt, mesh, (batch, L),
                        **({"rules": rules} if rules is not None else {}),
                    )
                else:
                    from pytorch_distributed_nn_tpu.training import (
                        dp_audit_bundle,
                    )

                    m = build_model(model_name, 10)
                    bundle = dp_audit_bundle(
                        m, opt, make_grad_sync("allreduce"), mesh,
                        input_spec(model_name), batch,
                    )
                cand.cost = _step_cost(bundle["step_fn"], bundle["args"])
                pred = predict_step_ms(cand.cost, profile, devices=total)
                cand.predicted_ms = pred["predicted_ms"]
                cand.compute_ms = pred["compute_ms"]
                cand.ici_ms = pred["ici_ms"]
                if validate:
                    cand.measured_ms = _measure_ms(
                        bundle["step_fn"], bundle["args"]
                    )
            except Exception as e:
                cand.skipped = str(e)
                logger.info("plan: skipping %s: %s", cand.label(), e)
            candidates.append(cand)

    ranked = sorted(
        (c for c in candidates if c.skipped is None),
        key=lambda c: c.predicted_ms,
    ) + [c for c in candidates if c.skipped is not None]
    result = {
        "model": model_name,
        "devices": devices,
        "global_batch": batch,
        "profile": {"name": profile.name, "source": profile.source},
        "candidates": [c.to_dict() for c in ranked],
        "top": ranked[0].label() if ranked and not ranked[0].skipped
        else None,
    }
    if validate:
        measured = [
            c for c in ranked
            if c.skipped is None and c.measured_ms is not None
        ]
        if measured:
            fastest = min(measured, key=lambda c: c.measured_ms)
            result["measured_fastest"] = fastest.label()
            result["agreement"] = fastest.label() == result["top"]
    return result


def render_plan(result: dict) -> str:
    """Human-readable ranked table."""
    lines = [
        f"plan: {result['model']} over {result['devices']} device(s), "
        f"global batch {result['global_batch']}, profile "
        f"{result['profile']['name']} ({result['profile']['source']})",
        "",
        f"  {'rank':>4} {'mesh (dp x tp x sp)':<26} {'pred ms':>9} "
        f"{'compute':>9} {'ici':>8} {'measured':>9}",
    ]
    rank = 0
    for c in result["candidates"]:
        if c.get("skipped"):
            lines.append(
                f"     - {_mesh_label(c):<26} skipped: {c['skipped']}"
            )
            continue
        rank += 1
        meas = (
            f"{c['measured_ms']:>9.2f}" if c.get("measured_ms") is not None
            else f"{'-':>9}"
        )
        lines.append(
            f"  {rank:>4} {_mesh_label(c):<26} {c['predicted_ms']:>9.2f} "
            f"{c['compute_ms']:>9.2f} {c['ici_ms']:>8.2f} {meas}"
        )
    if result.get("top"):
        lines.append("")
        lines.append(f"predicted fastest: {result['top']}")
    if "measured_fastest" in result:
        lines.append(
            f"measured fastest:  {result['measured_fastest']} "
            f"({'AGREE' if result.get('agreement') else 'DISAGREE'})"
        )
    return "\n".join(lines)


def _mesh_label(c: dict) -> str:
    m = c["mesh"]
    out = (
        f"{m['data']}x{m['model']}x{m['seq']}"
        if (m["model"] > 1 or m["seq"] > 1) else str(m["data"])
    )
    if c.get("rules") and c["rules"] != "default":
        out += f" [{c['rules']}]"
    return out

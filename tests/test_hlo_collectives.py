"""HLO-level assertions on the compiled SPMD steps, via the auditor.

The strongest single-host proxy for "the pod run will do what PERF.md
says" (round-3 verdict item 5): compile the real train steps over the
8-device mesh and assert the collectives XLA inserted are the ones the
design promises — all-reduce for data-parallel grad sync, a
collective-permute chain for ring attention, all-to-all for Ulysses —
and that no full-parameter all-gather snuck in (the classic GSPMD
mis-sharding failure; rule SL001 in docs/analysis.md).

These tests consume the analysis subsystem's public surface
(``spmd_audit_bundle`` / ``dp_audit_bundle`` → ``analysis.audit`` →
rule IDs) — the auditor's own adversarial coverage (SL001 firing when a
rule is deliberately broken, planted f64, etc.) lives in
tests/test_analysis.py.
"""

from pytorch_distributed_nn_tpu import analysis
from pytorch_distributed_nn_tpu.analysis.testing import (
    assert_collectives,
    assert_rules_absent,
)
from pytorch_distributed_nn_tpu.models import build_model
from pytorch_distributed_nn_tpu.models.transformer import bert_tiny
from pytorch_distributed_nn_tpu.optim import build_optimizer
from pytorch_distributed_nn_tpu.parallel import (
    make_grad_sync,
    make_mesh,
    make_mesh_attn,
)
from pytorch_distributed_nn_tpu.training import (
    dp_audit_bundle,
    spmd_audit_bundle,
)


def _spmd_report(seq_attn: str, compression: str = "none"):
    mesh = make_mesh(2, 2, 2)
    model = bert_tiny(
        attn_fn=make_mesh_attn(mesh, seq_attn),
        vocab_size=512, max_len=32, d_model=64, num_heads=4,
        num_layers=2, d_ff=128, dropout_rate=0.1,
    )
    opt = build_optimizer("adam", 1e-3)
    bundle = spmd_audit_bundle(
        model, opt, mesh, (4, 32), compression=compression
    )
    return analysis.audit(**bundle)


def test_dp_step_collectives():
    """Pure data parallelism: gradient sync is ONE all-reduce family — no
    gathers, permutes or transposes of any kind."""
    mesh = make_mesh(8, 1, 1)
    model = build_model("LeNet", 10)
    opt = build_optimizer("sgd", 0.1, momentum=0.9)
    sync = make_grad_sync("allreduce")
    bundle = dp_audit_bundle(model, opt, sync, mesh, (28, 28, 1), 16)
    report = analysis.audit(**bundle)
    assert_collectives(
        report,
        present=("all-reduce",),
        absent=("all-gather", "collective-permute", "all-to-all"),
    )
    assert_rules_absent(report, ("SL001", "SL003", "SL004"))


def test_ring_step_collectives():
    """dp×tp×sp with ring attention: the ring is a collective-permute
    chain; grads still all-reduce; SL001 (parameter-sized all-gather —
    a weight's sharding degenerated to gather-and-replicate) is absent."""
    report = _spmd_report("ring")
    assert_collectives(report, present=("collective-permute", "all-reduce"))
    assert_rules_absent(report, ("SL001", "SL003", "SL005"))


def test_ulysses_step_collectives():
    """dp×tp×sp with Ulysses attention: the seq<->heads reshard is an
    all-to-all; same no-parameter-gather guarantee."""
    report = _spmd_report("ulysses")
    assert_collectives(report, present=("all-to-all", "all-reduce"))
    assert_rules_absent(report, ("SL001", "SL003", "SL005"))


def test_tp_flash_step_collectives():
    """tp-only mesh with the Pallas flash attention (make_tp_flash_attn):
    the dp grad sync + tp projection reductions are still all-reduces and
    SL001 stays silent — the kernel swap must not change the comm pattern
    of the dense tp path."""
    from pytorch_distributed_nn_tpu.parallel import make_tp_flash_attn

    mesh = make_mesh(2, 2, 1)
    model = bert_tiny(
        attn_fn=make_tp_flash_attn(mesh),
        vocab_size=512, max_len=32, d_model=64, num_heads=4,
        num_layers=2, d_ff=128, dropout_rate=0.1,
    )
    opt = build_optimizer("adam", 1e-3)
    bundle = spmd_audit_bundle(model, opt, mesh, (4, 32))
    report = analysis.audit(**bundle)
    assert_collectives(report, present=("all-reduce",))
    assert_rules_absent(report, ("SL001", "SL003", "SL005"))


def test_gspmd_int8_rides_integer_collective():
    """compression='int8' on the dp×tp×sp path: the data-parallel gradient
    sync must move the QUANTIZED payload — an all-reduce over an integer
    (s32-accumulated int8) operand must exist in the compiled step, next
    to the unchanged tp/sp collectives, with SL001 still silent
    (training/spmd._int8_spmd_step)."""
    report = _spmd_report("ring", compression="int8")
    assert_collectives(report, present=("collective-permute", "all-reduce"))
    int_allreduce = [
        c for c in report.collectives
        if c.kind == "all-reduce" and c.dtype in ("s32", "s8", "u32")
    ]
    assert int_allreduce, (
        "no integer all-reduce found — the int8 payload is not riding "
        "the dp collective; inventory: "
        + str([(c.kind, c.dtype, c.shape) for c in report.collectives])
    )
    assert_rules_absent(report, ("SL001",))


def test_ps_int8_step_has_single_allreduce_family():
    """The PS-emulation + int8 path syncs via psum on int32/float — it must
    still lower to all-reduce, with no hidden gather of the int8 payload."""
    mesh = make_mesh(8, 1, 1)
    model = build_model("LeNet", 10)
    opt = build_optimizer("sgd", 0.1, momentum=0.9)
    sync = make_grad_sync("ps", num_aggregate=7, compression="int8")
    bundle = dp_audit_bundle(model, opt, sync, mesh, (28, 28, 1), 16)
    report = analysis.audit(**bundle)
    assert_collectives(report, present=("all-reduce",), absent=("all-gather",))
    assert_rules_absent(report, ("SL001",))


def test_report_inventory_shapes_and_bytes():
    """The report carries a usable inventory: per-collective dtype/shape/
    count and a positive ICI-bytes estimate for a step that syncs grads."""
    mesh = make_mesh(8, 1, 1)
    model = build_model("LeNet", 10)
    opt = build_optimizer("sgd", 0.1, momentum=0.9)
    sync = make_grad_sync("allreduce")
    bundle = dp_audit_bundle(model, opt, sync, mesh, (28, 28, 1), 16)
    report = analysis.audit(**bundle)
    assert report.est_ici_bytes_per_step() > 0
    ar = [c for c in report.collectives if c.kind == "all-reduce"]
    assert ar and all(c.group_size == 8 for c in ar), (
        "dp grad sync must reduce over the full 8-wide data axis: "
        + str([(c.dtype, c.shape, c.group_size) for c in ar])
    )
    # serialization round-trip is part of the CI contract
    d = report.to_dict()
    assert d["totals"]["by_kind"]["all-reduce"] >= 1
    assert d["findings"] == []

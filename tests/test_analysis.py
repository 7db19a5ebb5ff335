"""Adversarial coverage for the sharding auditor (analysis/).

The auditor guards every other test in this suite, so IT gets tested by
deliberately planting each failure class and asserting the right rule
fires — and nothing else does:

- SL001: drop the ``heads → model`` partition rule; the attention
  projection weights then re-materialize via full-parameter all-gathers
  every step, and the finding must name the offending parameters.
- SL003: plant a strong f64 literal in a step under enable_x64.
- SL002: a psum pinned inside a fori_loop body.
- SL004: a host callback (jax.debug.print) in the step.
- SL006: a second invocation with a different shape.

Plus pure-text unit tests of the HLO parser (no compilation).
"""

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import PartitionSpec as P

from pytorch_distributed_nn_tpu import analysis
from pytorch_distributed_nn_tpu.analysis import hlo as hlo_mod
from pytorch_distributed_nn_tpu.analysis.testing import (
    assert_rules_absent,
    assert_rules_fired,
)
from jax import shard_map
from pytorch_distributed_nn_tpu.models.transformer import bert_tiny
from pytorch_distributed_nn_tpu.optim import build_optimizer
from pytorch_distributed_nn_tpu.parallel import (
    DEFAULT_RULES,
    drop_rule,
    make_mesh,
    make_mesh_attn,
    override_rule,
    rules_dict,
)
from pytorch_distributed_nn_tpu.training import spmd_audit_bundle


def _tiny_bundle(rules):
    mesh = make_mesh(2, 2, 2)
    model = bert_tiny(
        attn_fn=make_mesh_attn(mesh, "ring"),
        vocab_size=512, max_len=32, d_model=64, num_heads=4,
        num_layers=2, d_ff=128, dropout_rate=0.1,
    )
    opt = build_optimizer("adam", 1e-3)
    return spmd_audit_bundle(model, opt, mesh, (4, 32), rules=rules)


class TestMisShardingSL001:
    def test_dropped_heads_rule_fires_sl001_with_param_paths(self):
        """The canonical silent failure: the ``heads → model`` annotation
        lost, every attention projection re-gathered to full on every
        device each step. SL001 must fire and name the weights."""
        bundle = _tiny_bundle(drop_rule(DEFAULT_RULES, "heads"))
        report = analysis.audit(**bundle, sl005_min_bytes=4096)
        assert_rules_fired(report, ("SL001",))
        offenders = {f.param for f in report.findings_for("SL001") if f.param}
        assert any("attn/query/kernel" in p for p in offenders), offenders
        assert any("attn/out/kernel" in p for p in offenders), offenders
        # SL005 independently flags the same kernels as replicated-but-
        # shardable (spec-level view of the same mis-annotation)
        assert_rules_fired(report, ("SL005",))
        sl005 = {f.param for f in report.findings_for("SL005")}
        assert any("attn/query/kernel" in p for p in sl005), sl005

    def test_rule_helpers(self):
        broken = drop_rule(DEFAULT_RULES, "heads")
        assert rules_dict(broken)["heads"] is None
        assert rules_dict(broken)["mlp"] == rules_dict(DEFAULT_RULES)["mlp"]
        moved = override_rule(DEFAULT_RULES, "kv", "model")
        assert rules_dict(moved)["kv"] == "model"


class TestPlantedStepDefects:
    def test_sl003_fires_on_planted_f64(self, devices):
        """A strong float64 constant in the step promotes the datapath to
        f64 — the auditor must see f64 results in the optimized HLO."""
        mesh = make_mesh(8, 1, 1)

        with jax.enable_x64(True):
            @jax.jit
            def step(x):
                poison = jnp.asarray(np.float64(1.5))  # strong f64
                return (x.astype(jnp.float64) * poison).sum()

            report = analysis.audit(step, (jnp.ones((8, 4)),), mesh)
        assert_rules_fired(report, ("SL003",))
        [f] = report.findings_for("SL003")
        assert f.count >= 1

    def test_sl003_silent_on_f32_step(self, devices):
        mesh = make_mesh(8, 1, 1)

        @jax.jit
        def step(x):
            return (x * 1.5).sum()

        report = analysis.audit(step, (jnp.ones((8, 4)),), mesh)
        assert_rules_absent(report, ("SL003",))

    def test_sl002_fires_on_loop_bound_collective(self, devices):
        """A psum whose value depends on the loop counter cannot be
        hoisted by XLA — it must be reported as a per-iteration
        collective."""
        mesh = make_mesh(8, 1, 1)

        @jax.jit
        @partial(
            shard_map,
            mesh=mesh,
            in_specs=P("data"),
            out_specs=P("data"),
            check_vma=False,
        )
        def step(x):
            def body(i, acc):
                return acc + lax.psum((x * i).sum(), "data")

            total = lax.fori_loop(0, 16, body, jnp.float32(0))
            return x + total

        report = analysis.audit(step, (jnp.ones((16, 4)),), mesh)
        assert_rules_fired(report, ("SL002",))
        [f] = [f for f in report.findings_for("SL002")]
        assert "all-reduce" in f.message

    def test_sl004_fires_on_host_callback(self, devices):
        mesh = make_mesh(8, 1, 1)

        @jax.jit
        def step(x):
            jax.debug.print("sum={s}", s=x.sum())
            return x * 2

        report = analysis.audit(step, (jnp.ones((8,)),), mesh)
        assert_rules_fired(report, ("SL004",))

    def test_sl006_fires_on_shape_churn(self, devices):
        mesh = make_mesh(8, 1, 1)

        @jax.jit
        def step(x):
            return x * 2

        report = analysis.audit(
            step, (jnp.ones((8,)),), mesh,
            second_args=(jnp.ones((16,)),),  # different shape → recompile
        )
        assert_rules_fired(report, ("SL006",))

    def test_sl006_silent_on_stable_shapes(self, devices):
        mesh = make_mesh(8, 1, 1)

        @jax.jit
        def step(x):
            return x * 2

        report = analysis.audit(
            step, (jnp.ones((8,)),), mesh,
            second_args=(jnp.zeros((8,)),),
        )
        assert_rules_absent(report, ("SL006",))

    def test_suppress_drops_findings(self, devices):
        mesh = make_mesh(8, 1, 1)

        @jax.jit
        def step(x):
            jax.debug.print("sum={s}", s=x.sum())
            return x * 2

        report = analysis.audit(
            step, (jnp.ones((8,)),), mesh, suppress=("SL004",)
        )
        assert_rules_absent(report, ("SL004",))


_FAKE_HLO = """\
HloModule jit_step, entry_computation_layout={()->f32[]}

%add.1 (a: f32[], b: f32[]) -> f32[] {
  %a = f32[] parameter(0)
  %b = f32[] parameter(1)
  ROOT %r = f32[] add(f32[] %a, f32[] %b)
}

%loop_body (p: (s32[], f32[8,4])) -> (s32[], f32[8,4]) {
  %p = (s32[], f32[8,4]{1,0}) parameter(0)
  %ar.2 = f32[8,4]{1,0} all-reduce(f32[8,4]{1,0} %gte), channel_id=2, replica_groups={{0,1,2,3}}, use_global_device_ids=true, to_apply=%add.1
}

%loop_cond (p: (s32[], f32[8,4])) -> pred[] {
  %p = (s32[], f32[8,4]{1,0}) parameter(0)
}

ENTRY %main (arg: f32[16,4]) -> f32[] {
  %arg = f32[16,4]{1,0} parameter(0)
  %ag.1 = f32[64,4,16]{2,0,1} all-gather(f32[64,2,16]{2,0,1} %arg), channel_id=1, replica_groups=[4,2]<=[8], dimensions={1}, use_global_device_ids=true, metadata={op_name="jit(step)/encoder/attn/query/dot_general"}
  %w.1 = (s32[], f32[8,4]{1,0}) while((s32[], f32[8,4]{1,0}) %t), condition=%loop_cond, body=%loop_body
  %cp.1 = f32[2,16]{1,0} collective-permute(f32[2,16]{1,0} %arg), channel_id=3, source_target_pairs={{0,1},{1,0}}
  %bad = f64[4]{0} convert(f32[4]{0} %arg)
  %cc.1 = f32[] custom-call(), custom_call_target="xla_ffi_python_cpu_callback"
}
"""


class TestHloParser:
    def test_parse_collectives(self):
        ops = hlo_mod.parse_collectives(_FAKE_HLO)
        kinds = sorted(op.kind for op in ops)
        assert kinds == ["all-gather", "all-reduce", "collective-permute"]
        ag = next(op for op in ops if op.kind == "all-gather")
        assert ag.shapes[0] == ("f32", (64, 4, 16))
        assert ag.group_size == 2
        assert "query" in ag.op_name
        assert not ag.in_loop
        ar = next(op for op in ops if op.kind == "all-reduce")
        assert ar.group_size == 4
        assert ar.in_loop, "all-reduce lives in the while body"
        cp = next(op for op in ops if op.kind == "collective-permute")
        assert cp.group_size == 2

    def test_ici_estimates(self):
        ops = hlo_mod.parse_collectives(_FAKE_HLO)
        ag = next(op for op in ops if op.kind == "all-gather")
        # 64*4*16 f32 = 16384 B, groups of 2 → (n-1)/n = 1/2
        assert ag.payload_bytes == 64 * 4 * 16 * 4
        assert ag.est_ici_bytes == ag.payload_bytes // 2
        ar = next(op for op in ops if op.kind == "all-reduce")
        # ring all-reduce moves 2·P·(n-1)/n
        assert ar.est_ici_bytes == int(2 * ar.payload_bytes * 3 / 4)

    def test_loop_computations_close_transitively(self):
        loops = hlo_mod.loop_computations(_FAKE_HLO)
        assert "loop_body" in loops and "loop_cond" in loops
        assert "add.1" in loops, "to_apply of an in-loop op is reachable"
        assert "main" not in loops

    def test_find_dtype_and_host_lines(self):
        f64 = hlo_mod.find_dtype_lines(_FAKE_HLO)
        assert len(f64) == 1 and "f64[4]" in f64[0]
        host = hlo_mod.find_host_ops(_FAKE_HLO)
        assert len(host) == 1 and "callback" in host[0]

    def test_rule_catalogue_is_stable(self):
        ids = [r.id for r in analysis.RULES]
        assert ids == ["SL001", "SL002", "SL003", "SL004", "SL005",
                       "SL006", "SL007"]
        assert set(analysis.DEFAULT_FAIL_ON) == {"SL001", "SL003"}


class TestDonationSL007:
    """SL007 judges the compiled module's ``input_output_alias`` table:
    a step must donate its large operands, a serving apply must donate
    none of its params. Off unless ``audit(donation=...)`` opts in —
    the audit bundles build with ``donate=False`` for SL006's sake."""

    def test_sl007_fires_on_undonated_step(self, devices):
        mesh = make_mesh(8, 1, 1)

        @jax.jit
        def step(state, batch):
            return state + batch.sum()

        state = jnp.ones((1024, 256))  # 1 MiB: old+new live across step
        report = analysis.audit(
            step, (state, jnp.ones((8, 4))), mesh, donation="step",
        )
        assert_rules_fired(report, ("SL007",))
        [f] = report.findings_for("SL007")
        assert f.count == 1 and "not donated" in f.message

    def test_sl007_silent_on_donating_step(self, devices):
        mesh = make_mesh(8, 1, 1)

        @partial(jax.jit, donate_argnums=0)
        def step(state, batch):
            return state + batch.sum()

        report = analysis.audit(
            step, (jnp.ones((1024, 256)), jnp.ones((8, 4))), mesh,
            donation="step",
        )
        assert_rules_absent(report, ("SL007",))

    def test_sl007_undonated_ok_exempts_by_path(self, devices):
        mesh = make_mesh(8, 1, 1)

        @jax.jit
        def step(state, batch):
            return state["w"] + batch.sum()

        report = analysis.audit(
            step, ({"w": jnp.ones((1024, 256))}, jnp.ones((8, 4))), mesh,
            donation="step", undonated_ok=("w",),
        )
        assert_rules_absent(report, ("SL007",))

    def test_sl007_fires_on_donating_apply(self, devices):
        """The serving-side inversion: params in donate_argnums means
        the first request frees the weights the next one needs. Only
        ALIASABLE donations matter — XLA silently drops the rest with a
        warning and the buffer survives — so the planted bias must
        shape-match the output to actually land in the alias table."""
        mesh = make_mesh(8, 1, 1)

        @partial(jax.jit, donate_argnums=0)
        def apply(params, x):
            return x @ params["w"] + params["b"]

        params = {"w": jnp.ones((64, 64)), "b": jnp.ones((8, 64))}
        report = analysis.audit(
            apply, (params, jnp.ones((8, 64))), mesh, donation="apply",
        )
        assert_rules_fired(report, ("SL007",))
        [f] = report.findings_for("SL007")
        assert "donates" in f.message

    def test_sl007_silent_on_clean_apply(self, devices):
        mesh = make_mesh(8, 1, 1)

        @jax.jit
        def apply(params, x):
            return x @ params["w"]

        report = analysis.audit(
            apply, ({"w": jnp.ones((64, 64))}, jnp.ones((8, 64))), mesh,
            donation="apply",
        )
        assert_rules_absent(report, ("SL007",))

"""Ring attention / Ulysses vs full attention, and the GSPMD dp×tp×sp step.

Runs on the 8-device virtual CPU mesh (conftest sets
xla_force_host_platform_device_count=8).
"""

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import shard_map
from jax.sharding import PartitionSpec as P

from pytorch_distributed_nn_tpu.models.transformer import (
    full_attention,
)
from pytorch_distributed_nn_tpu.parallel import (
    DATA_AXIS,
    SEQ_AXIS,
    make_mesh,
    make_mesh_attn,
    ring_attention,
    ulysses_attention,
)


def _qkvm(B=2, L=32, H=4, D=8, seed=0, pad=0):
    rng = np.random.RandomState(seed)
    q, k, v = (
        jnp.asarray(rng.randn(B, L, H, D).astype(np.float32)) for _ in range(3)
    )
    mask = np.ones((B, L), np.float32)
    if pad:
        mask[:, -pad:] = 0.0
    return q, k, v, jnp.asarray(mask)


def _run_seq_sharded(attn, mesh, q, k, v, mask, causal):
    spec = P(SEQ_AXIS)  # shard the length dim (axis 1 via full spec below)
    qspec = P(None, SEQ_AXIS, None, None)
    mspec = P(None, SEQ_AXIS)

    @partial(
        shard_map,
        mesh=mesh,
        in_specs=(qspec, qspec, qspec, mspec),
        out_specs=qspec,
        check_vma=False,
    )
    def f(q, k, v, m):
        return attn(q, k, v, m, causal=causal)

    return f(q, k, v, mask)


class TestRingAttention:
    @pytest.mark.parametrize("causal", [False, True])
    @pytest.mark.parametrize("impl", [ring_attention, ulysses_attention])
    def test_matches_full_attention(self, impl, causal):
        mesh = make_mesh(1, 1, 4, devices=jax.devices()[:4])
        q, k, v, mask = _qkvm()
        want = full_attention(q, k, v, mask, causal=causal)
        got = _run_seq_sharded(impl, mesh, q, k, v, mask, causal)
        np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)

    @pytest.mark.parametrize("impl", [ring_attention, ulysses_attention])
    def test_respects_pad_mask(self, impl):
        mesh = make_mesh(1, 1, 4, devices=jax.devices()[:4])
        q, k, v, mask = _qkvm(pad=8)
        want = full_attention(q, k, v, mask)
        got = _run_seq_sharded(impl, mesh, q, k, v, mask, False)
        np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)

    def test_ring_grads_match(self):
        """d(loss)/d(q,k,v) through ring attention == through full attention."""
        mesh = make_mesh(1, 1, 4, devices=jax.devices()[:4])
        q, k, v, mask = _qkvm(L=16)

        def loss_full(qkv):
            return (full_attention(*qkv, mask) ** 2).sum()

        def loss_ring(qkv):
            out = _run_seq_sharded(ring_attention, mesh, *qkv, mask, False)
            return (out ** 2).sum()

        g_full = jax.grad(loss_full)((q, k, v))
        g_ring = jax.grad(loss_ring)((q, k, v))
        for a, b in zip(g_full, g_ring):
            np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-4)

    @pytest.mark.parametrize("causal", [False, True])
    def test_ring_grads_match_causal_and_masked(self, causal):
        """The hand-written ring backward handles causal + pad mask."""
        mesh = make_mesh(1, 1, 4, devices=jax.devices()[:4])
        q, k, v, mask = _qkvm(L=16, pad=3)

        def loss_full(qkv):
            return (full_attention(*qkv, mask, causal=causal) ** 2).sum()

        def loss_ring(qkv):
            out = _run_seq_sharded(ring_attention, mesh, *qkv, mask, causal)
            return (out ** 2).sum()

        g_full = jax.grad(loss_full)((q, k, v))
        g_ring = jax.grad(loss_ring)((q, k, v))
        for a, b in zip(g_full, g_ring):
            np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-4)

    def test_ring_backward_residuals_stay_linear(self):
        """The custom-VJP ring backward recomputes per-hop probabilities
        instead of storing them: the grad jaxpr must hold NO scan-stacked
        (hops, B, H, Lc, Lc) probability residuals — reverse-mode autodiff
        through the forward loop (the round-1 implementation) produced
        exactly those, making long-context memory O(S·Lc²)."""
        mesh = make_mesh(1, 1, 4, devices=jax.devices()[:4])
        B, L, H, D = 2, 64, 2, 8
        Lc = L // 4
        rng = np.random.RandomState(0)
        q, k, v = (
            jnp.asarray(rng.randn(B, L, H, D).astype(np.float32))
            for _ in range(3)
        )
        mask = jnp.ones((B, L), jnp.float32)

        def loss(qkv):
            out = _run_seq_sharded(ring_attention, mesh, *qkv, mask, False)
            return (out ** 2).sum()

        jaxpr = jax.make_jaxpr(jax.grad(loss))((q, k, v))
        offenders = []

        def walk(jx):
            for eqn in jx.eqns:
                for var in list(eqn.invars) + list(eqn.outvars):
                    shape = getattr(getattr(var, "aval", None), "shape", ())
                    # stacked residual = rank>=5 with a trailing Lc x Lc
                    if (
                        len(shape) >= 5
                        and shape[-1] == Lc
                        and shape[-2] == Lc
                    ):
                        offenders.append(shape)
                for sub in eqn.params.values():
                    if hasattr(sub, "eqns"):
                        walk(sub)
                    elif hasattr(sub, "jaxpr") and hasattr(sub.jaxpr, "eqns"):
                        walk(sub.jaxpr)

        walk(jaxpr.jaxpr)
        assert not offenders, (
            f"ring backward stores stacked quadratic residuals: {offenders}"
        )

    def test_mesh_attn_wrapper_with_tp(self):
        """make_mesh_attn shards heads over 'model' and length over 'seq'."""
        mesh = make_mesh(2, 2, 2, devices=jax.devices()[:8])
        q, k, v, mask = _qkvm(B=4, L=16, H=4)
        want = full_attention(q, k, v, mask)
        got = jax.jit(make_mesh_attn(mesh, "ring"))(q, k, v, mask)
        np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)

    @pytest.mark.parametrize("causal", [False, True])
    def test_tp_flash_attn_wrapper(self, causal):
        """make_tp_flash_attn: flash kernel per head shard over (data,
        model) == dense full attention, incl. pad mask and causal."""
        from pytorch_distributed_nn_tpu.parallel import make_tp_flash_attn

        mesh = make_mesh(2, 2, 1, devices=jax.devices()[:4])
        q, k, v, mask = _qkvm(B=4, L=32, H=4, pad=5)
        want = full_attention(q, k, v, mask, causal=causal)
        got = jax.jit(
            partial(make_tp_flash_attn(mesh), causal=causal)
        )(q, k, v, mask)
        np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)


class TestSpmdTraining:
    def _train(self, num_data, num_model, num_seq, attn_impl=None, steps=8,
               compression="none", return_losses=False, grad_accum=1):
        from pytorch_distributed_nn_tpu.data.text import MLMBatches
        from pytorch_distributed_nn_tpu.models.transformer import bert_tiny
        from pytorch_distributed_nn_tpu.optim import build_optimizer
        from pytorch_distributed_nn_tpu.training.spmd import (
            build_spmd_train_step,
            create_spmd_state,
            text_batch_sharding,
        )

        n = num_data * num_model * num_seq
        mesh = make_mesh(num_data, num_model, num_seq,
                         devices=jax.devices()[:n])
        if attn_impl == "tp_flash":
            from pytorch_distributed_nn_tpu.parallel import (
                make_tp_flash_attn,
            )

            attn_fn = make_tp_flash_attn(mesh)
        else:
            attn_fn = make_mesh_attn(mesh, attn_impl) if attn_impl else None
        model = bert_tiny(
            attn_fn=attn_fn,
            vocab_size=64, max_len=32, d_model=32, num_heads=4,
            num_layers=2, d_ff=64, dropout_rate=0.0, dtype=jnp.float32,
        )
        opt = build_optimizer("sgd", 0.1, momentum=0.9)
        state, shardings = create_spmd_state(
            model, opt, jax.random.PRNGKey(0), (8, 32), mesh
        )
        step = build_spmd_train_step(model, opt, mesh, shardings,
                                     donate=False, compression=compression,
                                     grad_accum=grad_accum)
        bspec = text_batch_sharding(mesh)
        data = MLMBatches(vocab_size=64, seq_len=32, batch_size=8, seed=0)
        metrics = None
        losses = []
        for i, (x, y) in zip(range(steps), data):
            xb = jax.device_put(jnp.asarray(x), bspec)
            yb = jax.device_put(jnp.asarray(y), bspec)
            state, metrics = step(state, (xb, yb), jax.random.PRNGKey(7))
            if return_losses:
                losses.append(float(metrics["loss"]))
        if return_losses:
            return state, metrics, losses
        return state, metrics

    def test_dp_only_runs(self):
        state, m = self._train(2, 1, 1)
        assert np.isfinite(float(m["loss"]))
        assert int(state.step) == 8

    def test_tp_matches_dp(self):
        """Same seeds: dp=2/tp=2 training == dp=4 training (numerics)."""
        _, m_tp = self._train(2, 2, 1)
        _, m_dp = self._train(4, 1, 1)
        np.testing.assert_allclose(
            float(m_tp["loss"]), float(m_dp["loss"]), rtol=2e-4
        )

    @pytest.mark.parametrize("impl", ["ring", "ulysses"])
    def test_sp_matches_dp(self, impl):
        """Sequence-parallel attention training == plain full attention."""
        _, m_sp = self._train(2, 1, 2, attn_impl=impl)
        _, m_dp = self._train(2, 1, 1)
        np.testing.assert_allclose(
            float(m_sp["loss"]), float(m_dp["loss"]), rtol=2e-4
        )

    def test_dp_tp_sp_composed(self):
        state, m = self._train(2, 2, 2, attn_impl="ring")
        assert np.isfinite(float(m["loss"]))

    def test_gspmd_grad_accum_matches_full_batch(self):
        """grad_accum=2 on the dp×tp×sp GSPMD path == the full-batch step
        (exact pair accumulation: Σ grads / global masked count; round-4
        verdict item 6). dropout is 0 in this harness so the only
        difference is fp reassociation across the scan."""
        _, m_acc = self._train(2, 2, 2, attn_impl="ring", steps=4,
                               grad_accum=2)
        _, m_full = self._train(2, 2, 2, attn_impl="ring", steps=4)
        np.testing.assert_allclose(
            float(m_acc["loss"]), float(m_full["loss"]), rtol=1e-5
        )

    def test_gspmd_grad_accum_tp_only(self):
        """grad_accum composes with a tp-only mesh too (the pod memory
        lever where tp runs; no seq axis sharding in the microbatches)."""
        _, m_acc = self._train(2, 2, 1, steps=4, grad_accum=4)
        _, m_full = self._train(2, 2, 1, steps=4)
        np.testing.assert_allclose(
            float(m_acc["loss"]), float(m_full["loss"]), rtol=1e-5
        )

    def test_tp_flash_matches_dense(self):
        """Head-sharded Pallas flash attention under tp (sp=1) trains to
        the same loss as the dense tp path (round-4 verdict item 5)."""
        _, m_flash = self._train(2, 2, 1, attn_impl="tp_flash")
        _, m_dense = self._train(2, 2, 1)
        np.testing.assert_allclose(
            float(m_flash["loss"]), float(m_dense["loss"]), rtol=2e-4
        )

    @pytest.mark.parametrize("impl", ["ring", "ulysses"])
    def test_int8_first_step_matches_dense(self, impl):
        """The int8-compressed GSPMD step computes the SAME global masked
        mean (its loss metric comes from the identical forward; only the
        dp gradient payload is quantized): first-step loss must match the
        dense dp×tp×sp step almost exactly."""
        _, m8 = self._train(2, 2, 2, attn_impl=impl, steps=1,
                            compression="int8")
        _, md = self._train(2, 2, 2, attn_impl=impl, steps=1)
        np.testing.assert_allclose(
            float(m8["loss"]), float(md["loss"]), rtol=1e-5
        )

    def test_int8_trains_dp_tp_sp(self):
        """Quantized dp sync composed with tp/sp optimizes LIKE THE DENSE
        PATH does on the identical stream.

        Round-4 postmortem: the old form compared a single step-1 loss
        against a single step-8 loss with ~0.5% margin — int8 stochastic
        rounding noise plus any data-stream reshuffle flipped its sign.
        An absolute-drop margin is equally fragile: this tiny config
        descends only ~2% in 32 steps with or WITHOUT quantization
        (measured: dense tail8 4.0465 vs int8 4.0470). The robust claim
        is comparative — int8's trailing window must (a) be below its own
        leading window and (b) land within 0.05 nats of the dense path's
        trailing window, which pins 'quantization preserves optimization'
        independent of how fast this geometry happens to learn.
        """
        state, _, l8 = self._train(
            2, 2, 2, attn_impl="ring", steps=32, compression="int8",
            return_losses=True,
        )
        _, _, ld = self._train(
            2, 2, 2, attn_impl="ring", steps=32, return_losses=True,
        )
        head8 = float(np.mean(l8[:8]))
        tail8 = float(np.mean(l8[-8:]))
        tail_dense = float(np.mean(ld[-8:]))
        assert tail8 < head8, (
            f"int8 dp*tp*sp did not descend: head8={head8:.4f} "
            f"tail8={tail8:.4f} losses={l8}"
        )
        assert abs(tail8 - tail_dense) < 0.05, (
            f"int8 trajectory diverged from dense: int8 tail8={tail8:.4f} "
            f"dense tail8={tail_dense:.4f}"
        )
        assert int(state.step) == 32

    @pytest.mark.slow  # int8+TP training e2e (~38 s); int8 numerics stay
    # gated by the quantization unit tests and the serving artifact tests
    def test_int8_dp1_tp_only(self):
        """int8 under tp with dp=1: no data-parallel wire exists, so the
        path must degrade to quantize/dequantize noise WITHOUT emitting a
        collective (a psum over the size-1 manual axis trips an XLA
        partitioner RET_CHECK — found by the round-5 convergence run).
        First-step loss still matches dense (identical forward)."""
        _, m8 = self._train(1, 2, 1, steps=1, compression="int8")
        _, md = self._train(1, 2, 1, steps=1)
        np.testing.assert_allclose(
            float(m8["loss"]), float(md["loss"]), rtol=1e-5
        )
        state, m = self._train(1, 2, 1, steps=4, compression="int8")
        assert np.isfinite(float(m["loss"]))
        assert int(state.step) == 4

    def test_int8_trainer_wiring(self, tmp_path):
        """--compress-grad int8 composes with tp/sp through the Trainer
        (the round-3 rejection narrowed; topk still rejected)."""
        from pytorch_distributed_nn_tpu.training.trainer import (
            TrainConfig,
            Trainer,
        )

        cfg = TrainConfig(
            network="BertTiny", dataset="MLMSynth", batch_size=8,
            test_batch_size=8, optimizer="adam", lr=1e-3, max_steps=2,
            num_workers=2, tensor_parallel=2, seq_parallel=2,
            compression="int8", seq_len=32, vocab_size=64,
            train_dir=str(tmp_path), log_every=10, eval_batches=2,
        )
        tr = Trainer(cfg)
        try:
            history = tr.train()
        finally:
            tr.close()
        assert len(history) == 2
        assert np.isfinite(history[-1]["loss"])
        with pytest.raises(ValueError, match="topk"):
            Trainer(TrainConfig(
                network="BertTiny", dataset="MLMSynth", batch_size=8,
                num_workers=2, tensor_parallel=2, compression="topk",
                seq_len=32, vocab_size=64,
            ))

    def test_pallas_attn_trainer_tp_wiring(self, tmp_path):
        """--attn-impl pallas composes with tp-only meshes through the
        Trainer (round-4 verdict item 5); sp>1 still rejected."""
        from pytorch_distributed_nn_tpu.training.trainer import (
            TrainConfig,
            Trainer,
        )

        cfg = TrainConfig(
            network="BertTiny", dataset="MLMSynth", batch_size=8,
            test_batch_size=8, optimizer="adam", lr=1e-3, max_steps=2,
            num_workers=2, tensor_parallel=2, attn_impl="pallas",
            seq_len=32, vocab_size=64, train_dir=str(tmp_path),
            log_every=10, eval_batches=2,
        )
        tr = Trainer(cfg)
        try:
            history = tr.train()
        finally:
            tr.close()
        assert len(history) == 2
        assert np.isfinite(history[-1]["loss"])
        with pytest.raises(ValueError, match="seq_parallel"):
            Trainer(TrainConfig(
                network="BertTiny", dataset="MLMSynth", batch_size=8,
                num_workers=2, seq_parallel=2, attn_impl="pallas",
                seq_len=32, vocab_size=64,
            ))

    def test_params_actually_sharded(self):
        """TP shards the MLP kernel over the model axis."""
        from pytorch_distributed_nn_tpu.models.transformer import bert_tiny
        from pytorch_distributed_nn_tpu.optim import build_optimizer
        from pytorch_distributed_nn_tpu.training.spmd import create_spmd_state

        mesh = make_mesh(2, 2, 1, devices=jax.devices()[:4])
        model = bert_tiny(
            vocab_size=64, max_len=32, d_model=32, num_heads=4,
            num_layers=1, d_ff=64, dropout_rate=0.0, dtype=jnp.float32,
        )
        opt = build_optimizer("sgd", 0.1)
        state, shardings = create_spmd_state(
            model, opt, jax.random.PRNGKey(0), (4, 32), mesh
        )
        k = state.params["encoder"]["block_0"]["mlp_in"]["kernel"]
        spec = k.sharding.spec
        assert "model" in jax.tree.leaves(tuple(spec)), spec
        # a shard holds half the d_ff columns
        assert k.addressable_shards[0].data.shape == (32, 32)

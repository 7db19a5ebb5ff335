"""Pallas kernels in interpret mode: flash attention + int8 codec."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from pytorch_distributed_nn_tpu.models.transformer import full_attention
from pytorch_distributed_nn_tpu.ops import pallas_kernels as pk
from pytorch_distributed_nn_tpu.ops.pallas_kernels import (
    _causal_sweep,
    dequantize_int8,
    pallas_attention,
    quantize_int8,
)


def _qkv(B=2, L=128, H=2, D=32, seed=0):
    rng = np.random.RandomState(seed)
    return tuple(
        jnp.asarray(rng.randn(B, L, H, D).astype(np.float32))
        for _ in range(3)
    )


class TestFlashAttention:
    @pytest.mark.parametrize("causal", [False, True])
    def test_matches_full_attention(self, causal):
        q, k, v = _qkv()
        want = full_attention(q, k, v, None, causal=causal)
        got = pallas_attention(q, k, v, None, causal=causal)
        np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)

    def test_pad_mask(self):
        q, k, v = _qkv()
        mask = jnp.ones((2, 128)).at[:, 100:].set(0.0)
        want = full_attention(q, k, v, mask)
        got = pallas_attention(q, k, v, mask)
        np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)

    def test_multi_q_blocks(self):
        q, k, v = _qkv(L=256)
        want = full_attention(q, k, v, None)
        got = pallas_attention(q, k, v, None)
        np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)

    @pytest.mark.parametrize("causal", [False, True])
    def test_grads_match(self, causal):
        q, k, v = _qkv(L=128)

        def loss_p(qkv):
            return (pallas_attention(*qkv, None, causal=causal) ** 2).sum()

        def loss_f(qkv):
            return (full_attention(*qkv, None, causal=causal) ** 2).sum()

        gp = jax.grad(loss_p)((q, k, v))
        gf = jax.grad(loss_f)((q, k, v))
        for a, b in zip(gp, gf):
            np.testing.assert_allclose(a, b, rtol=2e-4, atol=2e-4)

    def test_in_transformer(self):
        """BertTiny with attn_fn=pallas_attention gives the same logits."""
        from pytorch_distributed_nn_tpu.models.transformer import bert_tiny

        kw = dict(vocab_size=64, max_len=128, d_model=64, num_heads=2,
                  num_layers=2, d_ff=128, dropout_rate=0.0,
                  dtype=jnp.float32)
        ref = bert_tiny(**kw)
        pal = bert_tiny(attn_fn=pallas_attention, **kw)
        toks = jax.random.randint(jax.random.PRNGKey(0), (2, 128), 4, 64)
        variables = ref.init({"params": jax.random.PRNGKey(1)}, toks)
        np.testing.assert_allclose(
            pal.apply(variables, toks), ref.apply(variables, toks),
            rtol=2e-4, atol=2e-4,
        )

    def test_short_length_clamps_block(self):
        q, k, v = _qkv(L=96)  # L < default block 512 -> blocks clamp to 96
        got = pallas_attention(q, k, v, None)
        want = full_attention(q, k, v, None)
        np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)

    @pytest.mark.parametrize("L", [600, 768])
    def test_non_power_of_two_lengths_pick_divisor_blocks(self, L):
        # 600 -> block 200, 768 -> block 384 (largest mult-of-8 divisor <=512)
        q, k, v = _qkv(L=L)
        got = pallas_attention(q, k, v, None)
        want = full_attention(q, k, v, None)
        np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)

    def test_rejects_length_with_no_valid_block(self):
        q, k, v = _qkv(L=514)  # 2*257: no multiple-of-8 divisor
        with pytest.raises(ValueError, match="pad the sequence"):
            pallas_attention(q, k, v, None)

    def test_grads_match_with_pad_mask(self):
        """Backward kernels re-apply the key pad mask blockwise."""
        q, k, v = _qkv(L=128)
        mask = jnp.ones((2, 128)).at[:, 96:].set(0.0)

        def loss_p(qkv):
            return (pallas_attention(*qkv, mask) ** 2).sum()

        def loss_f(qkv):
            return (full_attention(*qkv, mask) ** 2).sum()

        gp = jax.grad(loss_p)((q, k, v))
        gf = jax.grad(loss_f)((q, k, v))
        for a, b in zip(gp, gf):
            np.testing.assert_allclose(a, b, rtol=2e-4, atol=2e-4)

    @pytest.mark.parametrize("causal", [False, True])
    def test_grads_match_long_sequence(self, causal):
        """L=4096 (8 q-blocks x 8 k-blocks): the blockwise backward
        reproduces full-attention gradients across many blocks."""
        q, k, v = _qkv(B=1, L=4096, H=1, D=32, seed=3)

        def loss_p(qkv):
            return (pallas_attention(*qkv, None, causal=causal) ** 2).sum()

        def loss_f(qkv):
            return (full_attention(*qkv, None, causal=causal) ** 2).sum()

        gp = jax.grad(loss_p)((q, k, v))
        gf = jax.grad(loss_f)((q, k, v))
        for a, b in zip(gp, gf):
            np.testing.assert_allclose(a, b, rtol=5e-4, atol=5e-4)

    @pytest.mark.parametrize("causal", [False, True])
    def test_streamed_kernels_match(self, causal, monkeypatch):
        """L > _RESIDENT_MAX_L dispatches to the streamed-grid kernels
        (K/V and Q/dO flow through the grid with scratch accumulators —
        the unbounded-L path that runs L=65536 on one chip). Force the
        dispatch at a small L and check values AND grads against the
        resident path's ground truth (full_attention), with a pad mask."""
        from pytorch_distributed_nn_tpu.ops import pallas_kernels as pk

        monkeypatch.setattr(pk, "_RESIDENT_MAX_L", 64)
        # Shrink the block too: with the default 512, L=256 would be a
        # single (1, 1) inner grid and the cross-iteration scratch carry
        # (init / accumulate / finalize, causal block skip) would never
        # run more than once. 64 gives a 4x4 block grid.
        monkeypatch.setattr(pk, "_PREFERRED_BLOCK", 64)
        pk._FLASH_CACHE.clear()
        try:
            q, k, v = _qkv(B=2, L=256, H=2, D=32, seed=5)
            mask = jnp.asarray(
                np.arange(256)[None, :] < np.array([200, 256])[:, None]
            )
            valid = mask[:, :, None, None]

            def loss_p(qkv):
                out = pallas_attention(*qkv, mask, causal=causal)
                return (jnp.where(valid, out, 0) ** 2).sum()

            def loss_f(qkv):
                out = full_attention(*qkv, mask, causal=causal)
                return (jnp.where(valid, out, 0) ** 2).sum()

            got = pallas_attention(q, k, v, mask, causal=causal)
            want = full_attention(q, k, v, mask, causal=causal)
            np.testing.assert_allclose(
                jnp.where(valid, got, 0), jnp.where(valid, want, 0),
                rtol=2e-4, atol=2e-4,
            )
            gp = jax.grad(loss_p)((q, k, v))
            gf = jax.grad(loss_f)((q, k, v))
            for a, b in zip(gp, gf):
                np.testing.assert_allclose(a, b, rtol=5e-4, atol=5e-4)
        finally:
            pk._FLASH_CACHE.clear()

    @pytest.mark.parametrize("L,D,block,causal,window,padded", [
        (256, 32, 64, True, None, False), (256, 32, 64, True, 128, False),
        (256, 32, 64, True, 100, False), (256, 32, 64, False, None, False),
        (256, 32, 64, True, None, True), (256, 32, 64, True, 100, True),
        (256, 32, 64, False, None, True),
        (512, 128, 128, True, None, False),
        (512, 128, 128, True, 256, False),
        (512, 128, 128, True, 200, True),
        (512, 128, 128, False, None, True)],
        ids=["causal", "window_of_blocks", "window_not_blocks", "no_mask",
             "pad_causal", "pad_window", "pad_full", "w128_causal",
             "w128_window_of_blocks", "w128_pad_window_not_blocks",
             "w128_pad_full"])
    def test_streamed_forward_out_and_lse(self, L, D, block, causal, window,
                                          padded, monkeypatch):
        """The streamed forward alone on a 4 x 4 block grid: its output
        and its log-sum-exp residual against dense attention. Its running
        statistics are lane-replicated (128 lanes); at width 32 and blocks
        of 64 they are cut to fewer lanes, at width 128 and blocks of 128
        they are repeated whole, as at the cells' shapes."""
        monkeypatch.setattr(pk, "_RESIDENT_MAX_L", 64)
        B, H = 2, 2
        assert not pk._resident(L, D)
        q, k, v = _qkv(B=B, L=L, H=H, D=D, seed=21)
        mask = (jnp.asarray(np.arange(L)[None, :]
                            < np.array([L - 56, L])[:, None])
                if padded else None)
        with jax.default_matmul_precision("highest"):
            out, lse = pk._flash_forward(q, k, v, mask, causal, block,
                                         block, window)
            want = full_attention(q, k, v, mask, causal=causal,
                                  window=window)
            s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(D)
        keep = np.ones((B, 1, L, L), bool)
        if padded:
            keep = keep & np.asarray(mask)[:, None, None, :]
        if causal:
            pos = np.arange(L)
            seen = pos[:, None] >= pos[None, :]
            if window is not None:
                seen = seen & (pos[:, None] - pos[None, :] < window)
            keep = keep & seen
        want_lse = jax.nn.logsumexp(jnp.where(keep, s, -1e30), axis=-1)
        np.testing.assert_allclose(out, want, atol=2e-5)
        np.testing.assert_allclose(
            lse, want_lse.reshape(B * H, L, 1), atol=2e-5)

    def test_backward_has_no_quadratic_intermediate(self):
        """Training memory is sub-quadratic: no L×L array anywhere in the
        jaxpr of the flash VJP (the O(L²) score/probability matrices exist
        only as per-block tiles inside the kernels), while the stock XLA
        attention VJP does materialize them."""
        L = 2048
        q, k, v = _qkv(B=1, L=L, H=1, D=32)

        def big_avals(fn):
            jaxpr = jax.make_jaxpr(jax.grad(fn))((q, k, v))
            found = []

            def walk(jx):
                for eqn in jx.eqns:
                    for var in list(eqn.invars) + list(eqn.outvars):
                        aval = getattr(var, "aval", None)
                        shape = getattr(aval, "shape", ())
                        if sum(1 for d in shape if d >= L) >= 2:
                            found.append(shape)
                    for sub in eqn.params.values():
                        if hasattr(sub, "eqns"):
                            walk(sub)
                        elif hasattr(sub, "jaxpr") and hasattr(
                            sub.jaxpr, "eqns"
                        ):
                            walk(sub.jaxpr)
            walk(jaxpr.jaxpr)
            return found

        def loss_p(qkv):
            return (pallas_attention(*qkv, None) ** 2).sum()

        def loss_f(qkv):
            return (full_attention(*qkv, None) ** 2).sum()

        assert big_avals(loss_f), "sanity: XLA attention VJP has L×L arrays"
        assert not big_avals(loss_p), (
            f"flash VJP materializes quadratic arrays: {big_avals(loss_p)}"
        )


class TestCausalSweep:
    """The resident kernels' causal loop bounds (`_causal_sweep`)."""

    @pytest.mark.parametrize("own_is_query", [True, False],
                             ids=["fwd_dq", "dkv"])
    @pytest.mark.parametrize("L,bq,bk", [
        (2048, 512, 512), (2048, 256, 512), (2048, 512, 256),
        (768, 384, 384), (600, 600, 600), (8192, 512, 512)])
    def test_range_is_exactly_the_blocks_with_a_visible_score(
            self, L, bq, bk, own_is_query):
        """By brute force over positions: nothing needed is skipped,
        nothing wholly masked is swept."""
        pos = np.arange(L)
        visible = (pos[:, None] >= pos[None, :]).reshape(
            L // bq, bq, L // bk, bk).any(axis=(1, 3))  # (q block, k block)
        own, swept = (bq, bk) if own_is_query else (bk, bq)
        n = L // swept
        total = 0
        for j in range(L // own):
            lo, hi = _causal_sweep(True, j, own, swept, n, own_is_query)
            needed = visible[j] if own_is_query else visible[:, j]
            assert list(range(lo, hi)) == list(np.flatnonzero(needed)), j
            total += hi - lo
        assert total == visible.sum()
        if (L, bq, bk) == (8192, 512, 512):
            assert total == 136  # of 256: the LFM2 cell's shape

    @pytest.mark.parametrize("own_is_query", [True, False],
                             ids=["fwd_dq", "dkv"])
    def test_non_causal_bounds_are_python_ints(self, own_is_query):
        """A static trip count: BERT's programs lower as before."""
        lo, hi = _causal_sweep(False, jnp.int32(3), 512, 512, 16,
                               own_is_query)
        assert (type(lo), type(hi)) == (int, int)
        assert (lo, hi) == (0, 16)

    def test_causal_matches_full_attention_at_the_cells_head(self):
        """bfloat16, D = 64, 4 x 4 blocks of 512 (resident path), one batch
        row with its whole leading key block padded away: values and all
        three gradients against stock attention in float32. That row's
        first 512 queries see no key at all and are left out on both
        sides."""
        B, L, H, D = 2, 2048, 2, 64
        q, k, v = (x.astype(jnp.bfloat16)
                   for x in _qkv(B=B, L=L, H=H, D=D, seed=11))
        mask = jnp.asarray(np.arange(L)[None, :] >= np.array([512, 0])[:, None])
        valid = mask[:, :, None, None]
        w = _qkv(B=B, L=L, H=H, D=D, seed=12)[0]

        def loss(attn, qkv):
            out = attn(*qkv, mask, causal=True).astype(jnp.float32)
            return (jnp.where(valid, out, 0) * w).sum(), out

        f32 = tuple(x.astype(jnp.float32) for x in (q, k, v))
        (_, got), gp = jax.value_and_grad(
            lambda x: loss(pallas_attention, x), has_aux=True)((q, k, v))
        (_, want), gf = jax.value_and_grad(
            lambda x: loss(full_attention, x), has_aux=True)(f32)

        def rel(a, b):
            a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
            return np.linalg.norm(a - b) / np.linalg.norm(b)

        # bfloat16 rounding reads 0.002-0.003 here; a skipped block, 0.1 up
        assert rel(jnp.where(valid, got, 0), jnp.where(valid, want, 0)) < 6e-3
        for a, b in zip(gp, gf):
            assert rel(a, b) < 6e-3


class TestInt8Codec:
    def test_roundtrip_error_bounded(self):
        rng = np.random.RandomState(0)
        x = jnp.asarray(rng.randn(64, 256).astype(np.float32))
        q, scale = quantize_int8(x, 7)
        assert q.dtype == jnp.int8
        back = dequantize_int8(q, scale)
        # max error is one quantization step (stochastic rounding)
        step = float(jnp.max(jnp.abs(x))) / 127.0
        assert float(jnp.max(jnp.abs(back - x))) <= step * 1.001

    def test_stochastic_rounding_unbiased(self):
        x = jnp.full((8, 128), 0.5 * 3.0 / 127.0)  # halfway between steps
        qs = []
        for seed in range(50):
            q, scale = quantize_int8(
                jnp.concatenate([x, jnp.full((1, 128), 3.0 / 127.0 * 127)]),
                seed,
            )
            qs.append(np.asarray(q[:-1], np.float32))
        mean_q = np.mean(qs)
        assert 0.3 < mean_q < 0.7  # rounds up ~half the time

    def test_zero_input(self):
        q, scale = quantize_int8(jnp.zeros((8, 128)), 0)
        assert float(jnp.max(jnp.abs(dequantize_int8(q, scale)))) == 0.0

    def test_rejects_non_2d(self):
        with pytest.raises(ValueError):
            quantize_int8(jnp.zeros((2, 3, 4)), 0)

    def test_scaled_variant_matches_jnp_quant(self):
        """quantize_int8_scaled with a given scale ≈ g/scale, |err| <= 1."""
        from pytorch_distributed_nn_tpu.ops.pallas_kernels import (
            quantize_int8_scaled,
        )

        rng = np.random.RandomState(3)
        x = jnp.asarray(rng.randn(1, 4096).astype(np.float32))
        scale = float(jnp.max(jnp.abs(x))) / 127.0
        q = quantize_int8_scaled(x, 11, scale)
        assert q.dtype == jnp.int8
        err = np.abs(np.asarray(q, np.float32) - np.asarray(x) / scale)
        assert err.max() <= 1.0001  # stochastic rounding: one step max

    def test_scaled_variant_under_jit(self):
        from pytorch_distributed_nn_tpu.ops.pallas_kernels import (
            quantize_int8_scaled,
        )

        f = jax.jit(lambda x, s: quantize_int8_scaled(x, s, 0.1))
        q = f(jnp.ones((1, 256)), 5)
        assert q.shape == (1, 256)


class TestFusedLayerNorm:
    """fused_layer_norm vs the plain-jnp reference: values AND all three
    gradients, across the kernel's tiling regimes (grid>1, row padding,
    whole-block for D%128!=0, bf16 input)."""

    @staticmethod
    def _ref(x, g, b, eps=1e-6):
        xf = x.astype(jnp.float32)
        mu = jnp.mean(xf, axis=-1, keepdims=True)
        xc = xf - mu
        var = jnp.mean(xc * xc, axis=-1, keepdims=True)
        return xc * jax.lax.rsqrt(var + eps) * g + b

    @pytest.mark.parametrize(
        "shape,dtype,regime",
        [
            ((4, 256, 128), jnp.float32, "grid4"),      # N=1024, BN=256
            ((300, 128), jnp.float32, "row-pad"),       # pad 300 -> 512
            ((2, 8, 96), jnp.float32, "whole-block"),   # D % 128 != 0
            ((3, 5, 768), jnp.bfloat16, "bf16"),
            ((300, 2048), jnp.float32, "vmem-budget"),  # BN shrunk below 256
        ],
    )
    def test_values_and_grads(self, shape, dtype, regime):
        from pytorch_distributed_nn_tpu.ops.pallas_kernels import (
            fused_layer_norm,
        )

        rng = np.random.RandomState(7)
        x = jnp.asarray(rng.randn(*shape), dtype)
        g = jnp.asarray(rng.randn(shape[-1]), jnp.float32) + 1.0
        b = jnp.asarray(rng.randn(shape[-1]), jnp.float32)
        dy = jnp.asarray(rng.randn(*shape), jnp.float32)

        y = fused_layer_norm(x, g, b, out_dtype=jnp.float32)
        np.testing.assert_allclose(
            y, self._ref(x, g, b), rtol=2e-5, atol=2e-5
        )

        def scal(fn):
            return lambda x, g, b: jnp.sum(
                fn(x, g, b).astype(jnp.float32) * dy
            )

        got = jax.grad(
            scal(lambda x, g, b: fused_layer_norm(x, g, b, 1e-6,
                                                  jnp.float32)),
            argnums=(0, 1, 2),
        )(x, g, b)
        want = jax.grad(scal(self._ref), argnums=(0, 1, 2))(x, g, b)
        # dx in x.dtype; at bf16 compare with bf16-quantization tolerance
        tol = 2e-2 if dtype == jnp.bfloat16 else 5e-5
        for a, w in zip(got, want):
            np.testing.assert_allclose(
                a.astype(jnp.float32), w.astype(jnp.float32),
                rtol=tol, atol=tol,
            )

    def test_geometry_respects_vmem_budget(self):
        """BN is derived from the VMEM byte budget (~5 f32 copies of the
        (BN, D) block), not pinned at 256: wide d_model shrinks the block
        (multiple-of-8 sublanes) and an un-tileable D falls back to the
        jnp path instead of a Mosaic VMEM blow-up (round-5 advisor
        finding: d_model >= ~1600 with BN=256 exceeded ~16 MiB)."""
        from pytorch_distributed_nn_tpu.ops.pallas_kernels import (
            _LN_VMEM_BUDGET,
            _LN_WORKING_COPIES,
            _ln_geometry,
        )

        assert _ln_geometry(1024, 512) == (256, 0)  # narrow: unchanged
        for D in (1024, 2048, 4096, 8192):
            BN, pad = _ln_geometry(1024, D)
            assert BN % 8 == 0 and 8 <= BN < 1024
            assert _LN_WORKING_COPIES * BN * D * 4 <= _LN_VMEM_BUDGET
            assert (1024 + pad) % BN == 0
        # monotone: wider rows, fewer of them per block
        widths = [_ln_geometry(1024, D)[0] for D in (512, 2048, 8192)]
        assert widths == sorted(widths, reverse=True)
        # no legal block at all -> None (caller uses the jnp fallback)
        assert _ln_geometry(1024, 128 * 2048) is None
        assert _ln_geometry(0, 512) is None  # empty batch

    def test_out_dtype_written_directly(self):
        from pytorch_distributed_nn_tpu.ops.pallas_kernels import (
            fused_layer_norm,
        )

        x = jnp.ones((8, 128), jnp.bfloat16)
        g = jnp.ones((128,), jnp.float32)
        b = jnp.zeros((128,), jnp.float32)
        assert fused_layer_norm(x, g, b).dtype == jnp.bfloat16
        assert fused_layer_norm(
            x, g, b, out_dtype=jnp.float32
        ).dtype == jnp.float32

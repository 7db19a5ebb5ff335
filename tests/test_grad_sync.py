"""Gradient-sync tests on a real 8-device virtual mesh.

This is the testability the reference never had (SURVEY.md §4): PS
semantics — num-aggregate backup-worker drops
(src/sync_replicas_master_nn.py:179-182), averaging by num_aggregate
(:207) — verified without any cluster.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from pytorch_distributed_nn_tpu.ops import compression as C
from jax import shard_map
from pytorch_distributed_nn_tpu.parallel import make_grad_sync, make_mesh


def _per_replica_grads(n=8, shape=(4, 3), seed=0):
    rng = np.random.RandomState(seed)
    return rng.randn(n, *shape).astype(np.float32)


def _run_sync(sync, grads_stacked, key=None, state_stacked=None):
    """shard_map a sync stage over the data axis of an 8-device mesh."""
    mesh = make_mesh(8, 1)
    key = key if key is not None else jax.random.PRNGKey(0)

    @jax.jit
    @shard_map(
        mesh=mesh,
        in_specs=(P("data"), P(), P("data") if state_stacked is not None else P()),
        out_specs=(P("data"), P("data") if state_stacked is not None else P()),
    )
    def run(g_block, key, state_block):
        g = jax.tree.map(lambda x: x[0], g_block)  # unstack this replica's grad
        state = (
            jax.tree.map(lambda x: x[0], state_block)
            if state_stacked is not None
            else None
        )
        out, new_state = sync(g, state, key)
        expand = lambda t: jax.tree.map(lambda x: x[None], t)
        return expand(out), expand(new_state) if state_stacked is not None else None

    out, new_state = run(
        jnp.asarray(grads_stacked),
        key,
        jnp.asarray(state_stacked) if state_stacked is not None else None,
    )
    return np.asarray(out), (
        np.asarray(new_state) if state_stacked is not None else None
    )


def test_allreduce_is_mean():
    g = _per_replica_grads()
    sync = make_grad_sync("allreduce")
    out, _ = _run_sync(sync, g)
    for r in range(8):
        np.testing.assert_allclose(out[r], g.mean(0), rtol=1e-5)


def test_local_mode_no_sync():
    g = _per_replica_grads()
    sync = make_grad_sync("local")
    out, _ = _run_sync(sync, g)
    np.testing.assert_allclose(out, g, rtol=1e-6)


def test_ps_rank_arrival_takes_first_k():
    g = _per_replica_grads()
    k = 5
    sync = make_grad_sync("ps", num_aggregate=k, arrival="rank")
    out, _ = _run_sync(sync, g)
    expected = g[:k].sum(0) / k  # first k ranks aggregated, averaged by k
    for r in range(8):
        np.testing.assert_allclose(out[r], expected, rtol=1e-5)


def test_ps_random_arrival_drops_exactly_n_minus_k():
    g = _per_replica_grads()
    k = 3
    sync = make_grad_sync("ps", num_aggregate=k, arrival="random")
    out, _ = _run_sync(sync, g, key=jax.random.PRNGKey(7))
    # The result must equal mean-of-some-k-subset scaled by k; check that
    # out * k is a sum of exactly k of the inputs.
    target = out[0] * k
    best = None
    import itertools

    for combo in itertools.combinations(range(8), k):
        s = g[list(combo)].sum(0)
        err = np.abs(s - target).max()
        best = err if best is None else min(best, err)
    assert best < 1e-4, f"no k-subset matches (best err {best})"


def test_ps_num_aggregate_none_equals_allreduce():
    g = _per_replica_grads()
    out, _ = _run_sync(sync=make_grad_sync("ps", num_aggregate=None), grads_stacked=g)
    np.testing.assert_allclose(out[0], g.mean(0), rtol=1e-5)


def test_int8_compression_close_to_mean():
    g = _per_replica_grads(seed=3)
    sync = make_grad_sync("allreduce", compression="int8")
    out, _ = _run_sync(sync, g)
    amax = np.abs(g).max()
    # Per-replica quantization error <= amax/127 (stochastic rounding, 1 ulp);
    # the mean over 8 replicas keeps the same bound.
    np.testing.assert_allclose(out[0], g.mean(0), atol=amax / 127 + 1e-6)


def test_topk_error_feedback_conserves_gradient():
    g = _per_replica_grads(seed=5)
    ef = np.zeros_like(g)
    sync = make_grad_sync("allreduce", compression="topk", topk_ratio=0.25)
    out, new_ef = _run_sync(sync, g, state_stacked=ef)
    # sent + residual == g + old residual (nothing lost, only delayed)
    # out is the mean of per-replica sent values; reconstruct sent from ef.
    sent = g - new_ef  # since old ef was zero: sent = (g+0) - residual
    np.testing.assert_allclose(out[0], sent.mean(0), rtol=1e-5)
    # each replica keeps exactly ceil(0.25*12)=3 coords per 4x3 leaf
    for r in range(8):
        assert (sent[r] != 0).sum() == 3


def test_ps_topk_ef_preserves_dropped_gradient():
    """EF contract under PS backup-worker drops (random arrival): a replica
    masked out this step keeps its ENTIRE accumulated gradient in the
    error-feedback residual for a later step — neither aggregated nor lost."""
    g = _per_replica_grads(seed=31)
    k = 4
    sync = make_grad_sync(
        "ps", num_aggregate=k, arrival="random",
        compression="topk", topk_ratio=0.25,
    )
    ef = np.zeros_like(g)
    out, new_ef = _run_sync(
        sync, g, key=jax.random.PRNGKey(3), state_stacked=ef
    )
    # dropped replicas retain g in full; contributors only the un-sent part
    full = [r for r in range(8) if np.allclose(new_ef[r], g[r], rtol=1e-6)]
    assert len(full) == 8 - k
    contributors = [r for r in range(8) if r not in full]
    sent = np.stack([g[r] - new_ef[r] for r in contributors])
    np.testing.assert_allclose(out[0], sent.sum(0) / k, rtol=1e-4)


def test_ps_topk_permanent_exclusion_stays_bounded():
    """Deterministic exclusions (rank arrival past num_aggregate) do NOT
    retain their sent mass — a backup worker dropped every step must not
    grow its residual without bound (and checkpointed residuals must not
    become a delayed gradient bomb)."""
    g = _per_replica_grads(seed=32)
    k = 4
    sync = make_grad_sync(
        "ps", num_aggregate=k, arrival="rank",
        compression="topk", topk_ratio=0.25,
    )
    ef = np.zeros_like(g)
    _, new_ef = _run_sync(sync, g, state_stacked=ef)
    for r in range(k, 8):
        # residual = g - sent (top-k removed), NOT the full g
        assert not np.allclose(new_ef[r], g[r])
        assert (np.abs(new_ef[r]) <= np.abs(g[r]) + 1e-6).all()


def test_ps_topk_mass_conservation_over_steps():
    """Over K steps with random arrival no gradient mass is ever lost:
    sum over steps of (delivered mean * num_aggregate) plus the final
    residuals equals K * sum of per-replica gradients."""
    g = _per_replica_grads(seed=33)
    k = 6
    sync = make_grad_sync(
        "ps", num_aggregate=k, arrival="random",
        compression="topk", topk_ratio=0.25,
    )
    ef = np.zeros_like(g)
    delivered = np.zeros(g.shape[1:], np.float64)
    steps = 5
    for t in range(steps):
        out, ef = _run_sync(
            sync, g, key=jax.random.PRNGKey(100 + t), state_stacked=ef
        )
        delivered += np.asarray(out[0], np.float64) * k
    total_in = steps * g.sum(0).astype(np.float64)
    np.testing.assert_allclose(delivered + ef.sum(0), total_in, rtol=1e-4)


@pytest.mark.slow  # 2x160-step convergence comparison (~30 s)
def test_ps_topk_convergence_matches_allreduce():
    """End-to-end: PS with backup-worker drops + topk EF still converges
    comparably to plain allreduce (the EF fix makes this hold — without it,
    dropped replicas' gradient mass vanishes every step)."""
    from pytorch_distributed_nn_tpu.training.trainer import (
        TrainConfig,
        Trainer,
    )

    def run(**kw):
        # lr 0.005 / 160 steps, not 0.01 / 40: EF re-delivers dropped
        # mass in bursts (num_aggregate=1 of 2 ≈ 2x effective step), and
        # lr 0.01 sat past the oscillation edge on an earlier jaxlib —
        # the property pinned below is EF convergence, not the knee
        # position, so test inside the stable region on every stack.
        cfg = TrainConfig(
            network="LeNet", dataset="MNIST", batch_size=16,
            test_batch_size=16, max_steps=160, num_workers=2,
            synthetic_size=256, lr=0.005, log_every=10**9, **kw,
        )
        tr = Trainer(cfg)
        try:
            return tr.train()
        finally:
            tr.close()

    ar = run()
    # Trainer's grad-sync uses the default random arrival order
    ps = run(sync_mode="ps", num_aggregate=1, compression="topk",
             topk_ratio=0.25)
    # Allreduce reaches ~0.003; PS with num_aggregate=1 delivers half the
    # gradient mass late (EF), so it trails (~0.1 from 3.69) — but it must
    # clearly converge; without the EF fix the dropped mass is lost and it
    # stalls or diverges.
    assert ar[-1]["loss"] < 0.2
    assert ps[-1]["loss"] < ps[0]["loss"] / 2
    assert ps[-1]["loss"] < 1.5


def test_topk_mask_leaf_static_k():
    g = jnp.asarray(np.arange(12, dtype=np.float32).reshape(4, 3))
    mask = C._topk_mask_leaf(g, 0.5)
    assert int(mask.sum()) == 6
    assert mask[-1, -1] == 1  # largest magnitude kept


def test_topk_approx_method_keeps_about_k_and_conserves_mass():
    """The TPU-fast approx threshold keeps ~k coordinates; whatever it
    drops stays in the EF residual (sent + resid == acc exactly, for any
    threshold) — the property that makes the approximation benign."""
    rng = np.random.RandomState(0)
    g = jnp.asarray(rng.randn(64, 128).astype(np.float32))
    e = jnp.asarray(rng.randn(64, 128).astype(np.float32))
    k = int(g.size * 0.1 + 0.999999)
    mask = C._topk_mask_leaf(g, 0.1, method="approx")
    assert 0.5 * k <= int(mask.sum()) <= 2 * k
    sent, resid = C.topk_compress_ef({"w": g}, {"w": e}, 0.1, "approx")
    np.testing.assert_allclose(
        np.asarray(sent["w"] + resid["w"]), np.asarray(g + e), rtol=1e-6
    )
    # disjoint support: nothing is both sent and kept as residual
    assert float(jnp.sum(jnp.abs(sent["w"]) * jnp.abs(resid["w"]))) == 0.0


def test_config_validation():
    with pytest.raises(ValueError):
        make_grad_sync("gossip")
    with pytest.raises(ValueError):
        make_grad_sync("allreduce", compression="zip")


def test_straggler_kill_ranks_excluded_allreduce():
    """Killed replicas never contribute (reference C6 signal/timeout kill)."""
    g = _per_replica_grads(seed=9)
    sync = make_grad_sync("allreduce", kill_ranks=(2, 5))
    out, _ = _run_sync(sync, g)
    alive = [r for r in range(8) if r not in (2, 5)]
    expected = g[alive].mean(0)
    np.testing.assert_allclose(out[0], expected, rtol=1e-5)


def test_straggler_kill_with_ps_rank_arrival():
    g = _per_replica_grads(seed=11)
    # rank arrival order 0,1,2,... with rank 0 killed: contributors = 1,2,3
    sync = make_grad_sync(
        "ps", num_aggregate=3, arrival="rank", kill_ranks=(0,)
    )
    out, _ = _run_sync(sync, g)
    # positions < 3 are ranks 0,1,2; rank 0 killed -> only 1,2 contribute,
    # still divided by the fixed num_aggregate (reference :207 semantics)
    expected = g[[1, 2]].sum(0) / 3.0
    np.testing.assert_allclose(out[0], expected, rtol=1e-5)


def test_straggler_kill_int8_matches_uncompressed_divisor():
    """int8 compression must not change PS kill semantics: the divisor stays
    the FIXED num_aggregate, identical to the uncompressed branch."""
    g = _per_replica_grads(seed=12)
    kw = dict(num_aggregate=3, arrival="rank", kill_ranks=(0,))
    out_i8, _ = _run_sync(make_grad_sync("ps", compression="int8", **kw), g)
    expected = g[[1, 2]].sum(0) / 3.0
    # int8 stochastic quantization: loose tolerance, but a 1.5x divisor bug
    # (dividing by 2 live contributors) would blow way past it.
    np.testing.assert_allclose(out_i8[0], expected, atol=0.06)


class TestBucketedSync:
    """C12 parity: bucketed flat collectives (dead DDP path, ~1 MB buckets)."""

    def test_flatten_roundtrip_unaligned_boundaries(self):
        from pytorch_distributed_nn_tpu.ops.compression import (
            flatten_buckets,
            unflatten_buckets,
        )

        rng = np.random.RandomState(0)
        tree = {
            "a": jnp.asarray(rng.randn(7, 13).astype(np.float32)),
            "b": jnp.asarray(rng.randn(5).astype(np.float32)),
            "c": jnp.asarray(rng.randn(3, 2, 4).astype(np.float32)),
        }
        buckets, meta = flatten_buckets(tree, bucket_bytes=64)  # 16 floats
        assert all(b.size <= 16 for b in buckets)
        assert sum(b.size for b in buckets) == 7 * 13 + 5 + 24
        back = unflatten_buckets(buckets, meta)
        for k in tree:
            np.testing.assert_array_equal(back[k], tree[k])

    def test_bucketed_allreduce_matches_plain(self):
        g = _per_replica_grads(seed=21)
        plain, _ = _run_sync(make_grad_sync("allreduce"), g)
        bucketed, _ = _run_sync(
            make_grad_sync("allreduce", bucket_bytes=128), g
        )
        np.testing.assert_allclose(bucketed[0], plain[0], rtol=1e-6)

    def test_bucketed_ps_num_aggregate(self):
        g = _per_replica_grads(seed=22)
        kw = dict(num_aggregate=2, arrival="rank")
        plain, _ = _run_sync(make_grad_sync("ps", **kw), g)
        bucketed, _ = _run_sync(
            make_grad_sync("ps", bucket_bytes=64, **kw), g
        )
        np.testing.assert_allclose(bucketed[0], plain[0], rtol=1e-6)

    def test_bucketed_int8_within_tolerance(self):
        g = _per_replica_grads(seed=23)
        exact, _ = _run_sync(make_grad_sync("allreduce"), g)
        bucketed, _ = _run_sync(
            make_grad_sync("allreduce", compression="int8",
                           bucket_bytes=256),
            g,
        )
        # int8 over the shared-bucket scale: one quant step of the bucket amax
        step = np.abs(np.asarray(g)).max() / 127.0
        assert np.max(np.abs(np.asarray(bucketed[0]) - np.asarray(exact[0]))) \
            <= step * 1.01

    def test_bucketing_rejects_topk(self):
        with pytest.raises(ValueError, match="topk"):
            make_grad_sync("allreduce", compression="topk", bucket_bytes=64)

    def test_trainer_with_buckets(self):
        from pytorch_distributed_nn_tpu.training.trainer import (
            TrainConfig,
            Trainer,
        )

        cfg = TrainConfig(
            network="LeNet", dataset="MNIST", batch_size=8,
            test_batch_size=8, max_steps=2, num_workers=2,
            synthetic_size=64, bucket_bytes=1 << 20, log_every=10,
        )
        tr = Trainer(cfg)
        try:
            history = tr.train()
        finally:
            tr.close()
        assert len(history) == 2
        assert np.isfinite(history[-1]["loss"])


def test_kill_ranks_rejected_in_local_mode():
    with pytest.raises(ValueError):
        make_grad_sync("local", kill_ranks=(1,))

"""The Granite-4.0-H hybrid decoder at its tiny preset, against the
benchmark's plain float32 reference (``benchmark/reference/
granite4_h_micro_pp4.py``, which shares nothing with the program but the
parameter tree's names): the whole model's loss and gradients, the chunked
state-space scan (``ops/ssd.py``, interpret mode) against the recurrence
stepped position by position at three chunk sizes, decays that would
overflow a factored form, the boundary-state counter, planted faults
against the cell's limits, and the trainer's records.
"""

import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import manifest
from pytorch_distributed_nn_tpu.models import (
    GENERATIVE_MODELS,
    build_model,
    granite4_hybrid,
    lfm2,
)
from pytorch_distributed_nn_tpu.ops import ssd
from pytorch_distributed_nn_tpu.ops.metrics import masked_cross_entropy
from pytorch_distributed_nn_tpu.parallel.partitioning import unbox

REF = manifest.load_module(
    manifest.ROOT, "benchmark/reference/granite4_h_micro_pp4.py")
CONTROLS = manifest.load_module(
    manifest.ROOT, "benchmark/tools/granite4_controls.py")
L = 64


def ref_config(cfg: granite4_hybrid.Granite4HybridConfig, length: int):
    """The reference's view of a program configuration."""
    keys = ("vocab_size", "mamba_n_heads", "mamba_d_head", "mamba_d_state",
            "embedding_multiplier", "residual_multiplier", "logits_scaling",
            "attention_multiplier", "rms_norm_eps")
    return {"tokens_per_sample": length, "model": {
        **{k: getattr(cfg, k) for k in keys},
        "layer_types": list(cfg.layer_types)}}


def _widen(path, a):
    name = jax.tree_util.keystr(path)
    if "q_proj" in name or "k_proj" in name:
        return 20.0 * a
    if "A_log" in name:
        return jnp.full_like(a, np.log(0.25))       # A = -1/4
    if "dt_bias" in name:
        return jnp.full_like(a, np.log(np.expm1(0.05)))   # dt ~ 0.05
    return 5.0 * a if "kernel" in name or "embedding" in name else a


def init(model, tokens, seed=1):
    """Seeded weights, every projection and the embedding drawn five times
    wider than the model's 0.02, so that at 64 wide each mixer and the MLPs
    weigh in the loss beside the residual stream, and the query and key
    projections twenty times, so that attention's scores at its scale of
    1/16 are of order one (at 0.02 they are all but equal, and the softmax
    is flat at any scale). Both Mamba heads get A = -1/4 and dt near 0.05:
    a state that remembers some 80 positions, as the published layer's
    slowest heads remember hundreds, so that the state carried across the
    chunks of 16 weighs in the result (the seeded draw over two heads can
    leave both forgetting within a few positions)."""
    params = unbox(model.init(
        {"params": jax.random.PRNGKey(seed)}, tokens, train=False))["params"]
    return jax.tree_util.tree_map_with_path(_widen, params)


def rel(a, b):
    num = sum(float(jnp.sum((x - y) ** 2)) for x, y in zip(
        jax.tree.leaves(a), jax.tree.leaves(b)))
    den = sum(float(jnp.sum(y ** 2)) for y in jax.tree.leaves(b))
    return (num / den) ** 0.5


def program_loss(model, batch):
    return lambda p: masked_cross_entropy(
        model.apply({"params": p}, batch[0], train=True), batch[1])


@pytest.fixture(scope="module")
def tiny():
    model = build_model("Granite4Tiny")
    config = ref_config(model.config, L)
    batch = REF.make_batch(jax.random.PRNGKey(3), 2, config)
    return model, config, batch, init(model, batch[0])


@pytest.fixture(scope="module")
def reference(tiny):
    """(loss, gradients) of the plain reference at the tiny preset."""
    _, config, batch, params = tiny
    with jax.default_matmul_precision("highest"):
        return jax.jit(jax.value_and_grad(
            lambda p: REF.loss(p, batch, config)))(params)


def test_program_matches_the_plain_reference_in_loss_and_gradients(
        tiny, reference):
    model, _, batch, params = tiny
    with jax.default_matmul_precision("highest"):
        loss_p, grad_p = jax.jit(jax.value_and_grad(
            program_loss(model, batch)))(params)
    loss_r, grad_r = reference
    assert float(loss_p) == pytest.approx(float(loss_r), rel=1e-5)
    assert rel(grad_p, grad_r) < 1e-4
    # every leaf, the small ones too (A_log, dt_bias, D, the conv's bias)
    for (path, got), want in zip(jax.tree_util.tree_leaves_with_path(grad_p),
                                 jax.tree.leaves(grad_r)):
        name = jax.tree_util.keystr(path)
        assert float(jnp.abs(want).max()) > 0, name
        assert rel([got], [want]) < 1e-3, name
    assert set(params) == {"embed", "final_norm", "layer_0", "layer_1",
                           "layer_2"}
    assert set(params["layer_0"]["mamba"]) == {
        "in_proj", "conv_weight", "conv_bias", "dt_bias", "A_log", "D",
        "norm", "out_proj"}
    assert set(params["layer_1"]) == {
        "input_layernorm", "self_attn", "post_attention_layernorm",
        "shared_mlp"}
    # [z | x B C | dt] = 128 + (128 + 2 x 16) + 2; the head is the embedding
    assert params["layer_0"]["mamba"]["in_proj"]["kernel"].shape == (64, 290)
    assert params["layer_0"]["mamba"]["conv_weight"].shape == (4, 160)


def _scan_inputs(seed, batch=2, length=L, heads=3, p=8, n=16, a_max=1.5):
    k = jax.random.split(jax.random.PRNGKey(seed), 5)
    x = jax.random.normal(k[0], (batch, length, heads, p))
    dt = jax.nn.softplus(jax.random.normal(k[1], (batch, length, heads)) - 1)
    A = -jnp.exp(jax.random.uniform(k[2], (heads,), maxval=a_max))
    B = jax.random.normal(k[3], (batch, length, 1, n))
    C = jax.random.normal(k[4], (batch, length, 1, n))
    return x, dt, A, B, C


def _recurrence(x, dt, A, B, C):
    return REF.recurrence(x, dt, A, B[:, :, 0], C[:, :, 0])


@pytest.fixture(scope="module")
def recurrence():
    """The inputs, a cotangent, and the recurrence's output and gradients."""
    args = _scan_inputs(0)
    w = jax.random.normal(jax.random.PRNGKey(9), args[0].shape)
    with jax.default_matmul_precision("highest"):
        y = _recurrence(*args)
        grads = jax.grad(lambda *a: jnp.sum(_recurrence(*a) * w),
                         argnums=range(5))(*args)
    return args, w, y, grads


@pytest.mark.parametrize("chunk", [8, 16, 32])
def test_the_chunked_scan_is_the_recurrence_at_any_chunk(recurrence, chunk):
    """Four chunks or more of a 64-long sequence, so the carried state
    crosses three boundaries or more: the output and the gradient of every
    input agree with the recurrence to rounding, whatever the chunk."""
    args, w, y_r, grads_r = recurrence
    y, _ = ssd.ssd_scan(*args, chunk)
    assert y.shape == y_r.shape and y.dtype == y_r.dtype
    assert rel([y], [y_r]) < 1e-5
    grads = jax.grad(lambda *a: jnp.sum(ssd.ssd_scan(*a, chunk)[0] * w),
                     argnums=range(5))(*args)
    for name, got, want in zip(("x", "dt", "A", "B", "C"), grads, grads_r):
        assert got.shape == want.shape, name
        assert rel([got], [want]) < 1e-5, name


def test_the_boundary_statistic_is_the_largest_state_a_chunk_starts_from():
    args = _scan_inputs(1, batch=1, length=32)
    x, dt, A, B, C = args
    states = []
    s = jnp.zeros((1, 3, 8, 16))
    for t in range(32):
        if t % 8 == 0:
            states.append(s)
        s = (jnp.exp(dt[:, t] * A)[..., None, None] * s
             + (dt[:, t, :, None, None] * x[:, t, :, :, None])
             * B[:, t, 0][:, None, None])
    _, absmax = ssd.ssd_scan(*args, 8)
    want = max(float(jnp.abs(v).max()) for v in states)
    assert float(absmax) == pytest.approx(want, rel=1e-5) and want > 0


def test_decays_past_float32s_exponent_range_stay_finite():
    """A = -16 and dt = 0.1 at every position: a chunk of 64 falls to a
    cumsum of -102.4, where exp(a_i) exp(-a_j) would be 0 x inf. Every
    decay is the exp of a non-positive difference, so the output and every
    gradient are finite and still the recurrence's."""
    x, _, _, B, C = _scan_inputs(2, batch=1, length=128, heads=2)
    dt = jnp.full(x.shape[:3], 0.1)
    A = jnp.full((2,), -16.0)
    w = jax.random.normal(jax.random.PRNGKey(4), x.shape)
    y, absmax = ssd.ssd_scan(x, dt, A, B, C, 64)
    grads = jax.grad(lambda *a: jnp.sum(ssd.ssd_scan(*a, 64)[0] * w),
                     argnums=range(5))(x, dt, A, B, C)
    assert bool(jnp.isfinite(absmax))
    for v in (y, *grads):
        assert bool(jnp.all(jnp.isfinite(v)))
    with jax.default_matmul_precision("highest"):
        assert rel([y], [_recurrence(x, dt, A, B, C)]) < 1e-5


def test_bfloat16_operands_give_the_float32_scan_to_their_rounding(
        recurrence):
    """The cell's dtypes: x, B and C in bfloat16 into the matrix unit, the
    state and the decays float32; y comes back in x's dtype."""
    args, _, y_r, _ = recurrence
    x, dt, A, B, C = args
    low = [v.astype(jnp.bfloat16) for v in (x, B, C)]
    y, absmax = ssd.ssd_scan(low[0], dt, A, low[1], low[2], 16)
    assert y.dtype == jnp.bfloat16 and absmax.dtype == jnp.float32
    assert 1e-4 < rel([y.astype(jnp.float32)], [y_r]) < 2e-2
    dx = jax.grad(lambda v: jnp.sum(ssd.ssd_scan(
        v, dt, A, low[1], low[2], 16)[0].astype(jnp.float32)))(low[0])
    assert dx.dtype == jnp.bfloat16 and bool(jnp.all(jnp.isfinite(dx)))


def test_the_scan_takes_one_group_and_whole_chunks():
    x, dt, A, B, C = _scan_inputs(3, length=32)
    with pytest.raises(ValueError, match="one group"):
        ssd.ssd_scan(x, dt, A, jnp.concatenate([B, B], 2), C, 8)
    with pytest.raises(ValueError, match="multiple of the chunk"):
        ssd.ssd_scan(x, dt, A, B, C, 12)


@pytest.mark.parametrize("fault", sorted(CONTROLS.FAULTS))
def test_a_planted_fault_is_beyond_the_cells_limits(
        tiny, reference, monkeypatch, fault):
    """Planted in the program at the tiny preset (the CPU, 64 wide: not
    the cell's sizes), each fault of the chip's control tool fails the
    comparison ``correct`` makes, by at least one of the limits the
    full-size comparison is held to."""
    model, _, batch, params = tiny
    CONTROLS.FAULTS[fault](monkeypatch.setattr)
    with jax.default_matmul_precision("highest"):
        loss_p, grad_p = jax.jit(jax.value_and_grad(
            program_loss(model, batch)))(params)
    loss_r, grad_r = reference
    norm = lambda tree: sum(                                   # noqa: E731
        float(jnp.sum(x ** 2)) for x in jax.tree.leaves(tree)) ** 0.5
    read = {
        "loss_rel": abs(float(loss_p) - float(loss_r)) / float(loss_r),
        "grad_norm_rel": abs(norm(grad_p) - norm(grad_r)) / norm(grad_r),
        "grad_rel_err": rel(grad_p, grad_r),
    }
    assert any(read[k] > REF.TOLERANCE[k] for k in read), read


def test_the_controls_put_back_what_they_planted():
    g = granite4_hybrid
    names = ("ssd_scan", "short_conv", "GatedRMSNorm", "Mamba2Mixer",
             "Granite4Block", "NoPEAttention")
    before = [getattr(g, n) for n in names]
    for fault in CONTROLS.FAULTS:
        with CONTROLS.planted(fault):
            assert [getattr(g, n) for n in names] != before, fault
        assert [getattr(g, n) for n in names] == before, fault


def test_the_controls_split_a_distance_over_every_part(tiny):
    *_, params = tiny
    other = jax.tree.map(lambda x: x * 1.5 + 0.25, params)
    split = CONTROLS.by_part(other, params)
    assert set(split) == {name for name, _ in CONTROLS.PARTS}
    whole = sum(float(d) for d, _ in split.values())
    assert whole == pytest.approx(
        rel(other, params) ** 2 * sum(float(n) for _, n in split.values()),
        rel=1e-4)


def test_the_trainer_takes_the_preset_and_its_records_carry_the_counters(
        tmp_path):
    from pytorch_distributed_nn_tpu.training.trainer import (
        TrainConfig,
        Trainer,
    )

    stream = str(tmp_path / "stream.jsonl")
    trainer = Trainer(TrainConfig(
        network="Granite4Tiny", dataset="NextTokenSynth", batch_size=4,
        num_workers=2, seq_len=L, dtype="float32", optimizer="adam",
        lr=1e-3, log_every=5, max_steps=10, eval_freq=0, seed=3,
        attn_impl="pallas", metrics_path=stream,
        train_dir=str(tmp_path / "train")))
    try:
        before = jax.device_get(trainer.state.params)
        trainer.train()
        after = jax.device_get(trainer.state.params)
    finally:
        trainer.close()
    records = [json.loads(line) for line in open(stream)]
    steps = [r for r in records if r.get("kind") == "step"]
    assert len(steps) == 10 and steps[-1]["loss"] < steps[0]["loss"]
    for r in steps:
        # two Mamba layers, each scanning a replica's 2 sequences of 4
        # chunks; the largest boundary state of either
        assert (r["ssd_calls"], r["ssd_chunks"]) == (2, 2 * 2 * 4)
        assert 0 < r["ssd_state_absmax"] < 1e3
    for leaf in (("embed", "embedding"), ("layer_0", "mamba", "A_log"),
                 ("layer_2", "mamba", "conv_weight"),
                 ("layer_1", "self_attn", "q_proj", "kernel")):
        a, b = before, after
        for key in leaf:
            a, b = a[key], b[key]
        assert np.any(a != b), leaf


def test_the_model_says_that_it_cannot_decode(tiny):
    model, _, batch, params = tiny
    with pytest.raises(NotImplementedError, match="state"):
        model.apply({"params": params}, batch[0], return_kv=True)
    assert "Granite4Tiny" not in GENERATIVE_MODELS


def test_the_cells_preset_holds_the_share_the_configuration_states():
    cfg = build_model("Granite4_H_Micro_PP4").config
    full = granite4_hybrid.Granite4HybridConfig()
    assert (cfg.hidden_size, cfg.intermediate_size, cfg.num_attention_heads,
            cfg.num_key_value_heads, cfg.head_dim, cfg.mamba_n_heads,
            cfg.mamba_d_head, cfg.mamba_d_state, cfg.mamba_d_conv,
            cfg.mamba_n_groups, cfg.mamba_chunk_size) == (
        2048, 8192, 32, 8, 64, 64, 64, 128, 4, 1, 256)
    assert (cfg.embedding_multiplier, cfg.residual_multiplier,
            cfg.logits_scaling, cfg.attention_multiplier) == (
        12.0, 0.22, 8.0, 1 / 64)
    assert cfg.layer_types == full.layer_types[:10] == (
        ("mamba",) * 5 + ("attention",) + ("mamba",) * 4)
    assert full.num_hidden_layers == 40 and full.layer_types[:10] * 4 == (
        full.layer_types)
    assert cfg.vocab_size * 8 == full.vocab_size == 100352
    assert cfg.dtype == jnp.bfloat16 and cfg.remat and not full.remat
    # 2 x hidden: mamba_expand
    assert cfg.mamba_n_heads * cfg.mamba_d_head == 2 * cfg.hidden_size
    # the shared pieces are LFM2's
    assert granite4_hybrid.short_conv is lfm2.short_conv
    assert granite4_hybrid.GatedMLP is lfm2.GatedMLP

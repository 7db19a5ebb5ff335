"""The LFM2-MoE decoder at its tiny preset, against the benchmark's plain
float32 reference (``benchmark/reference/lfm2_8b_a1b_ep4.py``, which shares
nothing with the program but the parameter tree's names): the whole model's
loss and gradients, the expert layer's share arithmetic and dropless
dispatch, the router's bias rule, the short convolution's causality, the
KV-head mapping, next-token batches, and the counters the step records carry.
"""

import dataclasses
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import manifest
from pytorch_distributed_nn_tpu.data.text import (
    NUM_SPECIAL,
    NextTokenBatches,
    next_token_labels,
)
from pytorch_distributed_nn_tpu.models import build_model, lfm2
from pytorch_distributed_nn_tpu.ops import pallas_kernels as pk
from pytorch_distributed_nn_tpu.ops.metrics import (
    IGNORE_INDEX,
    masked_cross_entropy,
)
from pytorch_distributed_nn_tpu.parallel.partitioning import unbox

REF = manifest.load_module(
    manifest.ROOT, "benchmark/reference/lfm2_8b_a1b_ep4.py")


def ref_config(cfg: lfm2.Lfm2Config, length: int) -> dict:
    """The reference's view of a program configuration."""
    return {"tokens_per_sample": length, "model": {
        "vocab_size": cfg.vocab_size,
        "num_attention_heads": cfg.num_attention_heads,
        "num_key_value_heads": cfg.num_key_value_heads,
        "norm_eps": cfg.norm_eps, "rope_theta": cfg.rope_theta,
        "num_experts_per_tok": cfg.num_experts_per_tok,
        "routed_scaling_factor": cfg.routed_scaling_factor,
        "first_expert": cfg.experts_held[0],
        "layer_types": list(cfg.layer_types),
        "num_dense_layers": cfg.num_dense_layers,
    }}


def init(model, tokens, seed=1, bias_scale=0.1):
    """Seeded weights, the expert bias drawn well away from zero."""
    params = unbox(model.init(
        {"params": jax.random.PRNGKey(seed)}, tokens, train=False))["params"]
    for name, layer in params.items():
        if "moe" in layer:
            layer["moe"]["expert_bias"] = bias_scale * jax.random.normal(
                jax.random.PRNGKey(hash(name) % 1000),
                layer["moe"]["expert_bias"].shape)
    return params


def rel(a, b):
    num = sum(float(jnp.sum((x - y) ** 2)) for x, y in zip(
        jax.tree.leaves(a), jax.tree.leaves(b)))
    den = sum(float(jnp.sum(y ** 2)) for y in jax.tree.leaves(b))
    return (num / den) ** 0.5


@pytest.fixture(scope="module")
def tiny():
    model = build_model("Lfm2Tiny")
    config = ref_config(model.config, 64)
    batch = REF.make_batch(jax.random.PRNGKey(3), 2, config)
    return model, config, batch, init(model, batch[0])


def test_program_matches_the_plain_reference_in_loss_and_gradients(tiny):
    model, config, batch, params = tiny

    def program(p):
        return masked_cross_entropy(
            model.apply({"params": p}, batch[0], train=True), batch[1])

    with jax.default_matmul_precision("highest"):
        loss_p, grad_p = jax.jit(jax.value_and_grad(program))(params)
        loss_r, grad_r = jax.jit(jax.value_and_grad(
            lambda p: REF.loss(p, batch, config)))(params)
    assert float(loss_p) == pytest.approx(float(loss_r), rel=1e-5)
    assert rel(grad_p, grad_r) < 1e-4
    # no gradient reaches the expert bias, on either side
    for side in (grad_p, grad_r):
        assert not np.any(np.asarray(side["layer_2"]["moe"]["expert_bias"]))


def _layer(cfg, x, params):
    return lfm2.SparseExperts(cfg).apply(
        {"params": params}, x, mutable=[lfm2.COUNTERS])


def _whole_layer():
    """An uncut expert layer (8 experts, top-2) with seeded weights."""
    cfg = dataclasses.replace(
        build_model("Lfm2Tiny").config, experts_held=(0, 8))
    x = jax.random.normal(jax.random.PRNGKey(5), (2, 32, cfg.hidden_size))
    params = unbox(lfm2.SparseExperts(cfg).init(
        jax.random.PRNGKey(6), x))["params"]
    params["expert_bias"] = 0.1 * jax.random.normal(
        jax.random.PRNGKey(7), (cfg.num_experts,))
    return cfg, x, params


def _share(params, first, count):
    held = slice(first, first + count)
    return {**params, "experts": {
        k: v[held] for k, v in params["experts"].items()}}


def test_the_four_shares_add_up_to_the_uncut_reference_layer():
    cfg, x, params = _whole_layer()
    m = ref_config(cfg, 32)["model"]
    with jax.default_matmul_precision("highest"):
        whole = REF._expert_ffn(params, x, {**m, "first_expert": 0})
        total = jnp.zeros_like(whole)
        pairs = 0.0
        for first in (0, 2, 4, 6):
            part = dataclasses.replace(cfg, experts_held=(first, 2))
            y, counted = _layer(part, x, _share(params, first, 2))
            # each share is the reference's own share
            np.testing.assert_allclose(
                y, REF._expert_ffn(_share(params, first, 2), x,
                                   {**m, "first_expert": first}),
                atol=2e-6)
            total += y
            pairs += float(counted[lfm2.COUNTERS]["experts"]["moe_pairs"][0])
    np.testing.assert_allclose(total, whole, atol=5e-6)
    assert float(jnp.abs(whole).max()) > 1e-3
    # every (token, expert) pair is computed on exactly one chip
    assert pairs == 2 * 32 * cfg.num_experts_per_tok


def test_a_planted_routing_onto_the_held_experts_drops_nothing():
    cfg, x, params = _whole_layer()
    cfg = dataclasses.replace(cfg, num_experts_per_tok=4,
                              experts_held=(2, 4))
    params = _share(params, 2, 4)
    # the bias sends every token's four choices to experts 2..5
    params["expert_bias"] = jnp.where(
        (jnp.arange(8) >= 2) & (jnp.arange(8) < 6), 10.0, 0.0)
    tokens = 2 * 32
    with jax.default_matmul_precision("highest"):
        y, counted = _layer(cfg, x, params)
        want = REF._expert_ffn(params, x, {
            **ref_config(cfg, 32)["model"], "first_expert": 2})
    np.testing.assert_allclose(y, want, atol=5e-6)
    counted = {k: float(v[0])
               for k, v in counted[lfm2.COUNTERS]["experts"].items()}
    assert counted["moe_pairs"] == 4 * tokens          # the dropless bound
    assert counted["moe_rows"] >= counted["moe_pairs"]
    assert counted["moe_load_max"] == tokens           # each expert: every token
    assert counted["moe_load_mean"] == tokens and counted["moe_layers"] == 1
    # and a routing that sends nothing here computes nothing, finitely
    params["expert_bias"] = jnp.where(jnp.arange(8) >= 6, 10.0, -10.0)
    y, counted = _layer(dataclasses.replace(cfg, num_experts_per_tok=2),
                        x, params)
    assert not np.any(np.asarray(y))
    assert float(counted[lfm2.COUNTERS]["experts"]["moe_pairs"][0]) == 0


def test_the_ladder_at_the_cells_shapes_ends_in_the_dropless_bound():
    # SmallThinker: 16,384 tokens, top-6 of 64, 8 held; LFM2: top-4 of 32
    assert lfm2.ladder(16384 * 6, 8, 64) == (17408, 32768, 100352)
    assert lfm2.ladder(16384 * 4, 8, 32) == (22528, 43008, 67584)
    for pairs, count, experts in ((16384 * 6, 8, 64), (16384 * 4, 8, 32),
                                  (128, 2, 8), (256, 4, 8), (7, 3, 3)):
        for tile in (8, 256):
            rungs = lfm2.ladder(pairs, count, experts, tile)
            assert 1 <= len(rungs) <= 3 and list(rungs) == sorted(set(rungs))
            assert all(r % tile == 0 for r in rungs)
            # the last rung is dispatch's R: every pair here, a tile an expert
            assert rungs[-1] == (-(-pairs // tile) + count) * tile


_LADDER_TILE = 8        # 64 tokens, top-2 of 8, experts 2-3 held: 7, 12, 18 tiles


def _dense_experts(tokens, weights, w13, w2, sel, first, gate):
    """The held experts' share by a loop over them, every token through
    every held expert."""
    f, y = w2.shape[1], 0.0
    for e in range(w13.shape[0]):
        h = tokens @ w13[e]
        out = (gate(h[:, :f]) * h[:, f:]) @ w2[e]
        y += jnp.sum(jnp.where(sel == first + e, weights, 0), -1)[:, None] * out
    return y


@pytest.mark.parametrize("held, rows", [
    ((16, 16), 56),      # even routing: the first rung, with room
    ((32, 24), 56),      # the owned tiles are the first rung's, all 7
    ((32, 25), 96),      # one tile more: the second rung
    ((48, 48), 96),      # the second rung's 12 tiles, all owned
    ((49, 48), 144),     # one more: the last rung
    ((64, 64), 144),     # every pair onto the held experts: the bound
])
def test_every_rung_of_the_ladder_computes_the_dense_loops_share(
        monkeypatch, held, rows):
    """Routings planted so that the owned tiles land on each rung and on
    each side of a rung's edge: the rung is the smallest that covers them
    (``moe_rows_bound``), output and gradients are the dense loop's on
    every rung, and a higher rung than needed gives the same numbers."""
    monkeypatch.setattr(lfm2, "GMM_TILE_M", _LADDER_TILE)
    cfg = dataclasses.replace(
        build_model("Lfm2Tiny").config, experts_held=(2, 2))
    T, d, f = 64, cfg.hidden_size, cfg.moe_intermediate_size
    assert lfm2.ladder(2 * T, 2, 8, _LADDER_TILE) == (56, 96, 144)
    keys = jax.random.split(jax.random.PRNGKey(11), 5)
    tokens = jax.random.normal(keys[0], (T, d))
    weights = jax.random.uniform(keys[1], (T, 2), minval=0.2, maxval=0.8)
    w13 = jax.random.normal(keys[2], (2, d, 2 * f)) / d ** 0.5
    w2 = jax.random.normal(keys[3], (2, f, d)) / f ** 0.5
    probe = jax.random.normal(keys[4], (T, d))
    # token t's first choice is expert 2 while t < held[0] (else expert 0,
    # held elsewhere), its second expert 3 while t < held[1] (else 1)
    t = jnp.arange(T)
    sel = jnp.stack([jnp.where(t < held[0], 2, 0),
                     jnp.where(t < held[1], 3, 1)], axis=-1).astype(jnp.int32)

    def layer(tokens, weights, w13, w2):
        y, counted = lfm2.Experts(cfg).apply(
            {"params": {"w13": w13, "w2": w2}}, tokens, sel, weights,
            mutable=[lfm2.COUNTERS])
        return jnp.sum(y * probe), (y, counted[lfm2.COUNTERS])

    def dense(tokens, weights, w13, w2):
        y = _dense_experts(tokens, weights, w13, w2, sel, 2, jax.nn.silu)
        return jnp.sum(y * probe), y

    inputs = (tokens, weights, w13, w2)
    with jax.default_matmul_precision("highest"):
        (_, (y, counted)), grads = jax.value_and_grad(
            layer, (0, 1, 2, 3), has_aux=True)(*inputs)
        (_, want), want_grads = jax.value_and_grad(
            dense, (0, 1, 2, 3), has_aux=True)(*inputs)
        np.testing.assert_allclose(y, want, atol=1e-5)
        for got, ref in zip(grads, want_grads):
            np.testing.assert_allclose(got, ref, atol=2e-5)
        assert float(counted["moe_pairs"][0]) == sum(held)
        assert float(counted["moe_rows"][0]) == _LADDER_TILE * sum(
            max(1, -(-n // _LADDER_TILE)) for n in held)
        assert float(counted["moe_rows_bound"][0]) == rows
        # the same routing in the buffers of the last rung alone
        real_ladder = lfm2.ladder
        monkeypatch.setattr(lfm2, "ladder", lambda *a: real_ladder(*a)[-1:])
        (_, (y_top, counted)), grads_top = jax.value_and_grad(
            layer, (0, 1, 2, 3), has_aux=True)(*inputs)
    assert float(counted["moe_rows_bound"][0]) == 144
    np.testing.assert_allclose(y_top, y, atol=1e-6)
    for got, ref in zip(grads_top, grads):
        np.testing.assert_allclose(got, ref, atol=1e-6)


@pytest.mark.parametrize("every_block", [False, True])
def test_the_backward_pass_keeps_the_routing_the_forward_pass_decided(
        tiny, monkeypatch, every_block):
    """The router's scores are kept for the backward pass, not recomputed:
    a recomputation may round the layer's input elsewhere than the forward
    pass did and decide close calls otherwise (it did on the chip). With
    every block recomputed a policy keeps them (the callback below would
    run again all the same, so only the gradient is compared there)."""
    model, _, batch, params = tiny
    model = model.clone(config=dataclasses.replace(
        model.config, remat=every_block))
    scored = []
    real = jax.nn.sigmoid
    monkeypatch.setattr(jax.nn, "sigmoid", lambda x: (
        jax.debug.callback(lambda s: scored.append(s.shape), x), real(x))[1])

    def loss(p):
        return masked_cross_entropy(
            model.apply({"params": p}, batch[0], train=True), batch[1])

    grads = jax.block_until_ready(jax.jit(jax.grad(loss))(params))
    jax.effects_barrier()
    if not every_block:
        # the router's sigmoid ran once an expert layer: in the forward pass
        assert len(scored) == 4, scored
    plain = jax.jit(jax.grad(lambda p: masked_cross_entropy(
        model.clone(config=dataclasses.replace(
            model.config, remat=False)).apply(
                {"params": p}, batch[0], train=True), batch[1])))(params)
    assert rel(grads, plain) < 1e-6


def test_the_bias_changes_which_experts_are_selected_never_their_weights():
    scores = jax.nn.sigmoid(jax.random.normal(jax.random.PRNGKey(0), (64, 8)))
    bias = 0.5 * jax.random.normal(jax.random.PRNGKey(1), (8,))
    plain, _ = lfm2.route(scores, 0.0, 2)
    sel, weights = lfm2.route(scores, bias, 2)
    assert np.any(np.asarray(sel) != np.asarray(plain))
    # the selection is the top-2 of score + bias ...
    np.testing.assert_array_equal(
        np.sort(sel, axis=-1),
        np.sort(jax.lax.top_k(scores + bias, 2)[1], axis=-1))
    # ... and the weights are the selected experts' own scores, normalised
    picked = jnp.take_along_axis(scores, sel, axis=-1)
    np.testing.assert_allclose(
        weights, picked / (picked.sum(-1, keepdims=True) + 1e-6), rtol=1e-6)
    _, scaled = lfm2.route(scores, bias, 2, scaling=2.5)
    np.testing.assert_allclose(scaled, 2.5 * weights, rtol=1e-6)


def test_the_short_convolution_at_t_ignores_inputs_after_t():
    z = jax.random.normal(jax.random.PRNGKey(0), (2, 16, 8))
    taps = jax.random.normal(jax.random.PRNGKey(1), (3, 8))
    out = lfm2.short_conv(z, taps)
    later = lfm2.short_conv(z.at[:, 9:].add(1.0), taps)
    np.testing.assert_array_equal(out[:, :9], later[:, :9])
    assert np.all(np.asarray(out[:, 9:] != later[:, 9:]))
    # c_t = k0 z_{t-2} + k1 z_{t-1} + k2 z_t, zeros before the start
    np.testing.assert_allclose(out[:, 0], taps[2] * z[:, 0], rtol=1e-6)
    np.testing.assert_allclose(
        out[:, 5], taps[0] * z[:, 3] + taps[1] * z[:, 4] + taps[2] * z[:, 5],
        rtol=1e-5, atol=1e-6)


def test_the_kv_head_mapping_equals_repeated_kv(tiny):
    model, config, _, params = tiny
    cfg = model.config
    x = jax.random.normal(jax.random.PRNGKey(2), (2, 64, cfg.hidden_size))
    p = params["layer_1"]["attn"]
    with jax.default_matmul_precision("highest"):
        got = lfm2.GroupedQueryAttention(cfg).apply({"params": p}, x)
        # the reference maps heads with a grouped einsum: KV head j under
        # query heads 2j, 2j + 1
        want = REF._attention(p, x, config["model"])
    np.testing.assert_allclose(got, want, atol=2e-6)
    k = jnp.arange(2 * 3 * 2 * 4.0).reshape(2, 3, 2, 4)
    rep = lfm2.repeat_kv(k, 2)
    assert rep.shape == (2, 3, 4, 4)
    for h in range(4):
        np.testing.assert_array_equal(rep[:, :, h], k[:, :, h // 2])


def test_next_token_labels_are_the_tokens_shifted_with_ignore_last():
    batches = NextTokenBatches(vocab_size=64, seq_len=16, batch_size=3, seed=4)
    tokens, labels = next(batches)
    assert tokens.shape == labels.shape == (3, 16)
    assert tokens.dtype == labels.dtype == np.int32
    np.testing.assert_array_equal(labels[:, :-1], tokens[:, 1:])
    assert np.all(labels[:, -1] == IGNORE_INDEX)
    assert tokens.min() >= NUM_SPECIAL and tokens.max() < 64
    np.testing.assert_array_equal(next_token_labels(tokens), labels)
    # the walk follows the corpus's chain: each token is a successor of the last
    succ = batches.corpus.successors + NUM_SPECIAL
    assert all(tokens[b, t + 1] in succ[tokens[b, t] - NUM_SPECIAL]
               for b in range(3) for t in range(15))
    # the stream is a function of (seed, index), seekable, and the eval set fixed
    again = NextTokenBatches(vocab_size=64, seq_len=16, batch_size=3, seed=4)
    np.testing.assert_array_equal(next(again)[0], tokens)
    again.skip(2)
    next(batches), next(batches)
    np.testing.assert_array_equal(next(again)[0], next(batches)[0])
    first, second = batches.eval_set(2), again.eval_set(2)
    assert len(first) == 2
    np.testing.assert_array_equal(first[1][0], second[1][0])


@pytest.mark.parametrize("sizes", [[5, 0, 17, 8], [0, 0, 0, 0], [8, 8, 8, 16]])
def test_grouped_matmul_against_a_dense_loop(sizes):
    tile, rows, groups = 8, 72, len(sizes)
    sizes = jnp.array(sizes, jnp.int32)
    starts, meta = pk.group_tiles(sizes, rows, tile)
    assert np.all(np.asarray(starts) % tile == 0)
    r = jnp.arange(rows)
    member = [(r >= starts[g]) & (r < starts[g] + sizes[g])
              for g in range(groups)]
    real = sum(member).astype(bool)
    x = jnp.where(real[:, None],
                  jax.random.normal(jax.random.PRNGKey(0), (rows, 128)), 0)
    w = jax.random.normal(jax.random.PRNGKey(1), (groups, 128, 256))

    def kernel(x, w):
        y = pk.grouped_matmul(x, w, meta, tile)
        return jnp.sum(jnp.where(real[:, None], y, 0) ** 2)

    def dense(x, w):
        y = sum(jnp.where(m[:, None], x @ w[g], 0)
                for g, m in enumerate(member))
        return jnp.sum(y ** 2)

    with jax.default_matmul_precision("highest"):
        got, (dx, dw) = jax.value_and_grad(kernel, (0, 1))(x, w)
        want, (rx, rw) = jax.value_and_grad(dense, (0, 1))(x, w)
    assert float(got) == pytest.approx(float(want), rel=1e-5)
    # rows that hold nothing are the caller's to mask, in dx as in y
    np.testing.assert_allclose(jnp.where(real[:, None], dx, 0), rx, atol=1e-3)
    np.testing.assert_allclose(dw, rw, atol=1e-3)   # zeros for an empty group


@pytest.mark.parametrize("weighted", [True, False])
@pytest.mark.parametrize("sizes", [[5, 0, 17, 8], [0, 0, 0, 0], [24, 24, 24, 24]])
def test_sum_rows_against_a_scatter_add(sizes, weighted):
    """The row sum of combine and of dispatch's transpose: rows laid out
    by group, a group's rows in token order, a token at most once a group;
    a row that holds nothing is matched by no token, whatever it holds."""
    tile, tokens, d, groups = 8, 24, 128, len(sizes)
    rows = sum(sizes) + groups * tile
    rows += -rows % tile
    sizes = jnp.array(sizes, jnp.int32)
    starts, meta = pk.group_tiles(sizes, rows, tile)
    r = jnp.arange(rows)
    owner = jnp.repeat(meta[:-1], tile)
    offset = r - starts[owner]
    real = (offset < sizes[owner]) & (r // tile < meta[-1])
    # group g's rows hold g's first sizes[g] tokens of a seeded order, sorted
    order = jnp.stack([jnp.pad(jnp.sort(jax.random.permutation(
        jax.random.PRNGKey(g), tokens)[:int(n)]).astype(jnp.int32),
        (0, tokens - int(n))) for g, n in enumerate(sizes)])
    token = jnp.where(real, order[owner, jnp.clip(offset, 0, tokens - 1)], 0)
    keys = jax.random.split(jax.random.PRNGKey(9), 3)
    written = (r // tile < meta[-1])[:, None]
    values = jnp.where(written, jax.random.normal(keys[0], (rows, d)), jnp.nan)
    values = jnp.where(real[:, None] | ~written, values, 7.0)  # padding rows
    weight = jnp.where(real, jax.random.uniform(keys[1], (rows,)), 0.0)
    probe = jax.random.normal(keys[2], (tokens, d))

    def kernel(values, weight):
        out = pk.sum_rows(values, weight if weighted else None, token, real,
                          meta, groups, tokens, tile)
        return jnp.sum(out * probe), out

    def scatter(values, weight):
        scaled = values * weight[:, None] if weighted else values
        out = jnp.zeros((tokens, d)).at[token].add(
            jnp.where(real[:, None], scaled, 0))
        return jnp.sum(out * probe), out

    (_, got), grads = jax.value_and_grad(kernel, (0, 1), has_aux=True)(
        values, weight)
    (_, want), want_grads = jax.value_and_grad(scatter, (0, 1), has_aux=True)(
        values, weight)
    np.testing.assert_allclose(got, want, atol=1e-5)
    np.testing.assert_allclose(grads[0], jnp.where(
        real[:, None], want_grads[0], 0), atol=1e-5)
    if weighted:
        np.testing.assert_allclose(
            grads[1], jnp.where(real, want_grads[1], 0), atol=1e-5)


def test_the_trainer_takes_the_preset_and_its_records_carry_the_counters(
        tmp_path):
    from pytorch_distributed_nn_tpu.observability import reader
    from pytorch_distributed_nn_tpu.training.trainer import (
        TrainConfig,
        Trainer,
    )

    stream = str(tmp_path / "stream.jsonl")
    trainer = Trainer(TrainConfig(
        network="Lfm2Tiny", dataset="NextTokenSynth", batch_size=4,
        num_workers=2, seq_len=64, dtype="float32", optimizer="adam",
        lr=1e-3, log_every=5, max_steps=20, eval_freq=0, seed=3,
        metrics_path=stream, train_dir=str(tmp_path / "train")))
    try:
        before = jax.device_get(trainer.state.params)
        trainer.train()
        after = jax.device_get(trainer.state.params)
    finally:
        trainer.close()
    steps = [json.loads(line) for line in open(stream)]
    steps = [r for r in steps if r.get("kind") == "step"]
    assert len(steps) == 20 and steps[-1]["loss"] < steps[0]["loss"]
    tokens = 2 * 64                       # a replica's tokens a step
    # at these sizes the ladder is the dropless bound alone: a tile for the
    # 256 pairs and one an expert, four layers
    assert lfm2.ladder(2 * tokens, 4, 8) == (5 * pk.GMM_TILE_M,)
    for r in steps:
        assert r["moe_layers"] == 4
        assert 0 < r["moe_pairs"] <= r["moe_rows"]
        assert r["moe_rows"] <= r["moe_rows_bound"] == 4 * 5 * pk.GMM_TILE_M
        assert r["moe_pairs"] <= 4 * 2 * tokens       # top-2, four layers
        assert r["moe_load_max"] >= r["moe_load_mean"] > 0
    # Adam moves the weights and leaves the bias buffer where it was seeded
    np.testing.assert_array_equal(
        before["layer_1"]["moe"]["expert_bias"],
        after["layer_1"]["moe"]["expert_bias"])
    assert np.any(before["layer_1"]["moe"]["router"]
                  != after["layer_1"]["moe"]["router"])
    summary = reader.summarize_run(reader.read_stream(stream))
    moe = summary["experts"]
    assert moe["expert_layers"] == 4 and moe["max_over_mean_load"] >= 1
    assert 0 < moe["pairs_per_token"] <= 2 and 0 <= moe["pad_rows_pct"] < 100
    text = reader.render_summary(summary, None)
    assert "experts:" in text and "a token" in text


def test_the_trainer_refuses_a_text_model_without_a_text_dataset():
    from pytorch_distributed_nn_tpu.training.trainer import (
        TrainConfig,
        Trainer,
    )

    with pytest.raises(ValueError, match="NextTokenSynth"):
        Trainer(TrainConfig(network="Lfm2Tiny", dataset="Cifar10"))
    with pytest.raises(ValueError, match="requires a text model"):
        Trainer(TrainConfig(network="LeNet", dataset="NextTokenSynth"))


def test_the_model_says_that_it_cannot_decode(tiny):
    model, _, batch, params = tiny
    with pytest.raises(NotImplementedError, match="short convolution"):
        model.apply({"params": params}, batch[0], return_kv=True)
    assert "Lfm2Tiny" not in __import__(
        "pytorch_distributed_nn_tpu.models", fromlist=["x"]).GENERATIVE_MODELS


def test_the_cells_preset_holds_the_share_the_configuration_states():
    cfg = build_model("Lfm2_8B_A1B_EP4").config
    assert (cfg.num_experts, cfg.experts_held, cfg.num_experts_per_tok) == (
        32, (0, 8), 4)
    assert (cfg.hidden_size, cfg.intermediate_size, cfg.moe_intermediate_size,
            cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head_dim) == (
        2048, 7168, 1792, 32, 8, 64)
    assert cfg.layer_types == ("conv", "full_attention", "conv", "conv", "conv")
    assert cfg.num_dense_layers == 1 and cfg.vocab_size == 16384
    assert cfg.dtype == jnp.bfloat16 and not cfg.remat
    # the published model: 24 layers, attention at 2, 6, 10, 14, 18, 21
    full = lfm2.Lfm2Config()
    assert full.num_hidden_layers == 24 and full.num_dense_layers == 2
    assert [i for i, k in enumerate(full.layer_types)
            if k == "full_attention"] == [2, 6, 10, 14, 18, 21]


def _tile_kv(x, groups):
    """A wrong head mapping: query head h reads KV head h % kv_heads."""
    return jnp.tile(x, (1, 1, groups, 1))


def _late_conv(real_conv):
    """A convolution that sees one position ahead."""
    return lambda z, taps: jnp.roll(real_conv(z, taps), -1, axis=1)


def _wrong_expert(group_sizes, rows, tile_m=pk.GMM_TILE_M):
    """Every tile handed to the next expert's weights."""
    starts, meta = pk.group_tiles(group_sizes, rows, tile_m)
    owner = (meta[:-1] + 1) % group_sizes.shape[0]
    return starts, jnp.concatenate([owner, meta[-1:]])


def _dropping(real_dispatch):
    """A dispatch with a capacity: each token's second choice is dropped
    (its row holds nothing, which is what the row-space combine reads)."""
    def dispatch(sel, first, count, *a):
        pair, real, dest, local, meta, counts = real_dispatch(
            sel, first, count, *a)
        kept = pair % sel.shape[1] == 0
        return pair, real & kept, dest, local, meta, counts
    return dispatch


def _bias_in_weights(real_route):
    def route(scores, bias, k, scaling=1.0):
        return real_route(scores + bias, 0.0, k, scaling)
    return route


def _distance_with(tiny, monkeypatch, name, patch):
    """The benchmark's ``grad_rel_err`` with ``lfm2.<name>`` replaced."""
    model, config, batch, params = tiny
    monkeypatch.setattr(lfm2, name, patch(getattr(lfm2, name)))

    def program(p):
        return masked_cross_entropy(
            model.apply({"params": p}, batch[0], train=True), batch[1])

    with jax.default_matmul_precision("highest"):
        grad_p = jax.jit(jax.grad(program))(params)
        grad_r = jax.jit(jax.grad(
            lambda p: REF.loss(p, batch, config)))(params)
    return rel(grad_p, grad_r)


@pytest.mark.parametrize("name, patch", [
    ("repeat_kv", lambda real: _tile_kv),          # reads 0.62
    ("group_tiles", lambda real: _wrong_expert),   # 3.6
    ("dispatch", _dropping),                       # 0.14
])
def test_a_planted_fault_is_beyond_the_cells_limit(
        tiny, monkeypatch, name, patch):
    """Faults the cell's TOLERANCE catches: planted in the program at the
    tiny preset (the CPU, 64 wide: not the cell's sizes), each moves the
    gradient further from the plain reference's than the limit the
    full-size comparison is held to."""
    assert _distance_with(tiny, monkeypatch, name, patch) > (
        REF.TOLERANCE["grad_rel_err"])


@pytest.mark.parametrize("name, patch", [
    ("short_conv", _late_conv),                    # reads 0.011
    ("route", _bias_in_weights),                   # 0.026
])
def test_a_planted_fault_the_cells_limit_does_not_hold(
        tiny, monkeypatch, name, patch):
    """Faults the cell's TOLERANCE does NOT catch: a convolution that sees
    one position ahead and a bias that leaks into the weights move the
    gradient by 5,000 x the sound program's 1e-6 here, but stay under the
    full-size limit (the routing decisions that flip between bfloat16 and
    float32 cost more than they do: PERF.md section 7). The unit tests above
    hold them (causality position by position, the bias against the
    weights), the benchmark's ``correct`` would not."""
    distance = _distance_with(tiny, monkeypatch, name, patch)
    assert 0.005 < distance < REF.TOLERANCE["grad_rel_err"]

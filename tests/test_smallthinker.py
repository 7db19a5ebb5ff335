"""The SmallThinker decoder at its tiny preset, against the benchmark's
plain float32 reference (``benchmark/reference/smallthinker_21b_a3b_ep8.py``,
which shares nothing with the program but the parameter tree's names): the
whole model's loss and gradients, the expert layer's share arithmetic, the
window's reach position by position, the flash kernels with a window in
both families against dense masked attention, the sweep's block counts by
hand, planted faults against the cell's limits, the routing kept for the
backward pass, and the counters the step records carry.
"""

import dataclasses
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from flax import linen as nn

from benchmark import manifest
from pytorch_distributed_nn_tpu.models import (
    GENERATIVE_MODELS,
    build_model,
    lfm2,
    smallthinker,
)
from pytorch_distributed_nn_tpu.models.transformer import full_attention
from pytorch_distributed_nn_tpu.ops import pallas_kernels as pk
from pytorch_distributed_nn_tpu.ops.metrics import masked_cross_entropy
from pytorch_distributed_nn_tpu.parallel.partitioning import unbox

REF = manifest.load_module(
    manifest.ROOT, "benchmark/reference/smallthinker_21b_a3b_ep8.py")
L, W = 64, 16


def ref_config(cfg: smallthinker.SmallThinkerConfig, length: int) -> dict:
    """The reference's view of a program configuration."""
    return {"tokens_per_sample": length, "model": {
        "vocab_size": cfg.vocab_size,
        "num_attention_heads": cfg.num_attention_heads,
        "num_key_value_heads": cfg.num_key_value_heads,
        "rms_norm_eps": cfg.rms_norm_eps, "rope_theta": cfg.rope_theta,
        "rope_layout": list(cfg.rope_layout),
        "sliding_window_layout": list(cfg.sliding_window_layout),
        "sliding_window_size": cfg.sliding_window_size,
        "moe_num_active_primary_experts": cfg.moe_num_active_primary_experts,
        "first_expert": cfg.experts_held[0],
    }}


def init(model, tokens, seed=1):
    """Seeded weights, every matrix drawn five times wider than the
    model's 0.02: at 64 wide that makes attention, the experts and the
    router's logits of order one beside the residual stream, so that each
    weighs in the loss (at 0.02 the tiny model is its embedding and head)."""
    params = unbox(model.init(
        {"params": jax.random.PRNGKey(seed)}, tokens, train=False))["params"]
    return jax.tree.map(lambda a: 5.0 * a if a.ndim >= 2 else a, params)


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
    model = build_model("SmallThinkerTiny")
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
    # every leaf, the small ones too (a norm's scale, a router's gate)
    leaves_p = jax.tree_util.tree_leaves_with_path(grad_p)
    for (path, got), want in zip(leaves_p, jax.tree.leaves(grad_r)):
        assert float(jnp.abs(want).max()) > 0, path
        assert rel([got], [want]) < 1e-3, jax.tree_util.keystr(path)
    # the head is a matrix of its own: both it and the embedding learn
    assert params["lm_head"]["kernel"].shape == (64, 512)
    assert set(params["layer_0"]) == {
        "router", "input_norm", "attn", "post_attention_norm", "experts"}
    assert "swa" in params["layer_1"] and "attn" not in params["layer_1"]


def _expert_layer(cfg, u, sel, weights, params):
    y, counted = lfm2.Experts(cfg, "relu").apply(
        {"params": params}, u.reshape(-1, u.shape[-1]), sel, weights,
        mutable=[lfm2.COUNTERS])
    return y.reshape(u.shape), counted[lfm2.COUNTERS]


def test_the_four_shares_add_up_to_the_uncut_reference_layer():
    """No shared expert, so nothing is counted once: the four chips'
    parts are the whole layer."""
    cfg = dataclasses.replace(
        build_model("SmallThinkerTiny").config, experts_held=(0, 8))
    m = ref_config(cfg, 32)["model"]
    r = jax.random.normal(jax.random.PRNGKey(5), (2, 32, cfg.hidden_size))
    u = jax.random.normal(jax.random.PRNGKey(6), (2, 32, cfg.hidden_size))
    gate = jax.random.normal(
        jax.random.PRNGKey(7), (cfg.hidden_size, 8)) / 8
    params = unbox(lfm2.Experts(cfg, "relu").init(
        jax.random.PRNGKey(8), u.reshape(-1, 64),
        jnp.zeros((64, 2), jnp.int32), jnp.zeros((64, 2))))["params"]
    with jax.default_matmul_precision("highest"):
        sel_r, weights_r = REF.routing({"gate": gate}, r, m)
        sel, weights = smallthinker.route(
            (r @ gate).reshape(-1, 8), cfg.moe_num_active_primary_experts)
        np.testing.assert_array_equal(
            np.sort(sel, -1), np.sort(sel_r.reshape(-1, 2), -1))
        np.testing.assert_allclose(weights.sum(-1), 1.0, rtol=1e-6)
        whole = REF._expert_ffn(params, u, sel_r, weights_r,
                                {**m, "first_expert": 0})
        total, pairs = jnp.zeros_like(whole), 0.0
        for first in (0, 2, 4, 6):
            part = dataclasses.replace(cfg, experts_held=(first, 2))
            held = {k: v[first:first + 2] for k, v in params.items()}
            y, counted = _expert_layer(part, u, sel, weights, held)
            # each share is the reference's own share
            np.testing.assert_allclose(
                y, REF._expert_ffn(held, u, sel_r, weights_r,
                                   {**m, "first_expert": first}), atol=2e-6)
            total += y
            pairs += float(counted["moe_pairs"][0])
    np.testing.assert_allclose(total, whole, atol=5e-6)
    assert float(jnp.abs(whole).max()) > 1e-3
    # every (token, expert) pair is computed on exactly one chip
    assert pairs == 2 * 32 * cfg.moe_num_active_primary_experts


def test_the_router_weighs_the_selected_by_a_softmax_over_them():
    logits = jax.random.normal(jax.random.PRNGKey(0), (64, 8))
    sel, weights = smallthinker.route(logits, 3)
    np.testing.assert_array_equal(
        np.sort(sel, -1), np.sort(jax.lax.top_k(logits, 3)[1], -1))
    # softmax over all eight, renormalised over the three: the same function
    over_all = jnp.take_along_axis(jax.nn.softmax(logits, -1), sel, axis=-1)
    np.testing.assert_allclose(
        weights, over_all / over_all.sum(-1, keepdims=True), rtol=1e-5)


@pytest.mark.parametrize("attend", ["full", "pallas"])
def test_a_window_layer_at_t_ignores_inputs_before_its_window_and_after_t(
        tiny, attend):
    model, _, _, params = tiny
    cfg = model.config
    attn_fn = pk.pallas_attention if attend == "pallas" else None
    layer = smallthinker.Attention(cfg, W, True, attn_fn)
    p = params["layer_1"]["swa"]
    x = jax.random.normal(jax.random.PRNGKey(2), (1, L, cfg.hidden_size))
    t = 40
    with jax.default_matmul_precision("highest"):
        out = layer.apply({"params": p}, x)
        # keys t-W+1 .. t are seen: 25 .. 40
        before = layer.apply({"params": p}, x.at[:, :t - W + 1].add(1.0))
        after = layer.apply({"params": p}, x.at[:, t + 1:].add(1.0))
        edge = layer.apply({"params": p}, x.at[:, t - W + 1].add(1.0))
        np.testing.assert_allclose(out[:, t], before[:, t], atol=1e-6)
        np.testing.assert_allclose(out[:, :t + 1], after[:, :t + 1], atol=1e-6)
        assert float(jnp.abs(out[:, t] - edge[:, t]).max()) > 1e-4
        # a window that covers the sequence is causal attention
        whole = smallthinker.Attention(cfg, L, True, attn_fn).apply(
            {"params": p}, x)
        causal = smallthinker.Attention(cfg, None, True, attn_fn).apply(
            {"params": p}, x)
    np.testing.assert_allclose(whole, causal, atol=1e-6)
    assert float(jnp.abs(whole - out).max()) > 1e-4


def test_a_global_layer_has_no_positions(tiny):
    """NoPE: with the causal mask taken away by reading the last position
    only, a global layer's output there does not change when the earlier
    positions are permuted; a window layer's (rotary) does."""
    model, _, _, params = tiny
    cfg = model.config
    x = jax.random.normal(jax.random.PRNGKey(4), (1, L, cfg.hidden_size))
    perm = jnp.concatenate([jnp.arange(L - 1)[::-1], jnp.array([L - 1])])
    with jax.default_matmul_precision("highest"):
        for positions, same in ((False, True), (True, False)):
            layer = smallthinker.Attention(cfg, None, positions)
            p = params["layer_0"]["attn"]
            a = layer.apply({"params": p}, x)[:, -1]
            b = layer.apply({"params": p}, x[:, perm])[:, -1]
            assert bool(jnp.abs(a - b).max() < 1e-5) == same


def _dense(q, k, v, window):
    length, dim = q.shape[1], q.shape[-1]
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(dim)
    i, j = jnp.arange(length)[:, None], jnp.arange(length)[None, :]
    seen = (j <= i) & (i - j < window)
    probs = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v)


@pytest.mark.parametrize("family", ["resident", "streamed"])
@pytest.mark.parametrize("window", [64, 48, 20, 300],
                         ids=["blocks", "not_blocks", "under_a_block", "ge_L"])
def test_flash_attention_with_a_window_against_dense_masked_attention(
        family, window, monkeypatch):
    """Forward and all three gradients, in interpret mode, blocks of 32 at
    L = 256: a window of two blocks, of one and a half, of less than one,
    and one that covers the sequence (no window)."""
    monkeypatch.setattr(pk, "_PREFERRED_BLOCK", 32)
    if family == "streamed":
        monkeypatch.setattr(pk, "_RESIDENT_MAX_L", 64)
    pk._FLASH_CACHE.clear()
    try:
        assert pk._resident(256, 32) == (family == "resident")
        q, k, v, g = (jax.random.normal(key, (2, 256, 2, 32))
                      for key in jax.random.split(jax.random.PRNGKey(0), 4))
        with jax.default_matmul_precision("highest"):
            got = jax.value_and_grad(lambda q, k, v: (pk.pallas_attention(
                q, k, v, None, causal=True, window=window) * g).sum(),
                (0, 1, 2))(q, k, v)
            want = jax.value_and_grad(lambda q, k, v: (
                _dense(q, k, v, window) * g).sum(), (0, 1, 2))(q, k, v)
            xla = full_attention(q, k, v, None, causal=True, window=window)
        assert float(got[0]) == pytest.approx(float(want[0]), rel=1e-5)
        for a, b in zip(got[1], want[1]):
            np.testing.assert_allclose(a, b, atol=2e-5)
        np.testing.assert_allclose(xla, _dense(q, k, v, window), atol=2e-5)
        assert ((True, 32, None) in pk._FLASH_CACHE) == (window >= 256)
    finally:
        pk._FLASH_CACHE.clear()


def test_a_window_needs_causal_attention():
    q = jnp.zeros((1, 64, 1, 16))
    with pytest.raises(ValueError, match="causal"):
        pk.pallas_attention(q, q, q, None, causal=False, window=8)
    with pytest.raises(ValueError, match="causal"):
        full_attention(q, q, q, None, causal=False, window=8)
    with pytest.raises(ValueError, match="own key"):
        pk.pallas_attention(q, q, q, None, causal=True, window=0)


def test_the_sweeps_block_counts_with_a_window_by_hand():
    """The cell's geometry: L 16,384 in 32 blocks of 512, window 4096. A
    query block needs its own key block and the eight before it (its first
    row, j * 512, sees back to j * 512 - 4095: block j - 8); a key block
    its own query block and the eight after it."""
    n, b, w = 32, 512, 4096
    assert pk._causal_sweep(True, 0, b, b, n, True, w) == (0, 1)
    assert pk._causal_sweep(True, 5, b, b, n, True, w) == (0, 6)
    assert pk._causal_sweep(True, 8, b, b, n, True, w) == (0, 9)
    assert pk._causal_sweep(True, 9, b, b, n, True, w) == (1, 10)
    assert pk._causal_sweep(True, 31, b, b, n, True, w) == (23, 32)
    assert pk._causal_sweep(True, 0, b, b, n, False, w) == (0, 9)
    assert pk._causal_sweep(True, 23, b, b, n, False, w) == (23, 32)
    assert pk._causal_sweep(True, 30, b, b, n, False, w) == (30, 32)
    # the streamed grids are as long as the longest sweep
    assert pk._streamed_sweep(True, n, b, b, n, True, w)[0] == 9
    assert pk._streamed_sweep(True, n, b, b, n, False, w)[0] == 9
    assert pk._streamed_sweep(True, n, b, b, n, True)[0] == n
    assert pk._streamed_sweep(False, n, b, b, n, True)[0] == n
    for own_is_query in (True, False):
        blocks = sum(hi - lo for lo, hi in (
            pk._causal_sweep(True, j, b, b, n, own_is_query, w)
            for j in range(n)))
        assert blocks == 32 * 9 - 36 == 252       # against 528 with no window
    assert sum(j + 1 for j in range(n)) == 528
    # block 9's first row, 4608, sees back to key 513 under 4096 and to
    # key 511, in block 0, under a window two keys longer
    assert pk._causal_sweep(True, 9, b, b, n, True, w + 1) == (1, 10)
    assert pk._causal_sweep(True, 9, b, b, n, True, w + 2) == (0, 10)
    # against brute force over positions, unequal blocks, windows that are
    # no multiple of either
    for bq, bk, length, window in ((32, 64, 256, 48), (64, 32, 256, 100),
                                   (32, 32, 256, 1), (32, 32, 256, 33)):
        nq, nk = length // bq, length // bk
        for j in range(nq):
            rows = range(j * bq, j * bq + bq)
            need = [t for t in range(nk) if any(
                0 <= i - c < window
                for i in rows for c in range(t * bk, t * bk + bk))]
            assert pk._causal_sweep(True, j, bq, bk, nk, True, window) == (
                need[0], need[-1] + 1)
        for j in range(nk):
            cols = range(j * bk, j * bk + bk)
            need = [t for t in range(nq) if any(
                0 <= i - c < window
                for c in cols for i in range(t * bq, t * bq + bq))]
            assert pk._causal_sweep(True, j, bk, bq, nq, False, window) == (
                need[0], need[-1] + 1)
    # the index maps hold the sweep's last block once it is over
    _, at = pk._streamed_sweep(True, n, b, b, n, True, w)
    assert [int(at(9, t)) for t in range(9)] == list(range(1, 10))
    assert [int(at(2, t)) for t in range(9)] == [0, 1, 2] + [2] * 6
    assert pk._streamed_sweep(False, n, b, b, n, True)[1](3, 7) == 7


def test_the_resident_family_is_chosen_by_length_times_head_width():
    assert pk._resident(8192, 64) and not pk._resident(8193, 64)
    assert pk._resident(4096, 128) and not pk._resident(8192, 128)
    assert not pk._resident(16384, 128)             # the cell: streamed
    # under 64 wide the lanes are padded: by L alone, as before
    assert pk._resident(8192, 16) and not pk._resident(16384, 16)
    assert pk._resident(512, 64)                    # BERT, and LFM2 at 8192


def _tied(model, batch):
    def loss(p):
        head = {"kernel": p["embed"]["embedding"].T}
        return masked_cross_entropy(model.apply(
            {"params": {**p, "lm_head": head}}, batch[0], train=True),
            batch[1])
    return loss


def _with_config(**changes):
    return lambda model, batch: program_loss(model.clone(
        config=dataclasses.replace(model.config, **changes)), batch)


class _NormedRouter(smallthinker.Router):
    """A router that reads the attention block's normed input."""

    def __call__(self, tokens):
        return super().__call__(
            lfm2.rms_norm(tokens, 1.0, self.config.rms_norm_eps))


def _over_all(logits, k):
    """Softmax over all the experts, not renormalised over the selected."""
    sel, _ = lfm2.top_k(logits, logits, k)
    return sel, jnp.take_along_axis(jax.nn.softmax(logits, -1), sel, axis=-1)


FAULTS = {
    "rotary_on_the_global_layer":
        (_with_config(rope_layout=(1, 1, 1, 1)), None),
    "the_window_dropped": (_with_config(sliding_window_size=L), None),
    "the_router_fed_the_normed_input":
        (program_loss, lambda mp: mp.setattr(
            smallthinker, "Router", _NormedRouter)),
    "silu_for_relu":
        (program_loss, lambda mp: mp.setitem(lfm2.GATES, "relu", nn.silu)),
    "softmax_over_all_without_renormalising":
        (program_loss, lambda mp: mp.setattr(
            smallthinker, "route", _over_all)),
    "the_head_tied_to_the_embedding": (_tied, None),
}


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_a_planted_fault_is_beyond_the_cells_limits(
        tiny, reference, monkeypatch, fault):
    """Planted in the program at the tiny preset (the CPU, 64 wide: not
    the cell's sizes), each fault fails the comparison ``correct`` makes,
    by at least one of the limits the full-size comparison is held to."""
    model, _, batch, params = tiny
    make_loss, plant = FAULTS[fault]
    if plant:
        plant(monkeypatch)
    with jax.default_matmul_precision("highest"):
        loss_p, grad_p = jax.jit(jax.value_and_grad(
            make_loss(model, batch)))(params)
    loss_r, grad_r = reference
    norm = lambda tree: sum(                                   # noqa: E731
        float(jnp.sum(x ** 2)) for x in jax.tree.leaves(tree)) ** 0.5
    read = {
        "loss_rel": abs(float(loss_p) - float(loss_r)) / float(loss_r),
        "grad_norm_rel": abs(norm(grad_p) - norm(grad_r)) / norm(grad_r),
        "grad_rel_err": rel(grad_p, grad_r),
    }
    assert any(read[k] > REF.TOLERANCE[k] for k in read), read
    assert read["grad_rel_err"] > REF.TOLERANCE["grad_rel_err"], read


@pytest.mark.parametrize("every_block", [False, True])
def test_the_backward_pass_keeps_the_routing_the_forward_pass_decided(
        tiny, monkeypatch, every_block):
    """The router's logits are kept for the backward pass and the routing
    is not decided again from recomputed ones (recomputed, close calls came
    out otherwise on the chip: PERF.md section 6, PR 34). With every block
    recomputed a policy keeps the logits (the callback below would run
    again all the same, so only the gradient is compared there)."""
    model, _, batch, params = tiny
    model = model.clone(config=dataclasses.replace(
        model.config, remat=every_block))
    routed = []
    real = smallthinker.route

    def listening(logits, k):
        jax.debug.callback(lambda s: routed.append(s.shape), logits)
        return real(logits, k)

    monkeypatch.setattr(smallthinker, "route", listening)
    grads = jax.block_until_ready(
        jax.jit(jax.grad(program_loss(model, batch)))(params))
    jax.effects_barrier()
    if not every_block:
        # the routing was decided once a layer: in the forward pass
        assert len(routed) == 4, routed
    monkeypatch.setattr(smallthinker, "route", real)
    plain = jax.jit(jax.grad(program_loss(model.clone(
        config=dataclasses.replace(model.config, remat=False)), batch)))(
            params)
    assert rel(grads, plain) < 1e-6


def test_the_trainer_takes_the_preset_and_its_records_carry_the_counters(
        tmp_path):
    from pytorch_distributed_nn_tpu.observability import reader
    from pytorch_distributed_nn_tpu.training.trainer import (
        TrainConfig,
        Trainer,
    )

    stream = str(tmp_path / "stream.jsonl")
    trainer = Trainer(TrainConfig(
        network="SmallThinkerTiny", dataset="NextTokenSynth", batch_size=4,
        num_workers=2, seq_len=L, dtype="float32", optimizer="adam",
        lr=1e-3, log_every=5, max_steps=20, eval_freq=0, seed=3,
        attn_impl="pallas", metrics_path=stream,
        train_dir=str(tmp_path / "train")))
    try:
        before = jax.device_get(trainer.state.params)
        trainer.train()
        after = jax.device_get(trainer.state.params)
    finally:
        trainer.close()
    steps = [json.loads(line) for line in open(stream)]
    steps = [r for r in steps if r.get("kind") == "step"]
    assert len(steps) == 20 and steps[-1]["loss"] < steps[0]["loss"]
    tokens = 2 * L                        # a replica's tokens a step
    for r in steps:
        assert r["moe_layers"] == 4
        assert 0 < r["moe_pairs"] <= r["moe_rows"]
        assert r["moe_pairs"] <= 4 * 2 * tokens       # top-2, four layers
        assert r["moe_load_max"] >= r["moe_load_mean"] > 0
    # embedding and head are two matrices, and both moved
    for leaf in (("embed", "embedding"), ("lm_head", "kernel"),
                 ("layer_0", "router", "gate"),
                 ("layer_1", "swa", "query", "kernel")):
        a, b = before, after
        for key in leaf:
            a, b = a[key], b[key]
        assert np.any(a != b), leaf
    summary = reader.summarize_run(reader.read_stream(stream))
    moe = summary["experts"]
    assert moe["expert_layers"] == 4 and moe["max_over_mean_load"] >= 1
    assert 0 < moe["pairs_per_token"] <= 2 and 0 <= moe["pad_rows_pct"] < 100


def test_the_model_says_that_it_cannot_decode(tiny):
    model, _, batch, params = tiny
    with pytest.raises(NotImplementedError, match="layer kind"):
        model.apply({"params": params}, batch[0], return_kv=True)
    assert "SmallThinkerTiny" not in GENERATIVE_MODELS


def test_the_cells_preset_holds_the_share_the_configuration_states():
    cfg = build_model("SmallThinker_21B_A3B_EP8").config
    assert (cfg.moe_num_primary_experts, cfg.experts_held,
            cfg.moe_num_active_primary_experts) == (64, (0, 8), 6)
    assert (cfg.hidden_size, cfg.moe_ffn_hidden_size, cfg.num_attention_heads,
            cfg.num_key_value_heads, cfg.head_dim, cfg.sliding_window_size) == (
        2560, 768, 28, 4, 128, 4096)
    assert cfg.sliding_window_layout == cfg.rope_layout == (0, 1, 1, 1)
    assert cfg.num_hidden_layers == 4 and cfg.vocab_size == 18992
    assert cfg.dtype == jnp.bfloat16 and not cfg.remat
    assert cfg.moe_intermediate_size == cfg.moe_ffn_hidden_size
    # the published model: 52 layers, a global layer every fourth
    full = smallthinker.SmallThinkerConfig()
    assert full.num_hidden_layers == 52 and full.vocab_size == 151936
    assert [i for i, w in enumerate(full.sliding_window_layout)
            if not w] == list(range(0, 52, 4))
    assert full.rope_layout == full.sliding_window_layout

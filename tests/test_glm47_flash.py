"""The GLM-4.7-Flash decoder at its tiny preset, against the benchmark's
plain float32 reference (``benchmark/reference/glm47_flash_ep8.py``, which
shares nothing with the program but the parameter tree's names): the whole
model's loss and gradients, latent attention against attention written
head by head, the expert layer's share arithmetic with the shared expert,
the router's bias and scale, the prediction module's labels and what each
position may see, the flash kernels at head width 256, planted faults
against the cell's limits, and the trainer's records.
"""

import dataclasses
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import manifest
from pytorch_distributed_nn_tpu.data.text import (
    NextTokenBatches,
    next_token_labels,
)
from pytorch_distributed_nn_tpu.models import (
    GENERATIVE_MODELS,
    build_model,
    glm47_flash,
    lfm2,
)
from pytorch_distributed_nn_tpu.models.transformer import full_attention
from pytorch_distributed_nn_tpu.ops import pallas_kernels as pk
from pytorch_distributed_nn_tpu.ops.metrics import (
    IGNORE_INDEX,
    depth_loss_names,
    masked_cross_entropy,
)
from pytorch_distributed_nn_tpu.parallel.partitioning import unbox

REF = manifest.load_module(
    manifest.ROOT, "benchmark/reference/glm47_flash_ep8.py")
CONTROLS = manifest.load_module(
    manifest.ROOT, "benchmark/tools/glm47_controls.py")
L = 64


def ref_config(cfg: glm47_flash.Glm47FlashConfig, length: int) -> dict:
    """The reference's view of a program configuration."""
    keys = ("vocab_size", "num_attention_heads", "qk_nope_head_dim",
            "qk_rope_head_dim", "v_head_dim", "kv_lora_rank", "rms_norm_eps",
            "rope_theta", "num_experts_per_tok", "routed_scaling_factor",
            "num_hidden_layers", "first_k_dense_replace",
            "num_nextn_predict_layers")
    return {"tokens_per_sample": length, "model": {
        **{k: getattr(cfg, k) for k in keys},
        "first_expert": cfg.experts_held[0]}}


def init(model, tokens, seed=1):
    """Seeded weights, every matrix drawn five times wider than the
    model's 0.02 (at 64 wide that makes attention, the experts and the
    router's logits of order one beside the residual stream, so that each
    weighs in the loss) and the expert bias thirty times (of the order of
    the scores it is added to, so that where it enters shows)."""
    params = unbox(model.init(
        {"params": jax.random.PRNGKey(seed)}, tokens, train=False))["params"]
    return jax.tree_util.tree_map_with_path(
        lambda path, a: 30.0 * a if "expert_bias" in jax.tree_util.keystr(
            path) else (5.0 * a if a.ndim >= 2 else a), params)


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
    model = build_model("GLM47FlashTiny")
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
        logits = model.apply({"params": params}, batch[0])
        loss_p, grad_p = jax.jit(jax.value_and_grad(
            program_loss(model, batch)))(params)
    assert logits.shape == (2, L, 2, 512) and batch[1].shape == (2, L, 2)
    loss_r, grad_r = reference
    assert float(loss_p) == pytest.approx(float(loss_r), rel=1e-5)
    assert rel(grad_p, grad_r) < 1e-4
    # every leaf, the small ones too (a latent's norm, the router); the
    # expert bias is a buffer: no gradient on either side
    leaves_p = jax.tree_util.tree_leaves_with_path(grad_p)
    for (path, got), want in zip(leaves_p, jax.tree.leaves(grad_r)):
        name = jax.tree_util.keystr(path)
        if "expert_bias" in name:
            assert not np.any(got) and not np.any(want), name
            continue
        assert float(jnp.abs(want).max()) > 0, name
        assert rel([got], [want]) < 1e-3, name
    assert set(params) == {"embed", "lm_head", "final_norm", "layer_0",
                           "layer_1", "layer_2", "mtp_0"}
    assert set(params["layer_0"]) == {
        "input_layernorm", "mla", "post_attention_layernorm", "mlp"}
    assert set(params["layer_1"]) == {
        "input_layernorm", "mla", "post_attention_layernorm", "moe",
        "shared_expert"}
    assert set(params["mtp_0"]) == {"enorm", "hnorm", "eh_proj", "layer",
                                    "norm"}
    assert params["mtp_0"]["eh_proj"]["kernel"].shape == (128, 64)
    # the head is a matrix of its own, shared by both depths
    assert params["lm_head"]["kernel"].shape == (64, 512)


def _naive_mla(p, x, cfg):
    """Latent attention head by head, from the up-projected weights: a
    query head is [latent W_uq[:, h, :192] | rotary(latent W_uq[:, h,
    192:])], a key head [c_kv W_ukv[:, h, :192] | rotary(k_pe)] with the
    one k_pe of the token, a value head c_kv W_ukv[:, h, 192:]."""
    nope, rope = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim
    eps, theta = cfg.rms_norm_eps, cfg.rope_theta
    c_q = lfm2.rms_norm(x @ p["q_a_proj"]["kernel"], p["q_a_norm"]["scale"],
                        eps)
    a = x @ p["kv_a_proj"]["kernel"]
    c_kv = lfm2.rms_norm(a[..., :cfg.kv_lora_rank], p["kv_a_norm"]["scale"],
                         eps)
    k_pe = lfm2.rotary(a[..., None, cfg.kv_lora_rank:], theta)[:, :, 0]
    length = x.shape[1]
    causal = jnp.tril(jnp.ones((length, length), bool))
    heads = []
    for h in range(cfg.num_attention_heads):
        wq = p["q_b_proj"]["kernel"][:, h]
        wkv = p["kv_b_proj"]["kernel"][:, h]
        q = jnp.concatenate([c_q @ wq[:, :nope], lfm2.rotary(
            (c_q @ wq[:, nope:])[:, :, None], theta)[:, :, 0]], -1)
        k = jnp.concatenate([c_kv @ wkv[:, :nope], k_pe], -1)
        v = c_kv @ wkv[:, nope:]
        s = jnp.einsum("bqd,bkd->bqk", q, k) / np.sqrt(nope + rope)
        probs = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), -1)
        heads.append(jnp.einsum("bqk,bkd->bqd", probs, v))
    out = jnp.stack(heads, axis=2)
    return jnp.einsum("blhk,hkd->bld", out, p["o_proj"]["kernel"])


@pytest.mark.parametrize("attend", ["full", "pallas"])
def test_latent_attention_against_attention_written_head_by_head(
        tiny, attend):
    model, _, _, params = tiny
    cfg = model.config
    p = params["layer_1"]["mla"]
    x = jax.random.normal(jax.random.PRNGKey(2), (2, L, cfg.hidden_size))
    layer = glm47_flash.LatentAttention(
        cfg, pk.pallas_attention if attend == "pallas" else None)
    with jax.default_matmul_precision("highest"):
        got = layer.apply({"params": p}, x)
        want = _naive_mla(p, x, cfg)
        ref = REF.mla(p, x, ref_config(cfg, L)["model"])
    np.testing.assert_allclose(got, want, atol=2e-5)
    np.testing.assert_allclose(ref, want, atol=2e-5)
    assert float(jnp.abs(want).max()) > 1e-2
    # the rotary key is one a token: moving the query rows of one head
    # changes that head alone, and the key's rotary rows move every head
    assert p["kv_a_proj"]["kernel"].shape == (
        cfg.hidden_size, cfg.kv_lora_rank + cfg.qk_rope_head_dim)


def test_the_eight_shares_and_the_shared_expert_add_up_to_the_uncut_layer(
        tiny):
    """Eight chips of one expert each: the parts their routed experts give,
    with the shared expert counted once, are the reference's whole layer."""
    model, _, _, params = tiny
    cfg = dataclasses.replace(model.config, experts_held=(0, 8))
    m = {**ref_config(cfg, 32)["model"], "first_expert": 0}
    u = jax.random.normal(jax.random.PRNGKey(6), (2, 32, cfg.hidden_size))
    whole = unbox(lfm2.SparseExperts(cfg).init(
        jax.random.PRNGKey(8), u))["params"]
    whole = {**jax.tree.map(lambda a: 5.0 * a, whole),
             "expert_bias": params["layer_1"]["moe"]["expert_bias"]}
    shared = params["layer_1"]["shared_expert"]
    mlp = lfm2.GatedMLP(cfg.shared_expert)
    with jax.default_matmul_precision("highest"):
        want = REF.moe(whole, shared, u, m)
        total = mlp.apply({"params": shared}, u)
        pairs = 0.0
        for first in range(8):
            part = dataclasses.replace(cfg, experts_held=(first, 1))
            held = {**whole, "experts": {
                k: v[first:first + 1] for k, v in whole["experts"].items()}}
            y, counted = lfm2.SparseExperts(part).apply(
                {"params": held}, u, mutable=[lfm2.COUNTERS])
            # each share is the reference's own share
            np.testing.assert_allclose(y, REF.routed(
                held["experts"], u, *REF.routing(whole, u, m),
                {**m, "first_expert": first}), atol=5e-6)
            total = total + y
            pairs += float(counted[lfm2.COUNTERS]["experts"]["moe_pairs"][0])
    np.testing.assert_allclose(total, want, atol=2e-5)
    assert float(jnp.abs(want - mlp.apply({"params": shared}, u)).max()) > 1e-2
    assert pairs == 2 * 32 * cfg.num_experts_per_tok   # each pair, one chip


def test_the_router_bias_selects_and_does_not_weigh_and_the_scale_is_1_8(
        tiny, monkeypatch):
    """What the model's expert layers route with, heard from inside the
    forward pass: the top-k of score + bias, weighted by the scores alone
    normalised over the selected, times 1.8."""
    model, _, batch, params = tiny
    assert model.config.routed_scaling_factor == 1.8
    assert build_model("GLM47_Flash_EP8").config.routed_scaling_factor == 1.8
    heard = []
    real = lfm2.route

    def listening(scores, bias, k, scaling=1.0):
        sel, weights = real(scores, bias, k, scaling)
        heard.append((scores, bias, sel, weights, scaling))
        return sel, weights

    monkeypatch.setattr(lfm2, "route", listening)
    model.apply({"params": params}, batch[0])
    assert len(heard) == 3                       # two layers and the MTP's
    moved = 0
    for scores, bias, sel, weights, scaling in heard:
        assert scaling == 1.8
        np.testing.assert_array_equal(
            np.sort(sel, -1),
            np.sort(jax.lax.top_k(scores + bias, 2)[1], -1))
        picked = jnp.take_along_axis(scores, sel, -1)
        np.testing.assert_allclose(
            weights, 1.8 * picked / picked.sum(-1, keepdims=True), rtol=1e-5)
        np.testing.assert_allclose(weights.sum(-1), 1.8, rtol=1e-5)
        # the bias moves selections: it is large enough here to matter
        moved += int(np.sum(np.sort(sel, -1) != np.sort(
            jax.lax.top_k(scores, 2)[1], -1)))
    assert moved > 0


def _loads(sel, experts):
    return np.bincount(np.asarray(sel).reshape(-1), minlength=experts)


def test_the_balancing_rule_settles_each_expert_at_its_share():
    """noaux_tc's rule on scores that share a strong offset an expert (as
    a sequence's router inputs do at initialisation): the top-2 of 8 goes
    from two experts taking most pairs to every expert within a pair or
    two of its share, and the rule moves the bias only."""
    keys = jax.random.split(jax.random.PRNGKey(4), 3)
    offset = 0.3 * jax.random.normal(keys[0], (8,))
    scores = jax.nn.sigmoid(
        offset + 0.5 * jax.random.normal(keys[1], (512, 8)))
    bias = 0.01 * jax.random.normal(keys[2], (8,))
    share = 512 * 2 / 8
    before = _loads(lfm2.top_k(scores + bias, scores, 2)[0], 8)
    assert before.max() > 1.5 * share and before.min() < 0.6 * share
    settled = glm47_flash.balance_bias(scores, bias, 2)
    after = _loads(lfm2.top_k(scores + settled, scores, 2)[0], 8)
    assert after.sum() == 1024
    assert np.abs(after - share).max() <= 2, after
    # the bias it settled at is what evens the offset out
    assert np.corrcoef(np.asarray(settled), np.asarray(offset))[0, 1] < -0.9


def test_routing_is_balanced_layer_by_layer_on_the_drawn_sequences(tiny):
    """``balance_routing`` changes the expert biases and nothing else, and
    over the sequences it drew every expert layer, the prediction
    module's included, gives each of the 8 experts its share of the top-2,
    within a few pairs; starting from a bias that sends most pairs to two
    experts."""
    model, _, _, params = tiny
    paths = [("layer_1",), ("layer_2",), ("mtp_0", "layer")]
    skewed = params
    for path in paths:
        skewed = glm47_flash._with(
            skewed, path + ("moe", "expert_bias"),
            jnp.array([1.0, 1.0, 0, 0, 0, 0, 0, 0]))
    rng, length, n = jax.random.PRNGKey(9), 16, glm47_flash.BALANCE_SEQUENCES
    balanced = model.balance_routing(skewed, rng, length)
    for path, leaf in jax.tree_util.tree_leaves_with_path(balanced):
        if "expert_bias" not in jax.tree_util.keystr(path):
            old = skewed
            for key in path:
                old = old[key.key]
            assert leaf is old, path
    tokens = jax.random.randint(
        rng, (n, 1, length), 0, model.config.vocab_size).reshape(n, length)
    heard = []

    def listening(scores, bias, k, scaling=1.0):
        heard.append(np.asarray(lfm2.top_k(scores + bias, scores, k)[0]))
        return real(scores, bias, k, scaling)

    real = lfm2.route
    lfm2.route = listening
    try:
        for p in (skewed, balanced):
            model.apply({"params": p}, tokens)
    finally:
        lfm2.route = real
    share = n * length * 2 / 8
    for i, label in enumerate(("skewed", "balanced")):
        per_layer = [_loads(heard[i * 3 + j], 8) for j in range(3)]
        worst = max(np.abs(load - share).max() for load in per_layer)
        if label == "skewed":
            assert worst > share / 2, per_layer
        else:
            assert worst <= 3, per_layer


def test_every_block_recomputed_gives_the_same_gradient(tiny):
    """``remat`` (every block, the prediction module's included, recomputed
    in the backward pass, its routing kept) is the same function."""
    model, _, batch, params = tiny
    grads = [jax.jit(jax.grad(program_loss(model.clone(
        config=dataclasses.replace(model.config, remat=remat)), batch)))(
            params) for remat in (False, True)]
    assert rel(grads[1], grads[0]) < 1e-6


def test_the_labels_carry_a_depth_axis_with_the_right_shifts():
    tokens = np.arange(10, 16, dtype=np.int32)[None]
    one = next_token_labels(tokens)
    two = next_token_labels(tokens, 2)
    np.testing.assert_array_equal(one, [[11, 12, 13, 14, 15, IGNORE_INDEX]])
    assert two.shape == (1, 6, 2)
    np.testing.assert_array_equal(two[..., 0], one)
    np.testing.assert_array_equal(
        two[..., 1], [[12, 13, 14, 15, IGNORE_INDEX, IGNORE_INDEX]])
    # the loader and the reference's batches say the same
    x, y = next(NextTokenBatches(vocab_size=64, seq_len=16, batch_size=2,
                                 seed=1, depth=2))
    np.testing.assert_array_equal(y, next_token_labels(x, 2))
    tok, lab = REF.make_batch(jax.random.PRNGKey(0), 2, ref_config(
        build_model("GLM47FlashTiny").config, 16))
    np.testing.assert_array_equal(lab, next_token_labels(np.asarray(tok), 2))
    assert depth_loss_names(2) == ("loss_main", "loss_mtp")
    with pytest.raises(ValueError, match="prediction module"):
        depth_loss_names(3)


def test_no_position_sees_the_token_it_predicts_or_any_later_one(tiny):
    """Depth j at position i predicts token i + 1 + j: its logits do not
    move when that token or any later one changes, and do move with the
    token just before it (the prediction module's own input at j = 1)."""
    model, _, batch, params = tiny
    tokens = batch[0][:1]
    t = 40
    with jax.default_matmul_precision("highest"):
        apply = jax.jit(lambda tokens: model.apply({"params": params}, tokens))
        base = apply(tokens)
        for j in (0, 1):
            target = t + 1 + j
            later = apply(tokens.at[:, target:].add(1) % 512)
            np.testing.assert_allclose(base[:, :t + 1, j],
                                       later[:, :t + 1, j], atol=1e-5)
            before = apply(tokens.at[:, target - 1].add(1) % 512)
            assert float(jnp.abs(before[:, t, j] - base[:, t, j]).max()) > 1e-4


def _planted(fault, monkeypatch):
    CONTROLS.FAULTS[fault](monkeypatch.setattr)


@pytest.mark.parametrize("fault", sorted(CONTROLS.FAULTS))
def test_a_planted_fault_is_beyond_the_cells_limits(
        tiny, reference, monkeypatch, fault):
    """Planted in the program at the tiny preset (the CPU, 64 wide: not
    the cell's sizes), each fault of the chip's control tool fails the
    comparison ``correct`` makes, by at least one of the limits the
    full-size comparison is held to."""
    model, _, batch, params = tiny
    _planted(fault, monkeypatch)
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
    before = (glm47_flash.tokens_ahead, glm47_flash.LatentAttention,
              glm47_flash.GatedMLP, lfm2.route)
    for fault in CONTROLS.FAULTS:
        with CONTROLS.planted(fault):
            assert (glm47_flash.tokens_ahead, glm47_flash.LatentAttention,
                    glm47_flash.GatedMLP, lfm2.route) != before, fault
        assert (glm47_flash.tokens_ahead, glm47_flash.LatentAttention,
                glm47_flash.GatedMLP, lfm2.route) == before, fault


def test_the_controls_split_a_distance_over_every_part(tiny):
    """Every parameter of the model falls in one of the control tool's
    parts, and the parts' distances add up to the whole."""
    *_, params = tiny
    other = jax.tree.map(lambda x: x * 1.5 + 0.25, params)
    split = CONTROLS.by_part(other, params)
    assert set(split) == {name for name, _ in CONTROLS.PARTS}
    whole = rel(other, params) * sum(
        float(jnp.sum(x ** 2)) for x in jax.tree.leaves(params)) ** 0.5
    np.testing.assert_allclose(
        sum(float(d) for d, _ in split.values()) ** 0.5, whole, rtol=1e-5)


@pytest.mark.parametrize("family", ["resident", "streamed"])
def test_flash_attention_at_head_width_256_against_full_attention(
        family, monkeypatch):
    """The cell's head width, causal, forward and all three gradients in
    interpret mode at L = 256 in blocks of 64: the resident family, and
    the streamed one the cell runs (4096 x 256 is past the resident
    limit)."""
    monkeypatch.setattr(pk, "_PREFERRED_BLOCK", 64)
    if family == "streamed":
        monkeypatch.setattr(pk, "_RESIDENT_MAX_L", 64)
    pk._FLASH_CACHE.clear()
    try:
        assert pk._resident(256, 256) == (family == "resident")
        assert not pk._resident(4096, 256)
        q, k, v, g = (jax.random.normal(key, (1, 256, 2, 256))
                      for key in jax.random.split(jax.random.PRNGKey(0), 4))
        with jax.default_matmul_precision("highest"):
            got = jax.vjp(lambda q, k, v: pk.pallas_attention(
                q, k, v, None, causal=True), q, k, v)
            want = jax.vjp(lambda q, k, v: full_attention(
                q, k, v, None, causal=True), q, k, v)
            np.testing.assert_allclose(got[0], want[0], atol=2e-5)
            for a, b in zip(got[1](g), want[1](g)):
                np.testing.assert_allclose(a, b, atol=5e-5)
    finally:
        pk._FLASH_CACHE.clear()


def test_the_trainer_takes_the_preset_and_its_records_carry_both_losses(
        tmp_path):
    from pytorch_distributed_nn_tpu.observability import obs_cli, reader
    from pytorch_distributed_nn_tpu.training.trainer import (
        TrainConfig,
        Trainer,
    )

    stream = str(tmp_path / "stream.jsonl")
    trainer = Trainer(TrainConfig(
        network="GLM47FlashTiny", dataset="NextTokenSynth", batch_size=4,
        num_workers=2, seq_len=L, dtype="float32", optimizer="adam",
        lr=1e-3, log_every=5, max_steps=20, eval_freq=0, seed=3,
        attn_impl="pallas", metrics_path=stream,
        train_dir=str(tmp_path / "train")))
    try:
        assert trainer.label_depth == 2
        before = jax.device_get(trainer.state.params)
        trainer.train()
        after = jax.device_get(trainer.state.params)
    finally:
        trainer.close()
    # the trainer starts from the weights it seeds with the expert biases
    # balanced, and no step moves a bias (a buffer)
    seeded = unbox(trainer.model.init(
        {"params": jax.random.PRNGKey(3), "dropout": jax.random.PRNGKey(3)},
        jnp.zeros((1, L), jnp.int32), train=False))["params"]
    want = trainer.model.balance_routing(
        seeded, jax.random.fold_in(jax.random.PRNGKey(3), 1), L)
    for path in (("layer_1",), ("layer_2",), ("mtp_0", "layer")):
        bias = [glm47_flash._at(tree, path)["moe"]["expert_bias"]
                for tree in (seeded, want, before, after)]
        assert np.abs(bias[1] - bias[0]).max() > 1e-2, path
        np.testing.assert_allclose(bias[2], bias[1], atol=1e-6)
        np.testing.assert_array_equal(bias[3], bias[2])
    records = [json.loads(line) for line in open(stream)]
    steps = [r for r in records if r.get("kind") == "step"]
    assert len(steps) == 20 and steps[-1]["loss"] < steps[0]["loss"]
    tokens = 2 * L                        # a replica's tokens a step
    for r in steps:
        # one mean over both depths: between the two, nearer the main one
        # (it has one target more a sequence)
        lo, hi = sorted((r["loss_main"], r["loss_mtp"]))
        assert lo - 1e-5 <= r["loss"] <= hi + 1e-5
        assert r["moe_layers"] == 3                  # two layers and MTP's
        assert 0 < r["moe_pairs"] <= r["moe_rows"]
        assert r["moe_pairs"] <= 3 * 2 * tokens
    assert steps[-1]["loss_mtp"] < steps[0]["loss_mtp"]
    line = obs_cli._fmt_record(steps[-1])
    assert "loss_main=" in line and "loss_mtp=" in line
    for leaf in (("embed", "embedding"), ("lm_head", "kernel"),
                 ("mtp_0", "eh_proj", "kernel"),
                 ("layer_1", "shared_expert", "w1", "kernel"),
                 ("layer_2", "mla", "kv_b_proj", "kernel")):
        a, b = before, after
        for key in leaf:
            a, b = a[key], b[key]
        assert np.any(a != b), leaf
    summary = reader.summarize_run(reader.read_stream(stream))
    assert summary["experts"]["expert_layers"] == 3


def test_the_trainer_refuses_a_depth_its_data_cannot_give(tmp_path):
    from pytorch_distributed_nn_tpu.training.trainer import (
        TrainConfig,
        Trainer,
    )

    with pytest.raises(ValueError, match="NextTokenSynth"):
        Trainer(TrainConfig(
            network="GLM47FlashTiny", dataset="MLMSynth", batch_size=2,
            num_workers=1, seq_len=L, dtype="float32", max_steps=1,
            train_dir=str(tmp_path / "train")))


def test_the_model_says_that_it_cannot_decode(tiny):
    model, _, batch, params = tiny
    with pytest.raises(NotImplementedError, match="latent"):
        model.apply({"params": params}, batch[0], return_kv=True)
    assert "GLM47FlashTiny" not in GENERATIVE_MODELS


def test_the_cells_preset_holds_the_share_the_configuration_states():
    cfg = build_model("GLM47_Flash_EP8").config
    assert (cfg.n_routed_experts, cfg.experts_held, cfg.num_experts_per_tok,
            cfg.n_shared_experts) == (64, (0, 8), 4, 1)
    assert (cfg.hidden_size, cfg.q_lora_rank, cfg.kv_lora_rank,
            cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim,
            cfg.num_attention_heads, cfg.intermediate_size,
            cfg.moe_intermediate_size) == (
        2048, 768, 512, 192, 64, 256, 20, 10240, 1536)
    assert (cfg.num_hidden_layers, cfg.first_k_dense_replace,
            cfg.num_nextn_predict_layers, cfg.vocab_size) == (5, 1, 1, 19360)
    assert cfg.vocab_size * 8 == glm47_flash.Glm47FlashConfig().vocab_size
    assert cfg.dtype == jnp.bfloat16 and not cfg.remat
    assert cfg.shared_expert.intermediate_size == 1536
    assert cfg.num_experts == 64 and cfg.label_depth == 2
    # the published model: 47 layers, one of them dense
    full = glm47_flash.Glm47FlashConfig()
    assert (full.num_hidden_layers, full.first_k_dense_replace,
            full.experts_held) == (47, 1, (0, 64))

"""The FLOPs and bytes functions against counts made by hand, and the
ResNet one against what the program's own cost model says of its step."""

import pytest

from benchmark import manifest


def module(rel):
    return manifest.load_module(manifest.ROOT, rel)


def test_resnet18_by_hand():
    cell = manifest.resolve("resnet18_b4096")
    f = cell.module("flops")
    layers = {name: (need, dense) for name, need, dense, _ in
              f.conv_layers(cell.config)}
    # stem: 3x3 over 32x32, 3 -> 64; 94 of the 96 row taps are in bounds
    assert layers["stem"] == (94 * 94 * 3 * 64, 96 * 96 * 3 * 64)
    # a stage-4 conv works on a 4x4 map: 10 of 12 taps a dimension
    assert layers["stage4_block1.conv2"] == (10 * 10 * 512 * 512,
                                             12 * 12 * 512 * 512)
    # the strided 3x3 pads one side only: 8 outputs x 3 taps - 1
    assert layers["stage3_block0.conv1"][0] == 23 * 23 * 128 * 256
    assert layers["stage3_block0.shortcut"] == (8 * 8 * 128 * 256,) * 2
    assert len(layers) == 1 + 16 + 3 + 1
    # the figure papers quote: 0.5554 GMAC forward, x 3 x 2
    dense = sum(d for _, d in layers.values())
    assert dense == 555_422_720
    assert f.dense_flops_per_sample(cell.config) == 6 * dense
    # needed: every layer forward + two gradients, the stem only one
    need = sum(n for n, _ in layers.values())
    assert f.flops_per_sample(cell.config) == 6 * need - 2 * layers["stem"][0]
    assert f.flops_per_sample(cell.config) == pytest.approx(2.888e9, rel=1e-3)


def test_bert_base_by_hand():
    cell = manifest.resolve("bert_base_b32_L512")
    f = cell.module("flops")
    d, ff, v, layers, length = 768, 3072, 30522, 12, 512
    params = layers * (4 * d * d + 2 * d * ff) + d * d + d * v
    assert f.matmul_params(cell.config) == params == 108_965_376
    per_token = 6 * params + layers * 3 * 4 * length * d
    assert f.flops_per_token(cell.config) == per_token
    assert per_token == pytest.approx(0.7104e9, rel=1e-4)
    assert f.flops_per_sample(cell.config) == per_token * length


def test_bert_config_holds_the_published_widths_the_program_runs():
    from pytorch_distributed_nn_tpu.models.transformer import TransformerConfig

    m = manifest.resolve("bert_base_b32_L512").config["model"]
    published = TransformerConfig()
    assert (m["hidden_size"], m["num_hidden_layers"], m["intermediate_size"],
            m["num_attention_heads"], m["vocab_size"],
            m["max_position_embeddings"]) == (
        published.d_model, published.num_layers, published.d_ff,
        published.num_heads, published.vocab_size, published.max_len,
    ) == (768, 12, 3072, 12, 30522, 512)
    assert m["head_dim"] * m["num_attention_heads"] == m["hidden_size"]


def test_kernel_costs_by_hand():
    k = module("benchmark/flops/kernels.py")
    flash = k.flash_attention(batch=32, heads=12, length=512, head_dim=64)
    product = 2 * 384 * 512 * 512 * 64
    tensor, row = 384 * 512 * 64 * 2, 384 * 512 * 4
    assert flash["fwd"] == {"flops": 2 * product, "bytes": 4 * tensor + row}
    assert flash["dq"]["flops"] == 3 * product
    assert flash["dkv"] == {"flops": 4 * product,
                            "bytes": 6 * tensor + 2 * row}
    ln = k.fused_layer_norm(rows=16384, width=768, in_itemsize=2, out_itemsize=4)
    n = 16384 * 768
    assert ln["fwd"]["bytes"] == n * 2 + n * 4 + 2 * 16384 * 4
    assert ln["bwd"]["bytes"] == n * 2 + n * 4 + n * 2 + 2 * 16384 * 4 + 2 * 768 * 4
    peak = manifest.peak("TPU v5 lite")
    seconds, bound = k.min_seconds(flash["fwd"], peak)
    assert bound == "compute" and seconds == pytest.approx(2 * product / 197e12)
    seconds, bound = k.min_seconds(ln["fwd"], peak)
    assert bound == "memory" and seconds == pytest.approx(ln["fwd"]["bytes"] / 819e9)


def test_resnet_flops_within_5_percent_of_the_programs_step_cost():
    """The program prices its own step from the lowered HLO, pinned to
    XLA's cost analysis (``Trainer._static_step_cost``); no Mosaic call
    hides work from it on ResNet, so it is a cross-check of the count
    made from shapes. Lowering only: nothing compiles."""
    import jax

    from pytorch_distributed_nn_tpu.analysis import costmodel
    from pytorch_distributed_nn_tpu.models import build_model
    from pytorch_distributed_nn_tpu.optim import build_optimizer
    from pytorch_distributed_nn_tpu.parallel import make_grad_sync, make_mesh
    from pytorch_distributed_nn_tpu.training.train_step import dp_audit_bundle

    cell = manifest.resolve("resnet18_b4096")
    batch = 4
    bundle = dp_audit_bundle(
        build_model("ResNet18", 10), build_optimizer("sgd", 0.1),
        make_grad_sync("allreduce"), make_mesh(1, devices=jax.devices()[:1]),
        (32, 32, 3), batch)
    lowered = bundle["step_fn"].lower(*bundle["args"])
    analysis = lowered.cost_analysis()
    analysis = analysis[0] if isinstance(analysis, (list, tuple)) else analysis
    cost = costmodel.step_cost_from_hlo(
        lowered.as_text(dialect="hlo"), xla_flops=analysis.get("flops"),
        source="lowered")
    ours = cell.module("flops").flops_per_sample(cell.config) * batch
    assert ours == pytest.approx(cost.flops, rel=0.05)

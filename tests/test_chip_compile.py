"""What only the chip's compiler can show, checked with no chip: programs of
the main path lowered for a described TPU v5e (libtpu is installed here; the
devices are described, not attached) at the sizes the benchmark's cells run,
and the optimised module read for what a CPU run cannot see.

The topology is described inside a fixture, never at import, and every
test that needs it lives in this one file: one process at a time may load
libtpu, and pytest-xdist hands a file to one worker.
"""

import json
import math
import os
import re

import numpy as np
import pytest

os.environ.setdefault("TPU_LOG_DIR", "disabled")

N_IMAGES, BATCH = 50_000, 4096  # the resnet18_b4096 cells' shapes


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip_mesh(topo):
    from pytorch_distributed_nn_tpu.parallel import make_mesh

    return make_mesh(1, devices=topo.devices[:1])


@pytest.fixture(scope="module")
def cifar_prep():
    """(prep_fn, the per-image shape of what the loader holds resident,
    (H, W, C)) of a throw-away CIFAR-10 loader on the CPU: the loader wants
    real devices, its prep function is pure."""
    import jax

    from pytorch_distributed_nn_tpu.data import load_dataset
    from pytorch_distributed_nn_tpu.data.loader import DeviceDataLoader
    from pytorch_distributed_nn_tpu.parallel import make_mesh

    ds = load_dataset("Cifar10", train=True, synthetic_size=8)
    loader = DeviceDataLoader(
        ds, 8, make_mesh(1, devices=jax.devices("cpu")[:1]))
    return loader.prep_fn, loader.images.shape[1:], ds.raw_images.shape[1:]


def _loader_args(mesh, held_shape):
    """Shapes of (images, labels, idx, key) as the trainer passes them."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from pytorch_distributed_nn_tpu.parallel.mesh import DATA_AXIS

    rep = NamedSharding(mesh, P())
    split = NamedSharding(mesh, P(DATA_AXIS))
    return (
        jax.ShapeDtypeStruct((N_IMAGES, *held_shape), np.uint8, sharding=rep),
        jax.ShapeDtypeStruct((N_IMAGES,), jnp.int32, sharding=rep),
        jax.ShapeDtypeStruct((BATCH,), jnp.int32, sharding=split),
        jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=rep),
    )


_INSTRUCTION = re.compile(
    r"^(?:ROOT )?%\S+ = (?P<dtype>\w+)\[(?P<dims>[\d,]*)\]\S* "
    r"(?P<opcode>[\w-]+)\(")


def _entry_instructions(compiled):
    """(dtype, element count, opcode, line) of each array-valued
    instruction of the optimised module's entry computation."""
    text = compiled.as_text()
    entry = text[text.index("\nENTRY "):]
    out = []
    for line in map(str.strip, entry.splitlines()):
        m = _INSTRUCTION.match(line)
        if m:
            dims = [int(d) for d in m["dims"].split(",") if d]
            out.append((m["dtype"], math.prod(dims), m["opcode"], line))
    return out


def _assert_prep_is_cheap_on_the_chip(compiled, image_shape):
    """The three things that made the loader's crop/flip a seventh of the
    ResNet-18 step unseen (ledger, PR 25): a relayout of the whole resident
    data set on every step, per-image gathers over batch-sized arrays, and
    the reflect pad's reversals."""
    instructions = _entry_instructions(compiled)
    assert len(instructions) > 20, "the entry computation was not parsed"
    per_image = math.prod(image_shape)
    data_set_copies = [
        line for _, count, opcode, line in instructions
        if opcode == "copy" and count == N_IMAGES * per_image]
    assert not data_set_copies, data_set_copies
    # one gather may touch the images: the batch's rows out of the resident
    # set. (The step's other kCustom fusions gather B labels and B logits.)
    batch_gathers = [
        (dtype, line) for dtype, count, opcode, line in instructions
        if opcode == "fusion" and "kind=kCustom" in line
        and count >= BATCH * per_image]
    assert len(batch_gathers) <= 1, batch_gathers
    assert all(dtype == "u8" for dtype, _ in batch_gathers), batch_gathers
    reversals = [line for *_, opcode, line in instructions
                 if opcode == "reverse"]
    assert not reversals, reversals


def test_device_loader_prep_compiles_lean_for_v5e(one_chip_mesh, cifar_prep):
    import jax

    prep, held_shape, image_shape = cifar_prep
    compiled = jax.jit(prep).lower(
        *_loader_args(one_chip_mesh, held_shape)).compile()
    _assert_prep_is_cheap_on_the_chip(compiled, image_shape)
    # and nothing data-set-sized among its temporaries (the parent's padded
    # copy was 614 MB)
    assert compiled.memory_analysis().temp_size_in_bytes < 300e6


def test_fused_resnet18_step_compiles_lean_for_v5e(one_chip_mesh, cifar_prep):
    """The step the resnet18_b4096 cells run, built as Trainer builds it:
    the loader's prep inlined into the bf16 ResNet-18 SGD step."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from pytorch_distributed_nn_tpu.models import build_model
    from pytorch_distributed_nn_tpu.optim import build_optimizer
    from pytorch_distributed_nn_tpu.parallel import make_grad_sync
    from pytorch_distributed_nn_tpu.training.train_step import (
        build_train_step,
        create_train_state,
    )

    prep, held_shape, image_shape = cifar_prep
    mesh = one_chip_mesh
    rep = NamedSharding(mesh, P())
    model = build_model("ResNet18", 10, dtype=jnp.bfloat16)
    optimizer = build_optimizer("sgd", 0.1, momentum=0.9)
    sync = make_grad_sync("allreduce")
    state = jax.eval_shape(lambda: create_train_state(
        model, optimizer, sync, jax.random.PRNGKey(0), image_shape))
    state = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=rep), state)
    inner = build_train_step(model, optimizer, sync, mesh, donate=False)
    fused = jax.jit(
        lambda st, images, labels, idx, key, rng: inner(
            st, prep(images, labels, idx, key), rng),
        donate_argnums=(0,))
    images, labels, idx, key = _loader_args(mesh, held_shape)
    compiled = fused.lower(state, images, labels, idx, key, key).compile()
    _assert_prep_is_cheap_on_the_chip(compiled, image_shape)


def test_grouped_matmul_compiles_for_v5e_at_the_lfm2_cells_widths(
        topo, monkeypatch):
    """The expert layer's three Mosaic kernels (forward, dx on the
    transposed weight blocks, the weight gradient) at the widths of cell
    lfm2_8b_a1b_ep4_b2_L8192: 8 experts of 2048 x 3584 and 1792 x 2048 over
    the dropless bound of 4 x 16,384 rows. Interpret mode cannot see a tile
    Mosaic refuses or a block that does not fit VMEM."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding

    from pytorch_distributed_nn_tpu.ops import pallas_kernels as pk

    monkeypatch.setattr(pk, "_interpret", lambda: False)
    one = SingleDeviceSharding(topo.devices[0])
    tile, experts, d, f = pk.GMM_TILE_M, 8, 2048, 1792
    rows = 4 * 16384 + experts * tile

    def shape(dims, dtype):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one)

    def loss(x, w13, w2, meta):
        h = pk.grouped_matmul(x, w13, meta, tile)
        h = jax.nn.silu(h[:, :f]) * h[:, f:]
        y = pk.grouped_matmul(h, w2, meta, tile)
        return jnp.sum(y.astype(jnp.float32) ** 2)

    compiled = jax.jit(jax.grad(loss, (0, 1, 2))).lower(
        shape((rows, d), jnp.bfloat16), shape((experts, d, 2 * f), jnp.float32),
        shape((experts, f, d), jnp.float32),
        shape((rows // tile + 1,), jnp.int32)).compile()
    calls = [line for line in compiled.as_text().splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]
    # forward twice (the second call's input needs the first's result),
    # dx twice, dw twice
    assert len(calls) == 6
    outputs = sorted(_INSTRUCTION.match(c.strip()).group("dtype", "dims")
                     for c in calls)
    assert outputs.count(("f32", f"{experts},{d},{2 * f}")) == 1
    assert outputs.count(("f32", f"{experts},{f},{d}")) == 1
    assert outputs.count(("bf16", f"{rows},{d}")) == 2    # y and dx


def _computations(text):
    """name -> body of each computation of an optimised module's text."""
    return {m.group(1): m.group(2) for m in re.finditer(
        r"^(?:ENTRY )?%(\S+) \(.*?\) -> .*? \{\n(.*?)^\}", text, re.S | re.M)}


def _reached(computations, name, seen=None):
    """``name`` and every computation it calls, fusions included."""
    seen = set() if seen is None else seen
    if name in computations and name not in seen:
        seen.add(name)
        body = computations[name]
        called = re.findall(r"(?:calls|to_apply|body|condition)=%([\w.\-]+)", body)
        for group in re.findall(r"branch_computations=\{([^}]*)\}", body):
            called += re.findall(r"%([\w.\-]+)", group)
        for callee in called:
            _reached(computations, callee, seen)
    return seen


def test_the_expert_layers_rungs_compile_for_v5e_at_the_smallthinker_cells_widths(
        topo, monkeypatch):
    """The held experts' forward + backward as cell
    smallthinker_21b_a3b_ep8_b1_L16384 runs them (16,384 tokens, top-6 of
    64, 8 experts of 2560 x 1536 and 768 x 2560 held, ReLU gate, under
    nn.remat): one branch a rung in the forward and in the backward
    switch, each with its own Mosaic programs (2 + 6 calls), every call
    under the name the benchmark finds the family by, nothing sized by the
    dropless bound outside the last rung's branches, and no more
    temporaries than the bound's body alone needs (autodiff through the
    switch would hand every branch every other's residuals)."""
    import jax
    import jax.numpy as jnp
    from flax import linen as nn
    from jax.sharding import SingleDeviceSharding

    from pytorch_distributed_nn_tpu.models import build_model, lfm2
    from pytorch_distributed_nn_tpu.ops import pallas_kernels as pk

    monkeypatch.setattr(pk, "_interpret", lambda: False)
    one = SingleDeviceSharding(topo.devices[0])
    cfg = build_model("SmallThinker_21B_A3B_EP8").config
    T, k = 16384, cfg.moe_num_active_primary_experts
    d, f, count = cfg.hidden_size, cfg.moe_intermediate_size, 8
    rungs = lfm2.ladder(T * k, count, cfg.num_experts)
    assert rungs == (17408, 32768, 100352)

    def shape(dims, dtype):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one)

    class Layer(nn.Module):
        @nn.compact
        def __call__(self, tokens, sel, weights):
            return nn.remat(lfm2.Experts)(cfg, "relu", name="experts")(
                tokens, sel, weights)

    def loss(params, tokens, sel, weights):
        y, _ = Layer().apply({"params": params}, tokens, sel, weights,
                             mutable=[lfm2.COUNTERS])
        return jnp.sum(y ** 2)

    def compiled():
        return jax.jit(jax.grad(loss, (0, 1, 3))).lower(
            {"experts": {"w13": shape((count, d, 2 * f), jnp.float32),
                         "w2": shape((count, f, d), jnp.float32)}},
            shape((T, d), jnp.float32), shape((T, k), jnp.int32),
            shape((T, k), jnp.float32)).compile()

    laddered = compiled()
    text = laddered.as_text()
    with open(os.path.join(os.path.dirname(__file__), os.pardir, "benchmark",
                           "configs", "smallthinker_21b_a3b_ep8.json")) as f_:
        match = json.load(f_)["kernels"]["grouped_matmul"]["match"]
    names = re.findall(
        r'%(\S+) = [^\n]*custom_call_target="tpu_custom_call"', text)
    # the grouped matmuls are the calls the benchmark's pattern finds; the
    # row sums of combine and of dispatch's transpose are named otherwise
    family = [name for name in names if re.search(match, name)]
    assert len(family) == 8 * len(rungs), names
    assert sorted(set(re.sub(r"\.\d+$", "", name) for name in names)) == [
        "combine", "dispatch", "experts"]
    computations = _computations(text)
    switches = [re.findall(r"%([\w.\-]+)", group) for group in re.findall(
        r" conditional\(.*?branch_computations=\{([^}]*)\}", text)]
    assert sorted(map(len, switches)) == [len(rungs)] * 2   # forward, backward
    # a float array of bound x width: (T k, d), (R, d), (R, f), (T, k, d)
    bound = re.compile(
        r"= \(?(?:bf16|f32)\[(?:%d|%d),\d{3,}\]|= \(?(?:bf16|f32)\[%d,%d,"
        % (T * k, rungs[-1], T, k))
    calls = []
    for branches in switches:
        for rung, branch in enumerate(branches):
            bodies = [computations[c] for c in _reached(computations, branch)]
            calls.append(sum(
                bool(re.search(match, name)) for b in bodies
                for name in re.findall(
                    r'%(\S+) = [^\n]*custom_call_target="tpu_custom_call"', b)))
            sized = [line.strip()[:120] for b in bodies
                     for line in b.splitlines() if bound.search(line)]
            assert bool(sized) == (rung == len(rungs) - 1), (rung, sized[:3])
    assert sorted(calls) == [2] * len(rungs) + [6] * len(rungs)
    entry = computations[re.search(r"^ENTRY %(\S+)", text, re.M).group(1)]
    assert not [line for line in entry.splitlines() if bound.search(line)]
    # against the bound's body alone: one rung, nothing to switch over
    real_ladder = lfm2.ladder
    monkeypatch.setattr(lfm2, "ladder", lambda *a: real_ladder(*a)[-1:])
    alone = compiled()
    assert alone.as_text().count('custom_call_target="tpu_custom_call"') == 10
    assert (laddered.memory_analysis().temp_size_in_bytes
            < 1.05 * alone.memory_analysis().temp_size_in_bytes)


def test_causal_flash_backward_compiles_for_v5e_at_the_lfm2_cells_shape(
        topo, monkeypatch):
    """The attention layer of cell lfm2_8b_a1b_ep4_b2_L8192 (2 x 8192 x 32
    heads of 64, bfloat16, causal): the resident kernels' sweeps end or
    start at the diagonal, so their trip counts are dynamic, which only
    the real Mosaic compiler can refuse. The benchmark tells the three
    calls apart by their operand and result counts (the `kinds` of the
    configuration's `kernels.flash_attention`): a call that fits none is
    sorted as `unknown` and the cell's causal_flash_attention_roofline
    falls silent. Read here with the benchmark's own parser."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding

    from benchmark import trace
    from pytorch_distributed_nn_tpu.ops import pallas_kernels as pk

    monkeypatch.setattr(pk, "_interpret", lambda: False)
    x = jax.ShapeDtypeStruct((2, 8192, 32, 64), jnp.bfloat16,
                             sharding=SingleDeviceSharding(topo.devices[0]))

    def loss(q, k, v):
        out = pk.pallas_attention(q, k, v, None, causal=True)
        return jnp.sum(out.astype(jnp.float32) ** 2)

    compiled = jax.jit(jax.grad(loss, (0, 1, 2))).lower(x, x, x).compile()
    with open(os.path.join(os.path.dirname(__file__), os.pardir, "benchmark",
                           "configs", "lfm2_8b_a1b_ep4.json")) as f:
        family = json.load(f)["kernels"]["flash_attention"]
    # here the calls carry no flax module's name: sort by the counts alone
    kernels = {"flash_attention": {**family, "match": ""}}
    kinds = [trace.classify_kernel(trace.parse_op(line.strip()), kernels)[1]
             for line in compiled.as_text().splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]
    assert sorted(kinds) == sorted(family["calls_per_step"])  # one of each


@pytest.mark.parametrize("family, window", [
    ("window_attention", 4096), ("global_attention", None)])
def test_streamed_flash_compiles_for_v5e_at_the_smallthinker_cells_shape(
        topo, monkeypatch, family, window):
    """The attention layers of cell smallthinker_21b_a3b_ep8_b1_L16384
    (1 x 16,384 x 28 heads of 128, bfloat16): past the resident limit, so
    the streamed family, whose index maps compute each step's block from
    `_causal_sweep` (a floor division and a clamp on traced program
    indices, with and without a window): only the real Mosaic compiler can
    refuse that. The three calls are told apart as the benchmark does."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding

    from benchmark import trace
    from pytorch_distributed_nn_tpu.ops import pallas_kernels as pk

    monkeypatch.setattr(pk, "_interpret", lambda: False)
    assert not pk._resident(16384, 128)
    x = jax.ShapeDtypeStruct((1, 16384, 28, 128), jnp.bfloat16,
                             sharding=SingleDeviceSharding(topo.devices[0]))

    def loss(q, k, v):
        out = pk.pallas_attention(q, k, v, None, causal=True, window=window)
        return jnp.sum(out.astype(jnp.float32) ** 2)

    compiled = jax.jit(jax.grad(loss, (0, 1, 2))).lower(x, x, x).compile()
    with open(os.path.join(os.path.dirname(__file__), os.pardir, "benchmark",
                           "configs", "smallthinker_21b_a3b_ep8.json")) as f:
        spec = json.load(f)["kernels"][family]
    kernels = {family: {**spec, "match": ""}}
    kinds = [trace.classify_kernel(trace.parse_op(line.strip()), kernels)[1]
             for line in compiled.as_text().splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]
    assert sorted(kinds) == sorted(spec["kinds"])           # one of each


def test_streamed_flash_compiles_for_v5e_at_the_glm47_cells_head_width(
        topo, monkeypatch):
    """The latent-attention layers of cell glm47_flash_ep8_b1_L4096 (1 x
    4096 x 20 up-projected heads of 256, bfloat16, causal): the widest
    head any cell gives the kernels, past the resident limit, so the
    streamed family with its blocks of 512 x 256 in VMEM. The three calls
    are told apart as the benchmark does."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding

    from benchmark import trace
    from pytorch_distributed_nn_tpu.ops import pallas_kernels as pk

    monkeypatch.setattr(pk, "_interpret", lambda: False)
    assert not pk._resident(4096, 256)
    x = jax.ShapeDtypeStruct((1, 4096, 20, 256), jnp.bfloat16,
                             sharding=SingleDeviceSharding(topo.devices[0]))

    def loss(q, k, v):
        out = pk.pallas_attention(q, k, v, None, causal=True)
        return jnp.sum(out.astype(jnp.float32) ** 2)

    compiled = jax.jit(jax.grad(loss, (0, 1, 2))).lower(x, x, x).compile()
    with open(os.path.join(os.path.dirname(__file__), os.pardir, "benchmark",
                           "configs", "glm47_flash_ep8.json")) as f:
        spec = json.load(f)["kernels"]["mla_attention"]
    kernels = {"mla_attention": {**spec, "match": ""}}
    kinds = [trace.classify_kernel(trace.parse_op(line.strip()), kernels)[1]
             for line in compiled.as_text().splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]
    assert sorted(kinds) == sorted(spec["kinds"])           # one of each


def test_the_state_space_scan_compiles_for_v5e_at_the_granite_cells_shapes(
        topo, monkeypatch):
    """The Mamba layers of cell granite4_h_micro_pp4_b1_L4096 (1 x 4096, 64
    heads of 64, a 64 x 128 state, chunks of 256, bfloat16 operands): Mosaic
    takes the forward and the backward kernel with their blocks and scratch
    in the scoped VMEM, and the two calls are told apart as the benchmark
    does."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding

    from benchmark import trace
    from pytorch_distributed_nn_tpu.ops import pallas_kernels as pk
    from pytorch_distributed_nn_tpu.ops import ssd

    monkeypatch.setattr(pk, "_interpret", lambda: False)
    one = SingleDeviceSharding(topo.devices[0])

    def shaped(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one)

    def loss(x, dt, a, b, c):
        y, absmax = ssd.ssd_scan(x, dt, a, b, c, 256)
        return jnp.sum(y.astype(jnp.float32) ** 2) + absmax

    compiled = jax.jit(jax.grad(loss, range(5))).lower(
        shaped((1, 4096, 64, 64), jnp.bfloat16),
        shaped((1, 4096, 64), jnp.float32), shaped((64,), jnp.float32),
        shaped((1, 4096, 1, 128), jnp.bfloat16),
        shaped((1, 4096, 1, 128), jnp.bfloat16)).compile()
    with open(os.path.join(os.path.dirname(__file__), os.pardir, "benchmark",
                           "configs", "granite4_h_micro_pp4.json")) as f:
        spec = json.load(f)["kernels"]["ssd_scan"]
    kernels = {"ssd_scan": {**spec, "match": ""}}
    kinds = [trace.classify_kernel(trace.parse_op(line.strip()), kernels)[1]
             for line in compiled.as_text().splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]
    assert sorted(kinds) == sorted(spec["kinds"])           # one of each


@pytest.mark.parametrize("shape, window", [
    ((1, 16384, 28, 128), 4096), ((1, 16384, 28, 128), None),
    ((1, 4096, 20, 256), None), ((1, 16384, 4, 64), None)],
    ids=["smallthinker_window", "smallthinker_global", "glm47_latent",
         "width_64"])
@pytest.mark.parametrize("padded", [False, True], ids=["no_mask", "pad_mask"])
def test_streamed_forward_keeps_its_operands_and_fits_vmem_for_v5e(
        topo, monkeypatch, shape, window, padded):
    """The streamed forward keeps its running statistics lane-replicated
    in (512, 128) scratch; with or without a mask the Mosaic calls keep
    the operand and result counts the benchmark's `kernels` blocks sort
    them by (forward 4 -> 2, dq 7 -> 1, dkv 7 -> 2). Mosaic refuses a
    kernel whose blocks and scratch overrun the scoped VMEM, so the
    compile at head width 256 is the check that the wider statistics fit
    there; at width 64 the statistics meet the accumulator cut to 64
    lanes."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding

    from benchmark import trace
    from pytorch_distributed_nn_tpu.ops import pallas_kernels as pk

    monkeypatch.setattr(pk, "_interpret", lambda: False)
    B, L, H, D = shape
    assert not pk._resident(L, D)
    one = SingleDeviceSharding(topo.devices[0])
    x = jax.ShapeDtypeStruct(shape, jnp.bfloat16, sharding=one)
    m = jax.ShapeDtypeStruct((B, L), jnp.bool_, sharding=one)

    def loss(q, k, v, mask):
        out = pk.pallas_attention(q, k, v, mask if padded else None,
                                  causal=True, window=window)
        return jnp.sum(out.astype(jnp.float32) ** 2)

    compiled = jax.jit(jax.grad(loss, (0, 1, 2))).lower(x, x, x, m).compile()
    calls = [trace.parse_op(line.strip())
             for line in compiled.as_text().splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]
    assert sorted((op.operands, op.outputs) for op in calls) == [
        (4, 2), (7, 1), (7, 2)]
    assert pk._STAT_LANES == 128

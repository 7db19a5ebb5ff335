"""Compile cell glm47_flash_ep8_b1_L4096's train step for the v5e on a
machine with no chip:

    JAX_PLATFORMS=cpu python3 -m benchmark.tools.glm47_compile

``benchmark/compile_for_chip.py`` lowers a text step with labels in the
tokens' shape; this model trains on labels ``(B, L, 2)`` (the next token
and the prediction module's), so this tool builds the step as that one
does, with the trainer's loss and metric functions for two depths, and
prints one JSON line: the bytes a chip needs (``memory_analysis``) and the
Mosaic calls by kernel family and kind, named as the trace will name
them. A rehearsal, not a measurement: nothing runs. About 80 s on a CPU.
"""

from __future__ import annotations

import collections
import json
import os
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")


def main(argv=None) -> int:
    del argv
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import NamedSharding, PartitionSpec as P

    from benchmark import manifest, trace
    from pytorch_distributed_nn_tpu.models import build_model
    from pytorch_distributed_nn_tpu.ops import pallas_kernels
    from pytorch_distributed_nn_tpu.ops.metrics import (
        make_global_depth_losses,
        make_global_masked_cross_entropy,
        make_global_mlm_metrics,
        mlm_sums,
    )
    from pytorch_distributed_nn_tpu.optim import build_optimizer
    from pytorch_distributed_nn_tpu.parallel import make_grad_sync, make_mesh
    from pytorch_distributed_nn_tpu.parallel.mesh import DATA_AXIS
    from pytorch_distributed_nn_tpu.training.train_step import (
        build_train_step,
        create_train_state,
    )

    cell = manifest.resolve("glm47_flash_ep8_b1_L4096")
    config, tc = cell.config, cell.config["train_config"]
    batch, length = config["per_chip_batch"], tc["seq_len"]
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    mesh = make_mesh(1, devices=topo.devices[:1])
    rep, split = NamedSharding(mesh, P()), NamedSharding(mesh, P(DATA_AXIS))
    pallas_kernels._interpret = lambda: False   # compile the kernels for real
    model = build_model(tc["network"], dtype=jnp.bfloat16, max_len=length,
                        attn_fn=pallas_kernels.pallas_attention)
    depth = model.config.label_depth
    optimizer = build_optimizer(tc["optimizer"], tc["lr"])
    sync = make_grad_sync("allreduce")
    state = jax.eval_shape(lambda: create_train_state(
        model, optimizer, sync, jax.random.PRNGKey(0), (length,),
        input_dtype=jnp.int32))
    mlm = make_global_mlm_metrics(DATA_AXIS)
    depths = make_global_depth_losses(DATA_AXIS, depth)
    step = build_train_step(
        model, optimizer, sync, mesh,
        loss_fn=make_global_masked_cross_entropy(DATA_AXIS),
        metrics_fn=lambda logits, labels: {
            **mlm(logits, labels), **depths(logits, labels)},
        pair_accum_fn=mlm_sums)

    def shaped(tree):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=rep), tree)

    tokens = jax.ShapeDtypeStruct((batch, length), jnp.int32, sharding=split)
    labels = jax.ShapeDtypeStruct((batch, length, depth), jnp.int32,
                                  sharding=split)
    key = jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=rep)
    compiled = step.lower(shaped(state), (tokens, labels), key).compile()
    mem = compiled.memory_analysis()
    kernels = collections.Counter()
    for line in compiled.as_text().splitlines():
        line = line.strip().removeprefix("ROOT ")
        if not line.startswith("%") or " = " not in line:
            continue
        op = trace.parse_op(line)
        if op.target == trace.MOSAIC_TARGET:
            family, kind = trace.classify_kernel(op, config["kernels"])
            kernels[f"{family}.{kind}"] += 1
    print(json.dumps({
        "cell": cell.name,
        "bytes_per_chip": {
            "arguments": mem.argument_size_in_bytes,
            "outputs": mem.output_size_in_bytes,
            "temporaries": mem.temp_size_in_bytes,
            "aliased": mem.alias_size_in_bytes,
            "total_live": (mem.argument_size_in_bytes
                           + mem.output_size_in_bytes
                           + mem.temp_size_in_bytes
                           - mem.alias_size_in_bytes),
        },
        "mosaic_kernels": dict(kernels),
    }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

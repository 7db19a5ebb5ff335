"""Compile every cell's train step for the v5e, in the sandbox, with no
chip: ``python3 -m benchmark.compile_for_chip [cell ...]``.

A rehearsal, not a measurement: libtpu is installed here, so
``jax.experimental.topologies.get_topology_desc("v5e:2x2")`` describes
four chips that are not attached and ``.lower(...).compile()`` runs the
real XLA:TPU and Mosaic compilers against them. It answers, at no chip
time: does the step compile at the cell's full size, how many bytes does
it need on each chip (against the 16 GB and the benchmark's 25 % floor),
which collectives and how many Mosaic calls are in it, and how the trace
will name them. Nothing runs; no time, rate or utilisation comes from it.

The step is built the way ``Trainer.__init__`` builds it (same model
arguments, optimizer, gradient sync, loss functions, the loader's
on-device batch preparation fused in for image models), on a mesh of the
described devices, and lowered from shapes. Run it with
``JAX_PLATFORMS=cpu``; not a tier-1 test (a BERT step takes a minute).
"""

from __future__ import annotations

import collections
import json
import os
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")


def build_and_compile(cell) -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.experimental import topologies
    from jax.sharding import NamedSharding, PartitionSpec as P

    from benchmark import manifest, trace
    from pytorch_distributed_nn_tpu.models import (
        build_model,
        input_spec,
        is_text_model,
    )
    from pytorch_distributed_nn_tpu.ops import pallas_kernels
    from pytorch_distributed_nn_tpu.optim import build_optimizer
    from pytorch_distributed_nn_tpu.parallel import make_grad_sync, make_mesh
    from pytorch_distributed_nn_tpu.parallel.mesh import DATA_AXIS
    from pytorch_distributed_nn_tpu.training.train_step import (
        build_train_step,
        create_train_state,
    )

    driver = manifest.load_module(cell.root, cell.driver)
    config, tc, _ = driver.effective(cell, False)
    batch = config["per_chip_batch"] * cell.chips
    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    mesh = make_mesh(cell.chips, devices=topo.devices[:cell.chips])
    rep, split = NamedSharding(mesh, P()), NamedSharding(mesh, P(DATA_AXIS))
    dtype = {"float32": jnp.float32, "bfloat16": jnp.bfloat16}[tc["dtype"]]
    text = is_text_model(tc["network"])
    kw = {"dtype": dtype}
    fns = {}
    pallas_kernels._interpret = lambda: False   # compile the kernels for real
    if text:
        kw["max_len"] = tc["seq_len"]
        if tc.get("fused_ln"):
            kw["fused_ln"] = True
        if tc.get("attn_impl") == "pallas":
            kw["attn_fn"] = pallas_kernels.pallas_attention
        from pytorch_distributed_nn_tpu.ops.metrics import (
            make_global_masked_cross_entropy,
            make_global_mlm_metrics,
            mlm_sums,
        )

        fns = {"loss_fn": make_global_masked_cross_entropy(DATA_AXIS),
               "metrics_fn": make_global_mlm_metrics(DATA_AXIS),
               "pair_accum_fn": mlm_sums}
    model = build_model(tc["network"], 10, **kw)
    optimizer = build_optimizer(tc["optimizer"], tc["lr"],
                                momentum=tc.get("momentum", 0.9))
    sync = make_grad_sync("allreduce")
    in_shape = (tc["seq_len"],) if text else input_spec(tc["network"])
    in_dtype = jnp.int32 if text else jnp.float32
    state = jax.eval_shape(
        lambda: create_train_state(
            model, optimizer, sync, jax.random.PRNGKey(0), in_shape,
            num_replicas=cell.chips, input_dtype=in_dtype))

    def shaped(tree, sharding):
        return jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding),
            tree)

    key = jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=rep)
    if text:
        step = build_train_step(model, optimizer, sync, mesh, **fns)
        tok = jax.ShapeDtypeStruct((batch, tc["seq_len"]), jnp.int32,
                                   sharding=split)
        lowered = step.lower(shaped(state, rep), (tok, tok), key)
    else:
        # the trainer fuses the device loader's batch preparation into
        # the step; the loader itself wants real devices, so a throw-away
        # one on the CPU lends its (pure) prep function
        from pytorch_distributed_nn_tpu.data import load_dataset
        from pytorch_distributed_nn_tpu.data.loader import DeviceDataLoader

        n = tc["synthetic_size"]
        tiny = load_dataset(tc["dataset"], train=True, synthetic_size=8)
        prep = DeviceDataLoader(
            tiny, 8, make_mesh(1, devices=jax.devices("cpu")[:1])).prep_fn
        inner = build_train_step(model, optimizer, sync, mesh, donate=False)
        fused = jax.jit(
            lambda st, images, labels, idx, k, rng: inner(
                st, prep(images, labels, idx, k), rng),
            donate_argnums=(0,))
        h, w, c = input_spec(tc["network"])
        lowered = fused.lower(
            shaped(state, rep),
            jax.ShapeDtypeStruct((n, h, w, c), np.uint8, sharding=rep),
            jax.ShapeDtypeStruct((n,), jnp.int32, sharding=rep),
            jax.ShapeDtypeStruct((batch,), jnp.int32, sharding=split),
            key, key)
    compiled = lowered.compile()
    mem = compiled.memory_analysis()
    text_hlo = compiled.as_text()
    # the optimised module names its instructions as the trace will: read
    # it with the trace's own grammar
    collectives = collections.Counter()
    kernels = collections.Counter()
    for line in text_hlo.splitlines():
        line = line.strip()
        if line.startswith("ROOT "):
            line = line[5:]
        if not line.startswith("%") or " = " not in line:
            continue
        op = trace.parse_op(line)
        if op.collective:
            collectives[op.opcode] += 1
        if op.target == trace.MOSAIC_TARGET:
            family, kind = trace.classify_kernel(op, config.get("kernels"))
            kernels[f"{family}.{kind}"] += 1
    return {
        "cell": cell.name,
        "chips": cell.chips,
        "global_batch": batch,
        "bytes_per_chip": {
            "arguments": mem.argument_size_in_bytes,
            "outputs": mem.output_size_in_bytes,
            "temporaries": mem.temp_size_in_bytes,
            "aliased": mem.alias_size_in_bytes,
            "program": mem.generated_code_size_in_bytes,
            "total_live": (mem.argument_size_in_bytes
                           + mem.output_size_in_bytes
                           + mem.temp_size_in_bytes
                           - mem.alias_size_in_bytes),
        },
        "collectives": dict(collectives),
        "mosaic_kernels": dict(kernels),
        "hlo": text_hlo,
    }


def main(argv=None) -> int:
    from benchmark import manifest

    argv = sys.argv[1:] if argv is None else argv
    keep = None
    if argv and argv[0] == "--hlo-dir":
        keep, argv = argv[1], argv[2:]
    names = argv or [w["name"] for w in manifest.load()["workloads"]]
    for name in names:
        out = build_and_compile(manifest.resolve(name))
        hlo = out.pop("hlo")
        if keep:
            os.makedirs(keep, exist_ok=True)
            with open(os.path.join(keep, name + ".hlo.txt"), "w") as f:
                f.write(hlo)
        print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

"""The measurements behind ``reference/smallthinker_21b_a3b_ep8.py``'s
TOLERANCE, on the chip, at the cell's own sizes (``check_batch`` x 16,384,
published widths, the configuration's preset with the Pallas kernels):

    python3 -m benchmark.tools.smallthinker_controls --seeds 1 2 3

For every seed (weights as the trainer seeds them, the batch as
``correct.check`` draws it), against the float32 reference under matmul
precision "highest":

  program        the configuration as it is run: the three numbers
                 ``correct`` compares, and how many of the (token, slot)
                 routing decisions of each layer differ from the
                 reference's. The program's decisions are read out of the
                 gradient program itself, by a host callback this tool
                 puts around ``models.smallthinker.route`` (a second
                 program compiled for the purpose would round elsewhere
                 and decide the close calls otherwise: PERF.md section 6,
                 PR 34)
  reference_bf16 control: the reference itself computed in bfloat16
                 throughout (parameters, activations, statistics, softmax,
                 router, loss: the tool refuses a loss that is not
                 bfloat16), the nearest precision below the one the
                 configuration states; with its own flips against the
                 float32 reference

One JSON line a seed on stdout. Not part of a benchmark run.
"""

from __future__ import annotations

import argparse
import functools
import json
import sys


def main(argv=None) -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--workload", default="smallthinker_21b_a3b_ep8_b1_L16384")
    p.add_argument("--seeds", type=int, nargs="+", required=True)
    p.add_argument("--rehearse", action="store_true")
    p.add_argument("--out", default=None)
    args = p.parse_args(argv)

    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmark import manifest
    from benchmark.correct import _compare
    from pytorch_distributed_nn_tpu.models import build_model, smallthinker
    from pytorch_distributed_nn_tpu.ops.metrics import masked_cross_entropy
    from pytorch_distributed_nn_tpu.ops.pallas_kernels import pallas_attention
    from pytorch_distributed_nn_tpu.parallel.partitioning import unbox

    cell = manifest.resolve(args.workload)
    driver = manifest.load_module(cell.root, cell.driver)
    config, tc, _ = driver.effective(cell, args.rehearse)
    ref = cell.module("reference")
    m = config["model"]
    layers = m["num_hidden_layers"]
    length, n = config["tokens_per_sample"], config["check_batch"]
    dtype = {"float32": jnp.float32, "bfloat16": jnp.bfloat16}[tc["dtype"]]
    kw = {"dtype": dtype, "max_len": length}
    if tc.get("attn_impl") == "pallas":
        kw["attn_fn"] = pallas_attention
    model = build_model(tc["network"], **kw)

    # the program's routing decisions, from the gradient program itself:
    # the k-th call of ``route`` in a trace is layer k
    heard: dict = {}
    traced = [0]
    real_route = smallthinker.route

    def hear(layer, sel):
        heard.setdefault(layer, []).append(np.asarray(sel))

    def listening_route(logits, k):
        sel, weights = real_route(logits, k)
        jax.debug.callback(
            functools.partial(hear, traced[0] % layers), sel)
        traced[0] += 1
        return sel, weights

    smallthinker.route = listening_route

    def program_loss(params, batch):
        logits = model.apply({"params": params}, batch[0], train=True)
        return masked_cross_entropy(logits, batch[1])

    def reference_routed(params, batch):
        """The reference's own selections, layer by layer (its layers
        applied one by one here: nothing may leave a ``jax.checkpoint``)."""
        x = params["embed"]["embedding"][batch[0]]
        out = []
        for i, (windowed, positions) in enumerate(
                zip(m["sliding_window_layout"], m["rope_layout"])):
            p = params[f"layer_{i}"]
            out.append(ref.routing(p["router"], x, m)[0])
            x = ref._layer(p, x, m, bool(windowed), bool(positions))
        return out

    def highest(f):
        def run(*a):
            with jax.default_matmul_precision("highest"):
                return f(*a)
        return run

    def low_loss(params, batch):
        loss = ref.loss(params, batch, config)
        if loss.dtype != jnp.bfloat16:
            raise TypeError(f"the control's loss is {loss.dtype}: something "
                            "in the reference promoted it out of bfloat16")
        return loss.astype(jnp.float32)

    prog_grad = jax.jit(jax.value_and_grad(program_loss))
    ref_grad = highest(jax.jit(jax.value_and_grad(
        lambda p, b: ref.loss(p, b, config))))
    ref_sel = highest(jax.jit(reference_routed))
    low_grad = jax.jit(jax.value_and_grad(low_loss))
    low_sel = jax.jit(reference_routed)
    compare = jax.jit(_compare)

    def numbers(loss_x, grads_x, loss_r, grads_r):
        gx, gr, dist = (float(v) for v in compare(grads_x, grads_r))
        return {"loss": float(loss_x),
                "loss_rel": abs(float(loss_x) - float(loss_r)) / abs(float(loss_r)),
                "grad_norm_rel": abs(gx - gr) / gr,
                "grad_rel_err": dist / gr}

    def flips(a, b):
        """(token, slot) decisions of ``a`` that ``b`` did not make."""
        out = []
        for x, y in zip(a, b):
            x, y = np.asarray(x), np.asarray(y)
            x, y = x.reshape(-1, x.shape[-1]), y.reshape(-1, y.shape[-1])
            same = (x[:, :, None] == y[:, None, :]).any(-1)
            out.append(int((~same).sum()))
        return out

    sink = open(args.out, "a") if args.out else None
    for seed in args.seeds:
        rng = jax.random.PRNGKey(seed)
        tokens = jnp.zeros((1, length), jnp.int32)
        params = unbox(jax.jit(lambda r: model.init(
            {"params": r, "dropout": r}, tokens, train=False))(rng))["params"]
        batch = ref.make_batch(jax.random.PRNGKey(seed + 7), n, config)
        loss_r, grads_r = ref_grad(params, batch)
        sel_r = ref_sel(params, batch)
        line = {"seed": seed, "batch": n, "tokens": n * length,
                "loss_reference": float(loss_r),
                "decisions_per_layer":
                    n * length * m["moe_num_active_primary_experts"]}
        heard.clear()
        loss_x, grads_x = jax.block_until_ready(prog_grad(params, batch))
        jax.effects_barrier()
        line["program"] = numbers(loss_x, grads_x, loss_r, grads_r)
        line["program"]["flips_per_layer"] = flips(
            [heard[k][0] for k in range(layers)], sel_r)
        line["program"]["routings_heard_per_layer"] = [
            len(heard[k]) for k in range(layers)]
        del grads_x
        low = jax.tree.map(lambda a: a.astype(jnp.bfloat16), params)
        heard.clear()
        loss_l, grads_l = low_grad(low, batch)
        line["reference_bf16"] = numbers(
            loss_l, jax.tree.map(lambda a: a.astype(jnp.float32), grads_l),
            loss_r, grads_r)
        line["reference_bf16"]["flips_per_layer"] = flips(
            low_sel(low, batch), sel_r)
        del grads_l, grads_r, low
        text = json.dumps(line)
        print(text, flush=True)
        if sink:
            sink.write(text + "\n")
            sink.flush()
    smallthinker.route = real_route
    return 0


if __name__ == "__main__":
    sys.exit(main())

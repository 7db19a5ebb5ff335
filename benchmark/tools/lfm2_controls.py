"""The measurements behind ``reference/lfm2_8b_a1b_ep4.py``'s TOLERANCE,
on the chip, at the cell's own sizes (``check_batch`` x 8192, published
widths, the configuration's preset with the Pallas kernels):

    python3 -m benchmark.tools.lfm2_controls --seeds 1 2 3 [--planted 3]

For every seed (weights as the trainer seeds them, the batch as
``correct.check`` draws it), against the float32 reference under matmul
precision "highest":

  program        the configuration as it is run: the three numbers
                 ``correct`` compares, and how many of the (token, slot)
                 routing decisions of each expert layer differ from the
                 reference's. The program's decisions are read out of the
                 gradient program itself, by a host callback this tool puts
                 around ``models.lfm2.route``: a second program compiled
                 for the purpose rounds elsewhere and decides the close
                 calls otherwise (``apart_flips_per_layer`` counts how
                 many), and so does the expert layer's recomputation in
                 the backward pass if XLA fuses it differently
                 (``recomputed_flips_per_layer``)
  planted        (the first ``--planted`` seeds) the same program against
                 the reference made to route as the program's forward pass
                 did: what is left is rounding alone, and the distance
                 between the two rows is what the flipped decisions cost
  reference_bf16 control: the reference itself computed in bfloat16
                 throughout (parameters, activations, statistics, softmax,
                 router, loss: the tool refuses a loss that is not
                 bfloat16), the nearest precision below the one the
                 configuration states

One JSON line a seed on stdout; ``worst_leaves`` names the eight
parameters that carry most of a row's squared gradient distance (their
share of it, and each one's own relative distance). Not part of a
benchmark run.
"""

from __future__ import annotations

import argparse
import functools
import json
import sys


def main(argv=None) -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--workload", default="lfm2_8b_a1b_ep4_b2_L8192")
    p.add_argument("--seeds", type=int, nargs="+", required=True)
    p.add_argument("--planted", type=int, default=3,
                   help="how many of the seeds get the planted row")
    p.add_argument("--rehearse", action="store_true")
    p.add_argument("--out", default=None)
    args = p.parse_args(argv)

    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmark import manifest
    from benchmark.correct import _compare
    from pytorch_distributed_nn_tpu.models import build_model, lfm2
    from pytorch_distributed_nn_tpu.ops.metrics import masked_cross_entropy
    from pytorch_distributed_nn_tpu.ops.pallas_kernels import pallas_attention
    from pytorch_distributed_nn_tpu.parallel.partitioning import unbox

    cell = manifest.resolve(args.workload)
    driver = manifest.load_module(cell.root, cell.driver)
    config, tc, _ = driver.effective(cell, args.rehearse)
    ref = cell.module("reference")
    m = config["model"]
    length, n = config["tokens_per_sample"], config["check_batch"]
    dtype = {"float32": jnp.float32, "bfloat16": jnp.bfloat16}[tc["dtype"]]
    kw = {"dtype": dtype, "max_len": length}
    if tc.get("attn_impl") == "pallas":
        kw["attn_fn"] = pallas_attention
    model = build_model(tc["network"], **kw)
    expert_layers = [i for i in range(m["num_hidden_layers"])
                     if i >= m["num_dense_layers"]]

    # the program's routing decisions, from whichever program runs: the
    # k-th call of ``route`` in a trace is the k-th expert layer, and every
    # execution of it (forward, recomputation) leaves its selections here
    heard: dict = {}
    traced = [0]
    real_route = lfm2.route

    def hear(layer, sel):
        heard.setdefault(layer, []).append(np.asarray(sel))

    def listening_route(scores, bias, k, scaling=1.0):
        sel, weights = real_route(scores, bias, k, scaling)
        layer = traced[0] % len(expert_layers)
        traced[0] += 1
        jax.debug.callback(functools.partial(hear, layer), sel)
        return sel, weights

    lfm2.route = listening_route

    def listen(run, *a):
        """(result, forward selections, recomputed selections or None)."""
        heard.clear()
        out = jax.block_until_ready(run(*a))
        jax.effects_barrier()
        first = [heard[k][0] for k in range(len(expert_layers))]
        again = ([heard[k][1] for k in range(len(expert_layers))]
                 if all(len(v) > 1 for v in heard.values()) else None)
        return out, first, again

    def program_loss(params, batch):
        logits = model.apply({"params": params}, batch[0], train=True)
        return masked_cross_entropy(logits, batch[1])

    prog_grad = jax.jit(jax.value_and_grad(program_loss))
    prog_apart = jax.jit(program_loss)       # forward alone: another program

    def reference_routed(params, batch):
        """The reference's own selections, layer by layer (its layers
        applied one by one here: nothing may leave a ``jax.checkpoint``)."""
        x = params["embed"]["embedding"][batch[0]]
        out = []
        for i, kind in enumerate(m["layer_types"]):
            p = params[f"layer_{i}"]
            h = ref._rms(x, p["operator_norm"]["scale"], m["norm_eps"])
            x = x + (ref._attention(p["attn"], h, m)
                     if kind == "full_attention" else ref._conv(p["conv"], h))
            h = ref._rms(x, p["ffn_norm"]["scale"], m["norm_eps"])
            if i in expert_layers:
                out.append(ref.routing(p["moe"], h, m)[0])
                x = x + ref._expert_ffn(p["moe"], h, m)
            else:
                x = x + ref._dense_ffn(p["mlp"], h)
        return out

    def planted_loss(params, batch, sels):
        """The reference, routed as ``sels`` say (weights from its own
        scores at those experts)."""
        real, todo = ref.routing, list(sels)

        def planted(p, x, mm):
            scores = jax.nn.sigmoid(ref._mm(x, p["router"], "bld,de->ble"))
            sel = todo.pop(0).reshape(x.shape[0], x.shape[1], -1)
            picked = jnp.take_along_axis(scores, sel, axis=-1)
            w = picked / (picked.sum(axis=-1, keepdims=True) + 1e-6)
            return sel, w * mm["routed_scaling_factor"]

        ref.routing = planted
        try:
            return ref.loss(params, batch, config)
        finally:
            ref.routing = real

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

    ref_grad = highest(jax.jit(jax.value_and_grad(
        lambda p, b: ref.loss(p, b, config))))
    ref_sel = highest(jax.jit(reference_routed))
    planted_grad = highest(jax.jit(jax.value_and_grad(planted_loss)))
    low_grad = jax.jit(jax.value_and_grad(low_loss))
    compare = jax.jit(_compare)

    @jax.jit
    def by_leaf(a, b):
        return jax.tree.map(
            lambda x, y: jnp.stack([jnp.sum(jnp.square(x - y)),
                                    jnp.sum(jnp.square(y))]), a, b)

    def worst_leaves(grads_x, grads_r, top=8):
        pairs = jax.tree_util.tree_leaves_with_path(by_leaf(grads_x, grads_r))
        total = sum(float(v[0]) for _, v in pairs)
        pairs.sort(key=lambda kv: -float(kv[1][0]))
        return [{"leaf": jax.tree_util.keystr(path),
                 "share_of_distance2": float(v[0]) / total,
                 "own_rel_err": float(jnp.sqrt(v[0] / v[1]))}
                for path, v in pairs[:top]]

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
    for at, seed in enumerate(args.seeds):
        rng = jax.random.PRNGKey(seed)
        tokens = jnp.zeros((1, length), jnp.int32)
        params = unbox(jax.jit(lambda r: model.init(
            {"params": r, "dropout": r}, tokens, train=False))(rng))["params"]
        batch = ref.make_batch(jax.random.PRNGKey(seed + 7), n, config)
        loss_r, grads_r = ref_grad(params, batch)
        sel_r = ref_sel(params, batch)
        line = {"seed": seed, "batch": n, "tokens": n * length,
                "loss_reference": float(loss_r),
                "decisions_per_layer": n * length * m["num_experts_per_tok"]}
        (loss_x, grads_x), sel_x, sel_again = listen(prog_grad, params, batch)
        line["program"] = numbers(loss_x, grads_x, loss_r, grads_r)
        line["program"]["flips_per_layer"] = flips(sel_x, sel_r)
        if sel_again is not None:
            line["program"]["recomputed_flips_per_layer"] = flips(
                sel_again, sel_x)
        _, sel_apart, _ = listen(prog_apart, params, batch)
        line["program"]["apart_flips_per_layer"] = flips(sel_apart, sel_x)
        line["program"]["worst_leaves"] = worst_leaves(grads_x, grads_r)
        if at < args.planted:
            loss_p, grads_p = planted_grad(
                params, batch, [jnp.asarray(s) for s in sel_x])
            line["planted"] = numbers(loss_x, grads_x, loss_p, grads_p)
            line["planted"]["worst_leaves"] = worst_leaves(grads_x, grads_p)
            del grads_p
        del grads_x
        low = jax.tree.map(lambda a: a.astype(jnp.bfloat16), params)
        loss_l, grads_l = low_grad(low, batch)
        line["reference_bf16"] = numbers(
            loss_l, jax.tree.map(lambda a: a.astype(jnp.float32), grads_l),
            loss_r, grads_r)
        del grads_l, grads_r, low
        text = json.dumps(line)
        print(text, flush=True)
        if sink:
            sink.write(text + "\n")
            sink.flush()
    lfm2.route = real_route
    return 0


if __name__ == "__main__":
    sys.exit(main())

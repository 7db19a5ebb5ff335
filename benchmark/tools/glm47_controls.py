"""The measurements behind ``reference/glm47_flash_ep8.py``'s TOLERANCE, on
the chip, at the cell's own sizes (``check_batch`` x 4096, published
widths, the configuration's preset with the Pallas kernels):

    python3 -m benchmark.tools.glm47_controls --seeds 1 2 3

For every seed (weights as the trainer seeds them, the expert biases
balanced as the trainer balances them, the batch as
``correct.check`` draws it), against the float32 reference under matmul
precision "highest", the three numbers ``correct`` compares, of:

  program        the configuration as it is run
  reference_bf16 control: the reference itself computed in bfloat16
                 (parameters, activations, statistics, attention's
                 softmax, router, logits: the tool refuses a head input
                 that is not bfloat16) up to the logits, whose
                 log-softmax and mean are float32 as in the program; the
                 nearest precision below the one the configuration
                 states
  fault.<name>   the program with one of ``FAULTS`` planted (``--faults``
                 none leaves them out): each has to fail the comparison

Each reading also carries ``parts``: for each part of the model (``PARTS``)
its gradient's distance from the reference's over the reference's norm, and
its share of the whole distance squared, so a reading can be traced to the
layers that make it.

One JSON line a seed on stdout. Not part of a benchmark run. ``FAULTS`` is
also what ``tests/test_glm47_flash.py`` plants at the tiny preset.
"""

from __future__ import annotations

import argparse
import contextlib
import json
import sys


def _mtp_input_shifted_by_two(patch):
    """The prediction module reads token i + 2, its own target: the label
    leaks into the input."""
    from pytorch_distributed_nn_tpu.models import glm47_flash

    real = glm47_flash.tokens_ahead
    patch(glm47_flash, "tokens_ahead",
          lambda tokens, depth: real(tokens, 2 * depth))


def _k_rope_per_head(patch):
    """Each head's rotary key from its own up-projected channels (the last
    ``qk_rope_head_dim`` of its no-position key) instead of the one rotary
    key a token that every head reads."""
    import jax.numpy as jnp
    from flax import linen as nn

    from pytorch_distributed_nn_tpu.models import glm47_flash as g
    from pytorch_distributed_nn_tpu.models.transformer import (
        EMBED,
        HEADS,
        KV,
    )

    class PerHead(g.LatentAttention):
        @nn.compact
        def __call__(self, x):
            cfg = self.config
            H, nope, rope = (cfg.num_attention_heads, cfg.qk_nope_head_dim,
                             cfg.qk_rope_head_dim)
            eps, dt = cfg.rms_norm_eps, cfg.dtype
            c_q = g._proj(cfg.q_lora_rank, (EMBED, None), "q_a_proj", dt)(x)
            c_q = g.RMSNorm(eps, name="q_a_norm")(c_q).astype(dt)
            q = g._proj((H, nope + rope), (None, HEADS, KV), "q_b_proj",
                        dt)(c_q)
            c_kv = g._proj(cfg.kv_lora_rank + rope, (EMBED, None),
                           "kv_a_proj", dt)(x)[..., :cfg.kv_lora_rank]
            c_kv = g.RMSNorm(eps, name="kv_a_norm")(c_kv).astype(dt)
            kv = g._proj((H, nope + cfg.v_head_dim), (None, HEADS, KV),
                         "kv_b_proj", dt)(c_kv)
            k_nope, v = kv[..., :nope], kv[..., nope:]
            q = jnp.concatenate(
                [q[..., :nope], g.rotary(q[..., nope:], cfg.rope_theta)
                 .astype(dt)], axis=-1)
            k = jnp.concatenate(
                [k_nope, g.rotary(k_nope[..., nope - rope:], cfg.rope_theta)
                 .astype(dt)], axis=-1)
            attn = self.attn_fn if self.attn_fn is not None else (
                g.full_attention)
            return g._proj(cfg.hidden_size, (HEADS, KV, EMBED), "o_proj", dt,
                           axis=(-2, -1))(attn(q, k, v, None, causal=True))

    patch(g, "LatentAttention", PerHead)


def _shared_expert_dropped(patch):
    """The shared expert's weights are there, its output is not added."""
    import jax.numpy as jnp

    from pytorch_distributed_nn_tpu.models import glm47_flash, lfm2

    class Dropped(lfm2.GatedMLP):
        def __call__(self, x):
            y = super().__call__(x)
            return jnp.zeros_like(y) if self.name == "shared_expert" else y

    patch(glm47_flash, "GatedMLP", Dropped)


def _routed_scale_one(patch):
    """The routed weights sum to 1, not to ``routed_scaling_factor``."""
    from pytorch_distributed_nn_tpu.models import lfm2

    real = lfm2.route
    patch(lfm2, "route",
          lambda scores, bias, k, scaling=1.0: real(scores, bias, k, 1.0))


def _bias_in_the_weights(patch):
    """The expert bias enters the weights as well as the selection."""
    from pytorch_distributed_nn_tpu.models import lfm2

    def route(scores, bias, k, scaling=1.0):
        sel, weights = lfm2.top_k(scores + bias, scores + bias, k)
        return sel, weights / weights.sum(-1, keepdims=True) * scaling

    patch(lfm2, "route", route)


#: name -> plant(patch), ``patch(owner, attribute, value)`` as pytest's
#: ``monkeypatch.setattr``
FAULTS = {
    "mtp_input_shifted_by_two": _mtp_input_shifted_by_two,
    "k_rope_per_head": _k_rope_per_head,
    "shared_expert_dropped": _shared_expert_dropped,
    "routed_scale_one": _routed_scale_one,
    "bias_in_the_weights": _bias_in_the_weights,
}


#: part -> the test on a parameter's path that puts it there; the first
#: that holds wins, in this order
PARTS = (
    ("router", lambda path: "router" in path or "expert_bias" in path),
    ("routed_experts", lambda path: "experts" in path),
    ("shared_expert", lambda path: "shared_expert" in path),
    ("dense_mlp", lambda path: "mlp" in path),
    ("norms", lambda path: "scale" in path),
    ("mla", lambda path: "mla" in path),
    ("eh_proj", lambda path: "eh_proj" in path),
    ("embed", lambda path: "embed" in path),
    ("lm_head", lambda path: "lm_head" in path),
)


def part_of(path) -> str:
    """The ``PARTS`` name of a parameter at ``path`` (its keys)."""
    for name, holds in PARTS:
        if holds(path):
            return name
    raise KeyError(f"no part holds {path}")


def by_part(a, b):
    """{part: (|a - b|^2, |b|^2)} over the leaves of each part, float32."""
    import jax
    import jax.numpy as jnp

    out = {}
    for (path, x), y in zip(jax.tree_util.tree_flatten_with_path(a)[0],
                            jax.tree.leaves(b)):
        keys = tuple(getattr(k, "key", k) for k in path)
        x, y = x.astype(jnp.float32), y.astype(jnp.float32)
        d, n = out.get(part_of(keys), (0.0, 0.0))
        out[part_of(keys)] = (d + jnp.sum(jnp.square(x - y)),
                              n + jnp.sum(jnp.square(y)))
    return out


@contextlib.contextmanager
def planted(fault: str):
    """``FAULTS[fault]`` in place for the duration, then undone."""
    undo = []

    def patch(owner, name, value):
        undo.append((owner, name, getattr(owner, name)))
        setattr(owner, name, value)

    FAULTS[fault](patch)
    try:
        yield
    finally:
        for owner, name, value in reversed(undo):
            setattr(owner, name, value)


def main(argv=None) -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--workload", default="glm47_flash_ep8_b1_L4096")
    p.add_argument("--seeds", type=int, nargs="+", required=True)
    p.add_argument("--faults", choices=("all", "none"), default="all")
    p.add_argument("--rehearse", action="store_true")
    p.add_argument("--out", default=None)
    args = p.parse_args(argv)

    import jax
    import jax.numpy as jnp

    from benchmark import manifest
    from benchmark.correct import _compare
    from pytorch_distributed_nn_tpu.models import build_model
    from pytorch_distributed_nn_tpu.ops.metrics import masked_cross_entropy
    from pytorch_distributed_nn_tpu.ops.pallas_kernels import pallas_attention
    from pytorch_distributed_nn_tpu.parallel.partitioning import unbox

    cell = manifest.resolve(args.workload)
    driver = manifest.load_module(cell.root, cell.driver)
    config, tc, _ = driver.effective(cell, args.rehearse)
    ref = cell.module("reference")
    length, n = config["tokens_per_sample"], config["check_batch"]
    dtype = {"float32": jnp.float32, "bfloat16": jnp.bfloat16}[tc["dtype"]]
    kw = {"dtype": dtype, "max_len": length}
    if tc.get("attn_impl") == "pallas":
        kw["attn_fn"] = pallas_attention
    model = build_model(tc["network"], **kw)

    def program_loss(params, batch):
        logits = model.apply({"params": params}, batch[0], train=True)
        return masked_cross_entropy(logits, batch[1])

    def low_loss(params, batch):
        lifted = [x.dtype for x in jax.eval_shape(
            lambda p: ref.hidden(p, batch[0], config), params)
            if x.dtype != jnp.bfloat16]
        if lifted:
            raise TypeError(f"the control's head reads {lifted}: something "
                            "in the reference promoted it out of bfloat16")
        return ref.loss(params, batch, config)

    ref_grad = jax.jit(jax.value_and_grad(
        lambda p, b: ref.loss(p, b, config)))
    low_grad = jax.jit(jax.value_and_grad(low_loss))
    # one program a fault, traced with it planted (a jitted function
    # traces at its first call; it is planted around every call alike)
    faults = sorted(FAULTS) if args.faults == "all" else []
    programs = {name: jax.jit(jax.value_and_grad(program_loss))
                for name in [None, *faults]}
    compare = jax.jit(_compare)
    parts = jax.jit(by_part)

    def numbers(loss_x, grads_x, loss_r, grads_r):
        gx, gr, dist = (float(v) for v in compare(grads_x, grads_r))
        split = {k: (float(d), float(n))
                 for k, (d, n) in parts(grads_x, grads_r).items()}
        return {"loss": float(loss_x),
                "loss_rel": abs(float(loss_x) - float(loss_r)) / abs(float(loss_r)),
                "grad_norm_rel": abs(gx - gr) / gr,
                "grad_rel_err": dist / gr,
                "parts": {k: {"rel_err": (d / n) ** 0.5 if n else None,
                              "share": d / dist ** 2}
                          for k, (d, n) in split.items()}}

    def verdict(read):
        return {**read, "fails": sorted(
            k for k, limit in ref.TOLERANCE.items() if read[k] > limit)}

    sink = open(args.out, "a") if args.out else None
    for seed in args.seeds:
        rng = jax.random.PRNGKey(seed)
        tokens = jnp.zeros((1, length), jnp.int32)
        params = unbox(jax.jit(lambda r: model.init(
            {"params": r, "dropout": r}, tokens, train=False))(rng))["params"]
        # as training/trainer.py does after seeding them
        params = model.balance_routing(
            params, jax.random.fold_in(rng, 1), length)
        batch = ref.make_batch(jax.random.PRNGKey(seed + 7), n, config)
        with jax.default_matmul_precision("highest"):
            loss_r, grads_r = ref_grad(params, batch)
        line = {"seed": seed, "batch": n, "tokens": n * length,
                "loss_reference": float(loss_r)}
        for name, program in programs.items():
            with (planted(name) if name else contextlib.nullcontext()):
                loss_x, grads_x = program(params, batch)
                read = numbers(loss_x, grads_x, loss_r, grads_r)
            line["program" if name is None else f"fault.{name}"] = verdict(
                read)
            del grads_x
        low = jax.tree.map(lambda a: a.astype(jnp.bfloat16), params)
        loss_l, grads_l = low_grad(low, batch)
        # (the comparison computes in float32 whatever it is handed)
        line["reference_bf16"] = verdict(numbers(
            loss_l, grads_l, loss_r, grads_r))
        del grads_l, grads_r, low
        text = json.dumps(line)
        print(text, flush=True)
        if sink:
            sink.write(text + "\n")
            sink.flush()
    return 0


if __name__ == "__main__":
    sys.exit(main())

"""The measurements behind ``reference/granite4_h_micro_pp4.py``'s
TOLERANCE, on the chip, at the cell's own sizes (``check_batch`` x 4096,
published widths, the configuration's preset with the Pallas kernels):

    python3 -m benchmark.tools.granite4_controls --seeds 1 2 3

For every seed (weights as the trainer seeds them, the batch as
``correct.check`` draws it), against the float32 reference under matmul
precision "highest", the three numbers ``correct`` compares, of:

  program        the configuration as it is run
  reference_bf16 control: the reference itself computed in bfloat16
                 (parameters, activations, statistics, the recurrence's
                 state, attention's softmax, up to the logits, whose
                 log-softmax and mean are float32 as in the program: the
                 tool refuses a head input that is not bfloat16); the
                 nearest precision below the one the configuration states
  fault.<name>   the program with one of ``FAULTS`` planted (``--faults``
                 none leaves them out): each has to fail the comparison

Each reading also carries ``parts``: for each part of the model (``PARTS``)
its gradient's distance from the reference's over the reference's norm, and
its share of the whole distance squared.

One JSON line a seed on stdout. Not part of a benchmark run. ``FAULTS`` is
also what ``tests/test_granite4.py`` plants at the tiny preset.
"""

from __future__ import annotations

import argparse
import contextlib
import json
import math
import sys


def _state_reset_each_chunk(patch):
    """The scan forgets its state at every chunk boundary: each chunk is
    scanned as a sequence of its own."""
    from pytorch_distributed_nn_tpu.models import granite4_hybrid as g

    real = g.ssd_scan

    def scan(x, dt, a, b, c, chunk):
        batch, length = x.shape[:2]

        def cut(v):
            return v.reshape((batch * length // chunk, chunk) + v.shape[2:])

        y, absmax = real(cut(x), cut(dt), a, cut(b), cut(c), chunk)
        return y.reshape(x.shape), absmax

    patch(g, "ssd_scan", scan)


def _conv_sees_one_ahead(patch):
    """The causal convolution's window is one position late: position t
    reads t - K + 2 .. t + 1."""
    import jax.numpy as jnp

    from pytorch_distributed_nn_tpu.models import granite4_hybrid as g

    real = g.short_conv
    patch(g, "short_conv", lambda z, taps: real(
        jnp.pad(z[:, 1:], ((0, 0), (0, 1), (0, 0))), taps))


def _gate_after_norm(patch):
    """``RMSNorm(y) * silu(z)`` (norm_before_gate true) in place of
    ``RMSNorm(y * silu(z))``."""
    import jax.numpy as jnp
    from flax import linen as nn

    from pytorch_distributed_nn_tpu.models import granite4_hybrid as g

    class After(g.GatedRMSNorm):
        @nn.compact
        def __call__(self, y, z):
            scale = self.param("scale", nn.initializers.ones, (y.shape[-1],),
                               jnp.float32)
            return g.rms_norm(y.astype(jnp.float32), scale, self.eps) * (
                nn.silu(z.astype(jnp.float32)))

    patch(g, "GatedRMSNorm", After)


def _d_skip_left_out(patch):
    """The mixer reads ``D`` as zero: no skip from x to y."""
    import jax.numpy as jnp

    from pytorch_distributed_nn_tpu.models import granite4_hybrid as g

    class NoSkip(g.Mamba2Mixer):
        def param(self, name, *args, **kwargs):
            value = super().param(name, *args, **kwargs)
            return jnp.zeros_like(value) if name == "D" else value

    patch(g, "Mamba2Mixer", NoSkip)


def _residual_multiplier_left_out(patch):
    """Each residual branch added whole, not times 0.22."""
    from flax import linen as nn

    from pytorch_distributed_nn_tpu.models import granite4_hybrid as g

    class Whole(g.Granite4Block):
        @nn.compact
        def __call__(self, x):
            cfg = self.config
            h = g.RMSNorm(cfg.rms_norm_eps, name="input_layernorm")(x)
            if self.kind == "mamba":
                h, absmax = g.Mamba2Mixer(cfg, name="mamba")(
                    h.astype(cfg.dtype))
            else:
                h = g.NoPEAttention(cfg, self.attn_fn, name="self_attn")(
                    h.astype(cfg.dtype))
                absmax = 0.0
            x = x + h
            h = g.RMSNorm(cfg.rms_norm_eps, name="post_attention_layernorm")(x)
            return x + g.GatedMLP(cfg, name="shared_mlp")(
                h.astype(cfg.dtype)), absmax

    patch(g, "Granite4Block", Whole)


def _attention_scale_inverse_sqrt(patch):
    """Attention's softmax at the usual 1/sqrt(head_dim), not at
    ``attention_multiplier``."""
    from flax import linen as nn

    from pytorch_distributed_nn_tpu.models import granite4_hybrid as g
    from pytorch_distributed_nn_tpu.models.transformer import (
        EMBED,
        HEADS,
        KV,
    )

    class Unscaled(g.NoPEAttention):
        @nn.compact
        def __call__(self, x):
            cfg = self.config
            H, Hkv, D = (cfg.num_attention_heads, cfg.num_key_value_heads,
                         cfg.head_dim)
            q = g._proj((H, D), (EMBED, HEADS, KV), "q_proj", cfg.dtype)(x)
            k = g._proj((Hkv, D), (EMBED, HEADS, KV), "k_proj", cfg.dtype)(x)
            v = g._proj((Hkv, D), (EMBED, HEADS, KV), "v_proj", cfg.dtype)(x)
            attn = self.attn_fn if self.attn_fn is not None else (
                g.full_attention)
            out = attn(q, g.repeat_kv(k, H // Hkv), g.repeat_kv(v, H // Hkv),
                       None, causal=True)
            return g._proj(cfg.hidden_size, (HEADS, KV, EMBED), "o_proj",
                           cfg.dtype, axis=(-2, -1))(out)

    patch(g, "NoPEAttention", Unscaled)


#: name -> plant(patch), ``patch(owner, attribute, value)`` as pytest's
#: ``monkeypatch.setattr``
FAULTS = {
    "state_reset_each_chunk": _state_reset_each_chunk,
    "conv_sees_one_ahead": _conv_sees_one_ahead,
    "gate_after_norm": _gate_after_norm,
    "d_skip_left_out": _d_skip_left_out,
    "residual_multiplier_left_out": _residual_multiplier_left_out,
    "attention_scale_inverse_sqrt": _attention_scale_inverse_sqrt,
}


#: part -> the test on a parameter's path that puts it there; the first
#: that holds wins, in this order
PARTS = (
    ("scan", lambda path: any(k in path for k in ("A_log", "dt_bias", "D"))),
    ("mamba_conv", lambda path: "conv_weight" in path or "conv_bias" in path),
    ("mamba_proj", lambda path: "mamba" in path and "scale" not in path),
    ("attention", lambda path: "self_attn" in path),
    ("mlp", lambda path: "shared_mlp" in path),
    ("norms", lambda path: "scale" in path),
    ("embed", lambda path: "embed" in path),
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
    p.add_argument("--workload", default="granite4_h_micro_pp4_b1_L4096")
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
        head = jax.eval_shape(
            lambda p: ref.hidden(p, batch[0], config), params)
        if head.dtype != jnp.bfloat16:
            raise TypeError(f"the control's head reads {head.dtype}: "
                            "something in the reference promoted it out of "
                            "bfloat16")
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
                "parts": {k: {"rel_err": math.sqrt(d / n) if n else None,
                              "share": d / dist ** 2 if dist else None}
                          for k, (d, n) in split.items()}}

    def verdict(read):
        return {**read, "fails": sorted(
            k for k, limit in ref.TOLERANCE.items() if read[k] > limit)}

    sink = open(args.out, "a") if args.out else None
    try:
        for seed in args.seeds:
            rng = jax.random.PRNGKey(seed)
            tokens = jnp.zeros((1, length), jnp.int32)
            params = unbox(jax.jit(lambda r: model.init(
                {"params": r, "dropout": r}, tokens, train=False))(rng))[
                    "params"]
            batch = ref.make_batch(jax.random.PRNGKey(seed + 7), n, config)
            with jax.default_matmul_precision("highest"):
                loss_r, grads_r = ref_grad(params, batch)
            line = {"seed": seed, "batch": n, "tokens": n * length,
                    "loss_reference": float(loss_r)}
            for name, program in programs.items():
                with (planted(name) if name else contextlib.nullcontext()):
                    loss_x, grads_x = program(params, batch)
                    read = numbers(loss_x, grads_x, loss_r, grads_r)
                line["program" if name is None else f"fault.{name}"] = (
                    verdict(read))
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
    finally:
        if sink:
            sink.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())

"""A copy of the benchmark's tree with three throw-away cells ADDED — a
configuration (LeNet on MNIST-shaped synthetic data) with its plain
reference and FLOPs function, a mix, and a per-layer metric with a reader
of its own; and two more configurations that are cut to one chip
(``cut_rule``), one of them with per-layer patterns under its source's own
key names — as files and ``BENCHMARK.json`` entries only. No file that is
there is edited: that is what a later PR is allowed to do."""

import json
import os
import shutil
import textwrap

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

CONFIG = {
    "name": "lenet_mnist",
    "source": "https://github.com/hwang595/pytorch_distributed_nn/blob/master/src/model_ops/lenet.py",
    "reduced": [],
    "model": {"image": [28, 28, 1], "num_classes": 10},
    "sample": "one 28x28 image",
    "tokens_per_sample": 1,
    "per_chip_batch": 8,
    "train_config": {
        "network": "LeNet", "dataset": "MNIST", "synthetic_size": 64,
        "dtype": "float32", "optimizer": "sgd", "lr": 0.01, "log_every": 2,
    },
    "trace_steps": 3,
    "check_batch": 8,
    "flops": "benchmark/flops/lenet_mnist.py",
    "reference": "benchmark/reference/lenet_mnist.py",
}

#: What a file that is cut to one chip says (``cut_rule``): eight chips
#: share each layer, so 16 of 128 routed experts and 25,024 of 200,192
#: vocabulary rows are held here, with one of the two leading dense layers
#: and one whole period of four expert layers; every width as published.
_WIDTHS = {
    "hidden_size": 2048, "head_dim": 128, "num_attention_heads": 32,
    "num_key_value_heads": 4, "intermediate_size": 6144,
    "moe_intermediate_size": 1024, "num_experts_per_tok": 8,
}
CUT = {
    "reduced": ["num_hidden_layers", "num_dense_layers", "num_experts",
                "vocab_size"],
    "model": {**_WIDTHS, "num_hidden_layers": 5, "num_dense_layers": 1,
              "num_experts": 16, "vocab_size": 25024},
    "published": {**_WIDTHS, "num_hidden_layers": 32, "num_dense_layers": 2,
                  "num_experts": 128, "vocab_size": 200192},
    "deployment": {
        "chips_per_layer": 8,
        "how": "experts and vocabulary rows divided eight ways, attention "
               "whole on every chip; the layers left out lie on further "
               "chips, as the stages of a pipeline",
        "layer_period": 4,
        "leading_dense_layers": 1,
    },
}
#: A cut whose source spells its counts otherwise (the keys of the catalog's
#: window-and-global expert decoder): the routed experts under a name of
#: its own and two per-layer lists that only their shape tells, so the file
#: says which published layers it keeps. Eight chips share each layer: 8 of
#: 64 experts, 18,992 of 151,936 rows, one whole period of four layers (a
#: global layer without rotary positions, then three window layers).
_PATTERNED_WIDTHS = {
    "hidden_size": 2560, "head_dim": 128, "num_attention_heads": 28,
    "num_key_value_heads": 4, "moe_ffn_hidden_size": 768,
    "moe_num_active_primary_experts": 6, "sliding_window_size": 4096,
    "max_position_embeddings": 16384,
}
CUT_PATTERNED = {
    "reduced": ["num_hidden_layers", "moe_num_primary_experts", "vocab_size",
                "rope_layout", "sliding_window_layout"],
    "model": {**_PATTERNED_WIDTHS, "num_hidden_layers": 4,
              "moe_num_primary_experts": 8, "vocab_size": 18992,
              "rope_layout": [0, 1, 1, 1],
              "sliding_window_layout": [0, 1, 1, 1]},
    "published": {**_PATTERNED_WIDTHS, "num_hidden_layers": 52,
                  "moe_num_primary_experts": 64, "vocab_size": 151936,
                  "rope_layout": [0, 1, 1, 1] * 13,
                  "sliding_window_layout": [0, 1, 1, 1] * 13},
    "deployment": {
        "chips_per_layer": 8,
        "how": "experts and vocabulary rows divided eight ways, attention "
               "whole on every chip; the layers left out lie on further "
               "chips, as the stages of a pipeline",
        "layer_period": 4,
        "leading_dense_layers": 0,
        "kept_layer_ids": [0, 1, 2, 3],
    },
}
#: the further throw-aways: the files of the first, and a cut written into
#: each (the sizes are a fixture for the rule; nothing runs them)
CUT_CONFIGS = {
    name: {**CONFIG, **cut, "name": name,
           "model": {**CONFIG["model"], **cut["model"]}}
    for name, cut in (("lenet_mnist_cut", CUT),
                      ("lenet_mnist_patterned", CUT_PATTERNED))}
CELLS = {"lenet_tiny": "lenet_mnist", "lenet_cut_tiny": "lenet_mnist_cut",
         "lenet_patterned_tiny": "lenet_mnist_patterned"}

REFERENCE = '''
    """LeNet loss in plain float32 jax.numpy (a test's throw-away)."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    TOLERANCE = {"loss_rel": 1e-4, "grad_norm_rel": 1e-3, "grad_rel_err": 1e-3}


    def make_batch(key, n, config):
        kx, ky = jax.random.split(key)
        h, w, c = config["model"]["image"]
        return (jax.random.normal(kx, (n, h, w, c), jnp.float32),
                jax.random.randint(ky, (n,), 0, 10).astype(jnp.int32))


    def _conv(x, p):
        y = lax.conv_general_dilated(
            x, p["kernel"], (1, 1), "VALID",
            dimension_numbers=("NHWC", "HWIO", "NHWC"),
            precision=lax.Precision.HIGHEST)
        return y + p["bias"]


    def _pool(x):
        return lax.reduce_window(x, -jnp.inf, lax.max, (1, 2, 2, 1),
                                 (1, 2, 2, 1), "VALID")


    def loss(params, batch, config):
        x, y = batch
        x = jax.nn.relu(_pool(_conv(x, params["conv1"])))
        x = jax.nn.relu(_pool(_conv(x, params["conv2"])))
        x = x.reshape((x.shape[0], -1))
        for name in ("fc1", "fc2"):
            x = jnp.dot(x, params[name]["kernel"],
                        precision=lax.Precision.HIGHEST) + params[name]["bias"]
        logp = jax.nn.log_softmax(x, axis=-1)
        return -jnp.mean(jnp.take_along_axis(logp, y[:, None], axis=-1))
'''

FLOPS = '''
    def flops_per_sample(config):
        macs = 24 * 24 * 20 * 25 + 8 * 8 * 50 * 500 + 800 * 500 + 500 * 10
        return 6.0 * macs
'''

READER = '''
    def loss_at_close(ctx):
        """The loss of the window's last step."""
        steps = ctx.result["window"].steps
        return steps[-1]["loss"] if steps else None
'''


def add_cell(root: str) -> str:
    """Copy BENCHMARK.json + benchmark/ to ``root`` and add the cells
    of ``CELLS``. Returns ``root``."""
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), root)
    shutil.copytree(
        os.path.join(REPO, "benchmark"), os.path.join(root, "benchmark"),
        ignore=shutil.ignore_patterns("__pycache__"))
    before = _snapshot(root)

    def write(rel, text):
        with open(os.path.join(root, rel), "w") as f:
            f.write(textwrap.dedent(text).lstrip("\n"))

    write("benchmark/configs/lenet_mnist.json", json.dumps(CONFIG, indent=2))
    for name, config in CUT_CONFIGS.items():
        write(f"benchmark/configs/{name}.json", json.dumps(config, indent=2))
    write("benchmark/reference/lenet_mnist.py", REFERENCE)
    write("benchmark/flops/lenet_mnist.py", FLOPS)
    write("benchmark/mixes/train_short.json", json.dumps({
        "driver": "train", "warmup_windows": 2,
        "train_config": {"eval_freq": 0},
    }))
    metric = {"name": "loss_at_close", "unit": "nat", "better": "lower",
              "source": "program_counter", "layer": "train_step",
              "moves": "samples_per_s", "workloads": ["lenet_tiny"]}
    write("benchmark/layer_metrics/loss_at_close.json", json.dumps(
        {**metric, "reader": "benchmark/readers/loss.py:loss_at_close"}))
    write("benchmark/readers/loss.py", READER)

    path = os.path.join(root, "BENCHMARK.json")
    with open(path) as f:
        bench = json.load(f)
    bench["configs"].append({
        "name": "lenet_mnist", "source": CONFIG["source"],
        "file": "benchmark/configs/lenet_mnist.json", "reduced": [],
        "why": "a throw-away for the tests"})
    for name, config in CUT_CONFIGS.items():
        bench["configs"].append({
            "name": name, "source": CONFIG["source"],
            "file": f"benchmark/configs/{name}.json",
            "reduced": config["reduced"], "why": "a throw-away for the tests"})
    for cell, config in CELLS.items():
        bench["workloads"].append({
            "name": cell, "config": config, "traffic": "train_short",
            "chips": 1, "why": "a throw-away for the tests"})
    bench["per_layer"].append(metric)
    with open(path, "w") as f:
        json.dump(bench, f)
    after = _snapshot(root)
    changed = [p for p in before if p != "BENCHMARK.json"
               and before[p] != after.get(p)]
    assert not changed, f"adding a cell edited {changed}"
    return root


def _snapshot(root: str) -> dict:
    out = {}
    for dirpath, _, files in os.walk(root):
        for fn in files:
            p = os.path.join(dirpath, fn)
            with open(p, "rb") as f:
                out[os.path.relpath(p, root)] = f.read()
    return out

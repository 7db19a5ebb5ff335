"""A copy of the benchmark's tree with one throw-away cell ADDED — a
configuration (LeNet on MNIST-shaped synthetic data) with its plain
reference and FLOPs function, a mix, and a per-layer metric with a reader
of its own — as files and ``BENCHMARK.json`` entries only. No file that is
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
    """Copy BENCHMARK.json + benchmark/ to ``root`` and add the cell
    ``lenet_tiny``. Returns ``root``."""
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), root)
    shutil.copytree(
        os.path.join(REPO, "benchmark"), os.path.join(root, "benchmark"),
        ignore=shutil.ignore_patterns("__pycache__"))
    before = _snapshot(root)

    def write(rel, text):
        with open(os.path.join(root, rel), "w") as f:
            f.write(textwrap.dedent(text).lstrip("\n"))

    write("benchmark/configs/lenet_mnist.json", json.dumps(CONFIG, indent=2))
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
    bench["workloads"].append({
        "name": "lenet_tiny", "config": "lenet_mnist",
        "traffic": "train_short", "chips": 1,
        "why": "a throw-away for the tests"})
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

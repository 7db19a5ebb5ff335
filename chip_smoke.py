#!/usr/bin/env python3
"""The quickest proof that the system still starts on the chip.

Drives the main path once, through the entry points a user would call, at
the full width of the two models the trainer was built around (ResNet-18 /
CIFAR-10 b1024 bf16, BERT-base b32 x L512) plus one serving replica, with
synthetic data and seeded random weights, and checks what comes out by the
repo's own means. Run it through the chip tool:

    chiprun -- python3 chip_smoke.py                 # every one-chip leg
    chiprun --chips 4 -- python3 chip_smoke.py --legs four_chips

One process per chip: this parent never imports jax. Every leg is a child
process that exits before the next one starts; the trainer, its resume, the
polling evaluator and the serving replica run in turn, never side by side.

Exit code 0 and, as the LAST line of stdout, one JSON object with exactly
these keys, ``{"ok": true, "device": {"platform", "kind", "count"}}``, when
every leg passed; the line before it (``[chip_smoke] summary: {...}``, also
``chiprun_out/chip_smoke/summary.json``) carries the per-leg record:
seconds, compile vs step time, losses, kernel errors. Any failure — no TPU,
a leg that failed, the script run without the rest of the repo — exits
non-zero, names the leg on stderr and prints no result line. Logs of every
child land under ``chiprun_out/chip_smoke/`` (which the chip tool copies
back).
"""

from __future__ import annotations

import glob
import json
import math
import os
import shutil
import signal
import subprocess
import sys
import time
import urllib.request

REPO = os.path.dirname(os.path.abspath(__file__))
PKG = "pytorch_distributed_nn_tpu"
OUT = os.path.join(REPO, "chiprun_out", "chip_smoke")   # logs (kept)
WORK = os.path.join(REPO, ".chip_smoke_work")           # train dirs (removed)
BUDGET_S = 1150.0  # the contract allows 1200 s, compilation included
MARK = "CHIP_SMOKE_RESULT "

ONE_CHIP_LEGS = ("kernels", "resnet", "resume_eval", "sync_modes", "bert",
                 "serve")
ALL_LEGS = ("preflight",) + ONE_CHIP_LEGS + ("four_chips",)

RESNET = ["train", "--network", "ResNet18", "--dataset", "Cifar10",
          "--batch-size", "1024", "--learning-rate", "0.1",
          "--dtype", "bfloat16", "--data-dir", os.path.join(WORK, "data")]


class LegFailed(Exception):
    pass


# ---------------------------------------------------------------------------
# parent side: children, streams, checks (no jax in this process)
# ---------------------------------------------------------------------------

_children = []  # every Popen this process started and has not reaped
_t0 = time.monotonic()


def _remaining() -> float:
    return BUDGET_S - (time.monotonic() - _t0)


def _spawn(tag: str, argv, env=None):
    """Start one child in its own process group, output to OUT/<tag>.log."""
    log = os.path.join(OUT, f"{tag}.log")
    child_env = dict(os.environ, PYTHONPATH=REPO + os.pathsep
                     + os.environ.get("PYTHONPATH", ""), PYTHONUNBUFFERED="1")
    child_env.update(env or {})
    with open(log, "w") as f:
        p = subprocess.Popen(
            [sys.executable] + list(argv), cwd=REPO, env=child_env,
            stdout=f, stderr=subprocess.STDOUT, start_new_session=True,
        )
    _children.append(p)
    return p, log


def _kill(p) -> None:
    if p.poll() is None:
        try:
            os.killpg(p.pid, signal.SIGKILL)
        except ProcessLookupError:
            pass
        p.wait()
    if p in _children:
        _children.remove(p)


def _tail(log: str, n: int = 40) -> str:
    try:
        with open(log, errors="replace") as f:
            return "".join(f.readlines()[-n:])
    except OSError:
        return ""


def _run(tag: str, argv, env=None, cap_s: float = 600.0) -> str:
    """Run one child to its end; raises LegFailed unless it exits 0.
    Returns the path of its log."""
    p, log = _spawn(tag, argv, env)
    try:
        p.wait(timeout=max(1.0, min(cap_s, _remaining())))
    except subprocess.TimeoutExpired:
        _kill(p)
        raise LegFailed(f"{tag}: timed out (log: {log})\n{_tail(log)}")
    _children.remove(p)
    if p.returncode != 0:
        raise LegFailed(
            f"{tag}: exit code {p.returncode} (log: {log})\n{_tail(log)}"
        )
    return log


def _cli(tag: str, args, env=None, cap_s: float = 600.0) -> str:
    return _run(tag, ["-m", PKG] + list(args), env, cap_s)


def _self(tag: str, leg: str, env=None, cap_s: float = 600.0) -> dict:
    """Run one of this file's own jax-side legs as a child; returns the
    dict it reported."""
    log = _run(tag, [os.path.abspath(__file__), "--child", leg], env, cap_s)
    with open(log, errors="replace") as f:
        for line in reversed(f.readlines()):
            if line.startswith(MARK):
                return json.loads(line[len(MARK):])
    raise LegFailed(f"{tag}: child reported no result (log: {log})")


def _check(cond, msg: str) -> None:
    if not cond:
        raise LegFailed(msg)


def _read_stream(path: str):
    """(manifest, step records, events) of one telemetry JSONL stream."""
    manifest, steps, events = None, [], []
    with open(path) as f:
        for line in f:
            rec = json.loads(line)
            kind = rec.get("kind")
            if kind == "manifest":
                manifest = rec
            elif kind == "step":
                steps.append(rec)
            elif kind == "event":
                events.append(rec)
    return manifest, steps, events


def _check_train_stream(tag, path, device, start_step=0, last_step=None,
                        step_ms=None):
    """The run's own record, not its log: the manifest names the chip and
    carries the step cost, every logged step has a finite loss. Returns
    {first_window_s, step_ms, compile_s} — compile_s is the first log
    window's wall time minus that many steady steps (tracing, lowering
    and XLA compilation or the cache fetch all sit in that window). The
    steady step is the run's last window, or ``step_ms`` for a run that
    has only one."""
    _check(os.path.exists(path), f"{tag}: no telemetry stream at {path}")
    manifest, steps, _ = _read_stream(path)
    _check(manifest is not None, f"{tag}: stream has no manifest")
    backend = (manifest.get("versions") or {}).get("backend")
    _check(backend == device["platform"],
           f"{tag}: manifest backend {backend!r}, not {device['platform']!r}")
    cost = manifest.get("step_cost")
    _check(isinstance(cost, dict) and cost.get("flops", 0) > 0,
           f"{tag}: manifest carries no step_cost: {cost!r}")
    _check(cost.get("device_kind") == device["kind"]
           and cost.get("peak_flops_per_s", 0) > 0,
           f"{tag}: step_cost device_kind {cost.get('device_kind')!r} / "
           f"peak {cost.get('peak_flops_per_s')!r} does not match the "
           f"preflight device {device['kind']!r}")
    _check(manifest.get("start_step") == start_step,
           f"{tag}: started at step {manifest.get('start_step')}, "
           f"expected {start_step}")
    _check(steps, f"{tag}: no step records")
    bad = [s["step"] for s in steps if not math.isfinite(s["loss"])]
    _check(not bad, f"{tag}: non-finite loss at steps {bad}")
    _check(steps[0]["step"] == start_step + 1,
           f"{tag}: first record is step {steps[0]['step']}")
    if last_step is not None:
        _check(steps[-1]["step"] == last_step,
               f"{tag}: last record is step {steps[-1]['step']}, "
               f"expected {last_step}")
    first = [s for s in steps if s["step_time"] == steps[0]["step_time"]]
    first_window_s = steps[0]["step_time"] * len(first)
    out = {"first_window_s": round(first_window_s, 2),
           "final_loss": round(steps[-1]["loss"], 4)}
    if len(first) < len(steps):
        step_ms = out["step_ms"] = round(steps[-1]["step_time"] * 1000, 2)
    if step_ms is not None:
        out["compile_s"] = round(
            first_window_s - step_ms / 1000 * len(first), 2
        )
    return out


def _ir_count(dump_dir: str, needle: str) -> int:
    """Most occurrences of ``needle`` in any one module jax handed to the
    compiler (JAX_DUMP_IR_TO writes each module before the compile-cache
    lookup, so a cache hit dumps the same text as a cold compile)."""
    best = 0
    for path in glob.glob(os.path.join(dump_dir, "*.mlir")):
        with open(path, errors="replace") as f:
            best = max(best, f.read().count(needle))
    return best


# ---------------------------------------------------------------------------
# legs (parent side)
# ---------------------------------------------------------------------------


def leg_preflight(ctx) -> dict:
    info = _self("preflight", "preflight", cap_s=300)
    ctx["device"] = {k: info[k] for k in ("platform", "kind", "count")}
    ctx["cache_dir"] = info["cache_dir"]
    return info


def leg_kernels(ctx) -> dict:
    return _self("kernels", "kernels", cap_s=400)


def leg_resnet(ctx) -> dict:
    d = os.path.join(WORK, "resnet")
    _cli("resnet", RESNET + ["--max-steps", "40", "--log-every", "10",
                             "--eval-freq", "20", "--train-dir", d])
    out = _check_train_stream("resnet", os.path.join(d, "telemetry.jsonl"),
                              ctx["device"], 0, 40)
    for step in (20, 40):
        _check(os.path.exists(os.path.join(d, f"model_step_{step}")),
               f"resnet: checkpoint model_step_{step} was not published")
    return out


def leg_resume_eval(ctx) -> dict:
    """The same command, resumed in a fresh process (the compile cache's
    witness), then the polling evaluator once the trainer has exited."""
    d = os.path.join(WORK, "resnet")
    stream = os.path.join(d, "telemetry.jsonl")
    os.replace(stream, stream + ".cold")  # one stream per run
    _cli("resume", RESNET + ["--max-steps", "50", "--log-every", "10",
                             "--eval-freq", "20", "--resume",
                             "--train-dir", d])
    cold = ctx["results"]["resnet"]
    out = _check_train_stream("resume", stream, ctx["device"], 40, 50,
                              step_ms=cold["step_ms"])
    out["cold_compile_s"] = cold["compile_s"]
    _check(out["first_window_s"] < cold["first_window_s"],
           f"resume: first window {out['first_window_s']}s is not below "
           f"the cold run's {cold['first_window_s']}s — no compile-cache "
           f"hit in a fresh process (cache: {ctx['cache_dir']})")
    log = _cli("evaluator", [
        "evaluator", "--model-dir", d, "--network", "ResNet18",
        "--dataset", "Cifar10", "--eval-freq", "20", "--max-evals", "1",
        "--eval-interval", "1", "--timeout", "240",
        "--data-dir", os.path.join(WORK, "data"),
    ], cap_s=300)
    with open(log, errors="replace") as f:
        text = f.read()
    # Evaluator.evaluate_checkpoint runs verify_checkpoint before it
    # restores; a checkpoint that fails it is logged as corrupt and skipped
    _check("Evaluator evaluating step 20:" in text and "corrupt" not in text,
           f"evaluator: step-20 checkpoint was not verified and evaluated "
           f"(log: {log})\n{_tail(log)}")
    return out


def leg_sync_modes(ctx) -> dict:
    """The TPU-only gradient-sync branches: the Pallas PRNG quantizer
    inside shard_map (int8) and lax.approx_max_k (topk)."""
    n_agg = str(max(1, ctx["device"]["count"] - 1))
    out = {}
    for comp, needle in (("int8", "tpu_custom_call"), ("topk", "ApproxTopK")):
        d = os.path.join(WORK, f"sync_{comp}")
        ir = os.path.join(d, "ir")
        stream = os.path.join(d, "telemetry.jsonl")
        os.makedirs(d)
        _cli(f"sync_{comp}", RESNET + [
            "--max-steps", "6", "--log-every", "3", "--sync-mode", "ps",
            "--num-aggregate", n_agg, "--compress-grad", comp,
            "--metrics-path", stream, "--train-dir", d,
        ], env={"JAX_DUMP_IR_TO": ir})
        rec = _check_train_stream(f"sync_{comp}", stream, ctx["device"], 0, 6)
        rec["calls"] = _ir_count(ir, needle)
        _check(rec["calls"] > 0,
               f"sync_{comp}: no {needle} in any module handed to the "
               f"compiler — the TPU branch did not run (IR: {ir})")
        shutil.rmtree(ir, ignore_errors=True)
        out[comp] = rec
    return out


def leg_bert(ctx) -> dict:
    d = os.path.join(WORK, "bert")
    ir = os.path.join(d, "ir")
    stream = os.path.join(d, "telemetry.jsonl")
    os.makedirs(d)
    _cli("bert", [
        "train", "--network", "BertBase", "--dataset", "MLMSynth",
        "--optimizer", "adam", "--learning-rate", "1e-4",
        "--batch-size", "32", "--seq-len", "512", "--dtype", "bfloat16",
        "--attn-impl", "pallas", "--fused-ln", "--max-steps", "10",
        "--log-every", "5", "--test-batch-size", "32", "--eval-batches", "2",
        "--metrics-path", stream, "--train-dir", d,
    ], env={"JAX_DUMP_IR_TO": ir})
    out = _check_train_stream("bert", stream, ctx["device"], 0, 10)
    # 12 layers x (flash fwd + dq + dkv) + 26 LayerNorms x (fwd + bwd): the
    # train step must hold all of them as Mosaic custom calls — an
    # interpret-mode kernel or fused_layer_norm's jnp branch leaves none
    calls = _ir_count(ir, "tpu_custom_call")
    out["mosaic_calls"] = calls
    _check(calls >= 12 * 3 + 26 * 2,
           f"bert: only {calls} Mosaic custom calls in the train step, "
           f"expected 88 (flash fwd+bwd per layer, LayerNorm fwd+bwd per "
           f"norm; IR: {ir})")
    shutil.rmtree(ir, ignore_errors=True)
    return out


def _http(url: str, body=None, timeout: float = 60.0):
    data = None if body is None else json.dumps(body).encode()
    req = urllib.request.Request(url, data=data)
    with urllib.request.urlopen(req, timeout=timeout) as r:
        return r.status, json.loads(r.read())


def leg_serve(ctx) -> dict:
    d = os.path.join(WORK, "gpt")
    art = os.path.join(WORK, "gpt_artifact")
    port_file = os.path.join(WORK, "serve_port.json")
    _cli("serve_train", [
        "train", "--network", "GptMini", "--dataset", "MLMSynth",
        "--optimizer", "adam", "--learning-rate", "1e-3",
        "--batch-size", "32", "--max-steps", "4", "--eval-freq", "4",
        "--test-batch-size", "32", "--eval-batches", "1", "--train-dir", d,
    ])
    _check_train_stream("serve_train", os.path.join(d, "telemetry.jsonl"),
                        ctx["device"], 0, 4)
    _cli("serve_export", ["serve", "export", "--train-dir", d, "--out", art],
         cap_s=200)
    p, log = _spawn("serve_run", [
        "-m", PKG, "serve", "run", "--artifact", art, "--port", "0",
        "--port-file", port_file,
    ])
    try:
        t_up = time.monotonic()
        while not os.path.exists(port_file):
            _check(p.poll() is None,
                   f"serve: replica exited with {p.returncode} before it "
                   f"was ready (log: {log})\n{_tail(log)}")
            _check(time.monotonic() - t_up < min(400, _remaining()),
                   f"serve: replica not ready in time (log: {log})\n"
                   f"{_tail(log)}")
            time.sleep(0.5)
        warm_s = time.monotonic() - t_up
        with open(port_file) as f:
            addr = json.load(f)
        base = f"http://{addr['host']}:{addr['port']}"
        new_tokens = []
        for prompt in ([1, 2, 3, 4], list(range(5, 45)), [7] * 90):
            status, doc = _http(base + "/v1/generate",
                                {"inputs": [prompt], "max_new_tokens": 8})
            toks = doc["outputs"][0]
            _check(status == 200 and doc["new_tokens"] == [len(toks)]
                   and 0 < len(toks) <= 8
                   and all(isinstance(t, int) and 0 <= t < 1024
                           for t in toks),
                   f"serve: bad /v1/generate answer {status}: {doc}")
            new_tokens.append(len(toks))
        _, stats = _http(base + "/stats")
        _check(stats["served"] == 3 and stats["retraces"] == 0,
               f"serve: served={stats['served']} "
               f"retraces={stats['retraces']} after three requests")
        os.kill(addr["pid"], signal.SIGTERM)  # the zero-downtime drain
        try:
            p.wait(timeout=60)
        except subprocess.TimeoutExpired:
            raise LegFailed(f"serve: no exit 60 s after SIGTERM (log: {log})")
        _check(p.returncode == 0,
               f"serve: drain exited {p.returncode} (log: {log})\n"
               f"{_tail(log)}")
    finally:
        _kill(p)
    manifest, _, events = _read_stream(
        os.path.join(art, "serve", "serving.jsonl"))
    backend = (manifest.get("versions") or {}).get("backend")
    _check(backend == ctx["device"]["platform"],
           f"serve: replica's stream manifest says backend {backend!r}")
    _check(any(e.get("type") == "drain" for e in events),
           "serve: no drain event in the replica's stream")
    return {"warmup_s": round(warm_s, 1), "new_tokens": new_tokens,
            "retraces": stats["retraces"]}


def leg_four_chips(ctx) -> dict:
    if ctx["device"]["count"] < 4:
        print("[chip_smoke] four_chips: not run — "
              f"{ctx['device']['count']} device(s) visible", flush=True)
        return {"skipped": f"{ctx['device']['count']} device(s) visible"}
    ir = os.path.join(WORK, "four_ir")
    return _self("four_chips", "four_chips", env={"JAX_DUMP_IR_TO": ir},
                 cap_s=900)


LEGS = {
    "preflight": leg_preflight, "kernels": leg_kernels, "resnet": leg_resnet,
    "resume_eval": leg_resume_eval, "sync_modes": leg_sync_modes,
    "bert": leg_bert, "serve": leg_serve, "four_chips": leg_four_chips,
}


def verdict_line(device: dict) -> str:
    """The last line of a passing run: exactly these keys and no others —
    the checker that reads it refuses anything else. The per-leg record
    goes on the line before it and into summary.json."""
    return json.dumps({"ok": True, "device": {
        "platform": str(device["platform"]), "kind": str(device["kind"]),
        "count": int(device["count"]),
    }})


def main(argv) -> int:
    legs = list(ALL_LEGS)
    if argv[:1] == ["--legs"] and len(argv) == 2:
        legs = ["preflight"] + [x for x in argv[1].split(",")
                                if x != "preflight"]
    elif argv:
        print("usage: chip_smoke.py [--legs a,b]   legs: "
              + ",".join(ALL_LEGS), file=sys.stderr)
        return 2
    unknown = [x for x in legs if x not in LEGS]
    if unknown:
        print(f"unknown leg(s) {unknown}; legs: {','.join(ALL_LEGS)}",
              file=sys.stderr)
        return 2
    if "resume_eval" in legs and "resnet" not in legs:
        legs.insert(legs.index("resume_eval"), "resnet")
    shutil.rmtree(WORK, ignore_errors=True)
    shutil.rmtree(OUT, ignore_errors=True)
    os.makedirs(WORK)
    os.makedirs(OUT)
    ctx = {"results": {}}
    try:
        for name in legs:
            t = time.monotonic()
            info = LEGS[name](ctx)
            info = dict(info, seconds=round(time.monotonic() - t, 1))
            ctx["results"][name] = info
            print(f"[chip_smoke] {name}: ok {json.dumps(info)}", flush=True)
    except LegFailed as e:
        print(f"[chip_smoke] FAILED in leg {name!r}: {e}", file=sys.stderr)
        return 1
    finally:
        for p in list(_children):
            _kill(p)
        shutil.rmtree(WORK, ignore_errors=True)
    summary = {
        "ok": True,
        "device": ctx["device"],
        "legs": ctx["results"],
        "seconds": round(time.monotonic() - _t0, 1),
        "cache_dir": ctx["cache_dir"],
    }
    with open(os.path.join(OUT, "summary.json"), "w") as f:
        json.dump(summary, f, indent=2)
    # the per-leg record first, the verdict as the last line
    print("[chip_smoke] summary: " + json.dumps(summary), flush=True)
    print(verdict_line(ctx["device"]), flush=True)
    return 0


# ---------------------------------------------------------------------------
# child side: the legs that are library calls (these import jax)
# ---------------------------------------------------------------------------


def _report(info: dict) -> None:
    print(MARK + json.dumps(info), flush=True)


def child_preflight() -> int:
    """A TPU or nothing; then the versions, the native libraries rebuilt
    from source, and the compile cache's place."""
    import jax
    import jaxlib

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: JAX found no TPU — jax.devices()[0] is "
              f"{dev.platform}:{dev.device_kind}. Run this through the chip "
              f"tool; nothing here falls back to the CPU.", flush=True)
        return 1
    from importlib.metadata import version

    from pytorch_distributed_nn_tpu.analysis.calibration import (
        default_profile,
    )
    from pytorch_distributed_nn_tpu.utils import compile_cache

    # raises for a device_kind the peak table does not hold
    peak = default_profile(dev.platform, dev.device_kind).peak_flops_per_s
    subprocess.run(["make", "-C", os.path.join(REPO, "native"), "clean",
                    "all"], check=True)
    from pytorch_distributed_nn_tpu.data import native_augment
    from pytorch_distributed_nn_tpu.ops import host_codec

    if not (host_codec.available() and native_augment.available()):
        print("chip_smoke: native/*.so built but did not load", flush=True)
        return 1
    _report({
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices()), "jax": jax.__version__,
        "jaxlib": jaxlib.__version__, "libtpu": version("libtpu"),
        "python": sys.version.split()[0], "peak_flops_per_s": peak,
        "cache_dir": compile_cache.configure(),
        "cache_env": os.environ.get(compile_cache.ENV_VAR),
    })
    return 0


def _compiled(fn, *args):
    """Compile ``fn`` for the chip at HIGHEST matmul precision, insist that
    the optimized HLO holds a Mosaic custom call (so the kernel ran
    compiled, not interpreted), run it. Returns the outputs."""
    import jax

    with jax.default_matmul_precision("highest"):
        exe = jax.jit(fn).lower(*args).compile()
    if 'custom_call_target="tpu_custom_call"' not in exe.as_text():
        raise AssertionError(
            f"{getattr(fn, '__name__', fn)}: no Mosaic custom call in the "
            "compiled HLO — the kernel did not take the compiled path"
        )
    return exe(*args)


def child_kernels() -> int:
    """What the CLI cannot reach, once each against the jnp reference and
    within the tolerance the CPU tests already use
    (tests/test_pallas_kernels.py, tests/test_generate.py). Kernel and
    reference both run at HIGHEST matmul precision: at the TPU default,
    Mosaic and XLA alike round f32 matmul operands to bf16 (3.1e-3 at
    GptMini's decode head on a v5e), and the comparison would measure
    that rounding instead of the kernel."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from pytorch_distributed_nn_tpu.models.transformer import (
        decode_attention,
        decode_attention_fast,
        full_attention,
    )
    from pytorch_distributed_nn_tpu.ops import pallas_kernels as pk
    from pytorch_distributed_nn_tpu.utils import compile_cache

    compile_cache.configure()
    assert not pk._interpret(), "kernels leg reached interpret mode"
    errs, failed = {}, []

    def close(name, got, want, tol):
        got = np.asarray(got, np.float32)
        want = np.asarray(want, np.float32)
        err = float(np.max(np.abs(got - want) / (1.0 + np.abs(want))))
        errs[name] = float(f"{err:.3g}")
        if not err <= tol:  # also catches NaN
            failed.append(f"{name}: error {err:.3g} > tolerance {tol}")

    def ref(fn, *args):
        with jax.default_matmul_precision("highest"):
            return jax.jit(fn)(*args)

    def rand(seed, shape, dtype=jnp.float32):
        return jax.random.normal(jax.random.PRNGKey(seed), shape, dtype)

    # -- decode attention: GptMini's head and a deployable one -------------
    for name, (B, S, H, D), reference in (
        ("decode_d32", (8, 128, 4, 32), decode_attention),
        ("decode_d128", (8, 2048, 16, 128), decode_attention_fast),
    ):
        q, k, v = rand(0, (B, 1, H, D)), rand(1, (B, S, H, D)), \
            rand(2, (B, S, H, D))
        pos = (jnp.arange(B, dtype=jnp.int32) * (S // B) + S // B - 1)
        close(name, _compiled(pk.pallas_decode_attention, q, k, v, pos),
              ref(reference, q, k, v, pos), 1e-5)

    # -- flash attention fwd+bwd: resident (L=512) and streamed (L=16384) --
    def flash_loss(attn):
        def loss(q, k, v, mask):
            return (attn(q, k, v, mask) ** 2).sum()
        return jax.value_and_grad(loss, argnums=(0, 1, 2))

    for name, (B, L, H, D), tol in (
        ("flash_resident_L512", (2, 512, 12, 64), 2e-4),
        ("flash_streamed_L16384", (1, 16384, 1, 64), 5e-4),
    ):
        assert pk._resident(L, D) == ("resident" in name)
        q, k, v = (rand(i, (B, L, H, D)) for i in range(3))
        mask = jnp.ones((B, L)).at[:, L - L // 8:].set(0.0)
        got = _compiled(flash_loss(pk.pallas_attention), q, k, v, mask)
        want = ref(flash_loss(full_attention), q, k, v, mask)
        close(name + "_loss", got[0] / want[0], 1.0, tol)
        for g, w, leaf in zip(got[1], want[1], "qkv"):
            close(f"{name}_d{leaf}", g, w, tol)

    # -- fused LayerNorm fwd+bwd at BERT-base's activation shape ----------
    def ln_ref(x, g, b):
        xf = x.astype(jnp.float32)
        mu = jnp.mean(xf, axis=-1, keepdims=True)
        xc = xf - mu
        var = jnp.mean(xc * xc, axis=-1, keepdims=True)
        return xc * jax.lax.rsqrt(var + 1e-6) * g + b

    x, g, b = rand(3, (32, 512, 768)), rand(4, (768,)) + 1.0, rand(5, (768,))
    dy = rand(6, (32, 512, 768))

    def ln_loss(fn):
        def loss(x, g, b):
            return jnp.sum(fn(x, g, b) * dy)
        return jax.value_and_grad(loss, argnums=(0, 1, 2))

    close("ln_y", _compiled(pk.fused_layer_norm, x, g, b),
          ref(ln_ref, x, g, b), 2e-5)
    got = _compiled(ln_loss(pk.fused_layer_norm), x, g, b)
    want = ref(ln_loss(ln_ref), x, g, b)
    close("ln_loss", got[0] / want[0], 1.0, 5e-5)
    close("ln_dx", got[1][0], want[1][0], 5e-5)
    # dgamma/dbeta sum over all 16384 rows; the CPU test's 5e-5 is for
    # <= 1024 rows, and f32 summation error grows as sqrt(rows)
    for a, w, leaf in zip(got[1][1:], want[1][1:], ("gamma", "beta")):
        close(f"ln_d{leaf}", a, w, 5e-5 * math.sqrt(16384 / 1024))

    # -- standalone int8 codec on the hardware PRNG ------------------------
    x = rand(7, (512, 1024))
    q, scale = _compiled(lambda x: pk.quantize_int8(x, 7), x)
    assert q.dtype == jnp.int8
    back = _compiled(pk.dequantize_int8, q, scale)
    step = float(jnp.max(jnp.abs(x))) / 127.0
    err = float(jnp.max(jnp.abs(back - x)))
    errs["int8_roundtrip_steps"] = float(f"{err / step:.3g}")
    assert err <= step * 1.001, f"int8 roundtrip error {err} > step {step}"
    half = jnp.concatenate([jnp.full((8, 128), 1.5 / 127.0),
                            jnp.full((1, 128), 3.0)])
    means = [
        float(jnp.mean(_compiled(
            lambda x, s=seed: pk.quantize_int8(x, s), half
        )[0][:-1].astype(jnp.float32)))
        for seed in range(8)
    ]
    errs["int8_halfway_mean"] = float(f"{np.mean(means):.3g}")
    assert 0.3 < np.mean(means) < 0.7, f"stochastic rounding biased: {means}"
    assert len(set(means)) > 1, "the PRNG ignores its seed"
    flat = rand(8, (1, 300000))  # > _QUANT_CHUNK: the chunked grid
    s = float(jnp.max(jnp.abs(flat))) / 127.0
    qs = _compiled(lambda x: pk.quantize_int8_scaled(x, 11, s), flat)
    err = float(jnp.max(jnp.abs(qs.astype(jnp.float32) - flat / s)))
    errs["int8_scaled_steps"] = float(f"{err:.3g}")
    assert err <= 1.0001, f"scaled quantizer off by {err} steps"
    print(f"kernels: {errs}", flush=True)
    assert not failed, failed
    _report({"max_rel_err": errs})
    return 0


def child_four_chips() -> int:
    """Data, tensor and sequence parallelism on four real chips: the
    ResNet run at dp=4, then BERT-base under tp=2 x sp=2 with ring and
    with Ulysses attention. Library-level (the Trainer a ``train`` command
    builds) because the evidence is in the live arrays."""
    import jax
    import numpy as np

    from pytorch_distributed_nn_tpu.training.trainer import (
        TrainConfig,
        Trainer,
    )
    from pytorch_distributed_nn_tpu.utils import compile_cache

    compile_cache.configure()
    devices = jax.devices()
    assert len(devices) >= 4, devices
    ir = os.environ["JAX_DUMP_IR_TO"]
    out = {}
    bert = dict(network="BertBase", dataset="MLMSynth", optimizer="adam",
                lr=1e-4, batch_size=32, seq_len=512, dtype="bfloat16",
                test_batch_size=32, eval_batches=1, max_steps=4,
                log_every=2, tensor_parallel=2, seq_parallel=2)
    for name, cfg, needle in (
        ("resnet_dp4", dict(network="ResNet18", dataset="Cifar10",
                            batch_size=1024, lr=0.1, dtype="bfloat16",
                            max_steps=20, log_every=10),
         "stablehlo.all_reduce"),
        ("bert_tp2_sp2_ring", dict(bert, seq_attn="ring"),
         "stablehlo.collective_permute"),
        ("bert_tp2_sp2_ulysses", dict(bert, seq_attn="ulysses"),
         "stablehlo.all_to_all"),
    ):
        for f in glob.glob(os.path.join(ir, "*.mlir")):
            os.remove(f)
        trainer = Trainer(TrainConfig(
            train_dir=os.path.join(WORK, name),
            data_dir=os.path.join(WORK, "data"), **cfg,
        ))
        try:
            assert trainer.mesh.devices.size == len(devices), trainer.mesh
            history = trainer.train()
            trainer.evaluate()
            losses = [r["loss"] for r in history]
            assert losses and np.isfinite(losses).all(), (name, losses)
            # state really spread: every leaf lives on all the devices,
            # and under tp the sharded leaves hold DIFFERENT slices
            leaves = jax.tree.leaves(trainer.state.params)
            on = {s.device for leaf in leaves
                  for s in leaf.addressable_shards}
            assert on == set(devices), f"{name}: params on {on}"
            split = sum(
                len({str(s.index) for s in leaf.addressable_shards}) > 1
                for leaf in leaves
            )
            assert (split > 0) == ("tp2" in name), (name, split)
            in_use = [d.memory_stats()["bytes_in_use"] for d in devices]
            assert all(b > 0 for b in in_use), f"{name}: memory {in_use}"
        finally:
            trainer.close()
        calls = _ir_count(ir, needle)
        assert calls > 0, f"{name}: no {needle} in the step handed to XLA"
        out[name] = {
            "final_loss": round(losses[-1], 4),
            "step_ms": round(history[-1]["step_time"] * 1000, 2),
            "mesh": dict(trainer.mesh.shape), "sharded_leaves": split,
            "collective": needle, "collective_calls": calls,
            "min_bytes_in_use": min(in_use),
        }
        print(f"[four_chips] {name}: {out[name]}", flush=True)
    _report(out)
    return 0


CHILDREN = {"preflight": child_preflight, "kernels": child_kernels,
            "four_chips": child_four_chips}

if __name__ == "__main__":
    if sys.argv[1:2] == ["--child"]:
        sys.exit(CHILDREN[sys.argv[2]]())
    sys.exit(main(sys.argv[1:]))

"""Explain the b32->b64 per-token throughput regression on BERT-base.

Round-4 finding (a builder capture of July 2026 whose record was removed
with the environment it came from; not re-measured): at L=512 the b64
step ran ~5% SLOWER per token than b32 (110.8k vs 116.5k tok/s) —
and b64 is exactly the microbatch geometry the b256 grad-accum
convergence runs use, so the anomaly taxes the flagship runs.

This tool discriminates the candidate causes by measuring, for each
batch size, BOTH the wall step time (bench-style amortized window) and
the on-device step time plus per-op-family breakdown (xplane trace):

- host/dispatch overhead: wall grows while device time doesn't;
- a family whose per-token device time grows with B (layout copies,
  bandwidth-bound tail) names the regressing component directly;
- uniform per-family scaling instead points at clock/occupancy effects.

Writes docs/artifacts/b64_anomaly_r05.json and prints a per-family
per-token table. Run on the real chip (no platform forcing).
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import sys
import tempfile
import time

# TF's generated xplane protos need the pure-python protobuf impl on
# this image (same guard as tools/xplane_summary.py)
os.environ.setdefault("PROTOCOL_BUFFERS_PYTHON_IMPLEMENTATION", "python")

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)


def build_step(B, L=512, model_name="BertBase", attn_impl="pallas",
               fused_ln=False):
    import jax
    import jax.numpy as jnp

    from pytorch_distributed_nn_tpu.data.text import MLMBatches
    from pytorch_distributed_nn_tpu.models import build_model
    from pytorch_distributed_nn_tpu.ops.metrics import (
        make_global_masked_cross_entropy,
        make_global_mlm_metrics,
    )
    from pytorch_distributed_nn_tpu.ops.pallas_kernels import pallas_attention
    from pytorch_distributed_nn_tpu.optim import build_optimizer
    from pytorch_distributed_nn_tpu.parallel import (
        batch_sharding,
        make_grad_sync,
        make_mesh,
    )
    from pytorch_distributed_nn_tpu.parallel.mesh import DATA_AXIS
    from pytorch_distributed_nn_tpu.training import (
        build_train_step,
        create_train_state,
    )

    mesh = make_mesh(1)
    kw = {"attn_fn": pallas_attention} if attn_impl == "pallas" else {}
    if fused_ln:
        kw["fused_ln"] = True
    model = build_model(model_name, 10, dtype=jnp.bfloat16, **kw)
    opt = build_optimizer("adam", 1e-4)
    sync = make_grad_sync("allreduce")
    state = create_train_state(
        model, opt, sync, jax.random.PRNGKey(0), (L,), num_replicas=1,
        input_dtype=jnp.int32,
    )
    step = build_train_step(
        model, opt, sync, mesh,
        loss_fn=make_global_masked_cross_entropy(DATA_AXIS),
        metrics_fn=make_global_mlm_metrics(DATA_AXIS),
        donate=False,  # state reused across repeated timing calls
    )
    data = MLMBatches(vocab_size=model.config.vocab_size, seq_len=L,
                      batch_size=B)
    xb, yb = next(data)
    sh = batch_sharding(mesh)
    batch = (jax.device_put(jnp.asarray(xb), sh),
             jax.device_put(jnp.asarray(yb), sh))
    return step, state, batch


def measure(B, L, inner, windows, profile_steps, top,
            model_name="BertBase", attn_impl="pallas", fused_ln=False):
    import jax

    from pytorch_distributed_nn_tpu.utils.profiling import (
        device_step_time_ms,
        summarize_xplane,
    )

    step, state, batch = build_step(B, L, model_name, attn_impl, fused_ln)
    key = jax.random.PRNGKey(1)

    def run(n):
        s, m = state, None
        for i in range(n):
            s, m = step(state, batch, jax.random.fold_in(key, i))
        # consume the final metrics so nothing is dead code
        return float(jax.tree.leaves(m)[0])

    run(2)  # compile + warm
    # wall: amortized windows, median — one fetch closes each window
    walls = []
    for _ in range(windows):
        t0 = time.perf_counter()
        run(inner)
        walls.append((time.perf_counter() - t0) / inner * 1000)
    wall_ms = statistics.median(walls)

    trace_dir = tempfile.mkdtemp(prefix=f"b64anom_b{B}_")
    with jax.profiler.trace(trace_dir):
        run(profile_steps)
    dev_ms = device_step_time_ms(trace_dir, profile_steps)
    # {family: device_ms_per_step} from the (single) TPU plane; the
    # summarizer already folds the tail into an "(other N ops)" row so
    # the values sum to the true device total
    fam_ms = {}
    for _plane, ops in summarize_xplane(trace_dir, top=top).items():
        fam_ms = {
            o.name: round(o.total_ms / profile_steps, 3) for o in ops
        }
        break
    return {
        "batch": B,
        "seq_len": L,
        "wall_ms": round(wall_ms, 2),
        "wall_spread_ms": round(max(walls) - min(walls), 2),
        "device_ms": None if dev_ms is None else round(dev_ms, 2),
        "tokens_per_sec": round(B * L / wall_ms * 1000, 1),
        "per_family_ms": fam_ms,
        "trace_dir": trace_dir,
    }


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--batches", default="32,48,64,96,128")
    p.add_argument("--seq-len", type=int, default=512)
    p.add_argument("--model", default="BertBase")
    p.add_argument("--attn-impl", choices=["pallas", "full"],
                   default="pallas",
                   help="'full' for CPU smoke runs (Pallas is TPU-only)")
    p.add_argument("--fused-ln", action="store_true",
                   help="A/B lever: Pallas one-pass LayerNorm (the "
                        "bandwidth-tail experiment)")
    p.add_argument("--inner", type=int, default=30)
    p.add_argument("--windows", type=int, default=5)
    p.add_argument("--profile-steps", type=int, default=10)
    p.add_argument("--top", type=int, default=12)
    p.add_argument("--out",
                   default=os.path.join(REPO, "docs", "artifacts",
                                        "b64_anomaly_r05.json"))
    args = p.parse_args(argv)

    rows = []
    for B in (int(b) for b in args.batches.split(",")):
        try:
            r = measure(B, args.seq_len, args.inner, args.windows,
                        args.profile_steps, args.top,
                        args.model, args.attn_impl, args.fused_ln)
        except Exception as e:  # OOM at large B must not lose the rest
            r = {"batch": B, "error": f"{type(e).__name__}: {e}"}
        rows.append(r)
        print(json.dumps(r), file=sys.stderr, flush=True)

    ok = [r for r in rows if "error" not in r]
    if len(ok) >= 2:
        # per-token per-family comparison vs the smallest batch: the
        # family whose per-token cost GROWS with B is the regression
        base = ok[0]
        print(f"\nper-token scaling vs b{base['batch']} "
              "(ns/token; >1.0x = regressing family):")
        fams = sorted({f for r in ok for f in r["per_family_ms"]})
        for f in fams:
            cells = []
            b0 = base["per_family_ms"].get(f)
            for r in ok:
                ms = r["per_family_ms"].get(f)
                if ms is None:
                    cells.append("-")
                    continue
                ns_tok = ms * 1e6 / (r["batch"] * r["seq_len"])
                rel = ("" if not b0 else
                       f" ({ms / (b0 * r['batch'] / base['batch']):.2f}x)")
                cells.append(f"{ns_tok:.1f}{rel}")
            print(f"  {f:<28} " + "  ".join(cells))
    os.makedirs(os.path.dirname(args.out), exist_ok=True)
    with open(args.out, "w") as f:
        json.dump({"rows": rows}, f, indent=1)
    print(f"wrote {args.out}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())

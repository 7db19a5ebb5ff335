"""Generative decode path (serving/generate/, ISSUE 13).

The load-bearing invariant is pinned first: KV-cache decode is
BITWISE-equal to a full-recompute forward at every generated position —
the cache is an optimization, never an approximation. Around it: slot
allocation/eviction and the swap fence in the pool ledger, stop-token
and max_new_tokens handling, continuous-batch join/leave with the
zero-retrace assertion, the HTTP ``/v1/generate`` end-to-end, the
generation observability block and its compare gate, and the decode
cost model's arithmetic.
"""

import json
import os
import threading
import time
import urllib.request

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from pytorch_distributed_nn_tpu.models import build_model
from pytorch_distributed_nn_tpu.parallel.partitioning import unbox
from pytorch_distributed_nn_tpu.serving.generate import (
    GenerateScheduler,
    GenerativeEngine,
    KVCachePool,
    PoolExhausted,
)
from pytorch_distributed_nn_tpu.serving.loadgen import (
    make_tiny_decoder_artifact,
    sample_prompts,
    serving_telemetry,
)


@pytest.fixture(scope="module")
def decoder_artifact(tmp_path_factory):
    root = tmp_path_factory.mktemp("gen_artifact")
    return make_tiny_decoder_artifact(str(root))


@pytest.fixture(scope="module")
def engine(decoder_artifact):
    eng = GenerativeEngine(
        decoder_artifact, batch_buckets=(1, 2, 4), seq_buckets=(32, 64),
        pool_slots=6,
    )
    eng.warmup()
    return eng


def _scheduler(engine, telemetry=None, **kw):
    return GenerateScheduler(engine, telemetry=telemetry, **kw)


# ---------------------------------------------------------------------------
# KV-cache decode == full recompute, at every position
# ---------------------------------------------------------------------------

#: float32 ulps, at the scale of the largest logit, by which a cached
#: decode step may differ from the full forward (set from the dtype, not
#: from a run: jax 0.9.0 on the CPU differs by about 3)
DECODE_ULPS = 8


def test_kv_decode_equals_full_recompute_to_a_few_ulp():
    """Model-level pin: prefill + per-position cached decode reproduces
    the full causal forward's last-position logits and, exactly, its
    greedy token sequence.

    Not bit for bit: the decode step runs its matmuls on one row, and its
    attention on a broadcast query against the padded cache (S slots, the
    unused ones masked), where the full forward runs S rows. The terms
    summed are the same; the order XLA sums them in depends on the
    shapes, and since jax 0.9.0 the float32 results differ in their last
    bits (1.9e-7 at logits of order 0.5). What must hold is that the
    difference stays at rounding (``DECODE_ULPS`` ulp of the logits'
    scale) and never changes which token is chosen."""
    m = build_model("GptTiny")
    cfg = m.config
    rng = jax.random.PRNGKey(0)
    variables = unbox(
        m.init({"params": rng, "dropout": rng},
               jnp.zeros((1, 8), jnp.int32), train=False)
    )
    params = variables["params"]
    prompt = [5, 7, 9, 2]
    S = 32
    H, D = cfg.num_heads, cfg.d_model // cfg.num_heads

    buf = np.zeros((1, 8), np.int32)
    buf[0, : len(prompt)] = prompt
    mask = (np.arange(8)[None, :] < len(prompt)).astype(np.int32)
    logits, kvs = m.apply(
        {"params": params}, jnp.asarray(buf), mask=jnp.asarray(mask),
        return_kv=True,
    )
    cache = tuple(
        (
            jnp.zeros((1, S, H, D), jnp.float32).at[:, :8].set(kv[0]),
            jnp.zeros((1, S, H, D), jnp.float32).at[:, :8].set(kv[1]),
        )
        for kv in kvs
    )
    seq = list(prompt)
    tok = int(np.argmax(np.asarray(logits)[0, len(prompt) - 1]))
    for step in range(6):
        pos = len(prompt) + step
        dec, cache = m.apply(
            {"params": params}, jnp.asarray([[tok]], np.int32),
            cache=cache, positions=jnp.asarray([pos], np.int32),
        )
        seq.append(tok)
        full = np.zeros((1, S), np.int32)
        full[0, : len(seq)] = seq
        fmask = (np.arange(S)[None, :] < len(seq)).astype(np.int32)
        ref = m.apply({"params": params}, jnp.asarray(full),
                      mask=jnp.asarray(fmask))
        ref_row = np.asarray(ref)[0, len(seq) - 1]
        got = np.asarray(dec)[0]
        np.testing.assert_allclose(
            got, ref_row, rtol=0.0,
            atol=DECODE_ULPS * np.finfo(np.float32).eps
            * float(np.abs(ref_row).max()),
            err_msg=f"decode diverged from recompute at position {pos}",
        )
        tok = int(np.argmax(got))
        assert tok == int(np.argmax(ref_row)), (
            f"greedy token differs from recompute at position {pos}")


def test_engine_generation_matches_full_recompute(engine,
                                                  decoder_artifact):
    """End-to-end pin on the ENGINE path (pools, insert, padded decode
    batches): greedy generation through the scheduler equals a greedy
    full-recompute loop token for token."""
    from pytorch_distributed_nn_tpu.serving.artifact import load_artifact

    sched = _scheduler(engine)
    prompt = np.asarray([3, 1, 4, 1, 5, 9, 2, 6], np.int32)
    try:
        got = sched.submit(prompt, max_new_tokens=6,
                           timeout_s=30.0).wait(60.0)
    finally:
        sched.close()
    _, params, _ = load_artifact(decoder_artifact)
    seq = [int(t) for t in prompt]
    for _ in range(6):
        buf = np.zeros((1, 32), np.int32)
        buf[0, : len(seq)] = seq
        mask = (np.arange(32)[None, :] < len(seq)).astype(np.int32)
        logits = engine.model.apply(
            {"params": params}, jnp.asarray(buf), mask=jnp.asarray(mask)
        )
        seq.append(int(np.argmax(np.asarray(logits)[0, len(seq) - 1])))
    assert got == seq[len(prompt):]


def test_pallas_decode_attention_matches_reference():
    from pytorch_distributed_nn_tpu.models.transformer import (
        decode_attention,
        decode_attention_fast,
    )
    from pytorch_distributed_nn_tpu.ops.pallas_kernels import (
        pallas_decode_attention,
    )

    B, S, H, D = 3, 16, 4, 16
    q = jax.random.normal(jax.random.PRNGKey(0), (B, 1, H, D))
    k = jax.random.normal(jax.random.PRNGKey(1), (B, S, H, D))
    v = jax.random.normal(jax.random.PRNGKey(2), (B, S, H, D))
    pos = jnp.asarray([0, 7, 15], jnp.int32)
    ref = np.asarray(decode_attention(q, k, v, pos))
    np.testing.assert_allclose(
        np.asarray(decode_attention_fast(q, k, v, pos)), ref, atol=1e-5
    )
    np.testing.assert_allclose(
        np.asarray(pallas_decode_attention(q, k, v, pos)), ref, atol=1e-5
    )


# ---------------------------------------------------------------------------
# KV-cache pool ledger
# ---------------------------------------------------------------------------


def test_pool_alloc_exhaust_free_reuse():
    pool = KVCachePool(bucket=32, slots=2)
    a = pool.alloc(epoch=0)
    b = pool.alloc(epoch=0)
    assert {a, b} == {0, 1} and pool.free_slots == 0
    with pytest.raises(PoolExhausted):
        pool.alloc(epoch=0)
    pool.free(a)
    c = pool.alloc(epoch=0)  # freed slot joins the next request
    assert c == a and pool.live == 2
    # the scratch page is never allocatable
    assert pool.scratch == 2
    with pytest.raises(KeyError):
        pool.free(pool.scratch)


def test_pool_epoch_fence():
    pool = KVCachePool(bucket=32, slots=2)
    s = pool.alloc(epoch=0)
    assert pool.checkout(s, 0) == s
    # a swap bumps the engine epoch: the old page must be refused
    with pytest.raises(RuntimeError, match="swap fence"):
        pool.checkout(s, 1)
    assert pool.stale_slots(1) == [s]
    pool.rebind(s, 1)  # re-prefilled under the new weights
    assert pool.checkout(s, 1) == s and pool.stale_slots(1) == []
    pool.evict(s)
    assert pool.evictions == 1 and pool.free_slots == 2


def test_mid_round_swap_refused_without_fence_violation(engine):
    from pytorch_distributed_nn_tpu.serving.generate.engine import (
        StaleBatchEpoch,
    )

    bucket = min(engine.pools)
    pool = engine.pools[bucket]
    before = engine.fence_violations
    e0 = engine.epoch
    slot = pool.alloc(e0)
    try:
        # a swap lands between the scheduler's fence round (validated
        # at e0) and the decode dispatch: the whole batch is refused
        # but the ledger was never breached — no violation counted
        with engine._weights_lock:
            engine.epoch = e0 + 1
        with pytest.raises(StaleBatchEpoch):
            engine.decode(bucket, [slot], [0], [0], expected_epoch=e0)
        assert engine.fence_violations == before
        # a batch already stale when it was FORMED is a true contract
        # breach: validated epoch matches the engine, ledger convicts
        with pytest.raises(RuntimeError, match="swap fence"):
            engine.decode(bucket, [slot], [0], [0],
                          expected_epoch=engine.epoch)
        assert engine.fence_violations == before + 1
    finally:
        pool.free(slot)
        with engine._weights_lock:
            engine.epoch = e0
        engine.fence_violations = before


# ---------------------------------------------------------------------------
# stop tokens / max_new_tokens / validation
# ---------------------------------------------------------------------------


def test_stop_token_and_max_new(engine):
    sched = _scheduler(engine)
    try:
        # every token is a stop token -> exactly one emitted, reason=stop
        r = sched.submit([5, 6, 7], max_new_tokens=20,
                         stop_tokens=list(range(engine.vocab_size)),
                         timeout_s=30.0)
        out = r.wait(60.0)
        assert len(out) == 1 and r.finish_reason == "stop"
        # no stop token -> runs to max_new_tokens, reason=length
        r2 = sched.submit([5, 6, 7], max_new_tokens=5, timeout_s=30.0)
        out2 = r2.wait(60.0)
        assert len(out2) == 5 and r2.finish_reason == "length"
    finally:
        sched.close()


def test_submit_validation(engine):
    sched = _scheduler(engine)
    try:
        with pytest.raises(ValueError):
            sched.submit([], max_new_tokens=4)
        with pytest.raises(ValueError):
            sched.submit([1, 2, 3], max_new_tokens=0)
        with pytest.raises(ValueError):  # 60 + 10 > largest bucket 64
            sched.submit(list(range(1, 61)), max_new_tokens=10)
    finally:
        sched.close()


# ---------------------------------------------------------------------------
# continuous batching: join/leave at step boundaries, zero retraces
# ---------------------------------------------------------------------------


def test_continuous_batch_join_leave_zero_retraces(engine):
    sched = _scheduler(engine)
    rng = np.random.RandomState(7)
    try:
        # staggered waves: later submissions JOIN while earlier ones are
        # mid-decode; finishing sequences free slots for the tail wave
        waves = []
        for wave in range(3):
            waves.extend(
                sched.submit(
                    rng.randint(1, engine.vocab_size,
                                size=rng.randint(2, 24)).astype(np.int32),
                    max_new_tokens=8, timeout_s=30.0,
                )
                for _ in range(6)
            )
            time.sleep(0.01)
        outs = [r.wait(60.0) for r in waves]
    finally:
        sched.close()
    assert all(len(o) == 8 for o in outs)
    assert sched.served == 18 and sched.dropped == 0
    assert engine.retraces() == 0
    # coalescing actually happened: fewer decode steps than sequential
    # execution would need (18 requests x 7 post-prefill tokens)
    assert engine.decode_steps < 18 * 7
    assert engine.fence_violations == 0


def test_swap_fences_and_restamps(engine, decoder_artifact, tmp_path):
    art2 = make_tiny_decoder_artifact(str(tmp_path), seed=3, step=9)
    sched = _scheduler(engine)
    try:
        reqs = [
            sched.submit([1 + i, 2, 3], max_new_tokens=40, timeout_s=30.0)
            for i in range(3)
        ]
        # wait until generation is demonstrably mid-stream (a few
        # tokens out, none finished), THEN swap — deterministic fence
        # coverage without sleep-tuned timing
        deadline = time.monotonic() + 10.0
        while time.monotonic() < deadline:
            if any(len(r.tokens) >= 2 for r in reqs):
                break
            time.sleep(0.001)
        assert not any(r.done.is_set() for r in reqs)
        new_v = sched.swap(art2)
        outs = [r.wait(60.0) for r in reqs]
    finally:
        # restore the module fixture's weights for later tests
        sched.close()
        engine.swap(decoder_artifact)
    assert all(len(o) == 40 for o in outs)
    assert engine.fence_violations == 0
    # at least one in-flight sequence crossed the fence and restarted;
    # every fenced request's tokens are stamped with the NEW version
    fenced = [r for r in reqs if r.refences]
    assert sched.refenced_total >= 1 and fenced
    assert all(r.version == new_v for r in fenced)


def test_shadow_shares_executables_not_pools(engine, tmp_path):
    art2 = make_tiny_decoder_artifact(str(tmp_path), seed=4, step=11)
    before = engine._cache_size()
    shadow = engine.shadow(art2)
    assert shadow.version != engine.version
    sched = _scheduler(shadow)
    try:
        out = sched.submit([9, 8, 7], max_new_tokens=4,
                           timeout_s=30.0).wait(60.0)
    finally:
        sched.close()
    assert len(out) == 4
    # shared executables: serving the shadow compiled nothing
    assert engine._cache_size() == before and engine.retraces() == 0
    # separate pools: the shadow's generation left the stable ledger
    # untouched
    assert all(p.live == 0 for p in engine.pools.values())
    assert shadow.pools is not engine.pools


# ---------------------------------------------------------------------------
# HTTP end-to-end
# ---------------------------------------------------------------------------


def _post(url, doc, headers=None):
    req = urllib.request.Request(
        url, data=json.dumps(doc).encode(),
        headers={"Content-Type": "application/json", **(headers or {})},
    )
    try:
        with urllib.request.urlopen(req, timeout=60) as resp:
            return resp.status, json.loads(resp.read()), dict(
                resp.headers
            )
    except urllib.error.HTTPError as e:
        return e.code, json.loads(e.read() or b"{}"), dict(e.headers)


def test_http_generate_end_to_end(engine):
    from pytorch_distributed_nn_tpu.serving.server import ServingServer

    sched = _scheduler(engine)
    server = ServingServer(engine, None, port=0, generator=sched,
                           admin_token="sekrit")
    server.start()
    base = f"http://{server.host}:{server.port}"
    try:
        status, doc, headers = _post(
            f"{base}/v1/generate",
            {"inputs": [[5, 3, 1], [2, 4, 6, 8]], "max_new_tokens": 4},
            headers={"X-Request-Id": "gen-e2e"},
        )
        assert status == 200
        assert [len(o) for o in doc["outputs"]] == [4, 4]
        assert doc["new_tokens"] == [4, 4]
        assert doc["request_ids"] == ["gen-e2e", "gen-e2e.1"]
        assert doc["versions"] == [engine.version] * 2
        assert doc["finish"] == ["length", "length"]
        assert headers.get("X-Request-Id") == "gen-e2e"

        # /v1/infer explains itself away on a generative server
        status, doc, _ = _post(f"{base}/v1/infer",
                               {"inputs": [[1, 2, 3]]})
        assert status == 400 and "generate" in doc["error"]

        # malformed bodies are 400, not scheduler crashes
        status, _, _ = _post(f"{base}/v1/generate", {"inputs": []})
        assert status == 400
        status, _, _ = _post(
            f"{base}/v1/generate",
            {"inputs": [[1, 2]], "max_new_tokens": 0},
        )
        assert status == 400

        # /stats exposes the generative engine block
        with urllib.request.urlopen(f"{base}/stats", timeout=30) as r:
            stats = json.loads(r.read())
        assert stats["served"] >= 2
        gen = stats["generate"]
        assert gen["tokens_generated"] >= 8
        assert gen["retraces"] == 0 and gen["fence_violations"] == 0
    finally:
        server.close()
        sched.close()


def test_http_admin_swap_generative(engine, decoder_artifact, tmp_path):
    from pytorch_distributed_nn_tpu.serving.server import ServingServer

    art2 = make_tiny_decoder_artifact(str(tmp_path), seed=5, step=21)
    sched = _scheduler(engine)
    server = ServingServer(engine, None, port=0, generator=sched,
                           admin_token="sekrit")
    server.start()
    base = f"http://{server.host}:{server.port}"
    try:
        status, _, _ = _post(f"{base}/v1/admin/swap", {"artifact": art2})
        assert status == 403  # no token
        status, doc, _ = _post(
            f"{base}/v1/admin/swap", {"artifact": art2},
            headers={"X-Admin-Token": "sekrit"},
        )
        assert status == 200 and doc["status"] == "swapped"
        assert engine.version == doc["version"] != None  # noqa: E711
        status, doc, _ = _post(
            f"{base}/v1/admin/swap", {"artifact": art2, "canary": True},
            headers={"X-Admin-Token": "sekrit"},
        )
        assert status == 400  # canary needs a router
    finally:
        server.close()
        sched.close()
        engine.swap(decoder_artifact)


# ---------------------------------------------------------------------------
# observability: generation block, compare gate, tracing, metrics
# ---------------------------------------------------------------------------


def test_generation_observability_block(engine, tmp_path):
    from pytorch_distributed_nn_tpu.observability import reader, tracing

    serve_dir = str(tmp_path / "serve")
    os.makedirs(serve_dir)
    telemetry = serving_telemetry(serve_dir, engine,
                                  extra={"generative": True})
    sched = _scheduler(engine, telemetry=telemetry)
    prompts = sample_prompts(engine, 8, reserve=8)
    try:
        reqs = [sched.submit(p, max_new_tokens=6, timeout_s=30.0)
                for p in prompts]
        for r in reqs:
            r.wait(60.0)
    finally:
        sched.close()
        telemetry.close()
    # registry side: the token counter/histograms routed by log_step
    tokens = telemetry.registry.get("serving_tokens_total")
    assert tokens is not None and tokens.value == 48.0
    assert telemetry.registry.get("serving_ttft_seconds").count == 8
    assert telemetry.registry.get("serving_inter_token_seconds").count == 8

    rs = reader.read_stream(serve_dir)
    assert len(rs.steps) == 8
    for rec in rs.steps:
        assert set(rec["spans"]) >= set(tracing.GENERATE_SPANS)
        assert rec["new_tokens"] == 6 and rec["prompt_tokens"] >= 2
        assert rec["itl_ms"]["p99"] >= rec["itl_ms"]["p50"] > 0
        assert rec["version"] == engine.version
    summary = reader.summarize_run(rs)
    gen = summary["serving"]["generate"]
    assert gen["requests"] == 8 and gen["tokens"] == 48
    assert gen["tokens_per_s"] > 0
    assert gen["ttft_ms"]["p50"] > 0
    assert gen["inter_token_p99_ms"]["p99"] >= gen["inter_token_ms"]["p50"]
    # the rendered summary carries the generation block
    text = reader.render_summary(summary, rs.manifest)
    assert "generation:" in text and "inter-token" in text
    # span waterfall renders prefill/decode in wall order
    trace = tracing.render_trace(rs.steps[0])
    assert trace.index("prefill") < trace.index("decode")

    # compare gate: twin stream -> no regression; the generative rows
    # exist (inflate candidate ITL -> conviction)
    summary2 = json.loads(json.dumps(summary))  # deep copy
    lines, regs = reader.compare_runs(summary, summary2, threshold=0.2)
    assert not regs and any("gen ITL p99" in ln for ln in lines)
    bad = json.loads(json.dumps(summary))
    bad["serving"]["generate"]["inter_token_p99_ms"]["p99"] = (
        summary["serving"]["generate"]["inter_token_p99_ms"]["p99"] * 10
        + 50.0
    )
    _, regs = reader.compare_runs(summary, bad, threshold=0.2)
    assert any("gen ITL p99" in r["metric"] for r in regs)


def test_compare_skips_non_generative_streams(tmp_path):
    """A generative-vs-classifier (or training) compare must skip the
    generation rows, never false-fail on the absent family."""
    from pytorch_distributed_nn_tpu.observability import reader

    d = str(tmp_path / "train")
    reader.write_synthetic_run(d, steps=12)
    s = reader.summarize_run(reader.read_stream(d))
    assert s["serving"] is None or s["serving"].get("generate") is None
    lines, regs = reader.compare_runs(s, s, threshold=0.2)
    assert not regs
    assert not any("gen " in ln for ln in lines if "REGRESSION" in ln)


# ---------------------------------------------------------------------------
# decode cost model
# ---------------------------------------------------------------------------


def test_decode_phase_cost_arithmetic():
    from pytorch_distributed_nn_tpu.analysis.costmodel import (
        decode_phase_cost,
    )

    dc = decode_phase_cost(num_layers=2, d_model=64, d_ff=256,
                           vocab_size=256, cache_len=64, batch=1)
    # matmul params: L*(4d^2 + 2*d*d_ff) + d*vocab
    params = 2 * (4 * 64 * 64 + 2 * 64 * 256) + 64 * 256
    assert dc.flops_per_token == 2 * params + 4 * 64 * 64 * 2
    assert dc.attn_flops_per_token == 4 * 64 * 64 * 2
    assert dc.kv_read_bytes_per_token == 2 * 64 * 64 * 2 * 4
    # attention flops and KV bytes scale with cache length
    dc2 = decode_phase_cost(num_layers=2, d_model=64, d_ff=256,
                            vocab_size=256, cache_len=128, batch=1)
    assert dc2.attn_flops_per_token == 2 * dc.attn_flops_per_token
    assert dc2.kv_read_bytes_per_token == 2 * dc.kv_read_bytes_per_token
    # batching amortizes the weight read, not the KV read
    dc8 = decode_phase_cost(num_layers=2, d_model=64, d_ff=256,
                            vocab_size=256, cache_len=64, batch=8)
    assert dc8.hbm_bytes_per_token < dc.hbm_bytes_per_token
    assert dc8.kv_read_bytes_per_token == dc.kv_read_bytes_per_token
    # roofline: more bandwidth -> more tokens/s, monotonic
    lo = dc.predicted_tokens_per_s(5e10, 1e10)
    hi = dc.predicted_tokens_per_s(5e10, 1e11)
    assert hi > lo > 0


def test_analyze_cost_surfaces_decode_roofline():
    from pytorch_distributed_nn_tpu.cli import (
        _MODEL_ALIASES,
        _decode_cost_block,
    )

    class Args:
        model = "gpt_tiny"
        vocab_size = None
        seq_len = None
        d_model = None
        num_layers = None
        num_heads = None
        d_ff = None
        batch_size = None

    blk = _decode_cost_block(Args(), _MODEL_ALIASES["gpt_tiny"])
    assert blk is not None
    assert blk["predicted_tokens_per_s"] > 0
    assert blk["hbm_bytes_per_token"] > blk["kv_read_bytes_per_token"]
    assert "decode cost" in blk["text"]
    # non-generative models carry no decode block
    assert _decode_cost_block(Args(), "BertTiny") is None


# ---------------------------------------------------------------------------
# deadline drop under slot exhaustion
# ---------------------------------------------------------------------------


def test_deadline_drop_when_pool_exhausted(decoder_artifact):
    """A starved queue sheds load instead of serving late: tiny pool,
    long generations, a burst beyond capacity with a short deadline."""
    from pytorch_distributed_nn_tpu.serving.batcher import (
        DeadlineExceeded,
    )

    eng = GenerativeEngine(
        decoder_artifact, batch_buckets=(1, 2), seq_buckets=(64,),
        pool_slots=2,
    )
    eng.warmup()
    sched = _scheduler(eng)
    try:
        slow = [
            sched.submit([1, 2, 3], max_new_tokens=50, timeout_s=30.0)
            for _ in range(2)
        ]
        time.sleep(0.02)  # both slots live
        victim = sched.submit([4, 5, 6], max_new_tokens=50,
                              timeout_s=0.0)
        with pytest.raises(DeadlineExceeded):
            victim.wait(30.0)
        for r in slow:
            assert len(r.wait(60.0)) == 50
    finally:
        sched.close()
    assert sched.dropped == 1 and sched.served == 2
    assert eng.retraces() == 0

"""Availability-layer tests (serving/frontend.py + admission control,
docs/serving.md "Availability & overload").

Jax-free by design: the frontend is pure HTTP plumbing, so its routing,
breaker, hedging, admission and drain semantics are pinned against stub
replica servers; the bounded batcher is pinned against the fake-engine
pattern test_slo.py established. The full replica-process path (spawn,
SIGKILL, rolling restart) is covered by the ``replica_loss`` chaos
scenario and a ``@slow`` end-to-end here.
"""

import json
import os
import threading
import time
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

import numpy as np
import pytest

from pytorch_distributed_nn_tpu.observability import core, reader
from pytorch_distributed_nn_tpu.resilience.faults import FaultPlan
from pytorch_distributed_nn_tpu.serving.batcher import (
    Batcher,
    Draining,
    QueueShed,
)
from pytorch_distributed_nn_tpu.serving.faultinject import (
    ServingFaultInjector,
)
from pytorch_distributed_nn_tpu.serving.frontend import (
    CircuitBreaker,
    Frontend,
    FrontendShed,
    NoReplicaAvailable,
    frontend_telemetry,
)


# ---------------------------------------------------------------------------
# Circuit breaker state machine
# ---------------------------------------------------------------------------


class TestCircuitBreaker:
    def test_opens_on_threshold_consecutive_failures(self):
        br = CircuitBreaker(threshold=3, cooldown_s=60.0)
        assert br.record_failure() is False
        assert br.record_failure() is False
        assert br.record_failure() is True  # the edge
        assert br.state == CircuitBreaker.OPEN
        assert br.allow() is False  # cooldown not elapsed
        # further failures never re-edge the same outage
        assert br.record_failure() is False

    def test_success_resets_the_consecutive_count(self):
        br = CircuitBreaker(threshold=2)
        br.record_failure()
        assert br.record_success() is False  # was closed: no edge
        br.record_failure()
        assert br.state == CircuitBreaker.CLOSED

    def test_half_open_single_probe_then_close(self):
        br = CircuitBreaker(threshold=1, cooldown_s=0.01)
        assert br.record_failure() is True
        time.sleep(0.02)
        assert br.allow() is True  # the half-open probe slot
        assert br.allow() is False  # one probe at a time
        assert br.record_success() is True  # edge: open -> closed
        assert br.state == CircuitBreaker.CLOSED

    def test_half_open_failure_reopens_without_new_edge(self):
        br = CircuitBreaker(threshold=1, cooldown_s=0.01)
        br.record_failure()
        time.sleep(0.02)
        assert br.allow() is True
        assert br.record_failure() is False  # same outage, same edge
        assert br.state == CircuitBreaker.OPEN
        assert br.opens == 1

    def test_stale_success_while_open_is_ignored(self):
        br = CircuitBreaker(threshold=2, cooldown_s=60.0)
        br.record_failure()
        assert br.record_failure() is True  # the edge
        # a straggler 200 (a response the replica wrote BEFORE dying,
        # read out of the socket buffer after SIGKILL) must not close
        # an OPEN breaker — it would flap a new breaker_open edge on
        # the very next refused connection
        assert br.record_success() is False
        assert br.state == CircuitBreaker.OPEN
        assert br.record_failure() is False  # still the same outage

    def test_reset_closes_on_the_rejoin_edge(self):
        br = CircuitBreaker(threshold=1)
        br.record_failure()
        assert br.reset() is True  # rejoin: fresh replica, clean circuit
        assert br.state == CircuitBreaker.CLOSED
        assert br.reset() is False  # already closed: no edge

    def test_release_probe_frees_the_slot_without_deciding(self):
        br = CircuitBreaker(threshold=1, cooldown_s=0.01)
        br.record_failure()
        time.sleep(0.02)
        assert br.allow() is True  # the probe slot
        assert br.allow() is False
        # the probe's outcome was a reroute (503-draining / shed / 4xx):
        # no verdict on the outage, but the slot MUST come back
        br.release_probe()
        assert br.state == CircuitBreaker.HALF_OPEN
        assert br.allow() is True  # probe again
        assert br.record_success() is True

    def test_release_probe_is_a_noop_outside_half_open(self):
        br = CircuitBreaker(threshold=1)
        br.release_probe()
        assert br.state == CircuitBreaker.CLOSED
        br.record_failure()
        br.release_probe()
        assert br.state == CircuitBreaker.OPEN

    def test_force_open_edges_once(self):
        br = CircuitBreaker(threshold=3)
        assert br.force_open() is True
        assert br.force_open() is False  # already open: no double edge
        br2 = CircuitBreaker(threshold=1)
        br2.record_failure()  # opened by request failures
        assert br2.force_open() is False  # down-detection shares the edge


# ---------------------------------------------------------------------------
# FaultPlan serving kinds (request-count keyed)
# ---------------------------------------------------------------------------


class TestServingFaultGrammar:
    def test_parse_and_roundtrip(self):
        plan = FaultPlan.parse(
            "slow_infer@1:0.06s:x400,conn_reset@25,http_503@40:x3"
        )
        assert plan.has_serving_faults()
        assert plan.describe() == (
            "slow_infer@1:0.06s:x400,conn_reset@25,http_503@40:x3"
        )
        assert plan.serving_delay(1) == pytest.approx(0.06)
        assert plan.serving_delay(400) == pytest.approx(0.06)
        assert plan.serving_delay(401) == 0.0
        assert plan.should_conn_reset(25)
        assert not plan.should_conn_reset(26)
        assert [plan.should_503(i) for i in (39, 40, 42, 43)] == [
            False, True, True, False,
        ]

    def test_training_kinds_have_no_serving_hooks(self):
        plan = FaultPlan.parse("crash@5,delay@3:2.5s")
        assert not plan.has_serving_faults()
        assert plan.serving_delay(5) == 0.0

    @pytest.mark.parametrize("bad", [
        "crash@5:x3",           # count arg on a non-serving kind
        "slow_infer@1:p2",      # ranks never apply to serving kinds
        "http_503@0",           # request indices are 1-based
        "slow_infer@1:x0",      # empty coverage
        "wat@1",                # unknown kind
    ])
    def test_bad_specs_fail_at_parse(self, bad):
        with pytest.raises(ValueError):
            FaultPlan.parse(bad)


class _FakeEngine:
    max_batch = 4
    version = "fake@1:none"
    manifest = {"source": {"train_dir": "/x/fake", "step": 1},
                "quantize": "none", "network": "FakeNet"}

    def infer(self, xs):
        return [np.zeros(3) for _ in xs], {
            "bucket": 4, "batch": len(xs), "pad_ms": 0.05,
            "infer_ms": 0.5, "flops": None,
        }


class TestServingFaultInjector:
    def test_requires_serving_entries(self):
        with pytest.raises(ValueError, match="no serving-side"):
            ServingFaultInjector(FaultPlan.parse("crash@5"),
                                 telemetry=core.Telemetry())

    def test_slow_infer_bills_the_infer_stat_once_per_batch(self):
        t = core.Telemetry()
        inj = ServingFaultInjector(
            FaultPlan.parse("slow_infer@2:0.05s:x2"), telemetry=t
        )
        eng = _FakeEngine()
        inj.attach_engine(eng)
        t0 = time.monotonic()
        _, s1 = eng.infer([1])          # request 1: uncovered
        _, s2 = eng.infer([2, 3])       # requests 2-3: covered once
        _, s3 = eng.infer([4])          # request 4: uncovered
        wall = time.monotonic() - t0
        assert s1["infer_ms"] == 0.5 and s3["infer_ms"] == 0.5
        assert s2["infer_ms"] == pytest.approx(50.5, abs=1.0)
        assert 0.04 < wall < 0.5
        # one fault_injected per ENTRY, not per covered request
        assert inj.fired == 1

    def test_http_actions_count_requests(self):
        inj = ServingFaultInjector(
            FaultPlan.parse("conn_reset@2,http_503@3:x2"),
            telemetry=core.Telemetry(),
        )
        assert [inj.http_action() for _ in range(5)] == [
            None, "conn_reset", "http_503", "http_503", None,
        ]
        assert inj.fired == 2


# ---------------------------------------------------------------------------
# Bounded admission queue (batcher)
# ---------------------------------------------------------------------------


def _stream(tmp_path):
    return core.Telemetry.for_run(
        os.path.join(str(tmp_path), core.SERVING_BASENAME),
        core.run_manifest(config={"mode": "serving"}),
    )


class TestBoundedBatcher:
    def test_shed_past_the_bound_with_retry_after(self, tmp_path):
        t = _stream(tmp_path)
        b = Batcher(_FakeEngine(), telemetry=t, start=False, max_queue=3)
        for _ in range(3):
            b.submit(np.zeros(3), timeout_s=10.0)
        with pytest.raises(QueueShed) as ei:
            b.submit(np.zeros(3), timeout_s=10.0)
        assert ei.value.retry_after_s > 0
        assert b.shed == 1
        depth = t.registry.get("serving_queue_depth")
        peak = t.registry.get("serving_queue_depth_peak")
        assert depth is not None and depth.value == 3.0
        assert peak is not None and peak.value == 3.0
        assert t.registry.get("serving_shed_total").value == 1.0
        b.close(drain=False)
        t.close()
        rs = reader.read_stream(str(tmp_path))
        sheds = [e for e in rs.events if e.get("type") == "request_shed"]
        assert len(sheds) == 1
        assert sheds[0]["klass"] == "stable"
        assert sheds[0]["max_queue"] == 3
        assert sheds[0]["retry_after_s"] > 0
        assert sheds[0]["version"] == "fake@1:none"

    def test_canary_caps_before_stable_and_probe_never_sheds(self):
        b = Batcher(_FakeEngine(), telemetry=core.Telemetry(),
                    start=False, max_queue=4, canary_share=0.5)
        b.submit(np.zeros(3), klass="canary", timeout_s=10.0)
        b.submit(np.zeros(3), klass="canary", timeout_s=10.0)
        # canary is at its 50% share: the next canary sheds...
        with pytest.raises(QueueShed):
            b.submit(np.zeros(3), klass="canary", timeout_s=10.0)
        # ...while stable still admits up to the full bound...
        b.submit(np.zeros(3), klass="stable", timeout_s=10.0)
        b.submit(np.zeros(3), klass="stable", timeout_s=10.0)
        with pytest.raises(QueueShed):
            b.submit(np.zeros(3), klass="stable", timeout_s=10.0)
        # ...and probes always admit, even past the bound
        b.submit(np.zeros(3), klass="probe", timeout_s=10.0)
        with pytest.raises(ValueError, match="traffic class"):
            b.submit(np.zeros(3), klass="vip", timeout_s=10.0)
        b.close(drain=False)

    def test_unbounded_by_default(self):
        b = Batcher(_FakeEngine(), telemetry=core.Telemetry(),
                    start=False)
        for _ in range(64):
            b.submit(np.zeros(3), timeout_s=10.0)
        assert b.shed == 0
        b.close(drain=False)

    def test_begin_drain_refuses_new_admissions(self, tmp_path):
        t = _stream(tmp_path)
        b = Batcher(_FakeEngine(), telemetry=t)
        r = b.submit(np.zeros(3), timeout_s=10.0)
        r.wait(timeout=10.0)
        b.begin_drain()
        assert b.draining
        with pytest.raises(Draining):
            b.submit(np.zeros(3), timeout_s=10.0)
        b.begin_drain()  # idempotent: one typed event
        b.close()
        t.close()
        rs = reader.read_stream(str(tmp_path))
        drains = [e for e in rs.events if e.get("type") == "drain"]
        assert len(drains) == 1 and drains[0]["phase"] == "start"


class TestBoundedGenerateScheduler:
    class _FakeGenEngine:
        seq_buckets = (32,)
        version = "fake@1:none"

        def select_prompt_bucket(self, n):
            return 32

        def select_seq_bucket(self, n):
            if n > 32:
                raise ValueError("too long")
            return 32

    def test_shed_and_drain(self):
        from pytorch_distributed_nn_tpu.serving.generate.scheduler import (
            GenerateScheduler,
        )

        s = GenerateScheduler(self._FakeGenEngine(),
                              telemetry=core.Telemetry(),
                              start=False, max_queue=2)
        s.submit([1, 2, 3], max_new_tokens=4)
        s.submit([1, 2], max_new_tokens=4)
        with pytest.raises(QueueShed):
            s.submit([3], max_new_tokens=4)
        assert s.shed == 1
        s.begin_drain()
        with pytest.raises(Draining):
            s.submit([4], max_new_tokens=4)

    def test_shed_events_rate_limited_with_covering_count(self, tmp_path):
        """The generative path pays the same 1/s shed-event discipline
        as the batcher: under sustained overload one event per shed is
        an observability storm — the first shed emits, the rest
        accumulate into a trailing close-time tally, and summing the
        events' ``count`` recovers the exact total."""
        from pytorch_distributed_nn_tpu.serving.generate.scheduler import (
            GenerateScheduler,
        )

        t = _stream(tmp_path)
        s = GenerateScheduler(self._FakeGenEngine(), telemetry=t,
                              start=False, max_queue=1)
        s.submit([1, 2], max_new_tokens=4)  # fills the bound
        for _ in range(5):
            with pytest.raises(QueueShed) as ei:
                s.submit([3], max_new_tokens=4)
            assert ei.value.retry_after_s > 0
        assert s.shed == 5
        assert t.registry.get("serving_shed_total").value == 5.0
        s.close(drain=False)
        t.close()
        rs = reader.read_stream(str(tmp_path))
        sheds = [e for e in rs.events if e.get("type") == "request_shed"]
        assert len(sheds) == 2  # first emit + trailing flush, not 5
        assert sheds[0]["count"] == 1
        assert sheds[1]["count"] == 4 and sheds[1]["trailing"] is True
        assert all(e["generative"] for e in sheds)
        assert sum(e["count"] for e in sheds) == 5
        # nothing retired yet: the estimate falls back to 1.0s
        assert sheds[0]["retry_after_s"] == 1.0
        # and the summary's shed total comes from the counts
        sv = reader.serving_summary(rs)
        assert sv["shed"] == 5


# ---------------------------------------------------------------------------
# Frontend against stub replicas (jax-free)
# ---------------------------------------------------------------------------


class _StubReplica:
    """A controllable replica server: mode 'ok' answers 200, 'fail'
    answers 500, 'slow' sleeps then answers, 'reset' drops the
    connection, 'draining' refuses like a SIGTERMed replica."""

    def __init__(self, version="v1"):
        self.mode = "ok"
        self.slow_s = 0.5
        self.served = 0
        outer = self

        class H(BaseHTTPRequestHandler):
            def log_message(self, fmt, *args):
                pass

            def _reply(self, code, payload):
                body = json.dumps(payload).encode()
                self.send_response(code)
                self.send_header("Content-Length", str(len(body)))
                self.end_headers()
                self.wfile.write(body)

            def do_GET(self):
                if self.path == "/readyz":
                    if outer.mode == "draining":
                        self._reply(503, {"status": "draining",
                                          "draining": True})
                    else:
                        self._reply(200, {"status": "ready"})
                else:
                    self._reply(200, {"status": "ok"})

            def do_POST(self):
                n = int(self.headers.get("Content-Length", 0))
                self.rfile.read(n)
                outer.served += 1
                mode = outer.mode
                if mode == "reset":
                    self.close_connection = True
                    self.connection.close()
                    return
                if mode == "fail":
                    self._reply(500, {"error": "stub failure"})
                    return
                if mode == "draining":
                    self._reply(503, {"error": "draining",
                                      "draining": True})
                    return
                if mode == "slow":
                    time.sleep(outer.slow_s)
                self._reply(200, {
                    "outputs": [[0.0]],
                    "versions": [version],
                    "klass": self.headers.get("X-Traffic-Class"),
                    "request_ids": [self.headers.get("X-Request-Id")],
                })

        self.httpd = ThreadingHTTPServer(("127.0.0.1", 0), H)
        self.port = self.httpd.server_address[1]
        threading.Thread(target=self.httpd.serve_forever,
                         daemon=True).start()

    def close(self):
        self.httpd.shutdown()
        self.httpd.server_close()


@pytest.fixture()
def stub_pool(tmp_path):
    stubs = [_StubReplica(version=f"v{i}") for i in range(2)]
    tel = frontend_telemetry(str(tmp_path / "serve"))
    fe = Frontend(
        str(tmp_path / "fe"), telemetry=tel, timeout_s=2.0,
        max_inflight=64, retries=2, poll_s=0.05, lease_s=0.5,
        breaker_threshold=2, breaker_cooldown_s=0.2,
        hedge_ms=5000.0,  # effectively off unless a test lowers it
    )
    for i, s in enumerate(stubs):
        fe.attach_replica(f"r{i}", "127.0.0.1", s.port)
    fe.start()
    fe.wait_ready(timeout=10.0)
    yield fe, stubs, tel, str(tmp_path / "serve")
    fe.close(stop_replicas=False)
    tel.close()
    for s in stubs:
        s.close()


def _events(serve_dir):
    rs = reader.read_stream(serve_dir)
    out = {}
    for e in rs.events:
        out.setdefault(e.get("type", "?"), []).append(e)
    return rs, out


class TestFrontendRouting:
    def test_forward_and_stream_record(self, stub_pool):
        fe, stubs, tel, serve_dir = stub_pool
        status, payload = fe.forward({"inputs": [[1.0]]},
                                     request_id="trace-1")
        assert status == 200
        assert payload["request_ids"] == ["trace-1"]
        assert payload["attempts"] == 1
        assert payload["replica"] in ("r0", "r1")
        assert fe.forwarded == 1
        tel.flush()
        rs = reader.read_stream(serve_dir)
        assert len(rs.steps) == 1
        rec = rs.steps[0]
        assert rec["request_id"] == "trace-1"
        assert rec["latency_ms"] > 0
        assert rec["replica"] == payload["replica"]

    def test_failure_retries_on_the_other_replica(self, stub_pool):
        fe, stubs, tel, serve_dir = stub_pool
        stubs[0].mode = "fail"
        stubs[1].mode = "fail"
        # both broken: the client sees the upstream failure
        status, payload = fe.forward({"inputs": [[1.0]]})
        assert status == 500
        stubs[0].mode = "ok"
        stubs[1].mode = "ok"
        # one broken: invisible to the client
        stubs[0].mode = "reset"
        for _ in range(4):
            status, payload = fe.forward({"inputs": [[1.0]]})
            assert status == 200
        assert fe.retried > 0

    def test_breaker_opens_once_and_closes_after_probe(self, stub_pool):
        fe, stubs, tel, serve_dir = stub_pool
        stubs[0].mode = "fail"
        # threshold=2: drive enough traffic that r0 fails twice
        for _ in range(8):
            status, _ = fe.forward({"inputs": [[1.0]]})
            assert status == 200  # retries cover every failure
        r0 = fe._find("r0")
        assert r0.breaker.state == CircuitBreaker.OPEN
        # heal; past the cooldown the half-open probe closes it
        stubs[0].mode = "ok"
        time.sleep(0.3)
        deadline = time.monotonic() + 5.0
        while time.monotonic() < deadline \
                and r0.breaker.state != CircuitBreaker.CLOSED:
            fe.forward({"inputs": [[1.0]]})
            time.sleep(0.02)
        assert r0.breaker.state == CircuitBreaker.CLOSED
        tel.flush()
        _, ev = _events(serve_dir)
        assert len(ev.get("breaker_open", [])) == 1
        assert len(ev.get("breaker_close", [])) == 1
        assert ev["breaker_open"][0]["replica"] == "r0"

    def test_green_readyz_never_resets_an_open_breaker(self, stub_pool):
        """An alive-but-erroring replica (the http_503 fault shape)
        keeps answering /readyz 200 while its breaker is open. The
        health loop must NOT treat those green polls as breaker
        successes — that would close the breaker within one tick and
        defeat the cooldown/half-open discipline (and flap
        breaker_open/breaker_close against the one-edge-per-outage
        contract)."""
        fe, stubs, tel, serve_dir = stub_pool
        stubs[0].mode = "fail"  # requests 500, /readyz stays 200
        for _ in range(8):
            status, _ = fe.forward({"inputs": [[1.0]]})
            assert status == 200
        r0 = fe._find("r0")
        assert r0.breaker.state == CircuitBreaker.OPEN
        # no traffic: only health ticks run (poll_s=0.05 — this covers
        # several). The breaker must still be open afterwards; only a
        # request-path success or the half-open probe may close it.
        time.sleep(0.3)
        assert r0.breaker.state == CircuitBreaker.OPEN
        assert r0.state == "ready"  # readiness itself is untouched
        tel.flush()
        _, ev = _events(serve_dir)
        assert len(ev.get("breaker_open", [])) == 1
        assert len(ev.get("breaker_close", [])) == 0

    def test_probe_reroute_releases_the_probe_slot(self, tmp_path):
        """A half-open probe answered with 503+draining (a replica an
        operator SIGTERMed directly — the frontend doesn't know) must
        release the probe slot: otherwise the breaker stays
        probe-locked and the replica is unroutable forever."""
        stub = _StubReplica()
        tel = core.Telemetry()
        fe = Frontend(
            str(tmp_path / "fe"), telemetry=tel, timeout_s=2.0,
            poll_s=0.05, lease_s=30.0, breaker_threshold=1,
            breaker_cooldown_s=0.05, hedge_ms=5000.0, retries=0,
        )
        fe.attach_replica("r0", "127.0.0.1", stub.port)
        fe.start()
        fe.wait_ready(timeout=10.0)
        try:
            stub.mode = "fail"
            status, _ = fe.forward({"inputs": [[1.0]]})
            assert status == 500
            r0 = fe._find("r0")
            assert r0.breaker.state == CircuitBreaker.OPEN
            # server-side drain the frontend was never told about:
            # the probe's outcome is a reroute, not a verdict
            stub.mode = "draining"
            time.sleep(0.1)  # past the cooldown
            status, _ = fe.forward({"inputs": [[1.0]]})
            assert status == 503
            # the slot came back: once the replica heals, a later
            # probe closes the breaker instead of refusing forever
            stub.mode = "ok"
            deadline = time.monotonic() + 5.0
            while (time.monotonic() < deadline
                   and r0.breaker.state != CircuitBreaker.CLOSED):
                try:
                    fe.forward({"inputs": [[1.0]]})
                except NoReplicaAvailable:
                    pass
                time.sleep(0.02)
            assert r0.breaker.state == CircuitBreaker.CLOSED
        finally:
            fe.close(stop_replicas=False)
            tel.close()
            stub.close()

    def test_failed_forward_debits_availability(self, stub_pool):
        """A forward that exhausts its retries and returns 5xx is
        offered-but-not-served: it must land in the stream as a typed
        request_failed event and pull the summary's availability
        fraction below 1.0 (the outage case the metric exists for)."""
        fe, stubs, tel, serve_dir = stub_pool
        stubs[0].mode = "fail"
        stubs[1].mode = "fail"
        status, _ = fe.forward({"inputs": [[1.0]]})
        assert status == 500
        assert fe.failed == 1
        assert fe.state()["failed"] == 1
        stubs[0].mode = "ok"
        stubs[1].mode = "ok"
        for _ in range(3):
            status, _ = fe.forward({"inputs": [[1.0]]})
            assert status == 200
        tel.flush()
        rs, ev = _events(serve_dir)
        fails = ev.get("request_failed", [])
        assert len(fails) == 1
        assert fails[0]["layer"] == "frontend"
        assert fails[0]["status"] == 500
        sv = reader.serving_summary(rs)
        assert sv["requests"] == 3
        assert sv["failed"] == 1
        assert sv["availability"] == pytest.approx(0.75)

    def test_hedge_first_response_wins_and_dedups(self, stub_pool):
        fe, stubs, tel, serve_dir = stub_pool
        fe.hedge_ms = 30.0
        # whichever replica gets the primary is slow; the hedge lands on
        # the fast one and wins
        stubs[0].mode = "slow"
        stubs[1].mode = "slow"
        stubs[0].slow_s = stubs[1].slow_s = 0.4

        # make exactly one side slow by mode: set both slow, then speed
        # up r1 only
        stubs[1].slow_s = 0.0
        t0 = time.monotonic()
        status, payload = fe.forward({"inputs": [[1.0]]},
                                     request_id="hedged-1")
        wall = time.monotonic() - t0
        assert status == 200
        # either the primary hit the fast stub (no hedge needed) or the
        # hedge covered the slow primary — run until a hedge happened
        tries = 0
        while fe.hedges == 0 and tries < 20:
            fe.forward({"inputs": [[1.0]]})
            tries += 1
        assert fe.hedges > 0
        assert fe.hedge_wins > 0
        assert wall < 2.0
        tel.flush()
        _, ev = _events(serve_dir)
        hedges = ev.get("hedge", [])
        assert hedges and hedges[0]["after_ms"] >= 25.0
        assert {h["primary"] for h in hedges} <= {"r0", "r1"}

    def test_lease_declares_down_and_rejoin(self, stub_pool):
        fe, stubs, tel, serve_dir = stub_pool
        stubs[0].close()  # the replica vanishes (conn refused)
        deadline = time.monotonic() + 5.0
        while time.monotonic() < deadline \
                and fe.state()["ready"] != 1:
            time.sleep(0.05)
        assert fe.state()["ready"] == 1
        tel.flush()
        _, ev = _events(serve_dir)
        downs = ev.get("replica_down", [])
        assert len(downs) == 1 and downs[0]["replica"] == "r0"
        assert "lease" in downs[0]["reason"]
        # requests keep flowing on the survivor
        status, payload = fe.forward({"inputs": [[1.0]]})
        assert status == 200 and payload["replica"] == "r1"

    def test_no_replica_available(self, tmp_path):
        fe = Frontend(str(tmp_path / "fe"), telemetry=core.Telemetry())
        with pytest.raises(NoReplicaAvailable):
            fe.forward({"inputs": [[1.0]]})


class TestFrontendAdmission:
    def test_bound_sheds_with_retry_after_and_event(self, stub_pool):
        fe, stubs, tel, serve_dir = stub_pool
        fe.max_inflight = 2
        fe._admit("stable")
        fe._admit("stable")
        with pytest.raises(FrontendShed) as ei:
            fe._admit("stable")
        assert ei.value.retry_after_s > 0
        assert fe.shed == 1
        # probes bypass the bound entirely
        fe._admit("probe")
        tel.flush()
        _, ev = _events(serve_dir)
        sheds = ev.get("request_shed", [])
        assert len(sheds) == 1
        assert sheds[0]["layer"] == "frontend"
        assert sheds[0]["klass"] == "stable"

    def test_canary_share_caps_canary_inflight(self, stub_pool):
        fe, stubs, tel, serve_dir = stub_pool
        fe.max_inflight = 8
        fe.canary_share = 0.25  # cap = 2
        fe._admit("canary")
        fe._admit("canary")
        with pytest.raises(FrontendShed):
            fe._admit("canary")
        fe._admit("stable")  # stable unaffected

    def test_unknown_class_rejected(self, stub_pool):
        fe, stubs, tel, serve_dir = stub_pool
        with pytest.raises(ValueError, match="traffic class"):
            fe.forward({"inputs": [[1.0]]}, klass="vip")


class TestFrontendHTTP:
    def test_http_surface(self, stub_pool):
        import http.client

        fe, stubs, tel, serve_dir = stub_pool
        conn = http.client.HTTPConnection(fe.host, fe.port, timeout=10)
        body = json.dumps({"inputs": [[1.0]], "timeout_s": 2.0})
        conn.request("POST", "/v1/infer", body,
                     {"Content-Type": "application/json",
                      "X-Request-Id": "http-1"})
        resp = conn.getresponse()
        assert resp.status == 200
        assert resp.getheader("X-Request-Id") == "http-1"
        doc = json.loads(resp.read())
        assert doc["replica"] in ("r0", "r1")

        conn.request("GET", "/readyz")
        r = conn.getresponse()
        r.read()  # keep-alive: drain before the next request
        assert r.status == 200
        conn.request("GET", "/stats")
        st = json.loads(conn.getresponse().read())
        assert st["ready"] == 2 and st["forwarded"] >= 1
        assert {r["name"] for r in st["replicas"]} == {"r0", "r1"}

        conn.request("POST", "/v1/infer", "{}",
                     {"Content-Type": "application/json"})
        r = conn.getresponse()
        r.read()
        assert r.status == 400
        conn.close()

    def test_http_shed_carries_retry_after(self, stub_pool):
        import http.client

        fe, stubs, tel, serve_dir = stub_pool
        fe.max_inflight = 1
        fe._admit("stable")  # hold the only slot
        conn = http.client.HTTPConnection(fe.host, fe.port, timeout=10)
        conn.request("POST", "/v1/infer",
                     json.dumps({"inputs": [[1.0]]}),
                     {"Content-Type": "application/json"})
        resp = conn.getresponse()
        assert resp.status == 429
        assert int(resp.getheader("Retry-After")) >= 1
        doc = json.loads(resp.read())
        assert doc["retry_after_s"] > 0
        conn.close()


# ---------------------------------------------------------------------------
# Overload soak: 3x the sustainable rate against a bounded batcher
# ---------------------------------------------------------------------------


class TestOverloadSoak:
    def test_soak_sheds_bounded_and_occupancy_stays_at_the_bound(
            self, tmp_path):
        """Open-loop load far past the sustainable rate: the queue stays
        at its bound (never grows), the excess is shed as 429s with
        Retry-After, and the shed fraction — not the latency of the
        SERVED requests — absorbs the overload. All of it in counts: a
        time on a host that six xdist workers share is the host's (PR 30:
        `p99 < 100 ms`, then p99 against the twin's, both failed under
        the driver's command with nothing wrong). The twin's shed
        fraction is 0, so ``obs compare``'s shed-rate row skips by the
        a==0 contract instead of auto-failing the soak."""
        from pytorch_distributed_nn_tpu.serving.loadgen import (
            make_tiny_artifact,
            run_load,
            sample_inputs,
            serving_telemetry,
        )
        from pytorch_distributed_nn_tpu.serving.engine import (
            InferenceEngine,
        )

        artifact = make_tiny_artifact(str(tmp_path))
        engine = InferenceEngine(artifact, batch_buckets=(1, 2, 4, 8))
        engine.warmup()
        inputs = sample_inputs(engine, 64)

        def run(name, offered, max_queue):
            d = str(tmp_path / name)
            os.makedirs(d, exist_ok=True)
            tel = serving_telemetry(d, engine)
            b = Batcher(engine, telemetry=tel, max_queue=max_queue,
                        default_timeout_s=10.0)
            try:
                res = run_load(b, inputs, offered_rps=offered,
                               duration_s=1.0, timeout_s=10.0)
            finally:
                b.close()
                tel.close()
            return d, res, tel

        twin_dir, twin, _ = run("twin", 600.0, None)
        assert twin["shed"] == 0 and twin["dropped"] == 0
        # the bound is tiny (a quarter of the largest bucket); offered is
        # far past the measured ceiling (asserted below)
        bound = 2
        soak_dir, soak, soak_tel = run("soak", 12000.0, bound)
        # the offered rate really was >= 3x what the engine sustained
        assert soak["offered_rps"] >= 3.0 * soak["sustained_rps"]
        # excess absorbed by shedding, not queueing or deadline misses
        assert soak["shed"] > 0.3 * soak["submitted"]
        assert soak["dropped"] == 0
        assert soak["shed_fraction"] == pytest.approx(
            soak["shed"] / soak["submitted"], abs=1e-3
        )
        # the queue stayed at its bound, never grew past it
        peak = soak_tel.registry.get("serving_queue_depth_peak")
        assert peak is not None and 0 < peak.value <= bound
        sa = reader.summarize_run(reader.read_stream(twin_dir))
        sb = reader.summarize_run(reader.read_stream(soak_dir))
        assert sb["serving"]["shed"] == soak["shed"]
        assert sb["serving"]["availability"] < 1.0
        # served-request latency, as a count (Little's law): the sum of
        # the served requests' latencies over the run's wall time is the
        # mean number of admitted requests in the system, and that cannot
        # exceed the queue's bound plus one batch in flight. A stalled
        # host stretches both times alike, so the quotient does not move
        # with the load; an unbounded queue at this rate holds thousands.
        wall_s = soak["served"] / soak["sustained_rps"]
        in_system = sb["serving"]["latency_ms"]["total"] / 1e3 / wall_s
        assert 0 < in_system <= bound + max(engine.batch_buckets)
        # the soak's sheds do not fail obs compare against a twin that
        # shed nothing (its latency and rate rows are times: not asserted)
        lines, regressions = reader.compare_runs(sa, sb, threshold=0.2)
        assert not [r for r in regressions if "shed" in r["metric"]], (
            "\n".join(lines))


# ---------------------------------------------------------------------------
# Real replica processes (spawn -> kill -> rejoin): the slow e2e
# ---------------------------------------------------------------------------


@pytest.mark.slow
class TestFrontendE2E:
    def test_spawned_replicas_survive_kill_and_drain(self, tmp_path):
        from pytorch_distributed_nn_tpu.serving.loadgen import (
            make_tiny_artifact,
            run_http_load,
        )

        artifact = make_tiny_artifact(str(tmp_path))
        tel = frontend_telemetry(str(tmp_path / "serve"))
        fe = Frontend(str(tmp_path / "fe"), telemetry=tel,
                      timeout_s=5.0, poll_s=0.1, lease_s=2.0,
                      breaker_cooldown_s=1.0)
        try:
            for i in range(2):
                fe.spawn_replica(f"r{i}", artifact,
                                 serve_args=["--buckets", "1,2,4"])
            fe.start()
            fe.wait_ready(timeout=120.0)
            rng = np.random.RandomState(0)
            rows = [rng.rand(28, 28, 1).astype(np.float32).tolist()
                    for _ in range(4)]
            holder = {}

            def _load():
                holder["res"] = run_http_load(
                    fe.host, fe.port, rows, offered_rps=60.0,
                    duration_s=4.0, timeout_s=5.0, workers=32,
                )

            t = threading.Thread(target=_load)
            t.start()
            time.sleep(1.0)
            fe.kill_replica("r0")
            t.join(timeout=60)  # 4 s of load + 5 s request timeouts
            assert not t.is_alive()
            assert holder["res"]["failed"] == 0
            assert holder["res"]["ok"] == holder["res"]["submitted"]
            fe.restart_replica("r0")
            assert fe.state()["ready"] == 2
            assert fe.drain_replica("r1") is True  # SIGTERM exits rc=0
        finally:
            fe.close()
            tel.close()

"""The training spans (observability/spans.py): the primitive, the
catalogue's sites in the step loop, the loaders and the checkpoint writer
as a profiler trace shows them, and the clocks that were repaired with it
(``wall_ms``, the efficiency gauges, ``last_wait_ms``)."""

import glob
import threading
import time

import jax
import pytest

from pytorch_distributed_nn_tpu.observability import core, spans
from pytorch_distributed_nn_tpu.observability.reader import read_stream
from pytorch_distributed_nn_tpu.training.trainer import TrainConfig, Trainer


@pytest.fixture
def telemetry():
    t = core.Telemetry()
    previous = core.install(t)
    yield t
    core.uninstall(t, previous)


def _phase(telemetry, name):
    return telemetry.registry.get("phase_seconds", labels={"phase": name})


def test_span_nests_and_observes_from_two_threads(telemetry):
    def writer():
        with spans.span("ckpt/write"):
            with spans.span("ckpt/serialize"):
                time.sleep(0.01)

    thread = threading.Thread(target=writer)
    with spans.span("train/step") as outer:
        thread.start()
        for _ in range(2):
            with spans.span("train/data") as inner:
                time.sleep(0.002)
        thread.join(timeout=10)
    assert not thread.is_alive()
    assert _phase(telemetry, "train/step").count == 1
    assert _phase(telemetry, "train/data").count == 2
    assert _phase(telemetry, "ckpt/write").count == 1
    assert _phase(telemetry, "ckpt/serialize").count == 1
    assert outer.seconds >= inner.seconds >= 0.002
    assert _phase(telemetry, "train/step").sum == pytest.approx(outer.seconds)
    assert _phase(telemetry, "ckpt/write").sum >= 0.01


def test_a_name_outside_the_catalogue_raises():
    with pytest.raises(ValueError, match="catalogue"):
        spans.span("train/setp")
    assert len(spans.NAMES) == len(spans.CATALOGUE) == 16


def test_span_costs_microseconds_when_no_trace_runs(telemetry):
    with spans.span("train/step"):     # the first one imports jax.profiler
        pass
    n = 10_000
    t0 = time.perf_counter()
    for _ in range(n):
        with spans.span("train/step"):
            pass
    us = (time.perf_counter() - t0) / n * 1e6
    print(f"span enter/exit, no trace running: {us:.2f} us")
    assert us < 20
    assert _phase(telemetry, "train/step").count == n + 1


def test_efficiency_gauges_follow_wall_ms():
    cost = {"flops": 1e12, "peak_flops_per_s": 1e13}
    t = core.Telemetry(manifest={"step_cost": cost})
    t.log_step({"step": 1, "step_time": 0.1, "wall_ms": 200.0})
    assert t.registry.get("mfu").value == pytest.approx(1e12 / 0.2 / 1e13)
    # a stream from before wall_ms (obs export replays it): step_time
    t.log_step({"step": 2, "step_time": 0.1})
    assert t.registry.get("mfu").value == pytest.approx(1e12 / 0.1 / 1e13)


# -- the loaders' last_wait_ms is input/produce alone -------------------------

SLOW_MS = 100


def _slow(fn):
    def slowed(*args, **kw):
        time.sleep(SLOW_MS / 1000)
        return fn(*args, **kw)
    return slowed


@pytest.fixture
def slow_device_put(monkeypatch):
    monkeypatch.setattr(jax, "device_put", _slow(jax.device_put))


def _loader(kind, tmp_path):
    """(loader, the object and attribute that draw its next batch)."""
    from pytorch_distributed_nn_tpu.data import DataLoader, load_dataset
    from pytorch_distributed_nn_tpu.data.loader import DeviceDataLoader
    from pytorch_distributed_nn_tpu.data.streaming import (
        StreamingLoader,
        export_image_dataset,
    )
    from pytorch_distributed_nn_tpu.data.text import MLMBatches, MLMLoader
    from pytorch_distributed_nn_tpu.parallel import batch_sharding, make_mesh

    mesh = make_mesh(2)
    if kind == "mlm":
        batches = MLMBatches(batch_size=4, seq_len=16, vocab_size=64, seed=0)
        loader = MLMLoader(batches, sharding=batch_sharding(mesh))
        return loader, MLMBatches, "__next__"
    data = load_dataset("MNIST", train=True, synthetic_size=64)
    if kind == "stream":
        export_image_dataset(data, str(tmp_path), shards=2)
        loader = StreamingLoader(str(tmp_path), 8, seed=0, prefetch=0,
                                 sharding=batch_sharding(mesh))
        return loader, loader, "_next_raw"
    if kind == "host":
        loader = DataLoader(data, 8, prefetch=0, sharding=batch_sharding(mesh))
    else:
        loader = DeviceDataLoader(data, 8, mesh)
    return loader, loader, "_next_idx"


@pytest.mark.parametrize("kind", ["host", "device", "mlm", "stream"])
def test_last_wait_ms_is_the_host_work_not_the_dispatch(
        kind, monkeypatch, slow_device_put, telemetry, tmp_path):
    loader, owner, draw = _loader(kind, tmp_path)
    try:
        loader.next_batch()
        assert loader.last_wait_ms < SLOW_MS / 2     # device_put is not in it
        assert _phase(telemetry, "input/put").sum >= SLOW_MS / 1000
        monkeypatch.setattr(owner, draw, _slow(getattr(owner, draw)))
        loader.next_batch()
        assert SLOW_MS <= loader.last_wait_ms < 2 * SLOW_MS
        assert _phase(telemetry, "input/produce").count == 2
        if kind == "device":                         # the fused-step path
            loader.next_indices()
            assert SLOW_MS <= loader.last_wait_ms < 2 * SLOW_MS
    finally:
        loader.close()


# -- the catalogue in a trace of the program ----------------------------------

def _host_threads(xplane):
    """{thread: [(name, start_ns, end_ns)]} of the trace's host plane,
    the program's spans only."""
    data = jax.profiler.ProfileData.from_file(xplane)
    host = data.find_plane_with_name("/host:CPU")
    out = {}
    for i, line in enumerate(host.lines):
        events = [(e.name, e.start_ns, e.start_ns + e.duration_ns)
                  for e in line.events
                  if e.name.startswith(("train/", "input/", "ckpt/"))]
        if events:
            out[i] = sorted(events, key=lambda e: e[1])
    return out


PARENT = {
    "train/data": "train/step", "train/dispatch": "train/step",
    "train/flush": "train/step", "ckpt/save": "train/step",
    "input/produce": "train/data", "input/put": "train/data",
    "train/flush_fetch": "train/flush", "train/flush_publish": "train/flush",
    "ckpt/backpressure": "ckpt/save", "ckpt/snapshot": "ckpt/save",
    "ckpt/fetch": "ckpt/write", "ckpt/serialize": "ckpt/write",
    "ckpt/compress": "ckpt/write", "ckpt/file": "ckpt/write",
}


def test_trainer_trace_holds_the_catalogue_and_records_carry_wall_ms(
        tmp_path, monkeypatch):
    """Six LeNet steps, three of them traced, a save after every step
    with a writer slowed to 0.15 s: each save waits for the one before
    (``ckpt/backpressure``), so every write but the last closes inside
    the trace — and the last, still open when the trace stops, is there
    by its ``:begin`` event alone."""
    from pytorch_distributed_nn_tpu.training import checkpoint as ckpt

    real = ckpt.serialize_state

    def slow_serialize(state):
        time.sleep(0.15)
        return real(state)

    monkeypatch.setattr(ckpt, "serialize_state", slow_serialize)
    trainer = Trainer(TrainConfig(
        network="LeNet", dataset="MNIST", batch_size=64, test_batch_size=64,
        lr=0.01, momentum=0.9, max_steps=6, num_workers=2,
        synthetic_size=256, train_dir=str(tmp_path), log_every=2,
        eval_freq=1, profile_steps=3,
        metrics_path=str(tmp_path / "stream.jsonl"),
    ))
    try:
        t0 = time.monotonic()
        history = trainer.train()
    finally:
        trainer.close()

    # the wall clock: its sum is the run, loop entry to the last fetch
    steps = read_stream(str(tmp_path / "stream.jsonl")).steps
    assert [r["step"] for r in steps] == [1, 2, 3, 4, 5, 6]
    run_s = steps[-1]["mono"] - t0
    wall_s = sum(r["wall_ms"] for r in history) / 1000.0
    assert wall_s == pytest.approx(run_s, rel=0.05)
    assert all(r["step_time"] > 0 for r in history)  # kept, held to nothing
    # the dispatch stamps: every record has its gap, and their sum is the
    # run from loop entry to the last dispatch returning (the last flush
    # and the drain of the last save come after it)
    gaps_s = sum(r["dispatch_gap_ms"] for r in steps) / 1000.0
    assert all(r["dispatch_gap_ms"] > 0 for r in steps)
    assert 0.5 * run_s < gaps_s <= run_s

    found = glob.glob(str(tmp_path / "profile/plugins/profile/*/*.xplane.pb"))
    assert len(found) == 1
    threads = _host_threads(found[0])
    closed = {i: [e for e in ev if not e[0].endswith(spans.BEGIN)]
              for i, ev in threads.items()}
    by_thread = {i: {e[0] for e in ev} for i, ev in closed.items()}
    loop = max(closed, key=lambda i: sum(e[0] == "train/step" for e in closed[i]))
    writer = next(i for i in closed if "ckpt/write" in by_thread[i])
    assert writer != loop
    for name, thread, _ in spans.CATALOGUE:
        where = loop if thread == "loop" else writer
        assert name in by_thread[where], (name, thread)
    assert by_thread[loop] | by_thread[writer] == spans.NAMES

    # children inside parents: nesting is lexical on each thread (the
    # iteration that starts the trace opened its train/step before it)
    traced_from = min(e[1] for e in threads[loop]
                      if e[0] == "train/step" + spans.BEGIN)
    for i in (loop, writer):
        for name, start, end in closed[i]:
            parent = PARENT.get(name)
            if parent is None or start < traced_from:
                continue
            inside = [p for p in closed[i]
                      if p[0] == parent and p[1] <= start and end <= p[2]]
            # a child may close inside the trace while its parent does not
            # (the iteration that stops the trace, the last write)
            opened = [p for p in threads[i]
                      if p[0] == parent + spans.BEGIN and p[1] <= start]
            assert inside or opened, (name, start)
    # a span open when the trace stopped left its :begin and nothing else
    for i, name in ((loop, "train/step"), (writer, "ckpt/write")):
        begun = sum(e[0] == name + spans.BEGIN for e in threads[i])
        assert begun == sum(e[0] == name for e in closed[i]) + 1, name

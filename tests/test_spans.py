"""The training spans (observability/spans.py): the primitive, the
catalogue's sites in the step loop, the loaders and the checkpoint writer
as a profiler trace shows them, the clocks that were repaired with it
(``wall_ms``, the efficiency gauges, ``last_wait_ms``), and set-up's spans
with the compile listener that charges them (observability/compiles.py)."""

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
    assert len(spans.NAMES) == len(spans.CATALOGUE) == 22
    assert len(spans.SETUP_NAMES) == 6
    with pytest.raises(ValueError, match="SetupLog"):
        spans.span("setup/init")            # a run's SetupLog opens those
    with pytest.raises(ValueError, match="setup/"):
        spans.SetupLog(core.MetricRegistry()).span("train/step")


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
    # set-up precedes the traced window: every other name is in it
    for name, thread, _ in spans.CATALOGUE:
        if name in spans.SETUP_NAMES:
            continue
        where = loop if thread == "loop" else writer
        assert name in by_thread[where], (name, thread)
    assert by_thread[loop] | by_thread[writer] == spans.NAMES - spans.SETUP_NAMES

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


# -- set-up: setup/* spans and the compile listener ---------------------------

def _lenet(tmp_path, name="stream.jsonl", **kw):
    return Trainer(TrainConfig(**{
        "network": "LeNet", "dataset": "MNIST", "batch_size": 16,
        "test_batch_size": 16, "lr": 0.01, "max_steps": 4, "num_workers": 2,
        "synthetic_size": 64, "log_every": 2, "data_layout": "device",
        "train_dir": str(tmp_path), "metrics_path": str(tmp_path / name),
        **kw}))


def _events(path, etype):
    return [e for e in read_stream(str(path)).events if e["type"] == etype]


def _compiled(events):
    return sum(s["programs"]["compiled"] for e in events for s in e["spans"])


def _programs(registry, source="compiled"):
    counter = registry.get("programs_total", labels={"source": source})
    return counter.value if counter else 0


def test_setup_spans_nest_inside_init_and_go_out_as_events(tmp_path, telemetry):
    trainer = _lenet(tmp_path)
    try:
        [init_event] = _events(tmp_path / "stream.jsonl", "setup")
        trainer.train()
        trainer.start_step, trainer.config.max_steps = 4, 6
        trainer.train()
    finally:
        trainer.close()
    events = _events(tmp_path / "stream.jsonl", "setup")
    assert len(events) == 3 and events[0] == init_event
    by_name = {s["name"]: s for s in init_event["spans"]}
    assert set(by_name) == {"setup/init", "setup/model", "setup/step_build",
                            "setup/data", "setup/step_cost"}
    init = by_name.pop("setup/init")
    assert init["parent"] is None and init_event.get("step") is None
    for s in by_name.values():
        assert s["parent"] == "setup/init"
        assert init["mono0"] <= s["mono0"] <= s["mono1"] <= init["mono1"]
    children = sorted(by_name.values(), key=lambda s: s["mono0"])
    assert all(a["mono1"] <= b["mono0"] for a, b in zip(children, children[1:]))
    assert sum(s["seconds"] for s in children) <= init["seconds"]
    # one more event per train() call, when its first iteration closes
    assert [e["step"] for e in events[1:]] == [1, 5]
    for e in events[1:]:
        [first] = e["spans"]
        assert first["name"] == "setup/first_step"
        assert first["parent"] == "train/step"
        assert first["mono0"] >= init["mono1"]
    # the spans fed the run's registry, not the default installed before it
    assert _phase(telemetry, "setup/model") is None
    assert trainer.telemetry.registry.get(
        "phase_seconds", labels={"phase": "setup/model"}).count == 1
    assert trainer.telemetry.registry.get(
        "phase_seconds", labels={"phase": "setup/first_step"}).count == 2


def test_a_cold_first_step_compiles_and_a_second_call_compiles_nothing(tmp_path):
    trainer = _lenet(tmp_path)
    try:
        trainer.train()
        trainer.start_step, trainer.config.max_steps = 4, 6
        trainer.train()
        registry = trainer.telemetry.registry
    finally:
        trainer.close()
    stream = tmp_path / "stream.jsonl"
    _, call1, call2 = _events(stream, "setup")
    [first] = call1["spans"]
    assert first["programs"]["compiled"] >= 1
    assert first["compile_s"]["backend"] > 0
    assert any(f["span"] == "setup/first_step" and f["source"] == "compiled"
               for f in call1["slowest"])
    [again] = call2["spans"]
    assert again["programs"] == {"compiled": 0, "cached": 0, "lowered": 0}
    assert sum(again["compile_s"].values()) == 0
    # the steady loop made no program: no compile event in the stream
    assert _events(stream, "compile") == []
    # the registry holds what the stream does
    events = _events(stream, "setup")
    assert _programs(registry) == _compiled(events)
    backend = sum(s["compile_s"]["backend"] for e in events for s in e["spans"])
    assert registry.get("compile_seconds", labels={"stage": "backend"}
                        ).sum == pytest.approx(backend)


def _plant(trainer, at_call: int):
    """Make the loader's ``at_call``-th draw run a jitted function no one
    has called yet: one program compiled inside the step loop."""
    import numpy as np

    def planted(x):
        return x * 3 + 1

    real = trainer.train_loader.next_indices
    calls = []

    def next_indices():
        calls.append(1)
        if len(calls) == at_call:
            jax.block_until_ready(jax.jit(planted)(np.ones(7, np.float32)))
        return real()

    trainer.train_loader.next_indices = next_indices


def test_a_recompile_in_the_loop_is_one_compile_event_at_its_step(tmp_path):
    trainer = _lenet(tmp_path)
    try:
        assert trainer._fused_step is not None
        _plant(trainer, at_call=3)       # the draw of the third step
        trainer.train()
    finally:
        trainer.close()
    [event] = _events(tmp_path / "stream.jsonl", "compile")
    assert event["step"] == 3
    assert event["fun_name"] == "planted"
    assert event["source"] == "compiled"
    assert set(event["compile_s"]) == {"trace", "lower", "backend"}
    assert event["lowered"] == 1
    assert event["compile_s"]["backend"] > 0


def test_two_trainers_in_one_process_count_each_program_once(tmp_path):
    a = _lenet(tmp_path / "a")
    try:
        before = _programs(a.telemetry.registry)
        b = _lenet(tmp_path / "b")
        try:
            _plant(b, at_call=2)
            b.train()
        finally:
            b.close()
        # b's set-up and b's loop went to b alone
        assert _programs(a.telemetry.registry) == before
        assert len(_events(tmp_path / "b" / "stream.jsonl", "compile")) == 1
        a.train()
        registry = a.telemetry.registry
    finally:
        a.close()
    events = _events(tmp_path / "a" / "stream.jsonl", "setup")
    assert _events(tmp_path / "a" / "stream.jsonl", "compile") == []
    assert _programs(registry) == _compiled(events)
    assert len(events) == 2


def test_a_nested_trace_is_counted_once_and_a_fetch_is_cached():
    from pytorch_distributed_nn_tpu.observability import compiles

    import numpy as np

    compiles.install()
    compiles.install()                   # once a process, however asked

    def inner(x):
        time.sleep(0.05)                 # while being traced
        return x * 2

    def outer(x):
        return jax.jit(inner)(x) + 1

    log = spans.SetupLog(core.MetricRegistry())
    with log.span("setup/model") as s:
        jax.jit(outer)(np.ones(3, np.float32))
    tally = s.compiles
    assert tally.programs == {"compiled": 1, "cached": 0, "lowered": 1}
    assert tally.funs["outer"][1] == "compiled"
    # inner's trace ran inside outer's: its 50 ms are counted once
    assert 0.05 <= tally.seconds["trace"] < 0.09
    assert sum(tally.seconds.values()) <= s.seconds

    # a backend stage with a cache hit inside is a fetch
    backend = next(k for k, v in compiles.STAGE.items() if v == "backend")
    with log.span("setup/data") as s:
        compiles._opened(backend, 0.0)
        compiles._event(compiles.HIT)
        compiles._duration(compiles.FETCH, 0.25)
        compiles._closed(backend, 10.0, 10.5, fun_name="jit(step)")
    assert s.compiles.programs == {"compiled": 0, "cached": 1, "lowered": 0}
    assert s.compiles.fetch_s == 0.25
    assert s.compiles.funs == {"step": [0.5, "cached"]}
    assert _programs(log.registry, "cached") == 1

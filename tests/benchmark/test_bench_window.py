"""The window arithmetic on synthetic streams: what counts as the
measured window, and every way it can be invalid."""

import json
import types

import pytest

from benchmark import window


def stream(steps=200, log_every=20, step_s=0.1, flush_s=0.05, start=100.0,
           saves=(), save_stall_s=0.0, write_s=1.0, drop=(), nan_at=None):
    """A run's records: ``step`` records stamped as a flush writes them
    (all of a window's records right after its blocking fetch), plus
    ``checkpoint_write`` events for ``saves``."""
    records = [{"kind": "manifest"}]
    t = start
    for s in range(1, steps + 1):
        t += step_s
        if s % log_every == 0:
            t += flush_s
            for k in range(s - log_every + 1, s + 1):
                if k in drop:
                    continue
                records.append({
                    "kind": "step", "step": k, "mono": t + 1e-6 * (k % log_every),
                    "loss": float("nan") if k == nan_at else 2.0,
                    "step_time": step_s, "input_wait_ms": 0.5,
                })
            if s in saves:
                t += save_stall_s
                records.append({
                    "kind": "event", "type": "checkpoint_write", "step": s,
                    "mono": t + write_s, "stall_ms": 3.0,
                    "write_ms": 1000 * write_s * 0.8,
                    "fetch_ms": 1000 * write_s * 0.2,
                })
    return records


def measure(records, **kw):
    args = dict(first_step=40, log_every=20, global_batch=32, seconds=10.0)
    args.update(kw)
    return window.measure(records, **args)


def test_window_is_whole_log_windows_inside_seconds():
    # a window is 20 x 0.1 + 0.05 = 2.05 s: four close within 10 s
    w = measure(stream(), seconds=10.0)
    assert (w.first_step, w.last_step) == (40, 120)
    assert w.n_steps == 80 and w.samples == 80 * 32
    assert w.wall_s == pytest.approx(4 * 2.05)
    assert w.samples_per_s == pytest.approx(80 * 32 / (4 * 2.05))
    assert len(w.walls) == 4 and w.walls[0] == pytest.approx(2.05)
    # four windows are too few to be a measurement
    assert any("want 5" in p for p in w.problems)
    assert not w.ok


def test_a_long_enough_window_is_valid():
    w = measure(stream(), seconds=12.5)
    assert len(w.walls) == 6 and w.problems == [] and w.ok
    assert w.attempted == 120 and w.failed == 0


def test_clock_is_the_stamp_not_the_trainers_step_time():
    # the trainer's own step_time (0.1 s) leaves the flush out; the stamps
    # do not
    w = measure(stream(flush_s=0.4), seconds=100.0)
    assert w.wall_s / w.n_steps == pytest.approx(0.1 + 0.4 / 20)


@pytest.mark.parametrize("kw, expect", [
    (dict(drop=range(81, 101)), "no closing flush at step 100"),
    (dict(drop=(95,)), "step 95 has 0 records"),
])
def test_missing_records_invalidate_the_window(kw, expect):
    w = measure(stream(**kw), seconds=100.0)
    assert any(expect in p for p in w.problems), w.problems
    assert not w.ok


def test_a_compilation_inside_the_window_invalidates_it():
    records = stream()
    w = measure(records, seconds=100.0)
    assert w.ok
    inside = (w.opened + w.closed) / 2
    assert not measure(records, seconds=100.0, compiles=[inside]).ok
    # before the opening stamp (warm-up) or after the closing one: fine
    assert measure(records, seconds=100.0,
                   compiles=[w.opened - 1.0, w.closed + 1.0]).ok


def test_a_nonfinite_loss_is_a_failed_step():
    w = measure(stream(nan_at=77), seconds=100.0)
    assert w.failed == 1 and w.failed_steps == 1 and not w.ok
    assert w.attempted == w.n_steps


def test_warm_up_that_never_ended():
    w = measure(stream(steps=20))
    assert not w.ok and w.samples_per_s is None


def test_saves_are_counted_and_must_publish():
    saves = (40, 80, 120, 160)
    w = measure(stream(saves=saves, save_stall_s=0.3), seconds=100.0,
                eval_freq=40)
    # the save of step s begins right after s's closing flush: 40, 80, 120
    # and 160 begin inside [40, 200); 200's would begin after the window
    assert w.saves_started == [40, 80, 120, 160]
    assert [e["step"] for e in w.saves] == [40, 80, 120, 160]
    assert w.attempted == 160 + 4 and w.failed == 0 and w.ok
    # the stall is billed to the log window after the save
    assert w.walls[0] == pytest.approx(2.05 + 0.3)
    assert w.walls[1] == pytest.approx(2.05)
    # a save that never published is a failure
    unpublished = [r for r in stream(saves=saves) if not (
        r.get("type") == "checkpoint_write" and r["step"] == 120)]
    w = measure(unpublished, seconds=100.0, eval_freq=40)
    assert w.failed_saves == 1 and w.failed == 1 and not w.ok


def _ckpt_cost(records, **kw):
    from benchmark.readers import stream as readers

    w = measure(records, seconds=100.0, **kw)
    ctx = types.SimpleNamespace(result={"window": w, "records": records})
    return w, readers.ckpt_cost_ms_per_save(ctx)


@pytest.mark.parametrize("write_s", [
    1.0,    # the writer is done within the window its save began in
    2.4,    # ... spills 50 ms into the following window (on the chip the
            # write is 2.93-3.03 s of a 3.08 s window: a step 5 % faster
            # and it spills)
    5.0,    # ... outlasts the whole save period: it is never idle
])
def test_ckpt_cost_is_read_wherever_the_writer_thread_is(write_s):
    # what a save costs the step loop here is its 0.3 s stall, in the
    # window it begins in; the baseline is the windows no save began in
    w, cost = _ckpt_cost(
        stream(saves=(40, 80, 120, 160), save_stall_s=0.3, write_s=write_s),
        eval_freq=40)
    free = window.walls_without_a_save(w)
    assert len(free) == 4 and all(f == pytest.approx(2.05) for f in free)
    assert cost == pytest.approx(300.0)


def test_ckpt_cost_says_nothing_without_a_window_no_save_began_in():
    # eval_freq = log_every: every log window begins a save
    saves = tuple(range(20, 201, 20))
    w, cost = _ckpt_cost(stream(saves=saves, save_stall_s=0.3), eval_freq=20)
    assert len(w.saves_started) == len(w.walls) == 8
    assert window.walls_without_a_save(w) == [] and cost is None
    # and no save at all: nothing either
    w, cost = _ckpt_cost(stream())
    assert w.saves_started == [] and cost is None


def test_read_stream_drops_a_torn_tail(tmp_path):
    path = tmp_path / "s.jsonl"
    good = stream(steps=20)
    path.write_text("\n".join(json.dumps(r) for r in good) + '\n{"kind": "st')
    assert window.read_stream(str(path)) == json.loads(json.dumps(good))

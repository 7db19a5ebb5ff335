"""The step program (training/trainer.py ``_StepProgram``): a Trainer
traces and lowers the step its loop dispatches once, reads the manifest's
``step_cost`` from that ``Lowered``, compiles it at the first dispatch and
dispatches the ``Compiled`` from then on, in every ``train()`` call.
Lowerings are counted through the compile listener
(observability/compiles.py), which charges each ``lower`` stage to the
``setup/*`` span it fell in."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from pytorch_distributed_nn_tpu.observability import spans
from pytorch_distributed_nn_tpu.observability.reader import (
    read_stream,
    render_summary,
    summarize_run,
)
from pytorch_distributed_nn_tpu.training.trainer import TrainConfig, Trainer

TEXT = {"network": "BertTiny", "dataset": "MLMSynth", "seq_len": 32,
        "optimizer": "adam"}
# the device-layout image path (the fused step), a text path, and the
# text path on a four-device mesh
PATHS = {
    "device_images": {},
    "text": TEXT,
    "text_dp4": {**TEXT, "num_workers": 4, "batch_size": 8},
}


def _trainer(tmp_path, path, **kw):
    return Trainer(TrainConfig(**{
        "network": "LeNet", "dataset": "MNIST", "batch_size": 16,
        "test_batch_size": 8, "lr": 0.01, "max_steps": 4, "num_workers": 2,
        "synthetic_size": 64, "log_every": 2, "data_layout": "device",
        "train_dir": str(tmp_path),
        "metrics_path": str(tmp_path / "stream.jsonl"),
        **PATHS[path], **kw}))


@pytest.fixture
def lowerings(monkeypatch):
    """The fun_name of every ``lower`` stage the compile listener charges,
    in a span or outside every span."""
    seen = []
    add = spans.CompileTally.add

    def counting(self, stage, seconds, fun_name, source=None):
        if stage == "lower":
            seen.append(fun_name)
        add(self, stage, seconds, fun_name, source)

    monkeypatch.setattr(spans.CompileTally, "add", counting)
    return seen


def _setup_events(tmp_path):
    return [e for e in read_stream(str(tmp_path / "stream.jsonl")).events
            if e["type"] == "setup"]


def _two_calls(trainer):
    history = trainer.train()
    trainer.start_step, trainer.config.max_steps = 4, 6
    return history + trainer.train()


@pytest.mark.parametrize("path", sorted(PATHS))
def test_a_trainer_lowers_its_step_once_in_step_cost(tmp_path, lowerings,
                                                    path):
    trainer = _trainer(tmp_path, path)
    try:
        name = trainer._step._fn.__name__
        assert lowerings.count(name) == 1       # in the constructor
        history = _two_calls(trainer)
        # every step went through the held Compiled, none through the jit
        assert trainer._step._fn._cache_size() == 0
    finally:
        trainer.close()
    assert lowerings.count(name) == 1           # and nowhere after
    assert [r["step"] for r in history] == [1, 2, 3, 4, 5, 6]
    init, call1, call2 = _setup_events(tmp_path)
    by_name = {s["name"]: s for s in init["spans"]}
    assert by_name["setup/step_cost"]["programs"]["lowered"] == 1
    for event in (call1, call2):
        [first] = event["spans"]
        assert first["programs"]["lowered"] == 0
    # the first dispatch compiles (or fetches) the one lowering; the
    # second call makes no program at all
    [first] = call1["spans"]
    assert first["programs"]["compiled"] + first["programs"]["cached"] == 1
    [again] = call2["spans"]
    assert again["programs"] == {"compiled": 0, "cached": 0, "lowered": 0}


@pytest.mark.parametrize("path", ["device_images", "text"])
def test_the_step_program_trains_bitwise_as_the_jitted_step(tmp_path, path):
    """The loop through the held ``Compiled`` and the loop through the
    jitted function it was lowered from: the same losses and the same
    parameters, bit for bit."""

    def run(d, direct):
        trainer = _trainer(d, path)
        if direct:
            trainer._step = trainer._step._fn
        try:
            history = _two_calls(trainer)
            params = jax.device_get(trainer.state.params)
        finally:
            trainer.close()
        return [r["loss"] for r in history], params

    held_losses, held = run(tmp_path / "held", direct=False)
    jit_losses, jitted = run(tmp_path / "jit", direct=True)
    assert held_losses == jit_losses
    assert jax.tree.structure(held) == jax.tree.structure(jitted)
    for a, b in zip(jax.tree.leaves(held), jax.tree.leaves(jitted)):
        np.testing.assert_array_equal(a, b)


def test_the_step_program_refuses_a_batch_it_was_not_lowered_for(tmp_path):
    """A batch of another shape is an error, not a second program."""
    trainer = _trainer(tmp_path, "text")
    try:
        _two_calls(trainer)
        c = trainer.config
        tokens = jnp.zeros((c.batch_size, trainer.seq_len + 8), jnp.int32)
        with pytest.raises(TypeError, match="compiled"):
            trainer._step(trainer.state, (tokens, tokens),
                          jax.random.PRNGKey(0))
        assert trainer._step._fn._cache_size() == 0
    finally:
        trainer.close()


def test_a_sinkless_trainer_lowers_its_step_at_the_first_dispatch(
        tmp_path, lowerings):
    trainer = _trainer(tmp_path, "device_images", metrics_path=None)
    try:
        name = trainer._step._fn.__name__
        assert "step_cost" not in (trainer.telemetry.manifest or {})
        assert lowerings.count(name) == 0
        history = _two_calls(trainer)
    finally:
        trainer.close()
    assert lowerings.count(name) == 1
    assert len(history) == 6
    assert all(np.isfinite(r["loss"]) for r in history)


def test_the_manifest_prices_the_text_step_as_the_bare_lowering_did(
        tmp_path):
    """A text Trainer's ``step_cost`` FLOPs, read from the lowering the
    loop compiles (arguments with their shardings), equal those of the
    step lowered on bare shapes, as the manifest was priced before."""
    from pytorch_distributed_nn_tpu.analysis import costmodel

    trainer = _trainer(tmp_path, "text")
    try:
        cost = trainer.telemetry.manifest["step_cost"]

        def bare(a):
            return jax.ShapeDtypeStruct(a.shape, a.dtype)

        c = trainer.config
        tok = jax.ShapeDtypeStruct((c.batch_size, trainer.seq_len), jnp.int32)
        lowered = trainer.train_step.lower(
            jax.tree.map(bare, trainer.state), (tok, tok),
            bare(jax.random.PRNGKey(0)))
    finally:
        trainer.close()
    analysis = lowered.cost_analysis()
    analysis = analysis[0] if isinstance(analysis, (list, tuple)) else analysis
    before = costmodel.step_cost_from_hlo(
        lowered.as_text(dialect="hlo"), xla_flops=analysis.get("flops"),
        source="lowered")
    assert cost["source"] == "lowered"
    assert cost["flops"] == before.flops > 0


def test_obs_summary_prints_the_lowerings_of_each_setup_span(tmp_path):
    trainer = _trainer(tmp_path, "device_images")
    try:
        _two_calls(trainer)
    finally:
        trainer.close()
    text = render_summary(summarize_run(read_stream(
        str(tmp_path / "stream.jsonl"))))
    [line] = [ln for ln in text.splitlines() if ln.startswith("setup:")]
    fields = {p.split(" ")[0]: p for p in
              line[len("setup: "):].split(" · ")[0].split(", ")}
    assert fields["step_cost"].endswith("(1 lowered)")
    assert fields["first_step@1"].endswith("(0 lowered)")
    assert fields["first_step@5"].endswith("(0 lowered)")

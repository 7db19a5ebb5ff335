"""Checkpoint + native codec tests.

Covers: atomic `model_step_<N>` save/restore with optimizer state (resume —
the capability the reference lacked, SURVEY.md §5), and the C++ host codec
(reference: src/compression.py via c-blosc)."""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from pytorch_distributed_nn_tpu.models import build_model
from pytorch_distributed_nn_tpu.ops import host_codec
from pytorch_distributed_nn_tpu.optim import build_optimizer
from pytorch_distributed_nn_tpu.parallel import make_grad_sync
from pytorch_distributed_nn_tpu.training import checkpoint as ckpt
from pytorch_distributed_nn_tpu.training import create_train_state


@pytest.fixture(scope="module")
def small_state():
    model = build_model("LeNet", 10)
    opt = build_optimizer("sgd", 0.1, momentum=0.9)
    sync = make_grad_sync("allreduce")
    return model, opt, sync, create_train_state(
        model, opt, sync, jax.random.PRNGKey(0), (28, 28, 1)
    )


def test_codec_available_and_roundtrip():
    assert host_codec.available(), "native codec failed to build"
    a = np.random.RandomState(0).randn(257, 33).astype(np.float32)
    assert (host_codec.w_decompress(host_codec.w_compress(a)) == a).all()
    b = np.arange(1000, dtype=np.int64)
    out = host_codec.w_decompress(host_codec.w_compress(b))
    assert out.dtype == b.dtype and (out == b).all()


def test_codec_compresses_structured_data():
    # smooth data (like trained weights) must compress well with byteshuffle
    a = np.linspace(0, 1, 100_000, dtype=np.float32)
    blob = host_codec.w_compress(a)
    assert len(blob) < a.nbytes / 2


def test_checkpoint_roundtrip(tmp_path, small_state):
    model, opt, sync, state = small_state
    state = state.replace(step=jnp.int32(42))
    path = ckpt.save_checkpoint(str(tmp_path), state)
    assert path.endswith("model_step_42")
    template = create_train_state(
        model, opt, sync, jax.random.PRNGKey(1), (28, 28, 1)
    )
    restored = ckpt.restore_checkpoint(path, template)
    assert int(restored.step) == 42
    for a, b in zip(jax.tree.leaves(state.params), jax.tree.leaves(restored.params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # optimizer state (momentum buffers) must survive — resume capability
    for a, b in zip(
        jax.tree.leaves(state.opt_state), jax.tree.leaves(restored.opt_state)
    ):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_checkpoint_uncompressed_roundtrip(tmp_path, small_state):
    model, opt, sync, state = small_state
    path = ckpt.save_checkpoint(str(tmp_path), state, step=7, compress=False)
    restored = ckpt.restore_checkpoint(path, state)
    assert int(restored.step) == int(state.step)


def test_latest_step_and_restore_latest(tmp_path, small_state):
    *_, state = small_state
    assert ckpt.latest_step(str(tmp_path)) is None
    ckpt.save_checkpoint(str(tmp_path), state, step=10)
    ckpt.save_checkpoint(str(tmp_path), state, step=30)
    ckpt.save_checkpoint(str(tmp_path), state, step=20)
    assert ckpt.latest_step(str(tmp_path)) == 30
    restored = ckpt.restore_latest(str(tmp_path), state)
    assert restored is not None


def test_no_tmp_files_left(tmp_path, small_state):
    *_, state = small_state
    ckpt.save_checkpoint(str(tmp_path), state, step=1)
    assert not [f for f in os.listdir(tmp_path) if f.endswith(".tmp")]


def test_bad_magic_rejected(tmp_path, small_state):
    *_, state = small_state
    p = tmp_path / "model_step_5"
    p.write_bytes(b"XXXXjunk")
    with pytest.raises(ValueError):
        ckpt.restore_checkpoint(str(p), state)


# ---------------------------------------------------------------------------
# Sharded checkpoints (GSPMD path) — round-3 verdict item 3
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def spmd_state():
    """BertTiny state sharded over an 8-device (data=2, seq=2, model=2)
    mesh — tp-sharded params, the case where a full-state gather is the
    pod-scale killer."""
    from pytorch_distributed_nn_tpu.parallel import make_mesh
    from pytorch_distributed_nn_tpu.training.spmd import create_spmd_state

    model = build_model("BertTiny", 10, vocab_size=64, max_len=32)
    opt = build_optimizer("adam", 1e-3)
    mesh = make_mesh(2, 2, 2)
    state, shardings = create_spmd_state(
        model, opt, jax.random.PRNGKey(0), (8, 32), mesh
    )
    return model, opt, mesh, state, shardings


def _assert_states_equal(a, b):
    la, lb = jax.tree.leaves(a), jax.tree.leaves(b)
    assert len(la) == len(lb)
    for x, y in zip(la, lb):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def test_sharded_checkpoint_roundtrip_bit_exact(tmp_path, spmd_state):
    model, opt, mesh, state, shardings = spmd_state
    state = state.replace(step=jnp.int32(12))
    path = ckpt.save_sharded(str(tmp_path), state)
    assert path.endswith("model_step_12") and os.path.isdir(path)
    assert ckpt.latest_step(str(tmp_path)) == 12

    restored = ckpt.restore_sharded(path, state, shardings)
    _assert_states_equal(state, restored)
    # shardings land back on the mesh, not replicated
    specs = jax.tree.leaves(
        jax.tree.map(lambda x: str(x.sharding.spec), restored.params)
    )
    assert any("model" in s for s in specs)


def test_sharded_save_never_gathers(tmp_path, spmd_state, monkeypatch):
    """The save path must not materialize global state on any host: no
    process_allgather, and total bytes written ~= one copy of the state
    (each unique shard exactly once), not num_devices copies."""
    from jax.experimental import multihost_utils

    def boom(*a, **k):
        raise AssertionError("save path called process_allgather")

    monkeypatch.setattr(multihost_utils, "process_allgather", boom)
    *_, state, shardings = spmd_state
    path = ckpt.save_sharded(str(tmp_path), state, step=1)

    state_bytes = sum(
        np.asarray(l).nbytes if not isinstance(l, jax.Array)
        else l.size * l.dtype.itemsize
        for l in jax.tree.leaves(state)
    )
    written = 0
    for fname in os.listdir(path):
        if fname.endswith(".npz"):
            with np.load(os.path.join(path, fname)) as z:
                written += sum(z[k].nbytes for k in z.files)
    # replicated leaves are written once, sharded leaves shard-by-shard:
    # total must be ~one state, never the 8x of a per-device dump
    assert written <= state_bytes * 1.01


def test_sharded_restore_reshards_onto_different_topology(
    tmp_path, spmd_state
):
    """Topology-change restore: save from tp=2 mesh, restore onto a pure-DP
    mesh (the evaluator case) via the file/dir-dispatching
    restore_checkpoint."""
    from pytorch_distributed_nn_tpu.parallel import make_mesh
    from pytorch_distributed_nn_tpu.training.spmd import create_spmd_state

    model, opt, mesh, state, shardings = spmd_state
    path = ckpt.save_sharded(str(tmp_path), state, step=3)

    # host-array template with a DIFFERENT optimizer (evaluator contract)
    sync = make_grad_sync("allreduce")
    template = create_train_state(
        model, build_optimizer("sgd", 0.1), sync, jax.random.PRNGKey(1),
        (32,), input_dtype=jnp.int32,
    )
    restored = ckpt.restore_checkpoint(path, template, params_only=True)
    for (ka, a), (kb, b) in zip(
        jax.tree_util.tree_leaves_with_path(state.params),
        jax.tree_util.tree_leaves_with_path(restored.params),
    ):
        assert jax.tree_util.keystr(ka) == jax.tree_util.keystr(kb)
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    # and onto a different mesh sharding (dp-only)
    mesh2 = make_mesh(8, 1, 1)
    state2, shardings2 = create_spmd_state(
        model, opt, jax.random.PRNGKey(2), (8, 32), mesh2
    )
    restored2 = ckpt.restore_sharded(path, state2, shardings2)
    _assert_states_equal(state, restored2)


def test_sharded_restore_rejects_mismatched_tree(tmp_path, spmd_state):
    model, opt, mesh, state, shardings = spmd_state
    path = ckpt.save_sharded(str(tmp_path), state, step=5)
    bigger = build_model("BertTiny", 10, vocab_size=128, max_len=32)
    from pytorch_distributed_nn_tpu.training.spmd import create_spmd_state

    state2, shardings2 = create_spmd_state(
        bigger, opt, jax.random.PRNGKey(0), (8, 32), mesh
    )
    with pytest.raises(Exception):  # shape mismatch must not restore silently
        r = ckpt.restore_sharded(path, state2, shardings2)
        jax.block_until_ready(jax.tree.leaves(r))


def test_sharded_restore_rejects_missing_shard_files(tmp_path, spmd_state):
    """A partially-copied checkpoint (fewer shard files than the writing
    process count) must fail loudly, never zero-fill the gaps."""
    model, opt, mesh, state, shardings = spmd_state
    path = ckpt.save_sharded(str(tmp_path), state, step=7)
    for f in os.listdir(path):
        if f.startswith("shards_p"):
            os.remove(os.path.join(path, f))
    with pytest.raises(ValueError, match="zero-fill"):
        ckpt.restore_sharded(path, state, shardings)


def test_checkpoint_format_mismatch_is_explained(tmp_path, spmd_state):
    """Switching tp/sp config over an existing train_dir produces clear
    errors, not IsADirectoryError/NotADirectoryError."""
    model, opt, mesh, state, shardings = spmd_state
    # sharded DIRECTORY exists; a replicated save to the same step must
    # explain the config mismatch
    ckpt.save_sharded(str(tmp_path), state, step=9)
    from pytorch_distributed_nn_tpu.training.train_step import TrainState

    host_state = TrainState(
        step=jnp.int32(9), params={"w": jnp.zeros(3)}, opt_state={},
        batch_stats={}, ef_state=None,
    )
    with pytest.raises(ValueError, match="DIRECTORY"):
        ckpt.save_checkpoint(str(tmp_path), host_state, step=9)
    # replicated FILE exists; a sharded restore must explain likewise
    fpath = ckpt.save_checkpoint(str(tmp_path), host_state, step=11)
    with pytest.raises(ValueError, match="FILE"):
        ckpt.restore_sharded(fpath, state, shardings)


# ---------------------------------------------------------------------------
# The writer off the interpreter lock (PR 31): flax's bytes, the parent's
# file, and no state-sized pass with the lock held
# ---------------------------------------------------------------------------


def _lenet_state(opt_name, sync=None, num_replicas=1, **opt_kw):
    model = build_model("LeNet", 10)
    return create_train_state(
        model, build_optimizer(opt_name, 0.1, **opt_kw),
        sync or make_grad_sync("allreduce"), jax.random.PRNGKey(0),
        (28, 28, 1), num_replicas=num_replicas,
    )


def _resnet_sgd_state():
    # what the benchmark's checkpoint cell saves: params, momentum,
    # batch_stats
    model = build_model("ResNet18", 10)
    state = create_train_state(
        model, build_optimizer("sgd", 0.1, momentum=0.9),
        make_grad_sync("allreduce"), jax.random.PRNGKey(0), (32, 32, 3),
    )
    assert jax.tree.leaves(state.batch_stats)
    return jax.device_get(state)


def _ef_state():
    state = _lenet_state(
        "sgd", make_grad_sync("allreduce", compression="topk"),
        num_replicas=4, momentum=0.9,
    )
    assert jax.tree.leaves(state.ef_state)
    return jax.device_get(state)


def _odd_dtypes():
    rng = np.random.RandomState(1)
    return {
        "bf16": rng.randn(7, 33).astype(jnp.bfloat16),
        "i32": np.arange(-5, 300, dtype=np.int32).reshape(5, 61),
        "bool": rng.rand(9) > 0.5, "f64": rng.randn(3, 3),
        "big_endian": np.arange(6, dtype=">f4"),
    }


def _zero_d_and_scalars():
    return {
        "f32": np.float32(1.5).reshape(()),  # ext body of 16: a fixext
        "i32": np.int32(7).reshape(()), "i8": np.int8(1).reshape(()),
        "np_scalar": np.float32(2.5), "np_int": np.int64(3),
        "py_int": 3, "py_float": 0.25, "py_bool": True, "py_str": "x",
        "complex": 1 + 2j, "step": jnp.zeros([], jnp.int32),
    }


def _empty_subtrees():
    import optax

    return {
        "empty": {}, "none": None, "tuple": (), "list": [],
        "optax_empty": optax.EmptyState(),
        "nested": {"a": {}, "b": {"c": {}}},
        "zero_size": np.zeros((0, 4), np.float32),
        "after": np.ones(3, np.float32),
    }


def _non_contiguous():
    base = np.random.RandomState(2).randn(64, 48).astype(np.float32)
    leaves = {
        "transposed": base.T, "strided": base[::3, 1::2],
        "fortran": np.asfortranarray(base), "reversed": base[::-1],
        "broadcast": np.broadcast_to(np.float32(3.0), (5, 7)),
    }
    assert not any(v.flags.c_contiguous for v in leaves.values())
    return leaves


def _header_boundaries():
    # bin8 / bin16 / bin32 and fixext / ext8 / ext16 / ext32 on both sides
    # of each limit; 16 keys and more take a map16 header
    sizes = (0, 1, 2, 3, 4, 8, 16, 200, 255, 256, 257, 65500, 65535,
             65536, 70000)
    tree = {f"u8_{n}": np.full(n, 7, np.uint8) for n in sizes}
    tree["long_shape"] = np.ones((1,) * 17, np.float32)  # array16 shape
    assert len(tree) >= 16
    return tree


def _device_leaves():
    # the synchronous path hands the live device state to the writer
    state = _lenet_state("sgd", momentum=0.9)
    assert all(isinstance(x, jax.Array) for x in jax.tree.leaves(state))
    return state


_PAYLOAD_TREES = {
    "sgd_momentum_batch_stats": _resnet_sgd_state,
    "adam_tuple_opt_state": lambda: jax.device_get(_lenet_state("adam")),
    "bfloat16_int32_leaves": _odd_dtypes,
    "zero_d_leaves": _zero_d_and_scalars,
    "empty_subtree": _empty_subtrees,
    "error_feedback_residuals": _ef_state,
    "non_contiguous_leaf": _non_contiguous,
    "header_boundaries": _header_boundaries,
    "device_leaves": _device_leaves,
    "bare_array": lambda: np.arange(12, dtype=np.float32).reshape(3, 4),
}


@pytest.mark.parametrize("case", [*_PAYLOAD_TREES, "flax_fallback_chunked"])
def test_payload_is_flax_msgpack_byte_for_byte(case, monkeypatch):
    from flax import serialization

    if case == "flax_fallback_chunked":
        # a leaf over flax's chunking limit is written as a dict of
        # chunks: the walk hands it to flax's own packer, alone
        monkeypatch.setattr(serialization, "MAX_CHUNK_SIZE", 1000)
        tree = {
            "over": np.arange(1500, dtype=np.float32).reshape(30, 50),
            "at_limit": np.arange(250, dtype=np.float32),
            "under": np.arange(10, dtype=np.int32),
        }
        assert b"__msgpack_chunked_array__" in serialization.to_bytes(tree)
    else:
        tree = _PAYLOAD_TREES[case]()
    want = serialization.to_bytes(tree)
    got = ckpt.serialize_state(tree)
    assert got.dtype == np.uint8 and got.ndim == 1
    assert got.tobytes() == want
    # and it reads back as what flax reads back
    if isinstance(tree, dict):
        back = serialization.msgpack_restore(memoryview(got))
        assert list(back) == list(serialization.msgpack_restore(want))


def _parent_compress(data: bytes, level: int = 1, width: int = 4) -> bytes:
    """``host_codec.compress`` as it was before PR 31, on the library."""
    import ctypes

    lib = host_codec._load()
    cap = lib.pdtn_max_compressed_size(len(data))
    out = ctypes.create_string_buffer(cap)
    size = lib.pdtn_compress(data, len(data), out, cap, level, width)
    assert size >= 0
    header = np.zeros(1, host_codec._HEADER)
    header["orig_size"] = len(data)
    header["width"] = width
    return header.tobytes() + out.raw[:size]


@pytest.mark.parametrize("compress", [True, False],
                         ids=["compressed", "raw"])
def test_file_and_manifest_identical_to_the_parents_way(
        tmp_path, small_state, compress):
    import json
    import zlib

    from flax import serialization

    *_, state = small_state
    state = state.replace(step=jnp.int32(9))
    # the parent's writer: to_bytes, compress, magic, one blob
    payload = serialization.to_bytes(state)
    blob = (b"PDTZ" + _parent_compress(payload)) if compress else (
        b"PDTN" + payload)
    geometry = {"devices": 8, "processes": 1, "mesh": {"data": 8}}
    manifest = json.dumps({
        "format": "pdtn-file-meta-v1", "step": 9, "bytes": len(blob),
        "crc32": zlib.crc32(blob) & 0xFFFFFFFF, "geometry": geometry,
    })
    path = ckpt.save_checkpoint(
        str(tmp_path / "new"), state, compress=compress, geometry=geometry)
    with open(path, "rb") as f:
        assert f.read() == blob
    with open(ckpt.meta_path(path)) as f:
        assert f.read() == manifest
    assert ckpt.verify_checkpoint(path) == (True, "ok")
    # a file the parent wrote restores and verifies under the change
    old = tmp_path / "old" / "model_step_9"
    old.parent.mkdir()
    old.write_bytes(blob)
    (tmp_path / "old" / "model_step_9.meta.json").write_text(manifest)
    assert ckpt.verify_checkpoint(str(old)) == (True, "ok")
    for p in (path, str(old)):
        _assert_states_equal(state, ckpt.restore_checkpoint(p, state))
        assert list(ckpt.load_raw(p)) == list(
            serialization.to_state_dict(state))


@pytest.mark.parametrize("kind", ["bytes", "memoryview", "ndarray"])
def test_codec_roundtrips_from_any_buffer(kind):
    raw = np.random.RandomState(3).randn(5000).astype(np.float32)
    data = raw.tobytes()
    given = {"bytes": data, "memoryview": memoryview(data),
             "ndarray": raw}[kind]
    blob = host_codec.compress(given)
    assert type(blob) is bytes and blob == _parent_compress(data)
    buf = host_codec.compress_buffer(given)
    assert buf.dtype == np.uint8 and buf.tobytes() == blob
    for packed in (blob, memoryview(blob), buf):
        out = host_codec.decompress(packed)
        assert out == data and len(out) == len(data)
        np.testing.assert_array_equal(np.frombuffer(out, np.float32), raw)
    assert host_codec.decompress(host_codec.compress(b"")) == b""
    with pytest.raises(RuntimeError):
        host_codec.decompress(blob[:-7])


def _longest_exclusion(fn, repeats=5):
    """The longest stretch for which a second thread, which does nothing
    but take the interpreter lock again and again, was kept from it
    while ``fn`` ran: the least over ``repeats`` runs, because a hold
    that is in the code shows every time and the host's noise does not."""
    import threading
    import time

    best = float("inf")
    for _ in range(repeats):
        state = {"stop": False, "worst": 0.0}

        def spin():
            last = time.perf_counter()
            while not state["stop"]:
                now = time.perf_counter()
                state["worst"] = max(state["worst"], now - last)
                last = now

        t = threading.Thread(target=spin)
        t.start()
        time.sleep(0.02)
        state["worst"] = 0.0
        try:
            fn()
        finally:
            state["stop"] = True
            t.join()
        best = min(best, state["worst"])
    return best


def test_writer_does_not_hold_the_interpreter_lock():
    from flax import serialization

    # one 32 MB leaf: a leaf-by-leaf packb would hold the lock for all of it
    tree = {"params": {"embedding": np.ones((8, 1024, 1024), np.float32)},
            "step": np.int32(1)}
    want = serialization.to_bytes(tree)
    made = []

    def new_way():
        made.append(host_codec.compress_buffer(ckpt.serialize_state(tree)))

    held_new = _longest_exclusion(new_way)
    held_flax = _longest_exclusion(lambda: serialization.to_bytes(tree))
    assert host_codec.decompress(made[-1]) == want
    # a ratio inside one test, never a time: flax's packer keeps the other
    # thread out for whole copies of the leaf (46-62 ms here, sandbox CPU),
    # the writer for bookkeeping (1-2 ms); one leaf-sized copy under the
    # lock would read a quarter to a third of flax's
    assert held_new < 0.2 * held_flax, (held_new, held_flax)


def test_save_checkpoint_has_no_state_sized_pass_under_the_lock():
    """Structural: the file writer and the codec binding call neither
    flax's whole-tree packer nor ctypes' zero-filling allocator."""
    import inspect

    for fn in (ckpt.save_checkpoint, ckpt.serialize_state,
               ckpt._write_file_meta):
        src = inspect.getsource(fn)
        assert "to_bytes(" not in src, fn.__name__
        assert "create_string_buffer" not in src, fn.__name__
    assert "serialization.to_bytes(" not in inspect.getsource(ckpt)
    assert "create_string_buffer" not in inspect.getsource(host_codec)
    for fn in (host_codec.compress_buffer, host_codec.decompress):
        src = inspect.getsource(fn)
        assert "np.empty(" in src and ".raw" not in src, fn.__name__


def test_checkpoint_write_event_carries_the_writer_spans(
        tmp_path, small_state):
    from pytorch_distributed_nn_tpu.observability import core

    *_, state = small_state
    captured = []
    t = core.Telemetry()
    t.subscribe(captured.append)
    prev = core.install(t)
    try:
        ckpt.save_checkpoint(str(tmp_path), state, step=1)
        ckpt.save_checkpoint(str(tmp_path), state, step=2, compress=False)
    finally:
        core.uninstall(t, prev)
    writes = [e for e in captured if e.get("type") == "checkpoint_write"]
    assert [e["step"] for e in writes] == [1, 2]
    for e in writes:
        parts = e["serialize_ms"] + e["compress_ms"] + e["file_ms"]
        assert 0 < e["serialize_ms"] and 0 < e["file_ms"]
        assert parts <= e["write_ms"] * 1.001 + 0.01
        assert e["bytes"] == os.path.getsize(e["path"])
    assert writes[0]["compress_ms"] > 0 and writes[1]["compress_ms"] == 0.0

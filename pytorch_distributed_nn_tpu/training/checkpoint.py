"""Checkpointing: `model_step_<N>` files/directories + resume.

Capability parity with the reference's checkpoint flow — `torch.save
(state_dict)` to `<train_dir>/model_step_<N>` every `--eval-freq` steps
(reference: src/sync_replicas_master_nn.py:264-270,
src/distributed_worker.py:301-307), consumed by the NFS-polling evaluator
(src/distributed_evaluator.py:108-111) — plus what the reference never had
(SURVEY.md §5): optimizer state, EF residuals, and the step counter are
persisted so training can RESUME exactly, and writes are atomic
(tmp + rename) so a polling evaluator never reads a torn file.

Integrity layer (resilience subsystem, docs/resilience.md): every FILE
checkpoint gets a ``model_step_<N>.meta.json`` manifest (bytes + CRC32);
sharded checkpoints carry per-shard CRC32 entries in their meta.json.
``verify_checkpoint`` convicts truncation/bitflips without a restore,
``quarantine_checkpoint`` moves corrupt entries aside atomically, and
writes retry with backoff (safe: atomicity means a failed attempt never
published). ``save_checkpoint(fault_plan=...)`` is the torn-write
injection hook for the chaos suite.

Two formats under the same `model_step_<N>` naming contract:

- **Replicated** (`save_checkpoint`): one flax-msgpack file, optionally
  compressed with the native host codec (ops/host_codec — the C++
  descendant of the reference's Blosc weight codec, src/compression.py:
  32-46). The shard_map-DP path, where state is replicated anyway.
- **Sharded** (`save_sharded`): a `model_step_<N>/` DIRECTORY where each
  process writes only its addressable, replica-0 parameter shards (one
  .npz per process + meta.json). The GSPMD (tp/sp) path: a tp-sharded
  state is never gathered to any single host — the round-2 build's
  `process_allgather`-then-serialize save was O(model) per host per
  checkpoint, which is exactly what kills pod-scale checkpointing.
  Restore re-shards onto the live mesh (`restore_sharded`), or assembles
  full host arrays for consumers like the polling evaluator
  (`restore_checkpoint` dispatches on file-vs-directory).
"""

from __future__ import annotations

import json
import os
import re
import struct
import time
import zlib
from typing import Optional, Tuple

import jax
import msgpack
import numpy as np
from flax import serialization

from pytorch_distributed_nn_tpu.observability.core import get_telemetry
from pytorch_distributed_nn_tpu.observability.spans import span
from pytorch_distributed_nn_tpu.resilience.retry import retry_call
from pytorch_distributed_nn_tpu.training.train_step import TrainState

_STEP_RE = re.compile(r"^model_step_(\d+)$")
_MAGIC_RAW = b"PDTN"  # raw msgpack
_MAGIC_LZ = b"PDTZ"  # host-codec-compressed msgpack
_SHARDED_FORMAT = "pdtn-sharded-v1"
_FILE_META_FORMAT = "pdtn-file-meta-v1"
_DATA_STATE_FORMAT = "pdtn-data-state-v1"
_PUBLISHED_FORMAT = "pdtn-published-v1"
QUARANTINE_DIR = "quarantine"
#: registry of steps frozen into serving artifacts (serving/artifact.py):
#: ``--keep-last`` GC must never delete the step a published artifact came
#: from — it is the only bit-exact provenance of what is in production.
PUBLISHED_FILE = "published.json"


def checkpoint_path(directory: str, step: int) -> str:
    # naming parity: src/distributed_evaluator.py:113-114
    return os.path.join(directory, f"model_step_{step}")


def mesh_geometry(mesh) -> dict:
    """The geometry record stamped into checkpoint manifests: device count,
    process count and per-axis mesh extents. Elastic resume
    (resilience/elastic.py) compares this against the live fleet to decide
    whether ``--resume`` needs to reshard-on-load."""
    from pytorch_distributed_nn_tpu.parallel.mesh import axis_sizes

    return {
        "devices": int(mesh.devices.size),
        "processes": int(jax.process_count()),
        "mesh": axis_sizes(mesh),
    }


def _default_geometry() -> dict:
    """Geometry for manifest writers whose caller supplied none: the mesh
    factors are unknown, but device/process counts alone already let the
    elastic policy detect a shrunk or regrown fleet."""
    return {
        "devices": int(jax.device_count()),
        "processes": int(jax.process_count()),
    }


def checkpoint_geometry(path: str) -> Optional[dict]:
    """The geometry recorded when checkpoint ``path`` was written, or
    ``None`` (pre-geometry manifests, missing/unreadable sidecar)."""
    meta_file = (
        os.path.join(path, "meta.json") if os.path.isdir(path)
        else meta_path(path)
    )
    try:
        with open(meta_file) as f:
            meta = json.load(f)
    except (OSError, ValueError):
        return None
    geom = meta.get("geometry")
    return dict(geom) if isinstance(geom, dict) else None


def meta_path(path: str) -> str:
    """Integrity-manifest sidecar for a FILE checkpoint.

    ``model_step_<N>.meta.json`` deliberately does NOT match ``_STEP_RE``,
    so manifests never pollute the step scan.
    """
    return path + ".meta.json"


def data_state_path(path: str) -> str:
    """Input-pipeline iterator-state sidecar (docs/data.md):
    ``model_step_<N>.data.json`` carries the data loader's serializable
    iterator state (shard cursor / stream counter / packer carry) so a
    resumed run continues the exact batch sequence. Like the manifest it
    never matches ``_STEP_RE``. Works for both checkpoint formats (next
    to the file, or next to the sharded directory)."""
    return path + ".data.json"


def save_data_state(path: str, state: dict) -> None:
    """Atomically publish the iterator-state sidecar for checkpoint
    ``path``. Small (a shard cursor, not data), written after the
    checkpoint itself: a crash in between leaves a checkpoint without a
    sidecar, which resume treats as legacy (skip-based fast-forward),
    never as corruption."""
    sidecar = data_state_path(path)
    tmp = sidecar + ".tmp"

    def _publish():
        with open(tmp, "w") as f:
            json.dump({"format": _DATA_STATE_FORMAT, "state": state}, f,
                      sort_keys=True)
        os.replace(tmp, sidecar)

    retry_call(_publish, attempts=3, base_delay=0.05, retry_on=(OSError,),
               label=f"data-state write {path}")


def load_data_state(path: str) -> Optional[dict]:
    """The iterator state saved next to checkpoint ``path``, or ``None``
    (missing sidecar = legacy checkpoint; unreadable/mis-formatted =
    warn and fall back — a torn sidecar must cost skip-based resume,
    never the run)."""
    import logging

    sidecar = data_state_path(path)
    if not os.path.isfile(sidecar):
        return None
    try:
        with open(sidecar) as f:
            doc = json.load(f)
    except (OSError, ValueError) as e:
        logging.getLogger(__name__).warning(
            "unreadable iterator-state sidecar %s (%s); resume falls "
            "back to skip-based fast-forward", sidecar, e,
        )
        return None
    if doc.get("format") != _DATA_STATE_FORMAT:
        logging.getLogger(__name__).warning(
            "unknown iterator-state format %r in %s; ignoring",
            doc.get("format"), sidecar,
        )
        return None
    return doc.get("state")


def _codec():
    try:
        from pytorch_distributed_nn_tpu.ops import host_codec

        return host_codec if host_codec.available() else None
    except Exception:
        return None


_EXT_NDARRAY = 1  # flax.serialization._MsgpackExtType.ndarray
_FIXEXT = {1: b"\xd4", 2: b"\xd5", 4: b"\xd6", 8: b"\xd7", 16: b"\xd8"}


def _sized(n: int, codes: bytes) -> bytes:
    """A msgpack header that carries the length ``n`` in 1, 2 or 4 bytes:
    ``codes`` are the three type bytes, smallest first."""
    if n < 1 << 8:
        return codes[0:1] + struct.pack(">B", n)
    if n < 1 << 16:
        return codes[1:2] + struct.pack(">H", n)
    return codes[2:3] + struct.pack(">I", n)


def _ndarray_header(arr: np.ndarray) -> bytes:
    """Everything flax writes for an array leaf before its data: the ext
    header of type ``ndarray``, then the head of the inner msgpack tuple
    ``(shape, dtype.name, bin)`` (flax.serialization._ndarray_to_bytes)."""
    inner = (
        b"\x93" + msgpack.packb(arr.shape) + msgpack.packb(arr.dtype.name)
        + _sized(arr.nbytes, b"\xc4\xc5\xc6")
    )
    n = len(inner) + arr.nbytes
    ext = _FIXEXT.get(n) or _sized(n, b"\xc7\xc8\xc9")
    return ext + struct.pack("b", _EXT_NDARRAY) + inner


def serialize_state(state) -> np.ndarray:
    """The bytes of flax's ``to_bytes`` for ``state``, one for one, as a
    uint8 array, without a pass over the leaves' data under the
    interpreter lock.

    flax packs the whole tree in one ``msgpack.packb`` and copies each
    leaf three to four times on the way, all with the lock held: on the
    async writer's thread that kept the train loop from dispatching for
    as long as the state is large (PERF.md, PR 31). Here the lock is held
    for the map and leaf headers only (small ``bytes``, microseconds a
    leaf whatever its size); each leaf's data goes from the array into
    its slice of one uninitialised buffer by ``np.copyto``, which
    releases the lock. A leaf that is not a plain array flax would pack
    whole (a scalar, ``None``, an object dtype, an array over flax's
    chunking limit) goes through flax's own packer, alone.
    """
    pieces = []  # header bytes, and arrays where their data goes
    map_header = msgpack.Packer(autoreset=True).pack_map_header

    def walk(node):
        if type(node) is dict:
            pieces.append(map_header(len(node)))
            for key, child in node.items():
                pieces.append(msgpack.packb(key))
                walk(child)
            return
        if isinstance(node, jax.Array):
            node = np.asarray(node)
        if (
            type(node) is np.ndarray
            and not (node.dtype.hasobject or node.dtype.isalignedstruct)
            and node.nbytes <= serialization.MAX_CHUNK_SIZE
        ):
            pieces.append(_ndarray_header(node))
            pieces.append(node)
        else:
            pieces.append(serialization.msgpack_serialize(node))

    walk(serialization.to_state_dict(state))
    sizes = [
        p.nbytes if isinstance(p, np.ndarray) else len(p) for p in pieces
    ]
    out = np.empty(sum(sizes), np.uint8)
    off = 0
    for piece, n in zip(pieces, sizes):
        dst = out[off:off + n]
        if not isinstance(piece, np.ndarray):
            dst[:] = np.frombuffer(piece, np.uint8)
        elif n:
            np.copyto(dst.view(piece.dtype).reshape(piece.shape), piece)
        off += n
    return out


def save_checkpoint(
    directory: str, state: TrainState, step: Optional[int] = None,
    compress: bool = True, fault_plan=None, event_extra: Optional[dict] = None,
    data_state: Optional[dict] = None, geometry: Optional[dict] = None,
) -> str:
    """Write one atomic FILE checkpoint + its CRC32 manifest sidecar.

    The write itself (tmp + rename) is wrapped in a short retry with
    backoff (resilience/retry.py) — transient NFS/fuse EIO never kills
    the step, and atomicity makes the retry safe: a failed attempt never
    published anything. ``fault_plan`` is the injection hook: a
    ``torn_ckpt@<step>`` entry truncates the PUBLISHED file (simulated
    bitrot/partial copy), which the manifest then convicts on resume.

    ``state`` may be the live device state OR a host snapshot of it
    (``jax.device_get``): both serialize to identical msgpack bytes
    (``serialize_state``: flax's, byte for byte), which is what makes the
    async pipeline (training/async_ckpt.py) byte-identical to this
    synchronous path. That pipeline runs this function on a thread beside
    the train loop, so from here to the rename no pass over state-sized
    or leaf-sized bytes may hold the interpreter lock: such passes are
    numpy copies, the codec's foreign call, ``zlib.crc32`` and
    ``file.write``, from and into buffers allocated uninitialised.

    The ``checkpoint_write`` event carries ``write_ms`` (serialize +
    publish duration), its parts ``serialize_ms`` / ``compress_ms`` /
    ``file_ms`` (the three ``ckpt/*`` spans below) and ``stall_ms`` (how
    long the TRAIN LOOP was blocked — here the full write, since this
    call is synchronous). ``event_extra`` lets an overlapped caller
    override ``stall_ms`` with the actual loop blockage and add queueing
    fields.
    """
    t0 = time.perf_counter()
    os.makedirs(directory, exist_ok=True)
    step = int(state.step) if step is None else int(step)
    path = checkpoint_path(directory, step)
    tmp = path + ".tmp"
    # Refuse BEFORE the O(model) serialize/compress work; a stale tmp
    # DIRECTORY from a crashed sharded save would hit the same
    # unexplained IsADirectoryError at open() below.
    for p_ in (path, tmp):
        if os.path.isdir(p_):
            raise ValueError(
                f"{p_} exists as a sharded checkpoint DIRECTORY (written "
                "by a tp/sp>1 run); this run's config writes replicated "
                "FILE checkpoints — use a fresh --train-dir or the "
                "matching parallelism config"
            )
    with span("ckpt/serialize") as serialize:
        payload = serialize_state(state)
    codec = _codec() if compress else None
    compress_ms = 0.0
    # the file is these buffers one after the other; they are never
    # joined, because a join is a state-sized copy under the lock
    if codec is not None:
        with span("ckpt/compress") as compressed:
            pieces = (_MAGIC_LZ, codec.compress_buffer(payload))
        compress_ms = compressed.seconds * 1000
    else:
        pieces = (_MAGIC_RAW, payload)
    nbytes = sum(len(p) for p in pieces)

    # flaky_io fault: the FIRST publish attempt fails with a transient
    # OSError — exactly the NFS/fuse EIO the retry policy absorbs. The
    # retry emits the `retry` telemetry event, so the whole flaky-storage
    # path is observable end to end.
    flake = [fault_plan is not None and fault_plan.should_flake(step)]

    def _publish():
        if flake[0]:
            flake[0] = False
            get_telemetry().emit(
                "fault_injected", step=step, fault="flaky_io", path=path
            )
            raise OSError(f"fault: flaky_io@{step} — injected transient EIO")
        with open(tmp, "wb") as f:
            for piece in pieces:
                f.write(piece)
        # atomic: the polling evaluator never sees a torn file
        os.replace(tmp, path)

    with span("ckpt/file") as filed:
        retry_call(_publish, attempts=3, base_delay=0.05,
                   retry_on=(OSError,), label=f"checkpoint write {path}")
        _write_file_meta(path, step, pieces, geometry=geometry)
        if data_state is not None:
            save_data_state(path, data_state)
    if fault_plan is not None and fault_plan.should_tear(step):
        _tear_file(path)
        get_telemetry().emit(
            "fault_injected", step=step, fault="torn_ckpt", path=path
        )
    elapsed = time.perf_counter() - t0
    fields = {
        "path": path, "bytes": nbytes,
        "seconds": round(elapsed, 6), "format": "file",
        "write_ms": round(elapsed * 1000, 3),
        # synchronous save: the loop was blocked for the whole write;
        # the async pipeline overrides this with its (tiny) real stall
        "stall_ms": round(elapsed * 1000, 3),
        # the three writer spans, so a stream says where a write went
        # without a trace (compress_ms 0.0: written uncompressed)
        "serialize_ms": round(serialize.seconds * 1000, 3),
        "compress_ms": round(compress_ms, 3),
        "file_ms": round(filed.seconds * 1000, 3),
    }
    if event_extra:
        fields.update(event_extra)
    get_telemetry().emit("checkpoint_write", step=step, **fields)
    return path


def _write_file_meta(
    path: str, step: int, pieces, geometry: Optional[dict] = None,
) -> None:
    """Manifest AFTER the data publish: a crash in between leaves a
    manifest-less checkpoint, which verify treats as legacy-unverified
    (decode still gates it) rather than corrupt. ``pieces`` are the
    buffers the file is made of, in order."""
    mtmp = meta_path(path) + ".tmp"
    crc = 0
    for piece in pieces:
        crc = zlib.crc32(piece, crc)

    def _publish_meta():
        with open(mtmp, "w") as f:
            json.dump(
                {
                    "format": _FILE_META_FORMAT,
                    "step": step,
                    "bytes": sum(len(p) for p in pieces),
                    "crc32": crc & 0xFFFFFFFF,
                    # written-on geometry: what elastic resume compares the
                    # live fleet against (resilience/elastic.py)
                    "geometry": geometry or _default_geometry(),
                },
                f,
            )
        os.replace(mtmp, meta_path(path))

    retry_call(_publish_meta, attempts=3, base_delay=0.05,
               retry_on=(OSError,), label=f"manifest write {path}")


def _tear_file(path: str) -> None:
    """torn_ckpt fault: truncate the published file to half its bytes —
    the corruption the reference's non-atomic NFS writes produced
    naturally (src/distributed_evaluator.py) and ours cannot, injected so
    the detect/quarantine path stays testable."""
    size = os.path.getsize(path)
    with open(path, "r+b") as f:
        f.truncate(max(size // 2, 1))
    import logging

    logging.getLogger(__name__).warning(
        "fault: torn_ckpt — truncated %s from %d to %d bytes",
        path, size, max(size // 2, 1),
    )


def restore_checkpoint(
    path: str, state_template: TrainState, params_only: bool = False
) -> TrainState:
    """Restore a TrainState from a checkpoint file.

    ``state_template`` supplies the pytree structure (create a fresh state
    with `create_train_state` and pass it here) — standard flax msgpack
    restore semantics.

    ``params_only=True`` restores just step/params/batch_stats and keeps the
    template's optimizer/EF state — for consumers that only run forward
    (the polling evaluator), whose template need not match the trainer's
    optimizer choice.

    Dispatches on file-vs-directory: `model_step_<N>` directories (sharded
    GSPMD checkpoints, `save_sharded`) are assembled into full host
    arrays; with ``params_only=True`` this lets the evaluator consume a
    tp-sharded trainer's checkpoints on any mesh.
    """
    if os.path.isdir(path):
        return _restore_sharded_host(path, state_template, params_only)
    with open(path, "rb") as f:
        blob = f.read()
    payload = _decode_payload(path, blob)
    raw = serialization.msgpack_restore(payload)
    if params_only:
        return state_template.replace(
            step=serialization.from_state_dict(state_template.step, raw["step"]),
            params=serialization.from_state_dict(
                state_template.params, raw["params"]
            ),
            batch_stats=serialization.from_state_dict(
                state_template.batch_stats, raw["batch_stats"]
            ),
        )
    # Geometry gate BEFORE the flax restore: the only mesh-dependent leaves
    # in a FILE checkpoint are the per-replica EF residuals, and a resumed
    # run on a different data-parallel degree used to die here with a bare
    # flax shape error. Name both geometries and the way out instead.
    _check_ef_geometry(path, state_template, raw)
    return serialization.from_state_dict(state_template, raw)


def _ef_shapes(tree) -> list:
    return [tuple(np.shape(leaf)) for leaf in jax.tree_util.tree_leaves(tree)]


def _check_ef_geometry(path: str, template: TrainState, raw: dict) -> None:
    """Raise an ACTIONABLE error when the checkpoint's per-replica EF
    residuals cannot restore onto the live mesh (different data-parallel
    degree) — the up-front detection of a mesh mismatch that used to fail
    late with a cryptic flax shape error."""
    t_ef, r_ef = template.ef_state, raw.get("ef_state")
    if t_ef is None or r_ef is None:
        return
    ts, rs = _ef_shapes(t_ef), _ef_shapes(r_ef)
    if ts == rs:
        return
    recorded = checkpoint_geometry(path)
    old = recorded or (
        {"data-parallel replicas": rs[0][0]} if rs and rs[0] else {}
    )
    raise ValueError(
        f"{path}: checkpoint geometry mismatch — the error-feedback state "
        f"was saved with per-replica shapes {rs[:1]}... but the live mesh "
        f"expects {ts[:1]}... (checkpoint written on {old}; see the live "
        "run's mesh). Resume on the original geometry (--strict-geometry "
        "documents this contract), or let elastic resume reshard-on-load: "
        "training.checkpoint.restore_resharded / --resume without "
        "--strict-geometry (docs/resilience.md#elastic-resume)"
    )


def load_raw(path: str) -> dict:
    """Load a FILE checkpoint's raw state dict, no template required.

    Returns the msgpack tree as nested dicts of numpy arrays
    (``{"step", "params", "opt_state", "batch_stats", "ef_state"}``).
    For consumers whose model geometry DIFFERS from the writer's —
    the vocabulary-curriculum warm start (training/warm_start.py)
    resizes a smaller-vocab checkpoint into a bigger model, so no
    same-shape template can exist.
    """
    if os.path.isdir(path):
        raise ValueError(
            f"{path} is a sharded GSPMD checkpoint DIRECTORY (written by "
            "a tp/sp>1 run); load_raw reads FILE checkpoints only. Rewrite "
            "it as a file first: restore it on a 1-device mesh via "
            "restore_checkpoint(params_only=True) + save_checkpoint"
        )
    with open(path, "rb") as f:
        blob = f.read()
    return serialization.msgpack_restore(_decode_payload(path, blob))


def _decode_payload(path: str, blob: bytes) -> memoryview:
    """Shared container decode: magic-byte dispatch + host-codec inflate.
    The payload is a view (of ``blob``, or of the codec's output): no
    state-sized copy is made on the way back in either."""
    magic, payload = blob[:4], memoryview(blob)[4:]
    if magic == _MAGIC_LZ:
        codec = _codec()
        if codec is None:
            raise RuntimeError(
                f"{path} is host-codec compressed but the native codec "
                "is unavailable (build native/ first)"
            )
        return codec.decompress(payload)
    if magic != _MAGIC_RAW:
        raise ValueError(f"{path}: not a pytorch_distributed_nn_tpu checkpoint")
    return payload


# ---------------------------------------------------------------------------
# Sharded checkpoints (GSPMD path)
# ---------------------------------------------------------------------------


def _index_key(index, shape) -> str:
    """Canonical string for a shard's slice tuple: "0:4,8:16" ("" = scalar).

    `index` comes from `jax.Array.addressable_shards[..].index` (slices,
    possibly with None bounds); normalized against `shape` so the same
    region always maps to the same key.
    """
    parts = []
    for sl, dim in zip(index, shape):
        start = 0 if sl.start is None else int(sl.start)
        stop = dim if sl.stop is None else int(sl.stop)
        parts.append(f"{start}:{stop}")
    return ",".join(parts)


def _parse_index_key(key: str):
    if not key:
        return ()
    return tuple(
        slice(int(a), int(b))
        for a, b in (part.split(":") for part in key.split(","))
    )


def _flat_with_keys(tree):
    """[(keystr, leaf)] in deterministic flatten order."""
    leaves, _ = jax.tree_util.tree_flatten_with_path(tree)
    return [(jax.tree_util.keystr(path), leaf) for path, leaf in leaves]


def _barrier(tag: str):
    if jax.process_count() > 1:
        from jax.experimental import multihost_utils

        multihost_utils.sync_global_devices(f"pdtn_ckpt_{tag}")


def collect_host_shards(state) -> Tuple[dict, dict]:
    """Snapshot this process's addressable replica-0 shards to host arrays.

    Returns ``(shards, shapes)``: the ``{leaf_key|index_key: np.ndarray}``
    payload of this process's ``shards_p<N>.npz`` (the device→host fetch,
    which the async pipeline runs on the writer thread), and the global
    leaf-shape map
    for meta.json. Pure per-process work: NO collectives, so it is safe to
    call off the main thread (training/async_ckpt.py relies on this).
    """
    pidx = jax.process_index()
    shards = {}
    with span("ckpt/fetch"):
        for key, arr in _flat_with_keys(state):
            if not isinstance(arr, jax.Array):
                if pidx == 0:  # host scalars: one copy, process 0
                    shards[f"{key}|"] = np.asarray(arr)
                continue
            for shard in arr.addressable_shards:
                if shard.replica_id != 0:
                    continue
                ikey = _index_key(shard.index, arr.shape)
                skey = f"{key}|{ikey}"
                if skey not in shards:  # two local devices may own one region
                    shards[skey] = np.asarray(shard.data)
    shapes = {
        key: list(np.shape(leaf)) for key, leaf in _flat_with_keys(state)
    }
    return shards, shapes


def write_sharded_local(tmp: str, shards: dict) -> str:
    """Write this process's shard file into the staging directory.

    ``makedirs(exist_ok=True)`` instead of a process-0 mkdir + barrier:
    concurrent creates on a shared FS are idempotent, and the async writer
    thread cannot participate in collectives.
    """
    with span("ckpt/file"):
        os.makedirs(tmp, exist_ok=True)
        out = os.path.join(tmp, f"shards_p{jax.process_index():05d}.npz")
        np.savez(out, **shards)
    return out


def publish_sharded(
    tmp: str, final: str, step: int, shapes: dict,
    geometry: Optional[dict] = None,
) -> None:
    """Process-0 commit: checksum every shard file, write meta.json, and
    atomically rename the staging dir into place. The caller owns the
    barrier discipline: every process's shard file must be complete (and
    shared-FS-visible) before this runs — ``save_sharded`` barriers on the
    main thread; the async path commits single-process immediately and
    defers multi-process commits to the next main-thread wait point.

    The crc re-read is O(model) on one host per checkpoint — acceptable
    for an integrity manifest; disable by policy at pod scale if the
    re-read ever shows up in the checkpoint phase timer.
    """
    with span("ckpt/file"):
        crcs = {}
        for fname in sorted(os.listdir(tmp)):
            if fname.startswith("shards_p") and fname.endswith(".npz"):
                with open(os.path.join(tmp, fname), "rb") as f:
                    crcs[fname] = zlib.crc32(f.read()) & 0xFFFFFFFF
        with open(os.path.join(tmp, "meta.json"), "w") as f:
            json.dump(
                {
                    "format": _SHARDED_FORMAT,
                    "step": step,
                    "processes": jax.process_count(),
                    "crc32": crcs,
                    # global leaf shapes: restore validates the template
                    # against these so a config-mismatched restore fails
                    # loudly instead of zero-padding
                    "shapes": shapes,
                    "geometry": geometry or _default_geometry(),
                },
                f,
            )
        os.replace(tmp, final)


def save_sharded(
    directory: str, state: TrainState, step: Optional[int] = None,
    event_extra: Optional[dict] = None, data_state: Optional[dict] = None,
    geometry: Optional[dict] = None,
) -> str:
    """Write `model_step_<N>/` with each process's addressable shards.

    Every process must call this (collective: it barriers between
    write / publish on multi-host). NO process ever materializes the full
    state: each writes exactly the replica-0 shards it owns into
    `shards_p<process>.npz`, so per-host IO is O(model/num_hosts) for
    fully-sharded leaves and each unique shard lands in the checkpoint
    exactly once cluster-wide (replicated leaves are written only by the
    replica-0 owner). Process 0 additionally writes meta.json and performs
    the atomic tmp->final rename, preserving the torn-file-free contract
    the polling evaluator relies on (reference:
    src/sync_replicas_master_nn.py:264-270).

    The snapshot/write/publish stages are exposed individually
    (``collect_host_shards`` / ``write_sharded_local`` /
    ``publish_sharded``) so the async pipeline can run the d2h fetch and
    local write off the critical path while keeping this composite —
    and therefore the on-disk bytes — unchanged.
    """
    t0 = time.perf_counter()
    step = int(state.step) if step is None else int(step)
    final = checkpoint_path(directory, step)
    tmp = final + ".tmp"
    pidx = jax.process_index()
    shards, shapes = collect_host_shards(state)
    write_sharded_local(tmp, shards)
    _barrier(f"write_{step}")
    if pidx == 0:
        # meta.json is written AFTER the write barrier so process 0 can
        # checksum every (now complete, shared-FS-visible) shard file.
        publish_sharded(tmp, final, step, shapes, geometry=geometry)
        if data_state is not None:
            save_data_state(final, data_state)
    _barrier(f"publish_{step}")
    # each process logs its own shard write into its own stream (shard
    # bytes are per-process; process 0's event additionally covers the
    # manifest + publish work)
    elapsed = time.perf_counter() - t0
    fields = {
        "path": final,
        "bytes": sum(int(v.nbytes) for v in shards.values()),
        "seconds": round(elapsed, 6), "format": "sharded",
        "process": pidx,
        "write_ms": round(elapsed * 1000, 3),
        "stall_ms": round(elapsed * 1000, 3),
    }
    if event_extra:
        fields.update(event_extra)
    get_telemetry().emit("checkpoint_write", step=step, **fields)
    return final


def _load_shard_files(path: str):
    """({leaf_key: {index_key: np.ndarray}}, meta) from every process's npz.

    Known limitation: every process reads ALL shard files, so restore is
    O(model) host RAM per process even though the save is
    O(model/processes). Fine at the 110M-parameter scale this repo
    benchmarks; a pod-scale restore should lazily open each npz and load
    only members intersecting the process's addressable shards (npz
    members are zip entries — per-member lazy reads are possible without
    a format change).
    """
    meta_path = os.path.join(path, "meta.json")
    with open(meta_path) as f:
        meta = json.load(f)
    if meta.get("format") != _SHARDED_FORMAT:
        raise ValueError(f"{path}: unknown sharded checkpoint format {meta}")
    out: dict = {}
    shard_files = sorted(
        f for f in os.listdir(path)
        if f.startswith("shards_p") and f.endswith(".npz")
    )
    # Missing shard files would otherwise be SILENTLY zero-filled by
    # _assemble_full (partial rsync/copy of a pod checkpoint, a deleted
    # file) — exactly the kind of corruption that must fail loudly.
    expected = meta.get("processes")
    if expected is not None and len(shard_files) != expected:
        raise ValueError(
            f"{path}: found {len(shard_files)} shard file(s) but the "
            f"checkpoint was written by {expected} process(es) — partial "
            "copy or deleted shards; refusing to zero-fill the gaps"
        )
    import io

    crcs = meta.get("crc32") or {}
    for fname in shard_files:
        with open(os.path.join(path, fname), "rb") as f:
            raw = f.read()
        want = crcs.get(fname)
        if want is not None and (zlib.crc32(raw) & 0xFFFFFFFF) != want:
            raise ValueError(
                f"{path}/{fname}: CRC32 mismatch against meta.json — "
                "corrupt or torn shard file; quarantine and fall back to "
                "an older step (resilience/supervisor.resume_latest_valid)"
            )
        with np.load(io.BytesIO(raw)) as z:
            for k in z.files:
                leaf_key, _, ikey = k.rpartition("|")
                out.setdefault(leaf_key, {})[ikey] = z[k]
    return out, meta


def _check_leaf_shape(path: str, meta: dict, key: str, shape) -> None:
    saved = meta.get("shapes", {}).get(key)
    if saved is not None and tuple(saved) != tuple(shape):
        raise ValueError(
            f"{path}: leaf {key} has shape {tuple(shape)} in the restore "
            f"template but {tuple(saved)} in the checkpoint (different "
            "model/optimizer config?)"
        )


def _assemble_full(entries: dict, shape, dtype) -> np.ndarray:
    """Reassemble a full array from its saved shards (restore-side only —
    the save path never does this)."""
    if list(entries) == [""]:
        return np.asarray(entries[""], dtype=dtype)
    full = np.zeros(shape, dtype)
    for ikey, data in entries.items():
        full[_parse_index_key(ikey)] = data
    return full


def restore_sharded(path: str, template, shardings) -> TrainState:
    """Restore a sharded checkpoint directly onto the live mesh.

    ``template`` supplies pytree structure + leaf shapes/dtypes (the live
    state or `jax.eval_shape` thereof); ``shardings`` the matching
    NamedSharding tree (training/spmd.create_spmd_state returns it). Each
    device's shard is fed from the saved region of the same index when the
    mesh topology matches (the common resume case — zero resharding), and
    from a restore-side reassembly otherwise (topology-change resume).
    """
    if os.path.isfile(path):
        raise ValueError(
            f"{path} is a replicated FILE checkpoint (written by a "
            "tp=sp=1 run) but this config's sharded restore needs a "
            "model_step_<N>/ DIRECTORY — restore with restore_checkpoint "
            "on a matching config, or use a fresh --train-dir"
        )
    data, meta = _load_shard_files(path)
    t_leaves, treedef = jax.tree_util.tree_flatten_with_path(template)
    s_leaves = treedef.flatten_up_to(shardings)
    out = []
    for (pathelts, tleaf), sharding in zip(t_leaves, s_leaves):
        key = jax.tree_util.keystr(pathelts)
        if key not in data:
            raise KeyError(
                f"{path}: leaf {key} missing from checkpoint (saved with a "
                "different model/optimizer config?)"
            )
        entries = data[key]
        shape = tuple(np.shape(tleaf))
        dtype = np.dtype(tleaf.dtype)
        _check_leaf_shape(path, meta, key, shape)
        cache = {}

        def cb(index, entries=entries, shape=shape, dtype=dtype, cache=cache):
            ikey = _index_key(index, shape)
            hit = entries.get(ikey)
            if hit is not None:
                return np.asarray(hit, dtype=dtype)
            if "full" not in cache:
                cache["full"] = _assemble_full(entries, shape, dtype)
            return cache["full"][index]

        out.append(jax.make_array_from_callback(shape, sharding, cb))
    return jax.tree_util.tree_unflatten(treedef, out)


def restore_resharded(path: str, template: TrainState, shardings=None):
    """Elastic restore: load a checkpoint taken on ANY mesh onto the live one.

    The reshard-on-load entry point (docs/resilience.md#elastic-resume).
    Dispatches on both the on-disk format and the destination:

    - sharded DIRECTORY + ``shardings``: per-leaf callback assembly keyed
      by the NEW shardings (``restore_sharded``'s topology-change path) —
      each device shard is fed from the saved region when the slice grids
      line up, and from a restore-side full-array reassembly otherwise.
      Per-shard CRC32s are verified against meta.json as the shard files
      are consumed (``_load_shard_files``); a corrupt shard raises so the
      caller (``resume_latest_valid``) can quarantine and fall back.
    - FILE + ``shardings``: the replicated msgpack state is decoded once
      on the host, then each leaf is materialized straight onto its live
      sharding via ``jax.make_array_from_callback`` — a dp-only
      checkpoint restores onto a tp/sp mesh (and vice versa through the
      directory branch), so file<->sharded both directions work.
    - ``shardings=None``: host-array restore in ``template``'s structure
      (the shard_map-DP resume path; geometry-independent by
      construction).

    Optimizer state reshards alongside params (it is part of the same
    tree walk). The ONE geometry-dependent exception is the per-replica
    error-feedback residual tree: when the data-parallel degree changed,
    the saved residuals have no meaningful mapping onto the new replica
    set, so they are RESET to the template's zeros (logged; the elastic
    tolerance contract in docs/resilience.md covers the perturbation —
    at most one step's worth of re-accumulated compression error).
    """
    import logging

    if os.path.isdir(path):
        if shardings is not None:
            return restore_sharded(path, template, shardings)
        # sharded checkpoints never carry EF state (the GSPMD path has no
        # per-replica residuals); keep the template's own — and say so
        # when that actually drops information.
        if template.ef_state is not None:
            logging.getLogger(__name__).warning(
                "%s: sharded checkpoint carries no EF residuals; the "
                "template's fresh (zero) residuals are kept", path,
            )
        restored = _restore_sharded_host(
            path, template.replace(ef_state=None), params_only=False
        )
        return restored.replace(ef_state=template.ef_state)
    with open(path, "rb") as f:
        blob = f.read()
    raw = serialization.msgpack_restore(_decode_payload(path, blob))
    fields = {
        name: serialization.from_state_dict(getattr(template, name), raw[name])
        for name in ("step", "params", "opt_state", "batch_stats")
    }
    ef = template.ef_state
    raw_ef = raw.get("ef_state")
    if ef is not None and raw_ef is not None:
        if _ef_shapes(ef) == _ef_shapes(raw_ef):
            ef = serialization.from_state_dict(ef, raw_ef)
        else:
            logging.getLogger(__name__).warning(
                "%s: EF residuals reset — saved for a different "
                "data-parallel degree (%s vs live %s)",
                path, _ef_shapes(raw_ef)[:1], _ef_shapes(ef)[:1],
            )
    state = template.replace(**fields, ef_state=ef)
    # shape gate against the template (model/optimizer config mismatch
    # must fail loudly, mesh mismatch must NOT — that is the whole point)
    t_flat, _ = jax.tree_util.tree_flatten_with_path(template)
    s_flat = jax.tree_util.tree_leaves(state)
    for (pathelts, tleaf), sleaf in zip(t_flat, s_flat):
        if tuple(np.shape(tleaf)) != tuple(np.shape(sleaf)):
            raise ValueError(
                f"{path}: leaf {jax.tree_util.keystr(pathelts)} has shape "
                f"{tuple(np.shape(sleaf))} in the checkpoint but "
                f"{tuple(np.shape(tleaf))} in the restore template — "
                "different model/optimizer config, not a mesh change"
            )
    if shardings is None:
        return state
    flat, treedef = jax.tree_util.tree_flatten(state)
    s_leaves = treedef.flatten_up_to(shardings)
    out = []
    for host_leaf, sharding in zip(flat, s_leaves):
        arr = np.asarray(host_leaf)
        out.append(
            jax.make_array_from_callback(
                arr.shape, sharding, lambda idx, a=arr: a[idx]
            )
        )
    return jax.tree_util.tree_unflatten(treedef, out)


def _restore_sharded_host(path: str, state_template, params_only: bool):
    """Assemble full host arrays from a sharded checkpoint (the evaluator /
    single-device consumer path)."""
    data, meta = _load_shard_files(path)

    def subtree(template_sub, prefix):
        entries = _flat_with_keys(template_sub)
        leaves = []
        for key, tleaf in entries:
            full_key = prefix + key
            if full_key not in data:
                raise KeyError(f"{path}: leaf {full_key} missing")
            _check_leaf_shape(path, meta, full_key, np.shape(tleaf))
            leaves.append(
                _assemble_full(
                    data[full_key], np.shape(tleaf), np.dtype(tleaf.dtype)
                )
            )
        flat, treedef = jax.tree_util.tree_flatten(template_sub)
        return jax.tree_util.tree_unflatten(treedef, leaves)

    # TrainState is a dataclass pytree: leaf keys render as ".field[...]"
    step = subtree(state_template.step, ".step")
    params = subtree(state_template.params, ".params")
    batch_stats = subtree(state_template.batch_stats, ".batch_stats")
    if params_only:
        return state_template.replace(
            step=step, params=params, batch_stats=batch_stats
        )
    return state_template.replace(
        step=step,
        params=params,
        batch_stats=batch_stats,
        opt_state=subtree(state_template.opt_state, ".opt_state"),
        ef_state=subtree(state_template.ef_state, ".ef_state"),
    )


def latest_step(directory: str) -> Optional[int]:
    """Highest checkpointed step in `directory`, or None."""
    steps = all_steps(directory)
    return steps[-1] if steps else None


def all_steps(directory: str) -> list:
    """All checkpointed steps in ``directory``, ascending (may be [])."""
    if not os.path.isdir(directory):
        return []
    return sorted(
        int(m.group(1))
        for name in os.listdir(directory)
        if (m := _STEP_RE.match(name))
    )


def verify_checkpoint(path: str) -> Tuple[bool, str]:
    """Integrity check WITHOUT a full restore: ``(ok, reason)``.

    FILE checkpoints: byte length + CRC32 against the ``.meta.json``
    manifest sidecar (legacy manifest-less files fall back to a magic-byte
    check — "unverified", not "corrupt"). Sharded DIRECTORY checkpoints:
    per-shard CRC32 against meta.json plus the shard-count completeness
    check. Cost is one sequential read of the checkpoint — cheap next to
    a restore, and the reason string names exactly what failed.
    """
    if not os.path.exists(path):
        return False, "missing"
    if os.path.isdir(path):
        try:
            with open(os.path.join(path, "meta.json")) as f:
                meta = json.load(f)
        except (OSError, ValueError) as e:
            return False, f"unreadable meta.json: {e}"
        if meta.get("format") != _SHARDED_FORMAT:
            return False, f"unknown sharded format {meta.get('format')!r}"
        shard_files = sorted(
            f for f in os.listdir(path)
            if f.startswith("shards_p") and f.endswith(".npz")
        )
        expected = meta.get("processes")
        if expected is not None and len(shard_files) != expected:
            return False, (
                f"{len(shard_files)} shard file(s), expected {expected}"
            )
        crcs = meta.get("crc32") or {}
        for fname in shard_files:
            want = crcs.get(fname)
            if want is None:
                continue  # legacy manifest without checksums
            with open(os.path.join(path, fname), "rb") as f:
                got = zlib.crc32(f.read()) & 0xFFFFFFFF
            if got != want:
                return False, f"{fname}: CRC32 mismatch"
        return True, "ok"
    try:
        with open(path, "rb") as f:
            blob = f.read()
    except OSError as e:
        return False, f"unreadable: {e}"
    if blob[:4] not in (_MAGIC_RAW, _MAGIC_LZ):
        return False, "bad magic bytes"
    try:
        with open(meta_path(path)) as f:
            meta = json.load(f)
    except FileNotFoundError:
        return True, "ok (no manifest — legacy, unverified)"
    except (OSError, ValueError) as e:
        return False, f"unreadable manifest: {e}"
    if meta.get("bytes") is not None and meta["bytes"] != len(blob):
        return False, f"size mismatch: {len(blob)} != {meta['bytes']}"
    if meta.get("crc32") is not None:
        if (zlib.crc32(blob) & 0xFFFFFFFF) != meta["crc32"]:
            return False, "CRC32 mismatch"
    return True, "ok"


def quarantine_checkpoint(path: str) -> str:
    """Move a corrupt ``model_step_<N>`` (and its manifest) into
    ``<dir>/quarantine/`` — atomic renames, so the step scan never sees
    it again while the evidence survives for a post-mortem."""
    directory = os.path.dirname(path) or "."
    qdir = os.path.join(directory, QUARANTINE_DIR)
    os.makedirs(qdir, exist_ok=True)
    dest = os.path.join(qdir, os.path.basename(path))
    n = 0
    while os.path.exists(dest):  # same step quarantined twice
        n += 1
        dest = os.path.join(qdir, f"{os.path.basename(path)}.{n}")
    os.replace(path, dest)
    for sidecar in (meta_path, data_state_path):
        if os.path.exists(sidecar(path)):
            os.replace(sidecar(path), sidecar(dest))
    return dest


def restore_latest(
    directory: str, state_template: TrainState
) -> Optional[TrainState]:
    """Resume support the reference lacked: restore the newest checkpoint."""
    step = latest_step(directory)
    if step is None:
        return None
    return restore_checkpoint(checkpoint_path(directory, step), state_template)


# ---------------------------------------------------------------------------
# Published-step registry (serving exports): GC protection
# ---------------------------------------------------------------------------


def published_path(directory: str) -> str:
    return os.path.join(directory, PUBLISHED_FILE)


def published_steps(directory: str) -> set:
    """Steps recorded as frozen into serving artifacts (may be empty).

    An unreadable registry fails SAFE for GC: a warning plus an empty set
    would let ``--keep-last`` delete a published step, so corruption here
    raises — the operator fixes/removes ``published.json`` explicitly.
    """
    path = published_path(directory)
    if not os.path.isfile(path):
        return set()
    with open(path) as f:
        doc = json.load(f)
    if doc.get("format") != _PUBLISHED_FORMAT:
        raise ValueError(
            f"{path}: unknown published-step registry format "
            f"{doc.get('format')!r}"
        )
    return {int(e["step"]) for e in doc.get("artifacts", [])}


def record_published_step(directory: str, step: int, artifact: str) -> dict:
    """Append one artifact-export record to ``<dir>/published.json``
    (atomic read-modify-write; ``serve export`` calls this after a
    successful freeze). Idempotent per (step, artifact) pair."""
    path = published_path(directory)
    doc = {"format": _PUBLISHED_FORMAT, "artifacts": []}
    if os.path.isfile(path):
        with open(path) as f:
            doc = json.load(f)
        if doc.get("format") != _PUBLISHED_FORMAT:
            raise ValueError(
                f"{path}: unknown published-step registry format "
                f"{doc.get('format')!r}"
            )
    entry = {"step": int(step), "artifact": os.path.abspath(artifact),
             "time": time.time()}
    if not any(
        e.get("step") == entry["step"] and e.get("artifact") == entry["artifact"]
        for e in doc["artifacts"]
    ):
        doc["artifacts"].append(entry)
    tmp = path + ".tmp"

    def _publish():
        with open(tmp, "w") as f:
            json.dump(doc, f, indent=2, sort_keys=True)
        os.replace(tmp, path)

    retry_call(_publish, attempts=3, base_delay=0.05, retry_on=(OSError,),
               label=f"published-step registry {path}")
    return doc


def release_published_step(
    directory: str, step: int, artifact: Optional[str] = None
) -> dict:
    """Drop artifact-export records from ``<dir>/published.json`` — the
    protection-release half of the registry lifecycle (``cli registry
    gc``): once a registry entry is retired, its source checkpoint stops
    being production provenance and ``--keep-last`` GC may reclaim it.

    ``artifact=None`` releases every record for ``step``; otherwise only
    the matching (step, artifact) pair. The step's GC protection ends
    only when its LAST record is gone — two artifacts frozen from one
    step each hold their own claim. Atomic read-modify-write like
    :func:`record_published_step`; a missing registry is a no-op.
    """
    path = published_path(directory)
    if not os.path.isfile(path):
        return {"format": _PUBLISHED_FORMAT, "artifacts": []}
    with open(path) as f:
        doc = json.load(f)
    if doc.get("format") != _PUBLISHED_FORMAT:
        raise ValueError(
            f"{path}: unknown published-step registry format "
            f"{doc.get('format')!r}"
        )
    want = os.path.abspath(artifact) if artifact is not None else None
    doc["artifacts"] = [
        e for e in doc.get("artifacts", [])
        if not (
            int(e.get("step", -1)) == int(step)
            and (want is None or e.get("artifact") == want)
        )
    ]
    tmp = path + ".tmp"

    def _publish():
        with open(tmp, "w") as f:
            json.dump(doc, f, indent=2, sort_keys=True)
        os.replace(tmp, path)

    retry_call(_publish, attempts=3, base_delay=0.05, retry_on=(OSError,),
               label=f"published-step registry {path}")
    return doc


# ---------------------------------------------------------------------------
# Retention (--keep-last): bounded train_dir growth on long runs
# ---------------------------------------------------------------------------


def _checkpoint_bytes(path: str) -> int:
    """On-disk bytes of one checkpoint (file + manifest, or shard dir)."""
    total = 0
    try:
        if os.path.isdir(path):
            for fname in os.listdir(path):
                total += os.path.getsize(os.path.join(path, fname))
        else:
            total += os.path.getsize(path)
            if os.path.exists(meta_path(path)):
                total += os.path.getsize(meta_path(path))
        if os.path.exists(data_state_path(path)):
            total += os.path.getsize(data_state_path(path))
    except OSError:
        pass
    return total


def gc_checkpoints(
    directory: str, keep_last: int, protect=(),
) -> dict:
    """Delete checkpoints older than the newest ``keep_last`` steps.

    Retention policy (the ``--keep-last`` flag; run after every successful
    publish so a long run's ``train_dir`` stays bounded):

    - only VERIFIED checkpoints are deleted — a step that fails
      :func:`verify_checkpoint` is corruption *evidence*; the resume path
      quarantines it, GC never destroys it;
    - the resume target (the newest step that verifies — which may be
      OLDER than the ``keep_last`` window when the newest entries are
      torn) is never deleted;
    - steps in ``protect`` are never deleted (the trainer protects the
      step it resumed from until it publishes something newer);
    - steps recorded in the published-step registry
      (:func:`record_published_step` — ``serve export`` registers every
      step it freezes into a serving artifact) are never deleted: the
      source checkpoint is the bit-exact provenance of what is serving
      production traffic;
    - quarantined steps live under ``quarantine/`` and are invisible to
      the step scan, so they never count against ``keep_last``.

    Emits one ``checkpoint_gc`` telemetry event naming the deleted steps
    and bytes freed; returns ``{"deleted", "kept", "bytes_freed"}``.
    """
    if keep_last < 1:
        raise ValueError(f"keep_last must be >= 1, got {keep_last}")
    protect = set(protect) | published_steps(directory)
    steps = all_steps(directory)
    if len(steps) <= keep_last:
        return {"deleted": [], "kept": steps, "bytes_freed": 0}
    resume_target = None
    for s in steps[::-1]:
        ok, _ = verify_checkpoint(checkpoint_path(directory, s))
        if ok:
            resume_target = s
            break
    deleted, freed = [], 0
    for s in steps[:-keep_last]:
        if s == resume_target or s in protect:
            continue
        path = checkpoint_path(directory, s)
        ok, _ = verify_checkpoint(path)
        if not ok:
            continue  # corrupt evidence: quarantine's job, not GC's
        freed += _checkpoint_bytes(path)
        try:
            if os.path.isdir(path):
                import shutil

                shutil.rmtree(path)
            else:
                os.remove(path)
                if os.path.exists(meta_path(path)):
                    os.remove(meta_path(path))
            if os.path.exists(data_state_path(path)):
                os.remove(data_state_path(path))
        except OSError:
            import logging

            logging.getLogger(__name__).exception(
                "checkpoint GC could not delete %s", path
            )
            continue
        deleted.append(s)
    kept = all_steps(directory)
    if deleted:
        get_telemetry().emit(
            "checkpoint_gc", step=steps[-1], deleted=deleted, kept=kept,
            keep_last=keep_last, bytes_freed=freed,
        )
    return {"deleted": deleted, "kept": kept, "bytes_freed": freed}

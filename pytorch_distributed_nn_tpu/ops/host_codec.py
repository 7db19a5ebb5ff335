"""ctypes binding for the native host codec (native/codec.cpp).

API parity with the reference codec (reference: src/compression.py:18-46):
``compress``/``decompress`` over raw bytes plus ``w_compress``/
``w_decompress`` convenience wrappers for numpy arrays (the reference's
names for the weight path). The shared library is built on first use via
`make` — no pip deps.
"""

from __future__ import annotations

import ctypes
import os
import threading
from typing import Optional

import numpy as np

_NATIVE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
    "native",
)
_SO_PATH = os.path.join(_NATIVE_DIR, "libpdtn_codec.so")

_lib = None
_load_failed = False
_lock = threading.Lock()
_HEADER = np.dtype([("orig_size", "<u8"), ("width", "<u4"), ("pad", "<u4")])


def _load() -> Optional[ctypes.CDLL]:
    global _lib, _load_failed
    if _lib is not None:
        return _lib
    if _load_failed:
        return None
    with _lock:
        if _lib is not None:
            return _lib
        from pytorch_distributed_nn_tpu.utils.native_build import ensure_built

        if _load_failed or not ensure_built(_SO_PATH):
            _load_failed = True
            return None
        try:
            lib = ctypes.CDLL(_SO_PATH)
        except OSError:
            _load_failed = True
            return None
        lib.pdtn_max_compressed_size.restype = ctypes.c_uint64
        lib.pdtn_max_compressed_size.argtypes = [ctypes.c_uint64]
        lib.pdtn_compress.restype = ctypes.c_int64
        lib.pdtn_compress.argtypes = [
            ctypes.c_void_p, ctypes.c_uint64,
            ctypes.c_void_p, ctypes.c_uint64,
            ctypes.c_int, ctypes.c_uint32,
        ]
        lib.pdtn_decompress.restype = ctypes.c_int64
        lib.pdtn_decompress.argtypes = [
            ctypes.c_void_p, ctypes.c_uint64,
            ctypes.c_void_p, ctypes.c_uint64,
            ctypes.c_uint32,
        ]
        _lib = lib
        return _lib


def available() -> bool:
    return _load() is not None


def _flat_u8(data) -> np.ndarray:
    """`data` (bytes, memoryview, C-contiguous ndarray) as a flat uint8
    array over the same memory: no copy."""
    if isinstance(data, np.ndarray):
        return np.ascontiguousarray(data).reshape(-1).view(np.uint8)
    return np.frombuffer(data, np.uint8)


def _require_lib() -> ctypes.CDLL:
    lib = _load()
    if lib is None:
        raise RuntimeError("native codec unavailable (build native/ with make)")
    return lib


def compress_buffer(data, level: int = 1, width: int = 4) -> np.ndarray:
    """Compress a buffer into a uint8 array: the codec header, then the
    compressed body, with byte-shuffle width `width` (4 = float32).

    Nothing here touches the input or the output under the interpreter
    lock: the input is read by pointer, the output is allocated
    uninitialised and returned as a view of its used part, and the
    foreign call releases the lock. A checkpoint's writer thread calls
    this beside the train loop (training/checkpoint.py)."""
    lib = _require_lib()
    src = _flat_u8(data)
    n = src.size
    cap = int(lib.pdtn_max_compressed_size(n))
    out = np.empty(_HEADER.itemsize + cap, np.uint8)
    header = out[: _HEADER.itemsize].view(_HEADER)
    header["orig_size"] = n
    header["width"] = width
    header["pad"] = 0
    size = lib.pdtn_compress(
        src.ctypes.data, n, out.ctypes.data + _HEADER.itemsize, cap,
        level, width,
    )
    if size < 0:
        raise RuntimeError("pdtn_compress failed")
    return out[: _HEADER.itemsize + size]


def compress(data, level: int = 1, width: int = 4) -> bytes:
    """``compress_buffer`` as bytes, for callers with small payloads."""
    return compress_buffer(data, level=level, width=width).tobytes()


def decompress(blob) -> memoryview:
    """Inflate what ``compress`` / ``compress_buffer`` made. The result is
    a byte view of an uninitialised buffer the codec filled: it compares
    equal to the original ``bytes`` and feeds ``np.frombuffer`` and
    msgpack as they are, with no second copy."""
    lib = _require_lib()
    src = _flat_u8(blob)
    header = src[: _HEADER.itemsize].view(_HEADER)[0]
    n = int(header["orig_size"])
    width = int(header["width"])
    payload = src[_HEADER.itemsize :]
    out = np.empty(n, np.uint8)
    size = lib.pdtn_decompress(
        payload.ctypes.data, payload.size, out.ctypes.data, n, width
    )
    if size != n:
        raise RuntimeError("pdtn_decompress failed")
    return memoryview(out)


def w_compress(arr: np.ndarray, level: int = 1) -> bytes:
    """Array compression (reference: src/compression.py:32-37)."""
    arr = np.ascontiguousarray(arr)
    meta = (str(arr.dtype).encode() + b"|" +
            ",".join(map(str, arr.shape)).encode() + b"|")
    return meta + compress(arr, level=level, width=arr.dtype.itemsize)


def w_decompress(blob: bytes) -> np.ndarray:
    """Array decompression (reference: src/compression.py:39-46)."""
    dtype_end = blob.index(b"|")
    shape_end = blob.index(b"|", dtype_end + 1)
    dtype = np.dtype(blob[:dtype_end].decode())
    shape_s = blob[dtype_end + 1 : shape_end].decode()
    shape = tuple(int(s) for s in shape_s.split(",")) if shape_s else ()
    data = decompress(blob[shape_end + 1 :])
    return np.frombuffer(data, dtype).reshape(shape)


# gradient-path aliases (reference: src/compression.py:18-31)
g_compress = w_compress
g_decompress = w_decompress

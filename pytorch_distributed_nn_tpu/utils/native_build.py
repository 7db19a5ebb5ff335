"""Build-on-first-use for the native/ C++ libraries, done safely.

Shared by the ctypes bindings (ops/host_codec, data/native_augment):

- per-target builds (`make <lib>.so`) so one library's missing dependency
  (e.g. zlib for the codec) can't block another's build;
- an exclusive file lock around check+build so concurrent processes (the
  multi-process jax.distributed runs, pytest-xdist) can't race `make`
  into the same half-written .so;
- failed builds are memoized per path — the caller's fallback must not
  re-spawn a doomed compile on every hot-loop call.
"""

from __future__ import annotations

import os
import subprocess
import threading
from typing import Dict, Optional

_lock = threading.Lock()
_failed: Dict[str, bool] = {}


def _fresh(so_path: str) -> bool:
    """True when the library exists and is not older than its source.

    ``native/*.so`` are git-ignored build outputs that can outlive the
    source they were built from (a copied checkout carries them along),
    so an existing file is not trusted on sight. native/Makefile builds
    ``libpdtn_<name>.so`` from ``<name>.cpp``.
    """
    name = os.path.basename(so_path)[len("libpdtn_"):-len(".so")]
    src = os.path.join(os.path.dirname(so_path), name + ".cpp")
    try:
        return os.path.getmtime(so_path) >= os.path.getmtime(src)
    except OSError:
        return False


def ensure_built(so_path: str, timeout: float = 120.0) -> bool:
    """Make sure ``so_path`` is built from the current sources, running
    its make target if it is missing or stale.

    Returns False (and remembers the failure) when the build cannot be
    done here; True when an up-to-date library file exists.
    """
    if _fresh(so_path):
        return True
    with _lock:
        if _failed.get(so_path):
            return False
        native_dir = os.path.dirname(so_path)
        target = os.path.basename(so_path)
        lock_path = so_path + ".lock"
        try:
            import fcntl

            with open(lock_path, "w") as lf:
                fcntl.flock(lf, fcntl.LOCK_EX)
                try:
                    if not _fresh(so_path):
                        subprocess.run(
                            ["make", "-s", target], cwd=native_dir,
                            check=True, capture_output=True, timeout=timeout,
                        )
                finally:
                    fcntl.flock(lf, fcntl.LOCK_UN)
        except Exception:
            _failed[so_path] = True
            return False
        ok = _fresh(so_path)
        if not ok:
            _failed[so_path] = True
        return ok

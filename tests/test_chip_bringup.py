"""What the chip bring-up promises, as far as a CPU host can check it:
chip_smoke.py refuses to run without a TPU, the compile cache goes where
the operator (or one fixed path) says, and a stale native library is
rebuilt rather than trusted. All jax-light, seconds long."""

import importlib.util
import json
import os
import shutil
import subprocess
import sys
import time

import jax
import pytest

from pytorch_distributed_nn_tpu.utils import compile_cache, native_build

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_chip_smoke_refuses_to_run_without_a_tpu(tmp_path):
    """No accelerator: non-zero exit in seconds, the missing chip named,
    no result line, and no training step taken."""
    # a copy, so its logs and work dir land under tmp_path, not the repo
    script = shutil.copy(os.path.join(REPO, "chip_smoke.py"), tmp_path)
    t0 = time.monotonic()
    r = subprocess.run(
        [sys.executable, script], capture_output=True, text=True,
        timeout=120, cwd=str(tmp_path),
        env=dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=REPO),
    )
    assert r.returncode != 0
    assert time.monotonic() - t0 < 60
    assert "no TPU" in r.stderr and "preflight" in r.stderr
    assert '"ok"' not in r.stdout
    logs = tmp_path / "chiprun_out" / "chip_smoke"
    assert sorted(os.listdir(logs)) == ["preflight.log"]


def test_chip_smoke_last_line_holds_the_verdict_and_nothing_else():
    """The checker refuses a last line with any key beyond ok / device
    {platform, kind, count}; the per-leg record goes on the line before."""
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", os.path.join(REPO, "chip_smoke.py"))
    smoke = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(smoke)  # the parent side: no jax import
    line = smoke.verdict_line(
        {"platform": "tpu", "kind": "TPU v5 lite", "count": 1, "jax": "x"})
    assert "\n" not in line
    assert json.loads(line) == {
        "ok": True,
        "device": {"platform": "tpu", "kind": "TPU v5 lite", "count": 1},
    }


def test_compile_cache_defers_to_the_outer_variable(monkeypatch):
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv(compile_cache.ENV_VAR, "/some/dir")
    assert compile_cache.configure() == "/some/dir"
    # jax reads the variable itself; the helper set nothing in code
    assert jax.config.jax_compilation_cache_dir == before
    # CPU run, variable unset: no cache, the checkout stays clean
    monkeypatch.delenv(compile_cache.ENV_VAR)
    assert compile_cache.configure() is None
    assert jax.config.jax_compilation_cache_dir == before


def test_compile_cache_fixed_path_on_an_accelerator(monkeypatch):
    """An accelerator process with the variable unset caches under ONE
    fixed directory of the checkout — no temp name, pid or timestamp."""
    monkeypatch.delenv(compile_cache.ENV_VAR, raising=False)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    before = jax.config.jax_compilation_cache_dir
    try:
        assert compile_cache.configure() == os.path.join(REPO, ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == os.path.join(
            REPO, ".jax_cache"
        )
    finally:
        jax.config.update("jax_compilation_cache_dir", before)


@pytest.mark.skipif(
    not (shutil.which("make") and shutil.which("g++")),
    reason="needs make and g++",
)
def test_ensure_built_rebuilds_a_library_older_than_its_source(tmp_path):
    native = tmp_path / "native"
    native.mkdir()
    (native / "Makefile").write_text(
        "libpdtn_demo.so: demo.cpp\n"
        "\tg++ -O0 -shared -fPIC -o $@ $<\n"
    )
    (native / "demo.cpp").write_text('extern "C" int demo() { return 1; }\n')
    so = str(native / "libpdtn_demo.so")
    assert native_build.ensure_built(so)
    built = os.stat(so)
    # an up-to-date library is left alone
    assert native_build.ensure_built(so)
    assert os.stat(so).st_mtime_ns == built.st_mtime_ns
    # the source moves on: the existing file is no longer trusted
    os.utime(so, (built.st_mtime - 100, built.st_mtime - 100))
    (native / "demo.cpp").write_text('extern "C" int demo() { return 2; }\n')
    assert native_build.ensure_built(so)
    assert os.stat(so).st_mtime >= built.st_mtime
    import ctypes

    assert ctypes.CDLL(so).demo() == 2

"""The two guards of tests/conftest.py that let a whole run reach its end,
each proven on a small suite of its own run in a subprocess: a test that
blocks for ever fails alone with every thread's stack and the test after it
still runs (with xdist workers and without); a worker that dies late costs
one failure and the run ends."""

import os
import shutil
import subprocess
import sys

import pytest

BLOCKED = '''
import threading

import pytest

never = threading.Event()


def _parked_helper():
    never.wait()


@pytest.mark.time_limit(2)
def test_blocks_for_ever():
    threading.Thread(target=_parked_helper, daemon=True).start()
    never.wait()


def test_after_it_still_runs():
    pass
'''


XDIST = ["-p", "xdist", "-n", "2", "--dist", "loadfile"]


def _run_suite(tmp_path, files, mode):
    """pytest on ``files`` under a copy of this directory's conftest.py."""
    shutil.copy(os.path.join(os.path.dirname(__file__), "conftest.py"), tmp_path)
    for name, text in files.items():
        (tmp_path / name).write_text(text)
    run = subprocess.run(
        [sys.executable, "-m", "pytest", *sorted(files), "-q",
         "--rootdir", str(tmp_path), "-p", "no:cacheprovider",
         "-p", "no:randomly", *mode],
        cwd=tmp_path, capture_output=True, text=True, timeout=240,
    )
    return run, run.stdout + run.stderr


@pytest.mark.parametrize(
    "mode", [XDIST, ["-p", "no:xdist"]], ids=["xdist", "no_xdist"]
)
def test_blocked_test_fails_alone_with_stacks(tmp_path, mode):
    run, out = _run_suite(tmp_path, {"test_blocked.py": BLOCKED}, mode)
    assert run.returncode == 1, out
    assert "1 failed, 1 passed" in out, out
    assert "test_blocks_for_ever ran past its 2 s limit" in out, out
    # faulthandler's dump: the blocked main thread and the helper thread
    assert "most recent call first" in out, out
    assert "in test_blocks_for_ever" in out, out
    assert "in _parked_helper" in out, out


QUICK = "\n".join(f"def test_{c}(): pass" for c in "abcd")

# One test fewer than the quick files, so that the scheduler (most tests
# first) gives it to a worker that has finished a file already; test_1 lasts
# until the other worker is done and has been told to shut down.
DIES_ONCE = '''
import os
import time

MARK = os.path.join(os.path.dirname(__file__), "died_once")


def test_1():
    time.sleep(3)


def test_2():
    if not os.path.exists(MARK):
        open(MARK, "w").close()
        os.abort()


def test_3():
    pass
'''


def test_run_ends_after_a_worker_dies_late(tmp_path):
    """Without conftest's pytest_handlecrashitem this suite never ends under
    pytest-xdist 3.8.0: the replacement worker is handed a finished file."""
    files = {f"test_quick{i}.py": QUICK for i in range(3)}
    files["test_dies_once.py"] = DIES_ONCE
    run, out = _run_suite(tmp_path, files, XDIST)
    assert run.returncode == 1, out
    assert "crashed while running 'test_dies_once.py::test_2'" in out, out
    # the crash counts as one failure; the replacement worker runs test_2
    # again and the file's rest
    assert "1 failed, 15 passed" in out, out

"""Test harness: run everything on an 8-device virtual CPU mesh.

The reference had no tests at all (SURVEY.md §4); multi-node paths could only
be exercised by a real `mpirun`. Here every distributed path is testable on
one host: JAX's `--xla_force_host_platform_device_count` gives us 8 virtual
CPU devices to build real `jax.sharding.Mesh`es over.

Must run before `import jax` anywhere — hence env mutation at conftest import
time, and tests never override JAX_PLATFORMS.
"""

import faulthandler
import os
import signal
import sys
import threading

os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()
# Keep single-core CI deterministic and fast.
os.environ.setdefault("JAX_ENABLE_X64", "0")

import jax  # noqa: E402
import pytest  # noqa: E402

#: Seconds one test, its fixtures included, may take. Past it the test
#: fails alone with every thread's stack, and the tests after it still
#: run: under `--dist loadfile` a test that blocks for ever otherwise holds
#: the rest of its file until the whole run's limit cuts it (PR 25). Over
#: three times the slowest tier-1 test under `-n 6`;
#: `@pytest.mark.time_limit(seconds)` gives one test another limit.
TEST_TIME_LIMIT_S = 300.0


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "time_limit(seconds): this test's own time limit "
        "(default: conftest.TEST_TIME_LIMIT_S)",
    )


@pytest.hookimpl(optionalhook=True)
def pytest_handlecrashitem(crashitem, report, sched):
    """Keep the run going after a worker dies (an XLA abort, a segfault).

    pytest-xdist 3.8.0's loadfile scheduler puts every file the dead worker
    ever had back on its queue, the finished ones first, and hands the
    replacement worker one of those: nothing to run, so no completion ever
    asks for the next file, and once the other workers have finished the
    run sits until its limit cuts it (PR 25: a whole run held 10 minutes
    at 837 of 842 outcomes). Dropping the finished files leaves the crashed
    file's rest at the head of the queue."""
    queue = getattr(sched, "workqueue", None) or {}
    for scope in [s for s, unit in queue.items() if all(unit.values())]:
        del queue[scope]


@pytest.fixture(autouse=True)
def _time_limit(request):
    """Arm a wall-clock alarm around the test. A signal handler runs only
    on the main thread, and interrupts its lock waits, joins and sleeps."""
    if (
        not hasattr(signal, "SIGALRM")
        or threading.current_thread() is not threading.main_thread()
    ):
        yield
        return
    marker = request.node.get_closest_marker("time_limit")
    limit = float(marker.args[0]) if marker else TEST_TIME_LIMIT_S

    def on_alarm(signum, frame):
        faulthandler.dump_traceback(file=sys.__stderr__, all_threads=True)
        # pytest.fail raises a BaseException: no `except Exception` or
        # `except OSError` retry loop in the code under test swallows it
        pytest.fail(
            f"{request.node.nodeid} ran past its {limit:g} s limit "
            "(tests/conftest.py); every thread's stack is in the captured "
            "stderr"
        )

    previous = signal.signal(signal.SIGALRM, on_alarm)
    signal.setitimer(signal.ITIMER_REAL, limit)
    try:
        yield
    finally:
        signal.setitimer(signal.ITIMER_REAL, 0)
        signal.signal(signal.SIGALRM, previous)


@pytest.fixture(scope="session")
def devices():
    devs = jax.devices()
    assert len(devs) >= 8, f"expected >=8 virtual devices, got {len(devs)}"
    return devs


@pytest.fixture(scope="session")
def rng():
    return jax.random.PRNGKey(0)

"""Test harness: run everything on an 8-device virtual CPU mesh.

The reference had no tests at all (SURVEY.md §4); multi-node paths could only
be exercised by a real `mpirun`. Here every distributed path is testable on
one host: JAX's `--xla_force_host_platform_device_count` gives us 8 virtual
CPU devices to build real `jax.sharding.Mesh`es over.

Must run before `import jax` anywhere — hence env mutation at conftest import
time, and tests never override JAX_PLATFORMS.
"""

import os

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


@pytest.fixture(scope="session")
def devices():
    devs = jax.devices()
    assert len(devs) >= 8, f"expected >=8 virtual devices, got {len(devs)}"
    return devs


@pytest.fixture(scope="session")
def rng():
    return jax.random.PRNGKey(0)

"""Test harness: 8 simulated TPU ranks via the CPU host platform.

Mirrors the reference's test strategy (SURVEY.md §4): Horovod runs its
parallel suites under a real 2-process `horovodrun`; here N ranks are N
virtual devices in one process (`--xla_force_host_platform_device_count=8`),
which exercises the identical SPMD collective code paths that run on a pod
slice — better coverage per test than the reference's 2 processes.
"""

import os

# Must happen before jax import anywhere in the test process.  Set, not
# setdefault: the tests never take the chip, and every child they spawn
# inherits the choice.
os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import pytest  # noqa: E402

import horovod_tpu as hvd  # noqa: E402


def pytest_configure(config):
    config.addinivalue_line(
        "markers", "integration: multi-process integration tests")
    config.addinivalue_line(
        "markers", "slow: long-running tests excluded from the tier-1 "
        "smoke pass (-m 'not slow')")


@pytest.fixture(scope="session", autouse=True)
def hvd_init():
    hvd.init()
    yield
    hvd.shutdown()


@pytest.fixture()
def mesh():
    return hvd.global_mesh()

"""Test configuration.

TPU sharding tests run on a virtual 8-device CPU mesh
(``xla_force_host_platform_device_count``); real-TPU benchmarks live in
``bench.py``, not here.
"""

import os

flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = flags + " --xla_force_host_platform_device_count=8"

# Pin the platform before any backend initializes: assignment (not
# setdefault), because spawned ray workers inherit this env and must not
# open a real chip during the CPU suite, and a chip host's own
# environment names the TPU platform.
os.environ["JAX_PLATFORMS"] = "cpu"

import jax
import pytest

jax.config.update("jax_platforms", "cpu")


def pytest_configure(config):
    config.addinivalue_line(
        "markers", "slow: long-running tests excluded from the tier-1 run")
    config.addinivalue_line(
        "markers",
        "chaos: deterministic fault-injection tests (fast cases run tier-1; "
        "randomized seed sweeps are additionally marked slow)")


def pytest_sessionstart(session):
    # shm segments leaked by previously killed runs exhaust /dev/shm and
    # poison every store allocation in this run — clear them up front
    import glob
    import shutil

    for f in glob.glob("/dev/shm/raytpu_*"):
        try:
            if os.path.isdir(f):
                shutil.rmtree(f, ignore_errors=True)
            else:
                os.unlink(f)
        except OSError:
            pass


@pytest.fixture()
def ray_cluster():
    """One shared local cluster for API-level tests (reference
    ``ray_start_shared_local_modes`` style). Function-scoped but lazily
    shared: init() is a no-op while the cluster from a previous test is
    still up; tests that tear the global cluster down (multinode harness)
    simply cause the next user to boot a fresh one."""
    import ray_tpu

    ray_tpu.init(num_cpus=4, ignore_reinit_error=True)
    yield


def pytest_sessionfinish(session, exitstatus):
    import ray_tpu

    try:
        ray_tpu.shutdown()
    except Exception:
        pass

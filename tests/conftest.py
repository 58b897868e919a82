"""Test configuration.

TPU sharding tests run on a virtual 8-device CPU mesh
(``xla_force_host_platform_device_count``). Nothing here measures: the
device's numbers are ``benchmark/``'s, run by the driver on the chip.

Clusters. No cluster, chaos engine, virtual clock or config override
crosses a file boundary: every test file starts from none and leaves
none behind (``_no_inherited_cluster``, autouse), so a file that kills
workers or nodes, blacks out the GCS or installs a fault plan never
inherits a cluster other tests used and never bequeaths one it damaged;
such a file needs to do nothing for that. Inside a file, ``ray_cluster`` hands one
lazily shared local cluster from test to test, and boots a new one after
a test that failed (a test that timed out may have left its cluster
wedged, and would otherwise fail every test after it).
"""

import os

flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    flags += " --xla_force_host_platform_device_count=8"
# Most of a run's CPU time is the CPU compiler's: a debug stack with its
# interpreted Pallas calls is compiled once and run once. So LLVM's passes
# are off (what runs is a few steps at debug size), and every process of
# the run, the six workers, the ray workers they spawn and the rehearsals'
# subprocesses, which all inherit this environment, keeps what it compiled,
# however quick, where the others find it: the cache the program itself uses
# (``ray_tpu.tpu.compile_cache_env``), a directory set from outside left alone.
WITH_LLVM_PASSES = flags   # what ``tests/benchmark_suite/`` hands its rehearsals
if "xla_backend_optimization_level" not in flags:
    flags += " --xla_llvm_disable_expensive_passes=true --xla_backend_optimization_level=0"
os.environ["XLA_FLAGS"] = flags
from ray_tpu.tpu import compile_cache_env  # noqa: E402 - imports no jax

compile_cache_env(os.environ)
os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "0")
os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES", "-1")

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
        "randomized seed sweeps are additionally marked slow). Like every "
        "test file, a file of them starts from no cluster, chaos engine, "
        "virtual clock or config override and leaves none behind "
        "(conftest.py)")


def _mapped_shm_paths() -> set[str]:
    """Every ``/dev/shm/raytpu_*`` path that a live process has mapped. A
    process whose map this user may not read (init, another user's) is
    passed over: it started none of this user's clusters, and its own
    segments are not this user's (``pytest_sessionstart`` checks)."""
    import glob

    mapped = set()
    for maps in glob.glob("/proc/[0-9]*/maps"):
        try:
            with open(maps) as f:
                for line in f:
                    at = line.find("/dev/shm/raytpu_")
                    if at >= 0:
                        mapped.add(line[at:].rstrip("\n").removesuffix(" (deleted)"))
        except OSError:
            continue  # exited while we looked, or not ours to read
    return mapped


def pytest_sessionstart(session):
    # shm segments leaked by previously killed runs exhaust /dev/shm and
    # poison every store allocation in this run — clear them up front.
    # Only the dead ones: a segment some live process still maps (another
    # run of the tests, a benchmark's cluster beside them), one made in
    # the last minute (created, not yet mapped) or another user's is not
    # this run's to delete.
    # Once per run: the xdist workers leave it to the controller.
    if hasattr(session.config, "workerinput"):
        return
    import glob
    import shutil
    import time

    mapped = _mapped_shm_paths()
    for f in glob.glob("/dev/shm/raytpu_*"):
        try:
            st = os.stat(f)
            if st.st_uid != os.getuid() or time.time() - st.st_ctime < 60:
                continue
            if any(m == f or m.startswith(f + "/") for m in mapped):
                continue
            if os.path.isdir(f):
                shutil.rmtree(f, ignore_errors=True)
            else:
                os.unlink(f)
        except OSError:
            pass


_CLUSTER_SUSPECT = pytest.StashKey[bool]()


@pytest.hookimpl(hookwrapper=True)
def pytest_runtest_makereport(item, call):
    report = (yield).get_result()
    if report.failed:
        item.config.stash[_CLUSTER_SUSPECT] = True


def _no_cluster():
    import ray_tpu
    from ray_tpu import chaos
    from ray_tpu.core.rpc import set_chaos

    ray_tpu.shutdown()
    set_chaos(None)
    chaos.set_clock(None)


@pytest.fixture(scope="module", autouse=True)
def _the_suite_keeps_llvms_passes(request):
    """``tests/benchmark_suite/`` holds a rehearsal's steps to their share of
    a window (``test_bm_pauses.py``: a step beside a 0.9 s stop is left out
    whole, and at the unoptimised program's step time one stop alone passes
    the 15% cap), so its subprocesses compile as the program does; they
    share the run's compile cache all the same."""
    if "benchmark_suite" not in request.node.nodeid:
        yield
        return
    was, os.environ["XLA_FLAGS"] = os.environ["XLA_FLAGS"], WITH_LLVM_PASSES
    yield
    os.environ["XLA_FLAGS"] = was


@pytest.fixture()
def ray_cluster(request):
    """One shared local cluster for API-level tests (reference
    ``ray_start_shared_local_modes`` style). Function-scoped but lazily
    shared: init() is a no-op while the cluster from a previous test is
    still up; tests that tear the global cluster down (multinode harness)
    simply cause the next user to boot a fresh one. A cluster is never
    handed on from a test that failed: whatever that test left behind
    (a wedged lease queue, a dead worker pool) is shut down first."""
    import ray_tpu

    if request.config.stash.get(_CLUSTER_SUSPECT, False):
        request.config.stash[_CLUSTER_SUSPECT] = False
        _no_cluster()
    ray_tpu.init(num_cpus=4, ignore_reinit_error=True)
    yield


@pytest.fixture(scope="module", autouse=True)
def _no_inherited_cluster():
    """Every file starts from no cluster, no chaos engine, no virtual
    clock and the default config, and leaves it so. Whatever it boots
    after this (``ray_cluster``, a ``Cluster`` of its own) is its own.
    The config too: tests tighten timeouts and pass ``_system_config``,
    which writes to the one table every later cluster of the process
    reads."""
    from ray_tpu.core.config import reset_config

    _no_cluster()
    reset_config()
    yield
    _no_cluster()
    reset_config()

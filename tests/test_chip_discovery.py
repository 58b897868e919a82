"""Chip discovery, one process per chip, and the compile-cache helper.

CPU only: chips are faked with ``RAY_TPU_FAKE_CHIPS`` (the raylet then
advertises ``TPU`` and hands out chip indices exactly as on a chip host);
what the workers are checked for is the environment they were spawned
with, which is what makes libtpu open one chip and not the host.
"""

import os
import subprocess
import sys
import time

import pytest

import ray_tpu
from ray_tpu import tpu
from ray_tpu.core.raylet import Raylet, WorkerHandle

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_COUNT_IN_CHILD = """
import sys
from ray_tpu.tpu import detect_num_tpu_chips, detect_tpu_resources
print(detect_num_tpu_chips(), detect_tpu_resources().get("TPU"))
xb = sys.modules.get("jax._src.xla_bridge")
assert "jax" not in sys.modules, "chip discovery imported jax"
assert not (xb and xb._backends), "chip discovery created a JAX backend"
"""


@pytest.mark.parametrize("fake, jax_platforms, want", [
    (None, "cpu", "0 None"),      # this sandbox exposes no chip
    (None, None, "0 None"),       # ... whether or not the driver is pinned
    ("4", "cpu", "4 4.0"),
    ("1", None, "1 1.0"),
])
def test_discovery_counts_chips_without_jax(fake, jax_platforms, want):
    env = {k: v for k, v in os.environ.items()
           if k not in ("RAY_TPU_FAKE_CHIPS", "JAX_PLATFORMS")}
    if fake is not None:
        env["RAY_TPU_FAKE_CHIPS"] = fake
    if jax_platforms is not None:
        env["JAX_PLATFORMS"] = jax_platforms
    out = subprocess.run([sys.executable, "-c", _COUNT_IN_CHILD], env=env,
                         cwd=ROOT, capture_output=True, text=True, timeout=60)
    assert out.returncode == 0, out.stderr
    assert out.stdout.strip() == want


@pytest.mark.parametrize("accel, vfio, want", [
    (["/dev/accel0", "/dev/accel1"], None, 2),           # accel driver
    ([], ["0", "1", "2", "3", "vfio"], 4),               # numbered VFIO groups
    ([], ["vfio"], 0),                                   # the control node alone
    ([], None, 0),                                       # no /dev/vfio at all
])
def test_discovery_reads_device_files_not_the_environment(monkeypatch, accel,
                                                          vfio, want):
    def listdir(path):
        if vfio is None:
            raise FileNotFoundError(path)
        return vfio

    monkeypatch.delenv("RAY_TPU_FAKE_CHIPS", raising=False)
    # a one-chip machine cut from a four-chip host keeps the host's bounds
    monkeypatch.setenv("TPU_CHIPS_PER_HOST_BOUNDS", "2,2,1")
    monkeypatch.setattr(tpu.glob, "glob", lambda pattern: accel)
    monkeypatch.setattr(tpu.os, "listdir", listdir)
    assert tpu.detect_num_tpu_chips() == want


def test_chip_blocks_are_aligned_and_returned():
    raylet = Raylet.__new__(Raylet)
    raylet._free_chips = set(range(4))
    assert raylet._take_chips(1.0) == [0]
    assert raylet._take_chips(2.0) == [2, 3]     # not 1-2: no such sub-topology
    assert raylet._take_chips(1.0) == [1]
    with pytest.raises(ValueError, match="aligned block"):
        raylet._take_chips(1.0)
    with pytest.raises(ValueError, match="whole chips"):
        raylet._take_chips(0.5)
    w = WorkerHandle(worker_id="w", tpu_chips=[2, 3])
    raylet._return_chips(w)
    assert raylet._free_chips == {2, 3} and w.tpu_chips == []
    # two chips of four is a block the raylet can pick but libtpu did not
    # start on (PR 21 probe): refused, not guessed at
    for unsupported in ([2, 3], [0, 1, 2]):
        with pytest.raises(ValueError, match="1, 4 or 8"):
            tpu.set_visible_chips(unsupported)


def test_leases_get_disjoint_chips_and_a_host_lease_gets_all(monkeypatch):
    class _Seen:  # local: pickled by value, workers cannot import this file
        def env(self):
            return {k: v for k, v in os.environ.items()
                    if k.startswith("TPU_") or k == "JAX_COMPILATION_CACHE_DIR"}

    if ray_tpu.is_initialized():
        ray_tpu.shutdown()
    monkeypatch.setenv("RAY_TPU_FAKE_CHIPS", "4")
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    ray_tpu.init(num_cpus=4)
    try:
        assert ray_tpu.cluster_resources()["TPU"] == 4.0
        cls = ray_tpu.remote(resources={"TPU": 1}, num_cpus=0)(_Seen)
        a, b = cls.remote(), cls.remote()   # both hold their lease at once
        env_a, env_b = ray_tpu.get([a.env.remote(), b.env.remote()], timeout=60)
        assert {env_a["TPU_VISIBLE_CHIPS"], env_b["TPU_VISIBLE_CHIPS"]} == {"0", "1"}
        for env in (env_a, env_b):
            assert env["TPU_CHIPS_PER_PROCESS_BOUNDS"] == "1,1,1"
            assert env["TPU_PROCESS_BOUNDS"] == "1,1,1"
            # a worker that may compile for the chip keeps what it compiles
            assert env["JAX_COMPILATION_CACHE_DIR"] == os.path.join(ROOT, ".jax_cache")
        ray_tpu.kill(a)
        ray_tpu.kill(b)
        # the chips come back once the holders are dead; then one lease
        # of the whole host sees all four
        deadline = time.monotonic() + 60
        while ray_tpu.available_resources().get("TPU") != 4.0:
            assert time.monotonic() < deadline, "chips not returned"
            time.sleep(0.2)
        whole = ray_tpu.remote(resources={"TPU": 4}, num_cpus=0)(_Seen).remote()
        env = ray_tpu.get(whole.env.remote(), timeout=60)
        assert env["TPU_VISIBLE_CHIPS"] == "0,1,2,3"
        assert env["TPU_CHIPS_PER_PROCESS_BOUNDS"] == "2,2,1"
    finally:
        ray_tpu.shutdown()


def test_compile_cache_is_placed_from_outside_or_at_a_fixed_path():
    env = {"JAX_COMPILATION_CACHE_DIR": "/somewhere/else"}
    assert tpu.compile_cache_env(env) == "/somewhere/else"
    assert env == {"JAX_COMPILATION_CACHE_DIR": "/somewhere/else"}
    env = {}
    fixed = os.path.join(ROOT, ".jax_cache")
    assert tpu.compile_cache_env(env) == fixed
    assert env == {"JAX_COMPILATION_CACHE_DIR": fixed}
    assert tpu.compile_cache_env({}) == fixed   # no pid, time or temp name in it


def test_a_worker_that_leased_chips_refuses_to_run_on_the_cpu(monkeypatch):
    monkeypatch.setenv(tpu.ENV_VISIBLE_CHIPS, "0")
    assert tpu.leased_devices()[0].platform == "cpu"   # pinned to the CPU: fine
    monkeypatch.delenv("JAX_PLATFORMS")
    with pytest.raises(RuntimeError, match=r"leased TPU chips 0 .*CpuDevice"):
        tpu.leased_devices()
    monkeypatch.delenv(tpu.ENV_VISIBLE_CHIPS)
    assert tpu.leased_devices()                        # leased nothing: fine

"""TPU device-release fence.

The libtpu device lock is per-process and exclusive; the kernel releases
it only on process death. The raylet therefore kills a worker whose lease
held the ``TPU`` resource and re-grants that resource only once the
process is confirmed dead — otherwise the next TPU lease (e.g. a serve
replica starting right after a training job) crash-loops on device init
while the old holder drains (the round-3 serve-after-train failure).
"""

import os

import pytest

import ray_tpu
from ray_tpu.cluster_utils import Cluster


@pytest.fixture()
def tpu_cluster():
    if ray_tpu.is_initialized():
        ray_tpu.shutdown()  # replace the shared single-node cluster
    c = Cluster(
        initialize_head=True,
        head_node_args={"num_cpus": 2, "resources": {"TPU": 1.0}},
    )
    ray_tpu.init(address=c.address, num_cpus=0)
    yield c
    ray_tpu.shutdown()
    c.shutdown()


def _alive(pid: int) -> bool:
    try:
        os.kill(pid, 0)
        return True
    except OSError:
        return False


def test_tpu_lease_pipeline_reuses_the_holder_process(tpu_cluster):
    """Same-shape TPU tasks share a lease pipeline and thus the SAME
    process — the holder keeps the device; no restart tax per task."""

    @ray_tpu.remote(resources={"TPU": 1.0}, num_cpus=0)
    def f():
        return os.getpid()

    pids = {ray_tpu.get(f.remote(), timeout=60) for _ in range(3)}
    assert len(pids) == 1, f"TPU tasks in one pipeline should share a process, got {pids}"


def test_tpu_handoff_waits_for_holder_death(tpu_cluster):
    """Once a TPU lease is RETURNED, the next grant (here: a different
    resource shape, so a fresh lease) happens only after the previous
    holder's process is dead — no crash-looping on a held device lock."""

    @ray_tpu.remote(resources={"TPU": 1.0}, num_cpus=0)
    def hold():
        return os.getpid()

    pid1 = ray_tpu.get(hold.remote(), timeout=60)

    @ray_tpu.remote(resources={"TPU": 1.0}, num_cpus=1)
    def second(prev_pid):
        try:
            os.kill(prev_pid, 0)
            prev_alive = True
        except OSError:
            prev_alive = False
        return os.getpid(), prev_alive

    pid2, prev_alive = ray_tpu.get(second.remote(pid1), timeout=60)
    assert pid2 != pid1
    assert not prev_alive, "previous TPU holder was still alive at grant time"


def test_tpu_handoff_after_actor_kill(tpu_cluster):
    """The serve-after-train pattern: a long-lived TPU actor is killed and
    the next TPU actor starts first-try, after the holder died."""

    @ray_tpu.remote(resources={"TPU": 1.0}, num_cpus=0)
    class Holder:
        def pid(self):
            return os.getpid()

    a = Holder.remote()
    pid1 = ray_tpu.get(a.pid.remote(), timeout=60)
    ray_tpu.kill(a)

    b = Holder.remote()
    pid2 = ray_tpu.get(b.pid.remote(), timeout=60)
    assert pid2 != pid1
    assert not _alive(pid1), "killed TPU actor still alive after next grant"
    ray_tpu.kill(b)


def test_non_tpu_workers_still_pooled(tpu_cluster):
    """The fence is TPU-specific: plain CPU workers keep being reused."""

    @ray_tpu.remote(num_cpus=1)
    def f():
        return os.getpid()

    pids = {ray_tpu.get(f.remote(), timeout=60) for _ in range(3)}
    assert len(pids) == 1, f"CPU workers should be pooled, got {pids}"


def test_tpu_fence_survives_pg_teardown(tpu_cluster):
    """Killing a bundle-leased TPU actor and removing its placement group
    immediately (the ShardedEngineExecutor.shutdown pattern) must NOT
    re-grant the chip before the holder process is dead — _drop_bundle
    withholds fenced TPU shares from its release."""
    from ray_tpu.util import (
        PlacementGroupSchedulingStrategy,
        placement_group,
        remove_placement_group,
    )

    pg = placement_group([{"TPU": 1.0, "CPU": 1.0}])
    assert pg.wait(timeout_seconds=60)

    @ray_tpu.remote(resources={"TPU": 1.0}, num_cpus=0)
    class Holder:
        def pid(self):
            return os.getpid()

    a = Holder.options(
        scheduling_strategy=PlacementGroupSchedulingStrategy(
            placement_group=pg, placement_group_bundle_index=0),
    ).remote()
    pid1 = ray_tpu.get(a.pid.remote(), timeout=60)
    ray_tpu.kill(a)
    remove_placement_group(pg)  # immediately, as multi-host teardown does

    @ray_tpu.remote(resources={"TPU": 1.0}, num_cpus=0)
    def next_lease(prev):
        try:
            os.kill(prev, 0)
            return os.getpid(), True
        except OSError:
            return os.getpid(), False

    pid2, prev_alive = ray_tpu.get(next_lease.remote(pid1), timeout=60)
    assert pid2 != pid1
    assert not prev_alive, "PG teardown re-granted the chip before holder death"


def test_tpu_grant_fence_waits_for_external_lock_holder(tmp_path, monkeypatch):
    """GRANT-side fence: the libtpu device lock may be held by a process
    the raylet never tracked (a benchmark phase, a stray trainer). The
    first TPU lease after such a handoff must wait for the lock, not
    start a worker that crash-loops on device init."""
    import fcntl
    import threading
    import time as _time

    lockfile = tmp_path / "libtpu_lockfile"
    monkeypatch.setenv("RAY_TPU_LOCKFILE", str(lockfile))
    # Simulate the external holder: take the flock in THIS process.
    fd = os.open(lockfile, os.O_CREAT | os.O_RDWR, 0o666)
    fcntl.flock(fd, fcntl.LOCK_EX | fcntl.LOCK_NB)

    if ray_tpu.is_initialized():
        ray_tpu.shutdown()
    c = Cluster(
        initialize_head=True,
        head_node_args={"num_cpus": 2, "resources": {"TPU": 1.0}},
    )
    ray_tpu.init(address=c.address, num_cpus=0)
    try:
        @ray_tpu.remote(resources={"TPU": 1.0}, num_cpus=0)
        def probe():
            return _time.time()

        released_at = [None]

        def release_later():
            _time.sleep(3.0)
            released_at[0] = _time.time()
            fcntl.flock(fd, fcntl.LOCK_UN)

        t = threading.Thread(target=release_later)
        t.start()
        ran_at = ray_tpu.get(probe.remote(), timeout=60)
        t.join()
        assert released_at[0] is not None
        assert ran_at >= released_at[0], (
            "TPU task ran while the external device lock was still held")
    finally:
        os.close(fd)
        ray_tpu.shutdown()
        c.shutdown()

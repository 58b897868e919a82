"""Object spilling, primary-copy pinning, and the node memory monitor.

Reference behaviors being matched (TPU-native redesign, not a port):
  - primary copies are pinned and never silently evicted
    (src/ray/raylet/local_object_manager.h:110);
  - under memory pressure pinned objects spill to disk and restore on Get
    (python/ray/_private/external_storage.py:72);
  - the memory watcher kills the newest retriable lease instead of letting
    the OS OOM-kill the node (src/ray/common/memory_monitor.h:52,
    worker_killing_policy.cc).
"""

import os
import time

import numpy as np
import pytest

import ray_tpu
from ray_tpu.native.store import ShmStore


def _raylet():
    from ray_tpu.core import api

    return api._node.raylet


# ---------------------------------------------------------------- store unit


def test_pinned_object_survives_eviction(tmp_path):
    store = ShmStore(str(tmp_path / "arena"), 1 << 20)
    a, b = b"a" * 8, b"b" * 8
    store.put_sealed(a, b"payload-a")
    store.put_sealed(b, b"payload-b")
    store.pin(a)
    store.evict(1 << 20)
    assert store.contains(a) == 2  # pinned: survives
    assert store.contains(b) == 0  # unpinned: evicted
    store.unpin(a)
    store.evict(1 << 20)
    assert store.contains(a) == 0
    store.close()


def test_refcounted_object_not_evictable(tmp_path):
    store = ShmStore(str(tmp_path / "arena"), 1 << 20)
    a = b"a" * 8
    store.put_sealed(a, b"payload")
    store.add_ref(a)
    assert store.ref_count(a) == 1
    store.evict(1 << 20)
    assert store.contains(a) == 2
    store.release(a)
    store.evict(1 << 20)
    assert store.contains(a) == 0
    store.close()


# ------------------------------------------------------------ spill e2e


def test_ingest_2x_store_capacity_without_data_loss():
    """VERDICT #9 acceptance: put 2x the store's capacity while keeping every
    ObjectRef live; nothing may be lost — cold primaries spill to disk and
    restore on get."""
    ray_tpu.shutdown()
    capacity = 8 * 1024 * 1024
    ray_tpu.init(num_cpus=2, object_store_memory=capacity)
    try:
        n, size = 16, 1024 * 1024  # 16 MiB total = 2x capacity
        arrays = [np.full(size // 8, i, dtype=np.int64) for i in range(n)]
        refs = [ray_tpu.put(a) for a in arrays]

        raylet = _raylet()
        assert raylet._spilled, "expected spilling at 2x capacity"
        debug = {"spilled_bytes_total": raylet._spilled_bytes_total}
        assert debug["spilled_bytes_total"] > 0

        for i, ref in enumerate(refs):
            out = ray_tpu.get(ref)
            np.testing.assert_array_equal(out, arrays[i])
        assert raylet._restored_bytes_total > 0
    finally:
        ray_tpu.shutdown()


def test_task_returns_spill_and_restore():
    """Task returns are sealed through the raylet and therefore pinned;
    overflowing the store with returns must spill, not drop them."""
    ray_tpu.shutdown()
    ray_tpu.init(num_cpus=2, object_store_memory=8 * 1024 * 1024)
    try:

        @ray_tpu.remote
        def make(i):
            return np.full(256 * 1024, i, dtype=np.int64)  # 2 MiB each

        refs = [make.remote(i) for i in range(8)]  # 16 MiB total
        for i, ref in enumerate(refs):
            np.testing.assert_array_equal(ray_tpu.get(ref), np.full(256 * 1024, i, dtype=np.int64))
    finally:
        ray_tpu.shutdown()


def test_spilled_state_visible_in_list_objects():
    import asyncio

    ray_tpu.shutdown()
    ray_tpu.init(num_cpus=1, object_store_memory=8 * 1024 * 1024)
    try:
        refs = [ray_tpu.put(np.zeros(1024 * 1024 // 8, dtype=np.int64)) for _ in range(12)]
        raylet = _raylet()
        assert raylet._spilled
        listing = asyncio.run(raylet.handle_ListObjects({}))
        states = {o["object_id"]: o["state"] for o in listing["objects"]}
        assert "SPILLED" in states.values(), f"no SPILLED state in {set(states.values())}"
        assert "SEALED" in states.values()
        del refs
    finally:
        ray_tpu.shutdown()


def test_live_zero_copy_view_survives_spill_pressure():
    """A deserialized array aliases the shm arena; while it is alive the
    raylet holds a read ref (plasma Buffer lifetime semantics), so spilling
    under pressure must neither corrupt nor relocate it."""
    import gc

    ray_tpu.shutdown()
    ray_tpu.init(num_cpus=1, object_store_memory=8 * 1024 * 1024)
    try:
        src = np.arange(256 * 1024, dtype=np.int64)  # 2 MiB
        ref0 = ray_tpu.put(src)
        out0 = ray_tpu.get(ref0)  # zero-copy view into the arena

        # Flood the store with 2x capacity: everything spillable spills.
        refs = [ray_tpu.put(np.zeros(1024 * 1024 // 8, dtype=np.int64)) for _ in range(16)]
        raylet = _raylet()
        assert raylet._spilled
        np.testing.assert_array_equal(out0, src)  # view never corrupted
        assert ref0.id().binary() not in raylet._spilled

        oid = ref0.id().binary()
        del out0
        gc.collect()
        deadline = time.monotonic() + 10
        while raylet.store.ref_count(oid) > 0 and time.monotonic() < deadline:
            time.sleep(0.05)
        # Released: refcount drops to 0 — or to -1 (absent) if the proactive
        # spiller already moved the now-unreferenced object to disk.
        assert raylet.store.ref_count(oid) <= 0, "read ref leaked after view GC"
        if raylet.store.ref_count(oid) == -1:
            assert oid in raylet._spilled, "object vanished instead of spilling"
        np.testing.assert_array_equal(ray_tpu.get(ref0), src)  # still retrievable
        del refs
    finally:
        ray_tpu.shutdown()


# ----------------------------------------------------------- memory monitor


def test_oom_killer_kills_newest_retriable_lease_and_task_retries(tmp_path):
    ray_tpu.shutdown()
    ray_tpu.init(num_cpus=2)
    try:
        raylet = _raylet()
        fired = []
        marker = str(tmp_path / "attempts")

        def fake_usage():
            # High once, and only when the first attempt is inside its
            # body (its mark is written): a kill between the lease and
            # the task's first line leaves no mark, and the retry, which
            # nothing kills, then looks like a first attempt.
            if not fired and os.path.exists(marker) and any(
                w.state == "leased" and w.retriable for w in raylet._workers.values()
            ):
                fired.append(1)
                return 0.99
            return 0.0

        raylet._memory_usage_fn = fake_usage

        @ray_tpu.remote(max_retries=2)
        def flaky():
            with open(marker, "a") as f:
                f.write("x")
            attempts = os.path.getsize(marker)
            if attempts == 1:
                time.sleep(10)  # killed by the memory monitor mid-sleep
            return 42

        result = ray_tpu.get(flaky.remote(), timeout=60)
        assert result == 42
        assert fired, "memory monitor never fired"
        assert os.path.getsize(marker) >= 2, "task was not retried"
    finally:
        ray_tpu.shutdown()

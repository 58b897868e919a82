"""GCS fault tolerance: durable tables + restart recovery.

Reference: ``src/ray/gcs/store_client/redis_store_client.h:107`` (GCS
state survives in Redis; gcs_server restarts and clients reconnect).
Redesign under test: atomic-snapshot FileStorage + raylet heartbeat
re-registration + RetryableRpcClient reconnection on the same port.
"""

import time

import pytest

import ray_tpu
from ray_tpu.cluster_utils import Cluster
from ray_tpu.core.gcs_storage import FileStorage, pack_tables, unpack_tables


def test_file_storage_roundtrip_and_atomicity(tmp_path):
    st = FileStorage(str(tmp_path / "snap.msgpack"))
    tables = {"kv": {"a": b"\x00\x01"}, "jobs": {}, "next_job": 3,
              "actors": {}, "named_actors": {"n": "deadbeef"}, "placement_groups": {}}
    st.save_blob(pack_tables(tables))
    assert st.load() == tables
    # corrupt file -> load returns None, never raises
    (tmp_path / "snap.msgpack").write_bytes(b"garbage")
    assert FileStorage(str(tmp_path / "snap.msgpack")).load() is None


@pytest.fixture()
def ft_cluster():
    if ray_tpu.is_initialized():
        ray_tpu.shutdown()
    c = Cluster(
        initialize_head=True,
        head_node_args={"num_cpus": 4},
        enable_gcs_ft=True,
        _system_config={"health_check_failure_threshold": 3},
    )
    ray_tpu.init(address=c.address, num_cpus=0)
    yield c
    ray_tpu.shutdown()
    c.shutdown()


def test_what_was_acknowledged_survives_a_crash_at_once(ft_cluster):
    """A reply that acknowledges a change to a durable table leaves only
    after the snapshot that holds it (``gcs.durable``): the GCS is killed
    right after the acks, inside the 200 ms window the periodic snapshot
    would have needed, and every acknowledged change is there after the
    restart — a put, a delete, a job id handed out (a lost one is handed
    out twice), a named actor's registration."""
    from ray_tpu.core.worker import global_worker

    call = global_worker()._gcs_call

    @ray_tpu.remote
    class Named:
        def ping(self):
            return "pong"

    call("KvPut", {"key": "gone", "value": b"x", "overwrite": True})
    time.sleep(0.5)  # "gone" is in a snapshot: only an acked delete removes it
    call("KvPut", {"key": "kept", "value": b"v", "overwrite": True})
    assert call("KvDel", {"key": "gone"})["deleted"]
    job_id = call("AddJob", {"driver_address": ""})["job_id"]
    Named.options(name="acked", lifetime="detached", num_cpus=0.1).remote()
    ft_cluster.crash_gcs()
    ft_cluster.restart_gcs()
    ft_cluster.wait_for_nodes(2, timeout=30)

    assert call("KvGet", {"key": "kept"})["value"] == b"v"
    assert not call("KvGet", {"key": "gone"})["found"]
    assert call("AddJob", {"driver_address": ""})["job_id"] == job_id + 1
    assert ray_tpu.get(ray_tpu.get_actor("acked").ping.remote(), timeout=60) == "pong"


def test_concurrent_acknowledgements_share_a_snapshot(tmp_path):
    """Group commit: the ``durable`` handlers that finish in one turn of
    the event loop wait for ONE snapshot, so sixteen clients writing at
    once cost far fewer whole-table writes than writes acknowledged
    (2,400 against 196 puts a second at 2 MiB of state, PR 29), and every
    acknowledged write is in the file."""
    import asyncio

    from ray_tpu.core.gcs import GcsServer
    from ray_tpu.core.rpc import RpcClient

    async def storm():
        storage = FileStorage(str(tmp_path / "snap.msgpack"))
        gcs = GcsServer(storage=storage)
        await gcs.start()
        saves = []
        save_blob = storage.save_blob
        storage.save_blob = lambda blob: (saves.append(1), save_blob(blob))
        clients = [RpcClient(gcs.address) for _ in range(16)]

        async def puts(client, j):
            for i in range(20):
                await client.call("KvPut", {"key": f"k:{j}:{i}", "value": b"v",
                                            "overwrite": True}, 30.0)

        await asyncio.gather(*[puts(c, j) for j, c in enumerate(clients)])
        for c in clients:
            await c.close()
        await gcs.crash()  # no final flush: only what the acks waited for
        return len(saves), storage.load()

    saves, tables = asyncio.run(storm())
    assert len(tables["kv"]) == 320
    assert saves < 160, f"{saves} snapshots for 320 acknowledged puts"


def test_gcs_restart_recovers_cluster(ft_cluster):
    """Named detached actor, KV (function exports), and node membership all
    survive a GCS crash + restart; new work schedules afterwards."""

    @ray_tpu.remote
    class Counter:
        def __init__(self):
            self.n = 0

        def incr(self):
            self.n += 1
            return self.n

    counter = Counter.options(name="survivor", lifetime="detached").remote()
    assert ray_tpu.get(counter.incr.remote(), timeout=60) == 1
    time.sleep(0.6)  # let the persist loop snapshot the actor record

    ft_cluster.crash_gcs()
    ft_cluster.restart_gcs()

    # Raylets re-register within a heartbeat period.
    ft_cluster.wait_for_nodes(2, timeout=30)  # head + driver node

    # The named actor record was restored; the actor process never died.
    handle = ray_tpu.get_actor("survivor")
    assert ray_tpu.get(handle.incr.remote(), timeout=60) == 2

    # New tasks schedule on the recovered cluster (function defs in KV).
    @ray_tpu.remote
    def after_restart():
        return "scheduled"

    assert ray_tpu.get(after_restart.remote(), timeout=60) == "scheduled"


def test_actor_death_during_gcs_outage_reported_after_restart(ft_cluster):
    """An actor worker that dies while the GCS is down must still be
    reported once the GCS returns (queued death reports), not restored as
    a ghost ALIVE record."""
    import os
    import signal

    @ray_tpu.remote
    class Victim:
        def pid(self):
            return os.getpid()

    victim = Victim.options(name="victim", lifetime="detached").remote()
    pid = ray_tpu.get(victim.pid.remote(), timeout=60)
    time.sleep(0.6)  # snapshot the ALIVE record

    ft_cluster.crash_gcs()
    os.kill(pid, signal.SIGKILL)  # dies while the GCS is down
    time.sleep(1.0)
    ft_cluster.restart_gcs()

    deadline = time.monotonic() + 30
    while time.monotonic() < deadline:
        record = ft_cluster.gcs._actors.get(victim._actor_id.hex())
        if record is not None and record["state"] == "DEAD":
            break
        time.sleep(0.2)
    assert record is not None and record["state"] == "DEAD", record and record["state"]


def test_gcs_restart_without_ft_loses_state():
    """Control: with the default memory storage, a restarted GCS comes back
    empty (documents why enable_gcs_ft matters)."""
    if ray_tpu.is_initialized():
        ray_tpu.shutdown()
    c = Cluster(initialize_head=True, head_node_args={"num_cpus": 2})
    try:
        ray_tpu.init(address=c.address, num_cpus=0)

        @ray_tpu.remote
        class A:
            def ping(self):
                return "pong"

        A.options(name="gone", lifetime="detached").remote()
        time.sleep(0.5)
        c.crash_gcs()
        c.restart_gcs()
        c.wait_for_nodes(2, timeout=30)
        with pytest.raises(ValueError):
            ray_tpu.get_actor("gone")
    finally:
        ray_tpu.shutdown()
        c.shutdown()


def test_gcs_crash_during_actor_creation(ft_cluster):
    """The GCS dies WHILE actor creations are in flight: after restart,
    every creation either completes (restored PENDING records reschedule)
    or the caller gets a clean failure — never a silent hang (reference
    test_gcs_fault_tolerance.py actor-creation races)."""

    @ray_tpu.remote
    class Slow:
        def __init__(self):
            time.sleep(0.3)

        def ping(self):
            return "pong"

    actors = [Slow.options(num_cpus=0.1).remote() for _ in range(6)]
    time.sleep(0.15)  # mid-creation
    ft_cluster.crash_gcs()
    time.sleep(0.5)
    ft_cluster.restart_gcs()

    ok, dead = 0, 0
    for a in actors:
        try:
            assert ray_tpu.get(a.ping.remote(), timeout=60) == "pong"
            ok += 1
        except Exception:
            dead += 1
    # no hangs; the restored GCS must still be able to create NEW actors
    assert ok + dead == 6
    fresh = Slow.options(num_cpus=0.1).remote()
    assert ray_tpu.get(fresh.ping.remote(), timeout=60) == "pong"


def test_gcs_crash_during_pg_commit(ft_cluster):
    """The GCS dies in the middle of placement-group 2PC: after restart,
    creating placement groups works and the cluster's resources are not
    leaked by half-committed bundles."""
    from ray_tpu.util import placement_group, remove_placement_group

    pgs = [placement_group([{"CPU": 1}], strategy="PACK") for _ in range(3)]
    ft_cluster.crash_gcs()
    time.sleep(0.3)
    ft_cluster.restart_gcs()

    # Old PGs: ready or not, removal must not wedge anything.
    for pg in pgs:
        try:
            pg.wait(timeout_seconds=15)
        except Exception:
            pass
        try:
            remove_placement_group(pg)
        except Exception:
            pass
    # The full capacity must be allocatable again (no leaked reservations).
    fresh = placement_group([{"CPU": 1}, {"CPU": 1}], strategy="PACK")
    assert fresh.wait(timeout_seconds=60)
    remove_placement_group(fresh)


def test_gcs_crash_during_long_poll(ft_cluster, capfd):
    """A worker-log long-poll (driver side) survives a GCS restart: lines
    printed AFTER the restart actually reach the driver echo (cursor
    clamping on the restarted publisher), not just the task result."""

    @ray_tpu.remote
    def speak(tag):
        print(f"LOGLINE-{tag}")
        return tag

    assert ray_tpu.get(speak.remote("before"), timeout=60) == "before"
    ft_cluster.crash_gcs()
    time.sleep(0.3)
    ft_cluster.restart_gcs()
    assert ray_tpu.get(speak.remote("after"), timeout=60) == "after"
    # the driver's log-echo poller must deliver the post-restart line
    seen = ""
    deadline = time.time() + 30
    while "LOGLINE-after" not in seen and time.time() < deadline:
        time.sleep(0.5)
        out = capfd.readouterr()
        seen += out.out + out.err
    assert "LOGLINE-after" in seen, "post-restart worker log never reached the driver"

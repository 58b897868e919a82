"""Core task-path throughput machinery (ISSUE 6): lease multiplexing,
same-shape lease coalescing, task-event flush coalescing, the adaptive
push-batch invariants, and the per-call lease-denial-reason contract.

These are the SEMANTIC-EQUIVALENCE nets for the perf work: every
batched/coalesced path must produce the same grants, the same task
records, and the same recovery behavior as the serial path it replaces.
"""

from __future__ import annotations

import asyncio
import time

import pytest

import ray_tpu
from ray_tpu.core.config import get_config
from ray_tpu.core.task_events import (
    GcsTaskEventStore,
    TaskEventBuffer,
    coalesce_events,
    expand_event,
)
from ray_tpu.core.task_spec import TaskSpec
from ray_tpu.core.worker import (
    _next_push_batch,
    _pop_push_batch,
    global_worker,
)


@pytest.fixture()
def _knobs():
    """Snapshot/restore the config entries these tests tune."""
    cfg = get_config()
    keys = ("lease_grant_batch_size", "task_event_coalesce_ms",
            "worker_register_timeout_s", "task_push_batch_size",
            "rpc_max_retries")
    saved = {k: getattr(cfg, k) for k in keys}
    yield cfg
    for k, v in saved.items():
        setattr(cfg, k, v)


# ------------------------------------------------- push-batch invariants


def _spec(name: str, args: list | None = None) -> TaskSpec:
    return TaskSpec(task_id=name.encode(), job_id=b"j", name=name,
                    function_id=b"f", args=args or [])


def _inline_arg() -> dict:
    return {"t": "v", "meta": b"", "blob": b"x"}


def _ref_arg() -> dict:
    return {"t": "r", "id": b"o" * 28, "owner": "addr"}


def test_pop_push_batch_short_queue_never_batches():
    # A queue no deeper than the pipeline cap is parallel opportunity:
    # other pipelines can run those specs concurrently on other workers.
    queue = [_spec(f"t{i}") for i in range(5)]
    assert len(_pop_push_batch(queue, cur_batch=16, pipeline_cap=10)) == 1
    assert len(queue) == 4


def test_pop_push_batch_deep_queue_batches_to_cur_batch():
    queue = [_spec(f"t{i}") for i in range(30)]
    assert len(_pop_push_batch(queue, cur_batch=8, pipeline_cap=10)) == 8
    assert len(queue) == 22


def test_pop_push_batch_objectref_arg_ships_alone():
    # A ref-arg spec's dependency may be produced by an earlier spec of
    # the same batch, whose result only reaches the owner with the reply
    # — batching them would deadlock the chain.
    queue = ([_spec("a"), _spec("b")]
             + [_spec("r", [_ref_arg()])]
             + [_spec(f"c{i}") for i in range(20)])
    first = _pop_push_batch(queue, cur_batch=16, pipeline_cap=2)
    assert [s.name for s in first] == ["a", "b"]
    second = _pop_push_batch(queue, cur_batch=16, pipeline_cap=2)
    assert [s.name for s in second] == ["r"]
    # and a ref-arg spec at the head goes out alone too
    queue2 = [_spec("r2", [_ref_arg()])] + [_spec(f"d{i}") for i in range(20)]
    assert [s.name for s in _pop_push_batch(queue2, 16, 2)] == ["r2"]


def test_pop_push_batch_mixed_args_only_ref_matters():
    queue = ([_spec("v", [_inline_arg()])]
             + [_spec(f"w{i}") for i in range(20)])
    batch = _pop_push_batch(queue, cur_batch=4, pipeline_cap=2)
    assert len(batch) == 4  # inline args batch normally


def test_next_push_batch_ramps_and_resets():
    # fast batches ramp 1 -> 4 -> 16 (capped)
    assert _next_push_batch(1, 0.001, 16) == 4
    assert _next_push_batch(4, 0.001, 16) == 16
    assert _next_push_batch(16, 0.001, 16) == 16
    # ANY slow batch resets to 1 — a batch serializes execution on one
    # worker while other leased workers idle
    assert _next_push_batch(16, 0.25, 16) == 1
    assert _next_push_batch(4, 0.006, 16) == 1


# ------------------------------------- task-event coalescing equivalence


def _stage_recorder():
    calls: list[tuple] = []
    return calls, lambda stage, ms, node: calls.append((stage, round(ms, 6), node))


def test_event_coalescing_store_equivalence():
    """The acceptance net: a coalesced flush must produce byte-identical
    task records AND identical lease-stage histogram observations to the
    unbatched flush."""
    buf = TaskEventBuffer("w1", "n1")
    t0 = time.time()
    for i in range(20):
        tid = bytes([i]) * 4
        buf.record(tid, f"task{i}", "SUBMITTED")
        buf.record(tid, f"task{i}", "LEASED",
                   extra={"queue_wait_ms": 1.5, "spawn_ms": 0.25,
                          "worker_id": f"lease-worker-{i}"})
        buf.record(tid, f"task{i}", "RUNNING")
        buf.record(tid, f"task{i}", "FINISHED")
    raw, _ = buf.drain(coalesce_window_ms=0)
    assert len(raw) == 80
    coalesced = coalesce_events([dict(e) for e in raw], window_ms=60_000)
    assert len(coalesced) == 20  # one wire event per task
    assert all(len(e["transitions"]) == 4 for e in coalesced)

    plain_calls, plain_cb = _stage_recorder()
    co_calls, co_cb = _stage_recorder()
    plain_store = GcsTaskEventStore(on_stage=plain_cb)
    co_store = GcsTaskEventStore(on_stage=co_cb)
    plain_store.add_events(raw)
    co_store.add_events(coalesced)

    assert plain_store.list_tasks(limit=100) == co_store.list_tasks(limit=100)
    assert plain_calls == co_calls
    assert plain_store.count_by_state() == co_store.count_by_state()
    # timestamps survived exactly (records already compared equal, but be
    # explicit about the thing the histograms are computed from)
    for rec in co_store.list_tasks(limit=100):
        assert rec["events"]["SUBMITTED"] >= t0


def test_event_coalescing_window_splits_groups():
    events = [
        {"task_id": "a", "name": "t", "status": "SUBMITTED", "ts": 0.0,
         "worker_id": "w", "node_id": "n", "kind": 0},
        {"task_id": "a", "name": "t", "status": "RUNNING", "ts": 10.0,
         "worker_id": "w", "node_id": "n", "kind": 0},
    ]
    out = coalesce_events([dict(e) for e in events], window_ms=1000)
    assert len(out) == 2  # 10s apart: beyond the window, two wire events


def test_event_coalescing_passes_span_and_memory_through():
    events = [
        {"task_id": "a", "name": "t", "status": "SUBMITTED", "ts": 1.0,
         "worker_id": "w", "node_id": "n", "kind": 0},
        {"task_id": "tr1", "name": "s", "status": "SPAN", "ts": 1.0,
         "worker_id": "w", "node_id": "n", "kind": 0, "span": {"name": "s"}},
        {"task_id": "", "name": "memory_summary", "status": "MEMORY",
         "ts": 1.0, "worker_id": "w", "node_id": "n", "kind": 0,
         "memory": {"worker_id": "w"}},
        {"task_id": "a", "name": "t", "status": "FINISHED", "ts": 1.1,
         "worker_id": "w", "node_id": "n", "kind": 0},
    ]
    out = coalesce_events([dict(e) for e in events], window_ms=60_000)
    statuses = sorted(e["status"] for e in out)
    assert statuses == ["FINISHED", "MEMORY", "SPAN"]
    merged = [e for e in out if e.get("transitions")][0]
    assert [t["status"] for t in merged["transitions"]] == [
        "SUBMITTED", "FINISHED"]
    # expansion inverts exactly
    back = expand_event(merged)
    assert [e["status"] for e in back] == ["SUBMITTED", "FINISHED"]
    assert back[0]["task_id"] == "a" and back[0]["ts"] == 1.0


def test_event_coalescing_preserves_per_transition_extras():
    events = [
        {"task_id": "a", "name": "t", "status": "SUBMITTED", "ts": 1.0,
         "worker_id": "w", "node_id": "n", "kind": 0, "trace_id": "tr"},
        {"task_id": "a", "name": "t", "status": "LEASED", "ts": 1.1,
         "worker_id": "lease-w", "node_id": "n", "kind": 0,
         "queue_wait_ms": 3.5},
        {"task_id": "a", "name": "t", "status": "FAILED", "ts": 1.2,
         "worker_id": "w", "node_id": "n", "kind": 0, "error": "boom"},
    ]
    [merged] = coalesce_events([dict(e) for e in events], window_ms=60_000)
    back = expand_event(merged)
    assert back[0]["trace_id"] == "tr"
    assert back[1]["worker_id"] == "lease-w"  # per-transition override
    assert back[1]["queue_wait_ms"] == 3.5
    assert back[2]["error"] == "boom"
    assert merged["status"] == "FAILED"  # wire dict doubles as last status


# ------------------------------------------ lease denial reason contract


def test_lease_denial_reason_returned_per_call(ray_cluster, _knobs):
    """Regression for the `_last_lease_denial` race: two concurrent
    acquires for DIFFERENT scheduling shapes, replies interleaved so the
    second denial lands while the first is still in flight — each caller
    must see ITS OWN reason, and no shared instance attribute may exist."""
    w = global_worker()
    real_raylet = w.raylet

    class _StubRaylet:
        address = real_raylet.address

        async def call(self, method, payload=None, timeout=None):
            if method == "RequestWorkerLease":
                res = (payload["spec"].get("resources") or {})
                if "ShapeA" in res:
                    # A's denial arrives AFTER B's has been processed —
                    # the exact overwrite window of the old attribute.
                    await asyncio.sleep(0.3)
                    return {"granted": False, "reason": "reason-A"}
                return {"granted": False, "reason": "reason-B"}
            return await real_raylet.call(method, payload, timeout)

    spec_a = _spec("a")
    spec_a.resources = {"ShapeA": 1.0}
    spec_b = _spec("b")
    spec_b.resources = {"ShapeB": 1.0}
    w.raylet = _StubRaylet()
    try:
        async def _both():
            return await asyncio.gather(
                w._acquire_lease(spec_a), w._acquire_lease(spec_b))

        (la, ra), (lb, rb) = w.io.run_sync(_both())
    finally:
        w.raylet = real_raylet
    assert la is None and lb is None
    assert ra == "reason-A"
    assert rb == "reason-B"
    # the racy shared attribute is gone for good
    assert not hasattr(w, "_last_lease_denial")


def test_infeasible_lease_error_names_raylet_reason(ray_cluster, _knobs):
    cfg = _knobs
    cfg.worker_register_timeout_s = 1.5

    @ray_tpu.remote(max_retries=0, resources={"NoSuchThing": 1})
    def f():
        return 1

    with pytest.raises(Exception, match="infeasible"):
        ray_tpu.get(f.remote(), timeout=60)


# --------------------------------------- lease multiplexing equivalence


def test_multiplexed_lease_grants_equivalent_results(ray_cluster, _knobs):
    """Same workload under lease_grant_batch_size 1 (serial protocol) and
    4 (multiplexed): identical results, every task FINISHED — the grants
    differ only in how many round trips they cost."""
    cfg = _knobs

    @ray_tpu.remote
    def sq(i):
        return i * i

    for batch in (1, 4):
        cfg.lease_grant_batch_size = batch
        assert ray_tpu.get([sq.remote(i) for i in range(40)],
                           timeout=60) == [i * i for i in range(40)]


def test_raylet_extra_grants_lease_state(ray_cluster, _knobs):
    """Raylet-level contract: extra grants are real leases — resources
    acquired per grant, workers marked leased and un-acked until AckLease,
    everything released by ReturnWorker."""
    from ray_tpu.core import api as core_api

    node = core_api._node
    raylet = node.raylet

    # make sure a couple of idle default-env workers exist
    @ray_tpu.remote
    def warm():
        return None

    ray_tpu.get([warm.remote() for _ in range(8)])
    time.sleep(0.3)

    async def _run():
        idle_before = sum(1 for wid in raylet._idle
                          if raylet._workers[wid].env_hash == "")
        avail_before = raylet.resources.available.get("CPU")
        spec = {"task_id": b"mux-test", "name": "mux", "kind": 0,
                "resources": {"CPU": 1.0}, "max_retries": 1}
        reply = await raylet.handle_RequestWorkerLease(
            {"spec": spec, "num_workers": 3})
        assert reply["granted"], reply
        grants = [reply["worker_id"]] + [
            g["worker_id"] for g in reply.get("extra_grants") or ()]
        if idle_before >= 2:
            assert len(grants) >= 2, (idle_before, reply)
        for wid in grants:
            h = raylet._workers[wid]
            assert h.state == "leased"
            assert h.lease_resources.get("CPU") == 1.0
            assert h.lease_acked is False
        assert raylet.resources.available.get("CPU") == \
            avail_before - len(grants)
        await raylet.handle_AckLease({"worker_id": grants[0],
                                      "worker_ids": grants[1:]})
        assert all(raylet._workers[wid].lease_acked for wid in grants)
        for wid in grants:
            await raylet.handle_ReturnWorker({"worker_id": wid})
        assert raylet.resources.available.get("CPU") == avail_before
        return len(grants)

    assert node.services_loop.run_sync(_run(), timeout=30) >= 1
    # cluster still fully usable afterwards
    assert ray_tpu.get(warm.remote(), timeout=30) is None


def test_multiplexed_lease_recovers_from_dropped_reply(ray_cluster, _knobs):
    """ISSUE 6 acceptance: `rpc drop RequestWorkerLease` still recovers
    WITH multiplexing on — dropped grant replies strand multi-grants,
    the orphan watchdog reclaims them, retries land, every task settles."""
    from ray_tpu import chaos
    from ray_tpu.core.rpc import set_chaos

    cfg = _knobs
    cfg.lease_grant_batch_size = 4
    cfg.worker_register_timeout_s = 5.0
    saved_orphan = cfg.lease_orphan_timeout_s
    cfg.lease_orphan_timeout_s = 1.0

    @ray_tpu.remote(max_retries=5)
    def val(i):
        return i

    plan = {"name": "mux-lease-drop",
            "faults": [{"kind": "rpc", "method": "RequestWorkerLease",
                        "where": "response", "nth": 2,
                        "max_injections": 2}]}
    try:
        report = chaos.run_plan(
            plan, seed=7, verify=False,
            workload=lambda: ray_tpu.get(
                [val.remote(i) for i in range(24)], timeout=60))
        assert report["workload"] == list(range(24))
    finally:
        set_chaos(None)
        # Drain the stranded un-acked leases NOW, while the orphan
        # timeout is still 1 s: left behind, they age out ~10 s later
        # inside whatever test shares the cluster next — the cross-file
        # test_lease_wedge_watchdog_fires flake was exactly this test's
        # strands meeting that test's injected wedge entries.
        from ray_tpu.core import api as core_api

        raylet = core_api._node.raylet

        def _drained() -> bool:
            stale = any(
                w.state in ("leased", "dedicated") and not w.lease_acked
                and not w.loop_pinned for w in raylet._workers.values())
            waiting = any(not e["fut"].done()
                          for e in raylet._admission_queue)
            return not stale and not waiting

        deadline = time.monotonic() + 30
        while not _drained() and time.monotonic() < deadline:
            time.sleep(0.2)
        assert _drained(), "stranded un-acked leases were not reclaimed"
        cfg.lease_orphan_timeout_s = saved_orphan


def test_lease_coalesce_degrade_is_config_knob(ray_cluster, _knobs):
    """ISSUE 14 small fix: the stuck-leader de-coalesce window is the
    `lease_coalesce_degrade_ms` config entry (was a hard-coded 0.5 s) —
    a follower parked on a wedged leader's gate must degrade to its own
    lease RPC after the configured window."""
    cfg = get_config()
    saved = cfg.lease_coalesce_degrade_ms
    cfg.lease_coalesce_degrade_ms = 120.0
    w = global_worker()
    key = ("degrade-test", 0)
    acquires: list[int] = []

    async def scenario():
        # A leader holds the gate and NEVER resolves its waiters (the
        # stuck-leader shape: dropped reply / wedged spawn).
        w._lease_gates[key] = {"waiters": []}
        real_acquire = w._acquire_lease

        async def stub_acquire(spec, num_workers=1):
            acquires.append(num_workers)
            return None, "stub-denied"

        w._acquire_lease = stub_acquire
        try:
            t0 = time.monotonic()
            leases, reason = await w._acquire_lease_shared(key, _spec("d"))
            waited = time.monotonic() - t0
        finally:
            w._acquire_lease = real_acquire
            w._lease_gates.pop(key, None)
        return leases, reason, waited

    leases, reason, waited = w.io.run_sync(scenario(), timeout=30)
    # degraded: issued its OWN acquire after ~the configured window, not
    # the old 0.5 s constant and not the full RPC timeout
    assert leases is None and reason == "stub-denied"
    assert acquires, "follower never de-coalesced"
    assert 0.08 <= waited < 0.45, waited
    cfg.lease_coalesce_degrade_ms = saved


def test_lease_coalesce_degrade_reads_chaos_clock(ray_cluster, _knobs):
    """The degrade deadline rides the chaos clock: under a FROZEN
    VirtualClock the follower never degrades on wall time alone; an
    explicit advance() past the window fires it deterministically."""
    from ray_tpu.chaos import clock as chaos_clock

    cfg = get_config()
    saved = cfg.lease_coalesce_degrade_ms
    cfg.lease_coalesce_degrade_ms = 1000.0
    w = global_worker()
    key = ("degrade-vclock", 0)
    vclock = chaos_clock.VirtualClock(rate=0.0)  # frozen: manual advance only

    async def scenario():
        w._lease_gates[key] = {"waiters": []}
        real_acquire = w._acquire_lease
        degraded = asyncio.Event()

        async def stub_acquire(spec, num_workers=1):
            degraded.set()
            return None, "vclock-denied"

        w._acquire_lease = stub_acquire
        chaos_clock.set_clock(vclock)
        try:
            waiter = asyncio.ensure_future(
                w._acquire_lease_shared(key, _spec("v")))
            # Frozen clock: 0.4 real seconds (wall would NOT have degraded
            # yet anyway at 1000 ms — but virtual time hasn't moved at all).
            await asyncio.sleep(0.4)
            assert not degraded.is_set()
            vclock.advance(2.0)  # virtual 2 s > the 1 s window
            await asyncio.wait_for(degraded.wait(), timeout=10.0)
            leases, reason = await asyncio.wait_for(waiter, timeout=10.0)
            return leases, reason
        finally:
            chaos_clock.set_clock(None)
            w._acquire_lease = real_acquire
            w._lease_gates.pop(key, None)

    leases, reason = w.io.run_sync(scenario(), timeout=60)
    assert leases is None and reason == "vclock-denied"
    cfg.lease_coalesce_degrade_ms = saved


def test_node_table_refresh_is_shared(ray_cluster):
    """Concurrent refreshers ride one in-flight GetAllNodes, and a
    max_age hit skips the RPC entirely."""
    from ray_tpu.core import api as core_api

    node = core_api._node
    raylet = node.raylet
    calls = {"n": 0}
    real_gcs = raylet._gcs
    cfg = get_config()
    saved_hb = cfg.health_check_period_ms
    # Park the heartbeat loop (it refreshes the node table on its own
    # cadence and would race the counters); in-flight beat drains below.
    cfg.health_check_period_ms = 120_000
    time.sleep(1.3)

    class _CountingGcs:
        async def call(self, method, payload=None, timeout=None):
            if method == "GetAllNodes":
                calls["n"] += 1
                await asyncio.sleep(0.05)
            return await real_gcs.call(method, payload, timeout)

    async def _run():
        raylet._gcs = _CountingGcs()
        try:
            await asyncio.gather(*[raylet._refresh_node_table()
                                   for _ in range(8)])
            shared = calls["n"]
            await raylet._refresh_node_table(max_age_s=60.0)
            return shared, calls["n"]
        finally:
            raylet._gcs = real_gcs

    try:
        shared, after_cached = node.services_loop.run_sync(_run(), timeout=30)
    finally:
        cfg.health_check_period_ms = saved_hb
    assert shared == 1, f"8 concurrent refreshes paid {shared} RPCs"
    assert after_cached == shared  # max_age hit: no extra RPC


def test_actor_call_batching_equivalence(ray_cluster, _knobs):
    """A burst of calls to serialized actors (batched PushActorTasks) and
    a concurrency>1 actor (never batched) both keep per-actor order and
    exact results."""

    @ray_tpu.remote
    class Seq:
        def __init__(self):
            self.log = []

        def add(self, i):
            self.log.append(i)
            return i

        def get_log(self):
            return list(self.log)

    actors = [Seq.remote() for _ in range(3)]
    refs = [a.add.remote(i) for i in range(30) for a in actors]
    assert ray_tpu.get(refs, timeout=60) == [
        i for i in range(30) for _ in actors]
    for a in actors:
        # strict submission order per actor: the batched path must not
        # reorder (log ends with the add() calls in order, after them
        # the get_log call itself is serialized too)
        assert ray_tpu.get(a.get_log.remote(), timeout=30) == list(range(30))

    @ray_tpu.remote(max_concurrency=4)
    class Conc:
        def val(self, i):
            return i * 3

    c = Conc.remote()
    assert ray_tpu.get([c.val.remote(i) for i in range(20)],
                       timeout=60) == [i * 3 for i in range(20)]

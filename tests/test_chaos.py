"""Deterministic chaos subsystem: seeded fault plans, virtual time, and
automated recovery verification.

Mirrors the reference's chaos tests (``rpc_chaos.h`` +
``python/ray/tests/test_network_failure*.py`` style) with the
FoundationDB/Jepsen twist this build adds: every fault comes from a
seeded FaultPlan whose compiled schedule is byte-identical across runs,
and every scenario must end RecoveryVerifier-green.
"""

import os
import signal
import time

import pytest

import ray_tpu
from ray_tpu import chaos
from ray_tpu.core.config import get_config
from ray_tpu.core.rpc import RpcChaos, get_chaos, set_chaos
from ray_tpu.util import state

pytestmark = pytest.mark.chaos


def _wait_for(predicate, timeout=30.0, interval=0.2):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        result = predicate()
        if result:
            return result
        time.sleep(interval)
    return predicate()


@pytest.fixture(autouse=True)
def _clean_chaos():
    """Every test leaves no chaos engine, no virtual clock, and the
    config entries it touched restored."""
    cfg = get_config()
    saved = {k: getattr(cfg, k) for k in (
        "worker_register_timeout_s", "lease_orphan_timeout_s",
        "lease_wedge_threshold_s", "lease_wedge_check_interval_s",
        "memory_leak_check_interval_s", "memory_leak_intervals",
        "memory_leak_min_growth_refs", "memory_leak_min_growth_bytes",
        "memory_report_interval_ms", "task_events_flush_interval_ms",
        "rpc_max_retries", "rpc_retry_jitter", "task_max_retries",
        "lease_grant_batch_size")}
    yield
    set_chaos(None)
    chaos.set_clock(None)
    for key, value in saved.items():
        setattr(cfg, key, value)


# --------------------------------------------------------------- unit layer
def test_rpc_chaos_spec_modes():
    """Env-spec grammar: legacy positional probs stay compatible;
    nth-mode is deterministic; delay parses; same seed => same draws."""
    legacy = RpcChaos("Foo=1.0,0.0", seed=1)
    assert legacy.should_fail_request("Foo")
    assert not legacy.should_fail_response("Bar")

    nth = RpcChaos("Foo=nth:3,max:2", seed=1)
    hits = [nth.should_fail_request("Foo") for _ in range(9)]
    # deterministic: every 3rd call, capped at 2 injections
    assert hits == [False, False, True, False, False, True,
                    False, False, False]

    delay = RpcChaos("Foo=req:0.0,delay:50")
    assert delay.request_delay_s("Foo") == pytest.approx(0.05)
    assert delay.request_delay_s("Other") == 0.0

    a = RpcChaos("Foo=0.5,0.5", seed=42)
    b = RpcChaos("Foo=0.5,0.5", seed=42)
    draws_a = [a.should_fail_request("Foo") for _ in range(32)]
    draws_b = [b.should_fail_request("Foo") for _ in range(32)]
    assert draws_a == draws_b  # seeded: reproducible
    assert any(draws_a) and not all(draws_a)

    wild = RpcChaos("*=nth:1,max:1")
    assert wild.should_fail_request("Anything")
    assert not wild.should_fail_request("Anything")  # max hit
    assert ("rpc_request_drop", "Anything") in wild.injections_total


def test_retry_backoff_full_jitter(monkeypatch):
    """RetryableRpcClient: jitter ON samples U(0, base*2^n) windows;
    OFF keeps the legacy deterministic doubling (config flag)."""
    import asyncio

    from ray_tpu.core import rpc as rpc_mod

    cfg = get_config()
    cfg.rpc_max_retries = 3
    saved_base = cfg.rpc_retry_base_delay_ms
    cfg.rpc_retry_base_delay_ms = 20

    uniform_calls: list[tuple] = []
    real_uniform = rpc_mod.random.uniform

    def recording_uniform(a, b):
        uniform_calls.append((a, b))
        return 0.0 if a == 0.0 else real_uniform(a, b)

    monkeypatch.setattr(rpc_mod.random, "uniform", recording_uniform)

    def drive():
        async def _run():
            client = rpc_mod.RetryableRpcClient("127.0.0.1:1")  # dead port
            with pytest.raises(rpc_mod.RpcError):
                await client.call("Nope", {})
            await client.close()

        loop = asyncio.new_event_loop()
        t0 = time.monotonic()
        try:
            loop.run_until_complete(_run())
        finally:
            loop.close()
        return time.monotonic() - t0

    try:
        cfg.rpc_retry_jitter = True
        drive()
        base = cfg.rpc_retry_base_delay_ms / 1000.0
        # filter to the full-jitter windows this client sampled (a == 0)
        windows = [b for a, b in uniform_calls if a == 0.0][:3]
        assert windows == [base, base * 2, base * 4]

        uniform_calls.clear()
        cfg.rpc_retry_jitter = False
        elapsed = drive()
        assert not [c for c in uniform_calls if c[0] == 0.0]  # no sampling
        # legacy deterministic doubling: 20+40+80 ms of sleeps, minimum
        assert elapsed >= 0.13
    finally:
        cfg.rpc_retry_base_delay_ms = saved_base


def test_virtual_clock():
    clock = chaos.VirtualClock(rate=0.0)
    t0 = clock.now()
    time.sleep(0.05)
    assert clock.now() == t0  # frozen until advanced
    clock.advance(10.0)
    assert clock.now() == pytest.approx(t0 + 10.0)

    scaled = chaos.VirtualClock(rate=100.0)
    s0 = scaled.now()
    time.sleep(0.05)
    assert scaled.now() - s0 > 1.0  # 100x wall


def test_fault_schedule_byte_identical(capsys):
    """`cli chaos run <plan> --seed N --dry-run` prints a byte-identical
    schedule across runs; a different seed changes probabilistic plans."""
    from ray_tpu.cli import main

    assert main(["chaos", "run", "mixed-seeded", "--seed", "7",
                 "--dry-run"]) == 0
    first = capsys.readouterr().out
    assert main(["chaos", "run", "mixed-seeded", "--seed", "7",
                 "--dry-run"]) == 0
    second = capsys.readouterr().out
    assert first == second and first.strip()

    assert main(["chaos", "run", "mixed-seeded", "--seed", "8",
                 "--dry-run"]) == 0
    other_seed = capsys.readouterr().out
    assert other_seed != first

    assert main(["chaos", "plans"]) == 0
    listing = capsys.readouterr().out
    assert "lease-reply-drop" in listing and "gcs-blackout" in listing


# --------------------------------------------------------- cluster scenarios
@pytest.fixture()
def chaos_cluster(ray_cluster, _clean_chaos):
    """Shared local cluster with lease/watchdog knobs tightened so the
    fault scenarios resolve in seconds, not default-production minutes."""
    cfg = get_config()
    cfg.worker_register_timeout_s = 5.0
    cfg.lease_orphan_timeout_s = 1.0
    cfg.lease_wedge_check_interval_s = 0.2
    cfg.lease_wedge_threshold_s = 1.0
    yield


def test_run_plan_rpc_drop_task_retry_succeeds(chaos_cluster):
    """Bundled `push-client-drop`: owner-side PushTask drops; every task
    must settle successfully via retry, injections must be recorded and
    chaos-tagged, and recovery must verify green."""
    report = chaos.run_plan("push-client-drop", seed=1, verify_timeout_s=60)
    assert report["verify"]["ok"], report["verify"]["violations"]
    assert report["workload"]["failures"] == 0, report["workload"]
    assert any(k.startswith("rpc_client_drop") for k in report["injections"])
    # injected faults are distinguishable from organic failures
    tagged = [e for e in state.list_errors(limit=1000)
              if e.get("source") == "chaos"
              and (e.get("extra") or {}).get("chaos")
              and e.get("extra", {}).get("plan") == "push-client-drop"]
    assert tagged, "chaos injections never reached list_errors()"


def test_run_plan_worker_kill_lease_retry(chaos_cluster):
    """Bundled `worker-kill`: the first lease's worker is SIGKILLed at
    grant; the owner retries on a fresh worker and the run verifies."""
    report = chaos.run_plan("worker-kill", seed=0, verify_timeout_s=60)
    assert report["verify"]["ok"], report["verify"]["violations"]
    assert report["workload"]["failures"] == 0, report["workload"]
    assert report["injections"].get("kill_worker:kill_worker", 0) >= 1


def test_lease_reply_drop_orphan_reclaim(chaos_cluster):
    """Bundled `lease-reply-drop` (the ROADMAP-1c trigger): grant replies
    die on the wire. The owner's lease retry budget rides it out AND the
    raylet reclaims the stranded (never-acked) grants — before the
    AckLease/orphan-reclaim fix each dropped reply permanently stranded a
    CPU reservation and the suite cascaded into lease timeouts."""

    @ray_tpu.remote(max_retries=5)
    def probe(i):
        return i * i

    def workload():
        refs = [probe.remote(i) for i in range(8)]
        return {"results": ray_tpu.get(refs, timeout=60)}

    report = chaos.run_plan("lease-reply-drop", seed=3, workload=workload,
                            verify_timeout_s=60)
    assert report["verify"]["ok"], report["verify"]["violations"]
    assert report["workload"]["results"] == [i * i for i in range(8)]
    if report["injections"].get("rpc_response_drop:RequestWorkerLease"):
        # A grant reply was actually dropped: its reservation must have
        # been reclaimed (visible in debug state + the error channel).
        orphans = _wait_for(lambda: state.list_errors(
            error_type="lease_orphan", limit=1000))
        assert orphans, "stranded lease was never reclaimed"
        diag = state.cluster_diagnostics(error_limit=0)
        assert any(n.get("orphan_leases_total", 0) >= 1
                   for n in diag["nodes"])


def test_worker_kill_lineage_reconstruction(chaos_cluster):
    """Object lost from plasma after its worker finished: the owner
    resubmits the producing task from pinned lineage on get()."""
    import numpy as np

    from ray_tpu.core import api as core_api

    @ray_tpu.remote(max_retries=2)
    def make_blob():
        import numpy as np

        return np.arange(65536, dtype=np.float32)

    ref = make_blob.remote()
    first = ray_tpu.get(ref, timeout=60)
    assert first.shape == (65536,)
    del first  # release the zero-copy read pin before deleting the copy

    node = core_api._node
    oid = ref.id().binary()
    _wait_for(lambda: node.raylet.store.ref_count(oid) == 0, timeout=10)
    node.services_loop.run_sync(
        node.raylet.handle_PlasmaDelete({"id": oid, "force": True}))

    value = ray_tpu.get(ref, timeout=60)  # lineage reconstruction
    assert isinstance(value, np.ndarray) and value[-1] == 65535.0


def test_gcs_blackout_client_reconnects(chaos_cluster):
    """Bundled `gcs-blackout`: the GCS endpoint is unreachable for the
    window; RetryableRpcClient backoff rides it out and the driver
    reconnects — tasks submitted during the blackout still complete."""
    cfg = get_config()
    cfg.rpc_max_retries = 12  # enough backoff budget to cross the window

    @ray_tpu.remote(max_retries=5)
    def ping(i):
        return i + 1

    def workload():
        t0 = time.monotonic()
        refs = [ping.remote(i) for i in range(4)]
        results = ray_tpu.get(refs, timeout=60)
        return {"results": results, "elapsed_s": time.monotonic() - t0}

    report = chaos.run_plan("gcs-blackout", seed=0, workload=workload,
                            verify_timeout_s=60)
    assert report["verify"]["ok"], report["verify"]["violations"]
    assert report["workload"]["results"] == [1, 2, 3, 4]
    assert any(k.startswith("gcs_blackout") for k in report["injections"]), \
        report["injections"]
    # after the window the control plane answers again
    assert state.list_nodes()


def test_spill_write_error_object_survives(chaos_cluster):
    """Spill-disk write errors (bundled `spill-disk-error`): the disk
    write fails but the blob is retained in the pending buffer — the
    object restores from memory, degraded but never lost."""
    import numpy as np

    from ray_tpu.core import api as core_api

    value = np.arange(131072, dtype=np.float32)  # ~512 KB: plasma-sized
    ref = ray_tpu.put(value)
    node = core_api._node
    oid = ref.id().binary()
    _wait_for(lambda: node.raylet.store.ref_count(oid) == 0, timeout=10)

    engine = chaos.install("spill-disk-error", seed=0)
    try:
        async def _force_spill():
            return node.raylet._spill_objects(value.nbytes)

        freed = node.services_loop.run_sync(_force_spill())
        assert freed >= value.nbytes
        # the (async, executor-thread) disk write must have hit the fault
        assert _wait_for(lambda: engine.injections_total.get(
            ("spill_error", "spill_error")), timeout=10)
        # shm copy is gone, disk write failed -> pending buffer serves it
        assert node.raylet.store.contains(oid) == 0
        assert oid in node.raylet._spill_pending
    finally:
        chaos.uninstall()
    restored = ray_tpu.get(ref, timeout=60)
    assert np.array_equal(restored, value)


def test_fault_plan_kills_loop_stage_mid_loop(chaos_cluster):
    """Compiled-loop chaos (round 8): a `kill_loop_stage` FaultPlan rule
    kills one stage actor at EXACTLY its Nth tick (deterministic —
    between consuming the tick's inputs and producing its output). The
    driver must surface the death on a bounded get(), teardown must
    cascade through the surviving stages within a clock-bounded window
    (no stage left parked on a dead peer's channel), and recovery must
    verify green."""
    from ray_tpu.chaos.verifier import RecoveryVerifier
    from ray_tpu.dag import InputNode, compile_loop

    @ray_tpu.remote
    class Stage:
        def __init__(self, k):
            self.k = k

        def f(self, x):
            return x + self.k

    verifier = RecoveryVerifier(timeout_s=60)
    baseline = verifier.snapshot_baseline()
    a, b = Stage.remote(1), Stage.remote(10)
    plan = {"name": "loop-stage-kill", "faults": [
        {"kind": "kill_loop_stage", "nth": 3, "max_injections": 1}]}

    def _install_in_actor(instance, plan_dict, seed):
        # Runs IN the stage actor process: loop-tick faults fire where
        # the resident executor runs, not on the driver.
        from ray_tpu import chaos as _chaos

        _chaos.install(_chaos.FaultPlan.from_dict(plan_dict), seed,
                       publish=False)
        return True

    assert ray_tpu.get(
        a.__ray_call__.remote(_install_in_actor, plan, 0), timeout=60)

    with InputNode() as inp:
        dag = b.f.bind(a.f.bind(inp))
    loop = compile_loop(dag, credits=2)
    try:
        # ticks 1 and 2 stream normally; tick 3 kills stage `a` mid-tick
        assert loop.run(1) == 12
        assert loop.run(2) == 13
        loop.put(3)
        t0 = time.monotonic()
        with pytest.raises(Exception):
            loop.get(timeout=45.0)
        assert time.monotonic() - t0 < 60.0, "stage death never surfaced"
    finally:
        loop.teardown()
    # cascade completed within the (chaos-clock-measured) window: the
    # surviving stage exited via the force-closed ring, not a hang
    assert loop.torn_down_in_s < 30.0
    result = verifier.verify(baseline)
    assert result.ok, result.violations


def test_serve_replica_kill_request_retried(chaos_cluster):
    """A replica SIGKILLed under load: the in-flight request is re-routed
    to a live replica (router purges the corpse; the controller replaces
    it) instead of surfacing ActorDiedError to the caller."""
    from ray_tpu import serve

    @serve.deployment(num_replicas=1)
    class Echo:
        def pid(self):
            import os

            return os.getpid()

        def hello(self, x):
            return f"hello {x}"

    handle = serve.run(Echo.bind(), name="chaosapp", route_prefix=None,
                       _blocking=False)
    try:
        assert _wait_for(
            lambda: handle.hello.remote("a").result(timeout=30) == "hello a",
            timeout=60)
        pid = handle.pid.remote().result(timeout=30)
        os.kill(pid, signal.SIGKILL)
        # the request that lands on the corpse is retried on the
        # controller's replacement replica
        assert handle.hello.remote("b").result(timeout=60) == "hello b"
    finally:
        try:
            serve.delete("chaosapp")
        except Exception:
            pass


def test_affinity_map_survives_replica_death(chaos_cluster):
    """ISSUE 10: a prefix-group's affine replica SIGKILLed under it —
    the router purges the corpse's groups, the retried request lands on
    the replacement (riding the existing replica-death retry path), and
    the group's state there is COLD (fresh instance, no carried KV)."""
    import uuid as _uuid

    from ray_tpu import serve

    @serve.deployment(num_replicas=1)
    class Sticky:
        def __init__(self):
            self.instance = _uuid.uuid4().hex
            self.seen = 0

        def pid(self):
            import os

            return os.getpid()

        def ask(self, x):
            self.seen += 1
            return {"instance": self.instance, "seen": self.seen,
                    "answer": f"ok {x}"}

    handle = serve.run(Sticky.bind(), name="affchaos", route_prefix=None,
                       _blocking=False)
    session = handle.options(prefix_group="sess:chaos")
    try:
        first = None
        deadline = time.monotonic() + 60
        while time.monotonic() < deadline and first is None:
            try:
                first = session.ask.remote("a").result(timeout=30)
            except Exception:
                time.sleep(0.5)
        assert first and first["answer"] == "ok a"
        router = handle._get_router()
        affine = router._group_affinity.get("sess:chaos")
        assert affine is not None
        pid = session.pid.remote().result(timeout=30)
        os.kill(pid, signal.SIGKILL)
        # retried on the controller's replacement; the router must have
        # purged the corpse's group before re-routing
        second = session.ask.remote("b").result(timeout=60)
        assert second["answer"] == "ok b"
        assert second["instance"] != first["instance"]  # state died: cold
        assert second["seen"] == 1
        remapped = router._group_affinity.get("sess:chaos")
        assert remapped is not None and remapped != affine
    finally:
        try:
            serve.delete("affchaos")
        except Exception:
            pass


def test_cli_doctor_reports_active_fault_plan(chaos_cluster, capsys):
    """Operators must be able to tell injected pain from real pain:
    `cli doctor` shows the registered FaultPlan while one is installed."""
    from ray_tpu.cli import main

    chaos.install("worker-kill", seed=9)
    try:
        assert main(["doctor"]) == 0
        out = capsys.readouterr().out
        assert "ACTIVE FAULT PLAN" in out and "worker-kill" in out
        assert "seed=9" in out
    finally:
        chaos.uninstall()
    assert main(["doctor"]) == 0
    assert "ACTIVE FAULT PLAN" not in capsys.readouterr().out


def test_roadmap_1c_cascade_repro_under_virtual_clock(chaos_cluster):
    """ROADMAP 1c: the mid-suite lease-timeout cascade, reproduced
    deterministically — lease-RPC reply drops strand CPU reservations
    while leaked-ref pressure builds, under accelerated VirtualClock.

    Asserts the full diagnosis chain fires (lease_orphan reclaim, the
    wedge watchdog, the GCS memory_leak watcher) AND that the cluster
    heals: with the AckLease/orphan-reclaim fix every task completes and
    RecoveryVerifier ends green. Without the fix (revert the AckLease
    handshake) the stranded reservations never return and this test
    times out exactly like the original round-5 cascade."""
    import numpy as np

    cfg = get_config()
    cfg.worker_register_timeout_s = 4.0
    # Pin the serial one-lease-per-RPC protocol this cascade repro was
    # built on: owner-side lease multiplexing/coalescing (PR 6) issues
    # far fewer RequestWorkerLease RPCs for a same-shape burst, so the
    # admission queue never backs up behind the stranded grants and the
    # wedge stage of the diagnosis chain (correctly) has nothing to
    # report. The multiplexed path's recovery under the same fault is
    # covered by test_core_throughput.py::test_multiplexed_lease_recovers_from_dropped_reply.
    cfg.lease_grant_batch_size = 1
    cfg.lease_orphan_timeout_s = 2.0          # virtual seconds
    cfg.lease_wedge_threshold_s = 1.0         # virtual seconds
    cfg.lease_wedge_check_interval_s = 0.2
    cfg.memory_leak_check_interval_s = 0.3
    cfg.memory_leak_intervals = 2
    cfg.memory_leak_min_growth_refs = 10
    cfg.memory_leak_min_growth_bytes = 1
    cfg.memory_report_interval_ms = 150
    cfg.task_events_flush_interval_ms = 100

    # Virtual time at 5x: the multi-second watchdog thresholds replay in
    # fractions of real seconds, deterministically ordered by the clock.
    chaos.set_clock(chaos.VirtualClock(rate=5.0))

    plan = {
        "name": "roadmap-1c-cascade",
        "faults": [
            {"kind": "rpc", "method": "RequestWorkerLease",
             "where": "response", "nth": 2, "max_injections": 3},
        ],
    }

    @ray_tpu.remote(max_retries=5)
    def busy(i):
        time.sleep(0.2)
        return i

    leaked = []

    def workload():
        refs = [busy.remote(i) for i in range(8)]
        # leaked-ref pressure: the driver's refcount table grows
        # monotonically across memory reports while the cascade runs
        deadline = time.monotonic() + 4.0
        while time.monotonic() < deadline:
            leaked.extend(ray_tpu.put(np.zeros(256)) for _ in range(8))
            time.sleep(0.1)
        results = ray_tpu.get(refs, timeout=60)
        return {"results": results}

    report = chaos.run_plan(plan, seed=2, workload=workload,
                            verify=False)
    assert report["workload"]["results"] == list(range(8))
    assert report["injections"].get("rpc_response_drop:RequestWorkerLease"), \
        report["injections"]

    # the full diagnosis chain fired
    assert _wait_for(lambda: state.list_errors(
        error_type="lease_orphan", limit=1000), timeout=20), \
        "orphan-lease reclaim never fired"
    assert _wait_for(lambda: state.list_errors(
        error_type="lease_wedge", limit=1000), timeout=20), \
        "wedge watchdog never fired on the cascade"
    assert _wait_for(lambda: state.list_errors(
        error_type="memory_leak", limit=1000), timeout=30), \
        "memory_leak watcher never flagged the leaked-ref pressure"

    # drop the pressure and verify the cluster healed completely
    leaked.clear()
    verifier = chaos.RecoveryVerifier(timeout_s=60)
    result = verifier.verify({"ref_ids": set(), "num_errors": 0})
    assert result.checks["tasks_terminal"], result.violations
    assert result.checks["lease_queues_drained"], result.violations


@pytest.mark.slow
def test_randomized_seed_sweep(chaos_cluster):
    """Longer randomized sweeps: the seeded probabilistic mix must end
    RecoveryVerifier-green for every seed (reproducible on failure by
    re-running with the printed seed)."""
    for seed in range(4):
        report = chaos.run_plan("mixed-seeded", seed=seed,
                                verify_timeout_s=60)
        assert report["verify"]["ok"], (
            f"seed {seed}: {report['verify']['violations']}")
        assert report["workload"]["failures"] == 0, (
            f"seed {seed}: {report['workload']}")


@pytest.mark.slow
def test_bundled_plans_all_verify_green(chaos_cluster):
    """Acceptance sweep: every bundled FaultPlan ends verifier-green."""
    cfg = get_config()
    cfg.rpc_max_retries = 12
    for name in chaos.BUILTIN_PLANS:
        if name in ("spill-disk-error",):  # exercised by its own test
            continue
        report = chaos.run_plan(name, seed=1, verify_timeout_s=60)
        assert report["verify"]["ok"], (
            f"{name}: {report['verify']['violations']}")

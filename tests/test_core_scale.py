"""Many-raylet scale harness + actor-storm chaos (ISSUE 14).

Tier-1 runs a 4-raylet / shrunk-storm variant of exactly the code path
the full-size bench drives (``cli bench core --scale``); the full
8-raylet / 100k-task / 1k-actor acceptance run is marked ``slow``.
"""

from __future__ import annotations

import time

import pytest

from ray_tpu.core.config import get_config


@pytest.fixture()
def _fresh_cluster_slot():
    """The scale harness owns init/shutdown of a multi-raylet cluster:
    tear down any shared test cluster first, and leave nothing behind."""
    import ray_tpu

    try:
        ray_tpu.shutdown()
    except Exception:
        pass
    yield
    try:
        ray_tpu.shutdown()
    except Exception:
        pass


def test_scale_harness_smoke(_fresh_cluster_slot):
    """4-raylet shrunk variant: tasks spill across raylets, the actor
    storm lands on zygote pools, every core_scale_* cell is recorded."""
    from ray_tpu._core_scale_bench import run_core_scale_bench

    out = run_core_scale_bench(raylets=4, num_tasks=600, num_actors=24)
    assert out["core_scale_raylets_cfg"] == 4
    assert out["core_scale_tasks_per_s"] > 0
    assert out["core_scale_actor_creations_per_s"] > 0
    # the storm actually exercised the pool path on this box
    assert 0.0 <= out.get("core_scale_pooled_spawn_frac", 0.0) <= 1.0


def test_actor_storm_chaos_green(_fresh_cluster_slot):
    """Reduced actor-storm chaos smoke (the tier-1 half of the 1k-actor
    acceptance run): 4 raylets, a creation storm under the bundled
    `actor-storm` plan (kill-on-Nth-lease + mid-storm preemption notice),
    RecoveryVerifier green, zygote pools drained/refilled to baseline."""
    import ray_tpu
    from ray_tpu import chaos
    from ray_tpu.cluster_utils import Cluster

    cfg = get_config()
    saved = {k: getattr(cfg, k) for k in (
        "worker_register_timeout_s", "lease_orphan_timeout_s",
        "preempt_grace_s", "zygote_pool_size", "zygote_pool_refill_batch",
        "health_check_period_ms")}
    cfg.worker_register_timeout_s = 15.0
    cfg.lease_orphan_timeout_s = 2.0
    cfg.preempt_grace_s = 2.0
    cfg.zygote_pool_size = 4
    cfg.zygote_pool_refill_batch = 4
    # Fast heartbeats: the plan's preempt_slice rule fires on the
    # targeted node's 3rd heartbeat tick — it must land INSIDE the
    # shrunk storm window, not 3 wall-seconds into a 5-second test.
    cfg.health_check_period_ms = 250
    cluster = Cluster(initialize_head=False)
    try:
        for _ in range(4):
            cluster.add_node(wait=False, num_cpus=40)
        cluster.wait_for_nodes(4)
        ray_tpu.init(address=cluster.address, num_cpus=0)

        @ray_tpu.remote(max_restarts=3)
        class Storm:
            def ping(self, i):
                return i

        @ray_tpu.remote
        def warm():
            return None

        ray_tpu.get([warm.remote() for _ in range(16)], timeout=60)
        time.sleep(1.0)
        baseline_pools = _pool_sizes(cluster)

        def workload():
            actors = [Storm.remote() for _ in range(100)]
            ok = failures = 0
            for a in actors:
                try:
                    ray_tpu.get(a.ping.remote(1), timeout=60)
                    ok += 1
                except Exception:
                    failures += 1
            for a in actors:
                try:
                    ray_tpu.kill(a)
                except Exception:
                    pass
            del actors
            return {"ok": ok, "failures": failures}

        report = chaos.run_plan("actor-storm", seed=14, workload=workload,
                                verify_timeout_s=60)
        assert report["verify"]["ok"], report["verify"]["violations"]
        # the plan actually fired: worker kills and (4 nodes exist) the
        # mid-storm preemption notice
        assert report["injections"].get("kill_worker:kill_worker", 0) >= 1
        assert report["injections"].get("preempt_slice:preempt_slice", 0) >= 1
        # storm survived the chaos: restarts absorbed the kills
        assert report["workload"]["ok"] >= 95, report["workload"]

        # Zygote pools drained back to baseline: no dedicated workers
        # left, idle pools back at their per-key targets on every
        # NON-DRAINING raylet (the preempted node is drained by design).
        deadline = time.monotonic() + 60
        while time.monotonic() < deadline:
            if _pools_at_baseline(cluster) is None:
                break
            time.sleep(0.5)
        assert _pools_at_baseline(cluster) is None, (
            _pools_at_baseline(cluster), baseline_pools,
            _pool_sizes(cluster))
    finally:
        for k, v in saved.items():
            setattr(cfg, k, v)
        try:
            ray_tpu.shutdown()
        except Exception:
            pass
        cluster.shutdown()


def _pool_sizes(cluster) -> dict:
    out = {}
    for raylet in cluster.nodes:
        idle, _starting = raylet._pool_counts("")
        out[raylet.node_id.hex()] = idle
    return out


def _pools_at_baseline(cluster) -> str | None:
    """None when every live raylet is back at baseline; else a reason."""
    cfg = get_config()
    target = max(cfg.num_prestart_workers, cfg.zygote_pool_size)
    for raylet in cluster.nodes:
        if raylet._draining or raylet._shutdown:
            continue  # preempted mid-storm by the plan: drained by design
        nid = raylet.node_id.hex()[:8]
        stuck = [(w.worker_id[:8], w.actor_id[:8])
                 for w in raylet._workers.values() if w.state == "dedicated"]
        if stuck:
            return f"node {nid}: leaked dedicated workers {stuck}"
        idle, starting = raylet._pool_counts("")
        if idle + starting < target:  # drained: never refilled
            return f"node {nid}: pool {idle}+{starting} < target {target}"
    return None


@pytest.mark.slow
def test_scale_harness_full_acceptance(_fresh_cluster_slot):
    """The 10x-PR-6 acceptance run: >= 8 raylets, 100k tasks, 1k actors,
    plus the actor-storm chaos phase — hours-class on a laptop, so it
    rides the slow marker; ``cli bench core --scale`` runs the same code
    with env-tunable sizes."""
    from ray_tpu._core_scale_bench import run_core_scale_bench

    out = run_core_scale_bench(chaos=True)
    assert out["core_scale_raylets_cfg"] >= 8
    assert out["core_scale_tasks_cfg"] >= 100_000
    assert out["core_scale_actors_cfg"] >= 1000
    assert out["core_scale_tasks_per_s"] > 0
    assert out["core_scale_actor_creations_per_s"] > 0
    assert out.get("core_scale_chaos_verify_ok") == 1.0

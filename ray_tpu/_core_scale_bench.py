"""Cluster-scale core bench: many raylets, one GCS, one host (ROADMAP 4).

The single-node suite (``ray_tpu._core_bench``) measures the owner→raylet hot
path; this one stands up a MANY-RAYLET harness (``cluster_utils.Cluster``
— raylets are real asyncio services, workers are real subprocesses) and
drives the reference's cluster-scale shape: a task storm spilling across
nodes and a 1k-actor creation storm landing on runtime-env-keyed zygote
pools, all flushing task events into the sharded GCS store concurrently.

Metrics (guarded by ``ray_tpu.bench_check``):

  * ``core_scale_tasks_per_s``            — no-op round trips across N raylets
  * ``core_scale_actor_creations_per_s``  — creation-storm throughput
  * ``core_scale_pooled_spawn_frac``      — fraction of spawns served by
                                            zygote-pool forks during the run
  * ``core_scale_{raylets,tasks,actors}_cfg`` — size echoes (inputs)
  * ``core_scale_chaos_verify_ok``        — 1.0 when the ``actor-storm``
                                            FaultPlan run ends
                                            RecoveryVerifier-green
                                            (``chaos=True`` runs only)

Defaults are the 10x-PR-6 acceptance sizes (8 raylets / 100k tasks /
1k actors); every size is env-tunable (``RAY_TPU_CORE_SCALE_*``) so a
1-core sandbox can run a shrunk variant of the same code path, and
``RAY_TPU_BENCH_SKIP_CORE_SCALE=1`` emits the ``core_scale_skipped``
marker ``bench_check`` honors instead of silently vanishing the cells.
"""

from __future__ import annotations

import os
import time


def _env_int(name: str, default: int) -> int:
    return int(os.environ.get(name, default))


def run_core_scale_bench(*, raylets: int | None = None,
                         num_tasks: int | None = None,
                         num_actors: int | None = None,
                         chaos: bool = False,
                         chaos_seed: int = 0) -> dict:
    """Run the many-raylet scale phases and return the metrics dict.

    Must be called with no cluster initialized in this process: the
    harness owns init/shutdown (the driver attaches to the harness GCS
    with a 0-CPU local raylet, so every lease spills to the scale
    raylets)."""
    import ray_tpu
    from ray_tpu.cluster_utils import Cluster

    raylets = raylets or _env_int("RAY_TPU_CORE_SCALE_RAYLETS", 8)
    num_tasks = num_tasks or _env_int("RAY_TPU_CORE_SCALE_TASKS", 100_000)
    num_actors = num_actors or _env_int("RAY_TPU_CORE_SCALE_ACTORS", 1000)

    out: dict = {
        "core_scale_raylets_cfg": raylets,
        "core_scale_tasks_cfg": num_tasks,
        "core_scale_actors_cfg": num_actors,
    }

    # Per-raylet CPU pool: the actor storm pins one CPU token per live
    # actor, plus headroom for the task pipelines.
    cpus_per_node = max(8, (num_actors + raylets - 1) // raylets + 8)
    # Zygote pool sized per raylet for its share of the storm (echoed as
    # a _cfg input, restored on exit).
    pool = _env_int("RAY_TPU_CORE_SCALE_POOL",
                    min(32, max(4, num_actors // raylets)))
    out["core_scale_pool_cfg"] = pool
    from ray_tpu.core.config import get_config

    cfg = get_config()
    saved = {k: getattr(cfg, k)
             for k in ("zygote_pool_size", "zygote_pool_refill_batch")}
    cfg.zygote_pool_size = pool
    cfg.zygote_pool_refill_batch = 8
    cluster = Cluster(initialize_head=False)
    for _ in range(raylets):
        cluster.add_node(wait=False, num_cpus=cpus_per_node)
    cluster.wait_for_nodes(raylets)
    ray_tpu.init(address=cluster.address, num_cpus=0)

    @ray_tpu.remote
    def _noop():
        return None

    @ray_tpu.remote(max_restarts=2)
    class _Counter:
        def __init__(self):
            self.n = 0

        def ping(self, i):
            self.n += 1
            return i

    try:
        # Warmup: every raylet boots its zygote + prestart pool and the
        # driver's spillback path compiles before the timed windows.
        ray_tpu.get([_noop.remote() for _ in range(raylets * 8)],
                    timeout=300)

        # --- phase 1: cross-raylet task storm ---------------------------
        t0 = time.perf_counter()
        refs = [_noop.remote() for _ in range(num_tasks)]
        ray_tpu.get(refs, timeout=3600)
        dt = time.perf_counter() - t0
        del refs
        out["core_scale_tasks_per_s"] = round(num_tasks / dt, 1)

        # --- phase 2: actor creation storm ------------------------------
        spawn_before = _spawn_totals(cluster)
        t0 = time.perf_counter()
        actors = [_Counter.remote() for _ in range(num_actors)]
        ray_tpu.get([a.ping.remote(0) for a in actors], timeout=3600)
        create_dt = time.perf_counter() - t0
        out["core_scale_actor_creations_per_s"] = round(
            num_actors / create_dt, 1)
        spawn_after = _spawn_totals(cluster)
        delta = {k: spawn_after.get(k, 0) - spawn_before.get(k, 0)
                 for k in ("cold", "pooled")}
        spawned = sum(delta.values())
        if spawned:
            out["core_scale_pooled_spawn_frac"] = round(
                delta["pooled"] / spawned, 4)
        for a in actors:
            try:
                ray_tpu.kill(a)
            except Exception:
                pass
        del actors
        time.sleep(1.0)

        # --- phase 3 (optional): actor-storm chaos plan ------------------
        if chaos:
            out.update(_chaos_phase(num_actors, _Counter, seed=chaos_seed))
    finally:
        for k, v in saved.items():
            setattr(cfg, k, v)
        try:
            ray_tpu.shutdown()
        except Exception:
            pass
        cluster.shutdown()
    return out


def _spawn_totals(cluster) -> dict:
    totals = {"cold": 0, "pooled": 0}
    for raylet in cluster.nodes:
        for mode, n in raylet._spawn_stats.items():
            totals[mode] = totals.get(mode, 0) + n
    return totals


def _chaos_phase(num_actors: int, actor_cls, seed: int = 0) -> dict:
    """Run the bundled ``actor-storm`` FaultPlan against a reduced storm
    (a tenth of the main storm, at least 20 actors) and verify recovery."""
    import ray_tpu
    from ray_tpu import chaos

    storm = max(20, num_actors // 10)

    def workload() -> dict:
        actors = [actor_cls.remote() for _ in range(storm)]
        ok = failures = 0
        for a in actors:
            try:
                ray_tpu.get(a.ping.remote(0), timeout=300)
                ok += 1
            except Exception:
                failures += 1
        for a in actors:
            try:
                ray_tpu.kill(a)
            except Exception:
                pass
        del actors
        return {"actors": storm, "ok": ok, "failures": failures}

    try:
        report = chaos.run_plan("actor-storm", seed=seed, workload=workload,
                                verify_timeout_s=180)
        return {
            "core_scale_chaos_verify_ok": 1.0 if report["verify"]["ok"] else 0.0,
            "core_scale_chaos_storm_cfg": storm,
        }
    except chaos.ChaosVerificationError:
        return {"core_scale_chaos_verify_ok": 0.0,
                "core_scale_chaos_storm_cfg": storm}


def main() -> int:
    import json
    import sys

    result = run_core_scale_bench()
    print(json.dumps(result))
    return 0 if result.get("core_scale_tasks_per_s") else 1


if __name__ == "__main__":
    raise SystemExit(main())

"""Core task-path throughput suite (ROADMAP item 3).

Single-node edition of the Ray reference's many-tasks / many-actors /
many-objects release tests: the numbers that make "millions of users"
claims checkable, because serve routers, Data pipelines, and the
chaos/diagnostics subsystems all ride the same
``submit_task → RequestWorkerLease → push → ReturnWorker`` path this
suite saturates.

Three phases, each reported as a throughput metric guarded by
``ray_tpu.bench_check``:

  * ``core_tasks_per_s``          — no-op task round trips (submit 100k,
                                    get all)
  * ``core_actor_calls_per_s``    — actor method round trips across a
                                    pool of actors
  * ``core_obj_roundtrip_per_s``  — ``put``/``get`` fan-out of small
                                    objects

plus the p50 of every ``ray_tpu_lease_stage_ms`` stage observed during
the run (``core_lease_<stage>_p50_ms``) — the evidence trail for
attacking the owner→raylet→GCS hot path (PERF.md "core task path").

Sizes are env-tunable (``RAY_TPU_CORE_BENCH_{TASKS,ACTORS,CALLS,OBJECTS}``);
the defaults finish in a couple of minutes on a laptop-class node. Run
via ``python -m ray_tpu.cli bench core``. Host rates only: nothing here
touches a device.
"""

from __future__ import annotations

import os
import time


def _env_int(name: str, default: int) -> int:
    return int(os.environ.get(name, default))


def _merge_lease_stage_p50s() -> dict:
    """p50 per lease stage, buckets merged across nodes. Best-effort:
    the histograms ride the task-event flush, so poll briefly for the
    counts to land before reading."""
    try:
        from ray_tpu.util.metrics import get_metrics, histogram_quantile
    except Exception:
        return {}
    merged: dict[str, dict] = {}
    deadline = time.perf_counter() + 8.0
    while time.perf_counter() < deadline:
        rows = [m for m in get_metrics()
                if m.get("name") == "ray_tpu_lease_stage_ms" and m.get("count")]
        if rows:
            break
        time.sleep(0.5)
    else:
        rows = []
    for m in rows:
        stage = (m.get("tags") or {}).get("stage", "")
        agg = merged.get(stage)
        if agg is None:
            merged[stage] = {"buckets": list(m.get("buckets") or []),
                             "boundaries": list(m.get("boundaries") or []),
                             "count": m.get("count", 0)}
        else:
            for i, b in enumerate(m.get("buckets") or []):
                if i < len(agg["buckets"]):
                    agg["buckets"][i] += b
            agg["count"] += m.get("count", 0)
    out = {}
    for stage, agg in merged.items():
        q = histogram_quantile(agg, 0.5)
        if q is not None:
            out[f"core_lease_{stage}_p50_ms"] = round(q, 2)
            out[f"core_lease_{stage}_count_cfg"] = agg["count"]
    return out


def run_core_bench(*, num_tasks: int | None = None, num_actors: int | None = None,
                   calls_per_actor: int | None = None,
                   num_objects: int | None = None,
                   connect: bool = True) -> dict:
    """Run the three core phases and return the metrics dict. With
    ``connect`` (default) a local cluster is started and shut down; pass
    False to run inside an already-initialized driver."""
    import ray_tpu

    num_tasks = num_tasks or _env_int("RAY_TPU_CORE_BENCH_TASKS", 100_000)
    num_actors = num_actors or _env_int("RAY_TPU_CORE_BENCH_ACTORS", 100)
    calls_per_actor = calls_per_actor or _env_int("RAY_TPU_CORE_BENCH_CALLS", 100)
    num_objects = num_objects or _env_int("RAY_TPU_CORE_BENCH_OBJECTS", 10_000)
    # Zygote pool sized for the actor phase (how an operator expecting
    # this churn would run it): the creation storm binds pre-forked
    # registered workers instead of spawning at grant time. Echoed as a
    # _cfg input; restored after the run so later bench phases in the
    # same process don't inherit a storm-sized idle pool.
    pool = _env_int("RAY_TPU_CORE_BENCH_POOL", min(num_actors, 64))

    if connect:
        # Every actor pins a dedicated 1.0-CPU lease for its lifetime, so
        # the logical pool must cover the whole actor pool plus headroom
        # for the task pipelines (CPU here is a scheduling token, not a
        # core count).
        ray_tpu.init(num_cpus=_env_int(
            "RAY_TPU_CORE_BENCH_CPUS",
            max(num_actors + 16, os.cpu_count() or 8)),
            ignore_reinit_error=True)

    @ray_tpu.remote
    def _noop():
        return None

    @ray_tpu.remote
    class _Counter:
        def __init__(self):
            self.n = 0

        def ping(self, i):
            self.n += 1
            return i

    out: dict = {
        "core_tasks_cfg": num_tasks,
        "core_actors_cfg": num_actors,
        "core_actor_calls_cfg": num_actors * calls_per_actor,
        "core_objects_cfg": num_objects,
        "core_zygote_pool_cfg": pool,
    }

    try:
        _run_phases(out, _noop, _Counter, num_tasks=num_tasks,
                    num_actors=num_actors, calls_per_actor=calls_per_actor,
                    num_objects=num_objects, pool=pool)
    finally:
        if connect:
            ray_tpu.shutdown()
    return out


def _settle_workers(timeout_s: float = 20.0) -> None:
    """Wait until the local raylet's worker table stops churning (storm
    workers reaped, idle pool shrunk back toward target) so the next
    timed phase doesn't measure against a node busy burying processes.
    Best-effort: falls back to a fixed sleep off-process."""
    try:
        from ray_tpu.core import api as core_api

        raylet = core_api._node.raylet
    except Exception:
        time.sleep(2.0)
        return
    deadline = time.perf_counter() + timeout_s
    stable_since, last = None, None
    while time.perf_counter() < deadline:
        count = sum(1 for w in raylet._workers.values() if w.state != "dead")
        if count != last:
            last, stable_since = count, time.perf_counter()
        elif time.perf_counter() - stable_since > 1.5:
            return
        time.sleep(0.2)


def _prewarm_pool(pool: int, timeout_s: float = 30.0) -> None:
    """Size the zygote pool for the coming storm and wait (bounded) for
    the refill loop to fill it — the storm then measures pool binding,
    not fork backlog. In-process raylet only; silently best-effort."""
    try:
        from ray_tpu.core import api as core_api

        raylet = core_api._node.raylet
    except Exception:
        time.sleep(2.0)
        return
    deadline = time.perf_counter() + timeout_s
    while time.perf_counter() < deadline:
        idle = sum(1 for wid in raylet._idle
                   if (w := raylet._workers.get(wid)) and w.env_hash == "")
        if idle >= pool:
            return
        time.sleep(0.1)


def _run_phases(out: dict, _noop, _Counter, *, num_tasks: int,
                num_actors: int, calls_per_actor: int,
                num_objects: int, pool: int) -> None:
    import ray_tpu
    from ray_tpu.core.config import get_config

    # Warmup: boot the worker pool / zygote and compile the submit path
    # so the timed window measures the steady state, not cold start.
    ray_tpu.get([_noop.remote() for _ in range(64)])

    # --- phase 1: no-op task throughput ---------------------------------
    t0 = time.perf_counter()
    refs = [_noop.remote() for _ in range(num_tasks)]
    submit_dt = time.perf_counter() - t0
    ray_tpu.get(refs)
    dt = time.perf_counter() - t0
    del refs
    out["core_tasks_per_s"] = round(num_tasks / dt, 1)
    out["core_task_submit_per_s"] = round(num_tasks / submit_dt, 1)

    # --- phase 2: actor creation + call throughput -----------------------
    # The creation storm runs against a storm-sized zygote pool (scoped
    # to THIS phase: the pool knobs are restored right after the timed
    # window, and the idle-shrink reaper returns the node to baseline
    # before the call/object phases measure).
    cfg = get_config()
    saved_pool = {k: getattr(cfg, k)
                  for k in ("zygote_pool_size", "zygote_pool_refill_batch")}
    cfg.zygote_pool_size = pool
    cfg.zygote_pool_refill_batch = 8
    _prewarm_pool(pool)
    # The pool now covers the whole storm: drop the refill rate so
    # replacement forks don't compete with the storm for CPU inside the
    # timed window (they resume at full rate once the knobs restore).
    cfg.zygote_pool_refill_batch = 1
    t0 = time.perf_counter()
    actors = [_Counter.remote() for _ in range(num_actors)]
    # An actor is "created" once its first call returns.
    ray_tpu.get([a.ping.remote(0) for a in actors])
    create_dt = time.perf_counter() - t0
    # Canonical guarded name (round 14, the zygote-pool gate); the
    # original spelling stays for BENCH continuity across rounds.
    out["core_actor_creations_per_s"] = round(num_actors / create_dt, 1)
    out["core_actor_creates_per_s"] = out["core_actor_creations_per_s"]
    for k, v in saved_pool.items():
        setattr(cfg, k, v)
    # Let the idle-shrink reaper drain the storm pool back to baseline
    # so the call phase isn't measured against a node full of residents.
    _settle_workers()
    t0 = time.perf_counter()
    refs = [a.ping.remote(i)
            for i in range(calls_per_actor) for a in actors]
    ray_tpu.get(refs)
    call_dt = time.perf_counter() - t0
    out["core_actor_calls_per_s"] = round(
        num_actors * calls_per_actor / call_dt, 1)
    for a in actors:
        try:
            ray_tpu.kill(a)
        except Exception:
            pass
    del actors, refs
    # Let the killed actor workers actually exit before timing phase 3 —
    # 100 dying processes reaping mid-measurement is noise, not signal.
    time.sleep(2.0)
    _settle_workers()

    # --- phase 3: object put/get round trips ----------------------------
    payload = os.urandom(256)  # small: the inline (in-process store) path
    t0 = time.perf_counter()
    orefs = [ray_tpu.put((i, payload)) for i in range(num_objects)]
    ray_tpu.get(orefs)
    dt = time.perf_counter() - t0
    del orefs
    out["core_obj_roundtrip_per_s"] = round(num_objects / dt, 1)

    out.update(_merge_lease_stage_p50s())


def main() -> int:
    import json
    import sys

    result = run_core_bench()
    print(json.dumps(result))
    return 0 if result.get("core_tasks_per_s") else 1


if __name__ == "__main__":
    raise SystemExit(main())

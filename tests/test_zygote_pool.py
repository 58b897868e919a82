"""Zygote-pool worker spawn (ISSUE 14 tentpole a).

Env-hash keying is the safety net: a pooled worker must NEVER be handed
to a lease with a different ``_env_hash`` (a silently wrong interpreter/
env is worse than a slow spawn), interpreter-level envs must always pay
the cold spawn (the PR 1 enforcement path), and a pool key falling off
the LRU must take its zygote AND its idle workers with it.
"""

from __future__ import annotations

import sys
import time

import pytest

import ray_tpu
from ray_tpu.core.config import get_config
from ray_tpu.core.raylet import Raylet


@pytest.fixture()
def _pool_knobs():
    cfg = get_config()
    keys = ("zygote_pool_size", "zygote_pool_refill_batch",
            "zygote_pool_max_keys", "enable_worker_zygote",
            "idle_worker_killing_time_threshold_ms", "num_prestart_workers")
    saved = {k: getattr(cfg, k) for k in keys}
    yield cfg
    for k, v in saved.items():
        setattr(cfg, k, v)


def _raylet() -> Raylet:
    from ray_tpu.core import api as core_api

    return core_api._node.raylet


# ------------------------------------------------------------ eligibility


def test_interp_envs_never_zygote_eligible():
    """conda / py_executable / container / image_uri can never fork from
    a zygote of THIS interpreter — those must cold-spawn."""
    assert Raylet._zygote_eligible(None)
    assert Raylet._zygote_eligible({})
    assert Raylet._zygote_eligible({"env_vars": {"A": "1"}})
    assert Raylet._zygote_eligible({"working_dir": "/tmp"})
    assert Raylet._zygote_eligible({"pip": ["x"]})
    assert not Raylet._zygote_eligible({"py_executable": sys.executable})
    assert not Raylet._zygote_eligible({"conda": "base"})
    assert not Raylet._zygote_eligible({"container": {"image": "x"}})
    assert not Raylet._zygote_eligible({"image_uri": "img:tag"})


def test_interp_env_spawn_is_cold_and_untracked(ray_cluster, _pool_knobs):
    """A py_executable spawn takes the direct path: spawn_mode 'cold',
    no zygote booted for its env key, no pool key tracked."""
    raylet = _raylet()
    renv = {"py_executable": sys.executable}
    env_hash = raylet._env_hash(renv)
    before_keys = set(raylet._zygotes)
    handle = raylet._start_worker(renv)
    try:
        assert handle.spawn_mode == "cold"
        assert env_hash not in raylet._zygotes
        assert env_hash not in raylet._pool_keys
        assert set(raylet._zygotes) == before_keys
    finally:
        handle.proc.terminate()
        raylet._workers.pop(handle.worker_id, None)


# --------------------------------------------------------- env-hash match


def test_pooled_worker_never_handed_to_mismatched_lease(ray_cluster,
                                                        _pool_knobs):
    """Raylet-level contract: an idle pooled worker of env A is invisible
    to a lease wanting env B (and to the default env), in _get_idle_worker
    AND in the multiplexed extra-grant scan."""
    raylet = _raylet()
    env_a = {"env_vars": {"POOL_TEST_ENV": "a"}}
    env_b = {"env_vars": {"POOL_TEST_ENV": "b"}}
    hash_a, hash_b = raylet._env_hash(env_a), raylet._env_hash(env_b)
    assert hash_a != hash_b != ""

    @ray_tpu.remote(runtime_env=env_a)
    def probe_a():
        import os

        return os.environ.get("POOL_TEST_ENV")

    @ray_tpu.remote(runtime_env=env_b)
    def probe_b():
        import os

        return os.environ.get("POOL_TEST_ENV")

    # Workers of each env exist and are keyed correctly end to end: the
    # env var actually differs inside the processes.
    assert ray_tpu.get([probe_a.remote(), probe_b.remote()],
                       timeout=60) == ["a", "b"]
    by_hash = {}
    for w in raylet._workers.values():
        if w.state in ("idle", "leased"):
            by_hash.setdefault(w.env_hash, 0)
            by_hash[w.env_hash] += 1
    assert by_hash.get(hash_a, 0) >= 1
    assert by_hash.get(hash_b, 0) >= 1

    async def _mismatch_scan():
        # env-B lease must not pop an idle env-A worker even when only
        # env-A workers are idle: give it a near-zero timeout and check
        # the worker it returns (if any) is env-B keyed.
        w = await raylet._get_idle_worker(0.05, env_b)
        return w

    from ray_tpu.core import api as core_api

    w = core_api._node.services_loop.run_sync(_mismatch_scan(), timeout=30)
    if w is not None:
        assert w.env_hash == hash_b
        w.state = "idle"
        raylet._idle.append(w.worker_id)


# ------------------------------------------------------------ pool/evict


def test_pool_eviction_on_env_mismatch(ray_cluster, _pool_knobs):
    """Over zygote_pool_max_keys the LRU env key is evicted: pool key
    gone, its zygote killed, its idle workers reaped."""
    cfg = _pool_knobs
    cfg.zygote_pool_max_keys = 2
    raylet = _raylet()
    envs = [{"env_vars": {"POOL_EVICT_TEST": str(i)}} for i in range(3)]
    hashes = [raylet._env_hash(e) for e in envs]

    @ray_tpu.remote
    def mk(i):
        return i

    # Touch three env keys in order via the lease path.
    for i, env in enumerate(envs):
        ray_tpu.get(mk.options(runtime_env=env).remote(i), timeout=60)
    deadline = time.monotonic() + 10
    while time.monotonic() < deadline and hashes[0] in raylet._pool_keys:
        time.sleep(0.1)
    # Key 0 (least recently leased) was evicted; 1 and 2 survive.
    assert hashes[0] not in raylet._pool_keys
    assert hashes[1] in raylet._pool_keys
    assert hashes[2] in raylet._pool_keys
    assert hashes[0] not in raylet._zygotes
    # ... and no idle worker of the evicted env remains.
    deadline = time.monotonic() + 10
    while time.monotonic() < deadline:
        stale = [wid for wid in raylet._idle
                 if (w := raylet._workers.get(wid))
                 and w.env_hash == hashes[0]]
        if not stale:
            break
        time.sleep(0.1)
    assert not stale


def test_idle_pool_shrinks_to_target(ray_cluster, _pool_knobs):
    """Idle worker killing: a burst that balloons the default pool is
    reaped back toward the prestart/pool target after the idle
    threshold."""
    cfg = _pool_knobs
    cfg.idle_worker_killing_time_threshold_ms = 300
    raylet = _raylet()

    @ray_tpu.remote
    def burst(i):
        time.sleep(0.05)
        return i

    ray_tpu.get([burst.remote(i) for i in range(12)], timeout=60)
    target = max(cfg.num_prestart_workers, cfg.zygote_pool_size)
    deadline = time.monotonic() + 15
    while time.monotonic() < deadline:
        idle_default = sum(1 for wid in raylet._idle
                           if (w := raylet._workers.get(wid))
                           and w.env_hash == "")
        if idle_default <= target:
            break
        time.sleep(0.1)
    assert idle_default <= target, (idle_default, target)


# ----------------------------------------------------------- spawn modes


def test_spawn_histogram_records_pooled_and_cold(ray_cluster, _pool_knobs):
    """The ray_tpu_worker_spawn_ms histogram carries both modes, and the
    raylet's spawn counters saw pooled forks (the zygote is live in this
    suite)."""
    raylet = _raylet()

    @ray_tpu.remote
    def touch():
        return 1

    ray_tpu.get([touch.remote() for _ in range(8)], timeout=60)
    deadline = time.monotonic() + 20
    while time.monotonic() < deadline and not raylet._spawn_stats.get("pooled"):
        ray_tpu.get(touch.remote(), timeout=60)
        time.sleep(0.2)
    assert raylet._spawn_stats.get("pooled", 0) >= 1
    from ray_tpu.core.raylet import _spawn_hist

    snap = _spawn_hist().snapshot()
    modes = {row["tags"].get("mode") for row in snap}
    assert "pooled" in modes
    pooled = next(r for r in snap if r["tags"].get("mode") == "pooled")
    assert pooled["count"] >= 1

"""Elastic resilience (round 9): preemption-aware async checkpointing and
chaos-verified recovery.

The scenario production TPU users actually fear, made a measured event:
a spot slice preempted mid-train must resume from the latest
async-committed checkpoint (lag bounded by ``every_n_steps``, loss curve
continuous), and mid-serve traffic must re-route with zero failed client
requests — both through the REAL notice→drain→grace-kill path and
verified green by the chaos RecoveryVerifier.
"""

import json
import os
import threading
import time

import numpy as np
import pytest

import ray_tpu
from ray_tpu import chaos
from ray_tpu.core.config import get_config
from ray_tpu.resilience import (
    AsyncCheckpointManager,
    latest_committed,
    latest_registered,
    list_committed,
    load_checkpoint,
)
from ray_tpu.train.checkpoint import load_pytree, save_pytree
from ray_tpu.util import state

pytestmark = pytest.mark.chaos


def _wait_for(predicate, timeout=30.0, interval=0.1):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        result = predicate()
        if result:
            return result
        time.sleep(interval)
    return predicate()


@pytest.fixture(autouse=True)
def _clean_resilience():
    """No chaos engine, no virtual clock, and touched config restored."""
    cfg = get_config()
    saved = {k: getattr(cfg, k) for k in (
        "preempt_grace_s", "health_check_period_ms",
        "worker_register_timeout_s")}
    yield
    from ray_tpu.core.rpc import set_chaos

    set_chaos(None)
    chaos.set_clock(None)
    for key, value in saved.items():
        setattr(cfg, key, value)


# ------------------------------------------------------- async ckpt unit layer
def test_async_checkpoint_commit_and_keep_k(tmp_path):
    """Commits are atomic dirs with markers; keep-K GC retains the newest
    K committed versions; load_checkpoint returns tree + meta."""
    root = str(tmp_path / "ck")
    mgr = AsyncCheckpointManager(root, keep_k=2, register_with_gcs=False)
    try:
        for step in range(5):
            mgr.save(step, {"step": step, "w": np.full(16, float(step))},
                     metrics={"loss": 1.0 / (1 + step)})
            assert mgr.wait(20), "writer never drained"
        committed = list_committed(root)
        assert [s for s, _ in committed] == [3, 4]  # keep_k=2, newest win
        tree, meta = load_checkpoint(committed[-1][1])
        assert tree["step"] == 4 and float(tree["w"][0]) == 4.0
        assert meta["step"] == 4 and meta["metrics"]["loss"] == pytest.approx(0.2)
        # no half-commit debris
        assert not [d for d in os.listdir(root) if d.startswith(".tmp-")]
    finally:
        mgr.close()


def test_async_checkpoint_save_never_blocks(tmp_path):
    """The acceptance bound: with a writer that takes 300 ms per commit,
    save() must return in snapshot time (latest-wins coalescing absorbs
    the backlog) — async save adds no per-step blocking."""
    from ray_tpu.train.checkpoint import save_pytree as _real_save

    def slow_write(tree, path):
        time.sleep(0.3)
        _real_save(tree, path)

    mgr = AsyncCheckpointManager(str(tmp_path / "ck"), keep_k=None,
                                 register_with_gcs=False, write_fn=slow_write)
    try:
        blocks = [mgr.save(step, {"step": step, "w": np.zeros(4096)})
                  for step in range(4)]
        # each save blocked only for the host snapshot, not the 300 ms write
        assert max(blocks) < 150.0, blocks
        assert mgr.wait(20)
        assert mgr.last_committed["step"] == 3  # freshest state won
        assert mgr.metrics["dropped"] >= 1      # backlog was coalesced
        assert mgr.metrics["commits"] + mgr.metrics["dropped"] == 4
    finally:
        mgr.close()


def test_async_checkpoint_crash_mid_commit_invisible(tmp_path):
    """A writer death mid-commit (partial payload, no marker) leaves the
    PREVIOUS committed version visible — never a corrupt one."""
    root = str(tmp_path / "ck")

    def write(tree, path):
        from ray_tpu.train.checkpoint import save_pytree as real

        if tree["step"] == 1:
            with open(os.path.join(path, "state.pkl"), "wb") as f:
                f.write(b"\x80\x04partial")  # half-written, then death
            raise RuntimeError("simulated mid-commit kill")
        real(tree, path)

    mgr = AsyncCheckpointManager(root, register_with_gcs=False, write_fn=write)
    try:
        mgr.save(0, {"step": 0})
        assert mgr.wait(20)
        mgr.save(1, {"step": 1})
        assert mgr.wait(20)
        assert mgr.metrics["commit_errors"] == 1
        latest = latest_committed(root)
        assert latest["step"] == 0  # the dead commit is invisible
        tree, _ = load_checkpoint(latest["path"])
        assert tree["step"] == 0
        assert not [d for d in os.listdir(root) if d.startswith(".tmp-")]
    finally:
        mgr.close()


def test_load_checkpoint_refuses_uncommitted(tmp_path):
    d = tmp_path / "ckpt_00000007"
    d.mkdir()
    save_pytree({"step": 7}, str(d))  # payload present, marker absent
    with pytest.raises(FileNotFoundError, match="COMMITTED"):
        load_checkpoint(str(d))
    assert latest_committed(str(tmp_path)) is None


def test_save_pytree_atomic_kill_mid_write(tmp_path, monkeypatch):
    """Satellite regression: a kill mid-``save_pytree`` must leave the
    previous version (or none) — before the tmp+fsync+rename fix a
    truncated .pkl unpickled a prefix without complaint."""
    import pickle
    import sys

    # Force the pickle fallback (the path the fix hardens) even where
    # orbax — which brings its own tmp+rename commit — is installed.
    monkeypatch.setitem(sys.modules, "orbax.checkpoint", None)

    path = str(tmp_path / "ck")
    save_pytree({"step": 1, "w": np.arange(8)}, path)

    def dying_dump(obj, f, *a, **k):
        f.write(b"\x80\x04half-a-frame")  # partial bytes, then the kill
        raise KeyboardInterrupt

    monkeypatch.setattr(pickle, "dump", dying_dump)
    with pytest.raises(KeyboardInterrupt):
        save_pytree({"step": 2, "w": np.arange(8)}, path)
    # previous version intact (load uses pickle.load, unaffected)
    tree = load_pytree(path)
    assert tree["step"] == 1
    # no stray tmp files to mistake for checkpoints
    assert [f for f in os.listdir(path) if not f.startswith("state.pkl.tmp")] \
        == ["state.pkl"]
    # a fresh dir whose FIRST save dies yields nothing loadable-looking
    path2 = str(tmp_path / "ck2")
    with pytest.raises(KeyboardInterrupt):
        save_pytree({"step": 9}, path2)
    with pytest.raises(FileNotFoundError):
        load_pytree(path2)


# ------------------------------------------------------------ GCS registration
def test_checkpoint_registered_with_gcs(ray_cluster, tmp_path):
    """Every commit registers with the GCS; latest_registered resolves the
    newest version from the control plane (no worker-node state)."""
    import uuid

    run = f"regtest-{uuid.uuid4().hex[:6]}"
    mgr = AsyncCheckpointManager(str(tmp_path / "reg"), run_name=run, keep_k=2)
    try:
        mgr.save(3, {"step": 3})
        assert mgr.wait(20)
        entry = _wait_for(lambda: latest_registered(run), timeout=10)
        assert entry and entry["step"] == 3
        assert os.path.exists(os.path.join(entry["path"], "COMMITTED"))
        mgr.save(5, {"step": 5})
        assert mgr.wait(20)
        entry = _wait_for(
            lambda: (latest_registered(run) or {}).get("step") == 5
            and latest_registered(run), timeout=10)
        assert entry["step"] == 5
    finally:
        mgr.close()


# --------------------------------------------------------- preemption plumbing
class _CallCountClock:
    def __init__(self):
        self.t = 0.0

    def __call__(self) -> float:
        self.t += 1.0
        return self.t


def test_preemption_notice_drains_raylet_and_elastic_sees_it(tmp_path):
    """The notice plumbing end to end on a live 2-node cluster: the
    draining raylet refuses leases, the GCS flags the node + publishes
    ``node_preempted``, available_resources drops the capacity, and the
    elastic policy downsizes only after its two-check debounce."""
    from ray_tpu.cluster_utils import Cluster
    from ray_tpu.train import ElasticScalingPolicy, ScalingConfig

    if ray_tpu.is_initialized():
        ray_tpu.shutdown()
    c = Cluster(initialize_head=True, head_node_args={"num_cpus": 2},
                _system_config={"health_check_period_ms": 200})
    n2 = c.add_node(num_cpus=2)
    ray_tpu.init(address=c.address, num_cpus=0)
    try:
        scaling = ScalingConfig(num_workers=4, min_workers=1,
                                resources_per_worker={"CPU": 1})
        before = ElasticScalingPolicy(scaling, clock=_CallCountClock())
        assert before.group_size() == 4  # both nodes count

        # long grace: the node stays ALIVE+draining for the whole test
        c._loop.run_sync(n2.handle_PreemptionNotice(
            {"reason": "spot reclaim", "grace_s": 60.0}))
        assert _wait_for(
            lambda: any(n.get("draining") for n in state.list_nodes()),
            timeout=15), "draining flag never reached the node table"
        assert _wait_for(
            lambda: state.list_errors(error_type="node_preempted", limit=10),
            timeout=15), "node_preempted event never published"
        # capacity view: the draining node's CPUs are gone
        assert _wait_for(
            lambda: ray_tpu.available_resources().get("CPU", 0) <= 2.0,
            timeout=10)
        # draining raylet refuses a direct lease, loudly
        reply = c._loop.run_sync(n2.handle_RequestWorkerLease(
            {"spec": {"resources": {"CPU": 1.0}}, "grant_only_local": True}))
        assert not reply.get("granted") and not reply.get("spillback")
        assert "draining" in reply.get("reason", "")
        # elastic debounce: the shrunken target must hold two checks
        after = ElasticScalingPolicy(scaling, check_interval_s=1.0,
                                     clock=_CallCountClock())
        assert after.group_size(current=0) == 2
        assert after.monitor(0) is None     # first sighting: pending
        assert after.monitor(0) == 2        # held: resize decision
    finally:
        ray_tpu.shutdown()
        c.shutdown()


# ---------------------------------------------------------- acceptance: train
def test_preempt_slice_mid_train_resumes_from_async_ckpt(tmp_path):
    """THE acceptance scenario: a `preempt_slice` FaultPlan kills the
    training slice mid-run; the controller rebuilds on a replacement node
    and resumes from the latest GCS-registered async checkpoint with
    ``recovery_ckpt_lag_steps <= every_n_steps``, a continuous loss
    curve, and RecoveryVerifier green."""
    from ray_tpu.chaos.verifier import RecoveryVerifier
    from ray_tpu.cluster_utils import Cluster
    from ray_tpu.train import (CheckpointConfig, DataParallelTrainer,
                               FailureConfig, RunConfig, ScalingConfig)

    if ray_tpu.is_initialized():
        ray_tpu.shutdown()
    c = Cluster(initialize_head=True, head_node_args={"num_cpus": 4},
                _system_config={"health_check_period_ms": 200,
                                "preempt_grace_s": 0.4})
    spot = c.add_node(num_cpus=2, resources={"spot_slice": 1.0})
    ray_tpu.init(address=c.address, num_cpus=0)
    every_n = 2
    run_name = "resil_train"
    try:
        verifier = RecoveryVerifier(timeout_s=60)
        baseline = verifier.snapshot_baseline()

        def train_fn(config):  # nested: cloudpickled by value to workers
            import time as _t

            import numpy as _np

            from ray_tpu import train as tr
            from ray_tpu.resilience import load_checkpoint as _load

            start = 0
            ck = tr.get_checkpoint()
            if ck is not None:
                tree, _meta = _load(ck.path)
                start = int(tree["step"]) + 1
            for step in range(start, config["steps"]):
                # deterministic loss: continuity is checkable post-resume
                tr.report({"step": step, "loss": 1.0 / (1.0 + step),
                           "resumed_from": start},
                          state={"step": step,
                                 "w": _np.full(256, float(step),
                                               dtype=_np.float32)})
                _t.sleep(config.get("sleep_s", 0.1))
                if start == 0 and step == config["hold_at"]:
                    # A first attempt never finishes on its own: on a
                    # loaded host the notice can take longer to land
                    # than 30 steps take to run, and the preemption has
                    # to land MID-train whatever the host's pace.
                    _t.sleep(config["hold_s"])

        trainer = DataParallelTrainer(
            train_fn,
            train_loop_config={"steps": 30, "sleep_s": 0.1,
                               "hold_at": 28, "hold_s": 120.0},
            scaling_config=ScalingConfig(
                num_workers=1,
                resources_per_worker={"CPU": 1.0, "spot_slice": 1.0}),
            run_config=RunConfig(
                name=run_name, storage_path=str(tmp_path),
                checkpoint_config=CheckpointConfig(
                    async_save=True, every_n_steps=every_n, num_to_keep=3),
                failure_config=FailureConfig(max_failures=3)),
        )
        box = {}
        t = threading.Thread(target=lambda: box.update(result=trainer.fit()))
        t.start()
        # wait until training is underway AND committed a checkpoint, so
        # the preemption provably lands MID-train
        assert _wait_for(lambda: latest_registered(run_name), timeout=60), \
            "no async checkpoint was ever registered"
        engine = chaos.install({
            "name": "test-preempt-train",
            "faults": [{"kind": "preempt_slice", "nth": 3,
                        "max_injections": 1,
                        "node": spot.node_id.hex()[:16]}],
        }, seed=0)
        notice = _wait_for(
            lambda: state.list_errors(error_type="node_preempted", limit=10),
            timeout=60)
        assert notice, "the injected notice never drained the node"
        notice_clock = float((notice[0].get("extra") or {})
                             .get("notice_clock") or 0.0)
        # the replacement slice (in production: the autoscaler's
        # preempt_replaced launch; see test_autoscaler_v2)
        c.add_node(num_cpus=2, resources={"spot_slice": 1.0})
        t.join(timeout=60)
        assert not t.is_alive(), "fit() did not finish after the preemption"
        result = box["result"]
        assert result.error is None, result.error
        assert engine.injections_total.get(("preempt_slice", "preempt_slice"))

        steps = [m["step"] for m in result.metrics_history]
        assert steps[-1] == 29, steps[-5:]
        # the run restarted exactly once, resuming from a committed step:
        # the overlap (replayed steps) is the checkpoint lag. A restart is
        # where the ATTEMPT changes, not where the step falls back: killed
        # right after a commit, the resumed attempt replays nothing (lag 0).
        history = result.metrics_history
        restarts = [(a["step"], b["step"])
                    for a, b in zip(history, history[1:])
                    if b["resumed_from"] != a["resumed_from"]]
        assert len(restarts) == 1, restarts
        prev, cur = restarts[0]
        lag = prev - cur + 1
        assert 0 <= lag <= every_n, (prev, cur, lag)
        assert result.metrics["resumed_from"] == cur > 0
        # loss-curve continuity: every point sits on the one true curve
        for m in result.metrics_history:
            assert m["loss"] == pytest.approx(1.0 / (1.0 + m["step"]))
        # recovery stamped: resume bounded after the notice
        resumed = [e for e in result.recovery_events
                   if e.get("resumed_clock") is not None]
        assert resumed and resumed[0]["resume_path"], result.recovery_events
        resume_s = resumed[0]["resumed_clock"] - notice_clock
        assert 0.0 <= resume_s < 120.0, resume_s
        chaos.uninstall()
        verify = verifier.verify(baseline)
        assert verify.ok, verify.violations
    finally:
        try:
            chaos.uninstall()
        except Exception:
            pass
        ray_tpu.shutdown()
        c.shutdown()


# ---------------------------------------------------------- acceptance: serve
def test_preempt_mid_serve_proactive_reroute(tmp_path):
    """Preempt a node hosting a serve replica: the controller evicts it
    from the NOTICE (proactively — the replica is still alive), the
    router re-routes, and a client hammering the deployment sees ZERO
    failed requests; ``reroute_s`` is chaos-clock bounded."""
    from ray_tpu import serve
    from ray_tpu.cluster_utils import Cluster

    if ray_tpu.is_initialized():
        ray_tpu.shutdown()
    c = Cluster(initialize_head=True,
                head_node_args={"num_cpus": 3,
                                "resources": {"replica_slot": 1.0}},
                _system_config={"health_check_period_ms": 200,
                                "preempt_grace_s": 6.0})
    spot = c.add_node(num_cpus=2, resources={"replica_slot": 1.0})
    ray_tpu.init(address=c.address, num_cpus=0)
    try:
        @serve.deployment(num_replicas=2, ray_actor_options={
            "num_cpus": 0.1, "resources": {"replica_slot": 1.0}})
        class Echo:
            def hello(self, x):
                return f"hello {x}"

        handle = serve.run(Echo.bind(), name="resilapp", route_prefix=None,
                           _blocking=False)
        assert _wait_for(
            lambda: (serve.status().get("resilapp", {}).get("Echo", {})
                     .get("running_replicas") == 2),
            timeout=60), serve.status()
        # preempt a replica-hosting node that is NOT the controller's
        ctrl_node = next((a.get("node_id") for a in state.list_actors()
                          if a.get("name") == "SERVE_CONTROLLER"), "")
        victim = c.head_node if spot.node_id.hex() == ctrl_node else spot
        c._loop.run_sync(victim.handle_PreemptionNotice(
            {"reason": "spot reclaim", "grace_s": 6.0}))
        # client traffic across the eviction: zero failures allowed (the
        # replica-death retry may fire at most once per request, but the
        # PROACTIVE eviction should make even that unnecessary)
        failures = []
        for i in range(30):
            try:
                assert handle.hello.remote(i).result(timeout=30) == f"hello {i}"
            except Exception as e:  # pragma: no cover - the failure detail
                failures.append((i, repr(e)))
            time.sleep(0.05)
        assert not failures, failures
        evictions = _wait_for(
            lambda: (serve.status().get("resilapp", {}).get("Echo", {})
                     .get("preemption_evictions")),
            timeout=30)
        assert evictions, "no proactive eviction was recorded"
        ev = evictions[0]
        assert ev["node_id"] == victim.node_id.hex()
        # eviction happened off the NOTICE, inside the grace window —
        # i.e. before the node even died
        assert 0.0 <= ev["reroute_s"] < 6.0, ev
        # the corpse is out of the routing table; the survivor serves
        status = serve.status()["resilapp"]["Echo"]
        assert status["running_replicas"] >= 1
    finally:
        try:
            serve.delete("resilapp")
        except Exception:
            pass
        ray_tpu.shutdown()
        c.shutdown()


# ------------------------------------------------------------- cli chaos smoke
def test_cli_chaos_run_preempt_slice_smoke(tmp_path, capsys):
    """Tier-1 smoke (satellite): `cli chaos run` with a preempt_slice
    plan injects the notice deterministically, the workload survives on
    the remaining nodes, and recovery verifies green."""
    from ray_tpu.cli import main
    from ray_tpu.cluster_utils import Cluster

    # dry-run determinism of the bundled plan needs no cluster
    assert main(["chaos", "run", "slice-preempt", "--seed", "1",
                 "--dry-run"]) == 0
    first = capsys.readouterr().out
    assert main(["chaos", "run", "slice-preempt", "--seed", "1",
                 "--dry-run"]) == 0
    assert capsys.readouterr().out == first and "preempt_slice" in first

    if ray_tpu.is_initialized():
        ray_tpu.shutdown()
    c = Cluster(initialize_head=True, head_node_args={"num_cpus": 4},
                _system_config={"health_check_period_ms": 100,
                                "preempt_grace_s": 0.3})
    n2 = c.add_node(num_cpus=2)
    ray_tpu.init(address=c.address, num_cpus=0)
    try:
        plan_path = tmp_path / "preempt.yaml"
        plan_path.write_text(
            "name: preempt-smoke\n"
            "description: tier-1 preempt_slice smoke\n"
            "faults:\n"
            "  - kind: preempt_slice\n"
            "    nth: 1\n"
            "    max_injections: 1\n"
            f"    node: \"{n2.node_id.hex()[:16]}\"\n")
        rc = main(["chaos", "run", str(plan_path), "--seed", "0",
                   "--verify-timeout", "90"])
        out = capsys.readouterr().out
        assert rc == 0, out
        report = json.loads(out)
        assert report["workload"]["failures"] == 0, report["workload"]
        assert any(k.startswith("preempt_slice")
                   for k in report["injections"]), report["injections"]
        assert report["verify"]["ok"], report["verify"]["violations"]
        # the preempted node really died through the full path
        assert _wait_for(
            lambda: any(n["node_id"] == n2.node_id.hex()
                        and n["state"] == "DEAD"
                        for n in state.list_nodes()), timeout=30)
    finally:
        try:
            chaos.uninstall()
        except Exception:
            pass
        ray_tpu.shutdown()
        c.shutdown()


# --------------------------------------------------------------------------
# Round 11: GCE metadata-server preemption watcher (ROADMAP item 10a)


class _FakeMetadataServer:
    """Minimal GCE instance-metadata stand-in: serves the `preempted`
    key, flipping FALSE -> TRUE after `flips_after` requests, and
    records whether clients sent the required Metadata-Flavor header."""

    def __init__(self, flips_after: int):
        import http.server

        server = self

        class Handler(http.server.BaseHTTPRequestHandler):
            def do_GET(self):  # noqa: N802
                server.requests += 1
                server.flavors.append(
                    self.headers.get("Metadata-Flavor", ""))
                body = (b"TRUE" if server.requests > flips_after
                        else b"FALSE")
                self.send_response(200)
                self.send_header("Content-Length", str(len(body)))
                self.end_headers()
                self.wfile.write(body)

            def log_message(self, *a):
                pass

        self.requests = 0
        self.flavors: list[str] = []
        self._httpd = http.server.HTTPServer(("127.0.0.1", 0), Handler)
        self.url = (f"http://127.0.0.1:{self._httpd.server_address[1]}"
                    "/computeMetadata/v1/instance/preempted")
        self._thread = threading.Thread(
            target=self._httpd.serve_forever, daemon=True)
        self._thread.start()

    def close(self):
        self._httpd.shutdown()
        self._httpd.server_close()


def test_metadata_watcher_fires_once_on_preempted():
    """The watcher polls the metadata `preempted` key with the
    Metadata-Flavor header, ignores FALSE reads, fires the callback
    EXACTLY once when it flips TRUE, then stops on its own."""
    from ray_tpu.resilience import GceMetadataPreemptionWatcher

    server = _FakeMetadataServer(flips_after=2)
    fired: list[str] = []
    try:
        watcher = GceMetadataPreemptionWatcher(
            fired.append, url=server.url, poll_s=0.05).start()
        assert _wait_for(lambda: watcher.fired, timeout=10)
        watcher._thread.join(timeout=5)          # one-shot: thread exits
        assert not watcher._thread.is_alive()
        assert fired == ["gce metadata: instance preempted"]
        assert watcher.polls >= 3                # saw FALSE before TRUE
        assert all(f == "Google" for f in server.flavors)
    finally:
        server.close()


def test_metadata_watcher_errors_never_fire():
    """An unreachable metadata server must never drain a healthy node:
    errors count, the callback stays silent, stop() is clean."""
    from ray_tpu.resilience import GceMetadataPreemptionWatcher

    fired: list[str] = []
    watcher = GceMetadataPreemptionWatcher(
        fired.append, url="http://127.0.0.1:9/computeMetadata",
        poll_s=0.05, timeout_s=0.2).start()
    assert _wait_for(lambda: watcher.errors >= 2, timeout=10)
    watcher.stop()
    assert not fired and not watcher.fired


def test_metadata_watcher_feeds_raylet_drain_path():
    """Wired end-to-end: a raylet started with preempt_metadata_watch
    polls the (fake) metadata endpoint and enters the SAME draining
    path a PreemptionNotice RPC triggers — node flagged draining in the
    GCS, node_preempted published, node DEAD after the grace window."""
    server = _FakeMetadataServer(flips_after=1)
    cfg = get_config()
    saved = (cfg.preempt_metadata_watch, cfg.preempt_metadata_url,
             cfg.preempt_metadata_poll_s, cfg.preempt_grace_s)
    cfg.preempt_metadata_watch = True
    cfg.preempt_metadata_url = server.url
    cfg.preempt_metadata_poll_s = 0.05
    cfg.preempt_grace_s = 1.0
    from ray_tpu.cluster_utils import Cluster

    c = Cluster()
    try:
        c.add_node(num_cpus=1)
        ray_tpu.init(address=c.address)
        n2 = c.add_node(num_cpus=1)  # watcher starts with the config on
        assert _wait_for(
            lambda: any(n["node_id"] == n2.node_id.hex()
                        and (n.get("draining") or n["state"] == "DEAD")
                        for n in state.list_nodes()), timeout=30), \
            "metadata TRUE never reached the drain path"
        assert _wait_for(
            lambda: any(n["node_id"] == n2.node_id.hex()
                        and n["state"] == "DEAD"
                        for n in state.list_nodes()), timeout=30)
    finally:
        (cfg.preempt_metadata_watch, cfg.preempt_metadata_url,
         cfg.preempt_metadata_poll_s, cfg.preempt_grace_s) = saved
        server.close()
        ray_tpu.shutdown()
        c.shutdown()

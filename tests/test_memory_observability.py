"""Cluster memory observability: `ray memory`-style reference debugging
(ref types + creation callsites through the TaskEventBuffer→GCS path),
object-store/HBM accounting gauges, the GCS leak watcher, and on-demand
profiling capture.

Mirrors the reference's ``python/ray/tests/test_memstat.py`` /
``test_metrics_agent.py`` surfaces, TPU-scoped.
"""

import os
import time

import numpy as np
import pytest

import ray_tpu
from ray_tpu.util import state


def _poll(fn, timeout=30.0, interval=0.3):
    deadline = time.monotonic() + timeout
    value = fn()
    while not value and time.monotonic() < deadline:
        time.sleep(interval)
        value = fn()
    return value


@pytest.fixture(autouse=True)
def _cluster(ray_cluster):
    yield


# ----------------------------------------------------------------- unit layer


def test_callsite_names_user_frame():
    from ray_tpu.observability.memory import capture_callsite

    site = capture_callsite()
    assert "test_memory_observability.py" in site
    assert "test_callsite_names_user_frame" in site


def test_classify_ref_priorities():
    from ray_tpu.observability import memory as m

    assert m.classify_ref(local=1, submitted=1, contained_in=0, borrowers=0,
                          pinned=False) == m.USED_BY_PENDING_TASK
    assert m.classify_ref(local=1, submitted=0, contained_in=1, borrowers=0,
                          pinned=False) == m.CAPTURED_IN_OBJECT
    assert m.classify_ref(local=2, submitted=0, contained_in=0, borrowers=0,
                          pinned=False) == m.LOCAL_REFERENCE
    assert m.classify_ref(local=0, submitted=0, contained_in=0, borrowers=0,
                          pinned=True) == m.PINNED_IN_STORE


def test_leak_detector_unit():
    """Injected monotonic growth fires exactly once, names the top holder
    by callsite, and re-arms after the trend flattens."""
    from ray_tpu.observability.memory import GcsMemoryStore, leak_event_message

    store = GcsMemoryStore()

    def summary(n):
        return {
            "worker_id": "w1", "node_id": "n1", "ts": time.time(),
            "num_refs": n, "total_bytes": n * 100,
            "entries": [{"object_id": f"o{i}", "size": 100,
                         "ref_type": "LOCAL_REFERENCE",
                         "callsite": "leaky.py:7 in hoard"} for i in range(n)],
        }

    for n in (10, 20, 30, 40, 50):
        store.report(summary(n))
    leaks = store.detect_leaks(intervals=4, min_growth_bytes=1 << 40,
                               min_growth_refs=20)
    assert len(leaks) == 1 and leaks[0]["worker_id"] == "w1"
    assert leaks[0]["top_holders"][0]["callsite"] == "leaky.py:7 in hoard"
    assert "leaky.py:7 in hoard" in leak_event_message(leaks[0])
    # already reported: silent while growth continues
    store.report(summary(60))
    assert store.detect_leaks(intervals=4, min_growth_bytes=1 << 40,
                              min_growth_refs=20) == []
    # flat trend re-arms, a fresh monotonic run fires again
    for n in (60, 60, 60, 60, 60):
        store.report(summary(n))
    assert store.detect_leaks(intervals=4, min_growth_bytes=1 << 40,
                              min_growth_refs=20) == []
    for n in (80, 110, 140, 170, 200):
        store.report(summary(n))
    assert len(store.detect_leaks(intervals=4, min_growth_bytes=1 << 40,
                                  min_growth_refs=20)) == 1
    # node pinned-bytes trend uses the same machinery
    for b in (1 << 20, 2 << 20, 3 << 20, 4 << 20, 5 << 20):
        store.report_node("node-a", b)
    node_leaks = store.detect_leaks(intervals=4, min_growth_bytes=1 << 20,
                                    min_growth_refs=1 << 30)
    assert any(s["kind"] == "node_pinned_bytes" for s in node_leaks)


# ------------------------------------------------------- reference debugging


def test_leaked_ref_attributed_end_to_end(tmp_path, capsys):
    """Acceptance: a deliberately leaked ObjectRef is attributable — the
    memory summary (and `cli memory`) shows its size, a
    USED_BY_PENDING_TASK→LOCAL_REFERENCE ref type, and this file as the
    creation callsite."""
    leaked = ray_tpu.put(np.arange(1024, dtype=np.int64))  # deliberately kept

    marker = str(tmp_path / "release")

    @ray_tpu.remote
    def hold(x, path):
        while not os.path.exists(path):
            time.sleep(0.05)
        return int(x[0])

    pending = hold.remote(leaked, marker)
    oid_hex = leaked.id().hex()

    def _entry():
        for w in state.memory_summary().get("workers", []):
            for e in w.get("entries", []):
                if e["object_id"] == oid_hex:
                    return e
        return None

    entry = _poll(lambda: (e := _entry()) and e["ref_type"] == "USED_BY_PENDING_TASK" and e)
    assert entry, f"pending-task ref never reported: {_entry()}"
    assert entry["size"] >= 1024 * 8
    assert "test_memory_observability.py" in entry["callsite"]

    with open(marker, "w") as f:
        f.write("go")
    assert ray_tpu.get(pending, timeout=60) == 0

    entry = _poll(lambda: (e := _entry()) and e["ref_type"] == "LOCAL_REFERENCE" and e)
    assert entry, f"leaked ref never settled to LOCAL_REFERENCE: {_entry()}"
    assert entry["age_s"] >= 0.0

    # the CLI view renders the same attribution
    from ray_tpu.cli import main

    assert main(["memory"]) == 0
    out = capsys.readouterr().out
    assert "OBJECT_ID" in out and "REF_TYPE" in out
    assert oid_hex[:28] in out and "LOCAL_REFERENCE" in out
    assert "test_memory_observability.py" in out
    assert main(["memory", "--group-by-callsite"]) == 0
    out = capsys.readouterr().out
    assert "CALLSITE" in out and "test_memory_observability.py" in out


def test_list_objects_enriched_and_warns():
    ref = ray_tpu.put(np.zeros(200_000, dtype=np.float32))  # plasma-sized
    oid_hex = ref.id().hex()

    def _row():
        rows = state.list_objects()
        for r in rows:
            if r["object_id"] == oid_hex and r.get("ref_type"):
                return r
        return None

    row = _poll(_row)
    assert row, "plasma object never enriched with ref info"
    assert row["size"] >= 800_000
    assert row["ref_type"] == "LOCAL_REFERENCE"
    assert "test_memory_observability.py" in row["callsite"]

    # plasma-sized so they land in the raylet's store listing
    extra = [ray_tpu.put(np.zeros(200_000, dtype=np.float32)) for _ in range(3)]
    with pytest.warns(UserWarning, match="truncated"):
        state.list_objects(limit=1)
    del extra, ref


# ----------------------------------------------------------- node accounting


def test_spill_counters_and_memory_gauges():
    """Satellite: a spill round-trip moves the spill/restore counters in
    debug_state AND the ray_tpu_spill_* / object-store gauges; acceptance:
    used/spill/hbm gauges all appear in prometheus_text()."""
    from ray_tpu.core import api
    from ray_tpu.util.metrics import get_metrics, prometheus_text

    ray_tpu.shutdown()
    ray_tpu.init(num_cpus=2, object_store_memory=8 * 1024 * 1024)
    try:
        arrays = [np.full(1024 * 1024 // 8, i, dtype=np.int64) for i in range(16)]
        refs = [ray_tpu.put(a) for a in arrays]  # 16 MiB = 2x capacity
        raylet = api._node.raylet
        assert raylet._spilled_objects_total > 0
        for i, ref in enumerate(refs):
            np.testing.assert_array_equal(ray_tpu.get(ref), arrays[i])
        assert raylet._restored_objects_total > 0

        snap = raylet._debug_state_snapshot()
        store = snap["store"]
        assert store["spilled_objects_total"] > 0
        assert store["restored_objects_total"] > 0
        assert store["spilled_bytes_total"] > 0
        assert store["pinned_bytes"] > 0
        assert store["used_peak"] >= store["used"]
        assert "hbm" in snap and "worker_rss_bytes" in snap

        def _rows():
            rows = {m["name"]: m for m in get_metrics()}
            want = ("ray_tpu_object_store_used_bytes",
                    "ray_tpu_spill_bytes_total",
                    "ray_tpu_restore_bytes_total",
                    "ray_tpu_hbm_used_bytes",
                    "ray_tpu_worker_rss_bytes")
            if not all(n in rows for n in want):
                return None
            # gauges exist from registration; wait for the heartbeat that
            # carries the non-zero spill totals
            if rows["ray_tpu_spill_bytes_total"]["value"] <= 0:
                return None
            return rows

        rows = _poll(_rows)
        assert rows, "memory gauges never reached GetMetrics"
        assert rows["ray_tpu_spill_bytes_total"]["value"] > 0
        assert rows["ray_tpu_object_store_used_bytes"]["value"] > 0
        text = prometheus_text(list(rows.values()))
        for name in ("ray_tpu_object_store_used_bytes",
                     "ray_tpu_spill_bytes_total", "ray_tpu_hbm_used_bytes"):
            assert name in text
        del refs
    finally:
        ray_tpu.shutdown()


# --------------------------------------------------------------- leak watcher


def test_leak_watcher_fires_error_event():
    """Acceptance: injected monotonic refcount growth in the driver makes
    the GCS leak watcher publish a memory_leak ErrorEvent naming the
    hoarding callsite."""
    from ray_tpu.core.config import get_config

    cfg = get_config()
    saved = (cfg.memory_report_interval_ms, cfg.memory_leak_check_interval_s,
             cfg.memory_leak_intervals, cfg.memory_leak_min_growth_bytes,
             cfg.memory_leak_min_growth_refs)
    cfg.memory_report_interval_ms = 300
    cfg.memory_leak_check_interval_s = 0.5
    cfg.memory_leak_intervals = 3
    cfg.memory_leak_min_growth_bytes = 1 << 40  # trip on refs, not bytes
    cfg.memory_leak_min_growth_refs = 5
    hoard = []
    try:
        def _leaked():
            events = state.list_errors(error_type="memory_leak", limit=50)
            return [e for e in events
                    if "test_memory_observability.py" in e.get("message", "")]

        deadline = time.monotonic() + 45
        events = []
        while time.monotonic() < deadline and not events:
            hoard.append(ray_tpu.put(np.ones(8192, dtype=np.int64)))
            time.sleep(0.1)
            events = _leaked()
        assert events, "leak watcher never fired for the injected growth"
        ev = events[-1]
        assert ev["source"] == "gcs"
        assert "Top holders" in ev["message"]
        suspect = (ev.get("extra") or {}).get("suspect") or {}
        assert suspect.get("growth_refs", 0) > 0
    finally:
        (cfg.memory_report_interval_ms, cfg.memory_leak_check_interval_s,
         cfg.memory_leak_intervals, cfg.memory_leak_min_growth_bytes,
         cfg.memory_leak_min_growth_refs) = saved
        hoard.clear()


# ------------------------------------------------------------------ profiling


def test_profile_capture_and_listing(capsys):
    """cli profile triggers a jax.profiler capture on a worker via RPC;
    the artifact lands on disk and registers under list_profiles()."""
    reply = _poll(
        lambda: (r := state.capture_profile(duration=0.3)).get("path") and r,
        timeout=60.0, interval=1.0)
    assert reply, f"profile capture never succeeded: {state.capture_profile(duration=0.3)}"
    assert os.path.isdir(reply["path"])
    # jax writes plugins/profile/<ts>/*.xplane.pb under the trace dir
    found = []
    for root, _dirs, files in os.walk(reply["path"]):
        found.extend(os.path.join(root, f) for f in files)
    assert found, f"no profiler artifacts under {reply['path']}"

    profiles = _poll(lambda: [p for p in state.list_profiles()
                              if p.get("path") == reply["path"]])
    assert profiles and profiles[-1]["node_id"]

    from ray_tpu.cli import main

    assert main(["profile", "--list"]) == 0
    out = capsys.readouterr().out
    assert "PATH" in out and reply["path"][:48] in out


# ------------------------------------------------------------ tier-1 CI smoke


def test_cli_memory_and_doctor_smoke(capsys):
    """Satellite CI guard: `cli memory` and `cli doctor` both render
    against a live local cluster without error."""
    from ray_tpu.cli import main

    assert ray_tpu.get(ray_tpu.put(1), timeout=30) == 1
    assert _poll(lambda: state.memory_summary().get("num_workers", 0) >= 1)

    assert main(["memory"]) == 0
    out = capsys.readouterr().out
    assert "workers" in out and "OBJECT_ID" in out

    assert main(["doctor"]) == 0
    out = capsys.readouterr().out
    assert "per-node lease queues" in out and "GCS:" in out

    # dashboard endpoints behind /api/memory and /api/profiles
    from ray_tpu.dashboard import _collect

    summary = _collect("memory")
    assert "workers" in summary
    assert isinstance(_collect("profiles"), list)

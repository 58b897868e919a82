"""Always-warm serving fleet (round 19): standby demote/promote round
trips, the chunked weight-broadcast wire, the fleet policy pure
functions, and the serve-level scale-to-zero → first-request wake loop.

The regime under test: replica capacity as a WARM resource. A standby
replica keeps its weights in host RAM with the compile cache warm, so
promotion is one host→device transfer instead of minutes of init; N
cold replicas stream weights from one donor's broadcast instead of N
independent loads; an idle deployment parks at zero running replicas
and the first request promotes a standby back.
"""

import dataclasses
import json
import threading
import time
import urllib.error
import urllib.request

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import ray_tpu
from ray_tpu import serve
from ray_tpu.llm.engine import InferenceEngine, Request
from ray_tpu.llm.weights import (WeightBroadcastSource, host_to_device,
                                 params_fingerprint, receive_weight_stream,
                                 tree_bytes, tree_to_host)
from ray_tpu.models.llama import PRESETS, init_params
from ray_tpu.serve import fleet


@pytest.fixture(scope="module")
def small_model():
    cfg = dataclasses.replace(PRESETS["debug"], dtype=jnp.float32,
                              attn_impl="reference")
    params = init_params(cfg, jax.random.PRNGKey(0))
    return cfg, params


def _make_engine(small_model, **kw):
    cfg, params = small_model
    return InferenceEngine(cfg, params, max_slots=2, max_len=64,
                           enable_prefix_cache=False, **kw)


def _generate(eng, prompt, n=6):
    r = Request(f"r{time.time_ns()}", list(prompt), max_new_tokens=n)
    eng.add_request(r)
    while not r.done:
        eng.step()
    return list(r.generated)


# ------------------------------------------------------------ fleet policy
def test_scheduled_floor_picks_covering_window_max():
    now = 1000.0
    entries = [
        {"start": 900, "end": 1100, "min_replicas": 2},
        {"start": 990, "end": 1010, "min_replicas": 5},
        {"start": 1100, "end": 1200, "min_replicas": 9},  # not yet
        {"start": 800, "end": 1000, "min_replicas": 7},   # end-exclusive
    ]
    assert fleet.scheduled_floor(entries, now) == 5
    assert fleet.scheduled_floor(entries, 1150.0) == 9
    assert fleet.scheduled_floor(entries, 1500.0) == 0
    assert fleet.scheduled_floor(None, now) == 0


def test_scheduled_floor_skips_malformed_entries():
    entries = [{"start": "bad"}, None and {}, {"min_replicas": 3},
               {"start": 0, "end": 2e9, "min_replicas": "4"}]
    assert fleet.scheduled_floor(entries, 1000.0) == 4


def test_slope_projection_extrapolates_trend():
    # TTFT rising 10 ms/s: projecting 5 s ahead from the last sample.
    samples = [(t, 100.0 + 10.0 * t) for t in range(6)]
    proj = fleet.slope_projection(samples, 5.0)
    assert proj == pytest.approx(150.0 + 50.0, abs=1e-6)
    # Too few points / degenerate spread → no prediction.
    assert fleet.slope_projection(samples[:2], 5.0) is None
    assert fleet.slope_projection([(1.0, 5.0)] * 4, 5.0) is None
    # None values (no-traffic windows) are filtered, not crashed on.
    assert fleet.slope_projection([(0, None), (1, None)], 5.0) is None


def test_desired_standby_scale_to_zero_implies_one():
    assert fleet.desired_standby(None) == 0
    assert fleet.desired_standby({"standby_replicas": 3}) == 3
    # scale-to-zero without a standby would make the first request pay a
    # full cold start — the policy floors the pool at 1.
    assert fleet.desired_standby({"scale_to_zero_idle_s": 5.0}) == 1
    assert fleet.desired_standby(
        {"standby_replicas": 2, "scale_to_zero_idle_s": 5.0}) == 2

    class Obj:
        standby_replicas = 2
        scale_to_zero_idle_s = None

    assert fleet.desired_standby(Obj()) == 2


def test_should_scale_to_zero_threshold_and_unknowns():
    auto = {"scale_to_zero_idle_s": 10.0}
    assert fleet.should_scale_to_zero(11.0, auto)
    assert not fleet.should_scale_to_zero(9.0, auto)
    assert not fleet.should_scale_to_zero(None, auto)  # unknown idleness
    assert not fleet.should_scale_to_zero(11.0, {})    # feature off
    assert not fleet.should_scale_to_zero(11.0, None)


def test_fold_fleet_rows_min_idle_and_unknown_poisons():
    rows = [
        {"idle_s": 30.0, "residency_capable": True, "weights_on_host": False},
        {"idle_s": 5.0, "residency_capable": True, "weights_on_host": True},
    ]
    folded = fleet.fold_fleet_rows(rows)
    # The fleet is only as idle as its busiest replica.
    assert folded == {"idle_s": 5.0, "replicas": 2, "residency_capable": 2,
                      "host_resident": 1}
    # One replica with unknown idle age must block scale-to-zero.
    rows.append({"idle_s": None})
    assert fleet.fold_fleet_rows(rows)["idle_s"] is None
    assert fleet.fold_fleet_rows([]) is None


# -------------------------------------------------------- weight broadcast
def test_host_round_trip_preserves_bytes(small_model):
    _, params = small_model
    host = tree_to_host(params)
    back = host_to_device(host)
    want = params_fingerprint(params)
    assert params_fingerprint(host) == want
    assert params_fingerprint(back) == want
    assert tree_bytes(host) == tree_bytes(params)


def test_broadcast_parity_two_concurrent_readers(small_model):
    """The fan-out delivery path: TWO readers of one source both get a
    byte-identical copy of the donor's pytree."""
    _, params = small_model
    want = params_fingerprint(params)
    src = WeightBroadcastSource(params, model="m", n_readers=2)
    got: list = [None, None]

    def read(i):
        got[i] = receive_weight_stream(src.address, timeout_s=60.0)

    ts = [threading.Thread(target=read, args=(i,)) for i in range(2)]
    for t in ts:
        t.start()
    for t in ts:
        t.join(timeout=60)
    src.join(timeout=10)
    for res in got:
        assert res is not None and res["complete"], res and res["status"]
        assert res["fingerprint"] == want
        assert params_fingerprint(res["params"]) == want
        # Leaf-level byte parity, not just the digest.
        want_leaves = jax.tree_util.tree_leaves(params)
        got_leaves = jax.tree_util.tree_leaves(res["params"])
        assert len(want_leaves) == len(got_leaves)
        for a, b in zip(want_leaves, got_leaves):
            assert np.asarray(a).tobytes() == np.asarray(b).tobytes()


def test_broadcast_source_death_mid_stream_reports_incomplete(small_model):
    """Chaos: the donor dies after 2 chunks — the reader must come back
    with params=None and an honest status, never a half-built pytree."""
    _, params = small_model
    src = WeightBroadcastSource(params, model="m", n_readers=1,
                                chunk_bytes=64 << 10, _die_after_chunks=2)
    res = receive_weight_stream(src.address, timeout_s=30.0)
    src.join(timeout=10)
    assert res["params"] is None
    assert not res["complete"]
    assert res["status"] != "ok"


# --------------------------------------------------------- engine residency
def test_engine_demote_promote_round_trip(small_model):
    eng = _make_engine(small_model)
    prompt = [3, 1, 4, 1, 5, 9]
    before = _generate(eng, prompt)
    res = eng.demote_weights_to_host()
    assert res["ok"] and res["bytes"] > 0
    assert not eng.weights_resident()
    assert eng.executor.params is None
    assert eng.metrics["weights_demoted"] == 1
    out = eng.promote_weights_from_host()
    assert out["ok"] and not out.get("already")
    assert eng.weights_resident()
    assert eng.metrics["weights_promoted"] == 1
    assert eng.metrics["weight_promote_ms"] > 0
    # Promotion restored the exact weights: greedy decode is bit-stable.
    assert _generate(eng, prompt) == before


def test_engine_demote_refused_while_busy(small_model):
    eng = _make_engine(small_model)
    r = Request("busy", [1, 2, 3], max_new_tokens=4)
    eng.add_request(r)
    res = eng.demote_weights_to_host()
    assert not res["ok"] and res["reason"] == "busy"
    while not r.done:
        eng.step()
    assert eng.demote_weights_to_host()["ok"]
    eng.promote_weights_from_host()


def test_first_request_auto_promotes(small_model):
    """Scale-to-zero's wake at the engine layer: a request arriving at a
    demoted engine promotes the weights transparently."""
    eng = _make_engine(small_model)
    prompt = [2, 7, 1, 8]
    before = _generate(eng, prompt)
    assert eng.demote_weights_to_host()["ok"]
    assert not eng.weights_resident()
    assert _generate(eng, prompt) == before
    assert eng.weights_resident()
    assert eng.metrics["weights_promoted"] == 1


def test_install_weights_streams_into_demoted_engine(small_model):
    cfg, params = small_model
    eng = _make_engine(small_model)
    assert eng.demote_weights_to_host()["ok"]
    host = tree_to_host(params)
    out = eng.install_weights(host)
    assert out["ok"]
    assert eng.weights_resident()
    assert params_fingerprint(eng.executor.params) == \
        params_fingerprint(params)


# ------------------------------------------------------- promotion ladder
@pytest.fixture(scope="module")
def llm_replica():
    from ray_tpu.llm.serving import LLMDeployment

    dep = LLMDeployment("debug-128", max_slots=2, max_len=64, page_size=8,
                        prefill_chunk_size=32, attention_impl="dense",
                        use_compiled_loop=False, request_timeout_s=60)
    yield dep


def test_fleet_stats_idle_clock_and_residency(llm_replica):
    dep = llm_replica
    assert dep.generate("hi", max_new_tokens=4)
    row = dep.fleet_stats()
    assert row["residency_capable"]
    assert not row["weights_on_host"]
    assert row["idle_s"] >= 0.0
    assert dep.fleet_demote()["ok"]
    assert dep.fleet_stats()["weights_on_host"]
    out = dep.fleet_promote()
    assert out["ok"] and out["path"] == "host"
    assert dep.fleet_promote()["path"] == "resident"  # idempotent


def test_promote_via_broadcast_stream(llm_replica):
    """The controller's fan-out path: a donor stream feeds a demoted
    replica; the streamed install must reproduce the donor's bytes."""
    dep = llm_replica
    donor = dep.open_weight_stream(n_readers=1)
    assert donor and donor["weight_address"]
    assert dep.fleet_demote()["ok"]
    out = dep.fleet_promote(donor["weight_address"])
    assert out["ok"] and out["path"] == "stream"
    assert params_fingerprint(dep.engine.executor.params) == \
        donor["fingerprint"]


@pytest.mark.chaos
def test_promotion_survives_donor_death_via_host_fallback(llm_replica):
    """Chaos: the donor's broadcast dies after 1 chunk mid-promotion.
    The ladder degrades to the host-RAM copy — promotion still lands."""
    dep = llm_replica
    donor = dep.open_weight_stream(n_readers=1, _die_after_chunks=1)
    assert dep.fleet_demote()["ok"]
    out = dep.fleet_promote(donor["weight_address"])
    assert out["ok"] and out["path"] == "host"
    assert out["ladder"] and out["ladder"][0].startswith("stream:")
    assert dep.generate("ok", max_new_tokens=4)


@pytest.mark.chaos
def test_promotion_survives_dead_address_and_lost_host_copy(llm_replica):
    """Worst case: the donor address is unreachable AND the host copy is
    gone — the last rung re-inits from the deployment seed and still
    serves (weights are seed-derived in this repo, so the re-init is
    bit-exact)."""
    dep = llm_replica
    want = params_fingerprint(dep.engine.executor.params)
    assert dep.fleet_demote()["ok"]
    dep.engine._host_params = None  # simulate host-tier loss
    out = dep.fleet_promote("127.0.0.1:1")
    assert out["ok"] and out["path"] == "cold_init"
    assert params_fingerprint(dep.engine.executor.params) == want


# ----------------------------------------------------------- serve e2e
def _get(addr, path, timeout=60.0):
    try:
        with urllib.request.urlopen(addr + path, timeout=timeout) as r:
            return r.status, r.read()
    except urllib.error.HTTPError as e:
        return e.code, e.read()
    except Exception as e:
        return type(e).__name__, b""


def _dep_status(app="fleet"):
    return next(iter((serve.status().get(app) or {}).values()), None) or {}


def _wait_for(pred, timeout=60.0, period=0.25):
    deadline = time.time() + timeout
    while time.time() < deadline:
        st = _dep_status()
        if pred(st):
            return st
        time.sleep(period)
    return None


def test_scale_to_zero_and_first_request_wake_e2e(ray_cluster):
    """THE acceptance loop: deploy → serve → idle past the threshold →
    the deployment parks (0 running, warm standbys, still 'healthy') →
    the next request wakes it via the router poke → standby promotion
    (host path, no cold start) serves the request."""
    from ray_tpu.llm import build_llm_app

    serve.run(
        build_llm_app(
            "debug-128", max_slots=2, max_len=64, page_size=8,
            prefill_chunk_size=32, num_replicas=1, max_ongoing_requests=2,
            attention_impl="dense", use_compiled_loop=False,
            autoscaling_config={"min_replicas": 1, "max_replicas": 2,
                                "scale_to_zero_idle_s": 2.0}),
        name="fleet", route_prefix="/fleet", timeout_s=60.0)
    addr = serve.http_address()
    try:
        status, body = _get(addr, "/fleet?prompt=hi&max_new_tokens=4")
        assert status == 200, (status, body[:200])

        # Park: idle crosses the threshold → 0 running, ≥1 warm standby,
        # and the deployment still reports healthy.
        st = _wait_for(lambda s: s.get("scaled_to_zero")
                       and s.get("running_replicas") == 0
                       and s.get("standby_replicas", 0) >= 1
                       and s.get("fleet", {}).get("host_resident", 0) >= 1,
                       timeout=60.0)
        assert st is not None, _dep_status()
        assert st["healthy"]

        # Wake: the request lands on an empty table, the router pokes
        # the controller, a standby promotes, and the request completes.
        status, body = _get(addr, "/fleet?prompt=again&max_new_tokens=4")
        assert status == 200, (status, body[:200])
        st = _wait_for(lambda s: not s.get("scaled_to_zero")
                       and s.get("running_replicas", 0) >= 1)
        assert st is not None, _dep_status()
        promote = st.get("last_promote") or {}
        # Promotion came from the warm pool, not a cold start.
        assert promote.get("path") in ("host", "stream", "resident"), st
        triggers = [e["trigger"] for e in st.get("autoscale_events", [])]
        assert "scale_to_zero" in triggers and "wake" in triggers
    finally:
        serve.shutdown()


def test_standby_pool_demotes_excess_e2e(ray_cluster):
    """standby_replicas keeps a warm pool behind the active set: the
    controller starts one extra replica and demotes it to STANDBY
    instead of leaving it routable."""
    from ray_tpu.llm import build_llm_app

    serve.run(
        build_llm_app(
            "debug-128", max_slots=2, max_len=64, page_size=8,
            prefill_chunk_size=32, num_replicas=1, max_ongoing_requests=2,
            attention_impl="dense", use_compiled_loop=False,
            autoscaling_config={"min_replicas": 1, "max_replicas": 2,
                                "standby_replicas": 1}),
        name="fleet", route_prefix="/fleet", timeout_s=60.0)
    addr = serve.http_address()
    try:
        status, body = _get(addr, "/fleet?prompt=hi&max_new_tokens=4")
        assert status == 200, (status, body[:200])
        # Wait for the SETTLED pool shape (one running, one warm standby
        # whose host-RAM residency shows in the fold) — point-in-time
        # snapshots mid-reconcile can catch the pool half-built.
        st = _wait_for(lambda s: s.get("standby_replicas", 0) >= 1
                       and s.get("running_replicas", 0) >= 1
                       and (s.get("fleet") or {}).get("host_resident", 0) >= 1,
                       timeout=60.0)
        assert st is not None, _dep_status()
        # Traffic still lands on the running replica only.
        status, _ = _get(addr, "/fleet?prompt=more&max_new_tokens=4")
        assert status == 200
    finally:
        serve.shutdown()


def test_util_state_serve_fleet_surface(ray_cluster):
    """util.state.serve_fleet(): the fleet view reaches the GCS-state
    surface (and degrades to {} with no Serve instance)."""
    from ray_tpu.llm import build_llm_app
    from ray_tpu.util import state as util_state

    serve.run(
        build_llm_app(
            "debug-128", max_slots=2, max_len=64, page_size=8,
            prefill_chunk_size=32, num_replicas=1, max_ongoing_requests=2,
            attention_impl="dense", use_compiled_loop=False),
        name="fleet", route_prefix="/fleet", timeout_s=60.0)
    try:
        view = util_state.serve_fleet()
        row = next((v for k, v in view.items() if k.startswith("fleet#")),
                   None)
        assert row is not None, view
        assert row["running"] >= 1 and row["standby"] == 0
        assert row["scaled_to_zero"] is False
    finally:
        serve.shutdown()
    assert util_state.serve_fleet() == {}

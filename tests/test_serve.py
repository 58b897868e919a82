"""Serve: controller/replica FSM, router, rolling update, autoscaling,
HTTP ingress, replica-kill recovery.

Mirrors the reference's ``python/ray/serve/tests/`` acceptance surface
(controller.py:84, deployment_state.py:1249, pow_2_scheduler.py:52,
long_poll.py:204).
"""

import json
import textwrap
import threading
import time
import urllib.request

import pytest

import ray_tpu
from ray_tpu import serve


@pytest.fixture()
def serve_instance(ray_cluster):
    yield
    serve.shutdown()


def _http_get(url, timeout=30):
    try:
        with urllib.request.urlopen(url, timeout=timeout) as r:
            return json.loads(r.read())
    except urllib.error.HTTPError as e:
        return json.loads(e.read())


@serve.deployment(num_replicas=2, max_ongoing_requests=4)
class Echo:
    def __init__(self, prefix=""):
        self.prefix = prefix

    def __call__(self, request):
        return {"echo": self.prefix + request.query_params.get("msg", "")}


def test_echo_http_and_handle(serve_instance):
    handle = serve.run(Echo.bind("p:"), name="default", route_prefix="/")
    assert handle.remote(serve.Request(query={"msg": "x"})).result(timeout=60) == {"echo": "p:x"}
    addr = serve.http_address()
    assert _http_get(addr + "/?msg=y") == {"echo": "p:y"}
    assert _http_get(addr + "/-/healthz") == "ok"


def test_concurrent_http_traffic(serve_instance):
    serve.run(Echo.bind(), name="default", route_prefix="/")
    addr = serve.http_address()
    results, errors = [], []

    def worker(i):
        try:
            results.append(_http_get(f"{addr}/?msg={i}", timeout=60))
        except Exception as e:  # pragma: no cover
            errors.append(e)

    threads = [threading.Thread(target=worker, args=(i,)) for i in range(16)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=60)
    assert not errors
    assert sorted(r["echo"] for r in results) == sorted(str(i) for i in range(16))


def test_model_composition(serve_instance):
    """Ingress deployment calling a downstream deployment by handle."""

    @serve.deployment
    class Doubler:
        def double(self, x):
            return x * 2

    @serve.deployment
    class Ingress:
        def __init__(self, doubler):
            self.doubler = doubler

        def __call__(self, request):
            v = int(request.query_params.get("x", "0"))
            return {"doubled": self.doubler.double.remote(v).result(timeout=30)}

    serve.run(Ingress.bind(Doubler.bind()), name="compose", route_prefix="/compose")
    addr = serve.http_address()
    assert _http_get(addr + "/compose?x=21") == {"doubled": 42}
    serve.delete("compose")


def test_rolling_update_changes_version(serve_instance):
    serve.run(Echo.bind("v1:"), name="default", route_prefix="/")
    addr = serve.http_address()
    assert _http_get(addr + "/?msg=a") == {"echo": "v1:a"}
    # redeploy with new init args → new version → rolling replica swap
    serve.run(Echo.bind("v2:"), name="default", route_prefix="/")
    deadline = time.monotonic() + 60
    while time.monotonic() < deadline:
        if _http_get(addr + "/?msg=a") == {"echo": "v2:a"}:
            break
        time.sleep(0.2)
    assert _http_get(addr + "/?msg=b") == {"echo": "v2:b"}
    # service stayed up during the roll: every request must succeed
    for _ in range(5):
        assert _http_get(addr + "/?msg=c")["echo"].endswith(":c")


def test_replica_kill_recovery(serve_instance):
    @serve.deployment(num_replicas=1)
    class Pid:
        def __call__(self, request):
            import os

            return {"pid": os.getpid()}

        def die(self):
            import os

            os._exit(1)

    handle = serve.run(Pid.bind(), name="pid", route_prefix="/pid")
    pid1 = handle.remote(serve.Request()).result(timeout=60)["pid"]
    try:
        handle.die.remote().result(timeout=10)
    except Exception:
        pass
    # controller must detect the dead replica and start a replacement
    deadline = time.monotonic() + 90
    pid2 = None
    while time.monotonic() < deadline:
        try:
            pid2 = handle.remote(serve.Request()).result(timeout=15)["pid"]
            if pid2 != pid1:
                break
        except Exception:
            time.sleep(0.5)
    assert pid2 is not None and pid2 != pid1
    serve.delete("pid")


def test_autoscaling_up(serve_instance):
    @serve.deployment(
        max_ongoing_requests=2,
        autoscaling_config={
            "min_replicas": 1,
            "max_replicas": 3,
            "target_ongoing_requests": 1.0,
            "upscale_delay_s": 0.5,
            "downscale_delay_s": 60.0,
        },
    )
    class Slow:
        def __call__(self, request):
            time.sleep(1.5)
            return {"ok": True}

    serve.run(Slow.bind(), name="auto", route_prefix="/auto")
    addr = serve.http_address()

    stop = threading.Event()

    def hammer():
        while not stop.is_set():
            try:
                _http_get(addr + "/auto", timeout=30)
            except Exception:
                pass

    threads = [threading.Thread(target=hammer) for _ in range(6)]
    for t in threads:
        t.start()
    try:
        deadline = time.monotonic() + 60
        scaled = False
        while time.monotonic() < deadline:
            st = serve.status()["auto"]["Slow"]
            if st["running_replicas"] >= 2:
                scaled = True
                break
            time.sleep(0.5)
        assert scaled, f"never scaled above 1 replica: {serve.status()}"
    finally:
        stop.set()
        for t in threads:
            t.join(timeout=30)
    serve.delete("auto")


def test_autoscaling_latency_slo_up_and_down(serve_instance, capsys):
    """ISSUE 7 acceptance: `latency_slo` mode scales replicas from the
    windowed p95 of the replicas' own serve_ttft_ms histograms — up when
    the SLO is breached, back down once the quantile clears the headroom
    band — with each decision visible in the status history (`cli serve
    status`) and as a serve.autoscale span."""

    @serve.deployment(
        max_ongoing_requests=8,
        user_config={"ttft_ms": 400.0},
        autoscaling_config={
            "min_replicas": 1,
            "max_replicas": 3,
            "mode": "latency_slo",
            "target_ttft_ms": 100.0,
            "latency_window_s": 2.0,
            "slo_quantile": 0.95,
            "downscale_headroom": 0.5,
            "breach_cycles": 2,
            "upscale_delay_s": 0.5,
            "downscale_delay_s": 0.5,
        },
    )
    class FakeEngine:
        """Stands in for the LLM engine: records a configurable TTFT into
        the same serve_ttft_ms histogram the engine feeds, so the test
        drives the autoscaler's actual signal path deterministically."""

        def __init__(self):
            from ray_tpu.serve.replica import get_replica_context
            from ray_tpu.util.metrics import Histogram

            self._dep = (get_replica_context() or {}).get(
                "deployment", "FakeEngine")
            self._hist = Histogram(
                "serve_ttft_ms", "test ttft", tag_keys=("deployment",))
            self._ttft = 400.0

        def reconfigure(self, cfg):
            if cfg:
                self._ttft = float(cfg.get("ttft_ms", 400.0))

        def __call__(self, request):
            self._hist.observe(self._ttft, tags={"deployment": self._dep})
            return {"ttft": self._ttft}

    app = FakeEngine.bind()
    serve.run(app, name="slo", route_prefix="/slo")
    addr = serve.http_address()

    stop = threading.Event()

    def hammer():
        while not stop.is_set():
            try:
                _http_get(addr + "/slo", timeout=30)
            except Exception:
                pass
            time.sleep(0.2)

    threads = [threading.Thread(target=hammer) for _ in range(4)]
    for t in threads:
        t.start()
    try:
        deadline = time.monotonic() + 60
        while time.monotonic() < deadline:
            st = serve.status()["slo"]["FakeEngine"]
            if st["target_replicas"] >= 2:
                break
            time.sleep(0.25)
        assert st["target_replicas"] >= 2, f"never scaled up: {st}"
        up_events = [e for e in st["autoscale_events"] if e["to"] > e["from"]]
        assert up_events and up_events[0]["trigger"].startswith(
            "serve_ttft_ms_p95"), st["autoscale_events"]
        assert up_events[0]["value"] > 100.0  # the breaching p95 itself

        # Flip the simulated engine fast (config-only change, applied via
        # in-place reconfigure) and keep the traffic flowing: the
        # windowed p95 must clear the 50 ms headroom band and walk the
        # deployment back down to min_replicas.
        serve.run(app.deployment.options(
            user_config={"ttft_ms": 5.0}).bind(), name="slo",
            route_prefix="/slo")
        deadline = time.monotonic() + 90
        while time.monotonic() < deadline:
            st = serve.status()["slo"]["FakeEngine"]
            if st["target_replicas"] == 1 and any(
                    e["to"] < e["from"] for e in st["autoscale_events"]):
                break
            time.sleep(0.25)
        down_events = [e for e in st["autoscale_events"] if e["to"] < e["from"]]
        assert st["target_replicas"] == 1 and down_events, st["autoscale_events"]
        assert down_events[-1]["trigger"].startswith("serve_ttft_ms_p95")
    finally:
        stop.set()
        for t in threads:
            t.join(timeout=30)

    # The decision history is the `cli serve status` surface verbatim.
    from ray_tpu.cli import main as cli_main

    capsys.readouterr()
    assert cli_main(["serve", "status"]) == 0
    cli_out = capsys.readouterr().out
    assert "autoscaling=latency_slo" in cli_out
    assert "scale 1 -> 2" in cli_out and "scale 2 -> 1" in cli_out
    assert "serve_ttft_ms_p95" in cli_out

    # Every decision is also a span (flushed to the GCS span store).
    from ray_tpu.util.state import list_spans

    deadline = time.monotonic() + 30
    autoscale_spans = []
    while time.monotonic() < deadline:
        # High limit: the hammer phase floods the store with per-request
        # spans and the default most-recent-1000 window would cut the
        # handful of autoscale spans recorded mid-run.
        autoscale_spans = [s for s in list_spans(limit=50_000)
                           if s.get("name", "").startswith("serve.autoscale")]
        directions = {s.get("attrs", {}).get("to", 0)
                      - s.get("attrs", {}).get("from", 0)
                      for s in autoscale_spans}
        if any(d > 0 for d in directions) and any(d < 0 for d in directions):
            break
        time.sleep(1.0)
    assert any(s.get("attrs", {}).get("to", 0)
               > s.get("attrs", {}).get("from", 0) for s in autoscale_spans)
    assert any(s.get("attrs", {}).get("to", 0)
               < s.get("attrs", {}).get("from", 0) for s in autoscale_spans)
    serve.delete("slo")


def test_latency_slo_windowed_quantile_units():
    """Controller-internal SLO math, no cluster: probe histograms merge
    across replicas, the windowed quantile is a cumulative delta vs the
    snapshot preceding the window, and replica restarts (shrinking
    counts) clamp instead of going negative."""
    from ray_tpu.serve.controller import ServeController, _DeploymentState

    bounds = [10.0, 100.0, 1000.0]

    def row(buckets, count):
        return {"name": "serve_ttft_ms", "buckets": list(buckets),
                "boundaries": bounds, "count": count}

    merged = ServeController._merge_latency_rows({
        "r1": {"latency": [row([1, 2, 0, 0], 3)]},
        "r2": {"latency": [row([0, 1, 4, 0], 5)]},
        "r3": {"latency": []},
    })
    assert merged["serve_ttft_ms"][0] == [1, 3, 4, 0]
    assert merged["serve_ttft_ms"][2] == 8

    state = _DeploymentState("app", {"name": "d", "version": "v",
                                     "num_replicas": 1, "max_ongoing": 8})
    qtile = ServeController._windowed_quantile
    now = 1000.0
    # t=900: 10 slow observations; t=999: those plus 20 fast ones
    state.latency_history = [
        (900.0, {"serve_ttft_ms": ([0, 0, 10, 0], bounds, 10)}),
        (999.0, {"serve_ttft_ms": ([20, 0, 10, 0], bounds, 30)}),
    ]
    # window 30s: delta vs the t=900 snapshot = 20 fast obs -> p95 <= 10ms
    p95 = qtile(None, state, "serve_ttft_ms", 0.95, 30.0, now)
    assert p95 is not None and p95 <= 10.0
    # window covering everything: cumulative includes the slow bucket
    p95_all = qtile(None, state, "serve_ttft_ms", 0.95, 500.0, now)
    assert p95_all > 100.0
    # empty delta (no traffic since the pre-window snapshot) -> None
    full = ([20, 0, 10, 0], bounds, 30)
    state.latency_history = [(969.0, {"serve_ttft_ms": full}),
                             (999.5, {"serve_ttft_ms": full}),
                             (now, {"serve_ttft_ms": full})]
    assert qtile(None, state, "serve_ttft_ms", 0.95, 30.0, now) is None
    # replica restart: counts shrink below the base -> clamp, not negative
    state.latency_history = [
        (900.0, {"serve_ttft_ms": ([50, 0, 0, 0], bounds, 50)}),
        (now, {"serve_ttft_ms": ([5, 0, 0, 0], bounds, 5)}),
    ]
    assert qtile(None, state, "serve_ttft_ms", 0.95, 30.0, now) is None


def test_delete_application(serve_instance):
    serve.run(Echo.bind(), name="gone", route_prefix="/gone")
    addr = serve.http_address()
    assert _http_get(addr + "/gone?msg=z") == {"echo": "z"}
    serve.delete("gone")
    deadline = time.monotonic() + 30
    while time.monotonic() < deadline:
        result = _http_get(addr + "/gone?msg=z")
        if "error" in result:
            break
        time.sleep(0.2)
    assert "error" in _http_get(addr + "/gone?msg=z")


def test_serve_batch_decorator(serve_instance):
    """@serve.batch groups concurrent calls into one execution
    (reference batching.py:80)."""

    class Batched:
        def __init__(self):
            self.batch_sizes = []

        @serve.batch(max_batch_size=4, batch_wait_timeout_s=0.2)
        def handle(self, items):
            self.batch_sizes.append(len(items))
            return [x * 2 for x in items]

        def __call__(self, request):
            return self.handle(int(request.query_params.get("x", 0)))

        def sizes(self, request=None):
            return self.batch_sizes

    serve.run(serve.deployment(Batched, max_ongoing_requests=16).bind(),
              name="default", route_prefix="/")
    handle = serve.get_app_handle("default")

    results = {}

    def call(i):
        results[i] = handle.remote(serve.Request(query={"x": str(i)})).result(timeout=60)

    threads = [threading.Thread(target=call, args=(i,)) for i in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=60)
    assert results == {i: i * 2 for i in range(8)}
    sizes = handle.options(method_name="sizes").remote(None).result(timeout=60)
    # 8 calls with max_batch_size=4 must have been grouped (not 8x size-1).
    assert sum(sizes) == 8 and max(sizes) > 1, sizes


def test_serve_multiplexed_models(serve_instance):
    """@serve.multiplexed loads per-model state on demand, LRU-evicts
    beyond the cap, and routes by the request header
    (reference multiplex.py:22)."""

    class MultiModel:
        def __init__(self):
            self.loads = []

        @serve.multiplexed(max_num_models_per_replica=2)
        def get_model(self, model_id: str):
            self.loads.append(model_id)
            return {"id": model_id, "scale": len(model_id)}

        def __call__(self, request):
            model_id = serve.get_multiplexed_model_id()
            model = self.get_model(model_id)
            return {"model": model["id"], "loads": list(self.loads)}

    serve.run(serve.deployment(MultiModel, max_ongoing_requests=8).bind(),
              name="default", route_prefix="/")
    addr = serve.http_address()

    def call(model_id):
        req = urllib.request.Request(
            addr + "/", headers={"serve_multiplexed_model_id": model_id})
        return json.loads(urllib.request.urlopen(req, timeout=60).read())

    assert call("m1")["model"] == "m1"
    assert call("m1")["loads"].count("m1") == 1  # cached, not reloaded
    assert call("m2")["model"] == "m2"
    out = call("m3")  # cap 2: evicts LRU (m1)
    assert out["loads"] == ["m1", "m2", "m3"]
    out = call("m1")  # m1 was evicted: loads again
    assert out["loads"].count("m1") == 2


def test_declarative_config_deploy(serve_instance, tmp_path):
    """Apps described as data (YAML schema: import_path + args +
    per-deployment overrides) deploy without touching Python, and the
    dashboard exposes the Serve REST surface (reference serve/schema.py +
    PUT/GET /api/serve/applications)."""
    import sys
    import urllib.request as _rq

    mod_dir = tmp_path / "apps"
    mod_dir.mkdir()
    (mod_dir / "my_serve_app.py").write_text(textwrap.dedent("""
        from ray_tpu import serve

        class Echo2:
            def __init__(self, greeting="hi"):
                self.greeting = greeting

            def __call__(self, request):
                return {"msg": f"{self.greeting} {request.query_params.get('who', '')}"}

        def build(greeting="hi"):
            return serve.deployment(Echo2).bind(greeting)
    """))
    sys.path.insert(0, str(mod_dir))
    try:
        config = {
            "applications": [{
                "name": "cfg_app",
                "route_prefix": "/cfg",
                "import_path": "my_serve_app:build",
                "args": {"greeting": "hello"},
                # ship the module to replicas (reference schema runtime_env)
                "runtime_env": {"py_modules": [str(mod_dir / "my_serve_app.py")]},
                "deployments": [{"name": "Echo2", "num_replicas": 2,
                                 "max_ongoing_requests": 4}],
            }],
        }
        deployed = serve.deploy_config(config)
        assert deployed == {"cfg_app": "/cfg"}
        addr = serve.http_address()
        body = json.loads(_rq.urlopen(addr + "/cfg?who=world", timeout=60).read())
        assert body == {"msg": "hello world"}

        status = serve.serve_status()
        assert status["applications"]["cfg_app"]["status"] == "RUNNING"

        # YAML string form works too
        yaml_config = textwrap.dedent(f"""
            applications:
              - name: cfg_app2
                route_prefix: /cfg2
                import_path: my_serve_app:build
                args: {{greeting: yo}}
                runtime_env:
                  py_modules: ["{mod_dir / 'my_serve_app.py'}"]
        """)
        serve.deploy_config(yaml_config)
        body = json.loads(_rq.urlopen(addr + "/cfg2?who=x", timeout=60).read())
        assert body == {"msg": "yo x"}

        # REST surface via the dashboard
        from ray_tpu.dashboard import start_dashboard

        url = start_dashboard()
        rest = json.loads(_rq.urlopen(url + "/api/serve/applications", timeout=30).read())
        assert "cfg_app" in rest["applications"]
        req = _rq.Request(url + "/api/serve/applications/cfg_app2", method="DELETE")
        assert json.loads(_rq.urlopen(req, timeout=60).read()) == {"deleted": True}
    finally:
        sys.path.remove(str(mod_dir))


def test_grpc_proxy(serve_instance):
    """Unary gRPC calls route /<app>/<method> onto replicas through the
    shared router (reference proxy.py:534 gRPC proxy)."""
    import cloudpickle
    import grpc

    class MathService:
        def __call__(self, x):
            return x + 1

        def mul(self, a, b):
            return a * b

    serve.run(serve.deployment(MathService).bind(), name="math", route_prefix="/math")
    address = serve.start_grpc()

    channel = grpc.insecure_channel(address)
    call = channel.unary_unary("/math/__call__",
                               request_serializer=lambda b: b,
                               response_deserializer=lambda b: b)
    out = cloudpickle.loads(call(cloudpickle.dumps(((41,), {})), timeout=60))
    assert out == 42

    mul = channel.unary_unary("/math/mul",
                              request_serializer=lambda b: b,
                              response_deserializer=lambda b: b)
    assert cloudpickle.loads(mul(cloudpickle.dumps(((6, 7), {})), timeout=60)) == 42

    # unknown app -> INTERNAL error, not a hang
    bad = channel.unary_unary("/nope/__call__",
                              request_serializer=lambda b: b,
                              response_deserializer=lambda b: b)
    with pytest.raises(grpc.RpcError):
        bad(cloudpickle.dumps(((), {})), timeout=30)
    channel.close()


def test_serve_request_metrics(serve_instance):
    """Handle traffic shows up in the serve_* metrics family (reference:
    serve_num_router_requests / processing-latency metrics)."""
    app = Echo.bind()
    h = serve.run(app, name="metrics-app")
    for _ in range(3):
        assert "echo" in h.remote(serve.Request(query={"msg": "m"})).result(timeout=60)

    from ray_tpu.util.metrics import snapshot_all

    deadline = time.time() + 30
    found = {}
    while time.time() < deadline:
        found = {m["name"]: m for m in snapshot_all()
                 if m.get("tags", {}).get("deployment") == "Echo"}
        if "serve_num_requests_total" in found and "serve_request_latency_ms" in found:
            break
        time.sleep(0.2)
    assert found["serve_num_requests_total"]["value"] >= 3
    lat = found["serve_request_latency_ms"]
    assert lat["count"] >= 3 and sum(lat["buckets"]) >= 3


def test_serve_error_metrics(serve_instance):
    """Replica-side exceptions count in serve_num_errors_total."""

    @serve.deployment()
    class Boom:
        def __call__(self, request):
            raise RuntimeError("boom")

    h = serve.run(Boom.bind(), name="boom-app")
    with pytest.raises(Exception):
        h.remote(serve.Request(query={})).result(timeout=60)

    from ray_tpu.util.metrics import snapshot_all

    deadline = time.time() + 30
    while time.time() < deadline:
        errs = [m for m in snapshot_all()
                if m["name"] == "serve_num_errors_total"
                and m.get("tags", {}).get("deployment") == "Boom"]
        if errs and errs[0]["value"] >= 1:
            return
        time.sleep(0.2)
    raise AssertionError("replica error never counted in serve_num_errors_total")


def test_serve_microbench_components(serve_instance):
    """The microbenchmark suite's building blocks run against the SAME
    no-op app the module's __main__ measures (tiny sizes here)."""
    import urllib.request

    from ray_tpu.serve import microbench

    serve.run(microbench.build_noop_app(), name="default", route_prefix="/")
    handle = serve.get_app_handle("default").options(method_name="noop")
    addr = serve.http_address()
    with urllib.request.urlopen(addr + "/", timeout=60) as r:
        assert r.read() == b'"ok"'

    h = microbench.bench_handle_noop(handle, n_seq=10, n_conc=20, concurrency=4)
    assert h["p50_ms"] > 0 and h["rps"] > 0
    http = microbench.bench_http_noop(addr, n_seq=10, n_conc=20, concurrency=4)
    assert http["p50_ms"] >= h["p50_ms"] * 0.1 and http["rps"] > 0
    s = microbench.bench_streaming(addr, chunks=50, runs=2)
    assert s["chunks_per_s"] > 0 and s["first_chunk_ms"] > 0


# ---------------------------------------------------------------- local mode

def test_local_testing_mode_basic_and_composition():
    """In-process deployments without a cluster (reference
    serve/_private/local_testing_mode.py): same handler semantics as a
    real replica — composition, method routing, function deployments —
    at unit-test speed."""
    from ray_tpu import serve

    @serve.deployment
    class Doubler:
        def __call__(self, x):
            return 2 * x

        def triple(self, x):
            return 3 * x

    @serve.deployment
    class Ingress:
        def __init__(self, doubler):
            self.doubler = doubler

        def __call__(self, x):
            return self.doubler.remote(x).result() + 1

    handle = serve.run(Ingress.bind(Doubler.bind()), _local_testing_mode=True)
    assert handle.remote(10).result() == 21
    # direct method routing on a local handle
    d = serve.make_local_deployment_handle(Doubler.bind())
    assert d.remote(4).result() == 8
    assert d.triple.remote(4).result() == 12
    assert d.options(method_name="triple").remote(5).result() == 15

    @serve.deployment
    def add_one(x):
        return x + 1

    f = serve.make_local_deployment_handle(add_one.bind())
    assert f.remote(1).result() == 2


def _bare_router(replicas: dict[str, int]):
    """Router skeleton for affinity-policy unit tests: real
    assign/release/remove logic, no controller or long-poll behind it."""
    from collections import OrderedDict

    from ray_tpu.serve.router import Router

    r = Router.__new__(Router)
    r._key = "replicas::app::dep"
    r._lock = threading.Lock()
    r._cond = threading.Condition(r._lock)
    r._replicas = {rid: {"actor": f"actor-{rid}", "max_ongoing": cap}
                   for rid, cap in replicas.items()}
    r._inflight = {rid: 0 for rid in replicas}
    r._model_affinity = {}
    r._group_affinity = OrderedDict()
    r.affinity_stats = {"hits": 0, "misses": 0, "spills": 0,
                        "new_groups": 0}
    r._init_overload_state()
    return r


def test_router_affinity_sticky_under_steady_load():
    """ISSUE 10: requests carrying a prefix-group key stick to one
    replica while load is balanced; groupless requests still spread."""
    router = _bare_router({"r1": 8, "r2": 8})
    first, _ = router.assign_replica(prefix_group="sess:a")
    router.release(first)
    for _ in range(10):
        rid, _ = router.assign_replica(prefix_group="sess:a")
        assert rid == first
        router.release(rid)
    assert router.affinity_stats["hits"] == 10
    assert router.affinity_stats["new_groups"] == 1  # first-seen lookup
    assert router.affinity_stats["misses"] == 0      # no replica vanished
    assert router.affinity_stats["spills"] == 0


def test_router_affinity_spills_under_imbalance():
    """Load-aware spill: once the affine replica runs hotter than the
    coolest candidate by more than the margin, the group's request goes
    elsewhere (and the group remaps to the spill target, which now holds
    the freshest KV)."""
    from ray_tpu.core.config import get_config

    cfg = get_config()
    saved = cfg.serve_affinity_spill_margin
    cfg.serve_affinity_spill_margin = 2
    try:
        router = _bare_router({"r1": 16, "r2": 16})
        affine, _ = router.assign_replica(prefix_group="sess:s")
        other = "r2" if affine == "r1" else "r1"
        # run the affine replica hot: 3 extra in-flight vs 0 elsewhere
        with router._cond:
            router._inflight[affine] += 3
        rid, _ = router.assign_replica(prefix_group="sess:s")
        assert rid == other
        assert router.affinity_stats["spills"] == 1
        assert router._group_affinity["sess:s"] == other  # remapped
        # a saturated affine replica also spills rather than queueing
        with router._cond:
            router._inflight[other] = 16  # at its cap now
        rid2, _ = router.assign_replica(prefix_group="sess:s")
        assert rid2 == affine
        assert router.affinity_stats["spills"] == 2
    finally:
        cfg.serve_affinity_spill_margin = saved


def test_router_affinity_map_bounded_and_purged_on_death():
    """The group→replica map is bounded LRU, and a dead replica's groups
    are purged immediately (retries must cold-prefill elsewhere, never
    wait for the corpse)."""
    from ray_tpu.core.config import get_config

    cfg = get_config()
    saved = cfg.serve_affinity_map_size
    cfg.serve_affinity_map_size = 8
    try:
        router = _bare_router({"r1": 1000, "r2": 1000})
        for i in range(30):
            rid, _ = router.assign_replica(prefix_group=f"pfx:{i}")
            router.release(rid)
        assert len(router._group_affinity) <= 8
        assert "pfx:29" in router._group_affinity  # newest survive
        victim = router._group_affinity["pfx:29"]
        router.remove_replica(victim)
        assert all(rid != victim
                   for rid in router._group_affinity.values())
        # the group re-routes to a live replica and re-establishes
        rid, _ = router.assign_replica(prefix_group="pfx:29")
        assert rid != victim
        assert router._group_affinity["pfx:29"] == rid
    finally:
        cfg.serve_affinity_map_size = saved


def test_llm_serve_prefix_affinity_end_to_end(serve_instance):
    """Session-keyed HTTP requests through the real proxy land on one
    replica, hit its prefix cache on the follow-up, and the controller's
    app status reports the residency/affinity rates from the replica
    probes."""
    from ray_tpu.llm import build_llm_app

    app = build_llm_app("debug-128", num_replicas=2, max_slots=4,
                        max_len=128, page_size=16)
    serve.run(app, name="llm-affinity", route_prefix="/llm-aff")
    addr = serve.http_address()
    body = {"prompt": "You are a helpful assistant. Answer: hi",
            "max_tokens": 4, "session_id": "sess-42"}
    req = urllib.request.Request(
        f"{addr}/llm-aff/v1/completions",
        data=json.dumps(body).encode(),
        headers={"Content-Type": "application/json"})
    try:
        with urllib.request.urlopen(req, timeout=60) as r:
            first = json.loads(r.read())
        with urllib.request.urlopen(req, timeout=60) as r:
            second = json.loads(r.read())
        # greedy byte-parity across the cached re-send
        assert first["choices"][0]["text"] == second["choices"][0]["text"]
        # the controller folds the replicas' residency probes into status
        def affinity_status():
            st = serve.status().get("llm-affinity", {})
            dep = next(iter(st.values()), {})
            pa = dep.get("prefix_affinity") or {}
            return pa if pa.get("requests", 0) >= 2 else None

        deadline = time.monotonic() + 30
        pa = None
        while time.monotonic() < deadline and pa is None:
            pa = affinity_status()
            time.sleep(0.5)
        assert pa, "prefix_affinity never reached app status"
        # both session requests counted; the re-send hit the cache on
        # the SAME replica (affinity), so at least one cache hit
        assert pa["requests"] >= 2
        assert pa["cache_hits"] >= 1
        assert pa["groups"] >= 1
    finally:
        serve.delete("llm-affinity")


def test_local_testing_mode_streaming_multiplex_reconfigure():
    from ray_tpu import serve

    @serve.deployment(user_config={"k": 3})
    class Gen:
        def __init__(self):
            self.k = 1

        def reconfigure(self, cfg):
            self.k = cfg["k"]

        def stream(self, n):
            for i in range(n):
                yield i * self.k

        def which_model(self):
            return serve.get_multiplexed_model_id()

    h = serve.make_local_deployment_handle(Gen.bind())
    # The streaming path speaks the same wire messages as a real replica
    # (start head + chunks); user_config (k=3) applied through the real
    # ReplicaActor reconfigure path.
    msgs = list(h.options(method_name="stream").remote_streaming(3))
    assert msgs[0]["kind"] == "start"
    chunks = [int(m["data"]) for m in msgs[1:] if m["kind"] == "chunk"]
    assert chunks == [0, 3, 6]
    got = h.options(multiplexed_model_id="m7").which_model.remote().result()
    assert got == "m7"

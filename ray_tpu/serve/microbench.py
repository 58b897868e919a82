"""Serve microbenchmark suite: the stack's own overhead, no model.

Equivalent of the reference's Serve microbenchmarks
(``python/ray/serve/_private/benchmarks/`` — handle/HTTP noop latency
and streaming throughput). A no-op deployment isolates what the serving
stack itself costs — handle path (router + replica actor call), HTTP
path (proxy + router + replica), and the streaming generator path — so
a serving TTFT can be decomposed into stack time vs engine time. Host
rates: no model and no device.

Run: ``python -m ray_tpu.serve.microbench`` — prints one JSON line.
"""

from __future__ import annotations

import json
import statistics
import threading
import time
import urllib.request


def build_noop_app():
    """The no-op app the suite measures (module-level so tests exercise
    the same deployment ``main()`` runs)."""
    from . import api as serve
    from .deployment import deployment

    @deployment(max_ongoing_requests=64)
    class Noop:
        def __call__(self, request):
            if request.query_params.get("stream"):
                n = int(request.query_params.get("chunks", "100"))

                def gen():
                    yield {"__serve_response__": True,
                           "content_type": "text/event-stream"}
                    for i in range(n):
                        yield f"data: {i}\n\n"
                    yield "data: [DONE]\n\n"

                return gen()
            return "ok"

        def noop(self):
            return "ok"

    return Noop.bind()


def _pcts(samples_ms: list[float]) -> dict:
    s = sorted(samples_ms)
    return {
        "p50_ms": round(statistics.median(s), 2),
        "p95_ms": round(s[max(0, int(len(s) * 0.95) - 1)], 2),
    }


def _latency_then_throughput(fn, *, n_seq: int, n_conc: int,
                             concurrency: int) -> dict:
    """Shared harness: sequential latency percentiles, then threaded
    closed-loop throughput of ``fn`` (one no-op request per call)."""
    lat = []
    for _ in range(n_seq):
        t0 = time.perf_counter()
        fn()
        lat.append(1000 * (time.perf_counter() - t0))

    errors: list[str] = []
    counter = {"n": 0}
    lock = threading.Lock()

    def worker():
        while True:
            with lock:
                if counter["n"] >= n_conc:
                    return
                counter["n"] += 1
            try:
                fn()
            except Exception as e:
                errors.append(str(e))
                return

    threads = [threading.Thread(target=worker) for _ in range(concurrency)]
    t0 = time.perf_counter()
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    wall = time.perf_counter() - t0
    if errors:
        raise RuntimeError(f"throughput bench errors: {errors[:3]}")
    return {**_pcts(lat), "rps": round(n_conc / wall, 1),
            "concurrency": concurrency}


def bench_handle_noop(handle, *, n_seq: int = 300, n_conc: int = 300,
                      concurrency: int = 16) -> dict:
    """DeploymentHandle round trip: router slot + replica actor call +
    result transport."""
    def one():
        assert handle.remote().result(timeout=60) == "ok"

    return _latency_then_throughput(
        one, n_seq=n_seq, n_conc=n_conc, concurrency=concurrency)


def bench_http_noop(addr: str, *, n_seq: int = 300, n_conc: int = 300,
                    concurrency: int = 16) -> dict:
    """Full HTTP path: proxy parse + route + handle + chunk back."""
    def one():
        with urllib.request.urlopen(addr + "/", timeout=60) as r:
            assert r.read() == b'"ok"'

    return _latency_then_throughput(
        one, n_seq=n_seq, n_conc=n_conc, concurrency=concurrency)


def bench_streaming(addr: str, *, chunks: int = 2000, runs: int = 3) -> dict:
    """SSE chunk throughput through proxy + streaming-generator path, and
    time-to-first-chunk (the stack's share of streaming TTFT)."""
    rates = []
    ttfc = []
    for _ in range(runs):
        t0 = time.perf_counter()
        n = 0
        first = None
        with urllib.request.urlopen(
                addr + f"/?stream=1&chunks={chunks}", timeout=120) as r:
            for line in r:
                if line.startswith(b"data:"):
                    if first is None:
                        first = time.perf_counter() - t0
                    n += 1
        if first is None:
            raise RuntimeError(
                f"no SSE chunks received from {addr} (non-SSE response?)")
        rates.append(n / (time.perf_counter() - t0))
        ttfc.append(1000 * first)
    return {
        "chunks_per_s": round(statistics.median(rates), 1),
        "first_chunk_ms": round(statistics.median(ttfc), 2),
        "chunks": chunks,
    }


def main() -> dict:
    import ray_tpu
    from ray_tpu import serve

    ray_tpu.init(num_cpus=4, ignore_reinit_error=True)
    serve.run(build_noop_app(), name="microbench", route_prefix="/")
    handle = serve.get_app_handle("microbench").options(method_name="noop")
    addr = serve.http_address()
    # warmup: replica cold start + route table
    handle.remote().result(timeout=60)
    with urllib.request.urlopen(addr + "/", timeout=60) as r:
        r.read()

    out = {
        "handle_noop": bench_handle_noop(handle),
        "http_noop": bench_http_noop(addr),
        "streaming": bench_streaming(addr),
    }
    serve.shutdown()
    ray_tpu.shutdown()
    return out


if __name__ == "__main__":
    print(json.dumps(main()))

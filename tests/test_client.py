"""Ray Client (`ray://`): remote drivers proxied through a cluster-side
server (reference ``python/ray/util/client/__init__.py:200``)."""

import subprocess
import sys
import textwrap

import pytest

import ray_tpu


@pytest.fixture(autouse=True)
def _cluster(ray_cluster):
    yield


def test_client_mode_end_to_end():
    """A SEPARATE python process connects via ray:// and uses the normal
    API: tasks, puts/gets, ref args, actors, named actors, wait."""
    from ray_tpu.util.client import ClientServer

    server = ClientServer(host="127.0.0.1", port=0)
    try:
        # a named actor created cluster-side, visible to the client
        @ray_tpu.remote
        class Registry:
            def __init__(self):
                self.items = []

            def add(self, x):
                self.items.append(x)
                return len(self.items)

        reg = Registry.options(name="client_registry", lifetime="detached").remote()
        assert ray_tpu.get(reg.add.remote("seed"), timeout=60) == 1

        code = textwrap.dedent(f"""
            import ray_tpu
            ray_tpu.init(address="ray://{server.address}")

            @ray_tpu.remote
            def double(x):
                return x * 2

            # tasks + ref args
            a = double.remote(21)
            b = double.remote(a)
            assert ray_tpu.get(b, timeout=60) == 84

            # put/get + wait
            ref = ray_tpu.put({{"k": [1, 2, 3]}})
            assert ray_tpu.get(ref, timeout=60) == {{"k": [1, 2, 3]}}
            ready, not_ready = ray_tpu.wait([a, b], num_returns=2, timeout=60)
            assert len(ready) == 2 and not not_ready

            # client-created actor
            @ray_tpu.remote
            class Counter:
                def __init__(self):
                    self.n = 0
                def inc(self, k):
                    self.n += k
                    return self.n
            c = Counter.remote()
            assert ray_tpu.get(c.inc.remote(5), timeout=60) == 5
            assert ray_tpu.get(c.inc.remote(2), timeout=60) == 7

            # named actor created by the CLUSTER driver
            reg = ray_tpu.get_actor("client_registry")
            assert ray_tpu.get(reg.add.remote("from-client"), timeout=60) == 2

            # error propagation
            @ray_tpu.remote(max_retries=0)
            def boom():
                raise ValueError("client boom")
            try:
                ray_tpu.get(boom.remote(), timeout=60)
                raise SystemExit("no error raised")
            except ValueError as e:
                assert "client boom" in str(e)

            ray_tpu.shutdown()
            print("CLIENT_OK")
        """)
        proc = subprocess.run([sys.executable, "-c", code], capture_output=True,
                              text=True, timeout=60, cwd="/root/repo")
        assert "CLIENT_OK" in proc.stdout, proc.stderr[-2000:]

        # cluster-side state mutated by the client is visible here
        assert ray_tpu.get(reg.add.remote("post"), timeout=60) == 3
    finally:
        server.stop()
        # detached actors outlive handles: kill explicitly or the held CPU
        # starves every later test in the shared cluster
        try:
            ray_tpu.kill(reg)
        except Exception:
            pass


def test_client_streaming_generator():
    """num_returns="streaming" works over ray://: the proxy holds the
    real ObjectRefGenerator, the client iterates refs one round trip at
    a time, and close() cancels the producer."""
    from ray_tpu.util.client import ClientServer

    server = ClientServer(host="127.0.0.1", port=0)
    try:
        code = textwrap.dedent(f"""
            import ray_tpu
            ray_tpu.init(address="ray://{server.address}")

            @ray_tpu.remote(num_returns="streaming")
            def counter(n):
                for i in range(n):
                    yield i * 10

            gen = counter.remote(5)
            values = [ray_tpu.get(ref, timeout=60) for ref in gen]
            assert values == [0, 10, 20, 30, 40], values

            # early close: iteration stops, no error
            gen2 = counter.remote(1000)
            first = ray_tpu.get(next(gen2), timeout=60)
            assert first == 0
            gen2.close()

            # actor streaming method over the client boundary
            @ray_tpu.remote
            class Streamer:
                def gen(self, n):
                    for i in range(n):
                        yield i + 100
            st = Streamer.remote()
            g = st.gen.options(num_returns="streaming").remote(3)
            vals = [ray_tpu.get(r, timeout=60) for r in g]
            assert vals == [100, 101, 102], vals

            ray_tpu.shutdown()
            print("STREAM_OK")
        """)
        proc = subprocess.run([sys.executable, "-c", code], capture_output=True,
                              text=True, timeout=60, cwd="/root/repo")
        assert "STREAM_OK" in proc.stdout, proc.stderr[-2000:]
    finally:
        server.stop()


def test_client_actor_method_concurrency_group():
    """Regression (round-5 breakage): ``ActorMethod.remote`` always passes
    ``concurrency_group=`` to ``submit_actor_task`` — the client worker
    must accept AND forward it, including an explicit group selected via
    ``.options(concurrency_group=...)``."""
    from ray_tpu.util.client import ClientServer

    server = ClientServer(host="127.0.0.1", port=0)
    try:
        code = textwrap.dedent(f"""
            import ray_tpu
            ray_tpu.init(address="ray://{server.address}")

            @ray_tpu.remote(concurrency_groups={{"io": 2}})
            class Grouped:
                def plain(self):
                    return "ok"
                def fetch(self):
                    return "io-ok"

            g = Grouped.remote()
            assert ray_tpu.get(g.plain.remote(), timeout=60) == "ok"
            assert ray_tpu.get(
                g.fetch.options(concurrency_group="io").remote(),
                timeout=60) == "io-ok"
            ray_tpu.shutdown()
            print("CG_OK")
        """)
        proc = subprocess.run([sys.executable, "-c", code], capture_output=True,
                              text=True, timeout=60, cwd="/root/repo")
        assert "CG_OK" in proc.stdout, proc.stderr[-2000:]
    finally:
        server.stop()


def test_client_crash_reaps_session():
    """A client that dies WITHOUT disconnecting stops pinging; the proxy
    reaps the session: its actors are killed and its job finishes
    (reference: client reconnect-grace expiry)."""
    import time

    from ray_tpu.core.config import get_config
    from ray_tpu.core.worker import global_worker
    from ray_tpu.util.client import ClientServer

    old = get_config().client_session_timeout_s
    get_config().client_session_timeout_s = 3.0
    server = ClientServer(host="127.0.0.1", port=0)
    try:
        code = textwrap.dedent(f"""
            import os
            import ray_tpu
            ray_tpu.init(address="ray://{server.address}")

            @ray_tpu.remote
            class Held:
                def ping(self):
                    return "alive"

            h = Held.remote()
            assert ray_tpu.get(h.ping.remote(), timeout=60) == "alive"
            print("ACTOR_UP")
            os._exit(1)  # crash: no disconnect, no more pings
        """)
        proc = subprocess.run([sys.executable, "-c", code], capture_output=True,
                              text=True, timeout=60, cwd="/root/repo")
        assert "ACTOR_UP" in proc.stdout, proc.stderr[-2000:]

        # the per-client job was registered
        worker = global_worker()
        jobs = worker._gcs_call("GetAllJobs", {})["jobs"]
        client_jobs = [j for j in jobs
                       if str(j.get("driver_address", "")).startswith("ray-client:")]
        assert client_jobs, jobs

        # after the timeout, the session is reaped: actor dead, job done
        deadline = time.time() + 30
        while time.time() < deadline:
            actors = worker._gcs_call("ListActors", {}).get("actors", [])
            held = [a for a in actors
                    if a.get("class_name") == "Held" and a.get("state") == "ALIVE"]
            jobs = worker._gcs_call("GetAllJobs", {})["jobs"]
            cj = [j for j in jobs
                  if str(j.get("driver_address", "")).startswith("ray-client:")]
            if not held and all(j.get("state") == "FINISHED" for j in cj):
                break
            time.sleep(0.5)
        assert not held, f"session actor survived the reap: {held}"
        assert all(j.get("state") == "FINISHED" for j in cj), cj
    finally:
        get_config().client_session_timeout_s = old
        server.stop()


def test_client_session_expiry_fails_fast():
    """A client partitioned past the session timeout is NOT silently
    resurrected: the proxy rejects its next call with 'session expired'
    instead of letting it run against destroyed state."""
    from ray_tpu.core.config import get_config
    from ray_tpu.util.client import ClientServer

    cfg = get_config()
    old_t, old_p = cfg.client_session_timeout_s, cfg.client_ping_interval_s
    cfg.client_session_timeout_s = 2.0
    cfg.client_ping_interval_s = 30.0  # the client will not ping in time
    server = ClientServer(host="127.0.0.1", port=0)
    try:
        code = textwrap.dedent(f"""
            import time
            import ray_tpu
            ray_tpu.init(address="ray://{server.address}")
            ray_tpu.put(1)
            time.sleep(7)  # reaped server-side meanwhile
            try:
                ray_tpu.put(2)
                raise SystemExit("no error raised")
            except Exception as e:
                assert "session expired" in str(e), str(e)
            print("EXPIRED_OK")
        """)
        proc = subprocess.run([sys.executable, "-c", code], capture_output=True,
                              text=True, timeout=60, cwd="/root/repo")
        assert "EXPIRED_OK" in proc.stdout, proc.stderr[-2000:]
    finally:
        cfg.client_session_timeout_s = old_t
        cfg.client_ping_interval_s = old_p
        server.stop()

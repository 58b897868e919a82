"""Actor tests. Mirrors reference ``python/ray/tests/test_actor.py`` basics."""

import pytest

import ray_tpu


@pytest.fixture(autouse=True)
def _cluster(ray_cluster):
    yield


@ray_tpu.remote
class Counter:
    def __init__(self, start=0):
        self.n = start

    def incr(self, k=1):
        self.n += k
        return self.n

    def read(self):
        return self.n


def test_actor_create_and_call():
    c = Counter.remote()
    assert ray_tpu.get(c.incr.remote(), timeout=60) == 1
    assert ray_tpu.get(c.incr.remote(5), timeout=60) == 6


def test_actor_ordering():
    c = Counter.remote()
    refs = [c.incr.remote() for _ in range(20)]
    assert ray_tpu.get(refs, timeout=60) == list(range(1, 21))


def test_actor_constructor_args():
    c = Counter.remote(100)
    assert ray_tpu.get(c.read.remote(), timeout=60) == 100


def test_two_actors_independent():
    a, b = Counter.remote(), Counter.remote()
    ray_tpu.get([a.incr.remote(), a.incr.remote(), b.incr.remote()], timeout=60)
    assert ray_tpu.get(a.read.remote(), timeout=60) == 2
    assert ray_tpu.get(b.read.remote(), timeout=60) == 1


def test_named_actor():
    # the creator's handle must stay alive: non-detached named actors are
    # GC'd with their creator's handles (reference actor.py lifetime rules)
    creator_handle = Counter.options(name="test_named_counter").remote(7)
    h = ray_tpu.get_actor("test_named_counter")
    assert ray_tpu.get(h.read.remote(), timeout=60) == 7
    del creator_handle


def test_named_actor_gc_on_handle_drop():
    Counter.options(name="test_named_gc").remote(1)
    import gc, time

    gc.collect()
    # death removes the name from the GCS registry → get_actor raises
    for _ in range(100):
        try:
            ray_tpu.get_actor("test_named_gc")
        except ValueError:
            break
        time.sleep(0.1)
    else:
        raise AssertionError("named actor not reclaimed after handle drop")


def test_actor_handle_passing():
    c = Counter.remote()

    @ray_tpu.remote
    def use(handle):
        return ray_tpu.get(handle.incr.remote(10), timeout=30)

    assert ray_tpu.get(use.remote(c), timeout=60) == 10
    assert ray_tpu.get(c.read.remote(), timeout=60) == 10


def test_actor_method_error():
    @ray_tpu.remote
    class Bad:
        def fail(self):
            raise RuntimeError("actor method error")

    b = Bad.remote()
    with pytest.raises(RuntimeError):
        ray_tpu.get(b.fail.remote(), timeout=60)


def test_kill_actor():
    c = Counter.remote()
    ray_tpu.get(c.incr.remote(), timeout=60)
    ray_tpu.kill(c)
    import time

    with pytest.raises(ray_tpu.exceptions.RayTpuError):
        for _ in range(50):
            ray_tpu.get(c.incr.remote(), timeout=30)
            time.sleep(0.1)


def test_concurrency_groups(ray_cluster):
    """Named per-method concurrency pools (reference
    concurrency_group_manager.cc): an "io" group with 2 permits runs two
    io calls concurrently while the default pool (max_concurrency=1)
    stays serialized, and groups never contend with each other."""
    import time

    import ray_tpu

    @ray_tpu.remote(concurrency_groups={"io": 2, "compute": 1})
    class Worker:
        def __init__(self):
            self.active = {"io": 0, "default": 0}
            self.peak = {"io": 0, "default": 0}
            import threading

            self.lock = threading.Lock()

        def _enter(self, group):
            with self.lock:
                self.active[group] += 1
                self.peak[group] = max(self.peak[group], self.active[group])

        def _exit(self, group):
            with self.lock:
                self.active[group] -= 1

        @ray_tpu.method(concurrency_group="io")
        def io_call(self):
            self._enter("io")
            time.sleep(0.4)
            self._exit("io")
            return "io"

        def default_call(self):
            self._enter("default")
            time.sleep(0.2)
            self._exit("default")
            return "d"

        def peaks(self):
            return dict(self.peak)

    w = Worker.remote()
    t0 = time.monotonic()
    refs = [w.io_call.remote() for _ in range(4)]
    refs += [w.default_call.remote() for _ in range(2)]
    out = ray_tpu.get(refs, timeout=60)
    wall = time.monotonic() - t0
    assert out == ["io"] * 4 + ["d"] * 2
    peaks = ray_tpu.get(w.peaks.remote(), timeout=60)
    # The peak counters are the precise check: the io pool reached
    # exactly its 2 permits while the default pool stayed serialized.
    # (No wall-clock assertion: dispatch overhead on the 1-core CI host
    # dwarfs the 0.4s sleeps.)
    assert peaks["io"] == 2, peaks
    assert peaks["default"] == 1, peaks
    del wall

    # call-time group override routes into the io pool
    r = w.default_call.options(concurrency_group="io").remote()
    assert ray_tpu.get(r, timeout=60) == "d"

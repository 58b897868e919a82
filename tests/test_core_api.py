"""Core API integration tests on a shared local cluster.

Mirrors the reference's ``python/ray/tests/test_basic.py`` family.
"""

import numpy as np
import pytest

import ray_tpu


@pytest.fixture(autouse=True)
def _cluster(ray_cluster):
    yield


def test_simple_task():
    @ray_tpu.remote
    def f(x):
        return x + 1

    assert ray_tpu.get(f.remote(1), timeout=60) == 2


def test_task_chaining():
    @ray_tpu.remote
    def f(x):
        return x + 1

    ref = f.remote(0)
    for _ in range(4):
        ref = f.remote(ref)
    assert ray_tpu.get(ref, timeout=60) == 5


def test_put_get_roundtrip():
    for value in [1, "abc", {"k": [1, 2]}, None]:
        assert ray_tpu.get(ray_tpu.put(value), timeout=30) == value


def test_large_object_via_shm():
    arr = np.random.rand(500_000).astype(np.float32)
    ref = ray_tpu.put(arr)
    np.testing.assert_array_equal(ray_tpu.get(ref, timeout=30), arr)


def test_large_arg_and_return():
    @ray_tpu.remote
    def double(x):
        return x * 2

    arr = np.ones(500_000, dtype=np.float32)
    out = ray_tpu.get(double.remote(arr), timeout=60)
    np.testing.assert_array_equal(out, arr * 2)


def test_multiple_returns():
    @ray_tpu.remote(num_returns=2)
    def two():
        return 1, 2

    a, b = two.remote()
    assert ray_tpu.get([a, b], timeout=60) == [1, 2]


def test_kwargs():
    @ray_tpu.remote
    def f(a, b=0, c=0):
        return a + b + c

    assert ray_tpu.get(f.remote(1, c=5), timeout=60) == 6


def test_error_propagation():
    @ray_tpu.remote
    def boom():
        raise KeyError("missing")

    with pytest.raises(KeyError):
        ray_tpu.get(boom.remote(), timeout=60)


def test_error_type_preserved():
    @ray_tpu.remote
    def boom():
        raise ValueError("v")

    with pytest.raises(ray_tpu.RayTaskError):
        ray_tpu.get(boom.remote(), timeout=60)


def test_wait():
    @ray_tpu.remote
    def quick(i):
        return i

    refs = [quick.remote(i) for i in range(8)]
    ready, not_ready = ray_tpu.wait(refs, num_returns=8, timeout=60)
    assert len(ready) == 8 and not not_ready


def test_nested_tasks():
    @ray_tpu.remote
    def inner(x):
        return x * 10

    @ray_tpu.remote
    def outer(x):
        return ray_tpu.get(inner.remote(x), timeout=30) + 1

    assert ray_tpu.get(outer.remote(4), timeout=60) == 41


def test_ref_passed_to_task():
    @ray_tpu.remote
    def consume(x):
        return x + 1

    ref = ray_tpu.put(10)
    assert ray_tpu.get(consume.remote(ref), timeout=60) == 11


def test_cluster_resources():
    res = ray_tpu.cluster_resources()
    assert res.get("CPU", 0) >= 4


def test_runtime_env_env_vars():
    """Tasks with runtime_env={"env_vars"} run in workers started with
    those vars (reference: runtime_env plugin env_vars; worker_pool
    runtime-env-hash matching)."""
    import os

    @ray_tpu.remote
    def read_env():
        return os.environ.get("RAY_TPU_TEST_FLAVOR", "unset")

    assert ray_tpu.get(read_env.remote(), timeout=60) == "unset"
    tagged = read_env.options(runtime_env={"env_vars": {"RAY_TPU_TEST_FLAVOR": "special"}})
    assert ray_tpu.get(tagged.remote(), timeout=60) == "special"
    # default-env tasks must not land on the special worker
    assert ray_tpu.get(read_env.remote(), timeout=60) == "unset"


def test_runtime_env_actor():
    import os

    @ray_tpu.remote
    class EnvActor:
        def flavor(self):
            return os.environ.get("RAY_TPU_TEST_FLAVOR", "unset")

    a = EnvActor.options(runtime_env={"env_vars": {"RAY_TPU_TEST_FLAVOR": "actorenv"}}).remote()
    assert ray_tpu.get(a.flavor.remote(), timeout=60) == "actorenv"


def test_cancel_queued_running_and_force(ray_cluster):
    """ray_tpu.cancel (reference _private/worker.py:3086): a queued task
    fails with TaskCancelledError without running; a running task is
    interrupted at its next bytecode; force=True kills a hard-blocked
    worker — and a cancelled task is never retried."""
    import time

    import pytest as _pytest

    import ray_tpu
    from ray_tpu import TaskCancelledError

    # -- running task: interrupted at the next bytecode ------------------
    @ray_tpu.remote(max_retries=3)
    def spin():
        t0 = time.time()
        while time.time() - t0 < 60:
            sum(range(1000))  # plenty of bytecode boundaries
        return "finished"

    ref = spin.remote()
    time.sleep(2.0)  # let it lease + start
    ray_tpu.cancel(ref)
    with _pytest.raises(TaskCancelledError):
        ray_tpu.get(ref, timeout=60)

    # -- queued task: dropped before it ever runs ------------------------
    @ray_tpu.remote(num_cpus=0)
    class Gate:
        def __init__(self):
            self.started = 0
            self.open = False

        def arrive(self):
            self.started += 1

        def count(self):
            return self.started

        def release(self):
            self.open = True

        def is_open(self):
            return self.open

    gate = Gate.remote()
    n_cpus = int(ray_tpu.cluster_resources().get("CPU", 4))

    @ray_tpu.remote(num_cpus=1)
    def blocker(g):
        ray_tpu.get(g.arrive.remote(), timeout=60)
        while not ray_tpu.get(g.is_open.remote(), timeout=60):
            time.sleep(0.05)
        return "done"

    @ray_tpu.remote(num_cpus=1)
    def never():
        return "ran"

    # hold EVERY cpu; wait until all blockers are confirmed running
    blockers = [blocker.remote(gate) for _ in range(n_cpus)]
    # generous: worker cold-start under full-suite load on 1 core
    deadline = time.time() + 120
    while ray_tpu.get(gate.count.remote(), timeout=60) < n_cpus:
        assert time.time() < deadline, "blockers never started"
        time.sleep(0.05)
    queued = never.remote()   # no CPU free: must queue
    time.sleep(0.3)
    ray_tpu.cancel(queued)
    ray_tpu.get(gate.release.remote(), timeout=60)
    with _pytest.raises(TaskCancelledError):
        ray_tpu.get(queued, timeout=30)
    assert ray_tpu.get(blockers[0], timeout=60) == "done"

    # -- force: a worker hard-blocked in a C call dies, no retry ---------
    @ray_tpu.remote(max_retries=2)
    def hard_block():
        time.sleep(120)  # C-level block: async exc can't land
        return "never"

    ref = hard_block.remote()
    time.sleep(2.0)
    ray_tpu.cancel(ref, force=True)
    with _pytest.raises(TaskCancelledError):
        ray_tpu.get(ref, timeout=60)

    # put objects are not cancellable
    with _pytest.raises(ValueError, match="task returns"):
        ray_tpu.cancel(ray_tpu.put(1))

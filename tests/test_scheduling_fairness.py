"""Lease-admission fairness: actor creation must not be starved by task load.

Regression tests for the round-2 flake (`test_dag` executor loops timing
out under full-suite load): the raylet's resource admission is now a
priority+FIFO queue (`raylet._acquire_resources_queued`), so a flood of
task leases can never outrace a parked actor-creation lease.
"""

import time

import pytest

import ray_tpu


@pytest.fixture(autouse=True)
def _cluster(ray_cluster):
    yield


def test_actor_creation_under_task_flood():
    @ray_tpu.remote
    def busy(i):
        time.sleep(0.05)
        return i

    # Saturate the node with task leases (several scheduling categories so
    # multiple pipelines hold workers concurrently).
    refs = [busy.remote(i) for i in range(120)]
    refs += [busy.options(max_retries=0).remote(i) for i in range(120)]

    @ray_tpu.remote
    class A:
        def ping(self):
            return "pong"

    t0 = time.monotonic()
    actors = [A.remote() for _ in range(3)]
    out = [ray_tpu.get(a.ping.remote(), timeout=60) for a in actors]
    creation_s = time.monotonic() - t0
    assert out == ["pong"] * 3
    # Actor creation goes to the head of the admission queue: it must beat
    # the ~10s+ task backlog by a wide margin.
    assert creation_s < 45.0, f"actor creation took {creation_s:.1f}s under task flood"
    assert ray_tpu.get(refs, timeout=60) == list(range(120)) * 2


def test_dag_compiles_under_task_flood():
    """The exact round-2 flake shape: compile a DAG (actor creation +
    __ray_call__ loop install) while tasks churn."""
    from ray_tpu.dag import InputNode, MultiOutputNode

    @ray_tpu.remote
    def churn(i):
        time.sleep(0.02)
        return i

    refs = [churn.remote(i) for i in range(150)]

    @ray_tpu.remote
    class Worker:
        def double(self, x):
            return x * 2

    w = Worker.remote()
    with InputNode() as inp:
        dag = MultiOutputNode([w.double.bind(inp)])
    compiled = dag.experimental_compile()
    try:
        assert compiled.execute(21) == 42
    finally:
        compiled.teardown()
    ray_tpu.get(refs, timeout=60)

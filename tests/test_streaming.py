"""Streaming generators (``num_returns="streaming"``).

Mirrors the reference's ``python/ray/tests/test_streaming_generator.py``:
items are consumable BEFORE the task finishes, errors propagate at the
failing index, backpressure pauses the producer, and a worker death
mid-stream retries the generator.
"""

import time

import numpy as np
import pytest

import ray_tpu


@pytest.fixture(autouse=True)
def _cluster(ray_cluster):
    yield


def test_basic_streaming_task():
    @ray_tpu.remote(num_returns="streaming")
    def gen(n):
        for i in range(n):
            yield i * 10

    out = [ray_tpu.get(ref, timeout=60) for ref in gen.remote(5)]
    assert out == [0, 10, 20, 30, 40]


def test_items_arrive_before_task_finishes():
    @ray_tpu.remote(num_returns="streaming")
    def slow_gen():
        yield "first"
        time.sleep(5.0)
        yield "second"

    g = slow_gen.remote()
    t0 = time.monotonic()
    first = ray_tpu.get(next(g), timeout=30)
    elapsed = time.monotonic() - t0
    assert first == "first"
    # The first item must be visible well before the 5s sleep completes.
    assert elapsed < 4.0
    assert ray_tpu.get(next(g), timeout=30) == "second"
    with pytest.raises(StopIteration):
        next(g)


def test_streaming_empty_generator():
    @ray_tpu.remote(num_returns="streaming")
    def empty():
        return
        yield  # pragma: no cover

    assert list(empty.remote()) == []


def test_streaming_large_items_via_shm():
    @ray_tpu.remote(num_returns="streaming")
    def arrays():
        for i in range(3):
            yield np.full(300_000, i, dtype=np.float32)

    for i, ref in enumerate(arrays.remote()):
        arr = ray_tpu.get(ref, timeout=60)
        assert arr.shape == (300_000,)
        assert float(arr[0]) == float(i)


def test_streaming_error_mid_generation():
    @ray_tpu.remote(num_returns="streaming", max_retries=0)
    def bad_gen():
        yield 1
        yield 2
        raise ValueError("boom at index 2")

    g = bad_gen.remote()
    assert ray_tpu.get(next(g), timeout=60) == 1
    assert ray_tpu.get(next(g), timeout=60) == 2
    with pytest.raises(ValueError, match="boom"):
        next(g)


def test_streaming_not_a_generator():
    @ray_tpu.remote(num_returns="streaming", max_retries=0)
    def not_gen():
        return 42

    g = not_gen.remote()
    with pytest.raises(TypeError):
        next(g)


def test_streaming_actor_method():
    @ray_tpu.remote
    class Producer:
        def tokens(self, n):
            for i in range(n):
                yield f"tok{i}"

    p = Producer.remote()
    out = [ray_tpu.get(r, timeout=60) for r in p.tokens.options(num_returns="streaming").remote(4)]
    assert out == ["tok0", "tok1", "tok2", "tok3"]


def test_streaming_async_actor_generator():
    @ray_tpu.remote
    class AsyncProducer:
        async def tokens(self, n):
            import asyncio

            for i in range(n):
                await asyncio.sleep(0.01)
                yield i

    p = AsyncProducer.remote()
    out = [ray_tpu.get(r, timeout=60) for r in p.tokens.options(num_returns="streaming").remote(3)]
    assert out == [0, 1, 2]


def test_streaming_backpressure():
    """With backpressure=2 the producer must pause until items are consumed."""

    @ray_tpu.remote(num_returns="streaming", _generator_backpressure_num_objects=2)
    def gen():
        for i in range(6):
            yield (i, time.time())

    g = gen.remote()
    refs = []
    # Let the producer run ahead; it may produce at most ~backpressure items.
    time.sleep(2.0)
    t_consume_start = time.time()
    items = [ray_tpu.get(r, timeout=60) for r in g]
    assert [i for i, _ in items] == list(range(6))
    # Items beyond the backpressure window must be produced AFTER we began
    # consuming (the producer was paused during the 2s sleep).
    produced_late = [i for i, ts in items if ts >= t_consume_start]
    assert any(i >= 3 for i in produced_late), items


def test_streaming_retry_mid_items():
    """Kill the worker mid-stream: the generator retries and the consumer
    still sees every item (at-least-once re-report, deterministic ids)."""
    import os

    marker = "/tmp/raytpu_test_stream_mid_%d" % os.getpid()

    @ray_tpu.remote(num_returns="streaming", max_retries=2)
    def fragile(marker):
        for i in range(5):
            if i == 3 and not os.path.exists(marker):
                open(marker, "w").close()
                os._exit(1)
            yield i

    try:
        out = [ray_tpu.get(r, timeout=60) for r in fragile.remote(marker)]
    finally:
        if os.path.exists(marker):
            os.unlink(marker)
    assert out == [0, 1, 2, 3, 4]


def test_streaming_bad_args_surface_error():
    """Errors BEFORE the generator starts (wrong arity) must fail the
    stream, not silently complete it empty."""

    @ray_tpu.remote(num_returns="streaming", max_retries=0)
    def gen(n):
        yield n

    g = gen.remote(1, 2, 3)  # wrong arity -> TypeError before iteration
    with pytest.raises(TypeError):
        next(g)


def test_streaming_abandoned_consumer_cancels_producer():
    """Dropping the generator mid-stream cancels the (backpressured)
    producer instead of leaving it blocked forever."""

    @ray_tpu.remote(num_returns="streaming", _generator_backpressure_num_objects=1)
    def gen(path):
        import os

        try:
            for i in range(10_000):
                yield i
        finally:
            open(path, "w").write("closed")

    import os
    import tempfile

    path = tempfile.mktemp(prefix="raytpu_stream_cancel_")
    g = gen.remote(path)
    assert ray_tpu.get(next(g), timeout=60) == 0
    g.close()  # abandon
    deadline = time.monotonic() + 30
    while not os.path.exists(path) and time.monotonic() < deadline:
        time.sleep(0.2)
    try:
        assert os.path.exists(path), "producer was not cancelled within 30s"
    finally:
        if os.path.exists(path):
            os.unlink(path)


def test_streaming_state_released_after_exhaustion():
    @ray_tpu.remote(num_returns="streaming")
    def gen():
        yield 1

    from ray_tpu.core.worker import global_worker

    g = gen.remote()
    tid = g.task_id
    assert list(g) is not None
    assert tid not in global_worker()._streams


def test_streaming_async_consumption():
    """ObjectRefGenerator supports `async for` (used by Serve/LLM)."""
    import asyncio

    @ray_tpu.remote(num_returns="streaming")
    def gen():
        for i in range(4):
            yield i

    async def consume():
        out = []
        async for ref in gen.remote():
            out.append(ray_tpu.get(ref, timeout=60))
        return out

    assert asyncio.run(consume()) == [0, 1, 2, 3]


def test_streaming_abandon_drops_refcounter_entries():
    """Releasing a partially-consumed stream must also drop the
    owned-object refcounter bookkeeping for the unconsumed items
    (regression: each abandoned stream leaked refcounter entries)."""
    import time

    from ray_tpu.core.ids import ObjectID, TaskID
    from ray_tpu.core.worker import global_worker

    @ray_tpu.remote(num_returns="streaming")
    def gen():
        for i in range(8):
            yield i

    w = global_worker()
    g = gen.remote()
    tid = g.task_id
    assert ray_tpu.get(next(g), timeout=60) == 0
    # Let a few more items arrive at the owner before abandoning.
    deadline = time.time() + 30
    while time.time() < deadline:
        s = w._streams.get(tid)
        if s is not None and s.num_items >= 4:
            break
        time.sleep(0.05)
    g.close()
    unconsumed = [ObjectID.for_task_return(TaskID(tid), i + 1) for i in range(1, 8)]
    # Retry briefly: an in-flight ReportGeneratorItem racing the close drops
    # its entry microseconds after the handler's post-store re-check.
    deadline = time.time() + 10
    while time.time() < deadline and any(w.refcounter.has_ref(o) for o in unconsumed):
        time.sleep(0.05)
    assert not any(w.refcounter.has_ref(o) for o in unconsumed)

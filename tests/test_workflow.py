"""Durable workflows: checkpointed steps, crash resume, exactly-once.

Reference surface: ``python/ray/workflow/tests/test_basic_workflows.py``
(run/resume/get_output/list_all semantics).
"""

import os

import pytest

from ray_tpu import workflow


def test_workflow_runs_dag_and_persists_output(ray_cluster, tmp_path):
    def load():
        return [1, 2, 3]

    def double(xs):
        return [2 * x for x in xs]

    def total(xs):
        return sum(xs)

    dag = workflow.step(total)(workflow.step(double)(workflow.step(load)()))
    result = workflow.run(dag, workflow_id="wf-basic", storage=str(tmp_path))
    assert result == 12
    assert workflow.get_output("wf-basic", storage=str(tmp_path)) == 12
    assert workflow.get_status("wf-basic", storage=str(tmp_path)) == "SUCCESSFUL"
    assert ("wf-basic", "SUCCESSFUL") in workflow.list_all(storage=str(tmp_path))


def test_workflow_resume_skips_completed_steps(ray_cluster, tmp_path):
    """A step that crashed mid-workflow is retried on resume; steps that
    already checkpointed must NOT re-execute (exactly-once side effects)."""
    marker_dir = tmp_path / "markers"
    marker_dir.mkdir()

    def effect(name):
        # counts executions via filesystem side effect
        path = marker_dir / name
        with open(path, "a") as f:
            f.write("x")
        return name

    def fragile(dep):
        if not os.path.exists(marker_dir / "fixed"):
            raise RuntimeError("transient failure")
        return dep + "-done"

    dag = workflow.step(fragile)(workflow.step(effect)("a"))
    with pytest.raises(RuntimeError, match="transient"):
        workflow.run(dag, workflow_id="wf-crash", storage=str(tmp_path))
    assert workflow.get_status("wf-crash", storage=str(tmp_path)) == "FAILED"
    assert (marker_dir / "a").stat().st_size == 1  # step "a" ran once

    (marker_dir / "fixed").touch()
    result = workflow.resume("wf-crash", storage=str(tmp_path))
    assert result == "a-done"
    assert (marker_dir / "a").stat().st_size == 1  # NOT re-executed on resume
    assert workflow.get_status("wf-crash", storage=str(tmp_path)) == "SUCCESSFUL"


def test_workflow_diamond_shares_upstream(ray_cluster, tmp_path):
    """A diamond DAG evaluates the shared upstream once (memoized) and
    checkpoints each step separately."""
    calls = tmp_path / "calls"

    def src():
        with open(calls, "a") as f:
            f.write("s")
        return 10

    def left(x):
        return x + 1

    def right(x):
        return x + 2

    def join(a, b):
        return a * b

    shared = workflow.step(src)()
    dag = workflow.step(join)(workflow.step(left)(shared), workflow.step(right)(shared))
    assert workflow.run(dag, workflow_id="wf-diamond", storage=str(tmp_path)) == 11 * 12
    assert calls.stat().st_size == 1


def test_workflow_nested_container_steps_resolve(ray_cluster, tmp_path):
    """StepNodes nested in lists/dicts are dependencies too."""

    def make(v):
        return v

    def merge(items, named):
        return sum(items) + named["extra"]

    dag = workflow.step(merge)(
        [workflow.step(make)(1), workflow.step(make)(2)],
        {"extra": workflow.step(make)(10)},
    )
    assert workflow.run(dag, workflow_id="wf-nested", storage=str(tmp_path)) == 13


def test_workflow_listing_ignores_stray_files(ray_cluster, tmp_path):
    (tmp_path / "README.md").write_text("not a workflow")
    dag = workflow.step(lambda: 1)().options("one")
    workflow.run(dag, workflow_id="wf-real", storage=str(tmp_path))
    listing = workflow.list_all(storage=str(tmp_path))
    assert listing == [("wf-real", "SUCCESSFUL")]
    # read-only status probe must not create directories for unknown ids
    assert workflow.get_status("never-existed", storage=str(tmp_path)) is None
    assert not (tmp_path / "never-existed").exists()


def test_workflow_rerun_same_id_returns_checkpointed(ray_cluster, tmp_path):
    ticks = tmp_path / "ticks"

    def effect():
        with open(ticks, "a") as f:
            f.write("t")
        return 7

    dag = workflow.step(effect)()
    assert workflow.run(dag, workflow_id="wf-idem", storage=str(tmp_path)) == 7
    assert workflow.run(dag, workflow_id="wf-idem", storage=str(tmp_path)) == 7
    assert ticks.stat().st_size == 1  # second run fully served from storage


def test_independent_branches_run_concurrently(ray_cluster, tmp_path):
    """Two independent branches are in flight at the same time: the executor
    schedules every ready step (reference workflow_executor.py:32), not one
    at a time. Read as an order of events, not as an overlap of two sleeps:
    each step says that it has started and then waits, bounded, until the
    other has. A step ends only when both ran at once, however late the
    cluster starts the second one's worker: the two share a scheduling key,
    so while that worker is starting (longer than any sleep, beside busy
    processes) a step that has ended hands ITS worker to the one still
    queued, and two sleeps run one after the other with nothing serialized."""

    @workflow.step
    def meet(tag, other, where):
        import os
        import time

        open(os.path.join(where, tag), "w").close()
        deadline = time.monotonic() + 45.0
        while not os.path.exists(os.path.join(where, other)):
            if time.monotonic() > deadline:
                return tag, False
            time.sleep(0.02)
        return tag, True

    @workflow.step
    def join(a, b):
        return a, b

    import time as _time

    started = tmp_path / "started"
    started.mkdir()
    (tag_a, met_a), (tag_b, met_b) = workflow.run(
        join(meet("a", "b", str(started)), meet("b", "a", str(started))),
        workflow_id=f"wf-par-{_time.time_ns()}", storage=str(tmp_path), step_timeout_s=60.0)
    assert tag_a + tag_b == "ab"
    assert met_a and met_b, "branches serialized: one ended before the other had started"


def test_continuation_extends_workflow(ray_cluster, tmp_path):
    """A step returning workflow.continuation(sub_dag) dynamically extends
    the DAG; the sub-DAG's result becomes the step's result (reference
    workflow.continuation)."""

    @workflow.step
    def double(x):
        return x * 2

    @workflow.step
    def decide(x):
        if x < 10:
            return workflow.continuation(double(x + 3))
        return x

    @workflow.step
    def plus_one(x):
        return x + 1

    dag = plus_one(decide(2))
    out = workflow.run(dag, workflow_id="wf-cont", storage=str(tmp_path))
    assert out == (2 + 3) * 2 + 1  # continuation ran, parent saw its result


def test_recursive_continuations_checkpoint(ray_cluster, tmp_path):
    """Recursion via continuations (the reference's factorial example):
    each level checkpoints in its parent step's namespace."""

    @workflow.step
    def fact(n, acc=1):
        if n <= 1:
            return acc
        return workflow.continuation(fact(n - 1, acc * n))

    out = workflow.run(fact(5), workflow_id="wf-fact", storage=str(tmp_path))
    assert out == 120
    # rerun is fully served from checkpoints
    assert workflow.run(fact(5), workflow_id="wf-fact", storage=str(tmp_path)) == 120


def test_resume_inside_continuation_never_reruns_step_body(ray_cluster, tmp_path):
    """Crash between a step finishing (returning a continuation) and the
    sub-DAG completing: resume continues INSIDE the continuation; the
    step's own side effect happens exactly once."""
    body_runs = tmp_path / "body_runs"
    flaky_flag = tmp_path / "fail_once"
    flaky_flag.write_text("1")

    @workflow.step
    def sub(x):
        if os.path.exists(str(flaky_flag)):
            os.unlink(str(flaky_flag))
            raise RuntimeError("simulated crash inside the continuation")
        return x * 10

    @workflow.step
    def body():
        with open(str(body_runs), "a") as f:
            f.write("x")
        return workflow.continuation(sub(4))

    dag = body()
    with pytest.raises(Exception):
        workflow.run(dag, workflow_id="wf-cont-crash", storage=str(tmp_path))
    out = workflow.resume("wf-cont-crash", storage=str(tmp_path))
    assert out == 40
    assert body_runs.stat().st_size == 1, "step body re-ran on resume"


def test_event_step_unblocks_on_trigger(ray_cluster, tmp_path):
    """wait_for_event parks a step until trigger_event fires; the payload
    checkpoints like any result (reference workflow/event_listener.py)."""
    import threading
    import time as _time

    @workflow.step
    def combine(payload, tag):
        return f"{payload}-{tag}"

    key = f"approval-{_time.time_ns()}"
    dag = combine(workflow.wait_for_event(key), "done")

    def fire():
        _time.sleep(1.0)
        workflow.trigger_event(key, "approved")

    t = threading.Thread(target=fire)
    t.start()
    out = workflow.run(dag, workflow_id="wf-event", storage=str(tmp_path))
    t.join()
    assert out == "approved-done"
    # resume serves the event payload from its checkpoint (no re-listen)
    assert workflow.run(dag, workflow_id="wf-event", storage=str(tmp_path)) == "approved-done"


def test_deep_continuation_chain_is_iterative(ray_cluster, tmp_path):
    """A long tail-continuation chain must not exhaust the driver stack:
    the loop grafts each level into the ONE driver loop (no nested
    executors), and sibling branches keep checkpointing meanwhile."""
    import sys

    @workflow.step
    def count_down(n):
        if n <= 0:
            return "done"
        return workflow.continuation(count_down(n - 1))

    depth = 60
    limit = sys.getrecursionlimit()
    try:
        sys.setrecursionlimit(200)  # far below depth * frames-per-level
        out = workflow.run(count_down(depth), workflow_id="wf-deep",
                           storage=str(tmp_path), step_timeout_s=60)
    finally:
        sys.setrecursionlimit(limit)
    assert out == "done"

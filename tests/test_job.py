"""Job submission + runtime_env.

Reference surfaces: ``dashboard/modules/job/job_manager.py`` (submit,
status FSM, stop, logs) and runtime_env ``working_dir``/``env_vars``
(``python/ray/_private/runtime_env/``).
"""

import os
import textwrap
import time

import pytest

import ray_tpu
from ray_tpu.job import JobStatus, JobSubmissionClient


def test_job_succeeds_and_logs(ray_cluster):
    client = JobSubmissionClient()
    jid = client.submit_job(entrypoint="echo hello-from-job && echo line2")
    status = client.wait_until_terminal(jid, timeout=60)
    assert status == JobStatus.SUCCEEDED
    logs = client.get_job_logs(jid)
    assert "hello-from-job" in logs and "line2" in logs
    jobs = {j.submission_id: j for j in client.list_jobs()}
    assert jobs[jid].status == JobStatus.SUCCEEDED
    assert jobs[jid].end_time >= jobs[jid].start_time > 0


def test_job_failure_reported(ray_cluster):
    client = JobSubmissionClient()
    jid = client.submit_job(entrypoint="python -c 'import sys; sys.exit(3)'")
    assert client.wait_until_terminal(jid, timeout=60) == JobStatus.FAILED
    assert "code 3" in client.get_job_info(jid).message


def test_job_env_vars_and_working_dir(ray_cluster, tmp_path):
    (tmp_path / "helper_mod.py").write_text("VALUE = 'from-working-dir'\n")
    script = textwrap.dedent(
        """
        import os, helper_mod
        print("env:", os.environ["MY_JOB_VAR"])
        print("mod:", helper_mod.VALUE)
        """
    )
    (tmp_path / "main.py").write_text(script)
    client = JobSubmissionClient()
    jid = client.submit_job(
        entrypoint="python main.py",
        runtime_env={"working_dir": str(tmp_path), "env_vars": {"MY_JOB_VAR": "42"}},
    )
    assert client.wait_until_terminal(jid, timeout=60) == JobStatus.SUCCEEDED
    logs = client.get_job_logs(jid)
    assert "env: 42" in logs and "mod: from-working-dir" in logs


def test_job_driver_connects_to_cluster(ray_cluster, tmp_path):
    """The entrypoint is a real cluster driver: it connects via
    RAY_TPU_ADDRESS and runs a remote task on the shared cluster."""
    script = textwrap.dedent(
        """
        import ray_tpu
        ray_tpu.init()  # picks up RAY_TPU_ADDRESS
        @ray_tpu.remote
        def f():
            return "task-ran-on-cluster"
        print(ray_tpu.get(f.remote(), timeout=60))
        ray_tpu.shutdown()
        """
    )
    (tmp_path / "driver.py").write_text(script)
    client = JobSubmissionClient()
    jid = client.submit_job(
        entrypoint="python driver.py", runtime_env={"working_dir": str(tmp_path)}
    )
    status = client.wait_until_terminal(jid, timeout=60)
    logs = client.get_job_logs(jid)
    assert status == JobStatus.SUCCEEDED, logs
    assert "task-ran-on-cluster" in logs


def test_job_stop(ray_cluster):
    client = JobSubmissionClient()
    jid = client.submit_job(entrypoint="sleep 60")
    deadline = time.monotonic() + 30
    while client.get_job_status(jid) == JobStatus.PENDING:
        assert time.monotonic() < deadline
        time.sleep(0.1)
    assert client.stop_job(jid)
    assert client.wait_until_terminal(jid, timeout=30) == JobStatus.STOPPED


def test_task_runtime_env_working_dir(ray_cluster, tmp_path):
    """Per-task runtime_env working_dir: the worker imports modules from it."""
    (tmp_path / "task_helper.py").write_text("def ping():\n    return 'imported'\n")

    @ray_tpu.remote(runtime_env={"working_dir": str(tmp_path)})
    def uses_helper():
        import task_helper

        return task_helper.ping() + ":" + os.path.basename(os.getcwd())

    out = ray_tpu.get(uses_helper.remote(), timeout=60)
    assert out == f"imported:{os.path.basename(tmp_path)}"

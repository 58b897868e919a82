"""Train library: controller/worker-group/report/checkpoint/failure
semantics (reference: python/ray/train/v2/tests/)."""

import os

import pytest

import ray_tpu
from ray_tpu import train
from ray_tpu.train import (
    Checkpoint,
    CheckpointConfig,
    DataParallelTrainer,
    FailureConfig,
    JaxTrainer,
    RunConfig,
    ScalingConfig,
)


def test_single_worker_reports_metrics(ray_cluster, tmp_path):
    def train_fn(config):
        ctx = train.get_context()
        for step in range(3):
            train.report({"step": step, "rank": ctx.get_world_rank()})

    trainer = JaxTrainer(
        train_fn,
        scaling_config=ScalingConfig(num_workers=1),
        run_config=RunConfig(name="t1", storage_path=str(tmp_path)),
    )
    result = trainer.fit()
    assert result.error is None
    assert result.metrics["step"] == 2
    assert len(result.metrics_history) == 3


def test_two_workers_context(ray_cluster, tmp_path):
    def train_fn(config):
        ctx = train.get_context()
        train.report({"world_size": ctx.get_world_size(), "rank": ctx.get_world_rank()})

    trainer = JaxTrainer(
        train_fn,
        scaling_config=ScalingConfig(num_workers=2),
        run_config=RunConfig(name="t2", storage_path=str(tmp_path)),
    )
    result = trainer.fit()
    assert result.error is None
    assert result.metrics["world_size"] == 2
    assert result.metrics["rank"] == 0  # controller keeps rank-0 metrics


def test_checkpoint_roundtrip(ray_cluster, tmp_path):
    def train_fn(config):
        import tempfile

        resumed = train.get_checkpoint()
        start = 0
        if resumed:
            with resumed.as_directory() as d:
                start = int(open(os.path.join(d, "step.txt")).read())
        with tempfile.TemporaryDirectory() as d:
            with open(os.path.join(d, "step.txt"), "w") as f:
                f.write(str(start + 5))
            train.report({"final_step": start + 5}, checkpoint=Checkpoint.from_directory(d))

    run_cfg = RunConfig(
        name="ckpt", storage_path=str(tmp_path),
        checkpoint_config=CheckpointConfig(num_to_keep=2),
    )
    trainer = JaxTrainer(
        train_fn, scaling_config=ScalingConfig(num_workers=1), run_config=run_cfg,
    )
    result = trainer.fit()
    assert result.error is None
    assert result.checkpoint is not None
    with result.checkpoint.as_directory() as d:
        assert open(os.path.join(d, "step.txt")).read() == "5"

    # resume from the produced checkpoint
    trainer2 = JaxTrainer(
        train_fn,
        scaling_config=ScalingConfig(num_workers=1),
        run_config=RunConfig(name="ckpt2", storage_path=str(tmp_path)),
        resume_from_checkpoint=result.checkpoint,
    )
    r2 = trainer2.fit()
    assert r2.error is None
    assert r2.metrics["final_step"] == 10


def test_failure_policy_restarts_group(ray_cluster, tmp_path):
    marker = str(tmp_path / "attempted_once")

    def train_fn(config):
        if not os.path.exists(config["marker"]):
            open(config["marker"], "w").write("x")
            raise RuntimeError("injected first-attempt failure")
        train.report({"ok": 1})

    trainer = JaxTrainer(
        train_fn,
        train_loop_config={"marker": marker},
        scaling_config=ScalingConfig(num_workers=1),
        run_config=RunConfig(
            name="ft", storage_path=str(tmp_path),
            failure_config=FailureConfig(max_failures=1),
        ),
    )
    result = trainer.fit()
    assert result.error is None
    assert result.metrics == {"ok": 1}


def test_train_on_dataset(ray_cluster, tmp_path):
    """datasets= flows to workers as per-rank streaming_split iterators
    (reference: dataset.py:1598 + get_dataset_shard)."""
    from ray_tpu import data

    def train_fn(config):
        shard = train.get_dataset_shard("train")
        seen = sum(batch["id"].shape[0] for batch in shard.iter_batches(batch_size=8))
        train.report({"rows_seen": seen})

    trainer = JaxTrainer(
        train_fn,
        scaling_config=ScalingConfig(num_workers=2),
        run_config=RunConfig(name="ds", storage_path=str(tmp_path)),
        datasets={"train": data.range(64, parallelism=4)},
    )
    result = trainer.fit()
    assert result.error is None
    # split streams partition all 64 rows across the 2 workers
    assert result.metrics["rows_seen"] > 0
    assert result.metrics["rows_seen"] < 64


def test_train_dataset_worker_kill_resume(ray_cluster, tmp_path):
    """Worker dies mid-epoch → whole group restarts with a FRESH stream and
    resumes from the latest checkpoint (VERDICT round 1 #6)."""
    from ray_tpu import data

    marker = str(tmp_path / "killed_once")

    def train_fn(config):
        import os as _os

        resumed = train.get_checkpoint()
        start = 0
        if resumed:
            with resumed.as_directory() as d:
                start = int(open(_os.path.join(d, "start.txt")).read())
        shard = train.get_dataset_shard("train")
        rows = 0
        for batch in shard.iter_batches(batch_size=8):
            rows += batch["id"].shape[0]
            if rows >= 8 and not _os.path.exists(config["marker"]) and start == 0:
                open(config["marker"], "w").write("x")
                import tempfile

                with tempfile.TemporaryDirectory() as d:
                    open(_os.path.join(d, "start.txt"), "w").write("1")
                    train.report({"rows": rows}, checkpoint=Checkpoint.from_directory(d))
                raise RuntimeError("injected mid-epoch death")
        train.report({"rows": rows, "resumed_from": start})

    trainer = JaxTrainer(
        train_fn,
        train_loop_config={"marker": marker},
        scaling_config=ScalingConfig(num_workers=1),
        run_config=RunConfig(
            name="dsft", storage_path=str(tmp_path),
            failure_config=FailureConfig(max_failures=1),
        ),
        datasets={"train": data.range(32, parallelism=4)},
    )
    result = trainer.fit()
    assert result.error is None
    assert result.metrics["resumed_from"] == 1
    assert result.metrics["rows"] == 32  # fresh stream on restart


def test_failure_policy_exhausted(ray_cluster, tmp_path):
    def train_fn(config):
        raise RuntimeError("always fails")

    trainer = JaxTrainer(
        train_fn,
        scaling_config=ScalingConfig(num_workers=1),
        run_config=RunConfig(
            name="fail", storage_path=str(tmp_path),
            failure_config=FailureConfig(max_failures=0),
        ),
    )
    result = trainer.fit()
    assert result.error is not None
    assert "always fails" in str(result.error)


class _CallCountClock:
    """Fake clock for ElasticScalingPolicy: advances one "second" per
    call, so the resize debounce is driven by monitor() call counts
    instead of wall time — full-suite load cannot flake it."""

    def __init__(self):
        self.t = 0.0

    def __call__(self) -> float:
        self.t += 1.0
        return self.t


def test_elastic_scaling_upscale(tmp_path):
    import time
    """Elastic policy (min_workers set): the run starts at the feasible
    size, and when capacity grows mid-run the controller restarts the
    group slice-atomically at the larger size from the latest checkpoint
    (reference v2 scaling_policy ResizeDecision)."""
    import ray_tpu
    from ray_tpu.cluster_utils import Cluster
    from ray_tpu.train import ElasticScalingPolicy

    if ray_tpu.is_initialized():
        ray_tpu.shutdown()
    c = Cluster(initialize_head=True, head_node_args={"num_cpus": 1})
    ray_tpu.init(address=c.address, num_cpus=0)
    try:
        def train_fn(config):
            import os
            import tempfile
            import time

            ctx = train.get_context()
            start = 0
            ckpt = train.get_checkpoint()
            if ckpt is not None:
                with open(os.path.join(ckpt.path, "step.txt")) as f:
                    start = int(f.read())
            for step in range(start, 48):
                d = tempfile.mkdtemp()
                with open(os.path.join(d, "step.txt"), "w") as f:
                    f.write(str(step + 1))
                train.report(
                    {"step": step, "world": ctx.get_world_size()},
                    checkpoint=Checkpoint.from_directory(d),
                )
                time.sleep(0.25)

        scaling = ScalingConfig(num_workers=3, min_workers=1,
                                resources_per_worker={"CPU": 1})
        trainer = DataParallelTrainer(
            train_fn,
            scaling_config=scaling,
            run_config=RunConfig(name="elastic", storage_path=str(tmp_path)),
            scaling_policy=ElasticScalingPolicy(
                scaling, check_interval_s=2.0, clock=_CallCountClock()),
        )

        import threading

        result_box = {}

        def run():
            result_box["result"] = trainer.fit()

        t = threading.Thread(target=run)
        t.start()
        time.sleep(3.0)  # let the 1-worker attempt make progress
        c.add_node(num_cpus=2)  # capacity for 2 more workers
        t.join(timeout=60)
        assert not t.is_alive(), "elastic fit() did not finish"
        result = result_box["result"]
        assert result.error is None, result.error
        worlds = [m["world"] for m in result.metrics_history]
        # started small, resized up to the full 3 once capacity appeared
        assert worlds[0] == 1 and 3 in worlds, worlds
        # steps progressed across the resize (checkpoint resume, not restart)
        steps = [m["step"] for m in result.metrics_history]
        assert steps[-1] == 47 and steps[0] == 0
    finally:
        ray_tpu.shutdown()
        c.shutdown()


def test_elastic_scaling_downscale_on_node_death(tmp_path):
    """Losing a node mid-run shrinks the next attempt to the remaining
    capacity (slice-atomic restart from checkpoint) instead of failing
    the run or waiting for the lost capacity."""
    import time

    import ray_tpu
    from ray_tpu.cluster_utils import Cluster
    from ray_tpu.train import ElasticScalingPolicy

    if ray_tpu.is_initialized():
        ray_tpu.shutdown()
    c = Cluster(initialize_head=True, head_node_args={"num_cpus": 1},
                _system_config={"health_check_failure_threshold": 2})
    n2 = c.add_node(num_cpus=2)
    ray_tpu.init(address=c.address, num_cpus=0)
    try:
        deadline = time.time() + 30
        # The policy sizes the first attempt from what is AVAILABLE, which
        # a node reports after it has registered its total: node2's CPUs
        # must be available, not only counted, so the run STARTS at 3.
        while ray_tpu.available_resources().get("CPU", 0) < 3 and time.time() < deadline:
            time.sleep(0.2)
        assert ray_tpu.available_resources().get("CPU", 0) >= 3
        marks = tmp_path / "in_train_fn"
        marks.mkdir()

        def train_fn(config):
            import os
            import tempfile
            import time as _t

            ctx = train.get_context()
            open(os.path.join(str(marks),
                              f"{ctx.get_world_size()}-{ctx.get_world_rank()}"),
                 "w").close()
            start = 0
            ckpt = train.get_checkpoint()
            if ckpt is not None:
                with open(os.path.join(ckpt.path, "step.txt")) as f:
                    start = int(f.read())
            for step in range(start, 16):
                d = tempfile.mkdtemp()
                with open(os.path.join(d, "step.txt"), "w") as f:
                    f.write(str(step + 1))
                train.report(
                    {"step": step, "world": ctx.get_world_size()},
                    checkpoint=Checkpoint.from_directory(d),
                )
                _t.sleep(0.25)

        scaling = ScalingConfig(num_workers=3, min_workers=1,
                                resources_per_worker={"CPU": 1})
        trainer = DataParallelTrainer(
            train_fn,
            scaling_config=scaling,
            # max_failures bounds a hang, it is not what is asserted.
            # Between the workers' death and the GCS declaring their node
            # dead the controller sizes each restart from a view that
            # still holds the node, and every attempt made against that
            # view is a failure spent (two were seen on a loaded host).
            run_config=RunConfig(name="elastic_down", storage_path=str(tmp_path),
                                 failure_config=FailureConfig(max_failures=5)),
            scaling_policy=ElasticScalingPolicy(
                scaling, check_interval_s=2.0, clock=_CallCountClock()),
        )

        import threading

        box = {}
        t = threading.Thread(target=lambda: box.update(result=trainer.fit()))
        t.start()
        # Wait for EVIDENCE the 3-worker attempt is underway instead of a
        # wall-clock sleep: its first checkpoint in storage AND every rank
        # inside train_fn. Rank 0 alone is not enough: on a slow host it
        # checkpoints while the other ranks' actors are still being
        # created, and a node lost then fails the attempt at its start,
        # before the controller has taken a single world-3 report.
        import glob as _glob

        deadline = time.time() + 120
        while time.time() < deadline:
            if _glob.glob(str(tmp_path / "elastic_down" / "**" / "step.txt"),
                          recursive=True) \
                    and len(_glob.glob(str(marks / "3-*"))) == 3:
                break
            time.sleep(0.2)
        else:
            raise AssertionError("3-worker attempt never checkpointed")
        c.remove_node(n2)  # kill 2 of 3 workers' node
        t.join(timeout=60)
        assert not t.is_alive(), "fit() did not finish after node loss"
        result = box["result"]
        assert result.error is None, result.error
        # Started at 3, ended at 1. That it started at 3 is read from the
        # ranks that were inside train_fn, not from the first report the
        # controller took (replaces `worlds[0] == 3`): a node lost while
        # the controller still waits for the last `run_train_fn` reply
        # fails the attempt "at its start" with every rank already
        # stepping, and the reports of that attempt are never polled.
        ran = set(os.listdir(marks))
        assert {"3-0", "3-1", "3-2", "1-0"} <= ran, ran
        worlds = [m["world"] for m in result.metrics_history]
        assert worlds[-1] == 1 and set(worlds) <= {1, 3}, worlds
        assert result.metrics["step"] == 15, result.metrics
    finally:
        ray_tpu.shutdown()
        c.shutdown()

"""Trainers: the user-facing fit() entry points.

Reference: ``python/ray/train/v2/api/data_parallel_trainer.py:89``
(DataParallelTrainer.fit → TrainController) and
``v2/torch/torch_trainer.py:17``. The TPU-native flagship is
``JaxTrainer``: the train_fn runs as an SPMD program per host; inside it,
parallelism is expressed with ``ray_tpu.parallel`` meshes, not process
groups.
"""

from __future__ import annotations

from .checkpoint import Checkpoint
from .config import Result, RunConfig, ScalingConfig
from .controller import TrainController


class DataParallelTrainer:
    """Generic function trainer: N SPMD workers run ``train_loop_per_worker``.

    ``train_loop_per_worker`` is a closure that drives its own steps and
    calls ``train.report()``; ``train_loop_config`` is its plain dict.
    """

    def __init__(
        self,
        train_loop_per_worker,
        *,
        train_loop_config: dict | None = None,
        scaling_config: ScalingConfig | None = None,
        run_config: RunConfig | None = None,
        resume_from_checkpoint: Checkpoint | None = None,
        datasets: dict | None = None,
        scaling_policy=None,
    ):
        self._train_fn = train_loop_per_worker
        self._train_loop_config = train_loop_config
        self._scaling_config = scaling_config or ScalingConfig()
        self._run_config = run_config or RunConfig()
        self._resume = resume_from_checkpoint
        self._datasets = datasets or {}
        self._scaling_policy = scaling_policy

    def fit(self) -> Result:
        controller = TrainController(
            self._train_fn,
            train_loop_config=self._train_loop_config,
            scaling_config=self._scaling_config,
            run_config=self._run_config,
            resume_from_checkpoint=self._resume,
            datasets=self._datasets,
            scaling_policy=self._scaling_policy,
        )
        return controller.run()


class JaxTrainer(DataParallelTrainer):
    """The flagship TPU trainer (replaces the reference's TorchTrainer).

    Each worker hosts one JAX process; ``init_distributed`` wires
    ``jax.distributed`` for multi-host slices. Model/optimizer sharding is
    the train_fn's business via ``ray_tpu.parallel``.

    Spot-slice resilience: with ``CheckpointConfig(async_save=True,
    every_n_steps=N)`` the train_fn passes its state pytree to
    ``train.report(metrics, state=...)`` — rank 0 commits it atomically
    from a background thread and registers each version with the GCS, so
    a preempted slice restarts from the latest committed step
    (``ray_tpu/resilience/``).
    """

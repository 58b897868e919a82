"""ray_tpu.train: distributed training on TPU slices.

Reference: ``python/ray/train/`` v1+v2 (SURVEY.md §2.3, §3.4). The
controller-actor pattern is kept; NCCL process groups are replaced by
JAX SPMD — one worker per slice host, ``jax.distributed`` bootstrap,
parallelism via ``ray_tpu.parallel`` meshes inside the train_fn.
"""

from .checkpoint import Checkpoint, load_pytree, save_pytree
from .config import (
    CheckpointConfig,
    FailureConfig,
    Result,
    RunConfig,
    ScalingConfig,
)
from .controller import ElasticScalingPolicy, FixedScalingPolicy
from .session import get_checkpoint, get_context, get_dataset_shard, report
from .trainer import DataParallelTrainer, JaxTrainer

__all__ = [
    "Checkpoint",
    "CheckpointConfig",
    "DataParallelTrainer",
    "ElasticScalingPolicy",
    "FixedScalingPolicy",
    "FailureConfig",
    "JaxTrainer",
    "Result",
    "RunConfig",
    "ScalingConfig",
    "get_checkpoint",
    "get_dataset_shard",
    "get_context",
    "report",
    "load_pytree",
    "save_pytree",
]

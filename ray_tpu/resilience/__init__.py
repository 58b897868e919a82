"""Elastic resilience: preemption-aware checkpointing + bounded recovery.

The subsystem that turns a spot-slice preemption from a feared outage
into a measured event (ROADMAP item 6). Three pieces compose:

  * :mod:`ray_tpu.resilience.checkpoint` — async, atomically-committed
    train-state checkpoints, each committed version registered with the
    GCS so recovery finds the latest one without touching a dead node;
  * :mod:`ray_tpu.resilience.preemption` — the notice plumbing: hazard
    views over the GCS node table + the ``node_preempted`` ErrorEvent
    channel, consumed by the serve controller (proactive replica
    eviction);
  * the wiring that lives in the subsystems themselves: raylet draining
    (``core/raylet.py``), the ``preempt_slice`` FaultPlan kind
    (``chaos/plan.py``) and train controller resume
    (``train/controller.py``).
"""

from .checkpoint import (
    AsyncCheckpointManager,
    latest_committed,
    latest_registered,
    list_committed,
    load_checkpoint,
    register_latest,
)
from .metadata_watcher import GceMetadataPreemptionWatcher
from .preemption import PreemptionNotice, hazard_nodes

__all__ = [
    "AsyncCheckpointManager",
    "GceMetadataPreemptionWatcher",
    "PreemptionNotice",
    "hazard_nodes",
    "latest_committed",
    "latest_registered",
    "list_committed",
    "load_checkpoint",
    "register_latest",
]

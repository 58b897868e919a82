"""Device-mesh construction with named parallelism axes.

Axis vocabulary (every downstream component uses these names):

- ``dp``   — data parallel: replicate params, shard batch. Gradient psum.
- ``fsdp`` — fully-sharded data parallel (ZeRO-3): shard params *and* batch;
  all-gather params per layer, reduce-scatter grads.
- ``tp``   — tensor parallel (Megatron-style): shard attention heads and MLP
  hidden dim; all-reduce activations at block boundaries.
- ``sp``   — sequence/context parallel: shard the sequence axis; ring
  attention moves KV blocks around the ring (SURVEY.md §5.7 — green-field,
  the reference has no equivalent).
- ``pp``   — pipeline parallel: shard layers into stages.
- ``ep``   — expert parallel: shard MoE experts.
- ``dcn``  — multi-slice data parallel: the outermost axis spans TPU
  slices connected over the data-center network. Only per-step gradient
  all-reduces cross it; everything latency-bound stays on ICI inside a
  slice (the scaling-book multi-slice recipe).

The reference delegates TP/PP/EP to vLLM via placement-group GPU bundles
(``vllm_models.py:117-168``); here they are first-class mesh axes and XLA
inserts the collectives.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Sequence

import jax
import numpy as np
from jax.sharding import Mesh

from ..tpu import leased_devices

AXIS_ORDER = ("dcn", "pp", "dp", "fsdp", "sp", "ep", "tp")
# tp innermost: tensor-parallel collectives are per-layer and latency-bound,
# so they must ride the fastest ICI links (adjacent devices); dcn/pp/dp
# outermost, their collectives are per-step and bandwidth-tolerant — dcn
# traffic crosses slices over the data-center network.


@dataclasses.dataclass(frozen=True)
class MeshConfig:
    """Sizes for each parallelism axis. -1 on at most one axis means
    "absorb all remaining devices"."""

    dp: int = 1
    fsdp: int = 1
    tp: int = 1
    sp: int = 1
    pp: int = 1
    ep: int = 1
    dcn: int = 1

    def sizes(self) -> dict[str, int]:
        return {a: getattr(self, a) for a in AXIS_ORDER}

    def resolve(self, n_devices: int) -> "MeshConfig":
        """Fill in a single -1 axis so the product equals ``n_devices``."""
        sizes = self.sizes()
        wild = [a for a, s in sizes.items() if s == -1]
        if len(wild) > 1:
            raise ValueError(f"at most one axis may be -1, got {wild}")
        fixed = math.prod(s for s in sizes.values() if s != -1)
        if wild:
            if n_devices % fixed:
                raise ValueError(
                    f"{n_devices} devices not divisible by fixed axes product {fixed}"
                )
            sizes[wild[0]] = n_devices // fixed
        elif fixed != n_devices:
            raise ValueError(
                f"mesh {sizes} needs {fixed} devices but {n_devices} available"
            )
        return MeshConfig(**sizes)


def mesh_shape_for(n_devices: int, config: MeshConfig | None = None) -> MeshConfig:
    """Resolve a config against a device count; default is pure data parallel."""
    config = config or MeshConfig(dp=-1)
    return config.resolve(n_devices)


def create_mesh(
    config: MeshConfig | None = None,
    *,
    devices: Sequence[jax.Device] | None = None,
) -> Mesh:
    """Build a ``jax.sharding.Mesh`` over all (or given) devices.

    Device order: JAX's default device list already follows the physical
    torus enumeration on TPU, so a reshape keeps tp-adjacent devices
    physically adjacent on ICI. For multi-slice, set ``MeshConfig.dcn``:
    the dcn axis is aligned to slice boundaries (hybrid mesh) so only its
    per-step gradient sync crosses the data-center network.
    """
    devices = list(devices if devices is not None else leased_devices())
    config = mesh_shape_for(len(devices), config)
    sizes = config.sizes()
    shape = tuple(sizes[a] for a in AXIS_ORDER)
    if sizes["dcn"] > 1 and hasattr(devices[0], "slice_index"):
        # Real multi-slice pod: group devices by slice so the dcn axis is
        # EXACTLY the slice boundary. Shapes must be same-rank (per-axis
        # split between ICI and DCN); a rank mismatch would make np.block
        # concatenate slices along the innermost axis and silently put
        # latency-bound collectives on DCN. Config errors (e.g. dcn !=
        # number of slices) propagate — a misaligned fallback mesh would
        # be an order-of-magnitude silent regression.
        from jax.experimental import mesh_utils

        ici_shape = tuple(1 if a == "dcn" else sizes[a] for a in AXIS_ORDER)
        dcn_shape = tuple(sizes["dcn"] if a == "dcn" else 1 for a in AXIS_ORDER)
        dev_array = mesh_utils.create_hybrid_device_mesh(
            ici_shape, dcn_shape, devices=devices,
        )
        return Mesh(dev_array, AXIS_ORDER)
    # Single slice / virtual devices (no slice_index): plain torus reshape.
    dev_array = np.asarray(devices).reshape(shape)
    return Mesh(dev_array, AXIS_ORDER)


def local_mesh(config: MeshConfig | None = None) -> Mesh:
    """Mesh over this process's addressable devices only."""
    return create_mesh(config, devices=jax.local_devices())

"""TPU accelerator detection and slice-aware resource shaping.

Equivalent of the reference's ``TPUAcceleratorManager``
(``python/ray/_private/accelerators/tpu.py:70``, 393 LoC): detects TPU
hardware (the host's device files), exposes per-host chip counts as a
``TPU`` resource, sets chip-visibility env vars for workers, and
auto-creates the ``TPU-{type}-head`` resource on host 0 of a pod slice so a
single slice-head bundle can anchor STRICT_PACK placement groups
(reference ``tpu.py:31-44,170-192``).
"""

from __future__ import annotations

import functools
import glob
import os

# GKE/GCE environment variables (reference tpu.py:31-44).
_ENV_ACCEL_TYPE = "TPU_ACCELERATOR_TYPE"  # e.g. "v5litepod-16"
_ENV_WORKER_ID = "TPU_WORKER_ID"
_ENV_TPU_NAME = "TPU_NAME"
ENV_VISIBLE_CHIPS = "TPU_VISIBLE_CHIPS"


def detect_num_tpu_chips() -> int:
    """Number of TPU chips attached to this host, counted from the device
    files the host exposes: ``/dev/accel*`` (accel driver) or the numbered
    VFIO groups ``/dev/vfio/<n>`` — what the reference's
    ``TPUAcceleratorManager.get_current_node_num_accelerators`` counts.

    Never asks JAX: the raylet lives in the driver process, which must
    stay off the chip (a process that initializes the TPU backend holds
    it, and the worker that leased ``TPU`` then fails). The ``TPU_*``
    environment is not consulted either — a one-chip machine cut from a
    four-chip host keeps the host's ``TPU_CHIPS_PER_HOST_BOUNDS=2,2,1``
    while exposing a single VFIO group.
    """
    override = os.environ.get("RAY_TPU_FAKE_CHIPS")
    if override:
        return int(override)
    accel = glob.glob("/dev/accel*")
    if accel:
        return len(accel)
    try:
        return sum(1 for e in os.listdir("/dev/vfio") if e.isdigit())
    except OSError:
        return 0


@functools.lru_cache(maxsize=1)
def accelerator_type() -> str:
    """Slice type string like 'v5litepod-16', '' when not on TPU."""
    return os.environ.get(_ENV_ACCEL_TYPE, "")


def slice_name() -> str:
    return os.environ.get(_ENV_TPU_NAME, "")


def worker_index() -> int:
    return int(os.environ.get(_ENV_WORKER_ID, "0"))


def detect_tpu_resources() -> dict[str, float]:
    """Resources this host contributes.

    ``TPU``: chips on this host. ``TPU-{type}-head``: 1 on worker 0 of a
    slice so placement groups can target 'one bundle per slice'
    (reference tpu.py:70-192 get_current_node_tpu_pod_type etc.).
    """
    chips = detect_num_tpu_chips()
    if chips <= 0:
        return {}
    out: dict[str, float] = {"TPU": float(chips)}
    acc = accelerator_type()
    if acc and worker_index() == 0:
        out[f"TPU-{acc}-head"] = 1.0
    if slice_name():
        out[f"TPU-{slice_name()}"] = float(chips)
    return out


def num_hosts_for_type(acc_type: str) -> int:
    """Hosts in a slice of the given type, e.g. v5litepod-16 → 4 hosts.

    v5e: 4 chips/host (v5litepod-8 → 2 hosts); v5p/v4: 4 chips/host;
    suffix is the chip count for v4/v5p is cores — keep the simple
    chips/4 rule the reference uses for pod slices.
    """
    try:
        n_chips = int(acc_type.rsplit("-", 1)[1])
    except (IndexError, ValueError):
        return 1
    return max(1, n_chips // 4)


# Chip bounds of a process that owns ``n`` chips of one host: one chip, or
# a whole 4- or 8-chip host. On a v5e 2x2 host one chip alone, two
# one-chip processes side by side and the whole host all came up (PR 21
# probe); two 2-chip processes with bounds "1,2,1" exited at TPU start-up
# without a message, so a 2-chip lease is refused rather than guessed at.
_CHIP_BOUNDS = {1: "1,1,1", 4: "2,2,1", 8: "2,4,1"}


def set_visible_chips(chip_ids: list[int]) -> dict[str, str]:
    """Env vars that make a process see exactly ``chip_ids`` of the host
    and form a one-process slice of them (reference tpu.py
    ``set_current_process_visible_accelerator_ids``). Bounds smaller than
    the host also tell libtpu to skip its host-wide lockfile, so one
    process per chip can coexist."""
    bounds = _CHIP_BOUNDS.get(len(chip_ids))
    if bounds is None:
        raise ValueError(
            f"a TPU lease holds 1, 4 or 8 chips of a host, not {len(chip_ids)}")
    return {
        ENV_VISIBLE_CHIPS: ",".join(str(c) for c in chip_ids),
        "TPU_CHIPS_PER_PROCESS_BOUNDS": bounds,
        "TPU_PROCESS_BOUNDS": "1,1,1",
    }


def on_tpu() -> bool:
    """THE definition of "on the chip": JAX's default backend is ``tpu``.
    The Pallas kernels compile natively exactly when this holds and the
    engine picks the paged decode path on the same test."""
    import jax

    return jax.default_backend() == "tpu"


def leased_devices() -> list:
    """``jax.devices()`` for code that is about to place a model. A worker
    that was leased chips (the raylet exported ``TPU_VISIBLE_CHIPS`` into
    it) and is not pinned to the CPU must find them: JAX with no platform
    pinned falls back to the CPU with a warning when the TPU backend
    fails to start, and a train session or replica would then run there
    without a word."""
    import jax

    devices = jax.devices()
    leased = os.environ.get(ENV_VISIBLE_CHIPS)
    if (leased and os.environ.get("JAX_PLATFORMS") != "cpu"
            and devices[0].platform != "tpu"):
        raise RuntimeError(
            f"this worker leased TPU chips {leased} but JAX found no TPU: "
            f"jax.devices() = {devices}")
    return devices


def device_report() -> dict:
    """What this process's JAX holds, which attention paths it traced and
    what one call of each Pallas kernel costs at its traced shapes (the
    held range's ``moe_rows`` among them: its bytes, no FLOPs): what a
    worker sends back so its caller can tell a chip run from a quiet CPU
    one. Platform, kind and count are as JAX reports them."""
    from .ops.trace_log import kernel_costs, kernel_traces

    devices = leased_devices()
    stats = [d.memory_stats() or {} for d in devices]
    return {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
        "bytes_in_use": [int(m.get("bytes_in_use", 0)) for m in stats],
        "peak_bytes_in_use": [int(m.get("peak_bytes_in_use", 0)) for m in stats],
        "kernel_traces": kernel_traces(),
        "kernel_costs": kernel_costs(),
    }


_CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"


def compile_cache_env(env) -> str:
    """Place JAX's persistent compilation cache for a process about to be
    launched with ``env`` (or for this one, given ``os.environ`` before
    jax is imported). A directory set from outside is left alone; else
    the cache goes to a FIXED ``<checkout>/.jax_cache`` — the path is
    part of the cache key, so a directory that moves never hits. Returns
    the directory in effect."""
    if not env.get(_CACHE_ENV):
        env[_CACHE_ENV] = os.path.join(
            os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
            ".jax_cache")
    return env[_CACHE_ENV]

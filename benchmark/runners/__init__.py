"""Runners, found by the name a traffic mix's file gives (``runner``), plus
what they share. A runner module has ``run(ctx) -> dict``: it starts the
cluster, drives the cell, stops the cluster and returns the result object
(``correct``, ``attempted``, ``failed``, ``metrics``, ``device`` and, in a
traced run, ``breakdown``); ``run.py`` hands that to ``result.emit``.

The benchmark's own process never touches a chip: it is pinned to the CPU
before anything imports jax, and only the worker or replica that leased
``TPU`` does device work. No TPU in the cluster, or a worker on another
platform, fails the run: no measuring path falls back to the CPU.
"""

from __future__ import annotations

import dataclasses
import os
from typing import Callable


class RunFailure(Exception):
    """The run cannot give a result (no chip, wrong platform, a phase
    failed). Ends the process non-zero with no result line."""


@dataclasses.dataclass
class Context:
    cell: object                 # manifest.Cell
    seed: int
    seconds: float
    trace: bool
    rehearse: dict | None        # the rehearsal file, or None on the chip
    t_start_wall: float          # wall clock at process start
    t_start_mono: float          # the same instant on time.monotonic()
    say: Callable[[dict], None]  # an earlier line of the output

    @property
    def platform(self) -> str:
        return "cpu" if self.rehearse else "tpu"

    @property
    def weight_seed(self) -> int:
        """--seed may pass 2**31; a PRNG key takes 32 signed bits."""
        return self.seed % (2**31 - 1)


def lease(ctx: Context) -> tuple[dict, dict | None]:
    """(resources, runtime_env) of the worker that computes. On the chip it
    leases ``TPU``, drops the CPU pin every worker otherwise inherits and
    takes the mix's ``worker_env`` (settings of the TPU runtime that belong
    to the deployment, such as the size of its pinned transfer buffer)."""
    if ctx.rehearse:
        return {"CPU": 1}, None
    env = {"JAX_PLATFORMS": None,
           # cache every program, however quick its compile: a run after
           # the first should find all of them
           "JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS": "0",
           "JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES": "-1",
           **ctx.cell.traffic.get("worker_env", {})}
    return {"CPU": 1, "TPU": ctx.cell.chips}, {"env_vars": env}


def start_cluster(ctx: Context) -> None:
    import ray_tpu

    # log_to_driver off: what a worker prints must never reach this
    # process's output, least of all after the result line
    ray_tpu.init(num_cpus=8, _system_config={"log_to_driver": False})
    chips = ray_tpu.cluster_resources().get("TPU", 0.0)
    ctx.say({"cluster": {"TPU": chips}})
    if not ctx.rehearse and chips < ctx.cell.chips:
        raise RunFailure(f"this host exposes {chips:g} TPU chip(s), cell "
                         f"{ctx.cell.name} needs {ctx.cell.chips}")


def stop_cluster() -> None:
    import ray_tpu

    if ray_tpu.is_initialized():
        ray_tpu.shutdown()


def check_device(device: dict, ctx: Context) -> None:
    if device["platform"] != ctx.platform or (
            not ctx.rehearse and device["count"] != ctx.cell.chips):
        raise RunFailure(
            f"the worker ran on {device['count']} x {device['platform']} "
            f"({device['kind']}), the cell needs {ctx.cell.chips} x {ctx.platform}")


def kernel_native(traces: dict, kernel: str, platform: str) -> bool:
    """On the chip ``kernel`` was traced and only ever to its native Pallas
    lowering (never the interpreter, never a reference swap)."""
    native = traces.get(f"{kernel}:pallas", 0)
    other = [k for k in traces if k.startswith(kernel + ":") and k != f"{kernel}:pallas"]
    return (native > 0 and not other) if platform == "tpu" else native == 0


def llama_config(model: dict, **overrides):
    """The program's config object for a configuration file's ``model``."""
    from ray_tpu.models.llama import LlamaConfig

    return LlamaConfig(
        vocab_size=model["vocab_size"], hidden=model["hidden_size"],
        n_layers=model["num_hidden_layers"], n_heads=model["num_attention_heads"],
        n_kv_heads=model["num_key_value_heads"],
        intermediate=model["intermediate_size"], head_dim=model["head_dim"],
        rope_theta=float(model["rope_theta"]), norm_eps=float(model["rms_norm_eps"]),
        **overrides)


def capture_trace(work: Callable[[], object], profile_name: str) -> str:
    """Trace this process's devices while ``work()`` runs; the path of the
    ``.xplane.pb`` written. ``work`` runs inside a ``TraceAnnotation`` named
    ``benchmark_capture``: that event is the traced window (the profiler's
    own start and stop lie outside it). Cheap enough to sit inside a
    measured window; ``reduce_trace`` (seconds of parsing) belongs after it."""
    import glob
    import tempfile

    import jax

    from .. import trace_reduce

    options = jax.profiler.ProfileOptions()
    # Python frames are what idle gaps are attributed to; a profile may turn
    # them off where hooking every thread stalls the traced process
    options.python_tracer_level = int(
        trace_reduce.load_profile(profile_name).get("python_tracer", True))
    out = tempfile.mkdtemp(prefix="bench-trace-")
    jax.profiler.start_trace(out, profiler_options=options)
    try:
        with jax.profiler.TraceAnnotation("benchmark_capture"):
            work()
    finally:
        jax.profiler.stop_trace()
    paths = glob.glob(os.path.join(out, "plugins", "profile", "*", "*.xplane.pb"))
    if not paths:
        raise RunFailure(f"the profiler wrote no .xplane.pb under {out}")
    return paths[0]


def reduce_trace(path: str, profile_name: str, unions: dict | None = None) -> dict:
    """Reduce a captured trace here, where the chip is, to numbers only, and
    delete it. ``unions``: see ``trace_reduce.reduce_trace``."""
    import shutil

    from .. import trace_reduce

    try:
        summary = trace_reduce.reduce_trace(
            path, trace_reduce.load_profile(profile_name), unions)
        summary["trace_bytes"] = os.path.getsize(path)
        return summary
    finally:
        # <out>/plugins/profile/<time>/<host>.xplane.pb
        shutil.rmtree(os.path.dirname(os.path.dirname(os.path.dirname(
            os.path.dirname(path)))), ignore_errors=True)

"""Multi-process (multi-host) JAX bootstrap helpers.

The SPMD↔actor bridge (SURVEY.md §7.1): a controller creates one actor
per host, rank 0 picks a coordinator endpoint, and every process calls
``jax.distributed.initialize`` — the analogue of the reference's
``_setup_torch_process_group`` (``python/ray/train/torch/config.py:66``).
Shared by Train worker groups and multi-host LLM engine shards.
"""

from __future__ import annotations


def pick_coordinator_address() -> str:
    """Pick a routable ``host:port`` for the jax.distributed coordinator
    (rank 0 binds and serves it). A UDP "connect" selects the outbound
    interface without sending traffic — ``gethostbyname(gethostname())``
    resolves to loopback on common /etc/hosts setups, which would break
    every cross-host join."""
    import socket

    try:
        probe = socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
        probe.connect(("8.8.8.8", 80))
        host = probe.getsockname()[0]
        probe.close()
    except OSError:
        host = socket.gethostbyname(socket.gethostname())
    s = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
    s.bind(("", 0))
    port = s.getsockname()[1]
    s.close()
    return f"{host}:{port}"


def initialize_process(coordinator: str, num_processes: int, process_id: int) -> int:
    """``jax.distributed.initialize`` for one process of a multi-host
    group; returns the GLOBAL device count. On the CPU backend (tests,
    dryruns) cross-process collectives need the gloo implementation —
    configure it before the backend initializes."""
    import jax

    if num_processes > 1:
        import os

        if os.environ.get("JAX_PLATFORMS", "").startswith("cpu") or (
                jax.config.jax_platforms or "").startswith("cpu"):
            jax.config.update("jax_cpu_collectives_implementation", "gloo")
        jax.distributed.initialize(
            coordinator_address=coordinator,
            num_processes=num_processes,
            process_id=process_id,
        )
    return len(jax.devices())

"""Worker process entrypoint.

Equivalent of the reference's ``python/ray/_private/workers/default_worker.py``:
parses the raylet-provided arguments, connects the CoreWorker, then parks the
main thread while the io loop serves ``PushTask``.
"""

from __future__ import annotations

import argparse
import logging
import signal
import threading

from .ids import JobID
from .worker import MODE_WORKER, CoreWorker, set_global_worker


def run(argv: list[str] | None = None) -> None:
    parser = argparse.ArgumentParser()
    parser.add_argument("--raylet-address", required=True)
    parser.add_argument("--gcs-address", required=True)
    parser.add_argument("--node-id", required=True)
    parser.add_argument("--worker-id", required=True)
    parser.add_argument("--store-path", required=True)
    parser.add_argument("--store-capacity", type=int, required=True)
    parser.add_argument("--job-id", type=int, default=1)
    args = parser.parse_args(argv)

    logging.basicConfig(level=logging.INFO, format="[worker %(process)d] %(message)s")
    # SIGUSR1 → dump all thread stacks to the worker log (debugging stuck
    # workers; reference exposes the same via `ray stack`).
    import faulthandler

    faulthandler.register(signal.SIGUSR1, all_threads=True)
    worker = CoreWorker(
        mode=MODE_WORKER,
        gcs_address=args.gcs_address,
        raylet_address=args.raylet_address,
        node_id=args.node_id,
        store_path=args.store_path,
        store_capacity=args.store_capacity,
        job_id=JobID.from_int(args.job_id),
        worker_id=args.worker_id,
    )
    set_global_worker(worker)
    worker.connect()

    stop = threading.Event()
    signal.signal(signal.SIGTERM, lambda *a: stop.set())
    signal.signal(signal.SIGINT, lambda *a: stop.set())
    # Orphan watch: workers are direct children of their raylet. If the
    # raylet dies without a graceful stop (driver crash, kill -9), the
    # worker is reparented (PPID changes) — exit instead of idling forever
    # holding memory, sockets, and possibly the TPU chip (reference:
    # workers exit on raylet socket close).
    import os as _os

    parent = _os.getppid()
    while not stop.wait(timeout=2.0):
        if _os.getppid() != parent:
            break


def main() -> None:
    run()


if __name__ == "__main__":
    main()

"""Worker zygote: fork pre-imported worker processes in milliseconds.

The dominant cost of starting a worker is interpreter boot + the
framework import graph (~0.25 s with a pruned env). The zygote pays
that ONCE: the raylet spawns it with a worker environment, it imports
``worker_main`` and then serves fork requests over stdin/stdout — each
new worker is an ``os.fork`` (~ms) of the warm image (the reference's
prestarted-worker pool amortizes the same cost only to its pool depth; a
forkserver amortizes it for every worker).

Zygotes are runtime-env-KEYED: the raylet boots one zygote per env hash,
with that env's variables / PYTHONPATH / working_dir applied to the
zygote process itself — so import-time env vars (JAX_PLATFORMS, plugin
gates) are baked into the forked image exactly as a cold spawn with that
runtime_env would see them. Interpreter-level envs (conda /
py_executable / container) can never fork from a zygote of this
interpreter; the raylet always cold-spawns those.

Safety: the zygote is strictly single-threaded and starts no event loop,
so forking is well-defined; the child applies its per-worker env, detaches
its stdio to the worker log, and runs the normal ``worker_main`` entry.

Protocol (line-delimited JSON):
  zygote -> raylet:  {"ready": true}                 (after imports)
  raylet -> zygote:  {"worker_id": ..., "log": ..., "env": {k: v|null}}
  zygote -> raylet:  {"pid": <child pid>}
"""

from __future__ import annotations

import argparse
import json
import os
import signal
import sys


def main() -> None:
    parser = argparse.ArgumentParser()
    parser.add_argument("--raylet-address", required=True)
    parser.add_argument("--gcs-address", required=True)
    parser.add_argument("--node-id", required=True)
    parser.add_argument("--store-path", required=True)
    parser.add_argument("--store-capacity", required=True)
    args = parser.parse_args()

    # Pay the import cost once, pre-fork.
    from . import worker_main  # noqa: F401

    # Children are never waited on here: auto-reap them.
    signal.signal(signal.SIGCHLD, signal.SIG_IGN)

    out = os.fdopen(os.dup(1), "w", buffering=1)
    # The forked children must not inherit a live handle to the protocol
    # pipe (a child crash mid-write would corrupt framing): children close
    # it immediately after fork.
    out.write(json.dumps({"ready": True}) + "\n")

    parent = os.getppid()
    while True:
        line = sys.stdin.readline()
        if not line:
            break  # raylet closed our stdin: shut down (children
            # notice their PPID change and exit themselves)
        if os.getppid() != parent:
            break  # raylet/driver died: orphaned zygote exits
        try:
            req = json.loads(line)
        except json.JSONDecodeError:
            continue
        pid = os.fork()
        if pid == 0:
            # ---- child: become a normal worker process ----
            try:
                out.close()
                sys.stdin.close()
                for k, v in (req.get("env") or {}).items():
                    if v is None:
                        os.environ.pop(k, None)
                    else:
                        os.environ[k] = str(v)
                log_fd = os.open(req["log"],
                                 os.O_WRONLY | os.O_CREAT | os.O_APPEND, 0o644)
                os.dup2(log_fd, 1)
                os.dup2(log_fd, 2)
                os.close(log_fd)
                signal.signal(signal.SIGCHLD, signal.SIG_DFL)
                worker_main.run([
                    "--raylet-address", args.raylet_address,
                    "--gcs-address", args.gcs_address,
                    "--node-id", args.node_id,
                    "--worker-id", req["worker_id"],
                    "--store-path", args.store_path,
                    "--store-capacity", str(args.store_capacity),
                ])
            except BaseException:
                import traceback

                traceback.print_exc()
            finally:
                os._exit(0)
        out.write(json.dumps({"pid": pid}) + "\n")


if __name__ == "__main__":
    main()

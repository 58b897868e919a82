"""Run one cell of BENCHMARK.json once:

    python -m benchmark.run --workload <name> --seed <n> --seconds <s> --trace <0|1>

The last line of standard output is the result object, written by
``result.emit`` and by nothing else: this process keeps the only handle
on its real standard output, every child's and every library's output
goes to standard error, and the line is written after the cluster has
stopped, just before ``os._exit``.
"""

from __future__ import annotations

import argparse
import importlib
import json
import os
import sys
import threading
import time
import traceback

T_START_WALL, T_START_MONO = time.time(), time.monotonic()
HARD_LIMIT_S = 1150  # the contract's 1200 s for a compiling run, with room to stop


def _private_stdout() -> int:
    """Keep the real standard output as a descriptor only this process
    holds (``os.dup`` is not inherited) and point descriptor 1 at standard
    error, so no worker, raylet or library can write a line after — or
    into — the result."""
    sys.stdout.flush()
    real = os.dup(1)
    os.dup2(2, 1)
    return real


def _reap_children(limit_s: float = 15.0) -> None:
    """Wait until every child this process started has ended (the cluster's
    shutdown stops them; this collects them), for at most ``limit_s``."""
    deadline = time.monotonic() + limit_s
    try:
        while time.monotonic() < deadline:
            pid, _ = os.waitpid(-1, os.WNOHANG)
            if pid == 0:
                time.sleep(0.1)
    except ChildProcessError:
        pass  # no child is left


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=float, required=True)
    parser.add_argument("--trace", type=int, choices=(0, 1), required=True)
    parser.add_argument("--rehearse", action="store_true",
                        help="tests only: a tiny configuration on the CPU; "
                             "the line says platform cpu and is no measurement")
    args = parser.parse_args(argv)

    # Before anything imports jax: this process must never open a chip.
    os.environ["JAX_PLATFORMS"] = "cpu"
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    os.environ["PYTHONPATH"] = os.pathsep.join(
        [root] + [p for p in os.environ.get("PYTHONPATH", "").split(os.pathsep) if p])
    out_fd = _private_stdout()

    def say(obj: dict) -> None:
        os.write(out_fd, (json.dumps(obj, default=str) + "\n").encode())

    def give_up(code: int):
        # past the hard limit: stop what was started, print no result
        sys.stderr.write(f"benchmark: ran past {HARD_LIMIT_S}s, giving up\n")
        try:
            from .runners import stop_cluster

            stop_cluster()
        finally:
            os._exit(code)

    watchdog = threading.Timer(HARD_LIMIT_S, give_up, args=(4,))
    watchdog.daemon = True
    watchdog.start()
    code = 1
    try:
        from ray_tpu.tpu import compile_cache_env  # no ray_tpu here: no result

        from . import result
        from .manifest import HERE, Manifest
        from .runners import Context

        manifest = Manifest()
        problems = manifest.problems()
        if problems:
            raise SystemExit("BENCHMARK.json or its data files are unsound:\n  "
                             + "\n  ".join(problems))
        cell = manifest.cell(args.workload)
        rehearse = None
        if args.rehearse:
            with open(os.path.join(HERE, "rehearse.json")) as f:
                rehearse = json.load(f)
            if "xla_force_host_platform_device_count" not in os.environ.get("XLA_FLAGS", ""):
                os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                                           " --xla_force_host_platform_device_count=4")
        cache = compile_cache_env(os.environ)  # fixed path; workers inherit it
        say({"benchmark": cell.name, "seed": args.seed, "seconds": args.seconds,
             "trace": args.trace, "rehearsal": bool(rehearse), "compile_cache": cache,
             "cache_entries": len(os.listdir(cache)) if os.path.isdir(cache) else 0})
        ctx = Context(cell=cell, seed=args.seed, seconds=args.seconds,
                      trace=bool(args.trace), rehearse=rehearse,
                      t_start_wall=T_START_WALL, t_start_mono=T_START_MONO, say=say)
        runner = importlib.import_module(
            f"{__package__}.runners.{cell.traffic['runner']}")
        obj = runner.run(ctx)  # returns with the cluster stopped
        _reap_children()
        if rehearse:
            obj["rehearsal"] = True
        code = result.emit(
            obj, cell.declared(ctx.trace), trace=ctx.trace,
            chips=None if rehearse else cell.chips, platform=ctx.platform,
            out_fd=out_fd)
    except BaseException:  # noqa: BLE001 - the boundary: report, exit non-zero
        traceback.print_exc(file=sys.stderr)
        try:
            from .runners import stop_cluster

            stop_cluster()
        except BaseException:  # noqa: BLE001
            traceback.print_exc(file=sys.stderr)
    sys.stderr.flush()
    # no atexit hook and no thread gets to print after the result
    os._exit(code)


if __name__ == "__main__":
    main()

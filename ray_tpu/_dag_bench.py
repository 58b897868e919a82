"""Compiled-loop dispatch suite (ROADMAP item 4).

Measures what the persistent compiled-loop runtime (``dag/loop.py``)
exists to kill: the per-tick dynamic dispatch cost of steady-state
iteration, and its effect on the pipeline-parallel engine tick path.

Two phases, guarded by ``ray_tpu.bench_check``:

  * **Tick dispatch overhead** — a 2-stage trivial actor pipeline driven
    (a) dynamically (one ``.remote()`` chain + ``get`` per tick, the
    submit→lease→push path every iteration) and (b) through a compiled
    loop (channel write + read per tick, zero task submission).

      - ``dag_tick_dispatch_overhead_dynamic_us`` — dynamic per-tick µs
      - ``dag_tick_dispatch_overhead_us``         — compiled per-tick µs
      - ``dag_loop_ticks_per_s``                  — compiled PIPELINED
        tick rate (puts streamed ``credits`` deep, gets drained behind)

  * **pp decode tok/s** — the debug-model engine over a 1-host sharded
    executor with a pp=2 mesh, decoding the same workload through the
    dynamic per-burst RPC path and the compiled loop:

      - ``pp_decode_tok_s_dynamic`` / ``pp_decode_tok_s_compiled``

    On hosts that cannot run the pp shard_map programs (< 2
    devices) the phase records
    ``pp_decode_*_skipped`` markers instead — ``bench_check`` treats the
    absence as intentional, never as a silent regression.

Sizes are env-tunable (``RAY_TPU_DAG_BENCH_{TICKS,DECODE_BURSTS}``). Run
via ``python -m ray_tpu.cli bench dag``.
"""

from __future__ import annotations

import os
import sys
import time


def _env_int(name: str, default: int) -> int:
    return int(os.environ.get(name, default))


def _bench_tick_overhead(out: dict, ticks: int) -> None:
    import ray_tpu
    from ray_tpu.dag import InputNode, compile_loop

    @ray_tpu.remote
    class _Stage:
        def f(self, x):
            return x + 1

    a, b = _Stage.remote(), _Stage.remote()
    # Warm both actors (worker spawn + first-call export are not
    # dispatch overhead).
    ray_tpu.get([a.f.remote(0), b.f.remote(0)], timeout=120)

    # Dynamic: the per-tick task path — one submit→lease→push→return
    # chain per stage per tick, refs threading stage to stage.
    t0 = time.perf_counter()
    for i in range(ticks):
        assert ray_tpu.get(b.f.remote(a.f.remote(i)), timeout=120) == i + 2
    dyn_s = time.perf_counter() - t0
    out["dag_tick_dispatch_overhead_dynamic_us"] = round(
        dyn_s / ticks * 1e6, 1)

    with InputNode() as inp:
        dag = b.f.bind(a.f.bind(inp))
    loop = compile_loop(dag)
    try:
        assert loop.run(0) == 2  # warm the resident executors
        # Compiled, synchronous: one full channel round trip per tick —
        # the steady-state dispatch cost with zero task submission.
        t0 = time.perf_counter()
        for i in range(ticks):
            assert loop.run(i) == i + 2
        comp_s = time.perf_counter() - t0
        out["dag_tick_dispatch_overhead_us"] = round(comp_s / ticks * 1e6, 1)
        # Compiled, pipelined: puts stream ahead of gets (credits deep) —
        # the sustained tick rate of a busy loop.
        t0 = time.perf_counter()
        done = 0
        for i in range(ticks):
            loop.put(i)
            while loop.in_flight >= loop.credits:
                loop.get()
                done += 1
        while done < ticks:
            loop.get()
            done += 1
        out["dag_loop_ticks_per_s"] = round(
            ticks / (time.perf_counter() - t0), 1)
    finally:
        loop.teardown()
    out["dag_bench_ticks_cfg"] = ticks


def _bench_obs_overhead(out: dict, ticks: int) -> None:
    """Stall-recorder cost guard: the same 2-stage compiled loop timed
    with the per-tick stall recorder ON (the always-on default) vs OFF.

    Two estimates, one guard:

      - ``loop_obs_tick_{recording,baseline}_us`` — end-to-end A/B
        floors: both loops co-exist (an idle stage parks in a 1ms
        backoff poll) and short batches alternate between them, min
        over rounds. Honesty note: on a shared CPU sandbox the
        per-instance placement variance (±10%) exceeds the recorder's
        true cost (~2µs on a ~350µs tick), so the difference of these
        two cells carries that noise — they are REPORTED, not guarded.
      - ``loop_obs_overhead_frac`` — the GUARDED cell (PERF gate
        ≤ 0.02): the recorder's exact in-path ops (ring.record + the
        amortized span-cadence histogram flush + the time-gated
        snapshot-file write share) measured directly, over the measured
        tick-dispatch floor. The ops are pure in-process CPU, so the
        direct measurement is the same work the stage executor pays,
        without the channel round-trip noise.
      - ``dag_loop_stall_{wait_up,compute,wait_down}_frac`` — the
        recording loop's bottleneck-stage stall split (driver-visible
        proof the attribution pipeline works end to end)
    """
    import ray_tpu
    from ray_tpu.core.config import get_config
    from ray_tpu.dag import InputNode, compile_loop

    @ray_tpu.remote
    class _Stage:
        def f(self, x):
            return x + 1

    cfg = get_config()
    saved = cfg.dag_loop_stall_recording

    def build(recording: bool):
        # Fresh actors per mode: a resident tick executor parks its
        # actor's only thread, so loops can't share stage actors.
        cfg.dag_loop_stall_recording = recording
        a, b = _Stage.remote(), _Stage.remote()
        ray_tpu.get([a.f.remote(0), b.f.remote(0)], timeout=120)
        with InputNode() as inp:
            dag = b.f.bind(a.f.bind(inp))
        loop = compile_loop(dag)
        assert loop.run(0) == 2  # warm the resident executors
        return loop

    def batch(loop, n: int) -> float:
        t0 = time.perf_counter()
        for i in range(n):
            loop.run(i)
        return (time.perf_counter() - t0) / n

    rounds, per_batch = 24, max(20, ticks // 5)
    loops = {}
    try:
        loops["on"], loops["off"] = build(True), build(False)
        floors = {"on": None, "off": None}
        for r in range(rounds):
            for mode in (("on", "off") if r % 2 == 0 else ("off", "on")):
                dt = batch(loops[mode], per_batch)
                if floors[mode] is None or dt < floors[mode]:
                    floors[mode] = dt
        stats = loops["on"].stats(fallback_gcs=False)
    finally:
        cfg.dag_loop_stall_recording = saved
        for loop in loops.values():
            loop.teardown()
    on_s, off_s = floors["on"], floors["off"]
    out["loop_obs_tick_recording_us"] = round(on_s * 1e6, 2)
    out["loop_obs_tick_baseline_us"] = round(off_s * 1e6, 2)
    out["loop_obs_overhead_frac"] = round(
        _recorder_cost_s(cfg) / min(on_s, off_s), 4)
    bn = (stats or {}).get("bottleneck")
    if bn:
        frac = ((stats.get("stages") or {}).get(bn) or {}).get("frac") or {}
        for bucket in ("wait_up", "compute", "wait_down"):
            out[f"dag_loop_stall_{bucket}_frac"] = frac.get(bucket, 0.0)


def _recorder_cost_s(cfg) -> float:
    """Per-tick cost of the stall recorder's in-path work, measured
    directly: ``ring.record`` every tick, the bulk histogram flush every
    ``dag_loop_span_every`` ticks, and the snapshot-file write's
    time-gated share (one ~0.5ms write per ``_STALL_FILE_MIN_S``)."""
    import json
    import os
    import shutil
    import tempfile

    from ray_tpu.dag.loop import _STALL_FILE_MIN_S
    from ray_tpu.observability import loop_recorder
    from ray_tpu.util.metrics import Histogram

    ring = loop_recorder.StallRing(
        int(getattr(cfg, "dag_loop_stall_ring", 256)))
    hist = Histogram("loop_obs_bench_tick_ms",
                     boundaries=loop_recorder.TICK_MS_BOUNDARIES,
                     tag_keys=("loop", "stage", "bucket"), register=False)
    tags = tuple({"loop": "bench", "stage": "f", "bucket": b}
                 for b in loop_recorder.STALL_BUCKETS)
    flush_every = int(getattr(cfg, "dag_loop_span_every", 64) or 64)
    n = max(4000, 24 * flush_every)
    t0 = time.perf_counter()
    for k in range(1, n + 1):
        ring.record(0.05, 0.2, 0.01)
        if k % flush_every == 0:
            rows = ring.drain()
            hist.observe_many([r[0] for r in rows], tags=tags[0])
            hist.observe_many([r[1] for r in rows], tags=tags[1])
            hist.observe_many([r[2] for r in rows], tags=tags[2])
    per_tick_s = (time.perf_counter() - t0) / n

    d = tempfile.mkdtemp(prefix="loop_obs_bench_")
    try:
        path, snap = os.path.join(d, "stall.json"), ring.snapshot()
        t0 = time.perf_counter()
        for _ in range(8):
            tmp = path + ".tmp"
            with open(tmp, "w") as f:
                json.dump(snap, f)
            os.replace(tmp, path)
        write_s = (time.perf_counter() - t0) / 8
    finally:
        shutil.rmtree(d, ignore_errors=True)
    # one gated write per _STALL_FILE_MIN_S, spread over the ticks that
    # fit in that window (conservatively at a fast 100µs tick)
    return per_tick_s + write_s / (_STALL_FILE_MIN_S / 100e-6)


def _bench_pp_decode(out: dict, bursts: int) -> None:
    """Debug-model pp=2 decode through the sharded engine, dynamic vs
    compiled loop. Records skip markers when the host can't run pp."""
    from ray_tpu.llm import InferenceEngine, create_sharded_executor
    from ray_tpu.llm.engine import Request

    max_slots, max_len, page_size = 4, 128, 16

    def run(use_loop: bool) -> tuple[float, int]:
        executor = create_sharded_executor(
            "debug", 1,
            max_slots=max_slots,
            num_pages=InferenceEngine.total_pages(max_slots, max_len,
                                                  page_size),
            page_size=page_size,
            pp=2,
            seed=0,
            use_compiled_loop=use_loop,
        )
        try:
            eng = InferenceEngine(
                "debug", max_slots=max_slots, max_len=max_len,
                page_size=page_size, executor=executor, seed=0)
            budget = bursts * eng.decode_steps_per_dispatch
            reqs = [Request(f"r{i}", [7, 3, 5, 9][: i + 1] * 2,
                            max_new_tokens=budget + 8)
                    for i in range(max_slots)]
            for r in reqs:
                eng.add_request(r)
            # Drain admission + prefill + first-token flush so the timed
            # window is pure steady-state decode ticks.
            while not eng._active or eng._prefilling or eng._pending_first:
                eng.step()
            t0 = time.perf_counter()
            tokens = 0
            for _ in range(bursts):
                tokens += len(eng.step())
            dt = time.perf_counter() - t0
            return dt, tokens
        finally:
            executor.shutdown()

    dyn_s, dyn_tok = run(False)
    comp_s, comp_tok = run(True)
    out["pp_decode_tok_s_dynamic"] = round(dyn_tok / dyn_s, 1)
    out["pp_decode_tok_s_compiled"] = round(comp_tok / comp_s, 1)
    out["dag_bench_decode_bursts_cfg"] = bursts


def run_dag_bench(*, ticks: int | None = None, bursts: int | None = None,
                  connect: bool = True) -> dict:
    """Run both phases and return the metrics dict. With ``connect``
    (default) a local cluster is started and shut down; pass False to
    run inside an already-initialized driver."""
    import ray_tpu

    ticks = ticks or _env_int("RAY_TPU_DAG_BENCH_TICKS", 300)
    bursts = bursts or _env_int("RAY_TPU_DAG_BENCH_DECODE_BURSTS", 12)
    out: dict = {}
    if connect:
        ray_tpu.init(num_cpus=max(8, os.cpu_count() or 8),
                     ignore_reinit_error=True)
    try:
        _bench_tick_overhead(out, ticks)
        _bench_obs_overhead(out, ticks)
        try:
            _bench_pp_decode(out, bursts)
        except Exception as e:
            # Intentional skip on env gaps (bench_check honors the
            # markers); the real pp numbers come from the chip box.
            print(f"dag bench: pp decode phase skipped: {e}",
                  file=sys.stderr)
            out["pp_decode_skip_reason"] = f"{type(e).__name__}: {e}"
            out["pp_decode_tok_s_dynamic_skipped"] = True
            out["pp_decode_tok_s_compiled_skipped"] = True
    finally:
        if connect:
            try:
                ray_tpu.shutdown()
            except Exception:
                pass
    return out


if __name__ == "__main__":
    import json

    print(json.dumps(run_dag_bench(), indent=2))

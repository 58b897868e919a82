"""Runner of the training cells: ``JaxTrainer.fit`` of the benchmark's own
loop function on one worker that leased the cell's chips. The worker makes
the weights on the device from the seed, checks the program's logits and
loss against the plain reference, warms the step, then steps for the window;
every step is fenced by ``device_get`` of its loss.
"""

from __future__ import annotations

import math
import tempfile
import time

import numpy as np

from .. import flops, layer_metrics, pauses, stats, traffic
from . import (Context, RunFailure, capture_trace, check_device, kernel_native,
               lease, llama_config, reduce_trace, start_cluster, stop_cluster)

# The program's bf16 logits against the plain reference's float32 ones, on
# the same bf16-rounded weights: at every position of the check sequences
# the RMS of the difference over the vocabulary, as a share of the RMS of
# the reference's logits there, may reach LOGIT_RTOL at most. The program
# multiplies in bf16 with float32 accumulation, which over 24-32 layers
# reads 0.012-0.016 here (PERF.md Findings, PR 23); a dropped layer reads
# 0.2 and weights rounded to fp8 0.18, so 0.05 is three times the one and
# a quarter of the others. A mean cross entropy cannot tell them apart:
# on random tokens it is ln(vocab) plus a constant whatever the body does.
LOGIT_RTOL = 0.05
# The program's own loss path (chunked lm_head and cross entropy, which
# ``forward`` does not take) against the reference's mean cross entropy
# over the same n target tokens: at most LOSS_ATOL_SQRT_TOKENS / sqrt(n)
# apart. The per-token error in log-probability is ~0.011 RMS with either
# sign (PERF.md Findings, PR 23), so the mean over n tokens stands 0.011 /
# sqrt(n) off; 0.07 is six of those (2.2e-3 at 1023 tokens). A loss that
# skips or repeats some of the tokens is off by ~1 / sqrt(n).
LOSS_ATOL_SQRT_TOKENS = 0.07
CHECK_TOKENS = 1024
WARM_STEPS = 2
TRACE_STEPS = 3


def _loop(config: dict) -> None:
    """Runs in the train worker that leased the chips."""
    marks = [("loop_entered", time.time())]  # set-up's phases, by the wall clock

    def mark(name):
        marks.append((name, time.time()))

    import functools

    import jax
    import jax.numpy as jnp
    import optax

    from ray_tpu import train
    from ray_tpu.models import forward, init_params, loss_fn, param_axes
    from ray_tpu.parallel import MeshConfig, create_mesh
    from ray_tpu.parallel.sharding import logical_sharding, sharding_tree
    from ray_tpu.tpu import device_report, leased_devices

    from ..reference import dense_decoder

    model, sizes = config["model"], config["train"]
    cfg = llama_config(model, remat_policy=sizes["remat_policy"])
    mark("imports")
    devices = leased_devices()[:config["chips"]]
    mark("tpu_start")
    mesh = create_mesh(MeshConfig(**config["mesh"]), devices=devices)
    n_batch = math.prod(mesh.shape[a] for a in ("dcn", "dp", "fsdp"))
    rows_sharding = logical_sharding(mesh, ("batch", None))
    chunk = sizes["loss_chunk_tokens"]

    # weights on the device(s) in one jitted call, in the type they train in
    params = jax.jit(functools.partial(init_params, cfg),
                     out_shardings=sharding_tree(param_axes(cfg), mesh))(
        jax.random.PRNGKey(config["seed"]))
    opt = getattr(optax, sizes["optimizer"])(sizes["learning_rate"])
    opt_state = jax.jit(opt.init)(params)
    jax.block_until_ready((params, opt_state))
    mark("weights")

    @functools.partial(jax.jit, donate_argnums=(0, 1))
    def train_step(params, opt_state, batch):
        loss, grads = jax.value_and_grad(
            lambda p: loss_fn(p, batch, cfg, mesh=mesh, chunk_tokens=chunk))(params)
        updates, opt_state = opt.update(grads, opt_state, params)
        return optax.apply_updates(params, updates), opt_state, loss

    batches = iter(train.get_dataset_shard("train").iter_batches(
        batch_size=sizes["batch"], drop_last=True))

    def next_batch():
        host = next(batches, None)
        if host is None:
            raise RuntimeError(
                "the seeded rows ran out inside the run: raise the mix's rows_steps")
        return np.asarray(host["tokens"], np.int32)

    first = next_batch()
    batch = {"tokens": jax.device_put(first, rows_sharding)}
    t0 = time.monotonic()
    compiled = train_step.lower(params, opt_state, batch).compile()
    compile_s = time.monotonic() - t0
    mark("first_batch_and_step_program")
    mem = compiled.memory_analysis()
    program_bytes = int(mem.argument_size_in_bytes + mem.output_size_in_bytes
                        + mem.temp_size_in_bytes - mem.alias_size_in_bytes)

    # correctness, before the window: program vs plain reference, one
    # sequence per batch shard (the flash kernel runs per shard)
    check = first[:n_batch, :min(CHECK_TOKENS, first.shape[1])]
    on_device = jax.device_put(check, rows_sharding)
    prog_loss = float(jax.device_get(jax.jit(
        lambda p, t: loss_fn(p, {"tokens": t}, cfg, mesh=mesh, chunk_tokens=chunk))(
        params, on_device)))
    prog_logits = jax.jit(lambda p, t: forward(p, t, cfg, mesh=mesh))(params, on_device)
    ref_losses, logit_err = [], []
    for i, row in enumerate(check):
        ref_logits = dense_decoder.logits(
            params, jnp.asarray(row), rope_theta=model["rope_theta"],
            norm_eps=model["rms_norm_eps"])
        ref_losses.append(float(jax.device_get(
            dense_decoder.loss_of(ref_logits, jnp.asarray(row)))))
        logit_err.append(np.asarray(jax.device_get(
            dense_decoder.position_errors(prog_logits[i], ref_logits))))
    ref_loss = float(np.mean(ref_losses))
    logit_err = np.stack(logit_err)  # [sequence, position]
    del prog_logits, ref_logits
    mark("reference_check")

    def one_step(tokens):
        nonlocal params, opt_state
        params, opt_state, loss = compiled(
            params, opt_state, {"tokens": jax.device_put(tokens, rows_sharding)})
        return float(jax.device_get(loss))  # the completion fence

    for _ in range(WARM_STEPS):
        one_step(next_batch())
    mark("warm_steps")

    losses, step_t_a, step_ms, wait_ms, report_ms = [], [], [], [], []

    def timed_step():
        t_a = time.monotonic()
        tokens = next_batch()
        t_b = time.monotonic()
        losses.append(one_step(tokens))
        t_c = time.monotonic()
        train.report({"step": len(losses), "loss": losses[-1]})
        t_d = time.monotonic()
        step_t_a.append(t_a)
        wait_ms.append((t_b - t_a) * 1e3)
        step_ms.append((t_c - t_b) * 1e3)
        report_ms.append((t_d - t_c) * 1e3)

    trace_path = None
    t_w0_wall, t_w0 = time.time(), time.monotonic()
    # whole steps until the window's seconds have passed: the window ends
    # at a step boundary, so the rate is over all its work and all its time
    while time.monotonic() - t_w0 < config["seconds"]:
        if config["trace"] and len(losses) == 2:
            trace_path = capture_trace(
                lambda: [timed_step() for _ in range(TRACE_STEPS)],
                config["platform"])
        else:
            timed_step()
    window_s = time.monotonic() - t_w0
    device = device_report()
    summary = trace_path and reduce_trace(trace_path, config["platform"],
                                          config["unions"])
    train.report({"bench": {
        "t_window_start_wall": t_w0_wall, "window_s": window_s,
        "t_window_start_mono": t_w0, "clock_id": pauses.clock_id(),
        "steps": len(losses), "losses": losses, "step_t_a": step_t_a, "step_ms": step_ms,
        "data_wait_ms": wait_ms, "report_ms": report_ms,
        "compile_s": compile_s, "program_bytes": program_bytes, "marks": marks,
        "tpu_custom_calls": compiled.as_text().count("tpu_custom_call"),
        "prog_loss": prog_loss, "ref_loss": ref_loss,
        "check_tokens": int(check.shape[0] * (check.shape[1] - 1)),
        "logit_err_max": float(logit_err.max()),
        "logit_err_mean": float(logit_err.mean()),
        "device": device, "trace": summary}})


def run(ctx: Context) -> dict:
    from ray_tpu import data
    from ray_tpu.train import JaxTrainer, RunConfig, ScalingConfig

    cfg = ctx.rehearse or ctx.cell.config
    mix = ctx.cell.traffic
    seq = int(mix["seq"]) if not ctx.rehearse else int(ctx.rehearse["train_seq"])
    sizes = dict(cfg["train"])
    tokens_per_step = sizes["batch"] * seq
    rows = traffic.train_rows(mix, cfg["model"]["vocab_size"], sizes["batch"],
                              ctx.seed, seq=seq)
    marks = [("process_start", ctx.t_start_wall), ("parent_imports_and_rows", time.time())]
    watcher = pauses.Watcher()  # beside set-up and the window; stopped after it
    try:
        start_cluster(ctx)
        marks.append(("cluster", time.time()))
        resources, runtime_env = lease(ctx)
        result = JaxTrainer(
            _loop,
            train_loop_config={
                "model": cfg["model"], "train": sizes, "chips": ctx.cell.chips,
                "mesh": mix.get("mesh", {"dp": 1}), "seed": ctx.weight_seed,
                "seconds": ctx.seconds, "trace": ctx.trace,
                "platform": ctx.platform,
                "unions": layer_metrics.union_specs(ctx.cell.readers)},
            scaling_config=ScalingConfig(num_workers=1,
                                         resources_per_worker=resources,
                                         worker_runtime_env=runtime_env),
            run_config=RunConfig(name="bench-train",
                                 storage_path=tempfile.mkdtemp(prefix="bench-train-")),
            datasets={"train": data.from_numpy(rows, column="tokens")},
        ).fit()
    finally:
        watched = watcher.stop()
        stop_cluster()
    if result.error is not None:
        raise result.error
    m = (result.metrics or {}).get("bench")
    if m is None:
        raise RunFailure("the train worker reported no result")
    device = m["device"]
    check_device(device, ctx)
    chips = ctx.cell.chips
    window = pauses.window_report(m, watched, tokens_per_step=tokens_per_step,
                                  chips=chips, seconds=ctx.seconds)
    checks = {
        "losses_finite": all(math.isfinite(x) for x in m["losses"]),
        "logits_match_reference": m["logit_err_max"] <= LOGIT_RTOL,
        "loss_matches_reference": abs(m["prog_loss"] - m["ref_loss"])
        <= LOSS_ATOL_SQRT_TOKENS / math.sqrt(m["check_tokens"]),
        "flash_kernel_native": kernel_native(
            device["kernel_traces"], "flash_attention", ctx.platform),
        "custom_calls_compiled": (m["tpu_custom_calls"] > 0) == (ctx.platform == "tpu"),
    }
    marks += [tuple(x) for x in m["marks"]] + [("window_start", m["t_window_start_wall"])]
    ctx.say({"setup_phases_s": {b[0]: b[1] - a[1] for a, b in zip(marks, marks[1:])}})
    quart = lambda xs: [stats.percentile(xs, q) for q in (25, 50, 75)]  # noqa: E731
    ctx.say({"checks": checks, "prog_loss": m["prog_loss"], "ref_loss": m["ref_loss"],
             "logit_err_max": m["logit_err_max"], "logit_err_mean": m["logit_err_mean"],
             "check_tokens": m["check_tokens"],
             "steps": m["steps"], "window_s": m["window_s"],
             "step_ms_quartiles": quart(m["step_ms"]), "compile_s": m["compile_s"],
             **window["said"],
             "loss_first_last": [m["losses"][0], m["losses"][-1]],
             "program_bytes": m["program_bytes"],
             "peak_bytes_in_use": device["peak_bytes_in_use"],
             "tpu_custom_calls": m["tpu_custom_calls"],
             "kernel_traces": device["kernel_traces"]})
    out = {"correct": all(checks.values()), "attempted": m["steps"], "failed": 0,
           "device": {"platform": device["platform"], "kind": device["kind"],
                      "count": device["count"],
                      # the allocator's peak misses a program's temporaries on
                      # this backend (PERF.md): take the larger of it and the
                      # compiler's count for the step program
                      "memory_peak_bytes": max(max(device["peak_bytes_in_use"]),
                                               m["program_bytes"])}}
    if not ctx.trace:
        values = {"train_tok_s_chip": window["train_tok_s_chip"],
                  "setup_s": m["t_window_start_wall"] - ctx.t_start_wall}
    else:
        summary = m["trace"]
        if summary is None:
            raise RunFailure("the window ended before the trace was taken")
        ctx.say({"trace": {k: v for k, v in summary.items()
                           if k not in ("ops", "modules")},
                 "modules": summary["modules"]})
        peak = (ctx.rehearse["assumed_peak_flops_per_s"] if ctx.rehearse
                else flops.peaks(device["kind"])["bf16_flops_per_s"])
        obs = {"timers": {"data_wait_ms": window["data_wait_ms"],
                          "report_ms": window["report_ms"],
                          "step_ms_median": stats.percentile(m["step_ms"], 50)},
               # from the median step, not the window: the capture's own
               # start, stop and reduction sit inside a traced window
               "train": {"tok_s_chip": tokens_per_step / chips * 1e3
                         / stats.percentile(m["step_ms"], 50),
                         "peak_flops_per_s": peak,
                         "flops_per_token": flops.train_flops_per_token(cfg["model"], seq)},
               "trace": summary}
        values = layer_metrics.read_all(ctx.cell.readers, obs)
        out["device"].update(busy_s=summary["busy_s"], window_s=summary["window_s"])
        out["breakdown"] = {"device_ops": summary["device_ops"],
                            "idle_gaps": summary["idle_gaps"]}
    declared = ctx.cell.declared(ctx.trace)
    out["metrics"] = {k: {"value": v, "unit": declared[k]}
                      for k, v in values.items() if k in declared}
    return out

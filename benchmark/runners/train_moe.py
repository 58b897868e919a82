"""Runner of the training cells of a routed (mixture-of-experts) decoder:
the train runner's contract (``runners/train.py``: the same phases, the same
fenced steps, the same window rule, the same result line through
``result.emit``) with the configuration builder and the plain reference
swapped. Which model it builds is the configuration file's ``model_type``,
not this module's name, so that a later ``benchmark`` issue can fold the two
runners into one.

What decides ``correct``, all before the window, against
``reference/moe_decoder.py`` on the program's own bf16-rounded weights:

* logits at every position of the check rows (``LOGIT_RTOL``) and their
  median (``LOGIT_MEDIAN_RTOL``);
* the program's loss, auxiliary terms included (``LOSS_ATOL_SQRT_TOKENS``);
* ONE expert layer alone, at the configuration's widths, on a seeded bf16
  input (``LAYER_RTOL``): with random weights the whole model's logits can
  hide a routing fault that this shows;
* rows computed = tokens x experts per token in every step (nothing dropped);
* the grouped matmul and flash ran as native Pallas kernels on the chip.
"""

from __future__ import annotations

import json
import math
import os
import tempfile
import time

import numpy as np

from .. import flops, flops_moe, layer_metrics, pauses, stats, trace_reduce, traffic
from ..manifest import HERE
from . import (Context, RunFailure, capture_trace, check_device, kernel_native,
               lease, reduce_trace, start_cluster, stop_cluster)
from .train import CHECK_TOKENS, LOSS_ATOL_SQRT_TOKENS, TRACE_STEPS, WARM_STEPS

# A token is "near a tie" where the reference's k-th and (k+1)-th router
# probabilities lie closer than this share of the k-th: a bf16 and a float32
# hidden state may then order the two differently, which swaps one expert of
# the token's k for another and is no fault. In the layer check both sides
# see the SAME bf16 input and route in float32 at full precision, so only
# summation order separates them, and a token within 1e-4 is counted and
# reported, not compared (0-2 of 1,024 on the chip). In the whole model the
# program's hidden state carries bf16 rounding, ~0.4% of a router logit: on
# the chip at the published widths positions within 2% in any of 3 layers
# are 55-66% of all, and they alone read above 0.035 (PERF.md Findings, PR
# 26). There every position is compared, against a limit that allows for
# one swapped expert; the share of such positions and the worst of the
# others are reported.
LAYER_TIE_GAP = 1e-4
MODEL_TIE_GAP = 0.02
# The program's bf16 logits against the reference's float32 ones, position by
# position: RMS of the difference over the vocabulary as a share of the RMS
# of the reference's logits there (``dense_decoder.position_errors``). Two
# limits, with the chip's readings at the published widths over six seeds
# (PERF.md Findings, PR 26):
# * the MEDIAN over all positions: 0.0061-0.0072; top-7 for top-8 reads
#   0.039, fp8 weights 0.109, QK-norm left out 0.225 (its weights alone
#   0.155), a neighbour's expert weights 0.32, renormalised gates 0.42;
# * EVERY position: the worst reads 0.048-0.058, a position where bf16
#   swapped an expert (the worst position not near a tie: 0.010-0.034); fp8
#   weights read 0.131 here, a dense decoder's dropped layer 0.2. Twice the
#   dense runner's 0.05 is what one swapped expert of eight costs.
LOGIT_MEDIAN_RTOL = 0.02
LOGIT_RTOL = 0.1
# One expert layer, program (bf16, sorted dispatch, grouped matmul) against
# reference (float32, every expert on every token) on the same seeded bf16
# input, per token: RMS of the difference over the features as a share of
# the RMS of the reference's output; the worst token decides. bf16 products
# with float32 accumulation and one bf16 rounding of gate, up and the gated
# activation read 0.0044-0.0046 on the chip at the published widths (mean
# 0.0039). What it must catch, same runs, worst token / mean: top-7 for
# top-8 0.357 / 0.214, renormalised gates 2.53 / 1.65, an expert's rows times
# its neighbour's weights 1.55 / 1.35 (ONE expert's down projection swapped:
# 1.13 / 0.064), weights rounded to fp8 0.062 / 0.056. With the router's
# product at the TPU's default precision it read 0.37-0.46 itself: that is
# how ``moe.route`` came to ask for ``highest``.
LAYER_RTOL = 0.02
LAYER_TIES_MAX = 0.01  # of the layer check's tokens; 0-2 of 1,024 on the chip
QK_NORM_SPREAD = 0.5  # seeded q/k norm weights are uniform in 1 +- this


def model_config(model: dict, sizes: dict, **overrides):
    """The program's config object for a configuration file's ``model`` and
    ``train`` groups, by ``model_type``."""
    from ray_tpu.models.llama import LlamaConfig

    if model.get("model_type") != "olmoe":
        raise RunFailure(f"runner train_moe builds no model of type "
                         f"{model.get('model_type')!r}")
    return LlamaConfig(
        vocab_size=model["vocab_size"], hidden=model["hidden_size"],
        n_layers=model["num_hidden_layers"], n_heads=model["num_attention_heads"],
        n_kv_heads=model["num_key_value_heads"],
        intermediate=model["intermediate_size"], head_dim=model["head_dim"],
        rope_theta=float(model["rope_theta"]), norm_eps=float(model["rms_norm_eps"]),
        moe_experts=model["num_experts"], moe_top_k=model["num_experts_per_tok"],
        moe_norm_topk=bool(model["norm_topk_prob"]), qk_norm=True,
        moe_aux_weight=sizes["aux_loss_weight"], moe_z_weight=sizes["z_loss_weight"],
        **overrides)


def reference_arch(model: dict) -> dict:
    """What ``reference/moe_decoder.py`` needs to know of the same file."""
    return dict(rope_theta=float(model["rope_theta"]),
                norm_eps=float(model["rms_norm_eps"]),
                top_k=model["num_experts_per_tok"],
                norm_topk=bool(model["norm_topk_prob"]))


def seed_qk_norms(params, key):
    """Give the q and k norm weights seeded values in 1 +- QK_NORM_SPREAD.
    ``init_params`` makes them ones, as the model's own init does; against
    ones a program that left the norm's weight out, or the norm itself where
    the projection's RMS is near 1, would read the same as one that did not."""
    import jax

    layers = dict(params["layers"])
    for i, name in enumerate(("q_norm", "k_norm")):
        w = layers[name]
        u = jax.random.uniform(jax.random.fold_in(key, 101 + i), w.shape,
                               minval=-QK_NORM_SPREAD, maxval=QK_NORM_SPREAD)
        layers[name] = (1.0 + u).astype(w.dtype)
    return {**params, "layers": layers}


def near_ties(probs, top_k: int, gap: float):
    """[..., X] router probabilities -> [...] bool: the k-th and (k+1)-th
    largest lie within ``gap`` of the k-th."""
    import jax.numpy as jnp

    ranked = -jnp.sort(-probs, axis=-1)
    return ranked[..., top_k - 1] - ranked[..., top_k] < gap * ranked[..., top_k - 1]


def layer_errors(h, program, reference) -> dict:
    """One expert layer alone: ``program`` and ``reference`` are (layer
    weights, top_k, norm_topk); the program's ``moe_block`` against the
    reference's ``expert_layer`` on the same input h [S, E]. Returns the
    per-token errors' max and mean over the compared tokens, the near-ties
    set aside, and the rows the program computed and dropped."""
    import jax

    from ray_tpu.models.moe import moe_block

    from ..reference import moe_decoder

    weights, top_k, norm_topk = program
    got, aux = jax.jit(lambda h, w: moe_block(h[None], w, top_k=top_k,
                                              norm_topk=norm_topk))(h, weights)
    weights, top_k, norm_topk = reference
    want, routing = moe_decoder.expert_layer(h, weights, top_k=top_k, norm_topk=norm_topk)
    err = np.asarray(jax.device_get(moe_decoder.position_errors(got[0], want)))
    tie = np.asarray(jax.device_get(near_ties(routing["probs"], top_k, LAYER_TIE_GAP)))
    return {"max": float(err[~tie].max()), "mean": float(err[~tie].mean()),
            "ties": int(tie.sum()), "tokens": int(err.size),
            "rows": int(np.asarray(aux["rows"]).sum()), "dropped": int(aux["dropped"])}


def model_errors(cfg, params, rows, arch, *, mesh=None) -> dict:
    """The whole model: the program's ``forward`` on token rows [B, S] against
    the reference, position by position; positions where any layer's routing
    is near a tie are counted, and the worst of the others reported."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models import forward

    from ..reference import moe_decoder

    prog = jax.jit(lambda p, t: forward(p, t, cfg, mesh=mesh))(params, rows)
    errs, ties, ces, routings = [], [], [], []
    for i, row in enumerate(np.asarray(rows)):
        ref, routing = moe_decoder.logits(params, jnp.asarray(row), **arch)
        errs.append(np.asarray(jax.device_get(moe_decoder.position_errors(prog[i], ref))))
        ties.append(np.asarray(jax.device_get(
            near_ties(routing["probs"], arch["top_k"], MODEL_TIE_GAP).any(axis=0))))
        ces.append(moe_decoder.loss_of(ref, jnp.asarray(row)))
        routings.append(routing)
    err, tie = np.stack(errs), np.stack(ties)
    balance, z = moe_decoder.aux_losses(routings)
    ref_loss = (jnp.mean(jnp.stack(ces)) + cfg.moe_aux_weight * balance
                + cfg.moe_z_weight * z)
    return {"max": float(err.max()), "median": float(np.median(err)),
            "max_not_near_a_tie": float(err[~tie].max()) if not tie.all() else 0.0,
            "near_a_tie_share": float(tie.mean()),
            "ref_loss": float(jax.device_get(ref_loss)),
            "ref_load_balance": float(jax.device_get(balance)),
            "ref_z": float(jax.device_get(z))}


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
    from ray_tpu.models import init_params, loss_fn, param_axes
    from ray_tpu.parallel import MeshConfig, create_mesh
    from ray_tpu.parallel.sharding import logical_sharding, sharding_tree
    from ray_tpu.tpu import device_report, leased_devices

    model, sizes = config["model"], config["train"]
    overrides = {"remat_policy": sizes["remat_policy"]}
    if "dtype" in sizes:  # the rehearsal's float32; a configuration states none
        overrides["dtype"] = jnp.dtype(sizes["dtype"])
    cfg = model_config(model, sizes, **overrides)
    arch = reference_arch(model)
    mark("imports")
    devices = leased_devices()[:config["chips"]]
    mark("tpu_start")
    mesh = create_mesh(MeshConfig(**config["mesh"]), devices=devices)
    n_batch = math.prod(mesh.shape[a] for a in ("dcn", "dp", "fsdp"))
    rows_sharding = logical_sharding(mesh, ("batch", None))
    chunk = sizes["loss_chunk_tokens"]

    # weights on the device(s) in one jitted call, in the type they train in
    # the seed goes in as the key's value: a constant in the program would
    # compile it anew for every seed (29 s of set-up, builder's runs, PR 26)
    params = jax.jit(
        lambda key: seed_qk_norms(init_params(cfg, key), key),
        out_shardings=sharding_tree(param_axes(cfg), mesh))(
        jax.random.PRNGKey(config["seed"]))
    opt = getattr(optax, sizes["optimizer"])(sizes["learning_rate"])
    opt_state = jax.jit(opt.init)(params)
    jax.block_until_ready((params, opt_state))
    mark("weights")

    def loss_and_counters(p, batch):
        return loss_fn(p, batch, cfg, mesh=mesh, chunk_tokens=chunk, return_aux=True)

    @functools.partial(jax.jit, donate_argnums=(0, 1))
    def train_step(params, opt_state, batch):
        (loss, aux), grads = jax.value_and_grad(
            lambda p: loss_and_counters(p, batch), has_aux=True)(params)
        updates, opt_state = opt.update(grads, opt_state, params)
        # the program's counters, from the same pass as the loss
        counters = (loss, aux["rows_per_expert"], aux["rows_dropped"])
        return optax.apply_updates(params, updates), opt_state, counters

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
    prog_loss, prog_aux = jax.device_get(jax.jit(
        lambda p, t: loss_and_counters(p, {"tokens": t}))(params, on_device))
    whole = model_errors(cfg, params, on_device, arch, mesh=mesh)
    layer0 = jax.tree.map(lambda a: a[0], params["layers"])
    h = jax.random.normal(jax.random.PRNGKey(config["seed"] + 1),
                          (check.shape[1], cfg.hidden), cfg.dtype)
    side = (layer0, cfg.moe_top_k, cfg.moe_norm_topk)
    layer = layer_errors(h, side, side)
    del layer0, h
    mark("reference_check")

    rows_per_step = sizes["batch"] * first.shape[1] * cfg.moe_top_k
    losses, load, rows_wrong = [], [], []
    step_t_a, step_ms, wait_ms, report_ms = [], [], [], []

    def one_step(tokens):
        nonlocal params, opt_state
        params, opt_state, counters = compiled(
            params, opt_state, {"tokens": jax.device_put(tokens, rows_sharding)})
        loss, rows, dropped = jax.device_get(counters)  # the completion fence
        if int(dropped) or (rows.sum(axis=-1) != rows_per_step).any():
            rows_wrong.append([int(dropped), rows.sum(axis=-1).tolist()])
        return float(loss), float((rows.max(axis=-1) / rows.mean(axis=-1)).mean())

    for _ in range(WARM_STEPS):
        one_step(next_batch())
    mark("warm_steps")

    def timed_step():
        t_a = time.monotonic()
        tokens = next_batch()
        t_b = time.monotonic()
        loss, max_over_mean = one_step(tokens)
        t_c = time.monotonic()
        losses.append(loss)
        load.append(max_over_mean)
        train.report({"step": len(losses), "loss": loss,
                      "moe_load_max_over_mean": max_over_mean})
        t_d = time.monotonic()
        step_t_a.append(t_a)
        wait_ms.append((t_b - t_a) * 1e3)
        step_ms.append((t_c - t_b) * 1e3)
        report_ms.append((t_d - t_c) * 1e3)

    trace_path, traced = None, [0, 0]
    t_w0_wall, t_w0 = time.time(), time.monotonic()
    # whole steps until the window's seconds have passed: the window ends
    # at a step boundary, so the rate is over all its work and all its time
    while time.monotonic() - t_w0 < config["seconds"]:
        if config["trace"] and len(losses) == 2:
            traced = [len(losses), len(losses) + TRACE_STEPS]
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
        "prog_loss": float(prog_loss), "prog_ce": float(prog_aux["ce"]),
        "prog_load_balance": float(prog_aux["load_balance"]), "prog_z": float(prog_aux["z"]),
        "check_rows_per_layer": prog_aux["rows_per_expert"].sum(axis=-1).tolist(),
        "check_rows_dropped": int(prog_aux["rows_dropped"]),
        "check_tokens": int(check.shape[0] * (check.shape[1] - 1)),
        "check_positions": int(check.size), "rows_per_token": cfg.moe_top_k,
        "whole": whole, "layer": layer, "rows_wrong": rows_wrong[:5],
        "load_max_over_mean": load, "traced_steps": traced,
        "device": device, "trace": summary}})


def run(ctx: Context) -> dict:
    cfg = ctx.cell.config
    if ctx.rehearse:
        with open(os.path.join(HERE, "rehearse-moe.json")) as f:
            cfg = {**ctx.rehearse, **json.load(f)}
    mix = ctx.cell.traffic
    seq = int(mix["seq"]) if not ctx.rehearse else int(cfg["train_seq"])
    sizes = dict(cfg["train"])
    # before a cluster or a chip is touched: a program that cannot describe
    # this model (one from before it was supported) fails here, at once
    model_config(cfg["model"], sizes)
    from ray_tpu import data
    from ray_tpu.train import JaxTrainer, RunConfig, ScalingConfig

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
            run_config=RunConfig(name="bench-train-moe",
                                 storage_path=tempfile.mkdtemp(prefix="bench-train-moe-")),
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
    whole, layer, traces = m["whole"], m["layer"], device["kernel_traces"]
    n_layers = cfg["model"]["num_hidden_layers"]
    checks = {
        "losses_finite": all(math.isfinite(x) for x in m["losses"]),
        "logits_match_reference": whole["median"] <= LOGIT_MEDIAN_RTOL
        and whole["max"] <= LOGIT_RTOL,
        "loss_matches_reference": abs(m["prog_loss"] - whole["ref_loss"])
        <= LOSS_ATOL_SQRT_TOKENS / math.sqrt(m["check_tokens"]),
        "expert_layer_matches_reference": layer["max"] <= LAYER_RTOL
        and layer["ties"] <= LAYER_TIES_MAX * layer["tokens"],
        "no_row_dropped": not m["rows_wrong"] and m["check_rows_dropped"] == 0
        and layer["dropped"] == 0
        and layer["rows"] == layer["tokens"] * m["rows_per_token"]
        and m["check_rows_per_layer"]
        == [m["check_positions"] * m["rows_per_token"]] * n_layers,
        "flash_kernel_native": kernel_native(traces, "flash_attention", ctx.platform),
        "grouped_matmul_native": kernel_native(traces, "moe_gmm", ctx.platform)
        and kernel_native(traces, "moe_tgmm", ctx.platform),
        "custom_calls_compiled": (m["tpu_custom_calls"] > 0) == (ctx.platform == "tpu"),
    }
    marks += [tuple(x) for x in m["marks"]] + [("window_start", m["t_window_start_wall"])]
    ctx.say({"setup_phases_s": {b[0]: b[1] - a[1] for a, b in zip(marks, marks[1:])}})
    quart = lambda xs: [stats.percentile(xs, q) for q in (25, 50, 75)]  # noqa: E731
    ctx.say({"checks": checks, "limits": {
        "LOGIT_MEDIAN_RTOL": LOGIT_MEDIAN_RTOL, "LOGIT_RTOL": LOGIT_RTOL,
        "LAYER_RTOL": LAYER_RTOL,
        "loss_atol": LOSS_ATOL_SQRT_TOKENS / math.sqrt(m["check_tokens"]),
        "MODEL_TIE_GAP": MODEL_TIE_GAP, "LAYER_TIE_GAP": LAYER_TIE_GAP},
        "whole_model": whole, "expert_layer": layer,
        "prog_loss": m["prog_loss"], "prog_ce": m["prog_ce"],
        "prog_load_balance": m["prog_load_balance"], "prog_z": m["prog_z"],
        "check_tokens": m["check_tokens"],
        "check_rows_per_layer": m["check_rows_per_layer"],
        "rows_wrong": m["rows_wrong"],
        "load_max_over_mean_quartiles": quart(m["load_max_over_mean"]),
        "steps": m["steps"], "window_s": m["window_s"],
        "step_ms_quartiles": quart(m["step_ms"]), "compile_s": m["compile_s"],
        **window["said"],
        "loss_first_last": [m["losses"][0], m["losses"][-1]],
        "program_bytes": m["program_bytes"],
        "peak_bytes_in_use": device["peak_bytes_in_use"],
        "tpu_custom_calls": m["tpu_custom_calls"],
        "kernel_traces": traces, "kernel_costs": device.get("kernel_costs")})
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
        # the grouped-matmul calls the trace holds, recomputed ones included,
        # and THEIR seconds: both sides of the roofline count the same calls.
        # A CPU rehearsal interprets the kernel into plain ops, so its trace
        # holds none: the share of peak then reads 0 over the window.
        share = ctx.cell.readers.get("kernel.moe_gmm_share.train")
        gmm_s, gmm_calls = (trace_reduce.matching(summary["ops"], share["params"]["pattern"])
                            if share else (0.0, 0))
        first, last = m["traced_steps"]
        ctx.say({"moe_gmm_calls": gmm_calls, "moe_gmm_seconds": gmm_s})
        obs = {"timers": {"data_wait_ms": window["data_wait_ms"],
                          "report_ms": window["report_ms"],
                          "step_ms_median": stats.percentile(m["step_ms"], 50)},
               # from the median step, not the window: the capture's own
               # start, stop and reduction sit inside a traced window
               "train": {"tok_s_chip": tokens_per_step / chips * 1e3
                         / stats.percentile(m["step_ms"], 50),
                         "peak_flops_per_s": peak,
                         "flops_per_token": flops_moe.train_flops_per_token(
                             cfg["model"], seq)},
               "moe": {"load_max_over_mean": stats.mean(m["load_max_over_mean"][first:last]),
                       "gmm_flops_per_call": flops_moe.grouped_matmul_flops(
                           cfg["model"], tokens_per_step),
                       "gmm_calls": gmm_calls,
                       "gmm_seconds": gmm_s if gmm_calls else summary["window_s"]},
               "trace": summary}
        values = layer_metrics.read_all(ctx.cell.readers, obs)
        out["device"].update(busy_s=summary["busy_s"], window_s=summary["window_s"])
        out["breakdown"] = {"device_ops": summary["device_ops"],
                            "idle_gaps": summary["idle_gaps"]}
    declared = ctx.cell.declared(ctx.trace)
    out["metrics"] = {k: {"value": v, "unit": declared[k]}
                      for k, v in values.items() if k in declared}
    return out

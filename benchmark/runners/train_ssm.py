"""Runner of the training cells of a decoder of Mamba-2 state-space layers beside
un-roped grouped-query attention, under fixed multipliers and a tied
vocabulary (granite-4.0-h-micro). The train runner's contract
(``runners/train.py``: the same phases, the same fenced steps, the same window
rule through ``pauses.window_report``, the same result line through
``result.emit``) with the configuration builder and the plain reference
swapped, as ``train_sala.py``; the step comparison (``step_errors``) and the
set-up accounting (``COMPARISON_PHASES``) are ``train_swa.py``'s. Which model it
builds is the configuration file's ``model_type``.

What decides ``correct``, all before the window, against
``reference/ssm_decoder.py`` on the program's own bf16-rounded weights (the
program's draw, every norm weight and every head's skip moved by a seeded
+-0.5 so that one left out shows):

* THE RECURRENCE ALONE on seeded bf16 x, B and C and seeded steps of
  CHECK_TOKENS positions at the last state-space layer's rates, float32 out:
  the kernel against the reference's scan over positions (``STATE_RTOL``);
* ONE layer of each kind alone, at the configuration's widths, on a seeded
  bf16 input of CHECK_TOKENS positions: its output and the gradient of its
  input under a seeded cotangent (``MIXER_RTOL``, ``MIXER_GRAD_RTOL``);
* logits at every position of the batch's first row (``LOGIT_MEDIAN_RTOL``,
  ``LOGIT_MAX_RTOL``);
* THE TIMED STEP ITSELF, run once on the first batch: its loss
  (``LOSS_ATOL``), the statistics of its first gradient that the optimizer's
  new state holds and the change of every parameter leaf, against the
  reference's gradient on the same rows put through the same optimizer in
  float32 (``GRAD_STATS_RTOL``, ``UPDATE_ALONG_ATOL``);
* the counter, in that step and in every step of the window: the mean decay
  ``exp(dt A)`` lies inside (``DECAY_MEAN_RANGE``): the carried state matters;
* the scan and attention kernels ran native on the chip.

``BENCH_SSM_CONTROL`` in the environment puts a fault in the program's place
(or, for two, in the reference's), for showing that the comparison refuses it
(``CONTROLS``); such a run says so in its output and must end ``correct``
false.
"""

from __future__ import annotations

import functools
import json
import math
import os
import re
import tempfile
import time

import numpy as np

from .. import flops, flops_ssm, layer_metrics, pauses, stats, trace_reduce, traffic
from ..manifest import HERE
from . import (Context, RunFailure, capture_trace, check_device, kernel_native,
               lease, reduce_trace, start_cluster, stop_cluster)
from .train import TRACE_STEPS, WARM_STEPS
from .train_hybrid import NORM_SPREAD, _rel, seed_norms
from .train_swa import COMPARISON_PHASES, step_errors

# Tokens of a layer's check input: 32 chunks of the scan.
CHECK_TOKENS = 8192
# What can stand in the program's place (``BENCH_SSM_CONTROL``). The first five
# change the program that is timed and compared (the same leaves read
# otherwise, or a leaf zeroed); the sixth stands in the recurrence's own
# comparison alone; the next two change the REFERENCE; the last two leave the
# program as it is and change what the compared step is given or what is kept
# of it.
CONTROLS = {
    "fp8_weights": "the program computes with its bf16 weights rounded to float8_e4m3fn, "
                   "the nearest precision below the configuration's",
    "no_skip": "every state-space head's skip D is zero in the program: y = S C alone",
    "no_dt_bias": "dt_bias is zero in the program: dt = softplus(h W_dt)",
    "sqrt_scale": "the attention layer's scores are scaled by 64^-1/2, not by "
                  "attention_multiplier (1/64)",
    "residual_1": "a residual branch joins the stream unscaled: residual_multiplier left at 1",
    "bf16_state": "the recurrence alone, position by position with its state stored in "
                  "bfloat16",
    "gate_after_norm": "the REFERENCE norms y and then gates it (the gated DeltaNet's order), "
                       "where the model gates inside the norm",
    "reference_default_precision": "the REFERENCE's float32 products run at the backend's "
                                   "default precision (one bf16 pass on a TPU)",
    "half_batch": "the compared step is given the first half of its batch's tokens twice",
    "unchanged_state": "the compared step's new parameters and optimizer state are thrown away",
}
# The limits. Errors are the RMS of the difference over the features of a
# position (or over a leaf) as a share of the RMS of the reference's there.
# Each lies between two readings of THIS cell on the chip at the published
# widths, through this runner (my chip runs, PR 53; PERF.md section 6): the
# largest a sound run gave over its seeds (ten sound runs on ten seeds, and
# of the seven controls run the readings a control leaves alone) and the
# smallest that a control it is meant to refuse gave; the limit is their
# geometric mean.
# * The recurrence alone, float32 out, over all of [64, 8192, 64]: 7.08e-6 to
#   7.68e-6; a bfloat16 state 2.04e-3 (D left out 0.863). The reference's own
#   precision hardly shows here (8.12e-6 at the backend's default).
STATE_RTOL = 1.2e-4
# * One mixer alone on a seeded bf16 input of 8,192 positions, worst token: the
#   state-space layer 0.0058-0.0075, the attention layer 0.0033-0.0035; fp8
#   weights 0.0508 (the attention layer; the state-space one 0.0930; D left
#   out 1.68, dt_bias left out 1.88, 64^-1/2 for 1/64 1.12).
MIXER_RTOL = 0.019
#   and the gradient of that input under a seeded bf16 cotangent, worst token:
#   0.0056-0.0070 and 0.0043-0.0045; fp8 0.0705 (attention; state-space 0.0959).
MIXER_GRAD_RTOL = 0.022
# * Logits of the first row's 32,768 positions. The MEDIAN: 0.013587-0.013783
#   over eight seeds (mean 0.013653, standard deviation 0.00007); the REFERENCE
#   at the backend's default precision 0.014570, the smallest a control gave
#   (64^-1/2 for 1/64 0.0166, fp8 0.194, dt_bias left out 0.837, the residual
#   multiplier at 1 0.996, D left out 1.14). The reference at one bf16 pass
#   stands as far from the exact one as the bf16 program does, so the two
#   readings are a factor 1.057 apart and the limit has 2.8% of room either
#   way, seven standard deviations of the sound runs: what holds it is the
#   median's steadiness (as the ninth cell's, `train_sala.py`).
LOGIT_MEDIAN_RTOL = 0.01417
#   The WORST position: 0.01687-0.01812; default precision 0.01776 is refused
#   by the median, so the worst position's limit lies between the sound runs
#   and the next control, 64^-1/2 for 1/64 at 0.0984.
LOGIT_MAX_RTOL = 0.042
# * The compared step's loss on the first batch against the reference's over
#   the same 32,767 target tokens: sound runs within 1.24e-5; the residual
#   multiplier at 1 6.9e-5 (fp8 1.3e-4, D left out 3.5e-4). A WEAK limit by
#   nature (64^-1/2 for 1/64 moves it by 1.7e-5); no control rests on it alone.
LOSS_ATOL = 3e-5
# * The compared step's first gradient by what adafactor's new state holds of
#   it, the worst leaf: 0.055-0.112 on nine seeds and 0.224 on a tenth (always
#   an ``a_log`` or a ``dt_bias``: 64 numbers a layer, each a sum over 32,768
#   positions of terms that cancel, so bf16's rounding of x, B and C shows and
#   the reading moves with the seed; the median leaf 0.0229-0.0237); fp8 1.14
#   (the residual multiplier at 1 10.4, 64^-1/2 for 1/64 74). The reference's
#   precision does not show in the worst leaf (0.0763) and is the median
#   logits' to refuse.
GRAD_STATS_RTOL = 0.5
# * The change of every parameter leaf ALONG the reference's float32 update,
#   the worst judged leaf: 0.0280-0.0587 (a ``conv_bc``: 1,024 numbers); 64^-1/2
#   for 1/64 0.2635 (fp8 0.289, the residual multiplier at 1 0.980).
UPDATE_ALONG_ATOL = 0.12
# The mean of exp(dt A) over layers, heads and positions: the seeded steps
# (log-uniform in [0.001, 0.1] through softplus, around a projection of the
# input) against rates of 1..64 give decays from e^-0.001 to e^-6.4 a position.
# Near 0 no state would be carried and near 1 nothing forgotten.
DECAY_MEAN_RANGE = (0.05, 0.95)


def model_config(model: dict, sizes: dict, control: str | None = None, **overrides):
    """The program's config object for a configuration file's ``model`` and
    ``train`` groups; ``control`` plants a fault. A program from before this
    model was supported fails here (no ``models.mamba2``), before a cluster
    or a chip is touched."""
    if model.get("model_type") != "granitemoehybrid":
        raise RunFailure(f"runner train_ssm builds no model of type {model.get('model_type')!r}")
    try:
        from ray_tpu.models.gqa import ScaledGroupedQueryAttention
        from ray_tpu.models.llama import LlamaConfig
        from ray_tpu.models.mamba2 import Mamba2
    except ImportError as e:
        raise RunFailure(f"this program cannot describe the model: {e}") from e

    kinds, scales = flops_ssm.kinds(model), flops_ssm.multipliers(model)
    if control == "residual_1":
        scales["residual_scale"] = 1.0
    if control == "sqrt_scale":   # the spec's default: head_dim ** -0.5
        kinds["gqa"]["softmax_scale"] = None
    return LlamaConfig(
        vocab_size=model["vocab_size"], hidden=model["hidden_size"],
        n_layers=model["num_hidden_layers"], n_heads=model["num_attention_heads"],
        n_kv_heads=model["num_key_value_heads"], head_dim=kinds["gqa"]["head_dim"],
        intermediate=model["shared_intermediate_size"], norm_eps=float(model["rms_norm_eps"]),
        layer_pattern=tuple(flops_ssm.period(model)),
        mamba2=Mamba2(**kinds["mamba2"]), gqa=ScaledGroupedQueryAttention(**kinds["gqa"]),
        tie_embeddings=model["tie_word_embeddings"], **scales, **overrides)


def reference_arch(model: dict, control: str | None = None) -> dict:
    """What ``reference/ssm_decoder.py`` needs to know of the file."""
    return dict(kinds=flops_ssm.kinds(model), pattern=tuple(flops_ssm.period(model)),
                lead_pattern=(), norm_eps=float(model["rms_norm_eps"]),
                gate_inside=control != "gate_after_norm", **flops_ssm.multipliers(model))


def seed_leaves(params, key):
    """``seed_norms``, and every state-space head's skip D moved by the same
    seeded +-NORM_SPREAD: they start at 1 all alike, and one read for another
    would read the same."""
    import jax

    def move(path, leaf):
        if str(getattr(path[-1], "key", "")) != "d_skip":
            return leaf
        return leaf + jax.random.uniform(jax.random.fold_in(key, 53), leaf.shape,
                                         minval=-NORM_SPREAD, maxval=NORM_SPREAD)

    return jax.tree_util.tree_map_with_path(move, seed_norms(params, key))


def planted(params, control: str | None):
    """The program's leaves under a control that zeroes one."""
    import jax.numpy as jnp

    leaf = {"no_skip": "d_skip", "no_dt_bias": "dt_bias"}.get(control)
    if leaf is None:
        return params
    return {**params, "layers": {
        slot: {**layer, leaf: jnp.zeros_like(layer[leaf])} if leaf in layer else layer
        for slot, layer in params["layers"].items()}}


def _worst(err) -> dict:
    return {"max": float(err.max()), "mean": float(err.mean())}


def layer_errors(cfg, arch, layers, ref_layers, h, g, operands, control=None) -> dict:
    """The recurrence alone and one layer of each kind alone. ``layers`` = (a
    state-space layer's leaves, an attention layer's), ``ref_layers`` the
    reference's; h, g [S, E] the layers' input (bf16, already normed) and the
    cotangent of their output; ``operands`` = (x [1, H/r, S, rP], dt [1, H, S]
    float32, B, C [1, S, N]) of the recurrence."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models.gqa import gqa_mixer
    from ray_tpu.models.mamba2 import mamba2_mixer
    from ray_tpu.ops.ssd import ssd, ssd_scan

    from ..reference import ssm_decoder as ref

    get = lambda x: np.asarray(jax.device_get(x))  # noqa: E731
    f32 = lambda x: x.astype(jnp.float32)  # noqa: E731
    positions = jnp.arange(h.shape[0], dtype=jnp.int32)
    spec = arch["kinds"]["mamba2"]
    rate, skip = -jnp.exp(f32(layers[0]["a_log"])), f32(layers[0]["d_skip"])
    if control == "bf16_state":
        run = lambda x, dt, b, c: ssd_scan(  # noqa: E731
            x, dt, rate, b, c, skip, state_dtype=jnp.bfloat16)
    else:
        run = lambda x, dt, b, c: ssd(  # noqa: E731
            x, dt, rate, b, c, skip, chunk=spec["chunk"], out_dtype=jnp.float32)
    got = jax.jit(run)(*operands)[0]                                       # [H/r, S, rP]
    heads, p = spec["heads"], spec["head_dim"]

    def by_head(t):   # [H/r, S, rP] -> [S, H, P]
        return t.reshape(t.shape[0], t.shape[1], -1, p).swapaxes(0, 1).reshape(-1, heads, p)

    ref_rate = -jnp.exp(f32(ref_layers[0]["a_log"]))
    want = jax.jit(lambda x, dt, b, c: ref.recurrence(
        by_head(f32(x[0])), dt[0].T, ref_rate, f32(b[0]), f32(c[0]),
        f32(ref_layers[0]["d_skip"])))(*operands)
    out = {"recurrence": {"all": float(get(_rel(by_head(got), want, None)))}}
    del got, want

    def both(fn, h, g):
        """(y, dL/dh) of ``fn(h)`` -> y [S, E] under the cotangent g."""
        y, pull = jax.vjp(fn, h)
        return y, pull(g.astype(y.dtype))[0]

    got = jax.jit(lambda w: both(lambda h: mamba2_mixer(
        h[None], w, config=cfg, positions=positions)[0][0], h, g))(layers[0])
    want = jax.jit(lambda w: both(lambda h: ref.mamba_mixer(
        h, w, spec, arch["norm_eps"], gate_inside=arch["gate_inside"]), f32(h), f32(g)))(
            ref_layers[0])
    out["mamba"] = {"out": _worst(get(_rel(got[0], want[0], -1))),
                    "grad": _worst(get(_rel(got[1], want[1], -1)))}
    del got, want
    got = jax.jit(lambda w: both(lambda h: gqa_mixer(
        h[None], w, cfg.gqa, config=cfg, positions=positions)[0][0], h, g))(layers[1])
    want = jax.jit(lambda w: both(lambda h: ref.attention_mixer(
        h, w, arch["kinds"]["gqa"]), f32(h), f32(g)))(ref_layers[1])
    out["attention"] = {"out": _worst(get(_rel(got[0], want[0], -1))),
                        "grad": _worst(get(_rel(got[1], want[1], -1)))}
    return out


def check_operands(cfg, key, n: int):
    """Seeded operands of the recurrence alone at ``n`` positions: x, B, C
    standard normal in bf16, steps log-uniform over the seeded range."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models.mamba2 import DT_RANGE
    from ray_tpu.ops.ssd import heads_a_tile

    a = cfg.mamba2
    r = heads_a_tile(a.head_dim)
    keys = jax.random.split(key, 4)
    normal = lambda k, shape: jax.random.normal(k, shape, jnp.bfloat16)  # noqa: E731
    low, high = (math.log(x) for x in DT_RANGE)
    return (normal(keys[0], (1, a.heads // r, n, r * a.head_dim)),
            jnp.exp(jax.random.uniform(keys[1], (1, a.heads, n), jnp.float32, low, high)),
            normal(keys[2], (1, n, a.state)), normal(keys[3], (1, n, a.state)))


def _loop(config: dict) -> None:
    """Runs in the train worker that leased the chips."""
    marks = [("loop_entered", time.time())]  # set-up's phases, by the wall clock

    def mark(name):
        marks.append((name, time.time()))

    import jax
    import jax.numpy as jnp
    import optax

    from ray_tpu import train
    from ray_tpu.models import init_params, loss_fn, param_axes
    from ray_tpu.models.llama import forward_hidden
    from ray_tpu.parallel import MeshConfig, create_mesh
    from ray_tpu.parallel.sharding import logical_sharding, sharding_tree
    from ray_tpu.tpu import device_report, leased_devices

    from ..reference import ssm_decoder as ref

    model, sizes, control = config["model"], config["train"], config["control"]
    overrides = {"remat_policy": sizes["remat_policy"]}
    if "dtype" in sizes:  # the rehearsal's float32; a configuration states none
        overrides["dtype"] = jnp.dtype(sizes["dtype"])
    true_cfg = model_config(model, sizes, **overrides)
    cfg = model_config(model, sizes, control, **overrides)
    arch = reference_arch(model, control)
    if control == "reference_default_precision":
        ref.PRECISION[0] = None
    mark("imports")
    devices = leased_devices()[:config["chips"]]
    mark("tpu_start")
    mesh = create_mesh(MeshConfig(**config["mesh"]), devices=devices)
    rows_sharding = logical_sharding(mesh, ("batch", None))
    shardings = sharding_tree(param_axes(true_cfg), mesh)
    chunk = sizes["loss_chunk_tokens"]
    key = jax.random.PRNGKey(config["seed"])
    # one device: the scan's kernels have no per-shard call, and a mesh of one
    # is no mesh to them
    step_mesh = mesh if mesh.size > 1 else None

    # weights on the device in one jitted call, in the type they train in; the
    # seed goes in as the key's value (a constant would compile anew a seed).
    # Always the TRUE configuration's tree: the reference's weights, which a
    # control's config reads otherwise
    seeded = jax.jit(lambda key: seed_leaves(init_params(true_cfg, key), key),
                     out_shardings=shardings)
    # the leaves in the model's own type, a leaf and a cast at a time: under
    # one ``jit`` the chip's compiler drops a cast there and back
    fp8 = lambda tree: jax.tree.map(  # noqa: E731
        lambda a: a.astype(jnp.float8_e4m3fn).astype(a.dtype)
        if a.dtype == cfg.dtype and a.ndim > 1 else jnp.copy(a), tree)
    copy = jax.jit(lambda tree: jax.tree.map(jnp.copy, tree))
    program_weights = fp8 if control == "fp8_weights" else lambda t: planted(copy(t), control)

    def alone_on_device(gone, state):
        """``state`` = (parameters, optimizer state) from the host onto a
        device that holds nothing else of any size (``train_mla.py``'s): the
        step's scratch is most of the chip, and what the checks left behind
        cuts the free memory into smaller pieces."""
        for leaf in jax.tree.leaves(gone):
            if isinstance(leaf, jax.Array) and not leaf.is_deleted():
                leaf.delete()
        flat, tree = jax.tree.flatten(state)
        places = jax.tree.leaves(shardings)  # the parameters' leaves come first
        places += [None] * (len(flat) - len(places))
        return jax.tree.unflatten(tree, [jax.block_until_ready(jax.device_put(leaf, place))
                                         for leaf, place in zip(flat, places)])

    ref_params = seeded(key)
    params = program_weights(ref_params)
    opt = getattr(optax, sizes["optimizer"])(sizes["learning_rate"])
    jax.block_until_ready(params)
    mark("weights")
    opt_state = jax.jit(opt.init)(params)
    jax.block_until_ready(opt_state)
    mark("optimizer_state")

    @functools.partial(jax.jit, donate_argnums=(0, 1))
    def train_step(params, opt_state, batch):
        (loss, aux), grads = jax.value_and_grad(
            lambda p: loss_fn(p, batch, cfg, mesh=step_mesh, chunk_tokens=chunk,
                              return_aux=True), has_aux=True)(params)
        updates, opt_state = opt.update(grads, opt_state, params)
        # the program's counter, from the same pass as the loss
        return optax.apply_updates(params, updates), opt_state, (loss, aux["ssm_decay_mean"])

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
    mark("first_batch")
    t0 = time.monotonic()
    lowered = train_step.lower(params, opt_state, batch)
    mark("step_lowered")
    compiled = lowered.compile()
    compile_s = time.monotonic() - t0
    mark("step_compiled")
    mem = compiled.memory_analysis()
    summed_bytes = int(mem.argument_size_in_bytes + mem.output_size_in_bytes
                       + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    program_bytes = int(getattr(mem, "peak_memory_in_bytes", 0) or summed_bytes)

    # correctness, before the window: program vs plain reference. The
    # recurrence and one layer of each kind alone: the period's LAST
    # state-space layer and its attention layer
    pattern = cfg.layer_pattern
    pick = lambda tree, slot: jax.tree.map(lambda a: a[0], tree["layers"][slot])  # noqa: E731
    slots = (f"slot{len(pattern) - 1 - pattern[::-1].index('mamba2')}",
             f"slot{pattern.index('gqa')}")
    n_check = min(config["check_tokens"], first.shape[1])
    seeds = [jax.random.PRNGKey(config["seed"] + i) for i in (1, 2, 3)]
    h, g = (jax.random.normal(k, (n_check, cfg.hidden), cfg.dtype) for k in seeds[:2])
    layers = layer_errors(cfg, arch, tuple(pick(params, s) for s in slots),
                          tuple(pick(ref_params, s) for s in slots), h, g,
                          check_operands(cfg, seeds[2], n_check), control)
    del h, g, ref_params
    mark("layers")

    def first_row_logits(p, t):
        hidden = forward_hidden(p, t, cfg, mesh=step_mesh)
        return jnp.einsum("se,ve->sv", hidden[0], p["embed"],
                          preferred_element_type=jnp.float32)

    prog_logits = jax.device_get(jax.jit(first_row_logits)(
        params, jax.device_put(first, rows_sharding)))
    mark("logits")
    # the timed step itself, once, on the first batch
    given = batch
    if control == "half_batch":
        half = first.reshape(-1)[:first.size // 2]
        given = {"tokens": jax.device_put(np.concatenate([half, half]).reshape(first.shape),
                                          rows_sharding)}
    # the step's state to the host and back onto a device that holds nothing
    # else; a control that throws the step's result away keeps that copy
    kept = jax.device_get((params, opt_state))
    params, opt_state = alone_on_device((params, opt_state), kept)
    if control != "unchanged_state":
        kept = None
    mark("state_alone_on_device")
    params, opt_state, counters = compiled(params, opt_state, given)
    step0 = jax.device_get(counters)
    if kept is not None:
        params, opt_state = kept
    del given, kept
    mark("first_step")
    # The reference has the chip to itself: what the step left goes to the
    # host and comes back after the comparison
    after, opt_state = jax.device_get((params, opt_state))
    del params, counters, batch
    mark("step_moved_to_host")
    ref_params = seeded(key)
    ref_loss, seen, ref_grads = ref.loss_and_grads(ref_params, jnp.asarray(first), arch)
    mark("reference_step")
    err = np.asarray(jax.device_get(ref.position_errors(
        jnp.asarray(prog_logits), jnp.asarray(seen["logits"]))))
    whole = {"max": float(err.max()), "median": float(np.median(err)),
             "ref_loss": float(ref_loss)}
    start = fp8(ref_params) if control == "fp8_weights" else planted(ref_params, control)
    step = step_errors(opt, start, after, opt_state, ref_params, ref_grads)
    del prog_logits, seen, ref_grads
    params, opt_state = alone_on_device((start, ref_params), (after, opt_state))
    del after, start, ref_params
    mark("step_compared")

    losses, decay_means = [], []
    step_t_a, step_ms, wait_ms, report_ms = [], [], [], []

    def one_step(tokens):
        nonlocal params, opt_state
        params, opt_state, counters = compiled(
            params, opt_state, {"tokens": jax.device_put(tokens, rows_sharding)})
        loss, decay_mean = jax.device_get(counters)  # the fence
        return {"loss": float(loss), "ssm_decay_mean": float(decay_mean)}

    for _ in range(WARM_STEPS):
        one_step(next_batch())
    mark("warm_steps")

    def timed_step():
        t_a = time.monotonic()
        tokens = next_batch()
        t_b = time.monotonic()
        said = one_step(tokens)
        t_c = time.monotonic()
        losses.append(said["loss"])
        decay_means.append(said["ssm_decay_mean"])
        train.report({"step": len(losses), **said})
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
        "compile_s": compile_s, "program_bytes": program_bytes,
        "program_summed_bytes": summed_bytes, "marks": marks,
        "memory": {"arguments": int(mem.argument_size_in_bytes),
                   "temporaries": int(mem.temp_size_in_bytes)},
        "tpu_custom_calls": compiled.as_text().count("tpu_custom_call"),
        "prog_loss": float(step0[0]), "check_decay_mean": float(step0[1]),
        "check_tokens": int(first.shape[0] * (first.shape[1] - 1)),
        "whole": whole, "layers": layers, "step": step,
        "decay_means": decay_means, "traced_steps": traced, "device": device,
        "trace": summary}})


# the phases of ``_loop`` that are the comparison's own, left out of ``setup_s``
# (``train_swa.COMPARISON_PHASES`` and the state's trip before the first step)
PHASES_COMPARED = (*COMPARISON_PHASES, "state_alone_on_device")


def run(ctx: Context) -> dict:
    cfg = ctx.cell.config
    if ctx.rehearse:
        with open(os.path.join(HERE, "rehearse-ssm.json")) as f:
            cfg = {**ctx.rehearse, **json.load(f)}
    mix = ctx.cell.traffic
    seq = int(mix["seq"]) if not ctx.rehearse else int(cfg["train_seq"])
    sizes, model = dict(cfg["train"]), cfg["model"]
    control = os.environ.get("BENCH_SSM_CONTROL") or None
    if control not in (None, *CONTROLS):
        raise RunFailure(f"BENCH_SSM_CONTROL is {control!r}: one of {tuple(CONTROLS)}")
    # before a cluster or a chip is touched: a program that cannot describe
    # this model (one from before it was supported) fails here, at once
    try:
        model_config(model, sizes, control)
    except TypeError as e:
        raise RunFailure(f"this program cannot describe the model: {e}") from e
    from ray_tpu import data
    from ray_tpu.train import JaxTrainer, RunConfig, ScalingConfig

    tokens_per_step = sizes["batch"] * seq
    rows = traffic.train_rows(mix, model["vocab_size"], sizes["batch"], ctx.seed, seq=seq)
    marks = [("process_start", ctx.t_start_wall), ("parent_imports_and_rows", time.time())]
    watcher = pauses.Watcher()  # beside set-up and the window; stopped after it
    check_tokens = int(cfg.get("check_tokens", CHECK_TOKENS))
    try:
        start_cluster(ctx)
        marks.append(("cluster", time.time()))
        resources, runtime_env = lease(ctx)
        result = JaxTrainer(
            _loop,
            train_loop_config={
                "model": model, "train": sizes, "chips": ctx.cell.chips,
                "mesh": mix.get("mesh", {"dp": 1}), "seed": ctx.weight_seed,
                "seconds": ctx.seconds, "trace": ctx.trace,
                "platform": ctx.platform, "check_tokens": check_tokens, "control": control,
                "unions": layer_metrics.union_specs(ctx.cell.readers)},
            scaling_config=ScalingConfig(num_workers=1,
                                         resources_per_worker=resources,
                                         worker_runtime_env=runtime_env),
            run_config=RunConfig(name="bench-train-ssm",
                                 storage_path=tempfile.mkdtemp(prefix="bench-train-ssm-")),
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
    whole, layers, step = m["whole"], m["layers"], m["step"]
    traces = device["kernel_traces"]
    low, high = DECAY_MEAN_RANGE
    checks = {
        "losses_finite": all(math.isfinite(x) for x in m["losses"]),
        "recurrence_matches_reference": layers["recurrence"]["all"] <= STATE_RTOL,
        "mamba_layer_matches_reference": layers["mamba"]["out"]["max"] <= MIXER_RTOL
        and layers["mamba"]["grad"]["max"] <= MIXER_GRAD_RTOL,
        "attention_layer_matches_reference": layers["attention"]["out"]["max"] <= MIXER_RTOL
        and layers["attention"]["grad"]["max"] <= MIXER_GRAD_RTOL,
        "logits_match_reference": whole["median"] <= LOGIT_MEDIAN_RTOL
        and whole["max"] <= LOGIT_MAX_RTOL,
        "loss_matches_reference": abs(m["prog_loss"] - whole["ref_loss"]) <= LOSS_ATOL,
        "gradient_statistics_match_reference":
        step["grad_stats"]["worst"] <= GRAD_STATS_RTOL,
        "update_matches_reference": step["update"]["worst"] <= UPDATE_ALONG_ATOL,
        "the_carried_state_matters": all(
            low <= x <= high for x in [m["check_decay_mean"], *m["decay_means"]]),
        "attention_kernels_native": kernel_native(traces, "flash_attention", ctx.platform),
        "scan_kernels_native": kernel_native(traces, "ssd", ctx.platform),
        "custom_calls_compiled": (m["tpu_custom_calls"] > 0) == (ctx.platform == "tpu"),
    }
    marks += [tuple(x) for x in m["marks"]] + [("window_start", m["t_window_start_wall"])]
    phases = {b[0]: b[1] - a[1] for a, b in zip(marks, marks[1:])}
    compared_s = sum(phases[k] for k in PHASES_COMPARED)
    ctx.say({"setup_phases_s": phases, "comparison_s": compared_s})
    quart = lambda xs: [stats.percentile(xs, q) for q in (25, 50, 75)]  # noqa: E731
    ctx.say({"control": control and f"{control}: {CONTROLS[control]}", "checks": checks,
             "limits": {
        "STATE_RTOL": STATE_RTOL, "MIXER_RTOL": MIXER_RTOL, "MIXER_GRAD_RTOL": MIXER_GRAD_RTOL,
        "LOGIT_MEDIAN_RTOL": LOGIT_MEDIAN_RTOL, "LOGIT_MAX_RTOL": LOGIT_MAX_RTOL,
        "loss_atol": LOSS_ATOL, "GRAD_STATS_RTOL": GRAD_STATS_RTOL,
        "UPDATE_ALONG_ATOL": UPDATE_ALONG_ATOL, "DECAY_MEAN_RANGE": DECAY_MEAN_RANGE},
        "whole_model": whole, "layers": layers, "step": step,
        "prog_loss": m["prog_loss"], "check_decay_mean": m["check_decay_mean"],
        "check_tokens": m["check_tokens"],
        "decay_mean_quartiles": quart(m["decay_means"]),
        "steps": m["steps"], "window_s": m["window_s"],
        "step_ms_quartiles": quart(m["step_ms"]), "compile_s": m["compile_s"],
        **window["said"],
        "loss_first_last": [m["losses"][0], m["losses"][-1]],
        "program_bytes": m["program_bytes"], "memory": m["memory"],
        "peak_bytes_in_use": device["peak_bytes_in_use"],
        "tpu_custom_calls": m["tpu_custom_calls"],
        "kernel_traces": traces, "kernel_costs": device.get("kernel_costs")})
    out = {"correct": all(checks.values()), "attempted": m["steps"], "failed": 0,
           "device": {"platform": device["platform"], "kind": device["kind"],
                      "count": device["count"],
                      # the allocator's peak misses a program's temporaries on
                      # this backend (PERF.md): take the larger of it and the
                      # compiler's peak for the step program, as train_prerouted
                      "memory_peak_bytes": max(max(device["peak_bytes_in_use"]),
                                               m["program_bytes"]),
                      "program_peak_bytes": m["program_bytes"],
                      "program_arguments_and_temporaries_bytes":
                      m["program_summed_bytes"]}}
    if not ctx.trace:
        values = {"train_tok_s_chip": window["train_tok_s_chip"],
                  "setup_s": m["t_window_start_wall"] - ctx.t_start_wall - compared_s}
    else:
        summary = m["trace"]
        if summary is None:
            raise RunFailure("the window ended before the trace was taken")
        ctx.say({"trace": {k: v for k, v in summary.items()
                           if k not in ("ops", "modules")},
                 "modules": summary["modules"]})
        peaks = ({"bf16_flops_per_s": ctx.rehearse["assumed_peak_flops_per_s"],
                  "hbm_bytes_per_s": ctx.rehearse["assumed_peak_flops_per_s"] / 240}
                 if ctx.rehearse else flops.peaks(device["kind"]))
        # the calls the trace holds of each family of kernels, recomputed ones
        # included, THEIR seconds and the least seconds those same calls could
        # take doing the work they DO. A CPU rehearsal interprets the kernels
        # into plain ops, so its trace holds none: the share of the roofline
        # then reads 0 over the window.
        families = {"ssd": flops_ssm.ssd_kernel_costs(model, sizes["batch"], seq),
                    "flash": flops_ssm.flash_kernel_costs(model, sizes["batch"], seq)}
        obs_families, kernel_calls = {}, {}
        for family, costs in families.items():
            took, least = 0.0, 0.0
            for kernel, (kernel_flops, kernel_bytes) in costs.items():
                pattern = rf'^%{kernel}(\.[\w.\-]+)? = .*custom_call_target="tpu_custom_call"'
                seconds, calls = trace_reduce.matching(summary["ops"], pattern)
                took += seconds
                least += calls * flops_ssm.roofline_seconds(kernel_flops, kernel_bytes, peaks)
                # with one event's name as the trace printed it, for the readers' tests
                kernel_calls[kernel] = [calls, seconds, next(
                    (name[:600] for name in summary["ops"] if re.search(pattern, name)), None)]
            obs_families[family] = {"least_seconds": least,
                                    "seconds": took if took else summary["window_s"]}
        first, last = m["traced_steps"]
        ctx.say({"kernel_calls": kernel_calls, "kernel_families": obs_families,
                 "forward_flops_by_part": flops_ssm.forward_flops_by_part(model, seq)})
        obs = {"timers": {"data_wait_ms": window["data_wait_ms"],
                          "report_ms": window["report_ms"],
                          "step_ms_median": stats.percentile(m["step_ms"], 50)},
               # from the median step, not the window: the capture's own
               # start, stop and reduction sit inside a traced window
               "train": {"tok_s_chip": tokens_per_step / chips * 1e3
                         / stats.percentile(m["step_ms"], 50),
                         "peak_flops_per_s": peaks["bf16_flops_per_s"],
                         "flops_per_token": flops_ssm.train_flops_per_token(model, seq)},
               "ssm": {"decay_mean": stats.mean(m["decay_means"][first:last])},
               **obs_families,
               "trace": summary}
        values = layer_metrics.read_all(ctx.cell.readers, obs)
        out["device"].update(busy_s=summary["busy_s"], window_s=summary["window_s"])
        out["breakdown"] = {"device_ops": summary["device_ops"],
                            "idle_gaps": summary["idle_gaps"]}
    declared = ctx.cell.declared(ctx.trace)
    out["metrics"] = {k: {"value": v, "unit": declared[k]}
                      for k, v in values.items() if k in declared}
    return out


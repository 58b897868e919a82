"""Runner of the training cells of a decoder of block-selected sparse attention
beside lightning linear attention under fixed multipliers on the embedding,
the residual branches and the head's input (MiniCPM-SALA). The train runner's
contract (``runners/train.py``: the same phases, the same fenced steps, the
same window rule through ``pauses.window_report``, the same result line
through ``result.emit``) with the configuration builder and the plain
reference swapped, as ``train_prerouted.py``; the step comparison
(``step_errors``) and the set-up accounting (``COMPARISON_PHASES``) are
``train_swa.py``'s. Which model it builds is the configuration file's
``model_type``.

What decides ``correct``, all before the window, against
``reference/sparse_linear_decoder.py`` on the program's own bf16-rounded
weights (the program's draw, every norm weight moved by a seeded +-0.5 so that
a norm left out shows):

* THE RECURRENCE ALONE on seeded bf16 q, k and v of CHECK_TOKENS positions at
  the last lightning layer's decays, float32 out: the kernel against the
  reference's scan over positions (``STATE_RTOL``);
* ONE layer of each kind alone, at the configuration's widths, on a seeded
  bf16 input of CHECK_TOKENS positions (half of them see more than 64 blocks):
  its output and the gradient of its input under a seeded cotangent
  (``MIXER_RTOL``, ``MIXER_GRAD_RTOL``); the block-selected layer's reference
  attends the PROGRAM's sets, and makes its own by a sort beside them;
* the share of (query, kv group) sets on which the reference's own selection
  and the program's agree, in the single layer and in every block-selected
  layer of the model (``SETS_AGREE_MIN``);
* logits at every position of the batch's first row (``LOGIT_MEDIAN_RTOL``,
  ``LOGIT_MAX_RTOL``);
* THE TIMED STEP ITSELF, run once on the first batch: its loss
  (``LOSS_ATOL``), the statistics of its first gradient that the optimizer's
  new state holds and the change of every parameter leaf, against the
  reference's gradient on the same rows put through the same optimizer in
  float32 (``GRAD_STATS_RTOL``, ``UPDATE_ALONG_ATOL``);
* the counters, in that step and in every step of the window: attended pairs
  over causal pairs and the forced share of a set against their closed forms
  (``SHARE_ATOL``), computed tiles over live tiles 1;
* the lightning and attention kernels ran native on the chip.

``BENCH_SALA_CONTROL`` in the environment puts a fault in the program's place
(or, for one, in the reference's), for showing that the comparison refuses it
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

from .. import flops, flops_sala, layer_metrics, pauses, stats, trace_reduce, traffic
from ..manifest import HERE
from . import (Context, RunFailure, capture_trace, check_device, kernel_native,
               lease, reduce_trace, start_cluster, stop_cluster)
from .train import TRACE_STEPS, WARM_STEPS
from .train_hybrid import _rel, seed_norms
from .train_swa import COMPARISON_PHASES, step_errors

# Tokens of a layer's check input: 128 blocks of 64 keys, so half of the
# queries see more than 64 blocks and drop some; 64 chunks of the recurrence.
CHECK_TOKENS = 8192
# What can stand in the program's place (``BENCH_SALA_CONTROL``). The first
# four change the program that is timed and compared (the same leaves, a
# config that reads them otherwise, or other decays in the same leaf); the
# fifth stands in the recurrence's own comparison alone; the sixth changes
# the REFERENCE; the last two leave the program as it is and change what the
# compared step is given or what is kept of it.
CONTROLS = {
    "fp8_weights": "the program computes with its bf16 weights rounded to float8_e4m3fn, "
                   "the nearest precision below the configuration's",
    "no_layer_factor": "every lightning layer decays as layer 0 does: the decay without "
                       "its layer factor",
    "no_local_blocks": "the selection does not force the blocks of the 2,048-key window: "
                       "block 0, the query's own and the best-scoring 62 of all it can see",
    "scale_depth_1": "a residual branch is multiplied by 1 / sqrt(32), scale_depth left at 1",
    "bf16_state": "the recurrence alone, position by position with its state stored in "
                  "bfloat16",
    "reference_default_precision": "the REFERENCE's float32 products run at the backend's "
                                   "default precision (one bf16 pass on a TPU)",
    "half_batch": "the compared step is given the first half of its batch's tokens twice",
    "unchanged_state": "the compared step's new parameters and optimizer state are thrown away",
}
# The limits. Errors are the RMS of the difference over the features of a
# position (or over a leaf) as a share of the RMS of the reference's there.
# Each lies between two readings of THIS cell on the chip at the published
# widths, through this runner (my chip runs, PR 48; PERF.md section 6): the
# largest a sound run gave over its seeds (sixteen runs on sixteen seeds,
# the controls that leave a reading alone among them; none moves much with
# the seed) and the smallest that a control it is meant
# to refuse gave; the limit is their geometric mean.
# * The recurrence alone, float32 out, over all of [32, 8192, 128]: 3.01e-6 to
#   3.02e-6; a bfloat16 state 0.0145 (the decay without its layer factor 0.0689).
#   The reference's own precision does not show here: a backend's default
#   float32 product runs its [128] x [128, 128] readout on the vector units.
STATE_RTOL = 2e-4
# * One mixer alone on a seeded bf16 input of 8,192 positions, worst token:
#   the lightning layer 0.0053-0.0060, the block-selected one 0.0058-0.0066;
#   the decay without its layer factor 0.0599 (fp8 weights 0.109-0.111).
MIXER_RTOL = 0.019
#   and the gradient of that input under a seeded bf16 cotangent, worst token:
#   0.0056-0.0059 and 0.0070-0.0085; without the layer factor 0.0563 (fp8
#   0.106-0.134).
MIXER_GRAD_RTOL = 0.021
# * The share of (query, kv group) sets equal block for block in the
#   program's selection and the reference's own (a bf16 rounding of q or of a
#   pooled key flips a block at the 64th place): 0.966-0.972 of a layer's 8,192
#   queries, 0.919-0.924 of a 16k row's in the model; fp8 weights 0.662 / 0.323,
#   no forced window 0.507 / 0.254 (the first 4,096 queries of a row see at
#   most 64 blocks and always agree: a quarter of a 16k row, half of 8k).
SETS_AGREE_MIN = 0.78
# * Logits of the first row's 16,384 positions. The MEDIAN: 0.010872-0.010931
#   (it moves by half a percent with the seed); the REFERENCE at the backend's
#   default precision 0.012221, the smallest a control gave (the decay without
#   its layer factor 0.0325, fp8 0.218, scale_depth 1 0.338). The reference at
#   one bf16 pass stands as far from the exact one as the bf16 program does,
#   so the two readings are a factor 1.12 apart and the limit has 5% of room
#   either way: what holds it is the median's steadiness.
LOGIT_MEDIAN_RTOL = 0.0115
#   The WORST position: 0.01219-0.01242; default precision 0.01407 is refused
#   by the median, so the worst position's limit lies between the sound runs
#   and the next control, the decay without its layer factor at 0.0379.
LOGIT_MAX_RTOL = 0.022
# * The compared step's loss on the first batch against the reference's over
#   the same 16,383 target tokens: sound runs within 9.5e-6; ``half_batch``
#   0.000269. A WEAK limit by nature (scale_depth 1 moves it by 0.00019, the
#   decay without its layer factor by 0.000028); no control rests on it alone.
LOSS_ATOL = 5e-5
# * The compared step's first gradient by what adafactor's new state holds of
#   it, the worst leaf: 0.0414-0.0441 (``final_norm``; the norms' weights, kept
#   element by element, read 0.02-0.04 and the matrices, kept as means over rows
#   and columns, 0.0066-0.0076); the decay without its layer factor 0.0980 (a
#   ``q_norm``; fp8 0.734, scale_depth 1 0.735).
GRAD_STATS_RTOL = 0.065
# * The change of every parameter leaf ALONG the reference's float32 update,
#   the worst judged leaf: 0.0069-0.0093; fp8 0.0713 (scale_depth 1 0.170,
#   ``half_batch`` 0.457, ``unchanged_state`` 1).
UPDATE_ALONG_ATOL = 0.024
# * The counters against their closed forms: 1e-8 off (float32 sums over 16k
#   positions); no forced window reads a forced share of 0.0413 against 0.6136.
SHARE_ATOL = 1e-4


def model_config(model: dict, sizes: dict, control: str | None = None, **overrides):
    """The program's config object for a configuration file's ``model`` and
    ``train`` groups; ``control`` plants a fault. A program from before this
    model was supported fails here (no ``models.lightning``), before a cluster
    or a chip is touched."""
    if model.get("model_type") != "minicpm_sala":
        raise RunFailure(f"runner train_sala builds no model of type {model.get('model_type')!r}")
    try:
        from ray_tpu.models.block_sparse import BlockSparseAttention
        from ray_tpu.models.lightning import LightningAttention
        from ray_tpu.models.llama import LlamaConfig
    except ImportError as e:
        raise RunFailure(f"this program cannot describe the model: {e}") from e

    kinds, scales = flops_sala.kinds(model), flops_sala.multipliers(model)
    assert model["hidden_act"] == "silu" and not model["tie_word_embeddings"]
    if control == "scale_depth_1":
        scales["residual_scale"] /= model["scale_depth"]
    if control == "no_layer_factor":   # a stack so deep that every layer is its first
        kinds["lightning"]["depth"] = 10**9
    if control == "no_local_blocks":   # a window of one key: a query's own block alone
        kinds["block_sparse"]["window_size"] = 1
    return LlamaConfig(
        vocab_size=model["vocab_size"], hidden=model["hidden_size"],
        n_layers=model["num_hidden_layers"], n_heads=model["num_attention_heads"],
        n_kv_heads=model["num_key_value_heads"], head_dim=model["head_dim"],
        intermediate=model["intermediate_size"], norm_eps=float(model["rms_norm_eps"]),
        layer_pattern=tuple(flops_sala.period(model)), layer_ids=tuple(model["layer_ids"]),
        block_sparse=BlockSparseAttention(**kinds["block_sparse"]),
        lightning=LightningAttention(**kinds["lightning"]),
        **scales, **overrides)


def reference_arch(model: dict) -> dict:
    """What ``reference/sparse_linear_decoder.py`` needs to know of the file."""
    return dict(kinds=flops_sala.kinds(model), pattern=tuple(flops_sala.period(model)),
                lead_pattern=(), layer_ids=tuple(model["layer_ids"]),
                norm_eps=float(model["rms_norm_eps"]), **flops_sala.multipliers(model))


def _worst(err) -> dict:
    return {"max": float(err.max()), "mean": float(err.mean())}


def layer_errors(cfg, arch, layers, ref_layers, layer_id, h, g, qkv, control=None) -> dict:
    """The recurrence alone and one layer of each kind alone. ``layers`` = (a
    block-selected layer's leaves, a lightning layer's, of published index
    ``layer_id``), ``ref_layers`` the reference's; h, g [S, E] the layers'
    input (bf16, already normed) and the cotangent of their output; ``qkv``
    three [1, H, S, D] bf16 operands of the recurrence."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models.block_sparse import block_sparse_mixer
    from ray_tpu.models.lightning import lightning_mixer
    from ray_tpu.ops.lightning_attention import lightning_attention, lightning_scan

    from ..reference import sparse_linear_decoder as ref

    get = lambda x: np.asarray(jax.device_get(x))  # noqa: E731
    f32 = lambda x: x.astype(jnp.float32)  # noqa: E731
    positions = jnp.arange(h.shape[0], dtype=jnp.int32)
    spec = arch["kinds"]["lightning"]
    log_decay, scale = layers[1]["log_decay"], spec["head_dim"] ** -0.5
    if control == "bf16_state":
        run = lambda q, k, v: lightning_scan(  # noqa: E731
            q, k, v, log_decay, scale=scale, state_dtype=jnp.bfloat16)
    else:
        run = lambda q, k, v: lightning_attention(  # noqa: E731
            q, k, v, log_decay, scale=scale, out_dtype=jnp.float32)
    got = jax.jit(run)(*qkv)[0]                                            # [H, S, D]
    want = jax.jit(lambda q, k, v: ref.recurrence(
        *(f32(x[0]).swapaxes(0, 1) for x in (q, k, v)), ref.decays(spec, layer_id)) * scale)(*qkv)
    out = {"recurrence": {"all": float(get(_rel(got.swapaxes(0, 1), want, None)))}}
    del got, want

    def both(fn, h, g):
        """(y, dL/dh) of ``fn(h)`` -> y [S, E] under the cotangent g."""
        y, pull = jax.vjp(fn, h)
        return y, pull(g.astype(y.dtype))[0]

    # the lightning layer
    got = jax.jit(lambda w: both(lambda h: lightning_mixer(
        h[None], w, config=cfg, positions=positions)[0], h, g))(layers[1])
    want = jax.jit(lambda w: both(lambda h: ref.lightning_mixer(
        h, w, spec, layer_id, arch["norm_eps"]), f32(h), f32(g)))(ref_layers[1])
    out["lightning"] = {"out": _worst(get(_rel(got[0], want[0], -1))),
                        "grad": _worst(get(_rel(got[1], want[1], -1)))}
    del got, want

    # the block-selected layer: the reference attends the program's sets
    def program(w):
        def fn(h):
            y, aux = block_sparse_mixer(h[None], w, config=cfg, positions=positions,
                                        return_selection=True)
            return y[0], aux

        y, pull, aux = jax.vjp(fn, h, has_aux=True)
        return y, pull(g.astype(y.dtype))[0], aux

    sparse = arch["kinds"]["block_sparse"]

    def reference(w, sets):
        y, pull, own = jax.vjp(lambda h: ref.sparse_mixer(h, w, sparse, arch["norm_eps"], sets),
                               f32(h), has_aux=True)
        return y, pull(f32(g))[0], own

    got = jax.jit(program)(layers[0])
    aux = got[2]
    sets = aux["selection"][0]
    want = jax.jit(reference)(ref_layers[0], sets)
    out["sparse"] = {"out": _worst(get(_rel(got[0], want[0], -1))),
                     "grad": _worst(get(_rel(got[1], want[1], -1))),
                     "agree": ref.sets_agreement(get(want[2]), get(sets)),
                     **{k: float(aux[k]) for k in (
                         "block_kept_share", "block_forced_share", "block_tile_share")}}
    return out


def _with_decays(params, cfg, key):
    """``params`` with every lightning layer's decays as ``cfg`` gives them
    (a control's): the leaf no gradient moves, made as ``init_params`` makes
    it; the draws it is made beside are dead code there."""
    import jax

    from ray_tpu.models import init_params

    drawn = jax.jit(lambda k: {slot: layer["log_decay"] for slot, layer in
                               init_params(cfg, k)["layers"].items() if "log_decay" in layer})(key)
    return {**params, "layers": {
        slot: {**layer, "log_decay": drawn[slot]} if slot in drawn else layer
        for slot, layer in params["layers"].items()}}


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

    from ..reference import sparse_linear_decoder as ref

    model, sizes, control = config["model"], config["train"], config["control"]
    overrides = {"remat_policy": sizes["remat_policy"]}
    if "dtype" in sizes:  # the rehearsal's float32; a configuration states none
        overrides["dtype"] = jnp.dtype(sizes["dtype"])
    true_cfg = model_config(model, sizes, **overrides)
    cfg = model_config(model, sizes, control, **overrides)
    arch = reference_arch(model)
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
    # one device: the new kinds' kernels have no per-shard call, and a mesh of
    # one is no mesh to them
    step_mesh = mesh if mesh.size > 1 else None

    # weights on the device in one jitted call, in the type they train in; the
    # seed goes in as the key's value (a constant would compile anew a seed).
    # Always the TRUE configuration's tree: the reference's weights, which a
    # control's config reads otherwise
    seeded = jax.jit(lambda key: seed_norms(init_params(true_cfg, key), key),
                     out_shardings=shardings)
    # the leaves in the model's own type, a leaf and a cast at a time: under
    # one ``jit`` the chip's compiler drops a cast there and back
    fp8 = lambda tree: jax.tree.map(  # noqa: E731
        lambda a: a.astype(jnp.float8_e4m3fn).astype(a.dtype)
        if a.dtype == cfg.dtype else jnp.copy(a), tree)
    copy = jax.jit(lambda tree: jax.tree.map(jnp.copy, tree))
    program_weights = fp8 if control == "fp8_weights" else copy

    ref_params = seeded(key)
    params = program_weights(ref_params)
    if control == "no_layer_factor":
        params = _with_decays(params, cfg, key)
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
        # the program's counters, from the same pass as the loss
        counters = (loss, aux["attn_block_kept_share"], aux["attn_block_forced_share"],
                    aux["attn_block_tile_share"])
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
    # recurrence and one layer of each kind alone: the LAST period's
    # block-selected layer and its last lightning layer (the decay's layer
    # factor is farthest from 1 there)
    last = cfg.n_periods - 1
    pick = lambda tree, slot: jax.tree.map(lambda a: a[last], tree["layers"][slot])  # noqa: E731
    slots = (f"slot{cfg.layer_pattern.index('block_sparse')}",
             f"slot{len(cfg.layer_pattern) - 1 - cfg.layer_pattern[::-1].index('lightning')}")
    layer_id = cfg.layer_ids[last * len(cfg.layer_pattern) + int(slots[1][4:])]
    n_check = min(config["check_tokens"], first.shape[1])
    seeds = [jax.random.PRNGKey(config["seed"] + i) for i in (1, 2, 3, 4, 5)]
    a = cfg.lightning
    h, g = (jax.random.normal(k, (n_check, cfg.hidden), cfg.dtype) for k in seeds[:2])
    qkv = [jax.random.normal(k, (1, a.heads, n_check, a.head_dim), jnp.bfloat16)
           for k in seeds[2:]]
    layers = layer_errors(cfg, arch, tuple(pick(params, s) for s in slots),
                          tuple(pick(ref_params, s) for s in slots), layer_id, h, g, qkv,
                          control)
    del h, g, qkv, ref_params
    mark("layers")

    def logits_and_sets(p, t):
        hidden, aux = forward_hidden(p, t, cfg, mesh=step_mesh, return_aux=True,
                                     return_selection=True)
        # the first row's logits; every row's sets, a row's layers together
        return (jnp.einsum("se,ev->sv", hidden[0], p["lm_head"],
                           preferred_element_type=jnp.float32),
                jnp.moveaxis(aux["selection"], 1, 0))

    prog_logits, prog_sets = jax.device_get(jax.jit(logits_and_sets)(
        params, jax.device_put(first, rows_sharding)))
    mark("logits")
    # the timed step itself, once, on the first batch
    given = batch
    if control == "half_batch":
        half = first.reshape(-1)[:first.size // 2]
        given = {"tokens": jax.device_put(np.concatenate([half, half]).reshape(first.shape),
                                          rows_sharding)}
    # what the step is given is donated: a control that throws its result away
    # keeps a copy
    kept = jax.device_get((params, opt_state)) if control == "unchanged_state" else None
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
    # the reference's blocks attend the program's sets
    ref_loss, seen, ref_grads = ref.loss_and_grads(
        ref_params, jnp.asarray(first), arch, jnp.asarray(prog_sets))
    mark("reference_step")
    err = np.asarray(jax.device_get(ref.position_errors(
        jnp.asarray(prog_logits), jnp.asarray(seen["logits"]))))
    agree = [ref.sets_agreement(own, given) for own, given in zip(seen["own_sets"], prog_sets[0])]
    whole = {"max": float(err.max()), "median": float(np.median(err)),
             "ref_loss": float(ref_loss), "sets_agree": [x["sets"] for x in agree],
             "flags_agree": [x["flags"] for x in agree]}
    start = fp8(ref_params) if control == "fp8_weights" else ref_params
    if control == "no_layer_factor":
        start = _with_decays(start, cfg, key)
    step = step_errors(opt, start, after, opt_state, ref_params, ref_grads)
    del start, prog_logits, prog_sets, seen, ref_grads, ref_params
    params = jax.device_put(after, shardings)
    opt_state = jax.device_put(opt_state)
    del after
    mark("step_compared")

    losses = []
    counted = {"kept_share": [], "forced_share": [], "tile_share": []}
    step_t_a, step_ms, wait_ms, report_ms = [], [], [], []

    def one_step(tokens):
        nonlocal params, opt_state
        params, opt_state, counters = compiled(
            params, opt_state, {"tokens": jax.device_put(tokens, rows_sharding)})
        loss, kept_share, forced_share, tile_share = jax.device_get(counters)  # the fence
        return {"loss": float(loss), "attn_block_kept_share": float(kept_share),
                "attn_block_forced_share": float(forced_share),
                "attn_block_tile_share": float(tile_share)}

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
        for k in counted:
            counted[k].append(said[f"attn_block_{k}"])
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
        "prog_loss": float(step0[0]), "check_shares": [float(x) for x in step0[1:]],
        "check_tokens": int(first.shape[0] * (first.shape[1] - 1)),
        "whole": whole, "layers": layers, "step": step,
        "counted": counted, "traced_steps": traced, "device": device, "trace": summary}})


def run(ctx: Context) -> dict:
    cfg = ctx.cell.config
    if ctx.rehearse:
        with open(os.path.join(HERE, "rehearse-sala.json")) as f:
            cfg = {**ctx.rehearse, **json.load(f)}
    mix = ctx.cell.traffic
    seq = int(mix["seq"]) if not ctx.rehearse else int(cfg["train_seq"])
    sizes, model = dict(cfg["train"]), cfg["model"]
    control = os.environ.get("BENCH_SALA_CONTROL") or None
    if control not in (None, *CONTROLS):
        raise RunFailure(f"BENCH_SALA_CONTROL is {control!r}: one of {tuple(CONTROLS)}")
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
            run_config=RunConfig(name="bench-train-sala",
                                 storage_path=tempfile.mkdtemp(prefix="bench-train-sala-")),
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
    whole, layers, step, counted = m["whole"], m["layers"], m["step"], m["counted"]
    traces = device["kernel_traces"]
    sparse, lightning = layers["sparse"], layers["lightning"]
    n_check = min(check_tokens, seq)
    want = {n: (flops_sala.kept_share(model, n), flops_sala.forced_share(model, n))
            for n in {seq, n_check}}
    # a row whose every query sees at most ``topk`` blocks has whole sets, the
    # program's and the reference's alike: agreement is then no reading
    sizes_of = model["sparse_config"]
    drops = lambda n: n > sizes_of["topk"] * sizes_of["block_size"]  # noqa: E731
    checks = {
        "losses_finite": all(math.isfinite(x) for x in m["losses"]),
        "recurrence_matches_reference": layers["recurrence"]["all"] <= STATE_RTOL,
        "lightning_layer_matches_reference": lightning["out"]["max"] <= MIXER_RTOL
        and lightning["grad"]["max"] <= MIXER_GRAD_RTOL,
        "sparse_layer_matches_reference": sparse["out"]["max"] <= MIXER_RTOL
        and sparse["grad"]["max"] <= MIXER_GRAD_RTOL,
        "selection_agrees_with_the_sort":
        (sparse["agree"]["sets"] >= SETS_AGREE_MIN or not drops(n_check))
        and (all(x >= SETS_AGREE_MIN for x in whole["sets_agree"]) or not drops(seq)),
        "logits_match_reference": whole["median"] <= LOGIT_MEDIAN_RTOL
        and whole["max"] <= LOGIT_MAX_RTOL,
        "loss_matches_reference": abs(m["prog_loss"] - whole["ref_loss"]) <= LOSS_ATOL,
        "gradient_statistics_match_reference":
        step["grad_stats"]["worst"] <= GRAD_STATS_RTOL,
        "update_matches_reference": step["update"]["worst"] <= UPDATE_ALONG_ATOL,
        "kept_and_forced_shares_are_the_closed_forms":
        abs(sparse["block_kept_share"] - want[n_check][0]) <= SHARE_ATOL
        and abs(sparse["block_forced_share"] - want[n_check][1]) <= SHARE_ATOL
        and all(abs(x - want[seq][0]) <= SHARE_ATOL
                for x in [m["check_shares"][0], *counted["kept_share"]])
        and all(abs(x - want[seq][1]) <= SHARE_ATOL
                for x in [m["check_shares"][1], *counted["forced_share"]]),
        "every_live_tile_is_computed": all(
            x == 1.0 for x in [sparse["block_tile_share"], m["check_shares"][2],
                               *counted["tile_share"]]),
        "attention_kernels_native": kernel_native(traces, "flash_attention", ctx.platform),
        "lightning_kernels_native": kernel_native(traces, "lightning", ctx.platform),
        "custom_calls_compiled": (m["tpu_custom_calls"] > 0) == (ctx.platform == "tpu"),
    }
    marks += [tuple(x) for x in m["marks"]] + [("window_start", m["t_window_start_wall"])]
    phases = {b[0]: b[1] - a[1] for a, b in zip(marks, marks[1:])}
    compared_s = sum(phases[k] for k in COMPARISON_PHASES)
    ctx.say({"setup_phases_s": phases, "comparison_s": compared_s})
    quart = lambda xs: [stats.percentile(xs, q) for q in (25, 50, 75)]  # noqa: E731
    ctx.say({"control": control and f"{control}: {CONTROLS[control]}", "checks": checks,
             "limits": {
        "STATE_RTOL": STATE_RTOL, "MIXER_RTOL": MIXER_RTOL, "MIXER_GRAD_RTOL": MIXER_GRAD_RTOL,
        "SETS_AGREE_MIN": SETS_AGREE_MIN, "LOGIT_MEDIAN_RTOL": LOGIT_MEDIAN_RTOL,
        "LOGIT_MAX_RTOL": LOGIT_MAX_RTOL, "loss_atol": LOSS_ATOL,
        "GRAD_STATS_RTOL": GRAD_STATS_RTOL, "UPDATE_ALONG_ATOL": UPDATE_ALONG_ATOL,
        "SHARE_ATOL": SHARE_ATOL},
        "whole_model": whole, "layers": layers, "step": step,
        "prog_loss": m["prog_loss"], "check_shares": m["check_shares"],
        "expected_shares": {str(n): v for n, v in want.items()},
        "check_tokens": m["check_tokens"],
        "counted_quartiles": {k: quart(v) for k, v in counted.items()},
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
        # the calls the trace holds of each family of new kernels, recomputed
        # ones included, THEIR seconds and the least seconds those same calls
        # could take doing the work they DO. A CPU rehearsal interprets the
        # kernels into plain ops, so its trace holds none: the share of the
        # roofline then reads 0 over the window.
        families = {"lightning": flops_sala.lightning_kernel_costs(model, sizes["batch"], seq),
                    "blk": flops_sala.select_kernel_costs(model, sizes["batch"], seq)}
        obs_families, kernel_calls = {}, {}
        for family, costs in families.items():
            took, least = 0.0, 0.0
            for kernel, (kernel_flops, kernel_bytes) in costs.items():
                pattern = rf'^%{kernel}(\.[\w.\-]+)? = .*custom_call_target="tpu_custom_call"'
                seconds, calls = trace_reduce.matching(summary["ops"], pattern)
                took += seconds
                least += calls * flops_sala.roofline_seconds(kernel_flops, kernel_bytes, peaks)
                # with one event's name as the trace printed it, for the readers' tests
                kernel_calls[kernel] = [calls, seconds, next(
                    (name[:600] for name in summary["ops"] if re.search(pattern, name)), None)]
            obs_families[family] = {"least_seconds": least,
                                    "seconds": took if took else summary["window_s"]}
        first, last = m["traced_steps"]
        ctx.say({"kernel_calls": kernel_calls, "kernel_families": obs_families,
                 "forward_flops_by_part": flops_sala.forward_flops_by_part(model, seq)})
        obs = {"timers": {"data_wait_ms": window["data_wait_ms"],
                          "report_ms": window["report_ms"],
                          "step_ms_median": stats.percentile(m["step_ms"], 50)},
               # from the median step, not the window: the capture's own
               # start, stop and reduction sit inside a traced window
               "train": {"tok_s_chip": tokens_per_step / chips * 1e3
                         / stats.percentile(m["step_ms"], 50),
                         "peak_flops_per_s": peaks["bf16_flops_per_s"],
                         "flops_per_token": flops_sala.train_flops_per_token(model, seq)},
               "attn": {"block_kept_share": stats.mean(counted["kept_share"][first:last])},
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

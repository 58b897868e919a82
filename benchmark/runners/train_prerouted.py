"""Runner of the training cells of a decoder whose router reads the block's
input ahead of attention: un-roped full layers beside roped layers under a
causal window of several key blocks, grouped queries, no leading layer, no
shared expert, a chip's share of softmax-routed ReGLU experts and of the
vocabulary. The train runner's contract (``runners/train.py``: the same
phases, the same fenced steps, the same window rule through
``pauses.window_report``, the same result line through ``result.emit``) with
the configuration builder and the plain reference swapped, as
``train_swa.py``, whose step comparison (``step_errors``) and whose set-up
accounting (``COMPARISON_PHASES``) this runner imports. Which model it builds
is the configuration file's ``model_name``.

What decides ``correct``, all before the window, against
``reference/prerouted_moe_decoder.py`` on the program's own bf16-rounded
weights (``seeded_weights``: every norm weight moved by a seeded +-0.5, so
that a norm left out shows, and the mixers' output projections and the
embedding table at two published scales, so that seeded routers see tokens
that differ):

* ONE layer of each kind alone, at the configuration's widths, on a seeded
  bf16 input of CHECK_TOKENS positions (half of them past the window; rope at
  theta 1,500,000 has turned pair 0 through 8,192 rad and pair 63 through
  0.007): the full mixer and the window mixer (``MIXER_RTOL``), and the expert
  layer's share with its router reading a seeded input that is NOT the
  experts' own (``LAYER_RTOL``);
* logits at every position of the batch's first row, the median position
  (``LOGIT_MEDIAN_RTOL``; the worst position is reported and not judged: it is
  a position where bf16 swapped an expert, and reads what the seed drew);
* THE TIMED STEP ITSELF, run once on the first batch: its loss and its
  balance term (``LOSS_ATOL``, ``BALANCE_ATOL``); the statistics of its first
  gradient that the optimizer's new state holds and the change of every
  parameter leaf, against the reference's gradient on the same rows put
  through the same optimizer in float32, the matrices whose statistics are
  means over rows and columns (``GRAD_STATS_FACTORED_RTOL``,
  ``UPDATE_ALONG_FACTORED_ATOL``) apart from the norms and routers, kept
  element by element (``GRAD_STATS_RTOL``, ``UPDATE_ALONG_ATOL``);
* the counts, in that step and in every step of the window: rows routed =
  tokens x experts per token (nothing dropped), the held experts' share of
  them against 16 / 64 (``HELD_SHARE_RTOL``), the window layers' pairs over the
  causal pairs against the closed form (``WINDOW_SHARE_ATOL``), the share of
  the experts' gate products that ReLU zeroed inside (0, 1);
* the flash, window and grouped-matmul kernels ran native on the chip.

``BENCH_PREROUTED_CONTROL`` in the environment puts a fault in the program's
place, for showing that the comparison refuses it (``CONTROLS``); such a run
says so in its output and must end ``correct`` false.
"""

from __future__ import annotations

import dataclasses
import functools
import json
import math
import os
import re
import tempfile
import time

import numpy as np

from .. import flops, flops_prerouted, layer_metrics, pauses, stats, trace_reduce, traffic
from ..manifest import HERE
from . import (Context, RunFailure, capture_trace, check_device, kernel_native,
               lease, reduce_trace, start_cluster, stop_cluster)
from .train import TRACE_STEPS, WARM_STEPS
from .train_hybrid import _rel, logit_errors, seed_norms
from .train_moe import LAYER_TIE_GAP, LAYER_TIES_MAX, MODEL_TIE_GAP, near_ties
from .train_swa import COMPARISON_PHASES, UPDATE_MIN_LEAF, UPDATE_MIN_MOVED, step_errors

# Tokens of a layer's check input: two windows long, so half of the queries
# have keys dropped; eight 1024-blocks of the kernels each way.
CHECK_TOKENS = 8192
# What can stand in the program's place (``BENCH_PREROUTED_CONTROL``). The
# first nine change the program that is timed and compared (the same leaves, a
# config that reads them otherwise); the next two stand in the expert layer's
# single comparison alone (one routes on a tensor the whole program has no
# option for, the other reshapes a leaf); the last two leave the program as it
# is and change what the compared step is given or what is kept of it.
CONTROLS = {
    "fp8_weights": "the program computes with its bf16 weights rounded to float8_e4m3fn, "
                   "the nearest precision below the configuration's",
    "silu_experts": "the experts' gate is SiLU in ReLU's place (SwiGLU for ReGLU)",
    "router_after_attention": "the router reads the expert layer's own normed input, after "
                              "attention, as every other model here routes",
    "rope_on_full": "the full layers turn q and k by the window layers' rope",
    "no_rope_window": "the window layers leave q and k as projected",
    "window_512": "the window layers see 512 keys, not 4,096",
    "no_window": "the window layers see every causal key (a window as long as the row)",
    "gates_not_renormalised": "the six gates are the softmax over all 64 outputs as it is, "
                              "not renormalised over the six chosen",
    "router_normed": "the expert layer alone, its router reading the block's input AFTER the "
                     "attention norm: the other reading of 'router placed before attention'",
    "router_over_held": "the expert layer alone scoring the 16 held experts only: softmax "
                        "over 16 outputs, top-6 of them",
    "half_batch": "the compared step is given the first half of its batch's tokens twice",
    "unchanged_state": "the compared step's new parameters and optimizer state are thrown away",
}
LAYER_CONTROLS = ("router_normed", "router_over_held")
# The limits. Errors are the RMS of the difference over the features of a
# position (or over a leaf) as a share of the RMS of the reference's there.
# Each lies between two readings of THIS cell on the chip at the published
# widths, through this runner (my chip runs, PR 45; PERF.md section 6): the
# largest a sound run gave (twenty-one forward passes and eighteen whole steps,
# each on a seed of its own, with the final seeded weights; none moves much
# with the seed) and the smallest that a control gave; the limit is their
# geometric mean.
# * Logits of the first row's 16,384 positions, the MEDIAN: 0.0081 on every
#   seed; fp8 weights 0.0948 (SiLU experts 0.171, gates not renormalised
#   0.310, the router after attention 0.238).
LOGIT_MEDIAN_RTOL = 0.028
#   The WORST position is reported (``whole_model.max``) and decides nothing:
#   it reads 0.2241-0.3073 on sound steps, a position where bf16 swapped an
#   expert in some layer (96% of the positions are within 2% of a routing tie
#   in one of twelve layers; the worst position not near a tie 0.0094-0.103,
#   by the seed), fp8 0.295-0.306 and SiLU 0.302-0.305 beside it, and only the
#   routing's faults over it (the router after attention 0.421-0.455, gates
#   not renormalised 0.458-0.471), which the median, the layer and the step
#   refuse by 8x and more: no limit between such readings holds on a new seed.
# * One mixer alone on a seeded bf16 input of 8,192 positions, worst token:
#   0.0039-0.0042, both kinds; fp8 0.4277-0.4286 (``wo`` at a tenth of its
#   fan-in scale lies under fp8's smallest steps), no window 0.619, rope on
#   the full layer 0.766, none on the window layer 0.869, a 512-key window 2.37.
MIXER_RTOL = 0.042
# * The expert layer's share, worst token not within 1e-4 of a routing tie
#   (5-15 of 8,192 are): 0.0045-0.0049; fp8 0.0711, SiLU 0.440, gates not
#   renormalised 0.802; a router on the normed input or over the held
#   experts alone adds to tokens the reference adds nothing to (infinite).
LAYER_RTOL = 0.019
# * The compared step's loss on the first batch against the reference's over
#   the same 16,383 target tokens: sound runs within 0.00033; ``half_batch``
#   0.00453. A WEAK limit by nature (a fault moves a mean over 16,383 tokens
#   by what sampling moves it: rope on the full layers 0.00053, fp8 0.00011);
#   no control rests on it alone.
LOSS_ATOL = 0.0012
# * The balance term over all 64 experts and the row's tokens, the step's
#   against the reference's: within 0.000004; ``half_batch`` 0.000146.
BALANCE_ATOL = 0.000025
# * The compared step's first gradient, by what adafactor's new state holds of
#   it, in two groups (``by_factoring``; eighteen sound steps on eighteen seeds).
#   A bf16 step's gradient stands 4-13% off the float32 reference's ELEMENT BY
#   ELEMENT in every leaf (the head 0.038, the mixers' matrices 0.059-0.066,
#   ``attn_norm`` 0.062, the experts' matrices 0.103-0.126, ``mlp_norm`` 0.115,
#   the routers 0.118): the residual stream is stored in bf16, 1.3-9.2% of a
#   layer's tokens (more with depth) then choose another set of six experts
#   than the reference, and a seeded model's gradient is a sum of 16,384
#   tokens' terms of random sign, which a few changed terms in a hundred move
#   by a tenth. The plain float32 reference, its stream alone rounded to bf16
#   between a block's parts, stands as far from itself leaf for leaf (0.037,
#   0.058-0.065, 0.062, 0.101-0.123, 0.113, 0.118), and 0.020-0.058 with the
#   choice pinned to the unrounded run's; the program in float32 reads 0 on
#   the CPU (PERF.md section 6: my chip run, PR 45, call 19).
#   - the matrices (embedding, head, the mixers' and experts' projections), of
#     which adafactor keeps the MEANS of the squares along rows and columns,
#     512 elements and more, where that noise averages out: the worst leaf
#     0.0219-0.0330 (a mixer's ``wo``); the smallest a control gave 0.157 (the
#     router after attention; no window 0.176, fp8 0.239, rope on the full
#     layers 0.278, none on the window layers 0.283, SiLU 0.326, gates not
#     renormalised 0.922, ``unchanged_state`` 1.0, ``half_batch`` 1.94, a
#     512-key window 2.33).
GRAD_STATS_FACTORED_RTOL = 0.072
#   - the norms' weights and the routers (64 columns: under adafactor's 128),
#     kept element by element: the worst leaf 0.1387-0.1453 (slot3's
#     ``mlp_norm`` or router, the deepest; the squares stand 1.13 x the
#     element-wise gap apart); the smallest a control gave 0.449 (no window, an
#     ``attn_norm``; fp8 0.550, SiLU 0.577).
GRAD_STATS_RTOL = 0.25
# * The change of every parameter leaf ALONG the reference's float32 update,
#   the same two groups.
#   - the matrices: the worst leaf 0.0193-0.0204 (an expert's ``w_gate``); no
#     window 0.1009 (fp8 0.109, SiLU 0.164, gates not renormalised 0.265, the
#     router after attention 0.269, a 512-key window 0.478, ``half_batch``
#     0.482, rope on the full layers 0.528, none on the window layers 0.541).
UPDATE_ALONG_FACTORED_ATOL = 0.045
#   - the routers (no norm has enough elements that the reference's step
#     moves): 0.0911-0.0981. A first step of adafactor on a leaf it does not
#     factor is the gradient's SIGNS, and an element-wise gap of 0.12 turns
#     arctan(0.12) / pi = 4% of them; fp8 0.2442 (SiLU 0.382, ``half_batch``
#     0.509, the router after attention 0.551). The faults of attention read
#     0.094-0.138 here: the matrices' limits and the layers refuse them.
UPDATE_ALONG_ATOL = 0.155
# The held experts' share of all rows against 16 / 64, a layer and a step:
# 0.245-0.255 with the seeded weights of ``seeded_weights`` (0.02 off a
# quarter at most; plus four standard deviations of that many draws, which is
# what a rehearsal's few hundred rows need); a router over the held experts
# alone reads 1, three quarters off. Their geometric mean.
HELD_SHARE_RTOL = 0.25
# The window layers' pairs over the causal pairs, the program's count from the
# positions it was given against the closed form: 7e-10 off at 16,384 (float32
# sums); a 512-key window reads 0.0615 and no window 1 against 0.4375.
WINDOW_SHARE_ATOL = 1e-4


def model_config(model: dict, sizes: dict, control: str | None = None, **overrides):
    """The program's config object for a configuration file's ``model`` and
    ``train`` groups; ``control`` plants a fault. A program from before this
    model was supported fails here (no ``moe_router_input`` on a
    ``LlamaConfig``), before a cluster or a chip is touched."""
    if not str(model.get("model_name", "")).startswith("smallthinker"):
        raise RunFailure(f"runner train_prerouted builds no model named "
                         f"{model.get('model_name')!r}")
    from ray_tpu.models.gqa import GroupedQueryAttention
    from ray_tpu.models.llama import LlamaConfig

    kinds = {k: dict(v) for k, v in flops_prerouted.kinds(model).items()}
    if control == "rope_on_full":
        kinds["gqa"]["rope_theta"] = kinds["gqa_win"]["rope_theta"]
    if control == "no_rope_window":
        kinds["gqa_win"]["rope_theta"] = 0.0
    if control in ("window_512", "no_window"):
        kinds["gqa_win"]["window"] = 512 if control == "window_512" else 1 << 30
    first, last = model["experts_held"]
    assert model["moe_num_primary_experts"] == last - first + 1
    assert model["moe_primary_router_apply_softmax"] and not model["tie_word_embeddings"]
    try:
        return LlamaConfig(
            vocab_size=model["vocab_size"], hidden=model["hidden_size"],
            n_layers=model["num_hidden_layers"], n_heads=model["num_attention_heads"],
            n_kv_heads=model["num_key_value_heads"], head_dim=model["head_dim"],
            intermediate=model["moe_ffn_hidden_size"], norm_eps=float(model["rms_norm_eps"]),
            layer_pattern=tuple(flops_prerouted.period(model)),
            gqa=GroupedQueryAttention(**kinds["gqa"]),
            gqa_window=GroupedQueryAttention(**kinds["gqa_win"]),
            moe_experts=model["router_width"],
            moe_top_k=model["moe_num_active_primary_experts"],
            moe_norm_topk=bool(model["norm_topk_prob"]) and control != "gates_not_renormalised",
            moe_held=(first, model["moe_num_primary_experts"]),
            moe_router_input="mlp_norm" if control == "router_after_attention" else "block",
            moe_activation="silu" if control == "silu_experts" else "relu",
            moe_aux_weight=sizes["aux_loss_weight"], moe_z_weight=0.0, **overrides)
    except TypeError as e:
        raise RunFailure(f"this program cannot describe the model: {e}") from e


def seeded_weights(cfg, key, wo_scale: float):
    """The seeded weights of a run, the program's and the reference's alike:
    the program's own draw (``init_params``), every norm weight moved by a
    seeded +-0.5 (``seed_norms``), and two published conventions of scale
    that the program's fan-in draw does not have and this model needs:

    * every mixer's output projection ``wo`` times ``wo_scale`` = (2 x the
      published layer count)^-1/2, GPT-2's scaling of a residual branch's last
      projection. Attention with random weights adds to every token the mean
      of its context's values, and a direction all tokens share passes through
      every later attention whole (a mean of equal vectors is that vector):
      with ``wo`` at its fan-in scale it grows by about sqrt(2) a layer, and
      here no dense layer or shared expert adds anything of a token's own (the
      held experts add to a quarter of the rows). By layer 8 all tokens chose
      the same experts: a held share of 0.17-0.53 by layer and seed, a held
      range past its ``cap`` and 4 s steps on the expert-by-expert path (my
      chip runs, PR 45);
    * the embedding table at UNIT variance (its draw times ``sqrt(hidden)``,
      the embedding multiplier several families publish). At 1 / sqrt(hidden)
      a token's stream is the sum of its experts' outputs and little else, so
      one expert that bf16 swaps in an early layer (a few tokens in a hundred
      a layer, in any routed model) makes that position's logits unrelated to
      the reference's: the worst position read 1.43 and the gradient's
      statistics 0.75-0.92 on sound runs (my chip runs, PR 45).

    With both the shared direction stays at 3% of the stream at every depth,
    the routing is even (a held share of 0.246-0.254 in every layer), as a
    trained router's is by its balance loss, and a swapped expert moves a
    position's logits by a fraction."""
    import jax.numpy as jnp

    from ray_tpu.models import init_params

    params = seed_norms(init_params(cfg, key), key)
    scaled = lambda a, by: (a.astype(jnp.float32) * by).astype(a.dtype)  # noqa: E731
    return {**params, "embed": scaled(params["embed"], math.sqrt(cfg.hidden)), "layers": {
        slot: {**layer, "wo": scaled(layer["wo"], wo_scale)}
        for slot, layer in params["layers"].items()}}


def reference_arch(model: dict) -> dict:
    """What ``reference/prerouted_moe_decoder.py`` needs to know of the file."""
    return dict(kinds=flops_prerouted.kinds(model), pattern=tuple(flops_prerouted.period(model)),
                lead_pattern=(), norm_eps=float(model["rms_norm_eps"]),
                top_k=model["moe_num_active_primary_experts"],
                held_first=model["experts_held"][0])


def by_factoring(step: dict, v_row, sizes: dict) -> dict:
    """``step_errors``' readings by leaf again, the leaves whose gradient
    adafactor keeps as means over rows and columns (``factored``: ``v_row``,
    its state's tree by leaf name, holds a row of them) apart from those it
    keeps element by element (``elementwise``: norms, a router of 64
    columns): of each group the worst leaf's ``grad_stats`` and, among the
    leaves ``step_errors`` judges (``sizes``: elements by leaf name), the
    worst ``update``."""
    readings = step["by_leaf"]
    out = {}
    for group, names in (("factored", [n for n in sizes if v_row[n].size > 1]),
                         ("elementwise", [n for n in sizes if v_row[n].size <= 1])):
        judged = [n for n in names if sizes[n] >= UPDATE_MIN_LEAF
                  and readings["ref_moved_share"][n] >= UPDATE_MIN_MOVED]
        out[group] = {
            field: {"worst": max((readings[field][n] for n in among), default=0.0),
                    "leaf": max(among, key=readings[field].get, default=None)}
            for field, among in (("grad_stats", names), ("update", judged))}
    return out


def layer_errors(cfg, arch, layers, ref_layers, h, x_in, control=None) -> dict:
    """One layer of each kind alone: the program's mixers on the same input h
    [S, E] (bf16, already normed) and its expert layer through the MLP kind's
    own two hooks (``early`` on x_in [S, E], the block's input; ``apply`` on
    h), against the reference's (``arch``, ``ref_layers``). ``layers`` = a full
    layer's leaves and a window layer's, which is also the expert layer. A
    control of LAYER_CONTROLS changes what the program's expert layer is
    given here."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models.gqa import gqa_mixer
    from ray_tpu.models.moe import MOE
    from ray_tpu.ops import rms_norm

    from ..reference import prerouted_moe_decoder as ref

    get = lambda x: np.asarray(jax.device_get(x))  # noqa: E731
    positions = jnp.arange(h.shape[0], dtype=jnp.int32)
    out = {}
    for name, kind, spec, layer, ref_layer in (
            ("full", "gqa", cfg.gqa, layers[0], ref_layers[0]),
            ("window", "gqa_win", cfg.gqa_window, layers[1], ref_layers[1])):
        got, aux = jax.jit(lambda h, w, spec=spec: gqa_mixer(
            h[None], w, spec, config=cfg, positions=positions))(h, layer)
        want = jax.jit(lambda h, w, kind=kind: ref.gqa_mixer(h, w, arch["kinds"][kind]))(
            h, ref_layer)
        err = get(_rel(got[0], want, -1))
        out[name] = {"max": float(err.max()), "mean": float(err.mean())}
        if "window_share" in aux:
            out[name]["window_share"] = float(aux["window_share"])
    layer, program = layers[1], cfg
    if control == "router_over_held":
        first, count = cfg.moe_held
        layer = {**layer, "router": layer["router"][:, first:first + count]}
        program = dataclasses.replace(cfg, moe_experts=count, moe_held=None,
                                      moe_top_k=min(cfg.moe_top_k, count))

    def expert_layer(h, x_in, w):
        if control == "router_normed":
            x_in = rms_norm(x_in, w["attn_norm"], eps=cfg.norm_eps)
        early = MOE.early(x_in[None], w, config=program)
        return MOE.apply(h[None], w, config=program,
                         **({} if early is None else {"early": early}))

    got, aux = jax.jit(expert_layer)(h, x_in, layer)
    want, routing = jax.jit(lambda h, x_in, w: ref.expert_layer(
        h, x_in, w, top_k=arch["top_k"], first=arch["held_first"]))(h, x_in, ref_layers[1])
    # a token none of whose six experts is held here (one in six at 16 of 64)
    # gets nothing from this chip's share, on both sides: 0 where the program
    # adds nothing too, else infinite
    silent = get(jnp.all(want == 0, axis=-1))
    err = np.where(silent, np.where(get(jnp.any(got[0] != 0, axis=-1)), np.inf, 0.0),
                   get(_rel(got[0], jnp.where(silent[:, None], 1.0, want), -1)))
    tie = get(near_ties(routing["probs"], arch["top_k"], LAYER_TIE_GAP))
    out["experts"] = {"max": float(err[~tie].max()), "mean": float(err[~tie].mean()),
                      "ties": int(tie.sum()), "tokens": int(err.size),
                      "tokens_with_no_held_expert": int(silent.sum()),
                      "rows": int(get(aux["rows"]).sum()), "dropped": int(aux["dropped"]),
                      "held_share": float(aux.get("held_share", 1.0)),
                      "act_zero": float(aux.get("act_zero", -1.0))}
    return out


def _loop(config: dict) -> None:
    """Runs in the train worker that leased the chips."""
    marks = [("loop_entered", time.time())]  # set-up's phases, by the wall clock

    def mark(name):
        marks.append((name, time.time()))

    import jax
    import jax.numpy as jnp
    import optax

    from ray_tpu import train
    from ray_tpu.models import forward, loss_fn, param_axes
    from ray_tpu.parallel import MeshConfig, create_mesh
    from ray_tpu.parallel.sharding import logical_sharding, sharding_tree
    from ray_tpu.tpu import device_report, leased_devices

    from ..reference import prerouted_moe_decoder as ref

    model, sizes, control = config["model"], config["train"], config["control"]
    overrides = {"remat_policy": sizes["remat_policy"]}
    if "dtype" in sizes:  # the rehearsal's float32; a configuration states none
        overrides["dtype"] = jnp.dtype(sizes["dtype"])
    true_cfg = model_config(model, sizes, **overrides)
    cfg = model_config(model, sizes, control, **overrides)
    arch = reference_arch(model)
    mark("imports")
    devices = leased_devices()[:config["chips"]]
    mark("tpu_start")
    mesh = create_mesh(MeshConfig(**config["mesh"]), devices=devices)
    n_batch = math.prod(mesh.shape[a] for a in ("dcn", "dp", "fsdp"))
    rows_sharding = logical_sharding(mesh, ("batch", None))
    shardings = sharding_tree(param_axes(true_cfg), mesh)
    chunk = sizes["loss_chunk_tokens"]
    key = jax.random.PRNGKey(config["seed"])

    # weights on the device(s) in one jitted call, in the type they train in;
    # the seed goes in as the key's value (a constant would compile anew a
    # seed). Always the TRUE configuration's tree: the reference's weights,
    # which a control's config reads otherwise
    seeded = jax.jit(lambda key: seeded_weights(true_cfg, key, config["wo_scale"]),
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
    opt = getattr(optax, sizes["optimizer"])(sizes["learning_rate"])
    jax.block_until_ready(params)
    mark("weights")
    opt_state = jax.jit(opt.init)(params)
    jax.block_until_ready(opt_state)
    mark("optimizer_state")

    @functools.partial(jax.jit, donate_argnums=(0, 1))
    def train_step(params, opt_state, batch):
        (loss, aux), grads = jax.value_and_grad(
            lambda p: loss_fn(p, batch, cfg, mesh=mesh, chunk_tokens=chunk, return_aux=True),
            has_aux=True)(params)
        updates, opt_state = opt.update(grads, opt_state, params)
        # the program's counters, from the same pass as the loss; a control
        # whose experts are no ReGLU counts no zeroed product
        counters = (loss, aux["load_balance"], aux["rows_per_expert"].sum(axis=-1),
                    aux["rows_dropped"], aux["rows_per_held_expert"], aux["held_share"],
                    aux["attn_window_share"],
                    aux.get("act_zero", jnp.full_like(aux["held_share"], -1.0)))
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
    # the compiler's own peak where it gives one: this step's ``temp_size`` is
    # a heap half of which is fragmentation (arguments + temporaries read
    # 19.49 GB on a chip of 16.9 that runs the step; its peak 13.51: PR 45)
    summed_bytes = int(mem.argument_size_in_bytes + mem.output_size_in_bytes
                       + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    program_bytes = int(getattr(mem, "peak_memory_in_bytes", 0) or summed_bytes)

    # correctness, before the window: program vs plain reference. One layer of
    # each kind alone: the period's full layer and its first window layer
    pick = lambda tree, slot: jax.tree.map(lambda a: a[0], tree["layers"][slot])  # noqa: E731
    slots = (f"slot{cfg.layer_pattern.index('gqa')}", f"slot{cfg.layer_pattern.index('gqa_win')}")
    check = (min(config["check_tokens"], first.shape[1]), cfg.hidden)
    h = jax.random.normal(jax.random.PRNGKey(config["seed"] + 1), check, cfg.dtype)
    x_in = jax.random.normal(jax.random.PRNGKey(config["seed"] + 2), check, cfg.dtype)
    layers = layer_errors(cfg, arch, tuple(pick(params, s) for s in slots),
                          tuple(pick(ref_params, s) for s in slots), h, x_in, control)
    del h, x_in, ref_params
    mark("layers")
    prog_logits = jax.device_get(jax.jit(lambda p, t: forward(p, t, cfg, mesh=mesh)[0])(
        params, jax.device_put(first[:n_batch], rows_sharding)))
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
    ref_loss, seen, ref_grads = ref.loss_and_grads(
        ref_params, jnp.asarray(first), arch, aux_weight=sizes["aux_loss_weight"])
    mark("reference_step")
    whole = logit_errors(jnp.asarray(prog_logits),
                         {k: jnp.asarray(seen[k]) for k in ("logits", "probs")}, arch["top_k"])
    whole.update(ref_loss=float(ref_loss), ref_ce=float(seen["ce"]),
                 ref_balance=float(seen["balance"]))
    start = fp8(ref_params) if control == "fp8_weights" else ref_params
    step = step_errors(opt, start, after, opt_state, ref_params, ref_grads)
    named = lambda tree: {jax.tree_util.keystr(path): leaf for path, leaf in  # noqa: E731
                          jax.tree_util.tree_flatten_with_path(tree)[0]}
    step["by_factoring"] = by_factoring(
        step, named(opt_state[0].v_row), {n: a.size for n, a in named(after).items()})
    del start, prog_logits, seen, ref_grads, ref_params
    params = jax.device_put(after, shardings)
    opt_state = jax.device_put(opt_state)
    del after
    mark("step_compared")

    rows_per_step = sizes["batch"] * first.shape[1] * cfg.moe_top_k
    losses, rows_wrong = [], []
    counted = {"load_max_over_mean": [], "held_share": [], "rows_per_held_expert": [],
               "window_share": [], "act_zero": [], "act_zero_least": [], "act_zero_most": []}
    step_t_a, step_ms, wait_ms, report_ms = [], [], [], []

    def one_step(tokens):
        nonlocal params, opt_state
        params, opt_state, counters = compiled(
            params, opt_state, {"tokens": jax.device_put(tokens, rows_sharding)})
        loss, _, rows, dropped, rows_held, held_share, window_share, act_zero = jax.device_get(
            counters)  # the fence
        if int(dropped) or (rows != rows_per_step).any():
            rows_wrong.append([int(dropped), rows.tolist()])
        # over the experts this chip holds: their rows are what its grouped
        # matmuls compute
        return {"loss": float(loss),
                "moe_load_max_over_mean": float(
                    (rows_held.max(axis=-1) / rows_held.mean(axis=-1)).mean()),
                "held_share": float(held_share.mean()),
                "rows_per_held_expert": float(rows_held.mean()),
                "attn_window_share": float(window_share),
                "act_zero": float(act_zero.mean()), "act_zero_least": float(act_zero.min()),
                "act_zero_most": float(act_zero.max())}

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
        counted["load_max_over_mean"].append(said["moe_load_max_over_mean"])
        counted["window_share"].append(said["attn_window_share"])
        for k in ("held_share", "rows_per_held_expert", "act_zero", "act_zero_least",
                  "act_zero_most"):
            counted[k].append(said[k])
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
        "prog_loss": float(step0[0]), "prog_balance": float(step0[1]),
        "check_rows_per_layer": step0[2].tolist(), "check_rows_dropped": int(step0[3]),
        "check_held_share": step0[5].tolist(), "check_window_share": float(step0[6]),
        "check_act_zero": step0[7].tolist(),
        "check_tokens": int(first.shape[0] * (first.shape[1] - 1)),
        "check_positions": int(first.size), "rows_per_token": cfg.moe_top_k,
        "whole": whole, "layers": layers, "step": step, "rows_wrong": rows_wrong[:5],
        "counted": counted, "traced_steps": traced, "device": device, "trace": summary}})


def run(ctx: Context) -> dict:
    cfg = ctx.cell.config
    if ctx.rehearse:
        with open(os.path.join(HERE, "rehearse-prerouted.json")) as f:
            cfg = {**ctx.rehearse, **json.load(f)}
    mix = ctx.cell.traffic
    seq = int(mix["seq"]) if not ctx.rehearse else int(cfg["train_seq"])
    sizes, model = dict(cfg["train"]), cfg["model"]
    control = os.environ.get("BENCH_PREROUTED_CONTROL") or None
    if control not in (None, *CONTROLS):
        raise RunFailure(f"BENCH_PREROUTED_CONTROL is {control!r}: one of {tuple(CONTROLS)}")
    # before a cluster or a chip is touched: a program that cannot describe
    # this model (one from before it was supported) fails here, at once
    model_config(model, sizes, control)
    from ray_tpu import data
    from ray_tpu.train import JaxTrainer, RunConfig, ScalingConfig

    tokens_per_step = sizes["batch"] * seq
    rows = traffic.train_rows(mix, model["vocab_size"], sizes["batch"], ctx.seed, seq=seq)
    marks = [("process_start", ctx.t_start_wall), ("parent_imports_and_rows", time.time())]
    watcher = pauses.Watcher()  # beside set-up and the window; stopped after it
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
                "platform": ctx.platform,
                "check_tokens": int(cfg.get("check_tokens", CHECK_TOKENS)), "control": control,
                "wo_scale": (2.0 * cfg.get("num_hidden_layers_published",
                                           model["num_hidden_layers"])) ** -0.5,
                "unions": layer_metrics.union_specs(ctx.cell.readers)},
            scaling_config=ScalingConfig(num_workers=1,
                                         resources_per_worker=resources,
                                         worker_runtime_env=runtime_env),
            run_config=RunConfig(name="bench-train-prerouted",
                                 storage_path=tempfile.mkdtemp(prefix="bench-train-prerouted-")),
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
    grouped = step["by_factoring"]
    traces = device["kernel_traces"]
    experts = layers["experts"]
    n_layers = model["num_hidden_layers"]
    even_share = model["moe_num_primary_experts"] / model["router_width"]
    window_keys = model["sliding_window_size"]
    want_window = flops_prerouted.window_share(seq, window_keys)
    want_window_check = flops_prerouted.window_share(
        min(int(cfg.get("check_tokens", CHECK_TOKENS)), seq), window_keys)
    checks = {
        "losses_finite": all(math.isfinite(x) for x in m["losses"]),
        "logits_match_reference": whole["median"] <= LOGIT_MEDIAN_RTOL,
        "loss_matches_reference": abs(m["prog_loss"] - whole["ref_loss"]) <= LOSS_ATOL,
        "balance_matches_reference":
        abs(m["prog_balance"] - whole["ref_balance"]) <= BALANCE_ATOL,
        "gradient_statistics_match_reference":
        grouped["factored"]["grad_stats"]["worst"] <= GRAD_STATS_FACTORED_RTOL
        and grouped["elementwise"]["grad_stats"]["worst"] <= GRAD_STATS_RTOL,
        "update_matches_reference":
        grouped["factored"]["update"]["worst"] <= UPDATE_ALONG_FACTORED_ATOL
        and grouped["elementwise"]["update"]["worst"] <= UPDATE_ALONG_ATOL,
        "full_layer_matches_reference": layers["full"]["max"] <= MIXER_RTOL,
        "window_layer_matches_reference": layers["window"]["max"] <= MIXER_RTOL,
        "expert_layer_matches_reference": experts["max"] <= LAYER_RTOL
        and experts["ties"] <= max(2, LAYER_TIES_MAX * experts["tokens"]),
        "no_row_dropped": not m["rows_wrong"] and m["check_rows_dropped"] == 0
        and experts["dropped"] == 0
        and experts["rows"] == experts["tokens"] * m["rows_per_token"]
        and m["check_rows_per_layer"]
        == [m["check_positions"] * m["rows_per_token"]] * n_layers,
        "held_share_is_the_chips_share": all(
            abs(x - even_share) <= HELD_SHARE_RTOL * even_share + 4 * math.sqrt(
                even_share * (1 - even_share) / (tokens * m["rows_per_token"]))
            for x, tokens in [(experts["held_share"], experts["tokens"])] + [
                (x, tokens_per_step) for x in [*m["check_held_share"], *counted["held_share"]]]),
        "window_share_is_the_closed_form":
        abs(layers["window"]["window_share"] - want_window_check) <= WINDOW_SHARE_ATOL
        and all(abs(x - want_window) <= WINDOW_SHARE_ATOL
                for x in [m["check_window_share"], *counted["window_share"]]),
        "relu_zeroes_a_share_of_the_gate_products": all(
            0.0 < x < 1.0 for x in [experts["act_zero"], *m["check_act_zero"],
                                    *counted["act_zero_least"], *counted["act_zero_most"]]),
        "attention_kernels_native": kernel_native(traces, "flash_attention", ctx.platform),
        "grouped_matmul_native": kernel_native(traces, "moe_gmm", ctx.platform)
        and kernel_native(traces, "moe_tgmm", ctx.platform),
        "custom_calls_compiled": (m["tpu_custom_calls"] > 0) == (ctx.platform == "tpu"),
    }
    marks += [tuple(x) for x in m["marks"]] + [("window_start", m["t_window_start_wall"])]
    phases = {b[0]: b[1] - a[1] for a, b in zip(marks, marks[1:])}
    compared_s = sum(phases[k] for k in COMPARISON_PHASES)
    ctx.say({"setup_phases_s": phases, "comparison_s": compared_s})
    quart = lambda xs: [stats.percentile(xs, q) for q in (25, 50, 75)]  # noqa: E731
    ctx.say({"control": control and f"{control}: {CONTROLS[control]}", "checks": checks,
             "limits": {
        "LOGIT_MEDIAN_RTOL": LOGIT_MEDIAN_RTOL,
        "GRAD_STATS_FACTORED_RTOL": GRAD_STATS_FACTORED_RTOL, "GRAD_STATS_RTOL": GRAD_STATS_RTOL,
        "UPDATE_ALONG_FACTORED_ATOL": UPDATE_ALONG_FACTORED_ATOL,
        "UPDATE_ALONG_ATOL": UPDATE_ALONG_ATOL,
        "MIXER_RTOL": MIXER_RTOL, "LAYER_RTOL": LAYER_RTOL, "BALANCE_ATOL": BALANCE_ATOL,
        "loss_atol": LOSS_ATOL,
        "HELD_SHARE_RTOL": HELD_SHARE_RTOL, "WINDOW_SHARE_ATOL": WINDOW_SHARE_ATOL,
        "MODEL_TIE_GAP": MODEL_TIE_GAP, "LAYER_TIE_GAP": LAYER_TIE_GAP},
        "whole_model": whole, "layers": layers, "step": step,
        "prog_loss": m["prog_loss"], "prog_balance": m["prog_balance"],
        "check_window_share": m["check_window_share"], "expected_window_share": want_window,
        "check_tokens": m["check_tokens"],
        "check_rows_per_layer": m["check_rows_per_layer"],
        "check_held_share": m["check_held_share"], "check_act_zero": m["check_act_zero"],
        "rows_wrong": m["rows_wrong"],
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
                      # compiler's peak for the step program. Beside it, under
                      # names of their own, that peak and the sum the older
                      # runners report (arguments + outputs + temporaries -
                      # aliases: a heap half of which is fragmentation here)
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
        # the calls the trace holds of each family of attention kernels,
        # recomputed ones included, THEIR seconds and the least seconds those
        # same calls could take doing the USEFUL work. A CPU rehearsal
        # interprets the kernels into plain ops, so its trace holds none: the
        # share of the roofline then reads 0 over the window.
        families = {kind: flops_prerouted.attention_kernel_costs(model, name, sizes["batch"], seq)
                    for kind, name in (("flash", "gqa"), ("win", "gqa_win"))}
        obs_families, kernel_calls = {}, {}
        for family, costs in families.items():
            took, least = 0.0, 0.0
            for kernel, (kernel_flops, kernel_bytes) in costs.items():
                pattern = rf'^%{kernel}(\.[\w.\-]+)? = .*custom_call_target="tpu_custom_call"'
                seconds, calls = trace_reduce.matching(summary["ops"], pattern)
                took += seconds
                least += calls * flops_prerouted.roofline_seconds(
                    kernel_flops, kernel_bytes, peaks)
                # with one event's name as the trace printed it, for the readers' tests
                kernel_calls[kernel] = [calls, seconds, next(
                    (name[:600] for name in summary["ops"] if re.search(pattern, name)), None)]
            obs_families[family] = {"least_seconds": least,
                                    "seconds": took if took else summary["window_s"]}
        first, last = m["traced_steps"]
        # the grouped-matmul calls the trace holds and THEIR seconds, as
        # train_swa.py: a call's FLOPs are those of the rows the held experts
        # computed, from the traced steps' own count of them
        share = ctx.cell.readers.get("kernel.moe_gmm_share.train")
        gmm_s, gmm_calls = (trace_reduce.matching(summary["ops"], share["params"]["pattern"])
                            if share else (0.0, 0))
        held_share = stats.mean(counted["held_share"][first:last])
        rows_held = held_share * tokens_per_step * model["moe_num_active_primary_experts"]
        ctx.say({"moe_gmm_calls": gmm_calls, "moe_gmm_seconds": gmm_s,
                 "rows_held_a_layer": rows_held, "kernel_calls": kernel_calls,
                 "kernel_families": obs_families,
                 "forward_flops_by_part": flops_prerouted.forward_flops_by_part(model, seq)})
        obs = {"timers": {"data_wait_ms": window["data_wait_ms"],
                          "report_ms": window["report_ms"],
                          "step_ms_median": stats.percentile(m["step_ms"], 50)},
               # from the median step, not the window: the capture's own
               # start, stop and reduction sit inside a traced window
               "train": {"tok_s_chip": tokens_per_step / chips * 1e3
                         / stats.percentile(m["step_ms"], 50),
                         "peak_flops_per_s": peaks["bf16_flops_per_s"],
                         "flops_per_token": flops_prerouted.train_flops_per_token(model, seq)},
               "moe": {"load_max_over_mean": stats.mean(
                           counted["load_max_over_mean"][first:last]),
                       "held_share": held_share,
                       "act_zero_share": stats.mean(counted["act_zero"][first:last]),
                       "gmm_flops_per_call": 2.0 * rows_held * model["hidden_size"]
                       * model["moe_ffn_hidden_size"],
                       "gmm_calls": gmm_calls,
                       "gmm_seconds": gmm_s if gmm_calls else summary["window_s"]},
               "attn": {"window_share": stats.mean(counted["window_share"][first:last])},
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

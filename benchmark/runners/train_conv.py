"""Runner of the training cells of a decoder of double-gated short convolutions
beside roped grouped-query attention with per-head q/k norms, leading dense
layers, sigmoid-routed experts under a selection bias (a chip's share of them,
no shared expert) and a tied vocabulary (lfm2-8b-a1b). The train runner's
contract (``runners/train.py``: the same phases, the same fenced steps, the same
window rule through ``pauses.window_report``, the same result line through
``result.emit``) with the configuration builder and the plain reference swapped;
``train_ssm.py::run``'s skeleton (the watcher and the cluster stopped in one
``finally``), ``train_mla.py``'s counts of the routed rows, ``train_swa.py``'s
step comparison (``step_errors``) and set-up accounting (``COMPARISON_PHASES``).
Which model it builds is the configuration file's ``model_type``.

What decides ``correct``, all before the window, against
``reference/conv_moe_decoder.py`` on the program's own bf16-rounded weights (the
program's draw, every norm weight moved by a seeded +-0.5 and every selection
bias by a seeded +-0.02, so that one left out shows):

* ONE layer of each kind alone, at the configuration's widths, on a seeded bf16
  input of CHECK_TOKENS positions: a conv mixer and an attention mixer, each its
  output and the gradient of its input under a seeded cotangent, the worst token
  (``MIXER_RTOL``, ``MIXER_GRAD_RTOL``) and the conv mixer's output's mean over
  the tokens (``CONV_MEAN_RTOL``), and the expert layer's share, a
  token's error as a share of the layer's RMS over all tokens (``LAYER_RTOL``);
* logits at every position of the batch's first row, the median
  (``LOGIT_MEDIAN_RTOL``; the worst position is reported);
* THE TIMED STEP ITSELF, run once on the first batch: its loss and its balance
  term (``LOSS_ATOL``, ``BALANCE_ATOL``); the statistics of its first gradient
  that the optimizer's new state holds and the change of every parameter leaf,
  against the reference's gradient on the same rows put through the same
  optimizer in float32 (``GRAD_STATS_RTOL``, ``UPDATE_ALONG_ATOL``); the step of
  every selection bias against the reference's rule on the reference's own
  counts (``BIAS_AGREEMENT``);
* the counts: rows routed = tokens x experts per token in that step and in every
  step of the window (nothing dropped); the held experts' share of them against
  the reference's own count (``HELD_SHARE_RTOL``) and in every step of the
  window within ``HELD_SHARE_BAND`` of 8 / 32; the conv's ``sconv_past_share``
  in that step against the reference's (``PAST_SHARE_ATOL``) and in every step of
  the window inside ``PAST_SHARE_RANGE``: the earlier taps matter;
* the flash and grouped-matmul kernels ran native on the chip.

``BENCH_CONV_CONTROL`` in the environment puts a fault in the program's place
(or, for five, in the reference's), for showing that the comparison refuses it
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
import zlib

import numpy as np

from .. import flops, flops_conv, layer_metrics, pauses, stats, trace_reduce, traffic
from ..manifest import HERE
from . import (Context, RunFailure, capture_trace, check_device, kernel_native,
               lease, reduce_trace, start_cluster, stop_cluster)
from .train import TRACE_STEPS, WARM_STEPS
from .train_hybrid import _rel, logit_errors, seed_norms
from .train_moe import LAYER_TIE_GAP, MODEL_TIE_GAP, near_ties
from .train_sparse import bias_errors
from .train_ssm import _worst
from .train_swa import COMPARISON_PHASES, step_errors

# Positions of a layer's check input: a row of the cell's, two 4,096-blocks of
# the flash kernels' grid; rope's slowest pair has turned 0.012 rad there.
CHECK_TOKENS = 8192
# What can stand in the program's place (``BENCH_CONV_CONTROL``). PLANTED change
# the leaves the timed and compared program reads (the reference keeps the true
# ones); the next three give it a config that reads them otherwise; FAULTS
# change the REFERENCE's mathematics (``conv_moe_decoder``'s ``faults``) or its
# precision; the last two leave the program as it is and change what the
# compared step is given or what is kept of it.
CONTROLS = {
    "conv_left_out": "every conv's taps are (0, 0, 1) in the program: v = u, no earlier position",
    "taps_reversed": "every conv's taps in the other order: k_0 meets the current position",
    "thirds_x_b_c": "the in-projection's thirds read as X, B, C: the program's B is the "
                    "model's X, its C the model's B, its X the model's C",
    "fp8_weights": "the program computes with its bf16 weights rounded to float8_e4m3fn",
    "no_qk_norm": "q and k go to rope as projected: the per-head norms left out",
    "softmax_router": "the router scores by a softmax over its 32 outputs (no bias in the choice)",
    "no_c_gate": "the REFERENCE leaves the second gate out: out = v W_out",
    "silu_after_conv": "the REFERENCE puts a silu after the conv (the other causal convs' form)",
    "no_rope": "the REFERENCE leaves rope out: q and k go to the scores as normed",
    "bias_on_gates": "the REFERENCE's gates are the biased scores s + b, renormalised",
    "reference_default_precision": "the REFERENCE's float32 products run at the backend's "
                                   "default precision (one bf16 pass on a TPU), the nearest "
                                   "precision below the one it states",
    "half_batch": "the compared step is given the first half of its batch's tokens twice",
    "unchanged_state": "the compared step's new parameters and optimizer state are thrown away",
}
# Seeded selection biases are moved by a uniform +- this, each layer's 32 by a
# draw of its own. Adjacent sigmoid scores near the fourth of 32 lie ~0.03
# apart, so +-0.02 turns the choice of every few tokens (a bias left out
# shows), and the 8 held experts' share of the rows, which the step's time
# follows (0.11% of the step a percent of rows), stays within a few percent of
# a quarter in a layer and within one in the mean over 16: ``train_sparse``'s
# +-0.05 with ONE draw for the four slots of a period moved the mean share
# between 0.244 and 0.259 and the step by 0.7% from seed to seed (my chip
# runs, PR 56, call 1), most of the 1% bound.
BIAS_SPREAD = 0.02
PLANTED = ("conv_left_out", "taps_reversed", "thirds_x_b_c")
FAULTS = ("no_c_gate", "silu_after_conv", "no_rope", "bias_on_gates")
# The limits. Errors are the RMS of the difference over the features of a
# position (or over a leaf) as a share of the RMS of the reference's there. Each
# lies between two readings of THIS cell on the chip at the published widths,
# through this runner (my chip runs, PR 56; PERF.md section 6 has every
# reading): the largest a sound run gave over its seeds (and, of the ten controls
# run, the readings a control leaves alone) and the smallest that a control it is
# there to refuse gave; where nothing else is said the limit is their geometric
# mean.
# * One mixer alone on a seeded bf16 input of 8,192 positions, its output, the
#   worst token: the conv mixer 0.0049-0.0058, the attention mixer 0.0056-0.0093;
#   the head norms left out 0.600 (rope left out 0.941, the thirds misread 1.44,
#   the taps reversed 1.57, C left out 1.63, a silu after the conv 1.69, the conv
#   left out 2.06).
MIXER_RTOL = 0.065
#   and the gradient of that input under a seeded bf16 cotangent, worst token:
#   0.0041-0.0058 and 0.0071-0.0092; the head norms left out 0.694 (rope left out
#   1.16, the conv's controls 1.34-1.80).
MIXER_GRAD_RTOL = 0.077
#   The MEAN over the tokens of the conv mixer's output's error is what tells
#   the reference's own precision: it is an average over 16.8 M numbers' rounding
#   and hardly moves with the seed: 0.003701-0.003710 on ten sound runs of nine
#   weight seeds, 0.004050 against the reference at the backend's default
#   precision. (The attention mixer's mean moves with the seeded head norms,
#   0.00417-0.00461 sound and 0.00526 under that control: too near to judge.)
CONV_MEAN_RTOL = 0.00388
# * The expert layer's share, a token's error as a share of the layer's RMS, the
#   worst token not within 1e-4 of a routing tie: 0.0080-0.0092; the bias on the
#   gates 0.0366 (the reference at the backend's default precision 1.43: its
#   router's bf16 scores choose another fourth expert for a few tokens in a
#   hundred; a softmax router 2.11).
LAYER_RTOL = 0.018
# ... of whose tokens at most this share may lie within LAYER_TIE_GAP of a tie:
# 16-35 of 8,192 did. No control moves it.
TIES_MAX = 0.02
# * Logits of the first row's 8,192 positions, the MEDIAN: 0.0946-0.1020. A
#   tenth, and no rounding: EVERY position lies within 2% of a routing tie in
#   one of the 16 expert layers (``near_a_tie_share`` 0.9998-1.0), a bf16 stream
#   turns the fourth choice of a few tokens in a hundred a layer, and a chip
#   that holds a quarter of the experts adds or drops a whole expert's output
#   for such a token (the single layers above read 0.006-0.009 on the same
#   weights). The smallest a control gave: the head norms left out 0.148 (rope
#   left out 0.214, a softmax router 0.342, the conv's five 1.31-1.41). The
#   worst position (0.39-0.45) is reported and not judged, as the eighth cell's.
LOGIT_MEDIAN_RTOL = 0.122
# * The compared step's loss on the first batch against the reference's over the
#   same 32,764 target tokens: sound runs read -0.00018 to +0.00083; the thirds
#   misread -0.0031 (the conv left out -0.0087, the taps reversed -0.0185). A
#   WEAK limit, as its siblings' (the seventh cell's value, three times the
#   largest sound reading); no control rests on it alone.
LOSS_ATOL = 0.0025
# * The sequence-wise balance term over all 32 experts: within 1.5e-6; a softmax
#   router +0.00060 (C left out -0.00064, a silu after the conv -0.00092).
BALANCE_ATOL = 1e-4
# * The compared step's first gradient by what adafactor's new state holds of
#   it, the worst leaf: 0.388-0.412 on ten sound runs, always a router (the leaves adafactor keeps
#   element by element read 0.20-0.30, the factored matrices 0.01-0.03: the
#   swapped choices again, as the eighth cell's ``by_factoring``); the head
#   norms left out 1.00 (the conv's controls 1.21-6.2, a softmax router 17.8).
GRAD_STATS_RTOL = 0.65
# * The change of every parameter leaf ALONG the reference's float32 update, the
#   worst judged leaf: 0.327-0.336 (a router; the median leaf 0.055-0.059); a state
#   left unchanged reads 1.0 on every leaf, and so did the conv's five controls
#   (1.00-1.03; a softmax router 0.668, rope left out 0.754). Between the
#   reading and 1, with the more room above the reading.
UPDATE_ALONG_ATOL = 0.6
# * Share of the experts whose bias the step moved as the reference's rule moves
#   it from the reference's own counts (an expert whose rows are within a few
#   of the mean can go either way on a swapped choice): 0.9609-0.9922, 5 of a
#   leaf's 128 at the worst; a softmax router 0.469, a state left unchanged 0.
BIAS_AGREEMENT = 0.85
# * The held experts' share of all rows, the program's count against the
#   reference's on the same input, as a share of the reference's, in the layer
#   alone and in each of the 16 layers of the step: within 0.0064; a softmax
#   router 0.114.
HELD_SHARE_RTOL = 0.03
# every step of the window: the held experts' share as a multiple of 8 / 32,
# either way (0.244-0.260 read; the compact dispatch is compiled for twice the
# even share)
HELD_SHARE_BAND = 1.5
# the conv's past share, the program's count against the reference's on the
# same rows (within 3e-5; the conv left out 0.665), and the range every step of
# the window stays inside: seeded taps of equal variance give (K - 1) / K = 2/3
# (0.6648-0.6684 read); taps that leave the past out read 0
PAST_SHARE_ATOL = 0.01
PAST_SHARE_RANGE = (0.5, 0.8)


def model_config(model: dict, sizes: dict, control: str | None = None, **overrides):
    """The program's config object for a configuration file's ``model`` and
    ``train`` groups; ``control`` plants a fault. A program from before this
    model was supported fails here (``LlamaConfig`` takes no ``sconv_taps``:
    a TypeError), before a cluster or a chip is touched."""
    if model.get("model_type") != "lfm2_moe":
        raise RunFailure(f"runner train_conv builds no model of type {model.get('model_type')!r}")
    from ray_tpu.models.llama import LlamaConfig

    lead, period = flops_conv.lead_and_period(model)
    a = flops_conv.attention(model)
    assert model["norm_topk_prob"] and model["use_expert_bias"] and not model["conv_bias"]
    return LlamaConfig(
        vocab_size=model["vocab_size"], hidden=model["hidden_size"],
        n_layers=model["num_hidden_layers"], n_heads=a["heads"], n_kv_heads=a["kv_heads"],
        head_dim=a["head_dim"], rope_theta=a["rope_theta"], norm_eps=float(model["norm_eps"]),
        intermediate=model["moe_intermediate_size"], head_qk_norm=control != "no_qk_norm",
        layer_pattern=tuple(period), lead_pattern=tuple(lead),
        lead_intermediate=model["intermediate_size"], sconv_taps=model["conv_L_cache"],
        moe_experts=model["num_experts_published"], moe_top_k=model["num_experts_per_tok"],
        moe_norm_topk=bool(model["norm_topk_prob"]), moe_held=flops_conv.held(model),
        moe_score="softmax" if control == "softmax_router" else "sigmoid",
        moe_bias_rate=sizes["bias_rate"],
        moe_routed_scale=float(model["routed_scaling_factor"]),
        moe_aux_weight=sizes["aux_loss_weight"], moe_z_weight=0.0,
        tie_embeddings=model["tie_word_embeddings"], **overrides)


def reference_arch(model: dict, control: str | None = None) -> dict:
    """What ``reference/conv_moe_decoder.py`` needs to know of the file."""
    lead, period = flops_conv.lead_and_period(model)
    return dict(pattern=tuple(period), lead_pattern=tuple(lead),
                attn=flops_conv.attention(model), norm_eps=float(model["norm_eps"]),
                top_k=model["num_experts_per_tok"], norm_topk=bool(model["norm_topk_prob"]),
                held_first=flops_conv.held(model)[0],
                routed_scale=float(model["routed_scaling_factor"]),
                faults=frozenset({control} & set(FAULTS)))


def seed_leaves(params, key):
    """``seed_norms`` (the block norms, the heads' q and k norms and the final
    norm away from 1) and every selection bias moved by a seeded uniform
    +-BIAS_SPREAD, a draw of its own a leaf (``init_params`` starts a bias at 0,
    against which a choice that left it out reads the same as one that did
    not)."""
    import jax

    def move(path, leaf):
        if str(getattr(path[-1], "key", "")) != "router_bias":
            return leaf
        leaf_key = jax.random.fold_in(
            key, zlib.crc32(jax.tree_util.keystr(path).encode()) % 2**31)
        return leaf + jax.random.uniform(leaf_key, leaf.shape, minval=-BIAS_SPREAD,
                                         maxval=BIAS_SPREAD)

    return jax.tree_util.tree_map_with_path(move, seed_norms(params, key))


def planted(params, control: str | None):
    """The program's leaves under a control of PLANTED, wherever a conv layer's
    lie (leading layers and slots alike)."""
    import jax
    import jax.numpy as jnp

    if control not in PLANTED:
        return params

    def move(path, leaf):
        name = str(getattr(path[-1], "key", ""))
        if name == "conv" and control == "conv_left_out":
            return jnp.zeros_like(leaf).at[..., -1, :].set(1)
        if name == "conv" and control == "taps_reversed":
            return leaf[..., ::-1, :]
        if name == "w_in" and control == "thirds_x_b_c":
            return leaf[..., (2, 0, 1), :]
        return leaf

    return jax.tree_util.tree_map_with_path(move, params)


def layer_errors(cfg, arch, layers, ref_layers, h, g) -> dict:
    """One layer of each kind alone. ``layers`` = (a conv layer's leaves, an
    attention layer's with its expert layer), ``ref_layers`` the reference's; h,
    g [S, E] the layers' input (bf16, already normed) and the cotangent of
    their output."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models.llama import MIXERS
    from ray_tpu.models.moe import moe_block

    from ..reference import conv_moe_decoder as ref

    get = lambda x: np.asarray(jax.device_get(x))  # noqa: E731
    f32 = lambda x: x.astype(jnp.float32)  # noqa: E731
    positions = jnp.arange(h.shape[0], dtype=jnp.int32)
    faults = arch["faults"]

    def both(fn, h, g):
        """(y, dL/dh) of ``fn(h)`` -> y [S, E] under the cotangent g."""
        y, pull = jax.vjp(fn, h)
        return y, pull(g.astype(y.dtype))[0]

    def mixer(kind):
        def run(h, w):
            out = MIXERS[kind].apply(h[None], w, config=cfg, positions=positions, mesh=None)
            return (out[0] if isinstance(out, tuple) else out)[0]
        return run

    out = {}
    for name, kind, i, want_fn in (
            ("conv", "sconv", 0, lambda h, w: ref.conv_mixer(h, w, faults)[0]),
            ("attention", "attn", 1, lambda h, w: ref.attention_mixer(
                h, w, arch["attn"], arch["norm_eps"], faults))):
        got = jax.jit(lambda w, run=mixer(kind): both(lambda h: run(h, w), h, g))(layers[i])
        want = jax.jit(lambda w, fn=want_fn: both(lambda h: fn(h, w), f32(h), f32(g)))(
            ref_layers[i])
        out[name] = {"out": _worst(get(_rel(got[0], want[0], -1))),
                     "grad": _worst(get(_rel(got[1], want[1], -1)))}
        del got, want
    got, aux = jax.jit(lambda h, w: moe_block(
        h[None], w, top_k=cfg.moe_top_k, norm_topk=cfg.moe_norm_topk, held=cfg.moe_held,
        score=cfg.moe_score, routed_scale=cfg.moe_routed_scale))(h, layers[1])
    want, routing = jax.jit(lambda h, w: ref.expert_layer(
        h, w, top_k=arch["top_k"], norm_topk=arch["norm_topk"], first=arch["held_first"],
        scale=arch["routed_scale"], faults=faults))(h, ref_layers[1])
    # a token's error against the LAYER's size: the held experts add nothing to
    # a token none of them was chosen for (three in ten at 8 of 32, top-4)
    err = get(jnp.sqrt(jnp.mean(jnp.square(f32(got[0]) - want), axis=-1)
                       / jnp.mean(jnp.square(want))))
    tie = get(near_ties(routing["biased"], arch["top_k"], LAYER_TIE_GAP))
    first, count = cfg.moe_held
    ref_rows = get(routing["rows"])
    out["experts"] = {"max": float(err[~tie].max()), "mean": float(err[~tie].mean()),
                      "ties": int(tie.sum()), "tokens": int(err.size),
                      "rows": int(get(aux["rows"]).sum()), "dropped": int(aux["dropped"]),
                      "held_share": float(aux["held_share"]),
                      "ref_held_share": float(ref_rows[first:first + count].sum()
                                              / ref_rows.sum())}
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
    from ray_tpu.models import init_params, loss_fn, param_axes, update_buffers
    from ray_tpu.models.llama import forward_hidden
    from ray_tpu.parallel import MeshConfig, create_mesh
    from ray_tpu.parallel.sharding import logical_sharding, sharding_tree
    from ray_tpu.tpu import device_report, leased_devices

    from ..reference import conv_moe_decoder as ref

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
    # one device: a mesh of one is no mesh to the step
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
        # the selection biases: no gradient moves them, the step's counts do
        params = update_buffers(optax.apply_updates(params, updates), aux, cfg)
        # the program's counters, from the same pass as the loss
        counters = (loss, aux["load_balance"], aux["rows_per_expert"].sum(axis=-1),
                    aux["rows_dropped"], aux["rows_per_held_expert"], aux["held_share"],
                    aux["sconv_past_share"])
        return params, opt_state, counters

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

    # correctness, before the window: program vs plain reference. One layer of
    # each kind alone: the first period's LAST conv layer, and its attention
    # layer with the expert layer of the same block
    pattern = cfg.layer_pattern
    pick = lambda tree, slot: jax.tree.map(lambda a: a[0], tree["layers"][slot])  # noqa: E731
    slots = (f"slot{len(pattern) - 1 - pattern[::-1].index('sconv')}",
             f"slot{pattern.index('attn')}")
    n_check = min(config["check_tokens"], first.shape[1])
    h, g = (jax.random.normal(jax.random.PRNGKey(config["seed"] + i), (n_check, cfg.hidden),
                              cfg.dtype) for i in (1, 2))
    layers = layer_errors(cfg, arch, tuple(pick(params, s) for s in slots),
                          tuple(pick(ref_params, s) for s in slots), h, g)
    del h, g, ref_params
    mark("layers")

    def first_row_logits(p, t):
        hidden = forward_hidden(p, t, cfg, mesh=step_mesh)
        return jnp.einsum("se,ve->sv", hidden[0], p["embed"],
                          preferred_element_type=jnp.float32)

    prog_logits = jax.device_get(jax.jit(first_row_logits)(
        params, jax.device_put(first[:1], rows_sharding)))
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
    ref_loss, seen, ref_grads = ref.loss_and_grads(
        ref_params, jnp.asarray(first), arch, aux_weight=sizes["aux_loss_weight"])
    mark("reference_step")
    # a tie is the k-th and (k+1)-th of what the choice ranks: score + bias
    whole = logit_errors(jnp.asarray(prog_logits),
                         {"logits": jnp.asarray(seen["logits"]),
                          "probs": jnp.asarray(seen["biased"])}, arch["top_k"])
    ref_rows = np.asarray(jax.device_get(seen["rows_per_expert"]))
    first_held, held = cfg.moe_held
    whole.update(ref_loss=float(ref_loss), ref_ce=float(seen["ce"]),
                 ref_balance=float(seen["balance"]), ref_past_share=float(seen["past_share"]),
                 ref_held_share=(ref_rows[:, first_held:first_held + held].sum(axis=-1)
                                 / ref_rows.sum(axis=-1)).tolist())
    start = fp8(ref_params) if control == "fp8_weights" else planted(ref_params, control)
    step = step_errors(opt, start, after, opt_state, ref_params, ref_grads)
    bias = bias_errors(start, after, ref_rows, sizes["bias_rate"])
    del prog_logits, seen, ref_grads
    params, opt_state = alone_on_device((start, ref_params), (after, opt_state))
    del after, start, ref_params
    mark("step_compared")

    rows_per_step = sizes["batch"] * first.shape[1] * cfg.moe_top_k
    losses, rows_wrong = [], []
    counted = {"load_max_over_mean": [], "held_share": [], "rows_per_held_expert": [],
               "past_share": []}
    step_t_a, step_ms, wait_ms, report_ms = [], [], [], []

    def one_step(tokens):
        nonlocal params, opt_state
        params, opt_state, counters = compiled(
            params, opt_state, {"tokens": jax.device_put(tokens, rows_sharding)})
        loss, _, rows, dropped, rows_held, held_share, past = jax.device_get(counters)  # the fence
        if int(dropped) or (rows != rows_per_step).any():
            rows_wrong.append([int(dropped), rows.tolist()])
        # over the experts this chip holds: their rows are what its grouped
        # matmuls compute
        return {"loss": float(loss),
                "moe_load_max_over_mean": float(
                    (rows_held.max(axis=-1) / rows_held.mean(axis=-1)).mean()),
                "held_share": float(held_share.mean()),
                "rows_per_held_expert": float(rows_held.mean()),
                "sconv_past_share": float(past)}

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
        counted["held_share"].append(said["held_share"])
        counted["rows_per_held_expert"].append(said["rows_per_held_expert"])
        counted["past_share"].append(said["sconv_past_share"])
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
        "check_held_share": step0[5].tolist(), "check_past_share": float(step0[6]),
        "check_tokens": int(first.shape[0] * (first.shape[1] - 1)),
        "check_positions": int(first.size), "rows_per_token": cfg.moe_top_k,
        "whole": whole, "layers": layers, "step": step, "bias": bias,
        "rows_wrong": rows_wrong[:5], "counted": counted, "traced_steps": traced,
        "device": device, "trace": summary}})


# the phases of ``_loop`` that are the comparison's own, left out of ``setup_s``
# (``train_swa.COMPARISON_PHASES`` and the state's trip before the first step)
PHASES_COMPARED = (*COMPARISON_PHASES, "state_alone_on_device")


def run(ctx: Context) -> dict:
    cfg = ctx.cell.config
    if ctx.rehearse:
        with open(os.path.join(HERE, "rehearse-conv.json")) as f:
            cfg = {**ctx.rehearse, **json.load(f)}
    mix = ctx.cell.traffic
    seq = int(mix["seq"]) if not ctx.rehearse else int(cfg["train_seq"])
    sizes, model = dict(cfg["train"]), cfg["model"]
    control = os.environ.get("BENCH_CONV_CONTROL") or None
    if control not in (None, *CONTROLS):
        raise RunFailure(f"BENCH_CONV_CONTROL is {control!r}: one of {tuple(CONTROLS)}")
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
            run_config=RunConfig(name="bench-train-conv",
                                 storage_path=tempfile.mkdtemp(prefix="bench-train-conv-")),
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
    bias, traces, experts = m["bias"], device["kernel_traces"], layers["experts"]
    n_expert_layers = model["num_hidden_layers"] - model["num_dense_layers"]
    even_share = model["num_experts"] / model["num_experts_published"]
    low, high = PAST_SHARE_RANGE
    checks = {
        "losses_finite": all(math.isfinite(x) for x in m["losses"]),
        "conv_layer_matches_reference": layers["conv"]["out"]["max"] <= MIXER_RTOL
        and layers["conv"]["grad"]["max"] <= MIXER_GRAD_RTOL
        and layers["conv"]["out"]["mean"] <= CONV_MEAN_RTOL,
        "attention_layer_matches_reference": layers["attention"]["out"]["max"] <= MIXER_RTOL
        and layers["attention"]["grad"]["max"] <= MIXER_GRAD_RTOL,
        "expert_layer_matches_reference": experts["max"] <= LAYER_RTOL
        and experts["ties"] <= max(2, TIES_MAX * experts["tokens"]),
        "logits_match_reference": whole["median"] <= LOGIT_MEDIAN_RTOL,
        "loss_matches_reference": abs(m["prog_loss"] - whole["ref_loss"]) <= LOSS_ATOL,
        "balance_matches_reference":
        abs(m["prog_balance"] - whole["ref_balance"]) <= BALANCE_ATOL,
        "gradient_statistics_match_reference": step["grad_stats"]["worst"] <= GRAD_STATS_RTOL,
        "update_matches_reference": step["update"]["worst"] <= UPDATE_ALONG_ATOL,
        "bias_steps_as_the_reference": bias["agreement"] >= BIAS_AGREEMENT
        and bias["layers"] == n_expert_layers,
        "no_row_dropped": not m["rows_wrong"] and m["check_rows_dropped"] == 0
        and experts["dropped"] == 0
        and experts["rows"] == experts["tokens"] * m["rows_per_token"]
        and m["check_rows_per_layer"]
        == [m["check_positions"] * m["rows_per_token"]] * n_expert_layers,
        "held_share_is_the_chips_share": all(
            abs(got - want) <= HELD_SHARE_RTOL * want for got, want in [
                (experts["held_share"], experts["ref_held_share"]),
                *zip(m["check_held_share"], whole["ref_held_share"], strict=True)])
        and all(even_share / HELD_SHARE_BAND <= x <= even_share * HELD_SHARE_BAND
                for x in counted["held_share"]),
        "the_earlier_taps_matter":
        abs(m["check_past_share"] - whole["ref_past_share"]) <= PAST_SHARE_ATOL
        and all(low <= x <= high for x in [m["check_past_share"], *counted["past_share"]]),
        "attention_kernels_native": kernel_native(traces, "flash_attention", ctx.platform),
        "grouped_matmul_native": kernel_native(traces, "moe_gmm", ctx.platform)
        and kernel_native(traces, "moe_tgmm", ctx.platform),
        "custom_calls_compiled": (m["tpu_custom_calls"] > 0) == (ctx.platform == "tpu"),
    }
    marks += [tuple(x) for x in m["marks"]] + [("window_start", m["t_window_start_wall"])]
    phases = {b[0]: b[1] - a[1] for a, b in zip(marks, marks[1:])}
    compared_s = sum(phases[k] for k in PHASES_COMPARED)
    ctx.say({"setup_phases_s": phases, "comparison_s": compared_s})
    quart = lambda xs: [stats.percentile(xs, q) for q in (25, 50, 75)]  # noqa: E731
    ctx.say({"control": control and f"{control}: {CONTROLS[control]}", "checks": checks,
             "limits": {
        "MIXER_RTOL": MIXER_RTOL, "MIXER_GRAD_RTOL": MIXER_GRAD_RTOL, "LAYER_RTOL": LAYER_RTOL,
        "CONV_MEAN_RTOL": CONV_MEAN_RTOL,
        "TIES_MAX": TIES_MAX, "LOGIT_MEDIAN_RTOL": LOGIT_MEDIAN_RTOL, "loss_atol": LOSS_ATOL, "BALANCE_ATOL": BALANCE_ATOL,
        "GRAD_STATS_RTOL": GRAD_STATS_RTOL, "UPDATE_ALONG_ATOL": UPDATE_ALONG_ATOL,
        "BIAS_AGREEMENT": BIAS_AGREEMENT, "HELD_SHARE_RTOL": HELD_SHARE_RTOL,
        "HELD_SHARE_BAND": HELD_SHARE_BAND, "PAST_SHARE_ATOL": PAST_SHARE_ATOL,
        "PAST_SHARE_RANGE": PAST_SHARE_RANGE, "MODEL_TIE_GAP": MODEL_TIE_GAP,
        "LAYER_TIE_GAP": LAYER_TIE_GAP},
        "whole_model": whole, "layers": layers, "step": step, "bias": bias,
        "prog_loss": m["prog_loss"], "prog_balance": m["prog_balance"],
        "check_past_share": m["check_past_share"], "check_tokens": m["check_tokens"],
        "check_rows_per_layer": m["check_rows_per_layer"],
        "check_held_share": m["check_held_share"], "rows_wrong": m["rows_wrong"],
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
                      # compiler's peak for the step program, as train_ssm
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
        # the calls the trace holds of the plain flash kernels, recomputed ones
        # included, THEIR seconds and the least seconds those same calls could
        # take. A CPU rehearsal interprets the kernels into plain ops, so its
        # trace holds none: a share of the roofline then reads 0 over the window.
        took, least, kernel_calls = 0.0, 0.0, {}
        costs = flops_conv.flash_kernel_costs(model, sizes["batch"], seq)
        for kernel, (kernel_flops, kernel_bytes) in costs.items():
            pattern = rf'^%{kernel}(\.[\w.\-]+)? = .*custom_call_target="tpu_custom_call"'
            seconds, calls = trace_reduce.matching(summary["ops"], pattern)
            took += seconds
            least += calls * flops_conv.roofline_seconds(kernel_flops, kernel_bytes, peaks)
            # with one event's name as the trace printed it, for the readers' tests
            kernel_calls[kernel] = [calls, seconds, next(
                (name[:600] for name in summary["ops"] if re.search(pattern, name)), None)]
        flash = {"least_seconds": least, "seconds": took if took else summary["window_s"]}
        first, last = m["traced_steps"]
        # the grouped-matmul calls the trace holds and THEIR seconds, as
        # train_mla.py: a call's FLOPs are those of the rows the held experts
        # computed, from the traced steps' own count of them
        share = ctx.cell.readers.get("kernel.moe_gmm_share.train")
        gmm_s, gmm_calls = (trace_reduce.matching(summary["ops"], share["params"]["pattern"])
                            if share else (0.0, 0))
        held_share = stats.mean(counted["held_share"][first:last])
        rows_held = held_share * tokens_per_step * model["num_experts_per_tok"]
        gmm_flops, gmm_bytes = flops_conv.grouped_matmul_costs(model, rows_held)
        ctx.say({"moe_gmm_calls": gmm_calls, "moe_gmm_seconds": gmm_s,
                 "rows_held_a_layer": rows_held, "moe_gmm_bytes_per_call": gmm_bytes,
                 "kernel_calls": kernel_calls, "kernel_families": {"flash": flash},
                 "forward_flops_by_part": flops_conv.forward_flops_by_part(model, seq)})
        obs = {"timers": {"data_wait_ms": window["data_wait_ms"],
                          "report_ms": window["report_ms"],
                          "step_ms_median": stats.percentile(m["step_ms"], 50)},
               # from the median step, not the window: the capture's own
               # start, stop and reduction sit inside a traced window
               "train": {"tok_s_chip": tokens_per_step / chips * 1e3
                         / stats.percentile(m["step_ms"], 50),
                         "peak_flops_per_s": peaks["bf16_flops_per_s"],
                         "flops_per_token": flops_conv.train_flops_per_token(model, seq)},
               "moe": {"load_max_over_mean": stats.mean(
                           counted["load_max_over_mean"][first:last]),
                       "held_share": held_share,
                       "gmm_flops_per_call": gmm_flops,
                       "gmm_calls": gmm_calls,
                       "gmm_seconds": gmm_s if gmm_calls else summary["window_s"]},
               "sconv": {"past_share": stats.mean(counted["past_share"][first:last])},
               "flash": flash,
               "trace": summary}
        values = layer_metrics.read_all(ctx.cell.readers, obs)
        out["device"].update(busy_s=summary["busy_s"], window_s=summary["window_s"])
        out["breakdown"] = {"device_ops": summary["device_ops"],
                            "idle_gaps": summary["idle_gaps"]}
    declared = ctx.cell.declared(ctx.trace)
    out["metrics"] = {k: {"value": v, "unit": declared[k]}
                      for k, v in values.items() if k in declared}
    return out

"""Runner of the training cells of a decoder that mixes full and window
layers of grouped-query attention (a head count, a rope and a window a kind;
YaRN; a head-wise gate), a leading dense layer and softmax-routed experts
under a routed scale beside a plain shared expert, a chip's share of the
experts and of the vocabulary: the train runner's contract
(``runners/train.py``: the same phases, the same fenced steps, the same
window rule through ``pauses.window_report``, the same result line through
``result.emit``) with the configuration builder and the plain reference
swapped, as ``train_hybrid.py`` and ``train_sparse.py``. Which model it
builds is the configuration file's ``model_type``.

What decides ``correct``, all before the window, against
``reference/windowed_moe_decoder.py`` on the program's own bf16-rounded
weights (every norm weight first moved by a seeded +-0.5, so that a norm left
out shows):

* ONE layer of each kind alone, at the configuration's widths, on a seeded
  bf16 input of CHECK_TOKENS positions (the window drops keys there and
  YaRN's slowed pairs have turned): the full mixer and the window mixer
  (``MIXER_RTOL``), the expert layer's share (``LAYER_RTOL``);
* logits at every position of the batch's first row (``LOGIT_RTOL``,
  ``LOGIT_MEDIAN_RTOL``);
* THE TIMED STEP ITSELF, run once on the first batch: its loss and its
  balance term (``LOSS_ATOL``, ``BALANCE_ATOL``); the statistics
  of its first gradient that the optimizer's new state holds and the change
  of every parameter leaf (``GRAD_STATS_RTOL``, ``UPDATE_ALONG_ATOL``), against the
  reference's gradient on the same rows put through the same optimizer in
  float32;
* the counts, in that step and in every step of the window: rows routed =
  tokens x experts per token (nothing dropped), the held experts' share of
  them against 1/8 (``HELD_SHARE_RTOL``), the window layers' pairs over the
  causal pairs against the closed form (``WINDOW_SHARE_ATOL``);
* the flash, window and grouped-matmul kernels ran native on the chip.

``BENCH_SWA_CONTROL`` in the environment puts a fault in the program's place,
for showing that the comparison refuses it (``CONTROLS``); such a run says so
in its output and must end ``correct`` false.
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

from .. import flops, flops_swa, layer_metrics, pauses, stats, trace_reduce, traffic
from ..manifest import HERE
from . import (Context, RunFailure, capture_trace, check_device, kernel_native,
               lease, reduce_trace, start_cluster, stop_cluster)
from .train import TRACE_STEPS, WARM_STEPS
from .train_hybrid import _rel, logit_errors, seed_norms
from .train_moe import LAYER_TIE_GAP, LAYER_TIES_MAX, MODEL_TIE_GAP, near_ties

# Tokens of a layer's check input: four windows long, so most queries see a
# full band; positions at which YaRN's slowed pairs (128-fold) have turned
# by up to 0.04 rad where plain rope turns them 5 rad, and four 512-blocks of
# the window kernels, two 1024-blocks of the plain ones, each way.
CHECK_TOKENS = 2048
# What can stand in the program's place (``BENCH_SWA_CONTROL``). The first
# five change the program that is timed and compared (the same leaves, a
# config that reads them otherwise); the next two reshape leaves, so they
# stand in the single layers' comparison alone; the last two leave the program
# as it is and change what the compared step is given or what is kept of it.
CONTROLS = {
    "fp8_weights": "the program computes with its bf16 weights rounded to float8_e4m3fn, "
                   "the nearest precision below the configuration's",
    "no_window": "the window layers see every causal key (a window as long as the row)",
    "plain_rope": "the full layers turn by plain rope at theta 500,000: YaRN's blend and its "
                  "factor on cos and sin left out",
    "no_gate": "the head-wise gate left out of both kinds of layer",
    "no_scale": "the routed experts' gates not multiplied by 2.5",
    "win_48_heads": "the window layer alone at the full layers' head count: 48 of its 72 heads "
                    "(each kv head's first six)",
    "softmax_held": "the expert layer alone scoring the 32 held experts only: softmax over 32 "
                    "outputs, top-10 of them",
    "half_batch": "the compared step is given the first half of its batch's tokens twice",
    "unchanged_state": "the compared step's new parameters and optimizer state are thrown away",
}
LAYER_CONTROLS = ("win_48_heads", "softmax_held")
# The limits. Errors are the RMS of the difference over the features of a
# position (or over a leaf) as a share of the RMS of the reference's there.
# Each lies between two readings on the chip at the published widths, THROUGH
# THIS RUNNER (my chip runs, PR 39: both rounds, twenty-one seeds; PERF.md
# section 6): the largest the program gave over its seeds, and the smallest a
# control gave that the limit is there to refuse (``fp8_weights`` on three
# seeds for the layers, the logits and the gradient; ``half_batch`` and
# ``unchanged_state`` on three seeds for the step). The layers' readings
# hardly move with the seed: 16,384 positions and 1.7 B weights average it out.
# * Logits of the first row's 16,384 positions, the MEDIAN: 0.0177-0.0183
#   (five layers of two bf16 sub-blocks at ~0.004-0.008 each); fp8 weights
#   0.381-0.385. The limit is their geometric mean.
LOGIT_MEDIAN_RTOL = 0.08
# * Logits, EVERY position: the worst reads 0.354-0.418, a position where
#   bf16 swapped an expert in some layer (85% of the positions are within 2%
#   of a routing tie in one of four layers: 256 softmax outputs lie close,
#   ten are taken, and the routed sum counts 2.5-fold; the worst position not
#   near a tie 0.225-0.254); fp8 0.631-0.678. The limit is the geometric mean
#   of 0.418 and 0.631 (0.51): a factor of 1.2 each way is all the room there
#   is (over the seeds the reading spreads by 0.02).
LOGIT_RTOL = 0.5
# * One mixer alone on a seeded bf16 input of 2,048 positions, worst token:
#   the full layer (YaRN, 48 heads) 0.0074-0.0106, the window layer (72
#   heads) 0.0043-0.0044; fp8 0.111-0.118 and 0.089-0.094. Every other control
#   reads above 0.1 there (section 6). The limit is the geometric mean of
#   0.0106 and 0.089, rounded down.
MIXER_RTOL = 0.025
# * The expert layer's share, worst token not within 1e-4 of a routing tie
#   (2-12 of 2,048 are): 0.0045-0.0060; fp8 0.0715-0.0747; the scale left out
#   or a softmax over the held experts alone read above 0.43. Geometric mean
#   0.0207.
LAYER_RTOL = 0.02
# * The compared step's loss on the first batch (chunked head, cross entropy,
#   + 0.001 x the balance term) against the reference's over the same 16,383
#   target tokens. Sound runs read -0.00070 to +0.00040 (twenty-one seeds, RMS
#   0.00032; the hybrid cell's accepted limit at this count of tokens, 0.00086,
#   copied in the first round, stood 1.2 x off the largest); ``half_batch``
#   reads 0.0011, 0.0072 and 0.0146 off, ``plain_rope`` 0.0018-0.017, fp8
#   weights 0.0003-0.0045. The geometric mean of 0.00070 and ``half_batch``'s
#   median. A WEAK limit, and no control rests on it: a fault moves a mean over
#   16,383 tokens by what sampling moves it, and on a seed in three it falls
#   inside (``half_batch`` 0.0011, ``no_gate`` 0.0001); the gradient's and the
#   layers' limits refuse those.
LOSS_ATOL = 0.002
# * The balance term itself, over all 256 experts and the batch's tokens, the
#   step's against the reference's: within 0.00005 (bf16 swaps a few of
#   163,840 choices); ``half_batch`` 0.0042-0.0053 (fp8 0.00007-0.00039, on
#   both sides of the limit: others refuse it). The geometric mean of 0.00005
#   and 0.0042.
BALANCE_ATOL = 0.0005
# * The compared step's first gradient, by what the optimizer's new state
#   holds of it (adafactor: the mean of its squares along the rows and along
#   the columns of a matrix), against the same statistics of the reference's
#   gradient: the worst leaf 0.089-0.106 (a norm's weight; the median leaf
#   0.024-0.026); fp8 0.659-0.701, ``unchanged_state`` 1.0, ``half_batch``
#   1.13-1.28. The geometric mean of 0.106 and 0.659.
GRAD_STATS_RTOL = 0.25
# * The change of every parameter leaf in that step ALONG the reference's
#   float32 update (``step_errors``' ``update``): the worst leaf 0.045-0.050 (a
#   head-wise gate's leaf, all five of them alike; the median leaf
#   0.006); fp8 0.486-0.492 (a router; median 0.19), ``half_batch``
#   0.574-0.583 (median 0.445), ``unchanged_state`` 1.0 on every leaf. The
#   geometric mean of 0.050 and 0.486. The RMS of the two ROUNDED changes'
#   difference (``update_rounded``, the first round's measure, still
#   reported) reads 0.454 at worst and 0.30 in the median on the same sound
#   step: the reference's own step moves one element in five of a bf16
#   matrix at all (``ref_moved_share`` 0.21 in the median: the others' update
#   is under half a unit in the last place), and an element that a gradient 2%
#   off pushes over that edge on one side only counts there by a whole unit.
UPDATE_ALONG_ATOL = 0.15
# ... of a leaf of at least this many elements, of which the reference's own
# step, rounded as the leaf rounds, moves at least this share
# (``train_sparse``'s, for its reasons: a norm's weights of 0.5-1.5 do not move
# at all by a thousandth)
UPDATE_MIN_LEAF, UPDATE_MIN_MOVED = 1024, 0.02
# The phases of set-up (``marks``) that are the comparison's and not the
# program's: the single layers, the logits, the reference's step and the leaf
# by leaf comparison with the state's trips to the host and back. ``setup_s``
# leaves their seconds out; the step's own first run (``first_step``) stays in.
COMPARISON_PHASES = ("layers", "logits", "step_moved_to_host", "reference_step",
                     "step_compared")
# The held experts' share of all rows against 32 / 256: uniform ids over
# seeded weights route near-evenly; the share of a step reads within a few
# percent of an eighth (plus four standard deviations of that many draws,
# which is what a rehearsal's 384 rows need). A program that scored the held
# experts alone reads 1.
HELD_SHARE_RTOL = 0.15
# The window layers' pairs over the causal pairs, the program's count from
# the positions it was given against the closed form: float32 sums of 16,384
# terms agree to 1e-6; no window reads 1.
WINDOW_SHARE_ATOL = 1e-5


def model_config(model: dict, sizes: dict, control: str | None = None, **overrides):
    """The program's config object for a configuration file's ``model`` and
    ``train`` groups, by ``model_type``; ``control`` plants a fault. A program
    from before this model was supported fails here (no
    ``GroupedQueryAttention``), before a cluster or a chip is touched."""
    if model.get("model_type") != "laguna":
        raise RunFailure(f"runner train_swa builds no model of type "
                         f"{model.get('model_type')!r}")
    try:
        from ray_tpu.models.gqa import GroupedQueryAttention, Yarn
        from ray_tpu.models.llama import LlamaConfig
    except ImportError as e:
        raise RunFailure(f"this program has no grouped-query attention by spec: {e}") from e

    kinds = {k: dict(v) for k, v in flops_swa.kinds(model).items()}
    if control == "no_window":
        kinds["gqa_win"]["window"] = 1 << 30
    if control == "plain_rope":
        kinds["gqa"]["yarn"] = None
    if control == "no_gate":
        for spec in kinds.values():
            spec["gate"] = "none"
    specs = {k: GroupedQueryAttention(**{**v, "yarn": v["yarn"] and Yarn(**v["yarn"])})
             for k, v in kinds.items()}
    names, lead = flops_swa.layer_kinds(model), flops_swa.lead_layers(model)
    period = flops_swa.period(model)
    first, last = model["experts_held"]
    assert model["num_experts"] == last - first + 1 and model["decoder_sparse_step"] == 1
    assert not model["moe_apply_router_weight_on_input"]
    assert not model["moe_router_logit_softcapping"] and not model["attention_bias"]
    return LlamaConfig(
        vocab_size=model["vocab_size"], hidden=model["hidden_size"],
        n_layers=model["num_hidden_layers"], n_heads=model["num_attention_heads"],
        n_kv_heads=model["num_key_value_heads"], head_dim=model["head_dim"],
        intermediate=model["moe_intermediate_size"], norm_eps=float(model["rms_norm_eps"]),
        layer_pattern=tuple(period), lead_pattern=tuple(names[:lead]),
        lead_intermediate=model["intermediate_size"],
        gqa=specs["gqa"], gqa_window=specs["gqa_win"],
        moe_experts=model["router_width"], moe_top_k=model["num_experts_per_tok"],
        moe_norm_topk=bool(model["norm_topk_prob"]),
        moe_shared=model["shared_expert_intermediate_size"], moe_shared_gate=False,
        moe_held=(first, model["num_experts"]),
        moe_routed_scale=1.0 if control == "no_scale"
        else float(model["moe_routed_scaling_factor"]),
        moe_aux_weight=sizes["aux_loss_weight"], moe_z_weight=0.0, **overrides)


def reference_arch(model: dict) -> dict:
    """What ``reference/windowed_moe_decoder.py`` needs to know of the file."""
    names, lead = flops_swa.layer_kinds(model), flops_swa.lead_layers(model)
    return dict(kinds=flops_swa.kinds(model), pattern=tuple(flops_swa.period(model)),
                lead_pattern=tuple(names[:lead]), norm_eps=float(model["rms_norm_eps"]),
                top_k=model["num_experts_per_tok"], norm_topk=bool(model["norm_topk_prob"]),
                held_first=model["experts_held"][0],
                routed_scale=float(model["moe_routed_scaling_factor"]))


def layer_errors(cfg, arch, layers, ref_layers, h, control=None) -> dict:
    """One layer of each kind alone on the same input h [S, E] (bf16, already
    normed): the program's mixers and ``moe_block`` (``cfg``, ``layers`` = a
    full layer's leaves and a window layer's, which is also an expert layer)
    against the reference's (``arch``, ``ref_layers``). A control of
    LAYER_CONTROLS reshapes the program's leaves here."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models.gqa import gqa_mixer
    from ray_tpu.models.moe import moe_block

    from ..reference import windowed_moe_decoder as ref

    get = lambda x: np.asarray(jax.device_get(x))  # noqa: E731
    positions = jnp.arange(h.shape[0], dtype=jnp.int32)
    out = {}
    for name, kind, spec, layer, ref_layer in (
            ("full", "gqa", cfg.gqa, layers[0], ref_layers[0]),
            ("window", "gqa_win", cfg.gqa_window, layers[1], ref_layers[1])):
        if name == "window" and control == "win_48_heads":
            # the full layers' head count: each kv head's first 6 of its 9
            group, kept = spec.heads // spec.kv_heads, cfg.gqa.heads // spec.kv_heads
            take = np.array([i for i in range(spec.heads) if i % group < kept])
            spec = dataclasses.replace(spec, heads=cfg.gqa.heads)
            layer = {**layer, "wq": layer["wq"][:, take], "wo": layer["wo"][take],
                     "w_attn_gate": layer["w_attn_gate"][:, take]}
        got, aux = jax.jit(lambda h, w, spec=spec: gqa_mixer(
            h[None], w, spec, config=cfg, positions=positions))(h, layer)
        want = jax.jit(lambda h, w, kind=kind: ref.gqa_mixer(h, w, arch["kinds"][kind]))(
            h, ref_layer)
        err = get(_rel(got[0], want, -1))
        out[name] = {"max": float(err.max()), "mean": float(err.mean())}
        if "window_share" in aux:
            out[name]["window_share"] = float(aux["window_share"])
    layer, kw = layers[1], dict(held=cfg.moe_held, top_k=cfg.moe_top_k)
    if control == "softmax_held":
        first, count = cfg.moe_held
        layer, kw = {**layer, "router": layer["router"][:, first:first + count]}, dict(
            held=None, top_k=min(cfg.moe_top_k, count))
    got, aux = jax.jit(lambda h, w: moe_block(
        h[None], w, norm_topk=cfg.moe_norm_topk, routed_scale=cfg.moe_routed_scale, **kw))(
        h, layer)
    want, routing = jax.jit(lambda h, w: ref.expert_layer(
        h, w, top_k=arch["top_k"], norm_topk=arch["norm_topk"], first=arch["held_first"],
        scale=arch["routed_scale"]))(h, ref_layers[1])
    err = get(_rel(got[0], want, -1))
    tie = get(near_ties(routing["probs"], arch["top_k"], LAYER_TIE_GAP))
    out["experts"] = {"max": float(err[~tie].max()), "mean": float(err[~tie].mean()),
                      "ties": int(tie.sum()), "tokens": int(err.size),
                      "rows": int(get(aux["rows"]).sum()), "dropped": int(aux["dropped"]),
                      "held_share": float(aux.get("held_share", 1.0))}
    return out


@functools.lru_cache(maxsize=None)
def _leaf_errors(opt):
    """``step_errors``'s readings of one leaf, jitted once an optimizer."""
    import jax
    import jax.numpy as jnp

    def rel(got, want):
        size = jnp.linalg.norm(want.ravel())
        off = jnp.linalg.norm((got.astype(jnp.float32) - want).ravel())
        return jnp.where(size > 0, off / size, off)

    @jax.jit
    def errors(start, after, v_row, v_col, v, ref_start, ref_grad):
        p32, g32 = ref_start.astype(jnp.float32), ref_grad.astype(jnp.float32)
        d, state = opt.update(g32, opt.init(p32), p32)
        # rounded as the leaf's type rounds: ``reduce_precision`` and not a
        # cast there and back, which the chip's compiler drops
        kept = jnp.finfo(ref_start.dtype)
        want = jax.lax.reduce_precision(p32 + d, kept.nexp, kept.nmant) - p32
        got = after.astype(jnp.float32) - start.astype(jnp.float32)
        along, ref_along = jnp.sum(got * d), jnp.sum(want * d)
        ref_state = state[0]  # FactoredState; the fields a leaf does not use are one zero
        return (jnp.abs(1 - jnp.where(ref_along > 0, along / ref_along, 0.0)), rel(got, want),
                jnp.maximum(jnp.maximum(rel(v_row, ref_state.v_row), rel(v_col, ref_state.v_col)),
                            rel(v, ref_state.v)),
                jnp.linalg.norm(got.ravel()) / jnp.linalg.norm(p32.ravel()),
                jnp.mean(want != 0))

    return errors


def step_errors(opt, start, after, opt_state, ref_start, ref_grads: dict) -> dict:
    """What one step of the program did (parameters ``start`` -> ``after``, the
    optimizer's state after it; ``after`` may lie on the host) against the
    reference's step: the reference's gradient ``ref_grads`` (by leaf name) put
    through the same optimizer in float32 from ``ref_start``, d an element's
    float32 update and ``want`` the change it makes once the sum is rounded to
    the leaf's type. A leaf at a time:

    * ``grad_stats``: the statistics of the first gradient that adafactor's
      state holds, the RMS of the difference as a share of the RMS of the
      reference's (the worst of a leaf's);
    * ``update``: |1 - <got, d> / <want, d>|, how far the program's change
      ``got`` goes ALONG the reference's float32 update, against how far the
      reference's own rounded change goes. A state left unchanged reads 1, a
      step twice as long 1, a step on unrelated rows ~1; an element that
      rounding moves on one side and not on the other (|d| near half a unit in
      the last place) counts by its d, once, and as often up as down;
    * ``update_rounded``: the RMS of ``got - want`` as a share of the RMS of
      ``want``, for the record: it counts each such element by a whole unit;
    * ``moved``: how far the program moved the leaf, as a share of it;
      ``ref_moved_share``: the share of its elements that the reference's
      step moves at all once rounded (the others' d is under half a unit in
      the last place).

    ``update`` and ``update_rounded`` have their worst and median leaf among
    those of at least UPDATE_MIN_LEAF elements of which the reference's step
    moves UPDATE_MIN_MOVED or more, ``grad_stats`` among all."""
    import jax
    import optax

    had = opt_state[0]
    if not isinstance(had, optax.FactoredState):
        raise RunFailure("the step's comparison reads adafactor's state; the "
                         f"optimizer's first is {type(had).__name__}")
    errors = _leaf_errors(opt)
    flat = lambda tree: {jax.tree_util.keystr(path): leaf for path, leaf in  # noqa: E731
                         jax.tree_util.tree_flatten_with_path(tree)[0]}
    trees = [flat(t) for t in (start, after, had.v_row, had.v_col, had.v, ref_start)]
    fields = ("update", "update_rounded", "grad_stats", "moved", "ref_moved_share")
    by_leaf = {field: {} for field in fields}
    for name in trees[0]:
        readings = jax.device_get(errors(*(t[name] for t in trees), ref_grads[name]))
        for field, x in zip(fields, readings):
            by_leaf[field][name] = float(x)
    judged = [name for name, leaf in trees[0].items() if leaf.size >= UPDATE_MIN_LEAF
              and by_leaf["ref_moved_share"][name] >= UPDATE_MIN_MOVED]

    def worst(field, names):
        at = max(names, key=by_leaf[field].get)
        return {"worst": by_leaf[field][at], "leaf": at,
                "median": float(np.median([by_leaf[field][n] for n in names]))}

    return {"by_leaf": by_leaf, "leaves_judged": len(judged),
            "grad_stats": worst("grad_stats", list(trees[0])),
            "update": worst("update", judged), "update_rounded": worst("update_rounded", judged)}


def _loop(config: dict) -> None:
    """Runs in the train worker that leased the chips."""
    marks = [("loop_entered", time.time())]  # set-up's phases, by the wall clock

    def mark(name):
        marks.append((name, time.time()))

    import jax
    import jax.numpy as jnp
    import optax

    from ray_tpu import train
    from ray_tpu.models import forward, init_params, loss_fn, param_axes
    from ray_tpu.parallel import MeshConfig, create_mesh
    from ray_tpu.parallel.sharding import logical_sharding, sharding_tree
    from ray_tpu.tpu import device_report, leased_devices

    from ..reference import windowed_moe_decoder as ref

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
        # the program's counters, from the same pass as the loss
        counters = (loss, aux["load_balance"], aux["rows_per_expert"].sum(axis=-1),
                    aux["rows_dropped"], aux["rows_per_held_expert"], aux["held_share"],
                    aux["attn_window_share"])
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
    program_bytes = int(mem.argument_size_in_bytes + mem.output_size_in_bytes
                        + mem.temp_size_in_bytes - mem.alias_size_in_bytes)

    # correctness, before the window: program vs plain reference. One layer of
    # each kind alone: the period's full layer and its first window layer
    pick = lambda tree, slot: jax.tree.map(lambda a: a[0], tree["layers"][slot])  # noqa: E731
    slots = (f"slot{cfg.layer_pattern.index('gqa')}", f"slot{cfg.layer_pattern.index('gqa_win')}")
    h = jax.random.normal(jax.random.PRNGKey(config["seed"] + 1),
                          (min(config["check_tokens"], first.shape[1]), cfg.hidden), cfg.dtype)
    layers = layer_errors(cfg, arch, tuple(pick(params, s) for s in slots),
                          tuple(pick(ref_params, s) for s in slots), h, control)
    del h, ref_params
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
    # host and comes back after the comparison (the reference's float32
    # blocks and the 3.4 GB of its gradients beside two copies of the weights
    # leave no room for a block's backward pass at 16k positions)
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
    del start, prog_logits, seen, ref_grads, ref_params
    params = jax.device_put(after, shardings)
    opt_state = jax.device_put(opt_state)
    del after
    mark("step_compared")

    rows_per_step = sizes["batch"] * first.shape[1] * cfg.moe_top_k
    losses, rows_wrong = [], []
    counted = {"load_max_over_mean": [], "held_share": [], "rows_per_held_expert": [],
               "window_share": []}
    step_t_a, step_ms, wait_ms, report_ms = [], [], [], []

    def one_step(tokens):
        nonlocal params, opt_state
        params, opt_state, counters = compiled(
            params, opt_state, {"tokens": jax.device_put(tokens, rows_sharding)})
        loss, _, rows, dropped, rows_held, held_share, window_share = jax.device_get(
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
                "attn_window_share": float(window_share)}

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
        counted["window_share"].append(said["attn_window_share"])
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
        "compile_s": compile_s, "program_bytes": program_bytes, "marks": marks,
        "memory": {"arguments": int(mem.argument_size_in_bytes),
                   "temporaries": int(mem.temp_size_in_bytes)},
        "tpu_custom_calls": compiled.as_text().count("tpu_custom_call"),
        "prog_loss": float(step0[0]), "prog_balance": float(step0[1]),
        "check_rows_per_layer": step0[2].tolist(), "check_rows_dropped": int(step0[3]),
        "check_held_share": step0[5].tolist(), "check_window_share": float(step0[6]),
        "check_tokens": int(first.shape[0] * (first.shape[1] - 1)),
        "check_positions": int(first.size), "rows_per_token": cfg.moe_top_k,
        "whole": whole, "layers": layers, "step": step, "rows_wrong": rows_wrong[:5],
        "counted": counted, "traced_steps": traced, "device": device, "trace": summary}})


def run(ctx: Context) -> dict:
    cfg = ctx.cell.config
    if ctx.rehearse:
        with open(os.path.join(HERE, "rehearse-swa.json")) as f:
            cfg = {**ctx.rehearse, **json.load(f)}
    mix = ctx.cell.traffic
    seq = int(mix["seq"]) if not ctx.rehearse else int(cfg["train_seq"])
    sizes, model = dict(cfg["train"]), cfg["model"]
    control = os.environ.get("BENCH_SWA_CONTROL") or None
    if control not in (None, *CONTROLS):
        raise RunFailure(f"BENCH_SWA_CONTROL is {control!r}: one of {tuple(CONTROLS)}")
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
                "platform": ctx.platform, "check_tokens": CHECK_TOKENS, "control": control,
                "unions": layer_metrics.union_specs(ctx.cell.readers)},
            scaling_config=ScalingConfig(num_workers=1,
                                         resources_per_worker=resources,
                                         worker_runtime_env=runtime_env),
            run_config=RunConfig(name="bench-train-swa",
                                 storage_path=tempfile.mkdtemp(prefix="bench-train-swa-")),
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
    experts = layers["experts"]
    n_expert_layers = model["num_hidden_layers"] - flops_swa.lead_layers(model)
    even_share = model["num_experts"] / model["router_width"]
    want_window = flops_swa.window_share(seq, model["sliding_window"])
    want_window_check = flops_swa.window_share(min(CHECK_TOKENS, seq), model["sliding_window"])
    checks = {
        "losses_finite": all(math.isfinite(x) for x in m["losses"]),
        "logits_match_reference": whole["median"] <= LOGIT_MEDIAN_RTOL
        and whole["max"] <= LOGIT_RTOL,
        "loss_matches_reference": abs(m["prog_loss"] - whole["ref_loss"]) <= LOSS_ATOL,
        "balance_matches_reference":
        abs(m["prog_balance"] - whole["ref_balance"]) <= BALANCE_ATOL,
        "gradient_statistics_match_reference": step["grad_stats"]["worst"] <= GRAD_STATS_RTOL,
        "update_matches_reference": step["update"]["worst"] <= UPDATE_ALONG_ATOL,
        "full_layer_matches_reference": layers["full"]["max"] <= MIXER_RTOL,
        "window_layer_matches_reference": layers["window"]["max"] <= MIXER_RTOL,
        "expert_layer_matches_reference": experts["max"] <= LAYER_RTOL
        and experts["ties"] <= max(2, LAYER_TIES_MAX * experts["tokens"]),
        "no_row_dropped": not m["rows_wrong"] and m["check_rows_dropped"] == 0
        and experts["dropped"] == 0
        and experts["rows"] == experts["tokens"] * m["rows_per_token"]
        and m["check_rows_per_layer"]
        == [m["check_positions"] * m["rows_per_token"]] * n_expert_layers,
        "held_share_is_the_chips_share": all(
            abs(x - even_share) <= HELD_SHARE_RTOL * even_share + 4 * math.sqrt(
                even_share * (1 - even_share) / (tokens * m["rows_per_token"]))
            for x, tokens in [(experts["held_share"], experts["tokens"])] + [
                (x, tokens_per_step) for x in [*m["check_held_share"], *counted["held_share"]]]),
        "window_share_is_the_closed_form":
        abs(layers["window"]["window_share"] - want_window_check) <= WINDOW_SHARE_ATOL
        and all(abs(x - want_window) <= WINDOW_SHARE_ATOL
                for x in [m["check_window_share"], *counted["window_share"]]),
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
        "LOGIT_MEDIAN_RTOL": LOGIT_MEDIAN_RTOL, "LOGIT_RTOL": LOGIT_RTOL,
        "GRAD_STATS_RTOL": GRAD_STATS_RTOL, "UPDATE_ALONG_ATOL": UPDATE_ALONG_ATOL,
        "MIXER_RTOL": MIXER_RTOL, "LAYER_RTOL": LAYER_RTOL, "BALANCE_ATOL": BALANCE_ATOL,
        "loss_atol": LOSS_ATOL,
        "HELD_SHARE_RTOL": HELD_SHARE_RTOL, "WINDOW_SHARE_ATOL": WINDOW_SHARE_ATOL,
        "MODEL_TIE_GAP": MODEL_TIE_GAP, "LAYER_TIE_GAP": LAYER_TIE_GAP},
        "whole_model": whole, "layers": layers, "step": step,
        "prog_loss": m["prog_loss"], "prog_balance": m["prog_balance"],
        "check_window_share": m["check_window_share"], "expected_window_share": want_window,
        "check_tokens": m["check_tokens"],
        "check_rows_per_layer": m["check_rows_per_layer"],
        "check_held_share": m["check_held_share"],
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
                      # compiler's count for the step program
                      "memory_peak_bytes": max(max(device["peak_bytes_in_use"]),
                                               m["program_bytes"])}}
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
        families = {"flash": flops_swa.attention_kernel_costs(model, "gqa", sizes["batch"], seq),
                    "win": flops_swa.attention_kernel_costs(model, "gqa_win", sizes["batch"], seq)}
        obs_families, kernel_calls = {}, {}
        for family, costs in families.items():
            took, least = 0.0, 0.0
            for kernel, (kernel_flops, kernel_bytes) in costs.items():
                pattern = rf'^%{kernel}(\.[\w.\-]+)? = .*custom_call_target="tpu_custom_call"'
                seconds, calls = trace_reduce.matching(summary["ops"], pattern)
                took += seconds
                least += calls * flops_swa.roofline_seconds(kernel_flops, kernel_bytes, peaks)
                # with one event's name as the trace printed it, for the readers' tests
                kernel_calls[kernel] = [calls, seconds, next(
                    (name[:600] for name in summary["ops"] if re.search(pattern, name)), None)]
            obs_families[family] = {"least_seconds": least,
                                    "seconds": took if took else summary["window_s"]}
        first, last = m["traced_steps"]
        # the grouped-matmul calls the trace holds and THEIR seconds, as
        # train_hybrid.py: a call's FLOPs are those of the rows the held
        # experts computed, from the traced steps' own count of them
        share = ctx.cell.readers.get("kernel.moe_gmm_share.train")
        gmm_s, gmm_calls = (trace_reduce.matching(summary["ops"], share["params"]["pattern"])
                            if share else (0.0, 0))
        rows_held = (stats.mean(counted["held_share"][first:last]) * tokens_per_step
                     * model["num_experts_per_tok"])
        ctx.say({"moe_gmm_calls": gmm_calls, "moe_gmm_seconds": gmm_s,
                 "rows_held_a_layer": rows_held, "kernel_calls": kernel_calls,
                 "kernel_families": obs_families,
                 "forward_flops_by_part": flops_swa.forward_flops_by_part(model, seq)})
        obs = {"timers": {"data_wait_ms": window["data_wait_ms"],
                          "report_ms": window["report_ms"],
                          "step_ms_median": stats.percentile(m["step_ms"], 50)},
               # from the median step, not the window: the capture's own
               # start, stop and reduction sit inside a traced window
               "train": {"tok_s_chip": tokens_per_step / chips * 1e3
                         / stats.percentile(m["step_ms"], 50),
                         "peak_flops_per_s": peaks["bf16_flops_per_s"],
                         "flops_per_token": flops_swa.train_flops_per_token(model, seq)},
               "moe": {"load_max_over_mean": stats.mean(
                           counted["load_max_over_mean"][first:last]),
                       "gmm_flops_per_call": 2.0 * rows_held * model["hidden_size"]
                       * model["moe_intermediate_size"],
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

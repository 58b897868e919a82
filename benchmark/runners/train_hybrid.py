"""Runner of the training cells of a hybrid decoder (Gated DeltaNet layers
beside gated attention, routed experts with a shared one, a chip's share of
the experts and of the vocabulary): the train runner's contract
(``runners/train.py``: the same phases, the same fenced steps, the same
window rule through ``pauses.window_report``, the same result line through
``result.emit``) with the configuration builder and the plain reference
swapped, as ``train_moe.py``. Which model it builds is the configuration
file's ``model_type``.

What decides ``correct``, all before the window, against
``reference/hybrid_decoder.py`` on the program's own bf16-rounded weights
(every norm weight first moved by a seeded +-0.5, so that a norm left out
or a weight misread shows):

* THE TIMED STEP ITSELF, run once on the first batch (the cell's own rows,
  whole): the loss it returns with its load-balancing term
  (``LOSS_ATOL_SQRT_TOKENS``, ``LOAD_BALANCE_ATOL``); the statistics of its
  first gradient that the optimizer's new state holds (``GRAD_STATS_RTOL``)
  and the change of every parameter leaf (``UPDATE_RTOL``), each by the
  worst leaf, against ``jax.grad`` of the reference's loss on the same rows
  put through the same optimizer in float32;
* logits at every position of the batch's first row, whole
  (``LOGIT_RTOL``), and their median (``LOGIT_MEDIAN_RTOL``);
* ONE layer of each kind alone, at the configuration's widths, on a seeded
  bf16 input: the DeltaNet mixer (``MIXER_RTOL``) and, on the mixer's own
  q, k, v, g and beta, its scan alone against the token-by-token rule,
  the mean over heads (``SCAN_RTOL``: the one a bfloat16 state fails); the gated attention mixer
  (``MIXER_RTOL``); the expert layer's share (``LAYER_RTOL``);
* rows routed = tokens x experts per token in every step (nothing dropped);
* the ``gdn_``, flash and grouped-matmul kernels ran native on the chip.

``BENCH_HYBRID_CONTROL`` in the environment puts a fault in the program's
place, for showing that the comparison refuses it (``CONTROLS``); such a
run says so in its output and must end ``correct`` false.
``BENCH_HYBRID_ROUTING=skewed`` is no fault: program and reference alike get
a router that favours the held experts (``ROUTER_SKEW``), so that the held
range outgrows the dispatch's compact path and every step takes the path
that gathers all rows; such a run measures that path and must end
``correct`` true with no row dropped.
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

from .. import flops, flops_hybrid, layer_metrics, pauses, stats, trace_reduce, traffic
from ..manifest import HERE
from . import (Context, RunFailure, capture_trace, check_device, kernel_native,
               lease, reduce_trace, start_cluster, stop_cluster)
from .train import TRACE_STEPS, WARM_STEPS
from .train_moe import LAYER_TIE_GAP, LAYER_TIES_MAX, MODEL_TIE_GAP, near_ties

# Tokens of a layer's check input: 32 chunks of the delta rule, so state
# crosses 31 chunk boundaries, and two 1024-blocks of the flash kernels each
# way. (The whole model is compared on the cell's own rows: 128 chunks, eight
# blocks.)
CHECK_TOKENS = 2048
# What can stand in the program's place (``BENCH_HYBRID_CONTROL``):
# * ``fp8_weights``: the program computes with its bf16 weights rounded to
#   float8_e4m3fn, the nearest precision below the configuration's;
# * ``bf16_state``: the delta rule alone keeps its state in bfloat16;
# * ``half_batch``: the timed step is given the batch's first row twice, so
#   it trains on half the batch's tokens (a loss or a gradient that skips
#   tokens).
CONTROLS = ("fp8_weights", "bf16_state", "half_batch")
# The limits. Errors are the RMS of the difference over the features of a
# position (or over a head, or a leaf) as a share of the RMS of the
# reference's there. Each limit lies between two readings on the chip at the
# published widths (builder's runs, PR 32; PERF.md Findings): the largest the
# program gave over its seeds, and what a control above gives THROUGH THIS
# RUNNER, which ends ``correct`` false by it.
# * Logits of the first row's 8,192 positions, the MEDIAN: 0.0182-0.0194
#   (0.0266 under skewed routing; four layers of two bf16 sub-blocks at
#   ~0.005 each, and most positions within 2% of a routing tie in some
#   layer: 512 experts lie close); fp8 weights 0.281.
LOGIT_MEDIAN_RTOL = 0.06
# * Logits, EVERY position: the worst reads 0.131-0.151 (0.170 skewed), a
#   position where bf16 swapped an expert (the worst not near a tie 0.085-
#   0.094); fp8 0.478. The limit is the geometric mean of 0.170 and 0.478.
LOGIT_RTOL = 0.28
# * One mixer alone on a seeded bf16 input, worst token: DeltaNet 0.0064-
#   0.0091 (fp8 0.129-0.146), gated attention 0.0060-0.0068 (fp8 0.085-
#   0.091). Every fault planted in the tests (decay or beta left out, no L2
#   norm, no conv, either gate left out, rope over the whole head, w for
#   1 + w) reads above 0.1.
MIXER_RTOL = 0.025
# * The delta rule alone, on the mixer's own bf16-valued q, k, v, g and beta,
#   output in float32, the MEAN over the 32 heads: the kernels (float32 state
#   and operands, products at `highest`, the inverse at three bf16 passes)
#   read 0.000009-0.000041 against the token-by-token rule (25 readings);
#   the same chunks with the state rounded to bfloat16 after every chunk
#   read 0.00035-0.00063 (10 readings). The limit is the geometric mean of
#   0.000041 and 0.00035. This is the limit a bfloat16 state fails; nothing
#   else here can tell it from bf16 rounding of the mixer's other activations
#   (the mixer reads 0.0078 with it, 0.0077 without). The WORST head is
#   reported and not judged: it reads 0.00003-0.00033 against 0.00074-0.0023,
#   both following how slowly the seed's slowest head forgets.
SCAN_RTOL = 0.00012
# * The expert layer's share, worst token not within 1e-4 of a routing tie
#   (2-12 of 2,048 are): 0.0049-0.0055; fp8 0.081-0.098; top-9 for top-10 or
#   gates not renormalised read above 0.1 in the tests.
LAYER_RTOL = 0.02
# * The timed step's loss on the first batch (chunked head, cross entropy,
#   + 0.001 x the load-balancing term) against the reference's over the same
#   n = 2 x 8,191 target tokens: at most LOSS_ATOL_SQRT_TOKENS / sqrt(n) =
#   0.00086 apart. It reads within 0.000335 (RMS 0.00017 over 11 readings: a
#   per-token error in log-probability of ~0.02-0.03 with either sign, twice
#   a dense decoder's, whose runner's 0.07 would stand 3 RMS off and refuse a
#   run in a few hundred); the step given half the batch (``half_batch``: it
#   skips 8,191 tokens) reads 0.0022, fp8 weights 0.0026. The limit is the
#   geometric mean of 0.000335 and 0.0022.
LOSS_ATOL_SQRT_TOKENS = 0.11
# * The load-balancing term itself, over all 512 experts and the batch's
#   tokens, the step's against the reference's: within 0.00009 (bf16 swaps a
#   few of 163,840 choices); half the batch reads 0.00079; the limit is
#   their geometric mean. 0.001 x it is under the loss's own limit, so the
#   loss cannot show it missing; taken over the 64 held experts alone it
#   would read an eighth of that.
LOAD_BALANCE_ATOL = 0.0003
# * The timed step's first gradient, by what the optimizer's new state holds
#   of it (adafactor: the mean of its squares along the rows and along the
#   columns of a matrix, element by element of a small leaf), against the
#   same statistics of the reference's gradient: the worst leaf. Squares: a
#   gradient off by a factor 1 + e reads 2 e. Worst leaf 0.081-0.086 (0.111
#   skewed; a norm's weight or a head's decay; the median leaf 0.03, the
#   big matrices 0.002-0.03); fp8 0.684 (median leaf 0.18); half the batch
#   1.82 (its best leaf 0.57). The limit is near the geometric mean of 0.111
#   and 0.684.
GRAD_STATS_RTOL = 0.25
# * The change of every parameter leaf in that step against the reference's:
#   the reference's gradient through the same optimizer in float32, the sum
#   rounded to the leaf's type as the program's is. In bf16 an update of a
#   thousandth of a weight is under half a unit in the last place for four
#   weights in five, so both changes are one-unit flips of the fifth, and a
#   gradient a few percent off flips a few others: the leaves read 0.15-0.27
#   so, a norm's weight up to 0.36, and the held experts' three matrices
#   0.443-0.453 (the worst leaf in every run: a token bf16 routed to another
#   expert moves a whole row of two experts' gradients). fp8 reads 0.969
#   there and 0.80 in the median leaf, half the batch 1.087 and 0.99; a state
#   left unchanged reads 1, an unrelated gradient 1.41. The limit lies
#   between 0.453 and 0.969 with the more room above the reading.
UPDATE_RTOL = 0.7
# ... of a leaf of at least this many elements. A smaller one's is reported:
# the first step of adafactor moves every element of a vector by the same
# amount, up or down by the gradient's sign, so each of the 32 elements of a
# head's decay whose gradient is too near zero for bf16 to get its sign
# right adds 0.35^2 to the square of that leaf's reading (0, 0.35 and 0.5
# were read); its gradient's size is held to GRAD_STATS_RTOL as any leaf's.
UPDATE_MIN_LEAF = 1024
NORM_SPREAD = 0.5  # seeded norm weights are moved by a uniform +- this
# ``BENCH_HYBRID_ROUTING=skewed``: the router's columns of the held experts
# times this. At 1.5 they take ~43% of the rows where an even share is 12.5%
# and the compact path is compiled for 25% (``models/moe.py``'s HELD_CAPACITY).
ROUTER_SKEW = 1.5


def model_config(model: dict, sizes: dict, **overrides):
    """The program's config object for a configuration file's ``model`` and
    ``train`` groups, by ``model_type``. A program from before this model
    was supported fails here, before a cluster or a chip is touched."""
    from ray_tpu.models.llama import LlamaConfig

    if model.get("model_type") != "qwen3_next":
        raise RunFailure(f"runner train_hybrid builds no model of type "
                         f"{model.get('model_type')!r}")
    first, last = model["experts_held"]
    period = model["full_attention_interval"]
    assert model["linear_key_head_dim"] == model["linear_value_head_dim"]
    assert model["num_experts"] == last - first + 1 and model["decoder_sparse_step"] == 1
    return LlamaConfig(
        vocab_size=model["vocab_size"], hidden=model["hidden_size"],
        n_layers=model["num_hidden_layers"], n_heads=model["num_attention_heads"],
        n_kv_heads=model["num_key_value_heads"], head_dim=model["head_dim"],
        intermediate=model["moe_intermediate_size"],
        rope_theta=float(model["rope_theta"]), norm_eps=float(model["rms_norm_eps"]),
        layer_pattern=("gdn",) * (period - 1) + ("attn",),
        norm_plus_one=True, head_qk_norm=True, attn_out_gate=True,
        rotary_dim=int(model["head_dim"] * model["partial_rotary_factor"]),
        gdn_key_heads=model["linear_num_key_heads"],
        gdn_value_heads=model["linear_num_value_heads"],
        gdn_head_dim=model["linear_value_head_dim"],
        gdn_conv=model["linear_conv_kernel_dim"],
        moe_experts=model["router_width"], moe_top_k=model["num_experts_per_tok"],
        moe_norm_topk=bool(model["norm_topk_prob"]),
        moe_shared=model["shared_expert_intermediate_size"],
        moe_held=(first, model["num_experts"]),
        moe_aux_weight=sizes["aux_loss_weight"], moe_z_weight=0.0,
        **overrides)


def reference_arch(model: dict) -> dict:
    """What ``reference/hybrid_decoder.py`` needs to know of the same file."""
    period = model["full_attention_interval"]
    return dict(pattern=("gdn",) * (period - 1) + ("attn",),
                rope_theta=float(model["rope_theta"]),
                rotary_dim=int(model["head_dim"] * model["partial_rotary_factor"]),
                norm_eps=float(model["rms_norm_eps"]),
                key_heads=model["linear_num_key_heads"],
                value_heads=model["linear_num_value_heads"],
                top_k=model["num_experts_per_tok"],
                norm_topk=bool(model["norm_topk_prob"]),
                held_first=model["experts_held"][0])


def seed_norms(params, key):
    """Move every norm weight by a seeded uniform +- NORM_SPREAD.
    ``init_params`` starts them at 0 (``1 + w``) or 1 (the gated norm), as
    the model's own init does; against those a program that read ``w`` for
    ``1 + w`` somewhere the output is rescaled anyway, or left a norm's
    weight out, would read the same as one that did not."""
    import jax

    def move(path, leaf):
        name = str(getattr(path[-1], "key", ""))
        if not name.endswith("norm"):
            return leaf
        # a key of the leaf's own, the same in every process (no hash())
        leaf_key = jax.random.fold_in(
            key, zlib.crc32(jax.tree_util.keystr(path).encode()) % 2**31)
        u = jax.random.uniform(leaf_key, leaf.shape, minval=-NORM_SPREAD, maxval=NORM_SPREAD)
        return (leaf.astype(u.dtype) + u).astype(leaf.dtype)

    return jax.tree_util.tree_map_with_path(move, params)


def skew_router(params, held, factor: float = ROUTER_SKEW):
    """Every layer's router with the columns of the experts ``held`` =
    (first, count) scaled by ``factor``."""
    import jax

    first, count = held

    def scale(path, leaf):
        if str(getattr(path[-1], "key", "")) != "router":
            return leaf
        return leaf.at[..., first:first + count].multiply(factor)

    return jax.tree_util.tree_map_with_path(scale, params)


def _rel(got, want, axes):
    """RMS of got - want over ``axes`` as a share of the RMS of want there."""
    import jax.numpy as jnp

    got, want = got.astype(jnp.float32), want.astype(jnp.float32)
    return (jnp.sqrt(jnp.mean(jnp.square(got - want), axis=axes))
            / jnp.sqrt(jnp.mean(jnp.square(want), axis=axes)))


def layer_errors(cfg, arch, layers, h, *, scan=None, ref_layers=None) -> dict:
    """One layer of each kind alone on the same input h [S, E] (bf16,
    already normed): the program's mixers and ``moe_block`` against the
    reference's. ``layers`` = (a DeltaNet layer's leaves, an attention
    layer's); ``ref_layers`` the reference's, where a control gave the
    program others. ``scan`` swaps the program's rule (a bf16 state)."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models.gdn import gdn_mixer
    from ray_tpu.models.llama import MIXERS
    from ray_tpu.ops.gated_delta import gated_delta_rule
    from ray_tpu.models.moe import moe_block

    from ..reference import hybrid_decoder as ref

    gdn_layer, attn_layer = layers
    ref_gdn_layer, ref_attn_layer = ref_layers or layers
    get = lambda x: np.asarray(jax.device_get(x))  # noqa: E731
    out = {}
    # the DeltaNet mixer, and its scan alone on the mixer's own operands
    got, seen = jax.jit(lambda h, w: gdn_mixer(
        h[None], w, config=cfg, return_scan=True, scan=scan))(h, gdn_layer)
    want, _ = ref.gdn_mixer(h, ref_gdn_layer, key_heads=arch["key_heads"],
                            value_heads=arch["value_heads"], eps=arch["norm_eps"])
    err = get(_rel(got[0], want, -1))
    # the rule alone, on the mixer's own (bf16-valued) operands, its output
    # left in float32: a bf16 output's own rounding (0.16%) is what a bf16
    # state costs too, and would hide it
    operands = tuple(seen[k].astype(jnp.float32) for k in ("q", "k", "v", "g", "beta"))
    rule = jax.jit(scan or gated_delta_rule)(*operands)[0]
    by_head = get(_rel(rule, ref.delta_rule(*(x[0] for x in operands)), (1, 2)))
    out["gdn"] = {"max": float(err.max()), "mean": float(err.mean()),
                  "scan_worst_head": float(by_head.max()),
                  "scan_mean": float(by_head.mean())}
    # the gated attention mixer
    positions = jnp.arange(h.shape[0], dtype=jnp.int32)
    got = jax.jit(lambda h, w: MIXERS["attn"].apply(
        h[None], w, config=cfg, positions=positions, mesh=None))(h, attn_layer)
    want = ref.attn_mixer(h, ref_attn_layer, theta=arch["rope_theta"], eps=arch["norm_eps"],
                          rotary_dim=arch["rotary_dim"])
    err = get(_rel(got[0], want, -1))
    out["attn"] = {"max": float(err.max()), "mean": float(err.mean())}
    # the expert layer's share: held experts and the shared one
    got, aux = jax.jit(lambda h, w: moe_block(
        h[None], w, top_k=cfg.moe_top_k, norm_topk=cfg.moe_norm_topk,
        held=cfg.moe_held))(h, gdn_layer)
    want, routing = ref.expert_layer(h, ref_gdn_layer, top_k=arch["top_k"],
                                     norm_topk=arch["norm_topk"], first=arch["held_first"])
    err = get(_rel(got[0], want, -1))
    tie = get(near_ties(routing["probs"], arch["top_k"], LAYER_TIE_GAP))
    out["experts"] = {"max": float(err[~tie].max()), "mean": float(err[~tie].mean()),
                      "ties": int(tie.sum()), "tokens": int(err.size),
                      "rows": int(get(aux["rows"]).sum()), "dropped": int(aux["dropped"]),
                      "held_share": float(aux["held_share"])}
    return out


def reference_step(params, rows, arch, aux_weight: float):
    """The reference's side of a training step on token rows [B, S]: its
    loss, what it saw on the way (``hybrid_decoder.loss``'s ``seen``: the
    first row's logits and router probabilities, the two terms) and the
    loss's gradient by every leaf, in the leaf's own type."""
    import jax

    from ..reference import hybrid_decoder as ref

    (loss, seen), grads = jax.jit(jax.value_and_grad(
        lambda p, t: ref.loss(p, t, aux_weight=aux_weight, return_seen=True, **arch),
        has_aux=True))(params, rows)
    return loss, seen, grads


def logit_errors(got, seen, top_k: int) -> dict:
    """The program's logits ``got`` [S, vocab] of a row against the
    reference's for it (``seen``), position by position; positions where any
    layer's routing is near a tie are counted, and the worst of the others
    reported."""
    import jax

    from ..reference import hybrid_decoder as ref

    err = np.asarray(jax.device_get(ref.position_errors(got, seen["logits"])))
    tie = np.asarray(jax.device_get(
        near_ties(seen["probs"], top_k, MODEL_TIE_GAP).any(axis=0)))
    return {"max": float(err.max()), "median": float(np.median(err)),
            "max_not_near_a_tie": float(err[~tie].max()) if not tie.all() else 0.0,
            "near_a_tie_share": float(tie.mean())}


@functools.lru_cache(maxsize=None)
def _leaf_errors(opt):
    import jax
    import jax.numpy as jnp

    def rel(got, want):
        size = jnp.linalg.norm(want.ravel())
        off = jnp.linalg.norm((got.astype(jnp.float32) - want).ravel())
        return jnp.where(size > 0, off / size, off)

    @jax.jit
    def errors(start, after, v_row, v_col, v, ref_start, ref_grad):
        p32, g32 = ref_start.astype(jnp.float32), ref_grad.astype(jnp.float32)
        update, state = opt.update(g32, opt.init(p32), p32)
        # rounded as the leaf's type rounds. ``reduce_precision`` and not a
        # cast there and back, which a compiler that may keep excess
        # precision drops (the chip's does: the change then read 0.81 on
        # every bf16 leaf, the distance of an update from its own rounding)
        kept = jnp.finfo(ref_start.dtype)
        want = jax.lax.reduce_precision(p32 + update, kept.nexp, kept.nmant) - p32
        got = after.astype(jnp.float32) - start.astype(jnp.float32)
        had = state[0]  # FactoredState; the fields a leaf does not use are one zero
        return (rel(got, want),
                jnp.maximum(jnp.maximum(rel(v_row, had.v_row), rel(v_col, had.v_col)),
                            rel(v, had.v)),
                jnp.linalg.norm(got.ravel()) / jnp.linalg.norm(p32.ravel()))

    return errors


def step_errors(opt, start, after, opt_state, ref_start, ref_grads) -> dict:
    """What one step of the program did (parameters ``start`` -> ``after``,
    the optimizer's state after it) against the reference's step: the
    reference's gradient put through the same optimizer in float32 from
    ``ref_start``, the sum rounded to the leaf's type. A leaf at a time:
    ``update`` is the RMS of the difference of the two changes of a leaf as
    a share of the RMS of the reference's, ``grad_stats`` the same of the
    statistics of the first gradient that adafactor's state holds (the
    worst of a leaf's). Each with its worst leaf (``update``: of the leaves
    of at least UPDATE_MIN_LEAF elements) and every leaf's reading; ``moved``
    is how far the program moved the leaf, as a share of it."""
    import jax
    import optax

    had = opt_state[0]
    if not isinstance(had, optax.FactoredState):
        raise RunFailure("the step's comparison reads adafactor's state; the "
                         f"optimizer's first is {type(had).__name__}")
    errors = _leaf_errors(opt)
    names = [jax.tree_util.keystr(path)
             for path, _ in jax.tree_util.tree_flatten_with_path(ref_start)[0]]
    trees = (start, after, had.v_row, had.v_col, had.v, ref_start, ref_grads)
    by_leaf = {"update": {}, "grad_stats": {}, "moved": {}}
    for name, *leaves in zip(names, *(jax.tree.leaves(t) for t in trees)):
        update, grad_stats, moved = (float(x) for x in jax.device_get(errors(*leaves)))
        by_leaf["update"][name] = update
        by_leaf["grad_stats"][name] = grad_stats
        by_leaf["moved"][name] = moved
    out = {"by_leaf": by_leaf}
    sizes = dict(zip(names, (leaf.size for leaf in jax.tree.leaves(ref_start))))
    for what, least in (("update", UPDATE_MIN_LEAF), ("grad_stats", 0)):
        judged = {name: x for name, x in by_leaf[what].items() if sizes[name] >= least}
        worst = max(judged, key=judged.get)
        out[what] = {"worst": judged[worst], "leaf": worst}
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
    from ray_tpu.models import forward, init_params, loss_fn, param_axes
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

    # weights on the device(s) in one jitted call, in the type they train in;
    # the seed goes in as the key's value (a constant would compile anew a seed)
    def seeded(key):
        params = seed_norms(init_params(cfg, key), key)
        return skew_router(params, cfg.moe_held) if config["routing"] == "skewed" else params

    params = jax.jit(seeded, out_shardings=sharding_tree(param_axes(cfg), mesh))(
        jax.random.PRNGKey(config["seed"]))
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
                    aux["rows_dropped"], aux["rows_per_held_expert"], aux["held_share"])
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

    # correctness, before the window: program vs plain reference. The
    # reference keeps the seeded weights; a control gives the program others
    control = config["control"]
    copy = jax.jit(lambda tree: jax.tree.map(jnp.copy, tree))
    # the leaves in the model's own type, a leaf and a cast at a time: under
    # one ``jit`` the chip's compiler drops a cast there and back
    fp8 = lambda tree: jax.tree.map(  # noqa: E731
        lambda a: a.astype(jnp.float8_e4m3fn).astype(a.dtype)
        if a.dtype == cfg.dtype else jnp.copy(a), tree)
    as_program = fp8 if control == "fp8_weights" else copy
    ref_params = params
    params = as_program(ref_params)
    # one layer of each kind alone, and the first row's logits
    pick = lambda tree, slot: jax.tree.map(lambda a: a[0], tree["layers"][slot])  # noqa: E731
    slots = ("slot0", f"slot{len(cfg.layer_pattern) - 1}")
    h = jax.random.normal(jax.random.PRNGKey(config["seed"] + 1),
                          (min(config["check_tokens"], first.shape[1]), cfg.hidden), cfg.dtype)
    scan = None
    if control == "bf16_state":
        from ray_tpu.ops.gated_delta import chunked_jnp

        scan = functools.partial(chunked_jnp, state_dtype=jnp.bfloat16)
    layers = layer_errors(cfg, arch, tuple(pick(params, s) for s in slots), h, scan=scan,
                          ref_layers=tuple(pick(ref_params, s) for s in slots))
    del h
    prog_logits = jax.jit(lambda p, t: forward(p, t, cfg, mesh=mesh)[0])(
        params, jax.device_put(first[:n_batch], rows_sharding))
    jax.block_until_ready(prog_logits)
    mark("layers_and_logits")
    # the timed step itself, once, on the first batch
    given = np.stack([first[0]] * len(first)) if control == "half_batch" else first
    params, opt_state, counters = compiled(
        params, opt_state, {"tokens": jax.device_put(given, rows_sharding)})
    step0 = jax.device_get(counters)
    mark("first_step")
    ref_loss, seen, ref_grads = reference_step(ref_params, jnp.asarray(first), arch,
                                               cfg.moe_aux_weight)
    jax.block_until_ready(ref_grads)
    mark("reference_step")
    whole = logit_errors(prog_logits, seen, arch["top_k"])
    whole.update(ref_loss=float(ref_loss), ref_ce=float(seen["ce"]),
                 ref_load_balance=float(seen["load_balance"]))
    start = fp8(ref_params) if control == "fp8_weights" else ref_params
    step = step_errors(opt, start, params, opt_state, ref_params, ref_grads)
    del start
    del prog_logits, seen, ref_grads, ref_params
    mark("step_compared")

    rows_per_step = sizes["batch"] * first.shape[1] * cfg.moe_top_k
    losses, load, held, rows_wrong = [], [], [], []
    step_t_a, step_ms, wait_ms, report_ms = [], [], [], []

    def one_step(tokens):
        nonlocal params, opt_state
        params, opt_state, counters = compiled(
            params, opt_state, {"tokens": jax.device_put(tokens, rows_sharding)})
        loss, _, rows, dropped, rows_held, held_share = jax.device_get(counters)  # the fence
        if int(dropped) or (rows != rows_per_step).any():
            rows_wrong.append([int(dropped), rows.tolist()])
        # over the experts this chip holds: their rows are what its grouped
        # matmuls compute
        return (float(loss), float((rows_held.max(axis=-1) / rows_held.mean(axis=-1)).mean()),
                float(held_share.mean()))

    for _ in range(WARM_STEPS):
        one_step(next_batch())
    mark("warm_steps")

    def timed_step():
        t_a = time.monotonic()
        tokens = next_batch()
        t_b = time.monotonic()
        loss, max_over_mean, held_share = one_step(tokens)
        t_c = time.monotonic()
        losses.append(loss)
        load.append(max_over_mean)
        held.append(held_share)
        train.report({"step": len(losses), "loss": loss,
                      "moe_load_max_over_mean": max_over_mean,
                      "moe_held_share": held_share})
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
        "prog_loss": float(step0[0]), "prog_load_balance": float(step0[1]),
        "check_rows_per_layer": step0[2].tolist(), "check_rows_dropped": int(step0[3]),
        "check_held_share": step0[5].tolist(),
        "check_tokens": int(first.shape[0] * (first.shape[1] - 1)),
        "check_positions": int(first.size), "rows_per_token": cfg.moe_top_k,
        "whole": whole, "layers": layers, "step": step, "rows_wrong": rows_wrong[:5],
        "load_max_over_mean": load, "held_share": held, "traced_steps": traced,
        "device": device, "trace": summary}})


def run(ctx: Context) -> dict:
    cfg = ctx.cell.config
    if ctx.rehearse:
        with open(os.path.join(HERE, "rehearse-hybrid.json")) as f:
            cfg = {**ctx.rehearse, **json.load(f)}
    mix = ctx.cell.traffic
    seq = int(mix["seq"]) if not ctx.rehearse else int(cfg["train_seq"])
    sizes = dict(cfg["train"])
    # before a cluster or a chip is touched: a program that cannot describe
    # this model (one from before it was supported) fails here, at once
    model_config(cfg["model"], sizes)
    control = os.environ.get("BENCH_HYBRID_CONTROL") or None
    if control not in (None, *CONTROLS):
        raise RunFailure(f"BENCH_HYBRID_CONTROL is {control!r}: one of {CONTROLS}")
    routing = os.environ.get("BENCH_HYBRID_ROUTING") or None
    if routing not in (None, "skewed"):
        raise RunFailure(f"BENCH_HYBRID_ROUTING is {routing!r}: 'skewed' or unset")
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
                "platform": ctx.platform, "check_tokens": CHECK_TOKENS, "control": control,
                "routing": routing,
                "unions": layer_metrics.union_specs(ctx.cell.readers)},
            scaling_config=ScalingConfig(num_workers=1,
                                         resources_per_worker=resources,
                                         worker_runtime_env=runtime_env),
            run_config=RunConfig(name="bench-train-hybrid",
                                 storage_path=tempfile.mkdtemp(prefix="bench-train-hybrid-")),
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
    experts = layers["experts"]
    n_layers = cfg["model"]["num_hidden_layers"]
    checks = {
        "losses_finite": all(math.isfinite(x) for x in m["losses"]),
        "logits_match_reference": whole["median"] <= LOGIT_MEDIAN_RTOL
        and whole["max"] <= LOGIT_RTOL,
        "loss_matches_reference": abs(m["prog_loss"] - whole["ref_loss"])
        <= LOSS_ATOL_SQRT_TOKENS / math.sqrt(m["check_tokens"]),
        "load_balance_matches_reference":
        abs(m["prog_load_balance"] - whole["ref_load_balance"]) <= LOAD_BALANCE_ATOL,
        "gradient_statistics_match_reference": step["grad_stats"]["worst"] <= GRAD_STATS_RTOL,
        "update_matches_reference": step["update"]["worst"] <= UPDATE_RTOL,
        "gdn_layer_matches_reference": layers["gdn"]["max"] <= MIXER_RTOL,
        "gdn_scan_matches_the_rule": layers["gdn"]["scan_mean"] <= SCAN_RTOL,
        "attn_layer_matches_reference": layers["attn"]["max"] <= MIXER_RTOL,
        "expert_layer_matches_reference": experts["max"] <= LAYER_RTOL
        and experts["ties"] <= LAYER_TIES_MAX * experts["tokens"],
        "no_row_dropped": not m["rows_wrong"] and m["check_rows_dropped"] == 0
        and experts["dropped"] == 0
        and experts["rows"] == experts["tokens"] * m["rows_per_token"]
        and m["check_rows_per_layer"]
        == [m["check_positions"] * m["rows_per_token"]] * n_layers,
        "flash_kernel_native": kernel_native(traces, "flash_attention", ctx.platform),
        "grouped_matmul_native": kernel_native(traces, "moe_gmm", ctx.platform)
        and kernel_native(traces, "moe_tgmm", ctx.platform),
        "gdn_kernels_native": kernel_native(traces, "gdn", ctx.platform),
        "custom_calls_compiled": (m["tpu_custom_calls"] > 0) == (ctx.platform == "tpu"),
    }
    marks += [tuple(x) for x in m["marks"]] + [("window_start", m["t_window_start_wall"])]
    ctx.say({"setup_phases_s": {b[0]: b[1] - a[1] for a, b in zip(marks, marks[1:])}})
    quart = lambda xs: [stats.percentile(xs, q) for q in (25, 50, 75)]  # noqa: E731
    ctx.say({"control": control, "routing": routing, "checks": checks, "limits": {
        "LOGIT_MEDIAN_RTOL": LOGIT_MEDIAN_RTOL, "LOGIT_RTOL": LOGIT_RTOL,
        "GRAD_STATS_RTOL": GRAD_STATS_RTOL, "UPDATE_RTOL": UPDATE_RTOL,
        "MIXER_RTOL": MIXER_RTOL, "SCAN_RTOL": SCAN_RTOL, "LAYER_RTOL": LAYER_RTOL,
        "LOAD_BALANCE_ATOL": LOAD_BALANCE_ATOL,
        "loss_atol": LOSS_ATOL_SQRT_TOKENS / math.sqrt(m["check_tokens"]),
        "MODEL_TIE_GAP": MODEL_TIE_GAP, "LAYER_TIE_GAP": LAYER_TIE_GAP},
        "whole_model": whole, "layers": layers, "step": step,
        "prog_loss": m["prog_loss"], "prog_load_balance": m["prog_load_balance"],
        "check_tokens": m["check_tokens"],
        "check_rows_per_layer": m["check_rows_per_layer"],
        "check_held_share": m["check_held_share"],
        "rows_wrong": m["rows_wrong"],
        "load_max_over_mean_quartiles": quart(m["load_max_over_mean"]),
        "held_share_quartiles": quart(m["held_share"]),
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
        peaks = ({"bf16_flops_per_s": ctx.rehearse["assumed_peak_flops_per_s"],
                  "hbm_bytes_per_s": ctx.rehearse["assumed_peak_flops_per_s"] / 240}
                 if ctx.rehearse else flops.peaks(device["kind"]))
        # the gdn_ calls the trace holds, recomputed ones included, THEIR
        # seconds and the least seconds those same calls could take. A CPU
        # rehearsal interprets the kernels into plain ops, so its trace holds
        # none: the share of the roofline then reads 0 over the window.
        costs = flops_hybrid.gdn_kernel_costs(cfg["model"], sizes["batch"], seq)
        gdn_s, gdn_least_s, gdn_calls = 0.0, 0.0, {}
        for kernel, (kernel_flops, kernel_bytes) in costs.items():
            pattern = rf'^%{kernel}(\.[\w.\-]+)? = .*custom_call_target="tpu_custom_call"'
            seconds, calls = trace_reduce.matching(summary["ops"], pattern)
            gdn_s += seconds
            gdn_least_s += calls * flops_hybrid.roofline_seconds(kernel_flops, kernel_bytes, peaks)
            # with one event's name as the trace printed it, for the readers' tests
            gdn_calls[kernel] = [calls, seconds, next(
                (name[:600] for name in summary["ops"] if re.search(pattern, name)), None)]
        first, last = m["traced_steps"]
        # the grouped-matmul calls the trace holds and THEIR seconds, as
        # train_moe.py; a call's FLOPs are those of the rows the held experts
        # computed, 2 x rows x hidden x expert width, from the traced steps'
        # own count of them (the mean over layers and steps: every layer
        # makes the same calls)
        share = ctx.cell.readers.get("kernel.moe_gmm_share.train")
        gmm_s, gmm_calls = (trace_reduce.matching(summary["ops"], share["params"]["pattern"])
                            if share else (0.0, 0))
        rows_held = (stats.mean(m["held_share"][first:last]) * tokens_per_step
                     * cfg["model"]["num_experts_per_tok"])
        ctx.say({"moe_gmm_calls": gmm_calls, "moe_gmm_seconds": gmm_s,
                 "rows_held_a_layer": rows_held})
        ctx.say({"gdn_calls": gdn_calls, "gdn_least_seconds": gdn_least_s,
                 "forward_flops_by_part": flops_hybrid.forward_flops_by_part(
                     cfg["model"], seq)})
        obs = {"timers": {"data_wait_ms": window["data_wait_ms"],
                          "report_ms": window["report_ms"],
                          "step_ms_median": stats.percentile(m["step_ms"], 50)},
               # from the median step, not the window: the capture's own
               # start, stop and reduction sit inside a traced window
               "train": {"tok_s_chip": tokens_per_step / chips * 1e3
                         / stats.percentile(m["step_ms"], 50),
                         "peak_flops_per_s": peaks["bf16_flops_per_s"],
                         "flops_per_token": flops_hybrid.train_flops_per_token(
                             cfg["model"], seq)},
               "moe": {"load_max_over_mean": stats.mean(m["load_max_over_mean"][first:last]),
                       "gmm_flops_per_call": 2.0 * rows_held * cfg["model"]["hidden_size"]
                       * cfg["model"]["moe_intermediate_size"],
                       "gmm_calls": gmm_calls,
                       "gmm_seconds": gmm_s if gmm_calls else summary["window_s"]},
               "gdn": {"least_seconds": gdn_least_s,
                       "seconds": gdn_s if gdn_s else summary["window_s"]},
               "trace": summary}
        values = layer_metrics.read_all(ctx.cell.readers, obs)
        out["device"].update(busy_s=summary["busy_s"], window_s=summary["window_s"])
        out["breakdown"] = {"device_ops": summary["device_ops"],
                            "idle_gaps": summary["idle_gaps"]}
    declared = ctx.cell.declared(ctx.trace)
    out["metrics"] = {k: {"value": v, "unit": declared[k]}
                      for k, v in values.items() if k in declared}
    return out

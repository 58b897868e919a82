"""Runner of the training cells of a latent-attention decoder with a learned
selection of keys (full layers whose keys an indexer chooses beside window
layers, a leading dense layer, sigmoid-routed experts with a selection bias
and a plain shared one, a chip's share of the experts and of the
vocabulary): the train runner's contract (``runners/train.py``: the same
phases, the same fenced steps, the same window rule through
``pauses.window_report``, the same result line through ``result.emit``) with
the configuration builder and the plain reference swapped, as
``train_hybrid.py``. Which model it builds is the configuration file's
``model_type``.

What decides ``correct``, all before the window, against
``reference/latent_sparse_decoder.py`` on the program's own bf16-rounded
weights (every norm weight first moved by a seeded +-0.5 and every selection
bias by a seeded +-BIAS_SPREAD, so that a norm or a bias left out shows):

* ONE layer of each kind alone, at the configuration's widths, on a seeded
  bf16 input of CHECK_TOKENS positions (the window and the selection both
  drop keys there): the indexed mixer GIVEN the program's own key sets
  (``MIXER_RTOL``), how far the reference's own key sets and the program's
  agree (``SELECTION_AGREEMENT``), the indexer's loss of that layer
  (``INDEX_LOSS_RTOL``); the window mixer (``MIXER_RTOL``); the expert layer's
  share (``LAYER_RTOL``);
* logits at every position of the batch's first row, the reference given the
  key sets the program chose on that batch (``LOGIT_RTOL``,
  ``LOGIT_MEDIAN_RTOL``), and again the agreement of the two selections;
* THE TIMED STEP ITSELF, run once on the first batch: its loss, its balance
  term and its indexer's loss (``LOSS_ATOL``, ``BALANCE_ATOL``,
  ``INDEX_LOSS_RTOL``); the statistics of its first gradient that the
  optimizer's new state holds and the change of every parameter leaf
  (``GRAD_STATS_RTOL``, ``UPDATE_RTOL``), against ``jax.grad`` of the
  reference's loss on the same rows under the same key sets, a block at a
  time (the float32 gradient of all 1.8 B leaves does not fit beside the
  weights), put through the same optimizer in float32; the step of
  every selection bias against the reference's rule on the reference's own
  counts (``BIAS_AGREEMENT``); ``attn_selected_share`` against the count
  (``SELECTED_SHARE_ATOL``);
* rows routed = tokens x experts per token in every step (nothing dropped);
* the attention, indexer and grouped-matmul kernels ran native on the chip.

``BENCH_SPARSE_CONTROL`` in the environment puts a fault in the program's
place, for showing that the comparison refuses it (``CONTROLS``); such a run
says so in its output and must end ``correct`` false. ``BENCH_SPARSE_WITNESS``
changes the model on BOTH sides (``WITNESSES``), for showing where the size of
the whole-model readings comes from; such a run says so and must end
``correct`` true. A driver's run sets neither.
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

from .. import flops, flops_sparse, layer_metrics, pauses, stats, trace_reduce, traffic
from ..manifest import HERE
from . import (Context, RunFailure, capture_trace, check_device, kernel_native,
               lease, reduce_trace, start_cluster, stop_cluster)
from .train import TRACE_STEPS, WARM_STEPS
from .train_hybrid import _leaf_errors, _rel, seed_norms
from .train_moe import LAYER_TIE_GAP, near_ties

# Positions of a layer's check input: twice ``index_topk``, so the selection
# drops half the keys of the later queries, and eight windows.
CHECK_TOKENS = 4096
# What can stand in the program's place (``BENCH_SPARSE_CONTROL``), each a
# change to the program's configuration or weights that the reference does
# not get:
CONTROLS = {
    "window_512": "the window layers see 512 keys, not 513",
    "no_selection": "the full layers attend the whole causal triangle",
    "top_2047": "the indexer keeps 2,047 keys, not 2,048",
    "no_rescale": "the latents are not rescaled by (hidden / rank)^1/2",
    "no_gate": "the head-wise output gate is left out",
    "softmax_router": "the router's scores are a softmax, not sigmoids",
    "bias_ignored": "the selection bias is left out of the choice and never stepped",
    "fp8_weights": "the bf16 weights rounded to float8_e4m3fn",
    "half_batch": "the timed step is given the first half of its batch's tokens twice",
    "unchanged_state": "the step's new parameters and optimizer state are thrown away",
}
# A change to the model that program and reference BOTH get:
WITNESSES = {
    "no_rescale": "neither side rescales the latents: attention's scores are then "
                  "~N(0, 1) and not ~N(0, 6^2), and bf16's rounding is not amplified",
}
# seeded selection biases are moved by a uniform +- this (scores spread ~0.2);
# norm weights by ``train_hybrid.seed_norms``'s +-0.5
BIAS_SPREAD = 0.05
# The limits. Errors are the RMS of the difference over the features of a
# position (or a leaf) as a share of the RMS of the reference's there. Each
# lies between the largest the program gave over its seeds on the chip at the
# published widths (builder's runs, PR 34: seven seeds at 1 x 8192 and the
# eight at 2 x 8192, which read alike) and what a control above gives THROUGH
# THIS RUNNER, which ends ``correct`` false by it (PERF.md Findings has every
# reading). The whole-model readings are far larger than a single layer's:
# with seeded weights the rescaled latents make attention's scores ~N(0, 6^2),
# a softmax so sharp that a layer turns a 1% difference of its input into ~10%
# of its output, and five layers compound it (``BENCH_SPARSE_WITNESS=
# no_rescale`` shows it: with the rescale off on both sides the logits' median
# reads 0.0165, a leaf's gradient statistics 0.015 in the median and 0.14 at
# worst, a single layer 0.0058). So ``correct`` LEANS ON THE SINGLE LAYERS AND
# THE COUNTS, which tell a fault from rounding by a factor of ten and more; the
# whole-model limits stand behind them and refuse what no layer alone can show
# (a step on other rows, a state not stepped, weights of another precision).
# * Logits of the first row's 8,192 positions against the reference GIVEN the
#   program's key sets, the MEDIAN position: 0.109-0.114; fp8 weights 0.977,
#   no gate 1.09, no rescale 1.40. The geometric mean of 0.114 and 0.977.
LOGIT_MEDIAN_RTOL = 0.33
# * ... EVERY position: the worst reads 0.160-0.189; fp8 1.10, no gate 1.19.
LOGIT_RTOL = 0.45
# * One mixer alone on a seeded bf16 input of 4,096 positions, worst token,
#   the reference given the program's key sets: full 0.0139-0.0153, window
#   0.0128-0.0149; fp8 0.284 / 0.279; no gate 1.30 / 1.42; no rescale 0.99 /
#   0.98; a window of 512 reads 0.357 in the window layer.
MIXER_RTOL = 0.06
# * The expert layer's share, worst token not within 1e-4 of a routing tie:
#   0.0044-0.0047; fp8 0.085; the bias left out of the choice 0.193, a softmax
#   router 0.385.
LAYER_RTOL = 0.02
# ... of which tokens at most this share may be that near a tie: 38-61 of 4,096
# were (sigmoid scores crowd under 1: the 8th and 9th of 256 lie ~0.01 apart)
TIES_MAX = 0.03
# * The indexer's loss, the step's on its batch and one layer's alone, as a
#   share of the reference's: within 0.00013 (0.00002 the layer alone); fp8
#   0.026 (0.0017 the layer alone), no gate 0.043.
INDEX_LOSS_RTOL = 0.002
# * The timed step's loss (cross entropy + 0.0001 x the balance term + the
#   indexer's loss) against the reference's over the same target tokens. It
#   reads within 0.0017, at 8,191 tokens and at 16,382 alike (a bias of the
#   bf16 path, not noise that more tokens average out, so the limit is no
#   multiple of 1 / sqrt(tokens)); half the batch 0.011, fp8 0.045, no gate
#   0.056, no rescale 1.6. The geometric mean of 0.0017 and 0.045.
LOSS_ATOL = 0.008
# * The sequence-wise balance term itself: within 0.000035 over fifteen seeds; no
#   gate 0.0022, a softmax router 0.0045, no rescale 0.018 (fp8 and a window of
#   512 move it by 0.00006-0.00009 and are refused by other limits). 0.0001 x
#   it is far under the loss's own limit.
BALANCE_ATOL = 0.0002
# * The first gradient's statistics that adafactor's new state holds, worst
#   leaf: 0.36-0.41 (a latent's norm weight or a router: leaves whose gradient
#   is a sum over few positions' sharp attention; the median leaf 0.053); fp8
#   1.35 (median 0.21), no gate 1.37 (0.64), a state left unchanged 1.0, half
#   the batch 1.92.
GRAD_STATS_RTOL = 0.75
# * The change of every parameter leaf against the reference's gradient through
#   the same optimizer in float32, rounded as the leaf rounds: adafactor's FIRST
#   step moves every element by the same amount, up or down by its gradient's
#   sign, so this reads 2 x sqrt(the share of elements whose sign differs),
#   and in bf16 only one weight in five moves at all: 0.76-0.80 the worst leaf
#   (a window layer's held experts; 0.81 with a window of 512), 0.60-0.63 the
#   median; a softmax router 0.964, the bias ignored 0.994, a state left
#   unchanged 1.0, half the batch 1.07, fp8 and no gate 1.41 (an unrelated
#   gradient's reading).
#   The driver's rule for a training cell's parameter change: between the
#   first reading and 1, with the more room above the reading. No control reads
#   three times the program's largest here, so this limit alone tells little:
#   it refuses a step not taken or taken on other rows, and the layers refuse
#   the rest.
UPDATE_RTOL = 0.93
# ... of a leaf of at least this many elements,
UPDATE_MIN_LEAF = 1024
# of which the reference's own step, rounded as the leaf rounds, moves at least
# this share. A step of a thousandth of a leaf's RMS moves a bf16 element only
# where that is half a unit in its last place (2^-9 to 2^-8 of the element):
# one weight in five of a matrix drawn around 0, and of a norm's weights of
# 0.5-1.5 none, or one on one side. Such a leaf's change is nothing against
# nothing: it is reported, and its gradient's size is held to GRAD_STATS_RTOL
# as any leaf's. Between the two: 0.2 and 1 / 512.
UPDATE_MIN_MOVED = 0.02
# keys in both the reference's own key sets and the program's over the keys in
# either: the 2,048th and 2,049th key swap on rounding (bf16 index products
# against the reference's float32), as the k-th and (k+1)-th expert do: one
# layer alone 0.9986, the first row's two full layers 0.9896-0.9897 (the second
# follows a first layer's rounding); fp8 0.971 and 0.791, no gate 0.784, the
# whole triangle 0.75 and 0.4375
SELECTION_AGREEMENT = 0.94
# share of the experts whose bias the step moved as the reference's rule moves
# it from the reference's own counts: an expert whose rows are within a few of
# the mean can go either way on a swapped choice: 0.969-0.988 (fp8 0.965, no
# gate 0.938, half the batch 0.957: these are refused by other limits); a program without the leaf agrees nowhere
BIAS_AGREEMENT = 0.9
# keys attended over causal keys against the same count of the reference's own
# key sets: equal to the last digit in five seeds; one key a query fewer
# (top-2,047) reads 0.000183 off in the step and 0.000244 in the layer alone;
# the whole triangle 0.5625
SELECTED_SHARE_ATOL = 0.0001


def model_config(model: dict, sizes: dict, control: str | None = None, **overrides):
    """The program's config object for a configuration file's ``model`` and
    ``train`` groups, by ``model_type``; ``control`` plants a fault. A program
    from before this model was supported fails here (no ``LatentAttention``),
    before a cluster or a chip is touched."""
    if model.get("model_type") != "dots3_note":
        raise RunFailure(f"runner train_sparse builds no model of type "
                         f"{model.get('model_type')!r}")
    try:
        from ray_tpu.models.llama import LlamaConfig
        from ray_tpu.models.mla import LatentAttention
    except ImportError as e:
        raise RunFailure(f"this program has no latent attention: {e}") from e

    kinds = {k: dict(v) for k, v in flops_sparse.kinds(model).items()}
    if control == "window_512":
        kinds["mla_win"]["window"] -= 1
    if control == "no_selection":
        kinds["mla"]["index_top_k"] = 1 << 30
    if control == "top_2047":
        kinds["mla"]["index_top_k"] -= 1
    if control in ("no_rescale", "no_gate"):
        for spec in kinds.values():
            spec[control[3:]] = False
    names = flops_sparse.layer_kinds(model)
    lead = model["first_k_dense_replace"]
    period = names[lead:lead + 4]
    first, last = model["experts_held"]
    assert names[lead:] == period * ((len(names) - lead) // len(period))
    assert model["n_routed_experts"] == last - first + 1 and model["moe_layer_freq"] == 1
    assert model["scoring_func"] == "sigmoid" and model["topk_method"] == "noaux_tc"
    assert model["routed_scaling_factor"] == 1 and model["n_shared_experts"] == 1
    return LlamaConfig(
        vocab_size=model["vocab_size"], hidden=model["hidden_size"],
        n_layers=model["num_hidden_layers"], n_heads=model["num_attention_heads"],
        n_kv_heads=model["num_key_value_heads"], head_dim=model["v_head_dim"],
        intermediate=model["moe_intermediate_size"],
        norm_eps=float(model["rms_norm_eps"]),
        layer_pattern=tuple(period), lead_pattern=tuple(names[:lead]),
        lead_intermediate=model["intermediate_size"],
        mla=LatentAttention(**kinds["mla"]), mla_window=LatentAttention(**kinds["mla_win"]),
        moe_experts=model["router_width"], moe_top_k=model["num_experts_per_tok"],
        moe_norm_topk=bool(model["norm_topk_prob"]),
        moe_shared=model["moe_intermediate_size"] * model["n_shared_experts"],
        moe_shared_gate=False, moe_held=(first, model["n_routed_experts"]),
        moe_score="softmax" if control == "softmax_router" else "sigmoid",
        moe_bias_rate=0.0 if control == "bias_ignored" else sizes["bias_rate"],
        moe_aux_weight=sizes["aux_loss_weight"], moe_z_weight=0.0, **overrides)


def reference_arch(model: dict) -> dict:
    """What ``reference/latent_sparse_decoder.py`` needs to know of the file."""
    names = flops_sparse.layer_kinds(model)
    lead = model["first_k_dense_replace"]
    return dict(kinds=flops_sparse.kinds(model), pattern=tuple(names[lead:lead + 4]),
                lead_pattern=tuple(names[:lead]), norm_eps=float(model["rms_norm_eps"]),
                top_k=model["num_experts_per_tok"], norm_topk=bool(model["norm_topk_prob"]),
                held_first=model["experts_held"][0])


def seed_biases(params, key):
    """Move every selection bias by a seeded uniform +- BIAS_SPREAD:
    ``init_params`` starts it at 0, against which a choice that left it out
    reads the same as one that did not."""
    import jax

    def move(path, leaf):
        if str(getattr(path[-1], "key", "")) != "router_bias":
            return leaf
        return leaf + jax.random.uniform(jax.random.fold_in(key, 77), leaf.shape,
                                         minval=-BIAS_SPREAD, maxval=BIAS_SPREAD)

    return jax.tree_util.tree_map_with_path(move, params)


def as_program(ref_params, axes):
    """The leaves of ``ref_params`` that the program's tree (``axes``, its
    ``param_axes``) has: a control that drops a leaf gives the program a
    smaller tree."""
    if isinstance(axes, dict):
        return {k: as_program(ref_params[k], v) for k, v in axes.items()}
    return ref_params


def _agreement(own, got) -> float:
    """Keys in both the reference's own key sets and the program's, over the
    keys in either."""
    own, got = np.asarray(own, bool), np.asarray(got, bool)
    return float((own & got).sum() / (own | got).sum())


def layer_errors(cfg, arch, layers, ref_layers, h) -> dict:
    """One layer of each kind alone on the same input h [S, E] (bf16, already
    normed): the program's mixers and ``moe_block`` (``cfg``, ``layers`` = an
    indexed layer's leaves and a window layer's) against the reference's
    (``arch``, ``ref_layers``)."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models.mla import mla_mixer
    from ray_tpu.models.moe import moe_block

    from ..reference import latent_sparse_decoder as ref

    get = lambda x: np.asarray(jax.device_get(x))  # noqa: E731
    positions = jnp.arange(h.shape[0], dtype=jnp.int32)
    out = {}
    for name, spec, layer, ref_layer in (
            ("full", cfg.mla, layers[0], ref_layers[0]),
            ("window", cfg.mla_window, layers[1], ref_layers[1])):
        got, aux = jax.jit(lambda h, w, spec=spec: mla_mixer(
            h[None], w, spec, config=cfg, positions=positions, return_selection=True))(h, layer)
        key_set = aux.get("selection")
        want, seen = jax.jit(lambda h, w, ks, name=name: ref.mla_mixer(
            h, w, arch["kinds"]["mla" if name == "full" else "mla_win"], arch["norm_eps"], ks))(
            h, ref_layer, None if key_set is None else key_set[0])
        err = get(_rel(got[0], want, -1))
        out[name] = {"max": float(err.max()), "mean": float(err.mean())}
        if key_set is not None:
            out[name].update(
                selection_agreement=_agreement(seen["selection"], key_set[0]),
                selected_share=float(aux["selected_share"]),
                ref_selected_share=float(np.asarray(seen["selection"]).sum()
                                         / (h.shape[0] * (h.shape[0] + 1) / 2)),
                index_loss=float(aux["index_loss"]), ref_index_loss=float(seen["index_loss"]))
    got, aux = jax.jit(lambda h, w: moe_block(
        h[None], w, top_k=cfg.moe_top_k, norm_topk=cfg.moe_norm_topk, held=cfg.moe_held,
        score=cfg.moe_score))(h, layers[1])
    want, routing = jax.jit(lambda h, w: ref.expert_layer(
        h, w, top_k=arch["top_k"], norm_topk=arch["norm_topk"], first=arch["held_first"]))(
        h, ref_layers[1])
    err = get(_rel(got[0], want, -1))
    tie = get(near_ties(routing["biased"], arch["top_k"], LAYER_TIE_GAP))
    out["experts"] = {"max": float(err[~tie].max()), "mean": float(err[~tie].mean()),
                      "ties": int(tie.sum()), "tokens": int(err.size),
                      "rows": int(get(aux["rows"]).sum()), "dropped": int(aux["dropped"]),
                      "held_share": float(aux["held_share"])}
    return out


def reference_step(params, rows, key_sets, arch, sizes) -> tuple:
    """The reference's side of a training step on token rows [B, S] under the
    given key sets [B, indexed layers, S, S]: (loss, seen, {leaf path: the
    loss's gradient in the leaf's own type}). ``seen``: the first row's
    ``logits`` and the indexer's own ``selection`` there; the three terms;
    ``own_selected_share``;
    ``rows_per_expert`` over all rows.

    A BLOCK at a time, by hand: forward keeping each block's input; then the
    head's gradient, and back through the blocks, each block's own ``jax.vjp``
    under one ``jit`` a kind of block (the float32 weights of ONE block, their
    cotangents and its activations are on the device at a time: all 1.8 B
    leaves' do not fit beside the weights). The loss's two auxiliary terms
    enter each block's pull-back with their weights. The same numbers as
    ``jax.grad`` of ``reference.loss`` (a test holds them equal)."""
    import jax
    import jax.numpy as jnp

    from ..reference import latent_sparse_decoder as ref

    # where each block's leaves lie, and the leaves themselves only while a
    # block runs: a scanned layer's are a slice, which is a copy (2.5 GB of
    # them beside the weights is what two rows have no room for)
    blocks = [(kind, lead, where) for _, kind, lead, where in ref.layers_of(params, arch)]

    def leaves(where):
        tree = params[where[0]][where[1]]
        return tree if len(where) == 2 else jax.tree.map(lambda a: a[where[2]], tree)

    indexed = [bool(arch["kinds"][kind].get("index_heads")) for kind, _, _ in blocks]
    n_rows, n_experts_layers = rows.shape[0], sum(not lead for _, lead, _ in blocks)
    w_balance = sizes["aux_loss_weight"] / (n_rows * n_experts_layers)
    w_index = 1.0 / (n_rows * sum(indexed))
    eps = arch["norm_eps"]

    @functools.lru_cache(maxsize=None)
    def forward(kind, lead):
        return jax.jit(lambda x, layer, key_set: ref.block(x, layer, kind, lead, arch, key_set))

    @functools.lru_cache(maxsize=None)
    def backward(kind, lead):
        def pull(x, layer, key_set, ct):
            def terms(x, layer):
                y, seen, routing = ref.block(x, layer, kind, lead, arch, key_set)
                return y, (jnp.zeros((), jnp.float32)
                           + (w_balance * routing["balance"] if routing else 0.0)
                           + (w_index * seen["index_loss"] if seen else 0.0))

            return jax.vjp(terms, x, layer)[1]((ct, jnp.ones((), jnp.float32)))

        return jax.jit(pull)

    @jax.jit
    def head_terms(x, final_norm, lm_head, row):
        def ce_of(x, final_norm, lm_head):
            lg = ref.head(x, final_norm, lm_head, eps)
            return ref.loss_of(lg, row) / n_rows, lg

        (ce, lg), grads = jax.value_and_grad(ce_of, argnums=(0, 1, 2), has_aux=True)(
            x, final_norm, lm_head)
        return ce, lg, grads

    scatter = jax.jit(lambda ct, row: jnp.zeros(params["embed"].shape, jnp.float32)
                      .at[row].add(ct).astype(params["embed"].dtype))
    add = lambda a, b: b if a is None else jax.tree.map(  # noqa: E731
        lambda x, y: (x.astype(jnp.float32) + y.astype(jnp.float32)).astype(x.dtype), a, b)
    grads = {"embed": None, "final_norm": None, "lm_head": None, "blocks": [None] * len(blocks)}
    ce = balance = index = own_share = 0.0
    rows_per_expert, first_row = 0, {}
    for b in range(n_rows):
        row = rows[b]
        sets = iter(jnp.asarray(key_sets[b]))  # a row's at a time on the device
        xs, used, selections, counts = [jax.jit(ref.embed)(params["embed"], row)], [], [], []
        for (kind, lead, where), has_index in zip(blocks, indexed):
            used.append(next(sets) if has_index else None)
            x, seen, routing = forward(kind, lead)(xs[-1], leaves(where), used[-1])
            xs.append(x)
            if seen:
                index += float(seen["index_loss"]) * w_index
                selections.append(seen["selection"])
            if routing:
                balance += float(routing["balance"]) * w_balance
                counts.append(routing["rows"])
        rows_per_expert = rows_per_expert + jnp.stack(counts)
        row_ce, lg, (ct, d_norm, d_head) = head_terms(
            xs[-1], params["final_norm"], params["lm_head"], row)
        ce += float(row_ce)
        grads["final_norm"] = add(grads["final_norm"], d_norm)
        grads["lm_head"] = add(grads["lm_head"], d_head)
        for i in reversed(range(len(blocks))):
            kind, lead, where = blocks[i]
            ct, d_layer = backward(kind, lead)(xs[i], leaves(where), used[i], ct)
            grads["blocks"][i] = add(grads["blocks"][i], d_layer)
            xs.pop()
        grads["embed"] = add(grads["embed"], scatter(ct, row))
        share = jnp.mean(jnp.stack([jnp.sum(s) for s in selections])) / (
            row.shape[0] * (row.shape[0] + 1) / 2)
        own_share += float(share) / n_rows
        if b == 0:  # on the host: the next row needs the room
            first_row = {"logits": np.asarray(lg), "selection": np.stack(
                [np.asarray(s) for s in selections])}
        del lg, selections, used, sets
    # the blocks' gradients back under the leaves' own names
    by_name = {f"['{k}']": grads[k] for k in ("embed", "final_norm", "lm_head")}
    periods = {}
    for (_, _, where), d_layer in zip(blocks, grads["blocks"]):
        for leaf, g in d_layer.items():
            if where[0] == "lead_layers":
                by_name[f"['lead_layers']['{where[1]}']['{leaf}']"] = g
            else:
                periods.setdefault(f"['layers']['{where[1]}']['{leaf}']", []).append(g)
    by_name.update({name: jnp.stack(gs) for name, gs in periods.items()})
    seen = {**first_row, "ce": ce, "balance": balance / sizes["aux_loss_weight"],
            "index_loss": index,
            "own_selected_share": own_share, "rows_per_expert": rows_per_expert}
    return ce + balance + index, seen, by_name


def step_errors(opt, start, after, opt_state, ref_start, ref_grads: dict) -> dict:
    """What one step of the program did against the reference's step, a leaf at
    a time, as ``train_hybrid.step_errors``; ``after`` may lie on the host.
    The selection biases are left out: no gradient moves them and their step
    has a check of its own."""
    import jax
    import jax.numpy as jnp
    import optax

    had = opt_state[0]
    if not isinstance(had, optax.FactoredState):
        raise RunFailure("the step's comparison reads adafactor's state; the "
                         f"optimizer's first is {type(had).__name__}")
    errors = _leaf_errors(opt)

    @jax.jit
    def ref_moved(ref_start, ref_grad):
        # the share of a leaf's elements that the reference's step moves
        p32 = ref_start.astype(jnp.float32)
        update, _ = opt.update(ref_grad.astype(jnp.float32), opt.init(p32), p32)
        kept = jnp.finfo(ref_start.dtype)
        return jnp.mean(jax.lax.reduce_precision(p32 + update, kept.nexp, kept.nmant) != p32)

    names = [jax.tree_util.keystr(path)
             for path, _ in jax.tree_util.tree_flatten_with_path(start)[0]]
    ref_leaves = {jax.tree_util.keystr(p): leaf
                  for p, leaf in jax.tree_util.tree_flatten_with_path(ref_start)[0]}
    trees = (start, after, had.v_row, had.v_col, had.v)
    by_leaf = {"update": {}, "grad_stats": {}, "moved": {}, "ref_moved_share": {}}
    sizes = {}
    for name, *leaves in zip(names, *(jax.tree.leaves(t) for t in trees)):
        if name.endswith("['router_bias']"):
            continue
        update, grad_stats, moved = (float(x) for x in jax.device_get(
            errors(*leaves, ref_leaves[name], ref_grads[name])))
        by_leaf["update"][name] = update
        by_leaf["grad_stats"][name] = grad_stats
        by_leaf["moved"][name] = moved
        by_leaf["ref_moved_share"][name] = float(ref_moved(ref_leaves[name], ref_grads[name]))
        sizes[name] = leaves[0].size
    out = {"by_leaf": by_leaf}
    for what, least in (("update", UPDATE_MIN_LEAF), ("grad_stats", 0)):
        judged = {name: x for name, x in by_leaf[what].items() if sizes[name] >= least
                  and (what != "update"
                       or by_leaf["ref_moved_share"][name] >= UPDATE_MIN_MOVED)}
        worst = max(judged, key=judged.get)
        out[what] = {"worst": judged[worst], "leaf": worst,
                     "median": float(np.median(list(judged.values())))}
    return out


def bias_errors(start, after, ref_rows, rate: float) -> dict:
    """Every selection bias's step against the reference's rule on the
    reference's own counts ``ref_rows`` [expert layers, X]: the share of
    experts moved alike, the worst layer's. A program with no such leaf
    agrees nowhere."""
    import jax

    from ..reference import latent_sparse_decoder as ref

    def biases(tree):
        return {jax.tree_util.keystr(p): np.asarray(leaf, np.float32)
                for p, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]
                if str(getattr(p[-1], "key", "")) == "router_bias"}

    before, now = biases(start), biases(after)
    if not now:
        return {"agreement": 0.0, "abs_max": 0.0, "layers": 0}
    # slot i's leaf is [periods, X]: scanned layer p * slots + i
    slots = len(now)
    rows = np.asarray(ref_rows).reshape(-1, slots, np.asarray(ref_rows).shape[-1])
    worst = 1.0
    for i, name in enumerate(sorted(now)):
        want = np.asarray(ref.bias_after(before[name], rows[:, i], rate)) - before[name]
        got = now[name] - before[name]
        worst = min(worst, float(np.mean(np.sign(got) == np.sign(want))))
        # a step is the rate up or down, or nothing for an expert at the mean
        if (np.abs(np.abs(got) - rate) > 1e-3 * rate)[got != 0].any():
            worst = 0.0
    return {"agreement": worst, "abs_max": max(float(np.abs(b).max()) for b in now.values()),
            "layers": rows.shape[0] * slots}


def _loop(config: dict) -> None:
    """Runs in the train worker that leased the chips."""
    marks = [("loop_entered", time.time())]  # set-up's phases, by the wall clock

    in_use = []  # the device's bytes in use at each mark, for the reference's room

    def mark(name):
        marks.append((name, time.time()))
        try:
            stats = jax.local_devices()[0].memory_stats() or {}
            in_use.append((name, stats.get("bytes_in_use"), stats.get("largest_free_block_bytes")))
        except Exception:  # noqa: BLE001 - a backend without the statistic
            pass

    import jax
    import jax.numpy as jnp
    import optax

    from ray_tpu import train
    from ray_tpu.models import init_params, loss_fn, param_axes, update_buffers
    from ray_tpu.models.llama import forward_hidden
    from ray_tpu.parallel import MeshConfig, create_mesh
    from ray_tpu.parallel.sharding import logical_sharding, sharding_tree
    from ray_tpu.tpu import device_report, leased_devices

    from ..reference import latent_sparse_decoder as ref

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
    rows_sharding = logical_sharding(mesh, ("batch", None))
    chunk = sizes["loss_chunk_tokens"]
    key = jax.random.PRNGKey(config["seed"])

    # weights on the device(s) in one jitted call, in the type they train in;
    # the seed goes in as the key's value (a constant would compile anew a
    # seed). Always the TRUE configuration's tree: the reference's weights.
    seeded = jax.jit(lambda key: seed_biases(seed_norms(init_params(true_cfg, key), key), key),
                     out_shardings=sharding_tree(param_axes(true_cfg), mesh))
    # the leaves in the model's own type, a leaf and a cast at a time: under
    # one ``jit`` the chip's compiler drops a cast there and back
    fp8 = lambda tree: jax.tree.map(  # noqa: E731
        lambda a: a.astype(jnp.float8_e4m3fn).astype(a.dtype)
        if a.dtype == cfg.dtype else jnp.copy(a), tree)

    def program_weights(ref_params):
        # no copy but under fp8: the reference's arrays, less what a control drops
        params = as_program(ref_params, param_axes(cfg))
        return fp8(params) if control == "fp8_weights" else params

    ref_params = seeded(key)
    params = jax.jit(lambda tree: jax.tree.map(jnp.copy, tree))(program_weights(ref_params))
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
        # the selection biases: no gradient moves them, the step's counts do
        params = update_buffers(optax.apply_updates(params, updates), aux, cfg)
        biases = [leaf for path, leaf in jax.tree_util.tree_flatten_with_path(params)[0]
                  if str(getattr(path[-1], "key", "")) == "router_bias"]
        # the program's counters, from the same pass as the loss
        counters = (loss, aux["load_balance"], aux["index_loss"], aux["attn_selected_share"],
                    aux["rows_per_expert"].sum(axis=-1), aux["rows_dropped"],
                    aux["rows_per_held_expert"], aux["held_share"],
                    jnp.max(jnp.stack([jnp.abs(b).max() for b in biases]))
                    if biases else jnp.zeros(()))
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
    program_bytes = int(mem.argument_size_in_bytes + mem.output_size_in_bytes
                        + mem.temp_size_in_bytes - mem.alias_size_in_bytes)

    # correctness, before the window: program vs plain reference. One layer of
    # each kind alone: the period's indexed layer and its first window layer
    pick = lambda tree, slot: jax.tree.map(lambda a: a[0], tree["layers"][slot])  # noqa: E731
    h = jax.random.normal(jax.random.PRNGKey(config["seed"] + 1),
                          (min(config["check_tokens"], first.shape[1]), cfg.hidden), cfg.dtype)
    layers = layer_errors(cfg, arch,
                          (pick(params, "slot0"), pick(params, "slot1")),
                          (pick(ref_params, "slot0"), pick(ref_params, "slot1")), h)
    del h, ref_params
    mark("layers")

    # the first row's logits and the key sets the program chooses on the batch
    def logits_and_sets(p, t):
        hidden, aux = forward_hidden(p, t, cfg, mesh=mesh, return_aux=True,
                                     return_selection=True)
        return (jnp.einsum("se,ev->sv", hidden[0], p["lm_head"]).astype(jnp.float32),
                jnp.swapaxes(aux["selection"], 0, 1))

    prog_logits, key_sets = jax.device_get(jax.jit(logits_and_sets)(params, batch["tokens"]))
    mark("logits_and_key_sets")
    # The step's scratch is one block. Where what the checks above left behind
    # has cut the free memory into smaller pieces, the step's state goes to the
    # host and comes back into a memory that holds nothing else
    free = (jax.local_devices()[0].memory_stats() or {}).get("largest_free_block_bytes")
    if free is not None and free < mem.temp_size_in_bytes + (1 << 28):
        state = jax.device_get((params, opt_state))
        for leaf in jax.tree.leaves((params, opt_state)):
            leaf.delete()
        params = jax.device_put(state[0], sharding_tree(param_axes(cfg), mesh))
        opt_state = jax.device_put(state[1])
        del state
        mark("state_alone_on_device")
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
    # host and comes back after the comparison. Its program reserves its
    # scratch space from the bottom of the device's memory, below the lowest
    # live buffer wherever that lies, so the weights it reads are made anew
    # into an empty memory (a first try found 6.7 GB reservable with 4.4 in use)
    after, opt_state = jax.device_get((params, opt_state))
    del params, counters, batch
    mark("step_moved_to_host")
    ref_params = seeded(key)
    ref_loss, seen, ref_grads = reference_step(ref_params, jnp.asarray(first), key_sets,
                                               arch, sizes)
    mark("reference_step")
    err = np.asarray(jax.device_get(ref.position_errors(jnp.asarray(prog_logits),
                                                        jnp.asarray(seen["logits"]))))
    whole = {"max": float(err.max()), "median": float(np.median(err)),
             "selection_agreement": _agreement(seen["selection"], key_sets[0]),
             "ref_loss": float(ref_loss), "ref_ce": float(seen["ce"]),
             "ref_balance": float(seen["balance"]), "ref_index_loss": float(seen["index_loss"]),
             "ref_selected_share": float(seen["own_selected_share"])}
    start = program_weights(ref_params)
    step = step_errors(opt, start, after, opt_state, ref_params, ref_grads)
    bias = bias_errors(start, after, jax.device_get(seen["rows_per_expert"]),
                       sizes["bias_rate"])
    del start, prog_logits, seen, ref_grads, ref_params, key_sets
    params = jax.device_put(after, sharding_tree(param_axes(cfg), mesh))
    opt_state = jax.device_put(opt_state)
    del after
    mark("step_compared")

    rows_per_step = sizes["batch"] * first.shape[1] * cfg.moe_top_k
    losses, load, held, rows_wrong = [], [], [], []
    counted = {"index_loss": [], "selected_share": [], "rows_per_held_expert": [],
               "bias_abs_max": []}
    step_t_a, step_ms, wait_ms, report_ms = [], [], [], []

    def one_step(tokens):
        nonlocal params, opt_state
        params, opt_state, counters = compiled(
            params, opt_state, {"tokens": jax.device_put(tokens, rows_sharding)})
        (loss, _, index_loss, selected, rows, dropped, rows_held, held_share,
         bias_max) = jax.device_get(counters)  # the fence
        if int(dropped) or (rows != rows_per_step).any():
            rows_wrong.append([int(dropped), rows.tolist()])
        # over the experts this chip holds: their rows are what its grouped
        # matmuls compute
        return {"loss": float(loss), "index_loss": float(index_loss),
                "attn_selected_share": float(selected),
                "moe_load_max_over_mean": float(
                    (rows_held.max(axis=-1) / rows_held.mean(axis=-1)).mean()),
                "held_share": float(held_share.mean()),
                "rows_per_held_expert": float(rows_held.mean()),
                "router_bias_abs_max": float(bias_max)}

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
        load.append(said["moe_load_max_over_mean"])
        held.append(said["held_share"])
        counted["index_loss"].append(said["index_loss"])
        counted["selected_share"].append(said["attn_selected_share"])
        counted["rows_per_held_expert"].append(said["rows_per_held_expert"])
        counted["bias_abs_max"].append(said["router_bias_abs_max"])
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
        "bytes_in_use_at_marks": in_use,
        "memory": {"arguments": int(mem.argument_size_in_bytes),
                   "temporaries": int(mem.temp_size_in_bytes)},
        "tpu_custom_calls": compiled.as_text().count("tpu_custom_call"),
        "prog_loss": float(step0[0]), "prog_balance": float(step0[1]),
        "prog_index_loss": float(step0[2]), "prog_selected_share": float(step0[3]),
        "check_rows_per_layer": step0[4].tolist(), "check_rows_dropped": int(step0[5]),
        "check_held_share": step0[7].tolist(),
        "check_tokens": int(first.shape[0] * (first.shape[1] - 1)),
        "check_positions": int(first.size), "rows_per_token": cfg.moe_top_k,
        "whole": whole, "layers": layers, "step": step, "bias": bias,
        "rows_wrong": rows_wrong[:5], "load_max_over_mean": load, "held_share": held,
        "counted": counted, "traced_steps": traced, "device": device, "trace": summary}})


def expected_selected_share(seq: int, top_k: int) -> float:
    return flops_sparse.kept_pairs(seq, top_k) / (seq * (seq + 1) / 2)


def run(ctx: Context) -> dict:
    cfg = ctx.cell.config
    if ctx.rehearse:
        with open(os.path.join(HERE, "rehearse-sparse.json")) as f:
            cfg = {**ctx.rehearse, **json.load(f)}
    mix = ctx.cell.traffic
    seq = int(mix["seq"]) if not ctx.rehearse else int(cfg["train_seq"])
    sizes = dict(cfg["train"])
    control = os.environ.get("BENCH_SPARSE_CONTROL") or None
    if control not in (None, *CONTROLS):
        raise RunFailure(f"BENCH_SPARSE_CONTROL is {control!r}: one of {tuple(CONTROLS)}")
    witness = os.environ.get("BENCH_SPARSE_WITNESS") or None
    if witness not in (None, *WITNESSES):
        raise RunFailure(f"BENCH_SPARSE_WITNESS is {witness!r}: one of {tuple(WITNESSES)}")
    if witness == "no_rescale":
        cfg = {**cfg, "model": {**cfg["model"], "apply_mla_qkv_lora_rescale": False}}
    # before a cluster or a chip is touched: a program that cannot describe
    # this model (one from before it was supported) fails here, at once
    model_config(cfg["model"], sizes, control)
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
                "unions": layer_metrics.union_specs(ctx.cell.readers)},
            scaling_config=ScalingConfig(num_workers=1,
                                         resources_per_worker=resources,
                                         worker_runtime_env=runtime_env),
            run_config=RunConfig(name="bench-train-sparse",
                                 storage_path=tempfile.mkdtemp(prefix="bench-train-sparse-")),
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
    whole, layers, step, bias = m["whole"], m["layers"], m["step"], m["bias"]
    traces = device["kernel_traces"]
    experts, full = layers["experts"], layers["full"]
    model = cfg["model"]
    n_expert_layers = model["num_hidden_layers"] - model["first_k_dense_replace"]
    top_k = model["index_topk"]
    close = lambda got, want, rtol: abs(got - want) <= rtol * abs(want)  # noqa: E731
    least_share = expected_selected_share(m["check_positions"] // sizes["batch"], top_k)
    checks = {
        "losses_finite": all(math.isfinite(x) for x in m["losses"]),
        "logits_match_reference": whole["median"] <= LOGIT_MEDIAN_RTOL
        and whole["max"] <= LOGIT_RTOL,
        "loss_matches_reference": abs(m["prog_loss"] - whole["ref_loss"])
        <= LOSS_ATOL,
        "balance_matches_reference":
        abs(m["prog_balance"] - whole["ref_balance"]) <= BALANCE_ATOL,
        "index_loss_matches_reference":
        close(m["prog_index_loss"], whole["ref_index_loss"], INDEX_LOSS_RTOL)
        and close(full["index_loss"], full["ref_index_loss"], INDEX_LOSS_RTOL),
        "selection_agrees_with_reference":
        min(whole["selection_agreement"], full["selection_agreement"]) >= SELECTION_AGREEMENT,
        # the count of the reference's OWN key sets (ties and all), and never
        # fewer keys than min(t + 1, index_topk) a query, in any step
        "selected_share_is_the_count":
        abs(m["prog_selected_share"] - whole["ref_selected_share"]) <= SELECTED_SHARE_ATOL
        and abs(full["selected_share"] - full["ref_selected_share"]) <= SELECTED_SHARE_ATOL
        and all(x >= least_share - SELECTED_SHARE_ATOL
                for x in [m["prog_selected_share"], *m["counted"]["selected_share"]]),
        "gradient_statistics_match_reference": step["grad_stats"]["worst"] <= GRAD_STATS_RTOL,
        "update_matches_reference": step["update"]["worst"] <= UPDATE_RTOL,
        "bias_steps_as_the_reference": bias["agreement"] >= BIAS_AGREEMENT,
        "full_layer_matches_reference": full["max"] <= MIXER_RTOL,
        "window_layer_matches_reference": layers["window"]["max"] <= MIXER_RTOL,
        "expert_layer_matches_reference": experts["max"] <= LAYER_RTOL
        and experts["ties"] <= max(2, TIES_MAX * experts["tokens"]),
        "no_row_dropped": not m["rows_wrong"] and m["check_rows_dropped"] == 0
        and experts["dropped"] == 0
        and experts["rows"] == experts["tokens"] * m["rows_per_token"]
        and m["check_rows_per_layer"]
        == [m["check_positions"] * m["rows_per_token"]] * n_expert_layers,
        "attention_kernels_native": kernel_native(traces, "flash_attention", ctx.platform),
        "index_kernels_native": kernel_native(traces, "dsa_index", ctx.platform)
        and kernel_native(traces, "dsa_probs", ctx.platform),
        "grouped_matmul_native": kernel_native(traces, "moe_gmm", ctx.platform)
        and kernel_native(traces, "moe_tgmm", ctx.platform),
        "custom_calls_compiled": (m["tpu_custom_calls"] > 0) == (ctx.platform == "tpu"),
    }
    marks += [tuple(x) for x in m["marks"]] + [("window_start", m["t_window_start_wall"])]
    ctx.say({"setup_phases_s": {b[0]: b[1] - a[1] for a, b in zip(marks, marks[1:])}})
    quart = lambda xs: [stats.percentile(xs, q) for q in (25, 50, 75)]  # noqa: E731
    ctx.say({"control": control and f"{control}: {CONTROLS[control]}",
             "witness": witness and f"{witness}: {WITNESSES[witness]}", "checks": checks,
             "limits": {
        "LOGIT_MEDIAN_RTOL": LOGIT_MEDIAN_RTOL, "LOGIT_RTOL": LOGIT_RTOL,
        "GRAD_STATS_RTOL": GRAD_STATS_RTOL, "UPDATE_RTOL": UPDATE_RTOL,
        "MIXER_RTOL": MIXER_RTOL, "LAYER_RTOL": LAYER_RTOL,
        "INDEX_LOSS_RTOL": INDEX_LOSS_RTOL, "BALANCE_ATOL": BALANCE_ATOL,
        "SELECTION_AGREEMENT": SELECTION_AGREEMENT, "BIAS_AGREEMENT": BIAS_AGREEMENT,
        "SELECTED_SHARE_ATOL": SELECTED_SHARE_ATOL, "TIES_MAX": TIES_MAX,
        "LOSS_ATOL": LOSS_ATOL,
        "LAYER_TIE_GAP": LAYER_TIE_GAP},
        "whole_model": whole, "layers": layers, "step": step, "bias": bias,
        "prog_loss": m["prog_loss"], "prog_balance": m["prog_balance"],
        "prog_index_loss": m["prog_index_loss"],
        "prog_selected_share": m["prog_selected_share"],
        "expected_selected_share": least_share,
        "check_tokens": m["check_tokens"],
        "check_rows_per_layer": m["check_rows_per_layer"],
        "check_held_share": m["check_held_share"],
        "rows_wrong": m["rows_wrong"],
        "load_max_over_mean_quartiles": quart(m["load_max_over_mean"]),
        "held_share_quartiles": quart(m["held_share"]),
        "counted_quartiles": {k: quart(v) for k, v in m["counted"].items()},
        "steps": m["steps"], "window_s": m["window_s"],
        "step_ms_quartiles": quart(m["step_ms"]), "compile_s": m["compile_s"],
        **window["said"],
        "loss_first_last": [m["losses"][0], m["losses"][-1]],
        "program_bytes": m["program_bytes"], "memory": m["memory"],
        "bytes_in_use_at_marks": m["bytes_in_use_at_marks"],
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
        # the calls the trace holds of each family of kernels, recomputed
        # ones included, THEIR seconds and the least seconds those same calls
        # could take doing the USEFUL work. A CPU rehearsal interprets the
        # kernels into plain ops, so its trace holds none: the share of the
        # roofline then reads 0 over the window.
        families = {
            "sel": flops_sparse.attention_kernel_costs(model, "mla", sizes["batch"], seq),
            "win": flops_sparse.attention_kernel_costs(model, "mla_win", sizes["batch"], seq),
            "dsa": flops_sparse.index_kernel_costs(model, sizes["batch"], seq)}
        obs_families, kernel_calls = {}, {}
        for family, costs in families.items():
            took, least = 0.0, 0.0
            for kernel, (kernel_flops, kernel_bytes) in costs.items():
                pattern = rf'^%{kernel}(\.[\w.\-]+)? = .*custom_call_target="tpu_custom_call"'
                seconds, calls = trace_reduce.matching(summary["ops"], pattern)
                took += seconds
                least += calls * flops_sparse.roofline_seconds(kernel_flops, kernel_bytes, peaks)
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
        rows_held = (stats.mean(m["held_share"][first:last]) * tokens_per_step
                     * model["num_experts_per_tok"])
        ctx.say({"moe_gmm_calls": gmm_calls, "moe_gmm_seconds": gmm_s,
                 "rows_held_a_layer": rows_held, "kernel_calls": kernel_calls,
                 "kernel_families": obs_families,
                 "forward_flops_by_part": flops_sparse.forward_flops_by_part(model, seq)})
        obs = {"timers": {"data_wait_ms": window["data_wait_ms"],
                          "report_ms": window["report_ms"],
                          "step_ms_median": stats.percentile(m["step_ms"], 50)},
               # from the median step, not the window: the capture's own
               # start, stop and reduction sit inside a traced window
               "train": {"tok_s_chip": tokens_per_step / chips * 1e3
                         / stats.percentile(m["step_ms"], 50),
                         "peak_flops_per_s": peaks["bf16_flops_per_s"],
                         "flops_per_token": flops_sparse.train_flops_per_token(model, seq)},
               "moe": {"load_max_over_mean": stats.mean(m["load_max_over_mean"][first:last]),
                       "gmm_flops_per_call": 2.0 * rows_held * model["hidden_size"]
                       * model["moe_intermediate_size"],
                       "gmm_calls": gmm_calls,
                       "gmm_seconds": gmm_s if gmm_calls else summary["window_s"]},
               "attn": {"selected_share": stats.mean(
                   m["counted"]["selected_share"][first:last])},
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

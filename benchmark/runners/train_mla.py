"""Runner of the training cells of a latent-attention decoder that attends
every causal key (64 heads of 128 + 64 query and key features and 128 value
features, YaRN with its factor on the softmax scale), a leading dense layer
and sigmoid-routed experts with a selection bias under a routed scale beside a
plain shared expert, a chip's share of the experts and of the vocabulary: the
train runner's contract (``runners/train.py``: the same phases, the same
fenced steps, the same window rule through ``pauses.window_report``, the same
result line through ``result.emit``) with the configuration builder and the
plain reference swapped, as ``train_sparse.py`` and ``train_swa.py``, whose
step comparison (``train_swa.step_errors``) and helpers it imports. Which
model it builds is the configuration file's ``model_type``.

What decides ``correct``, all before the window, against
``reference/latent_decoder.py`` on the program's own bf16-rounded weights
(every norm weight first moved by a seeded +-0.5 and every selection bias by
a seeded +-0.05, so that a norm or a bias left out shows):

* ONE layer's two halves alone, at the configuration's widths, on a seeded
  bf16 input of CHECK_TOKENS positions (YaRN's slowed pairs have turned 0.07
  rad there where plain rope turns them 2.4; two 1,024-blocks of the kernels
  each way): the mixer (``MIXER_RTOL``), the expert layer's share
  (``LAYER_RTOL``);
* logits at every position of the batch's first row (``LOGIT_RTOL``,
  ``LOGIT_MEDIAN_RTOL``);
* THE TIMED STEP ITSELF, run once on the first batch: its loss and its
  balance term (``LOSS_ATOL``, ``BALANCE_ATOL``); the statistics of its first
  gradient that the optimizer's new state holds and the change of every
  parameter leaf (``GRAD_STATS_RTOL``, ``UPDATE_ALONG_ATOL``), against the
  reference's gradient on the same rows put through the same optimizer in
  float32; the step of every selection bias against the reference's rule on
  the reference's own counts (``BIAS_AGREEMENT``);
* the counts: rows routed = tokens x experts per token in that step and in
  every step of the window (nothing dropped); the held experts' share of them
  in the layer alone and in each layer of that step against the reference's
  own count (``HELD_SHARE_RTOL``), and in every step of the window within
  ``HELD_SHARE_BAND`` of 8 / 384;
* the flash and grouped-matmul kernels ran native on the chip.

``BENCH_MLA_CONTROL`` in the environment puts a fault in the program's place,
for showing that the comparison refuses it (``CONTROLS``); such a run says so
in its output and must end ``correct`` false.
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

from .. import flops, flops_mla, layer_metrics, pauses, stats, trace_reduce, traffic
from ..manifest import HERE
from . import (Context, RunFailure, capture_trace, check_device, kernel_native,
               lease, reduce_trace, start_cluster, stop_cluster)
from .train import TRACE_STEPS, WARM_STEPS
from .train_hybrid import _rel, logit_errors, seed_norms
from .train_moe import LAYER_TIE_GAP, MODEL_TIE_GAP, near_ties
from .train_sparse import bias_errors, seed_biases
from .train_swa import COMPARISON_PHASES, step_errors

# Positions of a layer's check input: half the length the model's YaRN group
# calls original; pair 20 of the 32 (the first slowed one) has turned 0.07 rad
# at the last of them under YaRN and 2.4 rad under plain rope; two
# 1,024-blocks of the plain kernels each way.
CHECK_TOKENS = 2048
# What can stand in the program's place (``BENCH_MLA_CONTROL``). The first six
# change the program that is timed and compared (the same leaves, a config
# that reads them otherwise); the seventh reshapes a leaf, so it stands in the
# single layer's comparison alone; the last two leave the program as it is
# and change what the compared step is given or what is kept of it.
CONTROLS = {
    "fp8_weights": "the program computes with its bf16 weights rounded to float8_e4m3fn, "
                   "the nearest precision below the configuration's",
    "plain_rope": "rope at theta 50,000's plain frequencies: YaRN's blend left out (the "
                  "softmax factor kept)",
    "no_mscale": "the softmax scale is 192^-1/2 alone: YaRN's factor 1.81326 left out",
    "yarn_on_cos": "YaRN in the grouped-query form: 1.34657 on cos and sin of q and k, "
                   "the softmax scale plain",
    "window_1024": "a query sees its last 1,024 keys only",
    "no_scale": "the routed experts' gates not multiplied by 2.827",
    "sigmoid_held": "the expert layer alone scoring the 8 held experts only: sigmoid over 8 "
                    "outputs, all of them chosen",
    "half_batch": "the compared step is given the first half of its batch's tokens twice",
    "unchanged_state": "the compared step's new parameters and optimizer state are thrown away",
}
LAYER_CONTROLS = ("sigmoid_held",)
# The limits. Errors are the RMS of the difference over the features of a
# position (or over a leaf) as a share of the RMS of the reference's there.
# Each lies between two readings of THIS cell on the chip at the published
# widths, through this runner (my chip runs, PR 41: twelve seeds of sound runs,
# every control on seeds 2900000005 and 77; PERF.md section 6): the largest the
# program gave over its seeds, the smallest that a control it is there to
# refuse gave, and the limit their geometric mean. The readings hardly move
# with the seed: 4,096 positions and 2.8 B weights average it out.
# * Logits of the first row's 4,096 positions, the MEDIAN: 0.0183-0.0191 (five
#   layers of two bf16 sub-blocks); fp8 weights 0.4725-0.4813.
LOGIT_MEDIAN_RTOL = 0.09
# * Logits, EVERY position: the worst reads 0.321-0.427, a position where bf16
#   swapped an expert in some layer (EVERY position lies within 2% of a
#   routing tie in one of four layers: 384 sigmoid scores crowd, eight are
#   taken and the routed sum counts 2.827-fold); fp8 0.630-0.636. A factor of
#   1.2 each way is all the room there is.
LOGIT_RTOL = 0.52
# * The mixer alone on a seeded bf16 input of 2,048 positions, worst token:
#   0.0074-0.0090; fp8 0.1768-0.1868, ``plain_rope`` 0.258-0.264,
#   ``yarn_on_cos`` 0.600-0.618, ``no_mscale`` 0.710-0.737, ``window_1024``
#   0.759-0.761.
MIXER_RTOL = 0.04
# * The expert layer's share, worst token not within 1e-4 of a routing tie:
#   0.0041-0.0047; fp8 0.0977-0.0985, ``no_scale`` 0.324-0.360,
#   ``sigmoid_held`` 1.305-1.321.
LAYER_RTOL = 0.021
# ... of which tokens at most this share may be that near a tie: 19-38 of 2,048
# were (sigmoid scores crowd under 1: the 8th and 9th of 384 lie ~0.005
# apart). No control moves it; twice the largest.
TIES_MAX = 0.04
# * The compared step's loss on the first batch (chunked head, cross entropy,
#   + 0.0001 x the balance term) against the reference's over the same 4,095
#   target tokens: sound runs read -0.00068 to +0.00136; ``half_batch`` 0.00441
#   and 0.02435 off (fp8 0.0039-0.0096, ``plain_rope`` 0.0033-0.0035). A WEAK
#   limit, as its siblings': a fault moves a mean over 4,095 tokens by what
#   sampling moves it; the gradient's and the layers' limits carry those.
LOSS_ATOL = 0.0025
# * The sequence-wise balance term itself, over all 384 experts: within
#   0.000203; ``half_batch`` 0.00204-0.00449 (``no_scale`` 0.0011-0.0017, fp8
#   0.0062-0.0070, ``window_1024`` 0.049-0.059).
BALANCE_ATOL = 0.00064
# * The compared step's first gradient, by what the optimizer's new state
#   holds of it (adafactor: the mean of its squares along the rows and along
#   the columns of a matrix), against the same statistics of the reference's
#   gradient: the worst leaf 0.130-0.211 (always the routers'; the median leaf
#   0.02); ``plain_rope`` 0.641-0.698, ``no_scale`` 0.870, fp8 0.911-0.953,
#   ``unchanged_state`` 1.0, ``half_batch`` 1.49-1.52.
GRAD_STATS_RTOL = 0.37
# * The change of every parameter leaf in that step ALONG the reference's
#   float32 update (``train_swa.step_errors``' ``update``): the worst leaf
#   0.050-0.073 (the routers'; the median leaf 0.004); ``no_scale`` 0.203-0.211,
#   ``half_batch`` 0.642, ``plain_rope`` 0.660-0.669, fp8 0.766-0.772,
#   ``unchanged_state`` 1.0 on every leaf.
UPDATE_ALONG_ATOL = 0.12
# * Share of the experts whose bias the step moved as the reference's rule
#   moves it from the reference's own counts (an expert whose rows are within a
#   few of the mean can go either way on a swapped choice): 0.9922-0.9974;
#   ``unchanged_state`` 0.0 (fp8 0.947-0.954, ``no_mscale`` 0.845-0.849, refused
#   by other limits). Of the disagreement, 0.0078 and 1.0, the geometric mean.
BIAS_AGREEMENT = 0.91
# * The held experts' share of all rows, the program's count against the
#   reference's on the same input, as a share of the reference's, in the layer
#   alone and in each layer of the step: within 0.0196 (the rows of tokens whose
#   8th and 9th expert swap on rounding); ``sigmoid_held`` 41.6-56.3 (a program
#   that scores the held experts alone reads 1 against ~8 / 384).
HELD_SHARE_RTOL = 0.9
# ... and in every step of the window as a multiple of 8 / 384, either way.
# The seeded +-0.05 of selection bias moves an expert's rows up to threefold
# (at 384 sigmoid scores the 8th largest lies where a score moves 0.12 a unit
# of logit), a layer's 8 held experts' sum between 0.3 and 1.8 times the even
# share (0.0059-0.0368 read over twelve seeds), their mean over the four layers
# by a quarter, and the bias's own steps then even it out through the window;
# three times the even share is also past the compact dispatch's bound of 2.25
# (``models/moe.py``'s HELD_CAPACITY, rounded up to row tiles).
HELD_SHARE_BAND = 3.0


def model_config(model: dict, sizes: dict, control: str | None = None, **overrides):
    """The program's config object for a configuration file's ``model`` and
    ``train`` groups, by ``model_type``; ``control`` plants a fault. A program
    from before this model was supported fails here (its ``MIXERS`` has no
    kind that attends every causal key), before a cluster or a chip is touched."""
    if model.get("model_type") != "kimi_k2":
        raise RunFailure(f"runner train_mla builds no model of type "
                         f"{model.get('model_type')!r}")
    from ray_tpu.models import llama, mla
    if flops_mla.KIND not in llama.MIXERS:
        raise RunFailure("this program has no latent attention over every causal key: "
                         f"its mixer kinds are {sorted(llama.MIXERS)}")

    spec = dict(flops_mla.spec(model))
    if control == "plain_rope":
        spec["yarn"] = None
    if control == "no_mscale":
        spec["softmax_factor"] = 1.0
    if control == "yarn_on_cos":
        spec["yarn"] = {**spec["yarn"], "attention_factor": math.sqrt(spec["softmax_factor"])}
        spec["softmax_factor"] = 1.0
    if control == "window_1024":
        spec["window"] = 1024
    spec["yarn"] = spec["yarn"] and llama.Yarn(**spec["yarn"])
    lead = model["first_k_dense_replace"]
    first, last = model["experts_held"]
    assert model["n_routed_experts"] == last - first + 1 and model["moe_layer_freq"] == 1
    assert model["scoring_func"] == "sigmoid" and model["topk_method"] == "noaux_tc"
    assert model["n_group"] == model["topk_group"] == 1 and model["seq_aux"]
    assert not model["attention_bias"] and not model["num_nextn_predict_layers"]
    assert model["num_key_value_heads"] == model["num_attention_heads"]
    return llama.LlamaConfig(
        vocab_size=model["vocab_size"], hidden=model["hidden_size"],
        n_layers=model["num_hidden_layers"], n_heads=model["num_attention_heads"],
        n_kv_heads=model["num_key_value_heads"], head_dim=model["v_head_dim"],
        intermediate=model["moe_intermediate_size"], norm_eps=float(model["rms_norm_eps"]),
        layer_pattern=(flops_mla.KIND,), lead_pattern=(flops_mla.KIND,) * lead,
        lead_intermediate=model["intermediate_size"], mla_full=mla.LatentAttentionYarn(**spec),
        moe_experts=model["router_width"], moe_top_k=model["num_experts_per_tok"],
        moe_norm_topk=bool(model["norm_topk_prob"]),
        moe_shared=model["moe_intermediate_size"] * model["n_shared_experts"],
        moe_shared_gate=False, moe_held=(first, model["n_routed_experts"]),
        moe_score="sigmoid", moe_bias_rate=sizes["bias_rate"],
        moe_routed_scale=1.0 if control == "no_scale"
        else float(model["routed_scaling_factor"]),
        moe_aux_weight=sizes["aux_loss_weight"], moe_z_weight=0.0, **overrides)


def reference_arch(model: dict) -> dict:
    """What ``reference/latent_decoder.py`` needs to know of the file."""
    return dict(spec=flops_mla.spec(model), lead_layers=model["first_k_dense_replace"],
                norm_eps=float(model["rms_norm_eps"]), top_k=model["num_experts_per_tok"],
                norm_topk=bool(model["norm_topk_prob"]), held_first=model["experts_held"][0],
                routed_scale=float(model["routed_scaling_factor"]))


def layer_errors(cfg, arch, layer, ref_layer, h, control=None) -> dict:
    """One expert layer's two halves alone on the same input h [S, E] (bf16,
    already normed): the program's mixer and ``moe_block`` (``cfg``,
    ``layer``) against the reference's (``arch``, ``ref_layer``). A control of
    LAYER_CONTROLS reshapes the program's leaves here."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models.mla import mla_mixer
    from ray_tpu.models.moe import moe_block

    from ..reference import latent_decoder as ref

    get = lambda x: np.asarray(jax.device_get(x))  # noqa: E731
    positions = jnp.arange(h.shape[0], dtype=jnp.int32)
    got, _ = jax.jit(lambda h, w: mla_mixer(
        h[None], w, cfg.mla_full, config=cfg, positions=positions))(h, layer)
    want = jax.jit(lambda h, w: ref.mla_mixer(h, w, arch["spec"], arch["norm_eps"]))(
        h, ref_layer)
    err = get(_rel(got[0], want, -1))
    out = {"mixer": {"max": float(err.max()), "mean": float(err.mean())}}
    kw = dict(held=cfg.moe_held, top_k=cfg.moe_top_k)
    if control == "sigmoid_held":
        first, count = cfg.moe_held
        layer, kw = {**layer, "router": layer["router"][:, first:first + count],
                     "router_bias": layer["router_bias"][first:first + count]}, dict(
            held=None, top_k=min(cfg.moe_top_k, count))
    got, aux = jax.jit(lambda h, w: moe_block(
        h[None], w, norm_topk=cfg.moe_norm_topk, score=cfg.moe_score,
        routed_scale=cfg.moe_routed_scale, **kw))(h, layer)
    want, routing = jax.jit(lambda h, w: ref.expert_layer(
        h, w, top_k=arch["top_k"], norm_topk=arch["norm_topk"], first=arch["held_first"],
        scale=arch["routed_scale"]))(h, ref_layer)
    err = get(_rel(got[0], want, -1))
    tie = get(near_ties(routing["biased"], arch["top_k"], LAYER_TIE_GAP))
    first, count = cfg.moe_held
    ref_rows = get(routing["rows"])
    out["experts"] = {"max": float(err[~tie].max()), "mean": float(err[~tie].mean()),
                      "ties": int(tie.sum()), "tokens": int(err.size),
                      "rows": int(get(aux["rows"]).sum()), "dropped": int(aux["dropped"]),
                      "held_share": float(aux.get("held_share", 1.0)),
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
    from ray_tpu.models import forward, init_params, loss_fn, param_axes, update_buffers
    from ray_tpu.parallel import MeshConfig, create_mesh
    from ray_tpu.parallel.sharding import logical_sharding, sharding_tree
    from ray_tpu.tpu import device_report, leased_devices

    from ..reference import latent_decoder as ref

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
    seeded = jax.jit(lambda key: seed_biases(seed_norms(init_params(true_cfg, key), key), key),
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

    def alone_on_device(gone, state):
        """``state`` = (parameters, optimizer state) from the host onto a
        device that holds nothing else of any size: ``gone``'s arrays are
        deleted first, and the leaves are placed one by one in the tree's
        order, each when the one before it lies, so that every run lays the
        step's state out alike (a run's step time followed where its leaves
        happened to lie: PERF.md section 6, PR 41)."""
        for leaf in jax.tree.leaves(gone):
            if isinstance(leaf, jax.Array) and not leaf.is_deleted():
                leaf.delete()
        flat, tree = jax.tree.flatten(state)
        places = jax.tree.leaves(shardings)  # the parameters' leaves come first
        places += [None] * (len(flat) - len(places))
        return jax.tree.unflatten(tree, [jax.block_until_ready(jax.device_put(leaf, place))
                                         for leaf, place in zip(flat, places)])

    @functools.partial(jax.jit, donate_argnums=(0, 1))
    def train_step(params, opt_state, batch):
        (loss, aux), grads = jax.value_and_grad(
            lambda p: loss_fn(p, batch, cfg, mesh=mesh, chunk_tokens=chunk, return_aux=True),
            has_aux=True)(params)
        updates, opt_state = opt.update(grads, opt_state, params)
        # the selection biases: no gradient moves them, the step's counts do
        params = update_buffers(optax.apply_updates(params, updates), aux, cfg)
        # the program's counters, from the same pass as the loss
        counters = (loss, aux["load_balance"], aux["rows_per_expert"].sum(axis=-1),
                    aux["rows_dropped"], aux["rows_per_held_expert"], aux["held_share"])
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

    # correctness, before the window: program vs plain reference. The first
    # expert layer's two halves alone
    pick = lambda tree: jax.tree.map(lambda a: a[0], tree["layers"])  # noqa: E731
    h = jax.random.normal(jax.random.PRNGKey(config["seed"] + 1),
                          (min(config["check_tokens"], first.shape[1]), cfg.hidden), cfg.dtype)
    layers = layer_errors(cfg, arch, pick(params), pick(ref_params), h, control)
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
    # The step's scratch is most of the chip and one block: its state goes to
    # the host and comes back into a memory that holds nothing else (what the
    # checks above left behind cuts the free memory into smaller pieces). A
    # control that throws the step's result away keeps that copy
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
    # host and comes back after the comparison (a block's float32 weights and
    # their cotangents beside two copies of 5.6 GB of weights leave no room)
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
                 ref_balance=float(seen["balance"]),
                 ref_held_share=(ref_rows[:, first_held:first_held + held].sum(axis=-1)
                                 / ref_rows.sum(axis=-1)).tolist())
    start = fp8(ref_params) if control == "fp8_weights" else ref_params
    step = step_errors(opt, start, after, opt_state, ref_params, ref_grads)
    bias = bias_errors(start, after, ref_rows, sizes["bias_rate"])
    del prog_logits, seen, ref_grads
    params, opt_state = alone_on_device((start, ref_params), (after, opt_state))
    del after, start, ref_params
    mark("step_compared")

    rows_per_step = sizes["batch"] * first.shape[1] * cfg.moe_top_k
    losses, rows_wrong = [], []
    counted = {"load_max_over_mean": [], "held_share": [], "rows_per_held_expert": []}
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
        return {"loss": float(loss),
                "moe_load_max_over_mean": float(
                    (rows_held.max(axis=-1) / rows_held.mean(axis=-1)).mean()),
                "held_share": float(held_share.mean()),
                "rows_per_held_expert": float(rows_held.mean())}

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
        "check_held_share": step0[5].tolist(),
        "check_tokens": int(first.shape[0] * (first.shape[1] - 1)),
        "check_positions": int(first.size), "rows_per_token": cfg.moe_top_k,
        "whole": whole, "layers": layers, "step": step, "bias": bias,
        "rows_wrong": rows_wrong[:5], "counted": counted, "traced_steps": traced,
        "device": device, "trace": summary}})


def run(ctx: Context) -> dict:
    cfg = ctx.cell.config
    if ctx.rehearse:
        with open(os.path.join(HERE, "rehearse-mla.json")) as f:
            cfg = {**ctx.rehearse, **json.load(f)}
    mix = ctx.cell.traffic
    seq = int(mix["seq"]) if not ctx.rehearse else int(cfg["train_seq"])
    sizes, model = dict(cfg["train"]), cfg["model"]
    control = os.environ.get("BENCH_MLA_CONTROL") or None
    if control not in (None, *CONTROLS):
        raise RunFailure(f"BENCH_MLA_CONTROL is {control!r}: one of {tuple(CONTROLS)}")
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
            run_config=RunConfig(name="bench-train-mla",
                                 storage_path=tempfile.mkdtemp(prefix="bench-train-mla-")),
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
    n_expert_layers = model["num_hidden_layers"] - model["first_k_dense_replace"]
    even_share = model["n_routed_experts"] / model["router_width"]
    checks = {
        "losses_finite": all(math.isfinite(x) for x in m["losses"]),
        "logits_match_reference": whole["median"] <= LOGIT_MEDIAN_RTOL
        and whole["max"] <= LOGIT_RTOL,
        "loss_matches_reference": abs(m["prog_loss"] - whole["ref_loss"]) <= LOSS_ATOL,
        "balance_matches_reference":
        abs(m["prog_balance"] - whole["ref_balance"]) <= BALANCE_ATOL,
        "gradient_statistics_match_reference": step["grad_stats"]["worst"] <= GRAD_STATS_RTOL,
        "update_matches_reference": step["update"]["worst"] <= UPDATE_ALONG_ATOL,
        "bias_steps_as_the_reference": bias["agreement"] >= BIAS_AGREEMENT
        and bias["layers"] == n_expert_layers,
        "mixer_matches_reference": layers["mixer"]["max"] <= MIXER_RTOL,
        "expert_layer_matches_reference": experts["max"] <= LAYER_RTOL
        and experts["ties"] <= max(2, TIES_MAX * experts["tokens"]),
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
        "loss_atol": LOSS_ATOL, "BIAS_AGREEMENT": BIAS_AGREEMENT, "TIES_MAX": TIES_MAX,
        "HELD_SHARE_RTOL": HELD_SHARE_RTOL, "HELD_SHARE_BAND": HELD_SHARE_BAND,
        "MODEL_TIE_GAP": MODEL_TIE_GAP, "LAYER_TIE_GAP": LAYER_TIE_GAP},
        "whole_model": whole, "layers": layers, "step": step, "bias": bias,
        "prog_loss": m["prog_loss"], "prog_balance": m["prog_balance"],
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
        # the calls the trace holds of the plain flash kernels, recomputed ones
        # included, THEIR seconds and the least seconds those same calls could
        # take doing the USEFUL work. A CPU rehearsal interprets the kernels
        # into plain ops, so its trace holds none: the share of the roofline
        # then reads 0 over the window.
        took, least, kernel_calls = 0.0, 0.0, {}
        costs = flops_mla.attention_kernel_costs(model, sizes["batch"], seq)
        for kernel, (kernel_flops, kernel_bytes) in costs.items():
            pattern = rf'^%{kernel}(\.[\w.\-]+)? = .*custom_call_target="tpu_custom_call"'
            seconds, calls = trace_reduce.matching(summary["ops"], pattern)
            took += seconds
            least += calls * flops_mla.roofline_seconds(kernel_flops, kernel_bytes, peaks)
            # with one event's name as the trace printed it, for the readers' tests
            kernel_calls[kernel] = [calls, seconds, next(
                (name[:600] for name in summary["ops"] if re.search(pattern, name)), None)]
        flash = {"least_seconds": least, "seconds": took if took else summary["window_s"]}
        first, last = m["traced_steps"]
        # the grouped-matmul calls the trace holds and THEIR seconds, as
        # train_hybrid.py: a call's FLOPs are those of the rows the held
        # experts computed, from the traced steps' own count of them
        share = ctx.cell.readers.get("kernel.moe_gmm_share.train")
        gmm_s, gmm_calls = (trace_reduce.matching(summary["ops"], share["params"]["pattern"])
                            if share else (0.0, 0))
        held_share = stats.mean(counted["held_share"][first:last])
        rows_held = held_share * tokens_per_step * model["num_experts_per_tok"]
        ctx.say({"moe_gmm_calls": gmm_calls, "moe_gmm_seconds": gmm_s,
                 "rows_held_a_layer": rows_held, "kernel_calls": kernel_calls,
                 "kernel_families": {"flash": flash},
                 "forward_flops_by_part": flops_mla.forward_flops_by_part(model, seq)})
        obs = {"timers": {"data_wait_ms": window["data_wait_ms"],
                          "report_ms": window["report_ms"],
                          "step_ms_median": stats.percentile(m["step_ms"], 50)},
               # from the median step, not the window: the capture's own
               # start, stop and reduction sit inside a traced window
               "train": {"tok_s_chip": tokens_per_step / chips * 1e3
                         / stats.percentile(m["step_ms"], 50),
                         "peak_flops_per_s": peaks["bf16_flops_per_s"],
                         "flops_per_token": flops_mla.train_flops_per_token(model, seq)},
               "moe": {"load_max_over_mean": stats.mean(
                           counted["load_max_over_mean"][first:last]),
                       "held_share": held_share,
                       "gmm_flops_per_call": 2.0 * rows_held * model["hidden_size"]
                       * model["moe_intermediate_size"],
                       "gmm_calls": gmm_calls,
                       "gmm_seconds": gmm_s if gmm_calls else summary["window_s"]},
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

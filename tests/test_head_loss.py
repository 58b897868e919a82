"""The chunked head loss (``models/llama.py::loss_fn``, scope
``lm_head_loss``): its value and every gradient against a plain, unchunked
``log_softmax`` written here, and how often it multiplies over the
vocabulary: three products a chunk when differentiated (logits, hidden
rows' gradient, ``lm_head``'s gradient), one when not."""

import dataclasses
import re
import signal

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import PRESETS, init_params, loss_fn, param_axes
from ray_tpu.models.llama import forward_hidden
from ray_tpu.parallel import MeshConfig, create_mesh
from ray_tpu.parallel.sharding import shard_params

TIME_LIMIT_S = 120


@pytest.fixture(autouse=True)
def _time_limit():
    """Each case its own limit (no pytest-timeout here): the alarm fails
    the test that is running, not the ones after it."""
    def out_of_time(signum, frame):
        raise TimeoutError(f"over {TIME_LIMIT_S} s")

    was = signal.signal(signal.SIGALRM, out_of_time)
    signal.setitimer(signal.ITIMER_REAL, TIME_LIMIT_S)
    yield
    signal.setitimer(signal.ITIMER_REAL, 0)
    signal.signal(signal.SIGALRM, was)


def _config(preset, dtype=jnp.float32):
    return dataclasses.replace(PRESETS[preset], dtype=dtype, attn_impl="reference",
                               remat=True, remat_policy="attn")


def _fsdp4():
    return create_mesh(MeshConfig(fsdp=4), devices=jax.devices()[:4])


def plain_loss(params, batch, cfg, mesh=None):
    """The whole ``[B, S, vocab]`` float32 logits, ``log_softmax``, the
    masked mean; a routed model's auxiliary terms as ``loss_fn`` adds them."""
    tokens = batch["tokens"]
    hidden, aux = forward_hidden(params, tokens, cfg, mesh=mesh, return_aux=True)
    logits = jnp.einsum("bse,ev->bsv", hidden[:, :-1], params["lm_head"],
                        preferred_element_type=jnp.float32)
    logp = jax.nn.log_softmax(logits, axis=-1)
    ll = jnp.take_along_axis(logp, tokens[:, 1:, None], axis=-1)[..., 0]
    mask = batch.get("mask")
    mask = jnp.ones_like(ll) if mask is None else mask[:, 1:].astype(jnp.float32)
    loss = -(ll * mask).sum() / jnp.maximum(mask.sum(), 1.0)
    if aux:
        loss = loss + cfg.moe_aux_weight * aux["load_balance"] + cfg.moe_z_weight * aux["z"]
    return loss


# (preset, rows x tokens, chunk, mask with zeros, cotangent, mesh): n = rows
# x (tokens - 1) targets. 4 x 33 gives n = 128.
CASES = {
    "dense": ("debug-128", (4, 33), 32, False, 1.0, False),
    "routed": ("llama-moe-debug", (4, 33), 32, False, 1.0, False),
    "mask-with-zeros": ("debug-128", (4, 33), 32, True, 1.0, False),
    "routed-mask-with-zeros": ("llama-moe-debug", (4, 33), 32, True, 1.0, False),
    "n-not-a-multiple-of-the-chunk": ("debug-128", (3, 50), 32, True, 1.0, False),
    "chunk-larger-than-n": ("debug-128", (3, 50), 1000, False, 1.0, False),
    "one-chunk-exactly": ("debug-128", (4, 33), 128, False, 1.0, False),
    "cotangent-3": ("debug-128", (3, 50), 64, True, 3.0, False),
    "routed-cotangent-3": ("llama-moe-debug", (3, 50), 64, False, 3.0, False),
    "fsdp4-mesh": ("debug-128", (4, 33), 32, True, 1.0, True),
    "fsdp4-mesh-padded": ("debug-128", (4, 50), 64, False, 3.0, True),
}


def _batch(cfg, shape, zeros_in_mask):
    batch = {"tokens": jax.random.randint(jax.random.PRNGKey(1), shape, 0, cfg.vocab_size)}
    if zeros_in_mask:
        mask = jax.random.uniform(jax.random.PRNGKey(2), shape) > 0.3
        batch["mask"] = mask.at[0].set(False).astype(jnp.float32)  # a row of nothing
    return batch


@pytest.mark.parametrize("case", list(CASES))
def test_value_and_every_gradient_match_a_plain_log_softmax(case):
    preset, shape, chunk, zeros_in_mask, cotangent, on_mesh = CASES[case]
    cfg = _config(preset)
    mesh = _fsdp4() if on_mesh else None
    params = init_params(cfg, jax.random.PRNGKey(0))
    if mesh is not None:
        params = shard_params(params, param_axes(cfg), mesh)
    batch = _batch(cfg, shape, zeros_in_mask)

    def both(fn, **kw):
        return jax.jit(jax.value_and_grad(
            lambda p: cotangent * fn(p, batch, cfg, mesh=mesh, **kw)))(params)

    loss, grads = both(loss_fn, chunk_tokens=chunk)
    want, want_grads = both(plain_loss)
    np.testing.assert_allclose(float(loss), float(want), rtol=2e-6)
    # not differentiated: the same number from the primal function
    alone = jax.jit(lambda p: loss_fn(p, batch, cfg, mesh=mesh, chunk_tokens=chunk))(params)
    np.testing.assert_allclose(cotangent * float(alone), float(loss), rtol=1e-6)
    flat, _ = jax.tree_util.tree_flatten_with_path(grads)
    assert len(flat) == len(jax.tree.leaves(params))
    for (path, got), ref in zip(flat, jax.tree.leaves(want_grads)):
        assert got.shape == ref.shape and got.dtype == ref.dtype, path
        scale = float(jnp.abs(ref).max())
        assert scale > 0, path  # every parameter is reached, lm_head and the body's
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref), atol=2e-5 * scale,
                                   rtol=0, err_msg=jax.tree_util.keystr(path))
    if mesh is not None:
        assert grads["lm_head"].sharding.is_equivalent_to(
            params["lm_head"].sharding, 2)


@pytest.mark.parametrize("preset", ["debug-128", "llama-moe-debug"])
def test_bf16_gradients_stay_within_bf16_of_the_plain_ones(preset):
    """At the type the cells train in: ``dlogits`` enters both products in
    float32 and ``lm_head``'s gradient is summed over chunks in bf16, so
    what differs from the plain way is a bf16 rounding a chunk."""
    cfg = _config(preset, jnp.bfloat16)
    params = init_params(cfg, jax.random.PRNGKey(0))
    batch = _batch(cfg, (4, 65), True)
    loss, grads = jax.jit(jax.value_and_grad(
        lambda p: loss_fn(p, batch, cfg, chunk_tokens=64)))(params)
    want, want_grads = jax.jit(jax.value_and_grad(
        lambda p: plain_loss(p, batch, cfg)))(params)
    np.testing.assert_allclose(float(loss), float(want), rtol=1e-5)
    for name in ("lm_head", "final_norm", "embed"):
        got, ref = (np.asarray(g[name], np.float32) for g in (grads, want_grads))
        assert grads[name].dtype == params[name].dtype
        np.testing.assert_allclose(got, ref, atol=0.03 * np.abs(ref).max(), rtol=0,
                                   err_msg=name)


def _sub_jaxprs(eqn):
    for value in eqn.params.values():
        for x in value if isinstance(value, (list, tuple)) else (value,):
            if hasattr(x, "jaxpr") and hasattr(x, "consts"):
                yield x.jaxpr
            elif hasattr(x, "eqns"):
                yield x


def _walk(jaxpr, path=()):
    for eqn in jaxpr.eqns:
        yield path, eqn
        for sub in _sub_jaxprs(eqn):
            yield from _walk(sub, path + (eqn.primitive.name,))


def _vocab_products(fn, *args, vocab):
    """(path of enclosing primitives, operand types) of every
    ``dot_general`` with a vocabulary-sized dimension."""
    return [(path, tuple(v.aval.str_short(short_dtypes=True) for v in eqn.invars))
            for path, eqn in _walk(jax.make_jaxpr(fn)(*args).jaxpr)
            if eqn.primitive.name == "dot_general"
            and any(vocab in v.aval.shape for v in (*eqn.invars, *eqn.outvars))]


@pytest.mark.parametrize("preset,return_aux", [
    ("debug-128", False), ("llama-moe-debug", False), ("llama-moe-debug", True)],
    ids=["dense", "routed", "routed-aux"])
def test_three_vocabulary_products_differentiated_and_one_alone(preset, return_aux):
    cfg = dataclasses.replace(PRESETS[preset], remat=True, remat_policy="attn")
    assert cfg.vocab_size not in (cfg.hidden, 96)  # the count keys on the size
    params = jax.eval_shape(lambda: init_params(cfg, jax.random.PRNGKey(0)))
    batch = {"tokens": jax.ShapeDtypeStruct((4, 97), jnp.int32)}  # n = 384: 4 chunks of 96

    def loss(p, b):
        out = loss_fn(p, b, cfg, chunk_tokens=96, return_aux=return_aux)
        return out[0] if return_aux else out

    c, e, v = 96, cfg.hidden, cfg.vocab_size
    differentiated = _vocab_products(jax.grad(loss), params, batch, vocab=v)
    # one scan holds all three, none under a remat / checkpoint of the chunk;
    # dlogits goes into both gradient products in float32
    assert sorted(differentiated) == sorted([
        (("scan",), (f"bf16[{c},{e}]", f"bf16[{e},{v}]")),   # logits
        (("scan",), (f"f32[{c},{v}]", f"bf16[{e},{v}]")),    # the hidden rows' gradient
        (("scan",), (f"bf16[{c},{e}]", f"f32[{c},{v}]")),    # lm_head's gradient
    ])
    alone = _vocab_products(loss, params, batch, vocab=v)
    assert [ops for _, ops in alone] == [(f"bf16[{c},{e}]", f"bf16[{e},{v}]")]
    assert not any("remat" in p or "checkpoint" in p for path, _ in alone for p in path)
    # and with value_and_grad the loss comes out of the same pass
    assert len(_vocab_products(jax.value_and_grad(loss), params, batch, vocab=v)) == 3


def test_fsdp4_reduces_one_block_of_logits_a_chunk():
    """Under fsdp=4 ``lm_head`` is sharded over ``embed``, the contracted
    dimension of the logits, so each chunk's float32 ``[chunk, vocab]``
    block is all-reduced: once, where the rematerialized chunk reduced it
    in the forward pass and again in the backward pass. Both gradient
    products are local to a device."""
    cfg = dataclasses.replace(PRESETS["debug-128"], attn_impl="reference",
                              remat=True, remat_policy="full")
    mesh = _fsdp4()
    params = shard_params(init_params(cfg, jax.random.PRNGKey(0)), param_axes(cfg), mesh)
    batch = {"tokens": jnp.zeros((4, 97), jnp.int32)}
    chunk, v = 96, cfg.vocab_size
    # the loss too, as a train step wants it: without it the compiler drops
    # a forward pass that only a rematerialized backward pass repeats
    text = jax.jit(jax.value_and_grad(
        lambda p, b: loss_fn(p, b, cfg, mesh=mesh, chunk_tokens=chunk))).lower(
        params, batch).compile().as_text()
    blocks = [line for line in text.splitlines()
              if re.search(rf"= f32\[{chunk},{v}\]\S* all-reduce(-start)?\(", line)]
    assert len(blocks) == 1, blocks  # one instruction, in the one scan's body
    assert "lm_head_loss" in blocks[0]
    # nothing of the head is gathered whole onto a device
    e = cfg.hidden
    assert not re.search(rf"= \w+\[{e},{v}\]\S* all-gather", text)

"""``tracing.with_passes``: WHEN in the step an op runs (``rt_pass`` = ``fwd``,
``remat`` on a ``jax.checkpoint``'s second run, ``bwd``), in each op's own text
beside ``rt_scope``, and the program otherwise as it was. Each case's step is
lowered once through ``loss_fn`` and once with the helper switched off (the
module's ``texts``). The scopes: ``tests/test_device_scopes.py``; a capture's
table by pass: ``tests/benchmark_suite/test_bm_passes.py``."""

import collections
import dataclasses
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.observability import tracing
from ray_tpu.observability.tracing import device_scope, with_passes
from test_device_scopes import KINDS, _stripped, check_passes

# case -> (debug preset, changes): the two light kinds the scope tests lower
# under remat ``attn``, and the dense one with no remat and with every block run
# again. The hybrid and the sparse kind (half a minute a lowering) are held to
# the same two checks on the texts ``tests/test_device_scopes.py`` compiles
# anyway: its ``without`` is lowered with scopes AND passes switched off
CASES = {
    **{kind: (KINDS[kind][0], {"remat_policy": "attn", **KINDS[kind][2]})
       for kind in ("dense", "routed")},
    "dense-no-remat": ("debug-128", {"remat": False}),
    "dense-remat-full": ("debug-128", {"remat_policy": "full"}),
}


def _config(case):
    from ray_tpu.models.llama import PRESETS

    preset, changes = CASES[case]
    return dataclasses.replace(PRESETS[preset], dtype=jnp.float32, **changes)


def _compiled_step(case) -> str:
    from ray_tpu.models.llama import init_params, loss_fn

    cfg = _config(case)
    params = jax.eval_shape(lambda: init_params(cfg, jax.random.PRNGKey(0)))
    batch = {"tokens": jax.ShapeDtypeStruct((2, 64), jnp.int32)}
    return jax.jit(jax.grad(lambda p, b: loss_fn(p, b, cfg, chunk_tokens=32))
                   ).lower(params, batch).compile().as_text()


def _without_passes(monkeypatch):
    import ray_tpu.models.llama

    monkeypatch.setattr(ray_tpu.models.llama, "with_passes", lambda fn, has_aux=False: fn)
    jax.clear_caches()


@pytest.fixture(scope="module")
def texts():
    """(case, with the helper or without) -> the compiled text of the tiny
    step, compiled once a module."""
    kept = {}

    def get(case, marked=True):
        if (case, marked) not in kept:
            with pytest.MonkeyPatch.context() as patch:
                if not marked:
                    _without_passes(patch)
                kept[case, marked] = _compiled_step(case)
            jax.clear_caches()
        return kept[case, marked]

    return get


@pytest.mark.parametrize("case", list(CASES))
def test_every_scoped_op_carries_the_pass_its_op_name_says(texts, case):
    check_passes(texts(case), remat=case != "dense-no-remat")


@pytest.mark.parametrize("case", list(CASES))
def test_the_program_and_the_scopes_are_the_undecorated_functions(texts, case):
    marked, plain = texts(case), texts(case, marked=False)
    assert "rt_pass" not in plain and 'rt_scope="' in plain
    assert _stripped(marked) == _stripped(plain)
    assert marked.count("custom-call") == plain.count("custom-call")
    paths = lambda text: collections.Counter(re.findall(r'rt_scope="([^"]*)"', text))  # noqa: E731
    assert paths(marked) == paths(plain)


@pytest.mark.parametrize("case", ["dense", "routed"])
def test_value_aux_and_every_gradient_equal_the_undecorated_functions(monkeypatch, case):
    from ray_tpu.models.llama import init_params, loss_fn

    cfg = _config(case)
    params = init_params(cfg, jax.random.PRNGKey(0))
    batch = {"tokens": jax.random.randint(jax.random.PRNGKey(1), (2, 64), 0, cfg.vocab_size),
             "mask": (jax.random.uniform(jax.random.PRNGKey(2), (2, 64)) > 0.2
                      ).astype(jnp.float32)}
    step = lambda: jax.jit(jax.value_and_grad(  # noqa: E731
        lambda p, b: loss_fn(p, b, cfg, chunk_tokens=32, return_aux=True), has_aux=True)
    )(params, batch)
    (loss, aux), grads = step()
    _without_passes(monkeypatch)
    (want, want_aux), want_grads = step()
    assert float(loss) == float(want)
    assert jax.tree.structure(aux) == jax.tree.structure(want_aux)
    if case == "routed":  # integer counters among it, from the forward pass
        assert any(jnp.issubdtype(x.dtype, jnp.integer) for x in jax.tree.leaves(aux))
    for got, ref in zip(jax.tree.leaves((aux, grads)), jax.tree.leaves((want_aux, want_grads))):
        assert got.dtype == ref.dtype
        np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))


def test_a_call_that_is_not_differentiated_holds_the_forward_pass_only():
    from ray_tpu.models.llama import init_params, loss_fn

    cfg = _config("dense")
    params = jax.eval_shape(lambda: init_params(cfg, jax.random.PRNGKey(0)))
    batch = {"tokens": jax.ShapeDtypeStruct((2, 64), jnp.int32)}
    text = jax.jit(lambda p, b: loss_fn(p, b, cfg, chunk_tokens=32)).lower(params, batch).as_text()
    assert set(re.findall(r'rt_pass = "([^"]*)"', text)) == {"fwd"}
    # every op a scope issued, and the loss's own sums outside any
    assert 'rt_scope = "' not in re.sub(r'rt_pass = "fwd", rt_scope = "[^"]*"', "", text)
    assert re.search(r'\{rt_pass = "fwd"\}', text)


def test_the_fsdp_step_lowers_on_four_devices_and_is_the_undecorated_program(monkeypatch):
    from ray_tpu.models.llama import init_params, loss_fn, param_axes
    from ray_tpu.parallel import MeshConfig, create_mesh
    from ray_tpu.parallel.sharding import logical_sharding, sharding_tree

    cfg = _config("dense")
    mesh = create_mesh(MeshConfig(fsdp=4), devices=jax.devices()[:4])
    shapes = jax.eval_shape(lambda: init_params(cfg, jax.random.PRNGKey(0)))
    params = jax.tree.map(lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh),
                          shapes, sharding_tree(param_axes(cfg), mesh))
    batch = {"tokens": jax.ShapeDtypeStruct((4, 64), jnp.int32,
                                            sharding=logical_sharding(mesh, ("batch", None)))}
    lower = lambda: jax.jit(jax.grad(  # noqa: E731
        lambda p, b: loss_fn(p, b, cfg, mesh=mesh, chunk_tokens=32))
    ).lower(params, batch).compile().as_text()
    marked = lower()
    _without_passes(monkeypatch)
    plain = lower()
    assert set(re.findall(r'rt_pass="([^"]*)"', marked)) == {"fwd", "remat", "bwd"}
    assert "rt_pass" not in plain and _stripped(marked) == _stripped(plain)
    assert "all-gather" in marked or "all-reduce" in marked  # it is partitioned
    # the ops of the per-shard flash call, inside a shard_map's body
    assert re.search(r'rt_pass="(remat|bwd)",rt_scope="stack/attn"', marked)


# --------------------------------------------------- the helper on its own
def _toy(w, x, flag):
    """A scan over a checkpointed block that holds a jit, a cond and a
    custom_vjp; returns (value, aux with an integer)."""

    @jax.custom_vjp
    def square(v):
        return v * v

    square.defvjp(lambda v: (v * v, v), lambda v, g: (2.0 * v * g + jnp.cos(g) * 0.0,))

    def block(c, wi):
        with device_scope("mlp"):
            c = jax.jit(jnp.tanh)(c @ wi)
            c = jax.lax.cond(flag, jnp.sin, jnp.cos, c)
        with device_scope("attn"):
            return square(c) + jnp.exp(c)

    def body(c, wi):
        return jax.checkpoint(block)(c, wi), None

    with device_scope("stack"):
        c, _ = jax.lax.scan(body, x, w)
    return jnp.sum(c), {"count": jnp.int32(3), "mean": c.mean()}


def _by_op(text):
    """op -> the set of (rt_pass, rt_scope) it is lowered under."""
    out = collections.defaultdict(set)
    for m in re.finditer(r'stablehlo\.(\w+)[^\n]*?rt_pass = "(\w+)"(?:, rt_scope = "([^"]*)")?',
                         text):
        out[m.group(1)].add((m.group(2), m.group(3)))
    return out


def test_the_walk_reaches_scan_checkpoint_jit_cond_and_a_backward_rule():
    args = (jnp.full((3, 8, 8), 0.1), jnp.ones((4, 8)), jnp.bool_(True))
    marked = jax.value_and_grad(with_passes(_toy, has_aux=True), has_aux=True)
    (value, aux), grad = jax.jit(marked)(*args)
    (want, want_aux), want_grad = jax.jit(jax.value_and_grad(_toy, has_aux=True))(*args)
    assert float(value) == float(want) and int(aux["count"]) == 3
    assert float(aux["mean"]) == float(want_aux["mean"])
    np.testing.assert_array_equal(np.asarray(grad), np.asarray(want_grad))
    ops = _by_op(jax.jit(marked).lower(*args).as_text())
    # the block's product: forward, run again, and two products of the backward
    assert ops["dot_general"] == {("fwd", "stack/mlp"), ("remat", "stack/mlp"),
                                  ("bwd", "stack/mlp")}
    assert ops["tanh"] == {("fwd", "stack/mlp"), ("remat", "stack/mlp")}      # inside the jit
    assert ("remat", "stack/mlp") in ops["sine"] | ops["cosine"]               # inside the cond
    assert ("remat", "stack/attn") in ops["exponential"]
    assert ("bwd", "stack/attn") in ops["cosine"]  # the rule's own op, under the CALL's scope
    assert ops["while"] == {("fwd", "stack"), ("bwd", "stack")}
    assert ("fwd", None) in ops["reduce"] and "remat" not in {p for p, _ in ops["reduce"]}


def test_what_is_not_differentiated_gets_a_zero_and_the_first_argument_a_gradient():
    fn = with_passes(lambda w, x, n: jnp.sum(jnp.sin(w) * x) * n)
    w, x = jnp.arange(4.0), jnp.arange(4.0) + 1.0
    np.testing.assert_allclose(jax.grad(fn)(w, x, 2), 2 * jnp.cos(w) * x, rtol=1e-6)
    # ``rest`` takes no cotangent: integer tokens and a float mask alike
    assert float(jnp.abs(jax.grad(fn, argnums=1)(w, x, 2)).max()) == 0.0
    assert fn.__name__ == "<lambda>" and float(fn(w, x, 2)) == float(jnp.sum(jnp.sin(w) * x) * 2)


def test_a_cotangent_for_aux_is_refused_aloud():
    """``aux`` comes from the forward pass, as under ``value_and_grad(has_aux=True)``;
    a silent zero for its gradient would be a wrong number."""
    fn = with_passes(lambda w: (jnp.sum(w ** 2), {"mean": w.mean(), "n": jnp.int32(2)}),
                     has_aux=True)
    w = jnp.arange(3.0)
    (value, aux), grad = jax.value_and_grad(fn, has_aux=True)(w)
    assert float(value) == 5.0 and float(aux["mean"]) == 1.0 and int(aux["n"]) == 2
    np.testing.assert_array_equal(np.asarray(grad), 2 * np.arange(3.0))
    with pytest.raises(TypeError, match="aux was given a cotangent"):
        jax.grad(lambda w: fn(w)[1]["mean"])(w)
    # a loss that nothing reads gets a zero, not an error
    np.testing.assert_array_equal(np.asarray(jax.grad(lambda w: 0.0 * fn(w)[0] + w.sum())(w)),
                                  np.ones(3))


def test_a_gradient_of_the_gradient_passes_through():
    """Pinned, not promised: the backward rule evaluates a jaxpr, which jax
    differentiates like any other code."""
    fn = with_passes(lambda w: jnp.sum(jnp.sin(w) ** 2))
    plain = lambda w: jnp.sum(jnp.sin(w) ** 2)  # noqa: E731
    w = jnp.arange(3.0)
    second = jax.jit(jax.grad(lambda w: jnp.sum(jax.grad(fn)(w) ** 2)))
    np.testing.assert_allclose(second(w), jax.grad(lambda w: jnp.sum(jax.grad(plain)(w) ** 2))(w),
                               rtol=1e-5)


def test_a_pallas_kernels_body_is_left_alone():
    from jax.experimental import pallas as pl

    def kernel(x_ref, o_ref):
        o_ref[...] = x_ref[...] * 2.0

    @jax.custom_vjp
    def double(x):
        return pl.pallas_call(kernel, out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
                              interpret=True)(x)

    double.defvjp(lambda x: (double(x), None), lambda _, g: (2.0 * g,))
    fn = with_passes(lambda w: jnp.sum(jax.checkpoint(lambda v: jnp.sin(double(v)))(w)))
    w = jnp.arange(8.0)
    np.testing.assert_allclose(jax.jit(jax.grad(fn))(w), 2 * jnp.cos(2 * w), rtol=1e-6)

    def calls(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                yield eqn
                continue
            for param in eqn.params.values():
                for sub in param if isinstance(param, (tuple, list)) else (param,):
                    if hasattr(sub, "eqns"):
                        yield from calls(getattr(sub, "jaxpr", sub))

    found = list(calls(jax.make_jaxpr(jax.grad(fn))(w).jaxpr))
    # the call itself runs in the forward pass and again under the checkpoint;
    # the kernel's own eqns stay as they were traced
    assert [e.ctx.xla_metadata["rt_pass"] for e in found] == ["fwd", "remat"]
    for eqn in found:
        body = eqn.params["jaxpr"].eqns
        assert body and {e.ctx.xla_metadata["rt_pass"] for e in body} == {"fwd"}


def test_the_private_surface_the_walk_relies_on():
    """A jax upgrade that moves one of these fails HERE: ``with_passes`` reads
    ``eqn.source_info.name_stack``, rewrites ``eqn.ctx.xla_metadata`` and
    evaluates with ``jax._src.core.eval_jaxpr``."""
    from jax._src import core
    from jax.experimental.xla_metadata import set_xla_metadata

    # 1. an eqn's own metadata is merged OVER the context open when it is
    # evaluated (why a context around the backward pass cannot mark it)
    with set_xla_metadata(rt_probe="eqn"):
        closed = jax.make_jaxpr(jnp.sin)(1.0)
    assert closed.eqns[0].ctx.xla_metadata == {"rt_probe": "eqn"}, (
        "JaxprEqnContext no longer keeps the xla_metadata open at trace time")

    def again(x):
        with set_xla_metadata(rt_probe="context", rt_other="kept"):
            return core.eval_jaxpr(closed.jaxpr, closed.consts, x)[0]

    text = jax.jit(again).lower(1.0).as_text()
    assert 'rt_probe = "eqn"' in text and 'rt_other = "kept"' in text, (
        "eval_jaxpr no longer merges an eqn's xla_metadata over the open context")
    # 2. a checkpoint's second run, and nothing else, is under this name
    grad = jax.make_jaxpr(jax.grad(lambda x: jnp.sum(jax.checkpoint(jnp.sin)(x))))(jnp.ones(3))
    inner = [e for e in grad.eqns if "jaxpr" in e.params]
    assert inner, "jax.checkpoint's transpose is no longer one eqn that holds a jaxpr"
    stacks = {e.primitive.name: str(e.source_info.name_stack)
              for e in inner[-1].params["jaxpr"].eqns}
    assert tracing._REMAT_STACK in stacks["cos"], (
        f"no eqn of a checkpoint's second run is under {tracing._REMAT_STACK!r}: {stacks}")
    assert tracing._REMAT_STACK not in stacks["mul"], stacks
    # 3. a context object is copied and given new metadata, the rest kept
    assert {"compute_type", "threefry_partitionable", "xla_metadata", "cur_abstract_mesh"} <= set(
        core.JaxprEqnContext.__slots__)

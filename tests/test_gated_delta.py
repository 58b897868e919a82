"""The chunked gated delta rule (``ops/gated_delta.py``), in plain ``jnp``
and as its four Pallas kernels (interpreted here: ``gdn_wy_fwd`` /
``gdn_wy_bwd`` for the chunk-local half, ``gdn_fwd`` / ``gdn_bwd`` for the
recurrence), against the rule token by token: the output and every
gradient, at rows of 1, 3 and 5 chunks, with decays near 0 (nothing
crosses a chunk) and near 1 (everything does). The chunk-local pair alone
is held to ``_prepare``, the plain ``jnp`` it replaces."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.ops import gated_delta as gd
from ray_tpu.ops import trace_log

IMPLS = {"jnp": gd.chunked_jnp,
         "kernels": functools.partial(gd.gated_delta_rule, interpret=True)}
# mean log decay a step: e^-20 forgets within a position, e^-0.001 keeps
# 94% of the state over a whole chunk
DECAYS = {"near-0": 20.0, "middle": 1.0, "near-1": 1e-3}


def token_by_token(q, k, v, g, beta):
    """S = e^g S; d = beta (v - S^T k); S += k d^T; o = S^T q, a head at a time."""
    def head(q, k, v, g, beta):
        def step(state, x):
            q_t, k_t, v_t, g_t, b_t = x
            state = jnp.exp(g_t) * state
            state = state + jnp.outer(k_t, b_t * (v_t - state.T @ k_t))
            return state, state.T @ q_t

        zero = jnp.zeros((q.shape[-1], v.shape[-1]), jnp.float32)
        return jax.lax.scan(step, zero, (q, k, v, g, beta))[1]

    return jax.vmap(jax.vmap(head))(q, k, v, g, beta)


def operands(t, scale, *, dk=32, dv=32, b=1, h=2, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    unit = lambda x: x / jnp.linalg.norm(x, axis=-1, keepdims=True)  # noqa: E731
    q = unit(jax.random.normal(ks[0], (b, h, t, dk))) * dk ** -0.5
    k = unit(jax.random.normal(ks[1], (b, h, t, dk)))
    v = jax.random.normal(ks[2], (b, h, t, dv))
    g = -scale * jax.nn.softplus(jax.random.normal(ks[3], (b, h, t)))
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (b, h, t)))
    return q, k, v, g, beta


def value_and_grads(fn, xs):
    """The output, and a random cotangent pulled back to every operand: one
    pass forward and one back."""
    ct = jax.random.normal(jax.random.PRNGKey(9), xs[2].shape)
    with jax.default_matmul_precision("highest"):
        out, pull_back = jax.vjp(fn, *xs)
        return (out,) + pull_back(ct)


@functools.lru_cache(maxsize=None)
def the_rules(chunks, decay):
    """The operands of a case and the rule's own output and gradients on
    them, made once for both implementations."""
    xs = operands(chunks * gd.CHUNK, DECAYS[decay])
    return xs, value_and_grads(token_by_token, xs)


@pytest.mark.parametrize("decay", list(DECAYS))
@pytest.mark.parametrize("chunks", [1, 3, 5])
@pytest.mark.parametrize("impl", list(IMPLS))
def test_output_and_every_gradient_match_the_rule_token_by_token(impl, chunks, decay):
    xs, want = the_rules(chunks, decay)
    got = value_and_grads(IMPLS[impl], xs)
    for name, a, b in zip(("o", "dq", "dk", "dv", "dg", "dbeta"), got, want):
        scale = float(jnp.abs(b).max())
        np.testing.assert_allclose(a, b, atol=1e-4 * scale, rtol=0, err_msg=name)


OPERANDS = ("qg", "kd", "w", "u", "aqk", "a")
GRADIENTS = ("dq", "dk", "dv", "dg", "dbeta")


@pytest.mark.parametrize("decay", list(DECAYS))
@pytest.mark.parametrize("chunks", [1, 3, 5])
@pytest.mark.parametrize("side", ["operands", "gradients"])
def test_the_chunk_local_kernels_make_what_prepare_makes(side, chunks, decay):
    """``gdn_wy_fwd``'s six operands against ``_prepare``'s, and
    ``gdn_wy_bwd`` alone: a random cotangent on each operand pulled back
    through the pair and through ``jax.vjp`` of ``_prepare``. Within 5e-5 of
    an array's largest entry (the pair's inverse runs three bf16 passes
    here too, ``_prepare``'s on the CPU whole float32 products); g's
    gradient within 5e-4: under the decay near 0 gamma reaches -1,300 and a
    float32 running sum is good to 1e-4 there, whatever order it takes."""
    xs = operands(chunks * gd.CHUNK, DECAYS[decay])
    pair = gd._make_wy(True)
    with jax.default_matmul_precision("highest"):
        want, pull_back = jax.vjp(gd._prepare, *xs)
    if side == "operands":
        names, got = OPERANDS, pair(*xs)
    else:
        cts = tuple(jax.random.normal(jax.random.PRNGKey(i), w.shape)
                    for i, w in enumerate(want))
        with jax.default_matmul_precision("highest"):
            names, want = GRADIENTS, pull_back(cts)
        got = jax.vjp(pair, *xs)[1](cts)
    for name, a, b in zip(names, got, want):
        assert a.shape == b.shape and a.dtype == b.dtype, name
        tol = 5e-4 if name == "dg" else 5e-5
        np.testing.assert_allclose(a, b, atol=tol * float(jnp.abs(b).max()), rtol=0,
                                   err_msg=name)


def test_state_crosses_chunk_boundaries_only_where_the_decay_lets_it():
    """Zeroing the first chunk's values changes later chunks' output when
    the decay is near 1 and leaves it alone when it is near 0: the
    comparison above would not see a broken inter-chunk recurrence under a
    decay that forgets everything."""
    for decay, moved in (("near-1", True), ("near-0", False)):
        q, k, v, g, beta = operands(3 * gd.CHUNK, DECAYS[decay])
        cut = v.at[:, :, :gd.CHUNK].set(0.0)
        a = IMPLS["kernels"](q, k, v, g, beta)[:, :, gd.CHUNK:]
        b = IMPLS["kernels"](q, k, cut, g, beta)[:, :, gd.CHUNK:]
        assert bool(jnp.abs(a - b).max() > 1e-3) is moved, decay


def test_a_bfloat16_state_reads_far_from_the_rule_and_a_float32_one_does_not():
    """What the benchmark's SCAN_RTOL separates, on bf16-valued q, k and v
    with the output left in float32 (a bf16 output's own rounding, 0.16%,
    would hide it): the state in float32 against the state rounded to
    bfloat16 after every chunk."""
    xs = operands(16 * gd.CHUNK, 0.02, dk=64, dv=64)
    xs = tuple(x.astype(jnp.bfloat16).astype(jnp.float32) for x in xs[:3]) + xs[3:]
    want = token_by_token(*xs)
    rel = lambda o: float(jnp.sqrt(jnp.mean((o - want) ** 2) / jnp.mean(want ** 2)))  # noqa: E731
    exact = rel(IMPLS["kernels"](*xs))
    rounded = rel(gd.chunked_jnp(*xs, state_dtype=jnp.bfloat16))
    assert exact < 1e-5 and rounded > 6e-4, (exact, rounded)


def test_rows_must_be_whole_chunks_and_the_kernels_record_their_cost():
    with pytest.raises(ValueError, match="multiple of 64"):
        gd.gated_delta_rule(*operands(100, 1.0))
    b, h, t, d = 1, 2, 128, 32
    gd.gated_delta_rule(*operands(t, 1.0), interpret=True)
    costs, rows = trace_log.kernel_costs(), b * h * t
    assert costs["gdn_fwd"]["flops"] == 3 * 2 * rows * d * d + 2 * rows * gd.CHUNK * d
    assert costs["gdn_bwd"]["flops"] == 10 * 2 * rows * d * d + 3 * 2 * rows * gd.CHUNK * d
    operand_bytes = rows * (4 * d + gd.CHUNK) * 4 + rows // gd.CHUNK * d * 4
    assert costs["gdn_fwd"]["bytes"] == operand_bytes + rows * d * 4
    assert costs["gdn_bwd"]["bytes"] == 3 * operand_bytes + rows * d * 4
    assert trace_log.kernel_traces()["gdn:interpret"] >= 1
    # the chunk-local pair: K K^T and Q K^T, the inverse's ten products, W and
    # U forward; those again, dT, dKb and dVb, dA's two products and four
    # products into dK and dQ backward
    c = gd.CHUNK
    forward = 2 * 2 * rows * c * d + 10 * 2 * rows * c * c + 2 * rows * c * 2 * d
    assert costs["gdn_wy_fwd"]["flops"] == forward
    assert costs["gdn_wy_bwd"]["flops"] == (
        forward + 2 * rows * c * 2 * d + 2 * 2 * rows * c * c + 4 * 2 * rows * c * d)
    rule_bytes = rows * (3 * d * 4 + 2 * 4)             # q, k, v, g, beta
    six_bytes = rows * (4 * d + c) * 4 + rows // c * 4
    assert costs["gdn_wy_fwd"]["bytes"] == rule_bytes + six_bytes
    assert costs["gdn_wy_bwd"]["bytes"] == 2 * rule_bytes + six_bytes
    assert trace_log.kernel_traces()["gdn_wy:interpret"] >= 1


def test_the_inverse_of_a_unit_lower_triangle_is_exact():
    a = jnp.tril(jax.random.normal(jax.random.PRNGKey(0), (3, 64, 64)), -1) * 0.3
    with jax.default_matmul_precision("highest"):
        got = gd._inverse_unit_lower(a)
        eye = jnp.eye(64)
        np.testing.assert_allclose(got @ (eye + a), jnp.broadcast_to(eye, a.shape), atol=2e-4)

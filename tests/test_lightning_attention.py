"""The lightning-attention kernels (Pallas interpreted on the CPU) against the
recurrence written position by position (``lightning_scan``)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.ops import trace_log
from ray_tpu.ops.lightning_attention import (CHUNK, kernel_costs, lightning_attention,
                                             lightning_scan)


def _inputs(seed, b, h, t, d, dtype):
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    q, k, v, g = (jax.random.normal(kk, (b, h, t, d), jnp.float32) for kk in ks)
    # slopes as the family's: a steep head whose lam^C underflows, a flat one
    log_decay = -jnp.asarray([0.84, 0.25, 0.004, 1e-5][:h], jnp.float32)
    return q.astype(dtype), k.astype(dtype), v.astype(dtype), g.astype(dtype), log_decay


def _err(a, b):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return np.linalg.norm(a - b) / np.linalg.norm(b)


@pytest.mark.parametrize("t", [CHUNK, 300, 5 * CHUNK, 8 * CHUNK])
@pytest.mark.parametrize("dtype,limit", [(jnp.float32, 1e-5), (jnp.bfloat16, 4e-3)])
def test_the_kernel_equals_the_scan_at_lengths_a_chunk_divides_and_not(t, dtype, limit):
    q, k, v, _, ld = _inputs(0, 2, 4, t, 32, dtype)
    o = lightning_attention(q, k, v, ld, scale=32 ** -0.5)
    want = lightning_scan(q, k, v, ld, scale=32 ** -0.5)
    assert o.shape == v.shape and o.dtype == v.dtype
    assert _err(o, want) < limit


@pytest.mark.parametrize("t", [300, 4 * CHUNK])
@pytest.mark.parametrize("dtype,limit", [(jnp.float32, 1e-5), (jnp.bfloat16, 6e-3)])
def test_the_gradients_equal_the_scans(t, dtype, limit):
    q, k, v, g, ld = _inputs(1, 1, 4, t, 32, dtype)
    f32 = lambda x: x.astype(jnp.float32)  # noqa: E731
    loss = lambda fn: lambda q, k, v: jnp.sum(  # noqa: E731
        f32(fn(q, k, v, ld, scale=0.25)) * f32(g))
    got = jax.grad(loss(lightning_attention), argnums=(0, 1, 2))(q, k, v)
    want = jax.grad(loss(lightning_scan), argnums=(0, 1, 2))(f32(q), f32(k), f32(v))
    for a, b, name in zip(got, want, "qkv"):
        assert a.dtype == q.dtype
        assert _err(a, b) < limit, name


def test_the_decay_gets_a_zero_gradient_and_a_head_takes_its_own():
    q, k, v, _, ld = _inputs(2, 1, 4, 2 * CHUNK, 16, jnp.float32)
    d_ld = jax.grad(lambda ld: jnp.sum(lightning_attention(q, k, v, ld)))(ld)
    assert not np.any(np.asarray(d_ld))
    # the heads' decays swapped: another output (head 0 forgets, head 3 does not)
    swapped = lightning_attention(q, k, v, ld[::-1])
    assert _err(swapped, lightning_scan(q, k, v, ld)) > 0.1
    # no decay at all is plain causal linear attention
    plain = lightning_attention(q, k, v, jnp.zeros_like(ld))
    scores = jnp.tril(jnp.einsum("bhid,bhjd->bhij", q, k, precision="highest"))
    assert _err(plain, jnp.einsum("bhij,bhjd->bhid", scores, v, precision="highest")) < 1e-5


def test_a_bfloat16_state_is_farther_from_the_rule_than_the_kernel():
    q, k, v, _, ld = _inputs(3, 1, 4, 8 * CHUNK, 32, jnp.bfloat16)
    want = lightning_scan(q, k, v, ld)
    kernel = _err(lightning_attention(q, k, v, ld), want)
    coarse = _err(lightning_scan(q, k, v, ld, state_dtype=jnp.bfloat16), want)
    assert kernel < 4e-3 < 3 * 4e-3 < coarse


def test_a_trace_records_both_kernels_costs_as_the_count_gives_them():
    q, k, v, g, ld = _inputs(4, 1, 2, 3 * CHUNK, 16, jnp.bfloat16)
    jax.grad(lambda q: jnp.sum(lightning_attention(q, k, v, ld).astype(jnp.float32)))(q)
    costs, want = trace_log.kernel_costs(), kernel_costs(1, 2, 3 * CHUNK, 16, 16, 2)
    for name in ("lightning_fwd", "lightning_bwd"):
        assert (costs[name]["flops"], costs[name]["bytes"]) == want[name]
    assert any(key.startswith("lightning:") for key in trace_log.kernel_traces())

"""The state-space scan's kernels (Pallas interpreted on the CPU) against the
recurrence written position by position (``ssd_scan``)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.ops import trace_log
from ray_tpu.ops.ssd import bwd_group_tiles, heads_a_tile, kernel_costs, ssd, ssd_scan

CHUNK = 16


def _inputs(seed, b, h, t, p, n, dtype, steep=1.0):
    r = heads_a_tile(p)
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    normal = lambda k, shape: jax.random.normal(k, shape, jnp.float32)  # noqa: E731
    x, g = (normal(k, (b, h // r, t, r * p)).astype(dtype) for k in ks[:2])
    bm, cm = (normal(k, (b, t, n)).astype(dtype) for k in ks[2:4])
    dt = jax.nn.softplus(normal(ks[4], (b, h, t)) - 2.0)
    # rates as the published start's: head h forgets at h + 1; ``steep`` x 200
    # underflows a chunk's decay (e^-400 and beyond)
    a = -steep * jnp.arange(1, h + 1, dtype=jnp.float32)
    d = normal(ks[5], (h,))
    return (x, dt, a, bm, cm, d), g


def _err(a, b):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return np.linalg.norm(a - b) / np.linalg.norm(b)


def _both(args, g, dtype):
    """(y, the six gradients) of the kernels and of the scan under the cotangent g."""
    f32 = lambda v: v.astype(jnp.float32)  # noqa: E731
    loss = lambda fn: lambda *a: jnp.sum(f32(fn(*a)) * f32(g))  # noqa: E731
    run = lambda *a: ssd(*a, chunk=CHUNK)  # noqa: E731
    got = jax.jit(lambda *a: (run(*a), jax.grad(loss(run), argnums=range(6))(*a)))(*args)
    exact = tuple(f32(v) for v in args)
    want = jax.jit(lambda *a: (ssd_scan(*a), jax.grad(loss(ssd_scan), argnums=range(6))(*a)))(
        *exact)
    return got, want


@pytest.mark.parametrize("t,p,steep",
                         [(4 * CHUNK, 32, 1.0), (50, 64, 1.0), (3 * CHUNK, 128, 200.0)],
                         ids=["whole-chunks-4-a-tile", "odd-length-2-a-tile", "underflow-1-a-tile"])
@pytest.mark.parametrize("dtype,limit", [(jnp.float32, 5e-6), (jnp.bfloat16, 4e-3)])
def test_the_kernels_equal_the_scan_forward_and_in_every_gradient(t, p, steep, dtype, limit):
    args, g = _inputs(0, 2, 4, t, p, 16, dtype, steep)
    # rates of -200 to -800: dt's gradient is a difference of terms 200-800 times its size
    limit *= 10 if steep > 1 else 1
    (y, grads), (want, want_grads) = _both(args, g, dtype)
    assert y.shape == args[0].shape and y.dtype == dtype
    assert np.isfinite(np.asarray(y, np.float32)).all()
    assert _err(y, want) < limit
    for got, exact, name, arg in zip(grads, want_grads, ("x", "dt", "a", "B", "C", "D"), args):
        assert got.dtype == arg.dtype and got.shape == arg.shape
        if name == "a" and steep > 1:
            # a head that forgets within a position leaves its rate next to no
            # gradient: a sum of dt's terms that cancel, judged on their scale
            assert np.abs(np.asarray(got - exact)).max() < limit * np.abs(want_grads[1]).max()
            continue
        assert _err(got, exact) < limit, name


def test_no_decay_and_no_skip_is_causal_linear_attention_and_the_skip_adds_d_x():
    (x, dt, a, bm, cm, d), _ = _inputs(1, 1, 4, 2 * CHUNK, 32, 16, jnp.float32)
    still = ssd(x, dt, jnp.zeros_like(a), bm, cm, jnp.zeros_like(d), chunk=CHUNK)
    heads = x.reshape(1, 1, -1, 4, 32)[:, 0]                          # [B, T, H, P]
    scores = jnp.tril(jnp.einsum("bin,bjn->bij", cm, bm, precision="highest"))
    want = jnp.einsum("bij,bjh,bjhp->bihp", scores, dt.swapaxes(1, 2), heads, precision="highest")
    assert _err(still.reshape(1, -1, 4, 32), want) < 1e-5
    skipped = ssd(x, dt, jnp.zeros_like(a), bm, cm, d, chunk=CHUNK)
    assert _err(skipped - still, jnp.repeat(d, 32) * x) < 1e-5


def test_a_bfloat16_state_is_farther_from_the_rule_than_the_kernels():
    args, _ = _inputs(2, 1, 4, 8 * CHUNK, 32, 16, jnp.bfloat16)
    args = (*args[:5], jnp.zeros_like(args[5]))     # the state's part alone: no skip
    want = ssd_scan(*args)
    kernel = _err(ssd(*args, chunk=CHUNK, out_dtype=jnp.float32), want)
    coarse = _err(ssd_scan(*args, state_dtype=jnp.bfloat16), want)
    assert kernel < 1e-5 < 2e-3 < coarse


def test_a_trace_records_both_kernels_costs_and_the_backward_groups_fit():
    args, g = _inputs(3, 1, 4, 3 * CHUNK, 32, 16, jnp.bfloat16)
    jax.grad(lambda x: jnp.sum(ssd(x, *args[1:], chunk=CHUNK).astype(jnp.float32)))(args[0])
    costs, want = trace_log.kernel_costs(), kernel_costs(1, 4, 3 * CHUNK, 32, 16, CHUNK, 2)
    for name in ("ssd_fwd", "ssd_bwd"):
        assert (costs[name]["flops"], costs[name]["bytes"]) == want[name]
    assert any(key.startswith("ssd:") for key in trace_log.kernel_traces())
    # granite's 64 heads of 64 over 128 chunks of 256: 4 tiles (8 heads) a group,
    # 32 MB of states; a quarter of the row lets 16 tiles in; heads of 48 fit no tile
    assert bwd_group_tiles(32, 2, 128, 128, 128) == 4
    assert bwd_group_tiles(32, 2, 32, 128, 128) == 16
    with pytest.raises(ValueError, match="lane tile"):
        heads_a_tile(48)

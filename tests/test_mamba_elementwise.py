"""``ops/mamba_elementwise.py``'s kernels, interpreted on the CPU, against the
plain functions ``models/mamba2.py`` keeps for every backend but the chip:
values and every gradient, across tile and chunk boundaries, and the whole
mixer down the kernel path."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import mamba2
from ray_tpu.models.llama import PRESETS, init_params
from ray_tpu.ops import conv_tiles
from ray_tpu.ops import mamba_elementwise as me
from ray_tpu.ops.ssd import ssd_scan
from ray_tpu.ops.trace_log import kernel_costs, kernel_traces

W = 128
# (tile rows, chunk rows, positions): one tile of one chunk; one tile of two
# chunks; three tiles of two chunks each; rows that end inside a tile (320 =
# 5 x 64, so a tile is 64 rows) and inside a chunk (a tile of 192 rows walked
# in three chunks of 64)
ROWS = {"one-tile": (128, 128, 128), "two-chunks": (128, 64, 128), "three-tiles": (128, 64, 384),
        "off-tile": (128, 64, 320), "off-chunk": (256, 128, 192)}


@pytest.fixture
def tiles(monkeypatch):
    """Small tiles, so that a short sequence crosses them."""
    def set_rows(name):
        tile, chunk, positions = ROWS[name]
        monkeypatch.setattr(me, "TILE_ROWS", tile)
        monkeypatch.setattr(conv_tiles, "CHUNK_ROWS", chunk)
        return positions
    return set_rows


def _draw(shape, dtype, seed, scale=1.0):
    return (scale * jax.random.normal(jax.random.PRNGKey(seed), shape, jnp.float32)).astype(dtype)


def _close(got, want, dtype, what):
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    assert got.shape == want.shape, what
    scale = float(np.abs(want).max())
    assert scale > 0, what
    # bfloat16: both sides round a float32 value that may differ in its last
    # bits, so one rounding step apart at the most
    tol = 5e-6 if dtype == jnp.float32 else 2.0 ** -7
    np.testing.assert_allclose(got, want, atol=tol * scale, rtol=0, err_msg=what)


def _plain_conv(x, taps, bias):
    return jax.nn.silu(mamba2.causal_conv(x, taps, bias)).astype(x.dtype)


def _kernel_conv(x, taps, bias):
    return me.conv_silu(x, taps, bias, interpret=True)


def _conv_operands(batch, channels, positions, dtype, seed=0):
    return (_draw((batch, channels, positions, W), dtype, seed),
            _draw((me.TAPS, channels, W), dtype, seed + 1, 0.5),
            _draw((channels, W), dtype, seed + 2, 0.5))


# a case compiles one program a side on the CPU (seconds each), so batch and
# type go together
@pytest.mark.parametrize("batch,dtype", [(1, jnp.float32), (2, jnp.bfloat16)],
                         ids=["1-f32", "2-bf16"])
@pytest.mark.parametrize("rows", list(ROWS))
@pytest.mark.parametrize("channels", [2, 32])
def test_conv_silu_matches_the_plain_functions(tiles, channels, rows, batch, dtype):
    """``silu(conv + bias)`` and the gradients of x, the taps and the bias."""
    positions = tiles(rows)
    operands = _conv_operands(batch, channels, positions, dtype)
    cotangent = _draw((batch, channels, positions, W), dtype, 10)

    def side(fn):
        return jax.jit(lambda ct, *a: (fn(*a), jax.vjp(fn, *a)[1](ct)))(cotangent, *operands)

    (got, got_grads), (want, want_grads) = side(_kernel_conv), side(_plain_conv)
    assert got.dtype == want.dtype == dtype
    _close(got, want, dtype, "conv")
    for name, a, b in zip(("x", "taps", "bias"), got_grads, want_grads):
        assert a.dtype == b.dtype == dtype
        _close(a, b, dtype, f"gradient of {name}")


@pytest.mark.parametrize("back", [1, 3], ids=["last-row", "three-rows-before"])
@pytest.mark.parametrize("boundary", ["tile", "chunk"])
def test_the_conv_reaches_across_a_boundary_both_ways(tiles, boundary, back):
    """An impulse ``back`` rows before a boundary moves the rows after the
    boundary that its taps reach (the rows a tile reads from the block before
    it, a chunk from the chunk before it) and no row further on; cotangents on
    the three rows after the boundary reach back to the three before it."""
    positions = tiles("three-tiles")
    edge = me.TILE_ROWS if boundary == "tile" else conv_tiles.CHUNK_ROWS
    x, taps, bias = _conv_operands(1, 2, positions, jnp.float32)
    moved = x.at[:, :, edge - back].add(1.0)
    reached = slice(edge, edge + me.TAPS - back)
    d_kernel = _kernel_conv(moved, taps, bias) - _kernel_conv(x, taps, bias)
    d_plain = _plain_conv(moved, taps, bias) - _plain_conv(x, taps, bias)
    assert float(jnp.abs(d_plain)[:, :, reached].max(axis=-1).min()) > 1e-4
    np.testing.assert_allclose(d_kernel[:, :, edge - back:reached.stop],
                               d_plain[:, :, edge - back:reached.stop], atol=1e-5)
    np.testing.assert_array_equal(d_kernel[:, :, reached.stop:], 0)
    np.testing.assert_array_equal(d_kernel[:, :, :edge - back], 0)
    after = slice(edge, edge + 3)
    only_after = jnp.zeros_like(x).at[:, :, after].set(_draw((1, 2, 3, W), jnp.float32, 30))
    got = jax.vjp(_kernel_conv, x, taps, bias)[1](only_after)[0]
    want = jax.vjp(_plain_conv, x, taps, bias)[1](only_after)[0]
    assert float(jnp.abs(want[:, :, edge - 3:edge]).max(axis=-1).min()) > 1e-4
    np.testing.assert_allclose(got[:, :, edge - 3:edge + 3], want[:, :, edge - 3:edge + 3], atol=1e-5)
    np.testing.assert_array_equal(got[:, :, :edge - 3], 0)
    np.testing.assert_array_equal(got[:, :, edge + 3:], 0)


def test_the_shapes_the_kernels_take_and_what_a_call_costs():
    assert me.fits(128, 32768, 4) and me.fits(128, 64, 4)
    assert not me.fits(128, 8192, 5)          # the windows are a width-4 conv's
    assert not me.fits(96, 8192, 4)           # the last axis is no lane tile
    assert not me.fits(256, 8192, 4)
    assert not me.fits(128, 100, 4)           # rows in no whole unit
    assert me._tiles(32, 32768) == (me.TILE_CHANNELS, me.TILE_ROWS)
    assert me._tiles(2, 192) == (2, 192) and me._tiles(6, 2048) == (6, 1024)
    x, taps, bias = _conv_operands(2, 2, 128, jnp.bfloat16)
    _kernel_conv(x, taps, bias)
    costs = kernel_costs()
    # x in and y out, the taps and the bias as they lie; backward x and the
    # cotangent in, dx out, the leaves in and their gradients out in float32
    leaves = taps.size + bias.size
    assert costs["mamba_conv_fwd"]["bytes"] == 2 * x.size * 2 + leaves * 2
    assert costs["mamba_conv_bwd"]["bytes"] == 3 * x.size * 2 + leaves * 2 + leaves * 4
    assert costs["mamba_conv_fwd"]["flops"] == 0
    assert kernel_traces()["mamba_conv:interpret"] >= 1


def test_the_whole_mixer_down_the_kernel_path_is_the_plain_mixer(monkeypatch):
    """``mamba2_mixer`` at two heads a lane tile and a state of 128 with the
    dispatch's ``on_tpu`` patched (the kernels then run interpreted here): the
    output and the gradient of the input and of every leaf equal the plain
    path's."""
    cfg = dataclasses.replace(
        PRESETS["granite-hybrid-debug"], dtype=jnp.float32, remat_policy="attn", n_layers=1,
        layer_pattern=("mamba2",), mamba2=mamba2.Mamba2(heads=4, head_dim=64, state=128, chunk=16))
    params = init_params(cfg, jax.random.PRNGKey(0))
    layer = jax.tree.map(lambda a: a[0], params["layers"])   # one kind: no slots
    layer = {k: v for k, v in layer.items() if k in mamba2.MAMBA2.axes(cfg)}
    layer["ssm_norm"] = layer["ssm_norm"] + _draw(layer["ssm_norm"].shape, jnp.float32, 5, 0.3)
    h = _draw((2, 64, cfg.hidden), jnp.float32, 6)
    positions = jnp.arange(64, dtype=jnp.int32)

    def run(h, layer):
        # the scan in plain ``jnp`` on both sides: its kernels have their own tests
        y, _ = mamba2.mamba2_mixer(h, layer, config=cfg, positions=positions, scan=ssd_scan)
        return (y * jnp.cos(jnp.arange(y.size).reshape(y.shape))).sum(), y

    def both():
        before = kernel_traces()
        (_, y), grads = jax.jit(jax.value_and_grad(run, argnums=(0, 1), has_aux=True))(h, layer)
        after = kernel_traces()
        took = {k for k in after if after[k] > before.get(k, 0)
                and k.startswith("mamba_")}
        return y, grads, took

    with jax.default_matmul_precision("highest"):
        want_y, want_grads, took = both()
        assert took == {"mamba_conv:jnp"}
        monkeypatch.setattr(mamba2, "on_tpu", lambda: True)
        got_y, got_grads, took = both()
        assert took == {"mamba_conv:interpret"}
    _close(got_y, want_y, jnp.float32, "y")
    _close(got_grads[0], want_grads[0], jnp.float32, "gradient of h")
    assert set(got_grads[1]) == set(mamba2.MAMBA2.axes(cfg))
    for name in want_grads[1]:
        _close(got_grads[1][name], want_grads[1][name], jnp.float32, f"gradient of {name}")

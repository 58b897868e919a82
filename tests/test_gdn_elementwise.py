"""``ops/gdn_elementwise.py``'s kernels, interpreted on the CPU, against the
plain functions ``models/gdn.py`` keeps for every backend but the chip:
values and every gradient, across tile and chunk boundaries, and the whole
mixer down the kernel path."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import gdn
from ray_tpu.models.llama import PRESETS, init_params
from ray_tpu.ops import conv_tiles
from ray_tpu.ops import gdn_elementwise as ge
from ray_tpu.ops.trace_log import kernel_costs, kernel_traces

D = 128
KEY_HEADS = 2
# (tile rows, chunk rows, positions): one tile of one chunk; one tile of two
# chunks; three tiles of two chunks each
ROWS = {"one-tile": (128, 128, 128), "two-chunks": (128, 64, 128), "three-tiles": (128, 64, 384)}


def _config(rep, dtype):
    return dataclasses.replace(PRESETS["hybrid-debug"], gdn_key_heads=KEY_HEADS,
                               gdn_value_heads=KEY_HEADS * rep, gdn_head_dim=D, dtype=dtype)


@pytest.fixture
def tiles(monkeypatch):
    """Small tiles, so that a short sequence crosses them: two heads a
    channel block."""
    def set_rows(name):
        tile, chunk, positions = ROWS[name]
        monkeypatch.setattr(ge, "TILE_ROWS", tile)
        monkeypatch.setattr(conv_tiles, "CHUNK_ROWS", chunk)
        monkeypatch.setattr(ge, "TILE_LANES", 2 * D)
        return positions
    return set_rows


def _draw(shape, dtype, seed, scale=1.0):
    return (scale * jax.random.normal(jax.random.PRNGKey(seed), shape, jnp.float32)).astype(dtype)


def _close(got, want, dtype, what):
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    assert got.shape == want.shape, what
    scale = float(np.abs(want).max())
    assert scale > 0, what
    # bfloat16: both sides round a float32 value that may differ in its last bits
    tol = 1e-5 if dtype == jnp.float32 else 2.0 ** -6
    np.testing.assert_allclose(got, want, atol=tol * scale, rtol=0, err_msg=what)


def _conv_sides(c, batch, positions, seed=0):
    kw, vw = gdn._widths(c)
    qkv = _draw((batch, positions, 2 * kw + vw), c.dtype, seed)
    conv_w = _draw((2 * kw + vw, c.gdn_conv), c.dtype, seed + 1, 0.5)
    kernels = lambda x, w: ge.conv_heads(  # noqa: E731
        x, w, key_heads=c.gdn_key_heads, value_heads=c.gdn_value_heads, out_dtype=c.dtype,
        interpret=True)
    plain = lambda x, w: gdn._conv_heads(x, w, c)  # noqa: E731
    return qkv, conv_w, kernels, plain


# a case compiles one program a side on the CPU (seconds each), so batch and
# type go together
@pytest.mark.parametrize("batch,dtype", [(1, jnp.float32), (2, jnp.bfloat16)],
                         ids=["1-f32", "2-bf16"])
@pytest.mark.parametrize("rows", list(ROWS))
@pytest.mark.parametrize("rep", [1, 2])
def test_conv_heads_and_the_gated_norm_match_the_plain_functions(tiles, rep, rows, batch, dtype):
    """q, k, v and the gradients of ``gdn_qkv`` and ``conv_w``; y and the
    gradients of ``gdn_o``, ``gdn_z`` and ``gdn_norm``."""
    c, positions = _config(rep, dtype), tiles(rows)
    vh = c.gdn_value_heads
    qkv, conv_w, conv_kernels, conv_plain = _conv_sides(c, batch, positions)
    o, z = _draw((batch, vh, positions, D), dtype, 20), _draw((batch, positions, vh * D), dtype, 21)
    weight = (1.0 + _draw((D,), jnp.float32, 22, 0.3)).astype(dtype)
    norm_kernels = lambda o, z, w: ge.gated_norm(  # noqa: E731
        o, z, w, eps=c.norm_eps, out_dtype=dtype, interpret=True)
    norm_plain = lambda o, z, w: gdn._gated_norm(  # noqa: E731
        o.transpose(0, 2, 1, 3), z.reshape(batch, positions, vh, D), w, c.norm_eps
    ).astype(dtype).reshape(batch, positions, vh * D)
    cotangents = (tuple(_draw((batch, vh, positions, D), dtype, 10 + i) for i in range(3)),
                  _draw((batch, positions, vh * D), dtype, 23))

    def side(conv, norm):
        f = lambda qkv, conv_w, o, z, w: (conv(qkv, conv_w), norm(o, z, w))  # noqa: E731
        return jax.jit(lambda ct, *a: (f(*a), jax.vjp(f, *a)[1](ct)))(
            cotangents, qkv, conv_w, o, z, weight)

    (got, got_grads), (want, want_grads) = side(conv_kernels, norm_kernels), side(conv_plain, norm_plain)
    for name, a, b in zip(("q", "k", "v", "y"), (*got[0], got[1]), (*want[0], want[1])):
        assert a.dtype == b.dtype == dtype
        _close(a, b, dtype, name)
    for name, a, b in zip(("gdn_qkv", "conv_w", "gdn_o", "gdn_z", "gdn_norm"), got_grads, want_grads):
        assert a.dtype == b.dtype == dtype
        _close(a, b, dtype, f"gradient of {name}")


@pytest.mark.parametrize("boundary", ["tile", "chunk"])
def test_the_conv_reaches_across_a_boundary_both_ways(tiles, boundary):
    """A change of the last row before a boundary moves the three rows after
    it (the rows a tile reads from the block before it, a chunk from the
    chunk before it), and cotangents on those three rows reach back to it."""
    c, positions = _config(2, jnp.float32), tiles("three-tiles")
    edge = ge.TILE_ROWS if boundary == "tile" else conv_tiles.CHUNK_ROWS
    qkv, conv_w, kernels, plain = _conv_sides(c, 1, positions)
    moved = qkv.at[:, edge - 1].add(1.0)
    after = slice(edge, edge + 3)
    for a, b, a0, b0 in zip(kernels(moved, conv_w), plain(moved, conv_w),
                            kernels(qkv, conv_w), plain(qkv, conv_w)):
        assert float(jnp.abs(b - b0)[:, :, after].max()) > 1e-3
        np.testing.assert_allclose((a - a0)[:, :, after], (b - b0)[:, :, after], atol=1e-5)
        np.testing.assert_array_equal((a - a0)[:, :, edge + 3:], 0)
    only_after = tuple(jnp.zeros((1, c.gdn_value_heads, positions, D)).at[:, :, after].set(
        _draw((1, c.gdn_value_heads, 3, D), jnp.float32, 30 + i)) for i in range(3))
    got = jax.vjp(kernels, qkv, conv_w)[1](only_after)[0]
    want = jax.vjp(plain, qkv, conv_w)[1](only_after)[0]
    assert float(jnp.abs(want[:, edge - 3:edge]).max(axis=-1).min()) > 1e-4
    np.testing.assert_allclose(got[:, edge - 3:edge + 3], want[:, edge - 3:edge + 3], atol=1e-5)
    np.testing.assert_array_equal(got[:, :edge - 3], 0)


def test_the_shapes_the_kernels_take_and_what_a_call_costs():
    assert ge.fits(128, 8192, 4) and ge.fits(256, 64, 4)
    assert not ge.fits(16, 128, 4)            # ``hybrid-debug``: a head is no lane tile
    assert not ge.fits(128, 100, 4)           # rows in no whole unit
    assert not ge.fits(128, 8192, 3)          # the windows are a width-4 conv's
    assert ge._tiles(8192, 128, 2048, 4096) == (ge.TILE_ROWS, ge.TILE_LANES)
    assert ge._tiles(192, 128, 256, 512) == (192, 256) and conv_tiles.chunks(192) == (192, 1)
    assert ge._tiles(2048, 128, 384, 768) == (1024, 384)
    c = _config(2, jnp.bfloat16)
    qkv, conv_w, kernels, _ = _conv_sides(c, 2, 128)
    kernels(qkv, conv_w)
    costs = kernel_costs()
    # qkv once and q, k, v at the value heads' count once; backward the
    # cotangents and qkv again, qkv's gradient and the taps' in float32
    heads = 3 * 2 * 128 * 4 * D * 2
    assert costs["gdn_conv_fwd"]["bytes"] == qkv.size * 2 + heads
    assert costs["gdn_conv_bwd"]["bytes"] == 2 * qkv.size * 2 + heads + 4 * conv_w.size
    assert kernel_traces()["gdn_conv:interpret"] >= 1


def test_the_whole_mixer_down_the_kernel_path_is_the_plain_mixer(monkeypatch):
    """``gdn_mixer`` at a head width of 128 with the dispatch's predicate
    patched (the kernels then run interpreted here): y, everything the scan
    saw and the gradient of every leaf equal the plain path's."""
    cfg = dataclasses.replace(_config(2, jnp.float32), remat_policy="attn")
    params = init_params(cfg, jax.random.PRNGKey(0))
    layer = jax.tree.map(lambda a: a[0], params["layers"]["slot0"])
    layer = {k: v for k, v in layer.items() if k in gdn.gdn_axes(cfg)}
    layer["gdn_norm"] = layer["gdn_norm"] + _draw(layer["gdn_norm"].shape, jnp.float32, 5, 0.3)
    h = _draw((1, 64, cfg.hidden), jnp.float32, 6)

    def run(h, layer):
        # the rule in plain ``jnp`` on both sides: its kernels have their own tests
        y, seen = gdn.gdn_mixer(h, layer, config=cfg, return_scan=True, scan=gdn.chunked_jnp)
        return (y * jnp.cos(jnp.arange(y.size).reshape(y.shape))).sum(), (y, seen)

    def both():
        before = kernel_traces()
        (_, (y, seen)), grads = jax.jit(jax.value_and_grad(run, argnums=(0, 1), has_aux=True))(h, layer)
        after = kernel_traces()
        took = {k for k in after if after[k] > before.get(k, 0) and k.startswith(("gdn_conv", "gdn_norm"))}
        return y, seen, grads, took

    with jax.default_matmul_precision("highest"):
        want_y, want_seen, want_grads, took = both()
        assert took == {"gdn_conv:jnp", "gdn_norm:jnp"}
        monkeypatch.setattr(gdn, "_in_vmem", lambda c, rows: True)
        got_y, got_seen, got_grads, took = both()
        assert took == {"gdn_conv:interpret", "gdn_norm:interpret"}
    _close(got_y, want_y, jnp.float32, "y")
    for name in want_seen:
        _close(got_seen[name], want_seen[name], jnp.float32, name)
    _close(got_grads[0], want_grads[0], jnp.float32, "gradient of h")
    assert set(got_grads[1]) == set(gdn.gdn_axes(cfg))
    for name in want_grads[1]:
        _close(got_grads[1][name], want_grads[1][name], jnp.float32, f"gradient of {name}")

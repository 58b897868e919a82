"""``ops/sconv_elementwise.py``'s kernels, interpreted on the CPU, against the
plain function ``models/short_conv.py`` keeps for every backend but the chip:
the output, the counter and every gradient, across tile and chunk boundaries;
which shapes take which path; and the whole step's calls."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import jaxpr_walk
from test_mamba_elementwise import _close
from ray_tpu.models import PRESETS, init_params, loss_fn, short_conv
from ray_tpu.models.short_conv import gated_conv, sconv_mixer
from ray_tpu.ops import conv_tiles
from ray_tpu.ops import sconv_elementwise as se
from ray_tpu.ops.trace_log import kernel_costs, kernel_traces

E = 256
# (tile rows, chunk rows, positions): three tiles of two chunks each (a tile
# edge and a chunk edge under the taps, the row's first two positions with
# zeros before them) and one tile of 192 rows walked in three chunks of 64
ROWS = {"three-tiles": (128, 64, 384), "off-chunk": (256, 128, 192)}


@pytest.fixture
def tiles(monkeypatch):
    """Small tiles, so that a short sequence crosses them, and two lane blocks."""
    def set_rows(name):
        tile, chunk, positions = ROWS[name]
        monkeypatch.setattr(se, "TILE_ROWS", tile)
        monkeypatch.setattr(se, "TILE_LANES", 128)
        monkeypatch.setattr(conv_tiles, "CHUNK_ROWS", chunk)
        return positions
    return set_rows


def _draw(shape, dtype, seed, scale=1.0):
    return jnp.asarray(scale * np.random.default_rng(seed).standard_normal(shape, np.float32),
                       dtype)


def _plain(bcx, taps):
    gated, share = gated_conv(*bcx, taps)
    return gated.astype(bcx.dtype), share


def _kernels(bcx, taps):
    return se.gated_conv3(bcx, taps, interpret=True)


# a case compiles one program on the CPU, so batch and type go together (without
# LLVM's passes, as every program of a run: ``conftest.py``'s ``XLA_FLAGS``)


@pytest.mark.parametrize("rows,batch,dtype", [
    ("three-tiles", 1, jnp.float32), ("three-tiles", 2, jnp.bfloat16),
    ("off-chunk", 2, jnp.bfloat16)],
    ids=["three-tiles-1-f32", "three-tiles-2-bf16", "off-chunk-2-bf16"])
def test_gated_conv3_matches_gated_conv(tiles, rows, batch, dtype):
    """``C * conv(B * X)``, ``past_share`` and the gradients of B, C, X and of
    each tap: rows of a batch apart, zeros before a row."""
    positions = tiles(rows)
    bcx, taps = _draw((3, batch, positions, E), dtype, 0), _draw((se.TAPS, E), dtype, 1, 0.5)
    cotangent = _draw((batch, positions, E), dtype, 2)

    def both(bcx, taps, cotangent):
        def side(fn):
            (out, share), pull = jax.vjp(fn, bcx, taps)
            return out, share, pull((cotangent, jnp.zeros_like(share)))
        return side(_kernels), side(_plain)

    (got, got_share, got_grads), (want, want_share, want_grads) = jax.jit(both)(
        bcx, taps, cotangent)
    assert got.dtype == want.dtype == dtype
    _close(got, want, dtype, "output")
    assert float(got_share) == pytest.approx(float(want_share), rel=1e-5)
    assert 0.4 < float(got_share) < 0.9
    for third, a, b in zip("BCX", got_grads[0], want_grads[0]):
        assert a.dtype == dtype
        _close(a, b, dtype, f"gradient of {third}")
    assert got_grads[1].dtype == dtype
    for i in range(se.TAPS):
        _close(got_grads[1][i], want_grads[1][i], dtype, f"gradient of tap {i}")


def test_the_shapes_the_kernels_take_and_the_mixer_without_them(monkeypatch):
    """``fits`` refuses a width off the lane tiling, rows off ``ROW_UNIT``, a
    conv of four taps and a dtype that is no 16- or 32-bit float; the mixer,
    even where the backend says chip, then runs ``gated_conv`` under its own
    name and says so. What a call costs: the bytes of each array once."""
    assert se.fits(2048, 8192, 3, jnp.bfloat16) and se.fits(128, 64, 3, jnp.float32)
    assert not se.fits(2048 + 64, 8192, 3, jnp.bfloat16)     # off the lane tiling
    assert not se.fits(2048, 8192 + 8, 3, jnp.bfloat16)      # rows in no whole unit
    assert not se.fits(2048, 8192, 4, jnp.bfloat16)          # the bodies are a 3-tap conv's
    assert not se.fits(2048, 8192, 3, jnp.float8_e4m3fn) and not se.fits(2048, 8192, 3, jnp.int32)
    assert se._tiles(8192, 2048) == (se.TILE_ROWS, se.TILE_LANES)
    assert se._tiles(1280, 384) == (640, 384)

    monkeypatch.setattr(short_conv, "on_tpu", lambda: True)
    called = []
    monkeypatch.setattr(short_conv, "gated_conv",
                        lambda *a: called.append(a[0].shape) or gated_conv(*a))
    cfg = PRESETS["conv-moe-debug"]

    def traced(width, rows, taps):
        layer = {"w_in": jnp.zeros((width, 3, width)), "conv": jnp.zeros((taps, width)),
                 "w_out": jnp.zeros((width, width))}
        before = kernel_traces()
        jaxpr = jax.make_jaxpr(lambda h: sconv_mixer(
            h, layer, config=cfg, positions=jnp.arange(rows))[0])(jnp.zeros((2, rows, width)))
        after = kernel_traces()
        kernels = [str(e.params["name"]) for e in jaxpr_walk.equations(jaxpr.jaxpr)
                   if e.primitive.name == "pallas_call"]
        return kernels, {k for k in after if k.startswith("sconv:") and after[k] > before.get(k, 0)}

    for shape in ((64, 128, 3), (128, 40, 3), (128, 128, 4)):
        assert traced(*shape) == ([], {"sconv:jnp"}), shape
    assert called == [(2, 128, 64), (2, 40, 128), (2, 128, 128)]
    assert traced(128, 128, 3) == (["sconv_fwd"], {"sconv:interpret"}) and len(called) == 3
    costs, third = kernel_costs(), 2 * 128 * 128 * 4
    assert costs["sconv_fwd"]["flops"] == costs["sconv_bwd"]["flops"] == 0
    assert costs["sconv_fwd"]["bytes"] == 4 * third + 3 * 128 * 4 + 2 * 8 * 128 * 4
    assert costs["sconv_bwd"]["bytes"] == 7 * third + 2 * 3 * 128 * 4


def test_the_step_under_remat_full_calls_forward_twice_and_backward_once_a_layer(monkeypatch):
    """``conv-moe-debug`` at a width of one lane tile, the kernels forced on
    (interpreted here), remat ``full`` (the cell's policy): every conv layer of
    the program's text holds ``sconv_fwd`` in its forward pass and again beside
    its one ``sconv_bwd``, whose operands are that second call's own (the thirds
    as the in-projection made them again, the taps) and the cotangent: no
    residual beyond the call's inputs."""
    monkeypatch.setattr(short_conv, "on_tpu", lambda: True)
    cfg = dataclasses.replace(PRESETS["conv-moe-debug"], hidden=128, remat_policy="full")
    params = jax.eval_shape(lambda key: init_params(cfg, key), jax.random.PRNGKey(0))
    tokens = jnp.zeros((2, 64), jnp.int32)
    jaxpr = jax.make_jaxpr(jax.grad(
        lambda p: loss_fn(p, {"tokens": tokens}, cfg, chunk_tokens=32)))(params)
    calls = [e for e in jaxpr_walk.equations(jaxpr.jaxpr) if e.primitive.name == "pallas_call"
             and str(e.params["name"]).startswith("sconv_")]
    forward = [e for e in calls if str(e.params["name"]) == "sconv_fwd"]
    backward = [e for e in calls if str(e.params["name"]) == "sconv_bwd"]
    # the program's text holds a scanned period once: its three conv slots and
    # the two leading layers
    layers = len(cfg.lead_pattern) + cfg.layer_pattern.count("sconv")
    assert (len(forward), len(backward)) == (2 * layers, layers)
    made = {id(v) for e in forward for v in e.outvars}
    for call in backward:
        thirds, taps, cotangent = call.invars[0], call.invars[3], call.invars[4]
        assert call.invars[:3] == [thirds] * 3 and thirds.aval.shape == (3, 2, 64, 128)
        assert sum(e.invars == call.invars[:4] for e in forward) == 1
        assert id(cotangent) not in made and id(thirds) not in made and id(taps) not in made
        assert [v.aval.shape for v in call.outvars] == [(3, 2, 64, 128), (3, 128)]

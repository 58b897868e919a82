"""The Mamba-2 mixer's channel-tile-major elementwise passes as Pallas TPU
kernels.

Between the projection and the scan ``models/mamba2.py`` runs, for the
projection's ``x [B, C, T, 128]`` (C lane tiles of ``r`` heads side by side, as
the scan takes them; and for ``B | C [B, 2, T, N]`` at a state of 128):

    y = silu(causal depthwise conv1d(x, 4 taps) + bias)        float32 inside

No matrix product, and in plain XLA a sixth of what the bytes allow: the
conv's four shifted views along the sublanes end every fusion, so float32
arrays of the whole ``[C, T, 128]`` go to HBM and come back, and the taps'
gradient is four reductions over them. Here a grid step takes a block
``(1, channel tiles, rows, 128)`` of the array as it lies, every float32
intermediate lives in VMEM and each array crosses HBM once a pass; the
mathematics and its precisions are the plain functions' (float32 inside, one
rounding on the way out). The shifted views, the chunk walk and the tile
arithmetic are ``ops/conv_tiles.py``'s, shared with the two other convs; the
bodies are this layout's own: a bias, no head split, no l2 norms.

``mamba_conv_fwd``  grid (channel block, batch, row tile). The taps [4, C, 128]
                    and the bias [C, 128] come as the leaves lie, in their
                    own dtype: a channel tile's are rows of their blocks,
                    cast in VMEM (a packing or a cast ahead of the call is a
                    program of parameters alone: the chip's compiler runs it
                    for all layers at the step's start and keeps its output
                    to the step's end, 18,944 bytes over the parent's peak
                    when this module packed them; PERF.md, PR 54). The
                    three rows before a tile come as a second, ``HALO``-row
                    block of the same array, zeros before position 0.
``mamba_conv_bwd``  the same grid, the row tiles walked BACKWARDS: an input's
                    gradient needs the pre-activation gradient of the three
                    positions after it, which a tile hands to the one before
                    it in VMEM. It makes the pre-activation again (the
                    residuals are the call's INPUTS: x before the conv, the
                    taps and the bias, never its output), writes ``dx`` once
                    and accumulates the taps' and the bias's gradients in
                    float32 over batch and row tiles.

``conv_silu`` is the pair under a ``jax.custom_vjp``. Under remat ``attn``
nothing new is saved: its outputs are names ``models/mamba2.py`` saves already
and the second run needs no forward call. Off the TPU the kernels run in the
Pallas interpreter (the tests); ``models/mamba2.py`` calls them only on the
chip and keeps its plain functions elsewhere.

The mixer's gated norm stays plain XLA: a kernel pair for it (one statistic
over a block of all 32 channel tiles) ran 1.27 / 2.20 ms a call against the
plain function's 1.90 / 3.77 alone, and SLOWED the step by 10 ms, because
inside the step the chip's compiler fuses the norm into the products beside
it (PERF.md section 6, PR 54).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..tpu import on_tpu
from .conv_tiles import (HALO, LANE, ROW_UNIT, SUBLANES, chunks, fold, largest, mosaic_params,
                         tapped, windows)
from .trace_log import note_kernel_cost, note_kernel_trace

# a grid step's block of the conv: channel tiles x rows x 128 lanes, a step
# costs ~0.35 us whatever it does (PR 38) and a tile's rows are contiguous
TILE_CHANNELS = 8
TILE_ROWS = 1024
TAPS = 4          # the conv's width


def fits(width: int, rows: int, taps: int) -> bool:
    """Whether the kernels take these shapes: a last axis of one lane tile,
    rows in whole units, a conv of ``TAPS`` taps."""
    return width == LANE and rows % ROW_UNIT == 0 and taps == TAPS


def _tiles(channels: int, rows: int):
    """(channel tiles, rows) of a grid step's block."""
    return largest(channels, 1, TILE_CHANNELS), largest(rows, ROW_UNIT, TILE_ROWS)


def _halo_rows(halo_ref, c, first_tile):
    """The 8 rows before a tile's channel tile c, float32: the halo block's
    last, zeros before position 0."""
    rows = halo_ref[0, c].astype(jnp.float32)[HALO - SUBLANES:]
    return jnp.where(first_tile, 0.0, rows)


def _taps(w_ref, b_ref):
    """c -> channel tile c's taps [4, 128] and bias [1, 128], float32, from the
    blocks of the leaves as they lie ([4, channel tiles, 128] and [channel
    tiles, 128] in the model's dtype, cast once a grid step): c is static, a
    tap a row of its tile."""
    w, b = w_ref[...].astype(jnp.float32), b_ref[...].astype(jnp.float32)
    return lambda c: (jnp.concatenate([w[j, c:c + 1] for j in range(TAPS)], axis=0), b[c:c + 1])


def _fwd_kernel(x_ref, halo_ref, w_ref, b_ref, y_ref):
    first_tile = pl.program_id(2) == 0
    c_rows, n_chunks = chunks(x_ref.shape[2])
    taps = _taps(w_ref, b_ref)
    for c in range(x_ref.shape[1]):        # static; ``fori_loop`` traces ``chunk`` at once
        w, bias = taps(c)

        def chunk(i, before):
            rows = pl.ds(pl.multiple_of(i * c_rows, c_rows), c_rows)
            x = x_ref[0, c, rows].astype(jnp.float32)
            pre = tapped(windows(x, TAPS, before), w) + bias
            y_ref[0, c, rows] = (pre * jax.nn.sigmoid(pre)).astype(y_ref.dtype)
            return x[c_rows - SUBLANES:]

        lax.fori_loop(0, n_chunks, chunk, _halo_rows(halo_ref, c, first_tile))


def _bwd_kernel(x_ref, halo_ref, w_ref, b_ref, dy_ref, dx_ref, dw_ref, db_ref, after_ref):
    b, s = pl.program_id(1), pl.program_id(2)
    # the row tiles are walked backwards: s = 0 is the sequence's end
    first_tile = s == pl.num_programs(2) - 1
    c_rows, n_chunks = chunks(x_ref.shape[2])
    taps = _taps(w_ref, b_ref)

    @pl.when(jnp.logical_and(b == 0, s == 0))
    def _first_of_a_channel_block():
        dw_ref[...] = jnp.zeros_like(dw_ref)
        db_ref[...] = jnp.zeros_like(db_ref)

    @pl.when(s == 0)
    def _end_of_a_sequence():
        after_ref[...] = jnp.zeros_like(after_ref)

    for c in range(x_ref.shape[1]):
        w, bias = taps(c)
        before = _halo_rows(halo_ref, c, first_tile)

        def chunk(n, carry):
            after, sums = carry
            i = n_chunks - 1 - n
            rows = pl.ds(pl.multiple_of(i * c_rows, c_rows), c_rows)
            x = x_ref[0, c, rows].astype(jnp.float32)
            own = x_ref[0, c, pl.ds(pl.multiple_of(jnp.maximum(i * c_rows - HALO, 0), HALO),
                                    HALO)].astype(jnp.float32)[HALO - SUBLANES:]
            z = windows(x, TAPS, jnp.where(i > 0, own, before))
            pre = tapped(z, w) + bias
            sig = jax.nn.sigmoid(pre)
            dc = dy_ref[0, c, rows].astype(jnp.float32) * (sig * (1.0 + pre * (1.0 - sig)))
            dx_ref[0, c, rows] = tapped(windows(dc, TAPS, after=after), w).astype(dx_ref.dtype)
            terms = [dc * zj for zj in z] + [dc]                # the taps', then the bias's
            return dc[:SUBLANES], tuple(t + fold(term) for t, term in zip(sums, terms))

        zeros = jnp.zeros((SUBLANES, LANE), jnp.float32)
        after, sums = lax.fori_loop(0, n_chunks, chunk, (after_ref[c], (zeros,) * (TAPS + 1)))
        after_ref[c] = after
        for j in range(TAPS):
            dw_ref[j, c:c + 1] += sums[j].sum(axis=0, keepdims=True)
        db_ref[c:c + 1] += sums[TAPS].sum(axis=0, keepdims=True)


def _specs(x, *, backwards):
    """The grid and the block specs both conv kernels share: the array's
    block, its halo, the taps and the bias. ``backwards`` walks the row tiles
    from the sequence's end."""
    b, c, t, _ = x.shape
    t_chan, t_rows = _tiles(c, t)
    n_tiles = t // t_rows
    tile_of = (lambda si: n_tiles - 1 - si) if backwards else (lambda si: si)
    halo_of = lambda si: jnp.maximum(tile_of(si) * (t_rows // HALO) - 1, 0)  # noqa: E731
    tile = pl.BlockSpec((1, t_chan, t_rows, LANE), lambda p, bi, si: (bi, p, tile_of(si), 0))
    halo = pl.BlockSpec((1, t_chan, HALO, LANE), lambda p, bi, si: (bi, p, halo_of(si), 0))
    taps = pl.BlockSpec((TAPS, t_chan, LANE), lambda p, bi, si: (0, p, 0))
    bias = pl.BlockSpec((t_chan, LANE), lambda p, bi, si: (p, 0))
    return (c // t_chan, b, n_tiles), tile, halo, taps, bias


def _conv_forward(x, taps, bias, *, interpret):
    note_kernel_trace("mamba_conv", "interpret" if interpret else "pallas")
    # x in and y out; backward x and dy in, dx out. The vector unit's ~20 / ~45
    # operations an element bind before the bytes do (PR 38); no FLOP is a
    # model's, so none is counted.
    nbytes = x.size * x.dtype.itemsize
    leaves = (taps.size + bias.size) * taps.dtype.itemsize
    note_kernel_cost("mamba_conv_fwd", 0, 2 * nbytes + leaves)
    note_kernel_cost("mamba_conv_bwd", 0, 3 * nbytes + leaves + (taps.size + bias.size) * 4)
    grid, tile, halo, taps_spec, bias_spec = _specs(x, backwards=False)
    return pl.pallas_call(
        _fwd_kernel, grid=grid, in_specs=[tile, halo, taps_spec, bias_spec], out_specs=tile,
        out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
        compiler_params=mosaic_params(), interpret=interpret, name="mamba_conv_fwd",
    )(x, x, taps, bias)


def _conv_backward(x, taps, bias, dy, *, interpret):
    grid, tile, halo, taps_spec, bias_spec = _specs(x, backwards=True)
    dx, dw, db = pl.pallas_call(
        _bwd_kernel, grid=grid, in_specs=[tile, halo, taps_spec, bias_spec, tile],
        out_specs=[tile, taps_spec, bias_spec],
        out_shape=[jax.ShapeDtypeStruct(x.shape, x.dtype),
                   jax.ShapeDtypeStruct(taps.shape, jnp.float32),
                   jax.ShapeDtypeStruct(bias.shape, jnp.float32)],
        scratch_shapes=[pltpu.VMEM((tile.block_shape[1], SUBLANES, LANE), jnp.float32)],
        compiler_params=mosaic_params(), interpret=interpret, name="mamba_conv_bwd",
    )(x, x, taps, bias, dy)
    return dx, dw.astype(taps.dtype), db.astype(bias.dtype)


@functools.lru_cache(maxsize=None)
def _make_conv(interpret: bool):
    @jax.custom_vjp
    def f(x, taps, bias):
        return _conv_forward(x, taps, bias, interpret=interpret)

    def fwd(x, taps, bias):
        # nothing new is saved: the second run under remat makes x again by
        # its product, as for the plain conv, and needs no forward call
        return f(x, taps, bias), (x, taps, bias)

    def bwd(res, dy):
        return _conv_backward(*res, dy, interpret=interpret)

    f.defvjp(fwd, bwd)
    return f


def conv_silu(x, taps, bias, *, interpret: bool | None = None):
    """``silu(conv + bias)`` [B, C, T, 128] in x's dtype of ``x [B, C, T, 128]``,
    ``taps [4, C, 128]`` and ``bias [C, 128]``: the causal depthwise conv over T
    with zeros before the row, float32 inside, one rounding on the way out.
    The leaves go in as they lie, in their own dtype (the module's docstring
    says why). Shapes as ``fits`` says. Differentiable in all three."""
    if interpret is None:
        interpret = not on_tpu()
    return _make_conv(bool(interpret))(x, taps, bias)

"""The double-gated short convolution's pass between its two products as
Pallas TPU kernels.

Between the in-projection and the out-projection ``models/short_conv.py``
runs, for the projection's thirds ``bcx = [B | C | X]  [3, batch, S, E]`` and
the taps ``k [3, E]``:

    u    = f32(B) * f32(X)
    past = k_0 u_{t-2} + k_1 u_{t-1}           depthwise, causal, zeros before the row
    v    = past + k_2 u_t
    out  = f32(C) * v                          one rounding to the model's dtype
    past_share = sum(past^2) / sum(v^2)        the mixer's counter

No matrix product, and in plain XLA a quarter of what the bytes allow: the
conv's shifted views along the sublanes end every fusion, so float32 arrays of
the whole ``[batch, S, E]`` go to HBM and come back, and the taps' gradient is
three reductions over them. Here a grid step takes a block ``(3, 1, rows,
lanes)`` of ``bcx`` as the product leaves it (the thirds are whole arrays: no
slice, no copy), every float32 intermediate lives in VMEM and each array
crosses HBM once a pass; the mathematics, its association and its precisions
are ``short_conv.gated_conv``'s. The shifted views, the chunk walk and the tile
arithmetic are ``ops/conv_tiles.py``'s, shared with the two other convs; the
bodies are this mixer's own: two gates, three taps, no bias, no activation,
no head split.

``sconv_fwd``  grid (lane block, batch, row tile). The taps come as the leaf
               lies, in its own dtype, cast in VMEM (a cast ahead of the call is
               a program of parameters alone that the chip's compiler keeps
               alive to the step's end; PERF.md, PR 54). The two rows before a
               tile come as ``HALO``-row blocks of the same array (B's and X's:
               the conv reads ``u``; C is pointwise), zeros before position 0.
               The counter's two sums leave as ``[2, 8, E]`` float32 partials,
               vreg adds over batch and row tiles, summed outside.
``sconv_bwd``  the same grid, the row tiles walked BACKWARDS: ``du`` needs
               ``dv = f32(dout) * f32(C)`` of the two positions after it, which
               a tile hands to the one before it in VMEM. It makes ``u`` and
               ``v`` again (the residuals are the call's INPUTS, never its
               output), writes ``d bcx [3, batch, S, E]`` as one array in the
               model's dtype and accumulates the taps' gradient in float32
               over batch and row tiles.

``gated_conv3`` is the pair under a ``jax.custom_vjp``. Off the TPU the kernels
run in the Pallas interpreter (the tests); ``models/short_conv.py`` calls them
only on the chip and keeps its plain function elsewhere.
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
                         windows)
from .trace_log import note_kernel_cost, note_kernel_trace

TAPS = 3
# a grid step's block of each third: a step costs ~0.35 us whatever it does
# (PR 38) and a block's row is one DMA burst of TILE_LANES elements
TILE_ROWS = 1024
TILE_LANES = 1024
_B, _C, _X = 0, 1, 2            # the thirds' places on ``bcx``'s leading axis


def fits(width: int, rows: int, taps: int, dtype) -> bool:
    """Whether the kernels take these shapes: features in whole lane tiles,
    rows in whole units, a conv of three taps, a float dtype of 16 or 32 bits."""
    dtype = jnp.dtype(dtype)
    return (width % LANE == 0 and rows % ROW_UNIT == 0 and taps == TAPS
            and jnp.issubdtype(dtype, jnp.floating) and dtype.itemsize in (2, 4))


def _tiles(rows: int, width: int):
    """(rows, lanes) of a grid step's block."""
    return largest(rows, ROW_UNIT, TILE_ROWS), largest(width, LANE, TILE_LANES)


def _u_before(b_halo_ref, x_halo_ref, lanes, first_tile):
    """``u`` of the 8 rows before a tile, float32: the halo blocks' last,
    zeros before position 0."""
    rows = lambda ref: ref[0, 0, :, lanes].astype(jnp.float32)[HALO - SUBLANES:]  # noqa: E731
    return jnp.where(first_tile, 0.0, rows(b_halo_ref) * rows(x_halo_ref))


def _conv(views, w):
    """(past, v) of the views ``[u_{t-2}, u_{t-1}, u_t]`` and the taps [3, 128],
    in ``gated_conv``'s association; of ``[dv_{t+2}, dv_{t+1}, dv_t]`` the
    conv's transpose (position t's u met k_2 at t, k_1 at t + 1, k_0 at t + 2)."""
    past = w[0:1] * views[0] + w[1:2] * views[1]
    return past, past + w[2:3] * views[2]


def _fwd_kernel(bcx_ref, b_halo_ref, x_halo_ref, w_ref, out_ref, sums_ref):
    first_tile = pl.program_id(2) == 0
    c_rows, n_chunks = chunks(bcx_ref.shape[2])

    @pl.when(jnp.logical_and(pl.program_id(1) == 0, first_tile))
    def _first_of_a_lane_block():
        sums_ref[...] = jnp.zeros_like(sums_ref)

    def lane_tile(h, _):
        lanes = pl.ds(pl.multiple_of(h * LANE, LANE), LANE)
        w = w_ref[:, lanes].astype(jnp.float32)

        def chunk(i, carry):
            before, past_sq, v_sq = carry
            rows = pl.ds(pl.multiple_of(i * c_rows, c_rows), c_rows)
            third = lambda g: bcx_ref[g, 0, rows, lanes].astype(jnp.float32)  # noqa: E731
            u = third(_B) * third(_X)
            past, v = _conv(windows(u, TAPS, before), w)
            out_ref[0, rows, lanes] = (third(_C) * v).astype(out_ref.dtype)
            return u[c_rows - SUBLANES:], past_sq + fold(past * past), v_sq + fold(v * v)

        zeros = jnp.zeros((SUBLANES, LANE), jnp.float32)
        _, past_sq, v_sq = lax.fori_loop(
            0, n_chunks, chunk, (_u_before(b_halo_ref, x_halo_ref, lanes, first_tile), zeros, zeros))
        sums_ref[0, :, lanes] += past_sq
        sums_ref[1, :, lanes] += v_sq

    lax.fori_loop(0, bcx_ref.shape[3] // LANE, lane_tile, None)


def _bwd_kernel(bcx_ref, b_halo_ref, x_halo_ref, w_ref, dout_ref, dbcx_ref, dw_ref, after_ref):
    b, s = pl.program_id(1), pl.program_id(2)
    # the row tiles are walked backwards: s = 0 is the sequence's end
    first_tile = s == pl.num_programs(2) - 1
    c_rows, n_chunks = chunks(bcx_ref.shape[2])

    @pl.when(jnp.logical_and(b == 0, s == 0))
    def _first_of_a_lane_block():
        dw_ref[...] = jnp.zeros_like(dw_ref)

    @pl.when(s == 0)
    def _end_of_a_sequence():
        after_ref[...] = jnp.zeros_like(after_ref)

    def lane_tile(h, _):
        lanes = pl.ds(pl.multiple_of(h * LANE, LANE), LANE)
        w = w_ref[:, lanes].astype(jnp.float32)
        before = _u_before(b_halo_ref, x_halo_ref, lanes, first_tile)

        def chunk(n, carry):
            after, sums = carry
            i = n_chunks - 1 - n
            rows = pl.ds(pl.multiple_of(i * c_rows, c_rows), c_rows)
            own = pl.ds(pl.multiple_of(jnp.maximum(i * c_rows - HALO, 0), HALO), HALO)
            third = lambda g, at=rows: bcx_ref[g, 0, at, lanes].astype(jnp.float32)  # noqa: E731
            gate_b, gate_c, x = third(_B), third(_C), third(_X)
            u_own = (third(_B, own) * third(_X, own))[HALO - SUBLANES:]
            views = windows(gate_b * x, TAPS, jnp.where(i > 0, u_own, before))
            _, v = _conv(views, w)
            dout = dout_ref[0, rows, lanes].astype(jnp.float32)
            dv = dout * gate_c
            _, du = _conv(windows(dv, TAPS, after=after), w)
            for g, grad in ((_B, du * x), (_C, dout * v), (_X, du * gate_b)):
                dbcx_ref[g, 0, rows, lanes] = grad.astype(dbcx_ref.dtype)
            return dv[:SUBLANES], tuple(t + fold(dv * view) for t, view in zip(sums, views))

        zeros = jnp.zeros((SUBLANES, LANE), jnp.float32)
        after, sums = lax.fori_loop(0, n_chunks, chunk, (after_ref[h], (zeros,) * TAPS))
        after_ref[h] = after
        dw_ref[:, lanes] += jnp.concatenate([t.sum(axis=0, keepdims=True) for t in sums], axis=0)

    lax.fori_loop(0, bcx_ref.shape[3] // LANE, lane_tile, None)


def _specs(bcx, *, backwards):
    """The grid and the block specs both kernels share: the thirds' block, B's
    and X's halos, the taps, and a ``[batch, S, E]`` array's block.
    ``backwards`` walks the row tiles from the sequence's end."""
    _, b, s, e = bcx.shape
    t_rows, t_lanes = _tiles(s, e)
    n_tiles = s // t_rows
    tile_of = (lambda si: n_tiles - 1 - si) if backwards else (lambda si: si)
    halo_of = lambda si: jnp.maximum(tile_of(si) * (t_rows // HALO) - 1, 0)  # noqa: E731
    thirds = pl.BlockSpec((3, 1, t_rows, t_lanes), lambda p, bi, si: (0, bi, tile_of(si), p))
    halo = lambda g: pl.BlockSpec((1, 1, HALO, t_lanes),  # noqa: E731
                                  lambda p, bi, si: (g, bi, halo_of(si), p))
    taps = pl.BlockSpec((TAPS, t_lanes), lambda p, bi, si: (0, p))
    tile = pl.BlockSpec((1, t_rows, t_lanes), lambda p, bi, si: (bi, tile_of(si), p))
    return (e // t_lanes, b, n_tiles), [thirds, halo(_B), halo(_X), taps], tile


def _forward(bcx, taps, *, interpret):
    note_kernel_trace("sconv", "interpret" if interpret else "pallas")
    # forward B, C, X in and one array out; backward those three and the
    # cotangent in, three gradients out. No FLOP is a model's, so none is
    # counted: ~15 / ~30 vector operations an element over 8 / 14 bytes.
    third = bcx.size // 3 * bcx.dtype.itemsize
    leaf = taps.size * taps.dtype.itemsize
    note_kernel_cost("sconv_fwd", 0, 4 * third + leaf + 2 * SUBLANES * bcx.shape[3] * 4)
    note_kernel_cost("sconv_bwd", 0, 7 * third + leaf + taps.size * 4)
    grid, ins, tile = _specs(bcx, backwards=False)
    return pl.pallas_call(
        _fwd_kernel, grid=grid, in_specs=ins,
        out_specs=[tile, pl.BlockSpec((2, SUBLANES, tile.block_shape[2]),
                                      lambda p, bi, si: (0, 0, p))],
        out_shape=[jax.ShapeDtypeStruct(bcx.shape[1:], bcx.dtype),
                   jax.ShapeDtypeStruct((2, SUBLANES, bcx.shape[3]), jnp.float32)],
        compiler_params=mosaic_params(), interpret=interpret, name="sconv_fwd",
    )(bcx, bcx, bcx, taps)


def _backward(bcx, taps, dout, *, interpret):
    grid, ins, tile = _specs(bcx, backwards=True)
    dbcx, dw = pl.pallas_call(
        _bwd_kernel, grid=grid, in_specs=ins + [tile], out_specs=[ins[0], ins[3]],
        out_shape=[jax.ShapeDtypeStruct(bcx.shape, bcx.dtype),
                   jax.ShapeDtypeStruct(taps.shape, jnp.float32)],
        scratch_shapes=[pltpu.VMEM((tile.block_shape[2] // LANE, SUBLANES, LANE), jnp.float32)],
        compiler_params=mosaic_params(), interpret=interpret, name="sconv_bwd",
    )(bcx, bcx, bcx, taps, dout)
    return dbcx, dw.astype(taps.dtype)


@functools.lru_cache(maxsize=None)
def _make(interpret: bool):
    @jax.custom_vjp
    def f(bcx, taps):
        return _forward(bcx, taps, interpret=interpret)

    def fwd(bcx, taps):
        # nothing new is saved: the thirds are made again from the block's
        # input by their product, as for the plain function
        return _forward(bcx, taps, interpret=interpret), (bcx, taps)

    def bwd(res, cotangents):
        return _backward(*res, cotangents[0], interpret=interpret)  # the sums carry no gradient

    f.defvjp(fwd, bwd)
    return f


def gated_conv3(bcx, taps, *, interpret: bool | None = None):
    """``(C * conv(B * X)`` [batch, S, E] in bcx's dtype, ``past_share``) of the
    thirds ``bcx [3, batch, S, E]`` = B, C, X and ``taps [3, E]``: the causal
    depthwise conv of three taps over S with zeros before the row, float32
    inside, one rounding on the way out; ``past_share`` as
    ``short_conv.gated_conv`` counts it, under ``stop_gradient``. The taps go in
    as the leaf lies, in their own dtype. Shapes as ``fits`` says.
    Differentiable in both."""
    if interpret is None:
        interpret = not on_tpu()
    out, sums = _make(bool(interpret))(bcx, taps)
    past_sq, v_sq = lax.stop_gradient(sums).sum(axis=(1, 2))
    return out, past_sq / jnp.maximum(v_sq, 1e-30)

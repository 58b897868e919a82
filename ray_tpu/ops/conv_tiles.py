"""What the three short-conv kernel pairs share: the shifted views along the
sublanes, the chunk walk, the tile arithmetic and the Mosaic parameters.

``ops/gdn_elementwise.py`` (DeltaNet's conv of four taps, a head at a time),
``ops/mamba_elementwise.py`` (Mamba-2's, a channel tile at a time) and
``ops/sconv_elementwise.py`` (LFM2's double-gated conv of three taps) each
walk a grid step's tile in chunks of rows, read a causal depthwise conv's
taps as views of one stack rolled along the sublanes, hand the rows a tile
needs of its neighbour on in VMEM, and sum gradients vreg by vreg. Their
bodies, grids, tiles and ``fits`` are their own; this module is the part none
of them owns, under public names, so that a change to it for one mixer is a
change the other two can see (``tests/test_conv_tiles.py``).
"""

from __future__ import annotations

import jax.numpy as jnp
from jax.experimental.pallas import tpu as pltpu

LANE = 128
# rows the loop inside a step handles at a time (a turn of the loop costs what
# ~100 rows do: 1.88 / 1.35 / 1.17 ms a forward call at 64 / 128 / 256; my chip
# runs, PR 38), and the unit rows come in
CHUNK_ROWS = 256
ROW_UNIT = 64
# rows of the block that holds the positions before a tile: a bfloat16 tile's
# 16 sublanes
HALO = 16
SUBLANES = 8
VMEM_LIMIT = 96 * 1024 * 1024  # of a v5e core's 128 MiB; the default scope is 16


def largest(whole: int, unit: int, most: int) -> int:
    """The largest multiple of ``unit`` that divides ``whole`` and is at most
    ``most`` (``unit`` itself divides it)."""
    return max(n for n in range(unit, max(most, unit) + 1, unit) if whole % n == 0)


def chunks(ref_rows: int):
    """(rows, count) of the chunks the loop inside a step walks a tile in."""
    c_rows = largest(ref_rows, ROW_UNIT, CHUNK_ROWS)
    return c_rows, ref_rows // c_rows


def mosaic_params():
    """Every axis of a three-axis grid in order: an output keeps its block
    between its phases, and the taps' gradient and the rows a tile hands on
    are summed along the grid."""
    return pltpu.CompilerParams(dimension_semantics=("arbitrary",) * 3,
                                vmem_limit_bytes=VMEM_LIMIT)


def windows(x, taps: int, before=None, after=None):
    """x [R, D] with the 8 rows before it (or after it) -> the ``taps`` [R, D]
    views a causal conv of that width reads: ``before`` gives
    ``x_{t-(taps-1)+j}``, ``after`` ``x_{t+(taps-1)-j}``, j = 0..taps-1. A
    view is the whole stack rolled along the sublanes (one rotation a vreg)
    and cut where tiles end: sliced at a row that is no multiple of 8, every
    sum of two views would move one of them (3.13 ms a backward call at
    2 x 8192 x 8192 against 2.08; my chip runs, PR 38)."""
    r, reach = x.shape[0], taps - 1
    if after is None:
        e = jnp.concatenate([before, x], axis=0)
        return [pltpu.roll(e, reach - j, 0)[SUBLANES:] for j in range(reach)] + [x]
    e = jnp.concatenate([x, after], axis=0)
    return [pltpu.roll(e, r + SUBLANES - (reach - j), 0)[:r] for j in range(reach)] + [x]


def tapped(views, w):
    """sum_j views[j] w[j]: the views [R, D] against the taps [len(views), D],
    added from the first on."""
    total = views[0] * w[0:1]
    for j in range(1, len(views)):
        total = total + views[j] * w[j:j + 1]
    return total


def fold(t):
    """[R, D] -> [8, D]: the sum of its vregs (no sublane reduction)."""
    return t.reshape(-1, SUBLANES, t.shape[-1]).sum(axis=0)

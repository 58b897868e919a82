"""Rows added into the rows their ids name, as a Pallas TPU kernel.

``sum_rows(src, ids, n)``   out[i] = sum of src[m] over the m with ids[m] == i
``take_rows(values, ids)``  values[ids], whose gradient is ``add_rows``
``add_rows(rows, ids, n)``  ``sum_rows``, whose gradient is ``take_rows``

The transpose of a row gather ``table[ids]``, and so its gradient. It is
how a held range of experts adds its rows back into their tokens
(``models/moe.py::_held_rows``) and how the embedding table gets its
gradient (``models/llama.py::forward_hidden``): both go through the pair
``take_rows`` / ``add_rows``, XLA's gather and this kernel, each the other's
gradient.

The gather itself stays XLA's: on a v5e it moves a 10 KB row in 0.08 us
and a 4 KB row in 0.007 (PERF.md, PR 42). What XLA does one row at a time
is the transpose, a scatter-add: 3.6-3.8 us a 10 KB row, 0.09-0.21 us a
4-6 KB row, beside a float32 ``[n, E]`` of zeros written, added into and
converted. The sum is therefore the kernel ``moe_rows`` (the name on a
TPU's op line): a float32 sum in VMEM, no float32 ``[n, E]`` and no
scatter in HBM.

It cuts the ``n`` destination rows into tiles of ``tm``, the largest power
of two up to ``ROW_TILE`` that divides ``n`` (16 rows, at a vocabulary of
18,992, cost what 256 do: PERF.md, PR 47); where that is under the 8 rows of
a sublane tile, the sum is made into the next multiple of ``ROW_TILE`` rows
and cut. Before the first
tile the scalar core places the rows by destination tile (a counting sort
whose counts XLA made; a tile's rows keep their order), so each tile owns a
run of (source row, destination row) pairs. Each pair's source row is
fetched from HBM by its own DMA, ``IN_FLIGHT`` of them under way at a time
(the queue runs on across tiles), and added into the tile's float32
accumulator; a destination no row names is written as zeros without a
fetch.

A source row is handed to the kernel as ``[E / 128, 128]``: Mosaic slices
an HBM array only along untiled dimensions (a ``[1, E]`` slice of a
``[R, E]`` array is refused: "must be aligned to tiling (8)"), and a row so
shaped is one contiguous block in HBM and whole vector registers in VMEM,
so adding it costs a handful of vector operations. The reshape before the
call is a pass over the source at bandwidth; the result leaves the kernel
as ``[n, E]``, laid out a lane tile at a time from the accumulator. The
``E / 128`` lane tiles of a row are padded to a multiple of 8 (a width of
2,560 is 20: 24 are fetched, which is what the array's tiled layout holds in
HBM anyway, and 20 laid out): the chip's compiler slices that dimension only
by whole sublane tiles too ("aligned to tiling (8), but is 20"; PR 45).

Off the TPU the kernel runs in the Pallas interpreter. A width that is no
multiple of 128 lanes takes XLA's scatter-add, and so do more rows than the
scalar memory holds ids for (98,304 less the destination tiles; an
embedding's step of 131,072 tokens on one chip would not compile);
``trace_log.kernel_traces()`` says which (``moe_rows:pallas`` /
``:interpret`` / ``:xla``).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..tpu import on_tpu
from .trace_log import note_kernel_cost, note_kernel_trace

ROW_TILE = 256  # destination rows a grid step; a float32 accumulator of 256 x 28 KB at width 7168
IN_FLIGHT = 16  # row copies under way
_VMEM_LIMIT = 64 * 1024 * 1024
_SMEM_LIMIT = 768 * 1024


def _rows_kernel(ids_ref, starts_ref, src_ref, out_ref, pairs_ref, next_ref, acc_ref, buf_ref,
                 sems, *, tm):
    """ids [M]: a source row's destination, -1 for none; starts [tiles + 1]:
    how many rows name a destination below tile i's first, the last entry
    how many name one at all. pairs [M] (SMEM): source row * tm + destination
    row in its tile, tile by tile; pair e lands in slot e % IN_FLIGHT."""
    i = pl.program_id(0)
    tiles = pl.num_programs(0)
    total = starts_ref[tiles]
    shift = tm.bit_length() - 1

    def copy(e):
        slot = e % IN_FLIGHT
        return pltpu.make_async_copy(src_ref.at[pairs_ref[e] >> shift], buf_ref.at[slot],
                                     sems.at[slot])

    def start(e, carry):
        copy(e).start()
        return carry

    @pl.when(i == 0)
    def _place():
        def first(t, carry):
            next_ref[t] = starts_ref[t]
            return carry

        def place(state):
            r, placed = state
            dest = ids_ref[r]

            @pl.when(dest >= 0)
            def _named():
                tile = dest >> shift
                pairs_ref[next_ref[tile]] = r * tm + (dest & (tm - 1))
                next_ref[tile] += 1

            return r + 1, placed + (dest >= 0).astype(jnp.int32)

        jax.lax.fori_loop(0, tiles, first, None)
        # up to the last row that names a destination: a held range's rows
        # past its end, half of ``cap`` under even routing, are not walked
        jax.lax.while_loop(lambda state: state[1] < total, place, (0, 0))
        jax.lax.fori_loop(0, jnp.minimum(IN_FLIGHT - 1, total), start, None)

    acc_ref[...] = jnp.zeros_like(acc_ref)

    def add(e, carry):
        copy(e).wait()
        acc_ref[pairs_ref[e] & (tm - 1)] += buf_ref[e % IN_FLIGHT].astype(jnp.float32)
        # into the slot the pair before this one was read from: a whole turn
        # of the loop lies between that read and this copy's first write
        ahead = e + IN_FLIGHT - 1

        @pl.when(ahead < total)
        def _next():
            copy(ahead).start()

        return carry

    jax.lax.fori_loop(starts_ref[i], starts_ref[i + 1], add, None)

    def lay(c, carry):
        lane = pl.multiple_of(c * 128, 128)
        out_ref[:, pl.ds(lane, 128)] = acc_ref[:, c, :].astype(out_ref.dtype)
        return carry

    jax.lax.fori_loop(0, out_ref.shape[1] // 128, lay, None)


def _tile(n: int) -> int:
    """Destination rows a grid step: the largest power of two up to
    ``ROW_TILE`` that divides ``n``."""
    tm = ROW_TILE
    while n % tm:
        tm //= 2
    return tm


def _ids_fit(m: int, tiles: int) -> bool:
    """Whether the scalar memory holds the kernel's ids and pairs [m], starts
    and next [tiles], int32: a v5e has 1 MiB, and its compiler takes 97,280
    rows into 501 tiles and refuses 131,072."""
    return 8 * (m + tiles) <= _SMEM_LIMIT


def sum_rows(src, ids, n: int, *, interpret: bool | None = None):
    """``out[i] = sum of src[m] over the m with ids[m] == i``, summed in
    float32, zero for an ``i`` no row names; a row whose id is -1 is added
    nowhere: src [M, E], ids [M] int32 in [-1, n) (device values) -> [n, E]
    in src's dtype. Not differentiable by itself: its gradient for ``src``
    is the gather ``g[ids]``, which ``add_rows`` pairs it with."""
    if interpret is None:
        interpret = not on_tpu()
    m, e = src.shape
    tm = _tile(n)
    if tm < 8 and not e % 128:
        # a vocabulary of 50,257: the chip's compiler takes an output block of
        # whole sublane tiles only
        whole = -(-n // ROW_TILE) * ROW_TILE
        return sum_rows(src, jnp.where(ids < n, ids, -1), whole, interpret=interpret)[:n]
    if e % 128 or not _ids_fit(m, n // tm):
        note_kernel_trace("moe_rows", "xla")
        out = jnp.zeros((n, e), jnp.float32).at[jnp.where(ids >= 0, ids, n)].add(
            src.astype(jnp.float32), mode="drop")
        return out.astype(src.dtype)
    note_kernel_trace("moe_rows", "interpret" if interpret else "pallas")
    note_kernel_cost("moe_rows", 0, (m + n) * e * src.dtype.itemsize)  # every row named, at most
    lanes = -(-e // 1024) * 8  # a row's lane tiles, in whole sublane tiles
    ids = jnp.where(ids < n, ids, -1)  # the kernel's scalar memory has no bounds check
    # rows a tile, as a compare-and-sum (``models/moe.py::route`` says why)
    tile = jnp.where(ids >= 0, ids // tm, -1)
    per_tile = jnp.sum(tile[:, None] == jnp.arange(n // tm)[None, :], axis=0, dtype=jnp.int32)
    starts = jnp.concatenate([jnp.zeros((1,), jnp.int32), jnp.cumsum(per_tile)])
    kernel = pl.pallas_call(
        functools.partial(_rows_kernel, tm=tm),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(n // tm,),
            in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec((tm, e), lambda i, ids, starts: (i, 0)),
            scratch_shapes=[pltpu.SMEM((m,), jnp.int32),
                            pltpu.SMEM((n // tm,), jnp.int32),
                            pltpu.VMEM((tm, lanes, 128), jnp.float32),
                            pltpu.VMEM((IN_FLIGHT, lanes, 128), src.dtype),
                            pltpu.SemaphoreType.DMA((IN_FLIGHT,))],
        ),
        out_shape=jax.ShapeDtypeStruct((n, e), src.dtype),
        compiler_params=pltpu.CompilerParams(dimension_semantics=("arbitrary",),
                                             vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
        name="moe_rows",
    )
    rows = src.reshape(m, e // 128, 128)
    if lanes != e // 128:
        rows = jnp.pad(rows, ((0, 0), (0, lanes - e // 128), (0, 0)))
    return kernel(ids, starts, rows)


@jax.custom_vjp
def take_rows(values, ids):
    """``values[ids]``: values [N, E], ids [M] int32 in [0, N) -> [M, E]. The
    gradient adds each row back into the row it was read from with
    ``add_rows``, where the gather's own transpose would be a scatter-add of
    rows. Out of range: an id of -1 (a held range's rows past its end) reads
    row 0, where plain indexing would wrap to the last row, and an id of N or
    more reads row N - 1 as plain indexing does; the gradient adds such a
    row nowhere. The grouped matmul computes no row outside its groups and
    hands none a gradient, so nothing masks a -1 row here (a select fused
    into XLA's gather cost it 0.29 -> 0.77 ms at 40,960 rows of 4 KB;
    PERF.md, PR 42)."""
    return values[jnp.maximum(ids, 0)]


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def add_rows(rows, ids, n):
    """``out[t] = sum of the rows whose id is t``, float32 sums, [n, E]:
    ``sum_rows``, the kernel ``moe_rows``. Its gradient is ``take_rows``:
    each is the other's transpose on the rows that name a row in [0, n)."""
    return sum_rows(rows, ids, n)


take_rows.defvjp(lambda values, ids: (take_rows(values, ids), (ids, values.shape[0])),
                 lambda res, g: (add_rows(g, *res), None))
add_rows.defvjp(lambda rows, ids, n: (add_rows(rows, ids, n), ids),
                lambda n, ids, g: (take_rows(g, ids), None))

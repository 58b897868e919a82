"""Grouped matmul over ragged row groups, as Pallas TPU kernels.

``lhs`` [M, K] holds rows sorted by group: the first ``group_sizes[0]``
rows belong to group 0, the next ``group_sizes[1]`` to group 1, and so on.
``grouped_matmul`` multiplies every row by its own group's matrix,
``rhs[g]`` [K, N]. The group sizes are device values: the shapes are
static, the boundaries are not. This is the expert product of a dropless
mixture-of-experts layer (``models/moe.py``): rows are (token, expert)
pairs sorted by expert, and no row is padded or dropped whatever the
routing's skew.

Two kernels, named for the trace (``pallas_call(name=)`` becomes the HLO
instruction's name on a TPU's op line):

``moe_gmm``   out[rows of g] = lhs[rows of g] @ rhs[g]   (or @ rhs[g]^T)
``moe_tgmm``  out[g] = lhs[rows of g]^T @ dout[rows of g]

The row axis is cut into tiles of ``tm`` rows. A tile that straddles a
group boundary is visited once per group it touches ("work items": at
most ``M / tm + G - 1`` of them, a static bound; the list of (group,
tile) pairs is computed on the device and handed to the kernel as
prefetched scalars), and each visit stores or accumulates only its own
group's rows. ``moe_gmm`` backs ``grouped_matmul`` and its gradient for
``lhs`` (the same product against ``rhs[g]^T``); ``moe_tgmm`` is the
gradient for ``rhs``.

Every visit multiplies all ``tm`` rows of its tile, so the tiles follow the
rows a group holds: ``gmm_tiles`` / ``tgmm_tiles`` choose (tm, tk, tn) when
a call is traced, from its static shapes alone (rows over groups, K, N, the
item size). At every row count the whole contraction is one block (a
group's matrix is then fetched once a group, not once a visit) beside the
widest column block that fits VMEM; the row tile is 128 rows under 2,048
rows a group and 256 at and above. Measured on a v5e, every product of a
layer alone: under 2,048 at four held-range cells' shapes (PERF.md, PR
43), at and above at 49,152 rows over 16 groups of 2560 x 768 and 131,072
over 64 of 2048 x 1024 (PERF.md, PR 46). ``tile_visits``
counts visits and rows multiplied for any routing without a chip;
``trace_log.kernel_costs()`` holds what a traced call chose (``tiles``,
``work_items``, ``rhs_resident``).

Off the TPU both kernels run in the Pallas interpreter, so the CPU tests
exercise the same tiling. A row count no tile divides takes
``jax.lax.ragged_dot``; ``trace_log.kernel_traces()`` says which path
each traced call took, so a quiet fall-back on the chip is visible.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..tpu import on_tpu
from .trace_log import note_kernel_cost, note_kernel_trace

# (tm, tk, tn) everywhere below: rows, contraction and output columns of one
# tile (``moe_tgmm``: rows, and the two sides of a group's output block).
_VMEM_LIMIT = 96 * 1024 * 1024  # a v5e core has 128 MiB; the default scope is 16
_VMEM_BLOCKS = 72 * 1024 * 1024  # of it for the rule's blocks; the rest is Mosaic's own


def _fit(requested: int, dim: int, align: int) -> int | None:
    """The whole of ``dim`` if it is at most ``requested``, else the largest
    multiple of ``align`` up to ``requested`` that divides it (None: none)."""
    if dim <= requested:
        return dim
    t = requested - requested % align
    while t >= align and dim % t:
        t -= align
    return t if t >= align else None


def _fit_tiles(tiles, m: int, k: int, n: int, dtype) -> tuple:
    """``tiles`` cut to divisors of the three dimensions, rows to the dtype's
    sublane packing and the others to lanes; None where nothing divides."""
    align = 16 if jnp.dtype(dtype).itemsize == 2 else 8
    return _fit(tiles[0], m, align), _fit(tiles[1], k, 128), _fit(tiles[2], n, 128)


def _gmm_vmem(tm: int, tk: int, tn: int, itemsize: int) -> int:
    """Bytes of ``moe_gmm``'s blocks: the lhs tile, the matrix block and the
    output tile, each buffered twice, and the float32 accumulator."""
    return 2 * itemsize * (tm * tk + tk * tn + tm * tn) + 4 * tm * tn


def _tgmm_vmem(tm: int, tk: int, tn: int, itemsize: int, out_itemsize: int) -> int:
    """Bytes of ``moe_tgmm``'s blocks: the two row tiles and the group's
    output block, each buffered twice, and the float32 accumulator."""
    return 2 * itemsize * (tm * tk + tm * tn) + (2 * out_itemsize + 4) * tk * tn


def _widest(dim: int, fits) -> int:
    """The widest block of ``dim`` (all of it, or a lane-aligned divisor)
    that ``fits``, and the narrowest where none does: Mosaic then refuses the
    call by its VMEM limit. That takes a contraction of ~36,000 columns
    beside a 128-lane block, five times the widest any cell has."""
    blocks = [dim] + [t for t in range((dim - 1) // 128 * 128, 0, -128) if dim % t == 0]
    return next((t for t in blocks if fits(t)), blocks[-1])


def _whole_blocks(m: int, groups: int, k: int, n: int, vmem) -> tuple[int, int, int]:
    """The rule: all of ``k``, the widest ``tn`` whose blocks, counted by
    ``vmem(tm, tk, tn)``, fit ``_VMEM_BLOCKS``, and a row tile of 128 rows
    where the groups would hold under 2,048 if ``m`` fell evenly on them,
    256 at and above.

    A tile that straddles a boundary is visited once a group it touches and
    every visit multiplies all its rows, so the row tile is small: at 192,
    640, 1,024 and 1,280 rows a group, of which a held range fills about
    half, 128 (the MXU's own side) read within 1% of the best of 64-512 in
    all six products of a layer (PERF.md, PR 43). At 3,072 rows a group of
    which 1,533 are valid a layer's products read 4% less at 256 than at
    128 or 512, and at 2,048 all valid 2% and 0.6% less (``moe_tgmm`` level
    with 512 there): half of 128's grid steps, of which a held range's
    ``cap`` leaves half dead, for 6-7% more rows multiplied. The widest
    ``tn`` was the best at both, and the whole contraction at every row
    tile (PERF.md, PR 46)."""
    tm = 128 if m // groups < 2048 else 256
    return tm, k, _widest(n, lambda tn: vmem(tm, k, tn) <= _VMEM_BLOCKS)


def gmm_tiles(m: int, groups: int, k: int, n: int, itemsize: int = 2) -> tuple[int, int, int]:
    """``moe_gmm``'s (tm, tk, tn) for lhs [m, k] against ``groups`` matrices
    [k, n] or, transposed, [n, k] (the blocks are the same bytes), before
    ``_fit_tiles``.

    The WHOLE contraction is one block: a group's matrix block then keeps
    its index over the group's consecutive visits and is fetched once a
    group and column block, which a small row tile's products would not
    hide; the wider ``tn``, the fewer times the rows are read again (at
    7168 x 2048 the whole matrix, 29 MB twice buffered: the call then runs
    at the matrices' bytes)."""
    return _whole_blocks(m, groups, k, n, lambda *tiles: _gmm_vmem(*tiles, itemsize))


def tgmm_tiles(m: int, groups: int, k: int, n: int, itemsize: int = 2,
               out_itemsize: int = 2) -> tuple[int, int, int]:
    """``moe_tgmm``'s (tm, tk, tn) for lhs [m, k] and dout [m, n] into
    ``groups`` blocks [k, n], before ``_fit_tiles``. The contraction is over
    ROWS; ``tk`` and ``tn`` cut a group's output block, and the rows are read
    once a block of the other side: ``tk`` is all of ``k`` (the float32
    accumulator and the twice-buffered block are 8 bytes an element of the
    block: 15.7 MB at 2560 x 768, 16.8 at 2048 x 1024)."""
    return _whole_blocks(
        m, groups, k, n, lambda *tiles: _tgmm_vmem(*tiles, itemsize, out_itemsize))


def _group_tiles(group_sizes, row_offset, m: int, tm: int):
    """Per group: absolute first row and end, the first row tile it touches
    and how many it touches (0 for an empty group)."""
    group_sizes = group_sizes.astype(jnp.int32)
    ends = jnp.cumsum(group_sizes) + (0 if row_offset is None else row_offset)
    starts = ends - group_sizes
    first = jnp.minimum(starts // tm, m // tm - 1)
    n_tiles = jnp.where(group_sizes > 0, (ends + tm - 1) // tm - first, 0)
    return starts, ends, first, n_tiles


def tile_visits(group_sizes, m: int, tm: int, row_offset=None) -> tuple[int, int]:
    """(visits, rows multiplied) of either kernel over ``m`` rows in tiles of
    ``tm`` under this routing: a tile is visited once per group it holds
    rows of, and every visit multiplies all ``tm`` rows. Rows multiplied
    over ``sum(group_sizes)`` is what the tiling costs; the kernels' own
    ``n_work`` is the same count (``_work_items``)."""
    n_tiles = _group_tiles(jnp.asarray(group_sizes), row_offset, m, tm)[3]
    visits = int(jnp.sum(n_tiles))
    return visits, visits * tm


def _work_items(group_sizes, row_offset, m: int, tm: int):
    """The (group, row tile) pairs the kernels visit, in row order.

    Returns ``offsets`` [G+1] (absolute first row of each group, and the end
    of the last), ``gids`` and ``tiles`` [W] and ``n_work`` [1], all int32;
    W = M/tm + G - 1 is static. Items past ``n_work`` repeat the last real
    one and the kernels skip them, so no block index changes for them."""
    g = group_sizes.shape[0]
    starts, ends, first, n_tiles = _group_tiles(group_sizes, row_offset, m, tm)
    work_ends = jnp.cumsum(n_tiles)
    n_work = work_ends[-1]
    w = jnp.minimum(jnp.arange(m // tm + g - 1, dtype=jnp.int32),
                    jnp.maximum(n_work - 1, 0))
    gids = jnp.minimum(jnp.searchsorted(work_ends, w, side="right"), g - 1)
    gids = gids.astype(jnp.int32)
    tiles = first[gids] + w - (work_ends - n_tiles)[gids]
    offsets = jnp.concatenate([starts[:1], ends]).astype(jnp.int32)
    return offsets, gids, tiles.astype(jnp.int32), n_work.reshape(1)


def _rows_in_group(offs_ref, gid_ref, tile_ref, w, tm):
    """[tm, 1] mask of this tile's rows that belong to item ``w``'s group,
    and whether that is all of them."""
    g = gid_ref[w]
    start, end = offs_ref[g], offs_ref[g + 1]
    row0 = tile_ref[w] * tm
    rows = row0 + jax.lax.broadcasted_iota(jnp.int32, (tm, 1), 0)
    whole = jnp.logical_and(start <= row0, row0 + tm <= end)
    return jnp.logical_and(rows >= start, rows < end), whole


def _gmm_kernel(offs_ref, gid_ref, tile_ref, nwork_ref, lhs_ref, rhs_ref,
                out_ref, acc_ref, *, tm, n_k, transpose_rhs):
    w, ki = pl.program_id(1), pl.program_id(2)
    real = w < nwork_ref[0]

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(real)
    def _compute():
        contract = (((1,), (1,)), ((), ())) if transpose_rhs else (((1,), (0,)), ((), ()))
        acc_ref[...] += jax.lax.dot_general(
            lhs_ref[...], rhs_ref[0], contract, preferred_element_type=jnp.float32)

    @pl.when(jnp.logical_and(real, ki == n_k - 1))
    def _store():
        mask, whole = _rows_in_group(offs_ref, gid_ref, tile_ref, w, tm)

        @pl.when(whole)
        def _all():
            out_ref[...] = acc_ref[...].astype(out_ref.dtype)

        @pl.when(jnp.logical_not(whole))
        def _some():
            # the tile's other rows keep what the neighbouring group's item
            # wrote: consecutive items on one tile share the output block
            out_ref[...] = jnp.where(mask, acc_ref[...].astype(out_ref.dtype),
                                     out_ref[...])


def _tgmm_kernel(offs_ref, gid_ref, tile_ref, nwork_ref, lhs_ref, dout_ref,
                 out_ref, acc_ref, *, tm, n_w):
    w = pl.program_id(2)
    g = gid_ref[w]
    first = jnp.logical_or(w == 0, gid_ref[jnp.maximum(w - 1, 0)] != g)
    last = jnp.logical_or(w == n_w - 1, gid_ref[jnp.minimum(w + 1, n_w - 1)] != g)

    @pl.when(first)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(w < nwork_ref[0])
    def _compute():
        mask, whole = _rows_in_group(offs_ref, gid_ref, tile_ref, w, tm)
        contract = (((0,), (0,)), ((), ()))

        @pl.when(whole)
        def _all():
            acc_ref[...] += jax.lax.dot_general(
                lhs_ref[...], dout_ref[...], contract,
                preferred_element_type=jnp.float32)

        @pl.when(jnp.logical_not(whole))
        def _some():
            dout = dout_ref[...]
            acc_ref[...] += jax.lax.dot_general(
                lhs_ref[...], jnp.where(mask, dout, jnp.zeros_like(dout)), contract,
                preferred_element_type=jnp.float32)

    @pl.when(last)
    def _store():
        out_ref[0] = acc_ref[...].astype(out_ref.dtype)


def _gmm(lhs, rhs, group_sizes, row_offset, *, transpose_rhs, tiles, interpret):
    """lhs [M, K] @ rhs[g] ([G, K, N], or [G, N, K] transposed) -> [M, N]."""
    m, k = lhs.shape
    n = rhs.shape[1] if transpose_rhs else rhs.shape[2]
    if tiles is None:
        tiles = gmm_tiles(m, rhs.shape[0], k, n, lhs.dtype.itemsize)
    tm, tk, tn = _fit_tiles(tiles, m, k, n, lhs.dtype)
    if None in (tm, tk, tn):
        note_kernel_trace("moe_gmm", "ragged_dot")
        return _ragged_reference(lhs, jnp.swapaxes(rhs, 1, 2) if transpose_rhs else rhs,
                                 group_sizes, row_offset)
    note_kernel_trace("moe_gmm", "interpret" if interpret else "pallas")
    scalars = _work_items(group_sizes, row_offset, m, tm)
    n_k = k // tk
    note_kernel_cost("moe_gmm", 2.0 * m * k * n,
                     (m * k + m * n) * lhs.dtype.itemsize + rhs.size * rhs.dtype.itemsize,
                     tiles=[tm, tk, tn], work_items=scalars[1].shape[0],
                     rhs_resident=n_k == 1)
    if transpose_rhs:
        rhs_spec = pl.BlockSpec((1, tn, tk), lambda ni, w, ki, o, g, t, c: (g[w], ni, ki))
    else:
        rhs_spec = pl.BlockSpec((1, tk, tn), lambda ni, w, ki, o, g, t, c: (g[w], ki, ni))
    out = pl.pallas_call(
        functools.partial(_gmm_kernel, tm=tm, n_k=n_k, transpose_rhs=transpose_rhs),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(n // tn, scalars[1].shape[0], n_k),
            in_specs=[
                pl.BlockSpec((tm, tk), lambda ni, w, ki, o, g, t, c: (t[w], ki)),
                rhs_spec,
            ],
            out_specs=pl.BlockSpec((tm, tn), lambda ni, w, ki, o, g, t, c: (t[w], ni)),
            scratch_shapes=[pltpu.VMEM((tm, tn), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((m, n), lhs.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
        name="moe_gmm",
    )(*scalars, lhs, rhs)
    if row_offset is not None:
        # rows outside this call's groups were never written
        rows = jnp.arange(m, dtype=jnp.int32)[:, None]
        out = jnp.where((rows >= scalars[0][0]) & (rows < scalars[0][-1]), out, 0)
    return out


def _tgmm(lhs, dout, group_sizes, row_offset, *, out_dtype, tiles, interpret):
    """out[g] = lhs[rows of g]^T @ dout[rows of g]: [G, K, N]."""
    (m, k), n = lhs.shape, dout.shape[1]
    g = group_sizes.shape[0]
    if tiles is None:
        tiles = tgmm_tiles(m, g, k, n, lhs.dtype.itemsize, jnp.dtype(out_dtype).itemsize)
    tm, tk, tn = _fit_tiles(tiles, m, k, n, lhs.dtype)
    if None in (tm, tk, tn):
        note_kernel_trace("moe_tgmm", "ragged_dot")
        return _ragged_transposed_reference(lhs, dout, group_sizes, row_offset, out_dtype)
    note_kernel_trace("moe_tgmm", "interpret" if interpret else "pallas")
    scalars = _work_items(group_sizes, row_offset, m, tm)
    n_w = scalars[1].shape[0]
    # ``rhs_resident`` here: ``tk`` spans lhs's width, so ``dout``'s row tiles
    # are read once (the group's output block is resident in either case)
    note_kernel_cost("moe_tgmm", 2.0 * m * k * n,
                     (m * k + m * n) * lhs.dtype.itemsize
                     + g * k * n * jnp.dtype(out_dtype).itemsize,
                     tiles=[tm, tk, tn], work_items=n_w, rhs_resident=tk == k)
    out = pl.pallas_call(
        functools.partial(_tgmm_kernel, tm=tm, n_w=n_w),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(k // tk, n // tn, n_w),
            in_specs=[
                pl.BlockSpec((tm, tk), lambda ki, ni, w, o, g, t, c: (t[w], ki)),
                pl.BlockSpec((tm, tn), lambda ki, ni, w, o, g, t, c: (t[w], ni)),
            ],
            out_specs=pl.BlockSpec((1, tk, tn), lambda ki, ni, w, o, g, t, c: (g[w], ki, ni)),
            scratch_shapes=[pltpu.VMEM((tk, tn), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((g, k, n), out_dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
        name="moe_tgmm",
    )(*scalars, lhs, dout)
    # an empty group has no work item: its block was never written
    return jnp.where((group_sizes > 0)[:, None, None], out, 0)


def _group_of_rows(group_sizes, row_offset, m):
    """Group id of each of ``m`` rows, G for rows outside every group."""
    ends = jnp.cumsum(group_sizes) + (0 if row_offset is None else row_offset)
    rows = jnp.arange(m, dtype=ends.dtype)
    gid = jnp.searchsorted(ends, rows, side="right")
    return jnp.where(rows >= ends[0] - group_sizes[0], gid, group_sizes.shape[0])


def _ragged_reference(lhs, rhs, group_sizes, row_offset):
    """The fall-back for a row count no tile divides: ``lax.ragged_dot``,
    with rows outside the groups zero."""
    if row_offset is None:
        return jax.lax.ragged_dot(lhs, rhs, group_sizes.astype(jnp.int32))
    sizes = jnp.concatenate([jnp.reshape(row_offset, (1,)), group_sizes]).astype(jnp.int32)
    padded = jnp.concatenate([jnp.zeros_like(rhs[:1]), rhs])
    out = jax.lax.ragged_dot(lhs, padded, sizes)
    inside = _group_of_rows(group_sizes, row_offset, lhs.shape[0]) < group_sizes.shape[0]
    return jnp.where(inside[:, None], out, 0)


def _ragged_transposed_reference(lhs, dout, group_sizes, row_offset, out_dtype):
    g = group_sizes.shape[0]
    onehot = jax.nn.one_hot(_group_of_rows(group_sizes, row_offset, lhs.shape[0]), g,
                            dtype=lhs.dtype)
    return jnp.einsum("mg,mk,mn->gkn", onehot, lhs, dout,
                      preferred_element_type=jnp.float32).astype(out_dtype)


@functools.lru_cache(maxsize=None)
def _make(gmm_tiles, tgmm_tiles, interpret, offset_given):
    kw = dict(interpret=interpret)

    @jax.custom_vjp
    def f(lhs, rhs, group_sizes, row_offset):
        return _gmm(lhs, rhs, group_sizes, row_offset if offset_given else None,
                    transpose_rhs=False, tiles=gmm_tiles, **kw)

    def fwd(lhs, rhs, group_sizes, row_offset):
        return f(lhs, rhs, group_sizes, row_offset), (lhs, rhs, group_sizes, row_offset)

    def bwd(res, g):
        lhs, rhs, group_sizes, row_offset = res
        offset = row_offset if offset_given else None
        # an override's tk and tn swap: the contraction is now over rhs's last axis
        swapped = gmm_tiles and (gmm_tiles[0], gmm_tiles[2], gmm_tiles[1])
        d_lhs = _gmm(g, rhs, group_sizes, offset, transpose_rhs=True, tiles=swapped, **kw)
        d_rhs = _tgmm(lhs, g, group_sizes, offset, out_dtype=rhs.dtype,
                      tiles=tgmm_tiles, **kw)
        return d_lhs, d_rhs, None, None

    f.defvjp(fwd, bwd)
    return f


def grouped_matmul(lhs, rhs, group_sizes, *, row_offset=None,
                   gmm_tiles=None, tgmm_tiles=None,
                   interpret: bool | None = None):
    """``out[i] = lhs[i] @ rhs[group of row i]``: lhs [M, K], rhs [G, K, N],
    group_sizes [G] int32 (device values) -> [M, N] in lhs's dtype, float32
    accumulation. Differentiable in ``lhs`` and ``rhs``.

    Rows are sorted by group. Without ``row_offset`` the groups start at
    row 0 and must cover all M rows. With it (an int32 scalar on the
    device: expert parallelism, where this device holds a contiguous range
    of the experts) the groups start at that row, and rows outside them
    come back zero, as do their gradients.

    The tiles follow the shapes (``gmm_tiles`` / ``tgmm_tiles`` the
    functions, above); the arguments of those names override them (tests).
    """
    if interpret is None:
        interpret = not on_tpu()
    offset = jnp.zeros((), jnp.int32) if row_offset is None else row_offset
    return _make(gmm_tiles and tuple(gmm_tiles), tgmm_tiles and tuple(tgmm_tiles), interpret,
                 row_offset is not None)(lhs, rhs, group_sizes, offset)

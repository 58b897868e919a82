"""Mamba-2's selective state-space scan (SSD, arXiv 2405.21060) in chunks, as two
Pallas TPU kernels.

Per head ``h`` of ``P`` features over a state of ``N`` features, with a float32
state ``S`` [P, N] that starts at zero, a step ``dt_t > 0`` of the head and the
position, a rate ``A_h < 0`` and a skip ``D_h`` of the head, and ONE ``B_t`` and
``C_t`` [N] for all heads of a position:

    S_t = exp(dt_t A_h) S_{t-1} + dt_t x_t (x) B_t
    y_t = S_t C_t + D_h x_t

The decay ``exp(dt_t A_h)`` is a function of the token AND of a parameter
(``ops/lightning_attention.py``'s is one constant a head; ``ops/gated_delta.py``
has a gate a position, under the delta rule's inverse), and the input is
weighted by ``dt``. ``ssd`` computes it ``chunk`` positions at a time. With ``S``
the state before a chunk, ``l_i`` the running sum of ``dt A_h`` inside the chunk
up to and with position i (all <= 0), ``G = C B^T`` [Q, Q] and
``L[i, j] = exp(l_i - l_j)`` for ``j <= i``, else 0:

    Y  = (G * L * dt_j) X + exp(l_i) (C S^T) + D X
    S <- exp(l_Q) S + (dt_j exp(l_Q - l_j) X)^T B

No power is divided by: every exponent is a sum of ``dt A`` over positions that
lie between, so a head whose chunk decays to nothing underflows to exact zeros
and nothing overflows. ``l`` is made outside the kernels (a product with a
triangle of ones at ``highest``), and JAX differentiates it: the kernels take
``dt`` and ``l`` [B, H, T] float32 and hand back gradients for both.

Layout. A head of 64 features is HALF a lane tile, so ``x`` comes with
``r = 128 / P`` heads side by side in a tile: [B, H / r, T, r P] (heads of 128 or
more: r = 1, the usual [B, H, T, P]). A tile's heads share every product
against the state (``C S^T`` gives all 128 lanes at once, ``(w X)^T B`` all 128
rows) and take the chunk-local product a head at a time, the other heads'
lanes as zeros. Per-head numbers of a position live as ROWS [1, Q] (heads on
sublanes in HBM); where a column [Q, 1] is needed it is made in VMEM from the
row by a masked sum over a [Q, Q] diagonal.

``ssd_fwd``  grid (batch, chunk): ALL heads of a chunk in one step, so ``G`` is
             one product a chunk for the 64 heads; a loop over the tiles, the
             states [H / r, r P, N] float32 in VMEM (2 MB at 64 x 64 x 128).
``ssd_bwd``  grid (batch, head group, 2 x chunks), as ``lightning_bwd`` /
             ``gdn_bwd``: the first pass runs the recurrence again and keeps
             every chunk's starting state in VMEM, the second walks the chunks
             backwards with the state's cotangent. No ``[chunks, heads, P, N]``
             array of states is ever in HBM; the heads go in groups so that a
             group's states fit (``_BWD_STATE_BYTES``: 8 heads at 128 chunks),
             each group making ``G`` for itself and its own share of dB and
             dC, summed outside.

Products. ``C B^T`` and ``dY X^T`` take bfloat16 operands as they are. Every
product with a float32 side (the decayed scores, the state, its cotangent)
runs that side as three bfloat16 pieces against the other side's bfloat16
(``gated_delta._pieces``: all 24 bits); float32 against float32 (the tests'
inputs) is ``highest``.

``ssd_scan`` is the recurrence as written above, a ``lax.scan`` over positions
in float32 at ``highest``: the kernels' oracle, and with ``state_dtype`` the
bfloat16-state control the comparisons must refuse.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..tpu import on_tpu
from .lightning_attention import _mm
from .trace_log import note_kernel_cost, note_kernel_trace

LANES = 128
_VMEM_LIMIT = 100 * 1024 * 1024  # of a v5e core's 128 MiB; the default scope is 16
# what the backward kernel may keep of a head group's chunk states
_BWD_STATE_BYTES = 32 * 1024 * 1024
HIGHEST = lax.Precision.HIGHEST


def heads_a_tile(p: int) -> int:
    """Heads side by side in one lane tile: 128 / P for heads under 128
    features, else 1."""
    if p < LANES and LANES % p:
        raise ValueError(f"heads of {p} features do not divide a lane tile")
    return max(LANES // p, 1)


def chunk_sums(a, chunk: int):
    """a [B, H, T] -> the running sum of a inside each chunk, up to and with
    a position; float32, every bit (a product with a triangle of ones)."""
    b, h, t = a.shape
    i = jnp.arange(chunk)
    ones = (i[:, None] >= i[None, :]).astype(jnp.float32)
    chunks = a.astype(jnp.float32).reshape(b, h, t // chunk, chunk)
    return jnp.einsum("ij,bhcj->bhci", ones, chunks, precision=HIGHEST).reshape(b, h, t)


def ssd_scan(x, dt, a, bm, cm, d, *, state_dtype=jnp.float32):
    """The recurrence position by position: x [B, H / r, T, r P], dt [B, H, T]
    float32, a and d [H], bm and cm [B, T, N] -> y float32, x's shape."""
    b, tiles, t, w = x.shape
    h = dt.shape[1]
    p = w * tiles // h
    hi = functools.partial(jnp.einsum, precision=HIGHEST)
    f32 = lambda v: v.astype(jnp.float32)  # noqa: E731
    xs = jnp.moveaxis(f32(x).reshape(b, tiles, t, h // tiles, p), 2, 0).reshape(t, b, h, p)
    a, d = f32(a)[None, :], f32(d)[None, :, None]

    def step(s, row):
        x_t, dt_t, b_t, c_t = row
        s = (jnp.exp(dt_t * a)[..., None, None] * f32(s)
             + hi("bhp,bn->bhpn", x_t * dt_t[..., None], b_t)).astype(state_dtype)
        return s, hi("bhpn,bn->bhp", f32(s), c_t) + d * x_t

    s0 = jnp.zeros((b, h, p, bm.shape[-1]), state_dtype)
    _, y = lax.scan(step, s0, (xs, jnp.moveaxis(f32(dt), 2, 0), jnp.moveaxis(f32(bm), 1, 0),
                               jnp.moveaxis(f32(cm), 1, 0)))
    return jnp.moveaxis(y.reshape(t, b, tiles, h // tiles, p), 0, 2).reshape(x.shape)


def _marks(q: int, w: int, p: int):
    """Index masks of a chunk: the causal triangle and the diagonal [Q, Q],
    which head of its tile a lane [1, W] and a state row [W, 1] belongs to."""
    i = lax.broadcasted_iota(jnp.int32, (q, q), 0)
    j = lax.broadcasted_iota(jnp.int32, (q, q), 1)
    lane = lax.broadcasted_iota(jnp.int32, (1, w), 1) // p
    row = lax.broadcasted_iota(jnp.int32, (w, 1), 0) // p
    return j <= i, i == j, lane, row


def _col(row, eye):
    """A row [1, Q] as a column [Q, 1]."""
    return jnp.sum(jnp.where(eye, row, 0.0), axis=1, keepdims=True)


def _row(col, eye):
    """A column [Q, 1] as a row [1, Q]."""
    return jnp.sum(jnp.where(eye, col, 0.0), axis=0, keepdims=True)


def _decayed(l_row, l_col, causal):
    """``L`` [Q, Q]: exp(l_i - l_j) on and under the diagonal, else 0."""
    return jnp.where(causal, jnp.exp(jnp.minimum(l_col - l_row, 0.0)), 0.0)


def _next_state(s, x, bm, w_map, whole_rows):
    """``exp(l_Q) S + (w X)^T B`` for a tile's heads: s [W, N], x [Q, W], w_map
    [Q, W] (a head's ``dt_j exp(l_Q - l_j)`` on its lanes), whole_rows [W, 1]."""
    return whole_rows * s + _mm(x.astype(jnp.float32) * w_map, bm, (0, 0))


def _head_maps(l_ref, dt_ref, first, r, q, eye, lane, row):
    """What a tile's ``r`` heads (rows ``first``.. of the refs' blocks) bring:
    per head its rows and columns (``l``, ``dt``, ``l`` as a column, ``exp(l_Q -
    l_j)``, ``l_Q``, ``dt_j exp(l_Q - l_j)``), and over the tile's lanes the maps
    ``exp(l_i)``, ``dt_j exp(l_Q - l_j)`` [Q, W] and ``exp(l_Q)`` by state row
    [W, 1]."""
    heads, e_map, w_map, whole_rows = [], 0.0, 0.0, 0.0
    for k in range(r):
        l_row, dt_row = l_ref[0, pl.ds(first + k, 1), :], dt_ref[0, pl.ds(first + k, 1), :]
        l_col, dt_col = _col(l_row, eye), _col(dt_row, eye)
        # the chunk's whole sum [1, 1]: a masked sum, which lands on lane 0 (a
        # slice of the last lane keeps its offset, and no broadcast takes that)
        total = jnp.sum(jnp.where(eye[q - 1:], l_row, 0.0), axis=1, keepdims=True)
        rest = jnp.exp(total - l_col)
        heads.append((l_row, dt_row, l_col, rest, total, dt_col * rest))
        e_map = jnp.where(lane == k, jnp.exp(l_col), e_map)
        w_map = jnp.where(lane == k, heads[-1][-1], w_map)
        whole_rows = jnp.where(row == k, jnp.exp(total), whole_rows)
    return heads, e_map, w_map, whole_rows


def _fwd_kernel(x_ref, b_ref, c_ref, dt_ref, l_ref, d_ref, y_ref, s_ref, *, tiles, r, p):
    q, w = x_ref.shape[2], x_ref.shape[3]

    @pl.when(pl.program_id(1) == 0)
    def _init():
        s_ref[...] = jnp.zeros_like(s_ref)

    causal, eye, lane, row = _marks(q, w, p)
    bm, cm = b_ref[0], c_ref[0]
    g = _mm(cm, bm, (1, 1))

    def tile(c, carry):
        x, s = x_ref[0, c], s_ref[c]
        heads, e_map, w_map, whole_rows = _head_maps(l_ref, dt_ref, c * r, r, q, eye, lane, row)
        y = e_map * _mm(cm, s, (1, 1)) + d_ref[c] * x.astype(jnp.float32)
        for k, (l_row, dt_row, l_col, *_) in enumerate(heads):
            m = _decayed(l_row, l_col, causal) * g * dt_row
            y += _mm(m, jnp.where(lane == k, x, jnp.zeros_like(x)), (1, 0))
        y_ref[0, c] = y.astype(y_ref.dtype)
        s_ref[c] = _next_state(s, x, bm, w_map, whole_rows)
        return carry

    lax.fori_loop(0, tiles, tile, 0)


def _bwd_kernel(x_ref, b_ref, c_ref, dt_ref, l_ref, d_ref, dy_ref,
                dx_ref, ddt_ref, dl_ref, db_ref, dc_ref, s_ref, ds_ref, states_ref,
                *, tiles, r, p, n_chunks):
    q, w = x_ref.shape[2], x_ref.shape[3]
    i = pl.program_id(2)
    causal, eye, lane, row = _marks(q, w, p)
    bm, cm = b_ref[0], c_ref[0]

    @pl.when(i == 0)
    def _init():
        s_ref[...] = jnp.zeros_like(s_ref)
        ds_ref[...] = jnp.zeros_like(ds_ref)

    @pl.when(i < n_chunks)
    def _states():
        # the recurrence again; every chunk's starting state stays in VMEM
        def tile(c, carry):
            s = s_ref[c]
            states_ref[i, c] = s
            _, _, w_map, whole_rows = _head_maps(l_ref, dt_ref, c * r, r, q, eye, lane, row)
            s_ref[c] = _next_state(s, x_ref[0, c], bm, w_map, whole_rows)
            return carry

        lax.fori_loop(0, tiles, tile, 0)

    @pl.when(i >= n_chunks)
    def _gradients():
        chunk = 2 * n_chunks - 1 - i
        g = _mm(cm, bm, (1, 1))
        last = lax.broadcasted_iota(jnp.int32, (q, 1), 0) == q - 1
        f32 = lambda v: v.astype(jnp.float32)  # noqa: E731

        def tile(c, carry):
            dg, db, dc = carry
            x, dy = x_ref[0, c], dy_ref[0, c]
            s, ds = states_ref[chunk, c], ds_ref[c]   # before the chunk; cotangent after it
            heads, e_map, w_map, whole_rows = _head_maps(
                l_ref, dt_ref, c * r, r, q, eye, lane, row)
            from_state = f32(dy) * _mm(cm, s, (1, 1))         # dY_i . (C_i S^T), by lane
            into_state = _mm(bm, ds, (1, 1))                  # B_j dS^T          [Q, W]
            fed = f32(x) * into_state
            overlap = jnp.sum(ds * s, axis=1, keepdims=True)  # [W, 1]
            dx = d_ref[c] * f32(dy) + w_map * into_state
            for k, (l_row, dt_row, l_col, rest, total, w_col) in enumerate(heads):
                mine = lane == k
                x_k, dy_k = (jnp.where(mine, t, jnp.zeros_like(t)) for t in (x, dy))
                decay = _decayed(l_row, l_col, causal)
                scores = _mm(dy_k, x, (1, 1))                 # dY_i . X_j of the head
                dg += scores * decay * dt_row
                kept = scores * decay * g
                dx += _mm(decay * g * dt_row, dy_k, (0, 0))
                ddt_row = jnp.sum(kept, axis=0, keepdims=True)
                dl_col = jnp.sum(kept * dt_row, axis=1, keepdims=True)
                own = lambda t: jnp.sum(  # noqa: E731  a head's lanes' sum [Q, 1]
                    jnp.where(mine, t, 0.0), axis=1, keepdims=True)
                u = own(fed)                                  # X_j . (dS B_j)    [Q, 1]
                at_end = jnp.sum(w_col * u, axis=0, keepdims=True) + jnp.exp(total) * jnp.sum(
                    jnp.where(row == k, overlap, 0.0), axis=0, keepdims=True)
                dl_col += jnp.exp(l_col) * own(from_state) - w_col * u + jnp.where(
                    last, at_end, 0.0)
                ddt_ref[0, pl.ds(c * r + k, 1), :] = ddt_row + _row(rest * u, eye)
                dl_ref[0, pl.ds(c * r + k, 1), :] = _row(dl_col, eye) - ddt_row * dt_row
                dc += jnp.exp(l_col) * _mm(dy_k, s, (1, 0))
                db += w_col * _mm(x_k, ds, (1, 0))
            dx_ref[0, c] = dx.astype(dx_ref.dtype)
            ds_ref[c] = whole_rows * ds + _mm(f32(dy) * e_map, cm, (0, 0))
            return dg, db, dc

        zeros = jnp.zeros((q, bm.shape[1]), jnp.float32)
        dg, db, dc = lax.fori_loop(0, tiles, tile,
                                   (jnp.zeros((q, q), jnp.float32), zeros, zeros))
        db_ref[0, 0] = db + _mm(dg, cm, (0, 0))
        dc_ref[0, 0] = dc + _mm(dg, bm, (1, 0))


def bwd_group_tiles(tiles: int, r: int, n_chunks: int, w: int, n: int) -> int:
    """Tiles of heads one group of ``ssd_bwd`` holds: the most (a divisor of
    ``tiles``, whole sublane tiles of heads or all of them) whose chunk states
    fit ``_BWD_STATE_BYTES``."""
    fits = [g for g in range(1, tiles + 1) if tiles % g == 0
            and (g == tiles or (g * r) % 8 == 0)
            and g * n_chunks * w * n * 4 <= _BWD_STATE_BYTES]
    if not fits:
        raise NotImplementedError(f"{n_chunks} chunks of states fit no head group in VMEM")
    return max(fits)


def kernel_costs(b: int, h: int, t: int, p: int, n: int, chunk: int, itemsize: int) -> dict:
    """One call's operations and bytes of each kernel, as the kernels DO them:
    2 x rows x columns x depth a product over a head's OWN features (the other
    heads' lanes of a tile are zeros, no work), whatever passes it takes; every
    operand and result once a pass that reads or writes it. Forward: ``C B^T``
    once a chunk, a head's decayed scores against X, ``C S^T`` and ``X^T B``.
    Backward: ``X^T B`` again for the states; ``C B^T`` and the two products of
    dG once a chunk and head group; a head's dY X^T, scores against dY, ``C
    S^T``, ``B dS^T``, dY S, X dS and dY^T C. It reads x, B, C, dt and l twice
    and dY once and writes dx, dt's and l's gradients and a group's dB and dC
    in float32."""
    r = heads_a_tile(p)
    tiles, n_chunks = h // r, t // chunk
    groups = tiles // bwd_group_tiles(tiles, r, n_chunks, r * p, n)
    inside = 2.0 * b * t * chunk * p * h           # one [Q, Q] x [Q, P] product a head
    state = 2.0 * b * t * p * n * h                # one product of the state's shape a head
    shared = 2.0 * b * t * chunk * n               # one [Q, Q] x [Q, N] product a chunk
    x_b, bc_b, rows_b = b * t * h * p * itemsize, 2 * b * t * n * itemsize, 2 * b * h * t * 4
    return {"ssd_fwd": (shared + inside + 2 * state, 2 * x_b + bc_b + rows_b),
            "ssd_bwd": (3 * shared * groups + 2 * inside + 7 * state,
                        4 * x_b + 2 * groups * bc_b + 3 * rows_b + groups * 2 * b * t * n * 4)}


def _geometry(x, dt):
    b, tiles, t, w = x.shape
    h = dt.shape[1]
    return b, tiles, t, w, h, h // tiles, w * tiles // h


def _skip_lanes(d, tiles, r, p):
    """D [H] -> [tiles, 1, W]: a head's skip on its lanes."""
    return jnp.repeat(d.astype(jnp.float32).reshape(tiles, 1, r), p, axis=-1)


def _forward(x, dt, l, bm, cm, d, *, chunk, interpret, out_dtype=None):
    b, tiles, t, w, h, r, p = _geometry(x, dt)
    n = bm.shape[-1]
    note_kernel_trace("ssd", "interpret" if interpret else "pallas")
    for name, (flops, nbytes) in kernel_costs(b, h, t, p, n, chunk, x.dtype.itemsize).items():
        note_kernel_cost(name, flops, nbytes)
    rows = pl.BlockSpec((1, h, chunk), lambda bi, i: (bi, 0, i))
    bc = pl.BlockSpec((1, chunk, n), lambda bi, i: (bi, i, 0))
    xs = pl.BlockSpec((1, tiles, chunk, w), lambda bi, i: (bi, 0, i, 0))
    return pl.pallas_call(
        functools.partial(_fwd_kernel, tiles=tiles, r=r, p=p),
        grid=(b, t // chunk),
        in_specs=[xs, bc, bc, rows, rows,
                  pl.BlockSpec((tiles, 1, w), lambda bi, i: (0, 0, 0))],
        out_specs=xs,
        out_shape=jax.ShapeDtypeStruct(x.shape, out_dtype or x.dtype),
        scratch_shapes=[pltpu.VMEM((tiles, w, n), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"), vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
        name="ssd_fwd",
    )(x, bm, cm, dt, l, _skip_lanes(d, tiles, r, p))


def _backward(x, dt, l, bm, cm, d, dy, *, chunk, interpret):
    b, tiles, t, w, h, r, p = _geometry(x, dt)
    n, n_chunks = bm.shape[-1], t // chunk
    gt = bwd_group_tiles(tiles, r, n_chunks, w, n)
    groups, last = tiles // gt, n_chunks - 1
    # first pass: chunks 0..last in order; second: last..0. What only the
    # second pass touches stays on ``last`` through the first, so nothing is
    # fetched or written back before the second pass fills it.
    both = lambda i: jnp.minimum(i, 2 * n_chunks - 1 - i)  # noqa: E731
    second = lambda i: jnp.minimum(last, 2 * n_chunks - 1 - i)  # noqa: E731
    xs = lambda at: pl.BlockSpec(  # noqa: E731
        (1, gt, chunk, w), lambda bi, g, i: (bi, g, at(i), 0))
    bc = pl.BlockSpec((1, chunk, n), lambda bi, g, i: (bi, both(i), 0))
    rows = lambda at: pl.BlockSpec(  # noqa: E731
        (1, gt * r, chunk), lambda bi, g, i: (bi, g, at(i)))
    part = pl.BlockSpec((1, 1, chunk, n), lambda bi, g, i: (bi, g, second(i), 0))
    f32 = lambda shape: jax.ShapeDtypeStruct(shape, jnp.float32)  # noqa: E731
    dx, ddt, dl, db, dc = pl.pallas_call(
        functools.partial(_bwd_kernel, tiles=gt, r=r, p=p, n_chunks=n_chunks),
        grid=(b, groups, 2 * n_chunks),
        in_specs=[xs(both), bc, bc, rows(both), rows(both),
                  pl.BlockSpec((gt, 1, w), lambda bi, g, i: (g, 0, 0)), xs(second)],
        out_specs=[xs(second), rows(second), rows(second), part, part],
        out_shape=[jax.ShapeDtypeStruct(x.shape, x.dtype), f32(dt.shape), f32(dt.shape),
                   f32((b, groups, t, n)), f32((b, groups, t, n))],
        scratch_shapes=[pltpu.VMEM((gt, w, n), jnp.float32), pltpu.VMEM((gt, w, n), jnp.float32),
                        pltpu.VMEM((n_chunks, gt, w, n), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
        name="ssd_bwd",
    )(x, bm, cm, dt, l, _skip_lanes(d, tiles, r, p), dy)
    return dx, ddt, dl, db.sum(axis=1).astype(bm.dtype), dc.sum(axis=1).astype(cm.dtype)


@functools.lru_cache(maxsize=None)
def _make(chunk: int, interpret: bool, out_dtype=None):
    @jax.custom_vjp
    def f(x, dt, l, bm, cm, d):
        return _forward(x, dt, l, bm, cm, d, chunk=chunk, interpret=interpret,
                        out_dtype=out_dtype)

    def fwd(x, dt, l, bm, cm, d):
        # the residuals are the operands alone: ``ssd_bwd`` makes the states
        return f(x, dt, l, bm, cm, d), (x, dt, l, bm, cm, d)

    def bwd(res, dy):
        x, dt, l, bm, cm, d = res
        dy = dy.astype(x.dtype)
        dx, ddt, dl, db, dc = _backward(x, dt, l, bm, cm, d, dy, chunk=chunk,
                                        interpret=interpret)
        b, tiles, t, w, h, r, p = _geometry(x, dt)
        by_head = (dy.astype(jnp.float32) * x.astype(jnp.float32)).reshape(b, tiles, t, r, p)
        return dx, ddt, dl, db, dc, by_head.sum(axis=(0, 2, 4)).reshape(h).astype(d.dtype)

    f.defvjp(fwd, bwd)
    return f


def ssd(x, dt, a, bm, cm, d, *, chunk: int = 256, interpret: bool | None = None,
        out_dtype=None):
    """y, x's shape and dtype, of the recurrence above for x [B, H / r, T, r P]
    (``r = heads_a_tile(P)`` heads side by side in a lane tile), dt [B, H, T]
    float32 (> 0), a [H] (< 0) and d [H] float32, bm and cm [B, T, N]. A length
    no chunk divides is run with zero rows after it (``dt`` 0: no decay, no
    input). Differentiable in all six. ``out_dtype`` float32 keeps the output
    as the kernel summed it (a comparison of the recurrence alone, under bf16's
    rounding otherwise)."""
    if interpret is None:
        interpret = not on_tpu()
    t = x.shape[2]
    short = -t % chunk
    if short:
        x = jnp.pad(x, ((0, 0), (0, 0), (0, short), (0, 0)))
        dt = jnp.pad(dt, ((0, 0), (0, 0), (0, short)))
        bm, cm = (jnp.pad(v, ((0, 0), (0, short), (0, 0))) for v in (bm, cm))
    dt = dt.astype(jnp.float32)
    sums = chunk_sums(dt * a.astype(jnp.float32)[None, :, None], chunk)
    y = _make(int(chunk), bool(interpret), out_dtype and jnp.dtype(out_dtype))(
        x, dt, sums, bm, cm, d.astype(jnp.float32))
    return y[:, :, :t] if short else y

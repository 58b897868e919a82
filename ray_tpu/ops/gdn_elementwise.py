"""The DeltaNet mixer's token-major elementwise passes as Pallas TPU kernels.

Between the projection and the delta rule ``models/gdn.py`` runs, for the
projection's ``qkv [B, S, 2 KH D + VH D]`` (KH key heads and VH value heads
of D features, each key head serving ``rep = VH / KH`` value heads):

    y       = silu(causal depthwise conv1d(qkv, width W))           float32
    q, k, v = split(y), a head = D features
    q, k    = l2norm(q) * D^-0.5, l2norm(k)                         per head
    q, k    = each key head once for every value head it serves
    q, k, v   as [B, VH, S, D] in the model's dtype

No matrix product, and in plain XLA a seventh of what the bytes allow: the
transposes to head-major end every fusion, so float32 arrays of the whole
``[B, S, C]`` go to HBM and come back. Here a grid step takes a tile of
``[rows, lanes]`` of ``qkv`` as it lies and writes it head by head: a head is
whole lane tiles, so block ``(1, rows, D)`` at channel block c of
``[B, S, C]`` and block ``(1, 1, rows, D)`` at head c of ``[B, H, S, D]`` are
the same tile and the transposition is the ``BlockSpec``s'. Every float32
intermediate lives in VMEM; the mathematics and its precisions are the plain
functions' (float32 inside, one rounding on the way out).

``gdn_conv_fwd``  grid (channel block, batch, row tile). The channel blocks
                  run through q's, then k's, then v's channels; each of the
                  three outputs follows the grid through its own phase and
                  keeps its block index outside it, so one array goes in and
                  nothing is sliced. The three rows before a tile come as a
                  second, ``HALO``-row block of the same array.
``gdn_conv_bwd``  the same grid, the row tiles walked BACKWARDS: an input's
                  gradient needs the pre-activation gradient of the three
                  positions after it, which a tile hands to the one before
                  it in VMEM. It makes the conv's output and the norms again
                  (the residuals are ``qkv`` and the taps), sums a key
                  head's ``rep`` cotangents, and writes ``d qkv [B, S, C]``
                  token-major and the taps' gradient, accumulated in float32
                  over batch and row tiles.

``conv_heads`` is the pair under a ``jax.custom_vjp``. Off the TPU the
kernels run in the Pallas interpreter (the tests); ``models/gdn.py`` calls
them only on the chip and keeps its plain functions elsewhere.
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

# a grid step's tile of qkv: a step costs ~0.35 us whatever it does, and a row
# of the block is one DMA burst of TILE_LANES x 2 bytes
TILE_ROWS = 1024
TILE_LANES = 1024
TAPS = 4          # the conv's width
L2_EPS = 1e-6     # ``models/gdn.py::_l2norm``'s


def fits(head_dim: int, rows: int, taps: int) -> bool:
    """Whether the kernels take these shapes: a head of whole lane tiles,
    rows in whole units, a conv of ``TAPS`` taps."""
    return head_dim % LANE == 0 and rows % ROW_UNIT == 0 and taps == TAPS


def _tiles(rows: int, head_dim: int, key_width: int, value_width: int):
    """(rows, lanes) of a grid step's tile: the largest whole units / whole
    heads under the module's sizes that divide the sequence / q's and v's
    channels."""
    t_rows = largest(rows, ROW_UNIT, TILE_ROWS)
    t_lanes = max(n for n in range(head_dim, max(TILE_LANES, head_dim) + 1, head_dim)
                  if key_width % n == 0 and value_width % n == 0)
    return t_rows, t_lanes


def _phase_index(p, b, s, first, count, n_batch, n_tiles):
    """Block indices (batch, channel block, row tile) of an array that the
    grid walks only while ``first <= p < first + count``: before its phase
    the array's first block, after it the last, so that the block changes
    only inside the phase and nothing is fetched or written outside it."""
    before, after = p < first, p >= first + count
    hold = lambda lo, hi, x: jnp.where(before, lo, jnp.where(after, hi, x))  # noqa: E731
    return (hold(0, n_batch - 1, b), jnp.clip(p - first, 0, count - 1),
            hold(0, n_tiles - 1, s))


def _halo_rows(halo_ref, lanes, first_tile):
    """The 8 rows before a tile, float32: the halo block's last, zeros
    before position 0."""
    rows = halo_ref[0, :, lanes].astype(jnp.float32)[HALO - SUBLANES:]
    return jnp.where(first_tile, 0.0, rows)


def _activation(z, w):
    """The conv's output and SiLU's parts from the four views and the taps:
    (c, sigmoid(c), c sigmoid(c))."""
    c = tapped(z, w)
    sig = jax.nn.sigmoid(c)
    return c, sig, c * sig


def _fwd_kernel(x_ref, halo_ref, w_ref, q_ref, k_ref, v_ref, *, n_key, d):
    p, first_tile = pl.program_id(0), pl.program_id(2) == 0
    heads, (c_rows, n_chunks) = x_ref.shape[2] // d, chunks(x_ref.shape[1])

    def head(h, write, scale):
        lanes = pl.ds(pl.multiple_of(h * d, d), d)
        w = w_ref[:, lanes]

        def chunk(i, before):
            rows = pl.ds(pl.multiple_of(i * c_rows, c_rows), c_rows)
            x = x_ref[0, rows, lanes].astype(jnp.float32)
            _, _, a = _activation(windows(x, TAPS, before), w)
            if scale is not None:
                a = a * (lax.rsqrt(jnp.sum(a * a, axis=-1, keepdims=True) + L2_EPS) * scale)
            write(h, rows, a)
            return x[c_rows - SUBLANES:]

        lax.fori_loop(0, n_chunks, chunk, _halo_rows(halo_ref, lanes, first_tile))

    def to_key_heads(ref):
        def write(h, rows, a):
            for r in range(ref.shape[2]):          # once for each value head it serves
                ref[0, h, r, rows] = a.astype(ref.dtype)
        return write

    def to_value_heads(h, rows, a):
        v_ref[0, h, rows] = a.astype(v_ref.dtype)

    for first, write, scale in ((0, to_key_heads(q_ref), d ** -0.5),
                                (n_key, to_key_heads(k_ref), 1.0),
                                (2 * n_key, to_value_heads, None)):
        last = first + n_key if scale is not None else pl.num_programs(0)

        @pl.when(jnp.logical_and(p >= first, p < last))
        def _phase(write=write, scale=scale):
            lax.fori_loop(0, heads, lambda h, _: head(h, write, scale), None)


def _bwd_kernel(x_ref, halo_ref, w_ref, dq_ref, dk_ref, dv_ref, dx_ref, dw_ref,
                after_ref, *, n_key, d):
    p, b, s = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    # the row tiles are walked backwards: s = 0 is the sequence's end
    first_tile = s == pl.num_programs(2) - 1
    heads, (c_rows, n_chunks) = x_ref.shape[2] // d, chunks(x_ref.shape[1])

    @pl.when(jnp.logical_and(b == 0, s == 0))
    def _first_of_a_channel_block():
        dw_ref[...] = jnp.zeros_like(dw_ref)

    @pl.when(s == 0)
    def _end_of_a_sequence():
        after_ref[...] = jnp.zeros_like(after_ref)

    def head(h, cotangent, scale):
        lanes = pl.ds(pl.multiple_of(h * d, d), d)
        w = w_ref[:, lanes]
        before = _halo_rows(halo_ref, lanes, first_tile)

        def chunk(n, carry):
            after, sums = carry
            i = n_chunks - 1 - n
            rows = pl.ds(pl.multiple_of(i * c_rows, c_rows), c_rows)
            x = x_ref[0, rows, lanes].astype(jnp.float32)
            own = x_ref[0, pl.ds(pl.multiple_of(jnp.maximum(i * c_rows - HALO, 0), HALO),
                                 HALO), lanes].astype(jnp.float32)[HALO - SUBLANES:]
            z = windows(x, TAPS, jnp.where(i > 0, own, before))
            c, sig, a = _activation(z, w)
            da = cotangent(h, rows)
            if scale is not None:
                # y = a r scale, r = (sum a^2 + eps)^-1/2:
                # da = scale r (g - a r^2 sum(g a))
                r = lax.rsqrt(jnp.sum(a * a, axis=-1, keepdims=True) + L2_EPS)
                da = (r * scale) * (da - a * (r * r * jnp.sum(da * a, axis=-1, keepdims=True)))
            dc = da * sig * (1.0 + c * (1.0 - sig))
            dx_ref[0, rows, lanes] = tapped(windows(dc, TAPS, after=after), w).astype(dx_ref.dtype)
            return dc[:SUBLANES], tuple(t + fold(dc * zj) for t, zj in zip(sums, z))

        zeros = jnp.zeros((SUBLANES, d), jnp.float32)
        after, sums = lax.fori_loop(0, n_chunks, chunk, (after_ref[h], (zeros,) * TAPS))
        after_ref[h] = after
        dw_ref[:, lanes] += jnp.concatenate(
            [t.sum(axis=0, keepdims=True) for t in sums], axis=0)

    def of_key_heads(ref):
        def cotangent(h, rows):                    # a key head's value heads, summed
            return sum(ref[0, h, r, rows].astype(jnp.float32) for r in range(ref.shape[2]))
        return cotangent

    def of_value_heads(h, rows):
        return dv_ref[0, h, rows].astype(jnp.float32)

    for first, cotangent, scale in ((0, of_key_heads(dq_ref), d ** -0.5),
                                    (n_key, of_key_heads(dk_ref), 1.0),
                                    (2 * n_key, of_value_heads, None)):
        last = first + n_key if scale is not None else pl.num_programs(0)

        @pl.when(jnp.logical_and(p >= first, p < last))
        def _phase(cotangent=cotangent, scale=scale):
            lax.fori_loop(0, heads, lambda h, _: head(h, cotangent, scale), None)


def _specs(x, key_heads, value_heads, *, backwards):
    """The grid and the block specs both kernels share: qkv's tile, its halo
    and the taps; q's, k's and v's blocks, each through its own phase; and
    (rep, D, q's channel blocks). ``backwards`` walks the row tiles from the
    sequence's end."""
    b, s, c = x.shape
    d = c // (2 * key_heads + value_heads)
    t_rows, t_lanes = _tiles(s, d, key_heads * d, value_heads * d)
    n_key, n_value = key_heads * d // t_lanes, value_heads * d // t_lanes
    n_tiles, rep, per = s // t_rows, value_heads // key_heads, t_lanes // d
    tile_of = (lambda si: n_tiles - 1 - si) if backwards else (lambda si: si)
    halo_of = lambda si: jnp.maximum(tile_of(si) * (t_rows // HALO) - 1, 0)  # noqa: E731
    tile = pl.BlockSpec((1, t_rows, t_lanes), lambda p, bi, si: (bi, tile_of(si), p))
    halo = pl.BlockSpec((1, HALO, t_lanes), lambda p, bi, si: (bi, halo_of(si), p))
    taps = pl.BlockSpec((TAPS, t_lanes), lambda p, bi, si: (0, p))

    def phase(first, count):
        return lambda p, bi, si: _phase_index(p, bi, tile_of(si), first, count, b, n_tiles)

    def key_heads_spec(first):
        at = phase(first, n_key)
        return pl.BlockSpec((1, per, rep, t_rows, d),
                            lambda *g: (at(*g)[0], at(*g)[1], 0, at(*g)[2], 0))

    at_v = phase(2 * n_key, n_value)
    value_spec = pl.BlockSpec((1, per, t_rows, d),
                              lambda *g: (at_v(*g)[0], at_v(*g)[1], at_v(*g)[2], 0))
    grid = (2 * n_key + n_value, b, n_tiles)
    heads = [key_heads_spec(0), key_heads_spec(n_key), value_spec]
    return grid, [tile, halo, taps], heads, (rep, d, n_key)


def _note_costs(x, key_heads, value_heads, out_dtype):
    """One call of each: the bytes are qkv once, q, k and v at the value
    heads' count once (and the cotangents and qkv's gradient); the operations
    2 W a channel for the conv and its two transposes, the activation and
    the norms ~a dozen more. The bytes bind."""
    b, s, c = x.shape
    d = c // (2 * key_heads + value_heads)
    width, elements = TAPS, b * s * c
    heads_bytes = 3 * b * s * value_heads * d * jnp.dtype(out_dtype).itemsize
    qkv_bytes = elements * x.dtype.itemsize
    note_kernel_cost("gdn_conv_fwd", (2 * width + 8) * elements, qkv_bytes + heads_bytes)
    note_kernel_cost("gdn_conv_bwd", (3 * 2 * width + 24) * elements,
                     2 * qkv_bytes + heads_bytes + 4 * width * c)


def _forward(x, w, *, key_heads, value_heads, out_dtype, interpret):
    note_kernel_trace("gdn_conv", "interpret" if interpret else "pallas")
    _note_costs(x, key_heads, value_heads, out_dtype)
    b, s, _ = x.shape
    grid, ins, outs, (rep, d, n_key) = _specs(x, key_heads, value_heads, backwards=False)
    key_shape = jax.ShapeDtypeStruct((b, key_heads, rep, s, d), out_dtype)
    q, k, v = pl.pallas_call(
        functools.partial(_fwd_kernel, n_key=n_key, d=d),
        grid=grid, in_specs=ins, out_specs=outs,
        out_shape=[key_shape, key_shape,
                   jax.ShapeDtypeStruct((b, value_heads, s, d), out_dtype)],
        compiler_params=mosaic_params(), interpret=interpret, name="gdn_conv_fwd",
    )(x, x, w)
    return q.reshape(v.shape), k.reshape(v.shape), v


def _backward(x, w, dq, dk, dv, *, key_heads, value_heads, interpret):
    b, s, _ = x.shape
    grid, ins, heads, (rep, d, n_key) = _specs(x, key_heads, value_heads, backwards=True)
    by_key_head = lambda t: t.reshape(b, key_heads, rep, s, d)  # noqa: E731
    dx, dw = pl.pallas_call(
        functools.partial(_bwd_kernel, n_key=n_key, d=d),
        grid=grid, in_specs=ins + heads, out_specs=ins[::2],
        out_shape=[jax.ShapeDtypeStruct(x.shape, x.dtype),
                   jax.ShapeDtypeStruct(w.shape, jnp.float32)],
        scratch_shapes=[pltpu.VMEM((ins[0].block_shape[2] // d, SUBLANES, d), jnp.float32)],
        compiler_params=mosaic_params(), interpret=interpret, name="gdn_conv_bwd",
    )(x, x, w, by_key_head(dq), by_key_head(dk), dv)
    return dx, dw


@functools.lru_cache(maxsize=None)
def _make(key_heads: int, value_heads: int, out_dtype, interpret: bool):
    kw = dict(key_heads=key_heads, value_heads=value_heads, interpret=interpret)

    @jax.custom_vjp
    def f(x, w):
        return _forward(x, w, out_dtype=out_dtype, **kw)

    def fwd(x, w):
        # nothing new is saved: qkv is what remat ``attn`` keeps by name
        return f(x, w), (x, w)

    def bwd(res, cotangents):
        return _backward(*res, *cotangents, **kw)

    f.defvjp(fwd, bwd)
    return f


def conv_heads(qkv, conv_w, *, key_heads: int, value_heads: int, out_dtype,
               interpret: bool | None = None):
    """q, k, v [B, VH, S, D] (``out_dtype``) of ``qkv [B, S, 2 KH D + VH D]``
    and the taps ``conv_w [C, 4]``: the causal depthwise conv, SiLU, q and k
    L2-normalised a head (q times D^-0.5) and repeated for the value heads
    each key head serves. Shapes as ``fits`` says. Differentiable in both."""
    if interpret is None:
        interpret = not on_tpu()
    f = _make(key_heads, value_heads, jnp.dtype(out_dtype), bool(interpret))
    taps = conv_w.T.astype(jnp.float32)            # [4, C]: a tap is a row of lanes
    return f(qkv, taps)


# --- the gated norm ------------------------------------------------------------
#
# After the rule: ``y = rmsnorm(o; w) * silu(z)`` a head, o head-major as the
# rule leaves it, z and y token-major as the projections hold them. The same
# tiles the other way round: block (1, heads, rows, D) of ``o [B, VH, S, D]`` and
# block (1, rows, heads x D) of ``z, y [B, S, VH D]``.


def _norm_parts(o, z, w, eps):
    """(r, u, sigmoid(z), silu(z)) of a chunk: o, z [R, D] float32, w [1, D];
    u = o r the normalised rows."""
    r = lax.rsqrt(jnp.mean(o * o, axis=-1, keepdims=True) + eps)
    sig = jax.nn.sigmoid(z)
    return r, o * r, sig, z * sig


def _norm_fwd_kernel(o_ref, z_ref, w_ref, y_ref, *, eps):
    heads, d = o_ref.shape[1], o_ref.shape[3]
    c_rows, n_chunks = chunks(o_ref.shape[2])
    w = w_ref[...]

    def head(h, _):
        lanes = pl.ds(pl.multiple_of(h * d, d), d)

        def chunk(i, _):
            rows = pl.ds(pl.multiple_of(i * c_rows, c_rows), c_rows)
            _, u, _, silu = _norm_parts(o_ref[0, h, rows].astype(jnp.float32),
                                        z_ref[0, rows, lanes].astype(jnp.float32), w, eps)
            y_ref[0, rows, lanes] = (u * w * silu).astype(y_ref.dtype)

        lax.fori_loop(0, n_chunks, chunk, None)

    lax.fori_loop(0, heads, head, None)


def _norm_bwd_kernel(o_ref, z_ref, w_ref, dy_ref, do_ref, dz_ref, dw_ref, *, eps):
    heads, d = o_ref.shape[1], o_ref.shape[3]
    c_rows, n_chunks = chunks(o_ref.shape[2])
    w = w_ref[...]

    @pl.when(jnp.logical_and(pl.program_id(0) == 0,
                             jnp.logical_and(pl.program_id(1) == 0, pl.program_id(2) == 0)))
    def _first():
        dw_ref[...] = jnp.zeros_like(dw_ref)

    def head(h, total):
        lanes = pl.ds(pl.multiple_of(h * d, d), d)

        def chunk(i, total):
            rows = pl.ds(pl.multiple_of(i * c_rows, c_rows), c_rows)
            z = z_ref[0, rows, lanes].astype(jnp.float32)
            r, u, sig, silu = _norm_parts(o_ref[0, h, rows].astype(jnp.float32), z, w, eps)
            g = dy_ref[0, rows, lanes].astype(jnp.float32)
            gu = g * u
            dz_ref[0, rows, lanes] = (gu * w * (sig * (1.0 + z * (1.0 - sig)))).astype(dz_ref.dtype)
            # u = o r, r = (mean o^2 + eps)^-1/2: do = r (du - u mean(du u))
            du = g * w * silu
            do = r * (du - u * jnp.mean(du * u, axis=-1, keepdims=True))
            do_ref[0, h, rows] = do.astype(do_ref.dtype)
            return total + fold(gu * silu)

        return lax.fori_loop(0, n_chunks, chunk, total)

    dw_ref[...] += lax.fori_loop(0, heads, head, jnp.zeros((SUBLANES, d), jnp.float32))


def _norm_specs(o):
    b, vh, s, d = o.shape
    t_rows, t_lanes = _tiles(s, d, vh * d, vh * d)
    grid = (b, vh * d // t_lanes, s // t_rows)
    by_head = pl.BlockSpec((1, t_lanes // d, t_rows, d), lambda bi, p, si: (bi, p, si, 0))
    by_token = pl.BlockSpec((1, t_rows, t_lanes), lambda bi, p, si: (bi, si, p))
    weight = pl.BlockSpec((1, d), lambda bi, p, si: (0, 0))
    return grid, by_head, by_token, weight


def _norm_forward(o, z, w, *, eps, out_dtype, interpret):
    note_kernel_trace("gdn_norm", "interpret" if interpret else "pallas")
    elements, item = o.size, o.dtype.itemsize
    # o and z in, y out; backward: dy in too, do and dz out. The bytes bind.
    note_kernel_cost("gdn_norm_fwd", 12 * elements, 3 * elements * item)
    note_kernel_cost("gdn_norm_bwd", 30 * elements, 5 * elements * item)
    grid, by_head, by_token, weight = _norm_specs(o)
    return pl.pallas_call(
        functools.partial(_norm_fwd_kernel, eps=eps),
        grid=grid, in_specs=[by_head, by_token, weight], out_specs=by_token,
        out_shape=jax.ShapeDtypeStruct(z.shape, out_dtype),
        compiler_params=mosaic_params(), interpret=interpret, name="gdn_norm_fwd",
    )(o, z, w)


def _norm_backward(o, z, w, dy, *, eps, interpret):
    grid, by_head, by_token, weight = _norm_specs(o)
    d = o.shape[3]
    do, dz, dw = pl.pallas_call(
        functools.partial(_norm_bwd_kernel, eps=eps),
        grid=grid, in_specs=[by_head, by_token, weight, by_token],
        out_specs=[by_head, by_token, pl.BlockSpec((SUBLANES, d), lambda bi, p, si: (0, 0))],
        out_shape=[jax.ShapeDtypeStruct(o.shape, o.dtype), jax.ShapeDtypeStruct(z.shape, z.dtype),
                   jax.ShapeDtypeStruct((SUBLANES, d), jnp.float32)],
        compiler_params=mosaic_params(), interpret=interpret, name="gdn_norm_bwd",
    )(o, z, w, dy)
    return do, dz, dw.sum(axis=0, keepdims=True)


@functools.lru_cache(maxsize=None)
def _make_norm(eps: float, out_dtype, interpret: bool):
    @jax.custom_vjp
    def f(o, z, w):
        return _norm_forward(o, z, w, eps=eps, out_dtype=out_dtype, interpret=interpret)

    def fwd(o, z, w):
        # the rule's output and z are what remat ``attn`` keeps by name
        return f(o, z, w), (o, z, w)

    def bwd(res, dy):
        return _norm_backward(*res, dy, eps=eps, interpret=interpret)

    f.defvjp(fwd, bwd)
    return f


def gated_norm(o, z, weight, *, eps: float, out_dtype, interpret: bool | None = None):
    """y [B, S, VH D] (``out_dtype``) = rmsnorm(o; weight) * silu(z), a head:
    o [B, VH, S, D] head-major, z [B, S, VH D] token-major, weight [D] (the
    weight itself, not 1 + w). Differentiable in all three."""
    if interpret is None:
        interpret = not on_tpu()
    f = _make_norm(float(eps), jnp.dtype(out_dtype), bool(interpret))
    return f(o, z, weight.astype(jnp.float32)[None])

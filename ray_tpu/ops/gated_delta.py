"""The gated delta rule in chunks, as four Pallas TPU kernels.

Per value head, with a float32 state ``S`` [dk, dv] that starts at zero
(Gated DeltaNet, arXiv 2412.06464):

    S_t = exp(g_t) S_{t-1}
    d_t = beta_t (v_t - S_t^T k_t)
    S_t = S_t + k_t d_t^T
    o_t = S_t^T q_t

``gated_delta_rule`` computes the same thing ``CHUNK`` = 64 positions at a
time (the WY form). With ``gamma`` the running sum of ``g`` inside a chunk,
``decay[i, j] = exp(gamma_i - gamma_j)`` for ``j <= i`` and a chunk's rows
``K, Q, V``:

    T  = (I + strict_lower(K_beta K^T * decay))^-1
    W  = T (K_beta * e^gamma)         U  = T V_beta
    V' = U - W S
    O  = (Q * e^gamma) S + (Q K^T * decay, lower) V'
    S <- e^{gamma_C} S + (K * e^{gamma_C - gamma})^T V'

The first two lines need no state and nothing of another chunk. In plain
XLA over all chunks at once (``_prepare``) every intermediate is a
[chunks, 64, 64] float32 array in HBM, its minor dimension padded to 128
lanes; here a chunk's q, k, v, g and beta go in and the recurrence's six
float32 operands come out, with everything between in VMEM (two chunks
share every tile, and a grid step batches its pairs: see the kernels):

``gdn_wy_fwd``  per chunk: the running sum of g, the decay matrix, K K^T and
                Q K^T, A, the inverse, W, U and the operands ``gdn_fwd``
                takes (``qg, kd, w, u, aqk, a``). Every grid step is its own.
``gdn_wy_bwd``  the same grid. It makes the chunk's matrices and the
                inverse's TRANSPOSE again (from A^T, elementwise like A: no
                saved residual but the rule's own operands), then the WY
                form's gradient: ``dT = dW Kb^T + dU Vb^T``, ``dA = -T^T dT
                T^T``, the products back to q, k and v, and g's gradient as
                the running sum, backwards, of the decay terms' row sums
                less their column sums.

The last three lines are a recurrence over chunks, which XLA runs as a
``while`` of small products through HBM; they are two kernels too:

``gdn_fwd``  one pass over the chunks of a (batch, head), the state in
             VMEM, emitting ``O``.
``gdn_bwd``  two passes in one call: the first runs the recurrence again
             and keeps every chunk's starting state in VMEM (64 KB each:
             8 MB at 128 chunks), the second walks the chunks backwards
             with the state's cotangent and emits the gradients of the
             six operands. No ``[chunks, heads, dk, dv]`` array of states
             is ever in HBM, saved or transient.

Each half is a ``jax.custom_vjp``; ``gated_delta_rule`` is one after the
other.

The inverse is a product: ``A`` is strictly lower triangular, so
``(I + A)^-1 = (I - A)(I + A^2)(I + A^4)...`` ends after log2(CHUNK)
factors; float32 products of three bf16 passes (in a kernel, where Mosaic
knows one pass and ``highest`` alone, as three one-pass products of the
operands' bf16 heads and rests).

``chunked_jnp`` is the same chunked form in plain ``jnp``, ``_prepare``
and the recurrence as a ``lax.scan``: the kernels' test oracle and the
speed to beat. Off the TPU the kernels run in the Pallas interpreter.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..tpu import on_tpu
from .trace_log import note_kernel_cost, note_kernel_trace

CHUNK = 64
# chunks one grid step handles: a step costs ~0.35 us whatever it does, and
# a chunk's products are small
CHUNKS_PER_STEP = 4
_VMEM_LIMIT = 96 * 1024 * 1024  # of a v5e core's 128 MiB; the default scope is 16
HIGHEST = lax.Precision.HIGHEST
# the inverse's [C, C] products: three bf16 passes (an error of ~2^-17 a
# product, far under what the comparison with the token-by-token rule
# allows) where ``highest`` runs six; the MXU's passes are what they cost
INVERSE_PRECISION = lax.Precision.HIGH


def _doubling(a, eye=None, mm=None):
    """(I + a)^-1 of strictly triangular a (either way); ``eye`` and the
    product ``mm`` are plain XLA's unless a kernel hands over its own."""
    if mm is None:
        eye = jnp.eye(CHUNK, dtype=a.dtype)
        mm = functools.partial(jnp.matmul, precision=INVERSE_PRECISION)
    inv, power, reach = eye - a, a, 2  # inv is exact up to a^(reach-1)
    while reach < CHUNK:
        power = mm(power, power)
        inv = mm(inv, eye + power)
        reach *= 2
    return inv


@jax.custom_vjp
def _inverse_unit_lower(a):
    """(I + a)^-1 for strictly lower triangular a [..., C, C], float32. Its
    gradient is the inverse's own, ``-T^T dT T^T``: two products, where
    differentiating the doubling would run twenty and keep ten residuals."""
    return _doubling(a)


def _inverse_fwd(a):
    t_inv = _doubling(a)
    return t_inv, t_inv


def _inverse_bwd(t_inv, g):
    mm = functools.partial(jnp.matmul, precision=INVERSE_PRECISION)
    t_t = jnp.swapaxes(t_inv, -1, -2)
    return (-mm(mm(t_t, g), t_t),)


_inverse_unit_lower.defvjp(_inverse_fwd, _inverse_bwd)


def _prepare(q, k, v, g, beta):
    """The chunk-local half: q, k [B, H, T, dk], v [B, H, T, dv] (q scaled,
    q and k normalised by the caller), g, beta [B, H, T] float32 -> the six
    operands of the recurrence, all float32: rounded to bfloat16 they would
    cost the state about what a bfloat16 state costs it (each chunk's
    increment is of the state's own size), and their traffic is small
    beside the products against the state:

    qg, kd, w [B, H, T, dk]; u [B, H, T, dv]; aqk [B, H, N, C, C];
    a [B, H, N] float32 (a chunk's whole decay)."""
    b, h, t, dk = k.shape
    n, c = t // CHUNK, CHUNK
    chunks = lambda x: x.reshape(b, h, n, c, *x.shape[3:])  # noqa: E731
    q, k, v = chunks(q), chunks(k), chunks(v)
    g = chunks(g.astype(jnp.float32))
    beta = chunks(beta.astype(jnp.float32))
    gamma = jnp.cumsum(g, axis=-1)                                  # [B,H,N,C]
    diff = gamma[..., :, None] - gamma[..., None, :]
    lower = jnp.tril(jnp.ones((c, c), bool))
    decay = jnp.where(lower, jnp.exp(jnp.where(lower, diff, 0.0)), 0.0)
    f32 = functools.partial(jnp.einsum, preferred_element_type=jnp.float32)
    kk = f32("bhnid,bhnjd->bhnij", k, k)
    a_mat = jnp.where(jnp.tril(jnp.ones((c, c), bool), -1),
                      kk * decay * beta[..., :, None], 0.0)
    t_inv = _inverse_unit_lower(a_mat)
    k_f, v_f = k.astype(jnp.float32), v.astype(jnp.float32)
    hi = functools.partial(jnp.einsum, precision=HIGHEST)
    w = hi("bhnij,bhnjd->bhnid", t_inv, k_f * (beta * jnp.exp(gamma))[..., None])
    u = hi("bhnij,bhnjd->bhnid", t_inv, v_f * beta[..., None])
    qg = q.astype(jnp.float32) * jnp.exp(gamma)[..., None]
    kd = k_f * jnp.exp(gamma[..., -1:] - gamma)[..., None]
    aqk = f32("bhnid,bhnjd->bhnij", q, k) * decay
    flat = lambda x: x.reshape(b, h, t, x.shape[-1])  # noqa: E731
    return flat(qg), flat(kd), flat(w), flat(u), aqk, jnp.exp(gamma[..., -1])


def _recurrence_jnp(qg, kd, w, u, aqk, a, *, state_dtype=jnp.float32,
                    out_dtype=jnp.float32):
    """The recurrence over chunks as a ``lax.scan`` (state in
    ``state_dtype``: float32 is the rule's; bfloat16 is what the tests
    hold the comparison's limits against)."""
    b, h, t, dk = qg.shape
    dv, n, c = u.shape[-1], t // CHUNK, CHUNK
    per_chunk = lambda x: jnp.moveaxis(  # noqa: E731
        x.reshape(b, h, n, c, x.shape[-1]), 2, 0).astype(jnp.float32)
    hi = functools.partial(jnp.einsum, precision=HIGHEST)

    def step(s, xs):
        qg_n, kd_n, w_n, u_n, aqk_n, a_n = xs
        s32 = s.astype(jnp.float32)
        vp = u_n - hi("bhck,bhkv->bhcv", w_n, s32)
        o = hi("bhck,bhkv->bhcv", qg_n, s32) + hi("bhij,bhjv->bhiv", aqk_n, vp)
        s32 = a_n[..., None, None] * s32 + hi("bhck,bhcv->bhkv", kd_n, vp)
        return s32.astype(state_dtype), o

    s0 = jnp.zeros((b, h, dk, dv), state_dtype)
    xs = (per_chunk(qg), per_chunk(kd), per_chunk(w), per_chunk(u),
          jnp.moveaxis(aqk, 2, 0).astype(jnp.float32),
          jnp.moveaxis(a, 2, 0).astype(jnp.float32))
    _, o = lax.scan(step, s0, xs)
    return jnp.moveaxis(o, 0, 2).reshape(b, h, t, dv).astype(out_dtype)


def chunked_jnp(q, k, v, g, beta, *, state_dtype=jnp.float32):
    """The chunked gated delta rule in plain ``jnp`` (see the module)."""
    return _recurrence_jnp(*_prepare(q, k, v, g, beta), state_dtype=state_dtype,
                           out_dtype=v.dtype)


def _dot(x, y, contract):
    """float32 product of two VMEM tiles, contracting x's and y's axes."""
    return lax.dot_general(
        x.astype(jnp.float32), y.astype(jnp.float32),
        ((contract[:1], contract[1:]), ((), ())),
        precision=HIGHEST, preferred_element_type=jnp.float32)


def _chunk_forward(s, qg, kd, w, u, aqk, a_row):
    """One chunk of the recurrence on values: (next state, O)."""
    vp = u.astype(jnp.float32) - _dot(w, s, (1, 0))
    o = _dot(qg, s, (1, 0)) + _dot(aqk, vp, (1, 0))
    return a_row * s + _dot(kd, vp, (0, 0)), o


def _fwd_kernel(qg_ref, kd_ref, w_ref, u_ref, aqk_ref, a_ref, o_ref, s_ref, *, cps):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        s_ref[...] = jnp.zeros_like(s_ref)

    s = s_ref[...]
    for j in range(cps):
        rows = pl.ds(j * CHUNK, CHUNK)
        s, o = _chunk_forward(
            s, qg_ref[0, 0, rows], kd_ref[0, 0, rows], w_ref[0, 0, rows],
            u_ref[0, 0, rows], aqk_ref[0, 0, j], a_ref[0, 0, j])
        o_ref[0, 0, rows] = o.astype(o_ref.dtype)
    s_ref[...] = s


def _bwd_kernel(qg_ref, kd_ref, w_ref, u_ref, aqk_ref, a_ref, do_ref,
                dqg_ref, dkd_ref, dw_ref, du_ref, daqk_ref, da_ref,
                s_ref, ds_ref, states_ref, *, cps, n_steps):
    i = pl.program_id(2)

    @pl.when(i == 0)
    def _init():
        s_ref[...] = jnp.zeros_like(s_ref)
        ds_ref[...] = jnp.zeros_like(ds_ref)

    @pl.when(i < n_steps)
    def _states():
        # the recurrence again; every chunk's starting state stays in VMEM
        s = s_ref[...]
        for j in range(cps):
            rows = pl.ds(j * CHUNK, CHUNK)
            states_ref[i * cps + j] = s
            s, _ = _chunk_forward(
                s, qg_ref[0, 0, rows], kd_ref[0, 0, rows], w_ref[0, 0, rows],
                u_ref[0, 0, rows], aqk_ref[0, 0, j], a_ref[0, 0, j])
        s_ref[...] = s

    @pl.when(i >= n_steps)
    def _gradients():
        step = 2 * n_steps - 1 - i
        ds = ds_ref[...]            # cotangent of the state AFTER the chunk
        for j in reversed(range(cps)):
            rows = pl.ds(j * CHUNK, CHUNK)
            s = states_ref[step * cps + j]
            qg, kd, w = qg_ref[0, 0, rows], kd_ref[0, 0, rows], w_ref[0, 0, rows]
            aqk, a_row = aqk_ref[0, 0, j], a_ref[0, 0, j]
            do = do_ref[0, 0, rows]
            vp = u_ref[0, 0, rows].astype(jnp.float32) - _dot(w, s, (1, 0))
            dvp = _dot(aqk, do, (0, 0)) + _dot(kd, ds, (1, 0))
            dqg_ref[0, 0, rows] = _dot(do, s, (1, 1)).astype(dqg_ref.dtype)
            daqk_ref[0, 0, j] = _dot(do, vp, (1, 1)).astype(daqk_ref.dtype)
            dkd_ref[0, 0, rows] = _dot(vp, ds, (1, 1)).astype(dkd_ref.dtype)
            du_ref[0, 0, rows] = dvp.astype(du_ref.dtype)
            dw_ref[0, 0, rows] = (-_dot(dvp, s, (1, 1))).astype(dw_ref.dtype)
            da_ref[0, 0, j] = jnp.broadcast_to(jnp.sum(ds * s), da_ref.shape[3:])
            ds = a_row * ds + _dot(qg, do, (0, 0)) - _dot(w, dvp, (0, 0))
        ds_ref[...] = ds


def _steps(t: int) -> tuple[int, int]:
    n = t // CHUNK
    cps = max(c for c in range(1, CHUNKS_PER_STEP + 1) if n % c == 0)
    return cps, n // cps


def _note_costs(qg, u, out_dtype):
    """One call's operations and bytes, as ``benchmark/flops_hybrid.py``
    counts them: 2 x rows x columns x depth a product."""
    b, h, t, dk = qg.shape
    dv, c, item = u.shape[-1], CHUNK, qg.dtype.itemsize
    out_item = jnp.dtype(out_dtype).itemsize
    rows = b * h * t
    state_products = 2.0 * rows * dk * dv          # one [C,dk] x [dk,dv] product
    chunk_products = 2.0 * rows * c * dv           # one [C,C] x [C,dv] product
    operands = rows * (3 * dk + dv + c) * item + rows // c * dv * 4
    # forward: W S, Qg S and Kd^T V' against the state, Aqk V' inside the
    # chunk. Backward: those again for the states, then V', Kd dS, dQg, dKd,
    # dW and the two products of dS against the state's shape, and Aqk^T dO
    # and dAqk inside the chunk; it reads the operands twice and dO once and
    # writes a gradient for each operand.
    note_kernel_cost("gdn_fwd", 3 * state_products + chunk_products,
                     operands + rows * dv * out_item)
    note_kernel_cost("gdn_bwd", 10 * state_products + 3 * chunk_products,
                     3 * operands + rows * dv * out_item)


def _blocks(b, h, t, dk, dv, cps, index):
    """Block specs of the six operands; ``index(i)`` maps a grid step to a
    block of ``cps`` chunks."""
    rows = lambda d: pl.BlockSpec(  # noqa: E731
        (1, 1, cps * CHUNK, d), lambda bi, hi, i: (bi, hi, index(i), 0))
    per_chunk = lambda *tail: pl.BlockSpec(  # noqa: E731
        (1, 1, cps) + tail, lambda bi, hi, i: (bi, hi, index(i)) + (0,) * len(tail))
    return [rows(dk), rows(dk), rows(dk), rows(dv),
            per_chunk(CHUNK, CHUNK), per_chunk(1, dv)]


def _lanes(a, dv):
    """[B, H, N] -> [B, H, N, 1, dv]: a scalar a chunk as a row of lanes,
    which multiplies a [dk, dv] state as it stands."""
    return jnp.broadcast_to(a.astype(jnp.float32)[..., None, None], a.shape + (1, dv))


def _forward(qg, kd, w, u, aqk, a, *, out_dtype, interpret):
    b, h, t, dk = qg.shape
    dv = u.shape[-1]
    cps, n_steps = _steps(t)
    note_kernel_trace("gdn", "interpret" if interpret else "pallas")
    _note_costs(qg, u, out_dtype)
    return pl.pallas_call(
        functools.partial(_fwd_kernel, cps=cps),
        grid=(b, h, n_steps),
        in_specs=_blocks(b, h, t, dk, dv, cps, lambda i: i),
        out_specs=pl.BlockSpec((1, 1, cps * CHUNK, dv), lambda bi, hi, i: (bi, hi, i, 0)),
        out_shape=jax.ShapeDtypeStruct(u.shape, out_dtype),
        scratch_shapes=[pltpu.VMEM((dk, dv), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
        name="gdn_fwd",
    )(qg, kd, w, u, aqk, _lanes(a, dv))


def _backward(qg, kd, w, u, aqk, a, do, *, interpret):
    b, h, t, dk = qg.shape
    dv = u.shape[-1]
    cps, n_steps = _steps(t)
    last = n_steps - 1
    # first pass: blocks 0..last in order; second: last..0. The gradients'
    # blocks (and dO's) stay on ``last`` through the first pass, so nothing
    # is fetched or written back before the second pass fills it.
    both = lambda i: jnp.minimum(i, 2 * n_steps - 1 - i)  # noqa: E731
    second = lambda i: jnp.minimum(last, 2 * n_steps - 1 - i)  # noqa: E731
    outs = _blocks(b, h, t, dk, dv, cps, second)
    shapes = [jax.ShapeDtypeStruct(x.shape, x.dtype) for x in (qg, kd, w, u, aqk)]
    shapes.append(jax.ShapeDtypeStruct(a.shape + (1, dv), jnp.float32))
    dqg, dkd, dw, du, daqk, da = pl.pallas_call(
        functools.partial(_bwd_kernel, cps=cps, n_steps=n_steps),
        grid=(b, h, 2 * n_steps),
        in_specs=_blocks(b, h, t, dk, dv, cps, both) + [pl.BlockSpec(
            (1, 1, cps * CHUNK, dv), lambda bi, hi, i: (bi, hi, second(i), 0))],
        out_specs=outs,
        out_shape=shapes,
        scratch_shapes=[pltpu.VMEM((dk, dv), jnp.float32),
                        pltpu.VMEM((dk, dv), jnp.float32),
                        pltpu.VMEM((t // CHUNK, dk, dv), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
        name="gdn_bwd",
    )(qg, kd, w, u, aqk, _lanes(a, dv), do)
    return dqg, dkd, dw, du, daqk, da[..., 0, 0].astype(a.dtype)


@functools.lru_cache(maxsize=None)
def _make(out_dtype, interpret: bool):
    @jax.custom_vjp
    def f(qg, kd, w, u, aqk, a):
        return _forward(qg, kd, w, u, aqk, a, out_dtype=out_dtype, interpret=interpret)

    def fwd(qg, kd, w, u, aqk, a):
        # the residuals are the operands alone: ``_prepare`` makes them again
        # from what a remat policy saved, and ``gdn_bwd`` the states
        return f(qg, kd, w, u, aqk, a), (qg, kd, w, u, aqk, a)

    def bwd(res, do):
        return _backward(*res, do, interpret=interpret)

    f.defvjp(fwd, bwd)
    return f


# --- the chunk-local half as kernels -----------------------------------------
#
# ``_prepare`` two chunks at a time: their q, k, v, g and beta in (96 KB in
# bf16), the six operands out (288 KB), every [C, C] intermediate in VMEM.
#
# Layout. A [C, C] float32 matrix fills half a vreg's 128 lanes and a quarter
# of the matrix unit, so a PAIR of chunks shares every tile: their [C, C]
# matrices side by side as one [C, 2C] tile ``[X1 | X2]``. Elementwise work
# is then on whole vregs; ``[X1 | X2] . diag(Y1, Y2) = [X1 Y1 | X2 Y2]`` is
# one product on a whole tile; ``diag(X1, X2) . [R1; R2]`` takes the pair's
# rows [2C, d] as they lie in HBM. A [C]-long vector (g, beta) comes as a row
# of lanes and goes to a column of sublanes, or is summed on the way, by a
# masked reduction of its broadcast: no relayout, exact.
#
# Order. The inverse is a chain of ten dependent products a chunk, each a
# few hundred cycles from its first push to its last pop. A grid step holds
# ``WY_PAIRS_PER_STEP`` pairs as the leading dimension of every array, so
# each level of the chain is one batched product and independent products
# stand next to each other (one pair a step takes 8.3 / 11.2 ms a call
# forward / backward at 2 x 32 x 8192 x 128, four 4.4 / 7.6, eight 4.8 /
# 7.6), and the kernel is traced once, not once a pair (a Python loop over
# eight pairs ran 3.8 / 5.7 ms and took eight times as long to trace and
# lower, nine calls a step program: a quarter more set-up).

PAIR = 2 * CHUNK
WY_PAIRS_PER_STEP = 4


def _pair_dot(x, y, contract=(1, 0), precision=None):
    """x [P, m, k] times y [P, k, n], pair by pair, contracting x's and y's
    axes (counted without P): one pass over the operands as they are unless
    ``precision`` says otherwise; a float32 accumulator."""
    dims = (((contract[0] + 1,), (contract[1] + 1,)), ((0,), (0,)))
    return lax.dot_general(x, y, dims, precision=precision,
                           preferred_element_type=jnp.float32)


def _pieces(x, n):
    """float32 x as n bfloat16 pieces, each the bf16 of what the ones before
    left: two hold 16 bits of x, three all 24."""
    out = []
    for _ in range(n - 1):
        out.append(x.astype(jnp.bfloat16))
        x = x - out[-1].astype(jnp.float32)
    return out + [x.astype(jnp.bfloat16)]


def _dot_by_pieces(x, y, contract=(1, 0), *, pieces=3):
    """float32 x times y as the caller handed it over. Against bfloat16 y
    one pass a piece of x: three pieces are the whole product, float32's own
    precision and what ``highest`` would give at six passes; two are 16 bits
    of x, for the products JAX's derivative of ``_prepare`` runs at one.
    Against float32 y (the tests), ``highest``."""
    if y.dtype != jnp.bfloat16:
        return _pair_dot(x, y.astype(jnp.float32), contract, HIGHEST)
    return sum(_pair_dot(piece, y, contract) for piece in _pieces(x, pieces))


def _diagonal(x, own):
    """[X1 | X2] -> [[X1, 0], [0, X2]]; ``own`` [2C, 2C] marks the blocks."""
    return jnp.where(own, jnp.concatenate([x, x], axis=-2), 0.0)


def _side_by_side(x, left):
    """The diagonal blocks of x [P, 2C, 2C] as [X11 | X22]."""
    return jnp.where(left, x[:, :CHUNK], x[:, CHUNK:])


def _dot3(x, y, own):
    """``INVERSE_PRECISION`` inside a kernel, where Mosaic knows one pass
    and ``highest`` only: [X1 Y1 | X2 Y2] as three bf16 passes, each
    operand's bf16 head and the bf16 of what the head left."""
    (x_hi, x_lo), (y_hi, y_lo) = _pieces(x, 2), _pieces(_diagonal(y, own), 2)
    return _pair_dot(x_hi, y_hi) + (_pair_dot(x_hi, y_lo) + _pair_dot(x_lo, y_hi))


def _inverse_pairs(a, eye, own):
    """[T1 | T2] of [A1 | A2]: ``_doubling`` with a kernel's own product."""
    return _doubling(a, eye.astype(jnp.float32), functools.partial(_dot3, own=own))


def _rowsum(x):
    return jnp.sum(x, axis=-1, keepdims=True)


def _colsum(x):
    return jnp.sum(x, axis=-2, keepdims=True)


def _columns(x, left):
    """The lane sums of each half of x [P, C, 2C], the right half's under
    the left's: the pair's column [P, 2C, 1]."""
    return jnp.concatenate(
        [_rowsum(jnp.where(left, x, 0.0)), _rowsum(jnp.where(left, 0.0, x))], axis=-2)


def _across(col, left):
    """A pair's column [P, 2C, 1], each chunk's across its half of [P, C, 2C]."""
    return jnp.where(left, col[:, :CHUNK], col[:, CHUNK:])


def _as_row(col, eye, left):
    """A pair's column [P, 2C, 1] as a row of lanes [P, 1, 2C]."""
    return _colsum(jnp.where(eye, _across(col, left), 0.0))


def _pair_index():
    i = lax.broadcasted_iota(jnp.int32, (CHUNK, PAIR), 0)
    lane = lax.broadcasted_iota(jnp.int32, (CHUNK, PAIR), 1)
    left = lane < CHUNK
    own = (lax.broadcasted_iota(jnp.int32, (PAIR, PAIR), 0) < CHUNK) == (
        lax.broadcasted_iota(jnp.int32, (PAIR, PAIR), 1) < CHUNK)
    return i, jnp.where(left, lane, lane - CHUNK), left, own


def _pair_local(q, k, g_row, beta_row, i, j, left):
    """What both kernels make of their pairs first: q, k [P, 2C, dk], g and
    beta rows [P, 1, 2C]. gamma (g's running sum within a chunk) and beta as
    the pair's column [P, 2C, 1], across its tile [P, C, 2C] and (gamma) as
    a row; the decay, K K^T and Q K^T side by side."""
    eye, lower = i == j, j <= i
    gamma_col = _columns(jnp.where(lower, g_row, 0.0), left)
    beta_col = _columns(jnp.where(eye, beta_row, 0.0), left)
    gamma, beta = _across(gamma_col, left), _across(beta_col, left)
    gamma_row = _as_row(gamma_col, eye, left)
    decay = jnp.where(lower, jnp.exp(jnp.where(lower, gamma - gamma_row, 0.0)), 0.0)
    kk = _side_by_side(_pair_dot(k, k, (1, 1)), left)
    qk = _side_by_side(_pair_dot(q, k, (1, 1)), left)
    return gamma_col, beta_col, gamma, gamma_row, beta, decay, kk, qk


def _last(gamma_col):
    """Each chunk's whole log decay under its own rows: [P, 2C, 1]."""
    shape = gamma_col.shape[:1] + (CHUNK, 1)
    return jnp.concatenate([jnp.broadcast_to(gamma_col[:, CHUNK - 1:CHUNK], shape),
                            jnp.broadcast_to(gamma_col[:, PAIR - 1:], shape)], axis=-2)


def _wy_fwd_kernel(q_ref, k_ref, v_ref, g_ref, beta_ref,
                   qg_ref, kd_ref, w_ref, u_ref, aqk_ref, a_ref):
    i, j, left, own = _pair_index()
    q, k, v, beta_row = q_ref[0, 0], k_ref[0, 0], v_ref[0, 0], beta_ref[0, 0]
    gamma_col, _, _, gamma_row, beta, decay, kk, qk = _pair_local(
        q, k, g_ref[0, 0], beta_row, i, j, left)
    t_inv = _inverse_pairs(jnp.where(j < i, kk * decay * beta, 0.0), i == j, own)
    # W = T (K beta e^gamma) = (T beta e^gamma, by column) K: q, k and v stay
    # as they came
    w_ref[0, 0] = _dot_by_pieces(_diagonal(t_inv * (beta_row * jnp.exp(gamma_row)), own), k)
    u_ref[0, 0] = _dot_by_pieces(_diagonal(t_inv * beta_row, own), v)
    last = _last(gamma_col)
    qg_ref[0, 0] = q.astype(jnp.float32) * jnp.exp(gamma_col)
    kd_ref[0, 0] = k.astype(jnp.float32) * jnp.exp(last - gamma_col)
    aqk = qk * decay
    aqk_ref[0, 0, :, 0], aqk_ref[0, 0, :, 1] = aqk[:, :, :CHUNK], aqk[:, :, CHUNK:]
    a_ref[0, 0] = jnp.exp(_as_row(last, i == j, left))


def _wy_bwd_kernel(q_ref, k_ref, v_ref, g_ref, beta_ref,
                   dqg_ref, dkd_ref, dw_ref, du_ref, daqk_ref, da_ref,
                   dq_ref, dk_ref, dv_ref, dg_ref, dbeta_ref):
    i, j, left, own = _pair_index()
    eye, lower = i == j, j <= i
    q, k, v, beta_row = q_ref[0, 0], k_ref[0, 0], v_ref[0, 0], beta_ref[0, 0]
    dqg, dkd, dw, du = dqg_ref[0, 0], dkd_ref[0, 0], dw_ref[0, 0], du_ref[0, 0]
    daqk = jnp.concatenate([daqk_ref[0, 0, :, 0], daqk_ref[0, 0, :, 1]], axis=-1)
    gamma_col, beta_col, gamma, gamma_row, beta, decay, kk, qk = _pair_local(
        q, k, g_ref[0, 0], beta_row, i, j, left)
    # T^T = (I + A^T)^-1 straight from A^T, which is elementwise like A:
    # every product below then takes T^T as it stands
    decay_t = jnp.where(j >= i, jnp.exp(jnp.where(j >= i, gamma_row - gamma, 0.0)), 0.0)
    t_t = _inverse_pairs(jnp.where(j > i, kk * decay_t * beta_row, 0.0), eye, own)
    # W = (T beta e^gamma) K and U = (T beta) V, scaled by column:
    # dT = (dW K^T) beta e^gamma + (dU V^T) beta
    d_t = (_side_by_side(_dot_by_pieces(dw, k, (1, 1)), left) * (beta_row * jnp.exp(gamma_row))
           + _side_by_side(_dot_by_pieces(du, v, (1, 1)), left) * beta_row)
    # T = (I + A)^-1: dA = -T^T dT T^T, where A is (strictly below)
    d_a = jnp.where(j < i, -_dot3(_dot3(t_t, d_t, own), t_t, own), 0.0)
    t_t = _diagonal(t_t, own)
    d_kb = _pair_dot(t_t, dw, precision=HIGHEST)        # of K beta e^gamma
    d_vb = _pair_dot(t_t, du, precision=HIGHEST)        # of V beta
    # A = K K^T * decay * beta (by row); Aqk = Q K^T * decay
    d_kk, d_qk = _diagonal(d_a * beta * decay, own), _diagonal(daqk * decay, own)
    d_diff = (d_a * beta * kk + daqk * qk) * decay
    q_f, k_f, v_f = (x.astype(jnp.float32) for x in (q, k, v))
    last = _last(gamma_col)
    e_gamma, e_rest = jnp.exp(gamma_col), jnp.exp(last - gamma_col)
    kb_sum, kd_sum = _rowsum(d_kb * k_f), _rowsum(dkd * k_f) * e_rest
    d_beta = _columns(d_a * kk * decay, left) + kb_sum * e_gamma + _rowsum(d_vb * v_f)
    d_gamma = (_columns(d_diff, left) - _columns(jnp.where(eye, _colsum(d_diff), 0.0), left)
               + (kb_sum * beta_col + _rowsum(dqg * q_f)) * e_gamma - kd_sum)
    # the chunk's last gamma also stands in ``a`` and in every kd: it takes
    # their gradients, and g's running sum hands them to every position
    d_last = (jnp.where(left[:1], _colsum(kd_sum[:, :CHUNK]), _colsum(kd_sum[:, CHUNK:]))
              + da_ref[0, 0] * jnp.exp(_as_row(last, eye, left)))
    swap = lambda x: jnp.swapaxes(x, -1, -2)  # noqa: E731
    dq_ref[0, 0] = (_dot_by_pieces(d_qk, k, pieces=2) + dqg * e_gamma).astype(dq_ref.dtype)
    dk_ref[0, 0] = (_dot_by_pieces(d_kk + swap(d_kk), k, pieces=2)
                    + _dot_by_pieces(swap(d_qk), q, pieces=2)
                    + d_kb * (beta_col * e_gamma) + dkd * e_rest).astype(dk_ref.dtype)
    dv_ref[0, 0] = (d_vb * beta_col).astype(dv_ref.dtype)
    # g's gradient is the running sum's, backwards: every later gamma
    dg_ref[0, 0] = d_last + _colsum(jnp.where(lower, _across(d_gamma, left), 0.0))
    dbeta_ref[0, 0] = _as_row(d_beta, eye, left)


def _note_wy_costs(q, v):
    """One call of each, counted as ``_note_costs`` counts: 2 x rows x
    columns x depth a product of a chunk's own matrices, whatever passes
    and whatever tile it takes; bytes once."""
    b, h, t, dk = q.shape
    dv, c, rows = v.shape[-1], CHUNK, b * h * t
    narrow = rows * ((2 * dk + dv) * q.dtype.itemsize + 2 * 4)    # q, k, v, g, beta
    wide = rows * (3 * dk + dv + c) * 4 + rows // c * 4           # the six operands
    qk_products = 2 * 2.0 * rows * c * dk                         # K K^T, Q K^T
    inverse = 10 * 2.0 * rows * c * c
    through_t = 2.0 * rows * c * (dk + dv)                        # W and U
    note_kernel_cost("gdn_wy_fwd", qk_products + inverse + through_t, narrow + wide)
    # backward: the chunk's matrices and T^T again; dT, dKb and dVb; dA (two
    # [C, C] products); three products into dK and one into dQ
    note_kernel_cost("gdn_wy_bwd", qk_products + inverse + 2 * through_t
                     + 2 * 2.0 * rows * c * c + 4 * 2.0 * rows * c * dk,
                     2 * narrow + wide)


def _wy_call(kernel, name, outs, args, *, interpret):
    """Every array by pairs of chunks, [B, H, N/2, ...]; a grid step takes
    ``pps`` pairs of a (batch, head) whole."""
    b, h, pairs = args[0].shape[:3]
    pps = max(p for p in range(1, WY_PAIRS_PER_STEP + 1) if pairs % p == 0)
    spec = lambda x: pl.BlockSpec(  # noqa: E731
        (1, 1, pps) + x.shape[3:], lambda bi, hi, i: (bi, hi, i) + (0,) * (len(x.shape) - 3))
    return pl.pallas_call(
        kernel,
        grid=(b, h, pairs // pps),
        in_specs=[spec(x) for x in args],
        out_specs=[spec(x) for x in outs],
        out_shape=outs,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel"),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
        name=name,
    )(*args)


def _by_pairs(x, per=PAIR):
    """[B, H, T, ...] -> [B, H, T / per, per, ...], after zeros up to a whole
    number of pairs of chunks (a chunk of zeros has A = 0, T = I and
    operands of zeros). ``per`` PAIR for positions, 2 for chunks."""
    short = -x.shape[2] % per
    if short:
        x = jnp.pad(x, [(0, 0), (0, 0), (0, short)] + [(0, 0)] * (x.ndim - 3))
    return x.reshape(x.shape[:2] + (x.shape[2] // per, per) + x.shape[3:])


def _lanes_by_pairs(x):
    """A value a position [B, H, T] as a row of lanes a pair: [B, H, N/2, 1, 2C]."""
    return _by_pairs(x)[:, :, :, None]


def _positions(x, t):
    """[B, H, N/2, 2C, ...] -> the first t positions [B, H, t, ...]."""
    return x.reshape(x.shape[:2] + (-1,) + x.shape[4:])[:, :, :t]


def _wy_forward(q, k, v, g, beta, *, interpret):
    b, h, t, dk = q.shape
    note_kernel_trace("gdn_wy", "interpret" if interpret else "pallas")
    _note_wy_costs(q, v)
    q, k, v = _by_pairs(q), _by_pairs(k), _by_pairs(v)
    g, beta = _lanes_by_pairs(g), _lanes_by_pairs(beta)
    pairs, f32 = q.shape[2], jnp.float32
    outs = [jax.ShapeDtypeStruct(s, f32) for s in (
        q.shape, q.shape, q.shape, v.shape, (b, h, pairs, 2, CHUNK, CHUNK), g.shape)]
    qg, kd, w, u, aqk, a = _wy_call(_wy_fwd_kernel, "gdn_wy_fwd", outs, (q, k, v, g, beta),
                                    interpret=interpret)
    n = t // CHUNK
    return (_positions(qg, t), _positions(kd, t), _positions(w, t), _positions(u, t),
            aqk.reshape(b, h, 2 * pairs, CHUNK, CHUNK)[:, :, :n],
            a.reshape(b, h, 2 * pairs, CHUNK)[:, :, :n, 0])


def _wy_backward(q, k, v, g, beta, dqg, dkd, dw, du, daqk, da, *, interpret):
    t = q.shape[2]
    f32 = lambda x: x.astype(jnp.float32)  # noqa: E731
    da = jnp.repeat(f32(da), CHUNK, axis=2)           # a chunk's value on its lanes
    rows = [_by_pairs(x) for x in (q, k, v)]
    lanes = [_lanes_by_pairs(x) for x in (g, beta)]
    args = (*rows, *lanes, *(_by_pairs(f32(x)) for x in (dqg, dkd, dw, du)),
            _by_pairs(f32(daqk), 2), _lanes_by_pairs(da))
    outs = [jax.ShapeDtypeStruct(x.shape, x.dtype) for x in rows + lanes]
    grads = _wy_call(_wy_bwd_kernel, "gdn_wy_bwd", outs, args, interpret=interpret)
    dq, dk, dv, dg, dbeta = grads
    return (*(_positions(x, t) for x in (dq, dk, dv)),
            _positions(dg[:, :, :, 0], t), _positions(dbeta[:, :, :, 0], t))


@functools.lru_cache(maxsize=None)
def _make_wy(interpret: bool):
    @jax.custom_vjp
    def f(q, k, v, g, beta):
        return _wy_forward(q, k, v, g, beta, interpret=interpret)

    def fwd(q, k, v, g, beta):
        # the residuals are the rule's own operands: ``gdn_wy_bwd`` makes the
        # chunk matrices and the inverse again in VMEM
        return f(q, k, v, g, beta), (q, k, v, g, beta)

    def bwd(res, cotangents):
        return _wy_backward(*res, *cotangents, interpret=interpret)

    f.defvjp(fwd, bwd)
    return f


def gated_delta_rule(q, k, v, g, beta, *, interpret: bool | None = None):
    """o [B, H, T, dv] of the gated delta rule for q, k [B, H, T, dk] (q
    scaled, both normalised by the caller, one key head per value head), v
    [B, H, T, dv], g (log decay, <= 0) and beta [B, H, T]. T is a multiple
    of ``CHUNK``. Differentiable in all five."""
    if q.shape[2] % CHUNK:
        raise ValueError(f"the gated delta rule takes rows of a multiple of "
                         f"{CHUNK} positions, not {q.shape[2]}")
    if interpret is None:
        interpret = not on_tpu()
    operands = _make_wy(bool(interpret))(
        q, k, v, g.astype(jnp.float32), beta.astype(jnp.float32))
    return _make(jnp.dtype(v.dtype), bool(interpret))(*operands)

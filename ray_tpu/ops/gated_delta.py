"""The gated delta rule in chunks, as Pallas TPU kernels.

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

The first two lines need no state: they are plain XLA over all chunks at
once (``_prepare``), differentiated by JAX. The last three are a
recurrence over chunks, which XLA runs as a ``while`` of small products
through HBM; here they are two kernels, named for the trace:

``gdn_fwd``  one pass over the chunks of a (batch, head), the state in
             VMEM, emitting ``O``.
``gdn_bwd``  two passes in one call: the first runs the recurrence again
             and keeps every chunk's starting state in VMEM (64 KB each:
             8 MB at 128 chunks), the second walks the chunks backwards
             with the state's cotangent and emits the gradients of the
             six operands. No ``[chunks, heads, dk, dv]`` array of states
             is ever in HBM, saved or transient.

The inverse is a product: ``A`` is strictly lower triangular, so
``(I + A)^-1 = (I - A)(I + A^2)(I + A^4)...`` ends after log2(CHUNK)
factors; float32 products of three bf16 passes.

``chunked_jnp`` is the same chunked form with the recurrence as a
``lax.scan``: the kernels' test oracle and the speed to beat. Off the TPU
the kernels run in the Pallas interpreter.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.ad_checkpoint import checkpoint_name
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


def _doubling(a):
    c = a.shape[-1]
    eye = jnp.eye(c, dtype=a.dtype)
    mm = functools.partial(jnp.matmul, precision=INVERSE_PRECISION)
    inv, power, reach = eye - a, a, 2  # inv is exact up to a^(reach-1)
    while reach < c:
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
    # The inverse is output and residual at once, under the checkpoint name
    # ``gdn_tinv``: a remat policy that saves it skips the ten products when
    # it runs the layer again. Saved with its rows paired into 128 lanes: a
    # minor dimension of 64 is padded to 128 in HBM, twice the bytes.
    c = a.shape[-1]
    packed = checkpoint_name(_doubling(a).reshape(a.shape[:-2] + (c // 2, 2 * c)),
                             "gdn_tinv")
    return packed.reshape(a.shape), packed


def _inverse_bwd(packed, g):
    mm = functools.partial(jnp.matmul, precision=INVERSE_PRECISION)
    t_t = jnp.swapaxes(packed.reshape(g.shape), -1, -2)
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
    return _make(jnp.dtype(v.dtype), bool(interpret))(*_prepare(q, k, v, g, beta))

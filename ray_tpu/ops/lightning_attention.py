"""Linear attention under a decay that is a constant of the head, in chunks,
as two Pallas TPU kernels.

Per head, with a float32 state ``S`` [dk, dv] that starts at zero and a decay
``lam = exp(log_decay)`` in (0, 1] (Lightning Attention: TransNormerLLM,
arXiv 2307.14995; MiniMax-Text-01):

    S_t = lam S_{t-1} + k_t^T v_t
    o_t = scale q_t S_t            = scale sum_{s<=t} lam^(t-s) (q_t . k_s) v_s

``lightning_attention`` computes it ``CHUNK`` = 128 positions at a time. With
``S`` the state before a chunk, ``Q, K, V`` its rows (i, j = 0..C-1) and
``M[i, j] = lam^(i-j)`` for ``j <= i``, else 0:

    O  = scale ((Q K^T * M) V + lam^(i+1) (Q S))
    S <- lam^C S + (lam^(C-1-j) K)^T V

No inverse and no operands of its own, as the delta rule's WY form has
(``ops/gated_delta.py``): q, k and v go in as the mixer made them (bfloat16)
and the output comes out, a position's three reads and one write. ``M`` is
made in VMEM from the head's one number; no power of ``lam`` is ever divided
by, so a head whose ``lam^C`` underflows (the steepest slope's e^-107) is exact
zeros and no overflow.

``lightning_fwd``  one pass over the chunks of a (batch, head), the state in
                   VMEM, emitting ``O``.
``lightning_bwd``  two passes in one call, as ``gdn_bwd``: the first runs the
                   recurrence again and keeps every chunk's starting state in
                   VMEM (64 KB each: 8 MB at 128 chunks), the second walks the
                   chunks backwards with the state's cotangent and emits dQ, dK
                   and dV. No ``[chunks, heads, dk, dv]`` array of states is
                   ever in HBM. The decay gets no gradient: it is no parameter.

Products. Q K^T and dO V^T take bfloat16 operands as they are (one pass, exact
products, a float32 sum). Every product that has a float32 side (the state,
the decayed scores) runs that side as three bfloat16 pieces against the other
side's bfloat16 (``_pieces``: all 24 bits, what ``highest`` gives at six
passes); float32 against float32 (the tests' inputs) is ``highest``.

``lightning_scan`` is the recurrence as written above, a ``lax.scan`` over
positions in float32 at ``highest``: the kernels' test oracle, and with
``state_dtype`` the bfloat16-state control the comparisons must refuse.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..tpu import on_tpu
from .gated_delta import _pieces
from .trace_log import note_kernel_cost, note_kernel_trace

CHUNK = 128
# chunks one grid step handles: a step costs ~0.35 us whatever it does
CHUNKS_PER_STEP = 4
_VMEM_LIMIT = 96 * 1024 * 1024  # of a v5e core's 128 MiB; the default scope is 16
HIGHEST = lax.Precision.HIGHEST


def lightning_scan(q, k, v, log_decay, *, scale: float = 1.0, state_dtype=jnp.float32):
    """The recurrence position by position: q, k [B, H, T, dk], v [B, H, T,
    dv], ``log_decay`` [H] float32 (<= 0) -> o [B, H, T, dv] float32."""
    b, h, t, dk = q.shape
    lam = jnp.exp(log_decay.astype(jnp.float32))[None, :, None, None]
    hi = functools.partial(jnp.einsum, precision=HIGHEST)
    rows = lambda x: jnp.moveaxis(x, 2, 0).astype(jnp.float32)  # noqa: E731

    def step(s, xs):
        q_t, k_t, v_t = xs
        s = lam * s.astype(jnp.float32) + hi("bhk,bhv->bhkv", k_t, v_t)
        s = s.astype(state_dtype)
        return s, hi("bhk,bhkv->bhv", q_t, s.astype(jnp.float32)) * scale

    s0 = jnp.zeros((b, h, dk, v.shape[-1]), state_dtype)
    _, o = lax.scan(step, s0, (rows(q), rows(k), rows(v)))
    return jnp.moveaxis(o, 0, 2)


def _mm(x, y, contract):
    """float32 product of two VMEM tiles contracting x's and y's axes:
    bfloat16 against bfloat16 as it stands; a float32 side as three bfloat16
    pieces against a bfloat16 side; float32 against float32 at ``highest``."""
    dims = ((contract[:1], contract[1:]), ((), ()))
    dot = functools.partial(lax.dot_general, dimension_numbers=dims,
                            preferred_element_type=jnp.float32)
    bf16 = jnp.bfloat16
    if x.dtype == bf16 and y.dtype == bf16:
        return dot(x, y)
    if y.dtype == bf16:
        return sum(dot(piece, y) for piece in _pieces(x, 3))
    if x.dtype == bf16:
        return sum(dot(x, piece) for piece in _pieces(y, 3))
    return dot(x.astype(jnp.float32), y.astype(jnp.float32), precision=HIGHEST)


def _decays(ld_row):
    """From a head's log decay as a row of lanes [1, C]: ``M`` [C, C], and as
    columns [C, 1] ``lam^(i+1)`` and ``lam^(C-1-i)``; ``lam^C`` [1, 1]."""
    i = lax.broadcasted_iota(jnp.int32, (CHUNK, CHUNK), 0)
    j = lax.broadcasted_iota(jnp.int32, (CHUNK, CHUNK), 1)
    m = jnp.where(j <= i, jnp.exp(ld_row * jnp.maximum(i - j, 0).astype(jnp.float32)), 0.0)
    ld = ld_row[:, :1]
    col = lax.broadcasted_iota(jnp.int32, (CHUNK, 1), 0).astype(jnp.float32)
    return m, jnp.exp(ld * (col + 1.0)), jnp.exp(ld * (CHUNK - 1.0 - col)), jnp.exp(ld * CHUNK)


def _next_state(s, k, v, k_dec, whole):
    return whole * s + _mm(k.astype(jnp.float32) * k_dec, v, (0, 0))


def _fwd_kernel(q_ref, k_ref, v_ref, ld_ref, o_ref, s_ref, *, cps, scale):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        s_ref[...] = jnp.zeros_like(s_ref)

    m, q_dec, k_dec, whole = _decays(ld_ref[0])
    s = s_ref[...]
    for c in range(cps):
        rows = pl.ds(c * CHUNK, CHUNK)
        q, k, v = q_ref[0, 0, rows], k_ref[0, 0, rows], v_ref[0, 0, rows]
        o = _mm(_mm(q, k, (1, 1)) * m, v, (1, 0)) + q_dec * _mm(q, s, (1, 0))
        o_ref[0, 0, rows] = (o * scale).astype(o_ref.dtype)
        s = _next_state(s, k, v, k_dec, whole)
    s_ref[...] = s


def _bwd_kernel(q_ref, k_ref, v_ref, ld_ref, do_ref, dq_ref, dk_ref, dv_ref,
                s_ref, ds_ref, states_ref, *, cps, n_steps, scale):
    i = pl.program_id(2)
    m, q_dec, k_dec, whole = _decays(ld_ref[0])

    @pl.when(i == 0)
    def _init():
        s_ref[...] = jnp.zeros_like(s_ref)
        ds_ref[...] = jnp.zeros_like(ds_ref)

    @pl.when(i < n_steps)
    def _states():
        # the recurrence again; every chunk's starting state stays in VMEM
        s = s_ref[...]
        for c in range(cps):
            rows = pl.ds(c * CHUNK, CHUNK)
            states_ref[i * cps + c] = s
            s = _next_state(s, k_ref[0, 0, rows], v_ref[0, 0, rows], k_dec, whole)
        s_ref[...] = s

    @pl.when(i >= n_steps)
    def _gradients():
        step = 2 * n_steps - 1 - i
        ds = ds_ref[...]            # cotangent of the state AFTER the chunk, unscaled
        for c in reversed(range(cps)):
            rows = pl.ds(c * CHUNK, CHUNK)
            s = states_ref[step * cps + c]
            q, k, v = q_ref[0, 0, rows], k_ref[0, 0, rows], v_ref[0, 0, rows]
            do = do_ref[0, 0, rows]
            a = _mm(q, k, (1, 1)) * m
            da = _mm(do, v, (1, 1)) * m
            dq = _mm(da, k, (1, 0)) + q_dec * _mm(do, s, (1, 1))
            dk = _mm(da, q, (0, 0)) + k_dec * _mm(v, ds, (1, 1))
            dv = _mm(a, do, (0, 0)) + k_dec * _mm(k, ds, (1, 0))
            dq_ref[0, 0, rows] = (dq * scale).astype(dq_ref.dtype)
            dk_ref[0, 0, rows] = (dk * scale).astype(dk_ref.dtype)
            dv_ref[0, 0, rows] = (dv * scale).astype(dv_ref.dtype)
            ds = whole * ds + _mm(q.astype(jnp.float32) * q_dec, do, (0, 0))
        ds_ref[...] = ds


def _steps(t: int) -> tuple[int, int]:
    n = t // CHUNK
    cps = max(c for c in range(1, CHUNKS_PER_STEP + 1) if n % c == 0)
    return cps, n // cps


def kernel_costs(b: int, h: int, t: int, dk: int, dv: int, itemsize: int) -> dict:
    """One call's operations and bytes of each kernel, as the kernels DO them:
    2 x rows x columns x depth a product, whatever passes it takes; every
    operand and result once a pass that reads or writes it. Forward: Q K^T
    and (Q K^T * M) V inside the chunk, Q S and K^T V against the state.
    Backward: K^T V again for the states, then five products inside the
    chunk (Q K^T, dO V^T, dA K, dA^T Q, A^T dO) and four against the state
    (dO S^T, V dS^T, K dS, Q^T dO); it reads k and v twice, q and dO once
    and writes three gradients."""
    rows = b * h * t
    inside = 2.0 * rows * CHUNK * (dk + dv) / 2      # one [C, C] product, mean of both depths
    state = 2.0 * rows * dk * dv                     # one product of the state's shape
    qk, vo = rows * dk * itemsize, rows * dv * itemsize
    tensors = 2 * qk + vo                            # q, k and v, or their gradients
    return {"lightning_fwd": (2 * inside + 2 * state, tensors + vo),
            "lightning_bwd": (5 * inside + 5 * state, 2 * tensors + vo + tensors)}


def _blocks(cps, index, *widths):
    return [pl.BlockSpec((1, 1, cps * CHUNK, d), lambda bi, hi, i: (bi, hi, index(i), 0))
            for d in widths]


def _lanes(log_decay):
    """[H] -> [H, 1, C]: a head's number as a row of lanes."""
    return jnp.broadcast_to(log_decay.astype(jnp.float32)[:, None, None],
                            log_decay.shape + (1, CHUNK))


_LD_SPEC = pl.BlockSpec((1, 1, CHUNK), lambda bi, hi, i: (hi, 0, 0))


def _forward(q, k, v, log_decay, *, scale, interpret, out_dtype=None):
    b, h, t, dk = q.shape
    dv = v.shape[-1]
    cps, n_steps = _steps(t)
    note_kernel_trace("lightning", "interpret" if interpret else "pallas")
    for name, (flops, nbytes) in kernel_costs(b, h, t, dk, dv, q.dtype.itemsize).items():
        note_kernel_cost(name, flops, nbytes)
    return pl.pallas_call(
        functools.partial(_fwd_kernel, cps=cps, scale=scale),
        grid=(b, h, n_steps),
        in_specs=_blocks(cps, lambda i: i, dk, dk, dv) + [_LD_SPEC],
        out_specs=_blocks(cps, lambda i: i, dv)[0],
        out_shape=jax.ShapeDtypeStruct(v.shape, out_dtype or v.dtype),
        scratch_shapes=[pltpu.VMEM((dk, dv), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
        name="lightning_fwd",
    )(q, k, v, _lanes(log_decay))


def _backward(q, k, v, log_decay, do, *, scale, interpret):
    b, h, t, dk = q.shape
    dv = v.shape[-1]
    cps, n_steps = _steps(t)
    last = n_steps - 1
    # first pass: blocks 0..last in order; second: last..0. The gradients'
    # blocks (and dO's) stay on ``last`` through the first pass, so nothing
    # is fetched or written back before the second pass fills it.
    both = lambda i: jnp.minimum(i, 2 * n_steps - 1 - i)  # noqa: E731
    second = lambda i: jnp.minimum(last, 2 * n_steps - 1 - i)  # noqa: E731
    return pl.pallas_call(
        functools.partial(_bwd_kernel, cps=cps, n_steps=n_steps, scale=scale),
        grid=(b, h, 2 * n_steps),
        in_specs=_blocks(cps, both, dk, dk, dv) + [_LD_SPEC] + _blocks(cps, second, dv),
        out_specs=_blocks(cps, second, dk, dk, dv),
        out_shape=[jax.ShapeDtypeStruct(x.shape, x.dtype) for x in (q, k, v)],
        scratch_shapes=[pltpu.VMEM((dk, dv), jnp.float32),
                        pltpu.VMEM((dk, dv), jnp.float32),
                        pltpu.VMEM((t // CHUNK, dk, dv), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
        name="lightning_bwd",
    )(q, k, v, _lanes(log_decay), do)


@functools.lru_cache(maxsize=None)
def _make(scale: float, interpret: bool, out_dtype=None):
    @jax.custom_vjp
    def f(q, k, v, log_decay):
        return _forward(q, k, v, log_decay, scale=scale, interpret=interpret,
                        out_dtype=out_dtype)

    def fwd(q, k, v, log_decay):
        # the residuals are the operands alone: ``lightning_bwd`` makes the states
        return f(q, k, v, log_decay), (q, k, v, log_decay)

    def bwd(res, do):
        q, k, v, log_decay = res
        grads = _backward(q, k, v, log_decay, do.astype(v.dtype), scale=scale,
                          interpret=interpret)
        return (*grads, jnp.zeros_like(log_decay))

    f.defvjp(fwd, bwd)
    return f


def lightning_attention(q, k, v, log_decay, *, scale: float = 1.0,
                        interpret: bool | None = None, out_dtype=None):
    """o [B, H, T, dv] in v's dtype of the recurrence above for q, k [B, H, T,
    dk], v [B, H, T, dv] and ``log_decay`` [H] float32 (<= 0; a constant: its
    gradient is zero). A length no chunk divides is run with zero rows after
    it, which add nothing to a state. Differentiable in q, k and v.
    ``out_dtype`` float32 keeps the output as the kernel summed it (a
    comparison of the recurrence alone, under bf16's rounding otherwise)."""
    if interpret is None:
        interpret = not on_tpu()
    t = q.shape[2]
    short = -t % CHUNK
    if short:
        q, k, v = (jnp.pad(x, ((0, 0), (0, 0), (0, short), (0, 0))) for x in (q, k, v))
    o = _make(float(scale), bool(interpret), out_dtype and jnp.dtype(out_dtype))(
        q, k, v, log_decay.astype(jnp.float32))
    return o[:, :, :t] if short else o

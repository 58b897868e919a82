"""A learned selection of keys for attention (DeepSeek-V3.2's "lightning
indexer" with its top-k, as dots3-note-prev's full layers use it): the index
scores, the key sets they choose, and what the indexer's own loss needs.

For a batch row, J index heads of Di features, queries t and keys s <= t:

    I[t, s] = sum_j w[t, j] relu(q_j[t] . k[s])                ``index_scores``
    S_t     = the ``top_k`` largest I[t, .] (every causal key while t < top_k;
              a tie at the last place keeps every tied key)    ``select_top_k``
    p[t, s] = sum_h softmax_h(attention's scores over S_t)[s]
    L_I     = mean_t KL(p[t, S_t] / sum || softmax(I[t, S_t]))  ``index_kl``

A ``[B, J, T, T]`` tensor is 17 GB in float32 at 8k, so the scores are three
Pallas kernels that keep a head's [block, block] tile in VMEM and sum over
the heads there: ``dsa_index_fwd`` and, for the loss's gradient,
``dsa_index_bwd_dq`` (the index queries' and the head weights') and
``dsa_index_bwd_dk`` (the index key's). The index product takes bfloat16
operands and accumulates in float32; the ReLU, the head weights and the sum
over heads are float32.

The loss is two kernels more, and p never leaves VMEM: ``dsa_probs`` sums
exp(q_h . k_h scale - lse_h) over attention's heads into a tile (from q, k
and the saved logsumexp), masks it by the key set and gathers each query's
statistics over its key blocks (sum p, sum p (log p - I), the kept scores'
logsumexp), which give its KL; ``dsa_probs_bwd`` makes the tile again and
writes the loss's gradient with respect to I, the one [B, T, T] float32 the
pair leaves in HBM. What stays XLA: the threshold (32 counting passes over
the bit patterns of a row's scores: a k-th largest with no sort) and the
mask, elementwise or row reductions over ``[B, T, T]``; and, for a length
no block divides, the plain form ``index_loss`` of
``head_summed_probs_reference``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..tpu import on_tpu
from .attention import _fit_block
from .trace_log import note_kernel_cost, note_kernel_trace

_VMEM_LIMIT = 96 * 1024 * 1024  # of a v5e core's 128 MiB; the default scope is 16
_PARAMS = dict(compiler_params=pltpu.CompilerParams(vmem_limit_bytes=_VMEM_LIMIT))
# query rows x keys of a tile. The forward kernel holds all J heads of a
# query block (J x 256 x 128 bf16 = 4 MB, twice for the pipeline); the
# backward kernels also hold a float32 accumulator of that shape.
FWD_BLOCKS = (256, 1024)
BWD_BLOCKS = (128, 1024)


def index_scores_reference(q_i, k_i, w):
    """Plain jnp: q_i [B, J, T, Di], k_i [B, T, Di], w [B, T, J] -> [B, T, T]
    float32, zero above the diagonal."""
    s = jnp.einsum("bjtd,bsd->bjts", q_i, k_i, preferred_element_type=jnp.float32)
    out = jnp.einsum("bjts,btj->bts", jax.nn.relu(s), w.astype(jnp.float32))
    t = q_i.shape[2]
    return jnp.where(jnp.tril(jnp.ones((t, t), bool)), out, 0.0)


def _causal_tile(qi, ki, block_q, block_k, shape, q_axis):
    q_ids = qi * block_q + jax.lax.broadcasted_iota(jnp.int32, shape, q_axis)
    k_ids = ki * block_k + jax.lax.broadcasted_iota(jnp.int32, shape, 1 - q_axis)
    return q_ids >= k_ids


def _head_scores(q, k):
    return jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                               preferred_element_type=jnp.float32)


def _fwd_kernel(q_ref, k_ref, w_ref, o_ref, *, block_q, block_k, heads):
    qi, ki = pl.program_id(1), pl.program_id(2)
    needed = ki * block_k <= qi * block_q + block_q - 1

    @pl.when(needed)
    def _compute():
        k, w = k_ref[0], w_ref[0]
        acc = jnp.zeros((block_q, block_k), jnp.float32)
        for j in range(heads):
            acc += jnp.maximum(_head_scores(q_ref[0, j], k), 0.0) * w[:, j:j + 1]
        o_ref[0] = jnp.where(_causal_tile(qi, ki, block_q, block_k, acc.shape, 0), acc, 0.0)

    @pl.when(jnp.logical_not(needed))
    def _skip():
        o_ref[0] = jnp.zeros_like(o_ref[0])


def _bwd_dq_kernel(q_ref, k_ref, w_ref, g_ref, dq_ref, dw_ref, dq_acc, dw_acc,
                   *, block_q, block_k, heads, n_k):
    """Query-major tiles, keys innermost: dq_j += (dI w_j [s_j > 0]) k and
    dw_j += rowsum(dI relu(s_j))."""
    qi, ki = pl.program_id(1), pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        dq_acc[:] = jnp.zeros_like(dq_acc)
        dw_acc[:] = jnp.zeros_like(dw_acc)

    @pl.when(ki * block_k <= qi * block_q + block_q - 1)
    def _compute():
        k, w = k_ref[0], w_ref[0]
        g = jnp.where(_causal_tile(qi, ki, block_q, block_k, (block_q, block_k), 0),
                      g_ref[0], 0.0)
        lane = jax.lax.broadcasted_iota(jnp.int32, dw_acc.shape, 1)
        dw = jnp.zeros(dw_acc.shape, jnp.float32)
        for j in range(heads):
            s = _head_scores(q_ref[0, j], k)
            row = jnp.sum(g * jnp.maximum(s, 0.0), axis=1, keepdims=True)
            dw += jnp.where(lane == j, row, 0.0)
            ds = jnp.where(s > 0.0, g * w[:, j:j + 1], 0.0).astype(k.dtype)
            dq_acc[j] += jax.lax.dot(ds, k, preferred_element_type=jnp.float32)
        dw_acc[:] += dw

    @pl.when(ki == n_k - 1)
    def _final():
        dq_ref[0] = dq_acc[:].astype(dq_ref.dtype)
        dw_ref[0] = dw_acc[:]


def _bwd_dk_kernel(q_ref, k_ref, wt_ref, gt_ref, dk_ref, dk_acc,
                   *, block_q, block_k, heads, n_q):
    """Key-major tiles, queries innermost: dk += sum_j (dI w_j [s_j > 0])^T
    q_j. The head weights come as rows ([J, T]) and dI transposed."""
    ki, qi = pl.program_id(1), pl.program_id(2)

    @pl.when(qi == 0)
    def _init():
        dk_acc[:] = jnp.zeros_like(dk_acc)

    @pl.when(qi * block_q + block_q - 1 >= ki * block_k)
    def _compute():
        k = k_ref[0]
        g_t = jnp.where(_causal_tile(qi, ki, block_q, block_k, (block_k, block_q), 1),
                        gt_ref[0], 0.0)
        acc = jnp.zeros(dk_acc.shape, jnp.float32)
        for j in range(heads):
            q = q_ref[0, j]
            s_t = _head_scores(k, q)                                  # [bk, bq]
            ds_t = jnp.where(s_t > 0.0, g_t * wt_ref[0, j:j + 1, :], 0.0).astype(k.dtype)
            acc += jax.lax.dot(ds_t, q, preferred_element_type=jnp.float32)
        dk_acc[:] += acc

    @pl.when(qi == n_q - 1)
    def _final():
        dk_ref[0] = dk_acc[:].astype(dk_ref.dtype)


def _blocks(t: int, requested) -> tuple[int, int] | None:
    bq, bk = _fit_block(requested[0], t), _fit_block(requested[1], t)
    if t % bq or t % bk or bq % 16 or (bk % 128 and bk != t):
        return None
    return bq, bk


def _index_cost(name: str, q_i, products: int, extra_bytes: float) -> None:
    """One call: ``products`` [T, T] x Di products a head over the causal
    triangle; bytes are the operands and results once."""
    b, heads, t, d = q_i.shape
    flops = products * 2.0 * b * heads * d * t * (t + 1) / 2
    item = q_i.dtype.itemsize
    note_kernel_cost(name, flops, b * t * d * item * (heads + 1) + b * t * heads * 4
                     + extra_bytes)


def _scores_forward(q_i, k_i, w, interpret):
    b, heads, t, d = q_i.shape
    blocks = _blocks(t, FWD_BLOCKS)
    if blocks is None:
        note_kernel_trace("dsa_index", "reference")
        return index_scores_reference(q_i, k_i, w)
    note_kernel_trace("dsa_index", "interpret" if interpret else "pallas")
    _index_cost("dsa_index_fwd", q_i, 1, b * t * t * 4)
    bq, bk = blocks
    return pl.pallas_call(
        functools.partial(_fwd_kernel, block_q=bq, block_k=bk, heads=heads),
        grid=(b, t // bq, t // bk),
        in_specs=[pl.BlockSpec((1, heads, bq, d), lambda bi, qi, ki: (bi, 0, qi, 0)),
                  pl.BlockSpec((1, bk, d), lambda bi, qi, ki: (bi, ki, 0)),
                  pl.BlockSpec((1, bq, heads), lambda bi, qi, ki: (bi, qi, 0))],
        out_specs=pl.BlockSpec((1, bq, bk), lambda bi, qi, ki: (bi, qi, ki)),
        out_shape=jax.ShapeDtypeStruct((b, t, t), jnp.float32),
        interpret=interpret, name="dsa_index_fwd", **({} if interpret else _PARAMS),
    )(q_i, k_i, w.astype(jnp.float32))


def _scores_backward(q_i, k_i, w, g, interpret):
    b, heads, t, d = q_i.shape
    blocks = _blocks(t, BWD_BLOCKS)
    if blocks is None:
        return jax.vjp(index_scores_reference, q_i, k_i, w)[1](g)
    bq, bk = blocks
    n_q, n_k = t // bq, t // bk
    w = w.astype(jnp.float32)
    _index_cost("dsa_index_bwd_dq", q_i, 2, b * t * t * 4 + b * heads * t * d * 4)
    _index_cost("dsa_index_bwd_dk", q_i, 2, b * t * t * 4)
    common = dict(interpret=interpret, **({} if interpret else _PARAMS))
    dq, dw = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, block_q=bq, block_k=bk, heads=heads, n_k=n_k),
        grid=(b, n_q, n_k),
        in_specs=[pl.BlockSpec((1, heads, bq, d), lambda bi, qi, ki: (bi, 0, qi, 0)),
                  pl.BlockSpec((1, bk, d), lambda bi, qi, ki: (bi, ki, 0)),
                  pl.BlockSpec((1, bq, heads), lambda bi, qi, ki: (bi, qi, 0)),
                  pl.BlockSpec((1, bq, bk), lambda bi, qi, ki: (bi, qi, ki))],
        out_specs=[pl.BlockSpec((1, heads, bq, d), lambda bi, qi, ki: (bi, 0, qi, 0)),
                   pl.BlockSpec((1, bq, heads), lambda bi, qi, ki: (bi, qi, 0))],
        out_shape=[jax.ShapeDtypeStruct(q_i.shape, q_i.dtype),
                   jax.ShapeDtypeStruct((b, t, heads), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((heads, bq, d), jnp.float32),
                        pltpu.VMEM((bq, heads), jnp.float32)],
        name="dsa_index_bwd_dq", **common,
    )(q_i, k_i, w, g)
    dk = pl.pallas_call(
        functools.partial(_bwd_dk_kernel, block_q=bq, block_k=bk, heads=heads, n_q=n_q),
        grid=(b, n_k, n_q),
        in_specs=[pl.BlockSpec((1, heads, bq, d), lambda bi, ki, qi: (bi, 0, qi, 0)),
                  pl.BlockSpec((1, bk, d), lambda bi, ki, qi: (bi, ki, 0)),
                  pl.BlockSpec((1, heads, bq), lambda bi, ki, qi: (bi, 0, qi)),
                  pl.BlockSpec((1, bk, bq), lambda bi, ki, qi: (bi, ki, qi))],
        out_specs=pl.BlockSpec((1, bk, d), lambda bi, ki, qi: (bi, ki, 0)),
        out_shape=jax.ShapeDtypeStruct(k_i.shape, k_i.dtype),
        scratch_shapes=[pltpu.VMEM((bk, d), jnp.float32)],
        name="dsa_index_bwd_dk", **common,
    )(q_i, k_i, jnp.swapaxes(w, 1, 2), jnp.swapaxes(g, 1, 2))
    return dq, dk, dw


@functools.lru_cache(maxsize=None)
def _make_scores(interpret: bool):
    @jax.custom_vjp
    def f(q_i, k_i, w):
        return _scores_forward(q_i, k_i, w, interpret)

    def bwd(res, g):
        dq, dk, dw = _scores_backward(*res, g, interpret)
        return dq, dk, dw.astype(res[2].dtype)

    f.defvjp(lambda q_i, k_i, w: (f(q_i, k_i, w), (q_i, k_i, w)), bwd)
    return f


def index_scores(q_i, k_i, w, *, interpret: bool | None = None):
    """I [B, T, T] float32 (zero above the diagonal) from the index queries
    q_i [B, J, T, Di], the index key k_i [B, T, Di] and the head weights w
    [B, T, J]. Differentiable in all three."""
    if interpret is None:
        interpret = not on_tpu()
    return _make_scores(interpret)(q_i, k_i, w)


def _ordered_bits(x):
    """float32 -> uint32 whose order is the floats' (-0.0 counted as 0.0)."""
    bits = jax.lax.bitcast_convert_type(x + 0.0, jnp.int32)
    bits = jnp.where(bits >= 0, bits, bits ^ jnp.int32(0x7FFFFFFF))
    return jax.lax.bitcast_convert_type(bits, jnp.uint32) ^ jnp.uint32(0x80000000)


def kth_largest(keys, top_k: int):
    """The ``top_k``-th largest of each row of ``keys`` [..., N] uint32 (0 where
    a row holds fewer than ``top_k`` non-zero keys: nothing is then below it),
    found bit by bit: 32 counts of the keys at or above a candidate, no sort."""
    def refine(i, prefix):
        candidate = prefix | (jnp.uint32(1) << (31 - i).astype(jnp.uint32))
        count = jnp.sum(keys >= candidate[..., None], axis=-1, dtype=jnp.int32)
        return jnp.where(count >= top_k, candidate, prefix)

    return jax.lax.fori_loop(0, 32, refine, jnp.zeros(keys.shape[:-1], jnp.uint32))


def select_top_k(scores, top_k: int):
    """Key sets [B, T, T] int8 from causal scores [B, T, T]: key s is in
    query t's set iff s <= t and I[t, s] is at least the ``top_k``-th largest
    of I[t, :t + 1] (all of them while t < top_k; ties at the threshold are
    all kept). The threshold is found bit by bit: 32 counts of the scores
    at or above a candidate, and no sort."""
    t = scores.shape[1]
    causal = jnp.tril(jnp.ones((t, t), bool))
    if top_k >= t:
        return jnp.broadcast_to(causal, scores.shape).astype(jnp.int8)
    # 0 is below every score's pattern: what a row may not see never counts
    keys = jnp.where(causal, _ordered_bits(scores), jnp.uint32(0))
    return (causal & (keys >= kth_largest(keys, top_k)[..., None])).astype(jnp.int8)


_NEG = -1e30  # below every score; finite, so that exp(_NEG - _NEG) is 1 and no NaN


def _walked(qi, ki, hi, heads):
    """The key block and head whose operands a grid step reads: its own
    inside the causal triangle; above it those of the row's last step
    inside, so that a skipped step fetches nothing."""
    needed = ki <= qi
    return jnp.where(needed, ki, qi), jnp.where(needed, hi, heads - 1)


def _sum_heads(q_ref, k_ref, lse_ref, acc, hi, sm_scale):
    """One head of a tile, KEY-major (the logsumexp is a row there):
    acc += exp(k_h q_h^T scale - lse_h)."""
    @pl.when(hi == 0)
    def _init():
        acc[:] = jnp.zeros_like(acc)

    s_t = _head_scores(k_ref[0, 0], q_ref[0, 0]) * sm_scale
    # a key outside the row's set can score above its logsumexp: it is
    # masked at the last head; keep it finite here
    acc[:] += jnp.exp(jnp.minimum(s_t - lse_ref[0, 0, 0], 0.0))


def _kept_probs(acc, mask_ref, qi, ki, block):
    """The finished tile query-major, zero outside the row's key set."""
    kept = (mask_ref[0].astype(jnp.int32) != 0) & _causal_tile(
        qi, ki, block, block, (block, block), 0)
    return kept, jnp.where(kept, acc[:].T, 0.0)


def _kl_fwd_kernel(q_ref, k_ref, lse_ref, s_ref, mask_ref, kl_ref, z_ref, lse_i_ref,
                   acc, z_acc, a_acc, m_acc, l_acc, *, sm_scale, block):
    """Grid (b, query block, key block, head). Over a row's key blocks:
    Z = sum p, A = sum p (log p - I), and the online max / sum of the kept
    scores' logsumexp; at the last, KL_t = A / Z - log Z + lse_I."""
    qi, ki, hi = pl.program_id(1), pl.program_id(2), pl.program_id(3)
    last_head = hi == pl.num_programs(3) - 1

    @pl.when((ki == 0) & (hi == 0))
    def _init():
        z_acc[:] = jnp.zeros_like(z_acc)
        a_acc[:] = jnp.zeros_like(a_acc)
        m_acc[:] = jnp.full_like(m_acc, _NEG)
        l_acc[:] = jnp.zeros_like(l_acc)

    @pl.when(ki <= qi)
    def _compute():
        _sum_heads(q_ref, k_ref, lse_ref, acc, hi, sm_scale)

    @pl.when((ki <= qi) & last_head)
    def _statistics():
        kept, p = _kept_probs(acc, mask_ref, qi, ki, block)
        scores = s_ref[0]
        z_acc[:] += jnp.sum(p, axis=1, keepdims=True)
        a_acc[:] += jnp.sum(jnp.where(
            p > 0.0, p * (jnp.log(jnp.maximum(p, 1e-38)) - scores), 0.0), axis=1, keepdims=True)
        m_new = jnp.maximum(m_acc[:], jnp.max(jnp.where(kept, scores, _NEG), axis=1,
                                              keepdims=True))
        l_acc[:] = l_acc[:] * jnp.exp(m_acc[:] - m_new) + jnp.sum(
            jnp.where(kept, jnp.exp(scores - m_new), 0.0), axis=1, keepdims=True)
        m_acc[:] = m_new

    @pl.when((ki == pl.num_programs(2) - 1) & last_head)
    def _final():
        z = z_acc[:]
        safe = jnp.maximum(z, 1e-30)
        lse_i = m_acc[:] + jnp.log(jnp.maximum(l_acc[:], 1e-30))
        kl_ref[0] = (a_acc[:] + z * (lse_i - jnp.log(safe))) / safe
        z_ref[0] = z
        lse_i_ref[0] = lse_i


def _kl_bwd_kernel(q_ref, k_ref, lse_ref, s_ref, mask_ref, lse_i_ref, soft_ref, prob_ref,
                   d_ref, acc, *, sm_scale, block):
    """The same sweep makes p again; the tile of dI is soft_t exp(I - lse_I)
    - prob_t p on the row's keys, 0 elsewhere (the two columns carry the
    cotangent, the mean and 1 / Z)."""
    qi, ki, hi = pl.program_id(1), pl.program_id(2), pl.program_id(3)
    last_head = hi == pl.num_programs(3) - 1

    @pl.when(ki <= qi)
    def _compute():
        _sum_heads(q_ref, k_ref, lse_ref, acc, hi, sm_scale)

    @pl.when((ki <= qi) & last_head)
    def _tile():
        kept, p = _kept_probs(acc, mask_ref, qi, ki, block)
        d_ref[0] = jnp.where(
            kept, soft_ref[0] * jnp.exp(s_ref[0] - lse_i_ref[0]) - prob_ref[0] * p, 0.0)

    @pl.when((ki > qi) & last_head)
    def _skip():
        d_ref[0] = jnp.zeros_like(d_ref[0])


def _column(block):
    """A query block's rows of a [B, T, 1] array: one number a query."""
    return pl.BlockSpec((1, block, 1), lambda bi, qi, ki, hi: (bi, qi, 0))


def _kl_sweep(kernel, name, operands, columns, out_specs, out_shape, scratch, block, interpret):
    """One sweep of either kernel over (b, query block, key block, head):
    q, k, the logsumexp as rows, the scores' and the key sets' tiles, then
    ``columns`` [B, T, 1]; a [block, block] float32 accumulator in VMEM."""
    q, k, lse, scores, mask = operands
    b, h, t, d = q.shape
    n = t // block

    def head_rows(of_query):
        def index(bi, qi, ki, hi):
            key, head = _walked(qi, ki, hi, h)
            return bi, head, (qi if of_query else key), 0
        return pl.BlockSpec((1, 1, block, d), index)

    def tile(bi, qi, ki, hi):
        return bi, qi, _walked(qi, ki, hi, h)[0]

    return pl.pallas_call(
        kernel, grid=(b, n, n, h),
        in_specs=[head_rows(True), head_rows(False),
                  pl.BlockSpec((1, 1, 1, 1, block),
                               lambda bi, qi, ki, hi: (bi, _walked(qi, ki, hi, h)[1], qi, 0, 0)),
                  pl.BlockSpec((1, block, block), tile), pl.BlockSpec((1, block, block), tile)]
        + [_column(block)] * len(columns),
        out_specs=out_specs, out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM((block, block), jnp.float32)] + scratch,
        interpret=interpret, name=name, **({} if interpret else _PARAMS),
    )(q, k, lse.reshape(b, h, n, 1, block), scores, mask, *columns)


def _kl_cost(name: str, q, tiles: int) -> None:
    """One call: a [T, T] x D product a head over the causal triangle; bytes
    are q, k and the logsumexp, ``tiles`` [T, T] float32 tiles (the scores
    read; dI written) and the int8 key sets."""
    b, h, t, d = q.shape
    note_kernel_cost(name, 2.0 * b * h * d * t * (t + 1) / 2,
                     2 * b * h * t * d * q.dtype.itemsize + b * h * t * 4
                     + tiles * b * t * t * 4 + b * t * t)


def _kl_forward(operands, sm_scale, block, interpret):
    """Each query's KL, its probabilities' sum Z and its kept scores'
    logsumexp, [B, T] float32 each."""
    b, _, t, _ = operands[0].shape
    _kl_cost("dsa_probs", operands[0], 1)
    out = _kl_sweep(
        functools.partial(_kl_fwd_kernel, sm_scale=sm_scale, block=block), "dsa_probs",
        operands, (), [_column(block)] * 3, [jax.ShapeDtypeStruct((b, t, 1), jnp.float32)] * 3,
        [pltpu.VMEM((block, 1), jnp.float32)] * 4, block, interpret)
    return tuple(x[..., 0] for x in out)


def _kl_backward(operands, z, lse_i, g, sm_scale, block, interpret):
    """g d(mean_t KL_t) / dI, [B, T, T] float32, zero outside the key sets."""
    b, _, t, _ = operands[0].shape
    _kl_cost("dsa_probs_bwd", operands[0], 2)
    safe = jnp.maximum(z, 1e-30)
    mean = g / (b * t)
    return _kl_sweep(
        functools.partial(_kl_bwd_kernel, sm_scale=sm_scale, block=block), "dsa_probs_bwd",
        operands, tuple(x[..., None] for x in (lse_i, mean * z / safe, mean / safe)),
        pl.BlockSpec((1, block, block), lambda bi, qi, ki, hi: (bi, qi, ki)),
        jax.ShapeDtypeStruct((b, t, t), jnp.float32), [], block, interpret)


@functools.lru_cache(maxsize=None)
def _make_kl(sm_scale: float, block: int, interpret: bool):
    """The loss with a gradient rule of its own. The rule names what the
    backward kernel needs beyond its operands, ``dsa_kl_z`` and
    ``dsa_kl_lse`` ([B, T] float32 each): a ``jax.checkpoint`` policy that
    saves both (remat ``attn``: ``models/mla.py``'s ``SAVE_NAMES``) runs the
    forward kernel once, and its second run of the block finds the call dead."""
    static = (sm_scale, block, interpret)

    @jax.custom_vjp
    def f(*operands):
        return jnp.mean(_kl_forward(operands, *static)[0])

    def fwd(*operands):
        kl, z, lse_i = _kl_forward(operands, *static)
        return jnp.mean(kl), (operands, checkpoint_name(z, "dsa_kl_z"),
                              checkpoint_name(lse_i, "dsa_kl_lse"))

    def bwd(residuals, g):
        # the target is a constant, the key sets are no numbers
        return None, None, None, _kl_backward(*residuals, g, *static), None

    f.defvjp(fwd, bwd)
    return f


def index_kl(q, k, lse, scores, mask, *, sm_scale: float, block: int = 1024,
             interpret: bool | None = None):
    """The indexer's loss, a scalar: mean over queries of KL(p || softmax(I)),
    both over the row's key set ``mask`` [B, T, T] int8, where p is
    attention's head-summed probabilities there (from q, k [B, H, T, D] and
    the attention kernel's logsumexp [B, H, T]) normalised to sum 1 a row, a
    constant, and ``scores`` [B, T, T] the index scores, the one operand with
    a gradient. ``index_loss`` of ``head_summed_probs_reference``, made tile
    by tile: no [B, T, T] probabilities leave VMEM. A length the block does
    not divide takes that plain form."""
    if interpret is None:
        interpret = not on_tpu()
    q, k, lse = (jax.lax.stop_gradient(x) for x in (q, k, lse))
    t = q.shape[2]
    block = _fit_block(block, t)
    if t % block or block % 128 and block != t:
        note_kernel_trace("dsa_probs", "reference")
        return index_loss(scores, head_summed_probs_reference(q, k, lse, sm_scale), mask)
    note_kernel_trace("dsa_probs", "interpret" if interpret else "pallas")
    return _make_kl(sm_scale, block, interpret)(q, k, lse, scores, mask)


def head_summed_probs_reference(q, k, lse, sm_scale):
    """sum over heads of exp(q_h . k_h scale - lse_h) as [B, T, T] float32,
    for q, k [B, H, T, D] and the attention kernel's logsumexp [B, H, T]:
    a head's attention probabilities wherever the key was in the row's set
    (elsewhere the number means nothing: ``index_loss`` masks)."""
    s = jnp.einsum("bhtd,bhsd->bhts", q, k, preferred_element_type=jnp.float32) * sm_scale
    return jnp.sum(jnp.exp(jnp.minimum(s - lse[..., None], 0.0)), axis=1)


def index_loss(scores, probs, mask):
    """mean over queries of KL(p || softmax(I)), both over the row's key set:
    ``scores`` and the head-summed ``probs`` [B, T, T], ``mask`` the key sets.
    ``probs`` is a target (no gradient); it is normalised to sum 1 a row."""
    kept = mask != 0
    p = jnp.where(kept, jax.lax.stop_gradient(probs), 0.0)
    p = p / jnp.maximum(jnp.sum(p, axis=-1, keepdims=True), 1e-30)
    log_q = jax.nn.log_softmax(jnp.where(kept, scores, -jnp.inf), axis=-1)
    kl = jnp.where(p > 0.0, p * (jnp.log(jnp.maximum(p, 1e-38)) - log_q), 0.0)
    return jnp.mean(jnp.sum(kl, axis=-1))

"""Which path each kernel call took, recorded when it was TRACED.

The Pallas entry points pick native lowering or the interpreter from the
backend, flash attention swaps in ``mha_reference`` for ragged shapes, and
the grouped matmul ``lax.ragged_dot`` for a row count no tile divides.
Those decisions are taken in Python while a jitted program is traced, so
the compiled program cannot be asked afterwards; this record can. A
worker reports it (train metrics, ``LLMDeployment.engine_metrics``) and
``chip_smoke.py`` fails when a chip run shows anything but ``pallas``.

The same moment knows the shapes, so the record also carries what one
call of each Pallas kernel costs (``kernel_costs``): the program's half
of a roofline share, whose other half is the kernel's seconds under the
same name on a profiler trace (``flash_fwd``, ``flash_bwd_dq``,
``flash_bwd_dkdv``, ``paged_decode``, ``moe_gmm``, ``moe_tgmm``,
``gdn_wy_fwd``, ``gdn_wy_bwd``, ``gdn_fwd``, ``gdn_bwd``; the delta rule's
chunk-local pair is traced as ``gdn_wy``, its recurrence as ``gdn``; the
DeltaNet mixer's elementwise pairs ``gdn_conv_fwd`` / ``gdn_conv_bwd`` and
``gdn_norm_fwd`` / ``gdn_norm_bwd``, traced as ``gdn_conv`` and ``gdn_norm``
(``pallas`` / ``interpret``, or ``jnp`` where the mixer's plain functions ran); the
Mamba-2 conv's ``mamba_conv_fwd`` / ``mamba_conv_bwd`` and the short conv's
``sconv_fwd`` / ``sconv_bwd`` (0 FLOPs, each array's bytes once), traced as
``mamba_conv`` and ``sconv`` the same way; the
attention kernels under a window or a key set ``attn_win_*`` / ``attn_sel_*``,
traced as ``flash_attention`` like the plain ones; the indexer's
``dsa_index_fwd`` / ``dsa_index_bwd_dq`` / ``dsa_index_bwd_dk``, traced as
``dsa_index``, and the loss's ``dsa_probs`` / ``dsa_probs_bwd``, traced as
``dsa_probs``; a held range's adds ``moe_rows``, 0 FLOPs and the bytes of the
rows it may fetch and of the rows it writes, traced as ``moe_rows``:
``pallas`` / ``interpret``, or ``xla`` where a width off the lane tiling took
XLA's scatter-add; lightning attention's ``lightning_fwd`` / ``lightning_bwd``,
traced as ``lightning``; the attention kernels under block sets
``attn_blk_*``, traced as ``flash_attention`` and costed as the tiles they
compute, ``note_block_set_cost``).
"""

from __future__ import annotations

import collections
import logging
import math
import threading

logger = logging.getLogger(__name__)

_lock = threading.Lock()
_counts: collections.Counter = collections.Counter()
_costs: dict[str, dict] = {}


def note_kernel_trace(kernel: str, path: str) -> None:
    """Count one trace of ``kernel`` down ``path`` (``"pallas"``,
    ``"interpret"``, ``"mha_reference"``, ``"ragged_dot"``, ``"jnp"`` or
    ``"xla"``); log the first of each."""
    key = f"{kernel}:{path}"
    with _lock:
        _counts[key] += 1
        first = _counts[key] == 1
    if first:
        logger.info("%s traced as %s", kernel, path)


def kernel_traces() -> dict[str, int]:
    """``{"<kernel>:<path>": times traced}`` for this process."""
    with _lock:
        return dict(_counts)


def note_kernel_cost(kernel: str, flops: float, nbytes: float,
                     steps: tuple[int, int] | None = None, **geometry) -> None:
    """Record what ONE call of the Pallas kernel named ``kernel`` costs at
    the shapes it was just traced with (the last trace wins). ``steps``, for
    a kernel whose last grid axis walks tiles: how many steps a (batch, head)
    takes, and how many of them compute (``grid_steps``, ``live_steps``).
    ``geometry``: what else the call chose from its shapes (the grouped
    matmuls' ``tiles``, ``work_items``, ``rhs_resident``), kept as given."""
    with _lock:
        traced = _costs.get(kernel, {}).get("traced", 0) + 1
        _costs[kernel] = {"traced": traced, "flops": float(flops),
                          "bytes": float(nbytes), **geometry}
        if steps is not None:
            _costs[kernel].update(grid_steps=int(steps[0]), live_steps=int(steps[1]))


def kernel_costs() -> dict[str, dict]:
    """``{"<kernel name>": {"traced": n, "flops": ..., "bytes": ...}}``:
    per call, at the shapes of the kernel's latest trace in this process; the
    attention kernels' entries also hold ``grid_steps`` and ``live_steps``
    (the ``attn_win_*`` ones ``heads_a_tile``, ``tiles`` and ``walked_pairs``
    too: ``note_attention_cost``), the grouped matmuls' ``tiles`` [tm, tk, tn],
    ``work_items`` (the static bound on row-tile visits, M / tm + G - 1) and
    ``rhs_resident`` (``moe_gmm``:
    ``tk`` spans the contraction, so a group's matrix block is fetched once a
    group and column block; ``moe_tgmm``: ``tk`` spans lhs's width)."""
    with _lock:
        return {k: dict(v) for k, v in _costs.items()}


_FLASH_MATMULS = {"flash_fwd": 2, "flash_bwd_dq": 3, "flash_bwd_dkdv": 4}


def note_flash_cost(kernel: str, q, k, *, causal: bool,
                    residuals: bool = True, steps=None) -> None:
    """Record one call of a flash kernel on q [B,Hq,Sq,D], k/v [B,Hkv,Sk,D].
    Each [Sq,Sk] x D matmul is 2*B*Hq*Sq*Sk*D FLOPs: the forward has two
    (QK^T, PV), dQ three (QK^T, dO V^T, dS K), dK/dV four (QK^T, P^T dO,
    dO V^T, dS^T Q); causal masking halves them. Bytes are the operands
    and results once: q-shaped arrays (q, o, dO, dQ), k and v, the
    per-query float32 statistics (the forward writes lse replicated over
    128 lanes, [B,Hq,Sq,128]; the backward kernels read lse and delta
    compact, [B,Hq,Sq] each), and dK/dV, which leave the kernel at the
    query-head count."""
    b, hq, sq, d = q.shape
    sk = k.shape[2]
    flops = _FLASH_MATMULS[kernel] * 2.0 * b * hq * sq * sk * d / (2 if causal else 1)
    q_b = b * hq * sq * d * q.dtype.itemsize
    kv_b = 2 * math.prod(k.shape) * k.dtype.itemsize
    stats = b * hq * sq * 4
    nbytes = {
        "flash_fwd": 2 * q_b + kv_b + (128 * stats if residuals else 0),
        "flash_bwd_dq": 3 * q_b + kv_b + 2 * stats,
        "flash_bwd_dkdv": 2 * q_b + kv_b + 2 * stats
        + 2 * b * hq * sk * d * k.dtype.itemsize,
    }[kernel]
    note_kernel_cost(kernel, flops, nbytes, steps)


_ATTENTION_WIDTHS = {"fwd": (1, 1), "bwd_dq": (2, 1), "bwd_dkdv": (2, 2)}


def _attention_bytes(part: str, q, k, v, residuals: bool) -> float:
    """Operands and results of one attention kernel call once, as
    ``note_flash_cost`` counts them, with v, o and dO at the value head's
    width."""
    b, hq, sq, d = q.shape
    sk, dv = k.shape[2], v.shape[3]
    q_b = b * hq * sq * d * q.dtype.itemsize
    o_b = b * hq * sq * dv * q.dtype.itemsize
    kv_b = (math.prod(k.shape) + math.prod(v.shape)) * k.dtype.itemsize
    stats = b * hq * sq * 4
    return {
        "fwd": q_b + o_b + kv_b + (128 * stats if residuals else 0),
        "bwd_dq": 2 * q_b + o_b + kv_b + 2 * stats,
        "bwd_dkdv": q_b + o_b + kv_b + 2 * stats
        + b * hq * sk * (d + dv) * k.dtype.itemsize,
    }[part]


def note_attention_cost(part: str, variant: str | None, q, k, v, pairs: float, *,
                        residuals: bool = True, masked: bool = False, steps=None,
                        **geometry) -> None:
    """Record one call of an attention kernel variant (``attn_win_*``: a
    window; ``attn_sel_*``: a key set a query row; a plain kernel whose value
    head is narrower than its key head keeps its ``flash_*`` name). FLOPs are
    those of the ``pairs`` (query, key) pairs a head KEEPS, whatever the
    kernel walks: a pair costs 2 D for each product over the key head's
    width (QK^T; in the backward kernels also dS K or dS^T Q) and 2 Dv for
    each over the value head's (PV; dO V^T; P^T dO). Bytes as
    ``note_flash_cost``, with v, o and dO at the value head's width and, where
    ``masked``, the int8 key sets once (a window's grouped queries write the
    logsumexp compact and dK / dV at the KV heads' count, fewer bytes than this
    counts: the count stays the one ``benchmark/flops_swa.py`` mirrors until
    both are recounted together). ``geometry``: what the call chose from its
    shapes, kept as given; the ``attn_win_*`` calls give ``heads_a_tile`` (the
    query heads whose rows a tile holds: a kv head's group when folded, else
    1), ``tiles`` (a step's [rows, keys]) and ``walked_pairs`` (the pairs a
    head's steps compute, against the ``pairs`` it keeps)."""
    b, hq, sq, d = q.shape
    sk, dv = k.shape[2], v.shape[3]
    n_d, n_dv = _ATTENTION_WIDTHS[part]
    flops = 2.0 * b * hq * pairs * (n_d * d + n_dv * dv)
    nbytes = _attention_bytes(part, q, k, v, residuals) + (b * sq * sk if masked else 0)
    name = f"flash_{part}" if variant is None else f"attn_{variant}_{part}"
    note_kernel_cost(name, flops, nbytes, steps, **geometry)


def note_block_set_cost(part: str, q, k, v, tiles: tuple[int, int], slab: int, *,
                        residuals: bool = True, steps=None) -> None:
    """Record one call of an attention kernel under block sets
    (``attn_blk_*``) as the work it DOES: every live tile of the walk
    (``steps[1]``) computes all of its ``block_q x block_k`` pairs, whatever
    its sets keep, and spreads its flags by one more product over ``slab``
    lanes. Bytes as ``note_attention_cost``, with the int8 flags [B, Hkv, Sq,
    Sk / set_block] once a query head (each head's steps fetch their slab)."""
    b, hq, sq, d = q.shape
    n_d, n_dv = _ATTENTION_WIDTHS[part]
    pairs = float(steps[1]) * tiles[0] * tiles[1]
    flops = 2.0 * b * hq * pairs * (n_d * d + n_dv * v.shape[3] + slab)
    nbytes = _attention_bytes(part, q, k, v, residuals) + b * hq * sq * slab
    note_kernel_cost(f"attn_blk_{part}", flops, nbytes, steps, tiles=list(tiles), slab=slab)


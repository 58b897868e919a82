"""Mamba-2 as a token mixer: a selective state-space layer (SSD, arXiv
2405.21060; the ``mamba`` layers of Granite-4.0-H), as ``models/lightning.py``
is lightning attention's and ``models/gdn.py`` the gated delta rule's.

For the normed input ``h`` of a position, H heads of P features over a state of
N, one B and one C for all heads (``groups`` 1):

    [z | x | B | C | dt] = h W_in            widths H P | H P | N | N | H, no bias
    [x | B | C] = silu(conv_K([x | B | C]) + b_conv)     causal, depthwise, K taps
    dt = softplus(dt + dt_bias);   A = -exp(A_log)                   a head each
    S_t = exp(dt_t A) S_{t-1} + dt_t x_t (x) B_t;   y_t = S_t C_t + D x_t
    out = (rmsnorm_{H P}(y * silu(z)) * w) W_out

The state [P, N] of a head is float32 and starts at zero. The gate goes INSIDE
the norm, whose one statistic runs over all H P features of a position
(``models/gdn.py`` norms a head, then gates). ``dt`` is not clipped.

The published class holds ``W_in`` as one matrix and the conv as one over all
H P + 2 N channels; the leaves here are its column blocks (``w_z``, ``w_x``
[E, H, P], ``w_bc`` [E, 2, N], ``w_dt`` [E, H]; ``conv_x`` [K, H, P], ``conv_bc``
[K, 2, N] and their biases), the same numbers and the same count, so that z
and x leave their products as the scan takes them: ``r = 128 / P`` heads side
by side in a lane tile, [B, H / r, T, r P] (``ops/ssd.py``; a head of 64
features is half a lane tile). ``dt_bias``, ``a_log`` and ``d_skip`` are
float32 whatever the model's type: a step of 0.001 is under bfloat16's spacing
at ``log 64``.

Named scopes ``mamba_proj``, ``mamba_conv``, ``mamba_scan``, ``mamba_out`` split a
layer on the trace. Under ``mamba_conv`` the conv with its bias and SiLU, over x
and over B | C, runs on the chip as ``ops/mamba_elementwise.py``'s kernel pair,
every float32 intermediate in VMEM, exactly when the code can see it fits
(``on_tpu()`` and that module's ``fits``: a last axis of one lane tile, rows in
whole units of 64, four taps); on every other backend and at every other
width ``causal_conv`` below is the mixer, the same mathematics at the same
precisions. The pair adds no saved name: its residuals are its inputs and
its outputs are ``mamba_x`` / ``mamba_bc``. The gated norm is plain XLA under
``mamba_out`` everywhere: the chip's compiler fuses it into the products
beside it, and a kernel pair for it slowed the step (PERF.md, PR 54).
``SAVE_NAMES`` is what the backward pass reads: x, B and C as the scan takes
them, dt, the scan's output and z; the chunk states are made again in VMEM.
The mixer counts ``decay_mean`` beside its output, the mean of ``exp(dt A)``
over heads and positions: near 0 the carried state is nothing and a scan that
dropped it would still compare well.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from ..observability.tracing import device_scope
from ..ops import mamba_elementwise
from ..ops.ssd import heads_a_tile, ssd
from ..ops.trace_log import note_kernel_trace
from ..tpu import on_tpu
from .kinds import LayerKind

SAVE_NAMES = ("mamba_x", "mamba_bc", "mamba_dt", "mamba_y", "mamba_z")
# the seeded steps: dt_bias is the inverse softplus of a log-uniform draw here
DT_RANGE = (0.001, 0.1)


@dataclasses.dataclass(frozen=True)
class Mamba2:
    """The widths of the Mamba-2 layers."""

    heads: int
    head_dim: int
    state: int
    groups: int = 1             # B / C groups; the scan takes one
    conv: int = 4               # taps of the causal depthwise conv
    chunk: int = 256            # positions the scan takes at a time

    def __post_init__(self):
        if self.groups != 1:
            raise NotImplementedError(f"{self.groups} B/C groups: the scan shares one B and "
                                      "one C among all heads")
        heads_a_tile(self.head_dim)


def _axes(c) -> dict:
    return {
        "w_z": ("embed", "heads", "head_dim"), "w_x": ("embed", "heads", "head_dim"),
        "w_bc": ("embed", None, None), "w_dt": ("embed", "heads"),
        "conv_x": (None, "heads", "head_dim"), "conv_x_bias": ("heads", "head_dim"),
        "conv_bc": (None, None, None), "conv_bc_bias": (None, None),
        "dt_bias": (None,), "a_log": (None,), "d_skip": (None,),
        "ssm_norm": ("norm",), "w_out": ("heads", "head_dim", "embed"),
    }


def _init(c, keys, lead, normal) -> dict:
    a = c.mamba2
    e, h, p, n, k = c.hidden, a.heads, a.head_dim, a.state, a.conv
    more = lambda i: jax.random.fold_in(keys[0], i)  # noqa: E731
    low, high = (jnp.log(jnp.float32(x)) for x in DT_RANGE)
    step = jnp.exp(jax.random.uniform(more(5), lead + (h,), jnp.float32, low, high))
    return {
        "w_z": normal(keys[0], lead + (e, h, p), e),
        "w_x": normal(keys[1], lead + (e, h, p), e),
        "w_bc": normal(keys[2], lead + (e, 2, n), e),
        "w_dt": normal(more(1), lead + (e, h), e),
        # the published conv's own start: its fan-in is its K taps
        "conv_x": normal(more(2), lead + (k, h, p), k),
        "conv_x_bias": normal(more(3), lead + (h, p), k),
        "conv_bc": normal(more(4), lead + (k, 2, n), k),
        "conv_bc_bias": normal(more(6), lead + (2, n), k),
        "dt_bias": step + jnp.log(-jnp.expm1(-step)),           # softplus^-1(step)
        "a_log": jnp.broadcast_to(jnp.log(jnp.arange(1, h + 1, dtype=jnp.float32)), lead + (h,)),
        "d_skip": jnp.ones(lead + (h,), jnp.float32),
        "ssm_norm": jnp.ones(lead + (h * p,), c.dtype),
        "w_out": normal(keys[3], lead + (h, p, e), h * p),
    }


def causal_conv(x, taps, bias):
    """x [B, C, T, W], taps [K, C, W], bias [C, W] -> float32 [B, C, T, W]:
    ``out_t = bias + sum_i taps[i] x_{t - (K - 1) + i}``, zeros before the row."""
    k, t = taps.shape[0], x.shape[2]
    padded = jnp.pad(x, ((0, 0), (0, 0), (k - 1, 0), (0, 0))).astype(jnp.float32)
    taps = taps.astype(jnp.float32)[:, None, :, None, :]
    out = bias.astype(jnp.float32)[None, :, None, :]
    for i in range(k):
        out = out + taps[i] * padded[:, :, i:i + t]
    return out


def gated_norm(y, z, weight, eps):
    """``rmsnorm(y * silu(z)) * weight`` over ALL features of a position: y, z
    [B, C, T, W], weight [C W]; float32 statistics."""
    c, w = y.shape[1], y.shape[3]
    g = y.astype(jnp.float32) * jax.nn.silu(z.astype(jnp.float32))
    var = jnp.mean(jnp.square(g), axis=(1, 3), keepdims=True)
    return (g * jax.lax.rsqrt(var + eps)
            * weight.astype(jnp.float32).reshape(1, c, 1, w)).astype(y.dtype)


def _conv_silu(x, taps, bias, dtype):
    """``silu(causal_conv(x) + bias)`` rounded once to ``dtype``: on the chip,
    at shapes ``mamba_elementwise.fits`` takes, as its kernel pair; the plain
    functions on every other backend and at every other width."""
    if (on_tpu() and x.dtype == dtype
            and mamba_elementwise.fits(x.shape[3], x.shape[2], taps.shape[0])):
        return mamba_elementwise.conv_silu(x, taps, bias)
    note_kernel_trace("mamba_conv", "jnp")
    return jax.nn.silu(causal_conv(x, taps, bias)).astype(dtype)


def mamba2_mixer(h, layer, *, config, positions, mesh=None, scan=None,
                 return_selection: bool = False):
    """h [B, S, E] (normed) -> (y [B, S, E], {"decay_mean"}). ``scan`` swaps the
    kernels for another implementation of the recurrence (tests; ``ssd_scan``);
    ``return_selection`` is the block's question to every mixer of a stack that
    selects keys somewhere: this one has no selection to return."""
    c, a = config, config.mamba2
    if mesh is not None and mesh.size > 1:
        raise NotImplementedError("the Mamba-2 layers run on one device: the scan's "
                                  "kernels have no per-shard call yet")
    e, r = c.hidden, heads_a_tile(a.head_dim)
    tiles, w = a.heads // r, r * a.head_dim
    # a leaf's [.., H, P] as the scan's tiles [.., H / r, r P]
    lanes = lambda t: t.reshape(t.shape[:-2] + (tiles, w))  # noqa: E731
    with device_scope("mamba_proj"):
        z = jnp.einsum("bse,ecw->bcsw", h, lanes(layer["w_z"]))
        x = jnp.einsum("bse,ecw->bcsw", h, lanes(layer["w_x"]))
        bc = jnp.einsum("bse,egn->bgsn", h, layer["w_bc"])
        dt = jnp.einsum("bse,eh->bhs", h, layer["w_dt"], preferred_element_type=jnp.float32)
        z = checkpoint_name(z, "mamba_z")
    with device_scope("mamba_conv"):
        # x last: a kernel's recorded cost is its latest trace's
        bc = _conv_silu(bc, layer["conv_bc"], layer["conv_bc_bias"], c.dtype)
        x = _conv_silu(x, lanes(layer["conv_x"]), lanes(layer["conv_x_bias"]), c.dtype)
        x = checkpoint_name(x, "mamba_x")
        bc = checkpoint_name(bc, "mamba_bc")
        dt = checkpoint_name(jax.nn.softplus(dt + layer["dt_bias"][:, None]), "mamba_dt")
    with device_scope("mamba_scan"):
        rate = -jnp.exp(layer["a_log"].astype(jnp.float32))
        y = (scan or ssd)(x, dt, rate, bc[:, 0], bc[:, 1], layer["d_skip"],
                          **({} if scan else {"chunk": a.chunk}))
        y = checkpoint_name(y.astype(c.dtype), "mamba_y")
        decay_mean = jax.lax.stop_gradient(jnp.mean(jnp.exp(dt * rate[:, None])))
    with device_scope("mamba_out"):
        g = gated_norm(y, z, layer["ssm_norm"], c.norm_eps)
        out = jnp.einsum("bcsw,cwe->bse", g, layer["w_out"].reshape(tiles, w, e))
    return out, {"decay_mean": decay_mean}


def _matmul_params(c) -> float:
    a = c.mamba2
    inner = a.heads * a.head_dim
    return c.hidden * (2 * inner + 2 * a.groups * a.state + a.heads) + inner * c.hidden


def _mixing_flops(c, seq: int) -> float:
    """Forward FLOPs a token of the recurrence ITSELF: the two products a
    token makes against a head's [P, N] state (``x (x) B`` into it, ``S C`` out
    of it: 2 x 2 P N). The products inside a chunk are the program's way to
    run it on matrix units, and no model FLOP."""
    a = c.mamba2
    return a.heads * 2 * 2.0 * a.head_dim * a.state


MAMBA2 = LayerKind(axes=_axes, init=_init, apply=mamba2_mixer,
                   matmul_params=_matmul_params, mixing_flops=_mixing_flops,
                   save_names=SAVE_NAMES)

__all__ = ["MAMBA2", "Mamba2", "SAVE_NAMES", "causal_conv", "gated_norm", "mamba2_mixer"]

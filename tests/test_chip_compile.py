"""The main path's Pallas kernels, compiled by the chip's own compiler.

The TPU compiler is installed here and compiles for a chip that is
described and not attached (``v5e:2x2``), so what Mosaic refuses — a slice
off the tiling, too much VMEM — fails here at no chip time, which
interpret-mode tests cannot show. Nothing runs: shapes only. ``interpret``
is passed explicitly because this process's backend is the CPU, so the
kernels' own default would pick the interpreter.
"""

import os

os.environ.setdefault("TPU_LOG_DIR", "disabled")  # else the compiler logs under /tmp

import jax
import jax.numpy as jnp
import pytest
from jax.experimental.compilation_cache import compilation_cache
from jax.sharding import SingleDeviceSharding

from ray_tpu.ops.attention import flash_attention
from ray_tpu.ops import (gated_delta, gdn_elementwise, mamba_elementwise, sconv_elementwise,
                         sparse_index)
from ray_tpu.ops.gated_delta import gated_delta_rule
from ray_tpu.ops.grouped_matmul import grouped_matmul
from ray_tpu.ops.lightning_attention import lightning_attention
from ray_tpu.ops.ssd import ssd
from ray_tpu.models.gqa import window_blocks
from ray_tpu.ops.moe_rows import sum_rows
from ray_tpu.ops.paged_attention import paged_decode_attention


@pytest.fixture(scope="module")
def chip():
    """One device of a described v5e:2x2, persistent cache off: a program
    compiled for a described chip is written to it but cannot be read
    back without one, and the next compile warns."""
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"cannot describe a v5e:2x2 topology: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _flash(chip, b, hq, hkv, s, d, backward):
    q = jax.ShapeDtypeStruct((b, hq, s, d), jnp.bfloat16, sharding=chip)
    kv = jax.ShapeDtypeStruct((b, hkv, s, d), jnp.bfloat16, sharding=chip)

    def fwd(q, k, v):
        return flash_attention(q, k, v, causal=True, interpret=False)

    def loss(q, k, v):
        return fwd(q, k, v).astype(jnp.float32).sum()

    fn = jax.grad(loss, argnums=(0, 1, 2)) if backward else fwd
    return jax.jit(fn).lower(q, kv, kv)


def _grouped_window(chip, backward, b=1, hq=72, hkv=8, s=16384, d=128, window=512):
    """The window kernels as ``models/gqa.py`` calls them: grouped queries,
    a kv head's group the rows of a tile, blocks that follow the window (at 512
    keys 128 queries a head against the band's 640 keys as one tile)."""
    block_q, block_k = window_blocks(window, hq // hkv)
    q = jax.ShapeDtypeStruct((b, hq, s, d), jnp.bfloat16, sharding=chip)
    kv = jax.ShapeDtypeStruct((b, hkv, s, d), jnp.bfloat16, sharding=chip)

    def fwd(q, k, v):
        return flash_attention(q, k, v, causal=True, window=window, block_q=block_q,
                               block_k=block_k, interpret=False)

    def loss(q, k, v):
        return fwd(q, k, v).astype(jnp.float32).sum()

    fn = jax.grad(loss, argnums=(0, 1, 2)) if backward else fwd
    return jax.jit(fn).lower(q, kv, kv)


def _lightning(chip, backward, b=1, h=32, t=16384, d=128):
    """MiniCPM-SALA's lightning layers at one row of 16,384: 32 heads of 128,
    bf16 operands, a decay a head; backward keeps 128 chunk states in VMEM."""
    x = jax.ShapeDtypeStruct((b, h, t, d), jnp.bfloat16, sharding=chip)
    decay = jax.ShapeDtypeStruct((h,), jnp.float32, sharding=chip)

    def fwd(q, k, v, log_decay):
        return lightning_attention(q, k, v, log_decay, scale=d ** -0.5, interpret=False)

    fn = jax.grad(lambda *a: fwd(*a).astype(jnp.float32).sum(), argnums=(0, 1, 2)
                  ) if backward else fwd
    return jax.jit(fn).lower(x, x, x, decay)


def _ssd(chip, backward, b=1, h=64, t=32768, p=64, n=128, chunk=256):
    """Granite-4.0-H's state-space layers at one row of 32,768: 64 heads of 64
    features, two a lane tile, over a state of 128 in chunks of 256; bf16 x, B
    and C, float32 steps; backward keeps a group of 8 heads' 128 chunk states in
    VMEM (32 MB of scratch)."""
    sd = lambda shape, dt=jnp.bfloat16: jax.ShapeDtypeStruct(shape, dt, sharding=chip)  # noqa: E731
    f32 = jnp.float32
    args = (sd((b, h * p // 128, t, 128)), sd((b, h, t), f32), sd((h,), f32),
            sd((b, t, n)), sd((b, t, n)), sd((h,), f32))
    fwd = lambda *a: ssd(*a, chunk=chunk, interpret=False)  # noqa: E731
    fn = jax.grad(lambda *a: fwd(*a).astype(f32).sum(), argnums=tuple(range(6))
                  ) if backward else fwd
    return jax.jit(fn).lower(*args)


def _block_sets(chip, backward, b=1, hq=32, hkv=2, t=16384, d=128, block=64):
    """MiniCPM-SALA's block-selected layers at one row of 16,384: 32 query heads
    over 2 kv heads under int8 block flags [B, KV, T, T / 64], a slab of 128 of
    a row's 256 flags a grid step."""
    sd = lambda shape, dt=jnp.bfloat16: jax.ShapeDtypeStruct(shape, dt, sharding=chip)  # noqa: E731
    args = (sd((b, hq, t, d)), sd((b, hkv, t, d)), sd((b, hkv, t, d)),
            sd((b, hkv, t, t // block), jnp.int8))
    fwd = lambda q, k, v, sets: flash_attention(  # noqa: E731
        q, k, v, block_sets=sets, set_block=block, interpret=False)
    fn = jax.grad(lambda *a: fwd(*a).astype(jnp.float32).sum(), argnums=(0, 1, 2)
                  ) if backward else fwd
    return jax.jit(fn).lower(*args)


def _paged_staging(chip, layers, kh, g, d, page, slots=8, max_len=2560, k_steps=32,
                   live_pages=8):
    """The engine's decode call: layer-stacked pool, staging rows of the
    fused dispatch, at the smoke's serve geometry."""
    pages_per_seq = max_len // page
    num_pages = slots + slots * pages_per_seq
    sd = lambda shape, dt=jnp.bfloat16: jax.ShapeDtypeStruct(shape, dt, sharding=chip)
    q = sd((slots, kh, g, d))
    pool = sd((layers, num_pages, kh, page, d))
    stage = sd((layers, slots, kh, k_steps, d))
    tables = sd((slots, pages_per_seq), jnp.int32)
    pos = sd((slots,), jnp.int32)
    scalar = sd((), jnp.int32)

    def fn(q, kp, vp, bt, pos, ks, vs, idx, layer):
        return paged_decode_attention(
            q, kp, vp, bt, pos, page_size=page, live_pages=live_pages,
            layer=layer, k_stage=ks, v_stage=vs, stage_idx=idx,
            interpret=False)

    return jax.jit(fn).lower(q, pool, pool, tables, pos, stage, stage, scalar, scalar)


def _grouped(chip, k, n, backward, rows=131072, groups=64, held=False):
    """OLMoE's expert products at 4 x 4096 tokens x 8 experts a token: the
    sorted rows against 64 matrices; backward adds the same product against
    the transposed matrices and the transposed product (``moe_tgmm``).
    ``held``: a held range's call (``rows`` its ``cap``, a row offset given),
    whose tiles the rule chooses from ``rows / groups``."""
    lhs = jax.ShapeDtypeStruct((rows, k), jnp.bfloat16, sharding=chip)
    rhs = jax.ShapeDtypeStruct((groups, k, n), jnp.bfloat16, sharding=chip)
    sizes = jax.ShapeDtypeStruct((groups,), jnp.int32, sharding=chip)

    def fwd(lhs, rhs, sizes):
        offset = jnp.zeros((), jnp.int32) if held else None
        return grouped_matmul(lhs, rhs, sizes, row_offset=offset, interpret=False)

    def loss(lhs, rhs, sizes):
        return (fwd(lhs, rhs, sizes).astype(jnp.float32) ** 2).sum()

    fn = jax.grad(loss, argnums=(0, 1)) if backward else fwd
    return jax.jit(fn).lower(lhs, rhs, sizes)


def _rows(chip, n, cap, e):
    """A held range's adds: ``cap`` rows of width ``e`` summed into ``n``
    tokens (``moe_rows``; the gather's gradient is the same call, and so is
    the embedding table's: ``cap`` tokens' rows into ``n`` = the vocabulary)."""
    rows = jax.ShapeDtypeStruct((cap, e), jnp.bfloat16, sharding=chip)
    ids = jax.ShapeDtypeStruct((cap,), jnp.int32, sharding=chip)
    return jax.jit(lambda r, i: sum_rows(r, i, n, interpret=False)).lower(rows, ids)


def _gdn(chip, backward, b=2, h=32, t=8192, d=128):
    """Qwen3-Next's DeltaNet scan at the benchmark's 2 x 8192: 32 value
    heads of 128, 128 chunks a row; backward keeps the 128 chunk states in
    VMEM (8 MB of scratch)."""
    x = jax.ShapeDtypeStruct((b, h, t, d), jnp.bfloat16, sharding=chip)
    gate = jax.ShapeDtypeStruct((b, h, t), jnp.float32, sharding=chip)

    def fwd(q, k, v, g, beta):
        return gated_delta_rule(q, k, v, g, beta, interpret=False)

    def loss(*a):
        return fwd(*a).astype(jnp.float32).sum()

    fn = jax.grad(loss, argnums=(0, 1, 2, 3, 4)) if backward else fwd
    return jax.jit(fn).lower(x, x, x, gate, gate)


def _gdn_wy(chip, backward, b=2, h=32, t=8192, d=128):
    """The rule's chunk-local half alone at the same shape: 8,192 chunk-heads,
    eight a grid step; backward takes a cotangent for each of the six
    operands, as ``gdn_bwd`` hands them over."""
    x = jax.ShapeDtypeStruct((b, h, t, d), jnp.bfloat16, sharding=chip)
    gate = jax.ShapeDtypeStruct((b, h, t), jnp.float32, sharding=chip)
    wy = gated_delta._make_wy(False)
    if not backward:
        return jax.jit(wy).lower(x, x, x, gate, gate)
    cotangents = tuple(jax.ShapeDtypeStruct(o.shape, o.dtype, sharding=chip)
                       for o in jax.eval_shape(wy, x, x, x, gate, gate))

    def pull_back(cts, *a):
        return jax.vjp(wy, *a)[1](cts)

    return jax.jit(pull_back).lower(cotangents, x, x, x, gate, gate)


def _gdn_elementwise(chip, what, backward, b=2, t=8192, kh=16, vh=32, d=128):
    """The DeltaNet mixer's elementwise kernels at the benchmark's 2 x 8192:
    ``conv`` from the projection's ``[2, 8192, 8192]`` and the four taps to q,
    k, v ``[2, 32, 8192, 128]`` (16 key heads, each written twice), ``norm``
    from the rule's output and z to the token-major input of ``w_out``;
    backward alone (the pull-back needs no forward call: the residuals are
    the inputs)."""
    sd = lambda shape: jax.ShapeDtypeStruct(shape, jnp.bfloat16, sharding=chip)  # noqa: E731
    heads, tokens = sd((b, vh, t, d)), sd((b, t, vh * d))
    if what == "conv":
        args, cotangent = (sd((b, t, (2 * kh + vh) * d)), sd(((2 * kh + vh) * d, 4))), (heads,) * 3
        fwd = lambda x, w: gdn_elementwise.conv_heads(  # noqa: E731
            x, w, key_heads=kh, value_heads=vh, out_dtype=jnp.bfloat16, interpret=False)
    else:
        args, cotangent = (heads, tokens, sd((d,))), tokens
        fwd = lambda o, z, w: gdn_elementwise.gated_norm(  # noqa: E731
            o, z, w, eps=1e-6, out_dtype=jnp.bfloat16, interpret=False)
    if not backward:
        return jax.jit(fwd).lower(*args)
    return jax.jit(lambda ct, *a: jax.vjp(fwd, *a)[1](ct)).lower(cotangent, *args)


def _mamba_conv(chip, backward, b=1, c=32, t=32768):
    """The Mamba-2 mixer's conv kernels at Granite-4.0-H's one row of 32,768:
    over x ``[1, 32, 32768, 128]`` as the projection leaves it (two heads of 64
    a lane tile) with its four taps and bias as the leaves lie; backward alone
    (the pull-back needs no forward call: the residuals are the inputs)."""
    sd = lambda shape: jax.ShapeDtypeStruct(shape, jnp.bfloat16, sharding=chip)  # noqa: E731
    args = (sd((b, c, t, 128)), sd((4, c, 128)), sd((c, 128)))
    fwd = lambda *a: mamba_elementwise.conv_silu(*a, interpret=False)  # noqa: E731
    if not backward:
        return jax.jit(fwd).lower(*args)
    return jax.jit(lambda ct, *a: jax.vjp(fwd, *a)[1](ct)).lower(args[0], *args)


def _sconv(chip, backward, b=4, t=8192, e=2048):
    """LFM2's double-gated conv at the benchmark's 4 x 8,192: the thirds
    ``[3, 4, 8192, 2048]`` as the in-projection leaves them and the three taps as
    the leaf lies; backward alone (the residuals are the inputs)."""
    sd = lambda shape: jax.ShapeDtypeStruct(shape, jnp.bfloat16, sharding=chip)  # noqa: E731
    args = (sd((3, b, t, e)), sd((3, e)))
    fwd = lambda *a: sconv_elementwise.gated_conv3(*a, interpret=False)[0]  # noqa: E731
    if not backward:
        return jax.jit(fwd).lower(*args)
    return jax.jit(lambda ct, *a: jax.vjp(fwd, *a)[1](ct)).lower(sd((b, t, e)), *args)


def _latent(chip, kind, backward, b=2, t=8192):
    """dots3-note-prev's attention at the benchmark's 2 x 8192: ``sel`` the
    full layers' (128 heads, a 192-wide key head and a 128-wide value head,
    under an int8 key set a query row, the logsumexp returned), ``win`` the
    window layers' (64 heads, 256 / 128, a 513-wide band in 512-blocks)."""
    sd = lambda shape, dt=jnp.bfloat16: jax.ShapeDtypeStruct(shape, dt, sharding=chip)  # noqa: E731
    if kind == "sel":
        args = (sd((b, 128, t, 192)), sd((b, 128, t, 192)), sd((b, 128, t, 128)),
                sd((b, t, t), jnp.int8))
        fwd = lambda q, k, v, m: flash_attention(  # noqa: E731
            q, k, v, mask=m, top_k=2048, return_lse=True, interpret=False)[0]
    else:
        args = (sd((b, 64, t, 256)), sd((b, 64, t, 256)), sd((b, 64, t, 128)))
        fwd = lambda q, k, v: flash_attention(  # noqa: E731
            q, k, v, window=513, block_q=512, block_k=512, interpret=False)
    fn = jax.grad(lambda *a: fwd(*a).astype(jnp.float32).sum(), argnums=(0, 1, 2)
                  ) if backward else fwd
    return jax.jit(fn).lower(*args)


def _latent_full(chip, backward, b=1, h=64, t=8192):
    """Kimi-K2's attention at one row of 8,192 (and of the 4,096 its cell runs):
    the PLAIN kernels at 64 query = 64 key heads, a 192-wide key head (one and
    a half lane tiles) beside a 128-wide value head, under YaRN's softmax scale."""
    sd = lambda d: jax.ShapeDtypeStruct((b, h, t, d), jnp.bfloat16, sharding=chip)  # noqa: E731
    fwd = lambda q, k, v: flash_attention(  # noqa: E731
        q, k, v, sm_scale=1.81326 * 192 ** -0.5, interpret=False)
    fn = jax.grad(lambda *a: fwd(*a).astype(jnp.float32).sum(), argnums=(0, 1, 2)
                  ) if backward else fwd
    return jax.jit(fn).lower(sd(192), sd(192), sd(128))


def _indexer(chip, what, b=2, t=8192):
    """Its indexer's kernels there: 64 index heads of 128 summed in VMEM
    (forward; the loss's gradient: two kernels), and the loss itself: the KL
    made where attention's head-summed probabilities are, from q, k, the
    logsumexp, the scores and the key sets (``probs``), and its gradient
    (``probs_bwd``: the forward kernel for its two statistics, then the
    rule's backward kernel)."""
    sd = lambda shape, dt=jnp.bfloat16: jax.ShapeDtypeStruct(shape, dt, sharding=chip)  # noqa: E731
    if what.startswith("probs"):
        q = sd((b, 128, t, 192))
        kl = lambda q, k, lse, scores, mask: sparse_index.index_kl(  # noqa: E731
            q, k, lse, scores, mask, sm_scale=192 ** -0.5, interpret=False)
        fn = jax.grad(kl, argnums=3) if what == "probs_bwd" else kl
        return jax.jit(fn).lower(q, q, sd((b, 128, t), jnp.float32),
                                 sd((b, t, t), jnp.float32), sd((b, t, t), jnp.int8))
    args = (sd((b, 64, t, 128)), sd((b, t, 128)), sd((b, t, 64), jnp.float32))
    scores = lambda *a: sparse_index.index_scores(*a, interpret=False)  # noqa: E731
    fn = jax.grad(lambda *a: scores(*a).sum(), argnums=(0, 1, 2)) if what == "bwd" else scores
    return jax.jit(fn).lower(*args)


CASES = {
    # llama3-1b widths: 32 q / 8 kv heads of 64, the train batch
    "flash-fwd-1b": lambda c: _flash(c, 8, 32, 8, 2048, 64, backward=False),
    "flash-bwd-1b": lambda c: _flash(c, 8, 32, 8, 2048, 64, backward=True),
    "paged-staging-1b": lambda c: _paged_staging(c, 16, 8, 4, 64, 64),
    # head_dim 128 (llama3-8b widths)
    "flash-fwd-d128": lambda c: _flash(c, 4, 32, 8, 2048, 128, backward=False),
    "flash-bwd-d128": lambda c: _flash(c, 4, 32, 8, 2048, 128, backward=True),
    "paged-staging-d128": lambda c: _paged_staging(c, 32, 8, 4, 128, 64),
    # q blocks the default 1024 does not divide: 768 (six lane tiles) and
    # 688 (no multiple of 128): the backward's [1, block_q] statistic rows
    "flash-bwd-s1536": lambda c: _flash(c, 1, 8, 4, 1536, 128, backward=True),
    "flash-bwd-s2064": lambda c: _flash(c, 1, 8, 4, 2064, 128, backward=True),
    # OLMoE-1B-7B's grouped matmuls at the benchmark's 131,072 rows: gate/up
    # (2048 -> 1024) and down (1024 -> 2048), each with its two gradients
    "moe-gmm-up": lambda c: _grouped(c, 2048, 1024, backward=False),
    "moe-gmm-up-grad": lambda c: _grouped(c, 2048, 1024, backward=True),
    "moe-gmm-down-grad": lambda c: _grouped(c, 1024, 2048, backward=True),
    # Qwen3-Next: the delta rule's two kernels, and flash at head_dim 256
    # with 16 q : 2 kv heads at the benchmark's 2 x 8192 (1024 x 1024 blocks
    # still fit the scoped VMEM at D = 256)
    "gdn-fwd": lambda c: _gdn(c, backward=False),
    "gdn-bwd": lambda c: _gdn(c, backward=True),
    "gdn-wy-fwd": lambda c: _gdn_wy(c, backward=False),
    "gdn-wy-bwd": lambda c: _gdn_wy(c, backward=True),
    "gdn-conv-fwd": lambda c: _gdn_elementwise(c, "conv", backward=False),
    "gdn-conv-bwd": lambda c: _gdn_elementwise(c, "conv", backward=True),
    "gdn-norm-fwd": lambda c: _gdn_elementwise(c, "norm", backward=False),
    "gdn-norm-bwd": lambda c: _gdn_elementwise(c, "norm", backward=True),
    "flash-fwd-d256-8k": lambda c: _flash(c, 2, 16, 2, 8192, 256, backward=False),
    "flash-bwd-d256-8k": lambda c: _flash(c, 2, 16, 2, 8192, 256, backward=True),
    # Qwen3-Next's held experts: 163,840 sorted rows of which a range is
    # computed, 64 experts of 2048 x 512
    "moe-gmm-held-grad": lambda c: _grouped(c, 2048, 512, backward=True, rows=163840),
    # dots3-note-prev: attention under a key set and under a window, value
    # heads narrower than key heads (192 / 128, 256 / 128), the indexer
    "attn-sel-fwd-8k": lambda c: _latent(c, "sel", backward=False),
    "attn-sel-bwd-8k": lambda c: _latent(c, "sel", backward=True),
    "attn-win-fwd-8k": lambda c: _latent(c, "win", backward=False),
    "attn-win-bwd-8k": lambda c: _latent(c, "win", backward=True),
    "dsa-index-fwd-8k": lambda c: _indexer(c, "fwd"),
    "dsa-index-bwd-8k": lambda c: _indexer(c, "bwd"),
    "dsa-probs-8k": lambda c: _indexer(c, "probs"),
    "dsa-probs-bwd-8k": lambda c: _indexer(c, "probs_bwd"),
    # Laguna-S-2.1 at one row of 16,384: the plain kernels at 48 query heads
    # over 8 kv heads, the window kernels at 72 over 8 (nine a kv head) under
    # a 512-key band
    "flash-fwd-48to8-16k": lambda c: _flash(c, 1, 48, 8, 16384, 128, backward=False),
    "flash-bwd-48to8-16k": lambda c: _flash(c, 1, 48, 8, 16384, 128, backward=True),
    "attn-win-fwd-72to8-16k": lambda c: _grouped_window(c, backward=False),
    "attn-win-bwd-72to8-16k": lambda c: _grouped_window(c, backward=True),
    # Kimi-K2: the plain kernels at 1 x 64 x 8192 x 192 / 128, and at the 4,096
    # positions its cell runs
    "flash-fwd-192v128-8k": lambda c: _latent_full(c, backward=False),
    "flash-bwd-192v128-8k": lambda c: _latent_full(c, backward=True),
    "flash-bwd-192v128-4k": lambda c: _latent_full(c, backward=True, t=4096),
    # the held range's adds at the four cells' shapes: rows of 40 and 56 lane
    # tiles (dots3, Kimi-K2), and 40,960 ids in scalar memory (Laguna, Qwen3-Next)
    "moe-rows-5120": lambda c: _rows(c, 16384, 8192, 5120),
    "moe-rows-7168": lambda c: _rows(c, 4096, 1536, 7168),
    "moe-rows-3072": lambda c: _rows(c, 16384, 40960, 3072),
    "moe-rows-2048": lambda c: _rows(c, 16384, 40960, 2048),
    # the held ranges' grouped matmuls at their ``cap`` rows, gate/up and down
    # with both gradients: 192 (Kimi-K2), 640 (Qwen3-Next), 1,280 (Laguna) and
    # 1,024 (dots3) rows a group choose other tiles than OLMoE's 2,048, and
    # keep the whole contraction (up to 7168 wide) in VMEM
    "moe-gmm-kimi-up-grad": lambda c: _grouped(c, 7168, 2048, True, 1536, 8, held=True),
    "moe-gmm-kimi-down-grad": lambda c: _grouped(c, 2048, 7168, True, 1536, 8, held=True),
    "moe-gmm-hybrid-up-grad": lambda c: _grouped(c, 2048, 512, True, 40960, 64, held=True),
    "moe-gmm-hybrid-down-grad": lambda c: _grouped(c, 512, 2048, True, 40960, 64, held=True),
    "moe-gmm-laguna-up-grad": lambda c: _grouped(c, 3072, 1024, True, 40960, 32, held=True),
    "moe-gmm-laguna-down-grad": lambda c: _grouped(c, 1024, 3072, True, 40960, 32, held=True),
    "moe-gmm-dots3-up-grad": lambda c: _grouped(c, 5120, 1536, True, 8192, 8, held=True),
    "moe-gmm-dots3-down-grad": lambda c: _grouped(c, 1536, 5120, True, 8192, 8, held=True),
    # SmallThinker-21BA3B at one row of 16,384: 28 query heads over 4 kv heads
    # (seven a kv head), the plain kernels with q and k un-roped and the window
    # kernels under a 4,096-key band at the blocks ``models/gqa.py`` gives them;
    # the held range's adds at 20 lane tiles a row (no multiple of 8) and
    # 49,152 ids; the held experts' grouped matmuls at 3,072 rows a group
    "flash-fwd-28to4-16k": lambda c: _flash(c, 1, 28, 4, 16384, 128, backward=False),
    "flash-bwd-28to4-16k": lambda c: _flash(c, 1, 28, 4, 16384, 128, backward=True),
    "attn-win-fwd-28to4-16k-w4096": lambda c: _grouped_window(
        c, backward=False, hq=28, hkv=4, window=4096),
    "attn-win-bwd-28to4-16k-w4096": lambda c: _grouped_window(
        c, backward=True, hq=28, hkv=4, window=4096),
    "moe-rows-2560": lambda c: _rows(c, 16384, 49152, 2560),
    "moe-gmm-prerouted-up-grad": lambda c: _grouped(c, 2560, 768, True, 49152, 16, held=True),
    "moe-gmm-prerouted-down-grad": lambda c: _grouped(c, 768, 2560, True, 49152, 16, held=True),
    # the embedding table's gradient (the same kernel, ``n`` the vocabulary,
    # the rows a step's tokens) where the vocabulary's destination tile is
    # awkward: Kimi-K2's 256 rows of 56 lane tiles, dots3's 64 of 40,
    # SmallThinker's 32 of 20 and Qwen3-Next's 16 of 16 over 1,187 grid steps
    "embed-rows-kimi": lambda c: _rows(c, 20480, 4096, 7168),
    "embed-rows-dots3": lambda c: _rows(c, 19008, 16384, 5120),
    "embed-rows-prerouted": lambda c: _rows(c, 37984, 16384, 2560),
    "embed-rows-hybrid": lambda c: _rows(c, 18992, 16384, 2048),
    # a vocabulary no 8 rows divide (GPT-2's): summed into 50,432 rows and cut
    "embed-rows-odd-vocab": lambda c: _rows(c, 50257, 8192, 2048),
    # MiniCPM-SALA at one row of 16,384: the lightning kernels at 32 heads of
    # 128, the attention kernels under block sets at 32 query heads over 2 kv
    # heads, and the embedding's gradient at 18,362 rows (no multiple of 128)
    "lightning-fwd-32h-16k": lambda c: _lightning(c, backward=False),
    "lightning-bwd-32h-16k": lambda c: _lightning(c, backward=True),
    "attn-blk-fwd-32to2-16k": lambda c: _block_sets(c, backward=False),
    "attn-blk-bwd-32to2-16k": lambda c: _block_sets(c, backward=True),
    "embed-rows-sala": lambda c: _rows(c, 18362, 16384, 4096),
    # granite-4.0-h-micro at one row of 32,768: the scan's kernels at 64 heads
    # of 64 (half a lane tile), the plain attention kernels at 32 : 8 heads of
    # 64 (the first head under 128 through them on the chip) and the tied
    # table's gradient at 25,088 rows and 32,768 ids a call
    "ssd-fwd-64h-32k": lambda c: _ssd(c, backward=False),
    "ssd-bwd-64h-32k": lambda c: _ssd(c, backward=True),
    # its conv over x as the projection leaves it, and over B | C (one lane
    # tile a state of 128: two channel tiles)
    "mamba-conv-fwd-32k": lambda c: _mamba_conv(c, backward=False),
    "mamba-conv-bwd-32k": lambda c: _mamba_conv(c, backward=True),
    "mamba-conv-fwd-bc-32k": lambda c: _mamba_conv(c, backward=False, c=2),
    "mamba-conv-bwd-bc-32k": lambda c: _mamba_conv(c, backward=True, c=2),
    "flash-fwd-32to8-d64-32k": lambda c: _flash(c, 1, 32, 8, 32768, 64, backward=False),
    "flash-bwd-32to8-d64-32k": lambda c: _flash(c, 1, 32, 8, 32768, 64, backward=True),
    "embed-rows-granite": lambda c: _rows(c, 25088, 32768, 2048),
    # lfm2-8b-a1b at four rows of 8,192: the short conv's mix between its products
    "sconv-fwd-4x8k": lambda c: _sconv(c, backward=False),
    "sconv-bwd-4x8k": lambda c: _sconv(c, backward=True),
}


# the routed experts' and the row kernels' cases are ``tests/test_chip_compile_moe.py``'s
# (a file goes to one worker, and these are four tenths of the compiles' time)
ROWS_AND_GROUPS = ("moe-", "embed-")


@pytest.mark.parametrize("case", [c for c in CASES if not c.startswith(ROWS_AND_GROUPS)])
def test_kernel_compiles_for_the_chip(chip, case):
    check_case(chip, case)


def check_case(chip, case):
    program = CASES[case](chip).compile().as_text()
    assert "tpu_custom_call" in program
    if case.startswith("dsa-probs"):  # the loss's forward kernel; with its gradient, both
        assert program.count('custom_call_target="tpu_custom_call"') == 1 + ("bwd" in case)
    if case.startswith(("gdn-conv", "gdn-norm", "mamba-conv", "sconv")):  # one kernel each way
        assert program.count('custom_call_target="tpu_custom_call"') == 1
    if case.startswith("moe-gmm") and case.endswith("-grad"):  # forward, d_lhs, d_rhs
        assert program.count('custom_call_target="tpu_custom_call"') == 3


@pytest.mark.parametrize("names,kernels", [
    (("q", "k", "v", "attn_out", "attn_lse"), 3), ((), 4)], ids=["saved", "recomputed"])
def test_saved_residual_names_decide_the_forward_kernel_count(chip, names, kernels):
    """internlm2-1.8b's attention at the benchmark's 2 x 4096: with the
    rule's residual names saved (remat ``attn``) the chip's program holds
    the forward kernel once beside dQ and dK/dV; with nothing saved it
    holds it twice. The backward kernels' [1, block_q] lse and delta rows
    compile too."""
    q = jax.ShapeDtypeStruct((2, 16, 4096, 128), jnp.bfloat16, sharding=chip)
    kv = jax.ShapeDtypeStruct((2, 8, 4096, 128), jnp.bfloat16, sharding=chip)
    attend = jax.checkpoint(
        lambda q, k, v: flash_attention(q, k, v, causal=True, interpret=False),
        policy=jax.checkpoint_policies.save_only_these_names(*names))

    def loss(q, k, v):
        # a nonlinear tail, so that the backward pass needs the output
        return (attend(q, k, v).astype(jnp.float32) ** 2).sum()

    program = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(q, kv, kv).compile().as_text()
    assert program.count('custom_call_target="tpu_custom_call"') == kernels


def test_a_mamba2_layer_calls_each_conv_forward_once_and_none_under_remat(chip, monkeypatch):
    """One ``mamba2`` layer's step (two heads of 64 a lane tile, a state of
    128) under remat ``attn`` with the kind's ``SAVE_NAMES``, every kernel
    module steered to the chip: the optimized text holds ``mamba_conv_fwd``
    ONCE per conv (x, and B | C) and none under ``rt_pass="remat"`` (its
    outputs are saved names and its residuals are its inputs, so the second run
    has no use for the call), ``mamba_conv_bwd`` once per conv."""
    import dataclasses
    import importlib
    import re
    import sys

    from ray_tpu.models.llama import MIXERS, PRESETS
    from ray_tpu.models.mamba2 import SAVE_NAMES, Mamba2
    from test_chip_compile_steps import KERNEL_MODULES, lowered_step

    for name in KERNEL_MODULES:
        importlib.import_module(name)
        monkeypatch.setattr(sys.modules[name], "on_tpu", lambda: True)
    assert MIXERS["mamba2"].save_names == SAVE_NAMES
    cfg = dataclasses.replace(
        PRESETS["granite-hybrid-debug"], n_layers=1, layer_pattern=("mamba2",), remat_policy="attn",
        mamba2=Mamba2(heads=4, head_dim=64, state=128, chunk=128))
    jax.clear_caches()
    calls = [line for line in lowered_step(chip, cfg).compile().as_text().splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]
    jax.clear_caches()
    passes = {}
    for line in calls:
        kernel = re.sub(r"[.\d]+$", "", line.split(" = ")[0].strip().removeprefix("ROOT ").lstrip("%"))
        if kernel.startswith("mamba_"):
            assert 'rt_scope="stack/attn/mamba_conv"' in line
            passes.setdefault(kernel, []).append(re.search(r'rt_pass="(\w+)"', line).group(1))
    assert passes == {"mamba_conv_fwd": ["fwd", "fwd"], "mamba_conv_bwd": ["bwd", "bwd"]}, passes


@pytest.mark.slow  # two whole-step compiles at the cell's size: 4-5 minutes
def test_saving_the_stream_costs_the_sparse_step_no_more_than_its_bytes(chip, monkeypatch):
    """``dots3-note-prev.train-8k-sparse``'s step (loss and gradient at 2 x
    8192 under remat ``attn``, every kernel module steered to the chip): with
    ``post_attn`` among the latent kinds' saved names the compiler's count
    rises, and by no more than TWO of the five layers' streams ([2, 8192,
    5120] bf16 = 167,772,160 bytes each) over the tuple without it. PR 52 read
    +193,583,104 here (the runner's whole step with its optimizer +235,457,536:
    the scanned layers' streams join the stacked residuals, which are not all
    live at the peak); ISSUE 52's own bound was all five, 838,860,800. Not in
    tier 1: two compiles of 70-85 s, and no smaller step shows it (one layer
    at 1 x 2048 and the debug preset read -0.5 to +0.8 MB, the scheduler's
    noise; CHANGES.md, PR 52)."""
    import dataclasses
    import importlib
    import json
    import sys

    from benchmark.manifest import HERE
    from benchmark.runners import train_sparse
    from ray_tpu.models.llama import MIXERS
    from ray_tpu.models.mla import LATENT_NAMES, SAVE_NAMES
    from test_chip_compile_steps import KERNEL_MODULES, lowered_step

    for name in KERNEL_MODULES:
        importlib.import_module(name)
        monkeypatch.setattr(sys.modules[name], "on_tpu", lambda: True)
    with open(os.path.join(HERE, "configs", "dots3-note-prev.json")) as f:
        conf = json.load(f)
    cfg = train_sparse.model_config(conf["model"], conf["train"], remat_policy="attn")
    rows, seq = conf["train"]["batch"], 8192

    def total():
        jax.clear_caches()
        m = lowered_step(chip, cfg, rows, seq, conf["train"]["loss_chunk_tokens"]
                         ).compile().memory_analysis()
        return (m.argument_size_in_bytes + m.output_size_in_bytes + m.temp_size_in_bytes
                - m.alias_size_in_bytes)

    saved = total()
    for kind in ("mla", "mla_win"):
        assert MIXERS[kind].save_names == SAVE_NAMES
        monkeypatch.setitem(MIXERS, kind,
                            dataclasses.replace(MIXERS[kind], save_names=LATENT_NAMES))
    remade = total()
    jax.clear_caches()
    assert 0 < saved - remade <= 2 * rows * seq * cfg.hidden * 2, (saved, remade)


def _without_locations(lowered_text: str) -> str:
    """A lowered program's text with every Mosaic call's bytecode replaced by
    its module's assembly WITHOUT debug info: the bytecode holds source lines,
    which move with every edit of the file."""
    import base64
    import re

    from jax._src.interpreters import mlir as jax_mlir
    from jax._src.lib import tpu
    from jax._src.lib.mlir import ir

    def body(match):
        ctx = jax_mlir.make_ir_context()
        tpu.register_dialect(ctx)
        ctx.allow_unregistered_dialects = True
        with ctx:
            module = ir.Module.parse(base64.b64decode(match.group(1)))
            return "BODY<<" + module.operation.get_asm(enable_debug_info=False) + ">>BODY"

    text = re.sub(r"loc\([^)]*\)", "", lowered_text)
    return re.sub(r'\\22body\\22: \\22([A-Za-z0-9+/=]+)\\22', body, text)


PLAIN_FLASH_KERNELS = "c344d17d2ea9862d6718e08c33b46ddcb50b010c808173cd60234679b8e19a23"


def test_the_plain_causal_kernels_are_the_parents_instruction_for_instruction(chip):
    """internlm2-1.8b's attention at the benchmark's 2 x 4096, forward and both
    backward kernels: the lowered program, its three Mosaic modules printed
    without source locations, is what PR 40's tree lowered here the same way
    (by its SHA-256). PR 40 moved the anchor: the grid's last axis walks a
    table of live tiles, so the modules read their tile from SMEM and hold
    no ``needed`` branch (until then the anchor was PR 33's tree, 2376b56,
    0eff5c1e...). A kernel variant must leave the plain path's traced
    instructions, their order included, as they were."""
    import hashlib

    q = jax.ShapeDtypeStruct((2, 16, 4096, 128), jnp.bfloat16, sharding=chip)
    kv = jax.ShapeDtypeStruct((2, 8, 4096, 128), jnp.bfloat16, sharding=chip)

    def loss(q, k, v):
        return flash_attention(q, k, v, causal=True, interpret=False).astype(jnp.float32).sum()

    import re

    text = _without_locations(
        jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(q, kv, kv).as_text(debug_info=False))
    kernels = re.findall(r"BODY<<.*?>>BODY", text, flags=re.S)
    assert len(kernels) == 3
    assert hashlib.sha256("".join(kernels).encode()).hexdigest() == PLAIN_FLASH_KERNELS, (
        [len(k) for k in kernels])

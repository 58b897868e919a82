"""Kernel correctness: flash attention vs reference, ring attention vs
full attention, rope/rmsnorm sanity."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
try:
    from jax import shard_map
except ImportError:  # jax < 0.6: keep the kernel tests collectable
    from jax.experimental.shard_map import shard_map
from jax.sharding import PartitionSpec as P

from ray_tpu.ops import (
    apply_rope,
    flash_attention,
    mha_reference,
    ring_attention,
    rms_norm,
)
from ray_tpu.parallel import MeshConfig, create_mesh


def _qkv(key, b=2, hq=4, hkv=2, s=256, d=64, dtype=jnp.float32):
    ks = jax.random.split(key, 3)
    q = jax.random.normal(ks[0], (b, hq, s, d), dtype)
    k = jax.random.normal(ks[1], (b, hkv, s, d), dtype)
    v = jax.random.normal(ks[2], (b, hkv, s, d), dtype)
    return q, k, v


def test_flash_matches_reference_causal():
    q, k, v = _qkv(jax.random.PRNGKey(0))
    out = flash_attention(q, k, v, causal=True, block_q=128, block_k=128)
    ref = mha_reference(q, k, v, causal=True)
    np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)


def test_flash_matches_reference_noncausal():
    q, k, v = _qkv(jax.random.PRNGKey(1), s=128)
    out = flash_attention(q, k, v, causal=False, block_q=64, block_k=64)
    ref = mha_reference(q, k, v, causal=False)
    np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)


def test_flash_odd_seq_falls_back():
    q, k, v = _qkv(jax.random.PRNGKey(2), s=100)
    out = flash_attention(q, k, v, causal=True)
    ref = mha_reference(q, k, v, causal=True)
    np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)


def test_ring_attention_matches_full():
    mesh = create_mesh(MeshConfig(sp=8))
    b, h, s, d = 2, 4, 128, 32
    key = jax.random.PRNGKey(3)
    q, k, v = _qkv(key, b=b, hq=h, hkv=h, s=s, d=d)

    spec = P(None, None, "sp", None)
    fn = shard_map(
        lambda q_, k_, v_: ring_attention(q_, k_, v_, axis="sp", causal=True),
        mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec, check_vma=False,
    )
    out = fn(q, k, v)
    ref = mha_reference(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5, rtol=2e-5)


def test_ring_attention_gqa():
    mesh = create_mesh(MeshConfig(dp=2, sp=4))
    q, k, v = _qkv(jax.random.PRNGKey(4), b=1, hq=4, hkv=2, s=64, d=16)
    spec = P(None, None, "sp", None)
    fn = shard_map(
        lambda q_, k_, v_: ring_attention(q_, k_, v_, axis="sp", causal=True),
        mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec, check_vma=False,
    )
    out = fn(q, k, v)
    ref = mha_reference(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5, rtol=2e-5)


def test_rms_norm():
    x = jax.random.normal(jax.random.PRNGKey(0), (4, 8))
    w = jnp.full((8,), 2.0)
    out = rms_norm(x, w)
    expected = 2.0 * x / np.sqrt((np.asarray(x) ** 2).mean(-1, keepdims=True) + 1e-6)
    np.testing.assert_allclose(out, expected, atol=1e-5, rtol=1e-5)


def test_rope_preserves_norm_and_zero_position():
    q = jax.random.normal(jax.random.PRNGKey(0), (1, 2, 8, 16))
    pos = jnp.arange(8, dtype=jnp.int32)
    out = apply_rope(q, pos)
    # rotation preserves per-pair norms
    np.testing.assert_allclose(
        np.linalg.norm(np.asarray(out), axis=-1),
        np.linalg.norm(np.asarray(q), axis=-1), rtol=1e-5)
    # position 0 is identity
    np.testing.assert_allclose(out[:, :, 0], q[:, :, 0], atol=1e-6)


def test_flash_attention_grads_match_reference():
    ks = jax.random.split(jax.random.PRNGKey(7), 3)
    q = jax.random.normal(ks[0], (2, 4, 256, 32))
    k = jax.random.normal(ks[1], (2, 2, 256, 32))
    v = jax.random.normal(ks[2], (2, 2, 256, 32))

    def loss(f):
        return lambda q_, k_, v_: (f(q_, k_, v_) ** 2).sum()

    gf = jax.grad(loss(lambda a, b, c: flash_attention(a, b, c, causal=True)),
                  argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss(lambda a, b, c: mha_reference(a, b, c, causal=True)),
                  argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gf, gr):
        np.testing.assert_allclose(a, b, atol=2e-4, rtol=2e-4)


@pytest.mark.parametrize("hq,hkv,seq", [(4, 2, 256), (4, 4, 256), (4, 2, 100)],
                         ids=["gqa", "mha", "ragged-fallback"])
def test_flash_attention_grads_under_saved_residual_names(hq, hkv, seq):
    """The vjp rule names its residuals (``attn_out``, compact ``attn_lse``):
    a ``jax.checkpoint`` policy that saves them hands the backward kernels
    copies, not a recomputation, so the gradients are the same bits as
    without ``jax.checkpoint`` — and the forward kernel is traced once."""
    ks = jax.random.split(jax.random.PRNGKey(11), 3)
    q = jax.random.normal(ks[0], (2, hq, seq, 32))
    k = jax.random.normal(ks[1], (2, hkv, seq, 32))
    v = jax.random.normal(ks[2], (2, hkv, seq, 32))

    def attend(q_, k_, v_):
        return flash_attention(q_, k_, v_, causal=True, block_q=128, block_k=128)

    def loss(f):
        return lambda q_, k_, v_: (f(q_, k_, v_) ** 2).sum()

    saved = jax.checkpoint(attend, policy=jax.checkpoint_policies.save_only_these_names(
        "q", "k", "v", "attn_out", "attn_lse"))
    plain = jax.grad(loss(attend), argnums=(0, 1, 2))
    under = jax.grad(loss(saved), argnums=(0, 1, 2))
    ref = jax.grad(loss(lambda a, b, c: mha_reference(a, b, c, causal=True)),
                   argnums=(0, 1, 2))
    for got, same, close in zip(under(q, k, v), plain(q, k, v), ref(q, k, v)):
        np.testing.assert_array_equal(np.asarray(got), np.asarray(same))
        np.testing.assert_allclose(got, close, atol=2e-4, rtol=2e-4)
    if seq % 16 == 0:  # on the kernels: saved, the forward runs once; unsaved, twice
        count = lambda f: str(jax.make_jaxpr(f)(q, k, v)).count("name=flash_fwd")
        assert count(under) == 1
        assert count(jax.grad(loss(jax.checkpoint(attend)), argnums=(0, 1, 2))) == 2


def test_flash_block_fits_seq_divisors():
    """Default blocks (1024) must CLAMP to a divisor of odd-but-tileable
    seqs (1536 -> 768) so those shapes stay on the Pallas kernel instead
    of silently falling back to the unblocked reference."""
    import numpy as np

    from ray_tpu.ops.attention import _fit_block, flash_attention, mha_reference

    assert _fit_block(1024, 2048) == 1024
    assert _fit_block(1024, 1536) == 768
    assert _fit_block(1024, 512) == 512
    assert _fit_block(512, 48) == 48
    # ragged (not a multiple of 16): no divisor works -> caller falls back
    assert 100 % _fit_block(1024, 100) != 0

    q = jax.random.normal(jax.random.PRNGKey(0), (1, 2, 1536, 64), jnp.float32)
    k = jax.random.normal(jax.random.PRNGKey(1), (1, 1, 1536, 64), jnp.float32)
    v = jax.random.normal(jax.random.PRNGKey(2), (1, 1, 1536, 64), jnp.float32)
    np.testing.assert_allclose(
        np.asarray(flash_attention(q, k, v)),
        np.asarray(mha_reference(q, k, v)), atol=2e-3)


def test_ulysses_attention_matches_full():
    """Ulysses SP (all-to-all heads<->sequence reshuffle + local flash)
    must match full attention exactly, including GQA head counts."""
    from ray_tpu.ops import ulysses_attention

    mesh = create_mesh(MeshConfig(dp=2, sp=4))
    spec = P(None, None, "sp", None)
    for hq, hkv in ((8, 8), (8, 4)):
        q, k, v = _qkv(jax.random.PRNGKey(5), b=2, hq=hq, hkv=hkv, s=128, d=32)
        fn = shard_map(
            lambda q_, k_, v_: ulysses_attention(q_, k_, v_, axis="sp", causal=True),
            mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
            check_vma=False,
        )
        out = fn(q, k, v)
        ref = mha_reference(q, k, v, causal=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=2e-5, rtol=2e-5)


def test_ulysses_in_model_forward():
    """attn_impl="ulysses" trains end-to-end over an sp mesh with the
    same loss as the reference attention (model-level parity)."""
    import dataclasses

    from ray_tpu.models import PRESETS, init_params, loss_fn

    mesh = create_mesh(MeshConfig(sp=4, dp=2))
    cfg = dataclasses.replace(PRESETS["debug"], dtype=jnp.float32,
                              attn_impl="ulysses")
    cfg_ref = dataclasses.replace(cfg, attn_impl="reference")
    params = init_params(cfg, jax.random.PRNGKey(0))
    batch = {"tokens": jax.random.randint(jax.random.PRNGKey(1), (2, 64), 0,
                                          cfg.vocab_size)}
    l_u = loss_fn(params, batch, cfg, mesh=mesh)
    l_r = loss_fn(params, batch, cfg_ref, mesh=mesh)
    np.testing.assert_allclose(float(l_u), float(l_r), rtol=1e-5)


def _paged_dense_ref(q, kp, vp, bt, pos, page):
    """Dense ground truth for the paged decode kernel: gather the full
    block-table capacity, mask positions beyond ``pos``."""
    n, kh, g, d = q.shape
    max_pages = bt.shape[1]
    gk = jnp.swapaxes(kp[bt], 1, 2).reshape(n, kh, -1, d)
    gv = jnp.swapaxes(vp[bt], 1, 2).reshape(n, kh, -1, d)
    live = jnp.arange(max_pages * page)[None] <= pos[:, None]
    s = jnp.einsum("nkgd,nktd->nkgt", q, gk).astype(jnp.float32) * d ** -0.5
    s = jnp.where(live[:, None, None], s, -jnp.inf)
    p = jax.nn.softmax(s, -1).astype(q.dtype)
    return jnp.einsum("nkgt,nktd->nkgd", p, gv)


def test_paged_decode_attention_matches_dense():
    from ray_tpu.ops.paged_attention import paged_decode_attention

    rng = np.random.default_rng(0)
    n, kh, g, d = 3, 2, 2, 32
    page, max_pages, pool = 16, 8, 32
    q = jnp.array(rng.standard_normal((n, kh, g, d)), jnp.float32)
    kp = jnp.array(rng.standard_normal((pool, kh, page, d)), jnp.float32)
    vp = jnp.array(rng.standard_normal((pool, kh, page, d)), jnp.float32)
    bt = jnp.array(rng.permutation(pool)[: n * max_pages].reshape(n, max_pages),
                   jnp.int32)
    # mixed fill levels incl. page-boundary edges and a full table
    pos = jnp.array([5, 40, 127], jnp.int32)
    ref = _paged_dense_ref(q, kp, vp, bt, pos, page)
    for ppb in (1, 3, None):  # incl. a ppb that does not divide max_pages
        out = paged_decode_attention(q, kp, vp, bt, pos, page_size=page,
                                     pages_per_block=ppb, interpret=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=2e-5, rtol=1e-4)


def test_paged_decode_attention_edges_and_bf16():
    from ray_tpu.ops.paged_attention import paged_decode_attention

    rng = np.random.default_rng(1)
    n, kh, g, d = 4, 2, 3, 16     # G=3: exercises the sublane pad path
    page, max_pages, pool = 16, 4, 24
    q = jnp.array(rng.standard_normal((n, kh, g, d)), jnp.float32)
    kp = jnp.array(rng.standard_normal((pool, kh, page, d)), jnp.float32)
    vp = jnp.array(rng.standard_normal((pool, kh, page, d)), jnp.float32)
    bt = jnp.array(rng.permutation(pool)[: n * max_pages].reshape(n, max_pages),
                   jnp.int32)
    # first token, page boundary both sides, overflow (pos past capacity:
    # decode_loop's done-slots keep incrementing pos — their output is
    # unspecified garbage but must stay finite, never NaN-poisoning)
    pos = jnp.array([0, 15, 16, max_pages * page + 7], jnp.int32)
    ref = _paged_dense_ref(q, kp, vp, bt, jnp.minimum(pos, max_pages * page - 1),
                           page)
    out = paged_decode_attention(q, kp, vp, bt, pos, page_size=page,
                                 interpret=True)
    np.testing.assert_allclose(np.asarray(out[:3]), np.asarray(ref[:3]),
                               atol=2e-5, rtol=1e-4)
    assert np.isfinite(np.asarray(out[3])).all()

    ref16 = _paged_dense_ref(q.astype(jnp.bfloat16), kp.astype(jnp.bfloat16),
                             vp.astype(jnp.bfloat16), bt,
                             jnp.minimum(pos, max_pages * page - 1), page)
    out16 = paged_decode_attention(
        q.astype(jnp.bfloat16), kp.astype(jnp.bfloat16),
        vp.astype(jnp.bfloat16), bt, pos, page_size=page, interpret=True)
    np.testing.assert_allclose(
        np.asarray(out16[:3], np.float32), np.asarray(ref16[:3], np.float32),
        atol=0.08)

"""The kernels under the latent-attention decoder's selection of keys: the
flash kernels under a window and under a key set (``ops/attention.py``), and
the indexer's scores, selection and KL (``ops/sparse_index.py``), against plain
``jnp`` in float32 on the CPU, interpreted. The model over them:
``tests/test_latent_sparse_model.py``."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import latent_sparse_decoder as ref
from ray_tpu.ops import sparse_index
from ray_tpu.ops.attention import flash_attention, mha_reference


def rel(got, want):
    return float(jnp.linalg.norm((got - want).ravel()) / jnp.linalg.norm(want.ravel()))


@pytest.mark.parametrize("kw", [dict(window=5), dict(window=40), dict(mask=True),
                                dict(mask=True, window=None)],
                         ids=["window5", "window40", "keyset", "keyset_again"])
@pytest.mark.parametrize("dims", [(48, 32), (64, 32)], ids=["192_128", "256_128"])
def test_attention_kernels_under_masks_match_mha_reference(kw, dims):
    d, dv = dims
    key = jax.random.PRNGKey(3)
    b, h, s = 2, 2, 128
    q, k = (jax.random.normal(jax.random.fold_in(key, i), (b, h, s, d)) for i in (0, 1))
    v = jax.random.normal(jax.random.fold_in(key, 2), (b, h, s, dv))
    kw = dict(kw)
    if kw.pop("mask", False):
        keep = jax.random.uniform(jax.random.fold_in(key, 3), (b, s, s)) < 0.3
        kw["mask"] = ((keep | jnp.eye(s, dtype=bool)) & jnp.tril(jnp.ones((s, s), bool))
                      ).astype(jnp.int8)
    got = lambda *x: flash_attention(*x, block_q=32, block_k=32, **kw)  # noqa: E731
    want = lambda *x: mha_reference(*x, **kw)  # noqa: E731
    assert rel(got(q, k, v), want(q, k, v)) < 1e-5
    g = jax.grad(lambda *x: jnp.sum(got(*x) ** 2), (0, 1, 2))(q, k, v)
    w = jax.grad(lambda *x: jnp.sum(want(*x) ** 2), (0, 1, 2))(q, k, v)
    assert max(rel(a, b_) for a, b_ in zip(g, w)) < 1e-5


def test_window_kernels_walk_only_the_band():
    from ray_tpu.ops.attention import _tile_walk

    # 8k rows in 512-blocks under a 513-wide window: two key blocks a query
    # block, two query blocks a key block, of sixteen (one in the first and
    # the last row: 31 tiles of 256, every one of them live)
    for key_major in (False, True):
        (q_blocks, k_blocks, first, last), steps = _tile_walk(
            16, 16, 512, 512, True, 513, key_major=key_major)
        rows = k_blocks if key_major else q_blocks
        assert np.bincount(rows).max() == 2 and (len(rows), len(rows)) == steps == (31, 31)
        assert (q_blocks - k_blocks).tolist() == [0] + [1, 0] * 15
        assert first.sum() == last.sum() == 16


def test_index_scores_and_selection_match_plain_jnp(monkeypatch):
    monkeypatch.setattr(sparse_index, "FWD_BLOCKS", (64, 128))
    monkeypatch.setattr(sparse_index, "BWD_BLOCKS", (32, 128))
    key = jax.random.PRNGKey(4)
    b, j, t, d = 2, 3, 256, 32
    q = jax.random.normal(key, (b, j, t, d))
    k = jax.random.normal(jax.random.fold_in(key, 1), (b, t, d))
    w = jax.random.normal(jax.random.fold_in(key, 2), (b, t, j))
    g = jax.random.normal(jax.random.fold_in(key, 3), (b, t, t))
    want = sparse_index.index_scores_reference(q, k, w)
    assert rel(sparse_index.index_scores(q, k, w), want) < 1e-5
    got_g = jax.grad(lambda *x: jnp.sum(sparse_index.index_scores(*x) * g), (0, 1, 2))(q, k, w)
    want_g = jax.grad(lambda *x: jnp.sum(sparse_index.index_scores_reference(*x) * g),
                      (0, 1, 2))(q, k, w)
    assert max(rel(a, b_) for a, b_ in zip(got_g, want_g)) < 1e-5
    mask = sparse_index.select_top_k(want, 16)
    assert np.array_equal(np.asarray(mask[0], bool), np.asarray(ref.select(want[0], 16)))
    counts = np.asarray(mask.sum(-1))
    # (a row whose 16th score is an exact 0, every head's ReLU shut, keeps its ties)
    assert (counts[:, :16] == np.arange(1, 17)).all() and (counts[:, 16:] >= 16).all()
    assert np.median(counts[:, 16:]) == 16
    # a tie at the last place keeps every tied key
    tied = want.at[:, -1, :20].set(7.0).at[:, -1, 20:].set(0.0)
    assert int(sparse_index.select_top_k(tied, 16)[0, -1].sum()) == 20


KL_CASES = {
    # two batch rows in 128-blocks of 256: the statistics gather over two key
    # blocks, the tile above the diagonal is skipped
    "two_rows_two_key_blocks": dict(b=2, h=3, t=256, d=48, block=128, top_k=16),
    # every row shorter than top_k: every causal key is in its set
    "every_causal_key_kept": dict(b=1, h=2, t=128, d=16, block=128, top_k=300),
    "a_tie_at_the_threshold": dict(b=2, h=2, t=256, d=16, block=128, top_k=16, tie=True),
    # a key outside the set scores above its row's logsumexp (over the set)
    "a_key_above_the_logsumexp": dict(b=1, h=2, t=128, d=16, block=128, top_k=4, clamp=True),
    "one_block": dict(b=2, h=2, t=32, d=16, block=1024, top_k=4),
    # no block divides 200: index_loss of head_summed_probs_reference itself
    "a_length_no_block_fits": dict(b=1, h=2, t=200, d=16, block=1024, top_k=8, path="reference"),
}


@pytest.mark.parametrize("case", list(KL_CASES))
def test_index_kl_and_its_gradient_match_the_plain_form(case):
    """The loss made tile by tile against ``index_loss`` of the plain
    head-summed probabilities, and its gradient by the backward kernel
    against ``jax.grad`` of that form."""
    from ray_tpu.ops import trace_log

    c = KL_CASES[case]
    b, h, t, d, top_k = (c[x] for x in ("b", "h", "t", "d", "top_k"))
    key = jax.random.PRNGKey(5)
    q, k = (jax.random.normal(jax.random.fold_in(key, i), (b, h, t, d)) for i in (0, 1))
    causal = jnp.tril(jnp.ones((t, t), bool))
    scores = jnp.where(causal, 2.0 * jax.random.normal(jax.random.fold_in(key, 2), (b, t, t)), 0.0)
    if c.get("tie"):
        scores = scores.at[:, -1, :20].set(7.0).at[:, -1, 20:].set(-1.0)
    mask = sparse_index.select_top_k(scores, top_k)
    if c.get("tie"):
        assert int(mask[0, -1].sum()) == 20
    s = jnp.einsum("bhtd,bhsd->bhts", q, k) * 0.3
    kept = mask[:, None] != 0
    lse = jax.nn.logsumexp(jnp.where(kept, s, -jnp.inf), axis=-1)
    if c.get("clamp"):
        assert float(jnp.max(jnp.where(~kept & causal, s - lse[..., None], -jnp.inf))) > 1.0
    before = trace_log.kernel_traces().get("dsa_probs:" + c.get("path", "interpret"), 0)
    want, want_g = jax.value_and_grad(lambda x: sparse_index.index_loss(
        x, sparse_index.head_summed_probs_reference(q, k, lse, 0.3), mask))(scores)
    got, (*others, got_g) = jax.value_and_grad(lambda q, k, lse, x: sparse_index.index_kl(
        q, k, lse, x, mask, sm_scale=0.3, block=c["block"]), (0, 1, 2, 3))(q, k, lse, scores)
    assert trace_log.kernel_traces()["dsa_probs:" + c.get("path", "interpret")] > before
    assert float(want) > 0.01 and abs(float(got) - float(want)) < 1e-5 * float(want)
    assert rel(got_g, want_g) < 1e-5
    # nothing outside the key sets, and no cotangent but the scores'
    assert not np.asarray(jnp.where(mask != 0, 0.0, got_g)).any()
    assert not any(np.asarray(x).any() for x in others)

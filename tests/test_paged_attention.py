"""Paged-attention v2 (staging-buffer) correctness.

Tier-1 (CPU) coverage for the kernel the TPU decode path defaults to:
the page pool is strictly READ-ONLY across a K-step fused dispatch,
tokens generated mid-dispatch accumulate in a small staging carry the
kernel folds into its online softmax, and ONE batched scatter commits
them back at the dispatch boundary (``ops/paged_attention.py``,
``llm/model.py::decode_loop``/``commit_staging``). The dense gather is
the numerical ground truth throughout.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.llm.engine import InferenceEngine, Request
from ray_tpu.llm.executor import resolve_attention_impl
from ray_tpu.models.llama import PRESETS, init_params


@pytest.fixture(scope="module")
def small_model():
    cfg = dataclasses.replace(PRESETS["debug"], dtype=jnp.float32,
                              attn_impl="reference")
    params = init_params(cfg, jax.random.PRNGKey(0))
    return cfg, params


# ------------------------------------------------------- kernel (staging)

def _dense_ref(q, kp, vp, bt, pos, page):
    n, kh, g, d = q.shape
    max_pages = bt.shape[1]
    gk = jnp.swapaxes(kp[bt], 1, 2).reshape(n, kh, -1, d)
    gv = jnp.swapaxes(vp[bt], 1, 2).reshape(n, kh, -1, d)
    live = jnp.arange(max_pages * page)[None] <= pos[:, None]
    s = jnp.einsum("nkgd,nktd->nkgt", q, gk).astype(jnp.float32) * d ** -0.5
    s = jnp.where(live[:, None, None], s, -jnp.inf)
    p = jax.nn.softmax(s, -1).astype(q.dtype)
    return jnp.einsum("nkgt,nktd->nkgd", p, gv)


def test_kernel_staging_rows_fold_into_softmax():
    """Staged rows [0, stage_idx] must be attended exactly as if they
    lived in the pool — including pos == 0 (no pool context at all)."""
    from ray_tpu.ops.paged_attention import paged_decode_attention, stage_rows

    rng = np.random.default_rng(3)
    n, kh, g, d = 3, 2, 2, 32
    page, max_pages, pool = 16, 8, 32
    q = jnp.array(rng.standard_normal((n, kh, g, d)), jnp.float32)
    kp = jnp.array(rng.standard_normal((pool, kh, page, d)), jnp.float32)
    vp = jnp.array(rng.standard_normal((pool, kh, page, d)), jnp.float32)
    bt = jnp.array(rng.permutation(pool)[: n * max_pages].reshape(n, max_pages),
                   jnp.int32)
    # positions incl. a page-boundary crossing INSIDE the staged range
    # (pos 17 with stage_idx 2 -> staged rows span positions 15..17)
    pos = jnp.array([5, 17, 40], jnp.int32)
    si = 2
    ref = _dense_ref(q, kp, vp, bt, pos, page)

    # Move the last si+1 positions of each slot out of the pool and into
    # the staging rows; poison the vacated pool entries to prove the
    # kernel reads staging, not the pool, for those positions.
    sc = stage_rows(8)
    ks = jnp.zeros((1, n, kh, sc, d), jnp.float32)
    vs = jnp.zeros((1, n, kh, sc, d), jnp.float32)
    kp2, vp2 = kp, vp
    base = pos - si
    for j in range(si + 1):
        p_abs = base + j
        wp = jnp.take_along_axis(bt, (p_abs // page)[:, None], axis=1)[:, 0]
        ks = ks.at[0, :, :, j].set(kp[wp, :, p_abs % page])
        vs = vs.at[0, :, :, j].set(vp[wp, :, p_abs % page])
        kp2 = kp2.at[wp, :, p_abs % page].set(1e6)
        vp2 = vp2.at[wp, :, p_abs % page].set(1e6)
    out = paged_decode_attention(q, kp2, vp2, bt, pos, page_size=page,
                                 k_stage=ks, v_stage=vs,
                                 stage_idx=jnp.int32(si), interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=1e-4)

    # pos == 0: no pool block runs (m = -inf, l = 0); the normalize must
    # still produce exactly the staged row-0 value.
    out0 = paged_decode_attention(q, kp2, vp2, bt, jnp.zeros((n,), jnp.int32),
                                  page_size=page, k_stage=ks, v_stage=vs,
                                  stage_idx=jnp.int32(0), interpret=True)
    ref0 = jnp.broadcast_to(vs[0, :, :, 0][:, :, None, :], out0.shape)
    np.testing.assert_allclose(np.asarray(out0), np.asarray(ref0),
                               atol=2e-5, rtol=1e-4)


def test_stage_rows_padding():
    from ray_tpu.ops.paged_attention import stage_rows

    assert stage_rows(1) == 16
    assert stage_rows(16) == 16
    assert stage_rows(17) == 32
    assert stage_rows(32) == 32


# --------------------------------------------- decode_loop commit parity

def test_decode_loop_pool_commit_matches_dense(small_model):
    """After a K-step paged dispatch the pool must hold exactly what the
    dense path wrote step-by-step — the ONE batched commit scatter is the
    only pool write, and a SECOND dispatch decoding from that pool must
    stay token-identical (staging-carry wraparound across the K-step
    boundary: positions cross a page edge mid-dispatch)."""
    from ray_tpu.llm.model import decode_loop, init_pages

    cfg, params = small_model
    page, slots, max_pages = 8, 3, 6
    num_pages = slots + slots * max_pages
    pages0 = init_pages(cfg, num_pages, page)
    rng = np.random.default_rng(0)
    # pre-filled context: random K/V in the live prefix of each table
    pages0 = {k: jnp.array(rng.standard_normal(v.shape), jnp.float32)
              for k, v in pages0.items()}
    bt = np.arange(slots, slots + slots * max_pages,
                   dtype=np.int32).reshape(slots, max_pages)
    bt = jnp.asarray(bt)
    # mid-page, page-boundary, and deep positions; K=8 crosses a page
    # edge for every slot inside the dispatch
    pos = jnp.array([5, 8, 12], jnp.int32)
    tokens = jnp.array([3, 7, 11], jnp.int32)
    temps = jnp.zeros(slots, jnp.float32)
    eos = jnp.full(slots, -1, jnp.int32)
    remaining = jnp.full(slots, 100, jnp.int32)
    key = jax.random.PRNGKey(1)
    K = 8

    def run(paged, pages):
        return decode_loop(
            params, {k: v.copy() for k, v in pages.items()}, bt, tokens, pos,
            temps, eos, remaining, key, config=cfg, page_size=page,
            n_steps=K, paged=paged, live_pages=max_pages)

    toks_d, _, pages_d = run(False, pages0)
    toks_p, _, pages_p = run(True, pages0)
    assert np.array_equal(np.asarray(toks_d), np.asarray(toks_p))
    for name in ("k", "v"):
        np.testing.assert_allclose(np.asarray(pages_d[name]),
                                   np.asarray(pages_p[name]),
                                   atol=1e-5, rtol=1e-5)

    # dispatch 2 decodes FROM the committed pool — proves the commit is
    # what the next dispatch actually reads
    def run2(paged, pages, toks1):
        return decode_loop(
            params, pages, bt, toks1[-1], pos + K, temps, eos,
            remaining - K, jax.random.PRNGKey(2), config=cfg,
            page_size=page, n_steps=K, paged=paged, live_pages=max_pages)

    toks2_d, _, _ = run2(False, pages_d, toks_d)
    toks2_p, _, _ = run2(True, pages_p, toks_p)
    assert np.array_equal(np.asarray(toks2_d), np.asarray(toks2_p))


def test_decode_loop_eos_slots_commit_to_trash(small_model):
    """A slot finishing mid-dispatch must keep its pool pages frozen —
    its remaining staged rows commit to its private trash page."""
    from ray_tpu.llm.model import decode_loop, init_pages

    cfg, params = small_model
    page, slots, max_pages = 8, 2, 4
    pages0 = init_pages(cfg, slots + slots * max_pages, page)
    rng = np.random.default_rng(5)
    pages0 = {k: jnp.array(rng.standard_normal(v.shape), jnp.float32)
              for k, v in pages0.items()}
    bt = jnp.asarray(np.arange(slots, slots + slots * max_pages,
                               dtype=np.int32).reshape(slots, max_pages))
    pos = jnp.array([6, 6], jnp.int32)
    tokens = jnp.array([3, 7], jnp.int32)
    args = (jnp.zeros(slots, jnp.float32), jnp.full(slots, -1, jnp.int32))
    key = jax.random.PRNGKey(1)
    # slot 0 exhausts `remaining` after 2 steps; slot 1 keeps going
    remaining = jnp.array([2, 100], jnp.int32)
    toks_d, _, pages_d = decode_loop(
        params, {k: v.copy() for k, v in pages0.items()}, bt, tokens, pos,
        args[0], args[1], remaining, key, config=cfg, page_size=page,
        n_steps=6, paged=False, live_pages=max_pages)
    toks_p, _, pages_p = decode_loop(
        params, {k: v.copy() for k, v in pages0.items()}, bt, tokens, pos,
        args[0], args[1], remaining, key, config=cfg, page_size=page,
        n_steps=6, paged=True, live_pages=max_pages)
    # pre-EOS steps identical everywhere; the live slot identical to the
    # end (a done slot's surplus tokens are unspecified and discarded)
    assert np.array_equal(np.asarray(toks_d)[:2], np.asarray(toks_p)[:2])
    assert np.array_equal(np.asarray(toks_d)[:, 1], np.asarray(toks_p)[:, 1])
    for name in ("k", "v"):
        # real (non-trash) pages identical between the two paths
        np.testing.assert_allclose(np.asarray(pages_d[name])[:, slots:],
                                   np.asarray(pages_p[name])[:, slots:],
                                   atol=1e-5, rtol=1e-5)


# ------------------------------------------------- engine-level parity

def _run_engine(cfg, params, prompts, impl, *, K=8, page_size=8,
                max_new_tokens=6, max_len=64):
    eng = InferenceEngine(cfg, params, max_slots=max(4, len(prompts)),
                          max_len=max_len, page_size=page_size,
                          decode_steps_per_dispatch=K, attention_impl=impl)
    reqs = [Request(f"r{i}", list(p), max_new_tokens=max_new_tokens)
            for i, p in enumerate(prompts)]
    for r in reqs:
        eng.add_request(r)
    while any(not r.done for r in reqs):
        eng.step()
    return [r.generated for r in reqs]


def test_engine_greedy_parity_uniform(small_model):
    cfg, params = small_model
    prompts = [[1, 5, 9, 2], [2, 4, 6, 8], [3, 1, 4, 1], [9, 9, 9, 9]]
    assert (_run_engine(cfg, params, prompts, "paged")
            == _run_engine(cfg, params, prompts, "dense"))


def test_engine_greedy_parity_skewed(small_model):
    """The paged kernel's reason to exist: one long-context slot + many
    short ones in the same batch (the 1x8k + 7x256 shape, scaled to
    tier-1 sizes) must stay token-identical to dense."""
    cfg, params = small_model
    long = list(range(1, 49))             # 48 tokens: 6 pages at page 8
    shorts = [[7, 3], [2, 4, 6], [11, 13, 17, 19]]
    prompts = [long] + shorts
    assert (_run_engine(cfg, params, prompts, "paged", max_new_tokens=8)
            == _run_engine(cfg, params, prompts, "dense", max_new_tokens=8))


def test_engine_greedy_parity_stage_wraparound(small_model):
    """K=8 fused steps from a mid-page start: the staged rows cross the
    page boundary inside ONE dispatch and the commit lands them on two
    different pages; tokens must survive the K-step boundary into the
    next dispatch too (max_new_tokens > K)."""
    cfg, params = small_model
    prompts = [[1, 2, 3, 4, 5], [8, 6, 7]]   # decode starts at pos 5 / 3
    assert (_run_engine(cfg, params, prompts, "paged", K=8, max_new_tokens=12)
            == _run_engine(cfg, params, prompts, "dense", K=8, max_new_tokens=12))


# ------------------------------------------------- impl selection / tp

def test_resolve_attention_impl():
    """"auto" must pick the kernel exactly when a TPU backend is present —
    on EVERY mesh shape: round 8 lifted pure-pp, round 15 lifted the
    pp x tp composition (the decode loop flattens to one manual region
    over both axes), so no TPU mesh resolves dense anymore."""
    import types

    tp_mesh = types.SimpleNamespace(shape={"tp": 4, "dp": 1})
    pp_mesh = types.SimpleNamespace(shape={"pp": 2, "dp": 1})
    pp_tp_mesh = types.SimpleNamespace(shape={"pp": 2, "tp": 2})
    assert resolve_attention_impl("auto", backend="tpu") == "paged"
    assert resolve_attention_impl("auto", backend="cpu") == "dense"
    assert resolve_attention_impl("auto", backend="gpu") == "dense"
    assert resolve_attention_impl("auto", tp_mesh, backend="tpu") == "paged"
    # ROADMAP item 4 closed: pp meshes take the kernel too
    assert resolve_attention_impl("auto", pp_mesh, backend="tpu") == "paged"
    # ROADMAP item 6 closed: composed pp x tp takes the kernel too
    # (flattened {"pp","tp"} manual region — the round-8 residue)
    assert resolve_attention_impl("auto", pp_tp_mesh, backend="tpu") == "paged"
    # explicit choices pass through untouched
    assert resolve_attention_impl("dense", backend="tpu") == "dense"
    assert resolve_attention_impl("paged", backend="cpu") == "paged"
    with pytest.raises(ValueError, match="attention_impl"):
        resolve_attention_impl("fused")
    # this CPU test process must resolve to dense
    assert resolve_attention_impl() == "dense"


def test_tensor_parallel_paged_parity(small_model):
    """attention_impl='paged' over a tp mesh (kernel shard_mapped over
    the KV-head axis) decodes token-identically to the single-device
    dense engine — the lifted mesh refusal of ROADMAP item 4."""
    from ray_tpu.parallel import MeshConfig, create_mesh

    cfg, params = small_model
    prompt = list(range(1, 22))
    expected = _run_engine(cfg, params, [prompt], "dense")[0]

    n = len(jax.devices())
    mesh = create_mesh(MeshConfig(tp=2, dp=max(1, n // 2)))
    eng = InferenceEngine(cfg, params, max_slots=2, max_len=64, page_size=8,
                          mesh=mesh, attention_impl="paged")
    assert eng.generate(list(prompt), max_new_tokens=6) == expected


def test_pipeline_parallel_paged_parity(small_model):
    """attention_impl='paged' over a pp mesh: the v2 staging carry rides
    the pipeline tick loop (per-stage local-layer staging + one
    commit_staging per stage at the dispatch boundary) and must decode
    token-identically to the single-device dense engine — the second
    half of ROADMAP item 4's lifted mesh refusal. Covers multi-dispatch
    continuation (committed pool re-read by the next burst) and
    mid-flight EOS (trash-committed staging rows)."""
    from ray_tpu.parallel import MeshConfig, create_mesh

    cfg, params = small_model
    prompts = [[1, 5, 9], [2, 4, 6, 8, 10, 12, 14], list(range(1, 20)),
               [7, 3, 7]]
    expected = _run_engine(cfg, params, prompts, "dense", max_new_tokens=12)

    n = len(jax.devices())
    mesh = create_mesh(MeshConfig(pp=2, dp=max(1, n // 2)))
    eng = InferenceEngine(cfg, params, max_slots=4, max_len=64, page_size=8,
                          mesh=mesh, attention_impl="paged")
    reqs = [Request(f"r{i}", list(p), max_new_tokens=12)
            for i, p in enumerate(prompts)]
    for r in reqs:
        eng.add_request(r)
    while any(not r.done for r in reqs):
        eng.step()
    assert [r.generated for r in reqs] == expected


def test_decode_block_manual_tp_psum_parity(small_model):
    """The flattened pp×tp region's hand-written tp collectives
    (decode_block/_mlp ``tp_axis=``: psum after the row-parallel wo and
    w_down) must reproduce the unsharded block bit-for-bit in f32. Runs
    WITHOUT shard_map: ``jax.vmap(axis_name="tp")`` over hand-split
    KV-head/mlp shards gives the same manual-collective semantics, so
    the sandbox (jax 0.4.37) covers the math the composed-mesh parity
    test exercises end-to-end on the driver's jax."""
    from ray_tpu.llm.model import decode_block

    cfg, params = small_model
    tp = 2
    rng = np.random.default_rng(5)
    page, n, max_pages = 8, 3, 4
    pool = 32
    layer = {k: v[0] for k, v in params["layers"].items()}  # layer 0
    kf = jnp.array(rng.standard_normal(
        (1, pool, cfg.n_kv_heads, page, cfg.head_dim)), jnp.float32)
    vf = jnp.array(rng.standard_normal(kf.shape), jnp.float32)
    x = jnp.array(rng.standard_normal((n, 1, cfg.hidden)), jnp.float32)
    bt = jnp.array(rng.permutation(pool)[: n * max_pages].reshape(
        n, max_pages), jnp.int32)
    pos = jnp.array([5, 11, 17], jnp.int32)
    widx = jnp.take_along_axis(bt, (pos // page)[:, None], axis=1)[:, 0]
    l = jnp.int32(0)

    # Ground truth: the unsharded block.
    full_x2, full_kf, full_vf, _ = decode_block(
        x, layer, kf, vf, l, bt, pos, widx, cfg, page)

    # Hand-shard heads/mlp the way the manual region receives them.
    def split(a, axis):
        return jnp.stack(jnp.split(a, tp, axis=axis))

    layer_sh = {
        "attn_norm": layer["attn_norm"], "mlp_norm": layer["mlp_norm"],
        "wq": split(layer["wq"], 1), "wk": split(layer["wk"], 1),
        "wv": split(layer["wv"], 1), "wo": split(layer["wo"], 0),
        "w_gate": split(layer["w_gate"], 1),
        "w_up": split(layer["w_up"], 1),
        "w_down": split(layer["w_down"], 0),
    }
    kf_sh, vf_sh = split(kf, 2), split(vf, 2)

    def shard_block(layer_local, kf_l, vf_l):
        return decode_block(x, layer_local, kf_l, vf_l, l, bt, pos, widx,
                            cfg, page, tp_axis="tp")

    x2_sh, kf2_sh, vf2_sh, _ = jax.vmap(
        shard_block, axis_name="tp",
        in_axes=({"attn_norm": None, "mlp_norm": None, "wq": 0, "wk": 0,
                  "wv": 0, "wo": 0, "w_gate": 0, "w_up": 0, "w_down": 0},
                 0, 0))(layer_sh, kf_sh, vf_sh)

    # psum'd activations are replicated across shards and exact in f32
    np.testing.assert_allclose(np.asarray(x2_sh[0]), np.asarray(full_x2),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_array_equal(np.asarray(x2_sh[0]),
                                  np.asarray(x2_sh[1]))
    # each shard wrote its local KV heads: concat == the unsharded pool
    np.testing.assert_array_equal(
        np.concatenate(list(np.asarray(kf2_sh)), axis=2),
        np.asarray(full_kf))
    np.testing.assert_array_equal(
        np.concatenate(list(np.asarray(vf2_sh)), axis=2),
        np.asarray(full_vf))


def test_paged_composed_pp_tp_parity(small_model):
    """Round 15: the composed pp x tp mesh takes the kernel. The decode
    loop runs as ONE flattened manual region over {"pp","tp"} — pp
    manual on layers, tp manual on KV heads, Megatron psums after
    wo/w_down, tiled logits all_gather before sampling — and must stay
    greedy byte-identical to the single-device dense engine (the lifted
    round-8 residue: `resolve_attention_impl` no longer falls back dense
    on exactly the mesh shape a real v5p slice uses)."""
    from ray_tpu.parallel import MeshConfig, create_mesh

    cfg, params = small_model
    n = len(jax.devices())
    if n < 4:
        pytest.skip("needs 4 devices for a pp=2 x tp=2 mesh")
    prompts = [[1, 5, 9], [2, 4, 6, 8, 10, 12, 14], list(range(1, 20)),
               [7, 3, 7]]
    expected = _run_engine(cfg, params, prompts, "dense", max_new_tokens=12)

    mesh = create_mesh(MeshConfig(pp=2, tp=2, dp=max(1, n // 4)))
    eng = InferenceEngine(cfg, params, max_slots=4, max_len=64, page_size=8,
                          mesh=mesh, attention_impl="paged")
    assert eng.attention_impl == "paged"
    reqs = [Request(f"r{i}", list(p), max_new_tokens=12)
            for i, p in enumerate(prompts)]
    for r in reqs:
        eng.add_request(r)
    while any(not r.done for r in reqs):
        eng.step()
    assert [r.generated for r in reqs] == expected

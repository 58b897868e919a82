"""``ops/conv_tiles.py``, what the three conv kernel pairs share: the shifted
views against plain slices of the stacked rows (through a one-step
interpreted ``pallas_call``, since they roll with ``pltpu.roll``), the chunk
walk and the tile arithmetic at the shapes the kernels' own tests state, the
vreg sum against a plain sum."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental import pallas as pl

from ray_tpu.ops import conv_tiles as ct

ROWS, D = 64, 128


def _views(x, halo, taps, side):
    """``windows`` of x [ROWS, D] and its 8 halo rows, stacked [taps, ROWS, D]."""
    def kernel(x_ref, halo_ref, out_ref):
        views = ct.windows(x_ref[...], taps, **{side: halo_ref[...]})
        assert len(views) == taps
        for j, view in enumerate(views):
            out_ref[j] = view

    return pl.pallas_call(kernel, out_shape=jax.ShapeDtypeStruct((taps, ROWS, D), x.dtype),
                          interpret=True)(x, halo)


@pytest.mark.parametrize("taps", [3, 4])
@pytest.mark.parametrize("side", ["before", "after"])
def test_windows_are_slices_of_the_stacked_rows(taps, side):
    """View j is the stack (the halo's 8 rows, then x; or x, then the halo's)
    from ``taps - 1 - j`` rows before position 0 (after it: that many rows
    further on), the last view x itself: a halo's rows are read, never wrapped."""
    rng = np.random.default_rng(taps)
    x = rng.standard_normal((ROWS, D), np.float32)
    halo = rng.standard_normal((ct.SUBLANES, D), np.float32)
    got = np.asarray(_views(jnp.asarray(x), jnp.asarray(halo), taps, side))
    reach = taps - 1
    if side == "before":
        stack, first = np.concatenate([halo, x]), ct.SUBLANES
        want = [stack[first - (reach - j):][:ROWS] for j in range(taps)]
    else:
        stack = np.concatenate([x, halo])
        want = [stack[reach - j:][:ROWS] for j in range(taps)]
    np.testing.assert_array_equal(got, np.stack(want))
    np.testing.assert_array_equal(got[-1], x)


def test_the_chunk_walk_and_the_tile_arithmetic():
    """The three shapes ``tests/test_gdn_elementwise.py`` and
    ``tests/test_mamba_elementwise.py`` state of their tiles: a whole tile
    walked in chunks of ``CHUNK_ROWS``, a sequence off the chunk as one chunk,
    the largest whole unit under the module's size."""
    assert ct.chunks(1024) == (ct.CHUNK_ROWS, 4) and ct.chunks(192) == (192, 1)
    assert ct.chunks(640) == (128, 5)
    assert ct.largest(8192, ct.ROW_UNIT, 1024) == 1024
    assert ct.largest(192, ct.ROW_UNIT, 1024) == 192 and ct.largest(2048, ct.ROW_UNIT, 1024) == 1024
    assert ct.largest(6, 1, 8) == 6 and ct.largest(32, 1, 8) == 8
    assert ct.largest(384, ct.LANE, 1024) == 384 and ct.largest(1280, ct.ROW_UNIT, 1024) == 640
    assert ct.largest(64, ct.ROW_UNIT, 16) == 64          # the unit itself, under ``most`` or not
    assert ct.HALO % ct.SUBLANES == 0 and ct.ROW_UNIT % ct.HALO == 0


def test_fold_and_tapped_against_plain_sums():
    rng = np.random.default_rng(0)
    t = rng.standard_normal((ct.CHUNK_ROWS, 2 * ct.LANE), np.float32)
    folded = np.asarray(ct.fold(jnp.asarray(t)))
    assert folded.shape == (ct.SUBLANES, 2 * ct.LANE)
    np.testing.assert_allclose(folded.sum(axis=0), t.sum(axis=0), rtol=1e-5, atol=1e-4)
    np.testing.assert_allclose(folded[3], t[3::ct.SUBLANES].sum(axis=0), rtol=1e-5, atol=1e-5)
    for taps in (3, 4):
        views = [rng.standard_normal((ROWS, D), np.float32) for _ in range(taps)]
        w = rng.standard_normal((taps, D), np.float32)
        want = sum(v * w[j] for j, v in enumerate(views))
        got = ct.tapped([jnp.asarray(v) for v in views], jnp.asarray(w))
        np.testing.assert_allclose(np.asarray(got), want, rtol=1e-6, atol=1e-6)


def test_the_mosaic_parameters_order_every_axis():
    params = ct.mosaic_params()
    assert tuple(params.dimension_semantics) == ("arbitrary",) * 3
    assert params.vmem_limit_bytes == ct.VMEM_LIMIT < 128 * 1024 * 1024

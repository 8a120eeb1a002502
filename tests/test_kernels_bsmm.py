"""Pallas block-sparse matmul vs the pure-jnp oracle: shape/dtype sweeps."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.bsmm import (bsmm_pallas, compact_tile_indices,
                                make_tile_plan, masked_matmul_pallas,
                                plan_matmul, row_block, row_block_fits)
from repro.kernels.ops import sparse_dense, tile_bitmap, tile_density
from repro.kernels.ref import bsmm_ref, expand_tile_mask, masked_matmul_ref

SHAPES = [
    (128, 128, 128, 128),
    (256, 384, 256, 128),
    (128, 256, 512, 128),
    (256, 256, 256, 64),        # smaller tiles
    (512, 128, 128, 128),
]
DTYPES = [jnp.float32, jnp.bfloat16]


def _tol(dtype):
    return dict(rtol=2e-2, atol=2e-1) if dtype == jnp.bfloat16 else \
        dict(rtol=1e-5, atol=1e-4)


@pytest.mark.parametrize("M,K,N,b", SHAPES)
@pytest.mark.parametrize("dtype", DTYPES)
@pytest.mark.parametrize("density", [0.0, 0.3, 1.0])
def test_bsmm_matches_oracle(M, K, N, b, dtype, density):
    rng = np.random.RandomState(hash((M, K, N, b)) % 2**31)
    x = jnp.asarray(rng.randn(M, K), dtype)
    w = jnp.asarray(rng.randn(K, N), dtype)
    tm = (rng.rand(K // b, N // b) < density).astype(np.int32)
    out = bsmm_pallas(x, w, tm, bm=b, bk=b, bn=b, interpret=True)
    ref = bsmm_ref(x, w, tm, b, b)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), **_tol(dtype))


@pytest.mark.parametrize("dtype", DTYPES)
def test_masked_matmul_matches_oracle(dtype):
    rng = np.random.RandomState(7)
    M = K = N = 256
    x = jnp.asarray(rng.randn(M, K), dtype)
    w = jnp.asarray(rng.randn(K, N), dtype)
    mask = (rng.rand(K, N) > 0.5).astype(np.float32)
    out = masked_matmul_pallas(x, w, jnp.asarray(mask), interpret=True)
    ref = masked_matmul_ref(x, w, jnp.asarray(mask, dtype))
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), **_tol(dtype))


def test_compact_indices_roundtrip():
    rng = np.random.RandomState(3)
    tm = (rng.rand(7, 5) > 0.6).astype(np.int32)
    idx, counts, kmax = compact_tile_indices(tm)
    assert kmax == max(1, counts.max())
    for j in range(5):
        live = set(np.nonzero(tm[:, j])[0].tolist())
        assert set(idx[j, :counts[j]].tolist()) == live


def test_sparse_dense_wrapper_fallback_and_tiled():
    rng = np.random.RandomState(5)
    w = rng.randn(384, 256).astype(np.float32)
    mask = np.ones_like(w)
    mask[:128] = 0
    # tiled path (leading dims folded)
    x = jnp.asarray(rng.randn(2, 64, 384), jnp.float32)
    out = sparse_dense(x, jnp.asarray(w), mask)
    ref = masked_matmul_ref(x.reshape(-1, 384), jnp.asarray(w),
                            jnp.asarray(mask)).reshape(2, 64, 256)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-4)
    # ragged fallback (non-tiling K)
    x2 = jnp.asarray(rng.randn(3, 100), jnp.float32)
    w2 = jnp.asarray(rng.randn(100, 60), jnp.float32)
    m2 = (rng.rand(100, 60) > 0.3).astype(np.float32)
    out2 = sparse_dense(x2, w2, m2)
    np.testing.assert_allclose(np.asarray(out2),
                               np.asarray(masked_matmul_ref(x2, w2,
                                                            jnp.asarray(m2))),
                               rtol=1e-5, atol=1e-4)


def test_tile_density_accounting():
    mask = np.ones((256, 256), np.float32)
    mask[:128, :128] = 0
    assert tile_density(mask) == 0.75
    bm = tile_bitmap(mask)
    assert bm.shape == (2, 2) and bm[0, 0] == 0 and bm.sum() == 3


def test_compact_indices_all_dead_column():
    """A fully-dead output column gets count 0 and placeholder indices
    that still point at a valid DMA target (tile 0)."""
    tm = np.ones((4, 3), np.int32)
    tm[:, 1] = 0
    idx, counts, kmax = compact_tile_indices(tm)
    assert counts.tolist() == [4, 0, 4]
    assert kmax == 4
    assert idx[1].tolist() == [0, 0, 0, 0]      # masked in-kernel


def test_compact_indices_dead_slots_repeat_last_live():
    """Slots past a column's live count repeat its last live index, so
    the pipeline sees an unchanged block and fetches nothing; an empty
    column points at tile 0."""
    tm = np.array([[1, 0, 0],
                   [0, 0, 1],
                   [1, 0, 1],
                   [0, 0, 1]], np.int32)
    idx, counts, kmax = compact_tile_indices(tm)
    assert counts.tolist() == [2, 0, 3] and kmax == 3
    assert idx.tolist() == [[0, 2, 2], [0, 0, 0], [1, 2, 3]]


def test_compact_indices_all_dead_mask_still_one_pass():
    idx, counts, kmax = compact_tile_indices(np.zeros((5, 4), np.int32))
    assert kmax == 1                    # grid dim must stay >= 1
    assert counts.tolist() == [0, 0, 0, 0]


def test_compact_indices_empty_mask():
    idx, counts, kmax = compact_tile_indices(np.zeros((0, 0), np.int32))
    assert counts.shape == (0,) and kmax == 1 and idx.shape == (0, 1)
    idx, counts, kmax = compact_tile_indices(np.zeros((3, 0), np.int32))
    assert counts.shape == (0,) and idx.shape == (0, 1)


def test_bsmm_rejects_non_tiling_last_tile():
    """K/N that leave a ragged (non-128-multiple) last tile must be
    rejected, not silently mis-indexed."""
    from repro.kernels.bsmm import GeometryError
    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.randn(128, 200), jnp.float32)     # K = 200
    w = jnp.asarray(rng.randn(200, 128), jnp.float32)
    with pytest.raises(GeometryError, match="tile") as ei:
        bsmm_pallas(x, w, np.ones((2, 1), np.int32), interpret=True)
    assert ei.value.shape == (128, 200, 128)      # structured context
    with pytest.raises(GeometryError):
        bsmm_pallas(jnp.asarray(rng.randn(100, 128), jnp.float32),
                    jnp.asarray(rng.randn(128, 128), jnp.float32),
                    np.ones((1, 1), np.int32), interpret=True)


def test_make_tile_plan_eligibility():
    assert make_tile_plan(np.ones((128, 200))) is None    # ragged N
    assert make_tile_plan(np.ones((100, 128))) is None    # ragged K
    assert make_tile_plan(np.ones((2, 128, 128))) is None  # not 2-D
    plan = make_tile_plan(np.ones((256, 128)))
    assert plan is not None
    assert (plan.live_tiles, plan.total_tiles) == (2, 2)


def test_plan_matmul_matches_dense_with_row_padding():
    """Tiny-M decode batches (padded to a sublane multiple) and dead
    tiles: plan_matmul == dense on pre-masked weights."""
    rng = np.random.RandomState(1)
    mask = np.ones((256, 128), np.float32)
    mask[:128] = 0.0                    # kill the first K tile
    w = jnp.asarray(rng.randn(256, 128) * mask, jnp.float32)
    plan = make_tile_plan(mask)
    assert plan.live_tiles == 1
    for lead in [(4,), (3, 1), (2, 64)]:
        x = jnp.asarray(rng.randn(*lead, 256), jnp.float32)
        np.testing.assert_allclose(np.asarray(plan_matmul(x, w, plan)),
                                   np.asarray(x @ w),
                                   rtol=1e-5, atol=1e-4)
    # plan=None is the dense path
    x = jnp.asarray(rng.randn(4, 256), jnp.float32)
    np.testing.assert_allclose(np.asarray(plan_matmul(x, w, None)),
                               np.asarray(x @ w), rtol=1e-6, atol=1e-5)


def test_grid_skips_match_savings():
    """The kernel's K-grid length equals the max live tiles per column —
    the compute saving the paper's crossbar savings maps to."""
    tm = np.zeros((8, 4), np.int32)
    tm[:2, 0] = 1
    tm[:5, 1] = 1
    idx, counts, kmax = compact_tile_indices(tm)
    assert kmax == 5                      # not 8: 3/8 of passes skipped
    assert counts.tolist() == [2, 5, 0, 0]


# -- the row block: as tall as VMEM allows ----------------------------------
@pytest.mark.parametrize("M,dtype,want", [
    (3, jnp.float32, (8, 8)),           # decode: one sublane-padded block
    (3, jnp.bfloat16, (8, 8)),
    (384, jnp.float32, (384, 384)),
    (384, jnp.bfloat16, (384, 384)),
    (2048, jnp.float32, (2048, 2048)),  # a 4 x 512 retrain batch: one block
    (2048, jnp.bfloat16, (2048, 2048)),
    (12800, jnp.bfloat16, (12800, 6400)),   # VMEM caps the block below Mp
    (12800, jnp.float32, (12800, 3200)),
])
def test_row_block(M, dtype, want):
    assert row_block(M, jnp.dtype(dtype)) == want


@pytest.mark.parametrize("dtype", DTYPES)
def test_row_block_is_the_largest_that_fits(dtype):
    Mp, bm = row_block(12800, jnp.dtype(dtype))
    assert row_block_fits(bm, dtype)
    taller = [b for b in range(bm + 128, Mp + 1, 128) if Mp % b == 0]
    assert taller and not any(row_block_fits(b, dtype) for b in taller)

"""The on-chip benchmark's ticket and weights
(benchmarks/chip/chipbench/ticket.py, weights.py): per-projection
density, at least one live tile, exact zeros, the same ticket from the
same seed, and the structure of ``core.masks.make_masks``."""
import os
import sys

import numpy as np
import pytest

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "..",
                                "benchmarks", "chip"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from chipbench import compare, ticket, weights  # noqa: E402

T = ticket.TILE


def tiles(mask2d):
    K, N = mask2d.shape
    rt, ct = -(-K // T), -(-N // T)
    m = np.pad(np.asarray(mask2d), ((0, rt * T - K), (0, ct * T - N)))
    return m.reshape(rt, T, ct, T).max(axis=(1, 3))


@pytest.mark.parametrize("n_tiles,density,keep", [
    (1024, 0.1, 102), (128, 0.1, 13), (2752, 0.1, 275), (4, 0.1, 1),
    (1, 0.1, 1), (10, 0.5, 5)])
def test_keep_count(n_tiles, density, keep):
    assert ticket.keep_count(n_tiles, density) == keep


def test_each_layer_of_a_stacked_leaf_keeps_its_own_top_tiles():
    w = jax.random.normal(jax.random.PRNGKey(0), (3, 4 * T, 2 * T))
    m = np.asarray(ticket.leaf_mask(w, 0.25))
    assert m.shape == w.shape and m.dtype == np.float32
    means = np.abs(np.asarray(w)).reshape(3, 4, T, 2, T).mean(axis=(2, 4))
    for layer in range(3):
        t = tiles(m[layer])
        assert t.sum() == 2                          # 25% of 8 tiles
        kept = means[layer][t == 1]
        assert kept.min() >= means[layer][t == 0].max()
        # whole tiles: every weight of a kept tile is kept
        assert m[layer].sum() == 2 * T * T


def test_a_ragged_conv_unroll_keeps_at_least_one_tile():
    w = jax.random.normal(jax.random.PRNGKey(1), (3, 3, 3, 64))
    m = np.asarray(ticket.leaf_mask(w, 0.1, conv=True))
    assert m.shape == w.shape
    assert np.array_equal(m, np.ones_like(m))        # one 27 x 64 tile


def test_conv_tiles_live_in_the_crossbar_unroll():
    w = jax.random.normal(jax.random.PRNGKey(2), (3, 3, 64, 256))
    m = np.asarray(ticket.leaf_mask(w, 0.2, conv=True))
    unrolled = np.transpose(m, (2, 0, 1, 3)).reshape(576, 256)
    t = tiles(unrolled)
    assert t.shape == (5, 2) and t.sum() == 2
    # inside a kept tile every weight is kept, outside none is
    full = np.kron(t, np.ones((T, T)))[:576]
    assert np.array_equal(unrolled, full)


SHAPES = {"embed": {"table": jax.ShapeDtypeStruct((256, 128), jnp.bfloat16)},
          "segments": [[{"attn": {
              "wq": jax.ShapeDtypeStruct((2, 256, 384), jnp.bfloat16)},
              "norm1": {"scale": jax.ShapeDtypeStruct((2, 256),
                                                      jnp.bfloat16)}}]]}


def prunable(path, leaf):
    return path.endswith("wq")


def draw(seed, ticket_seed=7, density=0.2):
    return weights.draw(SHAPES, seed, prunable=prunable,
                        conv=lambda p: False, density=density,
                        ticket_seed=ticket_seed)


def test_masks_have_the_structure_and_dtype_of_make_masks():
    from repro.core.masks import make_masks
    params, masks = draw(3)
    want = make_masks(params, prunable)
    assert jax.tree.structure(masks, is_leaf=lambda x: x is None) == \
        jax.tree.structure(want, is_leaf=lambda x: x is None)
    assert masks["segments"][0][0]["attn"]["wq"].dtype == jnp.float32
    assert masks["embed"]["table"] is None


def test_pruned_weights_are_exact_zeros_and_live_ones_are_not():
    params, masks = draw(2**40 + 3)
    w = np.asarray(params["segments"][0][0]["attn"]["wq"], np.float32)
    m = np.asarray(masks["segments"][0][0]["attn"]["wq"])
    assert np.all(w[m == 0] == 0)
    assert np.all(w[m == 1] != 0)
    for layer in range(2):
        assert tiles(m[layer]).sum() == 1            # 20% of 6 tiles
    assert compare.pruned_nonzero(params, masks) == 0


def test_same_seed_same_ticket_and_weights_other_seed_other_weights():
    p1, m1 = draw(11)
    p2, m2 = draw(11)
    p3, m3 = draw(12)
    for a, b in zip(jax.tree.leaves(p1), jax.tree.leaves(p2)):
        assert np.array_equal(np.asarray(a), np.asarray(b))
    wq = lambda p: np.asarray(p["segments"][0][0]["attn"]["wq"])  # noqa
    assert not np.array_equal(wq(p1), wq(p3))
    # the tile pattern comes from the ticket seed, so the programs the
    # ticket compiles into are the same for every --seed
    for a, b in zip(jax.tree.leaves(m1), jax.tree.leaves(m3)):
        assert np.array_equal(np.asarray(a), np.asarray(b))
    _, m4 = draw(11, ticket_seed=8)
    assert not np.array_equal(np.asarray(jax.tree.leaves(m1)[0]),
                              np.asarray(jax.tree.leaves(m4)[0]))


def test_initial_and_change_norms_redraw_the_same_values():
    params, masks = draw(5)
    again = weights.initial(SHAPES, 5, masks)
    for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(again)):
        assert np.array_equal(np.asarray(a), np.asarray(b))
    flat = compare.flat(params)
    flat["embed/table"] = flat["embed/table"] + 1
    got = weights.change_norms(SHAPES, 5, masks, flat,
                               lambda p: p.startswith("segments/"))
    assert got["embed/table"] == pytest.approx(np.sqrt(256 * 128), rel=1e-3)
    assert got["segments/0/0/attn/wq[1]"] == 0.0
    assert set(got) == {"embed/table", "segments/0/0/attn/wq[0]",
                        "segments/0/0/attn/wq[1]",
                        "segments/0/0/norm1/scale[0]",
                        "segments/0/0/norm1/scale[1]"}

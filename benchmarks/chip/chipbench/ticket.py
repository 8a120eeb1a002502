"""The benchmark's ticket: which 128x128 tiles of each weight stay live.

A ticket is an input of the benchmark, like its data.  For every
prunable leaf, and separately for each layer of a stacked leaf, it
keeps the top ``density`` fraction of 128x128 tiles ranked by mean |w|,
and at least one tile.  A convolution kernel (k, k, IC, OC) is ranked
in its crossbar unroll, the (IC*k*k, OC) matrix whose rows run over
(IC, k, k).  Tiles at a ragged edge are ranked by the mean over the
weights they hold.

Everything here is jax.numpy, so the mask is built on the device in
the same jitted call that draws the weights.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

TILE = 128


def keep_count(n_tiles: int, density: float) -> int:
    """Tiles kept out of ``n_tiles``: the nearest whole number to
    ``density * n_tiles``, and at least one."""
    return max(1, int(math.floor(density * n_tiles + 0.5)))


def as_matrices(w, conv: bool):
    """Leaf -> (L, R, C) stack of the matrices its tiles live in."""
    if conv:                              # (k, k, IC, OC) -> (IC*k*k, OC)
        k1, k2, ic, oc = w.shape
        return jnp.transpose(w, (2, 0, 1, 3)).reshape(1, ic * k1 * k2, oc)
    return w.reshape(-1, w.shape[-2], w.shape[-1])


def from_matrices(m, shape, conv: bool):
    """Inverse of ``as_matrices``."""
    if conv:
        k1, k2, ic, oc = shape
        return jnp.transpose(m.reshape(ic, k1, k2, oc), (1, 2, 0, 3))
    return m.reshape(shape)


def tile_grid(rows: int, cols: int, tile: int = TILE):
    return -(-rows // tile), -(-cols // tile)


def tile_means(m, tile: int = TILE):
    """(L, R, C) -> (L, Rt, Ct) mean |w| over the weights in each tile."""
    L, R, C = m.shape
    rt, ct = tile_grid(R, C, tile)
    pad = ((0, 0), (0, rt * tile - R), (0, ct * tile - C))
    a = jnp.pad(jnp.abs(m.astype(jnp.float32)), pad)
    n = jnp.pad(jnp.ones((R, C), jnp.float32), pad[1:])
    s = a.reshape(L, rt, tile, ct, tile).sum(axis=(2, 4))
    cnt = n.reshape(rt, tile, ct, tile).sum(axis=(1, 3))
    return s / cnt


def tile_bitmap(w, density: float, conv: bool = False, tile: int = TILE):
    """Leaf -> (L, Rt, Ct) {0,1} bitmap of the tiles the ticket keeps."""
    means = tile_means(as_matrices(w, conv), tile)
    L, rt, ct = means.shape
    k = keep_count(rt * ct, density)
    _, top = jax.lax.top_k(means.reshape(L, rt * ct), k)
    bits = jnp.zeros((L, rt * ct), jnp.float32)
    bits = bits.at[jnp.arange(L)[:, None], top].set(1.0)
    return bits.reshape(L, rt, ct)


def expand(bits, shape, conv: bool = False, tile: int = TILE):
    """(L, Rt, Ct) tile bitmap -> f32 {0,1} mask of the leaf's shape."""
    R, C = ((shape[2] * shape[0] * shape[1], shape[3]) if conv
            else shape[-2:])
    full = jnp.repeat(jnp.repeat(bits, tile, axis=1), tile, axis=2)
    return from_matrices(full[:, :R, :C], shape, conv)


def leaf_mask(w, density: float, conv: bool = False, tile: int = TILE):
    """The ticket's f32 {0,1} mask for one leaf, shaped like it."""
    return expand(tile_bitmap(w, density, conv, tile), w.shape, conv, tile)

"""Jitted public wrappers around the Pallas kernels.

``sparse_dense`` is the drop-in replacement for ``x @ w`` once a weight
has been ReaLPruned: it derives the static tile bitmap from the mask
(host-side, one-time) and dispatches the compacted block-sparse kernel.
Falls back to the jnp oracle for shapes that do not tile (tiny smoke
configs) and on platforms without Pallas TPU support.
"""
from __future__ import annotations

from typing import Optional

import jax.numpy as jnp
import numpy as np

from repro.configs.base import MXU_TILE
from repro.kernels import ref
from repro.kernels.bsmm import (make_tile_plan, plan_matmul,
                                tile_bitmap)  # noqa: F401  (re-export)
from repro.kernels.tile_stats import tile_stats_pallas


def tile_density(mask: np.ndarray, bk: int = MXU_TILE,
                 bn: int = MXU_TILE) -> float:
    """Fraction of live tiles — the kernel's compute/bandwidth cost."""
    bm = tile_bitmap(mask, bk, bn)
    return float(bm.mean())


def sparse_dense(x, w, mask: np.ndarray, *, bk: int = MXU_TILE,
                 bn: int = MXU_TILE, interpret: Optional[bool] = None):
    """x (..., K) @ pruned w (K, N) skipping dead 128×128 tiles.

    mask: host numpy elementwise {0,1} (static — pruning is offline).
    Differentiable: forward and both backward matmuls run block-sparse
    (``bsmm.bsmm_apply``); the explicit ``w * mask`` keeps the weight
    gradient elementwise-exact vs the dense masked oracle.  Ragged M
    (small retrain batches) is zero-padded inside ``plan_matmul``, whose
    ``bsmm.row_block`` picks the row blocking from M and the dtype: one
    sublane-padded block below a tile, else as few tile-multiple blocks
    as VMEM allows — only ragged K/N (or rectangular bk≠bn tiles) fall
    back to the dense oracle.
    """
    K, N = w.shape
    lead = x.shape[:-1]
    plan = (make_tile_plan(mask, tile=bk, interpret=interpret)
            if bk == bn else None)
    if plan is None:
        M = int(np.prod(lead)) if lead else 1
        out = ref.masked_matmul_ref(x.reshape(M, K), w,
                                    jnp.asarray(mask, w.dtype))
        return out.reshape(*lead, N)
    return plan_matmul(x, w * jnp.asarray(mask, w.dtype), plan)


def tile_stats(w, *, bk: int = MXU_TILE, bn: int = MXU_TILE,
               interpret: Optional[bool] = None):
    """Device-side per-tile (liveness, Σ|w|); pads ragged edges."""
    K, N = w.shape
    pk, pn = (-K) % bk, (-N) % bn
    if pk or pn:
        w = jnp.pad(w, ((0, pk), (0, pn)))
    return tile_stats_pallas(w, bk=bk, bn=bn, interpret=interpret)

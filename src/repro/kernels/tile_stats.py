"""Per-tile statistics Pallas kernel.

Computes, for every 128×128 weight tile, (liveness, Σ|w|) in one pass —
the device-side version of ``core.crossbar.xbar_stats`` used when masks
must be derived on-accelerator (e.g. re-deriving the bsmm tile bitmap
after a checkpoint restore without a host round-trip).
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.configs.base import MXU_TILE
from repro.kernels.bsmm import default_interpret, launch
from repro.kernels.spec import BlockMap, KernelSpec


def tile_stats_spec(*, K: int, N: int, bk: int = MXU_TILE,
                    bn: int = MXU_TILE,
                    dtype=jnp.float32) -> KernelSpec:
    """Launch geometry of the per-tile stats kernel: one grid cell per
    (bk, bn) weight tile, two (1, 1) outputs per cell.  VPU-only (no
    MXU), so the spec carries no flop model."""
    return KernelSpec(
        name="tile_stats",
        grid=(K // bk, N // bn),
        dims=("parallel", "parallel"),
        inputs=(BlockMap("w", (bk, bn), lambda i, j: (i, j),
                         (K, N), dtype),),
        outputs=(BlockMap("live", (1, 1), lambda i, j: (i, j),
                          (K // bk, N // bn), jnp.int32),
                 BlockMap("sums", (1, 1), lambda i, j: (i, j),
                          (K // bk, N // bn), jnp.float32)),
        guard=None,
        notes="reduction outputs, no scratch",
    )


def _tile_stats_kernel(w_ref, live_ref, sum_ref):
    blk = w_ref[...].astype(jnp.float32)
    s = jnp.sum(jnp.abs(blk))
    sum_ref[0, 0] = s
    live_ref[0, 0] = (jnp.any(blk != 0)).astype(jnp.int32)


def tile_stats_for_config(w, prune_cfg, *,
                          interpret: Optional[bool] = None):
    """Tile stats at a ``PruneConfig``'s crossbar geometry.

    The tile extents come from ``prune_cfg.xbar_rows/xbar_cols`` so the
    device-side bitmap agrees with the host-side ``xbar_stats``
    accounting for the same config; ragged edges are zero-padded.
    """
    bk, bn = int(prune_cfg.xbar_rows), int(prune_cfg.xbar_cols)
    K, N = w.shape
    pk, pn = (-K) % bk, (-N) % bn
    if pk or pn:
        w = jnp.pad(w, ((0, pk), (0, pn)))
    return tile_stats_pallas(w, bk=bk, bn=bn, interpret=interpret)


def tile_stats_pallas(w, *, bk: int = MXU_TILE, bn: int = MXU_TILE,
                      interpret: Optional[bool] = None):
    """w: (K, N) → (live (Kt, Nt) int32, sums (Kt, Nt) f32)."""
    K, N = w.shape
    assert K % bk == 0 and N % bn == 0, (w.shape, bk, bn)
    spec = tile_stats_spec(K=K, N=N, bk=bk, bn=bn, dtype=w.dtype)
    kernel = pl.pallas_call(
        _tile_stats_kernel,
        grid=spec.grid,
        in_specs=spec.pallas_in_specs(),
        out_specs=spec.pallas_out_specs(),
        out_shape=[jax.ShapeDtypeStruct((K // bk, N // bn), jnp.int32),
                   jax.ShapeDtypeStruct((K // bk, N // bn), jnp.float32)],
        interpret=default_interpret(interpret),
        name=spec.name,
    )
    return launch(kernel, w)

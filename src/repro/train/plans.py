"""Mask pytree → training-time ``TilePlan`` pytrees.

The paper's headline claim is that crossbar-aware pruning makes CNN
*training* ~20× faster, not just the deployed hardware smaller.  The
TPU analogue: once a ticket's masks are known, every retrain step's
matmuls (forward, dx, dw) can run through the block-sparse Pallas
kernels (``kernels.bsmm``) and scale with the live-tile count.  These
builders derive the per-weight plans from a session's mask pytree; the
adapters rebuild them after every prune round and close them into the
re-jitted train step, so later (sparser) retrain rounds are
proportionally cheaper.

The LM plan reuses the decode-plan walker (``models.plans``): the
training forward consumes the exact same structure — segments →
positions → {"attn": {...}, "mlp": {...}} — that the decode step does.
"""
from __future__ import annotations

from typing import Any, Optional, Tuple

import numpy as np

from repro.configs.base import MXU_TILE
from repro.kernels.bsmm import make_tile_plan
from repro.models.plans import PlanStats, build_decode_plan


def lm_train_plan(masks, *, tile: int = MXU_TILE,
                  interpret: Optional[bool] = None
                  ) -> Tuple[Optional[list], PlanStats]:
    """Transformer mask pytree → (train plan, PlanStats).

    Scanned segments union their bitmaps over the repeat axis (see
    ``models.plans.build_decode_plan``) — conservative but exact, since
    pruned weights are exact zeros.
    """
    return build_decode_plan(masks, tile=tile, interpret=interpret)


def cnn_train_plan(masks, *, tile: int = MXU_TILE,
                   interpret: Optional[bool] = None
                   ) -> Tuple[Optional[dict], PlanStats]:
    """CNN mask pytree → ({"fc": [plan|None, ...], "head": plan|None},
    PlanStats) for ``models.cnn.forward`` — or (None, stats) when no FC
    or head weight is routable (shapes that don't tile stay dense)."""
    stats = PlanStats()
    if not isinstance(masks, dict):
        return None, stats

    def leaf_plan(entry: Any, label: str):
        m = entry.get("w") if isinstance(entry, dict) else None
        if m is None:
            return None
        m = np.asarray(m)
        if m.ndim != 2:
            return None
        plan = make_tile_plan(m, tile=tile, interpret=interpret)
        if plan is None:
            stats.dense_fallback += 1
            return None
        stats.routed += 1
        stats.live_tiles += plan.live_tiles
        stats.total_tiles += plan.total_tiles
        stats.by_layer.append((label, plan.live_tiles, plan.total_tiles))
        return plan

    fc = [leaf_plan(e, f"fc.{j}") for j, e in enumerate(masks.get("fc", []))]
    head = leaf_plan(masks.get("head"), "head")
    if head is None and not any(p is not None for p in fc):
        return None, stats
    return {"fc": fc, "head": head}, stats

"""Mask pytree → per-projection ``TilePlan`` walker.

``build_decode_plan`` walks a mask pytree (same structure as the
parameter pytree, ``None`` on non-prunable leaves) and derives, for
every dense projection a transformer step executes, the static 128×128
tile bitmap — the TPU analogue of the paper's power-gated crossbar map
(Fig. 2).  The resulting plan mirrors ``params["segments"]`` so
``models.transformer`` can thread it layer-by-layer; the SAME structure
drives the serving decode step, the serving prefill, and the training
forward (the retrain loop), which is why this lives next to the models
rather than in ``serve`` or ``train``.

Scanned segments share one traced block body, so per-repeat bitmaps are
**unioned over the scan axis**: a tile is skipped only when it is dead
in every layer of the segment.  That is conservative but exact —
pruned weights are exact zeros, so computing a tile that is dead in
*this* layer (but live in a sibling) only adds zeros.

Geometry is fixed at the MXU's 128×128 here regardless of the pruning
config's crossbar shape: the plan describes what the TPU kernel can
skip, while ``core.crossbar`` keeps accounting in the paper's geometry.
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

from repro.configs.base import MXU_TILE
from repro.kernels.bsmm import GeometryError, TilePlan, make_tile_plan

# projection keys routed through the bsmm kernel
_ATTN_KEYS = ("wq", "wk", "wv", "wo")
_MLP_KEYS = ("up", "gate", "down")
_EXPERT_KEYS = ("up", "gate", "down")   # stacked (E, d, d_ff) MoE tensors


@dataclass
class PlanStats:
    """Aggregate tile accounting across every routed projection."""
    routed: int = 0             # projections with a bsmm plan
    dense_fallback: int = 0     # prunable projections left dense
    live_tiles: int = 0
    total_tiles: int = 0
    by_layer: List[Tuple[str, int, int]] = field(default_factory=list)

    @property
    def skipped_tile_fraction(self) -> float:
        if self.total_tiles == 0:
            return 0.0
        return 1.0 - self.live_tiles / self.total_tiles


def _union_mask(mask) -> Optional[np.ndarray]:
    """Mask leaf → 2-D union bitmap source.

    Leading axes — the scan-repeat axis of a stacked segment, the
    expert axis of an MoE tensor, or both ((reps, E, K, N)) — are
    union-reduced away: a tile is skipped only when it is dead in every
    layer/expert sharing the traced matmul, which is conservative but
    exact because pruned weights are exact zeros.
    """
    if mask is None:
        return None
    m = np.asarray(mask)
    if m.ndim > 2:
        m = (m != 0).any(axis=tuple(range(m.ndim - 2)))
    if m.ndim != 2:
        return None
    return m


def _plan_group(masks: Dict[str, Any], keys, label: str, stats: PlanStats,
                *, tile: int, interpret: Optional[bool],
                strict: bool = False) -> Optional[Dict[str, TilePlan]]:
    group: Dict[str, TilePlan] = {}
    for key in keys:
        m2 = _union_mask(masks.get(key))
        if m2 is None:
            continue
        plan = make_tile_plan(m2, tile=tile, interpret=interpret,
                              strict=strict, where=f"{label}.{key}")
        if plan is None:                  # shape does not tile — stay dense
            stats.dense_fallback += 1
            continue
        group[key] = plan
        stats.routed += 1
        stats.live_tiles += plan.live_tiles
        stats.total_tiles += plan.total_tiles
        stats.by_layer.append((f"{label}.{key}", plan.live_tiles,
                               plan.total_tiles))
    return group or None


def build_decode_plan(masks, *, tile: int = MXU_TILE,
                      interpret: Optional[bool] = None,
                      strict: bool = False
                      ) -> Tuple[Optional[list], PlanStats]:
    """Mask pytree → (plan mirroring params['segments'], PlanStats).

    Returns ``(None, empty stats)`` when the masks carry no routable
    structure (non-transformer params, MLA attention, MoE-only FFNs —
    those run dense).  ``strict=True`` turns per-projection dense
    fallbacks (shapes that don't tile) into a ``GeometryError`` naming
    the projection — for callers that expect full coverage.  An invalid
    ``tile`` raises ``GeometryError`` either way.
    """
    if tile <= 0:
        raise GeometryError(f"tile edge must be positive, got {tile}",
                            tile=tile, where="build_decode_plan")
    stats = PlanStats()
    if not isinstance(masks, dict) or "segments" not in masks:
        return None, stats
    plan: list = []
    any_entry = False
    for s_idx, pos_trees in enumerate(masks["segments"]):
        seg_plan = []
        for pos, ptree in enumerate(pos_trees):
            entry: Dict[str, Any] = {}
            if not isinstance(ptree, dict):
                seg_plan.append(None)
                continue
            attn = ptree.get("attn")
            # MLA (absorbed decode is einsum-shaped, not a K×N matmul)
            # is skipped: its dict carries w_dq/w_uq instead of wq.
            if isinstance(attn, dict) and "wq" in attn:
                g = _plan_group(attn, _ATTN_KEYS, f"seg{s_idx}.{pos}.attn",
                                stats, tile=tile, interpret=interpret,
                                strict=strict)
                if g:
                    entry["attn"] = g
            ffn = ptree.get("mlp")
            if isinstance(ffn, dict):
                g = _plan_group(ffn, _MLP_KEYS, f"seg{s_idx}.{pos}.mlp",
                                stats, tile=tile, interpret=interpret,
                                strict=strict)
                if g:
                    entry["mlp"] = g
            moe = ptree.get("moe")
            if isinstance(moe, dict):
                # stacked (E, d, d_ff) expert tensors union over the
                # expert axis (and the scan axis) into ONE shared plan:
                # the per-expert matmuls vmap over E with that plan
                g = _plan_group(moe, _EXPERT_KEYS, f"seg{s_idx}.{pos}.moe",
                                stats, tile=tile, interpret=interpret,
                                strict=strict)
                moe_entry: Dict[str, Any] = dict(g) if g else {}
                shared = moe.get("shared")
                if isinstance(shared, dict):
                    sg = _plan_group(shared, _MLP_KEYS,
                                     f"seg{s_idx}.{pos}.moe.shared",
                                     stats, tile=tile, interpret=interpret,
                                     strict=strict)
                    if sg:
                        moe_entry["shared"] = sg
                if moe_entry:
                    entry["moe"] = moe_entry
            any_entry = any_entry or bool(entry)
            seg_plan.append(entry or None)
        plan.append(seg_plan)
    if not any_entry:
        return None, stats
    return plan, stats

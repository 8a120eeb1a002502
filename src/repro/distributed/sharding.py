"""Sharding rules: logical-axis assignment with divisibility fallbacks.

Parallelism layout (DESIGN.md §4):
  * DP  — batch over ('pod', 'data')
  * TP  — projections column/row-parallel over 'model'
  * EP  — MoE expert axis over 'model'
  * SP  — decode KV caches sequence-sharded over 'model' when head
          counts don't divide (flash-decode style partial softmax)

Every rule degrades gracefully: a dimension is sharded only when the
mesh axis divides it, so the same code lowers on (16,16), (2,16,16) and
a 1-device CPU (smoke tests see a trivial mesh and all-replicated
specs).
"""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass
from typing import Any, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

# param-name classes (last path component)
_COL_PARALLEL = {"wq", "wk", "wv", "up", "gate", "w_in", "w_gate",
                 "w_uq", "w_uk", "w_uv", "wi", "wf", "wz",
                 "frame_adapter", "patch_proj"}
_ROW_PARALLEL = {"wo", "down", "w_out"}
_VOCAB_PARALLEL = {"table"}
_REPLICATED = {"router", "lam", "bi", "bf", "bq", "bk", "bv", "bz", "bo",
               "scale", "bias", "up_b", "down_b", "b"}


def _last_key(path: str) -> str:
    return path.split("/")[-1]


# attention projections whose sharded dim is n_heads*head_dim — a shard
# narrower than head_dim splits a head across devices, which the repo
# never allows (see cache_spec: involuntary SPMD remat in the attention
# einsums, and on multi-axis CPU meshes XLA's repartition of the RoPE'd
# k path is numerically unstable)
_HEAD_COL = {"wq", "wk", "wv", "w_uq", "w_uk", "w_uv"}
_HEAD_ROW = {"wo"}


@dataclass
class ShardingRules:
    mesh: Mesh
    head_dim: Optional[int] = None

    def __post_init__(self):
        names = self.mesh.axis_names
        self.dp_axes = tuple(a for a in ("pod", "data") if a in names)
        self.tp_axis = "model" if "model" in names else None
        self.tp_size = (self.mesh.shape[self.tp_axis]
                        if self.tp_axis else 1)
        self.dp_size = int(np.prod([self.mesh.shape[a]
                                    for a in self.dp_axes])) or 1

    # ------------------------------------------------------------------
    def _tp_if(self, dim: int):
        """'model' iff the axis exists and divides dim."""
        if self.tp_axis and dim % self.tp_size == 0 and dim >= self.tp_size:
            return self.tp_axis
        return None

    def _tp_if_heads(self, dim: int):
        """'model' iff it divides dim AND shards land on head boundaries
        (no-op guard when ``head_dim`` is unknown)."""
        ax = self._tp_if(dim)
        if ax and self.head_dim \
                and (dim // self.tp_size) % self.head_dim != 0:
            return None
        return ax

    def _dp_if(self, dim: int):
        if self.dp_axes and dim % self.dp_size == 0:
            return self.dp_axes
        return None

    # ------------------------------------------------------------------
    def param_spec(self, path: str, shape: Tuple[int, ...]) -> P:
        """PartitionSpec for one parameter leaf (stacked dims included)."""
        name = _last_key(path)
        nd = len(shape)
        if nd == 0:
            return P()
        is_moe = "/moe/" in path and name in ("up", "gate", "down")
        if is_moe:
            # (…, E, d, f): expert-parallel over model
            spec = [None] * nd
            spec[-3] = self._tp_if(shape[-3])
            return P(*spec)
        if name in _VOCAB_PARALLEL and nd >= 2:
            spec = [None] * nd
            spec[-2] = self._tp_if(shape[-2])     # vocab dim of (V, d)
            return P(*spec)
        if name in _REPLICATED or nd == 1:
            return P(*([None] * nd))
        if name in _COL_PARALLEL:
            tp = self._tp_if_heads if name in _HEAD_COL else self._tp_if
            spec = [None] * nd
            spec[-1] = tp(shape[-1])
            if spec[-1] is None and nd >= 2:
                spec[-2] = self._tp_if(shape[-2])
            return P(*spec)
        if name in _ROW_PARALLEL:
            tp = self._tp_if_heads if name in _HEAD_ROW else self._tp_if
            spec = [None] * nd
            spec[-2] = tp(shape[-2])
            if spec[-2] is None:
                spec[-1] = self._tp_if(shape[-1])
            return P(*spec)
        if name == "w" and nd >= 3:
            # block-diagonal (…, nb, bs, bs): shard the block axis
            spec = [None] * nd
            spec[-3] = self._tp_if(shape[-3])
            return P(*spec)
        if nd >= 2:
            # default: try column-parallel
            spec = [None] * nd
            spec[-1] = self._tp_if(shape[-1])
            return P(*spec)
        return P(*([None] * nd))

    def params_shardings(self, params_tree):
        """NamedSharding pytree for a (shape-)pytree of parameters."""
        from repro.core.masks import path_str

        def mk(path, leaf):
            if leaf is None:
                return None
            spec = self.param_spec(path_str(path), leaf.shape)
            return NamedSharding(self.mesh, spec)

        return jax.tree_util.tree_map_with_path(
            mk, params_tree, is_leaf=lambda x: x is None)

    # ------------------------------------------------------------------
    def opt_state_shardings(self, opt_tree, zero1: bool = True):
        """ZeRO-1: optimizer moments additionally sharded over 'data'.

        Each m/v leaf keeps its parameter's TP spec and gets the 'data'
        axis on the first remaining divisible dim (often the scan/stack
        dim) — cutting the dominant train-state memory by dp_size.  XLA
        inserts the reduce-scatter/all-gather pair this implies.
        """
        from repro.core.masks import path_str
        data_ax = "data" if "data" in self.mesh.axis_names else None
        dsize = self.mesh.shape.get("data", 1) if data_ax else 1

        def mk(path, leaf):
            if leaf is None:
                return None
            p = path_str(path)
            spec = list(self.param_spec(p, leaf.shape))
            if zero1 and data_ax and p.split("/")[0] in ("m", "v", "mu"):
                for i, (dim, s) in enumerate(zip(leaf.shape, spec)):
                    if s is None and dim % dsize == 0 and dim >= dsize:
                        spec[i] = data_ax
                        break
            return NamedSharding(self.mesh, P(*spec))

        return jax.tree_util.tree_map_with_path(
            mk, opt_tree, is_leaf=lambda x: x is None)

    # ------------------------------------------------------------------
    def batch_spec(self, shape: Tuple[int, ...]) -> P:
        """Inputs: batch over DP axes, rest replicated."""
        if not shape:
            return P()
        return P(self._dp_if(shape[0]), *([None] * (len(shape) - 1)))

    def batch_shardings(self, batch_tree):
        return jax.tree.map(
            lambda l: NamedSharding(self.mesh, self.batch_spec(l.shape)),
            batch_tree)

    # ------------------------------------------------------------------
    def cache_spec(self, path: str, shape: Tuple[int, ...]) -> P:
        """KV caches / recurrent states (stacked: leading reps dim).

        Heuristic: dim0 may be the scan-stack (reps) — we detect batch
        as the dim matching a DP-shardable size; shard heads on model
        when divisible, else the sequence/capacity dim (SP decode).
        """
        nd = len(shape)
        if nd == 0:
            return P()
        spec: list = [None] * nd
        # find the batch dim: first dim (or second for stacked caches)
        for bdim in range(min(2, nd)):
            if self._dp_if(shape[bdim]) is not None:
                spec[bdim] = self._dp_if(shape[bdim])
                break
        else:
            bdim = -1
        # shard one more dim on model: prefer heads (dim -2 of k/v),
        # else the capacity/sequence dim (head_dim sharding forces
        # involuntary SPMD remat in attention einsums — never pick it)
        if self.tp_axis:
            for cand in (nd - 2, nd - 3):
                if 0 <= cand < nd and spec[cand] is None \
                        and cand != bdim \
                        and shape[cand] % self.tp_size == 0 \
                        and shape[cand] >= self.tp_size:
                    spec[cand] = self.tp_axis
                    break
        return P(*spec)

    def cache_shardings(self, cache_tree):
        from repro.core.masks import path_str

        def mk(path, leaf):
            if leaf is None:
                return None
            return NamedSharding(self.mesh,
                                 self.cache_spec(path_str(path), leaf.shape))

        return jax.tree_util.tree_map_with_path(
            mk, cache_tree, is_leaf=lambda x: x is None)

    # ------------------------------------------------------------------
    def plan_spec(self, name: str, shape: Tuple[int, ...]) -> P:
        """PartitionSpec for one TilePlan index array.

        The compacted dispatch arrays are per-tile-column (forward
        ``idx``/``counts``: one row per N tile) or per-tile-row
        (transposed ``idx_t``/``counts_t``: one row per K tile) — the
        same axes the col-/row-parallel weight specs cut, so axis 0
        shards over 'model' when it divides and replicates otherwise.
        The flat live-tile coordinates (``kk``/``nn``) index the whole
        bitmap and stay replicated."""
        if not shape:
            return P()
        spec = [None] * len(shape)
        if name in ("idx", "counts", "idx_t", "counts_t"):
            spec[0] = self._tp_if(shape[0])
        return P(*spec)

    def shard_plan(self, plan_tree):
        """Device-put every TilePlan's index arrays with NamedShardings
        (static int fields and None leaves pass through untouched)."""
        fields = ("idx", "counts", "idx_t", "counts_t", "kk", "nn")

        def put(tp):
            if tp is None or not hasattr(tp, "_replace"):
                return tp
            upd = {}
            for f in fields:
                arr = getattr(tp, f, None)
                if arr is None:
                    continue
                sh = NamedSharding(self.mesh,
                                   self.plan_spec(f, np.shape(arr)))
                upd[f] = jax.device_put(jnp.asarray(arr), sh)
            return tp._replace(**upd)

        return jax.tree.map(
            put, plan_tree,
            is_leaf=lambda x: x is None or hasattr(x, "_replace"))

    # ------------------------------------------------------------------
    def activation_constrainer(self):
        """Returns f(x, tag_tuple) for transformer.set_constrain_fn."""
        mesh = self.mesh

        def constrain(x, tags):
            if len(tags) != x.ndim:
                return x
            spec = []
            for dim, tag in zip(x.shape, tags):
                if tag == "dp":
                    spec.append(self._dp_if(dim))
                elif tag == "model":
                    spec.append(self._tp_if(dim))
                else:
                    spec.append(None)
            return jax.lax.with_sharding_constraint(
                x, NamedSharding(mesh, P(*spec)))

        return constrain


_INSTALLED: Optional[ShardingRules] = None


def install(rules: Optional[ShardingRules]):
    """Activate activation constraints + MoE grouping (None → reset)."""
    global _INSTALLED
    from repro.models import hooks

    _INSTALLED = rules
    if rules is None:
        hooks.set_constrain_fn(lambda x, tags: x)
        hooks.set_moe_groups(1)
        hooks.set_kernel_mesh(None)
    else:
        hooks.set_constrain_fn(rules.activation_constrainer())
        hooks.set_moe_groups(rules.dp_size)
        hooks.set_kernel_mesh(rules.mesh)


def installed() -> Optional[ShardingRules]:
    """The rules currently installed (so scoped installers — the
    sharded ``ServeEngine`` traces — can save/restore around a trace)."""
    return _INSTALLED

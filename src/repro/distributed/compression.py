"""Gradient compression for the DP all-reduce.

Two compressors, both with error feedback (residual accumulation so the
compression error is re-injected next step — required for convergence):

  * ``TopKCompressor``   — keep the top-k fraction by |g| per leaf.
  * ``MaskAwareCompressor`` — the ReaLPrune-specific trick: pruned
    coordinates are *structurally* zero every step, so they are dropped
    from communication entirely (free 1/(1-sparsity)× reduction), then
    top-k is applied to the survivors.

``compressed_psum`` is the shard_map collective: each DP shard
contributes its top-k (values, indices); an all_gather of the sparse
representation + local scatter-add replaces the dense all-reduce.
Traffic: 2·k floats/ints per shard instead of the full gradient.
"""
from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np


@dataclass
class TopKCompressor:
    k_fraction: float = 0.01

    def init(self, params):
        return jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params)

    def compress(self, grads, residual):
        """Returns (sparse_grads, new_residual, stats).

        sparse_grads has the same dense shapes but only top-k nonzeros —
        the traffic reduction is realised by ``compressed_psum`` /
        counted by ``stats['sent_fraction']``.
        """
        sent = 0
        total = 0

        def comp(g, r):
            nonlocal sent, total
            acc = g.astype(jnp.float32) + r
            flat = acc.reshape(-1)
            k = max(1, int(self.k_fraction * flat.size))
            _, idx = jax.lax.top_k(jnp.abs(flat), k)
            vals = flat[idx]
            out = jnp.zeros_like(flat).at[idx].set(vals)
            sent += k
            total += flat.size
            return out.reshape(g.shape).astype(g.dtype), \
                (flat - out).reshape(g.shape)

        pairs = jax.tree.map(comp, grads, residual)
        sparse = jax.tree.map(lambda t: t[0], pairs,
                              is_leaf=lambda x: isinstance(x, tuple))
        new_res = jax.tree.map(lambda t: t[1], pairs,
                               is_leaf=lambda x: isinstance(x, tuple))
        return sparse, new_res, {"sent_fraction": sent / max(total, 1)}


@dataclass
class MaskAwareCompressor:
    """Skip pruned coordinates, then top-k the survivors.

    With 95% ReaLPrune sparsity the dense gradient all-reduce shrinks
    20× before any lossy compression — the paper's hardware saving
    reused as a communication saving.
    """
    masks: Any
    k_fraction: float = 1.0       # 1.0 = lossless w.r.t. surviving weights

    def init(self, params):
        # lossless: nothing is ever dropped, so there is no residual to
        # feed back — and no full-size f32 zeros to hold on the device
        if self.k_fraction >= 1.0:
            return None
        return jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params)

    def compress(self, grads, residual):
        from repro.core.masks import apply_masks
        sent = 0
        total = 0

        def count(g, m):
            nonlocal sent, total
            total += g.size
            sent += int(np.asarray(m).sum()) if m is not None else g.size
            return g

        masked = apply_masks(grads, self.masks)
        jax.tree_util.tree_map(
            lambda g: None, grads)  # structure walk only
        # count statically
        from repro.core.masks import path_str
        flat_masks = {}

        def visitm(path, leaf):
            flat_masks[path_str(path)] = leaf
            return leaf
        jax.tree_util.tree_map_with_path(visitm, self.masks,
                                         is_leaf=lambda x: x is None)

        def visitg(path, leaf):
            nonlocal sent, total
            m = flat_masks.get(path_str(path))
            total += leaf.size
            sent += leaf.size if m is None else int(np.asarray(m).sum())
            return leaf
        jax.tree_util.tree_map_with_path(visitg, grads)

        if self.k_fraction < 1.0:
            inner = TopKCompressor(self.k_fraction)
            sparse, new_res, st = inner.compress(masked, residual)
            st["sent_fraction"] *= sent / max(total, 1)
            return sparse, new_res, st
        return masked, residual, {"sent_fraction": sent / max(total, 1)}


def compressed_psum(x, axis_name: str, k: int):
    """Top-k sparse all-reduce primitive for use inside shard_map.

    Each shard sends (values, indices) of its local top-k; the gather +
    scatter-add reconstructs Σ_shards topk(g_shard).  Traffic per link:
    O(k · n_shards) instead of O(size).
    """
    flat = x.reshape(-1)
    vals, idx = jax.lax.top_k(jnp.abs(flat), k)
    vals = flat[idx]
    all_vals = jax.lax.all_gather(vals, axis_name)      # (n, k)
    all_idx = jax.lax.all_gather(idx, axis_name)
    out = jnp.zeros_like(flat)
    out = out.at[all_idx.reshape(-1)].add(all_vals.reshape(-1))
    return out.reshape(x.shape)


def dp_allreduce_compressed(grads_fn, mesh, dp_axis: str, k_fraction: float):
    """Wrap a per-shard grad function with a compressed DP all-reduce
    under shard_map (used by the optional compressed train step)."""
    from jax.sharding import PartitionSpec as P

    def reduced(*args):
        def inner(*a):
            g = grads_fn(*a)
            return jax.tree.map(
                lambda t: compressed_psum(
                    t, dp_axis, max(1, int(k_fraction * t.size))), g)
        return jax.shard_map(inner, mesh=mesh,
                             in_specs=P(dp_axis), out_specs=P())(*args)

    return reduced

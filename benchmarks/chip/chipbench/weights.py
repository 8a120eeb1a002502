"""Weights and the ticket, drawn on the device from the seed.

The program supplies only the layout: ``jax.eval_shape`` of its own
initialiser gives the tree, shapes and dtypes.  Every value is drawn
here, leaf by leaf from ``fold_in(key, leaf index)``, with the usual
initialisers: Glorot-uniform matrices and kernels, N(0, 0.02)
embeddings, unit norm and BatchNorm scales, zero biases.

The ticket's tile pattern is ranked on a draw from the cell's fixed
``ticket_seed``, not from ``--seed``: the program compiles a ticket's
tile plan into its kernels, so a pattern that moved with the seed would
compile new programs in every run.  ``--seed`` draws the values of the
live weights and the data.
"""
from __future__ import annotations

import functools
import math
from typing import Callable, Dict

import numpy as np

import jax
import jax.numpy as jnp

from chipbench import ticket


def seed_key(seed: int):
    """PRNG key for any whole number up to 2**62."""
    lo, hi = seed & 0x7FFFFFFF, (seed >> 31) & 0x7FFFFFFF
    return jax.random.fold_in(jax.random.PRNGKey(lo), hi)


def path_str(path) -> str:
    parts = []
    for p in path:
        for attr in ("key", "idx", "name"):
            if hasattr(p, attr):
                parts.append(str(getattr(p, attr)))
                break
        else:
            parts.append(str(p))
    return "/".join(parts)


def leaf_draw(key, path: str, shape, dtype):
    """One leaf's initial value, by its name and shape."""
    name = path.split("/")[-1]
    if name in ("scale", "var"):
        return jnp.ones(shape, dtype)
    if name in ("bias", "b", "mean") or len(shape) < 2:
        return jnp.zeros(shape, dtype)
    if name == "table" and path.startswith("embed"):
        return (0.02 * jax.random.normal(key, shape, jnp.float32)
                ).astype(dtype)
    if len(shape) == 4:                   # conv kernel (k, k, IC, OC)
        fan = shape[2] + shape[3]
    else:                                 # (..., K, N) matrix or table
        fan = shape[-2] + shape[-1]
    lim = math.sqrt(6.0 / fan)
    return jax.random.uniform(key, shape, jnp.float32, -lim, lim
                              ).astype(dtype)


def leaves_with_paths(tree):
    flat, treedef = jax.tree_util.tree_flatten_with_path(tree)
    return [(path_str(p), leaf) for p, leaf in flat], treedef


def draw(shapes, seed: int, *, prunable: Callable, conv: Callable,
         density: float, ticket_seed: int):
    """(params, masks) for the layout ``shapes``, in one jitted call.

    ``masks`` mirrors ``params`` with an f32 {0,1} array on every leaf
    that ``prunable(path, leaf)`` admits and None elsewhere, like
    ``repro.core.masks.make_masks``; ``params`` are zero where the
    masks are.  ``conv(path)`` marks convolution kernels.
    """
    items, treedef = leaves_with_paths(shapes)
    plan = tuple((p, tuple(s.shape), s.dtype, bool(prunable(p, s)),
                  bool(conv(p))) for p, s in items)
    params, masks = _draw(plan, float(density), seed_key(seed),
                          seed_key(ticket_seed))
    return (jax.tree_util.tree_unflatten(treedef, params),
            jax.tree_util.tree_unflatten(treedef, masks))


@functools.partial(jax.jit, static_argnums=(0, 1))
def _draw(plan, density, key, tkey):
    params, masks = [], []
    for i, (path, shape, dtype, prune, is_conv) in enumerate(plan):
        w = leaf_draw(jax.random.fold_in(key, i), path, shape, dtype)
        m = None
        if prune:
            rank_on = leaf_draw(jax.random.fold_in(tkey, i), path, shape,
                                jnp.float32)
            m = ticket.leaf_mask(rank_on, density, is_conv)
            w = w * m.astype(dtype)
        params.append(w)
        masks.append(m)
    return params, masks


def initial(shapes, seed: int, masks):
    """The masked initial value of every leaf, drawn again from the seed
    (the same values ``draw`` gave)."""
    items, treedef = leaves_with_paths(shapes)
    plan = tuple((p, tuple(s.shape), s.dtype) for p, s in items)
    out = _initial(plan, seed_key(seed), treedef.flatten_up_to(masks))
    return jax.tree_util.tree_unflatten(treedef, out)


def _initial_leaves(plan, key, mflat):
    return [w if m is None else w * m.astype(dtype)
            for (path, shape, dtype), m, w in zip(
                plan, mflat, (leaf_draw(jax.random.fold_in(key, i), *p)
                              for i, p in enumerate(plan)))]


_initial = jax.jit(_initial_leaves, static_argnums=(0,))


def change_norms(shapes, seed: int, masks, current: Dict[str, jax.Array],
                 stacked: Callable[[str], bool]) -> Dict[str, float]:
    """L2 norm of (current - initial) for every leaf, and for every
    layer of a stacked leaf (keys ``path[i]``), with the initial values
    drawn again inside the same call.  ``current`` maps a leaf's path
    to its value."""
    items, treedef = leaves_with_paths(shapes)
    plan = tuple((p, tuple(s.shape), s.dtype) for p, s in items)
    flags = tuple(bool(stacked(p)) for p, _ in items)
    vals = jax.device_get(_change_norms(
        plan, flags, seed_key(seed), treedef.flatten_up_to(masks),
        [current[p] for p, _ in items]))
    out: Dict[str, float] = {}
    for (p, _), v in zip(items, vals):
        v = np.asarray(v, np.float64)
        if v.ndim:
            out.update({f"{p}[{i}]": float(x) for i, x in enumerate(v)})
        else:
            out[p] = float(v)
    return out


@functools.partial(jax.jit, static_argnums=(0, 1))
def _change_norms(plan, flags, key, mflat, now):
    out = []
    for f, w0, w in zip(flags, _initial_leaves(plan, key, mflat), now):
        d = w.astype(jnp.float32) - w0.astype(jnp.float32)
        axes = tuple(range(1, d.ndim)) if f else None
        out.append(jnp.sqrt(jnp.sum(d * d, axis=axes)))
    return out

"""The numbers that decide ``correct`` for a training cell.

Each is taken by the worst leaf (a stacked leaf counts once per layer):
the gap between the program's norm of a leaf and the reference's,
measured against the larger of the reference's norm of that leaf and
of the median leaf.  Leaves whose reference gradient is under a
thousandth of the median leaf's move by round-off alone and are left
out of the change, by that rule and not by name.
"""
from __future__ import annotations

from typing import Callable, Dict, Iterable, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import weights
from chipbench.weights import leaves_with_paths

# A leaf moves by round-off alone when its reference gradient is under
# this share of the median leaf's.
STILL = 1e-3


def leaf_norms(tree, stacked: Callable[[str], bool],
               scale: float = 1.0) -> Dict[str, float]:
    """L2 norm of every leaf (of every layer of a stacked leaf), times
    ``scale``; None leaves are skipped."""
    items = [(p, x) for p, x in leaves_with_paths(tree)[0]
             if x is not None]
    if not items:
        return {}

    @jax.jit
    def norms(xs):
        out = []
        for (p, _), x in zip(items, xs):
            x = x.astype(jnp.float32)
            axes = tuple(range(1, x.ndim)) if stacked(p) else None
            out.append(jnp.sqrt(jnp.sum(jnp.square(x), axis=axes)))
        return out

    vals = jax.device_get(norms([x for _, x in items]))
    out: Dict[str, float] = {}
    for (p, _), v in zip(items, vals):
        v = np.asarray(v, np.float64) * scale
        if v.ndim:
            out.update({f"{p}[{i}]": float(x) for i, x in enumerate(v)})
        else:
            out[p] = float(v)
    return out


def moved(ref_grad: Dict[str, float], floor: float = STILL) -> set:
    """Leaves whose reference gradient is at least ``floor`` times the
    median leaf's."""
    med = float(np.median(list(ref_grad.values())))
    return {k for k, v in ref_grad.items() if v >= floor * med}


def leaf_gaps(prog: Dict[str, float], ref: Dict[str, float],
              keep: Optional[Iterable[str]] = None) -> Dict[str, float]:
    """Per leaf in ``keep`` (all if None), the gap of norms over the
    larger of the reference's norm of that leaf and of the median
    leaf; a leaf the program lacks reads infinite."""
    keys = sorted(ref if keep is None else set(keep))
    med = float(np.median([ref[k] for k in keys]))
    return {k: abs(prog[k] - ref[k]) / max(ref[k], med, 1e-30)
            if k in prog else float("inf") for k in keys}


def norm_gap(prog: Dict[str, float], ref: Dict[str, float],
             keep: Optional[Iterable[str]] = None) -> Tuple[float, str]:
    """(worst gap, its leaf) over the leaves in ``keep`` (all if None)."""
    gaps = leaf_gaps(prog, ref, keep)
    where = max(gaps, key=gaps.get)
    return gaps[where], where


def median_gap(prog: Dict[str, float], ref: Dict[str, float],
               keep: Optional[Iterable[str]] = None) -> Tuple[float, str]:
    """(the median leaf's gap, "median leaf") over ``keep``."""
    return float(np.median(list(leaf_gaps(prog, ref, keep).values()))), \
        "median leaf"


def loss_gap(prog, ref) -> float:
    """Largest relative gap between two sequences of losses."""
    prog, ref = np.asarray(prog, np.float64), np.asarray(ref, np.float64)
    if prog.shape != ref.shape or not np.all(np.isfinite(prog)):
        return float("inf")
    return float(np.max(np.abs(prog - ref) / np.abs(ref)))


def flat(tree) -> Dict:
    """path -> leaf, None leaves kept."""
    items, _ = jax.tree_util.tree_flatten_with_path(
        tree, is_leaf=lambda x: x is None)
    return {weights.path_str(p): x for p, x in items}


def pruned_nonzero(params, masks) -> int:
    """Weights under a zero mask that are not exactly zero."""
    bad = jax.tree.map(
        lambda p, m: None if m is None else jnp.sum((p != 0) & (m == 0)),
        params, masks, is_leaf=lambda x: x is None)
    return int(sum(int(x) for x in jax.tree.leaves(bad)))


def readings(trainer, first_moment: Callable, grad_scale: float, shapes,
             seed: int, masks, steps: int,
             stacked: Callable[[str], bool]) -> Dict:
    """Drive ``trainer`` through its first ``steps`` steps with
    ``Trainer.run`` and read what the check compares: each step's loss,
    the first gradient as the optimizer got it (``first_moment`` of the
    optimizer state after step 1, times ``grad_scale``), the change of
    the weights over the steps, and the pruned weights that are not
    zero."""
    losses, grad = [], None
    for k in range(steps):
        losses.append(trainer.run(1)["loss"])
        if k == 0:
            grad = leaf_norms(first_moment(trainer.state.opt_state),
                              stacked, grad_scale)
    p = trainer.state.params
    return {"losses": losses, "grad": grad,
            "change": weights.change_norms(shapes, seed, masks, flat(p),
                                           stacked),
            "pruned_nonzero": pruned_nonzero(p, masks)}


def numbers(prog: Dict, ref: Dict) -> Dict[str, Tuple[float, str]]:
    """Every number a training cell can compare, with the leaf it read
    worst at: the largest relative gap of the losses, and of the first
    step's loss alone; by the worst leaf and by the median leaf, the
    first gradient's and the change's gap of norms; pruned weights that
    are not exactly zero."""
    keep = moved(ref["grad"])
    return {"loss_gap": (loss_gap(prog["losses"], ref["losses"]), ""),
            "first_loss_gap": (loss_gap(prog["losses"][:1],
                                        ref["losses"][:1]), ""),
            "grad_gap": norm_gap(prog["grad"], ref["grad"]),
            "grad_median_gap": median_gap(prog["grad"], ref["grad"]),
            "change_gap": norm_gap(prog["change"], ref["change"], keep),
            "change_median_gap": median_gap(prog["change"], ref["change"],
                                            keep),
            "pruned_nonzero": (float(prog["pruned_nonzero"]), "")}


def checks(prog: Dict, ref: Dict, limits: Dict) -> list:
    """The numbers that decide ``correct``: those ``limits`` names, each
    with its limit."""
    return judge(numbers(prog, ref), limits)


def judge(got: Dict[str, Tuple[float, str]], limits: Dict) -> list:
    """A Check for each number ``limits`` names, from ``got`` (name ->
    (value, worst leaf))."""
    from chipbench.harness import Check
    return [Check(name, got[name][0], float(limit), got[name][1])
            for name, limit in limits.items()]

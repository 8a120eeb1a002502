"""Kernel microbenchmarks: block-sparse TRAINING-step tile-skip scaling.

Times one value_and_grad step — forward + dx + dw, all through the
block-sparse Pallas kernels (``bsmm_apply``'s custom VJP) — against the
dense jnp step, at several tile densities.  Alongside wall-clock it
reports the *predicted* TPU saving from the plan's static metadata:

    fwd passes  = kmax / Kt      (max live K-tiles per output column)
    dx  passes  = nmax / Nt      (transposed plan)
    dw  tiles   = live / total   (only live (bk, bn) grad tiles built)

On this CPU container the kernels run in interpret mode, so wall-clock
is an emulation proxy, NOT TPU time — the derived tile fractions are
the quantity the paper's training-speedup claim maps to.  On a real TPU
backend the kernels compile natively (interpret off) and the measured
saving should track the prediction.

``run()`` prints the CSV lines every bench module emits AND returns
machine-readable records; ``benchmarks/run.py --json`` persists them to
``BENCH_kernels.json`` so the repo accumulates a benchmark trajectory.
"""
from __future__ import annotations

from typing import Dict, List

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import Timer, csv_line
from repro.core.perf_model import bsmm_train_cost
from repro.kernels.bsmm import (default_interpret, make_tile_plan,
                                plan_matmul, row_block)

DENSITIES = (1.0, 0.5, 0.25, 0.0625)


def _mask_at_density(rng, K: int, N: int, b: int, density: float):
    """Elementwise mask whose TILE density is exactly ``density``."""
    Kt, Nt = K // b, N // b
    n_live = max(int(round(density * Kt * Nt)), 0)
    flat = np.zeros(Kt * Nt, np.int32)
    flat[rng.choice(Kt * Nt, n_live, replace=False)] = 1
    bitmap = flat.reshape(Kt, Nt)
    return np.repeat(np.repeat(bitmap, b, 0), b, 1).astype(np.float32)


def _time_step(fn, *args, iters: int = 10) -> float:
    out = fn(*args)
    jax.block_until_ready(out)
    with Timer() as t:
        for _ in range(iters):
            jax.block_until_ready(fn(*args))
    return t.us / iters


def run(M: int = 256, K: int = 512, N: int = 512, b: int = 128,
        iters: int = 10) -> List[Dict]:
    rng = np.random.RandomState(0)
    interpret = default_interpret()
    x = jnp.asarray(rng.randn(M, K), jnp.float32)
    w = jnp.asarray(rng.randn(K, N), jnp.float32)

    def dense_step(w):
        def loss(w):
            return jnp.sum(jnp.square(x @ w))
        return jax.value_and_grad(loss)(w)

    us_dense = _time_step(jax.jit(dense_step), w, iters=iters)
    records: List[Dict] = []
    us_full_plan = None           # density-1.0 kernel run: the anchor that
    for density in DENSITIES:     # isolates tile-skip from interpret overhead
        mask = _mask_at_density(rng, K, N, b, density)
        plan = make_tile_plan(mask, tile=b, interpret=interpret)
        wm = jnp.asarray(np.asarray(w) * mask)

        def sparse_step(w, plan=plan):
            def loss(w):
                return jnp.sum(jnp.square(plan_matmul(x, w, plan)))
            return jax.value_and_grad(loss)(w)

        us_sparse = _time_step(jax.jit(sparse_step), wm, iters=iters)
        if us_full_plan is None:
            us_full_plan = us_sparse
        Kt, Nt = K // b, N // b
        fwd_frac = plan.kmax / Kt
        dx_frac = plan.nmax / Nt
        dw_frac = plan.live_tiles / plan.total_tiles
        predicted_cost = (fwd_frac + dx_frac + dw_frac) / 3.0
        # the K306-audited analytic model: per-kernel passes/FLOPs/HBM
        # bytes for this exact plan (what the TPU regen compares against)
        cost = bsmm_train_cost(plan, M, bm=row_block(M, x.dtype, b)[1])
        rec = {
            "name": f"bsmm_train_density_{density}",
            "shape": [M, K, N],
            "tile": b,
            "tile_density": dw_frac,
            "kmax": plan.kmax, "kt": Kt,
            "nmax": plan.nmax, "nt": Nt,
            "live_tiles": plan.live_tiles,
            "total_tiles": plan.total_tiles,
            "us_dense": us_dense,
            "us_sparse": us_sparse,
            "measured_saving": 1.0 - us_sparse / us_dense,
            "measured_saving_vs_full_plan": 1.0 - us_sparse / us_full_plan,
            "predicted_saving": 1.0 - predicted_cost,
            "predicted_cost": {
                k: {"passes": c.passes, "flops": c.flops,
                    "hbm_bytes": c.hbm_bytes}
                for k, c in cost.items()},
            "interpret": interpret,
            "backend": jax.default_backend(),
        }
        records.append(rec)
        print(csv_line(
            rec["name"], us_sparse,
            f"tile_density={dw_frac:.3f};kgrid_frac={fwd_frac:.3f};"
            f"ngrid_frac={dx_frac:.3f};"
            f"predicted_saving={rec['predicted_saving']:.3f};"
            f"measured_saving={rec['measured_saving']:.3f};"
            f"vs_full_plan={rec['measured_saving_vs_full_plan']:.3f}"))
    return records


if __name__ == "__main__":
    run()

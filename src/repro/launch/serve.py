"""Production serving launcher (control plane over the batched engine).

    PYTHONPATH=src python -m repro.launch.serve --arch yi-6b --smoke \
        [--requests N] [--pruned FRAC] [--deadline S] [--heartbeat-dir D] \
        [--engines N] [--mesh DxM]

Requests are admitted through ``serve.frontend.ServeFrontend``: a
bounded intake queue backs onto the engine's capacity check, deadlines
cancel expired slots mid-decode, and (with ``--heartbeat-dir``) the
engine's per-tick heartbeat gates admission when the decode loop
wedges.  ``--engines N`` fronts N engines with a ``FleetRouter``
(least-loaded dispatch + heartbeat failover); ``--mesh DxM`` runs each
engine sharded over a (data, model) test mesh (virtual devices on CPU —
launch with ``XLA_FLAGS=--xla_force_host_platform_device_count=8``).
Same mesh/sharding story as train.py: ``--smoke`` serves the reduced
f32 config; without it the registered config serves as published, on
``--mesh`` or else on the mesh ``launch.mesh.make_launch_mesh`` builds
from the devices present.
"""
from __future__ import annotations

import argparse

import jax
import numpy as np

from repro.configs import get_arch, scaled_down
from repro.core import algorithm as alg
from repro.core.masks import apply_masks, lm_prunable, make_masks, \
    sparsity_fraction
from repro.distributed.fault_tolerance import HeartbeatMonitor
from repro.launch.compile_cache import use_compile_cache
from repro.launch.mesh import (make_fleet_meshes, make_launch_mesh,
                               make_test_mesh)
from repro.models import transformer as tfm
from repro.serve import FleetRouter, ServeEngine, ServeFrontend


def parse_mesh(spec):
    """'2x4' → (data=2, model=4)."""
    d, m = (int(x) for x in spec.lower().split("x"))
    return d, m


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="yi-6b")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--requests", type=int, default=16)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--pruned", type=float, default=0.0)
    ap.add_argument("--deadline", type=float, default=None,
                    help="per-request deadline in seconds (expired "
                         "requests free their slot mid-decode)")
    ap.add_argument("--heartbeat-dir", default=None,
                    help="HeartbeatMonitor root for decode-loop liveness")
    ap.add_argument("--engines", type=int, default=1,
                    help="fleet size (FleetRouter over N engines)")
    ap.add_argument("--mesh", default=None,
                    help="per-engine DxM test mesh, e.g. 1x2 (needs "
                         "D*M virtual/physical devices)")
    ap.add_argument("--multi-pod", action="store_true")
    args = ap.parse_args()

    use_compile_cache()
    cfg = get_arch(args.arch)
    if args.smoke:
        cfg = scaled_down(cfg, dtype="float32")
    if args.mesh:
        mesh = make_test_mesh(*parse_mesh(args.mesh))
    elif args.smoke:
        mesh = make_test_mesh()
    else:
        mesh = make_launch_mesh(multi_pod=args.multi_pod)

    params = tfm.init_params(jax.random.PRNGKey(0), cfg)
    masks = None
    if args.pruned > 0:
        masks = make_masks(params, lm_prunable)
        per_step = 1 - (1 - args.pruned) ** (1 / 3)
        for gran in ("filter", "channel", "index"):
            masks = alg.prune_step(params, masks, gran, per_step,
                                   lambda p: False)
        params = apply_masks(params, masks)
        print(f"serving at {sparsity_fraction(masks):.1%} sparsity "
              f"(crossbar-aware)")

    monitor = (HeartbeatMonitor(args.heartbeat_dir, deadline_s=30.0)
               if args.heartbeat_dir else None)

    def make_engine(mesh, heartbeat=None, worker="engine"):
        # engines install the rules scoped around their own traces, so
        # a fleet of sharded engines coexists in one process
        return ServeEngine(params=params, cfg=cfg,
                           prefill_fn=tfm.prefill,
                           decode_fn=tfm.decode_step,
                           batch_slots=8, capacity=256, masks=masks,
                           heartbeat=heartbeat, heartbeat_worker=worker,
                           mesh=mesh)

    rng = np.random.RandomState(0)
    if args.engines > 1:
        # one replica per device (or per --mesh-sized device group)
        d, m = parse_mesh(args.mesh) if args.mesh else (1, 1)
        router = FleetRouter(
            [make_engine(em) for em in make_fleet_meshes(args.engines, d, m)],
            monitor=monitor)
        for i in range(args.requests):
            router.submit(
                rng.randint(0, 200, rng.randint(4, 32)).astype(np.int32),
                uid=i, max_new_tokens=args.max_new,
                deadline_s=args.deadline)
        router.drain()
        rep = router.report
        print(f"fleet: {rep.live_engines}/{rep.engines} engines, "
              f"{rep.requests} requests, {rep.tokens_generated} tokens "
              f"({rep.tokens_per_s:.1f} tok/s, "
              f"failovers {rep.failovers}, "
              f"redispatched {rep.redispatched})")
        print(f"latency: ttft p50/p95 {rep.ttft_p50 * 1e3:.1f}/"
              f"{rep.ttft_p95 * 1e3:.1f}ms | per-request tok/s p50/p95 "
              f"{rep.tps_p50:.1f}/{rep.tps_p95:.1f} | "
              f"deadline misses {rep.deadline_misses}")
        return

    engine = make_engine(mesh, heartbeat=monitor)
    frontend = ServeFrontend(engine)
    for i in range(args.requests):
        frontend.submit(
            rng.randint(0, 200, rng.randint(4, 32)).astype(np.int32),
            uid=i, max_new_tokens=args.max_new,
            deadline_s=args.deadline)
    frontend.drain()
    rep = engine.report
    print(f"served {rep.requests} requests, {rep.tokens_generated} tokens "
          f"in {rep.decode_steps} decode steps "
          f"(occupancy {rep.slot_occupancy:.0%}, "
          f"{rep.tokens_per_s:.1f} tok/s, "
          f"bsmm={'on' if rep.bsmm_enabled else 'off'})")
    print(f"latency: ttft p50/p95 {rep.ttft_p50 * 1e3:.1f}/"
          f"{rep.ttft_p95 * 1e3:.1f}ms | per-request tok/s p50/p95 "
          f"{rep.tps_p50:.1f}/{rep.tps_p95:.1f} | "
          f"deadline misses {rep.deadline_misses}")


if __name__ == "__main__":
    main()

#!/usr/bin/env python3
"""Read, on the chip, the numbers that set a cell's limits.

    python3 benchmarks/chip/calibrate.py --workload yi6b-s8.retrain.t10 \\
        --kind program --seeds 1,2,3,4,5,6,7,8,9,10,11,12
    python3 benchmarks/chip/calibrate.py --workload yi6b-s8.retrain.t10 \\
        --kind control --seeds 1,2,3

For every seed it prints one JSON line with every number the check can
compare: for the program (the lower reading comes from these), for
the control and the planted faults (the upper reading), or, in a
float32 cell, for a second exact reference that differs from the
first by round-off alone (``twin``: what the numbers read when nothing
is wrong).  The
benchmark's own runs never run this.
"""
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [HERE, os.path.join(os.path.dirname(os.path.dirname(HERE)),
                                   "src")]


def main(argv=None) -> int:
    import argparse
    from chipbench import cells, device, harness
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--kind", choices=("program", "control", "twin"),
                    required=True)
    args = ap.parse_args(argv)
    cell = cells.load_cell(args.workload)
    job = cells.job_module(cell)
    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()
    try:
        devices = device.require_chips(cell.chips)
    except device.NoChip as e:
        print(f"calibrate: {e}", file=sys.stderr)
        return 3
    t0 = time.perf_counter()
    runs = [harness.Run(cell, int(s), 0.0, False, devices, t0)
            for s in args.seeds.split(",")]
    for seed, got in job.calibrate(runs, args.kind).items():
        print(json.dumps({"workload": cell.name, "seed": seed, **got}),
              flush=True)
    print(f"calibrate: {time.perf_counter() - t0:.1f} s", file=sys.stderr)
    return 0


if __name__ == "__main__":
    sys.exit(main())

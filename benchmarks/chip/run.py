#!/usr/bin/env python3
"""Run one benchmark cell once on the chip and print its result line.

    python3 benchmarks/chip/run.py --workload yi6b-s8.retrain.t10 \\
        --seed 7 --seconds 30 --trace 0

The cells, their configurations, traffic and metrics are named in
``BENCHMARK.json`` at the root of the checkout; see
``chipbench/harness.py``.  Without a TPU it exits nonzero and prints no
result.
"""
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [HERE, os.path.join(os.path.dirname(os.path.dirname(HERE)),
                                   "src")]

if __name__ == "__main__":
    try:
        from chipbench import harness
        import repro  # noqa: F401  the system under test
    except ImportError as e:
        print(f"bench: cannot import the benchmark or the program: {e}",
              file=sys.stderr)
        sys.exit(2)
    sys.exit(harness.main())

"""Benchmark harness: one module per paper table/figure + kernel/roofline.

Prints ``name,us_per_call,derived`` CSV (one line per measurement).

  fig5_sparsity   — paper Fig. 5 (achievable sparsity per method)
  fig6_crossbars  — paper Fig. 6 (crossbar savings, iso-performance)
  fig7_speedup    — paper Fig. 7 (training speedup, iso-area)
  fig8_layerwise  — paper Fig. 8 (ResNet-18 per-layer xbars/time)
  kernels_bench   — block-sparse train-step (fwd+bwd) tile-skip scaling
  recipes_bench   — staged recipe (paper-quant) per-stage trajectory
  paging_bench    — paged-KV decode bytes/step vs capacity & live context
  fleet_bench     — fleet scheduling throughput + router overhead vs engines
  roofline        — corrected roofline table from the dry-run cache

Run all: ``PYTHONPATH=src python -m benchmarks.run``
One:     ``PYTHONPATH=src python -m benchmarks.run fig6``
JSON:    ``PYTHONPATH=src python -m benchmarks.run kernels --json``
         writes ``BENCH_kernels.json``;
         ``... recipes --json`` writes ``BENCH_recipes.json`` (per-stage
         accuracy/sparsity/live-tile records for the tiny CNN recipe);
         ``... paging --json`` writes ``BENCH_paging.json``;
         ``... fleet --json`` writes ``BENCH_fleet.json`` (timings are
         CPU scheduling-only — see the module docstring).
"""
import argparse
import json
import platform

# benches whose run() returns machine-readable records --json can dump
_JSON_BENCHES = {"kernels": "BENCH_kernels.json",
                 "recipes": "BENCH_recipes.json",
                 "paging": "BENCH_paging.json",
                 "fleet": "BENCH_fleet.json"}


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("which", nargs="?", default="all",
                    choices=["all", "fig5", "fig6", "fig7", "fig8",
                             "kernels", "recipes", "paging", "fleet",
                             "roofline"])
    ap.add_argument("--json", nargs="?", const="", default=None,
                    metavar="PATH",
                    help="write the bench's records to PATH (default "
                         "BENCH_<bench>.json; needs `kernels` or "
                         "`recipes` in the run)")
    ap.add_argument("--force", action="store_true",
                    help="allow an interpret-mode run to overwrite a "
                         "record produced on a real backend")
    opts = ap.parse_args()
    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()
    which, json_path = opts.which, opts.json
    print("name,us_per_call,derived")
    mods = []
    if which in ("all", "fig8"):
        from benchmarks import fig8_layerwise
        mods.append(fig8_layerwise)
    if which in ("all", "fig6"):
        from benchmarks import fig6_crossbars
        mods.append(fig6_crossbars)
    if which in ("all", "fig7"):
        from benchmarks import fig7_speedup
        mods.append(fig7_speedup)
    if which in ("all", "kernels"):
        from benchmarks import kernels_bench
        mods.append(kernels_bench)
    if which in ("all", "recipes"):
        from benchmarks import recipes_bench
        mods.append(recipes_bench)
    if which in ("all", "paging"):
        from benchmarks import paging_bench
        mods.append(paging_bench)
    if which in ("all", "fleet"):
        from benchmarks import fleet_bench
        mods.append(fleet_bench)
    if which in ("all", "roofline"):
        from benchmarks import roofline
        mods.append(roofline)
    if which in ("all", "fig5"):
        from benchmarks import fig5_sparsity
        mods.append(fig5_sparsity)
    records = {}
    for m in mods:
        out = m.run()
        for bench in _JSON_BENCHES:
            if m.__name__.endswith(f"{bench}_bench"):
                records[bench] = out
    if json_path is not None:
        if not records:
            raise SystemExit("--json needs a record-producing bench in "
                             "the run (`kernels`, `recipes`, `paging`, "
                             "or `all`)")
        if json_path and len(records) > 1:
            raise SystemExit(
                "--json PATH is ambiguous with multiple record benches "
                "in one run (`all` produces several); drop the PATH to "
                "get the default BENCH_<bench>.json names, or run one "
                "bench at a time")
        import os

        import jax

        from repro.kernels.bsmm import default_interpret
        interpret = bool(default_interpret())
        for bench, recs in records.items():
            path = json_path or _JSON_BENCHES[bench]
            # kernel-timing benches: refuse to clobber a real-backend
            # record with an interpret-mode (CPU emulation) one — the
            # numbers are not comparable (TPU bring-up runbook step 3
            # regenerates these non-interpret on hardware)
            if bench in ("kernels", "paging") and interpret \
                    and not opts.force and os.path.exists(path):
                try:
                    with open(path) as f:
                        prev = json.load(f)
                except (OSError, ValueError):
                    prev = {}
                if prev.get("interpret_mode") is False:
                    raise SystemExit(
                        f"{path} holds a non-interpret "
                        f"({prev.get('backend')}) record; this run is "
                        f"interpret-mode and would bury it. Re-run "
                        f"with --force to overwrite anyway.")
            payload = {
                "bench": bench,
                "backend": jax.default_backend(),
                "interpret_mode": interpret,
                "python": platform.python_version(),
                "jax": jax.__version__,
                "records": recs,
            }
            with open(path, "w") as f:
                json.dump(payload, f, indent=2)
                f.write("\n")
            print(f"# wrote {path} ({len(recs)} records)")


if __name__ == '__main__':
    main()

"""Benchmark harness — one entry per paper table/figure plus kernel
microbenchmarks. Prints ``name,us_per_call,derived`` CSV.

Usage:
    PYTHONPATH=src python -m benchmarks.run              # default scale
    PYTHONPATH=src python -m benchmarks.run --scale quick
    PYTHONPATH=src python -m benchmarks.run --only fig5,kernels
    PYTHONPATH=src python -m benchmarks.run --engine packet   # packet backend
    PYTHONPATH=src python -m benchmarks.run --engine both     # fluid + packet
    PYTHONPATH=src python -m benchmarks.run --list       # suite table, no runs
    PYTHONPATH=src python -m benchmarks.run --sequential # pre-sweep loop
"""
from __future__ import annotations

import argparse
import sys
import traceback

# suites that pick their own engine(s): fidelity, fig_multipath, fig_geo
# and fig_training run both backends by design; kernels have no
# simulation engine
_ENGINE_AGNOSTIC = ("fidelity", "fig_multipath", "fig_geo", "fig_training",
                    "kernels")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--scale", default="default",
                    choices=["quick", "default", "full"])
    ap.add_argument("--only", default="",
                    help="comma-separated subset of suites (see --list)")
    ap.add_argument("--engine", default="fluid",
                    choices=["fluid", "packet", "both"],
                    help="simulation backend for the figure grids; 'both' "
                         "runs every selected suite once per engine "
                         "(packet rows are tagged fig*[packet])")
    ap.add_argument("--list", action="store_true",
                    help="print the suite table and exit without running")
    ap.add_argument("--sequential", action="store_true",
                    help="run figure grids cell-by-cell (the pre-sweep "
                         "baseline) instead of the batched sweep engine")
    ap.add_argument("--bench", action="store_true",
                    help="run the wall-clock regression guard instead of "
                         "figure suites: writes benchmarks/out/"
                         "BENCH_netsim.json and soft-warns on rows >1.3x "
                         "the committed baseline (see benchmarks.perf)")
    args = ap.parse_args()

    from repro import compile_cache
    compile_cache.enable()

    if args.bench:
        from benchmarks import perf
        perf.run_bench()
        return

    from benchmarks import figures, kernel_bench

    def kernels(scale, seq, eng):
        """Pallas/jnp kernel microbenchmarks (engine-agnostic)."""
        del scale, seq, eng
        return kernel_bench.all_benches()

    scale, seq = args.scale, args.sequential
    suites = {
        "fig1": figures.fig1_link_utilization,
        "fig5": figures.fig5_testbed_fct,
        "fig6": figures.fig6_fidelity,
        "fig7_8": figures.fig7_8_large_scale,
        "fig9": figures.fig9_workloads,
        "fig10": figures.fig10_cc_orthogonality,
        "fig11": figures.fig11_ablations,
        "failover": figures.failover_bench,
        "fig_large": figures.fig_large,
        "fig_multipath": figures.fig_multipath,
        "fig_geo": figures.fig_geo,
        "fig_training": figures.fig_training,
        "staleness": figures.staleness_ablation,
        "scenarios": figures.scenarios_bench,
        "fidelity": figures.fidelity_bench,
        "kernels": kernels,
    }

    if args.list:
        print(f"{'suite':<10} description")
        for name, fn in suites.items():
            doc = (fn.__doc__ or "").strip().splitlines()
            print(f"{name:<10} {doc[0] if doc else ''}")
        return

    wanted = [s for s in args.only.split(",") if s] or list(suites)
    unknown = sorted(set(wanted) - set(suites))
    if unknown:
        sys.exit(f"error: unknown suite(s): {', '.join(unknown)}\n"
                 f"valid suites: {', '.join(suites)}")

    engines = ["fluid", "packet"] if args.engine == "both" else [args.engine]

    print("name,us_per_call,derived")
    ok = True
    for name in wanted:
        for eng in engines:
            # engine-agnostic suites run exactly once per invocation
            if name in _ENGINE_AGNOSTIC and eng != engines[0]:
                continue
            try:
                for row, us, derived in suites[name](scale, seq, eng):
                    print(f"{row},{us:.0f},{derived}")
                    sys.stdout.flush()
            except Exception:
                ok = False
                traceback.print_exc()
                tag = name if eng == "fluid" else f"{name}[{eng}]"
                print(f"{tag},0,ERROR")
    if not ok:
        sys.exit(1)


if __name__ == "__main__":
    main()

"""Benchmark harness — one entry per paper table/figure.

  python -m benchmarks.run                    # default (CPU-minutes) pass
  python -m benchmarks.run --paper            # full-scale variants (slower)
  python -m benchmarks.run --list             # print benchmark names
  python -m benchmarks.run --only a,b,c       # run a comma-separated subset

Emits CSV to stdout (name,seconds,key=value ...) and JSON artifacts under
experiments/.
"""
import argparse
import time
import traceback


def main(argv=None) -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--paper", action="store_true",
                    help="full-scale variants (W=256 sweeps, full fig3)")
    ap.add_argument("--only", default=None,
                    help="comma-separated subset of benchmark names")
    ap.add_argument("--list", action="store_true",
                    help="print the benchmark names and exit")
    args = ap.parse_args(argv)

    from benchmarks import (bench_admm_vs_sgd, bench_cluster,
                            bench_compression, bench_cost, bench_drf,
                            bench_kernels, bench_load, bench_newton,
                            bench_phases, bench_scale, bench_workloads,
                            fig3_convergence, fig4_speedup,
                            fig67_histograms, fig8_coldstart, roofline)

    jobs = [
        ("kernels", lambda: bench_kernels.main()),
        ("fig8_coldstart", lambda: fig8_coldstart.main()),
        ("fig3_convergence", lambda: fig3_convergence.main(full=args.paper)),
        ("fig4_speedup", lambda: fig4_speedup.main(paper_scale=args.paper)),
        ("fig67_histograms", lambda: fig67_histograms.main(big=args.paper)),
        ("compression", lambda: bench_compression.main()),
        ("bench_cost", lambda: bench_cost.main()),
        ("bench_cluster", lambda: bench_cluster.main()),
        ("bench_drf", lambda: bench_drf.main()),
        ("bench_phases", lambda: bench_phases.main()),
        # the default pass runs the ~1k-job smoke trace; --paper replays
        # the full 10k-job Azure-model trace (minutes, not seconds)
        ("bench_load", lambda: bench_load.main(
            None if args.paper else ["--smoke"])),
        ("bench_workloads", lambda: bench_workloads.main()),
        ("bench_scale", lambda: bench_scale.main()),
        ("admm_vs_sgd", lambda: bench_admm_vs_sgd.main()),
        ("bench_newton", lambda: bench_newton.main()),
        ("roofline", lambda: roofline.main()),
    ]
    names = [name for name, _ in jobs]
    if args.list:
        print("\n".join(names))
        return
    only = None
    if args.only:
        only = [s.strip() for s in args.only.split(",") if s.strip()]
        unknown = sorted(set(only) - set(names))
        if unknown:
            ap.error(f"unknown benchmark(s) {unknown}; choose from {names}")
    from repro import compile_cache
    compile_cache.enable()
    print("name,seconds,status")
    failures = 0
    for name, fn in jobs:
        if only and name not in only:
            continue
        t0 = time.time()
        try:
            print(f"== {name} ==")
            fn()
            print(f"{name},{time.time()-t0:.1f},ok")
        except Exception as e:
            failures += 1
            traceback.print_exc()
            print(f"{name},{time.time()-t0:.1f},FAILED:{type(e).__name__}")
    if failures:
        raise SystemExit(1)


if __name__ == "__main__":
    main()

"""Benchmark orchestrator — one entry per paper table/figure.

Prints a ``name,us_per_call,derived`` CSV line per benchmark (suite-level
timing + the headline derived metric), then the detailed per-benchmark
output above it.

  python -m benchmarks.run            # all
  python -m benchmarks.run fig2 fig4  # subset
"""
from __future__ import annotations

import sys
import time


def main() -> None:
    import benchmarks.bench_ablation as ablation
    import benchmarks.bench_fig2 as fig2
    import benchmarks.bench_hallucination as halluc
    import benchmarks.bench_fig4 as fig4
    import benchmarks.bench_kernels as kernels
    import benchmarks.bench_serve as serve
    import benchmarks.bench_table1 as table1
    import benchmarks.bench_theory as theory
    import benchmarks.roofline as roofline
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()

    suites = {
        "theory": (theory.run, lambda r: "thm4.2-verified"),
        "fig2": (fig2.run, lambda r: f"pareto={r['claims']['pareto']}"),
        "table1": (table1.run,
                   lambda r: f"+{r['claims']['avg_gain_vs_greedy']*100:.1f}pts_vs_greedy"),
        "fig4": (fig4.run, lambda r: f"engine_pareto={r['claims']['engine_pareto']}"),
        "ablation": (ablation.run,
                     lambda r: f"best_lambda={r['best']}"),
        "hallucination": (halluc.run,
                          lambda r: f"-{r['reduction_pts']:.1f}pts_halluc"),
        "kernels": (kernels.run, lambda r: f"{len(r)}kernels"),
        "serve": (serve.run,
                  lambda r: "max_speedup={:.2f}x".format(
                      max(s["speedup"] for s in r["speedups"].values()))),
        "roofline": (roofline.run,
                     lambda r: f"{r.get('summary', {}).get('fits', 0)}/{r.get('summary', {}).get('n', 0)}fit16GB"
                     if r.get("summary") else "no-dryrun-data"),
    }
    want = sys.argv[1:] or list(suites)
    csv = []
    for name in want:
        fn, derive = suites[name]
        print(f"=== {name} ===")
        t0 = time.perf_counter()
        result = fn()
        us = (time.perf_counter() - t0) * 1e6
        csv.append(f"{name},{us:.0f},{derive(result)}")
    print("\nname,us_per_call,derived")
    for line in csv:
        print(line)


if __name__ == "__main__":
    main()

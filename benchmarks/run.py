"""Benchmark orchestrator — one function per paper table/figure.

Prints ``name,us_per_call,derived`` CSV per row.  Run:
    PYTHONPATH=src python -m benchmarks.run [--only fig13,...]
"""

from __future__ import annotations

import argparse
import sys
import time

from repro.compile_cache import enable_compile_cache


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    help="comma-separated prefixes to run (default: all)")
    args = ap.parse_args()

    from . import paper_figures as pf
    from . import serving_checkout as sc
    from . import serving_qps as sq
    from . import solver_scale as ss
    from . import system_benches as sb

    suites = [
        ("solver_scale", ss.solver_scale),
        ("serving_checkout", sc.serving_checkout),
        ("serving_qps", sq.serving_qps),
        ("fig13", pf.fig13_tradeoff_directed),
        ("fig14", pf.fig14_maxrec_directed),
        ("fig15", pf.fig15_undirected),
        ("fig16", pf.fig16_workload_aware),
        ("fig17", pf.fig17_running_times),
        ("tab2", pf.table2_exact_vs_mp),
        ("git_cmp", pf.git_comparison),
        ("scale", pf.scale_trend),
        ("kernel", sb.kernel_throughput),
        ("store", sb.store_roundtrip),
        ("restore", sb.restore_latency_vs_theta),
    ]
    only = set(args.only.split(",")) if args.only else None

    print("name,us_per_call,derived")
    t0 = time.monotonic()
    failures = 0
    for name, fn in suites:
        if only and name not in only:
            continue
        try:
            t1 = time.monotonic()
            for row in fn():
                print(row.csv())
            print(f"# suite {name} done in {time.monotonic()-t1:.1f}s",
                  file=sys.stderr)
        except Exception as e:  # pragma: no cover
            failures += 1
            print(f"# suite {name} FAILED: {type(e).__name__}: {e}",
                  file=sys.stderr)
    print(f"# total {time.monotonic()-t0:.1f}s, {failures} suite failures",
          file=sys.stderr)
    sys.exit(1 if failures else 0)


if __name__ == "__main__":
    enable_compile_cache()
    main()

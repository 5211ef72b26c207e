"""Benchmark aggregator: one module per paper table/figure.

  table3        Table 3  — MC vs GE vs ScaLAPACK(bs=1) wall times
  fig7_8        Fig 7/8  — speedups (measured + cluster-modeled)
  fig9_comm     Fig 9    — distribution time + collective traffic
  kernels       (ours)   — kernel roofline projections
  estimators    (ours)   — exact vs stochastic logdet: time + rel error by N
  roofline      (ours)   — 40-cell dry-run roofline table (if results exist)

``python -m benchmarks.run [--quick|--full]`` prints CSV lines per bench.
"""
from __future__ import annotations

import argparse
import sys
import traceback


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="smallest sizes (CI)")
    ap.add_argument("--full", action="store_true",
                    help="paper-size grid (hours on 1 core)")
    ap.add_argument("--only", default="",
                    help="comma list: table3,fig7_8,fig9,kernels,"
                         "estimators,roofline")
    args = ap.parse_args(argv)
    from repro.compile_cache import use_compile_cache
    use_compile_cache()
    only = set(args.only.split(",")) if args.only else None

    def want(name):
        return only is None or name in only

    failures = []

    if want("table3"):
        try:
            from benchmarks import table3
            if args.full:
                table3.main(["--full"])
            elif args.quick:
                table3.main(["--sizes", "128,256", "--procs", "1,2"])
            else:
                table3.main([])
        except Exception:
            failures.append("table3")
            traceback.print_exc()

    if want("fig7_8"):
        try:
            from benchmarks import fig7_8
            fig7_8.main([])
        except Exception:
            failures.append("fig7_8")
            traceback.print_exc()

    if want("fig9"):
        try:
            from benchmarks import fig9_comm
            fig9_comm.main(["--n", "128" if args.quick else "256",
                            "--procs", "2,4" if args.quick else "4,8"])
        except Exception:
            failures.append("fig9")
            traceback.print_exc()

    if want("kernels"):
        try:
            from benchmarks import kernels_bench
            kernels_bench.main(["--m", "512" if args.quick else "1024"])
        except Exception:
            failures.append("kernels")
            traceback.print_exc()

    if want("estimators"):
        try:
            from benchmarks import estimators_bench
            if args.full:
                estimators_bench.main(["--full"])
            elif args.quick:
                estimators_bench.main(["--sizes", "256,512", "--iters", "2"])
            else:
                estimators_bench.main([])
        except Exception:
            failures.append("estimators")
            traceback.print_exc()

    if want("roofline"):
        try:
            from benchmarks import roofline
            roofline.main([])
        except Exception:
            failures.append("roofline")
            traceback.print_exc()

    if failures:
        print(f"\nbenchmark FAILURES: {failures}")
        sys.exit(1)
    print("\nall benchmarks completed")


if __name__ == "__main__":
    main()

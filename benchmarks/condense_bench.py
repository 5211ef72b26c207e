"""Exact-path timing: the condensation engine's route matrix by N.

Times every serial engine route (schedule x update), the fused one-pass
variants of the staged routes, and the GE baseline at the gated sizes,
recording median wall seconds and the relative error against
``numpy.linalg.slogdet``.  Records go to ``bench_out/condense.json`` as

    {"n": ..., "route": "staged|rank1", "seconds": ..., "rel_err": ...,
     "pass": "fwd"}

(fused routes spell as ``staged|panel|fused``) and are gated by
``benchmarks.check_regression`` against the committed
``bench_out/condense_baseline.json`` exactly like the estimator records
(2x time + slack, 3x rel_err + floor; the GE rows are the runner-speed
probe), plus the headline fused acceptance — at N=1024 the fused
staged|panel route must beat the committed unfused staged|panel
baseline by >= 1.3x.  rank-1 routes are skipped above N=512 (O(n)
dispatches per step; they gate nothing the staged rows don't).
Refresh after a legitimate perf change:

    PYTHONPATH=src python -m benchmarks.condense_bench --sizes 256,512,1024
    cp bench_out/condense.json bench_out/condense_baseline.json
"""
from __future__ import annotations

import argparse
import json
from pathlib import Path

import numpy as np

from benchmarks._common import OUT_DIR, timeit, write_csv

DEFAULT_SIZES = (256, 512, 1024)
SERIAL_ROUTES = [("serial", "rank1"), ("serial", "panel"),
                 ("staged", "rank1"), ("staged", "panel")]
# fused one-pass variants of the staged routes (the production fused
# path); check_regression enforces the staged|panel|fused speedup floor
# against the committed staged|panel baseline at N=1024
FUSED_ROUTES = [("staged", "rank1"), ("staged", "panel")]
# rank-1 serial routes are O(n) separate device dispatches per step:
# past this size they dominate bench wall time without gating anything
# the staged rows don't already cover
SLOW_ROUTE_MAX_N = 512


def route_name(schedule: str, update: str, fused: bool = False) -> str:
    return f"{schedule}|{update}" + ("|fused" if fused else "")


def main(argv=None):
    import jax
    jax.config.update("jax_enable_x64", True)
    import jax.numpy as jnp

    from repro.core import pad_to_multiple, slogdet_ge
    from repro.core.engine import EngineConfig, build_serial

    ap = argparse.ArgumentParser()
    ap.add_argument("--sizes", default=",".join(map(str, DEFAULT_SIZES)))
    ap.add_argument("--iters", type=int, default=3)
    ap.add_argument("--k", type=int, default=32)
    ap.add_argument("--out", default=str(OUT_DIR / "condense.json"))
    args = ap.parse_args(argv)
    from repro.compile_cache import use_compile_cache
    use_compile_cache()
    sizes = tuple(int(s) for s in args.sizes.split(","))

    rng = np.random.default_rng(0)
    records = []
    for n in sizes:
        a_np = rng.standard_normal((n, n))
        ref = np.linalg.slogdet(a_np)[1]
        a = jnp.asarray(a_np)
        runs = []
        for schedule, update in SERIAL_ROUTES:
            if update == "rank1" and n > SLOW_ROUTE_MAX_N:
                continue
            cfg = EngineConfig(schedule=schedule, update=update,
                               panel_k=args.k)
            fn = build_serial(cfg)
            x = pad_to_multiple(a, args.k) if update == "panel" else a
            runs.append((route_name(schedule, update), fn, x))
        for schedule, update in FUSED_ROUTES:
            if update == "rank1" and n > SLOW_ROUTE_MAX_N:
                continue
            cfg = EngineConfig(schedule=schedule, update=update,
                               panel_k=args.k, fused=True)
            fn = build_serial(cfg)
            x = pad_to_multiple(a, args.k) if update == "panel" else a
            runs.append((route_name(schedule, update, fused=True), fn, x))
        runs.append(("ge", slogdet_ge, a))
        for name, fn, x in runs:
            t = timeit(fn, x, iters=args.iters)
            ld = float(fn(x)[1])
            rel = abs(ld - ref) / max(abs(ref), 1e-30)
            records.append({"n": n, "route": name, "seconds": t,
                            "rel_err": rel, "pass": "fwd"})
            print(f"condense n={n:5d} {name:14s} {t:8.4f}s "
                  f"rel_err={rel:.2e}")

    OUT_DIR.mkdir(exist_ok=True)
    out = Path(args.out)
    out.write_text(json.dumps(records, indent=1) + "\n")
    write_csv("condense.csv", ["n", "route", "seconds", "rel_err"],
              [[r["n"], r["route"], f"{r['seconds']:.5f}",
                f"{r['rel_err']:.3e}"] for r in records])
    print(f"condense -> {out}")
    return records


if __name__ == "__main__":
    main()

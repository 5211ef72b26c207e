"""Serving-path benchmark: warm bucketed batching vs one-request-at-a-time.

Drives one synthetic mixed-size workload (``--requests`` square matrices
with N drawn uniformly from ``--n-lo``..``--n-hi``; nearly every request
is a novel shape, as real mixed traffic is) through three serving modes:

  naive     what a user gets today: ``repro.plan((n, n))`` per request,
            one at a time.  The process-level plan cache is on (repeat
            shapes are free), but every *novel* shape pays its trace +
            compile inside the timed region — that is the cost the
            serving path exists to remove.
  bucketed  `LogdetService` with ``max_batch=1``: pad-to-bucket through
            warm executables, no batching.  Isolates what bucketing
            alone buys.
  batched   the full service: pad-to-bucket + continuous batching
            (``--max-batch``).  All requests are submitted open-loop and
            drained through the warm batch executables.

Service warmup (compiling the bucket x batch ladder) happens *before*
the timed region and is reported separately as ``warmup_s`` — a serving
process pays it once at startup, or never when ``--plan-dir`` points at
AOT artifacts from ``python -m repro.serve export``.

Per mode, the record carries throughput (requests/s), p50/p99 request
latency (submit -> result, saturated open-loop for the service modes),
max relative error vs ``numpy.linalg.slogdet``, and ``request_traces`` —
executable traces that happened during the timed region (the service
modes must report 0; `check_regression` fails otherwise and also gates
``batched >= 3x naive`` throughput, ratio-based so any machine can run
it).

JSON schema (``bench_out/serve.json``): a list of records, one per mode,
with the shared workload fields inlined::

    {"bench": "serve", "mode": "batched", "requests": 40,
     "n_lo": 64, "n_hi": 512, "unique_shapes": 38, "method": "exact",
     "seconds": ..., "throughput_rps": ..., "p50_ms": ..., "p99_ms": ...,
     "warmup_s": ..., "request_traces": 0, "rel_err_max": ...}

Refresh the committed baseline after a legitimate serving-path change::

    PYTHONPATH=src python -m benchmarks.serve_bench
    cp bench_out/serve.json bench_out/serve_baseline.json
"""
from __future__ import annotations

import argparse
import json
import time

import numpy as np

from benchmarks._common import OUT_DIR


def make_workload(requests: int, n_lo: int, n_hi: int, seed: int):
    """(matrices, reference logabsdets) — well-conditioned mixed sizes."""
    rng = np.random.default_rng(seed)
    sizes = rng.integers(n_lo, n_hi + 1, requests)
    mats, refs = [], []
    for n in sizes:
        # diagonally dominant: safely nonsingular at every size
        a = rng.standard_normal((n, n)) + np.eye(n) * (2.0 * np.sqrt(n))
        mats.append(a)
        refs.append(np.linalg.slogdet(a)[1])
    return mats, np.asarray(refs)


def _quantile_ms(lat_s, q: float) -> float:
    return float(np.quantile(np.asarray(lat_s), q) * 1e3)


def run_naive(mats, refs, method: str) -> dict:
    """One plan call per request, sequential — today's baseline path."""
    import repro
    from repro.core.plan import clear_plan_cache

    clear_plan_cache()
    lat, errs = [], []
    t0 = time.perf_counter()
    for a, ref in zip(mats, refs):
        t1 = time.perf_counter()
        p = repro.plan(a.shape, method=method, precision="float64",
                       validate=False)
        r = p(a)
        ld = float(r.logabsdet)
        lat.append(time.perf_counter() - t1)
        errs.append(abs(ld - ref) / max(abs(ref), 1.0))
    seconds = time.perf_counter() - t0
    return {"mode": "naive", "seconds": seconds,
            "throughput_rps": len(mats) / seconds,
            "p50_ms": _quantile_ms(lat, 0.5),
            "p99_ms": _quantile_ms(lat, 0.99),
            "warmup_s": 0.0, "request_traces": None,
            "rel_err_max": float(max(errs))}


def run_service(mats, refs, method: str, *, mode: str, buckets,
                max_batch: int, plan_dir=None) -> dict:
    """Submit the whole workload open-loop through a LogdetService."""
    from repro.serve import LogdetService, ServeConfig

    cfg = ServeConfig(buckets=buckets, max_batch=max_batch,
                      max_wait_ms=2.0, cache_capacity=128,
                      plan_dir=plan_dir, default_method=method)
    with LogdetService(cfg) as svc:
        warmup_s = svc.warmup()
        traces0 = svc.trace_count()
        t0 = time.perf_counter()
        futs = [svc.submit(a) for a in mats]
        done = [(f.result(timeout=600), time.perf_counter())
                for f in futs]
        seconds = time.perf_counter() - t0
        traces = svc.trace_count() - traces0
        errs = [abs(float(r.logabsdet) - ref) / max(abs(ref), 1.0)
                for (r, _), ref in zip(done, refs)]
        lat = [t_done - t0 for _, t_done in done]
    return {"mode": mode, "seconds": seconds,
            "throughput_rps": len(mats) / seconds,
            "p50_ms": _quantile_ms(lat, 0.5),
            "p99_ms": _quantile_ms(lat, 0.99),
            "warmup_s": warmup_s, "request_traces": traces,
            "rel_err_max": float(max(errs))}


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n", 1)[0])
    ap.add_argument("--requests", type=int, default=40)
    ap.add_argument("--n-lo", type=int, default=64)
    ap.add_argument("--n-hi", type=int, default=512)
    ap.add_argument("--method", default="exact")
    ap.add_argument("--buckets", default="64,128,192,256,384,512",
                    help="service bucket ladder (comma-separated)")
    ap.add_argument("--max-batch", type=int, default=8)
    ap.add_argument("--plan-dir", default=None,
                    help="AOT artifact dir (python -m repro.serve export)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--modes", default="naive,bucketed,batched")
    ap.add_argument("--out", default=str(OUT_DIR / "serve.json"))
    args = ap.parse_args(argv)
    from repro.compile_cache import use_compile_cache
    use_compile_cache()

    import jax
    jax.config.update("jax_enable_x64", True)

    buckets = tuple(int(b) for b in args.buckets.split(","))
    if max(b for b in buckets) < args.n_hi:
        ap.error(f"bucket ladder tops out at {max(buckets)} < "
                 f"--n-hi {args.n_hi}")
    mats, refs = make_workload(args.requests, args.n_lo, args.n_hi,
                               args.seed)
    shared = {"bench": "serve", "requests": args.requests,
              "n_lo": args.n_lo, "n_hi": args.n_hi,
              "unique_shapes": len({a.shape for a in mats}),
              "method": args.method, "max_batch": args.max_batch}
    print(f"workload: {args.requests} requests, "
          f"{shared['unique_shapes']} unique shapes in "
          f"[{args.n_lo}, {args.n_hi}], method={args.method}")

    records = []
    for mode in args.modes.split(","):
        if mode == "naive":
            rec = run_naive(mats, refs, args.method)
        elif mode == "bucketed":
            rec = run_service(mats, refs, args.method, mode="bucketed",
                              buckets=buckets, max_batch=1,
                              plan_dir=args.plan_dir)
        elif mode == "batched":
            rec = run_service(mats, refs, args.method, mode="batched",
                              buckets=buckets, max_batch=args.max_batch,
                              plan_dir=args.plan_dir)
        else:
            ap.error(f"unknown mode {mode!r}")
        rec = {**shared, **rec}
        records.append(rec)
        print(f"{mode:9s} {rec['throughput_rps']:8.2f} req/s  "
              f"p50={rec['p50_ms']:8.1f}ms p99={rec['p99_ms']:8.1f}ms  "
              f"warmup={rec['warmup_s']:5.1f}s  "
              f"traces={rec['request_traces']}  "
              f"rel_err={rec['rel_err_max']:.2e}")

    by_mode = {r["mode"]: r for r in records}
    if "naive" in by_mode and "batched" in by_mode:
        speedup = (by_mode["batched"]["throughput_rps"]
                   / by_mode["naive"]["throughput_rps"])
        print(f"batched vs naive throughput: x{speedup:.1f}")

    OUT_DIR.mkdir(exist_ok=True)
    with open(args.out, "w") as f:
        json.dump(records, f, indent=1)
    print(f"wrote {args.out}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())

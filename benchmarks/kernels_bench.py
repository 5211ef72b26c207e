"""Kernel-level benchmark: the condensation hot loop, unfused vs fused.

Two legs:

**Roofline micro-bench** (``bench_out/kernels.csv``) — the raw update
kernels.  On CPU the Pallas kernels run in interpret mode (correctness,
not speed), so speed here is (a) the XLA-fused jnp path wall-time, and
(b) the TPU projection from the kernel's exact byte/FLOP counts at v5e
roofline:
    rank-1:  (2*M*N flops, ~3*M*N*dtype bytes)  -> HBM-bound
    rank-K:  (2*M*N*K flops, ~(2*M*N + M*K + K*N)*dtype bytes) -> MXU-bound

**Fused-variant records** (``bench_out/kernels.json``) — the fused
kernels through their real call sites: the condensation engine (fused
one-pass steps vs the pivot/swap/update sequence, plus the bf16
mixed-precision route), the dense Chebyshev recurrence, and the dense
CG solve.  Each record is

    {"n": ..., "kernel": "condense|cheb|cg",
     "variant": "unfused|fused|fused_bf16",
     "seconds": ..., "rel_err": ...}

``rel_err`` is against the unfused full-precision leg of the SAME fresh
run: f32 fused variants must report 0.0 (bit-identical — the tests
assert it, this file records it); the bf16 variant must stay under the
documented error-model ceiling.  ``benchmarks.check_regression
--kernels`` gates these records (fused throughput floor vs the unfused
leg — a within-run ratio, no machine calibration — bf16 rel_err
ceiling, and absolute seconds vs the committed
``bench_out/kernels_baseline.json`` with unfused rows as the
runner-speed probe).  Refresh after a legitimate perf change:

    PYTHONPATH=src python -m benchmarks.kernels_bench
    cp bench_out/kernels.json bench_out/kernels_baseline.json
"""
from __future__ import annotations

import argparse
import json
from pathlib import Path

import numpy as np

from benchmarks._common import OUT_DIR, timeit, write_csv

HBM = 819e9
PEAK = 197e12

DEFAULT_SIZES = (256, 512)
CHEB_DEGREE = 32
CHEB_PROBES = 16
CG_RHS = 16


def _rel(got: float, want: float) -> float:
    return abs(got - want) / max(abs(want), 1e-30)


def roofline(m: int):
    """The original micro-bench: raw update kernels + TPU projections."""
    import jax
    import jax.numpy as jnp
    from repro.kernels import ref

    n = m
    rng = np.random.default_rng(0)
    a = jnp.asarray(rng.standard_normal((m, n)), jnp.float32)
    pc = jnp.asarray(rng.standard_normal((m,)), jnp.float32)
    pr = jnp.asarray(rng.standard_normal((n,)), jnp.float32)

    rows = []
    f1 = jax.jit(ref.rank1_update_ref)
    t = timeit(f1, a, pc, pr)
    proj = 3 * m * n * 4 / HBM
    rows.append(["rank1_update", m, n, 0, f"{t*1e6:.0f}",
                 f"{proj*1e6:.1f}", f"{2*m*n/proj/1e12:.2f}"])

    for k in (32, 128, 256):
        c = jnp.asarray(rng.standard_normal((m, k)), jnp.float32)
        r = jnp.asarray(rng.standard_normal((k, n)), jnp.float32)
        fk = jax.jit(ref.panel_update_ref)
        t = timeit(fk, a, c, r)
        flops = 2 * m * n * k
        bytes_ = (2 * m * n + m * k + k * n) * 4
        proj = max(flops / PEAK, bytes_ / HBM)
        rows.append(["panel_update", m, n, k, f"{t*1e6:.0f}",
                     f"{proj*1e6:.1f}", f"{flops/proj/1e12:.2f}"])

    path = write_csv("kernels.csv",
                     ["kernel", "M", "N", "K", "cpu_us", "tpu_proj_us",
                      "tpu_proj_tflops"], rows)
    for r in rows:
        print("kernel", *r, sep=",")
    print(f"kernels roofline -> {path}")
    return rows


def fused_records(sizes, iters: int, panel_k: int):
    """Fused-vs-unfused timings through the production call sites."""
    import jax
    import jax.numpy as jnp

    from repro.core import pad_to_multiple
    from repro.core.engine import EngineConfig, build_serial
    from repro.estimators.chebyshev import logdet_chebyshev
    from repro.estimators.operators import DenseOperator, cg_solve

    # a thin operator wrapper that dodges the DenseOperator isinstance
    # gate — the estimators' unfused loop bodies, same matvec cost
    class _Unfused:
        def __init__(self, a):
            self.a, self.shape, self.dtype = a, a.shape, a.dtype

        def mm(self, v):
            return self.a @ v

        mv = mm

        def diag(self):
            return jnp.diagonal(self.a)

        def trace_hint(self):
            return jnp.trace(self.a)

    rng = np.random.default_rng(0)
    records = []
    for n in sizes:
        a_np = rng.standard_normal((n, n))
        spd_np = (a_np @ a_np.T / n + 2.0 * np.eye(n)).astype(np.float32)

        # ---- condensation engine: unfused vs fused vs fused+bf16 ----
        a = pad_to_multiple(jnp.asarray(a_np, jnp.float64), panel_k)
        legs = [
            ("unfused", EngineConfig(schedule="staged", update="panel",
                                     panel_k=panel_k)),
            ("fused", EngineConfig(schedule="staged", update="panel",
                                   panel_k=panel_k, fused=True)),
            ("fused_bf16", EngineConfig(schedule="staged", update="panel",
                                        panel_k=panel_k, fused=True,
                                        precision="bf16")),
        ]
        base_ld = None
        for variant, cfg in legs:
            fn = build_serial(cfg)
            t = timeit(fn, a, iters=iters)
            ld = float(fn(a)[1])
            if base_ld is None:
                base_ld = ld
            records.append({"n": n, "kernel": "condense",
                            "variant": variant, "seconds": t,
                            "rel_err": _rel(ld, base_ld)})
            print(f"kernels n={n:5d} condense/{variant:10s} {t:8.4f}s "
                  f"rel_err={_rel(ld, base_ld):.2e}")

        # ---- Chebyshev three-term recurrence: fused vs operator path ----
        spd = jnp.asarray(spd_np)
        kw = dict(degree=CHEB_DEGREE, num_probes=CHEB_PROBES, seed=1)
        base_est = None
        for variant in ("unfused", "fused"):
            op = _Unfused(spd) if variant == "unfused" else spd

            def fn(x, o=op):
                return logdet_chebyshev(o, **kw).est
            t = timeit(fn, spd, iters=iters)
            est = float(fn(spd))
            if base_est is None:
                base_est = est
            records.append({"n": n, "kernel": "cheb", "variant": variant,
                            "seconds": t,
                            "rel_err": _rel(est, base_est)})
            print(f"kernels n={n:5d} cheb/{variant:14s} {t:8.4f}s "
                  f"rel_err={_rel(est, base_est):.2e}")

        # ---- CG matvec+axpy+dot chain: fused vs operator path ----
        b = jnp.asarray(rng.standard_normal((n, CG_RHS)), jnp.float32)
        base_x = None
        for variant in ("unfused", "fused"):
            op = _Unfused(spd) if variant == "unfused" \
                else DenseOperator(spd)

            def fn(bb, o=op):
                return cg_solve(o, bb, tol=1e-6).x
            t = timeit(fn, b, iters=iters)
            x = np.asarray(fn(b))
            if base_x is None:
                base_x = x
            rel = float(np.abs(x - base_x).max()
                        / max(np.abs(base_x).max(), 1e-30))
            records.append({"n": n, "kernel": "cg", "variant": variant,
                            "seconds": t, "rel_err": rel})
            print(f"kernels n={n:5d} cg/{variant:16s} {t:8.4f}s "
                  f"rel_err={rel:.2e}")
    return records


def main(argv=None):
    import jax
    jax.config.update("jax_enable_x64", True)

    ap = argparse.ArgumentParser()
    ap.add_argument("--m", type=int, default=2048,
                    help="roofline micro-bench square size")
    ap.add_argument("--n", type=int, default=2048)
    ap.add_argument("--sizes", default=",".join(map(str, DEFAULT_SIZES)),
                    help="fused-variant record sizes")
    ap.add_argument("--iters", type=int, default=3)
    ap.add_argument("--k", type=int, default=32)
    ap.add_argument("--out", default=str(OUT_DIR / "kernels.json"))
    ap.add_argument("--skip-roofline", action="store_true")
    args = ap.parse_args(argv)
    from repro.compile_cache import use_compile_cache
    use_compile_cache()

    rows = [] if args.skip_roofline else roofline(args.m)

    sizes = tuple(int(s) for s in args.sizes.split(","))
    records = fused_records(sizes, args.iters, args.k)
    OUT_DIR.mkdir(exist_ok=True)
    out = Path(args.out)
    out.write_text(json.dumps(records, indent=1) + "\n")
    print(f"kernels fused records -> {out}")
    return rows + records


if __name__ == "__main__":
    main()

"""Exact vs stochastic log-determinant: wall time and relative error by N,
across operator structures.

For each size the harness builds a seeded well-conditioned SPD input —
dense, or one of the structured implicit backends — computes an exact
reference logdet, then times every requested method (median of --iters
after a compile warm-up) and records the relative error.  Structures:

  dense      in-memory (n, n) matrix; all methods apply
  kron       KroneckerOperator(A, B) with nA ~ nB ~ sqrt(n); reference is
             the exact identity nB*logdet(A) + nA*logdet(B)
  toeplitz   SPD ToeplitzOperator from a geometrically decaying symbol
  stencil    1-D Laplacian-style StencilOperator (offsets -1/0/+1)

Exact condensation methods need a materialized matrix, so structured runs
cover the estimator methods only (others are skipped with a note).
Results go to bench_out/estimators.json as a list of records

    {"n": ..., "method": ..., "operator": ..., "pass": "fwd"|"grad",
     "seconds": ..., "logdet": ..., "rel_err": ..., "sem": ...}

plus a CSV twin for the roofline tooling.  Defaults stay CPU-friendly
(N up to 2048); --full sweeps the paper-scale range N in {512..8192} where
the O(N^3)-vs-O(N^2 * probes) crossover is unmistakable.

``--grad`` adds a forward+backward axis: each method is re-timed as
``jit(value_and_grad(logdet))`` — exact methods pay one dense inverse in
the backward pass, estimator methods one batched CG solve on the forward's
probes, and structured operators differentiate with respect to their own
parameters (Kronecker factors / Toeplitz symbol / stencil bands).  The
``pass`` field keys the regression gate (benchmarks/check_regression.py)
so backward-pass time is gated exactly like forward.

``--methods`` may include ``auto``: those rows go through ``repro.plan(a,
method="auto")`` and carry a ``method_used`` field recording what the
cost model picked — sweep ``--full --methods auto,mc_staged,slq`` to see
the selector flip from exact condensation to estimators at the dense
crossover (n ~ 2400 per device at default budgets) and stay on
estimators for every structured operator.

    PYTHONPATH=src python -m benchmarks.estimators_bench
    PYTHONPATH=src python -m benchmarks.estimators_bench --operator kron \
        --methods chebyshev,slq
    PYTHONPATH=src python -m benchmarks.estimators_bench --full \
        --methods auto,mc_staged,chebyshev,slq
    PYTHONPATH=src python -m benchmarks.estimators_bench --grad
"""
from __future__ import annotations

import argparse
import json

import numpy as np

from benchmarks._common import OUT_DIR, timeit, write_csv

DEFAULT_SIZES = (512, 1024, 2048)
FULL_SIZES = (512, 1024, 2048, 4096, 8192)
EXACT = {"mc", "mc_staged", "mc_blocked", "ge"}
OPERATORS = ("dense", "kron", "toeplitz", "stencil")


def make_spd(n: int, seed: int) -> np.ndarray:
    rng = np.random.default_rng(seed)
    x = rng.standard_normal((n, 2 * n))
    return x @ x.T / (2 * n) + 2.0 * np.eye(n)


def make_operator(structure: str, n: int, seed: int):
    """(operator_or_matrix, exact_reference_logdet, actual_n)."""
    import jax.numpy as jnp

    from repro.estimators import (
        KroneckerOperator, StencilOperator, ToeplitzOperator,
    )

    if structure == "dense":
        a = make_spd(n, seed)
        return jnp.asarray(a), float(np.linalg.slogdet(a)[1]), n
    if structure == "kron":
        na = max(int(round(np.sqrt(n))), 1)
        a, b = make_spd(na, seed), make_spd(na, seed + 1)
        # logdet(A (x) B) = nB logdet(A) + nA logdet(B): exact, no n x n
        ref = na * float(np.linalg.slogdet(a)[1]) \
            + na * float(np.linalg.slogdet(b)[1])
        return KroneckerOperator(jnp.asarray(a), jnp.asarray(b)), ref, na * na
    if structure == "toeplitz":
        c = 0.5 ** np.arange(n, dtype=np.float64)
        c[0] = 2.5                       # diagonally dominant -> SPD
        i = np.arange(n)
        dense = c[np.abs(i[:, None] - i[None, :])]
        ref = float(np.linalg.slogdet(dense)[1])
        return ToeplitzOperator(jnp.asarray(c)), ref, n
    if structure == "stencil":
        # 1-D Laplacian + shift: SPD tridiagonal
        dense = 2.5 * np.eye(n) - np.eye(n, k=1) - np.eye(n, k=-1)
        ref = float(np.linalg.slogdet(dense)[1])
        op = StencilOperator((-1, 0, 1),
                             jnp.asarray([-1.0, 2.5, -1.0]), n=n)
        return op, ref, n
    raise ValueError(f"unknown operator structure {structure!r}; "
                     f"choose from {OPERATORS}")


def grad_target(structure, a, plan_):
    """(scalar_fn, params) for jax.value_and_grad on this structure.

    Dense inputs differentiate with respect to the matrix entries;
    structured operators with respect to their own parameters, rebuilt
    inside the traced function so the structured pullback engages.  The
    plan is compiled once outside the traced function — only execution is
    timed/traced.
    """
    from repro.estimators import (
        KroneckerOperator, StencilOperator, ToeplitzOperator,
    )

    if structure == "dense":
        return (lambda p: plan_.logdet(p)), a
    if structure == "kron":
        return (lambda p: plan_.logdet(KroneckerOperator(p[0], p[1]))), \
            (a.a, a.b)
    if structure == "toeplitz":
        return (lambda p: plan_.logdet(ToeplitzOperator(p))), a.c
    if structure == "stencil":
        offsets = a.offsets
        return (lambda p: plan_.logdet(StencilOperator(offsets, p))), a.bands
    raise ValueError(structure)


def _bench_auto(a, ld_ref, n_actual, structure, args):
    """Time the auto-selector's pick for this (n, structure) cell.

    The interesting number is WHERE the cost model flips from exact
    condensation to estimators (dense: near n ~ 2400 per device at default
    budgets; structured operators: estimators at any n) — the emitted rows
    carry ``method_used`` so the crossover is visible in the JSON/CSV, and
    the plan is built once so the timings measure execution only.
    """
    import jax
    import repro

    p = repro.plan(a, method="auto", validate=False)
    res = p(a)
    t = timeit(lambda x: p.slogdet(x)[1], a, warmup=1, iters=args.iters)
    rec = {"n": n_actual, "method": "auto", "method_used": p.method,
           "operator": structure, "pass": "fwd", "seconds": t,
           "logdet_ref": ld_ref, "logdet": float(res.logabsdet),
           "rel_err": abs(float(res.logabsdet) - ld_ref) / abs(ld_ref)}
    if p.method in ("chebyshev", "slq"):
        rec["probes"] = int(p.config.num_probes)
    if p.compiled:
        # warm plan after the timed loop: anything beyond the first trace
        # is a retrace (gated to 0 by check_regression)
        rec["retraces"] = p.trace_count - 1
    if res.sem is not None and float(res.sem) > 0:
        rec["sem"] = float(res.sem)
    out = [rec]
    print(f"n={n_actual:5d} {structure:>8s} {'auto':>10s} "
          f" fwd: {t*1e3:9.1f} ms  rel_err={rec['rel_err']:.2e}  "
          f"-> {p.method}")
    if args.grad:
        tg = timeit(lambda x: jax.block_until_ready(p.value_and_grad(x)[1]),
                    a, warmup=1, iters=args.iters)
        resg, _ = p.value_and_grad(a)
        out.append({"n": n_actual, "method": "auto", "method_used": p.method,
                    "operator": structure, "pass": "grad", "seconds": tg,
                    "logdet_ref": ld_ref, "logdet": float(resg.logabsdet),
                    "rel_err": abs(float(resg.logabsdet) - ld_ref)
                    / abs(ld_ref)})
        print(f"n={n_actual:5d} {structure:>8s} {'auto':>10s} "
              f"grad: {tg*1e3:9.1f} ms  rel_err={out[-1]['rel_err']:.2e}  "
              f"-> {p.method}")
    return out


def main(argv=None):
    import jax
    jax.config.update("jax_enable_x64", True)
    import jax.numpy as jnp  # noqa: F401  (x64 must be set before use)

    import repro

    ap = argparse.ArgumentParser()
    ap.add_argument("--sizes", type=str, default="")
    ap.add_argument("--full", action="store_true",
                    help="sweep the paper-scale range 512..8192")
    ap.add_argument("--methods", type=str,
                    default="mc_staged,chebyshev,slq")
    ap.add_argument("--operator", type=str, default="dense",
                    help="comma list of input structures: "
                         "dense|kron|toeplitz|stencil (or 'all')")
    ap.add_argument("--num-probes", type=int, default=32)
    ap.add_argument("--degree", type=int, default=64)
    ap.add_argument("--num-steps", type=int, default=25)
    ap.add_argument("--iters", type=int, default=3)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--grad", action="store_true",
                    help="also time forward+backward via "
                         "jit(value_and_grad(logdet)) per method")
    args = ap.parse_args(argv)
    from repro.compile_cache import use_compile_cache
    use_compile_cache()

    if args.sizes:
        sizes = tuple(int(s) for s in args.sizes.split(","))
    else:
        sizes = FULL_SIZES if args.full else DEFAULT_SIZES
    methods = args.methods.split(",")
    structures = (OPERATORS if args.operator == "all"
                  else tuple(args.operator.split(",")))

    records = []
    for structure in structures:
        for n in sizes:
            a, ld_ref, n_actual = make_operator(structure, n, args.seed)

            for method in methods:
                if method == "auto":
                    records.extend(_bench_auto(a, ld_ref, n_actual,
                                               structure, args))
                    continue
                if structure != "dense" and method not in ("chebyshev",
                                                           "slq"):
                    print(f"n={n:5d} {method:>10s}: skipped (needs a "
                          f"materialized matrix, operator={structure})")
                    continue
                kw = {}
                if method == "chebyshev":
                    kw = dict(num_probes=args.num_probes, degree=args.degree,
                              seed=args.seed)
                elif method == "slq":
                    kw = dict(num_probes=args.num_probes,
                              num_steps=args.num_steps, seed=args.seed)

                # compile once; the timed loop executes the plan only
                p_method = repro.plan(a, method=method, validate=False, **kw)

                def run(x):
                    return p_method.slogdet(x)

                t = timeit(run, a, warmup=1, iters=args.iters)
                rec = {"n": n_actual, "method": method,
                       "operator": structure, "pass": "fwd", "seconds": t,
                       "logdet_ref": ld_ref}
                if method in EXACT:
                    _, ld = run(a)
                else:
                    # one unified-result pass yields value + standard error
                    res = p_method(a)
                    ld = res.logabsdet
                    rec["sem"] = float(res.sem)
                    rec["probes"] = int(p_method.config.num_probes)
                if p_method.compiled:
                    # warm plan: retraces beyond the first compile are a
                    # regression (check_regression gates this at 0)
                    rec["retraces"] = p_method.trace_count - 1
                rec["logdet"] = float(ld)
                rec["rel_err"] = abs(float(ld) - ld_ref) / abs(ld_ref)
                records.append(rec)
                print(f"n={n_actual:5d} {structure:>8s} {method:>10s} "
                      f" fwd: {t*1e3:9.1f} ms  rel_err={rec['rel_err']:.2e}")

                if not args.grad:
                    continue
                fn, params = grad_target(structure, a, p_method)
                vg = jax.jit(jax.value_and_grad(fn))
                tg = timeit(vg, params, warmup=1, iters=args.iters)
                val, _ = vg(params)
                grec = {"n": n_actual, "method": method,
                        "operator": structure, "pass": "grad",
                        "seconds": tg, "logdet_ref": ld_ref,
                        "logdet": float(val),
                        "rel_err": abs(float(val) - ld_ref) / abs(ld_ref)}
                records.append(grec)
                # NOTE: grad rows are jit(value_and_grad) end to end, while
                # fwd rows time the public eager call — grad can come out
                # FASTER at small N where eager dispatch dominates; compare
                # grad rows against grad rows (the gate keys on `pass`).
                print(f"n={n_actual:5d} {structure:>8s} {method:>10s} "
                      f"grad: {tg*1e3:9.1f} ms  rel_err={grec['rel_err']:.2e}")

    OUT_DIR.mkdir(exist_ok=True)
    out = OUT_DIR / "estimators.json"
    out.write_text(json.dumps(records, indent=2))
    write_csv("estimators.csv",
              ["n", "method", "method_used", "operator", "pass", "seconds",
               "logdet", "rel_err"],
              [[r["n"], r["method"], r.get("method_used", r["method"]),
                r["operator"], r["pass"],
                f"{r['seconds']:.6f}", f"{r['logdet']:.6f}",
                f"{r['rel_err']:.3e}"]
               for r in records])
    print(f"estimators -> {out}")
    return records


if __name__ == "__main__":
    main()

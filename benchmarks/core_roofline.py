"""Roofline analysis of the logdet CORE on the production mesh (§Perf P0).

Terms are ANALYTIC — the kernels are simple enough to count exactly (the
rank-1 step is literally one fused outer-product subtract over the (L, N)
local block; the panel GEMM is one (L,k)x(k,N) matmul) — and the STRUCTURE
(collectives per loop body) is machine-verified against the compiled HLO of
each variant at a reduced N (a fori_loop body is costed/parsed exactly once,
so body collective counts are per-step counts).

Variants:
  pmc              paper-faithful rank-1, full static width     (baseline)
  pmc_staged       + geometric shape staging (live-area ~1/3)   (It1)
  pmc_blocked_k    + rank-K panels (GEMM trailing update)       (It2)
  pmc_blocked_k*   k = sqrt(N/P): napkin-optimal panel width
  pmc_blocked_vmem + Pallas VMEM-resident panel factorization   (It3)
  pge              parallel GE (cyclic, global pivoting)        (comparison)

Run:  python -m benchmarks.core_roofline --n 65536 --procs 256
"""
from __future__ import annotations

import argparse
import json
import math

from benchmarks._common import run_with_devices, write_csv

# v5e, f32
MXU = 99e12        # f32 matmul peak
VPU = 4.9e12       # f32 vector peak (rank-1 updates)
HBM = 819e9
ICI = 49.5e9
LAT = 1e-6

CHILD = """
import json
import numpy as np
import jax, jax.numpy as jnp
from repro.core.parallel import parallel_slogdet_mc
from repro.core.blocked import parallel_slogdet_mc_blocked
from repro.core.gaussian import parallel_slogdet_ge
from repro.launch.mesh import make_rows_mesh
from repro.launch.hlo_analysis import collective_bytes

P = jax.device_count()
mesh = make_rows_mesh(P)
spec = jax.ShapeDtypeStruct(({n_lower}, {n_lower}), jnp.float32)
out = {{}}
for name, f in [("pmc", parallel_slogdet_mc(mesh)),
                ("pge", parallel_slogdet_ge(mesh)),
                ("pmc_blocked", parallel_slogdet_mc_blocked(mesh, k=16))]:
    st = collective_bytes(f.lower(spec).compile().as_text())
    out[name] = st.counts
print(json.dumps(out))
"""


def terms(name, n, p, *, k=None, staged=False, vmem=False):
    """Per-device roofline terms in seconds."""
    L = n // p
    area = 1.0 / 3.0 if staged else 1.0      # live-area fraction of updates
    if k is None:                             # rank-1 variants
        steps = n - p
        compute = 2 * L * n * steps * area / VPU
        memory = 8 * L * n * steps * area / HBM
        payload = (4 * n * area if staged else 4 * n)
        collective = steps * (2 * payload / ICI + LAT)
    else:
        n_panels = n / k
        gemm_f = 2 * L * n * k * n_panels * area          # = 2LN^2
        gemm_b = (8 * L * n + 8 * k * n) * n_panels * area
        fact_f = 2 * k * k * n * n_panels                 # redundant, VPU
        fact_b = (8 * k * n * n_panels if vmem            # one VMEM pass
                  else 8 * k * k * n * n_panels)          # k HBM passes
        compute = gemm_f / MXU + fact_f / VPU
        memory = (gemm_b + fact_b) / HBM
        collective = n_panels * (2 * 4 * k * n / ICI + LAT)
    if name == "pge":
        steps = n
        compute = 2 * L * n * steps / VPU
        memory = 8 * L * n * steps / HBM
        collective = steps * (2 * 2 * 4 * n / ICI + 3 * LAT)  # 2-row psum+argmax
    return {"compute_s": compute, "memory_s": memory,
            "collective_s": collective}


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--n", type=int, default=65536)
    ap.add_argument("--procs", type=int, default=256)
    ap.add_argument("--lower-n", type=int, default=2048)
    ap.add_argument("--verify-hlo", action="store_true",
                    help="compile at lower-n and check per-step collective "
                         "counts (slow: spawns a 256-device subprocess)")
    args = ap.parse_args(argv)
    from repro.compile_cache import use_compile_cache
    use_compile_cache()
    n, p = args.n, args.procs
    kstar = int(math.sqrt(n / p))

    variants = [
        ("pmc", dict()),
        ("pmc_staged", dict(staged=True)),
        ("pge", dict()),
        ("pmc_blocked_16", dict(k=16)),
        ("pmc_blocked_64", dict(k=64)),
        (f"pmc_blocked_k*={kstar}", dict(k=kstar)),
        (f"pmc_blocked_vmem_k32", dict(k=32, vmem=True, staged=True)),
    ]
    useful_s = (2 * n ** 3 / 3 / p) / MXU
    rows = []
    for name, kw in variants:
        t = terms(name, n, p, **kw)
        dom = max(t, key=t.get)
        bound = t[dom]
        rows.append([name, n, p, f"{t['compute_s']:.3f}", f"{t['memory_s']:.3f}",
                     f"{t['collective_s']:.3f}", dom.replace("_s", ""),
                     f"{useful_s / bound:.4f}"])
        print(f"core_roofline,{name},N={n},P={p},"
              f"compute={t['compute_s']:.3f}s,memory={t['memory_s']:.3f}s,"
              f"collective={t['collective_s']:.3f}s,"
              f"bottleneck={dom},roofline_frac={useful_s / bound:.4f}")

    if args.verify_hlo:
        counts = json.loads(run_with_devices(
            CHILD.format(n_lower=args.lower_n), args.procs, timeout=3000,
            x64=False))
        print("hlo per-body collective counts:", json.dumps(counts))
        assert counts["pmc"].get("all-reduce", 0) <= 4     # 1/step + tail
        assert counts["pge"].get("all-gather", 0) >= 2     # pivot search

    path = write_csv("core_roofline.csv",
                     ["variant", "N", "P", "compute_s", "memory_s",
                      "collective_s", "bottleneck", "roofline_frac"], rows)
    print(f"core_roofline -> {path}")
    return rows


if __name__ == "__main__":
    main()

"""Table 3: execution time of MC vs GE vs ScaLAPACK-style LU across matrix
sizes and processor counts.

This container has ONE physical core, so wall-clock across fake devices
measures algorithmic + partitioning overhead, not parallel speedup; the
MODELED speedup (fig7_8.py) uses per-step communication counts from the HLO
and the paper's cluster constants.  ``--full`` runs the paper's real grid
(1000..8000 x 1..128) — hours on this box, minutes on a pod.
"""
from __future__ import annotations

import argparse
import json

from benchmarks._common import run_with_devices, write_csv

CHILD = """
import json, time
import numpy as np
import jax
import repro
from repro.launch.mesh import make_rows_mesh
from repro.data.synthetic import random_matrix

sizes = {sizes}
methods = {methods}
n = jax.device_count()
mesh = make_rows_mesh(n)
out = []
for N in sizes:
    a = random_matrix(N, kind="normal", seed=N)
    ref = np.linalg.slogdet(a)[1]
    for m in methods:
        kw = dict(mesh=mesh) if m.startswith("p") else {{}}
        if m == "plu":
            kw["nb"] = 1      # the paper's ScaLAPACK setting (blocksize 1)
        plan = repro.plan(a, method=m, **kw)   # compile once, time execution
        f = lambda: plan.slogdet(a)
        ld = float(f()[1])            # warmup + correctness
        assert abs(ld - ref) < 1e-6 * max(1.0, abs(ref)), (m, N, ld, ref)
        ts = []
        for _ in range(3):
            t0 = time.perf_counter(); jax.block_until_ready(f()[1])
            ts.append(time.perf_counter() - t0)
        out.append((N, n, m, sorted(ts)[1]))
print(json.dumps(out))
"""


def run(sizes, procs, methods=("pmc", "pge", "plu"), serial=("mc", "ge")):
    rows = []
    # serial reference (paper: T_s = fastest serial among all algorithms)
    out = run_with_devices(
        CHILD.format(sizes=list(sizes), methods=list(serial)), 1)
    rows += [list(r) for r in json.loads(out)]
    for p in procs:
        out = run_with_devices(
            CHILD.format(sizes=list(sizes), methods=list(methods)), p)
        rows += [list(r) for r in json.loads(out)]
    path = write_csv("table3.csv", ["N", "procs", "method", "seconds"], rows)
    return rows, path


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true",
                    help="paper grid: 1000..8000 x 1..128 (slow on 1 core)")
    ap.add_argument("--sizes", default="")
    ap.add_argument("--procs", default="")
    args = ap.parse_args(argv)
    from repro.compile_cache import use_compile_cache
    use_compile_cache()
    if args.full:
        sizes = [1000, 2000, 3000, 4000, 5000, 6000, 7000, 8000]
        procs = [1, 2, 4, 8, 16, 32, 64, 128]
    else:
        sizes = [int(x) for x in args.sizes.split(",")] if args.sizes else [256, 512]
        procs = [int(x) for x in args.procs.split(",")] if args.procs else [1, 2, 4]
    rows, path = run(sizes, procs)
    print(f"table3 -> {path}")
    for r in rows:
        print("table3", *r, sep=",")
    return rows


if __name__ == "__main__":
    main()

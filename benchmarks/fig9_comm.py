"""Figure 9: data-distribution time + communication volume, MC vs GE vs LU.

Two measurements:
  1. *Distribution time* (measured): host->devices scatter of the matrix
     under block layout (MC) vs cyclic layout (GE/ScaLAPACK).  The cyclic
     layout pays an extra permutation copy — the paper's Fig. 9 (left).
  2. *Communication per run* (exact, from HLO): per-algorithm collective op
     counts and wire bytes parsed from the compiled module — the paper's
     Fig. 9 (right) re-expressed for ICI rings (no MPI wall-clock here).
     MC: 1 psum per eliminated row; GE: all-gather pivot search + 2-row
     psum per row; LU adds panel gathers.
"""
from __future__ import annotations

import argparse
import json

from benchmarks._common import run_with_devices, write_csv

CHILD = """
import json, time
import numpy as np
import jax, jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.core.parallel import parallel_slogdet_mc
from repro.core.blocked import parallel_slogdet_mc_blocked
from repro.core.gaussian import parallel_slogdet_ge, cyclic_perm
from repro.core.scalapack import parallel_slogdet_lu
from repro.launch.mesh import make_rows_mesh
from repro.launch.hlo_analysis import collective_bytes
from repro.data.synthetic import random_matrix

N = {N}
n = jax.device_count()
mesh = make_rows_mesh(n)
a = random_matrix(N, kind="normal", seed=0)
sh = NamedSharding(mesh, P("rows", None))
out = {{}}

for name, prep in [("block", lambda: a), ("cyclic", lambda: a[cyclic_perm(N, n)])]:
    fn = lambda: jax.device_put(prep(), sh)
    jax.block_until_ready(fn())
    ts = []
    for _ in range(5):
        t0 = time.perf_counter(); jax.block_until_ready(fn())
        ts.append(time.perf_counter() - t0)
    out[name + "_dist_s"] = sorted(ts)[2]

spec = jax.ShapeDtypeStruct((N, N), jnp.float64)
for name, f in [("pmc", parallel_slogdet_mc(mesh)),
                ("pmc_blocked", parallel_slogdet_mc_blocked(mesh, k=16)),
                ("pge", parallel_slogdet_ge(mesh)),
                ("plu", parallel_slogdet_lu(mesh, nb=1))]:
    txt = f.lower(spec).compile().as_text()
    st = collective_bytes(txt)
    out[name] = {{"counts": st.counts, "wire_bytes": st.wire_bytes}}
print(json.dumps(out))
"""


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--n", type=int, default=256)
    ap.add_argument("--procs", default="4,8")
    args = ap.parse_args(argv)
    from repro.compile_cache import use_compile_cache
    use_compile_cache()
    rows = []
    for p in [int(x) for x in args.procs.split(",")]:
        out = json.loads(run_with_devices(CHILD.format(N=args.n), p))
        print(f"fig9_dist,N={args.n},procs={p},"
              f"block={out['block_dist_s']:.4f}s,"
              f"cyclic={out['cyclic_dist_s']:.4f}s,"
              f"cyclic/block={out['cyclic_dist_s']/out['block_dist_s']:.2f}x")
        row = [args.n, p, out["block_dist_s"], out["cyclic_dist_s"]]
        for alg in ("pmc", "pmc_blocked", "pge", "plu"):
            st = out.get(alg)
            n_ops = sum(st["counts"].values())
            print(f"fig9_comm,{alg},procs={p},collective_ops={n_ops},"
                  f"wire_bytes={st['wire_bytes']:.3e}")
            row += [n_ops, st["wire_bytes"]]
        rows.append(row)
    path = write_csv(
        "fig9.csv",
        ["N", "procs", "block_dist_s", "cyclic_dist_s",
         "pmc_ops", "pmc_bytes", "pmcb_ops", "pmcb_bytes",
         "pge_ops", "pge_bytes", "plu_ops", "plu_bytes"], rows)
    print(f"fig9 -> {path}")
    return rows


if __name__ == "__main__":
    main()

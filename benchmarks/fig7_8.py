"""Figures 7 & 8: speedup curves per matrix size + average speedup.

Three views:
  * modeled: the paper-cluster model.  Per eliminated row,
      MC:  compute 2*N*m/P flops + 1 broadcast of m doubles
      GE:  compute + argmax allreduce + 2 broadcasts of m doubles
    with the paper's constants (640 GFLOP/s nodes, ~5 GB/s IB, ~1.5 us
    latency), producing the speedup shape the paper measured (MC > GE, both
    degrading past ~16-32 procs at small N);
  * measured (table3): T_s / T_p from table3.csv (on this 1-core container
    these show partitioning overhead, not parallelism — documented);
  * ``--measured``: the engine scaling bench — runs the mesh engine on
    1/2/4/8 fake devices across (update in {rank1, panel}) x (lookahead
    on/off), records wall seconds, speedup vs the P=1 run of the same
    update, and a bit-identity check of lookahead vs plain.  Written to
    ``bench_out/scaling.json`` + ``scaling.csv``; gated by
    ``benchmarks.check_regression --scaling`` against the committed
    ``scaling_baseline.json``.
"""
from __future__ import annotations

import argparse
import csv
import json
from collections import defaultdict
from pathlib import Path

from benchmarks._common import OUT_DIR, run_with_devices, write_csv

# paper-era cluster constants (Table 2: dual Xeon E5-2650v3 nodes, IB)
FLOPS = 640e9 / 20     # per MPI rank (20 ranks/node)
BW = 5e9               # bytes/s effective per link (FDR IB, shared)
LAT = 3e-6             # per-message latency, seconds


def model_time(N: int, P: int, alg: str) -> float:
    """Total modeled seconds for N x N on P ranks."""
    t = 0.0
    # distributed phase: N - P rows (MC) / N rows (GE); live width shrinks
    comp = 0.0
    comm = 0.0
    rows = N - P if alg == "mc" else N
    for i in range(rows):
        m = N - i
        comp += 2.0 * m * max(N - i, 1) / P / FLOPS      # rank-1 update share
        if P > 1:
            if alg == "mc":
                comm += LAT + 8.0 * m / BW                # 1 bcast
            else:
                comm += 3 * LAT + 2 * 8.0 * m / BW + 8.0 * P / BW  # argmax+2
    t = comp + comm
    if alg == "mc" and P > 1:
        t += 2.0 * P * P * P / 3 / FLOPS + LAT + 8.0 * P * P / BW  # tail
    return t


def modeled_speedups(sizes, procs):
    rows = []
    for N in sizes:
        t1 = model_time(N, 1, "mc")
        for P in procs:
            for alg in ("mc", "ge"):
                rows.append([N, P, alg, t1 / model_time(N, P, alg)])
    return rows


def measured_speedups(table3_csv: Path):
    by = {}
    with table3_csv.open() as f:
        for row in csv.DictReader(f):
            by[(int(row["N"]), int(row["procs"]), row["method"])] = \
                float(row["seconds"])
    sizes = sorted({k[0] for k in by})
    procs = sorted({k[1] for k in by if k[1] > 1} | {1})
    rows = []
    for N in sizes:
        serials = [v for (n, p, m), v in by.items()
                   if n == N and p == 1 and m in ("mc", "ge")]
        if not serials:
            continue
        ts = min(serials)
        for P in procs:
            for m in ("pmc", "pge", "plu"):
                if (N, P, m) in by:
                    rows.append([N, P, m, ts / by[(N, P, m)]])
    return rows


# ---------------------------------------------------------------------------
# --measured: the engine scaling bench (fake-device subprocesses)
# ---------------------------------------------------------------------------

# subprocess body: one device count per process (the device count is fixed
# at jax init).  Times every (update, lookahead) engine instantiation and
# checks lookahead bit-identity within the same process.
_SCALING_CODE = """
import json, time
import numpy as np
from repro.launch.mesh import make_mesh
from repro.core.engine import EngineConfig, build_mesh

P, N, iters = {P}, {N}, {iters}
mesh = make_mesh((P,), ("rows",))
rng = np.random.default_rng(0)
a = rng.standard_normal((N, N))
out = []
for update in ("rank1", "panel"):
    got = {{}}
    for la in (False, True):
        fn = build_mesh(EngineConfig(schedule="mesh", update=update,
                                     lookahead=la), mesh)
        s, l = fn(a)                       # compile outside the timing
        s, l = float(s), float(l)
        ts = []
        for _ in range(iters):
            t0 = time.perf_counter()
            r = fn(a)
            r[1].block_until_ready()
            ts.append(time.perf_counter() - t0)
        ts.sort()
        got[la] = (s, l, ts[len(ts) // 2])
    bit = got[True][:2] == got[False][:2]
    for la in (False, True):
        s, l, sec = got[la]
        out.append(dict(bench="scaling", n=N, procs=P, update=update,
                        lookahead=la, seconds=sec, sign=s, logabsdet=l,
                        bit_identical=bit))
print(json.dumps(out))
"""


def measured_scaling(n: int, procs, iters: int = 3) -> list[dict]:
    """Run the engine scaling grid; returns the scaling.json records.

    ``speedup`` is against the same update's (P=1, lookahead=off) run, so
    the curves are comparable to the paper's Fig. 7 T_s/T_p convention;
    ``throughput`` (1/seconds) is what `gate_scaling` ratios within one
    run — no machine calibration needed.
    """
    records: list[dict] = []
    for P in procs:
        out = run_with_devices(
            _SCALING_CODE.format(P=P, N=n, iters=iters), P)
        records.extend(json.loads(out.strip().splitlines()[-1]))
    base = {r["update"]: r["seconds"] for r in records
            if r["procs"] == 1 and not r["lookahead"]}
    for r in records:
        t1 = base.get(r["update"])
        r["speedup"] = (t1 / r["seconds"]) if t1 else None
        r["throughput"] = 1.0 / r["seconds"]
    return records


def run_measured(n: int, procs, iters: int) -> list[dict]:
    records = measured_scaling(n, procs, iters=iters)
    OUT_DIR.mkdir(exist_ok=True)
    path = OUT_DIR / "scaling.json"
    path.write_text(json.dumps(records, indent=1))
    print(f"scaling -> {path}")
    write_csv("scaling.csv",
              ["n", "procs", "update", "lookahead", "seconds", "speedup"],
              [[r["n"], r["procs"], r["update"], int(r["lookahead"]),
                f"{r['seconds']:.6f}", f"{r['speedup']:.4f}"]
               for r in records])
    for r in records:
        print(f"scaling,n={r['n']},P={r['procs']},{r['update']},"
              f"lookahead={int(r['lookahead'])},{r['seconds']:.4f}s,"
              f"speedup={r['speedup']:.3f},bit={r['bit_identical']}")
    return records


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--sizes", default="1000,2000,4000,8000")
    ap.add_argument("--procs", default="1,2,4,8,16,32,64,128")
    ap.add_argument("--measured", action="store_true",
                    help="run the engine scaling bench on fake devices "
                         "(writes bench_out/scaling.json + scaling.csv)")
    ap.add_argument("--measured-n", type=int, default=1024,
                    help="matrix size of the --measured grid")
    ap.add_argument("--measured-procs", default="1,2,4,8")
    ap.add_argument("--iters", type=int, default=3)
    args = ap.parse_args(argv)
    from repro.compile_cache import use_compile_cache
    use_compile_cache()
    sizes = [int(x) for x in args.sizes.split(",")]
    procs = [int(x) for x in args.procs.split(",")]

    if args.measured:
        return run_measured(args.measured_n,
                            [int(x) for x in args.measured_procs.split(",")],
                            args.iters)

    rows = modeled_speedups(sizes, procs)
    path = write_csv("fig7_modeled.csv", ["N", "procs", "alg", "speedup"], rows)
    print(f"fig7 modeled -> {path}")
    # fig8: average across sizes
    agg = defaultdict(list)
    for N, P, alg, s in rows:
        agg[(P, alg)].append(s)
    avg_rows = [[P, alg, sum(v) / len(v)] for (P, alg), v in sorted(agg.items())]
    path8 = write_csv("fig8_modeled.csv", ["procs", "alg", "avg_speedup"], avg_rows)
    for P, alg, s in avg_rows:
        print(f"fig8_modeled,{alg},procs={P},avg_speedup={s:.2f}")

    t3 = OUT_DIR / "table3.csv"
    if t3.exists():
        mrows = measured_speedups(t3)
        write_csv("fig7_measured.csv", ["N", "procs", "alg", "speedup"], mrows)
        for r in mrows:
            print("fig7_measured", *r, sep=",")
    return avg_rows


if __name__ == "__main__":
    main()

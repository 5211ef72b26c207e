"""Record the small chip traces that the trace tests read, on one TPU chip:

    python3 bench/tests/record_traces.py --out bench/tests/data --suffix _spans

Runs ``paper_dense.n1000`` (as ``exact``) and ``gp_rbf.n8192.grad`` (as
``grad``) at N=256 through ``run.run`` with ``--trace 1`` for two calls
each, and writes ``<out>/<exact|grad>_n256<suffix>.xplane.pb.gz``.
Exits with code 2 where there is no TPU.
"""
import argparse
import dataclasses
import glob
import gzip
import os
import shutil
import sys
import tempfile
import time
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH.parent / "src"))

import registry  # noqa: E402
import run  # noqa: E402

CELLS = {"exact": "paper_dense.n1000", "grad": "gp_rbf.n8192.grad"}
N = 256


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n", 1)[0])
    ap.add_argument("--out", default=str(BENCH / "tests" / "data"))
    ap.add_argument("--suffix", default="")
    ap.add_argument("--seed", type=int, default=2718281828)
    args = ap.parse_args(argv)
    os.makedirs(args.out, exist_ok=True)
    for short, name in CELLS.items():
        cell = registry.load_cell(name)
        config = dict(cell.config)
        if "n" in config:
            config["n"] = N
        cell = dataclasses.replace(
            cell, config=config,
            traffic={**cell.traffic, "n": N, "trace_calls": 2})
        with tempfile.TemporaryDirectory(prefix="bench-trace-") as tmp:
            try:
                result = run.run(cell, args.seed, 1.0, True,
                                 t0=time.perf_counter(), trace_dir=tmp)
            except run.NoChip as exc:
                print(f"record_traces: {exc}", file=sys.stderr)
                return 2
            found = glob.glob(os.path.join(tmp, "**", "*.xplane.pb"),
                              recursive=True)
            dest = os.path.join(args.out, f"{short}_n{N}{args.suffix}"
                                ".xplane.pb.gz")
            with open(found[0], "rb") as src, gzip.open(dest, "wb") as dst:
                shutil.copyfileobj(src, dst)
        print(f"{dest}: correct {result['correct']}, "
              f"{result['attempted']} calls, metrics {result['metrics']}")
    return 0


if __name__ == "__main__":
    sys.exit(main())

"""Read a cell's compared numbers for the program and for its control,
at the cell's own size, over several seeds, in one process.

    python3 bench/control.py --workload paper_dense.n8000 --seeds 11,12,13

For each seed it makes the cell's input pool as a run does, answers every
pool member once through its ``drivers/`` module's ``call`` (the program)
and once through its ``control_call`` (the program's bfloat16 path, or the
reference one precision down), and prints one JSON line per seed: the
numbers each side gives under the cell's comparison, beside the limits.
A limit holds only if the program passes it and the control fails one.
The benchmark's own runs never run this; ``tests/test_compare.py``
runs it at a size the CPU holds.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

BENCH = Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH.parent / "src"))

import registry  # noqa: E402
from run import seed_key  # noqa: E402


def readings(cell: registry.Cell, seeds, root: Path = registry.ROOT):
    """Yield ``{"seed", "program", "control"}`` per seed; each side maps a
    compared number's name to ``(value, limit)``."""
    import jax
    import numpy as np
    drv = registry.driver(cell.traffic["driver"], root)
    gen = registry.generator(cell.config["generator"], root)
    sides = {"program": (drv.build(cell.config, cell.traffic), drv.call),
             "control": (drv.build_control(cell.config, cell.traffic),
                         drv.control_call)}
    for seed in seeds:
        pool = jax.block_until_ready(
            gen.make(seed_key(seed), cell.config, cell.traffic))
        host_pool = [np.asarray(a) for a in pool]
        out = {"seed": seed}
        for side, (state, call) in sides.items():
            answers = [(j, j, tuple(np.asarray(x) for x in call(state, a)))
                       for j, a in enumerate(pool)]
            checks, _ = drv.check(host_pool, answers, answers,
                                  cell.traffic["limits"],
                                  cell.config["dtype"])
            out[side] = checks
        del pool
        yield out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n", 1)[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True,
                    help="comma-separated whole numbers")
    args = ap.parse_args(argv)
    import jax
    if jax.default_backend() != "tpu":
        print("control: needs a TPU", file=sys.stderr)
        return 2
    registry.use_cache()
    cell = registry.load_cell(args.workload)
    t0 = time.perf_counter()
    for line in readings(cell, [int(s) for s in args.seeds.split(",")]):
        line["elapsed_s"] = time.perf_counter() - t0
        print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

"""The benchmark's own tests run on the CPU at tiny sizes:

    python -m pytest bench/tests
"""
import dataclasses
import os
import sys
from pathlib import Path

os.environ.setdefault("JAX_PLATFORMS", "cpu")

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH.parent / "src"))

import registry  # noqa: E402

# CPU programs go to a cache of their own: entries written here without
# the access-time files that a size-capped cache keeps would make the
# chip's runs fail to write theirs, should the checkout be copied there
registry.CACHE = ".jax_cache_bench_tests"


def tiny(name: str, n: int, **traffic) -> registry.Cell:
    """The cell ``name`` of BENCHMARK.json at side ``n``."""
    cell = registry.load_cell(name)
    config = dict(cell.config)
    if "n" in config:
        config["n"] = n
    return dataclasses.replace(cell, config=config,
                               traffic={**cell.traffic, "n": n, **traffic})

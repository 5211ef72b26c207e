"""Find a cell's parts by name: nothing here names a configuration, a
traffic mix or a metric.

``BENCHMARK.json`` at the checkout root lists the cells, each a
configuration and a traffic mix, and the metrics.  Every part of a cell
sits in a file of its own under ``bench/``:

    configs/<config>.json       sizes, dtype and generator family
    traffic/<traffic>.json      N, the entry driven, the input pool, the
                                names of its metrics, limits
    generators/<family>.py      ``make(key, config, traffic)`` -> inputs
    drivers/<entry>.py          the call the window drives, its check
    metrics/<metric>.py         ``read(ctx)`` -> one number or None
    peaks.json                  the chip's peaks, by ``device_kind``

So a new cell or metric is new files plus entries in ``BENCHMARK.json``.
"""
from __future__ import annotations

import importlib.util
import json
import sys
from dataclasses import dataclass
from pathlib import Path
from types import ModuleType
from typing import List

ROOT = Path(__file__).resolve().parents[1]


@dataclass(frozen=True)
class Cell:
    """One workload of ``BENCHMARK.json`` with its files read."""
    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: List[dict]      # the end-to-end metrics this cell reports
    per_layer: List[dict]       # the per-layer metrics this cell reports


def spec(root: Path = ROOT) -> dict:
    return json.loads((Path(root) / "BENCHMARK.json").read_text())


def _reports(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(name: str, root: Path = ROOT) -> Cell:
    """The cell ``name`` of ``BENCHMARK.json``; KeyError if it has none."""
    bench = spec(root)
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; "
                       f"have {sorted(cells)}")
    w = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    config = json.loads((Path(root) / configs[w["config"]]["file"])
                        .read_text())
    traffic = json.loads(_file(root, "traffic", w["traffic"], ".json")
                         .read_text())
    if "n" in config and config["n"] != traffic["n"]:
        raise ValueError(f"cell {name}: traffic n={traffic['n']} but the "
                         f"configuration fixes n={config['n']}")
    e2e = [m for m in bench["end_to_end"] if _reports(m, name)]
    e2e_names = {m["name"] for m in e2e}
    # a per-layer metric without a workloads key goes with every cell
    # that reports the end-to-end metric it moves
    per_layer = [m for m in bench["per_layer"]
                 if (name in m["workloads"] if "workloads" in m
                     else m["moves"] in e2e_names)]
    return Cell(name=name, chips=int(w["chips"]), config=config,
                traffic=traffic, end_to_end=e2e, per_layer=per_layer)


def _file(root: Path, kind: str, name: str, suffix: str) -> Path:
    path = Path(root) / "bench" / kind / f"{name}{suffix}"
    if not path.is_file():
        raise FileNotFoundError(f"no {kind} file {path}")
    return path


def load_module(path: Path) -> ModuleType:
    """Import the file ``path`` under a name of its own."""
    mod_name = "bench_" + "_".join(path.with_suffix("").parts[-2:]) \
        .replace(".", "_")
    spec_ = importlib.util.spec_from_file_location(mod_name, path)
    mod = importlib.util.module_from_spec(spec_)
    sys.modules[mod_name] = mod         # dataclasses look their module up
    spec_.loader.exec_module(mod)
    return mod


def generator(family: str, root: Path = ROOT) -> ModuleType:
    return load_module(_file(root, "generators", family, ".py"))


def driver(entry: str, root: Path = ROOT) -> ModuleType:
    return load_module(_file(root, "drivers", entry, ".py"))


def metric_reader(name: str, root: Path = ROOT) -> ModuleType:
    return load_module(_file(root, "metrics", name, ".py"))


def work(name: str, root: Path = ROOT) -> ModuleType:
    return load_module(_file(root, "work", name, ".py"))


CACHE = ".jax_cache_bench"


def use_cache(root: Path = ROOT) -> Path:
    """Keep JAX's persistent compile cache at one fixed path inside the
    checkout, so that only a cell's first run there compiles, and cache
    every program however fast it compiles.  This overrides
    ``JAX_COMPILATION_CACHE_DIR``: every process of the benchmark, timed
    or not, reads the same cache."""
    import jax
    cache = Path(root) / CACHE
    cache.mkdir(exist_ok=True)          # JAX writes no entry without it
    jax.config.update("jax_compilation_cache_dir", str(cache))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return cache


def peaks(device_kind: str, root: Path = ROOT) -> dict:
    """The peaks of ``device_kind``.  An unknown kind is an error: a
    roofline share against a guessed peak is no measurement."""
    table = json.loads((Path(root) / "bench" / "peaks.json").read_text())
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"bench/peaks.json; have {sorted(table)}")
    return table[device_kind]

"""Roofline tooling: the measurement pass that calibrates the method
selector, plus the §Roofline report over launch/dryrun.py results.

Calibration (``--calibrate``)
-----------------------------
Measures the four terms `repro.core.calibration` prices routes with, on
THIS machine:

  gemm_flops        median-timed f32 GEMM at full float32 precision (the
                    MXU/BLAS roofline that prices panel updates and
                    estimator matvec slabs)
  gemm_flops_bf16   the same GEMM on bf16 operands (the mixed-precision
                    engine route)
  stream_bytes      median-timed fused rank-1 update (read + write the
                    buffer once: the streaming-bandwidth roofline of the
                    faithful condensation step)
  collective_lat /  a shard_map psum loop over every device the process
  collective_bytes  sees, at two payload sizes; the (latency, bandwidth)
                    line is fit from the two timings (null on one device)

all in one process, and merges them into ``bench_out/roofline_calibration
.json`` under this device's ``device_kind`` — the table ``select_method``
/ ``select_route`` load (see repro.core.calibration).  Run it on each
new kind of device (on the CPU, XLA_FLAGS=--xla_force_host_platform_
device_count=8 gives the collectives 8 devices):

    PYTHONPATH=src python -m benchmarks.roofline --calibrate

Report (default)
----------------
Reads launch/dryrun.py results (dryrun_results.jsonl) and renders the
§Roofline table (one row per arch x shape on the single-pod mesh): three
terms in seconds, dominant bottleneck, MODEL_FLOPS/HLO_FLOPs, and a
one-line lever per row.
"""
from __future__ import annotations

import argparse
import json
import time
from pathlib import Path

from benchmarks._common import OUT_DIR, write_csv

LEVERS = {
    "compute_s": "raise MXU utilization: larger per-chip tiles / fewer remat "
                 "recomputes (useful-FLOP fraction is the lever)",
    "memory_s": "cut HBM traffic: fuse bandwidth-bound stages, bf16 "
                "intermediates, larger arithmetic-intensity blocks",
    "collective_s": "cut wire bytes: reshard to kill duplicate all-gathers, "
                    "overlap collectives with compute, bf16 grad all-reduce",
}


def load(path: Path):
    recs = []
    for line in path.read_text().splitlines():
        try:
            recs.append(json.loads(line))
        except json.JSONDecodeError:
            pass
    return recs


def render(recs, mesh: str = "16x16"):
    rows = []
    seen = set()
    for r in recs:
        if r.get("mesh") != mesh or r.get("skipped"):
            continue
        key = (r["arch"], r["shape"])
        if key in seen:
            continue
        seen.add(key)
        frac = r.get("useful_flops_frac", 0.0)
        rows.append([
            r["arch"], r["shape"], r["kind"],
            f"{r['compute_s']:.4g}", f"{r['memory_s']:.4g}",
            f"{r['collective_s']:.4g}", r["bottleneck"].replace("_s", ""),
            f"{r['model_flops']:.3e}", f"{r['hlo_flops_global']:.3e}",
            f"{min(frac, 1.0):.2f}",
            f"{r['memory']['peak_bytes_per_device'] / 2**30:.2f}",
        ])
    skips = [[r["arch"], r["shape"], "SKIP", r.get("reason", "")]
             for r in recs if r.get("skipped") and r.get("mesh") == mesh]
    return rows, skips


# ---------------------------------------------------------------- calibrate

def _median_s(fn, *args, iters: int = 5, reps: int = 10) -> float:
    """Median wall seconds of one ``fn(*args)``, compile excluded.

    Each sample times ``reps`` calls issued back to back and blocks once,
    so the host's dispatch overlaps the device's work instead of adding
    to a sub-millisecond call.
    """
    import jax
    jax.block_until_ready(fn(*args))
    ts = []
    for _ in range(iters):
        t0 = time.perf_counter()
        outs = [fn(*args) for _ in range(reps)]
        jax.block_until_ready(outs)
        ts.append((time.perf_counter() - t0) / reps)
    return sorted(ts)[len(ts) // 2]


def _measure_collectives(steps: int = 200, payloads=(256, 65536)):
    """(latency_s, bytes_per_s, raw) fit from a two-payload psum loop over
    every device this process sees."""
    import jax
    import jax.numpy as jnp
    from jax import lax
    from jax.sharding import NamedSharding, PartitionSpec

    from repro.launch.mesh import make_rows_mesh

    mesh = make_rows_mesh()
    raw = {}
    for payload in payloads:
        def kernel(x):
            def body(i, acc):
                # one data-dependent psum per step: a pivot-row broadcast
                return lax.psum(x[0] + acc * 1e-9, "rows")
            acc = jnp.zeros(x.shape[1:], x.dtype)
            return lax.fori_loop(0, steps, body, acc)

        f = jax.jit(jax.shard_map(kernel, mesh=mesh,
                                  in_specs=PartitionSpec("rows", None),
                                  out_specs=PartitionSpec()))
        x = jax.device_put(
            jnp.zeros((mesh.size, payload), jnp.float32),
            NamedSharding(mesh, PartitionSpec("rows", None)))
        raw[str(payload)] = _median_s(f, x) / steps
    b1, b2 = (4 * p for p in payloads)          # f32 payload bytes
    t1, t2 = raw[str(payloads[0])], raw[str(payloads[1])]
    if t2 <= t1:                                # noise floor: all latency
        return max(t1, t2), 1e12, raw
    bw = (b2 - b1) / (t2 - t1)
    lat = max(t1 - b1 / bw, 1e-9)
    return lat, bw, raw


def calibrate(out_path: Path, *, gemm_n: int = 4096, stream_n: int = 4096):
    """Measure this device's entry and merge it into ``out_path``.

    One process: the GEMMs and the rank-1 stream run on the first
    device; the collectives run over every device the process sees
    (left out, as null, when it sees one).
    """
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core.calibration import current_device_kind
    from repro import numerics
    from repro.kernels import ref

    rng = np.random.default_rng(0)

    a = jnp.asarray(rng.standard_normal((gemm_n, gemm_n)), jnp.float32)
    b = jnp.asarray(rng.standard_normal((gemm_n, gemm_n)), jnp.float32)
    t_gemm = _median_s(jax.jit(numerics.matmul),
                       a, b)
    a16, b16 = a.astype(jnp.bfloat16), b.astype(jnp.bfloat16)
    t_bf16 = _median_s(jax.jit(lambda x, y: jnp.matmul(
        x, y, preferred_element_type=jnp.float32)), a16, b16)

    s = jnp.asarray(rng.standard_normal((stream_n, stream_n)), jnp.float32)
    pc = jnp.asarray(rng.standard_normal((stream_n,)), jnp.float32)
    pr = jnp.asarray(rng.standard_normal((stream_n,)), jnp.float32)
    t_r1 = _median_s(jax.jit(ref.rank1_update_ref), s, pc, pr)

    devices = jax.device_count()
    lat = coll_bw = raw = None
    if devices > 1:
        lat, coll_bw, raw = _measure_collectives()

    kind = current_device_kind()
    entry = {
        "gemm_flops": 2.0 * gemm_n ** 3 / t_gemm,
        "gemm_flops_bf16": 2.0 * gemm_n ** 3 / t_bf16,
        # read + write the buffer, stream the two vectors: ~3 x n^2 x 4 B
        "stream_bytes": 3.0 * stream_n * stream_n * 4 / t_r1,
        "collective_lat": lat,
        "collective_bytes": coll_bw,
        "source": f"measured:{jax.default_backend()}",
        "meta": {
            "gemm_n": gemm_n, "gemm_seconds": t_gemm,
            "gemm_bf16_seconds": t_bf16,
            "stream_n": stream_n, "rank1_seconds": t_r1,
            "collective_devices": devices,
            "collective_raw_s_per_step": raw,
            "jax": jax.__version__,
        },
    }
    table = {"devices": {}}
    if out_path.exists():
        table = json.loads(out_path.read_text())
    table["devices"][kind] = entry
    out_path.parent.mkdir(exist_ok=True)
    out_path.write_text(json.dumps(table, indent=2, sort_keys=True) + "\n")
    print(f"calibration[{kind}] -> {out_path}")
    for k in ("gemm_flops", "gemm_flops_bf16", "stream_bytes",
              "collective_lat", "collective_bytes"):
        print(f"  {k:18s} {entry[k]}")
    return entry


# ------------------------------------------------------------------- report

def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--results", default="dryrun_results.jsonl")
    ap.add_argument("--mesh", default="16x16")
    ap.add_argument("--calibrate", action="store_true",
                    help="measure the device roofline and write the "
                         "selector's calibration table")
    ap.add_argument("--out", default=str(OUT_DIR / "roofline_calibration.json"))
    args = ap.parse_args(argv)
    if args.calibrate:
        from repro.compile_cache import use_compile_cache
        use_compile_cache()
        return calibrate(Path(args.out))
    path = Path(args.results)
    if not path.exists():
        print(f"roofline: {path} not found — run "
              f"`python -m repro.launch.dryrun` first")
        return []
    recs = load(path)
    rows, skips = render(recs, args.mesh)
    header = ["arch", "shape", "kind", "compute_s", "memory_s",
              "collective_s", "bottleneck", "model_flops", "hlo_flops",
              "useful_frac", "peak_GiB_per_dev"]
    out = write_csv("roofline.csv", header, rows)
    print(f"roofline -> {out}")
    for r in rows:
        print("roofline", *r, sep=",")
    for s in skips:
        print("roofline_skip", *s[:3], sep=",")
    return rows


if __name__ == "__main__":
    main()

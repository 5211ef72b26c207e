"""Run one cell of the benchmark on the chip and print one JSON line.

    python3 bench/run.py --workload paper_dense.n8000 --seed 7 \
        --seconds 30 --trace 0

The cell is read from ``BENCHMARK.json`` and its files under ``bench/``
(``registry.py``).  One run makes its inputs on the device from
``--seed``, builds the plan and warms it with one call (set-up, timed as
``setup_s``), then one caller drives the cell's entry back to back over
the input pool until the first call that ends after ``--seconds``.  It
then compares what the window produced with the float64 reference and
prints, as the last line of standard output, ``correct``, ``attempted``,
``failed``, ``metrics``, ``device`` and, last, ``checks``: each number
compared with its limit (also the last lines of standard error).

``--trace 0`` reports the cell's end-to-end metrics.  ``--trace 1``
records a profiler trace of the window (at most the traffic's
``trace_calls`` calls) and reports the cell's per-layer metrics, read by
``bench/metrics/<name>.py`` from the reduced trace, with the device's
busy and window seconds and a ``breakdown``.

The run needs a TPU: with another backend, or fewer chips than the cell
asks for, it exits with code 2 and prints no result.
"""
import time

T0 = time.perf_counter()        # set-up counts from here

import argparse  # noqa: E402
import contextlib  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH.parent / "src"))

import registry  # noqa: E402

# jax.monitoring duration events that make up compiling: tracing,
# lowering, the backend compile, and a load from the persistent cache
COMPILE_EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
                  "/jax/core/compile/jaxpr_to_mlir_module_duration",
                  "/jax/core/compile/backend_compile_duration",
                  "/jax/compilation_cache/cache_retrieval_time_sec")


class CompileWatch:
    """The compile-duration events JAX reports, from registration until
    ``take`` (which starts afresh).  Tracing events nest (an outer jit
    traces the inner ones), so the seconds are the union of the events'
    intervals, each ending when JAX reports it."""

    def __init__(self):
        import jax
        self.spans, self.by = [], {}
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, name, secs, **_):
        if name in COMPILE_EVENTS:
            end = time.perf_counter()
            self.spans.append((end - secs, end))
            n, s = self.by.get(name, (0, 0.0))
            self.by[name] = (n + 1, s + secs)

    def take(self):
        """(seconds, backend compiles, {event: (count, summed seconds)})."""
        spans, by = sorted(self.spans), self.by
        self.spans, self.by = [], {}
        total, reach = 0.0, float("-inf")
        for a, b in spans:
            total += max(0.0, b - max(a, reach))
            reach = max(reach, b)
        return total, by.get(COMPILE_EVENTS[2], (0, 0.0))[0], by


class GcPauses:
    """Times the collector's passes from creation until ``stop``."""

    def __init__(self):
        self.pauses, self._t = [], None
        gc.callbacks.append(self._on)

    def _on(self, phase, info):
        if phase == "start":
            self._t = time.perf_counter()
        elif self._t is not None:
            self.pauses.append(time.perf_counter() - self._t)

    def stop(self):
        gc.callbacks.remove(self._on)

    def __str__(self):
        return (f"gc in the window {len(self.pauses)}x "
                f"{sum(self.pauses):.4f} s, longest "
                f"{max(self.pauses, default=0.0):.4f} s")


def seed_key(seed: int):
    """A PRNG key from any whole number: its 64 low bits, both halves."""
    import jax
    import numpy as np
    s = int(seed) % 2 ** 64
    return jax.random.fold_in(jax.random.PRNGKey(s & 0xFFFFFFFF),
                              np.uint32(s >> 32))


def p95(xs):
    import numpy as np
    return float(np.percentile(np.asarray(xs, np.float64), 95))


def run(cell: registry.Cell, seed: int, seconds: float, trace: bool, *,
        t0: float, root: Path = registry.ROOT, trace_dir=None,
        require_tpu: bool = True) -> dict:
    """One run of ``cell``: the result line as a dict."""
    import jax
    import numpy as np

    if require_tpu:
        have = jax.devices()
        if jax.default_backend() != "tpu" or len(have) < cell.chips:
            raise NoChip(f"cell {cell.name} needs {cell.chips} TPU chip(s); "
                         f"JAX has {len(have)} {jax.default_backend()} "
                         f"device(s)")
    registry.use_cache(root)
    watch = CompileWatch()
    config, traffic = cell.config, cell.traffic
    drv = registry.driver(traffic["driver"], root)
    gen = registry.generator(config["generator"], root)

    # ---- set-up: inputs on the device, the plan, one warm call
    marks = [("start", time.perf_counter() - t0)]
    pool = jax.block_until_ready(gen.make(seed_key(seed), config, traffic))
    marks.append(("inputs", time.perf_counter() - t0))
    state = drv.build(config, traffic)
    drv.call(state, pool[0])
    setup_s = time.perf_counter() - t0
    marks.append(("plan and warm call", setup_s))
    setup_compile_s, _, compile_by = watch.take()
    # what set-up made lives to the end: keep the collector from walking
    # it again in the window (a full pass over JAX's objects takes ~0.1 s)
    gc.freeze()
    pauses = GcPauses()

    # ---- the window: one caller, back to back, cycling over the pool
    trace_calls = int(traffic["trace_calls"]) if trace else None
    tmp = None
    if trace:
        if trace_dir is None:
            tmp = tempfile.TemporaryDirectory(prefix="bench-trace-")
            trace_dir = tmp.name
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(str(trace_dir), profiler_options=opts)
    span = jax.profiler.TraceAnnotation if trace else \
        (lambda name: contextlib.nullcontext())
    rng = np.random.default_rng(int(seed) % 2 ** 64)
    lat, ends, calls, sample = [], [], [], []
    i = 0
    with span("bench.window"):
        start = time.perf_counter()
        while True:
            j = i % len(pool)
            with span("bench.call"):
                t = time.perf_counter()
                out = drv.call(state, pool[j])
                end = time.perf_counter()
            lat.append(end - t)
            ends.append(end - t0)
            calls.append((i, j, drv.light(out)))
            # a reservoir of whole answers, drawn from the seed
            if len(sample) < drv.SAMPLE:
                sample.append((i, j, out))
            else:
                r = int(rng.integers(0, i + 1))
                if r < drv.SAMPLE:
                    sample[r] = (i, j, out)
            del out
            i += 1
            if end - start >= seconds or (trace and i >= trace_calls):
                break
    elapsed = end - start
    pauses.stop()
    if trace:
        jax.profiler.stop_trace()
    _, window_compiles, _ = watch.take()
    if window_compiles:
        print(f"bench: {window_compiles} compile(s) inside the window",
              file=sys.stderr)

    devices = jax.local_devices()
    memory_peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                      for d in devices)

    # ---- the check, with the program's state freed
    t_check = time.perf_counter()
    host = lambda out: tuple(np.asarray(x) for x in out)  # noqa: E731
    calls = [(i, j, host(out)) for i, j, out in calls]
    sample = sorted((i, j, host(out)) for i, j, out in sample)
    host_pool = [np.asarray(a) for a in pool]
    del pool, state
    checks, failed = drv.check(host_pool, calls, sample, traffic["limits"],
                               config["dtype"])
    print("bench: set-up " + ", ".join(f"{k} at {v:.3f} s" for k, v in marks)
          + f" (compiling or loading {setup_compile_s:.3f} s: "
          + ", ".join(f"{k.rsplit('/', 1)[1]} {n}x {v:.3f} s"
                      for k, (n, v) in compile_by.items())
          + "); window "
          f"{elapsed:.3f} s, {len(lat)} calls, slowest "
          + ", ".join(f"#{k} {lat[k]:.4f} s ending {ends[k]:.1f} s in"
                      for k in
                      sorted(range(len(lat)), key=lat.__getitem__)[-3:])
          + f"; {pauses}; check {time.perf_counter() - t_check:.3f} s",
          file=sys.stderr)
    correct = not failed and all(v <= lim for v, lim in checks.values())

    d0 = devices[0]
    result = {"correct": bool(correct), "attempted": len(lat),
              "failed": len(failed), "metrics": {},
              "device": {"platform": d0.platform, "kind": d0.device_kind,
                         "count": len(jax.devices()),
                         "memory_peak_bytes": int(memory_peak)}}
    if not trace:
        # the traffic names its metrics: the window over the calls it
        # completed, and the 95th percentile of their latency
        stats = {traffic["rate_metric"]: elapsed / len(lat),
                 "setup_s": setup_s}
        if "p95_metric" in traffic:
            stats[traffic["p95_metric"]] = p95(lat)
        for m in cell.end_to_end:
            result["metrics"][m["name"]] = {"value": stats[m["name"]],
                                            "unit": m["unit"]}
    else:
        trace_mod = registry.load_module(BENCH / "trace.py")
        reduced = trace_mod.reduce_dir(trace_dir)
        if tmp is not None:
            tmp.cleanup()
        ctx = trace_mod.Context(
            trace=reduced, calls=len(lat), n=int(traffic["n"]),
            setup_compile_s=setup_compile_s,
            peaks=registry.peaks(d0.device_kind, root),
            work=lambda name: registry.work(name, root))
        for m in cell.per_layer:
            value = registry.metric_reader(m["name"], root).read(ctx)
            if value is not None:
                result["metrics"][m["name"]] = {"value": value,
                                                "unit": m["unit"]}
        result["device"]["busy_s"] = reduced.busy_s
        result["device"]["window_s"] = reduced.window_s
        result["breakdown"] = reduced.breakdown()
    result["checks"] = {k: {"value": v, "limit": lim}
                        for k, (v, lim) in checks.items()}
    return result


class NoChip(RuntimeError):
    """The machine lacks the accelerator the cell needs."""


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n", 1)[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--trace-dir", default=None,
                    help="keep the profiler trace here (default: a "
                         "temporary directory, deleted)")
    args = ap.parse_args(argv)
    # the TPU runtime logs to /tmp/tpu_logs unless told otherwise: keep
    # them under this run's own TMPDIR
    os.environ.setdefault("TPU_LOG_DIR",
                          os.path.join(tempfile.gettempdir(), "tpu_logs"))
    cell = registry.load_cell(args.workload)
    try:
        result = run(cell, args.seed, args.seconds, bool(args.trace),
                     t0=T0, trace_dir=args.trace_dir)
    except NoChip as exc:
        print(f"bench: {exc}", file=sys.stderr)
        return 2
    for name, c in result["checks"].items():
        print(f"check {name}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

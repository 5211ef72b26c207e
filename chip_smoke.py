"""Run the logdet system's main paths once on a TPU and check the answers.

    python chip_smoke.py             # one chip: every phase below
    python chip_smoke.py --chips 4   # four chips: the mesh schedule only

The script needs a TPU: it exits non-zero before doing anything else when
JAX's default backend is not ``tpu``.  Each phase goes through the entry
points a user calls (``repro.plan``, ``value_and_grad``, ``LogdetService``
behind ``serve_http``, ``export`` / ``repro.load_plan``) on seeded data,
compares against a float64 numpy reference on the host, and prints one
line: the result, its error, the bound it is held to, and smoke timings
(wall seconds of the first call, compile included, and of a second call;
these are not benchmark numbers).  Each phase also prints the
``kernel.dispatch`` counters it traced (which implementation ran at that
N) and, for the routes that run Pallas kernels, the number of
``tpu_custom_call`` ops in the compiled program: a route that fell back
to the interpreter or to the jnp reference fails.

Bounds (float32 on the chip): exact logabsdet relative error 1e-5 and an
exact sign; ``precision="bf16"`` 5e-3 and an exact sign, on a matrix
whose smallest singular value is well above the bf16 rounding
(docs/api.md); the gradient 1e-4 relative Frobenius error against
``inv(A).T``; estimators within 3 of their own standard errors
(``sem``).  Any failed phase makes the exit code 1.  The last line of
standard output is one JSON object::

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}
"""
from __future__ import annotations

import argparse
import json
import sys
import tempfile
import threading
import time
import urllib.request
from pathlib import Path

import numpy as np

SRC = Path(__file__).resolve().parent / "src"

F32_RTOL = 1e-5
BF16_RTOL = 5e-3
GRAD_RTOL = 1e-4
EST_SEMS = 3.0

# one chip: (N of the paper's grid and beyond)
N_EXACT = 8000          # the paper's largest; not a multiple of 128
N_BIG = 16384           # a 1 GiB float32 buffer
N_GRAD = 4096
N_EST = 8192
N_EST_FUSED = 1024      # small enough for the VMEM-resident Chebyshev step
SERVE_SIZES = (100, 333, 700, 1024)
SERVE_BUCKETS = (128, 384, 768, 1024)
N_EXPORT = 512
# four chips: both updates, lookahead off and on
N_MESH = 16384


class Smoke:
    """Runs phases, records failures, prints one line per phase."""

    def __init__(self):
        self.failed: list = []

    def report(self, name: str, ok: bool, text: str) -> None:
        print(f"[{'ok' if ok else 'FAIL'}] {name}: {text}", flush=True)
        if not ok:
            self.failed.append(name)

    def phase(self, name: str, fn, *args, **kw):
        """Run one phase; an exception fails it without stopping the rest."""
        from repro import obs
        obs.reset()
        try:
            fn(self, name, *args, **kw)
        except Exception as exc:                # noqa: BLE001 — recorded
            import traceback
            traceback.print_exc()
            self.report(name, False, f"raised {type(exc).__name__}: {exc}")
        dispatch = dispatch_counts()
        print(f"    dispatch: {dispatch or '{}'}", flush=True)
        if any(b == "interpret" for (_, b) in dispatch):
            self.report(name, False, "a kernel ran in the interpreter")

    def kernels(self, name: str, text: str, want: bool = True) -> str:
        """Check the compiled program has (or lacks) Mosaic kernels."""
        n = text.count("tpu_custom_call")
        if want and n == 0:
            self.report(name, False, "no tpu_custom_call in the compiled "
                                     "program: the Pallas route was not taken")
        return f"tpu_custom_call x{n}"


def dispatch_counts() -> dict:
    """``{(op, backend): count}`` of the kernel.dispatch counters."""
    from repro import obs
    out = {}
    for key, v in obs.snapshot()["counters"].items():
        if key.startswith("kernel.dispatch{"):
            labels = dict(kv.split("=", 1)
                          for kv in key[len("kernel.dispatch{"):-1].split(","))
            out[(labels["op"], labels["backend"])] = int(v)
    return dict(sorted(out.items()))


def program_text(p, *args, **kw) -> str:
    """Compiled text of the executable plan ``p`` runs on ``args``: the
    same lowering as the call, so the compile cache answers it."""
    if p.compiled:
        return p._fwd.lower(*args, **kw).compile().as_text()
    from repro.core.plan import _parallel_kernel
    fn = _parallel_kernel(p.method, p.config, p.mesh, p.axis_name)
    return fn.lower(*args).compile().as_text()


def timed(fn, *args, **kw):
    """(result, first-call seconds, second-call seconds)."""
    import jax
    t0 = time.perf_counter()
    out = jax.block_until_ready(fn(*args, **kw))
    t1 = time.perf_counter()
    out2 = jax.block_until_ready(fn(*args, **kw))
    t2 = time.perf_counter()
    return out, out2, t1 - t0, t2 - t1


def general_matrix(n: int, seed: int) -> np.ndarray:
    """Dense Gaussian matrix, float32, generated in bulk."""
    return np.random.default_rng(seed).standard_normal(
        (n, n), dtype=np.float32)


def spd_matrix(n: int, seed: int) -> np.ndarray:
    """x x^T / n + 2 I in float32 (eigenvalues in about [2, 6])."""
    x = general_matrix(n, seed)
    a = x @ x.T / np.float32(n)
    a = (a + a.T) / np.float32(2)
    a[np.diag_indices(n)] += np.float32(2)
    return a


def negated_spd_matrix(n: int, seed: int) -> np.ndarray:
    """`spd_matrix` with its first row negated: determinant sign -1, and
    the same singular values (all >= 2)."""
    a = spd_matrix(n, seed)
    a[0] = -a[0]
    return a


def slogdet64(a: np.ndarray):
    s, ld = np.linalg.slogdet(a.astype(np.float64))
    return float(s), float(ld)


def exact_line(got, ref, rtol):
    """(ok, text) for a (sign, logabsdet) pair against a float64 one."""
    sign, ld = got
    err = abs(ld - ref[1]) / abs(ref[1])
    ok = sign == ref[0] and err <= rtol
    return ok, (f"sign {sign:+.0f} (ref {ref[0]:+.0f}) logabsdet {ld!r} "
                f"(ref {ref[1]!r}) rel_err {err:.3e} <= {rtol:g}")


# ------------------------------------------------------------------ phases

def exact_phase(sm: Smoke, name: str, a, ref, rtol=F32_RTOL,
                results=None, **plan_kw):
    import repro
    n = a.shape[0]
    p = repro.plan((n, n), **plan_kw)
    res, _, t1, t2 = timed(p, a)
    ok, text = exact_line((float(res.sign), float(res.logabsdet)), ref, rtol)
    cfg = p.config
    route = (f"{cfg.schedule}/{cfg.update} k={cfg.k} backend={cfg.backend}"
             + (" fused" if cfg.fused else "")
             + (f" {cfg.precision}" if cfg.precision else ""))
    kern = sm.kernels(name, program_text(p, a, key=None, probes=None))
    sm.report(name, ok, f"{route}: {text}; {kern}; smoke timing "
                        f"{t1:.2f} s first call, {t2:.2f} s second")
    if results is not None:
        results[name] = (float(res.sign), float(res.logabsdet))


def grad_phase(sm: Smoke, name: str, n: int, seed: int):
    import repro
    a = spd_matrix(n, seed)
    p = repro.plan((n, n), method="exact")
    t0 = time.perf_counter()
    res, g = p.value_and_grad(a)
    g = np.asarray(g, np.float64)
    t1 = time.perf_counter() - t0
    want = np.linalg.inv(a.astype(np.float64)).T
    err = np.linalg.norm(g - want) / np.linalg.norm(want)
    ok_v, text = exact_line((float(res.sign), float(res.logabsdet)),
                            slogdet64(a), F32_RTOL)
    ok = ok_v and err <= GRAD_RTOL
    sm.report(name, ok, f"grad rel_fro_err {err:.3e} <= {GRAD_RTOL:g}; "
                        f"value {text}; smoke timing {t1:.2f} s first call")


def estimator_phase(sm: Smoke, name: str, n: int, seed: int, method: str,
                    want_kernels: bool = False):
    import repro
    a = spd_matrix(n, seed)
    ref = slogdet64(a)[1]
    p = repro.plan((n, n), method=method)
    res, _, t1, t2 = timed(p, a)
    est, sem = float(res.logabsdet), float(res.sem)
    err = abs(est - ref)
    ok = np.isfinite(est) and err <= EST_SEMS * sem
    kern = sm.kernels(name, program_text(p, a, key=None, probes=None,
                                         lmin=None, lmax=None),
                      want=want_kernels)
    sm.report(name, ok, f"logabsdet {est!r} (ref {ref!r}) |err| {err:.4g} "
                        f"= {err / sem:.2f} sem (sem {sem:.4g}) <= "
                        f"{EST_SEMS:g} sem; {kern}; smoke timing "
                        f"{t1:.2f} s first call, {t2:.2f} s second")


def serve_phase(sm: Smoke, name: str, seed: int):
    """LogdetService + serve_http in this process; ~8 requests over HTTP."""
    import repro
    from repro.serve import LogdetService, ServeConfig
    from repro.serve.http import serve_http

    rng = np.random.default_rng(seed)
    mats = [rng.standard_normal((n, n), dtype=np.float32)
            for n in SERVE_SIZES]
    cfg = ServeConfig(buckets=SERVE_BUCKETS, max_batch=4, max_wait_ms=5.0)
    errs, fails = [], []
    with LogdetService(cfg) as svc:
        t0 = time.perf_counter()
        svc.warmup()
        t_warm = time.perf_counter() - t0
        warm = svc.trace_count()
        server = serve_http(svc, port=0)
        thread = threading.Thread(target=server.serve_forever, daemon=True)
        thread.start()
        base = f"http://127.0.0.1:{server.server_address[1]}/v1/logdet"

        def post(body):
            req = urllib.request.Request(
                base, data=json.dumps(body).encode(),
                headers={"Content-Type": "application/json"})
            with urllib.request.urlopen(req, timeout=600) as resp:
                return json.load(resp)

        def check(a, got):
            ref = slogdet64(a)
            err = abs(got["logabsdet"] - ref[1]) / abs(ref[1])
            errs.append(err)
            if got["sign"] != ref[0] or err > F32_RTOL:
                fails.append((a.shape[0], got, ref))

        try:
            t0 = time.perf_counter()
            # four single requests from concurrent clients...
            outs = [None] * len(mats)

            def client(i):
                outs[i] = post({"matrix": mats[i].tolist()})

            threads = [threading.Thread(target=client, args=(i,))
                       for i in range(len(mats))]
            for t in threads:
                t.start()
            for t in threads:
                t.join()
            for a, got in zip(mats, outs):
                check(a, got)
            # ...and one call carrying a batch of four
            batch = post({"matrices": [m.tolist() for m in mats]})
            for a, got in zip(mats, batch["results"]):
                check(a, got)
            t_req = time.perf_counter() - t0
        finally:
            server.shutdown()
            server.server_close()
        after = svc.trace_count()
    methods = sorted({o["method"] for o in outs + batch["results"]})
    ok = not fails and after == warm and len(errs) == 2 * len(mats)
    sm.report(name, ok,
              f"{len(errs)} requests n={SERVE_SIZES} methods={methods}: "
              f"max rel_err {max(errs):.3e} <= {F32_RTOL:g}, "
              f"{len(fails)} wrong; traces {warm} after warmup -> {after} "
              f"after requests; smoke timing warmup {t_warm:.2f} s, "
              f"requests {t_req:.2f} s")

    # export one plan and reload it: the same answer, bit for bit
    a = rng.standard_normal((N_EXPORT, N_EXPORT), dtype=np.float32)
    p = repro.plan((N_EXPORT, N_EXPORT), method="exact")
    want = float(p(a).logabsdet)
    with tempfile.TemporaryDirectory() as d:
        path = str(Path(d) / "plan.repro-plan")
        p.export(path)
        q = repro.load_plan(path)
        got = float(q(a).logabsdet)
    ok = got == want and q.trace_count == 0
    sm.report(name + " export", ok,
              f"n={N_EXPORT} loaded plan {got!r} == live plan {want!r}: "
              f"{got == want}; loaded-plan traces {q.trace_count}")


def device_bytes(key: str) -> list:
    """``memory_stats()[key]`` of every device (0 where not reported)."""
    import jax
    return [(d.memory_stats() or {}).get(key, 0) for d in jax.devices()]


def mesh_run(sm: Smoke, name: str, p, a, rows, label: str):
    """One call of the mesh plan ``p``: (sign, logabsdet), printed.  The
    program is lowered for a row-sharded input, so a host ``a`` never
    lands whole on one chip."""
    import jax
    t0 = time.perf_counter()
    res = jax.block_until_ready(p(a))
    t1 = time.perf_counter() - t0
    got = (float(res.sign), float(res.logabsdet))
    spec = jax.ShapeDtypeStruct(a.shape, np.float32, sharding=rows)
    kern = sm.kernels(name, program_text(p, spec))
    print(f"    mesh {label} k={p.config.k}: sign {got[0]:+.0f} logabsdet "
          f"{got[1]!r}; {kern}; smoke timing {t1:.2f} s first call",
          flush=True)
    return got


def mesh_phase(sm: Smoke, name: str):
    """The row-distributed mesh schedule over four chips, both updates
    with lookahead off and on, against the one-chip staged x panel result
    on the same matrix and float64.

    The first plan gets the host matrix and places its rows on their
    chips itself; the later ones get it row-sharded by ``device_put``.
    """
    import jax
    import repro
    from concurrent.futures import ThreadPoolExecutor
    from jax.sharding import NamedSharding, PartitionSpec
    from repro.launch.mesh import make_rows_mesh

    mesh = make_rows_mesh(jax.device_count())
    rows = NamedSharding(mesh, PartitionSpec("rows", None))
    n = N_MESH
    host = general_matrix(n, 11)
    with ThreadPoolExecutor(1) as pool:     # the float64 reference, meanwhile
        ref64 = pool.submit(slogdet64, host)
        mesh_res, a = {}, None
        for update in ("rank1", "panel"):
            for lookahead in (False, True):
                p = repro.plan((n, n), method="exact", schedule="mesh",
                               update=update, lookahead=lookahead, mesh=mesh)
                label = f"{update} n={n} lookahead={lookahead}"
                if a is None:
                    mesh_res[(update, lookahead)] = mesh_run(
                        sm, name, p, host, rows, label + " host input")
                    peaks = device_bytes("peak_bytes_in_use")
                    whole = 4 * n * n
                    sm.report(f"{name} placement",
                              min(peaks) > 0 and max(peaks) < 1.25 * min(peaks),
                              f"peak_bytes_in_use per device {peaks} = "
                              f"{[round(b / whole, 3) for b in peaks]} x the "
                              f"{n}^2 float32 matrix: balanced within 1.25x")
                    a = jax.device_put(host, rows)  # each chip gets its rows
                    print(f"    placed n={n}: shard bytes "
                          f"{[s.data.nbytes for s in a.addressable_shards]} "
                          f"of {whole}", flush=True)
                else:
                    mesh_res[(update, lookahead)] = mesh_run(
                        sm, name, p, a, rows, label)
            same = mesh_res[(update, False)] == mesh_res[(update, True)]
            sm.report(f"{name} {update} n={n} lookahead", same,
                      f"lookahead on == off bit for bit: {same}")
        del a

        one = repro.plan((n, n), method="exact", schedule="staged",
                         update="panel")
        r1 = one(host)
        ref1 = (float(r1.sign), float(r1.logabsdet))
        ref = ref64.result()
        for (update, lookahead), got in mesh_res.items():
            err = abs(got[1] - ref1[1]) / abs(ref1[1])
            ok_ref, ref_text = exact_line(got, ref, F32_RTOL)
            sm.report(f"{name} {update} lookahead={lookahead} vs references",
                      ok_ref and got[0] == ref1[0] and err <= 2 * F32_RTOL,
                      f"mesh {got!r} vs one-chip staged/panel {ref1!r}: "
                      f"rel diff {err:.3e} <= {2 * F32_RTOL:g}; vs float64: "
                      f"{ref_text}")


# -------------------------------------------------------------------- main

def run_one_chip(sm: Smoke) -> None:
    a = general_matrix(N_EXACT, 0)
    ref = slogdet64(a)
    fused_pair = {}
    base = dict(method="exact", schedule="staged")
    sm.phase(f"exact staged/rank1 N={N_EXACT}", exact_phase, a, ref,
             results=fused_pair, update="rank1", **base)
    sm.phase(f"exact staged/rank1 fused N={N_EXACT}", exact_phase, a, ref,
             results=fused_pair, update="rank1", fused=True, **base)
    same = len(set(fused_pair.values())) == 1 and len(fused_pair) == 2
    sm.report("fused == unfused (f32, bit for bit)", same,
              f"{list(fused_pair.values())}")
    sm.phase(f"exact staged/panel N={N_EXACT}", exact_phase, a, ref,
             update="panel", **base)
    sm.phase(f"exact auto (rtol=1e-5) N={N_EXACT}", exact_phase, a, ref,
             method="auto", rtol=1e-5)
    del a

    # bf16 rounds every GEMM operand, so its sign is that of a perturbed
    # matrix: exact only where the perturbation stays below the smallest
    # singular value, which a Gaussian N=8000 matrix (~1/sqrt(N)) is not.
    # A negative determinant keeps the sign check able to fail.
    a = negated_spd_matrix(N_EXACT, 6)
    sm.phase(f"exact staged/panel bf16 N={N_EXACT} det<0", exact_phase, a,
             slogdet64(a), rtol=BF16_RTOL, update="panel", precision="bf16",
             **base)
    del a

    a = general_matrix(N_BIG, 1)
    sm.phase(f"exact staged/panel N={N_BIG}", exact_phase, a, slogdet64(a),
             update="panel", **base)
    del a

    sm.phase(f"value_and_grad N={N_GRAD}", grad_phase, N_GRAD, 2)
    sm.phase(f"chebyshev N={N_EST}", estimator_phase, N_EST, 3, "chebyshev")
    sm.phase(f"slq N={N_EST}", estimator_phase, N_EST, 3, "slq")
    sm.phase(f"chebyshev fused N={N_EST_FUSED}", estimator_phase,
             N_EST_FUSED, 4, "chebyshev", want_kernels=True)
    sm.phase("serve", serve_phase, 5)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n", 1)[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the mesh schedule over four chips")
    args = ap.parse_args(argv)

    import jax
    if jax.default_backend() != "tpu":
        print(f"chip_smoke: needs a TPU; JAX's backend is "
              f"{jax.default_backend()!r}", file=sys.stderr)
        return 2
    devices = jax.devices()
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX sees "
              f"{len(devices)} device(s)", file=sys.stderr)
        return 2

    sys.path.insert(0, str(SRC))
    from repro import obs
    from repro.compile_cache import use_compile_cache
    use_compile_cache()
    obs.configure("metrics")       # kernel.dispatch counters per phase
    d0 = devices[0]
    print(f"device: {d0.platform} {d0.device_kind} x{len(devices)}; "
          f"jax {jax.__version__}; x64 {jax.config.jax_enable_x64}",
          flush=True)

    sm = Smoke()
    if args.chips == 4:
        sm.phase("mesh", mesh_phase)
    else:
        run_one_chip(sm)
    if sm.failed:
        print(f"chip_smoke: {len(sm.failed)} phase(s) failed: {sm.failed}",
              flush=True)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": d0.platform, "kind": d0.device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

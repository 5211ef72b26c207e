"""``repro.plan()`` — the compiled plan/execute log-determinant API.

The paper's pitch is picking the *right* algorithm for the scale at hand
(condensation vs Gaussian elimination vs ScaLAPACK vs the stochastic
estimators).  This module makes that decision happen **once**, at plan
time, the way ``jax.jit`` separates trace time from run time:

    p = repro.plan((4096, 4096), method="auto", mesh=mesh)
    res = p(a)              # LogdetResult: sign, logabsdet, sem, diagnostics
    res = p(a2)             # same compiled executable — no re-trace

A `LogdetPlan` is a frozen artifact holding

  * the problem spec (shape, batch, dtype, operator structure),
  * the *resolved* method — ``method="auto"`` runs the cost model below,
  * a validated typed config (`ExactConfig` | `ChebyshevConfig` |
    `SLQConfig` — see repro.core.configs) instead of a kwargs namespace,
  * the padding / sharding strategy, and
  * a pre-jitted callable (plus a lazily-built ``value_and_grad`` twin).

Every execution path returns the same `LogdetResult`; the legacy string
API (``repro.core.slogdet`` / ``logdet_batched``) survives as deprecated
shims over plans (see repro.core.api and docs/api.md for migration).

The cost model (`select_route` / `select_method`)
-------------------------------------------------
Inputs: N (and batch), the operator's `plan_hints()` (per-column matvec
FLOPs, materializability), the mesh device count, the requested accuracy
``rtol`` — and the **measured roofline calibration table**
(repro.core.calibration: sustained GEMM FLOP/s, streaming bandwidth, and
per-collective latency/bandwidth, produced by ``python -m
benchmarks.roofline --calibrate``).  Decision tree:

  1. operator input                          -> estimator family
     (only the matrix-free estimators run through the operator
     protocol; exact condensation needs the dense array itself);
  2. ``rtol`` < 1e-3 (more digits than Monte-Carlo noise allows at sane
     probe budgets)                          -> exact family;
  3. otherwise compare *modeled seconds* (not raw FLOPs): the best exact
     engine route vs ``(probe x step budget) x matvec_flops`` priced on
     the measured GEMM roofline; cheapest wins.  Because the mesh
     communication term (per-step collective latency + payload bytes)
     does not shrink with P, both the dense<->estimator and the
     serial<->mesh crossovers move with device count;
  4. family -> concrete route: the exact family resolves to an
     `EngineConfig` *tuple* (schedule x update x backend — e.g. staged x
     rank1 for small N, staged x panel once GEMMs amortize, mesh x panel
     when collectives pay for themselves); estimators pick ``chebyshev``
     when spectral bounds are already known, else ``slq``.
"""
from __future__ import annotations

import contextlib
import dataclasses
import math
import time
import warnings
from collections import OrderedDict
from dataclasses import dataclass, field
from typing import Any, Callable, Optional, Tuple

import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec

from repro import obs

from repro.core.calibration import (
    Calibration, estimator_cost, exact_cost, load_calibration,
)
from repro.core.configs import (
    ChebyshevConfig, ESTIMATOR_METHODS, EXACT_METHODS, ExactConfig,
    LEGACY_EXACT_ROUTES, LogdetConfig, METHODS, SLQConfig,
    config_for, filter_for_method as _filter_for_method, validate_config,
)
from repro.core.engine import EngineConfig, LEGACY_ROUTES
from repro.core.result import Diagnostics, LogdetResult

__all__ = ["plan", "LogdetPlan", "ProblemSpec", "spec_of", "select_method",
           "select_route", "clear_plan_cache"]

# probe-budget the selector assumes when none is configured yet: the SLQ
# defaults (bounds-free, the conservative estimator choice)
_DEFAULT_EST_COLS = 25 * 32
# Monte-Carlo noise floor: below this requested rtol, estimators would need
# absurd probe counts (error ~ 1/sqrt(k)); the selector goes exact
_EST_RTOL_FLOOR = 1e-3
# spectral_bounds: 2 power iterations of 32 steps + 1 closing matvec each
_BOUNDS_COLS = 2 * (32 + 1)


def _is_tracer(x) -> bool:
    return isinstance(x, jax.core.Tracer)


def _mark_trace(trace_log: list) -> None:
    """Record one trace of a compiled forward.  Runs inside the jitted
    body, i.e. exactly once per (re)trace — the second and later marks on
    one plan are retraces, the thing a spec-stable workload must not do."""
    trace_log.append(1)
    obs.inc("plan.traces")
    if len(trace_log) > 1:
        obs.inc("plan.retraces")


# --------------------------------------------------------------------------
# problem specification
# --------------------------------------------------------------------------

@dataclass(frozen=True)
class ProblemSpec:
    """What a plan is compiled *for* — everything shape-static.

    ``kind``          "dense" (n, n) | "batched" (B, n, n) | "operator"
    ``n``             matrix side
    ``batch``         leading stack size, or None
    ``dtype``         canonical dtype string ("float64", ...)
    ``structure``     operator structure tag from `plan_hints` ("dense",
                      "kron", "toeplitz", ...) — "dense"/"batched" for
                      array inputs
    ``matvec_flops``  FLOPs one matvec column costs (cost-model input)
    ``materializable`` whether exact O(n^3) methods can run on this input
    ``device_count``  devices the operator's own matvec spans
    """
    kind: str
    n: int
    batch: Optional[int]
    dtype: str
    structure: str
    matvec_flops: float
    materializable: bool = True
    device_count: int = 1


def _dense_spec(shape: Tuple[int, ...], dtype) -> ProblemSpec:
    if len(shape) == 2 and shape[0] == shape[1]:
        n, batch, kind = int(shape[0]), None, "dense"
    elif len(shape) == 3 and shape[1] == shape[2]:
        n, batch, kind = int(shape[1]), int(shape[0]), "batched"
    else:
        raise ValueError(
            f"expected square matrix (n, n) or stack (B, n, n), got {shape}")
    dtype = jnp.dtype(dtype) if dtype is not None else jnp.result_type(float)
    dtype = jax.dtypes.canonicalize_dtype(dtype)   # f64 -> f32 sans x64
    return ProblemSpec(kind=kind, n=n, batch=batch, dtype=str(dtype),
                       structure=kind, matvec_flops=2.0 * n * n,
                       materializable=True)


def spec_of(x, dtype=None) -> ProblemSpec:
    """Coerce an input — int N, shape tuple, array, operator, or an
    existing spec — into a `ProblemSpec` for planning."""
    if isinstance(x, ProblemSpec):
        return x
    from repro.estimators.operators import is_operator
    if is_operator(x):
        hints = x.plan_hints()
        return ProblemSpec(
            kind="operator", n=int(x.shape[-1]),
            batch=getattr(x, "batch", None), dtype=str(jnp.dtype(x.dtype)),
            structure=hints.structure, matvec_flops=float(hints.matvec_flops),
            materializable=bool(hints.materializable),
            device_count=int(hints.device_count))
    if isinstance(x, int):
        return _dense_spec((x, x), dtype)
    if isinstance(x, tuple):
        return _dense_spec(x, dtype)
    arr_dtype = getattr(x, "dtype", None)
    shape = getattr(x, "shape", None)
    if shape is None:
        raise TypeError(
            f"cannot plan for {type(x).__name__}; pass a size, shape tuple, "
            "array, stack, or LinearOperator")
    return _dense_spec(tuple(shape), dtype if dtype is not None else arr_dtype)


# --------------------------------------------------------------------------
# cost model
# --------------------------------------------------------------------------

# panel updates cannot amortize their triangular-solve bookkeeping below a
# few panels' worth of rows; the selector only offers them above this.
# The panel width itself comes from the calibration-driven tile autotuner
# (repro.kernels.autotune) so the selector prices the geometry the
# kernels actually run.
_PANEL_MIN_N_FACTOR = 4
# below this modeled exact wall time there is nothing worth trading:
# Monte-Carlo noise buys ~2-3 digits, so the estimator family only wins
# when exact condensation is actually expensive
_EXACT_FREE_SECONDS = 0.05


def select_route(x, *, mesh=None, axis_name: str = "rows",
                 rtol: Optional[float] = None,
                 bounds_known: bool = False,
                 est_cols: Optional[int] = None,
                 calibration: Optional[Calibration] = None,
                 precision: Optional[str] = None,
                 ) -> Tuple[str, Optional[EngineConfig]]:
    """Resolve ``method="auto"`` to a route **tuple**.

    Returns ``(method, engine_config)``: the estimator methods carry
    ``None`` (they have no engine axes); the exact family returns
    ``("exact", EngineConfig(schedule, update, panel_k, backend))`` — the
    cheapest engine instantiation under the measured calibration table
    (`repro.core.calibration.load_calibration` unless ``calibration`` is
    given), with ``panel_k`` resolved by the tile autotuner.
    ``precision="bf16"`` prices GEMM work at the bf16 calibration rate
    and restricts the search to the exact family (the mixed-precision
    route is an engine feature; estimators have no bf16 path).  Pure and
    cheap — call it directly to ask "what would the planner do" without
    building a plan.
    """
    spec = spec_of(x)
    devices = int(mesh.shape[axis_name]) if mesh is not None \
        else spec.device_count
    est_method = "chebyshev" if bounds_known else "slq"

    if spec.kind == "operator":
        # only the matrix-free estimators run on operator inputs (plan
        # rejects exact methods for them), whatever the cost comparison
        # says — `materializable` is advisory, not a dispatch route
        return est_method, None

    cal = calibration if calibration is not None else load_calibration()
    itemsize = jnp.dtype(spec.dtype).itemsize
    route, exact_t = _best_exact_route(spec, devices, cal, itemsize,
                                       precision=precision)

    if precision == "bf16":
        # the quantized-GEMM route only exists in the exact engine
        return "exact", route
    if rtol is not None and rtol < _EST_RTOL_FLOOR:
        return "exact", route

    cols = est_cols if est_cols is not None \
        else _DEFAULT_EST_COLS + _BOUNDS_COLS
    est_t = estimator_cost(spec.n, cols, spec.matvec_flops, devices, cal,
                           itemsize=itemsize, batch=spec.batch or 1)
    # estimators trade digits (and the sign) for time: only leave the
    # exact family when exact is both slow enough to care about AND
    # modeled slower than the estimator budget
    if exact_t <= _EXACT_FREE_SECONDS or exact_t <= est_t:
        return "exact", route
    return est_method, None


def select_method(x, *, mesh=None, axis_name: str = "rows",
                  rtol: Optional[float] = None,
                  bounds_known: bool = False,
                  est_cols: Optional[int] = None,
                  calibration: Optional[Calibration] = None) -> str:
    """The method name `select_route` resolves to (family-level answer)."""
    return select_route(x, mesh=mesh, axis_name=axis_name, rtol=rtol,
                        bounds_known=bounds_known, est_cols=est_cols,
                        calibration=calibration)[0]


def _best_exact_route(spec: ProblemSpec, devices: int, cal: Calibration,
                      itemsize: int, precision: Optional[str] = None,
                      ) -> Tuple[EngineConfig, float]:
    """Cheapest exact engine instantiation under the calibration table."""
    from repro.kernels.autotune import resolved_panel_k
    n, b = spec.n, spec.batch or 1
    tuned_k = resolved_panel_k(n, itemsize=itemsize, precision=precision,
                               cal=cal)
    if spec.batch is not None:
        # stacks run one matrix per device (vmapped serial schedule)
        candidates = [("serial", "rank1", 1, False),
                      ("serial", "panel", 1, False)]
    else:
        candidates = [("staged", "rank1", 1, False),
                      ("staged", "panel", 1, False)]
        if devices > 1:
            # each mesh route is offered plain and pipelined — lookahead
            # hides broadcast latency behind the bulk update, so the
            # serial<->mesh crossover moves left when its overhead term
            # is smaller than the hidden communication
            candidates += [("mesh", "rank1", devices, False),
                           ("mesh", "panel", devices, False),
                           ("mesh", "rank1", devices, True),
                           ("mesh", "panel", devices, True)]
    if n < _PANEL_MIN_N_FACTOR * tuned_k:
        candidates = [c for c in candidates if c[1] != "panel"]

    def cost_of(c):
        schedule, update, devs, la = c
        return exact_cost(n, devs, cal, update=update,
                          panel_k=tuned_k, itemsize=itemsize,
                          batch=b, lookahead=la, precision=precision)

    best = min(candidates, key=cost_of)
    schedule, update, devs, la = best
    return EngineConfig(schedule=schedule, update=update,
                        panel_k=tuned_k, lookahead=la,
                        precision=precision), cost_of(best)


def _flops_est(method: str, spec: ProblemSpec, cfg: LogdetConfig,
               devices: int) -> Tuple[Optional[int], float]:
    """(matvec_cols, flops_est) diagnostics for the resolved path."""
    b = spec.batch or 1
    if method in EXACT_METHODS:
        return None, b * (2.0 / 3.0) * spec.n ** 3 / devices
    if isinstance(cfg, ChebyshevConfig):
        cols = cfg.degree * cfg.num_probes
        if cfg.lmin is None or cfg.lmax is None:
            cols += _BOUNDS_COLS
    else:
        cols = min(cfg.num_steps, spec.n) * cfg.num_probes
    return cols, b * cols * spec.matvec_flops / devices


# --------------------------------------------------------------------------
# execution builders
# --------------------------------------------------------------------------

def _is_mesh_exact(method: str, cfg: LogdetConfig) -> bool:
    """Does this exact method distribute one matrix over a mesh?"""
    if method in ("pge", "plu"):
        return True
    return (method == "exact" and isinstance(cfg, ExactConfig)
            and cfg.schedule == "mesh")


def _serial_exact_core(method: str, cfg: ExactConfig) -> Callable:
    from repro.core import engine as _engine
    from repro.core import gaussian as _gaussian
    from repro.core.api import pad_to_multiple
    if method == "ge":
        return _gaussian.slogdet_ge
    if method == "exact":
        ecfg = cfg.engine_config()
        fn = _engine.build_serial(ecfg)
        if ecfg.update == "panel":
            # pad so every panel is full; diag(A, I) preserves the result
            k = ecfg.panel_k
            return lambda x: fn(pad_to_multiple(x, k))
        return fn
    raise AssertionError(method)


# parallel executables are expensive to build (shard_map closure + jit);
# plans share them through this cache — the successor of the lru_cache
# that used to sit on repro.core.api._parallel_fn
_KERNEL_CACHE: dict = {}


def _parallel_kernel(method: str, cfg: ExactConfig, mesh, axis_name: str):
    if method == "exact":
        key = ("engine", cfg.engine_config(), mesh, axis_name)
    else:
        key = (method, mesh, axis_name, cfg.nb)
    fn = _KERNEL_CACHE.get(key)
    if fn is None:
        from repro.core import engine as _engine
        from repro.core import gaussian as _gaussian
        from repro.core import scalapack as _scalapack
        if method == "exact":
            fn = _engine.build_mesh(cfg.engine_config(), mesh, axis_name)
        elif method == "pge":
            fn = _gaussian.parallel_slogdet_ge(mesh, axis_name)
        elif method == "plu":
            fn = _scalapack.parallel_slogdet_lu(mesh, axis_name, nb=cfg.nb)
        else:
            raise AssertionError(method)
        _KERNEL_CACHE[key] = fn
    return fn


def _shard_rows(a, dtype, mesh, axis_name: str, mult: int):
    """``a`` as ``dtype``, embedded in ``diag(a, I)`` to a multiple of
    ``mult`` rows, row-sharded over ``mesh``.

    A host array goes straight to its shards, so no device ever holds
    the whole matrix; a device array is resharded where it lives.
    """
    if _is_tracer(a):
        return jnp.asarray(a, dtype)
    from repro.core.api import pad_to_multiple
    rows = NamedSharding(mesh, PartitionSpec(axis_name, None))
    if isinstance(a, jax.Array):
        return jax.device_put(pad_to_multiple(a.astype(dtype), mult), rows)
    a = np.asarray(a, dtype)
    n = a.shape[0]
    pad = (-n) % mult
    if pad:
        a = np.pad(a, ((0, pad), (0, pad)))
        a[np.arange(n, n + pad), np.arange(n, n + pad)] = 1
    return jax.device_put(a, rows)


def _widen_bounds_for_padding(kw: dict) -> dict:
    """diag(A, I) padding adds unit eigenvalues: user-supplied Chebyshev
    bounds must be widened to bracket 1, else T_j blows up outside [-1, 1]
    on the padded directions."""
    kw = dict(kw)
    if kw.get("lmin") is not None:
        kw["lmin"] = min(float(kw["lmin"]), 1.0)
    if kw.get("lmax") is not None:
        kw["lmax"] = max(float(kw["lmax"]), 1.0)
    return kw


def _build_forward(spec: ProblemSpec, method: str, cfg: LogdetConfig,
                   mesh, axis_name: str, dtype, trace_log: list):
    """(fwd, compiled, padded_n): fwd maps execution inputs to the raw
    ``(sign, logabsdet, sem)`` triple; ``compiled`` says whether fwd is a
    pre-jitted executable (vs an eager composition of cached inner jits)."""
    from repro.core.api import pad_to_multiple

    padded_n = spec.n

    if method in EXACT_METHODS:
        from repro.estimators.grad import exact_slogdet_vjp

        if _is_mesh_exact(method, cfg):
            if mesh is None:
                raise ValueError(f"method {method!r} requires a mesh")
            p = int(mesh.shape[axis_name])
            mult = int(np.lcm(p, cfg.nb)) if method == "plu" else p
            padded_n = -(-spec.n // mult) * mult if spec.n else 0
            pfn = _parallel_kernel(method, cfg, mesh, axis_name)
            wrapped = exact_slogdet_vjp(
                lambda x: pfn(pad_to_multiple(x, mult)))

            def fwd(a, key=None, probes=None):  # eager: pfn is jitted inside
                trace_log.append(1)
                s, ld = wrapped(_shard_rows(a, dtype, mesh, axis_name, mult))
                return s, ld, jnp.zeros(ld.shape, ld.dtype)

            return fwd, False, padded_n

        if method == "exact" and cfg.update == "panel":
            padded_n = -(-spec.n // cfg.k) * cfg.k if spec.n else 0
        core = _serial_exact_core(method, cfg)
        wrapped = exact_slogdet_vjp(core)
        call = jax.vmap(wrapped) if spec.batch is not None else wrapped

        def fwd(a, key=None, probes=None):
            # body runs at trace time: the span measures staging cost
            with obs.span("plan.trace", cat="trace", method=method):
                _mark_trace(trace_log)
                a = jnp.asarray(a, dtype)
                s, ld = call(a)
                return s, ld, jnp.zeros(ld.shape, ld.dtype)

        return jax.jit(fwd), True, padded_n

    # ---------------------------------------------------------- estimators
    est_kw = cfg.estimator_kwargs()

    def _merge_bounds(base_kw, lmin, lmax, widen: bool):
        """Config bounds overridden by runtime (possibly traced) bounds;
        padding still widens the runtime values to bracket 1."""
        if lmin is None and lmax is None:
            return base_kw
        kw = dict(base_kw)
        if lmin is not None:
            kw["lmin"] = jnp.minimum(jnp.asarray(lmin, dtype), 1.0) \
                if widen else lmin
        if lmax is not None:
            kw["lmax"] = jnp.maximum(jnp.asarray(lmax, dtype), 1.0) \
                if widen else lmax
        return kw

    if spec.kind == "operator":
        # eager: the operator instance carries its own (cached) inner jits;
        # estimate_logdet handles registry lookup / probe sharing / VJPs
        def fwd(op, key=None, probes=None, lmin=None, lmax=None):
            from repro import estimators as _est
            trace_log.append(1)
            kw = _merge_bounds(est_kw, lmin, lmax, widen=False)
            res = _est.estimate_logdet(op, method=method, key=key,
                                       probes=probes, **kw)
            return jnp.ones(res.est.shape, res.est.dtype), res.est, res.sem

        return fwd, False, padded_n

    if mesh is not None:
        p = int(mesh.shape[axis_name])
        padded_n = -(-spec.n // p) * p if spec.n else 0
        padded = padded_n != spec.n
        pad_kw = _widen_bounds_for_padding(est_kw) if padded else est_kw

        def fwd(a, key=None, probes=None, lmin=None, lmax=None):
            # eager: ShardedOperator construction (device_put) inside
            from repro import estimators as _est
            trace_log.append(1)
            a = jnp.asarray(a, dtype)
            op = _est.ShardedOperator(pad_to_multiple(a, p), mesh, axis_name)
            kw = _merge_bounds(pad_kw, lmin, lmax, widen=padded)
            res = _est.estimate_logdet(op, method=method, key=key,
                                       probes=probes, **kw)
            return jnp.ones(res.est.shape, res.est.dtype), res.est, res.sem

        return fwd, False, padded_n

    def fwd(a, key=None, probes=None, lmin=None, lmax=None):
        from repro import estimators as _est
        # body runs at trace time: the span measures staging cost
        with obs.span("plan.trace", cat="trace", method=method):
            _mark_trace(trace_log)
            a = jnp.asarray(a, dtype)
            kw = _merge_bounds(est_kw, lmin, lmax, widen=False)
            res = _est.estimate_logdet(a, method=method, key=key,
                                       probes=probes, **kw)
            return jnp.ones(res.est.shape, res.est.dtype), res.est, res.sem

    return jax.jit(fwd), True, padded_n


def _build_value_and_grad(spec: ProblemSpec, method: str, cfg: LogdetConfig,
                          mesh, axis_name: str, dtype, fwd):
    """vag(x, key) -> ((sign, logabsdet, sem), grad, cg_iters|None).

    The gradient of ``logabsdet`` (summed over the batch for stacks) with
    respect to the input — the dense matrix entries, or the operator's own
    parameters for structured inputs.  ``fwd`` is the plan's OWN compiled
    forward (shared, so building the backward never re-traces it).
    Estimator paths run the Hutchinson pullback explicitly (same probes as
    the forward, one transposed CG solve) so the solve's iteration count
    surfaces as a diagnostic instead of vanishing inside a custom-VJP
    rule.
    """
    from repro.core.api import pad_to_multiple

    if method in EXACT_METHODS:
        def vag(a, key=None):
            # mirror __call__'s kwarg structure so the jit cache is shared
            out = fwd(a, key=None, probes=None)
            a = jnp.asarray(a, dtype)
            if a.shape[-1] == 0:
                return out, jnp.zeros_like(a), None
            # one batched LAPACK inverse — the analytic pullback A^{-T}
            bar = jnp.swapaxes(jnp.linalg.inv(a), -1, -2).astype(a.dtype)
            return out, bar, None

        return vag

    est_kw = cfg.estimator_kwargs()
    probe_kw = {"num_probes": cfg.num_probes}
    if isinstance(cfg, ChebyshevConfig):
        probe_kw["probe_kind"] = cfg.probe_kind
    # bounds widening must mirror the forward exactly: only when the mesh
    # embedding actually padded (diag(A, I) adds unit eigenvalues)
    pad_widens = False
    if mesh is not None and spec.kind != "operator":
        pad_widens = spec.n % int(mesh.shape[axis_name]) != 0

    def vag(x, key=None):
        from repro import estimators as _est
        from repro.estimators.grad import (
            hutchinson_pullback, operator_grad_info, shared_probes,
        )
        if spec.kind != "operator":
            x = jnp.asarray(x, dtype)
            if mesh is not None:
                p = int(mesh.shape[axis_name])
                x = pad_to_multiple(x, p)
                op = _est.ShardedOperator(x, mesh, axis_name)
            else:
                op = _est.as_operator(x)
        else:
            op = x
        info = operator_grad_info(op)
        if info is None:
            raise TypeError(
                f"value_and_grad needs a grad-registered operator; "
                f"{type(op).__name__} has no registration (see "
                "repro.estimators.register_operator_grad)")
        if key is None:
            key = jax.random.PRNGKey(cfg.seed)
        probes = shared_probes(method, op, key, probe_kw)
        kw = _widen_bounds_for_padding(est_kw) if pad_widens else est_kw
        res = _est.estimate_logdet(op, method=method, key=key,
                                   probes=probes, **kw)
        g = jnp.ones(res.est.shape, res.est.dtype)
        bar, cg = hutchinson_pullback(
            op, info.params(op), probes, g, info=info,
            cg_tol=cfg.grad_cg_tol, cg_maxiter=cfg.grad_cg_maxiter)
        if mesh is not None and spec.kind != "operator":
            # d logdet(diag(A, I))/dA is exactly the A-block of the padded
            # pullback; the identity block's cotangent is discarded
            bar = bar[..., :spec.n, :spec.n]
        sign = jnp.ones(res.est.shape, res.est.dtype)
        return (sign, res.est, res.sem), bar, cg.iters

    return vag


# --------------------------------------------------------------------------
# the plan artifact
# --------------------------------------------------------------------------

@dataclass(frozen=True)
class LogdetPlan:
    """A compiled log-determinant computation: spec + typed config +
    resolved strategy + pre-jitted executable.  Build with `repro.plan`;
    call with data; reuse freely — repeated calls with spec-matching
    inputs hit the jit cache, never re-trace.
    """
    spec: ProblemSpec
    method: str                     # resolved (never "auto")
    config: LogdetConfig
    mesh: Any = None
    axis_name: str = "rows"
    grad: bool = False
    validate: bool = True
    compiled: bool = field(default=True)
    diagnostics: Diagnostics = field(default_factory=Diagnostics)
    _fwd: Callable = field(default=None, repr=False, compare=False)
    _trace_log: list = field(default_factory=list, repr=False, compare=False)
    _cache: dict = field(default_factory=dict, repr=False, compare=False)
    _bound: Any = field(default=None, repr=False, compare=False)

    # -------------------------------------------------------------- exec

    def __call__(self, a=None, *, key=None, probes=None,
                 lmin=None, lmax=None) -> LogdetResult:
        """Execute the plan -> `LogdetResult`.

        ``a`` may be omitted when the plan was built from a concrete array
        or operator (it stays bound to the plan).  ``key``/``probes``/
        ``lmin``/``lmax`` are estimator-only runtime inputs — fresh
        randomness or traced spectral bounds without recompiling.
        """
        x = self._input(a)
        self._check(x, key, probes, lmin, lmax)
        traced = any(_is_tracer(v) for v in (x, key, probes, lmin, lmax))
        tele = not traced and obs.trace_enabled()
        if tele:
            # isolate this execution's telemetry from earlier buffered
            # streams (direct estimator calls, interleaved plans)
            obs.flush_telemetry()
            obs.drain_telemetry()
        t0 = None if traced else time.perf_counter()
        span = contextlib.nullcontext() if traced else \
            obs.span("plan.execute", method=self.method)
        with span:
            args = (x, key, probes, lmin, lmax)
            sign, ld, sem = self._forward(*args) if traced else \
                self._dispatch("fwd", self._forward, *args)
            diags = self.diagnostics
            if not traced:
                with obs.span("plan.wait"):
                    jax.block_until_ready(ld)
                wall = time.perf_counter() - t0
                conv = None
                if tele:
                    obs.flush_telemetry()
                    conv = obs.drain_telemetry() or None
                    if conv:
                        self._cache["last_convergence"] = conv
                diags = dataclasses.replace(
                    diags, wall_time_s=wall, convergence=conv)
                obs.inc("plan.executions", method=self.method)
                if self.method in ESTIMATOR_METHODS:
                    obs.inc("estimator.probes", self.config.num_probes)
        return LogdetResult(sign=sign, logabsdet=ld, sem=sem,
                            method_used=self.method, diagnostics=diags)

    def _forward(self, x, key, probes, lmin, lmax):
        if self.method in EXACT_METHODS:
            return self._fwd(x, key=None, probes=None)
        return self._fwd(x, key=key, probes=probes, lmin=lmin, lmax=lmax)

    def _dispatch(self, entry: str, fn: Callable, *args, **kwargs):
        """``fn(*args, **kwargs)`` inside a ``plan.dispatch`` span.  The
        plan's first dispatch of ``entry`` also nests a ``plan.compile``
        span: tracing, lowering and compiling, or the load from the
        persistent cache, happen there."""
        with obs.span("plan.dispatch"):
            done = self._cache.setdefault("dispatched", set())
            if entry in done:
                return fn(*args, **kwargs)
            with obs.span("plan.compile", cat="compile", entry=entry):
                out = fn(*args, **kwargs)
            done.add(entry)
            return out

    def slogdet(self, a=None, *, key=None, probes=None, lmin=None,
                lmax=None):
        """Raw ``(sign, logabsdet)`` pair — the legacy-shim entry point.

        Skips input validation and diagnostics assembly: exactly the
        compiled computation, nothing else.
        """
        x = self._input(a)
        if self.method in EXACT_METHODS:
            sign, ld, _ = self._fwd(x, key=key, probes=probes)
        else:
            sign, ld, _ = self._fwd(x, key=key, probes=probes,
                                    lmin=lmin, lmax=lmax)
        return sign, ld

    def logdet(self, a=None, *, key=None, probes=None, lmin=None,
               lmax=None) -> jax.Array:
        """``log|det|`` alone — differentiable, jit/vmap-composable."""
        return self.slogdet(a, key=key, probes=probes, lmin=lmin,
                            lmax=lmax)[1]

    def value_and_grad(self, a=None, *, key=None):
        """Execute forward AND backward -> ``(LogdetResult, grad)``.

        ``grad`` is d(sum of logabsdet)/d(input): matrix-shaped for dense
        input, parameter-shaped for structured operators (Kronecker
        factors, Toeplitz column/row, stencil bands).  Estimator plans
        report the backward CG solve's iteration count in
        ``result.diagnostics.cg_iters``.
        """
        x = self._input(a)
        self._check(x, key, None)
        traced = _is_tracer(x) or _is_tracer(key)
        tele = not traced and obs.trace_enabled()
        if tele:
            obs.flush_telemetry()
            obs.drain_telemetry()
        t0 = None if traced else time.perf_counter()
        span = contextlib.nullcontext() if traced else \
            obs.span("plan.backward", method=self.method)
        with span:
            vag = self._cache.get("vag")
            if vag is None:
                vag = _build_value_and_grad(
                    self.spec, self.method, self.config, self.mesh,
                    self.axis_name, jnp.dtype(self.spec.dtype), self._fwd)
                self._cache["vag"] = vag
            (sign, ld, sem), bar, cg_iters = vag(x, key=key) if traced \
                else self._dispatch("vag", vag, x, key=key)
            diags = self.diagnostics
            if not traced:
                with obs.span("plan.wait"):
                    jax.block_until_ready(bar)
                wall = time.perf_counter() - t0
                conv = None
                if tele:
                    obs.flush_telemetry()
                    conv = obs.drain_telemetry() or None
                    if conv:
                        self._cache["last_convergence"] = conv
                iters = None if cg_iters is None else int(cg_iters)
                if iters is not None:
                    obs.observe("cg.iters", iters, method=self.method)
                diags = dataclasses.replace(
                    diags, wall_time_s=wall, cg_iters=iters, convergence=conv)
        result = LogdetResult(sign=sign, logabsdet=ld, sem=sem,
                              method_used=self.method, diagnostics=diags)
        return result, bar

    # ----------------------------------------------------------- helpers

    @property
    def trace_count(self) -> int:
        """Times the forward computation has been traced (compiled plans)
        or executed (eager mesh/operator plans).  A spec-stable workload
        through a compiled plan holds this at 1."""
        return len(self._trace_log)

    def audit(self, passes=None, include_grad: bool = False):
        """Statically audit this plan's lowering -> `AuditReport`.

        Lowers a fresh forward (and, with ``include_grad``, the backward)
        at the plan's avals and runs the registered IR checker passes:
        no dense factorizations on matrix-free paths, no host callbacks
        with observability off, collective payloads within their analytic
        bounds, dtype discipline, and stage coverage.  Never executes or
        re-traces the live plan.  See docs/analysis.md.
        """
        from repro.analysis.audit import audit_plan
        return audit_plan(self, pass_ids=passes, include_grad=include_grad)

    def export(self, path: str) -> str:
        """AOT-serialize this plan's compiled forward to ``path``.

        The artifact carries a device-fingerprint header and replays
        bit-identically via `repro.load_plan` in any matching process —
        with zero traces and zero compiles at load or request time.
        Only compiled, non-operator plans are exportable; see
        repro.serve.aot for the full contract.
        """
        from repro.serve.aot import export_plan
        return export_plan(self, path)

    def explain(self) -> str:
        """Human-readable report of what this plan resolved to and what
        it has observed: route, modeled cost, trace/retrace state, and —
        after an execution under ``REPRO_OBS=trace`` — the most recent
        convergence telemetry.  Purely observational; no device work.
        """
        spec, d = self.spec, self.diagnostics
        shape = f"n={spec.n}" if spec.batch is None \
            else f"batch={spec.batch} n={spec.n}"
        lines = [
            f"LogdetPlan[{self.method}]",
            f"  spec: {spec.kind} {shape} dtype={spec.dtype} "
            f"structure={spec.structure}",
            f"  config: {self.config}",
            f"  execution: {'compiled (jit)' if self.compiled else 'eager'}"
            f", devices={d.device_count}"
            + (f", padded {spec.n} -> {d.padded_n}"
               if d.padded_n not in (None, spec.n) else ""),
            f"  traces: {self.trace_count}"
            + ("" if not self.compiled or self.trace_count <= 1
               else f"  (RETRACED {self.trace_count - 1}x — check input "
                    f"shapes/dtypes)"),
            f"  modeled cost: flops_est={d.flops_est:.3g}"
            + (f", matvec_cols={d.matvec_cols}"
               if d.matvec_cols is not None else "")
            + (f", backward cg_iters={d.cg_iters}"
               if d.cg_iters is not None else ""),
        ]
        if self.method == "exact" and isinstance(self.config, ExactConfig):
            from repro.kernels.autotune import tile_config
            prec = self.config.precision
            tiles = tile_config(spec.n,
                                itemsize=jnp.dtype(spec.dtype).itemsize,
                                precision=prec)
            lines.insert(3, f"  precision: {prec or 'native'}"
                         + (" (bf16 GEMM operands, full-precision "
                            "accumulators)" if prec == "bf16" else ""))
            lines.insert(4, f"  tiles[{tiles.source}]: "
                         f"panel_k={self.config.k} "
                         f"(autotuned {tiles.panel_k}), "
                         f"block={tiles.block_m}x{tiles.block_n}")
        conv = self._cache.get("last_convergence")
        if conv:
            lines.append("  last convergence (REPRO_OBS=trace):")
            for name, vals in sorted(conv.items()):
                finite = [v for v in vals if math.isfinite(v)]
                final = f"{finite[-1]:.3g}" if finite else "n/a"
                lines.append(
                    f"    {name}: {len(vals)} points, final {final}")
        elif obs.trace_enabled() and self.method not in EXACT_METHODS:
            lines.append("  last convergence: none recorded yet "
                         "(execute the plan first)")
        if obs.metrics_enabled():
            hits = obs.counter_value("plan.cache.hits")
            misses = obs.counter_value("plan.cache.misses")
            lines.append(f"  obs[{obs.mode()}]: plan cache "
                         f"{hits:g} hits / {misses:g} misses "
                         f"(process-wide)")
        else:
            lines.append("  obs: off (set REPRO_OBS=metrics|trace for "
                         "counters and convergence telemetry)")
        return "\n".join(lines)

    def _input(self, a):
        if a is None:
            a = self._bound
        if a is None:
            raise TypeError(
                "this plan was built from a shape spec; pass the matrix "
                "(or operator) to execute on")
        if self.spec.kind != "operator":
            shape = tuple(getattr(a, "shape", ()))
            want = ((self.spec.n, self.spec.n) if self.spec.batch is None
                    else (self.spec.batch, self.spec.n, self.spec.n))
            if shape != want:
                raise ValueError(
                    f"plan was compiled for shape {want}, got {shape}")
        return a

    def _check(self, x, key, probes, lmin=None, lmax=None):
        if self.method in EXACT_METHODS:
            if any(v is not None for v in (key, probes, lmin, lmax)):
                raise TypeError(f"exact method {self.method!r} takes no "
                                "key/probes/bounds")
            return
        if (self.validate and self.spec.kind != "operator"
                and not _is_tracer(x)):
            _validate_spd_like(x, self.method)


def _validate_spd_like(a, method: str):
    """Necessary-condition SPD screen for dense inputs routed to
    estimators: symmetry and a positive diagonal — catches the
    silent-garbage case (estimators compute tr(log A), which is
    meaningless for non-SPD input) with a clear error instead.  Runs as
    O(n^2) reductions on-device; only the three scalars cross to host."""
    x = jnp.asarray(a)
    if x.size == 0:
        return
    stats = jnp.stack([jnp.max(jnp.abs(x)),
                       jnp.max(jnp.abs(x - jnp.swapaxes(x, -1, -2))),
                       jnp.min(jnp.diagonal(x, axis1=-2, axis2=-1))])
    scale, asym, dmin = (float(v) for v in np.asarray(stats))  # ONE sync
    scale = scale or 1.0
    # sqrt(eps) * scale: far above accumulated GEMM rounding asymmetry of
    # symmetric products (~n*eps), far below any structural asymmetry
    tol = float(np.sqrt(jnp.finfo(x.dtype).eps)) * scale
    if asym > tol:
        raise ValueError(
            f"estimator method {method!r} computes tr(log A) and assumes "
            f"symmetric positive-definite input, but the matrix is not "
            f"symmetric (max |A - A^T| = {asym:.3g}). Use an exact method "
            f"('mc', 'ge', 'pmc', ...) for general matrices, pass "
            f"validate=False to repro.plan to skip this check, or "
            f"symmetrize the input.")
    if dmin <= 0:
        raise ValueError(
            f"estimator method {method!r} assumes positive-definite input, "
            f"but the diagonal has non-positive entries (min = {dmin:.3g}) "
            f"— tr(log A) is undefined. Use an exact method for indefinite "
            f"matrices, or pass validate=False to repro.plan to skip this "
            f"check.")


# --------------------------------------------------------------------------
# the factory + plan cache
# --------------------------------------------------------------------------

_PLAN_CACHE: "OrderedDict[tuple, LogdetPlan]" = OrderedDict()
_PLAN_CACHE_SIZE = 128


def clear_plan_cache():
    """Drop all cached plans and parallel kernels (test/debug hook)."""
    _PLAN_CACHE.clear()
    _KERNEL_CACHE.clear()


def plan(x, *, method: str = "auto", mesh=None, axis_name: str = "rows",
         precision=None, grad: bool = False, config: LogdetConfig = None,
         validate: bool = True, rtol: Optional[float] = None,
         **kwargs) -> LogdetPlan:
    """Compile a log-determinant plan for a problem shape.

    ``x``          what to plan for: an int N, a shape tuple ``(n, n)`` /
                   ``(B, n, n)``, a concrete array or stack, a
                   `LinearOperator`, or a `ProblemSpec`.  Concrete inputs
                   stay bound to the plan, so ``plan(a)()`` works.
    ``method``     a method name, or ``"auto"`` to run the cost model
                   (`select_method`) over N, structure, devices and
                   ``rtol``.
    ``mesh``       1-D device mesh for the distributed paths (parallel
                   condensation / row-sharded estimator matvecs).
    ``precision``  dtype override (e.g. ``"float32"``); inputs are cast.
                   ``"bf16"``/``"bfloat16"`` is different: it selects the
                   mixed-precision ENGINE route (bf16 GEMM operands,
                   full-precision accumulators — exact family only); the
                   input dtype is untouched.
    ``grad``       pre-build the ``value_and_grad`` executable now rather
                   than on first use.
    ``config``     an explicit typed config (`ExactConfig` |
                   `ChebyshevConfig` | `SLQConfig`) — mutually exclusive
                   with per-method ``**kwargs`` and with ``method="auto"``.
    ``validate``   screen dense estimator inputs for symmetry / positive
                   diagonal at call time (skipped under tracing).
    ``rtol``       requested relative accuracy — steers the auto-selector
                   (below 1e-3 only exact methods qualify).
    ``**kwargs``   per-method knobs, validated into the typed config
                   (``degree=...``, ``num_probes=...``, ``k=...``, ...).
                   With ``method="auto"`` the estimator knobs also inform
                   the cost estimate; knobs belonging to the family the
                   selector did NOT pick are dropped (exact is at least
                   as accurate), while names no method defines still
                   raise.

    Returns a `LogdetPlan`.  Plans for dense/batched specs are cached:
    equal spec + method + config + mesh reuse one compiled executable
    (this cache is what makes the deprecated string API non-retracing).
    """
    engine_precision = None
    if precision in ("bf16", "bfloat16"):
        # mixed-precision engine route, NOT a storage-dtype cast: the
        # buffer and all sign/parity/log accumulators keep the input
        # dtype; only GEMM/outer operands are quantized (docs/api.md)
        engine_precision = "bf16"
        precision = None
    spec = spec_of(x, dtype=precision)
    if (precision is not None or engine_precision is not None) \
            and spec.kind == "operator":
        raise ValueError("precision overrides apply to array inputs; "
                         "cast the operator's parameters instead")
    if precision is not None:
        spec = dataclasses.replace(spec, dtype=str(jnp.dtype(precision)))

    if mesh is not None and spec.batch is not None:
        raise TypeError(
            "mesh sharding applies to a single (n, n) matrix; batched "
            "stacks run one device per matrix — drop mesh, or map a "
            "single-matrix plan over the stack")

    if method == "auto":
        if config is not None:
            raise ValueError(
                "method='auto' with an explicit config is ambiguous — the "
                "config pins the method family; pass the method name")
        bounds_known = (kwargs.get("lmin") is not None
                        and kwargs.get("lmax") is not None)
        probes = kwargs.get("num_probes", 32)
        est_cols = (kwargs.get("degree", 64) * probes if bounds_known
                    else kwargs.get("num_steps", 25) * probes + _BOUNDS_COLS)
        method, route = select_route(spec, mesh=mesh, axis_name=axis_name,
                                     rtol=rtol, bounds_known=bounds_known,
                                     est_cols=est_cols,
                                     precision=engine_precision)
        # the resolved family keeps its own knobs; the other family's are
        # dropped (typo-only names still raise inside the filter)
        kwargs = _filter_for_method(method, kwargs)
        if route is not None:
            # the selector's engine tuple, user-supplied axes winning;
            # panel_k is the autotuned width exact_cost priced, so auto
            # RUNS the geometry it modeled
            kwargs.setdefault("schedule", route.schedule)
            kwargs.setdefault("update", route.update)
            kwargs.setdefault("k", route.panel_k)
            if route.schedule == "mesh":
                kwargs.setdefault("lookahead", route.lookahead)
    elif method in LEGACY_EXACT_ROUTES:
        schedule, update = LEGACY_ROUTES[method]
        warnings.warn(
            f"exact route string {method!r} is deprecated: it is the "
            f"engine instantiation method='exact', schedule={schedule!r}, "
            f"update={update!r} — request that directly (docs/api.md has "
            f"the route matrix)", DeprecationWarning, stacklevel=2)
        if config is not None:
            config = validate_config(method, config)
            for axis, val in (("schedule", schedule), ("update", update)):
                got = getattr(config, axis)
                if got not in (None, val):
                    raise TypeError(
                        f"route {method!r} pins {axis}={val!r} but the "
                        f"config says {got!r}; use method='exact' to "
                        f"choose engine axes freely")
            config = dataclasses.replace(config, schedule=schedule,
                                         update=update)
        else:
            for axis, val in (("schedule", schedule), ("update", update)):
                if kwargs.get(axis, val) != val:
                    raise TypeError(
                        f"route {method!r} pins {axis}={val!r}; got "
                        f"{kwargs[axis]!r} — use method='exact' to choose "
                        f"engine axes freely")
            kwargs["schedule"] = schedule
            kwargs["update"] = update
        method = "exact"
    elif method not in METHODS:
        raise ValueError(
            f"unknown method {method!r}; choose from {METHODS} or 'auto'")

    if config is not None:
        if kwargs:
            raise TypeError(
                f"pass knobs either via config= or keywords, not both "
                f"(got config and {sorted(kwargs)})")
        cfg = validate_config(method, config)
    else:
        cfg = config_for(method, kwargs)
    if engine_precision is not None:
        if method != "exact":
            raise ValueError(
                f"precision='bf16' is the condensation engine's "
                f"mixed-precision route; method {method!r} has no "
                "quantized-GEMM path (use method='exact' or 'auto')")
        got = cfg.precision
        if got not in (None, engine_precision):
            raise ValueError(
                f"precision='bf16' conflicts with config precision "
                f"{got!r}")
        cfg = dataclasses.replace(cfg, precision=engine_precision)
    if method == "exact":
        cfg = cfg.resolved(mesh_present=mesh is not None)
    _refuse_f64_kernels(method, cfg, spec)

    if spec.kind == "operator":
        if method not in ESTIMATOR_METHODS:
            raise TypeError(
                f"method {method!r} needs a materialized matrix; operator "
                f"inputs require an estimator method "
                f"{sorted(ESTIMATOR_METHODS)}")
        if mesh is not None:
            raise TypeError("operator inputs carry their own distribution; "
                            "mesh is only accepted for dense array inputs")

    if _is_mesh_exact(method, cfg):
        if spec.batch is not None:
            raise TypeError(
                f"method {method!r} (mesh schedule) distributes ONE matrix "
                "over the mesh; batched stacks need a serial or staged "
                "schedule — map a single-matrix plan over the stack instead")
        if mesh is None:
            raise ValueError(
                "engine schedule 'mesh' requires a mesh" if method == "exact"
                else f"method {method!r} requires a mesh")

    cache_key = None
    if spec.kind != "operator":
        # validate is call-time behavior, not part of the compiled artifact
        cache_key = (spec, method, cfg, mesh, axis_name)
        cached = _PLAN_CACHE.get(cache_key)
        obs.inc("plan.cache.hits" if cached is not None
                else "plan.cache.misses")
        if cached is not None:
            _PLAN_CACHE.move_to_end(cache_key)
            if grad and "vag" not in cached._cache:
                # honor the prebuild contract on cache hits too
                cached._cache["vag"] = _build_value_and_grad(
                    spec, method, cfg, mesh, axis_name,
                    jnp.dtype(spec.dtype), cached._fwd)
            if cached.validate != validate or cached.grad != grad:
                cached = dataclasses.replace(cached, validate=validate,
                                             grad=grad)
            return _bind(cached, x)

    # diagnostics must reflect the EXECUTION: a supplied mesh only spans
    # devices for routes that actually distribute (mesh-schedule exact,
    # sharded estimator matvecs) — a serial route picked by the selector
    # despite a mesh runs on one device
    if mesh is not None and (_is_mesh_exact(method, cfg)
                             or method in ESTIMATOR_METHODS):
        devices = int(mesh.shape[axis_name])
    else:
        devices = spec.device_count
    with obs.span("plan.build", method=method, n=spec.n):
        trace_log: list = []
        dtype = jnp.dtype(spec.dtype)
        fwd, compiled, padded_n = _build_forward(
            spec, method, cfg, mesh, axis_name, dtype, trace_log)
        cols, flops = _flops_est(method, spec, cfg, devices)
        p = LogdetPlan(
            spec=spec, method=method, config=cfg, mesh=mesh,
            axis_name=axis_name, grad=grad, validate=validate,
            compiled=compiled,
            diagnostics=Diagnostics(matvec_cols=cols, flops_est=flops,
                                    padded_n=padded_n, device_count=devices),
            _fwd=fwd, _trace_log=trace_log)
        if grad:
            p._cache["vag"] = _build_value_and_grad(
                spec, method, cfg, mesh, axis_name, dtype, fwd)
    obs.set_gauge("plan.flops_est", flops, method=method)
    if cache_key is not None:
        _PLAN_CACHE[cache_key] = p
        while len(_PLAN_CACHE) > _PLAN_CACHE_SIZE:
            _PLAN_CACHE.popitem(last=False)
    return _bind(p, x)


def _refuse_f64_kernels(method: str, cfg: LogdetConfig,
                        spec: ProblemSpec) -> None:
    """Refuse, at plan time, a float64 plan whose Pallas kernels would be
    compiled for the TPU: Mosaic has no float64, so it would otherwise
    fail at the first call as a kernel compile error."""
    from repro.kernels import ops as _kops
    if spec.dtype != "float64" or not _kops.on_tpu():
        return
    if method == "exact":
        backend = cfg.backend
    elif method in ESTIMATOR_METHODS:
        backend = _kops.kernel_backend()
    else:
        return
    if backend == "pallas":
        raise ValueError(
            f"a float64 {method!r} plan cannot run the Pallas kernels on the "
            "TPU (Mosaic has no float64): plan in float32 "
            "(precision='float32' or a float32 input), or use the xla "
            "backend (REPRO_KERNEL_BACKEND=xla, or backend='xla' for "
            "method='exact')")


def _bind(p: LogdetPlan, x) -> LogdetPlan:
    """Attach a concrete input to a (possibly shared) plan instance."""
    from repro.estimators.operators import is_operator
    concrete = (is_operator(x)
                or (hasattr(x, "shape") and not isinstance(x, ProblemSpec)
                    and not _is_tracer(x)))
    if not concrete:
        return p
    return dataclasses.replace(p, _bound=x)

"""Legacy string-dispatch log-determinant API — deprecated shims over
``repro.plan``.

``slogdet(a, method=..., mesh=..., **kwargs)`` and ``logdet_batched`` are
kept for one release as thin wrappers that build (and cache) a
`repro.core.plan.LogdetPlan` per (spec, method, config, mesh) and execute
it, so existing callers keep identical numerics, error behavior and
gradient rules while emitting a `DeprecationWarning`.  New code should
build a plan once and call it:

    p = repro.plan((n, n), method="auto")     # or a concrete method name
    sign, logabsdet = p(a)                    # LogdetResult unpacks

See docs/api.md for the full plan lifecycle, the typed config reference
(`ExactConfig` / `ChebyshevConfig` / `SLQConfig`), the method decision
tree behind ``method="auto"``, and the migration guide from this module's
string API.

`pad_to_multiple` is not deprecated — it is the shared embedding primitive
(``A -> diag(A, I)``, determinant-preserving) that plans and the parallel
kernels both use.
"""
from __future__ import annotations

import warnings

import jax
import jax.numpy as jnp

from repro.core.configs import (
    ESTIMATOR_METHODS as _EST_METHODS, METHODS, PARALLEL_METHODS,
)

__all__ = ["slogdet", "logdet", "logdet_batched", "pad_to_multiple",
           "METHODS"]

_PARALLEL = set(PARALLEL_METHODS)
_ESTIMATOR = set(_EST_METHODS)


def pad_to_multiple(a: jax.Array, mult: int) -> jax.Array:
    """Embed ``a`` in ``diag(a, I_pad)`` so N becomes a multiple of ``mult``."""
    n = a.shape[0]
    pad = (-n) % mult
    if pad == 0:
        return a
    out = jnp.zeros((n + pad, n + pad), a.dtype)
    out = out.at[:n, :n].set(a)
    idx = jnp.arange(n, n + pad)
    # identity padding in the INPUT dtype: a Python 1.0 would weakly
    # promote integer / low-precision inputs (int32 -> f32, bf16 -> f32)
    return out.at[idx, idx].set(jnp.ones((), a.dtype))


def _warn_deprecated(name: str, repl: str):
    from repro import obs
    obs.inc("compat.deprecated", fn=name)
    warnings.warn(
        f"repro.core.{name}() is deprecated: build a plan once with "
        f"repro.plan({repl}) and call it (docs/api.md has the migration "
        f"guide)", DeprecationWarning, stacklevel=3)


def _runtime_bounds(est_kw: dict) -> dict:
    """Pop traced lmin/lmax out of the config keywords.

    Typed configs are static and hashable (they key the plan cache), so
    bounds that arrive as tracers (callers computing them under jit/grad)
    ride as execution inputs instead — same numerics as the pre-plan API,
    which threaded array kwargs through the custom VJP explicitly."""
    rt = {}
    for name in ("lmin", "lmax"):
        v = est_kw.get(name)
        if isinstance(v, jax.core.Tracer):
            rt[name] = est_kw.pop(name)
    return rt


def _plan_call(a, method, mesh, axis_name, k, nb, est_kw):
    """Route one legacy call through a cached plan, preserving the string
    API's validation order and error messages."""
    from repro.core.plan import plan as _make_plan

    if method not in METHODS:
        raise ValueError(f"unknown method {method!r}; choose from {METHODS}")
    from repro.estimators.operators import is_operator as _is_op
    if _is_op(a):
        # operator inputs: only the matrix-free estimator methods apply
        if method not in _ESTIMATOR:
            raise TypeError(
                f"method {method!r} needs a materialized matrix; operator "
                f"inputs require an estimator method {sorted(_ESTIMATOR)}")
        if mesh is not None:
            raise TypeError("operator inputs carry their own distribution; "
                            "mesh is only accepted for dense array inputs")
        key = est_kw.pop("key", None)
        probes = est_kw.pop("probes", None)
        rt = _runtime_bounds(est_kw)
        p = _make_plan(a, method=method, validate=False, **est_kw)
        return p.slogdet(a, key=key, probes=probes, **rt)

    a_arr = a if isinstance(a, jax.Array) else jnp.asarray(a)
    shape = tuple(a_arr.shape)
    if len(shape) != 2 or shape[0] != shape[1]:
        raise ValueError(f"expected square matrix, got {shape}")

    if method in _ESTIMATOR:
        key = est_kw.pop("key", None)
        probes = est_kw.pop("probes", None)
        rt = _runtime_bounds(est_kw)
        p = _make_plan(a_arr, method=method, mesh=mesh,
                       axis_name=axis_name, validate=False, **est_kw)
        return p.slogdet(a_arr, key=key, probes=probes, **rt)

    kw = {"k": k, "nb": nb} if method in _PARALLEL or method == "mc_blocked" \
        else {}
    kw.update(est_kw)          # exact + estimator kwargs -> typed TypeError
    p = _make_plan(a_arr, method=method, mesh=mesh, axis_name=axis_name,
                      validate=False, **kw)
    return p.slogdet(a_arr)


def slogdet(a, *, method: str = "mc", mesh=None, axis_name: str = "rows",
            k: int = 32, nb: int = 1, **est_kw):
    """Sign and log|det| of a square matrix. numpy.linalg.slogdet semantics.

    .. deprecated:: use ``repro.plan(...)`` — this shim builds a cached
       plan per (shape, method, config, mesh) and executes it.

    Estimator methods ("chebyshev", "slq") assume SPD input, return sign 1,
    and accept the keywords of `ChebyshevConfig` / `SLQConfig` plus the
    runtime ``key``/``probes`` arrays.  Exact methods reject estimator
    keywords.  All methods are ``jax.grad``-safe through the logdet output.
    """
    _warn_deprecated("slogdet", "shape, method=...")
    return _plan_call(a, method, mesh, axis_name, k, nb, est_kw)


def logdet(a, *, method: str = "mc", mesh=None, axis_name: str = "rows",
           k: int = 32, nb: int = 1, **est_kw):
    """log|det(a)| — the paper's quantity (sign discarded).

    .. deprecated:: use ``repro.plan(...).logdet(a)``.
    """
    _warn_deprecated("logdet", "shape, method=...")
    return _plan_call(a, method, mesh, axis_name, k, nb, est_kw)[1]


def logdet_batched(stack, *, method: str = "chebyshev", **kw):
    """``log|det|`` per matrix of an SPD (B, N, N) stack -> (B,).

    .. deprecated:: use ``repro.plan(stack.shape, method=...)`` — a batched
       plan returns a `LogdetResult` whose fields carry the leading batch
       axis.
    """
    _warn_deprecated("logdet_batched", "(B, n, n), method=...")
    from repro.core.plan import plan as _make_plan
    from repro.estimators import ESTIMATOR_METHODS as _est_names
    from repro.estimators.operators import is_operator as _is_op

    if _is_op(stack):
        if getattr(stack, "batch", None) is None:
            raise ValueError(
                "logdet_batched needs a batched operator (with a .batch "
                "axis); use estimate_logdet for a single operator")
        if method not in _ESTIMATOR:
            raise TypeError(
                f"method {method!r} needs a materialized (B, n, n) stack; "
                "operator inputs require an estimator method "
                f"{_est_names}")
        key = kw.pop("key", None)
        probes = kw.pop("probes", None)
        p = _make_plan(stack, method=method, validate=False, **kw)
        return p.logdet(stack, key=key, probes=probes)

    stack = jnp.asarray(stack)
    if stack.ndim != 3 or stack.shape[1] != stack.shape[2]:
        raise ValueError(f"expected (B, n, n) stack, got {stack.shape}")
    if method not in _ESTIMATOR:
        # any exact engine route, vmapped per matrix; mesh schedules raise
        # a clear TypeError inside plan (ONE matrix per mesh)
        p = _make_plan(stack, method=method, validate=False, **kw)
        return p.logdet(stack)
    key = kw.pop("key", None)
    probes = kw.pop("probes", None)
    p = _make_plan(stack, method=method, validate=False, **kw)
    return p.logdet(stack, key=key, probes=probes)

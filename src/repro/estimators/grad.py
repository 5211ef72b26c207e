"""Differentiable log-determinants: custom VJP rules for every path.

The paper motivates log-determinants through generative learning, which in
practice means *training* — ``jax.grad`` through ``logdet(Sigma)``.  The
analytic derivative is

    d logdet(A) = tr(A^{-1} dA),      i.e.  d logdet / dA = A^{-T},

and neither the condensation pivot schedule nor the estimator recurrences
are things one wants to differentiate *through*: pivoting is piecewise
control flow (autodiff would thread cotangents through argmax/swap noise),
and the Chebyshev/Lanczos recurrences would retain every intermediate slab.
This module registers ``jax.custom_vjp`` rules instead:

Exact methods (``mc``, ``ge``, ``pmc``, ...)
    `exact_slogdet_vjp` wraps any ``a -> (sign, logdet)`` computation with
    the analytic pullback ``bar_a = g * A^{-T}`` (one LU-based inverse in
    the backward pass — the same O(N^3) class as the forward; the
    condensation core does not retain its factors, so the inverse is
    recomputed rather than read off the forward's elimination).  The sign
    output is piecewise constant and gets a zero gradient.

Estimator methods (``chebyshev``, ``slq``)
    The Hutchinson identity runs backwards: with probes ``z_c``,

        A^{-T} = E[(A^{-T} z) z^T]  ~=  (1/k) sum_c (A^{-T} z_c) z_c^T,

    so the cotangent is realized *matrix-free* by one batched CG solve on
    the SAME probe slab the forward pass consumed (the shared key/probes
    are plumbed through `estimate_logdet`; backward cost ~ one CG solve
    per probe column, no dense inverse and no O(n^2) intermediate for
    structured operators).  ``sem``/``samples`` of the returned
    `TraceEstimate` are Monte-Carlo diagnostics and are treated as
    non-differentiable constants.

Structured operators receive *structured* cotangents: the pullback of the
bilinear form ``sum_c w_c^T A(theta) z_c`` with respect to the operator's
own parameters — factor-shaped for `KroneckerOperator`, first-column/
row-shaped for `ToeplitzOperator`, band-shaped for `StencilOperator` —
never a dense (n, n) tangent.  Third-party duck-typed operators can opt in
via `register_operator_grad`; unregistered operators fall back to plain
autodiff through the estimator recurrence (correct but memory-hungry,
and it differentiates the *estimate*, not the estimand).

Second-order derivatives of these rules are not defined (the backward pass
itself contains a ``lax.while_loop``); take gradients once.
"""
from __future__ import annotations

from typing import Any, Callable, NamedTuple, Optional

import numpy as np

import jax
import jax.numpy as jnp
from jax import dtypes as _jdtypes

from repro.estimators.chebyshev import logdet_chebyshev
from repro.estimators.hutchinson import TraceEstimate, make_probes
from repro.estimators.operators import (
    BatchedOperator, DenseOperator, KroneckerOperator, ShardedOperator,
    StencilOperator, ToeplitzOperator, as_operator, cg_solve,
)
from repro.estimators.slq import logdet_slq
from repro.kernels.ref import stencil_mv_ref
from repro.numerics import full_precision

__all__ = [
    "estimate_logdet", "exact_slogdet_vjp", "hutchinson_pullback",
    "shared_probes",
    "register_operator_grad", "operator_grad_info", "OperatorGradInfo",
]

_ESTIMATORS = {"chebyshev": logdet_chebyshev, "slq": logdet_slq}
ESTIMATOR_METHODS = tuple(_ESTIMATORS)


# --------------------------------------------------------------------------
# operator registry: how each backend exposes its differentiable parameters
# --------------------------------------------------------------------------

class OperatorGradInfo(NamedTuple):
    """How the grad machinery sees one operator class.

    ``params(op)`` extracts the differentiable parameter pytree;
    ``rebuild(op, params)`` reconstructs an equivalent operator from it
    (reading only *static* attributes — offsets, mesh, axis names — off
    the original instance); ``apply(op, params, z)`` computes
    ``A(params) @ z`` with plain differentiable jnp ops for the bilinear
    pullback (defaults to ``rebuild(op, params).mm(z)``); ``dense=True``
    short-circuits the pullback to the closed-form outer product
    ``(g/k) * W Z^T`` when the parameters ARE the matrix entries.
    """
    params: Callable[[Any], Any]
    rebuild: Callable[[Any, Any], Any]
    apply: Optional[Callable[[Any, Any, jax.Array], jax.Array]] = None
    dense: bool = False


_REGISTRY: dict = {}


def register_operator_grad(cls, *, params, rebuild, apply=None,
                           dense: bool = False) -> None:
    """Register a structured pullback for an operator class.

    Lets user-defined (duck-typed) operators receive structured gradients
    from the logdet estimators instead of the autodiff-through-recurrence
    fallback.  See `OperatorGradInfo` for the callback contracts.
    """
    _REGISTRY[cls] = OperatorGradInfo(params, rebuild, apply, dense)


def operator_grad_info(op) -> Optional[OperatorGradInfo]:
    """Registered grad info for ``op`` (exact class first, then bases)."""
    info = _REGISTRY.get(type(op))
    if info is not None:
        return info
    for cls, entry in _REGISTRY.items():
        if isinstance(op, cls):
            return entry
    return None


register_operator_grad(
    DenseOperator,
    params=lambda op: op.a,
    rebuild=lambda op, a: DenseOperator(a),
    dense=True)
register_operator_grad(
    BatchedOperator,
    params=lambda op: op.stack,
    rebuild=lambda op, s: BatchedOperator(s),
    dense=True)
register_operator_grad(
    ShardedOperator,
    params=lambda op: op.a,
    rebuild=lambda op, a: ShardedOperator(
        a, op.mesh, op.axis_name, use_kernel=op.use_kernel),
    dense=True)
register_operator_grad(
    KroneckerOperator,
    params=lambda op: (op.a, op.b),
    rebuild=lambda op, p: KroneckerOperator(p[0], p[1]))
register_operator_grad(
    ToeplitzOperator,
    # symmetric operators hold the same array as c and r, so both halves
    # of the cotangent flow back into the single first-column parameter
    params=lambda op: (op.c, op.r),
    rebuild=lambda op, p: ToeplitzOperator(p[0], p[1]))
register_operator_grad(
    StencilOperator,
    params=lambda op: op.bands,
    rebuild=lambda op, b: StencilOperator(op.offsets, b),
    # bypass the Pallas kernel dispatch: the jnp reference is the
    # differentiable description of the banded contraction on any backend
    apply=lambda op, b, z: stencil_mv_ref(b, z, offsets=op.offsets))


# --------------------------------------------------------------------------
# exact methods: shared analytic VJP
# --------------------------------------------------------------------------

def exact_slogdet_vjp(fn: Callable[[jax.Array], Any]):
    """Wrap an exact ``a -> (sign, logabsdet)`` computation with its VJP.

    The backward pass is the analytic ``bar_a = g_logdet * inv(a).T`` —
    the pivot control flow of the forward (condensation column swaps, GE
    row exchanges, blocked panels) is never differentiated through.  The
    sign output is locally constant: its cotangent is discarded.
    """

    @jax.custom_vjp
    def f(a):
        return fn(a)

    def f_fwd(a):
        return fn(a), a

    def f_bwd(a, ct):
        g = ct[1]                                  # logdet cotangent only
        if a.shape[-1] == 0:
            return (jnp.zeros_like(a),)
        with full_precision():
            inv = jnp.linalg.inv(a)
        bar = g * jnp.swapaxes(inv, -1, -2)
        return (bar.astype(a.dtype),)

    f.defvjp(f_fwd, f_bwd)
    return f


# --------------------------------------------------------------------------
# estimator methods: Hutchinson pullback on the forward's own probes
# --------------------------------------------------------------------------

def shared_probes(method: str, op, key, kw):
    """The exact probe slab the named estimator would draw internally.

    Mirrors each estimator's key discipline (`logdet_chebyshev` splits the
    key into bounds/probes halves; `logdet_slq` consumes it whole) so the
    forward value is bit-identical to a direct estimator call, and the
    backward pass reuses the very same probes.
    """
    n = op.shape[-1]
    batch = getattr(op, "batch", None)
    num = kw.get("num_probes", 32)
    if method == "chebyshev":
        kp = jax.random.split(key)[1]
        kind = kw.get("probe_kind", "rademacher")
    else:
        kp, kind = key, "rademacher"
    return make_probes(kp, n, num, kind=kind, dtype=op.dtype,
                       batch_shape=(batch,) if batch else ())


def hutchinson_pullback(op, params, probes, g, *, info=None,
                        cg_tol: float = 1e-8, cg_maxiter=None):
    """The logdet cotangent on an operator's own parameters, matrix-free.

    Realizes ``bar_params = vjp_params[(g/k) sum_c w_c^T A(params) z_c]``
    with ``w = A^{-T} Z`` solved by one batched transposed CG on the probe
    slab ``Z`` — the estimator backward pass, exposed as a plain function
    so callers (the custom-VJP rule below, and `repro.plan`'s explicit
    ``value_and_grad`` path) can also read the solve's convergence
    evidence.  Returns ``(bar_params, CGResult)``.

    ``op`` is the template operator (static attributes), ``params`` its
    differentiable parameter pytree (may be traced), ``g`` the logdet
    cotangent (scalar, or (B,) for batched operators).
    """
    info = operator_grad_info(op) if info is None else info
    if info is None:
        raise TypeError(
            f"no grad registration for {type(op).__name__}; register one "
            "with repro.estimators.register_operator_grad")
    op_b = info.rebuild(op, params)
    cg = cg_solve(op_b, probes, transpose=True, tol=cg_tol,
                  maxiter=cg_maxiter)
    w = cg.x                                         # A^{-T} Z, matrix-free
    k = probes.shape[-1]
    scale = (jnp.asarray(g) / k).astype(probes.dtype)
    if info.dense:
        bar = scale[..., None, None] * jnp.einsum("...ik,...jk->...ij",
                                                  w, probes)
    else:
        w2 = scale[..., None, None] * w
        apply_fn = info.apply or (
            lambda o, pp, zz: info.rebuild(o, pp).mm(zz))
        _, pull = jax.vjp(
            lambda pp: (w2 * apply_fn(op, pp, probes)).sum(), params)
        (bar,) = pull(jnp.ones((), w2.dtype))
    return bar, cg


def _zero_cotangent(x):
    """Zero cotangent matching jax's tangent-type rules (float0 for ints)."""
    if jnp.issubdtype(jnp.result_type(x), jnp.inexact):
        return jnp.zeros_like(x)
    return np.zeros(jnp.shape(x), _jdtypes.float0)


def estimate_logdet(a, method: str = "chebyshev", **kw) -> TraceEstimate:
    """Dispatch to a logdet estimator by name — differentiably.

    See `logdet_chebyshev` / `logdet_slq` for the method-specific keywords.
    The returned `TraceEstimate` supports ``jax.grad`` through ``.est``:
    the custom VJP solves ``A^T W = Z`` on the forward pass's own probe
    slab with `cg_solve` (matrix-free; control the solve with
    ``grad_cg_tol`` / ``grad_cg_maxiter``) and pulls the Hutchinson
    cotangent back onto the operator's parameters — dense entries,
    Kronecker factors, Toeplitz first column/row, or stencil bands.
    ``sem`` and ``samples`` are non-differentiable diagnostics.
    """
    if method not in _ESTIMATORS:
        raise ValueError(
            f"unknown estimator {method!r}; choose from {ESTIMATOR_METHODS}")
    fwd_fn = _ESTIMATORS[method]
    mesh = kw.pop("mesh", None)
    axis_name = kw.pop("axis_name", "rows")
    cg_tol = kw.pop("grad_cg_tol", 1e-8)
    cg_maxiter = kw.pop("grad_cg_maxiter", None)

    op = as_operator(a, mesh=mesh, axis_name=axis_name)
    info = operator_grad_info(op)
    if info is None:
        # unregistered duck-typed operator: plain forward; jax.grad (if
        # requested) differentiates through the estimator recurrence
        return fwd_fn(op, **kw)

    params = info.params(op)
    key = kw.pop("key", None)
    seed = kw.pop("seed", 0)
    if key is None:
        key = jax.random.PRNGKey(seed)
    probes = kw.pop("probes", None)
    if probes is None:
        probes = shared_probes(method, op, key, kw)
    else:
        probes = jnp.asarray(probes, op.dtype)

    # split remaining keywords: traced/array values (lmin/lmax bounds, ...)
    # must ride through the custom_vjp as explicit arguments — closing over
    # a tracer inside fwd/bwd would leak it
    static_kw, array_kw = {}, {}
    for name, val in kw.items():
        (array_kw if isinstance(val, jax.Array) else static_kw)[name] = val
    array_kw["key"] = key
    array_kw["probes"] = probes

    def compute(p, arrs):
        call_kw = dict(static_kw)
        call_kw.update(arrs)
        return fwd_fn(info.rebuild(op, p), **call_kw)

    @jax.custom_vjp
    def f(p, arrs):
        return compute(p, arrs)

    def f_fwd(p, arrs):
        return compute(p, arrs), (p, arrs)

    def f_bwd(res, ct):
        p, arrs = res
        bar, _ = hutchinson_pullback(op, p, arrs["probes"], ct.est,
                                     info=info, cg_tol=cg_tol,
                                     cg_maxiter=cg_maxiter)
        zeros = jax.tree_util.tree_map(_zero_cotangent, arrs)
        return bar, zeros

    f.defvjp(f_fwd, f_bwd)
    return f(params, array_kw)

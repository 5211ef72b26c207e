"""Pallas TPU kernels: fused estimator recurrences (Chebyshev, CG).

The stochastic Chebyshev estimator's three-term recurrence used to make
two passes over ``A`` worth of traffic per degree: the shifted matvec
``(2 A w - c w) / width`` materializes ``A w`` to HBM, then the axpy
``2 mv - w_prev`` and the probe dot ``(v * w).sum`` stream the slab
again.  Same story for CG's hot chain ``ap = A p; alpha = rz / p.ap;
x += alpha p; r -= alpha ap``.  Both are one-matvec-plus-epilogue
shapes: the epilogue is O(n k) next to the O(n^2 k) matvec, so the win
is keeping the slab VMEM-resident — read ``A`` once, finish the
recurrence before anything round-trips HBM.

Single-block kernels (grid=()): ``A`` plus the probe slabs must fit the
VMEM budget (checked by the dispatch layer in `repro.kernels.ops`,
which falls back to the identical unfused jnp reference for oversized
operands).  The arithmetic is ordered exactly as the unfused reference
in `repro.kernels.ref` so f32 results are bit-identical (asserted in
tests/test_kernels.py).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import I0
from repro.numerics import dot_precision

__all__ = [
    "cheb_step_kernel", "cheb_step_pallas",
    "cg_step_kernel", "cg_step_pallas", "VMEM_BUDGET",
]

VMEM_BUDGET = 8 * 1024 * 1024  # bytes; A + slabs must fit


def _pref(dt):
    return jnp.float64 if dt == jnp.float64 else jnp.float32


def cheb_step_kernel(a_ref, w_ref, wprev_ref, v_ref, center_ref,
                     width_ref, wnext_ref, dots_ref):
    """w_next = 2*(2 A w - c w)/width - w_prev; dots = (v * w_next).sum(0)."""
    a = a_ref[...]
    w = w_ref[...]
    center = center_ref[0]
    width = width_ref[0]
    aw = jnp.dot(a, w, precision=dot_precision(a.dtype),
                 preferred_element_type=_pref(a.dtype)).astype(a.dtype)
    mv = (2.0 * aw - center * w) / width
    w_next = 2.0 * mv - wprev_ref[...]
    wnext_ref[...] = w_next
    dots_ref[...] = (v_ref[...] * w_next).sum(0)


@functools.partial(jax.jit, static_argnames=("interpret",))
def cheb_step_pallas(a: jax.Array, w: jax.Array, w_prev: jax.Array,
                     v: jax.Array, center, width, *,
                     interpret: bool = False):
    """Fused Chebyshev three-term step; returns (w_next, dots)."""
    n, k = w.shape
    center = jnp.asarray(center, a.dtype).reshape(1)
    width = jnp.asarray(width, a.dtype).reshape(1)
    w_next, dots = pl.pallas_call(
        cheb_step_kernel,
        in_specs=[
            pl.BlockSpec((n, n), lambda: (I0, I0)),   # A, VMEM-resident
            pl.BlockSpec((n, k), lambda: (I0, I0)),   # w
            pl.BlockSpec((n, k), lambda: (I0, I0)),   # w_prev
            pl.BlockSpec((n, k), lambda: (I0, I0)),   # v
            pl.BlockSpec((1,), lambda: (I0,)),       # center
            pl.BlockSpec((1,), lambda: (I0,)),       # width
        ],
        out_specs=[
            pl.BlockSpec((n, k), lambda: (I0, I0)),
            pl.BlockSpec((k,), lambda: (I0,)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n, k), a.dtype),
            jax.ShapeDtypeStruct((k,), a.dtype),
        ],
        interpret=interpret,
    )(a, w, w_prev, v, center, width)
    return w_next, dots


def cg_step_kernel(a_ref, p_ref, x_ref, r_ref, rz_ref, x_out, r_out):
    """ap = A p; alpha = rz / p.ap (0/0 -> 0); x += alpha p; r -= alpha ap."""
    a = a_ref[...]
    p = p_ref[...]
    ap = jnp.dot(a, p, precision=dot_precision(a.dtype),
                 preferred_element_type=_pref(a.dtype)).astype(a.dtype)
    den = (p * ap).sum(0)
    rz = rz_ref[...]
    # same guarded division as operators.solve._safe_div: converged
    # columns have vanishing denominators and must take exact no-ops
    tiny = jnp.finfo(den.dtype).tiny
    safe = jnp.where(jnp.abs(den) > tiny, den, 1.0)
    alpha = jnp.where(jnp.abs(den) > tiny, rz / safe,
                      jnp.zeros_like(rz))[None, :]
    x_out[...] = x_ref[...] + alpha * p
    r_out[...] = r_ref[...] - alpha * ap


@functools.partial(jax.jit, static_argnames=("interpret",))
def cg_step_pallas(a: jax.Array, p: jax.Array, x: jax.Array, r: jax.Array,
                   rz: jax.Array, *, interpret: bool = False):
    """Fused CG matvec+axpy+dot chain; returns (x_new, r_new)."""
    n, k = p.shape
    x_new, r_new = pl.pallas_call(
        cg_step_kernel,
        in_specs=[
            pl.BlockSpec((n, n), lambda: (I0, I0)),   # A, VMEM-resident
            pl.BlockSpec((n, k), lambda: (I0, I0)),   # p
            pl.BlockSpec((n, k), lambda: (I0, I0)),   # x
            pl.BlockSpec((n, k), lambda: (I0, I0)),   # r
            pl.BlockSpec((k,), lambda: (I0,)),       # rz
        ],
        out_specs=[
            pl.BlockSpec((n, k), lambda: (I0, I0)),
            pl.BlockSpec((n, k), lambda: (I0, I0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n, k), a.dtype),
            jax.ShapeDtypeStruct((n, k), a.dtype),
        ],
        interpret=interpret,
    )(a, p, x, r, rz)
    return x_new, r_new

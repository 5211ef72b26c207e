"""Pure-jnp oracles for every Pallas kernel (numerical ground truth).

Every contraction takes full float32 products (`repro.numerics`).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.numerics import matmul

__all__ = ["rank1_update_ref", "panel_update_ref", "matvec_ref",
           "stencil_mv_ref", "fused_step_ref", "cheb_step_ref",
           "cg_step_ref"]


def rank1_update_ref(a: jax.Array, pc: jax.Array, pr: jax.Array) -> jax.Array:
    """a (M, N) - outer(pc, pr)."""
    return a - jnp.outer(pc, pr)


def panel_update_ref(a: jax.Array, c: jax.Array, r: jax.Array) -> jax.Array:
    """a (M, N) - c (M, K) @ r (K, N)."""
    return a - matmul(c, r)


def fused_step_ref(a: jax.Array, l, last, pc: jax.Array, pr: jax.Array,
                   col_l: jax.Array, col_last: jax.Array) -> jax.Array:
    """Fused column swap (l <-> last) + rank-1 update, one select pass.

    Expresses the engine's scatter-swap + outer-subtract sequence as a
    single elementwise pass: bit-identical (the swap is pure data
    movement; the multiply-subtract is the same arithmetic).  ``pc`` /
    ``pr`` may be lower precision (bf16 operands); the product is
    accumulated back into the buffer dtype.
    """
    cols = jnp.arange(a.shape[1])
    sw = jnp.where(cols[None, :] == l, col_last[:, None],
                   jnp.where(cols[None, :] == last, col_l[:, None], a))
    return sw - (pc[:, None] * pr[None, :]).astype(a.dtype)


def cheb_step_ref(a: jax.Array, w: jax.Array, w_prev: jax.Array,
                  v: jax.Array, center, width):
    """One Chebyshev three-term step; returns (w_next, probe dots).

    Op-for-op the loop body of `estimators.chebyshev.logdet_chebyshev`
    (shifted matvec, axpy, probe dot) so f32 results are bit-identical.
    """
    mv = (2.0 * matmul(a, w) - center * w) / width
    w_next = 2.0 * mv - w_prev
    return w_next, (v * w_next).sum(-2)


def cg_step_ref(a: jax.Array, p: jax.Array, x: jax.Array, r: jax.Array,
                rz: jax.Array):
    """One CG matvec+axpy chain; returns (x_new, r_new).

    Op-for-op the hot half of `operators.solve.cg_solve`'s loop body,
    including the guarded 0/0 -> 0 alpha of converged columns.
    """
    ap = matmul(a, p)
    den = (p * ap).sum(-2)
    tiny = jnp.finfo(den.dtype).tiny
    safe = jnp.where(jnp.abs(den) > tiny, den, 1.0)
    alpha = jnp.where(jnp.abs(den) > tiny, rz / safe,
                      jnp.zeros_like(rz))[..., None, :]
    return x + alpha * p, r - alpha * ap


def matvec_ref(a: jax.Array, x: jax.Array) -> jax.Array:
    """a (M, N) @ x (N,) or (N, K)."""
    return matmul(a, x.astype(a.dtype))


def stencil_mv_ref(bands: jax.Array, x: jax.Array, *,
                   offsets: tuple) -> jax.Array:
    """y[i] = sum_d bands[d, i] * x[i + offsets[d]], zero outside [0, n)."""
    vec = x.ndim == 1
    x2 = (x[:, None] if vec else x).astype(bands.dtype)
    n = x2.shape[0]
    lo = min(min(offsets), 0)
    hi = max(max(offsets), 0)
    xp = jnp.pad(x2, ((-lo, hi), (0, 0)))
    y = jnp.zeros_like(x2)
    for d, off in enumerate(offsets):
        start = off - lo
        y = y + bands[d][:, None] * xp[start:start + n]
    return y[:, 0] if vec else y

"""Pallas TPU kernel: tiled dense matvec / multi-vector matvec.

The stochastic estimators (repro/estimators) reduce every log-determinant to
a stream of products ``A @ V`` where ``V`` stacks a handful of probe vectors
(K ~ 8..64 columns).  With K << 128 the MXU runs far from peak, so — like the
rank-1 condensation update — the product is HBM-bandwidth-bound: every f32
element of ``A`` is read exactly once for ~2K FLOPs.  The kernel's job is to
guarantee that single pass: each (bm, bn) tile of ``A`` is loaded into VMEM
once, multiplied against the resident (bn, K) slab of ``V``, and accumulated
into the (bm, K) output tile across the reduction grid axis.

Grid: ``(M/bm, N/bn)`` with the reduction axis ``j`` innermost, so the output
tile for row-block ``i`` stays resident in VMEM while ``j`` sweeps — the
standard Pallas accumulate-in-place pattern (init at j==0, += after).

VMEM per program: ``bm*bn + bn*K + bm*K`` floats; the default 256x512 f32
tile with K=64 is ~0.7 MiB, well under the ~16 MiB budget, and (bm, bn) are
multiples of the (8, 128) f32 VREG tile.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import I0, out_struct, vary_like
from repro.numerics import dot_precision

__all__ = ["matvec_kernel", "matvec_pallas"]

DEFAULT_BM = 256
DEFAULT_BN = 512


def matvec_kernel(a_ref, x_ref, o_ref):
    """o[i] += a[i, j] @ x[j]; o initialized on the first reduction step."""
    @pl.when(pl.program_id(1) == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    o_ref[...] += jnp.dot(a_ref[...], x_ref[...],
                          precision=dot_precision(a_ref.dtype),
                          preferred_element_type=o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("bm", "bn", "interpret"))
def matvec_pallas(a: jax.Array, x: jax.Array, *,
                  bm: int = DEFAULT_BM, bn: int = DEFAULT_BN,
                  interpret: bool = False) -> jax.Array:
    """``a (M, N) @ x (N,) or (N, K)`` via a tiled Pallas kernel."""
    vec = x.ndim == 1
    (x,) = vary_like(a, x)
    x2 = x[:, None] if vec else x
    m, n = a.shape
    k = x2.shape[1]
    bm = min(bm, m)
    bn = min(bn, n)
    # Partial tiles along the reduction axis would fold padding garbage into
    # the accumulator (unlike the output axes, where it is just discarded) —
    # zero-pad N up front so every j-tile is full.
    n_pad = (-n) % bn
    if n_pad:
        a = jnp.pad(a, ((0, 0), (0, n_pad)))
        x2 = jnp.pad(x2, ((0, n_pad), (0, 0)))
        n += n_pad
    grid = (pl.cdiv(m, bm), pl.cdiv(n, bn))
    out = pl.pallas_call(
        matvec_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bn), lambda i, j: (i, j)),
            pl.BlockSpec((bn, k), lambda i, j: (j, I0)),
        ],
        out_specs=pl.BlockSpec((bm, k), lambda i, j: (i, I0)),
        out_shape=out_struct((m, k), a.dtype, a),
        interpret=interpret,
    )(a, x2.astype(a.dtype))
    return out[:, 0] if vec else out

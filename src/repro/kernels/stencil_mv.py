"""Pallas TPU kernel: banded / sparse-stencil matvec.

A stencil operator applies ``y[i] = sum_d bands[d, i] * x[i + offsets[d]]``
— a handful of diagonals instead of a dense matrix.  The arithmetic
intensity is tiny (one multiply-add per band element), so the product is
purely bandwidth-bound: the kernel's job is one pass over the (nb, n) band
table with the probe slab resident in VMEM.

Grid: ``(M/bm,)`` over row tiles.  Per program: the (nb, bm) band tile for
its rows, the whole zero-padded slab ``xp (m_pad + span, k)`` (estimator
slabs are k ~ 8..64 columns — a few hundred KiB, far under the ~16 MiB
VMEM budget for any n this kernel targets), and the (bm, k) output tile.
Each band contributes a ``pl.ds``-shifted (bm, k) window of ``xp`` scaled
by its coefficient column; offsets are static Python ints so the band loop
unrolls at trace time.

Zero padding (``-lo`` rows above, ``hi + tile remainder`` below) realizes
the Dirichlet boundary — rows whose stencil pokes outside [0, n) read
zeros — and keeps every window in range, so no masking is needed.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import I0

__all__ = ["stencil_mv_kernel", "stencil_mv_pallas"]

DEFAULT_BM = 256


def stencil_mv_kernel(bands_ref, xp_ref, o_ref, *, offsets, lo, bm):
    """o[i] = sum_d bands[d, i] * xp[i + offsets[d] - lo] for the row tile."""
    i = pl.program_id(0)
    acc = jnp.zeros(o_ref.shape, o_ref.dtype)
    for d, off in enumerate(offsets):
        window = xp_ref[pl.ds(i * bm + (off - lo), bm), :]
        acc += bands_ref[d, :][:, None] * window
    o_ref[...] = acc


@functools.partial(jax.jit,
                   static_argnames=("offsets", "bm", "interpret"))
def stencil_mv_pallas(bands: jax.Array, x: jax.Array, *, offsets: tuple,
                      bm: int = DEFAULT_BM,
                      interpret: bool = False) -> jax.Array:
    """Banded matvec ``y[i] = sum_d bands[d, i] * x[i + offsets[d]]``.

    ``bands (nb, n)`` holds one coefficient row per diagonal offset;
    ``x (n,) or (n, k)``; out-of-range reads are zero (Dirichlet).
    """
    vec = x.ndim == 1
    x2 = (x[:, None] if vec else x).astype(bands.dtype)
    n, k = x2.shape
    lo = min(min(offsets), 0)
    hi = max(max(offsets), 0)
    bm = min(bm, n)
    m_pad = -(-n // bm) * bm
    span = hi - lo
    xp = jnp.pad(x2, ((-lo, hi + (m_pad - n)), (0, 0)))
    bands_p = jnp.pad(bands, ((0, 0), (0, m_pad - n)))
    out = pl.pallas_call(
        functools.partial(stencil_mv_kernel, offsets=offsets, lo=lo, bm=bm),
        grid=(m_pad // bm,),
        in_specs=[
            pl.BlockSpec((bands.shape[0], bm), lambda i: (I0, i)),
            pl.BlockSpec((m_pad + span, k), lambda i: (I0, I0)),
        ],
        out_specs=pl.BlockSpec((bm, k), lambda i: (i, I0)),
        out_shape=jax.ShapeDtypeStruct((m_pad, k), bands.dtype),
        interpret=interpret,
    )(bands_p, xp)
    out = out[:n]
    return out[:, 0] if vec else out

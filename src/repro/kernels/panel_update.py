"""Pallas TPU kernel: rank-K panel (trailing) update — the MXU path.

The beyond-paper blocked condensation (core/blocked.py) turns K rank-1
condensation steps into one trailing update

    A -= C @ R        C: (M, K) coefficients, R: (K, N) pivot panel

with arithmetic intensity ~K/2 FLOP/byte — a real matmul that belongs on
the MXU.  The kernel fuses the GEMM with the subtraction so the trailing
matrix is read and written exactly once (no A' = C@R temporary in HBM).

Tiling: grid (M/bm, N/bn); each program reads
  a tile (bm, bn), c slab (bm, K), r slab (K, bn)
and issues a single (bm x K) @ (K x bn) MXU contraction with f32
accumulation.  bm = bn = 256 and K <= 256 keeps the footprint
(256*256 + 2*256*K) * 4B < 1.3 MiB — far under VMEM; K and the block
dims should be multiples of 128 for full MXU occupancy (the blocked
algorithm's panel width IS this K, so the config plumbs straight into
BlockSpec).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import I0, out_struct, vary_like
from repro.numerics import dot_precision

__all__ = ["panel_update_kernel", "panel_update_pallas"]

DEFAULT_BM = 256
DEFAULT_BN = 256


def panel_update_kernel(a_ref, c_ref, r_ref, o_ref):
    """o = a - c @ r with f32 MXU accumulation."""
    a = a_ref[...]
    c = c_ref[...]              # (bm, K)
    r = r_ref[...]              # (K, bn)
    acc = jax.lax.dot_general(
        c, r, (((1,), (0,)), ((), ())),
        precision=dot_precision(c.dtype),
        preferred_element_type=jnp.float32 if a.dtype != jnp.float64 else jnp.float64,
    )
    o_ref[...] = a - acc.astype(a.dtype)


@functools.partial(jax.jit, static_argnames=("bm", "bn", "interpret"))
def panel_update_pallas(a: jax.Array, c: jax.Array, r: jax.Array, *,
                        bm: int = DEFAULT_BM, bn: int = DEFAULT_BN,
                        interpret: bool = False) -> jax.Array:
    """a (M, N) - c (M, K) @ r (K, N) via a tiled Pallas kernel."""
    m, n = a.shape
    k = c.shape[1]
    if r.shape != (k, n) or c.shape != (m, k):
        raise ValueError(f"shape mismatch: a={a.shape} c={c.shape} r={r.shape}")
    c, r = vary_like(a, c, r)
    bm = min(bm, m)
    bn = min(bn, n)
    grid = (pl.cdiv(m, bm), pl.cdiv(n, bn))
    return pl.pallas_call(
        panel_update_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bn), lambda i, j: (i, j)),
            pl.BlockSpec((bm, k), lambda i, j: (i, I0)),
            pl.BlockSpec((k, bn), lambda i, j: (I0, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j: (i, j)),
        out_shape=out_struct((m, n), a.dtype, a),
        interpret=interpret,
    )(a, c, r)

"""Pallas TPU kernel: fused rank-1 condensation update.

The hot loop of the faithful algorithm (paper pseudocode step 4.11):

    local_A[row, col] -= pivot_column[row] * pivot_row[col]

Arithmetic intensity is ~0.25 FLOP/byte (2 FLOPs per 8-byte f32
read+write pair), so the kernel is HBM-bandwidth-bound and runs on the
VPU.  The kernel's job is to guarantee exactly ONE pass over the buffer
per step: read the (bm, bn) tile, fuse multiply-subtract, write back —
no separate outer-product materialization (which a naive
``a - jnp.outer(pc, pr)`` could do under a fusion-hostile scheduler).

Tiling: grid (M/bm, N/bn); each program reads
  a  tile (bm, bn)   from VMEM
  pc slab (bm, 1)
  pr slab (1, bn)
VMEM footprint per program: bm*bn + bm + bn floats.  Default 256x512 f32
= 512 KiB + eps, well under the ~16 MiB v5e VMEM budget, and both dims
are multiples of the (8, 128) f32 VREG tile.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import I0, out_struct, vary_like

__all__ = ["rank1_update_kernel", "rank1_update_pallas"]

DEFAULT_BM = 256
DEFAULT_BN = 512


def rank1_update_kernel(a_ref, pc_ref, pr_ref, o_ref):
    """o = a - pc * pr  (pc broadcast over cols, pr over rows)."""
    a = a_ref[...]
    pc = pc_ref[...]            # (bm, 1)
    pr = pr_ref[...]            # (1, bn)
    o_ref[...] = a - pc * pr


@functools.partial(jax.jit, static_argnames=("bm", "bn", "interpret"))
def rank1_update_pallas(a: jax.Array, pc: jax.Array, pr: jax.Array, *,
                        bm: int = DEFAULT_BM, bn: int = DEFAULT_BN,
                        interpret: bool = False) -> jax.Array:
    """a (M, N) - outer(pc (M,), pr (N,)) via a tiled Pallas kernel."""
    m, n = a.shape
    pc, pr = vary_like(a, pc, pr)
    bm = min(bm, m)
    bn = min(bn, n)
    grid = (pl.cdiv(m, bm), pl.cdiv(n, bn))
    return pl.pallas_call(
        rank1_update_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bn), lambda i, j: (i, j)),
            pl.BlockSpec((bm, 1), lambda i, j: (i, I0)),
            pl.BlockSpec((1, bn), lambda i, j: (I0, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j: (i, j)),
        out_shape=out_struct((m, n), a.dtype, a),
        interpret=interpret,
    )(a, pc[:, None], pr[None, :])

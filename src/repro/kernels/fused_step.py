"""Pallas TPU kernel: fused one-pass condensation step.

The engine's rank-1 step used to make three passes over the trailing
buffer: the §2.4 column swap (two scatters), then the rank-1 update
(read + write).  But the swap and the update commute into ONE
elementwise pass once the swap is expressed as a per-column select —
column ``l`` takes the old column ``last``, column ``last`` takes the
old column ``l``, everything else passes through — fused with the
multiply-subtract:

    out[:, j] = select_swap(a, j) - pc * pr[j]

Bit-identical to the scatter+outer sequence (pure data movement plus the
same multiply-subtract, asserted in tests/test_kernels.py) and the
buffer is read and written exactly once per step instead of three times.

The O(n) pivot bookkeeping — argmax over the live pivot row, pivot-row
normalization, sign/parity tracking — stays outside the kernel (it
touches one row, not the O(n^2) buffer) in `repro.kernels.ops
.fused_condense_step`, which is the dispatch entry the engine calls.

Tiling: grid (M/bm, N/bn); each program reads
  a tile (bm, bn), the two swap columns + pivot column as (bm, 1) slabs,
  the pivot row as a (1, bn) slab, and the scalar column ids l / last.
Default tiles come from the calibration-driven autotuner
(`repro.kernels.autotune`).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl

from repro.kernels import I0

__all__ = ["fused_step_kernel", "fused_step_pallas"]


def fused_step_kernel(l_ref, last_ref, a_ref, cl_ref, clast_ref,
                      pc_ref, pr_ref, o_ref, *, bn: int):
    """o = swap_select(a; l<->last) - pc * pr, one pass over the tile."""
    j0 = pl.program_id(1) * bn
    cols = j0 + lax.broadcasted_iota(jnp.int32, (1, bn), 1)
    l = l_ref[0]
    last = last_ref[0]
    a = a_ref[...]
    sw = jnp.where(cols == l, clast_ref[...],
                   jnp.where(cols == last, cl_ref[...], a))
    # pc/pr may ride in at a lower precision (bf16 operands); the product
    # is accumulated back into the buffer dtype
    o_ref[...] = sw - (pc_ref[...] * pr_ref[...]).astype(a.dtype)


@functools.partial(jax.jit, static_argnames=("bm", "bn", "interpret"))
def fused_step_pallas(a: jax.Array, l, last, pc: jax.Array, pr: jax.Array,
                      col_l: jax.Array, col_last: jax.Array, *,
                      bm: int = 256, bn: int = 512,
                      interpret: bool = False) -> jax.Array:
    """Fused swap(l<->last) + rank-1 update via a tiled Pallas kernel.

    ``a (M, N)``; ``l`` / ``last`` scalar column ids; ``pc (M,)`` pivot
    column (zeroed at dead/pivot rows); ``pr (N,)`` normalized pivot row;
    ``col_l`` / ``col_last (M,)`` the two pre-swap columns.
    """
    m, n = a.shape
    bm = min(bm, m)
    bn = min(bn, n)
    grid = (pl.cdiv(m, bm), pl.cdiv(n, bn))
    l = jnp.asarray(l, jnp.int32).reshape(1)
    last = jnp.asarray(last, jnp.int32).reshape(1)
    return pl.pallas_call(
        functools.partial(fused_step_kernel, bn=bn),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1,), lambda i, j: (I0,)),       # l
            pl.BlockSpec((1,), lambda i, j: (I0,)),       # last
            pl.BlockSpec((bm, bn), lambda i, j: (i, j)),  # a tile
            pl.BlockSpec((bm, 1), lambda i, j: (i, I0)),   # col_l slab
            pl.BlockSpec((bm, 1), lambda i, j: (i, I0)),   # col_last slab
            pl.BlockSpec((bm, 1), lambda i, j: (i, I0)),   # pc slab
            pl.BlockSpec((1, bn), lambda i, j: (I0, j)),   # pr slab
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), a.dtype),
        interpret=interpret,
    )(l, last, a, col_l[:, None], col_last[:, None], pc[:, None],
      pr[None, :])

"""Pallas TPU kernel: VMEM-resident panel factorization (§Perf P0/It3).

The blocked algorithm's panel factorization runs k rank-1 condensation steps
over a (k, N) panel.  Done with jnp ops, each step re-reads and re-writes the
panel through HBM: 8*k^2*N bytes per panel — at k ~ L this costs as much
traffic as the whole baseline.  But a (k, N) f32 panel at k=32, N<=32k is
at most 4 MiB — it FITS IN VMEM.  This kernel keeps the panel resident for
all k steps: HBM traffic drops to one read + one write (8*k*N), a k-fold
cut — the TPU-native realization of the paper's §2.4 cache-contiguity
insight.

Single-block kernel (grid=()): panel must satisfy k*N*itemsize <= 4 MiB.
The input and output copies plus the select temporaries of a 4 MiB panel
fit v5e's default 16 MiB scoped VMEM; an 8 MiB panel does not.
Scalars (live column count m0, sign parity offset r_pos) ride in through
SMEM.  Outputs: factorized panel R (k,N) in final swapped coordinates,
chosen pivot columns ls (k,), and the panel's (sign, logdet) contribution —
bit-identical semantics to core.engine.panel_factor.  The kernel returns
the k pivots and the wrapper sums their logs, because Mosaic cannot lower
the accurate log (`repro.numerics.log_abs`).

Mosaic has no lowering for traced-index gathers and scatters, so every
row/column access is a masked select against an iota, and every read of
one element or column is a one-hot reduction (exact: the other addends
are zeros).  The argmax is max-then-first-index, which is what
``jnp.argmax`` returns.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import out_struct, vary_like
from repro.numerics import log_abs

__all__ = ["panel_factor_kernel", "panel_factor_pallas"]

VMEM_BUDGET = 4 * 1024 * 1024  # bytes; panel must fit


def _pick(mask, x, axis):
    """The one element of ``x`` under ``mask`` along ``axis`` (keepdims)."""
    return jnp.sum(jnp.where(mask, x, jnp.zeros_like(x)), axis=axis,
                   keepdims=True)


def panel_factor_kernel(m0_ref, rpos_ref, panel_ref, r_ref, ls_ref,
                        pivots_ref, sign_ref):
    k, n = panel_ref.shape
    dt = panel_ref.dtype
    one = jnp.ones((1, 1), dt)
    m0 = m0_ref[0]
    r_pos = rpos_ref[0]
    cols = lax.broadcasted_iota(jnp.int32, (1, n), 1)
    rows = lax.broadcasted_iota(jnp.int32, (k, 1), 0)
    steps = lax.broadcasted_iota(jnp.int32, (1, k), 1)

    def body(carry):
        j, ls, pivots, sign = carry
        buf = r_ref[...]
        m = m0 - j
        last = m - 1
        row = _pick(rows == j, buf, 0)                       # (1, n)
        absrow = jnp.where(cols < m, jnp.abs(row), -jnp.inf)
        top = jnp.max(absrow, axis=1, keepdims=True)
        l = jnp.min(jnp.where(absrow == top, cols, jnp.int32(n)), axis=1,
                    keepdims=True)                           # (1, 1)
        pv = _pick(cols == l, row, 1)

        # swap columns l <-> last across the whole panel
        cl = _pick(cols == l, buf, 1)                        # (k, 1)
        clast = _pick(cols == last, buf, 1)
        buf = jnp.where(cols == l, clast, jnp.where(cols == last, cl, buf))

        # normalize the pivot row; store it back (it becomes R[j])
        row = _pick(rows == j, buf, 0)
        safe = jnp.where(pv == 0, one, pv)
        pr = jnp.where(pv == 0, jnp.zeros_like(row), row / safe)
        pr = jnp.where((cols == last) & (pv != 0), one, pr)
        buf = jnp.where(rows == j, pr, buf)

        # rank-1 update of the remaining panel rows (j+1..k-1)
        pc = _pick(cols == last, buf, 1)
        pc = jnp.where(rows <= j, jnp.zeros_like(pc), pc)
        r_ref[...] = buf - pc * pr

        ls = jnp.where(steps == j, l, ls)
        pivots = jnp.where(steps == j, pv, pivots)
        odd = lax.rem(r_pos + m - 1, jnp.int32(2)) != 0
        parity = jnp.where(odd, -one, one)
        swap_sign = jnp.where(l == last, one, -one)
        pv_sign = jnp.where(pv > 0, one, jnp.where(pv < 0, -one, 0 * one))
        sign = sign * pv_sign * swap_sign * parity
        return j + 1, ls, pivots, sign

    r_ref[...] = panel_ref[...]
    # a while_loop, not fori_loop: fori_loop's static bounds give an i64
    # index under jax_enable_x64, and Mosaic has no 64-bit integers
    _, ls, pivots, sign = lax.while_loop(
        lambda c: c[0] < k, body,
        (jnp.int32(0), jnp.zeros((1, k), jnp.int32), jnp.zeros((1, k), dt),
         one))
    ls_ref[...] = ls
    pivots_ref[...] = pivots
    sign_ref[...] = sign


@functools.partial(jax.jit, static_argnames=("interpret",))
def panel_factor_pallas(panel: jax.Array, m0, r_pos=0, *,
                        interpret: bool = False):
    """VMEM-resident panel factorization; returns (R, ls, sign, logdet)."""
    k, n = panel.shape
    if k * n * panel.dtype.itemsize > VMEM_BUDGET:
        raise ValueError(f"panel {panel.shape} exceeds VMEM budget")
    m0, r_pos = vary_like(panel, jnp.asarray(m0, jnp.int32).reshape(1),
                          jnp.asarray(r_pos, jnp.int32).reshape(1))
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    vmem = pl.BlockSpec(memory_space=pltpu.VMEM)
    r, ls, pivots, sign = pl.pallas_call(
        panel_factor_kernel,
        in_specs=[smem, smem, vmem],
        out_specs=[vmem, vmem, vmem, vmem],
        out_shape=[
            out_struct((k, n), panel.dtype, panel),
            out_struct((1, k), jnp.int32, panel),
            out_struct((1, k), panel.dtype, panel),
            out_struct((1, 1), panel.dtype, panel),
        ],
        interpret=interpret,
    )(m0, r_pos, panel)
    # the pivots' logs, summed in the engine's order (Mosaic has no
    # accurate log)
    logdet = lax.fori_loop(0, k, lambda j, acc: acc + log_abs(pivots[0, j]),
                           pivots[0, 0] * 0)
    return r, ls[0], sign[0, 0], logdet

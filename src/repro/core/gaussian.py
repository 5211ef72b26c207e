"""Gaussian elimination baselines (paper §2.5, §3).

The paper compares Matrix Condensation against a self-implemented parallel
Gaussian Elimination with partial pivoting.  GE *must* eliminate top-to-bottom,
so load balance requires a **cyclic row distribution**, and partial pivoting
requires a **global pivot search + cross-processor row exchange** each step —
the two costs MC avoids.  We reproduce both faithfully:

  * ``slogdet_ge``            — serial GE with partial pivoting (static shapes).
  * ``parallel_slogdet_ge``   — shard_map parallel GE, cyclic rows, global
                                argmax pivot search, pivot-row and displaced-row
                                broadcasts (the paper's extra communications).

Communication per step (counted in benchmarks/fig9_comm.py):
  GE:  global argmax (all-reduce) + 2 row broadcasts  (pivot row + displaced row)
  MC:  1 row broadcast                                 (see core/parallel.py)
"""
from __future__ import annotations

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax

# GE stays a separate algorithm family (the paper's comparison baseline),
# but the sign/permutation helpers are the engine's shared ones
from repro.core.engine import cyclic_perm, guarded_pivot, perm_parity
from repro.numerics import log_abs

__all__ = ["slogdet_ge", "parallel_slogdet_ge", "ge_step_fn", "cyclic_perm", "perm_parity"]


@jax.jit
def slogdet_ge(a: jax.Array):
    """Serial Gaussian elimination with partial pivoting.

    Returns ``(sign, logabsdet)`` with `numpy.linalg.slogdet` semantics.
    Static-shape friendly: every step works on the full buffer with masking.
    """
    n = a.shape[0]
    if a.ndim != 2 or a.shape[1] != n:
        raise ValueError(f"expected square matrix, got {a.shape}")
    if n == 0:
        return jnp.ones((), a.dtype), jnp.zeros((), a.dtype)

    rows = jnp.arange(n)

    def body(t, carry):
        buf, sign, logdet = carry
        col = jnp.take(buf, t, axis=1)
        # partial pivot: global argmax of |col| among rows >= t
        cand = jnp.where(rows >= t, jnp.abs(col), -jnp.inf)
        r = jnp.argmax(cand)
        p = buf[r, t]

        # swap rows r <-> t
        row_r = buf[r]
        row_t = buf[t]
        buf = buf.at[r].set(row_t)
        buf = buf.at[t].set(row_r)
        sign = sign * jnp.where(r == t, 1.0, -1.0).astype(a.dtype)

        pr = buf[t]                                   # pivot row (unnormalized)
        safe_p = guarded_pivot(p, a.dtype)
        factor = jnp.where(rows > t, jnp.take(buf, t, axis=1) / safe_p, 0.0)
        buf = buf - factor[:, None] * pr[None, :]

        sign = sign * jnp.sign(p)
        logdet = logdet + log_abs(p)
        return buf, sign, logdet

    buf, sign, logdet = lax.fori_loop(
        0, n, body, (a, jnp.ones((), a.dtype), jnp.zeros((), a.dtype))
    )
    return sign, logdet


def ge_step_fn(axis_name: str):
    """Per-step body of parallel GE for use inside shard_map.

    Cyclic row distribution: global row ``g`` lives on device ``g % P`` at
    local index ``g // P``.  Returns ``step(t, (local, sign, ld))`` where
    ``local`` has shape (L, N).
    """

    def step(t, carry):
        local, sign, logdet = carry
        L, N = local.shape
        P = lax.axis_size(axis_name)
        me = lax.axis_index(axis_name)
        lrow = jnp.arange(L)
        grow = lrow * P + me                     # global index of each local row

        # ---- 1. global pivot search over column t among global rows >= t ----
        col = jnp.take(local, t, axis=1)
        cand = jnp.where(grow >= t, jnp.abs(col), -jnp.inf)
        lmax_i = jnp.argmax(cand)
        lmax_v = cand[lmax_i]
        vals = lax.all_gather(lmax_v, axis_name)           # (P,) comm #1
        grs = lax.all_gather(grow[lmax_i], axis_name)      # (P,)
        best = jnp.argmax(vals)                            # first max: determinstic
        pivot_g = grs[best]                                # global pivot row

        # ---- 2. broadcast pivot row and displaced row t ----------------------
        owner_p = pivot_g % P
        owner_t = t % P
        li_p = pivot_g // P
        li_t = t // P
        mine_p = owner_p == me
        mine_t = owner_t == me
        contrib_p = jnp.where(mine_p, local[li_p], jnp.zeros((N,), local.dtype))
        contrib_t = jnp.where(mine_t, local[li_t], jnp.zeros((N,), local.dtype))
        # two row broadcasts == GE's extra comm vs MC (psum realizes bcast)
        both = lax.psum(jnp.stack([contrib_p, contrib_t]), axis_name)  # comm #2
        pivot_row, row_t = both[0], both[1]
        p = pivot_row[t]

        # ---- 3. row exchange: owner of row t gets pivot row and vice versa --
        swapped = pivot_g != t
        new_lt = jnp.where(swapped & mine_t, pivot_row, local[li_t])
        local = local.at[li_t].set(new_lt)
        new_lp = jnp.where(swapped & mine_p, row_t, local[li_p])
        local = local.at[li_p].set(new_lp)

        # ---- 4. elimination on my rows with global index > t ----------------
        safe_p = guarded_pivot(p, local.dtype)
        factor = jnp.where(grow > t, jnp.take(local, t, axis=1) / safe_p, 0.0)
        local = local - factor[:, None] * pivot_row[None, :]

        sign = sign * jnp.where(swapped, -1.0, 1.0).astype(local.dtype)
        sign = sign * jnp.sign(p)
        logdet = logdet + log_abs(p)
        return local, sign, logdet

    return step


def parallel_slogdet_ge(mesh, axis_name: str = "rows"):
    """Parallel GE with partial pivoting over a 1-D device mesh.

    Returns a jitted function ``f(a) -> (sign, logabsdet)`` for an ``(N, N)``
    matrix with ``N`` divisible by the mesh size.  Rows are distributed
    cyclically (global row g -> device g % P), which is what load-balances GE
    (paper Fig. 1) but costs a strided scatter (benchmarked in fig9).
    """
    from jax.sharding import PartitionSpec

    step = ge_step_fn(axis_name)
    nproc = int(np.prod([mesh.shape[a] for a in ([axis_name] if isinstance(axis_name, str) else axis_name)]))

    def kernel(local):
        # local: (L, N) cyclic block, row-major as in the paper
        N = local.shape[1]
        sign0 = lax.pcast(jnp.ones((), local.dtype), axis_name, to="varying")
        ld0 = lax.pcast(jnp.zeros((), local.dtype), axis_name, to="varying")
        local, sign, logdet = lax.fori_loop(0, N, step, (local, sign0, ld0))
        # sign/logdet are accumulated identically on all devices.
        return sign.reshape(1), logdet.reshape(1)

    shmapped = jax.shard_map(
        kernel,
        mesh=mesh,
        in_specs=(PartitionSpec(axis_name, None),),
        out_specs=(PartitionSpec(axis_name), PartitionSpec(axis_name)),
    )

    import functools as _ft

    @_ft.lru_cache(maxsize=8)
    def _go(n: int):
        if n % nproc:
            raise ValueError(f"N={n} not divisible by mesh size {nproc}")
        perm = cyclic_perm(n, nproc)
        parity = perm_parity(perm)

        @jax.jit
        def go(a):
            ac = a[jnp.asarray(perm)]
            sign, logdet = shmapped(ac)
            return sign[0] * jnp.asarray(parity, a.dtype), logdet[0]

        return go

    def run(a):
        return _go(a.shape[0])(a)

    run.lower = lambda a: _go(a.shape[0]).lower(a)   # HLO introspection
    return run

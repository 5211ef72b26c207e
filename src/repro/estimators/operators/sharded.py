"""Mesh-sharded backend: row-distributed dense matvec via shard_map.

Matrix rows live sharded over a 1-D device mesh; probe slabs are
replicated; each device multiplies its (L, n) row block against the
resident (n, k) slab (through the tiled Pallas matvec kernel on TPU) and
the row chunks concatenate back along the row axis.  The layout matches
the parallel condensation core (device ``p`` owns rows ``[p*L, (p+1)*L)``)
so a matrix can be handed from the exact path to the estimator path
without a resharding pass.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec

from repro.estimators.operators.base import (
    LinearOperator, PlanHints, check_square,
)

__all__ = ["ShardedOperator", "rowwise_matvec_specs"]


def rowwise_matvec_specs(axis_name: str):
    """(in_specs, out_specs) for a row-distributed matvec under shard_map.

    Matrix rows sharded over ``axis_name``, probe slab replicated, result row
    chunks concatenated back along the row axis.
    """
    p = PartitionSpec
    return (p(axis_name, None), p(None, None)), p(axis_name, None)


@functools.lru_cache(maxsize=16)
def _sharded_mm(mesh, axis_name: str, use_kernel: bool):
    from repro.kernels import ops as _kops

    def kernel(local, v):            # local (L, n), v (n, k) replicated
        if use_kernel:
            return _kops.matvec(local, v)
        return local @ v

    in_specs, out_specs = rowwise_matvec_specs(axis_name)
    return jax.jit(jax.shard_map(kernel, mesh=mesh,
                              in_specs=in_specs, out_specs=out_specs))


class ShardedOperator(LinearOperator):
    """Row-distributed dense operator over a 1-D mesh.

    ``n`` must be divisible by the mesh size (pad via
    ``repro.core.pad_to_multiple``, which leaves the determinant unchanged).
    """

    def __init__(self, a: jax.Array, mesh, axis_name: str = "rows", *,
                 use_kernel: bool = True):
        a = jnp.asarray(a)
        check_square(a.shape)
        nproc = int(mesh.shape[axis_name])
        if a.shape[0] % nproc:
            raise ValueError(
                f"N={a.shape[0]} not divisible by mesh size {nproc}; "
                "pad with repro.core.pad_to_multiple first")
        self.mesh = mesh
        self.axis_name = axis_name
        self.use_kernel = use_kernel
        self.shape = a.shape
        self.dtype = a.dtype
        self.a = jax.device_put(
            a, NamedSharding(mesh, PartitionSpec(axis_name, None)))
        self._mm = _sharded_mm(mesh, axis_name, use_kernel)

    def mm(self, v):
        return self._mm(self.a, v.astype(self.dtype))

    def rmm(self, v):
        # transposed matvec as (v^T A)^T on the row-sharded buffer — the
        # contraction over the sharded row axis lowers to a psum under
        # XLA's sharding propagation, no explicit shard_map needed
        vt = jnp.swapaxes(v.astype(self.dtype), -1, -2)
        return jnp.swapaxes(vt @ self.a, -1, -2)

    def diag(self):
        # gathers one element per row — cheap relative to any matvec
        return jnp.diagonal(self.a)

    def trace_hint(self):
        return jnp.trace(self.a)

    def to_dense(self):
        return self.a

    def plan_hints(self):
        # dense cost split across the mesh; rows are resident (sharded), so
        # the exact parallel condensation path stays available
        n = self.n
        p = int(self.mesh.shape[self.axis_name])
        return PlanHints(structure="sharded", matvec_flops=2.0 * n * n / p,
                         materializable=True, device_count=p)

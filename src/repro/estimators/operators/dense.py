"""Dense backend: wraps an in-memory (n, n) matrix.

The reference backend — zero structure assumed, one XLA GEMM per ``mm``.
Right when the matrix already fits in device memory and N is moderate;
every structured backend in this package exists to beat it on memory
(never materialize A) or FLOPs (exploit structure in the product).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.estimators.operators.base import (
    LinearOperator, PlanHints, check_square,
)
from repro.numerics import matmul

__all__ = ["DenseOperator"]


class DenseOperator(LinearOperator):
    """Wraps an in-memory (n, n) matrix."""

    def __init__(self, a: jax.Array):
        a = jnp.asarray(a)
        check_square(a.shape)
        self.a = a
        self.shape = a.shape
        self.dtype = a.dtype

    def mm(self, v):
        return matmul(self.a, v)

    def mv(self, v):
        return matmul(self.a, v)

    def rmm(self, v):
        return matmul(self.a.T, v)

    def rmv(self, v):
        return matmul(self.a.T, v)

    def diag(self):
        return jnp.diagonal(self.a)

    def trace_hint(self):
        return jnp.trace(self.a)

    def to_dense(self):
        return self.a

    def plan_hints(self):
        # the matrix is already resident: exact O(n^3) methods are fair game
        n = self.n
        return PlanHints(structure="dense", matvec_flops=2.0 * n * n,
                         materializable=True)

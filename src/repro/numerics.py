"""The float32 accuracy policy, in one place.

On the TPU a float32 matmul runs by default as one bfloat16 pass, and the
float32 log is approximate (up to 1e-4 absolute error, measured on a
v5e).  Summed over N pivots or N products, either breaks the float32
contract.  The engine, the kernels and their references, the operators
and the gradient take their contractions and pivot logs from here, so a
new call site cannot silently fall back to the fast defaults.  On the CPU
both defaults are already exact and nothing changes.

A leaf module: it imports jax only, and every layer may import it.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

__all__ = ["HIGHEST", "dot_precision", "matmul", "full_precision",
           "log_abs"]

HIGHEST = lax.Precision.HIGHEST


def dot_precision(dtype):
    """The ``precision=`` of a contraction over ``dtype`` operands.

    HIGHEST, except for bfloat16 operands (the ``precision="bf16"``
    routes), which take the default single pass: Mosaic rejects HIGHEST
    on them, and it would not add digits the operands lack.
    """
    return None if jnp.dtype(dtype) == jnp.bfloat16 else HIGHEST


def matmul(a, b):
    """``a @ b`` with full float32 products."""
    return jnp.matmul(a, b, precision=dot_precision(a.dtype))


def full_precision():
    """Context in which every matmul inside a jnp routine (``inv``,
    ``solve``, ...) runs with full float32 products."""
    return jax.default_matmul_precision("highest")


def log_abs(p):
    """``log|p|`` at full float32 accuracy (the accurate TPU log).

    Mosaic cannot lower it: Pallas kernels return pivots and the log is
    taken outside them.
    """
    return lax.log(jnp.abs(p), accuracy=lax.AccuracyMode.HIGHEST)

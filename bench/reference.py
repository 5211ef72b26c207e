"""The plain reference: float64 LAPACK on the host (through numpy and
scipy).

It imports nothing of ``repro`` and takes only the inputs the benchmark
made (never a factor, a table or a result of the program).
"""
from __future__ import annotations

import numpy as np
from scipy.linalg import lapack


def factor64(a) -> tuple:
    """``(sign, logabsdet, cond1)`` of ``a`` in float64: LU with partial
    pivoting (``dgetrf``), and the 1-norm condition number that
    ``dgecon`` estimates from it."""
    a64 = np.array(a, np.float64, order="F")
    anorm = float(np.abs(a64).sum(axis=0).max())
    lu, piv, info = lapack.dgetrf(a64, overwrite_a=True)
    if info < 0:
        raise ValueError(f"dgetrf: argument {-info} is invalid")
    d = np.diag(lu)
    if info > 0:                                # exactly singular
        return 0.0, -np.inf, np.inf
    swaps = np.count_nonzero(piv != np.arange(len(piv)))
    sign = (-1.0) ** swaps * float(np.prod(np.sign(d)))
    rcond, info = lapack.dgecon(lu, anorm, norm="1")
    return sign, float(np.sum(np.log(np.abs(d)))), 1.0 / rcond


def logdet_grad64(a) -> np.ndarray:
    """d log|det A| / dA = A^{-T}, in float64."""
    return np.linalg.inv(np.asarray(a, np.float64)).T


def rel_fro_err(got, want: np.ndarray) -> float:
    got = np.asarray(got, np.float64)
    return float(np.linalg.norm(got - want) / np.linalg.norm(want))

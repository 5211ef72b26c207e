"""The comparisons that decide ``correct``: what the timed path produced
against the float64 reference (``reference.py``).

Each takes answers as ``(call index, pool index, answer)`` triples and
the dtype the configuration states, and returns ``(checks, failed)``:
``checks`` maps a short name to ``(value, limit)``, and ``failed`` is the
set of calls that missed a limit.  A NaN reads as infinity, so it fails
every limit.  An answer in another dtype than the configuration's fails
(``dtype_mismatches``, limit 0): a program that lowers its precision may
not lower the yardstick with it.

The logabsdet error is compared in units of ``cond1(A) * u``, ``u`` the
unit roundoff of the configuration's dtype: a backward-stable
factorization errs by about that much, so the number is steady from
matrix to matrix.
A relative error is not: on Gaussian matrices its tail follows the
smallest singular value, and one matrix in a few hundred reads a hundred
times the median.
"""
from __future__ import annotations

import math

import numpy as np

from reference import factor64, logdet_grad64, rel_fro_err


def _finite(x: float) -> float:
    return x if math.isfinite(x) else math.inf


def values(pool, calls, limits: dict, dtype: str):
    """Sign and logabsdet (``answer[:2]``) of every call."""
    want = np.dtype(dtype)
    u = float(np.finfo(want).eps) / 2
    refs = {}
    mismatches, wrong_dtype, worst, failed = 0, 0, 0.0, set()
    for i, j, out in calls:
        if j not in refs:
            refs[j] = factor64(pool[j])
        ref_sign, ref_ld, cond = refs[j]
        ld = np.asarray(out[1])
        err = _finite(float(abs(float(ld) - ref_ld) / (cond * u)))
        bad_sign = float(out[0]) != ref_sign
        bad_dtype = ld.dtype != want
        mismatches += bad_sign
        wrong_dtype += bad_dtype
        worst = max(worst, err)
        if bad_sign or bad_dtype or err > limits["logabsdet_err_cond"]:
            failed.add(i)
    return {"sign_mismatches": (mismatches, limits["sign_mismatches"]),
            "dtype_mismatches": (wrong_dtype, 0),
            "logabsdet_err_cond": (worst, limits["logabsdet_err_cond"])}, \
        failed


def grads(pool, sample, limits: dict, dtype: str):
    """Relative Frobenius error of each sampled gradient (``answer[2]``)."""
    want = np.dtype(dtype)
    refs, wrong_dtype, worst, failed = {}, 0, 0.0, set()
    for i, j, out in sample:
        if j not in refs:
            refs[j] = logdet_grad64(pool[j])
        g = np.asarray(out[2])
        err = _finite(rel_fro_err(g, refs[j]))
        wrong_dtype += g.dtype != want
        worst = max(worst, err)
        if g.dtype != want or err > limits["grad_rel_err"]:
            failed.add(i)
    return {"grad_dtype_mismatches": (wrong_dtype, 0),
            "grad_rel_err": (worst, limits["grad_rel_err"])}, failed

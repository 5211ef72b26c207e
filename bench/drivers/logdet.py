"""Exact log-determinant through the entry a user calls:
``repro.plan((n, n), method="auto", rtol=...)``, then ``LogdetPlan.__call__``.

The rtol is what an exact caller passes: it keeps the estimators out and
leaves the route, the panel width and the kernels to the planner.
"""
from __future__ import annotations

import compare

SAMPLE = 0                      # answers kept whole for the check


def build(config: dict, traffic: dict, precision=None):
    import repro
    n = int(traffic["n"])
    return repro.plan((n, n), method="auto", rtol=float(traffic["rtol"]),
                      precision=precision)


def call(plan, x) -> tuple:
    """One logdet.  ``LogdetPlan.__call__`` returns after
    ``block_until_ready``."""
    res = plan(x)
    return res.sign, res.logabsdet


def build_control(config: dict, traffic: dict):
    """The program's own lower-precision path: bfloat16 GEMM operands."""
    return build(config, traffic, precision="bf16")


control_call = call


def light(out) -> tuple:
    """The part of an answer kept for every call of the window."""
    return out


def check(pool, calls, sample, limits: dict, dtype: str):
    return compare.values(pool, calls, limits, dtype)

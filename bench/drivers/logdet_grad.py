"""Log-determinant and its gradient through the entry a user calls:
``repro.plan((n, n), method="auto", rtol=...)``, then
``LogdetPlan.value_and_grad``: the forward, and the backward that
``_build_value_and_grad`` builds."""
from __future__ import annotations

from pathlib import Path

import compare
import registry

SAMPLE = 4                      # gradients kept: a reservoir, from the seed

# the same plan as logdet.py builds
build = registry.load_module(Path(__file__).with_name("logdet.py")).build


def call(plan, x) -> tuple:
    """One step.  ``value_and_grad`` returns after ``block_until_ready``
    of the gradient."""
    res, g = plan.value_and_grad(x)
    return res.sign, res.logabsdet, g


def build_control(config: dict, traffic: dict):
    return build(config, traffic, precision="bf16")


def control_call(plan, x) -> tuple:
    """The reference in the program's place one precision down: the value
    from the program's bfloat16 path, the gradient as the inverse of the
    input rounded to bfloat16 (an inverse computed in bfloat16 can only
    be worse)."""
    import jax
    import jax.numpy as jnp
    res = plan(x)
    xb = x.astype(jnp.bfloat16).astype(x.dtype)
    g = jax.block_until_ready(jnp.linalg.inv(xb).T)
    return res.sign, res.logabsdet, g


def light(out) -> tuple:
    return out[:2]


def check(pool, calls, sample, limits: dict, dtype: str):
    """The values of every call, the gradients of the sample."""
    checks, failed = compare.values(pool, calls, limits, dtype)
    grad_checks, grad_failed = compare.grads(pool, sample, limits, dtype)
    return {**checks, **grad_checks}, failed | grad_failed

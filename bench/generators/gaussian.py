"""Dense N x N matrices with independent standard normal entries, the
paper's test matrices (arXiv:1811.08057, section 5), made on the device."""
from __future__ import annotations

import jax
import jax.numpy as jnp


def make(key, config: dict, traffic: dict) -> tuple:
    """A pool of ``traffic["pool"]`` distinct matrices, in one jitted call."""
    n, m = int(traffic["n"]), int(traffic["pool"])
    dtype = jnp.dtype(config["dtype"])

    @jax.jit
    def pool(key):
        return tuple(jax.random.normal(k, (n, n), dtype)
                     for k in jax.random.split(key, m))

    return pool(key)

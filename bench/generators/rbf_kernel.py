"""RBF kernel matrices of an exact-GP marginal likelihood, made on the
device: ``K = s2 * exp(-|x_i - x_j|^2 / (2 l^2)) + noise * I`` over N
points ``x ~ N(0, I_d)`` (standardised features)."""
from __future__ import annotations

import jax
import jax.numpy as jnp


def make(key, config: dict, traffic: dict) -> tuple:
    """A pool of ``traffic["pool"]`` kernel matrices, each over its own
    points, in one jitted call."""
    n, m, d = int(traffic["n"]), int(traffic["pool"]), int(config["d"])
    ell, s2, noise = (float(config[k]) for k in
                      ("lengthscale", "outputscale", "noise"))
    dtype = jnp.dtype(config["dtype"])

    def kernel(key):
        x = jax.random.normal(key, (n, d), dtype)
        # differences, not |x|^2 + |y|^2 - 2 x.y: no matmul that the TPU
        # would round to bfloat16
        d2 = jnp.sum(jnp.square(x[:, None, :] - x[None, :, :]), axis=-1)
        k = s2 * jnp.exp(d2 * (-0.5 / (ell * ell)))
        # the reduction over d may round i,j and j,i apart: symmetrize
        return (k + k.T) * 0.5 + noise * jnp.eye(n, dtype=dtype)

    @jax.jit
    def pool(key):
        return tuple(kernel(k) for k in jax.random.split(key, m))

    return pool(key)

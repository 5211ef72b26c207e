"""Pallas TPU kernels for the condensation engine and the estimators.

`repro.kernels.ops` is the dispatch layer the algorithms call; `ref`
holds the jnp references the kernels are tested against.
"""
import jax
import numpy as np

# The constant block index of a BlockSpec index map.  A bare Python 0
# becomes an i64 constant under jax_enable_x64, which Mosaic cannot lower
# (every kernel fails to compile for the TPU); an int32 0 always lowers.
I0 = np.int32(0)


# Inside shard_map a pallas_call must state how its outputs vary over the
# mesh axes, and its body combines operands only when they vary alike (a
# broadcast pivot row is replicated, the local block is not).  Outside
# shard_map both helpers are no-ops.

def out_struct(shape, dtype, like) -> jax.ShapeDtypeStruct:
    """A kernel output aval varying over the same mesh axes as ``like``."""
    return jax.ShapeDtypeStruct(shape, dtype, vma=jax.typeof(like).vma)


def vary_like(like, *xs):
    """``xs``, each marked varying over the mesh axes ``like`` varies on."""
    want = jax.typeof(like).vma
    return tuple(
        jax.lax.pcast(x, tuple(want - jax.typeof(x).vma), to="varying")
        if want - jax.typeof(x).vma else x for x in xs)

"""Shared test fixtures.

NOTE: XLA_FLAGS / host device count is deliberately NOT set here — smoke
tests and benchmarks must see the real single CPU device.  Tests that need
multiple devices spawn a subprocess (see tests/_subproc.py).
"""
import numpy as np
import pytest

import jax

jax.config.update("jax_enable_x64", True)


@pytest.fixture
def rng():
    return np.random.default_rng(0)


@pytest.fixture
def mesh1():
    """Single-device 1-D mesh — exercises shard_map plumbing in-process."""
    from repro.launch.mesh import make_mesh
    return make_mesh((1,), ("rows",))

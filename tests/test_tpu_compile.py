"""The main path's Pallas kernels compile for a TPU v5e, at the widths the
chip smoke (``chip_smoke.py``) runs them.

Nothing runs: each kernel is lowered and compiled for a described,
unattached ``v5e:2x2`` topology, so the chip's own compiler (Mosaic)
refuses here what it would refuse on the chip.  The session runs with
``jax_enable_x64`` on (conftest), which is what once broke every kernel
(Python-int block indices became i64): these tests are the regression
test for the int32 index maps.
"""
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

F32, I32 = jnp.float32, jnp.int32


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "cannot"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    # compiles for a described chip cannot be read back from the persistent
    # cache without one; keep them out of it
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)


def _compile(one_chip, fn, *shapes):
    """Compiled text of ``fn`` on ``shapes`` ((shape, dtype) pairs)."""
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes]
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text
    return text


def test_x64_is_on():
    """The compiles below must run under x64 to guard the index maps."""
    assert jax.config.jax_enable_x64


def test_rank1_update_n8000(one_chip):
    from repro.kernels.condense_step import rank1_update_pallas
    n = 8000
    _compile(one_chip, rank1_update_pallas,
             ((n, n), F32), ((n,), F32), ((n,), F32))


@pytest.mark.parametrize("n", [8000, 16384])
def test_fused_step(one_chip, n):
    from repro.kernels.fused_step import fused_step_pallas
    _compile(one_chip, fused_step_pallas,
             ((n, n), F32), ((), I32), ((), I32),
             ((n,), F32), ((n,), F32), ((n,), F32), ((n,), F32))


def test_panel_update_k32(one_chip):
    from repro.kernels.panel_update import panel_update_pallas
    n, k = 16384, 32
    _compile(one_chip, panel_update_pallas,
             ((n, n), F32), ((n, k), F32), ((k, n), F32))


def test_panel_factor_k32_n16384(one_chip):
    from repro.kernels.panel_factor import panel_factor_pallas
    _compile(one_chip, panel_factor_pallas,
             ((32, 16384), F32), ((), I32), ((), I32))


def test_matvec(one_chip):
    from repro.kernels.matvec import matvec_pallas
    _compile(one_chip, matvec_pallas, ((8192, 8192), F32), ((8192, 32), F32))


def test_cheb_step_n1024(one_chip):
    from repro.kernels.fused_est import cheb_step_pallas
    n, k = 1024, 32
    _compile(one_chip, cheb_step_pallas,
             ((n, n), F32), ((n, k), F32), ((n, k), F32), ((n, k), F32),
             ((), F32), ((), F32))


def test_cg_step_n1024(one_chip):
    from repro.kernels.fused_est import cg_step_pallas
    n, k = 1024, 32
    _compile(one_chip, cg_step_pallas,
             ((n, n), F32), ((n, k), F32), ((n, k), F32), ((n, k), F32),
             ((k,), F32))

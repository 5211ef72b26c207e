"""Parallel algorithms: in-process on a 1-device mesh (plumbing) and in a
subprocess with 8 fake devices (real multi-device semantics, incl. the
paper's block/cyclic distributions and pivot broadcasts)."""
import numpy as np
import pytest

import jax

from repro.core import slogdet
from tests._subproc import run_with_devices


PARALLEL_METHODS = ["pmc", "pmc_blocked", "pge", "plu"]


@pytest.mark.parametrize("method", PARALLEL_METHODS)
def test_parallel_one_device(method, mesh1, rng):
    a = rng.standard_normal((24, 24))
    s_ref, ld_ref = np.linalg.slogdet(a)
    s, ld = slogdet(a, method=method, mesh=mesh1, k=8, nb=4)
    assert float(s) == pytest.approx(s_ref)
    np.testing.assert_allclose(float(ld), ld_ref, rtol=1e-9)


@pytest.mark.slow
def test_parallel_eight_devices():
    out = run_with_devices(
        """
import sys; sys.path.insert(0, %r)
from repro.core import slogdet
from repro.launch.mesh import make_mesh
mesh = make_mesh((8,), ("rows",))
rng = np.random.default_rng(11)
for n in (64, 100):
    a = rng.standard_normal((n, n))
    s_ref, ld_ref = np.linalg.slogdet(a)
    for m in ("pmc", "pmc_blocked", "pge", "plu"):
        s, ld = slogdet(a, method=m, mesh=mesh, k=4, nb=2)
        assert float(s) == s_ref, (m, n, float(s), s_ref)
        assert abs(float(ld) - ld_ref) < 1e-8, (m, n, float(ld), ld_ref)
print("OK")
""" % __import__("tests._subproc", fromlist=["SRC"]).SRC,
        n_devices=8,
    )
    assert "OK" in out


@pytest.mark.slow
def test_parallel_matches_across_device_counts():
    """Same matrix, 1/2/4/8 devices -> identical logdet to 1e-10 (paper §3
    reports 10 significant digits across processor counts)."""
    code = """
import sys; sys.path.insert(0, %r)
from repro.core import slogdet
from repro.launch.mesh import make_mesh
mesh = make_mesh((jax.device_count(),), ("rows",))
rng = np.random.default_rng(42)
a = rng.standard_normal((96, 96))
s, ld = slogdet(a, method="pmc", mesh=mesh)
print(repr(float(ld)))
""" % __import__("tests._subproc", fromlist=["SRC"]).SRC
    vals = [float(run_with_devices(code, n).strip()) for n in (1, 2, 4, 8)]
    ref = np.linalg.slogdet(np.random.default_rng(42).standard_normal((96, 96)))[1]
    for v in vals:
        np.testing.assert_allclose(v, ref, rtol=1e-10)

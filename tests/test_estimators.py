"""Stochastic estimator subsystem: accuracy vs the exact condensation core,
operator backends (dense / batched / sharded), probe statistics, and the
Pallas tiled matvec kernel vs its jnp oracle."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core import logdet_batched, slogdet
from repro.estimators import (
    BatchedOperator,
    DenseOperator,
    ShardedOperator,
    chebyshev_coeffs_log,
    estimate_logdet,
    hutchinson_trace,
    lanczos,
    logdet_chebyshev,
    logdet_slq,
    make_probes,
    spectral_bounds,
)
from repro.kernels import ref
from repro.kernels.matvec import matvec_pallas


def make_spd(n, seed, shift=2.0):
    rng = np.random.default_rng(seed)
    x = rng.standard_normal((n, 2 * n))
    return x @ x.T / (2 * n) + shift * np.eye(n)


# ---------------------------------------------------------------- accuracy

@pytest.mark.parametrize("method,kw", [
    ("chebyshev", dict(degree=64, num_probes=32)),
    ("slq", dict(num_steps=25, num_probes=32)),
])
def test_estimator_median_rel_err(method, kw):
    """Acceptance: < 1e-2 median relative error vs method='mc' on seeded
    well-conditioned SPD matrices."""
    errs = []
    for seed in range(5):
        a = make_spd(192, seed)
        _, ld_exact = slogdet(a, method="mc")
        _, ld_est = slogdet(a, method=method, seed=seed, **kw)
        errs.append(abs(float(ld_est) - float(ld_exact)) / abs(float(ld_exact)))
    assert np.median(errs) < 1e-2, errs


def test_estimate_logdet_tracks_uncertainty():
    a = make_spd(128, 0)
    res = estimate_logdet(a, method="chebyshev", num_probes=16, seed=1)
    assert res.samples.shape == (16,)
    assert float(res.sem) > 0
    # the reported standard error should bracket the truth within ~5 sigma
    _, ld_ref = np.linalg.slogdet(a)
    assert abs(float(res.est) - ld_ref) < 5 * float(res.sem) + 1.0


def test_estimator_unknown_method():
    with pytest.raises(ValueError, match="unknown estimator"):
        estimate_logdet(make_spd(16, 0), method="taylor")


def test_slogdet_rejects_estimator_kwargs_on_exact():
    with pytest.raises(TypeError, match="estimator keywords"):
        slogdet(np.eye(8), method="mc", num_probes=4)


# ---------------------------------------------------------------- batched

def test_logdet_batched_matches_vmapped_exact():
    """Acceptance: stack of >= 8 covariances vs a vmapped exact reference."""
    stack = np.stack([make_spd(64, s, shift=1.5 + 0.1 * s) for s in range(8)])
    ref_ld = np.array([np.linalg.slogdet(m)[1] for m in stack])

    exact = np.asarray(logdet_batched(stack, method="mc"))
    np.testing.assert_allclose(exact, ref_ld, rtol=1e-10)

    for method, kw in [("chebyshev", dict(degree=64, num_probes=48)),
                       ("slq", dict(num_steps=25, num_probes=48))]:
        est = np.asarray(logdet_batched(stack, method=method, seed=0, **kw))
        rel = np.abs(est - ref_ld) / np.abs(ref_ld)
        assert np.median(rel) < 1e-2, (method, rel)


def test_logdet_batched_validation():
    with pytest.raises(ValueError, match="stack"):
        logdet_batched(np.eye(4))
    with pytest.raises(TypeError, match="keywords"):
        logdet_batched(np.stack([np.eye(4)] * 2), method="mc", num_probes=4)


# ---------------------------------------------------------------- operators

def test_dense_and_batched_operator_agree(rng):
    stack = np.stack([make_spd(32, s) for s in range(3)])
    v = rng.standard_normal((3, 32, 5))
    got = BatchedOperator(stack).mm(jnp.asarray(v))
    want = np.stack([stack[b] @ v[b] for b in range(3)])
    np.testing.assert_allclose(np.asarray(got), want, rtol=1e-12)
    one = DenseOperator(stack[1]).mm(jnp.asarray(v[1]))
    np.testing.assert_allclose(np.asarray(one), want[1], rtol=1e-12)


def test_sharded_operator_matches_dense(mesh1, rng):
    a = make_spd(48, 7)
    v = rng.standard_normal((48, 6))
    for use_kernel in (False, True):
        op = ShardedOperator(jnp.asarray(a), mesh1, use_kernel=use_kernel)
        np.testing.assert_allclose(np.asarray(op.mm(jnp.asarray(v))), a @ v,
                                   rtol=1e-12)


def test_sharded_operator_validation(mesh1):
    with pytest.raises(ValueError, match="square"):
        ShardedOperator(jnp.zeros((4, 5)), mesh1)


def test_sharded_estimate_matches_dense_path(mesh1):
    a = make_spd(64, 3)
    op = ShardedOperator(jnp.asarray(a), mesh1)
    got = logdet_chebyshev(op, degree=48, num_probes=32, seed=0)
    want = logdet_chebyshev(a, degree=48, num_probes=32, seed=0)
    np.testing.assert_allclose(float(got.est), float(want.est), rtol=1e-10)


@pytest.mark.slow
def test_sharded_operator_four_devices():
    from tests._subproc import run_with_devices
    out = run_with_devices(
        """
import sys; sys.path.insert(0, %r)
from repro.launch.mesh import make_mesh
from repro.estimators import ShardedOperator, logdet_slq
rng = np.random.default_rng(0)
n = 96
x = rng.standard_normal((n, 2 * n))
a = x @ x.T / (2 * n) + 2.0 * np.eye(n)
mesh = make_mesh((4,), ("rows",))
op = ShardedOperator(jnp.asarray(a), mesh)
v = jnp.asarray(rng.standard_normal((n, 4)))
assert np.allclose(np.asarray(op.mm(v)), a @ np.asarray(v), rtol=1e-10)
est = logdet_slq(op, num_steps=25, num_probes=32, seed=0)
ref = np.linalg.slogdet(a)[1]
assert abs(float(est.est) - ref) / abs(ref) < 2e-2, (float(est.est), ref)
print("OK")
""" % __import__("tests._subproc", fromlist=["SRC"]).SRC,
        n_devices=4,
    )
    assert "OK" in out


# ---------------------------------------------------------------- pieces

def test_hutchinson_trace_plain(rng):
    a = make_spd(96, 1)
    for kind in ("rademacher", "gaussian"):
        probes = make_probes(jax.random.PRNGKey(0), 96, 128, kind=kind)
        res = hutchinson_trace(lambda v: jnp.asarray(a) @ v, probes)
        rel = abs(float(res.est) - np.trace(a)) / np.trace(a)
        assert rel < 0.05, (kind, rel)
        assert float(res.sem) > 0


def test_make_probes_validation():
    with pytest.raises(ValueError, match="probe kind"):
        make_probes(jax.random.PRNGKey(0), 8, 4, kind="sobol")


def test_spectral_bounds_bracket():
    a = make_spd(80, 2)
    w = np.linalg.eigvalsh(a)
    lo, hi = spectral_bounds(DenseOperator(jnp.asarray(a)),
                             jax.random.PRNGKey(0))
    assert float(lo) <= w.min() * 1.001
    assert float(hi) >= w.max() * 0.999
    assert float(lo) > 0


def test_chebyshev_coeffs_recover_log():
    """sum_j c_j T_j(t(x)) must reproduce log(x) on the interval."""
    lmin, lmax = 0.5, 4.0
    c = np.asarray(chebyshev_coeffs_log(lmin, lmax, 48, jnp.float64))
    xs = np.linspace(lmin * 1.01, lmax * 0.99, 50)
    ts = (2 * xs - (lmax + lmin)) / (lmax - lmin)
    acc = np.polynomial.chebyshev.chebval(ts, c)
    np.testing.assert_allclose(acc, np.log(xs), atol=1e-10)


def test_lanczos_tridiagonalizes():
    """For m = n the Gauss quadrature is exact: recover v^T log(A) v."""
    n = 24
    a = make_spd(n, 4)
    v0 = jnp.asarray(np.random.default_rng(0).standard_normal((n, 1)))
    alpha, beta = lanczos(lambda v: jnp.asarray(a) @ v, v0, n)
    t = (np.diag(np.asarray(alpha)[0]) + np.diag(np.asarray(beta)[0], 1)
         + np.diag(np.asarray(beta)[0], -1))
    # T and A share a spectrum when the Krylov space fills the whole space
    np.testing.assert_allclose(np.sort(np.linalg.eigvalsh(t)),
                               np.linalg.eigvalsh(a), rtol=1e-8)


def test_slq_breakdown_safe():
    """Early Krylov breakdown (A = c*I) must not produce NaNs."""
    a = 3.0 * np.eye(32)
    res = logdet_slq(a, num_steps=10, num_probes=8, seed=0)
    assert np.isfinite(float(res.est))
    np.testing.assert_allclose(float(res.est), 32 * np.log(3.0), rtol=1e-10)


def test_chebyshev_degree_validation():
    with pytest.raises(ValueError, match="degree"):
        logdet_chebyshev(np.eye(8), degree=0)


# ---------------------------------------------------------------- kernel

SHAPES_MV = [(8, 8, 1), (64, 64, 8), (100, 130, 16), (256, 512, 64),
             (33, 257, 3)]


@pytest.mark.parametrize("shape", SHAPES_MV)
@pytest.mark.parametrize("dt", [np.float32, np.float64])
def test_matvec_kernel_sweep(shape, dt, rng):
    m, n, k = shape
    a = rng.standard_normal((m, n)).astype(dt)
    x = rng.standard_normal((n, k)).astype(dt)
    tol = dict(rtol=3e-5, atol=3e-5) if dt == np.float32 else \
        dict(rtol=1e-12, atol=1e-12)
    got = matvec_pallas(a, x, interpret=True)
    np.testing.assert_allclose(np.asarray(got), ref.matvec_ref(a, x), **tol)


def test_matvec_kernel_vector_form(rng):
    a = rng.standard_normal((96, 112)).astype(np.float32)
    v = rng.standard_normal((112,)).astype(np.float32)
    got = matvec_pallas(a, v, interpret=True)
    assert got.shape == (96,)
    np.testing.assert_allclose(np.asarray(got), a @ v, rtol=3e-5, atol=3e-5)


@pytest.mark.parametrize("bm,bn", [(8, 128), (32, 64), (256, 512)])
def test_matvec_block_shapes(bm, bn, rng):
    """Result must not depend on tiling."""
    a = rng.standard_normal((300, 520)).astype(np.float32)
    x = rng.standard_normal((520, 7)).astype(np.float32)
    got = matvec_pallas(a, x, bm=bm, bn=bn, interpret=True)
    np.testing.assert_allclose(np.asarray(got), ref.matvec_ref(a, x),
                               rtol=3e-5, atol=3e-5)

"""Structured implicit-operator backends + matrix-free CG.

Every structured ``mm`` is checked against an independently built dense
materialization, the protocol surface (diag / trace_hint / to_dense)
against numpy, CG against ``jnp.linalg.solve``, and the adversarial
shapes the backends must survive: n=1, non-power-of-two sizes, odd and
asymmetric bandwidths.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core import slogdet
from repro.estimators import (
    BatchedOperator,
    DenseOperator,
    KroneckerOperator,
    LinearOperator,
    ShardedOperator,
    StencilOperator,
    ToeplitzOperator,
    as_operator,
    cg_solve,
    estimate_logdet,
    make_probes,
)
from repro.kernels.ref import stencil_mv_ref
from repro.kernels.stencil_mv import stencil_mv_pallas


def make_spd(n, seed, shift=2.0):
    rng = np.random.default_rng(seed)
    x = rng.standard_normal((n, 2 * n))
    return x @ x.T / (2 * n) + shift * np.eye(n)


def toeplitz_dense(c, r=None):
    r = c if r is None else r
    n = len(c)
    i = np.arange(n)
    d = i[:, None] - i[None, :]
    vals = np.concatenate([np.asarray(r)[1:][::-1], np.asarray(c)])
    return vals[d + n - 1]


# ------------------------------------------------------------- Kronecker

@pytest.mark.parametrize("na,nb", [(4, 6), (6, 4), (1, 5), (8, 8)])
def test_kron_mm_matches_dense(na, nb, rng):
    a, b = make_spd(na, 0), make_spd(nb, 1)
    op = KroneckerOperator(jnp.asarray(a), jnp.asarray(b))
    dense = np.kron(a, b)
    v = rng.standard_normal((na * nb, 5))
    np.testing.assert_allclose(np.asarray(op.mm(jnp.asarray(v))), dense @ v,
                               rtol=1e-11, atol=1e-11)
    w = rng.standard_normal((na * nb,))
    np.testing.assert_allclose(np.asarray(op.mv(jnp.asarray(w))), dense @ w,
                               rtol=1e-11, atol=1e-11)


def test_kron_protocol_surface():
    a, b = make_spd(5, 2), make_spd(3, 3)
    op = KroneckerOperator(jnp.asarray(a), jnp.asarray(b))
    dense = np.kron(a, b)
    np.testing.assert_allclose(np.asarray(op.diag()), np.diag(dense),
                               rtol=1e-12)
    np.testing.assert_allclose(float(op.trace_hint()), np.trace(dense),
                               rtol=1e-12)
    np.testing.assert_allclose(np.asarray(op.to_dense()), dense, rtol=1e-12)
    assert op.shape == (15, 15)


def test_kron_validation():
    with pytest.raises(ValueError, match="left factor"):
        KroneckerOperator(jnp.zeros((2, 3)), jnp.eye(2))
    with pytest.raises(ValueError, match="slab"):
        KroneckerOperator(jnp.eye(2), jnp.eye(3)).mm(jnp.zeros((5, 2)))


def test_kron_slogdet_acceptance():
    """slogdet(KroneckerOperator(A, B), method="slq") matches dense
    slogdet(jnp.kron(A, B)) to within 3 sem at n_A = n_B = 64."""
    a, b = make_spd(64, 10), make_spd(64, 11)
    op = KroneckerOperator(jnp.asarray(a), jnp.asarray(b))
    _, ld = slogdet(op, method="slq", num_probes=32, num_steps=25, seed=0)
    res = estimate_logdet(op, method="slq", num_probes=32, num_steps=25,
                          seed=0)
    _, ld_dense = np.linalg.slogdet(np.kron(a, b))
    assert abs(float(ld) - ld_dense) < 3 * float(res.sem), \
        (float(ld), ld_dense, float(res.sem))


# -------------------------------------------------------------- Toeplitz

@pytest.mark.parametrize("n", [1, 2, 37, 64])
def test_toeplitz_symmetric_matches_dense(n, rng):
    c = 0.5 ** np.arange(n)
    c[0] = 2.5
    op = ToeplitzOperator(jnp.asarray(c))
    dense = toeplitz_dense(c)
    v = rng.standard_normal((n, 3))
    np.testing.assert_allclose(np.asarray(op.mm(jnp.asarray(v))), dense @ v,
                               rtol=1e-11, atol=1e-11)
    np.testing.assert_allclose(np.asarray(op.to_dense()), dense, rtol=1e-12)


def test_toeplitz_nonsymmetric_matches_dense(rng):
    n = 23                                     # non-power-of-two
    g = np.random.default_rng(5)
    c = g.standard_normal(n)
    r = g.standard_normal(n)
    r[0] = c[0]
    op = ToeplitzOperator(jnp.asarray(c), jnp.asarray(r))
    dense = toeplitz_dense(c, r)
    v = rng.standard_normal((n, 4))
    np.testing.assert_allclose(np.asarray(op.mm(jnp.asarray(v))), dense @ v,
                               rtol=1e-10, atol=1e-10)


def test_toeplitz_protocol_surface():
    c = np.array([3.0, 1.0, 0.5])
    op = ToeplitzOperator(jnp.asarray(c))
    np.testing.assert_allclose(np.asarray(op.diag()), [3.0, 3.0, 3.0])
    assert float(op.trace_hint()) == pytest.approx(9.0)


def test_toeplitz_validation():
    with pytest.raises(ValueError, match="first column"):
        ToeplitzOperator(jnp.eye(3))
    with pytest.raises(ValueError, match="first row"):
        ToeplitzOperator(jnp.ones((4,)), jnp.ones((3,)))


def test_toeplitz_estimator_logdet():
    n = 100
    c = 0.5 ** np.arange(n)
    c[0] = 2.5
    dense = toeplitz_dense(c)
    _, ld_ref = np.linalg.slogdet(dense)
    res = estimate_logdet(ToeplitzOperator(jnp.asarray(c)),
                          method="chebyshev", degree=64, num_probes=48,
                          seed=0)
    assert abs(float(res.est) - ld_ref) / abs(ld_ref) < 2e-2


# --------------------------------------------------------------- Stencil

@pytest.mark.parametrize("n,offsets", [
    (11, (-1, 0, 1)),
    (37, (-3, -1, 0, 2, 7)),                   # odd, asymmetric bandwidths
    (1, (0,)),
    (64, (-5, 0, 5)),
])
def test_stencil_mm_matches_dense(n, offsets, rng):
    bands = rng.standard_normal((len(offsets), n))
    op = StencilOperator(offsets, jnp.asarray(bands))
    dense = np.zeros((n, n))
    for d, off in enumerate(offsets):
        for i in range(max(0, -off), min(n, n - off)):
            dense[i, i + off] = bands[d, i]
    v = rng.standard_normal((n, 3))
    np.testing.assert_allclose(np.asarray(op.mm(jnp.asarray(v))), dense @ v,
                               rtol=1e-12, atol=1e-12)
    np.testing.assert_allclose(np.asarray(op.to_dense()), dense, atol=1e-15)


def test_stencil_constant_bands_and_diag():
    op = StencilOperator((-1, 0, 1), jnp.asarray([-1.0, 2.5, -1.0]), n=9)
    np.testing.assert_allclose(np.asarray(op.diag()), np.full(9, 2.5))
    assert float(op.trace_hint()) == pytest.approx(9 * 2.5)
    off_diag = StencilOperator((1,), jnp.asarray([1.0]), n=4)
    np.testing.assert_allclose(np.asarray(off_diag.diag()), np.zeros(4))


def test_stencil_validation():
    with pytest.raises(ValueError, match="duplicate"):
        StencilOperator((0, 0), jnp.asarray([1.0, 2.0]), n=4)
    with pytest.raises(ValueError, match="require n"):
        StencilOperator((0,), jnp.asarray([1.0]))
    with pytest.raises(ValueError, match="out of range"):
        StencilOperator((4,), jnp.asarray([1.0]), n=4)
    with pytest.raises(ValueError, match="band rows"):
        StencilOperator((0, 1), jnp.asarray([[1.0] * 4]), n=4)


def test_stencil_estimator_logdet():
    n = 128
    op = StencilOperator((-1, 0, 1), jnp.asarray([-1.0, 2.5, -1.0]), n=n)
    _, ld_ref = np.linalg.slogdet(np.asarray(op.to_dense()))
    res = estimate_logdet(op, method="slq", num_steps=30, num_probes=48,
                          seed=0)
    assert abs(float(res.est) - ld_ref) / abs(ld_ref) < 5e-2


# -------------------------------------------------- stencil Pallas kernel

@pytest.mark.parametrize("n,offsets,bm", [
    (11, (-1, 0, 1), 4),
    (300, (-3, -1, 0, 2, 7), 256),
    (1, (0,), 8),
    (37, (-5, 0, 5), 16),                      # bm does not divide n
])
def test_stencil_kernel_vs_ref(n, offsets, bm, rng):
    bands = jnp.asarray(rng.standard_normal((len(offsets), n)))
    x = jnp.asarray(rng.standard_normal((n, 3)))
    got = stencil_mv_pallas(bands, x, offsets=offsets, bm=bm, interpret=True)
    want = stencil_mv_ref(bands, x, offsets=offsets)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-12, atol=1e-12)


def test_stencil_kernel_vector_form(rng):
    bands = jnp.asarray(rng.standard_normal((3, 50)))
    v = jnp.asarray(rng.standard_normal((50,)))
    got = stencil_mv_pallas(bands, v, offsets=(-1, 0, 1), interpret=True)
    assert got.shape == (50,)
    want = stencil_mv_ref(bands, v, offsets=(-1, 0, 1))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-12)


# -------------------------------------------------------------------- CG

def test_cg_matches_dense_solve(rng):
    a = make_spd(48, 0)
    b = rng.standard_normal((48, 5))
    res = cg_solve(jnp.asarray(a), jnp.asarray(b))
    assert bool(res.converged)
    np.testing.assert_allclose(np.asarray(res.x), np.linalg.solve(a, b),
                               rtol=1e-7, atol=1e-8)


def test_cg_vector_rhs_and_no_precondition(rng):
    a = make_spd(32, 1)
    b = rng.standard_normal((32,))
    for precondition in (True, False):
        res = cg_solve(jnp.asarray(a), jnp.asarray(b),
                       precondition=precondition)
        assert res.x.shape == (32,)
        np.testing.assert_allclose(np.asarray(res.x), np.linalg.solve(a, b),
                                   rtol=1e-7, atol=1e-8)


def test_cg_batched_operator(rng):
    stack = np.stack([make_spd(24, s, shift=1.5 + 0.2 * s) for s in range(4)])
    b = rng.standard_normal((4, 24, 3))
    res = cg_solve(BatchedOperator(jnp.asarray(stack)), jnp.asarray(b))
    want = np.stack([np.linalg.solve(stack[i], b[i]) for i in range(4)])
    assert bool(res.converged)
    assert res.resnorm.shape == (4, 3)
    np.testing.assert_allclose(np.asarray(res.x), want, rtol=1e-7, atol=1e-8)


@pytest.mark.parametrize("structure", ["kron", "toeplitz", "stencil"])
def test_cg_on_structured_operators(structure, rng):
    if structure == "kron":
        a, b_f = make_spd(6, 2), make_spd(7, 3)
        op = KroneckerOperator(jnp.asarray(a), jnp.asarray(b_f))
        dense = np.kron(a, b_f)
    elif structure == "toeplitz":
        c = 0.5 ** np.arange(40)
        c[0] = 2.5
        op = ToeplitzOperator(jnp.asarray(c))
        dense = toeplitz_dense(c)
    else:
        op = StencilOperator((-1, 0, 1), jnp.asarray([-1.0, 2.5, -1.0]),
                             n=40)
        dense = np.asarray(op.to_dense())
    b = rng.standard_normal((op.n, 4))
    res = cg_solve(op, jnp.asarray(b))
    assert bool(res.converged)
    np.testing.assert_allclose(np.asarray(res.x), np.linalg.solve(dense, b),
                               rtol=1e-6, atol=1e-7)


def test_cg_adversarial_shapes(rng):
    # n = 1: one scalar division must solve it in a step
    res = cg_solve(jnp.asarray([[4.0]]), jnp.asarray([8.0]))
    np.testing.assert_allclose(np.asarray(res.x), [2.0], rtol=1e-12)
    # maxiter too small: must report non-convergence, not hang or lie
    a = make_spd(64, 4, shift=0.05)            # stiffer spectrum
    b = rng.standard_normal((64, 2))
    res = cg_solve(jnp.asarray(a), jnp.asarray(b), maxiter=1, tol=1e-14)
    assert not bool(res.converged)
    assert int(res.iters) == 1


def test_cg_x0_and_validation(rng):
    a = make_spd(16, 5)
    b = rng.standard_normal((16, 2))
    x_true = np.linalg.solve(a, b)
    res = cg_solve(jnp.asarray(a), jnp.asarray(b),
                   x0=jnp.asarray(x_true * 0.99))
    assert int(res.iters) < 16                 # warm start converges faster
    np.testing.assert_allclose(np.asarray(res.x), x_true, rtol=1e-7,
                               atol=1e-8)
    with pytest.raises(ValueError, match="rhs rows"):
        cg_solve(jnp.asarray(a), jnp.asarray(b[:7]))


# ------------------------------------------------------ protocol plumbing

def test_as_operator_passthrough_and_duck_typing():
    a = make_spd(8, 0)
    op = DenseOperator(jnp.asarray(a))
    assert as_operator(op) is op
    assert isinstance(as_operator(KroneckerOperator(jnp.eye(2), jnp.eye(2))),
                      KroneckerOperator)

    class Scaled:                              # duck-typed, no subclassing
        shape = (8, 8)
        dtype = jnp.float64

        def mm(self, v):
            return 2.0 * v

    duck = Scaled()
    assert as_operator(duck) is duck


def test_base_to_dense_and_trace_hint_defaults():
    class Shift(LinearOperator):
        def __init__(self, n):
            self.shape = (n, n)
            self.dtype = jnp.float64

        def mm(self, v):
            return 3.0 * v

    op = Shift(6)
    np.testing.assert_allclose(np.asarray(op.to_dense()), 3.0 * np.eye(6))
    assert op.diag() is None
    assert op.trace_hint() is None             # no diag -> no free trace


def test_dense_batched_protocol_surface(rng):
    a = make_spd(12, 6)
    op = DenseOperator(jnp.asarray(a))
    np.testing.assert_allclose(np.asarray(op.diag()), np.diag(a))
    np.testing.assert_allclose(float(op.trace_hint()), np.trace(a))
    stack = np.stack([make_spd(10, s) for s in range(3)])
    bop = BatchedOperator(jnp.asarray(stack))
    np.testing.assert_allclose(np.asarray(bop.diag()),
                               np.stack([np.diag(m) for m in stack]))
    np.testing.assert_allclose(np.asarray(bop.trace_hint()),
                               np.stack([np.trace(m) for m in stack]))


def test_logdet_batched_accepts_batched_operator():
    from repro.core import logdet_batched
    stack = np.stack([make_spd(48, s, shift=1.5 + 0.1 * s)
                      for s in range(4)])
    ref = np.array([np.linalg.slogdet(m)[1] for m in stack])
    op = BatchedOperator(jnp.asarray(stack))
    est = np.asarray(logdet_batched(op, method="slq", num_steps=25,
                                    num_probes=48, seed=0))
    assert est.shape == (4,)
    assert np.median(np.abs(est - ref) / np.abs(ref)) < 1e-2
    with pytest.raises(TypeError, match="materialized"):
        logdet_batched(op, method="mc")
    with pytest.raises(ValueError, match="batched operator"):
        logdet_batched(DenseOperator(jnp.asarray(stack[0])), method="slq")


def test_slogdet_operator_rejects_exact_and_mesh(mesh1):
    op = KroneckerOperator(jnp.eye(4), jnp.eye(4))
    with pytest.raises(TypeError, match="materialized"):
        slogdet(op, method="mc")
    with pytest.raises(TypeError, match="own distribution"):
        slogdet(op, method="slq", mesh=mesh1)


# ------------------------------------------------------------ dtype hygiene

def test_make_probes_threads_dtype():
    """On float64-enabled hosts an f32 operator must get f32 probes — the
    default must not silently upcast the matvec slab."""
    v64 = make_probes(jax.random.PRNGKey(0), 16, 4)
    assert v64.dtype == jnp.result_type(float)  # canonical default (x64 on)
    v32 = make_probes(jax.random.PRNGKey(0), 16, 4, dtype=jnp.float32)
    assert v32.dtype == jnp.float32
    with pytest.raises(ValueError, match="floating"):
        make_probes(jax.random.PRNGKey(0), 16, 4, dtype=jnp.int32)


@pytest.mark.parametrize("method,kw", [
    ("chebyshev", dict(degree=32, num_probes=8)),
    ("slq", dict(num_steps=15, num_probes=8)),
])
def test_estimators_preserve_f32_under_x64(method, kw):
    a = jnp.asarray(make_spd(48, 0), jnp.float32)
    res = estimate_logdet(a, method=method, seed=0, **kw)
    assert res.est.dtype == jnp.float32
    assert res.samples.dtype == jnp.float32


# --------------------------------------------------------------- sharded

def test_sharded_operator_all_devices(rng):
    """Runs on however many devices the process sees — 1 on dev boxes, 8 in
    the CI multi-device job (XLA_FLAGS=--xla_force_host_platform_device_count=8)."""
    from repro.launch.mesh import make_mesh
    ndev = jax.device_count()
    n = 96 if 96 % ndev == 0 else 12 * ndev
    a = make_spd(n, 9)
    mesh = make_mesh((ndev,), ("rows",))
    op = ShardedOperator(jnp.asarray(a), mesh)
    v = rng.standard_normal((n, 6))
    np.testing.assert_allclose(np.asarray(op.mm(jnp.asarray(v))), a @ v,
                               rtol=1e-10)
    np.testing.assert_allclose(np.asarray(op.diag()), np.diag(a), rtol=1e-12)
    est = estimate_logdet(op, method="slq", num_steps=25, num_probes=32,
                          seed=0)
    ld_ref = np.linalg.slogdet(a)[1]
    assert abs(float(est.est) - ld_ref) / abs(ld_ref) < 2e-2


def test_cg_on_sharded_operator(mesh1, rng):
    a = make_spd(32, 8)
    op = ShardedOperator(jnp.asarray(a), mesh1)
    b = rng.standard_normal((32, 2))
    res = cg_solve(op, jnp.asarray(b))
    assert bool(res.converged)
    np.testing.assert_allclose(np.asarray(res.x), np.linalg.solve(a, b),
                               rtol=1e-7, atol=1e-8)

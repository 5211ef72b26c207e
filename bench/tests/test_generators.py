import jax
import numpy as np
import pytest

import registry
from run import seed_key

FAMILIES = [("gaussian", "paper_dense.n1000"),
            ("rbf_kernel", "gp_rbf.n8192.grad")]


def make(family, cell, seed, n=48, pool=3):
    cell = registry.load_cell(cell)
    traffic = {**cell.traffic, "n": n, "pool": pool}
    out = registry.generator(family).make(seed_key(seed), cell.config, traffic)
    return [np.asarray(a) for a in out]


@pytest.mark.parametrize("family,cell", FAMILIES)
def test_same_seed_same_pool(family, cell):
    a, b = make(family, cell, 7), make(family, cell, 7)
    assert all(np.array_equal(x, y) for x, y in zip(a, b))


@pytest.mark.parametrize("family,cell", FAMILIES)
@pytest.mark.parametrize("other", [8, 7 + 2 ** 32, -7])
def test_other_seed_other_pool(family, cell, other):
    a, b = make(family, cell, 7), make(family, cell, other)
    assert not any(np.array_equal(x, y) for x, y in zip(a, b))


@pytest.mark.parametrize("family,cell", FAMILIES)
def test_pool_members_distinct_float32(family, cell):
    pool = make(family, cell, 2 ** 40 + 3)
    assert len(pool) == 3
    assert all(x.shape == (48, 48) and x.dtype == np.float32 for x in pool)
    assert not np.array_equal(pool[0], pool[1])
    assert not np.array_equal(pool[1], pool[2])


def test_rbf_kernel_is_symmetric_positive_definite():
    for k in make("rbf_kernel", "gp_rbf.n8192.grad", 3):
        assert np.array_equal(k, k.T)
        w = np.linalg.eigvalsh(k.astype(np.float64))
        assert w.min() > 0.009          # noise 0.01 bounds it below
        assert np.allclose(np.diag(k), 1.01)


def test_seed_key_takes_64_bits():
    keys = {tuple(np.asarray(jax.random.key_data(seed_key(s))))
            for s in (0, 1, 2 ** 32, 2 ** 32 + 1, 2 ** 63, -1)}
    assert len(keys) == 6

import math

import ml_dtypes
import numpy as np
import pytest

import compare
import control
from conftest import tiny
from reference import factor64

LIMITS = {"sign_mismatches": 0, "logabsdet_err_cond": 1.0,
          "grad_rel_err": 1e-2}


def pool(n=24, m=2):
    rng = np.random.default_rng(0)
    return [rng.standard_normal((n, n)).astype(np.float32) for _ in range(m)]


def test_reference_agrees_with_numpy():
    for a in pool(40, 3) + [-np.eye(5)]:
        sign, ld, cond = factor64(a)
        want = np.linalg.slogdet(np.asarray(a, np.float64))
        assert sign == want[0] and ld == pytest.approx(want[1], rel=1e-12)
        assert cond >= 1 and cond == pytest.approx(
            np.linalg.cond(np.asarray(a, np.float64), 1), rel=0.5)


def test_values_pass_the_reference_and_count_each_fault():
    p = pool()
    ref = [factor64(a) for a in p]
    good = [(i, i % 2, (np.float32(ref[i % 2][0]), np.float32(ref[i % 2][1])))
            for i in range(4)]
    checks, failed = compare.values(p, good, LIMITS, "float32")
    assert checks["sign_mismatches"] == (0, 0)
    assert checks["dtype_mismatches"] == (0, 0)
    assert checks["logabsdet_err_cond"][0] < 1 and not failed
    bad = list(good)
    bad[1] = (1, 1, (-good[1][2][0], good[1][2][1]))
    bad[2] = (2, 0, (good[2][2][0], good[2][2][1] * np.float32(1.001)))
    bad[3] = (3, 1, (good[3][2][0], np.float32(math.nan)))
    checks, failed = compare.values(p, bad, LIMITS, "float32")
    assert checks["sign_mismatches"][0] == 1
    assert checks["logabsdet_err_cond"][0] == math.inf
    assert failed == {1, 2, 3}


def test_an_answer_in_a_lower_dtype_fails_and_keeps_the_stated_unit():
    """The unit is the configuration's roundoff: a bfloat16 answer is not
    measured against bfloat16's own, coarser one."""
    p = pool()
    ref = [factor64(a) for a in p]
    low = [(i, i % 2, (np.float32(ref[i % 2][0]),
                       np.asarray(ref[i % 2][1], ml_dtypes.bfloat16)))
           for i in range(2)]
    checks, failed = compare.values(p, low, LIMITS, "float32")
    assert checks["dtype_mismatches"] == (2, 0) and failed == {0, 1}
    u = np.finfo(np.float32).eps / 2
    worst = max(abs(float(out[1]) - ref[j][1]) / (ref[j][2] * u)
                for _, j, out in low)
    assert checks["logabsdet_err_cond"][0] == pytest.approx(worst)


def test_grads_pass_the_inverse_and_fail_a_perturbed_one():
    p = pool()
    inv = [np.linalg.inv(a.astype(np.float64)).T for a in p]
    checks, failed = compare.grads(p, [(0, 0, (1, 0, inv[0])),
                                       (5, 1, (1, 0, inv[1] * 1.1)),
                                       (6, 0, (1, 0, inv[0].astype(
                                           ml_dtypes.bfloat16)))],
                                   LIMITS, "float64")
    assert failed == {5, 6}
    assert checks["grad_dtype_mismatches"] == (1, 0)
    assert checks["grad_rel_err"][0] == pytest.approx(0.1)


@pytest.mark.parametrize("name,n", [("paper_dense.n8000", 64),
                                    ("paper_dense.n1000", 96),
                                    ("gp_rbf.n8192.grad", 64)])
def test_program_passes_and_control_fails(name, n):
    """The cell's own comparison and limits, at a size the CPU holds: the
    float32 program passes every number, the control (one precision
    down) fails at least one, on three seeds."""
    cell = tiny(name, n)
    got = list(control.readings(cell, [11, 12, 2 ** 33 + 13]))
    assert len(got) == 3
    for r in got:
        assert all(v <= lim for v, lim in r["program"].values()), r
        assert any(v > lim for v, lim in r["control"].values()), r

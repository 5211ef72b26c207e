"""A run with the timed path broken underneath must come out not correct.

Each test drives the rest of a run (everything but the look for a chip)
on the CPU at a tiny size, with ``LogdetPlan`` patched where the answer
is produced.
"""
import time

import jax.numpy as jnp
import pytest
from repro.core.plan import LogdetPlan

import run
from conftest import tiny

CELLS = [("paper_dense.n1000", 64), ("paper_dense.n8000", 96),
         ("gp_rbf.n8192.grad", 64)]


def run_tiny(cell, seed=5):
    return run.run(cell, seed, 0.3, False, t0=time.perf_counter(),
                   require_tpu=False)


def test_sound_runs_are_correct_and_report_the_cells_metrics():
    for name, n in CELLS:
        cell = tiny(name, n)
        res = run_tiny(cell, seed=2 ** 35 + 1)
        assert res["correct"] and res["failed"] == 0, res
        assert res["attempted"] >= 2
        assert set(res["metrics"]) == {m["name"] for m in cell.end_to_end}
        assert all(v["value"] > 0 for v in res["metrics"].values())
        assert list(res)[-1] == "checks"
        assert res["device"]["count"] >= 1


def _patch_result(monkeypatch, alter):
    """Alter every answer where the plan produces it, value and gradient."""
    call, vag = LogdetPlan.__call__, LogdetPlan.value_and_grad

    def patched_call(self, a=None, **kw):
        return alter(call(self, a, **kw))

    def patched_vag(self, a=None, **kw):
        res, g = vag(self, a, **kw)
        return alter(res), g

    monkeypatch.setattr(LogdetPlan, "__call__", patched_call)
    monkeypatch.setattr(LogdetPlan, "value_and_grad", patched_vag)


FAULTS = {
    "logabsdet_altered": lambda r: r.__class__(
        sign=r.sign, logabsdet=r.logabsdet * 1.0001, sem=r.sem,
        method_used=r.method_used, diagnostics=r.diagnostics),
    "sign_flipped": lambda r: r.__class__(
        sign=-r.sign, logabsdet=r.logabsdet, sem=r.sem,
        method_used=r.method_used, diagnostics=r.diagnostics),
    # a lower precision that lowers the answer's dtype with it
    "logabsdet_as_bfloat16": lambda r: r.__class__(
        sign=r.sign, logabsdet=r.logabsdet.astype(jnp.bfloat16), sem=r.sem,
        method_used=r.method_used, diagnostics=r.diagnostics),
}


@pytest.mark.parametrize("name,n", CELLS)
@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_an_altered_answer_is_not_correct(monkeypatch, name, n, fault):
    _patch_result(monkeypatch, FAULTS[fault])
    res = run_tiny(tiny(name, n))
    assert res["correct"] is False and res["failed"] == res["attempted"]


@pytest.mark.parametrize("name,n", CELLS)
def test_a_stale_answer_is_not_correct(monkeypatch, name, n):
    """The state left unchanged: every call returns the first answer."""
    first = []

    def stale(r):
        if not first:
            first.append(r)
        return first[0]

    _patch_result(monkeypatch, stale)
    res = run_tiny(tiny(name, n))
    assert res["correct"] is False and res["failed"] > 0


GRAD_FAULTS = {
    "altered": ("grad_rel_err", lambda g: g.at[0, 0].add(jnp.abs(g).max() * 3)),
    "as_bfloat16": ("grad_dtype_mismatches",
                    lambda g: g.astype(jnp.bfloat16)),
}


@pytest.mark.parametrize("fault", sorted(GRAD_FAULTS))
def test_an_altered_gradient_is_not_correct(monkeypatch, fault):
    check, alter = GRAD_FAULTS[fault]
    vag = LogdetPlan.value_and_grad

    def patched(self, a=None, **kw):
        res, g = vag(self, a, **kw)
        return res, alter(g)

    monkeypatch.setattr(LogdetPlan, "value_and_grad", patched)
    res = run_tiny(tiny("gp_rbf.n8192.grad", 64))
    assert res["correct"] is False
    assert res["checks"][check]["value"] > res["checks"][check]["limit"]

"""The trace reduction on traces recorded on a TPU v5e by ``run.py
--trace 1`` (N=256, two calls each, gzipped):

- ``exact_n256``: ``paper_dense`` at N=256 through ``LogdetPlan.__call__``;
- ``grad_n256``: ``gp_rbf`` at N=256 through ``value_and_grad``.
"""
import pytest

import registry

DATA = registry.ROOT / "bench" / "tests" / "data"
trace_mod = registry.load_module(registry.ROOT / "bench" / "trace.py")


@pytest.fixture(scope="module", params=["exact_n256", "grad_n256"])
def reduced(request):
    return request.param, trace_mod.reduce_file(
        DATA / f"{request.param}.xplane.pb.gz")


def test_busy_is_the_union_of_ops_and_within_the_window(reduced):
    _, r = reduced
    assert 0 < r.busy_s <= r.window_s
    # ops nest (a while op holds its body), never overlap otherwise: the
    # own times add up to the union
    assert r.op_seconds() == pytest.approx(r.busy_s, rel=1e-6)
    assert all(r.window[0] <= o.start <= o.end <= r.window[1] for o in r.ops)
    idle = r.idle_pct()
    assert 0 < idle < 100


def test_harness_spans_name_the_idle_gaps(reduced):
    _, r = reduced
    names = {n for n, _, _ in r.spans}
    assert {"bench.window", "bench.call"} <= names
    assert sum(1 for n, _, _ in r.spans if n == "bench.call") == 2
    gaps = r.gaps()
    assert gaps and all(g[0] in names for g in gaps)
    assert sum(g[1] for g in gaps) == pytest.approx(r.window_s - r.busy_s,
                                                     rel=1e-6)


def test_ops_carry_their_scope_program_and_category(reduced):
    name, r = reduced
    modules = {o.module for o in r.ops}
    assert "jit_fwd" in modules
    if name == "grad_n256":
        assert "jit_inv" in modules      # the backward's inverse
    else:
        assert modules == {"jit_fwd"}
    scoped = [o for o in r.ops if "engine.panel_apply" in o.scope]
    assert scoped and all(o.scope.startswith("jit(fwd)/") for o in scoped)
    assert any(o.category == "custom-call" and "kernel.panel_update" in
               o.scope for o in r.ops)


def test_breakdown_is_short_and_sorted(reduced):
    _, r = reduced
    b = r.breakdown()
    for key in ("device_ops", "idle_gaps"):
        assert 0 < len(b[key]) <= 10
        secs = [v for _, v in b[key]]
        assert secs == sorted(secs, reverse=True)
    assert sum(v for _, v in b["device_ops"]) <= r.busy_s * (1 + 1e-9)


def _ctx(r, n=256, calls=2):
    return trace_mod.Context(
        trace=r, calls=calls, n=n, setup_compile_s=1.5,
        peaks=registry.peaks("TPU v5 lite"), work=registry.work)


def test_each_cells_metrics_read_a_number(reduced):
    name, r = reduced
    cell = "gp_rbf.n8192.grad" if name == "grad_n256" else \
        "paper_dense.n1000"
    for m in registry.load_cell(cell).per_layer:
        v = registry.metric_reader(m["name"]).read(_ctx(r))
        assert v is not None and v > 0, m["name"]
        if m["unit"] == "%":
            assert v <= 100, m["name"]


def test_roofline_share_is_the_least_time_over_device_time_per_call():
    r = trace_mod.reduce_file(DATA / "exact_n256.xplane.pb.gz")
    read = registry.metric_reader("exact_logdet_roofline").read
    # at N=256 one read of the matrix (819 GB/s) bounds it, not the
    # operations (197 TFLOP/s)
    least = max(2 / 3 * 256 ** 3 / 197e12, 4 * 256 ** 2 / 819e9)
    assert least == 4 * 256 ** 2 / 819e9
    assert read(_ctx(r)) == pytest.approx(100 * least / (r.busy_s / 2))
    assert read(_ctx(r, calls=4)) == pytest.approx(2 * read(_ctx(r)))


def test_a_metric_with_nothing_to_read_returns_none():
    r = trace_mod.reduce_file(DATA / "exact_n256.xplane.pb.gz")
    assert registry.metric_reader("grad.backward_share").read(
        _ctx(trace_mod.Reduced(window=r.window, ops=[], spans=r.spans))) \
        is None
    assert registry.metric_reader("device_idle.logdet").read(
        _ctx(trace_mod.Reduced(window=r.window, ops=[], spans=r.spans))) \
        is None

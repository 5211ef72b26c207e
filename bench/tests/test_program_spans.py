"""The program's host spans in the recorded traces, and the readers of
``engine.panel_swap_share`` and ``engine.panel_swap_share.small``:

- on hand-made reductions, where the answer is known;
- on traces recorded on a TPU v5e by ``record_traces.py --suffix _spans``
  (N=256, two calls each): ``exact_n256_spans`` (``paper_dense`` through
  ``LogdetPlan.__call__``) and ``grad_n256_spans`` (``gp_rbf`` through
  ``value_and_grad``), of a program that has the panel loops' scopes and
  writes the ``plan.*`` spans;
- on the older ``exact_n256`` and ``grad_n256``, of a program that has
  neither: the accepted readers read what they read there before, and
  the new ones read nothing.
"""
import gzip

import pytest

import registry

DATA = registry.ROOT / "bench" / "tests" / "data"
trace_mod = registry.load_module(registry.ROOT / "bench" / "trace.py")
Op, Reduced = trace_mod.Op, trace_mod.Reduced
NEW = ("engine.panel_swap_share", "engine.panel_swap_share.small")


def _ctx(r, n=256, calls=2):
    return trace_mod.Context(
        trace=r, calls=calls, n=n, setup_compile_s=1.5,
        peaks=registry.peaks("TPU v5 lite"), work=registry.work)


def _read(name, r):
    return registry.metric_reader(name).read(_ctx(r))


def plan_spans(path):
    """The ``plan.*`` events of the trace's host plane, as (name, start,
    end) in ns: the spans ``obs.span`` writes."""
    from jax.profiler import ProfileData
    with gzip.open(path, "rb") as f:
        data = ProfileData.from_serialized_xspace(f.read())
    return [(e.name, e.start_ns, e.start_ns + e.duration_ns)
            for plane in data.planes if plane.name == trace_mod.HOST_PLANE
            for line in plane.lines for e in line.events
            if e.name.startswith("plan.")]


@pytest.fixture(scope="module", params=["exact_n256_spans",
                                        "grad_n256_spans"])
def spans(request):
    path = DATA / f"{request.param}.xplane.pb.gz"
    return request.param, trace_mod.reduce_file(path), plan_spans(path)


# ------------------------------------------------------------ by hand

def _op(start, end, own, scope="", category="data formatting"):
    return Op(start=start, end=end, own=own, name="%op", module="jit_fwd",
              scope=scope, category=category)


SWAP_BODY = ("jit(fwd)/jit(_staged_stage_panel)/while/body/closed_call/"
             "engine.panel_swap/while/body/closed_call/")
PANEL_BODY = "jit(fwd)/jit(_staged_stage_panel)/while/body/closed_call/"


def _panel_loop():
    """A panel loop (0-200 ns) around a swap loop (10-110 ns).  The swap
    body: a scoped update and an unscoped copy.  The panel body: the
    scoped copy into the swap's layout, an unscoped copy and the GEMM."""
    return [
        _op(0, 200, 40, category="while"),
        _op(10, 110, 50, category="while"),
        _op(20, 30, 10, SWAP_BODY + "dynamic_update_slice:",
            "dynamic-update-slice"),
        _op(40, 80, 40),
        _op(120, 140, 20, PANEL_BODY + "engine.panel_swap/while:"),
        _op(140, 160, 20),
        _op(160, 180, 20, PANEL_BODY + "engine.panel_apply/dot_general:",
            "convolution"),
    ]


def test_swap_share_counts_the_swap_loop_and_its_unscoped_body():
    r = Reduced(window=(0, 200), ops=_panel_loop(), spans=[],
                busy=[(0, 200)])
    # the inner while 50, its body 10 + 40, the scoped copy 20; not the
    # panel loop's unscoped copy, the GEMM or the panel while
    assert _read("engine.panel_swap_share", r) == pytest.approx(60.0)
    assert _read("engine.panel_swap_share.small", r) == pytest.approx(60.0)


def test_swap_share_reads_nothing_without_the_scope_or_on_two_devices():
    ops = [o for o in _panel_loop() if "panel_swap" not in o.scope]
    r = Reduced(window=(0, 200), ops=ops, spans=[], busy=[(0, 200)])
    assert _read("engine.panel_swap_share", r) is None
    r = Reduced(window=(0, 200), ops=_panel_loop(), spans=[],
                busy=[(0, 200), (0, 200)], devices=2)
    assert _read("engine.panel_swap_share", r) is None


# ------------------------------------------------- recorded, new program

def test_the_plans_spans_sit_inside_the_calls(spans):
    name, r, program = spans
    names = {n for n, _, _ in program}
    entry = "plan.backward" if name.startswith("grad") else "plan.execute"
    assert {entry, "plan.dispatch", "plan.wait"} <= names
    assert not any(n.startswith("bench.") for n in names)
    calls = [(a, b) for n, a, b in r.spans if n == "bench.call"]
    inside = [(n, a, b) for n, a, b in program
              if any(c0 <= a <= b <= c1 for c0, c1 in calls)]
    assert sum(1 for n, _, _ in inside if n == entry) == 2
    assert sum(1 for n, _, _ in inside if n == "plan.wait") == 2


def test_new_readers_read_a_number(spans):
    _, r, _ = spans
    for name in NEW:
        v = _read(name, r)
        assert v is not None and 0 < v <= 100, name


def test_the_swap_loop_is_found_in_the_trace(spans):
    _, r, _ = spans
    scoped = [o for o in r.ops if "engine.panel_swap" in o.scope.split("/")]
    assert scoped
    for s in ("engine.panel_substitute", "engine.panel_park",
              "engine.stage_shrink"):
        assert any(s in o.scope.split("/") for o in r.ops), s
    # the share counts more than the ops that carry the scope themselves
    own = 100.0 * sum(o.own for o in scoped) / sum(o.own for o in r.ops)
    assert _read("engine.panel_swap_share", r) > own


# ------------------------------------------------- recorded, old program

# what the accepted readers read on these traces at the parent commit
OLD = {
    "exact_n256": {"device_idle.small_logdet": 44.12758167105788,
                   "exact_logdet_roofline.small": 0.019616129106240394,
                   "engine.update_share.small": 0.5861647469508188},
    "grad_n256": {"device_idle.logdet_grad": 37.82193864435233,
                  "grad.backward_share": 8.707219083911422,
                  "engine.update_share": 0.5345378185087284},
}


@pytest.mark.parametrize("trace", sorted(OLD))
def test_old_traces_read_as_before_and_the_new_readers_read_nothing(trace):
    path = DATA / f"{trace}.xplane.pb.gz"
    r = trace_mod.reduce_file(path)
    assert plan_spans(path) == []
    for name, value in OLD[trace].items():
        assert _read(name, r) == value, name
    for name in NEW:
        assert _read(name, r) is None, name

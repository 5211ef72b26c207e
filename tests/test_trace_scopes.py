"""What a profiler trace can name in the exact forward.

- The panel stage's loops carry named scopes (``engine.panel_swap``,
  ``engine.panel_substitute``, ``engine.panel_park``,
  ``engine.stage_shrink``) in the compiled program's op metadata, and the
  scopes change nothing else in that program.
- ``obs.span`` writes the plan's host spans (``plan.execute``,
  ``plan.backward``, ``plan.dispatch``, ``plan.wait``, ``plan.compile``)
  into a ``jax.profiler`` trace with obs off, and still records nothing in
  obs's own buffer.

Matrix sizes here (96, 67, 71) are unique to this file, and the compile
tests clear JAX's caches around them, so no jit cache serves a trace made
with other scopes.
"""
import contextlib
import glob
import os
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import repro
from repro import obs
from repro.core.plan import clear_plan_cache

NEW_SCOPES = ("engine.panel_swap", "engine.panel_substitute",
              "engine.panel_park", "engine.stage_shrink")


@pytest.fixture(autouse=True)
def obs_off():
    obs.reset()
    obs.configure("off")
    yield
    obs.reset()
    obs.configure("off")


def _staged_panel_hlo(n=96, k=8) -> str:
    """Compiled HLO of a freshly traced staged x panel forward."""
    clear_plan_cache()
    jax.clear_caches()
    a = jnp.asarray(np.random.default_rng(0).standard_normal((n, n)),
                    jnp.float32)
    p = repro.plan(a, method="exact", schedule="staged", update="panel",
                   k=k)
    return p._fwd.lower(a).compile().as_text()


def _without_metadata(text: str) -> str:
    """The program with its op metadata and source-location tables cut."""
    body = text[text.index("HloModule"):]
    body = re.sub(r"(FileNames|FunctionNames|FileLocations|StackFrames)\n"
                  r"(.*\n)*?\n", "", body)
    return re.sub(r",? ?metadata=\{[^}]*\}", "", body)


def _ops(text: str):
    return re.findall(r"^\s*(?:ROOT )?%\S+ = .*?\b([a-z][\w-]*)\(",
                      _without_metadata(text), flags=re.M)


@pytest.fixture(scope="module")
def staged_panel_hlo():
    yield _staged_panel_hlo()
    clear_plan_cache()
    jax.clear_caches()


@pytest.mark.parametrize("scope", NEW_SCOPES)
def test_panel_stage_scope_reaches_the_op_metadata(staged_panel_hlo, scope):
    names = re.findall(r'op_name="([^"]*)"', staged_panel_hlo)
    assert any(scope in name.split("/") for name in names), scope


def test_panel_stage_scopes_change_only_metadata(staged_panel_hlo,
                                                 monkeypatch):
    real = obs.stage
    monkeypatch.setattr(
        obs, "stage", lambda name, **kw: contextlib.nullcontext()
        if name in NEW_SCOPES else real(name, **kw))
    try:
        bare = _staged_panel_hlo()
    finally:
        monkeypatch.undo()
        clear_plan_cache()
        jax.clear_caches()
    assert not any(s in bare for s in NEW_SCOPES)
    ops, bare_ops = _ops(staged_panel_hlo), _ops(bare)
    assert len(ops) == len(bare_ops) > 100
    assert ops.count("custom-call") == bare_ops.count("custom-call")
    assert _without_metadata(staged_panel_hlo) == _without_metadata(bare)


def _spd(n, seed=0):
    rng = np.random.default_rng(seed)
    m = rng.standard_normal((n, n))
    return jnp.asarray(m @ m.T + n * np.eye(n))


def _host_span_names(trace_dir) -> set:
    from jax.profiler import ProfileData
    found = glob.glob(os.path.join(str(trace_dir), "**", "*.xplane.pb"),
                      recursive=True)
    assert len(found) == 1, found
    data = ProfileData.from_file(found[0])
    return {e.name for plane in data.planes if plane.name == "/host:CPU"
            for line in plane.lines for e in line.events}


def test_plan_spans_reach_the_profiler_trace_with_obs_off(tmp_path):
    assert obs.mode() == "off"
    clear_plan_cache()
    a = _spd(67)
    p = repro.plan(a, method="exact")
    p(a)                                # the forward compiles outside
    with jax.profiler.trace(str(tmp_path)):
        p(a)
        p.value_and_grad(a)             # its first dispatch: plan.compile
    names = _host_span_names(tmp_path)
    assert {"plan.execute", "plan.backward", "plan.dispatch", "plan.wait",
            "plan.compile"} <= names
    assert obs.events() == []


def test_plan_records_nothing_in_the_buffer_with_obs_off():
    clear_plan_cache()
    a = _spd(71)
    p = repro.plan(a, method="exact")
    p(a)
    p(a)
    p.value_and_grad(a)
    assert obs.events() == []


def test_plan_compile_nests_in_the_first_dispatch_only():
    obs.configure("trace")
    clear_plan_cache()
    a = _spd(71)
    p = repro.plan(a, method="exact")
    p(a)
    p(a)
    evs = obs.events()
    names = [e["name"] for e in evs]
    assert names.count("plan.execute") == 2
    assert names.count("plan.dispatch") == 2
    assert names.count("plan.wait") == 2
    assert names.count("plan.compile") == 1
    assert names.count("plan.trace") == 1
    compile_ = next(e for e in evs if e["name"] == "plan.compile")
    first = next(e for e in evs if e["name"] == "plan.dispatch")
    assert first["ts"] <= compile_["ts"]
    assert compile_["ts"] + compile_["dur"] <= \
        first["ts"] + first["dur"] + 1e-3
    assert compile_["depth"] == first["depth"] + 1

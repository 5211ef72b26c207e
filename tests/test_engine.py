"""The unified condensation engine: every (schedule x update x backend)
route must agree with ``jnp.linalg.slogdet`` on sign AND logabsdet —
including permuted, negative-determinant and near-singular inputs — and
the legacy route strings must be pure aliases of engine instantiations.

This file runs under the CI deprecation gate (-W error::DeprecationWarning)
so nothing here may touch a legacy spelling unguarded.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

import repro
from repro.core.engine import (
    EngineConfig, LEGACY_ROUTES, build_mesh, build_serial, engine_slogdet,
)

SCHEDULES_SERIAL = ("serial", "staged")
UPDATES = ("rank1", "panel")
BACKENDS = ("xla", "pallas")


def _cases():
    rng = np.random.default_rng(42)
    cases = {}
    cases["random"] = rng.standard_normal((48, 48))
    # odd size, big scale: exercises remainder steps + log-domain math
    cases["scaled_odd"] = rng.standard_normal((37, 37)) * 1e6
    # permutation matrix: det = +-1, sign tracking must be exact
    cases["permutation"] = np.eye(41)[rng.permutation(41)]
    # negative determinant: SPD with one negated row
    spd = rng.standard_normal((32, 64))
    spd = spd @ spd.T / 64 + 2.0 * np.eye(32)
    neg = spd.copy()
    neg[3] = -neg[3]
    cases["negative_det"] = neg
    # near-singular: rank-4 + tiny ridge (logabsdet very negative but finite)
    b = rng.standard_normal((24, 4))
    cases["near_singular"] = b @ b.T + 1e-10 * np.eye(24)
    return cases


CASES = _cases()


# near_singular sits at condition ~1e10: condensation and LAPACK may
# legitimately differ in the last ~6 bits of a very negative logabsdet
_CASE_RTOL = {"near_singular": 1e-5}


def assert_matches_ref(got, a, rtol=1e-9, case=None):
    s, ld = float(got[0]), float(got[1])
    s_ref, ld_ref = np.linalg.slogdet(np.asarray(a))
    assert s == pytest.approx(s_ref), (s, s_ref)
    rtol = max(rtol, _CASE_RTOL.get(case, 0.0))
    np.testing.assert_allclose(ld, ld_ref, rtol=rtol, atol=1e-8)


@pytest.mark.parametrize("case", sorted(CASES))
@pytest.mark.parametrize("update", UPDATES)
@pytest.mark.parametrize("schedule", SCHEDULES_SERIAL)
def test_serial_routes_match_slogdet(schedule, update, case):
    cfg = EngineConfig(schedule=schedule, update=update, panel_k=8,
                       min_size=16, backend="xla")
    a = jnp.asarray(CASES[case])
    if update == "panel":
        # panel routes factor full K-panels; plans pad — mirror that here
        from repro.core import pad_to_multiple
        a = pad_to_multiple(a, 8)
    assert_matches_ref(engine_slogdet(a, cfg), a, rtol=1e-8, case=case)


@pytest.mark.parametrize("update", UPDATES)
@pytest.mark.parametrize("backend", BACKENDS)
def test_backend_axis_matches_slogdet(update, backend, monkeypatch):
    """The Pallas hook path (interpret mode on CPU, forced via the env
    override) must agree with the XLA expressions digit for digit."""
    if backend == "pallas":
        monkeypatch.setenv("REPRO_KERNEL_BACKEND", "interpret")
    a = jnp.asarray(CASES["random"][:24, :24])
    cfg = EngineConfig(schedule="serial", update=update, panel_k=8,
                       backend=backend)
    assert_matches_ref(engine_slogdet(a, cfg), a, rtol=1e-8)


def test_staged_panel_combination_is_new_but_correct():
    """staged x panel had no legacy route string; it must still be a
    first-class engine point."""
    rng = np.random.default_rng(7)
    a = rng.standard_normal((200, 200))
    cfg = EngineConfig(schedule="staged", update="panel", panel_k=16,
                       min_size=32)
    assert_matches_ref(engine_slogdet(jnp.asarray(a), cfg), a, rtol=1e-8)


@pytest.mark.parametrize("update", UPDATES)
def test_mesh_routes_match_slogdet_one_device(update, mesh1):
    rng = np.random.default_rng(3)
    a = rng.standard_normal((24, 24))
    cfg = EngineConfig(schedule="mesh", update=update, panel_k=8)
    fn = build_mesh(cfg, mesh1)
    assert_matches_ref(fn(jnp.asarray(a)), a)


def test_mesh_route_validates_divisibility(mesh1):
    cfg = EngineConfig(schedule="mesh")
    fn = build_mesh(cfg, mesh1)
    fn(jnp.eye(8))                      # 8 % 1 == 0: fine
    with pytest.raises(ValueError, match="schedule"):
        build_serial(cfg)               # mesh cfg needs build_mesh
    with pytest.raises(ValueError, match="mesh"):
        engine_slogdet(jnp.eye(8), cfg)  # no mesh supplied


def test_engine_config_validation():
    with pytest.raises(ValueError, match="schedule"):
        EngineConfig(schedule="spiral")
    with pytest.raises(ValueError, match="update"):
        EngineConfig(update="rank3")
    with pytest.raises(ValueError, match="backend"):
        EngineConfig(backend="rocm")
    with pytest.raises(ValueError, match="shrink"):
        EngineConfig(shrink=1.5)


def test_legacy_route_table_covers_the_condensation_matrix():
    """Every non-mesh legacy route string denotes a serial engine point and
    reproduces it exactly (the step logic exists once)."""
    rng = np.random.default_rng(11)
    a = jnp.asarray(rng.standard_normal((40, 40)))
    from repro.core import pad_to_multiple
    for route, (schedule, update) in LEGACY_ROUTES.items():
        if schedule == "mesh":
            continue
        cfg = EngineConfig(schedule=schedule, update=update)
        x = pad_to_multiple(a, cfg.panel_k) if update == "panel" else a
        s, ld = engine_slogdet(x, cfg)
        s_ref, ld_ref = np.linalg.slogdet(np.asarray(a))
        assert float(s) == pytest.approx(s_ref), route
        np.testing.assert_allclose(float(ld), ld_ref, rtol=1e-8)


def test_legacy_wrappers_are_engine_aliases():
    """The historical module entry points must be the engine's functions,
    not copies — the acceptance criterion that the rank-1/panel step logic
    exists in exactly one module."""
    from repro.core import blocked, condense, engine, parallel
    assert condense.slogdet_condense is engine.condense_full
    assert condense.condense_steps is engine.condense_steps
    assert condense.combine_slogdet is engine.combine_slogdet
    assert blocked.panel_factor is engine.panel_factor
    assert blocked.apply_panel is engine.apply_panel
    assert blocked.slogdet_condense_blocked is engine.blocked_full
    assert parallel.mc_step_fn is engine.mc_step_fn
    assert parallel.mc_local_phase is engine.mc_local_phase


def test_shared_sign_helpers_back_the_baselines():
    from repro.core import engine, gaussian, scalapack
    assert gaussian.cyclic_perm is engine.cyclic_perm
    assert gaussian.perm_parity is engine.perm_parity
    perm = np.array([1, 0, 2])
    assert engine.perm_parity(perm) == -1.0
    assert engine.perm_parity(engine.cyclic_perm(8, 2)).__abs__() == 1.0


# ---------------------------------------------------------------------------
# lookahead: the pipelined mesh schedule must be bit-identical to the
# plain one and its factor stage must exist only when enabled
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("case", sorted(CASES))
@pytest.mark.parametrize("update", UPDATES)
def test_lookahead_bit_identical_one_device(update, case, mesh1):
    """lookahead=True reorders the schedule but never the arithmetic on
    the local block: (sign, logabsdet) must match bit for bit."""
    a = jnp.asarray(CASES[case])
    plain = build_mesh(
        EngineConfig(schedule="mesh", update=update, panel_k=8), mesh1)(a)
    la = build_mesh(
        EngineConfig(schedule="mesh", update=update, panel_k=8,
                     lookahead=True), mesh1)(a)
    assert float(la[0]) == float(plain[0]), case
    assert float(la[1]) == float(plain[1]), case


def test_lookahead_requires_mesh_schedule():
    with pytest.raises(ValueError, match="lookahead"):
        EngineConfig(schedule="staged", lookahead=True)
    from repro.core.configs import ExactConfig
    with pytest.raises(ValueError, match="lookahead"):
        ExactConfig(schedule="serial", lookahead=True)
    with pytest.raises(ValueError, match="mesh"):
        ExactConfig(lookahead=True).resolved(mesh_present=False)
    assert ExactConfig(lookahead=True).resolved(
        mesh_present=True).engine_config().lookahead


@pytest.mark.parametrize("update", UPDATES)
def test_lookahead_stage_only_when_enabled(update, mesh1):
    """The obs.stage("engine.lookahead_factor") named scope must reach the
    compiled HLO exactly when the flag is set — the structural half of
    the 'lookahead is real now' claim, certified by the `stage-coverage`
    analysis pass in BOTH directions: the flag-on program carries the
    stage, and auditing it under a flag-off claim fails (and vice versa —
    each program is the other's mutation proof).  n=32 with panel_k=8
    gives the panel kernel more than one full panel, so the pipelined
    loop body (where the stage lives) actually traces."""
    from repro.analysis import AuditContext, run_passes

    a = jnp.eye(32)
    cfgs = [EngineConfig(schedule="mesh", update=update, panel_k=8,
                         lookahead=la) for la in (False, True)]
    plain, la = (build_mesh(c, mesh1).lower(a).compile().as_text()
                 for c in cfgs)
    ctxs = [AuditContext(label=f"mesh|{update}|la={flag}", method="exact",
                         schedule="mesh", update=update, panel_k=8,
                         lookahead=flag, n=32, devices=1)
            for flag in (False, True)]
    pid = ("stage-coverage",)
    assert run_passes(plain, ctxs[0], pid).ok
    assert run_passes(la, ctxs[1], pid).ok
    # cross-audits: an inert flag or a phantom stage must be findings
    assert any(f.where == "engine.lookahead_factor"
               for f in run_passes(la, ctxs[0], pid).errors)
    assert any(f.where == "engine.lookahead_factor"
               for f in run_passes(plain, ctxs[1], pid).errors)


def test_lookahead_wrappers_accept_and_thread_the_flag(mesh1):
    """The historical wrappers must run the pipelined kernel silently —
    no stale UserWarning — and still reject unknown keywords."""
    import warnings
    from repro.core.blocked import parallel_slogdet_mc_blocked
    from repro.core.parallel import parallel_slogdet_mc
    rng = np.random.default_rng(9)
    a = jnp.asarray(rng.standard_normal((24, 24)))
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        got_p = parallel_slogdet_mc_blocked(mesh1, k=8, lookahead=True)(a)
        got_r = parallel_slogdet_mc(mesh1, lookahead=True)(a)
    assert not [w for w in caught if "lookahead" in str(w.message)], caught
    assert_matches_ref(got_p, a)
    assert_matches_ref(got_r, a)
    # unknown inert kwargs are a TypeError, not silent acceptance
    with pytest.raises(TypeError):
        parallel_slogdet_mc_blocked(mesh1, lookbehind=True)
    with pytest.raises(TypeError):
        parallel_slogdet_mc(mesh1, lookbehind=True)


def test_mesh_tail_gathers_only_live_columns(mesh1):
    """The tail all_gather must move the (P,) live-column prefix, never
    full (N,) rows — 8*P^2 bytes on the wire, not 8*N*P.  Certified by
    the `collective-payload-budget` analysis pass, whose analytic bound
    encodes exactly this; the pass's own mutation proof (an artificially
    re-widened gather) lives in tests/test_analysis.py."""
    from repro.analysis import AuditContext, parse_module, run_passes

    n = 32
    fn = build_mesh(EngineConfig(schedule="mesh", update="rank1"), mesh1)
    txt = fn.lower(jnp.eye(n)).as_text()
    mod = parse_module(txt)
    gathers = [i for i in mod.collectives()
               if i.opcode.startswith("all-gather")]
    assert gathers, "tail all_gather missing from the lowered mesh kernel"
    report = run_passes(mod, AuditContext(
        label="mesh|rank1 fwd", method="exact", schedule="mesh",
        update="rank1", n=n, devices=1), ("collective-payload-budget",))
    assert report.ok, report.summary()


@pytest.mark.slow
def test_lookahead_bit_identical_across_devices():
    """Bit-identity of the pipelined schedule on real fake-device meshes:
    P in {2, 4, 8} x update x sign-stressing inputs."""
    from tests._subproc import run_with_devices, SRC
    out = run_with_devices(
        """
import sys; sys.path.insert(0, %r)
from repro.core.engine import EngineConfig, build_mesh
from repro.launch.mesh import make_mesh
rng = np.random.default_rng(13)
n = 48
cases = {
    "random": rng.standard_normal((n, n)),
    "permutation": np.eye(n)[rng.permutation(n)],
    "near_singular": None,
}
b = rng.standard_normal((n, 4))
cases["near_singular"] = b @ b.T + 1e-10 * np.eye(n)
neg = rng.standard_normal((n, n)); neg[5] = -neg[5]
cases["negative_det"] = neg
for P in (2, 4, 8):
    mesh = make_mesh((P,), ("rows",))
    for update in ("rank1", "panel"):
        for name, a in cases.items():
            k = dict(schedule="mesh", update=update, panel_k=8)
            s0, l0 = build_mesh(EngineConfig(**k), mesh)(a)
            s1, l1 = build_mesh(EngineConfig(**k, lookahead=True), mesh)(a)
            assert float(s0) == float(s1), (P, update, name)
            assert float(l0) == float(l1), (P, update, name)
print("OK")
""" % SRC,
        n_devices=8,
    )
    assert "OK" in out


@pytest.mark.slow
def test_engine_mesh_routes_eight_devices():
    """The unified engine on a real 8-fake-device mesh: round-robin
    schedule, both update modes, against numpy."""
    from tests._subproc import run_with_devices, SRC
    out = run_with_devices(
        """
import sys; sys.path.insert(0, %r)
import repro
from repro.core.engine import EngineConfig, build_mesh
from repro.launch.mesh import make_mesh
mesh = make_mesh((8,), ("rows",))
rng = np.random.default_rng(5)
for n in (64, 96):
    a = rng.standard_normal((n, n))
    s_ref, ld_ref = np.linalg.slogdet(a)
    for update in ("rank1", "panel"):
        cfg = EngineConfig(schedule="mesh", update=update, panel_k=4)
        s, ld = build_mesh(cfg, mesh)(jnp.asarray(a))
        assert float(s) == s_ref, (update, n, float(s), s_ref)
        assert abs(float(ld) - ld_ref) < 1e-8, (update, n, float(ld), ld_ref)
# diagnostics reflect execution: a serial route ignores the mesh
p_mesh = repro.plan((64, 64), method="exact", schedule="mesh", mesh=mesh)
p_serial = repro.plan((64, 64), method="exact", schedule="staged", mesh=mesh)
assert p_mesh.diagnostics.device_count == 8, p_mesh.diagnostics
assert p_serial.diagnostics.device_count == 1, p_serial.diagnostics
print("OK")
""" % SRC,
        n_devices=8,
    )
    assert "OK" in out


# ------------------------------------------------- fused one-pass steps

@pytest.mark.parametrize("case", sorted(CASES))
@pytest.mark.parametrize("update", UPDATES)
@pytest.mark.parametrize("schedule", SCHEDULES_SERIAL)
def test_fused_bit_identical(schedule, update, case):
    """fused=True collapses pivot/swap/update into one pass but reorders
    no arithmetic: (sign, logabsdet) must match the unfused engine bit
    for bit on every case, including permuted / negative-det /
    near-singular inputs."""
    a = jnp.asarray(CASES[case])
    if update == "panel":
        from repro.core import pad_to_multiple
        a = pad_to_multiple(a, 8)
    kw = dict(schedule=schedule, update=update, panel_k=8, min_size=16,
              backend="xla")
    plain = engine_slogdet(a, EngineConfig(**kw))
    fused = engine_slogdet(a, EngineConfig(fused=True, **kw))
    assert float(fused[0]) == float(plain[0]), case
    assert float(fused[1]) == float(plain[1]), case


@pytest.mark.parametrize("update", UPDATES)
def test_fused_interpret_backend_matches_slogdet(update, monkeypatch):
    """The fused Pallas kernel (interpret mode on CPU, forced via the env
    override) must still produce a correct logdet on odd-size input."""
    monkeypatch.setenv("REPRO_KERNEL_BACKEND", "interpret")
    a = jnp.asarray(CASES["scaled_odd"])
    if update == "panel":
        from repro.core import pad_to_multiple
        a = pad_to_multiple(a, 8)
    cfg = EngineConfig(schedule="staged", update=update, panel_k=8,
                       min_size=16, fused=True, backend="auto")
    assert_matches_ref(engine_slogdet(a, cfg), a, rtol=1e-8,
                       case="scaled_odd")


@pytest.mark.parametrize("case", ["random", "negative_det"])
def test_bf16_precision_error_model(case):
    """precision='bf16' quantizes GEMM operands only: the sign must stay
    exact and logabsdet within the documented |rel err| <= 5e-3 of the
    full-precision engine at these sizes (measured 4e-4..2e-3); fused and unfused bf16 routes
    agree bit for bit (same quantization points)."""
    a = jnp.asarray(CASES[case], jnp.float32)
    from repro.core import pad_to_multiple
    a = pad_to_multiple(a, 8)
    kw = dict(schedule="staged", update="panel", panel_k=8, min_size=16,
              backend="xla")
    exact = engine_slogdet(a, EngineConfig(**kw))
    mixed = engine_slogdet(a, EngineConfig(precision="bf16", **kw))
    assert float(mixed[0]) == float(exact[0]), "sign must survive bf16"
    rel = abs(float(mixed[1]) - float(exact[1])) / abs(float(exact[1]))
    assert rel < 5e-3, (case, rel)
    mixed_fused = engine_slogdet(
        a, EngineConfig(fused=True, precision="bf16", **kw))
    assert float(mixed_fused[0]) == float(mixed[0])
    assert float(mixed_fused[1]) == float(mixed[1])


def test_fused_requires_serial_schedule():
    with pytest.raises(ValueError, match="fused"):
        EngineConfig(schedule="mesh", fused=True)
    from repro.core.configs import ExactConfig
    with pytest.raises(ValueError, match="fused"):
        ExactConfig(fused=True).resolved(mesh_present=True)
    # serial resolution keeps the flag
    assert ExactConfig(fused=True).resolved(
        mesh_present=False).engine_config().fused
    with pytest.raises(ValueError, match="precision"):
        EngineConfig(precision="fp8")


@pytest.mark.parametrize("update", UPDATES)
def test_fused_stage_only_when_enabled(update):
    """Mirror of the lookahead stage-coverage proof: the compiled program
    must carry engine.fused_step exactly when fused=True (and then drop
    engine.pivot/swap/update), certified by the stage-coverage pass in
    both directions so an inert flag or a phantom stage is a finding."""
    from repro.analysis import AuditContext, run_passes

    a = jnp.eye(32)
    cfgs = [EngineConfig(schedule="staged", update=update, panel_k=8,
                         min_size=16, fused=f) for f in (False, True)]
    plain, fused = (jax.jit(lambda x, c=c: engine_slogdet(x, c))
                    .lower(a).compile().as_text() for c in cfgs)
    ctxs = [AuditContext(label=f"staged|{update}|fused={flag}",
                         method="exact", schedule="staged", update=update,
                         panel_k=8, fused=flag, n=32, devices=1)
            for flag in (False, True)]
    pid = ("stage-coverage",)
    assert run_passes(plain, ctxs[0], pid).ok
    assert run_passes(fused, ctxs[1], pid).ok
    assert any(f.where == "engine.fused_step"
               for f in run_passes(fused, ctxs[0], pid).errors)
    assert any(f.where == "engine.fused_step"
               for f in run_passes(plain, ctxs[1], pid).errors)

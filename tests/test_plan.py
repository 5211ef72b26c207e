"""The plan/execute API: `repro.plan` -> `LogdetPlan` -> `LogdetResult`.

This file is the deprecation gate's target: it exercises ONLY the new API
(plus `pytest.warns`-guarded shim checks), so CI runs it with
``-W error::DeprecationWarning`` to prove internal code never routes
through the legacy string shims.

Covers: typed config validation, the auto-selector's crossover (exact for
small dense N, estimators for large N / implicit operators, mesh-aware),
the unified `LogdetResult` across every path, the non-SPD screen, plan
caching / no-retrace behavior, and diagnostics-rich gradients.
"""
import dataclasses

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import repro
from repro import (
    ChebyshevConfig, EngineConfig, ExactConfig, LogdetResult, SLQConfig,
    select_method, select_route,
)
from repro.estimators import StencilOperator, ToeplitzOperator


def make_spd(n, seed, shift=2.0):
    rng = np.random.default_rng(seed)
    x = rng.standard_normal((n, 2 * n))
    return x @ x.T / (2 * n) + shift * np.eye(n)


def spec_with_devices(n, devices):
    return dataclasses.replace(repro.spec_of((n, n)), device_count=devices)


# ------------------------------------------------------------ typed configs

def test_config_validation_rejects_bad_values():
    with pytest.raises(ValueError, match="degree"):
        ChebyshevConfig(degree=0)
    with pytest.raises(ValueError, match="num_probes"):
        SLQConfig(num_probes=0)
    with pytest.raises(ValueError, match="lmax"):
        ChebyshevConfig(lmin=4.0, lmax=1.0)
    with pytest.raises(ValueError, match="k must be"):
        ExactConfig(k=0)
    with pytest.raises(ValueError, match="schedule"):
        ExactConfig(schedule="diagonal")
    with pytest.raises(ValueError, match="update"):
        ExactConfig(update="rank2")
    with pytest.raises(ValueError, match="backend"):
        ExactConfig(backend="cuda")
    with pytest.raises(ValueError, match="schedule"):
        EngineConfig(schedule="bogus")
    with pytest.raises(ValueError, match="panel_k"):
        EngineConfig(panel_k=0)


def test_plan_rejects_unknown_and_misfiled_kwargs():
    with pytest.raises(TypeError, match="estimator keywords"):
        repro.plan((8, 8), method="exact", num_probes=4)
    with pytest.warns(DeprecationWarning, match="deprecated"):
        with pytest.raises(TypeError, match="estimator keywords"):
            repro.plan((8, 8), method="mc", num_probes=4)
    with pytest.raises(TypeError, match="unknown keywords"):
        repro.plan((8, 8), method="chebyshev", num_steps=10)
    with pytest.raises(TypeError, match="unknown keywords"):
        repro.plan((8, 8), method="slq", degree=16)
    with pytest.raises(ValueError, match="unknown method"):
        repro.plan((8, 8), method="cholesky")


def test_plan_config_instance_must_match_method():
    p = repro.plan((8, 8), method="slq", config=SLQConfig(num_steps=5))
    assert p.config.num_steps == 5
    with pytest.raises(TypeError, match="SLQConfig"):
        repro.plan((8, 8), method="slq", config=ChebyshevConfig())
    with pytest.raises(TypeError, match="not both"):
        repro.plan((8, 8), method="slq", config=SLQConfig(), num_probes=4)
    with pytest.raises(ValueError, match="ambiguous"):
        repro.plan((8, 8), method="auto", config=SLQConfig())


# ------------------------------------------------------------- auto select

def test_auto_picks_exact_below_crossover():
    assert select_method((64, 64)) == "exact"
    assert select_method((512, 512)) == "exact"
    # batched small stacks: vmapped exact condensation per matrix
    assert select_method((8, 64, 64)) == "exact"


def test_auto_resolves_route_tuples_not_strings():
    """The selector answers with an EngineConfig tuple for the exact
    family — and the tuple's axes respond to the problem shape."""
    m, route = select_route((64, 64))
    assert m == "exact" and isinstance(route, EngineConfig)
    # small N: the autotuner narrows panels (k=8) so rank-K updates
    # amortize even here — fixed-width 32 used to force rank-1
    assert route.update in ("rank1", "panel")
    assert route.schedule in ("serial", "staged")
    if route.update == "panel":
        assert route.panel_k <= 16, route.panel_k
    # large single-device exact work rides the MXU: panel updates
    m2, route2 = select_route((2048, 2048), rtol=1e-9)
    assert m2 == "exact" and route2.update == "panel"
    # batched stacks run the vmapped serial schedule, never mesh
    m3, route3 = select_route((8, 64, 64))
    assert m3 == "exact" and route3.schedule == "serial"
    # estimator picks carry no engine tuple
    m4, route4 = select_route((8192, 8192))
    assert m4 == "slq" and route4 is None


def test_auto_picks_estimator_above_crossover():
    assert select_method((8192, 8192)) == "slq"
    assert select_method((4, 8192, 8192)) == "slq"
    # known spectral bounds unlock the cheaper Chebyshev path
    assert select_method((8192, 8192), bounds_known=True) == "chebyshev"


def test_auto_picks_estimator_for_implicit_operators():
    # structure makes the matvec cheap AND the matrix unmaterializable:
    # estimators are the only family, at any size
    op = ToeplitzOperator(jnp.asarray(np.r_[2.5, 0.5 ** np.arange(1, 64)]))
    assert select_method(op) == "slq"
    st = StencilOperator((-1, 0, 1), jnp.asarray([-1.0, 2.5, -1.0]), n=64)
    assert select_method(st) == "slq"


def test_auto_on_materializable_operator_stays_matrix_free():
    """Dense/sharded OPERATORS advertise materializable=True, but exact
    methods take arrays, not operators — auto must stay on estimators."""
    from repro.estimators import DenseOperator
    op = DenseOperator(jnp.asarray(make_spd(32, 0)))
    assert op.plan_hints().materializable
    assert select_method(op) == "slq"
    p = repro.plan(op, method="auto", num_probes=16)
    assert p.method == "slq"
    assert jnp.isfinite(p().logabsdet)


def test_batched_stack_rejects_mesh_up_front(mesh1):
    stack = np.stack([make_spd(16, s) for s in range(2)])
    for method in ("auto", "mc", "slq"):
        with pytest.raises(TypeError, match="one device per matrix"):
            repro.plan(stack, method=method, mesh=mesh1)


def test_auto_drops_other_familys_kwargs():
    # below the crossover auto resolves to exact: the estimator knobs are
    # dropped rather than crashing the plan the selector picked
    p = repro.plan((64, 64), method="auto", num_probes=16)
    assert p.method == "exact" and isinstance(p.config, ExactConfig)
    # above the crossover the same knobs land in the estimator config
    p2 = repro.plan((8192, 8192), method="auto", num_probes=16)
    assert p2.method == "slq" and p2.config.num_probes == 16
    # typos no family understands still fail loudly
    with pytest.raises(TypeError, match="unknown keywords"):
        repro.plan((64, 64), method="auto", num_probs=16)


def test_auto_accuracy_demand_forces_exact():
    # at rtol below the Monte-Carlo floor only exact methods qualify
    assert select_method((8192, 8192), rtol=1e-8) == "exact"
    assert select_method((8192, 8192), rtol=1e-2) == "slq"


# ------------------------------------------- calibrated cost model (roofline)

def test_calibration_table_is_measured_not_static():
    """The selector must load the committed measured roofline table, not
    fall back to the static defaults."""
    from repro.core.calibration import load_calibration
    cal = load_calibration()
    assert cal.source.startswith("measured"), cal
    for v in (cal.gemm_flops, cal.stream_bytes, cal.collective_lat,
              cal.collective_bytes):
        assert v > 0


def _write_table(tmp_path, devices):
    import json
    path = tmp_path / "table.json"
    path.write_text(json.dumps({"devices": devices}))
    return path


def test_calibration_lookup_by_device_kind(tmp_path):
    from repro.core.calibration import (CALIBRATE_CMD, CalibrationError,
                                        clear_calibration_cache,
                                        current_device_kind,
                                        load_calibration)
    entry = dict(gemm_flops=1e14, gemm_flops_bf16=1.9e14, stream_bytes=8e11,
                 collective_lat=None, collective_bytes=None,
                 source="measured:tpu")
    path = _write_table(tmp_path, {
        "TPU v5 lite": entry,
        "cpu": dict(entry, gemm_flops=1e11, source="measured:cpu"),
    })
    clear_calibration_cache()
    tpu = load_calibration(path, device_kind="TPU v5 lite")
    assert (tpu.gemm_flops, tpu.source) == (1e14, "measured:tpu")
    assert tpu.gemm_rate("bf16") == 1.9e14
    # the default key is the device this process runs on
    assert current_device_kind() == jax.devices()[0].device_kind
    assert load_calibration(path).gemm_flops == (
        1e14 if current_device_kind() == "TPU v5 lite" else 1e11)
    # a single-device entry cannot price a mesh
    with pytest.raises(CalibrationError, match="one device"):
        tpu.collective()
    # an unknown kind is an error naming the calibrate command, never the
    # static defaults
    with pytest.raises(CalibrationError) as err:
        load_calibration(path, device_kind="TPU v9 imaginary")
    assert CALIBRATE_CMD in str(err.value)
    assert "TPU v9 imaginary" in str(err.value)


def test_calibration_static_is_explicit_opt_in(monkeypatch, tmp_path):
    from repro.core.calibration import (STATIC_DEFAULT, CalibrationError,
                                        clear_calibration_cache,
                                        load_calibration)
    monkeypatch.setenv("REPRO_CALIBRATION", "static")
    assert load_calibration() is STATIC_DEFAULT
    monkeypatch.setenv("REPRO_CALIBRATION",
                       str(_write_table(tmp_path, {"other": {}})))
    clear_calibration_cache()
    with pytest.raises(CalibrationError, match="no entry for device kind"):
        load_calibration()
    monkeypatch.setenv("REPRO_CALIBRATION", str(tmp_path / "missing.json"))
    with pytest.raises(CalibrationError, match="cannot read"):
        load_calibration()


def test_committed_calibration_covers_cpu_and_v5e():
    """The committed table has one measured entry per device kind the
    repo runs on, each with a measured bf16 GEMM rate."""
    import json
    from repro.core.calibration import calibration_path, load_calibration
    table = json.loads(calibration_path().read_text())["devices"]
    for kind in ("cpu", "TPU v5 lite"):
        cal = load_calibration(device_kind=kind)
        assert cal.source.startswith("measured"), (kind, cal)
        assert cal.gemm_rate("bf16") == table[kind]["gemm_flops_bf16"] > 0


def _dense_est_crossover(devices, cal=None, lo=32, hi=1 << 22):
    """Smallest N where the selector leaves the exact family."""
    def method(n):
        return select_method(spec_with_devices(n, devices), calibration=cal)
    assert method(lo) == "exact"
    assert method(hi) != "exact"
    while hi - lo > 1:
        mid = (lo + hi) // 2
        if method(mid) == "exact":
            lo = mid
        else:
            hi = mid
    return hi


def _serial_mesh_crossover(devices, lo=8, hi=1 << 22):
    """Smallest N where the exact family flips to the mesh schedule
    (rtol pinned below the Monte-Carlo floor so exact always wins)."""
    def schedule(n):
        return select_route(spec_with_devices(n, devices), rtol=1e-9)[1] \
            .schedule
    assert schedule(lo) != "mesh"
    assert schedule(hi) == "mesh"
    while hi - lo > 1:
        mid = (lo + hi) // 2
        if schedule(mid) == "mesh":
            hi = mid
        else:
            lo = mid
    return hi


def test_dense_estimator_crossover_varies_with_devices():
    """The static-FLOP model divided both sides by P, making the crossover
    device-count-invariant; the measured model's collective terms do not
    shrink with P, so it must move.  Priced on the committed v5e entry,
    whose collective terms were measured over four chips."""
    from repro.core.calibration import load_calibration
    cal = load_calibration(device_kind="TPU v5 lite")
    assert _dense_est_crossover(1, cal) != _dense_est_crossover(8, cal)


def test_dense_estimator_crossover_at_exact_floor_ignores_devices():
    """Where exact first costs more than the exact-free floor only after
    the estimator is already cheaper at every P, the floor sets the
    crossover and P cannot move it: the committed CPU entry's case."""
    from repro.core.calibration import load_calibration, exact_cost
    from repro.core.plan import _EXACT_FREE_SECONDS
    cal = load_calibration(device_kind="cpu")
    c1, c8 = _dense_est_crossover(1, cal), _dense_est_crossover(8, cal)
    assert c1 == c8

    def exact_t(n):
        return min(exact_cost(n, 1, cal, update=u, itemsize=8)
                   for u in ("rank1", "panel"))
    assert exact_t(c1 - 1) <= _EXACT_FREE_SECONDS < exact_t(c1)


def test_serial_mesh_crossover_varies_with_devices():
    c2, c8 = _serial_mesh_crossover(2), _serial_mesh_crossover(8)
    assert c2 != c8
    # small matrices never pay the per-step broadcast latency
    assert min(c2, c8) > 32


def _serial_mesh_crossover_cal(devices, cal, lo=8, hi=1 << 22):
    def schedule(n):
        return select_route(spec_with_devices(n, devices), rtol=1e-9,
                            calibration=cal)[1].schedule
    if schedule(hi) != "mesh":
        return hi + 1
    while hi - lo > 1:
        mid = (lo + hi) // 2
        if schedule(mid) == "mesh":
            hi = mid
        else:
            lo = mid
    return hi


def test_mesh_crossover_prices_the_communication_term():
    """Degrading the measured collective terms 100x must push the mesh
    schedule's break-even point up — the selector really reads the table,
    not a constant."""
    from repro.core.calibration import load_calibration
    cal = load_calibration()
    slow = dataclasses.replace(cal, collective_lat=cal.collective_lat * 100,
                               collective_bytes=cal.collective_bytes / 100)
    assert _serial_mesh_crossover_cal(8, slow) > \
        _serial_mesh_crossover_cal(8, cal)


def test_auto_plan_resolves_and_executes():
    a = make_spd(48, 0)
    p = repro.plan(a, method="auto")
    assert p.method == "exact"              # resolved, never "auto"
    assert p.config.schedule in ("serial", "staged")
    res = p()
    assert isinstance(res, LogdetResult)
    assert res.method_used == "exact"
    np.testing.assert_allclose(float(res.logabsdet),
                               np.linalg.slogdet(a)[1], rtol=1e-9)


def test_auto_operator_plan_executes():
    c = np.zeros(96)
    c[0], c[1] = 2.5, -1.0
    op = ToeplitzOperator(jnp.asarray(c))
    p = repro.plan(op, method="auto", num_probes=32)
    assert p.method == "slq"
    res = p()
    i = np.arange(96)
    ref = np.linalg.slogdet(c[np.abs(i[:, None] - i[None, :])])[1]
    assert abs(float(res.logabsdet) - ref) < 5 * float(res.sem) + 0.5


def test_auto_routes_non_spd_to_clear_error():
    n = 4096                                # above the crossover
    rng = np.random.default_rng(0)
    a = rng.standard_normal((n, n)) + n * np.eye(n)   # NOT symmetric
    p = repro.plan((n, n), method="auto")
    assert p.method in ("chebyshev", "slq")
    with pytest.raises(ValueError, match="not symmetric"):
        p(a)
    bad_diag = -np.eye(n)                   # symmetric but indefinite
    with pytest.raises(ValueError, match="positive-definite"):
        p(bad_diag)


def test_validate_false_skips_spd_screen():
    a = make_spd(16, 0)
    p = repro.plan((16, 16), method="slq", validate=False, num_probes=8)
    assert jnp.isfinite(p(a).logabsdet)


# --------------------------------------------------------- unified results

@pytest.mark.parametrize("method,kw", [
    ("exact", dict(schedule="serial")),
    ("exact", dict(schedule="staged")),
    ("exact", dict(schedule="serial", update="panel", k=16)),
    ("ge", {}),
    ("chebyshev", dict(degree=48, num_probes=32)),
    ("slq", dict(num_steps=20, num_probes=32)),
])
def test_every_path_returns_logdet_result(method, kw):
    a = make_spd(96, 1)
    ref = np.linalg.slogdet(a)[1]
    res = repro.plan(a, method=method, **kw)()
    assert isinstance(res, LogdetResult)
    assert res.method_used == method
    assert float(res.sign) == 1.0
    assert res.sem is not None
    np.testing.assert_allclose(float(res.logabsdet), ref, rtol=5e-2)
    d = res.diagnostics
    assert d.wall_time_s is not None and d.wall_time_s >= 0
    assert d.padded_n == 96 and d.device_count == 1
    if method in ("chebyshev", "slq"):
        assert float(res.sem) > 0
        assert d.matvec_cols is not None and d.matvec_cols > 0
    else:
        assert float(res.sem) == 0.0
        assert d.matvec_cols is None
    assert d.flops_est is not None and d.flops_est > 0
    # legacy-style tuple unpacking works on the unified result
    s, ld = res
    assert float(s) == 1.0 and float(ld) == float(res.logabsdet)


def test_batched_plan_unified_result():
    stack = np.stack([make_spd(32, s, shift=1.5 + 0.1 * s) for s in range(4)])
    ref = np.array([np.linalg.slogdet(m)[1] for m in stack])
    exact = repro.plan(stack, method="exact", schedule="serial")()
    np.testing.assert_allclose(np.asarray(exact.logabsdet), ref, rtol=1e-9)
    assert exact.sign.shape == (4,) and float(exact.sem.max()) == 0.0
    est = repro.plan(stack, method="slq", num_probes=48)()
    assert est.logabsdet.shape == (4,) and est.sem.shape == (4,)
    rel = np.abs(np.asarray(est.logabsdet) - ref) / np.abs(ref)
    assert np.median(rel) < 5e-2


def test_batched_stack_accepts_any_serial_engine_route():
    """logdet_batched used to hardwire the 'mc' route; stacks now run any
    engine route — panel updates included — and mesh schedules fail with
    a targeted error, not a generic one."""
    stack = np.stack([make_spd(24, s) for s in range(3)])
    ref = np.array([np.linalg.slogdet(m)[1] for m in stack])
    for kw in (dict(schedule="serial", update="panel", k=8),
               dict(schedule="staged"),
               dict(schedule="staged", update="panel", k=8)):
        res = repro.plan(stack, method="exact", **kw)()
        np.testing.assert_allclose(np.asarray(res.logabsdet), ref,
                                   rtol=1e-8)
    with pytest.raises(TypeError, match="ONE matrix"):
        repro.plan(stack, method="exact", schedule="mesh")
    # the non-deprecated batched entry point takes engine routes too
    from repro.estimators import logdet_batched as est_batched
    got = est_batched(stack, method="exact", update="panel", k=8)
    np.testing.assert_allclose(np.asarray(got), ref, rtol=1e-8)


def test_mesh_plan_matches_serial(mesh1):
    a = make_spd(24, 2)
    res = repro.plan(a, method="exact", schedule="mesh", mesh=mesh1)()
    np.testing.assert_allclose(float(res.logabsdet),
                               np.linalg.slogdet(a)[1], rtol=1e-9)
    # a supplied mesh resolves the default schedule to "mesh"
    p_default = repro.plan(a, method="exact", mesh=mesh1)
    assert p_default.config.schedule == "mesh"
    est = repro.plan(a, method="chebyshev", mesh=mesh1,
                     num_probes=16, degree=32)()
    direct = repro.plan(a, method="chebyshev", num_probes=16, degree=32)()
    np.testing.assert_allclose(float(est.logabsdet),
                               float(direct.logabsdet), rtol=1e-10)


def test_mesh_panel_plan_matches_serial(mesh1):
    a = make_spd(24, 12)
    res = repro.plan(a, method="exact", schedule="mesh", update="panel",
                     k=8, mesh=mesh1)()
    np.testing.assert_allclose(float(res.logabsdet),
                               np.linalg.slogdet(a)[1], rtol=1e-9)
    assert float(res.sign) == float(np.linalg.slogdet(a)[0])


def test_mesh_schedule_without_mesh_is_an_error():
    with pytest.raises(ValueError, match="requires a mesh"):
        repro.plan((16, 16), method="exact", schedule="mesh")


def test_spec_only_plan_requires_matching_input():
    p = repro.plan((16, 16), method="exact", schedule="serial")
    with pytest.raises(TypeError, match="shape spec"):
        p()
    with pytest.raises(ValueError, match="compiled for shape"):
        p(np.eye(8))
    s, ld = p(np.eye(16) * 3.0)
    np.testing.assert_allclose(float(ld), 16 * np.log(3.0), rtol=1e-12)


def test_precision_override_casts():
    a = make_spd(24, 3)                      # float64 under x64
    p = repro.plan((24, 24), method="exact", precision="float32")
    res = p(a)
    assert res.logabsdet.dtype == jnp.float32


def test_exact_plan_rejects_runtime_randomness():
    p = repro.plan((8, 8), method="exact")
    with pytest.raises(TypeError, match="key"):
        p(np.eye(8), key=jax.random.PRNGKey(0))


# ------------------------------------------------- caching and no-retrace

def test_plan_cache_shares_compiled_executable():
    a = make_spd(20, 4)
    p1 = repro.plan(a, method="exact", schedule="staged")
    p2 = repro.plan((20, 20), method="exact", schedule="staged")
    assert p1._fwd is p2._fwd                 # one artifact, both handles
    p3 = repro.plan((20, 20), method="exact",
                    config=ExactConfig(schedule="staged"))
    assert p3._fwd is p1._fwd                 # default config == no kwargs
    # the bare default spelling resolves to staged x rank1 too
    p4 = repro.plan((20, 20), method="exact")
    assert p4._fwd is p1._fwd


# ------------------------------------------------- legacy exact routes

def test_legacy_route_strings_resolve_to_engine_instantiations():
    """Every legacy condensation route string is a deprecated alias for an
    engine tuple: same plan cache entry, hence bit-identical results."""
    from repro.core.engine import LEGACY_ROUTES
    a = make_spd(20, 4)
    for route, (schedule, update) in LEGACY_ROUTES.items():
        if schedule == "mesh":
            continue                          # exercised in the mesh tests
        with pytest.warns(DeprecationWarning, match="deprecated"):
            p_legacy = repro.plan((20, 20), method=route, k=8)
        p_engine = repro.plan((20, 20), method="exact", schedule=schedule,
                              update=update, k=8)
        assert p_legacy.method == "exact"
        assert p_legacy.config.schedule == schedule
        assert p_legacy.config.update == update
        assert p_legacy._fwd is p_engine._fwd, route   # bit-identical
        legacy_res = p_legacy(a)
        engine_res = p_engine(a)
        assert float(legacy_res.sign) == float(engine_res.sign)
        assert float(legacy_res.logabsdet) == float(engine_res.logabsdet)


def test_legacy_mesh_route_strings_resolve_to_engine(mesh1):
    a = make_spd(16, 6)
    for route, update in (("pmc", "rank1"), ("pmc_blocked", "panel")):
        with pytest.warns(DeprecationWarning, match="deprecated"):
            p_legacy = repro.plan((16, 16), method=route, mesh=mesh1, k=8)
        p_engine = repro.plan((16, 16), method="exact", schedule="mesh",
                              update=update, k=8, mesh=mesh1)
        assert p_legacy.config.schedule == "mesh"
        assert p_legacy.config.update == update
        assert p_legacy._fwd is p_engine._fwd, route
        np.testing.assert_allclose(float(p_legacy(a).logabsdet),
                                   np.linalg.slogdet(a)[1], rtol=1e-9)


def test_legacy_route_rejects_conflicting_engine_axes():
    with pytest.warns(DeprecationWarning):
        with pytest.raises(TypeError, match="pins"):
            repro.plan((16, 16), method="mc", schedule="staged")
    with pytest.warns(DeprecationWarning):
        with pytest.raises(TypeError, match="pins"):
            repro.plan((16, 16), method="mc_blocked", update="rank1")


def test_plan_cache_keys_on_resolved_kernel_backend(monkeypatch):
    """backend='auto' is pinned at plan time: flipping the env override
    must build a new executable, not serve the stale cached one."""
    monkeypatch.delenv("REPRO_KERNEL_BACKEND", raising=False)
    p1 = repro.plan((21, 21), method="exact", schedule="serial")
    assert p1.config.backend in ("xla", "pallas")     # pinned, not "auto"
    monkeypatch.setenv("REPRO_KERNEL_BACKEND", "interpret")
    p2 = repro.plan((21, 21), method="exact", schedule="serial")
    assert p2.config.backend == "interpret"
    assert p2._fwd is not p1._fwd


def test_float64_pallas_plan_refused_on_tpu(monkeypatch):
    """Mosaic has no float64: on the TPU a float64 plan that would compile
    Pallas kernels is refused when it is built, naming float32."""
    from repro.kernels import ops
    monkeypatch.delenv("REPRO_KERNEL_BACKEND", raising=False)
    monkeypatch.setattr(ops, "on_tpu", lambda: True)
    assert jax.config.jax_enable_x64
    for kw in (dict(method="exact"), dict(method="exact", update="panel"),
               dict(method="chebyshev")):
        with pytest.raises(ValueError, match="float32"):
            repro.plan((76, 76), **kw)
        # float32 builds (the kernels compile for the chip, see
        # test_tpu_compile.py)
        p = repro.plan((76, 76), precision="float32", **kw)
        assert p.spec.dtype == "float32"
    # the xla backend has float64
    p = repro.plan((76, 76), method="exact", backend="xla")
    assert p.spec.dtype == "float64"


def test_repeated_plan_calls_do_not_retrace():
    p = repro.plan((24, 24), method="chebyshev", num_probes=8, degree=16)
    assert p.compiled
    a = make_spd(24, 0)
    r1 = p(a, key=jax.random.PRNGKey(0))
    r2 = p(jnp.asarray(a) + 0.01 * jnp.eye(24), key=jax.random.PRNGKey(1))
    r3 = p(a, key=jax.random.PRNGKey(2))
    assert p.trace_count == 1, f"retraced: {p.trace_count}"
    assert float(r1.logabsdet) != float(r2.logabsdet)
    assert float(r1.logabsdet) != float(r3.logabsdet)  # fresh key, new draw


def test_exact_plan_does_not_retrace_either():
    p = repro.plan((16, 16), method="exact", schedule="serial")
    p(make_spd(16, 0))
    p(make_spd(16, 1))
    p(make_spd(16, 2))
    assert p.trace_count == 1
    # value_and_grad reuses the plan's own compiled forward
    p.value_and_grad(make_spd(16, 3))
    assert p.trace_count == 1


def test_legacy_shim_reuses_plan_cache():
    from repro.core.plan import _PLAN_CACHE
    a = make_spd(28, 5)
    with pytest.warns(DeprecationWarning, match="deprecated"):
        from repro.core import slogdet
        s1, ld1 = slogdet(a, method="mc_staged")
    before = len(_PLAN_CACHE)
    with pytest.warns(DeprecationWarning, match="deprecated"):
        s2, ld2 = slogdet(np.asarray(a) * 1.0, method="mc_staged")
    assert len(_PLAN_CACHE) == before         # second call: cache hit
    assert float(ld1) == float(ld2)
    # and the shim agrees with the engine plan it wraps
    res = repro.plan(a, method="exact", schedule="staged")()
    assert float(res.logabsdet) == float(ld1)


def test_legacy_logdet_batched_warns_and_matches():
    stack = np.stack([make_spd(24, s) for s in range(3)])
    with pytest.warns(DeprecationWarning, match="deprecated"):
        from repro.core import logdet_batched
        legacy = logdet_batched(stack, method="mc")
    res = repro.plan(stack, method="exact", schedule="serial")()
    np.testing.assert_array_equal(np.asarray(legacy),
                                  np.asarray(res.logabsdet))


def test_runtime_bounds_are_execution_inputs():
    """Concrete scalar bounds bake into the (hashable) config; traced
    bounds ride the call — both produce the bounded-Chebyshev value."""
    a = make_spd(32, 9)
    lo, hi = 0.5, 40.0
    static = repro.plan(a, method="chebyshev", num_probes=8, degree=16,
                        lmin=lo, lmax=hi)
    base = static()
    # concrete 0-d arrays coerce into the config (cache stays hashable)
    arr_cfg = repro.plan(a, method="chebyshev", num_probes=8, degree=16,
                         lmin=jnp.asarray(lo), lmax=jnp.asarray(hi))
    assert arr_cfg.config.lmin == lo and arr_cfg._fwd is static._fwd
    # traced bounds cannot be static config ...
    with pytest.raises(TypeError, match="execution time"):
        jax.jit(lambda b: repro.plan((32, 32), method="chebyshev",
                                     lmin=b, lmax=4.0).config)(jnp.asarray(lo))
    # ... they are runtime inputs instead, inside or outside jit
    unbounded = repro.plan(a, method="chebyshev", num_probes=8, degree=16)
    rt = unbounded(lmin=jnp.asarray(lo), lmax=jnp.asarray(hi))
    np.testing.assert_allclose(float(rt.logabsdet), float(base.logabsdet),
                               rtol=1e-12)
    jit_ld = jax.jit(lambda x, b: unbounded.logdet(x, lmin=b[0], lmax=b[1]))(
        jnp.asarray(a), jnp.asarray([lo, hi]))
    np.testing.assert_allclose(float(jit_ld), float(base.logabsdet),
                               rtol=1e-12)


def test_legacy_shim_accepts_traced_bounds():
    a = jnp.asarray(make_spd(24, 10))
    from repro.core import slogdet
    with pytest.warns(DeprecationWarning):
        ref = slogdet(a, method="chebyshev", num_probes=8, degree=16,
                      lmin=0.5, lmax=40.0)[1]

        def f(x, b):
            return slogdet(x, method="chebyshev", num_probes=8, degree=16,
                           lmin=b[0], lmax=b[1])[1]

        got = jax.jit(f)(a, jnp.asarray([0.5, 40.0]))
    np.testing.assert_allclose(float(got), float(ref), rtol=1e-12)


def test_mesh_value_and_grad_matches_forward_without_padding(mesh1):
    """User bounds that exclude 1 must NOT be widened when the mesh
    embedding did not pad — forward and value_and_grad must agree."""
    a = make_spd(32, 11)                     # 32 % 1 == 0: no padding
    p = repro.plan(a, method="chebyshev", mesh=mesh1, num_probes=8,
                   degree=16, lmin=1.5, lmax=40.0)
    k = jax.random.PRNGKey(0)
    fwd = p(a, key=k)
    vag_res, _ = p.value_and_grad(a, key=k)
    np.testing.assert_allclose(float(vag_res.logabsdet),
                               float(fwd.logabsdet), rtol=1e-12)


def test_grad_prebuild_honored_on_cache_hit():
    repro.plan((20, 20), method="ge")                  # populate cache
    p = repro.plan((20, 20), method="ge", grad=True)   # cache hit
    assert p.grad and "vag" in p._cache


# ---------------------------------------------------------------- gradients

def test_plan_logdet_fn_is_differentiable_exact():
    a = jnp.asarray(make_spd(12, 6))
    p = repro.plan((12, 12), method="exact")
    g = jax.grad(lambda x: p.logdet(x))(a)
    np.testing.assert_allclose(np.asarray(g),
                               np.linalg.inv(np.asarray(a)).T,
                               rtol=1e-8, atol=1e-10)


def test_plan_logdet_fn_composes_with_jit_and_vmap():
    p = repro.plan((12, 12), method="slq", num_probes=8, num_steps=10)
    stack = jnp.asarray(np.stack([make_spd(12, s) for s in range(3)]))
    g = jax.vmap(jax.grad(lambda x: p.logdet(x, key=jax.random.PRNGKey(0))))(
        stack)
    assert g.shape == stack.shape and bool(jnp.isfinite(g).all())


def test_value_and_grad_exact():
    a = make_spd(16, 7)
    res, bar = repro.plan(a, method="exact").value_and_grad()
    np.testing.assert_allclose(float(res.logabsdet),
                               np.linalg.slogdet(a)[1], rtol=1e-9)
    np.testing.assert_allclose(np.asarray(bar), np.linalg.inv(a).T,
                               rtol=1e-8, atol=1e-10)
    assert res.diagnostics.cg_iters is None   # analytic inverse, no CG


def test_value_and_grad_estimator_reports_cg_iters():
    a = make_spd(32, 8)
    p = repro.plan(a, method="chebyshev", num_probes=64, degree=48)
    res, bar = p.value_and_grad(key=jax.random.PRNGKey(3))
    assert res.diagnostics.cg_iters is not None
    assert res.diagnostics.cg_iters > 0
    # the explicit pullback must agree with autodiff through the plan
    g = jax.grad(lambda x: p.logdet(x, key=jax.random.PRNGKey(3)))(
        jnp.asarray(a))
    np.testing.assert_allclose(np.asarray(bar), np.asarray(g),
                               rtol=1e-6, atol=1e-8)


def test_value_and_grad_structured_operator():
    ka = make_spd(5, 0)
    kb = make_spd(6, 1)
    from repro.estimators import KroneckerOperator
    op = KroneckerOperator(jnp.asarray(ka), jnp.asarray(kb))
    p = repro.plan(op, method="slq", num_probes=32, num_steps=20)
    res, (ga, gb) = p.value_and_grad()
    assert ga.shape == (5, 5) and gb.shape == (6, 6)   # factor-shaped
    assert res.diagnostics.cg_iters > 0
    ref = 6 * np.linalg.slogdet(ka)[1] + 5 * np.linalg.slogdet(kb)[1]
    assert abs(float(res.logabsdet) - ref) < 5 * float(res.sem) + 0.5


# --------------------------------------------------------- pad dtype fix

@pytest.mark.parametrize("dtype", [jnp.int32, jnp.float16, jnp.bfloat16])
def test_pad_to_multiple_preserves_dtype(dtype):
    from repro.core import pad_to_multiple
    a = jnp.ones((3, 3), dtype)
    out = pad_to_multiple(a, 4)
    assert out.dtype == dtype, (out.dtype, dtype)
    assert out.shape == (4, 4)
    assert int(out[3, 3]) == 1


# ----------------------------------------------------------- plan hints

def test_plan_hints_advertised_by_all_backends():
    from repro.estimators import (
        BatchedOperator, DenseOperator, KroneckerOperator, StencilOperator,
        ToeplitzOperator,
    )
    n = 36
    a = jnp.asarray(make_spd(n, 0))
    cases = {
        "dense": DenseOperator(a),
        "batched": BatchedOperator(a[None]),
        "kron": KroneckerOperator(a[:6, :6], a[:6, :6]),
        "toeplitz": ToeplitzOperator(a[0]),
        "stencil": StencilOperator((-1, 0, 1),
                                   jnp.asarray([-1.0, 2.5, -1.0]), n=n),
    }
    for name, op in cases.items():
        h = op.plan_hints()
        assert h.structure == name
        assert h.matvec_flops > 0
        assert h.device_count >= 1
    # structure beats dense on per-column cost
    assert (cases["stencil"].plan_hints().matvec_flops
            < cases["dense"].plan_hints().matvec_flops)
    assert cases["dense"].plan_hints().materializable
    assert not cases["kron"].plan_hints().materializable


# ------------------------------------------- bf16 route + tile autotuning

def test_plan_bf16_is_engine_route_not_storage_cast():
    """precision='bf16' selects the mixed-precision engine route: the
    spec keeps its storage dtype, the config carries precision='bf16',
    and the result stays within the engine's documented error model."""
    a = jnp.asarray(make_spd(64, 0), jnp.float32)
    p = repro.plan(a, method="exact", precision="bf16")
    assert p.method == "exact"
    assert p.spec.dtype == "float32"          # storage untouched
    assert p.config.precision == "bf16"
    r = p(a)
    s_ref, ld_ref = np.linalg.slogdet(np.asarray(a))
    assert float(r.sign) == s_ref
    assert abs(float(r.logabsdet) - ld_ref) / abs(ld_ref) < 5e-3


def test_plan_bf16_rejects_estimators_and_conflicts():
    a = jnp.asarray(make_spd(64, 1), jnp.float32)
    with pytest.raises(ValueError, match="mixed-precision"):
        repro.plan(a, method="slq", precision="bf16")
    with pytest.raises(ValueError, match="mixed-precision"):
        repro.plan(a, method="chebyshev", precision="bf16", degree=8)
    # an explicit matching config precision merges cleanly
    p = repro.plan(a, method="exact", precision="bf16",
                   config=ExactConfig(precision="bf16"))
    assert p.config.precision == "bf16"
    # and a bare config carries the route without the top-level kwarg
    p2 = repro.plan(a, method="exact", config=ExactConfig(precision="bf16"))
    assert p2.config.precision == "bf16"


def test_select_route_prices_bf16_separately():
    """bf16 restricts auto to the exact family and prices its GEMM term
    at the calibrated bf16 rate through the autotuner."""
    m, route = select_route((2048, 2048), precision="bf16")
    assert m == "exact" and route.precision == "bf16"
    # a size where native auto would hand off to estimators stays exact
    m2, route2 = select_route((8192, 8192), precision="bf16")
    assert m2 == "exact" and route2 is not None


def test_auto_runs_the_panel_k_it_priced():
    """The auto path must execute the autotuned panel width exact_cost
    modeled — cfg.k == route.panel_k, no fixed-32 drift."""
    n = 512
    m, route = select_route((n, n))
    assert m == "exact"
    a = jnp.asarray(make_spd(n, 2))
    p = repro.plan(a, method="auto")
    if p.method == "exact":
        assert p.config.k == route.panel_k
    from repro.kernels.autotune import resolved_panel_k
    assert route.panel_k == resolved_panel_k(
        n, itemsize=8, precision=None)


def test_explain_reports_precision_and_tiles():
    a = jnp.asarray(make_spd(64, 3), jnp.float32)
    text = repro.plan(a, method="exact", precision="bf16").explain()
    assert "precision: bf16" in text
    assert "tiles[" in text and "panel_k=" in text
    native = repro.plan(a, method="exact").explain()
    assert "precision: native" in native

"""repro.analysis — IR parser, checker passes, lint, audit drivers, CLI.

Three layers of proof:

  * **round-trip**: the committed HLO fixtures (serial / mesh / compiled
    lookahead lowerings) parse to byte-identical golden instruction
    tables, so a parser change that silently re-reads shapes or scopes
    shows up as a golden diff;
  * **mutation**: every registered pass FAILS on a deliberately broken
    program (an un-sliced tail all-gather, a callback left in obs-off
    HLO, a phantom lookahead stage, ...) and stays clean on the real
    lowering — a pass that cannot fail proves nothing;
  * **integration**: `LogdetPlan.audit()`, the allowlist round-trip, the
    AOT artifact audit, and the `python -m repro.analysis` exit codes.

Matrix sizes here (18/22) are unique to this file so module-level jit
caches never serve a stale trace from another test file.
"""
import dataclasses
import json
import pathlib
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro import obs
from repro.analysis import (
    AuditContext, AuditReport, Finding, PASSES, DEFAULT_PASS_IDS,
    apply_allowlist, audit_aot_dir, audit_artifact,
    expected_engine_stages, lint_source, lint_paths, load_allowlist,
    parse_module, run_passes,
)
from repro.analysis import DEFAULT_ALLOWLIST
from repro.analysis.ir import collective_payload_bytes
from repro.core.plan import plan as make_plan

FIXTURES = pathlib.Path(__file__).parent / "fixtures" / "hlo"
FIXTURE_NAMES = ("serial_rank1_stablehlo", "mesh_rank1_stablehlo",
                 "mesh_panel_lookahead_hlo")


@pytest.fixture(autouse=True)
def obs_off():
    """Tests below flip obs modes; never leak state into other files."""
    obs.configure("off")
    yield
    obs.configure("off")


def _fixture(name: str) -> str:
    return (FIXTURES / f"{name}.txt").read_text()


# =========================================================== parser: fixtures

@pytest.mark.parametrize("name", FIXTURE_NAMES)
def test_fixture_round_trip(name):
    """parse(fixture).dump() must equal the committed golden table."""
    got = parse_module(_fixture(name)).dump()
    want = (FIXTURES / f"{name}.golden.tsv").read_text()
    assert got == want, (
        f"{name}: parsed instruction table drifted from the golden — if "
        "the parser change is intentional, regenerate via "
        "tests/fixtures/hlo/regenerate.py and review the diff")


def test_fixture_dialects_and_content():
    serial = parse_module(_fixture("serial_rank1_stablehlo"))
    mesh = parse_module(_fixture("mesh_rank1_stablehlo"))
    hlo = parse_module(_fixture("mesh_panel_lookahead_hlo"))
    assert serial.dialect == mesh.dialect == "stablehlo"
    assert hlo.dialect == "hlo"
    # the mesh kernel's collectives survive normalization
    assert not serial.collectives()
    ops = {i.opcode.replace("-start", "") for i in mesh.collectives()}
    assert "all-gather" in ops and "all-reduce" in ops
    # compiled HLO carries the named-scope ancestry StableHLO lacks
    assert not serial.scope_names() and not mesh.scope_names()
    scopes = hlo.scope_names()
    for stage in ("engine.pivot", "engine.swap", "engine.update",
                  "engine.mesh_tail", "engine.broadcast",
                  "engine.lookahead_factor"):
        assert any(s == stage or s.endswith("/" + stage) or stage in s
                   for s in scopes) or stage in hlo.text, stage


# ========================================================= parser: edge cases

def test_hlo_tuple_of_tuple_and_token_shapes():
    txt = """HloModule t

ENTRY main {
  %p = f64[4]{0} parameter(0)
  %q = u1[2]{0} parameter(1)
  ROOT %t = ((f64[4]{0}, u1[2]{0}), token[]) tuple(%p, %q)
}
"""
    mod = parse_module(txt)
    assert mod.dialect == "hlo"
    t = mod.instructions[-1]
    assert t.opcode == "tuple"
    assert [s.dtype for s in t.result_shapes] == ["f64", "u1", "token"]
    # u1 occupies one unpacked byte, token none: 4*8 + 2*1 + 0
    assert t.result_bytes == 34
    assert t.operands == ("p", "q")


def test_mlir_token_dynamic_and_scalar_shapes():
    txt = """module @m {
  func.func public @main(%arg0: tensor<4x4xf32>) -> tensor<i1> {
    %0 = stablehlo.constant dense<true> : tensor<i1>
    %1 = stablehlo.create_token : !stablehlo.token
    %2 = stablehlo.custom_call @foo(%arg0) : (tensor<4x4xf32>) -> tensor<2x?xf64>
    return %0 : tensor<i1>
  }
}
"""
    mod = parse_module(txt)
    assert mod.dialect == "stablehlo"
    by_op = {i.opcode: i for i in mod.instructions}
    assert by_op["constant"].result_shapes[0].dtype == "pred"
    assert by_op["constant"].result_shapes[0].dims == ()
    assert by_op["create-token"].result_shapes[0].dtype == "token"
    assert by_op["create-token"].result_bytes == 0
    cc = by_op["custom-call"]
    assert cc.custom_call_target == "foo"
    assert cc.result_shapes[0].dims == (2, 0)       # dynamic dim -> 0
    assert cc.operand_shapes[0] .dims == (4, 4)


def test_custom_call_target_both_dialects():
    hlo = ('ENTRY e {\n  %c = f64[4]{0} custom-call(%a), '
           'custom_call_target="lapack_dgetrf_ffi"\n}\n')
    mlir = ('module @m {\n  %0 = stablehlo.custom_call @lapack_dgetrf_ffi'
            '(%arg0) : (tensor<4xf64>) -> tensor<4xf64>\n}\n')
    assert parse_module(hlo).custom_call_targets() == \
        {"lapack_dgetrf_ffi": 1}
    assert parse_module(mlir).custom_call_targets() == \
        {"lapack_dgetrf_ffi": 1}


def test_async_collective_pairs_count_once():
    txt = """HloModule a

ENTRY main {
  %p = f64[8]{0} parameter(0)
  %s = f64[16]{0} all-gather-start(%p), dimensions={0}
  ROOT %d = f64[16]{0} all-gather-done(%s)
}
"""
    mod = parse_module(txt)
    coll = mod.collectives()
    assert len(coll) == 1 and coll[0].opcode == "all-gather-start"
    # optimized HLO prints operands by NAME; payload resolution goes
    # through the symbol table, ring convention: received = out - in
    sizes = {i.name: i.result_bytes for i in mod.instructions}
    assert collective_payload_bytes(coll[0], sizes) == 8 * 8


def test_scope_ancestry_strips_wrappers():
    txt = ('  %x = f64[2]{0} add(%a, %b), metadata={op_name='
           '"jit(f)/jit(main)/while/body/engine.update/add"}\n')
    (i,) = parse_module("ENTRY e {\n" + txt + "}\n").instructions
    assert i.scopes == ("engine.update",)
    assert i.in_scope("engine.update")
    assert not i.in_scope("engine.pivot")


# ================================================== passes: mutation proofs

MESH_CTX = AuditContext(label="mesh|rank1 fwd", method="exact",
                        schedule="mesh", update="rank1", n=16, devices=1,
                        itemsize=8)


def test_payload_budget_clean_on_real_mesh_lowering():
    r = run_passes(_fixture("mesh_rank1_stablehlo"), MESH_CTX,
                   ("collective-payload-budget",))
    assert r.ok, r.summary()


def test_payload_budget_fails_on_unsliced_tail_gather():
    """Mutation: re-widen the tail all-gather to full (N,) rows — the
    pre-PR-8 wire bug — and the budget pass must trip."""
    broken = "\n".join(
        ln.replace("1x1xf64", "1x16xf64") if "all_gather" in ln else ln
        for ln in _fixture("mesh_rank1_stablehlo").splitlines())
    r = run_passes(broken, MESH_CTX, ("collective-payload-budget",))
    assert not r.ok
    assert any("all-gather" in f.message and "analytic bound" in f.message
               for f in r.errors)


def test_payload_budget_only_applies_to_mesh_schedule():
    broken = _fixture("mesh_rank1_stablehlo").replace("1x1xf64", "1x16xf64")
    ctx = dataclasses.replace(MESH_CTX, schedule="serial")
    assert run_passes(broken, ctx, ("collective-payload-budget",)).ok


def test_no_host_callback_catches_leaked_telemetry():
    """Mutation pair: the SAME chebyshev program lowered under obs=trace
    is an error for an obs-off context and legitimate for a trace one —
    this is tests/test_obs.py's grep as a reusable pass."""
    from repro.estimators.chebyshev import logdet_chebyshev

    obs.configure("trace")
    a = jax.ShapeDtypeStruct((18, 18), jnp.float64)
    txt = jax.jit(
        lambda x: logdet_chebyshev(x, degree=8, num_probes=4)[0]
    ).lower(a).as_text()
    obs.configure("off")

    leaked = run_passes(txt, AuditContext(method="chebyshev",
                                          obs_mode="off"),
                        ("no-host-callback",))
    assert not leaked.ok
    assert all(f.pass_id == "no-host-callback" for f in leaked.errors)
    legit = run_passes(txt, AuditContext(method="chebyshev",
                                         obs_mode="trace"),
                       ("no-host-callback",))
    assert legit.ok


def test_no_host_callback_flags_host_transfer_ops():
    txt = ("ENTRY e {\n  %o = token[] outfeed(%a, %t)\n}\n")
    r = run_passes(txt, AuditContext(obs_mode="off"), ("no-host-callback",))
    assert not r.ok and "outfeed" in r.errors[0].message


DENSE_HLO = """HloModule d

ENTRY main {
  %p = f64[16,16]{1,0} parameter(0)
  %f = (f64[16,16]{1,0}, s32[16]{0}) custom-call(%p), custom_call_target="lapack_dgetrf_ffi"
  ROOT %r = f64[16,16]{1,0} get-tuple-element(%f), index=0
}
"""


def test_no_dense_factorization_fails_on_lapack_call():
    r = run_passes(DENSE_HLO, AuditContext(method="slq", matrix_free=True),
                   ("no-dense-factorization",))
    assert not r.ok and "lapack_dgetrf_ffi" in r.errors[0].message


def test_no_dense_factorization_flags_structural_ops_too():
    txt = ("ENTRY e {\n  %c = f64[8,8]{1,0} cholesky(%a)\n"
           "  %s = f64[8,8]{1,0} triangular-solve(%c, %b), lower=true\n}\n")
    r = run_passes(txt, AuditContext(matrix_free=True),
                   ("no-dense-factorization",))
    assert len(r.errors) == 2


def test_no_dense_factorization_allows_exact_plans():
    """The exact route is ENTITLED to factorize — the pass keys off the
    matrix-free claim, so the same text is clean for an exact context."""
    r = run_passes(DENSE_HLO, AuditContext(method="exact",
                                           matrix_free=False),
                   ("no-dense-factorization",))
    assert r.ok


UPCAST_MLIR = """module @m {
  func.func public @main(%arg0: tensor<4x4xf32>) -> tensor<4x4xf64> {
    %0 = stablehlo.convert %arg0 : (tensor<4x4xf32>) -> tensor<4x4xf64>
    return %0 : tensor<4x4xf64>
  }
}
"""


def test_dtype_discipline_fails_on_silent_upcast():
    r = run_passes(UPCAST_MLIR, AuditContext(dtype="float32"),
                   ("dtype-discipline",))
    assert not r.ok and "upcast" in r.errors[0].message


def test_dtype_discipline_entitles_f64_plans():
    assert run_passes(UPCAST_MLIR, AuditContext(dtype="float64"),
                      ("dtype-discipline",)).ok


LA_CTX = AuditContext(label="mesh|panel|la fwd", method="exact",
                      schedule="mesh", update="panel", panel_k=4,
                      lookahead=True, n=16, devices=1)


def test_stage_coverage_clean_on_real_lookahead_program():
    r = run_passes(_fixture("mesh_panel_lookahead_hlo"), LA_CTX,
                   ("stage-coverage",))
    assert r.ok, r.summary()


def test_stage_coverage_fails_on_phantom_stage():
    """Mutation: claim lookahead=False against a program that DOES carry
    the pipelined stage — the inverse of the inert-flag bug."""
    ctx = dataclasses.replace(LA_CTX, lookahead=False)
    r = run_passes(_fixture("mesh_panel_lookahead_hlo"), ctx,
                   ("stage-coverage",))
    assert not r.ok
    assert any(f.where == "engine.lookahead_factor" and
               "forbid" in f.message for f in r.errors)


def test_stage_coverage_fails_on_missing_stages():
    """Mutation: a scope-free program (StableHLO never prints scopes)
    audited as a compiled serial engine must report every missing stage —
    the inert-flag bug class itself."""
    ctx = AuditContext(method="exact", schedule="serial", update="rank1",
                       n=16)
    r = run_passes(_fixture("serial_rank1_stablehlo"), ctx,
                   ("stage-coverage",))
    missing = sorted(f.where for f in r.errors)
    assert missing == ["engine.pivot", "engine.swap", "engine.update"]
    assert all("inert" in f.message for f in r.errors)


def test_stage_coverage_skips_estimators_without_explicit_map():
    r = run_passes(_fixture("serial_rank1_stablehlo"),
                   AuditContext(method="slq", n=16), ("stage-coverage",))
    assert r.ok and not r.findings


def test_expected_engine_stages_geometry():
    base = dict(method="exact", n=32, devices=1, panel_k=8)
    serial = expected_engine_stages(AuditContext(
        schedule="serial", update="rank1", **base))
    assert serial["engine.pivot"] and not serial["engine.mesh_tail"]
    assert not serial["engine.lookahead_factor"]
    # pipelined rank-1: pivot selection is subsumed into the lookahead
    # factorization — expecting a separate pivot phase would be wrong
    la_r1 = expected_engine_stages(AuditContext(
        schedule="mesh", update="rank1", lookahead=True, **base))
    assert la_r1["engine.lookahead_factor"] and not la_r1["engine.pivot"]
    # ...but at P >= 2 the (P, P) tail's serial condensation step brings
    # the pivot scope back
    la_r1_p8 = expected_engine_stages(AuditContext(
        schedule="mesh", update="rank1", lookahead=True, method="exact",
        n=32, devices=8, panel_k=8))
    assert la_r1_p8["engine.pivot"]
    # panel keeps its pivot; its loop only traces with > one full panel
    la_pn = expected_engine_stages(AuditContext(
        schedule="mesh", update="panel", lookahead=True, **base))
    assert la_pn["engine.lookahead_factor"] and la_pn["engine.pivot"]
    tiny = expected_engine_stages(AuditContext(
        schedule="mesh", update="panel", lookahead=True, method="exact",
        n=8, devices=1, panel_k=8))
    assert not tiny["engine.lookahead_factor"]


def test_exportable_custom_calls_policy():
    cb = ('module @m {\n  %0 = stablehlo.custom_call '
          '@xla_python_cpu_callback(%arg0) : (tensor<4xf64>) -> '
          'tensor<4xf64>\n}\n')
    legacy = cb.replace("xla_python_cpu_callback", "lapack_dgetrf")
    ffi = cb.replace("xla_python_cpu_callback", "lapack_dgetrf_ffi")
    exp = AuditContext(kind="export")
    # python callbacks and legacy opaque-pointer calls block the export;
    # registry-resolved *_ffi targets survive by name
    assert not run_passes(cb, exp, ("exportable-custom-calls",)).ok
    assert not run_passes(legacy, exp, ("exportable-custom-calls",)).ok
    assert run_passes(ffi, exp, ("exportable-custom-calls",)).ok
    # the pass only screens export lowerings
    assert run_passes(cb, AuditContext(kind="forward"),
                      ("exportable-custom-calls",)).ok


def test_exportable_custom_calls_accepts_mosaic_kernels():
    """A Pallas kernel lowered for the TPU is a `tpu_custom_call` whose
    compiled body rides in its backend config: it ships across processes.
    A python callback next to it still blocks the export."""
    # recorded (trimmed) from jax.jit(rank1_update_pallas).lower(...) on a
    # described v5e chip
    mosaic = (
        'module @jit_rank1_update_pallas {\n'
        '  func.func public @main(%arg0: tensor<256x512xf32>, %arg1: '
        'tensor<256xf32>, %arg2: tensor<512xf32>) -> (tensor<256x512xf32>) {\n'
        '    %0 = stablehlo.broadcast_in_dim %arg1, dims = [0] : '
        '(tensor<256xf32>) -> tensor<256x1xf32>\n'
        '    %1 = stablehlo.broadcast_in_dim %arg2, dims = [1] : '
        '(tensor<512xf32>) -> tensor<1x512xf32>\n'
        '    %2 = stablehlo.custom_call @tpu_custom_call(%arg0, %0, %1) '
        '{backend_config = "{\\"custom_call_config\\": {\\"body\\": '
        '\\"TUzvUgFNTElSMjIuMC4wZ2l0\\"}}", kernel_name = '
        '"rank1_update_kernel"} : (tensor<256x512xf32>, tensor<256x1xf32>, '
        'tensor<1x512xf32>) -> tensor<256x512xf32>\n'
        '    return %2 : tensor<256x512xf32>\n'
        '  }\n'
        '}\n')
    exp = AuditContext(kind="export")
    assert run_passes(mosaic, exp, ("exportable-custom-calls",)).ok
    cb = mosaic.replace(
        "    return %2",
        "    stablehlo.custom_call @xla_python_cpu_callback(%2) : "
        "(tensor<256x512xf32>) -> ()\n    return %2")
    assert not run_passes(cb, exp, ("exportable-custom-calls",)).ok


def test_run_passes_labels_and_registry():
    assert set(DEFAULT_PASS_IDS) <= set(PASSES)
    r = run_passes(DENSE_HLO, dataclasses.replace(MESH_CTX, label="lbl"),
                   ("no-dense-factorization",))
    assert r.passes_run == ["no-dense-factorization"]
    assert r.contexts == ["lbl"]


# ================================================== report + allowlist

def test_finding_ident_is_line_stable():
    a = Finding(pass_id="p", severity="error", message="m",
                where="src/x.py:12", context="lint")
    b = dataclasses.replace(a, where="src/x.py:99", message="other words")
    assert a.ident == b.ident == "p::lint::src/x.py"
    with pytest.raises(ValueError, match="severity"):
        Finding(pass_id="p", severity="fatal", message="m")


def test_report_json_round_trip():
    r = AuditReport(findings=[
        Finding(pass_id="p", severity="warning", message="m", where="w",
                context="c", code="snippet")],
        passes_run=["p"], contexts=["c"], meta={"k": 1})
    r2 = AuditReport.from_json(r.to_json())
    assert r2.findings == r.findings
    assert r2.passes_run == ["p"] and r2.meta == {"k": 1}
    assert json.loads(r.to_json())["ok"] is True   # warnings don't fail
    assert r.summary().startswith("audit: 1 finding(s)")


def test_allowlist_load_apply_and_reject(tmp_path):
    toml = tmp_path / "allow.toml"
    toml.write_text(
        '# waivers\n'
        '[[timing-no-block]]\n'
        'where = "src/launch/*.py:*"\n'
        'code = "run_cell"\n'
        'reason = "times compilation on purpose"\n')
    allow = load_allowlist(toml)
    assert list(allow) == ["timing-no-block"]

    hit = Finding(pass_id="timing-no-block", severity="error", message="m",
                  where="src/launch/dryrun.py:40", context="lint",
                  code="run_cell")
    miss_where = dataclasses.replace(hit, where="src/core/plan.py:40")
    miss_code = dataclasses.replace(hit, code="other_fn")
    report = apply_allowlist(
        AuditReport(findings=[hit, miss_where, miss_code]), allow)
    waived, kept_w, kept_c = report.findings
    assert waived.waived and waived.severity == "info" \
        and "[waived: times compilation on purpose]" in waived.message
    assert not kept_w.waived and kept_w.severity == "error"
    assert not kept_c.waived
    assert not report.ok          # the unmatched errors still fail

    # a reason-less waiver must refuse to load — never silently accept
    toml.write_text('[[x]]\nwhere = "*"\n')
    with pytest.raises(ValueError, match="reason"):
        load_allowlist(toml)
    # and so must a typo'd line (it would otherwise widen the waiver)
    toml.write_text('[[x]]\nreason = unquoted\n')
    with pytest.raises(ValueError, match="unparseable"):
        load_allowlist(toml)
    assert load_allowlist(tmp_path / "absent.toml") == {}


def test_committed_allowlist_is_valid():
    allow = load_allowlist(DEFAULT_ALLOWLIST)
    assert allow, "committed allowlist unexpectedly empty"
    for pid, entries in allow.items():
        assert pid in tuple(PASSES) + (
            "unused-config-kwarg", "implicit-dtype", "timing-no-block",
            "deprecated-route"), pid
        for e in entries:
            assert e["reason"].strip()


# ================================================================== lint

def test_lint_unused_config_kwarg():
    bad = ("def f(a, *, lookahead=False):\n"
           "    return a + 1\n")
    (f,) = lint_source(bad, "m.py", rules=("unused-config-kwarg",))
    assert "lookahead" in f.message and f.where == "m.py:1"
    good = ("def f(a, *, lookahead=False):\n"
            "    return a + int(lookahead)\n")
    assert not lint_source(good, "m.py", rules=("unused-config-kwarg",))
    # stubs and _-prefixed sinks are exempt by design
    stub = ("def f(a, *, lookahead=False):\n"
            "    raise NotImplementedError\n")
    sink = ("def f(a, *, _unused=False):\n"
            "    return a\n")
    assert not lint_source(stub, "m.py", rules=("unused-config-kwarg",))
    assert not lint_source(sink, "m.py", rules=("unused-config-kwarg",))


def test_lint_implicit_dtype():
    bad = "x = jnp.zeros((4, 4))\n"
    (f,) = lint_source(bad, "m.py", rules=("implicit-dtype",))
    assert "dtype" in f.message
    assert not lint_source("x = jnp.zeros((4, 4), dtype=a.dtype)\n",
                           "m.py", rules=("implicit-dtype",))
    assert not lint_source("x = jnp.zeros((4, 4), jnp.float32)\n",
                           "m.py", rules=("implicit-dtype",))
    assert not lint_source("x = jnp.zeros_like(a)\n",
                           "m.py", rules=("implicit-dtype",))
    assert not lint_source("x = np.zeros((4, 4))\n",
                           "m.py", rules=("implicit-dtype",))


def test_lint_timing_no_block():
    bad = ("def bench(f, a):\n"
           "    t0 = time.perf_counter()\n"
           "    f(a)\n"
           "    return time.perf_counter() - t0\n")
    (f,) = lint_source(bad, "m.py", rules=("timing-no-block",))
    assert "block_until_ready" in f.message
    good = bad.replace("    f(a)\n",
                       "    jax.block_until_ready(f(a))\n")
    assert not lint_source(good, "m.py", rules=("timing-no-block",))
    single = ("def stamp():\n"
              "    return time.perf_counter()\n")
    assert not lint_source(single, "m.py", rules=("timing-no-block",))


def test_lint_deprecated_route():
    bad = "r = slogdet(a, method='pmc')\n"
    (f,) = lint_source(bad, "launch/train.py",
                       rules=("deprecated-route",))
    assert "'pmc'" in f.message
    assert not lint_source(bad, "core/api.py",
                           rules=("deprecated-route",))
    assert not lint_source("r = slogdet(a, method='exact')\n",
                           "launch/train.py", rules=("deprecated-route",))


def test_lint_paths_reports_syntax_errors(tmp_path):
    (tmp_path / "ok.py").write_text("x = 1\n")
    (tmp_path / "broken.py").write_text("def f(:\n")
    report = lint_paths([tmp_path], root=tmp_path)
    assert [f.where for f in report.errors] == ["broken.py"]
    assert "unparseable" in report.errors[0].message


def test_repo_lint_is_clean_under_committed_allowlist():
    """Satellite (b)'s acceptance, as a test: the shipped source tree
    lints clean once the committed waivers apply."""
    import repro
    pkg = pathlib.Path(repro.__file__).resolve().parent
    report = apply_allowlist(
        lint_paths([pkg], root=pkg.parent),
        load_allowlist(DEFAULT_ALLOWLIST))
    assert report.ok, report.summary()


# ====================================================== audit integration

def test_plan_audit_serial_exact_clean():
    p = make_plan((18, 18), method="exact", schedule="serial",
                  update="rank1")
    report = p.audit()
    assert report.ok, report.summary()
    assert set(report.passes_run) == set(DEFAULT_PASS_IDS)
    assert report.contexts and "exact:serial/rank1" in report.contexts[0]


def test_plan_audit_mesh_lookahead_clean(mesh1):
    p = make_plan((22, 22), method="exact", schedule="mesh",
                  update="rank1", lookahead=True, mesh=mesh1)
    report = p.audit()
    assert report.ok, report.summary()


def test_plan_audit_estimator_with_grad_is_matrix_free():
    """tests/test_grad.py's dense-solve grep, via the shared pass — and
    include_grad covers the backward lowering too."""
    p = make_plan((18, 18), method="chebyshev", degree=8, num_probes=4,
                  seed=0, grad=True)
    report = p.audit(passes=["no-dense-factorization", "no-host-callback"],
                     include_grad=True)
    assert report.ok, report.summary()
    labels = report.contexts
    assert any("backward" in c for c in labels), labels


def test_plan_audit_pass_subset_respected():
    p = make_plan((18, 18), method="exact", schedule="serial")
    report = p.audit(passes=["no-host-callback"])
    assert report.passes_run == ["no-host-callback"]


def test_aot_artifact_audit_round_trip(tmp_path):
    p = make_plan((18, 18), method="exact", schedule="serial")
    path = str(tmp_path / "serial.reproplan")
    p.export(path)
    report = audit_artifact(path)
    assert report.ok, report.summary()
    assert "exportable-custom-calls" in report.passes_run
    assert "stage-coverage" not in report.passes_run   # post-fusion text

    dir_report = audit_aot_dir(tmp_path)
    assert dir_report.meta["artifacts"] == 1 and dir_report.ok


def test_aot_dir_audit_warns_when_empty(tmp_path):
    report = audit_aot_dir(tmp_path)
    assert report.ok                       # warning, not error
    assert any(f.pass_id == "aot-scan" for f in report.warnings)


# ================================================================== CLI

def _cli(argv):
    from repro.analysis.__main__ import main
    return main(argv)


def test_cli_lint_exit_codes(tmp_path, capsys):
    bad = tmp_path / "mod.py"
    bad.write_text("import jax.numpy as jnp\nx = jnp.zeros((4,))\n")
    assert _cli(["--lint", "--src", str(tmp_path)]) == 1
    out = capsys.readouterr().out
    assert "implicit-dtype" in out

    waiver = tmp_path / "allow.toml"
    waiver.write_text('[[implicit-dtype]]\nwhere = "*mod.py:*"\n'
                      'reason = "test fixture"\n')
    assert _cli(["--lint", "--src", str(tmp_path),
                 "--allowlist", str(waiver)]) == 0

    bad.write_text("x = 1\n")
    assert _cli(["--lint", "--src", str(tmp_path)]) == 0


def test_cli_json_artifact(tmp_path, capsys):
    src = tmp_path / "clean.py"
    src.write_text("x = 1\n")
    out = tmp_path / "report.json"
    assert _cli(["--lint", "--src", str(tmp_path),
                 "--json", str(out)]) == 0
    payload = json.loads(out.read_text())
    assert payload["ok"] is True and "passes_run" in payload
    capsys.readouterr()


def test_cli_rejects_unknown_pass(tmp_path):
    with pytest.raises(SystemExit):
        _cli(["--lint", "--src", str(tmp_path), "--passes", "nope"])


def test_cli_requires_a_mode():
    with pytest.raises(SystemExit):
        _cli([])

"""Checker passes over normalized IR modules.

Each pass proves one structural invariant of a lowered/compiled program —
invariants the paper's speedup claim (and three shipped bugs) hang on:

  ``no-dense-factorization``     matrix-free estimator forward/backward
                                 HLO contains no LAPACK custom calls,
                                 triangular solves, or dense inverses
  ``no-host-callback``           obs-off programs are callback-free (the
                                 telemetry gate really gates)
  ``collective-payload-budget``  every mesh-schedule collective moves at
                                 most its analytic payload — the tail
                                 all-gather is O(P^2) bytes, never O(N*P)
                                 (the PR 8 wire-bytes bug class)
  ``dtype-discipline``           no silent f32 -> f64 promotions in a
                                 sub-f64 program (the PR 4 upcast bug
                                 class; groundwork for bf16 condensation)
  ``stage-coverage``             each engine schedule's named scopes are
                                 present exactly when its flags say so
                                 (the PR 6-era inert ``lookahead=`` class)
  ``exportable-custom-calls``    AOT-exported programs carry no host
                                 function pointers (the serve/aot screen)

A pass is ``run(module, ctx) -> [Finding]`` registered under a stable id;
`run_passes` drives any subset.  Passes that need named-scope ancestry
declare ``wants="hlo"`` (scopes only print in compiled HLO text) — the
audit drivers compile when a wanting pass is selected, everything else
runs fine on lowered StableHLO.
"""
from __future__ import annotations

import re
from dataclasses import dataclass, replace
from typing import Callable, Dict, List, Optional, Tuple

from repro.analysis.ir import Instruction, Module, parse_module
from repro.analysis.report import AuditReport, Finding

__all__ = [
    "AuditContext", "PASSES", "register_pass", "run_passes",
    "expected_engine_stages", "SAFE_CUSTOM_CALLS", "DEFAULT_PASS_IDS",
]

# custom-call targets that are safe to ship across processes: layout /
# sharding markers XLA resolves internally, and Mosaic TPU kernels (the
# compiled Pallas kernel rides inside the call's backend config and the
# TPU runtime executes it, no host pointer involved).  Anything else —
# LAPACK handles in particular — is a host-function pointer that does
# NOT survive a process boundary and would segfault at call time.
SAFE_CUSTOM_CALLS = frozenset({"Sharding", "SPMDFullToShardShape",
                               "SPMDShardToFullShape", "tpu_custom_call"})

# LAPACK/BLAS factorization + solve custom-call families, plus the HLO
# ops XLA may lower them to.  Matching is substring on the custom-call
# target (lapack_dgetrf_ffi, blas_dtrsm, cusolver_getrf, ...).
_FACTORIZATION_TARGETS = ("getrf", "getrs", "potrf", "potrs", "trsm",
                          "gesdd", "gesvd", "geev", "sytrd", "geqrf",
                          "orgqr", "gehrd")
_FACTORIZATION_OPS = ("triangular-solve", "cholesky")

_CALLBACK_MARKERS = ("callback", "py_func", "host_func")
_HOST_TRANSFER_OPS = ("infeed", "outfeed", "send", "recv")


@dataclass(frozen=True)
class AuditContext:
    """What the audited program *is* — the pass inputs.

    ``label``     context string carried onto findings ("mesh|panel fwd")
    ``method``    resolved plan method ("exact", "chebyshev", "slq", ...)
    ``kind``      "forward" | "backward" | "export"
    ``schedule``/``update``/``lookahead``/``panel_k`` engine axes (exact)
    ``fused``     the engine's one-pass condensation steps are on (the
                  per-step pivot/swap/update scopes collapse into
                  ``engine.fused_step``)
    ``precision`` the engine's mixed-precision route (``"bf16"`` -> the
                  program MUST lower bf16-operand contractions; the
                  bf16 -> f32 accumulate converts are intentional)
    ``n``/``devices``/``itemsize``  payload-budget geometry
    ``dtype``     canonical dtype string of the planned computation
    ``obs_mode``  the REPRO_OBS mode the program was lowered under
    ``matrix_free``  the program claims to never materialize/factor A
    ``expected_stages``  named-scope presence map for stage-coverage;
                  None -> derived from the engine axes via
                  `expected_engine_stages`
    """
    label: str = ""
    method: str = ""
    kind: str = "forward"
    schedule: Optional[str] = None
    update: Optional[str] = None
    lookahead: bool = False
    panel_k: int = 32
    fused: bool = False
    precision: Optional[str] = None
    n: int = 0
    devices: int = 1
    itemsize: int = 8
    dtype: str = "float64"
    obs_mode: str = "off"
    matrix_free: bool = False
    expected_stages: Optional[Dict[str, bool]] = None


@dataclass
class Pass:
    id: str
    run: Callable[[Module, AuditContext], List[Finding]]
    description: str
    wants: str = "any"          # "hlo" (needs compiled text) | "any"


PASSES: Dict[str, Pass] = {}


def register_pass(pass_id: str, description: str, wants: str = "any"):
    def deco(fn):
        PASSES[pass_id] = Pass(id=pass_id, run=fn, description=description,
                               wants=wants)
        return fn
    return deco


def run_passes(module, ctx: AuditContext,
               pass_ids: Optional[Tuple[str, ...]] = None) -> AuditReport:
    """Run the selected passes over ``module`` (a `Module` or raw text)."""
    if not isinstance(module, Module):
        module = parse_module(module)
    report = AuditReport()
    for pid in (pass_ids if pass_ids is not None else tuple(PASSES)):
        p = PASSES[pid]
        findings = [replace(f, context=f.context or ctx.label)
                    for f in p.run(module, ctx)]
        report.findings.extend(findings)
        report.passes_run.append(pid)
    if ctx.label:
        report.contexts.append(ctx.label)
    return report


def _finding(pid: str, instr: Instruction, message: str,
             severity: str = "error") -> Finding:
    return Finding(pass_id=pid, severity=severity, message=message,
                   where=instr.name, code=instr.raw)


# --------------------------------------------------------------------------
# the passes
# --------------------------------------------------------------------------

@register_pass(
    "no-dense-factorization",
    "matrix-free programs contain no LAPACK custom calls, triangular "
    "solves, or dense factorizations (Han et al. estimator contract)")
def _no_dense_factorization(mod: Module, ctx: AuditContext) -> List[Finding]:
    if not (ctx.matrix_free or ctx.method in ("chebyshev", "slq")):
        return []
    out = []
    for i in mod.instructions:
        t = (i.custom_call_target or "").lower()
        if t and any(m in t for m in _FACTORIZATION_TARGETS):
            out.append(_finding(
                "no-dense-factorization", i,
                f"dense factorization custom call {i.custom_call_target!r} "
                f"in a matrix-free {ctx.method or 'estimator'} "
                f"{ctx.kind} program"))
        elif i.opcode in _FACTORIZATION_OPS:
            out.append(_finding(
                "no-dense-factorization", i,
                f"dense {i.opcode} op in a matrix-free "
                f"{ctx.method or 'estimator'} {ctx.kind} program"))
    return out


@register_pass(
    "no-host-callback",
    "programs lowered with observability off contain no host callbacks "
    "or host transfers (telemetry must be structurally absent, not idle)")
def _no_host_callback(mod: Module, ctx: AuditContext) -> List[Finding]:
    if ctx.obs_mode not in ("off", "metrics"):
        return []           # trace mode legitimately plants callbacks
    out = []
    for i in mod.instructions:
        t = (i.custom_call_target or "").lower()
        if t and any(m in t for m in _CALLBACK_MARKERS):
            out.append(_finding(
                "no-host-callback", i,
                f"host callback {i.custom_call_target!r} in a program "
                f"lowered with obs={ctx.obs_mode!r} — trace-gated "
                "telemetry leaked into the hot path"))
        elif i.opcode in _HOST_TRANSFER_OPS:
            out.append(_finding(
                "no-host-callback", i,
                f"host transfer op {i.opcode!r} in a program lowered "
                f"with obs={ctx.obs_mode!r}"))
    return out


def _collective_budgets(ctx: AuditContext) -> Dict[str, int]:
    """Analytic per-collective payload caps for a mesh-schedule engine
    program (bytes, max(operand, result) convention).

    The loop broadcasts move one pivot row / one ``(R, ls)`` K-panel —
    O(k * N) bytes; the tail all-gather moves the (P, P) live block plus
    a (P,) column — O(P^2).  Anything bigger means a shard of dead
    columns went over the wire (the pre-PR-8 8*N*P-byte tail bug).
    64 bytes of slop covers index/sign scalars riding along.
    """
    k = ctx.panel_k if ctx.update == "panel" else 1
    p, n, isz = max(ctx.devices, 1), ctx.n, ctx.itemsize
    return {
        "all-gather": isz * (p * max(p, k) + p) + 64,
        "all-reduce": isz * k * (n + 2 * k) + 64,
        "reduce-scatter": isz * k * (n + 2 * k) + 64,
        "all-to-all": isz * k * (n + 2 * k) + 64,
        "collective-permute": isz * k * (n + 2 * k) + 64,
    }


@register_pass(
    "collective-payload-budget",
    "every mesh-schedule collective payload stays within the route's "
    "analytic bound — the tail all-gather is O(P^2) bytes, never O(N*P)")
def _collective_payload_budget(mod: Module,
                               ctx: AuditContext) -> List[Finding]:
    if ctx.schedule != "mesh" or ctx.n <= 0:
        return []
    budgets = _collective_budgets(ctx)
    tail_budget = ctx.itemsize * (ctx.devices * ctx.devices
                                  + ctx.devices) + 64
    out = []
    for i in mod.collectives():
        base = i.opcode.replace("-start", "")
        payload = max(i.result_bytes, i.operand_bytes)
        budget = budgets.get(base)
        if i.in_scope("engine.mesh_tail"):
            # inside the tail everything is (P, P)-sized — even the
            # reduce of the combined slogdet parts
            budget = tail_budget
        if budget is None or payload <= budget:
            continue
        out.append(_finding(
            "collective-payload-budget", i,
            f"{base} moves {payload} bytes, analytic bound is {budget} "
            f"(n={ctx.n}, P={ctx.devices}, k={ctx.panel_k}, "
            f"update={ctx.update}) — a live-data slice is missing "
            "before the collective"))
    return out


_32BIT = ("float32", "bfloat16", "float16")


_CONTRACTION_OPS = ("dot", "dot-general", "multiply")


@register_pass(
    "dtype-discipline",
    "no silent f32/bf16/f16 -> f64 promotions in a sub-f64 program "
    "(padding helpers and dtype-less literals are the usual culprits); "
    "with precision='bf16' the program must actually lower bf16-operand "
    "contractions (quantize-then-upcast-before-multiply is inert)")
def _dtype_discipline(mod: Module, ctx: AuditContext) -> List[Finding]:
    out: List[Finding] = []
    if ctx.precision == "bf16":
        # the mixed-precision route quantizes GEMM/outer operands to
        # bf16 and accumulates in the buffer dtype.  bf16 -> f32
        # converts are therefore INTENTIONAL here (the accumulate leg,
        # not a silent upcast) — but at least one contraction must
        # consume bf16 operands, else the quantization was optimized
        # away / upcast before the multiply and the route is inert.
        has_bf16_mul = any(
            any(s.dtype == "bf16" for s in i.operand_shapes)
            for i in mod.instructions if i.opcode in _CONTRACTION_OPS)
        if not has_bf16_mul:
            out.append(Finding(
                pass_id="dtype-discipline", severity="error",
                message="bf16-silent-upcast: precision='bf16' program "
                        "lowers no bf16-operand contraction — operands "
                        "were promoted back to full precision before "
                        "the multiply, so the mixed-precision route is "
                        "inert",
                where="precision=bf16"))
    if ctx.dtype not in _32BIT:
        return out          # an f64 plan is entitled to f64 arithmetic
    for i in mod.instructions:
        if i.opcode != "convert":
            continue
        src = {s.dtype for s in i.operand_shapes}
        dst = {s.dtype for s in i.result_shapes}
        if "f64" in dst and src & {"f32", "bf16", "f16"}:
            out.append(_finding(
                "dtype-discipline", i,
                f"silent upcast {sorted(src & {'f32', 'bf16', 'f16'})} "
                f"-> f64 in a {ctx.dtype} program — a dtype-less literal "
                "or widening helper is promoting the pipeline"))
    if not out:
        # no explicit converts: any f64-valued instruction at all still
        # means the program left its precision (weaker signal -> warning)
        for i in mod.instructions:
            if i.opcode in ("constant", "parameter", "iota"):
                continue
            if any(s.dtype == "f64" for s in i.result_shapes):
                out.append(_finding(
                    "dtype-discipline", i,
                    f"f64-valued {i.opcode} in a {ctx.dtype} program",
                    severity="warning"))
                break
    return out


def expected_engine_stages(ctx: AuditContext) -> Dict[str, bool]:
    """Which `obs.stage` scopes MUST (True) / MUST NOT (False) appear in
    a compiled engine program, given its flags and geometry.

    Derived from the engine's structure (verified against lowerings of
    every schedule x update x lookahead variant):

      * ``engine.mesh_tail`` / ``engine.broadcast``: mesh schedule only.
      * ``engine.lookahead_factor``: iff ``lookahead=True`` AND the
        pipelined loop body actually traces — the panel variant's
        prologue/loop only exists when a device owns more than one full
        panel (``(n/P - 1) // k >= 1``); with fewer rows the kernel falls
        through to the shared rank-1 remainder path and the scope is
        legitimately absent.
      * ``engine.pivot``: every schedule's step — EXCEPT the pipelined
        rank-1 mesh kernel on ONE device, where pivot selection happens
        inside the early-applied next-row factorization and is
        deliberately scoped ``engine.lookahead_factor`` (there is no
        separate pivot phase to attribute time to).  At P >= 2 the
        (P, P) tail reduction runs the serial condensation redundantly
        on every device and its step re-introduces the pivot scope.
      * ``engine.swap``/``engine.update``: every schedule's step.
      * ``fused=True`` (serial/staged only): the per-step pivot/swap/
        update scopes collapse into ``engine.fused_step`` — the one-pass
        kernel selects the pivot, swaps, and updates inside a single
        scope, so the three per-step scopes MUST be absent and
        ``engine.fused_step`` MUST be present.

    The map is exact for the supported audit geometries (panel kernels
    keep a rank-1 remainder, i.e. ``(n/P - 1) % k != 0``); degenerate
    no-remainder layouts should pass ``expected_stages`` explicitly.
    """
    mesh = ctx.schedule == "mesh"
    la_traces = False
    if mesh and ctx.lookahead:
        if ctx.update == "panel":
            local = ctx.n // max(ctx.devices, 1)
            la_traces = (local - 1) // max(ctx.panel_k, 1) >= 1
        else:
            la_traces = ctx.n >= 2
    pivot_subsumed = (bool(la_traces) and ctx.update == "rank1"
                      and ctx.devices <= 1)
    fused = bool(ctx.fused) and not mesh
    return {
        "engine.pivot": not pivot_subsumed and not fused,
        "engine.swap": not fused,
        "engine.update": not fused,
        "engine.fused_step": fused,
        "engine.mesh_tail": mesh,
        "engine.broadcast": mesh,
        "engine.lookahead_factor": bool(la_traces),
    }


@register_pass(
    "stage-coverage",
    "each engine schedule's named scopes reach the compiled program "
    "exactly when its flags say so (no inert flags, no phantom stages)",
    wants="hlo")
def _stage_coverage(mod: Module, ctx: AuditContext) -> List[Finding]:
    if (ctx.method != "exact" and ctx.expected_stages is None) or ctx.n < 2:
        return []
    expected = ctx.expected_stages
    if expected is None:
        expected = expected_engine_stages(ctx)
    present = mod.scope_names()
    # scopes can be swallowed into fusion metadata the table misses; the
    # full dotted stage name in the raw text is the robust fallback
    out = []
    for stage, want in sorted(expected.items()):
        have = stage in present or stage in mod.text
        if want and not have:
            out.append(Finding(
                pass_id="stage-coverage", severity="error",
                message=f"stage {stage!r} missing from the compiled "
                        f"program although the route's flags require it "
                        f"(schedule={ctx.schedule}, update={ctx.update}, "
                        f"lookahead={ctx.lookahead}) — the flag is inert",
                where=stage))
        elif not want and have:
            out.append(Finding(
                pass_id="stage-coverage", severity="error",
                message=f"stage {stage!r} present although the route's "
                        f"flags forbid it (schedule={ctx.schedule}, "
                        f"update={ctx.update}, lookahead={ctx.lookahead})",
                where=stage))
    return out


def _export_safe_target(target: str) -> bool:
    """Can this custom-call target survive serialization?

    Safe: the XLA-internal sharding markers, and jaxlib's FFI targets
    (``lapack_*_ffi``, ...) — those resolve BY NAME through the process's
    FFI registry at load time, and the artifact fingerprint already pins
    the jax/jaxlib version providing them.  Unsafe: python callbacks
    (pointers to THIS process's interpreter state) and legacy non-FFI
    custom calls (opaque descriptor blobs baked at compile time).
    """
    if target in SAFE_CUSTOM_CALLS:
        return True
    low = target.lower()
    if any(m in low for m in _CALLBACK_MARKERS):
        return False
    return low.endswith("_ffi")


@register_pass(
    "exportable-custom-calls",
    "AOT-exported programs reference no host function pointers — only "
    "registry-resolved custom-call targets survive serialization")
def _exportable_custom_calls(mod: Module,
                             ctx: AuditContext) -> List[Finding]:
    if ctx.kind != "export":
        return []
    bad = sorted(t for t in set(mod.custom_call_targets())
                 if not _export_safe_target(t))
    if not bad:
        return []
    return [Finding(
        pass_id="exportable-custom-calls", severity="error",
        message=f"plan lowers to XLA custom calls {bad} (host function "
                "handles that do not survive serialization across "
                "processes); only pure-XLA and registry-resolved FFI "
                "programs are AOT-exportable",
        where=bad[0])]


# the default pass set audit drivers run (export screening is opt-in —
# it only makes sense with kind="export")
DEFAULT_PASS_IDS = ("no-dense-factorization", "no-host-callback",
                    "collective-payload-budget", "dtype-discipline",
                    "stage-coverage")

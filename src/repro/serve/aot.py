"""AOT plan export/import — compile once, serve forever.

A serving process must never pay trace or compile time at request time.
`export_plan` takes a *compiled* `LogdetPlan`, lowers a fresh forward at
the plan's exact avals, compiles it, and serializes the XLA executable
(``jax.experimental.serialize_executable``) together with a JSON header
describing what the artifact is for.  `load_plan` reverses it: the
returned `LogdetPlan` wraps the deserialized executable directly — its
``trace_count`` stays 0 forever and the ``plan.traces`` metric never
moves, which is the property tests/test_serve.py asserts.

File layout (single file, magic-tagged)::

    REPROPLAN\\x00 | u32 header_len | header JSON | pickle(payload, trees)

The header carries a format version, the problem spec, the typed config,
and a **device fingerprint** (platform, device kind, device count, jax
version, x64 state).  XLA executables are only valid on the hardware and
runtime they were compiled for; `load_plan` refuses a mismatch with a
field-by-field error instead of letting XLA segfault on a stale binary.

What can be exported: any plan with ``plan.compiled`` — the serial/staged
exact engine routes (single or batched) and the single-device dense
estimators.  Mesh-schedule and operator plans compose eagerly over cached
inner executables and raise `PlanExportError`.  Exported programs are
additionally screened through the `repro.analysis` pass framework
(``exportable-custom-calls`` + ``no-host-callback``): LAPACK handles and
host callbacks do not survive process boundaries; the repro engine and
estimators lower to pure XLA ops, so this screen only trips on foreign
code or telemetry left enabled.

AOT-loaded plans are execute-only: they cannot be traced into an outer
``jit``/``grad`` (the executable is a binary, not a jaxpr) and
``value_and_grad`` raises — re-plan locally when you need gradients.
"""
from __future__ import annotations

import dataclasses
import json
import pickle
import struct
import time
from typing import Any, Dict, Optional, Tuple

import numpy as np
import jax
import jax.numpy as jnp

from repro import obs
from repro.core.configs import (
    ESTIMATOR_METHODS, config_from_dict, config_to_dict,
)
from repro.core.result import Diagnostics

__all__ = [
    "PLAN_FORMAT", "PlanExportError", "PlanFingerprintError",
    "device_fingerprint", "export_plan", "load_plan", "read_header",
]

PLAN_FORMAT = 1
_MAGIC = b"REPROPLAN\x00"

class PlanExportError(ValueError):
    """The plan cannot be exported as an AOT artifact."""


class PlanFingerprintError(ValueError):
    """The artifact was compiled for a different device/runtime."""


def device_fingerprint() -> Dict[str, Any]:
    """What an XLA executable is pinned to in this process."""
    dev = jax.devices()[0]
    return {
        "platform": dev.platform,
        "device_kind": dev.device_kind,
        "device_count": jax.device_count(),
        "jax_version": jax.__version__,
        "x64": bool(jax.config.jax_enable_x64),
    }


def _screen_export(lowered, plan) -> None:
    """Refuse programs whose executables cannot cross a process boundary.

    Runs the shared `repro.analysis` pass framework over the lowering
    with ``kind="export"``: custom-call targets outside
    `repro.analysis.passes.SAFE_CUSTOM_CALLS` (LAPACK handles are host
    function pointers that do not survive serialization) and leaked host
    callbacks both block the export.
    """
    from repro.analysis.audit import context_for
    from repro.analysis.passes import run_passes

    ctx = dataclasses.replace(context_for(plan, kind="export"),
                              obs_mode="off")
    report = run_passes(lowered.as_text(), ctx,
                        ("exportable-custom-calls", "no-host-callback"))
    if not report.ok:
        raise PlanExportError("; ".join(
            f.message for f in report.errors))


def export_plan(plan, path: str) -> str:
    """Serialize ``plan``'s compiled forward to ``path``; returns ``path``.

    The artifact replays bit-identically in any process whose device
    fingerprint matches (`load_plan` enforces this).  The live plan's
    trace counters are untouched — export lowers a fresh forward from the
    plan's spec/config rather than re-tracing the plan's own executable.
    """
    # imported here, not at module top: core.plan lazily imports THIS
    # module for LogdetPlan.export
    from repro.core.plan import _build_forward
    from jax.experimental.serialize_executable import serialize

    if plan.spec.kind == "operator":
        raise PlanExportError(
            "operator plans compose the operator's own executables and "
            "cannot be exported; export a dense/batched plan instead")
    if not plan.compiled:
        raise PlanExportError(
            "only compiled plans are exportable; mesh-schedule and "
            "sharded-estimator plans compose eager shard_map executables "
            f"(plan: method={plan.method!r}, mesh={plan.mesh is not None})")

    spec, method, cfg = plan.spec, plan.method, plan.config
    dtype = jnp.dtype(spec.dtype)
    shape = ((spec.n, spec.n) if spec.batch is None
             else (spec.batch, spec.n, spec.n))
    a_aval = jax.ShapeDtypeStruct(shape, dtype)

    # a fresh forward with a scratch trace log: exporting must not mark a
    # retrace on the live plan
    fwd, compiled_flag, _padded_n = _build_forward(
        spec, method, cfg, None, plan.axis_name, dtype, trace_log=[])
    assert compiled_flag, "compiled plan rebuilt as eager"

    key_info: Optional[Dict[str, Any]] = None
    with obs.span("serve.aot.export", method=method, n=spec.n):
        if method in ESTIMATOR_METHODS:
            k0 = np.asarray(jax.random.PRNGKey(getattr(cfg, "seed", 0)))
            key_info = {"shape": list(k0.shape), "dtype": str(k0.dtype)}
            k_aval = jax.ShapeDtypeStruct(k0.shape, k0.dtype)
            lowered = jax.jit(lambda a, key: fwd(a, key=key)) \
                .lower(a_aval, k_aval)
        else:
            lowered = jax.jit(lambda a: fwd(a)).lower(a_aval)
        _screen_export(lowered, plan)
        payload, in_tree, out_tree = serialize(lowered.compile())

    header = {
        "format": PLAN_FORMAT,
        "method": method,
        "spec": dataclasses.asdict(spec),
        "config": config_to_dict(cfg),
        "key": key_info,
        "padded_n": plan.diagnostics.padded_n,
        "fingerprint": device_fingerprint(),
        "created_unix": time.time(),
    }
    head = json.dumps(header, sort_keys=True).encode()
    with open(path, "wb") as f:
        f.write(_MAGIC)
        f.write(struct.pack("<I", len(head)))
        f.write(head)
        pickle.dump((payload, in_tree, out_tree), f)
    obs.inc("serve.aot.exports", method=method)
    return path


def _read(path: str) -> Tuple[Dict[str, Any], bytes]:
    with open(path, "rb") as f:
        magic = f.read(len(_MAGIC))
        if magic != _MAGIC:
            raise PlanExportError(
                f"{path}: not a repro plan artifact (bad magic)")
        (hlen,) = struct.unpack("<I", f.read(4))
        header = json.loads(f.read(hlen))
        blob = f.read()
    if header.get("format") != PLAN_FORMAT:
        raise PlanExportError(
            f"{path}: plan format {header.get('format')!r} not supported "
            f"(this build reads format {PLAN_FORMAT})")
    return header, blob


def read_header(path: str) -> Dict[str, Any]:
    """Parse and return the JSON header only (no executable load)."""
    return _read(path)[0]


def check_fingerprint(header: Dict[str, Any], path: str) -> None:
    want, have = header["fingerprint"], device_fingerprint()
    bad = [f"{k}: artifact={want.get(k)!r} process={have.get(k)!r}"
           for k in sorted(set(want) | set(have))
           if want.get(k) != have.get(k)]
    if bad:
        raise PlanFingerprintError(
            f"{path}: plan was compiled for a different device/runtime — "
            + "; ".join(bad)
            + ". Re-export on this host (plan.export) or serve on the "
            "hardware the artifact was built for.")


def _is_tracer(x) -> bool:
    return isinstance(x, jax.core.Tracer)


def load_plan(path: str, *, validate: bool = True,
              check_device: bool = True):
    """Load an exported plan — zero traces, zero compiles, ever.

    Returns a `LogdetPlan` whose forward is the deserialized executable.
    ``check_device=False`` skips the fingerprint check (only for tests
    that tamper with headers; a real mismatch can crash the process).
    """
    from repro.core.plan import LogdetPlan, ProblemSpec, _flops_est
    from jax.experimental.serialize_executable import deserialize_and_load

    header, blob = _read(path)
    if check_device:
        check_fingerprint(header, path)

    spec = ProblemSpec(**header["spec"])
    try:
        cfg = config_from_dict(header["config"])
    except ValueError as exc:
        raise PlanExportError(f"{path}: {exc}") from None
    method = header["method"]

    with obs.span("serve.aot.load", method=method, n=spec.n):
        payload, in_tree, out_tree = pickle.loads(blob)
        executable = deserialize_and_load(payload, in_tree, out_tree)

    dtype = jnp.dtype(spec.dtype)
    estimator = method in ESTIMATOR_METHODS
    if estimator:
        default_key = np.asarray(
            jax.random.PRNGKey(getattr(cfg, "seed", 0)))

    def fwd(a, key=None, probes=None, lmin=None, lmax=None):
        if any(_is_tracer(v) for v in (a, key, probes, lmin, lmax)):
            raise TypeError(
                "AOT-loaded plans are execute-only: the forward is a "
                "deserialized XLA binary and cannot be traced into jit/"
                "grad/vmap — build a local plan with repro.plan instead")
        if probes is not None or lmin is not None or lmax is not None:
            raise TypeError(
                "AOT-loaded plans accept `key` only; probes and spectral "
                "bounds were baked in (or resolved) at export time")
        if getattr(a, "dtype", None) != dtype:
            a = jnp.asarray(a, dtype)
        if not estimator:
            if key is not None:
                raise TypeError(
                    f"exact method {method!r} takes no key")
            return executable(a)
        k = default_key if key is None else key
        return executable(a, k)

    cols, flops = _flops_est(method, spec, cfg, 1)
    plan = LogdetPlan(
        spec=spec, method=method, config=cfg, mesh=None, grad=False,
        validate=validate, compiled=True,
        diagnostics=Diagnostics(matvec_cols=cols, flops_est=flops,
                                padded_n=header.get("padded_n", spec.n),
                                device_count=1),
        _fwd=fwd, _trace_log=[])
    plan._cache["aot_path"] = path
    plan._cache["vag"] = _vag_unavailable
    obs.inc("serve.aot.loads", method=method)
    return plan


def _vag_unavailable(x, key=None):
    raise NotImplementedError(
        "AOT-loaded plans are execute-only; gradients need a locally "
        "built plan (repro.plan(..., grad=True))")

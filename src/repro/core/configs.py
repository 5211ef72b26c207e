"""Typed per-method configuration for the plan API.

Each log-determinant method family gets one frozen dataclass holding every
knob it understands, validated at construction — replacing the flat
``**kwargs`` namespace the string API used to thread through dispatch.  A
config is hashable (all fields are static Python values), so it can key
the plan cache: two ``repro.plan`` calls with equal specs and equal
configs share one compiled executable.

Runtime *arrays* — PRNG ``key``, pre-drawn ``probes``, traced spectral
bounds — are deliberately NOT config fields: they are execution inputs,
passed to the plan call itself, so changing them never invalidates a
compiled plan.

  ExactConfig      mc / mc_staged / mc_blocked / ge / pmc / pmc_blocked /
                   pge / plu — panel width ``k``, block-cyclic tile ``nb``
  ChebyshevConfig  stochastic Chebyshev (Han et al.): degree, probe budget,
                   optional spectral bounds, backward-CG knobs
  SLQConfig        stochastic Lanczos quadrature (Ubaru et al.): Lanczos
                   steps, probe budget, backward-CG knobs

`config_for` maps legacy keyword soup onto the right dataclass and is the
single place the shim layer (`repro.core.api`) translates old calls.
"""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass
from typing import Optional, Union

from repro.core.engine import (
    BACKENDS as _ENGINE_BACKENDS, EngineConfig, LEGACY_ROUTES,
    SCHEDULES as _ENGINE_SCHEDULES, UPDATES as _ENGINE_UPDATES,
)

__all__ = [
    "ExactConfig", "ChebyshevConfig", "SLQConfig", "LogdetConfig",
    "EngineConfig", "config_for", "config_to_dict", "config_from_dict",
    "EXACT_METHODS", "ESTIMATOR_METHODS",
    "PARALLEL_METHODS", "METHODS", "LEGACY_EXACT_ROUTES",
]

# "exact" is the unified condensation engine (schedule x update x backend,
# see repro.core.engine); the five legacy route strings are deprecated
# aliases for fixed engine tuples; ge/pge/plu are the comparison baselines
LEGACY_EXACT_ROUTES = tuple(LEGACY_ROUTES)
EXACT_METHODS = ("exact",) + LEGACY_EXACT_ROUTES + ("ge", "pge", "plu")
PARALLEL_METHODS = ("pmc", "pmc_blocked", "pge", "plu")
ESTIMATOR_METHODS = ("chebyshev", "slq")
METHODS = EXACT_METHODS + ESTIMATOR_METHODS

# every keyword the estimator family understands — used to phrase the
# "exact method got estimator keywords" error precisely
_ESTIMATOR_KW = frozenset({
    "num_probes", "degree", "num_steps", "seed", "lmin", "lmax",
    "probe_kind", "grad_cg_tol", "grad_cg_maxiter", "key", "probes",
})


def _require(cond: bool, msg: str):
    if not cond:
        raise ValueError(msg)


@dataclass(frozen=True)
class ExactConfig:
    """Knobs of the exact O(N^3) condensation / elimination family.

    The condensation engine's three axes (``method="exact"``):

    ``schedule`` — "serial" | "staged" | "mesh"; ``None`` resolves at plan
                   time ("mesh" when a mesh is supplied, else "staged").
    ``update``   — "rank1" | "panel"; ``None`` resolves to "rank1".
    ``backend``  — "auto" | "xla" | "pallas" kernel backend.
    ``k``        — panel width of the rank-K update.
    ``shrink``/``min_size`` — staged-schedule geometry.
    ``lookahead`` — mesh-only: pipeline the next pivot row / panel so its
                   broadcast overlaps the current bulk update
                   (bit-identical results; see `engine.EngineConfig`).
                   Requires ``schedule`` unset (mesh resolves when a mesh
                   is present) or explicitly ``"mesh"``.
    ``fused``    — serial/staged-only: one-pass rank-1 condensation steps
                   (bit-identical results; see `engine.EngineConfig`).
    ``precision`` — ``None`` (native) or ``"bf16"``: quantize GEMM /
                   outer-product operands to bfloat16; the buffer and
                   every sign/parity/log accumulator stay in the input
                   dtype (error model in docs/api.md).

    Baseline-only knob: ``nb`` — block-cyclic tile size of the
    ScaLAPACK-style LU (``plu``).  Methods that do not use a knob ignore
    it, so one config class serves every exact method.
    """
    k: int = 32
    nb: int = 1
    schedule: Optional[str] = None
    update: Optional[str] = None
    backend: str = "auto"
    shrink: float = 0.75
    min_size: int = 64
    lookahead: bool = False
    fused: bool = False
    precision: Optional[str] = None

    def __post_init__(self):
        _require(int(self.k) >= 1, f"k must be >= 1, got {self.k}")
        _require(int(self.nb) >= 1, f"nb must be >= 1, got {self.nb}")
        _require(self.schedule is None or self.schedule in _ENGINE_SCHEDULES,
                 f"unknown schedule {self.schedule!r}; "
                 f"one of {_ENGINE_SCHEDULES}")
        _require(self.update is None or self.update in _ENGINE_UPDATES,
                 f"unknown update {self.update!r}; one of {_ENGINE_UPDATES}")
        _require(self.backend in _ENGINE_BACKENDS,
                 f"unknown backend {self.backend!r}; "
                 f"one of {_ENGINE_BACKENDS}")
        _require(0.0 < float(self.shrink) < 1.0,
                 f"shrink must be in (0, 1), got {self.shrink}")
        _require(int(self.min_size) >= 2,
                 f"min_size must be >= 2, got {self.min_size}")
        _require(not self.lookahead or self.schedule in (None, "mesh"),
                 "lookahead pipelines the mesh schedule's broadcast; it "
                 f"requires schedule='mesh' (or unset), got "
                 f"{self.schedule!r}")
        _require(not self.fused or self.schedule != "mesh",
                 "fused one-pass steps are a serial/staged optimization; "
                 "the mesh schedule pipelines via lookahead instead")
        _require(self.precision in (None, "bf16"),
                 f"unknown precision {self.precision!r}; "
                 "one of (None, 'bf16')")

    def resolved(self, *, mesh_present: bool = False) -> "ExactConfig":
        """Pin the engine axes (plan-time resolution of the defaults).

        ``backend="auto"`` is pinned to the concrete process backend here
        so the plan/kernel caches key on what was actually built — a
        later REPRO_KERNEL_BACKEND flip misses the cache instead of
        being served a stale executable.
        """
        from repro.core.engine import resolve_backend
        sched = self.schedule or ("mesh" if mesh_present else "staged")
        if self.lookahead and sched != "mesh":
            raise ValueError(
                "lookahead requires the mesh schedule: pass a mesh (or "
                f"schedule='mesh'); resolution chose {sched!r}")
        if self.fused and sched == "mesh":
            raise ValueError(
                "fused one-pass steps are a serial/staged optimization "
                "(the mesh schedule pipelines via lookahead); drop the "
                "mesh or pass schedule='serial'/'staged' explicitly")
        upd = self.update or "rank1"
        backend = resolve_backend(self.backend)
        if (sched == self.schedule and upd == self.update
                and backend == self.backend):
            return self
        return dataclasses.replace(self, schedule=sched, update=upd,
                                   backend=backend)

    def engine_config(self) -> EngineConfig:
        """The `EngineConfig` this config denotes (axes must be resolved)."""
        _require(self.schedule is not None and self.update is not None,
                 "engine axes unresolved; call .resolved() first")
        return EngineConfig(schedule=self.schedule, update=self.update,
                            panel_k=self.k, backend=self.backend,
                            shrink=self.shrink, min_size=self.min_size,
                            lookahead=self.lookahead, fused=self.fused,
                            precision=self.precision)


@dataclass(frozen=True)
class ChebyshevConfig:
    """Knobs of the stochastic Chebyshev estimator (SPD input).

    ``degree``       expansion degree — truncation bias decays ~rho^-degree
    ``num_probes``   Hutchinson probes — noise shrinks ~1/sqrt(num_probes)
    ``probe_kind``   "rademacher" (variance-minimizing) or "gaussian"
    ``seed``         default PRNG seed when no key is passed at call time
    ``lmin``/``lmax`` spectral bounds; None -> power-iteration bracket
    ``grad_cg_tol``/``grad_cg_maxiter`` backward-pass CG solve control
    """
    degree: int = 64
    num_probes: int = 32
    probe_kind: str = "rademacher"
    seed: int = 0
    lmin: Optional[float] = None
    lmax: Optional[float] = None
    grad_cg_tol: float = 1e-8
    grad_cg_maxiter: Optional[int] = None

    def __post_init__(self):
        _require(int(self.degree) >= 1,
                 f"degree must be >= 1, got {self.degree}")
        _require(int(self.num_probes) >= 1,
                 f"num_probes must be >= 1, got {self.num_probes}")
        _require(self.probe_kind in ("rademacher", "gaussian"),
                 f"unknown probe_kind {self.probe_kind!r}")
        for name in ("lmin", "lmax"):
            v = getattr(self, name)
            if v is None:
                continue
            try:
                # coerce 0-d arrays / np scalars to a hashable float —
                # configs key the plan cache
                object.__setattr__(self, name, float(v))
            except Exception:
                # traced bounds cannot be static config: they are
                # execution inputs — plan_(a, lmin=..., lmax=...)
                raise TypeError(
                    f"{name} in the config must be a static scalar; pass "
                    f"traced bounds at execution time instead "
                    f"(plan(a, {name}=...))") from None
        if self.lmin is not None and self.lmax is not None:
            _require(float(self.lmax) > float(self.lmin),
                     f"need lmax > lmin, got [{self.lmin}, {self.lmax}]")

    def estimator_kwargs(self) -> dict:
        """Keywords for `repro.estimators.estimate_logdet`."""
        kw = dict(degree=self.degree, num_probes=self.num_probes,
                  probe_kind=self.probe_kind, seed=self.seed,
                  grad_cg_tol=self.grad_cg_tol,
                  grad_cg_maxiter=self.grad_cg_maxiter)
        if self.lmin is not None:
            kw["lmin"] = self.lmin
        if self.lmax is not None:
            kw["lmax"] = self.lmax
        return kw


@dataclass(frozen=True)
class SLQConfig:
    """Knobs of the stochastic Lanczos quadrature estimator (SPD input).

    ``num_steps``    Lanczos steps — quadrature error ~exp(-4m/sqrt(cond))
    ``num_probes``   Hutchinson probes — noise shrinks ~1/sqrt(num_probes)
    ``seed``         default PRNG seed when no key is passed at call time
    ``grad_cg_tol``/``grad_cg_maxiter`` backward-pass CG solve control
    """
    num_steps: int = 25
    num_probes: int = 32
    seed: int = 0
    grad_cg_tol: float = 1e-8
    grad_cg_maxiter: Optional[int] = None

    def __post_init__(self):
        _require(int(self.num_steps) >= 1,
                 f"num_steps must be >= 1, got {self.num_steps}")
        _require(int(self.num_probes) >= 1,
                 f"num_probes must be >= 1, got {self.num_probes}")

    def estimator_kwargs(self) -> dict:
        """Keywords for `repro.estimators.estimate_logdet`."""
        return dict(num_steps=self.num_steps, num_probes=self.num_probes,
                    seed=self.seed, grad_cg_tol=self.grad_cg_tol,
                    grad_cg_maxiter=self.grad_cg_maxiter)


LogdetConfig = Union[ExactConfig, ChebyshevConfig, SLQConfig]

_CONFIG_CLS = {
    **{m: ExactConfig for m in EXACT_METHODS},
    "chebyshev": ChebyshevConfig,
    "slq": SLQConfig,
}


def config_cls_for(method: str):
    """The config dataclass governing ``method`` (ValueError if unknown)."""
    try:
        return _CONFIG_CLS[method]
    except KeyError:
        raise ValueError(
            f"unknown method {method!r}; choose from {METHODS}") from None


def config_for(method: str, kwargs: dict) -> LogdetConfig:
    """Build the typed config for ``method`` from legacy-style keywords.

    Exact methods reject estimator keywords with a TypeError (matching the
    historical string-API behavior); every family rejects keywords it does
    not define, by name, so typos fail loudly instead of being swallowed
    by a ``**kwargs`` sink.
    """
    cls = config_cls_for(method)
    names = {f.name for f in dataclasses.fields(cls)}
    extra = set(kwargs) - names
    if extra:
        if cls is ExactConfig and extra & _ESTIMATOR_KW:
            raise TypeError(f"method {method!r} takes no estimator "
                            f"keywords: {sorted(extra)}")
        raise TypeError(
            f"unknown keywords for method {method!r}: {sorted(extra)} "
            f"(valid: {sorted(names)})")
    return cls(**kwargs)


def filter_for_method(method: str, kwargs: dict) -> dict:
    """Keep the keywords the resolved method's family understands.

    Used by ``method="auto"``: the caller cannot know the family in
    advance, so knobs for the *other* family are dropped (passing
    ``num_probes`` must not crash a call the cost model resolved to exact
    condensation — exact is at least as accurate).  Keywords no family
    defines still raise, so typos fail loudly.
    """
    known = set().union(*({f.name for f in dataclasses.fields(c)}
                          for c in (ExactConfig, ChebyshevConfig,
                                    SLQConfig)))
    unknown = set(kwargs) - known
    if unknown:
        raise TypeError(
            f"unknown keywords: {sorted(unknown)} (no method understands "
            f"them; valid names: {sorted(known)})")
    names = {f.name for f in dataclasses.fields(config_cls_for(method))}
    return {k: v for k, v in kwargs.items() if k in names}


def config_to_dict(config: LogdetConfig) -> dict:
    """JSON-safe dict encoding of a typed config, tagged with its class.

    Inverse of `config_from_dict`; this is the on-disk form the AOT plan
    header (repro.serve.aot) carries, so an exported artifact records the
    exact knobs it was compiled with.
    """
    if not isinstance(config, (ExactConfig, ChebyshevConfig, SLQConfig)):
        raise TypeError(f"not a logdet config: {type(config).__name__}")
    return {"type": type(config).__name__, **dataclasses.asdict(config)}


def config_from_dict(d: dict) -> LogdetConfig:
    """Rebuild a typed config from `config_to_dict` output (validating)."""
    d = dict(d)
    name = d.pop("type", None)
    cls = {"ExactConfig": ExactConfig, "ChebyshevConfig": ChebyshevConfig,
           "SLQConfig": SLQConfig}.get(name)
    if cls is None:
        raise ValueError(f"unknown config type {name!r}")
    names = {f.name for f in dataclasses.fields(cls)}
    extra = set(d) - names
    if extra:
        raise ValueError(
            f"unknown fields for {name}: {sorted(extra)} — artifact from "
            "a newer build?")
    return cls(**d)


def validate_config(method: str, config: LogdetConfig) -> LogdetConfig:
    """Check that an explicit config instance matches ``method``'s family."""
    cls = config_cls_for(method)
    if not isinstance(config, cls):
        raise TypeError(
            f"method {method!r} needs a {cls.__name__}, "
            f"got {type(config).__name__}")
    return config

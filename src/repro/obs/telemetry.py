"""Convergence telemetry: host-side streams fed by ``jax.debug.callback``.

The estimators run under jit; their per-probe / per-iteration state
lives on device.  When ``REPRO_OBS=trace`` *at trace time*, the
estimator modules stage a ``jax.debug.callback`` that ships small
arrays (a running-`sem` curve, one CG residual per iteration) to a
process-wide buffer here.  The gate is checked while tracing, so with
obs off **nothing is staged** — the lowered HLO contains no host
callbacks at all (asserted in tests/test_obs.py), which is how the
<1%-overhead-when-disabled budget is met.

Two emit shapes:

:func:`emit_curve`
    One callback per execution carrying a whole 1-D curve (e.g. the
    running sem over probes 1..k, computed vectorized on device via
    :func:`running_sem`).

:func:`emit_point`
    One callback per loop iteration carrying ``(step, value)`` — used
    inside ``lax.while_loop`` bodies (CG residual).  Callbacks may
    arrive out of order; :func:`drain` sorts by step.

Callbacks are asynchronous: call :func:`flush` (→ ``jax.effects_barrier``)
before draining.
"""
from __future__ import annotations

import functools
import math
import threading
from typing import Any, Dict, List

import jax
import jax.numpy as jnp
import numpy as np

from repro.obs import config as _cfg

_lock = threading.Lock()
_curves: Dict[str, List[float]] = {}
_points: Dict[str, List[tuple]] = {}


def enabled() -> bool:
    """True when telemetry callbacks should be staged (trace mode)."""
    return _cfg.trace_enabled()


# ---------------------------------------------------------------- sinks
def _sink_curve(name: str, values: Any) -> None:
    vals = [float(v) for v in np.asarray(values).ravel()]
    with _lock:
        _curves.setdefault(name, []).extend(vals)


def _sink_point(name: str, step: Any, value: Any) -> None:
    with _lock:
        _points.setdefault(name, []).append(
            (int(np.asarray(step)), float(np.asarray(value))))


# ---------------------------------------------------------------- emits
def emit_curve(name: str, values: jax.Array) -> None:
    """Stage a callback shipping a 1-D curve off device (trace mode only)."""
    if not enabled():
        return
    jax.debug.callback(functools.partial(_sink_curve, name), values)


def emit_point(name: str, value: jax.Array, step: jax.Array) -> None:
    """Stage a per-iteration callback (trace mode only)."""
    if not enabled():
        return
    jax.debug.callback(functools.partial(_sink_point, name), step, value)


# ------------------------------------------------------------- helpers
def running_sem(samples: jax.Array) -> jax.Array:
    """Running standard error over sample prefixes, vectorized.

    ``samples[..., j]`` is the j-th probe's estimate; returns a curve of
    shape (k,) where entry j-1 is the sem of the first j probes
    (batch-averaged if ``samples`` has leading dims).  Entry 0 is inf —
    a single probe has no spread estimate.
    """
    x = jnp.asarray(samples)
    x = x.reshape(-1, x.shape[-1]) if x.ndim > 1 else x[None]
    k = x.shape[-1]
    idx = jnp.arange(1, k + 1, dtype=x.dtype)
    mean = jnp.cumsum(x, axis=-1) / idx
    var = (jnp.cumsum(x * x, axis=-1) - idx * mean * mean) / jnp.maximum(
        idx - 1.0, 1.0)
    sem = jnp.sqrt(jnp.maximum(var, 0.0) / idx)
    sem = sem.at[..., 0].set(jnp.inf)
    return sem.mean(axis=0)


def flush() -> None:
    """Block until all staged debug callbacks have run."""
    jax.effects_barrier()


def drain() -> Dict[str, List[float]]:
    """Pop and return all buffered streams as ``{name: [floats]}``.

    Point streams are sorted by step.  Non-finite values are kept (the
    exporters sanitize them); call :func:`flush` first.
    """
    with _lock:
        curves = {k: list(v) for k, v in _curves.items()}
        points = {k: sorted(v) for k, v in _points.items()}
        _curves.clear()
        _points.clear()
    out: Dict[str, List[float]] = dict(curves)
    for name, pts in points.items():
        out[name] = [v for _, v in pts]
    return out


def peek() -> Dict[str, int]:
    """Stream names -> buffered lengths, without draining."""
    with _lock:
        out = {k: len(v) for k, v in _curves.items()}
        out.update({k: len(v) for k, v in _points.items()})
    return out


def sanitize(values: List[float]) -> List[Any]:
    """Replace non-finite entries with None for strict-JSON export."""
    return [v if math.isfinite(v) else None for v in values]


def reset() -> None:
    with _lock:
        _curves.clear()
        _points.clear()

"""Measured-roofline calibration for the method/route selector.

``select_method`` used to price methods with static FLOP constants, which
made every crossover device-count-invariant (both sides divided by P) and
wrong on any machine that is not the one the constants were guessed for.
This module replaces the constants with a **measured calibration table**:

  gemm_flops        sustained GEMM throughput per device (FLOP/s) — prices
                    panel updates and estimator matvec slabs (MXU work)
  stream_bytes      sustained streaming read+write bandwidth per device
                    (bytes/s) — prices the rank-1 update (HBM-bound)
  collective_lat    per-collective latency (s) — the fixed cost of every
                    pivot-row broadcast on the mesh schedule
  collective_bytes  collective payload bandwidth (bytes/s)

The table is produced by ``python -m benchmarks.roofline --calibrate``
(times an f32 and a bf16 GEMM, a fused rank-1 update, and — when the
process sees more than one device — a shard_map psum loop at two payload
sizes, then fits latency + bandwidth) and persisted as JSON with one
entry per ``device_kind`` (``jax.devices()[0].device_kind``: "cpu",
"TPU v5 lite", ...).  Search order for the table: ``$REPRO_CALIBRATION``
(a path, or ``static`` to force the built-in defaults — the explicit
opt-in for tests), then the committed ``bench_out/roofline_calibration
.json``.  A device kind with no entry is an error, never a default.

The cost functions below are the single place route timings are modeled;
`repro.core.plan.select_route` consumes them.  Because the mesh terms
(latency x steps + bytes / collective bandwidth) do NOT shrink with P,
the dense<->estimator and serial<->mesh crossovers now move with device
count — the paper's own Fig. 7/8 story, priced per machine.
"""
from __future__ import annotations

import functools
import json
import math
import os
from dataclasses import dataclass
from pathlib import Path
from typing import Optional, Tuple

__all__ = [
    "Calibration", "CalibrationError", "STATIC_DEFAULT", "load_calibration",
    "clear_calibration_cache", "calibration_path", "current_device_kind",
    "exact_cost", "estimator_cost", "CALIBRATE_CMD",
]

_ENV_VAR = "REPRO_CALIBRATION"
_TABLE_NAME = "roofline_calibration.json"
# probes per matvec slab the estimators batch into one pass (make_probes
# default) — sets how many sequential collectives an estimator run needs
_EST_SLAB = 32

CALIBRATE_CMD = "PYTHONPATH=src python -m benchmarks.roofline --calibrate"


class CalibrationError(ValueError):
    """No usable measured calibration for this device."""


@dataclass(frozen=True)
class Calibration:
    """Per-device roofline terms; see the module docstring.

    ``gemm_flops_bf16`` prices the mixed-precision engine route.  The
    collective terms are None in an entry measured on a single device;
    pricing a mesh with such an entry raises.
    """
    gemm_flops: float = 4.0e10
    stream_bytes: float = 1.5e10
    collective_lat: Optional[float] = 2.0e-5
    collective_bytes: Optional[float] = 4.0e9
    source: str = "static-default"
    gemm_flops_bf16: Optional[float] = 8.0e10

    def __post_init__(self):
        for name in ("gemm_flops", "stream_bytes", "collective_lat",
                     "collective_bytes", "gemm_flops_bf16"):
            v = getattr(self, name)
            if v is not None and not float(v) > 0:
                raise ValueError(f"calibration {name} must be > 0, got {v}")

    def gemm_rate(self, precision: Optional[str] = None) -> float:
        """Sustained GEMM FLOP/s for an engine precision route."""
        if precision in (None, "f32", "f64", "native"):
            return float(self.gemm_flops)
        if precision == "bf16":
            if self.gemm_flops_bf16 is None:
                raise CalibrationError(
                    f"calibration {self.source!r} has no measured bf16 GEMM "
                    f"rate; re-measure it with `{CALIBRATE_CMD}`")
            return float(self.gemm_flops_bf16)
        raise ValueError(f"unknown precision {precision!r}")

    def collective(self) -> Tuple[float, float]:
        """(latency s, bytes/s) of one collective; raises if unmeasured."""
        if self.collective_lat is None or self.collective_bytes is None:
            raise CalibrationError(
                f"calibration {self.source!r} was measured on one device "
                "and cannot price a mesh; run "
                f"`{CALIBRATE_CMD}` on a host with several devices")
        return float(self.collective_lat), float(self.collective_bytes)


STATIC_DEFAULT = Calibration()


def calibration_path() -> Optional[Path]:
    """Where the table is loaded from (None -> the static defaults)."""
    env = os.environ.get(_ENV_VAR, "").strip()
    if env:
        return None if env.lower() == "static" else Path(env)
    return Path(__file__).resolve().parents[3] / "bench_out" / _TABLE_NAME


def current_device_kind() -> str:
    """``device_kind`` of the first device: the table's key."""
    import jax
    return jax.devices()[0].device_kind


@functools.lru_cache(maxsize=8)
def _load(path_str: str, kind: str) -> Calibration:
    try:
        raw = json.loads(Path(path_str).read_text())
    except (OSError, json.JSONDecodeError) as e:
        raise CalibrationError(
            f"cannot read calibration table {path_str}: {e}; measure one "
            f"with `{CALIBRATE_CMD}`") from None
    devices = raw.get("devices", {})
    entry = devices.get(kind)
    if entry is None:
        raise CalibrationError(
            f"calibration table {path_str} has no entry for device kind "
            f"{kind!r} (it has {sorted(devices)}); measure one on this "
            f"device with `{CALIBRATE_CMD}`")

    def opt(name):
        v = entry.get(name)
        return None if v is None else float(v)

    return Calibration(
        gemm_flops=float(entry["gemm_flops"]),
        stream_bytes=float(entry["stream_bytes"]),
        collective_lat=opt("collective_lat"),
        collective_bytes=opt("collective_bytes"),
        source=str(entry.get("source", f"measured:{kind}")),
        gemm_flops_bf16=opt("gemm_flops_bf16"),
    )


def load_calibration(path=None, *, device_kind: Optional[str] = None,
                     ) -> Calibration:
    """The measured entry for this device (or ``device_kind``).

    Raises `CalibrationError` when the table has no entry for the device
    kind; ``REPRO_CALIBRATION=static`` returns `STATIC_DEFAULT` instead.
    """
    if path is None:
        path = calibration_path()
        if path is None:
            return STATIC_DEFAULT
    return _load(str(path), device_kind or current_device_kind())


def clear_calibration_cache():
    """Re-read tables on next load (test hook / after re-calibration)."""
    _load.cache_clear()


# --------------------------------------------------------------------------
# route cost model (seconds)
# --------------------------------------------------------------------------

def exact_cost(n: int, devices: int, cal: Calibration, *,
               update: str = "rank1", panel_k: Optional[int] = None,
               itemsize: int = 8, batch: int = 1,
               lookahead: bool = False,
               precision: Optional[str] = None) -> float:
    """Modeled wall time of an exact condensation route.

    ``devices == 1`` prices the serial/staged schedules; ``devices > 1``
    the mesh schedule — compute splits P ways, but every eliminated row
    (or K-row panel) still pays one broadcast, so the communication term
    is NOT divided by P.  Batched stacks run one device per matrix (no
    collectives), so ``batch`` scales the compute term only.

    ``panel_k=None`` resolves through the calibration-driven tile
    autotuner (`repro.kernels.autotune`) — the same resolution the
    kernels use, so ``method="auto"`` prices the geometry that actually
    runs.  ``precision="bf16"`` prices the GEMM term at the measured bf16
    rate.

    ``lookahead`` prices the pipelined mesh schedule: the double-buffered
    broadcast overlaps the bulk trailing update, hiding up to the
    per-device compute time of the communication term, at the price of
    an extra early-apply of each step/panel to the next pivot rows
    (~``2 * width^2 * n`` FLOPs per step, width = panel_k or 1).
    """
    if n <= 1:
        return 0.0
    if panel_k is None:
        from repro.kernels.autotune import resolved_panel_k
        panel_k = resolved_panel_k(n, itemsize=itemsize,
                                   precision=precision, cal=cal)
    flops = (2.0 / 3.0) * float(n) ** 3
    if update == "panel":
        # rank-K trailing updates are GEMMs: MXU/peak-FLOP bound
        compute = flops / cal.gemm_rate(precision)
    else:
        # rank-1 updates stream the live block once per step: with staged
        # scheduling the touched area is ~1.5 x sum_m m^2 ~ n^3/2 elements,
        # read + write  =>  ~ itemsize * n^3 bytes end to end
        compute = itemsize * float(n) ** 3 / cal.stream_bytes
    cost = batch * compute / devices
    if devices > 1:
        if update == "panel":
            steps = max(1, n // panel_k)
            payload = itemsize * panel_k * n          # (K x N) panel + ls
            width = panel_k
        else:
            steps = n
            payload = itemsize * n                    # one normalized row
            width = 1
        # tree/butterfly collectives pay the latency once per hop, and the
        # hop count grows with the device fan-out: ~log2(P) depth
        lat, bw = cal.collective()
        lat = lat * max(1.0, math.log2(devices))
        comm = steps * (lat + payload / bw)
        if lookahead:
            # the in-flight collective overlaps the bulk update: only the
            # part of comm that exceeds per-device compute stays exposed
            hidden = min(comm, cost)
            overhead = steps * 2.0 * width * width * n / cal.gemm_flops
            cost += (comm - hidden) + overhead
        else:
            cost += comm
    return cost


def estimator_cost(n: int, cols: int, matvec_flops: float, devices: int,
                   cal: Calibration, *, itemsize: int = 8,
                   batch: int = 1) -> float:
    """Modeled wall time of a stochastic estimator run.

    ``cols`` is the probe x step budget (total matvec columns); matvec
    slabs are GEMM-shaped, so compute prices against the measured GEMM
    roofline.  On a mesh the row-sharded matvec reduces one slab per
    sequential step.
    """
    compute = batch * cols * matvec_flops / (devices * cal.gemm_flops)
    cost = compute
    if devices > 1:
        seq = max(1, cols // _EST_SLAB)
        payload = itemsize * n * _EST_SLAB
        lat, bw = cal.collective()
        cost += seq * (lat + payload / bw)
    return cost

"""Reduce a profiler trace (``.xplane.pb``) to what the per-layer metrics
read: device busy and idle time, device time by named scope, kernel and
program, and the idle gaps named by the harness span the host was in.

Where things are in a trace of a TPU v5e under jax 0.9.0 (looked at by
hand, and checked by ``tests/test_trace.py`` on a recorded trace):

- Plane ``/device:TPU:<k>``.  Line ``XLA Ops`` holds one event per HLO
  op that ran, named by its HLO text (``%fusion.221 = f32[1,1]...``);
  the ops of one line do not overlap.  Line ``XLA Modules`` holds one
  event per program run, named ``<module>(<program id>)``, for example
  ``jit_fwd(14961617596983474773)``.  ``Async XLA Ops`` holds copies in
  flight beside the ops; they are not counted as busy.
- Each op's event metadata on that plane carries ``tf_op``, its
  named-scope path (``jit(fwd)/jit(_staged_stage_panel)/while/body/
  engine.panel_apply/dot_general:``), ``program_id`` and
  ``hlo_category``.  ``jax.profiler.ProfileData`` gives events but not
  metadata stats, so those are read from the protobuf here.
- Plane ``/host:CPU``, line ``python``: the ``TraceAnnotation`` spans
  (``bench.window``, ``bench.call``).
- Device and host events share one clock, nanoseconds from the start of
  the profile.
"""
from __future__ import annotations

import glob
import gzip
import os
from collections import defaultdict
from dataclasses import dataclass, field
from typing import Callable, Dict, List, Optional, Tuple

DEVICE_PREFIX = "/device:TPU:"
HOST_PLANE = "/host:CPU"
HARNESS_PREFIX = "bench."


# ------------------------------------------------------------ protobuf

def _varint(b: bytes, i: int) -> Tuple[int, int]:
    x = shift = 0
    while True:
        c = b[i]
        i += 1
        x |= (c & 0x7F) << shift
        shift += 7
        if c < 0x80:
            return x, i


def _fields(b: bytes):
    """(field number, value) of one protobuf message, not recursing."""
    i, n = 0, len(b)
    while i < n:
        key, i = _varint(b, i)
        num, wire = key >> 3, key & 7
        if wire == 0:
            v, i = _varint(b, i)
        elif wire == 2:
            size, i = _varint(b, i)
            v, i = b[i:i + size], i + size
        elif wire == 1:
            v, i = b[i:i + 8], i + 8
        elif wire == 5:
            v, i = b[i:i + 4], i + 4
        else:
            raise ValueError(f"protobuf wire type {wire} not handled")
        yield num, v


# XSpace.planes=1; XPlane.name=2, event_metadata=4, stat_metadata=5;
# XEventMetadata.name=2, stats=5; XStatMetadata.id=1, name=2;
# XStat.metadata_id=1, uint64=3, int64=4, str=5
def op_metadata(raw: bytes) -> Dict[str, Dict[str, dict]]:
    """``{device plane: {(op text, program id): {"tf_op",
    "program_id", "hlo_category"}}}`` from a serialized XSpace."""
    want = {"tf_op", "program_id", "hlo_category"}
    out = {}
    for num, plane in _fields(raw):
        if num != 1:
            continue
        parts = list(_fields(plane))
        name = next((v.decode() for f, v in parts if f == 2), "")
        if not name.startswith(DEVICE_PREFIX):
            continue
        stat_names = {}
        for f, v in parts:
            if f == 5:
                entry = dict(_fields(v))
                meta = dict(_fields(entry.get(2, b"")))
                stat_names[meta.get(1)] = meta.get(2, b"").decode()
        ops = {}
        for f, v in parts:
            if f != 4:
                continue
            meta = list(_fields(dict(_fields(v)).get(2, b"")))
            op = next((x.decode() for g, x in meta if g == 2), "")
            stats = {}
            for g, x in meta:
                if g != 5:
                    continue
                s = dict(_fields(x))
                key = stat_names.get(s.get(1))
                if key in want:
                    val = s.get(5, s.get(3, s.get(4)))
                    stats[key] = val.decode() if isinstance(val, bytes) \
                        else val
            # an op's HLO text can repeat in two programs: key by both
            ops[(op, str(stats.get("program_id", "")))] = stats
        out[name] = ops
    return out


# ------------------------------------------------------------ reduction

@dataclass(frozen=True)
class Op:
    start: float            # ns
    end: float              # ns
    own: float              # ns not covered by ops nested in it
    name: str               # HLO op name, e.g. "%fusion.221"
    module: str             # program name without its id, e.g. "jit_fwd"
    scope: str              # named-scope path (tf_op)
    category: str           # hlo_category


@dataclass
class Reduced:
    """One traced window: its device ops and the harness's host spans."""
    window: Tuple[float, float]                     # ns
    ops: List[Op]
    spans: List[Tuple[str, float, float]]           # harness spans, ns
    busy: List[Tuple[float, float]] = field(default_factory=list)  # per
    # device, each device's intervals merged
    devices: int = 1

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) * 1e-9

    @property
    def busy_s(self) -> float:
        """Union of op intervals, averaged over the devices traced."""
        return sum(b - a for a, b in self.busy) * 1e-9 / self.devices

    def op_seconds(self, keep: Callable[[Op], bool] = lambda op: True
                   ) -> float:
        """Device time of the ops ``keep`` accepts, each counted without
        the ops nested in it (a ``while`` op holds its body's ops)."""
        return sum(o.own for o in self.ops if keep(o)) * 1e-9

    def idle_pct(self) -> Optional[float]:
        if not self.ops or self.window_s <= 0:
            return None
        return 100.0 * (1.0 - self.busy_s / self.window_s)

    def gaps(self) -> List[Tuple[str, float]]:
        """Idle stretches of the device in the window, longest first, each
        named by the innermost harness span around its middle."""
        out, t = [], self.window[0]
        for a, b in _merge(self.busy) + [(self.window[1], self.window[1])]:
            if a > t:
                out.append((self._host_at((t + a) / 2), (a - t) * 1e-9))
            t = max(t, b)
        return sorted(out, key=lambda g: -g[1])

    def _host_at(self, t: float) -> str:
        inner = None
        for name, a, b in self.spans:
            if a <= t <= b and (inner is None or a >= inner[1]):
                inner = (name, a)
        return inner[0] if inner else "outside the harness spans"

    def breakdown(self, top: int = 10) -> dict:
        """The contract's ``breakdown``: device time by op label, and the
        longest idle gaps."""
        by = defaultdict(float)
        for o in self.ops:
            by[op_label(o)] += o.own * 1e-9
        ops = sorted(by.items(), key=lambda kv: -kv[1])[:top]
        return {"device_ops": [[k, v] for k, v in ops],
                "idle_gaps": [[k, v] for k, v in self.gaps()[:top]]}


def scopes(op: Op) -> List[str]:
    """The named scopes of an op's path, outermost first."""
    return [p for p in op.scope.rstrip(":").split("/")[:-1]]


def op_label(op: Op) -> str:
    """``<innermost engine./kernel. scope or module>:<HLO category>``."""
    owner = next((s for s in reversed(scopes(op))
                  if s.startswith(("engine.", "kernel."))), op.module)
    return f"{owner}:{op.category or op.name.split('.')[0].lstrip('%')}"


def _merge(intervals):
    out = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], b))
        else:
            out.append((a, b))
    return out


def reduce_file(path: str) -> Reduced:
    """Reduce one ``.xplane.pb`` (or ``.xplane.pb.gz``).  The window is
    the harness's ``bench.window`` span; ops are clipped to it."""
    from jax.profiler import ProfileData
    with (gzip.open if str(path).endswith(".gz") else open)(path, "rb") as f:
        raw = f.read()
    meta = op_metadata(raw)
    data = ProfileData.from_serialized_xspace(raw)
    spans, device_lines = [], {}
    for plane in data.planes:
        if plane.name == HOST_PLANE:
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(HARNESS_PREFIX):
                        spans.append((e.name, e.start_ns,
                                      e.start_ns + e.duration_ns))
        elif plane.name.startswith(DEVICE_PREFIX):
            lines = {line.name: [(e.start_ns, e.duration_ns, e.name)
                                 for e in line.events]
                     for line in plane.lines}
            device_lines[plane.name] = lines
    windows = [(a, b) for name, a, b in spans if name == "bench.window"]
    if not windows:
        raise ValueError(f"{path}: no bench.window span in the trace")
    w0, w1 = windows[0]
    ops, busy = [], []
    for plane, lines in device_lines.items():
        # "jit_fwd(14961617596983474773)": the program's name and id
        modules = sorted((s, s + d) + tuple(n.rstrip(")").split("(", 1))
                         for s, d, n in lines.get("XLA Modules", []))
        plane_meta = meta.get(plane, {})
        # containers (a while op) start first and end last: sort so, and
        # take each op's time off the op it is nested in
        events = sorted(((max(s, w0), min(s + d, w1), text)
                         for s, d, text in lines.get("XLA Ops", [])
                         if s < w1 and s + d > w0),
                        key=lambda e: (e[0], -e[1]))
        nested = [0.0] * len(events)
        stack, k, plane_ops = [], 0, []
        for idx, (a, b, text) in enumerate(events):
            while stack and events[stack[-1]][1] <= a:
                stack.pop()
            if stack:
                nested[stack[-1]] += b - a
            stack.append(idx)
        for idx, (a, b, text) in enumerate(events):
            while k + 1 < len(modules) and modules[k + 1][0] <= a:
                k += 1
            module, pid = modules[k][2:4] if modules and \
                modules[k][0] <= a else ("?", "")
            m = plane_meta.get((text, pid), {})
            plane_ops.append(Op(start=a, end=b, own=b - a - nested[idx],
                                name=text.split(" = ")[0],
                                module=module, scope=m.get("tf_op", ""),
                                category=m.get("hlo_category", "")))
        ops += plane_ops
        busy += _merge((o.start, o.end) for o in plane_ops)
    return Reduced(window=(w0, w1), ops=ops, spans=spans, busy=busy,
                   devices=max(1, len(device_lines)))


def reduce_dir(trace_dir: str) -> Reduced:
    """Reduce the one ``.xplane.pb`` under ``trace_dir``."""
    found = glob.glob(os.path.join(str(trace_dir), "**", "*.xplane.pb"),
                      recursive=True)
    if len(found) != 1:
        raise ValueError(f"expected one .xplane.pb under {trace_dir}, "
                         f"found {len(found)}")
    return reduce_file(found[0])


@dataclass
class Context:
    """What a metric reader gets: the reduced trace and the run's facts."""
    trace: Reduced
    calls: int                  # calls of the window that the trace holds
    n: int
    setup_compile_s: float
    peaks: dict
    work: Callable              # name -> the module under bench/work/

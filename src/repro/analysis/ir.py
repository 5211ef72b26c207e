"""Normalized IR tables over lowered/compiled XLA programs.

The checker passes in `repro.analysis.passes` prove structural claims
about a program — "the tail all-gather moves O(P^2) bytes", "the
estimator backward is factorization-free" — and those claims live at the
instruction level.  This module parses the two text forms jax hands us
into ONE normalized instruction table:

  * **StableHLO MLIR** (``lowered.as_text()``): pre-optimization, every
    op the trace emitted survives, but no scope metadata is printed.
  * **HLO text** (``lowered.compile().as_text()``): post-optimization,
    ops carry ``metadata={op_name="jit(f)/.../engine.mesh_tail/..."}`` —
    the named-scope ancestry `obs.stage` planted — at the cost of fusion
    having swallowed some instructions.

Each `Instruction` records opcode (normalized to HLO spelling:
``all-gather``, not ``stablehlo.all_gather``), result/operand shapes with
dtypes, named-scope ancestry, and the custom-call target when present;
`Module` is the queryable table.  `collective_bytes` / `roofline` (the
dry-run cost model this parser grew out of — repro.launch.hlo_analysis
re-exports them for compatibility) are implemented on top.

Wire-byte conventions (ring algorithms, per device):
  all-reduce         2 x operand bytes   (reduce-scatter + all-gather phases)
  all-gather         output bytes - operand bytes (received shards)
  reduce-scatter     operand bytes - output bytes
  all-to-all         operand bytes       (each device re-sends its shard)
  collective-permute operand bytes
"""
from __future__ import annotations

import re
from dataclasses import dataclass, field
from typing import Dict, Iterable, List, Optional, Tuple

__all__ = [
    "Shape", "Instruction", "Module", "parse_module", "shape_bytes",
    "collective_bytes", "roofline", "HW", "CollectiveStats",
    "COLLECTIVE_OPS", "collective_payload_bytes",
]

# TPU v5e hardware constants (per chip)
HW = {
    "peak_flops_bf16": 197e12,     # FLOP/s
    "hbm_bw": 819e9,               # B/s
    "ici_bw": 49.5e9,              # B/s per link direction (~50 GB/s)
}

# HLO dtype -> bytes.  Sub-byte types (u1/s1/u2/s2/u4/s4) occupy one byte
# each in unpacked HLO buffers; token/opaque carry no payload.
_DTYPE_BYTES = {
    "pred": 1, "s1": 1, "u1": 1, "s2": 1, "u2": 1, "s4": 1, "u4": 1,
    "s8": 1, "u8": 1, "s16": 2, "u16": 2,
    "s32": 4, "u32": 4, "s64": 8, "u64": 8,
    "f8e4m3fn": 1, "f8e5m2": 1, "f8e4m3b11fnuz": 1, "f8e4m3fnuz": 1,
    "f8e5m2fnuz": 1, "f8e8m0fnu": 1,
    "bf16": 2, "f16": 2, "f32": 4, "f64": 8,
    "c64": 8, "c128": 16, "token": 0, "opaque": 0,
}

# MLIR element type -> HLO dtype name
_MLIR_DTYPE = {
    "i1": "pred", "i2": "s2", "i4": "s4", "i8": "s8", "i16": "s16",
    "i32": "s32", "i64": "s64",
    "ui1": "u1", "ui2": "u2", "ui4": "u4", "ui8": "u8", "ui16": "u16",
    "ui32": "u32", "ui64": "u64",
    "si8": "s8", "si16": "s16", "si32": "s32", "si64": "s64",
    "bf16": "bf16", "f16": "f16", "f32": "f32", "f64": "f64",
    "f8E4M3FN": "f8e4m3fn", "f8E5M2": "f8e5m2",
    "complex<f32>": "c64", "complex<f64>": "c128",
}

COLLECTIVE_OPS = ("all-gather", "all-reduce", "reduce-scatter",
                  "all-to-all", "collective-permute")

# dtype[dims]: covers f32[4,4]{1,0}, u1[8], token[] and bare scalars f32[]
_HLO_SHAPE_RE = re.compile(r"([a-z][a-z0-9]*)\[([\d,]*)\]")
# instruction definition:  [ROOT] [%]name = <shape or (tuple)> opcode(...)
_HLO_DEF_RE = re.compile(
    r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*"
    r"((?:\((?:[^()]|\([^)]*\))*\))|(?:[a-z][a-z0-9]*\[[\d,]*\]\S*))\s+"
    r"([\w\-]+)")
_HLO_OPERAND_RE = re.compile(r"%([\w.\-]+)")
_HLO_OP_NAME_RE = re.compile(r'op_name="([^"]*)"')
_HLO_TARGET_RE = re.compile(r'custom_call_target="([^"]*)"')

# tensor<4x4xf32>, tensor<f32>, tensor<8x!quant...> (unknown kept raw),
# tensor<2x?xf32> (dynamic dims -> 0), !stablehlo.token
_MLIR_TENSOR_RE = re.compile(r"tensor<([^<>]*(?:<[^<>]*>)?[^<>]*)>")
_MLIR_DEF_RE = re.compile(r"^\s*(%[\w#.\-]+(?::\d+)?)\s*=\s*"
                          r'(?:"([\w.]+)"|([\w.]+))')
# a custom call with no results (host callbacks lower to one) has no
# `%x =` prefix; it still has to land in the table
_MLIR_VOID_CALL_RE = re.compile(r'^\s*()(?:"(stablehlo\.custom_call)"|'
                                r"(stablehlo\.custom_call))\b")
_MLIR_TARGET_RE = re.compile(r"custom_call\s+@([\w.\-]+)|@([\w.\-]+)\s*\(")
_MLIR_SCOPE_RE = re.compile(r'loc\("([^"]*)"')


@dataclass(frozen=True)
class Shape:
    """One result/operand aval: dtype (HLO spelling) + static dims."""
    dtype: str
    dims: Tuple[int, ...] = ()

    @property
    def bytes(self) -> int:
        n = 1
        for d in self.dims:
            n *= d
        return n * _DTYPE_BYTES.get(self.dtype, 0)


def shape_bytes(shapes: Iterable[Shape]) -> int:
    """Total byte size of a (possibly nested, already flattened) result."""
    return sum(s.bytes for s in shapes)


@dataclass(frozen=True)
class Instruction:
    """One normalized instruction row.

    ``opcode`` uses HLO spelling (``all-gather``); StableHLO ops are
    mapped (``stablehlo.all_gather`` -> ``all-gather``).  ``scopes`` is
    the named-scope ancestry from ``metadata={op_name=...}`` (compiled
    HLO) — empty in the StableHLO dialect, which does not print it.
    Tuple results arrive flattened into ``result_shapes`` (nested tuples
    too — the parser unnests ``((f32[4], u1[2]), token[])``).
    """
    name: str
    opcode: str
    result_shapes: Tuple[Shape, ...] = ()
    operand_shapes: Tuple[Shape, ...] = ()
    operands: Tuple[str, ...] = ()
    scopes: Tuple[str, ...] = ()
    custom_call_target: Optional[str] = None
    line_no: int = 0
    raw: str = ""

    @property
    def result_bytes(self) -> int:
        return shape_bytes(self.result_shapes)

    @property
    def operand_bytes(self) -> int:
        return shape_bytes(self.operand_shapes)

    def in_scope(self, name: str) -> bool:
        return any(name == s or s.endswith("/" + name) for s in self.scopes)


@dataclass
class Module:
    """Normalized instruction table for one lowered/compiled program."""
    dialect: str                       # "hlo" | "stablehlo"
    instructions: List[Instruction] = field(default_factory=list)
    text: str = ""

    def find(self, opcode_prefix: str) -> List[Instruction]:
        """Instructions whose opcode starts with ``opcode_prefix`` (the
        async ``-start`` forms match their base opcode)."""
        return [i for i in self.instructions
                if i.opcode.startswith(opcode_prefix)]

    def collectives(self) -> List[Instruction]:
        """Cross-device collectives, async pairs counted once (``-start``
        kept, ``-done`` dropped)."""
        out = []
        for i in self.instructions:
            base = _collective_base(i.opcode)
            if base is not None and not i.opcode.endswith("-done"):
                out.append(i)
        return out

    def custom_call_targets(self) -> Dict[str, int]:
        targets: Dict[str, int] = {}
        for i in self.instructions:
            if i.custom_call_target:
                targets[i.custom_call_target] = \
                    targets.get(i.custom_call_target, 0) + 1
        return targets

    def scope_names(self) -> set:
        names = set()
        for i in self.instructions:
            names.update(i.scopes)
        return names

    def dump(self) -> str:
        """Stable normalized text form (round-trip/debug aid): one line
        per instruction — name, opcode, result shapes, scopes, target."""
        rows = []
        for i in self.instructions:
            shapes = ",".join(
                f"{s.dtype}[{'x'.join(map(str, s.dims))}]"
                for s in i.result_shapes)
            rows.append("\t".join([
                i.name, i.opcode, shapes or "-",
                "/".join(i.scopes) or "-", i.custom_call_target or "-"]))
        return "\n".join(rows)


def _collective_base(opcode: str) -> Optional[str]:
    base = opcode
    for suffix in ("-start", "-done"):
        if base.endswith(suffix):
            base = base[: -len(suffix)]
    return base if base in COLLECTIVE_OPS else None


# --------------------------------------------------------------------------
# HLO text dialect
# --------------------------------------------------------------------------

def _parse_hlo_shapes(text: str) -> Tuple[Shape, ...]:
    """Every dtype[dims] occurrence in ``text`` — tuples (and tuples of
    tuples) flatten naturally since each leaf prints its own shape."""
    shapes = []
    for dt, dims in _HLO_SHAPE_RE.findall(text):
        if dt not in _DTYPE_BYTES:
            continue
        shapes.append(Shape(dt, tuple(int(d) for d in dims.split(",") if d)))
    return tuple(shapes)


def _scopes_of(op_name: str) -> Tuple[str, ...]:
    """Named-scope components of a jax op_name path.

    ``jit(f)/jit(main)/while/body/engine.broadcast/psum`` — the jit(...) /
    transform wrappers and the trailing primitive name are structure, the
    dotted components in between are user `jax.named_scope` frames."""
    parts = [p for p in op_name.split("/") if p]
    out = []
    for p in parts[:-1] if len(parts) > 1 else parts:
        if p.startswith(("jit(", "vmap(", "pmap(", "transpose(", "jvp(",
                         "pjit(", "custom_vjp(", "custom_jvp(", "remat(")):
            continue
        if p in ("while", "body", "cond"):
            continue
        out.append(p)
    return tuple(out)


def _parse_hlo(text: str) -> Module:
    mod = Module(dialect="hlo", text=text)
    for ln, line in enumerate(text.splitlines(), start=1):
        m = _HLO_DEF_RE.match(line)
        if not m:
            continue
        name, out_txt, op = m.group(1), m.group(2), m.group(3).lower()
        rest = line[m.end():]
        paren = rest.find("(")
        operand_txt = ""
        if paren >= 0:
            depth, j = 0, paren
            for j in range(paren, len(rest)):
                if rest[j] == "(":
                    depth += 1
                elif rest[j] == ")":
                    depth -= 1
                    if depth == 0:
                        break
            operand_txt = rest[paren + 1:j]
        meta = _HLO_OP_NAME_RE.search(line)
        target = _HLO_TARGET_RE.search(line)
        mod.instructions.append(Instruction(
            name=name, opcode=op,
            result_shapes=_parse_hlo_shapes(out_txt),
            operand_shapes=_parse_hlo_shapes(operand_txt),
            operands=tuple(_HLO_OPERAND_RE.findall(operand_txt)),
            scopes=_scopes_of(meta.group(1)) if meta else (),
            custom_call_target=target.group(1) if target else None,
            line_no=ln, raw=line.strip()))
    return mod


# --------------------------------------------------------------------------
# StableHLO MLIR dialect
# --------------------------------------------------------------------------

def _mlir_shape(spec: str) -> Optional[Shape]:
    """``4x4xf32`` / ``f32`` / ``2x?xbf16`` -> Shape (dynamic dims -> 0)."""
    spec = spec.strip()
    parts = spec.split("x")
    # element type may itself contain 'x' only for complex<...> (handled
    # as the joined tail)
    for split in range(len(parts)):
        elem = "x".join(parts[split:])
        dtype = _MLIR_DTYPE.get(elem)
        if dtype is None:
            continue
        dims = []
        ok = True
        for d in parts[:split]:
            if d == "?":
                dims.append(0)
            elif d.isdigit():
                dims.append(int(d))
            else:
                ok = False
                break
        if ok:
            return Shape(dtype, tuple(dims))
    if spec in ("!stablehlo.token", "token"):
        return Shape("token")
    return None


def _parse_mlir_types(text: str) -> Tuple[Shape, ...]:
    shapes = []
    for spec in _MLIR_TENSOR_RE.findall(text):
        s = _mlir_shape(spec)
        if s is not None:
            shapes.append(s)
    for _ in re.findall(r"!stablehlo\.token", text):
        shapes.append(Shape("token"))
    return tuple(shapes)


def _normalize_mlir_op(op: str) -> str:
    op = op.split(".")[-1]            # stablehlo.all_gather -> all_gather
    return op.replace("_", "-")


def _parse_stablehlo(text: str) -> Module:
    mod = Module(dialect="stablehlo", text=text)
    for ln, line in enumerate(text.splitlines(), start=1):
        m = _MLIR_DEF_RE.match(line) or _MLIR_VOID_CALL_RE.match(line)
        if not m:
            continue
        name = m.group(1).split(":")[0].lstrip("%") or f"void.{ln}"
        op = _normalize_mlir_op(m.group(2) or m.group(3))
        # the type annotation after ':' — `(operands) -> results` for the
        # generic form, a bare type for the pretty form
        res_txt, opnd_txt = line, ""
        sig = re.search(r":\s*\(([^:]*)\)\s*->\s*(.*)$", line)
        if sig:
            opnd_txt, res_txt = sig.group(1), sig.group(2)
        else:
            bare = re.search(r":\s*(tensor<[^:]*|!stablehlo\.token\s*$)",
                             line)
            res_txt = bare.group(1) if bare else ""
        target = None
        if "custom_call" in line or "@" in line:
            tm = _MLIR_TARGET_RE.search(line)
            if tm:
                target = tm.group(1) or tm.group(2)
        scope = _MLIR_SCOPE_RE.search(line)
        mod.instructions.append(Instruction(
            name=name, opcode=op,
            result_shapes=_parse_mlir_types(res_txt),
            operand_shapes=_parse_mlir_types(opnd_txt),
            operands=tuple(re.findall(r"%([\w#.\-]+)",
                                      line[m.end():sig.start() if sig
                                           else len(line)])),
            scopes=_scopes_of(scope.group(1)) if scope else (),
            custom_call_target=target if op == "custom-call" else None,
            line_no=ln, raw=line.strip()))
    return mod


# --------------------------------------------------------------------------
# entry point + collective accounting
# --------------------------------------------------------------------------

def parse_module(text: str) -> Module:
    """Parse HLO text or StableHLO MLIR into a normalized `Module`.

    Dialect is auto-detected: MLIR programs open with ``module @`` /
    contain ``stablehlo.`` ops; everything else parses as HLO text."""
    head = text[:4096]
    if ("stablehlo." in text or "mhlo." in head
            or head.lstrip().startswith(("module @", "module attributes",
                                         "func.func"))):
        return _parse_stablehlo(text)
    return _parse_hlo(text)


def collective_payload_bytes(instr: Instruction,
                             sizes: Optional[Dict[str, int]] = None
                             ) -> float:
    """Per-device wire bytes of one collective (ring conventions)."""
    base = _collective_base(instr.opcode)
    out_bytes = instr.result_bytes
    in_bytes = instr.operand_bytes
    if in_bytes == 0 and sizes:
        in_bytes = sum(sizes.get(o, 0) for o in instr.operands)
    if base == "all-reduce":
        return 2 * in_bytes
    if base == "all-gather":
        return max(out_bytes - in_bytes, out_bytes // 2)
    if base == "reduce-scatter":
        return max(in_bytes - out_bytes, in_bytes // 2)
    return max(in_bytes, out_bytes)     # all-to-all, collective-permute


@dataclass
class CollectiveStats:
    counts: Dict[str, int] = field(default_factory=dict)
    wire_bytes: float = 0.0          # per device
    by_op: Dict[str, float] = field(default_factory=dict)


def collective_bytes(hlo_text: str) -> CollectiveStats:
    """Parse per-device wire bytes from (post-SPMD) HLO text.

    Operands are printed by NAME in optimized HLO dumps; a first pass
    builds the name -> result-bytes symbol table so payloads resolve.

    NOTE on while loops: collectives inside a while body are counted once
    (same undercount as cost_analysis); the dry-run lowers with unrolled
    layer stacks so per-step traffic is exact for the roofline table.
    """
    mod = parse_module(hlo_text)
    sizes = {i.name: i.result_bytes for i in mod.instructions}
    stats = CollectiveStats()
    for instr in mod.collectives():
        base = _collective_base(instr.opcode)
        wire = collective_payload_bytes(instr, sizes)
        stats.counts[base] = stats.counts.get(base, 0) + 1
        stats.by_op[base] = stats.by_op.get(base, 0.0) + wire
        stats.wire_bytes += wire
    return stats


def roofline(*, flops: float, hbm_bytes: float, wire_bytes_per_chip: float,
             chips: int, hw: Dict[str, float] = HW) -> Dict[str, float]:
    """Three-term roofline (seconds) + bottleneck."""
    terms = {
        "compute_s": flops / (chips * hw["peak_flops_bf16"]),
        "memory_s": hbm_bytes / (chips * hw["hbm_bw"]),
        "collective_s": wire_bytes_per_chip / hw["ici_bw"],
    }
    terms["bottleneck"] = max(
        ("compute_s", "memory_s", "collective_s"), key=lambda k: terms[k])
    terms["step_s_lower_bound"] = max(
        terms["compute_s"], terms["memory_s"], terms["collective_s"])
    return terms

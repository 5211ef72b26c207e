"""The main path's Pallas kernels compile for a TPU v5e, at the widths the
chip smoke (``chip_smoke.py``) runs them, the N=8000 and N=1000 panel
stages compile with no whole-buffer move in their panel loop, and the exact
backward's own-LU program (``exact_pullback`` past ``XLA_LU_ROWS``) holds
at most three matrices.

Nothing runs: each kernel is lowered and compiled for a described,
unattached ``v5e:2x2`` topology, so the chip's own compiler (Mosaic)
refuses here what it would refuse on the chip.  The session runs with
``jax_enable_x64`` on (conftest), which is what once broke every kernel
(Python-int block indices became i64): these tests are the regression
test for the int32 index maps.
"""
import re

import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

F32, I32 = jnp.float32, jnp.int32


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "cannot"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    # compiles for a described chip cannot be read back from the persistent
    # cache without one; keep them out of it
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)


def _compile(one_chip, fn, *shapes):
    """Compiled text of ``fn`` on ``shapes`` ((shape, dtype) pairs)."""
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes]
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text
    return text


def test_x64_is_on():
    """The compiles below must run under x64 to guard the index maps."""
    assert jax.config.jax_enable_x64


def test_rank1_update_n8000(one_chip):
    from repro.kernels.condense_step import rank1_update_pallas
    n = 8000
    _compile(one_chip, rank1_update_pallas,
             ((n, n), F32), ((n,), F32), ((n,), F32))


@pytest.mark.parametrize("n", [8000, 16384])
def test_fused_step(one_chip, n):
    from repro.kernels.fused_step import fused_step_pallas
    _compile(one_chip, fused_step_pallas,
             ((n, n), F32), ((), I32), ((), I32),
             ((n,), F32), ((n,), F32), ((n,), F32), ((n,), F32))


def test_panel_update_k32(one_chip):
    from repro.kernels.panel_update import panel_update_pallas
    n, k = 16384, 32
    _compile(one_chip, panel_update_pallas,
             ((n, n), F32), ((n, k), F32), ((k, n), F32))


def test_panel_factor_k32_n16384(one_chip):
    from repro.kernels.panel_factor import panel_factor_pallas
    _compile(one_chip, panel_factor_pallas,
             ((32, 16384), F32), ((), I32), ((), I32))


def test_matvec(one_chip):
    from repro.kernels.matvec import matvec_pallas
    _compile(one_chip, matvec_pallas, ((8192, 8192), F32), ((8192, 32), F32))


def test_cheb_step_n1024(one_chip):
    from repro.kernels.fused_est import cheb_step_pallas
    n, k = 1024, 32
    _compile(one_chip, cheb_step_pallas,
             ((n, n), F32), ((n, k), F32), ((n, k), F32), ((n, k), F32),
             ((), F32), ((), F32))


def test_cg_step_n1024(one_chip):
    from repro.kernels.fused_est import cg_step_pallas
    n, k = 1024, 32
    _compile(one_chip, cg_step_pallas,
             ((n, n), F32), ((n, k), F32), ((n, k), F32), ((n, k), F32),
             ((k,), F32))


def _computations(text):
    """{name: instruction lines} of a compiled HLO module's text."""
    comps, cur = {}, None
    for line in text.splitlines():
        head = re.match(r"^(?:ENTRY )?%?([\w.\-]+) .*\{\s*$", line)
        if head:
            cur = comps.setdefault(head.group(1), [])
        elif line.strip() == "}":
            cur = None
        elif cur is not None:
            cur.append(line)
    return comps


# ops a panel ran at N=1000, k=32 when the panel loop swapped B's columns
# (one gather and two whole-buffer copies a panel), counted as `_launched`
# counts them with ``jax_enable_x64`` on, as conftest sets it (64 with it
# off)
COLUMN_SWAP_PANEL_OPS = 145
_NOT_RUN = ("parameter", "get-tuple-element", "bitcast", "tuple", "constant")
_OP = re.compile(r"\s*(?:ROOT )?%\S+ = (\(.*?\)|\S+) ([a-z][\w-]*)\(")


def _launched(comps, body):
    """Ops one pass of the loop ``body`` runs: its own and, once each,
    those of the loops and calls it holds; a fusion counts as one."""
    ops = []
    for line in comps[body]:
        op = _OP.match(line)
        if not op or op.group(2) in _NOT_RUN:
            continue
        ops.append(op.group(2))
        if op.group(2) == "while":
            ops += _launched(comps, re.search(r"body=%?([\w.\-]+)",
                                              line).group(1))
        elif op.group(2) == "call":
            ops += _launched(comps, re.search(r"to_apply=%?([\w.\-]+)",
                                              line).group(1))
    return ops


@pytest.mark.parametrize("n, k, steps", [(8000, 64, 2000), (1000, 32, 250)])
def test_panel_swap_moves_no_whole_buffer_per_swap(one_chip, monkeypatch, n,
                                                   k, steps):
    """The first stage of the exact logdet at N=8000 (staged x panel,
    k=64) and N=1000 (k=32): the loop that holds ``kernel.panel_update``,
    and every computation it calls, holds no copy, transpose or gather of
    the whole buffer.  Only the GEMM's custom-call (in place), the swaps'
    row writes and the park write the whole-buffer shape.  At N=1000,
    where each op's latency sets the time, a panel runs no more ops than
    when it swapped B's columns."""
    from repro.core.engine import _staged_stage_panel
    from repro.kernels import ops
    monkeypatch.setattr(ops, "on_tpu", lambda: True)    # the chip's kernels
    text = _compile(one_chip,
                    lambda b: _staged_stage_panel(b, steps, k, "pallas"),
                    ((n, n), F32))
    comps = _computations(text)
    loops = [re.search(r"body=%?([\w.\-]+)", line).group(1)
             for lines in comps.values() for line in lines
             if re.search(r" while\(", line)
             and any("kernel.panel_update/" in x
                     for x in comps[re.search(r"body=%?([\w.\-]+)",
                                              line).group(1)])]
    assert len(loops) == 1, loops
    body = loops[0]
    seen, todo, writes = {body}, [body], []
    while todo:                          # the body and what it calls
        for line in comps[todo.pop()]:
            op = _OP.match(line)
            if op and op.group(1).startswith(f"f32[{n},{n}]") \
                    and op.group(2) not in _NOT_RUN:
                name = re.search(r'op_name="([^"]*)"', line)
                writes.append((op.group(2), name.group(1) if name else ""))
            for callee in re.findall(
                    r"(?:calls|body|condition|to_apply)=%?([\w.\-]+)", line):
                if callee not in seen:
                    seen.add(callee)
                    todo.append(callee)
    assert not [w for w in writes
                if w[0] in ("copy", "transpose", "gather")], writes
    allowed = {"custom-call": ("kernel.panel_update",),
               "dynamic-update-slice": ("engine.panel_swap",
                                        "engine.panel_park")}
    for kind, name in writes:
        assert kind in allowed and any(
            s in name.split("/") for s in allowed[kind]), (kind, name)
    assert "output_to_operand_aliasing" in next(
        line for line in comps[body] if "kernel.panel_update/" in line
        and "custom-call(" in line)
    if n == 1000:
        assert len(_launched(comps, body)) <= COLUMN_SWAP_PANEL_OPS


@pytest.mark.parametrize("n", [4096, 8192])
def test_exact_pullback_holds_at_most_three_matrices(one_chip, n):
    """The exact backward's own-LU program (what a v5e runs past
    ``XLA_LU_ROWS``), ``g * A^{-T}`` for one float32 matrix, compiles
    into one program whose temporaries and output together take at most
    three N x N matrices (the factor, the answer's column strips and the
    answer), and its ops carry the scope ``grad.pullback``.  At n=4096 a
    matrix is 64 MiB, which the compiler may keep in the chip's 128 MiB
    of VMEM, out of this count; at n=8192 it cannot.  XLA's own LU, the
    branch below ``XLA_LU_ROWS``, takes minutes to compile for a v5e on
    a CPU, so the chip's runs measure that one."""
    from repro.estimators.grad import _pullback_program
    args = [jax.ShapeDtypeStruct((n, n), F32, sharding=one_chip),
            jax.ShapeDtypeStruct((), F32, sharding=one_chip)]
    compiled = _pullback_program.lower(*args, own_lu=True).compile()
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes + mem.output_size_in_bytes \
        <= 3 * 4 * n * n
    names = re.findall(r'op_name="([^"]*)"', compiled.as_text())
    assert any("grad.pullback" in name.split("/") for name in names)

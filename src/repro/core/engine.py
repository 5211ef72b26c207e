"""The unified condensation engine: one schedule x update x backend core.

The paper's contribution is ONE step — pivot-column argmax (§2.2), row
factoring (§2.3), column swap (§2.4) — yet the repo used to reimplement it
four times (serial, staged, blocked, mesh).  This module is the single
implementation, parameterized on three orthogonal axes:

  schedule   "serial"  one static buffer, one rank-per-step fori_loop
             "staged"  geometric re-jit over shrinking static shapes
             "mesh"    round-robin block rows over a 1-D device mesh
                       (shard_map; the paper's parallel schedule)
  update     "rank1"   the faithful outer-product subtract (VPU/bandwidth)
             "panel"   rank-K panels: factorize K rows, ONE trailing GEMM
                       (MXU; the paper's "future work", blocked-LU style)
  backend    "xla"       plain jnp expressions, XLA-fused
             "pallas"    the fused Pallas kernels (repro.kernels.ops);
                         off-TPU the kernel bodies run in interpret mode
                         — never a silent fall-through to the reference
             "interpret" the kernel bodies through the Pallas interpreter
                         (deterministic CPU coverage; what CI forces via
                         REPRO_KERNEL_BACKEND=interpret)
             "auto"      resolves to the process default at plan time
                         (env override, else pallas on TPU / xla off)

Every combination shares exactly one implementation of pivot selection,
§2.4 column-swap bookkeeping, sign/parity tracking, the remainder rank-1
steps, and the P x P tail reduction (`mesh_tail`).  The legacy modules
(core/condense.py, core/blocked.py, core/parallel.py) are thin wrappers
over this engine; the Gaussian-elimination and ScaLAPACK baselines stay
separate algorithms but adopt the shared sign helpers (`perm_parity`,
`cyclic_perm`, `guarded_pivot`) and `combine_slogdet`.

Route vocabulary: a legacy route string maps to an `EngineConfig` tuple
via `LEGACY_ROUTES` —

    mc          -> (serial, rank1)      mc_staged   -> (staged, rank1)
    mc_blocked  -> (serial, panel)      pmc         -> (mesh,   rank1)
    pmc_blocked -> (mesh,   panel)

plus the combinations no legacy string ever exposed (staged x panel, any
x pallas).  New code requests ``repro.plan(..., method="exact",
schedule=..., update=..., backend=...)``.
"""
from __future__ import annotations

import functools
import math
from dataclasses import dataclass
from typing import Callable, Optional, Tuple

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec

from repro import obs
from repro.numerics import log_abs, matmul

__all__ = [
    "EngineConfig", "LEGACY_ROUTES", "SCHEDULES", "UPDATES", "BACKENDS",
    "build_serial", "build_mesh", "engine_slogdet",
    "condense_steps", "condense_full", "panel_factor", "apply_panel",
    "panel_rounds_serial", "mc_step_fn", "mc_local_phase", "mesh_tail",
    "combine_slogdet", "guarded_pivot", "cyclic_perm",
    "perm_parity",
    "stage_schedule",
]

SCHEDULES = ("serial", "staged", "mesh")
UPDATES = ("rank1", "panel")
# "interpret" runs the Pallas kernel bodies through the interpreter —
# the deterministic off-TPU coverage backend CI forces via
# REPRO_KERNEL_BACKEND; "pallas" off-TPU degrades to it automatically
BACKENDS = ("auto", "xla", "pallas", "interpret")


@dataclass(frozen=True)
class EngineConfig:
    """One point in the schedule x update x backend design space.

    ``panel_k``   panel width of the rank-K update (ignored for rank1).
    ``shrink``    geometric stage ratio of the staged schedule.
    ``min_size``  size at which the staged schedule stops re-jitting.
    ``lookahead`` mesh-only: pipeline the next pivot row / panel — its
                  owner factors it from an early-applied copy *before*
                  the bulk trailing update of the current one, so the
                  broadcast collective is double-buffered and overlaps
                  compute instead of serializing with it.  Bit-identical
                  results (asserted in tests/test_engine.py).
    ``fused``     serial/staged-only: run the condensation step as ONE
                  pass over the buffer — pivot argmax + §2.4 swap + the
                  rank-1 update in a single fused kernel (the swap
                  becomes a per-column select), and the panel schedule's
                  K sequential swap scatters become one composed-
                  permutation gather.  Bit-identical results (asserted
                  in tests/test_engine.py); the mesh schedule pipelines
                  via ``lookahead`` instead.
    ``precision`` ``None`` (native) or ``"bf16"``: quantize the
                  GEMM / outer-product operands to bfloat16 while the
                  buffer and all sign/parity/log accumulators stay in
                  the input dtype (the mixed-precision MXU route; error
                  model documented in docs/api.md).
    Frozen + hashable so it can ride inside `ExactConfig` and key the
    plan cache.
    """
    schedule: str = "staged"
    update: str = "rank1"
    panel_k: int = 32
    backend: str = "auto"
    shrink: float = 0.75
    min_size: int = 64
    lookahead: bool = False
    fused: bool = False
    precision: Optional[str] = None

    def __post_init__(self):
        if self.schedule not in SCHEDULES:
            raise ValueError(
                f"unknown schedule {self.schedule!r}; one of {SCHEDULES}")
        if self.update not in UPDATES:
            raise ValueError(
                f"unknown update {self.update!r}; one of {UPDATES}")
        if self.backend not in BACKENDS:
            raise ValueError(
                f"unknown backend {self.backend!r}; one of {BACKENDS}")
        if int(self.panel_k) < 1:
            raise ValueError(f"panel_k must be >= 1, got {self.panel_k}")
        if not (0.0 < float(self.shrink) < 1.0):
            raise ValueError(f"shrink must be in (0, 1), got {self.shrink}")
        if int(self.min_size) < 2:
            raise ValueError(f"min_size must be >= 2, got {self.min_size}")
        if self.lookahead and self.schedule != "mesh":
            raise ValueError(
                "lookahead pipelines the mesh schedule's broadcast; it "
                f"requires schedule='mesh', got {self.schedule!r}")
        if self.fused and self.schedule == "mesh":
            raise ValueError(
                "fused one-pass steps are a serial/staged optimization; "
                "the mesh schedule pipelines via lookahead instead")
        if self.precision not in (None, "bf16"):
            raise ValueError(
                f"unknown precision {self.precision!r}; one of "
                "(None, 'bf16')")


# legacy route string -> (schedule, update); the historical spellings all
# ran the XLA backend with default staging knobs
LEGACY_ROUTES = {
    "mc": ("serial", "rank1"),
    "mc_staged": ("staged", "rank1"),
    "mc_blocked": ("serial", "panel"),
    "pmc": ("mesh", "rank1"),
    "pmc_blocked": ("mesh", "panel"),
}


# --------------------------------------------------------------------------
# backend hooks
# --------------------------------------------------------------------------

def resolve_backend(backend: str) -> str:
    """Pin ``"auto"`` to the concrete process backend.

    The resolved value keys plan caches, so the REPRO_KERNEL_BACKEND env
    override is captured at resolution time — flipping the env var later
    builds a new executable instead of serving a stale cached one.
    """
    if backend != "auto":
        return backend
    from repro.kernels import ops as _kops
    return _kops.kernel_backend()


def _hooks(backend: str, precision: Optional[str] = None,
           ) -> Tuple[Optional[Callable], Optional[Callable]]:
    """(update_fn, gemm_fn) for the resolved backend; None == inline jnp.

    The resolved backend is passed explicitly to the kernel entry points:
    an engine built for "pallas"/"interpret" always runs the kernel
    bodies, never the jnp reference, whatever the env var says later.
    A mixed-precision route (``precision="bf16"``) always goes through
    the kernel entry points — even on the xla backend — so the operand
    quantization lives in exactly one place (kernels/ops.py).
    """
    backend = resolve_backend(backend)
    if backend == "xla" and precision is None:
        return None, None
    from repro.kernels import ops as _kops
    return (functools.partial(_kops.rank1_update, backend=backend,
                              precision=precision),
            functools.partial(_kops.panel_update, backend=backend,
                              precision=precision))


# --------------------------------------------------------------------------
# shared sign / pivot helpers (used by the engine AND the GE/LU baselines)
# --------------------------------------------------------------------------

def guarded_pivot(p, dtype):
    """A division-safe pivot: 1 where ``p == 0`` (caller masks the result)."""
    return jnp.where(p == 0, jnp.ones((), dtype), p)


def combine_slogdet(parts) -> Tuple[jax.Array, jax.Array]:
    """Combine (sign, logabsdet) contributions multiplicatively."""
    sign = functools.reduce(lambda a, b: a * b, [p[0] for p in parts])
    logdet = functools.reduce(lambda a, b: a + b, [p[1] for p in parts])
    return sign, logdet


def cyclic_perm(n: int, p: int) -> np.ndarray:
    """Permutation mapping block layout to cyclic: out[d*L + i] = i*p + d."""
    return np.arange(n).reshape(n // p, p).T.reshape(-1)


def perm_parity(perm: np.ndarray) -> float:
    """Parity (+1/-1) of a permutation via cycle decomposition (O(n))."""
    seen = np.zeros(len(perm), dtype=bool)
    parity = 1.0
    for start in range(len(perm)):
        if seen[start]:
            continue
        clen = 0
        j = start
        while not seen[j]:
            seen[j] = True
            j = int(perm[j])
            clen += 1
        if clen % 2 == 0:
            parity = -parity
    return parity


# --------------------------------------------------------------------------
# the condensation step (rank-1) — THE shared implementation
# --------------------------------------------------------------------------

def _condense_step(buf: jax.Array, t, n_total: int, sign, logdet, *,
                   update_fn=None, step_fn=None):
    """One condensation step on the full static buffer.

    Live region at step ``t``: rows [t, N), cols [0, N - t).  Pivot row is
    row ``t`` (serial schedule); pivot column is the max-abs entry of the
    live part of row ``t``.  Returns the updated (buf, sign, logdet).

    ``step_fn(buf, t) -> (buf, l, p)`` replaces the three-pass pivot /
    swap / update sequence with the fused one-pass kernel
    (`repro.kernels.ops.fused_condense_step`) — bit-identical buffers;
    the sign/parity/log bookkeeping below is shared by both paths.
    """
    n = n_total
    m = n - t                       # live size (traced)
    last = m - 1

    if step_fn is not None:
        with obs.stage("engine.fused_step"):
            buf, l, p = step_fn(buf, t)
        swap_sign = jnp.where(l == last, 1.0, -1.0).astype(buf.dtype)
    else:
        col_ids = jnp.arange(n)
        live_col = col_ids < m

        with obs.stage("engine.pivot"):
            row = buf[t]                                # (N,)
            absrow = jnp.where(live_col, jnp.abs(row), -jnp.inf)
            l = jnp.argmax(absrow)                      # pivot col (traced)
            p = row[l]                                  # pivot value

        # --- column swap l <-> m-1 (paper §2.4) ----------------------------
        with obs.stage("engine.swap"):
            col_l = buf[:, l]
            col_last = buf[:, last]
            buf = buf.at[:, l].set(col_last)
            buf = buf.at[:, last].set(col_l)
            swap_sign = jnp.where(l == last, 1.0, -1.0).astype(buf.dtype)

            # pivot row in swapped coordinates, normalized by the pivot
            # (§2.3).
            row = row.at[l].set(row[last])
            # row[last] still holds the pre-swap value; the true pivot now
            # sits at position `last` in the buffer.  Force it so
            # pr[last] == 1 exactly, which zeroes the pivot column for all
            # updated rows.
            row = row.at[last].set(p)
            safe_p = guarded_pivot(p, buf.dtype)
            pr = jnp.where(p == 0, jnp.zeros_like(row), row / safe_p)

            # pivot column entries; zero at the pivot row so it stays
            # untouched.
            pc = buf[:, last]
            pc = pc.at[t].set(0.0)
            # Rows above t are dead; zero them too so the baseline buffer
            # stays finite (cosmetic — they are never read again).
            pc = jnp.where(jnp.arange(n) < t, 0.0, pc)

        with obs.stage("engine.update"):
            if update_fn is None:
                buf = buf - jnp.outer(pc, pr)
            else:
                buf = update_fn(buf, pc, pr)

    # sign bookkeeping: pivot sign, column swap, and Laplace expansion of the
    # pivot (active row 0, active column m-1) => (-1)^(m-1).
    parity = jnp.where((m - 1) % 2 == 0, 1.0, -1.0).astype(buf.dtype)
    sign = sign * jnp.sign(p) * swap_sign * parity
    logdet = logdet + log_abs(p)
    return buf, sign, logdet


def condense_steps(buf: jax.Array, n_steps: int, *, t0: int = 0,
                   update_fn=None, step_fn=None):
    """Run ``n_steps`` condensation steps starting at step offset ``t0``.

    Returns (buf, sign, logdet) with sign/logdet the *contribution* of these
    steps (combine with `combine_slogdet`).
    """
    n = buf.shape[0]

    def body(t, carry):
        b, s, ld = carry
        return _condense_step(b, t, n, s, ld, update_fn=update_fn,
                              step_fn=step_fn)

    # Derive the initial sign/logdet carries from `buf` so they inherit its
    # varying-manual-axes type when called inside shard_map (tail solve).
    zero = buf[0, 0] * 0
    return lax.fori_loop(t0, t0 + n_steps, body, (buf, zero + 1, zero))


def _step_hooks(use_kernel, fused: bool, precision: Optional[str]):
    """(update_fn, step_fn) for the serial/staged rank-1 drivers.

    ``fused`` routes every step through the one-pass kernel entry;
    otherwise a kernel request or a mixed-precision route builds the
    classic rank-1 update hook (precision quantization lives in
    kernels/ops.py).  (None, None) == inline jnp, the historical path.
    """
    req = _kernel_request(use_kernel)
    if fused:
        from repro.kernels import ops as _kops
        return None, functools.partial(_kops.fused_condense_step,
                                       backend=req or "xla",
                                       precision=precision)
    if req is not None or precision is not None:
        from repro.kernels import ops as _kops
        return functools.partial(_kops.rank1_update, backend=req or "xla",
                                 precision=precision), None
    return None, None


@functools.partial(jax.jit,
                   static_argnames=("use_kernel", "fused", "precision"))
def condense_full(a: jax.Array, *, use_kernel=False, fused: bool = False,
                  precision: Optional[str] = None):
    """Full serial rank-1 condensation — (sign, logabsdet).

    The faithful baseline (legacy `slogdet_condense`): every step updates
    the full static buffer.  ``use_kernel=True`` forces the Pallas rank-1
    kernel body (interpret mode off-TPU) regardless of the backend probe;
    a backend string ("pallas" | "interpret") pins it exactly.
    ``fused=True`` runs each step as ONE pass over the buffer (pivot +
    swap + update, bit-identical); ``precision="bf16"`` quantizes the
    rank-1 operands only.
    """
    n = a.shape[0]
    if a.ndim != 2 or a.shape[1] != n:
        raise ValueError(f"expected square matrix, got {a.shape}")
    if n == 0:
        return jnp.ones((), a.dtype), jnp.zeros((), a.dtype)
    if n == 1:
        return jnp.sign(a[0, 0]), log_abs(a[0, 0])

    update_fn, step_fn = _step_hooks(use_kernel, fused, precision)
    buf, sign, logdet = condense_steps(a, n - 1, update_fn=update_fn,
                                       step_fn=step_fn)
    p = buf[n - 1, 0]
    return sign * jnp.sign(p), logdet + log_abs(p)


# --------------------------------------------------------------------------
# the panel (rank-K) primitives — THE shared implementation
# --------------------------------------------------------------------------

def panel_factor(panel: jax.Array, m0, *, r_pos=0, update_fn=None):
    """Factorize a K-row condensation panel.

    Args:
      panel: (K, N) rows to eliminate (static shape; live cols are [0, m0)).
      m0:    live column count before this panel (may be traced).
      r_pos: number of live rows above the panel's rows in the global live
             ordering (0 for the serial schedule; ``p*(L-(r+1)K)`` for the
             round-robin parallel schedule) — used only for sign tracking.

    Returns ``(R, ls, sign, logdet)``:
      R:  (K, N) normalized pivot rows in the final (all-K-swaps) coordinates.
      ls: (K,) pivot column index chosen at each step, *in the coordinates
          current at that step* — consumers must replay the swaps in order.
    """
    K, N = panel.shape
    dt = panel.dtype
    cols = jnp.arange(N)

    def body(k, carry):
        buf, ls, sign, logdet = carry
        m = m0 - k                       # live cols at this step
        last = m - 1
        row = buf[k]
        absrow = jnp.where(cols < m, jnp.abs(row), -jnp.inf)
        l = jnp.argmax(absrow)
        pv = row[l]

        # swap columns l <-> last across the whole panel buffer
        cl = jnp.take(buf, l, axis=1)
        clast = jnp.take(buf, last, axis=1)
        buf = buf.at[:, l].set(clast)
        buf = buf.at[:, last].set(cl)

        # normalize the pivot row; store it back (it becomes R[k])
        row = buf[k]
        safe = guarded_pivot(pv, dt)
        pr = jnp.where(pv == 0, jnp.zeros_like(row), row / safe)
        pr = pr.at[last].set(jnp.where(pv == 0, pr[last], 1.0))
        buf = buf.at[k].set(pr)

        # rank-1 update of the remaining panel rows (k+1..K-1)
        pc = jnp.take(buf, last, axis=1)
        pc = jnp.where(jnp.arange(K) <= k, 0.0, pc)
        if update_fn is None:
            buf = buf - jnp.outer(pc, pr)
        else:
            buf = update_fn(buf, pc, pr)

        ls = ls.at[k].set(l.astype(ls.dtype))
        parity = jnp.where((r_pos + m - 1) % 2 == 0, 1.0, -1.0).astype(dt)
        swap_sign = jnp.where(l == last, 1.0, -1.0).astype(dt)
        sign = sign * jnp.sign(pv) * swap_sign * parity
        logdet = logdet + log_abs(pv)
        return buf, ls, sign, logdet

    zero = panel[0, 0] * 0
    ls0 = jnp.zeros((K,), jnp.int32) + (zero * 0).astype(jnp.int32)
    with obs.stage("engine.panel_factor"):
        R, ls, sign, logdet = lax.fori_loop(
            0, K, body, (panel, ls0, zero + 1, zero)
        )
    return R, ls, sign, logdet


def apply_panel(block: jax.Array, R: jax.Array, ls: jax.Array, m0,
                row_mask: jax.Array, *, gemm_fn=None, fused: bool = False):
    """Apply a factorized panel to a trailing row block.

    Args:
      block:    (Lb, N) trailing rows (full static width).
      R, ls:    panel factorization output (R in final coordinates).
      m0:       live columns before the panel.
      row_mask: (Lb,) 1.0 for rows that must be updated, 0.0 for dead/pivot rows.

    Returns the updated block.  ``gemm_fn(block, C, R)`` may override the
    final GEMM (Pallas kernel hook); default is ``block - C @ R``.
    ``fused=True`` replaces the K sequential swap scatters (2K passes
    over the block) with ONE composed-permutation gather — pure data
    movement, bit-identical, and the panel schedule's dominant traffic
    saving (the swaps re-stream the whole trailing block per panel).
    """
    Lb, N = block.shape
    K = R.shape[0]

    if fused:
        # compose the K swaps on an O(N) index vector, then gather once
        def perm_body(k, idx):
            l = ls[k]
            last = m0 - 1 - k
            il = idx[l]
            ilast = idx[last]
            return idx.at[l].set(ilast).at[last].set(il)

        with obs.stage("engine.panel_swap_gather"):
            idx = lax.fori_loop(0, K, perm_body, jnp.arange(N))
            block = jnp.take(block, idx, axis=1)
    else:
        # replay the K column swaps in order: swap ls[k] <-> (m0-1-k)
        def swap_body(k, blk):
            l = ls[k]
            last = m0 - 1 - k
            cl = jnp.take(blk, l, axis=1)
            clast = jnp.take(blk, last, axis=1)
            blk = blk.at[:, l].set(clast)
            blk = blk.at[:, last].set(cl)
            return blk

        block = lax.fori_loop(0, K, swap_body, block)

    # pivot-column block, reversed so column k corresponds to pivot k
    pc_cols = lax.dynamic_slice(block, (0, m0 - K), (Lb, K))   # (Lb, K)
    Pc = jnp.flip(pc_cols, axis=1)

    # T[k', k] = R[k', pos(pivot k)] — unit upper-triangular in (k', k)
    t_cols = lax.dynamic_slice(R, (0, m0 - K), (K, K))
    T = jnp.flip(t_cols, axis=1)

    # C @ T = Pc: substitute column by column, elementwise and in a fixed
    # order.  On the TPU, solve_triangular gives a row last-bit different
    # results in different programs, which broke the bit-identity of the
    # lookahead mesh schedule with the plain one.
    U = jnp.triu(T, 1)

    def substitute(j, X):
        xj = lax.dynamic_slice_in_dim(X, j, 1, axis=1)     # final column j
        return X - xj * lax.dynamic_slice_in_dim(U, j, 1, axis=0)

    C = lax.fori_loop(0, K, substitute, Pc) * row_mask[:, None]

    with obs.stage("engine.panel_apply"):
        if gemm_fn is None:
            return block - matmul(C, R)
        return gemm_fn(block, C, R)


def _kernel_request(use_kernel) -> Optional[str]:
    """Normalize a driver's ``use_kernel`` argument to a backend request.

    ``False``/``None`` -> None (inline jnp); ``True`` -> "pallas" (the
    historical explicit-kernel spelling; off-TPU it degrades to the
    interpreter inside kernels.ops); a string passes through verbatim so
    an "interpret" config is honored even on TPU.
    """
    if not use_kernel:
        return None
    return "pallas" if use_kernel is True else use_kernel


def panel_factor_dispatch(use_kernel):
    """The panel-factorization hook for a backend choice.

    A truthy ``use_kernel`` (True or a backend string) routes full panels
    through the VMEM-resident Pallas kernel (`kernels.ops
    .panel_factor_vmem`, §Perf P0/It3 — one HBM read + write per panel
    instead of k) whenever the panel fits the VMEM budget; oversized
    panels and the XLA backend use the shared jnp implementation.  Both
    are bit-identical (asserted in test_kernels).
    """
    req = _kernel_request(use_kernel)
    if req is None:
        return lambda panel, m0, r_pos=0, update_fn=None: panel_factor(
            panel, m0, r_pos=r_pos, update_fn=update_fn)

    def factor(panel, m0, r_pos=0, update_fn=None):
        from repro.kernels import ops as _kops
        from repro.kernels.panel_factor import VMEM_BUDGET
        k, n = panel.shape
        if k * n * panel.dtype.itemsize <= VMEM_BUDGET:
            return _kops.panel_factor_vmem(panel, m0, r_pos, backend=req)
        # counted, so a reader of kernel.dispatch sees the kernel skipped
        obs.inc("kernel.dispatch", op="panel_factor_vmem", backend="xla")
        return panel_factor(panel, m0, r_pos=r_pos, update_fn=update_fn)

    return factor


def panel_rounds_serial(buf: jax.Array, n_panels: int, k: int, *,
                        q0: int = 0, gemm_fn=None, update_fn=None,
                        factor_fn=None, fused: bool = False):
    """Run ``n_panels`` serial K-panels starting at panel offset ``q0``.

    The serial-schedule panel loop shared by the blocked driver and the
    staged x panel stages.  Returns (buf, sign, logdet) contributions.
    """
    n = buf.shape[0]
    rows = jnp.arange(n)
    if factor_fn is None:
        factor_fn = panel_factor_dispatch(False)

    def body(q, carry):
        b, sign, logdet = carry
        t0 = q * k
        m0 = n - t0
        panel = lax.dynamic_slice(b, (t0, 0), (k, n))
        R, ls, psign, plogdet = factor_fn(panel, m0, update_fn=update_fn)
        row_mask = (rows >= t0 + k).astype(b.dtype)
        b = apply_panel(b, R, ls, m0, row_mask, gemm_fn=gemm_fn,
                        fused=fused)
        # park the factorized rows back so dead region stays finite
        b = lax.dynamic_update_slice(b, R, (t0, 0))
        return b, sign * psign, logdet + plogdet

    zero = buf[0, 0] * 0
    return lax.fori_loop(q0, q0 + n_panels, body, (buf, zero + 1, zero))


def _gemm_hook(use_kernel, precision: Optional[str]):
    """The trailing-GEMM hook for the serial/staged panel drivers."""
    req = _kernel_request(use_kernel)
    if req is None and precision is None:
        return None
    from repro.kernels import ops as _kops
    return functools.partial(_kops.panel_update, backend=req or "xla",
                             precision=precision)


@functools.partial(jax.jit,
                   static_argnames=("k", "use_kernel", "fused", "precision"))
def blocked_full(a: jax.Array, *, k: int = 32, use_kernel=False,
                 fused: bool = False, precision: Optional[str] = None):
    """Serial blocked condensation: panels of ``k`` rows, rank-k GEMMs.

    Numerically equivalent to `condense_full` up to roundoff; exercises the
    exact panel/trailing structure used by the mesh x panel variant.
    """
    n = a.shape[0]
    if a.ndim != 2 or a.shape[1] != n:
        raise ValueError(f"expected square matrix, got {a.shape}")
    if n <= k:
        return condense_full(a, use_kernel=use_kernel, fused=fused,
                             precision=precision)

    gemm_fn = _gemm_hook(use_kernel, precision)
    n_panels = (n - 1) // k
    buf, sign, logdet = panel_rounds_serial(
        a, n_panels, k, gemm_fn=gemm_fn,
        factor_fn=panel_factor_dispatch(use_kernel), fused=fused)

    # remainder: rank-1 steps from t0 = n_panels*k to n-2, then the 1x1 tail
    t0 = n_panels * k
    if fused or precision is not None:
        update_fn, step_fn = _step_hooks(use_kernel, fused, precision)
    else:
        update_fn, step_fn = None, None  # historical inline-jnp remainder
    buf, rsign, rlogdet = condense_steps(buf, n - 1 - t0, t0=t0,
                                         update_fn=update_fn,
                                         step_fn=step_fn)
    p = buf[n - 1, 0]
    return (sign * rsign * jnp.sign(p),
            logdet + rlogdet + log_abs(p))


# --------------------------------------------------------------------------
# staged schedule (geometric re-jit over shrinking static shapes)
# --------------------------------------------------------------------------

def stage_schedule(n: int, shrink: float, min_size: int):
    """Static (size, steps) schedule: run `steps` at static size `size`."""
    sched = []
    size = n
    while size > min_size:
        nxt = max(min_size, int(math.ceil(size * shrink)))
        steps = size - nxt
        if steps <= 0:
            break
        sched.append((size, steps))
        size = nxt
    sched.append((size, size - 1))  # finish to 1x1
    return sched


@functools.partial(jax.jit, static_argnames=("steps", "use_kernel", "fused",
                                             "precision"))
def _staged_stage_rank1(buf, steps: int, use_kernel=False,
                        fused: bool = False,
                        precision: Optional[str] = None):
    if fused or precision is not None:
        update_fn, step_fn = _step_hooks(use_kernel, fused, precision)
    else:
        update_fn, step_fn = None, None  # historical inline-jnp stages
    b, s, ld = condense_steps(buf, steps, update_fn=update_fn,
                              step_fn=step_fn)
    n = buf.shape[0]
    live = lax.slice(b, (steps, 0), (n, n - steps))
    return live, s, ld


@functools.partial(jax.jit, static_argnames=("steps", "k", "use_kernel",
                                             "fused", "precision"))
def _staged_stage_panel(buf, steps: int, k: int, use_kernel=False,
                        fused: bool = False,
                        precision: Optional[str] = None):
    """One staged stage eliminating ``steps`` rows via K-panels + remainder."""
    gemm_fn = _gemm_hook(use_kernel, precision)
    n = buf.shape[0]
    n_panels = steps // k
    b, s, ld = panel_rounds_serial(
        buf, n_panels, k, gemm_fn=gemm_fn,
        factor_fn=panel_factor_dispatch(use_kernel), fused=fused)
    rem = steps - n_panels * k
    if rem > 0:
        if fused or precision is not None:
            update_fn, step_fn = _step_hooks(use_kernel, fused, precision)
        else:
            update_fn, step_fn = None, None
        b, rs, rld = condense_steps(b, rem, t0=n_panels * k,
                                    update_fn=update_fn, step_fn=step_fn)
        s, ld = s * rs, ld + rld
    live = lax.slice(b, (steps, 0), (n, n - steps))
    return live, s, ld


def staged_full(a: jax.Array, *, shrink: float = 0.75, min_size: int = 64,
                update: str = "rank1", k: int = 32,
                use_kernel=False, fused: bool = False,
                precision: Optional[str] = None):
    """Geometric shape-staged condensation (§Perf optimization 1).

    Runs condensation in stages of static shape, slicing out the live prefix
    between stages.  FLOP waste drops from ~3x (full static buffer) to ~1.5x
    with shrink=0.75 at the cost of a handful of compilations.  With
    ``update="panel"`` each stage runs rank-K panels (MXU GEMMs) instead of
    rank-1 steps — the schedule x update combination no legacy route named.
    """
    n = a.shape[0]
    if n <= min_size:
        if update == "panel" and n > k:
            return blocked_full(a, k=k, use_kernel=use_kernel, fused=fused,
                                precision=precision)
        return condense_full(a, use_kernel=use_kernel, fused=fused,
                             precision=precision)
    parts = []
    buf = a
    for size, steps in stage_schedule(n, shrink, min_size):
        if buf.shape[0] != size:  # defensive; schedule and buffer must agree
            raise AssertionError((buf.shape, size))
        if size - steps <= 1:
            if update == "panel" and size > k:
                parts.append(blocked_full(buf, k=k, use_kernel=use_kernel,
                                          fused=fused, precision=precision))
            else:
                parts.append(condense_full(buf, use_kernel=use_kernel,
                                           fused=fused, precision=precision))
            buf = None
            break
        if update == "panel" and steps >= k:
            buf, s, ld = _staged_stage_panel(buf, steps, k, use_kernel,
                                             fused, precision)
        else:
            buf, s, ld = _staged_stage_rank1(buf, steps, use_kernel,
                                             fused, precision)
        parts.append((s, ld))
    if buf is not None:
        if update == "panel" and buf.shape[0] > k:
            parts.append(blocked_full(buf, k=k, use_kernel=use_kernel,
                                      fused=fused, precision=precision))
        else:
            parts.append(condense_full(buf, use_kernel=use_kernel,
                                       fused=fused, precision=precision))
    return combine_slogdet(parts)


# --------------------------------------------------------------------------
# mesh schedule (round-robin block rows, shard_map)
# --------------------------------------------------------------------------

def mc_step_fn(axis_name: str, *, update_fn=None):
    """Per-global-step body of parallel MC for use inside shard_map.

    ``local`` has shape (L, N) — the device's contiguous row block.  Global
    step ``t`` maps to (round ``i = t // P``, owner ``p = t % P``); the owner
    eliminates its local row ``i``.  Returns ``step(t, carry)`` with carry
    ``(local, sign, logdet)`` where sign/logdet are *per-device partial*
    contributions (combine with psum / product at the end, paper step 6).
    """

    def step(t, carry):
        local, sign, logdet = carry
        L, N = local.shape
        P = lax.axis_size(axis_name)
        me = lax.axis_index(axis_name)
        i = t // P                            # round = owner's local row index
        p = t % P                             # owner device
        m = N - t                             # live column count
        last = m - 1                          # post-swap pivot column
        mine = me == p

        # ---- owner: local pivot choice + row normalization (no comm) -------
        with obs.stage("engine.pivot"):
            row = local[i]
            live_col = jnp.arange(N) < m
            absrow = jnp.where(live_col, jnp.abs(row), -jnp.inf)
            l = jnp.argmax(absrow)
            pv = row[l]
            # swap l <-> last inside the pivot row, normalize: pr[last] == 1
            rl, rlast = row[l], row[last]
            row = row.at[l].set(rlast).at[last].set(pv)
            safe = guarded_pivot(pv, local.dtype)
            pr = jnp.where(pv == 0, jnp.zeros_like(row), row / safe)
            pr = pr.at[last].set(jnp.where(pv == 0, pr[last], 1.0))

        # ---- broadcast: ONE collective for (normalized row, column index) ---
        with obs.stage("engine.broadcast"):
            pr_b, l_b = lax.psum(
                (jnp.where(mine, pr, jnp.zeros_like(pr)),
                 jnp.where(mine, l, jnp.zeros_like(l))),
                axis_name,
            )

        # ---- every device: column swap l_b <-> last on its block ------------
        with obs.stage("engine.swap"):
            cl = jnp.take(local, l_b, axis=1)
            clast = jnp.take(local, last, axis=1)
            local = local.at[:, l_b].set(clast)
            local = local.at[:, last].set(cl)

        # ---- rank-1 condensation update on live rows -------------------------
        with obs.stage("engine.update"):
            pc = jnp.take(local, last, axis=1)
            dead = i + (me <= p)              # rows [0, dead) are retired
            pc = jnp.where(jnp.arange(L) < dead, 0.0, pc)
            if update_fn is None:
                local = local - jnp.outer(pc, pr_b)
            else:
                local = update_fn(local, pc, pr_b)

        # ---- owner accumulates its logdet/sign contribution ------------------
        r_pos = p * (L - 1 - i)               # live rows above the pivot row
        parity = jnp.where((r_pos + m - 1) % 2 == 0, 1.0, -1.0).astype(local.dtype)
        swap_sign = jnp.where(l == last, 1.0, -1.0).astype(local.dtype)
        step_sign = jnp.sign(pv) * swap_sign * parity
        sign = jnp.where(mine, sign * step_sign, sign)
        logdet = logdet + jnp.where(mine, log_abs(pv), 0.0)
        return local, sign, logdet

    return step


def mc_local_phase(local, axis_name: str, *, t0: int = 0,
                   n_steps: int | None = None, update_fn=None):
    """Run the distributed condensation phase; local block (L, N).

    Returns (local, sign_partial, logdet_partial) after ``n_steps`` global
    steps starting at ``t0`` (default: the full ``(L-1)*P`` schedule).
    """
    L, N = local.shape
    P = lax.axis_size(axis_name)
    if n_steps is None:
        n_steps = (L - 1) * P - t0
    step = mc_step_fn(axis_name, update_fn=update_fn)
    sign0 = lax.pcast(jnp.ones((), local.dtype), axis_name, to="varying")
    ld0 = lax.pcast(jnp.zeros((), local.dtype), axis_name, to="varying")
    return lax.fori_loop(t0, t0 + n_steps, step, (local, sign0, ld0))


def mesh_tail(local, sign, logdet, axis_name: str):
    """The shared P x P tail reduction (paper pseudocode steps 5-8).

    Each device holds ONE live row (its last); ``all_gather`` forms the
    final P x P matrix, the tail slogdet runs redundantly on every device
    (cheaper than gather-to-master + scalar scatter on TPU), and the
    per-device partial (sign, logdet) contributions combine via
    psum / all_gather-product.  Returns per-device (1,) outputs for the
    shard_map out_specs.
    """
    L, N = local.shape
    P = lax.axis_size(axis_name)
    with obs.stage("engine.mesh_tail"):
        # slice the live columns (the [0, P) prefix) BEFORE the gather:
        # the collective moves 8*P^2 bytes, not 8*N*P — gathering full
        # rows only to discard N - P columns inflated tail traffic N/P x
        live = lax.dynamic_slice(local, (L - 1, 0), (1, N))[0, :]
        live = lax.slice(live, (0,), (P,))          # live cols are prefix
        tail = lax.all_gather(live, axis_name)      # (P, P): device-ordered
        tsign, tlogdet = condense_full(tail)        # redundant on all devs

        logdet_total = lax.psum(logdet, axis_name) + tlogdet
        signs = lax.all_gather(sign, axis_name)
        sign_total = jnp.prod(signs) * tsign
        return sign_total.reshape(1), logdet_total.reshape(1)


def _mesh_rank1_kernel(axis_name: str, update_fn=None):
    def kernel(local):
        local, sign, logdet = mc_local_phase(local, axis_name,
                                             update_fn=update_fn)
        return mesh_tail(local, sign, logdet, axis_name)

    return kernel


def _mesh_panel_kernel(axis_name: str, k: int, *, gemm_fn=None,
                       update_fn=None, factor_fn=None):
    """Round-robin K-panel mesh kernel.

    Device ``p`` factorizes panels of ``k`` of its own rows (keeping MC's
    local pivoting — still no global pivot search), broadcasts ``(R, ls)``
    once per panel, and every device applies the rank-k GEMM to its live
    rows.  Remainder rows use the rank-1 schedule; the final P x P tail is
    gathered and solved redundantly (`mesh_tail`).
    """

    if factor_fn is None:
        factor_fn = panel_factor_dispatch(False)

    def kernel(local):
        L, N = local.shape
        P = lax.axis_size(axis_name)
        me = lax.axis_index(axis_name)
        n_rounds = (L - 1) // k
        lrow = jnp.arange(L)
        zero = local[0, 0] * 0

        def panel_step(g, carry):
            """Global panel index g = r*P + p."""
            local, sign, logdet = carry
            r = g // P
            p = g % P
            t0 = g * k
            m0 = N - t0
            mine = me == p

            panel = lax.dynamic_slice(local, (r * k, 0), (k, N))
            r_pos = p * (L - (r + 1) * k)
            R, ls, psign, plogdet = factor_fn(panel, m0, r_pos=r_pos,
                                              update_fn=update_fn)

            R_b, ls_b = lax.psum(
                (jnp.where(mine, R, jnp.zeros_like(R)),
                 jnp.where(mine, ls, jnp.zeros_like(ls))),
                axis_name,
            )

            dead = jnp.where(me <= p, (r + 1) * k, r * k)
            row_mask = (lrow >= dead).astype(local.dtype)
            local = apply_panel(local, R_b, ls_b, m0, row_mask,
                                gemm_fn=gemm_fn)

            sign = jnp.where(mine, sign * psign, sign)
            logdet = logdet + jnp.where(mine, plogdet, zero)
            return local, sign, logdet

        carry = (local, zero + 1, zero)
        if n_rounds > 0:  # static: L, k known at trace time
            carry = lax.fori_loop(0, n_rounds * P, panel_step, carry)
        local, sign, logdet = carry

        # remainder rows: rank-1 schedule continuing at t = n_rounds*k per dev
        rem = (L - 1) - n_rounds * k
        if rem > 0:
            step = mc_step_fn(axis_name, update_fn=update_fn)
            t_start = n_rounds * k * P
            local, rsign, rlogdet = lax.fori_loop(
                t_start, t_start + rem * P, step, (local, zero + 1, zero))
            sign = sign * rsign
            logdet = logdet + rlogdet

        return mesh_tail(local, sign, logdet, axis_name)

    return kernel


# --------------------------------------------------------------------------
# lookahead mesh kernels (double-buffered broadcast, LU-style pipelining)
# --------------------------------------------------------------------------
#
# The plain mesh kernels serialize per step: factor -> broadcast -> bulk
# update, so every collective sits on the critical path between the
# owner's factorization and everyone's trailing update.  The lookahead
# kernels restructure the loop so the broadcast of step/panel g+1 is
# *issued before* the bulk update of step/panel g and only *consumed on
# the next iteration* — double buffering.  With no data dependency
# between the in-flight collective and the trailing update, XLA's
# latency-hiding scheduler overlaps them; per panel the exposed
# (non-overlapped) collective count drops from one to zero at steady
# state.
#
# The price is an early apply: before the owner of g+1 can factor its
# rows, those rows need step/panel g applied.  The early apply runs on a
# sliced COPY (k x N for panels, 1 x N for rank-1) with exactly the
# per-row arithmetic of the bulk update, so the pivots it selects — and
# therefore (sign, logabsdet) — are bit-identical to the non-lookahead
# schedule (asserted across schedule x update x P in tests).  `local`
# itself is only ever advanced by the same bulk updates as before.


def _mesh_rank1_lookahead_kernel(axis_name: str, update_fn=None):
    """Rank-1 mesh kernel with single-row lookahead.

    Carry holds the already-broadcast ``(pr, l)`` of the current step;
    each iteration early-applies the current step to the *next* pivot
    row, factors/normalizes it, issues its broadcast, and only then runs
    the bulk rank-1 update of the current step.
    """

    def select_pivot(row, m, dtype):
        """Pivot choice + §2.3/§2.4 row normalization (owner-local)."""
        N = row.shape[0]
        last = m - 1
        absrow = jnp.where(jnp.arange(N) < m, jnp.abs(row), -jnp.inf)
        l = jnp.argmax(absrow)
        pv = row[l]
        rlast = row[last]
        row = row.at[l].set(rlast).at[last].set(pv)
        safe = guarded_pivot(pv, dtype)
        pr = jnp.where(pv == 0, jnp.zeros_like(row), row / safe)
        pr = pr.at[last].set(jnp.where(pv == 0, pr[last], 1.0))
        return pr, l, pv

    def kernel(local):
        L, N = local.shape
        P = lax.axis_size(axis_name)
        me = lax.axis_index(axis_name)
        dt = local.dtype
        zero = local[0, 0] * 0                # device-varying scalar zero
        n_steps = (L - 1) * P
        if n_steps == 0:
            return mesh_tail(local, zero + 1, zero, axis_name)

        def bcast(pr, l, mine):
            return lax.psum(
                (jnp.where(mine, pr, jnp.zeros_like(pr)),
                 jnp.where(mine, l, jnp.zeros_like(l))),
                axis_name,
            )

        def contribution(pv, l, m, i, p, sign, logdet, mine):
            r_pos = p * (L - 1 - i)
            parity = jnp.where((r_pos + m - 1) % 2 == 0, 1.0, -1.0).astype(dt)
            swap_sign = jnp.where(l == m - 1, 1.0, -1.0).astype(dt)
            step_sign = jnp.sign(pv) * swap_sign * parity
            sign = jnp.where(mine, sign * step_sign, sign)
            logdet = logdet + jnp.where(mine, log_abs(pv), zero)
            return sign, logdet

        # prologue: step 0's pivot row, broadcast in flight before the loop
        pr0, l0, pv0 = select_pivot(local[0], N, dt)
        sign, logdet = contribution(pv0, l0, N, 0, 0, zero + 1, zero, me == 0)
        pr_b, l_b = bcast(pr0, l0, me == 0)

        def body(t, carry):
            local, pr_b, l_b, sign, logdet = carry
            m = N - t
            last = m - 1

            # ---- lookahead: early-apply step t to the NEXT pivot row,
            # factor it, and issue its broadcast before the bulk update
            with obs.stage("engine.lookahead_factor"):
                t1 = t + 1
                i1 = t1 // P
                p1 = t1 % P
                mine1 = me == p1
                row = local[i1]
                rl, rlast = row[l_b], row[last]
                row = row.at[l_b].set(rlast).at[last].set(rl)
                pc_i = row[last]
                if update_fn is None:
                    row = (row[None, :]
                           - jnp.outer(pc_i[None], pr_b))[0]
                else:
                    row = update_fn(row[None, :], pc_i[None], pr_b)[0]
                pr1, l1, pv1 = select_pivot(row, m - 1, dt)
            with obs.stage("engine.broadcast"):
                pr_nb, l_nb = bcast(pr1, l1, mine1)

            # ---- bulk: the plain step-t swap + rank-1 update ------------
            with obs.stage("engine.swap"):
                cl = jnp.take(local, l_b, axis=1)
                clast = jnp.take(local, last, axis=1)
                local = local.at[:, l_b].set(clast)
                local = local.at[:, last].set(cl)
            with obs.stage("engine.update"):
                i = t // P
                p = t % P
                pc = jnp.take(local, last, axis=1)
                dead = i + (me <= p)
                pc = jnp.where(jnp.arange(L) < dead, 0.0, pc)
                if update_fn is None:
                    local = local - jnp.outer(pc, pr_b)
                else:
                    local = update_fn(local, pc, pr_b)

            sign, logdet = contribution(pv1, l1, m - 1, i1, p1,
                                        sign, logdet, mine1)
            return local, pr_nb, l_nb, sign, logdet

        carry = (local, pr_b, l_b, sign, logdet)
        if n_steps > 1:
            carry = lax.fori_loop(0, n_steps - 1, body, carry)
        local, pr_b, l_b, sign, logdet = carry

        # epilogue: bulk update of the final step (its broadcast is the
        # one left in the carry; no further lookahead to issue)
        t_last = n_steps - 1
        m = N - t_last
        last = m - 1
        cl = jnp.take(local, l_b, axis=1)
        clast = jnp.take(local, last, axis=1)
        local = local.at[:, l_b].set(clast)
        local = local.at[:, last].set(cl)
        pc = jnp.take(local, last, axis=1)
        dead = t_last // P + (me <= t_last % P)
        pc = jnp.where(jnp.arange(L) < dead, 0.0, pc)
        if update_fn is None:
            local = local - jnp.outer(pc, pr_b)
        else:
            local = update_fn(local, pc, pr_b)

        return mesh_tail(local, sign, logdet, axis_name)

    return kernel


def _mesh_panel_lookahead_kernel(axis_name: str, k: int, *, gemm_fn=None,
                                 update_fn=None, factor_fn=None):
    """Round-robin K-panel mesh kernel with LU-style lookahead.

    The owner of panel g+1 factors it from an early-applied (K x N) copy
    while every device still has the bulk rank-K GEMM of panel g ahead of
    it in program order; the ``(R, ls)`` broadcast of panel g+1 is issued
    between the two, double-buffered through the loop carry, so the
    collective overlaps the trailing GEMM instead of serializing with
    it.  Remainder rows and the P x P tail are shared with the plain
    kernel (bit-identical by construction).
    """

    if factor_fn is None:
        factor_fn = panel_factor_dispatch(False)

    def kernel(local):
        L, N = local.shape
        P = lax.axis_size(axis_name)
        me = lax.axis_index(axis_name)
        n_rounds = (L - 1) // k
        n_panels = n_rounds * P
        lrow = jnp.arange(L)
        zero = local[0, 0] * 0
        ones_k = jnp.ones((k,), local.dtype)

        def factor_at(local, g):
            """Factor global panel g from MY rows (valid on the owner)."""
            r = g // P
            p = g % P
            panel = lax.dynamic_slice(local, (r * k, 0), (k, N))
            r_pos = p * (L - (r + 1) * k)
            return factor_fn(panel, N - g * k, r_pos=r_pos,
                             update_fn=update_fn)

        def bcast(R, ls, mine):
            return lax.psum(
                (jnp.where(mine, R, jnp.zeros_like(R)),
                 jnp.where(mine, ls, jnp.zeros_like(ls))),
                axis_name,
            )

        def bulk_apply(local, R_b, ls_b, g):
            r = g // P
            p = g % P
            dead = jnp.where(me <= p, (r + 1) * k, r * k)
            row_mask = (lrow >= dead).astype(local.dtype)
            return apply_panel(local, R_b, ls_b, N - g * k, row_mask,
                               gemm_fn=gemm_fn)

        sign, logdet = zero + 1, zero
        if n_panels > 0:
            # prologue: factor + broadcast panel 0 (no trailing GEMM to
            # hide it behind yet)
            R0, ls0, psign0, plogdet0 = factor_at(local, 0)
            mine0 = me == 0
            sign = jnp.where(mine0, sign * psign0, sign)
            logdet = logdet + jnp.where(mine0, plogdet0, zero)
            R_b, ls_b = bcast(R0, ls0, mine0)

            def panel_step(g, carry):
                """Bulk-apply panel g; lookahead-factor + broadcast g+1."""
                local, R_b, ls_b, sign, logdet = carry
                g1 = g + 1
                r1 = g1 // P
                p1 = g1 % P
                mine1 = me == p1

                # ---- lookahead: early-apply panel g to MY candidate
                # rows for panel g+1 (a sliced copy — `local` is only
                # ever advanced by the bulk applies), then factor
                with obs.stage("engine.lookahead_factor"):
                    nxt = lax.dynamic_slice(local, (r1 * k, 0), (k, N))
                    nxt = apply_panel(nxt, R_b, ls_b, N - g * k, ones_k,
                                      gemm_fn=gemm_fn)
                    r_pos1 = p1 * (L - (r1 + 1) * k)
                    R1, ls1, psign1, plogdet1 = factor_fn(
                        nxt, N - g1 * k, r_pos=r_pos1, update_fn=update_fn)
                # issue the double-buffered broadcast of panel g+1 — no
                # data dependency with the bulk GEMM below, so the
                # collective can overlap it
                with obs.stage("engine.broadcast"):
                    R_nb, ls_nb = bcast(R1, ls1, mine1)

                # ---- bulk rank-K GEMM of panel g on the live rows -------
                local = bulk_apply(local, R_b, ls_b, g)

                sign = jnp.where(mine1, sign * psign1, sign)
                logdet = logdet + jnp.where(mine1, plogdet1, zero)
                return local, R_nb, ls_nb, sign, logdet

            carry = (local, R_b, ls_b, sign, logdet)
            if n_panels > 1:
                carry = lax.fori_loop(0, n_panels - 1, panel_step, carry)
            local, R_b, ls_b, sign, logdet = carry
            # epilogue: the last panel's bulk GEMM
            local = bulk_apply(local, R_b, ls_b, n_panels - 1)

        # remainder rows: rank-1 schedule continuing at t = n_rounds*k per
        # device — shared with the plain kernel, bit-identical
        rem = (L - 1) - n_rounds * k
        if rem > 0:
            step = mc_step_fn(axis_name, update_fn=update_fn)
            t_start = n_rounds * k * P
            local, rsign, rlogdet = lax.fori_loop(
                t_start, t_start + rem * P, step, (local, zero + 1, zero))
            sign = sign * rsign
            logdet = logdet + rlogdet

        return mesh_tail(local, sign, logdet, axis_name)

    return kernel


# --------------------------------------------------------------------------
# engine builders — the single entry points every route resolves to
# --------------------------------------------------------------------------

def build_serial(cfg: EngineConfig) -> Callable:
    """``a -> (sign, logabsdet)`` for the serial / staged schedules."""
    if cfg.schedule == "mesh":
        raise ValueError("mesh schedule needs build_mesh(cfg, mesh)")
    rb = resolve_backend(cfg.backend)
    # drivers take the exact backend string so "interpret" is honored
    # even on TPU (False == inline jnp, same as the xla hooks)
    use_kernel = False if rb == "xla" else rb

    if cfg.schedule == "serial":
        if cfg.update == "rank1":
            return lambda a: condense_full(a, use_kernel=use_kernel,
                                           fused=cfg.fused,
                                           precision=cfg.precision)
        k = cfg.panel_k
        return lambda a: blocked_full(a, k=k, use_kernel=use_kernel,
                                      fused=cfg.fused,
                                      precision=cfg.precision)

    # staged
    return lambda a: staged_full(
        a, shrink=cfg.shrink, min_size=cfg.min_size, update=cfg.update,
        k=cfg.panel_k, use_kernel=use_kernel, fused=cfg.fused,
        precision=cfg.precision)


def build_mesh(cfg: EngineConfig, mesh, axis_name: str = "rows", *,
               update_fn=None, gemm_fn=None) -> Callable:
    """``a -> (sign, logabsdet)`` over a 1-D device mesh.

    ``update_fn`` / ``gemm_fn`` override the backend hooks (benchmark /
    test injection); by default they resolve from ``cfg.backend``.
    """
    if cfg.schedule != "mesh":
        raise ValueError(f"build_mesh needs schedule='mesh', got {cfg.schedule!r}")
    nproc = int(mesh.shape[axis_name])
    factor_fn = None
    if update_fn is None and gemm_fn is None:
        update_fn, gemm_fn = _hooks(cfg.backend, cfg.precision)
        if gemm_fn is not None and resolve_backend(cfg.backend) != "xla":
            factor_fn = panel_factor_dispatch(resolve_backend(cfg.backend))

    if cfg.update == "rank1":
        if cfg.lookahead:
            kernel = _mesh_rank1_lookahead_kernel(axis_name,
                                                  update_fn=update_fn)
        else:
            kernel = _mesh_rank1_kernel(axis_name, update_fn=update_fn)
    elif cfg.lookahead:
        kernel = _mesh_panel_lookahead_kernel(axis_name, cfg.panel_k,
                                              gemm_fn=gemm_fn,
                                              update_fn=update_fn,
                                              factor_fn=factor_fn)
    else:
        kernel = _mesh_panel_kernel(axis_name, cfg.panel_k,
                                    gemm_fn=gemm_fn, update_fn=update_fn,
                                    factor_fn=factor_fn)

    shmapped = jax.shard_map(
        kernel,
        mesh=mesh,
        in_specs=(PartitionSpec(axis_name, None),),
        out_specs=(PartitionSpec(axis_name), PartitionSpec(axis_name)),
    )

    @jax.jit
    def run(a):
        n = a.shape[0]
        if n % nproc:
            raise ValueError(f"N={n} not divisible by mesh size {nproc}")
        sign, logdet = shmapped(a)
        return sign[0], logdet[0]

    return run


def engine_slogdet(a: jax.Array, cfg: EngineConfig = EngineConfig(), *,
                   mesh=None, axis_name: str = "rows"):
    """One-shot engine execution (tests / benchmarks / exploration).

    Production code should build once via `build_serial` / `build_mesh`
    (or, better, `repro.plan(..., method="exact", ...)`) and reuse.
    """
    if cfg.schedule == "mesh":
        if mesh is None:
            raise ValueError("mesh schedule requires a mesh")
        return build_mesh(cfg, mesh, axis_name)(a)
    return build_serial(cfg)(a)

"""`LogdetService` — the warm, continuously-batching logdet engine.

Ties the pieces together: admission (`batching.admit`), the bucket
ladder, the warm `PlanCache`, AOT plan preloading, and a single drain
thread that coalesces whatever is pending into homogeneous padded
stacks and runs each through one warm executable::

    with LogdetService(ServeConfig(buckets=(64, 128, 256))) as svc:
        svc.warmup()
        fut = svc.submit(a, method="auto")      # returns a Future
        result = fut.result()                   # per-request LogdetResult

Throughput comes from never compiling at request time: every request is
padded up to a bucket rung and drained through a plan that was warmed at
startup (or AOT-loaded from ``plan_dir`` — see repro.serve.aot).  The
drain is one thread by design: requests queue while a batch executes and
are coalesced when it finishes — continuous batching, no locks on the
hot path, strict FIFO fairness.

Ordering guarantees: admission order is request order (`submit` is the
serialization point); the drain preserves FIFO across groups (oldest
request first) and within a group (results are split back by position).
Completion order across *different* buckets is not guaranteed — a small
matrix behind a large one may finish first; per-request futures make
that safe.
"""
from __future__ import annotations

import dataclasses
import os
import threading
import time
from dataclasses import dataclass, field
from typing import Dict, Optional, Sequence, Tuple

import numpy as np
import jax.numpy as jnp

from repro import obs
from repro.core.configs import ESTIMATOR_METHODS, METHODS
from repro.core.result import LogdetResult
from repro.serve.batching import BatchGroup, Request, admit, coalesce
from repro.serve.bucket import (
    DEFAULT_BUCKETS, BucketLadder, PlanCache, bucket_batch, stack_to_bucket,
)

__all__ = ["ServeConfig", "LogdetService", "ServiceClosed", "plan_filename"]


class ServiceClosed(RuntimeError):
    """The service is closed.

    Raised by `LogdetService.submit` after `close()`, and set on the
    futures of requests that were still queued when the drain thread
    stopped — a queued request must fail loudly, never hang its client.
    """


@dataclass(frozen=True)
class ServeConfig:
    """Serving knobs — everything the deployment tunes.

    ``buckets``        the shape ladder (requests above the top rung are
                       rejected at admission)
    ``max_batch``      largest stack one drain dispatch runs
    ``max_wait_ms``    how long the drain lingers for a batch to fill
                       once at least one request is pending (0 = drain
                       immediately; latency-vs-throughput dial)
    ``cache_capacity`` warm executables kept before LRU eviction
    ``plan_dir``       directory of AOT-exported plans to load instead
                       of compiling (see ``python -m repro.serve export``)
    ``default_method`` method used when a request does not name one
    ``dtype``          serving dtype; requests are cast on admission
                       (float32: the TPU's kernels have no float64)
    ``seed``           base of the per-batch estimator key sequence
    """
    buckets: Tuple[int, ...] = DEFAULT_BUCKETS
    max_batch: int = 16
    max_wait_ms: float = 2.0
    cache_capacity: int = 32
    plan_dir: Optional[str] = None
    default_method: str = "auto"
    dtype: str = "float32"
    seed: int = 0

    def __post_init__(self):
        if self.max_batch < 1:
            raise ValueError(f"max_batch must be >= 1, got {self.max_batch}")
        if self.max_wait_ms < 0:
            raise ValueError(
                f"max_wait_ms must be >= 0, got {self.max_wait_ms}")
        if self.default_method != "auto" and self.default_method not in METHODS:
            raise ValueError(
                f"unknown default_method {self.default_method!r}")
        object.__setattr__(self, "buckets",
                           BucketLadder(self.buckets).buckets)


def plan_filename(method: str, bucket: int, batch: int, dtype: str) -> str:
    """Canonical artifact name `python -m repro.serve export` writes and
    the service looks for inside ``plan_dir``."""
    return f"{method}-n{bucket}-B{batch}-{dtype}.repro-plan"


class LogdetService:
    """Bucketed, continuously-batching log-determinant service."""

    def __init__(self, config: ServeConfig = ServeConfig()):
        self.config = config
        self.ladder = BucketLadder(config.buckets)
        self.plans = PlanCache(capacity=config.cache_capacity)
        self._np_dtype = np.dtype(config.dtype)
        self._cond = threading.Condition()
        self._pending: list = []
        self._thread: Optional[threading.Thread] = None
        self._closed = False
        self._auto: Dict[tuple, str] = {}   # (bucket, rtol) -> method
        self._key_counter = int(config.seed)
        self._key_lock = threading.Lock()

    # ------------------------------------------------------------ admission

    def submit(self, a, *, method: Optional[str] = None,
               rtol: Optional[float] = None):
        """Admit one ``(n, n)`` matrix; returns a Future[LogdetResult].

        Raises immediately (not via the future) on malformed input:
        non-square, non-finite, or larger than the top bucket rung.
        """
        if self._closed:
            raise ServiceClosed("service is closed")
        m = method or self.config.default_method
        if m != "auto" and m not in METHODS:
            raise ValueError(f"unknown method {m!r}; one of {METHODS} "
                             "or 'auto'")
        req = admit(a, self.ladder, method=m, rtol=rtol,
                    dtype=self._np_dtype)
        obs.inc("serve.requests", method=m)
        obs.observe("serve.request_n", req.n)
        with self._cond:
            if self._closed:
                raise ServiceClosed("service is closed")
            self._ensure_thread()
            self._pending.append(req)
            self._cond.notify()
        return req.future

    def logdet(self, a, *, method: Optional[str] = None,
               rtol: Optional[float] = None,
               timeout: Optional[float] = None) -> LogdetResult:
        """Synchronous convenience wrapper over `submit`."""
        return self.submit(a, method=method, rtol=rtol).result(timeout)

    # ---------------------------------------------------------------- plans

    def _resolve(self, method: str, bucket: int,
                 rtol: Optional[float]) -> str:
        """Pin ``method="auto"`` per (bucket, rtol) — resolved once, on
        the single-matrix spec, so batching never changes the answer."""
        if method != "auto":
            return method
        key = (bucket, rtol)
        got = self._auto.get(key)
        if got is None:
            from repro.core.plan import select_method
            got = select_method((bucket, bucket), rtol=rtol)
            self._auto[key] = got
        return got

    def _plan_for(self, method: str, bucket: int, batch: int):
        key = (method, bucket, batch, self.config.dtype)
        return self.plans.get(key, lambda: self._build_plan(*key))

    def _build_plan(self, method: str, bucket: int, batch: int,
                    dtype: str):
        path = None
        if self.config.plan_dir:
            cand = os.path.join(self.config.plan_dir,
                                plan_filename(method, bucket, batch, dtype))
            if os.path.exists(cand):
                path = cand
        if path is not None:
            from repro.serve.aot import load_plan
            return load_plan(path, validate=False)
        import repro
        shape = (bucket, bucket) if batch == 1 else (batch, bucket, bucket)
        return repro.plan(shape, method=method, precision=dtype,
                          validate=False)

    def warmup(self, methods: Optional[Sequence[str]] = None,
               batches: Optional[Sequence[int]] = None,
               buckets: Optional[Sequence[int]] = None) -> float:
        """Build (or AOT-load) and execute every plan the drain can need,
        so no request ever pays a compile.  Returns wall seconds spent.

        Defaults: the configured ``default_method``, every bucket rung,
        and the full batch ladder 1, 2, 4, ... ``max_batch``.
        """
        t0 = time.perf_counter()
        methods = list(methods or [self.config.default_method])
        if batches is None:
            batches, b = [], 1
            while b < self.config.max_batch:
                batches.append(b)
                b *= 2
            batches.append(self.config.max_batch)
        with obs.span("serve.warmup"):
            for bucket in (buckets or self.ladder.buckets):
                for m in methods:
                    method = self._resolve(m, bucket, None)
                    for batch in dict.fromkeys(batches):
                        plan = self._plan_for(method, bucket, batch)
                        eye = stack_to_bucket([], bucket, batch,
                                              self._np_dtype)
                        x = eye if batch > 1 else eye[0]
                        if method in ESTIMATOR_METHODS:
                            plan(x, key=self._next_key()).logabsdet\
                                .block_until_ready()
                        else:
                            plan(x).logabsdet.block_until_ready()
        dt = time.perf_counter() - t0
        obs.set_gauge("serve.warmup_s", dt)
        return dt

    def _next_key(self) -> np.ndarray:
        """Fresh PRNG key per batch, derived host-side (no jax dispatch:
        this is exactly the (hi, lo) split an int seed becomes)."""
        with self._key_lock:
            c = self._key_counter
            self._key_counter += 1
        return np.array([c >> 32, c & 0xFFFFFFFF], np.uint32)

    # ---------------------------------------------------------------- drain

    def _ensure_thread(self):
        if self._thread is None:
            self._thread = threading.Thread(
                target=self._drain_loop, name="repro-serve-drain",
                daemon=True)
            self._thread.start()

    def _drain_loop(self):
        wait_s = self.config.max_wait_ms / 1e3
        batch: list = []
        try:
            while True:
                with self._cond:
                    while not self._pending and not self._closed:
                        self._cond.wait()
                    if wait_s > 0 and not self._closed \
                            and len(self._pending) < self.config.max_batch:
                        deadline = time.perf_counter() + wait_s
                        while (len(self._pending) < self.config.max_batch
                               and not self._closed):
                            rem = deadline - time.perf_counter()
                            if rem <= 0:
                                break
                            self._cond.wait(rem)
                    popped, self._pending = self._pending, []
                    done = self._closed and not popped
                if done:
                    # keep `batch` pointing at the last popped work so the
                    # exit cleanup below can still fail anything _run_group
                    # left unresolved (e.g. it was wedged past close())
                    return
                batch = popped
                for group in coalesce(batch, self.config.max_batch):
                    self._run_group(group)
        finally:
            # the drain is stopping — normally (close) or by a crash
            # outside _run_group's guard (e.g. coalesce).  Whatever is
            # still queued, or popped but unprocessed, must fail loudly
            # instead of leaving forever-pending futures.
            self._fail_queued(batch)

    def _fail_queued(self, extra: Sequence = ()) -> None:
        """Fail every queued (and ``extra``) request with `ServiceClosed`."""
        with self._cond:
            leftovers, self._pending = self._pending, []
        exc = ServiceClosed(
            "service closed before this request was served")
        for r in list(extra) + leftovers:
            if not r.future.done():
                obs.inc("serve.responses", status="closed")
                r.future.set_exception(exc)

    def _run_group(self, g: BatchGroup) -> None:
        try:
            method = self._resolve(g.method, g.bucket, g.rtol)
            m = len(g.requests)
            batch = bucket_batch(m, self.config.max_batch)
            plan = self._plan_for(method, g.bucket, batch)
            stack = stack_to_bucket([r.a for r in g.requests],
                                    g.bucket, batch, self._np_dtype)
            x = stack if batch > 1 else stack[0]
            now = time.perf_counter()
            with obs.span("serve.batch", method=method, bucket=g.bucket,
                          size=m):
                if method in ESTIMATOR_METHODS:
                    res = plan(x, key=self._next_key())
                else:
                    res = plan(x)
            exec_ms = (time.perf_counter() - now) * 1e3
            signs = np.atleast_1d(np.asarray(res.sign))
            lds = np.atleast_1d(np.asarray(res.logabsdet))
            sems = np.atleast_1d(np.asarray(res.sem))
            for i, r in enumerate(g.requests):
                diags = dataclasses.replace(
                    res.diagnostics, padded_n=g.bucket)
                if r.future.done():      # already failed by close()
                    continue
                r.future.set_result(LogdetResult(
                    sign=signs[i], logabsdet=lds[i], sem=sems[i],
                    method_used=res.method_used, diagnostics=diags))
                obs.observe("serve.queue_wait_ms",
                            (now - r.t_submit) * 1e3)
                obs.observe("serve.pad_ratio", g.bucket / r.n)
            obs.inc("serve.batches", method=method, bucket=g.bucket)
            obs.inc("serve.responses", m, status="ok")
            obs.observe("serve.batch_size", m)
            obs.observe("serve.batch_fill", m / batch)
            obs.observe("serve.exec_ms", exec_ms, bucket=g.bucket)
        except Exception as exc:           # noqa: BLE001 — fail the futures
            obs.inc("serve.responses", len(g.requests), status="error")
            for r in g.requests:
                if not r.future.done():
                    r.future.set_exception(exc)

    # ------------------------------------------------------------ lifecycle

    def close(self, timeout: Optional[float] = 30.0) -> None:
        """Drain remaining requests, then stop the drain thread.

        Requests still queued when the drain stops — it crashed earlier,
        or ``timeout`` expired with it wedged — get `ServiceClosed` set
        on their futures; `submit` raises `ServiceClosed` from now on.
        """
        with self._cond:
            self._closed = True
            self._cond.notify_all()
        if self._thread is not None:
            self._thread.join(timeout)
            self._thread = None
        # normally the drain already failed its own leftovers on exit;
        # this covers a wedged or previously-crashed thread
        self._fail_queued()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()

    # ---------------------------------------------------------------- intro

    def trace_count(self) -> int:
        """Total traces across every warm plan — a warm, spec-stable
        service holds this constant between calls (the zero-recompile
        property serve_bench and tests assert)."""
        return sum(p.trace_count for p in
                   (self.plans.get(k) for k in self.plans.keys())
                   if p is not None)

    def stats(self) -> dict:
        """JSON-friendly operational snapshot (served at ``GET /stats``)."""
        snap = obs.snapshot()
        serve_counters = {k: v for k, v in snap["counters"].items()
                          if k.startswith("serve.")}
        return {
            "buckets": list(self.ladder.buckets),
            "max_batch": self.config.max_batch,
            "max_wait_ms": self.config.max_wait_ms,
            "dtype": self.config.dtype,
            "plans_cached": len(self.plans),
            "plan_keys": ["|".join(map(str, k)) for k in self.plans.keys()],
            "auto_resolution": {f"n{b}" + (f"@rtol={r}" if r else ""): m
                                for (b, r), m in sorted(self._auto.items())},
            "trace_count": self.trace_count(),
            "pending": len(self._pending),
            "counters": serve_counters,
            "quantiles": {
                name: {"p50": obs.quantile(name, 0.5),
                       "p99": obs.quantile(name, 0.99)}
                for name in ("serve.queue_wait_ms", "serve.batch_size")
            },
        }

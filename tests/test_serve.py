"""repro.serve: AOT export/import, bucketing, batching, the service, HTTP.

Everything runs on tiny matrices (buckets of 8/16/32) — the serving
semantics under test are size-independent.  The one subprocess test
(`test_aot_cross_process_bit_identical`) is the acceptance property:
an artifact exported here replays bit-identically in a fresh process
with zero traces and zero compiles.
"""
import json
import struct
import threading
import urllib.request

import numpy as np
import pytest

import jax

import repro
from repro import obs
from repro.serve import (
    BucketLadder, LogdetService, PlanCache, ServeConfig, ServiceClosed,
    bucket_batch, coalesce, pad_to_bucket, stack_to_bucket,
)
from repro.serve.aot import (
    PlanExportError, PlanFingerprintError, read_header,
)
from repro.serve.batching import Request, admit
from tests._subproc import run_with_devices


@pytest.fixture
def metrics():
    """Metrics-mode obs with a clean registry, restored afterwards."""
    prev = obs.mode()
    obs.configure("metrics")
    obs.reset()
    yield obs
    obs.reset()
    obs.configure(prev)


def _spd(rng, n):
    a = rng.standard_normal((n, n)) * 0.05
    return np.eye(n) * 2.0 + (a + a.T) / 2


# ---------------------------------------------------------------- ladder

def test_ladder_boundaries():
    lad = BucketLadder((8, 16, 32))
    assert lad.bucket_for(1) == 8
    assert lad.bucket_for(8) == 8        # exactly on a rung
    assert lad.bucket_for(9) == 16       # just over
    assert lad.bucket_for(16) == 16
    assert lad.bucket_for(17) == 32
    assert lad.bucket_for(32) == 32
    with pytest.raises(ValueError, match="exceeds the top bucket"):
        lad.bucket_for(33)
    with pytest.raises(ValueError):
        lad.bucket_for(0)


def test_ladder_sorts_and_dedupes():
    assert BucketLadder((32, 8, 8, 16)).buckets == (8, 16, 32)
    with pytest.raises(ValueError):
        BucketLadder(())
    with pytest.raises(ValueError):
        BucketLadder((0, 8))


def test_bucket_batch():
    assert bucket_batch(1, 8) == 1
    assert bucket_batch(2, 8) == 2
    assert bucket_batch(3, 8) == 4
    assert bucket_batch(5, 8) == 8
    assert bucket_batch(8, 8) == 8
    assert bucket_batch(100, 8) == 8     # capped
    with pytest.raises(ValueError):
        bucket_batch(0, 8)


def test_padding_preserves_slogdet(rng):
    a = rng.standard_normal((5, 5))
    padded = pad_to_bucket(a, 8)
    s0, ld0 = np.linalg.slogdet(a)
    s1, ld1 = np.linalg.slogdet(padded)
    assert s0 == s1
    assert ld1 == pytest.approx(ld0, abs=1e-12)


def test_stack_identity_filler(rng):
    mats = [rng.standard_normal((5, 5)), rng.standard_normal((7, 7))]
    stack = stack_to_bucket(mats, 8, 4)
    assert stack.shape == (4, 8, 8)
    for i, m in enumerate(mats):
        assert np.linalg.slogdet(stack[i])[1] == pytest.approx(
            np.linalg.slogdet(m)[1], abs=1e-12)
    for i in (2, 3):                     # filler slots: exact identity
        np.testing.assert_array_equal(stack[i], np.eye(8))


# ------------------------------------------------------------ plan cache

def test_plan_cache_lru_eviction_order(metrics):
    cache = PlanCache(capacity=2)
    cache.put(("a",), 1)
    cache.put(("b",), 2)
    assert cache.get(("a",)) == 1        # touch "a": "b" is now oldest
    cache.put(("c",), 3)                 # evicts "b"
    assert cache.keys() == [("a",), ("c",)]
    assert cache.get(("b",)) is None
    assert obs.counter_value("serve.plan_cache.evictions") == 1
    built = cache.get(("d",), lambda: 4)  # builder path evicts "a"
    assert built == 4
    assert cache.keys() == [("c",), ("d",)]
    assert obs.counter_value("serve.plan_cache.evictions") == 2
    assert obs.counter_value("serve.plan_cache.hits") == 1


# ------------------------------------------------------------- coalescing

def test_coalesce_groups_and_fifo(rng):
    lad = BucketLadder((8, 16))
    reqs = [admit(rng.standard_normal((n, n)), lad, method=m, rtol=None,
                  dtype=np.float64)
            for n, m in [(5, "exact"), (12, "exact"), (7, "exact"),
                         (6, "chebyshev"), (8, "exact")]]
    groups = coalesce(reqs, max_batch=8)
    keys = [(g.bucket, g.method) for g in groups]
    assert sorted(keys) == [(8, "chebyshev"), (8, "exact"), (16, "exact")]
    assert groups[0].oldest <= groups[1].oldest <= groups[2].oldest
    exact8 = next(g for g in groups if (g.bucket, g.method) == (8, "exact"))
    assert [r.n for r in exact8.requests] == [5, 7, 8]  # admission order


def test_coalesce_chunks_at_max_batch():
    reqs = [Request(a=np.eye(2), n=2, bucket=8, method="exact", rtol=None)
            for _ in range(5)]
    groups = coalesce(reqs, max_batch=2)
    assert [len(g.requests) for g in groups] == [2, 2, 1]
    flat = [r.id for g in groups for r in g.requests]
    assert flat == sorted(flat)          # FIFO across the chunks


def test_admit_rejects_bad_input(rng):
    lad = BucketLadder((8,))
    with pytest.raises(ValueError, match="square"):
        admit(rng.standard_normal((4, 5)), lad, method="exact", rtol=None,
              dtype=np.float64)
    bad = np.eye(4)
    bad[0, 0] = np.nan
    with pytest.raises(ValueError, match="non-finite"):
        admit(bad, lad, method="exact", rtol=None, dtype=np.float64)
    with pytest.raises(ValueError, match="exceeds the top bucket"):
        admit(np.eye(9), lad, method="exact", rtol=None, dtype=np.float64)


# -------------------------------------------------------------- AOT plans

def test_aot_roundtrip_bit_identical(tmp_path, rng, metrics):
    a = rng.standard_normal((12, 12))
    p = repro.plan((12, 12), method="exact", validate=False)
    want = float(p(a).logabsdet)
    path = str(tmp_path / "p.repro-plan")
    assert p.export(path) == path
    traces_before = obs.counter_value("plan.traces")

    q = repro.load_plan(path)
    assert q.trace_count == 0
    got = q(a)
    assert float(got.logabsdet) == want              # bit-identical
    assert float(q(a).logabsdet) == want
    assert q.trace_count == 0                        # still zero
    assert obs.counter_value("plan.traces") == traces_before
    assert got.method_used == "exact"


def test_aot_export_does_not_retrace_live_plan(rng, tmp_path):
    p = repro.plan((12, 12), method="exact", validate=False)
    p(rng.standard_normal((12, 12)))
    before = p.trace_count
    p.export(str(tmp_path / "p.repro-plan"))
    assert p.trace_count == before


def test_aot_estimator_roundtrip(tmp_path, rng):
    a = _spd(rng, 16)
    p = repro.plan((16, 16), method="slq", validate=False)
    want = float(p(a).logabsdet)
    path = str(tmp_path / "slq.repro-plan")
    p.export(path)
    q = repro.load_plan(path)
    assert float(q(a).logabsdet) == want       # default key == cfg.seed
    key = jax.random.PRNGKey(7)
    assert float(q(a, key=key).logabsdet) == float(p(a, key=key).logabsdet)
    with pytest.raises(TypeError, match="probes"):
        q(a, probes=np.ones((4, 16)))


def test_aot_loaded_plan_is_execute_only(tmp_path, rng):
    p = repro.plan((8, 8), method="exact", validate=False)
    path = str(tmp_path / "p.repro-plan")
    p.export(path)
    q = repro.load_plan(path)
    a = rng.standard_normal((8, 8))
    with pytest.raises(TypeError, match="takes no key"):
        q(a, key=jax.random.PRNGKey(0))
    with pytest.raises(TypeError, match="execute-only"):
        jax.jit(lambda x: q.logdet(x))(a)
    with pytest.raises(NotImplementedError, match="execute-only"):
        q.value_and_grad(a)


def test_aot_header_and_fingerprint_mismatch(tmp_path, rng):
    p = repro.plan((8, 8), method="exact", validate=False)
    path = str(tmp_path / "p.repro-plan")
    p.export(path)
    header = read_header(path)
    assert header["format"] == 1
    assert header["method"] == "exact"
    assert header["spec"]["n"] == 8
    assert header["fingerprint"]["platform"] == jax.devices()[0].platform

    # tamper: pretend the artifact came from another jax / device
    raw = open(path, "rb").read()
    magic_len = len(b"REPROPLAN\x00")
    (hlen,) = struct.unpack_from("<I", raw, magic_len)
    start = magic_len + 4
    header["fingerprint"]["jax_version"] = "9.9.9"
    new_head = json.dumps(header, sort_keys=True).encode()
    tampered = (raw[:magic_len] + struct.pack("<I", len(new_head))
                + new_head + raw[start + hlen:])
    bad = tmp_path / "tampered.repro-plan"
    bad.write_bytes(tampered)
    with pytest.raises(PlanFingerprintError, match="jax_version"):
        repro.load_plan(str(bad))
    # the escape hatch skips the check (same process, so actually safe)
    q = repro.load_plan(str(bad), check_device=False)
    a = rng.standard_normal((8, 8))
    assert np.isfinite(float(q(a).logabsdet))


def test_aot_rejects_non_artifact(tmp_path):
    junk = tmp_path / "junk.repro-plan"
    junk.write_bytes(b"definitely not a plan")
    with pytest.raises(PlanExportError, match="bad magic"):
        repro.load_plan(str(junk))


def test_aot_rejects_uncompiled_plan(mesh1):
    p = repro.plan((16, 16), method="exact", mesh=mesh1, validate=False)
    if p.compiled:
        pytest.skip("mesh plan unexpectedly compiled")
    with pytest.raises(PlanExportError, match="compiled"):
        p.export("/dev/null")


def test_aot_cross_process_bit_identical(tmp_path, rng):
    """The acceptance property: export here, load in a FRESH process,
    bit-identical logabsdet with zero traces/compiles (plan.trace_count
    and the plan.traces metric both stay 0 over there)."""
    a = rng.standard_normal((12, 12))
    p = repro.plan((12, 12), method="exact", validate=False)
    want = float(p(a).logabsdet)
    path = str(tmp_path / "x.repro-plan")
    p.export(path)
    np.save(tmp_path / "a.npy", a)
    out = run_with_devices(f"""
from repro import obs
obs.configure("metrics")
import repro
q = repro.load_plan({path!r})
a = np.load({str(tmp_path / 'a.npy')!r})
r = q(a)
assert q.trace_count == 0, q.trace_count
assert obs.counter_value("plan.traces") == 0
print(repr(float(r.logabsdet)))
""", 1)
    assert float(out.strip()) == want


# --------------------------------------------------------------- service

def test_serve_config_validation():
    with pytest.raises(ValueError, match="max_batch"):
        ServeConfig(max_batch=0)
    with pytest.raises(ValueError, match="max_wait_ms"):
        ServeConfig(max_wait_ms=-1)
    with pytest.raises(ValueError, match="default_method"):
        ServeConfig(default_method="nope")
    assert ServeConfig(buckets=(32, 8, 16)).buckets == (8, 16, 32)


def test_service_mixed_size_drain_unpermuted(rng, metrics):
    cfg = ServeConfig(buckets=(8, 16, 32), max_batch=4, max_wait_ms=1.0,
                      dtype="float64")
    with LogdetService(cfg) as svc:
        mats = [rng.standard_normal((n, n))
                for n in (5, 8, 13, 16, 30, 7, 9, 32)]
        futs = [svc.submit(a) for a in mats]
        for a, f in zip(mats, futs):     # results in submission order
            res = f.result(timeout=120)
            assert float(res.logabsdet) == pytest.approx(
                np.linalg.slogdet(a)[1], abs=1e-8)
            assert res.diagnostics.padded_n in (8, 16, 32)
        def traces():
            return {k: svc.plans.get(k).trace_count
                    for k in svc.plans.keys()}

        warm = traces()
        futs = [svc.submit(a) for a in mats]
        for f in futs:
            f.result(timeout=120)
        # no (method, bucket, batch) shape of round 1 traces again; the
        # drain timing may form a batch size round 1 did not, which
        # traces once
        after = traces()
        assert {k: after[k] for k in warm} == warm
        assert all(after[k] == 1 for k in after.keys() - warm.keys())
        assert svc.trace_count() == sum(after.values())
        assert obs.counter_value("serve.responses", status="ok") == 16
        stats = svc.stats()
        assert stats["trace_count"] == sum(after.values())
        assert stats["quantiles"]["serve.batch_size"]["p50"] is not None


def test_service_estimator_requests(rng):
    cfg = ServeConfig(buckets=(16,), max_batch=2, max_wait_ms=1.0)
    with LogdetService(cfg) as svc:
        a = _spd(rng, 14)
        res = svc.logdet(a, method="chebyshev", timeout=120)
        assert res.method_used == "chebyshev"
        assert float(res.logabsdet) == pytest.approx(
            np.linalg.slogdet(a)[1], rel=0.1)
        assert np.isfinite(float(res.sem))


def test_service_warmup_then_zero_traces(rng):
    cfg = ServeConfig(buckets=(8, 16), max_batch=2, max_wait_ms=0.0,
                      default_method="exact")
    with LogdetService(cfg) as svc:
        svc.warmup()
        warm = svc.trace_count()
        assert warm > 0
        futs = [svc.submit(rng.standard_normal((n, n)))
                for n in (3, 8, 11, 16, 5)]
        for f in futs:
            assert np.isfinite(float(f.result(timeout=120).logabsdet))
        assert svc.trace_count() == warm


def test_service_drain_failure_fails_futures(rng, monkeypatch):
    cfg = ServeConfig(buckets=(8,), max_batch=2)
    svc = LogdetService(cfg)
    monkeypatch.setattr(svc, "_build_plan",
                        lambda *a: (_ for _ in ()).throw(RuntimeError("boom")))
    try:
        fut = svc.submit(np.eye(4))
        with pytest.raises(RuntimeError, match="boom"):
            fut.result(timeout=60)
    finally:
        svc.close()
    with pytest.raises(RuntimeError, match="closed"):
        svc.submit(np.eye(4))


def test_service_close_fails_queued_requests(rng, monkeypatch):
    # Regression: a request still queued when the drain thread stops used
    # to be left with a forever-pending future, hanging any client blocked
    # in .result().  close() must fail it with ServiceClosed promptly.
    cfg = ServeConfig(buckets=(8,), max_batch=1, max_wait_ms=0.0)
    svc = LogdetService(cfg)
    entered, release = threading.Event(), threading.Event()

    def wedge(group):
        entered.set()
        release.wait(60)

    monkeypatch.setattr(svc, "_run_group", wedge)
    try:
        first = svc.submit(np.eye(4))
        assert entered.wait(30)          # drain popped `first` and wedged
        queued = svc.submit(np.eye(4))   # stays queued behind the wedge

        got = {}

        def client():
            try:
                got["res"] = queued.result(timeout=60)
            except Exception as exc:     # noqa: BLE001 — recorded for assert
                got["exc"] = exc

        t = threading.Thread(target=client)
        t.start()
        svc.close(timeout=0.2)           # wedged drain: join times out
        t.join(30)
        assert not t.is_alive(), "client is still blocked on a dead request"
        assert isinstance(got.get("exc"), ServiceClosed)
        with pytest.raises(ServiceClosed, match="closed"):
            svc.submit(np.eye(4))
    finally:
        release.set()                    # unwedge so the thread can exit
    # once the drain resumes and exits, the popped-but-unprocessed request
    # is failed too (drain-exit cleanup), not leaked
    with pytest.raises(ServiceClosed):
        first.result(timeout=30)


def test_service_submit_rejections(rng):
    cfg = ServeConfig(buckets=(8,), max_batch=2)
    with LogdetService(cfg) as svc:
        with pytest.raises(ValueError, match="exceeds the top bucket"):
            svc.submit(np.eye(9))
        with pytest.raises(ValueError, match="unknown method"):
            svc.submit(np.eye(4), method="nope")


def test_service_plan_dir_loads_aot(tmp_path, rng, monkeypatch):
    """A plan_dir-backed service never traces — not even at warmup."""
    from repro import compile_cache
    from repro.serve.__main__ import main as serve_main
    # the CLI turns on the persistent compile cache; keep the test
    # process's compiles out of the checkout
    monkeypatch.setattr(compile_cache, "use_compile_cache", lambda: None)
    serve_main(["export", "--out", str(tmp_path), "--buckets", "8",
                "--max-batch", "2", "--method", "exact",
                "--dtype", "float64"])
    cfg = ServeConfig(buckets=(8,), max_batch=2, plan_dir=str(tmp_path),
                      default_method="exact", dtype="float64")
    with LogdetService(cfg) as svc:
        svc.warmup()
        assert svc.trace_count() == 0
        a = rng.standard_normal((6, 6))
        res = svc.logdet(a, timeout=120)
        assert float(res.logabsdet) == pytest.approx(
            np.linalg.slogdet(a)[1], abs=1e-8)
        assert svc.trace_count() == 0


# ------------------------------------------------------------------ HTTP

def test_http_roundtrip(rng):
    from repro.serve.http import serve_http

    cfg = ServeConfig(buckets=(8,), max_batch=2, max_wait_ms=0.5,
                      dtype="float64")
    with LogdetService(cfg) as svc:
        server = serve_http(svc, port=0)
        port = server.server_address[1]
        t = threading.Thread(target=server.serve_forever, daemon=True)
        t.start()
        try:
            base = f"http://127.0.0.1:{port}"
            a = rng.standard_normal((6, 6)) + np.eye(6) * 4

            req = urllib.request.Request(
                f"{base}/v1/logdet",
                data=json.dumps({"matrix": a.tolist()}).encode(),
                headers={"Content-Type": "application/json"})
            with urllib.request.urlopen(req) as resp:
                assert resp.status == 200
                body = json.load(resp)
            assert body["logabsdet"] == pytest.approx(
                np.linalg.slogdet(a)[1], abs=1e-8)
            assert body["bucket"] == 8

            multi = urllib.request.Request(
                f"{base}/v1/logdet",
                data=json.dumps(
                    {"matrices": [a.tolist(), (2 * np.eye(3)).tolist()],
                     "method": "exact"}).encode())
            with urllib.request.urlopen(multi) as resp:
                results = json.load(resp)["results"]
            assert results[1]["logabsdet"] == pytest.approx(
                3 * np.log(2.0), abs=1e-10)

            with urllib.request.urlopen(f"{base}/healthz") as resp:
                assert json.load(resp)["status"] == "ok"
            with urllib.request.urlopen(f"{base}/stats") as resp:
                assert json.load(resp)["buckets"] == [8]

            bad = urllib.request.Request(
                f"{base}/v1/logdet",
                data=json.dumps({"matrix": [[1, 2, 3]]}).encode())
            with pytest.raises(urllib.error.HTTPError) as err:
                urllib.request.urlopen(bad)
            assert err.value.code == 400
            with pytest.raises(urllib.error.HTTPError) as err:
                urllib.request.urlopen(f"{base}/nope")
            assert err.value.code == 404
        finally:
            server.shutdown()


# -------------------------------------------------------------- quantile

def test_obs_quantile(metrics):
    for v in range(1, 101):
        obs.observe("q.test", float(v))
    assert obs.quantile("q.test", 0.5) == pytest.approx(50.5)
    assert obs.quantile("q.test", 0.99) == pytest.approx(99.01)
    assert obs.quantile("q.test", 0.0) == 1.0
    assert obs.quantile("q.test", 1.0) == 100.0
    assert obs.quantile("nothing.observed", 0.5) is None
    with pytest.raises(ValueError):
        obs.quantile("q.test", 1.5)
    # the histogram summary dict shape is unchanged (snapshot contract)
    h = obs.snapshot()["histograms"]["q.test"]
    assert h == {"count": 100.0, "sum": 5050.0, "min": 1.0, "max": 100.0}

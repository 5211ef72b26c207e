"""CI smoke test for ``python -m repro.serve``: start the server, POST one
request, assert 200 + finite logabsdet, and assert zero request-time
traces via the /stats endpoint.

Spawns the real entrypoint as a subprocess (``--port 0``), waits for the
``serving on http://...`` ready line, then exercises the public HTTP
surface exactly the way the docs/serving.md walkthrough does.

Usage::

    PYTHONPATH=src python tools/serve_smoke.py
"""
from __future__ import annotations

import json
import math
import os
import re
import subprocess
import sys
import time
import urllib.request
from pathlib import Path

SRC = str(Path(__file__).resolve().parents[1] / "src")
READY = re.compile(r"serving on http://([\d.]+):(\d+)")


def main() -> int:
    env = dict(os.environ)
    env["PYTHONPATH"] = SRC + os.pathsep + env.get("PYTHONPATH", "")
    proc = subprocess.Popen(
        [sys.executable, "-m", "repro.serve", "serve", "--port", "0",
         "--buckets", "16,32", "--max-batch", "2", "--dtype", "float64"],
        env=env, stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
        text=True)
    try:
        deadline = time.monotonic() + 300
        host = port = None
        for line in proc.stdout:
            print("server:", line.rstrip())
            m = READY.search(line)
            if m:
                host, port = m.group(1), int(m.group(2))
                break
            if time.monotonic() > deadline:
                raise TimeoutError("server never printed the ready line")
        if port is None:
            raise RuntimeError(
                f"server exited (rc={proc.wait()}) before becoming ready")

        base = f"http://{host}:{port}"
        matrix = [[4.0, 1.0, 0.0], [1.0, 3.0, 1.0], [0.0, 1.0, 5.0]]
        req = urllib.request.Request(
            f"{base}/v1/logdet",
            data=json.dumps({"matrix": matrix}).encode(),
            headers={"Content-Type": "application/json"})
        with urllib.request.urlopen(req, timeout=120) as resp:
            assert resp.status == 200, resp.status
            body = json.load(resp)
        print("response:", body)
        assert math.isfinite(body["logabsdet"]), body
        assert abs(body["logabsdet"] - math.log(51.0)) < 1e-6, body

        with urllib.request.urlopen(f"{base}/stats", timeout=30) as resp:
            stats = json.load(resp)
        warm = stats["trace_count"]
        with urllib.request.urlopen(req, timeout=120) as resp:
            assert resp.status == 200
        with urllib.request.urlopen(f"{base}/stats", timeout=30) as resp:
            stats = json.load(resp)
        assert stats["trace_count"] == warm, (
            f"request-time trace: {warm} -> {stats['trace_count']}")
        print(f"serve smoke OK (warm traces: {warm}, request-time: 0)")
        return 0
    finally:
        proc.terminate()
        try:
            proc.wait(timeout=10)
        except subprocess.TimeoutExpired:
            proc.kill()


if __name__ == "__main__":
    sys.exit(main())

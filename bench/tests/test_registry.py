"""The harness is driven by data: every part of a cell is found by name."""
import hashlib
import json
import shutil
import subprocess
import sys

import pytest

import registry

ROOT = registry.ROOT


def test_every_cell_and_metric_has_its_files():
    spec = registry.spec()
    names = [w["name"] for w in spec["workloads"]]
    assert len(names) == len(set(names))
    for w in spec["workloads"]:
        cell = registry.load_cell(w["name"])
        assert cell.chips == w["chips"] == 1
        registry.generator(cell.config["generator"])
        drv = registry.driver(cell.traffic["driver"])
        assert callable(drv.call) and callable(drv.check)
        assert {m["name"] for m in cell.end_to_end} == {
            "setup_s", cell.traffic["rate_metric"],
            *([cell.traffic["p95_metric"]] if "p95_metric" in cell.traffic
              else [])}
        assert cell.per_layer, w["name"]
        assert set(cell.traffic["limits"]) >= {"sign_mismatches",
                                               "logabsdet_err_cond"}
    for m in spec["per_layer"]:
        assert callable(registry.metric_reader(m["name"]).read)
        for cell in m.get("workloads", ()):
            assert cell in names


def test_bounds_and_run_seconds_within_the_contract():
    spec = registry.spec()
    assert 1 <= spec["run_seconds"] <= 51
    for m in spec["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25, m["name"]
    runs = 2 + 14 * 24
    assert runs * (spec["run_seconds"] + 60) + 24 * 180 + 1200 <= 43200


def _digest(root):
    return {p.relative_to(root): hashlib.sha256(p.read_bytes()).hexdigest()
            for p in sorted(root.rglob("*")) if p.is_file()}


def test_a_new_cell_and_metric_are_new_files_and_entries(tmp_path):
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    before = _digest(tmp_path / "bench")
    b = tmp_path / "bench"
    (b / "configs" / "toy.json").write_text(json.dumps(
        {"name": "toy", "generator": "gaussian", "dtype": "float32"}))
    (b / "traffic" / "n32.json").write_text(json.dumps(
        {"driver": "logdet", "rate_metric": "logdet_s", "n": 32, "rtol": 1e-5, "pool": 2,
         "trace_calls": 2, "limits": {"sign_mismatches": 0,
                                      "logabsdet_err_cond": 1.0}}))
    (b / "metrics" / "toy.ops.py").write_text(
        "def read(ctx):\n    return float(len(ctx.trace.ops))\n")
    spec = json.loads((tmp_path / "BENCHMARK.json").read_text())
    spec["configs"].append({"name": "toy", "source": "x",
                            "file": "bench/configs/toy.json", "reduced": [],
                            "why": "x"})
    spec["workloads"].append({"name": "toy.n32", "config": "toy",
                              "traffic": "n32", "chips": 1, "why": "x"})
    for m in spec["end_to_end"]:
        if m["name"] == "logdet_s":
            m["workloads"].append("toy.n32")
    spec["per_layer"].append({"name": "toy.ops", "unit": "ops",
                              "better": "higher", "source": "device_trace",
                              "layer": "device", "moves": "logdet_s"})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))

    cell = registry.load_cell("toy.n32", root=tmp_path)
    assert cell.traffic["n"] == 32 and cell.config["name"] == "toy"
    assert {m["name"] for m in cell.end_to_end} == {"logdet_s", "setup_s"}
    assert [m["name"] for m in cell.per_layer] == ["setup.compile_s",
                                                 "toy.ops"]
    reader = registry.metric_reader("toy.ops", root=tmp_path)
    assert reader.read(type("C", (), {"trace": type("T", (), {
        "ops": [1, 2]})()})()) == 2.0
    assert registry.generator("gaussian", root=tmp_path).make
    # no file that was there changed
    after = _digest(tmp_path / "bench")
    assert {k: after[k] for k in before} == before


def test_an_unknown_workload_is_refused():
    with pytest.raises(KeyError):
        registry.load_cell("no.such.cell")


def _run_cli(cwd, env_extra=None):
    env = {"JAX_PLATFORMS": "cpu", "PATH": "/usr/bin:/bin",
           "HOME": str(cwd), **(env_extra or {})}
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "paper_dense.n1000",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_off_a_tpu_the_run_fails_and_prints_no_result():
    out = _run_cli(ROOT)
    assert out.returncode == 2 and out.stdout == ""
    assert "TPU" in out.stderr


def test_benchmark_files_alone_do_not_run(tmp_path):
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    out = _run_cli(tmp_path)
    assert out.returncode != 0 and out.stdout == ""

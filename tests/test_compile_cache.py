"""`repro.compile_cache`: where the entry points put JAX's compile cache."""
import os
import subprocess
import sys
from pathlib import Path

import jax

from repro import compile_cache

REPO = Path(__file__).resolve().parents[1]


def _record_updates(monkeypatch):
    calls = []
    monkeypatch.setattr(jax.config, "update",
                        lambda name, value: calls.append((name, value)))
    return calls


def test_env_var_set_means_nothing_is_configured(monkeypatch, tmp_path):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    calls = _record_updates(monkeypatch)
    assert compile_cache.use_compile_cache() is None
    assert calls == []


def test_env_var_unset_uses_the_fixed_checkout_path(monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    calls = _record_updates(monkeypatch)
    got = compile_cache.use_compile_cache()
    assert got == compile_cache.CACHE_DIR == REPO / ".jax_cache"
    assert calls == [("jax_compilation_cache_dir", str(REPO / ".jax_cache"))]
    # the same path every time: it is part of each cache entry's key
    assert compile_cache.use_compile_cache() == got


def test_cache_dir_is_ignored_by_git():
    out = subprocess.run(["git", "check-ignore", "-q", ".jax_cache/x"],
                         cwd=REPO, capture_output=True)
    if out.returncode == 128:          # not a git checkout: read the file
        lines = (REPO / ".gitignore").read_text().split()
        assert ".jax_cache/" in lines
    else:
        assert out.returncode == 0


def test_library_import_sets_no_cache():
    """Importing the library leaves the cache alone; only entry points
    call `use_compile_cache`."""
    code = ("import jax, repro, repro.serve, repro.kernels.ops; "
            "print(repr(jax.config.jax_compilation_cache_dir))")
    env = dict(os.environ, PYTHONPATH=str(REPO / "src"), JAX_PLATFORMS="cpu")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    out = subprocess.run([sys.executable, "-c", code], cwd=REPO, env=env,
                         capture_output=True, text=True, timeout=120)
    assert out.returncode == 0, out.stderr
    assert out.stdout.strip().splitlines()[-1] == "None"

"""Platform plumbing of the entry points: kernel mode, compile cache, and
`chip_smoke.py`'s refusal to run anywhere but on a TPU."""
import os
import shutil
import subprocess
import sys

import jax

from repro.kernels.backend import resolve_interpret
from repro.launch import compile_cache

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_resolve_interpret_follows_platform():
    assert jax.default_backend() != "tpu"
    assert resolve_interpret() is True
    assert resolve_interpret(None) is True
    assert resolve_interpret(False) is False
    assert resolve_interpret(True) is True


def test_compile_cache_prefers_environment(monkeypatch):
    calls = []
    monkeypatch.setattr(jax.config, "update",
                        lambda *a: calls.append(a))
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/somewhere/cache")
    assert compile_cache.enable_compile_cache() == "/somewhere/cache"
    assert calls == []          # JAX reads the variable itself


def test_compile_cache_fixed_path_in_checkout(monkeypatch):
    calls = []
    monkeypatch.setattr(jax.config, "update",
                        lambda *a: calls.append(a))
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    path = compile_cache.enable_compile_cache()
    assert path == os.path.join(ROOT, ".jax_cache")
    assert calls == [("jax_compilation_cache_dir", path)]
    assert compile_cache.enable_compile_cache() == path   # never moves


def _smoke(cwd):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    return subprocess.run([sys.executable, "chip_smoke.py"], cwd=cwd,
                          env=env, capture_output=True, text=True,
                          timeout=300)


def test_chip_smoke_refuses_cpu():
    out = _smoke(ROOT)
    assert out.returncode != 0
    assert "platform=cpu" in out.stdout
    assert '"ok"' not in out.stdout


def test_chip_smoke_needs_the_repository(tmp_path):
    shutil.copy(os.path.join(ROOT, "chip_smoke.py"), tmp_path)
    out = _smoke(tmp_path)
    assert out.returncode != 0
    assert '"ok"' not in out.stdout

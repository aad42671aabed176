"""Entry-point plumbing: the persistent compilation cache that entry
points turn on, and ``chip_smoke.py``'s refusal to run without a TPU."""
import os
import subprocess
import sys
from pathlib import Path

import jax
import pytest

from repro.launch import cache

REPO = Path(__file__).resolve().parent.parent


@pytest.fixture
def restore_cache_config():
    from jax.experimental.compilation_cache import compilation_cache
    saved = (jax.config.jax_compilation_cache_dir,
             jax.config.jax_persistent_cache_min_compile_time_secs)
    yield
    jax.config.update("jax_compilation_cache_dir", saved[0])
    jax.config.update("jax_persistent_cache_min_compile_time_secs",
                      saved[1])
    compilation_cache.reset_cache()


def test_compile_cache_defaults_to_the_repo(monkeypatch,
                                            restore_cache_config):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    path = cache.enable_compile_cache()
    assert path == str(REPO / ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == path
    assert jax.config.jax_persistent_cache_min_compile_time_secs == 0


def test_compile_cache_env_dir_wins(monkeypatch, tmp_path,
                                    restore_cache_config):
    # JAX reads the variable itself; the entry point must set no other
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    jax.config.update("jax_compilation_cache_dir", str(tmp_path))
    assert cache.enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == str(tmp_path)


def test_importing_the_library_leaves_the_cache_off():
    code = ("import jax, repro.core.problem, repro.serve, "
            "repro.launch.cache; print(jax.config.jax_compilation_cache_dir)")
    env = dict(os.environ, PYTHONPATH=str(REPO / "src"))
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=120)
    assert out.returncode == 0, out.stderr[-2000:]
    assert out.stdout.strip() == "None"


@pytest.mark.parametrize("env", [{"JAX_PLATFORMS": "cpu"},
                                 {"REPRO_FORCE_INTERPRET": "1"}])
def test_chip_smoke_refuses_to_run_off_the_chip(env):
    full = dict(os.environ, JAX_PLATFORMS="cpu")
    full.update(env)
    out = subprocess.run([sys.executable, str(REPO / "chip_smoke.py")],
                         env=full, capture_output=True, text=True,
                         timeout=120)
    assert out.returncode != 0
    assert '"ok"' not in out.stdout

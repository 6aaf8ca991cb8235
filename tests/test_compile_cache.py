"""The entry points' compile-cache helper sets only what it must."""

import jax

from repro.launch import compile_cache


def test_env_dir_is_left_to_jax(monkeypatch, tmp_path):
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert compile_cache.enable() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before


def test_cpu_backend_caches_nothing(monkeypatch):
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    assert jax.default_backend() == "cpu"
    assert compile_cache.enable() is None
    assert jax.config.jax_compilation_cache_dir == before


def test_default_dir_is_fixed_inside_the_checkout():
    repo = compile_cache.DEFAULT_DIR.parent
    assert compile_cache.DEFAULT_DIR.name == ".jax_cache"
    assert (repo / "src" / "repro" / "launch" / "compile_cache.py").exists()

"""Persistent compile cache placement (``repro.launch.compile_cache``)."""
import jax
import pytest

from repro.launch import compile_cache


@pytest.fixture
def restore_cache_config():
    was = (jax.config.jax_compilation_cache_dir,
           jax.config.jax_persistent_cache_min_compile_time_secs)
    yield
    jax.config.update("jax_compilation_cache_dir", was[0])
    jax.config.update("jax_persistent_cache_min_compile_time_secs", was[1])


def test_default_dir_is_fixed_inside_checkout(monkeypatch,
                                              restore_cache_config):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    path = compile_cache.enable_compile_cache()
    root = compile_cache.default_dir().parent
    assert path == str(root / ".jax_cache")
    assert (root / "src" / "repro" / "launch" / "compile_cache.py").is_file()
    assert jax.config.jax_compilation_cache_dir == path
    assert jax.config.jax_persistent_cache_min_compile_time_secs == 0
    # a second call lands on the same directory: nothing run-specific
    assert compile_cache.enable_compile_cache() == path


def test_installed_package_falls_back_to_working_dir(monkeypatch,
                                                    tmp_path):
    monkeypatch.setattr(compile_cache, "_CHECKOUT", tmp_path / "site")
    monkeypatch.chdir(tmp_path)
    assert compile_cache.default_dir() == tmp_path / ".jax_cache"


def test_env_dir_is_left_to_jax(monkeypatch, restore_cache_config,
                                tmp_path):
    jax.config.update("jax_compilation_cache_dir", None)
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert compile_cache.enable_compile_cache() == str(tmp_path)
    # the variable is JAX's own; the helper sets no directory over it
    assert jax.config.jax_compilation_cache_dir is None

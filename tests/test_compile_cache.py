"""Where the entry points keep JAX's persistent compilation cache."""
import jax

from repro.core import compile_cache


def test_cache_dir_is_fixed_in_the_checkout(tmp_path, monkeypatch):
    monkeypatch.delenv(compile_cache.ENV, raising=False)
    assert compile_cache.cache_dir(str(tmp_path)) == str(
        tmp_path / ".jax_cache")


def test_cache_dir_from_env_is_left_to_jax(tmp_path, monkeypatch):
    """With JAX_COMPILATION_CACHE_DIR set, JAX already uses it: enable()
    reports it and sets no other directory."""
    monkeypatch.setenv(compile_cache.ENV, str(tmp_path / "outside"))
    before = jax.config.jax_compilation_cache_dir
    assert compile_cache.enable(str(tmp_path)) == str(tmp_path / "outside")
    assert jax.config.jax_compilation_cache_dir == before

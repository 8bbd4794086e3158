"""The entry points' persistent compilation cache location."""
import jax

from repro.launch import compile_cache


def _restore(was):
    jax.config.update("jax_compilation_cache_dir", was)


def test_env_dir_is_left_alone(monkeypatch, tmp_path):
    was = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv(compile_cache.ENV_VAR, str(tmp_path))
    try:
        assert compile_cache.enable_compile_cache() == str(tmp_path)
        assert jax.config.jax_compilation_cache_dir == was   # not rewritten
    finally:
        _restore(was)


def test_default_is_fixed_path_in_checkout(monkeypatch):
    was = jax.config.jax_compilation_cache_dir
    monkeypatch.delenv(compile_cache.ENV_VAR, raising=False)
    try:
        path = compile_cache.enable_compile_cache()
        assert path == compile_cache.enable_compile_cache()  # stable
        assert jax.config.jax_compilation_cache_dir == path
        root = compile_cache.CHECKOUT_CACHE.parent
        assert (root / "src" / "repro").is_dir()
        assert compile_cache.CHECKOUT_CACHE.name == ".jax_cache"
        assert ".jax_cache/" in (root / ".gitignore").read_text().split()
    finally:
        _restore(was)

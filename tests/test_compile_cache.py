"""Where the entry points put JAX's persistent compilation cache."""
import jax

from repro.launch import compile_cache


def test_checkout_cache_when_unset(monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    was = jax.config.jax_compilation_cache_dir
    try:
        got = compile_cache.use_compile_cache()
        assert got == str(compile_cache.CHECKOUT_CACHE)
        assert jax.config.jax_compilation_cache_dir == got
        assert compile_cache.CHECKOUT_CACHE.parent.joinpath(
            "chip_smoke.py").exists()          # the checkout root
    finally:
        jax.config.update("jax_compilation_cache_dir", was)


def test_environment_directory_wins(monkeypatch, tmp_path):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    was = jax.config.jax_compilation_cache_dir
    assert compile_cache.use_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == was   # nothing set in code

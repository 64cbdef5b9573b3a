"""Where ``repro.compile_cache`` puts JAX's persistent compilation cache.

The helper is steered onto its accelerator branch here (the suite runs on
the CPU, where it leaves the cache off), and JAX's cache settings are
restored afterwards so the rest of the suite stays uncached."""
import jax
import pytest

from repro import compile_cache

_KEYS = ("jax_compilation_cache_dir", "jax_enable_compilation_cache",
         "jax_persistent_cache_min_compile_time_secs",
         "jax_persistent_cache_min_entry_size_bytes")


@pytest.fixture
def on_chip(monkeypatch):
    saved = {k: getattr(jax.config, k) for k in _KEYS}
    monkeypatch.setattr(compile_cache.jax, "default_backend", lambda: "tpu")
    yield
    for k, v in saved.items():
        jax.config.update(k, v)


def test_cache_off_on_cpu():
    assert compile_cache.enable_compile_cache() is None


def test_cache_defaults_to_checkout_dir(on_chip, monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    path = compile_cache.enable_compile_cache()
    assert path == str(compile_cache.CHECKOUT_CACHE_DIR)
    assert jax.config.jax_compilation_cache_dir == path
    assert compile_cache.CHECKOUT_CACHE_DIR.name == ".jax_cache"


def test_cache_env_dir_wins(on_chip, monkeypatch, tmp_path):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert compile_cache.enable_compile_cache() == str(tmp_path)
    # the variable is JAX's own: no directory is set in code
    assert jax.config.jax_compilation_cache_dir == before

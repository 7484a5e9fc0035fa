"""The entry points' persistent compile cache: JAX's own
``JAX_COMPILATION_CACHE_DIR`` stands; otherwise a fixed path in the
checkout."""
import os

from repro import jax_cache

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_env_setting_stands_and_default_is_in_checkout(monkeypatch):
    calls = []
    monkeypatch.setattr(jax_cache.jax.config, "update",
                        lambda *a: calls.append(a))
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere")
    assert jax_cache.enable_persistent_cache() == "/elsewhere"
    assert calls == []
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    path = jax_cache.enable_persistent_cache()
    assert path == os.path.join(REPO, ".jax_cache")
    assert calls == [("jax_compilation_cache_dir", path)]

"""Keep the benchmark's runs in these tests from changing JAX's global
configuration for the tests that share their worker process."""
import jax
import pytest

_KEYS = ("jax_persistent_cache_min_compile_time_secs",
         "jax_persistent_cache_min_entry_size_bytes")


@pytest.fixture(autouse=True)
def _no_persistent_cache(monkeypatch):
    from repro.launch import compile_cache
    monkeypatch.setattr(compile_cache, "use_compile_cache", lambda: "off in tests")
    saved = {k: getattr(jax.config, k) for k in _KEYS}
    yield
    for k, v in saved.items():
        jax.config.update(k, v)

"""Where ``repro.compile_cache.configure`` puts JAX's persistent cache.

No program is compiled between setting and restoring the options, so
nothing is written to either directory.
"""

from pathlib import Path

import pytest

jax = pytest.importorskip("jax")

from repro import compile_cache  # noqa: E402

_OPTIONS = ("jax_compilation_cache_dir",
            "jax_persistent_cache_min_compile_time_secs")


@pytest.fixture
def restore_jax_options():
    saved = {k: getattr(jax.config, k) for k in _OPTIONS}
    yield
    for k, v in saved.items():
        jax.config.update(k, v)


def test_environment_variable_decides(monkeypatch, restore_jax_options):
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere/cache")
    assert compile_cache.configure() == "/elsewhere/cache"
    # JAX reads the variable itself: no directory is set in code
    assert jax.config.jax_compilation_cache_dir == before
    assert jax.config.jax_persistent_cache_min_compile_time_secs == 0.0


def test_default_is_the_checkout(monkeypatch, restore_jax_options):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    path = compile_cache.configure()
    checkout = Path(__file__).resolve().parents[1]
    assert path == str(checkout / ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == path
    assert jax.config.jax_persistent_cache_min_compile_time_secs == 0.0

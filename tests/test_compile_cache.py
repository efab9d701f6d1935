"""Where the launchers and `chip_smoke.py` keep JAX's persistent
compilation cache."""
from pathlib import Path

import jax
import pytest

from repro.launch.compile_cache import DEFAULT_CACHE_DIR, enable_compile_cache

REPO = Path(__file__).resolve().parents[1]


@pytest.fixture
def cache_config():
    """Restore the cache directory afterwards (nothing compiles meanwhile,
    so no cache is opened at the test's directory)."""
    before = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", before)


def test_environment_variable_is_honoured(cache_config, monkeypatch,
                                          tmp_path):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before


def test_fixed_path_in_the_checkout_otherwise(cache_config, monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    assert DEFAULT_CACHE_DIR == REPO / ".jax_cache"
    assert enable_compile_cache() == str(REPO / ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == str(REPO / ".jax_cache")
    assert ".jax_cache/" in (REPO / ".gitignore").read_text().splitlines()

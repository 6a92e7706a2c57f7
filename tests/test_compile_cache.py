"""The persistent compilation cache goes where the environment says, else
to one fixed directory in the repository."""
from pathlib import Path

import jax
import pytest

from repro.launch.compile_cache import CACHE_ENV, REPO_CACHE_DIR, enable_compile_cache


@pytest.fixture
def restore_cache_dir():
    was = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", was)


def test_environment_places_the_cache(monkeypatch, restore_cache_dir, tmp_path):
    monkeypatch.setenv(CACHE_ENV, str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before  # JAX reads the variable itself


def test_unset_environment_uses_the_repo_directory(monkeypatch, restore_cache_dir):
    monkeypatch.delenv(CACHE_ENV, raising=False)
    assert enable_compile_cache() == str(REPO_CACHE_DIR)
    assert jax.config.jax_compilation_cache_dir == str(REPO_CACHE_DIR)
    assert REPO_CACHE_DIR == Path(__file__).resolve().parents[1] / ".jax_cache"

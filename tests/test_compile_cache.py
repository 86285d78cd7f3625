"""Where entry points put JAX's persistent compile cache."""
import pathlib

import jax
import pytest
from jax.experimental.compilation_cache import compilation_cache

from repro.launch import compile_cache

REPO = pathlib.Path(__file__).resolve().parents[1]


@pytest.fixture
def restore_cache_dir():
    before = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", before)
    compilation_cache.reset_cache()


def test_env_var_directory_wins_and_nothing_is_set(monkeypatch, tmp_path,
                                                   restore_cache_dir):
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert compile_cache.enable_compile_cache() == tmp_path
    assert jax.config.jax_compilation_cache_dir == before


def test_default_is_fixed_ignored_path_in_checkout(monkeypatch, restore_cache_dir):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    path = compile_cache.enable_compile_cache()
    assert path == REPO / ".jax_cache"
    assert jax.config.jax_compilation_cache_dir == str(path)
    assert ".jax_cache/" in (REPO / ".gitignore").read_text().split()

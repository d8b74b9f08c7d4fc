"""Where the entry points put JAX's persistent compilation cache."""

import os

import jax
import pytest

from repro.launch.compile_cache import CACHE_DIR, use_compile_cache

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture
def restore_cache_dir():
    before = jax.config.jax_compilation_cache_dir
    yield before
    jax.config.update("jax_compilation_cache_dir", before)


def test_env_dir_is_left_to_jax(monkeypatch, tmp_path, restore_cache_dir):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert use_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == restore_cache_dir


def test_fixed_dir_inside_checkout(monkeypatch, restore_cache_dir):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    first = use_compile_cache()
    second = use_compile_cache()
    assert first == second == CACHE_DIR
    assert jax.config.jax_compilation_cache_dir == CACHE_DIR
    assert os.path.dirname(CACHE_DIR) == REPO
    with open(os.path.join(REPO, ".gitignore")) as f:
        ignored = {line.strip() for line in f}
    assert os.path.basename(CACHE_DIR) + "/" in ignored

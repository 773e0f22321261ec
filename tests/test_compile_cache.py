"""Where the entry points put JAX's persistent compilation cache."""
from __future__ import annotations

import os

import jax
import pytest

from repro.launch import compile_cache


@pytest.fixture
def restore_cache_dir():
    was = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", was)


def test_env_var_wins_and_is_not_overridden(monkeypatch, restore_cache_dir,
                                            tmp_path):
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert compile_cache.configure() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before


def test_default_is_one_fixed_ignored_dir_in_the_checkout(
        monkeypatch, restore_cache_dir):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    path = compile_cache.configure()
    assert jax.config.jax_compilation_cache_dir == path
    root = compile_cache.DEFAULT_DIR.parent
    assert path == os.path.join(root, ".jax_cache")
    assert os.path.isfile(os.path.join(root, "chip_smoke.py"))
    # the same path on every call, and .gitignore lists it
    assert compile_cache.configure() == path
    with open(os.path.join(root, ".gitignore")) as f:
        assert ".jax_cache/" in f.read().split()

"""The persistent compilation cache helper: placed from outside when
``JAX_COMPILATION_CACHE_DIR`` is set, else at a fixed repo path."""
import os
from pathlib import Path

import jax
import pytest

from repro import compile_cache


@pytest.fixture
def cache_config():
    before = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", before)


def test_importing_sets_no_cache_path():
    # only JAX's own reading of the variable, if any; nothing of ours
    want = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    assert jax.config.jax_compilation_cache_dir == want


def test_outside_dir_wins_and_nothing_is_set(monkeypatch, cache_config):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere/cache")
    before = jax.config.jax_compilation_cache_dir
    assert compile_cache.enable() == "/elsewhere/cache"
    assert jax.config.jax_compilation_cache_dir == before


def test_default_dir_is_fixed_under_the_repo(monkeypatch, cache_config):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    got = compile_cache.enable()
    repo = Path(__file__).resolve().parents[1]
    assert got == str(repo / ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == got
    assert ".jax_cache/" in (repo / ".gitignore").read_text().splitlines()

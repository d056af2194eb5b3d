"""Where the persistent compilation cache lives (repro.runtime)."""

import os

import pytest

from repro import runtime

pytestmark = pytest.mark.tier1


def test_cache_dir_follows_the_environment(monkeypatch, tmp_path):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert runtime.compile_cache_dir() == str(tmp_path)


def test_cache_dir_defaults_to_the_repo(monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    assert runtime.compile_cache_dir() == os.path.join(repo, ".jax_cache")
    assert runtime.compile_cache_dir() == runtime.compile_cache_dir()

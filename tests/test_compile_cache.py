"""The persistent-compilation-cache helper the entry points call."""
from pathlib import Path

import jax
import pytest

from repro.launch import compile_cache

REPO = Path(__file__).resolve().parents[1]


@pytest.fixture
def restore_cache_dir():
    old = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", old)


def test_env_var_is_honoured_and_nothing_else_set(monkeypatch, tmp_path):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    calls = []
    monkeypatch.setattr(jax.config, "update",
                        lambda *a, **k: calls.append(a))
    assert compile_cache.use_compile_cache() == str(tmp_path)
    assert calls == []


def test_fallback_is_fixed_and_inside_the_checkout(monkeypatch,
                                                  restore_cache_dir):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    first = compile_cache.use_compile_cache()
    assert first == compile_cache.use_compile_cache()
    assert Path(first) == REPO / ".jax_cache"
    assert jax.config.jax_compilation_cache_dir == first


def test_fallback_dir_is_ignored_by_git():
    ignored = (REPO / ".gitignore").read_text().split()
    assert ".jax_cache/" in ignored

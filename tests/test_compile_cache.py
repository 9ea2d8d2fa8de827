"""The persistent compilation cache lives at one fixed place."""
import pathlib

import jax

from repro import compile_cache

REPO = pathlib.Path(__file__).resolve().parents[1]


def test_env_var_wins_and_nothing_is_set(monkeypatch):
    """With JAX_COMPILATION_CACHE_DIR set, JAX reads it itself: the helper
    chooses no directory and touches no config."""
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere/cache")
    calls = []
    monkeypatch.setattr(jax.config, "update",
                        lambda *a: calls.append(a))
    assert compile_cache.enable_compile_cache() == "/elsewhere/cache"
    assert calls == []


def test_default_is_fixed_path_in_checkout(monkeypatch):
    """Unset, the cache is <checkout>/.jax_cache — the same path on every
    call (never a temp name, a pid or a time) — and git ignores it."""
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    calls = []
    monkeypatch.setattr(jax.config, "update",
                        lambda *a: calls.append(a))
    first = compile_cache.enable_compile_cache()
    second = compile_cache.enable_compile_cache()
    assert first == second == str(REPO / ".jax_cache")
    assert calls == [("jax_compilation_cache_dir", first)] * 2
    assert ".jax_cache/" in (REPO / ".gitignore").read_text().splitlines()

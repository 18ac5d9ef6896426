"""repro.compile_cache: where entry points keep JAX's persistent cache."""

import pathlib

import jax

from repro import compile_cache

ROOT = pathlib.Path(__file__).resolve().parents[1]


def test_environment_directory_wins_and_nothing_else_is_set(monkeypatch,
                                                           tmp_path):
    monkeypatch.setenv(compile_cache.ENV_VAR, str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert compile_cache.enable() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before


def test_default_is_one_fixed_ignored_directory_in_the_checkout(monkeypatch):
    monkeypatch.delenv(compile_cache.ENV_VAR, raising=False)
    before = jax.config.jax_compilation_cache_dir
    try:
        path = compile_cache.enable()
        assert jax.config.jax_compilation_cache_dir == path
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
    assert pathlib.Path(path) == ROOT / ".jax_cache"
    assert path == compile_cache.cache_dir()          # stable, not per call
    assert ".jax_cache/" in (ROOT / ".gitignore").read_text().split()

"""The entry points' persistent compilation cache sits at a fixed place."""
from pathlib import Path

import jax

from repro.launch.compile_cache import place_compile_cache

ROOT = Path(__file__).resolve().parents[1]


def test_env_dir_is_left_to_jax(monkeypatch, tmp_path):
    """With JAX_COMPILATION_CACHE_DIR set, JAX reads it itself: the
    helper reports it and sets nothing in code."""
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert place_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before


def test_default_dir_is_fixed_and_gitignored(monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    before = jax.config.jax_compilation_cache_dir
    try:
        got = place_compile_cache()
        assert jax.config.jax_compilation_cache_dir == got
        assert Path(got) == ROOT / ".jax_cache"
        assert place_compile_cache() == got          # no pid, no time
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
    assert ".jax_cache/" in (ROOT / ".gitignore").read_text().split()

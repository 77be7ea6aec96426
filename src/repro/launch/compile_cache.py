"""Where JAX's persistent compilation cache lives.

A compiled program is found again only under the same directory path,
so the cache sits at a fixed place: the directory that
`JAX_COMPILATION_CACHE_DIR` names when it is set (JAX reads the variable
itself; nothing is set here), and `<checkout>/.jax_cache` otherwise. The
latter is listed in `.gitignore`. Entry points call `place_compile_cache`
in their `main()` before they compile anything; importing this module
changes nothing.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

CHECKOUT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def place_compile_cache() -> str:
    """Turn the persistent compilation cache on at its fixed place and
    return that directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE_DIR))
    return str(CHECKOUT_CACHE_DIR)

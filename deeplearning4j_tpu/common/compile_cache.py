"""Where JAX's persistent compilation cache lives — one rule, used by every
entry script that initialises a backend (`chip_smoke.py`, `benchmarks/run.py`,
`tools/*`, `--replica-serve` children, `tests/conftest.py`).

If `JAX_COMPILATION_CACHE_DIR` is set, JAX reads it itself and nothing is
set in code: whoever runs the program decides where compiled programs
persist (a chip machine may come with the variable pointing at a directory
that outlives the call). If it is not set, the cache is
`<checkout>/.jax_cache` — a fixed path, because the directory is part of the
cache key and a directory that moves never hits. The path is git-ignored and
listed in `.chiprunignore` (a CPU test cache must not be copied to the chip
machine).

Kept out of `common/__init__` so the numpy-only worker paths never pull
jax in transitively.
"""
from __future__ import annotations

import os

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")


def enable_compile_cache():
    """Turn the persistent cache on (call before the first compile).
    Returns the directory in use."""
    path = os.environ.get(ENV_VAR)
    if path:
        return path
    import jax
    jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    return DEFAULT_DIR


def cache_entries(path):
    """Compiled programs on disk under `path` (0 for a directory that does
    not exist yet). JAX keeps an access-time file beside each entry; those
    are not counted."""
    try:
        return sum(1 for name in os.listdir(path)
                   if not name.endswith("-atime"))
    except FileNotFoundError:
        return 0

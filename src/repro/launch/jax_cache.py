"""Persistent compilation cache for the entry scripts.

A call on the chip starts with no compiled code, and the programs that
serve a chip-sized store take minutes to compile.  Entry scripts
(``chip_smoke.py``, ``benchmarks/run.py``, ``examples/serve_kg.py``) call
:func:`enable` once, before they compile anything; library modules never
touch the setting.
"""
from __future__ import annotations

import os

import jax

ENV = "JAX_COMPILATION_CACHE_DIR"
CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))))


def enable(root: str = CHECKOUT) -> str:
    """Keep JAX's persistent compilation cache where ``$JAX_COMPILATION_
    CACHE_DIR`` says (JAX reads that variable itself, so its setting is left
    alone), else at ``<checkout>/.jax_cache`` — a fixed path, since the
    path is part of what the cache matches.  Returns the directory."""
    if os.environ.get(ENV):
        return os.environ[ENV]
    path = os.path.join(root, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path

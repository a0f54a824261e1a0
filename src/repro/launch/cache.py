"""Where the entry points keep JAX's persistent compilation cache.

Called once by each entry point (``chip_smoke.py``, ``launch/serve.py``,
``launch/train.py``) before it compiles anything; no library module
calls it, so importing ``repro`` never changes jax configuration.
"""
from __future__ import annotations

import os

import jax

#: the checkout this file lives in (``<checkout>/src/repro/launch``)
CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))))


def enable_compile_cache(root: str = CHECKOUT) -> str:
    """Turn on the persistent compilation cache; returns its directory.

    When ``JAX_COMPILATION_CACHE_DIR`` is set, jax has already read it
    and it stands: nothing is set here.  Otherwise the cache goes to
    ``<root>/.jax_cache`` — a fixed path (never a temp dir, pid or
    timestamp), so a later run in the same checkout finds its entries
    again."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = os.path.join(root, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path

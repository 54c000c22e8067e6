"""Where JAX's persistent compilation cache lives.

Placed from outside when ``JAX_COMPILATION_CACHE_DIR`` is set (JAX reads the
variable itself; nothing is set here), otherwise at one fixed path inside
the checkout. The path is part of what a cache is good for: a directory
that moves with a temp name, a pid or the time is never found again by the
next process, so none of those is ever used.
"""

from __future__ import annotations

import os

#: ``<checkout>/.jax_cache`` — listed in ``.gitignore``.
DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
    ".jax_cache")


def use_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its directory and return
    it. Call from an entry point's ``main`` before the first compile — never
    at import."""
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if placed:
        return placed
    import jax

    jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    return DEFAULT_DIR

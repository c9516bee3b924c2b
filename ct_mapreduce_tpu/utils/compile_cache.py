"""Where this program keeps JAX's persistent compilation cache.

One ingest step costs minutes to compile for the chip, so every entry
point that traces calls :func:`configure` before its first trace. The
cache's path is part of its key: it is either the directory the
operator names in ``JAX_COMPILATION_CACHE_DIR`` (JAX reads that
variable itself — nothing is set in code then) or one fixed directory
inside the checkout, never a temporary name.
"""

from __future__ import annotations

import os

_REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
CHECKOUT_CACHE_DIR = os.path.join(_REPO, ".jax_cache")


def configure() -> str:
    """Point JAX's persistent compilation cache at its one place and
    return that directory."""
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR", "")
    if placed:
        return placed
    import jax

    jax.config.update("jax_compilation_cache_dir", CHECKOUT_CACHE_DIR)
    return CHECKOUT_CACHE_DIR

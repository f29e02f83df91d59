"""Where JAX's persistent compilation cache lives for this checkout.

Entry points call `use_compile_cache` once, before their first compile;
importing this module changes nothing.
"""

from __future__ import annotations

import os
import pathlib

import jax

__all__ = ["use_compile_cache"]

# a fixed path inside the checkout (git-ignored): a cache directory whose
# name changes from run to run never hits
_CHECKOUT_CACHE_DIR = (pathlib.Path(__file__).resolve().parents[3]
                       / ".jax_cache")


def use_compile_cache() -> str:
    """Turn on the persistent compilation cache and return its directory.

    When ``JAX_COMPILATION_CACHE_DIR`` is set, JAX has already read it
    and this leaves JAX's setting alone.  Otherwise the cache goes to
    ``<checkout>/.jax_cache``, so a second run from the same checkout reads
    back what the first one compiled."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(_CHECKOUT_CACHE_DIR))
    return str(_CHECKOUT_CACHE_DIR)

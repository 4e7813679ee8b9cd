"""Placement of JAX's persistent compilation cache for the entry points.

At published widths every segment program and the fused adaptive program
take seconds to tens of seconds to compile; the persistent cache lets a
later process (or a second executor in the same process) load them
instead.  The cache key includes the directory, so the directory is
fixed: ``JAX_COMPILATION_CACHE_DIR`` when it is set (JAX reads it
itself), else ``<checkout>/.jax_cache``.

Called by the entry points (``chip_smoke.py``, ``examples/*.py``,
``benchmarks/run.py``) before their first compile — never on library
import and never from the tests.

The key includes each op's metadata: profiles attribute device time by
the model's name scopes (``attn``, ``ffn``, …), and JAX's default key
leaves metadata out, so a program that differs from a cached one only in
its scopes would load the cached executable and trace under its stale
names.  Source locations are left out of the metadata, so the key does
not move with the checkout's path or an edit's line numbers.
"""
from __future__ import annotations

import os

import jax

#: the checkout this module lives in (``<checkout>/src/repro/``)
CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def enable() -> str:
    """Turn the persistent compilation cache on and return its directory.
    Leaves the choice to JAX when ``JAX_COMPILATION_CACHE_DIR`` is set."""
    jax.config.update("jax_traceback_in_locations_limit", 0)
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = os.path.join(CHECKOUT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path

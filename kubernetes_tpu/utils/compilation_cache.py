"""Persistent XLA compilation cache.

The reference pays no compile cost (Go is AOT); our analog of its instant
cold start is XLA program persistence: first-ever compile of each
(policy, capacities, flags) solver variant lands on disk, later processes
load it in well under a second.

Placement: where `JAX_COMPILATION_CACHE_DIR` is set, JAX reads it itself
and this module sets no directory. Otherwise the cache lives at one fixed
path inside the checkout (`<repo>/.jax_cache`, gitignored) — the path is
part of each entry's key, so a directory that moves never hits. Every
plane that compiles calls `enable()` before its first compile (JAX
initializes its cache at the first compile that finds a directory set).
"""

from __future__ import annotations

import logging
import os

log = logging.getLogger(__name__)

DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")


def enable() -> str | None:
    """Idempotent: point JAX's persistent compilation cache at
    DEFAULT_DIR unless the environment already placed it. Returns the
    directory in use, or None (logged) when it cannot be created."""
    import jax

    if os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        return os.environ["JAX_COMPILATION_CACHE_DIR"]
    try:
        os.makedirs(DEFAULT_DIR, exist_ok=True)
    except OSError as e:
        log.warning("persistent compilation cache off: cannot create %s "
                    "(%s)", DEFAULT_DIR, e)
        return None
    jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    return DEFAULT_DIR

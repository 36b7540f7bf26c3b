"""Where JAX's persistent compilation cache lives — the ONE place that says.

The cache directory is part of the cache key's usefulness: a directory
that moves between runs never hits. So the rule is fixed here and every
checkout script (chip_smoke.py, bench.py, benchmark/run.py,
__graft_entry__.py) calls :func:`enable` instead of naming a directory:

* ``JAX_COMPILATION_CACHE_DIR`` set — JAX reads the variable itself; this
  module sets NO directory in code, so whoever runs the program places
  the cache from outside.
* unset — one fixed path inside the checkout, ``<repo>/.jax_cache``
  (gitignored). Never a tempdir, a pid or a timestamp.

This is JAX's own XLA-executable cache. The ``.jexec`` / ``.jplan``
artifact caches (serving/execcache.py, parallel/planner.py) are separate planes and are not placed here.
"""

from __future__ import annotations

import os

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"

# <repo>/.jax_cache: the checkout root is the parent of the paddle_tpu
# package directory
DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")

_HIT = "/jax/compilation_cache/cache_hits"
_MISS = "/jax/compilation_cache/cache_misses"


def resolve_dir():
    """The directory the persistent compile cache uses: the environment's
    when ``JAX_COMPILATION_CACHE_DIR`` is set, else :data:`DEFAULT_DIR`."""
    return os.environ.get(ENV_VAR) or DEFAULT_DIR


class CacheStats:
    """Persistent-cache hits and misses since :func:`enable` — counted
    from JAX's own monitoring events, so "did this run compile anything"
    is read off the compiler, not inferred from wall time."""

    def __init__(self):
        self.hits = 0
        self.misses = 0

    def _on_event(self, event, **_kw):
        if event == _HIT:
            self.hits += 1
        elif event == _MISS:
            self.misses += 1


_listening = None       # the CacheStats whose listener is registered


def enable():
    """Turn the persistent compilation cache on at :func:`resolve_dir`.
    Returns ``(directory, CacheStats)``, the counts starting at zero. Call
    before the first compile. A second call in one process takes the
    first call's listener off JAX's monitoring bus before it puts its own
    on: one listener a process, however often this is called."""
    global _listening
    import jax

    path = resolve_dir()
    if not os.environ.get(ENV_VAR):
        jax.config.update("jax_compilation_cache_dir", path)
    if _listening is not None:
        jax.monitoring.unregister_event_listener(_listening._on_event)
    _listening = stats = CacheStats()
    jax.monitoring.register_event_listener(stats._on_event)
    return path, stats


__all__ = ["ENV_VAR", "DEFAULT_DIR", "CacheStats", "enable", "resolve_dir"]

"""Process-wide JAX configuration for the TPU serving stack.

Imported by every jax-touching subpackage (engine/models/kv/parallel)
before any tracing happens.  The store tier (config/protocol/lib/server)
stays jax-free and must not import this.

The installed JAX (0.9) already defaults ``jax_threefry_partitionable`` to
true — the form whose keys split identically on every device, which the
tp/sp paths and sharded weight init rely on — so nothing is set for it
here; ``JAX_THREEFRY_PARTITIONABLE`` remains JAX's own switch.

Persistent compilation cache: a serving process compiles one program per
shape bucket, and on an accelerator that is most of a cold start.  The
cache directory is part of each entry's key, so it has to be the same
path in every process that should share compiled programs:

* ``JAX_COMPILATION_CACHE_DIR`` set: JAX reads it; nothing is set here,
  so whoever launches the process places the cache.
* not set: ``<checkout>/.jax_cache`` — fixed, never a temporary
  directory, a pid or a timestamp.
* ``JAX_PLATFORMS`` pinning ``cpu`` (the test suite): no default.  XLA:CPU
  reloads of ahead-of-time results warn about machine-feature mismatches
  and can SIGILL on a different host; the CPU suite gains little from it.
"""

from __future__ import annotations

import os

import jax

DEFAULT_COMPILATION_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))), ".jax_cache"
)


def default_cache_dir(environ) -> str | None:
    """The cache directory this module should set given ``environ``, or
    None when it must set none (placed from outside, or CPU-pinned)."""
    if environ.get("JAX_COMPILATION_CACHE_DIR"):
        return None
    if environ.get("JAX_PLATFORMS", "").strip().lower() == "cpu":
        return None
    return DEFAULT_COMPILATION_CACHE_DIR


_cache_dir = default_cache_dir(os.environ)
if _cache_dir is not None:
    jax.config.update("jax_compilation_cache_dir", _cache_dir)

"""Persistent XLA compilation cache for driver processes.

The reference pays JVM+Spark startup per driver run; our analog cost is
XLA compilation of the solver/evaluator kernels. A persistent on-disk
cache makes every driver run after the first reuse compiled executables,
so short CLI jobs (heart-sized trainings, scoring runs) are not dominated
by compile time.

Placement: where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it
itself and this module sets no directory. Where it is not, the cache
lives at ONE fixed path inside the checkout (``<checkout>/.jax_cache``,
git-ignored) — the path is part of the cache key, so a directory that
moves (a home directory, a per-process or per-machine name) never hits.
Every process of a run (train, score, serve) therefore shares one cache.

CPU-pinned processes (``JAX_PLATFORMS=cpu`` — the test harness) do not
persist: XLA:CPU AOT results are specialized to the compiling machine's
CPU features, and a checkout that travels between hosts must never serve
one machine's entries to another.

The cache is capped (LRU-evicted by JAX) at 1 GiB unless
``JAX_COMPILATION_CACHE_MAX_SIZE`` says otherwise, and every kernel is
persisted, however fast it compiled — short CLI runs are dominated by
many sub-second compiles. Opt out with ``PHOTON_DISABLE_COMPILE_CACHE=1``.
"""

from __future__ import annotations

import os

#: The one in-checkout location used when the environment names none.
DEFAULT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")

_MAX_CACHE_BYTES = 1 << 30  # 1 GiB, LRU-evicted by JAX

_enabled = False


def enable_persistent_compile_cache() -> bool:
    """Idempotently turn on JAX's persistent compilation cache. Returns
    whether this call left it active (False when disabled via env or the
    process is pinned to the CPU platform). Never initializes a backend:
    drivers call this first thing in ``main()``, before argument parsing
    and before any ``jax.distributed.initialize()``."""
    global _enabled
    if _enabled:
        return True
    if os.environ.get("PHOTON_DISABLE_COMPILE_CACHE"):
        return False
    import jax

    # an in-process jax_platforms override (scripts pin "cpu" before first
    # backend use) wins over the environment's default
    platforms = (str(jax.config.jax_platforms or "")
                 or os.environ.get("JAX_PLATFORMS", "")).strip().lower()
    if platforms.startswith("cpu"):
        return False
    # whoever placed the cache also sizes it: JAX reads both variables itself
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", DEFAULT_CACHE_DIR)
    if not os.environ.get("JAX_COMPILATION_CACHE_MAX_SIZE"):
        jax.config.update("jax_compilation_cache_max_size", _MAX_CACHE_BYTES)
    # growth is bounded by the LRU cap, so persist everything
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    _enabled = True
    return True

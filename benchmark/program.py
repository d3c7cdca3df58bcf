"""The few places where the harness touches the program besides a kind's
step: its compile cache, its compile counters and the device's memory.

From the program the benchmark takes the system under test and its
counters; every number's arithmetic stays on this side.
"""

from __future__ import annotations


def enable_compile_cache() -> bool:
    """The program's own persistent cache: where
    ``JAX_COMPILATION_CACHE_DIR`` says, else ``<checkout>/.jax_cache``."""
    from photon_ml_tpu.utils.compile_cache import (
        enable_persistent_compile_cache,
    )

    return enable_persistent_compile_cache()


def arm_compile_counters() -> None:
    """Route the solvers' jitted entry points through ``obs/compile.py`` so
    that every compile is counted and timed (``compile_secs{site}``)."""
    from photon_ml_tpu.obs import compile as obs_compile

    obs_compile.arm()


def counters() -> dict:
    """Totals of the program's compile counters."""
    from photon_ml_tpu.obs.metrics import REGISTRY

    return {"compile_secs": float(REGISTRY.counter("compile_secs").total()),
            "compiles": float(REGISTRY.counter("compiles").total())}


def memory_peak_bytes(devices) -> int:
    """Peak bytes in use on the fullest device."""
    peak = 0
    for dev in devices:
        stats = dev.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return peak

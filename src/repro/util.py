"""Small shared utilities."""
from __future__ import annotations

import os


def costing_mode() -> bool:
    """True while the dry-run is costing HLO.

    XLA's cost_analysis counts a rolled ``lax.scan`` body ONCE, not
    trip-count times (verified empirically — exactly 1/L). Under costing
    mode, inner scans (chunked attention, SSD chunk scan) unroll so their
    work is counted; the *layer* scan is handled by the dry-run's L=1/L=2
    extrapolation instead (see launch/dryrun.py).
    """
    return os.environ.get("REPRO_COSTING", "0") == "1"


def scan_unroll() -> bool | int:
    return True if costing_mode() else 1


# The checkout this package was imported from (src/repro/util.py -> root).
CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache and return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads that directory
    itself and no other is set here. Otherwise the cache lives at the fixed
    ``<checkout>/.jax_cache`` (the directory is part of what a later run
    must find again, so it never comes from a temp name, pid or time).
    Every compile is cached, however short.
    """
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = os.path.join(CHECKOUT, ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path

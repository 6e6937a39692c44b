"""XLA host-platform virtual-device setup, import-order safe.

Several entry points (the dry-run driver, the test session, the bench
driver) need jax's CPU backend split into N placeholder devices via
``XLA_FLAGS=--xla_force_host_platform_device_count=N``.  The flag only
takes effect if it is in the environment BEFORE the first jax import,
and naively assigning ``os.environ["XLA_FLAGS"]`` discards whatever
flags the user already set.  :func:`force_host_device_count` is the one
shared, merge-don't-clobber implementation:

  * existing ``XLA_FLAGS`` content is preserved (the new flag is
    appended), and
  * an already-present ``xla_force_host_platform_device_count`` wins —
    the caller's N is NOT applied over an explicit user choice.

The split applies to CPU runs only (``JAX_PLATFORMS=cpu``): on an
accelerator the virtual host devices would serve no purpose.

Deliberately jax-free at import: importing this module never initializes
a backend, so it is safe to call from conftest files and module
top-levels that must run before jax.  :func:`setup_compile_cache`, the
persistent-compilation-cache placement every entry point calls, imports
jax only when it runs.
"""
from __future__ import annotations

import os

DEVICE_COUNT_FLAG = "--xla_force_host_platform_device_count"

# <repo>/.jax_cache: src/repro/launch/xla_env.py is three levels below
CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))), ".jax_cache")


def force_host_device_count(n: int) -> bool:
    """Merge ``--xla_force_host_platform_device_count=n`` into
    ``XLA_FLAGS`` when ``JAX_PLATFORMS`` is ``cpu``.  Returns True when
    the flag was applied, False when the run is not CPU-only or an
    existing device-count flag was respected instead.  Must run before
    the first jax import to have any effect."""
    if os.environ.get("JAX_PLATFORMS") != "cpu":
        return False
    existing = os.environ.get("XLA_FLAGS", "")
    if DEVICE_COUNT_FLAG.lstrip("-") in existing:
        return False
    os.environ["XLA_FLAGS"] = \
        f"{existing} {DEVICE_COUNT_FLAG}={int(n)}".strip()
    return True


def setup_compile_cache() -> str:
    """Place JAX's persistent compilation cache; returns its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and
    nothing is set here.  Otherwise the cache goes to ``<repo>/.jax_cache``,
    a fixed path inside the checkout: the path is part of each entry's
    key, so a temporary or per-process directory would never hit."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax
    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    return CACHE_DIR

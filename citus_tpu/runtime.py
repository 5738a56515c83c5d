"""JAX runtime configuration guard.

The framework's routing contract (host/device hash parity, int64 shard keys)
requires 64-bit types on device.  JAX defaults to x64-off and silently
downcasts int64 → int32 at jnp.asarray, which would silently break shuffle
routing (rows land on wrong shards, joins lose rows).  Every entry point —
Session, executors, bench — calls ensure_jax_configured() before touching
device arrays.
"""

from __future__ import annotations

import os

_configured = False

# where JAX's persistent compilation cache lives when the environment
# does not say (JAX_COMPILATION_CACHE_DIR): <checkout>/.jax_cache
COMPILE_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    ".jax_cache")


def ensure_jax_configured(platform: str | None = None,
                          host_device_count: int | None = None) -> None:
    """Idempotently enable x64 (and optionally pick a platform / virtual
    device count).  Must run before the first JAX backend use; platform and
    device-count changes after backend init raise RuntimeError."""
    global _configured
    if host_device_count is not None and not _configured:
        flags = os.environ.get("XLA_FLAGS", "")
        if "xla_force_host_platform_device_count" not in flags:
            os.environ["XLA_FLAGS"] = (
                flags + f" --xla_force_host_platform_device_count={host_device_count}")

    import jax

    # set through the config API, which holds whatever the environment
    # says: int64 shard keys are not optional
    jax.config.update("jax_enable_x64", True)
    if platform is not None:
        jax.config.update("jax_platforms", platform)
    if not _configured:
        # persistent XLA executable cache: repeated plan shapes skip the
        # cold compile across processes.  CPU-backend processes skip
        # it: XLA's CPU executable.serialize() segfaults after a few
        # hundred distinct compilations in one process (observed
        # killing 500-query fuzz runs), and the in-process plan cache
        # covers repeats there anyway.  The backend itself is asked,
        # not the configuration: `JAX_PLATFORMS=tpu,cpu` names the CPU
        # and runs on the chip.
        if jax.default_backend() == "cpu":
            jax.config.update("jax_enable_compilation_cache", False)
        else:
            # the directory is part of the cache key, so it is either
            # the one the environment names or one fixed place inside
            # the checkout — never a path that moves with $HOME
            if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
                jax.config.update("jax_compilation_cache_dir",
                                  COMPILE_CACHE_DIR)
            jax.config.update(
                "jax_persistent_cache_min_compile_time_secs", 1.0)
    _configured = True


def require_x64() -> None:
    import jax

    if not jax.config.jax_enable_x64:
        raise RuntimeError(
            "citus_tpu requires jax_enable_x64 (int64 shard keys); call "
            "citus_tpu.runtime.ensure_jax_configured() before device work")

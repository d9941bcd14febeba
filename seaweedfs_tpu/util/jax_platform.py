"""JAX start-up rules shared by every module that touches a device.

Two things live here, both applied where a module first imports jax:

- ``configure_compile_cache()``: the persistent XLA compile cache. A cold
  process on the chip otherwise recompiles every (k, r, width) program
  it serves. The directory is placeable from outside: when
  ``JAX_COMPILATION_CACHE_DIR`` is set JAX reads it itself and this code
  sets no directory; otherwise it is ``<checkout>/.jax_cache``, derived
  from this package's own path (the path is part of the cache key, so it
  must not move between process starts). Where ``JAX_PLATFORMS`` asks
  for the ``cpu`` nothing is cached: CPU programs compile in milliseconds and
  XLA:CPU logs a machine-feature complaint for every entry it reloads.
- ``require_tpu()``: ``-ec.backend tpu|mesh`` means the TPU. On any other
  platform it is an error unless ``JAX_PLATFORMS`` explicitly puts
  ``cpu`` first (how the tier-1 tests and the CPU rehearsals run).
"""

from __future__ import annotations

import os
import re
import sys
from typing import Optional

_COUNT_FLAG = "--xla_force_host_platform_device_count"
_CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"
_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
_configured = False


def compile_cache_dir() -> str:
    """Where the compile cache lives for this process."""
    return os.environ.get(_CACHE_ENV) or os.path.join(_CHECKOUT, ".jax_cache")


def configure_compile_cache() -> Optional[str]:
    """Turn the persistent compile cache on (idempotent); returns its
    directory, or None where the CPU was asked for. The EC kernels
    compile in 0.5-3 s, under JAX's default 1 s keep threshold, so the
    thresholds are lowered to keep them."""
    global _configured
    if cpu_explicitly_requested():
        return None
    if not _configured:
        import jax
        if not os.environ.get(_CACHE_ENV):
            jax.config.update("jax_compilation_cache_dir",
                              compile_cache_dir())
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
        _configured = True
    return compile_cache_dir()


def cpu_explicitly_requested() -> bool:
    """JAX_PLATFORMS puts cpu first: somebody asked for the CPU as the
    platform to compute on. `tpu,cpu` is not that — there cpu is the
    host platform beside a TPU that must initialise."""
    return os.environ.get("JAX_PLATFORMS", "").split(",")[0] \
        .strip().lower() == "cpu"


def backend_initialized() -> bool:
    """Has this process initialised an XLA backend? Asked by the status
    routes, which must not be the call that does it — importing jax is
    not yet reaching for the chip, so the import alone does not count."""
    if "jax" not in sys.modules:
        return False
    from jax._src import xla_bridge
    return xla_bridge.backends_are_initialized()


def default_platform() -> str:
    """The platform JAX computes on; backend-init errors propagate."""
    import jax
    return jax.default_backend()


def require_tpu(backend: str, platform: Optional[str] = None) -> str:
    """Platform check for the device codecs, made where they first touch
    JAX. `platform` defaults to the one JAX computes on (a mesh passes
    its own devices'). Returns the platform."""
    if platform is None:
        platform = default_platform()
    if platform != "tpu" and not cpu_explicitly_requested():
        raise RuntimeError(
            f"-ec.backend {backend} needs a TPU but JAX computes on "
            f"{platform!r}; set JAX_PLATFORMS=cpu to run the device "
            f"programs on the CPU on purpose")
    return platform


def set_host_device_count_flag(n: int, flags: Optional[str] = None) -> str:
    """Return XLA_FLAGS with the host-device-count flag forced to ``n``,
    replacing any existing value rather than keeping a stale one."""
    flags = os.environ.get("XLA_FLAGS", "") if flags is None else flags
    flags = re.sub(rf"{_COUNT_FLAG}=\d+", "", flags)
    return (flags.strip() + f" {_COUNT_FLAG}={n}").strip()

"""Small shared utilities: env parsing, caching, dtype helpers.

Reference parity: horovod/common/utils/env_parser.cc (SetBoolFromEnv et al.)
and horovod/common/util.py. On TPU these collapse into plain Python since
there is no C env-parser boundary.
"""

from __future__ import annotations

import os
from typing import Optional

# Env vars keep the HOROVOD_ prefix for drop-in familiarity.
_ENV_PREFIXES = ("HOROVOD_", "HVD_TPU_")


def getenv(name: str, default: Optional[str] = None) -> Optional[str]:
    """Look up NAME under every accepted prefix (HOROVOD_NAME wins)."""
    for prefix in _ENV_PREFIXES:
        val = os.environ.get(prefix + name)
        if val is not None:
            return val
    return default


def env_bool(name: str, default: bool = False) -> bool:
    val = getenv(name)
    if val is None:
        return default
    return val.strip().lower() in ("1", "true", "yes", "on")


def env_int(name: str, default: int) -> int:
    val = getenv(name)
    if val is None:
        return default
    try:
        return int(val)
    except ValueError:
        return default


def env_float(name: str, default: float) -> float:
    val = getenv(name)
    if val is None:
        return default
    try:
        return float(val)
    except ValueError:
        return default


def is_tpu_backend() -> bool:
    """True when jax's default backend is a TPU — the predicate the
    auto-default perf features key on (conv0 space-to-depth, flash
    length routing).  A backend that fails to initialize raises here:
    it must not quietly turn those features off."""
    import jax

    return jax.default_backend() == "tpu"


def force_cpu_platform(n_devices: Optional[int] = None) -> None:
    """Select the CPU host platform (optionally with n virtual devices)
    from inside a process that has not initialized a backend yet — for
    entry points that must never take the chip whatever the caller's
    environment says (multichip dry runs, simulated-scaling children).
    Equivalent to launching with JAX_PLATFORMS=cpu and
    XLA_FLAGS=--xla_force_host_platform_device_count=n."""
    if n_devices is not None:
        flags = os.environ.get("XLA_FLAGS", "")
        if "xla_force_host_platform_device_count" not in flags:
            os.environ["XLA_FLAGS"] = (
                flags + f" --xla_force_host_platform_device_count={n_devices}"
            ).strip()
    import jax
    jax.config.update("jax_platforms", "cpu")


# <checkout>/.jax_cache — fixed, derived from the package's location:
# the directory is part of the cache key, so a path that moves (tempfile,
# pid, time) never hits.
_DEFAULT_COMPILE_CACHE = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")


def configure_compile_cache() -> str:
    """Point JAX's persistent compilation cache somewhere and return the
    directory in use.  With JAX_COMPILATION_CACHE_DIR set JAX reads it
    itself and nothing is touched; otherwise the cache goes to
    `<checkout>/.jax_cache`.  Called by `hvd.init()` and by every entry
    point that compiles without calling it."""
    env_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env_dir:
        return env_dir
    import jax
    jax.config.update("jax_compilation_cache_dir", _DEFAULT_COMPILE_CACHE)
    return _DEFAULT_COMPILE_CACHE

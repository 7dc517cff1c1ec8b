"""Process-level JAX setup applied once by framework entry points.

Persistent XLA compilation cache: trial-engine executables are keyed by
bucket shapes that recur across processes (bench runs, agent restarts), so
caching compiles on disk removes the first-compile cost from every fresh
process. The rule for where it lives:

- ``JAX_COMPILATION_CACHE_DIR`` set: JAX reads it itself and this module
  sets no directory at all — whoever launches the process places the cache.
- unset: one fixed directory inside the checkout (``<repo>/.jax_cache``,
  git-ignored). The path is part of every cache key, so it is never built
  from flags, a hash, a pid or the time: a directory that moves never hits.
"""

from __future__ import annotations

import functools
import os

#: the one in-checkout cache directory (parent of the package directory)
DEFAULT_CACHE_DIR = os.path.join(
    os.path.dirname(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    ),
    ".jax_cache",
)

_done = False


@functools.lru_cache(maxsize=1)
def host_fingerprint() -> str:
    """Stable fingerprint of THIS host's CPU capabilities.

    CPU-lowered AOT exports (utils/aot_cache.py) embed the compile
    machine's feature set; a shared storage root across heterogeneous hosts
    would otherwise let host B load host A's binary and SIGILL.
    Partitioning the export directory by (machine, cpu-flag set) makes a
    feature mismatch structurally impossible.
    """
    import hashlib
    import platform as _platform

    parts = [_platform.machine(), _platform.system()]
    try:
        with open("/proc/cpuinfo") as f:
            for line in f:
                if line.startswith("flags") or line.startswith("Features"):
                    # flags are a stable, unordered capability set per host
                    parts.append(" ".join(sorted(line.split(":", 1)[1].split())))
                    break
    except OSError:
        # non-Linux: the processor string (coarser, still machine-specific
        # enough to split x86 from arm etc.)
        parts.append(_platform.processor())
    return hashlib.sha256("|".join(parts).encode()).hexdigest()[:16]


def compile_cache_dir() -> str:
    """Where this process's persistent compile cache lives (for the device
    line entry points print)."""
    return os.environ.get("JAX_COMPILATION_CACHE_DIR") or DEFAULT_CACHE_DIR


def setup_jax() -> None:
    global _done
    if _done:
        return
    _done = True
    import jax

    # TPUML_PLATFORM=cpu|tpu pins the backend for THIS process before the
    # first backend touch: a chip belongs to one process, so supervised
    # child agents and extra local executors run host-side while one
    # process owns the chip. A pin that cannot be applied raises — carrying
    # on on whatever backend comes up would hide the device.
    platform = os.environ.get("TPUML_PLATFORM")
    if platform:
        jax.config.update("jax_platforms", platform)

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        os.makedirs(DEFAULT_CACHE_DIR, exist_ok=True)
        jax.config.update("jax_compilation_cache_dir", DEFAULT_CACHE_DIR)
    # cache every compile: each fresh process otherwise re-pays even the
    # sub-second ones
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)

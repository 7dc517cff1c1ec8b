"""Disk cache of exported (AOT) trial executables.

The XLA persistent compilation cache (utils/jax_setup.py) removes the
*compile* cost from fresh processes, but each process still pays Python
tracing for every trial-engine executable (seconds for the larger model
kernels). `jax.export` serializes the traced StableHLO module; deserializing
it in a later process skips tracing entirely, and its compile hits the XLA
persistent cache — together they take a fresh-process dispatch from
~3-12 s of trace+compile down to ~a second of (cached) executable load.

This is the TPU-framework counterpart of the reference scheduler persisting
its learned runtime model across restarts (scheduler_service.py:44-46): warm
state survives process boundaries so the steady-state cost, not the cold
cost, is what jobs pay.

Entries are keyed by the executable identity (kernel/static/shapes/splits/
chunk and the staged leaves' own shape/dtype signature: trial_map._aot_key
behind the engine's tag), the lowering platform, the
jax version, and a content fingerprint of this package's compute-path
sources — a code change invalidates every blob, so a stale cache can never
resurrect old kernel behavior. A blob that cannot be read back, or a
program ``jax.export`` refuses, takes the traced path with a logged
warning; a program that cannot LOWER raises — that is the job's error, not
a cache miss (CS230_AOT_CACHE=0 disables the cache outright).
"""

from __future__ import annotations

import hashlib
import os
import threading
from typing import Any, Optional, Sequence, Tuple

from . import backend as _backend
from .logging import get_logger

logger = get_logger("tpuml.aot_cache")

_FINGERPRINT: Optional[str] = None
_LOCK = threading.Lock()

# compute-path packages whose source content keys the cache
_CODE_DIRS = ("models", "ops", "parallel")


def cache_dir() -> str:
    override = os.environ.get("CS230_AOT_DIR")
    if override:
        return override
    from .config import get_config

    return os.path.join(get_config().storage.root, "aot_cache")


def enabled() -> bool:
    """On by default on accelerator backends; OFF on CPU, where tracing is
    the cheap part and the cache's payoff (the TPU fleet) is absent.
    ``CS230_AOT_CACHE=force`` overrides; ``0`` disables everywhere."""
    flag = os.environ.get("CS230_AOT_CACHE", "1")
    if flag == "0":
        return False
    if flag == "force":
        return True
    return not _backend.on_cpu()


def _code_fingerprint() -> str:
    """sha256 over the compute-path sources (content, not mtime: rebuilds
    and checkouts must not produce false hits or misses)."""
    global _FINGERPRINT
    if _FINGERPRINT is not None:
        return _FINGERPRINT
    with _LOCK:
        if _FINGERPRINT is not None:
            return _FINGERPRINT
        h = hashlib.sha256()
        pkg_root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        for sub in _CODE_DIRS:
            root = os.path.join(pkg_root, sub)
            for dirpath, _, files in sorted(os.walk(root)):
                for name in sorted(files):
                    if name.endswith(".py"):
                        path = os.path.join(dirpath, name)
                        h.update(name.encode())
                        with open(path, "rb") as f:
                            h.update(f.read())
        _FINGERPRINT = h.hexdigest()
        return _FINGERPRINT


def _generation() -> str:
    """Cache generation: code fingerprint + jax version + host CPU
    fingerprint. Blobs live in a per-generation subdirectory so superseded
    generations are prunable. The host fingerprint keeps heterogeneous
    machines sharing a storage root (the deploy/ fleet story) from loading
    each other's machine-feature-specific binaries (SIGILL hazard flagged
    by the cpu_aot_loader)."""
    import jax

    host = ""
    if _backend.on_cpu():
        # only CPU-lowered exports embed host machine features; TPU blobs
        # are device code and MUST stay shared across a heterogeneous-CPU
        # fleet (the whole payoff of a shared storage root)
        from .jax_setup import host_fingerprint

        host = host_fingerprint()
    return hashlib.sha256(
        (_code_fingerprint() + jax.__version__ + host).encode()
    ).hexdigest()[:16]


_PRUNE_AGE_S = 7 * 24 * 3600
_PRUNED = False


def _prune_stale_generations(root: str, keep: str) -> None:
    """Drop superseded generation dirs, but only ones untouched for
    _PRUNE_AGE_S and only once per process: two live processes on different
    code/jax versions sharing a storage root must not delete each other's
    active caches on every write (they'd silently degrade both to
    re-tracing, and could race a sibling's in-flight tmp file)."""
    import shutil
    import time

    global _PRUNED
    if _PRUNED:
        return
    _PRUNED = True
    now = time.time()
    try:
        for name in os.listdir(root):
            path = os.path.join(root, name)
            if name == keep or not os.path.isdir(path):
                continue
            try:
                ages = [os.path.getmtime(path)]
                with os.scandir(path) as it:
                    ages += [e.stat().st_mtime for e in it]
            except OSError:
                continue
            if now - max(ages) > _PRUNE_AGE_S:
                shutil.rmtree(path, ignore_errors=True)
    except OSError:
        pass


def _blob_path(key_parts: Sequence[Any]) -> str:
    ident = repr(tuple(key_parts)) + _backend.name()
    digest = hashlib.sha256(ident.encode()).hexdigest()
    return os.path.join(cache_dir(), _generation(), f"{digest}.jaxexport")


def generation_inventory() -> dict:
    """Blob count/bytes of the CURRENT cache generation — what a prewarm
    pass (runtime/prewarm.py) can load without tracing. One cheap
    directory scan; zeros when the cache is disabled or empty."""
    out = {"n_blobs": 0, "bytes": 0, "dir": None}
    try:
        if not enabled():
            return out
        gen_dir = os.path.join(cache_dir(), _generation())
        out["dir"] = gen_dir
        with os.scandir(gen_dir) as it:
            for entry in it:
                if entry.name.endswith(".jaxexport"):
                    out["n_blobs"] += 1
                    out["bytes"] += entry.stat().st_size
    except OSError:
        pass
    return out


def aot_jit(fn, key_parts: Sequence[Any], example_args: Tuple[Any, ...]):
    """Return (callable, source) where source is "aot" (deserialized, no
    tracing) or "traced". The callable has the same signature as ``fn`` and
    is jit-compiled either way.

    ``example_args`` are only inspected for shape/dtype (avals); on the cold
    path they drive one ``jax.export`` trace that doubles as the live
    executable, so tracing happens at most once per process either way.
    """
    import jax

    if not enabled():
        return jax.jit(fn), "traced"

    from jax import export as jex

    from ..obs import counter_inc

    path = _blob_path(key_parts)
    if os.path.exists(path):
        try:
            with open(path, "rb") as f:
                exp = jex.deserialize(f.read())
            counter_inc("tpuml_aot_cache_hits_total")
            return jax.jit(exp.call), "aot"
        except Exception as e:  # noqa: BLE001 — stale/corrupt blob: re-trace
            logger.warning("AOT blob %s unreadable (%r); re-tracing", path, e)
            try:
                os.remove(path)
            except OSError:
                pass
    counter_inc("tpuml_aot_cache_misses_total")

    # Pallas kernels lower to Mosaic custom calls, which jax.export flags
    # as non-stable across versions; the generation directory already keys
    # on jax version + code content, so replay of a same-generation blob is
    # safe — disable the stability check.
    checks = [
        jex.DisabledSafetyCheck.custom_call("tpu_custom_call"),
        jex.DisabledSafetyCheck.custom_call("Mosaic"),
    ]
    refusal = None
    try:
        exp = jex.export(jax.jit(fn), disabled_checks=checks)(*example_args)
    except Exception as e:  # noqa: BLE001 — told apart just below
        refusal = e
    if refusal is not None:
        # export traces AND lowers: a program the compiler refuses (a bad
        # Pallas block shape, say) must surface as the job's one error,
        # not be retried as a plain jit that fails again elsewhere. Only
        # when the plain lowering passes was the refusal export's own.
        jax.jit(fn).lower(*example_args)
        logger.warning("AOT export refused (%r); tracing instead", refusal)
        return jax.jit(fn), "traced"
    try:
        blob = exp.serialize()
        os.makedirs(os.path.dirname(path), exist_ok=True)
        _prune_stale_generations(cache_dir(), _generation())
        tmp = f"{path}.tmp.{os.getpid()}"
        with open(tmp, "wb") as f:
            f.write(blob)
        os.replace(tmp, path)  # atomic: concurrent executors race safely
    except OSError as e:  # read-only or full storage root: run uncached
        logger.warning("AOT blob not written (%r)", e)
    return jax.jit(exp.call), "traced"

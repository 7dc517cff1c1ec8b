"""Multi-tenant staged-dataset cache: one device copy per (dataset, device).

The per-TrialData staging cache in parallel/trial_map keyed device copies
on the TrialData *object*, so N concurrent jobs that each resolved their
own TrialData over the same public dataset re-staged it N times — N x the
~3.4 s upload the r5 cold-start breakdown measured, for bytes already
sitting in HBM (ROADMAP item 5; PAPER.md §2's task-farm shape makes the
same-dataset fan-out the common case, not the corner).

This module is the process-global replacement:

- **content-fingerprint keys**: every staged entry is keyed by a sha1 over
  the dataset's actual bytes + shape/dtype + ``n_classes`` + an optional
  ``preprocess_salt`` attribute, plus the default device identity and the
  caller's entry subkey (placement, prepared-form salt).
  Two TrialData objects with identical content share one device copy; a
  dtype or preprocessing difference can never collide. Beyond raw
  dataset tensors, the same keying carries *solver precomputes*: the
  packed LogReg path stages its padded bf16 design matrix and its
  per-(dataset, fold-signature) Lipschitz bound here
  (``models/logistic.py::batched_staged_extras`` via the trial engine's
  ``batched_extra`` subkeys), so repeat dispatches hit instead of
  recomputing.
- **single-flight staging**: concurrent misses on one key perform exactly
  ONE upload — later arrivals wait on the maker's event and reuse its
  entry. ``stats()["uploads"]`` is the observable the concurrency
  benchmark (benchmarks/staging_concurrency.py) and its fast test pin.
- **mesh-shaped entries** (the elastic trial fabric,
  docs/ARCHITECTURE.md "Elastic trial fabric"): a multi-device mesh job
  stages the dataset with ONE host->device upload per
  (dataset, host) — the plain single-device entry, shared with
  single-device jobs — and then builds its mesh-placed form (trial-axis
  replicated or data-axis row-sharded) with an on-device
  ``jax.device_put`` broadcast/reshard that moves bytes over ICI, never
  through the host again. Mesh entries carry the mesh axis spec in
  their subkey so the 1-D replicated and 2-D sharded forms coexist;
  they are cached with ``transport="ici"``, which counts
  ``replications``/``ici_bytes`` instead of host ``uploads`` —
  ``uploads_by_key()`` therefore keeps meaning *host* uploads, the
  <=1-per-(dataset, host) observable the mesh tests pin.
- **refcounted LRU under a device-memory budget**: runs pin the entries
  they touch (``pin_begin``/``pin_end``, wired through
  ``trial_map.run_trials``); eviction walks LRU order, skips pinned
  entries, and stops at ``CS230_STAGE_CACHE_MB`` (default: 40% of the
  device's reported memory limit).
- **observability**: ``tpuml_stage_cache_{hits,misses,uploads,evictions}
  _total`` counters + ``tpuml_stage_cache_{bytes,entries}`` gauges
  (docs/OBSERVABILITY.md), and ``stage.upload`` / ``stage.evict``
  flight-recorder events.

``CS230_STAGE_CACHE=0`` disables the module entirely and restores the
legacy per-TrialData staging path bit-for-bit (parity-pinned in
tests/test_stage_cache.py).
"""

from __future__ import annotations

import collections
import hashlib
import os
import threading
import time
from typing import Any, Callable, Dict, List, Optional, Tuple

from ..obs import counter_inc, gauge_set, record_event
from ..utils.logging import get_logger

logger = get_logger("tpuml.stagecache")


def enabled() -> bool:
    """CS230_STAGE_CACHE=0 restores the legacy per-TrialData staging
    cache (the parity valve). Read per call so tests can flip it live."""
    return os.environ.get("CS230_STAGE_CACHE", "1") != "0"


def strict_enabled() -> bool:
    """CS230_STAGE_STRICT=1 turns the stage budget from advisory into a
    hard ceiling: a single host upload larger than ``budget_bytes()``
    raises :class:`StageBudgetExceeded` instead of staging anyway. On a
    real device that oversize ``device_put`` is an HBM OOM; the strict
    valve reproduces the failure deterministically on CPU, which is how
    the streaming OOM-repro benchmark/tests pin "legacy staging fails
    where CS230_STREAM completes" (benchmarks/streaming_micro.py)."""
    return os.environ.get("CS230_STAGE_STRICT", "0") == "1"


class StageBudgetExceeded(RuntimeError):
    """A single staged entry exceeds the stage-cache budget under
    ``CS230_STAGE_STRICT=1`` — the CPU-deterministic stand-in for the
    device OOM the same upload would hit on real hardware."""


def budget_bytes() -> int:
    """Device-memory budget for staged entries. ``CS230_STAGE_CACHE_MB``
    pins it; the default is 40% of the device's reported bytes_limit
    (backends without memory_stats fall back to the same 8 GB assumption
    the trial engine's chunk planner uses)."""
    env = os.environ.get("CS230_STAGE_CACHE_MB")
    if env:
        try:
            return max(int(float(env) * 1e6), 1)
        except ValueError:
            pass
    try:
        import jax

        stats = jax.devices()[0].memory_stats()
        if stats and "bytes_limit" in stats:
            return int(0.4 * stats["bytes_limit"])
    except Exception:  # noqa: BLE001 — no backend / no stats: fallback
        pass
    return int(0.4 * 8e9)


def dataset_fingerprint(data) -> str:
    """Content fingerprint of a TrialData: sha1 over the dataset bytes,
    shape/dtype signature, n_classes, and the optional ``preprocess_salt``
    attribute (preprocessing pipelines that rewrite bytes already move the
    hash; the salt covers semantic changes that do not — e.g. a label
    re-encode producing identical bytes by coincidence). Cached on the
    TrialData object: the hash walks every byte once (~0.1 s for the 25 MB
    covertype matrix), which is noise next to one staging upload but not
    next to a cache hit."""
    fp = getattr(data, "_content_fp", None)
    if fp is not None:
        return fp
    import numpy as np

    h = hashlib.sha1()
    X = data.X
    leaves = (
        [X[k] for k in sorted(X)] if isinstance(X, dict) else [X]
    )
    for leaf in leaves:
        a = np.ascontiguousarray(np.asarray(leaf))
        h.update(repr((a.shape, str(a.dtype))).encode())
        h.update(a.tobytes())
    y = np.ascontiguousarray(np.asarray(data.y))
    h.update(
        repr((y.shape, str(y.dtype), int(getattr(data, "n_classes", 0)))).encode()
    )
    h.update(y.tobytes())
    h.update(str(getattr(data, "preprocess_salt", "")).encode())
    fp = h.hexdigest()
    try:
        object.__setattr__(data, "_content_fp", fp)
    except Exception:  # noqa: BLE001 — exotic TrialData subclass: recompute
        pass
    return fp


def host_signature() -> tuple:
    """Host identity for mesh-shaped cache keys: the "once per host" half
    of the mesh staging contract. Keyed by (platform, process index) —
    every process of a multi-host SPMD slice stages its own local copy,
    but all devices OF one host share it."""
    try:
        import jax

        return (str(jax.devices()[0].platform), int(jax.process_index()))
    except Exception:  # noqa: BLE001 — no backend yet
        return ("none", 0)


def _tree_nbytes(value: Any) -> int:
    import jax

    total = 0
    for leaf in jax.tree_util.tree_leaves(value):
        nbytes = getattr(leaf, "nbytes", None)
        if nbytes is not None:
            total += int(nbytes)
    return total


class _Entry:
    __slots__ = ("value", "nbytes", "refs")

    def __init__(self, value: Any, nbytes: int):
        self.value = value
        self.nbytes = nbytes
        #: live pins from in-flight runs — never evicted while > 0
        self.refs = 0


class StagedDatasetCache:
    """Process-global refcounted LRU of device-resident staged tensors.

    Keys are opaque tuples built by the trial engine:
    ``(dataset_fingerprint, device_signature, *entry_subkey)``. Values are
    whatever the staging ``make()`` returned (device arrays / pytrees).
    """

    def __init__(self):
        self._lock = threading.Lock()
        self._entries: "collections.OrderedDict[Any, _Entry]" = (
            collections.OrderedDict()
        )
        #: key -> Event for a staging upload currently in flight
        self._inflight: Dict[Any, threading.Event] = {}
        self._bytes = 0
        self._local = threading.local()
        self._stats = {
            "hits": 0,
            "misses": 0,
            "uploads": 0,
            "evictions": 0,
            "unevictable_overflows": 0,
            # ---- mesh fabric accounting (transport="ici" entries) ----
            #: on-device broadcast/reshard builds of mesh-shaped entries
            "replications": 0,
            #: bytes uploaded host->device (misses of transport="host"
            #: entries)
            "host_upload_bytes": 0,
            #: bytes moved device-to-device (ICI on TPU meshes) building
            #: mesh-shaped entries
            "ici_bytes": 0,
        }
        #: per-key upload counts — the concurrency benchmark's observable
        self._uploads_by_key: collections.Counter = collections.Counter()

    # ---------------- pin scopes (refcounting) ----------------
    #
    # A run (trial_map.run_trials) opens a pin scope; every entry it
    # touches gains one ref for the scope's lifetime, so eviction under
    # memory pressure can never drop a tensor out from under an in-flight
    # dispatch. Scopes are per-thread and nest (coordinator job threads
    # and cluster workers each run their own).

    def pin_begin(self) -> int:
        stack = getattr(self._local, "stack", None)
        if stack is None:
            stack = self._local.stack = []
        stack.append(set())
        return len(stack)

    def pin_end(self, token: int) -> None:
        stack = getattr(self._local, "stack", None)
        if not stack:
            return
        pinned = stack.pop()
        with self._lock:
            for key in pinned:
                entry = self._entries.get(key)
                if entry is not None:
                    entry.refs = max(0, entry.refs - 1)

    def _pin_locked(self, key: Any) -> None:
        stack = getattr(self._local, "stack", None)
        if not stack:
            return
        scope = stack[-1]
        if key not in scope:
            scope.add(key)
            entry = self._entries.get(key)
            if entry is not None:
                entry.refs += 1

    # ---------------- explicit refs (cross-thread pins) ----------------
    #
    # Pin scopes are thread-local, which is right for a run's own thread
    # but useless for the streaming prefetch worker: it stages block i+1
    # on a different thread than the one consuming block i. acquire()
    # therefore takes an explicit ref on the staged entry that release()
    # drops from ANY thread — the streamer holds one per in-flight or
    # prefetched block so LRU pressure can never evict them mid-pass.

    def acquire(
        self, key: Any, make: Callable[[], Any], *,
        transport: str = "host", ici_bytes: Optional[int] = None,
    ) -> Tuple[Any, str]:
        """``get_or_stage`` plus one explicit ref on the entry. The loop
        closes the stage->pin race: if the entry was evicted between the
        stage returning and the ref landing (another tenant's burst), we
        simply re-stage — the ref is only ever taken on a live entry
        holding the value we are about to hand out."""
        while True:
            value, outcome = self.get_or_stage(
                key, make, transport=transport, ici_bytes=ici_bytes
            )
            with self._lock:
                entry = self._entries.get(key)
                if entry is not None and entry.value is value:
                    entry.refs += 1
                    return value, outcome

    def release(self, key: Any) -> None:
        """Drop one explicit ref taken by :meth:`acquire`."""
        with self._lock:
            entry = self._entries.get(key)
            if entry is not None:
                entry.refs = max(0, entry.refs - 1)

    # ---------------- lookup / staging ----------------

    def get_or_stage(
        self, key: Any, make: Callable[[], Any], *,
        transport: str = "host", ici_bytes: Optional[int] = None,
    ) -> Tuple[Any, str]:
        """Return ``(value, outcome)`` where outcome is ``"hit"`` (cached),
        ``"wait"`` (another thread staged it while we waited — no upload
        paid by THIS caller beyond the wait), or ``"miss"`` (this caller
        performed the upload). Exactly one concurrent caller per key runs
        ``make()``; a failed make releases the waiters to retry (the next
        one becomes the maker).

        ``transport`` attributes the miss's bytes: ``"host"`` (default)
        is a host->device staging upload and counts toward ``uploads`` /
        ``host_upload_bytes``; ``"ici"`` is an on-device broadcast/reshard of
        an already-resident tensor (mesh-shaped entries) and counts
        toward ``replications`` / ``ici_bytes`` instead — *never* toward
        the host upload counters the <=1-per-(dataset, host) contract
        is asserted on. ``ici_bytes`` overrides the traffic estimate for
        an ici miss (e.g. nbytes x (n_devices - 1) for a full replicate);
        default is the made value's footprint."""
        waited = False
        while True:
            with self._lock:
                entry = self._entries.get(key)
                if entry is not None:
                    self._entries.move_to_end(key)
                    self._stats["hits"] += 1
                    self._pin_locked(key)
                    counter_inc("tpuml_stage_cache_hits_total")
                    return entry.value, ("wait" if waited else "hit")
                ev = self._inflight.get(key)
                if ev is None:
                    ev = threading.Event()
                    self._inflight[key] = ev
                    break
            waited = True
            ev.wait()

        t0 = time.perf_counter()
        try:
            value = make()
        except BaseException:
            # release waiters to retry (one becomes the next maker)
            with self._lock:
                self._inflight.pop(key, None)
            ev.set()
            raise
        wall_s = time.perf_counter() - t0
        nbytes = _tree_nbytes(value)
        ici = transport == "ici"
        budget = budget_bytes()
        if not ici and strict_enabled() and nbytes > budget:
            # strict budget: refuse the oversize upload (the CPU stand-in
            # for the HBM OOM it would be on device) and release waiters
            # — they will retry and hit the same ceiling deterministically
            with self._lock:
                self._stats["unevictable_overflows"] += 1
                self._inflight.pop(key, None)
            ev.set()
            del value
            counter_inc("tpuml_stage_cache_overflow_total")
            record_event(
                "stage.overflow", key=repr(key), nbytes=nbytes,
                budget_bytes=budget, reason="strict",
            )
            raise StageBudgetExceeded(
                f"staged entry {key!r} is {nbytes / 1e6:.1f} MB but the "
                f"stage budget is {budget / 1e6:.1f} MB "
                "(CS230_STAGE_STRICT=1); stream the dataset instead "
                "(CS230_STREAM, data/streaming.py) or raise "
                "CS230_STAGE_CACHE_MB"
            )
        moved = int(ici_bytes) if (ici and ici_bytes is not None) else nbytes
        evicted: List[Tuple[Any, int]] = []
        overflow = 0
        with self._lock:
            self._entries[key] = _Entry(value, nbytes)
            self._entries.move_to_end(key)
            self._bytes += nbytes
            self._stats["misses"] += 1
            if ici:
                self._stats["replications"] += 1
                self._stats["ici_bytes"] += moved
            else:
                self._stats["uploads"] += 1
                self._stats["host_upload_bytes"] += nbytes
                self._uploads_by_key[key] += 1
            self._pin_locked(key)
            evicted, overflow = self._evict_over_budget_locked(exclude=key)
            total_bytes, n_entries = self._bytes, len(self._entries)
            # entry inserted: waiters must see it BEFORE the event fires,
            # or they would loop back into a duplicate upload
            self._inflight.pop(key, None)
        ev.set()
        counter_inc("tpuml_stage_cache_misses_total")
        if ici:
            counter_inc("tpuml_stage_cache_replications_total")
            counter_inc("tpuml_stage_cache_ici_bytes_total", float(moved))
        else:
            counter_inc("tpuml_stage_cache_uploads_total")
            counter_inc("tpuml_stage_cache_host_upload_bytes_total", float(nbytes))
        gauge_set("tpuml_stage_cache_bytes", float(total_bytes))
        gauge_set("tpuml_stage_cache_entries", float(n_entries))
        record_event(
            "stage.replicate" if ici else "stage.upload",
            key=repr(key), nbytes=nbytes, wall_s=round(wall_s, 6),
            cache_bytes=total_bytes, cache_entries=n_entries,
            **({"ici_bytes": moved} if ici else {}),
        )
        for ekey, enbytes in evicted:
            counter_inc("tpuml_stage_cache_evictions_total")
            record_event("stage.evict", key=repr(ekey), nbytes=enbytes)
        if overflow:
            # every survivor was pinned: the cache is committed beyond
            # its budget. The overflow is forced (live tensors are never
            # dropped) but no longer silent — operators alert on the
            # counter, the flight recorder carries the context.
            counter_inc("tpuml_stage_cache_overflow_total")
            record_event(
                "stage.overflow", key=repr(key), nbytes=nbytes,
                overflow_bytes=overflow, budget_bytes=budget,
                cache_bytes=total_bytes, cache_entries=n_entries,
                reason="pinned",
            )
        return value, "miss"

    def _evict_over_budget_locked(
        self, exclude: Any = None
    ) -> Tuple[List[Tuple[Any, int]], int]:
        """LRU eviction down to the budget, skipping pinned entries and
        the just-inserted key (a single over-budget dataset must stage and
        serve its run, then age out). Returns the evicted (key, nbytes)
        plus the bytes still over budget after eviction (non-zero only
        when every survivor is pinned — the caller emits the overflow
        counter/event outside the lock)."""
        budget = budget_bytes()
        evicted: List[Tuple[Any, int]] = []
        if self._bytes <= budget:
            return evicted, 0
        for key in list(self._entries):
            if self._bytes <= budget:
                break
            entry = self._entries[key]
            if key == exclude or entry.refs > 0:
                continue
            del self._entries[key]
            self._bytes -= entry.nbytes
            self._stats["evictions"] += 1
            evicted.append((key, entry.nbytes))
        overflow = max(self._bytes - budget, 0)
        if overflow:
            # every survivor is pinned (or the newcomer itself): nothing
            # more can go — record the overflow, never drop live tensors
            self._stats["unevictable_overflows"] += 1
        if evicted:
            logger.info(
                "Staged-dataset cache evicted %d entries (%.1f MB) to fit "
                "the %.0f MB budget",
                len(evicted), sum(nb for _, nb in evicted) / 1e6,
                budget / 1e6,
            )
        return evicted, overflow

    # ---------------- introspection / tests ----------------

    def stats(self) -> Dict[str, Any]:
        with self._lock:
            out = dict(self._stats)
            out["entries"] = len(self._entries)
            out["bytes"] = self._bytes
            out["pinned"] = sum(
                1 for e in self._entries.values() if e.refs > 0
            )
            return out

    def uploads_by_key(self) -> Dict[Any, int]:
        """Per-key upload counts since process start (or ``clear()``) —
        the exactly-one-upload-per-(dataset, device) observable."""
        with self._lock:
            return dict(self._uploads_by_key)

    def contains(self, key: Any) -> bool:
        with self._lock:
            return key in self._entries

    def keys(self) -> List[Any]:
        with self._lock:
            return list(self._entries)

    def clear(self) -> None:
        """Drop every entry and reset counters (tests)."""
        with self._lock:
            self._entries.clear()
            self._uploads_by_key.clear()
            self._bytes = 0
            for k in self._stats:
                self._stats[k] = 0
        gauge_set("tpuml_stage_cache_bytes", 0.0)
        gauge_set("tpuml_stage_cache_entries", 0.0)


#: the process-global cache instance every executor/run shares
STAGE_CACHE = StagedDatasetCache()

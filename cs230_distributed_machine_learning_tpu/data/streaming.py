"""Out-of-core row-block streaming: datasets bigger than the stage budget.

Every pre-16 workload staged the FULL design matrix on-device, so the
largest trainable dataset was bounded by device memory minus the stage
cache budget (``data/stage_cache.py``). The paper's task-farm model
(PAPER.md §2.1 — workers fit estimators over shared-volume CSVs of
arbitrary size) has no such ceiling, and the Pallas kernels already
iterate row tiles internally (``packed_nesterov_step`` streams Ab tiles
VMEM<->HBM); this module lifts that tile loop's outer level to
HBM<->host:

- **row-block plans** (``plan_blocks``): the dataset is tiled into
  uniform row blocks (``CS230_STREAM_BLOCK_ROWS`` pins the block height;
  the default sizes blocks at ~1/8 of the stage-cache budget so a
  double-buffered pair plus the fold tensors stay well inside it). The
  last block is zero-padded to the uniform height — solver drivers see
  zero sample weights on pad rows, which contribute exactly nothing to
  gradients, histograms, or scores.
- **blocks are ordinary staged forms**: block ``i`` lives in the
  multi-tenant stage cache under
  ``(dataset_fingerprint, host_signature(), "block", *form, i)`` — so
  concurrent tenants streaming the same dataset share uploads
  (single-flight), repeat passes are cache hits while the budget allows,
  and LRU eviction reclaims blocks the pass has already consumed.
- **double-buffered upload** (``RowBlockStreamer``): a one-worker
  prefetch thread stages block ``i+1`` (host fetch -> ``device_put``)
  while the caller computes on block ``i``, hiding the transfer wall
  behind compute.
  In-flight and prefetched blocks hold an explicit cache ref
  (``StagedDatasetCache.acquire``/``release``) so LRU pressure from
  other tenants can never drop them mid-pass.
- **per-host block sets** (``host_block_set``): on a 2-D row-sharded
  mesh each host streams a disjoint contiguous range of blocks — the
  PR 15 ``"rows"`` mesh staging form generalized from "one shard per
  host" to "one block set per host" (block keys already carry
  ``host_signature()``).
- **disk-backed blocks** (``CsvBlockSource``): chunked CSV ingest
  (``data/download.py::iter_csv_chunks`` + the two-pass scaler in
  ``data/preprocess.py``) feeds blocks without ever materializing the
  full matrix on the host.

Valves (all joined into kernel ``trace_salt`` by the consuming kernels):

- ``CS230_STREAM``: ``auto`` (default — stream when the legacy staged
  form would exceed half the stage budget), ``0``/``off`` (legacy
  single-shot staging, bit-for-bit), ``1``/``force``.
- ``CS230_STREAM_BLOCK_ROWS``: pin the block height.
- ``CS230_STREAM_DOUBLE_BUFFER=0``: disable the prefetch worker (the
  A/B lever the overlap benchmark measures).

Observability: ``tpuml_stream_*`` counters, one ``stage.stream``
flight-recorder event per pass, and devprof's ``stream`` phase
(transfer wall hidden behind compute) — docs/OBSERVABILITY.md.
"""

from __future__ import annotations

import collections
import contextlib
import dataclasses
import os
import threading
import time
from concurrent.futures import ThreadPoolExecutor
from typing import Any, Callable, Iterable, Iterator, List, Optional, Tuple

import numpy as np

from ..obs import child_span, counter_inc, record_event
from ..utils.logging import get_logger
from .stage_cache import STAGE_CACHE, _tree_nbytes, budget_bytes

logger = get_logger("tpuml.streaming")

#: floor on the auto block height — below this the per-block dispatch
#: overhead dominates any transfer overlap
_MIN_BLOCK_ROWS = 256

#: auto-sized blocks target this fraction of the stage-cache budget, so a
#: double-buffered pair (in-flight + prefetched) plus the padded fold
#: tensors and a few consumed-but-unevicted blocks stay inside it
_BLOCK_BUDGET_FRACTION = 8

#: CS230_STREAM=auto streams when the legacy single-shot staged form
#: would exceed this fraction of the stage budget (past it, one dataset
#: crowds out every other tenant even when it technically fits)
_AUTO_BUDGET_FRACTION = 0.5

#: the transient device bytes of ONE block while the kernel computes on it
#: (every lane's intermediates: logits, residuals) get this multiple of the
#: stage budget. At the default budget (0.4 of the device) the resident
#: blocks, the double-buffered pair and one block's work stay within 0.7
_WORK_BUDGET_FRACTION = 0.75


def stream_mode() -> str:
    """Resolve ``CS230_STREAM``: ``off`` | ``auto`` | ``force``. Read per
    call so tests can flip it live; consuming kernels fold the RESOLVED
    mode into ``trace_salt`` so every executable cache keys on it."""
    raw = os.environ.get("CS230_STREAM", "auto").lower()
    if raw in ("0", "off", "false"):
        return "off"
    if raw in ("1", "force"):
        return "force"
    return "auto"


def stream_double_buffer() -> bool:
    """CS230_STREAM_DOUBLE_BUFFER=0 disables the prefetch worker — the
    benchmark's A/B lever for the overlap measurement."""
    return os.environ.get("CS230_STREAM_DOUBLE_BUFFER", "1") != "0"


def should_stream(nbytes: int) -> bool:
    """Stream a dataset whose legacy single-shot staged footprint is
    ``nbytes``? ``force``/``off`` override; ``auto`` compares against
    half the stage-cache budget."""
    mode = stream_mode()
    if mode == "off":
        return False
    if mode == "force":
        return True
    return float(nbytes) > _AUTO_BUDGET_FRACTION * budget_bytes()


@dataclasses.dataclass(frozen=True)
class BlockPlan:
    """Uniform row-block tiling of an ``n``-row dataset: ``n_blocks``
    blocks of ``rows`` rows each; the last block is zero-padded up to
    ``rows`` (consumers see zero sample weights on pad rows)."""

    n: int
    rows: int
    n_blocks: int

    @property
    def n_pad(self) -> int:
        return self.rows * self.n_blocks

    def start(self, i: int) -> int:
        return i * self.rows

    def size(self, i: int) -> int:
        """Real (unpadded) rows of block ``i``."""
        return min(self.n, (i + 1) * self.rows) - i * self.rows

    def block_ids(self) -> range:
        return range(self.n_blocks)


def work_budget_bytes() -> int:
    """Device bytes one block's computation may hold beside the cache."""
    return int(_WORK_BUDGET_FRACTION * budget_bytes())


def plan_blocks(n: int, row_bytes: int, rows: Optional[int] = None, *,
                work_row_bytes: int = 0) -> BlockPlan:
    """Tile ``n`` rows of ``row_bytes`` bytes each into uniform blocks.
    ``CS230_STREAM_BLOCK_ROWS`` (or the ``rows`` argument) pins the block
    height. The default is the lower of two heights: the block's own bytes
    at ``budget_bytes() / 8``, and ``work_row_bytes`` (what one row of the
    block costs while the kernel computes on it: all its lanes'
    intermediates together) at ``work_budget_bytes()``, so a block shrinks
    as the lanes grow."""
    if rows is None:
        env = os.environ.get("CS230_STREAM_BLOCK_ROWS")
        if env:
            try:
                rows = max(int(float(env)), 1)
            except ValueError:
                rows = None
    if rows is None:
        target = max(budget_bytes() // _BLOCK_BUDGET_FRACTION, 1)
        rows = int(target // max(int(row_bytes), 1))
        if work_row_bytes > 0:
            rows = min(rows, int(work_budget_bytes() // int(work_row_bytes)))
        rows = max(_MIN_BLOCK_ROWS, rows)
    rows = max(1, min(int(rows), max(int(n), 1)))
    n_blocks = max(1, -(-int(n) // rows))
    return BlockPlan(n=int(n), rows=rows, n_blocks=n_blocks)


def host_block_set(n_blocks: int, n_shards: int, shard_idx: int) -> range:
    """Disjoint contiguous block range for one host of a row-sharded
    mesh: the 2-D ``"rows"`` staging form generalized to block sets.
    Every block belongs to exactly one shard; shards differ in size by at
    most one block. Block keys already carry ``host_signature()``, so two
    hosts' block sets can never collide in the cache."""
    if not 0 <= shard_idx < n_shards:
        raise ValueError(f"shard_idx {shard_idx} outside [0, {n_shards})")
    base, extra = divmod(int(n_blocks), int(n_shards))
    start = shard_idx * base + min(shard_idx, extra)
    stop = start + base + (1 if shard_idx < extra else 0)
    return range(start, stop)


def pad_rows(blk: np.ndarray, rows: int) -> np.ndarray:
    """Zero-pad a partial tail block up to the uniform block height."""
    short = rows - blk.shape[0]
    if short <= 0:
        return blk
    pad = np.zeros((short,) + blk.shape[1:], blk.dtype)
    return np.concatenate([blk, pad], axis=0)


def array_block_source(
    arr, plan: BlockPlan
) -> Callable[[int], np.ndarray]:
    """Host block fetcher over an in-memory array: slice + zero-pad."""

    def fetch(i: int) -> np.ndarray:
        s = plan.start(i)
        blk = np.asarray(arr[s : s + plan.rows])
        return pad_rows(blk, plan.rows)

    return fetch


class RowBlockStreamer:
    """Double-buffered iterator over staged row blocks.

    ``iter_blocks()`` yields ``(block_id, row_start, device_value)`` in
    ascending block order; call it once per pass over the data (a solver
    makes one pass per iteration). While a pass runs, the in-flight block
    and the prefetched next block each hold an explicit stage-cache ref
    (``acquire``), released as the consumer advances — LRU pressure from
    concurrent tenants evicts only blocks the pass is done with, and a
    repeat pass re-stages (or re-hits) them through the ordinary
    single-flight path.

    ``fetch_host(i)`` produces the host-side block (already padded to
    ``plan.rows``); ``to_device`` uploads it. Both run on the prefetch
    worker thread when double-buffering is on.
    """

    def __init__(
        self,
        base_key: tuple,
        fetch_host: Callable[[int], Any],
        to_device: Callable[[Any], Any],
        plan: BlockPlan,
        *,
        block_ids: Optional[Iterable[int]] = None,
        double_buffer: Optional[bool] = None,
        cache=None,
        row_shape: Optional[Tuple[int, ...]] = None,
    ):
        self._base_key = tuple(base_key)
        self._fetch_host = fetch_host
        self._to_device = to_device
        self.plan = plan
        #: per-row feature shape of the DECODED block (kernel drivers
        #: derive their resident-state geometry from it)
        self.row_shape = tuple(row_shape) if row_shape is not None else None
        self._ids = (
            list(block_ids) if block_ids is not None else list(plan.block_ids())
        )
        self._db = (
            stream_double_buffer() if double_buffer is None else bool(double_buffer)
        )
        self._cache = cache if cache is not None else STAGE_CACHE
        self._stats_lock = threading.Lock()
        self.stats = {
            "passes": 0,
            "blocks": 0,       # blocks yielded (hits + uploads)
            "uploads": 0,      # blocks that paid a host upload
            "bytes": 0,        # bytes uploaded (post-compression)
            "upload_s": 0.0,   # upload wall on the worker (misses only)
            "wait_s": 0.0,     # consumer blocked waiting for a block
            "hits": 0,         # blocks the cache already held
            "dispatch_s": 0.0,  # consumer's wall on each block (its enqueue)
            "device_wait_s": 0.0,  # blocked on the device (:meth:`wait`)
        }

    def block_key(self, i: int) -> tuple:
        return self._base_key + (int(i),)

    def block_ids(self) -> List[int]:
        return list(self._ids)

    # ---------------- internals ----------------

    def _acquire(self, i: int):
        """Stage (or hit) block ``i`` with an explicit cache ref held.
        Runs on the prefetch worker when double-buffering is on."""
        key = self.block_key(i)
        made = {}

        def make():
            import jax

            val = self._to_device(self._fetch_host(int(i)))
            # block until the device copy lands so the measured wall is
            # the actual upload, not an async enqueue
            val = jax.block_until_ready(val)
            made["nbytes"] = _tree_nbytes(val)
            return val

        t0 = time.perf_counter()
        val, outcome = self._cache.acquire(key, make)
        wall = time.perf_counter() - t0
        return key, val, outcome, wall, made.get("nbytes", 0)

    def iter_blocks(self) -> Iterator[Tuple[int, int, Any]]:
        """One pass over the block set, in ascending order. Re-invoke for
        each additional pass (stats accumulate across passes)."""
        ids = list(self._ids)
        ex = (
            ThreadPoolExecutor(max_workers=1, thread_name_prefix="tpuml-stream")
            if self._db and len(ids) > 1
            else None
        )
        pending: "collections.deque" = collections.deque()
        pos = 0
        blocks = uploads = nbytes = hits = 0
        upload_s = wait_s = dispatch_s = 0.0

        def submit():
            nonlocal pos
            if pos < len(ids):
                i = ids[pos]
                pos += 1
                fut = ex.submit(self._acquire, i) if ex is not None else None
                pending.append((i, fut))

        try:
            submit()
            while pending:
                # keep exactly one extra block in flight: the worker
                # uploads block i+1 while the caller computes on block i
                submit()
                i, fut = pending.popleft()
                t0 = time.perf_counter()
                if fut is not None:
                    key, val, outcome, up_wall, up_bytes = fut.result()
                else:
                    key, val, outcome, up_wall, up_bytes = self._acquire(i)
                wait_s += time.perf_counter() - t0
                blocks += 1
                if outcome != "hit":
                    uploads += 1
                    nbytes += up_bytes
                    upload_s += up_wall
                else:
                    hits += 1
                    counter_inc("tpuml_stream_cache_hits_total")
                counter_inc("tpuml_stream_blocks_total")
                t0 = time.perf_counter()
                try:
                    yield i, self.plan.start(i), val
                finally:
                    # the consumer advanced: this block is evictable again
                    dispatch_s += time.perf_counter() - t0
                    self._cache.release(key)
        finally:
            # abandoned pass / worker error: drop refs the prefetcher took
            while pending:
                _, fut = pending.popleft()
                if fut is None:
                    continue
                try:
                    key = fut.result()[0]
                except BaseException:  # noqa: BLE001 — maker failed: no ref
                    continue
                self._cache.release(key)
            if ex is not None:
                ex.shutdown(wait=True)
            self._finish_pass(blocks, uploads, nbytes, upload_s, wait_s,
                              hits, dispatch_s)

    def _finish_pass(self, blocks, uploads, nbytes, upload_s, wait_s,
                     hits, dispatch_s):
        if blocks == 0:
            return
        with self._stats_lock:
            self.stats["passes"] += 1
            self.stats["blocks"] += blocks
            self.stats["uploads"] += uploads
            self.stats["bytes"] += nbytes
            self.stats["upload_s"] += upload_s
            self.stats["wait_s"] += wait_s
            self.stats["hits"] += hits
            self.stats["dispatch_s"] += dispatch_s
        hidden_s = max(upload_s - wait_s, 0.0)
        counter_inc("tpuml_stream_passes_total")
        if nbytes:
            counter_inc("tpuml_stream_bytes_total", float(nbytes))
        if upload_s > 0.0:
            counter_inc("tpuml_stream_upload_seconds_total", upload_s)
        if wait_s > 0.0:
            counter_inc("tpuml_stream_wait_seconds_total", wait_s)
        # devprof overlap attribution: the hidden share of the transfer
        # wall lands in the ``stream`` phase; the blocking remainder rides
        # the engine's stage accumulator like any other staging wait
        from ..obs import devprof

        devprof.device_seconds("stream", hidden_s)
        record_event(
            "stage.stream",
            blocks=blocks,
            uploads=uploads,
            nbytes=nbytes,
            upload_s=round(upload_s, 6),
            wait_s=round(wait_s, 6),
            hidden_s=round(hidden_s, 6),
            hidden_frac=(
                round(hidden_s / upload_s, 4) if upload_s > 0.0 else None
            ),
            double_buffer=self._db,
        )

    # ---------------- spans ----------------

    @contextlib.contextmanager
    def pass_span(self, kind: str):
        """The ``stream.pass`` span of one pass over the block set, opened
        by the kernel's driver around its ``iter_blocks`` loop: ``kind``
        (the driver's name for the pass: ``power`` / ``step`` / ``eval`` for
        the LogReg solver), and on exit ``blocks``, ``uploaded_bytes``,
        ``cache_hits``, ``wait_s`` (blocked on a block that was not ready)
        and ``dispatch_s`` (the driver's wall on the blocks: enqueueing
        their programs)."""
        with self._stats_lock:
            before = dict(self.stats)
        with child_span("stream.pass", kind=kind) as sp:
            yield sp
            with self._stats_lock:
                now = dict(self.stats)
            sp.attrs.update(
                blocks=now["blocks"] - before["blocks"],
                uploaded_bytes=now["bytes"] - before["bytes"],
                cache_hits=now["hits"] - before["hits"],
                wait_s=now["wait_s"] - before["wait_s"],
                dispatch_s=now["dispatch_s"] - before["dispatch_s"],
            )

    def wait(self, value):
        """Block until ``value`` is computed, as an ``executor.wait
        on=result`` span: the driver's per-step sync and final read wait
        for the device, and ``stats["device_wait_s"]`` sums them."""
        import jax

        with child_span("executor.wait", on="result"):
            t0 = time.perf_counter()
            value = jax.block_until_ready(value)
            waited = time.perf_counter() - t0
        with self._stats_lock:
            self.stats["device_wait_s"] += waited
        return value

    # ---------------- derived stats ----------------

    def hidden_fraction(self) -> Optional[float]:
        """Share of the cumulative transfer wall hidden behind compute:
        ``1 - wait/upload`` (None until an upload happened)."""
        with self._stats_lock:
            up, wait = self.stats["upload_s"], self.stats["wait_s"]
        if up <= 0.0:
            return None
        return max(0.0, 1.0 - wait / up)


class CsvBlockSource:
    """Sequential, rewindable host block source over chunked CSV ingest.

    ``open_blocks()`` must return a fresh iterator of ``(X_chunk, ...)``
    row arrays (any chunk heights — e.g. ``data/preprocess.py::
    iter_design_blocks``); this class re-chunks them to the plan's
    uniform block height. ``fetch(i)`` serves ascending block indices
    within a pass; an index rewind (a new pass) restarts the underlying
    reader, so the full matrix never materializes on the host — the
    resident set is one reader chunk plus one assembled block.
    """

    def __init__(self, open_blocks: Callable[[], Iterable[np.ndarray]], plan: BlockPlan):
        self._open = open_blocks
        self.plan = plan
        self._lock = threading.Lock()
        self._reader: Optional[Iterator[np.ndarray]] = None
        self._next_block = 0
        self._buf: List[np.ndarray] = []
        self._buf_rows = 0

    def _restart(self):
        self._reader = iter(self._open())
        self._next_block = 0
        self._buf = []
        self._buf_rows = 0

    def fetch(self, i: int) -> np.ndarray:
        rows = self.plan.rows
        with self._lock:
            if self._reader is None or i < self._next_block:
                self._restart()
            if i > self._next_block:
                # a skipped-ahead fetch (per-host block sets): discard
                # intervening rows without assembling them into blocks
                for _ in range(self._next_block, i):
                    self._fill(rows)
                    self._drop(rows)
                    self._next_block += 1
            self._fill(rows)
            blk = self._take(rows)
            self._next_block += 1
        return pad_rows(blk, rows)

    def _fill(self, rows: int):
        while self._buf_rows < rows and self._reader is not None:
            try:
                chunk = np.asarray(next(self._reader))
            except StopIteration:
                self._reader = None
                break
            if chunk.shape[0]:
                self._buf.append(chunk)
                self._buf_rows += chunk.shape[0]

    def _take(self, rows: int) -> np.ndarray:
        got: List[np.ndarray] = []
        need = rows
        while need > 0 and self._buf:
            head = self._buf[0]
            if head.shape[0] <= need:
                got.append(head)
                need -= head.shape[0]
                self._buf.pop(0)
            else:
                got.append(head[:need])
                self._buf[0] = head[need:]
                need = 0
        self._buf_rows -= sum(g.shape[0] for g in got)
        if not got:
            return np.zeros((0,), np.float32)
        return np.concatenate(got, axis=0) if len(got) > 1 else got[0]

    def _drop(self, rows: int):
        self._take(rows)

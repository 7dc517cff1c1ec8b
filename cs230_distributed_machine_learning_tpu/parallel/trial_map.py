"""Trial execution engine: a bucket of trials in one dispatch.

This is the TPU-native replacement for the reference's entire
Kafka->scheduler->worker dispatch of per-trial sklearn fits
(``task_handler.py:185-236`` fan-out; ``worker.py:289-363`` per-trial fit +
5-fold CV). ``run_trials`` buckets the trials by static (shape-determining)
config and runs each bucket as

    plan -> stage -> build -> dispatch, then one drain of all buckets

- :func:`plan_bucket` chooses the engine, the placement and the chunk
  geometry, once, from host-side facts. Five engines: ``generic`` (vmap
  over the K+1 split masks x vmap over T trials with hyperparameters as
  arrays; on a mesh XLA partitions the trial axis), ``packed`` (a kernel's
  fused ``build_batched_fn``, whole on one device or under ``shard_map``
  on every chip of a 1-D trial mesh, each on its share of the trials),
  ``chunked`` (a kernel's init / step / eval protocol: one long fit over
  several bounded dispatches), ``streamed`` (row blocks of a matrix that
  does not fit the stage budget) and ``host`` (the generic program on the
  host's CPU, for a bucket not worth one device round trip).
- ``_stage_X`` / ``_stage_folds`` / ``_stage_extras`` put the job-invariant
  tensors where the plan's placement says, through the stage cache.
- :func:`_build_executable` is the one constructor of executables (cache,
  counters, ``executor.compile`` span, cost capture, packing, AOT export).
- :func:`_dispatch` enqueues a bucket chunk by chunk without blocking;
  ``_run_chunked`` and ``_run_streamed`` run theirs blocking. All add to
  one :class:`_Run`, whose drain fetches the results.

XLA compiles a bucket once (static shapes, traced hypers); cross-trial
aggregation (argmax of mean_cv_score) happens on-device, so the only host
traffic is the final scalar results, replacing the reference's per-trial
Kafka round trips.
"""

from __future__ import annotations

import collections
import dataclasses
import functools
import os
import threading
import time
from concurrent.futures import ThreadPoolExecutor
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..data import stage_cache as _sc
from ..models.base import ModelKernel, TrialData
from ..obs import child_span, counter_inc, obs_enabled, observe
from ..ops.folds import SplitPlan
from ..utils import backend as _backend
from ..utils.aot_cache import aot_jit
from .distributed import fetch as _fetch
from .distributed import prefetch_async
from .mesh import mesh_info, pad_to_multiple
from .packing import Packed, PackSpec, pack_spec_of, pack_wrap, unpack

_compiled_cache: Dict[Any, Any] = {}


def _cache_count(hit: bool) -> None:
    """In-process executable-cache accounting (obs catalog)."""
    counter_inc(
        "tpuml_executable_cache_hits_total"
        if hit
        else "tpuml_executable_cache_misses_total"
    )


class _PhaseAcc(threading.local):
    """Per-thread phase-time accumulators for the current run_trials call:
    stage (host->device uploads on cache miss) and fetch (blocking
    device->host transfers). Thread-local because coordinator job threads
    and cluster worker loops run trial batches concurrently."""

    def __init__(self):
        self.stage = 0.0
        self.fetch = 0.0


_PHASE = _PhaseAcc()


def _sds(a):
    return jax.ShapeDtypeStruct(a.shape, a.dtype)


def _dispatch_span(mesh, engine: str, chunk_no: int, lanes: int,
                   n_trials: int, **attrs):
    """The ``executor.dispatch`` span of one chunk. ``engine`` says which
    of the four forms ran it (``packed``: a kernel's ``build_batched_fn``,
    with ``block`` trials a weight block and ``blocks`` a device, and for a
    kernel whose block is made of slabs ``slab_lanes`` lanes a slab of
    which ``slab_pad_lanes`` are dead columns, and what the kernel's
    ``dispatch_attrs`` reads off the staged extras: the packed LogReg fit's
    ``tile_skip_pct``;
    ``generic``: the vmapped fit; ``chunked``; ``streamed``), counted in
    ``tpuml_engine_dispatch_total{engine, mesh}``. ``lanes`` is the chunk
    size the executable was compiled for, so ``lanes_padding`` trial lanes
    run on a repeated hyperparameter row and are dropped after the fetch; on
    a mesh the chunk is a multiple of its devices and the lanes are counted
    (``tpuml_mesh_lanes_total{kind}``)."""
    n_devices, shape = mesh_info(mesh)
    padding = lanes - n_trials
    counter_inc(
        "tpuml_engine_dispatch_total", engine=engine,
        mesh="none" if n_devices == 1 else f"{len(shape)}d",
    )
    if n_devices > 1:
        counter_inc("tpuml_mesh_lanes_total", n_trials, kind="real")
        counter_inc("tpuml_mesh_lanes_total", padding, kind="padding")
    return child_span("executor.dispatch", engine=engine, chunk=chunk_no,
                      n_trials=n_trials, n_devices=n_devices, lanes=lanes,
                      lanes_padding=padding, **attrs)


def _xla_only(fn):
    """The form of a mesh executable that XLA partitions: ``auto`` kernel
    valves on their XLA formulation, because ``jit`` with mesh shardings
    cannot partition a Mosaic kernel. That is every family's generic
    vmapped fit and the chunked protocol on any mesh, and every family on
    a (trials, data) mesh; a kernel's ``build_batched_fn`` on a 1-D trial
    mesh is not partitioned but run whole on every device
    (:func:`_shard_batched`) and keeps its kernel. The scope decorates the
    function, so it is entered on every call of the Python body — which is
    every trace."""
    return _backend.xla_formulations()(fn)


def _deal_lanes(fn, n_dev: int, dev_chunk: int, trial_keys):
    """Deal a chunk's lanes round-robin over the devices of a trial mesh.

    ``P(trials)`` hands device ``k`` the lanes ``[k * dev_chunk, (k + 1) *
    dev_chunk)``, and the host fills the first lanes of a chunk with its
    real trials: undealt, they would pile on the first devices. Host lane
    ``j`` runs on device ``j % n_dev`` in slot ``j // n_dev``, so the
    devices' real counts differ by at most one and each device's padding
    lanes are its own. Both gathers are static permutations inside the
    executable; the host still sees "the first lanes are real"."""
    chunk = n_dev * dev_chunk
    # position dev * dev_chunk + slot of the dealt order <- host lane
    dealt_from = np.arange(chunk).reshape(dev_chunk, n_dev).T.reshape(-1)
    host_from = np.argsort(dealt_from)

    def dealt(X, y, TW, EW, hyper):
        with jax.named_scope("tpuml.pack"):
            hyper = {
                k: jnp.take(v, dealt_from, axis=0) if k in trial_keys else v
                for k, v in hyper.items()
            }
        out = fn(X, y, TW, EW, hyper)
        with jax.named_scope("tpuml.pack"):
            return jax.tree_util.tree_map(
                lambda a: jnp.take(a, host_from, axis=0), out
            )

    return dealt


def _shard_map_trials(fn, mesh, trial_axis: str, trial_keys):
    """``fn`` (a kernel's batched function for ONE device's chunk) run on
    every device of a 1-D trial mesh on that device's run of lanes:
    dataset, folds and staged extras replicated, per-trial hypers and
    every result leaf split over the trial axis. Nothing is partitioned
    and nothing crosses chips inside, so a Mosaic kernel stays in."""

    def sharded(X, y, TW, EW, hyper):
        hyper_specs = {
            k: P(trial_axis) if k in trial_keys else P() for k in hyper
        }
        return jax.shard_map(
            fn, mesh=mesh,
            in_specs=(P(), P(), P(), P(), hyper_specs),
            out_specs=P(trial_axis), check_vma=False,
        )(X, y, TW, EW, hyper)

    return sharded


def _shard_batched(fn, mesh, trial_axis: str, dev_chunk: int, trial_keys,
                   extra_keys):
    """The packed engine's mesh executable: ``fn`` under
    :func:`_shard_map_trials`, lanes dealt by :func:`_deal_lanes`, jitted
    with the shardings every mesh executable has (replicated data,
    trial-sharded hypers and results)."""
    n_dev = int(mesh.shape[trial_axis])
    trial_keys = frozenset(trial_keys)
    repl = NamedSharding(mesh, P())
    tsh = NamedSharding(mesh, P(trial_axis))
    hyper_sh = {**{k: tsh for k in trial_keys}, **{k: repl for k in extra_keys}}
    return jax.jit(
        _deal_lanes(
            _shard_map_trials(fn, mesh, trial_axis, trial_keys),
            n_dev, dev_chunk, trial_keys,
        ),
        in_shardings=(repl, repl, repl, repl, hyper_sh),
        out_shardings=tsh,
    )


# ---- device cost accounting -----------------------------------------------
#
# Each cached executable carries its XLA cost analysis (flops, bytes
# accessed), captured ONCE at construction; every dispatch adds it to the
# run, so the executor can derive achieved FLOP/s per batch: what the
# hardware executed. The analytical estimate (kernel.macs_estimate) is
# added per bucket alongside: model FLOPs, comparable across
# implementations, the MFU numerator.


def _capture_cost(fn, example_args) -> Optional[Dict[str, float]]:
    """XLA cost analysis of ``fn`` lowered at ``example_args``:
    {"flops": ..., "bytes": ...} (either value may be absent), or None when
    ``CS230_OBS=0`` or the backend/lowering offers no analysis. One extra
    trace + lower at executable-construction time, never on the dispatch
    path: the result is cached in ``_compiled_cache`` beside the
    executable."""
    if not obs_enabled():
        return None
    try:
        analysis = jax.jit(fn).lower(*example_args).cost_analysis()
        if isinstance(analysis, (list, tuple)):  # per-device form
            analysis = analysis[0] if analysis else {}
        out: Dict[str, float] = {}
        flops = analysis.get("flops")
        if flops is not None and float(flops) > 0:
            out["flops"] = float(flops)
        nbytes = analysis.get("bytes accessed")
        if nbytes is not None and float(nbytes) > 0:
            out["bytes"] = float(nbytes)
        return out or None
    except Exception:  # noqa: BLE001 — accounting must never fail a job
        return None


def _fetch_result(out, spec: Optional[PackSpec]):
    """One dispatch result -> (host pytree, n_blocking_fetches, bytes).

    Packed results (``spec`` given, or ``out`` already a ``Packed``) cross
    the link as ONE buffer via a single device_get; unpacked dicts pay one
    conversion per leaf — and under a multi-process mesh go through the
    collective fetch. Each blocking fetch feeds the
    ``tpuml_executor_fetch_seconds`` histogram and the per-run phase
    accumulator (TrialRunResult.fetch_time_s)."""
    with child_span("executor.fetch") as sp:
        t0 = time.perf_counter()
        if isinstance(out, Packed):
            out, spec = out.buf, out.spec
        # devices the result's shards are read from (a mesh result is
        # trial-sharded: one transfer a device and leaf)
        sp.attrs["n_devices"] = len({
            d for leaf in jax.tree_util.tree_leaves(out)
            if isinstance(leaf, jax.Array) for d in leaf.sharding.device_set
        }) or 1
        # waiting for the fit, apart from moving its bytes
        with child_span("executor.wait", on="result"):
            jax.block_until_ready(out)
        if spec is not None:
            buf = np.asarray(jax.device_get(out))
            result = unpack(buf, spec), 1, buf.nbytes
        else:
            host = _fetch(out)
            leaves = jax.tree_util.tree_leaves(host)
            result = host, len(leaves), sum(int(l.nbytes) for l in leaves)
        dt = time.perf_counter() - t0
        sp.attrs["bytes"] = result[2]
    observe("tpuml_executor_fetch_seconds", dt)
    _PHASE.fetch += dt
    return result


def _example_args(X, y, TW, EW, hyper_names, chunk):
    """Shape/dtype skeleton of one dispatch — drives the AOT export trace."""
    hyper = {
        k: jax.ShapeDtypeStruct((chunk,), jnp.float32)
        for k in (hyper_names or ["_pad"])
    }
    return (jax.tree_util.tree_map(_sds, X), _sds(y), _sds(TW), _sds(EW), hyper)


def _aot_key(kernel, static, X, n_classes, n_splits, chunk, hyper_names):
    """Everything that determines a bucket's traced program: the identity
    of an executable in ``_compiled_cache`` (behind its engine's tag) and
    of its AOT blob on disk."""
    leaves, treedef = jax.tree_util.tree_flatten(X)
    x_sig = (
        str(treedef),
        tuple((tuple(a.shape), str(a.dtype)) for a in leaves),
    )
    return (
        kernel.name,
        tuple(sorted((k, str(v)) for k, v in static.items())),
        x_sig,
        n_classes,
        n_splits,
        chunk,
        tuple(hyper_names),
        # env knobs read at trace time change the program without landing
        # in static: a mid-process flip must miss, on disk and in memory
        kernel.trace_salt(),
        os.environ.get("CS230_PALLAS_INTERPRET", ""),
    )


def _prepared_data(kernel, data, static_key, static):
    """Bucket-level prepare_data (tree binning etc.), cached ON the
    TrialData object so repeat jobs over a coordinator-cached dataset skip
    it. The prepare step round-trips the device (bin_data computes on
    device and is fetched back) — a large share of a tiny job's steady
    cost. Keying by (kernel, static bucket key)
    is exact: prepare_data only reads shape-determining statics, which is
    precisely what the bucket key hashes. Lifetime rides the dataset
    cache: evicting the TrialData drops the prepared forms with it."""
    cache = getattr(data, "_prepared_cache", None)
    if cache is None:
        cache = {}
        try:
            object.__setattr__(data, "_prepared_cache", cache)
        except Exception:  # exotic TrialData subclass: just don't cache
            cache = None
    # trace_salt folds in the resolve-time env knobs (CS230_TREE_DEEP_N,
    # CS230_DEEP_W_FORCE, ...) that change prepare_data output without
    # changing the static bucket key — a knob flip mid-process must miss
    key = (kernel.name, static_key, kernel.trace_salt())
    hit = cache is not None and key in cache
    # one span a bucket, hits included: on a miss the host bins the whole
    # table (quantiles, a device round trip for the codes), which is most
    # of what a forest search's first bucket does before its first dispatch
    with child_span("executor.prepare", outcome="hit" if hit else "miss",
                    bytes=0) as sp:
        if hit:
            return cache[key]
        prepared = kernel.prepare_data(np.asarray(data.X), static)
        sp.attrs["bytes"] = int(_sc._tree_nbytes(prepared))
    if cache is not None:
        cache[key] = prepared
    return prepared


#: distinct staged entries kept per dataset — each can be dataset-sized in
#: HBM, so a static-param sweep over many buckets must not pin one copy
#: per bucket forever (LRU; fold tensors and X share the budget)
_STAGED_CACHE_MAX = 6

#: one lock for every TrialData._device_cache — coordinator job threads
#: share DatasetCache entries, so inserts/evictions on the same OrderedDict
#: can interleave; operations under the lock are dict-op cheap
_STAGED_LOCK = threading.Lock()


def _device_sig() -> tuple:
    """Default-device identity for the staged-dataset cache key — the
    "per (dataset, device)" half of the multi-tenant staging contract."""
    d = jax.devices()[0]
    return (str(d.platform), int(d.id))


def _mesh_leaf_sharding_fn(mesh, data_axis, n):
    """THE row-sharding rule for dataset pytrees on a mesh, shared by the
    staging path (_staged_mesh — what gets placed) and the executable
    path (_get_compiled's in_shardings — what jit expects): leaves whose
    leading dim is the sample count shard their rows over ``data_axis``
    (2-D mesh), everything else replicates. One function so the
    staged-placement == in_shardings invariant cannot drift: a divergence
    would make every dispatch silently re-shard the full dataset."""
    replicated = NamedSharding(mesh, P())

    def leaf_sharding(leaf):
        if (
            data_axis is not None
            and hasattr(leaf, "ndim") and leaf.ndim >= 1
            and leaf.shape[0] == n
        ):
            spec = [None] * leaf.ndim
            spec[0] = data_axis
            return NamedSharding(mesh, P(*spec))
        return replicated

    return leaf_sharding


def _data_row_count(data) -> int:
    """Sample count used to recognize row-sharded leaves — one derivation
    for both users of _mesh_leaf_sharding_fn."""
    X = data.X
    return X.shape[0] if not isinstance(X, dict) else data.n_samples


def _mesh_axes_subkey(mesh) -> tuple:
    """Mesh axis spec + device identity for mesh-shaped cache keys (staged
    forms and executables):
    (((axis, size), ...), (device ids...)). The axis spec keeps the 1-D
    trial-replicated and 2-D data-sharded staged forms of one dataset
    distinct; the device ids keep two same-shaped meshes over DIFFERENT
    device subsets distinct — an entry committed to the wrong devices
    would fail the consumer jit's in_shardings, not reshard."""
    return (
        tuple((str(a), int(s)) for a, s in mesh.shape.items()),
        tuple(int(d.id) for d in mesh.devices.flat),
    )


def _staged_mesh(data, key, dev_key, make_dev, nbytes, mesh, trial_axis,
                 replicate_only=False):
    """Mesh-shaped staged form of a job-invariant pytree: the dataset, the
    labels and fold masks, or a staged extra of a kernel's packed path
    (docs/ARCHITECTURE.md "Elastic trial fabric"): ONE host->device
    upload or build per (dataset, host) — the plain
    single-device entry ``dev_key`` built by ``make_dev``, shared with
    single-device jobs over the same content — then an on-device
    ``jax.device_put`` broadcast (1-D trial mesh: replicated) or reshard
    (2-D mesh: rows split over the data axis) that moves ``nbytes`` over
    ICI instead of N independent host uploads. Both layers ride the
    multi-tenant stage cache: single-flight (8 concurrent mesh jobs build
    one copy), refcount pinning, and LRU eviction all apply, and the mesh
    entry's subkey carries the mesh axis spec so differently-shaped meshes
    coexist.

    ``nbytes`` is the form's size on one device, for the ICI estimate;
    ``None`` reads it off the single-device entry when the mesh form has
    to be built.

    ``replicate_only=True`` forces full replication even on a 2-D mesh —
    the chunked-fit protocol's executables expect replicated data
    (its in_shardings, _run_chunked). Falls back to the legacy
    per-dispatch placement by jit when the cache valve is off."""
    if not _sc.enabled():
        # legacy: leave staging/placement to jit's sharding machinery
        return make_dev()

    n_dev, _ = mesh_info(mesh)
    data_axis = (
        None if replicate_only
        else next((a for a in mesh.shape if a != trial_axis), None)
    )
    # the shared rule: what gets placed here is exactly what
    # _get_compiled's in_shardings expect, so jit never re-shards it
    _leaf_sharding = _mesh_leaf_sharding_fn(
        mesh, data_axis, _data_row_count(data)
    )
    form = "rows" if data_axis is not None else "repl"
    mesh_key = (
        (_sc.dataset_fingerprint(data), _sc.host_signature())
        + tuple(key) + ("mesh", _mesh_axes_subkey(mesh), form)
    )

    def make_mesh():
        # layer 1 — the host upload: the ordinary single-device staged entry
        # (key-identical to the single-device f32 path, so a mesh job and
        # a single-device job over one dataset share ONE upload)
        host_val = _staged_device(data, tuple(dev_key), make_dev)
        # layer 2 — ICI: broadcast/reshard the resident copy across the
        # local mesh; device-to-device, never through the host again
        return jax.tree_util.tree_map(
            lambda leaf: jax.device_put(leaf, _leaf_sharding(leaf)),
            host_val,
        )

    if nbytes is None and not _sc.STAGE_CACHE.contains(mesh_key):
        # a form whose size only its maker knows (a kernel's staged extra):
        # build the single-device entry first and read it there
        nbytes = _sc._tree_nbytes(_staged_device(data, tuple(dev_key), make_dev))
    # replication traffic: every device beyond the source gets a full
    # copy; a row reshard moves ~one full pass of the data in total
    ici_est = (nbytes or 0) * (n_dev - 1 if form == "repl" else 1)
    with child_span("executor.stage", what="mesh." + form, of=_stage_what(key),
                    transport="ici") as sp:
        t0 = time.perf_counter()
        stage_before = _PHASE.stage
        val, outcome = _sc.STAGE_CACHE.get_or_stage(
            mesh_key, make_mesh, transport="ici", ici_bytes=ici_est
        )
        wall = time.perf_counter() - t0
        # the host upload is the inner span's; this one moved bytes over ICI
        moved = ici_est if outcome == "miss" else 0
        sp.attrs.update(outcome=outcome, bytes=0, ici_bytes=moved)
    if moved:
        counter_inc("tpuml_mesh_replicated_bytes_total", float(moved))
    if outcome != "hit":
        # the inner host upload already added its own wall to the phase
        # accumulator; add only the replicate remainder so the run's
        # staging time covers both layers without double-counting
        inner = _PHASE.stage - stage_before
        _PHASE.stage += max(0.0, wall - inner)
    return val


def _stage_what(key) -> str:
    """The ``what`` of an ``executor.stage`` span, from the entry's subkey:
    ``data`` (the design matrix, any staged form), ``folds`` (labels and
    fold masks), or the name a kernel gave a staged extra."""
    head = str(key[0])
    if head == "X":
        return "data"
    if head == "batched_extra":
        return str(key[2])
    return "folds" if head.endswith("folds") else head


def _staged_device(data, key, make):
    """Device copies of job-invariant tensors (the dataset, fold masks).

    Default path: the process-global multi-tenant staged-dataset cache
    (data/stage_cache.py), keyed by (content fingerprint, device, entry
    subkey) with single-flight uploads and refcounted LRU eviction under
    the device-memory budget — N concurrent jobs over the same dataset
    stage it ONCE per (dataset, device): re-staging a dataset-sized
    matrix per job can cost more than the fit it feeds.

    ``CS230_STAGE_CACHE=0`` falls back to the legacy per-TrialData-object
    cache below (bit-for-bit identical staging, no cross-job sharing)."""
    if _sc.enabled():
        with child_span("executor.stage", what=_stage_what(key)) as sp:
            gkey = (_sc.dataset_fingerprint(data), _device_sig()) + tuple(key)
            t0 = time.perf_counter()
            val, outcome = _sc.STAGE_CACHE.get_or_stage(gkey, make)
            dt = time.perf_counter() - t0
            sp.attrs.update(
                outcome=outcome,
                bytes=_sc._tree_nbytes(val) if outcome == "miss" else 0,
            )
        if outcome != "hit":
            if outcome == "miss":
                # only real uploads feed the histogram (hit contract);
                # "wait" time still counts as this run's staging wall
                observe("tpuml_executor_stage_seconds", dt)
            _PHASE.stage += dt
        return val
    with _STAGED_LOCK:
        cache = getattr(data, "_device_cache", None)
        if cache is None:
            cache = collections.OrderedDict()
            try:
                object.__setattr__(data, "_device_cache", cache)
            except Exception:
                cache = None
        if cache is not None and key in cache:
            cache.move_to_end(key)
            return cache[key]
    # make() outside the lock: staging can be a ~20 s host->device upload,
    # and a duplicate make() from a concurrent job thread is benign —
    # unlike a concurrent LRU eviction between insert and a re-read, which
    # would KeyError. The local `val` is returned directly so eviction of
    # this key by another thread can never fail THIS call.
    with child_span("executor.stage", what=_stage_what(key),
                    outcome="miss") as sp:
        t0 = time.perf_counter()
        val = make()
        dt = time.perf_counter() - t0
        sp.attrs["bytes"] = _sc._tree_nbytes(val)
    # only misses are observed: a cache hit is not a staging upload
    observe("tpuml_executor_stage_seconds", dt)
    _PHASE.stage += dt
    if cache is not None:
        with _STAGED_LOCK:
            cache[key] = val
            while len(cache) > _STAGED_CACHE_MAX:
                cache.popitem(last=False)
    return val


def _call_with_prepared(fn, prepared, *args):
    """Invoke a kernel cost hook, passing the prepared-data dict to kernels
    whose estimators price it (tree kernels: grouped histograms change the
    true MAC count) while staying compatible with 3-arg estimators."""
    try:
        return fn(*args, prepared=prepared)
    except TypeError:
        return fn(*args)


#: buckets whose total analytical MACs fall below this run on the HOST XLA
#: CPU backend when the default backend is an accelerator: dispatching an
#: iris-sized fit to an accelerator costs more in round-trip
#: latency than the entire computation. This is a placement decision in the
#: spirit of the reference's size-aware scheduler (scheduler_service.py:
#: 167-191), applied at the host-vs-accelerator level.
_HOST_EXEC_MACS = float(os.environ.get("CS230_HOST_EXEC_MACS", 2e8))


def _make_batched(kernel, static, has_hyper):
    from ..obs.curves import curves_enabled

    # trial telemetry plane: kernels exposing fit_curve emit bounded
    # in-scan traces as extra result leaves (curve_*) that ride the
    # packed fetch / mesh sharding like any other output. The decision is
    # baked at trace time; kernel.trace_salt() carries the valve, so
    # every executable cache re-keys when it flips.
    capture = curves_enabled() and hasattr(kernel, "fit_curve")

    def scores_for_trial(X, y, TW, EW, hyper):
        if not has_hyper:
            hyper = {}

        # the scopes label every device op of the executable in a profiler
        # trace: the fit half, the scoring half (docs/OBSERVABILITY.md)
        def one_split(tw, ew):
            if capture:
                with jax.named_scope("tpuml.fit"):
                    fitted, curve = kernel.fit_curve(X, y, tw, hyper, static)
                with jax.named_scope("tpuml.eval"):
                    out = dict(kernel.evaluate(fitted, X, y, ew, static))
                for k, v in curve.items():
                    out["curve_" + k] = v
                return out
            with jax.named_scope("tpuml.fit"):
                fitted = kernel.fit(X, y, tw, hyper, static)
            with jax.named_scope("tpuml.eval"):
                return kernel.evaluate(fitted, X, y, ew, static)

        return jax.vmap(one_split)(TW, EW)

    return jax.vmap(scores_for_trial, in_axes=(None, None, None, None, 0))


@dataclasses.dataclass
class TrialRunResult:
    """Per-trial metrics in submission order, plus batch-level timing.

    ``device_best`` is the (submission-order index, mean_cv_score) winner as
    computed ON DEVICE by the collective argmax over the mesh-sharded score
    vector — present whenever the run executed sharded dispatches on a
    multi-device mesh."""

    trial_metrics: List[Dict[str, Any]]
    compile_time_s: float
    run_time_s: float
    n_dispatches: int
    device_best: Optional[tuple] = None
    #: blocking device->host result transfers performed (a packed result:
    #: ONE per dispatched buffer; a per-leaf one: one per pytree leaf)
    n_host_fetches: int = 0
    #: bytes crossing the device->host boundary in those fetches
    result_bytes: int = 0
    #: wall seconds in host->device staging uploads (cache misses only)
    stage_time_s: float = 0.0
    #: wall seconds in blocking device->host result fetches
    fetch_time_s: float = 0.0
    # ---- device cost accounting (None when CS230_OBS=0 / unavailable) ----
    #: analytical model FLOPs of the whole run (2 * macs * splits * trials,
    #: summed over buckets whose kernel publishes macs_estimate) — the MFU
    #: numerator
    model_flops: Optional[float] = None
    #: XLA cost-analysis FLOPs summed over dispatches (what the hardware
    #: actually executed, padding and recompute included)
    xla_flops: Optional[float] = None
    #: XLA cost-analysis bytes accessed, summed over dispatches
    bytes_accessed: Optional[float] = None
    #: fraction of this run's buckets with a model-FLOP estimate (1.0 =
    #: model_flops prices the whole run; consumers must not read a partial
    #: sum as a total)
    flops_coverage: Optional[float] = None
    #: largest ``peak_bytes_in_use`` of the run's devices, the process's
    #: high-water so far and not this run's; None on the CPU
    hbm_peak_bytes: Optional[int] = None
    #: distinct devices that held shards of a dispatched result — read off
    #: the output arrays' shardings, not assumed from the mesh shape
    n_result_devices: int = 1


# ---- the bucket plan ------------------------------------------------------
#
# Which engine runs a bucket, where, and at what chunk geometry is decided
# here and nowhere else, from host-side facts only (shapes, the kernel's
# hooks, the mesh, the memory budgets): nothing below asks the kernel or
# the mesh which path it is on again.


def _host_put(a):
    """``a`` on the host's XLA CPU backend, whatever the default backend."""
    return jax.device_put(np.asarray(a), jax.local_devices(backend="cpu")[0])


@dataclasses.dataclass(frozen=True)
class Placement:
    """Where a bucket's executable runs and its staged forms live:
    ``host`` (the XLA CPU backend of an accelerator process), ``device``
    (one accelerator device: no mesh, or a mesh of one), ``mesh_1d`` (a
    mesh whose only axis is the trial axis) or ``mesh_2d`` ((trials,
    data)). ``mesh`` is None off a multi-device mesh."""

    kind: str
    mesh: Optional[Mesh] = None
    trial_axis: str = "trials"

    @classmethod
    def of(cls, mesh, trial_axis: str = "trials") -> "Placement":
        """The accelerator placement a caller's mesh asks for (the host
        CPU is the plan's choice, never the caller's)."""
        if mesh_info(mesh)[0] == 1:
            return cls("device", None, trial_axis)
        kind = "mesh_1d" if tuple(mesh.shape) == (trial_axis,) else "mesh_2d"
        return cls(kind, mesh, trial_axis)

    @property
    def n_dev(self) -> int:
        """Devices along the trial axis."""
        return 1 if self.mesh is None else int(self.mesh.shape[self.trial_axis])

    def trial_put(self):
        """How a per-trial host vector reaches its lanes."""
        if self.kind == "host":
            return _host_put
        if self.mesh is None:
            return jnp.asarray
        # placed trial-sharded straight from the host: jnp.asarray would
        # land on device 0 and be resharded every dispatch
        return functools.partial(
            jax.device_put,
            device=NamedSharding(self.mesh, P(self.trial_axis)),
        )


@dataclasses.dataclass(frozen=True)
class BucketPlan:
    """How one bucket (the trials of one static config) runs.

    ``engine``: ``host`` (the generic program on the host CPU: a bucket
    whose whole work is trivial next to one device round trip),
    ``streamed`` (row blocks through ``kernel.stream_scores``: a matrix
    that crowds the stage budget), ``chunked`` (the kernel's init / step /
    eval protocol: one fit over several bounded dispatches), ``packed``
    (the kernel's fused ``build_batched_fn``, whole on one device or on
    every device of a 1-D trial mesh) or ``generic`` (the vmapped fit,
    partitioned by XLA on a mesh).

    ``chunk`` is the trial lanes of one dispatch, all devices together.
    Packed: ``block`` trials a weight block, ``blocks`` blocks and
    ``dev_chunk`` lanes a device; ``slab_lanes`` and ``slab_pad_lanes``
    where the kernel says what a block is made of (``batched_slab``: the
    packed LogReg fit's class slab and its dead lanes). Generic and
    chunked: ``mem_cap`` trials the memory budget admits a dispatch, and
    ``split_width`` folds a dispatch when one trial's whole fold stack
    passes half a device's memory (None: all folds in one). Chunked:
    ``steps_ahead`` step programs the host may enqueue ahead of the device
    (each holds its own copy of the carried state until its consumer ran)."""

    engine: str
    placement: Placement
    static: Dict[str, Any]
    hyper_names: Tuple[str, ...]
    chunk: int
    block: Optional[int] = None
    blocks: Optional[int] = None
    dev_chunk: Optional[int] = None
    slab_lanes: Optional[int] = None
    slab_pad_lanes: Optional[int] = None
    mem_cap: Optional[int] = None
    split_width: Optional[int] = None
    chunk_plan: Optional[Dict[str, Any]] = None
    steps_ahead: Optional[int] = None
    batched_fn: Optional[Callable] = dataclasses.field(
        default=None, compare=False, repr=False
    )


def _resolved_static(kernel, static_key, n, d, n_classes, scoring=None,
                     split_plan: Optional[SplitPlan] = None):
    """A bucket's static config before its hypers are known."""
    static = kernel.static_from_key(static_key)
    if hasattr(kernel, "resolve_static"):
        static = kernel.resolve_static(static, n, d, n_classes)
    static["_n_classes"] = n_classes
    if scoring is not None:
        # only non-default scorers join the key: default jobs keep their
        # (already disk-cached) executables byte-identical
        static["_scoring"] = scoring
    if split_plan is not None and hasattr(kernel, "with_fit_rows"):
        # a kernel that fits on its split's training rows alone sizes its
        # arrays by the most rows a split trains on (a host fact of the plan)
        static = kernel.with_fit_rows(static, n, split_plan.fit_rows)
    return static


def _memory_chunk_cap(kernel, n, d, static, n_splits, n_dev) -> int:
    """Trials per dispatch bounded by per-device HBM: each in-flight trial
    holds ~memory_estimate_mb per split concurrently under the split vmap."""
    per_trial_mb = max(kernel.memory_estimate_mb(n, d, static), 0.5) * max(n_splits, 1)
    budget_mb = 0.5 * _backend.device_memory_mb() * max(n_dev, 1)
    return max(n_dev, int(budget_mb / per_trial_mb))


def _split_width(kernel, n, d, static, n_splits) -> Optional[int]:
    """Folds one dispatch may hold when a single trial's fold stack (the
    per-split working set times ``n_splits`` under the split vmap) passes
    half a device's memory: Nyström SVC's [n, m] features per split lane,
    deep or wide trees at large n. None when the stack fits. The budget is
    PER DEVICE: at one trial a device, fold memory does not divide by the
    device count."""
    per_split_mb = max(kernel.memory_estimate_mb(n, d, static), 0.5)
    budget_mb = 0.5 * _backend.device_memory_mb()
    if per_split_mb * n_splits <= budget_mb:
        return None
    width = max(1, min(n_splits, int(budget_mb / per_split_mb)))
    return width if width < n_splits else None


def plan_bucket(kernel, static, bucket_hypers, host_X, *, n, d, n_classes,
                n_splits, mesh=None, trial_axis="trials", scoring=None,
                max_trials_per_batch=256) -> BucketPlan:
    """Choose a bucket's engine, placement and chunk geometry. Host-only:
    no upload, no trace, no dispatch. ``static`` is
    :func:`_resolved_static`'s, ``bucket_hypers`` the traced hypers of the
    bucket's trials and ``host_X`` the host form that would be staged (the
    float32 matrix, or the kernel's ``prepare_data`` dict). The predicates
    run in one order, the first that holds names the engine: chunked,
    host, streamed, packed, generic."""
    if hasattr(kernel, "bucket_static"):
        static = kernel.bucket_static(static, bucket_hypers)
    n_trials = len(bucket_hypers)
    n_splits = int(n_splits)
    place = Placement.of(mesh, trial_axis)
    plan = functools.partial(
        BucketPlan, static=static,
        hyper_names=tuple(sorted(bucket_hypers[0])),
    )

    # A kernel with a chunked-fit protocol (tree ensembles) splits one
    # trial's fit across several bounded-time dispatches whenever its own
    # plan says the fit is long enough, on one device or with the trial
    # axis sharded over all of a mesh's devices (data replicated). Chunked
    # buckets always take the device path: their executables are
    # device-platform AOT blobs.
    chunk_plan = None
    if hasattr(kernel, "chunked_plan"):
        chunk_plan = _call_with_prepared(
            kernel.chunked_plan, host_X, static, n, d, n_classes, n_splits
        )
    if chunk_plan:
        n_all = mesh_info(place.mesh)[0]
        # bounded by BOTH the cross-dispatch state and the kernel's
        # per-trial working set (histogram buffers etc.)
        state_mb = 4.0 * n * max(n_classes, 1) * n_splits / 1e6
        mem_cap = _memory_chunk_cap(kernel, n, d, static, n_splits, n_all)
        chunk = max(1, min(
            n_trials, mem_cap,
            int(0.25 * n_all * _backend.device_memory_mb() / max(state_mb, 1.0)),
            64 * n_all,
        ))
        chunk = max(n_all, pad_to_multiple(chunk, n_all))
        # a step's output is a new copy of the state, alive until the next
        # step and the curve's eval have read it, and the host enqueues far
        # faster than the device runs: the state's quarter of the device(s)
        # is what bounds the steps in flight (unbounded, a search of 32
        # stages held 11 copies and one of 100 would hold 50: PERF.md, PR 34)
        state_budget_mb = 0.25 * n_all * _backend.device_memory_mb()
        steps_ahead = max(1, int(state_budget_mb / max(chunk * state_mb, 1.0)) - 1)
        return plan(
            "chunked", place, chunk=chunk, mem_cap=mem_cap,
            chunk_plan=chunk_plan, steps_ahead=steps_ahead,
            split_width=(
                _split_width(kernel, n, d, static, n_splits)
                if chunk == 1 else None
            ),
        )

    # Host fast path, decided before any accelerator transfer: dispatching
    # an iris-sized fit to an accelerator costs more in round trips than
    # the whole computation. Only kernels publishing an analytical cost
    # opt in.
    if (
        place.kind == "device"
        and not _backend.on_cpu()
        and hasattr(kernel, "macs_estimate")
        and _call_with_prepared(kernel.macs_estimate, host_X, n, d, static)
        * max(n_splits, 1) * n_trials <= _HOST_EXEC_MACS
    ):
        return plan("host", dataclasses.replace(place, kind="host"),
                    chunk=min(max_trials_per_batch, n_trials))

    # Out-of-core row-block streaming (data/streaming.py), decided BEFORE
    # any X staging so the oversized single-shot upload (the thing
    # CS230_STAGE_STRICT turns into a hard error) never happens.
    # CS230_STREAM=force/off overrides the auto threshold.
    if (
        place.kind == "device"
        and scoring is None
        and hasattr(kernel, "stream_scores")
    ):
        from ..data.streaming import should_stream, stream_mode

        if (
            stream_mode() != "off"
            and kernel.stream_applicable(static, n, d)
            and should_stream(_sc._tree_nbytes(host_X))
        ):
            return plan("streamed", place,
                        chunk=min(max_trials_per_batch, n_trials))

    # A kernel's fused batched path (the Pallas packed LogisticRegression
    # fit, the MLP epoch kernel) takes over the whole chunk: one jitted
    # call = fit + eval, with its own chunk geometry from a device's share
    # of the bucket: the kernel names the weight block that holds the
    # share, the chunk is whole blocks a device. A (trials, data) mesh and
    # a custom scorer (fused paths score by the default metric) stay on
    # the generic path, as does a bucket the kernel declines (None).
    if (
        hasattr(kernel, "build_batched_fn")
        and place.kind in ("device", "mesh_1d")
        and scoring is None
    ):
        share = -(-n_trials // place.n_dev)
        block = kernel.batched_trial_block(share, n_splits)
        dev_chunk = max(block, min(kernel.batched_chunk_cap,
                                   pad_to_multiple(share, block)))
        batched_fn = kernel.build_batched_fn(
            static=static, n=n, d=d, n_classes=n_classes, n_splits=n_splits,
            chunk=dev_chunk,
        )
        if batched_fn is not None:
            slab = (kernel.batched_slab(block, n_splits)
                    if hasattr(kernel, "batched_slab") else {})
            return plan(
                "packed", place, chunk=dev_chunk * place.n_dev, block=block,
                blocks=dev_chunk // block, dev_chunk=dev_chunk,
                batched_fn=batched_fn, **slab,
            )

    mem_cap = _memory_chunk_cap(kernel, n, d, static, n_splits, place.n_dev)
    chunk = min(max_trials_per_batch, mem_cap,
                pad_to_multiple(n_trials, place.n_dev))
    chunk = max(place.n_dev, pad_to_multiple(chunk, place.n_dev))
    return plan(
        "generic", place, chunk=chunk, mem_cap=mem_cap,
        split_width=(
            _split_width(kernel, n, d, static, n_splits)
            if chunk == place.n_dev else None
        ),
    )


# ---- the run accumulator --------------------------------------------------


class _Run:
    """What one ``run_trials`` call adds up, shared by every engine: the
    results by submission index, the timings and counts that become the
    ``TrialRunResult``, and the dispatches enqueued but not yet fetched.
    Dispatches queue without blocking and are drained at the end: each
    blocking round trip is pure latency, so a multi-bucket job (a grid
    over a static param) overlaps its transfers instead of paying them
    serially."""

    def __init__(self, n_trials: int, split_plan: SplitPlan, task: str,
                 scoring: Optional[str]):
        self.split_plan, self.task, self.scoring = split_plan, task, scoring
        self.results: List[Optional[Dict[str, Any]]] = [None] * n_trials
        self.compile_time = 0.0
        self.run_time = 0.0
        self.dispatches = 0
        self.n_fetches = 0
        self.result_bytes = 0
        # cost accounting (valve read once: a mid-run flip must not
        # produce a half-priced result)
        self.acct = obs_enabled()
        self.model_flops = 0.0
        self.n_buckets = 0
        self.buckets_priced = 0
        self.xla_flops = 0.0
        self.xla_bytes = 0.0
        #: (out | [(group out, n folds)], batch_idx[, a last step on the
        #: fetched result]) awaiting the drain
        self.pending: List[Any] = []
        #: (lane, score, batch_idx) of each chunk's collective argmax
        #: (multi-device mesh only), read at the drain
        self.pending_best: List[Any] = []
        self.device_best: Optional[tuple] = None
        self.n_result_devices = 1
        self.t_first_dispatch: Optional[float] = None
        #: the labels and fold masks on the accelerator, staged at most
        #: once a run and not at all by an all-host job
        self.device_folds: Optional[tuple] = None

    def folds(self, data, place: Placement):
        if place.kind == "host":
            return _stage_folds(data, self.split_plan, place)
        if self.device_folds is None:
            self.device_folds = _stage_folds(data, self.split_plan, place)
        return self.device_folds

    def price_bucket(self, kernel, host_X, n, d, static, n_trials) -> None:
        """Analytical model FLOPs of a whole bucket (2 * per-(trial,
        split) MACs * splits * trials): free to compute, and it covers
        every engine the bucket may take."""
        self.n_buckets += 1
        if not (self.acct and hasattr(kernel, "macs_estimate")):
            return
        try:
            macs = _call_with_prepared(kernel.macs_estimate, host_X, n, d, static)
            self.model_flops += (
                2.0 * float(macs) * max(self.split_plan.n_splits, 1) * n_trials
            )
            self.buckets_priced += 1
        except Exception:  # noqa: BLE001 — estimator bug: unpriced bucket
            pass

    def dispatched(self, cost: Optional[Dict[str, float]]) -> None:
        """One dispatch of an executable executes its cost analysis once."""
        self.dispatches += 1
        if cost:
            self.xla_flops += cost.get("flops", 0.0)
            self.xla_bytes += cost.get("bytes", 0.0)

    def await_compile(self, out, t0: float):
        """Block on a fresh executable's first dispatch, so that its XLA
        compile is attributed; steady-state dispatches queue."""
        with child_span("executor.wait", on="first_run"):
            out = jax.block_until_ready(out)
        dt = time.perf_counter() - t0
        self.compile_time += dt
        observe("tpuml_executor_compile_seconds", dt)
        return out

    def merge_best(self, idx: int, score: float) -> None:
        """sklearn's first-max rule GLOBALLY: on equal scores keep the
        smaller submission index (chunks and buckets arrive out of global
        submission order, so "first seen" is not enough)."""
        cur = self.device_best
        if cur is None or score > cur[1] or (score == cur[1] and idx < cur[0]):
            self.device_best = (idx, score)

    def chunk_best(self, place: Placement, score, batch_idx, lanes: int,
                   n_splits: int):
        """Enqueue the collective argmax over a chunk's trial-sharded
        score vector: XLA inserts the ICI all-gather / reduce, and only
        two replicated scalars come back to the host."""
        bi, bs = _chunk_best(
            place.mesh, place.trial_axis, lanes, n_splits,
            self.split_plan.n_folds,
        )(score, jnp.int32(len(batch_idx)))
        self.pending_best.append((bi, bs, batch_idx))
        self.n_result_devices = max(
            self.n_result_devices, len(score.sharding.device_set)
        )

    def read_best(self, pending) -> None:
        """Read the collective argmax of each ``(lane, score, batch_idx)``
        and merge the winners. On a mesh this is where the host waits for
        the chunks' fits: the wait is named apart from the reads."""
        with child_span("executor.fetch", what="argmax", bytes=0):
            with child_span("executor.wait", on="argmax"):
                jax.block_until_ready([(bi, bs) for bi, bs, _ in pending])
            for bi, bs, batch_idx in pending:
                pos, score = int(bi), float(bs)
                self.n_fetches += 2  # the argmax's two replicated scalars
                if pos < len(batch_idx) and np.isfinite(score):
                    self.merge_best(batch_idx[pos], score)

    def fetch(self, out, spec: Optional[PackSpec] = None):
        host, n_fetches, n_bytes = _fetch_result(out, spec)
        self.n_fetches += n_fetches
        self.result_bytes += n_bytes
        return host

    def record(self, out: Dict[str, np.ndarray], batch_idx) -> None:
        for j, gi in enumerate(batch_idx):
            self.results[gi] = _postprocess(
                out, j, self.split_plan, self.task, self.scoring
            )

    def drain(self) -> None:
        """Fetch everything enqueued and close the dispatch window."""
        # start every pending device->host copy before the first blocking
        # conversion (serial round trips otherwise)
        for bi, bs, _ in self.pending_best:
            prefetch_async((bi, bs))
        for out, *_ in self.pending:
            for og, _size in out if isinstance(out, list) else [(out, None)]:
                prefetch_async(og.buf if isinstance(og, Packed) else og)
        if self.pending_best:
            self.read_best(self.pending_best)
            self.pending_best.clear()
        for out, batch_idx, *post in self.pending:
            if isinstance(out, list):
                out = _join_split_groups(
                    [(self.fetch(og), size) for og, size in out]
                )
            else:
                out = self.fetch(out)
            for step in post:
                out = step(out)
            self.record(out, batch_idx)
        self.pending.clear()
        if self.t_first_dispatch is not None:
            self.run_time += time.perf_counter() - self.t_first_dispatch
            self.t_first_dispatch = None

    def result(self, mesh) -> TrialRunResult:
        acct = self.acct
        return TrialRunResult(
            trial_metrics=[r for r in self.results if r is not None],
            compile_time_s=self.compile_time,
            run_time_s=self.run_time,
            n_dispatches=self.dispatches,
            device_best=self.device_best,
            n_host_fetches=self.n_fetches,
            result_bytes=self.result_bytes,
            stage_time_s=_PHASE.stage,
            fetch_time_s=_PHASE.fetch,
            model_flops=(
                self.model_flops if acct and self.buckets_priced else None
            ),
            xla_flops=self.xla_flops if acct and self.xla_flops > 0 else None,
            bytes_accessed=(
                self.xla_bytes if acct and self.xla_bytes > 0 else None
            ),
            flops_coverage=(
                self.buckets_priced / self.n_buckets
                if acct and self.n_buckets else None
            ),
            hbm_peak_bytes=(
                _backend.hbm_peak_bytes(
                    list(mesh.devices.flat) if mesh is not None else None
                )
                if acct else None
            ),
            n_result_devices=self.n_result_devices,
        )


# ---- the executable builder -----------------------------------------------


@dataclasses.dataclass(frozen=True)
class _Part:
    """One program of an executable-cache entry: ``fn`` at the shapes of
    ``example``. ``disk_key`` names its AOT blob (None: jitted in this
    process only); ``mesh_jit`` jits it with a mesh's shardings instead.
    ``to_host``: its result crosses to the host (False: it is state that
    stays on the device); ``priced``: its dispatches are priced."""

    fn: Callable
    example: tuple
    disk_key: Optional[tuple] = None
    mesh_jit: Optional[Callable] = None
    to_host: bool = True
    priced: bool = True


def _build_executable(key, make_parts):
    """THE executable constructor of all four tags (``host``, ``batched``,
    ``generic``, ``chunked``: the first element of ``key``). Returns
    ``(((fn, pack_spec, cost), ...), fresh)``, one triple for each
    :class:`_Part` that ``make_parts()`` names, from ``_compiled_cache``
    where the key is there (``fresh`` False). The ``executor.compile``
    span's ``cache`` says where the entry came from: ``hit`` (this
    process's cache), ``aot`` (a disk blob), ``traced`` (built here). A
    fresh entry's stages are ``executor.build`` children of it, one a part
    and stage: ``stage`` = ``cost`` (:func:`_capture_cost`), ``pack_spec``,
    ``export`` (:func:`aot_jit`; ``source`` = aot / traced) or
    ``mesh_jit``; what is left of the parent is ``make_parts()`` itself.

    The one rule of the result's form: a ONE-DEVICE program whose result
    crosses to the host packs it (one uint8 buffer, one transfer:
    :func:`pack_wrap`), carries its XLA cost analysis (captured once, on
    the pre-pack form) and is exported to disk. A MESH program hands back
    the per-leaf dict: its score vector feeds the on-device collective
    argmax and the per-device fetch; it takes no cost capture (a sharded
    lowering would pay a second full trace; the analytical bucket
    accounting still prices it) and has no blob (process-local; the
    persistent compile cache holds its compile). A program that keeps
    state on the device packs nothing either."""
    with child_span("executor.compile", cache="hit") as sp:
        fresh = key not in _compiled_cache
        _cache_count(not fresh)
        if fresh:
            built = []
            for part in make_parts():
                fn, spec, cost, source = part.fn, None, None, "traced"
                if part.mesh_jit is not None:
                    with child_span("executor.build", stage="mesh_jit"):
                        fn = part.mesh_jit(fn)
                else:
                    if part.priced:
                        with child_span("executor.build", stage="cost"):
                            cost = _capture_cost(fn, part.example)
                    if part.to_host:
                        with child_span("executor.build", stage="pack_spec"):
                            spec = pack_spec_of(fn, part.example)
                            fn = pack_wrap(fn)
                    if part.disk_key is None:
                        fn = jax.jit(fn)
                    else:
                        with child_span("executor.build", stage="export") as bsp:
                            fn, source = aot_jit(fn, part.disk_key, part.example)
                            bsp.attrs["source"] = source
                built.append((fn, spec, cost))
            _compiled_cache[key] = tuple(built)
            sp.attrs["cache"] = source
    return _compiled_cache[key], fresh


_compile_pool: Optional[ThreadPoolExecutor] = None


def _compile_ahead(key, built, examples) -> Callable[[], tuple]:
    """Start the backend compiles (or persistent-cache loads) of a freshly
    built entry's one-device programs on worker threads. The returned
    ``wait()`` gives the entry's ``(fn, pack_spec, cost)`` triples with the
    compiled programs in the jitted ones' place, and leaves them in
    ``_compiled_cache[key]``. ``jax.jit`` compiles at a program's first
    call, on the caller's thread: a search whose every trial is a bucket
    of its own (the forests) then compiles bucket after bucket, most of a
    minute a step program at a Covertype shape, with the host's other cores idle.
    XLA compiles outside the GIL, so the caller goes on to trace the next
    bucket meanwhile."""
    global _compile_pool
    if _compile_pool is None:
        _compile_pool = ThreadPoolExecutor(
            max_workers=4, thread_name_prefix="tpuml-compile")
    compiling = [
        _compile_pool.submit(lambda fn=fn, ex=ex: fn.lower(*ex).compile())
        for (fn, _, _), ex in zip(built, examples)
    ]

    def wait():
        # for the compiler's worker threads, not for the chip
        with child_span("executor.wait", on="compile"):
            done = tuple(
                (c.result(), spec, cost)
                for c, (_, spec, cost) in zip(compiling, built)
            )
        _compiled_cache[key] = done
        return done

    return wait


def _mesh_key(mesh) -> tuple:
    """What a mesh adds to an executable's cache key: nothing off a mesh,
    else its axes and device ids (not ``id(mesh)``: a collected Mesh's
    address can be recycled by another)."""
    return () if mesh is None else (_mesh_axes_subkey(mesh),)


def _get_compiled(kernel, static_key, static, mesh, trial_axis, data, plan, chunk,
                  hyper_names, X_proto=None, y=None, TW=None, EW=None,
                  n_splits_override=None):
    """The generic (vmapped) executable of a bucket: (fn,
    pack_spec_or_None, cost_or_None, fresh). On a mesh XLA partitions it,
    so it is traced on the XLA formulations (:func:`_xla_only`)."""
    n_splits = n_splits_override or plan.n_splits
    # a 1-device mesh is compilation-equivalent to no mesh: drop the
    # NamedShardings so the executable is AOT-exportable and its disk key
    # is mesh-independent
    mesh = Placement.of(mesh, trial_axis).mesh
    X_ex = X_proto if X_proto is not None else jax.ShapeDtypeStruct(
        data.X.shape, jnp.float32
    )
    key = ("generic",) + _aot_key(
        kernel, static, X_ex, data.n_classes, n_splits, chunk, hyper_names
    )

    def parts():
        batched = _make_batched(kernel, static, bool(hyper_names))
        if mesh is None:
            example = _example_args(X_ex, y, TW, EW, hyper_names, chunk)
            return [_Part(batched, example, disk_key=key)]
        replicated = NamedSharding(mesh, P())
        trial_sharded = NamedSharding(mesh, P(trial_axis))
        ins = (replicated,) * 4
        # 2-D mesh (trials, data): additionally shard the sample dimension
        # of the dataset arrays across the data axis: XLA inserts the
        # psum / all-gather collectives inside each trial's fit
        data_axis = next((a for a in mesh.shape if a != trial_axis), None)
        if data_axis is not None and X_proto is not None:
            # shared with _staged_mesh: the staged placement and these
            # in_shardings must agree or every dispatch re-shards
            leaf_sharding = _mesh_leaf_sharding_fn(
                mesh, data_axis, _data_row_count(data)
            )
            w_sh = NamedSharding(mesh, P(None, data_axis))
            ins = (jax.tree_util.tree_map(leaf_sharding, X_proto),
                   NamedSharding(mesh, P(data_axis)), w_sh, w_sh)
        return [_Part(
            _xla_only(batched), (),
            mesh_jit=lambda fn: jax.jit(
                fn, in_shardings=ins + (trial_sharded,),
                out_shardings=trial_sharded,
            ),
        )]

    ((fn, spec, cost),), fresh = _build_executable(key + _mesh_key(mesh), parts)
    return fn, spec, cost, fresh


def _bucket_executable(kernel, bp: BucketPlan, static_key, data, split_plan,
                       X, extras):
    """The executable of a host, packed or generic bucket and whether it
    was built by this call: ((fn, pack_spec, cost), fresh)."""
    place, names = bp.placement, bp.hyper_names
    y_np, TW, EW = np.asarray(data.y), split_plan.train_w, split_plan.eval_w
    if bp.engine == "generic":
        width = bp.split_width or int(split_plan.n_splits)
        fn, spec, cost, fresh = _get_compiled(
            kernel, static_key, bp.static, place.mesh, place.trial_axis, data,
            split_plan, bp.chunk, names, X, y_np, TW[:width], EW[:width],
            n_splits_override=bp.split_width,
        )
        return (fn, spec, cost), fresh

    key = _aot_key(kernel, bp.static, X, data.n_classes, split_plan.n_splits,
                   bp.chunk, names)
    example = _example_args(X, y_np, TW, EW, names, bp.chunk)
    if bp.engine == "host":
        key = ("host",) + key

        def parts():
            # traced (the scope wraps every call of the Python body) for
            # the platform it runs on, not the default one; no blob: it
            # would be the CPU's program under the accelerator's name
            fn = _backend.host_execution()(
                _make_batched(kernel, bp.static, bool(names))
            )
            return [_Part(fn, example)]
    else:
        key = ("batched",) + key + _mesh_key(place.mesh)
        if extras:
            # the staged extras join the executable's input signature
            example[4].update({k: _sds(v) for k, v in extras.items()})
            key += ("extras", tuple(
                (k, tuple(v.shape), str(v.dtype))
                for k, v in sorted(extras.items())
            ))

        def parts():
            if place.mesh is None:
                return [_Part(bp.batched_fn, example, disk_key=key)]
            return [_Part(
                bp.batched_fn, example,
                mesh_jit=lambda fn: _shard_batched(
                    fn, place.mesh, place.trial_axis, bp.dev_chunk,
                    names or ["_pad"], sorted(extras or ()),
                ),
            )]

    (exe,), fresh = _build_executable(key, parts)
    return exe, fresh


# ---- staging by placement -------------------------------------------------


def _stage_X(data, x_key, host_X, place: Placement, replicate_only=False):
    """The bucket's design matrix (or prepared forms) where the plan
    places it, float32 as the kernels take it. On a mesh: uploaded from
    the host ONCE per (dataset, host) and broadcast / resharded over ICI
    (:func:`_staged_mesh`)."""
    if place.kind == "host":
        return _staged_device(
            data, x_key + ("host",),
            lambda: jax.tree_util.tree_map(_host_put, host_X),
        )

    def make():
        return jax.tree_util.tree_map(jnp.asarray, host_X)

    if place.mesh is None:
        return _staged_device(data, x_key + ("dev",), make)
    return _staged_mesh(
        data, x_key, x_key + ("dev",), make, _sc._tree_nbytes(host_X),
        place.mesh, place.trial_axis, replicate_only=replicate_only,
    )


def _stage_folds(data, split_plan: SplitPlan, place: Placement):
    """(y, train masks, eval masks) where the plan places the bucket. The
    host CPU takes them as they are and an unsigned plan has nothing to
    key a cache entry on. On a 1-D trial mesh every executable takes them
    replicated, so they are replicated ONCE like the dataset (left on
    device 0, each dispatch's jit copied them to every chip again)."""
    parts = (np.asarray(data.y), split_plan.train_w, split_plan.eval_w)
    if place.kind == "host":
        return tuple(_host_put(a) for a in parts)

    def make():
        return tuple(jnp.asarray(a) for a in parts)

    key = ("folds", split_plan.signature)
    if split_plan.signature is None:
        return make()
    if place.kind == "mesh_1d":
        return _staged_mesh(
            data, key, key, make, _sc._tree_nbytes(parts),
            place.mesh, place.trial_axis,
        )
    return _staged_device(data, key, make)


def _stage_extras(kernel, bp: BucketPlan, data, split_plan: SplitPlan, x_key,
                  host_X, X, folds) -> Optional[Dict[str, Any]]:
    """Dispatch-invariant forms a packed kernel wants precomputed (the
    LogReg padded bf16 design matrix and per-split Lipschitz bound):
    built ONCE per (dataset, device, subkey) in the stage cache and merged
    into every dispatch's hyper dict, so the per-dispatch jit stops paying
    for them. On a mesh like the data and the folds: one build on ONE
    chip, from the single-device entries the mesh forms were replicated
    from, then a copy to every chip over ICI."""
    if not hasattr(kernel, "batched_staged_extras"):
        return None
    n, d = data.X.shape
    place, signature = bp.placement, split_plan.signature
    specs = kernel.batched_staged_extras(
        static=bp.static, n=n, d=d, n_classes=data.n_classes,
        n_splits=split_plan.n_splits, fold_signature=signature,
        block=bp.block,
    )
    if not specs:
        return None

    def ctx():
        Xc, (y, TW, EW) = X, folds
        if place.mesh is not None:
            one = Placement("device", None, place.trial_axis)
            Xc = _stage_X(data, x_key, host_X, one)
            if signature is not None:
                y, TW, EW = _stage_folds(data, split_plan, one)
        return {"X": Xc, "y": y, "TW": TW, "EW": EW}

    extras = {}
    for name in sorted(specs):
        subkey, make = specs[name]
        build = lambda m=make: m(ctx())  # noqa: E731
        if subkey is None:
            # nothing stable to key on (an unsigned fold plan): still
            # hoisted out of the per-dispatch jit, not cached across runs
            extras[name] = build()
            continue
        ekey = ("batched_extra", kernel.name, name) + tuple(subkey)
        if place.mesh is None:
            extras[name] = _staged_device(data, ekey, build)
        else:
            extras[name] = _staged_mesh(
                data, ekey, ekey, build, None, place.mesh, place.trial_axis
            )
    return extras


# ---- dispatch ---------------------------------------------------------------


def _hyper_batch(hypers, batch_idx, hyper_names, chunk) -> Dict[str, np.ndarray]:
    """The per-trial hyper vectors of one chunk, float32 ``[chunk]``:
    padding lanes repeat the last real trial's row and are dropped after
    the fetch."""
    if not hyper_names:
        return {"_pad": np.zeros((chunk,), np.float32)}
    pad = [batch_idx[-1]] * (chunk - len(batch_idx))
    return {
        k: np.asarray([hypers[gi][k] for gi in list(batch_idx) + pad], np.float32)
        for k in hyper_names
    }


def _split_groups(TW, EW, width: int):
    """The device fold masks cut into groups of ``width`` folds, as
    ``(train masks, eval masks, n real folds)``; the last group is padded
    by repeating a fold, and its padded columns are dropped by
    :func:`_join_split_groups`."""
    groups = []
    n_splits = int(TW.shape[0])
    for s0 in range(0, n_splits, width):
        size = min(width, n_splits - s0)
        twg, ewg = TW[s0 : s0 + size], EW[s0 : s0 + size]
        if size < width:
            twg = jnp.concatenate([twg, jnp.repeat(twg[-1:], width - size, 0)])
            ewg = jnp.concatenate([ewg, jnp.repeat(ewg[-1:], width - size, 0)])
        groups.append((twg, ewg, size))
    return groups


def _join_split_groups(fetched) -> Dict[str, np.ndarray]:
    """[(host result of a fold group, n real folds)] -> one result over
    all folds."""
    return {
        k: np.concatenate([og[k][:, :size] for og, size in fetched], axis=1)
        for k in fetched[0][0]
    }


def _dispatch(run: _Run, kernel, bp: BucketPlan, exe, fresh: bool, X, folds,
              extras, hypers, idxs) -> None:
    """Enqueue a host, packed or generic bucket, chunk by chunk, without
    blocking (but for a fresh executable's first dispatch); results wait
    in ``run.pending`` for the drain."""
    fn, spec, cost = exe
    place, chunk = bp.placement, bp.chunk
    y, TW, EW = folds
    to_dev = place.trial_put()
    groups = [(TW, EW, None)]
    if bp.split_width:
        groups = _split_groups(TW, EW, bp.split_width)
    # the host engine runs the generic program: its span says so
    engine, attrs = "generic", {}
    if bp.engine == "packed":
        engine, attrs = "packed", {"block": bp.block, "blocks": bp.blocks}
        if bp.slab_lanes is not None:
            attrs.update(slab_lanes=bp.slab_lanes,
                         slab_pad_lanes=bp.slab_pad_lanes)
        if (run.acct and run.split_plan.signature is not None
                and hasattr(kernel, "dispatch_attrs")):
            # read off the staged extras: only where spans are kept, and not
            # from extras an unsigned plan builds anew for every search
            attrs.update(kernel.dispatch_attrs(bp.static, X, extras or {}))
    # a kernel whose dispatch says what it ran (the SVC solvers' steps) is
    # waited for inside the span that carries it: a chunk's fetch, one
    # round trip a chunk, precedes the next chunk's enqueue
    said = getattr(kernel, "dispatch_result", None)
    for start in range(0, len(idxs), chunk):
        batch_idx = idxs[start : start + chunk]
        with _dispatch_span(place.mesh, engine, start // chunk, chunk,
                            len(batch_idx), **attrs) as sp:
            hyper_arg = {
                k: to_dev(v) for k, v in
                _hyper_batch(hypers, batch_idx, bp.hyper_names, chunk).items()
            }
            if extras:
                hyper_arg = {**hyper_arg, **extras}
            t0 = time.perf_counter()
            if run.t_first_dispatch is None:
                run.t_first_dispatch = t0
            outs = []
            for g, (twg, ewg, size) in enumerate(groups):
                out = fn(X, y, twg, ewg, hyper_arg)
                if fresh and start == 0 and g == 0:
                    # the FIRST group only: later ones reuse the
                    # executable, their device time is run time
                    out = run.await_compile(out, t0)
                run.dispatched(cost)
                outs.append((Packed(out, spec) if spec is not None else out, size))
            if said is not None and place.n_dev == 1:
                with child_span("executor.wait", on="result"):
                    jax.block_until_ready([o.buf if isinstance(o, Packed) else o
                                           for o, _ in outs])
                out = _join_split_groups([(run.fetch(o), size) for o, size in outs])
                sp.attrs.update(said(bp.static, int(X.shape[0]), out, len(batch_idx)))
                run.record(out, batch_idx)
                continue
            if bp.split_width:
                run.pending.append((outs, batch_idx))
                continue
            out = outs[0][0]
            if place.n_dev > 1:
                run.chunk_best(place, out["score"], batch_idx, chunk,
                               int(run.split_plan.n_splits))
            run.pending.append((out, batch_idx))


def run_trials(
    kernel: ModelKernel,
    data: TrialData,
    plan: SplitPlan,
    param_dicts: Sequence[Dict[str, Any]],
    *,
    mesh: Optional[Mesh] = None,
    trial_axis: str = "trials",
    max_trials_per_batch: int = 256,
    scoring: Optional[str] = None,
    warm_only: bool = False,
) -> TrialRunResult:
    """Run all trials (one per param dict), bucketing by static config.

    ``scoring`` is a sklearn scorer name honored by every kernel's evaluate
    (ops/metrics.py registry); None keeps the reference worker's defaults
    (accuracy / r2). It joins the static dict, so it is part of every
    executable cache key.

    ``warm_only=True`` is the prewarm path (runtime/prewarm.py): every
    bucket's executable is constructed (AOT blob deserialize or trace) and
    its staged tensors uploaded, but nothing is dispatched — the returned
    result carries the construction/staging timings and no metrics.

    Entries of the staged-dataset cache touched by this run are pinned
    (refcounted) for its duration so concurrent jobs' memory-pressure
    evictions can never drop a tensor out from under a dispatch.
    """
    if scoring is not None:
        # fail loudly at the engine boundary, not inside a trace: every
        # entry point (executor, benchmarks, direct callers) inherits the
        # unknown-name / multiclass-binary / margin-capability checks
        from ..ops.metrics import validate_scoring

        validate_scoring(scoring, kernel.task, data.n_classes, kernel)
    token = _sc.STAGE_CACHE.pin_begin() if _sc.enabled() else None
    try:
        return _run_buckets(
            kernel, data, plan, param_dicts, mesh, trial_axis,
            max_trials_per_batch, scoring, warm_only,
        )
    finally:
        if token is not None:
            _sc.STAGE_CACHE.pin_end(token)


def _run_buckets(kernel, data, split_plan, param_dicts, mesh, trial_axis,
                 max_trials_per_batch, scoring, warm_only) -> TrialRunResult:
    """Bucket the trials by static (shape-determining) config; for each
    bucket: plan -> stage -> build -> dispatch (or hand it to the chunked
    or the streamed engine); drain; result."""
    n, d = data.X.shape
    run = _Run(len(param_dicts), split_plan, kernel.task, scoring)
    # phase accumulators for THIS call (thread-local: concurrent jobs in
    # other threads keep their own), read back into the TrialRunResult
    _PHASE.stage = 0.0
    _PHASE.fetch = 0.0
    buckets: Dict[Any, List[int]] = {}
    hypers: List[Dict[str, float]] = []
    for i, params in enumerate(param_dicts):
        static_key, hyper = kernel.canonicalize(params)
        hypers.append(hyper)
        buckets.setdefault(static_key, []).append(i)

    #: the dispatches of the chunked buckets built so far (off a mesh)
    chunked: List[Callable[[], None]] = []
    for static_key, idxs in buckets.items():
        static = _resolved_static(kernel, static_key, n, d, data.n_classes,
                                  scoring, split_plan)
        # bucket-level data prep (feature binning for trees): computed
        # once, shared by every trial and split, cached across jobs on the
        # TrialData. Without it every bucket stages the same [n, d] matrix:
        # keyed by placement alone, an 8-bucket MLP grid uploads X once
        if hasattr(kernel, "prepare_data"):
            host_X = _prepared_data(kernel, data, static_key, static)
            x_key = ("X", kernel.name, static_key, kernel.trace_salt())
        else:
            host_X = np.asarray(data.X, np.float32)
            x_key = ("X",)
        # host-only, but not free in a process's first search: a kernel's
        # fused path imports its Pallas module here
        with child_span("executor.plan") as sp:
            bp = plan_bucket(
                kernel, static, [hypers[i] for i in idxs], host_X, n=n, d=d,
                n_classes=data.n_classes, n_splits=split_plan.n_splits,
                mesh=mesh, trial_axis=trial_axis, scoring=scoring,
                max_trials_per_batch=max_trials_per_batch,
            )
            run.price_bucket(kernel, host_X, n, d, bp.static, len(idxs))
            sp.attrs.update(engine=bp.engine, chunk=bp.chunk)
        place = bp.placement
        if bp.engine == "streamed":
            # nothing to prewarm that is worth a full block pass: the
            # streamed executables build lazily on the first real pass
            if not warm_only:
                run.drain()  # as for the chunked engine below
                _run_streamed(run, kernel, bp, host_X, data, hypers, idxs)
            continue
        X = _stage_X(data, x_key, host_X, place,
                     replicate_only=bp.engine == "chunked")
        if bp.engine == "chunked":
            # built now, dispatched once every bucket is built, fetched at
            # the drain: a fresh bucket's programs compile on worker
            # threads while the host prepares and traces the next bucket's
            dispatch = _run_chunked(run, kernel, bp, X, run.folds(data, place),
                                    data, hypers, idxs, warm_only)
            if dispatch is not None:
                chunked.append(dispatch)
            continue
        folds = run.folds(data, place)
        extras = None
        if bp.engine == "packed":
            extras = _stage_extras(kernel, bp, data, split_plan, x_key, host_X,
                                   X, folds)
        exe, fresh = _bucket_executable(
            kernel, bp, static_key, data, split_plan, X, extras
        )
        # prewarm stops here: executables constructed, tensors staged —
        # the cold path a first trial would otherwise pay inline
        if not warm_only:
            _dispatch(run, kernel, bp, exe, fresh, X, folds, extras, hypers,
                      idxs)

    for dispatch in chunked:
        dispatch()
    run.drain()
    return run.result(mesh)


def fit_single(
    kernel: ModelKernel,
    data: TrialData,
    plan: SplitPlan,
    params: Dict[str, Any],
    split: int = 0,
):
    """Fit one configuration on one split's train subset (default: the
    holdout-train split) and return the fitted params pytree (host numpy).
    Used to materialize the best model artifact after aggregation
    (reference pickles every trial's model, worker.py:352-356; we refit
    only the winner), and per CV fold by the callable-scoring fallback."""
    n, d = data.X.shape
    static_key, hyper = kernel.canonicalize(params)
    static = _resolved_static(kernel, static_key, n, d, data.n_classes)

    if hasattr(kernel, "prepare_data"):
        X = jax.tree_util.tree_map(
            jnp.asarray, _prepared_data(kernel, data, static_key, static)
        )
    else:
        X = jnp.asarray(data.X, jnp.float32)
    y = jnp.asarray(data.y)
    w = jnp.asarray(plan.train_w[split])
    hyper_arg = {k: jnp.asarray(v, jnp.float32) for k, v in hyper.items()}
    fit_key = (
        "fit_single",
        kernel.name,
        tuple(sorted((k, str(v)) for k, v in static.items())),
        data.X.shape,
        data.n_classes,
    )

    # ensemble kernels on large data: materialize the winner's trees across
    # bounded-time dispatches too (same rationale as the chunked trial path)
    chunk_plan = None
    if hasattr(kernel, "chunked_plan") and hasattr(kernel, "fit_chunk"):
        chunk_plan = _call_with_prepared(
            kernel.chunked_plan, X, static, n, d, data.n_classes, 1
        )
    if chunk_plan:
        n_chunks = int(chunk_plan["n_chunks"])
        ck = fit_key + ("chunked", n_chunks, chunk_plan["trees_per_chunk"])
        _cache_count(ck in _compiled_cache)
        if ck not in _compiled_cache:
            _compiled_cache[ck] = (
                jax.jit(lambda X, y, w, h: kernel.chunk_init(X, y, w, h, static)),
                jax.jit(
                    lambda X, y, w, h, ci, carry: kernel.fit_chunk(
                        X, y, w, h, static, ci, carry, chunk_plan
                    )
                ),
            )
        f_init, f_chunk = _compiled_cache[ck]
        carry = f_init(X, y, w, hyper_arg)
        parts = []
        for ci in range(n_chunks):
            carry, part = f_chunk(X, y, w, hyper_arg, jnp.int32(ci), carry)
            parts.append(part)  # device arrays: dispatches pipeline
        n_units = int(static.get("n_estimators", 100))
        for p in parts:
            prefetch_async(p)
        parts = [jax.tree_util.tree_map(np.asarray, p) for p in parts]
        trees = jax.tree_util.tree_map(
            lambda *xs: np.concatenate(xs, axis=0)[:n_units], *parts
        )
        fitted = kernel.assemble_artifact(trees, X, hyper_arg, static, y, w)
        return jax.tree_util.tree_map(np.asarray, fitted), static

    _cache_count(fit_key in _compiled_cache)
    if fit_key not in _compiled_cache:
        _compiled_cache[fit_key] = jax.jit(
            lambda X, y, w, h: kernel.fit(X, y, w, h, static)
        )
    fitted = _compiled_cache[fit_key](X, y, w, hyper_arg)
    return jax.tree_util.tree_map(np.asarray, fitted), static


def run_trials_callable(
    kernel: ModelKernel,
    data: TrialData,
    plan: SplitPlan,
    params_list: Sequence[Dict[str, Any]],
    scorer,
) -> List[Dict[str, Any]]:
    """Host-side fallback for CALLABLE ``scoring``: per (trial, fold) the
    kernel fits on device (fit_single — jit-cached per static bucket, so
    the accelerated fit is kept), the fitted params are exported to a real
    sklearn estimator (runtime/sklearn_export), and the user's
    ``scorer(estimator, X_eval, y_eval)`` runs on host. Slower than the
    jitted scorer registry (one export + one host call per fold) but
    correct for ANY sklearn-scorer callable — the reference client passed
    arbitrary ``scoring`` through and its worker silently dropped it
    (DistributedLibrary core.py:135-138, worker.py:320-349); here it ranks
    trials. Returns per-trial metrics dicts shaped like _postprocess's."""
    from ..runtime.sklearn_export import to_sklearn

    X_np = np.asarray(data.X)
    y_np = np.asarray(data.y)
    results: List[Dict[str, Any]] = []
    for params in params_list:
        split_scores: List[float] = []
        scorer_errors: List[str] = []
        for s in range(plan.n_splits):
            fitted, static = fit_single(kernel, data, plan, params, split=s)
            est = to_sklearn({
                "model_type": kernel.name,
                "parameters": params,
                "static": dict(static),
                "fitted_params": fitted,
            })
            keep = np.asarray(plan.eval_w[s]) > 0
            try:
                split_scores.append(float(scorer(est, X_np[keep], y_np[keep])))
            except Exception as e:  # noqa: BLE001 — a scorer bug fails THIS
                # trial (ranked last), not the whole job
                split_scores.append(float("nan"))
                scorer_errors.append(f"split {s}: {e!r}")
        metrics: Dict[str, Any] = {"scoring": "callable",
                                   "score": split_scores[0]}
        if plan.n_folds >= 2 and len(split_scores) > 1:
            metrics["cv_scores"] = split_scores[1:]
            metrics["mean_cv_score"] = float(np.mean(split_scores[1:]))
        else:
            metrics["mean_cv_score"] = split_scores[0]
        # ANY non-finite split (a holdout-only scorer failure included)
        # marks the trial diverged — a silently-NaN holdout score with a
        # finite CV mean would hide the error entirely
        if not all(np.isfinite(v) for v in split_scores):
            metrics["mean_cv_score"] = float("-inf")
            metrics["diverged"] = True
            if scorer_errors:
                metrics["scorer_error"] = "; ".join(scorer_errors)
        results.append(metrics)
    return results


def _chunk_best(mesh, trial_axis: str, chunk: int, n_splits: int, n_folds: int):
    """Cached jitted reducer: trial-sharded [chunk, n_splits] scores ->
    replicated (argmax lane, mean-CV score). The in/out sharding mismatch is
    what makes XLA emit the cross-chip collective (all-gather or reduce over
    ICI on TPU meshes). ``n_valid`` masks padding lanes; non-finite scores
    rank last, mirroring _postprocess's diverged-trial rule."""
    key = ("chunk_best", chunk, n_splits, n_folds) + _mesh_key(mesh)
    if key in _compiled_cache:
        return _compiled_cache[key]

    @jax.named_scope("tpuml.collective")
    def reduce(score, n_valid):
        if n_folds >= 2:
            mean_cv = jnp.mean(score[:, 1:], axis=1)
        else:
            mean_cv = score[:, 0]
        lane = jnp.arange(score.shape[0])
        mean_cv = jnp.where(
            (lane < n_valid) & jnp.isfinite(mean_cv), mean_cv, -jnp.inf
        )
        i = jnp.argmax(mean_cv)  # first max: sklearn's tie rule
        return i.astype(jnp.int32), mean_cv[i]

    sharded = NamedSharding(mesh, P(trial_axis, None))
    repl = NamedSharding(mesh, P())
    fn = jax.jit(reduce, in_shardings=(sharded, repl), out_shardings=(repl, repl))
    _compiled_cache[key] = fn
    return fn


def _run_chunked(run: _Run, kernel, bp: BucketPlan, X, folds, data, hypers,
                 idxs, warm_only: bool = False) -> Optional[Callable[[], None]]:
    """Run one bucket through the kernel's chunked-fit protocol.

    init -> n_chunks x step -> eval, all vmapped over (trials, splits); the
    cross-dispatch state is the kernel's accumulator pytree (e.g. summed
    per-tree predictions for a forest). Dispatches are NOT synchronized
    between steps — they pipeline on the device queue; only eval's output is
    fetched (packed into one byte buffer on the single-device path, so the
    whole bucket's scores cross the link as ONE transfer). On a mesh the
    trial axis of hypers and state is NamedSharded across devices (data
    replicated) so each chip carries its trial slice through every chunk,
    and a chunk with an unsplit fold stack merges its collective-argmax
    winner into the run (read at once: on a mesh the bucket blocks). Off a
    mesh this only BUILDS the bucket and returns its dispatch, which the
    caller runs once every bucket is built; the result is fetched at the
    run's drain like every other bucket's. Every trial of a forest search
    is a bucket of its own: a freshly built bucket's three programs compile
    on worker threads (:func:`_compile_ahead`) while the host traces the
    next bucket's, and the dispatch waits for its own compile alone.
    """
    static, chunk_plan, chunk = bp.static, bp.chunk_plan, bp.chunk
    hyper_names = bp.hyper_names
    mesh, trial_axis = bp.placement.mesh, bp.placement.trial_axis
    y, TW, EW = folds
    n_chunks = int(chunk_plan["n_chunks"])

    from ..obs.curves import curve_points, curves_enabled

    # sampled-chunk curve stride; 0 = capture off (single-chunk plans
    # have no intermediate prefix to evaluate)
    curve_stride = (
        max(1, -(-n_chunks // curve_points()))
        if curves_enabled() and n_chunks > 1 and not warm_only
        else 0
    )

    def _h(hyper):
        return hyper if hyper_names else {}

    def init_b(X, y, TW, EW, hyper):
        with jax.named_scope("tpuml.fit"):
            return jax.vmap(
                lambda tw: kernel.chunk_init(X, y, tw, _h(hyper), static)
            )(TW)

    def step_b(X, y, TW, EW, hyper, ci, state):
        with jax.named_scope("tpuml.fit"):
            return jax.vmap(
                lambda tw, st: kernel.chunk_step(
                    X, y, tw, _h(hyper), static, ci, st, chunk_plan
                )
            )(TW, state)

    def eval_b(X, y, TW, EW, hyper, state):
        with jax.named_scope("tpuml.eval"):
            return jax.vmap(
                lambda ew, st: kernel.chunk_eval(X, y, ew, _h(hyper), static, st)
            )(EW, state)

    vinit = jax.vmap(init_b, in_axes=(None, None, None, None, 0))
    vstep = jax.vmap(step_b, in_axes=(None, None, None, None, 0, None, 0))
    veval = jax.vmap(eval_b, in_axes=(None, None, None, None, 0, 0))
    if mesh is not None:
        vinit, vstep, veval = _xla_only(vinit), _xla_only(vstep), _xla_only(veval)

    sg = bp.split_width or int(run.split_plan.n_splits)
    split_groups = _split_groups(TW, EW, sg)
    base = _aot_key(
        kernel, static, X, data.n_classes, sg, chunk, hyper_names
    ) + (n_chunks, chunk_plan.get("trees_per_chunk"))

    examples: List[tuple] = []

    def parts():
        args_i = _example_args(X, y, *split_groups[0][:2], hyper_names, chunk)
        state_ex = jax.eval_shape(vinit, *args_i)
        args_s = args_i + (jax.ShapeDtypeStruct((), jnp.int32), state_ex)
        args_e = args_i + (state_ex,)
        examples.extend((args_i, args_s, args_e))
        if mesh is None:
            # only eval's output crosses to the host; no dispatch of the
            # protocol is priced (the bucket's analytical FLOPs are)
            return [
                _Part(vinit, args_i, ("chunk_init",) + base,
                      to_host=False, priced=False),
                _Part(vstep, args_s, ("chunk_step",) + base,
                      to_host=False, priced=False),
                _Part(veval, args_e, ("chunk_eval",) + base, priced=False),
            ]
        repl = NamedSharding(mesh, P())
        tsh = NamedSharding(mesh, P(trial_axis))

        def trial_sharded(tree):
            return jax.tree_util.tree_map(lambda _: tsh, tree)

        ins = (jax.tree_util.tree_map(lambda _: repl, X), repl, repl, repl,
               trial_sharded(args_i[4]))
        st_sh = trial_sharded(state_ex)

        def jit(ins, outs):
            return lambda fn: jax.jit(fn, in_shardings=ins, out_shardings=outs)

        return [
            _Part(vinit, args_i, mesh_jit=jit(ins, st_sh)),
            _Part(vstep, args_s, mesh_jit=jit(ins + (repl, st_sh), st_sh)),
            _Part(veval, args_e, mesh_jit=jit(
                ins + (st_sh,), trial_sharded(jax.eval_shape(veval, *args_e)))),
        ]

    # compile_time here is executable construction (trace or AOT load)
    # only: the first batch's wall is real chunked compute. XLA compiles of
    # freshly traced executables land in the first batch's run_time; the
    # persistent compile cache keeps that small.
    t_build = time.perf_counter()
    key = ("chunked",) + base + _mesh_key(mesh)
    built, fresh = _build_executable(key, parts)
    if fresh:
        dt = time.perf_counter() - t_build
        run.compile_time += dt
        observe("tpuml_executor_compile_seconds", dt)
    if warm_only:
        return None
    compiled = None
    if fresh and mesh is None:
        compiled = _compile_ahead(key, built, examples)

    # what a chunked bucket did, on its dispatch spans: the chunk geometry
    # and, where the kernel says it (the forests), the shape of one fit
    shape_attrs = {"n_chunks": n_chunks, "split_lanes": sg, "mem_cap": bp.mem_cap}
    if "trees_per_chunk" in chunk_plan:
        shape_attrs["trees_per_chunk"] = int(chunk_plan["trees_per_chunk"])
    if hasattr(kernel, "dispatch_attrs"):
        shape_attrs.update(kernel.dispatch_attrs(static, X))

    def dispatch() -> None:
        fns = None
        for start in range(0, len(idxs), chunk):
            batch_idx = idxs[start : start + chunk]
            hyper_arg = {
                k: jnp.asarray(v) for k, v in
                _hyper_batch(hypers, batch_idx, hyper_names, chunk).items()
            }
            if "hist_levels_by_route" in shape_attrs:
                # tree levels histogrammed by this batch, by the form they took
                fits = (len(batch_idx) * int(run.split_plan.n_splits)
                        * int(static.get("n_estimators", 1)))
                for part in shape_attrs["hist_levels_by_route"].split(","):
                    route, count = part.split(":")
                    counter_inc("tpuml_tree_levels_total", fits * int(count), route=route)
            if run.t_first_dispatch is None:
                run.t_first_dispatch = time.perf_counter()
            with _dispatch_span(mesh, "chunked", start // chunk, chunk,
                                len(batch_idx), **shape_attrs) as sp:
                if fns is None:
                    # a fresh bucket's compile is waited for in its first
                    # batch's span, where jit's first-call compile landed
                    fns = compiled() if compiled is not None else built
                (fi, _, _), (fs, _, _), (fe, fe_spec, _) = fns
                group_outs = []
                group_curves = []
                for twg, ewg, size in split_groups:
                    state = fi(X, y, twg, ewg, hyper_arg)
                    mids = []
                    ahead = collections.deque()
                    for ci in range(n_chunks):
                        state = fs(X, y, twg, ewg, hyper_arg, jnp.int32(ci), state)
                        if bp.steps_ahead and bp.steps_ahead < n_chunks:
                            # the plan's bound on the states in flight
                            ahead.append(state)
                            if len(ahead) > bp.steps_ahead:
                                with child_span("executor.wait", on="backpressure"):
                                    jax.block_until_ready(ahead.popleft())
                        if (
                            curve_stride
                            and (ci + 1) % curve_stride == 0
                            and ci < n_chunks - 1
                        ):
                            # trial telemetry plane: score-vs-chunk curve via
                            # strided extra eval dispatches on the existing fe
                            # executable (the accumulator protocol makes every
                            # prefix a valid model) — the tree kernels themselves
                            # are untouched. eval is O(n*k) against the chunk's
                            # O(n*k*trees) build, so the sampled extra evals stay
                            # inside the curve overhead gate.
                            mids.append(fe(X, y, twg, ewg, hyper_arg, state))
                    group_outs.append((fe(X, y, twg, ewg, hyper_arg, state), size))
                    group_curves.append(mids)
                # programs enqueued for the batch: init + steps + evals (the
                # curve's sampled evals among them) of every split group
                sp.attrs["dispatches"] = (
                    (2 + n_chunks) * len(split_groups)
                    + sum(len(mids) for mids in group_curves)
                )
                run.dispatches += sp.attrs["dispatches"]
            if mesh is not None:
                score = group_outs[0][0]["score"]
                run.n_result_devices = max(
                    run.n_result_devices, len(score.sharding.device_set)
                )
                if len(split_groups) == 1:
                    # collective argmax on the trial-sharded eval output, read
                    # at once (the bucket runs blocking); split-group runs skip
                    # it: their fold means span executables
                    bi, bs = _chunk_best(
                        mesh, trial_axis, chunk, sg, run.split_plan.n_folds
                    )(score, jnp.int32(len(batch_idx)))
                    run.read_best([(bi, bs, batch_idx)])

            def with_curve(out, mids=group_curves, sizes=[z for _, z in group_outs]):
                """The drain's last step for this batch: the sampled evals
                fetched and laid beside the final one, chunk by chunk: the
                score, and each ``curve_<channel>`` leaf the kernel's eval
                names (boosting: ``gmax``). Stride and steps count trees
                (stages) where the plan says how many a chunk holds."""
                named = [k for k in out if k.startswith("curve_")]
                if not curve_stride:  # no curve: the eval's channel leaves go
                    return {k: v for k, v in out.items() if k not in named}
                at, cs = 0, {k: [] for k in ["score"] + named}
                for row, size in zip(mids, sizes):
                    fetched = [run.fetch(og, fe_spec) for og in row]
                    for k in cs:
                        cs[k].append(np.stack(
                            [f[k][:, :size] for f in fetched]
                            + [out[k][:, at:at + size]], axis=-1))
                    at += size
                for k in named:
                    out[k] = np.concatenate(cs[k], axis=1)
                out["curve_score"] = np.concatenate(cs["score"], axis=1)
                shape2 = out["score"].shape[:2]
                unit = int(chunk_plan.get("trees_per_chunk", 1))
                steps = min(n_chunks * unit, int(static.get("n_estimators", n_chunks * unit)))
                out["curve_stride"] = np.full(shape2, float(curve_stride * unit), np.float32)
                out["curve_steps"] = np.full(shape2, float(steps), np.float32)
                return out

            run.pending.append((
                [(Packed(og, fe_spec) if fe_spec is not None else og, size)
                 for og, size in group_outs],
                batch_idx, with_curve,
            ))

    if mesh is not None:
        dispatch()
        return None
    return dispatch


def _run_streamed(run: _Run, kernel, bp: BucketPlan, X_np, data, hypers,
                  idxs) -> None:
    """Run one bucket through the kernel's out-of-core streaming driver.

    The full design matrix never stages: ``kernel.stream_form`` names the
    blockable host array, ``data/streaming.py`` tiles it into row blocks
    staged (double-buffered) through the multi-tenant cache, and
    ``kernel.stream_scores`` accumulates partial gradients/histograms
    across blocks — scores match the single-shot path (bitwise for
    integer tree stats, f32-summation-order for float gradients;
    tests/test_streaming.py pins both). The padded fold tensors are
    ordinary staged entries (three small keys, so the strict budget
    judges each alone); the block cache keys carry
    ``host_signature()`` + the kernel's trace_salt + the staged form.

    The block height counts the lanes (``plan_blocks``'s
    ``work_row_bytes``: the kernel's ``stream_lane_row_bytes`` times the
    chunk's trials x splits), so a block's intermediates fit beside the
    cached blocks. The bucket runs blocking; the consumer's blocked
    block-wait time lands in ``_PHASE.stage`` like any other staging wall
    (the hidden share is devprof's ``stream`` phase), and only the driver's
    waits for the device (``streamer.wait``: ``executor.wait on=result``
    spans) are ``run_time``. Each pass over the blocks is a ``stream.pass``
    span under the chunk's ``executor.dispatch``, which also says what the
    kernel's ``stream_dispatch_attrs`` does (the LogReg fit's
    ``grad_kernel``).
    """
    from ..data.streaming import (
        RowBlockStreamer, array_block_source, plan_blocks,
    )

    split_plan = run.split_plan
    blockable, form_salt = kernel.stream_form(X_np, bp.static)
    n = int(blockable.shape[0])
    row_bytes = int(blockable.nbytes // max(n, 1))
    # every chunk is padded to ``bp.chunk`` trials: the lanes a pass computes
    lanes = bp.chunk * int(split_plan.n_splits)
    lane_row_bytes = (kernel.stream_lane_row_bytes(bp.static)
                      if hasattr(kernel, "stream_lane_row_bytes") else 0)
    bplan = plan_blocks(n, row_bytes, work_row_bytes=lanes * lane_row_bytes)
    base_key = (
        _sc.dataset_fingerprint(data), _sc.host_signature(), "block",
        kernel.name, kernel.trace_salt(), tuple(form_salt), bplan.rows,
    )
    streamer = RowBlockStreamer(
        base_key, array_block_source(blockable, bplan), jnp.asarray, bplan,
        row_shape=tuple(blockable.shape[1:]),
    )

    n_pad = bplan.n_pad
    pad = n_pad - n

    def _pad_y():
        yv = np.asarray(data.y)
        return jnp.asarray(np.concatenate([yv, np.zeros((pad,), yv.dtype)]))

    def _pad_w(W):
        W = np.asarray(W, np.float32)
        return jnp.asarray(
            np.concatenate([W, np.zeros((W.shape[0], pad), np.float32)], 1)
        )

    if split_plan.signature is not None:
        y_d = _staged_device(
            data, ("stream_folds", split_plan.signature, n_pad, "y"), _pad_y
        )
        TW_d = _staged_device(
            data, ("stream_folds", split_plan.signature, n_pad, "tw"),
            lambda: _pad_w(split_plan.train_w),
        )
        EW_d = _staged_device(
            data, ("stream_folds", split_plan.signature, n_pad, "ew"),
            lambda: _pad_w(split_plan.eval_w),
        )
    else:
        y_d, TW_d, EW_d = (
            _pad_y(), _pad_w(split_plan.train_w), _pad_w(split_plan.eval_w)
        )

    chunk = bp.chunk
    form_attrs = (kernel.stream_dispatch_attrs(
        bp.static, int(blockable.shape[1]), chunk, int(split_plan.n_splits))
        if hasattr(kernel, "stream_dispatch_attrs") else {})
    for start in range(0, len(idxs), chunk):
        batch_idx = idxs[start : start + chunk]
        hyper_batch = _hyper_batch(hypers, batch_idx, bp.hyper_names, chunk)
        before = dict(streamer.stats)
        with _dispatch_span(None, "streamed", start // chunk, chunk,
                            len(batch_idx), block_rows=bplan.rows,
                            n_blocks=bplan.n_blocks,
                            split_lanes=int(split_plan.n_splits),
                            **form_attrs):
            out = kernel.stream_scores(
                streamer, y_d, TW_d, EW_d, hyper_batch, bp.static, n
            )
        with child_span("executor.fetch") as sp:
            out = jax.device_get(streamer.wait(
                out if isinstance(out, dict) else {"score": out}))
            sp.attrs.update(n_devices=1, bytes=sum(
                int(v.nbytes) for v in out.values()))
        _PHASE.stage += streamer.stats["wait_s"] - before["wait_s"]
        run.run_time += (streamer.stats["device_wait_s"]
                         - before["device_wait_s"])
        run.dispatches += streamer.stats["blocks"] - before["blocks"]
        run.record({k: np.asarray(v, np.float32) for k, v in out.items()},
                   batch_idx)


def _postprocess(out: Dict[str, np.ndarray], j: int, plan: SplitPlan, task: str,
                 scoring: Optional[str] = None) -> Dict[str, Any]:
    """Split 0 = holdout test metrics; splits 1..K = CV fold scores.
    mean_cv_score is the trial-ranking key (reference task_handler.py:254-263).
    With a custom ``scoring``, the holdout score is reported under the scorer
    name instead of the default accuracy/r2_score keys."""
    metrics: Dict[str, Any] = {}
    score = float(out["score"][j, 0])
    if scoring is not None:
        metrics[scoring] = score
        metrics["scoring"] = scoring
    elif task == "classification":
        metrics["accuracy"] = score
    elif task == "transform":
        metrics["score"] = score
    else:
        metrics["r2_score"] = score
    if task == "regression" and "mse" in out:
        metrics["mse"] = float(out["mse"][j, 0])
    if plan.n_folds >= 2:
        cv = out["score"][j, 1:]
        metrics["cv_scores"] = [float(v) for v in cv]
        metrics["mean_cv_score"] = float(np.mean(cv))
    else:
        metrics["mean_cv_score"] = score
    # a diverged trial (NaN/inf score from a pathological hyper combo) must
    # rank last, not poison the sort — Python sorted() with NaN is undefined
    if not np.isfinite(metrics["mean_cv_score"]):
        metrics["mean_cv_score"] = float("-inf")
        metrics["diverged"] = True
    channels = {
        k[len("curve_"):]: out[k][j]
        for k in out
        if k.startswith("curve_") and k not in ("curve_stride", "curve_steps")
    }
    if channels:
        from ..obs.curves import build_curve_record

        # stride/steps ride as per-(trial, split) leaves purely so they
        # share the score transport; they are bucket-constant
        stride = int(np.asarray(out["curve_stride"])[j].flat[0])
        steps = int(np.asarray(out["curve_steps"])[j].flat[0])
        metrics["curve"] = build_curve_record(
            channels, stride, steps, tail=np.asarray(out["score"][j]).reshape(-1)
        )
    return metrics

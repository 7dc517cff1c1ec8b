"""Trial executor: runs subtask batches on the local device mesh.

The TPU-native replacement for the reference worker process
(``aws-prod/worker/worker.py:156-363``): where a reference worker consumes
one Kafka message, re-reads the CSV, and runs one sklearn fit on CPU, an
executor here receives a *list* of subtasks, groups them by model family,
and dispatches them to the vmapped/sharded trial engine
(parallel/trial_map.py) — all trials of a batch fit in parallel across the
mesh. Per-subtask results and metrics messages keep the reference's wire
schema (``worker.py:233-254``) so the feedback consumers (store, placement
engine's runtime predictor) are drop-in.
"""

from __future__ import annotations

import contextlib
import threading
import time
from typing import Any, Callable, Dict, List, Optional

from ..data.datasets import DatasetCache
from ..models.registry import get_kernel
from ..obs import (
    child_span,
    counter_inc,
    gauge_set,
    obs_enabled,
    observe,
    process_token,
    record_batch_device_seconds,
    span,
)
from ..data.stage_cache import dataset_fingerprint
from ..ops.folds import SPLIT_PLAN_CACHE
from ..parallel.trial_map import fit_single, run_trials
from ..utils.config import get_config
from ..utils.flops import mfu as _mfu
from ..utils.logging import get_logger

logger = get_logger("tpuml.executor")

ResultCallback = Callable[[str, str, Optional[Dict[str, Any]]], None]
MetricsCallback = Callable[[Dict[str, Any]], None]


def record_hbm_gauges() -> None:
    """Refresh ``tpuml_device_hbm_bytes{kind=used|peak|limit}`` from the
    local device's memory_stats. Backends without stats (CPU) write
    nothing — the family stays at its registered zero. Called after every
    executed batch and at /metrics/prom scrape time."""
    if not obs_enabled():
        return
    from ..utils.backend import device_memory_stats

    stats = device_memory_stats()
    for kind, key in (
        ("used", "bytes_in_use"),
        ("peak", "peak_bytes_in_use"),
        ("limit", "bytes_limit"),
    ):
        v = stats.get(key)
        if v is not None:
            gauge_set("tpuml_device_hbm_bytes", float(v), kind=kind)


class ResourceSampler:
    """Background CPU/mem sampling at a fixed cadence DURING a fit.

    The reference samples psutil every 0.5 s in a thread while the sklearn
    fit runs and reports the averages (worker.py:201-221, 240-241); those
    averages are two of the runtime predictor's 7 features, so a single
    instantaneous snapshot (the round-2 form) fed it near-noise. Also
    tracks device-memory stats (peak bytes in use across samples) — the
    accelerator-side resource signal psutil can't see.
    """

    def __init__(self, interval_s: float = 0.5):
        self.interval_s = interval_s
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None
        self._cpu: List[float] = []
        self._mem: List[float] = []
        self._dev_peak_mb: Optional[float] = None

    def _sample_device(self) -> None:
        # max over CURRENT bytes_in_use samples: this fit's observed peak.
        # (peak_bytes_in_use is monotonic over the backend's lifetime — it
        # would report the largest batch ever, not this one)
        from ..utils.backend import device_memory_stats

        used = device_memory_stats().get("bytes_in_use")
        if used is not None:
            mb = used / 1e6
            if self._dev_peak_mb is None or mb > self._dev_peak_mb:
                self._dev_peak_mb = mb

    def _loop(self) -> None:
        try:
            import psutil
        except ImportError:
            return
        psutil.cpu_percent(interval=None)  # prime the delta-based counter
        while not self._stop.wait(self.interval_s):
            self._cpu.append(psutil.cpu_percent(interval=None))
            self._mem.append(psutil.virtual_memory().percent)
            self._sample_device()

    def __enter__(self) -> "ResourceSampler":
        self._thread = threading.Thread(target=self._loop, daemon=True)
        self._thread.start()
        return self

    def __exit__(self, *exc) -> None:
        self._stop.set()
        if self._thread is not None:
            self._thread.join(timeout=self.interval_s + 1)
        self._sample_device()  # at least one device reading even on fast fits

    def averages(self) -> Dict[str, Optional[float]]:
        """Averaged samples; falls back to one instantaneous reading when
        the fit finished inside the first sampling interval."""
        cpu = mem = None
        if self._cpu:
            cpu = float(sum(self._cpu) / len(self._cpu))
            mem = float(sum(self._mem) / len(self._mem))
        else:
            try:
                import psutil

                cpu = psutil.cpu_percent(interval=None)
                mem = psutil.virtual_memory().percent
            except ImportError:
                pass
        return {
            "cpu_percent_avg": cpu,
            "mem_percent_avg": mem,
            "device_peak_mem_mb": self._dev_peak_mb,
        }


class LocalExecutor:
    """Executes trial batches on the local mesh. ``executor_id`` plays the
    role of the reference's worker_id (assigned at /subscribe,
    scheduler_service.py:157-165)."""

    def __init__(
        self,
        executor_id: str = "exec-0",
        *,
        mesh=None,
        cache: Optional[DatasetCache] = None,
        max_trials_per_batch: Optional[int] = None,
        fault_injector: Optional["FaultInjector"] = None,
    ):
        from ..utils.jax_setup import setup_jax

        setup_jax()
        cfg = get_config()
        self.executor_id = executor_id
        self.mesh = mesh
        self.cache = cache or DatasetCache()
        self.max_trials_per_batch = max_trials_per_batch or cfg.execution.max_trials_per_batch
        self.trial_axis = cfg.execution.trial_axis
        self.fault_injector = fault_injector
        #: live run_subtasks calls — the prewarm worker's yield signal
        self._inflight = 0
        self._inflight_lock = threading.Lock()
        #: cooperative-cancel set (docs/SEARCH.md): subtask_id -> highest
        #: cancelled attempt. Fed by the coordinator (poll response
        #: ``cancel`` list / in-process push); consumed at the next batch
        #: boundary — the matching trials are dropped from the batch and
        #: posted as terminal ``pruned`` results instead of running
        self._cancel_lock = threading.Lock()
        self._cancelled: Dict[str, int] = {}

    @property
    def busy(self) -> bool:
        """True while at least one subtask batch is executing. The
        background prewarm worker (runtime/prewarm.py) polls this and
        yields the device to real placements."""
        return self._inflight > 0

    def cancel(self, items) -> None:
        """Mark attempts cancelled (the cooperative-cancel contract,
        docs/SEARCH.md). ``items``: dicts with ``subtask_id`` (+ optional
        ``attempt``). Matching trials still queued or batched stop at the
        next batch boundary and post a terminal ``pruned`` result; a
        trial already inside a fused device dispatch finishes that
        dispatch (the rung) — cancellation is between batches, never a
        mid-kernel abort."""
        with self._cancel_lock:
            for item in items or []:
                stid = item.get("subtask_id") if isinstance(item, dict) else item
                if not stid:
                    continue
                attempt = (
                    int(item.get("attempt") or 0)
                    if isinstance(item, dict)
                    else 0
                )
                self._cancelled[stid] = max(
                    self._cancelled.get(stid, 0), attempt
                )
            # bound the set: entries for subtasks this executor never sees
            # (the cancel list is fleet-broadcast) must not accumulate for
            # the process lifetime — active cancels re-arrive on every
            # poll, so evicting the oldest is safe
            while len(self._cancelled) > 4096:
                self._cancelled.pop(next(iter(self._cancelled)))

    def _take_cancelled(self, subtasks, idxs):
        """Split a group into (live, cancelled) index lists; cancelled
        entries are consumed from the set (a later duplicate delivery of
        the same subtask re-arrives via the next poll's cancel list). A
        task stamped with a HIGHER attempt than the cancel is NOT
        cancelled — a legitimately re-issued attempt (post-restart
        re-dispatch) must survive a stale entry."""
        with self._cancel_lock:
            if not self._cancelled:
                return idxs, []
            live, cancelled = [], []
            for gi in idxs:
                st = subtasks[gi]
                stid = st["subtask_id"]
                marked = self._cancelled.get(stid)
                if marked is not None and int(st.get("attempt") or 0) <= marked:
                    cancelled.append(gi)
                    self._cancelled.pop(stid, None)
                else:
                    live.append(gi)
        return live, cancelled

    def _post_pruned(self, st, results, gi, on_result, on_metrics) -> None:
        """Terminal ``pruned`` result for a cancelled attempt: the trial
        never (re-)runs. The paired metrics message carries no timing and
        ``cancelled: true`` so the scheduler releases the worker's books
        WITHOUT feeding the runtime predictor or its calibration windows
        (runtime/scheduler.on_metrics)."""
        result = {
            "subtask_id": st["subtask_id"],
            "job_id": st.get("job_id"),
            "model_type": st.get("model_type"),
            "parameters": st.get("parameters"),
            "status": "pruned",
            "pruned": True,
            "prune_reason": "cancelled",
            "attempt": int(st.get("attempt") or 0),
        }
        if st.get("asha"):
            result["asha"] = dict(st["asha"])
        results[gi] = result
        counter_inc("tpuml_subtasks_pruned_total")
        logger.info(
            "Cancelled subtask %s pruned at the batch boundary",
            st["subtask_id"],
        )
        if on_result:
            on_result(st["subtask_id"], "pruned", result)
        if on_metrics:
            on_metrics({
                "worker_id": self.executor_id,
                "subtask_id": st["subtask_id"],
                "status": "PRUNED",
                "cancelled": True,
                "algo": st.get("model_type"),
                "obs_pid": process_token(),
            })

    def run_subtasks(
        self,
        subtasks: List[Dict[str, Any]],
        *,
        on_result: Optional[ResultCallback] = None,
        on_metrics: Optional[MetricsCallback] = None,
    ) -> List[Dict[str, Any]]:
        """Run subtasks grouped by (dataset, model_type); returns results in
        input order. Callbacks fire per subtask as batches complete."""
        with self._inflight_lock:
            self._inflight += 1
        try:
            return self._run_subtasks(
                subtasks, on_result=on_result, on_metrics=on_metrics
            )
        finally:
            with self._inflight_lock:
                self._inflight -= 1

    def _run_subtasks(
        self,
        subtasks: List[Dict[str, Any]],
        *,
        on_result: Optional[ResultCallback] = None,
        on_metrics: Optional[MetricsCallback] = None,
    ) -> List[Dict[str, Any]]:
        results: List[Optional[Dict[str, Any]]] = [None] * len(subtasks)
        groups: Dict[Any, List[int]] = {}
        for i, st in enumerate(subtasks):
            groups.setdefault((st["dataset_id"], st["model_type"]), []).append(i)

        for (dataset_id, model_type), idxs in groups.items():
            # cooperative cancel, checked at every batch boundary: trials
            # the coordinator pruned mid-flight are dropped here and
            # posted as terminal ``pruned`` results instead of burning
            # the rest of their budget (docs/SEARCH.md)
            idxs, cancelled = self._take_cancelled(subtasks, idxs)
            for gi in cancelled:
                self._post_pruned(
                    subtasks[gi], results, gi, on_result, on_metrics
                )
            if not idxs:
                continue
            received_at = time.time()
            # the batch rides the submitting job's trace (trace_id stamped
            # into each subtask spec by the coordinator); direct callers
            # (benchmarks) carry none — then no span is opened at all
            tid = next(
                (
                    subtasks[i].get("trace_id")
                    for i in idxs
                    if subtasks[i].get("trace_id")
                ),
                None,
            )
            batch_cm = (
                span(
                    "executor.batch",
                    trace_id=tid,
                    worker=self.executor_id,
                    model_type=model_type,
                    dataset_id=dataset_id,
                    n_subtasks=len(idxs),
                )
                if tid
                else contextlib.nullcontext(None)
            )
            try:
                with batch_cm as batch_sp:
                    self._run_group(
                        subtasks, idxs, dataset_id, model_type, received_at,
                        results, on_result, on_metrics, batch_sp,
                    )
            except Exception as e:  # noqa: BLE001 — task-level failure semantics
                if _is_device_fatal(e):
                    # a poisoned backend fails every later dispatch in this
                    # process: do NOT publish per-task failures (the owner
                    # keeps the tasks queued, so the dead-worker sweep can
                    # requeue them onto live executors) — escalate instead
                    raise DeviceLostError(
                        f"device backend lost on {self.executor_id}: {e}"
                    ) from e
                logger.exception("Batch failed for %s/%s", dataset_id, model_type)
                for gi in idxs:
                    st = subtasks[gi]
                    result = {
                        "subtask_id": st["subtask_id"],
                        "job_id": st.get("job_id"),
                        "model_type": model_type,
                        "parameters": st["parameters"],
                        "status": "failed",
                        "error": str(e),
                        # attempt-id stamp: the coordinator's retry/dedup
                        # path must know WHICH attempt failed — a stale
                        # attempt's failure must not consume retry budget
                        "attempt": int(st.get("attempt") or 0),
                    }
                    if st.get("speculative"):
                        result["speculative"] = True
                    results[gi] = result
                    counter_inc("tpuml_subtasks_failed_total")
                    if on_result:
                        on_result(st["subtask_id"], "failed", result)
        return results  # type: ignore[return-value]

    def _run_group(
        self, subtasks, idxs, dataset_id, model_type, received_at,
        results, on_result, on_metrics, batch_sp,
    ) -> None:
        """Execute one (dataset, model_type) group on the trial engine and
        emit per-subtask results/metrics. ``batch_sp`` is the enclosing
        ``executor.batch`` span handle (or None); every step below — data
        load, split plan, the engine's stage / compile / dispatch / fetch,
        result emission — records a real child span of it
        (docs/OBSERVABILITY.md "Reading GET /trace")."""
        if self.fault_injector is not None:
            self.fault_injector.before_batch(self.executor_id, model_type)
        kernel = get_kernel(model_type)
        with child_span("executor.load_data", dataset_id=dataset_id):
            data = self.cache.get(dataset_id, kernel.task)
        tp = subtasks[idxs[0]].get("train_params", {}) or {}
        scoring = _normalize_scoring(
            tp.get("scoring"), kernel.task, data.n_classes, kernel
        )
        plan = _split_plan(
            data,
            data.y if kernel.task == "regression" else _np(data.y),
            task=kernel.task,
            n_folds=_coerce_cv(tp.get("cv")),
            test_size=float(tp.get("test_size", get_config().execution.default_test_size)),
            random_state=tp.get("random_state", 42),
        )
        started_at = time.time()
        with ResourceSampler() as sampler:
            if callable(scoring) and not isinstance(scoring, str):
                # host-side fallback: device fits per fold, sklearn
                # export, user scorer on host (trial_map docstring)
                from ..parallel.trial_map import (
                    TrialRunResult,
                    run_trials_callable,
                )

                t0 = time.time()
                metrics_list = run_trials_callable(
                    kernel, data, plan,
                    [subtasks[i]["parameters"] for i in idxs],
                    scoring,
                )
                run = TrialRunResult(
                    trial_metrics=metrics_list,
                    compile_time_s=0.0,
                    run_time_s=time.time() - t0,
                    n_dispatches=len(idxs) * plan.n_splits,
                )
            else:
                run = run_trials(
                    kernel,
                    data,
                    plan,
                    [subtasks[i]["parameters"] for i in idxs],
                    mesh=self.mesh,
                    trial_axis=self.trial_axis,
                    max_trials_per_batch=self.max_trials_per_batch,
                    scoring=scoring,
                )
        finished_at = time.time()
        if self.fault_injector is not None and self.fault_injector.drop_batch_results(
            self.executor_id
        ):
            # silent-worker chaos: the batch RAN (compute burned) but no
            # result/metrics message ever leaves this executor — the lease
            # layer must recover the subtasks (docs/ROBUSTNESS.md)
            logger.warning(
                "FaultInjector: dropping results of a %d-trial %s batch on %s",
                len(idxs), model_type, self.executor_id,
            )
            return
        observe("tpuml_executor_dispatch_seconds", run.run_time_s)
        # device-time attribution (obs/devprof.py): the engine's phase
        # totals, accumulated into the
        # tpuml_executor_device_seconds_total{phase=} counter
        record_batch_device_seconds(
            run.compile_time_s, run.stage_time_s,
            run.run_time_s, run.fetch_time_s,
        )
        resources = sampler.averages()
        batch_cost = self._record_batch_cost(
            run, model_type, dataset_id, len(idxs), resources
        )
        self._record_batch_summary(batch_sp, run, batch_cost)
        per_trial_time = run.run_time_s / max(len(idxs), 1)
        # winner-by-ICI-collective: run_trials' on-device argmax over
        # the mesh-sharded scores (multi-device only). The marked
        # result lets the coordinator select the winner from the
        # device reduction instead of a host sort.
        device_best_pos = (
            run.device_best[0] if run.device_best is not None else None
        )
        # the span's three shares (no span a subtask): building the result
        # dicts, the result callback, the metrics message and its callback
        build_s = on_result_s = on_metrics_s = 0.0
        with child_span("executor.emit", n_subtasks=len(idxs)) as emit_sp:
            for j, gi in enumerate(idxs):
                t_build = time.perf_counter()
                st = subtasks[gi]
                result = {
                    "subtask_id": st["subtask_id"],
                    "job_id": st.get("job_id"),
                    "model_type": model_type,
                    "parameters": st["parameters"],
                    "search_params": st.get("search_params"),
                    "training_time": per_trial_time,
                    "status": "completed",
                    # attempt-id stamp for result-ingest dedup under retries
                    # and speculative duplicates (docs/ROBUSTNESS.md)
                    "attempt": int(st.get("attempt") or 0),
                    **run.trial_metrics[j],
                }
                if st.get("speculative"):
                    result["speculative"] = True
                if st.get("asha"):
                    # rung stamp echoed so the coordinator's rung controller
                    # can attribute the score without a spec lookup race
                    result["asha"] = dict(st["asha"])
                if device_best_pos == j:
                    result["device_argmax"] = True
                if j == 0 and batch_cost is not None:
                    # the batch's cost record rides exactly ONE result (the
                    # primary) into the job store, where GET /cost/<job_id>
                    # aggregates it — stamping every result would overcount
                    result["batch_cost"] = batch_cost
                results[gi] = result
                counter_inc("tpuml_subtasks_completed_total")
                t_result = time.perf_counter()
                if on_result:
                    on_result(st["subtask_id"], "completed", result)
                t_metrics = time.perf_counter()
                if on_metrics:
                    on_metrics(
                        self._metrics_message(
                            st, received_at, started_at, finished_at,
                            model_type, resources, run=run,
                            batch_size=len(idxs), primary=(j == 0),
                            batch_cost=batch_cost,
                            score=run.trial_metrics[j].get("mean_cv_score"),
                            curve=run.trial_metrics[j].get("curve"),
                        )
                    )
                build_s += t_result - t_build
                on_result_s += t_metrics - t_result
                on_metrics_s += time.perf_counter() - t_metrics
            emit_sp.attrs.update(build_s=build_s, on_result_s=on_result_s,
                                 on_metrics_s=on_metrics_s)

    def _record_batch_cost(
        self, run, model_type: str, dataset_id: str, batch_size: int,
        resources: Optional[Dict[str, Any]] = None,
    ) -> Optional[Dict[str, Any]]:
        """Device cost accounting for one executed batch: feed the
        ``tpuml_executor_flops_total`` / ``_bytes_total`` / ``_mfu`` /
        ``tpuml_device_hbm_bytes`` families and build the per-batch cost
        record that rides the primary result into the job store (the
        ``GET /cost/<job_id>`` input). Returns None when CS230_OBS=0 —
        the valve disables cost accounting end to end."""
        if not obs_enabled():
            return None
        n_devices = 1
        if self.mesh is not None:
            import numpy as np

            n_devices = int(np.prod(list(self.mesh.shape.values())))
        flops = run.model_flops if run.model_flops is not None else run.xla_flops
        # MFU only from a COMPLETE model-FLOP sum (a partially priced run
        # must report null, not an understated figure — flops_coverage
        # contract, trial_map), over the peak of EVERY participating
        # device (whole-mesh FLOPs over one chip's peak would read Nx)
        mfu_val = (
            _mfu(run.model_flops, run.run_time_s, n_devices=n_devices)
            if run.flops_coverage == 1.0
            else None
        )
        if flops is not None:
            counter_inc("tpuml_executor_flops_total", flops, model=model_type)
        if run.bytes_accessed is not None:
            counter_inc(
                "tpuml_executor_bytes_total", run.bytes_accessed,
                model=model_type,
            )
        if mfu_val is not None:
            gauge_set("tpuml_executor_mfu", mfu_val, model=model_type)
        record_hbm_gauges()
        # per-batch HBM: the sampler's max over bytes_in_use DURING this
        # fit (memory_stats' peak_bytes_in_use is monotonic over the
        # process lifetime — it would pin every later batch to the
        # largest batch ever; run.hbm_peak_bytes keeps that lifetime
        # high-water as the fallback when the sampler saw nothing)
        dev_peak_mb = (resources or {}).get("device_peak_mem_mb")
        hbm_peak = (
            int(dev_peak_mb * 1e6)
            if dev_peak_mb is not None
            else run.hbm_peak_bytes
        )
        return {
            "model_type": model_type,
            "dataset_id": dataset_id,
            "n_subtasks": batch_size,
            "n_devices": n_devices,
            "n_result_devices": run.n_result_devices,
            "device_seconds": run.run_time_s,
            "model_flops": run.model_flops,
            "xla_flops": run.xla_flops,
            "bytes_accessed": run.bytes_accessed,
            "flops_coverage": run.flops_coverage,
            "mfu": mfu_val,
            "hbm_peak_bytes": hbm_peak,
        }

    @staticmethod
    def _record_batch_summary(
        batch_sp, run, batch_cost: Optional[Dict[str, Any]] = None,
    ) -> None:
        """Summary attributes of the ``executor.batch`` span: the engine's
        transfer and timer totals, and the batch's cost record so trace
        timelines price themselves. (The phases themselves are the span's
        real children, recorded where they happen.)"""
        if batch_sp is None or getattr(batch_sp, "span_id", None) is None:
            return
        batch_sp.attrs.update(
            n_dispatches=run.n_dispatches,
            n_host_fetches=run.n_host_fetches,
            result_bytes=run.result_bytes,
            compile_time_s=round(run.compile_time_s, 6),
            run_time_s=round(run.run_time_s, 6),
        )
        if batch_cost is not None:
            batch_sp.attrs.update(
                {
                    k: batch_cost[k]
                    for k in ("model_flops", "xla_flops", "bytes_accessed",
                              "mfu", "hbm_peak_bytes")
                    if batch_cost.get(k) is not None
                }
            )

    def prewarm_hint(
        self, hint: Dict[str, Any], mode: str = "construct"
    ) -> Dict[str, Any]:
        """Warm one coordinator prewarm hint: resolve the dataset (which
        fetches + parses it on a cold agent and stages it into the
        multi-tenant device cache), then construct every bucket executable
        the hinted job shape would use (``run_trials(warm_only=True)`` —
        AOT blob deserialize or trace, the inline cold cost this kills).
        ``mode="execute"`` additionally dispatches the warmed bucket once
        with the hinted parameters and discards the result, so the first
        real trial also finds a finished XLA compile.

        Hint schema (Coordinator.prewarm_hints): ``{model_type,
        dataset_id, parameters, n_trials, train_params}`` — ``n_trials``
        matters because the trial-chunk geometry is part of every
        executable cache key; warming the wrong chunk warms nothing.
        It is capped at THIS executor's ``max_trials_per_batch``: a
        scheduled worker never sees more trials per batch than its
        long-poll cap (agent._poll_tasks passes exactly this value), so
        the full-batch geometry — what a saturated queue delivers cold —
        is the shape worth warming, and a bigger hinted job would warm a
        chunk size no delivered batch ever has. String ``scoring``
        survives into the warm (it is part of the executable key);
        callable scoring cannot arrive here (REST-serialized hints)."""
        kernel = get_kernel(hint["model_type"])
        data = self.cache.get(hint["dataset_id"], kernel.task)
        tp = dict(hint.get("train_params") or {})
        scoring = tp.get("scoring")
        scoring = _normalize_scoring(
            scoring if isinstance(scoring, str) else None,
            kernel.task, data.n_classes, kernel,
        )
        plan = _split_plan(
            data,
            data.y if kernel.task == "regression" else _np(data.y),
            task=kernel.task,
            n_folds=_coerce_cv(tp.get("cv")),
            test_size=float(
                tp.get("test_size", get_config().execution.default_test_size)
            ),
            random_state=tp.get("random_state", 42),
        )
        n_trials = max(
            1, min(int(hint.get("n_trials") or 1), self.max_trials_per_batch)
        )
        params = dict(hint.get("parameters") or {})
        run = run_trials(
            kernel,
            data,
            plan,
            [params] * n_trials,
            mesh=self.mesh,
            trial_axis=self.trial_axis,
            max_trials_per_batch=self.max_trials_per_batch,
            scoring=scoring,
            warm_only=(mode != "execute"),
        )
        return {
            "model_type": hint["model_type"],
            "dataset_id": hint["dataset_id"],
            "n_trials": n_trials,
            "mode": mode,
            "compile_s": round(run.compile_time_s, 6),
            "stage_s": round(run.stage_time_s, 6),
            "run_s": round(run.run_time_s, 6),
            "n_dispatches": run.n_dispatches,
        }

    def fit_artifact(self, subtask: Dict[str, Any]) -> Dict[str, Any]:
        """Refit one configuration on the holdout-train split and return a
        serializable artifact dict (see runtime/artifacts.py)."""
        kernel = get_kernel(subtask["model_type"])
        data = self.cache.get(subtask["dataset_id"], kernel.task)
        tp = subtask.get("train_params", {}) or {}
        plan = _split_plan(
            data,
            _np(data.y),
            task=kernel.task,
            n_folds=0,
            test_size=float(tp.get("test_size", get_config().execution.default_test_size)),
            random_state=tp.get("random_state", 42),
        )
        fitted, static = fit_single(kernel, data, plan, subtask["parameters"])
        return {
            "model_type": subtask["model_type"],
            "parameters": subtask["parameters"],
            "static": {k: v for k, v in static.items()},
            "fitted_params": fitted,
        }

    def _metrics_message(self, st, received_at, started_at, finished_at,
                         algo, resources=None, run=None, batch_size=1,
                         primary=False, batch_cost=None, score=None,
                         curve=None):
        """Reference metrics schema (worker.py:233-243): CPU/mem averaged
        over the fit by the 0.5 s-cadence ResourceSampler (the predictor's
        feature inputs), plus device peak-memory — the accelerator signal
        the reference had no analog for — and the batch's host<->device
        transfer accounting (dispatches / blocking fetches / result bytes),
        the observability for the packed single-fetch transport."""
        resources = resources or {}
        msg = {
            "worker_id": self.executor_id,
            "subtask_id": st["subtask_id"],
            "status": "DONE",
            "received_at": received_at,
            "started_at": started_at,
            "finished_at": finished_at,
            "cpu_percent_avg": resources.get("cpu_percent_avg"),
            "mem_percent_avg": resources.get("mem_percent_avg"),
            "device_peak_mem_mb": resources.get("device_peak_mem_mb"),
            "algo": algo,
            # the process (host:pid) that ALREADY observed this batch's
            # phase/cost metrics into its local registry — the
            # coordinator's ingest (cluster.push_metrics) skips
            # re-observing when the message originated in its own process
            # (the in-process-agent test topology would otherwise
            # double-observe; docs/OBSERVABILITY.md)
            "obs_pid": process_token(),
        }
        a = st.get("asha")
        if a:
            # rung boundary (docs/SEARCH.md): the intermediate validation
            # score + rung/resource ride the metrics message so the
            # coordinator's on_metrics can feed the rung controller before
            # the result lands, and the scheduler's predictor feed can
            # normalize the rung's wall time by its resource fraction
            msg["rung"] = int(a.get("rung", 0))
            msg["resource"] = int(a.get("resource", 0))
            msg["intermediate_score"] = score
            big = a.get("max_resource")
            if isinstance(big, (int, float)) and big > 0:
                msg["asha_resource_fraction"] = min(
                    max(float(a.get("resource", 0)) / float(big), 0.01), 1.0
                )
        if run is not None:
            # batch_-prefixed: these are totals for the WHOLE run_trials
            # batch this subtask rode in (every subtask of the batch
            # carries the same numbers — summing them per job would
            # overcount by the batch size; divide by batch_n_subtasks or
            # dedupe on them instead). ``batch_primary`` marks exactly one
            # message per batch — the dedup handle consumers (e.g. the
            # coordinator's remote-metrics ingest, cluster.push_metrics)
            # key batch-level observations on.
            msg["batch_n_subtasks"] = batch_size
            msg["batch_n_dispatches"] = run.n_dispatches
            msg["batch_device_fetches"] = run.n_host_fetches
            msg["batch_result_bytes"] = run.result_bytes
            msg["batch_primary"] = bool(primary)
            msg["batch_compile_s"] = run.compile_time_s
            msg["batch_stage_s"] = run.stage_time_s
            msg["batch_dispatch_s"] = run.run_time_s
            msg["batch_fetch_s"] = run.fetch_time_s
        if batch_cost is not None:
            # remote agents have no exposition endpoint: the batch's cost
            # figures ride the metrics message so the coordinator's ingest
            # can count them fleet-wide (same dedup contract as the phase
            # timers: batch_primary + obs_pid)
            msg["batch_model_flops"] = batch_cost.get("model_flops")
            msg["batch_xla_flops"] = batch_cost.get("xla_flops")
            msg["batch_bytes_accessed"] = batch_cost.get("bytes_accessed")
            msg["batch_mfu"] = batch_cost.get("mfu")
            msg["batch_hbm_peak_bytes"] = batch_cost.get("hbm_peak_bytes")
        if curve is not None:
            # trial telemetry plane: the per-trial convergence trace rides
            # the metrics message so the coordinator can ingest (and
            # watchdog) it live, before the result settles. Per-SUBTASK —
            # no batch dedup needed; the curve store dedups re-delivery
            # through the result transport on (subtask, rung, attempt).
            msg["curve"] = curve
            msg["attempt"] = int(st.get("attempt") or 0)
        return msg


class DeviceLostError(RuntimeError):
    """The executor's accelerator backend is poisoned (e.g. an UNAVAILABLE
    RPC fault on a TPU chip): every later dispatch in this process will
    fail, so the owning worker must leave the pool instead of emitting
    per-task failures. Containment per runtime mode:

    - remote agent (runtime/agent.py): exits the process — the scheduler's
      dead-worker sweep requeues its tasks, and a supervisor/compose
      restart policy brings a fresh process (and backend) back.
    - in-process worker (runtime/cluster.py): kills itself without
      unsubscribe, so its tasks requeue onto surviving executors.
    """


#: substrings marking an unrecoverable backend fault (vs a per-batch error
#: like RESOURCE_EXHAUSTED/INVALID_ARGUMENT, which stays task-level)
_FATAL_MARKERS = (
    "UNAVAILABLE",
    "DATA_LOSS",
    "device is in an invalid state",
    "backend has been poisoned",
    "lost connection to the device",
)


def _is_multiprocess() -> bool:
    """True only inside a live multi-process (slice) runtime — the context
    where a broad network-error marker really does mean the collective is
    dead for every later dispatch."""
    try:
        import jax

        return jax.process_count() > 1
    except Exception:  # noqa: BLE001 — no backend yet: not a slice
        return False


def _is_device_fatal(e: BaseException) -> bool:
    msg = f"{type(e).__name__}: {e}"
    if isinstance(e, DeviceLostError):
        return True
    # a backend that never came up (e.g. two processes contending for one
    # chip) fails every batch this process will ever run — process-fatal
    if "Unable to initialize backend" in msg:
        return True
    # cross-process collective failure (a slice sibling died mid-program:
    # gloo on CPU fleets, ICI/barrier errors on TPU slices): every later
    # sharded dispatch on this rank fails too, and publishing per-task
    # FAILED results would make the sibling's crash terminal for the job —
    # escalate so the tasks stay queued for the dead-worker requeue
    # (tests/test_chaos_spmd.py pins this path). The broad network markers
    # ("heartbeat", "Connection reset by peer") only escalate under a
    # multi-process slice: on a single-process executor a transient
    # network hiccup on a remote device whose message happens to contain
    # them fails ONE batch, not the whole agent. The
    # collective-specific prefixes stay unconditional — a gloo/coordination
    # error cannot occur outside a collective runtime.
    if "JaxRuntimeError" in msg or "XlaRuntimeError" in msg:
        if any(m in msg for m in ("Gloo ", "coordination service")):
            return True
        if any(
            m in msg
            for m in (
                "Connection reset by peer",
                "Connection closed by peer",
                "heartbeat",
            )
        ) and _is_multiprocess():
            return True
    if "XlaRuntimeError" not in msg and "DeviceLost" not in msg:
        return False
    return any(m in msg for m in _FATAL_MARKERS)


class FaultInjector:
    """Test/chaos hooks (SURVEY.md §5.3: 'add real fault injection hooks'):
    delay a host's batches, fail N batches (task-level), drop the results
    of N batches silently (``drop_results`` — the compute runs but no
    result or metrics message leaves the executor: the silent/hung-worker
    scenario the lease layer recovers), or poison the device backend
    (process-level) — immediately or after N healthy batches
    (``device_lost_after``, the kill-mid-job chaos scenario).
    ``only_worker=`` scopes every mode to one executor id, so a shared
    injector can target a single worker deterministically."""

    def __init__(self, delay_s: float = 0.0, fail_batches: int = 0,
                 device_lost: bool = False,
                 device_lost_after: Optional[int] = None,
                 drop_results: int = 0,
                 only_worker: Optional[str] = None):
        self.delay_s = delay_s
        self.fail_batches = fail_batches
        self.device_lost = device_lost
        self.device_lost_after = device_lost_after
        self.drop_results = drop_results
        self.only_worker = only_worker
        self._batches_seen = 0

    def _targets(self, executor_id: str) -> bool:
        return self.only_worker is None or executor_id == self.only_worker

    def before_batch(self, executor_id: str, model_type: str) -> None:
        if not self._targets(executor_id):
            return
        if self.delay_s > 0:
            time.sleep(self.delay_s)
        if self.device_lost or (
            self.device_lost_after is not None
            and self._batches_seen >= self.device_lost_after
        ):
            raise DeviceLostError(
                f"fault injection: simulated backend loss on {executor_id}"
            )
        if self.fail_batches > 0:
            self.fail_batches -= 1
            raise RuntimeError(f"fault injection: simulated batch failure on {executor_id}")
        self._batches_seen += 1  # only batches that passed injection count

    def drop_batch_results(self, executor_id: str) -> bool:
        """True when this batch's results/metrics must be silently dropped
        (consumes one ``drop_results`` budget unit). Called by the executor
        after the batch ran, before any emission."""
        if not self._targets(executor_id):
            return False
        if self.drop_results > 0:
            self.drop_results -= 1
            return True
        return False


def _np(y):
    import numpy as np

    return np.asarray(y)


def _split_plan(data, y, **kw):
    """The batch's fold plan under an ``executor.split_plan`` span, from
    the process-wide memo (``ops/folds.py::SplitPlanCache``): the splitters
    walk every row on the host once per (dataset, split arguments), not
    once per batch. The key's first half is the fingerprint the stage
    cache prefixes to the same masks' device copy, memoised on ``data``."""
    with child_span("executor.split_plan", n_rows=len(y)) as sp:
        # a dataset's first search hashes every byte of it here (memoised
        # on ``data``): ``fingerprint_s`` says how much of the span that was
        t0 = time.perf_counter()
        fingerprint = dataset_fingerprint(data)
        sp.attrs["fingerprint_s"] = time.perf_counter() - t0
        plan, outcome = SPLIT_PLAN_CACHE.get_or_build(fingerprint, y, **kw)
        sp.attrs.update(
            n_splits=plan.n_splits, signature=str(plan.signature),
            outcome=outcome,
        )
    counter_inc("tpuml_split_plan_cache_total", outcome=outcome)
    return plan


def _normalize_scoring(scoring, task: str, n_classes: int = 0, kernel=None):
    """Validate a job's ``scoring`` and collapse the task defaults to None
    (so default jobs keep their cached executables). The reference worker
    silently dropped custom scoring (worker.py:320-349); here an unsupported
    scorer fails the batch with a clear error instead — including the cases
    sklearn itself rejects (binary-average scorers on multiclass targets)
    and the one it can't know about (margin scorers on kernels with no
    decision margin)."""
    from ..ops.metrics import validate_scoring

    if scoring is None:
        return None
    if task != "transform" and scoring == (
        "accuracy" if task == "classification" else "r2"
    ):
        return None
    validate_scoring(scoring, task, n_classes, kernel)
    return scoring


def _coerce_cv(cv) -> int:
    """Accept the cv forms sklearn search wrappers take: None (default 5),
    an int, or a CV splitter object (use its fold count; fold *assignment*
    still follows our default splitters)."""
    if cv is None:
        return get_config().execution.default_cv_folds
    if isinstance(cv, (int, float)):
        return int(cv)
    if hasattr(cv, "get_n_splits"):
        return int(cv.get_n_splits())
    try:
        return int(cv)
    except (TypeError, ValueError):
        return get_config().execution.default_cv_folds

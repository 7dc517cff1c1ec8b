"""Device cost accounting, per-worker health, trace-correlated logs.

Covers the observability layer of ISSUE 3: cost-analysis capture + MFU
math (None-safe on the CPU backend), the HBM gauge, worker EWMA/straggler
flagging (advisory-only placement), the /cost and /healthz routes on a
live two-worker topology, the metrics-ingest double-observe dedupe, and
the JSON log formatter's trace stamping.
"""

import json
import logging
import os
import time

import pytest

from cs230_distributed_machine_learning_tpu.obs import (
    REGISTRY,
    activate,
    span,
)
from cs230_distributed_machine_learning_tpu.runtime.scheduler import (
    PlacementEngine,
)


# ---------------- trial-engine cost capture ----------------


def _iris_run(params_list, **kw):
    import numpy as np
    from sklearn.datasets import load_iris

    from cs230_distributed_machine_learning_tpu.models.base import TrialData
    from cs230_distributed_machine_learning_tpu.models.registry import get_kernel
    from cs230_distributed_machine_learning_tpu.ops.folds import build_split_plan
    from cs230_distributed_machine_learning_tpu.parallel.trial_map import run_trials

    X, y = load_iris(return_X_y=True)
    Xs = ((X - X.mean(0)) / X.std(0)).astype(np.float32)
    data = TrialData(X=Xs, y=y.astype(np.int32), n_classes=3)
    plan = build_split_plan(y, task="classification", n_folds=3)
    return run_trials(
        get_kernel("LogisticRegression"), data, plan, params_list, **kw
    )


def test_run_trials_captures_cost_and_is_none_safe_on_cpu():
    out = _iris_run([{"C": 0.5}, {"C": 1.0}])
    # analytical model FLOPs: LogReg publishes macs_estimate -> full coverage
    assert out.model_flops is not None and out.model_flops > 0
    assert out.flops_coverage == 1.0
    # XLA cost analysis works on the CPU backend too
    assert out.xla_flops is not None and out.xla_flops > 0
    assert out.bytes_accessed is not None and out.bytes_accessed > 0
    # None-safe values where CPU has no answer: no HBM stats, no peak rate
    assert out.hbm_peak_bytes is None
    from cs230_distributed_machine_learning_tpu.utils.flops import mfu

    assert mfu(out.model_flops, max(out.run_time_s, 1e-6)) is None


def test_cost_accounting_obeys_obs_valve(monkeypatch):
    monkeypatch.setenv("CS230_OBS", "0")
    out = _iris_run([{"C": 1.0}])
    assert out.model_flops is None
    assert out.xla_flops is None
    assert out.bytes_accessed is None
    assert out.flops_coverage is None
    assert out.hbm_peak_bytes is None


def test_executor_stamps_batch_cost_on_primary_result_only():
    from cs230_distributed_machine_learning_tpu.data.datasets import (
        materialize_builtin,
    )
    from cs230_distributed_machine_learning_tpu.runtime.executor import (
        LocalExecutor,
    )
    from cs230_distributed_machine_learning_tpu.runtime.subtasks import (
        create_subtasks,
    )

    materialize_builtin("iris")
    subtasks = create_subtasks(
        "cost-job", "sess", "iris",
        {
            "model_type": "LogisticRegression",
            "search_type": "GridSearchCV",
            "base_estimator_params": {"max_iter": 120},
            "param_grid": {"C": [0.5, 1.0, 2.0]},
        },
        {"test_size": 0.2, "random_state": 0, "cv": 3},
    )
    messages = []
    results = LocalExecutor().run_subtasks(
        subtasks, on_metrics=messages.append
    )
    with_cost = [r for r in results if "batch_cost" in r]
    assert len(with_cost) == 1  # exactly one per (dataset, model) batch
    cost = with_cost[0]["batch_cost"]
    assert cost["model_type"] == "LogisticRegression"
    assert cost["dataset_id"] == "iris"
    assert cost["n_subtasks"] == 3
    assert cost["device_seconds"] >= 0
    assert cost["model_flops"] > 0
    assert cost["mfu"] is None  # CPU backend: no peak rate -> null MFU
    # the same figures ride the primary metrics message for remote ingest
    from cs230_distributed_machine_learning_tpu.obs import process_token

    primaries = [m for m in messages if m.get("batch_primary")]
    assert len(primaries) == 1
    assert primaries[0]["batch_model_flops"] == cost["model_flops"]
    assert primaries[0]["obs_pid"] == process_token()


def test_mfu_populates_when_device_peak_is_known(monkeypatch):
    """On accelerators (device_peak_flops known) MFU must come out a real
    fraction — simulated here by pinning the peak-rate lookup, since the
    tier-1 box is CPU-only."""
    from cs230_distributed_machine_learning_tpu.utils import backend as backend_mod

    monkeypatch.setattr(backend_mod, "device_peak_flops", lambda: 1e12)
    from cs230_distributed_machine_learning_tpu.runtime.executor import (
        LocalExecutor,
    )

    run = _iris_run([{"C": 1.0}])
    cost = LocalExecutor()._record_batch_cost(
        run, "LogisticRegression", "iris", 1
    )
    assert cost["mfu"] is not None
    expected = run.model_flops / max(run.run_time_s, 1e-12) / 1e12
    assert cost["mfu"] == pytest.approx(expected)
    # the executor gauge carries the same value
    assert REGISTRY.gauge("tpuml_executor_mfu").value(
        model="LogisticRegression"
    ) == pytest.approx(expected)


def test_job_cost_mfu_populates_with_known_peak(monkeypatch):
    """GET /cost aggregation: with a peak rate available, job-level MFU is
    model_flops / device_seconds / peak (null stays correct on CPU)."""
    from cs230_distributed_machine_learning_tpu.runtime.coordinator import (
        Coordinator,
    )
    from cs230_distributed_machine_learning_tpu.utils import backend as backend_mod

    coord = Coordinator()
    sid = coord.create_session()
    from cs230_distributed_machine_learning_tpu.runtime.subtasks import (
        create_subtasks,
    )
    from cs230_distributed_machine_learning_tpu.data.datasets import (
        materialize_builtin,
    )

    materialize_builtin("iris")
    subtasks = create_subtasks(
        "jc", sid, "iris",
        {
            "model_type": "LogisticRegression",
            "search_type": "GridSearchCV",
            "base_estimator_params": {"max_iter": 120},
            "param_grid": {"C": [1.0]},
        },
        {"test_size": 0.2, "random_state": 0, "cv": 3},
    )
    coord.store.create_job(sid, "jc", {"dataset_id": "iris",
                                       "model_details": {}}, subtasks)
    results = coord.executor.run_subtasks(subtasks)
    for st, r in zip(subtasks, results):
        coord.store.update_subtask(sid, "jc", st["subtask_id"],
                                   r.get("status", "completed"), r)
    report_cpu = coord.job_cost("jc")
    assert report_cpu["mfu"] is None  # CPU: no peak rate
    monkeypatch.setattr(backend_mod, "device_peak_flops", lambda: 1e12)
    report = coord.job_cost("jc")
    assert report["n_groups"] == 1
    assert report["mfu"] == pytest.approx(
        report["model_flops"] / report["device_seconds"] / 1e12
    )
    assert coord.job_cost("no-such-job") is None


def test_hbm_gauge_silent_on_cpu():
    from cs230_distributed_machine_learning_tpu.runtime.executor import (
        record_hbm_gauges,
    )

    g = REGISTRY.gauge("tpuml_device_hbm_bytes")
    before = g.labelsets()
    record_hbm_gauges()  # CPU memory_stats() is None -> must write nothing
    assert g.labelsets() == before


# ---------------- gauges ----------------


def test_gauge_remove_drops_labeled_cell():
    from cs230_distributed_machine_learning_tpu.obs import MetricsRegistry

    reg = MetricsRegistry()
    g = reg.gauge("w_gauge")
    g.set(1.5, wid="worker-0")
    g.set(2.5, wid="worker-1")
    g.remove(wid="worker-0")
    assert {"wid": "worker-1"} in g.labelsets()
    assert {"wid": "worker-0"} not in g.labelsets()
    assert 'wid="worker-0"' not in "\n".join(g.render())


# ---------------- worker health / stragglers ----------------


def _feed_batches(engine, wid, batch_s, n=3):
    for i in range(n):
        engine.record_outcome(wid, True)
        now = time.time()
        engine.on_metrics(
            {
                "worker_id": wid,
                "subtask_id": f"{wid}-st{i}",
                "started_at": now - batch_s,
                "finished_at": now,
            }
        )


def test_worker_ewma_and_straggler_flagging():
    engine = PlacementEngine(bus=None)
    fast = engine.subscribe()
    slow = engine.subscribe()
    _feed_batches(engine, fast, 0.1)
    _feed_batches(engine, slow, 1.0)  # >3x the peer median -> straggler
    snap = engine.health_snapshot()
    assert snap[fast]["ewma_batch_s"] == pytest.approx(0.1, rel=0.05)
    assert snap[slow]["ewma_batch_s"] == pytest.approx(1.0, rel=0.05)
    assert snap[fast]["straggler"] is False
    assert snap[slow]["straggler"] is True
    assert snap[slow]["failure_ratio"] == 0.0
    assert snap[slow]["heartbeat_age_s"] >= 0
    # gauges carry the wid label for both workers
    g = REGISTRY.gauge("tpuml_worker_ewma_batch_seconds")
    assert g.value(wid=slow) == pytest.approx(1.0, rel=0.05)
    assert REGISTRY.gauge("tpuml_worker_straggler").value(wid=slow) == 1.0
    assert REGISTRY.gauge("tpuml_worker_straggler").value(wid=fast) == 0.0


def test_straggler_penalty_is_advisory_only():
    engine = PlacementEngine(bus=None)
    fast = engine.subscribe()
    slow = engine.subscribe()
    _feed_batches(engine, fast, 0.1)
    _feed_batches(engine, slow, 5.0)
    # both idle: placement prefers the healthy worker via the score penalty
    assert engine.place({"subtask_id": "t1"}) == fast
    # the straggler stays ELIGIBLE — semantics unchanged: with the fast
    # worker removed, tasks still place on the flagged one
    engine.unsubscribe(fast)
    assert engine.place({"subtask_id": "t2"}) == slow


def test_failure_ratio_counts_outcomes():
    engine = PlacementEngine(bus=None)
    wid = engine.subscribe()
    engine.record_outcome(wid, True)
    engine.record_outcome(wid, False)
    engine.record_outcome(wid, False)
    assert engine.health_snapshot()[wid]["failure_ratio"] == pytest.approx(2 / 3)


def test_unsubscribe_drops_worker_gauges():
    engine = PlacementEngine(bus=None)
    a = engine.subscribe()
    b = engine.subscribe()
    _feed_batches(engine, a, 0.2)
    _feed_batches(engine, b, 0.2)
    g = REGISTRY.gauge("tpuml_worker_heartbeat_age_seconds")
    assert {"wid": a} in g.labelsets()
    engine.unsubscribe(a)
    assert {"wid": a} not in g.labelsets()
    assert {"wid": b} in g.labelsets()


# ---------------- metrics-ingest dedupe (the double-observe fix) ----------------


def test_push_metrics_skips_same_process_observations():
    """An agent running in the coordinator's process already observed its
    phase histograms locally — the /task_metrics ingest must not observe
    them again (the documented double-observe; docs/OBSERVABILITY.md)."""
    from cs230_distributed_machine_learning_tpu.obs import process_token
    from cs230_distributed_machine_learning_tpu.runtime.cluster import (
        ClusterRuntime,
    )

    cluster = ClusterRuntime()
    try:
        wid = cluster.register_remote()
        h = REGISTRY.histogram("tpuml_executor_dispatch_seconds")
        c = REGISTRY.counter("tpuml_executor_flops_total")
        msg = {
            "batch_primary": True,
            "algo": "LogisticRegression",
            "batch_dispatch_s": 0.25,
            "batch_model_flops": 1e6,
        }
        remote = f"otherhost:{os.getpid()}"  # host-qualified: same pid
        # on ANOTHER host must still count (token, not bare pid)
        before_h = h.count()
        before_c = c.value(model="LogisticRegression")
        cluster.push_metrics(wid, {**msg, "obs_pid": process_token()})
        assert h.count() == before_h  # same process: already observed
        assert c.value(model="LogisticRegression") == before_c
        cluster.push_metrics(wid, {**msg, "obs_pid": remote})
        assert h.count() == before_h + 1  # a real remote process counts
        assert c.value(model="LogisticRegression") == before_c + 1e6
        # same contract on the result path: a same-process agent's POST
        # must not double-count subtask outcomes, and the wire-only
        # obs_pid stamp never reaches the stored result
        done = REGISTRY.counter("tpuml_subtasks_completed_total")
        sub = cluster.bus.subscribe("result")
        before_done = done.value()
        cluster.push_result(wid, {"subtask_id": "r1", "status": "completed",
                                  "obs_pid": process_token()})
        assert done.value() == before_done
        cluster.push_result(wid, {"subtask_id": "r2", "status": "completed",
                                  "obs_pid": remote})
        assert done.value() == before_done + 1
        for _ in range(2):
            _, published = sub.get(timeout=5)
            assert "obs_pid" not in published
        sub.close()
    finally:
        cluster.shutdown()


# ---------------- /cost + /healthz on a live two-worker topology ----------------


def test_cost_and_healthz_routes_two_worker_cluster():
    from werkzeug.test import Client

    from cs230_distributed_machine_learning_tpu.client.introspection import (
        extract_model_details,
    )
    from cs230_distributed_machine_learning_tpu.runtime.cluster import (
        ClusterRuntime,
    )
    from cs230_distributed_machine_learning_tpu.runtime.coordinator import (
        Coordinator,
    )
    from cs230_distributed_machine_learning_tpu.runtime.server import create_app
    from sklearn.linear_model import LogisticRegression
    from sklearn.model_selection import GridSearchCV

    cluster = ClusterRuntime()
    w0 = cluster.add_executor()
    w1 = cluster.add_executor()
    coord = Coordinator(cluster=cluster)
    client = Client(create_app(coord))
    try:
        sid = client.post("/create_session").get_json()["session_id"]
        est = GridSearchCV(
            LogisticRegression(max_iter=120), {"C": [0.3, 1.0, 3.0]}, cv=3
        )
        payload = {
            "dataset_id": "iris",
            "model_details": extract_model_details(est),
            "train_params": {"test_size": 0.2, "random_state": 0, "cv": 3},
        }
        jid = client.post(
            f"/train/{sid}", data=json.dumps(payload),
            content_type="application/json",
        ).get_json()["job_id"]
        deadline = time.time() + 120
        while time.time() < deadline:
            st = client.get(f"/check_status/{sid}/{jid}").get_json()
            if st["job_status"] in ("completed", "failed"):
                break
            time.sleep(0.25)
        assert st["job_status"] == "completed"

        cost = client.get(f"/cost/{jid}").get_json()
        assert cost["job_id"] == jid
        assert cost["n_groups"] >= 1
        assert cost["device_seconds"] > 0
        assert cost["model_flops"] > 0
        assert cost["mfu"] is None  # CPU backend
        group = cost["groups"][0]
        assert group["model_type"] == "LogisticRegression"
        assert group["n_subtasks"] >= 1
        assert client.get("/cost/no-such-job").status_code == 404

        hz = client.get("/healthz").get_json()
        assert hz["status"] in ("ok", "degraded")
        assert hz["device"]["reachable"] is True
        assert hz["n_workers"] == 2
        assert set(hz["workers"]) == {w0, w1}
        assert set(hz["queue_depths"]) == {w0, w1}
        for h in hz["workers"].values():
            assert "ewma_batch_s" in h and "failure_ratio" in h
        # the scrape surface exposes the same two workers as labeled gauges
        prom = client.get("/metrics/prom").get_data(as_text=True)
        assert f'tpuml_worker_heartbeat_age_seconds{{wid="{w0}"}}' in prom
        assert f'tpuml_worker_heartbeat_age_seconds{{wid="{w1}"}}' in prom
        assert 'tpuml_executor_flops_total{model="LogisticRegression"}' in prom
    finally:
        cluster.shutdown()


# ---------------- JSON structured logs ----------------


def test_json_formatter_stamps_trace_and_span_ids():
    from cs230_distributed_machine_learning_tpu.utils.logging import (
        JsonFormatter,
    )

    fmt = JsonFormatter()

    def emit(msg):
        rec = logging.LogRecord(
            "tpuml.test", logging.INFO, __file__, 1, msg, (), None,
            func="emit",
        )
        return json.loads(fmt.format(rec))

    with activate("feedbead00000000"):
        with span("log.parent") as sp:
            line = emit("inside span")
            assert line["trace_id"] == "feedbead00000000"
            assert line["span_id"] == sp.span_id
            assert line["msg"] == "inside span"
            assert line["level"] == "INFO"
    outside = emit("outside")
    assert "trace_id" not in outside and "span_id" not in outside


def test_json_formatter_serializes_exceptions():
    import sys

    from cs230_distributed_machine_learning_tpu.utils.logging import (
        JsonFormatter,
    )

    try:
        raise ValueError("kaput")
    except ValueError:
        rec = logging.LogRecord(
            "tpuml.test", logging.ERROR, __file__, 1, "boom", (),
            sys.exc_info(), func="emit",
        )
    line = json.loads(JsonFormatter().format(rec))
    assert "ValueError: kaput" in line["exc"]


def test_get_logger_opts_into_json_via_env(monkeypatch):
    monkeypatch.setenv("CS230_LOG_JSON", "1")
    from cs230_distributed_machine_learning_tpu.utils.logging import (
        JsonFormatter,
        get_logger,
    )

    logger = get_logger("tpuml.jsontest")  # fresh name -> configured now
    assert any(
        isinstance(h.formatter, JsonFormatter) for h in logger.handlers
    )

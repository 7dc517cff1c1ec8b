"""Durability + observability: journal resume, metrics snapshot, faults."""

import json
import os

from sklearn.linear_model import LogisticRegression

from cs230_distributed_machine_learning_tpu import MLTaskManager
from cs230_distributed_machine_learning_tpu.runtime.coordinator import Coordinator
from cs230_distributed_machine_learning_tpu.runtime.executor import (
    FaultInjector,
    LocalExecutor,
)
from cs230_distributed_machine_learning_tpu.runtime.store import JobStore
from cs230_distributed_machine_learning_tpu.utils.config import get_config


def test_journal_replay_restores_job_state(tmp_path):
    jd = str(tmp_path / "journal")
    store = JobStore(journal_dir=jd)
    sid = store.create_session()
    subtasks = [{"subtask_id": f"j-subtask-{i}"} for i in range(3)]
    store.create_job(sid, "j", {"dataset_id": "iris"}, subtasks)
    store.update_subtask(sid, "j", "j-subtask-0", "completed", {"mean_cv_score": 0.9})
    store.update_subtask(sid, "j", "j-subtask-1", "failed", {"error": "boom"})

    resumed = JobStore(journal_dir=jd)  # fresh process, replay
    assert resumed.has_session(sid)
    progress = resumed.job_progress(sid, "j")
    assert progress["tasks_completed"] == 2  # 1 completed + 1 failed
    assert progress["tasks_pending"] == 1
    assert resumed.subtask_results(sid, "j")[0]["mean_cv_score"] == 0.9

    # finalize in the resumed store; a third replay sees completion
    resumed.finalize_job(sid, "j", {"results": [], "best_result": None})
    third = JobStore(journal_dir=jd)
    assert third.job_progress(sid, "j")["job_status"] == "completed"


def test_coordinator_journal_survives_restart():
    coord = Coordinator(journal=True)
    m = MLTaskManager(coordinator=coord)
    m.train(LogisticRegression(max_iter=300), "iris", show_progress=False)

    coord2 = Coordinator(journal=True)  # same storage root -> replays
    status = coord2.check_status(m.session_id, m.job_id)
    assert status["job_status"] == "completed"
    assert status["job_result"]["best_result"]["accuracy"] > 0.8


def test_metrics_json_snapshot():
    coord = Coordinator()
    m = MLTaskManager(coordinator=coord)
    m.train(LogisticRegression(max_iter=300), "iris", show_progress=False)
    m.check_job_status()
    path = os.path.join(get_config().storage.root, "metrics.json")
    assert os.path.exists(path)
    snap = json.load(open(path))
    assert snap and snap[0]["status"] == "completed"


def test_fault_injection_fails_batch_then_recovers():
    injector = FaultInjector(fail_batches=1)
    coord = Coordinator(executor=LocalExecutor(fault_injector=injector))
    coord.executor.cache = coord.cache
    m = MLTaskManager(coordinator=coord)
    status = m.train(LogisticRegression(max_iter=300), "iris", show_progress=False)
    assert status["job_status"] == "completed"
    assert len(status["job_result"]["failed"]) == 1  # injected failure surfaced
    # next job is healthy again
    status2 = m.train(LogisticRegression(max_iter=300), "iris", show_progress=False)
    assert status2["job_result"]["best_result"] is not None


def test_wait_job_is_event_driven():
    """wait_job blocks until finalize_job fires the event, with no polling,
    and returns immediately for already-finalized jobs."""
    import threading
    import time

    store = JobStore()
    sid = store.create_session()
    store.create_job(sid, "j", {}, [{"subtask_id": "j-subtask-0"}])

    assert store.wait_job(sid, "j", timeout=0.05) is False  # not done yet

    t = threading.Timer(
        0.1, store.finalize_job, args=(sid, "j", {"results": [], "best_result": None})
    )
    t0 = time.time()
    t.start()
    try:
        assert store.wait_job(sid, "j", timeout=5.0) is True
        assert time.time() - t0 < 2.0  # woke on the event, not the timeout
        assert store.wait_job(sid, "j", timeout=0.0) is True  # already done
    finally:
        t.cancel()


def _rich_journal(jd: str) -> str:
    """Write a journal exercising EVERY op type: session, job, placement
    (+lease), result acks (completed and failed), an attempt bump, and the
    finalize. Returns the session id."""
    store = JobStore(journal_dir=jd)
    sid = store.create_session()
    subtasks = [{"subtask_id": f"f-subtask-{i}"} for i in range(3)]
    store.create_job(sid, "f", {"dataset_id": "iris"}, subtasks)
    store.record_placement(
        sid, "f", "f-subtask-0", "worker-0", attempt=0, lease_deadline=123.5
    )
    store.update_subtask(
        sid, "f", "f-subtask-0", "completed",
        {"mean_cv_score": 0.9, "attempt": 0},
    )
    store.record_attempt(
        sid, "f", "f-subtask-1", attempt=1, failures=1, excluded=["worker-0"]
    )
    store.record_placement(sid, "f", "f-subtask-1", "worker-1", attempt=1)
    store.update_subtask(
        sid, "f", "f-subtask-1", "failed", {"error": "boom", "attempt": 1}
    )
    store.update_subtask(
        sid, "f", "f-subtask-2", "completed", {"mean_cv_score": 0.8}
    )
    store.finalize_job(sid, "f", {"results": [], "best_result": None})
    return sid


def test_journal_crash_point_fuzz(tmp_path):
    """Replay must never raise no matter where a crash truncated the
    journal, and the truncated store must accept the remaining suffix:
    appending the rest of the ops and replaying again reproduces the full
    state (the coordinator-crash recovery contract, docs/ROBUSTNESS.md
    "Coordinator recovery")."""
    jd_full = str(tmp_path / "full")
    sid = _rich_journal(jd_full)
    raw = open(os.path.join(jd_full, "jobs.jsonl"), "rb").read()
    lines = raw.splitlines(keepends=True)
    assert len(lines) >= 8  # every op type is present
    want = JobStore(journal_dir=jd_full).job_progress(sid, "f")

    for i in range(len(lines) + 1):
        jd = str(tmp_path / f"cut{i}")
        os.makedirs(jd)
        path = os.path.join(jd, "jobs.jsonl")
        with open(path, "wb") as f:
            f.writelines(lines[:i])
        cut = JobStore(journal_dir=jd)  # must never raise
        assert cut.replay_skipped == 0
        # the suffix (ordered after the prefix, so every reference it
        # makes was created earlier) must apply cleanly on top
        with open(path, "ab") as f:
            f.writelines(lines[i:])
        resumed = JobStore(journal_dir=jd)
        assert resumed.job_progress(sid, "f") == want


def test_journal_torn_write_repaired(tmp_path):
    """A crash mid-append leaves a torn (non-JSON, unterminated) final
    line: replay skips it, repairs the tail with a newline, and ops
    appended after recovery survive the NEXT replay instead of
    concatenating onto the torn bytes."""
    jd_full = str(tmp_path / "full")
    _rich_journal(jd_full)
    raw = open(os.path.join(jd_full, "jobs.jsonl"), "rb").read()
    lines = raw.splitlines(keepends=True)

    jd = str(tmp_path / "torn")
    os.makedirs(jd)
    path = os.path.join(jd, "jobs.jsonl")
    with open(path, "wb") as f:
        f.writelines(lines[:3])
        f.write(lines[3][: len(lines[3]) // 2])  # torn mid-line, no \n
    store = JobStore(journal_dir=jd)  # must not raise
    assert store.replay_skipped == 1
    assert store.replay_ops.get("create_job") == 1
    # post-recovery append starts on a clean line (tail repair)
    sid2 = store.create_session()
    third = JobStore(journal_dir=jd)
    assert third.has_session(sid2)
    assert third.replay_skipped == 1  # still just the one torn line


def test_placement_journal_replayed(tmp_path):
    """The `place` op restores placed_worker/placed_attempt/lease_deadline
    into the spec — how a restarted coordinator tells dispatched in-flight
    subtasks from never-dispatched ones."""
    jd = str(tmp_path / "journal")
    store = JobStore(journal_dir=jd)
    sid = store.create_session()
    store.create_job(
        sid, "p", {}, [{"subtask_id": "p-subtask-0"}, {"subtask_id": "p-subtask-1"}]
    )
    store.record_placement(
        sid, "p", "p-subtask-0", "worker-3", attempt=2, lease_deadline=99.5
    )

    resumed = JobStore(journal_dir=jd)
    spec = resumed.get_job(sid, "p")["subtasks"]["p-subtask-0"]["spec"]
    assert spec["placed_worker"] == "worker-3"
    assert spec["placed_attempt"] == 2
    assert spec["lease_deadline"] == 99.5
    # the sibling was never placed: no stamps
    other = resumed.get_job(sid, "p")["subtasks"]["p-subtask-1"]["spec"]
    assert "placed_worker" not in other
    assert resumed.replay_ops["place"] == 1
    assert resumed.replay_ops["create_job"] == 1


def test_unfinished_counts_for_admission():
    store = JobStore()
    sid_a = store.create_session()
    sid_b = store.create_session()
    store.create_job(sid_a, "a1", {}, [{"subtask_id": f"a1-s{i}"} for i in range(4)])
    store.create_job(sid_b, "b1", {}, [{"subtask_id": "b1-s0"}])
    store.update_subtask(sid_a, "a1", "a1-s0", "completed", {"mean_cv_score": 1.0})
    counts = store.unfinished_counts()
    assert counts["jobs"] == 2
    assert counts["per_session"] == {sid_a: 1, sid_b: 1}
    assert counts["pending_subtasks"] == 4  # 3 left on a1 + 1 on b1
    store.finalize_job(sid_b, "b1", {"results": [], "best_result": None})
    counts = store.unfinished_counts()
    assert counts["jobs"] == 1
    assert counts["per_session"] == {sid_a: 1}


def test_coordinator_resumes_inflight_job():
    """A coordinator killed mid-job must complete the job after restart with
    NO client resubmission: journal replay restores state, resume_inflight
    re-dispatches the subtasks that never reported."""
    from cs230_distributed_machine_learning_tpu.runtime.subtasks import (
        create_subtasks,
    )

    # simulate the dead coordinator's journal: job created, 1 of 3 subtasks
    # completed, never finalized (the process died here)
    jd = get_config().storage.journal_dir
    store = JobStore(journal_dir=jd)
    sid = store.create_session()
    model_details = {
        "model_type": "LogisticRegression",
        "search_type": "GridSearchCV",
        "base_estimator_params": {"max_iter": 300},
        "param_grid": {"C": [0.1, 1.0, 10.0]},
    }
    subtasks = create_subtasks("jobr", sid, "iris", model_details, {"cv": 3})
    assert len(subtasks) == 3
    store.create_job(sid, "jobr", {"dataset_id": "iris"}, subtasks)
    store.update_subtask(
        sid, "jobr", subtasks[0]["subtask_id"], "completed",
        {"subtask_id": subtasks[0]["subtask_id"], "status": "completed",
         "mean_cv_score": 0.91, "accuracy": 0.9},
    )
    del store

    # restart: resume_inflight dispatches the 2 unreported subtasks
    coord = Coordinator(journal=True)
    assert coord.store.wait_job(sid, "jobr", timeout=120)
    status = coord.check_status(sid, "jobr")
    assert status["job_status"] == "completed"
    results = status["job_result"]["results"]
    assert len(results) == 3  # 1 journaled + 2 re-run
    fresh = [r for r in results if r["mean_cv_score"] != 0.91]
    assert len(fresh) >= 2 and all(r["status"] == "completed" for r in fresh)


def test_resume_with_all_subtasks_done_just_aggregates():
    """Coordinator died between last result and finalize: resume must
    aggregate without re-running anything."""
    from cs230_distributed_machine_learning_tpu.runtime.subtasks import (
        create_subtasks,
    )

    jd = get_config().storage.journal_dir
    store = JobStore(journal_dir=jd)
    sid = store.create_session()
    md = {"model_type": "LogisticRegression", "search_type": None,
          "base_estimator_params": {"max_iter": 300}}
    subtasks = create_subtasks("jobd", sid, "iris", md, {})
    store.create_job(sid, "jobd", {"dataset_id": "iris"}, subtasks)
    for st in subtasks:
        store.update_subtask(
            sid, "jobd", st["subtask_id"], "completed",
            {"subtask_id": st["subtask_id"], "status": "completed",
             "mean_cv_score": 0.88, "accuracy": 0.9},
        )
    del store

    coord = Coordinator(journal=True)
    assert coord.store.wait_job(sid, "jobd", timeout=30)
    res = coord.check_status(sid, "jobd")["job_result"]
    assert res["best_result"]["mean_cv_score"] == 0.88

"""The fold plan is built once per (dataset, split arguments), not once per
search: ``ops/folds.py::SplitPlanCache`` behind ``runtime/executor.py::
_split_plan``.

A hit must return the arrays a miss built, and a miss must build what a
direct ``build_split_plan`` call builds, so every test compares against
that call: there is no switch that turns the memo off.
"""

import sys
import threading
import time

import numpy as np
import pytest
from sklearn.linear_model import LogisticRegression
from sklearn.model_selection import GridSearchCV

from cs230_distributed_machine_learning_tpu import MLTaskManager
from cs230_distributed_machine_learning_tpu.data.datasets import stage_arrays
from cs230_distributed_machine_learning_tpu.data.stage_cache import (
    dataset_fingerprint,
)
from cs230_distributed_machine_learning_tpu.models.base import TrialData
from cs230_distributed_machine_learning_tpu.obs import REGISTRY, TRACER
from cs230_distributed_machine_learning_tpu.ops import folds
from cs230_distributed_machine_learning_tpu.ops.folds import (
    SPLIT_PLAN_CACHE,
    SplitPlanCache,
    build_split_plan,
)
from cs230_distributed_machine_learning_tpu.runtime import executor

BASE = dict(task="classification", n_folds=5, test_size=0.2, random_state=42)


def _data(seed, n=120):
    rng = np.random.default_rng(seed)
    X = rng.normal(size=(n, 4)).astype(np.float32)
    y = rng.integers(0, 3, size=n).astype(np.int32)
    return TrialData(X=X, y=y, n_classes=3)


def _get(cache, data, **kw):
    return cache.get_or_build(dataset_fingerprint(data), np.asarray(data.y), **kw)


def _is_hit(cache, data, plan, **kw):
    got, outcome = _get(cache, data, **kw)
    return got is plan and outcome == "hit"


def _same_masks(plan, y, **kw):
    direct = build_split_plan(y, **kw)
    np.testing.assert_array_equal(plan.train_w, direct.train_w)
    np.testing.assert_array_equal(plan.eval_w, direct.eval_w)
    assert (plan.n_folds, plan.signature) == (direct.n_folds, direct.signature)


@pytest.fixture(autouse=True)
def _empty_memo():
    SPLIT_PLAN_CACHE.clear()
    yield
    SPLIT_PLAN_CACHE.clear()


def _counts():
    c = REGISTRY.counter("tpuml_split_plan_cache_total")
    return {o: c.value(outcome=o) for o in ("hit", "miss", "bypass")}


def _split_plan_spans(manager):
    # the job thread records its spans just after the finalize that woke
    # the client
    deadline = time.time() + 5
    while time.time() < deadline:
        spans = TRACER.spans_for(manager.trace_id)
        if any(s["name"] == "job.aggregate" for s in spans):
            break
        time.sleep(0.01)
    return [s["attrs"] for s in spans if s["name"] == "executor.split_plan"]


def test_second_lookup_is_a_hit_and_returns_the_same_plan():
    cache, data = SplitPlanCache(), _data(0)
    first, o1 = _get(cache, data, **BASE)
    second, o2 = _get(cache, data, **BASE)
    assert (o1, o2) == ("miss", "hit")
    assert second is first
    _same_masks(first, np.asarray(data.y), **BASE)
    # an equal dataset in another object is the same content
    twin = TrialData(X=data.X.copy(), y=data.y.copy(), n_classes=3)
    assert _is_hit(cache, twin, first, **BASE)


def test_equal_n_and_different_labels_get_their_own_masks():
    """The hazard of the bare signature: ``y`` is not in it, and
    StratifiedKFold reads ``y``."""
    cache = SplitPlanCache()
    a, b = _data(1), _data(2)
    assert len(a.y) == len(b.y) and not np.array_equal(a.y, b.y)
    pa, oa = _get(cache, a, **BASE)
    pb, ob = _get(cache, b, **BASE)
    assert (oa, ob) == ("miss", "miss")
    assert pa.signature == pb.signature
    assert not np.array_equal(pa.train_w, pb.train_w)
    _same_masks(pa, np.asarray(a.y), **BASE)
    _same_masks(pb, np.asarray(b.y), **BASE)
    assert _is_hit(cache, a, pa, **BASE)
    assert _is_hit(cache, b, pb, **BASE)


@pytest.mark.parametrize(
    "change",
    [{"n_folds": 3}, {"n_folds": 0}, {"test_size": 0.25}, {"random_state": 7},
     {"task": "regression"}],
    ids=lambda c: "-".join(f"{k}={v}" for k, v in c.items()),
)
def test_a_change_in_any_split_argument_is_a_miss(change):
    cache, data = SplitPlanCache(), _data(3)
    base, _ = _get(cache, data, **BASE)
    kw = {**BASE, **change}
    other, outcome = _get(cache, data, **kw)
    assert outcome == "miss" and other is not base
    _same_masks(other, np.asarray(data.y), **kw)
    assert _is_hit(cache, data, base, **BASE)
    assert _is_hit(cache, data, other, **kw)


@pytest.mark.parametrize(
    "random_state", [None, np.random.RandomState(0)], ids=["none", "instance"]
)
def test_a_fresh_draw_is_never_memoised(random_state):
    cache, data = SplitPlanCache(), _data(4)
    kw = {**BASE, "random_state": random_state}
    p1, o1 = _get(cache, data, **kw)
    p2, o2 = _get(cache, data, **kw)
    assert (o1, o2) == ("bypass", "bypass")
    assert p1 is not p2 and len(cache) == 0
    assert not np.array_equal(p1.train_w[0], p2.train_w[0])  # two draws
    np.testing.assert_array_equal(p1.train_w[1:], p2.train_w[1:])  # same folds
    assert p1.train_w.flags.writeable  # its own arrays, nobody shares them


def test_memoised_masks_refuse_writes():
    plan, _ = _get(SplitPlanCache(), _data(5), **BASE)
    for w in (plan.train_w, plan.eval_w):
        assert not w.flags.writeable
        with pytest.raises(ValueError):
            w[0, 0] = 1.0
        with pytest.raises(ValueError):
            w[1:3][0, 0] = 1.0  # the engine's slices are views: as frozen


def test_byte_cap_evicts_the_oldest_and_admits_the_newest():
    datas = [_data(10 + i) for i in range(4)]
    one = 2 * 6 * 120 * 4  # two float32 masks of (5 + 1) x 120
    cache = SplitPlanCache(max_bytes=2 * one + one // 2)
    plans = [_get(cache, d, **BASE)[0] for d in datas[:2]]
    assert (len(cache), cache.nbytes()) == (2, 2 * one)
    assert _is_hit(cache, datas[0], plans[0], **BASE)  # 1 is now oldest
    _get(cache, datas[2], **BASE)
    assert (len(cache), cache.nbytes()) == (2, 2 * one)
    assert _get(cache, datas[0], **BASE)[1] == "hit"
    assert _get(cache, datas[2], **BASE)[1] == "hit"
    assert _get(cache, datas[1], **BASE)[1] == "miss"  # it went, and is back
    # a plan larger than the whole cap is admitted, alone
    big = _data(20, n=1200)
    plan, outcome = _get(cache, big, **BASE)
    assert outcome == "miss" and plan.train_w.nbytes * 2 > cache.max_bytes
    assert (len(cache), cache.nbytes()) == (1, 10 * one)
    assert _is_hit(cache, big, plan, **BASE)
    assert folds.SPLIT_PLAN_CACHE_BYTES == SPLIT_PLAN_CACHE.max_bytes == 1 << 30


def test_concurrent_misses_on_one_key_build_once(monkeypatch):
    builds, real = [], folds.build_split_plan

    def slow_build(y, **kw):
        builds.append(threading.get_ident())
        time.sleep(0.05)  # hold the build open while the others arrive
        return real(y, **kw)

    monkeypatch.setattr(folds, "build_split_plan", slow_build)
    cache, data = SplitPlanCache(), _data(6)
    fp, y = dataset_fingerprint(data), np.asarray(data.y)
    n_threads = 16
    start, got, errors = threading.Barrier(n_threads), [], []

    def worker():
        try:
            start.wait(timeout=10)
            got.append(cache.get_or_build(fp, y, **BASE))
        except Exception as e:  # noqa: BLE001 — reported below
            errors.append(e)

    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        threads = [threading.Thread(target=worker) for _ in range(n_threads)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=30)
    finally:
        sys.setswitchinterval(interval)
    assert not errors and not any(t.is_alive() for t in threads)
    assert len(builds) == 1
    assert sorted(o for _, o in got) == ["hit"] * (n_threads - 1) + ["miss"]
    assert all(p is got[0][0] for p, _ in got)
    assert (len(cache), cache.nbytes()) == (1, 2 * 6 * 120 * 4)


def test_a_failed_build_is_not_memoised_and_frees_the_key(monkeypatch):
    cache, data = SplitPlanCache(), _data(7)
    real = folds.build_split_plan

    def broken(y, **kw):
        raise RuntimeError("splitter failed")

    monkeypatch.setattr(folds, "build_split_plan", broken)
    with pytest.raises(RuntimeError, match="splitter failed"):
        _get(cache, data, **BASE)
    monkeypatch.setattr(folds, "build_split_plan", real)
    assert len(cache) == 0
    assert _get(cache, data, **BASE)[1] == "miss"


def _search():
    return GridSearchCV(LogisticRegression(max_iter=200), {"C": [0.1, 1.0, 10.0]}, cv=3)


def _scores(status):
    assert status["job_status"] == "completed"
    assert status["job_result"]["failed"] == []
    return sorted(
        (r["parameters"]["C"], r["accuracy"], tuple(r["cv_scores"]))
        for r in status["job_result"]["results"]
    )


def test_searches_share_one_plan_and_say_so_in_span_and_counter():
    rng = np.random.default_rng(27)
    X = rng.normal(size=(181, 5)).astype(np.float32)
    y = (X[:, 0] + 0.5 * rng.normal(size=181) > 0).astype(np.int64)
    stage_arrays("plan27", X, y)
    manager, before = MLTaskManager(), _counts()
    outcomes, scores = [], []
    for _ in range(2):
        scores.append(_scores(manager.train(_search(), "plan27", show_progress=False)))
        (attrs,) = _split_plan_spans(manager)
        assert (attrs["n_rows"], attrs["n_splits"]) == (181, 4)
        assert attrs["signature"] == "('classification', 181, 3, 0.2, 42)"
        outcomes.append(attrs["outcome"])
    assert outcomes == ["miss", "hit"]
    after = _counts()
    assert {o: after[o] - before[o] for o in after} == {"hit": 1, "miss": 1, "bypass": 0}
    assert scores[0] == scores[1]
    # a job that asks for a fresh draw is built afresh and says so
    manager.train(
        _search(), "plan27", train_params={"random_state": None}, show_progress=False
    )
    (attrs,) = _split_plan_spans(manager)
    assert attrs["outcome"] == "bypass"
    assert _counts()["bypass"] - before["bypass"] == 1 and len(SPLIT_PLAN_CACHE) == 1


def test_memoised_searches_score_as_a_directly_built_plan_does(monkeypatch):
    manager = MLTaskManager()
    first = _scores(manager.train(_search(), "iris", show_progress=False))
    second = _scores(manager.train(_search(), "iris", show_progress=False))
    assert len(SPLIT_PLAN_CACHE) == 1
    monkeypatch.setattr(
        executor, "_split_plan", lambda data, y, **kw: build_split_plan(y, **kw)
    )
    direct = _scores(manager.train(_search(), "iris", show_progress=False))
    assert first == second == direct

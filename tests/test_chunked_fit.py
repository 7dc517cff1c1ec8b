"""Chunked-fit protocol (trees): multi-dispatch forest fits must score the
same as the single-dispatch path (same RNG-keyed trees, accumulated
soft-vote), and the engine must route through it when the MACs budget says
one dispatch would be too long."""

import numpy as np
import pytest

from cs230_distributed_machine_learning_tpu.models.base import TrialData
from cs230_distributed_machine_learning_tpu.models.registry import get_kernel
from cs230_distributed_machine_learning_tpu.ops.folds import build_split_plan
from cs230_distributed_machine_learning_tpu.parallel import trial_map


def _toy(task="classification", n=400, d=8, seed=0):
    rng = np.random.RandomState(seed)
    X = rng.randn(n, d).astype(np.float32)
    if task == "classification":
        y = (X[:, 0] + 0.5 * X[:, 1] > 0).astype(np.int32)
        return TrialData(X=X, y=y, n_classes=2)
    y = (X[:, 0] * 2 + X[:, 1] + 0.1 * rng.randn(n)).astype(np.float32)
    return TrialData(X=X, y=y, n_classes=0)


@pytest.mark.parametrize("model,task", [
    ("RandomForestClassifier", "classification"),
    ("RandomForestRegressor", "regression"),
    ("GradientBoostingClassifier", "classification"),
    ("GradientBoostingRegressor", "regression"),
])
def test_chunked_matches_quality(model, task, monkeypatch):
    """Forcing many chunks must score the SAME as the single-dispatch path:
    both derive per-tree/-stage keys as fold_in(t) of the trial seed, so the
    fitted ensembles are identical up to float reduction order."""
    data = _toy(task)
    plan = build_split_plan(np.asarray(data.y), task=task, n_folds=3)
    kernel = get_kernel(model)
    params = [{"n_estimators": 40, "max_depth": 4, "random_state": 0}]

    trial_map._compiled_cache.clear()
    run_mono = trial_map.run_trials(kernel, data, plan, params)
    assert run_mono.n_dispatches == 1

    monkeypatch.setenv("CS230_TREE_CHUNK_MACS", "1e6")  # force many chunks
    trial_map._compiled_cache.clear()
    run_chunked = trial_map.run_trials(kernel, data, plan, params)
    assert run_chunked.n_dispatches > 2  # init + steps + eval

    m0 = run_mono.trial_metrics[0]
    m1 = run_chunked.trial_metrics[0]
    assert m1["mean_cv_score"] == pytest.approx(m0["mean_cv_score"], abs=1e-5)
    if task == "classification":
        assert m1["accuracy"] == pytest.approx(m0["accuracy"], abs=1e-5)
    else:
        assert m1["r2_score"] == pytest.approx(m0["r2_score"], abs=1e-4)


def test_chunked_plan_thresholds():
    kernel = get_kernel("RandomForestClassifier")
    static = kernel.resolve_static(
        {"n_estimators": 100, "max_depth": 10, "n_bins": 128}, 116202, 54, 7
    )
    plan = kernel.chunked_plan(static, 116202, 54, 7, 6)
    assert plan is not None and plan["n_chunks"] > 1
    # tiny problem: no chunking
    static = kernel.resolve_static({"n_estimators": 10, "max_depth": 3}, 150, 4, 3)
    assert kernel.chunked_plan(static, 150, 4, 3, 6) is None


@pytest.mark.parametrize("model,task", [
    ("KNeighborsClassifier", "classification"),
    ("KNeighborsRegressor", "regression"),
])
def test_knn_chunked_matches_monolithic(model, task, monkeypatch):
    """Query-row chunking must produce the SAME predictions as one dispatch
    (KNN is deterministic — exact equality expected)."""
    data = _toy(task, n=3500)  # > 3 query blocks so >1 chunk is possible
    plan = build_split_plan(np.asarray(data.y), task=task, n_folds=3)
    kernel = get_kernel(model)
    params = [{"n_neighbors": 5}]

    trial_map._compiled_cache.clear()
    mono = trial_map.run_trials(kernel, data, plan, params)
    assert mono.n_dispatches == 1

    monkeypatch.setenv("CS230_KNN_CHUNK_MACS", "1e5")
    static = kernel.resolve_static({"n_neighbors": 5, "weights": "uniform", "p": 2},
                                   3500, data.X.shape[1], data.n_classes)
    assert kernel.chunked_plan(static, 3500, data.X.shape[1], data.n_classes, 4)["n_chunks"] > 1
    trial_map._compiled_cache.clear()
    chunked = trial_map.run_trials(kernel, data, plan, params)
    assert chunked.n_dispatches > 3  # init + >=2 steps + eval

    np.testing.assert_allclose(
        mono.trial_metrics[0]["mean_cv_score"],
        chunked.trial_metrics[0]["mean_cv_score"],
        rtol=1e-6,
    )


def test_chunked_grid_multiple_trials(monkeypatch):
    """A small grid through the chunked path: per-trial results keep
    submission order and rank sensibly."""
    monkeypatch.setenv("CS230_TREE_CHUNK_MACS", "1e6")
    data = _toy("classification")
    plan = build_split_plan(np.asarray(data.y), task="classification", n_folds=3)
    kernel = get_kernel("RandomForestClassifier")
    params = [
        {"n_estimators": 10, "max_depth": 3, "random_state": 0},
        {"n_estimators": 30, "max_depth": 5, "random_state": 0},
    ]
    trial_map._compiled_cache.clear()
    run = trial_map.run_trials(kernel, data, plan, params)
    assert len(run.trial_metrics) == 2
    for m in run.trial_metrics:
        assert 0.5 < m["mean_cv_score"] <= 1.0

@pytest.mark.parametrize("model,task", [
    ("RandomForestClassifier", "classification"),
    ("GradientBoostingRegressor", "regression"),
])
def test_fit_single_chunked_artifact(model, task, monkeypatch):
    """fit_single through the chunked branch must yield a usable artifact
    whose predictions score like the monolithic one."""
    import jax.numpy as jnp

    data = _toy(task)
    plan = build_split_plan(np.asarray(data.y), task=task, n_folds=3)
    kernel = get_kernel(model)
    params = {"n_estimators": 20, "max_depth": 4, "random_state": 0}

    trial_map._compiled_cache.clear()
    fitted_mono, static = trial_map.fit_single(kernel, data, plan, params)

    monkeypatch.setenv("CS230_TREE_CHUNK_MACS", "1e6")
    trial_map._compiled_cache.clear()
    fitted_chunk, static2 = trial_map.fit_single(kernel, data, plan, params)

    # same tree-count artifact, comparable in-sample quality
    assert fitted_chunk["trees"]["leaf_val"].shape == fitted_mono["trees"]["leaf_val"].shape
    import jax

    X = jnp.asarray(data.X)
    pred_c = np.asarray(kernel.predict(
        jax.tree_util.tree_map(jnp.asarray, fitted_chunk), X, static))
    y = np.asarray(data.y)
    if task == "classification":
        assert (pred_c == y).mean() > 0.85
    else:
        ss = 1 - ((pred_c - y) ** 2).sum() / ((y - y.mean()) ** 2).sum()
        assert ss > 0.7


def test_split_axis_chunking_matches(monkeypatch):
    """When one trial x n_splits exceeds the memory budget, folds run across
    dispatches; scores must be identical to the single-group run."""
    data = _toy("classification", n=600)
    plan = build_split_plan(np.asarray(data.y), task="classification", n_folds=5)
    kernel = get_kernel("RandomForestClassifier")
    params = [{"n_estimators": 12, "max_depth": 4, "random_state": 0}]
    monkeypatch.setenv("CS230_TREE_CHUNK_MACS", "1e6")  # force chunked path

    trial_map._compiled_cache.clear()
    full = trial_map.run_trials(kernel, data, plan, params)

    static = kernel.resolve_static(
        {"n_estimators": 12, "max_depth": 4, "random_state": 0}, 600, 8, 2
    )
    static["_n_classes"] = 2
    per = max(kernel.memory_estimate_mb(600, 8, static), 0.5)
    # budget = 0.5 * device_mb = 3 * per -> splits run in groups of 3 (6 total)
    monkeypatch.setattr(trial_map._backend, "device_memory_mb", lambda: 6.0 * per)
    trial_map._compiled_cache.clear()
    grouped = trial_map.run_trials(kernel, data, plan, params)

    assert grouped.n_dispatches > full.n_dispatches  # split groups multiplied
    m0, m1 = full.trial_metrics[0], grouped.trial_metrics[0]
    assert m1["mean_cv_score"] == pytest.approx(m0["mean_cv_score"], abs=1e-6)
    assert m1["cv_scores"] == pytest.approx(m0["cv_scores"], abs=1e-6)

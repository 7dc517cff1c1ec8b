"""Trial engine: vmapped multi-trial execution on the 8-device mesh."""

import numpy as np
from sklearn.datasets import load_iris

from cs230_distributed_machine_learning_tpu.models.base import TrialData
from cs230_distributed_machine_learning_tpu.models.registry import get_kernel
from cs230_distributed_machine_learning_tpu.ops.folds import build_split_plan
from cs230_distributed_machine_learning_tpu.parallel import trial_map
from cs230_distributed_machine_learning_tpu.parallel.trial_map import run_trials


def _iris_data():
    X, y = load_iris(return_X_y=True)
    return TrialData(X=X.astype(np.float32), y=y.astype(np.int32), n_classes=3)


def test_run_trials_grid_on_mesh(eight_device_mesh):
    data = _iris_data()
    plan = build_split_plan(np.asarray(data.y), task="classification", n_folds=5)
    kernel = get_kernel("LogisticRegression")
    params = [{"C": c} for c in [0.001, 0.01, 0.1, 1.0, 10.0]]
    out = run_trials(kernel, data, plan, params, mesh=eight_device_mesh)
    assert len(out.trial_metrics) == 5
    for m in out.trial_metrics:
        assert 0.0 <= m["accuracy"] <= 1.0
        assert len(m["cv_scores"]) == 5
        assert abs(m["mean_cv_score"] - np.mean(m["cv_scores"])) < 1e-6
    # regularization ordering: tiny C must underperform moderate C
    scores = [m["mean_cv_score"] for m in out.trial_metrics]
    assert scores[0] < max(scores[2:])


def test_run_trials_single_trial_no_mesh():
    data = _iris_data()
    plan = build_split_plan(np.asarray(data.y), task="classification", n_folds=5)
    kernel = get_kernel("LogisticRegression")
    out = run_trials(kernel, data, plan, [{}])
    assert len(out.trial_metrics) == 1
    assert out.trial_metrics[0]["accuracy"] > 0.8


def test_trial_count_not_multiple_of_devices(eight_device_mesh):
    """Padding: 11 trials on 8 devices must still return 11 results."""
    data = _iris_data()
    plan = build_split_plan(np.asarray(data.y), task="classification", n_folds=3)
    kernel = get_kernel("LogisticRegression")
    params = [{"C": 0.05 * (i + 1)} for i in range(11)]
    out = run_trials(kernel, data, plan, params, mesh=eight_device_mesh)
    assert len(out.trial_metrics) == 11


def test_static_bucketing_separates_compiles():
    """Different static configs (fit_intercept) must not collide."""
    data = _iris_data()
    plan = build_split_plan(np.asarray(data.y), task="classification", n_folds=0)
    kernel = get_kernel("LogisticRegression")
    params = [{"C": 1.0, "fit_intercept": True}, {"C": 1.0, "fit_intercept": False}]
    out = run_trials(kernel, data, plan, params)
    assert len(out.trial_metrics) == 2


def test_host_fast_path_used_for_tiny_buckets(monkeypatch):
    """Tiny buckets of kernels with an analytical cost estimate run on the
    host CPU backend (placement decision); scores must match the device
    path. On a CPU-default backend the flag is moot — this exercises the
    decision logic and the result plumbing."""
    import jax

    from cs230_distributed_machine_learning_tpu.models.base import TrialData
    from cs230_distributed_machine_learning_tpu.models.registry import get_kernel
    from cs230_distributed_machine_learning_tpu.ops.folds import build_split_plan

    X = np.random.RandomState(0).randn(120, 5).astype(np.float32)
    y = (X[:, 0] > 0).astype(np.int32)
    data = TrialData(X=X, y=y, n_classes=2)
    plan = build_split_plan(y, task="classification", n_folds=3)
    kernel = get_kernel("LogisticRegression")
    static = kernel.resolve_static({"fit_intercept": True, "penalty": "l2"},
                                   120, 5, 2)
    static["_n_classes"] = 2
    # the analytical estimate puts an iris-scale bucket under the host cap
    assert kernel.macs_estimate(120, 5, static) * 4 * 8 < trial_map._HOST_EXEC_MACS
    out = trial_map.run_trials(kernel, data, plan,
                               [{"C": c} for c in (0.1, 1.0, 10.0)])
    assert len(out.trial_metrics) == 3
    for m in out.trial_metrics:
        assert 0.5 <= m["mean_cv_score"] <= 1.0


def test_generic_split_group_chunking_matches_monolithic(monkeypatch):
    """When one trial x all folds exceeds the memory budget, the generic
    (non-chunked-protocol) path must run fold groups across dispatches and
    still produce identical metrics — Nyström SVC's [n, m]-per-lane OOM at
    full Covertype is the motivating case (r3)."""
    data = _iris_data()
    plan = build_split_plan(np.asarray(data.y), task="classification", n_folds=5)
    kernel = get_kernel("LogisticRegression")
    params = [{"C": 1.0}]

    base = run_trials(kernel, data, plan, params)

    # tiny budget: per-split estimate x 6 splits >> budget -> fold groups
    monkeypatch.setattr(trial_map._backend, "device_memory_mb", lambda: 4.0 * max(
        kernel.memory_estimate_mb(len(data.X), data.X.shape[1], {"_n_classes": 3}),
        0.5))
    trial_map._compiled_cache.clear()
    grouped = run_trials(kernel, data, plan, params)

    assert grouped.n_dispatches > base.n_dispatches
    a, b = base.trial_metrics[0], grouped.trial_metrics[0]
    assert a["accuracy"] == b["accuracy"]
    np.testing.assert_allclose(a["cv_scores"], b["cv_scores"], atol=1e-6)

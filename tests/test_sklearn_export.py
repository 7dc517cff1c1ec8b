"""sklearn export parity: every family's artifact loads into a real sklearn
estimator whose predict matches the kernel (VERDICT r3 item 5).

Reference contract being matched: the worker pickles fitted sklearn
estimators and the master serves them (``aws-prod/worker/worker.py:352-356``,
``aws-prod/master/master.py:270-291``) — any sklearn user can .predict()
with the download. Our artifacts are kernel dicts; runtime/sklearn_export.py
constructs the equivalent sklearn object and injects the fitted state.
"""

import numpy as np
import pytest
from sklearn.datasets import make_classification, make_regression

from cs230_distributed_machine_learning_tpu.models.base import TrialData
from cs230_distributed_machine_learning_tpu.models.registry import get_kernel
from cs230_distributed_machine_learning_tpu.ops.folds import build_split_plan
from cs230_distributed_machine_learning_tpu.parallel.trial_map import fit_single
from cs230_distributed_machine_learning_tpu.runtime.artifacts import (
    predict_with_artifact,
)
from cs230_distributed_machine_learning_tpu.runtime.sklearn_export import to_sklearn


def _data(kind, seed=0, n=300):
    if kind == "cls3":
        X, y = make_classification(
            n_samples=n, n_features=6, n_informative=4, n_classes=3, random_state=seed
        )
        return TrialData(X=X.astype(np.float32), y=y.astype(np.int32), n_classes=3)
    if kind == "cls2":
        X, y = make_classification(
            n_samples=n, n_features=6, n_informative=4, n_classes=2, random_state=seed
        )
        return TrialData(X=X.astype(np.float32), y=y.astype(np.int32), n_classes=2)
    X, y = make_regression(n_samples=n, n_features=6, noise=5.0, random_state=seed)
    return TrialData(X=X.astype(np.float32), y=y.astype(np.float32), n_classes=0)


def _fit_artifact(name, data, params):
    kernel = get_kernel(name)
    plan = build_split_plan(
        np.asarray(data.y), task=kernel.task, n_folds=0, test_size=0.2, random_state=42
    )
    fitted, static = fit_single(kernel, data, plan, params)
    return {
        "model_type": name,
        "parameters": params,
        "static": static,
        "fitted_params": fitted,
    }, kernel


_XQ = np.random.RandomState(9).randn(120, 6).astype(np.float32)


def _assert_parity(artifact, kernel, exact=True):
    ours = np.asarray(predict_with_artifact(artifact, _XQ))
    est = to_sklearn(artifact)
    theirs = np.asarray(est.predict(_XQ.astype(np.float64)))
    if kernel.task == "classification":
        rate = float(np.mean(ours == theirs))
        assert rate == 1.0 if exact else rate > 0.99, rate
    else:
        rel = float(np.max(np.abs(ours - theirs)) / (np.std(ours) + 1e-9))
        assert rel < 1e-4, rel
    return est


@pytest.mark.parametrize(
    "name,kind,params",
    [
        ("LogisticRegression", "cls3", {"C": 1.0}),
        ("LogisticRegression", "cls2", {"C": 0.1}),
        ("Ridge", "reg", {"alpha": 1.0}),
        ("LinearRegression", "reg", {}),
        ("MLPClassifier", "cls3", {"hidden_layer_sizes": [8], "max_iter": 30}),
        ("MLPClassifier", "cls2", {"hidden_layer_sizes": [8], "max_iter": 30}),
        ("MLPRegressor", "reg", {"hidden_layer_sizes": [8], "max_iter": 30}),
        ("KNeighborsClassifier", "cls3", {"n_neighbors": 3}),
        ("KNeighborsRegressor", "reg", {"n_neighbors": 4}),
        ("GaussianNB", "cls3", {}),
        ("DecisionTreeClassifier", "cls3", {"max_depth": 4}),
        ("DecisionTreeRegressor", "reg", {"max_depth": 4}),
        ("RandomForestClassifier", "cls3", {"n_estimators": 5, "max_depth": 4}),
        ("RandomForestRegressor", "reg", {"n_estimators": 4, "max_depth": 3}),
        ("GradientBoostingClassifier", "cls3", {"n_estimators": 5}),
        ("GradientBoostingClassifier", "cls2", {"n_estimators": 5}),
        ("GradientBoostingRegressor", "reg", {"n_estimators": 5}),
        ("SVC", "cls3", {"C": 1.0}),
        ("SVC", "cls2", {"C": 1.0}),
        ("SVR", "reg", {"C": 1.0}),
    ],
)
def test_export_predict_parity(name, kind, params):
    artifact, kernel = _fit_artifact(name, _data(kind), params)
    est = _assert_parity(artifact, kernel)
    # the export is a REAL estimator of the expected class
    assert type(est).__name__ == name or hasattr(est, "steps")


def test_export_deep_arena_trees(monkeypatch):
    """sklearn RF defaults (max_depth=None) use the frontier-compacted deep
    builder on large data; its arena trees must export too."""
    monkeypatch.setenv("CS230_TREE_DEEP_N", "200")
    for name, kind, params in [
        ("RandomForestClassifier", "cls3", {"n_estimators": 4}),
        ("DecisionTreeClassifier", "cls3", {}),
        ("RandomForestRegressor", "reg", {"n_estimators": 3}),
    ]:
        artifact, kernel = _fit_artifact(name, _data(kind, n=600), params)
        assert artifact["static"].get("_deep"), "deep path not exercised"
        _assert_parity(artifact, kernel)


def test_export_deep_arena_degenerate_root(monkeypatch):
    """A deep tree whose root never splits (constant target) is a
    single-leaf arena tree; its export must return the root's leaf value,
    not an unallocated zero slot."""
    monkeypatch.setenv("CS230_TREE_DEEP_N", "200")
    X = np.random.RandomState(0).randn(600, 6).astype(np.float32)
    data = TrialData(X=X, y=np.full(600, 7.0, np.float32), n_classes=0)
    artifact, kernel = _fit_artifact("DecisionTreeRegressor", data, {})
    assert artifact["static"].get("_deep")
    est = to_sklearn(artifact)
    preds = est.predict(_XQ.astype(np.float64))
    assert np.allclose(preds, 7.0), preds[:5]


def test_svc_public_attr_sign_convention():
    """sklearn negates dual_coef_/intercept_ vs the libsvm internals for
    binary models only; users reading the public attrs of the export must
    see what a genuinely fitted SVC exposes."""
    from sklearn.svm import SVC

    for kind in ["cls2", "cls3"]:
        data = _data(kind)
        artifact, _ = _fit_artifact("SVC", data, {"C": 1.0})
        est = to_sklearn(artifact)
        sk = SVC().fit(np.asarray(data.X, np.float64), np.asarray(data.y))
        # conventions, not values: public == -internal iff binary
        sign = -1.0 if kind == "cls2" else 1.0
        assert np.allclose(est.dual_coef_, sign * est._dual_coef_)
        assert np.allclose(est.intercept_, sign * est._intercept_)
        assert np.allclose(sk.dual_coef_, sign * sk._dual_coef_)
        assert np.allclose(sk.intercept_, sign * sk._intercept_)


def test_export_nystrom_svm(monkeypatch):
    """Large-n SVC/SVR use the Nystrom primal; binary SVC and SVR export as
    Pipeline(Nystroem, linear head); multiclass Nystrom is the one
    unrepresentable case and must raise, not silently mispredict."""
    import cs230_distributed_machine_learning_tpu.models.svm as svm_mod

    monkeypatch.setattr(svm_mod, "_MAX_N", 400)
    art_c2, k_c2 = _fit_artifact("SVC", _data("cls2", seed=1, n=600), {"C": 1.0})
    assert art_c2["static"].get("_nystrom"), "nystrom path not exercised"
    est = _assert_parity(art_c2, k_c2)
    assert hasattr(est, "steps")  # Pipeline(Nystroem -> LinearSVC)

    art_r, k_r = _fit_artifact("SVR", _data("reg", n=600), {"C": 1.0})
    _assert_parity(art_r, k_r)

    art_c3, _ = _fit_artifact("SVC", _data("cls3", seed=1, n=600), {"C": 1.0})
    with pytest.raises(NotImplementedError, match="predict_with_artifact"):
        to_sklearn(art_c3)


def test_load_best_model_end_to_end():
    """The client flow: train -> download -> load as sklearn -> predict."""
    from sklearn.ensemble import RandomForestClassifier
    from sklearn.model_selection import GridSearchCV

    from cs230_distributed_machine_learning_tpu import MLTaskManager

    m = MLTaskManager()
    status = m.train(
        GridSearchCV(
            RandomForestClassifier(n_estimators=10, random_state=0),
            {"max_depth": [3, 5]},
            cv=3,
        ),
        "iris",
        {"random_state": 0},
        show_progress=False,
    )
    assert status["job_status"] == "completed"
    est = m.load_best_model()
    assert type(est).__name__ == "RandomForestClassifier"
    from sklearn.datasets import load_iris

    X, y = load_iris(return_X_y=True)
    acc = float(np.mean(est.predict(X) == y))
    assert acc > 0.9
    # raw artifact form still available
    art = m.load_best_model(as_sklearn=False)
    assert art["model_type"] == "RandomForestClassifier"


def test_export_roundtrips_sklearn_pickle():
    """The exported estimator survives pickle (the reference's wire
    format) and still predicts identically."""
    import pickle

    artifact, kernel = _fit_artifact("GradientBoostingClassifier", _data("cls3"),
                                     {"n_estimators": 5})
    est = to_sklearn(artifact)
    est2 = pickle.loads(pickle.dumps(est))
    a = est.predict(_XQ.astype(np.float64))
    b = est2.predict(_XQ.astype(np.float64))
    assert np.array_equal(a, b)


def test_export_svc_fitted_on_its_training_rows():
    """A fit over the split's training rows (``_fit_rows``, the plan's
    largest split: this split's rows and pad rows of weight 0) exports to an
    sklearn SVC that predicts what the program does; its dispatch says the
    rows and the Gram it streamed, and counts the lane as ``train``."""
    import jax.numpy as jnp

    from cs230_distributed_machine_learning_tpu.obs import REGISTRY
    from cs230_distributed_machine_learning_tpu.parallel import trial_map

    data = _data("cls3")
    kernel = get_kernel("SVC")
    # folds train on 240 rows, the holdout on 225
    plan = build_split_plan(np.asarray(data.y), task="classification", n_folds=5,
                            test_size=0.25, random_state=42)
    params = {"C": 1.0}
    static_key, hyper = kernel.canonicalize(params)
    static = trial_map._resolved_static(kernel, static_key, 300, 6, 3, None, plan)
    rows = static["_fit_rows"]
    assert rows == plan.fit_rows < 300
    w = plan.train_w[0]
    assert int(w.sum()) < rows  # the holdout split is padded
    fitted = kernel.fit(jnp.asarray(data.X), jnp.asarray(data.y), jnp.asarray(w),
                        {k: jnp.asarray(v, jnp.float32) for k, v in hyper.items()}, static)
    fitted = {k: np.asarray(v) for k, v in fitted.items()}
    assert fitted["X"].shape == (rows, 6) and fitted["dual"].shape == (3, rows)
    artifact = {"model_type": "SVC", "parameters": params, "static": static,
                "fitted_params": fitted}
    _assert_parity(artifact, kernel)

    lanes = REGISTRY.counter("tpuml_svm_exact_lanes_total")
    before = {r: lanes.value(rows=r) for r in ("train", "table")}
    steps = np.full((1, 2), 600, np.int32)
    said = kernel.dispatch_result(static, 300, {"ascent_steps": steps}, 1)
    assert said["svm.fit_rows"] == rows and said["svm.gram_bytes"] == rows * rows * 2
    assert said["svm.steps_sum"] == 1200 and said["svm.solver"] == "exact"
    assert lanes.value(rows="train") == before["train"] + 2
    table = {k: v for k, v in static.items() if k != "_fit_rows"}
    said = kernel.dispatch_result(table, 300, {"ascent_steps": steps}, 1)
    assert said["svm.fit_rows"] == 300 and said["svm.gram_bytes"] == 300 * 300 * 2
    assert lanes.value(rows="table") == before["table"] + 2

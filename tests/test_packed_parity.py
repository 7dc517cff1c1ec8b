"""Transfer layer: packed single-fetch outputs + compressed staging.

The trial executables concatenate every result leaf into ONE flat byte
buffer on device (trial_map._pack_wrap) so a job's results cross the
host<->device boundary in a single transfer — the per-leaf path pays one
round trip PER LEAF (the cost floor of tiny jobs). Packing is a bitcast, so the packed path must be BITWISE identical
to the per-leaf path; compressed staging (CS230_STAGE_DTYPE=bf16) trades
upload bytes for a documented score tolerance.
"""

import os

import numpy as np
import pytest
from sklearn.datasets import load_iris

from cs230_distributed_machine_learning_tpu.models.base import TrialData
from cs230_distributed_machine_learning_tpu.models.registry import get_kernel
from cs230_distributed_machine_learning_tpu.ops.folds import build_split_plan
from cs230_distributed_machine_learning_tpu.parallel import trial_map
from cs230_distributed_machine_learning_tpu.parallel.trial_map import run_trials


def _cls_data():
    X, y = load_iris(return_X_y=True)
    return TrialData(X=X.astype(np.float32), y=y.astype(np.int32), n_classes=3)


def _reg_data():
    rng = np.random.RandomState(0)
    X = rng.randn(200, 6).astype(np.float32)
    y = (X @ rng.randn(6) + 0.1 * rng.randn(200)).astype(np.float32)
    return TrialData(X=X, y=y, n_classes=0)


def _run(kname, data, plan, params):
    return run_trials(get_kernel(kname), data, plan, params)


@pytest.fixture
def _transfer_env(monkeypatch):
    """Isolate the transfer-layer env knobs and the in-process executable
    cache (the knobs change executable signatures, so cached entries from
    other tests must not leak across flag flips)."""
    saved = dict(trial_map._compiled_cache)
    trial_map._compiled_cache.clear()
    yield monkeypatch
    trial_map._compiled_cache.clear()
    trial_map._compiled_cache.update(saved)


#: >= 3 model families across the engine's dispatch paths: generic vmap
#: (LogReg), generic regression with a 2-leaf result dict (Ridge), a
#: closed-form family (GaussianNB), and the chunked-fit protocol (RF)
_FAMILIES = [
    ("GaussianNB", "cls", [{}]),
    ("LogisticRegression", "cls", [{"C": c} for c in (0.1, 1.0)]),
    ("Ridge", "reg", [{"alpha": a} for a in (0.1, 1.0)]),
    ("RandomForestClassifier", "cls", [{"n_estimators": 8, "max_depth": 3}]),
]


def test_packed_results_bitwise_identical_to_per_leaf(_transfer_env):
    monkeypatch = _transfer_env
    cls_data, reg_data = _cls_data(), _reg_data()
    cls_plan = build_split_plan(
        np.asarray(cls_data.y), task="classification", n_folds=3
    )
    reg_plan = build_split_plan(
        np.asarray(reg_data.y), task="regression", n_folds=3
    )

    monkeypatch.setenv("CS230_PACKED_FETCH", "1")
    packed = {}
    for kname, kind, params in _FAMILIES:
        data, plan = (cls_data, cls_plan) if kind == "cls" else (reg_data, reg_plan)
        packed[kname] = _run(kname, data, plan, params)

    monkeypatch.setenv("CS230_PACKED_FETCH", "0")
    trial_map._compiled_cache.clear()
    for kname, kind, params in _FAMILIES:
        data, plan = (cls_data, cls_plan) if kind == "cls" else (reg_data, reg_plan)
        perleaf = _run(kname, data, plan, params)
        for mp, ml in zip(packed[kname].trial_metrics, perleaf.trial_metrics):
            assert set(mp) == set(ml), kname
            for key in mp:
                # BITWISE: packing is a bitcast, not a numeric conversion
                assert mp[key] == ml[key], (kname, key, mp[key], ml[key])


def test_packed_path_fetches_once_per_job(_transfer_env):
    """The observable the whole layer exists for: ONE blocking device->host
    transfer for a whole tiny job (the per-leaf path pays one per leaf)."""
    monkeypatch = _transfer_env
    monkeypatch.setenv("CS230_PACKED_FETCH", "1")
    data = _cls_data()
    plan = build_split_plan(np.asarray(data.y), task="classification", n_folds=3)
    out = _run("GaussianNB", data, plan, [{}])
    assert out.n_host_fetches == 1
    assert out.result_bytes > 0

    # Ridge's result dict has 2 leaves (score, mse): still one fetch packed
    reg = _reg_data()
    rplan = build_split_plan(np.asarray(reg.y), task="regression", n_folds=3)
    out = _run("Ridge", reg, rplan, [{"alpha": 1.0}])
    assert out.n_host_fetches == 1

    monkeypatch.setenv("CS230_PACKED_FETCH", "0")
    trial_map._compiled_cache.clear()
    out = _run("Ridge", reg, rplan, [{"alpha": 1.0}])
    assert out.n_host_fetches == 2  # one per leaf


#: bf16 has ~8 relative-precision bits: fold scores over iris-scale data
#: stay within this of the f32 staging (documented in docs/API.md)
_BF16_SCORE_TOL = 5e-3


def test_bf16_staging_within_documented_tolerance(_transfer_env):
    monkeypatch = _transfer_env
    data = _cls_data()
    plan = build_split_plan(np.asarray(data.y), task="classification", n_folds=3)
    params = [{"C": c} for c in (0.1, 1.0)]

    monkeypatch.setenv("CS230_STAGE_DTYPE", "f32")
    base = _run("LogisticRegression", data, plan, params)

    monkeypatch.setenv("CS230_STAGE_DTYPE", "bf16")
    trial_map._compiled_cache.clear()
    bf16 = _run("LogisticRegression", data, plan, params)

    for mb, mf in zip(bf16.trial_metrics, base.trial_metrics):
        assert abs(mb["mean_cv_score"] - mf["mean_cv_score"]) <= _BF16_SCORE_TOL
        assert abs(mb["accuracy"] - mf["accuracy"]) <= _BF16_SCORE_TOL

    # the staged device copy really is narrow: the upload was the point.
    # Staged entries live in the multi-tenant stage cache by default
    # (data/stage_cache.py) and on the TrialData object under
    # CS230_STAGE_CACHE=0 — check whichever holds them.
    from cs230_distributed_machine_learning_tpu.data import stage_cache as sc

    keys = list(getattr(data, "_device_cache", None) or {})
    if sc.enabled():
        keys += sc.STAGE_CACHE.keys()
    bf16_entries = [k for k in keys if "bf16" in k]
    assert bf16_entries, keys


def test_int8_staging_scores_close_to_f32(_transfer_env):
    monkeypatch = _transfer_env
    data = _cls_data()
    plan = build_split_plan(np.asarray(data.y), task="classification", n_folds=3)

    monkeypatch.setenv("CS230_STAGE_DTYPE", "f32")
    base = _run("LogisticRegression", data, plan, [{"C": 1.0}])

    monkeypatch.setenv("CS230_STAGE_DTYPE", "int8")
    trial_map._compiled_cache.clear()
    q = _run("LogisticRegression", data, plan, [{"C": 1.0}])
    # int8 is lossier than bf16 (per-column affine grid): looser bound
    assert abs(
        q.trial_metrics[0]["mean_cv_score"] - base.trial_metrics[0]["mean_cv_score"]
    ) <= 2e-2


def test_stage_compress_decode_roundtrip_shapes():
    """Host-side compress + traced decode invert to the matrix shape/dtype
    (values to the staging dtype's precision)."""
    import jax

    rng = np.random.RandomState(1)
    X = (rng.randn(32, 5) * 3).astype(np.float32)
    for mode, tol in (("bf16", 3e-2), ("int8", 6e-2)):
        comp = trial_map._stage_compress(X, mode)
        dec = np.asarray(jax.jit(trial_map._stage_decode)(
            jax.tree_util.tree_map(np.asarray, comp)
        ))
        assert dec.shape == X.shape and dec.dtype == np.float32
        scale = np.abs(X).max()
        assert np.max(np.abs(dec - X)) <= tol * scale

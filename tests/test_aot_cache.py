"""AOT executable cache (utils/aot_cache.py): round-trip, invalidation,
fallback. Runs on the CPU backend (conftest) — the cache is platform-keyed,
so these entries never collide with TPU blobs."""

import os
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from cs230_distributed_machine_learning_tpu.utils import aot_cache


def _blobs(root):
    return sorted(Path(root).rglob("*.jaxexport"))


@pytest.fixture()
def tmp_aot_dir(tmp_path, monkeypatch):
    monkeypatch.setenv("CS230_AOT_DIR", str(tmp_path))
    # the cache defaults OFF on the CPU backend (tracing is the cheap part
    # there); force it on so the round-trip machinery itself stays covered
    monkeypatch.setenv("CS230_AOT_CACHE", "force")
    return tmp_path


def _fn(x, h):
    return {"score": jnp.tanh(x @ x.T).sum() * h["c"]}


def _example():
    return (
        jnp.ones((8, 8), jnp.float32),
        {"c": jnp.asarray(2.0, jnp.float32)},
    )


def test_cold_then_warm_round_trip(tmp_aot_dir):
    key = ("test", "round_trip", 8)
    fn1, src1 = aot_cache.aot_jit(_fn, key, _example())
    assert src1 == "traced"
    out1 = fn1(*_example())
    assert len(_blobs(tmp_aot_dir)) == 1

    fn2, src2 = aot_cache.aot_jit(_fn, key, _example())
    assert src2 == "aot"
    out2 = fn2(*_example())
    np.testing.assert_allclose(np.asarray(out1["score"]), np.asarray(out2["score"]))


def test_distinct_keys_distinct_blobs(tmp_aot_dir):
    aot_cache.aot_jit(_fn, ("a",), _example())
    aot_cache.aot_jit(_fn, ("b",), _example())
    assert len(_blobs(tmp_aot_dir)) == 2


def test_corrupt_blob_falls_back_and_heals(tmp_aot_dir):
    key = ("test", "corrupt")
    aot_cache.aot_jit(_fn, key, _example())
    (blob,) = _blobs(tmp_aot_dir)
    blob.write_bytes(b"not a serialized module")
    fn, src = aot_cache.aot_jit(_fn, key, _example())
    assert src == "traced"  # corrupt entry dropped, re-traced
    out = fn(*_example())
    assert np.isfinite(float(out["score"]))
    # re-written: next load hits
    _, src2 = aot_cache.aot_jit(_fn, key, _example())
    assert src2 == "aot"


def test_lowering_error_is_the_one_error(tmp_aot_dir):
    """A program the compiler refuses must raise out of aot_jit with the
    compiler's own message — not come back as a plain jit that fails again,
    elsewhere, on first call (a bad Pallas block shape ended a chip job
    with two unrelated tracebacks)."""
    from jax.experimental import pallas as pl

    def refused(x):  # a compiled Pallas call cannot lower on the CPU backend
        def body(x_ref, o_ref):
            o_ref[:] = x_ref[:] * 2.0

        return pl.pallas_call(
            body, out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype)
        )(x)

    with pytest.raises(ValueError, match="interpret mode"):
        aot_cache.aot_jit(refused, ("refused",), (jnp.ones((8, 128)),))
    assert len(_blobs(tmp_aot_dir)) == 0


def test_disabled_by_env(tmp_aot_dir, monkeypatch):
    monkeypatch.setenv("CS230_AOT_CACHE", "0")
    _, src = aot_cache.aot_jit(_fn, ("off",), _example())
    assert src == "traced"
    assert len(_blobs(tmp_aot_dir)) == 0


def test_engine_results_stable_across_aot_reload(tmp_aot_dir):
    """run_trials twice in-process with a fresh AOT dir: the second bucket
    build deserializes and must produce identical metrics."""
    from cs230_distributed_machine_learning_tpu.models.base import TrialData
    from cs230_distributed_machine_learning_tpu.models.registry import get_kernel
    from cs230_distributed_machine_learning_tpu.ops.folds import build_split_plan
    from cs230_distributed_machine_learning_tpu.parallel import trial_map

    rng = np.random.RandomState(0)
    X = rng.randn(64, 6).astype(np.float32)
    y = (X[:, 0] + X[:, 1] > 0).astype(np.int32)
    data = TrialData(X=X, y=y, n_classes=2)
    plan = build_split_plan(y, task="classification", n_folds=3)
    kernel = get_kernel("LogisticRegression")
    params = [{"C": 0.5}, {"C": 2.0}]

    def scores():
        trial_map._compiled_cache.clear()
        run = trial_map.run_trials(kernel, data, plan, params)
        return [m["mean_cv_score"] for m in run.trial_metrics]

    first = scores()
    second = scores()  # in-process cache cleared -> hits the AOT blob
    np.testing.assert_allclose(first, second, rtol=1e-6)

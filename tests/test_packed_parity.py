"""Transfer layer: packed single-fetch outputs.

A one-device trial executable concatenates every result leaf into ONE flat
byte buffer on device (packing.pack_wrap) so a job's results cross the
host<->device boundary in a single transfer; a per-leaf result pays one
round trip PER LEAF (the cost floor of tiny jobs). Packing is a bitcast,
so the unpacked result must be BITWISE identical to the function's own.
"""

import numpy as np
import pytest
from sklearn.datasets import load_iris

from cs230_distributed_machine_learning_tpu.models.base import TrialData
from cs230_distributed_machine_learning_tpu.models.registry import get_kernel
from cs230_distributed_machine_learning_tpu.ops.folds import build_split_plan
from cs230_distributed_machine_learning_tpu.parallel import packing, trial_map
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
def _fresh_executables():
    """Isolate the in-process executable cache."""
    saved = dict(trial_map._compiled_cache)
    trial_map._compiled_cache.clear()
    yield
    trial_map._compiled_cache.clear()
    trial_map._compiled_cache.update(saved)


#: families of the generic vmapped engine: a classifier with traced hypers
#: (LogReg), a regressor with a 2-leaf result dict (Ridge), a closed-form
#: family without hypers that matter (GaussianNB)
_FAMILIES = [
    ("GaussianNB", "cls", [{}]),
    ("LogisticRegression", "cls", [{"C": c} for c in (0.1, 1.0)]),
    ("Ridge", "reg", [{"alpha": a} for a in (0.1, 1.0)]),
]


@pytest.mark.parametrize("kname,kind,params", _FAMILIES,
                         ids=[f[0] for f in _FAMILIES])
def test_packed_results_bitwise_identical_to_per_leaf(kname, kind, params):
    """unpack(pack(fn)(args)) == fn(args), bit for bit, for the real
    vmapped program of a kernel: the reference is the function itself."""
    import jax
    import jax.numpy as jnp

    data = _cls_data() if kind == "cls" else _reg_data()
    plan = build_split_plan(
        np.asarray(data.y),
        task="classification" if kind == "cls" else "regression", n_folds=3,
    )
    kernel = get_kernel(kname)
    n, d = data.X.shape
    keys, hypers = zip(*(kernel.canonicalize(p) for p in params))
    static = trial_map._resolved_static(kernel, keys[0], n, d, data.n_classes)
    if hasattr(kernel, "bucket_static"):
        static = kernel.bucket_static(static, list(hypers))
    names = sorted(hypers[0])
    hyper = {k: jnp.asarray([h[k] for h in hypers], jnp.float32) for k in names}
    if not names:
        hyper = {"_pad": jnp.zeros((len(params),), jnp.float32)}
    fn = trial_map._make_batched(kernel, static, bool(names))
    args = (jnp.asarray(data.X), jnp.asarray(data.y),
            jnp.asarray(plan.train_w), jnp.asarray(plan.eval_w), hyper)

    per_leaf = jax.jit(fn)(*args)
    spec = packing.pack_spec_of(fn, args)
    buf = jax.jit(packing.pack_wrap(fn))(*args)
    assert buf.dtype == jnp.uint8 and buf.ndim == 1
    unpacked = packing.unpack(np.asarray(buf), spec)

    assert set(unpacked) == set(per_leaf), kname
    for key, leaf in per_leaf.items():
        leaf = np.asarray(leaf)
        assert unpacked[key].dtype == leaf.dtype and unpacked[key].shape == leaf.shape
        # BITWISE: packing is a bitcast, not a numeric conversion
        assert unpacked[key].tobytes() == leaf.tobytes(), (kname, key)


def test_packed_path_fetches_once_per_job(_fresh_executables):
    """The observable the whole layer exists for: ONE blocking device->host
    transfer for a whole tiny one-device job, however many leaves."""
    data = _cls_data()
    plan = build_split_plan(np.asarray(data.y), task="classification", n_folds=3)
    out = _run("GaussianNB", data, plan, [{}])
    assert out.n_host_fetches == 1
    assert out.result_bytes > 0

    # Ridge's result dict has 2 leaves (score, mse): still one fetch
    reg = _reg_data()
    rplan = build_split_plan(np.asarray(reg.y), task="regression", n_folds=3)
    out = _run("Ridge", reg, rplan, [{"alpha": 1.0}])
    assert out.n_host_fetches == 1

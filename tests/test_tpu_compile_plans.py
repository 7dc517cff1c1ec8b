"""The chunked plans the corrected lane estimate admits, compiled for a v5e
with no chip attached (PR 34, review 1).

``memory_estimate_mb``'s complete-builder branch fell thirtyfold when the
[n, m] routing forms were read out of it, and ``plan_bucket`` fills half a
chip by it. Two plans it now makes that no run had carried: the boosting
cell's shape at the memory cap (21 trials, 126 lanes), and a forest at a set
``max_depth`` (the same branch, k + 1 integer stat columns) at its cap. Each
step program must compile, and hold what the plan says beside the states
the plan lets the host enqueue ahead.
"""

import jax
import jax.numpy as jnp
import pytest

from cs230_distributed_machine_learning_tpu.models.registry import get_kernel
from cs230_distributed_machine_learning_tpu.parallel import trial_map

from test_tpu_compile import S, _lower_and_compile, _sds, tpu_backend  # noqa: F401

V5E_MB = 16_900.0  # one chip's bytes_limit

#: (case, estimator, params, (n, d, classes), trials asked, chunk the plan gives)
_PLANS = [
    ("boost_higgs_at_the_cap", "GradientBoostingClassifier",
     {"n_estimators": 32, "max_depth": 8, "random_state": 0}, (1_000_000, 28, 2), 30, 21),
    ("forest_covertype_depth_8_at_the_cap", "RandomForestClassifier",
     {"n_estimators": 100, "max_depth": 8, "random_state": 0}, (581_012, 54, 7), 30, 10),
]


@pytest.mark.parametrize("estimator,params,shape,n_trials,chunk",
                         [p[1:] for p in _PLANS], ids=[p[0] for p in _PLANS])
def test_the_plan_at_the_memory_cap_compiles_and_fits(tpu_backend, monkeypatch, estimator, params,
                                                      shape, n_trials, chunk):
    n, d, c = shape
    monkeypatch.setattr(trial_map._backend, "device_memory_mb", lambda: V5E_MB)
    kernel = get_kernel(estimator)
    static_key, hyper = kernel.canonicalize(params)
    static = trial_map._resolved_static(kernel, static_key, n, d, c)
    X = {"X": _sds((n, d), jnp.float32), "xb": _sds((n, d), jnp.int32),
         "edges": _sds((d, 127), jnp.float32)}
    plan = trial_map.plan_bucket(kernel, static, [dict(hyper)] * n_trials, X,
                                 n=n, d=d, n_classes=c, n_splits=S)
    assert (plan.engine, plan.chunk, plan.split_width) == ("chunked", chunk, None)
    static, chunk_plan = plan.static, plan.chunk_plan
    hy = {k: _sds((chunk,), jnp.float32) for k in plan.hyper_names}
    y, TW = _sds((n,), jnp.int32), _sds((S, n), jnp.float32)

    def init_b(X, y, TW, hyper):
        return jax.vmap(lambda tw: kernel.chunk_init(X, y, tw, hyper, static))(TW)

    def step_b(X, y, TW, hyper, ci, state):
        return jax.vmap(lambda tw, st: kernel.chunk_step(
            X, y, tw, hyper, static, ci, st, chunk_plan))(TW, state)

    if hy:
        vinit = jax.vmap(init_b, in_axes=(None, None, None, 0))
        vstep = jax.vmap(step_b, in_axes=(None, None, None, 0, None, 0))
    else:  # no traced hyper: the trial axis is the state's alone
        vinit = lambda X, y, TW, hyper: jax.vmap(lambda _: init_b(X, y, TW, hyper))(jnp.arange(chunk))  # noqa: E731
        vstep = jax.vmap(step_b, in_axes=(None, None, None, None, None, 0))
    state = jax.eval_shape(vinit, X, y, TW, hy)
    compiled = _lower_and_compile(vstep, X, y, TW, hy, _sds((), jnp.int32), state)
    if compiled is None:
        return  # lowered only: no deviceless topology here
    mem = compiled.memory_analysis()
    lanes = chunk * S
    lane_mb = kernel.memory_estimate_mb(n, d, static)
    # the compiler holds no more a lane than the estimate says (and a fixed
    # 0.2 GB that no lane owns): it was 30 times under the old estimate
    assert mem.temp_size_in_bytes < lanes * lane_mb * 1e6 + 0.2e9, (mem.temp_size_in_bytes / lanes, lane_mb)
    # temporaries, arguments and every copy of the state the plan lets be in
    # flight (the one being read and ``steps_ahead`` more) fit the chip
    # (a state's bytes are the planner's 4 n k a lane, or a tile's padding more)
    in_flight = (plan.steps_ahead + 1) * mem.output_size_in_bytes
    assert in_flight < 0.3 * V5E_MB * 1e6
    assert mem.temp_size_in_bytes + mem.argument_size_in_bytes + in_flight < 0.9 * V5E_MB * 1e6

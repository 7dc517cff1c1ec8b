"""LogisticRegression on a four-device trial mesh at a shape where the
engine picks the Nesterov solver, as the benchmark's four-chip cell does
(``logreg_rows5m_mesh4.rs64c``): 54 features x 7 classes needs more than
4 000 000 / (55 * 7) = 10 390 rows, and the other mesh tests run 160 rows,
which is the Newton solver.

The mesh run is held to the same trials on one device (the sharding is an
execution detail) and to a plain float32 fit written here (the solver's
published semantics: Nesterov momentum t / (t + 3), step 1 / L with L from
a 30-step power iteration, stop once max|G| < tol), per split, scores and
``gmax`` learning curves. A CPU run proves results and counts, no time.
"""

import numpy as np
import pytest

import jax

from cs230_distributed_machine_learning_tpu.data import stage_cache
from cs230_distributed_machine_learning_tpu.models.base import TrialData
from cs230_distributed_machine_learning_tpu.models.registry import get_kernel
from cs230_distributed_machine_learning_tpu.obs import REGISTRY, TRACER, span
from cs230_distributed_machine_learning_tpu.ops.folds import build_split_plan
from cs230_distributed_machine_learning_tpu.parallel import trial_map
from cs230_distributed_machine_learning_tpu.parallel.mesh import trial_mesh

N, D, C_CLASSES, STEPS = 10_500, 54, 7, 30


@pytest.fixture(scope="module")
def covertype_like():
    rng = np.random.RandomState(7)
    centers = rng.randn(C_CLASSES, D).astype(np.float32)
    y = rng.choice(C_CLASSES, size=N, p=[0.36, 0.49, 0.06, 0.01, 0.02, 0.03, 0.03]).astype(np.int32)
    X = (0.6 * centers[y] + rng.randn(N, D)).astype(np.float32)
    data = TrialData(X=X, y=y, n_classes=C_CLASSES)
    plan = build_split_plan(y, task="classification", n_folds=3, random_state=42)
    kernel = get_kernel("LogisticRegression")
    static = kernel.resolve_static(kernel.static_from_key(kernel.canonicalize({})[0]), N, D, C_CLASSES)
    assert static["_method"] == "nesterov"  # the solver the cell runs
    return data, plan


def _trials(n):
    rng = np.random.RandomState(11)
    return [{"C": float(10 ** rng.uniform(-4, 1)), "tol": [1e-4, 1e-3][i % 2], "max_iter": STEPS}
            for i in range(n)]


def _plain_fit(X, y, tw, ew, C, tol, max_iter):
    """One (trial, split) in float32 numpy: (held-out accuracy, gmax at every step)."""
    A = np.concatenate([X, np.ones((len(X), 1), np.float32)], axis=1)
    Y = np.eye(C_CLASSES, dtype=np.float32)[y]
    pen = np.ones((D + 1, 1), np.float32)
    pen[-1] = 0.0  # the intercept is not penalised
    v = np.ones(D + 1, np.float32)
    for _ in range(30):
        u = A.T @ (tw * (A @ v))
        v = u / max(np.linalg.norm(u), 1e-12)
    L = 0.5 * C * float(v @ (A.T @ (tw * (A @ v)))) + 1.0 + 1e-6
    W = Wp = np.zeros((D + 1, C_CLASSES), np.float32)
    done, gmax = False, []
    for t in range(STEPS):
        V = W + np.float32(t / (t + 3.0)) * (W - Wp)
        Z = A @ V
        P = np.exp(Z - Z.max(axis=1, keepdims=True))
        P /= P.sum(axis=1, keepdims=True)
        G = np.float32(C) * (A.T @ (tw[:, None] * (P - Y))) + pen * V
        g = float(np.abs(G).max())
        gmax.append(g)
        if t < max_iter and not done:
            W, Wp = V - G / np.float32(L), W
        done = done or g < tol
    hit = (np.argmax(A @ W, axis=1) == y).astype(np.float32)
    return float((hit * ew).sum() / ew.sum()), np.asarray(gmax)


def _scores_and_curves(run):
    scores = np.asarray([[m["accuracy"]] + m["cv_scores"] for m in run.trial_metrics])
    curves = np.asarray([m["curve"]["gmax"] for m in run.trial_metrics], np.float64)
    assert all(m["curve"]["stride"] == 1 for m in run.trial_metrics)
    return scores, curves  # [trials, splits], [trials, splits, steps]


CASES = {  # trials, max_trials_per_batch -> dispatches, padding lanes on four devices
    "divides_the_chunk": (8, 256, 1, 0),
    "leaves_padding_lanes": (6, 256, 1, 2),
    "cap_forces_several_dispatches": (6, 4, 2, 2),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_mesh_fit_matches_one_device_and_a_plain_float32_fit(covertype_like, case):
    data, plan = covertype_like
    n_trials, cap, n_dispatches, n_padding = CASES[case]
    params = _trials(n_trials)
    kernel = get_kernel("LogisticRegression")
    mesh = trial_mesh(jax.devices()[:4])
    padding = REGISTRY.counter("tpuml_mesh_lanes_total")
    padding_before = padding.value(kind="padding")
    on_mesh = trial_map.run_trials(kernel, data, plan, params, mesh=mesh, max_trials_per_batch=cap)
    solo = trial_map.run_trials(kernel, data, plan, params)
    assert on_mesh.n_dispatches == n_dispatches and on_mesh.n_result_devices == 4
    assert padding.value(kind="padding") - padding_before == n_padding
    assert len(on_mesh.trial_metrics) == n_trials  # padding lanes are dropped
    s_mesh, c_mesh = _scores_and_curves(on_mesh)
    s_solo, c_solo = _scores_and_curves(solo)
    # one formulation, two partitionings: the same arithmetic on every row,
    # summed in another order. A float32 sum that differs in its last bits
    # can round a bfloat16 matmul operand of the next step the other way,
    # 2**-8 = 0.4% of that operand, and late slots are differences of nearly
    # equal sums: 0.6% was read between chunks of 4 and of 6, so 2%.
    np.testing.assert_allclose(s_mesh, s_solo, atol=1e-3)  # two held-out rows of 2 100
    np.testing.assert_allclose(c_mesh, c_solo, rtol=2e-2, atol=1e-5)
    # the collective argmax names the trial the host's arithmetic names
    means = s_mesh[:, 1:].mean(axis=1)
    assert on_mesh.device_best[1] == pytest.approx(means.max(), abs=1e-6)

    # against the plain fit, two trials (the first and the last, which sits
    # in the padded chunk) on every split. The program rounds its matmul
    # operands to bfloat16 (8 significant bits, 0.4% an operand); the gradient
    # sums 10 500 such products, so its largest entry moves by a few parts
    # in a thousand while it is large and by more once the fit has nearly
    # converged and the entry is a difference of nearly equal sums: the
    # curve is held to 3% of its start, the score to 1% (21 held-out rows).
    for j in (0, n_trials - 1):
        for s in range(plan.n_splits):
            score, gmax = _plain_fit(data.X, data.y, plan.train_w[s], plan.eval_w[s],
                                     params[j]["C"], params[j]["tol"], params[j]["max_iter"])
            assert abs(s_mesh[j, s] - score) < 0.01, (j, s, s_mesh[j, s], score)
            assert np.abs(c_mesh[j, s] - gmax).max() < 0.03 * gmax[0], (j, s)


PACKED_CASES = {  # trials -> trials a weight block, blocks a device, lanes of the
    # chunk, lanes of a class slab at the fixture's four splits and its dead ones
    "sixteen_a_chip": (64, 16, 1, 64, 128, 64),
    "ten_dealt_3_3_2_2": (10, 16, 1, 64, 128, 64),
    "two_blocks_a_chip": (520, 128, 2, 1024, 512, 0),
}


@pytest.mark.parametrize("case", sorted(PACKED_CASES))
def test_packed_fit_on_every_chip_matches_one_device_and_a_plain_float32_fit(
        covertype_like, monkeypatch, case):
    """The kernel's packed fit (``build_batched_fn``, the Pallas step
    interpreted) per device under ``shard_map``: the same trials on one
    device give the same scores and curves, whatever block the share got
    and whichever device and slot a trial was dealt to."""
    monkeypatch.setenv("CS230_PALLAS_INTERPRET", "1")
    data, plan = covertype_like
    n_trials, block, blocks, lanes, slab, slab_pad = PACKED_CASES[case]
    assert plan.n_splits * block + slab_pad == slab
    params = _trials(n_trials)
    kernel = get_kernel("LogisticRegression")
    mesh = trial_mesh(jax.devices()[:4])
    engines = REGISTRY.counter("tpuml_engine_dispatch_total")
    packed_before = engines.value(engine="packed", mesh="1d")
    with span("test.batch") as root:
        on_mesh = trial_map.run_trials(kernel, data, plan, params, mesh=mesh)
    solo = trial_map.run_trials(kernel, data, plan, params)
    assert on_mesh.n_dispatches == 1 and on_mesh.n_result_devices == 4
    assert engines.value(engine="packed", mesh="1d") - packed_before == 1
    (dispatch,) = [s["attrs"] for s in TRACER.spans_for(root.trace_id)
                   if s["name"] == "executor.dispatch"]
    # a block of 128 skips the (row tile, split) groups the replicated
    # occupancy table marks empty; the narrower blocks run the whole slab
    n_pad = -(-data.X.shape[0] // 2048) * 2048
    tiles = np.pad(np.asarray(plan.train_w), ((0, 0), (0, n_pad - data.X.shape[0])))
    occupied = (tiles.reshape(plan.n_splits, n_pad // 256, 256) != 0).any(axis=2)
    skip = 100.0 * (1 - occupied.mean()) if block == 128 else 0.0
    assert dispatch == {"engine": "packed", "block": block, "blocks": blocks, "chunk": 0,
                        "n_devices": 4, "n_trials": n_trials, "lanes": lanes,
                        "lanes_padding": lanes - n_trials,
                        "slab_lanes": slab, "slab_pad_lanes": slab_pad,
                        "tile_skip_pct": pytest.approx(skip)}
    assert (skip > 0) == (block == 128)  # the padded tail tiles, at least
    assert lanes == 4 * blocks * block  # every device a whole number of blocks
    assert len(on_mesh.trial_metrics) == n_trials  # padding lanes are dropped
    s_mesh, c_mesh = _scores_and_curves(on_mesh)
    s_solo, c_solo = _scores_and_curves(solo)
    # a lane's arithmetic does not depend on its block's width or its neighbours
    np.testing.assert_allclose(s_mesh, s_solo, atol=1e-6)
    np.testing.assert_allclose(c_mesh, c_solo, rtol=1e-6, atol=1e-9)
    # the best entry is a real trial, the one the host's arithmetic names
    means = s_mesh[:, 1:].mean(axis=1)
    best, best_score = on_mesh.device_best
    assert 0 <= best < n_trials and best_score == pytest.approx(means.max(), abs=1e-6)
    assert means[best] == pytest.approx(means.max(), abs=1e-6)
    # against the plain fit as above: the first trial and the last, which is
    # dealt to another device and a later slot
    for j in (0, n_trials - 1):
        for s in range(plan.n_splits):
            score, gmax = _plain_fit(data.X, data.y, plan.train_w[s], plan.eval_w[s],
                                     params[j]["C"], params[j]["tol"], params[j]["max_iter"])
            assert abs(s_mesh[j, s] - score) < 0.01, (j, s, s_mesh[j, s], score)
            assert np.abs(c_mesh[j, s] - gmax).max() < 0.03 * gmax[0], (j, s)


def test_lanes_are_dealt_round_robin_and_come_back_in_host_order():
    """Ten real trials in a chunk of 128 on four devices: 3, 3, 2, 2 of them
    a device, each device's padding its own, and the result in host order."""
    import jax.numpy as jnp

    n_dev, dev_chunk, n_real = 4, 32, 10
    seen = {}

    def per_device_view(X, y, TW, EW, hyper):
        seen["lanes"] = hyper["lane"]  # in the order P("trials") would split it
        return {"score": jnp.stack([hyper["lane"], hyper["lane"] + 0.5], axis=1)}

    lane = jnp.arange(n_dev * dev_chunk, dtype=jnp.float32)
    out = trial_map._deal_lanes(per_device_view, n_dev, dev_chunk, {"lane"})(
        None, None, None, None, {"lane": lane, "_extra": jnp.zeros(3)})
    per_device = np.asarray(seen["lanes"]).reshape(n_dev, dev_chunk)
    assert [(row < n_real).sum() for row in per_device] == [3, 3, 2, 2]
    for k, row in enumerate(per_device):
        assert list(row) == list(range(k, n_dev * dev_chunk, n_dev))  # slot j // n_dev
    np.testing.assert_array_equal(np.asarray(out["score"])[:, 0], np.asarray(lane))


def test_dispatch_spans_and_lane_counters_read_what_the_dispatches_did(covertype_like):
    data, plan = covertype_like
    kernel = get_kernel("LogisticRegression")
    mesh = trial_mesh(jax.devices()[:4])
    lanes = REGISTRY.counter("tpuml_mesh_lanes_total")
    replicated = REGISTRY.counter("tpuml_mesh_replicated_bytes_total")
    before = {k: lanes.value(kind=k) for k in ("real", "padding")}
    stage_cache.STAGE_CACHE.clear()  # whatever an earlier test staged of this dataset
    rep_before = replicated.value()
    trial_map.run_trials(kernel, data, plan, _trials(6), mesh=mesh, max_trials_per_batch=4)  # stages
    rep_cold = replicated.value() - rep_before
    with span("test.batch") as root:
        trial_map.run_trials(kernel, data, plan, _trials(6), mesh=mesh, max_trials_per_batch=4)
    spans = TRACER.spans_for(root.trace_id)
    dispatch = [s["attrs"] for s in spans if s["name"] == "executor.dispatch"]
    assert [(a["engine"], a["chunk"], a["n_trials"], a["lanes"], a["lanes_padding"], a["n_devices"])
            for a in dispatch] == [("generic", 0, 4, 4, 0, 4), ("generic", 1, 2, 4, 2, 4)]
    real = lanes.value(kind="real") - before["real"]
    padding = lanes.value(kind="padding") - before["padding"]
    assert (real, padding) == (12, 4)  # two runs of 6 trials in chunks of 4
    assert real + padding == 2 * sum(a["lanes"] for a in dispatch)
    # the dataset and the fold tensors were replicated once, by the cold run
    x_bytes = data.X.astype(np.float32).nbytes
    fold_bytes = data.y.nbytes + plan.train_w.nbytes + plan.eval_w.nbytes
    assert rep_cold == 3 * (x_bytes + fold_bytes)  # a full copy to each of the other three
    assert replicated.value() - rep_before == rep_cold  # the warm run moved nothing
    stages = [s["attrs"] for s in spans if s["name"] == "executor.stage" and s["attrs"]["what"] == "mesh.repl"]
    assert {a["of"] for a in stages} == {"data", "folds"}
    assert all(a["transport"] == "ici" and a["outcome"] == "hit" and a["ici_bytes"] == 0 for a in stages)
    fetch = [s["attrs"] for s in spans if s["name"] == "executor.fetch" and "n_devices" in s["attrs"]]
    assert len(fetch) == 2 and all(a["n_devices"] == 4 and a["bytes"] > 0 for a in fetch)

"""The boosted-trees search against its plain reference (PR 34).

The cell ``gbt_higgs.rs8`` of the benchmark guards depth-8
``GradientBoostingClassifier`` through the chunked engine. Here, at a size
the CPU compiles in seconds (4000 rows of a HIGGS-shaped table cut to 10
columns, 6 stages of depth 4): the program's split scores through
``run_trials`` on the chunked path and its raw per-row scores (``fit`` +
``_raw_scores``) against ``perfbench/references/
GradientBoostingClassifier.py``, the curve's ``gmax`` against the
reference's, the reference's controls and faults held to "not correct", and
what a boosted bucket says of itself.

The reference is read at ``precision="f32"`` here: the CPU's ``DEFAULT`` dot
keeps its float32 operands whole, where the chip rounds them to bfloat16 and
the reference's default does as the chip. ``min_samples_leaf`` is 10
(hessian units: forty rows or more a child) so that no two candidate splits
cut the same few rows: such exact ties are decided by the last bit of a
prefix sum, which the program's triangular matmul and numpy's running sum
round differently, and one split that falls the other way moves every later
stage (the chip run's parity probe counts those at the cell's size). Near
ties that are not exact are left: of five seeds tried, four agree on all six
(trial, split) fits to 4e-7 a row and one has two fits that part (0.09);
the seed below is one of the four.
"""

import importlib.util
import json
import os
import sys

import numpy as np
import pytest

from cs230_distributed_machine_learning_tpu.models.base import TrialData
from cs230_distributed_machine_learning_tpu.models.registry import get_kernel
from cs230_distributed_machine_learning_tpu.ops.folds import build_split_plan
from cs230_distributed_machine_learning_tpu.parallel import trial_map

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "perfbench")
CELL = "gbt_higgs.rs8"
ROWS, COLUMNS, STAGES, DEPTH, CV, SEED = 4000, {"n_features": 10, "n_low": 7}, 6, 4, 2, 2**31 + 35
FIXED = {"n_estimators": STAGES, "max_depth": DEPTH, "min_samples_leaf": 10, "random_state": 0}
TRIALS = [{**FIXED, "learning_rate": 0.1, "subsample": 1.0},
          {**FIXED, "learning_rate": 0.3, "subsample": 0.7}]
#: |F - F_ref| a row: the same splits, so the same leaves; a leaf's value is
#: a quotient of two float32 sums taken in another order (a few ulps of
#: values up to about 3), added over six stages
F_TOLERANCE = 2e-5


def _load_run():
    if "perfbench_run" in sys.modules:
        return sys.modules["perfbench_run"]
    spec = importlib.util.spec_from_file_location("perfbench_run", os.path.join(BENCH, "run.py"))
    mod = importlib.util.module_from_spec(spec)
    sys.modules["perfbench_run"] = mod
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def toy():
    """The toy table, the program's scores of both trials through the
    chunked engine, its raw scores of one lane, and the reference's."""
    import jax
    import jax.numpy as jnp

    run = _load_run()
    cell = run.load_cell(CELL)
    cell["config"]["dataset"].update(n_samples=ROWS, **COLUMNS)
    X, y = run.make_dataset(cell, SEED)
    family = run.load_module("references/GradientBoostingClassifier.py")
    kernel = get_kernel("GradientBoostingClassifier")
    plan = build_split_plan(y, task="classification", n_folds=CV)
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("CS230_TREE_CHUNK_MACS", "3e9")  # the chunked engine: three chunks of two stages
        trial_map._compiled_cache.clear()
        ran = trial_map.run_trials(kernel, TrialData(X=X, y=y, n_classes=2), plan, TRIALS)
    got = np.asarray([[m["accuracy"]] + list(m["cv_scores"]) for m in ran.trial_metrics])
    splits = (plan.train_w, plan.eval_w)
    static_key, hyper = kernel.canonicalize(TRIALS[1])
    static = trial_map._resolved_static(kernel, static_key, ROWS, X.shape[1], 2)
    Xd = jax.tree_util.tree_map(jnp.asarray, kernel.prepare_data(X, static))
    model = kernel.fit(Xd, jnp.asarray(y), jnp.asarray(plan.train_w[0]),
                       {k: jnp.float32(v) for k, v in hyper.items()}, static)
    F = np.asarray(kernel._raw_scores(model, Xd, static))[:, 1]
    cols = np.ascontiguousarray(family.bin_codes(X, 128).T)
    return {"run": run, "cell": cell, "X": X, "y": y, "family": family, "kernel": kernel,
            "static": static, "Xd": Xd, "plan": plan, "splits": splits, "ran": ran, "got": got,
            "F": F, "cols": cols, "ref": family.reference(X, y, 2, TRIALS, splits, precision="f32")}


def _judge(toy, scores, curves):
    """The score and curve numbers of the comparison of a run, for
    ``scores`` and ``curves`` (a trial's ``curve`` record each) in the
    program's place, against the configuration's own limits."""
    compare = toy["run"].load_module("lib/compare.py")
    gaps = np.abs(np.asarray(scores, np.float64) - toy["ref"]["score"].astype(np.float64))
    curve_gaps = []
    for j, rec in enumerate(curves):
        got, at = compare.curve_rows({"curve": rec}, STAGES)
        curve_gaps.append(compare.curve_gap(got, toy["ref"]["gmax"][j][:, at]))
    numbers = {"score_gap_max": float(gaps.max()), "score_gap_mean": float(gaps.mean()),
               "curve_gap_median": float(np.median(np.concatenate([c.ravel() for c in curve_gaps])))}
    limits = {k: v for k, v in toy["cell"]["config"]["limits"].items() if k in numbers}
    assert set(limits) == set(numbers)
    return compare.judge(numbers, limits)


def _as_curves(gmax):
    """A reference's ``gmax`` [trials, splits, stages] as the curve records
    a program that sampled every second stage would return."""
    return [{"gmax": g[:, 1::2].tolist(), "stride": 2} for g in gmax]


def test_the_chunked_path_scores_what_the_reference_scores(toy):
    assert toy["ran"].n_dispatches > 3  # init + three steps + evals: the chunked protocol
    ref = toy["ref"]["score"]
    assert toy["got"].shape == ref.shape == (2, CV + 1)
    assert 0.55 < ref.min() and ref.max() < 0.85
    # the same trees, so the same predictions: not one held-out row differs
    assert np.abs(toy["got"] - ref).max() < 1e-6
    correct, table = _judge(toy, toy["got"], [m["curve"] for m in toy["ran"].trial_metrics])
    assert correct, table
    # the two trials differ in what they were given, and it shows
    assert np.abs(ref[0] - ref[1]).max() > 0.002


def test_the_curve_holds_the_largest_held_out_residual_stage_by_stage(toy):
    """Three chunks of two stages: the curve's ``gmax`` is read after stages
    2, 4 and 6 (stride and steps count stages, not chunks), beside the score
    there, and lands on the reference's to float32 rounding."""
    for j, m in enumerate(toy["ran"].trial_metrics):
        rec = m["curve"]
        assert (rec["stride"], rec["steps"]) == (2, STAGES)
        got = np.asarray(rec["gmax"])
        assert got.shape == np.asarray(rec["score"]).shape == (CV + 1, 3)
        want = toy["ref"]["gmax"][j][:, 1::2]
        assert 0.5 < want.min() and want.max() < 1.0 and np.all(np.diff(want, axis=1) > 0)
        assert np.abs(got - want).max() < 1e-6
        assert np.allclose(np.asarray(rec["score"])[:, -1], rec["tail"])


@pytest.mark.parametrize("precision,agrees", [("f32", True), ("float8_e4m3fn", False)])
def test_raw_scores_hold_the_operands_precision(toy, precision, agrees):
    """Per-row F of one lane after the last stage. With the operands whole
    the reference lands on the program's F to a few float32 ulps; with the
    (g, h) operands on the e4m3 grid other splits win and the tolerance is
    passed by orders of magnitude: this is the number that sees a lower
    precision than stated, which the accuracy of a split cannot."""
    F_ref = toy["family"].fit_scores(toy["cols"], toy["y"], toy["plan"].train_w[0], TRIALS[1], 128,
                                     precision=precision)
    gap = np.abs(F_ref - toy["F"])
    assert bool(gap.max() <= F_TOLERANCE) is agrees, (gap.max(), gap.mean())
    if not agrees:
        assert gap.mean() > 100 * F_TOLERANCE


@pytest.mark.parametrize("control", [{"precision": "float8_e4m3fn"}, {"fault": "first_order"},
                                     {"fault": "half_stages"}, {"fault": "no_subsample"}],
                         ids=lambda c: next(iter(c.values())))
def test_each_control_reads_not_correct(toy, control):
    """The reference in a lower precision than stated, or broken in a known
    way, in the program's place, fails the configuration's limits, the
    curve's among them (no_subsample only on the trial that subsamples: the
    other is its own control)."""
    out = toy["family"].reference(toy["X"], toy["y"], 2, TRIALS, toy["splits"],
                                  **{"precision": "f32", **control})
    correct, table = _judge(toy, out["score"], _as_curves(out["gmax"]))
    assert not correct, table
    assert table["curve_gap_median"][0] > 2 * table["curve_gap_median"][1], table
    if control == {"fault": "no_subsample"}:
        assert np.array_equal(out["score"][0], toy["ref"]["score"][0])
        assert np.array_equal(out["gmax"][0], toy["ref"]["gmax"][0])


def test_a_boosted_bucket_says_its_stages_and_its_levels_by_route(toy):
    attrs = toy["kernel"].dispatch_attrs(toy["static"], toy["Xd"])
    assert attrs == {"stages": STAGES, "hist_levels_by_route": f"scatter:{DEPTH}", "route_levels": DEPTH}


def test_the_reference_draws_the_programs_row_masks_and_rounds_to_the_grids(toy):
    import jax

    family = toy["family"]
    for t in (0, 5):
        sub, _ = jax.random.split(jax.random.fold_in(jax.random.PRNGKey(0), t))
        want = np.asarray(jax.random.uniform(sub, (ROWS,))) < np.float32(0.7)
        assert np.array_equal(family.stage_mask(0, t, ROWS, 0.7), want) and 0.65 < want.mean() < 0.75
    assert family.stage_mask(0, 3, ROWS, 1.0).all()
    x = np.asarray([0.3, -0.3, 0.0, 1.0, 0.123456, 3e-5], np.float32)
    import ml_dtypes

    assert np.array_equal(family._q(x, "bfloat16"), x.astype(ml_dtypes.bfloat16).astype(np.float32))
    assert np.array_equal(family._q(x, "float8_e4m3fn"), x.astype(ml_dtypes.float8_e4m3fn).astype(np.float32))
    assert family._q(x, "f32") is x


def test_the_reference_in_worker_processes_is_the_reference(toy, monkeypatch):
    """At a benchmark's size the reference shares its fits out over copies of
    its own file, processes on the CPU; what they return is what the threads
    return, to the bit."""
    monkeypatch.setattr(toy["family"], "PROCESS_WORK", 0)
    out = toy["family"].reference(toy["X"], toy["y"], 2, TRIALS, toy["splits"], precision="f32")
    assert np.array_equal(out["score"], toy["ref"]["score"]) and np.array_equal(out["gmax"], toy["ref"]["gmax"])


def test_the_configuration_keeps_the_sources_shapes():
    cfg = json.load(open(os.path.join(BENCH, "configs", "gbt_higgs.json")))
    assert cfg["reduced"] == ["n_samples", "n_estimators"] and len(cfg["source"]) <= 200
    est, ds = cfg["estimator"]["params"], cfg["dataset"]
    assert (est["max_depth"], est["learning_rate"], est["subsample"]) == (8, 0.1, 1.0)
    assert (ds["n_features"], ds["n_classes"]) == (28, 2) and cfg["histograms"]["n_bins"] == 128
    kernel = get_kernel("GradientBoostingClassifier")
    static_key, hyper = kernel.canonicalize(est)
    static = trial_map._resolved_static(kernel, static_key, ds["n_samples"], 28, 2)
    # both sampled hyperparameters are traced: one executable for every draw
    assert set(hyper) == {"learning_rate", "subsample"} and static["_depth"] == 8 and static["_n_bins"] == 128

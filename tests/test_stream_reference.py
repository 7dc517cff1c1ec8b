"""The streamed LogisticRegression search against its plain reference (PR 40).

The cell ``logreg_mnist8m.rs32`` of the benchmark guards the streamed
engine (``parallel/trial_map.py::_run_streamed``, ``data/streaming.py``, the
block-accumulated Nesterov solver of ``models/logistic.py``). Here, at a
size the CPU runs in seconds (3000 rows of 64 pixels drawn by the cell's own
generator, 10 classes, 4 trials, 3 row blocks): the program's split scores
and ``gmax`` learning curves through ``run_trials`` on the streamed path,
against ``perfbench/references/LogisticRegression.py`` on the same rows and
splits, held by the benchmark's own comparison (``lib/compare.py``) to the
configuration's limits; and the float8 control in the program's place held
to "not correct".

On the CPU the power iteration and the scoring keep float32 operands where
the chip rounds them to bfloat16 (the gradient's operands are bfloat16 on
both), and a 600-row holdout moves an accuracy in steps of 1/600: the
score limit here is the toy's (``SCORE_GAP_MAX``); the curve's is the file's
(the program's gap in units of the bfloat16 reference's own).
"""

import importlib.util
import json
import os
import sys

import numpy as np
import pytest

from cs230_distributed_machine_learning_tpu.data import stage_cache as sc
from cs230_distributed_machine_learning_tpu.models.base import TrialData
from cs230_distributed_machine_learning_tpu.models.registry import get_kernel
from cs230_distributed_machine_learning_tpu.ops.folds import build_split_plan
from cs230_distributed_machine_learning_tpu.parallel.trial_map import run_trials

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "perfbench")
CELL = "logreg_mnist8m.rs32"
ROWS, PIXELS, BLOCK, CV, SEED = 3000, 64, 1000, 5, 2**31 + 40
TRIALS = [{"C": 1e-3, "tol": 1e-4}, {"C": 0.05, "tol": 1e-3}, {"C": 1.0, "tol": 1e-4},
          {"C": 30.0, "tol": 1e-3}]
#: two held-out rows in 600 of the holdout, one in 600 of a fold
SCORE_GAP_MAX = 2.0 / 600 + 1e-6


def _load(name, rel):
    if name not in sys.modules:
        spec = importlib.util.spec_from_file_location(name, os.path.join(BENCH, rel))
        sys.modules[name] = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(sys.modules[name])
    return sys.modules[name]


@pytest.fixture(scope="module")
def cell():
    run = _load("perfbench_run", "run.py")
    cell = run.load_cell(CELL)
    cell["config"]["dataset"].update(n_samples=ROWS, n_features=PIXELS)
    X, y = run.make_dataset(cell, SEED)
    return run, cell, X, y


def _streamed(monkeypatch, X, y, params):
    monkeypatch.setenv("CS230_STAGE_CACHE", "1")
    monkeypatch.setenv("CS230_STREAM", "force")
    monkeypatch.setenv("CS230_STREAM_BLOCK_ROWS", str(BLOCK))
    sc.STAGE_CACHE.clear()
    data = TrialData(X=X, y=y, n_classes=10)
    plan = build_split_plan(np.asarray(y), task="classification", n_folds=CV,
                            test_size=0.2, random_state=42)
    try:
        return run_trials(get_kernel("LogisticRegression"), data, plan, params)
    finally:
        sc.STAGE_CACHE.clear()


def _as_search(out, params):
    """The program's trials as the harness reads a search's reply."""
    results = [{"subtask_id": f"t-{i}", "status": "completed", "parameters": p, **m}
               for i, (p, m) in enumerate(zip(params, out.trial_metrics))]
    best = max(results, key=lambda r: r["mean_cv_score"])
    return {"job_status": "completed", "job_result": {"results": results, "best_result": best}}


def test_streamed_scores_and_curves_match_the_plain_reference(cell, monkeypatch):
    run, cell, X, y = cell
    params = [{"max_iter": 100, **t} for t in TRIALS]
    out = _streamed(monkeypatch, X, y, params)
    assert all(m["curve"]["steps"] <= 100 and m["curve"]["stride"] == 2 for m in out.trial_metrics)
    compare = run.load_module("lib/compare.py")
    ref = run.load_module("references/LogisticRegression.py")
    cell["traffic"].update(n_iter=len(TRIALS), check_trials=len(TRIALS))
    numbers, detail = compare.compare(cell, TRIALS, SEED, X, y, [_as_search(out, params)],
                                      ref.reference)
    assert detail["picked"] == list(range(len(TRIALS)))
    limits = dict(cell["config"]["limits"], score_gap_max=SCORE_GAP_MAX)
    limits.pop("failed_trials")
    correct, table = compare.judge(numbers, limits)
    assert correct, table
    # the stated control in the program's place parts from the reference by
    # more than the limit's multiple of the stated precision's own gap
    f8 = ref.reference(X, y, 10, params, detail["splits"], precision="float8_e4m3fn")
    at = detail["curve_at"]
    gap = compare.curve_gap(f8["gmax"][:, :, at].astype(np.float64), detail["ref_gmax"][:, :, at])
    ratio = np.median(gap) / numbers["yardstick_gap_median"]
    assert ratio > limits["curve_gap_vs_yardstick"] > numbers["curve_gap_vs_yardstick"]


def test_the_cell_file_states_what_the_streamed_path_runs():
    cfg = json.load(open(os.path.join(BENCH, "configs", "logreg_mnist8m.json")))
    assert cfg["control"] == {"precision": "float8_e4m3fn"}
    assert {"curve_gap_vs_yardstick", "score_gap_max"} <= set(cfg["limits"])
    assert cfg["yardstick"] == {"precision": "bfloat16"}
    assert "bfloat16" in cfg["precision"] and "streamed engine" in cfg["deployment"]

"""Work of one search of the GradientBoostingClassifier family, from shapes alone.

A stage of binary boosting grows one complete tree level-wise by
bin-and-scatter: at each level every training row adds its gradient and its
hessian into one histogram cell for each feature, rows x features x 2 adds a
level, ``max_depth`` levels a stage, ``n_estimators`` stages a fit. That is
the work the search needs; an implementation that contracts one-hot
operands on the MXU executes orders of magnitude more multiply-adds (nodes x
bins for every add), which is its own choice and is not counted, and
building only the left children halves nothing the search asked for.
Scoring walks each held-out row down each stage's tree (a comparison a
level) and adds its leaf's value. Useful work only, so a share of it cannot
pass 100%."""

from __future__ import annotations

from typing import Any, Dict

#: statistics a row adds to a cell: its gradient and its hessian
STATS = 2


def hist_adds(train_rows: float, d: int, depth: int) -> float:
    return train_rows * d * STATS * depth


def hist_bytes(train_rows: float, d: int, depth: int, n_bins: int) -> float:
    """Least traffic of one stage's level histograms: each training row's
    codes (a byte a feature) and its two float32 statistics, once a level;
    every histogram cell of every level written once (float32)."""
    cells = sum(2 ** level * d * n_bins * STATS for level in range(depth))
    return depth * train_rows * (d + 4.0 * STATS) + 4.0 * cells


def hist_op_pattern(cell: Dict[str, Any]) -> str:
    """How a device trace names the ops that compute the level histograms
    (an event's name is its HLO text). The Pallas kernel would carry its own
    name, ``level_histogram``. The XLA matmul form has none that a program
    can set: it is a loop over row chunks whose carried result is one
    float32 accumulator ``[..lanes.., 2 x nodes, features x bins]`` (stats
    major over the level's built nodes: the root, then the left children),
    and that last extent is the configuration's alone."""
    cfg = cell["config"]
    cols = int(cfg["dataset"]["n_features"]) * int(cfg["histograms"]["n_bins"])
    acc = rf"f32\[(?:\d+,)+{cols}\]"
    return rf"level_histogram|^%while\S* = \(.*{acc}.* while\("


def search_work(cell: Dict[str, Any], flops) -> Dict[str, float]:
    cfg, traffic = cell["config"], cell["traffic"]
    ds, est = cfg["dataset"], cfg["estimator"]["params"]
    n, d = int(ds["n_samples"]), int(ds["n_features"])
    T, K = int(traffic["n_iter"]), int(traffic["cv"])
    train, held = flops.split_rows(n, K, float(traffic["test_size"]))
    stages, depth = int(est["n_estimators"]), int(est["max_depth"])
    n_bins = int(cfg["histograms"]["n_bins"])
    fit = T * stages * hist_adds(train, d, depth)
    splits = K + 1
    return {"fit_flops": fit, "score_flops": T * stages * held * (depth + 1),
            "kernel_flops": fit, "hist_op_pattern": hist_op_pattern(cell),
            "kernel_bytes": T * stages * splits * hist_bytes(train / splits, d, depth, n_bins)}

"""Work of one search of the RandomForestClassifier family, from shapes alone.

A tree grown level-wise by bin-and-scatter adds, at each level, every
training row's class stats into one histogram cell for each feature its
node considers (``max_features`` of them): rows x features x stats adds a
level, over the levels the configuration states (``arena.levels``). That is
the work the search needs; an implementation that contracts one-hot
operands on the MXU executes orders of magnitude more multiply-adds
(frontier width x bins for every add), which is its own choice and is not
counted. Scoring routes each held-out row down each tree (a comparison a
level) and adds its leaf's class shares to the vote."""

from __future__ import annotations

from typing import Any, Dict

import numpy as np


def features_considered(spec, d: int) -> int:
    if spec in (None, "sqrt"):
        return max(1, int(np.sqrt(d)))
    if spec == "log2":
        return max(1, int(np.log2(max(d, 2))))
    if isinstance(spec, float) and 0 < spec <= 1:
        return max(1, int(spec * d))
    return max(1, min(int(spec), d))


def frontier_widths(arena: Dict[str, Any]):
    """Nodes histogrammed at each level: the frontier doubles from the root
    up to the arena's width, which falls to ``low`` from ``split_level``."""
    hi, split_level, low = arena["width_schedule"]
    out, w = [], 1
    for level in range(int(arena["levels"])):
        out.append(w)
        w = min(2 * w, hi if level + 1 < split_level else low)
    return out


def hist_adds(train_rows: float, mf: float, k: int, levels: int) -> float:
    return train_rows * mf * k * levels


def hist_bytes(train_rows: float, mf: float, k: int, arena: Dict[str, Any]) -> float:
    """Least traffic of one tree's level histograms: each training row's
    codes of the features considered (a byte each), its label and count (a
    byte each), once a level; every histogram cell written once (float32)."""
    fine, deep = arena["bins"]
    cells = sum(w * mf * (fine if 2 * w < arena["occupancy"] else deep) * k
                for w in frontier_widths(arena))
    return int(arena["levels"]) * train_rows * (mf + 2.0) + 4.0 * cells


#: bins of a one-hot or binary column's histogram (the program's coarse
#: feature group; a configuration may state another under ``arena``)
COARSE_BINS = 4


def hist_op_pattern(cell: Dict[str, Any]) -> str:
    """How a device trace names the ops that compute the level histograms,
    whatever computes them (an event's name is its HLO text). The Pallas
    kernel carries its own name, ``level_histogram``. The XLA form has none
    that a program can set: it is a loop over row chunks whose result holds
    one int32 accumulator for each feature group, ``[split lanes, ...,
    nodes x classes, columns x bins]``, and those last extents are the
    configuration's alone (continuous columns x the fine or the deep bin
    count; one-hot columns x the coarse bin count)."""
    cfg = cell["config"]
    ds, arena = cfg["dataset"], cfg.get("arena")
    if not arena or "onehot_blocks" not in ds:
        return r"level_histogram"
    lanes = int(cell["traffic"]["cv"]) + 1
    cont = "|".join(str(int(ds["n_continuous"]) * int(b)) for b in arena["bins"])
    coarse = sum(int(b) for b in ds["onehot_blocks"]) * int(arena.get("coarse_bins", COARSE_BINS))
    acc = lambda cols: rf"s32\[{lanes},(?:\d+,)+(?:{cols})\]"  # noqa: E731
    return rf"level_histogram|^%while\S* = \(.*{acc(cont)}.*{acc(coarse)}.* while\("


def search_work(cell: Dict[str, Any], flops) -> Dict[str, float]:
    cfg, traffic = cell["config"], cell["traffic"]
    ds, est, arena = cfg["dataset"], cfg["estimator"]["params"], cfg["arena"]
    n, d, k = int(ds["n_samples"]), int(ds["n_features"]), int(ds["n_classes"])
    T, K = int(traffic["n_iter"]), int(traffic["cv"])
    train, held = flops.split_rows(n, K, float(traffic["test_size"]))
    trees, levels = int(est["n_estimators"]), int(arena["levels"])
    space = traffic["param_distributions"].get("max_features", [est.get("max_features")])
    mf = float(np.mean([features_considered(s, d) for s in space]))  # a trial's, on average
    fit = T * trees * hist_adds(train, mf, k, levels)
    splits = K + 1
    return {"fit_flops": fit, "score_flops": T * trees * held * (levels + k),
            "kernel_flops": fit, "hist_op_pattern": hist_op_pattern(cell),
            "kernel_bytes": T * trees * splits * hist_bytes(train / splits, mf, k, arena)}

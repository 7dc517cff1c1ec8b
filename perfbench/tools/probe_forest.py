"""Readings the forest cell's size and its sklearn gap are set from.

    python3 perfbench/tools/probe_forest.py --workload rf_covertype.rs4 --seed 7 \
        --trees 4,6 --sklearn 1

For each tree count: the cell's search through the program's normal entry
with ``n_estimators`` set to it, cold and then warm, and the warm search's
seconds per (tree, split): what the configuration's sizing rule reads
(``assumed.n_estimators``). With ``--sklearn``: the last count's warm
search, trial by trial, beside sklearn's own ``cross_val_score`` of the
same estimator on the same rows and folds, on the raw table, on the
table's 48 quantile codes (what binning alone costs) and on the codes with
depth and leaves held to the arena's budget. With ``--trees ""`` nothing is
timed and no chip is needed: the program's side of the sklearn table is then
the plain reference's (which the cell's own comparison holds to the program,
run by run). Prints one ``FOREST`` line a reading. Not part of a benchmark
run; decides nothing in ``correct``.
"""

from __future__ import annotations

import argparse
import copy
import gc
import json
import os
import sys

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
import run  # noqa: E402  (perfbench/run.py)


def timed_searches(cell, seed, devices):
    X, y = run.make_dataset(cell, seed)
    manager, coordinator = run.build_system(cell, X, y, devices)
    search = run.build_search(cell, seed)
    first = run.train_once(manager, search, cell)
    warm = run.train_once(manager, search, cell)
    memory = run.memory_peak_bytes(devices)
    del manager, coordinator
    run.free_program_state()
    return X, y, first, warm, memory


def sklearn_scores(cell, X, y, params, variant):
    """Mean 5-fold accuracy of sklearn's forest with ``params`` on the raw
    table, on its quantile codes, or on the codes within the arena's budget."""
    from sklearn.ensemble import RandomForestClassifier
    from sklearn.model_selection import StratifiedKFold, cross_val_score

    extra = {}
    if variant != "raw":
        family = run.load_module("references/RandomForestClassifier.py")
        X = family.bin_codes(X, family.schedule(len(y))["fine"])[0].astype(np.float32)
    if variant == "codes_budget":
        arena = cell["config"]["arena"]
        hi, split_level, low = arena["width_schedule"]
        extra = {"max_depth": int(arena["levels"]),
                 "max_leaf_nodes": hi * (split_level - int(np.log2(hi))) + low * (
                     int(arena["levels"]) - split_level)}
    return [float(cross_val_score(
        RandomForestClassifier(**{**params, **extra, "random_state": rs}, n_jobs=-1), X, y,
        cv=StratifiedKFold(int(cell["traffic"]["cv"]))).mean()) for rs in (0, 1, 2)]


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--trees", required=True)
    ap.add_argument("--sklearn", type=int, default=0)
    args = ap.parse_args()
    import jax

    from cs230_distributed_machine_learning_tpu.utils.jax_setup import setup_jax

    setup_jax()
    base = run.load_cell(args.workload)
    devices = jax.devices()[: base["chips"]]
    trees = [int(t) for t in args.trees.split(",") if t]
    if trees and devices[0].platform != "tpu":
        print("probe_forest: timing needs a TPU", file=sys.stderr)
        return 2
    compare = run.load_module("lib/compare.py")
    traffic = base["traffic"]
    fits = int(traffic["n_iter"]) * (int(traffic["cv"]) + 1)
    cell, got = base, None
    for count in trees:
        cell = copy.deepcopy(base)
        cell["config"]["estimator"]["params"]["n_estimators"] = count
        X, y, first, warm, memory = timed_searches(cell, args.seed, devices)
        print("FOREST " + json.dumps({
            "n_estimators": count, "first_search_s": first["wall_s"], "warm_search_s": warm["wall_s"],
            "s_per_tree_split": warm["wall_s"] / (fits * count), "memory": memory,
            "failed": compare.count_failed(warm["status"], int(traffic["n_iter"]))}), flush=True)
        got = [float(r["mean_cv_score"]) for r in
               compare._by_index(warm["status"]["job_result"]["results"])]
        gc.collect()
    if args.sklearn:
        combos = run.search_kind(cell).expected(traffic, args.seed)
        params = cell["config"]["estimator"]["params"]
        if got is None:
            X, y = (np.asarray(a) for a in run.make_dataset(cell, args.seed))
            splits = compare.split_masks(y, int(traffic["cv"]), float(traffic["test_size"]),
                                         int(traffic["split_random_state"]))
            family = run.load_module("references/RandomForestClassifier.py")
            scores = family.reference(X, y, cell["config"]["dataset"]["n_classes"],
                                      [{**params, **c} for c in combos], splits)["score"]
            got = [float(row[1:].mean()) for row in scores]  # split 0 is the holdout
        fixed = {k: v for k, v in params.items() if k != "random_state"}
        for i, combo in enumerate(combos):
            row = {"params": combo, "program_cv_mean": got[i]}
            for variant in ("raw", "codes", "codes_budget"):
                row["sklearn_" + variant] = sklearn_scores(cell, X, y, {**fixed, **combo}, variant)
            print("FOREST " + json.dumps(row), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

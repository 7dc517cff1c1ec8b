"""Whether the program grows the reference's trees on this backend.

    python3 perfbench/tools/probe_tree_parity.py --workload rf_covertype.rs4 --seed 7 \\
        --max-features sqrt --min-samples-leaf 1 --split 0

The named split lanes of one trial of the cell (one compile), tree by tree: the program's deep
builder (``kernel._one_tree``, as the chunked step calls it) beside the
reference's ``grow_tree`` on the same table, bootstrap and feature subsets.
Prints a ``PARITY`` line a tree (splits on each side, arena slots whose
split record differs, the first such id with both records, rows whose leaf
value differs at all) and one for the forest's held-out votes. It is how
PR 32 found that the chip split pure nodes and ordered equal gains by their
last bit (14 849 of a tree's slots differed; none in 46 of 48 trees since, the two by an
argmax flip inside one node). About three minutes
on the chip, most of it one compile. Decides nothing in ``correct``.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
import run  # noqa: E402  (perfbench/run.py)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--max-features", default="sqrt")
    ap.add_argument("--min-samples-leaf", type=int, default=1)
    ap.add_argument("--split", default="0", help="split lanes, e.g. 0 or 0,3 or all")
    ap.add_argument("--rows", type=int, default=0, help="cut the table (CPU rehearsals)")
    args = ap.parse_args()
    import jax
    import jax.numpy as jnp

    from cs230_distributed_machine_learning_tpu.models.registry import get_kernel
    from cs230_distributed_machine_learning_tpu.parallel import trial_map
    from cs230_distributed_machine_learning_tpu.utils.jax_setup import setup_jax

    setup_jax()
    cell = run.load_cell(args.workload)
    if args.rows:
        cell["config"]["dataset"]["n_samples"] = args.rows
    X, y = (np.asarray(a) for a in run.make_dataset(cell, args.seed))
    n, d = X.shape
    k = int(cell["config"]["dataset"]["n_classes"])
    traffic = cell["traffic"]
    ref = run.load_module("references/RandomForestClassifier.py")
    TW, EW = run.load_module("lib/compare.py").split_masks(
        y, int(traffic["cv"]), float(traffic["test_size"]), int(traffic["split_random_state"]))
    params = {**cell["config"]["estimator"]["params"], "max_features": args.max_features,
              "min_samples_leaf": args.min_samples_leaf}
    kernel = get_kernel(cell["config"]["estimator"]["class"])
    static_key, _ = kernel.canonicalize(params)
    static = trial_map._resolved_static(kernel, static_key, n, d, k)
    Xd = jax.tree_util.tree_map(jnp.asarray, kernel.prepare_data(X, static))
    yd = jnp.asarray(y, jnp.int32)

    @jax.jit
    def one_tree(Xd, yd, w, t):
        S, _ = kernel._stat_matrix(yd, w, static)
        tree = kernel._one_tree(Xd, S, w, static,
                                jax.random.fold_in(jax.random.PRNGKey(static["_seed"]), t))
        return tree, kernel._tree_predict(Xd["xb"], tree, static)

    sched = ref.schedule(n)
    codes, fine = ref.bin_codes(X, sched["fine"])
    mf, msl = ref.resolve(params, n, d)
    n_ids = 2 * sched["width"] * sched["levels"] + 3
    lanes = range(len(TW)) if args.split == "all" else [int(x) for x in args.split.split(",")]
    for lane in lanes:
        w = jnp.asarray(TW[lane], jnp.float32)
        votes = {"program": np.zeros((n, k), np.float32), "reference": np.zeros((n, k), np.float32)}
        for t in range(int(params["n_estimators"])):
            tree, vals = jax.tree_util.tree_map(np.asarray, one_tree(Xd, yd, w, jnp.int32(t)))
            boot_key, feat_key = ref.tree_keys(int(params["random_state"]), t)
            counts = ref.bootstrap_counts(boot_key, TW[lane] > 0)
            node, leaf_val, (feat_a, bin_a, child_a) = ref.grow_tree(
                codes, fine, y, counts, *ref.node_features(feat_key, n_ids, d, mf, fine),
                sched, msl, k)
            A = len(child_a) - 1
            split = child_a[:A] > 0
            differs = (child_a[:A] != tree["child"][:A]) | (split & (
                (feat_a[:A] != tree["feat"][:A]) | (bin_a[:A] != tree["bin"][:A])))
            row = {"split": lane, "tree": t, "splits_reference": int(split.sum()),
                   "splits_program": int((tree["child"][:A] > 0).sum()),
                   "slots_differing": int(differs.sum()),
                   "rows_leaf_value_differs": int((leaf_val[node] != vals).any(1).sum()),
                   "leaf_value_gap_max": float(np.abs(leaf_val[node] - vals).max())}
            if differs.any():
                i = int(np.flatnonzero(differs)[0])
                row["first_differing_id"] = {
                    "id": i, "reference": [int(child_a[i]), int(feat_a[i]), int(bin_a[i])],
                    "program": [int(tree["child"][i]), int(tree["feat"][i]), int(tree["bin"][i])]}
            print("PARITY " + json.dumps(row), flush=True)
            votes["program"] += vals
            votes["reference"] += leaf_val[node]
        held = EW[lane] > 0
        pred = {name: v.argmax(1) for name, v in votes.items()}
        print("PARITY " + json.dumps({
            "split": lane, "device": jax.devices()[0].platform, "held_out_rows": int(held.sum()),
            "predicted_differently": int((pred["program"] != pred["reference"])[held].sum()),
            **{"accuracy_" + name: float((p == y)[held].mean()) for name, p in pred.items()}}),
            flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

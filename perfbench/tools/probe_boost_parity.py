"""Whether the program grows the reference's boosted trees on this backend.

    python3 perfbench/tools/probe_boost_parity.py --workload gbt_higgs.rs8 --seed 7 \\
        --learning-rate 0.1 --subsample 0.8 --split 0

One split lane of one trial of the cell (one compile of one stage), stage by
stage: the program's ``kernel._stage`` (as the chunked step calls it) beside
the reference's ``grow_tree`` on the same table and the same row mask. Two
readings a precision of the reference (``bfloat16``, the stated one;
``f32``; ``float8_e4m3fn``, the control):

* *forced*: every stage of the reference starts from the program's own raw
  score, so a stage's differences are that stage's alone: how many of its
  255 split records differ (near-ties decided by the last bits of a float32
  sum), and on how many rows its increment of the score differs;
* *free*: the reference runs on its own score from F0, as the comparison
  that decides ``correct`` runs it; the per-row gap of F after the last
  stage (one early split that falls the other way moves every later stage).

A program that rounded its histogram operands lower than the configuration
states would read against ``bfloat16`` what ``float8_e4m3fn`` reads here.
Prints a ``PARITY`` line a stage and a precision and a summary line a
precision. A few minutes on the chip. Decides nothing in ``correct``.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
import run  # noqa: E402  (perfbench/run.py)

PRECISIONS = ("bfloat16", "f32", "float8_e4m3fn")


def gap_summary(a, b):
    g = np.abs(np.asarray(a, np.float64) - np.asarray(b, np.float64))
    return {"gap_max": float(g.max()), "gap_mean": float(g.mean()), "gap_median": float(np.median(g)),
            "rows_over_1e-3": int((g > 1e-3).sum()), "rows_over_1e-6": int((g > 1e-6).sum())}


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--learning-rate", type=float, default=0.1)
    ap.add_argument("--subsample", type=float, default=0.8)
    ap.add_argument("--split", type=int, default=0)
    ap.add_argument("--rows", type=int, default=0, help="cut the table (CPU rehearsals)")
    ap.add_argument("--stages", type=int, default=0, help="other than the configuration's")
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
    traffic = cell["traffic"]
    ref = run.load_module("references/GradientBoostingClassifier.py")
    TW, EW = run.load_module("lib/compare.py").split_masks(
        y, int(traffic["cv"]), float(traffic["test_size"]), int(traffic["split_random_state"]))
    params = {**cell["config"]["estimator"]["params"], "learning_rate": args.learning_rate,
              "subsample": args.subsample}
    if args.stages:
        params["n_estimators"] = args.stages
    stages, depth, seed = int(params["n_estimators"]), int(params["max_depth"]), int(params["random_state"])
    kernel = get_kernel(cell["config"]["estimator"]["class"])
    static_key, _ = kernel.canonicalize(params)
    static = trial_map._resolved_static(kernel, static_key, n, d, 2)
    n_bins, msl = int(static["_n_bins"]), float(static["_msl"])
    prepared = kernel.prepare_data(X, static)
    codes = ref.bin_codes(X, n_bins)
    cols = np.ascontiguousarray(codes.T)
    print("PARITY " + json.dumps({"device": jax.devices()[0].platform, "rows": n, "stages": stages,
                                  "codes_equal": bool(np.array_equal(codes, np.asarray(prepared["xb"])))}),
          flush=True)
    xb, yd = jnp.asarray(prepared["xb"]), jnp.asarray(y, jnp.int32)
    w = jnp.asarray(TW[args.split], jnp.float32)
    hyper = {"learning_rate": jnp.float32(args.learning_rate), "subsample": jnp.float32(args.subsample)}

    @jax.jit
    def stage(xb, yd, w, F, t):
        key = jax.random.fold_in(jax.random.PRNGKey(static["_seed"]), t)
        return kernel._stage(xb, yd, w, hyper, static, F, key)

    F = kernel._f0(n, kernel._prior(yd, w, static), static)
    y1 = (y == 1).astype(np.float32)
    wn = np.asarray(TW[args.split], np.float32)
    lr = np.float32(args.learning_rate)
    F_prog = [np.asarray(F)[:, 1]]
    totals = {p: {"splits_differing": 0, "rows_increment_differs": 0} for p in PRECISIONS}
    for t in range(stages):
        F, trees = stage(xb, yd, w, F, jnp.int32(t))
        F_prog.append(np.asarray(F)[:, 1])
        sf, sb = np.asarray(trees["split_feat"][0]), np.asarray(trees["split_bin"][0])
        mask = ref.stage_mask(seed, t, n, args.subsample).astype(np.float32) * wn
        g, h = ref.stage_stats(y1, F_prog[t], mask)
        for p in PRECISIONS:  # forced: this stage from the program's own score
            leaf, leaf_val, rf, rb = ref.grow_tree(cols, g, h, depth, n_bins, msl, p)
            differs = (rf != sf) | (rb != sb)
            inc = gap_summary(F_prog[t] + lr * leaf_val[leaf], F_prog[t + 1])
            totals[p]["splits_differing"] += int(differs.sum())
            totals[p]["rows_increment_differs"] += inc["rows_over_1e-6"]
            row = {"stage": t, "precision": p, "splits_differing": int(differs.sum()), "of": len(sf), **inc}
            if differs.any():
                i = int(np.flatnonzero(differs)[0])
                row["first_differing_node"] = {"node": i, "reference": [int(rf[i]), int(rb[i])],
                                               "program": [int(sf[i]), int(sb[i])]}
            print("PARITY " + json.dumps(row), flush=True)
    held = EW[args.split] > 0
    for p in PRECISIONS:  # free: the reference on its own score, as `correct` reads it
        F_ref = ref.fit_scores(cols, y, wn, params, n_bins, precision=p)
        pred = {"program": F_prog[-1] > 0, "reference": F_ref > 0}
        print("PARITY " + json.dumps({
            "precision": p, "forced": totals[p], "of_splits": stages * (2 ** depth - 1),
            "free_F": gap_summary(F_ref, F_prog[-1]), "held_out_rows": int(held.sum()),
            "predicted_differently": int((pred["program"] != pred["reference"])[held].sum()),
            **{"accuracy_" + k: float((v == (y == 1))[held].mean()) for k, v in pred.items()}}),
            flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

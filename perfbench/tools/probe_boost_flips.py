"""How many lanes of a boosted search keep the reference's trees, and for how long.

    python3 perfbench/tools/probe_boost_flips.py --workload gbt_higgs.rs8 --seeds 1,2,3

The comparison that decides ``correct`` holds the median of the curve's gaps
(``curve_gap_median``), and that is a clean lane's only while fewer than half
of the compared lane-slots lie behind a near-tie that fell the other way
(two gains apart in the last bits of a float32 sum: from that stage on the
lane is another, equally good model and reads what a lower precision reads).
For each seed: one warm search through the program's normal entry, the
reference's curve of EVERY (trial, split) lane, and for each lane the first
sampled stage whose relative gap passes ``--clean`` (1e-5: a clean slot
reads 1e-7 to 1e-5, a flipped one 1e-4 to 1e-2). Prints one ``FLIPS`` line a
seed: lanes that never flipped, the share of lane-slots behind a flip (the
number to hold against one half), the rate a slot, and each lane's
hyperparameters beside its first flipped slot. About four minutes a seed on
the chip. Decides nothing in ``correct``; PERF.md section 6 (PR 34) has the
readings the cell's ``check_trials`` was sized from.
"""

from __future__ import annotations

import argparse
import gc
import json
import os
import sys

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
import run  # noqa: E402  (perfbench/run.py)


def scan(cell, seed, devices, clean):
    compare = run.load_module("lib/compare.py")
    family = run.load_module(f"references/{cell['config']['estimator']['class']}.py")
    traffic = cell["traffic"]
    n_iter, cv = int(traffic["n_iter"]), int(traffic["cv"])
    X, y = run.make_dataset(cell, seed)
    manager, coordinator = run.build_system(cell, X, y, devices)
    search = run.build_search(cell, seed)
    run.train_once(manager, search, cell)
    warm = run.train_once(manager, search, cell)
    del manager, coordinator
    run.free_program_state()
    combos = run.search_kind(cell).expected(traffic, seed)
    params = [{**cell["config"]["estimator"]["params"], **c} for c in combos]
    splits = compare.split_masks(np.asarray(y), cv, float(traffic["test_size"]),
                                 int(traffic["split_random_state"]))
    ref = family.reference(X, y, int(cell["config"]["dataset"]["n_classes"]), params, splits)
    got = compare._by_index(warm["status"]["job_result"]["results"])
    lanes = []
    for i in range(n_iter):
        curve, at = compare.curve_rows(got[i], ref["gmax"].shape[2])
        gaps = compare.curve_gap(curve, ref["gmax"][i][:, at])  # [splits, slots]
        for s in range(cv + 1):
            over = np.flatnonzero(gaps[s] > clean)
            lanes.append({**{k: float(v) for k, v in combos[i].items()}, "trial": i, "split": s,
                          "first_flipped_slot": int(over[0]) if len(over) else -1,
                          "slots": int(gaps.shape[1]), "gap_median": float(np.median(gaps[s]))})
    behind = [ln["slots"] - ln["first_flipped_slot"] if ln["first_flipped_slot"] >= 0 else 0 for ln in lanes]
    at_risk = [ln["first_flipped_slot"] + 1 if ln["first_flipped_slot"] >= 0 else ln["slots"] for ln in lanes]
    flipped = sum(ln["first_flipped_slot"] >= 0 for ln in lanes)
    return {"seed": seed, "search_s": warm["wall_s"], "lanes": len(lanes), "never_flipped": len(lanes) - flipped,
            "lane_slots_behind_a_flip": sum(behind) / sum(ln["slots"] for ln in lanes),
            "flips_a_slot": flipped / sum(at_risk), "by_lane": lanes}


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--clean", type=float, default=1e-5)
    ap.add_argument("--rows", type=int, default=0, help="cut the table to this many rows: a CPU smoke, no reading")
    args = ap.parse_args()
    import jax

    from cs230_distributed_machine_learning_tpu.utils.jax_setup import setup_jax

    setup_jax()
    cell = run.load_cell(args.workload)
    if args.rows:
        cell["config"]["dataset"]["n_samples"] = args.rows
    devices = jax.devices()[: cell["chips"]]
    if devices[0].platform != "tpu" and not args.rows:
        print("probe_boost_flips: no TPU", file=sys.stderr)
        return 2
    for seed in (int(s) for s in args.seeds.split(",")):
        print("FLIPS " + json.dumps(scan(cell, seed, devices, args.clean)), flush=True)
        gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())

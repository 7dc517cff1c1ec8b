"""Readings a limit is set from, many seeds in one process (set-up is long).

    python3 perfbench/tools/probe_limits.py --workload <cell> --seeds 1,2,3 --controls 3

For each seed: the dataset, one search through the program's normal entry,
and the comparison of a run (``compare.compare`` then ``compare.judge``) on
what it returned: the lower readings. Then the upper readings, each through
the same two functions: the plain reference put in the program's place in
the configuration's ``control`` precision and in ``bfloat16`` (the stated
precision, which has to pass), and for the first ``--controls`` seeds also
in ``int8``, in each part of the control alone, and with each fault the
family's reference declares. Prints one ``PROBE`` line a seed. Not part of a
benchmark run.
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


def in_the_programs_place(status, out, detail, stride):
    """The program's reply with the sampled trials' answers replaced by
    ``out`` (a reference's ``score`` and, where the family has one, ``gmax``)."""
    status = copy.deepcopy(status)
    res = status["job_result"]
    by_index = {int(r["subtask_id"].rsplit("-", 1)[1]): r for r in res["results"]}
    for j, i in enumerate(detail["picked"]):
        r = by_index[i]
        r["accuracy"] = float(out["score"][j, 0])
        r["cv_scores"] = [float(v) for v in out["score"][j, 1:]]
        r["mean_cv_score"] = float(np.mean(r["cv_scores"]))
        if "gmax" in out:
            r["curve"] = {"gmax": out["gmax"][j][:, detail["curve_at"]].tolist(), "stride": stride}
    res["best_result"] = max(res["results"], key=lambda r: r["mean_cv_score"])
    return status


def readings(cell, seed, controls, devices):
    """One seed's readings: the program's, and those of each control (with
    ``controls`` also int8 and each fault) put in its place."""
    config = cell["config"]
    compare = run.load_module("lib/compare.py")
    family = run.load_module(f"references/{config['estimator']['class']}.py")
    X, y = run.make_dataset(cell, seed)
    manager, coordinator = run.build_system(cell, X, y, devices)
    first = run.train_once(manager, run.build_search(cell, seed), cell)
    memory = run.memory_peak_bytes(devices)
    del manager, coordinator
    run.free_program_state()
    memo = {}

    def reference(X, y, n_classes, params, splits, **kw):
        key = json.dumps(kw, sort_keys=True)
        if key not in memo:
            memo[key] = family.reference(X, y, n_classes, params, splits, **kw)
        return memo[key]

    combos = run.search_kind(cell).expected(cell["traffic"], seed)

    def read(status):
        numbers, detail = compare.compare(cell, combos, seed, X, y, [status], reference)
        numbers["failed_trials"] = float(compare.count_failed(status, int(cell["traffic"]["n_iter"])))
        correct, _table = compare.judge(numbers, config["limits"])
        return {"correct": correct, **numbers, "gaps": detail["gaps"].mean(axis=0).tolist()}, detail

    program, detail = read(first["status"])
    row = {"seed": seed, "search_s": first["wall_s"], "memory": memory, "program": program,
           "ref_scores": [float(detail["ref"].min()), float(detail["ref"].max())],
           "by_trial": [{**{k_: float(v) for k_, v in combos[i].items()}, "gap": float(g)}
                        for i, g in zip(detail["picked"], detail["trial_gap"])]}
    n_classes = int(config["dataset"]["n_classes"])
    stride = 1 if detail["curve_at"] is None else int(detail["curve_at"][0]) + 1
    control = config["control"]
    runs = {"control": control, "bfloat16": {"precision": "bfloat16"}}
    if controls:
        runs["int8"] = {"precision": "int8"}
        if len(control) > 1:  # each part of the control alone
            runs.update({f"control.{k}": {k: v} for k, v in control.items()})
        runs.update({f: {"fault": f} for f in family.FAULTS})
    for name, kw in runs.items():
        out = reference(X, y, n_classes, detail["params"], detail["splits"], **kw)
        row[name], _ = read(in_the_programs_place(first["status"], out, detail, stride))
    return row


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--controls", type=int, default=0)
    args = ap.parse_args()
    import jax

    from cs230_distributed_machine_learning_tpu.utils.jax_setup import setup_jax

    setup_jax()
    cell = run.load_cell(args.workload)
    devices = jax.devices()[: cell["chips"]]
    if devices[0].platform != "tpu":
        print("probe_limits: no TPU", file=sys.stderr)
        return 2
    for k, seed in enumerate(int(s) for s in args.seeds.split(",")):
        print("PROBE " + json.dumps(readings(cell, seed, k < args.controls, devices)), flush=True)
        gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())

"""Benchmark: hyperparameter-search throughput vs the sklearn/CPU reference.

Runs a RandomizedSearchCV-style LogisticRegression sweep on a Covertype-shaped
synthetic dataset (the north-star config, scaled for round time) on the
available accelerator via the full framework path (MLTaskManager ->
coordinator -> trial engine), and measures the same trials executed the
reference way (per-trial sklearn fits + 5-fold cross_val_score on CPU,
worker.py:289-349 semantics) on a subsample of trials for the denominator.

    python bench.py             # ONE chip: the packed single-device path
    python bench.py --chips 4   # trial_mesh over 4 chips: the sharded path

The two are different executables (trial_map: on a mesh the packed Pallas
fit runs per chip under shard_map, at a block as wide as a chip's share
of the trials), so the chip count is asked for, never taken from
whatever the host happens to hold; the output names the device and the
path it measured.

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline", ...}.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

N_ROWS = int(os.environ.get("BENCH_ROWS", 0))  # 0 = builtin covertype (116k x 54)
N_TRIALS = int(os.environ.get("BENCH_TRIALS", 1000))
# sklearn denominator sample: stratified across the C range (per-trial cost
# varies strongly with C under loguniform(1e-3, 1e2)); >=8 keeps the
# extrapolation honest (round-1 used 2, flagged as soft)
SK_TRIALS = int(os.environ.get("BENCH_SK_TRIALS", 16))
REPS = int(os.environ.get("BENCH_REPS", 3))
# stalls are one-sided additive noise on top of the compute-bound steady
# state, so the bench keeps adding steady passes (up to BENCH_MAX_REPS)
# until the fastest-3 window agrees to BENCH_TARGET_SPREAD, then scores
# that window's median. Every pass is still reported in steady_s.
MAX_REPS = int(os.environ.get("BENCH_MAX_REPS", 9))
TARGET_SPREAD = float(os.environ.get("BENCH_TARGET_SPREAD", 0.04))
CV = 5


def main() -> None:
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--chips", type=int, default=1,
                        help="devices of the trial mesh (default: one chip)")
    n_chips = parser.parse_args().chips

    import jax
    from sklearn.linear_model import LogisticRegression
    from sklearn.model_selection import RandomizedSearchCV

    from cs230_distributed_machine_learning_tpu import MLTaskManager
    from cs230_distributed_machine_learning_tpu.runtime.coordinator import Coordinator
    from cs230_distributed_machine_learning_tpu.parallel.mesh import trial_mesh

    from scipy.stats import loguniform

    dataset = f"synthetic_{N_ROWS}x54x7" if N_ROWS else "covertype"
    param_distributions = {
        "C": loguniform(1e-3, 1e2),  # continuous: exactly n_iter distinct trials
        "tol": [1e-4, 1e-3],
    }

    devices = jax.devices()
    if n_chips > len(devices):
        sys.exit(f"bench.py: --chips {n_chips} asked, {len(devices)} visible")
    device = {
        "platform": devices[0].platform,
        "device_kind": devices[0].device_kind,
        "count": len(devices),
        "chips_used": n_chips,
        "path": "single-device" if n_chips == 1 else f"trial_mesh({n_chips})",
    }
    print("device:", json.dumps(device), flush=True)
    mesh = trial_mesh(devices[:n_chips]) if n_chips > 1 else None
    manager = MLTaskManager(coordinator=Coordinator(mesh=mesh))
    search = RandomizedSearchCV(
        LogisticRegression(max_iter=200),
        param_distributions,
        n_iter=N_TRIALS,
        cv=CV,
        random_state=0,
    )

    # median of >=REPS steady passes: the first pass warms trace/AOT/XLA
    # caches and is reported separately as cold_s, then the scoreboard
    # value is the median steady pass with its (max-min)/median spread
    def one_pass():
        t0 = time.time()
        status = manager.train(search, dataset, {"random_state": 42},
                               show_progress=False, timeout=3600)
        dt = time.time() - t0
        # direct mode reports "completed" even with every trial failed
        result = status["job_result"]
        if (status["job_status"] != "completed" or result["failed"]
                or len(result["results"]) != N_TRIALS):
            sys.exit(
                f"bench.py: job_status={status['job_status']!r}, "
                f"{len(result['results'])}/{N_TRIALS} results, "
                f"{len(result['failed'])} failed: {result['failed'][:1]}"
            )
        return dt

    def best_window(xs, k=3):
        w = sorted(xs)[: min(k, len(xs))]
        return w, (w[-1] - w[0]) / max(float(np.median(w)), 1e-9)

    cold = one_pass()
    steady = [one_pass() for _ in range(REPS)]
    window, spread = best_window(steady)
    while spread > TARGET_SPREAD and len(steady) < MAX_REPS:
        steady.append(one_pass())  # noisy window: keep sampling
        window, spread = best_window(steady)
    wall = float(np.median(window))

    trials_per_sec = N_TRIALS / wall

    # ---- reference-style denominator: sklearn per-trial fit + 5-fold CV ----
    from sklearn.model_selection import ParameterSampler, cross_val_score
    from cs230_distributed_machine_learning_tpu.data.datasets import DatasetCache

    cache = manager._coordinator.cache
    data = cache.get(dataset, "classification")
    X, y = np.asarray(data.X), np.asarray(data.y)
    # stratified subsample of the ACTUAL trial population: slow (small-C,
    # slow-converging) and fast trials both represented
    from cs230_distributed_machine_learning_tpu.utils.flops import stratified_by

    population = list(
        ParameterSampler(param_distributions, n_iter=N_TRIALS, random_state=0)
    )
    sampled = stratified_by(population, lambda p: p["C"], SK_TRIALS)
    per_trial_times = []
    for params in sampled:
        model = LogisticRegression(max_iter=200, **params)
        from sklearn.model_selection import train_test_split

        Xt, _, yt, _ = train_test_split(X, y, test_size=0.2, random_state=42)
        t0 = time.time()
        model.fit(Xt, yt)
        cross_val_score(model, X, y, cv=CV)
        per_trial_times.append(time.time() - t0)
    sk_per_trial = float(np.mean(per_trial_times))
    sk_total_est = sk_per_trial * N_TRIALS
    speedup = sk_total_est / wall
    # extrapolation error = standard error of the MEAN over the stratified
    # sample (std/sqrt(k)); the raw std measures the genuine per-trial cost
    # spread of the loguniform-C population, not estimator uncertainty
    sk_rel_err = float(
        np.std(per_trial_times)
        / max(sk_per_trial, 1e-9)
        / np.sqrt(max(len(per_trial_times), 1))
    )

    # ---- 8-worker fleet denominator (the reference's own deployment
    # shape: 4-8 worker containers, docker-compose.yml:133-199) measured by
    # benchmarks/eight_worker_baseline.py into EIGHT_WORKER_BASELINE.json;
    # the >=8x north-star target divides against THIS number ----
    vs_8worker = None
    ew_path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                           "benchmarks", "EIGHT_WORKER_BASELINE.json")
    if os.path.exists(ew_path):
        try:
            with open(ew_path) as f:
                ew = json.load(f)
            # a fleet measured with fewer cores than workers is time-sliced
            # single-core throughput — dividing against it would overstate
            # the speedup vs a REAL 8-worker fleet by up to the worker count
            if (ew.get("dataset") == dataset and ew.get("n_trials")
                    and not ew.get("contention_bound")
                    and ew.get("cpu_count", 0) >= ew.get("workers", 8)):
                ew_total = ew["wall_s"] * (N_TRIALS / ew["n_trials"])
                vs_8worker = round(ew_total / wall, 2)
        except (OSError, ValueError, KeyError):
            pass

    # ---- committed FULL-RUN denominator (benchmarks/FULL_SKLEARN_CONFIG3
    # .json: every one of the 1000 draws measured once, uncontended —
    # 9219.6 s total, mean 9.22 s/trial; the per-pass 16-draw stratified
    # estimate validated within 3.9% of it). Emitted alongside the
    # per-pass estimate so the headline no longer rests on extrapolation
    # when the trial population matches the committed run ----
    vs_baseline_fullrun = None
    fr_path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                           "benchmarks", "FULL_SKLEARN_CONFIG3.json")
    if os.path.exists(fr_path) and dataset == "covertype":
        try:
            with open(fr_path) as f:
                fr = json.load(f)
            if (fr.get("n_trials_done") == fr.get("n_trials_target")
                    and fr.get("n_trials_target") == N_TRIALS):
                fr_mean = float(fr["mean_per_trial_s"])
                vs_baseline_fullrun = round(fr_mean * N_TRIALS / wall, 2)
        except (OSError, ValueError, KeyError):
            pass

    # ---- idealized 8-worker bound: the north star's own units, answered
    # honestly when no real 8-core fleet is available to measure. Assumes
    # PERFECT linear scaling of the measured single-core sklearn per-trial
    # time across 8 workers (zero Kafka/scheduler/stragglers overhead) —
    # the most favorable possible case for the reference fleet, so the
    # true vs-fleet speedup is >= this number's interpretation ----
    vs_8worker_ideal = round((sk_per_trial * N_TRIALS / 8) / wall, 2)

    # ---- achieved FLOP/s + MFU (model-analytical FLOPs / wall / peak) ----
    from cs230_distributed_machine_learning_tpu.models.registry import get_kernel
    from cs230_distributed_machine_learning_tpu.utils.flops import (
        analytical_flops,
        mfu,
    )

    kernel = get_kernel("LogisticRegression")
    static = kernel.resolve_static(
        {"fit_intercept": True, "penalty": "l2"}, X.shape[0], X.shape[1], 7
    )
    static["_n_classes"] = 7
    static = kernel.bucket_static(static, [{"max_iter": 200}])
    flops = analytical_flops(kernel, static, X.shape[0], X.shape[1], CV + 1, N_TRIALS)
    util = mfu(flops, wall, n_devices=n_chips)

    print(
        json.dumps(
            {
                "metric": "randomized_search_trials_per_sec",
                "device": device,
                "value": round(trials_per_sec, 3),
                "unit": f"trials/s ({N_TRIALS} LogReg trials, {dataset}, cv={CV})",
                "vs_baseline": round(speedup, 2),
                "spread": round(spread, 3),
                "reps": len(steady),
                "cold_s": round(cold, 2),
                "steady_s": [round(s, 2) for s in steady],
                "steady_window": [round(s, 2) for s in window],
                "flops": flops,
                "achieved_flops_per_sec": round(flops / wall) if flops else None,
                "mfu": round(util, 4) if util is not None else None,
                "sk_trials_sampled": len(sampled),
                "sk_rel_err": round(sk_rel_err, 3),
                "vs_baseline_fullrun": vs_baseline_fullrun,
                "vs_8worker": vs_8worker,
                "vs_8worker_ideal": vs_8worker_ideal,
                "vs_8worker_ideal_note": (
                    "single-core sklearn per-trial time / 8 (perfect linear "
                    "worker scaling, zero fleet overhead) vs measured wall"
                ),
            }
        )
    )


if __name__ == "__main__":
    sys.exit(main())

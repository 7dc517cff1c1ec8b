"""Training-time-vs-dataset-size scaling curve.

Parity harness for the reference's scaling experiment (`results3.py:20-42`:
RandomForest training time on 1%→100% fractions of a large Kaggle retail
dataset through the distributed stack). Here the dataset is Covertype-shaped
(builtin, no egress) and each fraction runs through the full framework path
(MLTaskManager → coordinator → sharded trial engine), once cold-ish and once
steady, plus the sklearn single-process reference for the denominator.

Writes benchmarks/SCALING_MEASURED.json and prints one line per fraction.

Usage: python benchmarks/scaling_curve.py  [SCALE_MODEL=LogisticRegression]
"""

from __future__ import annotations

import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from cs230_distributed_machine_learning_tpu import MLTaskManager  # noqa: E402
from cs230_distributed_machine_learning_tpu.runtime.coordinator import Coordinator  # noqa: E402

# results3.py:20; CS230_SCALING_FRACTIONS="0.01,0.05" re-measures a subset,
# merging into the existing JSON by fraction (partial refresh after a
# change that only affects some scales)
FRACTIONS = tuple(
    float(f) for f in os.environ.get(
        "CS230_SCALING_FRACTIONS", "0.01,0.05,0.1,0.25,0.5,1.0"
    ).split(",")
)
MODEL = os.environ.get("SCALE_MODEL", "RandomForestClassifier")
SK_FULL_CAP_S = float(os.environ.get("SCALE_SK_CAP_S", 120))


def _estimator():
    if MODEL == "LogisticRegression":
        from sklearn.linear_model import LogisticRegression

        return LogisticRegression(max_iter=200)
    from sklearn.ensemble import RandomForestClassifier

    return RandomForestClassifier(n_estimators=100, random_state=42)


def main() -> None:
    import warnings

    warnings.filterwarnings("ignore")
    from sklearn.model_selection import cross_val_score, train_test_split

    manager = MLTaskManager(coordinator=Coordinator())
    cache = manager._coordinator.cache
    full = cache.get("covertype", "classification")
    X_full, y_full = np.asarray(full.X), np.asarray(full.y)
    n_full = X_full.shape[0]

    from cs230_distributed_machine_learning_tpu.data.datasets import dataset_dir

    report = []
    sk_skipped = False
    for frac in FRACTIONS:
        n = max(64, int(n_full * frac))
        rng = np.random.RandomState(0)
        idx = rng.permutation(n_full)[:n]
        Xf, yf = X_full[idx], y_full[idx]

        # stage the fraction as its own dataset id (CSV contract: target last)
        did = f"covertype_frac_{int(frac * 100)}"
        ddir = os.path.join(dataset_dir(did), "preprocessed")
        os.makedirs(ddir, exist_ok=True)
        csv = os.path.join(ddir, f"{did}_preprocessed.csv")

        def _row_count(path):
            with open(path) as f:
                return sum(1 for _ in f) - 1

        if not os.path.exists(csv) or _row_count(csv) != n:
            header = ",".join([f"f{i}" for i in range(Xf.shape[1])] + ["target"])
            tmp = csv + f".tmp.{os.getpid()}"
            np.savetxt(
                tmp,
                np.column_stack([Xf, yf]),
                delimiter=",",
                header=header,
                comments="",
                fmt="%.6g",
            )
            os.replace(tmp, csv)  # atomic: interrupted runs can't leave a torn file

        # sklearn reference (worker.py:289-349 semantics), capped for the
        # largest fractions via linear extrapolation from the previous point
        sk_time = None
        sk_cv = None
        extrapolated = False
        reused = None
        if os.environ.get("CS230_SCALING_REUSE_SK") == "1":
            # framework-side sweeps: reuse the committed sklearn point for
            # this fraction instead of burning ~8 min re-measuring it
            out_path = os.path.join(
                os.path.dirname(os.path.abspath(__file__)), "SCALING_MEASURED.json"
            )
            try:
                with open(out_path) as f:
                    old = json.load(f)
                reused = next(
                    (p for p in old.get("points", [])
                     if p.get("fraction") == frac and old.get("model") == MODEL),
                    None,
                )
            except (OSError, ValueError):
                pass
        if reused is not None:
            sk_time = reused["sklearn_s"]
            sk_cv = reused.get("cv_sklearn")
            extrapolated = bool(reused.get("sklearn_extrapolated"))
        elif not sk_skipped:
            model = _estimator()
            t0 = time.time()
            Xt, Xe, yt, ye = train_test_split(Xf, yf, test_size=0.2, random_state=42)
            model.fit(Xt, yt)
            model.score(Xe, ye)
            sk_cv = float(np.mean(cross_val_score(model, Xf, yf, cv=5)))
            sk_time = time.time() - t0
            if sk_time > SK_FULL_CAP_S:
                sk_skipped = True  # larger fractions: extrapolate
        else:
            prev = report[-1]
            sk_time = prev["sklearn_s"] * (n / prev["n_rows"])
            extrapolated = True

        def _timed_ok():
            t0 = time.time()
            status = manager.train(
                _estimator(), did, {"random_state": 42}, show_progress=False,
                timeout=3600,
            )
            dt = time.time() - t0
            # "completed" includes all-subtasks-failed jobs (failure counts
            # toward completion by design) — a benchmark point must have
            # actually trained
            assert status["job_status"] == "completed", status
            result = status["job_result"]
            assert len(result["results"]) == 1 and not result.get("failed"), result
            return dt, result["best_result"].get("mean_cv_score")

        wall, ours_cv = _timed_ok()
        # steady = best of two post-compile passes: stalls are
        # one-sided additive noise (same rationale as bench.py's fastest-3
        # window), and a single noisy second pass once recorded a "steady"
        # 1.7x above the first pass
        steady = min(_timed_ok()[0] for _ in range(2))

        report.append(
            {
                "fraction": frac,
                "n_rows": int(n),
                "sklearn_s": round(float(sk_time), 3),
                "sklearn_extrapolated": extrapolated,
                "framework_s": round(wall, 3),
                "framework_steady_s": round(steady, 3),
                "cv_ours": round(ours_cv, 4) if ours_cv is not None else None,
                "cv_sklearn": round(sk_cv, 4) if sk_cv is not None else None,
            }
        )
        print(
            f"frac {frac:>5.0%} ({n:>7} rows): sklearn {sk_time:7.2f}s"
            f"{'~' if extrapolated else ' '} ours {wall:6.2f}s"
            f" (steady {steady:6.2f}s)"
            f"  cv {ours_cv if ours_cv is not None else float('nan'):.4f}"
            f" vs sk {sk_cv if sk_cv is not None else float('nan'):.4f}"
        )

    out = os.path.join(os.path.dirname(os.path.abspath(__file__)), "SCALING_MEASURED.json")
    points = report
    if os.path.exists(out):
        try:  # merge by fraction into any existing SAME-MODEL curve, so a
            # partial run (any fraction subset) never drops measured points
            with open(out) as f:
                old = json.load(f)
            if old.get("model") == MODEL:
                fresh = {p["fraction"] for p in report}
                points = sorted(
                    [p for p in old.get("points", [])
                     if p.get("fraction") not in fresh] + report,
                    key=lambda p: p["fraction"],
                )
        except (OSError, ValueError):
            pass
    tmp = f"{out}.tmp.{os.getpid()}"
    with open(tmp, "w") as f:
        json.dump({"model": MODEL, "points": points}, f, indent=2)
    os.replace(tmp, out)
    print(f"wrote {out}")


if __name__ == "__main__":
    main()

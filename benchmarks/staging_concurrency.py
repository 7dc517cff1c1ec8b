"""N concurrent jobs, one dataset: O(1) staging uploads per (dataset, device).

ROADMAP item 5 / ISSUE 8 acceptance: before the multi-tenant staged-dataset
cache (data/stage_cache.py), N concurrent jobs over the same public dataset
each re-staged it — N x the ~3.4 s upload the r5 cold breakdown measured,
for bytes already in HBM. This harness runs N jobs in parallel threads,
each with its OWN TrialData instance (the separate-tenant topology: nothing
shared but dataset *content*), and counts actual host->device staging
uploads in both modes:

- cache ON  (default): the stage cache's single-flight upload counter —
  the committed claim is exactly ONE upload per (dataset, device, staged
  form): one for the design matrix, one for the fold tensors.
- cache OFF (``CS230_STAGE_CACHE=0``): the legacy per-TrialData path,
  counted via the ``tpuml_executor_stage_seconds`` histogram observations
  (one per upload) — the N-uploads-per-N-jobs baseline.

The same contract is pinned fast in
tests/test_stage_cache.py::test_concurrent_tenants_stage_once; this
harness is the committed at-scale artifact (covertype-sized matrix) and
runs in the nightly chaos workflow (deploy/ci.sh chaos).

Writes benchmarks/STAGING_CONCURRENCY.json.

Usage: python benchmarks/staging_concurrency.py
"""

from __future__ import annotations

import json
import os
import sys
import threading
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

N_JOBS = int(os.environ.get("STAGE_CONC_JOBS", 8))
TRIALS_PER_JOB = int(os.environ.get("STAGE_CONC_TRIALS", 2))
OUT = os.path.join(
    os.path.dirname(os.path.abspath(__file__)), "STAGING_CONCURRENCY.json"
)


def _run_jobs(datasets, plan, kernel):
    from cs230_distributed_machine_learning_tpu.parallel.trial_map import (
        run_trials,
    )

    barrier = threading.Barrier(len(datasets))
    errors = []

    def job(data):
        try:
            barrier.wait()
            run = run_trials(
                kernel, data, plan,
                [{"var_smoothing": 10.0 ** -(9 + i)} for i in range(TRIALS_PER_JOB)],
            )
            assert run.trial_metrics
        except Exception as e:  # noqa: BLE001
            errors.append(repr(e))

    threads = [threading.Thread(target=job, args=(d,)) for d in datasets]
    t0 = time.perf_counter()
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    wall = time.perf_counter() - t0
    if errors:
        raise RuntimeError(f"{len(errors)} jobs failed: {errors[:3]}")
    return wall


def main() -> None:
    import jax

    from cs230_distributed_machine_learning_tpu.data.datasets import (
        DatasetCache,
    )
    from cs230_distributed_machine_learning_tpu.data.stage_cache import (
        STAGE_CACHE,
    )
    from cs230_distributed_machine_learning_tpu.models.base import TrialData
    from cs230_distributed_machine_learning_tpu.models.registry import (
        get_kernel,
    )
    from cs230_distributed_machine_learning_tpu.obs import REGISTRY
    from cs230_distributed_machine_learning_tpu.ops.folds import (
        build_split_plan,
    )

    base = DatasetCache().get("covertype", "classification")
    X, y = np.asarray(base.X, np.float32), np.asarray(base.y)
    # one TrialData PER JOB: separate tenants share dataset content only
    tenants = lambda: [  # noqa: E731
        TrialData(X=X, y=y, n_classes=base.n_classes) for _ in range(N_JOBS)
    ]
    kernel = get_kernel("GaussianNB")
    plan = build_split_plan(
        y, task="classification", n_folds=3, test_size=0.2, random_state=42
    )

    # ---- cache ON: single-flight, content-fingerprint keyed ----
    os.environ.pop("CS230_STAGE_CACHE", None)
    STAGE_CACHE.clear()
    wall_on = _run_jobs(tenants(), plan, kernel)
    stats = STAGE_CACHE.stats()
    by_key = STAGE_CACHE.uploads_by_key()
    uploads_on = stats["uploads"]
    assert uploads_on == 2, (
        f"expected exactly 2 uploads (X + fold tensors), got {uploads_on}: "
        f"{by_key}"
    )
    assert max(by_key.values()) == 1, by_key

    # ---- cache OFF: the legacy per-TrialData baseline ----
    hist = REGISTRY.histogram("tpuml_executor_stage_seconds")
    os.environ["CS230_STAGE_CACHE"] = "0"
    before = hist.count()
    wall_off = _run_jobs(tenants(), plan, kernel)
    uploads_off = hist.count() - before
    os.environ.pop("CS230_STAGE_CACHE", None)

    out = {
        "metric": "staging_concurrency",
        "backend": jax.default_backend(),
        "device": str(jax.devices()[0]),
        "dataset": f"covertype {X.shape[0]}x{X.shape[1]} f32",
        "n_concurrent_jobs": N_JOBS,
        "trials_per_job": TRIALS_PER_JOB,
        "cache_on": {
            "uploads": uploads_on,
            "uploads_by_key_max": max(by_key.values()),
            "hits": stats["hits"],
            "wall_s": round(wall_on, 3),
        },
        "cache_off": {
            "uploads": uploads_off,
            "wall_s": round(wall_off, 3),
        },
        "upload_reduction": f"{uploads_off}x -> {uploads_on}x",
        "note": (
            "cache ON stages exactly once per (dataset, device, staged "
            "form): 1 design-matrix upload + 1 fold-tensor upload across "
            f"{N_JOBS} concurrent jobs (single-flight: concurrent misses "
            "wait for the one maker). cache OFF re-stages per TrialData — "
            "the per-job upload tax this PR removes. Upload counts are "
            "backend-independent; what an avoided upload is worth in "
            "seconds is not measured on the current code. wall_s is NOT the comparison "
            "metric: the first mode to run (cache ON) pays the one-time "
            "XLA compile both modes then share."
        ),
    }
    with open(OUT, "w") as f:
        json.dump(out, f, indent=1)
    print(json.dumps(out, indent=1))


if __name__ == "__main__":
    main()

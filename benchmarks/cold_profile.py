"""Where does bench.py's COLD pass spend its time?

VERDICT r4 weak #5: driver cold 8.26 s vs steady 3.94 s. This harness runs
ONE bench-shaped job in a fresh process and wall-clocks its phases:

  import+backend  |  dataset load (host)  |  submit->first-result  |  rest

plus, inside the engine, the first dispatch's trace/compile/AOT-load split
is visible via CS230_TRACE_TIMING log lines if enabled. Run it twice: the
second run shows which phase the warm caches actually remove.

Usage: python benchmarks/cold_profile.py

## Measured before/after mode (ISSUE 8)

  python benchmarks/cold_profile.py --measure

runs TWO fresh subprocesses over the same job shape and commits
benchmarks/COLD_PROFILE_MEASURED.json:

- **before**: ``CS230_STAGE_CACHE=0 CS230_PREWARM=0`` — the pre-PR-8 cold
  path: the first job pays executable construction (AOT load / trace +
  first-dispatch XLA compile) and the staging upload inline.
- **after**: the staged-dataset cache on, plus an ``execute``-mode prewarm
  of the job's hint (what a registered agent does in the background
  before its first placement, runtime/prewarm.py) — then the SAME job is
  submitted and measured.

Per pass the engine's own phase accounting is read from the metrics
registry (histogram sum deltas around the measured job): compile
(AOT-load/trace + first-dispatch XLA compile), stage (host->device
uploads), dispatch (device execution window), fetch (device->host). The
committed claim is the reduction of the *cold-path phases* (compile +
stage — the 2.2 s + 3.4 s of the r5 breakdown) and of the job wall.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

T0 = time.time()

OUT = os.path.join(
    os.path.dirname(os.path.abspath(__file__)), "COLD_PROFILE_MEASURED.json"
)
_PASS_MARK = "COLD_PASS_JSON:"

COLD_MODEL = os.environ.get("COLD_MODEL", "LogisticRegression")
COLD_DATASET = os.environ.get("COLD_DATASET", "synthetic_8000x16")
COLD_MEASURE_TRIALS = int(os.environ.get("COLD_MEASURE_TRIALS", 32))
COLD_CV = int(os.environ.get("COLD_CV", 2))


def mark(label, t_prev):
    now = time.time()
    print(f"{label:38s} {now - t_prev:6.2f}s  (t+{now - T0:6.2f})", flush=True)
    return now


def _phase_sums():
    from cs230_distributed_machine_learning_tpu.obs import REGISTRY

    return {
        name: REGISTRY.histogram(name).sum()
        for name in (
            "tpuml_executor_compile_seconds",
            "tpuml_executor_stage_seconds",
            "tpuml_executor_dispatch_seconds",
            "tpuml_executor_fetch_seconds",
        )
    }


def _job_payload():
    import numpy as np

    grid = [float(c) for c in np.logspace(-3, 2, COLD_MEASURE_TRIALS)]
    return {
        "dataset_id": COLD_DATASET,
        "model_details": {
            "model_type": COLD_MODEL,
            "search_type": "GridSearchCV",
            "param_grid": {"C": grid},
        },
        "train_params": {"cv": COLD_CV, "test_size": 0.2, "random_state": 42},
    }


def run_pass(which: str) -> None:
    """One fresh-process measured pass (``--pass before|after``): emits a
    machine-readable JSON line the ``--measure`` parent collects."""
    t_start = time.time()
    from cs230_distributed_machine_learning_tpu.runtime.coordinator import (
        Coordinator,
    )

    coord = Coordinator()
    coord.cache.get(COLD_DATASET, "classification")  # host parse, own line
    setup_s = time.time() - t_start

    prewarm_s = None
    if which == "after":
        # what a registering agent's background prewarm does with the
        # coordinator's hint for this (hot) job shape — executables
        # compiled + dataset staged BEFORE the measured job arrives
        t_pw = time.time()
        coord.executor.prewarm_hint(
            {
                "model_type": COLD_MODEL,
                "dataset_id": COLD_DATASET,
                "parameters": {"C": 1.0},
                "n_trials": COLD_MEASURE_TRIALS,
                "train_params": {
                    "cv": COLD_CV, "test_size": 0.2, "random_state": 42,
                },
            },
            mode="execute",
        )
        prewarm_s = time.time() - t_pw

    def _timed_job():
        sid = coord.create_session()
        t_submit = time.time()
        out = coord.submit_train(sid, _job_payload())
        status = coord.wait_for_completion(sid, out["job_id"], timeout_s=3600)
        assert status["job_status"] in ("completed", "completed_with_failures")
        return time.time() - t_submit

    base = _phase_sums()
    job_wall_s = _timed_job()  # the FIRST job this process sees: cold
    deltas = {k: v - base[k] for k, v in _phase_sums().items()}
    steady_wall_s = _timed_job()  # same job, warm caches: the steady floor

    record = {
        "pass": which,
        "setup_s": round(setup_s, 3),
        "prewarm_background_s": (
            round(prewarm_s, 3) if prewarm_s is not None else None
        ),
        "job_wall_s": round(job_wall_s, 3),
        "steady_wall_s": round(steady_wall_s, 3),
        # bench.py's cold_s definition: first-job wall minus the steady
        # floor of the identical job in the same process — the number the
        # ROADMAP <=5 s bar is stated against
        "cold_overhead_s": round(max(job_wall_s - steady_wall_s, 0.0), 3),
        # the ISSUE-8 phase names, from the engine's own accounting:
        "aot_load_or_compile_s": round(
            deltas["tpuml_executor_compile_seconds"], 3
        ),
        "staging_upload_s": round(deltas["tpuml_executor_stage_seconds"], 3),
        "first_batch_dispatch_s": round(
            deltas["tpuml_executor_dispatch_seconds"], 3
        ),
        "result_fetch_s": round(deltas["tpuml_executor_fetch_seconds"], 3),
    }
    record["cold_path_s"] = round(
        record["aot_load_or_compile_s"] + record["staging_upload_s"], 3
    )
    print(_PASS_MARK + json.dumps(record), flush=True)


def measure() -> None:
    """Parent of the two fresh-process passes; writes the committed JSON.

    A chip belongs to one process: both children run and exit BEFORE this
    parent touches a JAX backend (its first touch is the ``backend`` field
    of the document, below the loop). Keep that order."""
    import jax

    passes = {}
    for which, env_over in (
        ("before", {"CS230_STAGE_CACHE": "0", "CS230_PREWARM": "0"}),
        ("after", {"CS230_PREWARM": "execute"}),
    ):
        env = {
            k: v for k, v in os.environ.items()
            if k not in ("CS230_STAGE_CACHE", "CS230_PREWARM")
        }
        env.update(env_over)
        proc = subprocess.run(
            [sys.executable, os.path.abspath(__file__), "--pass", which],
            env=env, capture_output=True, text=True, timeout=3600,
        )
        sys.stderr.write(proc.stderr[-2000:])
        if proc.returncode != 0:
            raise RuntimeError(
                f"{which} pass failed (rc={proc.returncode}):\n"
                f"{proc.stdout[-2000:]}"
            )
        line = next(
            ln for ln in proc.stdout.splitlines() if ln.startswith(_PASS_MARK)
        )
        passes[which] = json.loads(line[len(_PASS_MARK):])

    def _red(key):
        b, a = passes["before"][key], passes["after"][key]
        return round(1.0 - a / b, 3) if b else None

    out = {
        "metric": "cold_profile_measured",
        "backend": jax.default_backend(),
        "device": str(jax.devices()[0]),
        "model": COLD_MODEL,
        "dataset": COLD_DATASET,
        "n_trials": COLD_MEASURE_TRIALS,
        "cv": COLD_CV,
        "before": passes["before"],
        "after": passes["after"],
        "cold_overhead_reduction": _red("cold_overhead_s"),
        "cold_path_reduction": _red("cold_path_s"),
        "job_wall_reduction": _red("job_wall_s"),
        "note": (
            "Fresh process per pass; the measured job is identical — only "
            "the PR-8 data-plane valves differ. 'after' runs the "
            "execute-mode prewarm an agent performs in the background "
            "between register and first placement (its wall is reported "
            "separately as prewarm_background_s: idle-window work, not "
            "first-job latency). cold_overhead_s is bench.py's cold_s "
            "definition (first job minus steady floor of the identical "
            "job) — the ROADMAP <=5 s bar's unit; cold_path_s sums the "
            "engine's compile+stage phase accounting for the first job "
            "(on a one-chunk job the compile histogram includes the "
            "first-dispatch compute, so cold_overhead_s is the honest "
            "headline). Measured on the backend named in this document; "
            "a CPU run is a count of phases, not a device time."
        ),
    }
    with open(OUT, "w") as f:
        json.dump(out, f, indent=1)
    print(json.dumps(out, indent=1))


def main() -> None:
    t = T0
    from sklearn.linear_model import LogisticRegression
    from sklearn.model_selection import RandomizedSearchCV
    from scipy.stats import loguniform

    t = mark("sklearn/scipy imports", t)

    import jax

    jax.devices()
    t = mark("jax import + backend init", t)

    from cs230_distributed_machine_learning_tpu import MLTaskManager
    from cs230_distributed_machine_learning_tpu.runtime.coordinator import Coordinator
    from cs230_distributed_machine_learning_tpu.parallel.mesh import trial_mesh

    t = mark("framework imports", t)

    manager = MLTaskManager(coordinator=Coordinator(mesh=trial_mesh()))
    t = mark("coordinator init", t)

    # force the dataset into the host cache before the job so its cost is
    # its own line
    manager._coordinator.cache.get("covertype", "classification")
    t = mark("dataset load (host)", t)

    n_trials = int(os.environ.get("COLD_TRIALS", 1000))
    search = RandomizedSearchCV(
        LogisticRegression(max_iter=200),
        {"C": loguniform(1e-3, 1e2), "tol": [1e-4, 1e-3]},
        n_iter=n_trials, cv=5, random_state=0,
    )
    status = manager.train(search, "covertype", {"random_state": 42},
                           show_progress=False, timeout=3600)
    assert status["job_status"] == "completed"
    t = mark(f"cold pass ({n_trials} trials)", t)

    t0 = time.time()
    status = manager.train(search, "covertype", {"random_state": 42},
                           show_progress=False, timeout=3600)
    assert status["job_status"] == "completed"
    mark("steady pass", t0)


if __name__ == "__main__":
    if "--measure" in sys.argv:
        measure()
    elif "--pass" in sys.argv:
        run_pass(sys.argv[sys.argv.index("--pass") + 1])
    else:
        main()

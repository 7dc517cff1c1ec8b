"""Out-of-core streaming micro-benchmark: the OOM repro + overlap profile.

Two measurements, one committed document (benchmarks/STREAMING_MICRO.json):

1. **oom_repro** — the acceptance pin for ROADMAP's "fits in HBM" break:
   a dataset ≥10x the stage-cache budget (CS230_STAGE_CACHE_MB=2 against
   a ~20 MB design matrix) is fitted through the trial engine for BOTH
   streamed families — LogReg (Nesterov) and a tree-histogram forest —
   with ``CS230_STAGE_STRICT=1`` turning the budget into a hard wall
   (the portable test double for a device OOM):
   - ``CS230_STREAM=0`` (legacy single-shot staging) must FAIL with
     ``StageBudgetExceeded``;
   - ``CS230_STREAM=auto`` must COMPLETE, block working set inside the
     budget, and report the same-quality score.

2. **overlap_profile** — what double buffering actually hides: a
   row-block pass whose per-block compute exceeds the per-block
   host-fetch+upload wall, run with ``CS230_STREAM_DOUBLE_BUFFER`` on
   and off in INTERLEAVED pairs (logreg_profile methodology: paired
   reps cancel thermal/background drift; each rep uses a fresh cache so
   every block pays its upload). Reported per state: pass wall, upload
   wall, consumer wait, hidden seconds and the hidden fraction
   ``1 - wait/upload``. The committed acceptance bar: ≥50% of the
   transfer wall hidden with the buffer ON (off is structurally ~0).

Usage: python benchmarks/streaming_micro.py [--quick]
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

OUT = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                   "STREAMING_MICRO.json")

# the OOM-repro geometry: 80000 x 64 f32 = 20.5 MB vs a 2 MB budget
# (10.2x); 4096-row blocks = 1 MB each, so streamed working sets (a
# double-buffered pair + folds) stay well inside the wall
OOM_ENV = {
    "CS230_STAGE_STRICT": "1",
    "CS230_STAGE_CACHE_MB": "2",
    "CS230_STREAM_BLOCK_ROWS": "4096",
}
N_OOM, D_OOM, C_OOM = 80_000, 64, 7


def _set_env(kv):
    old = {}
    for k, v in kv.items():
        old[k] = os.environ.get(k)
        if v is None:
            os.environ.pop(k, None)
        else:
            os.environ[k] = v
    return old


def _oom_data():
    from cs230_distributed_machine_learning_tpu.models.base import TrialData

    rng = np.random.default_rng(0)
    X = rng.normal(size=(N_OOM, D_OOM)).astype(np.float32)
    W = rng.normal(size=(D_OOM, C_OOM))
    y = np.argmax(
        X @ W + rng.normal(scale=0.5, size=(N_OOM, C_OOM)), 1
    ).astype(np.int32)
    return TrialData(X=X, y=y, n_classes=C_OOM)


def _run_engine(kernel_name, params, data, mode):
    from cs230_distributed_machine_learning_tpu.data import stage_cache as sc
    from cs230_distributed_machine_learning_tpu.models.registry import get_kernel
    from cs230_distributed_machine_learning_tpu.ops.folds import build_split_plan
    from cs230_distributed_machine_learning_tpu.parallel.trial_map import run_trials

    sc.STAGE_CACHE.clear()
    old = _set_env({**OOM_ENV, "CS230_STREAM": mode})
    plan = build_split_plan(
        np.asarray(data.y), task="classification", n_folds=0
    )
    t0 = time.perf_counter()
    try:
        out = run_trials(get_kernel(kernel_name), data, plan, params)
        wall = time.perf_counter() - t0
        return {
            "outcome": "completed",
            "wall_s": round(wall, 2),
            "accuracy": round(out.trial_metrics[0]["accuracy"], 4),
            "n_dispatches": out.n_dispatches,
        }
    except sc.StageBudgetExceeded as e:
        return {
            "outcome": "failed",
            "error": "StageBudgetExceeded",
            "message": str(e)[:200],
        }
    finally:
        _set_env(old)
        sc.STAGE_CACHE.clear()


def oom_repro(quick: bool):
    data = _oom_data()
    budget_mb = float(OOM_ENV["CS230_STAGE_CACHE_MB"])
    footprint_mb = data.X.nbytes / 1e6
    families = {
        "logreg_nesterov": (
            "LogisticRegression",
            [{"C": 1.0, "max_iter": 5 if quick else 10}],
        ),
        "rf_histogram": (
            "RandomForestClassifier",
            [{"n_estimators": 1 if quick else 2, "max_depth": 4,
              "n_bins": 16, "random_state": 0}],
        ),
    }
    out = {
        "dataset": f"{N_OOM}x{D_OOM} f32 = {footprint_mb:.1f} MB",
        "stage_budget_mb": budget_mb,
        "footprint_over_budget_x": round(footprint_mb / budget_mb, 1),
        "block_rows": int(OOM_ENV["CS230_STREAM_BLOCK_ROWS"]),
        "families": {},
    }
    ok = True
    for fam, (kern, params) in families.items():
        legacy = _run_engine(kern, params, data, "0")
        streamed = _run_engine(kern, params, data, "auto")
        out["families"][fam] = {"stream_off": legacy, "stream_auto": streamed}
        ok = ok and legacy["outcome"] == "failed" \
            and streamed["outcome"] == "completed"
    out["acceptance"] = {
        "rule": "CS230_STREAM=0 fails with StageBudgetExceeded AND "
                "CS230_STREAM=auto completes, for both families",
        "passed": ok,
    }
    return out


def overlap_profile(quick: bool):
    import jax
    import jax.numpy as jnp

    from cs230_distributed_machine_learning_tpu.data.stage_cache import (
        StagedDatasetCache,
    )
    from cs230_distributed_machine_learning_tpu.data.streaming import (
        RowBlockStreamer, array_block_source, plan_blocks,
    )

    n, d = (16_384, 256) if quick else (65_536, 256)
    rows = 4096
    reps = 2 if quick else 4
    compute_iters = 8
    rng = np.random.default_rng(1)
    arr = rng.normal(size=(n, d)).astype(np.float32)
    plan = plan_blocks(n, row_bytes=d * 4, rows=rows)

    @jax.jit
    def burn(blk, M):
        # per-block compute sized to exceed the per-block upload wall —
        # the regime streaming targets (compute-bound passes)
        acc = blk
        for _ in range(compute_iters):
            acc = jnp.tanh(acc @ M)
        return acc.sum()

    M = jnp.asarray(rng.normal(size=(d, d)).astype(np.float32) * 0.05)
    # warm the executable outside the timed reps
    jax.block_until_ready(burn(jnp.zeros((rows, d), jnp.float32), M))

    def one_pass(db: bool):
        cache = StagedDatasetCache()  # fresh: every block pays its upload
        s = RowBlockStreamer(
            ("fp", ("bench", 0), "block", "overlap"),
            array_block_source(arr, plan),
            lambda b: jnp.asarray(b),
            plan,
            double_buffer=db,
            cache=cache,
            row_shape=(d,),
        )
        t0 = time.perf_counter()
        tot = 0.0
        for _i, _start, blk in s.iter_blocks():
            tot += float(burn(blk, M))
        wall = time.perf_counter() - t0
        st = s.stats
        return {
            "pass_wall_s": wall,
            "upload_s": st["upload_s"],
            "wait_s": st["wait_s"],
            "hidden_s": max(st["upload_s"] - st["wait_s"], 0.0),
            "checksum": tot,
        }

    runs = {"double_buffer_on": [], "double_buffer_off": []}
    for _ in range(reps):  # interleaved pairs: on, off, on, off...
        runs["double_buffer_on"].append(one_pass(True))
        runs["double_buffer_off"].append(one_pass(False))
    # identical block set + executable => identical checksums across states
    sums = {round(r["checksum"], 3) for rs in runs.values() for r in rs}
    assert len(sums) == 1, f"state-dependent result: {sums}"

    def med(rs, k):
        return float(np.median([r[k] for r in rs]))

    states = {}
    for state, rs in runs.items():
        up, wait = med(rs, "upload_s"), med(rs, "wait_s")
        states[state] = {
            "pass_wall_s": round(med(rs, "pass_wall_s"), 4),
            "upload_s": round(up, 4),
            "wait_s": round(wait, 4),
            "hidden_s": round(max(up - wait, 0.0), 4),
            "hidden_frac": round(max(0.0, 1.0 - wait / up), 4)
            if up > 0 else None,
        }
    hidden_on = states["double_buffer_on"]["hidden_frac"] or 0.0
    return {
        "dataset": f"{n}x{d} f32, {plan.n_blocks} blocks of {rows} rows "
                   f"({rows * d * 4 / 1e6:.1f} MB each)",
        "reps_interleaved_pairs": reps,
        "compute_per_block": f"{compute_iters}x tanh-matmul [rows,d]@[d,d]",
        "states": states,
        "wall_saved_s": round(
            states["double_buffer_off"]["pass_wall_s"]
            - states["double_buffer_on"]["pass_wall_s"], 4
        ),
        "acceptance": {
            "rule": ">=50% of the transfer wall hidden with the "
                    "double buffer ON",
            "hidden_frac_on": hidden_on,
            "passed": hidden_on >= 0.5,
        },
    }


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--quick", action="store_true",
                    help="smaller shapes / fewer reps (committed numbers "
                         "use the full geometry)")
    args = ap.parse_args()

    import jax

    out = {
        "metric": "out_of_core_streaming_micro",
        "backend": jax.default_backend(),
        "device": str(jax.devices()[0]),
        "oom_repro": oom_repro(args.quick),
        "overlap_profile": overlap_profile(args.quick),
        "note": (
            "oom_repro uses CS230_STAGE_STRICT=1 as the portable stand-in "
            "for a device OOM: the budget wall fires exactly where a real "
            "HBM allocation would. The overlap profile's hidden fraction "
            "is 1 - wait/upload over a fresh-cache pass (every block pays "
            "its upload); interleaved on/off pairs cancel drift. On this "
            "CPU backend the upload is a host->XLA copy; the hidden "
            "share of a real host->HBM link is not measured."
        ),
    }
    with open(OUT, "w") as f:
        json.dump(out, f, indent=1)
    print(json.dumps(out, indent=1))
    ok = (out["oom_repro"]["acceptance"]["passed"]
          and out["overlap_profile"]["acceptance"]["passed"])
    return 0 if ok else 1


if __name__ == "__main__":
    main()

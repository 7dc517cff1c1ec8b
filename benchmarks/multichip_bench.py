"""Multi-device trial-throughput scaling curve (ROADMAP item 4 acceptance).

The driver's MULTICHIP_r0N.json records only prove the mesh paths
*correct*. This harness measures a *throughput* curve:
trials/s at 1/2/4/8 devices with an efficiency-vs-ideal column, run
end-to-end through the mesh-sharded trial engine (``run_trials`` with a
1-D ``trials`` mesh) and the mesh-aware stage cache (one host upload per
(dataset, host), ICI replication — docs/ARCHITECTURE.md "Elastic trial
fabric").

Modes:

- **parent (default)**: for each count in ``--devices`` (default 1,2,4,8)
  spawn a fresh subprocess with
  ``XLA_FLAGS=--xla_force_host_platform_device_count=N`` and
  ``JAX_PLATFORMS=cpu`` — the same forced-host-device pattern
  tests/test_distributed_mesh.py and conftest.py use — collect its
  measurement, and write ``benchmarks/MULTICHIP_BENCH_r01.json`` (or
  ``--out``). Forced host devices share one CPU: this mode checks the
  control flow and counts, it measures no device rate.
- **worker** (``--worker N``, internal): measure trials/s over this
  process's devices and print one JSON line.
- **``--native``**: measure over the REAL local devices of this process's
  backend (1..len(jax.devices()), powers of two) instead of forced host
  devices, all in THIS process (one process owns the chips; no children).

Gate (``--check``, on by default in parent mode): with both endpoints of
the curve measured, at least one config must scale >1.0x from min to max
device count — the forced-host-device curve shares one CPU's cores, so
ideal scaling is NOT expected there; beating one device at all is the
CPU-provable part of the contract.

Usage:
    python benchmarks/multichip_bench.py                  # full curve
    python benchmarks/multichip_bench.py --devices 1,2 --quick   # CI smoke
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

OUT_DEFAULT = os.path.join(REPO, "benchmarks", "MULTICHIP_BENCH_r01.json")

#: benchmark configs: name -> (builder kwargs). "logreg" exercises the
#: generic vmapped+sharded dispatch path; "forest" the chunked-fit
#: protocol with trial-axis NamedSharding (replicated data).
CONFIGS = {
    # shapes chosen where the per-trial solver scan dominates over the
    # matmul widths: on the forced-host CPU mesh a single device's
    # intra-op pool already parallelizes big matmuls across every core,
    # so small-op/many-iteration workloads are where cross-device
    # parallelism is visible at all (probed 2026-08; big-matmul shapes
    # measured ~1.0x flat)
    "logreg": {
        "model_type": "LogisticRegression",
        "n": 1024, "d": 8, "n_classes": 3, "n_trials": 128, "cv": 2,
        "params": lambda i: {"C": 10.0 ** ((i % 16) / 4.0 - 2.0)},
    },
    "forest": {
        "model_type": "RandomForestClassifier",
        "n": 1024, "d": 16, "n_classes": 3, "n_trials": 32, "cv": 2,
        "params": lambda i: {
            "n_estimators": 20, "max_depth": 6,
            "min_samples_split": 2 + (i % 4),
        },
    },
}


def _make_data(cfg, seed=0):
    import numpy as np

    from cs230_distributed_machine_learning_tpu.models.base import TrialData

    rng = np.random.RandomState(seed)
    n, d, k = cfg["n"], cfg["d"], cfg["n_classes"]
    X = rng.randn(n, d).astype(np.float32)
    W = rng.randn(d, k).astype(np.float32)
    y = np.argmax(X @ W + 0.5 * rng.randn(n, k), axis=1).astype(np.int32)
    return TrialData(X=X, y=y, n_classes=k)


def _measure_config(name, cfg, mesh, reps):
    """Trials/s of one config on ``mesh``: one warmup run (compile +
    staging paid), then ``reps`` timed runs over the steady path."""
    from cs230_distributed_machine_learning_tpu.models.registry import get_kernel
    from cs230_distributed_machine_learning_tpu.ops.folds import build_split_plan
    from cs230_distributed_machine_learning_tpu.parallel.trial_map import run_trials

    kernel = get_kernel(cfg["model_type"])
    data = _make_data(cfg)
    import numpy as np

    plan = build_split_plan(
        np.asarray(data.y), task="classification", n_folds=cfg["cv"],
        test_size=0.2, random_state=0,
    )
    params = [cfg["params"](i) for i in range(cfg["n_trials"])]
    run_trials(kernel, data, plan, params, mesh=mesh)  # warmup
    t0 = time.perf_counter()
    best = None
    for _ in range(reps):
        res = run_trials(kernel, data, plan, params, mesh=mesh)
        best = res.device_best or best
    wall = time.perf_counter() - t0
    return {
        "trials_per_s": round(cfg["n_trials"] * reps / wall, 2),
        "wall_s": round(wall, 3),
        "n_trials": cfg["n_trials"],
        "reps": reps,
        "n_dispatches": res.n_dispatches,
        "best_score": (
            round(float(best[1]), 6) if best is not None
            else round(
                max(m["mean_cv_score"] for m in res.trial_metrics), 6
            )
        ),
    }


def _worker(n_devices, reps, only=None):
    import jax

    from cs230_distributed_machine_learning_tpu.data import stage_cache as sc
    from cs230_distributed_machine_learning_tpu.parallel.mesh import trial_mesh

    devs = jax.devices()[:n_devices]
    assert len(devs) == n_devices, (
        f"wanted {n_devices} devices, backend has {len(jax.devices())}"
    )
    mesh = trial_mesh(devs) if n_devices > 1 else None
    out = {"devices": n_devices, "backend": jax.default_backend(),
           "configs": {}}
    # delta-based accounting: --native runs several points in ONE process
    # and stats() is process-cumulative, so each point must report only
    # its own traffic (subprocess mode starts from zero either way)
    before = sc.STAGE_CACHE.stats()
    for name, cfg in CONFIGS.items():
        if only and name not in only:
            continue
        out["configs"][name] = _measure_config(name, cfg, mesh, reps)
    stats = sc.STAGE_CACHE.stats()
    # the mesh-cache contract, observable per curve point: host uploads
    # stay O(datasets) while replications carry the mesh forms
    out["stage_cache"] = {
        k: stats[k] - before[k]
        for k in ("uploads", "replications", "host_upload_bytes", "ici_bytes")
    }
    print(json.dumps(out))
    return 0


def _spawn_point(n, reps, only, timeout_s=1800):
    env = dict(os.environ)
    flags = env.get("XLA_FLAGS", "")
    import re

    flag = f"--xla_force_host_platform_device_count={n}"
    if "xla_force_host_platform_device_count" in flags:
        flags = re.sub(
            r"--?xla_force_host_platform_device_count=\d+", flag, flags
        )
    else:
        flags = (flags + " " + flag).strip()
    env["XLA_FLAGS"] = flags
    env["JAX_PLATFORMS"] = "cpu"
    cmd = [sys.executable, os.path.abspath(__file__),
           "--worker", str(n), "--reps", str(reps)]
    if only:
        cmd += ["--only", ",".join(only)]
    proc = subprocess.run(
        cmd, capture_output=True, text=True, timeout=timeout_s, cwd=REPO,
        env=env,
    )
    if proc.returncode != 0:
        return {"devices": n, "error": f"exit {proc.returncode}",
                "stderr_tail": proc.stderr[-2000:]}
    for line in reversed(proc.stdout.strip().splitlines()):
        line = line.strip()
        if line.startswith("{"):
            try:
                return json.loads(line)
            except json.JSONDecodeError:
                continue
    return {"devices": n, "error": "no JSON on stdout",
            "stdout_tail": proc.stdout[-500:]}


def _curve(points):
    """Attach the efficiency-vs-ideal column: eff(N) = (tps_N / tps_1) / N
    per config (1.0 = perfect linear scaling over the base count)."""
    base = next((p for p in points if not p.get("error")), None)
    curve = []
    for p in points:
        row = {"devices": p.get("devices")}
        if p.get("error"):
            row["error"] = p["error"]
            curve.append(row)
            continue
        row["configs"] = {}
        for name, m in p["configs"].items():
            entry = dict(m)
            b = (base or {}).get("configs", {}).get(name)
            if b and b["trials_per_s"] > 0 and base is not p:
                speedup = m["trials_per_s"] / b["trials_per_s"]
                ideal = p["devices"] / base["devices"]
                entry["speedup_vs_base"] = round(speedup, 3)
                entry["efficiency_vs_ideal"] = round(speedup / ideal, 3)
            elif base is p:
                entry["speedup_vs_base"] = 1.0
                entry["efficiency_vs_ideal"] = 1.0
            row["configs"][name] = entry
        row["stage_cache"] = p.get("stage_cache")
        curve.append(row)
    return curve


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--worker", type=int, default=None,
                    help="internal: measure over this process's devices")
    ap.add_argument("--devices", default="1,2,4,8")
    ap.add_argument("--reps", type=int, default=None)
    ap.add_argument("--quick", action="store_true",
                    help="fewer reps (CI smoke)")
    ap.add_argument("--only", default=None,
                    help="comma-separated config subset")
    ap.add_argument("--native", action="store_true",
                    help="measure over the real local devices in-process "
                         "instead of forced host "
                         "devices in subprocesses")
    ap.add_argument("--out", default=OUT_DEFAULT)
    ap.add_argument("--no-check", dest="check", action="store_false",
                    help="skip the >1.0x min->max scaling gate")
    args = ap.parse_args()
    reps = args.reps or (1 if args.quick else 3)
    only = [s for s in (args.only or "").split(",") if s] or None

    if args.worker is not None:
        return _worker(args.worker, reps, only)

    import platform

    if args.native:
        import jax

        n_all = len(jax.devices())
        counts = [c for c in (1, 2, 4, 8, 16, 32) if c <= n_all]
        points = []
        for n in counts:
            # in-process: executable/stage caches key on the mesh
            # signature, so successive counts don't collide
            import io
            from contextlib import redirect_stdout

            buf = io.StringIO()
            with redirect_stdout(buf):
                _worker(n, reps, only)
            points.append(json.loads(buf.getvalue().strip().splitlines()[-1]))
        mode = f"native ({jax.default_backend()})"
    else:
        counts = [int(c) for c in args.devices.split(",") if c.strip()]
        points = [_spawn_point(n, reps, only) for n in counts]
        mode = "forced-host-devices (XLA_FLAGS) subprocesses"

    doc = {
        "run": "r01",
        "mode": mode,
        "host": platform.node(),
        "device_counts": counts,
        "curve": _curve(points),
        "note": (
            "trials/s through run_trials on a 1-D trials mesh, steady "
            "state (warmup excluded), mesh-aware stage cache on. "
            "efficiency_vs_ideal = speedup / ideal-linear; the CPU "
            "forced-host-device points share one host's cores, so "
            "sub-ideal efficiency there is expected — the committed "
            "contract on CPU is >1.0x min->max scaling on >=1 config."
        ),
    }
    if not args.native:
        doc["device_curve"] = (
            "not measured: forced host devices; run `multichip_bench.py "
            "--native` on a host with chips"
        )

    ok_points = [p for p in doc["curve"] if not p.get("error")]
    gate = None
    if args.check and len(ok_points) >= 2:
        lo, hi = ok_points[0], ok_points[-1]
        ratios = {
            name: round(
                hi["configs"][name]["trials_per_s"]
                / lo["configs"][name]["trials_per_s"], 3,
            )
            for name in hi.get("configs", {})
            if name in lo.get("configs", {})
            and lo["configs"][name]["trials_per_s"] > 0
        }
        gate = {
            "base_devices": lo["devices"], "top_devices": hi["devices"],
            "scaling_ratios": ratios,
            "passed": any(r > 1.0 for r in ratios.values()),
        }
        doc["gate"] = gate

    with open(args.out, "w") as f:
        json.dump(doc, f, indent=2)
        f.write("\n")
    for p in doc["curve"]:
        if p.get("error"):
            print(f"devices={p['devices']}: ERROR {p['error']}")
            continue
        row = ", ".join(
            f"{name}={m['trials_per_s']}/s"
            f" (eff {m.get('efficiency_vs_ideal', '-')})"
            for name, m in p["configs"].items()
        )
        print(f"devices={p['devices']}: {row}")
    print(json.dumps({"out": args.out, "gate": gate}))
    if gate is not None and not gate["passed"]:
        print("GATE FAILED: no config scaled >1.0x "
              f"{gate['base_devices']}->{gate['top_devices']} devices",
              file=sys.stderr)
        return 2
    if any(p.get("error") for p in doc["curve"]):
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())

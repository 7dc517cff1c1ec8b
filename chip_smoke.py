"""Chip smoke: the system's main path, once, on the TPU, in one process.

    python chip_smoke.py              # flagship search + REST surface
                                      # (+ the mesh leg when >= 4 chips)
    python chip_smoke.py --families   # also one job per Pallas-backed family

1. The flagship search (bench.py's own: 1000-trial RandomizedSearchCV
   LogisticRegression on the built-in covertype, cv=5) through
   ``MLTaskManager.train`` -> Coordinator -> LocalExecutor -> run_trials ->
   the packed Pallas fit, cold then warm, with sklearn parity on sampled
   trials.
2. The same process as a server: the REST surface on a loopback port over a
   cluster coordinator with one local executor, driven by
   ``MLTaskManager(url=...)`` over SSE.
3. With four chips visible, the flagship again on ``trial_mesh`` of four.

The script fails when ``jax.devices()[0].platform != "tpu"``: it never sets
``JAX_PLATFORMS``, never retries on the CPU, and every check raises. One
process owns the chip; the server is a thread of it. The last line of
stdout is the result object the driver reads.
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import sys
import tempfile
import threading
import time
import traceback
from typing import Any, Dict, List, Optional

FLAGSHIP_DATASET = "covertype"  # 116 202 x 54, 7 classes, from a seed
FLAGSHIP_TRIALS = 1000          # -> one 1024-trial packed chunk
REST_TRIALS = 128               # -> one 128-trial packed chunk
CV = 5
MAX_ITER = 200
#: |mean_cv_score - sklearn| bound of tests/test_search_parity.py
PARITY_TOL = 0.02


class SmokeFailure(RuntimeError):
    """A check of the smoke did not hold."""


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


# ---------------------------------------------------------------- device

def device_line() -> Dict[str, Any]:
    """Print (first) what this process computes on; return the device
    record of the result object."""
    import importlib.metadata as md

    import jax
    import jaxlib

    from cs230_distributed_machine_learning_tpu.utils import backend
    from cs230_distributed_machine_learning_tpu.utils.jax_setup import (
        compile_cache_dir, setup_jax,
    )

    setup_jax()  # the entry points' own setup: places the compile cache
    d = backend.describe()
    cache = compile_cache_dir()
    entries = len(os.listdir(cache)) if os.path.isdir(cache) else 0
    try:
        libtpu = md.version("libtpu")
    except md.PackageNotFoundError:
        libtpu = "absent"
    print(
        f"device: platform={d['platform']} device_kind={d['device_kind']!r} "
        f"count={d['count']} default_backend={d['backend']} "
        f"jax={jax.__version__} jaxlib={jaxlib.__version__} libtpu={libtpu} "
        f"compile_cache={cache} entries={entries}",
        flush=True,
    )
    return {
        "platform": d["platform"], "kind": d["device_kind"],
        "count": d["count"],
    }


def require_tpu(device: Dict[str, Any]) -> None:
    from cs230_distributed_machine_learning_tpu.utils import backend

    if not backend.on_tpu():
        sys.exit(
            "chip_smoke: no TPU: jax.devices()[0].platform is "
            f"{device['platform']!r}; this script runs on the chip only"
        )
    for valve in ("CS230_PALLAS_INTERPRET", "CS230_FUSED_STEP",
                  "CS230_MASKED_GRAD"):
        check(valve not in os.environ, f"{valve} is set; the smoke runs "
              "the default (auto) paths compiled")


# -------------------------------------------------------------- workload

def _distributions() -> Dict[str, Any]:
    from scipy.stats import loguniform

    return {"C": loguniform(1e-3, 1e2), "tol": [1e-4, 1e-3]}


def flagship_search(n_trials: int, max_iter: int = MAX_ITER, cv: int = CV):
    from sklearn.linear_model import LogisticRegression
    from sklearn.model_selection import RandomizedSearchCV

    return RandomizedSearchCV(
        LogisticRegression(max_iter=max_iter), _distributions(),
        n_iter=n_trials, cv=cv, random_state=0,
    )


def rest_search(n_trials: int, max_iter: int = MAX_ITER, cv: int = CV):
    """A slice of the flagship search the REST transport can carry: it
    serializes list-valued grids only (client/introspection.py), so the C
    values of the flagship's first ``n_trials`` draws go as a list."""
    from sklearn.linear_model import LogisticRegression
    from sklearn.model_selection import ParameterSampler, RandomizedSearchCV

    draws = ParameterSampler(_distributions(), n_iter=n_trials, random_state=0)
    return RandomizedSearchCV(
        LogisticRegression(max_iter=max_iter),
        {"C": sorted(p["C"] for p in draws), "tol": [1e-4, 1e-3]},
        n_iter=n_trials, cv=cv, random_state=0,
    )


def _counter(name: str, **labels: str) -> float:
    from cs230_distributed_machine_learning_tpu.obs import REGISTRY

    return REGISTRY.counter(name).value(**labels)


def _engine_counters() -> Dict[str, float]:
    return {
        "hits": _counter("tpuml_executable_cache_hits_total"),
        "misses": _counter("tpuml_executable_cache_misses_total"),
        "dispatch_s": _counter(
            "tpuml_executor_device_seconds_total", phase="dispatch"
        ),
    }


def train_once(manager, search, dataset: str, timeout: float, **kw):
    """One job through ``MLTaskManager.train``; the wall ends when the host
    holds the fetched results (train returns the terminal status)."""
    t0 = time.perf_counter()
    status = manager.train(
        search, dataset, {"random_state": 42}, show_progress=False,
        timeout=timeout, **kw,
    )
    return status, time.perf_counter() - t0


def check_job(status: Dict[str, Any], n_trials: int, what: str) -> List[dict]:
    """``job_status`` alone proves nothing: direct mode reports
    ``completed`` with every trial in ``failed``."""
    import math

    check(status.get("job_status") == "completed",
          f"{what}: job_status={status.get('job_status')!r}")
    result = status.get("job_result") or {}
    failed = result.get("failed") or []
    check(failed == [], f"{what}: {len(failed)} failed trials, first: "
          f"{failed[:1]}")
    results = result.get("results") or []
    check(len(results) == n_trials,
          f"{what}: {len(results)} results, expected {n_trials}")
    bad = [r for r in results if not math.isfinite(r["mean_cv_score"])]
    check(not bad, f"{what}: {len(bad)} non-finite mean_cv_score")
    check(result.get("best_result") is not None, f"{what}: no best_result")
    return results


def flagship_leg(dataset: str, n_trials: int, *, max_iter: int = MAX_ITER,
                 cv: int = CV, mesh=None, timeout: float = 900.0):
    """Cold then warm flagship job on one coordinator; returns what was
    observed. Device-independent checks only — the chip's are
    ``check_device_work``/``check_packed_path``."""
    from cs230_distributed_machine_learning_tpu import MLTaskManager
    from cs230_distributed_machine_learning_tpu.runtime.coordinator import (
        Coordinator,
    )

    coord = Coordinator(mesh=mesh)
    manager = MLTaskManager(coordinator=coord)
    search = flagship_search(n_trials, max_iter, cv)
    c0 = _engine_counters()
    cold, cold_s = train_once(manager, search, dataset, timeout)
    c1 = _engine_counters()
    warm, warm_s = train_once(manager, search, dataset, timeout)
    c2 = _engine_counters()
    check_job(cold, n_trials, "cold job")
    results = check_job(warm, n_trials, "warm job")
    check(c2["misses"] == c1["misses"],
          f"warm job built {c2['misses'] - c1['misses']:.0f} fresh executables")
    check(c2["hits"] > c1["hits"], "warm job hit no cached executable")
    return {
        "results": results,
        "best": warm["job_result"]["best_result"],
        "cold_s": cold_s,
        "warm_s": warm_s,
        "fresh_executables_cold": c1["misses"] - c0["misses"],
        "dispatch_s_warm": c2["dispatch_s"] - c1["dispatch_s"],
        "cost": coord.job_cost(manager.job_id),
        "data": coord.cache.get(dataset, "classification"),
    }


def check_sklearn_parity(data, results: List[dict], *, n_check: int = 3,
                         max_iter: int = MAX_ITER, cv: int = CV,
                         tol: float = PARITY_TOL) -> List[dict]:
    """Sampled trials across the C range against sklearn's own
    ``cross_val_score`` on the same folds (the engine builds its fold masks
    with sklearn's StratifiedKFold, ops/folds.py)."""
    import numpy as np
    from sklearn.linear_model import LogisticRegression
    from sklearn.model_selection import cross_val_score

    from cs230_distributed_machine_learning_tpu.utils.flops import (
        stratified_by,
    )

    X, y = np.asarray(data.X), np.asarray(data.y)
    rows = []
    for r in stratified_by(results, lambda r: r["parameters"]["C"], n_check):
        p = r["parameters"]
        model = LogisticRegression(max_iter=max_iter, C=p["C"], tol=p["tol"])
        sk = float(cross_val_score(model, X, y, cv=cv).mean())
        rows.append({"C": p["C"], "tol": p["tol"],
                     "ours": r["mean_cv_score"], "sklearn": sk})
        check(abs(r["mean_cv_score"] - sk) < tol,
              f"sklearn parity: C={p['C']:.4g} ours={r['mean_cv_score']:.4f} "
              f"sklearn={sk:.4f} (tolerance {tol})")
    return rows


def check_packed_path(data, n_trials: int, cv: int = CV) -> None:
    """The single-device flagship must have run the packed Pallas fit with
    the fused step, compiled, on the device — not the host fast path."""
    from cs230_distributed_machine_learning_tpu.models.registry import (
        get_kernel,
    )
    from cs230_distributed_machine_learning_tpu.ops.pallas_logreg import (
        fused_step_applicable,
    )
    from cs230_distributed_machine_learning_tpu.parallel import trial_map
    from cs230_distributed_machine_learning_tpu.utils import backend

    n, d = data.X.shape
    kernel = get_kernel("LogisticRegression")
    static = kernel.resolve_static(
        dict(kernel.static_defaults), n, d, data.n_classes
    )
    static["_n_classes"] = data.n_classes
    static = kernel.bucket_static(static, [{"max_iter": MAX_ITER}])
    check(not backend.pallas_interpret(), "Pallas interpret mode is on")
    check(kernel.batched_applicable(static, n, d),
          "batched_applicable is false: the packed path did not apply")
    dpp = -(-(d + 1) // 64) * 64
    check(fused_step_applicable(dpp, max(data.n_classes, 2) * (cv + 1) * 128),
          "auto would fall through to the legacy scan body (VMEM gate)")
    macs = kernel.macs_estimate(n, d, static) * (cv + 1) * n_trials
    check(macs > 100 * trial_map._HOST_EXEC_MACS,
          f"workload ({macs:.3g} MACs) is near the host fast path line")
    kinds = {k[0] for k in trial_map._compiled_cache if isinstance(k, tuple)}
    check("batched" in kinds, f"no packed executable was built: {kinds}")
    check("host" not in kinds, "a bucket was routed to the host CPU")


def check_device_work(obs: Dict[str, Any], what: str) -> None:
    cost = obs["cost"] or {}
    check(obs["dispatch_s_warm"] > 0,
          f"{what}: device_seconds_total{{phase=dispatch}} did not move")
    check(cost.get("hbm_peak_bytes"), f"{what}: cost report has no HBM "
          f"high-water: {cost.get('hbm_peak_bytes')!r}")
    check(cost.get("mfu"), f"{what}: cost report has no MFU: "
          f"{cost.get('mfu')!r}")


# ------------------------------------------------------------- REST leg

def rest_leg(dataset: str, n_trials: int, *, max_iter: int = MAX_ITER,
             cv: int = CV, timeout: float = 600.0) -> Dict[str, Any]:
    """Deployment mode 3 in this process: cluster coordinator + one local
    executor behind the REST surface on a loopback port, driven by a remote
    ``MLTaskManager`` over the SSE stream."""
    import requests
    from werkzeug.serving import make_server

    from cs230_distributed_machine_learning_tpu import MLTaskManager
    from cs230_distributed_machine_learning_tpu.runtime.cluster import (
        ClusterRuntime,
    )
    from cs230_distributed_machine_learning_tpu.runtime.coordinator import (
        Coordinator,
    )
    from cs230_distributed_machine_learning_tpu.runtime.server import (
        create_app,
    )

    cluster = ClusterRuntime()
    cluster.add_executor()
    coord = Coordinator(cluster=cluster)
    server = make_server("127.0.0.1", 0, create_app(coord), threaded=True)
    thread = threading.Thread(target=server.serve_forever, daemon=True)
    thread.start()
    try:
        url = f"http://127.0.0.1:{server.server_port}"
        manager = MLTaskManager(url=url)
        # stream=True submits on /train_status and returns only on the
        # terminal SSE event
        status, wall = train_once(
            manager, rest_search(n_trials, max_iter, cv), dataset,
            timeout, stream=True,
        )
        check_job(status, n_trials, "REST job (terminal SSE event)")
        polled = manager.check_status()
        check_job(polled, n_trials, "REST job (/check_status)")
        cost = requests.get(f"{url}/cost/{manager.job_id}", timeout=30)
        cost.raise_for_status()
        health = requests.get(f"{url}/healthz", timeout=30)
        health.raise_for_status()
    finally:
        server.shutdown()
        thread.join(timeout=30)
        server.server_close()
        cluster.shutdown()
    check(not thread.is_alive(), "server thread did not stop")
    return {"wall_s": wall, "cost": cost.json(), "healthz": health.json(),
            "best": status["job_result"]["best_result"]}


# ------------------------------------------------------------- mesh leg

def check_mesh_results(obs: Dict[str, Any], one_chip: Dict[str, Any],
                       n_devices: int = 4) -> Dict[str, Any]:
    """All chips held result shards, the winner came from the on-device
    collective argmax, and it is the one-chip winner up to the two
    formulations' numerical tie band."""
    groups = obs["cost"]["groups"]
    check(groups and all(g["n_result_devices"] == n_devices for g in groups),
          f"score shards did not sit on {n_devices} devices: "
          f"{[g['n_result_devices'] for g in groups]}")
    best = obs["best"]
    check(best.get("winner_via") == "ici_argmax",
          f"winner_via={best.get('winner_via')!r}")
    # both legs run the packed Pallas fit (the mesh per chip, under
    # shard_map); the tolerance dates from the mesh's XLA formulation —
    # compare trial by trial
    ours = {r["parameters"]["C"]: r["mean_cv_score"] for r in obs["results"]}
    theirs = {r["parameters"]["C"]: r["mean_cv_score"]
              for r in one_chip["results"]}
    worst = max(abs(ours[c] - theirs[c]) for c in ours)
    check(worst < PARITY_TOL,
          f"mesh vs one-chip scores differ by {worst:.4f}")
    same = best["parameters"] == one_chip["best"]["parameters"]
    # a different argmax is a tie broken the other way only if the mesh
    # scores both winners within the formulations' own spread
    gap = abs(ours[best["parameters"]["C"]]
              - ours[one_chip["best"]["parameters"]["C"]])
    check(same or gap <= worst,
          f"mesh winner {best['parameters']} vs one-chip "
          f"{one_chip['best']['parameters']}: gap {gap:.5f} > spread "
          f"{worst:.5f}")
    return {"winner_equal": same, "max_score_diff": worst}


def mesh_leg(one_chip: Dict[str, Any], n_trials: int) -> Dict[str, Any]:
    """The flagship on ``trial_mesh`` of four chips, after the one-chip
    leg; every chip's memory high-water must move."""
    import jax

    from cs230_distributed_machine_learning_tpu.parallel.mesh import trial_mesh

    devices = jax.devices()[:4]
    peak0 = [d.memory_stats()["peak_bytes_in_use"] for d in devices]
    obs = flagship_leg(FLAGSHIP_DATASET, n_trials, mesh=trial_mesh(devices))
    check_device_work(obs, "mesh leg")
    peak1 = [d.memory_stats()["peak_bytes_in_use"] for d in devices]
    # chip 0 may already stand at its high-water from the one-chip leg
    check(all(b > a for a, b in zip(peak0[1:], peak1[1:])),
          f"not every chip's peak_bytes_in_use moved: {peak0} -> {peak1}")
    return {**obs, **check_mesh_results(obs, one_chip), "peak_bytes": peak1}


# -------------------------------------------------- families (by hand)

def _sk_cv(estimator, data, cv: int) -> float:
    import numpy as np
    from sklearn.model_selection import cross_val_score

    return float(cross_val_score(
        estimator, np.asarray(data.X), np.asarray(data.y), cv=cv
    ).mean())


def families_leg() -> List[Dict[str, Any]]:
    """One job per Pallas-backed family at a shape where ``auto`` selects
    its kernel, through ``MLTaskManager.train``, scored against sklearn at
    the tolerance of that family's CPU parity test. Not part of the default
    run (time); its output is quoted in CHANGES.md."""
    from sklearn.ensemble import RandomForestClassifier
    from sklearn.neighbors import KNeighborsClassifier
    from sklearn.neural_network import MLPClassifier

    cases = [
        # fused MLP epoch kernel: adam, n >= 4096 (models/mlp.py)
        ("mlp_fused_epoch", "synthetic_20000x64x10", 3, 0.05,
         MLPClassifier(hidden_layer_sizes=(128,), max_iter=30,
                       random_state=0),
         {"alpha": [1e-4, 1e-3]}),
        # shallow forest, integer stats, d*n_bins inside the histogram
        # gate (10 x 128 bins): the Pallas level histogram
        ("rf_pallas_hist", "synthetic_20000x10x3", 3, 0.05,
         RandomForestClassifier(n_estimators=20, max_depth=6,
                                random_state=0),
         {"min_samples_leaf": [1, 5]}),
        # grow-to-purity forest above the deep threshold: the chunked
        # protocol over the frontier arena (24-bin levels ride the kernel)
        ("rf_deep_chunked", "synthetic_30000x54x7", 3, 0.05,
         RandomForestClassifier(n_estimators=8, random_state=0),
         {"min_samples_leaf": [1]}),
        # fused top-k kernel: n >= 150 000 (models/knn.py)
        ("knn_topk", "synthetic_160000x16x4", 3, 0.02,
         KNeighborsClassifier(n_neighbors=5),
         {"weights": ["uniform"]}),
    ]
    rows, failures = [], []
    for name, dataset, cv, tol, est, grid in cases:
        try:
            rows.append(_family_case(name, dataset, cv, tol, est, grid))
        except Exception as e:  # noqa: BLE001 — re-raised below, all at once
            traceback.print_exc()
            failures.append(f"{name}: {e!r}")
    check(not failures, "families: " + "; ".join(failures))
    return rows


def _family_case(name, dataset, cv, tol, est, grid) -> Dict[str, Any]:
    from sklearn.model_selection import GridSearchCV

    from cs230_distributed_machine_learning_tpu import MLTaskManager
    from cs230_distributed_machine_learning_tpu.runtime.coordinator import (
        Coordinator,
    )

    coord = Coordinator()
    manager = MLTaskManager(coordinator=coord)
    n_trials = len(next(iter(grid.values())))
    status, wall = train_once(
        manager, GridSearchCV(est, grid, cv=cv), dataset, 900.0
    )
    r = check_job(status, n_trials, name)[0]
    data = coord.cache.get(dataset, "classification")
    sk = _sk_cv(est.set_params(**r["parameters"]), data, cv)
    row = {"family": name, "dataset": dataset, "wall_s": round(wall, 2),
           "ours": round(r["mean_cv_score"], 4), "sklearn": round(sk, 4)}
    print("family:", json.dumps(row), flush=True)
    check(abs(r["mean_cv_score"] - sk) < tol,
          f"{name}: ours={r['mean_cv_score']:.4f} sklearn={sk:.4f} "
          f"(tolerance {tol})")
    return row


# ------------------------------------------------------------------ main

def round_trip_ms(n: int = 20) -> float:
    """Median wall of one jitted scalar add plus the fetch of its result —
    the dispatch floor every tiny job pays (informational)."""
    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda x: x + 1.0)
    x = jnp.float32(0.0)
    float(f(x))  # compile
    walls = []
    for _ in range(n):
        t0 = time.perf_counter()
        float(f(x))
        walls.append((time.perf_counter() - t0) * 1e3)
    return statistics.median(walls)


def watch_xla_compiles() -> Dict[str, float]:
    """Sum JAX's own compile telemetry for the whole run: seconds inside
    the backend compile call (a persistent-cache hit costs only the
    retrieval) and the persistent cache's hits and misses."""
    import jax.monitoring as mon

    seen = {"compile_s": 0.0, "cache_hits": 0, "cache_misses": 0}

    def on_duration(event: str, duration: float, **_kw) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            seen["compile_s"] += duration

    def on_event(event: str, **_kw) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            seen["cache_hits"] += 1
        elif event == "/jax/compilation_cache/cache_misses":
            seen["cache_misses"] += 1

    mon.register_event_duration_secs_listener(on_duration)
    mon.register_event_listener(on_event)
    return seen


def main(argv: Optional[List[str]] = None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--families", action="store_true",
                        help="also run one job per Pallas-backed family")
    args = parser.parse_args(argv)

    device = device_line()
    require_tpu(device)
    xla = watch_xla_compiles()

    from cs230_distributed_machine_learning_tpu.utils.config import get_config

    with tempfile.TemporaryDirectory(prefix="chip_smoke_") as root:
        # datasets, journals and artifacts of this run die with it
        get_config().storage.root = root

        one = flagship_leg(FLAGSHIP_DATASET, FLAGSHIP_TRIALS)
        check_packed_path(one["data"], FLAGSHIP_TRIALS)
        check_device_work(one, "flagship")
        print(f"flagship: {FLAGSHIP_TRIALS}/{FLAGSHIP_TRIALS} trials, "
              f"failed=[] , packed Pallas path, "
              f"fresh executables cold={one['fresh_executables_cold']:.0f} "
              f"warm=0, mfu={one['cost']['mfu']:.4f} "
              f"hbm_peak_bytes={one['cost']['hbm_peak_bytes']}", flush=True)
        parity = check_sklearn_parity(one["data"], one["results"])
        print("sklearn parity:", json.dumps(parity), flush=True)

        rest = rest_leg(FLAGSHIP_DATASET, REST_TRIALS)
        hz = rest["healthz"]["device"]
        check(hz.get("platform") == device["platform"],
              f"/healthz device: {hz}")
        print(f"rest: {REST_TRIALS}/{REST_TRIALS} trials over SSE in "
              f"{rest['wall_s']:.2f} s, /healthz device={hz['platform']} "
              f"{hz.get('device_kind')!r}", flush=True)

        mesh = None
        if device["count"] >= 4:
            mesh = mesh_leg(one, FLAGSHIP_TRIALS)
            print(f"mesh4: {FLAGSHIP_TRIALS}/{FLAGSHIP_TRIALS} trials, "
                  f"winner_via=ici_argmax, winner_equal={mesh['winner_equal']} "
                  f"max_score_diff={mesh['max_score_diff']:.5f} "
                  f"peak_bytes={mesh['peak_bytes']}", flush=True)

        if args.families:
            families_leg()

        print(f"informational: flagship cold_wall_s={one['cold_s']:.2f} "
              f"warm_wall_s={one['warm_s']:.2f} (each ended by the host "
              "fetch of the results)"
              + (f"; mesh4 cold_wall_s={mesh['cold_s']:.2f} "
                 f"warm_wall_s={mesh['warm_s']:.2f}" if mesh else "")
              + f"; scalar jit+fetch round trip median of 20 = "
              f"{round_trip_ms():.3f} ms; XLA backend compile "
              f"{xla['compile_s']:.1f} s in all, persistent cache "
              f"hits={xla['cache_hits']} misses={xla['cache_misses']}",
              flush=True)
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

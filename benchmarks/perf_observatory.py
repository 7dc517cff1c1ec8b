"""Perf observatory: valve A/B regression harness.

Four PRs of kernel/data-plane work are valve-gated and parity-pinned, but
nothing would NOTICE if a valve's fast path silently regressed (fell back
to legacy, lost its cache keying, grew an extra copy). This harness makes
each perf valve's cost measurable and gateable:

- **Valve A/B**: for every perf valve (``CS230_FUSED_STEP``,
  ``CS230_MASKED_GRAD``, ``CS230_HIST_KERNEL``, ``CS230_STAGE_CACHE``)
  run a small workload
  that exercises the valve's real code path — through ``run_trials`` where
  possible, so the executable caches' ``trace_salt`` keying is part of
  what's measured — with the valve ON and OFF in **interleaved pairs**
  (the logreg_profile methodology: the deltas are the signal, and
  sequential best-of lets machine drift swamp them). Reports median,
  min, and spread per state.
- **Noise-aware comparator**: fresh measurements gate against the
  committed ``benchmarks/PERF_OBSERVATORY.json`` baselines; a regression
  is a median beyond ``max(current spread, baseline spread, noise
  floor)`` over the baseline. Missing baselines and backend mismatches
  are SKIPS, never crashes. ``PERF_OBS_INJECT=component.state=factor``
  (or ``all=factor``) multiplies current medians before the compare —
  the CI drill proving the gate actually trips (deploy/ci.sh perf).
Usage:
  python benchmarks/perf_observatory.py [--quick] [--check]
      [--baseline PATH] [--out PATH] [--noise-floor F]
  python benchmarks/perf_observatory.py --compare-only RESULTS.json

``--quick`` only reduces repetitions (shapes are identical), so quick
measurements stay comparable against a full-mode baseline.
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import sys
import time
from typing import Any, Callable, Dict, List, Optional, Tuple

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
OUT_DEFAULT = os.path.join(REPO, "benchmarks", "PERF_OBSERVATORY.json")
#: default noise floor for the comparator — the committed profiles note
#: ±15-25% run-to-run spread on the 2-core dev container, and CI runners
#: vary more; a REAL valve regression (silent legacy fallback, lost cache
#: keying) shows up as 2x+, far beyond this
NOISE_FLOOR = float(os.environ.get("PERF_OBS_NOISE_FLOOR", 0.35))


# ---------------------------------------------------------------------------
# comparator (pure — unit-tested in tests/test_perf_observatory.py)
# ---------------------------------------------------------------------------


def host_fingerprint() -> Dict[str, Any]:
    """What makes absolute wall-clock medians comparable across runs: the
    machine class. Recorded into every measurement document; the
    comparator refuses to gate absolute medians across different hosts
    (a runner 1.6x slower than the dev box would flag everything; one
    1.6x faster would absorb a real 2x regression)."""
    import platform

    return {"machine": platform.machine(), "cpus": os.cpu_count()}


def compare_to_baseline(
    current: Dict[str, Any],
    baseline: Optional[Dict[str, Any]],
    *,
    noise_floor: float = NOISE_FLOOR,
) -> Tuple[List[Dict[str, Any]], List[Dict[str, Any]], List[Dict[str, Any]]]:
    """Noise-aware gate: (regressions, checked, skipped).

    Same-host (matching ``host`` fingerprints, or baseline predates
    them): a component state regresses when its median exceeds the
    baseline median by more than ``max(current spread, baseline spread,
    noise_floor)`` (relative). Cross-host: absolute wall clocks are not
    comparable, so the gate falls back to the machine-independent
    within-run signal — the on-vs-off DELTA (a silent fast-path fallback
    collapses it toward the off cost) — regressing when the current
    delta worsens by more than the same tolerance in percentage points.
    Missing baseline entries, unmeasured states, and a backend mismatch
    are SKIPS — the gate must never crash or false-fail on an
    incomparable pair."""
    regressions: List[Dict[str, Any]] = []
    checked: List[Dict[str, Any]] = []
    skipped: List[Dict[str, Any]] = []
    comps = (current or {}).get("components") or {}
    base_comps = (baseline or {}).get("components") or {}
    if baseline is None or not base_comps:
        return [], [], [
            {"component": c, "reason": "no baseline document"} for c in comps
        ]
    cur_backend = (current or {}).get("backend")
    base_backend = (baseline or {}).get("backend")
    if cur_backend and base_backend and cur_backend != base_backend:
        return [], [], [
            {
                "component": c,
                "reason": f"backend mismatch ({cur_backend} vs baseline "
                          f"{base_backend})",
            }
            for c in comps
        ]
    cur_host = (current or {}).get("host")
    base_host = (baseline or {}).get("host")
    same_host = not cur_host or not base_host or cur_host == base_host
    for comp, cur in sorted(comps.items()):
        base = base_comps.get(comp)
        if base is None:
            skipped.append({"component": comp, "reason": "no baseline entry"})
            continue
        tol = max(
            *(
                float((d.get(s) or {}).get("spread") or 0.0)
                for d in (cur, base) for s in ("on", "off")
            ),
            float(noise_floor),
        )
        if not same_host:
            # cross-host: gate the within-run on/off delta only
            cd, bd = cur.get("delta_on_vs_off_pct"), base.get(
                "delta_on_vs_off_pct"
            )
            if cd is None or bd is None:
                skipped.append({
                    "component": comp,
                    "reason": "host mismatch and no on/off delta to compare",
                })
                continue
            entry = {
                "component": comp,
                "state": "delta_on_vs_off",
                "current_delta_pct": float(cd),
                "baseline_delta_pct": float(bd),
                "tolerance_pct_points": round(100.0 * tol, 1),
                "mode": "cross-host",
            }
            checked.append(entry)
            if float(cd) - float(bd) > 100.0 * tol:
                regressions.append(entry)
            continue
        for state in ("on", "off"):
            c, b = cur.get(state), base.get(state)
            if (
                not isinstance(c, dict) or not isinstance(b, dict)
                or not c.get("median_s") or not b.get("median_s")
            ):
                skipped.append({
                    "component": f"{comp}.{state}",
                    "reason": "state unmeasured in current or baseline",
                })
                continue
            ratio = float(c["median_s"]) / float(b["median_s"])
            entry = {
                "component": comp,
                "state": state,
                "current_median_s": float(c["median_s"]),
                "baseline_median_s": float(b["median_s"]),
                "ratio": round(ratio, 4),
                "tolerance": round(tol, 4),
            }
            checked.append(entry)
            if ratio > 1.0 + tol:
                regressions.append(entry)
    return regressions, checked, skipped


def apply_injection(current: Dict[str, Any], spec: str) -> Dict[str, Any]:
    """Multiply medians per ``PERF_OBS_INJECT`` — comma-separated
    ``comp[.state]=factor`` entries; ``all`` targets every component and
    ``all.on`` / ``all.off`` one state across every component (the CI
    drill uses ``all.on`` so the injected regression also shifts the
    on/off DELTA the cross-host mode gates on — a uniform ``all`` is, by
    design, invisible to it). The touched components' deltas are
    recomputed from the scaled medians. Returns a mutated deep copy;
    malformed entries are ignored (the drill must not crash the gate it
    is testing)."""
    import copy

    doc = copy.deepcopy(current)
    comps = doc.get("components") or {}
    for item in (spec or "").split(","):
        item = item.strip()
        if not item or "=" not in item:
            continue
        target, _, factor_s = item.partition("=")
        try:
            factor = float(factor_s)
        except ValueError:
            continue
        comp_key, _, state = target.partition(".")
        states = (state,) if state in ("on", "off") else ("on", "off")
        comp_keys = list(comps) if comp_key == "all" else [comp_key]
        for comp in comp_keys:
            entry = comps.get(comp)
            if not isinstance(entry, dict):
                continue
            for s in states:
                cell = entry.get(s)
                if isinstance(cell, dict) and cell.get("median_s"):
                    cell["median_s"] = float(cell["median_s"]) * factor
            on_m = (entry.get("on") or {}).get("median_s")
            off_m = (entry.get("off") or {}).get("median_s")
            if on_m and off_m:
                entry["delta_on_vs_off_pct"] = round(
                    100.0 * (on_m - off_m) / off_m, 1
                )
    return doc


# ---------------------------------------------------------------------------
# measurement
# ---------------------------------------------------------------------------


def _sync(o) -> None:
    import jax

    jax.block_until_ready(o)


def _stats(samples: List[float]) -> Dict[str, Any]:
    med = statistics.median(samples)
    return {
        "median_s": med,
        "min_s": min(samples),
        "spread": (max(samples) - min(samples)) / med if med else None,
        "samples": [round(s, 6) for s in samples],
    }


class _EnvPatch:
    """Set env vars for a scope, restoring the previous values exactly."""

    def __init__(self, **env: Optional[str]):
        self.env = env
        self.saved: Dict[str, Optional[str]] = {}

    def __enter__(self):
        for k, v in self.env.items():
            self.saved[k] = os.environ.get(k)
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v
        return self

    def __exit__(self, *exc):
        for k, v in self.saved.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v


def _synthetic_data(n: int, d: int, c: int, seed: int = 0):
    import numpy as np

    from cs230_distributed_machine_learning_tpu.data.datasets import TrialData

    rng = np.random.RandomState(seed)
    X = rng.randn(n, d).astype(np.float32)
    y = rng.randint(0, c, n).astype(np.int32)
    return TrialData(X=X, y=y, n_classes=c)


def _build_executor_workload(
    model_type: str,
    env: Dict[str, str],
    *,
    n: int,
    d: int,
    c: int,
    n_trials: int,
    params: Dict[str, Any],
    cv: int = 3,
    fresh_data: bool = False,
) -> Callable[[], None]:
    """One measured rep = ``run_trials`` over a synthetic dataset with the
    component's env in force. The executable caches key the valves via
    ``trace_salt``, so each state compiles (and warms) its OWN
    executables; interleaved timed reps then hit the right cache entries.
    ``fresh_data=True`` rebuilds the TrialData object per rep — content
    identical, object fresh — which is exactly the boundary the staging
    valves differ on (the content-fingerprint cache hits, the legacy
    per-object cache restages)."""
    import numpy as np

    from cs230_distributed_machine_learning_tpu.models.registry import get_kernel
    from cs230_distributed_machine_learning_tpu.ops.folds import build_split_plan
    from cs230_distributed_machine_learning_tpu.parallel.trial_map import run_trials

    kernel = get_kernel(model_type)
    data = _synthetic_data(n, d, c)
    plan = build_split_plan(
        np.asarray(data.y), task=kernel.task, n_folds=cv,
        test_size=0.2, random_state=0,
    )
    param_dicts = [dict(params) for _ in range(n_trials)]

    def one_rep() -> None:
        nonlocal data
        with _EnvPatch(**env):
            if fresh_data:
                data = _synthetic_data(n, d, c)
            run_trials(kernel, data, plan, param_dicts)

    with _EnvPatch(**env):
        # warm: compile + stage under this state's env so the timed reps
        # measure the steady state, not one cold XLA trace
        run_trials(kernel, data, plan, param_dicts)
    return one_rep


def _build_packed_step_workload(env: Dict[str, str]) -> Optional[Callable[[], None]]:
    """The fused-Nesterov valve's real target is the PACKED scan body
    (logreg_profile.measure_packed_step): build the packed batched fn
    under this state's env (interpret mode off-TPU) and time one jitted
    call. None when the packed path is not applicable on this backend."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from cs230_distributed_machine_learning_tpu.models.registry import get_kernel
    from cs230_distributed_machine_learning_tpu.utils import backend

    on_tpu = backend.on_tpu()
    n = 4096 if on_tpu else 2048
    d, c, s, chunk = 54, 7, 6, 128
    steps = int(os.environ.get("PERF_OBS_PACK_STEPS", 2))
    rng = np.random.RandomState(0)
    build_env = dict(env)
    if not on_tpu:
        build_env["CS230_PALLAS_INTERPRET"] = "1"
    with _EnvPatch(**build_env):
        kernel = get_kernel("LogisticRegression")
        static = {"fit_intercept": True, "penalty": "l2",
                  "_method": "nesterov", "_n_classes": c, "_iters": steps}
        fn = kernel.build_batched_fn(
            static=static, n=n, d=d, n_classes=c, n_splits=s, chunk=chunk,
        )
        if fn is None:
            return None
        fn = jax.jit(fn)
        X = jnp.asarray(rng.randn(n, d).astype(np.float32))
        y = jnp.asarray(rng.randint(0, c, n).astype(np.int32))
        TW = jnp.asarray((rng.rand(s, n) > 0.3).astype(np.float32))
        EW = jnp.asarray((rng.rand(s, n) > 0.5).astype(np.float32))
        hyper = {
            "C": jnp.asarray(
                np.geomspace(0.05, 5.0, chunk).astype(np.float32)
            ),
            "max_iter": jnp.full((chunk,), 1e6, jnp.float32),
            "tol": jnp.zeros((chunk,), jnp.float32),
        }
        args = (X, y, TW, EW, hyper)
        _sync(fn(*args))  # compile + warm

    def one_rep() -> None:
        with _EnvPatch(**build_env):
            _sync(fn(*args))

    return one_rep


#: the valve components: key -> (valve, on value, off value, builder).
#: Builders take the state env and return a zero-arg measured rep (or
#: None when the path is inapplicable on this backend — a SKIP).
def _components() -> Dict[str, Dict[str, Any]]:
    lr_params = {"C": 1.0, "max_iter": 20.0, "tol": 0.0}
    return {
        "fused_step": {
            "valve": "CS230_FUSED_STEP",
            "on_value": "pallas",
            "off_value": "legacy",
            "build": _build_packed_step_workload,
            "what": "packed Nesterov scan body: fused Pallas step kernel "
                    "vs the legacy XLA elementwise body (PR 10)",
        },
        "masked_grad": {
            "valve": "CS230_MASKED_GRAD",
            "on_value": "auto",
            "off_value": "legacy",
            "build": lambda env: _build_executor_workload(
                "LogisticRegression", env,
                n=2048, d=16, c=4, n_trials=8, params=lr_params,
            ),
            "what": "LogReg gradient: fold mask fused into the softmax "
                    "normalizer vs the legacy masked elementwise pass (PR 6)",
        },
        "hist_kernel": {
            "valve": "CS230_HIST_KERNEL",
            "on_value": "auto",
            "off_value": "matmul",
            "build": lambda env: _build_executor_workload(
                "RandomForestClassifier", env,
                n=2048, d=8, c=3, n_trials=2,
                params={"n_estimators": 2.0, "max_depth": 4.0},
            ),
            "what": "tree level histograms: backend-routed kernel "
                    "(pallas/scatter) vs the one-hot matmul contraction (PR 6)",
        },
        "stage_cache": {
            "valve": "CS230_STAGE_CACHE",
            "on_value": "1",
            "off_value": "0",
            "build": lambda env: _build_executor_workload(
                "LogisticRegression", env,
                n=65536, d=32, c=4, n_trials=2,
                params={"C": 1.0, "max_iter": 3.0, "tol": 0.0},
                cv=2, fresh_data=True,
            ),
            "what": "multi-tenant staged-dataset cache: content-fingerprint "
                    "hit across jobs vs per-object restaging (PR 8)",
        },
    }


def measure_components(
    *, reps: int, only: Optional[List[str]] = None
) -> Tuple[Dict[str, Any], Dict[str, str]]:
    """Interleaved A/B measurement of every component. Returns
    (components, skipped): per component, per state median/min/spread
    over ``reps`` interleaved pairs."""
    results: Dict[str, Any] = {}
    skipped: Dict[str, str] = {}
    for key, comp in _components().items():
        if only and key not in only:
            continue
        valve = comp["valve"]
        states = {
            "on": {valve: comp["on_value"]},
            "off": {valve: comp["off_value"]},
        }
        t_start = time.perf_counter()
        fns: Dict[str, Callable[[], None]] = {}
        try:
            for state, env in states.items():
                fn = comp["build"](env)
                if fn is None:
                    raise _Inapplicable(
                        f"{key}: workload not applicable on this backend"
                    )
                fns[state] = fn
        except _Inapplicable as e:
            skipped[key] = str(e)
            print(f"{key}: SKIPPED ({e})", flush=True)
            continue
        except Exception as e:  # noqa: BLE001 — one component's failure
            # must not abort the others; it surfaces in the report
            skipped[key] = f"build failed: {type(e).__name__}: {e}"
            print(f"{key}: SKIPPED (build failed: {e})", flush=True)
            continue
        walls: Dict[str, List[float]] = {s: [] for s in fns}
        for _ in range(reps):
            for state, fn in fns.items():  # interleaved: on, off, on, off...
                t0 = time.perf_counter()
                fn()
                walls[state].append(time.perf_counter() - t0)
        entry: Dict[str, Any] = {
            "valve": valve,
            "on_value": comp["on_value"],
            "off_value": comp["off_value"],
            "what": comp["what"],
        }
        for state in ("on", "off"):
            entry[state] = _stats(walls[state])
        if entry["off"]["median_s"]:
            entry["delta_on_vs_off_pct"] = round(
                100.0
                * (entry["on"]["median_s"] - entry["off"]["median_s"])
                / entry["off"]["median_s"],
                1,
            )
        results[key] = entry
        print(
            f"{key:14s} on {entry['on']['median_s']*1e3:9.2f} ms"
            f" (spread {entry['on']['spread']:.0%})"
            f" | off {entry['off']['median_s']*1e3:9.2f} ms"
            f" (spread {entry['off']['spread']:.0%})"
            f" | delta {entry.get('delta_on_vs_off_pct', 0):+.1f}%"
            f" | {time.perf_counter() - t_start:.1f}s",
            flush=True,
        )
    return results, skipped


class _Inapplicable(RuntimeError):
    pass


# ---------------------------------------------------------------------------
# CLI
# ---------------------------------------------------------------------------


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--quick", action="store_true",
                    help="fewer interleaved pairs (shapes unchanged, so "
                         "results stay baseline-comparable)")
    ap.add_argument("--check", action="store_true",
                    help="compare fresh measurements against --baseline; "
                         "exit 1 on a regression beyond the noise gate")
    ap.add_argument("--baseline", default=OUT_DEFAULT,
                    help="baseline JSON for --check / --compare-only")
    ap.add_argument("--out", default=OUT_DEFAULT,
                    help="where to write the measurement document")
    ap.add_argument("--noise-floor", type=float, default=NOISE_FLOOR)
    ap.add_argument("--only", action="append", default=None,
                    help="measure only these component keys")
    ap.add_argument("--compare-only", metavar="RESULTS",
                    help="skip measuring; load RESULTS as the current "
                         "document and run the gate (the CI injection "
                         "drill path)")
    args = ap.parse_args()

    os.environ.setdefault("JAX_PLATFORMS", "cpu")

    # the gate's baseline is read BEFORE anything is written: a --check
    # run whose --out defaults to the committed baseline path must
    # compare against the COMMITTED numbers, not its own fresh document
    # (and must not clobber the committed file either — it writes to a
    # .fresh.json sibling instead)
    baseline = None
    if (args.check or args.compare_only) and os.path.exists(args.baseline):
        with open(args.baseline) as f:
            baseline = json.load(f)

    if args.compare_only:
        with open(args.compare_only) as f:
            current = json.load(f)
    else:
        reps = 3 if args.quick else 5
        import jax

        doc: Dict[str, Any] = {
            "benchmark": "perf_observatory",
            "backend": jax.default_backend(),
            "device": str(jax.devices()[0]),
            "host": host_fingerprint(),
            "mode": "quick" if args.quick else "full",
            "reps_per_state": reps,
            "methodology": (
                "interleaved on/off pairs per valve (logreg_profile "
                "round-robin precedent); medians + relative spread; "
                "workloads run the real run_trials path where possible so "
                "trace_salt cache keying is under test; --quick changes "
                "reps only, never shapes"
            ),
        }
        comps, skipped = measure_components(reps=reps, only=args.only)
        doc["components"] = comps
        if skipped:
            doc["skipped"] = skipped
        out_path = args.out
        if args.check and os.path.abspath(out_path) == os.path.abspath(
            args.baseline
        ):
            out_path = args.out + ".fresh.json"
        with open(out_path, "w") as f:
            json.dump(doc, f, indent=1)
        print(f"wrote {out_path}", flush=True)
        current = doc

    if not (args.check or args.compare_only):
        return 0

    inject = os.environ.get("PERF_OBS_INJECT")
    if inject:
        current = apply_injection(current, inject)
        print(f"PERF_OBS_INJECT={inject} applied", flush=True)
    regressions, checked, skipped_cmp = compare_to_baseline(
        current, baseline, noise_floor=args.noise_floor
    )
    print(json.dumps({
        "gate": "perf_observatory",
        "checked": len(checked),
        "skipped": len(skipped_cmp),
        "regressions": regressions,
    }, indent=1))
    if regressions:
        print(f"PERF REGRESSION: {len(regressions)} component state(s) "
              f"beyond the noise gate", file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    raise SystemExit(main())

"""Per-component microbenchmark of the flagship LogReg trial step.

The randomized-search headline plateaued at 253.9 trials/s = 41.5% MFU
(BENCH_r05.json) with ~2.4x theoretical headroom, and the gap had no
attributed breakdown — this harness decomposes one nesterov LogReg trial
step at the north-star shape (Covertype 116k x 54, 7 classes, 6 fold
lanes) into the terms that can possibly own it:

- ``grad_masked``      — one full gradient iteration the LEGACY way
                         (CS230_MASKED_GRAD=legacy): P = softmax(A @ W);
                         G = C * A.T @ (w * (P - Y)) + penalty (2 MXU
                         matmuls + softmax, bf16 inputs / f32 accumulation
                         like models/logistic.py).
- ``grad_masked_fused``— the same iteration with the fold mask applied
                         IN-KERNEL (PR 6, the production formulation):
                         log w rides the softmax exponent and the masked
                         label term w*Y is hoisted out of the loop, so no
                         masked copy of the probabilities is materialized.
                         The masked-in-kernel vs masked-outside delta is
                         the recovered fold-mask overhead.
- ``grad_unmasked``    — the same without any fold mask; the
                         grad_masked - grad_unmasked difference is the
                         fold-mask overhead the static {0,1}-weight CV
                         design paid per iteration before the fusion.
- ``lipschitz_power``  — the 30-step power iteration computing the step
                         size (once per split per bucket, amortized over
                         all trials and iterations).
- ``eval_epilogue``    — logits + argmax + masked accuracy over the full
                         dataset (once per trial per split).
- ``dispatch_floor``   — wall time of a minimal jitted dispatch + scalar
                         fetch: the irreducible host->device->host round
                         trip every dispatch pays.
- ``result_fetch``     — blocking device->host fetch of a [1024, 6] f32
                         score buffer (the packed single-fetch result of a
                         full chunk), measured end to end.
- ``packed_step``      — the PACKED path's per-iteration wall, fused step
                         kernel (CS230_FUSED_STEP=pallas, ISSUE 10) vs
                         the legacy scan body, measured INTERLEAVED at
                         two scan lengths so the eval epilogue and
                         dispatch overhead difference out; plus the
                         modeled per-iteration HBM traffic (bytes/iter
                         before vs after) at the north-star shape.

Measurement follows benchmarks/deep_profile.py: each in-jit component runs
ITERS times inside one jitted fori_loop with iteration-dependent inputs
(defeats hoisting), synced by a scalar fetch; reported per-iteration after
subtracting the measured dispatch floor. Host-boundary components
(dispatch_floor, result_fetch) are wall-clock medians instead.

Writes benchmarks/LOGREG_PROFILE_MEASURED.json with the raw numbers plus a
derived attribution of a whole max_iter=200 trial step.

Usage: python benchmarks/logreg_profile.py
       [PROF_N=116202 PROF_D=54 PROF_C=7 PROF_S=6 PROF_ITERS=3 PROF_REPS=3]
"""

from __future__ import annotations

import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

N = int(os.environ.get("PROF_N", 116_202))
D = int(os.environ.get("PROF_D", 54))
C = int(os.environ.get("PROF_C", 7))
S = int(os.environ.get("PROF_S", 6))  # holdout + 5 CV folds
ITERS = int(os.environ.get("PROF_ITERS", 3))
REPS = int(os.environ.get("PROF_REPS", 3))
MAX_ITER = int(os.environ.get("PROF_MAX_ITER", 200))  # bench.py's cap
OUT = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                   "LOGREG_PROFILE_MEASURED.json")


def sync(o):
    leaf = jax.tree_util.tree_leaves(o)[0]
    np.asarray(jax.device_get(jnp.ravel(leaf)[0]))


def timed_loop(step, init):
    """step(i, carry) -> carry; best per-iteration seconds over REPS."""

    def loop(c):
        return jax.lax.fori_loop(0, ITERS, step, c)

    f = jax.jit(loop)
    out = f(init)
    sync(out)
    best = float("inf")
    for _ in range(REPS):
        t0 = time.perf_counter()
        out = f(init)
        sync(out)
        best = min(best, time.perf_counter() - t0)
    return best / ITERS


def wall_median(fn, reps=7):
    fn()  # warm
    walls = []
    for _ in range(reps):
        t0 = time.perf_counter()
        fn()
        walls.append(time.perf_counter() - t0)
    return float(np.median(walls))


def _ceil_to(x, m):
    return -(-x // m) * m


def _packed_hbm_model(n, d, c, S, chunk, Tw=128):
    """Modeled per-iteration HBM bytes of the packed Nesterov scan body.
    (Deliberately independent of the row-tile size ``bm``: tiling changes
    how the stream is chunked, not the total bytes moved.)

    Stream terms (identical before/after): the bf16 design matrix, label
    and fold-weight tiles, re-read once per weight block. Weight terms
    (the fusion target): the legacy body's XLA elementwise round-trips
    over the [n_wb, dpp, NB] f32 tensors vs the fused kernel's single
    in-place read+write of W/Wp. ``legacy_weight_bytes`` assumes XLA
    fuses every elementwise chain perfectly (the optimistic bound:
    read W/Wp -> write V_bf16; kernel read V_bf16 -> write Graw; one
    fused scale+gmax+writeback pass re-reading Graw/W/Wp and writing
    W/Wp). ``legacy_weight_bytes_unfused`` materializes every named
    intermediate (V f32, G) separately — the pessimistic bound."""
    dp = d + 1
    dpp = _ceil_to(dp, 64)
    n_pad = _ceil_to(n, 2048)
    n_wb = chunk // Tw
    NB = c * S * Tw
    Wt = n_wb * dpp * NB * 4  # one full f32 pass over the weight tensors
    stream = n_wb * (n_pad * dpp * 2 + n_pad * 4 + n_pad * S * 4)
    legacy_w = Wt * (2 + 0.5 + 0.5 + 1 + 1 + 2 + 2)  # 9 f32-equivalents
    legacy_w_unfused = Wt * (2 + 1 + 1 + 0.5 + 0.5 + 1 + 2 + 1 + 2 + 2 + 2)
    fused_w = Wt * 4  # W/Wp read + aliased in-place write
    return {
        "shape": {"n": n, "d": d, "n_classes": c, "splits": S,
                  "chunk": chunk, "n_wb": n_wb, "dpp": dpp, "NB": NB},
        "stream_bytes_per_iter": stream,
        "weight_tensor_pass_bytes": Wt,
        "legacy_weight_bytes_per_iter": legacy_w,
        "legacy_weight_bytes_per_iter_unfused": legacy_w_unfused,
        "fused_weight_bytes_per_iter": fused_w,
        "legacy_total_bytes_per_iter": stream + legacy_w,
        "fused_total_bytes_per_iter": stream + fused_w,
        "total_reduction_pct_fused_vs_legacy": round(
            100.0 * (legacy_w - fused_w) / (stream + legacy_w), 1
        ),
    }


def measure_packed_step():
    """Fused step kernel vs legacy scan body on the PACKED path, on this
    backend. On CPU both variants run the Pallas kernel through the
    interpreter (one interpret call per iteration either way — legacy
    calls packed_softmax_grad, fused calls packed_nesterov_step), so the
    comparison isolates exactly what the fusion removes: the XLA
    elementwise round-trips around the gradient. Two scan lengths per
    variant difference out the eval epilogue + dispatch overhead;
    variants interleave round-robin (PR 6 precedent: their DELTA is the
    signal and sequential best-of lets machine drift swamp it)."""
    from cs230_distributed_machine_learning_tpu.models.registry import get_kernel
    from cs230_distributed_machine_learning_tpu.utils import backend

    on_tpu = backend.on_tpu()
    # the packed path's TPU gate needs n >= 4096; CPU (interpret) keeps
    # the smaller default so the section stays tractable through the
    # Pallas interpreter
    n = int(os.environ.get("PROF_PACK_N", 0)) or (4096 if on_tpu else 2048)
    lo = int(os.environ.get("PROF_PACK_STEPS_LO", 2))
    hi = int(os.environ.get("PROF_PACK_STEPS_HI", 6))
    reps = int(os.environ.get("PROF_PACK_REPS", 3))
    chunk, Tw = 128, 128
    rng = np.random.RandomState(0)
    saved = {k: os.environ.get(k)
             for k in ("CS230_PALLAS_INTERPRET", "CS230_FUSED_STEP")}
    if not on_tpu:
        os.environ["CS230_PALLAS_INTERPRET"] = "1"
    kernel = get_kernel("LogisticRegression")
    X = jnp.asarray(rng.randn(n, D).astype(np.float32))
    y = jnp.asarray(rng.randint(0, C, n).astype(np.int32))
    TW = jnp.asarray((rng.rand(S, n) > 0.3).astype(np.float32))
    EW = jnp.asarray((rng.rand(S, n) > 0.5).astype(np.float32))
    hyper = {
        "C": jnp.asarray(np.geomspace(0.05, 5.0, chunk).astype(np.float32)),
        # never converge, never hit max_iter: every scan step does work
        "max_iter": jnp.full((chunk,), 1e6, jnp.float32),
        "tol": jnp.zeros((chunk,), jnp.float32),
    }
    fns = {}
    try:
        for mode in ("legacy", "pallas"):
            os.environ["CS230_FUSED_STEP"] = mode
            for steps in (lo, hi):
                static = {"fit_intercept": True, "penalty": "l2",
                          "_method": "nesterov", "_n_classes": C,
                          "_iters": steps}
                fn = kernel.build_batched_fn(
                    static=static, n=n, d=D, n_classes=C, n_splits=S,
                    chunk=chunk,
                )
                if fn is None:
                    # packed path not applicable at this shape/backend:
                    # skip the section, never abort the whole harness
                    msg = (f"packed path not applicable (backend="
                           f"{jax.default_backend()}, n={n}) — section skipped")
                    print(f"packed step: {msg}", flush=True)
                    return {}, {"skipped": msg}
                fns[(mode, steps)] = jax.jit(fn)
    finally:
        for k, v in saved.items():
            os.environ.pop(k, None) if v is None else os.environ.__setitem__(k, v)

    args = (X, y, TW, EW, hyper)
    for f in fns.values():
        sync(f(*args))  # compile + warm
    walls = {k: [] for k in fns}
    for _ in range(max(reps, 3)):
        for k, f in fns.items():
            t0 = time.perf_counter()
            sync(f(*args))
            walls[k].append(time.perf_counter() - t0)
    per_iter = {}
    for mode in ("legacy", "pallas"):
        # pair same-rep walls so shared drift cancels in the difference
        deltas = [
            (b - a) / (hi - lo)
            for a, b in zip(walls[(mode, lo)], walls[(mode, hi)])
        ]
        per_iter[mode] = deltas
    metrics = {
        "packed_step_legacy_ms_per_iter": min(per_iter["legacy"]) * 1e3,
        "packed_step_fused_ms_per_iter": min(per_iter["pallas"]) * 1e3,
        "packed_step_legacy_median_ms_per_iter": float(
            np.median(per_iter["legacy"])
        ) * 1e3,
        "packed_step_fused_median_ms_per_iter": float(
            np.median(per_iter["pallas"])
        ) * 1e3,
    }
    spread = {
        m: (max(v) - min(v)) / max(min(v), 1e-9)
        for m, v in per_iter.items()
    }
    for mode, label in (("legacy", "packed step (legacy body):"),
                        ("pallas", "packed step (fused kernel):")):
        print(f"{label:30s}{min(per_iter[mode])*1e3:9.2f} ms/iter  "
              f"(median {float(np.median(per_iter[mode]))*1e3:.2f}, "
              f"spread {spread[mode]:.0%})", flush=True)
    info = {
        "backend_note": (
            "compiled TPU kernels" if on_tpu else
            "CPU: BOTH variants run their Pallas kernel through the "
            "interpreter (one interpret call/iter each), so the delta "
            "isolates the XLA elementwise round-trips the fusion removes"
        ),
        "pack_shape": {"n": n, "d": D, "n_classes": C, "splits": S,
                       "chunk": chunk, "Tw": Tw},
        "steps_lo_hi": [lo, hi],
        "reps": max(reps, 3),
        "spread_pct": {m: round(100 * s, 1) for m, s in spread.items()},
        "hbm_bytes_per_iter_modeled_north_star": _packed_hbm_model(
            116_202, 54, 7, 6, 1024
        ),
    }
    return metrics, info


def main() -> None:
    rng = np.random.RandomState(0)
    dp = D + 1  # + intercept
    A = jnp.asarray(rng.randn(N, dp).astype(np.float32))
    Ab = A.astype(jnp.bfloat16)
    Y = jnp.asarray(
        np.eye(C, dtype=np.float32)[rng.randint(0, C, N)]
    )  # [N, C] one-hot
    W0 = jnp.asarray(rng.randn(S, dp, C).astype(np.float32) * 0.01)
    w_masks = jnp.asarray((rng.rand(S, N) < 0.8).astype(np.float32))
    Cs = jnp.float32(1.0)

    def mm(a, b):
        return jnp.matmul(
            a.astype(jnp.bfloat16), b.astype(jnp.bfloat16),
            preferred_element_type=jnp.float32,
        )

    results = {}

    # ---- 1. the three gradient-iteration formulations ----
    # masked-outside (legacy), masked IN-KERNEL (the PR-6 fused
    # formulation models/logistic.py now runs: the mask folds into the
    # softmax normalizer — e * (w/den) — and the masked label term w*Y is
    # loop-invariant, hoisted exactly as the solver scan hoists it), and
    # unmasked. Their DIFFERENCES are the whole point, so they are
    # measured INTERLEAVED (round-robin reps, best-of per variant):
    # sequential best-of-REPS lets machine-load drift between components
    # swamp a few-percent delta.
    def grad_masked_step(i, carry):
        W, acc = carry

        def one(Wl, wl):
            P = jax.nn.softmax(mm(A, Wl), axis=-1)
            G = Cs * mm(A.T, wl[:, None] * (P - Y)) + 1.0 * Wl
            return G

        G = jax.vmap(one)(W + i * 1e-6, w_masks)
        return (W, acc + G.sum())

    WY = w_masks[:, :, None] * Y[None]  # [S, n, C], precomputed per fit

    def grad_fused_step(i, carry):
        W, acc = carry

        def one(Wl, wl, WYl):
            Z = mm(A, Wl)
            e = jnp.exp(Z - jnp.max(Z, axis=-1, keepdims=True))
            scale = (wl / jnp.sum(e, axis=-1))[:, None]
            G = Cs * mm(A.T, e * scale - WYl) + 1.0 * Wl
            return G

        G = jax.vmap(one)(W + i * 1e-6, w_masks, WY)
        return (W, acc + G.sum())

    def grad_unmasked_step(i, carry):
        W, acc = carry

        def one(Wl):
            P = jax.nn.softmax(mm(A, Wl), axis=-1)
            G = Cs * mm(A.T, (P - Y)) + 1.0 * Wl
            return G

        G = jax.vmap(one)(W + i * 1e-6)
        return (W, acc + G.sum())

    variants = {
        "grad_masked_ms_per_iter": grad_masked_step,
        "grad_masked_fused_ms_per_iter": grad_fused_step,
        "grad_unmasked_ms_per_iter": grad_unmasked_step,
    }
    init = (W0, jnp.zeros(()))
    fns = {}
    for key, step in variants.items():
        f = jax.jit(lambda c, _s=step: jax.lax.fori_loop(0, ITERS, _s, c))
        sync(f(init))  # compile + warm
        fns[key] = f
    walls = {key: [] for key in fns}
    grad_reps = max(REPS, 8)
    for _ in range(grad_reps):
        for key, f in fns.items():
            t0 = time.perf_counter()
            sync(f(init))
            walls[key].append((time.perf_counter() - t0) / ITERS)
    for key, label in (
        ("grad_masked_ms_per_iter", f"grad (masked, {S} lanes):"),
        ("grad_masked_fused_ms_per_iter", "grad (masked IN-KERNEL):"),
        ("grad_unmasked_ms_per_iter", "grad (no fold mask):"),
    ):
        results[key] = min(walls[key]) * 1e3
        results[key.replace("_ms_per_iter", "_median_ms_per_iter")] = (
            float(np.median(walls[key])) * 1e3
        )
        spread = (max(walls[key]) - min(walls[key])) / min(walls[key])
        print(f"{label:30s}{min(walls[key])*1e3:9.2f} ms/iter  "
              f"(median {float(np.median(walls[key]))*1e3:.2f}, "
              f"spread {spread:.0%})", flush=True)

    # ---- 2b. packed scan body: fused step kernel vs legacy (ISSUE 10) ----
    pack_metrics, pack_info = measure_packed_step()
    results.update(pack_metrics)

    # ---- 3. Lipschitz power iteration (30 steps, per split) ----
    def power_step(i, carry):
        v, acc = carry

        def one(vl, wl):
            u = A.T @ (wl * (A @ vl))
            return u / jnp.maximum(jnp.linalg.norm(u), 1e-12)

        v = jax.vmap(one)(v + i * 1e-9, w_masks)
        return (v, acc + v.sum())

    v0 = jnp.ones((S, dp), jnp.float32)
    t = timed_loop(power_step, (v0, jnp.zeros(())))
    results["lipschitz_power_ms_total"] = t * 1e3 * 30  # 30 steps per fit
    print(f"lipschitz power (30 steps):   {t*1e3*30:9.2f} ms/bucket-split",
          flush=True)

    # ---- 4. eval epilogue: logits + argmax + masked accuracy ----
    def eval_step(i, carry):
        W, acc = carry

        def one(Wl, wl):
            pred = jnp.argmax(mm(A, Wl + i * 1e-6), axis=-1)
            ytrue = jnp.argmax(Y, axis=-1)
            hit = (pred == ytrue).astype(jnp.float32)
            return jnp.sum(hit * wl) / jnp.maximum(jnp.sum(wl), 1e-12)

        s = jax.vmap(one)(W, w_masks)
        return (W, acc + s.sum())

    t = timed_loop(eval_step, (W0, jnp.zeros(())))
    results["eval_epilogue_ms"] = t * 1e3
    print(f"eval epilogue ({S} lanes):    {t*1e3:9.2f} ms/trial", flush=True)

    # ---- 5. dispatch floor: minimal jitted call + scalar fetch ----
    tiny = jnp.zeros(())
    f_tiny = jax.jit(lambda x: x + 1.0)
    t = wall_median(lambda: np.asarray(jax.device_get(f_tiny(tiny))))
    results["dispatch_floor_ms"] = t * 1e3
    print(f"dispatch floor:               {t*1e3:9.2f} ms/dispatch", flush=True)

    # ---- 6. packed result fetch: one [1024, S] f32 buffer ----
    score_buf = jnp.asarray(rng.rand(1024, S).astype(np.float32))
    f_id = jax.jit(lambda x: x * 1.0)
    t = wall_median(lambda: np.asarray(jax.device_get(f_id(score_buf))))
    results["result_fetch_ms_per_chunk"] = t * 1e3
    print(f"packed result fetch [1024,{S}]: {t*1e3:7.2f} ms/chunk", flush=True)

    # ---- derived attribution of one max_iter=200 trial step ----
    # the production fit now runs the FUSED (masked-in-kernel) gradient;
    # the legacy masked-outside component stays measured for the delta
    grad_legacy = results["grad_masked_ms_per_iter"]
    grad = results["grad_masked_fused_ms_per_iter"]
    unmasked = results["grad_unmasked_ms_per_iter"]
    mask_oh_legacy = max(grad_legacy - unmasked, 0.0)
    mask_oh = max(grad - unmasked, 0.0)
    fit_ms = MAX_ITER * grad
    # per-trial amortized terms at the bench chunk geometry (1000 trials,
    # one bucket): lipschitz once per bucket, fetch once per chunk of 1024
    amort_lip = results["lipschitz_power_ms_total"] / 1000.0
    amort_fetch = results["result_fetch_ms_per_chunk"] / 1000.0
    amort_dispatch = results["dispatch_floor_ms"] / 1000.0
    total = fit_ms + results["eval_epilogue_ms"] + amort_lip + amort_fetch \
        + amort_dispatch
    attribution = {
        "gradient_bandwidth_pct": round(100 * MAX_ITER * unmasked / total, 1),
        "fold_mask_overhead_pct": round(100 * MAX_ITER * mask_oh / total, 1),
        "fold_mask_overhead_legacy_ms_per_iter": round(mask_oh_legacy, 4),
        "fold_mask_overhead_fused_ms_per_iter": round(mask_oh, 4),
        "fold_mask_overhead_recovered_pct_of_legacy": round(
            100 * (1.0 - mask_oh / mask_oh_legacy) if mask_oh_legacy > 0 else 0.0,
            1,
        ),
        "eval_epilogue_pct": round(100 * results["eval_epilogue_ms"] / total, 1),
        "lipschitz_amortized_pct": round(100 * amort_lip / total, 1),
        "dispatch_amortized_pct": round(100 * amort_dispatch / total, 1),
        "result_fetch_amortized_pct": round(100 * amort_fetch / total, 1),
        "trial_step_ms_modeled": round(total, 2),
    }
    out = {
        "metric": "logreg_trial_step_profile",
        "backend": jax.default_backend(),
        "device": str(jax.devices()[0]),
        "shape": {"n": N, "d": D, "n_classes": C, "splits": S,
                  "max_iter": MAX_ITER},
        "iters": ITERS,
        "reps": REPS,
        # the interleaved gradient variants run a floor of 8 round-robin
        # reps regardless of PROF_REPS — record what actually ran
        "grad_variant_reps": grad_reps,
        "components": {k: round(v, 4) for k, v in results.items()},
        "attribution_per_trial": attribution,
        "packed_step": pack_info,
        "note": (
            "in-jit components measured deep_profile-style (fori_loop, "
            "iteration-dependent inputs, dispatch floor subtracted by "
            "construction); the three gradient formulations are measured "
            "INTERLEAVED (round-robin reps) because their deltas are the "
            "signal; attribution models one max_iter=200 trial of the "
            "1000-trial bench chunked at 1024 trials/dispatch, on the "
            "FUSED (masked-in-kernel) gradient the fit runs since PR 6; "
            "grad_masked is the legacy masked-outside formulation kept "
            "for the before/after delta. CAVEAT (2026-08-03, PR 6): "
            "measured on a 2-core CPU container whose per-variant spread "
            "across runs is +/-15-25% — the grad-formulation deltas here "
            "are WITHIN measurement noise, i.e. on this backend/XLA the "
            "legacy fold-mask overhead itself is no longer resolvable "
            "(an earlier decomposition that attributed ~20% was "
            "measured before this round on another installation). The fused formulation is "
            "kept as the production path on op-count grounds (it strictly "
            "removes the per-iteration masked elementwise pass) and the "
            "Pallas lane/packed kernels apply the mask in VMEM on TPU; "
            "re-measure on the chip before attributing anything. "
            "PACKED STEP (2026-08-03, PR 10): packed_step_* compares the "
            "fused Nesterov step kernel (CS230_FUSED_STEP) against the "
            "legacy scan body ON THIS BACKEND — on CPU both run one "
            "interpreted Pallas call per iteration, so the delta is the "
            "XLA elementwise traffic the fusion removes, NOT the MXU "
            "win; the same +/-15-25% noise-floor caveat applies, and the "
            "bytes/iter accounting under packed_step.hbm_bytes_per_iter_"
            "modeled_north_star is a MODEL (optimistic-XLA-fusion legacy "
            "bound vs the aliased in-place fused kernel), to be "
            "validated by the TPU deep-profile in the BENCH_r06 round."
        ),
    }
    with open(OUT, "w") as f:
        json.dump(out, f, indent=1)
    print(json.dumps(out))


if __name__ == "__main__":
    main()

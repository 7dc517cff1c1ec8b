"""LogisticRegression kernel: multinomial softmax regression, TPU-first.

Capability target: the reference's `LogisticRegression` trials
(``aws-prod/worker/worker.py:43``) — sklearn's L2-penalized logistic
regression (lbfgs solver), scored by accuracy and 5-fold CV. Instead of
per-trial CPU fits, this kernel is pure-functional and vmappable: one
compiled executable fits *all* trials in a bucket, with ``C``/``max_iter``/
``tol`` traced per-trial scalars.

Objective (matching sklearn): ``0.5 * ||W_coef||_F^2 + C * sum_i w_i *
xent_i`` with the intercept unpenalized. Two solvers, chosen at bucket-build
time from data shape (see ``resolve_static``):

- **newton**: exact full-Hessian Newton steps (quadratic convergence; the
  Hessian build is two MXU matmuls). Used when ``(d+1)*n_classes`` and the
  per-sample workspace are small. Converges to the same optimum as sklearn's
  lbfgs, so scores — and therefore ``best_params_`` — agree to tolerance.
- **nesterov**: accelerated full-batch gradient descent with a
  power-iteration Lipschitz step size, for large ``n*d*c`` (e.g. Covertype).
  Per-iteration cost is one [n,d]x[d,c] matmul — ideal MXU shape.

For binary problems sklearn fits a single logit; a 2-column softmax with the
penalty doubled has the same optimum predictive distribution (the penalty on
the logit difference matches), so we always use the softmax form and scale
the penalty by 2 when ``n_classes == 2``.

Known limitation: iteration counts are compile-time caps (``_NEWTON_STEPS``,
``_NESTEROV_STEPS``) because scan lengths are static; a per-trial
``max_iter`` below the cap is honored via masking, but one above it is
truncated. Newton's quadratic convergence makes 25 steps ample in practice;
the Nesterov path may under-converge vs sklearn lbfgs on hard problems —
revisit with an L-BFGS kernel if score-parity tests show drift.
"""

from __future__ import annotations

import os
from typing import Any, Dict

import numpy as np

import jax
import jax.numpy as jnp

from ..utils import backend as _backend
from .base import ModelKernel, add_intercept

_NEWTON_STEPS = 25
_NESTEROV_STEPS = 400
# newton only when the flattened Hessian dim and the [n, dp*c] workspace fit
_NEWTON_MAX_DIM = 512
_NEWTON_MAX_WORKSPACE = 4_000_000


class LogisticRegressionKernel(ModelKernel):
    name = "LogisticRegression"
    task = "classification"
    hyper_defaults = {"C": 1.0, "max_iter": 100.0, "tol": 1e-4}
    static_defaults = {"fit_intercept": True, "penalty": "l2"}

    def trace_salt(self):
        """CS230_MASKED_GRAD selects the masked-gradient formulation and
        CS230_FUSED_STEP the packed scan body at trace time (see
        ``_masked_grad_mode`` / ``_fused_step_mode``) — both must key
        every executable cache like the tree histogram knobs do. The salt
        carries the RESOLVED modes, not the raw strings: invalid/alias
        values collapse to the same behavior and must share a cache key.
        CS230_STREAM joins them (resolved off/auto/force): the streamed
        and single-shot drivers stage different dataset forms, so every
        executable/prepared cache must re-key when the valve moves.
        CS230_CURVES joins too: with capture on, the solver scans carry
        a trace buffer and emit extra outputs, so flipping the valve (or
        CS230_CURVE_POINTS) must re-key every executable cache."""
        from ..data.streaming import stream_mode
        from ..obs.curves import curves_salt

        return (_masked_grad_mode(), _fused_step_mode(), stream_mode(),
                curves_salt())

    def resolve_static(self, static: Dict[str, Any], n: int, d: int, n_classes: int):
        if static.get("penalty") not in ("l2", None, "none"):
            raise ValueError(
                f"LogisticRegression penalty={static.get('penalty')!r} not supported"
            )
        c = max(int(n_classes), 2)
        dp = d + (1 if static.get("fit_intercept", True) else 0)
        method = (
            "newton"
            if dp * c <= _NEWTON_MAX_DIM and n * dp * c <= _NEWTON_MAX_WORKSPACE
            else "nesterov"
        )
        return {**static, "_method": method}

    def fit(self, X, y, w, hyper: Dict[str, Any], static: Dict[str, Any]):
        return self._fit(X, y, w, hyper, static, trace=False)[0]

    def fit_curve(self, X, y, w, hyper: Dict[str, Any], static: Dict[str, Any]):
        """Capture hook (docs/OBSERVABILITY.md "Trial telemetry plane"):
        same fit, plus a bounded grad-norm trace written from inside the
        solver scan — one f32 sample per ``stride`` iterations, at most
        ``CS230_CURVE_POINTS`` slots. Returns ``(params, curve)`` with
        ``curve = {"gmax": [P'], "stride": scalar, "steps": scalar}``."""
        return self._fit(X, y, w, hyper, static, trace=True)

    def _fit(self, X, y, w, hyper: Dict[str, Any], static: Dict[str, Any],
             trace: bool):
        n_classes = int(static["_n_classes"])
        c = max(n_classes, 2)
        fit_intercept = bool(static.get("fit_intercept", True))
        use_penalty = static.get("penalty") in ("l2",)

        A = add_intercept(X, fit_intercept)
        dp = A.shape[1]
        Y = jax.nn.one_hot(y, c, dtype=jnp.float32)
        w = w.astype(jnp.float32)

        C = jnp.asarray(hyper["C"], jnp.float32)
        max_iter = jnp.asarray(hyper["max_iter"], jnp.float32)
        tol = jnp.asarray(hyper["tol"], jnp.float32)

        lam = jnp.where(use_penalty, 1.0, 0.0) * (2.0 if n_classes == 2 else 1.0)
        # intercept row is unpenalized (sklearn semantics)
        pen_mask = jnp.ones((dp, c), jnp.float32)
        if fit_intercept:
            pen_mask = pen_mask.at[-1, :].set(0.0)

        W0 = jnp.zeros((dp, c), jnp.float32)

        # large-n path: bf16 matmul inputs with f32 accumulation — the MXU's
        # native mode, ~4x the f32 throughput; Newton path stays f32 (its
        # Hessian solve is precision-sensitive and small anyway)
        if static["_method"] == "nesterov":
            def mm(a, b):
                return jnp.matmul(
                    a.astype(jnp.bfloat16),
                    b.astype(jnp.bfloat16),
                    preferred_element_type=jnp.float32,
                )
        else:
            mm = jnp.matmul

        mode = _masked_grad_mode()
        if static["_method"] == "newton":
            steps = int(static.get("_iters", _NEWTON_STEPS))
            W, tr = _newton(A, Y, w, W0, mm, C, lam, pen_mask, max_iter, tol,
                            steps, fused=(mode != "legacy"), trace=trace)
        else:
            steps = int(static.get("_iters", _NESTEROV_STEPS))
            grad_fn = _make_masked_grad_fn(A, Y, y, w, C, lam, pen_mask, mm, mode)
            W, tr = _nesterov(A, w, W0, grad_fn, C, lam, max_iter, tol, steps,
                              trace=trace)
        if not trace:
            return W, None
        from ..obs.curves import trace_stride

        stride = trace_stride(steps)
        return W, {
            "gmax": tr,
            "stride": jnp.asarray(float(stride), jnp.float32),
            "steps": jnp.asarray(float(steps), jnp.float32),
        }

    def bucket_static(self, static: Dict[str, Any], hypers) -> Dict[str, Any]:
        """Engine hook: with the bucket's hyper values known, cap the static
        scan length at the largest per-trial max_iter so masked-out
        iterations aren't executed at all."""
        cap = _NEWTON_STEPS if static["_method"] == "newton" else _NESTEROV_STEPS
        max_iters = [int(h.get("max_iter", 100)) for h in hypers] or [cap]
        return {**static, "_iters": max(1, min(cap, max(max_iters)))}

    # ---- out-of-core row-block streaming (data/streaming.py) ----

    def stream_applicable(self, static: Dict[str, Any], n: int, d: int) -> bool:
        """Only the Nesterov driver accumulates across row blocks: its
        gradient and power-iteration are row sums. Newton's Hessian
        solve wants the whole workspace resident — and its n-threshold
        (``_NEWTON_MAX_WORKSPACE``) keeps it under any realistic stage
        budget anyway."""
        return static.get("_method") == "nesterov"

    def stream_form(self, X_np, static: Dict[str, Any]):
        """Engine hook: the row-major host array blocks are sliced from,
        plus a salt naming the form in block cache keys."""
        return np.asarray(X_np, np.float32), ("raw", "f32")

    def stream_lane_row_bytes(self, static: Dict[str, Any]) -> int:
        """Engine hook: device bytes one row of a block costs one (trial,
        split) lane while ``grad_block`` computes on it, the block plan's
        ``work_row_bytes`` a lane: the float32 logits and the bfloat16
        residual of each class, and the row's float32 max, sum and scale.
        The compiled v5e program holds 58 of this rule's 72 at ten classes
        (tests/test_tpu_compile.py)."""
        c = max(int(static["_n_classes"]), 2)
        return c * (4 + 2) + 3 * 4

    def stream_scores(self, streamer, y_pad, TW, EW, hyper_batch, static, n):
        """Block-accumulated Nesterov over a RowBlockStreamer: one pass
        per solver iteration (plus 31 Lipschitz passes and one eval
        pass), partial gradients summed across blocks — the ``fit`` +
        ``weighted_accuracy`` composition restructured so no array of
        ``n`` rows is ever device-resident. Pad rows carry zero sample
        weight, so every block-sum matches the single-shot value up to
        f32 summation order (the parity tests/test_streaming.py pins).
        Returns the packed path's leaves on the device, for the engine to
        fetch: ``score`` [T, S] and, with curves on, ``curve_gmax`` [T, S,
        slots], ``curve_stride`` and ``curve_steps``."""
        return _stream_nesterov_scores(
            streamer, y_pad, TW, EW, hyper_batch, static, n
        )

    def predict(self, params, X, static: Dict[str, Any]):
        fit_intercept = bool(static.get("fit_intercept", True))
        A = add_intercept(X, fit_intercept)
        return jnp.argmax(A @ params, axis=-1).astype(jnp.int32)

    def predict_margin(self, params, X, static: Dict[str, Any]):
        """Binary decision margin = logit(class 1) - logit(class 0) (the
        2-column softmax's logit difference equals sklearn's single-logit
        decision_function up to solver tolerance)."""
        fit_intercept = bool(static.get("fit_intercept", True))
        A = add_intercept(X, fit_intercept)
        Z = A @ params
        return Z[:, 1] - Z[:, 0]

    def predict_proba(self, params, X, static: Dict[str, Any]):
        """Softmax class probabilities (sklearn's multinomial
        predict_proba up to solver tolerance)."""
        fit_intercept = bool(static.get("fit_intercept", True))
        A = add_intercept(X, fit_intercept)
        return jax.nn.softmax(A @ params, axis=-1)

    def memory_estimate_mb(self, n, d, static):
        # marginal per-(trial,split) working set: a few [n, c] activation/
        # gradient buffers (the [n, d] design matrix is shared, not vmapped)
        c = max(int(static.get("_n_classes", 2)), 2)
        return max(1.0, 3.0 * 4.0 * n * c / 1e6)

    def macs_estimate(self, n, d, static):
        """Analytical per-(trial, split) cost — lets the engine route
        sub-accelerator-scale buckets to host execution."""
        c = max(int(static.get("_n_classes", 2)), 2)
        newton = static.get("_method") == "newton"
        steps = int(
            static.get("_iters", _NEWTON_STEPS if newton else _NESTEROV_STEPS)
        )
        per_iter = 3.0 * n * (d + 1) * c
        if newton:
            dim = (d + 1) * c
            per_iter += n * dim * (d + 1) + float(dim) ** 3
        return steps * per_iter

    # ---- fused Pallas batched path (ops/pallas_logreg.py) ----------------
    #
    # On TPU, large-n nesterov buckets bypass the generic vmap engine: all
    # trials' weights are packed class-major into one matrix and the whole
    # fit (gradient scan) + eval runs as ONE jitted call per chunk. The
    # probabilities tensor never touches HBM and each dispatch amortizes the
    # host round-trip (measured ~7x per-iteration over the vmap path on
    # v5e for the Covertype north-star config).

    #: trials in the widest packed weight block (``ops/pallas_logreg.py::
    #: TRIAL_BLOCK``); a chunk beyond it is a whole number of such blocks
    batched_trial_multiple = 128
    batched_chunk_cap = 1024

    def batched_trial_block(self, trials_per_device: int, n_splits: int) -> int:
        """Trials a packed weight block (``Tw``) for a device's share of a
        bucket: the width that holds the share with the fewest packed
        columns (16, 32, 64 or ``batched_trial_multiple``; each class slab
        is padded to whole 128-lane vregs, so every width runs). The engine
        rounds a device's chunk up to it and ``build_batched_fn`` reads the
        same width back from that chunk."""
        from ..ops.pallas_logreg import packed_trial_block

        return packed_trial_block(trials_per_device, n_splits)

    def batched_slab(self, block: int, n_splits: int) -> Dict[str, int]:
        """What a weight block of ``block`` trials is made of, for the
        engine's plan and dispatch span: ``slab_lanes`` lanes a class slab,
        ``slab_pad_lanes`` of them dead columns (zero weights, sample
        weight 0, dropped at unpack)."""
        from ..ops.pallas_logreg import slab_lanes

        lanes = slab_lanes(n_splits, block)
        return {"slab_lanes": lanes, "slab_pad_lanes": lanes - n_splits * block}

    def batched_applicable(self, static: Dict[str, Any], n: int, d: int) -> bool:
        if static.get("_method") != "nesterov":
            return False
        dpp = _ceil_to(d + 2, 64)  # + intercept, rounded
        if dpp > 512:  # W block would blow the VMEM budget
            return False
        if _backend.pallas_interpret():
            return True
        return _backend.auto_pallas() and n >= 4096

    def batched_staged_extras(self, static, n, d, n_classes, n_splits,
                              fold_signature=None, block=None):
        """Dispatch-invariant device inputs of the packed path, staged by
        the trial engine in the multi-tenant stage cache
        (data/stage_cache.py) and merged into the dispatch ``hyper`` dict
        under the returned names:

        - ``_logreg_ab``: the padded bf16 design matrix — every dispatch
          after the first stops re-padding and re-casting the full A
          inside the jit (and repeat jobs over a cached dataset pay
          nothing at all).
        - ``_logreg_lam_max``: the per-split Lipschitz power iteration
          (30 matmul round-trips over A), which depends only on (dataset,
          fold weights) — keyed by the fold-plan signature so every chunk
          dispatch after the first is a cache hit.
        - ``_logreg_occ``: the step kernel's per-(row tile, split)
          occupancy table (``ops/pallas_logreg.py::tile_occupancy``),
          which depends only on the fold weights — keyed like the bound.
          Only where the kernel reads it (``_step_form``: the fused step
          at a ``block`` of 128 trials); a narrower block, or a caller
          that names none, stages what it always did.

        Returns ``{name: (subkey | None, make)}``; ``make(ctx)`` receives
        ``{"X", "y", "TW", "EW"}`` device args. A ``None``
        subkey means compute once per bucket, don't cache (no fold
        signature to key on). Empty in ``legacy`` mode: the rollback path
        must keep deriving everything inline, bit-for-bit."""
        if _fused_step_mode() == "legacy":
            return {}
        if not self.batched_applicable(static, n, d):
            return {}
        from ..ops.pallas_logreg import tile_occupancy

        geo = _packed_geometry(static, n, d, n_classes, n_splits)
        fit_intercept, dp = geo["fit_intercept"], geo["dp"]
        dpp, n_pad, bm = geo["dpp"], geo["n_pad"], geo["bm"]

        def folds_key(name, *geometry):
            return None if fold_signature is None else (
                name, fold_signature) + geometry

        def pad_a(X):
            A = add_intercept(X, fit_intercept)
            return jnp.pad(A, ((0, n_pad - n), (0, dpp - dp)))

        def make_ab(ctx):
            return jax.jit(lambda X: pad_a(X).astype(jnp.bfloat16))(ctx["X"])

        def pad_tw(TW):
            return jnp.pad(TW.astype(jnp.float32), ((0, 0), (0, n_pad - n)))

        def make_lam_max(ctx):
            def compute(X, TW):
                return _packed_lam_max(pad_a(X), pad_tw(TW))

            return jax.jit(compute)(ctx["X"], ctx["TW"])

        def make_occ(ctx):
            return jax.jit(lambda TW: tile_occupancy(pad_tw(TW), bm=bm))(ctx["TW"])

        specs = {
            "_logreg_ab": (("ab", fit_intercept, dpp, n_pad), make_ab),
            "_logreg_lam_max": (
                folds_key("lam_max", fit_intercept, dpp, n_pad), make_lam_max,
            ),
        }
        if block is not None and _step_form(geo, block)[1]:
            specs["_logreg_occ"] = (folds_key("occ", n_pad, bm), make_occ)
        return specs

    def dispatch_attrs(self, static, X, extras) -> Dict[str, Any]:
        """What the packed engine's ``executor.dispatch`` span says of a
        bucket's staged extras: ``tile_skip_pct``, the share of the step
        kernel's (row tile, split) column groups that the staged occupancy
        table marks empty; 0.0 where none is staged (a narrower block, the
        legacy body: the whole slab on every tile). The splits are counted
        off the per-split bound staged beside the table."""
        occ = extras.get("_logreg_occ")
        if occ is None:
            return {"tile_skip_pct": 0.0}
        from ..ops.pallas_logreg import tile_skip_pct

        n_splits = int(extras["_logreg_lam_max"].shape[0])
        return {"tile_skip_pct": tile_skip_pct(occ, n_splits)}

    def build_batched_fn(self, static, n, d, n_classes, n_splits, chunk):
        """Returns fn(X, y, TW, EW, hyper) -> {"score": [chunk, n_splits]}
        (same contract as the engine's vmapped executable), or None when the
        packed path doesn't apply. One call = full fit scan + eval.

        ``hyper`` may carry the staged forms from
        ``batched_staged_extras`` (the engine merges them in); when absent
        — direct calls, benchmarks, ``legacy`` mode — everything is
        derived inline, bit-identically."""
        if not self.batched_applicable(static, n, d):
            return None
        Tw = self.batched_trial_block(chunk, n_splits)
        if chunk % Tw:
            return None

        from ..ops.pallas_logreg import (
            packed_nesterov_step,
            packed_softmax_grad,
            slab_lanes,
            tile_occupancy,
        )

        interpret = _backend.pallas_interpret()
        geo = _packed_geometry(static, n, d, n_classes, n_splits)
        c, S = geo["c"], geo["S"]
        fit_intercept = geo["fit_intercept"]
        lam = geo["lam"]
        steps = int(static.get("_iters", _NESTEROV_STEPS))
        n_wb = chunk // Tw
        # a class slab: S*Tw real (split, trial) columns, then dead ones up
        # to whole vregs (zero weights; scores and curves dropped at unpack)
        Breal = S * Tw
        Bblk = slab_lanes(S, Tw)
        NB = c * Bblk
        dp, dpp = geo["dp"], geo["dpp"]
        bm = geo["bm"]
        rc = geo["rc"]  # eval row-chunk
        n_pad = geo["n_pad"]  # multiple of rc (and of bm)
        use_fused, skip = _step_form(geo, Tw)
        from ..obs.curves import curves_enabled, trace_stride

        capture = curves_enabled()
        tr_stride = trace_stride(steps) if capture else 1
        tr_used = -(-steps // tr_stride) if capture else 0

        # static column maps: slab col j -> (split, trial-in-block); a dead
        # column reads the last split's row, like any other valid index
        j = np.arange(Bblk)
        split_of = (j // Tw).clip(max=S - 1)
        trial_map = (np.arange(n_wb)[:, None] * Tw + (j % Tw)[None, :]).clip(
            max=chunk - 1
        )
        # rows: penalty applies to real feature rows, never the intercept/pad
        pen_row = np.zeros((1, dpp, 1), np.float32)
        pen_row[0, :dp, 0] = 1.0
        if fit_intercept:
            pen_row[0, dp - 1, 0] = 0.0

        split_of_j = jnp.asarray(split_of)
        trial_map_j = jnp.asarray(trial_map)
        pen_row_j = jnp.asarray(pen_row)

        def fn(X, y, TW, EW, hyper):
            with jax.named_scope("tpuml.fit"):
                A = None
                if "_logreg_ab" not in hyper or "_logreg_lam_max" not in hyper:
                    A = add_intercept(X, fit_intercept)  # [n, dp] f32
                    A = jnp.pad(A, ((0, n_pad - n), (0, dpp - dp)))
                Ab = (
                    hyper["_logreg_ab"]
                    if "_logreg_ab" in hyper
                    else A.astype(jnp.bfloat16)
                )
                y_pad = jnp.pad(y.astype(jnp.int32), (0, n_pad - n))
                y2 = y_pad[:, None]
                TWp = jnp.pad(TW.astype(jnp.float32), ((0, 0), (0, n_pad - n)))
                EWp = jnp.pad(EW.astype(jnp.float32), ((0, 0), (0, n_pad - n)))
                WSP = TWp.T  # [n_pad, S]

                Cb = jnp.take(hyper["C"], trial_map_j)  # [n_wb, Bblk]
                maxit_b = jnp.take(hyper["max_iter"], trial_map_j)
                tol_b = jnp.take(hyper["tol"], trial_map_j)

                # Lipschitz bound per split: L <= 0.5*C*lam_max(A' diag(w) A)
                # + lam — precomputed once per (dataset, folds) and staged by
                # batched_staged_extras when available, else inline
                lam_max_s = (
                    hyper["_logreg_lam_max"]
                    if "_logreg_lam_max" in hyper
                    else _packed_lam_max(A, TWp)
                )  # [S]
                lam_s = lam_max_s[split_of_j]  # [Bblk]
                step_b = 1.0 / (0.5 * Cb * lam_s[None, :] + lam + 1e-6)

                W0 = jnp.zeros((n_wb, dpp, NB), jnp.float32)
                done0 = jnp.zeros((n_wb, Bblk), bool)

                # fixed-length scan (length already capped to the bucket's max
                # max_iter by bucket_static's _iters). A while_loop with an
                # all-converged early exit measures ~20% SLOWER here: the
                # per-step cond reduce acts as a barrier, and slow-converging
                # trials run to max_iter anyway.
                tr0 = (
                    jnp.zeros((tr_used, n_wb, Bblk), jnp.float32)
                    if capture else None
                )

                if use_fused:
                    pen_col = pen_row_j[0]  # [dpp, 1]
                    # staged once per (dataset, fold plan) like the bound,
                    # else inline; loop-invariant either way
                    occ = None
                    if skip:
                        occ = (
                            hyper["_logreg_occ"]
                            if "_logreg_occ" in hyper
                            else tile_occupancy(TWp, bm=bm)
                        )

                    def body(carry, t):
                        W, Wp, done, tr = carry
                        W, Wp, gmax = packed_nesterov_step(
                            Ab, W, Wp, y2, WSP, t, done.astype(jnp.float32),
                            step_b, Cb, maxit_b, pen_col, occ,
                            c=c, S=S, Tw=Tw, bm=bm, lam=lam,
                            interpret=interpret,
                        )
                        done = jnp.logical_or(done, gmax < tol_b)
                        if capture:
                            tr = tr.at[jnp.asarray(t, jnp.int32) // tr_stride].set(gmax)
                        return (W, Wp, done, tr), None

                else:
                    step_full = jnp.tile(step_b, (1, c))[:, None, :]  # [n_wb,1,NB]
                    Cb_full = jnp.tile(Cb, (1, c))[:, None, :]

                    def body(carry, t):  # legacy scan body — parity reference
                        W, Wp, done, tr = carry
                        mom = t / (t + 3.0)
                        V = W + mom * (W - Wp)
                        Graw = packed_softmax_grad(
                            Ab, V.astype(jnp.bfloat16), y2, WSP,
                            c=c, S=S, Tw=Tw, bm=bm, interpret=interpret,
                        )
                        G = Cb_full * Graw + lam * pen_row_j * V
                        gmax = jnp.max(
                            jnp.abs(G).reshape(n_wb, dpp, c, Bblk), axis=(1, 2)
                        )  # [n_wb, Bblk]
                        active = jnp.logical_and(
                            t < maxit_b, jnp.logical_not(done)
                        )
                        act = jnp.tile(active, (1, c))[:, None, :]
                        W_new = jnp.where(act, V - step_full * G, W)
                        Wp_new = jnp.where(act, W, Wp)
                        done = jnp.logical_or(done, gmax < tol_b)
                        if capture:
                            tr = tr.at[jnp.asarray(t, jnp.int32) // tr_stride].set(gmax)
                        return (W_new, Wp_new, done, tr), None

                (W, _, _, tr_out), _ = jax.lax.scan(
                    body, (W0, W0, done0, tr0), jnp.arange(steps, dtype=jnp.float32)
                )

            with jax.named_scope("tpuml.eval"):
                # ---- eval: streamed row chunks, argmax over the class axis ----
                # (f32: eval runs once per dispatch, and argmax ties near fold
                # boundaries are where bf16 noise could flip best_params_)
                def eval_body(acc, start):
                    a = jax.lax.dynamic_slice(Ab, (start, 0), (rc, dpp)).astype(
                        jnp.float32
                    )
                    logits = jnp.einsum(
                        "rd,wdn->wrn", a, W, preferred_element_type=jnp.float32
                    )
                    pred = jnp.argmax(logits.reshape(n_wb, rc, c, Bblk), axis=2)
                    yc = jax.lax.dynamic_slice(y_pad, (start,), (rc,))
                    # slice the [S, n_pad] fold weights first, then expand to
                    # trials: keeps the loop-invariant at [S, n_pad] instead of
                    # materializing a [Bblk, n_pad] gather (~S*Tw/S x larger —
                    # ~1.8 GB on the Covertype north-star config)
                    wev = jax.lax.dynamic_slice(
                        EWp, (0, start), (S, rc)
                    )[split_of_j].T  # [rc, Bblk]
                    hit = (pred == yc[None, :, None]).astype(jnp.float32)
                    acc = acc + jnp.sum(hit * wev[None], axis=1)
                    return acc, None

                acc0 = jnp.zeros((n_wb, Bblk), jnp.float32)
                acc, _ = jax.lax.scan(
                    eval_body, acc0, jnp.arange(0, n_pad, rc, dtype=jnp.int32)
                )
                den = jnp.maximum(jnp.sum(EW.astype(jnp.float32), axis=1), 1e-12)  # [S]
                score_b = acc / den[split_of_j][None, :]
            with jax.named_scope("tpuml.pack"):
                score = (
                    score_b[:, :Breal].reshape(n_wb, S, Tw)
                    .transpose(0, 2, 1).reshape(chunk, S)
                )
                out = {"score": score}
                if capture:
                    # same lane->(trial, split) mapping as score, with the
                    # trace-slot axis carried along as a trailing dim
                    curve = (
                        tr_out.transpose(1, 2, 0)[:, :Breal]
                        .reshape(n_wb, S, Tw, tr_used)
                        .transpose(0, 2, 1, 3)
                        .reshape(chunk, S, tr_used)
                    )
                    out["curve_gmax"] = curve
                    out["curve_stride"] = jnp.full(
                        (chunk, S), float(tr_stride), jnp.float32
                    )
                    out["curve_steps"] = jnp.full(
                        (chunk, S), float(steps), jnp.float32
                    )
            return out

        return fn


def _ceil_to(x: int, m: int) -> int:
    from ..parallel.mesh import pad_to_multiple

    return pad_to_multiple(x, m)


def _masked_grad_mode() -> str:
    """Valve for the fused masked-gradient formulation (ISSUE 6 tentpole).

    - ``auto`` (default): fused-mask XLA formulation everywhere; the fused
      Pallas lane kernel for large-n nesterov fits on a real TPU backend.
    - ``xla``: fused-mask XLA formulation only (never the lane kernel).
    - ``pallas``: force the Pallas lane kernel (compiled; interpreted only
      under CS230_PALLAS_INTERPRET=1 on the CPU backend). Applies to
      the grad-descent driver only: the ``_newton`` driver needs the
      probabilities for its Hessian anyway, so it always runs the fused
      XLA form (any non-``legacy`` mode).
    - ``legacy``: the pre-fusion formulation (separate ``w*(P-Y)``
      elementwise pass per iteration), kept for A/B and rollback.
    """
    mode = os.environ.get("CS230_MASKED_GRAD", "auto").lower()
    return mode if mode in ("auto", "xla", "pallas", "legacy") else "auto"


def _fused_step_mode() -> str:
    """Valve for the fused packed Nesterov step kernel (ISSUE 10 tentpole).

    - ``auto`` (default): one ``packed_nesterov_step`` Pallas call per
      scan iteration — momentum extrapolation, masked softmax-Gram
      gradient, C/L2 scaling, the ``max|G|`` reduce, and the done-masked
      W/Wp writeback all fused in VMEM — whenever the packed path runs (TPU, or interpret mode on CPU)
      and the weight blocks pass the VMEM gate
      (``fused_step_applicable``); the legacy body otherwise.
    - ``pallas``: force the fused kernel, bypassing the VMEM gate (tests
      force tiny shapes through it; combine with CS230_PALLAS_INTERPRET=1
      off-TPU).
    - ``legacy``: the pre-fusion scan body (separate XLA elementwise
      passes around ``packed_softmax_grad``), kept as the parity
      reference and rollback — it also keeps deriving Ab and the
      Lipschitz bound inline (no staged extras), bit-for-bit the old
      path.
    """
    mode = os.environ.get("CS230_FUSED_STEP", "auto").lower()
    return mode if mode in ("auto", "pallas", "legacy") else "auto"


def _step_form(geo, Tw: int):
    """The step a packed fit at a block of ``Tw`` trials scans, as
    ``(fused, skip)``: ``auto`` routes through the fused step kernel
    whenever its weight blocks fit the VMEM gate; ``pallas`` forces it
    (tiny test shapes); ``legacy`` keeps the pre-fusion scan body as the
    parity reference. ``skip``: a split's lanes are whole vregs (a block
    of 128), so the fused kernel takes the occupancy table and leaves out
    the (row tile, split) column groups it marks empty."""
    from ..ops.pallas_logreg import (
        fused_step_applicable,
        slab_lanes,
        tile_skip_applicable,
    )

    mode = _fused_step_mode()
    NB = geo["c"] * slab_lanes(geo["S"], Tw)
    fused = mode == "pallas" or (
        mode == "auto" and fused_step_applicable(geo["dpp"], NB, geo["bm"])
    )
    return fused, fused and tile_skip_applicable(geo["S"], Tw)


def _packed_geometry(static, n, d, n_classes, n_splits):
    """Shared shape/penalty derivation of the packed path —
    ``build_batched_fn`` and ``batched_staged_extras`` must agree on
    every padded dimension or the staged forms would not match the
    executable's expectations."""
    c = max(int(n_classes), 2)
    fit_intercept = bool(static.get("fit_intercept", True))
    use_pen = static.get("penalty") in ("l2",)
    lam = (2.0 if n_classes == 2 else 1.0) if use_pen else 0.0
    dp = d + (1 if fit_intercept else 0)
    rc = 2048
    return {
        "bm": 256,  # the kernels' row tile; divides rc, so also n_pad
        "c": c,
        "S": int(n_splits),
        "fit_intercept": fit_intercept,
        "lam": lam,
        "dp": dp,
        "dpp": _ceil_to(dp, 64),
        "rc": rc,
        "n_pad": _ceil_to(n, rc),
    }


def _packed_lam_max(A, TWp):
    """Per-split Lipschitz bound ``lam_max(A' diag(w) A)`` via a 30-step
    power iteration. Factored out so the inline path (legacy / direct
    calls) and the stage-cache precompute (``batched_staged_extras``) run
    the SAME formula — the precompute is keyed by (dataset fingerprint,
    fold-plan signature), which is exactly what this reads."""

    def lam_max_for(w):
        def power(v, _):
            u = A.T @ (w * (A @ v))
            return u / jnp.maximum(jnp.linalg.norm(u), 1e-12), None

        v0 = jnp.ones((A.shape[1],), jnp.float32)
        v, _ = jax.lax.scan(power, v0, None, length=30)
        return jnp.dot(v, A.T @ (w * (A @ v)))

    return jax.vmap(lam_max_for)(TWp)


def _make_masked_grad_fn(A, Y, y, w, C, lam, pen_mask, mm, mode):
    """Per-iteration masked-gradient closure for the grad-descent driver.

    The fused formulations eliminate the measured fold-mask overhead
    (benchmarks/LOGREG_PROFILE_MEASURED.json): the mask folds into the
    softmax normalizer (``w * softmax(z) == exp(z - max) * (w / den)``)
    and the masked label term ``w*Y`` is loop-invariant (hoisted out of
    the solver scan), so a masked iteration runs at most the op count of
    an unmasked one — no masked copy of A or of the probabilities is ever
    materialized.
    """
    if mode == "legacy":
        def grad_fn(W):
            P = jax.nn.softmax(mm(A, W), axis=-1)
            G = C * mm(A.T, w[:, None] * (P - Y)) + lam * pen_mask * W
            return G, P
        return grad_fn

    n, dp = A.shape
    c = Y.shape[1]
    use_pallas = mode == "pallas" or (
        mode == "auto" and _backend.auto_pallas() and n >= 4096
    )
    if use_pallas:
        from ..ops.pallas_logreg import masked_softmax_grad

        bm = 256
        dpp = _ceil_to(dp, 128)
        cp = _ceil_to(c, 128)
        n_pad = _ceil_to(n, bm)
        # loop-invariant paddings: staged once per fit, reused every step
        Ab = jnp.pad(A.astype(jnp.float32), ((0, n_pad - n), (0, dpp - dp))).astype(
            jnp.bfloat16
        )
        y2 = jnp.pad(y.astype(jnp.int32), (0, n_pad - n))[:, None]
        wm = jnp.pad(w.astype(jnp.float32), (0, n_pad - n))[:, None]
        interp = _backend.pallas_interpret()

        def grad_fn(W):
            Wp = jnp.pad(W, ((0, dpp - dp), (0, cp - c))).astype(jnp.bfloat16)
            Gk = masked_softmax_grad(Ab, Wp, y2, wm, c=c, bm=bm, interpret=interp)
            G = C * Gk[:dp, :c] + lam * pen_mask * W
            return G, None
        return grad_fn

    WY = w[:, None] * Y  # loop-invariant: hoisted out of the solver scan

    def grad_fn(W):
        # w * softmax(Z) with the mask folded into the per-row normalizer:
        # e * (w/den) — an [n,1] divide replacing softmax's [n,c] divide,
        # so the masked iteration is never costlier than an unmasked one
        Z = mm(A, W)
        e = jnp.exp(Z - jnp.max(Z, axis=-1, keepdims=True))
        scale = (w / jnp.sum(e, axis=-1))[:, None]
        G = C * mm(A.T, e * scale - WY) + lam * pen_mask * W
        return G, None
    return grad_fn


def _trace_buf(steps, trace, shape=()):
    """(stride, buffer) for an in-scan grad-norm trace; ``(1, None)``
    when capture is off — ``None`` is an empty pytree, so the scan carry
    and jaxpr are bit-identical to the pre-curves path (the strict no-op
    contract tests/test_obs.py pins)."""
    if not trace:
        return 1, None
    from ..obs.curves import trace_stride

    stride = trace_stride(int(steps))
    used = -(-int(steps) // stride)
    return stride, jnp.zeros((used,) + tuple(shape), jnp.float32)


def _newton(A, Y, w, W0, mm, C, lam, pen_mask, max_iter, tol,
            steps=_NEWTON_STEPS, fused=True, trace=False):
    n, dp = A.shape
    c = Y.shape[1]
    dim = dp * c
    # tiny ridge on the unpenalized (intercept) entries breaks the softmax
    # gauge direction that would otherwise make the Hessian singular
    pen_diag = (lam * pen_mask + 1e-5 * (1.0 - pen_mask)).reshape(-1)

    def objective(W):
        logp = jax.nn.log_softmax(A @ W, axis=-1)
        nll = -jnp.sum(w * jnp.sum(Y * logp, axis=-1))
        return C * nll + 0.5 * jnp.sum((lam * pen_mask) * W * W)

    alphas = jnp.asarray([1.0, 0.5, 0.25, 0.1, 0.02], jnp.float32)
    # fused-mask restructuring: the masked label term wc*Y is loop-invariant
    # (hoisted out of the scan) and the single masked product WP = wc*P is
    # shared by the gradient AND both Hessian terms — the legacy per-step
    # masked copies of A (``A*wc``) and of the residual are never built
    WYc = (C * w)[:, None] * Y

    def grad_and_P(W):
        if not fused:
            P = jax.nn.softmax(mm(A, W), axis=-1)
            G = C * mm(A.T, w[:, None] * (P - Y)) + lam * pen_mask * W
            WP = (w * C)[:, None] * P
            return G, P, WP
        P = jax.nn.softmax(mm(A, W), axis=-1)
        WP = (w * C)[:, None] * P  # the one masked elementwise pass
        G = mm(A.T, WP - WYc) + lam * pen_mask * W
        return G, P, WP

    stride, tr0 = _trace_buf(steps, trace)

    def step(carry, t):
        W, done, tr = carry
        G, P, WP = grad_and_P(W)
        # Hessian: H[(i,a),(j,b)] = sum_n wc_n A_ni A_nj (P_na δab − P_na P_nb)
        # block-diagonal part: per class a, A' diag(wc * P_a) A == A' diag(WP_a) A
        blocks = jnp.einsum("ni,na,nj->aij", A, WP, A)  # [c, dp, dp]
        H = jnp.zeros((dp, c, dp, c), jnp.float32)
        H = H.at[:, jnp.arange(c), :, jnp.arange(c)].add(blocks)
        # rank-correction part: U' UW with U[n, dp*c] = A_ni * P_na and
        # UW = A_ni * WP_na (== (U * wc) without materializing a third
        # masked copy beyond WP itself)
        U = (A[:, :, None] * P[:, None, :]).reshape(n, dim)
        UW = (A[:, :, None] * WP[:, None, :]).reshape(n, dim)
        H = H.reshape(dim, dim) - U.T @ UW
        H = H + jnp.diag(pen_diag) + 1e-6 * jnp.eye(dim, dtype=jnp.float32)
        delta = jnp.linalg.solve(H, G.reshape(-1)).reshape(dp, c)
        # ill-conditioned solves (high C, saturated P, f32) can yield
        # non-finite deltas: fall back to a normalized gradient step
        delta_ok = jnp.all(jnp.isfinite(delta))
        gnorm = jnp.linalg.norm(G) + 1e-12
        delta = jnp.where(delta_ok, delta, G / gnorm)
        # backtracking: take the candidate step with the lowest objective
        # (guards against overshoot on separable data)
        objs = jax.vmap(lambda a: objective(W - a * delta))(alphas)
        best = jnp.argmin(objs)
        alpha = jnp.where(objs[best] < objective(W), alphas[best], 0.0)
        gmax = jnp.max(jnp.abs(G))
        active = jnp.logical_and(t < max_iter, jnp.logical_not(done))
        # select, don't multiply: 0 * non-finite delta would poison W
        take = jnp.logical_and(active, alpha > 0.0)
        W = jnp.where(take, W - alpha * delta, W)
        done = jnp.logical_or(done, gmax < tol)
        if trace:
            tr = tr.at[jnp.asarray(t, jnp.int32) // stride].set(gmax)
        return (W, done, tr), None

    (W, _, tr), _ = jax.lax.scan(
        step, (W0, jnp.asarray(False), tr0),
        jnp.arange(steps, dtype=jnp.float32)
    )
    return W, tr


def _nesterov(A, w, W0, grad_fn, C, lam, max_iter, tol, steps=_NESTEROV_STEPS,
              trace=False):
    # Lipschitz bound: L <= 0.5 * C * lambda_max(A' diag(w) A) + lam
    v = jnp.ones((A.shape[1],), jnp.float32)

    def power_step(v, _):
        u = A.T @ (w * (A @ v))
        return u / jnp.maximum(jnp.linalg.norm(u), 1e-12), None

    v, _ = jax.lax.scan(power_step, v, None, length=30)
    lam_max = jnp.dot(v, A.T @ (w * (A @ v)))
    L = 0.5 * C * lam_max + lam + 1e-6
    step = 1.0 / L

    stride, tr0 = _trace_buf(steps, trace)

    def body(carry, t):
        W, W_prev, done, tr = carry
        mom = t / (t + 3.0)
        V = W + mom * (W - W_prev)
        G, _ = grad_fn(V)
        gmax = jnp.max(jnp.abs(G))
        active = jnp.logical_and(t < max_iter, jnp.logical_not(done))
        W_new = jnp.where(active, V - step * G, W)
        W_prev_new = jnp.where(active, W, W_prev)
        done = jnp.logical_or(done, gmax < tol)
        if trace:
            # gmax is evaluated unconditionally even once the lane is
            # done/past max_iter (the update is what's masked), so the
            # trace tail freezes at the converged gradient norm
            tr = tr.at[jnp.asarray(t, jnp.int32) // stride].set(gmax)
        return (W_new, W_prev_new, done, tr), None

    (W, _, _, tr), _ = jax.lax.scan(
        body,
        (W0, W0, jnp.asarray(False), tr0),
        jnp.arange(steps, dtype=jnp.float32),
    )
    return W, tr


# ---------------- out-of-core streamed Nesterov driver ----------------
#
# The single-shot path stages the full [n, dp] design matrix and lets
# jax.lax.scan drive _nesterov over it. Past the stage budget that staging
# is exactly the OOM the streaming layer exists to avoid, so this driver
# restructures the same solver around row blocks: every quantity the
# solver reduces over rows (the power-iteration application, the masked
# gradient, the weighted-accuracy numerator) becomes a sum of per-block
# partial reductions, accumulated in f32 across one streamed pass per
# solver step. Block order is fixed (ascending), so results are
# deterministic; they differ from the single-shot values only by f32
# summation order (tests/test_streaming.py pins the tolerance). The
# trial/split axes stay batched on device — resident state is
# W/W_prev/V/G at [T, S, dp, c] plus the fold tensors, independent of n.

_STREAM_FN_CACHE: Dict[Any, Any] = {}


class _BuiltAtFirstCall:
    """A jitted block program compiled at its first call for the shapes it
    is given, from that call's own arguments, in an ``executor.compile``
    span (``engine=streamed``) whose ``executor.build stage=compile`` child
    holds the backend's compile or persistent-cache load, as every other
    engine's executables are built; later calls with those shapes run the
    executable. ``fn`` is the jitted function."""

    def __init__(self, fn):
        self.fn, self._exes = fn, {}

    def __call__(self, *args):
        key = tuple(None if a is None else (a.shape, a.dtype) for a in args)
        exe = self._exes.get(key)
        if exe is None:
            from ..obs import child_span

            with child_span("executor.compile", engine="streamed",
                            cache="traced"):
                with child_span("executor.build", stage="compile"):
                    exe = self._exes[key] = self.fn.lower(*args).compile()
        return exe(*args)


def _stream_fns(rows, d, c, S, T, fit_intercept, lam):
    """Per-block / per-iteration pieces of the streamed Nesterov solver,
    ``(power_block, power_norm, extrapolate, grad_block, update,
    eval_block)``, each built at its first call (:class:`_BuiltAtFirstCall`)
    and cached on geometry: the engine re-enters stream_scores once per
    trial chunk and every repeat chunk re-dispatches these."""
    key = (rows, d, c, S, T, bool(fit_intercept), float(lam))
    fns = _STREAM_FN_CACHE.get(key)
    if fns is not None:
        return fns

    dp = d + (1 if fit_intercept else 0)
    pen = np.ones((dp, c), np.float32)
    if fit_intercept:
        pen[-1, :] = 0.0
    pen_mask = jnp.asarray(pen)

    def design(blk):
        return add_intercept(blk, bool(fit_intercept))

    @jax.jit
    def power_block(blk, u, v, TW, start):
        # one block's contribution to u = A' diag(w) A v, all splits
        A = design(blk)
        wb = jax.lax.dynamic_slice(TW, (0, start), (S, rows))
        t = jnp.einsum("rd,sd->sr", A, v)
        return u + jnp.einsum("sr,rd->sd", wb * t, A)

    @jax.jit
    def power_norm(u):
        return u / jnp.maximum(
            jnp.linalg.norm(u, axis=1, keepdims=True), 1e-12
        )

    @jax.jit
    def extrapolate(W, Wp, t):
        mom = t / (t + 3.0)
        return W + mom * (W - Wp)

    @jax.jit
    def grad_block(blk, G, V, y_pad, TW, start):
        # the fused masked-gradient formulation of _make_masked_grad_fn,
        # restricted to one block: bf16 matmul inputs, f32 accumulation.
        # Pad rows have wb == 0, so both their softmax term and their
        # label term vanish exactly.
        A = design(blk)
        yb = jax.lax.dynamic_slice(y_pad, (start,), (rows,))
        wb = jax.lax.dynamic_slice(TW, (0, start), (S, rows))
        Z = jnp.einsum(
            "rd,tsdc->tsrc",
            A.astype(jnp.bfloat16), V.astype(jnp.bfloat16),
            preferred_element_type=jnp.float32,
        )
        e = jnp.exp(Z - jnp.max(Z, axis=-1, keepdims=True))
        scale = wb[None] / jnp.sum(e, axis=-1)            # [T, S, rows]
        Yb = jax.nn.one_hot(yb, c, dtype=jnp.float32)
        WY = wb[:, :, None] * Yb[None]                    # [S, rows, c]
        R = e * scale[..., None] - WY[None]
        return G + jnp.einsum(
            "rd,tsrc->tsdc",
            A.astype(jnp.bfloat16), R.astype(jnp.bfloat16),
            preferred_element_type=jnp.float32,
        )

    @jax.jit
    def update(W, Wp, V, G_raw, t, done, lam_max, C, max_iter, tol, tr, slot):
        # _nesterov's scan body, batched over (trial, split) lanes, with
        # the cross-block gradient sum supplied instead of grad_fn(V); the
        # learning curve's slot ``slot`` of ``tr`` (None: capture off) takes
        # this step's gmax, last sample of a stride window winning
        G = C[:, None, None, None] * G_raw + lam * pen_mask[None, None] * V
        gmax = jnp.max(jnp.abs(G), axis=(2, 3))           # [T, S]
        if tr is not None:
            tr = tr.at[slot].set(gmax)
        L = 0.5 * C[:, None] * lam_max[None, :] + lam + 1e-6
        step = (1.0 / L)[:, :, None, None]
        active = jnp.logical_and(t < max_iter[:, None], jnp.logical_not(done))
        a4 = active[:, :, None, None]
        W_new = jnp.where(a4, V - step * G, W)
        Wp_new = jnp.where(a4, W, Wp)
        done = jnp.logical_or(done, gmax < tol[:, None])
        idle = jnp.logical_or(done, (t + 1.0) >= max_iter[:, None])
        return W_new, Wp_new, done, jnp.all(idle), tr

    @jax.jit
    def eval_block(blk, acc, W, y_pad, EW, start):
        # weighted-accuracy numerator, one block at a time (pad rows
        # carry zero eval weight); f32 logits like predict()
        A = design(blk)
        yb = jax.lax.dynamic_slice(y_pad, (start,), (rows,))
        ewb = jax.lax.dynamic_slice(EW, (0, start), (S, rows))
        Z = jnp.einsum("rd,tsdc->tsrc", A, W)
        hit = (jnp.argmax(Z, axis=-1) == yb[None, None, :]).astype(jnp.float32)
        return acc + jnp.einsum("sr,tsr->ts", ewb, hit)

    fns = tuple(_BuiltAtFirstCall(f) for f in (
        power_block, power_norm, extrapolate, grad_block, update, eval_block))
    _STREAM_FN_CACHE[key] = fns
    return fns


def _stream_nesterov_scores(streamer, y_pad, TW, EW, hyper_batch, static, n):
    from ..obs.curves import curves_enabled, trace_stride

    n_classes = int(static["_n_classes"])
    c = max(n_classes, 2)
    fit_intercept = bool(static.get("fit_intercept", True))
    use_penalty = static.get("penalty") in ("l2",)
    lam = (1.0 if use_penalty else 0.0) * (2.0 if n_classes == 2 else 1.0)

    C = jnp.asarray(np.asarray(hyper_batch["C"], np.float32))
    max_iter = jnp.asarray(np.asarray(hyper_batch["max_iter"], np.float32))
    tol = jnp.asarray(np.asarray(hyper_batch["tol"], np.float32))
    T = int(C.shape[0])
    S = int(TW.shape[0])
    rows = int(streamer.plan.rows)
    d = int(streamer.row_shape[0])
    dp = d + (1 if fit_intercept else 0)
    steps = int(static.get("_iters", _NESTEROV_STEPS))

    stride = trace_stride(steps)
    slots = -(-steps // stride) if curves_enabled() else None
    power_block, power_norm, extrapolate, grad_block, update, eval_block = (
        _stream_fns(rows, d, c, S, T, fit_intercept, lam)
    )

    # Lipschitz bound: _nesterov's 30-step power iteration plus the
    # Rayleigh quotient — 31 streamed applications of A' diag(w) A
    v = jnp.ones((S, dp), jnp.float32)
    u = jnp.zeros((S, dp), jnp.float32)
    for it in range(31):
        u = jnp.zeros((S, dp), jnp.float32)
        with streamer.pass_span("power"):
            for _i, start, blk in streamer.iter_blocks():
                u = power_block(blk, u, v, TW, jnp.asarray(start, jnp.int32))
        if it < 30:
            v = power_norm(u)
    lam_max = jnp.sum(v * u, axis=1)                      # [S]

    # the learning curve at the packed path's slots (trace_stride): slot
    # t // stride holds the gmax of the last step of its window
    tr = None if slots is None else jnp.zeros((slots, T, S), jnp.float32)
    W = jnp.zeros((T, S, dp, c), jnp.float32)
    Wp = W
    done = jnp.zeros((T, S), bool)
    ran = 0
    for t in range(steps):
        tf = jnp.asarray(t, jnp.float32)
        V = extrapolate(W, Wp, tf)
        G = jnp.zeros((T, S, dp, c), jnp.float32)
        with streamer.pass_span("step"):
            for _i, start, blk in streamer.iter_blocks():
                G = grad_block(blk, G, V, y_pad, TW,
                               jnp.asarray(start, jnp.int32))
        W, Wp, done, idle, tr = update(
            W, Wp, V, G, tf, done, lam_max, C, max_iter, tol, tr,
            jnp.asarray(t // stride, jnp.int32),
        )
        ran = t + 1
        # host-visible early exit: once every (trial, split) lane is
        # converged or past its max_iter, the remaining scan steps would
        # be masked no-ops — each costing a full pass over the blocks
        if bool(streamer.wait(idle)):
            break

    acc = jnp.zeros((T, S), jnp.float32)
    with streamer.pass_span("eval"):
        for _i, start, blk in streamer.iter_blocks():
            acc = eval_block(blk, acc, W, y_pad, EW,
                             jnp.asarray(start, jnp.int32))
    den = jnp.maximum(jnp.sum(EW.astype(jnp.float32), axis=1), 1e-12)
    out = {"score": acc / den[None, :]}
    if tr is not None:
        # the packed path's leaves: [T, S, slots] and per-lane stride and
        # steps (the steps run: an early exit leaves the later slots out)
        out["curve_gmax"] = jnp.transpose(tr, (1, 2, 0))
        out["curve_stride"] = jnp.full((T, S), float(stride), jnp.float32)
        out["curve_steps"] = jnp.full((T, S), float(ran), jnp.float32)
    return out

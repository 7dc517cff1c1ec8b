"""SVM kernels (SVC / SVR), solved in the dual on-device.

Capability target: the reference's `SVC`/`SVR` trials
(``aws-prod/worker/worker.py:44,50``) — sklearn's RBF-kernel SVMs. The
reference fits libsvm's SMO on CPU per trial; SMO's sequential
working-set updates are hostile to XLA, so this kernel solves the same
box-constrained dual QP with *projected gradient ascent* and a
power-iteration Lipschitz step — every iteration is one [n,n]x[n] matvec
against the precomputed kernel Gram matrix, which XLA batches across
vmapped trials into MXU-sized matmuls.

The dual is solved with its REAL constraint set — the box AND the
``sum(t * alpha) = 0`` hyperplane (libsvm semantics): each ascent step
projects onto the intersection by bisection (`_project_box_hyperplane`,
O(n) per iteration), and the intercept comes from the KKT conditions over
free support vectors afterwards. (Round 3 replaced the earlier
regularized-bias K+1 approximation, which cost ~0.03-0.08 CV on
unbalanced Covertype class pairs; old artifacts predict through a
back-compat branch.)

Multiclass SVC follows sklearn's one-vs-one scheme: c(c-1)/2 binary
machines fit with per-pair weight masks (more masked fits — free under
vmap), votes aggregated with sklearn's tie-breaking (first max).

Hypers: ``C`` (traced), ``epsilon`` for SVR (traced). ``gamma`` is static:
a numeric value is part of the bucket's key, so every value a search draws
is an executable of its own; "scale"/"auto" resolve per fit from the masked
data like sklearn (one executable). ``kernel`` ("rbf" | "linear" | "poly")
is static.

What a fit ran is handed back with it: the exact dual's ``steps`` (the
ascent's iterations, a KKT stop or the cap; the Nyström solve's fixed
count) ride the scores as the ``ascent_steps`` leaf, and the engine's
dispatch reads them (``SVCKernel.dispatch_result``) into the span and the
``tpuml_svm_*`` counters. ``fit_curve`` also hands back the exact dual's
learning curve: the dual objective's linear term, the sum of all
machines' coefficients, after each step.

The exact dual fits on its split's training rows alone where the trial
engine hands it ``_fit_rows``, the most rows a split of the plan trains on
(``with_fit_rows``): each split gathers its training rows in table order,
padded with rows of weight 0, and its Gram, power iteration, ascent and
intercepts run over those [fit_rows] rows. The rows a split masks added
exact zeros to every product, so the fit is the same up to summation
order; at 24 000 training rows of 30 000 a step streams 64% of the bytes.
Without the static (a direct ``fit``, a refit) the Gram is the table's.

Above ``_MAX_N`` samples the exact [n, n] Gram matrix is dropped for a
**Nyström primal** solve (round-1 verdict #5 — the 30k gate previously
errored at Covertype scale): m landmark rows give features
``Z = K(X, L) @ K_LL^{-1/2}`` (one [n,m,d] matmul + a [m,m] eigh), and each
machine solves the primal squared-hinge (SVC) / huberized
epsilon-insensitive (SVR) objective on Z with Nesterov descent — every
iteration one [n,m]x[m] matvec, batched across OvO pairs/vmapped trials on
the MXU. With the r4 solver budget (1200 steps — see ``_nystrom_steps``)
the full-Covertype SVC point measures CV 0.926, ABOVE exact sklearn SVC on
the 30k subsample it can actually complete (0.865); the reference's libsvm
workers could not complete the full fit at all (SMO is O(n^2..3) —
Covertype SVC would run for days).
"""

from __future__ import annotations

import os
from typing import Any, Dict

import jax
import jax.numpy as jnp
import numpy as np

from ..obs import counter_inc
from .base import ModelKernel

_PG_STEPS = int(os.environ.get("CS230_SVM_PG_STEPS", "600"))
_MAX_N = 30_000
#: slots of the exact dual's learning curve over the step cap: the
#: coefficients' sum moves smoothly, 24 slots of 25 steps follow it at 600
_CURVE_SLOTS = 24


def _pg_steps() -> int:
    """Step cap of the exact dual's ascent (``CS230_SVM_PG_STEPS``)."""
    return int(os.environ.get("CS230_SVM_PG_STEPS", _PG_STEPS))


def _nystrom_steps() -> int:
    """Nesterov step count for the Nyström primal solve. The r3 default of
    300 was severely underconverged at full-Covertype scale (the analytic
    Lipschitz bound makes steps tiny): measured CV on the 116k-row SVC
    point was 0.834 @ 300 steps -> 0.897 @ 600 -> 0.926 @ 1200 -> 0.929
    @ 2400, at essentially FLAT wall time (~190 s; Z construction and
    prediction dominate, the [n,m] matvec iterations are cheap on the
    MXU). 1200 sits at the knee and takes the full-Covertype row past
    sklearn's 30k-subsample 0.865 (VERDICT r3 #6 asked for >=0.855)."""
    return int(os.environ.get("CS230_SVM_NYSTROM_STEPS", "1200"))


def _kmeans_iters() -> int:
    """Lloyd iterations refining the landmark set; DEFAULT 0 (off) — a
    measured negative result on Covertype-like data: k-means landmarks
    scored CV 0.798 where uniform rows scored 0.897 (same m=4096, same
    600-step solve). 44 of the 54 features are binary, so centroid
    averaging moves landmarks off the data manifold and degrades the
    Nyström basis; uniform rows are already on-manifold. The knob stays
    for continuous-feature datasets where coverage beats density."""
    return int(os.environ.get("CS230_SVM_KMEANS_ITERS", "0"))


def _nystrom_m(n: int) -> int:
    """Landmark count for the Nyström primal path, scaled with n: the
    rank-m approximation error is what separated full-Covertype SVC from
    sklearn's subsample score (VERDICT r2 #4b: -0.045 CV at flat m=2048).
    n/16 keeps the feature matrix Z [n, m] and the m^2 eigendecomposition
    affordable while roughly tracking the kernel spectrum the data adds;
    measured on v5e at 116k rows: m=4096 closes most of the flat-2048 gap
    (see tests/test_svm.py covertype tolerance)."""
    env = os.environ.get("CS230_SVM_NYSTROM_M")
    if env:
        return int(env)
    return int(min(4096, max(2048, n // 16)))


def _gram(X1, X2, kernel: str, gamma, degree, coef0):
    if kernel == "linear":
        return X1 @ X2.T
    if kernel == "poly":
        return (gamma * (X1 @ X2.T) + coef0) ** degree
    # rbf
    d2 = (
        jnp.sum(X1 * X1, 1)[:, None]
        + jnp.sum(X2 * X2, 1)[None, :]
        - 2.0 * (X1 @ X2.T)
    )
    return jnp.exp(-gamma * jnp.maximum(d2, 0.0))


def _kmeans_landmarks(X, init_centers, iters: int, chunk: int = 16384):
    """Lloyd's k-means refinement of the Nyström landmark set, fully
    on-device. OFF by default: on full Covertype this MEASURED WORSE
    than uniform rows (CV 0.798 vs 0.897 at the same m and solver
    budget) — 44/54 features are binary, and centroid averaging moves
    landmarks off the data manifold (see ``_kmeans_iters``). It remains
    available for continuous-feature data, where center coverage of the
    input space (not row density) bounds the Nyström approximation
    error. Each Lloyd iteration is two MXU matmuls per row chunk
    ([chunk,d]x[d,m] distances, then the one-hot-assignment
    accumulation [m,chunk]x[chunk,d]); rows stream through a lax.scan
    so the [n, m] distance matrix never materializes at full n."""
    n, d = X.shape
    C = init_centers
    m = C.shape[0]
    chunk = min(chunk, n)
    pad = (-n) % chunk
    Xp = jnp.pad(X, ((0, pad), (0, 0)))
    valid = jnp.pad(jnp.ones((n,), jnp.float32), (0, pad))
    Xc = Xp.reshape(-1, chunk, d)
    vc = valid.reshape(-1, chunk)

    def lloyd(C, _):
        cn = jnp.sum(C * C, axis=1)

        def chunk_step(carry, inp):
            sums, counts = carry
            xb, vb = inp
            d2 = cn[None, :] - 2.0 * (xb @ C.T)  # +||x||^2 is argmin-invariant
            a = jnp.argmin(d2, axis=1)
            onehot = jax.nn.one_hot(a, m, dtype=jnp.bfloat16) * vb[:, None].astype(jnp.bfloat16)
            sums = sums + jnp.matmul(
                onehot.T, xb.astype(jnp.bfloat16),
                preferred_element_type=jnp.float32,
            )
            counts = counts + jnp.sum(onehot.astype(jnp.float32), axis=0)
            return (sums, counts), None

        (sums, counts), _ = jax.lax.scan(
            chunk_step,
            (jnp.zeros((m, d), jnp.float32), jnp.zeros((m,), jnp.float32)),
            (Xc, vc),
        )
        # empty clusters keep their previous center (stay a valid landmark)
        return jnp.where(counts[:, None] > 0.5,
                         sums / jnp.maximum(counts[:, None], 1.0), C), None

    C, _ = jax.lax.scan(lloyd, C, None, length=iters)
    return C


def _nystrom_features(X, landmarks, kernel: str, gamma, degree, coef0):
    """Z [n, m] with Z Z' ~ K(X, X): K(X, L) @ K(L, L)^{-1/2} (psd sqrt via
    eigh with a floor on the spectrum)."""
    KL = _gram(X, landmarks, kernel, gamma, degree, coef0)
    KLL = _gram(landmarks, landmarks, kernel, gamma, degree, coef0)
    vals, vecs = jnp.linalg.eigh(KLL)
    inv_sqrt = vecs * jax.lax.rsqrt(jnp.maximum(vals, 1e-6))[None, :]
    return KL @ inv_sqrt, inv_sqrt


def _nesterov_primal(Z, grad_fn, L_est, steps):
    """min_w f(w) by Nesterov descent with an analytic Lipschitz bound."""

    def body(carry, t):
        w, w_prev = carry
        v = w + (t / (t + 3.0)) * (w - w_prev)
        g = grad_fn(v)
        w_new = v - g / L_est
        return (w_new, w), None

    w0 = jnp.zeros((Z.shape[1],), jnp.float32)
    (w, _), _ = jax.lax.scan(
        body, (w0, w0), jnp.arange(steps, dtype=jnp.float32)
    )
    return w


def _matvec_f32(Q, v):
    """Q @ v with f32 accumulation whatever Q's storage dtype (the dual
    ascent stores Q/K in bf16 to halve the HBM stream that bounds it)."""
    return jnp.matmul(Q, v.astype(Q.dtype), preferred_element_type=jnp.float32)


def _lipschitz_eta(Q):
    """1/lambda_max(Q) step size by 25-iteration power method.

    The start vector is a fixed pseudo-random waveform: an all-ones start
    sits EXACTLY in the null space of the SVR block matrix [[K,-K],[-K,K]]
    (Q @ [u;u] = 0 by construction) and would leave the estimate riding on
    float rounding noise; any structured pattern risks a similar
    orthogonality accident (alternating signs re-enter that null space at
    even n). cos(1.7*i + 0.3) has non-negligible overlap with every
    eigenspace of interest and is deterministic across runs."""
    n = Q.shape[0]
    v = jnp.cos(1.7 * jnp.arange(n, dtype=jnp.float32) + 0.3)

    def power(v, _):
        u = _matvec_f32(Q, v)
        return u / jnp.maximum(jnp.linalg.norm(u), 1e-12), None

    v, _ = jax.lax.scan(power, v, None, length=25)
    return 1.0 / jnp.maximum(jnp.dot(v, _matvec_f32(Q, v)), 1e-6)


def _project_box_hyperplane_cols(A_raw, TS, hi, iters: int = 30):
    """Euclidean projection of each column p of ``A_raw`` [n, P] onto
    {0 <= a <= hi[:, p], sum(TS[:, p] * a) = 0} (TS in {-1, 0, +1}):
    a(lam) = clip(A_raw - lam*TS, 0, hi); phi(lam) = sum(TS * a(lam)) is
    monotone non-increasing in lam per column, so per-column bisection
    finds the roots. O(nP) per iteration, fully vectorized."""
    def phi(lam):  # [P] -> [P]
        return jnp.sum(TS * jnp.clip(A_raw - lam[None, :] * TS, 0.0, hi), axis=0)

    span = jnp.max(hi) + jnp.max(jnp.abs(A_raw)) + 1.0
    lo_l = jnp.full((A_raw.shape[1],), -span)
    hi_l = jnp.full((A_raw.shape[1],), span)

    def body(carry, _):
        lo_l, hi_l = carry
        mid = 0.5 * (lo_l + hi_l)
        go_right = phi(mid) > 0
        return (jnp.where(go_right, mid, lo_l), jnp.where(go_right, hi_l, mid)), None

    (lo_l, hi_l), _ = jax.lax.scan(body, (lo_l, hi_l), None, length=iters)
    lam = 0.5 * (lo_l + hi_l)
    return jnp.clip(A_raw - lam[None, :] * TS, 0.0, hi)


def _project_box_hyperplane(a_raw, t, lo, hi, iters: int = 30):
    """Single-machine form: delegates to the column-batched projection
    (every call site uses lo = 0, which the cols form hardcodes)."""
    del lo  # always 0 at every call site; the cols form assumes it
    return _project_box_hyperplane_cols(
        a_raw[:, None], t[:, None],
        jnp.broadcast_to(hi, a_raw.shape)[:, None], iters,
    )[:, 0]


def _fista_ascent(qmatvec, project, lin, x0, eta, steps: int, tol: float,
                  diag, stride=None):
    """Shared FISTA loop (single and multi-machine duals): maximize
    lin.x - 0.5 x'Qx - 0.5 diag||x||^2 over the projection set, with the
    KKT stop — a fixed point of project(x + eta*grad) IS a KKT point, so
    the loop exits when a step's displacement over its step size,
    max|x_new - x| / eta (the projected gradient's largest entry), falls
    to ``tol``: a KKT violation in the dual gradient's own units, whose
    linear term is 1, as libsvm's ``tol`` is. (A displacement held to the
    box size instead stops at the first step wherever eta < tol * C: eta
    is 1 / lambda_max, which falls as the rows grow, and at 24 000
    training rows every C above about 0.5 stopped there with its
    coefficients a step from zero.) Returns ``(x, steps ran)``, and with a
    ``stride`` the learning curve as a third element: the objective's
    linear term lin.x after each step, summed over every column, one slot
    a ``stride`` steps (the slot's last step), the slots after the stop
    holding the last value. It is read beside the step's displacement from
    the same iterate: the whole objective at the extrapolated point,
    0.5 * sum(y * (lin + g)), needs y and the gradient at once, which on a
    v5e moved the projection's operands out of VMEM and cost 16% of a
    search; this costs none that a search shows."""
    def cond(carry):
        x, x_prev, tk, k, res = carry[:5]
        live = res > tol if tol > 0 else jnp.bool_(True)
        return (k < steps) & live

    def body(carry):
        x, x_prev, tk, k, _ = carry[:5]
        t_next = 0.5 * (1.0 + jnp.sqrt(1.0 + 4.0 * tk * tk))
        y = x + ((tk - 1.0) / t_next) * (x - x_prev)
        g = lin - qmatvec(y) - diag * y
        x_new = project(y + eta * g)
        res = jnp.max(jnp.abs(x_new - x) / eta)
        out = (x_new, x, t_next, k + 1, res)
        if stride is None:
            return out
        return out + (carry[5].at[k // stride].set(jnp.sum(lin * x_new)),)

    carry = (x0, x0, jnp.float32(1.0), jnp.int32(0), jnp.float32(jnp.inf))
    if stride is None:
        x, _, _, k, _ = jax.lax.while_loop(cond, body, carry)
        return x, k
    slots = -(-steps // stride)
    x, _, _, k, _, curve = jax.lax.while_loop(
        cond, body, carry + (jnp.zeros((slots,), jnp.float32),))
    last = (k - 1) // stride
    return x, k, jnp.where(jnp.arange(slots) > last, curve[last], curve)


def _kkt_tol() -> float:
    """Early-stop tolerance for the dual ascent: the largest entry of the
    projected gradient, a step's displacement over its step size, in the
    dual gradient's units (libsvm's default ``tol``, 1e-3). 0 disables the
    stop (fixed step count — the pre-r5 behavior, and the A/B baseline)."""
    return float(os.environ.get("CS230_SVM_KKT_TOL", "1e-3"))


def _constrained_dual_ascent(Q, lin, t, lo, hi, steps=None, diag=0.0):
    """max_a lin.a - 0.5 a'Qa s.t. lo <= a <= hi AND sum(t*a) = 0 — the
    C-SVM dual's REAL constraint set (libsvm semantics). The box-only form
    approximated the intercept by penalizing it into the kernel (K+1),
    which costs accuracy on unbalanced class pairs; projecting onto the
    box∩hyperplane intersection (bisection, _project_box_hyperplane) each
    step solves the constrained dual directly, and the intercept comes
    from the KKT conditions afterwards.

    r5: FISTA acceleration + KKT-residual early stop. Plain projected
    ascent with the 1/L step needs O(kappa) iterations; the Nesterov
    t-sequence extrapolation (accelerated projected gradient on the
    equivalent convex minimization) gets O(sqrt(kappa)) at identical
    per-step cost — the step is still one [n,n] matvec, the fit's
    HBM-bound term. The while_loop stops once the projected gradient's
    largest entry (a step's displacement over its step size) falls to
    ``_kkt_tol()`` (a stationarity certificate for the projection
    operator: a fixed point of P_C(a + eta*grad) IS a KKT point), so easy
    machines stop early instead of burning the full budget. vmapped lanes
    run until the SLOWEST lane converges — still bounded by ``steps``."""
    if steps is None:
        steps = _pg_steps()

    # the ascent is HBM-bound, not FLOP-bound: the [n, n] kernel operand
    # streams from memory on every step (~540 MB x 600 steps per OvO pair
    # at 11.6k rows in f32). RBF callers therefore pass Q ALREADY in bf16
    # — half the stream, 2.05x measured on the 11.6k-row Covertype model-
    # matrix fit (33.7 -> 16.4 s); its CV moved +0.001 (0.8161 -> 0.8171,
    # still at sklearn parity), inside what the box/hyperplane projection
    # absorbs for entries bounded in [0, 1]. ``diag`` applies the
    # stability ridge analytically in f32 — 1e-6 is below bf16 resolution
    # near 1.0, so it cannot ride inside a bf16 matrix.
    return _fista_ascent(
        qmatvec=lambda a: _matvec_f32(Q, a),
        project=lambda x: _project_box_hyperplane(x, t, lo, hi),
        lin=lin,
        x0=jnp.zeros((Q.shape[0],), jnp.float32),
        eta=_lipschitz_eta(Q),
        steps=steps,
        tol=_kkt_tol(),
        diag=diag,
    )[0]


def _constrained_dual_ascent_multi(Kb, lin, TS, hi, steps=None, diag=0.0,
                                   stride=None, rows=None):
    """ALL OvO machines of one fit in ONE ascent: A [n, P] dual columns,
    and the steps the ascent ran (and with a ``stride`` its curve:
    :func:`_fista_ascent`). ``rows`` are the table indices of Kb's rows
    (all of the table's by default): the power iteration starts from them.

    The per-pair form (vmap over ``_constrained_dual_ascent``) re-streams
    the SAME [n, n] kernel operand once per machine per iteration — at
    11.6k rows x 21 pairs x 6 fold lanes that is ~34 GB per iteration, and
    measured wall time was FLAT in the step cap because the stream, not
    the math, was the bill. Batched, each iteration is one
    [n, n] x [n, P] matmul: Kb streams ONCE per iteration per lane
    (~126x less HBM traffic), with Q's pair masks applied as elementwise
    TS factors (Q_p @ v = ts_p * (K @ (ts_p * v))). FISTA extrapolation
    and the KKT displacement stop carry over; the loop exits when the
    SLOWEST machine converges."""
    if steps is None:
        steps = _pg_steps()
    tol = _kkt_tol()

    def qmatvec(V):  # [n, P] -> [n, P], f32 accumulation
        return TS * jnp.matmul(
            Kb, (TS * V).astype(Kb.dtype), preferred_element_type=jnp.float32
        )

    # per-machine 1/lambda_max by batched power iteration (the waveform
    # start rationale is in _lipschitz_eta)
    n, P = lin.shape
    idx = jnp.arange(n) if rows is None else rows
    v = jnp.broadcast_to(
        jnp.cos(1.7 * idx.astype(jnp.float32) + 0.3)[:, None], (n, P)
    )

    def power(v, _):
        u = qmatvec(v)
        return u / jnp.maximum(jnp.linalg.norm(u, axis=0, keepdims=True), 1e-12), None

    v, _ = jax.lax.scan(power, v, None, length=25)
    lam_max = jnp.maximum(jnp.sum(v * qmatvec(v), axis=0), 1e-6)

    return _fista_ascent(
        qmatvec=qmatvec,
        project=lambda X: _project_box_hyperplane_cols(X, TS, hi),
        lin=lin,
        x0=jnp.zeros((n, P), jnp.float32),
        eta=(1.0 / lam_max)[None, :],
        steps=steps,
        tol=tol,
        diag=diag,
        stride=stride,
    )


def _curve_stride(steps: int) -> int:
    """Steps a slot of the exact dual's curve: ``_CURVE_SLOTS`` slots over
    the step cap (fewer where ``CS230_CURVE_POINTS`` asks for fewer)."""
    from ..obs.curves import trace_stride

    return max(trace_stride(steps), -(-steps // _CURVE_SLOTS))


class SVCKernel(ModelKernel):
    name = "SVC"
    task = "classification"
    hyper_defaults = {"C": 1.0}
    static_defaults = {"kernel": "rbf", "gamma": "scale", "degree": 3, "coef0": 0.0}

    def trace_salt(self):
        """Solver knobs read from env at trace time (module docstring) —
        they change the compiled program, so they must key the AOT cache
        (a knob flip must not load the pre-knob executable). CS230_CURVES
        joins them: with capture on, the exact dual's loop carries its
        curve and the program hands it back."""
        from ..obs.curves import curves_salt

        return (
            _pg_steps(),
            _nystrom_steps(),
            _kmeans_iters(),
            os.environ.get("CS230_SVM_NYSTROM_M", ""),
            os.environ.get("CS230_SVM_KKT_TOL", ""),
            curves_salt(),
        )

    def resolve_static(self, static: Dict[str, Any], n: int, d: int, n_classes: int):
        if static.get("kernel") not in ("rbf", "linear", "poly"):
            raise ValueError(f"SVC: unsupported kernel {static.get('kernel')!r}")
        g = static.get("gamma", "scale")
        if isinstance(g, (int, float)):
            static = {**static, "_gamma_mode": "numeric", "_gamma_value": float(g)}
        else:
            static = {**static, "_gamma_mode": g}
        if n > _MAX_N:
            # beyond the exact-Gram gate: Nyström primal (module docstring)
            static = {**static, "_nystrom": True, "_m": min(_nystrom_m(n), n)}
        return static

    def with_fit_rows(self, static: Dict[str, Any], n: int, fit_rows: int):
        """The exact dual's Gram over the most rows a split trains on
        (``_fit_rows``), where that is fewer than the table's ``n``."""
        if static.get("_nystrom") or not 0 < fit_rows < n:
            return static
        return {**static, "_fit_rows": int(fit_rows)}

    # ---- shared Nyström-primal machinery (SVC + SVR) ----

    def _nystrom_Z(self, X, gamma, static):
        n = X.shape[0]
        m = int(static["_m"])
        idx = np.random.RandomState(17).choice(n, m, replace=False)
        landmarks = X[jnp.asarray(idx)]
        iters = _kmeans_iters()
        if iters > 0:
            landmarks = _kmeans_landmarks(X, landmarks, iters)
        Z, inv_sqrt = _nystrom_features(
            X, landmarks, static["kernel"], gamma,
            static.get("degree", 3), static.get("coef0", 0.0),
        )
        Z = jnp.concatenate([Z, jnp.ones((n, 1), jnp.float32)], axis=1)
        # Lipschitz ingredient: lambda_max(Z'Z) by power iteration
        v = jnp.ones((Z.shape[1],), jnp.float32)

        def power(v, _):
            u = Z.T @ (Z @ v)
            return u / jnp.maximum(jnp.linalg.norm(u), 1e-12), None

        v, _ = jax.lax.scan(power, v, None, length=20)
        lam_max = jnp.maximum(jnp.dot(v, Z.T @ (Z @ v)), 1e-6)
        return Z, landmarks, inv_sqrt, lam_max

    def fit(self, X, y, w, hyper: Dict[str, Any], static: Dict[str, Any]):
        return self._fit(X, y, w, hyper, static, trace=False)[0]

    def fit_curve(self, X, y, w, hyper: Dict[str, Any], static: Dict[str, Any]):
        """Capture hook (docs/OBSERVABILITY.md "Trial telemetry plane"): the
        same fit, and the exact dual's learning curve ``{"gmax": [slots],
        "stride", "steps"}``: the sum of the coefficients of all machines
        after each step, the dual objective's linear term
        (``_fista_ascent``), named ``gmax`` as the other families' curve
        channel is. The Nyström primal hands back no curve."""
        return self._fit(X, y, w, hyper, static, trace=True)

    def _fit(self, X, y, w, hyper: Dict[str, Any], static: Dict[str, Any],
             trace: bool):
        X = X.astype(jnp.float32)
        w = w.astype(jnp.float32)
        c = max(int(static["_n_classes"]), 2)
        C = jnp.asarray(hyper["C"], jnp.float32)
        gamma = self._gamma(X, w, static)
        if static.get("_nystrom"):
            return self._fit_nystrom(X, y, w, C, gamma, static, c), {}
        rows = None
        if "_fit_rows" in static:
            # the split's training rows in table order, then pad rows (the
            # table's row 0) of weight 0 up to the plan's largest split;
            # gamma above is the masked table's, to the bit
            rows = jnp.nonzero(w > 0, size=int(static["_fit_rows"]))[0]
            live = jnp.arange(rows.shape[0]) < jnp.sum(w > 0)
            X, y, w = X[rows], y[rows], jnp.where(live, w[rows], 0.0)
        K = _gram(X, X, static["kernel"], gamma, static.get("degree", 3), static.get("coef0", 0.0))
        # bf16 kernel operand for the ascent's matvec stream (the fit's
        # HBM-bound term, see _constrained_dual_ascent) — RBF only, whose
        # entries are bounded in [0, 1]; linear/poly Gram entries are
        # unbounded on unscaled data, where bf16's relative rounding could
        # swamp the O(1) linear term near convergence. The KKT intercept
        # below keeps the f32 K either way.
        Kb = K.astype(jnp.bfloat16) if static["kernel"] == "rbf" else K

        pairs = [(i, j) for i in range(c) for j in range(i + 1, c)]
        pa = jnp.asarray([p[0] for p in pairs])
        pb = jnp.asarray([p[1] for p in pairs])

        # ALL OvO machines in one batched ascent (A [n, P]): the per-pair
        # vmap re-streamed the [n, n] Gram once per machine per iteration
        # and was measured step-cap-FLAT at 13.7 s on the 11.6k model-
        # matrix row — the HBM stream, not the math, was the bill. See
        # _constrained_dual_ascent_multi. libsvm's actual dual: box AND
        # the sum(t*alpha)=0 hyperplane per machine; stability ridge
        # rides analytically (diag=1e-6).
        S = (((y[:, None] == pa[None, :]) | (y[:, None] == pb[None, :]))
             & (w > 0)[:, None]).astype(jnp.float32)  # [n, P]
        T = jnp.where(y[:, None] == pa[None, :], 1.0, -1.0)
        TS = T * S
        steps = _pg_steps()
        stride = _curve_stride(steps) if trace else None
        A, ran, *curve = _constrained_dual_ascent_multi(
            Kb, S, TS, C * S, steps=steps, diag=1e-6, stride=stride, rows=rows)
        # KKT intercepts: average t_i - (margin) over FREE support vectors
        # (0 < alpha < C) per machine; fall back to all SVs. The margins'
        # product is float32 at HIGHEST: the default takes one bfloat16
        # pass on a TPU, and the compiler then keeps only the bf16 Gram
        F = jnp.matmul(K, A * TS, precision=jax.lax.Precision.HIGHEST,
                       preferred_element_type=jnp.float32)
        free = S * (A > 1e-6 * C) * (A < C * (1.0 - 1e-6))
        anyv = S * (A > 1e-6 * C)
        use = jnp.where(jnp.sum(free, axis=0) > 0.5, free, anyv)
        b = jnp.sum(use * (T - F), axis=0) / jnp.maximum(
            jnp.sum(use, axis=0), 1e-6
        )
        params = {"X": X, "dual": (A * TS).T, "intercept": b, "gamma": gamma,
                  "pairs_a": pa, "pairs_b": pb, "steps": ran}
        if not trace:
            return params, {}
        return params, {
            "gmax": curve[0],
            "stride": jnp.asarray(float(stride), jnp.float32),
            "steps": jnp.asarray(float(steps), jnp.float32),
        }

    def _fit_nystrom(self, X, y, w, C, gamma, static, c):
        """Primal squared-hinge OvO machines on Nyström features."""
        Z, landmarks, inv_sqrt, lam_max = self._nystrom_Z(X, gamma, static)
        pairs = [(i, j) for i in range(c) for j in range(i + 1, c)]
        pa = jnp.asarray([p[0] for p in pairs])
        pb = jnp.asarray([p[1] for p in pairs])
        L_est = 1.0 + 2.0 * C * lam_max

        def fit_pair(cls_a, cls_b):
            s = (((y == cls_a) | (y == cls_b)) & (w > 0)).astype(jnp.float32)
            t = jnp.where(y == cls_a, 1.0, -1.0)

            def grad(wv):
                margin = jnp.maximum(0.0, 1.0 - t * (Z @ wv))
                return wv - 2.0 * C * (Z.T @ (s * t * margin))

            return _nesterov_primal(Z, grad, L_est, _nystrom_steps())

        W = jax.vmap(fit_pair)(pa, pb)  # [n_pairs, m+1]
        return {
            "W": W,
            "landmarks": landmarks,
            "inv_sqrt": inv_sqrt,
            "gamma": gamma,
            "pairs_a": pa,
            "pairs_b": pb,
            "steps": jnp.int32(_nystrom_steps()),
        }

    def _pair_decisions(self, params, X, static: Dict[str, Any]):
        """[nq, n_pairs] OvO decision values; >0 votes pairs_a."""
        if "W" in params:
            Zq = _gram(
                X.astype(jnp.float32), params["landmarks"], static["kernel"],
                params["gamma"], static.get("degree", 3), static.get("coef0", 0.0),
            ) @ params["inv_sqrt"]
            Zq = jnp.concatenate([Zq, jnp.ones((X.shape[0], 1), jnp.float32)], 1)
            return Zq @ params["W"].T
        Kq = _gram(
            X.astype(jnp.float32),
            params["X"],
            static["kernel"],
            params["gamma"],
            static.get("degree", 3),
            static.get("coef0", 0.0),
        )
        dec = Kq @ params["dual"].T  # [nq, n_pairs], >0 votes class pairs_a
        if "intercept" in params:
            return dec + params["intercept"][None, :]
        # artifacts fitted before the KKT-intercept form: K+1 bias
        return dec + jnp.sum(params["dual"], axis=1)[None, :]

    def evaluate(self, params, X, y, w, static: Dict[str, Any]) -> Dict[str, Any]:
        """The score, and the steps the fit's solver ran as the
        ``ascent_steps`` leaf, which the engine's dispatch reads
        (:meth:`dispatch_result`)."""
        out = dict(super().evaluate(params, X, y, w, static))
        out["ascent_steps"] = jnp.asarray(params["steps"], jnp.int32)
        return out

    def dispatch_result(self, static: Dict[str, Any], n: int, out,
                        n_trials: int) -> Dict[str, Any]:
        """What a dispatch ran, from its fetched result (``ascent_steps``
        [lanes, splits], of which the first ``n_trials`` lanes are real):
        the attributes of its ``executor.dispatch`` span and the
        ``tpuml_svm_*`` counters. ``svm.fit_rows`` are the rows a lane's
        fit holds (``_fit_rows``, else the table's ``n``), and
        ``svm.gram_bytes`` the operand one ascent step streams a lane (the
        bfloat16 [fit_rows, fit_rows] Gram of the exact dual, the float32
        [n, m+1] Nyström features), so a lane streams ``steps`` times it."""
        steps = np.asarray(out["ascent_steps"], np.int64)[:n_trials]
        nystrom = bool(static.get("_nystrom"))
        c = max(int(static["_n_classes"]), 2)
        rows = int(static.get("_fit_rows", n))
        if nystrom:
            gram_bytes = 4 * n * (int(static["_m"]) + 1)
        else:
            gram_bytes = rows * rows * (2 if static["kernel"] == "rbf" else 4)
            counter_inc("tpuml_svm_exact_lanes_total", steps.size,
                        rows="train" if rows < n else "table")
        total = int(steps.sum())
        counter_inc("tpuml_svm_ascent_steps_total", total,
                    solver="nystrom" if nystrom else "exact")
        counter_inc("tpuml_svm_gram_stream_bytes_total", float(total) * gram_bytes)
        return {"svm.solver": "nystrom" if nystrom else "exact",
                "svm.machines": c * (c - 1) // 2, "svm.fit_rows": rows,
                "svm.gram_bytes": gram_bytes,
                "svm.steps_max": int(steps.max(initial=0)), "svm.steps_sum": total}

    def predict(self, params, X, static: Dict[str, Any]):
        c = max(int(static["_n_classes"]), 2)
        dec = self._pair_decisions(params, X, static)
        vote_a = (dec > 0).astype(jnp.float32)
        votes = jnp.zeros((X.shape[0], c), jnp.float32)
        votes = votes.at[:, params["pairs_a"]].add(vote_a)
        votes = votes.at[:, params["pairs_b"]].add(1.0 - vote_a)
        return jnp.argmax(votes, axis=-1).astype(jnp.int32)

    def predict_margin(self, params, X, static: Dict[str, Any]):
        """Binary decision function, positive for class 1 (the single OvO
        pair's value is positive for pairs_a == class 0, hence the sign
        flip — matches sklearn's binary decision_function orientation)."""
        return -self._pair_decisions(params, X, static)[:, 0]

    def _gamma(self, X, w, static):
        if static.get("_gamma_mode") == "numeric":
            return jnp.asarray(static["_gamma_value"], jnp.float32)
        if static.get("_gamma_mode") == "auto":
            return jnp.asarray(1.0 / X.shape[1], jnp.float32)
        # sklearn's "scale": 1 / (n_features * X.var()) over the training
        # rows, the variance of all their entries about ONE mean
        w = w.astype(jnp.float32)
        cells = jnp.maximum(jnp.sum(w), 1.0) * X.shape[1]
        mean = jnp.sum(X * w[:, None]) / cells
        var = jnp.sum(w[:, None] * (X - mean) ** 2) / cells
        return 1.0 / jnp.maximum(X.shape[1] * var, 1e-12)

    def memory_estimate_mb(self, n, d, static):
        if static.get("_nystrom"):
            m = int(static.get("_m", 2048)) + 1
            return max(1.0, 4.0 * (2.0 * n * m + n * d) / 1e6)
        # the fit's float32 and bfloat16 Grams over its rows, or the
        # scoring's [n, rows] kernel block, whichever is the larger
        rows = int(static.get("_fit_rows", n))
        return max(1.0, 4.0 * (rows * max(2 * rows, n) + n * d) / 1e6)


class SVRKernel(ModelKernel):
    name = "SVR"
    task = "regression"
    hyper_defaults = {"C": 1.0, "epsilon": 0.1}
    static_defaults = {"kernel": "rbf", "gamma": "scale", "degree": 3, "coef0": 0.0}

    resolve_static = SVCKernel.resolve_static
    trace_salt = SVCKernel.trace_salt
    _gamma = SVCKernel._gamma
    _nystrom_Z = SVCKernel._nystrom_Z
    memory_estimate_mb = SVCKernel.memory_estimate_mb

    def fit(self, X, y, w, hyper: Dict[str, Any], static: Dict[str, Any]):
        X = X.astype(jnp.float32)
        y = y.astype(jnp.float32)
        w = w.astype(jnp.float32)
        C = jnp.asarray(hyper["C"], jnp.float32)
        eps = jnp.asarray(hyper["epsilon"], jnp.float32)
        gamma = self._gamma(X, w, static)
        if static.get("_nystrom"):
            return self._fit_nystrom(X, y, w, C, eps, gamma, static)
        K = _gram(X, X, static["kernel"], gamma, static.get("degree", 3), static.get("coef0", 0.0))
        s = (w > 0).astype(jnp.float32)
        n = K.shape[0]
        # dual in beta = alpha - alpha*: max y.b - eps|b| - 0.5 b'Kb, |b|<=C,
        # AND sum(beta) = 0 (the intercept's constraint — same libsvm
        # semantics as the SVC fix above). Solved in the split form
        # [alpha; alpha*] >= 0 with t = [+1; -1] carrying the constraint.
        # masked Gram, computed once: the ascent streams it as bf16 (RBF
        # only — see the SVC fit note), the KKT intercept reuses the f32
        # form; the stability ridge moves to the analytic diag (1e-6 is
        # below bf16 resolution near 1.0 — it cannot ride inside a bf16
        # matrix, and its 1e-6*beta contribution to the intercept matvec
        # is noise)
        Ks = K * (s[:, None] * s[None, :])
        Ksb = Ks.astype(jnp.bfloat16) if static["kernel"] == "rbf" else Ks
        Q = jnp.block([[Ksb, -Ksb], [-Ksb, Ksb]])
        lin = jnp.concatenate([(y - eps) * s, (-y - eps) * s])
        box = jnp.concatenate([C * s, C * s])
        t = jnp.concatenate([s, -s])
        a = _constrained_dual_ascent(Q, lin, t, 0.0, box, diag=1e-6)
        beta = (a[:n] - a[n:]) * s
        # KKT intercept: free upper SVs sit on y - f = eps, free lower on
        # y - f = -eps
        f = Ks @ beta
        free_up = s * (a[:n] > 1e-6 * C) * (a[:n] < C * (1.0 - 1e-6))
        free_dn = s * (a[n:] > 1e-6 * C) * (a[n:] < C * (1.0 - 1e-6))
        num = jnp.sum(free_up * (y - f - eps)) + jnp.sum(free_dn * (y - f + eps))
        den = jnp.sum(free_up) + jnp.sum(free_dn)
        b = jnp.where(den > 0.5, num / jnp.maximum(den, 1e-6),
                      jnp.sum(s * (y - f)) / jnp.maximum(jnp.sum(s), 1e-6))
        return {"X": X, "dual": beta, "intercept": b, "gamma": gamma}

    def _fit_nystrom(self, X, y, w, C, eps, gamma, static):
        """Primal huberized epsilon-insensitive regression on Nyström
        features: l(r) = max(0, |r| - eps)^2."""
        Z, landmarks, inv_sqrt, lam_max = self._nystrom_Z(X, gamma, static)
        s = (w > 0).astype(jnp.float32)
        L_est = 1.0 + 2.0 * C * lam_max

        def grad(wv):
            r = Z @ wv - y
            dl = 2.0 * jnp.sign(r) * jnp.maximum(jnp.abs(r) - eps, 0.0)
            return wv + C * (Z.T @ (s * dl))

        wv = _nesterov_primal(Z, grad, L_est, _nystrom_steps())
        return {"W": wv, "landmarks": landmarks, "inv_sqrt": inv_sqrt, "gamma": gamma}

    def predict(self, params, X, static: Dict[str, Any]):
        if "W" in params:
            Zq = _gram(
                X.astype(jnp.float32), params["landmarks"], static["kernel"],
                params["gamma"], static.get("degree", 3), static.get("coef0", 0.0),
            ) @ params["inv_sqrt"]
            Zq = jnp.concatenate([Zq, jnp.ones((X.shape[0], 1), jnp.float32)], 1)
            return Zq @ params["W"]
        Kq = _gram(
            X.astype(jnp.float32),
            params["X"],
            static["kernel"],
            params["gamma"],
            static.get("degree", 3),
            static.get("coef0", 0.0),
        )
        out = Kq @ params["dual"]
        if "intercept" in params:
            return out + params["intercept"]
        # artifacts fitted before the KKT-intercept form used K+1 bias
        return out + jnp.sum(params["dual"])


from .registry import register_kernel  # noqa: E402  (self-registration on import)

register_kernel(SVCKernel())
register_kernel(SVRKernel())

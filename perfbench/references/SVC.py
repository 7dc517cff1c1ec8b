"""Plain reference of the SVC family's search semantics: sklearn's
``SVC(kernel="rbf", gamma="scale")``, libsvm's one-vs-one C-SVC, solved in
the exact dual.

What a trial is on one split, from the estimator's published description:

- ``gamma`` is sklearn's "scale", ``1 / (n_features * X.var())`` over the
  split's training rows (the variance of all their entries about one mean);
  ``K = exp(-gamma * ||x - x'||^2)`` over those rows;
- one binary machine for each class pair (a, b), a < b, on the pair's
  training rows, ``t = +1`` on class a and ``-1`` on b: libsvm's dual, max
  ``sum(alpha) - 0.5 * sum_ij alpha_i alpha_j t_i t_j K_ij`` subject to
  ``0 <= alpha <= C`` and ``sum(t * alpha) = 0``, with a ridge of 1e-6 on
  the diagonal;
- the intercept is the mean of ``t_i - f(x_i)`` over the free support
  vectors (``0 < alpha < C``), over all support vectors where none is free;
- a held-out row gets sklearn's one-vs-one vote (class a wins a pair where
  the decision value is above 0; the most votes win, the first class on a
  tie), and the score is the accuracy over the held-out rows.

**Departure from libsvm, named:** libsvm solves each dual by SMO; this
solves it as the system documents its solver, which reaches the same
optimum by another path and is what the learning curve follows: FISTA with
the step ``1 / lambda_max`` of each machine's matrix from a 25-step power
iteration (started at ``cos(1.7 i + 0.3)`` over each row's index ``i`` in
the table), every step projected onto the box and the hyperplane by 30
bisections of the hyperplane's multiplier, all machines of a split stopping
together at the first step whose displacement over its step size (the
projected gradient's largest entry, in the units of libsvm's ``tol``) is
at most 1e-3, or after 600 steps. The curve (``gmax``, the name the harness
reads) is the sum of all machines' coefficients after each step (the
dual objective's linear term), held at its last value after the stop.

Straightforward ``jax.numpy`` in float32 at ``highest`` matmul precision,
one split's Gram at a time (24 000 training rows: 2.3 GB), the trials of a
split side by side over it; it imports nothing of the program and takes
nothing the program has made.

``precision`` puts every matmul operand on a coarser grid first: the rows
in the Gram's cross term, the Gram and the vector in the ascent's and the
power iteration's products and in the intercepts', the held-out Gram and
the coefficients in the decision. ``int8`` is a symmetric 8-bit grid with
one scale a tensor; any other name is a float grid of ``GRIDS``
(``float8_e4m3fn`` scaled to the tensor's largest entry, ``bfloat16`` as it
is).

``fault`` breaks the fit in a known way (``half_batch``: every second
training row left out of the ascent's gradient, the step taken as if
nothing were missing).
"""

from __future__ import annotations

import functools

import numpy as np

# A float grid: significant bits, least normal exponent, and the largest
# value, to which a tensor's largest entry is scaled (None: not scaled).
GRIDS = {"bfloat16": (8, -100, None), "float8_e4m3fn": (4, -6, 448.0)}
FAULTS = ("half_batch",)

POWER_STEPS = 25
STEP_CAP = 600
TOL = 1e-3
RIDGE = 1e-6
BISECTIONS = 30
#: training and held-out rows are padded to a multiple of this, so the
#: splits (whose sizes differ by a row or two) share one program
ROW_PAD = 512


def _q(x, precision):
    """A matmul operand on the precision's grid, back in float32. Rounded
    by arithmetic on float32 values and through no narrow type, so that
    every backend rounds alike."""
    import jax.numpy as jnp

    if precision == "f32":
        return x
    amax = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30)
    if precision == "int8":
        scale = amax / 127.0
        return jnp.clip(jnp.round(x / scale), -127.0, 127.0) * scale
    bits, emin, top = GRIDS[precision]
    scale = amax / top if top else 1.0
    _, ex = jnp.frexp(x / scale)  # |x / scale| = m * 2**ex with m in [0.5, 1)
    step = jnp.ldexp(jnp.float32(1.0), jnp.maximum(ex - 1, emin) - (bits - 1))
    return jnp.round(x / scale / step) * step * scale


def _rbf(A, B, gamma, precision):
    """exp(-gamma * ||a - b||^2): the norms in float32, the cross term a
    matmul of the operands on the grid."""
    import jax.numpy as jnp

    d2 = (jnp.sum(A * A, 1)[:, None] + jnp.sum(B * B, 1)[None, :]
          - 2.0 * (_q(A, precision) @ _q(B, precision).T))
    return jnp.exp(-gamma * jnp.maximum(d2, 0.0))


def _project(A_raw, TS, hi):
    """Each column of ``A_raw`` onto {0 <= a <= hi, sum(TS * a) = 0}: the
    multiplier lam of clip(A_raw - lam * TS, 0, hi) by bisection of the
    monotone sum(TS * a(lam)) over [-span, span]."""
    import jax
    import jax.numpy as jnp

    def phi(lam):
        return jnp.sum(TS * jnp.clip(A_raw - lam[None, :] * TS, 0.0, hi), axis=0)

    span = jnp.max(hi) + jnp.max(jnp.abs(A_raw)) + 1.0
    lo = jnp.full((A_raw.shape[1],), -span)
    up = jnp.full((A_raw.shape[1],), span)

    def halve(bounds, _):
        lo, up = bounds
        mid = 0.5 * (lo + up)
        right = phi(mid) > 0
        return (jnp.where(right, mid, lo), jnp.where(right, up, mid)), None

    (lo, up), _ = jax.lax.scan(halve, (lo, up), None, length=BISECTIONS)
    return jnp.clip(A_raw - (0.5 * (lo + up))[None, :] * TS, 0.0, hi)


@functools.partial(__import__("jax").jit, static_argnames=("c", "precision", "fault"))
def _split(Xtr, ytr, rows, real, Xte, yte, held, C, *, c, precision, fault):
    """Every trial of ``C`` [L] on one split: (accuracy [L], curve [L,
    STEP_CAP]). ``rows`` are the training rows' indices in the table,
    ``real`` / ``held`` 0/1 marks of the rows that are not padding."""
    import jax
    import jax.numpy as jnp

    d = Xtr.shape[1]
    with jax.default_matmul_precision("highest"):
        cells = jnp.sum(real) * d
        mean = jnp.sum(Xtr * real[:, None]) / cells
        var = jnp.sum(real[:, None] * (Xtr - mean) ** 2) / cells
        gamma = 1.0 / jnp.maximum(d * var, 1e-12)
        K = _q(_rbf(Xtr, Xtr, gamma, precision), precision)
        # the ascent's gradient: with the fault, every second row's
        # coefficient adds nothing to any machine's gradient
        Kg = K * (jnp.arange(K.shape[0]) % 2 == 0)[None, :] if fault == "half_batch" else K

        pa = np.array([a for a in range(c) for b in range(a + 1, c)])
        pb = np.array([b for a in range(c) for b in range(a + 1, c)])
        S = (((ytr[:, None] == pa[None, :]) | (ytr[:, None] == pb[None, :]))
             & (real[:, None] > 0)).astype(jnp.float32)  # [m, machines]
        T = jnp.where(ytr[:, None] == pa[None, :], 1.0, -1.0)
        TS = T * S

        def matvec(M, V):  # Q_p v_p of every machine p: TS * (M @ (TS * V))
            return TS * (M @ _q(TS * V, precision))

        def power(v, _):
            u = matvec(K, v)
            return u / jnp.maximum(jnp.linalg.norm(u, axis=0, keepdims=True), 1e-12), None

        v0 = jnp.broadcast_to(jnp.cos(1.7 * rows.astype(jnp.float32) + 0.3)[:, None], S.shape)
        v, _ = jax.lax.scan(power, v0, None, length=POWER_STEPS)
        eta = 1.0 / jnp.maximum(jnp.sum(v * matvec(K, v), axis=0), 1e-6)  # [machines]

        def solve(Ct):
            hi = Ct * S

            def live(carry):
                return (carry[3] < STEP_CAP) & (carry[4] > TOL)

            def step(carry):
                x, x_prev, t, k, _, curve = carry
                t_next = 0.5 * (1.0 + jnp.sqrt(1.0 + 4.0 * t * t))
                yv = x + ((t - 1.0) / t_next) * (x - x_prev)
                g = S - matvec(Kg, yv) - RIDGE * yv
                x_new = _project(yv + eta[None, :] * g, TS, hi)
                curve = curve.at[k].set(jnp.sum(S * x_new))
                return x_new, x, t_next, k + 1, jnp.max(jnp.abs(x_new - x) / eta[None, :]), curve

            zero = jnp.zeros_like(S)
            A, _, _, k, _, curve = jax.lax.while_loop(
                live, step, (zero, zero, jnp.float32(1.0), jnp.int32(0), jnp.float32(jnp.inf),
                             jnp.zeros((STEP_CAP,), jnp.float32)))
            curve = jnp.where(jnp.arange(STEP_CAP) >= k, curve[k - 1], curve)

            coef = A * TS
            F = K @ _q(coef, precision)
            free = S * (A > 1e-6 * Ct) * (A < Ct * (1.0 - 1e-6))
            anyv = S * (A > 1e-6 * Ct)
            use = jnp.where(jnp.sum(free, axis=0) > 0.5, free, anyv)
            b = jnp.sum(use * (T - F), axis=0) / jnp.maximum(jnp.sum(use, axis=0), 1e-6)

            dec = _q(_rbf(Xte, Xtr, gamma, precision), precision) @ _q(coef, precision) + b[None, :]
            win_a = (dec > 0).astype(jnp.float32)
            votes = jnp.zeros((Xte.shape[0], c), jnp.float32)
            votes = votes.at[:, pa].add(win_a).at[:, pb].add(1.0 - win_a)
            hit = (jnp.argmax(votes, axis=1) == yte).astype(jnp.float32)
            return jnp.sum(hit * held) / jnp.sum(held), curve

        return jax.vmap(solve)(C)


def _padded(a, size):
    return np.concatenate([a, np.zeros((size - len(a),) + a.shape[1:], a.dtype)])


def reference(X, y, n_classes, params, splits, *, precision="f32", fault=None):
    """What a search returns for the given trials on the given splits:
    ``score`` [len(params), n_splits] and ``gmax`` [len(params), n_splits,
    STEP_CAP], the coefficients' sum after every solver step (the learning curve
    the system hands back with each trial).

    ``params``: one dict per trial with the estimator's full parameters
    (``C``; ``kernel`` "rbf" and ``gamma`` "scale", the only ones this
    states). ``splits``: (train [S, n], eval [S, n]) 0/1 masks."""
    import jax.numpy as jnp

    if any(p.get("kernel", "rbf") != "rbf" or p.get("gamma", "scale") != "scale" for p in params):
        raise ValueError("the reference states the RBF kernel at gamma='scale' only")
    X, y = np.asarray(X, np.float32), np.asarray(y, np.int32)
    TW, EW = splits
    C = jnp.asarray([float(p["C"]) for p in params], jnp.float32)
    score = np.zeros((len(params), TW.shape[0]), np.float32)
    gmax = np.zeros((len(params), TW.shape[0], STEP_CAP), np.float32)
    for s in range(TW.shape[0]):
        tr, te = np.flatnonzero(TW[s]), np.flatnonzero(EW[s])
        m = -(-len(tr) // ROW_PAD) * ROW_PAD
        q = -(-len(te) // ROW_PAD) * ROW_PAD
        acc, curve = _split(
            jnp.asarray(_padded(X[tr], m)), jnp.asarray(_padded(y[tr], m)),
            jnp.asarray(_padded(tr.astype(np.int32), m)),
            jnp.asarray(_padded(np.ones(len(tr), np.float32), m)),
            jnp.asarray(_padded(X[te], q)), jnp.asarray(_padded(y[te], q)),
            jnp.asarray(_padded(np.ones(len(te), np.float32), q)), C,
            c=max(int(n_classes), 2), precision=precision, fault=fault)
        score[:, s] = np.asarray(acc)
        gmax[:, s] = np.asarray(curve)
    return {"score": score, "gmax": gmax}

"""Plain reference of the LogisticRegression family's search semantics.

What a trial is, from the estimator's published description and this
system's documented solver choice for large n (accelerated full-batch
gradient descent on sklearn's objective ``0.5*||W||^2 + C*sum_i xent_i``,
intercept unpenalised, step 1/L with L = 0.5*C*lam_max(A' diag(w) A) + 1
from a 30-step power iteration, momentum t/(t+3), stop when max|G| < tol or
t reaches max_iter), fitted on each split's training rows and scored by
accuracy on its held-out rows. Straightforward ``jax.numpy`` in float32 at
``highest`` matmul precision, in row blocks so it fits beside nothing else;
it imports nothing of the program and takes nothing the program has made.

``precision`` puts every matmul operand on a coarser grid first: the design
matrix, the power iteration's vectors, the extrapolated weights and the
residual. ``int8`` is a symmetric 8-bit grid with one scale a tensor; any
other name is a float grid of ``GRIDS`` (``float8_e4m3fn`` scaled to the tensor's
largest entry, ``bfloat16`` as it is). The power iteration belongs to it,
as it does in the program (the backend's default of one bfloat16 pass).

``fault`` breaks the fit in a known way (``half_batch``: every second row
left out of the gradient, the step taken as if nothing were missing).
"""

from __future__ import annotations

import functools

import numpy as np

# A float grid: significant bits, least normal exponent, and the largest
# value, to which a tensor's largest entry is scaled (None: not scaled).
GRIDS = {"bfloat16": (8, -100, None), "float8_e4m3fn": (4, -6, 448.0)}
FAULTS = ("half_batch",)

POWER_STEPS = 30
STEP_CAP = 400


def _q(x, precision):
    """A matmul operand on the precision's grid, back in float32. Rounded
    by arithmetic on float32 values and through no narrow type, so that
    every backend rounds alike (PERF.md, PR 25)."""
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


@functools.partial(__import__("jax").jit,
                   static_argnames=("c", "steps", "block", "precision", "fault"))
def _fit_and_score(X, y, TW, EW, C, max_iter, tol, split_of, *, c, steps, block, precision,
                   fault):
    import jax
    import jax.numpy as jnp

    n, d = X.shape
    S = TW.shape[0]
    L = C.shape[0]
    dp = d + 1
    nb = -(-n // block)
    pad = nb * block - n
    A = jnp.concatenate([X.astype(jnp.float32), jnp.ones((n, 1), jnp.float32)], axis=1)
    A = _q(jnp.pad(A, ((0, pad), (0, 0))), precision).reshape(nb, block, dp)
    yb = jnp.pad(y.astype(jnp.int32), (0, pad)).reshape(nb, block)
    TWb = jnp.pad(TW.astype(jnp.float32), ((0, 0), (0, pad))).T.reshape(nb, block, S)
    EWb = jnp.pad(EW.astype(jnp.float32), ((0, 0), (0, pad))).T.reshape(nb, block, S)
    GWb = TWb * (jnp.arange(block) % 2 == 0)[None, :, None] if fault == "half_batch" else TWb
    pen = jnp.ones((dp, 1, 1), jnp.float32).at[-1].set(0.0)

    with jax.default_matmul_precision("highest"):
        def gram_apply(v):  # v [S, dp] -> A' diag(w_s) A v_s for each split
            vq = _q(v, precision)

            def blk(u, xs):
                a, w = xs
                t = (a @ vq.T) * w  # [block, S]
                return u + _q(t, precision).T @ a, None
            u, _ = jax.lax.scan(blk, jnp.zeros((S, dp), jnp.float32), (A, TWb))
            return u

        def power(v, _):
            u = gram_apply(v)
            return u / jnp.maximum(jnp.linalg.norm(u, axis=1, keepdims=True), 1e-12), None

        v, _ = jax.lax.scan(power, jnp.ones((S, dp), jnp.float32), None, length=POWER_STEPS)
        lam_max = jnp.sum(v * gram_apply(v), axis=1)  # [S]
        step = 1.0 / (0.5 * C * lam_max[split_of] + 1.0 + 1e-6)  # [L]

        def grad(V):  # V [dp, L, c]
            Vq = _q(V, precision).reshape(dp, L * c)

            def blk(G, xs):
                a, yy, w = xs
                P = jax.nn.softmax((a @ Vq).reshape(block, L, c), axis=-1)
                R = (P - jax.nn.one_hot(yy, c, dtype=jnp.float32)[:, None, :]) * w[:, split_of, None]
                return G + (a.T @ _q(R, precision).reshape(block, L * c)).reshape(dp, L, c), None

            G, _ = jax.lax.scan(blk, jnp.zeros((dp, L, c), jnp.float32), (A, yb, GWb))
            return C[None, :, None] * G + pen * V

        def body(carry, t):
            W, Wp, done = carry
            V = W + (t / (t + 3.0)) * (W - Wp)
            G = grad(V)
            gmax = jnp.max(jnp.abs(G), axis=(0, 2))
            active = jnp.logical_and(t < max_iter, jnp.logical_not(done))[None, :, None]
            W_new = jnp.where(active, V - step[None, :, None] * G, W)
            Wp_new = jnp.where(active, W, Wp)
            return (W_new, Wp_new, jnp.logical_or(done, gmax < tol)), gmax

        W0 = jnp.zeros((dp, L, c), jnp.float32)
        (W, _, _), gmax_trace = jax.lax.scan(body, (W0, W0, jnp.zeros((L,), bool)),
                                             jnp.arange(steps, dtype=jnp.float32))
        Wq = _q(W, precision).reshape(dp, L * c)

        def ev(acc, xs):
            a, yy, w = xs
            pred = jnp.argmax((a @ Wq).reshape(block, L, c), axis=-1)
            hit = (pred == yy[:, None]).astype(jnp.float32)
            return acc + jnp.sum(hit * w[:, split_of], axis=0), None

        acc, _ = jax.lax.scan(ev, jnp.zeros((L,), jnp.float32), (A, yb, EWb))
    den = jnp.maximum(jnp.sum(EW.astype(jnp.float32), axis=1), 1e-12)
    return acc / den[split_of], gmax_trace.T  # [L], [L, steps]


def reference(X, y, n_classes, params, splits, *, precision="f32", fault=None, block=65536):
    """What a search returns for the given trials on the given splits:
    ``score`` [len(params), n_splits] and ``gmax`` [len(params), n_splits,
    steps], the largest gradient entry at every solver step (the learning
    curve the system hands back with each trial).

    ``params``: one dict per trial with the estimator's full parameters
    (``C``, ``tol``, ``max_iter``). ``splits``: (train [S, n], eval [S, n])
    0/1 masks."""
    import jax.numpy as jnp

    TW, EW = splits
    S = TW.shape[0]
    T = len(params)
    rep = lambda k: np.repeat(np.asarray([float(p[k]) for p in params], np.float32), S)
    steps = max(1, min(STEP_CAP, max(int(p["max_iter"]) for p in params)))
    block = min(int(block), -(-X.shape[0] // 8) * 8)
    score, gmax = _fit_and_score(
        jnp.asarray(X), jnp.asarray(y), jnp.asarray(TW, jnp.uint8), jnp.asarray(EW, jnp.uint8),
        jnp.asarray(rep("C")), jnp.asarray(rep("max_iter")), jnp.asarray(rep("tol")),
        jnp.asarray(np.tile(np.arange(S, dtype=np.int32), T)),
        c=max(int(n_classes), 2), steps=steps, block=block, precision=precision, fault=fault)
    return {"score": np.asarray(score, np.float32).reshape(T, S),
            "gmax": np.asarray(gmax, np.float32).reshape(T, S, steps)}

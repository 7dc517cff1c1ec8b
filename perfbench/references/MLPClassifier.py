"""Plain reference of the MLPClassifier family's search semantics.

sklearn's MLPClassifier as this system documents it: ReLU hidden layers,
softmax cross-entropy, Glorot-uniform weights and zero biases drawn from
``random_state`` (``PRNGKey(random_state)``, one split per layer), one row
permutation per epoch from the same key, minibatches of ``batch_size`` rows
in permutation order (the ragged tail dropped), loss = mean weighted batch
loss + alpha/2*||W||^2 / batch weight, Adam (0.9, 0.999, 1e-8) with float32
moments, ``max_iter`` epochs with no early stop; each split trains on its
training rows (weight 0 elsewhere) and is scored by accuracy on its held-out
rows. Forward and backward are written out by hand in float32 at ``highest``
matmul precision. It imports nothing of the program and takes nothing the
program has made.

``precision`` puts every matmul operand on a coarser grid first: ``int8`` a
symmetric 8-bit grid with one scale a tensor, any other name a float grid
of ``GRIDS`` (``float8_e4m3fn`` scaled to the tensor's largest entry, so that the small
residuals of a 200-row batch do not underflow it; ``bfloat16`` as it is).
``state_precision`` puts the parameters and both Adam moments on a grid
after every step (``bfloat16``: the step below the float32 state the
configuration states). ``fault`` breaks the fit in a known way:
``half_batch`` leaves every second row of each minibatch out and takes the
mean over the rest, ``no_bias_correction`` drops Adam's bias correction.
"""

from __future__ import annotations

import functools

import numpy as np

# A float grid: significant bits, least normal exponent, and the largest
# value, to which a tensor's largest entry is scaled (None: not scaled).
GRIDS = {"bfloat16": (8, -100, None), "float8_e4m3fn": (4, -6, 448.0)}
FAULTS = ("half_batch", "no_bias_correction")

B1, B2, EPS = 0.9, 0.999, 1e-8


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
                   static_argnames=("dims", "epochs", "bs", "seed", "block", "precision",
                                    "state_precision", "fault"))
def _fit_and_score(X, y, TW, EW, alpha, lr, split_of, *, dims, epochs, bs, seed, block, precision,
                   state_precision, fault):
    import jax
    import jax.numpy as jnp

    n, d = X.shape
    L = alpha.shape[0]
    c = dims[-1]
    n_layers = len(dims) - 1
    n_batches = max(1, n // bs)
    q = functools.partial(_q, precision=precision)
    qs = functools.partial(_q, precision=state_precision)
    X = X.astype(jnp.float32)
    Y = jax.nn.one_hot(y, c, dtype=jnp.float32)
    TWl = TW.astype(jnp.float32)[split_of]  # [L, n]

    key = jax.random.PRNGKey(seed)
    key, init_key = jax.random.split(key)
    Ws, Bs = [], []
    for i in range(n_layers):
        init_key, sub = jax.random.split(init_key)
        bound = jnp.sqrt(6.0 / (dims[i] + dims[i + 1]))
        W = jax.random.uniform(sub, (dims[i], dims[i + 1]), jnp.float32, -bound, bound)
        Ws.append(jnp.tile(W[None], (L, 1, 1)))
        Bs.append(jnp.zeros((L, dims[i + 1]), jnp.float32))
    perms = jax.vmap(lambda k: jax.random.permutation(k, n)[: n_batches * bs])(
        jax.random.split(key, epochs))
    batches = perms.reshape(epochs * n_batches, bs)
    zeros = lambda tree: [jnp.zeros_like(a) for a in tree]

    def forward(Ws, Bs, xb):  # xb [bs, d] shared by every lane
        zs, acts = [], [xb]
        h = jnp.einsum("bd,ldh->lbh", q(xb), q(Ws[0])) + q(Bs[0])[:, None, :]
        for li in range(n_layers):
            if li:
                h = jnp.einsum("lbh,lhk->lbk", q(acts[-1]), q(Ws[li])) + q(Bs[li])[:, None, :]
            zs.append(h)
            acts.append(jnp.maximum(h, 0.0) if li < n_layers - 1 else h)
        return zs, acts

    with jax.default_matmul_precision("highest"):
        def step(carry, idx):
            Ws, Bs, mW, mB, vW, vB, t = carry
            t = t + 1.0
            xb, yb, wb = X[idx], Y[idx], TWl[:, idx]  # [bs,d] [bs,c] [L,bs]
            if fault == "half_batch":
                wb = wb * (jnp.arange(bs) % 2 == 0)
            bw = jnp.maximum(jnp.sum(wb, axis=1), 1e-12)  # [L]
            zs, acts = forward(Ws, Bs, xb)
            dz = (jax.nn.softmax(acts[-1], axis=-1) - yb[None]) * (wb / bw[:, None])[:, :, None]
            bc1, bc2 = (1.0, 1.0) if fault == "no_bias_correction" else (1.0 - B1 ** t, 1.0 - B2 ** t)
            Ws, Bs, mW, mB, vW, vB = map(list, (Ws, Bs, mW, mB, vW, vB))
            for li in range(n_layers - 1, -1, -1):
                a_in = acts[li]
                if li == 0:
                    gW = jnp.einsum("bd,lbk->ldk", q(a_in), q(dz))
                else:
                    gW = jnp.einsum("lbh,lbk->lhk", q(a_in), q(dz))
                gW = gW + (alpha / bw)[:, None, None] * Ws[li]
                gB = jnp.sum(q(dz), axis=1)
                if li > 0:
                    da = jnp.einsum("lbk,lhk->lbh", q(dz), q(Ws[li]))
                    dz = da * (zs[li - 1] > 0.0)
                mW[li] = qs(B1 * mW[li] + (1 - B1) * gW)
                vW[li] = qs(B2 * vW[li] + (1 - B2) * gW * gW)
                Ws[li] = qs(Ws[li] - lr[:, None, None] * (mW[li] / bc1) / (jnp.sqrt(vW[li] / bc2) + EPS))
                mB[li] = qs(B1 * mB[li] + (1 - B1) * gB)
                vB[li] = qs(B2 * vB[li] + (1 - B2) * gB * gB)
                Bs[li] = qs(Bs[li] - lr[:, None] * (mB[li] / bc1) / (jnp.sqrt(vB[li] / bc2) + EPS))
            return (Ws, Bs, mW, mB, vW, vB, t), None

        carry0 = (Ws, Bs, zeros(Ws), zeros(Bs), zeros(Ws), zeros(Bs), jnp.asarray(0.0))
        (Ws, Bs, *_), _ = jax.lax.scan(step, carry0, batches)

        nb = -(-n // block)
        pad = nb * block - n
        Xe = jnp.pad(X, ((0, pad), (0, 0))).reshape(nb, block, d)
        ye = jnp.pad(y.astype(jnp.int32), (0, pad)).reshape(nb, block)
        EWl = jnp.pad(EW.astype(jnp.float32), ((0, 0), (0, pad)))[split_of]  # [L, n_pad]
        EWl = EWl.reshape(L, nb, block).transpose(1, 0, 2)

        def ev(acc, xs):
            xb, yy, w = xs
            _, acts = forward(Ws, Bs, xb)
            hit = (jnp.argmax(acts[-1], axis=-1) == yy[None, :]).astype(jnp.float32)
            return acc + jnp.sum(hit * w, axis=1), None

        acc, _ = jax.lax.scan(ev, jnp.zeros((L,), jnp.float32), (Xe, ye, EWl))
    den = jnp.maximum(jnp.sum(EW.astype(jnp.float32), axis=1), 1e-12)
    return acc / den[split_of]


def reference(X, y, n_classes, params, splits, *, precision="f32", state_precision="f32",
              fault=None, block=1024):
    """``score`` [len(params), n_splits] of the given trials on the given
    splits (this family hands back no learning curve on the timed path)."""
    import jax.numpy as jnp

    TW, EW = splits
    S = TW.shape[0]
    T = len(params)
    p0 = params[0]
    hls = p0.get("hidden_layer_sizes", (100,))
    hls = (int(hls),) if isinstance(hls, (int, float)) else tuple(int(h) for h in hls)
    n, d = X.shape
    bs = p0.get("batch_size", "auto")
    bs = min(200, n) if bs == "auto" else min(int(bs), n)
    rep = lambda k: np.repeat(np.asarray([float(p[k]) for p in params], np.float32), S)
    out = _fit_and_score(
        jnp.asarray(X), jnp.asarray(y), jnp.asarray(TW, jnp.uint8), jnp.asarray(EW, jnp.uint8),
        jnp.asarray(rep("alpha")), jnp.asarray(rep("learning_rate_init")),
        jnp.asarray(np.tile(np.arange(S, dtype=np.int32), T)),
        dims=(d, *hls, max(int(n_classes), 2)), epochs=int(p0["max_iter"]), bs=bs,
        seed=int(p0.get("random_state") or 0), block=int(block), precision=precision,
        state_precision=state_precision, fault=fault)
    return {"score": np.asarray(out, np.float32).reshape(T, S)}

"""MLP kernels (classifier + regressor), sklearn-MLP semantics on TPU.

Capability target: BASELINE.md config 5 (MLPClassifier RandomizedSearchCV on
MNIST — "stresses per-chip jit"). Mirrors sklearn's MLPClassifier/Regressor
defaults: relu hidden layers, minibatch Adam (batch 200), L2 penalty
``alpha``, log-loss / squared-loss. Architecture (``hidden_layer_sizes``),
activation, batch size, and epoch count are static (shape/trip-count);
``alpha`` and ``learning_rate_init`` are traced so learning-rate/penalty
sweeps share one compile.

Minibatching under the split-mask regime: batches are fixed random
permutation slices of the full (static-size) dataset with per-sample weights
multiplying the loss — rows outside the split contribute zero gradient, so
one compiled update serves all K+1 splits of every trial. The whole fit is
one ``lax.scan`` over epochs x batches of a jitted Adam step — exactly the
training-loop shape XLA pipelines best on TPU.
"""

from __future__ import annotations

import os
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..utils import backend as _backend
from .base import ModelKernel

_EPOCH_CAP = 100


def _v_dtype_mode() -> str:
    """Storage dtype of the generic path's SECOND Adam moment:
    ``bf16`` (default — stochastic rounding, halves the dominant Adam-state
    HBM term) or ``f32`` (the pre-PR-6 layout, for A/B and rollback)."""
    mode = os.environ.get("CS230_MLP_V_DTYPE", "bf16").lower()
    return mode if mode in ("bf16", "f32") else "bf16"


def _sr_bf16(x32, key):
    """Stochastically round f32 -> bf16: add uniform bits below the bf16
    mantissa boundary, then truncate. Unbiased (E[q(x)] == x), so EMA
    updates smaller than bf16's round-to-nearest deadband accumulate in
    expectation instead of freezing — the property that makes a bf16
    second Adam moment safe (beta2=0.999 updates are ~0.1% of v, under
    the ~0.4% deadband). Inputs are non-negative finite EMAs; the add may
    carry into the exponent, which is exactly round-up."""
    u = jax.lax.bitcast_convert_type(x32.astype(jnp.float32), jnp.uint32)
    r = jax.random.bits(key, x32.shape, jnp.uint32) & jnp.uint32(0xFFFF)
    u = (u + r) & jnp.uint32(0xFFFF0000)
    return jax.lax.bitcast_convert_type(u, jnp.float32).astype(jnp.bfloat16)


def _act(name: str):
    return {
        "relu": jax.nn.relu,
        "tanh": jnp.tanh,
        "logistic": jax.nn.sigmoid,
        "identity": lambda x: x,
    }[name]


class _MLPBase(ModelKernel):
    hyper_defaults = {"alpha": 1e-4, "learning_rate_init": 1e-3}
    static_defaults = {
        "hidden_layer_sizes": (100,),
        "activation": "relu",
        "batch_size": "auto",
        "max_iter": 200,
        "random_state": 0,
        "solver": "adam",
        "beta_1": 0.9,
        "beta_2": 0.999,
        "epsilon": 1e-8,
        "shuffle": True,
        "early_stopping": False,
        "tol": 1e-4,
        "learning_rate": "constant",
        "momentum": 0.9,
        "n_iter_no_change": 10,
        "nesterovs_momentum": True,
        "power_t": 0.5,
        "validation_fraction": 0.1,
        "max_fun": 15000,
    }
    ignored_params = ModelKernel.ignored_params - {"random_state", "solver", "max_fun"}

    def trace_salt(self):
        """Fused-path env knobs read at trace time (lane packing) — they
        change the compiled program without landing in ``static``. The
        salt carries the EFFECTIVE boolean, not the raw string: only the
        exact value "1" changes pick_k, so "0"/"yes"/unset must share one
        cache key (a raw-string salt would force spurious retraces).
        CS230_CURVES joins: with capture on the Adam/SGD scans carry
        trace buffers and ``fit`` routes through value_and_grad, so the
        valve (and CS230_CURVE_POINTS) must re-key executables."""
        from ..obs.curves import curves_salt

        return (
            "1" if os.environ.get("CS230_MLP_K16") == "1" else "",
            _v_dtype_mode(),
            curves_salt(),
        )

    def resolve_static(self, static: Dict[str, Any], n: int, d: int, n_classes: int):
        hls = static.get("hidden_layer_sizes", (100,))
        if isinstance(hls, (int, float)):
            hls = (int(hls),)
        hls = tuple(int(h) for h in hls)
        bs = static.get("batch_size", "auto")
        bs = min(200, n) if bs == "auto" else min(int(bs), n)
        epochs = min(int(static.get("max_iter", 200)), _EPOCH_CAP)
        if static.get("activation", "relu") not in ("relu", "tanh", "logistic", "identity"):
            raise ValueError(f"MLP: unsupported activation {static.get('activation')!r}")
        if static.get("solver", "adam") not in ("adam", "sgd"):
            # lbfgs would silently train with the wrong optimizer — the
            # reference's sklearn honors it, so fail loudly instead
            raise ValueError(
                f"MLP: unsupported solver {static.get('solver')!r} "
                "(supported: adam, sgd)"
            )
        if static.get("learning_rate", "constant") not in (
            "constant", "invscaling", "adaptive"
        ):
            raise ValueError(
                f"MLP: unsupported learning_rate {static.get('learning_rate')!r}"
            )
        return {
            **static,
            "_hls": hls,
            "_bs": bs,
            "_epochs": epochs,
            "_seed": int(static.get("random_state") or 0),
        }

    def _dims(self, d: int, static: Dict[str, Any]) -> Tuple[int, ...]:
        out = self._out_dim(static)
        return (d, *static["_hls"], out)

    def macs_estimate(self, n, d, static):
        """fwd+bwd over all layer matmuls x epochs (3x fwd MAC rule)."""
        dims = self._dims(d, static)
        layer_macs = sum(a * b for a, b in zip(dims[:-1], dims[1:]))
        bs = int(static["_bs"])
        n_batches = max(1, n // bs)
        return 3.0 * static["_epochs"] * n_batches * bs * layer_macs

    def memory_estimate_mb(self, n, d, static):
        """Marginal per-(trial, split) working set: params + Adam moments +
        per-step batch activations — NOT the [n, d] dataset (shared across
        lanes, counted once by the engine). The base-class default charged
        each lane ~3x the dataset (~0.5 GB at MNIST scale), capping
        dispatches at ~2 trials and costing ~50 RPC round trips per job
        plus tiny-lane matmuls; the true footprint is a few MB, so the
        whole search fits one dispatch with hundreds of vmapped lanes."""
        dims = self._dims(d, static)
        wparams = sum(a * b + b for a, b in zip(dims[:-1], dims[1:]))
        bs = int(static.get("_bs", 200))
        # params (f32) + m + v (both bf16 by default; CS230_MLP_V_DTYPE=f32
        # widens v back to the pre-PR-6 layout)
        v_bytes = 2 if _v_dtype_mode() == "bf16" else 4
        state_mb = wparams * (4 + 2 + v_bytes) / 1e6
        act_mb = 3.0 * bs * sum(dims) * 4 / 1e6  # fwd+bwd live activations
        return max(1.0, state_mb + act_mb + 1.0)

    def _init(self, key, dims):
        """sklearn's Glorot-uniform init."""
        params = []
        for i in range(len(dims) - 1):
            key, sub = jax.random.split(key)
            fan_in, fan_out = dims[i], dims[i + 1]
            # sklearn uses factor 6 for relu/tanh/identity ("glorot")
            bound = jnp.sqrt(6.0 / (fan_in + fan_out))
            W = jax.random.uniform(sub, (fan_in, fan_out), jnp.float32, -bound, bound)
            params.append({"W": W, "b": jnp.zeros((fan_out,), jnp.float32)})
        return params

    def _forward(self, params, X, static, mm=None):
        act = _act(static.get("activation", "relu"))
        mm = mm or jnp.matmul
        h = X
        for layer in params[:-1]:
            h = act(mm(h, layer["W"]) + layer["b"])
        return mm(h, params[-1]["W"]) + params[-1]["b"]

    def fit(self, X, y, w, hyper: Dict[str, Any], static: Dict[str, Any]):
        return self._fit(X, y, w, hyper, static, trace=False)[0]

    def fit_curve(self, X, y, w, hyper: Dict[str, Any], static: Dict[str, Any]):
        """Capture hook (docs/OBSERVABILITY.md "Trial telemetry plane"):
        same fit, plus bounded in-scan traces — per-step loss and
        grad-norm on the Adam path (``jax.value_and_grad`` replaces
        ``jax.grad``; the loss's forward pass is shared with the gradient
        so the extra cost is the two trace writes), per-epoch loss on the
        SGD path (already computed for the adaptive schedule). Returns
        ``(params, curve)``."""
        return self._fit(X, y, w, hyper, static, trace=True)

    def _fit(self, X, y, w, hyper: Dict[str, Any], static: Dict[str, Any],
             trace: bool):
        X = X.astype(jnp.float32)
        w = w.astype(jnp.float32)
        n, d = X.shape
        bs = static["_bs"]
        epochs = static["_epochs"]
        n_batches = max(1, n // bs)
        alpha = jnp.asarray(hyper["alpha"], jnp.float32)
        lr = jnp.asarray(hyper["learning_rate_init"], jnp.float32)
        b1 = float(static.get("beta_1", 0.9))
        b2 = float(static.get("beta_2", 0.999))
        eps = float(static.get("epsilon", 1e-8))

        dims = self._dims(d, static)
        key = jax.random.PRNGKey(static["_seed"])
        key, init_key = jax.random.split(key)
        params = self._init(init_key, dims)
        target = self._target(y, static)

        # bf16 matmuls (f32 accumulation) for the fwd/bwd passes — the MXU's
        # native mode; and bf16 moments. The fit is Adam-STATE-bandwidth
        # bound, not compute bound (params+m+v stream from HBM every step
        # while each step's matmul touches only batch_size rows), so
        # shrinking moment bytes matters more than the matmul rate.
        # The second moment needs care: beta2=0.999 makes per-step updates
        # ~0.1% of v, below bf16's ~0.4% round-to-nearest deadband — a
        # nearest-rounded bf16 v freezes at stale values and silently
        # suppresses the effective step size (m's beta1=0.9 steps are ~25x
        # the deadband, safe with nearest rounding). A bf16 v is therefore
        # stored with STOCHASTIC rounding: the quantizer is unbiased, so
        # sub-deadband updates land in expectation instead of vanishing
        # (convergence-parity vs the f32 v pinned in tests/test_mlp.py;
        # CS230_MLP_V_DTYPE=f32 restores the old state layout).
        def mm(a, b):
            return jnp.matmul(
                a.astype(jnp.bfloat16), b.astype(jnp.bfloat16),
                preferred_element_type=jnp.float32,
            )

        def loss_fn(p, xb, tb, wb):
            # sklearn scaling: mean batch loss + alpha/2 * ||W||^2 / batch size,
            # with split-mask weights zeroing out-of-split rows
            pred = self._forward(p, xb, static, mm=mm)
            batch_w = jnp.maximum(jnp.sum(wb), 1e-12)
            data_loss = jnp.sum(self._loss(pred, tb) * wb) / batch_w
            l2 = sum(jnp.sum(layer["W"] ** 2) for layer in p)
            return data_loss + 0.5 * alpha * l2 / batch_w

        grad_fn = jax.value_and_grad(loss_fn) if trace else jax.grad(loss_fn)

        total_steps = epochs * n_batches
        if trace:
            from ..obs.curves import trace_stride

            tr_stride = trace_stride(total_steps)
            tr_used = -(-total_steps // tr_stride)
            tr0 = (jnp.zeros((tr_used,), jnp.float32),
                   jnp.zeros((tr_used,), jnp.float32))
        else:
            tr_stride, tr0 = 1, None

        bf16 = jnp.bfloat16
        v_bf16 = _v_dtype_mode() == "bf16"
        m0 = jax.tree_util.tree_map(lambda a: jnp.zeros_like(a, bf16), params)
        v0 = jax.tree_util.tree_map(
            lambda a: jnp.zeros_like(a, bf16 if v_bf16 else jnp.float32), params
        )
        sr_key = jax.random.fold_in(key, 0x5A)  # stochastic-rounding stream

        def step(carry, inp):
            p, m, v, t, tr = carry
            idx = inp
            xb = X[idx]
            tb = target[idx]
            wb = w[idx]
            if trace:
                loss, g = grad_fn(p, xb, tb, wb)
                gmax = jnp.max(jnp.asarray(
                    [jnp.max(jnp.abs(leaf))
                     for leaf in jax.tree_util.tree_leaves(g)]
                ))
                ti = jnp.asarray(t, jnp.int32) // tr_stride
                tr = (tr[0].at[ti].set(loss), tr[1].at[ti].set(gmax))
            else:
                g = grad_fn(p, xb, tb, wb)
            t = t + 1.0
            # moment math in f32, storage in bf16 (carry dtype)
            m = jax.tree_util.tree_map(
                lambda a, b: (b1 * a.astype(jnp.float32) + (1 - b1) * b
                              ).astype(bf16), m, g)
            if v_bf16:
                # unbiased bf16 storage: per-step, per-leaf random bits
                # derived from the (fit-seed, step) pair keep the scan
                # carry free of PRNG state
                kt = jax.random.fold_in(sr_key, t.astype(jnp.int32))
                leaves, treedef = jax.tree_util.tree_flatten(v)
                vkeys = jax.tree_util.tree_unflatten(
                    treedef, list(jax.random.split(kt, len(leaves)))
                )
                v = jax.tree_util.tree_map(
                    lambda a, b, k: _sr_bf16(
                        b2 * a.astype(jnp.float32) + (1 - b2) * b * b, k
                    ),
                    v, g, vkeys,
                )
            else:
                v = jax.tree_util.tree_map(
                    lambda a, b: b2 * a + (1 - b2) * b * b, v, g)
            mhat = jax.tree_util.tree_map(
                lambda a: a.astype(jnp.float32) / (1 - b1**t), m)
            vhat = jax.tree_util.tree_map(
                lambda a: a.astype(jnp.float32) / (1 - b2**t), v)
            p = jax.tree_util.tree_map(
                lambda a, mh, vh: a - lr * mh / (jnp.sqrt(vh) + eps), p, mhat, vhat
            )
            return (p, m, v, t, tr), None

        # precompute shuffled batch indices for all epochs: [epochs*n_batches, bs]
        def epoch_perm(k):
            return jax.random.permutation(k, n)[: n_batches * bs].reshape(n_batches, bs)

        perm_keys = jax.random.split(key, epochs)
        batches = jax.vmap(epoch_perm)(perm_keys).reshape(-1, bs)

        if static.get("solver", "adam") == "sgd":
            return self._fit_sgd(
                X, target, w, params, batches.reshape(epochs, n_batches, bs),
                loss_fn, lr, static, n, trace=trace,
            )

        (params, _, _, _, tr), _ = jax.lax.scan(
            step, (params, m0, v0, jnp.asarray(0.0), tr0), batches
        )
        if not trace:
            return params, None
        return params, {
            "loss": tr[0],
            "gmax": tr[1],
            "stride": jnp.asarray(float(tr_stride), jnp.float32),
            "steps": jnp.asarray(float(total_steps), jnp.float32),
        }

    def _fit_sgd(self, X, target, w, params, batches, loss_fn, lr0, static, n,
                 trace=False):
        """sklearn SGDOptimizer semantics: velocity momentum (plain or
        Nesterov) with the three learning-rate schedules —
        ``constant``; ``invscaling`` lr = lr_init / (t+1)^power_t with t
        advancing by n samples per epoch (sklearn's ``t_``); ``adaptive``
        divides lr by 5 once the epoch loss fails to improve by ``tol``
        for n_iter_no_change+1 consecutive epochs (floored at 1e-6).
        Like the Adam path, tol-based EARLY STOPPING is not applied — the
        full max_iter budget runs (a documented simplification; the lr
        schedule itself is honored)."""
        momentum = float(static.get("momentum", 0.9))
        nesterov = bool(static.get("nesterovs_momentum", True))
        schedule = static.get("learning_rate", "constant")
        power_t = float(static.get("power_t", 0.5))
        tol = float(static.get("tol", 1e-4))
        no_change = int(static.get("n_iter_no_change", 10))
        tmap = jax.tree_util.tree_map

        def batch_step(carry, idx):
            p, vel, lr_t = carry
            loss, g = jax.value_and_grad(loss_fn)(p, X[idx], target[idx], w[idx])
            vel = tmap(lambda v, gg: momentum * v - lr_t * gg, vel, g)
            if nesterov:
                p = tmap(lambda a, v, gg: a + momentum * v - lr_t * gg, p, vel, g)
            else:
                p = tmap(lambda a, v: a + v, p, vel)
            return (p, vel, lr_t), loss

        epochs = int(batches.shape[0])
        if trace:
            from ..obs.curves import trace_stride

            tr_stride = trace_stride(epochs)
            tr_used = -(-epochs // tr_stride)
            tr0 = jnp.zeros((tr_used,), jnp.float32)
        else:
            tr_stride, tr0 = 1, None

        def epoch_step(carry, xs):
            p, vel, lr_t, t_samples, best, wait, tr = carry
            ebatches, e_idx = xs
            (p, vel, _), losses = jax.lax.scan(
                batch_step, (p, vel, lr_t), ebatches
            )
            epoch_loss = jnp.mean(losses)
            if trace:
                tr = tr.at[e_idx // tr_stride].set(epoch_loss)
            t_samples = t_samples + n
            if schedule == "invscaling":
                lr_t = lr0 / (t_samples + 1.0) ** power_t
            elif schedule == "adaptive":
                improved = epoch_loss < best - tol
                wait = jnp.where(improved, 0, wait + 1)
                cut = wait > no_change
                lr_t = jnp.where(cut, jnp.maximum(lr_t / 5.0, 1e-6), lr_t)
                wait = jnp.where(cut, 0, wait)
                best = jnp.minimum(best, epoch_loss)
            return (p, vel, lr_t, t_samples, best, wait, tr), None

        vel0 = tmap(jnp.zeros_like, params)
        (params, _, _, _, _, _, tr), _ = jax.lax.scan(
            epoch_step,
            (params, vel0, lr0 * jnp.asarray(1.0, jnp.float32),
             jnp.asarray(0.0, jnp.float32),
             jnp.asarray(jnp.inf, jnp.float32), jnp.asarray(0, jnp.int32),
             tr0),
            (batches, jnp.arange(epochs, dtype=jnp.int32)),
        )
        if not trace:
            return params, None
        return params, {
            "loss": tr,
            "stride": jnp.asarray(float(tr_stride), jnp.float32),
            "steps": jnp.asarray(float(epochs), jnp.float32),
        }


    # ---- fused Pallas batched path (ops/pallas_mlp.py) -------------------
    #
    # On TPU, adam/constant-lr buckets at real-data scale bypass the generic
    # vmap engine: the whole epoch's minibatch loop runs as ONE Pallas
    # kernel with (params, m, v) resident in VMEM and k (trial x split)
    # lanes packed per grid step. The generic path streams ~20 B of Adam
    # state per param per STEP through HBM — the measured 7.3%-MFU floor at
    # MNIST scale (VERDICT r3 #4); the fused kernel pays that per EPOCH.

    batched_trial_multiple = 1
    batched_chunk_cap = 64

    def batched_trial_block(self, trials_per_device: int, n_splits: int) -> int:
        """Lanes pack per (trial, split), so any trial count is a block."""
        return self.batched_trial_multiple

    def batched_applicable(self, static: Dict[str, Any], n: int, d: int) -> bool:
        solver = static.get("solver", "adam")
        if solver not in ("adam", "sgd"):
            return False
        # learning_rate schedules are sgd-only in sklearn (adam ignores
        # them); all three ride the fused path — constant/invscaling as a
        # per-epoch lr column, adaptive via the kernel's epoch-loss slab
        if not static.get("shuffle", True) or static.get("early_stopping"):
            return False
        if len(static["_hls"]) > 3:
            return False
        # non-8-multiple batch sizes pad each batch block with zero-weight
        # slots (sublane rule); no eligibility cut needed
        if _backend.pallas_interpret():
            return True
        return _backend.auto_pallas() and n >= 4096

    def build_batched_fn(self, static, n, d, n_classes, n_splits, chunk):
        """fn(X, y, TW, EW, hyper) -> {"score": [chunk, n_splits]} (+"mse"
        for regressors) — fit via the fused Pallas epoch kernel, eval in
        XLA. Same contract as the engine's vmapped executable."""
        if not self.batched_applicable(static, n, d):
            return None

        from ..ops.pallas_mlp import build_epoch_fn, pick_k

        interpret = _backend.pallas_interpret()
        classification = self.task == "classification"
        c = self._out_dim(static)
        dims = self._dims(d, static)
        act = static.get("activation", "relu")
        bs = int(static["_bs"])
        epochs = int(static["_epochs"])
        n_batches = max(1, n // bs)
        R = n_batches * bs
        # TPU sublane rule: batch blocks pad to a multiple of 8 rows; pad
        # slots replay row 0 with zero weight (no gradient contribution)
        bs_pad = -(-bs // 8) * 8
        S = int(n_splits)
        L0 = chunk * S
        solver = static.get("solver", "adam")
        schedule = static.get("learning_rate", "constant")
        adaptive = solver == "sgd" and schedule == "adaptive"
        k = pick_k(dims, bs_pad, solver=solver)
        Lk = -(-L0 // k) * k
        seed = int(static["_seed"])
        b1 = float(static.get("beta_1", 0.9))
        b2 = float(static.get("beta_2", 0.999))
        eps = float(static.get("epsilon", 1e-8))
        momentum = float(static.get("momentum", 0.9))
        nesterov = bool(static.get("nesterovs_momentum", True))
        power_t = float(static.get("power_t", 0.5))
        tol = float(static.get("tol", 1e-4))
        no_change = int(static.get("n_iter_no_change", 10))
        # the kernel hardcodes sklearn's Adam constants; non-default values
        # must take the generic path, which honors them
        if solver == "adam" and (b1, b2, eps) != (0.9, 0.999, 1e-8):
            return None

        # lane = trial * S + split; padded lanes replay lane 0 (discarded)
        ls_np = np.concatenate(
            [np.arange(L0, dtype=np.int32) % S,
             np.zeros(Lk - L0, dtype=np.int32)]
        )
        lane_split = jnp.asarray(ls_np)
        epoch_fn = build_epoch_fn(
            dims, act, bs_pad, n_batches, Lk, k, classification,
            solver=solver, momentum=momentum, nesterov=nesterov,
            track_loss=adaptive, interpret=interpret,
        )

        def _lane_vec(h):  # [chunk] hyper -> [Lk, 1] per-lane column
            v = jnp.repeat(h.astype(jnp.float32), S)
            v = jnp.concatenate([v, jnp.broadcast_to(v[:1], (Lk - L0,))])
            return v[:, None]

        rc = 256  # eval row chunk: [Lk, rc, max_h] activations stay <200 MB
        n_pad = -(-n // rc) * rc
        # matmul operand dtype: bf16 on the MXU; the CPU interpreter (test
        # coverage) lacks the mixed bf16->f32 dot
        mdt = jnp.float32 if interpret else jnp.bfloat16

        def fn(X, y, TW, EW, hyper):
            with jax.named_scope("tpuml.fit"):
                Xb = X.astype(mdt)
                if classification:
                    Y = jax.nn.one_hot(y, c, dtype=jnp.bfloat16)
                else:
                    Y = y.astype(jnp.float32)[:, None]
                TWf = TW.astype(jnp.float32)
                lr = _lane_vec(hyper["learning_rate_init"])
                alpha = _lane_vec(hyper["alpha"])

                key = jax.random.PRNGKey(seed)
                key, init_key = jax.random.split(key)
                params = self._init(init_key, dims)
                per_layer = 6 if solver == "adam" else 4
                n_moments = 2 if solver == "adam" else 1
                state = []
                for layer in params:
                    # biases ride as [Lk, 8, out] row-identical slabs (see
                    # ops/pallas_mlp.py kernel docstring for the layout rule)
                    for leaf in (layer["W"], jnp.tile(layer["b"][None, :], (8, 1))):
                        state.append(jnp.tile(leaf[None], (Lk,) + (1,) * leaf.ndim))
                        for _ in range(n_moments):
                            state.append(jnp.zeros((Lk,) + leaf.shape, jnp.float32))
                # reorder to the kernel's per-layer layout: (pW, pB, mW, mB,
                # vW, vB) for adam, (pW, pB, velW, velB) for sgd
                half = 1 + n_moments
                flat = []
                for li in range(len(params)):
                    chunk6 = state[2 * half * li : 2 * half * (li + 1)]
                    Wslabs, Bslabs = chunk6[:half], chunk6[half:]
                    flat.extend([Wslabs[0], Bslabs[0]])
                    for j in range(1, half):
                        flat.extend([Wslabs[j], Bslabs[j]])
                state = flat
                if adaptive:
                    state.append(jnp.zeros((Lk, 8, 128), jnp.float32))

                ekeys = jax.random.split(key, epochs)
                t0s = jnp.arange(epochs, dtype=jnp.int32) * n_batches

                if bs_pad != bs:
                    pad_mask = jnp.asarray(
                        np.concatenate(
                            [np.ones((n_batches, bs), np.float32),
                             np.zeros((n_batches, bs_pad - bs), np.float32)], 1
                        ).reshape(-1)
                    )
                else:
                    pad_mask = None

                def _epoch_rows(perm):
                    if bs_pad == bs:
                        return perm
                    idx = perm.reshape(n_batches, bs)
                    return jnp.concatenate(
                        [idx, jnp.zeros((n_batches, bs_pad - bs), idx.dtype)], 1
                    ).reshape(-1)

                def _run_epoch(st, key_e, t0, lr_col):
                    perm = jax.random.permutation(key_e, n)[:R]
                    idx = _epoch_rows(perm)
                    Wl = TWf[:, idx].T[:, lane_split]  # [Rp, Lk], lane-minor
                    if pad_mask is not None:
                        Wl = Wl * pad_mask[:, None]
                    return epoch_fn(
                        Xb[idx], Y[idx], Wl, lr_col, alpha,
                        t0.reshape(1, 1), st,
                    ), Wl

                if not adaptive:
                    def body(st, xs):
                        key_e, t0 = xs
                        if solver == "sgd" and schedule == "invscaling":
                            # sklearn t_ advances by n samples per epoch
                            e = (t0 // n_batches).astype(jnp.float32)
                            lr_col = lr / (e * n + 1.0) ** power_t
                        else:
                            lr_col = lr
                        st, _ = _run_epoch(st, key_e, t0, lr_col)
                        return st, None

                    state, _ = jax.lax.scan(body, state, (ekeys, t0s))
                else:
                    def body(carry, xs):
                        st, lr_col, best, wait = carry
                        key_e, t0 = xs
                        st = st[:-1] + [jnp.zeros_like(st[-1])]  # reset loss acc
                        st, Wl = _run_epoch(st, key_e, t0, lr_col)
                        data_loss = st[-1][:, 0, 0] / n_batches  # [Lk]
                        # L2 term added host-side from end-of-epoch weights
                        # (sklearn accumulates it per batch; the improvement
                        # signal only needs epoch resolution)
                        l2 = jnp.zeros((Lk,), jnp.float32)
                        for li in range(len(params)):
                            Wli = st[per_layer * li]
                            l2 = l2 + jnp.sum(
                                Wli.astype(jnp.float32) ** 2,
                                axis=tuple(range(1, Wli.ndim)),
                            )
                        bw_mean = jnp.maximum(jnp.sum(Wl, axis=0) / n_batches, 1e-12)
                        epoch_loss = data_loss + 0.5 * alpha[:, 0] * l2 / bw_mean
                        improved = epoch_loss < best - tol
                        wait = jnp.where(improved, 0, wait + 1)
                        cut = wait > no_change
                        lr_col = jnp.where(
                            cut[:, None], jnp.maximum(lr_col / 5.0, 1e-6), lr_col
                        )
                        wait = jnp.where(cut, 0, wait)
                        best = jnp.minimum(best, epoch_loss)
                        return (st, lr_col, best, wait), None

                    carry0 = (
                        state, lr,  # [Lk, 1] per-lane column (mutated by cuts)
                        jnp.full((Lk,), jnp.inf, jnp.float32),
                        jnp.zeros((Lk,), jnp.int32),
                    )
                    (state, _, _, _), _ = jax.lax.scan(body, carry0, (ekeys, t0s))

            with jax.named_scope("tpuml.eval"):
                # ---- eval (XLA): weighted score per lane over row chunks ----
                pWs = [state[per_layer * li] for li in range(len(params))]
                pBs = [state[per_layer * li + 1][:, 0:1, :] for li in range(len(params))]
                act_f = _act(act)
                Xe = jnp.pad(Xb, ((0, n_pad - n), (0, 0)))
                EWp = jnp.pad(EW.astype(jnp.float32), ((0, 0), (0, n_pad - n)))
                if classification:
                    ye = jnp.pad(y.astype(jnp.int32), (0, n_pad - n))
                else:
                    ye = jnp.pad(y.astype(jnp.float32), (0, n_pad - n))

                def forward_chunk(start):
                    h = jax.lax.dynamic_slice(Xe, (start, 0), (rc, d))
                    out = jnp.einsum(
                        "rd,ldh->lrh", h, pWs[0].astype(mdt),
                        preferred_element_type=jnp.float32,
                    ) + pBs[0]
                    for li in range(1, len(params)):
                        out = jnp.einsum(
                            "lrh,lhk->lrk",
                            act_f(out).astype(mdt),
                            pWs[li].astype(mdt),
                            preferred_element_type=jnp.float32,
                        ) + pBs[li]
                    ewc = jax.lax.dynamic_slice(
                        EWp, (0, start), (S, rc)
                    )[lane_split]  # [Lk, rc]
                    return out, ewc

                if classification:
                    def ebody(acc, start):
                        out, ewc = forward_chunk(start)
                        pred = jnp.argmax(out, axis=-1)
                        yc = jax.lax.dynamic_slice(ye, (start,), (rc,))
                        hit = (pred == yc[None, :]).astype(jnp.float32)
                        return acc + jnp.sum(hit * ewc, axis=1), None

                    acc, _ = jax.lax.scan(
                        ebody, jnp.zeros((Lk,), jnp.float32),
                        jnp.arange(0, n_pad, rc),
                    )
                    den = jnp.sum(EWp, axis=1)[lane_split]
                    score = acc / jnp.maximum(den, 1e-12)
                    return {"score": score[:L0].reshape(chunk, S)}

                def ebody(carry, start):
                    sw, swy, swyy, ssr = carry
                    out, ewc = forward_chunk(start)
                    pred = out[:, :, 0]
                    yc = jax.lax.dynamic_slice(ye, (start,), (rc,))[None, :]
                    sw = sw + jnp.sum(ewc, axis=1)
                    swy = swy + jnp.sum(ewc * yc, axis=1)
                    swyy = swyy + jnp.sum(ewc * yc * yc, axis=1)
                    ssr = ssr + jnp.sum(ewc * (yc - pred) ** 2, axis=1)
                    return (sw, swy, swyy, ssr), None

                z = jnp.zeros((Lk,), jnp.float32)
                (sw, swy, swyy, ssr), _ = jax.lax.scan(
                    ebody, (z, z, z, z), jnp.arange(0, n_pad, rc)
                )
                swc = jnp.maximum(sw, 1e-12)
                ss_tot = jnp.maximum(swyy - swy * swy / swc, 1e-12)
                r2 = 1.0 - ssr / ss_tot
                mse = ssr / swc
                return {
                    "score": r2[:L0].reshape(chunk, S),
                    "mse": mse[:L0].reshape(chunk, S),
                }

        return fn


class MLPClassifierKernel(_MLPBase):
    name = "MLPClassifier"
    task = "classification"

    def _out_dim(self, static):
        return max(int(static["_n_classes"]), 2)

    def _target(self, y, static):
        return jax.nn.one_hot(y, self._out_dim(static), dtype=jnp.float32)

    def _loss(self, pred, tb):
        logp = jax.nn.log_softmax(pred, axis=-1)
        return -jnp.sum(tb * logp, axis=-1)

    def predict(self, params, X, static: Dict[str, Any]):
        logits = self._forward(params, X.astype(jnp.float32), static)
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)

    def predict_margin(self, params, X, static: Dict[str, Any]):
        logits = self._forward(params, X.astype(jnp.float32), static)
        return logits[:, 1] - logits[:, 0]

    def predict_proba(self, params, X, static: Dict[str, Any]):
        logits = self._forward(params, X.astype(jnp.float32), static)
        return jax.nn.softmax(logits, axis=-1)


class MLPRegressorKernel(_MLPBase):
    name = "MLPRegressor"
    task = "regression"

    def _out_dim(self, static):
        return 1

    def _target(self, y, static):
        return y.astype(jnp.float32)[:, None]

    def _loss(self, pred, tb):
        return 0.5 * jnp.sum((pred - tb) ** 2, axis=-1)

    def predict(self, params, X, static: Dict[str, Any]):
        return self._forward(params, X.astype(jnp.float32), static)[:, 0]


from .registry import register_kernel  # noqa: E402  (self-registration on import)

register_kernel(MLPClassifierKernel())
register_kernel(MLPRegressorKernel())

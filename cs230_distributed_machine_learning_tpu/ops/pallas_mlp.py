"""Fused Pallas MLP training: the whole Adam minibatch epoch on-chip.

Capability target: BASELINE config 5 (MLPClassifier RandomizedSearchCV,
sklearn-MLP semantics — the reference worker fits `MLPClassifier`,
``aws-prod/worker/worker.py:36-57``). The generic vmapped fit
(models/mlp.py) is Adam-STATE-bandwidth bound, not compute bound: at
sklearn's batch-size semantics (<=256 rows/step) every step streams
params + both moments through HBM (~20 B/param/step/lane) while the
step's matmuls only touch ``batch_size`` rows — measured 7.3% MFU at
MNIST scale (VERDICT r3 #4).

This kernel breaks that floor by keeping (params, m, v) RESIDENT in VMEM
across all of an epoch's steps:

- grid = (lane_groups, n_batches), step-minor: the state blocks' index
  maps ignore the step axis, so Mosaic keeps them in VMEM across every
  step of a lane group — HBM state traffic collapses from per-STEP to
  per-EPOCH (``n_batches``x less);
- k lanes (trial x CV-split instances) are packed per grid step: they
  share the epoch-shuffled batch block (every lane of a bucket shares
  the shuffle stream — sklearn seeds it from ``random_state``, which is
  static per bucket), so the [bs, d] activations load once per k fits
  and the 3x2xk matmuls fill the MXU pipeline between batch copies;
- the epoch loop (lax.scan in models/mlp.py) re-shuffles rows in XLA
  (one gather) and re-enters the kernel with the carried state.

Semantics match models/mlp.py's scan step exactly — same Glorot init,
same permutation stream, same bf16 matmuls with f32 accumulation, same
loss scaling (mean weighted batch loss + alpha/2 * ||W||^2 / batch
weight) — with one deliberate upgrade: the first moment stays f32 (the
generic path stores it bf16 purely to cut the HBM traffic this kernel
does not pay).
"""

from __future__ import annotations

import functools
import os
from typing import Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

B1 = 0.9
B2 = 0.999
EPS = 1e-8
_LOG_B1 = float(np.log(B1))
_LOG_B2 = float(np.log(B2))


def _act_and_grad(name: str):
    """(activation, derivative-from-(z, a)) pair for hidden layers."""
    if name == "relu":
        return (lambda z: jnp.maximum(z, 0.0),
                lambda z, a: (z > 0.0).astype(jnp.float32))
    if name == "tanh":
        return jnp.tanh, lambda z, a: 1.0 - a * a
    if name == "logistic":
        return jax.nn.sigmoid, lambda z, a: a * (1.0 - a)
    return (lambda z: z), (lambda z, a: jnp.ones_like(a))


def _dot(a, b, dims, *, interpret: bool = False):
    # bf16 operands, f32 accumulation — the MXU's native mode, matching the
    # generic fit's matmul precision. The CPU interpreter (test coverage)
    # lacks the mixed bf16->f32 dot, so it computes in f32.
    dt = jnp.float32 if interpret else jnp.bfloat16
    return jax.lax.dot_general(
        a.astype(dt), b.astype(dt),
        (dims, ((), ())), preferred_element_type=jnp.float32,
    )


def _epoch_kernel(
    x_ref, y_ref, w_ref, lr_ref, alpha_ref, t0_ref, *state,
    act: str, k: int, n_layers: int, classification: bool,
    solver: str = "adam", momentum: float = 0.9, nesterov: bool = True,
    track_loss: bool = False, interpret: bool = False,
):
    """One grid step = one solver minibatch update for k packed lanes.

    ``state`` = (inputs..., outputs...): per layer, [k-block] slabs of
    (pW, pB, mW, mB, vW, vB) for adam or (pW, pB, velW, velB) for sgd
    (sklearn SGDOptimizer: velocity momentum, optionally Nesterov) —
    plus, when ``track_loss``, one trailing [k, 8, 128] per-lane
    epoch-loss accumulator slab (the adaptive-lr schedule's signal).
    Outputs are initialized from the inputs at step 0 and updated in
    place; their blocks revisit (index maps ignore the step axis) so they
    stay in VMEM until the lane group changes.

    Biases are carried as [k, 8, out] slabs of 8 IDENTICAL sublane rows:
    Mosaic cannot relayout the [1, out] vectors a scalar bias row would
    produce ("non-singleton logical dimension is replicated" compile
    error), so bias broadcast/reduction ride two tiny ones-matmuls
    ([bs, 8] x [8, out] and [8, bs] x [bs, out]) that keep every
    intermediate in a native 2-D layout. Elementwise updates preserve the
    row-identical invariant.
    """
    per_layer = 6 if solver == "adam" else 4
    n_half = per_layer * n_layers + (1 if track_loss else 0)
    ins, outs = state[:n_half], state[n_half:]
    step = pl.program_id(1)
    act_f, act_g = _act_and_grad(act)

    @pl.when(step == 0)
    def _init():
        for o, i_ in zip(outs, ins):
            o[...] = i_[...]

    t = (t0_ref[0, 0] + step + 1).astype(jnp.float32)
    bc1 = 1.0 - jnp.exp(t * _LOG_B1)
    bc2 = 1.0 - jnp.exp(t * _LOG_B2)

    xb = x_ref[...]
    yb = y_ref[...].astype(jnp.float32)
    bs = xb.shape[0]
    ones_b = jnp.full((bs, 8), 0.125, jnp.float32)  # bias broadcast operand
    ones_r = jnp.ones((8, bs), jnp.float32)  # bias reduction operand
    wv = w_ref[...]  # [bs, n_lanes] f32 split weights, shuffled like rows
    lrv = lr_ref[...]  # [n_lanes, 1]
    alv = alpha_ref[...]
    n_lanes = wv.shape[1]
    lane_iota_row = jax.lax.broadcasted_iota(jnp.int32, (1, n_lanes), 1)
    lane_iota_col = jax.lax.broadcasted_iota(jnp.int32, (n_lanes, 1), 0)
    lg = pl.program_id(0)

    def refs(li):
        return outs[per_layer * li : per_layer * (li + 1)]

    for i in range(k):
        # per-lane scalars/vectors via masked reduce (TPU block-shape rules
        # disallow k-row blocks narrower than a sublane, and the full
        # [bs, n_lanes] / [n_lanes, 1] operands are tiny)
        lane = lg * k + i
        lr = jnp.sum(jnp.where(lane_iota_col == lane, lrv, 0.0))
        alpha = jnp.sum(jnp.where(lane_iota_col == lane, alv, 0.0))
        # keepdims: 1-D [bs] vectors hit the same Mosaic replicated-dim
        # relayout error as scalar bias rows — stay 2-D throughout
        wb = jnp.sum(jnp.where(lane_iota_row == lane, wv, 0.0), axis=1,
                     keepdims=True)  # [bs, 1]
        bw = jnp.maximum(jnp.sum(wb), 1e-12)

        # ---- forward ----
        h = xb
        zs, acts = [], [xb]
        for li in range(n_layers):
            pW, pB = refs(li)[0], refs(li)[1]
            z = _dot(h, pW[i], ((1,), (0,)), interpret=interpret)
            z = z + _dot(ones_b, pB[i], ((1,), (0,)), interpret=interpret)
            a = act_f(z) if li < n_layers - 1 else z
            zs.append(z)
            acts.append(a)
            h = a

        # ---- output-layer gradient of the mean weighted loss ----
        if classification:
            p = jax.nn.softmax(acts[-1], axis=-1)
            dz = (p - yb) * (wb / bw)
        else:
            dz = (acts[-1] - yb) * (wb / bw)

        if track_loss:
            # per-batch DATA loss (the adaptive schedule's improvement
            # signal; the L2 term is added host-side per epoch)
            if classification:
                logp = jnp.log(jnp.maximum(p, 1e-12))
                batch_loss = -jnp.sum(yb * logp * wb) / bw
            else:
                batch_loss = 0.5 * jnp.sum(
                    (acts[-1] - yb) ** 2 * wb
                ) / bw
            loss_ref = outs[-1]
            loss_ref[i] = loss_ref[i] + batch_loss

        # ---- backward + in-place update, last layer first ----
        for li in range(n_layers - 1, -1, -1):
            slabs = refs(li)
            pW, pB = slabs[0], slabs[1]
            gW = _dot(acts[li], dz, ((0,), (0,)), interpret=interpret) + (alpha / bw) * pW[i]
            gB = _dot(ones_r, dz, ((1,), (0,)), interpret=interpret)
            if li > 0:
                da = _dot(dz, pW[i], ((1,), (1,)), interpret=interpret)
                dz = da * act_g(zs[li - 1], acts[li])

            if solver == "adam":
                _, _, mW, mB, vW, vB = slabs
                m = B1 * mW[i] + (1.0 - B1) * gW
                v = B2 * vW[i] + (1.0 - B2) * gW * gW
                mW[i], vW[i] = m, v
                pW[i] = pW[i] - lr * (m / bc1) / (jnp.sqrt(v / bc2) + EPS)

                mb = B1 * mB[i] + (1.0 - B1) * gB
                vb = B2 * vB[i] + (1.0 - B2) * gB * gB
                mB[i], vB[i] = mb, vb
                pB[i] = pB[i] - lr * (mb / bc1) / (jnp.sqrt(vb / bc2) + EPS)
            else:  # sgd: sklearn velocity momentum (+ Nesterov look-ahead)
                _, _, velW, velB = slabs
                vw = momentum * velW[i] - lr * gW
                vb = momentum * velB[i] - lr * gB
                velW[i], velB[i] = vw, vb
                if nesterov:
                    pW[i] = pW[i] + momentum * vw - lr * gW
                    pB[i] = pB[i] + momentum * vb - lr * gB
                else:
                    pW[i] = pW[i] + vw
                    pB[i] = pB[i] + vb


def vmem_lane_bytes(dims: Sequence[int], bs: int, solver: str = "adam") -> int:
    """Per-lane VMEM working set: 2x (in+out blocks) state slabs (3x f32
    for adam's params+moments, 2x for sgd's params+velocity) plus the
    step's live activations — the k-chooser's denominator."""
    params = sum(a * b + b for a, b in zip(dims[:-1], dims[1:]))
    acts = bs * (2 * sum(dims) + max(dims))
    per_layer = 12 if solver == "adam" else 8
    return 2 * per_layer * params + 4 * acts


def pick_k(dims: Sequence[int], bs: int, budget_bytes: int = 48 * 2**20,
           solver: str = "adam") -> int:
    """Largest k in {16,8,4,2,1} whose packed state fits the VMEM budget.

    The budget tracks the raised per-kernel vmem limit (the pallas_call
    passes compiler_params vmem_limit_bytes=100 MB), less headroom for
    the double-buffered batch blocks. k=16 (r5) opt-in via
    CS230_MLP_K16=1 — measured NEUTRAL on config 5 (same 23 s steady):
    at MNIST dims the kernel is batch-copy-bound, not lane-bound."""
    per = max(vmem_lane_bytes(dims, bs, solver), 1)
    ks = (16, 8, 4, 2, 1) if os.environ.get("CS230_MLP_K16") == "1" else (8, 4, 2, 1)
    for k in ks:
        if k * per <= budget_bytes:
            return k
    return 1


def build_epoch_fn(
    dims: Tuple[int, ...],
    act: str,
    bs: int,
    n_batches: int,
    n_lanes: int,
    k: int,
    classification: bool,
    solver: str = "adam",
    momentum: float = 0.9,
    nesterov: bool = True,
    track_loss: bool = False,
    interpret: bool = False,
):
    """fn(Xs, Ys, Wlane, lr, alpha, t0, state) -> state.

    ``Xs`` [n_batches*bs, d] bf16 and ``Ys`` [n_batches*bs, c] are the
    epoch-shuffled rows/targets; ``Wlane`` [n_batches*bs, n_lanes] f32 the
    per-lane split weights in the same shuffled row order (lane-minor so
    batch-step blocks satisfy TPU block-shape rules); ``lr``/``alpha``
    [n_lanes, 1]; ``t0`` [1, 1] int32 (completed step count). ``state`` is
    the flat per-layer list of [n_lanes, ...] — (pW, pB, mW, mB, vW, vB)
    for adam, (pW, pB, velW, velB) for sgd — plus, when ``track_loss``, a
    trailing [n_lanes, 8, 128] epoch-loss accumulator (zeroed at step 0,
    read back at [:, 0, 0]); biases are carried [n_lanes, 8, out] with
    identical sublane rows (see the kernel docstring).
    ``n_lanes`` must be a multiple of ``k``; ``bs`` a multiple of 8.
    """
    assert n_lanes % k == 0, (n_lanes, k)
    n_layers = len(dims) - 1
    grid = (n_lanes // k, n_batches)

    def lane_spec(shape):
        return pl.BlockSpec(
            (k,) + tuple(shape[1:]),
            lambda lg, s, _nd=len(shape): (lg,) + (0,) * (_nd - 1),
        )

    kern = functools.partial(
        _epoch_kernel, act=act, k=k, n_layers=n_layers,
        classification=classification, solver=solver, momentum=momentum,
        nesterov=nesterov, track_loss=track_loss, interpret=interpret,
    )

    def fn(Xs, Ys, Wlane, lr, alpha, t0, state):
        in_specs = [
            pl.BlockSpec((bs, dims[0]), lambda lg, s: (s, 0)),
            pl.BlockSpec((bs, dims[-1]), lambda lg, s: (s, 0)),
            pl.BlockSpec((bs, n_lanes), lambda lg, s: (s, 0)),
            pl.BlockSpec((n_lanes, 1), lambda lg, s: (0, 0)),
            pl.BlockSpec((n_lanes, 1), lambda lg, s: (0, 0)),
            pl.BlockSpec((1, 1), lambda lg, s: (0, 0)),
        ] + [lane_spec(a.shape) for a in state]
        out_specs = [lane_spec(a.shape) for a in state]
        out_shape = [jax.ShapeDtypeStruct(a.shape, a.dtype) for a in state]
        kwargs = {}
        if not interpret:
            # the packed lane state overflows the default 16 MB scoped-vmem
            # budget by design — residency is the point; v5e has 128 MB
            kwargs["compiler_params"] = pltpu.CompilerParams(
                vmem_limit_bytes=100 * 2**20,
            )
        return list(
            pl.pallas_call(
                kern,
                grid=grid,
                in_specs=in_specs,
                out_specs=out_specs,
                out_shape=out_shape,
                interpret=interpret,
                name="mlp_fused_epoch",
                **kwargs,
            )(Xs, Ys, Wlane, lr, alpha, t0, *state)
        )

    return fn

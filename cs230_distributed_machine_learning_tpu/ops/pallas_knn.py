"""Fused KNN top-k search as a Pallas TPU kernel.

The hot op of the KNN kernels (models/knn.py) is "for each query, the k
nearest masked training rows". The pure-XLA path computes a [block, n]
distance matrix and runs ``lax.top_k`` on it — for large n that round-trips
hundreds of MB of distances through HBM per block. This kernel fuses the
whole search: it streams training-set tiles through VMEM, computes the
distance tile on the MXU, and folds it into a running per-query top-k held
in VMEM scratch — the [nq, n] distance matrix never exists.

Grid: (query_blocks, train_blocks), train innermost so the running-best
scratch persists across a query block's sweep. Top-k merge is k rounds of
(min, first-argmin-via-iota, mask) — VPU reductions only, no sort.

Used on TPU for large n (models/knn.py gates on backend + size);
``interpret=True`` runs the same kernel on CPU for tests.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_BQ = 256   # query tile rows
_BT = 2048  # train tile cols (VMEM: BT*d floats + BQ*BT distance tile)
_INF = 3.4e38  # plain float: jnp constants would be captured consts in the kernel


def _kernel(q_ref, qsq_ref, xt_ref, tsq_ref, w_ref, d2_out, idx_out, best_d2, best_idx, *, k: int):
    j = pl.program_id(1)
    n_j = pl.num_programs(1)

    @pl.when(j == 0)
    def _init():
        best_d2[:] = jnp.full_like(best_d2, jnp.float32(_INF))
        best_idx[:] = jnp.full_like(best_idx, -1)

    # distance tile on the MXU: [BQ, BT]
    d2 = (
        qsq_ref[:]
        + tsq_ref[:]
        - 2.0 * jnp.dot(q_ref[:], xt_ref[:].T, preferred_element_type=jnp.float32)
    )
    d2 = jnp.maximum(d2, 0.0)
    d2 = jnp.where(w_ref[:] > 0.0, d2, _INF)

    col = jax.lax.broadcasted_iota(jnp.int32, d2.shape, 1)
    global_col = col + j * _BT

    def merge_one(s, carry):
        d2_c, bd, bi = carry
        # row minimum of the remaining tile
        m = jnp.min(d2_c, axis=1, keepdims=True)                     # [BQ, 1]
        is_min = d2_c == m
        # first position achieving the minimum
        pos = jnp.min(jnp.where(is_min, global_col, jnp.int32(2**30)), axis=1, keepdims=True)
        first = is_min & (global_col == pos)
        # fold into the worst best-slot if better
        worst = jnp.max(bd, axis=1, keepdims=True)                   # [BQ, 1]
        wcol = jax.lax.broadcasted_iota(jnp.int32, bd.shape, 1)
        wpos = jnp.min(
            jnp.where(bd == worst, wcol, jnp.int32(2**30)), axis=1, keepdims=True
        )
        take = (m < worst)                                           # [BQ, 1]
        slot = (wcol == wpos) & take
        bd = jnp.where(slot, m, bd)
        bi = jnp.where(slot, pos, bi)
        # retire the extracted column
        d2_c = jnp.where(first & take, _INF, d2_c)
        return d2_c, bd, bi

    carry = (d2, best_d2[:], best_idx[:])
    carry = jax.lax.fori_loop(0, k, lambda s, c: merge_one(s, c), carry)
    _, bd, bi = carry
    best_d2[:] = bd
    best_idx[:] = bi

    @pl.when(j == n_j - 1)
    def _emit():
        # sort the k slots ascending by distance (k is tiny: selection sort
        # with the same min/mask trick)
        bd = best_d2[:]
        bi = best_idx[:]
        out_d = jnp.full_like(bd, _INF)
        out_i = jnp.full_like(bi, -1)
        wcol = jax.lax.broadcasted_iota(jnp.int32, bd.shape, 1)

        def sort_step(s, c):
            bd_c, bi_c, od, oi = c
            m = jnp.min(bd_c, axis=1, keepdims=True)
            mpos = jnp.min(
                jnp.where(bd_c == m, wcol, jnp.int32(2**30)), axis=1, keepdims=True
            )
            sel = wcol == mpos
            val_i = jnp.sum(jnp.where(sel, bi_c, 0), axis=1, keepdims=True)
            od = jnp.where(wcol == s, m, od)
            oi = jnp.where(wcol == s, val_i, oi)
            bd_c = jnp.where(sel, _INF, bd_c)
            return bd_c, bi_c, od, oi

        _, _, out_d, out_i = jax.lax.fori_loop(0, k, sort_step, (bd, bi, out_d, out_i))
        d2_out[:] = out_d
        idx_out[:] = out_i


@functools.partial(jax.jit, static_argnames=("k", "interpret"))
def knn_topk(Q, Xt, w, k: int, interpret: bool = False):
    """For each row of Q: the k smallest masked squared distances into Xt.

    Returns (d2 [nq, k] ascending, idx [nq, k] global train-row indices).
    Rows with w<=0 are excluded. Shapes are padded to tile multiples
    internally.
    """
    nq, d = Q.shape
    n = Xt.shape[0]
    k = int(k)

    nq_p = pl.cdiv(nq, _BQ) * _BQ
    n_p = pl.cdiv(n, _BT) * _BT
    Qp = jnp.zeros((nq_p, d), jnp.float32).at[:nq].set(Q.astype(jnp.float32))
    Xp = jnp.zeros((n_p, d), jnp.float32).at[:n].set(Xt.astype(jnp.float32))
    wp = jnp.zeros((n_p,), jnp.float32).at[:n].set(w.astype(jnp.float32))
    qsq = jnp.sum(Qp * Qp, axis=1, keepdims=True)          # [nq_p, 1]
    tsq = jnp.sum(Xp * Xp, axis=1)[None, :]                # [1, n_p]

    grid = (nq_p // _BQ, n_p // _BT)
    d2_out, idx_out = pl.pallas_call(
        functools.partial(_kernel, k=k),
        grid=grid,
        in_specs=[
            pl.BlockSpec((_BQ, d), lambda i, j: (i, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((_BQ, 1), lambda i, j: (i, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((_BT, d), lambda i, j: (j, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((1, _BT), lambda i, j: (0, j), memory_space=pltpu.VMEM),
            pl.BlockSpec((1, _BT), lambda i, j: (0, j), memory_space=pltpu.VMEM),
        ],
        out_specs=[
            pl.BlockSpec((_BQ, k), lambda i, j: (i, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((_BQ, k), lambda i, j: (i, 0), memory_space=pltpu.VMEM),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((nq_p, k), jnp.float32),
            jax.ShapeDtypeStruct((nq_p, k), jnp.int32),
        ],
        scratch_shapes=[
            pltpu.VMEM((_BQ, k), jnp.float32),
            pltpu.VMEM((_BQ, k), jnp.int32),
        ],
        interpret=interpret,
        name="knn_topk",
    )(Qp, qsq, Xp, tsq, wp[None, :])
    return d2_out[:nq], idx_out[:nq]

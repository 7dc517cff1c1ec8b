"""K-nearest-neighbors kernels (classifier + regressor), MXU-first.

Capability target: the reference's `KNeighborsClassifier` /
`KNeighborsRegressor` trials (``aws-prod/worker/worker.py:45,51``). The
distance computation is the classic ||q||^2 + ||x||^2 - 2 q.x expansion —
one [B,d]x[d,n] matmul per query block, exactly the shape the MXU wants —
with queries processed in fixed-size blocks via ``lax.map`` so the [n,n]
distance matrix never materializes for large datasets.

"Fitting" a KNN is storing the training set: here that's the {0,1} split
mask (the full X/y arrays are shared by every split and trial), so the K+1
CV fits per trial are free. ``n_neighbors`` changes the top-k shape and is
therefore static (one compile bucket per k, as SURVEY.md §7's bucketing
prescribes); ``weights`` ("uniform" | "distance") is static control flow.

sklearn-matching details: Euclidean (minkowski p=2) metric; distance
weighting uses 1/d with exact-match (d=0) queries collapsing onto the
matched neighbors; classification ties resolve to the smallest label, which
argmax-over-counts reproduces.
"""

from __future__ import annotations

import os
from typing import Any, Dict

import jax
import jax.numpy as jnp
import numpy as np

from ..utils import backend as _backend
from .base import ModelKernel

_QUERY_BLOCK = 1024
_TRAIN_TILE = 16384
#: neighbor counts at or below this use k min-extractions in place of
#: lax.top_k (see tile_step) — the crossover where k sequential row
#: reductions beat the sort network over the tile width
_SMALL_K = 16
# above this many training rows on TPU, use the fused Pallas top-k kernel
# (streams train tiles through VMEM; the XLA path streams the same tiles
# but pays a per-tile sort-based top-k merge in HBM)
_PALLAS_MIN_N = 150_000


def _use_pallas(n: int) -> bool:
    return n >= _PALLAS_MIN_N and _backend.auto_pallas()


class _KNNBase(ModelKernel):
    hyper_defaults: Dict[str, float] = {}
    static_defaults = {"n_neighbors": 5, "weights": "uniform", "p": 2}

    def resolve_static(self, static: Dict[str, Any], n: int, d: int, n_classes: int):
        if int(static.get("p", 2)) != 2:
            raise ValueError("KNN: only p=2 (euclidean) is supported")
        if static.get("weights") not in ("uniform", "distance"):
            raise ValueError(f"KNN: unsupported weights={static.get('weights')!r}")
        k = int(static.get("n_neighbors", 5))
        return {**static, "n_neighbors": min(k, n)}

    def fit(self, X, y, w, hyper: Dict[str, Any], static: Dict[str, Any]):
        return {
            "X": X.astype(jnp.float32),
            "y": y,
            "w": w.astype(jnp.float32),
        }

    def _neighbors(self, params, Q, static):
        """Per query: (top-k distances^2, top-k train indices)."""
        k = int(static["n_neighbors"])
        Xt = params["X"]
        w = params["w"]
        if _use_pallas(Xt.shape[0]):
            from ..ops.pallas_knn import knn_topk

            return knn_topk(Q, Xt, w, k)
        big = jnp.float32(3.4e38)
        n, d = Xt.shape

        # train side padded to tile multiples; padded rows carry w=0 so
        # they are masked to +inf distance
        T = min(_TRAIN_TILE, max(n, 1))
        n_tp = ((n + T - 1) // T) * T
        Xtp = jnp.pad(Xt, ((0, n_tp - n), (0, 0)))
        wp = jnp.pad(w, (0, n_tp - n))
        sq_tp = jnp.sum(Xtp * Xtp, axis=1)

        nq = Q.shape[0]
        pad = (-nq) % _QUERY_BLOCK
        Qp = jnp.pad(Q, ((0, pad), (0, 0)))
        blocks = Qp.reshape(-1, _QUERY_BLOCK, d)

        def one_block(qb):
            sq_q = jnp.sum(qb * qb, axis=1, keepdims=True)

            # stream train tiles, merging into a running top-k: peak memory
            # is [block, tile + k], never [block, n] (an n x n distance/sort
            # workspace faults the device at Covertype scale). Tie-break to
            # the smallest train index (sklearn order): earlier tiles sit
            # first in the merge concat and lax.top_k prefers lower
            # positions on ties.
            def tile_step(carry, tstart):
                best_d, best_i = carry
                xt = jax.lax.dynamic_slice(Xtp, (tstart, 0), (T, d))
                st = jax.lax.dynamic_slice(sq_tp, (tstart,), (T,))
                wt = jax.lax.dynamic_slice(wp, (tstart,), (T,))
                d2 = sq_q + st[None, :] - 2.0 * (qb @ xt.T)
                d2 = jnp.where(wt[None, :] > 0, jnp.maximum(d2, 0.0), big)
                cat_d = jnp.concatenate([best_d, d2], axis=1)
                idx_tile = jnp.broadcast_to(
                    tstart + jnp.arange(T, dtype=jnp.int32)[None, :], d2.shape
                )
                cat_i = jnp.concatenate([best_i, idx_tile], axis=1)
                if k <= _SMALL_K:
                    # k min-extractions instead of lax.top_k's full sort
                    # network over the tile width — each extraction is a
                    # pair of row reductions plus one masked pass, all VPU
                    # vector ops (the 11.6k-row model-matrix KNN fit went
                    # 0.92 -> 0.13 s steady, identical CV; top_k was the
                    # whole cost). argmin takes the FIRST minimum,
                    # preserving sklearn's smaller-train-index tie order
                    # like top_k's lower-position preference did.
                    iota = jax.lax.broadcasted_iota(
                        jnp.int32, cat_d.shape, 1
                    )
                    cur = cat_d
                    ds, is_ = [], []
                    for _ in range(k):
                        j = jnp.argmin(cur, axis=1)[:, None]
                        hit = iota == j
                        ds.append(jnp.min(cur, axis=1, keepdims=True))
                        is_.append(
                            jnp.sum(jnp.where(hit, cat_i, 0), axis=1,
                                    keepdims=True)
                        )
                        cur = jnp.where(hit, big, cur)
                    return (
                        jnp.concatenate(ds, axis=1),
                        jnp.concatenate(is_, axis=1),
                    ), None
                neg, sel = jax.lax.top_k(-cat_d, k)
                return (-neg, jnp.take_along_axis(cat_i, sel, axis=1)), None

            init = (
                jnp.full((qb.shape[0], k), big),
                jnp.zeros((qb.shape[0], k), jnp.int32),
            )
            (best_d, best_i), _ = jax.lax.scan(
                tile_step, init, jnp.arange(0, n_tp, T, dtype=jnp.int32)
            )
            return best_d, best_i

        d2s, idxs = jax.lax.map(one_block, blocks)
        return (
            d2s.reshape(-1, k)[:nq],
            idxs.reshape(-1, k)[:nq],
        )

    @staticmethod
    def _vote_weights(d2, static):
        if static.get("weights") == "distance":
            d = jnp.sqrt(jnp.maximum(d2, 0.0))
            inv = 1.0 / jnp.maximum(d, 1e-12)
            # sklearn: if any neighbor matches exactly, only exact matches vote
            has_zero = jnp.any(d <= 1e-12, axis=1, keepdims=True)
            zero_w = (d <= 1e-12).astype(jnp.float32)
            return jnp.where(has_zero, zero_w, inv)
        return jnp.ones_like(d2)

    def memory_estimate_mb(self, n, d, static):
        # tiled top-k workspace: [QUERY_BLOCK, TRAIN_TILE] per split plus
        # the shared [n, d] dataset (the [block, n] full distance matrix no
        # longer exists)
        return max(1.0, 4.0 * (n * d + 3 * _QUERY_BLOCK * _TRAIN_TILE) / 1e6)

    def macs_estimate(self, n, d, static):
        """Scoring-time n x n distance sweep dominates (fit is free)."""
        return float(n) * n * max(d, 1)

    # ---- chunked-fit protocol (parallel/trial_map.py chunked path) ----
    # KNN "training" is free; the cost is the n_query x n_train distance
    # sweep at scoring time. Chunks split the QUERY rows: each dispatch
    # predicts one row range into an accumulating prediction vector, so the
    # per-dispatch device time stays bounded at any dataset size.

    def chunked_plan(self, static, n, d, n_classes, n_splits):
        # per-dispatch budget from measured effective throughput. Large k
        # pays lax.top_k's per-tile sort merge (~2.5e10 MACs/s — far below
        # the matmul-bound kernels); k <= _SMALL_K rides the min-extraction
        # path, measured ~6.6x faster (0.92 -> 0.14 s on the 11.6k model-
        # matrix fit), so its budget scales up accordingly — the stale
        # small budget would issue ~7x more dispatches than the bounded-
        # device-time target needs.
        # the raised budget applies only when the min-extraction path will
        # actually run: k <= _SMALL_K AND the Pallas top-k kernel is NOT
        # taking over (same gate the kernel uses — n >= _PALLAS_MIN_N on an
        # accelerator backend; the Pallas path's throughput the 6.6x
        # measurement does not cover, so its budget stays conservative)
        # gate on the PER-FOLD training rows the kernel will actually see
        # (~(s-1)/s of n under s-fold CV), matching _neighbors' own check
        train_rows = n if n_splits <= 1 else (n * (n_splits - 1)) // n_splits
        small_path = (
            int(static.get("n_neighbors", 5)) <= _SMALL_K
            and not _use_pallas(train_rows)
        )
        default = 1.6e12 if small_path else 2.5e11
        chunk_macs = float(os.environ.get("CS230_KNN_CHUNK_MACS", default))
        macs = float(max(n_splits, 1)) * n * n * max(d, 1)
        n_chunks = int(np.ceil(macs / chunk_macs))
        if n_chunks <= 1:
            return None
        q = int(np.ceil(n / n_chunks))
        q = max(_QUERY_BLOCK, ((q + _QUERY_BLOCK - 1) // _QUERY_BLOCK) * _QUERY_BLOCK)
        n_chunks = int(np.ceil(n / q))
        if n_chunks <= 1:  # rounding collapsed it: monolithic is cheaper
            return None
        return {"n_chunks": n_chunks, "rows_per_chunk": q}

    def _chunk_state_dtype(self):
        return jnp.int32 if self.task == "classification" else jnp.float32

    def chunk_init(self, X, y, w, hyper, static):
        return jnp.zeros((X.shape[0],), self._chunk_state_dtype())

    def chunk_step(self, X, y, w, hyper, static, chunk_idx, state, plan):
        Xa = X.astype(jnp.float32)
        q = plan["rows_per_chunk"]
        n = Xa.shape[0]
        # dynamic_slice clamps the start, so the final (ragged) chunk
        # re-predicts a few overlapping rows with identical values
        start = jnp.minimum(chunk_idx * q, max(n - q, 0))
        Q = jax.lax.dynamic_slice(Xa, (start, 0), (min(q, n), Xa.shape[1]))
        params = self.fit(Xa, y, w, hyper, static)
        preds = self.predict(params, Q, static).astype(self._chunk_state_dtype())
        return jax.lax.dynamic_update_slice(state, preds, (start,))

    def chunk_eval(self, X, y, w_eval, hyper, static, state):
        from ..ops.metrics import (
            classification_score,
            regression_score,
            weighted_mse,
        )

        scoring = static.get("_scoring")
        if self.task == "classification":
            return {"score": classification_score(
                scoring, y, state, w_eval, static.get("_n_classes", 2))}
        return {
            "score": regression_score(scoring, y, state, w_eval),
            "mse": weighted_mse(y, state, w_eval),
        }


class KNNClassifierKernel(_KNNBase):
    name = "KNeighborsClassifier"
    task = "classification"

    def predict(self, params, X, static: Dict[str, Any]):
        c = max(int(static["_n_classes"]), 2)
        d2, idx = self._neighbors(params, X.astype(jnp.float32), static)
        labels = params["y"][idx]  # [nq, k]
        votes = self._vote_weights(d2, static)
        counts = jnp.sum(jax.nn.one_hot(labels, c, dtype=jnp.float32) * votes[..., None], axis=1)
        return jnp.argmax(counts, axis=-1).astype(jnp.int32)


class KNNRegressorKernel(_KNNBase):
    name = "KNeighborsRegressor"
    task = "regression"

    def predict(self, params, X, static: Dict[str, Any]):
        d2, idx = self._neighbors(params, X.astype(jnp.float32), static)
        targets = params["y"][idx].astype(jnp.float32)
        votes = self._vote_weights(d2, static)
        return jnp.sum(targets * votes, axis=1) / jnp.maximum(jnp.sum(votes, axis=1), 1e-12)


from .registry import register_kernel  # noqa: E402  (self-registration on import)

register_kernel(KNNClassifierKernel())
register_kernel(KNNRegressorKernel())

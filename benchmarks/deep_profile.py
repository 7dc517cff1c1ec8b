"""Per-component microbenchmark of one deep-builder level at production
shape (full Covertype RF: n=116k rows, W=1024 frontier, 24 bins, 6
fold-lanes vmapped) on the real device.

The r4 finding was that the fit is bound by W-proportional terms, not
histogram MACs (BASELINE.md "Grouped histograms"); this harness pins WHICH
term so the r5 attack goes to the right place. PR 6 adds the alternative
histogram kernels (``histscatter``: the bin-and-scatter segment-sum form;
``histpallas``: the fused Pallas kernel, interpreter off-TPU) so the
one-hot matmul baseline and its replacements are A/B-able on any backend.

Measurement: a dispatch plus a fetch costs a round trip of its own, so
each component runs ITERS times
inside one jitted fori_loop with iteration-dependent inputs (defeats
loop-invariant hoisting), synced by a scalar fetch, and reports
(total - overhead) / ITERS.

Usage: python benchmarks/deep_profile.py  [PROF_W=1024 PROF_LANES=6]
       [PROF_N=0 (row subsample, 0=all) PROF_OUT=path.json]
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

from cs230_distributed_machine_learning_tpu.ops import trees as T  # noqa: E402

W = int(os.environ.get("PROF_W", 1024))
LANES = int(os.environ.get("PROF_LANES", 6))
ITERS = int(os.environ.get("PROF_ITERS", 5))
REPS = int(os.environ.get("PROF_REPS", 3))
#: row subsample for CPU-feasible runs (0 = full dataset)
SUB_N = int(os.environ.get("PROF_N", 0))
#: when set, component timings land in this JSON (ms per level/op)
OUT = os.environ.get("PROF_OUT", "")
#: comma-list of component keys to run (default all): hist,histscatter,
#: histpallas,histc,route,route2,pieces,gain,topk,topk2,leaf
ONLY = set(
    k for k in os.environ.get("PROF_ONLY", "").split(",") if k
)

RESULTS = {}


def record(key, label, t_ms):
    RESULTS[key] = round(t_ms, 3)
    print(f"{label:38s}{t_ms:8.1f} ms")


def want(key):
    return not ONLY or key in ONLY
NB = 24
KK = 8  # 7 classes + count
A_CAP = 2 * W * 24


def sync(o):
    leaf = jax.tree_util.tree_leaves(o)[0]
    np.asarray(jax.device_get(leaf.ravel()[0]))


def timed_loop(step, init):
    """step(i, carry) -> carry; returns best per-iter seconds over REPS."""

    def loop(c):
        return jax.lax.fori_loop(0, ITERS, step, c)

    f = jax.jit(loop)
    out = f(init)
    sync(out)
    best = float("inf")
    for _ in range(REPS):
        t0 = time.time()
        out = f(init)
        sync(out)
        best = min(best, time.time() - t0)
    return best / ITERS


def main():
    from cs230_distributed_machine_learning_tpu.data.datasets import DatasetCache

    cache = DatasetCache()
    data = cache.get("covertype", "classification")
    X = np.asarray(data.X, np.float32)
    y = np.asarray(data.y, np.int32)
    if SUB_N and SUB_N < len(X):
        # CPU-feasible subsample: the one-hot matmul baseline is O(n*W*kk*
        # d*nb) MACs — intractable at the full shape without an MXU
        sub = np.random.RandomState(0).permutation(len(X))[:SUB_N]
        X, y = X[sub], y[sub]
    n, d = X.shape
    print(f"covertype {n}x{d}, W={W}, lanes={LANES}, iters={ITERS}", flush=True)

    edges = T.quantile_bins(X, NB)
    xb_d = jnp.asarray(np.asarray(T.bin_data(X, edges)))

    rng = np.random.RandomState(0)
    local0 = jnp.asarray(rng.randint(0, W, size=(LANES, n)).astype(np.int32))
    SC = jnp.asarray(
        (np.eye(KK, dtype=np.float32)[y % KK] * rng.randint(1, 3, (n, 1)))[None]
        .repeat(LANES, 0)
    )
    node0 = jnp.asarray(rng.randint(0, A_CAP, size=(LANES, n)).astype(np.int32))
    frontier = jnp.asarray(
        np.sort(rng.choice(A_CAP, (LANES, W), replace=False), axis=1).astype(np.int32)
    )
    bf = jnp.asarray(rng.randint(0, d, size=(LANES, W)).astype(np.int32))
    bb = jnp.asarray(rng.randint(0, NB, size=(LANES, W)).astype(np.int32))
    do_split = jnp.asarray(rng.rand(LANES, W) < 0.8)
    left_id = jnp.asarray(rng.randint(0, A_CAP, size=(LANES, W)).astype(np.int32))

    # ---- 1. level histogram (s8 path, as the classification fit ran it
    # pre-PR-6: the one-hot matmul baseline) ----
    if want("hist"):
        os.environ["CS230_HIST_KERNEL"] = "matmul"

        def hist_step(i, acc):
            loc = (local0 + i) % W  # iteration-dependent: no hoisting
            H = jax.vmap(
                lambda l, sc: T._level_histogram(l, xb_d, sc, W, NB, None, True)
            )(loc, SC)
            return acc + H.sum()  # full reduce keeps every cell live

        t = timed_loop(hist_step, jnp.zeros(()))
        os.environ.pop("CS230_HIST_KERNEL", None)
        record("hist_matmul_ms_per_level", f"hist s8 one-hot (W={W}):", t * 1e3)

    # ---- 1s. bin-and-scatter level histogram (ops/pallas_hist.py,
    # the CS230_HIST_KERNEL=scatter / CPU-auto form) ----
    if want("histscatter"):
        from cs230_distributed_machine_learning_tpu.ops.pallas_hist import (
            level_histogram_scatter as _scatter,
        )

        def hist_scatter_step(i, acc):
            loc = (local0 + i) % W
            H = jax.vmap(lambda l, sc: _scatter(l, xb_d, sc, W, NB))(loc, SC)
            return acc + H.sum()

        t = timed_loop(hist_scatter_step, jnp.zeros(()))
        record("hist_scatter_ms_per_level", f"hist bin-and-scatter (W={W}):", t * 1e3)

    # ---- 1p. fused Pallas level histogram (compiled on TPU; off-TPU this
    # times the INTERPRETER — functional coverage only, not a perf number) ----
    if want("histpallas"):
        from cs230_distributed_machine_learning_tpu.ops.pallas_hist import (
            level_histogram_pallas as _pallas,
        )

        from cs230_distributed_machine_learning_tpu.utils import backend

        interp = backend.pallas_interpret()

        def hist_pallas_step(i, acc):
            loc = (local0 + i) % W
            H = jax.vmap(
                lambda l, sc: _pallas(
                    l, xb_d, sc, W, NB, integer_stats=True, interpret=interp)
            )(loc, SC)
            return acc + H.sum()

        t = timed_loop(hist_pallas_step, jnp.zeros(()))
        record(
            "hist_pallas_ms_per_level"
            + ("_INTERPRET" if interp else ""),
            f"hist Pallas fused (W={W}):", t * 1e3,
        )

    # ---- 1b. COMPACT level histogram (sorted-rows block form) ----
    if want("histc"):
        os.environ["CS230_HIST_COMPACT"] = "1"

        def histc_step(i, acc):
            loc = (local0 + i) % W
            H = jax.vmap(
                lambda l, sc: T._level_histogram_compact(
                    l, xb_d, sc, W, NB, None, True)
            )(loc, SC)
            return acc + H.sum()

        t = timed_loop(histc_step, jnp.zeros(()))
        record("hist_compact_ms_per_level", f"hist COMPACT (R={T._COMPACT_R}, M={T._COMPACT_M}):", t * 1e3)

    # ---- 2c. routing primitive costs (searchsorted / row gathers) ----
    if want("pieces"):
        def ss_step(i, node):
            out = jax.vmap(
                lambda nd, fr: jnp.searchsorted(fr, nd)
            )(node, (frontier + i) % A_CAP)
            return (node + out % 3) % A_CAP

        t = timed_loop(ss_step, node0)
        record("searchsorted_ms", "searchsorted [n] in [W]:", t * 1e3)

        def gather_small_step(i, node):
            out = jax.vmap(lambda nd, tb: tb[jnp.minimum(nd, W - 1)])(
                node, (bf + i) % d
            )
            return (node + out) % A_CAP

        t = timed_loop(gather_small_step, node0)
        record("row_gather_table_ms", "row gather [n] from [W] table:", t * 1e3)

        def gather_xb_step(i, node):
            f_i = jnp.minimum(node, d - 1)
            out = jax.vmap(
                lambda fi: jnp.take_along_axis(xb_d, fi[:, None], axis=1)[:, 0]
            )(f_i)
            return (node + out + i) % A_CAP

        t = timed_loop(gather_xb_step, node0)
        record("row_gather_xb_ms", "row gather xb[row, f_row]:", t * 1e3)

        def sort_step(i, node):
            s = jnp.sort((node + i) % A_CAP, axis=1)
            return s

        t = timed_loop(sort_step, node0)
        record("sort_keys_ms", "sort [lanes, n] keys:", t * 1e3)

    # ---- 2. routing block (one-hot masks, as build_tree_deep) ----
    if want("route"):
        def route_step(i, node):
            def one(node, frontier, bf, bb, do_split, left_id):
                eq = node[:, None] == jnp.where(frontier >= 0, frontier, -1)[None, :]
                in_split = (eq & do_split[None, :]).any(1)
                cols = T._col_select(xb_d, bf, NB)
                le_node = cols <= bb[None, :].astype(cols.dtype)
                go_left = jnp.any(eq & le_node, axis=1)
                l_i = jnp.dot(
                    eq.astype(jnp.float32), left_id.astype(jnp.float32),
                    precision=jax.lax.Precision.HIGHEST,
                ).astype(jnp.int32)
                return jnp.where(in_split, l_i + 1 - go_left.astype(jnp.int32), node)

            out = jax.vmap(one)(node, (frontier + i) % A_CAP, bf, bb, do_split, left_id)
            return out % A_CAP

        t = timed_loop(route_step, node0)
        record("route_onehot_ms_per_level", f"routing one-hot masks (W={W}):", t * 1e3)

    # ---- 2b. routing via sorted-frontier searchsorted + row gathers ----
    if want("route2"):
        def route_gather_step(i, node):
            def one(node, frontier, bf, bb, do_split, left_id):
                slot = jnp.minimum(jnp.searchsorted(frontier, node), W - 1)
                hit = frontier[slot] == node
                in_split = hit & do_split[slot]
                f_i = bf[slot]
                b_i = bb[slot]
                go_left = jnp.take_along_axis(xb_d, f_i[:, None], axis=1)[:, 0] <= b_i
                l_i = left_id[slot]
                return jnp.where(in_split, l_i + 1 - go_left.astype(jnp.int32), node)

            out = jax.vmap(one)(node, (frontier + i) % A_CAP, bf, bb, do_split, left_id)
            return out % A_CAP

        t = timed_loop(route_gather_step, node0)
        record("route_gather_ms_per_level", "routing searchsorted+gather:", t * 1e3)

    # shared candidate-stage inputs (blocks 3-4b). H0 is ~2 GB — generate
    # ON DEVICE (no reason to upload 2 GB of random numbers)
    H0 = jax.jit(
        lambda: jax.random.uniform(
            jax.random.PRNGKey(0), (LANES, 2 * W, d, NB, KK), jnp.float32
        )
    )()
    cgain0 = jnp.asarray(rng.rand(LANES, 2 * W).astype(np.float32))
    cid = jnp.asarray(rng.randint(0, A_CAP, (LANES, 2 * W)).astype(np.int32))

    # ---- 3. split gain + pick over 2W candidates ----
    if want("gain"):
        def gain_step(i, carry):
            acc, H0 = carry  # H0 rides the carry: a closure capture would
            # embed 2 GB as an HLO constant
            H = H0 + i * 1e-6
            g = jax.vmap(lambda h: T._split_gain(h, KK - 1, NB, 1.0))(H)
            bg, bfx, bbx = jax.vmap(lambda g: T._pick_best(g, NB))(g)
            return (acc + bg.sum() + bfx.sum() + bbx.sum(), H0)

        t = timed_loop(gain_step, (jnp.zeros(()), H0))
        record("gain_pick_ms_per_level", "split gain + pick (2W cand):", t * 1e3)

    # ---- 4. top_k W of 2W + candidate H gather ----
    if want("topk"):
        def topk_step(i, carry):
            acc, H0 = carry
            cg = cgain0 + i * 1e-6

            def one(cg, cid, H):
                vals, sel = jax.lax.top_k(cg, W)
                return vals, cid[sel], H[sel]

            vals, ids, Hs = jax.vmap(one)(cg, cid, H0)
            return (acc + vals.sum() + ids.sum() + Hs.sum(), H0)

        t = timed_loop(topk_step, (jnp.zeros(()), H0))
        record("topk_gather_ms_per_level", f"top_k {W} of {2*W} + H gather:", t * 1e3)

    # ---- 4b. top_k alone ----
    if want("topk2"):
        def topk_only_step(i, acc):
            cg = cgain0 + i * 1e-6
            vals, sel = jax.vmap(lambda c: jax.lax.top_k(c, W))(cg)
            return acc + vals.sum() + sel.sum()

        t = timed_loop(topk_only_step, jnp.zeros(()))
        record("topk_only_ms_per_level", f"top_k {W} of {2*W} alone:", t * 1e3)

    # ---- 5. leaf segment_sum epilogue (once per tree, for scale) ----
    if want("leaf"):
        def leaf_step(i, acc):
            nd = (node0 + i) % (A_CAP + 1)
            S = jax.vmap(
                lambda nd, sc: jax.ops.segment_sum(sc, nd, num_segments=A_CAP + 1)
            )(nd, SC)
            return acc + S.sum()

        t = timed_loop(leaf_step, jnp.zeros(()))
        record("leaf_segment_sum_ms", "leaf segment_sum (per tree):", t * 1e3)

    if OUT:
        payload = {
            "metric": "deep_tree_level_profile",
            "backend": jax.default_backend(),
            "device": str(jax.devices()[0]),
            "shape": {"n": n, "d": d, "W": W, "n_bins": NB, "kk": KK,
                      "lanes": LANES},
            "iters": ITERS,
            "reps": REPS,
            "components_ms": RESULTS,
            "note": os.environ.get("PROF_NOTE", ""),
        }
        with open(OUT, "w") as f:
            json.dump(payload, f, indent=1)
        print(f"wrote {OUT}")


if __name__ == "__main__":
    main()

"""Plain reference of the GradientBoostingClassifier family's search semantics.

Binary gradient boosting on log-loss as this system documents it
(``models/trees.py::GradientBoostingClassifierKernel``,
``ops/trees.py::build_tree``, ``docs/KERNELS.md``), written out in numpy
float32 with one ``bincount`` a level, a feature and a statistic: no lanes, no kernels,
no one-hot operands (at a benchmark's size the fits are shared out over
worker processes, copies of this file on the CPU). It imports nothing of the program and takes nothing
the program has made; only the random bits come from ``jax.random``,
because the keys define the answer.

*Binning.* Per feature, the ``n_bins - 1`` interior quantiles of the whole
table (``n_bins`` = 128, fewer on a table of fewer rows), duplicates
dropped; a value's code is the number of cut points at or below it.

*A fit* on the rows of non-zero fold weight ``w`` starts from the raw score
``F0`` = the weighted log-odds of the classes, ``log(p1) - log(p0)``. Stage
``t`` of a fit with ``random_state`` r takes the key ``fold_in(PRNGKey(r),
t)``, split into a subsample key and a feature key (unused: every feature
is considered). Its row mask is ``(uniform(subsample key, n) < subsample)``
times ``w``, a Bernoulli draw a row. With ``p = sigmoid(F)`` the gradient
and hessian of a row are ``g = (y - p) mask`` and ``h = max(p (1 - p),
1e-12) mask``.

*A stage's tree* is the complete binary tree of ``max_depth`` levels, grown
level-wise. At each level every row sits in one node; the level's
histograms hold (sum g, sum h) for each (node, feature, bin), those of the
left children built from the rows that went left and those of the right
children by subtraction from the parent's. A node's best split is the
(feature, bin) of the largest gain ``G_L^2 / H_L + G_R^2 / H_R - G_P^2 /
H_P`` over the prefix sums left and right of the threshold, both sides
holding at least ``min_samples_leaf`` *in hessian units* (sum h, not rows),
the last bin excluded, the first in (feature, bin) order on a tie. A node
whose best gain is at or under 1e-7 passes its rows through to its left
child. A leaf's value is ``sum g / sum h`` of its rows, and ``F +=
learning_rate x leaf value`` for every row of the table. A split's score
is the accuracy of ``F > 0`` over its held-out rows. Its learning curve
``gmax`` holds, stage by stage, the largest ``|y - sigmoid(F)|`` over those
rows: a number that is first-order in one row's raw score, where the
accuracy moves only when a row crosses zero.

Departures from sklearn's ``GradientBoostingClassifier``, each the system's
stated algorithm: thresholds are quantile bin edges of the whole table, not
midpoints between a node's sorted values; the split search is
hessian-weighted (second-order, XGBoost's gain) where sklearn searches
``friedman_mse`` on the residuals and only sets the leaf values by a Newton
step; the row mask is a Bernoulli draw a row where sklearn draws exactly
``subsample x n`` rows; ``min_samples_leaf`` bounds a child's hessian sum,
not its row count; every tree is complete to ``max_depth`` (pass-through
nodes where sklearn stops).

``precision`` rounds the operands of the histogram contraction, the g and h
of every row, to a narrower grid before they are summed (the sums
themselves stay float32): ``bfloat16`` is what the configuration states,
since a TPU's ``DEFAULT`` dot rounds its float32 operands so; ``f32`` keeps
them whole (the CPU's dot). Leaf sums take the rows' g and h whole at every
setting, as the program's do. ``fault`` breaks the fit in a known way:
``first_order`` puts the mask in the hessian's place (gradient boosting
without the second order), ``half_stages`` stops after half the stages,
``no_subsample`` takes every training row in every stage.
"""

from __future__ import annotations

import os
from concurrent.futures import ThreadPoolExecutor

import numpy as np

# A float grid: significant bits, least normal exponent, largest value.
GRIDS = {"bfloat16": (8, -126, 3.3895e38), "float8_e4m3fn": (4, -6, 448.0)}
FAULTS = ("first_order", "half_stages", "no_subsample")

N_BINS = 128
EPS = np.float32(1e-12)
MIN_GAIN = np.float32(1e-7)


def _q(x, precision):
    """Values on the precision's grid, back in float32 (by arithmetic on
    float32 values, through no narrow type: XLA removes a cast pair)."""
    if precision == "f32":
        return x
    x = np.asarray(x, np.float32)
    bits, emin, top = GRIDS[precision]
    _, ex = np.frexp(x)  # |x| = m * 2**ex with m in [0.5, 1)
    step = np.ldexp(np.float32(1.0), np.maximum(ex - 1, emin) - (bits - 1)).astype(np.float32)
    return np.clip(np.round(x / step) * step, -top, top).astype(np.float32)


def bin_codes(X, n_bins: int):
    """codes [n, d] uint8: per-feature quantile codes of the whole table."""
    X = np.asarray(X)
    qs = np.linspace(0, 1, n_bins + 1)[1:-1]
    edges = np.quantile(X, qs, axis=0).T.astype(np.float32)  # [d, n_bins - 1]
    codes = np.empty(X.shape, np.uint8)
    for f in range(X.shape[1]):
        codes[:, f] = np.searchsorted(np.unique(edges[f]), X[:, f].astype(np.float32), side="right")
    return codes


def stage_mask(random_state: int, t: int, n: int, subsample):
    """The Bernoulli row mask of stage ``t``, [n] bool."""
    import jax

    sub_key, _feat_key = jax.random.split(jax.random.fold_in(jax.random.PRNGKey(int(random_state)), t))
    return np.asarray(jax.random.uniform(sub_key, (n,))) < np.float32(subsample)


def prior_score(y, w):
    """F0 of class 1: the weighted log-odds, each share floored as the
    program floors it."""
    w = w.astype(np.float32)
    total = np.maximum(np.sum(w, dtype=np.float32), EPS)
    share = [np.maximum(np.sum(w * (y == c), dtype=np.float32) / total, EPS) for c in (0, 1)]
    return np.float32(np.log(share[1]) - np.log(share[0]))


def grow_tree(cols, g, h, depth: int, n_bins: int, msl, precision="bfloat16"):
    """One complete tree of ``depth`` levels on the rows where ``h`` > 0;
    ``cols`` [d, n] are the codes, a feature a row. Returns (leaf [n] of
    every row of the table, leaf_val [2**depth], split_feat, split_bin
    [2**depth - 1] in breadth-first order)."""
    d, n = cols.shape
    rows = np.flatnonzero(h > 0)
    x_in = cols[:, rows]  # [d, rows]
    gq = _q(g[rows], precision).astype(np.float64)
    hq = _q(h[rows], precision).astype(np.float64)
    split_feat = np.zeros(2 ** depth - 1, np.int64)
    split_bin = np.full(2 ** depth - 1, n_bins - 1, np.int64)
    local = np.zeros(n, np.int64)  # every row's node within its level
    all_rows = np.arange(n)
    msl = np.float32(msl)
    H_prev = None
    for level in range(depth):
        m = 2 ** level
        if level == 0:
            take, at = slice(None), np.zeros(len(rows), np.int64)
        else:  # the left children's rows, at their parent's place
            loc = local[rows]
            take = np.flatnonzero(loc % 2 == 0)
            at = (loc[take] // 2) * n_bins
        size = max(m // 2, 1) * n_bins
        gt, ht = gq[take], hq[take]
        H = np.empty((max(m // 2, 1), d, n_bins, 2), np.float32)
        for f in range(d):
            idx = at + x_in[f][take]
            H[:, f, :, 0] = np.bincount(idx, weights=gt, minlength=size).reshape(-1, n_bins)
            H[:, f, :, 1] = np.bincount(idx, weights=ht, minlength=size).reshape(-1, n_bins)
        if level > 0:  # right children by subtraction
            H = np.stack([H, H_prev - H], axis=1).reshape(m, d, n_bins, 2)
        H_prev = H
        Gc = np.cumsum(H[..., 0], axis=2, dtype=np.float32)
        Hc = np.cumsum(np.maximum(H[..., 1], np.float32(0)), axis=2, dtype=np.float32)
        G_tot, H_tot = Gc[:, :, -1:], Hc[:, :, -1:]
        Gr, Hr = G_tot - Gc, H_tot - Hc
        gain = (Gc * Gc / np.maximum(Hc, EPS) + Gr * Gr / np.maximum(Hr, EPS)
                - G_tot * G_tot / np.maximum(H_tot, EPS))
        valid = (Hc >= msl) & (Hr >= msl) & (np.arange(n_bins)[None, None, :] < n_bins - 1)
        flat = np.where(valid, gain, -np.inf).astype(np.float32).reshape(m, d * n_bins)
        best = flat.argmax(axis=1)
        do = flat[np.arange(m), best] > MIN_GAIN
        bf = np.where(do, best // n_bins, 0)
        bb = np.where(do, best % n_bins, n_bins - 1)
        split_feat[m - 1: 2 * m - 1], split_bin[m - 1: 2 * m - 1] = bf, bb
        go_left = cols[bf[local], all_rows] <= bb[local]
        local = 2 * local + 1 - go_left
    leaves = 2 ** depth
    Gl = np.bincount(local[rows], weights=g[rows], minlength=leaves).astype(np.float32)
    Hl = np.bincount(local[rows], weights=h[rows], minlength=leaves).astype(np.float32)
    return local, Gl / np.maximum(Hl, EPS), split_feat, split_bin


def stage_stats(y1, F, mask, fault=None):
    """(g, h) of one stage from the raw score of class 1."""
    p = (np.float32(1.0) / (np.float32(1.0) + np.exp(-F, dtype=np.float32))).astype(np.float32)
    g = ((y1 - p) * mask).astype(np.float32)
    h = mask if fault == "first_order" else np.maximum(p * (np.float32(1.0) - p), EPS) * mask
    return g, h.astype(np.float32)


def residual_max(y1, F, w_eval):
    """The largest absolute pseudo-residual ``|y - sigmoid(F)|`` over the
    rows of non-zero held-out weight: the functional gradient's largest
    component there, first-order in one row's raw score."""
    p = (np.float32(1.0) / (np.float32(1.0) + np.exp(-F, dtype=np.float32))).astype(np.float32)
    return np.float32(np.max(np.abs(y1 - p) * (w_eval > 0)))


def fit_scores(cols, y, w, params, n_bins, precision="bfloat16", fault=None, trees=None,
               gmax=None, w_eval=None):
    """The raw score of class 1 of every row of the table after the fit on
    the rows of weight ``w`` [n]; ``cols`` [d, n] are the codes, a feature
    a row. ``trees``, a list, receives each stage's (split_feat, split_bin,
    leaf_val); ``gmax``, a list, :func:`residual_max` over the rows of
    ``w_eval`` after each stage (a fit that stops early stays where it is)."""
    n = len(y)
    y1 = (np.asarray(y) == 1).astype(np.float32)
    w = np.asarray(w, np.float32)
    stages = int(params.get("n_estimators", 100))
    depth = int(params.get("max_depth", 3))
    lr = np.float32(params.get("learning_rate", 0.1))
    subsample = 1.0 if fault == "no_subsample" else float(params.get("subsample", 1.0))
    msl = params.get("min_samples_leaf", 1)
    if isinstance(msl, float) and msl < 1:
        msl = max(1, int(msl * n))
    seed = int(params.get("random_state") or 0)
    F = np.full(n, prior_score(np.asarray(y), w), np.float32)
    for t in range(stages // 2 if fault == "half_stages" else stages):
        mask = stage_mask(seed, t, n, subsample).astype(np.float32) * w
        g, h = stage_stats(y1, F, mask, fault)
        leaf, leaf_val, sf, sb = grow_tree(cols, g, h, depth, n_bins, msl, precision)
        F = (F + lr * leaf_val[leaf]).astype(np.float32)
        if trees is not None:
            trees.append((sf, sb, leaf_val))
        if gmax is not None:
            gmax.append(residual_max(y1, F, w_eval))
    if gmax is not None:
        gmax.extend(gmax[-1:] * (stages - len(gmax)))
    return F


def _fit_job(cols, y, TW, EW, params, n_bins, precision, fault, job):
    """(score, gmax curve) of trial ``job[0]`` on split ``job[1]``."""
    p, s = params[job[0]], job[1]
    ew, gmax = EW[s].astype(np.float32), []
    F = fit_scores(cols, y, TW[s], p, n_bins, precision, fault, gmax=gmax, w_eval=ew)
    return float(np.sum(ew * ((F > 0) == (y == 1))) / np.sum(ew)), [float(v) for v in gmax]


def _in_processes(cols, y, TW, EW, params, n_bins, precision, fault, jobs, workers):
    """The fits shared out over ``workers`` copies of this file, each a
    process on the CPU (numpy's ``bincount`` holds the interpreter's lock,
    so threads run about three wide). The arrays travel as files under the
    temporary directory; a worker touches no accelerator."""
    import json
    import shutil
    import subprocess
    import sys
    import tempfile

    d = tempfile.mkdtemp(prefix="gbt_reference_")
    try:
        for name, a in (("cols", cols), ("y", np.asarray(y)), ("TW", np.asarray(TW)), ("EW", np.asarray(EW))):
            np.save(os.path.join(d, name + ".npy"), a)
        with open(os.path.join(d, "spec.json"), "w") as f:
            json.dump({"params": params, "n_bins": n_bins, "precision": precision, "fault": fault}, f)
        env = {**os.environ, "JAX_PLATFORMS": "cpu"}
        procs = [subprocess.Popen([sys.executable, os.path.abspath(__file__), d, json.dumps(jobs[w::workers])],
                                  stdout=subprocess.PIPE, text=True, env=env) for w in range(workers)]
        done = {}
        for proc in procs:
            out, _ = proc.communicate()
            if proc.returncode != 0:
                raise RuntimeError(f"a reference worker left with {proc.returncode}")
            line = next(ln for ln in out.splitlines() if ln.startswith("FITS "))
            done.update({tuple(job): (score, gmax) for job, score, gmax in json.loads(line[5:])})
        return [done[tuple(job)] for job in jobs]
    finally:
        shutil.rmtree(d, ignore_errors=True)


def _worker(d, jobs):
    import json

    cols, y, TW, EW = (np.load(os.path.join(d, name + ".npy")) for name in ("cols", "y", "TW", "EW"))
    with open(os.path.join(d, "spec.json")) as f:
        spec = json.load(f)
    fits = [(job, *_fit_job(cols, y, TW, EW, spec["params"], spec["n_bins"], spec["precision"],
                            spec["fault"], job)) for job in jobs]
    print("FITS " + json.dumps(fits), flush=True)


#: rows x stages x fits past which the fits go to worker processes (the
#: cell's 48 fits of 32 stages on 1 000 000 rows are 1.5e9; a CPU test's
#: are under 1e6)
PROCESS_WORK = 1e8


def reference(X, y, n_classes, params, splits, precision="bfloat16", fault=None):
    """Per-split accuracy ``score`` [trials, cv + 1] of each boosted model
    in ``params`` on the (training, held-out) masks ``splits``, and its
    learning curve ``gmax`` [trials, cv + 1, stages]: the largest absolute
    residual over the split's held-out rows after each stage."""
    assert int(n_classes) == 2, "the reference states the binary algorithm only"
    X, y = np.asarray(X), np.asarray(y)
    TW, EW = splits
    n_bins = min(N_BINS, max(8, len(y)))
    cols = np.ascontiguousarray(bin_codes(X, n_bins).T)
    params = [{k: (v.item() if isinstance(v, np.generic) else v) for k, v in p.items()} for p in params]
    jobs = [(i, s) for i in range(len(params)) for s in range(len(TW))]
    try:
        cpus = len(os.sched_getaffinity(0))
    except AttributeError:
        cpus = os.cpu_count() or 2
    workers = max(1, min(len(jobs), cpus - 1))
    work = float(len(y)) * sum(int(params[i].get("n_estimators", 100)) for i, _ in jobs)
    if work > PROCESS_WORK and workers > 1:
        done = _in_processes(cols, y, TW, EW, params, n_bins, precision, fault, jobs, workers)
    else:
        with ThreadPoolExecutor(max_workers=workers) as pool:
            done = list(pool.map(lambda job: _fit_job(cols, y, TW, EW, params, n_bins, precision, fault, job), jobs))
    shape = (len(params), len(TW))
    return {"score": np.asarray([d[0] for d in done], np.float32).reshape(shape),
            "gmax": np.asarray([d[1] for d in done], np.float32).reshape(shape + (-1,))}


if __name__ == "__main__":
    import json
    import sys

    _worker(sys.argv[1], [tuple(job) for job in json.loads(sys.argv[2])])

"""Plain reference of the RandomForestClassifier family's search semantics.

sklearn's ``RandomForestClassifier(max_depth=None)`` as this system
documents it (``models/trees.py``, ``ops/trees.py::build_tree_deep``,
``docs/KERNELS.md``), written out in numpy float32 with one ``bincount`` a
level: no kernels, no lanes, no arena of histograms. It imports nothing of
the program and takes nothing the program has made; only the random bits
come from ``jax.random``, because the keys define the answer.

*Binning.* Per feature, the ``n_bins - 1`` interior quantiles of the whole
table (``n_bins`` = 48 in the arena), duplicates dropped; a value's code is
the number of cut points at or below it. A feature with at most
``COARSE_BINS`` (4) codes is a *coarse* feature (a one-hot column has two),
and where at least 8 features are coarse and one is not they are
histogrammed at their own codes at every level.

*A tree* ``t`` of a forest with ``random_state`` r takes the key
``fold_in(PRNGKey(r), t)``, split into a bootstrap key and a feature key.
The bootstrap is the exact multinomial: ``n`` draws (``n`` = all rows of the
table) uniform over the rows of non-zero fold weight, counts capped at 127.
A node with arena id ``i`` may split on the ``max_features`` features whose
uniforms, drawn from ``fold_in(feature key, i)``, are the smallest.

*Growth* is level-wise over a frontier of at most ``width(level)`` nodes.
A node's best split is the (feature, bin) of the largest gain
``sum_k L_k^2 / C_L + sum_k R_k^2 / C_R - sum_k P_k^2 / C_P`` over the
bootstrap-weighted class counts left and right of the threshold, both sides
holding at least ``min_samples_leaf`` weighted rows, the last bin excluded;
a gain that does not pass 2^-19 of the parent's own ``sum_k P_k^2 / C_P``
counting as 0 (float32 noise: a pure node's gain is 0 only where ``x^2 / x``
is ``x`` to the last bit, which the chip's division does not promise);
gains compared on 12 bits of mantissa (the low 11 cleared), so that one
exact gain reached through different roundings compares equal and position
decides: first in (fine features by id, then coarse features by id; bin)
order on a tie. Frontier nodes whose gain passes 1e-7 split; their children
take ids ``n_alloc + 2 * rank`` (left) and one more (right) in frontier
order. The next frontier is the ``min(2 W, width(level + 1))`` children of
the largest own best gain, equal gains at the cut going to the earlier
candidates, kept in candidate order (left children in frontier order, then
right children); the rest are leaves. While ``2 W`` is below ``occ`` (256) candidates
are scored on all 48 thresholds, from then on on every third (16 bins;
coarse features always on their own); the recorded threshold stays in fine
codes. After ``levels`` rounds every node left is a leaf, its value the
weighted class shares of its in-bag rows. A forest's answer for a row is
the class of the largest mean leaf value; a split's score the accuracy over
its held-out rows.

*The schedule* follows ``resolve_static``'s documented bands: ``levels`` =
min(24, ceil(log2 n) + 8); width 64 / 128 / 256 / 1024 / 1536 for n up to
5 000 / 24 576 / 49 152 / 80 000 / beyond; above 80 000 rows the deep
resolution is 16 bins and the width falls to 512 from level 17 (1024 wide:
from level 16).

Departures from sklearn's exact CART, each the system's stated algorithm:
thresholds are quantile bin edges, not midpoints between sorted values (a
category rarer than one row in 48 shares its code with the rest of its
column and cannot be split off); the gain is S^2/C, which orders splits as
gini does; growth stops at ``levels`` and at the frontier's width, so the
leaves past the budget are impure; the bootstrap draws ``n`` rows, not
``n_train``; feature subsets are per node id, not per visit. Below
``CS230_TREE_DEEP_N`` rows the program uses a complete-tree builder this
file does not describe.

``precision`` holds the class-count statistics in a narrower type wherever
the fit keeps them as numbers: each row's weighted one-hot (the histogram's
operand), and the accumulator of every histogram cell and of every leaf's
class sums, the running sum back on the grid after each row's add. The
stated precision keeps all of them exact. With bootstrap counts under 16 the
operand is exact on every grid here; a narrow accumulator is not: it stalls
once an add is under half its spacing, so every large cell reads alike. ``fault``
breaks the fit in a known way: ``no_feature_subsets`` lets every node split
on every feature, ``half_trees`` leaves the second half of the trees out of
the vote.
"""

from __future__ import annotations

import os
from concurrent.futures import ThreadPoolExecutor

import numpy as np

# A float grid: significant bits, least normal exponent, largest value.
GRIDS = {"bfloat16": (8, -100, 3.3895e38), "float8_e4m3fn": (4, -6, 448.0)}
FAULTS = ("no_feature_subsets", "half_trees")

N_BINS, FINE_BINS, COARSE_BINS, OCC = 128, 48, 4, 256
EPS = np.float32(1e-12)
GAIN_NOISE = np.float32(2.0 ** -19)


def _q(x, precision):
    """Values on the precision's grid, back in float32 (by arithmetic on
    float32 values, through no narrow type); beyond the grid's largest
    value they stay there."""
    if precision == "f32":
        return x
    x = np.asarray(x, np.float32)
    if precision == "int8":
        return np.clip(np.round(x), -127.0, 127.0).astype(np.float32)
    bits, emin, top = GRIDS[precision]
    _, ex = np.frexp(x)  # |x| = m * 2**ex with m in [0.5, 1)
    step = np.ldexp(np.float32(1.0), np.maximum(ex - 1, emin) - (bits - 1)).astype(np.float32)
    return np.clip(np.round(x / step) * step, -top, top).astype(np.float32)


def _rank(g):
    """A float32 gain as it is compared: its low 11 mantissa bits cleared."""
    return (np.ascontiguousarray(g, np.float32).view(np.int32) & np.int32(-2048)).view(np.float32)


def accumulate(idx, w, size: int, precision):
    """Sum of ``w`` into the cells ``idx`` of an accumulator of ``size``
    cells held in ``precision``: in row order, the running sum back on the
    grid after every add (a narrow accumulator stalls once an add is under
    half its spacing: 1 into 16 on the e4m3 grid, into 256 on bfloat16)."""
    if precision == "f32":
        return np.bincount(idx, weights=w, minlength=size).astype(np.float32)
    w = _q(w, precision)
    order = np.argsort(idx, kind="stable")
    idx, w = idx[order], w[order]
    first = np.flatnonzero(np.r_[True, idx[1:] != idx[:-1]])
    rank = np.arange(len(idx)) - np.repeat(first, np.diff(np.r_[first, len(idx)]))
    by_rank = np.argsort(rank, kind="stable")  # every cell's r-th add, r by r
    idx, w = idx[by_rank], w[by_rank]
    ends = np.cumsum(np.bincount(rank))
    # past this value no add of this call can move a cell any more
    wmax = max(float(w.max(initial=1.0)), 1.0)
    stalled = 127.0 if precision == "int8" else min(
        GRIDS[precision][2], 2.0 ** (GRIDS[precision][0] + int(np.log2(wmax)) + 1))
    acc = np.zeros(size, np.float32)
    for a, b in zip(np.r_[0, ends[:-1]], ends):
        live = acc[idx[a:b]] < stalled
        if not live.any():
            break  # a cell's later adds come after its earlier ones
        cells = idx[a:b][live]
        acc[cells] = _q(acc[cells] + w[a:b][live], precision)
    return acc


def schedule(n: int):
    """The arena's shape for a table of ``n`` rows: levels, the frontier's
    width at each level, the fine and deep bin counts."""
    levels = min(24, int(np.ceil(np.log2(max(n, 8)))) + 8)
    width = 64 if n <= 5000 else 128 if n <= 24576 else 256 if n <= 49152 else \
        1024 if n <= 80000 else 1536
    deep_cap = FINE_BINS if width < 1024 else 24 if width < 1536 else 16
    fine = min(N_BINS, max(8, n), FINE_BINS)
    deep = min(fine, deep_cap, 24)
    occ = 384 if width == 256 else OCC
    if not (deep < fine and fine % deep == 0):
        fine, deep = min(fine, deep_cap), min(fine, deep_cap)
    if n > 80000:
        split_at, low = (17, 512) if width >= 1536 else (16, width // 2)
    else:
        split_at, low = levels, width
    return {"levels": levels, "width": max(width, low), "fine": fine, "deep": deep, "occ": occ,
            "width_at": lambda lvl: width if lvl < split_at else low}


def bin_codes(X, n_bins: int):
    """(codes [n, d] uint8, fine [d] bool): quantile codes, and which
    features follow the level's resolution (the others keep their own)."""
    X = np.asarray(X)
    qs = np.linspace(0, 1, n_bins + 1)[1:-1]
    edges = np.quantile(X, qs, axis=0).T.astype(np.float32)  # [d, n_bins - 1]
    codes = np.empty(X.shape, np.uint8)
    n_codes = np.empty(X.shape[1], np.int64)
    for f in range(X.shape[1]):
        cuts = np.unique(edges[f])
        codes[:, f] = np.searchsorted(cuts, X[:, f].astype(np.float32), side="right")
        n_codes[f] = 1 + len(cuts)
    coarse = n_codes <= COARSE_BINS
    grouped = coarse.sum() >= 8 and (~coarse).sum() >= 1
    return codes, (~coarse if grouped else np.ones(X.shape[1], bool))


def tree_keys(random_state: int, t: int):
    import jax

    return jax.random.split(jax.random.fold_in(jax.random.PRNGKey(int(random_state)), t))


def bootstrap_counts(boot_key, active):
    """n draws with replacement, uniform over the rows where ``active``."""
    import jax

    n = len(active)
    caw = np.cumsum(active.astype(np.int64))
    targets = np.asarray(jax.random.randint(boot_key, (n,), 1, max(int(caw[-1]), 1) + 1))
    rows = np.searchsorted(caw, targets, side="left")
    return np.minimum(np.bincount(rows, minlength=n), 127).astype(np.float32)


def node_features(feat_key, n_nodes: int, d: int, mf: int, fine):
    """(F [n_nodes, w] feature ids, ok [n_nodes, w]): the features node id
    ``i`` may split on, fine features first and by id within a kind."""
    import jax

    order = np.argsort(np.argsort(np.where(fine, 0, d) + np.arange(d), kind="stable"))
    if mf >= d:
        F = np.broadcast_to(np.argsort(order)[None, :], (n_nodes, d))
        return F, np.ones(F.shape, bool)
    draw = jax.jit(jax.vmap(lambda i: jax.random.uniform(jax.random.fold_in(feat_key, i), (d,))))
    u = np.asarray(draw(np.arange(n_nodes, dtype=np.int32)))
    allowed = u <= np.sort(u, axis=1)[:, mf - 1: mf]
    w = int(allowed.sum(1).max())
    F = np.argsort(np.where(allowed, order[None, :], d + np.arange(d)[None, :]), axis=1)[:, :w]
    return F, np.take_along_axis(allowed, F, axis=1)


def grow_tree(codes, fine, y, counts, F, ok, sched, msl, k, precision="f32"):
    """One tree on the rows of non-zero ``counts``; returns (node [n] the
    leaf every row of the table ends in, leaf_val [ids, k], and the arena's
    split records (feature, threshold, left child) by node id)."""
    n, d = codes.shape
    levels, fine_nb = sched["levels"], sched["fine"]
    A = 2 * sched["width"] * levels + 2
    feat_a = np.zeros(A + 1, np.int64)
    bin_a = np.zeros(A + 1, np.int64)
    child_a = np.zeros(A + 1, np.int64)
    node = np.zeros(n, np.int64)
    inb = np.flatnonzero(counts > 0)
    x_in, y_in = codes[inb], y[inb].astype(np.int64)
    c_in = counts[inb]
    msl = np.float32(msl)
    all_rows = np.arange(n)

    def best_splits(cand, res):
        """Best (gain, feature, fine threshold) of each candidate id."""
        m, w = len(cand), F.shape[1]
        local = np.full(A + 2, -1, np.int64)
        local[cand] = np.arange(m)
        loc = local[node[inb]]
        rows = np.flatnonzero(loc >= 0)
        loc = loc[rows]
        Fc, okc = F[cand], ok[cand]
        Fr = Fc[loc]  # [R, w]
        ratio = fine_nb // res
        cd = np.take_along_axis(x_in[rows], Fr, axis=1).astype(np.int64)
        if ratio > 1:
            cd = np.where(fine[Fr], cd // ratio, cd)
        idx = ((loc[:, None] * w + np.arange(w)[None, :]) * res + cd) * k + y_in[rows][:, None]
        H = accumulate(idx.ravel(), np.repeat(c_in[rows], w), m * w * res * k,
                       precision).reshape(m, w, res, k)
        Scum = np.cumsum(H, axis=2, dtype=np.float32)
        Ccum = Scum.sum(-1, dtype=np.float32)
        S_tot, C_tot = Scum[:, :, -1:, :], Ccum[:, :, -1:]
        Sr, Cr = S_tot - Scum, C_tot - Ccum
        gain = ((Scum * Scum).sum(-1, dtype=np.float32) / np.maximum(Ccum, EPS)
                + (Sr * Sr).sum(-1, dtype=np.float32) / np.maximum(Cr, EPS))
        parent = (S_tot * S_tot).sum(-1, dtype=np.float32) / np.maximum(C_tot, EPS)
        last = np.where(fine[Fc], res, COARSE_BINS)[:, :, None] - 1  # the degenerate bin
        valid = (Ccum >= msl) & (Cr >= msl) & (np.arange(res)[None, None, :] < last) \
            & okc[:, :, None]
        g = gain - parent
        g = np.where(g > GAIN_NOISE * parent, g, np.float32(0.0))
        flat = _rank(np.where(valid, g, -np.inf).astype(np.float32)).reshape(m, w * res)
        best = flat.argmax(axis=1)
        at = np.arange(m)
        feat = Fc[at, best // res]
        b = best % res
        return flat[at, best], feat, np.where(fine[feat], (b + 1) * ratio - 1, b)

    def res_at(cand_w):
        return fine_nb if cand_w < sched["occ"] else sched["deep"]

    frontier = np.zeros(1, np.int64)
    res = res_at(2)
    gain, bf, bb = best_splits(frontier, res)
    n_alloc, W = 1, 1
    for level in range(levels):
        do = gain > np.float32(1e-7)
        do &= n_alloc + 2 * np.cumsum(do) <= A
        left = n_alloc + 2 * (np.cumsum(do) - do)
        split, left = frontier[do], left[do]
        feat_a[split], bin_a[split], child_a[split] = bf[do], bb[do], left
        c = child_a[node]
        go_left = codes[all_rows, feat_a[node]] <= bin_a[node]
        node = np.where(c > 0, c + 1 - go_left, node)
        n_alloc += 2 * len(split)
        if level == levels - 1 or not len(split):
            break
        cand = np.concatenate([left, left + 1])
        res = min(res, res_at(2 * W))
        cgain, cbf, cbb = best_splits(cand, res)
        W = min(2 * W, sched["width_at"](level + 1))
        keep = np.arange(len(cand))
        if W < len(cand):
            cut = np.sort(cgain)[-W]
            above, at = cgain > cut, cgain == cut
            keep = np.flatnonzero(above | (at & (np.cumsum(at) <= W - above.sum())))
        keep = keep[cgain[keep] > -np.inf]
        frontier, gain, bf, bb = cand[keep], cgain[keep], cbf[keep], cbb[keep]

    leaf_S = accumulate(node[inb] * k + y_in, c_in, (A + 1) * k, precision).reshape(A + 1, k)
    leaf_val = leaf_S / np.maximum(leaf_S.sum(1, dtype=np.float32), EPS)[:, None]
    return node, leaf_val, (feat_a, bin_a, child_a)


def resolve(params, n: int, d: int):
    assert params.get("max_depth") is None, "the reference grows to purity only"
    mf = params.get("max_features", "sqrt") or "sqrt"
    if mf == "sqrt":
        mf = max(1, int(np.sqrt(d)))
    elif mf == "log2":
        mf = max(1, int(np.log2(max(d, 2))))
    elif isinstance(mf, float) and 0 < mf <= 1:
        mf = max(1, int(mf * d))
    msl = params.get("min_samples_leaf", 1)
    if isinstance(msl, float) and msl < 1:
        msl = max(1, int(msl * n))
    return max(1, min(int(mf), d)), float(msl)


def reference(X, y, n_classes, params, splits, precision="f32", fault=None):
    """Per-split accuracy [trials, cv + 1] of each forest in ``params`` on
    the (training, held-out) masks ``splits``."""
    X, y = np.asarray(X), np.asarray(y)
    TW, EW = splits
    n, d = X.shape
    k = max(int(n_classes), 2)
    sched = schedule(n)
    n_ids = 2 * sched["width"] * sched["levels"] + 3
    codes, fine = bin_codes(X, sched["fine"])

    # What trials and splits share, made once: a tree's bootstrap depends on
    # (random_state, tree, split), its nodes' feature subsets on
    # (random_state, tree, max_features).
    forests, boots, feats = [], {}, {}
    for p in params:
        mf, msl = resolve(p, n, d)
        if fault == "no_feature_subsets":
            mf = d
        seed, T = int(p.get("random_state") or 0), int(p.get("n_estimators", 100))
        forests.append((seed, T, mf, msl))
        for t in range(T):
            boot_key, feat_key = tree_keys(seed, t)
            for s in range(len(TW)):
                if (seed, t, s) not in boots:
                    boots[seed, t, s] = bootstrap_counts(boot_key, TW[s] > 0) \
                        if p.get("bootstrap", True) else (TW[s] > 0).astype(np.float32)
            if (seed, t, mf) not in feats:
                feats[seed, t, mf] = node_features(feat_key, n_ids, d, mf, fine)

    def forest_score(job):
        (seed, T, mf, msl), s = forests[job[0]], job[1]
        votes = np.zeros((n, k), np.float32)
        for t in range(T // 2 if fault == "half_trees" else T):
            node, leaf_val, _ = grow_tree(codes, fine, y, boots[seed, t, s], *feats[seed, t, mf],
                                          sched, msl, k, precision)
            votes += leaf_val[node]
        pred = np.argmax(votes / np.float32(T), axis=1)
        ew = EW[s].astype(np.float32)
        return float(np.sum(ew * (pred == y)) / np.sum(ew))

    jobs = [(i, s) for i in range(len(params)) for s in range(len(TW))]
    with ThreadPoolExecutor(max_workers=max(1, min(6, (os.cpu_count() or 2) - 1))) as pool:
        scores = list(pool.map(forest_score, jobs))
    return {"score": np.asarray(scores, np.float32).reshape(len(params), len(TW))}

"""Histogram decision-tree builder: the TPU-native tree-learning core.

Replaces the sklearn tree fits the reference workers run per trial
(RandomForest*/GradientBoosting* rows of the whitelist,
``aws-prod/worker/worker.py:38-52``). sklearn's exact, depth-first,
sorted-split CART is sequential and pointer-chasing — the histogram
formulation (LightGBM-style) is the TPU shape of the same computation:

- features are pre-binned once per dataset into ``n_bins`` quantile bins
  (int codes), so a split candidate is (feature, bin);
- trees grow **level-wise** over a complete binary tree of static depth:
  at level l every sample sits at one of 2^l nodes, and all node×feature×bin
  histograms are built as one-hot matmul contractions on the MXU
  (``_level_histogram``; TPU scatters serialize, matmuls don't), with the
  right-child histograms derived by subtraction from the parent level,
  followed by a cumulative sum over bins;
- the split score is the unified proxy ``sum_k S_k^2 / C`` (left+right),
  which instantiates to variance gain (regression, S=sum y, C=count), gini
  gain (classification, S=class counts), and the Newton gain
  (boosting, S=grad sums, C=hess sums) — one builder serves RF and GBT;
- nodes that can't split become pass-through (route everything left), so
  shapes never depend on data.

Everything is jittable and vmappable over trials; per-node random feature
subsets (RF's max_features) use threshold-masked uniforms.
"""

from __future__ import annotations

import os
from functools import partial
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..utils import backend as _backend

_EPS = 1e-12


def quantile_bins(X: np.ndarray, n_bins: int) -> np.ndarray:
    """Host-side: per-feature bin edges (n_bins-1 interior cutpoints) from
    quantiles of the full dataset. Computed once per dataset+n_bins and
    shared by every trial/fold (the reference re-reads and re-sorts data
    per subtask; here binning is a one-time cost).

    Duplicate quantiles (low-cardinality features — e.g. one-hot columns,
    where most quantiles coincide) are DEDUPED per feature and the tail
    padded with +inf: the distinct cut set is unchanged (identical split
    candidates), but bin codes become compact ([0, n_distinct]), which is
    what lets the deep builder histogram low-cardinality features in a
    narrow-bin group (see build_tree_deep ``groups``)."""
    qs = np.linspace(0, 1, n_bins + 1)[1:-1]
    edges = np.quantile(X, qs, axis=0).T  # [d, n_bins-1]
    out = np.full(edges.shape, np.inf, np.float32)
    for f in range(edges.shape[0]):
        u = np.unique(edges[f])  # sorted, deduped
        out[f, : len(u)] = u
    return np.ascontiguousarray(out)


@jax.jit
def _bin_data_impl(X, edges):
    return jax.vmap(
        lambda col, e: jnp.searchsorted(e, col, side="right"), in_axes=(1, 0), out_axes=1
    )(X, edges).astype(jnp.int32)


def bin_data(X, edges) -> jnp.ndarray:
    """Map raw features to bin codes with per-column searchsorted (jitted:
    one cached executable per dataset shape, not per-primitive dispatches)."""
    return _bin_data_impl(jnp.asarray(X, jnp.float32), jnp.asarray(edges, jnp.float32))


_HIST_ROW_CHUNK = 16384


def _hist_kernel_mode() -> str:
    """CS230_HIST_KERNEL valve over the level-histogram implementations:

    - ``matmul``  — the XLA one-hot matmul contraction below (both 0/1
      operands materialize in HBM; the left one's columns are stat-major
      since PR 33, so nothing re-tiles it on the way to the dot);
    - ``pallas``  — the fused Pallas kernel (ops/pallas_hist.py): one-hot
      tiles built in VMEM, accumulator page resident across row tiles;
    - ``scatter`` — the literal bin-and-scatter segment-sum form
      (O(n*d*kk) adds; the fast form without an MXU);
    - ``auto`` (default) — scatter on CPU, matmul otherwise. On the v5e
      the matmul form (s8 operands for integer stats) beat the Pallas
      kernel by 3.4 times over a whole Covertype forest search (45.8 s
      against 157.2 s, 232 405 rows, frontier 1536; PR 32): the kernel
      rebuilds a row tile's bin one-hot for each of a level's 64-node
      blocks and feeds the MXU 256-row contractions. It stays behind the
      valve until it reads each row tile once.

    The valve is read at trace time and keyed into every executable cache
    via the tree kernels' ``trace_salt``.
    """
    mode = os.environ.get("CS230_HIST_KERNEL", "auto").lower()
    return mode if mode in ("auto", "matmul", "scatter", "pallas") else "auto"


def _resolve_hist_kernel(integer_stats: bool, ds, n_binss, kk: int) -> str:
    mode = _hist_kernel_mode()
    if mode != "auto":
        return mode
    if _backend.on_cpu():
        return "scatter"
    return "matmul"  # float stats keep the HIGHEST-precision contraction


def _level_histogram_multi(local, xbs, SC, n_nodes: int, n_binss,
                           precision=None, integer_stats: bool = False):
    """:func:`_level_histogram_forms` under the ``level_histogram`` scope:
    every form's ops carry that name in a device trace (the Pallas kernel's
    own name is the same), so a reader finds the histograms whatever
    computes them."""
    with jax.named_scope("level_histogram"):
        return _level_histogram_forms(
            local, xbs, SC, n_nodes, n_binss, precision, integer_stats)


def _level_histogram_forms(local, xbs, SC, n_nodes: int, n_binss,
                           precision=None, integer_stats: bool = False):
    """Feature-grouped level histograms in ONE row scan: a tuple of
    [n_nodes, d_g, nb_g, kk] histograms, one per (xb_g, nb_g) feature group.

    Computed as (one_hot(node) ⊗ SC)ᵀ @ one_hot(bins_g) over row chunks: two
    0/1 one-hot operands make the contraction a pure MXU matmul, replacing
    segment-sum scatters (which serialize on TPU and dominated tree-fit time
    ~10-30x). Rows stream through a lax.scan so peak memory is
    O(row_chunk · (n_nodes·kk + sum d_g·nb_g)) regardless of n.

    The left operand T1 = one_hot(node) ⊗ SC ([row_chunk, kk*n_nodes], the
    histogram's dominant memory-traffic term at wide frontiers) is built
    ONCE per chunk and contracted against every group's bin one-hot — this
    is why grouped histograms fuse into one scan instead of calling a
    single-group kernel per group (an A/B of the two-scan form measured NO
    win: the duplicated T1 traffic ate the narrower matmuls' savings).

    Its columns are **stat-major** (column ``stat * n_nodes + node``): one
    [row_chunk, n_nodes] slab a stat, concatenated, which the TPU compiler
    writes slab by slab straight into the layout the dots read, and the
    accumulators' rows keep that order until the histograms leave this
    function. Until PR 33 the columns were node-major (``node * kk + stat``,
    from a [row_chunk, n_nodes, kk] product reshaped): the compiler put the
    kk = 7 stats on sublanes, padded to a tile, and re-tiled the whole
    operand with a ``reshape`` op of its own before the dots, a second write
    and read of it in every row chunk of every level: 14.5 s of a 46.8 s
    Covertype forest search on the v5e and 1.1 GB of its peak memory
    (PERF.md section 6, PR 33; tests/test_tpu_compile.py pins the compiled
    row loop). The same order built as one rows-minor product,
    ``(SC.T[:, None, :] * one_hot(node).T[None]).reshape(kk * n_nodes,
    row_chunk)``, was 1.9 s a search slower and, though bit-equal alone,
    gave wrong trees inside the compiled step program on the chip (PERF.md,
    the same section): do not go back to it without that check.

    ``integer_stats``: the stat columns are small non-negative integers
    (< 128 — classification one-hots times bootstrap counts, which
    _bootstrap_counts caps): run the contraction as s8 x s8 -> s32 on the
    MXU (2x the bf16 rate on v5e), bit-exact by construction.

    The CS230_HIST_KERNEL valve (see ``_hist_kernel_mode``) can replace
    this whole contraction with the fused Pallas kernel or the
    bin-and-scatter segment-sum form — all three share the contract and
    the parity guarantees pinned in tests/test_pallas_hist.py.
    """
    n = xbs[0].shape[0]
    ds = tuple(xb.shape[1] for xb in xbs)
    kk = SC.shape[1]
    kern = _resolve_hist_kernel(integer_stats, ds, n_binss, kk)
    if kern == "scatter":
        from .pallas_hist import level_histogram_scatter

        return tuple(
            level_histogram_scatter(local, xb, SC, n_nodes, nb)
            for xb, nb in zip(xbs, n_binss)
        )
    if kern == "pallas":
        from .pallas_hist import level_histogram_pallas

        interp = _backend.pallas_interpret()
        return tuple(
            level_histogram_pallas(
                local, xb, SC, n_nodes, nb,
                integer_stats=integer_stats, interpret=interp,
            )
            for xb, nb in zip(xbs, n_binss)
        )
    rc = min(_HIST_ROW_CHUNK, n)
    n_pad = ((n + rc - 1) // rc) * rc
    if n_pad != n:
        # padded rows carry zero stats — they land in node 0/bin 0 cells
        # with zero contribution
        local = jnp.pad(local, (0, n_pad - n))
        xbs = tuple(jnp.pad(xb, ((0, n_pad - n), (0, 0))) for xb in xbs)
        SC = jnp.pad(SC, ((0, n_pad - n), (0, 0)))

    # Integer stats under DEFAULT precision ride the s8 MXU path (2x bf16
    # rate on v5e), exact by construction: 0/1 one-hots pick single <128
    # terms, accumulation in s32. Float stats keep their dtype — TPU's
    # in-dot DEFAULT truncation applies there, but an explicit bf16 cast
    # would ALSO degrade CPU/GPU backends (where DEFAULT is full f32).
    int8_path = bool(integer_stats) and precision in (
        None, jax.lax.Precision.DEFAULT
    )
    op_dt = jnp.int8 if int8_path else SC.dtype
    acc_dt = jnp.int32 if int8_path else jnp.float32

    def body(Hs, start):
        lb = jax.lax.dynamic_slice(local, (start,), (rc,))
        SCb = jax.lax.dynamic_slice(SC, (start, 0), (rc, kk)).astype(op_dt)
        N = jax.nn.one_hot(lb, n_nodes, dtype=op_dt)  # [rc, nodes]
        # stat-major: one [rc, nodes] slab a stat, written side by side into
        # the layout the dots read (the docstring says why)
        T1 = jnp.concatenate([N * SCb[:, j:j + 1] for j in range(kk)], axis=1)
        out = []
        for H, xb, d, n_bins in zip(Hs, xbs, ds, n_binss):
            xbb = jax.lax.dynamic_slice(xb, (start, 0), (rc, d))
            B = (
                xbb[:, :, None]
                == jnp.arange(n_bins, dtype=xbb.dtype)[None, None, :]
            ).astype(op_dt).reshape(rc, d * n_bins)
            out.append(H + jnp.dot(
                T1.T,
                B,
                precision=None if int8_path else precision,
                preferred_element_type=acc_dt,
            ))
        return tuple(out), None

    H0 = tuple(
        jnp.zeros((n_nodes * kk, d * n_bins), acc_dt)
        for d, n_bins in zip(ds, n_binss)
    )
    starts = jnp.arange(0, n_pad, rc, dtype=jnp.int32)
    Hs, _ = jax.lax.scan(body, H0, starts)
    # rows are stat-major over nodes; cols feature-major over bins
    return tuple(
        H.astype(jnp.float32).reshape(kk, n_nodes, d, n_bins).transpose(
            1, 2, 3, 0
        )
        for H, d, n_bins in zip(Hs, ds, n_binss)
    )


def _level_histogram(local, xb, SC, n_nodes: int, n_bins: int, precision=None,
                     integer_stats: bool = False):
    """Single-group form of ``_level_histogram_multi`` (same contract as
    always: [n_nodes, d, n_bins, kk])."""
    return _level_histogram_multi(
        local, (xb,), SC, n_nodes, (n_bins,), precision, integer_stats
    )[0]


#: compact-histogram geometry (sparsity-exploiting level histograms below).
#: R rows per block, M one-hot node columns per block; arithmetic shrinks
#: by ~W/M relative to the dense one-hot form. Env-tunable for sweeps.
#:
#: MEASURED NEGATIVE RESULT (kept off by default, r3 A/B on v5e, 25%
#: Covertype RF: dense 87 ms vs compact 107 ms per tree-split): the W-fold
#: arithmetic redundancy of the dense one-hot matmul is CHEAPER on the MXU
#: than the row movement compaction needs — one [n]-row sort + two row
#: gathers cost ~2 ms/level/lane, more than the entire dense histogram
#: matmul they replace (~2 ms at peak). FLOPs are free; data movement
#: isn't. The kernel stays for narrow-MXU parts / future sweeps
#: (CS230_HIST_COMPACT=1), exactness covered by tests.
_COMPACT_R = int(os.environ.get("CS230_HIST_BLOCK_ROWS", "2048"))
_COMPACT_M = int(os.environ.get("CS230_HIST_BLOCK_NODES", "64"))
_COMPACT_ENABLE = os.environ.get("CS230_HIST_COMPACT", "0") == "1"


def _level_histogram_compact(local, xb, SC, n_nodes: int, n_bins: int,
                             precision=None, integer_stats: bool = False):
    """Sparsity-exploiting level histogram: same contract as
    ``_level_histogram`` ([n_nodes, d, n_bins, kk] from per-row stats), but
    ~W/M less arithmetic for wide frontiers.

    The dense form pays ``n x n_nodes`` one-hot work although each row
    belongs to exactly ONE node — a W-fold redundancy at the deep arena's
    W=256 (VERDICT r2 weak #2). This kernel compacts rows per node first
    (the LightGBM-style layout, rebuilt for static XLA shapes):

    1. sort rows by node id (dead rows, ``local == n_nodes``, sort last);
    2. rank each row by its node's *distinct index* in sorted order, and
       split ranks into supergroups of M distinct nodes; pad the sorted
       layout so every R-row block holds rows of ONE supergroup — then
       every block sees at most M distinct nodes BY CONSTRUCTION (no
       data-dependent fallback; at most ceil((n_nodes+1)/M) supergroups
       exist, so padding is bounded by K*R rows, all static);
    3. per block, contract a *narrow* one-hot ``[R, M*kk]`` against the
       bin one-hot ``[R, d*n_bins]`` on the MXU (this is where the W/M
       saving lives);
    4. route each block's M mini-rows to their global node rows with a
       small ``one_hot(slot_of) @ mini`` matmul (scatter-free).

    All steps are gathers, cumsums, and matmuls — no scatter, no cond —
    so the kernel vmaps over (trials, splits, trees) like the dense form.
    """
    n, d = xb.shape
    kk = SC.shape[1]
    R, M = _COMPACT_R, _COMPACT_M
    K = (n_nodes + 1 + M - 1) // M  # supergroups (incl. the dead id)
    n_blocks = (n + R - 1) // R + K  # upper bound incl. supergroup padding
    n_pad = n_blocks * R
    dt = jnp.bfloat16 if (n_bins <= 256 and precision in
                          (None, jax.lax.Precision.DEFAULT)) else jnp.float32

    # ---- 1. sort rows by node ----
    perm = jnp.argsort(local)
    sl = local[perm]

    # ---- 2. distinct-rank, supergroups, padded layout ----
    change = jnp.concatenate(
        [jnp.zeros((1,), jnp.int32), (sl[1:] != sl[:-1]).astype(jnp.int32)]
    )
    drank = jnp.cumsum(change)  # [n] global distinct index of each row
    sg = drank // M  # supergroup of each sorted row, < K
    # s[k] = first sorted index of supergroup k (n if absent)
    s = jnp.searchsorted(sg, jnp.arange(K + 1, dtype=jnp.int32), side="left")
    c = s[1:] - s[:-1]  # rows per supergroup
    padded_len = ((c + R - 1) // R) * R
    t = jnp.concatenate(
        [jnp.zeros((1,), jnp.int32), jnp.cumsum(padded_len)]
    )  # padded start of each supergroup

    # source index for every padded position (gather form — no scatter)
    p = jnp.arange(n_pad, dtype=jnp.int32)
    k_p = jnp.clip(
        jnp.searchsorted(t, p, side="right") - 1, 0, K - 1
    )
    src = p - t[k_p] + s[k_p]
    valid = (src < s[k_p + 1]) & (p < t[K])
    src = jnp.where(valid, src, 0)

    # ---- gather the padded layout ----
    take = jnp.where(valid, perm[src], 0)
    xbs = jnp.take(xb, take, axis=0).astype(dt)  # [n_pad, d] codes
    SCs = jnp.where(
        valid[:, None], jnp.take(SC, take, axis=0), 0.0
    ).astype(dt)
    # block-local node rank, < M by construction
    loc = jnp.where(valid, drank[src] - M * k_p, M - 1)

    # ---- 3+4a. per-block narrow one-hot contraction, accumulated into
    # supergroup pages as we go. A block's m-th one-hot column is its
    # supergroup's distinct rank k*M + m — a GLOBAL coordinate — so each
    # block's mini histogram can be added straight onto its supergroup's
    # [M*kk, d*n_bins] page (dynamic_update_slice accumulate under scan).
    # Doing the block matmuls one-at-a-time this way keeps the working set
    # at one page instead of materializing the full [nb, M*kk, d*n_bins]
    # tensor (~750 MB/level at production shapes, profiled as the top
    # fusion cost of the naive form).
    locb = loc.reshape(n_blocks, R)
    xbsb = xbs.reshape(n_blocks, R, d)
    SCsb = SCs.reshape(n_blocks, R, kk)
    sg_of_block = k_p.reshape(n_blocks, R)[:, 0]  # [nb]

    def block_body(acc, args):
        lb, xbb, SCb, sg = args
        N = jax.nn.one_hot(lb, M, dtype=dt)  # [R, M]
        T1 = (N[:, :, None] * SCb[:, None, :]).reshape(R, M * kk)
        B = (
            xbb[:, :, None] == jnp.arange(n_bins, dtype=dt)[None, None, :]
        ).astype(dt).reshape(R, d * n_bins)
        page = jax.lax.dot_general(
            T1, B, (((0,), (0,)), ((), ())),
            precision=precision, preferred_element_type=jnp.float32,
        )  # [M*kk, d*n_bins]
        upd = jax.lax.dynamic_slice(
            acc, (sg, 0, 0), (1, M * kk, d * n_bins)
        ) + page[None]
        return jax.lax.dynamic_update_slice(acc, upd, (sg, 0, 0)), None

    acc0 = jnp.zeros((K, M * kk, d * n_bins), jnp.float32)
    acc, _ = jax.lax.scan(
        block_body, acc0, (locb, xbsb, SCsb, sg_of_block)
    )
    mini_sg = acc.reshape(K * M, kk, d * n_bins)
    # node id of global distinct rank q = sl at the first row with drank==q
    q = jnp.arange(K * M, dtype=jnp.int32)
    first = jnp.searchsorted(drank, q, side="left")
    nid = jnp.where(
        (first < n) & (jnp.take(drank, jnp.minimum(first, n - 1)) == q),
        jnp.take(sl, jnp.minimum(first, n - 1)),
        n_nodes,
    )
    route = jax.nn.one_hot(nid, n_nodes, dtype=jnp.float32)  # [K*M, W]
    H = jnp.einsum(
        "qw,qkx->wkx",
        route,
        mini_sg,
        precision=jax.lax.Precision.HIGHEST,
    )
    return H.reshape(n_nodes, kk, d, n_bins).transpose(0, 2, 3, 1)


def _use_compact(n: int, n_nodes: int) -> bool:
    """Static gate: compaction wins when the frontier is wider than the
    block one-hot (arithmetic shrinks ~n_nodes/M) and the data is large
    enough that the K*R padding overhead is amortized."""
    return (
        _COMPACT_ENABLE
        and n_nodes > 2 * _COMPACT_M
        and n >= 8 * _COMPACT_R
    )


#: a gain under this share of the parent's own score is float32 noise, not
#: a split (16 ulps). The deep builder snaps it to zero: a pure node's gain
#: is L^2/C_L + R^2/C_R - P^2/C_P = 0 in exact arithmetic and on the CPU's
#: IEEE division, but the TPU's divide is a refined reciprocal, x^2/x is not
#: x to the last bit there, and the few e-7 of the parent it leaves passed
#: the absolute 1e-7 threshold: one pure node in a hundred was split, each
#: taking a frontier slot and two arena ids (which key every later node's
#: feature subset) to no end (PR 32: 98 of 11 526 splits of a 186 000-row
#: tree, and no tree the CPU's from level 8 on).
GAIN_NOISE = 2.0 ** -19


def _rank_gain(g):
    """A gain as the deep builder compares it (argmax within a node, the
    frontier's cut): its float32 with the low 11 mantissa bits cleared.
    Small nodes hold small integer counts, so many candidates share one
    exact gain (4/3, 0.8, ...) reached through different roundings; which of
    them wins then hung on the last bit of a division, and the CPU and the
    chip grew different trees from the same rows. On 12 bits equal gains
    compare equal, and position decides, on every backend alike."""
    bits = jax.lax.bitcast_convert_type(g.astype(jnp.float32), jnp.int32)
    return jax.lax.bitcast_convert_type(bits & jnp.int32(-2048), jnp.float32)


def _split_gain(H, k: int, n_bins: int, min_samples_leaf: float,
                noise_floor: float = 0.0):
    """Per-(node, feature, bin) split gain from a histogram.

    H: [m, d, n_bins, k+1] (stats + count). Returns gain [m, d, n_bins] with
    invalid candidates at -inf. The score is the unified S^2/C proxy (gini /
    variance / Newton gain depending on what S, C carry); identical math to
    the level-wise builder's inline version. A valid candidate whose gain
    does not pass ``noise_floor`` times the parent's score reads 0.
    """
    Sh = H[..., :k]
    Ch = jnp.maximum(H[..., k], 0.0)
    # prefix sums over bins as a triangular-ones contraction: jnp.cumsum on
    # the [.., n_bins, ..] axis lowers to a slow sequential/log-pass TPU
    # fusion (profiled ~30 ms per stage at production batch); the matmul is
    # one MXU pass over a [n_bins, n_bins] mask
    tri = jnp.tril(jnp.ones((n_bins, n_bins), jnp.float32))  # tri[b', b<=b']
    hp = jax.lax.Precision.HIGHEST
    Scum = jnp.einsum("mdbk,cb->mdck", Sh, tri, precision=hp)
    Ccum = jnp.einsum("mdb,cb->mdc", Ch, tri, precision=hp)
    S_tot = Scum[:, :, -1:, :]
    C_tot = Ccum[:, :, -1:]
    Sr = S_tot - Scum
    Cr = C_tot - Ccum
    gain = jnp.sum(Scum**2, -1) / jnp.maximum(Ccum, _EPS) + jnp.sum(
        Sr**2, -1
    ) / jnp.maximum(Cr, _EPS)
    parent = jnp.sum(S_tot**2, -1) / jnp.maximum(C_tot, _EPS)  # [m, d, 1]
    valid = (Ccum >= min_samples_leaf) & (Cr >= min_samples_leaf)
    # last bin = degenerate split (empty right)
    valid = valid & (jnp.arange(n_bins)[None, None, :] < n_bins - 1)
    g = gain - parent
    if noise_floor:
        g = jnp.where(g > noise_floor * parent, g, 0.0)
    return jnp.where(valid, g, -jnp.inf)


def _pick_best(gain, n_bins: int):
    """argmax over (feature, bin) per node: (best_gain, feat, bin)."""
    m = gain.shape[0]
    flat = gain.reshape(m, -1)
    best = jnp.argmax(flat, axis=1)
    bg = jnp.take_along_axis(flat, best[:, None], axis=1)[:, 0]
    bf = (best // n_bins).astype(jnp.int32)
    bb = (best % n_bins).astype(jnp.int32)
    return bg, bf, bb


#: largest per-level node count handled by the gather-free routing /
#: leaf-aggregation forms below. Per-sample gathers from tiny tables
#: (``tab[node]``) and tiny-segment scatters (``segment_sum``) both lower to
#: serialized TPU kernels — profiled at ~45 ms per gather per level and
#: ~46 ms per segment_sum at a production trial batch (168 lanes x 29k
#: rows), which made them >95% of a GradientBoosting stage's device time.
#: The one-hot matmul / compare-reduce forms are MXU/VPU passes (~3-5 ms).
#: Past this node count the O(n*m) masked forms lose to the O(n) gather.
_LOOKUP_M = 256


def _col_select(xb, feats, n_bins: int):
    """[n, m] matrix whose column j is ``xb[:, feats[j]]`` — a dynamic
    column gather expressed as a one-hot contraction. Exact: bin codes are
    integers < 256, representable in bf16, and the one-hot picks a single
    term per output, so f32 accumulation reproduces the codes bit-exactly.
    """
    d = xb.shape[1]
    if n_bins > 256:  # codes could exceed bf16's exact-integer range
        oh = jax.nn.one_hot(feats, d, dtype=jnp.float32)
        return jnp.dot(
            xb.astype(jnp.float32), oh.T, precision=jax.lax.Precision.HIGHEST
        )
    oh = jax.nn.one_hot(feats, d, dtype=jnp.bfloat16)
    return jnp.dot(
        xb.astype(jnp.bfloat16), oh.T, preferred_element_type=jnp.float32
    )


def _route_left(xb, local, bf, bb, n_bins: int):
    """Per-sample go-left decision for one level, gather-free: compare every
    node's split column against its bin and mask-reduce by the sample's node
    id, instead of ``xb[arange(n), bf[local]] <= bb[local]``."""
    m = bf.shape[0]
    cols = _col_select(xb, bf, n_bins)                      # [n, m] f32
    le = cols <= bb[None, :].astype(cols.dtype)             # [n, m]
    oh = local[:, None] == jnp.arange(m, dtype=local.dtype)
    return jnp.any(oh & le, axis=1)


def _leaf_sums(leaf_local, SC, n_leaves: int):
    """``one_hot(leaf).T @ SC`` — scatter-free segment_sum over tree leaves.
    Exact one-hot selection with f32 accumulation; summation order differs
    from segment_sum only in float addition order (~1 ulp)."""
    oh = jax.nn.one_hot(leaf_local, n_leaves, dtype=SC.dtype)
    return jax.lax.dot_general(
        oh,
        SC,
        (((0,), (0,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32,
    )


def _leaf_select(leaf_local, V, n_leaves: int):
    """``one_hot(leaf) @ V`` — gather-free ``V[leaf]`` for leaf-value
    lookup. Exact: the one-hot picks a single f32 row per sample."""
    oh = jax.nn.one_hot(leaf_local, n_leaves, dtype=V.dtype)
    return jnp.dot(oh, V, precision=jax.lax.Precision.HIGHEST)


def leaf_values(leaf_local, leaf_val):
    """``leaf_val[leaf_local]``: each row's leaf value from its leaf id,
    gather-free up to ``_LOOKUP_M`` leaves. The one place that choice is
    made: ``predict_tree`` (after its walk), the streamed forests and the
    boosting stages (from the builder's own ids) all read values here."""
    n_leaves = leaf_val.shape[0]
    if n_leaves <= _LOOKUP_M:
        return _leaf_select(leaf_local, leaf_val, n_leaves)
    return leaf_val[leaf_local]


def _feature_subset_allowed(node_ids, key, max_features: Optional[int], d: int):
    """[m, d] bool mask of each node's random feature subset (or None when
    all features are allowed), keyed by arena node id (fold_in) so chunked/
    monolithic fits draw identical subsets. The mask is computed over the
    GLOBAL feature space so grouped-histogram builds (which slice it per
    group) sample the same subsets as ungrouped builds."""
    if max_features is None or max_features >= d:
        return None

    def one(cid):
        return jax.random.uniform(jax.random.fold_in(key, cid), (d,))

    u = jax.vmap(one)(jnp.maximum(node_ids, 0))
    thresh = jnp.sort(u, axis=1)[:, max_features - 1 : max_features]
    return u <= thresh




def _hist_with_count_multi(local, xbs, SC, n_nodes, n_binss, precision, k,
                           count_from_stats: bool):
    """Feature-grouped level histograms, each [m, d_g, nb_g, k+1], in one
    row scan. When the stat columns sum to the count column exactly
    (classification: S = one_hot(y) * w, C = w), the count histogram is
    derived as the sum over class histograms instead of contracting an
    extra column — one fewer MXU row per node, exact."""
    if not count_from_stats:
        return _level_histogram_multi(local, xbs, SC, n_nodes, n_binss, precision)
    # count_from_stats == classification: stats are one_hot(y) x integer
    # bootstrap/fold counts (< 128 by _bootstrap_counts' cap) — the s8 MXU
    # path applies
    Hs = _level_histogram_multi(local, xbs, SC[:, :k], n_nodes, n_binss,
                                precision, integer_stats=True)
    return tuple(
        jnp.concatenate([H, jnp.sum(H, axis=-1, keepdims=True)], axis=-1)
        for H in Hs
    )


def _hist_with_count(local, xb, SC, n_nodes, n_bins, precision, k,
                     count_from_stats: bool):
    """Single-group level histogram [m, d, nb, k+1]. Wide frontiers on
    large data may route to the compacted (sparsity-exploiting) histogram;
    the static gate keeps the dense form where its one-hot is already
    narrow."""
    if _use_compact(xb.shape[0], n_nodes):
        if not count_from_stats:
            return _level_histogram_compact(local, xb, SC, n_nodes, n_bins, precision)
        H = _level_histogram_compact(local, xb, SC[:, :k], n_nodes, n_bins,
                                     precision, integer_stats=True)
        return jnp.concatenate([H, jnp.sum(H, axis=-1, keepdims=True)], axis=-1)
    return _hist_with_count_multi(
        local, (xb,), SC, n_nodes, (n_bins,), precision, k, count_from_stats
    )[0]


def build_tree_with_leaves(
    xb,
    S,
    C,
    *,
    depth: int,
    n_bins: int,
    min_samples_leaf: float = 1.0,
    max_features: Optional[int] = None,
    key=None,
    precision=jax.lax.Precision.HIGHEST,
    count_from_stats: bool = False,
):
    """Fit one tree, and say where every row of ``xb`` ended.

    xb: [n, d] int32 bin codes. S: [n, k] per-sample weighted target stats
    (already multiplied by sample weight). C: [n] per-sample weights
    (counts for RF, hessians for boosting; 0 = sample not in this fit).
    Returns ``(tree, leaf_local)``: ``tree`` is {"split_feat" [2^depth-1],
    "split_bin" [2^depth-1], "leaf_val" [2^depth, k], "leaf_weight"
    [2^depth]}; ``leaf_local`` [n] int32 is each row's leaf, rows of weight
    0 included (routing reads bin codes only), and is what
    ``_route(xb, split_feat, split_bin, depth, n_bins)`` returns, to the
    bit: a caller that wants the tree's values on its own training table
    reads them with ``leaf_values`` and walks nothing again. The ids stay
    out of the tree dict: trees are stacked into artifacts.

    precision: matmul precision for the histogram contraction. HIGHEST
    (default) for float-valued stats (boosting gradients); integer-valued
    stats (RF one-hot counts, exact in bf16) may pass DEFAULT for ~3x
    faster histograms with bit-identical sums.
    """
    n, d = xb.shape
    k = S.shape[1]
    S = S.astype(jnp.float32)
    C = C.astype(jnp.float32)
    n_internal = 2**depth - 1

    split_feat = jnp.zeros((n_internal,), jnp.int32)
    split_bin = jnp.full((n_internal,), n_bins - 1, jnp.int32)  # pass-through
    node = jnp.zeros((n,), jnp.int32)
    feat_ids = jnp.arange(d, dtype=jnp.int32)

    SC = jnp.concatenate([S, C[:, None]], axis=1)  # [n, k+1] stats+count

    H_prev = None
    for level in range(depth):
        n_nodes = 2**level
        base = n_nodes - 1
        local = node - base
        # histograms [n_nodes, d, n_bins, k+1] via one-hot matmuls on the
        # MXU (node/bin membership as 0/1 operands contracted over rows) —
        # TPU scatters serialize, matmuls don't. Levels past the root use
        # the subtraction trick: build only LEFT children (half the node
        # dim), right = parent − left (exact for integer stats; gains clamp
        # the f32 cancellation tails) — halves total histogram work.
        if level == 0:
            H = _hist_with_count(local, xb, SC, n_nodes, n_bins, precision,
                                 k, count_from_stats)
        else:
            went_left = (local % 2 == 0).astype(SC.dtype)
            H_left = _hist_with_count(
                local // 2, xb, SC * went_left[:, None], n_nodes // 2, n_bins,
                precision, k, count_from_stats,
            )
            H = jnp.stack([H_left, H_prev - H_left], axis=1).reshape(
                n_nodes, d, n_bins, k + 1
            )
        H_prev = H
        gain = _split_gain(H, k, n_bins, min_samples_leaf)

        if max_features is not None and max_features < d:
            key, sub = jax.random.split(key)
            u = jax.random.uniform(sub, (n_nodes, d))
            thresh = jnp.sort(u, axis=1)[:, max_features - 1 : max_features]
            allowed = u <= thresh
            gain = jnp.where(allowed[:, :, None], gain, -jnp.inf)

        best_gain, bf, bb = _pick_best(gain, n_bins)
        do_split = best_gain > 1e-7
        bf = jnp.where(do_split, bf, 0)
        bb = jnp.where(do_split, bb, n_bins - 1)

        split_feat = jax.lax.dynamic_update_slice(split_feat, bf, (base,))
        split_bin = jax.lax.dynamic_update_slice(split_bin, bb, (base,))

        if n_nodes <= _LOOKUP_M:
            go_left = _route_left(xb, local, bf, bb, n_bins)
        else:
            f_i = split_feat[node]
            b_i = split_bin[node]
            go_left = xb[jnp.arange(n), f_i] <= b_i
        node = 2 * node + 1 + jnp.where(go_left, 0, 1)

    leaf_local = node - n_internal
    n_leaves = 2**depth
    if n_leaves <= _LOOKUP_M:
        SCl = _leaf_sums(leaf_local, SC, n_leaves)
        Sl, Cl = SCl[:, :k], SCl[:, k]
    else:
        Sl = jax.ops.segment_sum(S, leaf_local, num_segments=n_leaves)
        Cl = jax.ops.segment_sum(C, leaf_local, num_segments=n_leaves)
    leaf_val = Sl / jnp.maximum(Cl, _EPS)[:, None]
    tree = {
        "split_feat": split_feat,
        "split_bin": split_bin,
        "leaf_val": leaf_val,
        "leaf_weight": Cl,
    }
    return tree, leaf_local


def build_tree(
    xb,
    S,
    C,
    *,
    depth: int,
    n_bins: int,
    min_samples_leaf: float = 1.0,
    max_features: Optional[int] = None,
    key=None,
    precision=jax.lax.Precision.HIGHEST,
    count_from_stats: bool = False,
) -> Dict[str, jnp.ndarray]:
    """``build_tree_with_leaves``'s tree alone, for callers that score
    other rows than they fitted on."""
    return build_tree_with_leaves(
        xb, S, C, depth=depth, n_bins=n_bins, min_samples_leaf=min_samples_leaf,
        max_features=max_features, key=key, precision=precision,
        count_from_stats=count_from_stats,
    )[0]


# ---------------- out-of-core streamed builder ----------------
#
# build_tree's per-level work is two row reductions (the level histogram
# and, at the end, the leaf stat sums) plus O(2^depth) node-level math.
# Both reductions are plain sums over rows, so they block-accumulate: one
# streamed pass per level (route the pending previous-level split, then
# add the block's histogram contribution), one final pass for the last
# routing + leaf sums — depth + 1 passes total, with resident state only
# the per-sample node ids [n_pad] and stats [n_pad, k+1] (a few bytes per
# row vs the [n, d] bin matrix). For integer stats (RF classification:
# one-hot counts, the s8 histogram path) every partial sum is exact, so
# the streamed tree is BITWISE-identical to build_tree's — the parity
# tests/test_streaming.py pins split_feat/split_bin/leaf_val equality.
# Float stats (boosting gradients) match within f32 summation order.

#: jitted per-level block steps, keyed on static geometry so every tree
#: of every trial re-dispatches the same executables
_STREAM_TREE_FNS: Dict[Any, Any] = {}


def _stream_tree_level_fn(d, k, n_bins, level, precision, count_from_stats):
    """One block's step of streamed level ``level``: apply the pending
    previous-level routing to the block's rows, then accumulate the
    block's contribution to the level histogram (left-children only past
    the root — the subtraction trick runs AFTER the pass, on the summed
    histogram, exactly as in build_tree)."""
    ckey = ("level", d, k, n_bins, level, precision, count_from_stats)
    fn = _STREAM_TREE_FNS.get(ckey)
    if fn is not None:
        return fn
    n_nodes = 2**level
    base = n_nodes - 1

    @jax.jit
    def fn(carry, SC, bf, bb, xb_b, start):
        node, H = carry
        rows = xb_b.shape[0]
        nb = jax.lax.dynamic_slice(node, (start,), (rows,))
        scb = jax.lax.dynamic_slice(SC, (start, 0), (rows, SC.shape[1]))
        if level > 0:
            prev_nodes = n_nodes // 2
            prev_base = prev_nodes - 1
            lp = nb - prev_base
            if prev_nodes <= _LOOKUP_M:
                go_left = _route_left(xb_b, lp, bf, bb, n_bins)
            else:
                go_left = xb_b[jnp.arange(rows), bf[lp]] <= bb[lp]
            nb = 2 * nb + 1 + jnp.where(go_left, 0, 1)
            node = jax.lax.dynamic_update_slice(node, nb, (start,))
        local = nb - base
        if level == 0:
            Hb = _hist_with_count(local, xb_b, scb, n_nodes, n_bins,
                                  precision, k, count_from_stats)
        else:
            went_left = (local % 2 == 0).astype(scb.dtype)
            Hb = _hist_with_count(
                local // 2, xb_b, scb * went_left[:, None], n_nodes // 2,
                n_bins, precision, k, count_from_stats,
            )
        return node, H + Hb

    _STREAM_TREE_FNS[ckey] = fn
    return fn


def _stream_tree_leaf_fn(d, k, n_bins, depth):
    """The final streamed pass: apply the last level's pending routing,
    then accumulate per-leaf stat sums."""
    ckey = ("leaf", d, k, n_bins, depth)
    fn = _STREAM_TREE_FNS.get(ckey)
    if fn is not None:
        return fn
    n_internal = 2**depth - 1
    n_leaves = 2**depth
    prev_nodes = 2 ** (depth - 1)
    prev_base = prev_nodes - 1

    @jax.jit
    def fn(carry, SC, bf, bb, xb_b, start):
        node, SCl = carry
        rows = xb_b.shape[0]
        nb = jax.lax.dynamic_slice(node, (start,), (rows,))
        scb = jax.lax.dynamic_slice(SC, (start, 0), (rows, SC.shape[1]))
        lp = nb - prev_base
        if prev_nodes <= _LOOKUP_M:
            go_left = _route_left(xb_b, lp, bf, bb, n_bins)
        else:
            go_left = xb_b[jnp.arange(rows), bf[lp]] <= bb[lp]
        nb = 2 * nb + 1 + jnp.where(go_left, 0, 1)
        node = jax.lax.dynamic_update_slice(node, nb, (start,))
        leaf_local = nb - n_internal
        if n_leaves <= _LOOKUP_M:
            add = _leaf_sums(leaf_local, scb, n_leaves)
        else:
            add = jnp.concatenate(
                [
                    jax.ops.segment_sum(
                        scb[:, :k], leaf_local, num_segments=n_leaves
                    ),
                    jax.ops.segment_sum(
                        scb[:, k], leaf_local, num_segments=n_leaves
                    )[:, None],
                ],
                axis=1,
            )
        return node, SCl + add

    _STREAM_TREE_FNS[ckey] = fn
    return fn


def build_tree_streamed(
    stream_pass,
    S,
    C,
    d: int,
    *,
    depth: int,
    n_bins: int,
    min_samples_leaf: float = 1.0,
    max_features: Optional[int] = None,
    key=None,
    precision=jax.lax.Precision.HIGHEST,
    count_from_stats: bool = False,
):
    """build_tree over streamed row blocks: depth + 1 passes, identical
    split/leaf math.

    ``stream_pass(fn, carry, *consts)`` must run one ascending pass over
    the bin-code blocks, folding ``carry = fn(carry, *consts, xb_b,
    start)`` per block (the kernel drivers wrap a RowBlockStreamer).
    ``S``/``C`` are the full padded per-sample
    stats/counts — zero on pad rows, so pads land in node 0's histograms
    with zero weight and contribute nothing anywhere, exactly like a
    zero-count sample in build_tree.

    Returns ``(tree, node)`` where ``tree`` matches build_tree's dict and
    ``node`` is the final per-sample node id array — prediction for the
    fitting dataset is a resident ``leaf_val[node - n_internal]`` lookup,
    no extra pass over the data. The per-level random feature subsets
    consume ``key`` in build_tree's exact split order, so subset draws
    are bitwise-identical."""
    if depth < 1:
        raise ValueError("build_tree_streamed requires depth >= 1")
    n_pad = S.shape[0]
    k = S.shape[1]
    S = S.astype(jnp.float32)
    C = C.astype(jnp.float32)
    SC = jnp.concatenate([S, C[:, None]], axis=1)
    n_internal = 2**depth - 1

    split_feat = jnp.zeros((n_internal,), jnp.int32)
    split_bin = jnp.full((n_internal,), n_bins - 1, jnp.int32)
    node = jnp.zeros((n_pad,), jnp.int32)

    H_prev = None
    bf = jnp.zeros((1,), jnp.int32)
    bb = jnp.zeros((1,), jnp.int32)
    for level in range(depth):
        n_nodes = 2**level
        base = n_nodes - 1
        fn = _stream_tree_level_fn(d, k, n_bins, level, precision,
                                   count_from_stats)
        H0 = jnp.zeros(
            (n_nodes if level == 0 else n_nodes // 2, d, n_bins, k + 1),
            jnp.float32,
        )
        node, Hl = stream_pass(fn, (node, H0), SC, bf, bb)
        if level == 0:
            H = Hl
        else:
            H = jnp.stack([Hl, H_prev - Hl], axis=1).reshape(
                n_nodes, d, n_bins, k + 1
            )
        H_prev = H
        gain = _split_gain(H, k, n_bins, min_samples_leaf)

        if max_features is not None and max_features < d:
            key, sub = jax.random.split(key)
            u = jax.random.uniform(sub, (n_nodes, d))
            thresh = jnp.sort(u, axis=1)[:, max_features - 1 : max_features]
            allowed = u <= thresh
            gain = jnp.where(allowed[:, :, None], gain, -jnp.inf)

        best_gain, bf, bb = _pick_best(gain, n_bins)
        do_split = best_gain > 1e-7
        bf = jnp.where(do_split, bf, 0)
        bb = jnp.where(do_split, bb, n_bins - 1)

        split_feat = jax.lax.dynamic_update_slice(split_feat, bf, (base,))
        split_bin = jax.lax.dynamic_update_slice(split_bin, bb, (base,))

    leaf_fn = _stream_tree_leaf_fn(d, k, n_bins, depth)
    SCl0 = jnp.zeros((2**depth, k + 1), jnp.float32)
    node, SCl = stream_pass(leaf_fn, (node, SCl0), SC, bf, bb)
    Sl, Cl = SCl[:, :k], SCl[:, k]
    leaf_val = Sl / jnp.maximum(Cl, _EPS)[:, None]
    tree = {
        "split_feat": split_feat,
        "split_bin": split_bin,
        "leaf_val": leaf_val,
        "leaf_weight": Cl,
    }
    return tree, node


#: features with at most this many bin codes qualify for the deep builder's
#: narrow coarse-histogram group (one-hot/binary columns: 2 codes)
COARSE_BINS = int(os.environ.get("CS230_COARSE_BINS", "4"))

#: slots of the deep builder's frontier in the narrow early levels (1, 2, 4,
#: ... nodes): one shape for all of them (see the level plan there)
FRONTIER_PAD = 64


def _deep_schedules(w_schedule, nb_schedule):
    """The deep builder's width and resolution schedules in force: the
    kernel's resolved static (production path, in every cache key) unless
    the env sweep hooks CS230_DEEP_WSCHED=hi:split:lo / CS230_DEEP_NBSCHED=
    occ:deep are set (keyed via trace_salt)."""
    def swept(name, default):
        raw = os.environ.get(name, "")
        return tuple(int(x) for x in raw.split(":")) if raw else default

    w_schedule = swept("CS230_DEEP_WSCHED", w_schedule)
    nb_schedule = swept("CS230_DEEP_NBSCHED", nb_schedule)
    return w_schedule, nb_schedule


def deep_hist_routes(ds, nbs, *, levels: int, width: int, n_bins: int, kk: int,
                     integer_stats: bool, w_schedule=None, nb_schedule=None):
    """{route: histograms} of one ``build_tree_deep`` fit: which of pallas /
    matmul / scatter :func:`_resolve_hist_kernel` picks for the root's
    histogram and for each level's children (``levels`` in all), under the
    same width and resolution schedules (env sweep hooks included). ``ds``
    and ``nbs`` are the feature groups' widths and full bin counts. Shapes
    only: what the trial engine's dispatch span says of a fit it may have
    loaded as an AOT blob and never traced."""
    w_schedule, nb_schedule = _deep_schedules(w_schedule, nb_schedule)
    occ_w, nb_deep = nb_schedule if nb_schedule is not None else (0, n_bins)

    def res_at(cand_w):
        return n_bins if (occ_w <= 0 or cand_w < occ_w) else nb_deep

    def route(r):
        return _resolve_hist_kernel(
            integer_stats, ds, tuple(r if nb == n_bins else nb for nb in nbs), kk)

    r, W = res_at(2), 1
    routes = {route(r): 1}
    for level in range(levels - 1):
        r = min(r, res_at(2 * W))
        routes[route(r)] = routes.get(route(r), 0) + 1
        cap = width if w_schedule is None else (
            w_schedule[0] if level + 1 < w_schedule[1] else w_schedule[2])
        W = min(2 * W, cap)
    return routes


def build_tree_deep(
    xb,
    S,
    C,
    *,
    levels: int,
    width: int,
    n_bins: int,
    min_samples_leaf: float = 1.0,
    max_features: Optional[int] = None,
    key=None,
    precision=jax.lax.Precision.HIGHEST,
    count_from_stats: bool = False,
    groups: Optional[Dict[str, jnp.ndarray]] = None,
    w_schedule: Optional[Tuple[int, int, int]] = None,
    nb_schedule: Optional[Tuple[int, int]] = None,
) -> Dict[str, jnp.ndarray]:
    """Deep tree via frontier-compacted level-wise growth (batched best-first).

    The complete-tree builder above pays 2^level histogram rows per level —
    infeasible past depth ~10. sklearn's ``max_depth=None`` grows to purity
    (depth 25-45 on Covertype-scale data, the reference's exact-CART fit at
    ``aws-prod/worker/worker.py:315``), so this builder keeps an *arena* of
    nodes and, per level, histograms only an active frontier of at most
    ``width`` nodes:

    - each level: split every frontier node whose best gain is positive;
      histogram the LEFT children mapped to parent slots (one matmul with
      one-hot dim ``width``), derive right children by subtraction — so both
      children's exact best-split gains are known for the cost of one
      histogram;
    - the next frontier = top-``width`` children by their OWN best gain
      (``lax.top_k`` finds the cut) — true-gain best-first selection, not a
      proxy — kept in candidate order, equal gains at the cut going to the
      earlier candidates; children not selected (budget) or unsplittable
      (gain <= eps, min_samples_leaf) become leaves. Gains are compared on
      12 bits of mantissa (:func:`_rank_gain`) and one under ``GAIN_NOISE``
      of the parent's score is none, so the tree does not hang on the last
      bit of a backend's division (the CPU and the chip grow the same);
    - per-level cost is O(n * width * kk * d * n_bins) MACs regardless of
      depth, all on the MXU; total leaf budget ~ width * levels (~12k at the
      defaults), the regime sklearn's grow-to-purity needs.

    ``groups`` (optional): feature-grouped histograms. Low-cardinality
    features (one-hot/binary columns — 44 of Covertype's 54) waste nearly
    the whole n_bins axis of the histogram, and per-level cost is linear in
    the bin total; splitting features into a continuous group (full n_bins)
    and a coarse group (COARSE_BINS bins) cuts histogram MACs by
    sum(nb_f)/d*n_bins — ~3x on Covertype — with the identical split
    candidate set (quantile_bins dedup makes coarse codes compact). The
    dict carries {"xb_cont" [n, dc], "xb_coarse" [n, db], "fid_cont" [dc],
    "fid_coarse" [db]}; split records stay in GLOBAL feature ids, so
    routing, prediction, and artifacts are unchanged.

    ``nb_schedule`` (occ_w, nb_deep): ADAPTIVE bin resolution by frontier
    occupancy. Split resolution matters most while nodes are big (early,
    narrow frontier) and the histogram conv's cost is linear in bins x
    frontier width (the per-level MXU term profiled as >50% of a deep
    level) — so candidate evaluation runs at the full ``n_bins`` while the
    candidate frontier is <= occ_w nodes, and at the 2^s-fold coarser
    ``nb_deep`` beyond. Coarse candidates are formed by summing ADJACENT
    fine histogram bins, so the coarse candidate set is an exact subset of
    the fine threshold set: split records stay in FINE bin units (the last
    fine code of the chosen coarse bin) and routing/prediction/artifacts
    are untouched. Resolution is monotone non-increasing over levels (a
    width-schedule drop never re-raises it).

    Shapes are static: the frontier at level l holds min(2^l, width) nodes
    in the next larger of a few slot counts (``FRONTIER_PAD`` and the width
    schedule's caps; holes carry id -1), so early levels don't pay the full
    budget and runs of levels with one shape are one ``lax.scan`` body; the
    arena is a fixed ``2*width*levels + 2`` slots, and routing state is one
    int32 per sample.
    Returns {"feat","bin","child" [A+1], "leaf_val" [A+1, k]}; ``child`` is
    the left-child arena id (0 = leaf; right child = left + 1).
    """
    n, d = xb.shape
    k = S.shape[1]
    S = S.astype(jnp.float32)
    C = C.astype(jnp.float32)
    # decaying width schedule (hi, split_level, lo): full breadth while
    # nodes are big, prune past split_level — per-level cost is linear in
    # the frontier width, and deep levels split mostly-pure low-gain
    # nodes, so narrowing them buys wall time at small CV cost (measured
    # on full Covertype: (1024, 16, 512) = 232 -> 176 s at -0.0017 CV).
    # ``w_schedule`` comes from the kernel's resolved static (production
    # path, in every cache key); env CS230_DEEP_WSCHED is the sweep hook
    # and takes precedence (keyed via trace_salt).
    w_schedule, nb_schedule = _deep_schedules(w_schedule, nb_schedule)
    if w_schedule is not None:
        w_hi, w_split, w_lo = (int(x) for x in w_schedule)
        width_at = lambda lvl: w_hi if lvl < w_split else w_lo  # noqa: E731
        width = max(w_hi, w_lo)
    else:
        width_at = lambda lvl: width  # noqa: E731
    A = 2 * width * levels + 2  # arena capacity; index A = scratch slot
    SC = jnp.concatenate([S, C[:, None]], axis=1)
    if key is None:
        key = jax.random.PRNGKey(0)

    feat_a = jnp.zeros((A + 1,), jnp.int32)
    bin_a = jnp.full((A + 1,), n_bins - 1, jnp.int32)
    child_a = jnp.zeros((A + 1,), jnp.int32)
    node = jnp.zeros((n,), jnp.int32)
    n_alloc = jnp.int32(1)
    # per-level routing tables [levels, width] for the gather-free predict
    # walk: arena id / split column / bin / left child of every node SPLIT
    # at that level (-1 id = no node). predict_tree_deep routes with the
    # same compare/matmul forms the fit uses, instead of per-row gathers
    # from the [A+1] arena tables (profiled ~3x slower).

    # feature groups: (xb columns, global feature ids or None, bin count)
    if groups is not None:
        gspec = (
            (groups["xb_cont"], groups["fid_cont"], n_bins),
            (groups["xb_coarse"], groups["fid_coarse"], COARSE_BINS),
        )
    else:
        gspec = ((xb, None, n_bins),)

    # adaptive bin resolution (docstring): r(level) = n_bins while the
    # candidate frontier is narrow, nb_deep once wide; monotone. Applies
    # to groups histogrammed at the full n_bins (the continuous/single
    # group) — the COARSE_BINS group is already minimal.
    if nb_schedule is not None:
        occ_w, nb_deep = (int(x) for x in nb_schedule)
        if nb_deep <= 0 or n_bins % max(nb_deep, 1) or nb_deep > n_bins:
            raise ValueError(
                f"nb_schedule deep bins {nb_deep} must divide n_bins {n_bins}"
            )
    else:
        occ_w, nb_deep = 0, n_bins

    def res_at(cand_w: int) -> int:
        # strict <: a band whose saturated candidate frontier equals occ_w
        # (2 x its width cap) must go coarse AT saturation, not stay fine
        return n_bins if (occ_w <= 0 or cand_w < occ_w) else nb_deep

    def g_res(r: int, nbg: int) -> int:
        # per-group resolution: only full-resolution groups follow r
        return r if nbg == n_bins else nbg

    def coarsen(H, r_from: int, r_to: int):
        if r_from == r_to:
            return H
        m, dg, _, kkp = H.shape
        return H.reshape(m, dg, r_to, r_from // r_to, kkp).sum(3)

    def hist_groups(local, m, r):
        xgs = tuple(
            xg if g_res(r, nbg) == nbg else xg // (nbg // r)
            for xg, _, nbg in gspec
        )
        nbs = tuple(g_res(r, nbg) for _, _, nbg in gspec)
        if len(gspec) == 1:
            # single group: keep the compact-histogram opt-in gate reachable
            # (_use_compact routes wide frontiers when CS230_HIST_COMPACT=1)
            return (_hist_with_count(
                local, xgs[0], SC, m, nbs[0], precision, k,
                count_from_stats,
            ),)
        # ONE row scan for all groups: the dominant [row_chunk, m*kk]
        # one-hot ⊗ stats operand is built once and contracted against each
        # group's bin one-hot (see _level_histogram_multi)
        return _hist_with_count_multi(
            local,
            xgs,
            SC, m,
            nbs,
            precision, k, count_from_stats,
        )

    def best_from_hists(Hs, node_ids, r):
        """Per-node best (gain, GLOBAL feature, FINE bin) across groups;
        ties keep the earlier group (continuous first)."""
        allowed = _feature_subset_allowed(node_ids, key, max_features, d)
        best = None
        for Hg, (_, fidg, nbg) in zip(Hs, gspec):
            rg = g_res(r, nbg)
            g = _rank_gain(_split_gain(Hg, k, rg, min_samples_leaf, GAIN_NOISE))
            if allowed is not None:
                ag = allowed if fidg is None else jnp.take(allowed, fidg, axis=1)
                g = jnp.where(ag[:, :, None], g, -jnp.inf)
            bg, bfl, bbl = _pick_best(g, rg)
            if rg != nbg:
                # coarse candidate b covers fine codes [b*ratio, (b+1)*ratio)
                # -> the equivalent FINE threshold is its last code
                bbl = (bbl + 1) * (nbg // rg) - 1
            bfg = bfl if fidg is None else jnp.take(fidg, bfl).astype(jnp.int32)
            if best is None:
                best = (bg, bfg, bbl)
            else:
                new = bg > best[0]
                best = (
                    jnp.maximum(bg, best[0]),
                    jnp.where(new, bfg, best[1]),
                    jnp.where(new, bbl, best[2]),
                )
        return best

    # ---- the level plan: every level's shapes, from the schedules alone ----
    # w_act[l]: the nodes the budget allows at level l (1, 2, 4, ... up to
    # the width schedule's cap). The frontier ARRAY of a level is padded to
    # the next of a few slot counts (:data:`FRONTIER_PAD`, the schedule's
    # caps), holes carrying id -1 and gain -inf, so that runs of levels share
    # one shape and ride one ``lax.scan`` body: a Covertype fit's 24 levels
    # are 9 traced bodies, not 24 (with the prediction walk's scan, a minute
    # less of the TPU compiler's three a step program, and a third of the
    # trace: PR 32, whose four-bucket search could not start inside seven
    # minutes). Holes cost histogram width only in the narrow early levels
    # (about 8% more frontier slots over a fit).
    w_act = [1]
    for level in range(levels - 1):
        w_act.append(min(2 * w_act[-1], width_at(level + 1)))
    classes = sorted({min(FRONTIER_PAD, width)}
                     | {width_at(level) for level in range(levels)})
    slots = [next(c for c in classes if c >= w) for w in w_act]
    plan, r_H = [], res_at(2)
    for level in range(levels - 1):
        # candidate resolution for this level's 2*w children (monotone
        # non-increasing); a level whose children outnumber the next
        # level's budget cuts them by gain
        r_c = min(r_H, res_at(2 * w_act[level]))
        plan.append((slots[level], slots[level + 1], r_H, r_c,
                     2 * w_act[level] > w_act[level + 1]))
        r_H = r_c

    def split_level(carry):
        """Split the frontier's nodes of positive gain and route their rows
        to the children. Returns the carry, this level's routing-table rows
        and the rows' left-child slots (``W_l`` for a row that stays)."""
        node, n_alloc, feat_a, bin_a, child_a, frontier, gain, bf, bb, H = carry
        W_l = frontier.shape[0]
        do_split = (gain > 1e-7) & (frontier >= 0)
        rank_inc = jnp.cumsum(do_split.astype(jnp.int32))
        do_split = do_split & (n_alloc + 2 * rank_inc <= A)
        rank_inc = jnp.cumsum(do_split.astype(jnp.int32))
        rank_exc = rank_inc - do_split.astype(jnp.int32)
        left_id = n_alloc + 2 * rank_exc

        # write split records; masked rows land in the scratch slot A
        idx = jnp.where(do_split, frontier, A)
        feat_a = feat_a.at[idx].set(jnp.where(do_split, bf, 0))
        bin_a = bin_a.at[idx].set(jnp.where(do_split, bb, n_bins - 1))
        child_a = child_a.at[idx].set(jnp.where(do_split, left_id, 0))

        # route samples sitting in split nodes to their children —
        # gather-free: per-row arena-table gathers (slot_tab[node],
        # tab[slot], xb[arange, f]) serialize on TPU (~1.9 ms/level/lane
        # profiled at 25% Covertype vs 0.57 ms for this compare/matmul
        # form). Frontier width <= W keeps the [n, W_l] masks small.
        eq = node[:, None] == jnp.where(frontier >= 0, frontier, -1)[None, :]
        slot = jnp.where(
            eq.any(1), jnp.argmax(eq, axis=1), W_l
        ).astype(jnp.int32)
        in_split = (eq & do_split[None, :]).any(1)
        # per-node split column for each row, as a one-hot matmul column
        # select (bf16 exact: codes < 256); threshold compare per node
        cols = _col_select(xb, bf, n_bins)                     # [n, W_l]
        le_node = cols <= bb[None, :].astype(cols.dtype)
        go_left = jnp.any(eq & le_node, axis=1)
        # left-child ids can exceed bf16's exact range: f32 one-hot matmul
        l_i = jnp.dot(
            eq.astype(jnp.float32),
            left_id.astype(jnp.float32),
            precision=jax.lax.Precision.HIGHEST,
        ).astype(jnp.int32)
        node = jnp.where(
            in_split, l_i + 1 - go_left.astype(jnp.int32), node
        )
        n_alloc = n_alloc + 2 * rank_inc[-1]

        pad = width - W_l
        rows = (
            jnp.pad(jnp.where(do_split, frontier, -1), (0, pad), constant_values=-1),
            jnp.pad(bf, (0, pad)), jnp.pad(bb, (0, pad)), jnp.pad(left_id, (0, pad)),
        )
        local_left = jnp.where(in_split & go_left, slot, W_l)
        carry = (node, n_alloc, feat_a, bin_a, child_a, frontier, gain, bf, bb, H)
        return carry, rows, (do_split, left_id, local_left)

    def grow_level(carry, W_next: int, r_H: int, r_c: int, cut_by_gain: bool):
        """One level but the last: split, histogram the children, and make
        the next frontier of ``W_next`` slots from them."""
        carry, rows, (do_split, left_id, local_left) = split_level(carry)
        node, n_alloc, feat_a, bin_a, child_a, frontier, _, _, _, H = carry
        W_l = frontier.shape[0]
        # children's histograms: left by matmul over parent slots, right by
        # subtraction (exact for integer stats; float tails are gain-clamped);
        # parents coarsen by adjacent-bin sums — exact
        if r_c != r_H:
            H = tuple(
                coarsen(h, g_res(r_H, nbg), g_res(r_c, nbg))
                for h, (_, _, nbg) in zip(H, gspec)
            )
        H_L = hist_groups(local_left, W_l, r_c)
        cand_H = tuple(
            jnp.concatenate([hl, h - hl], axis=0)  # [2*W_l, d_g, nb_g, k+1]
            for h, hl in zip(H, H_L)
        )
        cand_id = jnp.concatenate(
            [jnp.where(do_split, left_id, -1), jnp.where(do_split, left_id + 1, -1)]
        )
        cgain, cbf, cbb = best_from_hists(cand_H, cand_id, r_c)
        cgain = jnp.where(cand_id >= 0, cgain, -jnp.inf)

        if 2 * W_l < W_next:
            # more slots than children: holes at the end
            more = W_next - 2 * W_l
            cgain = jnp.pad(cgain, (0, more), constant_values=-jnp.inf)
            cand_id = jnp.pad(cand_id, (0, more), constant_values=-1)
            cbf, cbb = jnp.pad(cbf, (0, more)), jnp.pad(cbb, (0, more))
            cand_H = tuple(
                jnp.pad(h, ((0, more),) + ((0, 0),) * (h.ndim - 1)) for h in cand_H)
        if 2 * W_l <= W_next:
            sel = None
        else:
            # the W_next children of the largest gain, KEPT IN CANDIDATE
            # ORDER (left children in frontier order, then right children):
            # ids are dealt in frontier order and key each node's feature
            # subset, so an order by gain would hang every later node on how
            # near-equal gains happen to round. At the cut, equal gains go to
            # the earlier candidates (top_k only finds the cut's value).
            # Where the budget has room for every child the cut is -inf:
            # the children close ranks over the holes, in the same order.
            cut = (jax.lax.top_k(cgain, W_next)[0][-1] if cut_by_gain
                   else jnp.float32(-jnp.inf))
            above, at = cgain > cut, cgain == cut
            room = W_next - jnp.sum(above.astype(jnp.int32))
            keep = above | (at & (jnp.cumsum(at.astype(jnp.int32)) <= room))
            sel = jnp.nonzero(keep, size=W_next, fill_value=0)[0]
            cgain, cand_id, cbf, cbb = cgain[sel], cand_id[sel], cbf[sel], cbb[sel]
            cand_H = tuple(h[sel] for h in cand_H)
        frontier = jnp.where(cgain > -jnp.inf, cand_id, -1)
        return (node, n_alloc, feat_a, bin_a, child_a, frontier,
                cgain, cbf, cbb, cand_H), rows

    # root: full histogram + its best split, in the first level's slots
    frontier = jnp.zeros((1,), jnp.int32)
    H = hist_groups(node, 1, res_at(2))
    gain, bf, bb = best_from_hists(H, frontier, res_at(2))
    more = slots[0] - 1
    carry = (
        node, n_alloc, feat_a, bin_a, child_a,
        jnp.pad(frontier, (0, more), constant_values=-1),
        jnp.pad(gain, (0, more), constant_values=-jnp.inf),
        jnp.pad(bf, (0, more)), jnp.pad(bb, (0, more)),
        tuple(jnp.pad(h, ((0, more),) + ((0, 0),) * (h.ndim - 1)) for h in H),
    )
    tables = []  # per-level routing-table rows, each [levels in the run, width]
    level = 0
    while level < levels - 1:
        sig = plan[level]
        run = 1
        if sig[0] == sig[1] and sig[2] == sig[3]:
            # the levels after this one that share its shapes: one scan body
            while level + run < levels - 1 and plan[level + run] == sig:
                run += 1
        if run == 1:
            carry, rows = grow_level(carry, *sig[1:])
            tables.append(tuple(r[None] for r in rows))
        else:
            carry, rows = jax.lax.scan(
                lambda c, _, sig=sig: grow_level(c, *sig[1:]), carry, None, length=run)
            tables.append(rows)
        level += run
    # children of the last level are leaves
    carry, rows, _ = split_level(carry)
    tables.append(tuple(r[None] for r in rows))
    node, _, feat_a, bin_a, child_a = carry[:5]
    lvl_ids, lvl_feat, lvl_bin, lvl_left = (
        jnp.concatenate(t) for t in zip(*tables))

    leaf_S = jax.ops.segment_sum(S, node, num_segments=A + 1)
    leaf_C = jax.ops.segment_sum(C, node, num_segments=A + 1)
    leaf_val = leaf_S / jnp.maximum(leaf_C, _EPS)[:, None]
    return {
        "feat": feat_a,
        "bin": bin_a,
        "child": child_a,
        "leaf_val": leaf_val,
        "leaf_weight": leaf_C,
        "level_ids": lvl_ids,
        "level_feat": lvl_feat,
        "level_bin": lvl_bin,
        "level_left": lvl_left,
    }


@partial(jax.jit, static_argnames=("levels",))
def _route_deep(xb, feat, bins, child, levels: int):
    n = xb.shape[0]
    node = jnp.zeros((n,), jnp.int32)
    for _ in range(levels):
        c = child[node]
        go_left = xb[jnp.arange(n), feat[node]] <= bins[node]
        node = jnp.where(c > 0, c + 1 - go_left.astype(jnp.int32), node)
    return node


@partial(jax.jit, static_argnames=("levels", "n_bins"))
def _route_deep_levels(xb, level_ids, level_feat, level_bin, level_left,
                       levels: int, n_bins: int):
    """Gather-free arena routing: at step l a row advances iff its node is
    in that level's split table (a node is split at exactly one level, so
    the walk is equivalent to the child[node] gather walk — profiled ~3x
    faster: [n, W] compare/one-hot-matmul forms instead of three per-row
    [A+1]-table gathers per level)."""
    n = xb.shape[0]

    def walk(node, table):
        ids, feat, bins, left = table
        eq = node[:, None] == ids[None, :]  # -1 ids never match (node >= 0)
        in_split = eq.any(1)
        cols = _col_select(xb, feat, n_bins or 1 << 30)
        le = cols <= bins[None, :].astype(cols.dtype)
        go_left = jnp.any(eq & le, axis=1)
        l_i = jnp.dot(
            eq.astype(jnp.float32),
            left.astype(jnp.float32),
            precision=jax.lax.Precision.HIGHEST,
        ).astype(jnp.int32)
        return jnp.where(in_split, l_i + 1 - go_left.astype(jnp.int32), node), None

    # one body for every level: the tables are [levels, width] already
    node, _ = jax.lax.scan(
        walk, jnp.zeros((n,), jnp.int32),
        (level_ids[:levels], level_feat[:levels], level_bin[:levels], level_left[:levels]))
    return node


def predict_tree_deep(xb, tree, levels: int, n_bins: int = 0):
    """Leaf values for binned query rows against an arena tree. Trees
    fitted with per-level routing tables take the gather-free walk;
    older artifacts fall back to the arena-table gather walk."""
    if "level_ids" in tree:
        leaf = _route_deep_levels(
            xb, tree["level_ids"], tree["level_feat"], tree["level_bin"],
            tree["level_left"], levels, n_bins,
        )
    else:
        leaf = _route_deep(xb, tree["feat"], tree["bin"], tree["child"], levels)
    return tree["leaf_val"][leaf]


@partial(jax.jit, static_argnames=("depth", "n_bins"))
def _route(xb, split_feat, split_bin, depth: int, n_bins: int = 0):
    n = xb.shape[0]
    node = jnp.zeros((n,), jnp.int32)
    for level in range(depth):
        base, m = 2**level - 1, 2**level
        if m <= _LOOKUP_M:
            # gather-free: this level's split records are a static slice
            bf = jax.lax.slice(split_feat, (base,), (base + m,))
            bb = jax.lax.slice(split_bin, (base,), (base + m,))
            go_left = _route_left(xb, node - base, bf, bb, n_bins or 1 << 30)
        else:
            f_i = split_feat[node]
            b_i = split_bin[node]
            go_left = xb[jnp.arange(n), f_i] <= b_i
        node = 2 * node + 1 + jnp.where(go_left, 0, 1)
    return node - (2**depth - 1)


def predict_tree(xb, tree, depth: int, n_bins: int = 0):
    """Leaf values for each row of binned query data. ``n_bins`` (when
    known) lets the gather-free router use the fast bf16 column select."""
    leaf = _route(xb, tree["split_feat"], tree["split_bin"], depth, n_bins)
    return leaf_values(leaf, tree["leaf_val"])

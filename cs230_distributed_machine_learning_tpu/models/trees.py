"""Tree-ensemble kernels: RandomForest and GradientBoosting (clf + reg).

Capability target: the four ensemble rows of the reference whitelist
(``aws-prod/worker/worker.py:38-49``). Built on the histogram tree core
(ops/trees.py). Design notes:

- Structural hyperparameters (n_estimators, max_depth, max_features,
  n_bins) are static — they change scan lengths/shapes, so each combo is a
  compile bucket; learning_rate and subsample are traced.
- sklearn's ``max_depth=None`` (grow to purity) is capped at a static depth
  (10) — a documented approximation; unsplittable nodes pass through, so a
  shallower-than-cap tree is representable exactly. An EXPLICIT max_depth
  may go to 14 on the ensemble kernels (their chunked fits bound dispatch
  time; each level doubles histogram work).
- RF bootstrap is the exact multinomial resample (n categorical draws from
  the weight-masked rows -> per-row counts), per-node feature subsets follow
  max_features ("sqrt"/"log2"/int/float). Forest prediction averages leaf
  class distributions and argmaxes — sklearn's soft-vote semantics.
- GBT is Newton-step boosting on log-loss/squared-loss gradients (leaf
  value = sum g / sum h), with sklearn's (k-1)/k multinomial leaf scaling;
  stages run under ``lax.scan``, trees per class under ``vmap``.
- Trees bin features once per dataset (quantile bins) via the
  ``prepare_data`` hook the trial engine calls once per bucket — the
  reference re-read the CSV per subtask; we don't even re-bin.

Split scores use the unified S^2/C gain rather than sklearn's exact
friedman_mse/gini-on-sorted-values; scores match sklearn statistically
(tests assert tolerance, not bit equality) — SURVEY.md §7 flags trees as
the riskiest parity item and this is the deliberate trade.
"""

from __future__ import annotations

import os
from typing import Any, Dict

import jax
import jax.numpy as jnp
import numpy as np

from ..ops.trees import (
    bin_data,
    build_tree,
    build_tree_deep,
    build_tree_with_leaves,
    leaf_values,
    predict_tree,
    predict_tree_deep,
    quantile_bins,
)
from .base import ModelKernel

# Complete-tree caps (small data / GBT): each level doubles histogram work,
# so the level-wise complete builder stops at 10 (heuristic) / 14 (explicit
# on chunked kernels). Above _DEEP_N samples, kernels that grow to purity in
# sklearn (RF, DecisionTree — the reference's exact-CART fit,
# aws-prod/worker/worker.py:315) switch to the frontier-compacted deep
# builder (ops/trees.build_tree_deep): depth to _DEEP_LEVELS with a
# _DEEP_W-node active frontier per level, the regime where Covertype-class
# accuracy lives (sklearn RF cv ~0.95 needs depth ~25, not 10).
_DEPTH_CAP = 10
_DEPTH_HARD_CAP = 14
_DEEP_LEVELS = int(os.environ.get("CS230_DEEP_LEVELS", "24"))
#: levels past log2(n) the arena may grow (purity trees on real data run
#: well past log2(n); sweep hook for the depth-vs-time trade)
_DEEP_LEVEL_MARGIN = int(os.environ.get("CS230_DEEP_LEVEL_MARGIN", "8"))
_DEEP_LEVELS_EXPLICIT = 32
# Deep-arena defaults, re-swept on-device in r3 (RF-100, v5e, after the
# gather-free routing + s8 histogram work made width ~40% cheaper):
#   50% Covertype (sklearn cv 0.8113, 207 s):
#     W=256 nb=64  94.6 s cv 0.7894   W=384 nb=64 131.2 s cv 0.7991
#     W=512 nb=64 163.6 s cv 0.8048   W=512 nb=48 125.9 s cv 0.8040
#   100% Covertype: W=512 nb=48 225.8 s cv 0.8224 (r2 default: 320 s 0.8008)
# Frontier WIDTH is the binding capacity (deeper levels alone changed
# nothing: cv 0.7896 at levels=30); coarser 48-bin quantiles buy the wider
# frontier back at unchanged cv. The width formula itself scales with n
# (2^ceil(log2(n/64))), so this cap only binds past ~33k rows — small
# fractions keep their narrower, faster arenas. Env-tunable for sweeps.
#
# r4: per-level histogram cost is ~ W x n_bins, so width and bins TRADE at
# constant cost — and at full Covertype the trade strongly favors width.
# (The sklearn denominator was re-measured UNCONTENDED at 413.9-420.2 s /
# cv 0.8400 — the r3 613.7/672.5 s figures were CPU-contended; see
# BASELINE.md r4. First-pass times unless noted.)
#   W=768  nb=32 cv 0.8295   W=896 nb=28 cv 0.8318
#   W=1024 nb=24 cv 0.8328 (231.9 s steady = 1.80x vs honest 417 s)
#   W=1024 nb=16 cv 0.8309 (206.6 s steady = 2.02x)
#   W=1536 nb=16 cv 0.8366 (286.7 s)   W=2048 nb=12 cv 0.8365 (saturates)
# The top width band therefore pairs W=1024 with 24 bins; the narrower
# bands keep the 48-bin cap their parity anchors were measured at.
_DEEP_W = int(os.environ.get("CS230_DEEP_W", "1536"))
_DEEP_BINS_CAP = int(os.environ.get("CS230_DEEP_BINS", "48"))
#: bins cap when the TOP width bands are in play (n > 49152): the measured
#: constant-cost width/bins trade above. _DEEP_BINS_WIDEST applies at the
#: 1536-wide band (n > 80k), where the r5 Pareto sweep landed on
#: (1536, 17, 512) + adaptive 48/16: CV 0.8368 (-0.0027 vs sklearn) at
#: 200.4 s = 2.42x — the first default inside BOTH r4 #4 bars.
_DEEP_BINS_WIDE = int(os.environ.get("CS230_DEEP_BINS_WIDE", "24"))
_DEEP_BINS_WIDEST = int(os.environ.get("CS230_DEEP_BINS_WIDEST", "16"))
#: r5 adaptive bin resolution (ops/trees.build_tree_deep nb_schedule):
#: candidate evaluation runs at the full (fine) binning while the
#: candidate frontier has <= _DEEP_BINS_OCC nodes — early splits on BIG
#: nodes get fine thresholds — and at _DEEP_BINS_DEEP once wide, where the
#: frontier-width x bins product is the profiled per-level MXU cost.
#: 0 disables (single resolution everywhere).
_DEEP_BINS_OCC = int(os.environ.get("CS230_DEEP_BINS_OCC", "256"))
_DEEP_BINS_DEEP = int(os.environ.get("CS230_DEEP_BINS_DEEP", "24"))


_deep_w_force_warned: set = set()


def _warn_deep_w_force(width: int) -> None:
    if width in _deep_w_force_warned:
        return
    _deep_w_force_warned.add(width)
    from ..utils import get_logger

    get_logger().warning(
        "CS230_DEEP_W_FORCE=%d overrides the deep-arena width bands for "
        "EVERY grow-to-purity fit in this process", width,
    )


_deep_bins_warned: set = set()


def _warn_deep_bins_clamp(requested: int, cap: int) -> None:
    """Once-per-process notice that the deep arena overrides an explicitly
    requested finer n_bins (CS230_DEEP_BINS / CS230_DEEP_BINS_WIDE caps) —
    callers otherwise can't detect the divergence (ADVICE r2)."""
    if (requested, cap) in _deep_bins_warned:
        return
    _deep_bins_warned.add((requested, cap))
    from ..utils import get_logger

    get_logger().warning(
        "deep-tree arena clamps requested n_bins=%d to %d "
        "(CS230_DEEP_BINS / CS230_DEEP_BINS_WIDE; large-n grow-to-purity "
        "path only)",
        requested,
        cap,
    )


def _deep_n_threshold() -> int:
    """Sample count above which grow-to-purity kernels use the deep builder
    (env-tunable so CPU tests can exercise the deep path on small data).

    r4 re-measure at the boundary (1,162-row Covertype curve draw, RF-100):
    sklearn's CV across 8 seeds is 0.4969 +- 0.0067 (min 0.4819, the
    committed seed-42 row 0.5112 is its high tail); the complete builder's
    depth cap (min(10, ceil(log2(n)) - 2) = 9 here) lands at 0.4802 —
    BELOW sklearn's seed minimum — while the deep grow-to-purity arena
    scores 0.4914, inside 1 sigma of the seed mean, at 2.23 s steady vs
    the committed 3.17 s sklearn row (the r4 tree kernels cut the deep
    path's small-n cost ~2x from the r2-era 4.3 s that previously
    justified 4096). Raising arena width/bins beyond the small-n band
    buys nothing (W=128/nb=128 measured 0.4889): the residual delta is
    bootstrap/feature-subset RNG, not capacity. Above 1024 rows, every
    fraction of the scaling curve runs the builder whose depth semantics
    match sklearn's."""
    return int(os.environ.get("CS230_TREE_DEEP_N", "1024"))


def _resolve_max_features(spec, d: int, default) -> int:
    if spec is None:
        spec = default
    if spec in ("sqrt", "auto"):
        return max(1, int(np.sqrt(d)))
    if spec == "log2":
        return max(1, int(np.log2(max(d, 2))))
    if isinstance(spec, float) and 0 < spec <= 1:
        return max(1, int(spec * d))
    if spec in (1.0, "all"):
        return d
    return max(1, min(int(spec), d))


class _TreeBase(ModelKernel):
    #: default for max_features resolution (overridden per family)
    _mf_default: Any = 1.0

    def trace_salt(self):
        """ops/trees.py env knobs read at trace/import time that change the
        compiled program but don't land in ``static`` — they must key every
        executable cache (same hazard the SVC solver knobs hit: a knob flip
        silently reloading the pre-knob AOT blob). CS230_STREAM (resolved)
        joins them: the streamed and single-shot drivers stage different
        dataset forms under different keys."""
        from ..data.streaming import stream_mode
        from ..ops.trees import _hist_kernel_mode

        return (
            stream_mode(),
            os.environ.get("CS230_DEEP_WSCHED", ""),
            _hist_kernel_mode(),  # resolved, not raw: aliases share a key
            os.environ.get("CS230_HIST_COMPACT", "0"),
            os.environ.get("CS230_HIST_BLOCK_ROWS", ""),
            os.environ.get("CS230_HIST_BLOCK_NODES", ""),
            os.environ.get("CS230_COARSE_BINS", ""),
            os.environ.get("CS230_TREE_GROUP_MB", ""),
            os.environ.get("CS230_DEEP_NBSCHED", ""),
            os.environ.get("CS230_DEEP_BINS_OCC", ""),
            os.environ.get("CS230_DEEP_BINS_DEEP", ""),
        )
    #: sklearn semantics grow this family to purity (RF/DecisionTree) —
    #: eligible for the deep frontier-compacted builder on large data
    _supports_deep = False

    def resolve_static(self, static: Dict[str, Any], n: int, d: int, n_classes: int):
        n_bins = int(static.get("n_bins", 128))
        n_bins = min(n_bins, max(8, n))
        depth = static.get("max_depth")
        # explicit depths past the complete-builder's cap route to the deep
        # arena; the cap is kernel-dependent (chunked ensembles honor up to
        # _DEPTH_HARD_CAP complete levels, plain DT only _DEPTH_CAP), so the
        # honored depth stays monotonic in the requested depth
        _complete_cap = (
            _DEPTH_HARD_CAP if hasattr(self, "chunked_plan") else _DEPTH_CAP
        )
        deep = (
            self._supports_deep
            and n > _deep_n_threshold()
            and (depth is None or int(depth) > _complete_cap)
        )
        if deep:
            grow_to_purity = depth is None
            if grow_to_purity:
                levels = min(
                    _DEEP_LEVELS,
                    int(np.ceil(np.log2(max(n, 8)))) + _DEEP_LEVEL_MARGIN,
                )
            else:
                levels = min(int(depth), _DEEP_LEVELS_EXPLICIT)
            # Width by explicit monotone bands anchored at on-device
            # parity measurements, r5 re-anchored under adaptive bins
            # (Covertype RF-100, CV delta vs sklearn in parens):
            # 5.8k->128 (+0.007 BEATS, 3.6x), 11.6k->128 (-0.006, 6.3 s
            # = 5.2x), 29k->256 (-0.007, 3.8x), 58k->1024 (+0.0002
            # BEATS, 127.8 s), 116k->1536+(1536,17,512)+deep16 (-0.0027,
            # 200.4 s = 2.42x — BASELINE.md r5 sweep table). Band edges
            # sit between measured points, so every n gets the narrowest
            # width whose band endpoints sat inside the 0.01 parity band;
            # the smallest deep fits (n just over the 1024 threshold)
            # keep 64-wide arenas.
            bins_cap = _DEEP_BINS_CAP
            force_w = os.environ.get("CS230_DEEP_W_FORCE")
            if force_w:
                # sweep/parity hook: bypass the width bands entirely (the
                # BASELINE.md full-scale Pareto knob). Applies to EVERY
                # deep fit while set — warn once so a forgotten export
                # doesn't silently inflate small fits 12x.
                try:
                    width = int(force_w)
                    if width < 64:
                        raise ValueError(force_w)
                except ValueError:
                    raise ValueError(
                        f"CS230_DEEP_W_FORCE={force_w!r}: expected an "
                        "integer arena width >= 64"
                    ) from None
                _warn_deep_w_force(width)
            else:
                if n <= 5000:
                    width = 64
                elif n <= 24576:
                    width = 128
                elif n <= 49152:
                    width = 256
                elif n <= 80_000:
                    # the 58k (50%) parity point BEATS sklearn at 1024
                    # (0.8121 vs 0.8113, r4) — keep its measured band
                    width = 1024
                else:
                    # r5 Pareto: 1536 through the critical mid levels with
                    # a 512 tail and 48/16 adaptive bins (sweep table in
                    # BASELINE.md r5) — CV -0.0027 at 2.42x
                    width = 1536
                width = min(_DEEP_W, width)
                if width >= 1024:
                    # top bands: trade bins for width at constant histogram
                    # cost (W x n_bins) — measured strictly better CV. Only
                    # when the wide arena is actually in play (a user pinning
                    # CS230_DEEP_W to a narrower arena keeps the 48-bin cap
                    # its parity points were measured at).
                    bins_cap = min(
                        bins_cap,
                        _DEEP_BINS_WIDEST if width >= 1536 else _DEEP_BINS_WIDE,
                    )
            depth = levels
            # coarser quantile bins in the deep arena (see sweep table at
            # _DEEP_W): ~1.5x faster histograms AND better CV than 128 —
            # like the depth caps, this deliberately overrides a finer
            # user-requested binning for the deep path only.
            #
            # r5: BINNING stays at the fine cap (_DEEP_BINS_CAP, 48);
            # bins_cap (24 at the wide band) becomes the DEEP-level
            # resolution of the adaptive nb_schedule instead of a global
            # clamp — early/narrow-frontier candidates keep the fine
            # thresholds (big-node splits are where resolution buys CV),
            # wide frontiers pay only the coarse bin axis.
            fine_cap = max(_DEEP_BINS_CAP, bins_cap)
            eff_fine = min(n_bins, fine_cap)
            deep_nb = min(eff_fine, min(bins_cap, _DEEP_BINS_DEEP))
            nb_occ = _DEEP_BINS_OCC
            if os.environ.get("CS230_DEEP_BINS_OCC") is None and width == 256:
                # the 256-wide band needs its LAST pre-saturation level
                # (W_l=128, candidates=256) fine too: 25% Covertype
                # measured occ 256 -> CV -0.0104 (outside the band) vs
                # occ 384 -> -0.0065 at 22.6 s (3.8x). Applied only when
                # the knob is at its default.
                nb_occ = 384
            sched_ok = (
                nb_occ > 0
                and deep_nb < eff_fine
                and eff_fine % deep_nb == 0
            )
            # warn against the cap that will ACTUALLY apply: the fine cap
            # when the adaptive schedule engages, the flat deep cap when it
            # does not (disabled/non-dividing resolutions)
            cap_used = fine_cap if sched_ok else bins_cap
            if "n_bins" in static and n_bins > cap_used:
                _warn_deep_bins_clamp(n_bins, cap_used)
            n_bins = min(n_bins, cap_used)
            nb_sched = (nb_occ, deep_nb) if sched_ok else None
        elif depth is None:
            # small data: the complete-tree builder to ~log2(n) levels is
            # already near-purity and cheaper to compile than the arena
            depth = min(_DEPTH_CAP, max(3, int(np.ceil(np.log2(max(n, 8)))) - 2))
        else:
            # deep explicit requests are only safe for kernels whose fits
            # chunk across dispatches; plain DecisionTree (no chunked
            # protocol) keeps the uniform cap
            hard = _DEPTH_HARD_CAP if hasattr(self, "chunked_plan") else _DEPTH_CAP
            depth = min(int(depth), hard)
        mf = _resolve_max_features(static.get("max_features"), d, self._mf_default)
        msl = static.get("min_samples_leaf", 1)
        if isinstance(msl, float) and msl < 1:
            msl = max(1, int(msl * n))
        out = {
            **static,
            "_depth": depth,
            "_n_bins": n_bins,
            "_mf": mf,
            "_msl": float(msl),
            "_seed": int(static.get("random_state") or 0),
        }
        if deep:
            out["_deep"] = True
            out["_levels"] = levels
            out["_W"] = width
            if nb_sched is not None:
                out["_nb_sched"] = nb_sched
            if width >= 1536 and n > 80_000 and grow_to_purity and not force_w:
                # r5 top band: one extra wide level, then a hard 512 tail —
                # the measured Pareto point (200.4 s, CV 0.8368); the
                # formula-tail (width//2 = 768) costs ~10% more for no
                # measured CV
                out["_wsched"] = (width, 17, 512)
            elif width >= 1024 and n > 80_000 and grow_to_purity and not force_w:
                # decaying width schedule at full scale: per-level cost is
                # linear in frontier width and the deepest levels split
                # mostly-pure low-gain nodes. Measured on full Covertype
                # RF-100 (sklearn 417 s / cv 0.8400): no schedule 231.9 s
                # cv 0.8328; (1024,16,512) 175.8 s = 2.37x at cv 0.8311
                # (-0.0089, inside the 0.01 band); (1024,12,512) is the
                # over-pruned point (146.6 s but cv 0.8236). Gated to the
                # grow-to-purity path (where it was validated — a user's
                # EXPLICIT max_depth keeps the exact requested width) and
                # to n > 80k so the 58k band point keeps its measured
                # margin.
                out["_wsched"] = (width, 16, width // 2)
        return out

    def memory_estimate_mb(self, n: int, d: int, static: Dict[str, Any]) -> float:
        """What one (trial, split) lane holds on the device while it fits.

        Deep (arena) mode is frontier-bounded: ~4 histogram-sized buffers of
        W rows (H, left+right candidates, gathered next-H) plus the binned
        dataset; the deep arena routes by O(n) gathers.

        The complete builder is depth-aware: the deepest level's histogram
        [2^(depth-1) nodes, d, bins, k+1] (x3 for H/H_prev/H_left) -- 16x
        growth from depth 10 to 14 must throttle trials-per-dispatch -- plus
        a dozen per-row vectors over the FULL row count (node ids, the stat
        columns and their went-left copy, masks, the carried scores): 24 +
        8 (k+1) bytes a row. Its gather-free routing / leaf forms
        (ops/trees._route_left/_leaf_sums/_leaf_select) are written as
        [n, m] one-hot compares and products. The TPU compiler fuses each
        into its reduction and holds no [n, m] buffer: the boosting step
        program at 1 000 000 x 28, depth 8, 128 bins compiles to 41-54 MB
        of temporaries a lane at 12 to 126 lanes, 68 at 6, half of it at
        half the rows, and a forest's step at a set ``max_depth`` likewise
        (deviceless v5e compiles, PR 34: tests/test_tpu_compile.py; the
        estimate that counted 6 n m + 4 n 2^depth bytes for those forms
        said 2 033 MB a lane there, so one trial's six folds were
        dispatched in two groups where forty-eight lanes fit). No other
        compiler was read, so off a TPU those forms still count. Past
        _LOOKUP_M nodes the builder gathers and segment-sums instead
        (another 64 bytes a row at depth 10). The binned dataset and the
        histogram's bin one-hot are one copy for every lane of a dispatch
        and live in the half of the device the lane budget leaves alone;
        they are not a lane's. The state a chunked fit carries from step
        to step is the planner's to count (``plan_bucket``: a copy for
        each step enqueued ahead), not this estimate's."""
        from ..ops.trees import _LOOKUP_M
        from ..utils import backend as _backend

        n_bins = int(static.get("_n_bins", 128))
        kk = max(int(static.get("_n_classes", 2)), 2) + 1
        # forest kernels fit T trees concurrently (_tree_group_size): their
        # per-tree buffers coexist, so the engine's lane throttle must see
        # the multiplied working set
        group = (
            self._tree_group_size(n, d, static)
            if hasattr(self, "_tree_group_size") else 1
        )
        if static.get("_deep"):
            W = int(static["_W"])
            hist = 4.0 * W * d * n_bins * kk * 4
            return max(1.0, (group * hist + 4.0 * n * d * 2) / 1e6)
        depth = int(static.get("_depth", 8))
        hist = 3.0 * (2 ** max(depth - 1, 0)) * d * n_bins * kk * 4
        rows = (24.0 + 8.0 * kk) * n
        if 2**depth > _LOOKUP_M:
            rows += 64.0 * n
        if not _backend.on_tpu():
            # the [n, m] routing mask (f32 columns + 2 bool masks, ~6 B a
            # cell, m capped at _LOOKUP_M) and the [n, n_leaves] f32
            # leaf-sum one-hot (only where the leaves fit the lookup form)
            m_route = min(2 ** max(depth - 1, 0), _LOOKUP_M)
            m_leaf = 2**depth if 2**depth <= _LOOKUP_M else 0
            rows += (6.0 * m_route + 4.0 * m_leaf) * n
        return max(1.0, group * (hist + rows) / 1e6)

    @staticmethod
    def _hist_cols(static, d, prepared=None):
        """Effective bin-column total of the level histogram: d * n_bins
        ungrouped, or the grouped sum d_cont*n_bins + d_coarse*COARSE_BINS
        when prepare_data staged feature groups."""
        from ..ops.trees import COARSE_BINS

        n_bins = int(static.get("_n_bins", 128))
        sched = static.get("_nb_sched")
        if sched:
            # adaptive resolution: the wide (deep) levels dominate the
            # MAC-weighted level sum, so cost at the deep resolution
            n_bins = int(sched[1])
        if (
            prepared is not None
            and isinstance(prepared, dict)
            and "xb_coarse" in prepared
        ):
            d_b = prepared["xb_coarse"].shape[1]
            return (d - d_b) * n_bins + d_b * COARSE_BINS
        return d * n_bins

    def macs_estimate(self, n, d, static, prepared=None):
        """Histogram-contraction MACs of one (trial, split) fit — used for
        host-vs-accelerator placement, chunk planning, and the harnesses'
        MFU accounting. ``prepared`` (the prepare_data dict, when the caller
        has it) prices grouped histograms at their true bin total instead of
        d*n_bins — a ~3x overcharge on one-hot-heavy data like Covertype
        that would otherwise schedule ~3x too many chunk dispatches."""
        kk = (
            max(int(static.get("_n_classes", 2)), 2) + 1
            if self.task == "classification"
            else 2
        )
        cols = self._hist_cols(static, d, prepared)
        trees = int(static.get("n_estimators", 1))
        if static.get("_deep"):
            W = int(static["_W"])
            levels = int(static["_levels"])
            ramp = int(np.log2(W))
            sched = static.get("_wsched")
            if sched:
                # width-scheduled arena: hi-width levels then lo-width tail
                hi, split, lo = (int(x) for x in sched)
                w_sum = (
                    max(min(split, levels) - ramp + 2, 2) * hi
                    + max(levels - split, 0) * lo
                )
            else:
                w_sum = max(levels - ramp + 2, 2) * W
            per_tree = float(n) * kk * cols * w_sum
        else:
            depth = int(static.get("_depth", 8))
            per_tree = float(n) * (2 ** max(depth - 1, 0)) * kk * cols
        return trees * per_tree

    def _fit_one_tree(self, X, S, C, static, key, precision):
        """Dispatch to the complete-tree or deep arena builder. ``X`` is the
        prepared-data dict (or a bare binned matrix); the deep builder
        additionally receives the feature-grouped histogram arrays when
        prepare_data staged them."""
        xb = X["xb"] if isinstance(X, dict) else X
        common = dict(
            n_bins=static["_n_bins"],
            min_samples_leaf=static["_msl"],
            max_features=static["_mf"] if static["_mf"] < xb.shape[1] else None,
            key=key,
            precision=precision,
            # classification stats are one_hot(y)*w columns that sum to the
            # count column exactly — derive it from the class histograms
            # instead of contracting an extra MXU row per node
            count_from_stats=self.task == "classification",
        )
        if static.get("_deep"):
            groups = None
            if isinstance(X, dict) and "xb_coarse" in X:
                groups = {kk: X[kk] for kk in
                          ("xb_cont", "xb_coarse", "fid_cont", "fid_coarse")}
            return build_tree_deep(
                xb, S, C, levels=static["_levels"], width=static["_W"],
                groups=groups, w_schedule=static.get("_wsched"),
                nb_schedule=static.get("_nb_sched"), **common
            )
        return build_tree(xb, S, C, depth=static["_depth"], **common)

    def _tree_predict(self, xq, tree, static):
        if static.get("_deep"):
            return predict_tree_deep(
                xq, tree, static["_levels"], static["_n_bins"]
            )
        return predict_tree(xq, tree, static["_depth"], static["_n_bins"])

    # trial-engine hook: bin once per bucket, share across trials/splits
    def prepare_data(self, X: np.ndarray, static: Dict[str, Any]):
        from ..ops.trees import COARSE_BINS

        edges = quantile_bins(np.asarray(X), static["_n_bins"])
        xb = np.asarray(bin_data(X, edges))
        out = {"X": np.asarray(X, np.float32), "xb": xb, "edges": edges}
        if static.get("_deep"):
            # feature-grouped histograms: low-cardinality columns (one-hot/
            # binary — quantile dedup gives them <= COARSE_BINS codes) go to
            # a narrow-bin group; per-level cost is linear in the bin total,
            # so this is ~3x fewer histogram MACs on Covertype (44/54
            # columns are binary) at an identical split-candidate set
            n_codes = 1 + np.isfinite(edges).sum(axis=1)
            coarse = n_codes <= COARSE_BINS
            if coarse.sum() >= 8 and (~coarse).sum() >= 1:
                fid_cont = np.where(~coarse)[0].astype(np.int32)
                fid_coarse = np.where(coarse)[0].astype(np.int32)
                out.update(
                    xb_cont=np.ascontiguousarray(xb[:, fid_cont]),
                    xb_coarse=np.ascontiguousarray(xb[:, fid_coarse]),
                    fid_cont=fid_cont,
                    fid_coarse=fid_coarse,
                )
        return out

    @staticmethod
    def _query_bins(params, X, static):
        """Accept either prepared data (dict with precomputed bins) or a raw
        feature matrix (artifact-inference path: bin via stored edges)."""
        if isinstance(X, dict):
            return X["xb"]
        return bin_data(X, params["edges"])

    # random_state seeds the forest/boosting PRNG: keep it (override the
    # base class's blanket ignore)
    ignored_params = ModelKernel.ignored_params - {"random_state"}


def _bootstrap_counts(key, w, n):
    """Exact bootstrap: n draws with replacement from rows where w>0.

    Uniform-over-active-rows multinomial via inverse-CDF searchsorted —
    O(n log n), unlike jax.random.categorical whose gumbel matrix is
    [draws, categories] = n x n (54 GB at Covertype scale).

    Counts are capped at 127 so classification histograms can ride the s8
    MXU path (ops/trees integer_stats). The cap is unreachable in
    practice: P(one specific row drawn >=128 times in n uniform draws)
    <= C(n,128)/n^128 < 1/128! ~ 1e-216 for any n the deep path sees."""
    active = (w > 0).astype(jnp.int32)
    caw = jnp.cumsum(active)
    n_active = caw[-1]
    targets = jax.random.randint(key, (n,), 1, jnp.maximum(n_active, 1) + 1)
    # the draws are made once and then searched: without the barrier XLA
    # fuses randint's remainder chain into the binary search's loop body
    # (every probe draws again; 29 s of TPU compile against 6, PR 32)
    caw, targets = jax.lax.optimization_barrier((caw, targets))
    rows = jnp.searchsorted(caw, targets, side="left")
    counts = jax.ops.segment_sum(
        jnp.ones((n,), jnp.float32), rows, num_segments=n
    )
    return jnp.minimum(counts, 127.0)


class _RandomForestBase(_TreeBase):
    _supports_deep = True  # sklearn RF default grows each tree to purity
    static_defaults = {
        "n_estimators": 100,
        "max_depth": None,
        "min_samples_leaf": 1,
        "min_samples_split": 2,
        "max_features": None,
        "bootstrap": True,
        "random_state": 0,
        "n_bins": 128,
        "criterion": "default",
        "min_weight_fraction_leaf": 0.0,
        "max_leaf_nodes": None,
        "min_impurity_decrease": 0.0,
        "oob_score": False,
        "ccp_alpha": 0.0,
        "max_samples": None,
        "monotonic_cst": None,
    }

    def _one_tree(self, X, S, C, static, key):
        boot_key, feat_key = jax.random.split(key)
        if static.get("bootstrap", True):
            counts = _bootstrap_counts(boot_key, C, S.shape[0])
        else:
            counts = (C > 0).astype(jnp.float32)
        return self._fit_one_tree(
            X,
            S * counts[:, None],
            C * counts,
            static,
            feat_key,
            # classification stats are small-integer counts x 0/1 one-hots —
            # exact in bf16, so the fast MXU path loses nothing; regression
            # stats are continuous y*w sums and need full f32
            (
                jax.lax.Precision.DEFAULT
                if self.task == "classification"
                else jax.lax.Precision.HIGHEST
            ),
        )

    def _tree_group(self, X, S, C, static, keys):
        """The trees of one group (``keys`` [T, 2]), stacked. A group of one
        is fitted as it stands: a size-1 vmap over the key makes the
        bootstrap's ``randint`` a draw with a batched key AND (under the
        split lanes) a batched bound, which the TPU compiler took 106 s
        over at a Covertype row count (PR 32; 4.5 s with the key as it is)."""
        if keys.shape[0] == 1:
            tree = self._one_tree(X, S, C, static, keys[0])
            return jax.tree_util.tree_map(lambda a: a[None], tree)
        return jax.vmap(lambda k: self._one_tree(X, S, C, static, k))(keys)

    def dispatch_attrs(self, static: Dict[str, Any], X) -> Dict[str, Any]:
        """What the chunked engine's ``executor.dispatch`` span says of one
        fit's shape: levels, the arena's width, and which histogram form
        (pallas / matmul / scatter) its levels take. Shapes only."""
        from ..ops.trees import COARSE_BINS, _resolve_hist_kernel, deep_hist_routes

        xb = X["xb"] if isinstance(X, dict) else X
        d, n_bins = int(xb.shape[1]), int(static["_n_bins"])
        integer = self.task == "classification"
        kk = max(int(static.get("_n_classes", 2)), 2) if integer else 2
        if static.get("_deep"):
            ds, nbs = (d,), (n_bins,)
            if isinstance(X, dict) and "xb_coarse" in X:
                ds = (int(X["xb_cont"].shape[1]), int(X["xb_coarse"].shape[1]))
                nbs = (n_bins, COARSE_BINS)
            levels, width = int(static["_levels"]), int(static["_W"])
            routes = deep_hist_routes(
                ds, nbs, levels=levels, width=width, n_bins=n_bins, kk=kk,
                integer_stats=integer, w_schedule=static.get("_wsched"),
                nb_schedule=static.get("_nb_sched"))
        else:
            levels = int(static["_depth"])
            width = 2 ** max(levels - 1, 0)
            routes = {_resolve_hist_kernel(integer, (d,), (n_bins,), kk): levels}
        return {
            "levels": levels, "arena_width": width,
            "hist_route": next(iter(routes)) if len(routes) == 1 else "mixed",
            "hist_levels_by_route": ",".join(f"{r}:{c}" for r, c in sorted(routes.items())),
        }

    def _tree_group_size(self, n: int, d: int, static: Dict[str, Any]) -> int:
        """Trees fitted CONCURRENTLY per sequential step (an inner vmap
        inside the tree loop). At small n the per-level ops are latency-
        bound, not bandwidth-bound — profiled on-device: lax.top_k cost is
        FLAT in the vmapped lane count, and the histogram's marginal
        per-lane cost is ~60% of its solo cost — so running trees one at a
        time wastes most of each level's fixed cost. The group is sized by
        a per-lane memory budget: at full-Covertype shapes (W=1024) the
        candidate-histogram buffers alone are GBs and T collapses to 1,
        which is also the bandwidth-bound regime where batching stops
        paying. Keys stay fold_in(t), so grouped, sequential, and chunked
        fits of one config produce bit-identical trees."""
        kk = (
            max(int(static.get("_n_classes", 2)), 2) + 1
            if self.task == "classification"
            else 2
        )
        n_bins = int(static.get("_n_bins", 128))
        if static.get("_deep"):
            W = int(static["_W"])
            route_w = W
        else:
            from ..ops.trees import _LOOKUP_M

            W = 2 ** max(int(static.get("_depth", 8)) - 1, 1)
            route_w = min(W, _LOOKUP_M)
        # per-tree working set: ~4 live candidate-histogram buffers
        # [2W, d, nb, kk] f32 + the [n, W] routing masks (~6 B/elem) +
        # per-row stat/leaf vectors
        per_tree_mb = (
            4.0 * 2 * W * d * n_bins * kk * 4
            + 6.0 * n * route_w
            + 16.0 * n * kk
        ) / 1e6
        # DEFAULT 64 MB => T=1 at every realistic shape: tree batching is a
        # MEASURED NEGATIVE on a v5e before this round (10% Covertype RF-100
        # steady: T=1 10.0 s, T=2 11.6 s, T=5 13.4 s — the batched levels'
        # histogram working set multiplies while none of the level ops turn
        # out to be latency-bound enough to amortize). The knob stays for
        # hardware where the trade differs; it keys trace_salt.
        budget = float(os.environ.get("CS230_TREE_GROUP_MB", 64))
        return int(max(1, min(8, budget / max(per_tree_mb, 1.0))))

    def _fit_forest(self, X, S, C, static):
        n_trees = int(static.get("n_estimators", 100))
        base_key = jax.random.PRNGKey(static["_seed"])
        xb = X["xb"] if isinstance(X, dict) else X
        T = self._tree_group_size(xb.shape[0], xb.shape[1], static)
        G = -(-n_trees // T)
        # per-tree keys via fold_in(t) — the SAME stream the chunked paths
        # use, so monolithic and chunked fits of one config are identical.
        # Padding trees (t >= n_trees) are fitted and sliced off (<= T-1
        # wasted fits per forest).
        keys = jax.vmap(lambda t: jax.random.fold_in(base_key, t))(
            jnp.arange(G * T)
        )
        fit_group = jax.vmap(lambda k: self._one_tree(X, S, C, static, k))
        out = jax.lax.map(
            fit_group, jax.tree_util.tree_map(
                lambda a: a.reshape(G, T, *a.shape[1:]), keys
            )
        )
        return jax.tree_util.tree_map(
            lambda a: a.reshape(G * T, *a.shape[2:])[:n_trees], out
        )

    # ---- chunked-fit protocol (parallel/trial_map.py chunked path) ----
    # A forest fit on a large dataset is one long sequential device program
    # (lax.map over trees); splitting the trees across several dispatches
    # bounds single-dispatch device time (remote-device RPC deadlines) and
    # lets full-depth trees run at any dataset size. Trees are independent,
    # so the cross-dispatch state is just the running sum of per-tree leaf
    # predictions for every row; eval finalizes the soft-vote mean.

    def chunked_plan(self, static, n, d, n_classes, n_splits, prepared=None):
        chunk_macs = float(os.environ.get("CS230_TREE_CHUNK_MACS", 4e13))
        trees = int(static.get("n_estimators", 100))
        # single source of truth for the histogram MAC formulas (complete
        # and deep-arena): the same estimate drives host placement and MFU
        macs = float(max(n_splits, 1)) * self.macs_estimate(n, d, static, prepared)
        n_chunks = int(np.ceil(macs / chunk_macs))
        if n_chunks <= 1:
            return None
        trees_per_chunk = int(np.ceil(trees / n_chunks))
        return {"n_chunks": int(np.ceil(trees / trees_per_chunk)),
                "trees_per_chunk": trees_per_chunk}

    def _stat_matrix(self, y, w, static):
        if self.task == "classification":
            c = max(int(static["_n_classes"]), 2)
            return jax.nn.one_hot(y, c, dtype=jnp.float32) * w[:, None], c
        return (y.astype(jnp.float32) * w)[:, None], 1

    def chunk_init(self, X, y, w, hyper, static):
        xb = X["xb"] if isinstance(X, dict) else X
        _, k = self._stat_matrix(y, w, static)
        return jnp.zeros((xb.shape[0], k), jnp.float32)

    def chunk_step(self, X, y, w, hyper, static, chunk_idx, state, plan):
        xb = X["xb"] if isinstance(X, dict) else X
        S, _ = self._stat_matrix(y, w.astype(jnp.float32), static)
        C = w.astype(jnp.float32)
        n_trees = int(static.get("n_estimators", 100))
        g = plan["trees_per_chunk"]
        base_key = jax.random.PRNGKey(static["_seed"])
        T = self._tree_group_size(xb.shape[0], xb.shape[1], static)
        G = -(-g // T)

        def one_group(carry, gi):
            i = gi * T + jnp.arange(T)
            t = chunk_idx * g + i
            keys = jax.vmap(lambda tt: jax.random.fold_in(base_key, tt))(t)
            trees = self._tree_group(X, S, C, static, keys)
            vals = jax.vmap(
                lambda tr: self._tree_predict(xb, tr, static)
            )(trees)  # [T, n, k]
            # i < g guards group padding (those ids belong to the NEXT
            # chunk, which will fit them itself — adding here would double
            # count); t < n_trees guards the final chunk's tail
            live = ((i < g) & (t < n_trees)).astype(jnp.float32)
            return carry + jnp.sum(live[:, None, None] * vals, axis=0), None

        state, _ = jax.lax.scan(one_group, state, jnp.arange(G))
        return state

    def chunk_eval(self, X, y, w_eval, hyper, static, state):
        from ..ops.metrics import (
            classification_score,
            margin_score,
            proba_score,
            regression_score,
            scoring_needs_margin,
            scoring_needs_proba,
            weighted_mse,
        )

        scoring = static.get("_scoring")
        n_trees = int(static.get("n_estimators", 100))
        mean = state / float(n_trees)
        if self.task == "classification":
            if scoring_needs_margin(scoring):
                return {"score": margin_score(scoring, y, mean[:, 1] - mean[:, 0], w_eval)}
            if scoring_needs_proba(scoring):
                proba = mean / jnp.maximum(
                    jnp.sum(mean, axis=-1, keepdims=True), 1e-12
                )
                return {"score": proba_score(
                    scoring, y, proba, w_eval, static.get("_n_classes", 2))}
            pred = jnp.argmax(mean, axis=-1).astype(jnp.int32)
            return {"score": classification_score(
                scoring, y, pred, w_eval, static.get("_n_classes", 2))}
        pred = mean[:, 0]
        return {
            "score": regression_score(scoring, y, pred, w_eval),
            "mse": weighted_mse(y, pred, w_eval),
        }

    # artifact materialization (trial_map.fit_single chunked branch)
    def fit_chunk(self, X, y, w, hyper, static, chunk_idx, carry, plan):
        w = w.astype(jnp.float32)
        S, _ = self._stat_matrix(y, w, static)
        g = plan["trees_per_chunk"]
        base_key = jax.random.PRNGKey(static["_seed"])
        xb = X["xb"] if isinstance(X, dict) else X
        T = self._tree_group_size(xb.shape[0], xb.shape[1], static)
        G = -(-g // T)
        idx = chunk_idx * g + jnp.arange(G * T)
        keys = jax.vmap(lambda t: jax.random.fold_in(base_key, t))(idx)
        trees = jax.lax.map(
            lambda ks: self._tree_group(X, S, w, static, ks),
            jax.tree_util.tree_map(
                lambda a: a.reshape(G, T, *a.shape[1:]), keys
            ),
        )
        trees = jax.tree_util.tree_map(
            lambda a: a.reshape(G * T, *a.shape[2:])[:g], trees
        )
        return carry, trees

    def assemble_artifact(self, trees, X, hyper, static, data_y, data_w):
        params = {"trees": trees}
        if isinstance(X, dict):
            params["edges"] = X["edges"]
        return params

    # ---- out-of-core row-block streaming (data/streaming.py) ----

    def stream_applicable(self, static: Dict[str, Any], n: int, d: int) -> bool:
        """Complete-tree classification forests only. The deep arena's
        frontier compaction keeps [n, W] routing masks resident and
        re-bins per level — not block-accumulable; regression float
        stats would trade the bitwise histogram guarantee for f32-order
        drift in the SPLITS themselves (not just the scores), so those
        families fall back to single-shot (or chunked) staging."""
        return (
            not static.get("_deep")
            and self.task == "classification"
            and int(static.get("_depth", 0)) >= 1
        )

    def stream_form(self, X_np, static: Dict[str, Any]):
        """Blocks are sliced from the prepared bin-code matrix (the only
        per-row array the builder reads); edges/X stay host-side."""
        xb = X_np["xb"] if isinstance(X_np, dict) else np.asarray(X_np)
        return np.ascontiguousarray(xb), ("xb", int(static["_n_bins"]))

    def stream_scores(self, streamer, y_pad, TW, EW, hyper_batch, static, n):
        """Block-accumulated forest fit + soft-vote accuracy over a
        RowBlockStreamer: (depth + 1) passes per tree via
        ops/trees.build_tree_streamed, which is BITWISE build_tree for
        these integer-stat histograms — per-tree splits and leaf values
        are identical to the single-shot path, per-tree keys stay
        ``fold_in(t)``, and bootstrap counts are drawn on the UNPADDED
        row range so the multinomial stream matches exactly. Prediction
        for the fitting rows reuses the builder's final node ids — a
        resident leaf lookup, no extra pass."""
        from ..ops.trees import build_tree_streamed

        c = max(int(static["_n_classes"]), 2)
        n_splits = int(TW.shape[0])
        n_pad = int(TW.shape[1])
        d = int(streamer.row_shape[0])
        depth = int(static["_depth"])
        n_bins = int(static["_n_bins"])
        mf = static["_mf"] if static["_mf"] < d else None
        n_trees = int(static.get("n_estimators", 100))
        base_key = jax.random.PRNGKey(static["_seed"])
        n_internal = 2**depth - 1

        def stream_pass(fn, carry, *consts):
            with streamer.pass_span("level"):
                for _i, start, blk in streamer.iter_blocks():
                    carry = fn(
                        carry, *consts, blk, jnp.asarray(start, jnp.int32),
                    )
            return carry

        y1 = jax.nn.one_hot(y_pad, c, dtype=jnp.float32)       # [n_pad, c]
        pad_zeros = jnp.zeros((n_pad - int(n),), jnp.float32)
        scores = np.zeros((n_splits,), np.float32)
        for s in range(n_splits):
            w = TW[s].astype(jnp.float32)
            Sw = y1 * w[:, None]
            acc = jnp.zeros((n_pad, c), jnp.float32)
            for t in range(n_trees):
                key = jax.random.fold_in(base_key, t)
                boot_key, feat_key = jax.random.split(key)
                if static.get("bootstrap", True):
                    counts = jnp.concatenate(
                        [_bootstrap_counts(boot_key, w[: int(n)], int(n)),
                         pad_zeros]
                    )
                else:
                    counts = (w > 0).astype(jnp.float32)
                tree, node = build_tree_streamed(
                    stream_pass,
                    Sw * counts[:, None],
                    w * counts,
                    d,
                    depth=depth,
                    n_bins=n_bins,
                    min_samples_leaf=static["_msl"],
                    max_features=mf,
                    key=feat_key,
                    precision=jax.lax.Precision.DEFAULT,
                    count_from_stats=True,
                )
                acc = acc + leaf_values(node - n_internal, tree["leaf_val"])
            mean = acc / float(n_trees)
            pred = jnp.argmax(mean, axis=-1).astype(jnp.int32)
            ew = EW[s].astype(jnp.float32)
            num = jnp.sum((pred == y_pad).astype(jnp.float32) * ew)
            scores[s] = float(streamer.wait(
                num / jnp.maximum(jnp.sum(ew), 1e-12)))
        # trials in one bucket share an identical static config (RF hypers
        # are static), so every trial of the chunk gets the same row
        n_t = len(next(iter(hyper_batch.values()))) if hyper_batch else 1
        return np.broadcast_to(scores, (max(int(n_t), 1), n_splits)).copy()

    def _forest_leaf_mean(self, params, xq, static):
        trees = params["trees"]
        n_trees = jax.tree_util.tree_leaves(trees)[0].shape[0]
        T = max(1, min(
            self._tree_group_size(xq.shape[0], xq.shape[1], static), n_trees
        ))
        G = -(-n_trees // T)

        def one(tree):
            return self._tree_predict(xq, tree, static)

        # wrap-around padding to G*T (pad can exceed n_trees for tiny
        # forests, so slice-padding is NOT enough); padded predictions are
        # sliced off before the mean
        idx = jnp.arange(G * T) % n_trees
        grouped = jax.tree_util.tree_map(
            lambda a: jnp.take(a, idx, axis=0).reshape(G, T, *a.shape[1:]),
            trees,
        )
        vals = jax.lax.map(jax.vmap(one), grouped)  # [G, T, nq, k]
        vals = vals.reshape(G * T, *vals.shape[2:])[:n_trees]
        return jnp.mean(vals, axis=0)


class RandomForestClassifierKernel(_RandomForestBase):
    name = "RandomForestClassifier"
    task = "classification"
    _mf_default = "sqrt"

    def fit(self, X, y, w, hyper: Dict[str, Any], static: Dict[str, Any]):
        c = max(int(static["_n_classes"]), 2)
        w = w.astype(jnp.float32)
        S = jax.nn.one_hot(y, c, dtype=jnp.float32) * w[:, None]
        trees = self._fit_forest(X, S, w, static)
        return self.assemble_artifact(trees, X, hyper, static, y, w)

    def predict(self, params, X, static: Dict[str, Any]):
        xq = self._query_bins(params, X, static)
        proba = self._forest_leaf_mean(params, xq, static)
        return jnp.argmax(proba, axis=-1).astype(jnp.int32)

    def predict_margin(self, params, X, static: Dict[str, Any]):
        """Binary margin = p(class 1) - p(class 0): monotone in the positive
        class probability, so rank metrics (roc_auc) match sklearn's
        predict_proba[:, 1] ranking."""
        xq = self._query_bins(params, X, static)
        proba = self._forest_leaf_mean(params, xq, static)
        return proba[:, 1] - proba[:, 0]

    def predict_proba(self, params, X, static: Dict[str, Any]):
        """Soft-vote mean of per-tree leaf class distributions (sklearn
        forest predict_proba semantics)."""
        xq = self._query_bins(params, X, static)
        proba = self._forest_leaf_mean(params, xq, static)
        return proba / jnp.maximum(jnp.sum(proba, axis=-1, keepdims=True), 1e-12)


class RandomForestRegressorKernel(_RandomForestBase):
    name = "RandomForestRegressor"
    task = "regression"
    _mf_default = 1.0

    def fit(self, X, y, w, hyper: Dict[str, Any], static: Dict[str, Any]):
        w = w.astype(jnp.float32)
        S = (y.astype(jnp.float32) * w)[:, None]
        trees = self._fit_forest(X, S, w, static)
        return self.assemble_artifact(trees, X, hyper, static, y, w)

    def predict(self, params, X, static: Dict[str, Any]):
        xq = self._query_bins(params, X, static)
        return self._forest_leaf_mean(params, xq, static)[:, 0]


#: the least hessian a row inside a stage's mask carries into the stage's
#: histograms and leaf sums; a row outside the mask carries none (the floor
#: comes before the mask, PR 34)
BOOST_HESSIAN_FLOOR = 1e-12


class _GradientBoostingBase(_TreeBase):
    """Boosting stages are sequential, so the chunked-fit state is the
    raw-score vector F carried across dispatches (chunk_step advances g
    stages; chunk_eval scores directly from F — no trees needed for the
    trial-search path). Subclasses provide ``_prior``/``_f0``/``_stage``."""

    def chunked_plan(self, static, n, d, n_classes, n_splits, prepared=None):
        chunk_macs = float(os.environ.get("CS230_TREE_CHUNK_MACS", 4e13))
        stages = int(static.get("n_estimators", 100))
        # Tiny node*kk contraction dims at the default depth 3 underfill the
        # MXU; the classifier additionally runs bf16 histograms (~1.6x
        # faster than the regressor's full-precision ones), so the weight
        # that keeps each dispatch's wall time in the RF-chunk envelope is
        # task-dependent. The raw MAC count is macs_estimate (also used for
        # host placement and MFU accounting).
        weight = 6.0 if self.task == "classification" else 10.0
        macs = weight * float(max(n_splits, 1)) * self.macs_estimate(n, d, static)
        n_chunks = int(np.ceil(macs / chunk_macs))
        if n_chunks <= 1:
            return None
        per_chunk = int(np.ceil(stages / n_chunks))
        return {"n_chunks": int(np.ceil(stages / per_chunk)),
                "trees_per_chunk": per_chunk}

    def _trees_per_stage(self, static) -> int:
        """One tree a stage, one a class past two classes."""
        nc = max(int(static.get("_n_classes", 2)), 2)
        return nc if (self.task == "classification" and nc > 2) else 1

    def macs_estimate(self, n, d, static):
        """Per-stage (grad, hess) histogram trees: k_eff trees of kk=2."""
        stages = int(static.get("n_estimators", 100))
        k_eff = self._trees_per_stage(static)
        depth = int(static.get("_depth", 3))
        n_bins = int(static.get("_n_bins", 128))
        return float(stages) * k_eff * n * (2 ** max(depth - 1, 0)) * 2 * d * n_bins

    def dispatch_attrs(self, static: Dict[str, Any], X) -> Dict[str, Any]:
        """What the chunked engine's ``executor.dispatch`` span says of one
        fit's shape: its sequential stages, and the levels each stage
        histograms (a complete tree of ``depth`` levels, one a class past
        two classes) by the form they take (pallas / matmul / scatter),
        which is what ``tpuml_tree_levels_total{route}`` counts. Shapes
        only."""
        from ..ops.trees import _resolve_hist_kernel

        xb = X["xb"] if isinstance(X, dict) else X
        route = _resolve_hist_kernel(
            False, (int(xb.shape[1]),), (int(static["_n_bins"]),), 2)
        levels = int(static["_depth"]) * self._trees_per_stage(static)
        return {"stages": int(static.get("n_estimators", 100)),
                "hist_levels_by_route": f"{route}:{levels}",
                # routing passes over the table a stage: one a level a tree,
                # the builder's own; the update is read off its leaf ids
                "route_levels": levels}

    def chunk_init(self, X, y, w, hyper, static):
        xb = X["xb"] if isinstance(X, dict) else X
        w = w.astype(jnp.float32)
        return self._f0(xb.shape[0], self._prior(y, w, static), static)

    def chunk_step(self, X, y, w, hyper, static, chunk_idx, state, plan):
        # same stage loop as fit_chunk; XLA dead-code-eliminates the
        # unused stacked trees under jit
        state, _ = self.fit_chunk(X, y, w, hyper, static, chunk_idx, state, plan)
        return state

    def chunk_eval(self, X, y, w_eval, hyper, static, state):
        from ..ops.metrics import (
            classification_score,
            margin_score,
            proba_score,
            regression_score,
            scoring_needs_margin,
            scoring_needs_proba,
            weighted_mse,
        )

        scoring = static.get("_scoring")
        # the learning curve's ``gmax`` channel: a leaf of every eval, laid
        # out stage by stage by the engine's sampled evals
        curve = {"curve_gmax": self._residual_max(y, state, w_eval, static)}
        if self.task == "classification":
            if scoring_needs_margin(scoring):
                # binary F keeps column 0 at zero, so the logit difference
                # is just F[:, 1] - F[:, 0]
                return {"score": margin_score(
                    scoring, y, state[:, 1] - state[:, 0], w_eval), **curve}
            if scoring_needs_proba(scoring):
                return {"score": proba_score(
                    scoring, y, jax.nn.softmax(state, axis=-1), w_eval,
                    static.get("_n_classes", 2)), **curve}
            pred = jnp.argmax(state, axis=-1).astype(jnp.int32)
            return {"score": classification_score(
                scoring, y, pred, w_eval, static.get("_n_classes", 2)), **curve}
        return {
            "score": regression_score(scoring, y, state, w_eval),
            "mse": weighted_mse(y, state, w_eval),
            **curve,
        }

    def _residual_max(self, y, F, w_eval, static):
        """The largest absolute pseudo-residual over the split's held-out
        rows: ``|y - p|`` of the carried scores (``|y - F|`` for a
        regressor), the functional gradient's largest component there. It
        is first-order in one row's raw score, where a split's accuracy
        moves only when a row crosses zero: what a reader of the curve (the
        benchmark's comparison among them) sees the fit's arithmetic by."""
        if self.task == "classification":
            c = max(int(static.get("_n_classes", 2)), 2)
            Y = jax.nn.one_hot(y, c, dtype=jnp.float32)
            if c > 2:
                R = jnp.max(jnp.abs(Y - jax.nn.softmax(F, axis=-1)), axis=-1)
            else:
                R = jnp.abs(Y[:, 1] - jax.nn.sigmoid(F[:, 1]))
        else:
            R = jnp.abs(y.astype(jnp.float32) - F)
        return jnp.max(jnp.where(w_eval > 0, R, 0.0))

    # artifact materialization (trial_map.fit_single chunked branch)
    def fit_chunk(self, X, y, w, hyper, static, chunk_idx, carry, plan):
        xb = X["xb"] if isinstance(X, dict) else X
        w = w.astype(jnp.float32)
        n_stages = int(static.get("n_estimators", 100))
        g = plan["trees_per_chunk"]
        base_key = jax.random.PRNGKey(static["_seed"])

        def one(F, i):
            t = chunk_idx * g + i
            key = jax.random.fold_in(base_key, t)
            F_new, trees = self._stage(xb, y, w, hyper, static, F, key)
            live = t < n_stages
            F_out = jax.tree_util.tree_map(
                lambda a, b: jnp.where(live, a, b), F_new, F
            )
            return F_out, trees

        carry, trees = jax.lax.scan(one, carry, jnp.arange(g))
        return carry, trees

    def assemble_artifact(self, trees, X, hyper, static, data_y, data_w):
        params = {
            "trees": trees,
            "prior": self._prior(data_y, data_w.astype(jnp.float32), static),
            "lr": jnp.asarray(hyper["learning_rate"], jnp.float32),
        }
        if isinstance(X, dict):
            params["edges"] = X["edges"]
        return params

    hyper_defaults = {"learning_rate": 0.1, "subsample": 1.0}
    static_defaults = {
        "n_estimators": 100,
        "max_depth": 3,
        "min_samples_leaf": 1,
        "min_samples_split": 2,
        "max_features": None,
        "random_state": 0,
        "n_bins": 128,
        "loss": "default",
        "criterion": "friedman_mse",
        "init": None,
        "alpha": 0.9,
        "validation_fraction": 0.1,
        "n_iter_no_change": None,
        "tol": 1e-4,
        "min_weight_fraction_leaf": 0.0,
        "max_leaf_nodes": None,
        "min_impurity_decrease": 0.0,
        "ccp_alpha": 0.0,
    }
    _mf_default = 1.0


class GradientBoostingClassifierKernel(_GradientBoostingBase):
    name = "GradientBoostingClassifier"
    task = "classification"

    def _prior(self, y, w, static):
        c = max(int(static["_n_classes"]), 2)
        Y = jax.nn.one_hot(y, c, dtype=jnp.float32)
        wsum = jnp.maximum(jnp.sum(w), 1e-12)
        return jnp.log(jnp.maximum(jnp.sum(Y * w[:, None], 0) / wsum, 1e-12))

    def _f0(self, n, prior, static):
        c = max(int(static["_n_classes"]), 2)
        if c > 2:
            return jnp.broadcast_to(prior, (n, c))
        return jnp.stack(
            [jnp.zeros(n), jnp.broadcast_to(prior[1] - prior[0], (n,))], axis=1
        )

    def _stage(self, xb, y, w, hyper, static, F, key):
        """One boosting stage: (F, key) -> (F', per-class trees)."""
        c = max(int(static["_n_classes"]), 2)
        n = xb.shape[0]
        depth, n_bins = static["_depth"], static["_n_bins"]
        lr = jnp.asarray(hyper["learning_rate"], jnp.float32)
        subsample = jnp.asarray(hyper["subsample"], jnp.float32)
        Y = jax.nn.one_hot(y, c, dtype=jnp.float32)
        leaf_scale = (c - 1) / c if c > 2 else 1.0
        sub_key, feat_key = jax.random.split(key)
        mask = (jax.random.uniform(sub_key, (n,)) < subsample).astype(jnp.float32) * w
        P = jax.nn.softmax(F, axis=-1) if c > 2 else jax.nn.sigmoid(F)
        # the hessian's floor comes before the mask: a row outside the mask
        # (another fold's, or not drawn by this stage's subsample) has no
        # hessian at all. Floored after it (until PR 34) every such row
        # carried 1e-12 into a cell of every level's histogram.
        if c > 2:
            G = (Y - P) * mask[:, None]
            H = jnp.maximum(P * (1.0 - P), BOOST_HESSIAN_FLOOR) * mask[:, None]
        else:
            G = (Y[:, 1:] - P[:, 1:]) * mask[:, None]
            H = jnp.maximum(P[:, 1:] * (1.0 - P[:, 1:]), BOOST_HESSIAN_FLOOR) * mask[:, None]

        def per_class(g, h, k2):
            # (tree, leaf of every row): the builder has routed the whole
            # table, rows outside the mask too, so the stage reads its
            # update off those ids and walks the finished tree no second time
            return build_tree_with_leaves(
                xb,
                g[:, None],
                h,
                depth=depth,
                n_bins=n_bins,
                min_samples_leaf=static["_msl"],
                max_features=static["_mf"] if static["_mf"] < xb.shape[1] else None,
                key=k2,
                # log-loss gradients/hessians are bounded in [-1, 1] and
                # boosting self-corrects split noise: bf16 histogram
                # matmuls measure ~1.6x faster with unchanged CV score
                # (regression keeps HIGHEST — residual magnitudes are
                # unbounded)
                precision=jax.lax.Precision.DEFAULT,
            )

        kdim = G.shape[1]
        keys = jax.random.split(feat_key, kdim)
        trees, leaves = jax.vmap(per_class, in_axes=(1, 1, 0))(G, H, keys)
        delta = jax.vmap(leaf_values)(leaves, trees["leaf_val"])[:, :, 0].T  # [n, kdim]
        if c > 2:
            F = F + lr * leaf_scale * delta
        else:
            F = F.at[:, 1].add(lr * delta[:, 0])
        return F, trees

    def fit(self, X, y, w, hyper: Dict[str, Any], static: Dict[str, Any]):
        xb = X["xb"] if isinstance(X, dict) else X
        n = xb.shape[0]
        w = w.astype(jnp.float32)
        n_stages = int(static.get("n_estimators", 100))
        base_key = jax.random.PRNGKey(static["_seed"])

        def stage(F, t):
            # fold_in(t) stage keys — identical stream to the chunked paths
            return self._stage(
                xb, y, w, hyper, static, F, jax.random.fold_in(base_key, t)
            )

        _, trees = jax.lax.scan(
            stage, self._f0(n, self._prior(y, w, static), static),
            jnp.arange(n_stages),
        )
        return self.assemble_artifact(trees, X, hyper, static, y, w)

    def _raw_scores(self, params, X, static: Dict[str, Any]):
        c = max(int(static["_n_classes"]), 2)
        depth, nbq = static["_depth"], static["_n_bins"]
        xq = self._query_bins(params, X, static)
        prior = params["prior"]
        lr = params["lr"]
        leaf_scale = (c - 1) / c if c > 2 else 1.0

        def per_stage(F, stage_trees):
            def upd(tree):
                return predict_tree(xq, tree, depth, nbq)[:, 0]

            delta = jax.vmap(upd)(stage_trees).T
            if c > 2:
                return F + lr * leaf_scale * delta, None
            return F.at[:, 1].add(lr * delta[:, 0]), None

        n = xq.shape[0]
        F0 = (
            jnp.broadcast_to(prior, (n, c))
            if c > 2
            else jnp.stack(
                [jnp.zeros(n), jnp.broadcast_to(prior[1] - prior[0], (n,))], axis=1
            )
        )
        F, _ = jax.lax.scan(per_stage, F0, params["trees"])
        return F

    def predict(self, params, X, static: Dict[str, Any]):
        return jnp.argmax(self._raw_scores(params, X, static), axis=-1).astype(jnp.int32)

    def predict_margin(self, params, X, static: Dict[str, Any]):
        F = self._raw_scores(params, X, static)
        return F[:, 1] - F[:, 0]

    def predict_proba(self, params, X, static: Dict[str, Any]):
        """Softmax over raw boosting scores (sklearn GBT predict_proba)."""
        return jax.nn.softmax(self._raw_scores(params, X, static), axis=-1)


class GradientBoostingRegressorKernel(_GradientBoostingBase):
    name = "GradientBoostingRegressor"
    task = "regression"

    def _prior(self, y, w, static):
        wsum = jnp.maximum(jnp.sum(w), 1e-12)
        return jnp.sum(y.astype(jnp.float32) * w) / wsum

    def _f0(self, n, prior, static):
        return jnp.full((n,), prior)

    def _stage(self, xb, y, w, hyper, static, F, key):
        n = xb.shape[0]
        depth, n_bins = static["_depth"], static["_n_bins"]
        lr = jnp.asarray(hyper["learning_rate"], jnp.float32)
        subsample = jnp.asarray(hyper["subsample"], jnp.float32)
        sub_key, feat_key = jax.random.split(key)
        mask = (jax.random.uniform(sub_key, (n,)) < subsample).astype(jnp.float32) * w
        g = (y.astype(jnp.float32) - F) * mask
        tree, leaf_local = build_tree_with_leaves(
            xb,
            g[:, None],
            mask,
            depth=depth,
            n_bins=n_bins,
            min_samples_leaf=static["_msl"],
            max_features=static["_mf"] if static["_mf"] < xb.shape[1] else None,
            key=feat_key,
        )
        F = F + lr * leaf_values(leaf_local, tree["leaf_val"])[:, 0]
        return F, tree

    def fit(self, X, y, w, hyper: Dict[str, Any], static: Dict[str, Any]):
        xb = X["xb"] if isinstance(X, dict) else X
        n = xb.shape[0]
        w = w.astype(jnp.float32)
        n_stages = int(static.get("n_estimators", 100))
        base_key = jax.random.PRNGKey(static["_seed"])

        def stage(F, t):
            # fold_in(t) stage keys — identical stream to the chunked paths
            return self._stage(
                xb, y, w, hyper, static, F, jax.random.fold_in(base_key, t)
            )

        _, trees = jax.lax.scan(
            stage, self._f0(n, self._prior(y, w, static), static),
            jnp.arange(n_stages),
        )
        return self.assemble_artifact(trees, X, hyper, static, y, w)

    def predict(self, params, X, static: Dict[str, Any]):
        depth, nbq = static["_depth"], static["_n_bins"]
        xq = self._query_bins(params, X, static)
        lr = params["lr"]

        def per_stage(F, tree):
            return F + lr * predict_tree(xq, tree, depth, nbq)[:, 0], None

        F0 = jnp.full((xq.shape[0],), params["prior"])
        F, _ = jax.lax.scan(per_stage, F0, params["trees"])
        return F


from .registry import register_kernel  # noqa: E402  (self-registration on import)

register_kernel(RandomForestClassifierKernel())
register_kernel(RandomForestRegressorKernel())
register_kernel(GradientBoostingClassifierKernel())
register_kernel(GradientBoostingRegressorKernel())

"""Interpret-mode parity for the fused level-histogram kernels
(ops/pallas_hist.py) vs the XLA one-hot matmul reference in ops/trees.py.

Runs on CPU: the Pallas kernel through its interpreter, the scatter
(segment-sum) form natively, and the CS230_HIST_KERNEL valve end to end
through a real tree fit — so tier-1 covers every histogram implementation
without a TPU.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from cs230_distributed_machine_learning_tpu.ops import trees as T
from cs230_distributed_machine_learning_tpu.ops.pallas_hist import (
    level_histogram_pallas,
    level_histogram_scatter,
    pallas_hist_applicable,
)


def _matmul_reference(local, xb, SC, W, nb, float_stats=False):
    """The pre-PR-6 one-hot matmul form, pinned as the parity reference
    regardless of what CS230_HIST_KERNEL routes to."""
    prec = jax.lax.Precision.HIGHEST if float_stats else None
    return T._level_histogram_multi(
        local, (xb,), SC, W, (nb,), prec, integer_stats=not float_stats
    )[0]


# (n, d, n_bins, n_nodes, kk): odd row counts, single-node levels, node
# counts straddling the 64-node block, narrow/wide bin axes
SHAPES = [
    (1000, 7, 16, 20, 4),
    (4097, 12, 24, 70, 8),
    (300, 3, 8, 1, 2),
    (513, 5, 32, 130, 3),
    (257, 2, 2, 9, 1),
]


@pytest.mark.parametrize("shape", SHAPES, ids=[str(s) for s in SHAPES])
def test_pallas_hist_matches_matmul_integer_stats(shape):
    """Integer stats (classification one-hots x bootstrap counts) must be
    BIT-exact across all three forms — including dead rows (id == W)."""
    n, d, nb, W, kk = shape
    rng = np.random.RandomState(0)
    local = jnp.asarray(rng.randint(0, W + 1, n).astype(np.int32))
    xb = jnp.asarray(rng.randint(0, nb, (n, d)).astype(np.int32))
    SC = jnp.asarray(rng.randint(0, 5, (n, kk)).astype(np.float32))
    want = np.asarray(_matmul_reference(local, xb, SC, W, nb))
    got_p = np.asarray(level_histogram_pallas(
        local, xb, SC, W, nb, integer_stats=True, interpret=True))
    got_s = np.asarray(level_histogram_scatter(local, xb, SC, W, nb))
    np.testing.assert_array_equal(got_p, want)
    np.testing.assert_array_equal(got_s, want)


def test_pallas_hist_float_stats_tolerance():
    """Float stats (boosting gradients/hessians) agree to f32
    summation-order tolerance with the HIGHEST-precision matmul form."""
    rng = np.random.RandomState(1)
    n, d, nb, W, kk = 2000, 6, 16, 30, 3
    local = jnp.asarray(rng.randint(0, W, n).astype(np.int32))
    xb = jnp.asarray(rng.randint(0, nb, (n, d)).astype(np.int32))
    SC = jnp.asarray(rng.randn(n, kk).astype(np.float32))
    want = np.asarray(_matmul_reference(local, xb, SC, W, nb, float_stats=True))
    got_p = np.asarray(level_histogram_pallas(local, xb, SC, W, nb, interpret=True))
    got_s = np.asarray(level_histogram_scatter(local, xb, SC, W, nb))
    scale = np.abs(want).max() + 1e-9
    assert np.abs(got_p - want).max() / scale < 1e-5
    assert np.abs(got_s - want).max() / scale < 1e-5


def test_pallas_hist_vmap_lanes():
    """The chunked tree protocol vmaps histograms over (trial, split)
    lanes — both kernels must compose with vmap (shared bin codes,
    batched node ids / stats)."""
    rng = np.random.RandomState(2)
    n, d, nb, W, kk, L = 900, 4, 8, 22, 3, 5
    xb = jnp.asarray(rng.randint(0, nb, (n, d)).astype(np.int32))
    locs = jnp.asarray(rng.randint(0, W + 1, (L, n)).astype(np.int32))
    SCs = jnp.asarray(rng.randint(0, 4, (L, n, kk)).astype(np.float32))
    want = jnp.stack([
        _matmul_reference(locs[i], xb, SCs[i], W, nb) for i in range(L)
    ])
    got_p = jax.vmap(
        lambda l, sc: level_histogram_pallas(
            l, xb, sc, W, nb, integer_stats=True, interpret=True)
    )(locs, SCs)
    got_s = jax.vmap(
        lambda l, sc: level_histogram_scatter(l, xb, sc, W, nb)
    )(locs, SCs)
    np.testing.assert_array_equal(np.asarray(got_p), np.asarray(want))
    np.testing.assert_array_equal(np.asarray(got_s), np.asarray(want))


def test_hist_kernel_valve_routes_and_agrees(monkeypatch):
    """CS230_HIST_KERNEL must actually switch the implementation inside
    _level_histogram_multi, and every setting must produce the same
    histogram for integer stats."""
    rng = np.random.RandomState(3)
    n, d, nb, W, kk = 1500, 5, 12, 17, 4
    local = jnp.asarray(rng.randint(0, W + 1, n).astype(np.int32))
    xb = jnp.asarray(rng.randint(0, nb, (n, d)).astype(np.int32))
    SC = jnp.asarray(rng.randint(0, 3, (n, kk)).astype(np.float32))
    outs = {}
    monkeypatch.setenv("CS230_PALLAS_INTERPRET", "1")
    for mode in ("matmul", "scatter", "pallas"):
        monkeypatch.setenv("CS230_HIST_KERNEL", mode)
        outs[mode] = np.asarray(
            T._level_histogram(local, xb, SC, W, nb, None, True)
        )
    np.testing.assert_array_equal(outs["matmul"], outs["scatter"])
    np.testing.assert_array_equal(outs["matmul"], outs["pallas"])


def test_hist_kernel_valve_full_tree_fit(monkeypatch):
    """End to end: a build_tree fit must produce the identical tree
    under every CS230_HIST_KERNEL setting (integer stats, fold-masked
    counts) — the valve is a pure implementation switch."""
    rng = np.random.RandomState(4)
    n, d, nb, depth, k = 2000, 6, 16, 4, 3
    X = rng.randn(n, d).astype(np.float32)
    y = rng.randint(0, k, n)
    edges = T.quantile_bins(X, nb)
    xb = T.bin_data(X, edges)
    S = jnp.asarray(np.eye(k, dtype=np.float32)[y])
    C = jnp.asarray((rng.rand(n) > 0.2).astype(np.float32))
    trees = {}
    monkeypatch.setenv("CS230_PALLAS_INTERPRET", "1")
    for mode in ("matmul", "scatter", "pallas"):
        monkeypatch.setenv("CS230_HIST_KERNEL", mode)
        jax.clear_caches()
        trees[mode] = jax.tree_util.tree_map(
            np.asarray,
            T.build_tree(
                xb, S * C[:, None], C, depth=depth, n_bins=nb,
                precision=None, count_from_stats=True,
            ),
        )
    for mode in ("scatter", "pallas"):
        for key in ("split_feat", "split_bin", "leaf_weight"):
            np.testing.assert_array_equal(
                trees["matmul"][key], trees[mode][key], err_msg=(mode, key)
            )


def test_pallas_hist_applicability_gate():
    """The static shape gate keeps ineligible shapes off the kernel (the
    auto route must fall back rather than blow the VMEM budget)."""
    assert pallas_hist_applicable(54, 24, 8)  # covertype production shape
    assert pallas_hist_applicable(54, 32, 7)
    # covertype at 64 bins: 18.4 MB against the 16 MB scoped-VMEM limit,
    # refused by the v5e compiler (tests/test_tpu_compile.py compiles it)
    assert not pallas_hist_applicable(54, 64, 7)
    assert not pallas_hist_applicable(784, 64, 8)  # MNIST-wide: page too big
    assert not pallas_hist_applicable(10, 512, 8)  # bins over the lane cap


# ---------------------------------------------------------------------------
# The matmul form's stat-major order and its undoing (PR 33). The left
# operand and the accumulators' rows are ``stat * n_nodes + node``; the
# result leaves as [n_nodes, d, n_bins, kk] through
# ``reshape(kk, n_nodes, d, n_bins).transpose(1, 2, 3, 0)``. A wrong undoing
# still has the right shape, so the scatter form (which never has the order)
# is the judge, over two feature groups of different bin counts in one call,
# a row count that is no multiple of the row chunk (the padded rows) and
# dead rows (id == n_nodes).
# ---------------------------------------------------------------------------

_N_ROWS, _GROUPS = 1237, ((5, 16), (9, 4))  # (columns, bins) a group


def _grouped_case(rng, n_nodes, kk, float_stats, lanes=None):
    lead = () if lanes is None else (lanes,)
    local = jnp.asarray(rng.randint(0, n_nodes + 1, lead + (_N_ROWS,)).astype(np.int32))
    xbs = tuple(jnp.asarray(rng.randint(0, nb, (_N_ROWS, d)).astype(np.int32))
                for d, nb in _GROUPS)
    stats = (rng.randn(*lead, _N_ROWS, kk) if float_stats
             else rng.randint(0, 5, lead + (_N_ROWS, kk)))
    return local, xbs, jnp.asarray(stats.astype(np.float32))


def _grouped(monkeypatch, mode, local, xbs, SC, n_nodes, float_stats):
    monkeypatch.setenv("CS230_HIST_KERNEL", mode)
    prec = jax.lax.Precision.HIGHEST if float_stats else None
    nbs = tuple(nb for _, nb in _GROUPS)

    def one(lo, sc):
        return T._level_histogram_multi(
            lo, xbs, sc, n_nodes, nbs, prec, integer_stats=not float_stats)

    # the split lanes as trial_map._run_chunked maps them: node ids and
    # stats batched, the bin codes shared
    Hs = one(local, SC) if local.ndim == 1 else jax.vmap(one)(local, SC)
    return [np.asarray(H) for H in Hs]


@pytest.mark.parametrize("float_stats", [False, True], ids=["int", "float"])
@pytest.mark.parametrize("lanes", [None, 3], ids=["plain", "vmap"])
@pytest.mark.parametrize("kk", [2, 7])
@pytest.mark.parametrize("n_nodes", [1, 17, 64])
def test_matmul_stat_major_order_matches_scatter(monkeypatch, n_nodes, kk, lanes, float_stats):
    """Bit-equal for integer stats (s8 x s8 -> s32), allclose for float
    stats (float32 operands, HIGHEST), whatever the frontier and however
    many stat columns; alone and under ``jax.vmap`` over split lanes, where
    the lane axis leads the operand and the accumulators and the order's
    undoing stays per lane."""
    monkeypatch.setattr(T, "_HIST_ROW_CHUNK", 512)  # 1237 rows: two whole chunks and a padded one
    lead = () if lanes is None else (lanes,)
    local, xbs, SC = _grouped_case(np.random.RandomState(n_nodes * 10 + kk), n_nodes, kk, float_stats, lanes)
    got = _grouped(monkeypatch, "matmul", local, xbs, SC, n_nodes, float_stats)
    want = _grouped(monkeypatch, "scatter", local, xbs, SC, n_nodes, float_stats)
    # every live row lands once a column: the stats' totals are the input's
    totals = np.where((np.asarray(local) < n_nodes)[..., None], np.asarray(SC), 0).sum(axis=-2)
    for g, w, (d, nb) in zip(got, want, _GROUPS):
        assert g.shape == lead + (n_nodes, d, nb, kk) and g.dtype == np.float32
        if float_stats:
            np.testing.assert_allclose(g, w, rtol=1e-5, atol=1e-5 * np.abs(w).max())
            np.testing.assert_allclose(g.sum(axis=(-4, -2)), np.broadcast_to(totals[..., None, :], lead + (d, kk)),
                                       rtol=1e-4, atol=1e-3)
        else:
            np.testing.assert_array_equal(g, w)
            np.testing.assert_array_equal(g.sum(axis=(-4, -2)), np.broadcast_to(totals[..., None, :], lead + (d, kk)))

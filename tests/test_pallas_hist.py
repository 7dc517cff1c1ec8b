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

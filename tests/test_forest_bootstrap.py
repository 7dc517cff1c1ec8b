"""The forest's bootstrap counts, ``models/trees.py::_bootstrap_counts``.

The function counts a tree's n draws in rank space and expands the counts
to rows with one gather. It must give the integers that an inverse-CDF
search of each draw gives, bit for bit: the trees, and the benchmark's
comparison with ``perfbench/references/RandomForestClassifier.py``, depend
on it. Each case checks it against that search written out here and against
the reference's own numpy form, on the key the forest draws a tree with.
"""

import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from cs230_distributed_machine_learning_tpu.models.trees import _bootstrap_counts

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SEED = 3300200077


def _reference():
    path = os.path.join(ROOT, "perfbench", "references", "RandomForestClassifier.py")
    spec = importlib.util.spec_from_file_location("rf_reference_bootstrap", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


REF = _reference()


def _searched(key, w, n):
    """Each draw's row by a binary search of the active rows' running
    count, then the rows counted."""
    active = (w > 0).astype(jnp.int32)
    caw = jnp.cumsum(active)
    targets = jax.random.randint(key, (n,), 1, jnp.maximum(caw[-1], 1) + 1)
    rows = jnp.searchsorted(caw, targets, side="left")
    counts = jax.ops.segment_sum(jnp.ones((n,), jnp.float32), rows, num_segments=n)
    return jnp.minimum(counts, 127.0)


def _weights(n, share, rng):
    """Fold weights over n rows, a ``share`` of them above zero; the
    positive ones are not all 1, since only ``w > 0`` may matter."""
    w = np.where(rng.random(n) < share, rng.choice([1.0, 2.0, 0.5], n), 0.0)
    return w.astype(np.float32)


def _one_row(n, row):
    w = np.zeros((n,), np.float32)
    w[row] = 1.0
    return w


#: (n, fold weights) a case; "padded_tail" is built as the streamed forest
#: builds it, the table's zero-weight pad rows after the n real ones
CASES = {
    "n1": lambda rng: _weights(1, 1.0, rng),
    "none_active": lambda rng: _weights(1000, 0.0, rng),
    "one_active": lambda rng: _one_row(1000, 417),
    "all_active": lambda rng: _weights(5000, 1.0, rng),
    "active_20pct": lambda rng: _weights(5000, 0.2, rng),
    "active_80pct": lambda rng: _weights(5000, 0.8, rng),
    "padded_tail": lambda rng: np.concatenate(
        [_weights(3000, 0.8, rng), np.zeros((512,), np.float32)]),
}
PADDED_ROWS = 3000

_counts = jax.jit(_bootstrap_counts, static_argnums=2)
_search = jax.jit(_searched, static_argnums=2)


@pytest.mark.parametrize("case", sorted(CASES))
def test_bootstrap_counts_equal_search_and_reference(case):
    w = CASES[case](np.random.default_rng(len(case)))
    boot_key, _ = REF.tree_keys(SEED, 3)
    if case == "padded_tail":
        n = PADDED_ROWS
        got = np.concatenate([np.asarray(_counts(boot_key, jnp.asarray(w[:n]), n)),
                              np.zeros((len(w) - n,), np.float32)])
        want = np.asarray(_search(boot_key, jnp.asarray(w[:n]), n))
        assert not got[n:].any()
        got = got[:n]
    else:
        n = len(w)
        got = np.asarray(_counts(boot_key, jnp.asarray(w), n))
        want = np.asarray(_search(boot_key, jnp.asarray(w), n))
    active = w[:n] > 0
    # the reference counts draws past the last row in a bin of its own,
    # which only a table with no active row fills
    ref = REF.bootstrap_counts(boot_key, active)[:n]
    assert got.dtype == np.float32 and got.shape == (n,)
    np.testing.assert_array_equal(got, want)
    np.testing.assert_array_equal(got, ref)
    assert not got[~active].any()
    # n draws in all, where the cap at 127 leaves them whole
    assert got.sum() == (0 if not active.any() else min(n, 127) if active.sum() == 1 else n)


def test_bootstrap_counts_under_vmap_over_keys_and_lanes():
    """As ``_one_tree`` runs it: mapped over tree keys and split lanes."""
    n, rng = 4000, np.random.default_rng(7)
    W = np.stack([_weights(n, share, rng) for share in (0.8, 0.8, 0.2)])
    keys = jnp.stack([REF.tree_keys(SEED, t)[0] for t in range(4)])
    per_lane = jax.vmap(lambda k, w: _bootstrap_counts(k, w, n), in_axes=(None, 0))
    got = np.asarray(jax.jit(jax.vmap(per_lane, in_axes=(0, None)))(keys, jnp.asarray(W)))
    assert got.shape == (4, 3, n)
    for t in range(4):
        for s in range(3):
            want = np.asarray(_search(keys[t], jnp.asarray(W[s]), n))
            np.testing.assert_array_equal(got[t, s], want)
            np.testing.assert_array_equal(got[t, s], REF.bootstrap_counts(keys[t], W[s] > 0))

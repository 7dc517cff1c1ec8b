"""Tests for the fused packed LogisticRegression path (ops/pallas_logreg.py).

Runs on CPU: the Pallas kernel itself in interpreter mode, the packed-path
solver via CS230_PALLAS_INTERPRET=1, both checked against the generic
vmapped engine path (which is itself parity-tested against sklearn in
test_search_parity.py).
"""

import dataclasses
import os

import numpy as np
import pytest

import jax.numpy as jnp

from cs230_distributed_machine_learning_tpu.models.base import TrialData
from cs230_distributed_machine_learning_tpu.models.registry import get_kernel
from cs230_distributed_machine_learning_tpu.ops.folds import build_split_plan
from cs230_distributed_machine_learning_tpu.ops.pallas_logreg import (
    fused_step_applicable,
    masked_softmax_grad,
    masked_softmax_grad_reference,
    packed_nesterov_step,
    packed_nesterov_step_reference,
    packed_softmax_grad,
    packed_softmax_grad_reference,
    slab_lanes,
)
from cs230_distributed_machine_learning_tpu.parallel import trial_map


def test_kernel_matches_reference_interpret():
    rng = np.random.RandomState(0)
    c, S, Tw, bm = 4, 3, 128, 256
    n_pad, dpp, n_wb = 512, 64, 2
    NB = c * S * Tw
    Ab = jnp.asarray(rng.randn(n_pad, dpp).astype(np.float32)).astype(jnp.bfloat16)
    W3 = jnp.asarray((rng.randn(n_wb, dpp, NB) * 0.2).astype(np.float32)).astype(
        jnp.bfloat16
    )
    y2 = jnp.asarray(rng.randint(0, c, (n_pad, 1)).astype(np.int32))
    WSP = jnp.asarray((rng.rand(n_pad, S) > 0.3).astype(np.float32))

    ref = np.asarray(packed_softmax_grad_reference(Ab, W3, y2, WSP, c=c, S=S, Tw=Tw))
    got = np.asarray(
        packed_softmax_grad(Ab, W3, y2, WSP, c=c, S=S, Tw=Tw, bm=bm, interpret=True)
    )
    scale = np.abs(ref).max() + 1e-9
    assert np.abs(got - ref).max() / scale < 5e-3


# (n_pad, dpp, c, cp, bm): odd-ish row/feature paddings, binary through
# 7-class, row tiles that don't divide 256
_MASKED_SHAPES = [
    (512, 128, 7, 128, 256),
    (256, 128, 2, 128, 128),
    (768, 256, 5, 128, 256),
    (1024, 128, 3, 256, 512),
]


@pytest.mark.parametrize("shape", _MASKED_SHAPES, ids=[str(s) for s in _MASKED_SHAPES])
def test_masked_lane_kernel_matches_reference_interpret(shape):
    """The fused masked-gradient lane kernel (fold mask applied in VMEM,
    bf16 Gram with f32 reduction) vs its XLA reference, at bf16 tolerance."""
    n_pad, dpp, c, cp, bm = shape
    rng = np.random.RandomState(0)
    Ab = jnp.asarray(rng.randn(n_pad, dpp).astype(np.float32)).astype(jnp.bfloat16)
    W = jnp.asarray((rng.randn(dpp, cp) * 0.3).astype(np.float32))
    W = W.at[:, c:].set(0.0).astype(jnp.bfloat16)
    y2 = jnp.asarray(rng.randint(0, c, (n_pad, 1)).astype(np.int32))
    wm = jnp.asarray((rng.rand(n_pad, 1) > 0.3).astype(np.float32))
    ref = np.asarray(masked_softmax_grad_reference(Ab, W, y2, wm, c=c))
    got = np.asarray(masked_softmax_grad(Ab, W, y2, wm, c=c, bm=bm, interpret=True))
    scale = np.abs(ref).max() + 1e-9
    assert np.abs(got - ref).max() / scale < 5e-3
    # padded class columns must stay exactly zero
    np.testing.assert_array_equal(got[:, c:], 0.0)


def test_masked_lane_kernel_vmap_fold_lanes():
    """vmap over (splits) and (trials x splits) — the engine's batching —
    with per-lane {0,1} fold masks and SHARED (unreplicated) A."""
    import jax

    rng = np.random.RandomState(1)
    n_pad, dpp, c, cp, bm, S, T = 512, 128, 3, 128, 256, 4, 2
    Ab = jnp.asarray(rng.randn(n_pad, dpp).astype(np.float32)).astype(jnp.bfloat16)
    y2 = jnp.asarray(rng.randint(0, c, (n_pad, 1)).astype(np.int32))
    Ws = jnp.asarray((rng.randn(T, S, dpp, cp) * 0.2).astype(np.float32))
    Ws = Ws.at[..., c:].set(0.0).astype(jnp.bfloat16)
    wms = jnp.asarray((rng.rand(S, n_pad, 1) > 0.25).astype(np.float32))

    def one(Wl, wl):
        return masked_softmax_grad(Ab, Wl, y2, wl, c=c, bm=bm, interpret=True)

    got = jax.vmap(jax.vmap(one, in_axes=(0, 0)), in_axes=(0, None))(Ws, wms)
    ref = jax.vmap(
        jax.vmap(
            lambda Wl, wl: masked_softmax_grad_reference(Ab, Wl, y2, wl, c=c),
            in_axes=(0, 0),
        ),
        in_axes=(0, None),
    )(Ws, wms)
    scale = float(jnp.abs(ref).max()) + 1e-9
    assert float(jnp.abs(got - ref).max()) / scale < 5e-3


def test_masked_reference_is_the_fused_formulation():
    """The reference's log-shift form (exp(z - lse + log w)) must equal
    the naive w * (softmax - onehot) gradient — including w == 0 rows and
    non-binary sample weights."""
    rng = np.random.RandomState(2)
    n, dpp, c, cp = 400, 64, 4, 8
    Ab = jnp.asarray(rng.randn(n, dpp).astype(np.float32))
    W = jnp.asarray((rng.randn(dpp, cp) * 0.5).astype(np.float32)).at[:, c:].set(0.0)
    y2 = jnp.asarray(rng.randint(0, c, (n, 1)).astype(np.int32))
    wm = jnp.asarray((rng.rand(n, 1) * 2.0 * (rng.rand(n, 1) > 0.3)).astype(np.float32))
    got = np.asarray(masked_softmax_grad_reference(Ab, W, y2, wm, c=c))
    Z = np.asarray(Ab) @ np.asarray(W)[:, :c]
    P = np.exp(Z - Z.max(1, keepdims=True))
    P /= P.sum(1, keepdims=True)
    Y = np.eye(c, dtype=np.float32)[np.asarray(y2)[:, 0]]
    want = np.asarray(Ab).T @ (np.asarray(wm) * (P - Y))
    np.testing.assert_allclose(got[:, :c], want, rtol=1e-4, atol=1e-3)
    assert not np.isnan(got).any()


def test_fit_fused_masked_grad_matches_legacy(monkeypatch):
    """models/logistic.py drivers under the CS230_MASKED_GRAD valve: the
    fused XLA formulation and the Pallas lane kernel (interpret) must
    reproduce the legacy masked-outside solver within bf16 solver
    tolerance, for both the grad-descent and _newton drivers."""
    import jax

    from cs230_distributed_machine_learning_tpu.models.registry import get_kernel

    rng = np.random.RandomState(3)
    n, d, c = 700, 8, 4
    X = rng.randn(n, d).astype(np.float32)
    wt = rng.randn(d, c).astype(np.float32)
    y = np.argmax(X @ wt + 0.6 * rng.randn(n, c), axis=1).astype(np.int32)
    w = (rng.rand(n) > 0.25).astype(np.float32)
    kernel = get_kernel("LogisticRegression")
    hyper = {
        "C": jnp.float32(1.0),
        "max_iter": jnp.float32(80),
        "tol": jnp.float32(1e-5),
    }

    # a forced Pallas kernel compiles; on the CPU backend only the
    # interpreter valve makes it runnable
    monkeypatch.setenv("CS230_PALLAS_INTERPRET", "1")

    def fit(mode, method):
        monkeypatch.setenv("CS230_MASKED_GRAD", mode)
        static = kernel.resolve_static(
            {"fit_intercept": True, "penalty": "l2"}, n, d, c
        )
        static = {**static, "_n_classes": c, "_method": method}
        jax.clear_caches()
        return np.asarray(
            kernel.fit(jnp.asarray(X), jnp.asarray(y), jnp.asarray(w), hyper, static)
        )

    for method in ("nesterov", "newton"):
        W_legacy = fit("legacy", method)
        W_fused = fit("xla", method)
        scale = np.abs(W_legacy).max() + 1e-9
        assert np.abs(W_fused - W_legacy).max() / scale < 5e-3, method
    W_pallas = fit("pallas", "nesterov")
    W_legacy = fit("legacy", "nesterov")
    scale = np.abs(W_legacy).max() + 1e-9
    assert np.abs(W_pallas - W_legacy).max() / scale < 5e-3


# ---------------- fused packed Nesterov step (ISSUE 10) ----------------


def _dead_columns(c, S, Tw):
    """[NB] bool: the lanes past ``S*Tw`` of each of the ``c`` class slabs."""
    return np.tile(np.arange(slab_lanes(S, Tw)) >= S * Tw, c)


def _fused_step_inputs(c, S, n_wb=2, n_pad=512, dpp=64, seed=0, Tw=128):
    """Random inputs on the packed layout: ``B`` is the padded slab, the
    weights of its dead columns are 0 as the layout's contract says, the
    per-column vectors carry ordinary values there."""
    rng = np.random.RandomState(seed)
    B = slab_lanes(S, Tw)
    NB = c * B
    live = ~_dead_columns(c, S, Tw)
    Ab = jnp.asarray(rng.randn(n_pad, dpp).astype(np.float32)).astype(
        jnp.bfloat16
    )
    W = jnp.asarray((rng.randn(n_wb, dpp, NB) * 0.2 * live).astype(np.float32))
    Wp = jnp.asarray((rng.randn(n_wb, dpp, NB) * 0.2 * live).astype(np.float32))
    y2 = jnp.asarray(rng.randint(0, c, (n_pad, 1)).astype(np.int32))
    WSP = jnp.asarray((rng.rand(n_pad, S) > 0.3).astype(np.float32))
    done = jnp.asarray((rng.rand(n_wb, B) > 0.7).astype(np.float32))
    step = jnp.asarray((0.01 + rng.rand(n_wb, B) * 0.1).astype(np.float32))
    Cb = jnp.asarray((0.1 + rng.rand(n_wb, B)).astype(np.float32))
    # mixed max_iter: half the columns sit AT/past the boundary (t >= 2)
    maxit = jnp.asarray(
        np.where(rng.rand(n_wb, B) > 0.5, 100.0, 2.0).astype(np.float32)
    )
    pen = np.ones((dpp, 1), np.float32)
    pen[-10:] = 0.0  # intercept/pad rows unpenalized
    return Ab, W, Wp, y2, WSP, done, step, Cb, maxit, jnp.asarray(pen), Tw


@pytest.mark.parametrize("c,S,lam", [(2, 3, 2.0), (7, 3, 1.0), (3, 2, 0.0)])
def test_fused_step_kernel_matches_reference_interpret(c, S, lam):
    """packed_nesterov_step (momentum + masked gradient + C/L2 scaling +
    max|G| reduce + done/max_iter-masked writeback, one VMEM pass) vs its
    pure-XLA reference — the legacy scan-body algebra on the same packed
    layout — at the bf16 Gram tolerance. Covers binary (doubled penalty),
    7-class, and the unpenalized (lam=0) form, with done-frozen columns
    and max_iter-boundary columns mixed in."""
    Ab, W, Wp, y2, WSP, done, step, Cb, maxit, pen, Tw = _fused_step_inputs(c, S)
    t = 3.0
    got = packed_nesterov_step(
        Ab, W, Wp, y2, WSP, t, done, step, Cb, maxit, pen,
        c=c, S=S, Tw=Tw, bm=256, lam=lam, interpret=True,
    )
    ref = packed_nesterov_step_reference(
        Ab, W, Wp, y2, WSP, t, done, step, Cb, maxit, pen,
        c=c, S=S, Tw=Tw, lam=lam,
    )
    for name, g, r in zip(("W_new", "Wp_new", "gmax"), got, ref):
        g, r = np.asarray(g), np.asarray(r)
        scale = np.abs(r).max() + 1e-9
        assert np.abs(g - r).max() / scale < 5e-3, name


@pytest.mark.parametrize("Tw", [16, 32, 64, 128])
@pytest.mark.parametrize("S", [4, 6, 11])
def test_fused_step_kernel_matches_reference_at_every_trial_block(S, Tw):
    """The widths ``packed_trial_block`` chooses among, at the benchmark's
    7 classes x 6 splits, at four splits (16 and 32 both fill one vreg a
    slab) and at eleven (every width padded; three classes, so an
    interpreted case stays in seconds): each class slab is ``S*Tw`` lanes
    rounded up to whole 128-lane vregs, the kernel's column arithmetic
    must agree with the reference's at every width, and a dead column
    comes back exactly 0."""
    c, lam = (3 if S == 11 else 7), 1.0
    Ab, W, Wp, y2, WSP, done, step, Cb, maxit, pen, _ = _fused_step_inputs(
        c, S, Tw=Tw
    )
    got = packed_nesterov_step(
        Ab, W, Wp, y2, WSP, 3.0, done, step, Cb, maxit, pen,
        c=c, S=S, Tw=Tw, bm=256, lam=lam, interpret=True,
    )
    ref = packed_nesterov_step_reference(
        Ab, W, Wp, y2, WSP, 3.0, done, step, Cb, maxit, pen,
        c=c, S=S, Tw=Tw, lam=lam,
    )
    B = slab_lanes(S, Tw)
    assert got[0].shape == W.shape and got[2].shape == (W.shape[0], B)
    dead = _dead_columns(c, S, Tw)
    assert dead.sum() == c * (B - S * Tw)
    for name, g, r in zip(("W_new", "Wp_new", "gmax"), got, ref):
        g, r = np.asarray(g), np.asarray(r)
        assert np.abs(g - r).max() / (np.abs(r).max() + 1e-9) < 5e-3, name
        # weights [n_wb, dpp, NB] and gmax [n_wb, B] both end on a lane axis
        assert not g[..., dead[: g.shape[-1]]].any(), name


def _kernel_eqns(jaxpr, out):
    """Every equation of a jaxpr and of the jaxprs in its parameters (the
    Pallas kernel body, ``pl.when`` branches, inner jits)."""
    for e in jaxpr.eqns:
        out.append(e)
        for v in e.params.values():
            for sub in v if isinstance(v, (list, tuple)) else [v]:
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    _kernel_eqns(sub, out)
    return out


@pytest.mark.parametrize("Tw", [16, 32, 64, 128])
def test_fused_step_slices_every_slab_on_a_vreg_boundary(Tw):
    """What the padding is for, read off the traced kernel: every static
    slice of a value and every window of a ref over the packed columns
    starts at a multiple of 128 lanes and is whole vregs wide; a padded
    slab builds its sample-weight tile without a lane concatenation, a
    dense one keeps the ``Tw``-wide pieces it always had."""
    import functools

    import jax

    c, dpp, bm = 7, 64, 256
    sds = jax.ShapeDtypeStruct
    for S in (4, 6, 11):
        B = slab_lanes(S, Tw)
        W = sds((1, dpp, c * B), jnp.float32)
        col = sds((1, B), jnp.float32)
        jaxpr = jax.make_jaxpr(functools.partial(
            packed_nesterov_step, c=c, S=S, Tw=Tw, bm=bm, lam=1.0,
        ))(
            sds((2 * bm, dpp), jnp.bfloat16), W, W, sds((2 * bm, 1), jnp.int32),
            sds((2 * bm, S), jnp.float32), sds((), jnp.float32),
            col, col, col, col, sds((dpp, 1), jnp.float32),
        )
        windows, lane_concats = [], []
        for e in _kernel_eqns(jaxpr.jaxpr, []):
            if not e.invars:  # iota, program_id
                continue
            name, shape = e.primitive.name, e.invars[0].aval.shape
            if name == "slice" and shape[-1] >= 128:
                start, limit = e.params["start_indices"][-1], e.params["limit_indices"][-1]
                windows.append((start, limit - start))
            elif name in ("get", "swap", "addupdate") and shape[-1] >= 128:
                (idx,) = jax.tree_util.tree_unflatten(
                    e.params["tree"], e.invars[1 if name == "get" else 2:]
                )
                windows.append((idx.indices[-1].start, idx.indices[-1].size))
            elif name == "concatenate" and e.params["dimension"] == len(shape) - 1:
                lane_concats.append([v.aval.shape[-1] for v in e.invars])
        assert len(windows) >= 4 * c, (S, Tw)  # logits, accumulator, epilogue
        assert all(start % 128 == 0 and size % 128 == 0
                   for start, size in windows), (S, Tw, windows)
        if B == S * Tw:
            assert lane_concats == [[Tw] * S], (S, Tw)
        else:
            assert lane_concats == [], (S, Tw)


def test_trial_block_holds_the_share_in_the_fewest_padded_columns():
    """Every width runs (slabs are padded to whole vregs), so the block of
    a share is the one that holds it with the fewest packed columns
    ``c * slab_lanes(S, Tw)``, the narrower on a tie; beyond the widest,
    whole blocks of 128."""
    from cs230_distributed_machine_learning_tpu.ops.pallas_logreg import (
        TRIAL_BLOCK, TRIAL_BLOCKS, packed_trial_block,
    )

    for S in (1, 2, 4, 6, 11):
        for Tw in TRIAL_BLOCKS:
            lanes = slab_lanes(S, Tw)
            assert lanes % 128 == 0 and 0 <= lanes - S * Tw < 128
        for trials in (1, 3, 16, 17, 32, 33, 64, 65, 128, 130, 1000):
            Tw = packed_trial_block(trials, S)
            assert Tw in TRIAL_BLOCKS
            assert Tw >= min(trials, TRIAL_BLOCK)  # it holds the share
            holds = [w for w in TRIAL_BLOCKS if w >= min(trials, TRIAL_BLOCK)]
            assert slab_lanes(S, Tw) == min(slab_lanes(S, w) for w in holds)
            assert not any(  # the narrower on a tie
                slab_lanes(S, w) == slab_lanes(S, Tw) for w in holds if w < Tw
            )
    # lanes a slab at six splits; at four, 16 and 32 both fill one vreg
    assert [slab_lanes(6, w) for w in TRIAL_BLOCKS] == [128, 256, 384, 768]
    assert [slab_lanes(4, w) for w in TRIAL_BLOCKS] == [128, 128, 256, 512]
    # the one-chip benchmark cell's geometry is what it was: 128 trials, one
    # dense block; the four-chip cell's share of 16 rides a block of 16
    assert packed_trial_block(128, 6) == TRIAL_BLOCK == 128
    assert packed_trial_block(16, 6) == 16 and packed_trial_block(16, 4) == 16
    kernel = get_kernel("LogisticRegression")
    assert kernel.batched_trial_block(128, 6) == kernel.batched_trial_multiple
    assert kernel.batched_slab(128, 6) == {"slab_lanes": 768, "slab_pad_lanes": 0}
    assert kernel.batched_slab(16, 6) == {"slab_lanes": 128, "slab_pad_lanes": 32}
    assert not hasattr(get_kernel("MLPClassifier"), "batched_slab")


def test_fused_step_freezes_done_and_past_max_iter_columns():
    """The writeback contract at the convergence-mask edges: a column with
    done == 1, or with t >= its max_iter, keeps W and Wp EXACTLY (the
    kernel must write the old values, not a near-copy)."""
    c, S = 3, 2
    Ab, W, Wp, y2, WSP, _, step, Cb, _, pen, Tw = _fused_step_inputs(c, S)
    n_wb, _, _ = W.shape
    B = slab_lanes(S, Tw)
    assert B == S * Tw  # a dense slab: every column is a real one
    done = jnp.zeros((n_wb, B), jnp.float32).at[:, ::3].set(1.0)
    maxit = jnp.full((n_wb, B), 100.0, jnp.float32).at[:, 1::3].set(5.0)
    t = 5.0  # AT the max_iter boundary: t < maxit is False for the 5.0 cols
    W_new, Wp_new, _ = packed_nesterov_step(
        Ab, W, Wp, y2, WSP, t, done, step, Cb, maxit, pen,
        c=c, S=S, Tw=Tw, bm=256, lam=1.0, interpret=True,
    )
    frozen = np.zeros(B, bool)
    frozen[::3] = True   # done
    frozen[1::3] = True  # past max_iter
    frozen_nb = np.tile(frozen, c)
    W_new, Wp_new = np.asarray(W_new), np.asarray(Wp_new)
    np.testing.assert_array_equal(W_new[:, :, frozen_nb], np.asarray(W)[:, :, frozen_nb])
    np.testing.assert_array_equal(Wp_new[:, :, frozen_nb], np.asarray(Wp)[:, :, frozen_nb])
    # active columns must actually move
    assert np.abs(W_new[:, :, ~frozen_nb] - np.asarray(W)[:, :, ~frozen_nb]).max() > 0


def test_fused_step_leaves_its_inputs_untouched():
    """W_new/Wp_new are fresh buffers (no input_output_aliases: the
    aliased form was wrong compiled on a v5e); the caller's arrays stay
    valid and un-mutated — two identical calls give identical results and
    the inputs keep their original values."""
    c, S = 2, 2
    Ab, W, Wp, y2, WSP, done, step, Cb, maxit, pen, Tw = _fused_step_inputs(c, S)
    W0 = np.asarray(W).copy()
    args = (Ab, W, Wp, y2, WSP, 2.0, done, step, Cb, maxit, pen)
    kw = dict(c=c, S=S, Tw=Tw, bm=256, lam=2.0, interpret=True)
    out1 = packed_nesterov_step(*args, **kw)
    out2 = packed_nesterov_step(*args, **kw)
    for a, b in zip(out1, out2):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    np.testing.assert_array_equal(np.asarray(W), W0)


def test_fused_step_vmem_gate():
    """auto-mode routing: the north-star shape fits, a dpp=512 block
    falls back to the legacy scan body."""
    NB = 7 * 6 * 128
    assert fused_step_applicable(64, NB, 256)
    assert not fused_step_applicable(512, NB, 256)


# ---------------- the occupancy table: empty column groups (ISSUE 36) -------


def _fold_weights(case, n_pad, S, bm, seed=0):
    """``WSP [n_pad, S]`` of the four shapes of fold plan: ``runs`` (split 0
    a random 80%, split k >= 1 holds out the k-th run of rows, as unshuffled
    stratified folds do: whole tiles empty for one split), ``shuffled`` (every
    split a random 80%: no empty group), ``tail`` (runs, and the last tile
    pad rows, weight 0 in every split), ``two_empty`` (runs, and the first
    tile empty for the last split too: the kernel leaves the lowest empty
    split out and computes the other's zeros), ``two_blocks`` (runs; the
    caller takes n_wb = 2)."""
    rng = np.random.RandomState(seed)
    w = (rng.rand(n_pad, S) > 0.2).astype(np.float32)
    if case != "shuffled":
        w[:, 1:] = 1.0
        run = n_pad // (S - 1)
        for k in range(1, S):
            w[(k - 1) * run : k * run, k] = 0.0
    if case == "tail":
        w[n_pad - bm :] = 0.0
    if case == "two_empty":
        w[:bm, S - 1] = 0.0
    return w


_FOLD_CASES = ("runs", "shuffled", "tail", "two_empty", "two_blocks")


@pytest.mark.parametrize("case", _FOLD_CASES[:4])  # two_blocks: runs' weights
@pytest.mark.parametrize("S", [4, 6])
def test_tile_occupancy_is_any_over_the_tile(case, S):
    from cs230_distributed_machine_learning_tpu.ops.pallas_logreg import (
        tile_occupancy, tile_skip_pct,
    )

    bm, n_tiles = 128, 2 * (S - 1) + 1
    w = _fold_weights(case, n_tiles * bm, S, bm)
    occ = tile_occupancy(jnp.asarray(w.T), bm=bm)
    assert occ.shape == (n_tiles,) and occ.dtype == jnp.int32
    want = (w.reshape(n_tiles, bm, S) != 0).any(axis=1)  # [n_tiles, S]
    got = (np.asarray(occ)[:, None] >> np.arange(S)) & 1
    np.testing.assert_array_equal(got, want)
    assert tile_skip_pct(occ, S) == pytest.approx(100.0 * (1 - want.mean()))
    if case == "shuffled":
        assert want.all()
    else:
        assert not want[:, 1:].all() and want[:, 0][: n_tiles - 1].all()
    if case == "tail":
        assert not want[-1].any()


@pytest.mark.parametrize("case", _FOLD_CASES)
@pytest.mark.parametrize("c", [2, 7])
@pytest.mark.parametrize("S,bm", [(4, 128), (6, 128), (6, 256)])
def test_fused_step_with_the_table_is_the_unskipped_body_bit_for_bit(S, bm, c, case):
    """At a block of 128 ``packed_nesterov_step`` with the occupancy table
    (a tile with an empty split runs a slab without its column group, a
    tile empty for all does nothing) against the unskipped whole-slab
    body, ``W``, ``Wp`` and ``gmax`` bit for bit over three steps: a
    skipped group would have added exact zeros. At a row tile of 128 and
    at the shipped one of 256 (the benchmark cell's six splits). This is
    the interpreter: the compiled kernel is held to the same comparison at
    the cell's shape by ``perfbench/tools/probe_logreg_skip_parity.py`` on
    the chip."""
    from cs230_distributed_machine_learning_tpu.ops.pallas_logreg import (
        tile_occupancy,
    )

    n_wb = 2 if case == "two_blocks" else 1
    n_pad = (2 * (S - 1) + 1) * bm
    Ab, W, Wp, y2, _, done, step, Cb, maxit, pen, Tw = _fused_step_inputs(
        c, S, n_wb=n_wb, n_pad=n_pad
    )
    w = _fold_weights(case, n_pad, S, bm)
    WSP = jnp.asarray(w)
    occ = tile_occupancy(WSP.T, bm=bm)
    if case != "shuffled":
        assert (np.asarray(occ) != (1 << S) - 1).any()
    plain, tabled = (W, Wp), (W, Wp)
    for t in (0.0, 1.0, 2.0):
        args = (y2, WSP, t, done, step, Cb, maxit, pen)
        kw = dict(c=c, S=S, Tw=Tw, bm=bm, lam=1.0, interpret=True)
        *plain, g0 = packed_nesterov_step(Ab, *plain, *args, **kw)
        *tabled, g1 = packed_nesterov_step(Ab, *tabled, *args, occ, **kw)
        for name, a, b in zip(("W", "Wp", "gmax"), (*plain, g0), (*tabled, g1)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b), err_msg=name)
    assert np.asarray(g0).any() and not np.array_equal(plain[0], W)


@pytest.mark.parametrize("Tw", [16, 32, 64, 128])
def test_only_a_block_of_128_branches_on_the_table(Tw):
    """The choice of body is made from ``Tw``, a shape: handed the table,
    the narrower blocks (a split is part of a vreg) trace the whole-slab
    body, their kernel holding the two ``pl.when`` it always had (first
    tile, last tile) and no scalar-prefetch operand; a block of 128 adds
    two (the whole slab, the slab less an empty split) and, without the
    table, none."""
    import functools

    import jax

    c, S, dpp, bm, n_pad = 7, 6, 64, 256, 1024
    sds = jax.ShapeDtypeStruct
    B = slab_lanes(S, Tw)
    W = sds((1, dpp, c * B), jnp.float32)
    col = sds((1, B), jnp.float32)
    args = (
        sds((n_pad, dpp), jnp.bfloat16), W, W, sds((n_pad, 1), jnp.int32),
        sds((n_pad, S), jnp.float32), sds((), jnp.float32),
        col, col, col, col, sds((dpp, 1), jnp.float32),
    )
    step = functools.partial(
        packed_nesterov_step, c=c, S=S, Tw=Tw, bm=bm, lam=1.0, interpret=True
    )

    def conds_and_operands(*a):
        eqns = _kernel_eqns(jax.make_jaxpr(step)(*a).jaxpr, [])
        (call,) = [e for e in eqns if e.primitive.name == "pallas_call"]
        return (sum(e.primitive.name == "cond" for e in eqns), len(call.invars))

    table = sds((n_pad // bm,), jnp.int32)
    assert conds_and_operands(*args) == (2, 11)
    assert conds_and_operands(*args, table) == (
        (4, 12) if Tw == 128 else (2, 11)
    )


def test_kernel_tells_the_dispatch_span_the_staged_tables_skip_share(monkeypatch):
    """The occupancy table is staged only where the step kernel reads it
    (the fused step at a block of 128), and ``dispatch_attrs`` of the
    kernel reads its skip share off the staged extras; 0.0 where none is
    staged (a narrower block, no block named, the legacy body)."""
    n, d, c, S = 2500, 5, 3, 3
    kernel, static, fn = _build_packed_fn(monkeypatch, "pallas", n, d, c, S)
    X, y, _, EW, hyper = _packed_fn_inputs(n, d, c, S, 128)
    w = _fold_weights("runs", 4096, S, 256)[:n]  # n_pad: 2 eval chunks of 2048
    TW = jnp.asarray(w.T)

    def specs(**kw):
        return kernel.batched_staged_extras(
            static=static, n=n, d=d, n_classes=c, n_splits=S,
            fold_signature=("runs", 36), **kw,
        )

    for narrow in ({}, {"block": 16}, {"block": 32}, {"block": 64}):
        assert set(specs(**narrow)) == {"_logreg_ab", "_logreg_lam_max"}, narrow
    wide = specs(block=128)
    assert set(wide) == {"_logreg_ab", "_logreg_lam_max", "_logreg_occ"}
    assert wide["_logreg_occ"][0] == ("occ", ("runs", 36), 4096, 256)
    ctx = {"X": X, "y": y, "TW": TW, "EW": EW}
    extras = {name: make(ctx) for name, (_, make) in wide.items()}
    tiles = np.pad(w, ((0, 4096 - n), (0, 0))).reshape(16, 256, S)
    want = 100.0 * (1 - (tiles != 0).any(axis=1).mean())
    assert 30 < want < 60  # a run a CV split, and the six pad tiles
    assert kernel.dispatch_attrs(static, X, extras) == {
        "tile_skip_pct": pytest.approx(want)
    }
    del extras["_logreg_occ"]
    assert kernel.dispatch_attrs(static, X, extras) == {"tile_skip_pct": 0.0}
    assert kernel.dispatch_attrs(static, X, {}) == {"tile_skip_pct": 0.0}
    # the staged table and the inline derivation give the same fit
    base = fn(X, y, TW, EW, hyper)
    staged = fn(X, y, TW, EW, {**hyper, "_logreg_occ": wide["_logreg_occ"][1](ctx)})
    for k in ("score", "curve_gmax"):
        np.testing.assert_array_equal(np.asarray(base[k]), np.asarray(staged[k]))
    monkeypatch.setenv("CS230_FUSED_STEP", "legacy")
    assert specs(block=128) == {}


def test_dispatch_span_carries_tile_skip_pct_and_the_reader_reads_it(monkeypatch):
    """Through the engine: a packed LogReg bucket's ``executor.dispatch``
    span says ``tile_skip_pct`` beside ``slab_lanes``, and the benchmark's
    reader (``perfbench/layer_metrics/logreg_tile_skip_pct.py``) averages
    it over the window's searches; a program without the attribute gives
    the reader nothing."""
    import importlib.util

    from cs230_distributed_machine_learning_tpu.obs import TRACER
    from cs230_distributed_machine_learning_tpu.obs.tracing import span

    monkeypatch.setenv("CS230_PALLAS_INTERPRET", "1")
    data = _toy(n=2500)
    kernel = get_kernel("LogisticRegression")
    plan = build_split_plan(data.y, task="classification")  # cv=5, unshuffled
    orig_resolve = kernel.resolve_static
    monkeypatch.setattr(
        kernel, "resolve_static",
        lambda *a: {**orig_resolve(*a), "_method": "nesterov"},
    )
    tiles = np.pad(np.asarray(plan.train_w), ((0, 0), (0, 4096 - 2500)))
    want = 100.0 * (1 - (tiles.reshape(6, 16, 256) != 0).any(axis=2).mean())
    assert want > 40  # six pad tiles of sixteen, and a tile or two a CV split

    def dispatch_attrs(job, tid, n_trials, plan=plan):
        TRACER.bind_job(job, tid)
        params = [{"C": 1.0, "tol": 1e-4, "max_iter": 3}] * n_trials
        with span("executor.batch", trace_id=tid):
            trial_map.run_trials(kernel, data, plan, params)
        (d,) = [s for s in TRACER.spans_for(tid) if s["name"] == "executor.dispatch"]
        assert d["attrs"]["engine"] == "packed" and "slab_lanes" in d["attrs"]
        return d["attrs"]

    narrow = dispatch_attrs("job-skip-36", "t1le5k1p00000036", 3)
    assert narrow["block"] == 16 and narrow["tile_skip_pct"] == 0.0
    wide = dispatch_attrs("job-skip-36w", "t1le5k1p000036aa", 65)
    assert wide["block"] == 128
    assert wide["tile_skip_pct"] == pytest.approx(want)
    # an unsigned plan's table is built anew for every search: the engine
    # does not wait for it on the host, and the span says nothing
    unsigned = dispatch_attrs("job-skip-36u", "t1le5k1p000036bb", 65,
                              dataclasses.replace(plan, signature=None))
    assert unsigned["block"] == 128 and "tile_skip_pct" not in unsigned

    spec = importlib.util.spec_from_file_location(
        "logreg_tile_skip_pct",
        os.path.join(os.path.dirname(__file__), "..", "perfbench",
                     "layer_metrics", "logreg_tile_skip_pct.py"),
    )
    reader = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(reader)
    assert reader.read({"searches": [{"job_id": "job-skip-36"}]}) == 0.0
    assert reader.read({"searches": [{"job_id": "never-traced"}]}) is None
    for i, share in enumerate((16.0, 17.0)):
        TRACER.bind_job(f"job-skip-36-{i}", f"t1le5k1p0000036{i}")
        TRACER.record({
            "trace_id": f"t1le5k1p0000036{i}", "span_id": "d0", "parent_id": None,
            "name": "executor.dispatch", "start": 1.79e9, "end": 1.79e9 + 1,
            "attrs": {"engine": "packed", "tile_skip_pct": share},
            "process": "pid:1",
        })
    two = {"searches": [{"job_id": "job-skip-36-0"}, {"job_id": "job-skip-36-1"}]}
    assert reader.read(two) == pytest.approx(16.5)


def _build_packed_fn(monkeypatch, mode, n, d, c, S, fit_intercept=True,
                     steps=12, chunk=128):
    """kernel.build_batched_fn under a CS230_FUSED_STEP mode, plus matching
    random inputs (n deliberately NOT a multiple of the 2048 eval row
    chunk, d NOT a multiple of 64 — the padded-geometry edges)."""
    import jax

    monkeypatch.setenv("CS230_PALLAS_INTERPRET", "1")
    monkeypatch.setenv("CS230_FUSED_STEP", mode)
    jax.clear_caches()
    kernel = get_kernel("LogisticRegression")
    static = {
        "fit_intercept": fit_intercept, "penalty": "l2",
        "_method": "nesterov", "_n_classes": c, "_iters": steps,
    }
    fn = kernel.build_batched_fn(
        static=static, n=n, d=d, n_classes=c, n_splits=S, chunk=chunk
    )
    assert fn is not None
    return kernel, static, fn


def _packed_fn_inputs(n, d, c, S, chunk, seed=0):
    rng = np.random.RandomState(seed)
    X = jnp.asarray(rng.randn(n, d).astype(np.float32))
    y = jnp.asarray(rng.randint(0, c, n).astype(np.int32))
    TW = jnp.asarray((rng.rand(S, n) > 0.3).astype(np.float32))
    EW = jnp.asarray((rng.rand(S, n) > 0.5).astype(np.float32))
    hyper = {
        "C": jnp.asarray(np.geomspace(0.05, 5.0, chunk).astype(np.float32)),
        "max_iter": jnp.asarray(
            np.where(np.arange(chunk) % 2, 60.0, 3.0).astype(np.float32)
        ),
        "tol": jnp.asarray(np.full(chunk, 1e-4, np.float32)),
    }
    return X, y, TW, EW, hyper


@pytest.mark.parametrize("folds", ["random", "runs"])
@pytest.mark.parametrize("c,fit_intercept", [(2, True), (7, True), (3, False)])
def test_packed_fn_fused_matches_legacy_scan_body(monkeypatch, c, fit_intercept, folds):
    """End-to-end packed fn (fit scan + eval) parity: CS230_FUSED_STEP=
    pallas vs legacy, across binary/7-class and fit_intercept on/off,
    with per-trial max_iter below the scan cap (mask edges exercised) and
    non-multiple n/d padding. At a block of 128 and the shipped row tile
    of 256 the fused step reads the occupancy table: the pad rows make
    tiles empty for every split (skipped whole), and ``runs`` (a CV split
    holds out a run of rows, as unshuffled stratified folds do) makes
    tiles empty for one, which run the slab less that split's column
    group; the legacy body computes every column of every tile."""
    n, d, S, chunk = 700, 5, 3, 128
    _, _, fn_legacy = _build_packed_fn(
        monkeypatch, "legacy", n, d, c, S, fit_intercept
    )
    X, y, TW, EW, hyper = _packed_fn_inputs(n, d, c, S, chunk)
    if folds == "runs":
        tw = np.ones((S, n), np.float32)
        tw[0] = np.asarray(TW)[0]
        tw[1, :256] = 0.0
        tw[2, 256:512] = 0.0
        TW = jnp.asarray(tw)
    score_legacy = np.asarray(fn_legacy(X, y, TW, EW, hyper)["score"])
    _, _, fn_fused = _build_packed_fn(
        monkeypatch, "pallas", n, d, c, S, fit_intercept
    )
    score_fused = np.asarray(fn_fused(X, y, TW, EW, hyper)["score"])
    assert score_fused.shape == (chunk, S)
    np.testing.assert_allclose(score_fused, score_legacy, atol=2e-3)


def test_packed_fn_staged_extras_bitwise(monkeypatch):
    """The staged forms (padded bf16 Ab, precomputed Lipschitz bound) fed
    through hyper must reproduce the inline derivation BITWISE — they are
    the same ops, hoisted."""
    n, d, c, S, chunk = 700, 5, 3, 3, 128
    kernel, static, fn = _build_packed_fn(monkeypatch, "pallas", n, d, c, S)
    X, y, TW, EW, hyper = _packed_fn_inputs(n, d, c, S, chunk)
    base = np.asarray(fn(X, y, TW, EW, hyper)["score"])

    specs = kernel.batched_staged_extras(
        static=static, n=n, d=d, n_classes=c, n_splits=S,
        fold_signature=("test", 1), block=chunk,
    )
    assert set(specs) == {"_logreg_ab", "_logreg_lam_max", "_logreg_occ"}
    ctx = {"X": X, "y": y, "TW": TW, "EW": EW}
    extras = {name: make(ctx) for name, (subkey, make) in specs.items()}
    assert extras["_logreg_ab"].dtype == jnp.bfloat16
    assert extras["_logreg_lam_max"].shape == (S,)
    assert extras["_logreg_occ"].shape == (2048 // 256,)
    assert extras["_logreg_occ"].dtype == jnp.int32
    with_extras = np.asarray(fn(X, y, TW, EW, {**hyper, **extras})["score"])
    np.testing.assert_array_equal(with_extras, base)


def test_packed_fn_sixteen_trials_alone_and_inside_a_block_of_128(monkeypatch):
    """The same 16 trials fitted at a chunk of 16 (a block of 16: slabs of
    48 real lanes padded to 128) and as the first 16 lanes of a chunk of
    128 (a dense block of 128): a trial's columns never meet another's,
    dead columns or not, so the scores agree bit for bit. On the chip the
    ``curve_gmax`` leaves do too (PERF.md, PR 31's width sweep); under the
    interpreter XLA's CPU matmul blocks its contraction by the operands'
    width, so the curves are held to a few units in the last place."""
    n, d, c, S = 700, 5, 3, 3
    kernel, _, fn128 = _build_packed_fn(monkeypatch, "pallas", n, d, c, S, chunk=128)
    assert kernel.batched_slab(128, S)["slab_pad_lanes"] == 0
    X, y, TW, EW, hyper = _packed_fn_inputs(n, d, c, S, 128)
    wide = fn128(X, y, TW, EW, hyper)
    _, _, fn16 = _build_packed_fn(monkeypatch, "pallas", n, d, c, S, chunk=16)
    assert kernel.batched_trial_block(16, S) == 16
    assert kernel.batched_slab(16, S) == {"slab_lanes": 128, "slab_pad_lanes": 80}
    alone = fn16(X, y, TW, EW, {k: v[:16] for k, v in hyper.items()})
    assert alone["score"].shape == (16, S)
    np.testing.assert_array_equal(
        np.asarray(alone["score"]), np.asarray(wide["score"][:16])
    )
    curve, curve_wide = (np.asarray(o["curve_gmax"][:16]) for o in (alone, wide))
    assert curve.shape == curve_wide.shape and curve.any()
    np.testing.assert_allclose(curve, curve_wide, rtol=1e-4, atol=0)


def test_packed_fn_legacy_mode_has_no_extras(monkeypatch):
    """CS230_FUSED_STEP=legacy restores the pre-fusion path bit-for-bit:
    no staged extras exist, everything is derived inline."""
    monkeypatch.setenv("CS230_FUSED_STEP", "legacy")
    kernel = get_kernel("LogisticRegression")
    static = {
        "fit_intercept": True, "penalty": "l2",
        "_method": "nesterov", "_n_classes": 3,
    }
    monkeypatch.setenv("CS230_PALLAS_INTERPRET", "1")
    assert kernel.batched_staged_extras(
        static=static, n=700, d=5, n_classes=3, n_splits=3,
        fold_signature=("sig",),
    ) == {}
    assert kernel.trace_salt()[1] == "legacy"


def _toy(n=600, d=9, n_classes=3, seed=0):
    rng = np.random.RandomState(seed)
    X = rng.randn(n, d).astype(np.float32)
    w_true = rng.randn(d, n_classes).astype(np.float32)
    y = np.argmax(X @ w_true + 0.5 * rng.randn(n, n_classes), axis=1).astype(np.int32)
    return TrialData(X=X, y=y, n_classes=n_classes)


def test_packed_path_matches_vmap_engine(monkeypatch):
    monkeypatch.setenv("CS230_PALLAS_INTERPRET", "1")
    data = _toy()
    plan = build_split_plan(data.y, task="classification", n_folds=3)
    kernel = get_kernel("LogisticRegression")
    params = [
        {"C": c, "tol": 1e-4, "max_iter": 60} for c in [0.01, 0.1, 1.0, 10.0]
    ]

    # force the nesterov/packed-eligible method for this small problem
    orig_resolve = kernel.resolve_static

    def force_nesterov(static, n, d, n_classes):
        out = orig_resolve(static, n, d, n_classes)
        return {**out, "_method": "nesterov"}

    monkeypatch.setattr(kernel, "resolve_static", force_nesterov)

    out_batched = trial_map.run_trials(kernel, data, plan, params)
    assert out_batched.n_dispatches == 1  # one fused call for the whole bucket

    monkeypatch.setattr(kernel, "batched_applicable", lambda *a, **kw: False)
    trial_map._compiled_cache.clear()
    out_vmap = trial_map.run_trials(kernel, data, plan, params)

    for mb, mv in zip(out_batched.trial_metrics, out_vmap.trial_metrics):
        assert mb["mean_cv_score"] == pytest.approx(mv["mean_cv_score"], abs=2e-3)
        assert mb["accuracy"] == pytest.approx(mv["accuracy"], abs=2e-3)


def test_packed_path_pads_partial_chunks(monkeypatch):
    """Trial counts that aren't a multiple of the 128-trial block still
    return exactly one result per requested trial."""
    monkeypatch.setenv("CS230_PALLAS_INTERPRET", "1")
    data = _toy(n=400, d=5, n_classes=2, seed=1)
    plan = build_split_plan(data.y, task="classification", n_folds=2)
    kernel = get_kernel("LogisticRegression")
    orig_resolve = kernel.resolve_static
    monkeypatch.setattr(
        kernel,
        "resolve_static",
        lambda s, n, d, c: {**orig_resolve(s, n, d, c), "_method": "nesterov"},
    )
    params = [{"C": c, "max_iter": 40} for c in np.logspace(-2, 1, 5)]
    out = trial_map.run_trials(kernel, data, plan, params)
    assert len(out.trial_metrics) == 5
    for m in out.trial_metrics:
        assert 0.0 <= m["mean_cv_score"] <= 1.0

"""Fused softmax-regression gradient as a Pallas TPU kernel.

The LogisticRegression north-star config (1000-trial RandomizedSearchCV on
Covertype, BASELINE.md) is HBM-bound on the pure-XLA path: every solver
iteration materializes the softmax probabilities tensor
``[trials, splits, n, classes]`` between the two matmuls, and with
``classes`` (7) as the minor dimension the layout pads to 128 lanes —
measured ~10 ms/iteration at 6.6 TF/s on v5e for a 64-trial x 6-split
batch. This kernel fuses the whole gradient:

    G[b] = A^T @ (w[b] * (softmax(A @ W[b]) - Y))     for all b = (trial, split)

streaming row tiles of the shared design matrix A through VMEM. The
probabilities never touch HBM.

Packing: all trials' weight columns are packed into one matrix with a
**class-major** column layout, ``col = a * Bp + s * Tw + t`` per block of
``Tw`` trials (a = class, s = split, t = trial-in-block), where the class
slab ``Bp = slab_lanes(S, Tw)`` is ``S * Tw`` rounded up to whole 128-lane
vregs. The grouped softmax over classes then becomes elementwise ops over
``c`` statically sliced ``[bm, Bp]`` tiles, each starting on a vreg
boundary — no lane shuffles, no padding of the class dimension.

Lanes ``[S*Tw, Bp)`` of every slab are **dead columns**: zero weights in,
sample weight 0 in the kernel (so residual and gradient are exactly 0 and
the weights stay 0), ``max|G|`` 0, dropped at unpack. Where ``S * Tw`` is
a multiple of 128 (six splits at 64 and 128 trials) there are none and the
layout is the dense ``(a * S + s) * Tw + t``.

Empty column groups. A fold weight is ``{0, 1}`` and masks the residual
after the logits matmul and the softmax, so a row tile in which split
``s`` trains on no row pays for split ``s``'s ``c * Tw`` columns and adds
exact zeros. Unshuffled stratified folds (sklearn's ``cv=k`` default) hold
runs of rows, so about one CV split in ``k`` is empty in every tile, and a
padded tail tile is empty for every split. ``tile_occupancy`` packs
``any(WSP[tile, s] != 0)`` into one int32 a row tile (bit ``s``), and where
a split's lanes are whole vregs (``tile_skip_applicable``: ``Tw % 128 ==
0``) ``packed_nesterov_step`` takes that table as a scalar-prefetch operand:
a tile with an empty split leaves that split's column group out and runs a
slab one group narrower (still one wide logits dot: a split at a time, in
dots of 128 columns, lost 54% on the v5e), a tile empty for every split
does nothing, every other tile runs the whole slab. A skipped group adds
nothing, which is what it added before: the outputs are bit-equal with and
without the table. At the narrower widths (16, 32, 64: a split is part of
a vreg) the table is ignored and the whole-slab body runs.

The streamed solver's block gradient (``streamed_softmax_grad``, the end
of this file) is the same arithmetic on another grid: one cached float32
row block a call, read as the device holds it, with the class groups on
the sublane axis of a transposed logits tile. It shares only the per-tile
softmax (``_class_residuals``) with the packed kernels.

Replaces (in effect) the per-trial sklearn fit of the reference worker
(``aws-prod/worker/worker.py:289-349``) for the LogisticRegression family;
see models/logistic.py for the solver that drives it.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

#: trials in the widest weight block; the packed block width is ``c * Bp``
TRIAL_BLOCK = 128
#: the widths ``packed_trial_block`` chooses among, narrowest first
TRIAL_BLOCKS = (16, 32, 64, TRIAL_BLOCK)


def slab_lanes(S: int, Tw: int) -> int:
    """Lanes of one class slab (``Bp``): ``S * Tw`` real (split, trial)
    columns rounded up to whole 128-lane vregs."""
    return -(-S * Tw // 128) * 128


def _sample_weight_slab(wsp_ref, bm: int, S: int, Tw: int):
    """The per-(sample, split, trial) weight tile ``[bm, Bp]``: split
    ``s``'s column of ``wsp_ref`` on lanes ``[s*Tw, (s+1)*Tw)``, 0 on a
    slab's dead lanes. A dense slab concatenates ``Tw``-wide broadcasts
    (six splits at ``Tw`` 64 and 128: half and whole vregs, the program it
    always was). In a padded slab (six splits at 16 and 32) such pieces
    are the relayout the padding is there to avoid: it selects by lane
    index instead."""
    Bp = slab_lanes(S, Tw)
    if Bp == S * Tw:
        return jnp.concatenate(
            [jnp.broadcast_to(wsp_ref[:, s : s + 1], (bm, Tw)) for s in range(S)],
            axis=1,
        )
    lane = jax.lax.broadcasted_iota(jnp.int32, (bm, Bp), 1)
    wexp = jnp.zeros((bm, Bp), jnp.float32)
    for s in reversed(range(S)):
        wexp = jnp.where(lane < (s + 1) * Tw, wsp_ref[:, s : s + 1], wexp)
    return wexp


def _class_residuals(z, c: int, onehot, wexp):
    """The per-tile softmax arithmetic every LogReg gradient kernel shares,
    so that they cannot drift: grouped softmax over ``c`` class tiles ->
    the weighted residual ``(p - y) * w`` of each class, in bfloat16 (the
    Gram dot's operand). A generator: class ``a_i``'s residual is formed
    when the caller asks for it, so a caller that dots each one before
    asking for the next holds one residual tile at a time.

    z       class -> f32 tile of that class's logits (elementwise across
            classes: each class is its own tile, no cross-lane reductions)
    onehot  class -> f32 ``y == class``, broadcastable against a tile
    wexp    f32 sample weight of every element of a tile
    yields  ``(class, residual tile)``
    """
    m = z(0)
    for a_i in range(1, c):
        m = jnp.maximum(m, z(a_i))
    es = [jnp.exp(z(a_i) - m) for a_i in range(c)]
    den = es[0]
    for a_i in range(1, c):
        den = den + es[a_i]
    rden = 1.0 / den
    for a_i, e in enumerate(es):
        y = onehot(a_i)
        yield a_i, ((e * rden - y) * wexp).astype(jnp.bfloat16)


def _softmax_gram(a, logits, L: int, c: int, yv, wexp, add):
    """The packed gradient bodies' tile: ``_class_residuals`` over the
    ``c`` class slabs -> one Gram dot a class, handed to ``add``.

    a       [bm, dpp]   bf16  design-matrix row tile
    logits  [bm, c*L]   f32   class-major: class ``a_i`` is the tile
                              ``[:, a_i*L : (a_i+1)*L]``
    yv      [bm, 1]     i32   labels for the tile rows
    wexp    [bm, L]     f32   sample weight of every column
    add     (class, [dpp, L] f32) -> None: accumulates the class's Gram tile
    """
    # per class: residual tile and its gradient contribution (c small dots
    # instead of one concat keeps everything statically sliced)
    for a_i, r in _class_residuals(
        lambda k: logits[:, k * L : (k + 1) * L], c,
        lambda k: (yv == k).astype(jnp.float32), wexp,  # [bm, 1] broadcasts
    ):
        add(a_i, jax.lax.dot_general(
            a,
            r,
            dimension_numbers=(((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        ))  # [dpp, L]


def _tile_softmax_gram(a, W, yv, wsp_ref, acc_ref, *, c: int, S: int, Tw: int):
    """Whole-slab (row-tile x weight-block) gradient body of ``_grad_kernel``
    and of ``_fused_step_kernel`` (every tile where no occupancy table
    rides along, the tiles where every split trains where one does):
    logits for every column in one dot -> ``_softmax_gram`` over the ``c``
    class slabs. ``_narrow_softmax_gram`` computes the same columns less
    one split's; the two MUST give bit-equal gradients (the
    fused-vs-legacy parity contract), which tests/test_pallas_logreg.py
    pins.

    a   [bm, dpp]      bf16  design-matrix row tile (shared by all trials)
    W   [dpp, NB]      bf16  packed weights operand, NB = c*Bp, class-major
    yv  [bm, 1]        i32   labels for the tile rows
    wsp_ref [bm, S]    f32   per-split {0,1} sample-weight ref
    acc_ref [1, dpp, NB] f32 accumulator block, revisited across row tiles

    ``B`` below is the slab ``Bp``: every slice starts on a vreg boundary.
    """
    B = slab_lanes(S, Tw)
    # logits for every (class, split, trial) column: one MXU pass, f32 out
    logits = jnp.dot(a, W, preferred_element_type=jnp.float32)  # [bm, NB]
    # per-(sample, split, trial) weight tile, broadcast from the S columns
    wexp = _sample_weight_slab(wsp_ref, a.shape[0], S, Tw)  # [bm, B]

    def add(a_i, g_a):
        acc_ref[0, :, a_i * B : (a_i + 1) * B] += g_a

    _softmax_gram(a, logits, B, c, yv, wexp, add)


def _narrow_softmax_gram(a, w_at, yv, wsp_ref, acc_ref, dead, *, c: int, S: int, Tw: int):
    """``_tile_softmax_gram`` over a slab one split narrower: split
    ``dead`` (a traced scalar), whose fold weights are all zero in this
    row tile, is left out, and the other ``S - 1`` splits' column groups
    (``Tw`` lanes each, whole vregs: ``tile_skip_applicable``) are laid
    side by side, so the logits stay one wide dot. The dead group's
    residual is ``(p - y) * 0``: exact zeros whose Gram product adds exact
    zeros to a float32 accumulator, so leaving its columns untouched gives
    the same bits; and a column's dot does not depend on which other
    columns share the matmul.

    w_at  sl -> bf16 ``[dpp, Tw]``: the packed weights operand's columns
          ``sl`` (a ``pl.ds`` of ``Tw`` lanes)
    """
    B = slab_lanes(S, Tw)
    bm, L = a.shape[0], (S - 1) * Tw
    # slot k holds split k below the dead one and k + 1 from it on
    after = [k >= dead for k in range(S - 1)]
    splits = [k + after[k].astype(jnp.int32) for k in range(S - 1)]

    def cols(a_i, s):
        return pl.ds(pl.multiple_of(a_i * B + s * Tw, 128), Tw)

    W = jnp.concatenate(
        [w_at(cols(a_i, s)) for a_i in range(c) for s in splits], axis=1
    )  # [dpp, c*L]
    logits = jnp.dot(a, W, preferred_element_type=jnp.float32)  # [bm, c*L]
    wexp = jnp.concatenate(
        [
            jnp.broadcast_to(
                jnp.where(after[k], wsp_ref[:, k + 1 : k + 2], wsp_ref[:, k : k + 1]),
                (bm, Tw),
            )
            for k in range(S - 1)
        ],
        axis=1,
    )  # [bm, L]

    def add(a_i, g_a):
        for k, s in enumerate(splits):
            acc_ref[0, :, cols(a_i, s)] += g_a[:, k * Tw : (k + 1) * Tw]

    _softmax_gram(a, logits, L, c, yv, wexp, add)


def _grad_kernel(a_ref, w_ref, y_ref, wsp_ref, g_ref, *, c: int, S: int, Tw: int):
    """One (weight-block, row-tile) grid step.

    a_ref   [bm, dpp]      bf16  design-matrix row tile (shared by all trials)
    w_ref   [1, dpp, NB]   bf16  packed weights, NB = c*Bp, class-major
    y_ref   [bm, 1]        i32   labels for the tile rows
    wsp_ref [bm, S]        f32   per-split {0,1} sample weights
    g_ref   [1, dpp, NB]   f32   output: A^T (w (P - Y)), accumulated over row tiles

    Always the whole-slab body, no group skipped: the parity reference of
    ``_fused_step_kernel``'s narrower slabs (bit-equal outputs).
    """
    i = pl.program_id(1)

    @pl.when(i == 0)
    def _init():
        g_ref[0] = jnp.zeros_like(g_ref[0])

    _tile_softmax_gram(a_ref[:], w_ref[0], y_ref[:], wsp_ref, g_ref, c=c, S=S, Tw=Tw)


@functools.partial(jax.jit, static_argnames=("c", "S", "Tw", "bm", "interpret"))
def packed_softmax_grad(
    Ab, W3, y2, WSP, *, c: int, S: int, Tw: int = TRIAL_BLOCK, bm: int = 256, interpret: bool = False
):
    """G3[wb] = A^T @ (w * (softmax(A @ W3[wb]) - Y)) for every packed column.

    Ab  [n_pad, dpp]       bf16, n_pad % bm == 0 (pad rows must have w == 0)
    W3  [n_wb, dpp, NB]    bf16, NB == c*Bp, column = a*Bp + s*Tw + t
    y2  [n_pad, 1]         i32
    WSP [n_pad, S]         f32
    returns G3 [n_wb, dpp, NB] f32 (dead columns exactly 0)
    """
    n_pad, dpp = Ab.shape
    n_wb, _, NB = W3.shape
    assert NB == c * slab_lanes(S, Tw), (NB, c, S, Tw)
    assert n_pad % bm == 0, (n_pad, bm)

    grid = (n_wb, n_pad // bm)
    kernel = functools.partial(_grad_kernel, c=c, S=S, Tw=Tw)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, dpp), lambda wb, i: (i, 0)),
            pl.BlockSpec((1, dpp, NB), lambda wb, i: (wb, 0, 0)),
            pl.BlockSpec((bm, 1), lambda wb, i: (i, 0)),
            pl.BlockSpec((bm, S), lambda wb, i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((1, dpp, NB), lambda wb, i: (wb, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((n_wb, dpp, NB), jnp.float32),
        interpret=interpret,
        name="packed_softmax_grad",
    )(Ab, W3, y2, WSP)


#: gate on the fused step's weight-resident blocks (W/Wp in + W/Wp out,
#: all f32 — 16 bytes per (row, packed column))
_FUSED_STEP_VMEM_BYTES = 8 * 1024 * 1024

#: scoped-VMEM limit the fused step asks of the compiler (v5e: 128 MiB
#: physical, 16 MiB default). At the gate's edge the double-buffered weight
#: blocks take 2 x 8 MiB and the row tile's [bm, NB] logits, exp tiles and
#: residual at most ~25 MB more (NB <= 8192 at dpp = 64); the rest is
#: headroom for operands XLA itself parks in VMEM — at small n it prefetches
#: the whole design matrix there, which overflowed the default limit.
_FUSED_STEP_VMEM_LIMIT = 64 * 1024 * 1024


def packed_trial_block(trials: int, S: int) -> int:
    """The weight block for a share of ``trials`` trials at ``S`` splits:
    the width in ``TRIAL_BLOCKS`` that holds the share with the fewest
    packed columns ``c * slab_lanes(S, Tw)``, the narrower on a tie,
    ``TRIAL_BLOCK`` at most. A slab never shrinks as ``Tw`` grows, so that
    is the narrowest width that holds the share. Kernel time follows the
    packed columns (v5e, 5M x 54, 7 classes, 6 splits, 100 steps, one
    block a fit: 9.53 s at 128 = 768 lanes a slab, 5.24 s at 64 = 384,
    3.80 s at 32 = 256, 2.33 s at 16 = 128), so a share of 16 in a block
    of 128 would spend seven eighths of the fit on padding lanes. At four
    splits 16 and 32 both make a slab of 128 and read 2.36 and 2.34 s.

    Every width runs because slabs are padded to whole vregs. Dense slabs
    off a vreg boundary lost what their columns saved: 5.98 s at 32 (192
    lanes a slab) and 7.23 s at 16 (96), against 5.24 s at 64."""
    return next((Tw for Tw in TRIAL_BLOCKS if Tw >= trials), TRIAL_BLOCK)


def fused_step_applicable(dpp: int, NB: int, bm: int = 256) -> bool:
    """VMEM gate for ``packed_nesterov_step``'s ``auto`` routing: the four
    f32 weight blocks (W/Wp in, W/Wp out) must fit the budget, which keeps
    the whole call inside ``_FUSED_STEP_VMEM_LIMIT``. Forced modes
    (``CS230_FUSED_STEP=pallas``) bypass this — tests run tiny shapes, and
    an operator forcing the kernel owns the consequences."""
    return 16 * dpp * NB + 2 * bm * dpp <= _FUSED_STEP_VMEM_BYTES


def tile_skip_applicable(S: int, Tw: int) -> bool:
    """Whether ``packed_nesterov_step`` can skip a split's column group:
    the split's ``Tw`` lanes of a class slab are whole vregs, there is a
    slab left without it, and the splits fit the bits of one int32.
    Narrower blocks (a split is part of a vreg) run the whole-slab body."""
    return Tw % 128 == 0 and 1 < S < 32


@functools.partial(jax.jit, static_argnames=("bm",))
def tile_occupancy(TWp, *, bm: int = 256):
    """The per-(row tile, split) occupancy table of ``packed_nesterov_step``:
    int32 ``[n_pad // bm]``, bit ``s`` of entry ``i`` set where any row of
    tile ``i`` has a non-zero weight in split ``s`` (one word a tile, so
    that the scalar-prefetch operand stays small in SMEM: 78 KB at 5M
    rows). It depends on the fold plan alone: made once per (dataset, fold
    plan) and staged (models/logistic.py).

    TWp [S, n_pad] f32  the fold weights split-major (``WSP.T``),
                        n_pad % bm == 0, S < 32
    """
    S, n_pad = TWp.shape
    occ = jnp.any(TWp.reshape(S, n_pad // bm, bm) != 0, axis=2)  # [S, n_tiles]
    return jnp.sum(
        occ.astype(jnp.int32) << jnp.arange(S, dtype=jnp.int32)[:, None], axis=0
    )


def tile_skip_pct(occ, S: int) -> float:
    """Share of the (row tile, split) column groups that ``occ`` lets the
    step kernel skip, in percent. A host read of the table: the device
    copies it once and the array keeps the copy."""
    words = np.asarray(occ)
    return 100.0 * (1.0 - int(np.bitwise_count(words).sum()) / (words.size * S))


def _fused_step_kernel(
    *refs, c: int, S: int, Tw: int, lam: float, n_tiles: int, skip: bool
):
    """One (weight-block, row-tile) grid step of the FULL Nesterov update.

    occ_ref   [n_tiles]      i32   (``skip`` only: scalar prefetch, SMEM)
                                   ``tile_occupancy``'s word a row tile
    a_ref     [bm, dpp]      bf16  design-matrix row tile (shared by all trials)
    w_ref     [1, dpp, NB]   f32   W, packed class-major (NB = c*Bp)
    wp_ref    [1, dpp, NB]   f32   W_prev
    y_ref     [bm, 1]        i32   labels for the tile rows
    wsp_ref   [bm, S]        f32   per-split {0,1} sample weights
    t_ref     [1, 1]         f32   iteration index t (SMEM scalar)
    done_ref  [1, 1, B]      f32   1.0 where the trial already converged
                                   (B = Bp, the padded slab, throughout)
    step_ref  [1, 1, B]      f32   per-(split, trial) step size
    cb_ref    [1, 1, B]      f32   per-trial C
    maxit_ref [1, 1, B]      f32   per-trial max_iter
    pen_ref   [dpp, 1]       f32   L2 penalty row mask (0 on intercept/pad)
    wout_ref  [1, dpp, NB]   f32   OUT W_new; doubles as the cross-tile Gram
                                   accumulator
    wpout_ref [1, dpp, NB]   f32   OUT Wp_new
    gmax_ref  [1, 1, B]      f32   OUT per-(split, trial) max|G|

    The per-column vectors carry a unit middle axis so their block's last
    two dims equal the array's (``(1, B)`` of ``[n_wb, 1, B]``): a
    ``(1, B)`` block of an ``[n_wb, B]`` array is refused by the TPU
    lowering whenever ``n_wb > 1`` (sublane dim must be 8-divisible or
    full).

    The look-ahead iterate ``V = W + mom*(W - Wp)`` is formed in VMEM from
    the resident W/Wp blocks each row tile (VPU-cheap next to the tile's
    MXU work) — V never exists in HBM. The raw gradient accumulates across
    row tiles in the wout block; the LAST tile's epilogue applies the
    per-trial C scaling + L2 penalty, reduces ``max|G|``, and performs the
    done/max_iter-masked W/Wp writeback.

    With ``skip`` the tile's word ``occ_ref[i]`` picks its body: every
    split occupied, the whole-slab body (``_tile_softmax_gram``), as in
    ``_grad_kernel``; none occupied (a padded tail tile), nothing; else
    ``_narrow_softmax_gram`` without the lowest empty split's column group,
    which would have added exact zeros (a second empty split of the same
    tile is computed as before: zeros; unshuffled stratified folds leave
    one). The look-ahead columns are formed inside the chosen branch.
    Without ``skip`` every tile runs the whole-slab body. The table is
    indexed by the row tile alone and shared by the weight blocks.

    The outputs are NOT aliased onto the W/Wp inputs. An earlier form
    (``input_output_aliases={1: 0, 2: 1}``) passed interpret-mode parity
    bit for bit and computed wrong weights compiled on a v5e: the
    accumulator block's write-backs land in the buffer the W input block
    is read from. The scan carry ping-pongs two buffers instead; HBM
    traffic on the weights is the same 4 passes.
    """
    occ_ref, refs = (refs[0], refs[1:]) if skip else (None, refs)
    (a_ref, w_ref, wp_ref, y_ref, wsp_ref, t_ref, done_ref, step_ref,
     cb_ref, maxit_ref, pen_ref, wout_ref, wpout_ref, gmax_ref) = refs
    i = pl.program_id(1)
    B = slab_lanes(S, Tw)
    t = t_ref[0, 0]
    mom = t / (t + 3.0)

    @pl.when(i == 0)
    def _init():
        wout_ref[0] = jnp.zeros_like(wout_ref[0])

    def look_ahead(sl=slice(None)):
        # columns of the look-ahead iterate, recomputed per tile from the
        # VMEM-resident blocks
        return (
            w_ref[0, :, sl] + mom * (w_ref[0, :, sl] - wp_ref[0, :, sl])
        ).astype(jnp.bfloat16)

    def whole_slab():
        # the tile's gradient, accumulating into the W_new output block
        Vb = look_ahead()
        _tile_softmax_gram(a_ref[:], Vb, y_ref[:], wsp_ref, wout_ref, c=c, S=S, Tw=Tw)

    if skip:
        occ = occ_ref[i]
        full = (1 << S) - 1
        pl.when(occ == full)(whole_slab)

        @pl.when(jnp.logical_and(occ != full, occ != 0))
        def _narrow_slab():
            # the lowest empty split: as many as the low bits that are all set
            dead = sum(
                ((occ & ((2 << s) - 1)) == (2 << s) - 1).astype(jnp.int32)
                for s in range(S)
            )
            _narrow_softmax_gram(
                a_ref[:], look_ahead, y_ref[:], wsp_ref, wout_ref, dead,
                c=c, S=S, Tw=Tw,
            )
    else:
        whole_slab()

    @pl.when(i == n_tiles - 1)
    def _epilogue():
        W = w_ref[0]
        Wp = wp_ref[0]
        V = W + mom * (W - Wp)  # f32 this time: the writeback operand
        cb = cb_ref[0]  # [1, B]
        step = step_ref[0]
        pen = pen_ref[:]  # [dpp, 1]
        active = jnp.logical_and(t < maxit_ref[0], done_ref[0] == 0.0)  # [1, B]
        gmax = None
        for a_i in range(c):
            sl = slice(a_i * B, (a_i + 1) * B)
            Vb_ = V[:, sl]
            G = cb * wout_ref[0, :, sl] + lam * (pen * Vb_)  # [dpp, B]
            gm = jnp.max(jnp.abs(G), axis=0, keepdims=True)  # [1, B]
            gmax = gm if gmax is None else jnp.maximum(gmax, gm)
            wout_ref[0, :, sl] = jnp.where(active, Vb_ - step * G, W[:, sl])
            wpout_ref[0, :, sl] = jnp.where(active, W[:, sl], Wp[:, sl])
        gmax_ref[0] = gmax


@functools.partial(
    jax.jit, static_argnames=("c", "S", "Tw", "bm", "lam", "interpret")
)
def packed_nesterov_step(
    Ab, W3, Wp3, y2, WSP, t, done, step_b, Cb, maxit_b, pen_col, occ=None,
    *, c: int, S: int, Tw: int = TRIAL_BLOCK, bm: int = 256,
    lam: float = 0.0, interpret: bool = False,
):
    """ONE full Nesterov iteration of the packed LogReg fit, fused.

    Replaces the legacy scan body's four XLA elementwise round-trips over
    the ``[n_wb, dpp, NB]`` weight tensors (momentum extrapolation, C/L2
    gradient scaling, the ``max|G|`` reduce, the done-masked writeback)
    with in-VMEM epilogues around the streamed softmax-Gram gradient.
    Per-iteration HBM traffic on the weight tensors drops from ~10 full
    f32 passes to 4 (W/Wp read + W/Wp write).

    Ab      [n_pad, dpp]     bf16  (n_pad % bm == 0; pad rows carry w == 0)
    W3      [n_wb, dpp, NB]  f32   NB == c*Bp, column = a*Bp + s*Tw + t,
                                   dead columns (lanes >= S*Tw of a slab) 0
    Wp3     [n_wb, dpp, NB]  f32
    y2      [n_pad, 1]       i32
    WSP     [n_pad, S]       f32
    t       scalar           f32   iteration index (momentum = t/(t+3))
    done    [n_wb, B]        f32   1.0 freezes the (split, trial) column;
                                   B = Bp here and below, any finite value
                                   on a dead column
    step_b  [n_wb, B]        f32   per-column step size
    Cb      [n_wb, B]        f32   per-column C
    maxit_b [n_wb, B]        f32   per-column max_iter
    pen_col [dpp, 1]         f32   L2 row mask (0 on intercept + pad rows)
    occ     [n_pad // bm]    i32   optional ``tile_occupancy(WSP.T, bm=bm)``:
                                   where ``tile_skip_applicable(S, Tw)`` the
                                   column groups it marks empty are skipped;
                                   ignored at the other widths
    lam     static float           L2 strength (0 disables the penalty)

    Returns ``(W_new, Wp_new, gmax)`` with shapes/dtypes of
    ``(W3, Wp3, [n_wb, B] f32)``; dead columns come back exactly 0 in all
    three. The three are bit-equal with and without ``occ``.
    """
    n_pad, dpp = Ab.shape
    n_wb, _, NB = W3.shape
    B = slab_lanes(S, Tw)
    assert NB == c * B, (NB, c, S, Tw)
    assert n_pad % bm == 0, (n_pad, bm)
    n_tiles = n_pad // bm

    skip = occ is not None and tile_skip_applicable(S, Tw)
    prefetch = (occ,) if skip else ()

    t2 = jnp.asarray(t, jnp.float32).reshape(1, 1)
    kernel = functools.partial(
        _fused_step_kernel, c=c, S=S, Tw=Tw, lam=float(lam), n_tiles=n_tiles,
        skip=skip,
    )
    # index maps also receive the scalar-prefetch refs, and ignore them
    row_tile = lambda wb, i, *_: (i, 0)  # noqa: E731
    w_block = lambda wb, i, *_: (wb, 0, 0)  # noqa: E731
    whole = lambda wb, i, *_: (0, 0)  # noqa: E731
    col_spec = pl.BlockSpec((1, 1, B), w_block)
    cols = [v.reshape(n_wb, 1, B) for v in (done, step_b, Cb, maxit_b)]
    W_new, Wp_new, gmax = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(prefetch),
            grid=(n_wb, n_tiles),
            in_specs=[
                pl.BlockSpec((bm, dpp), row_tile),
                pl.BlockSpec((1, dpp, NB), w_block),
                pl.BlockSpec((1, dpp, NB), w_block),
                pl.BlockSpec((bm, 1), row_tile),
                pl.BlockSpec((bm, S), row_tile),
                pl.BlockSpec((1, 1), whole, memory_space=pltpu.SMEM),
                col_spec,
                col_spec,
                col_spec,
                col_spec,
                pl.BlockSpec((dpp, 1), whole),
            ],
            out_specs=[
                pl.BlockSpec((1, dpp, NB), w_block),
                pl.BlockSpec((1, dpp, NB), w_block),
                col_spec,
            ],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((n_wb, dpp, NB), jnp.float32),
            jax.ShapeDtypeStruct((n_wb, dpp, NB), jnp.float32),
            jax.ShapeDtypeStruct((n_wb, 1, B), jnp.float32),
        ],
        interpret=interpret,
        name="packed_nesterov_step",
        **({} if interpret else {"compiler_params": pltpu.CompilerParams(
            vmem_limit_bytes=_FUSED_STEP_VMEM_LIMIT)}),
    )(*prefetch, Ab, W3, Wp3, y2, WSP, t2, *cols, pen_col)
    return W_new, Wp_new, gmax.reshape(n_wb, B)


def packed_nesterov_step_reference(
    Ab, W3, Wp3, y2, WSP, t, done, step_b, Cb, maxit_b, pen_col,
    *, c: int, S: int, Tw: int = TRIAL_BLOCK, lam: float = 0.0,
):
    """Pure-XLA reference of ``packed_nesterov_step`` — literally the
    legacy scan body's algebra (models/logistic.py pre-fusion) on the
    same packed layout, for parity tests."""
    n_wb, dpp, NB = W3.shape
    B = slab_lanes(S, Tw)
    t = jnp.asarray(t, jnp.float32)
    mom = t / (t + 3.0)
    V = W3 + mom * (W3 - Wp3)
    Graw = packed_softmax_grad_reference(
        Ab, V.astype(jnp.bfloat16), y2, WSP, c=c, S=S, Tw=Tw
    )
    cb_full = jnp.tile(Cb, (1, c))[:, None, :]  # [n_wb, 1, NB]
    step_full = jnp.tile(step_b, (1, c))[:, None, :]
    pen_row = pen_col.reshape(1, dpp, 1)
    G = cb_full * Graw + lam * pen_row * V
    gmax = jnp.max(jnp.abs(G).reshape(n_wb, dpp, c, B), axis=(1, 2))
    active = jnp.logical_and(t < maxit_b, done == 0.0)  # [n_wb, B]
    act = jnp.tile(active, (1, c))[:, None, :]
    W_new = jnp.where(act, V - step_full * G, W3)
    Wp_new = jnp.where(act, W3, Wp3)
    return W_new, Wp_new, gmax


def _masked_grad_kernel(a_ref, w_ref, y_ref, wm_ref, g_ref, *, c: int):
    """One row-tile grid step of the per-lane masked gradient.

    a_ref  [bm, dpp]  bf16  design-matrix row tile (shared by every lane)
    w_ref  [dpp, cp]  bf16  one lane's weights, classes zero-padded to cp
    y_ref  [bm, 1]    i32   labels for the tile rows
    wm_ref [bm, 1]    f32   per-(sample, split) {0,1} fold weight (or any
                            non-negative sample weight)
    g_ref  [dpp, cp]  f32   output accumulator, revisited across row tiles

    The fold mask streams through VMEM with the row tile and is applied to
    the residual *inside* the kernel — the masked copies of the
    probabilities / residual never exist in HBM. The Gram product
    ``A^T @ r`` runs with bf16 operands and f32 accumulation (the MXU's
    native mode), reduced across row tiles in the f32 output block.
    """
    i = pl.program_id(0)
    a = a_ref[:]
    logits = jnp.dot(a, w_ref[:], preferred_element_type=jnp.float32)  # [bm, cp]
    col = jax.lax.broadcasted_iota(jnp.int32, logits.shape, 1)
    # zero-padded weight columns produce logits == 0 which would pollute
    # the softmax: mask them to -inf-ish before the row max
    logits = jnp.where(col < c, logits, -1e30)
    m = jnp.max(logits, axis=-1, keepdims=True)
    e = jnp.exp(logits - m)
    p = e / jnp.sum(e, axis=-1, keepdims=True)
    onehot = (y_ref[:] == col).astype(jnp.float32)
    r = ((p - onehot) * wm_ref[:]).astype(jnp.bfloat16)  # [bm, cp], VMEM-only

    @pl.when(i == 0)
    def _init():
        g_ref[:] = jnp.zeros_like(g_ref)

    g_ref[:] += jax.lax.dot_general(
        a,
        r,
        dimension_numbers=(((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )


@functools.partial(jax.jit, static_argnames=("c", "bm", "interpret"))
def masked_softmax_grad(Ab, W, y2, wm, *, c: int, bm: int = 256, interpret: bool = False):
    """G = A^T @ (w * (softmax(A @ W) - Y)) for ONE (trial, split) lane.

    The generic (non-packed) drivers' masked gradient as a fused kernel:
    fold mask applied in-kernel, probabilities never materialized in HBM,
    Gram product in bf16 with f32 reduction. Composes with ``jax.vmap``
    (the engine's trials x splits batching adds grid dimensions).

    Ab [n_pad, dpp] bf16 (n_pad % bm == 0; pad rows must carry wm == 0)
    W  [dpp, cp]    bf16 (classes zero-padded to cp; cols >= c are ignored)
    y2 [n_pad, 1]   i32
    wm [n_pad, 1]   f32
    returns G [dpp, cp] f32 (cols >= c are zero)
    """
    n_pad, dpp = Ab.shape
    cp = W.shape[1]
    assert n_pad % bm == 0, (n_pad, bm)
    return pl.pallas_call(
        functools.partial(_masked_grad_kernel, c=c),
        grid=(n_pad // bm,),
        in_specs=[
            pl.BlockSpec((bm, dpp), lambda i: (i, 0)),
            pl.BlockSpec((dpp, cp), lambda i: (0, 0)),
            pl.BlockSpec((bm, 1), lambda i: (i, 0)),
            pl.BlockSpec((bm, 1), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((dpp, cp), lambda i: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((dpp, cp), jnp.float32),
        interpret=interpret,
        name="masked_softmax_grad",
    )(Ab, W, y2, wm)


def masked_softmax_grad_reference(Ab, W, y2, wm, *, c: int):
    """Pure-XLA reference of ``masked_softmax_grad`` (same padded layout).

    This is also the *fused-mask formulation* the solver uses on non-TPU
    backends: the fold weight folds into the softmax normalizer
    (``w * softmax(z) == exp(z - max) * (w / den)``), so a masked
    iteration replaces softmax's [n, c] divide with an [n, 1] divide and
    an [n, c] multiply — never costlier than an unmasked gradient, and no
    masked copy of the probabilities is ever materialized as a separate
    elementwise pass.
    """
    A = Ab.astype(jnp.float32)
    cp = W.shape[1]
    Z = A @ W.astype(jnp.float32)
    col = jnp.arange(cp)[None, :]
    Z = jnp.where(col < c, Z, -1e30)
    e = jnp.exp(Z - jnp.max(Z, axis=-1, keepdims=True))
    Pw = e * (wm / jnp.sum(e, axis=-1, keepdims=True))
    WY = jnp.where(y2 == col, wm, 0.0)
    return A.T @ (Pw - WY)


def packed_softmax_grad_reference(Ab, W3, y2, WSP, *, c: int, S: int, Tw: int = TRIAL_BLOCK):
    """Pure-XLA reference of the kernel (same packing), for parity tests."""
    n_pad, dpp = Ab.shape
    n_wb, _, NB = W3.shape
    B = slab_lanes(S, Tw)
    A = Ab.astype(jnp.float32)
    y = y2[:, 0]

    def one_block(W):  # [dpp, NB]
        logits = A @ W  # [n, NB]
        L = logits.reshape(n_pad, c, B)
        P = jax.nn.softmax(L, axis=1)
        onehot = jax.nn.one_hot(y, c, dtype=jnp.float32)  # [n, c]
        # [n, B]: split-major blocks, 0 on a slab's dead lanes
        wexp = jnp.pad(jnp.repeat(WSP, Tw, axis=1), ((0, 0), (0, B - S * Tw)))
        R = (P - onehot[:, :, None]) * wexp[:, None, :]
        return jnp.einsum("nd,ncb->dcb", A, R).reshape(dpp, NB)

    return jax.vmap(one_block)(W3)


# ---------------- the streamed solver's block gradient ----------------
#
# The streamed Nesterov solver (models/logistic.py::_stream_fns) makes one
# pass over the cached float32 row blocks a step, and a block's gradient is
# one call of the kernel below: the logits of every (trial, split, class)
# column, the grouped softmax, the weighted residual and A'R, row tile by
# row tile, with the logits never leaving VMEM. It reads the block as the
# stage cache holds it ([rows, d] float32, cast to bf16 a tile) and adds
# the intercept itself, so no XLA op copies or casts the block.
#
# Layout: transposed, so that the class groups lie on the sublane axis.
# The logits tile is Z' = V' A_tile' ([c*Lp, bm]); class ``a``'s group is
# rows ``[a*Lp, (a+1)*Lp)``, inside it row ``s*T + t`` is (trial t, split
# s), and ``Lp`` is S*T rounded up to a bf16 sublane tile (16): no column
# is padded to a 128-lane slab, and the [S, bm] tile of the rows-minor fold
# weights and the [1, bm] labels broadcast down the rows untransposed.
# The gradient is G' += R' A_tile ([c*Lp, d]).

#: scoped-VMEM limit the streamed block gradient asks of the compiler
#: (v5e: 128 MiB physical, 16 MiB default): the row tiles' buffers, the
#: residual scratch and the tile's temporaries; XLA places V' and G' in
#: VMEM beside them itself
_STREAM_GRAD_VMEM_LIMIT = 64 * 1024 * 1024
#: what ``stream_grad_applicable`` admits by ``stream_grad_vmem_bytes``:
#: three quarters of the v5e's VMEM. The deviceless v5e compile at the
#: streamed cell's block (269 451 x 784, 10 classes, six splits, a row tile
#: of 512) held up to 128 trials (an estimate of 123 MB) and ran out of
#: VMEM at 192 (183 MB)
_STREAM_GRAD_VMEM_BYTES = 96 * 1024 * 1024
#: the streamed kernel's row tile: one block-step of logreg_mnist8m.rs32
#: took 11.81 ms at 256, 10.38 at 512 and 10.34 at 1024 on a v5e (XLA's
#: form 19.29)
STREAM_GRAD_ROWS = 512


def stream_class_rows(S: int, T: int) -> int:
    """Rows of one class group of the streamed kernel's transposed logits
    (``Lp``): the ``S * T`` (split, trial) lanes rounded up to 16, a bf16
    sublane tile, so that every group starts on a tile boundary."""
    return -(-S * T // 16) * 16


def stream_grad_vmem_bytes(d: int, c: int, S: int, T: int,
                           bm: int = STREAM_GRAD_ROWS) -> int:
    """VMEM the streamed block gradient holds at a geometry: V' (bf16) and
    the G' accumulator (f32), lane-padded, the bias column and the
    intercept's partial sums, the float32 row tile twice (the pipeline's
    two buffers), and a tile's residual scratch beside four float32
    [c*Lp, bm] temporaries (logits, exponentials, their sum and scale)."""
    N = c * stream_class_rows(S, T)
    dl, d8 = -(-d // 128) * 128, -(-d // 8) * 8
    return (N * dl * (2 + 4) + 2 * N * 128 * 4 + 2 * d8 * bm * 4
            + N * bm * (2 + 4 * 4))


def stream_grad_applicable(d: int, c: int, S: int, T: int,
                           bm: int = STREAM_GRAD_ROWS) -> bool:
    """Whether a geometry's whole column block fits the kernel's VMEM: V'
    and G' are resident for the whole block, so more lanes or a wider table
    take the XLA form instead."""
    return stream_grad_vmem_bytes(d, c, S, T, bm) <= _STREAM_GRAD_VMEM_BYTES


def _stream_grad_kernel(at_ref, vt_ref, b_ref, y_ref, tw_ref, g_ref, gb_ref, r_scr,
                        *, rows: int, c: int, S: int, T: int, intercept: bool):
    """One row tile of a block's gradient, accumulated over the grid.

    at_ref  [d, bm]     f32   the block's row tile, transposed (the cached
                              block's own layout on the device); the ragged
                              last tile's columns past ``rows`` are garbage
    vt_ref  [N, d]      bf16  V', N = c*Lp, row a*Lp + s*T + t
    b_ref   [N, 1]      f32   the intercept row of V (bf16-rounded)
    y_ref   [1, bm]     i32   labels of the tile's rows
    tw_ref  [S, bm]     f32   fold weights of the tile's rows
    g_ref   [N, d]      f32   OUT G' = R' A, revisited across row tiles
    gb_ref  [N, 128]    f32   OUT the intercept row of G, as 128 partial
                              sums a column (``intercept`` only)
    r_scr   [N, bm]     bf16  the tile's residual R'

    Rows past ``rows`` are zeroed in A *and* in the weights (garbage times
    zero can be NaN); their labels are left as they are, since a weight of
    zero takes the label term out. Lanes ``[S*T, Lp)`` of a group have zero
    columns in V' and weight 0, so their residual is exactly 0.
    """
    i = pl.program_id(0)
    bm = at_ref.shape[1]
    Lp = vt_ref.shape[0] // c

    @pl.when(i == 0)
    def _init():
        g_ref[...] = jnp.zeros_like(g_ref)
        gb_ref[...] = jnp.zeros_like(gb_ref)

    keep = jax.lax.broadcasted_iota(jnp.int32, (1, bm), 1) < rows - i * bm
    at = jnp.where(keep, at_ref[...], 0.0).astype(jnp.bfloat16)
    tw = jnp.where(keep, tw_ref[...], 0.0)
    y = y_ref[...]
    zt = jnp.dot(vt_ref[...], at, preferred_element_type=jnp.float32)  # [N, bm]
    if intercept:
        zt = zt + b_ref[...]
    # split s's weights on rows [s*T, (s+1)*T) of a group, 0 from S*T on
    row = jax.lax.broadcasted_iota(jnp.int32, (Lp, bm), 0)
    wexp = jnp.zeros((Lp, bm), jnp.float32)
    for s in reversed(range(S)):
        wexp = jnp.where(row < (s + 1) * T, tw[s : s + 1, :], wexp)
    for a_i, r in _class_residuals(
        lambda k: zt[k * Lp : (k + 1) * Lp], c,
        lambda k: (y == k).astype(jnp.float32), wexp,
    ):
        r_scr[a_i * Lp : (a_i + 1) * Lp, :] = r
        if intercept:
            rf = r.astype(jnp.float32)
            part = rf[:, 0:128]
            for k in range(1, bm // 128):
                part = part + rf[:, k * 128 : (k + 1) * 128]
            gb_ref[a_i * Lp : (a_i + 1) * Lp, :] += part
    g_ref[...] += jax.lax.dot_general(
        r_scr[...], at, dimension_numbers=(((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    )  # [N, d]


@functools.partial(
    jax.jit, static_argnames=("c", "fit_intercept", "bm", "interpret")
)
def streamed_softmax_grad(blk, V, yb, wb, *, c: int, fit_intercept: bool,
                          bm: int = STREAM_GRAD_ROWS, interpret: bool = False):
    """One row block's gradient ``A' (w * (softmax(A V) - Y))`` for every
    (trial, split) lane, ``A`` the block with the intercept column where
    ``fit_intercept``: bf16 operands into float32 accumulators, float32
    softmax and weights.

    blk [rows, d]         f32  the cached block, passed to the kernel as it
                               is, transposed: on a TPU the device keeps a
                               [rows, d] array rows-minor, so ``blk.T`` is
                               a bitcast (any height: the ragged last row
                               tile is masked in the kernel, not padded here)
    V   [T, S, dp, c]     f32  the look-ahead iterate, dp = d + intercept
    yb  [rows]            i32  the block's labels
    wb  [S, rows]         f32  the block's fold weights (0 on pad rows)
    returns [T, S, dp, c] f32
    """
    rows, d = blk.shape
    T, S, dp, _ = V.shape
    L, Lp = S * T, stream_class_rows(S, T)
    N = c * Lp
    Vt = jnp.transpose(V, (3, 1, 0, 2)).reshape(c, L, dp)
    Vt = jnp.pad(Vt, ((0, 0), (0, Lp - L), (0, 0))).reshape(N, dp).astype(jnp.bfloat16)
    bias = (Vt[:, d:] if fit_intercept else jnp.zeros((N, 1))).astype(jnp.float32)
    whole = lambda i: (0, 0)  # noqa: E731
    lanes = lambda i: (0, i)  # noqa: E731
    gt, gb = pl.pallas_call(
        functools.partial(_stream_grad_kernel, rows=rows, c=c, S=S, T=T,
                          intercept=bool(fit_intercept)),
        grid=(pl.cdiv(rows, bm),),
        in_specs=[
            pl.BlockSpec((d, bm), lanes),
            pl.BlockSpec((N, d), whole),
            pl.BlockSpec((N, 1), whole),
            pl.BlockSpec((1, bm), lanes),
            pl.BlockSpec((S, bm), lanes),
        ],
        out_specs=[pl.BlockSpec((N, d), whole), pl.BlockSpec((N, 128), whole)],
        out_shape=[
            jax.ShapeDtypeStruct((N, d), jnp.float32),
            jax.ShapeDtypeStruct((N, 128), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((N, bm), jnp.bfloat16)],
        interpret=interpret,
        name="streamed_softmax_grad",
        **({} if interpret else {"compiler_params": pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=_STREAM_GRAD_VMEM_LIMIT)}),
    )(blk.T, Vt[:, :d], bias, yb.reshape(1, rows), wb)
    cols = [gt] + ([jnp.sum(gb, axis=1, keepdims=True)] if fit_intercept else [])
    G = jnp.concatenate(cols, axis=1).reshape(c, Lp, dp)[:, :L]
    return jnp.transpose(G.reshape(c, S, T, dp), (2, 1, 3, 0))

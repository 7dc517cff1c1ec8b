"""Fused level-histogram kernels for the chunked tree protocol.

The tree families' per-level cost is dominated by the histogram
contraction ``H[m, f, b, k] = sum_r onehot(node)[r, m] * SC[r, k] *
onehot(bin)[r, f, b]`` (52% of the per-level budget at the production
shape, benchmarks/deep_profile.py). The XLA one-hot matmul form
(``ops/trees.py:_level_histogram_multi``) materializes BOTH 0/1 operands
in HBM between the elementwise one-hot construction and the dot — the
``T1 = onehot(node) ⊗ SC`` tensor ([row_chunk, kk*n_nodes], its columns
stat-major since PR 33, so it is written once in the layout the dot reads;
~1 GB/level of write+read traffic per lane at W=1024) is the measured
dominant memory-traffic term.

Two replacements, selected by the ``CS230_HIST_KERNEL`` valve in
ops/trees.py:

- ``level_histogram_pallas`` — a Pallas TPU kernel that builds both
  one-hot operands as VMEM intermediates inside the grid step and feeds
  them straight to the MXU: the [bm, Mb*kk] and [bm, d*n_bins] 0/1 tiles
  never exist in HBM, and the [Mb*kk, d*n_bins] accumulator page stays
  resident in VMEM across all row tiles of a node block. Bin-and-scatter
  semantics, MXU execution (true per-row scatters serialize ~10-30x on
  TPU — measured, see ops/trees.py).
- ``level_histogram_scatter`` — the literal bin-and-scatter formulation
  (one segment-sum per feature): O(n*d*kk) adds instead of the matmul's
  O(n*W*kk*d*n_bins) MACs. This is the fast form on scatter-friendly
  backends (CPU: the one-hot matmul's W-fold arithmetic redundancy is
  catastrophic without an MXU to hide it — measured ~13x at W=64, see
  benchmarks/DEEP_PROFILE_HIST_{BEFORE,AFTER}.json).

Both reproduce the matmul form exactly for integer-valued stats (every
product is exact; f32/s32 accumulation of integers < 2^24), and to f32
summation-order tolerance for float stats. Parity is pinned on CPU by
tests/test_pallas_hist.py (the Pallas kernel through its interpreter).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

#: rows per grid step / node-block width of the Pallas kernel. Mb * kk
#: one-hot columns per tile keeps T1 at [256, 512] and the accumulator
#: page at [512, d*n_bins] — a few MB of VMEM at covertype shapes.
ROW_TILE = 256
NODE_BLOCK = 64


def _ceil_to(x: int, m: int) -> int:
    return ((x + m - 1) // m) * m


def _hist_kernel(lb_ref, xb_ref, sc_ref, h_ref, *, Mb: int, kk: int, d: int,
                 n_bins: int, xpad: int, op_dt):
    """One (node-block, row-tile) grid step.

    lb_ref [bm, 1]   i32  per-row node id (rows outside this block no-op)
    xb_ref [bm, d]   i32  per-row bin codes
    sc_ref [bm, kk]  f32  per-row stats (pad rows must carry zeros)
    h_ref  [1, kk*Mb, xpad] f32 accumulator page, revisited across row
           tiles; rows are k-major (row = k*Mb + m), cols feature-major
           (col = f*n_bins + b, zero-padded to xpad).
    """
    nb = pl.program_id(0)
    i = pl.program_id(1)
    bm = lb_ref.shape[0]
    base = nb * Mb

    lb = lb_ref[:]  # [bm, 1]
    node_col = jax.lax.broadcasted_iota(jnp.int32, (bm, Mb), 1) + base
    N = (lb == node_col).astype(op_dt)  # [bm, Mb] block-local one-hot
    sc = sc_ref[:].astype(op_dt)

    # T1 = one_hot(node) ⊗ SC, k-major columns — built in VMEM, never HBM
    t1_parts = [N * sc[:, j : j + 1] for j in range(kk)]
    T1 = jnp.concatenate(t1_parts, axis=1)  # [bm, kk*Mb]

    # bin one-hot, feature-major columns, zero-padded to the tile width
    xb = xb_ref[:]
    bin_col = jax.lax.broadcasted_iota(jnp.int32, (bm, n_bins), 1)
    b_parts = [
        (xb[:, f : f + 1] == bin_col).astype(op_dt) for f in range(d)
    ]
    if xpad > d * n_bins:
        b_parts.append(jnp.zeros((bm, xpad - d * n_bins), op_dt))
    B = jnp.concatenate(b_parts, axis=1)  # [bm, xpad]

    @pl.when(i == 0)
    def _init():
        h_ref[0] = jnp.zeros_like(h_ref[0])

    h_ref[0] += jax.lax.dot_general(
        T1,
        B,
        dimension_numbers=(((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )


@functools.partial(
    jax.jit,
    static_argnames=("n_nodes", "n_bins", "bm", "Mb", "integer_stats", "interpret"),
)
def level_histogram_pallas(local, xb, SC, n_nodes: int, n_bins: int, *,
                           bm: int = ROW_TILE, Mb: int = NODE_BLOCK,
                           integer_stats: bool = False,
                           interpret: bool = False):
    """[n_nodes, d, n_bins, kk] level histogram (same contract as
    ``ops/trees.py:_level_histogram``) as a fused Pallas kernel.

    ``integer_stats`` selects bf16 one-hot/stat operands (exact: every
    product is a single stat value < 2^8 picked by 0/1 factors, summed in
    f32); float stats use f32 operands. The interpreter path (CPU test
    coverage) always computes in f32.
    """
    n, d = xb.shape
    kk = SC.shape[1]
    Mb = min(Mb, _ceil_to(max(n_nodes, 8), 8))
    n_pad = _ceil_to(n, bm)
    if n_pad != n:
        # pad rows carry zero stats — wherever their node id lands, the
        # contribution is zero
        local = jnp.pad(local, (0, n_pad - n))
        xb = jnp.pad(xb, ((0, n_pad - n), (0, 0)))
        SC = jnp.pad(SC, ((0, n_pad - n), (0, 0)))
    NBk = pl.cdiv(n_nodes, Mb)
    xpad = _ceil_to(d * n_bins, 128)
    op_dt = jnp.float32 if (interpret or not integer_stats) else jnp.bfloat16

    kernel = functools.partial(
        _hist_kernel, Mb=Mb, kk=kk, d=d, n_bins=n_bins, xpad=xpad, op_dt=op_dt
    )
    out = pl.pallas_call(
        kernel,
        grid=(NBk, n_pad // bm),
        in_specs=[
            pl.BlockSpec((bm, 1), lambda nb, i: (i, 0)),
            pl.BlockSpec((bm, d), lambda nb, i: (i, 0)),
            pl.BlockSpec((bm, kk), lambda nb, i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((1, kk * Mb, xpad), lambda nb, i: (nb, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((NBk, kk * Mb, xpad), jnp.float32),
        interpret=interpret,
        name="level_histogram",
    )(local[:, None].astype(jnp.int32), xb.astype(jnp.int32),
      SC.astype(jnp.float32))

    # [NBk, kk, Mb, d, n_bins] -> [NBk*Mb, d, n_bins, kk] -> [n_nodes, ...]
    H = out[:, :, : d * n_bins].reshape(NBk, kk, Mb, d, n_bins)
    return H.transpose(0, 2, 3, 4, 1).reshape(NBk * Mb, d, n_bins, kk)[:n_nodes]


#: Mosaic's default scoped-VMEM limit per kernel on v5e (the call sets no
#: ``vmem_limit_bytes``); a kernel whose blocks and temporaries exceed it
#: is refused at compile time ("exceeded scoped vmem limit")
_SCOPED_VMEM_BYTES = 16 * 2**20


def pallas_hist_vmem_bytes(d: int, n_bins: int, kk: int, *,
                           bm: int = ROW_TILE, Mb: int = NODE_BLOCK) -> int:
    """What one grid step of ``_hist_kernel`` allocates, at the widest
    node block: the f32 accumulator page ``[kk*Mb, xpad]`` double-buffered
    as the output block plus the dot result before it is added in, the
    ``[bm, xpad]`` bin one-hot and its concatenation pieces, and the
    ``[bm, kk*Mb]`` T1 tile (counted at 4 bytes: conservative for bf16).
    Checked against deviceless v5e compiles of d=54 x {17..256} bins x
    {3,7,8} stats: every refused shape counts over the limit."""
    xpad = _ceil_to(d * n_bins, 128)
    page = kk * Mb * xpad * 4
    return 3 * page + 2 * bm * xpad * 4 + bm * kk * Mb * 4


def pallas_hist_applicable(d: int, n_bins: int, kk: int) -> bool:
    """Static shape gate of the ``auto`` route: the kernel's VMEM
    allocation must fit the compiler's scoped limit."""
    return (
        kk <= 16
        and n_bins <= 256
        and pallas_hist_vmem_bytes(d, n_bins, kk) <= _SCOPED_VMEM_BYTES
    )


def level_histogram_scatter(local, xb, SC, n_nodes: int, n_bins: int):
    """The literal bin-and-scatter form: one segment-sum per feature.

    O(n * d * kk) scatter-adds; exact f32 accumulation (bit-identical to
    the matmul form for integer stats, summation-order ulps for floats).
    Rows whose node id falls outside [0, n_nodes) are dropped — the same
    dead-row semantics as the one-hot forms.
    """
    n, d = xb.shape
    local = local.astype(jnp.int32)
    seg = n_nodes * n_bins
    valid = (local >= 0) & (local < n_nodes)
    base = jnp.where(valid, local, n_nodes) * n_bins  # invalid -> dropped
    cols = []
    for f in range(d):
        idx = jnp.where(valid, base + xb[:, f], seg)
        cols.append(
            jax.ops.segment_sum(SC, idx, num_segments=seg).reshape(
                n_nodes, n_bins, SC.shape[1]
            )
        )
    return jnp.stack(cols, axis=1)  # [n_nodes, d, n_bins, kk]

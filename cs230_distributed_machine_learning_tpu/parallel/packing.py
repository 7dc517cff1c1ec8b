"""The byte format of a packed trial result.

Every blocking device->host conversion is its own round trip, paid PER
LEAF of the result pytree: the cost floor of tiny jobs (iris-sized grids,
GaussianNB). A one-device trial executable therefore concatenates all its
result leaves into ONE flat uint8 buffer inside the jitted computation
(bitcast, so f32/int leaves stay bit-identical); the host fetches that
single buffer with one ``jax.device_get`` and reassembles the pytree with
zero-copy numpy views. Which executables pack is the trial engine's rule
(``trial_map._build_executable``); what the bytes mean is known here only.
"""

from __future__ import annotations

import dataclasses
from typing import Any

import numpy as np

import jax
import jax.numpy as jnp


@dataclasses.dataclass(frozen=True)
class PackSpec:
    """Host-side recipe to reassemble a result pytree from one byte buffer."""

    treedef: Any
    shapes: tuple
    dtypes: tuple
    offsets: tuple
    nbytes: int


class Packed:
    """A packed device buffer awaiting its single-transfer host fetch."""

    __slots__ = ("buf", "spec")

    def __init__(self, buf, spec: PackSpec):
        self.buf = buf
        self.spec = spec


def pack_spec_of(fn, example_args) -> PackSpec:
    """Abstract-trace ``fn`` to learn its output tree; no device work."""
    out = jax.eval_shape(fn, *example_args)
    leaves, treedef = jax.tree_util.tree_flatten(out)
    shapes = tuple(tuple(int(s) for s in l.shape) for l in leaves)
    dtypes = tuple(np.dtype(l.dtype) for l in leaves)
    sizes = [
        int(np.prod(s, dtype=np.int64)) * dt.itemsize
        for s, dt in zip(shapes, dtypes)
    ]
    offs = np.concatenate([[0], np.cumsum(sizes, dtype=np.int64)])
    return PackSpec(
        treedef, shapes, dtypes, tuple(int(o) for o in offs[:-1]), int(offs[-1])
    )


def pack_wrap(fn):
    """Wrap a to-be-jitted trial function so its result leaves the device
    as one flat uint8 buffer (bitcast + concat traced into the executable).
    Pair with the PackSpec from ``pack_spec_of`` on the same example args."""

    def packed(*args):
        leaves = jax.tree_util.tree_leaves(fn(*args))
        with jax.named_scope("tpuml.pack"):
            parts = []
            for leaf in leaves:
                leaf = jnp.asarray(leaf)
                if leaf.dtype == jnp.bool_:
                    leaf = leaf.astype(jnp.uint8)
                parts.append(
                    jax.lax.bitcast_convert_type(leaf, jnp.uint8).reshape(-1)
                )
            if not parts:
                return jnp.zeros((0,), jnp.uint8)
            return jnp.concatenate(parts) if len(parts) > 1 else parts[0]

    return packed


def unpack(buf_np: np.ndarray, spec: PackSpec):
    """Reassemble the result pytree from one fetched byte buffer (views,
    not copies — and bitwise identical to the per-leaf path)."""
    buf_np = np.ascontiguousarray(buf_np)
    leaves = []
    for off, shape, dt in zip(spec.offsets, spec.shapes, spec.dtypes):
        size = int(np.prod(shape, dtype=np.int64)) * dt.itemsize
        raw = buf_np[off : off + size]
        if dt == np.dtype(bool):
            leaves.append(raw.view(np.uint8).astype(bool).reshape(shape))
        else:
            leaves.append(raw.view(dt).reshape(shape))
    return jax.tree_util.tree_unflatten(spec.treedef, leaves)

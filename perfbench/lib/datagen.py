"""The benchmark's one data generator: a dataset from a spec and a seed.

A configuration's ``dataset`` block names a ``kind`` (a file of its own,
``datasets/<kind>.py``) and its parameters; everything is drawn on the
device in one jitted call from ``--seed`` and handed to the program as host
numpy (the program stages host arrays). The same seed gives the same bytes; seeds up to 2**32 and beyond are folded in
two halves, since a 32-bit JAX key takes no more than 31 bits at once.
"""

from __future__ import annotations

import functools
from typing import Any, Dict, Tuple

import numpy as np


def seed_key(seed: int, stream: int = 0):
    import jax

    seed = int(seed)
    key = jax.random.key(seed & 0x7FFFFFFF)
    key = jax.random.fold_in(key, (seed >> 31) & 0x7FFFFFFF)
    return jax.random.fold_in(key, stream)


def make_dataset(spec: Dict[str, Any], seed: int, generate) -> Tuple[np.ndarray, np.ndarray]:
    """(X [n, d] float32, y [n] int32) on the host, from the spec and seed.
    ``generate`` is the kind's function, ``datasets/<kind>.py::generate``."""
    import jax

    spec = {k: tuple(v) if isinstance(v, list) else v for k, v in spec.items() if k != "kind"}
    params = {"n": int(spec.pop("n_samples")), "d": int(spec.pop("n_features")),
              "c": int(spec.pop("n_classes")), **spec}
    X, y = jax.jit(functools.partial(generate, **params))(seed_key(seed))
    return np.asarray(X), np.asarray(y)

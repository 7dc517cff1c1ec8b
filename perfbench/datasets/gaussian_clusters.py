"""Dataset kind ``gaussian_clusters``: the shape of a standardised tabular set."""

from __future__ import annotations

import numpy as np


def generate(key, *, n, d, c, n_informative, n_redundant, class_sep, label_noise,
             class_prior, scale_sigma):
    """Class clusters in an informative subspace, redundant linear
    combinations of it, pure-noise columns, per-feature scales, and a share
    of labels redrawn: the shape of a standardised tabular set."""
    import jax
    import jax.numpy as jnp

    ky, kc, kz, km, kr, kn, ks, kf, kl = jax.random.split(key, 9)
    prior = jnp.asarray(class_prior, jnp.float32)
    y = jax.random.categorical(ky, jnp.log(prior / prior.sum()), shape=(n,))
    centers = class_sep * jax.random.normal(kc, (c, n_informative), jnp.float32)
    Z = centers[y] + jax.random.normal(kz, (n, n_informative), jnp.float32)
    M = jax.random.normal(km, (n_informative, n_redundant), jnp.float32)
    R = Z @ (M / np.sqrt(n_informative)) + 0.1 * jax.random.normal(
        kr, (n, n_redundant), jnp.float32)
    N = jax.random.normal(kn, (n, d - n_informative - n_redundant), jnp.float32)
    X = jnp.concatenate([Z, R, N], axis=1)
    X = X * jnp.exp(scale_sigma * jax.random.normal(ks, (1, d), jnp.float32))
    flip = jax.random.uniform(kf, (n,)) < label_noise
    y = jnp.where(flip, jax.random.randint(kl, (n,), 0, c), y)
    return X, y.astype(jnp.int32)

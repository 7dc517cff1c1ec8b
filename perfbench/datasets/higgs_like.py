"""Dataset kind ``higgs_like``: the column kinds of UCI HIGGS."""

from __future__ import annotations

# The configurations that use this table boost under the program's stated
# hessian rule (models/trees.py: the floor before the mask, so a row outside
# a stage's mask carries nothing) and are sized for an engine that sends all
# of a bucket's (trial, fold) lanes out in one program (PR 34). A program
# from before both runs the cell's search in 61 s where this one takes 25,
# holds 0.86 GB of the chip where a new cell has to hold 2.1, and needs 300
# of the harness's 360 s for a run (my chip run, PR 34): it cannot run the
# configuration as stated, and fails here, at once, before anything is made
# or compiled.
from cs230_distributed_machine_learning_tpu.models.trees import BOOST_HESSIAN_FLOOR as _HESSIAN_RULE  # noqa: F401


def generate(key, *, n, d, c, n_low, class_sep, tail_share, tail_power, high_noise,
             label_noise, class_prior, scale_sigma):
    """``n_low`` "low-level" continuous columns, then ``d - n_low``
    "high-level" ones (HIGGS: 21 kinematic measurements and 7 functions of
    them a physicist derived). Each class is a mixture of two Gaussian
    components in the low-level space (the components' centres drawn from
    the key, each ``class_sep`` a column from the origin in units of the
    noise, so that every seed's table is about as hard), so a class is no
    half-space and a deep tree gains over a shallow one; a ``tail_share`` of
    the low-level columns is stretched to a heavy tail (``sign(z) |z| **
    tail_power``), all under per-feature scales. A high-level column is a
    non-linear function of three low-level ones, a product or a root sum of
    squares by turns, plus noise of ``high_noise`` of its own spread. A
    share of the labels is redrawn from the prior, so that no model can be
    right about every held-out row."""
    import jax
    import jax.numpy as jnp

    assert c == 2 and 0 < n_low < d, "a binary table with low- and high-level columns"
    ky, km, kc, kz, ks, kp, kh, kf, kl = jax.random.split(key, 9)
    prior = jnp.asarray(class_prior, jnp.float32)
    log_prior = jnp.log(prior / prior.sum())
    y = jax.random.categorical(ky, log_prior, shape=(n,))
    comp = jax.random.bernoulli(km, 0.5, (n,)).astype(jnp.int32)
    centers = jax.random.normal(kc, (c, 2, n_low), jnp.float32)
    centers = class_sep * jnp.sqrt(float(n_low)) * centers / jnp.linalg.norm(centers, axis=-1, keepdims=True)
    Z = centers[y, comp] + jax.random.normal(kz, (n, n_low), jnp.float32)
    heavy = jnp.arange(n_low) < int(round(tail_share * n_low))
    low = jnp.where(heavy[None, :], jnp.sign(Z) * jnp.abs(Z) ** tail_power, Z)
    n_high = d - n_low
    picks = jnp.stack([jax.random.permutation(jax.random.fold_in(kp, j), n_low)[:3]
                       for j in range(n_high)])  # [n_high, 3] low-level columns
    a, b, e = (Z[:, picks[:, i]] for i in range(3))  # [n, n_high] each
    product = (jnp.arange(n_high) % 2 == 0)[None, :]
    high = jnp.where(product, a * b + 0.5 * e, jnp.sqrt(a * a + b * b + e * e))
    high = high + high_noise * jnp.std(high, axis=0, keepdims=True) * jax.random.normal(
        kh, (n, n_high), jnp.float32)
    X = jnp.concatenate([low, high], axis=1)
    X = X * jnp.exp(scale_sigma * jax.random.normal(ks, (1, d), jnp.float32))
    flip = jax.random.uniform(kf, (n,)) < label_noise
    y = jnp.where(flip, jax.random.categorical(kl, log_prior, shape=(n,)), y)
    return X.astype(jnp.float32), y.astype(jnp.int32)

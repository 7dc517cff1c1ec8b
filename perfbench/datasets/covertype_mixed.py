"""Dataset kind ``covertype_mixed``: the column kinds of UCI Covertype."""

from __future__ import annotations

# The configurations that use this table grow their forests under the deep
# builder's stated gain rules (ops/trees.py: GAIN_NOISE, gains compared on 12
# bits, the frontier in candidate order; PR 32). A program without them
# splits pure nodes on the chip, grows other trees there than its own CPU
# path and the reference, and its cold first search of such a cell passes
# the harness's 900 s: it cannot run the configuration, and fails here, at
# once, before anything is made or compiled.
from cs230_distributed_machine_learning_tpu.ops.trees import GAIN_NOISE as _GAIN_RULES  # noqa: F401


def generate(key, *, n, d, c, n_continuous, onehot_blocks, class_sep, block_class_pull,
             block_feature_pull, category_skew, label_noise, class_prior, scale_sigma):
    """``n_continuous`` continuous columns carrying class clusters under
    per-feature scales, then one one-hot block per entry of
    ``onehot_blocks`` (Covertype: 4 wilderness areas, 40 soil types). A
    row's category in a block is the largest of: a base log-frequency that
    falls with the category's rank (``category_skew``: some soil types are
    rare), a pull toward its class, a linear pull of its continuous columns,
    and Gumbel noise. A share of the labels is redrawn, so that no tree can
    be right about every held-out row and trees grown to purity run deep."""
    import jax
    import jax.numpy as jnp

    assert n_continuous + sum(onehot_blocks) == d, "column kinds must add up to n_features"
    ky, kc, kz, ks, kf, kl, kb = jax.random.split(key, 7)
    prior = jnp.asarray(class_prior, jnp.float32)
    y = jax.random.categorical(ky, jnp.log(prior / prior.sum()), shape=(n,))
    centers = class_sep * jax.random.normal(kc, (c, n_continuous), jnp.float32)
    Z = centers[y] + jax.random.normal(kz, (n, n_continuous), jnp.float32)
    cols = [Z * jnp.exp(scale_sigma * jax.random.normal(ks, (1, n_continuous), jnp.float32))]
    for b, width in enumerate(onehot_blocks):
        ka, kw, kg = jax.random.split(jax.random.fold_in(kb, b), 3)
        base = -category_skew * jnp.log1p(jnp.arange(width, dtype=jnp.float32))
        by_class = block_class_pull * jax.random.normal(ka, (c, width), jnp.float32)
        by_feature = block_feature_pull * jax.random.normal(
            kw, (n_continuous, width), jnp.float32) / jnp.sqrt(float(n_continuous))
        logits = base[None, :] + by_class[y] + Z @ by_feature + jax.random.gumbel(kg, (n, width))
        cols.append(jax.nn.one_hot(jnp.argmax(logits, axis=1), width, dtype=jnp.float32))
    flip = jax.random.uniform(kf, (n,)) < label_noise
    y = jnp.where(flip, jax.random.randint(kl, (n,), 0, c), y)
    return jnp.concatenate(cols, axis=1), y.astype(jnp.int32)

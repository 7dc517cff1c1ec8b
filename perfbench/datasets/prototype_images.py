"""Dataset kind ``prototype_images``: MNIST's shape and range."""

from __future__ import annotations


def generate(key, *, n, d, c, pixel_density, contrast, noise, label_noise):
    """Ten sparse prototypes pulled toward their mean, a per-sample stroke
    gain, pixel noise, clipped to [0, 1]: MNIST's shape and range."""
    import jax
    import jax.numpy as jnp

    kp, ki, ky, kg, kn, kf, kl = jax.random.split(key, 7)
    mask = jax.random.uniform(kp, (c, d)) < pixel_density
    proto = jnp.where(mask, jax.random.uniform(ki, (c, d), minval=0.3, maxval=1.0), 0.0)
    mean = proto.mean(axis=0, keepdims=True)
    proto = mean + contrast * (proto - mean)
    y = jax.random.randint(ky, (n,), 0, c)
    gain = jax.random.uniform(kg, (n, 1), minval=0.6, maxval=1.0)
    X = proto[y] * gain + noise * jax.random.normal(kn, (n, d), jnp.float32)
    X = jnp.clip(X, 0.0, 1.0).astype(jnp.float32)
    flip = jax.random.uniform(kf, (n,)) < label_noise
    y = jnp.where(flip, jax.random.randint(kl, (n,), 0, c), y)
    return X, y.astype(jnp.int32)

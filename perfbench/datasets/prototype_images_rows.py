"""Dataset kind ``prototype_images_rows``: ``prototype_images``'s
distribution (MNIST's shape and range), drawn in row chunks.

One jitted call still makes the whole table, but a loop over row chunks
writes each chunk's rows in place into the result, so the device holds
the table and one chunk's draws: 0.44 GB of scratch beside the table,
where the one-shot kind keeps its draws, more than the table again, beside
its result (deviceless v5e compiles, PR 40), and its peak, not the
program's, would be what the run's memory peak reads."""

from __future__ import annotations

#: rows drawn at once, at most
ROW_CHUNK = 65536


def generate(key, *, n, d, c, pixel_density, contrast, noise, label_noise):
    """Ten sparse prototypes pulled toward their mean, a per-sample stroke
    gain, pixel noise, clipped to [0, 1], labels redrawn at
    ``label_noise``: each chunk of rows from its own key."""
    import jax
    import jax.numpy as jnp

    kp, ki, kr = jax.random.split(key, 3)
    mask = jax.random.uniform(kp, (c, d)) < pixel_density
    proto = jnp.where(mask, jax.random.uniform(ki, (c, d), minval=0.3, maxval=1.0), 0.0)
    mean = proto.mean(axis=0, keepdims=True)
    proto = mean + contrast * (proto - mean)
    n_chunks = -(-n // ROW_CHUNK)
    rows = -(-n // n_chunks)

    def chunk(i):
        ky, kg, kn, kf, kl = jax.random.split(jax.random.fold_in(kr, i), 5)
        y = jax.random.randint(ky, (rows,), 0, c)
        gain = jax.random.uniform(kg, (rows, 1), minval=0.6, maxval=1.0)
        X = proto[y] * gain + noise * jax.random.normal(kn, (rows, d), jnp.float32)
        X = jnp.clip(X, 0.0, 1.0).astype(jnp.float32)
        flip = jax.random.uniform(kf, (rows,)) < label_noise
        return X, jnp.where(flip, jax.random.randint(kl, (rows,), 0, c), y).astype(jnp.int32)

    def put(i, Xy):
        Xc, yc = chunk(i)
        start = jnp.minimum(i * rows, n - rows)  # the last chunk overlaps
        return (jax.lax.dynamic_update_slice(Xy[0], Xc, (start, 0)),
                jax.lax.dynamic_update_slice(Xy[1], yc, (start,)))

    return jax.lax.fori_loop(0, n_chunks, put, (jnp.zeros((n, d), jnp.float32),
                                                jnp.zeros((n,), jnp.int32)))

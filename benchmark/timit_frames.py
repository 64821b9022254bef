"""Stand-in TIMIT frames for the `timit_cosine` configuration, made on
the device from the seed in one jitted call a split (the sandbox has no
TIMIT features; `benchmark/datagen.py` holds the image data and is not
edited).

A frame of class c is ``sqrt(signal) * s * mu_c + sqrt(1 - signal) * e``:
``mu_c`` a class direction with unit-variance entries, ``s`` a random
sign, ``e`` isotropic unit noise. So every dimension has unit variance,
the frames have full rank, and every class has mean zero: a linear model
on the frames separates nothing and stays at chance, while cos(w.x + b)
is even in ``s`` and tells |mu_c . w| apart, which is the work the random
features are there to do. At gamma 0.05555 the phases w.x spread by
gamma * sqrt(dim), 1.17 radians at 440 dimensions, which keeps a block's
Gram well conditioned at lambda 0."""

from functools import partial

import jax
import jax.numpy as jnp

from . import datagen


@partial(jax.jit, static_argnames=("n", "num_classes", "dim"))
def _split(class_key, key, signal, *, n, num_classes, dim):
    centres = jax.random.normal(class_key, (num_classes, dim))
    kl, ks, kn = jax.random.split(key, 3)
    labels = jax.random.randint(kl, (n,), 0, num_classes, jnp.int32)
    sign = jax.random.rademacher(ks, (n, 1), jnp.float32)
    noise = jax.random.normal(kn, (n, dim))
    frames = (jnp.sqrt(signal) * sign * centres[labels]
              + jnp.sqrt(1.0 - signal) * noise)
    return frames, labels


def timit_like(n_train, n_test, seed, num_classes, dim, signal):
    """((train frames, labels), (test frames, labels)) on the device."""
    classes = datagen.seed_key(seed, 0)
    return tuple(
        _split(classes, datagen.seed_key(seed, stream), jnp.float32(signal),
               n=n, num_classes=num_classes, dim=dim)
        for stream, n in ((1, n_train), (2, n_test)))

"""The benchmark's data, made on the device from the seed in one jitted
call per split, in the type the pipelines take (float32 rows, int32
labels). Nothing is read from disk and nothing is made on the host.

`cifar_like` is the distribution of `loaders/cifar_loader.synthetic_cifar`
(smooth class templates, a mix toward another class, a circular shift,
pixel noise, scaled to 0..255), vectorised: the original's Python loop
of one `np.roll` per image was most of the chip smoke's 16.8 s of data
set-up."""

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np


def seed_key(seed, stream):
    """A PRNG key from any whole-number seed (the driver's are larger
    than 32 signed bits hold) and a stream number."""
    seed = int(seed)
    key = jax.random.PRNGKey(seed & 0x7FFFFFFF)
    key = jax.random.fold_in(key, (seed >> 31) & 0x7FFFFFFF)
    return jax.random.fold_in(key, stream)


def program_seed(seed):
    """The seed handed to the program's own configuration, which feeds
    it to `jax.random.PRNGKey` as a 32-bit number."""
    return int(seed) % (2**31 - 1)


@partial(jax.jit, static_argnames=("n", "num_classes", "side"))
def _cifar_split(class_key, key, noise, confusion, *, n, num_classes, side):
    kf, kp, ka = jax.random.split(class_key, 3)
    freqs = jax.random.normal(kf, (num_classes, 4, 2))
    phases = jax.random.uniform(kp, (num_classes, 4), maxval=2 * np.pi)
    amps = jax.random.uniform(ka, (num_classes, 4, 3), minval=0.5, maxval=1.0)
    yy, xx = jnp.meshgrid(jnp.arange(side), jnp.arange(side), indexing="ij")
    wave = jnp.sin(freqs[:, :, 0, None, None] * yy / 5.0
                   + freqs[:, :, 1, None, None] * xx / 5.0
                   + phases[:, :, None, None])            # (k, 4, s, s)
    templates = jnp.einsum("kfyx,kfc->kyxc", wave, amps)   # (k, s, s, 3)

    kl, ko, km, ks, kn = jax.random.split(key, 5)
    labels = jax.random.randint(kl, (n,), 0, num_classes, jnp.int32)
    other = (labels + jax.random.randint(ko, (n,), 1, num_classes)) % num_classes
    mix = jax.random.uniform(km, (n, 1, 1, 1), maxval=confusion)
    images = (1.0 - mix) * templates[labels] + mix * templates[other]
    shifts = jax.random.randint(ks, (n, 2), -4, 5)
    images = jax.vmap(lambda im, s: jnp.roll(im, s, axis=(0, 1)))(images, shifts)
    images = images + noise * jax.random.normal(kn, images.shape)
    lo, hi = images.min(), images.max()
    return (images - lo) / (hi - lo) * 255.0, labels


def cifar_like(n_train, n_test, seed, num_classes=10, side=32, noise=0.6,
               confusion=0.0):
    """((train images, labels), (test images, labels)) on the device."""
    classes = seed_key(seed, 0)
    return tuple(
        _cifar_split(classes, seed_key(seed, stream), jnp.float32(noise),
                     jnp.float32(confusion), n=n, num_classes=num_classes,
                     side=side)
        for stream, n in ((1, n_train), (2, n_test)))

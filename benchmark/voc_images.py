"""The VOC stand-in's images, made on the device from the seed: decoded
photographs' shape (n, height, width, 3) in uint8 with multi-hot labels
(n, classes), one to three classes an image.

Each class is a texture of its own: two gratings, each with an
orientation and a wavelength the class owns (a fifth of a half turn
apart between classes that share a wavelength pair, so dense SIFT's
eight orientation bins over four bin sizes tell them apart). An image is
a composition: over clutter (gratings of orientations and wavelengths
drawn afresh an image, which belong to no class) and pixel noise, every
label's texture fills a soft-edged elliptic region placed at random,
with a phase drawn afresh, under a colour cast and a brightness of the
image's own. A phase drawn afresh makes a class's mean image flat, so a
linear model on the raw pixels has nothing to fit, while the gradient
orientation statistics that SIFT and the Fisher vector keep are the
class's. ``texture``, ``clutter`` and ``noise`` set how hard it is.

An image's labels: one with probability 0.6, two 0.3, three 0.1 (1.5 on
average, VOC 2007's is about 1.4 to 1.5), distinct classes drawn
uniformly."""

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from .datagen import seed_key

MAX_LABELS = 3


def class_gratings(num_classes):
    """(orientations, wavelengths) of every class's two gratings,
    (classes, 2) each: the structure is fixed, so that every seed's
    classes are equally far apart; the images vary with the seed."""
    c = np.arange(num_classes)
    first = np.pi * (c % 5) / 5.0
    second = first + np.pi * (1 + (c // 5) % 4) / 5.0
    long_wave = np.asarray([6.0, 9.0, 13.0, 18.0])[(c // 5) % 4]
    return (np.stack([first, second], axis=1).astype(np.float32),
            np.stack([long_wave, 0.6 * long_wave], axis=1).astype(np.float32))


def _grating(yy, xx, theta, wavelength, phase):
    return jnp.sin(2.0 * jnp.pi * (xx * jnp.cos(theta) + yy * jnp.sin(theta))
                   / wavelength + phase)


def _image(key, labels, thetas, waves, texture, clutter, noise, *, height,
           width):
    """One (height, width, 3) uint8 image of the classes ``labels``
    (`MAX_LABELS` ids, -1 where there is none)."""
    yy, xx = jnp.meshgrid(jnp.arange(height, dtype=jnp.float32),
                          jnp.arange(width, dtype=jnp.float32), indexing="ij")
    kc, kp, kr, kn, kt = jax.random.split(key, 5)
    # clutter: four gratings of no class
    ct, cw, cp = jax.random.split(kc, 3)
    c_theta = jax.random.uniform(ct, (4,), maxval=np.pi)
    c_wave = jax.random.uniform(cw, (4,), minval=5.0, maxval=24.0)
    c_phase = jax.random.uniform(cp, (4,), maxval=2 * np.pi)
    image = clutter * sum(
        _grating(yy, xx, c_theta[i], c_wave[i], c_phase[i]) for i in range(4))
    phases = jax.random.uniform(kp, (MAX_LABELS, 2), maxval=2 * np.pi)
    centre = jax.random.uniform(kr, (MAX_LABELS, 2), minval=0.2, maxval=0.8)
    radius = jax.random.uniform(jax.random.fold_in(kr, 1), (MAX_LABELS, 2),
                                minval=0.18, maxval=0.35)
    for j in range(MAX_LABELS):
        c = jnp.maximum(labels[j], 0)
        tex = sum(_grating(yy, xx, thetas[c, g], waves[c, g], phases[j, g])
                  for g in range(2))
        r2 = (((yy / height - centre[j, 0]) / radius[j, 0]) ** 2
              + ((xx / width - centre[j, 1]) / radius[j, 1]) ** 2)
        region = jax.nn.sigmoid(6.0 * (1.0 - r2))
        image = image + jnp.where(labels[j] >= 0, texture, 0.0) * region * tex
    image = image + noise * jax.random.normal(kn, image.shape)
    cast = jax.random.uniform(kt, (3,), minval=0.7, maxval=1.0)
    level = jax.random.uniform(jax.random.fold_in(kt, 1), (), minval=0.35,
                               maxval=0.65)
    rgb = (level + 0.12 * image)[:, :, None] * cast
    return jnp.clip(jnp.round(255.0 * rgb), 0, 255).astype(jnp.uint8)


@partial(jax.jit, static_argnames=("n", "num_classes", "height", "width"))
def _split(key, thetas, waves, texture, clutter, noise, *, n, num_classes,
           height, width):
    kl, kk, ki = jax.random.split(key, 3)
    count = 1 + (jax.random.uniform(kk, (n,)) > 0.6).astype(jnp.int32) + (
        jax.random.uniform(jax.random.fold_in(kk, 1), (n,)) > 0.9
    ).astype(jnp.int32)
    count = jnp.minimum(count, MAX_LABELS)
    # distinct classes: the first ids of a permutation of the classes
    ids = jax.vmap(lambda k: jax.random.permutation(k, num_classes)
                   [:MAX_LABELS])(jax.random.split(kl, n))
    labels = jnp.where(jnp.arange(MAX_LABELS)[None, :] < count[:, None],
                       ids, -1)
    multi_hot = jnp.zeros((n, num_classes), jnp.float32).at[
        jnp.arange(n)[:, None], jnp.maximum(labels, 0)].max(
            (labels >= 0).astype(jnp.float32))
    one = partial(_image, thetas=thetas, waves=waves, texture=texture,
                  clutter=clutter, noise=noise, height=height, width=width)
    # sixteen images' float32 planes at a time, not the split's
    images = jax.lax.map(lambda kl_: one(kl_[0], kl_[1]),
                         (jax.random.split(ki, n), labels), batch_size=16)
    return images, multi_hot


def voc_like(n_train, n_test, seed, num_classes=20, height=375, width=500,
             texture=1.0, clutter=0.6, noise=0.5):
    """((train images, multi-hot labels), (test images, labels)) on the
    device."""
    thetas, waves = class_gratings(num_classes)
    return tuple(
        _split(seed_key(seed, stream), thetas, waves, jnp.float32(texture),
               jnp.float32(clutter), jnp.float32(noise), n=n,
               num_classes=num_classes, height=height, width=width)
        for stream, n in ((1, n_train), (2, n_test)))

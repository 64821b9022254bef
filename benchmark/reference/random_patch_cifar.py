"""The plain reference of RandomPatchCifar: explicit patches, patch-mean
subtraction, ZCA whitening and the filter bank as one matrix product,
the two-sided rectifier, sum-pooling by slices, standard scaling and
block coordinate descent over the same blocks. The filters and the
whitener are the model's random parameters ("weights"): they come from
the program's `learn_filters` with the same seed and data, and
everything after them is computed here.

The images go through in chunks small enough that one chunk's conv
outputs (chunk x 27 x 27 x filters float32) stay near a gigabyte; the
test set is predicted chunk by chunk, so its 80,000 features an image
are never held for all 10,000 images at once."""

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from ..configs.random_patch_cifar import program_config
from . import plain

CONV_OUTPUT_ELEMENTS = 2**28  # a chunk's conv outputs: a gigabyte of float32


def _features(images, G, mu, p, alpha, pool, stride):
    x = images / 255.0
    n, h, w, c = x.shape
    gy, gx = h - p + 1, w - p + 1
    # (n, gy, gx, p*p*c), a patch flattened in (row, column, channel) order
    patches = jnp.concatenate(
        [x[:, i:i + gy, j:j + gx, :] for i in range(p) for j in range(p)],
        axis=-1)
    patches = patches - patches.mean(axis=-1, keepdims=True) - mu
    z = patches @ G
    r = jnp.concatenate(
        [jnp.maximum(0.0, z - alpha), jnp.maximum(0.0, -z - alpha)], axis=-1)
    pooled = jnp.stack(
        [jnp.stack([r[:, oy:oy + pool, ox:ox + pool, :].sum(axis=(1, 2))
                    for ox in range(0, gx - pool + 1, stride)], axis=1)
         for oy in range(0, gy - pool + 1, stride)], axis=1)
    return pooled.reshape(n, -1)


def _in_chunks(f, images, chunk):
    """``f`` over ``images`` a chunk at a time; rows of the results."""
    n = images.shape[0]
    steps = -(-n // chunk)
    padded = jnp.pad(images, ((0, steps * chunk - n),) + ((0, 0),) * 3)
    out = jax.lax.map(f, padded.reshape((steps, chunk) + images.shape[1:]))
    return out.reshape((steps * chunk,) + out.shape[2:])[:n]


@partial(jax.jit, static_argnames=("chunk", "shape"))
def _featurize(images, G, mu, *, chunk, shape):
    return _in_chunks(lambda xb: _features(xb, G, mu, *shape), images, chunk)


@partial(jax.jit, static_argnames=("chunk", "shape"))
def _predict(images, G, mu, mean, std, W, b, *, chunk, shape):
    return _in_chunks(
        lambda xb: plain.predict(
            (_features(xb, G, mu, *shape) - mean) / std, W, b),
        images, chunk)


@partial(jax.jit, donate_argnums=0)
def _standardize(X):
    n = X.shape[0]
    mean = X.mean(axis=0)
    std = jnp.sqrt(jnp.maximum(
        ((X - mean) ** 2).sum(axis=0) / max(n - 1.0, 1.0), 0.0))
    std = jnp.where(std == 0.0, 1.0, std)
    return (X - mean) / std, mean, std


def predict(train, test, sizes, seed):
    """Test predictions (numpy int array) of the reference fitted on
    ``train``."""
    from keystone_tpu.pipelines.random_patch_cifar import learn_filters

    filters, whitener = learn_filters(train.data, program_config(sizes, seed))
    p = sizes["patch_size"]
    gy = sizes["image_height"] - p + 1
    gx = sizes["image_width"] - p + 1
    chunk = max(1, min(1024, CONV_OUTPUT_ELEMENTS
                       // (gy * gx * sizes["num_filters"])))
    shape = (p, sizes["alpha"], sizes["pool_size"], sizes["pool_stride"])
    n = train.data.count
    with jax.default_matmul_precision("highest"):
        G = (jnp.asarray(whitener.whitener, jnp.float32)
             @ jnp.asarray(filters, jnp.float32).T)
        mu = jnp.asarray(whitener.means, jnp.float32)
        X, mean, std = _standardize(_featurize(
            train.data.array[:n], G, mu, chunk=chunk, shape=shape))
        Y = plain.indicators(train.labels.array[:n], sizes["num_classes"])
        W, b = plain.block_least_squares(
            X, Y, min(sizes["block_size"], X.shape[1]), sizes["bcd_iters"],
            sizes["lam"])
        del X
        return np.asarray(_predict(
            test.data.array[:test.data.count], G, mu, mean, std, W, b,
            chunk=chunk, shape=shape))

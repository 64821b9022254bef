"""The plain reference of RandomPatchCifarKernel: explicit patches,
patch-mean subtraction, ZCA whitening and the filter bank as one matrix
product, the two-sided rectifier, sum-pooling by slices, standard
scaling by its own moments, then kernel ridge regression written out:
the Gauss-Seidel iteration on (K + lam I) alpha = Y over contiguous
column blocks of the Gaussian kernel matrix, each block formed afresh
in every epoch (no cache), and the test scores K(test, train) alpha by
train blocks. The filters and the whitener are the model's random
parameters ("weights"): they come from the program's `learn_filters`
with the same seed and data, and everything after them is computed
here, in float32 at `highest` matmul precision.

One departure from float32 throughout, and why. The configuration states
the featurizer's convolution at the backend's default matmul precision
(`default_matmul_operands` in its file: on a TPU the patches and the
folded filter bank are rounded to bfloat16, the products and sums are
float32, and the patch means are taken of the rounded patches). The
reference rounds the same operands the same way and computes the rest
exactly, so that what is left to differ is the kernel solver, which the
configuration states in float32 (as `reference/timit_cosine.py` does for
its projection). On the CPU the default is float32 and the tests say so
in their sizes.

The images go through in chunks (`reference/random_patch_cifar.py`'s
loop); the test scores are accumulated a train block at a time."""

import jax
import jax.numpy as jnp
import numpy as np

from ..configs.random_patch_cifar_kernel import program_config
from . import plain
from .random_patch_cifar import _in_chunks, _standardize

CHUNK = 1024  # images a step: 322 MB of float32 patches


def _features(images, G, mu, p, alpha, pool, stride, operands):
    x = images / 255.0
    n, h, w, c = x.shape
    gy, gx = h - p + 1, w - p + 1
    # (n, gy, gx, p*p*c), a patch flattened in (row, column, channel) order
    patches = jnp.concatenate(
        [x[:, i:i + gy, j:j + gx, :] for i in range(p) for j in range(p)],
        axis=-1).astype(operands)
    # (patch - mean(patch) - mu) @ G with the product's operands rounded
    z = (jnp.matmul(patches, G.astype(operands),
                    preferred_element_type=jnp.float32)
         - patches.astype(jnp.float32).mean(axis=-1, keepdims=True)
         * G.sum(axis=0) - mu @ G)
    r = jnp.concatenate(
        [jnp.maximum(0.0, z - alpha), jnp.maximum(0.0, -z - alpha)], axis=-1)
    pooled = jnp.stack(
        [jnp.stack([r[:, oy:oy + pool, ox:ox + pool, :].sum(axis=(1, 2))
                    for ox in range(0, gx - pool + 1, stride)], axis=1)
         for oy in range(0, gy - pool + 1, stride)], axis=1)
    return pooled.reshape(n, -1)


@jax.jit(static_argnames=("shape",))
def _featurize(images, G, mu, *, shape):
    return _in_chunks(lambda xb: _features(xb, G, mu, *shape), images, CHUNK)


@jax.jit
def _kernel_block(X, Xb, gamma):
    """exp(-gamma |x - y|^2) for every row x of X and y of Xb."""
    d2 = ((X * X).sum(axis=1)[:, None] + (Xb * Xb).sum(axis=1)[None, :]
          - 2.0 * X @ Xb.T)
    return jnp.exp(-gamma * d2)


@jax.jit(static_argnames=("width",))
def _block_step(X, Y, alpha, KA, start, lam, gamma, *, width):
    """One Gauss-Seidel update of the ``width`` rows from ``start``: one
    compiled program for every block of a fit (the start is an
    argument), so the reference does not compile a step a block."""
    def rows(a):
        return jax.lax.dynamic_slice_in_dim(a, start, width, 0)

    Kb = _kernel_block(X, rows(X), gamma)
    residual = rows(Y) - rows(KA) - lam * rows(alpha)
    system = rows(Kb) + lam * jnp.eye(width, dtype=X.dtype)
    # symmetric positive definite: Cholesky (an LU solve of 2,048
    # columns took the chip seconds, PR 24)
    delta = jax.scipy.linalg.cho_solve(
        jax.scipy.linalg.cho_factor(system), residual)
    alpha = jax.lax.dynamic_update_slice_in_dim(
        alpha, rows(alpha) + delta, start, 0)
    return alpha, KA + Kb @ delta


def kernel_ridge(X, Y, block, epochs, lam, gamma, seed):
    """alpha of (K + lam I) alpha = Y by Gauss-Seidel over contiguous
    blocks of ``block`` columns: each epoch visits the blocks in the
    order `numpy.random.default_rng(seed + epoch)` shuffles them into,
    and for block b solves (K_bb + lam I) delta = Y_b - (K alpha)_b -
    lam alpha_b, then adds delta to alpha_b and K[:, b] delta to
    K alpha. Every block's columns of K are formed afresh in every
    epoch."""
    n = X.shape[0]
    starts = list(range(0, n, block))
    alpha = jnp.zeros_like(Y)
    KA = jnp.zeros_like(Y)
    for epoch in range(epochs):
        for b in np.random.default_rng(seed + epoch).permutation(len(starts)):
            alpha, KA = _block_step(
                X, Y, alpha, KA, starts[b], lam, gamma,
                width=min(block, n - starts[b]))
    return alpha


def fit(train, sizes, seed):
    """The reference fitted on ``train``: (featurize, X, alpha), where
    ``featurize(images)`` gives the scaled features of any images, X
    the training set's and alpha the dual model. Call it under
    `jax.default_matmul_precision("highest")`."""
    from keystone_tpu.pipelines.random_patch_cifar import learn_filters

    config = program_config(sizes, seed)
    filters, whitener = learn_filters(train.data, config)
    n = train.data.count
    G = (jnp.asarray(whitener.whitener, jnp.float32)
         @ jnp.asarray(filters, jnp.float32).T)
    mu = jnp.asarray(whitener.means, jnp.float32)
    shape = (sizes["patch_size"], sizes["alpha"], sizes["pool_size"],
             sizes["pool_stride"], jnp.dtype(sizes["default_matmul_operands"]))
    X, mean, std = _standardize(
        _featurize(train.data.array[:n], G, mu, shape=shape))
    Y = plain.indicators(train.labels.array[:n], sizes["num_classes"])
    alpha = kernel_ridge(X, Y, min(sizes["kernel_block"], n),
                         sizes["num_epochs"], sizes["lam"], sizes["gamma"],
                         config.seed)
    return (lambda images: (_featurize(images, G, mu, shape=shape) - mean)
            / std), X, alpha


def scores(train, test, sizes, seed):
    """Class scores (numpy, test rows by classes) of the reference
    fitted on ``train``."""
    with jax.default_matmul_precision("highest"):
        featurize, X, alpha = fit(train, sizes, seed)
        T = featurize(test.data.array[:test.data.count])
        block = min(sizes["kernel_block"], X.shape[0])
        out = jnp.zeros((T.shape[0], alpha.shape[1]), jnp.float32)
        for s in range(0, X.shape[0], block):
            out = out + (_kernel_block(T, X[s:s + block], sizes["gamma"])
                         @ alpha[s:s + block])
        return np.asarray(out)


def predict(train, test, sizes, seed):
    """Test predictions (numpy int array) of the reference."""
    return np.argmax(scores(train, test, sizes, seed), axis=-1)

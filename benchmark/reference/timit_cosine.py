"""The plain reference of TimitPipeline: every branch's features as
cos(X @ W + b), the branches side by side, block coordinate descent
least squares over the same blocks (one a branch) and the class scores,
in float32 at `highest` matmul precision. The random W and b of every
branch are the model's random parameters ("weights"): they are read off
the program's `cosine_branches` built with the same seed, and everything
after them is computed here.

One departure from float32 throughout, and why. The configuration
states two of its products at the backend's default matmul precision
(`default_matmul_operands` in its file: on a TPU the operands are
rounded to bfloat16, the products and sums are float32): the
projection X @ W of the featurizer and the scoring product of the
fitted model. The reference rounds the same operands the same way and
computes the rest exactly, so that what is left to differ is the solver,
which the configuration states in float32: with every product in
float32 the featurizer's rounding alone moved 0.4% of the test
predictions, and a solver run in bfloat16 hid behind it (my chip runs,
PR 28; PERF.md section 4). On the CPU the default is float32 and the
tests say so in their sizes.

The test frames go through in chunks, so their features (16,384 a frame
at the benchmark's width) are never held for the whole test set."""

import jax
import jax.numpy as jnp
import numpy as np

from ..configs.timit_cosine import program_config
from . import plain

CHUNK = 4096  # test frames a step: 268 MB of float32 features at 16,384


def _weights(sizes, seed):
    from keystone_tpu.pipelines.timit import cosine_branches

    branches = cosine_branches(program_config(sizes, seed), sizes["input_dim"])
    return (jnp.concatenate([jnp.asarray(b.W) for b in branches], axis=1),
            jnp.concatenate([jnp.asarray(b.b) for b in branches]))


def _product(A, B, operands):
    """A @ B with both operands rounded to ``operands`` and the products
    and sums in float32."""
    return jnp.matmul(A.astype(operands), B.astype(operands),
                      preferred_element_type=jnp.float32)


@jax.jit(static_argnames="operands")
def _features(X, W, b, *, operands):
    return jnp.cos(_product(X, W, operands) + b)


@jax.jit(static_argnames="operands")
def _scores(X, W, b, M, c, *, operands):
    return _product(_features(X, W, b, operands=operands), M, operands) + c


def scores(train, test, sizes, seed):
    """Class scores (numpy, test rows by classes) of the reference
    fitted on ``train``."""
    W, b = _weights(sizes, seed)
    operands = jnp.dtype(sizes["default_matmul_operands"])
    n, m = train.data.count, test.data.count
    with jax.default_matmul_precision("highest"):
        X = _features(train.data.array[:n], W, b, operands=operands)
        Y = plain.indicators(train.labels.array[:n], sizes["num_classes"])
        M, c = plain.block_least_squares(
            X, Y, sizes["block_size"], sizes["bcd_iters"], sizes["lam"])
        del X
        frames = test.data.array[:m]
        return np.concatenate([
            np.asarray(_scores(frames[i:i + CHUNK], W, b, M, c,
                               operands=operands))
            for i in range(0, m, CHUNK)])


def predict(train, test, sizes, seed):
    """Test predictions (numpy int array) of the reference."""
    return np.argmax(scores(train, test, sizes, seed), axis=-1)

"""The plain reference of TimitPipeline fitted across a mesh, on ONE
device: the frames are pulled to the mesh's first device and the fit
works a feature block at a time, so that it fits one chip where the
(n, d) features of all four chips' rows do not (262,144 x 16,384 x 4 B =
17.2 GB), and shares nothing with the SPMD partitioner it judges.

A block step makes the block's features from the frames (cos(X @ W_i +
b_i), one branch, 4.29 GB at the benchmark's sizes), centres them by
their own column means, and then does what `plain.block_least_squares`
does: add the block's part back to the residual, solve (Xb'Xb + lam I)
Wb = Xb'R, take it out again. The features are made anew in every epoch
and never kept; a block's Cholesky factor is (it depends on nothing an
epoch changes, and forming the Gram again would cost 8.8 TFLOP a block
step). Float32 at `highest` throughout, but for the two products that the
configuration states at the backend's default precision
(`default_matmul_operands`), whose operands `reference/timit_cosine.py`'s
helpers round the same way; W and b are read off the program's
`cosine_branches` as there."""

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from . import plain
from . import timit_cosine as one_chip


@partial(jax.jit, static_argnames="operands")
def _centred_block(frames, W, b, *, operands):
    X = one_chip._features(frames, W, b, operands=operands)
    xm = X.mean(axis=0)
    return X - xm, xm


@jax.jit
def _factor(Xb, lam):
    G = Xb.T @ Xb + lam * jnp.eye(Xb.shape[1], dtype=Xb.dtype)
    return jax.scipy.linalg.cho_factor(G)[0]


@partial(jax.jit, donate_argnums=(1,))
def _block_step(Xb, R, Wb, factor):
    R = R + Xb @ Wb
    Wb = jax.scipy.linalg.cho_solve((factor, False), Xb.T @ R)
    return R - Xb @ Wb, Wb


def fit(frames, labels, W, b, sizes):
    """(M, c): the model over the gathered features and its intercept,
    from frames and labels that live on one device."""
    operands = jnp.dtype(sizes["default_matmul_operands"])
    block, k = sizes["block_size"], sizes["num_classes"]
    lam = jnp.float32(sizes["lam"])
    starts = range(0, W.shape[1], block)
    Y = plain.indicators(labels, k)
    ym = Y.mean(axis=0)
    R = Y - ym
    del Y
    Ws = [jnp.zeros((min(block, W.shape[1] - s), k), jnp.float32)
          for s in starts]
    factors, means = [None] * len(starts), [None] * len(starts)
    for _ in range(sizes["bcd_iters"]):
        for i, s in enumerate(starts):
            Xb, means[i] = _centred_block(
                frames, W[:, s:s + block], b[s:s + block], operands=operands)
            if factors[i] is None:
                factors[i] = _factor(Xb, lam)
            R, Ws[i] = _block_step(Xb, R, Ws[i], factors[i])
            del Xb  # one block of features at a time
    M = jnp.concatenate(Ws, axis=0)
    return M, ym - jnp.concatenate(means) @ M


def scores(train, test, sizes, seed):
    """Class scores (numpy, test rows by classes) of the reference
    fitted on ``train``, computed on the mesh's first device."""
    device = train.data.mesh.devices.flat[0]
    on_one = lambda x: jax.device_put(x, device)
    W, b = map(on_one, one_chip._weights(sizes, seed))
    operands = jnp.dtype(sizes["default_matmul_operands"])
    n, m = train.data.count, test.data.count
    with jax.default_matmul_precision("highest"):
        M, c = fit(on_one(train.data.array)[:n], on_one(train.labels.array)[:n],
                   W, b, sizes)
        frames = on_one(test.data.array)[:m]
        return np.concatenate([
            np.asarray(one_chip._scores(frames[i:i + one_chip.CHUNK], W, b, M,
                                        c, operands=operands))
            for i in range(0, m, one_chip.CHUNK)])


def predict(train, test, sizes, seed):
    """Test predictions (numpy int array) of the reference."""
    return np.argmax(scores(train, test, sizes, seed), axis=-1)

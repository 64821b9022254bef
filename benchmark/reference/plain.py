"""Plain pieces the references share: block coordinate descent least
squares with an L2 term, written out as loops of `jax.numpy` calls in
float32 at `highest` matmul precision. No kernel, no fusion, no cache,
no executor, and nothing imported from the program."""

from functools import partial

import jax
import jax.numpy as jnp


def indicators(labels, num_classes):
    return 2.0 * jax.nn.one_hot(labels, num_classes, dtype=jnp.float32) - 1.0


@partial(jax.jit, donate_argnums=0)
def _centre(X):
    xm = X.mean(axis=0)
    return X - xm, xm


def block_least_squares(X, Y, block, epochs, lam):
    """Centred ridge regression by exact block updates: for each epoch
    and each block of ``block`` columns, add the block's part back to
    the residual, solve (Xb'Xb + lam I) Wb = Xb'R and take it out again.
    With one block and one epoch this is the exact ridge solve. ``X`` is
    given up (its buffer is reused for the centred copy). Returns (W, b)
    with b the intercept."""
    with jax.default_matmul_precision("highest"):
        X, xm = _centre(X)  # in place: two copies do not fit at TIMIT's size
        ym = Y.mean(axis=0)
        R = Y - ym
        d = X.shape[1]
        starts = list(range(0, d, block))
        Ws = [jnp.zeros((min(block, d - s), Y.shape[1]), jnp.float32)
              for s in starts]
        for _ in range(epochs):
            for i, s in enumerate(starts):
                Xb = X[:, s:s + block]
                R = R + Xb @ Ws[i]
                G = Xb.T @ Xb + lam * jnp.eye(Xb.shape[1], dtype=jnp.float32)
                # G is symmetric positive definite: Cholesky. (An LU solve
                # of 2,048 columns took the chip seconds, PR 24.)
                Ws[i] = jax.scipy.linalg.cho_solve(
                    jax.scipy.linalg.cho_factor(G), Xb.T @ R)
                R = R - Xb @ Ws[i]
        W = jnp.concatenate(Ws, axis=0)
        return W, ym - xm @ W


def predict(X, W, b):
    with jax.default_matmul_precision("highest"):
        return jnp.argmax(X @ W + b, axis=-1)

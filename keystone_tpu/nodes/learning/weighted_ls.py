"""Class-weighted least squares solvers.

Reference: nodes/learning/BlockWeightedLeastSquares.scala:36-371 and
PerClassWeightedLeastSquares.scala:31-223 + internal/
ReWeightedLeastSquares.scala:18-142.

The model: for output class c every example gets weight
  w_i(c) = mixtureWeight / n_c   if y_i = c   else (1−mixtureWeight) / n
i.e. each class's column of W solves its own weighted ridge problem.

The reference reshuffles data into one-partition-per-class
(`groupByClasses`, :111-131) and treeReduces per-class Gram matrices
(:211-226). TPU-native: no reshuffle — the per-class Grams are a single
batched einsum over the data-sharded X with a weight matrix (n, k), and
the per-class solves are a vmapped Cholesky. Class-partition parallelism
becomes a batched (class-major) solve on device (SURVEY.md §2.7).
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from ...data.dataset import Dataset
from ...workflow.pipeline import LabelEstimator
from .linear import LinearMapper


@partial(jax.jit, static_argnames=("block_size", "num_blocks", "num_iter"))
def _bwls_fit(X, Y, mask, lam, mixture_weight, block_size, num_blocks, num_iter):
    with jax.default_matmul_precision("highest"):
        n_pad, d_pad = X.shape
        k = Y.shape[1]
        dtype = X.dtype
        count = jnp.sum(mask)

        # Y is ±1 one-hot (masked); class membership and sizes from it
        member = (Y > 0).astype(dtype) * mask[:, None]  # (n, k)
        n_c = jnp.maximum(jnp.sum(member, axis=0), 1.0)  # (k,)
        # per-example per-class weights (n, k)
        Wts = (
            mixture_weight * member / n_c
            + (1.0 - mixture_weight) * mask[:, None] / count
        )

        # center per class with weighted means (the reference's per-block
        # covariance blend collapses to weighted centering + weighted Gram)
        wsum = jnp.sum(Wts, axis=0)  # (k,) == 1 by construction, kept general
        xbar = (Wts.T @ X) / wsum[:, None]  # (k, d) weighted feature means
        ybar = jnp.sum(Wts * Y, axis=0) / wsum  # (k,)

        eye = lam * jnp.eye(block_size, dtype=dtype)

        def block_step(carry, b_idx):
            W, R = carry  # W: (nb, B, k); R: (n, k) weighted residual of Yc
            Xb = jax.lax.dynamic_slice_in_dim(X, b_idx * block_size, block_size, 1)
            xbar_b = jax.lax.dynamic_slice_in_dim(xbar, b_idx * block_size, block_size, 1)
            Wb = W[b_idx]
            # centered block per class: Xb - xbar_b[c] — handled inside the
            # weighted Gram algebra below (means fold into rank-1 terms).
            R1 = R + Xb @ Wb
            # per-class weighted Gram: G[c] = (Xb*w_c)ᵀXb − wsum_c·x̄_b,c x̄_b,cᵀ
            XW = jnp.einsum("nb,nc->cnb", Xb, Wts)  # (k, n, B) weighted copies
            G = jnp.einsum("cnb,nd->cbd", XW, Xb)
            G = G - jnp.einsum("c,cb,cd->cbd", wsum, xbar_b, xbar_b)
            # per-class correlation: C[c] = (Xb*w_c)ᵀ R1[:,c] − x̄_b,c·(w_cᵀR1_c)
            C = jnp.einsum("cnb,nc->cb", XW, R1)
            rbar = jnp.sum(Wts * R1, axis=0)  # (k,)
            C = C - xbar_b * rbar[:, None]
            # LU, not Cholesky (`assume_a="pos"`): inside this program the
            # batched Cholesky solve came back wrong on every multi-chip
            # v5e mesh (max |Δ| 0.17 of 0.38 from the one-device answer,
            # with G and C themselves right to 4e-7) and right on one
            # chip and on virtual CPU devices; the LU solve was right
            # everywhere (PERF.md, PR 22). The factorizations are k·B³
            # against the Gram's k·n·B², so the form of the solve is not
            # where this solver's time goes.
            Wb_new = jax.vmap(
                lambda Gc, Cc: jnp.linalg.solve(Gc + eye, Cc)
            )(G, C).T  # (B, k)
            R2 = R1 - Xb @ Wb_new
            return (W.at[b_idx].set(Wb_new), R2), None

        def epoch(carry, _):
            carry, _ = jax.lax.scan(block_step, carry, jnp.arange(num_blocks))
            return carry, None

        W0 = jnp.zeros((num_blocks, block_size, k), dtype)
        R0 = (Y - ybar) * mask[:, None]
        (W, _), _ = jax.lax.scan(epoch, (W0, R0), None, length=num_iter)
        W_full = W.reshape(d_pad, k)
        b = ybar - jnp.einsum("cd,dc->c", xbar, W_full)
        return W_full, b


class BlockWeightedLeastSquaresEstimator(LabelEstimator):
    """Class-weighted BCD (BlockWeightedLeastSquares.scala:36-371)."""

    def __init__(self, block_size: int, num_iter: int, lam: float,
                 mixture_weight: float = 0.5):
        self.block_size = block_size
        self.num_iter = num_iter
        self.lam = lam
        self.mixture_weight = mixture_weight
        self.weight = 3 * num_iter + 1

    def abstract_fit(self, in_specs):
        from ...analysis.specs import supervised_fit_spec

        return supervised_fit_spec(in_specs, self.label)

    def fit(self, data: Dataset, labels: Dataset) -> LinearMapper:
        X, Y = data.array, labels.array
        d = X.shape[1]
        bs = min(self.block_size, d)
        num_blocks = -(-d // bs)
        if num_blocks * bs != d:
            X = jnp.pad(X, [(0, 0), (0, num_blocks * bs - d)])
        W, b = _bwls_fit(
            X, Y, data.mask_as(X.dtype),
            jnp.asarray(self.lam, X.dtype),
            jnp.asarray(self.mixture_weight, X.dtype),
            bs, num_blocks, self.num_iter,
        )
        return LinearMapper(W[:d], b)


class PerClassWeightedLeastSquares(LabelEstimator):
    """Single-shot variant via the same weighted normal equations
    (PerClassWeightedLeastSquares.scala:31-223 delegating to
    ReWeightedLeastSquaresSolver): one block, one sweep."""

    def __init__(self, lam: float, mixture_weight: float = 0.5):
        self.lam = lam
        self.mixture_weight = mixture_weight

    def fit(self, data: Dataset, labels: Dataset) -> LinearMapper:
        est = BlockWeightedLeastSquaresEstimator(
            data.array.shape[1], 1, self.lam, self.mixture_weight
        )
        return est.fit(data, labels)

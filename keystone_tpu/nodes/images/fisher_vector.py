"""Fisher vector encoding.

Reference: nodes/images/FisherVector.scala:14-94 (Sanchez et al. closed
form over GMM posteriors :33-53) and the native enceval variant
(external/FisherVector.scala:17-55, EncEval.cxx `calcAndGetFVs`). The
C++ encoder is replaced by a jitted einsum program — per image:
posteriors (nd×k GEMM), then first/second-order aggregated gradients.

`GMMFisherVectorEstimator` keeps the reference's optimizable shape
(FisherVector.scala:86-94 picks native iff k ≥ 32); here both routes are
the same device kernel so optimize() just returns the default.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from ...data.dataset import HostDataset
from ...workflow.pipeline import Estimator, OptimizableEstimator, Transformer
from ..learning.gmm import GaussianMixtureModel, GaussianMixtureModelEstimator


def _fisher_batch(X, means, variances, weights):
    """FVs of a batch of descriptor matrices X (b, nd, d) → (b, d, 2k)
    (each matching the reference's DenseMatrix[d, 2k] layout,
    FisherVector.scala:33-53). Two products an image: the posteriors'
    Mahalanobis form as [x², x] (nd, 2d) against [1/var; -2 mu/var], and
    both moments as q' [x, x²]: the (nd, k) arrays are written and read
    once each where four products made it twice."""
    with jax.named_scope("ks.fisher"), \
            jax.default_matmul_precision("highest"):
        nd, d = X.shape[1:]
        moments_in = jnp.concatenate([X, X * X], axis=2)  # (b, nd, 2d)
        inv = 1.0 / variances  # (k, d)
        # ||x-m||²_inv = x²·inv - 2x·(m·inv) + m²·inv
        quad = (
            moments_in @ jnp.concatenate([-2.0 * means * inv, inv], axis=1).T
            + jnp.sum(means * means * inv, axis=1)
        )
        logp = jnp.log(weights) - 0.5 * (
            quad + jnp.sum(jnp.log(variances), axis=1)
            + d * jnp.log(2.0 * jnp.pi))
        q = jax.nn.softmax(logp, axis=2)  # (b, nd, k)
        sigma = jnp.sqrt(variances)  # (k, d)
        # S0_k = sum_i q_ik ; S1_k = sum_i q_ik x_i ; S2_k = sum_i q_ik x_i²
        S0 = jnp.sum(q, axis=1)[:, :, None]  # (b, k, 1)
        S = jnp.einsum("bnk,bnd->bkd", q, moments_in)
        S1, S2 = S[:, :, :d], S[:, :, d:]
        w = weights[:, None]
        # gradient wrt means:   (S1 - mu*S0) / (sigma * sqrt(w) * nd)
        g_mu = (S1 - means * S0) / (sigma * jnp.sqrt(w) * nd)
        # gradient wrt sigmas:  (S2 - 2 mu S1 + (mu²-sigma²) S0) / (sigma² sqrt(2w) nd)
        g_sig = (
            S2 - 2.0 * means * S1 + (means**2 - variances) * S0
        ) / (variances * jnp.sqrt(2.0 * w) * nd)
        return jnp.concatenate(
            [g_mu.transpose(0, 2, 1), g_sig.transpose(0, 2, 1)], axis=2)


@jax.jit
def _fisher_vector(X, means, variances, weights):
    """FV of one descriptor matrix X (nd, d) → (d, 2k)."""
    return _fisher_batch(X[None], means, variances, weights)[0]


class FisherVector(Transformer):
    """Descriptor matrix (nd, d) → FV matrix (d, 2k)
    (FisherVector.scala:14-62). Traceable: in a fused program a
    microbatch of descriptor matrices is encoded where it was made."""

    fusable = True
    chunkable = True  # pure per-item fn: distributes over chunks
    precision_tolerance = "exact"  # posteriors and moments: f32 at highest

    def __init__(self, gmm: GaussianMixtureModel):
        self.gmm = gmm

    def abstract_apply(self, elem):
        from ...analysis.specs import SpecMismatchError, shape_struct

        if getattr(elem, "ndim", 0) != 2:
            raise SpecMismatchError(
                "FisherVector input element must be a 2-D descriptor matrix")
        return shape_struct((int(elem.shape[-1]), 2 * self.gmm.k), np.float32)

    def apply(self, x):
        return _fisher_vector(
            jnp.asarray(x, jnp.float32),
            self.gmm.means,
            self.gmm.variances,
            self.gmm.weights,
        )

    def fuse(self):
        g = self.gmm
        return (("FisherVector",), (g.means, g.variances, g.weights),
                lambda p, xb: _fisher_batch(xb, *p))

    def count_rows(self, elem, rows: int):
        from ...telemetry import counter

        counter("fisher.images").inc(rows)

    def apply_batch(self, data):
        if isinstance(data, HostDataset):
            return HostDataset([np.asarray(self.apply(x)) for x in data.items])
        from ..util.fusion import FusedBatchTransformer

        return FusedBatchTransformer([self]).apply_batch(data)


def _fv_fit_spec(k: int, label: str):
    """TransformerSpec of a to-be-fitted FV encoder: descriptor matrix
    (nd, d) → (d, 2k) float32 — the output geometry depends only on the
    configured component count, so it is decidable before the GMM fit
    runs (what lets the serving certifier price the FV apply path)."""
    from ...analysis.specs import (
        SpecMismatchError,
        TransformerSpec,
        shape_struct,
    )

    def elem_fn(elem):
        if getattr(elem, "ndim", 0) != 2:
            raise SpecMismatchError(
                f"{label} input element must be a 2-D descriptor matrix")
        return shape_struct((int(elem.shape[-1]), 2 * k), np.float32)

    return TransformerSpec(elem_fn, label=label)


def _fv_apply_flops(k: int, in_elem) -> "float | None":
    """≈8·nd·d·k per item: the posterior GEMM (2·nd·d·k), the S1/S2
    aggregation GEMMs (4·nd·d·k), and the elementwise posterior and
    gradient work. Declared so the roofline's fitted-apply model prices
    the FV encoder at its honest order — the generic dense in×out map
    charges descriptor rows against output rows, ~nd/8 times over."""
    import jax as _jax

    leaves = _jax.tree_util.tree_leaves(in_elem)
    if len(leaves) != 1 or getattr(leaves[0], "ndim", 0) != 2:
        return None
    nd, d = leaves[0].shape
    return 8.0 * float(nd) * float(d) * float(k)


class ScalaGMMFisherVectorEstimator(Estimator):
    """Fit a GMM on descriptor samples, return the FV encoder
    (FisherVector.scala:69-84)."""

    fusable_fit = True  # always fits a traceable FisherVector

    def __init__(self, k: int, num_iters: int = 30, seed: int = 0):
        self.k = k
        self.num_iters = num_iters
        self.seed = seed

    def abstract_fit(self, in_specs):
        return _fv_fit_spec(self.k, self.label)

    def abstract_apply_flops(self, in_elem, out_elem):
        return _fv_apply_flops(self.k, in_elem)

    def fit(self, data) -> FisherVector:
        gmm = GaussianMixtureModelEstimator(
            self.k, num_iters=self.num_iters, seed=self.seed
        ).fit(data)
        return FisherVector(gmm)


# the "native" route of the reference is the same device kernel here
EncEvalGMMFisherVectorEstimator = ScalaGMMFisherVectorEstimator


class GMMFisherVectorEstimator(OptimizableEstimator):
    """Optimizable FV estimator (FisherVector.scala:86-94). Both the
    reference's scala and enceval routes map to the same XLA kernel, so
    the choice is degenerate — kept for API parity."""

    fusable_fit = True  # either route fits a traceable FisherVector

    def __init__(self, k: int, num_iters: int = 30, seed: int = 0):
        self.k = k
        self.num_iters = num_iters
        self.seed = seed

    def abstract_fit(self, in_specs):
        return _fv_fit_spec(self.k, self.label)

    def abstract_apply_flops(self, in_elem, out_elem):
        return _fv_apply_flops(self.k, in_elem)

    @property
    def default(self) -> Estimator:
        return ScalaGMMFisherVectorEstimator(self.k, self.num_iters, self.seed)

    def optimize(self, sample, num_per_shard) -> Estimator:
        return self.default

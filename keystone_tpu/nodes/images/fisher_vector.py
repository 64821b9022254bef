"""Fisher vector encoding.

Reference: nodes/images/FisherVector.scala:14-94 (Sanchez et al. closed
form over GMM posteriors :33-53) and the native enceval variant
(external/FisherVector.scala:17-55, EncEval.cxx `calcAndGetFVs`). The
C++ encoder is replaced by a jitted program — per image: posteriors
(nd×k GEMM), then first/second-order aggregated gradients; on a TPU the
posteriors and moments are one Pallas kernel (`ops.fisher_moments_pallas`).

`GMMFisherVectorEstimator` keeps the reference's optimizable shape
(FisherVector.scala:86-94 picks native iff k ≥ 32); here both routes are
the same device kernel so optimize() just returns the default.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from ...data.dataset import HostDataset
from ...ops.pallas_kernels import fisher_moments_pallas, use_fisher_kernel
from ...workflow.pipeline import Estimator, OptimizableEstimator, Transformer
from ..learning.gmm import GaussianMixtureModel, GaussianMixtureModelEstimator


def _fisher_moments_reference(X, means, variances, weights):
    """The jnp form of `fisher_moments_pallas`: S0 (b, k) and S1, S2
    transposed (b, d, k) of descriptor matrices X (b, nd, d). Two
    products an image: the posteriors' Mahalanobis form as [x², x]
    (nd, 2d) against [1/var; -2 mu/var], and both moments as q' [x, x²];
    XLA writes the (nd, k) log-densities to HBM and reads them back for
    the softmax's sum, the moments and S0."""
    d = X.shape[2]
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
    # S0_k = sum_i q_ik ; S1_k = sum_i q_ik x_i ; S2_k = sum_i q_ik x_i²
    S = jnp.einsum("bnk,bnd->bkd", q, moments_in)
    return (jnp.sum(q, axis=1), S[:, :, :d].transpose(0, 2, 1),
            S[:, :, d:].transpose(0, 2, 1))


def _fisher_batch(X, means, variances, weights):
    """FVs of a batch of descriptor matrices X (b, nd, d) → (b, d, 2k)
    (each matching the reference's DenseMatrix[d, 2k] layout,
    FisherVector.scala:33-53), from the posterior-weighted moments:
    on a TPU, for an image of at least one tile of descriptors and k a
    multiple of 128 (d and k as wide as its VMEM holds), one Pallas
    kernel that keeps the posteriors in VMEM (`use_fisher_kernel`); the
    jnp form everywhere else."""
    with jax.named_scope("ks.fisher"), \
            jax.default_matmul_precision("highest"):
        nd, d = X.shape[1:]
        if use_fisher_kernel(nd, d, means.shape[0]):
            S0, S1, S2 = fisher_moments_pallas(X, means, variances, weights)
        else:
            S0, S1, S2 = _fisher_moments_reference(
                X, means, variances, weights)
        S0 = S0[:, None, :]  # (b, 1, k)
        means, variances = means.T, variances.T  # (d, k)
        sigma = jnp.sqrt(variances)
        # gradient wrt means:   (S1 - mu*S0) / (sigma * sqrt(w) * nd)
        g_mu = (S1 - means * S0) / (sigma * jnp.sqrt(weights) * nd)
        # gradient wrt sigmas:  (S2 - 2 mu S1 + (mu²-sigma²) S0) / (sigma² sqrt(2w) nd)
        g_sig = (
            S2 - 2.0 * means * S1 + (means**2 - variances) * S0
        ) / (variances * jnp.sqrt(2.0 * weights) * nd)
        return jnp.concatenate([g_mu, g_sig], axis=2)


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

    def rows_one_pass(self, nd: int) -> int:
        """Descriptors of an nd-descriptor image that `_fisher_batch`
        hands to the one-pass kernel (`use_fisher_kernel`): all of them
        or none."""
        d, k = self.gmm.means.shape[1], self.gmm.k
        return nd if use_fisher_kernel(nd, d, k) else 0

    def count_rows(self, elem, rows: int):
        """`fisher.images` and `fisher.rows_one_pass`: what one dispatch
        of a program holding this stage encodes, from the shapes."""
        from ...telemetry import counter

        counter("fisher.images").inc(rows)
        counter("fisher.rows_one_pass").inc(
            rows * self.rows_one_pass(int(elem.shape[0])))

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

"""PCA family (reference nodes/learning/PCA.scala:19-247,
DistributedPCA.scala:20-74, ApproximatePCA.scala:22-85).

Three fits, as in the reference:
  - `PCAEstimator` — "local": SVD of a (sampled) matrix on one replica
    (the reference collects to the driver for LAPACK sgesvd).
  - `DistributedPCAEstimator` — TSQR: per-shard QR inside `shard_map`,
    all-gather the R factors, QR again, then SVD of the final R
    (the reference uses mlmatrix TSQR; the communication pattern — a
    tree of R-factor reductions — becomes one all-gather over ICI since
    R is tiny (d×d)).
  - `ApproximatePCAEstimator` — randomized sketch (Halko-Martinsson-
    Tropp algs 4.4/5.1): Gaussian test matrix, q power iterations with
    QR re-orthonormalization, SVD of the small projected matrix.

Items can be vectors (datasets of rows) or per-item descriptor matrices
(the SIFT path: (num_descriptors, d) per image) — `PCATransformer`
applies to either.
"""

from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from ...data.dataset import Dataset, HostDataset
from ...parallel import mesh as meshlib
from ...workflow.pipeline import Estimator, OptimizableEstimator, Transformer
from .cost_model import CostModel, CostProfile


def _sign_convention(V):
    """Match the reference's matlab sign convention (PCA.scala:196-206):
    flip each component so its largest-|.| coordinate is positive."""
    idx = jnp.argmax(jnp.abs(V), axis=0)
    signs = jnp.sign(V[idx, jnp.arange(V.shape[1])])
    return V * signs


class PCATransformer(Transformer):
    """x @ components, x a vector or a (rows × d) descriptor matrix."""

    def __init__(self, components):
        self.components = jnp.asarray(components)  # (d, k)

    def abstract_apply(self, elem):
        from ...analysis.specs import SpecMismatchError, shape_struct

        d, k = self.components.shape
        if getattr(elem, "ndim", 0) >= 1:
            if elem.shape[-1] != d:
                raise SpecMismatchError(
                    f"PCA components are ({d}, {k}) but the input element's "
                    f"last axis is {elem.shape[-1]}")
            return shape_struct(tuple(elem.shape[:-1]) + (k,),
                                self.components.dtype)
        raise SpecMismatchError("PCA input element must be at least 1-D")

    def apply(self, x):
        return jnp.asarray(x) @ self.components

    def apply_batch(self, data):
        if isinstance(data, HostDataset):
            return data.map(lambda x: np.asarray(x) @ np.asarray(self.components))
        return data.map_batches(
            lambda X: _project(X, self.components), jitted=False
        )


@jax.jit
def _project(X, comps):
    return X @ comps


BatchPCATransformer = PCATransformer  # the reference's per-matrix variant


def _collect_rows(data, max_rows: Optional[int] = None) -> np.ndarray:
    """Stack a dataset of vectors or descriptor matrices into one host
    matrix (the reference's collect-to-driver, PCA.scala:177-185)."""
    if isinstance(data, HostDataset):
        rows = [np.atleast_2d(np.asarray(x)) for x in data.items]
        X = np.concatenate(rows, axis=0)
    elif isinstance(data, Dataset):
        X = np.asarray(data.numpy())
        if X.ndim == 3:
            X = X.reshape(-1, X.shape[-1])
    else:
        X = np.atleast_2d(np.asarray(data))
    if max_rows is not None and X.shape[0] > max_rows:
        idx = np.linspace(0, X.shape[0] - 1, max_rows, dtype=np.int64)
        X = X[idx]
    return X.astype(np.float32)


@jax.jit
def _svd_components(X):
    with jax.default_matmul_precision("highest"):
        mu = jnp.mean(X, axis=0)
        _, _, Vt = jnp.linalg.svd(X - mu, full_matrices=False)
        return _sign_convention(Vt.T)


def _pca_fit_spec(dims: int, label: str, train_spec=None):
    """TransformerSpec of a to-be-fitted PCA: last axis d → dims, with d
    pinned from the training spec when known."""
    from ...analysis.specs import (
        SpecMismatchError,
        TransformerSpec,
        is_known,
        shape_struct,
    )
    import jax as _jax

    d = None
    if train_spec is not None and is_known(getattr(train_spec, "element", None)):
        leaves = _jax.tree_util.tree_leaves(train_spec.element)
        if len(leaves) == 1 and getattr(leaves[0], "ndim", 0) >= 1:
            d = int(leaves[0].shape[-1])

    def elem_fn(elem):
        if getattr(elem, "ndim", 0) < 1:
            raise SpecMismatchError(f"{label} input element must be ≥ 1-D")
        if d is not None and elem.shape[-1] != d:
            raise SpecMismatchError(
                f"{label} was fit on {d}-dim rows but the input element's "
                f"last axis is {elem.shape[-1]}")
        return shape_struct(tuple(elem.shape[:-1]) + (dims,), np.float32)

    return TransformerSpec(elem_fn, label=label)


class PCAEstimator(Estimator):
    """Local PCA via SVD (PCA.scala:162-247)."""

    precision_tolerance = "exact"  # moments/decomposition: f32 inputs

    def __init__(self, dims: int, sample_rows: Optional[int] = 100_000):
        self.dims = dims
        self.sample_rows = sample_rows

    def abstract_fit(self, in_specs):
        return _pca_fit_spec(self.dims, self.label,
                             in_specs[0] if in_specs else None)

    def fit(self, data) -> PCATransformer:
        X = _collect_rows(data, self.sample_rows)
        V = _svd_components(jnp.asarray(X))
        return PCATransformer(V[:, : self.dims])


@partial(jax.jit, static_argnames=("n_shards",))
def _tsqr_r(X, n_shards: int):
    """R factor of a TSQR over the data-sharded X (DistributedPCA.scala:47)."""
    with jax.default_matmul_precision("highest"):
        if n_shards == 1:
            return jnp.linalg.qr(X, mode="r")

        from jax.sharding import PartitionSpec as P

        mesh = meshlib.current_mesh()

        def local_qr(xs):
            r = jnp.linalg.qr(xs, mode="r")  # (d, d)
            return r[None]

        rs = jax.shard_map(
            local_qr, mesh=mesh,
            in_specs=(P(meshlib.DATA_AXIS),), out_specs=P(meshlib.DATA_AXIS),
            check_vma=False,
        )(X)  # (n_shards, d, d), sharded; gather is d² per shard — tiny
        stacked = rs.reshape(-1, X.shape[1])
        return jnp.linalg.qr(stacked, mode="r")


class DistributedPCAEstimator(Estimator):
    """PCA via TSQR + SVD of R (DistributedPCA.scala:20-74)."""

    precision_tolerance = "exact"  # moments/decomposition: f32 inputs

    def __init__(self, dims: int):
        self.dims = dims

    def abstract_fit(self, in_specs):
        return _pca_fit_spec(self.dims, self.label,
                             in_specs[0] if in_specs else None)

    def abstract_sharding(self, in_shardings, in_specs):
        """TSQR's first stage is a per-shard QR inside `shard_map` over
        the ``data`` axis (`_tsqr_r`): the training rows must arrive
        data-sharded or the factorization implicitly reshards the whole
        matrix first (KP601)."""
        from ...analysis.sharding import fit_sharding_demands

        return fit_sharding_demands(1)

    def fit(self, data) -> PCATransformer:
        if isinstance(data, HostDataset):
            data = Dataset(_collect_rows(data))
        X = data.array
        valid_rows = data.count
        if X.ndim == 3:  # descriptor matrices: flatten rows
            rows_per_item = X.shape[1]
            X = X.reshape(-1, X.shape[-1])
            valid_rows = data.count * rows_per_item  # padded items are zero rows at the end
        mu = jnp.sum(X, axis=0) / valid_rows
        # center via masked subtraction (padded rows stay zero)
        Xc = (X - mu) * (jnp.arange(X.shape[0]) < valid_rows)[:, None]
        R = _tsqr_r(Xc, data.n_shards)
        _, _, Vt = jnp.linalg.svd(R, full_matrices=False)
        V = _sign_convention(Vt.T)
        return PCATransformer(V[:, : self.dims])


@partial(jax.jit, static_argnames=("k", "q"))
def _randomized_components(X, key, k: int, q: int):
    """HMT randomized range finder + power iterations
    (ApproximatePCA.scala:22-85)."""
    with jax.default_matmul_precision("highest"):
        mu = jnp.mean(X, axis=0)
        Xc = X - mu
        d = X.shape[1]
        omega = jax.random.normal(key, (d, k), X.dtype)
        Y = Xc @ omega
        Q, _ = jnp.linalg.qr(Y)
        for _ in range(q):
            Q, _ = jnp.linalg.qr(Xc.T @ Q)
            Q, _ = jnp.linalg.qr(Xc @ Q)
        B = Q.T @ Xc  # (k, d)
        _, _, Vt = jnp.linalg.svd(B, full_matrices=False)
        return _sign_convention(Vt.T)


class ApproximatePCAEstimator(Estimator):
    """Randomized sketch PCA (ApproximatePCA.scala:22-85)."""

    precision_tolerance = "exact"  # moments/decomposition: f32 inputs

    def __init__(self, dims: int, oversample: int = 10, q: int = 2, seed: int = 0):
        self.dims = dims
        self.oversample = oversample
        self.q = q
        self.seed = seed

    def abstract_fit(self, in_specs):
        return _pca_fit_spec(self.dims, self.label,
                             in_specs[0] if in_specs else None)

    def fit(self, data) -> PCATransformer:
        X = (
            data.array
            if isinstance(data, Dataset)
            else jnp.asarray(_collect_rows(data))
        )
        if X.ndim == 3:
            X = X.reshape(-1, X.shape[-1])
        V = _randomized_components(
            X, jax.random.PRNGKey(self.seed), self.dims + self.oversample, self.q
        )
        return PCATransformer(V[:, : self.dims])


class LocalPCACostModel(CostModel):
    def cost(self, p, cpu_weight=None, mem_weight=None, network_weight=None):
        cw, _, nw = self._weights(cpu_weight, mem_weight, network_weight)
        # collect everything to one replica + one SVD there
        return nw * 4.0 * p.n * p.d + cw * (2.0 * p.n * p.d * p.d)


class DistributedPCACostModel(CostModel):
    def cost(self, p, cpu_weight=None, mem_weight=None, network_weight=None):
        cw, _, nw = self._weights(cpu_weight, mem_weight, network_weight)
        # per-shard QR + d×d R gather + small SVD
        return cw * (2.0 * p.n * p.d * p.d / p.num_chips + 2.0 * p.d**3) + nw * (
            4.0 * p.d * p.d * p.num_chips
        )


class ColumnPCAEstimator(OptimizableEstimator):
    """Cost-model choice between local and distributed PCA
    (PCA.scala:117-155)."""

    def __init__(self, dims: int, num_chips: Optional[int] = None):
        self.dims = dims
        self.num_chips = num_chips
        self.chosen = None

    def abstract_fit(self, in_specs):
        # both cost-model outcomes (local/distributed) fit the same
        # last-axis d -> dims projection, so the spec is decidable
        # before the choice is
        return _pca_fit_spec(self.dims, self.label,
                             in_specs[0] if in_specs else None)

    @property
    def default(self) -> Estimator:
        return PCAEstimator(self.dims)

    def optimize(self, sample, num_per_shard) -> Estimator:
        chips = self.num_chips or meshlib.n_data_shards()
        if isinstance(sample, HostDataset) and len(sample):
            first = np.asarray(sample.items[0])
            d = first.shape[-1]
            rows_per_item = first.shape[0] if first.ndim == 2 else 1
        else:
            leaf = jax.tree_util.tree_leaves(sample.data)[0]
            d = leaf.shape[-1]
            rows_per_item = leaf.shape[1] if leaf.ndim == 3 else 1
        p = CostProfile(
            n=num_per_shard * chips * rows_per_item, d=d, k=self.dims,
            sparsity=1.0, num_chips=chips,
        )
        if LocalPCACostModel().cost(p) <= DistributedPCACostModel().cost(p):
            self.chosen = "local"
            return PCAEstimator(self.dims)
        self.chosen = "distributed"
        return DistributedPCAEstimator(self.dims)

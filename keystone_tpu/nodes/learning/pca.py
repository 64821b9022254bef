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
    """x @ components, x a vector or a (rows × d) descriptor matrix, in
    float32 at `highest` matmul precision (on a TPU the default would
    round both operands to bfloat16)."""

    fusable = True
    chunkable = True  # pure per-item fn: distributes over chunks
    precision_tolerance = "exact"  # a projection onto fitted components

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
        return _project(jnp.asarray(x), self.components)

    def fuse(self):
        return (("PCA",), (self.components,),
                lambda p, xb: _project_fn(xb, p[0]))

    def apply_batch(self, data):
        if isinstance(data, HostDataset):
            return data.map(lambda x: np.asarray(x) @ np.asarray(self.components))
        return data.map_batches(
            lambda X: _project(X, self.components), jitted=False
        )


def _project_fn(X, comps):
    with jax.named_scope("ks.pca.apply"):
        return jnp.matmul(X, comps.astype(X.dtype),
                          precision=jax.lax.Precision.HIGHEST)


_project = jax.jit(_project_fn)


BatchPCATransformer = PCATransformer  # the reference's per-matrix variant


def _collect_rows(data, max_rows: Optional[int] = None) -> np.ndarray:
    """Stack a dataset of vectors or descriptor matrices into one host
    matrix (the reference's collect-to-driver, PCA.scala:177-185)."""
    if isinstance(data, HostDataset):
        from ...telemetry import counter

        counter("sampler.host_bytes").inc(sum(
            x.nbytes for x in data.items if isinstance(x, jax.Array)))
        rows = [np.atleast_2d(np.asarray(x)) for x in data.items]
        X = np.concatenate(rows, axis=0)
    elif isinstance(data, Dataset):
        from ...telemetry import counter

        X = np.asarray(data.numpy())
        counter("sampler.host_bytes").inc(X.nbytes)
        if X.ndim == 3:
            X = X.reshape(-1, X.shape[-1])
    else:
        X = np.atleast_2d(np.asarray(data))
    if max_rows is not None and X.shape[0] > max_rows:
        idx = np.linspace(0, X.shape[0] - 1, max_rows, dtype=np.int64)
        X = X[idx]
    return X.astype(np.float32)


def _device_rows(data: Dataset):
    """A device dataset of vectors or of per-item matrices as one
    (rows, d) matrix on the device, and how many of its leading rows are
    real (padded items are zero rows at the end). Nothing crosses to the
    host."""
    X = data.array
    valid = data.count
    if X.ndim == 3:
        valid *= X.shape[1]
        X = X.reshape(-1, X.shape[-1])
    return X, valid


def _centered(X, valid):
    """X minus the mean of its ``valid`` leading rows, the rest zero."""
    live = (jnp.arange(X.shape[0]) < valid)[:, None]
    mu = jnp.sum(jnp.where(live, X, 0.0), axis=0) / valid
    return jnp.where(live, X - mu, 0.0)


def _pca_fit_svd(X, valid):
    """Components of the ``valid`` leading rows of X by one SVD of the
    centred matrix."""
    with jax.named_scope("ks.pca.fit"), \
            jax.default_matmul_precision("highest"):
        _, _, Vt = jnp.linalg.svd(_centered(X, valid), full_matrices=False)
        return _sign_convention(Vt.T)


_pca_fit_svd = jax.jit(_pca_fit_svd)  # the XLA module `jit__pca_fit_svd`


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
    fusable_fit = True  # always fits a traceable PCATransformer

    def __init__(self, dims: int, sample_rows: Optional[int] = 100_000):
        self.dims = dims
        self.sample_rows = sample_rows

    def abstract_fit(self, in_specs):
        return _pca_fit_spec(self.dims, self.label,
                             in_specs[0] if in_specs else None)

    def fit(self, data) -> PCATransformer:
        from ...telemetry import dispatch, span

        with span("pca_fit", cat="solver", layer="solver", route="svd"):
            if isinstance(data, Dataset):
                # all of a device dataset's rows, where they are
                X, valid = _device_rows(data)
            else:
                X = jnp.asarray(_collect_rows(data, self.sample_rows))
                valid = X.shape[0]
            with dispatch("_pca_fit_svd"):
                V = _pca_fit_svd(X, valid)
        return PCATransformer(V[:, : self.dims])


#: rows a leaf of the one-chip TSQR tree factors: 8,192 x d is a few MB,
#: and the leaves' QRs run as one batched factorization
TSQR_LEAF_ROWS = 8192


def _tsqr_r(X, n_shards: int):
    """R factor of a TSQR over the data-sharded X (DistributedPCA.scala:47).
    On one shard the tree's leaves are row blocks of `TSQR_LEAF_ROWS`
    (zero rows added to fill the last do not change R)."""
    with jax.default_matmul_precision("highest"):
        d = X.shape[1]
        if n_shards == 1:
            leaf = TSQR_LEAF_ROWS
            if X.shape[0] <= leaf:
                return jnp.linalg.qr(X, mode="r")
            blocks = -(-X.shape[0] // leaf)
            X = jnp.pad(X, [(0, blocks * leaf - X.shape[0]), (0, 0)])
            rs = jnp.linalg.qr(X.reshape(blocks, leaf, d), mode="r")
            return jnp.linalg.qr(rs.reshape(-1, d), mode="r")

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


@partial(jax.jit, static_argnames=("n_shards",))
def _pca_fit_tsqr(X, valid, n_shards: int):
    """Components by TSQR and an SVD of the small R: the XLA module
    `jit__pca_fit_tsqr`."""
    with jax.named_scope("ks.pca.fit"), \
            jax.default_matmul_precision("highest"):
        R = _tsqr_r(_centered(X, valid), n_shards)
        _, _, Vt = jnp.linalg.svd(R, full_matrices=False)
        return _sign_convention(Vt.T)


class DistributedPCAEstimator(Estimator):
    """PCA via TSQR + SVD of R (DistributedPCA.scala:20-74)."""

    precision_tolerance = "exact"  # moments/decomposition: f32 inputs
    fusable_fit = True  # always fits a traceable PCATransformer

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
        from ...telemetry import dispatch, span

        if isinstance(data, HostDataset):
            data = Dataset(_collect_rows(data))
        with span("pca_fit", cat="solver", layer="solver", route="tsqr"):
            X, valid = _device_rows(data)
            with dispatch("_pca_fit_tsqr"):
                V = _pca_fit_tsqr(X, valid, data.n_shards)
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


#: what one SVD of a tall centred matrix costs over a QR of it that keeps
#: R alone: the SVD forms Q and then U. Measured on a TPU v5 lite at
#: 997,189 x 128 in float32 at `highest` (my chip run, PR 40): 306 ms for
#: `_pca_fit_svd`, 44 ms for `_pca_fit_tsqr` (leaves of 8,192 rows)
SVD_OVER_QR = 7.0


class LocalPCACostModel(CostModel):
    def cost(self, p, cpu_weight=None, mem_weight=None, network_weight=None):
        cw, _, nw = self._weights(cpu_weight, mem_weight, network_weight)
        # the rows of the other chips brought to one replica (none where
        # there is one chip), then one SVD of the whole matrix there
        away = 4.0 * p.n * p.d * (p.num_chips - 1) / p.num_chips
        return nw * away + cw * SVD_OVER_QR * (2.0 * p.n * p.d * p.d)


class DistributedPCACostModel(CostModel):
    def cost(self, p, cpu_weight=None, mem_weight=None, network_weight=None):
        cw, _, nw = self._weights(cpu_weight, mem_weight, network_weight)
        # per-shard QR (a tree of leaves on one chip) + d×d R gather +
        # the small SVD of R
        return cw * (2.0 * p.n * p.d * p.d / p.num_chips
                     + SVD_OVER_QR * 2.0 * p.d**3) + nw * (
            4.0 * p.d * p.d * p.num_chips
        )


class ColumnPCAEstimator(OptimizableEstimator):
    """Cost-model choice between local and distributed PCA
    (PCA.scala:117-155)."""

    fusable_fit = True  # either route fits a traceable PCATransformer

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

"""Linear models and the exact least-squares solver.

Reference: nodes/learning/LinearMapper.scala:18-161 and
LocalLeastSquaresEstimator.scala:16-61.

The reference computes distributed normal equations with mlmatrix
(`NormalEquations`: per-partition AᵀA/Aᵀb GEMMs + treeReduce + local
solve on the driver). Here the whole thing is one jitted program over the
data-sharded X/Y: XLA turns `X.T @ X` into per-shard partial Grams plus an
all-reduce over the mesh ``data`` axis, and the (replicated) Cholesky
solve runs identically on every chip — the driver/executor split
disappears.

Intercepts are fit via the Gram-correction identity rather than
materializing centered copies: Xcᵀ Xc = XᵀX − n·x̄x̄ᵀ, which also
sidesteps the padded-zero-rows problem (raw sums are exact under
padding).
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from ...data.dataset import Dataset
from ...workflow.pipeline import LabelEstimator, Transformer


@jax.jit
def _gemm_bias(X, W, b):
    """Module-level jit: one compile per shape, shared by every linear
    model instance (rebuilding a pipeline must not recompile)."""
    return X @ W + b


class LinearMapper(Transformer):
    """y = xW (+ b). The model is replicated over the mesh; the batch path
    is a single sharded GEMM (LinearMapper.scala:18-63)."""

    chunkable = True  # per-row GEMM: distributes over host chunks
    precision_tolerance = "exact"  # solver apply: f32/HIGHEST inputs

    def __init__(self, W, b=None, feature_scaler=None):
        self.W = W
        self.b = b
        self.feature_scaler = feature_scaler

    @property
    def fusable(self) -> bool:
        """Traceable (a GEMM) unless it carries an untraceable feature
        scaler — then the chain degrades to sequential apply."""
        return self.feature_scaler is None or bool(
            getattr(self.feature_scaler, "fusable", False))

    def fuse(self):
        scaler = self.feature_scaler
        has_b = self.b is not None
        b = self.b if has_b else jnp.zeros(self.W.shape[1], self.W.dtype)
        if scaler is None:
            return (("LinearMapper", has_b), (self.W, b),
                    lambda p, X: X @ p[0] + p[1])
        if hasattr(scaler, "fuse"):
            s_key, s_params, s_fn = scaler.fuse()
        else:  # fusable (traceable apply) but no decomposition: vmap it,
            # keyed on instance identity like any opaque stage
            s_key, s_params = ("opaque", id(scaler)), ()
            s_fn = lambda p, X: jax.vmap(scaler.apply)(X)  # noqa: E731

        def fn(p, X):
            W_, b_, sp = p
            return s_fn(sp, X) @ W_ + b_

        return (("LinearMapper", has_b, s_key), (self.W, b, s_params), fn)

    def abstract_apply(self, elem):
        from ...analysis.specs import SpecMismatchError, shape_struct

        d, k = self.W.shape
        if getattr(elem, "ndim", None) == 1 and elem.shape[0] != d:
            raise SpecMismatchError(
                f"LinearMapper holds a ({d}, {k}) model but the input "
                f"element has {elem.shape[0]} features")
        return shape_struct((k,), self.W.dtype)

    def apply(self, x):
        if self.feature_scaler is not None:
            x = self.feature_scaler.apply(x)
        out = jnp.asarray(x) @ self.W
        if self.b is not None:
            out = out + self.b
        return out

    def apply_batch(self, data):
        if not isinstance(data, Dataset):
            return super().apply_batch(data)  # host chunks: per-item path
        if self.feature_scaler is not None:
            data = self.feature_scaler.apply_batch(data)
        b = self.b if self.b is not None else jnp.zeros(self.W.shape[1], self.W.dtype)
        return data.map_batches(lambda X: _gemm_bias(X, self.W, b), jitted=False)


@partial(jax.jit, static_argnames=("fit_intercept", "x_sharding"))
def _normal_equations(X, Y, count, lam, fit_intercept: bool, x_sharding=None):
    with jax.default_matmul_precision("highest"):
        return _normal_equations_impl(X, Y, count, lam, fit_intercept, x_sharding)


def _normal_equations_impl(X, Y, count, lam, fit_intercept, x_sharding=None):
    if x_sharding is not None:  # dp × tp Gram on a ('data','model') mesh
        X = jax.lax.with_sharding_constraint(X, x_sharding)
    # Raw sums are exact under zero-padding.
    A = X.T @ X
    B = X.T @ Y
    d = X.shape[1]
    if fit_intercept:
        xm = jnp.sum(X, axis=0) / count
        ym = jnp.sum(Y, axis=0) / count
        A = A - count * jnp.outer(xm, xm)
        B = B - count * jnp.outer(xm, ym)
    A = A + lam * jnp.eye(d, dtype=X.dtype)
    W = jax.scipy.linalg.solve(A, B, assume_a="pos")
    if fit_intercept:
        b = ym - xm @ W
    else:
        b = jnp.zeros(Y.shape[1], dtype=X.dtype)
    return W, b


class LinearMapEstimator(LabelEstimator):
    """Exact OLS/ridge via distributed normal equations
    (LinearMapper.scala:69-161)."""

    fusable_fit = True  # always fits a traceable LinearMapper
    precision_tolerance = "exact"  # exact normal equations

    def __init__(self, lam: float = 0.0, fit_intercept: bool = True):
        self.lam = lam
        self.fit_intercept = fit_intercept

    def abstract_fit(self, in_specs):
        from ...analysis.specs import supervised_fit_spec

        return supervised_fit_spec(in_specs, self.label)

    def fit(self, data: Dataset, labels: Dataset) -> LinearMapper:
        from ...parallel import mesh as meshlib
        from ...telemetry import dispatch, span

        with span(self.label, cat="solver", layer="solver"), \
                dispatch("_normal_equations"):
            W, b = _normal_equations(
                data.array,
                labels.array,
                jnp.float32(data.count),
                jnp.float32(self.lam),
                self.fit_intercept,
                x_sharding=meshlib.feature_sharding(
                    data.mesh, data.array.shape[1]),
            )
        return LinearMapper(W, b if self.fit_intercept else None)

    @staticmethod
    def compute_cost(data: Dataset, labels: Dataset, lam: float, W, b=None) -> float:
        """Ridge objective value (LinearMapper.scala:129-161)."""
        X, Y = data.array, labels.array
        pred = X @ W + (0.0 if b is None else b)
        resid = (pred - Y) * data.mask[:, None]
        return float(0.5 * jnp.sum(resid**2) + 0.5 * lam * jnp.sum(W**2))


class SparseLinearMapper(Transformer):
    """Apply a dense linear model to sparse inputs
    (SparseLinearMapper.scala:13-50).

    TPUs have no efficient sparse GEMM, so the product runs host-side as
    CSR @ dense (the reference likewise keeps SparseVector dot products
    on the JVM); the dense (n, k) result then moves to the device. For a
    single datum the row's nonzeros index directly into W.
    """

    def __init__(self, W, b=None):
        import numpy as np

        self.W = np.asarray(W)
        self.b = None if b is None else np.asarray(b)

    def apply(self, x):
        import numpy as np
        import scipy.sparse as sp

        if sp.issparse(x):
            row = sp.csr_matrix(x)
            if row.shape[0] == 1:
                out = self.W[row.indices].T @ row.data
            else:
                out = np.asarray(row @ self.W)
        else:
            out = np.asarray(x) @ self.W
        return out + self.b if self.b is not None else out

    def apply_batch(self, data):
        import numpy as np

        from ...data.sparse import SparseDataset

        if isinstance(data, SparseDataset):
            out = np.asarray(data.matrix @ self.W, np.float32)
            if self.b is not None:
                out = out + self.b
            return Dataset(out, mesh=data.mesh)
        # Dense input: stay on device — same sharded GEMM as LinearMapper.
        return LinearMapper(self.W, self.b).apply_batch(data)


@jax.jit
def _dual_solve(X, Y, mask, lam):
    with jax.default_matmul_precision("highest"):
        return _dual_solve_impl(X, Y, mask, lam)


def _dual_solve_impl(X, Y, mask, lam):
    # K = X Xᵀ on masked rows; solve (K + λI)α = Y; W = Xᵀα.
    Xm = X * mask[:, None]
    K = Xm @ Xm.T
    n = X.shape[0]
    # Padded rows have zero K-rows and zero targets -> alpha = 0 for them.
    alpha = jax.scipy.linalg.solve(
        K + lam * jnp.eye(n, dtype=X.dtype), Y * mask[:, None], assume_a="pos"
    )
    return Xm.T @ alpha


class LocalLeastSquaresEstimator(LabelEstimator):
    """Dual-form ridge for d ≫ n: collect to one replica, solve the n×n
    kernelized system (LocalLeastSquaresEstimator.scala:16-61)."""

    fusable_fit = True  # always fits a traceable LinearMapper

    def __init__(self, lam: float = 0.0):
        self.lam = lam

    def abstract_fit(self, in_specs):
        from ...analysis.specs import supervised_fit_spec

        return supervised_fit_spec(in_specs, self.label)

    def fit(self, data: Dataset, labels: Dataset) -> LinearMapper:
        from ...telemetry import dispatch, span

        with span(self.label, cat="solver", layer="solver"):
            mask = data.mask_as(data.array.dtype)
            with dispatch("_dual_solve"):
                W = _dual_solve(data.array, labels.array, mask,
                                jnp.float32(self.lam))
        return LinearMapper(W)

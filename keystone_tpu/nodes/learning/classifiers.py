"""Naive Bayes, logistic regression, and LDA.

The reference wraps Spark MLlib for NB and logistic regression
(NaiveBayesModel.scala:12-69, LogisticRegressionModel.scala:34-94) and
uses Breeze eig for LDA (LinearDiscriminantAnalysis.scala:17-68). Here
all three are native: NB is two masked sharded reductions; logistic
regression is jitted L-BFGS on the softmax objective (gradients
all-reduced over the mesh by GSPMD); LDA is a host generalized-eigh of
the small (d×d) scatter matrices.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import optax
import scipy.linalg

from ...data.dataset import Dataset, HostDataset
from ...data.sparse import SparseDataset
from ...workflow.pipeline import LabelEstimator, Transformer


def _as_dense(x):
    """Sparse input → dense ndarray. The single-datum serving path
    receives the 1×V CSR rows `SparseFeatureVectorizer.apply` emits;
    densifying (rather than gathering by the row's indices) keeps the
    scoring shape-stable across documents, so warm serving never
    recompiles — the single/batch duality of Operator.scala:77-100."""
    import scipy.sparse as sp

    if sp.issparse(x):
        arr = np.asarray(x.todense())
        return arr.ravel() if arr.shape[0] == 1 else arr
    return x


class NaiveBayesModel(Transformer):
    """x → log-posterior vector (NaiveBayesModel.scala:12-40)."""

    def __init__(self, log_priors, log_cond):
        self.log_priors = jnp.asarray(log_priors)  # (k,)
        self.log_cond = jnp.asarray(log_cond)  # (k, d)

    def apply(self, x):
        x = _as_dense(x)
        out = _nb_scores(
            jnp.atleast_2d(jnp.asarray(x, jnp.float32)),
            self.log_priors, self.log_cond)
        return out[0] if np.ndim(x) == 1 else out

    def apply_batch(self, data):
        if isinstance(data, SparseDataset):
            data = data.densify()
        return data.map_batches(
            lambda X: _nb_scores(X, self.log_priors, self.log_cond), jitted=False
        )


@jax.jit
def _nb_scores(X, log_priors, log_cond):
    return log_priors + X @ log_cond.T


class NaiveBayesEstimator(LabelEstimator):
    """Multinomial NB with Laplace smoothing (NaiveBayesModel.scala:42-69).
    labels: int class ids; data: nonnegative count features."""

    def __init__(self, num_classes: int, lam: float = 1.0):
        self.num_classes = num_classes
        self.lam = lam

    def fit(self, data, labels) -> NaiveBayesModel:
        if isinstance(data, SparseDataset):
            X = np.asarray(data.matrix.todense(), np.float32)
            y = np.asarray(labels.numpy() if hasattr(labels, "numpy") else labels)
            onehot = np.eye(self.num_classes, dtype=np.float32)[y.ravel()]
            class_counts = onehot.sum(axis=0)
            feat_counts = onehot.T @ X
        else:
            X, mask = data.array, data.mask_as(jnp.float32)
            y = labels.array
            onehot = jax.nn.one_hot(y, self.num_classes) * mask[:, None]
            class_counts = jnp.sum(onehot, axis=0)
            feat_counts = onehot.T @ X
        log_priors = jnp.log(
            (jnp.asarray(class_counts) + self.lam)
            / (jnp.sum(jnp.asarray(class_counts)) + self.lam * self.num_classes)
        )
        smoothed = jnp.asarray(feat_counts) + self.lam
        log_cond = jnp.log(smoothed / jnp.sum(smoothed, axis=1, keepdims=True))
        return NaiveBayesModel(log_priors, log_cond)


@partial(jax.jit, static_argnames=("num_classes", "num_iters"))
def _logreg_fit(X, y, mask, lam, num_classes: int, num_iters: int):
    with jax.default_matmul_precision("highest"):
        n, d = X.shape
        count = jnp.sum(mask)
        onehot = jax.nn.one_hot(y, num_classes) * mask[:, None]

        def loss(W):
            logits = X @ W
            logz = jax.scipy.special.logsumexp(logits, axis=1)
            ll = jnp.sum((jnp.sum(logits * onehot, axis=1) - logz) * mask)
            return -ll / count + 0.5 * lam * jnp.sum(W * W)

        opt = optax.lbfgs()
        W0 = jnp.zeros((d, num_classes), X.dtype)
        state0 = opt.init(W0)
        vg = optax.value_and_grad_from_state(loss)

        def step(carry, _):
            W, state = carry
            value, grad = vg(W, state=state)
            updates, state = opt.update(
                grad, state, W, value=value, grad=grad, value_fn=loss
            )
            return (optax.apply_updates(W, updates), state), value

        (W, _), _ = jax.lax.scan(step, (W0, state0), None, length=num_iters)
        return W


class LogisticRegressionModel(Transformer):
    def __init__(self, W):
        self.W = W

    def apply(self, x):
        return jnp.argmax(jnp.asarray(_as_dense(x)) @ self.W, axis=-1)

    def apply_batch(self, data):
        if isinstance(data, SparseDataset):
            data = data.densify()
        return data.map_batches(
            lambda X: jnp.argmax(X @ self.W, axis=-1), jitted=False
        )

    def scores(self, data: Dataset):
        if isinstance(data, SparseDataset):
            data = data.densify()
        return data.map_batches(lambda X: X @ self.W, jitted=False)


class LogisticRegressionEstimator(LabelEstimator):
    """Multinomial logistic regression via L-BFGS
    (LogisticRegressionModel.scala:34-94)."""

    def __init__(self, num_classes: int, lam: float = 0.0, num_iters: int = 50):
        self.num_classes = num_classes
        self.lam = lam
        self.num_iters = num_iters
        self.weight = num_iters

    def fit(self, data, labels) -> LogisticRegressionModel:
        if isinstance(data, SparseDataset):
            data = data.densify()
        W = _logreg_fit(
            data.array,
            labels.array if isinstance(labels, Dataset) else jnp.asarray(labels),
            data.mask_as(data.array.dtype),
            jnp.float32(self.lam),
            self.num_classes,
            self.num_iters,
        )
        return LogisticRegressionModel(W)


class LinearDiscriminantAnalysis(LabelEstimator):
    """Multiclass LDA via generalized eigendecomposition of S_W⁻¹S_B
    (LinearDiscriminantAnalysis.scala:17-68). Host solve: d is small."""

    def __init__(self, num_dims: int):
        self.num_dims = num_dims

    def fit(self, data, labels) -> Transformer:
        X = np.asarray(data.numpy(), np.float64)
        y = np.asarray(labels.numpy() if hasattr(labels, "numpy") else labels).ravel()
        classes = np.unique(y)
        mu = X.mean(axis=0)
        d = X.shape[1]
        Sw = np.zeros((d, d))
        Sb = np.zeros((d, d))
        for c in classes:
            Xc = X[y == c]
            mc = Xc.mean(axis=0)
            Sw += (Xc - mc).T @ (Xc - mc)
            Sb += len(Xc) * np.outer(mc - mu, mc - mu)
        Sw += 1e-6 * np.eye(d)
        vals, vecs = scipy.linalg.eigh(Sb, Sw)
        order = np.argsort(vals)[::-1]
        components = vecs[:, order[: self.num_dims]].astype(np.float32)
        from .pca import PCATransformer

        return PCATransformer(components)

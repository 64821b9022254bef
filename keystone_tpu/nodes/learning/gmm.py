"""Diagonal-covariance Gaussian mixture model.

Reference: nodes/learning/GaussianMixtureModel.scala:19-106 (transformer),
GaussianMixtureModelEstimator.scala:25-203 (local EM, Sanchez et al.
recipe with cluster/variance floors), and the native enceval variant
(utils/external/EncEval.scala `computeGMM`). The C++/JNI EM is replaced
by jitted EM on device — the E and M steps are two GEMMs each, which is
exactly what the MXU wants — over row blocks, started by k-means++ on
the device (`jit__gmm_init`, `jit__gmm_em`).
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from ...data.dataset import Dataset
from ...workflow.pipeline import Estimator, Transformer


def _log_joint(X, means, variances, weights):
    """log(w_k N(x | mu_k, diag var_k)) for every row and component, by
    the batched Mahalanobis GEMM trick (GaussianMixtureModel.scala:49-80)."""
    with jax.default_matmul_precision("highest"):
        inv = 1.0 / variances  # (k, d)
        # ||x-m||²_inv = x²·inv - 2x·(m·inv) + m²·inv
        quad = (
            (X * X) @ inv.T
            - 2.0 * X @ (means * inv).T
            + jnp.sum(means * means * inv, axis=1)
        )
        logdet = jnp.sum(jnp.log(variances), axis=1)
        d = X.shape[1]
        return (
            jnp.log(weights)
            - 0.5 * (quad + logdet + d * jnp.log(2.0 * jnp.pi))
        )


@jax.jit
def _log_gauss_posteriors(X, means, variances, weights):
    """log p(k|x) for diagonal Gaussians."""
    logp = _log_joint(X, means, variances, weights)
    return logp - jax.scipy.special.logsumexp(logp, axis=1, keepdims=True)


class GaussianMixtureModel(Transformer):
    """x → thresholded posterior assignment vector
    (GaussianMixtureModel.scala:19-106)."""

    def __init__(self, means, variances, weights, posterior_threshold: float = 1e-4):
        self.means = jnp.asarray(means)  # (k, d)
        self.variances = jnp.asarray(variances)  # (k, d)
        self.weights = jnp.asarray(weights)  # (k,)
        self.posterior_threshold = posterior_threshold

    @property
    def k(self) -> int:
        return self.means.shape[0]

    def posteriors(self, X):
        return jnp.exp(
            _log_gauss_posteriors(
                jnp.atleast_2d(jnp.asarray(X)), self.means, self.variances, self.weights
            )
        )

    def apply(self, x):
        x2 = jnp.atleast_2d(jnp.asarray(x))
        q = self.posteriors(x2)
        q = jnp.where(q < self.posterior_threshold, 0.0, q)
        return q[0] if jnp.ndim(x) == 1 else q

    @staticmethod
    def load_csv(means_path, variances_path, weights_path) -> "GaussianMixtureModel":
        """Sideband CSV loading (GaussianMixtureModel.scala:97-105).

        Reference on-disk layout is dims × clusters ("# of Dims by # of
        Cluster", GaussianMixtureModel.scala:19); this class stores
        (k, d), so means/variances transpose on load."""
        return GaussianMixtureModel(
            np.loadtxt(means_path, delimiter=",", ndmin=2).T,
            np.loadtxt(variances_path, delimiter=",", ndmin=2).T,
            # k=1 yields a 0-d array from loadtxt; posteriors need (k,)
            np.atleast_1d(np.loadtxt(weights_path, delimiter=",")),
        )


#: rows an E-step holds posteriors for at a time: 65,536 x 256 is 67 MB
#: where all of a million samples' would be a gigabyte, twice over
EM_BLOCK_ROWS = 65536
#: width of the two-level draw in the k-means++ initialization
_DRAW_ROW = 1024


def _live(n: int, valid):
    return jnp.arange(n) < valid


def _moments(X, valid):
    """Mean and variance by column of the ``valid`` leading rows."""
    live = _live(X.shape[0], valid)[:, None]
    mean = jnp.sum(jnp.where(live, X, 0.0), axis=0) / valid
    var = jnp.sum(jnp.where(live, (X - mean) ** 2, 0.0), axis=0) / valid
    return mean, var


def _draw(weights, u):
    """An index drawn with probability in proportion to ``weights``
    (non-negative, a multiple of `_DRAW_ROW` long) from one uniform
    ``u``: a row of `_DRAW_ROW` by the rows' cumulated totals, then an
    entry within the row, so no running sum is longer than a row."""
    rows = weights.reshape(-1, _DRAW_ROW)
    totals = jnp.sum(rows, axis=1)
    edges = jnp.cumsum(totals)
    target = u * edges[-1]
    r = jnp.minimum(jnp.searchsorted(edges, target, side="right"),
                    rows.shape[0] - 1)
    within = target - (edges[r] - totals[r])
    c = jnp.minimum(
        jnp.searchsorted(jnp.cumsum(rows[r]), within, side="right"),
        _DRAW_ROW - 1)
    return r * _DRAW_ROW + c


@partial(jax.jit, static_argnames=("k", "init"))
def _gmm_init(X, valid, key, k: int, init: str = "kmeans++"):
    """The centres an EM fit starts from and the data's variance by
    column, on the device, one program (`jit__gmm_init`). k-means++
    seeding (KMeansPlusPlus.scala:16-80, as
    `GaussianMixtureModelEstimator.scala` starts from): the first centre
    uniformly among the ``valid`` rows, each later one with probability
    in proportion to the squared distance to the nearest centre so far:
    k passes over the rows, none on the host. ``init`` "random": k rows
    drawn uniformly."""
    with jax.named_scope("ks.gmm.init"):
        n, d = X.shape
        live = _live(n, valid)
        _, var = _moments(X, valid)
        if init != "kmeans++":
            return X[jax.random.randint(key, (k,), 0, valid)], var
        pad = -n % _DRAW_ROW
        keys = jax.random.split(key, k)

        def distance(c):
            return jnp.where(live, jnp.sum((X - c) ** 2, axis=1), 0.0)

        first = X[jax.random.randint(keys[0], (), 0, valid)]
        centers = jnp.zeros((k, d), X.dtype).at[0].set(first)

        def body(i, carry):
            centers, d2 = carry
            idx = _draw(jnp.pad(d2, (0, pad)), jax.random.uniform(keys[i]))
            c = X[jnp.minimum(idx, valid - 1)]
            return centers.at[i].set(c), jnp.minimum(d2, distance(c))

        centers, _ = jax.lax.fori_loop(1, k, body, (centers, distance(first)))
        return centers, var


def gmm_start(X, valid, k: int, seed: int, init: str = "kmeans++"):
    """The mixture an EM fit starts from, on the device: (means,
    variances, weights, the data's variance by column). Centres by
    k-means++ or a seeded draw of rows, every variance the data's own,
    equal weights."""
    means, var = _gmm_init(X, valid, jax.random.PRNGKey(seed), k, init)
    global_var = var + 1e-6
    return (means, jnp.tile(global_var, (k, 1)),
            jnp.full((k,), 1.0 / k, X.dtype), global_var)


@partial(jax.jit, static_argnames=("num_iters",))
def _gmm_em(X, valid, means0, variances0, weights0, min_variance,
            num_iters: int):
    """``num_iters`` EM iterations over the ``valid`` leading rows of X,
    one program (`jit__gmm_em`). An E-step goes through the rows in
    blocks of `EM_BLOCK_ROWS` and keeps only the sums the M-step needs
    (sum q, q'X, q'X^2), so no more than a block's posteriors are ever
    held. Also returns the mean log-likelihood of the rows under the
    mixture each iteration started from."""
    with jax.named_scope("ks.gmm.em"), \
            jax.default_matmul_precision("highest"):
        n, d = X.shape
        k = means0.shape[0]
        block = min(EM_BLOCK_ROWS, n)
        blocks = -(-n // block)
        Xb = jnp.pad(X, [(0, blocks * block - n), (0, 0)]).reshape(
            blocks, block, d)
        starts = jnp.arange(blocks) * block

        def e_step(params, xs):
            means, variances, weights = params
            x, start = xs
            live = (start + jnp.arange(block) < valid)[:, None]
            logp = _log_joint(x, means, variances, weights)
            lse = jax.scipy.special.logsumexp(logp, axis=1, keepdims=True)
            q = jnp.where(live, jnp.exp(logp - lse), 0.0)  # (block, k)
            return (jnp.sum(q, axis=0), q.T @ x, q.T @ (x * x),
                    jnp.sum(jnp.where(live, lse, 0.0)))

        def step(params, _):
            def add(acc, xs):
                return jax.tree_util.tree_map(
                    jnp.add, acc, e_step(params, xs)), None

            zero = (jnp.zeros((k,), X.dtype), jnp.zeros((k, d), X.dtype),
                    jnp.zeros((k, d), X.dtype), jnp.zeros((), X.dtype))
            (nk, s1, s2, ll), _ = jax.lax.scan(add, zero, (Xb, starts))
            safe_nk = jnp.maximum(nk, 1e-8)
            new_means = s1 / safe_nk[:, None]
            new_vars = jnp.maximum(
                s2 / safe_nk[:, None] - new_means**2, min_variance)
            new_weights = jnp.maximum(nk / valid, 1e-10)
            new_weights = new_weights / jnp.sum(new_weights)
            return (new_means, new_vars, new_weights), ll / valid

        (means, variances, weights), ll = jax.lax.scan(
            step, (means0, variances0, weights0), None, length=num_iters)
        return means, variances, weights, ll


class GaussianMixtureModelEstimator(Estimator):
    """Local EM with k-means++ (or random) init and variance floors
    (GaussianMixtureModelEstimator.scala:25-203), on every row it is
    given: a device dataset's rows are used where they are, the
    initialization and a fixed number of EM iterations are a program
    each, and nothing is cut or pulled to the host."""

    precision_tolerance = "exact"  # moments/decomposition: f32 inputs

    def __init__(
        self,
        k: int,
        num_iters: int = 30,
        init: str = "kmeans++",
        min_variance_factor: float = 0.01,
        seed: int = 0,
    ):
        self.k = k
        self.num_iters = num_iters
        if init not in ("kmeans++", "random"):
            raise ValueError("init must be 'kmeans++' or 'random'")
        self.init = init
        self.min_variance_factor = min_variance_factor
        self.seed = seed

    def fit(self, data) -> GaussianMixtureModel:
        from ...telemetry import counter, dispatch, span
        from .pca import _collect_rows, _device_rows

        with span("gmm_fit", cat="solver", layer="solver", k=self.k,
                  iters=self.num_iters):
            if isinstance(data, Dataset):
                X, valid = _device_rows(data)
            else:
                X = jnp.asarray(_collect_rows(data))
                valid = X.shape[0]
            with dispatch("_gmm_init"):
                means0, variances0, weights0, global_var = gmm_start(
                    X, valid, self.k, self.seed, self.init)
            # variance floor relative to the global variance (Sanchez et al.)
            with dispatch("_gmm_em"):
                means, variances, weights, ll = _gmm_em(
                    X, valid, means0, variances0, weights0,
                    self.min_variance_factor * global_var, self.num_iters)
            counter("gmm.em_iterations").inc(self.num_iters)
        model = GaussianMixtureModel(means, variances, weights)
        #: mean log-likelihood of the rows before each iteration
        model.log_likelihood_trace = ll
        return model

"""The plain reference of VOCSIFTFisher, in straightforward `jax.numpy`
in float32 at `highest` matmul precision, independent of the code under
test.

Dense SIFT is written from the VLFeat description (the docstring of
`keystone_tpu/nodes/images/sift.py`): per scale the image smoothed by an
explicit Gaussian of sigma binSize/6 and support ceil(4 sigma) under
edge replication, gradients by central differences (one-sided at the
borders), the magnitude shared linearly between the two nearest of
eight orientation bins, spatial binning by a triangular kernel of unit
integral and half-width binSize under edge replication, descriptors
sampled at the bin centres frame + bin * binSize (frames `step` apart
from the scale's offset, column-outer and row-inner) with the flat
window's Gaussian reweighting, then L2 normalization, the clamp at 0.2,
renormalization, the contrast threshold and the short quantization.
Smoothing and binning are matrix products with banded matrices that
hold the kernel and the edge replication (`_edge_matrix`), the sampling
an index by integer arrays: none of the program's shifted sums or
strided slices.

PCA is the top eigenvectors of the samples' covariance, each flipped so
that its largest coordinate is positive (the repo's sign convention). EM
is the textbook iteration (responsibilities, then weights, means and
variances from them, the variance floored at a hundredth of the data's)
over row blocks. The Fisher vector is the closed form of Sanchez et al.:
the gradients with respect to means and standard deviations from the
deviations (x - mu_k) / sigma_k, written out as (descriptors, k, d)
arrays a block of descriptors at a time. Then the three normalizations
and `plain.block_least_squares`.

The reference is handed the program's random choices: the rows each
sampler keeps (`sample_rows`) and the centres EM starts from
(`gmm_start` on the reference's own samples), both seeded. For the
comparison of scores it is also handed the PCA basis and the mixture the
timed fit learned (as `reference/random_patch_cifar.py` is handed the
learned filters and whitener), so that EM's sensitivity to rounding does
not set the limit; its own PCA and its own EM are compared with the
program's separately (`benchmark/modes/fit_multilabel.py`). Images go
through in blocks of `IMAGES` so that it fits.

One departure from float32 at `highest` throughout, and why: the
configuration states the fitted model's scoring product (features @ W)
at the backend's default matmul precision, as `BlockLinearMapper`'s
fused apply runs it (`default_matmul_operands` in its file), and the
reference rounds the same two operands the same way, as
`reference/timit_cosine.py` does, so that what is left to differ is the
featurizer and the solver."""

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from . import plain

ORIENTATIONS = 8
GRID = 4
EPSILON = 1.19209290e-07
CONTRAST = 0.005
WINDOW = 1.5
MAGNIF = 6.0
IMAGES = 8  # a block: 8 x 73,866 x 128 floats of descriptors is 302 MB
DESCRIPTORS = 512  # of one image at a time in the Fisher vector's sums
EM_ROWS = 32768


# ---------------------------------------------------------------- SIFT

def _edge_matrix(n, taps):
    """(n, n): row i holds ``taps`` centred on i, the taps that fall off
    either end added to the end's column (edge replication)."""
    r = (len(taps) - 1) // 2
    M = np.zeros((n, n), np.float64)
    for i in range(n):
        for k, t in enumerate(taps):
            M[i, min(max(i + k - r, 0), n - 1)] += t
    return M.astype(np.float32)


def _gaussian(sigma):
    r = max(int(np.ceil(4.0 * sigma)), 1)
    k = np.exp(-0.5 * (np.arange(-r, r + 1) / sigma) ** 2)
    return k / k.sum()


def _triangle(bs):
    return (bs - np.abs(np.arange(-(bs - 1), bs))) / float(bs * bs)


def _window_mean(bs, index):
    delta = bs * (index - (GRID - 1) / 2.0)
    xs = np.arange(-bs + 1, bs)
    return float(np.mean(np.exp(-0.5 * ((xs + delta) / (bs * WINDOW)) ** 2))) * bs


def _smooth(x, taps):
    """Rows then columns of the two last axes, by banded matrices."""
    h, w = x.shape[-2:]
    return jnp.einsum("ij,...jk,lk->...il", _edge_matrix(h, taps), x,
                      _edge_matrix(w, taps))


def _difference(x, axis):
    x = jnp.moveaxis(x, axis, 0)
    d = jnp.concatenate([(x[1] - x[0])[None], (x[2:] - x[:-2]) / 2.0,
                         (x[-1] - x[-2])[None]], axis=0)
    return jnp.moveaxis(d, 0, axis)


def _scale(gray, bs, step, off):
    b, h, w = gray.shape
    sm = _smooth(gray, _gaussian(bs / MAGNIF))
    dy, dx = _difference(sm, 1), _difference(sm, 2)
    mag = jnp.sqrt(dx * dx + dy * dy)
    t = jnp.mod(jnp.arctan2(dy, dx) / (2.0 * jnp.pi) * ORIENTATIONS,
                ORIENTATIONS)
    lo = jnp.floor(t)
    frac = t - lo
    lo = lo.astype(jnp.int32) % ORIENTATIONS
    hi = (lo + 1) % ORIENTATIONS
    o = jnp.arange(ORIENTATIONS)[None, :, None, None]
    maps = ((lo[:, None] == o) * (mag * (1.0 - frac))[:, None]
            + (hi[:, None] == o) * (mag * frac)[:, None])  # (b, 8, h, w)
    agg = _smooth(maps, _triangle(bs))
    span = bs * (GRID - 1) + 1
    n_r = max(((h - 1) - span + 1 - off) // step + 1, 0)
    n_c = max(((w - 1) - span + 1 - off) // step + 1, 0)
    rr = (off + step * np.arange(n_r))[:, None] + bs * np.arange(GRID)
    cc = (off + step * np.arange(n_c))[:, None] + bs * np.arange(GRID)
    wm = np.asarray([_window_mean(bs, i) for i in range(GRID)], np.float32)
    d = agg[:, :, rr][:, :, :, :, cc]  # (b, 8, n_r, 4, n_c, 4)
    d = d * wm[None, None, None, :, None, None] * wm
    d = d.transpose(0, 4, 2, 3, 5, 1).reshape(
        b, n_c * n_r, GRID * GRID * ORIENTATIONS)
    norm = jnp.sqrt(jnp.sum(d * d, axis=-1, keepdims=True)) + EPSILON
    d = jnp.minimum(d / norm, 0.2)
    d = d / (jnp.sqrt(jnp.sum(d * d, axis=-1, keepdims=True)) + EPSILON)
    d = jnp.where(norm < CONTRAST, 0.0, d)
    return jnp.minimum(jnp.floor(512.0 * d), 255.0)


def sift(gray, sizes):
    """(b, h, w) grayscale in [0, 1] -> (b, descriptors, 128)."""
    S = sizes["num_scales"]
    return jnp.concatenate(
        [_scale(gray, sizes["sift_bin"] + 2 * s,
                sizes["sift_step"] + s * sizes["scale_step"],
                max((1 + 2 * S) - 3 * s, 0)) for s in range(S)], axis=1)


# ----------------------------------------------------------------- PCA

def pca_basis(X, dims):
    """(d, dims): the top eigenvectors of the covariance of X's rows."""
    Xc = X - X.mean(axis=0)
    cov = (Xc.T @ Xc) / (X.shape[0] - 1)
    _, vectors = jnp.linalg.eigh(cov)
    V = vectors[:, ::-1][:, :dims]
    top = jnp.argmax(jnp.abs(V), axis=0)
    return V * jnp.sign(V[top, jnp.arange(dims)])


def largest_principal_angle(A, B):
    """Radians between the column spaces of A and B (float64, host)."""
    qa, _ = np.linalg.qr(np.asarray(A, np.float64))
    qb, _ = np.linalg.qr(np.asarray(B, np.float64))
    s = np.linalg.svd(qa.T @ qb, compute_uv=False)
    return float(np.arccos(np.clip(s.min(), -1.0, 1.0)))


# ------------------------------------------------------------------ EM

def _log_joint(x, means, variances, weights):
    inv = 1.0 / variances
    quad = ((x * x) @ inv.T - 2.0 * x @ (means * inv).T
            + jnp.sum(means * means * inv, axis=1))
    return (jnp.log(weights) - 0.5 * (
        quad + jnp.sum(jnp.log(2.0 * jnp.pi * variances), axis=1)))


def _row_blocks(X):
    n, d = X.shape
    rows = min(EM_ROWS, n)
    blocks = -(-n // rows)
    Xb = jnp.pad(X, [(0, blocks * rows - n), (0, 0)]).reshape(blocks, rows, d)
    live = (jnp.arange(blocks * rows) < n).reshape(blocks, rows, 1)
    return Xb, live


@partial(jax.jit, static_argnames=("iters",))
def em(X, means, variances, weights, floor, *, iters):
    """``iters`` textbook EM iterations of a diagonal mixture on X's
    rows from the given start; the variances floored at ``floor``."""
    Xb, live = _row_blocks(X)
    n = X.shape[0]

    def iteration(params, _):
        def block(acc, xl):
            x, l = xl
            logp = _log_joint(x, *params)
            r = jnp.where(l, jax.nn.softmax(logp, axis=1), 0.0)
            return (acc[0] + r.sum(axis=0), acc[1] + r.T @ x,
                    acc[2] + r.T @ (x * x)), None

        k, d = params[0].shape
        (nk, s1, s2), _ = jax.lax.scan(
            block, (jnp.zeros((k,)), jnp.zeros((k, d)), jnp.zeros((k, d))),
            (Xb, live))
        nk = jnp.maximum(nk, 1e-8)
        mu = s1 / nk[:, None]
        var = jnp.maximum(s2 / nk[:, None] - mu * mu, floor)
        w = jnp.maximum(nk / n, 1e-10)
        return (mu, var, w / w.sum()), None

    params, _ = jax.lax.scan(iteration, (means, variances, weights), None,
                             length=iters)
    return params


@jax.jit
def mean_log_likelihood(X, means, variances, weights):
    Xb, live = _row_blocks(X)

    def block(total, xl):
        x, l = xl
        lse = jax.scipy.special.logsumexp(
            _log_joint(x, means, variances, weights), axis=1, keepdims=True)
        return total + jnp.sum(jnp.where(l, lse, 0.0)), None

    total, _ = jax.lax.scan(block, jnp.zeros(()), (Xb, live))
    return total / X.shape[0]


# ------------------------------------------------------- Fisher vector

def fisher_vector(x, means, variances, weights):
    """(nd, d) descriptors -> the (d * 2k,) Fisher vector, laid out as
    the program's (d, 2k) matrix flattened: for every dimension the k
    mean gradients, then the k deviation gradients."""
    nd, d = x.shape
    k = means.shape[0]
    sigma = jnp.sqrt(variances)
    blocks = -(-nd // DESCRIPTORS)
    xb = jnp.pad(x, [(0, blocks * DESCRIPTORS - nd), (0, 0)]).reshape(
        blocks, DESCRIPTORS, d)
    live = (jnp.arange(blocks * DESCRIPTORS) < nd).reshape(
        blocks, DESCRIPTORS, 1)

    def block(acc, xl):
        xs, l = xl
        u = (xs[:, None, :] - means) / sigma  # (descriptors, k, d)
        logp = jnp.log(weights) - 0.5 * jnp.sum(
            u * u + jnp.log(2.0 * jnp.pi * variances), axis=2)
        q = jnp.where(l, jax.nn.softmax(logp, axis=1), 0.0)[:, :, None]
        return (acc[0] + jnp.sum(q * u, axis=0),
                acc[1] + jnp.sum(q * (u * u - 1.0), axis=0)), None

    (g_mu, g_sigma), _ = jax.lax.scan(
        block, (jnp.zeros((k, d)), jnp.zeros((k, d))), (xb, live))
    g_mu = g_mu / (nd * jnp.sqrt(weights)[:, None])
    g_sigma = g_sigma / (nd * jnp.sqrt(2.0 * weights)[:, None])
    return jnp.concatenate([g_mu, g_sigma], axis=0).T.reshape(-1)


def _normalize(v):
    return v / jnp.maximum(jnp.sqrt(jnp.sum(v * v, axis=-1, keepdims=True)),
                           2.2e-16)


# ----------------------------------------------------------- featurizer

def _gray(images):
    return (images.astype(jnp.float32) / 255.0) @ jnp.asarray(
        [0.299, 0.587, 0.114], jnp.float32)


@partial(jax.jit, static_argnames=("shape",))
def _descriptor_samples(images, rows, *, shape):
    return sift(_gray(images), dict(shape))[:, rows]


@partial(jax.jit, static_argnames=("shape",))
def _block_features(images, basis, means, variances, weights, rows, raw_rows,
                    *, shape):
    """A block of images -> (features, the reduced descriptors at
    ``rows``, the descriptors themselves at ``raw_rows``)."""
    descriptors = sift(_gray(images), dict(shape))
    reduced = descriptors @ basis
    fv = jax.lax.map(
        lambda x: fisher_vector(x, means, variances, weights), reduced)
    fv = _normalize(fv)
    fv = _normalize(jnp.sign(fv) * jnp.sqrt(jnp.abs(fv)))
    return fv, reduced[:, rows], descriptors[:, raw_rows]


def _in_blocks(fn, images):
    """``fn`` over blocks of `IMAGES` images, the last block taken from
    the end; every output of ``fn`` put together along the images."""
    n = images.shape[0]
    size = min(IMAGES, n)
    parts = []
    for start in range(0, n, size):
        begin = min(start, n - size)
        out = fn(images[begin:begin + size])
        out = out if isinstance(out, tuple) else (out,)
        parts.append(tuple(o[start - begin:] for o in out))
    return tuple(jnp.concatenate(p, axis=0) for p in zip(*parts))


def _shape(sizes):
    return tuple((k, sizes[k]) for k in
                 ("num_scales", "sift_bin", "sift_step", "scale_step"))


def _count(images):
    return images.count if hasattr(images, "count") else images.shape[0]


def sampler_rows(sizes, seed, which, n, descriptors):
    """The rows the program's two samplers keep: `sample_rows` with the
    seeds `build_featurizer` gives them."""
    from keystone_tpu.nodes.stats.normalization import sample_rows

    from .. import datagen

    num = max(1, sizes[f"num_{which}_samples"] // n)
    if num >= descriptors:
        return np.arange(descriptors, dtype=np.int32)
    return sample_rows(descriptors, num,
                       datagen.program_seed(seed) + (which == "gmm"))


def own_pca(train_images, sizes, seed):
    """The reference's own PCA basis, from its own descriptors at the
    PCA sampler's rows of every training image."""
    with jax.default_matmul_precision("highest"):
        n = train_images.shape[0]
        nd = jax.eval_shape(
            lambda x: sift(_gray(x), sizes), train_images[:1]).shape[1]
        rows = sampler_rows(sizes, seed, "pca", n, nd)
        (samples,) = _in_blocks(
            lambda x: _descriptor_samples(x, rows, shape=_shape(sizes)),
            train_images)
        return pca_basis(samples.reshape(-1, samples.shape[-1]),
                         sizes["pca_dims"])


def own_mixture(samples, sizes, seed):
    """The reference's own EM on ``samples`` (rows, d) from the start the
    program's seeded initialization gives on them."""
    from keystone_tpu.nodes.learning.gmm import gmm_start

    from .. import datagen

    with jax.default_matmul_precision("highest"):
        means, variances, weights, spread = gmm_start(
            samples, samples.shape[0], sizes["gmm_k"],
            datagen.program_seed(seed))
        return em(samples, means, variances, weights, 0.01 * spread,
                  iters=sizes["gmm_iters"])


def features(images, basis, mixture, sizes, seed, n_train):
    """(features (n, 2 * pca_dims * k), the reduced descriptors at the
    mixture sampler's rows (n, rows, pca_dims), the descriptors at the
    PCA sampler's rows (n, rows, 128)) of ``images`` under the given PCA
    basis and mixture, in one pass."""
    with jax.default_matmul_precision("highest"):
        nd = jax.eval_shape(
            lambda x: sift(_gray(x), sizes), images[:1]).shape[1]
        rows = sampler_rows(sizes, seed, "gmm", n_train, nd)
        raw_rows = sampler_rows(sizes, seed, "pca", n_train, nd)
        return _in_blocks(
            lambda x: _block_features(x, basis, *mixture, rows, raw_rows,
                                      shape=_shape(sizes)), images)


def fit_and_score(train, test, sizes, seed, basis=None, mixture=None):
    """The reference end to end. With ``basis`` and ``mixture`` None it
    fits its own PCA and its own mixture (from its own start); handed
    the program's, it computes everything else. Returns a dict: `scores`
    (numpy, test rows by classes), `gmm_samples` (device, the training
    set's reduced descriptors at the mixture sampler's rows, flattened)
    `pca_samples` (device, its descriptors at the PCA sampler's rows,
    flattened: what `pca_basis` makes the reference's own basis from) and
    the `basis` and `mixture` used."""
    n = _count(train.data)
    train_images = train.data.array[:n]
    test_images = test.data.array[:_count(test.data)]
    with jax.default_matmul_precision("highest"):
        if basis is None:
            basis = own_pca(train_images, sizes, seed)
        if mixture is None:
            # two passes: the samples under the basis, then the features
            nd = jax.eval_shape(
                lambda x: sift(_gray(x), sizes), train_images[:1]).shape[1]
            rows = sampler_rows(sizes, seed, "gmm", n, nd)
            (samples,) = _in_blocks(
                lambda x: _descriptor_samples(
                    x, rows, shape=_shape(sizes)) @ basis, train_images)
            mixture = own_mixture(
                samples.reshape(-1, samples.shape[-1]), sizes, seed)
        X, samples, raw = features(
            train_images, basis, mixture, sizes, seed, n)
        Y = train.labels.array[:n].astype(jnp.float32)
        W, b = plain.block_least_squares(
            X, Y, sizes["solver_block"], sizes["bcd_iters"], sizes["lam"])
        del X
        T, _, _ = features(test_images, basis, mixture, sizes, seed, n)
        # the fitted model's scoring product runs at the backend's default
        # matmul precision (`default_matmul_operands` in the sizes: on a
        # TPU both operands rounded to bfloat16, products and sums float32)
        operands = jnp.dtype(sizes.get("default_matmul_operands", "float32"))
        scores = np.asarray(jnp.matmul(
            T.astype(operands), W.astype(operands),
            preferred_element_type=jnp.float32) + b)
    return {"scores": scores, "basis": basis, "mixture": mixture,
            "gmm_samples": samples.reshape(-1, samples.shape[-1]),
            "pca_samples": raw.reshape(-1, raw.shape[-1])}


def predict(train, test, sizes, seed):
    """The top-scoring class of every test image (numpy int array), by
    the reference fitted on its own (its own PCA, its own mixture)."""
    return np.argmax(fit_and_score(train, test, sizes, seed)["scores"],
                     axis=-1)

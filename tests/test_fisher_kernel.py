"""The Fisher encoding's posteriors and moments in one pass: the Pallas
kernel (`ops.fisher_moments_pallas`, in interpret mode on the CPU)
against the jnp form (`fisher_vector._fisher_moments_reference`), which
of the two `_fisher_batch` takes, and what `fisher.rows_one_pass`
counts.

Both compute every product in float32 at `highest` and the softmax in
float32 with the max subtracted; the kernel sums the moments tile by
tile, so the two agree to float32 rounding and no closer."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from keystone_tpu import telemetry
from keystone_tpu.nodes.images import fisher_vector
from keystone_tpu.nodes.images.fisher_vector import FisherVector
from keystone_tpu.nodes.learning.gmm import GaussianMixtureModel
from keystone_tpu.ops import pallas_kernels as pk

D = 80  # voc_fit's PCA width


def _mixture(rng, k, d=D):
    """A diagonal GMM whose centres lie among the descriptors: means of
    the scale of the data, variances from a tenth to a half of it,
    weights uneven."""
    means = (rng.normal(size=(k, d)) * 0.5).astype(np.float32)
    variances = rng.uniform(0.1, 0.5, size=(k, d)).astype(np.float32)
    weights = rng.dirichlet(np.full(k, 2.0)).astype(np.float32)
    return means, variances, weights


def _descriptors(rng, b, nd, means):
    """Descriptors near the centres, so that the posteriors are sharp
    for some rows and spread for others."""
    centre = rng.integers(0, means.shape[0], size=(b, nd))
    noise = rng.normal(size=(b, nd, means.shape[1])) * 0.6
    return (means[centre] + noise).astype(np.float32)


def _kernel(X, mixture, tile):
    return [np.asarray(m) for m in pk.fisher_moments_pallas(
        jnp.asarray(X), *map(jnp.asarray, mixture), tile=tile,
        interpret=True)]


def _reference(X, mixture):
    with jax.default_matmul_precision("highest"):
        return [np.asarray(m) for m in jax.jit(
            fisher_vector._fisher_moments_reference)(X, *mixture)]


def _exact(X, mixture):
    """S0, S1 and S2 in float64 numpy, from the definition."""
    means, variances, weights = (a.astype(np.float64) for a in mixture)
    X = X.astype(np.float64)
    logp = np.log(weights) - 0.5 * (
        ((X[:, :, None, :] - means) ** 2 / variances).sum(-1)
        + np.log(2 * np.pi * variances).sum(-1))
    q = np.exp(logp - logp.max(-1, keepdims=True))
    q /= q.sum(-1, keepdims=True)
    return (q.sum(1), np.einsum("bnk,bnd->bdk", q, X),
            np.einsum("bnk,bnd->bdk", q, X * X))


def _assert_close(got, want, rtol=1e-5):
    assert got.shape == want.shape
    assert np.isfinite(got).all()
    assert np.abs(got - want).max() <= rtol * np.abs(want).max()


@pytest.mark.parametrize("b,nd,k,tile", [
    (1, 512, 256, 256),    # whole tiles
    (2, 700, 256, 256),    # a masked last tile of 188 rows
    (8, 300, 256, 256),    # under two tiles, eight images
    (1, 384, 128, 128),    # k of one lane width, whole tiles
    (8, 1000, 128, 256),   # k = 128, a masked last tile of 232 rows
    (2, 200, 128, 256),    # under one tile: padded to one
], ids=lambda v: str(v))
def test_the_kernel_s_moments_are_the_reference_s(b, nd, k, tile):
    rng = np.random.default_rng(b * 10_000 + nd + k)
    mixture = _mixture(rng, k)
    X = _descriptors(rng, b, nd, mixture[0])
    S0, S1, S2 = _kernel(X, mixture, tile)
    assert S0.shape == (b, k) and S1.shape == S2.shape == (b, D, k)
    for got, ref, exact in zip((S0, S1, S2), _reference(X, mixture),
                               _exact(X, mixture)):
        _assert_close(got, ref)
        # float32 rounding of the log-densities and the sums, no more:
        # bf16 products anywhere would be off by 1e-3
        _assert_close(got, exact, rtol=5e-6)
    # every descriptor's posteriors sum to one: rows past nd add nothing
    np.testing.assert_allclose(S0.sum(axis=1), nd, rtol=1e-5)


def test_a_descriptor_far_from_every_centre_takes_its_nearest_component():
    """A row whose log-densities are all below -1e4: without the max
    subtracted each exp is 0 and its posteriors 0/0. With it the row
    goes whole to the component nearest it in the Mahalanobis sense."""
    rng = np.random.default_rng(7)
    mixture = _mixture(rng, 256)
    X = _descriptors(rng, 1, 600, mixture[0])
    X[0, 417] = 60.0
    means, variances, weights = mixture
    logp = (np.log(weights)
            - 0.5 * (((60.0 - means.astype(np.float64)) ** 2 / variances)
                     + np.log(2 * np.pi * variances)).sum(axis=1))
    assert logp.max() < -1e4
    S0, S1, S2 = _kernel(X, mixture, 256)
    want = _reference(X, mixture)
    for got, ref in zip((S0, S1, S2), want):
        _assert_close(got, ref)
    rest = _kernel(np.delete(X, 417, axis=1), mixture, 256)[0]
    nearest = np.zeros(256)
    nearest[np.argmax(logp)] = 1.0
    np.testing.assert_allclose(S0[0] - rest[0], nearest, atol=1e-3)


def test_the_gate_takes_the_kernel_on_a_tpu_from_one_tile_at_whole_lanes(
        monkeypatch):
    assert jax.default_backend() != "tpu"
    assert not pk.use_fisher_kernel(73866, D, 256)
    monkeypatch.setattr(pk.jax, "default_backend", lambda: "tpu")
    assert pk.use_fisher_kernel(73866, D, 256)  # a VOC image's full pass
    assert pk.use_fisher_kernel(pk.FISHER_TILE, D, 128)
    assert not pk.use_fisher_kernel(pk.FISHER_TILE - 1, D, 256)
    assert not pk.use_fisher_kernel(73866, 64, 8)  # the ImageNet pipeline's
    assert not pk.use_fisher_kernel(73866, D, 200)
    # wider mixtures take smaller tiles, and a width past what a step's
    # VMEM holds takes the jnp form
    assert pk.fisher_tile(D, 256) == pk.FISHER_TILE
    assert pk.fisher_tile(128, 1024) == 768
    assert pk.use_fisher_kernel(768, 128, 1024)
    assert not pk.use_fisher_kernel(767, 128, 1024)
    assert not pk.use_fisher_kernel(73866, 129, 256)
    assert not pk.use_fisher_kernel(73866, D, 1152)
    from keystone_tpu.workflow.env import config_override

    with config_override(pallas_kernels=False):
        assert not pk.use_fisher_kernel(73866, D, 256)


def _gmm(mixture):
    return GaussianMixtureModel(*mixture)


def _kernel_on(monkeypatch, tile=128):
    monkeypatch.setattr(fisher_vector, "use_fisher_kernel",
                        lambda nd, d, k: nd >= tile and k % 128 == 0)
    monkeypatch.setattr(fisher_vector, "fisher_moments_pallas",
                        functools.partial(pk.fisher_moments_pallas,
                                          tile=tile, interpret=True))


def _lowers_to_the_kernel(X, mixture) -> bool:
    # a fresh function each time: a jaxpr is cached by the function
    jaxpr = jax.make_jaxpr(
        lambda *a: fisher_vector._fisher_batch(*a))(X, *mixture)
    return "pallas_call" in str(jaxpr)


def test_the_dispatcher_picks_the_reference_off_the_tpu(monkeypatch):
    rng = np.random.default_rng(3)
    mixture = _mixture(rng, 128)
    X = jnp.asarray(_descriptors(rng, 2, 333, mixture[0]))
    assert not _lowers_to_the_kernel(X, mixture)
    want = np.asarray(jax.jit(fisher_vector._fisher_batch)(X, *mixture))
    assert want.shape == (2, D, 256)
    _kernel_on(monkeypatch)
    assert _lowers_to_the_kernel(X, mixture)
    got = np.asarray(fisher_vector._fisher_batch(X, *mixture))
    _assert_close(got, want, rtol=1e-5)
    # the host path of one matrix goes through the same gate (a shape
    # no other test traces it at)
    assert "pallas_call" in str(jax.make_jaxpr(fisher_vector._fisher_vector)(
        X[1], *mixture))
    one = np.asarray(FisherVector(_gmm(mixture)).apply(np.asarray(X[1])))
    _assert_close(one, want[1], rtol=1e-5)


def test_rows_one_pass_counts_the_kernel_s_rows(monkeypatch):
    """0 off the TPU; images x descriptors where the kernel encodes
    them, beside `fisher.images`; 0 again where k is not whole lanes."""
    from keystone_tpu.data.dataset import Dataset
    from keystone_tpu.nodes.util.fusion import FusedBatchTransformer

    rng = np.random.default_rng(4)
    mixture = _mixture(rng, 128)
    X = _descriptors(rng, 3, 300, mixture[0])
    images = telemetry.counter("fisher.images")
    one_pass = telemetry.counter("fisher.rows_one_pass")

    def delta(mix, xs):
        before = images.value, one_pass.value
        out = FusedBatchTransformer([FisherVector(_gmm(mix))]).apply_batch(
            Dataset(xs)).numpy()
        return images.value - before[0], one_pass.value - before[1], out

    assert FisherVector(_gmm(mixture)).rows_one_pass(300) == 0
    counted, rows, want = delta(mixture, X)
    assert (counted, rows) == (3, 0)
    _kernel_on(monkeypatch)
    assert FisherVector(_gmm(mixture)).rows_one_pass(300) == 300
    counted, rows, got = delta(mixture, X)
    assert (counted, rows) == (3, 3 * 300)
    _assert_close(got, want, rtol=1e-5)
    small = _mixture(rng, 8)
    assert delta(small, _descriptors(rng, 2, 300, small[0]))[:2] == (2, 0)

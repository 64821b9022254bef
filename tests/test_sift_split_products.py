"""SIFT's products whose constant matrix is exact in bf16 (the integer
binning bands, the ones of the row sums) in three bf16 passes where
`highest` runs six: the float32 operand in three pieces against the
constant in one. The value is the same float32 sum; what the products
take, what stays at `highest`, and what `sift.split_products` counts."""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

from keystone_tpu import telemetry
from keystone_tpu.nodes.images import sift
from keystone_tpu.nodes.images.sift import SIFTExtractor

H, W = 375, 500  # a VOC 2007 image
VOC = SIFTExtractor(3, 4, 4, 0)  # step 3, bin 4, 4 scales, scaleStep 0


def _voc_image(seed=0):
    rng = np.random.default_rng(seed)
    yy, xx = np.meshgrid(np.arange(H), np.arange(W), indexing="ij")
    return np.clip(0.5 + 0.25 * np.sin(xx / 7.0 + yy / 11.0)
                   + 0.1 * rng.normal(size=(H, W)), 0, 1).astype(np.float32)


@pytest.mark.parametrize("scale", range(4))
def test_the_integer_bands_of_every_voc_scale_are_exact_in_bf16(scale):
    bs, step, off = VOC._scales()[scale]
    n_r, n_c = sift.frame_grid(H, W, bs, step, off)
    for band in (sift._bin_rows(W, n_c, bs, step, off),
                 sift._bin_rows(H, n_r, bs, step, off),
                 sift._band_matrix(H, sift._triangle(bs)),
                 sift._band_matrix(W, sift._triangle(bs))):
        assert sift._exact_in_bf16(band)
        assert band.max() <= bs * bs and (band == np.round(band)).all()
    assert not sift._exact_in_bf16(
        sift._band_matrix(H, sift._gaussian_taps(bs / sift.MAGNIF)))


@pytest.mark.parametrize("bs", [4, 6, 8, 10])
def test_the_product_is_the_float32_sum(bs):
    """Along the lanes (the column product) and along the rows (the row
    product): within float32 rounding of a float64 product, and within
    1e-6 of the `highest` product on both operands."""
    rng = np.random.default_rng(bs)
    x = rng.uniform(size=(2, 3, H, W)).astype(np.float32)
    off = max(9 - 3 * (bs - 4) // 2, 0)
    n_r, n_c = sift.frame_grid(H, W, bs, 3, off)
    for axis, m in ((3, sift._bin_rows(W, n_c, bs, 3, off)),
                    (2, sift._bin_rows(H, n_r, bs, 3, off))):
        got = np.asarray(sift._exact_operand_product(jnp.asarray(x), m, axis))
        want = np.moveaxis(np.tensordot(x.astype(np.float64), m.astype(
            np.float64), axes=([axis], [1])), -1, axis)
        bound = np.moveaxis(np.tensordot(np.abs(x).astype(np.float64), np.abs(
            m).astype(np.float64), axes=([axis], [1])), -1, axis)
        assert (np.abs(got - want) <= 4 * np.finfo(np.float32).eps * bound
                ).all()
        highest = np.moveaxis(np.asarray(lax.dot_general(
            jnp.asarray(x), m, (((axis,), (1,)), ((), ())),
            precision=lax.Precision.HIGHEST)), -1, axis)
        np.testing.assert_allclose(got, highest, rtol=1e-6,
                                   atol=1e-6 * np.abs(highest).max())


def _precisions(fn, *args):
    return re.findall(r"precision = \[(\w+), (\w+)\]",
                      jax.jit(fn).lower(*args).as_text())


def test_default_falls_on_the_exact_constant_and_nowhere_else():
    """The exact band is taken at `default` (one bf16 piece) on the
    constant's side and the map at `highest`; a band that is not exact
    (the Gaussian) keeps `highest` on both. `jnp.einsum` would have put
    `default` on the map for the row product: it swaps the operands."""
    x = jax.ShapeDtypeStruct((2, 8, 40, 48), jnp.float32)
    tri = sift._band_matrix(40, sift._triangle(4))
    gauss = sift._band_matrix(40, sift._gaussian_taps(4 / sift.MAGNIF))
    assert _precisions(lambda v: sift._exact_operand_product(v, tri, 2), x) \
        == [("HIGHEST", "DEFAULT")]
    assert _precisions(lambda v: sift._exact_operand_product(v, gauss, 2),
                       x) == [("HIGHEST", "HIGHEST")]
    gray = jax.ShapeDtypeStruct((2, 40, 48), jnp.float32)
    # a scale: two Gaussian products, two binning products
    assert sorted(_precisions(lambda g: sift._sift_one_scale(g, 4, 3, 0),
                              gray)) == \
        2 * [("HIGHEST", "DEFAULT")] + 2 * [("HIGHEST", "HIGHEST")]
    desc = jax.ShapeDtypeStruct((2, 10, 128), jnp.float32)
    assert _precisions(sift._normalize_quantize, desc) \
        == 2 * [("HIGHEST", "DEFAULT")]


def test_a_band_too_wide_for_bf16_keeps_highest_and_is_not_counted():
    """binSize 25: the edge folds sum to up to 25 x 26 / 2 = 325, an
    integer of nine significant bits, past what bf16 holds exactly (24
    and under are exact), so the full bands of the sampling path stay at
    `highest` and only the two row sums count."""
    assert sift._exact_in_bf16(sift._band_matrix(H, sift._triangle(24)))
    wide = SIFTExtractor(3, 25, 1, 0)
    band = sift._band_matrix(H, sift._triangle(25))
    assert band.max() == 325 and not sift._exact_in_bf16(band)
    rows = np.arange(0, wide.num_descriptors(H, W), 50)
    assert wide.split_products(H, W, rows) == 2
    gray = jax.ShapeDtypeStruct((1, H, W), jnp.float32)
    assert ("HIGHEST", "DEFAULT") not in _precisions(
        lambda g: sift._aggregated_maps(g, 25), gray)


def test_split_products_counts_ten_an_image_at_voc_s_configuration():
    """8 binning products and 2 row sums an image, full pass or sampling
    pass, counted from the shapes as a fused program is dispatched."""
    from keystone_tpu.data.dataset import Dataset
    from keystone_tpu.nodes.stats import ColumnSampler
    from keystone_tpu.nodes.util.fusion import FusedBatchTransformer

    rows = np.sort(np.random.default_rng(0).choice(
        VOC.num_descriptors(H, W), 199, replace=False))
    assert VOC.split_products(H, W) == 10
    assert VOC.split_products(H, W, rows) == 10
    images = np.random.default_rng(1).uniform(size=(3, 48, 64)).astype(
        np.float32)
    counter = telemetry.counter("sift.split_products")
    for stages in ([VOC], [VOC, ColumnSampler(30, 7)]):
        before = counter.value
        FusedBatchTransformer(stages).apply_batch(Dataset(images)).numpy()
        assert counter.value - before == 3 * 10


def _float_tap_descriptors(gray):
    """`_batch` as it was before the integer bands: the window means and
    1/bs² inside float32 binning matrices, every product at `highest`."""
    def bin_rows(n, count, bs, step, off):
        band = sift._band_matrix(n, sift._triangle(bs) / (bs * bs))
        centres = off + step * np.arange(count)
        return np.concatenate(
            [sift._bin_window_mean(bs, i) * band[centres + i * bs]
             for i in range(4)], axis=0).astype(np.float32)

    def row_sums(x):
        return jnp.matmul(x, np.ones((128, 128), np.float32),
                          precision=lax.Precision.HIGHEST)

    parts = []
    for bs, step, off in VOC._scales():
        maps = sift._orientation_maps(gray, bs)
        b, _, h, w = maps.shape
        n_r, n_c = sift.frame_grid(h, w, bs, step, off)
        cols = jnp.einsum("bohw,vw->bohv", maps, bin_rows(w, n_c, bs, step, off),
                          precision=lax.Precision.HIGHEST)
        bins = jnp.einsum("uh,bohv->bouv", bin_rows(h, n_r, bs, step, off),
                          cols, precision=lax.Precision.HIGHEST)
        desc = bins.reshape(b, 8, 4, n_r, 4, n_c).transpose(0, 5, 3, 2, 4, 1)
        parts.append(desc.reshape(b, n_c * n_r, 128))
    desc = jnp.concatenate(parts, axis=1)
    norm = jnp.sqrt(row_sums(desc * desc)) + sift.VL_EPSILON_F
    desc = jnp.minimum(desc / norm, 0.2)
    desc = desc / (jnp.sqrt(row_sums(desc * desc)) + sift.VL_EPSILON_F)
    desc = jnp.where(norm < sift.CONTRAST_THRESHOLD, 0.0, desc)
    return jnp.minimum(jnp.floor(512.0 * desc), 255.0)


def test_voc_descriptors_are_the_float_tap_formulation_s():
    gray = jnp.asarray(_voc_image())[None]
    got = np.asarray(jax.jit(VOC._batch)(gray))
    want = np.asarray(jax.jit(_float_tap_descriptors)(gray))
    assert got.shape == want.shape == (1, VOC.num_descriptors(H, W), 128)
    diff = np.abs(got - want)
    assert diff.max() <= 1.0 and np.mean(diff != 0) <= 1e-5

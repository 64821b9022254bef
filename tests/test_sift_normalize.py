"""SIFT's descriptor normalization in one pass: the Pallas kernel
(`ops.sift_normalize_pallas`, in interpret mode on the CPU) against the
jnp reference (`sift._normalize_quantize_reference`), which of the two
the dispatcher takes, and what `sift.rows_normalized_one_pass` counts.

The kernel sums a row's squares along the lanes where the reference
takes a product with ones, so a value may cross a `floor` boundary: the
two agree on all but 1e-4 of the entries, those by one unit."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from keystone_tpu import telemetry
from keystone_tpu.nodes.images import sift
from keystone_tpu.nodes.images.sift import SIFTExtractor
from keystone_tpu.ops import pallas_kernels as pk

MATH = dict(eps=sift.VL_EPSILON_F, clamp=0.2, contrast=sift.CONTRAST_THRESHOLD)


def _raw(rng, b, n):
    """Raw descriptors as SIFT makes them: non-negative bins, a norm that
    varies from row to row (0 to about 0.5, some under the contrast
    threshold)."""
    return (rng.gamma(0.5, 1.0, size=(b, n, 128))
            * rng.uniform(0.0, 0.05, size=(b, n, 1))).astype(np.float32)


def _kernel(parts, tile=256):
    return np.asarray(pk.sift_normalize_pallas(
        [jnp.asarray(p) for p in parts], tile=tile, interpret=True, **MATH))


def _reference(parts):
    return np.asarray(sift._normalize_quantize_reference(
        jnp.concatenate([jnp.asarray(p) for p in parts], axis=1)))


def _assert_agree(got, want):
    assert got.shape == want.shape
    diff = np.abs(got - want)
    assert diff.max() <= 1.0
    assert np.mean(diff != 0) <= 1e-4


@pytest.mark.parametrize("sizes", [
    [37],                   # under one tile
    [256],                  # one whole tile
    [300],                  # a tile and a ragged one
    [1000],
    [512, 256],             # parts of whole tiles
    [1000, 77, 513, 256],   # parts that start and end inside tiles
    [5, 700, 3],
], ids=lambda s: "+".join(map(str, s)))
def test_the_kernel_is_the_reference_over_the_parts_side_by_side(sizes):
    rng = np.random.default_rng(len(sizes) * 1000 + sizes[0])
    parts = [_raw(rng, 3, n) for n in sizes]
    got = _kernel(parts)
    assert got.shape == (3, sum(sizes), 128)
    _assert_agree(got, _reference(parts))
    assert ((got >= 0) & (got <= 255) & (got == np.floor(got))).all()


def test_rows_under_the_contrast_threshold_and_zero_rows_come_out_zero():
    rng = np.random.default_rng(1)
    x = _raw(rng, 2, 600)
    x[:, ::7] *= 1e-4   # norms under 5e-5
    x[:, 3::11] = 0.0   # no gradient at all
    got = _kernel([x[:, :250], x[:, 250:]])
    _assert_agree(got, _reference([x]))
    norm = np.sqrt((x.astype(np.float64) ** 2).sum(-1))
    low = norm < sift.CONTRAST_THRESHOLD
    assert low.mean() > 0.2
    assert (got[low] == 0).all() and (got[:, 3::11] == 0).all()
    assert (got[~low].max(-1) > 0).all()


def test_rows_where_the_clamp_binds_on_many_entries():
    """Rows with few large bins: after the first normalization many
    entries pass 0.2 and are clamped, and the second normalization lifts
    them again (to 255 where four or fewer bins carry the row)."""
    rng = np.random.default_rng(2)
    b, n = 2, 700
    x = np.zeros((b, n, 128), np.float32)
    for i in range(b):
        for r in range(n):
            k = rng.integers(1, 24)
            cols = rng.choice(128, k, replace=False)
            x[i, r, cols] = rng.uniform(0.01, 1.0, size=k)
    first = x / (np.sqrt((x.astype(np.float64) ** 2).sum(-1, keepdims=True))
                 + sift.VL_EPSILON_F)
    assert ((first > 0.2).sum(-1) >= 2).mean() > 0.5
    got = _kernel([x[:, :333], x[:, 333:]])
    want = _reference([x])
    _assert_agree(got, want)
    assert (want == 255).any() and np.mean(got == 255) == np.mean(want == 255)


def test_the_gate_takes_the_kernel_on_a_tpu_from_one_tile_on(monkeypatch):
    assert jax.default_backend() != "tpu"
    assert not pk.use_sift_normalize(10 ** 6)
    monkeypatch.setattr(pk.jax, "default_backend", lambda: "tpu")
    assert pk.use_sift_normalize(pk.SIFT_NORMALIZE_TILE)
    assert pk.use_sift_normalize(73866)  # a VOC image's full pass
    assert not pk.use_sift_normalize(pk.SIFT_NORMALIZE_TILE - 1)
    assert not pk.use_sift_normalize(199)  # a sampling pass's frames
    from keystone_tpu.workflow.env import config_override

    with config_override(pallas_kernels=False):
        assert not pk.use_sift_normalize(73866)


def _lowers_to_the_kernel(parts) -> bool:
    # a fresh function each time: a jaxpr is cached by the function
    jaxpr = jax.make_jaxpr(lambda *p: sift._normalize_quantize(*p))(*parts)
    return "pallas_call" in str(jaxpr)


def test_the_dispatcher_picks_the_reference_off_the_tpu(monkeypatch):
    rng = np.random.default_rng(3)
    parts = [jnp.asarray(_raw(rng, 2, n)) for n in (300, 120)]
    assert not _lowers_to_the_kernel(parts)
    np.testing.assert_array_equal(
        np.asarray(sift._normalize_quantize(*parts)), _reference(parts))
    monkeypatch.setattr(sift, "use_sift_normalize", lambda rows: True)
    monkeypatch.setattr(sift, "sift_normalize_pallas", functools.partial(
        pk.sift_normalize_pallas, tile=128, interpret=True))
    assert _lowers_to_the_kernel(parts)
    _assert_agree(np.asarray(sift._normalize_quantize(*parts)),
                  _reference(parts))


def test_rows_normalized_one_pass_counts_the_kernel_s_rows(monkeypatch):
    """0 off the TPU; images x descriptors where the kernel takes a full
    pass; 0 for a sampling pass's few rows, whose row sums stay the
    reference's products (`sift.split_products` counts them)."""
    from keystone_tpu.data.dataset import Dataset
    from keystone_tpu.nodes.stats import ColumnSampler
    from keystone_tpu.nodes.util.fusion import FusedBatchTransformer

    ex = SIFTExtractor(3, 4, 4, 0)
    h, w = 44, 60
    nd = ex.num_descriptors(h, w)
    rng = np.random.default_rng(4)
    images = rng.uniform(size=(3, h, w)).astype(np.float32)
    count = telemetry.counter("sift.rows_normalized_one_pass")
    split = ex.split_products(h, w)

    def delta(stages, imgs):
        before = count.value
        out = FusedBatchTransformer(stages).apply_batch(Dataset(imgs)).numpy()
        return count.value - before, out

    assert ex.rows_normalized_one_pass(h, w) == 0
    assert delta([ex], images)[0] == 0
    reference = np.asarray(jax.jit(ex._batch)(jnp.asarray(images[:2])))
    monkeypatch.setattr(sift, "use_sift_normalize", lambda rows: rows >= 64)
    monkeypatch.setattr(sift, "sift_normalize_pallas", functools.partial(
        pk.sift_normalize_pallas, tile=64, interpret=True))
    assert nd >= 64
    assert ex.rows_normalized_one_pass(h, w) == nd
    assert ex.split_products(h, w) == split - 2
    counted, out = delta([ex], images[:2])
    assert counted == 2 * nd
    _assert_agree(np.asarray(out), reference)
    assert delta([ex, ColumnSampler(30, 7)], images)[0] == 0

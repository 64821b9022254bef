"""Dense multi-scale SIFT — vl_dsift fast-mode numerics, TPU-native.

Reference: nodes/images/external/SIFTExtractor.scala:16-40 → JNI →
VLFeat.cxx:40-210: per scale s, binSize = bin + 2s, sample step =
step + s·scaleStep, `vl_imsmooth_f` of the ORIGINAL image with
sigma = binSize/6 (magnif, VLFeat.cxx:45,87), bounds offset
off = (1+2·numScales) − 3s so scales align (:95-99), vl_dsift in
flat-window fast mode with windowSize 1.5 (:100-104), contrast
threshold 0.005 zeroing (:63,140-147), descriptors transposed and
×512 short-scaled with a 255 clamp (:252-259).

The vl_dsift fast path is convolutional, so it maps directly onto XLA
(one traced function of a batch of images, all scales in one program;
the maps keep the image's (rows, columns) as their minor axes and the
eight orientations lead):

  1. Gaussian-smooth per scale: separable, support ceil(4σ),
     edge-replicate padding (vl_imsmooth semantics); kernel and padding
     are one banded matrix an axis, and the convolution two products.
  2. Gradients: central differences inside, one-sided at borders
     (dsift.c's update pass) — exactly `jnp.gradient`.
  3. Soft-assign magnitude into 8 orientation channels (linear
     interpolation between adjacent bins).
  4. Spatial binning = per-channel TRIANGULAR convolution of unit
     integral and half-width binSize, edge-replicate padding
     (vl_imconvcoltri_f — bilinear bin interpolation under a flat
     window), NOT a box filter; banded products as in step 1.
  5. Descriptors are the aggregated maps at bin centers
     frame + bin·binSize (frames lie `step` apart): the binning products
     of step 4 are taken at those rows and columns alone (`_bin_rows`),
     so the choice costs nothing; each spatial bin is reweighted by
     the mean of a Gaussian window (σ = 1.5·binSize) over its support,
     ×binSize (flat-window Gaussian reweighting).
  6. L2 normalize (+VL_EPSILON_F) → clamp 0.2 → renormalize; zero
     descriptors whose first-pass norm < 0.005; ×512, floor, clamp 255
     (the JNI short quantization).

The reference feeds vlfeat the TRANSPOSED image (Image.scala:89-104
flattening with xDim = height) and un-transposes each descriptor at the
end; this module computes the algebraically identical direct form: the
output orientation bins land on atan2(d/drow, d/dcol) and the descriptor
layout is [row-bin (slow), col-bin, orientation (fast)], with frames
ordered column-outer / row-inner. Golden-tested against the scalar-loop
oracle `tests/descriptor_reference_impls.vl_dsift_multiscale` (which
implements the literal transposed pipeline) on a real image.

Descriptor counts per (image size, params) are static
(`SIFTExtractor.num_descriptors`), so the whole extractor is one jitted
program of a batch.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from ...data.dataset import HostDataset
from ...ops.pallas_kernels import sift_normalize_pallas, use_sift_normalize
from ...workflow.pipeline import Transformer

NUM_ORIENTATIONS = 8
GRID = 4  # 4x4 spatial bins
VL_EPSILON_F = 1.19209290e-07
CONTRAST_THRESHOLD = 0.005  # VLFeat.cxx:63
WINDOW_SIZE = 1.5           # VLFeat.cxx:104
MAGNIF = 6.0                # VLFeat.cxx:45


def _gaussian_taps(sigma: float) -> np.ndarray:
    """vl_imsmooth_f kernel: support ceil(4σ), normalized."""
    radius = max(int(np.ceil(4.0 * sigma)), 1)
    x = np.arange(-radius, radius + 1, dtype=np.float64)
    k = np.exp(-0.5 * (x / sigma) ** 2)
    return (k / k.sum()).astype(np.float32)


def _triangle(bin_size: int) -> np.ndarray:
    """vl_imconvcoltri_f kernel times bs²: the integer taps bs−|k| (the
    kernel's are (bs−|k|)/bs², of unit integral). A band of them with
    its edge folds holds integers up to bs(bs+1)/2, exact in bf16 for
    every bin size up to 24."""
    bs = bin_size
    return (bs - np.abs(np.arange(-(bs - 1), bs))).astype(np.float32)


def _bin_window_mean(bin_size: int, bin_index: int) -> float:
    """_vl_dsift_get_bin_window_mean × binSize: Gaussian-window mean over
    the bin's triangular support, restoring unit kernel height."""
    delta = bin_size * (bin_index - (GRID - 1) / 2.0)
    sigma = bin_size * WINDOW_SIZE
    xs = np.arange(-bin_size + 1, bin_size, dtype=np.float64)
    return float(np.mean(np.exp(-0.5 * ((xs + delta) / sigma) ** 2))) * bin_size


def _bin_scales(bin_size: int) -> np.ndarray:
    """(4,): what a bin made with the integer band `_triangle` is
    multiplied by, along each axis: the triangle's 1/bs² and the flat
    window's mean of the bin, in float64 and rounded once."""
    s = np.asarray([_bin_window_mean(bin_size, i) for i in range(GRID)])
    return (s / (bin_size * bin_size)).astype(np.float32)


def _band_matrix(n: int, taps) -> np.ndarray:
    """(n, n): row i holds ``taps`` centred on column i, the taps that
    fall off either end added to the end's column: correlation with
    symmetric taps under EDGE-REPLICATE padding (VL_PAD_BY_CONTINUITY)
    as one matrix."""
    r = (len(taps) - 1) // 2
    cols = np.clip(np.arange(n)[:, None] + np.arange(len(taps)) - r, 0, n - 1)
    M = np.zeros((n, n), np.float64)
    np.add.at(M, (np.repeat(np.arange(n), len(taps)), cols.ravel()),
              np.tile(np.asarray(taps, np.float64), n))
    return M.astype(np.float32)


def _exact_in_bf16(m: np.ndarray) -> bool:
    return bool((m == m.astype(jnp.bfloat16).astype(np.float32)).all())


def _exact_operand_product(x, m: np.ndarray, axis: int):
    """``x`` (float32) contracted along ``axis`` with the columns of the
    constant matrix ``m`` (out, in), whose rows take the axis's place.
    On a TPU a float32 product at `highest` is six bf16 passes, each a
    pair of the operands' bf16 pieces (hi·hi, hi·mid, mid·hi, hi·lo,
    lo·hi, mid·mid). Where ``m`` is exact in bf16 its mid and low pieces
    are zero, and so are three of the passes: ``m`` is then taken at
    `default` (one piece) against ``x`` at `highest` (three pieces, which
    hold float32 exactly), the same three nonzero partial products in
    float32. Any other ``m`` is taken at `highest` on both sides.
    `lax.dot_general` and not `jnp.einsum`, which may swap the operands
    and not the precisions with them."""
    axis = axis % x.ndim
    precision = ((lax.Precision.HIGHEST, lax.Precision.DEFAULT)
                 if _exact_in_bf16(m) else lax.Precision.HIGHEST)
    out = lax.dot_general(x, m, (((axis,), (1,)), ((), ())),
                          precision=precision,
                          preferred_element_type=jnp.float32)
    return jnp.moveaxis(out, -1, axis)


def _sep_conv_edge(x, taps):
    """Separable convolution of the two last axes (rows, then columns)
    as two products with banded matrices, in float32
    (`_exact_operand_product`: six bf16 passes, three where the band is
    exact in bf16): on a TPU a stencil of 7 to 19 taps as shifted sums
    keeps the vector unit shuffling lanes (8.4 ms an image at VOC's size
    for the four scales, my chip run, PR 40), and the matrix unit does
    the same sums as a product with a matrix that is mostly zeros many
    times faster."""
    h, w = x.shape[-2:]
    rows = _exact_operand_product(x, _band_matrix(h, taps), -2)
    return _exact_operand_product(rows, _band_matrix(w, taps), -1)


def frame_grid(h: int, w: int, bin_size: int, step: int, off: int):
    """(rows, columns) of one scale's frame grid: frames span
    [off, dim-1] with footprint 3*binSize+1."""
    span = bin_size * (GRID - 1) + 1
    n_r = max(((h - 1) - span + 1 - off) // step + 1, 0)
    n_c = max(((w - 1) - span + 1 - off) // step + 1, 0)
    return n_r, n_c


def _orientation_maps(gray, bin_size: int):
    """Steps 1 to 3 for one scale of a batch of images (b, h, w): the
    eight orientation maps before spatial binning, (b, 8, h, w)."""
    sigma = bin_size / MAGNIF
    sm = _sep_conv_edge(gray, _gaussian_taps(sigma))
    # gradients: central interior, one-sided borders (vl semantics ==
    # jnp.gradient); dy is d/drow, dx is d/dcol
    dy = jnp.gradient(sm, axis=1)
    dx = jnp.gradient(sm, axis=2)
    mag = jnp.sqrt(dx * dx + dy * dy)
    ang = jnp.arctan2(dy, dx)

    # soft orientation binning: linear interp between adjacent bins.
    # The orientation axis leads, so every map keeps (h, w) minor.
    t = jnp.mod(ang / (2.0 * jnp.pi) * NUM_ORIENTATIONS, NUM_ORIENTATIONS)
    lo = jnp.floor(t)
    frac = t - lo
    lo = lo.astype(jnp.int32) % NUM_ORIENTATIONS
    hi = (lo + 1) % NUM_ORIENTATIONS
    w_lo, w_hi = mag * (1.0 - frac), mag * frac
    return jnp.stack(
        [jnp.where(lo == o, w_lo, 0.0) + jnp.where(hi == o, w_hi, 0.0)
         for o in range(NUM_ORIENTATIONS)], axis=1)  # (b, 8, h, w)


def _aggregated_maps(gray, bin_size: int):
    """Steps 1 to 4: the orientation maps after the flat-window spatial
    binning (a triangular conv per channel) with the integer band
    `_triangle`, so bs² times the unit-integral one's, (b, 8, h, w)."""
    return _sep_conv_edge(_orientation_maps(gray, bin_size),
                          _triangle(bin_size))


def _bin_rows(n: int, count: int, bin_size: int, step: int, off: int):
    """(4 * count, n): for each of the four bins along an axis of length
    ``n``, the rows of the integer triangular band matrix at the bin's
    centres of the ``count`` frames (``step`` apart from ``off``):
    binning and the strided choice of the frames' bin centres as ONE
    matrix, exact in bf16; the scales are `_bin_scales`'."""
    band = _band_matrix(n, _triangle(bin_size))
    centres = off + step * np.arange(count)
    return np.concatenate([band[centres + i * bin_size] for i in range(GRID)],
                          axis=0)


def _sift_one_scale(gray, bin_size: int, step: int, off: int):
    """All raw descriptors of one scale of a batch of images (b, h, w):
    (b, num_desc, 128), before normalization."""
    maps = _orientation_maps(gray, bin_size)
    b, _, h, w = maps.shape
    n_r, n_c = frame_grid(h, w, bin_size, step, off)
    if n_r == 0 or n_c == 0:
        return jnp.zeros((b, 0, GRID * GRID * NUM_ORIENTATIONS), maps.dtype)
    # A descriptor's bin (i, j) is the binned map at the frame's corner
    # plus (i, j) * binSize, and the frames lie ``step`` apart: the
    # products below bin the maps AT those places and nowhere else, so
    # no map of all places is made and nothing is gathered or sliced out
    # of one (sixteen slices of stride 3 along the lanes were 10 of a
    # full pass's 12.4 ms an image on a v5e, a general gather of the
    # same 9.45 million elements more: my chip runs, PR 40).
    cols = _exact_operand_product(
        maps, _bin_rows(w, n_c, bin_size, step, off), 3) \
        * np.repeat(_bin_scales(bin_size), n_c)
    bins = _exact_operand_product(
        cols, _bin_rows(h, n_r, bin_size, step, off), 2) \
        * np.repeat(_bin_scales(bin_size), n_r)[:, None]
    # (b, 8, (i, r), (j, c)) -> frames column-outer / row-inner (the
    # reference's frame order), features [row-bin, col-bin, orientation]
    desc = bins.reshape(b, NUM_ORIENTATIONS, GRID, n_r, GRID, n_c)
    desc = desc.transpose(0, 5, 3, 2, 4, 1)
    return desc.reshape(b, n_c * n_r, GRID * GRID * NUM_ORIENTATIONS)


def _sift_some_frames(gray, bin_size: int, step: int, off: int, frames):
    """The raw descriptors of the frames numbered ``frames`` (a static
    sorted array of indices into the scale's column-outer, row-inner
    frame order) and of no others: (b, len(frames), 128). A few hundred
    frames of an image's tens of thousands: their bins are gathered
    from the aggregated maps and no other descriptor is made."""
    agg = _aggregated_maps(gray, bin_size)
    h, w = agg.shape[2:]
    n_r, _ = frame_grid(h, w, bin_size, step, off)
    frames = np.asarray(frames)
    bin_off = np.arange(GRID) * bin_size
    rows = off + (frames % n_r)[:, None] * step + bin_off  # (m, 4)
    cols = off + (frames // n_r)[:, None] * step + bin_off
    scales = _bin_scales(bin_size)
    # (b, 8, m, 4, 4): orientation o, frame, row-bin i, col-bin j
    desc = agg[:, :, rows[:, :, None], cols[:, None, :]]
    desc = desc * scales[None, :] * scales[:, None]
    return desc.transpose(0, 2, 3, 4, 1).reshape(
        agg.shape[0], len(frames), GRID * GRID * NUM_ORIENTATIONS)


def _row_sums(x):
    """Every row's sum, in every one of the row's places: a product with
    a matrix of ones, in float32 by three bf16 passes (the ones are exact:
    `_exact_operand_product`). On a TPU a sum along the 128 lanes is
    seven rounds of lane shuffles a vector register and the result has
    to be spread over the lanes again; the matrix unit gives both at
    once."""
    d = x.shape[-1]
    return _exact_operand_product(x, np.ones((d, d), np.float32), -1)


def _normalize_quantize_reference(desc):
    """vl normalization of raw descriptors (..., 128): L2+eps -> clamp
    0.2 -> L2+eps; contrast zeroing; the JNI short quantization
    floor(512·v) clamped to 255."""
    norm = jnp.sqrt(_row_sums(desc * desc)) + VL_EPSILON_F
    desc = jnp.minimum(desc / norm, 0.2)
    desc = desc / (jnp.sqrt(_row_sums(desc * desc)) + VL_EPSILON_F)
    desc = jnp.where(norm < CONTRAST_THRESHOLD, 0.0, desc)
    return jnp.minimum(jnp.floor(512.0 * desc), 255.0)


def _normalize_quantize(*parts):
    """`_normalize_quantize_reference` of the raw descriptors (b, n_p,
    128) of ``parts`` side by side along the rows. Where
    `use_sift_normalize` takes their rows (a TPU, and at least one tile
    of descriptors an image: the full pass) one Pallas kernel reads each
    raw row once and writes it quantized once, at its place among the
    parts'; the reference first writes the parts' concatenation, and its
    two row sums are arrays as large as the descriptors, each a pass
    through HBM. The kernel's sums run in another order, which may move
    a value across a `floor` boundary by one unit."""
    if use_sift_normalize(sum(p.shape[-2] for p in parts)):
        return sift_normalize_pallas(
            [p for p in parts if p.shape[-2]], eps=VL_EPSILON_F, clamp=0.2,
            contrast=CONTRAST_THRESHOLD)
    return _normalize_quantize_reference(jnp.concatenate(parts, axis=-2))


def _frames_by_scale(scales, h: int, w: int, rows):
    """(scale, its frames among ``rows``) for each scale with some:
    ``rows`` number the descriptors of all scales side by side."""
    rows = np.asarray(rows)
    start = 0
    for b, st, off in scales:
        count = int(np.prod(frame_grid(h, w, b, st, off)))
        mine = rows[(rows >= start) & (rows < start + count)] - start
        if len(mine):
            yield (b, st, off), mine
        start += count


@functools.lru_cache(maxsize=16)
def _split_products(scales, h: int, w: int, rows, row_sums: bool) -> int:
    """`SIFTExtractor.split_products`: the two binning products of each
    scale that has descriptors (with ``rows``, of each scale that has
    frames among them) and, with ``row_sums``, the two row sums, where
    the matrix is exact in bf16. The Gaussian smoothing's bands never
    are."""
    bands = []
    if rows is None:
        for b, st, off in scales:
            n_r, n_c = frame_grid(h, w, b, st, off)
            if n_r and n_c:
                bands += [_bin_rows(w, n_c, b, st, off),
                          _bin_rows(h, n_r, b, st, off)]
    else:
        for (b, _, _), _ in _frames_by_scale(scales, h, w, rows):
            bands += [_band_matrix(h, _triangle(b)),
                      _band_matrix(w, _triangle(b))]
    if bands and row_sums:
        d = GRID * GRID * NUM_ORIENTATIONS
        bands += 2 * [np.ones((d, d), np.float32)]
    return sum(map(_exact_in_bf16, bands))


class SIFTExtractorInterface(Transformer):
    """(reference nodes/images/SIFTExtractor.scala:9)"""


class SIFTExtractor(SIFTExtractorInterface):
    """Dense multi-scale SIFT: grayscale (H, W) or (H, W, 1) image in
    [0, 1] → (num_descriptors, 128) float matrix of quantized shorts in
    [0, 255] (external/SIFTExtractor.scala:16-40 semantics, scales
    concatenated).

    Defaults mirror SIFTExtractor.scala:17 (step 3, bin 4, 4 scales,
    scale_step 1); the reference's VLFeatSuite/enceval configuration uses
    scale_step=0 (VLFeat.cxx:77-79 note).

    One traced function serves a single image, a host bucket and a fused
    stage: a batch (b, H, W) through all scales in one program. On a
    device `Dataset` the batch path is a fused program of its own, so a
    set of images goes through a microbatch at a time and never as one
    vmap over the whole set.
    """

    fusable = True
    chunkable = True  # per-item host map: distributes over chunks
    precision_tolerance = "exact"  # quantized descriptors: float32 stencils

    def __init__(self, step: int = 3, bin_size: int = 4, num_scales: int = 4,
                 scale_step: int = 1):
        self.step = step
        self.bin_size = bin_size
        self.num_scales = num_scales
        self.scale_step = scale_step

    def _scales(self):
        """(bin size, step, offset) of every scale."""
        S = self.num_scales
        # the offset is clamped like vl_dsift clamps its bounds to the
        # image: for num_scales >= 5 the raw offset goes negative
        return [(self.bin_size + 2 * s, self.step + s * self.scale_step,
                 max((1 + 2 * S) - 3 * s, 0)) for s in range(S)]

    def num_descriptors(self, h: int, w: int) -> int:
        """Descriptors of an (h, w) image, over all scales."""
        return sum(int(np.prod(frame_grid(h, w, b, st, off)))
                   for b, st, off in self._scales())

    def abstract_apply(self, elem):
        from ...analysis.specs import SpecMismatchError, shape_struct

        shape = tuple(elem.shape)
        if len(shape) == 3 and shape[-1] == 1:
            shape = shape[:2]
        if len(shape) != 2:
            raise SpecMismatchError(
                "SIFT input element must be a grayscale (H, W) or "
                f"(H, W, 1) image, got {tuple(elem.shape)}")
        return shape_struct(
            (self.num_descriptors(*shape), GRID * GRID * NUM_ORIENTATIONS),
            np.float32)

    def _batch(self, gray):
        """(b, H, W) or (b, H, W, 1) → (b, num_descriptors, 128)."""
        if gray.ndim == 4:
            gray = gray[..., 0]
        gray = gray.astype(jnp.float32)
        with jax.named_scope("ks.sift"):
            # the scales' descriptors side by side, normalized in one pass
            return _normalize_quantize(
                *[_sift_one_scale(gray, b, st, off)
                  for b, st, off in self._scales()])

    def _batch_rows(self, gray, rows):
        """The descriptors numbered ``rows`` (static, sorted) of every
        image of the batch, as `_batch(gray)[:, rows]` has them, without
        making the others: what a sampler right behind this stage asks
        for."""
        if gray.ndim == 4:
            gray = gray[..., 0]
        gray = gray.astype(jnp.float32)
        h, w = gray.shape[1:]
        with jax.named_scope("ks.sift"):
            return _normalize_quantize(
                *[_sift_some_frames(gray, *scale, mine) for scale, mine
                  in _frames_by_scale(tuple(self._scales()), h, w, rows)])

    def batch_fn(self):
        return self._batch

    def fuse(self):
        return (("SIFT", self.step, self.bin_size, self.num_scales,
                 self.scale_step), (), lambda p, xb: self._batch(xb))

    def absorb(self, followers):
        """A `ColumnSampler` behind it: the sampled stage."""
        from ..stats.normalization import ColumnSampler

        if followers and type(followers[0]) is ColumnSampler:
            return _SampledSIFTStage(self, followers[0]), 1
        return None

    def split_products(self, h: int, w: int, rows=None) -> int:
        """Products an (h, w) image takes in the three-pass form
        (`_exact_operand_product`) in `_batch`, or with ``rows`` in
        `_batch_rows`: from the shapes and the matrices alone. The row
        sums count where the reference normalizes."""
        return _split_products(
            tuple(self._scales()), h, w,
            None if rows is None else tuple(np.asarray(rows).tolist()),
            not self.rows_normalized_one_pass(h, w, rows))

    def rows_normalized_one_pass(self, h: int, w: int, rows=None) -> int:
        """Descriptors of an (h, w) image that `_normalize_quantize`
        hands to the one-pass kernel (`use_sift_normalize`) in `_batch`,
        or with ``rows`` in `_batch_rows`: all of them or none."""
        n = self.num_descriptors(h, w) if rows is None else len(rows)
        return n if use_sift_normalize(n) else 0

    def count_rows(self, elem, rows: int, mine=None):
        """`sift.images`, `sift.descriptors`, `sift.split_products`,
        `sift.rows_normalized_one_pass`: what one dispatch of a program
        holding this stage extracts, from the shapes; ``mine`` numbers
        the descriptors a sampler behind it keeps (`_SampledSIFTStage`)."""
        from ...telemetry import counter

        h, w = elem.shape[:2]
        kept = self.num_descriptors(h, w) if mine is None else len(mine)
        counter("sift.images").inc(rows)
        counter("sift.descriptors").inc(rows * kept)
        counter("sift.split_products").inc(
            rows * self.split_products(h, w, mine))
        counter("sift.rows_normalized_one_pass").inc(
            rows * self.rows_normalized_one_pass(h, w, mine))

    def _jitted_batch(self):
        fn = self.__dict__.get("_jitted")
        if fn is None:
            fn = self.__dict__["_jitted"] = jax.jit(self._batch)
        return fn

    def apply(self, image):
        return self._jitted_batch()(jnp.asarray(image, jnp.float32)[None])[0]

    def apply_batch(self, data):
        if isinstance(data, HostDataset):
            # bucket-by-shape: one dispatch per (shape, chunk), not per image
            from ...utils import batching

            return HostDataset(
                batching.map_host_batched(data.items, self._jitted_batch()))
        from ..util.fusion import FusedBatchTransformer

        return FusedBatchTransformer([self]).apply_batch(data)

    def apply_batch_stream(self, data):
        # overlap engine: double-buffered dispatch, chunks stream to the
        # consumer as they drain (see utils/batching.py)
        from ...utils import batching

        return batching.map_host_batched_stream(
            data.items, self._jitted_batch())


class _SampledSIFTStage(Transformer):
    """SIFTExtractor >> ColumnSampler as one stage
    (`SIFTExtractor.absorb`): the rows the sampler keeps are known
    before a descriptor is made (a seeded choice by the matrix's
    height), so only those frames' bins are gathered from the
    aggregated maps, normalized and quantized: a sampling pass over a
    set of images costs the stencils and never holds, transposes or
    normalizes the 73,866 descriptors an image it would throw away. The
    values are the unfused pair's."""

    fusable = True
    chunkable = True
    precision_tolerance = "exact"  # SIFT's

    def __init__(self, sift, sampler):
        self.sift = sift
        self.sampler = sampler

    @property
    def label(self) -> str:
        return f"{self.sift.label}>>{self.sampler.label}"

    def abstract_apply(self, elem):
        return self.sampler.abstract_apply(self.sift.abstract_apply(elem))

    def apply(self, x):
        return self.sampler.apply(self.sift.apply(x))

    def _rows(self, h: int, w: int):
        """The descriptors the sampler keeps, or None where it keeps all
        and the stage is `SIFTExtractor._batch`."""
        from ..stats.normalization import sample_rows

        nd, num = self.sift.num_descriptors(h, w), self.sampler.num_cols
        return None if nd <= num else sample_rows(nd, num, self.sampler.seed)

    def fuse(self):
        sift = self.sift

        def fn(p, xb):
            rows = self._rows(*xb.shape[1:3])
            if rows is None:
                return sift._batch(xb)
            with jax.named_scope("ks.sift.sample"):
                return sift._batch_rows(xb, rows)

        return (("SampledSIFT", sift.fuse()[0], self.sampler.num_cols,
                 self.sampler.seed), (), fn)

    def count_rows(self, elem, rows: int):
        from ...telemetry import counter

        self.sift.count_rows(elem, rows, self._rows(*elem.shape[:2]))
        counter("sampler.rows_kept").inc(
            rows * self.abstract_apply(elem).shape[0])
